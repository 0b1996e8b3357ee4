#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up (set-up), measures for `--seconds`, checks every placement
against the numpy reference (`reference.py`) after the window has closed, and
prints one JSON object as the last line of its standard output. Needs a TPU:
without one it exits non-zero and prints no result. `--rehearse` is the only
other form: the CPU by name, the configuration's toy counts, the result
labelled `"rehearsal": true` with `platform: cpu` — never a device number.

Everything that belongs to one cell is data found by name: the cell in
`BENCHMARK.json`, its configuration in `configs/<config>.json`, its traffic
in `traffic/<traffic>.json`, whose `driver` names `drivers/<driver>.py`, and
each per-layer metric's reader in `layer_metrics/<metric>.py`; a pod template's
key beyond the core's in `reference_features/<key>.py` and
`object_features/<key>.py` (`features.py`). See README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.2f}s] {msg}", flush=True)


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench_dir: str, manifest: dict, workload: str) -> dict:
    """The cell, its configuration, traffic, driver and metric lists, all
    found by name under `bench_dir`."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have: {', '.join(sorted(cells))})")
    cell = cells[workload]
    with open(os.path.join(bench_dir, "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def applies(metric: dict, reported: set) -> bool:
        if "workloads" in metric:
            return workload in metric["workloads"]
        return metric.get("moves") in reported

    e2e = [m for m in manifest["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    return {
        "cell": cell, "traffic": traffic,
        "config_path": os.path.join(bench_dir, "configs",
                                    cell["config"] + ".json"),
        "driver_path": os.path.join(bench_dir, "drivers",
                                    traffic["driver"] + ".py"),
        "end_to_end": e2e,
        "per_layer": [m for m in manifest["per_layer"]
                      if applies(m, reported)],
    }


def read_layer_metrics(bench_dir: str, metrics: list, obs: dict) -> dict:
    """Each metric's own reader over what the run observed; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = load_module(os.path.join(bench_dir, "layer_metrics",
                                          m["name"] + ".py"))
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def replay(result: dict, bench_dir: str) -> tuple:
    """The reference over what the run did, in the order it did it: pods
    created, retried and deleted, nodes added and removed, by the operation's
    name (`reference.replay`; an operation it does not know fails the run).
    `may_pend`, where the driver gives it, names the template groups whose
    pods may find no node and must then stay unbound (PostFilter runs for
    them); `evictions` (victim -> preemptor) and `nominations` (preemptor ->
    node), where it gives them, are what the run's PostFilter did, and a run
    that gives none must have had none to give."""
    import reference
    ref = reference.Reference(result["nodes"], bench_dir)
    expected = reference.replay(ref, result["templates"], result["log"],
                                result.get("may_pend", ()))
    return reference.compare(expected, result["placements"],
                             result.get("evictions"),
                             result.get("nominations")), \
        ref.over_allocatable()


def device_report(rehearse: bool) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if rehearse:
        out["rehearsal"] = True
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU by name, toy counts, labelled; for the tests")
    ap.add_argument("--bench-dir", default=HERE,
                    help="directory holding configs/, traffic/, drivers/, "
                         "layer_metrics/ and pod features looked for ahead "
                         "of this one's (the tests point it elsewhere)")
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--keep-out", action="store_true",
                    help="keep this run's directory (trace, client files, "
                         "apiserver log) under benchmark_out/")
    args = ap.parse_args(argv)

    with open(args.manifest) as f:
        manifest = json.load(f)
    found = find_cell(args.bench_dir, manifest, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "kubernetes_tpu")):
        print("benchmark: the kubernetes_tpu package is not beside this "
              "directory — there is no system to measure", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # Every program lands in the persistent cache (JAX's default skips
    # compiles under a second, which would then recompile in every run).
    # Where it lives is the program's one rule (kubernetes_tpu/
    # compile_cache.py): JAX_COMPILATION_CACHE_DIR if set, else
    # <checkout>/.jax_cache; children inherit both through the environment.
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import jax

    import objects
    import tracereduce
    from compilemeter import CompileMeter

    devs = jax.devices()
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) < int(found["cell"]["chips"])):
        print(f"benchmark: cell {args.workload} needs "
              f"{found['cell']['chips']} TPU chip(s); jax.devices() reports "
              f"{len(devs)} x {devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 3
    meter = CompileMeter()
    # A directory of this run's own: runs that share a checkout (the tests'
    # workers, a driver's pair) never see each other's trace or client files.
    out_root = os.path.join(ROOT, "benchmark_out")
    os.makedirs(out_root, exist_ok=True)
    out_dir = tempfile.mkdtemp(prefix=f"{args.workload}-{args.seed}-",
                               dir=out_root)
    trace_dir = os.path.join(out_dir, "trace")
    marks = {}

    @contextlib.contextmanager
    def profiler():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def window_opens():
        marks["setup_s"] = time.perf_counter() - T_START
        marks["compile_open"] = meter.snapshot()
        say(f"window opens: set-up {marks['setup_s']:.2f}s, compile "
            f"{marks['compile_open']}")

    def window_closes():
        marks["compile_close"] = meter.snapshot()

    ctx = types.SimpleNamespace(
        config=objects.load_config(found["config_path"], args.rehearse),
        traffic=found["traffic"], seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), rehearse=args.rehearse,
        root=ROOT, bench_dir=os.path.abspath(args.bench_dir),
        out_dir=out_dir, say=say, profiler=profiler,
        window_opens=window_opens, window_closes=window_closes)
    driver = load_module(found["driver_path"])
    result = driver.run(ctx)

    # -- correct: every placement against the reference, and the chip did it
    t_ref = time.perf_counter()
    refused = None
    try:
        cmp_, over = replay(result, ctx.bench_dir)
    except (ValueError, RuntimeError, KeyError) as e:
        # a log the reference cannot follow (a retry of a pod it has bound,
        # a pending pod the log never retries, no node for a pod that must
        # have one) is a run that is not correct, and says why, not a crash
        refused = f"{type(e).__name__}: {e}"
        cmp_ = dict.fromkeys(
            ("unbound", "unexpected", "pending", "evictions",
             "evictions_differing", "nominations_differing"), 0)
        cmp_.update(compared=len(result["placements"]),
                    differing=len(result["placements"]),
                    examples=[], preemption_examples=[])
        over = []
    say(f"reference replayed {cmp_['compared']} pods in "
        f"{time.perf_counter() - t_ref:.2f}s"
        + (f" ({cmp_['pending']} of them expected to stay pending)"
           if cmp_["pending"] else "")
        + (f"; {cmp_['evictions']} evictions expected"
           if cmp_["evictions"] else ""))
    # a program first met inside the window shows as a compile or as a load
    # from the persistent cache: either is set-up that leaked into the window
    compiled = sum(marks["compile_close"][k] - marks["compile_open"][k]
                   for k in ("compiles", "cache_hits"))
    checks = [("placements_differing", cmp_["differing"], 0),
              ("pods_unbound", cmp_["unbound"], 0),
              ("pods_unexpected", cmp_["unexpected"], 0),
              ("evictions_differing", cmp_["evictions_differing"], 0),
              ("nominations_differing", cmp_["nominations_differing"], 0),
              ("nodes_over_allocatable", len(over), 0),
              ("compiles_in_window", compiled, 0)] + list(result["guards"])
    if refused:
        say(f"the reference refuses the log, every placement counts as "
            f"differing: {refused}")
        checks.insert(0, ("log_refused_by_the_reference", 1, 0))
    correct = True
    compared = {}
    for name, got, limit in checks:
        ok = got <= limit
        correct &= ok
        compared[name] = {"value": got, "limit": limit}
        say(f"compared {name}: {got} (limit {limit})"
            + ("" if ok else "  <-- FAILS"))
    if cmp_["examples"]:
        say(f"differing placements, e.g. (pod, reference, run): "
            f"{cmp_['examples']}")
    if cmp_["preemption_examples"]:
        say(f"differing evictions or nominations, e.g. (pod, reference, "
            f"run): {cmp_['preemption_examples']}")

    # -- metrics
    device = device_report(args.rehearse)
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        obs = result["obs"]
        obs["device"] = device
        xplane = tracereduce.newest_xplane(trace_dir)
        if xplane is not None:
            reduced = tracereduce.reduce(tracereduce.load(xplane))
            obs.setdefault("traced", {})["reduced"] = reduced
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
            say(f"trace: busy {reduced['busy_s']:.4f}s of "
                f"{reduced['window_s']:.4f}s on {reduced['devices']} "
                f"device(s); programs "
                f"{ {k: v for k, v in reduced['modules'].items()} }")
        line["metrics"] = read_layer_metrics(
            args.bench_dir, found["per_layer"], obs)
    else:
        e2e = dict(result["e2e"], setup_s=marks["setup_s"])
        line["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                       "unit": m["unit"]}
                           for m in found["end_to_end"]}
    line["device"] = device
    # each number compared beside its limit: last in the line, and the last
    # lines on standard error (what a driver keeps of a run that fails)
    line["compared"] = compared
    # a run that did not get this far leaves its directory for the post-mortem
    if args.keep_out:
        say(f"kept {out_dir}")
    else:
        shutil.rmtree(out_dir, ignore_errors=True)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
