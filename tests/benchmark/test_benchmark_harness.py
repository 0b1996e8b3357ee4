"""The benchmark harness (benchmark/run.py) driven on the CPU at toy size:
the contract's result line for every cell, refusal without a TPU, cells,
configurations, traffic and layer metrics found by name in a directory that
this test makes, `correct` coming out false when the timed path is broken,
and the yardstick's own arithmetic (trace reduction, /metrics reading, kernel
bytes). No timing is asserted."""

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
RUN = os.path.join(BENCH, "run.py")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import kernelcost  # noqa: E402
import prom  # noqa: E402
import tracereduce  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")


def _run(args, env=None, cwd=ROOT, timeout=600):
    # tests/conftest.py gives this process 8 virtual CPU devices for the
    # mesh suites; a one-chip cell is rehearsed on one device
    e = {k: v for k, v in os.environ.items()
         if k not in ("BENCH_RUN", "XLA_FLAGS")}
    e.update(env or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=timeout)


def _last_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _expected_metrics(cell, kind):
    out = set()
    e2e = {m["name"] for m in MANIFEST["end_to_end"]
           if cell in m.get("workloads", [cell])}
    if kind == "end_to_end":
        return e2e
    for m in MANIFEST["per_layer"]:
        listed = m.get("workloads")
        if (cell in listed) if listed else (m["moves"] in e2e):
            out.add(m["name"])
    return out


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_line(cell, trace):
    proc = _run([RUN, "--workload", cell, "--seed", "2147483659",
                 "--seconds", "1", "--trace", str(trace), "--rehearse"],
                env={"BENCH_RUN": "ignored"})
    line = _last_line(proc)
    keys = {"correct", "attempted", "failed", "metrics", "device", "compared"}
    assert set(line) == keys | ({"breakdown"} if trace else set())
    # each number compared beside its limit: last in the line, and the last
    # lines of standard error
    assert list(line)[-1] == "compared" and "placements_differing" in line[
        "compared"]
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert proc.stderr.strip().splitlines()[-len(line["compared"]):] == [
        f"compared {k}: {c['value']} (limit {c['limit']})"
        for k, c in line["compared"].items()]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["rehearsal"] is True
    assert {"kind", "count", "memory_peak_bytes"} <= set(dev)
    want = _expected_metrics(cell, "per_layer" if trace else "end_to_end")
    if trace:
        # a share of the chip's roofline needs the chip's peak: a rehearsal
        # reads nothing there (benchmark/peaks.json has no CPU row)
        want = {m for m in want if not m.endswith("_roofline")}
        # a toy restore that fits the program's 4,096-event journal keeps
        # the score hint, so every wave is hint-bound (`basic-5k.waves`):
        # no batch retires, no pop takes a pod, and the two readers of
        # retired batches find nothing to read
        waves = [l for l in proc.stdout.splitlines() if "] wave " in l]
        if waves and all(" batches 0 " in l for l in waves):
            want -= {"commit_batch_share", "pop_run_share"}
        assert dev["window_s"] > 0 and "busy_s" in dev
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
    assert set(line["metrics"]) == want
    units = {m["name"]: m["unit"]
             for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name] and np.isfinite(m["value"])


def test_runs_that_share_a_checkout_keep_to_their_own_files():
    """Three traced runs of one cell and seed at once, as the tests' workers
    and a driver's pair would make them: each reads its own trace, and only
    the one asked to keeps its directory."""
    args = [sys.executable, RUN, "--workload", "spread-5k.waves", "--seed",
            "3", "--seconds", "0.5", "--trace", "1", "--rehearse"]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [subprocess.Popen(args + extra, cwd=ROOT, env=env, text=True,
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE)
             for extra in ([], [], ["--keep-out"])]
    kept = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, out[-2000:] + err[-2000:]
        line = json.loads(out.strip().splitlines()[-1])
        assert line["correct"] is True and line["device"]["window_s"] > 0
        assert line["breakdown"]["device_ops"]
        assert "kernel_ms_per_batch" in line["metrics"]
        kept += re.findall(r"kept (\S+)", out)
    assert len(kept) == 1 and tracereduce.newest_xplane(
        os.path.join(kept[0], "trace"))
    shutil.rmtree(kept[0])
    left = os.listdir(os.path.join(ROOT, "benchmark_out"))
    assert not [d for d in left if d.startswith("spread-5k.waves-3-")]


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    proc = _run([RUN, "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0"], env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode not in (0, None)
    assert '"correct"' not in proc.stdout


def test_alone_with_its_manifest_it_exits_nonzero(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: there is no system to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in MANIFEST["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run([str(tmp_path / "benchmark" / "run.py"), "--workload",
                 CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0",
                 "--rehearse"], cwd=str(tmp_path))
    assert proc.returncode not in (0, None)
    assert '"correct"' not in proc.stdout


def test_a_broken_timed_path_comes_out_not_correct(tmp_path):
    """The rest of a run with the look for a chip skipped (--rehearse) and
    an answer altered where it is produced: one bind of the window lands on
    another node than the scheduler chose."""
    script = tmp_path / "broken.py"
    script.write_text(f"""
import sys
sys.path.insert(0, {ROOT!r}); sys.path.insert(0, {BENCH!r})
from kubernetes_tpu.core.clientset import FakeClientset
import run
bind = FakeClientset.bind
def broken(self, pod, node_name):
    if pod.name == "w0-7":
        node_name = next(n for n in sorted(self.nodes) if n != node_name)
    return bind(self, pod, node_name)
FakeClientset.bind = broken
sys.exit(run.main(["--workload", "basic-5k.waves", "--seed", "5",
                   "--seconds", "0.5", "--trace", "0", "--rehearse"]))
""")
    line = _last_line(_run([str(script)]))
    assert line["correct"] is False
    differing = line["compared"]["placements_differing"]
    assert differing["value"] >= 1 and differing["limit"] == 0


TOY_BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "toy_bench")
TOY_FEATURE = {"toyZoneAffinity": {
    "required": [f"zone-{i}" for i in range(8)], "preferred": ["zone-3"]}}


def _bench_with_a_toy_feature(tmp_path, rehearse):
    """A bench directory with a configuration of its own, whose measured
    pods carry a pod feature that only this directory has the files of."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "drivers", "layer_metrics"):
        (bench / d).mkdir(parents=True)
    for d in ("reference_features", "object_features"):
        shutil.copytree(os.path.join(TOY_BENCH, d), bench / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(BENCH, "configs", "basic-5k.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny-basic"
    cfg["rehearse"] = rehearse
    cfg["measurePods"]["template"].update(TOY_FEATURE)
    (bench / "configs" / "tiny-basic.json").write_text(json.dumps(cfg))
    return bench


def test_new_files_are_found_by_name(tmp_path):
    """A third configuration with a pod feature of its own (a reference file
    with a filter and a score, a builder file), a second driver with its
    traffic file, a cell and a layer metric that reads a new observation:
    new files and new entries only, no file of the repository touched."""
    bench = _bench_with_a_toy_feature(
        tmp_path, {"nodes": 120, "initPods": 10, "measurePods": 60})
    # two warm-up waves: the second wave of these pods patches the plan, and
    # that program must be met before the window (compiles_in_window)
    (bench / "traffic" / "waves-twice.json").write_text(json.dumps(
        {"driver": "twice", "warmup_waves": 2, "traced_waves": 1}))
    (bench / "drivers" / "twice.py").write_text(f"""
import importlib.util
spec = importlib.util.spec_from_file_location(
    "waves_original", {os.path.join(BENCH, "drivers", "waves.py")!r})
waves = importlib.util.module_from_spec(spec)
spec.loader.exec_module(waves)
def run(ctx):
    result = waves.run(ctx)
    result["obs"]["twice"] = {{"waves_seen": 2 * result["obs"]["window"]["waves"]}}
    return result
""")
    (bench / "layer_metrics" / "waves_seen_twice.py").write_text(
        "def read(obs):\n    return (obs.get('twice') or {}).get('waves_seen')\n")
    shutil.copy(os.path.join(BENCH, "layer_metrics", "hint_hit_rate.py"),
                bench / "layer_metrics")
    manifest = {
        "workloads": [{"name": "tiny-basic.twice", "config": "tiny-basic",
                       "traffic": "waves-twice", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "pods_per_s", "unit": "pods/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "waves_seen_twice", "unit": "waves", "moves": "pods_per_s"},
            {"name": "hint_hit_rate", "unit": "%", "moves": "pods_per_s"},
            {"name": "absent_elsewhere", "unit": "x", "moves": "pods_per_s",
             "workloads": ["another.cell"]}]}
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    base = [RUN, "--workload", "tiny-basic.twice", "--seed", "9", "--seconds",
            "0.5", "--rehearse", "--bench-dir", str(bench), "--manifest",
            str(tmp_path / "manifest.json")]
    line = _last_line(_run(base + ["--trace", "0"]))
    assert set(line["metrics"]) == {"pods_per_s", "setup_s"}
    line = _last_line(_run(base + ["--trace", "1"]))
    assert set(line["metrics"]) == {"waves_seen_twice", "hint_hit_rate"}
    assert line["metrics"]["waves_seen_twice"]["value"] >= 2
    assert line["correct"] is True
    # without the feature's files the same cell is refused, not run blind
    shutil.rmtree(bench / "object_features")
    proc = _run(base + ["--trace", "0"])
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
    assert "Unpaired" in proc.stderr
    # a run that dies keeps its directory for the post-mortem; this one's is
    # not wanted
    out = os.path.join(ROOT, "benchmark_out")
    for d in os.listdir(out):
        if d.startswith("tiny-basic.twice-9-"):
            shutil.rmtree(os.path.join(out, d))


def test_the_open_loops_children_find_a_feature_by_name(tmp_path):
    """The sender process builds its pods from the template: it looks for
    the feature's builder where the run does (--bench-dir first)."""
    bench = _bench_with_a_toy_feature(
        tmp_path, {"nodes": 200, "initPods": 40, "measurePods": 400})
    shutil.copy(os.path.join(BENCH, "traffic", "open-0.8knee.json"),
                bench / "traffic")
    for f in ("open.py", "open_client.py"):
        shutil.copy(os.path.join(BENCH, "drivers", f), bench / "drivers")
    manifest = {
        "workloads": [{"name": "tiny-basic.open", "config": "tiny-basic",
                       "traffic": "open-0.8knee", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "bind_p50_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    line = _last_line(_run([
        RUN, "--workload", "tiny-basic.open", "--seed", "9", "--seconds", "1",
        "--trace", "0", "--rehearse", "--bench-dir", str(bench), "--manifest",
        str(tmp_path / "manifest.json")]))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0


# -- the manifest ----------------------------------------------------------

def test_manifest_keeps_to_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and os.path.isdir(os.path.join(ROOT, p))
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0] + "/")
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert len(c["source"]) <= 200
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    pairs = set()
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(MANIFEST["workloads"])
    assert {w["config"] for w in MANIFEST["workloads"]} == set(configs)
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    layers = set()
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.add(m["layer"])
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", ()):
            assert w in cells
    for cell in cells:
        assert len(_expected_metrics(cell, "end_to_end")) >= 2
        assert _expected_metrics(cell, "per_layer")
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_files_under_paths_are_named_from_a_names_characters():
    for p in MANIFEST["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert PATH.match(rel), rel


# -- the yardstick's arithmetic ---------------------------------------------

def _brute_busy(events, t0, t1, step=1000.0):
    """Busy nanoseconds on a grid of `step` ns: an independent count."""
    grid = np.arange(t0, t1, step)
    busy = np.zeros(len(grid), bool)
    for _, s, d in events:
        busy |= (grid + step / 2 >= s) & (grid + step / 2 < s + d)
    return busy.sum() * step


def test_trace_reduction_on_the_recorded_trace():
    with open(os.path.join(BENCH, "testdata", "trace_small.json")) as f:
        events = json.load(f)
    ops = events["devices"]["/device:TPU:0"]["ops"]
    spans = events["host"]
    assert len(ops) > 50 and spans
    got = tracereduce.reduce(events)
    t0 = min(s for _, s, _ in spans)
    t1 = max(s + d for _, s, d in spans)
    assert got["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert got["devices"] == 1 and 0 < got["busy_s"] < got["window_s"]
    # busy: the union of the op intervals, against a count on a 1 us grid
    brute = _brute_busy(ops, t0, t1) / 1e9
    assert got["busy_s"] == pytest.approx(brute, rel=0.02)
    # every idle second is given to a span or to `outside_spans`, once
    idle = got["window_s"] - got["busy_s"]
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
    # kernel time: the scheduling program's runs, clipped to the window
    mods = events["devices"]["/device:TPU:0"]["modules"]
    want = sum(min(s + d, t1) - max(s, t0) for n, s, d in mods
               if n.startswith("jit_schedule_batch")
               and min(s + d, t1) > max(s, t0)) / 1e9
    sec, runs = 0.0, 0
    for n, m in got["modules"].items():
        if n.startswith(tracereduce.SCHEDULING_PROGRAMS):
            sec, runs = sec + m["seconds"], runs + m["runs"]
    assert runs >= 1 and sec == pytest.approx(want)
    obs = {"traced": {"reduced": got, "counters": {"device_batches": runs}}}
    assert tracereduce.kernel_time(obs) == (pytest.approx(want), runs)
    assert tracereduce.kernel_time({"traced": {"reduced": got}}) is None


def test_trace_reduction_by_hand():
    events = {
        "devices": {"/device:TPU:0": {
            "ops": [["x", 5, 10], ["y", 12, 10], ["x", 60, 5], ["z", 130, 10]],
            "modules": [["jit_schedule_batch(1)", 5, 17],
                        ["jit_schedule_batch(1)", 60, 5], ["jit_other", 130, 10]]}},
        "host": [["wave", 0, 100], ["plan.build", 10, 20], ["inner", 15, 10],
                 ["plan.build", 50, 10], ["restore", 120, 30]]}
    got = tracereduce.reduce(events)
    assert got["busy_s"] == pytest.approx(32e-9)        # [5,22] [60,65] [130,140]
    assert got["window_s"] == pytest.approx(150e-9)
    gaps = dict(got["idle_gaps"])
    assert gaps["wave"] == pytest.approx(60e-9)         # [0,5] [30,50] [65,100]
    assert gaps["plan.build"] == pytest.approx(15e-9)   # [25,30] [50,60]
    assert gaps["inner"] == pytest.approx(3e-9)         # [22,25]
    assert gaps["restore"] == pytest.approx(20e-9)      # [120,130] [140,150]
    assert dict(got["device_ops"])["x"] == pytest.approx(15e-9)
    assert got["modules"]["jit_schedule_batch(1)"]["runs"] == 2


def test_prometheus_reading():
    before = prom.parse('h_bucket{le="0.1"} 1\nh_bucket{le="0.2"} 1\n'
                        'h_bucket{le="+Inf"} 1\n'
                        'c_total{result="dispatched"} 2\n')
    after = prom.parse('# HELP h x\nh_bucket{le="0.1"} 51\n'
                       'h_bucket{le="0.2"} 101\nh_bucket{le="+Inf"} 101\n'
                       'c_total{result="dispatched"} 12\nc_total{result="x"} 5\n')
    d = prom.delta(after, before)
    assert prom.total(d, "c_total", result="dispatched") == 10
    assert prom.by_label(d, "c_total", "result") == {"dispatched": 10, "x": 5}
    assert prom.quantile(d, "h", 0.5) == pytest.approx(0.1)
    assert prom.quantile(d, "h", 0.99) == pytest.approx(0.198)
    assert prom.quantile(d, "missing", 0.5) is None


def test_kernel_bytes_and_peaks():
    assert kernelcost.least_bytes_per_batch(1, 0) == 8 * 8 + 4 + 5 * 8
    assert kernelcost.least_bytes_per_batch(0, 1) == 16 + 4
    assert kernelcost.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        kernelcost.peaks("cpu")
    share = kernelcost.hbm_roofline_share(1.0, 1, 5000, 1024, 50, "TPU v5 lite")
    assert 0 < share < 100


# -- the served cell's tail: a share end to end, the percentile per layer ----

def _load(path, name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("limit_ms, want", [
    (50, 50.0),      # the sample AT the limit counts as within it
    (100, 75.0),
    (200, 75.0),
    (500, 100.0),
])
def test_share_of_pods_bound_within_a_limit(limit_ms, want):
    """Four pods: one unbound until the client gave up counts beyond every
    limit a manifest names, a share is of ALL samples, in per cent."""
    open_driver = _load(os.path.join(BENCH, "drivers", "open.py"),
                        "bench_open_for_share")
    assert limit_ms in open_driver.WITHIN_MS
    latency = [10.0, 50.0, 100.0, 499.0]
    assert open_driver._within_share(latency, limit_ms) == want
    assert open_driver._within_share(latency + [15000.0] * 4, limit_ms) \
        == want / 2


def test_the_tail_reader_takes_the_drivers_own_number():
    """One code path for one number: the reader hands on the percentile that
    the driver worked out beside its end-to-end metrics."""
    read = _load(os.path.join(BENCH, "layer_metrics", "bind_tail_p99_ms.py"),
                 "reader_bind_tail").read
    assert read({"client": {"e2e": {"bind_p99_ms": 312.5}}}) == 312.5
    # another driver's cell has no such sample: nothing, not 0
    assert read({"client": {"latency_ms": []}}) is None
    assert read({}) is None
