"""`churn-5k.waves` (PR 40): the entries and the cell pinned by name, the
`priority` pod feature and its refusals, the driver's reading of a wave's
log, the cell through `run.py --rehearse` against both of the program's
schedulers (`correct`, every count of `compared` at its limit), what two
rehearsals of one seed share, the event controls and the feature's own on a
rehearsal's own log, and each new reader on a small recorded `obs`. No
timing is asserted."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import features  # noqa: E402
import objects  # noqa: E402
import reference  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

CELL = "churn-5k.waves"
NEW_METRICS = {
    "failed_attempt_ms": ("ms", "lower", "program_span",
                          "host scheduler loop"),
    "plan_rebuild_full_per_wave": ("builds/wave", "lower", "program_counter",
                                   "feature build and mirror"),
    "structural_rebuild_share": ("%", "lower", "program_span",
                                 "feature build and mirror"),
    "cluster_event_wait_p50_ms": ("ms", "lower", "program_counter",
                                  "control plane and host scheduler loop"),
    "preempt_dry_run_roofline": ("%", "higher", "device_trace", "kernels"),
}
# the accepted per-layer metrics whose readers find something in the cell
ALSO_UNDER = {
    "host_commit_share", "gc_pause_share", "device_wait_share",
    "hint_hit_rate", "plan_build_share", "kernel_ms_per_batch",
    "schedule_batch_roofline", "loop_unnamed_share", "queue_pop_share",
    "inbox_drain_share", "device_dispatch_share", "commit_batch_share", "kernel_hidden_share", "fetch_tail_ms",
    "launch_gap_ms", "collector_pause_share", "plan_adopt_share",
    "cycle_self_share", "pop_run_share"}
# `backlog_at_pop_mean` reads a value here too, and cannot list the cell: an
# accepted test holds its `workloads` to `basic-5k-50k.waves` alone
# (tests/benchmark/test_benchmark_basic50k.py), a `benchmark` PR's to edit
SEEDS = (7, 3000000019)           # the driver's seeds exceed 32 signed bits


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(name):
    return _module(os.path.join(BENCH, "layer_metrics", name + ".py"),
                   "churn_reader_" + name.replace(".", "_"))


driver = _module(os.path.join(BENCH, "drivers", "waves_churn.py"),
                 "churn_driver")


def _config(rehearse=True):
    return objects.load_config(
        os.path.join(BENCH, "configs", "churn-5k.json"), rehearse)


def _traffic():
    with open(os.path.join(BENCH, "traffic", "waves-churn.json")) as f:
        return json.load(f)


# -- the manifest: what this PR appended, by name ----------------------------

def test_the_cell_its_configuration_and_the_five_entries_are_appended():
    configs = [c["name"] for c in MANIFEST["configs"]]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    metrics = [m["name"] for m in MANIFEST["per_layer"]]
    # after the parent's last, wherever a later PR has put its own
    assert configs.index("churn-5k") > configs.index("basic-5k-50k")
    assert cells.index(CELL) > cells.index("basic-5k-50k.waves")
    for name in NEW_METRICS:
        assert metrics.index(name) > metrics.index("pop_run_share")
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == "churn-5k")
    assert cfg["file"] == "benchmark/configs/churn-5k.json"
    assert cfg["source"].endswith(
        "misc/performance-config.yaml:157 "
        "SchedulingWithMixedChurn/5000Nodes_10000Pods")
    assert cfg["reduced"] == ["churn.service"]
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "churn-5k", "waves-churn", 1)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, (unit, better, source, layer) in NEW_METRICS.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, better, source, layer, "pods_per_s")
        assert CELL in m["workloads"]
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    pods_per_s = next(m for m in MANIFEST["end_to_end"]
                      if m["name"] == "pods_per_s")
    assert CELL in pods_per_s["workloads"]
    for name in ALSO_UNDER:
        assert CELL in by_name[name]["workloads"], name
    # a cell is appended to a list, never put in between
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        lists = m.get("workloads", [])
        if CELL in lists and "basic-5k-50k.waves" in lists:
            assert lists.index(CELL) > lists.index("basic-5k-50k.waves")


def test_the_configuration_is_the_sources_and_the_traffic_the_issues():
    cfg = _config(rehearse=False)
    node = {"cpu": 4, "memory": "32Gi", "pods": 110, "zones": 1}
    assert cfg["nodes"] == {"count": 5000, "template": node}
    assert cfg["measurePods"] == {
        "count": 10000, "template": {"cpu": "100m", "memory": "500Mi"}}
    assert cfg["initPods"]["count"] == 0
    assert cfg["churn"]["node"]["template"] == node
    assert cfg["churn"]["pod"]["template"] == {
        "cpu": 9, "memory": "500Mi", "priority": 10}
    assert "left_out" in cfg["churn"]["service"]
    assert cfg["reduced"] == ["churn.service"]
    assert {"nodes", "pods", "churn", "churn names", "churn interval",
            "rehearse"} <= set(cfg["assumed"])
    params = _traffic()
    assert params["driver"] == "waves_churn"
    assert (params["warmup_waves"], params["traced_waves"]) == (2, 2)
    assert params["churn_objects"] == ["node", "pod", "service"]
    assert (params["churn_mode"], params["churn_number"]) == ("recreate", 1)
    assert (params["step_every_bound_pods"], params["steps_per_wave"]) == (
        900, 10)
    # the cell's marks, and a rehearsal's: the tenth at nine tenths
    assert driver.step_every(params, 10000) == 900
    assert driver.step_every(params, 1000) == 90
    assert driver.step_objects(cfg, params) == ["node", "pod"]
    # the pods of a wave are created at once: nothing gates a create
    assert "slice" not in json.dumps(params)
    # a rehearsal's batches are a tenth of the cell's, as its wave is
    assert params["rehearse"]["max_batch"] == 100


# -- the `priority` pod feature ----------------------------------------------

NODES = [{"name": f"n{i}", "zone": "zone-0", "cpu": 4000,
          "memory": 8 << 30, "pods": 110} for i in range(3)]
PLAIN = {"cpu": 1, "memory": "1Gi"}


def test_priority_is_found_on_both_sides_and_refuses_what_it_does_not_model():
    ref_side = features.load("reference", "priority")
    assert features.load("objects", "priority") is not None
    assert ref_side.parse(10, {}) == 10
    for bad in ("10", 1.5, True, None):
        with pytest.raises(reference.Unmodelled):
            ref_side.parse(bad, {})
    pod = objects.make_pod_prototype({"cpu": 9, "memory": "500Mi",
                                      "priority": 10})
    assert pod.priority == 10


def test_a_pod_that_can_evict_nothing_pends_and_one_that_could_evicts():
    ref = reference.Reference(NODES)
    for i in range(9):
        ref.schedule(f"p{i}", PLAIN)
    # 9 cpu fit no node of 4, with or without the pods on it: it pends, and
    # its PostFilter, which finds no candidate, changes nothing
    assert ref.schedule("large", {"cpu": 9, "memory": "1Gi", "priority": 10},
                        may_pend=True) is None
    assert list(ref.pending) == ["large"]
    assert ref.evicted == {} and ref.nominated == {}
    assert ref.candidate_searches == 1
    # a pod with a priority that finds a node is placed as any other
    other = reference.Reference(NODES)
    for i in range(9):
        other.schedule(f"p{i}", PLAIN)
    assert ref.schedule("fits", {"cpu": 1, "memory": "1Gi", "priority": 10}
                        ) == other.schedule("fits", PLAIN)
    # three pods of 1 cpu a node (p0 p3 p6 on n0, p1 p4 p7 on n1, p2 p5 p8
    # on n2: the emptiest node, the first of them): 2 cpu fit no node now,
    # and would fit each once its pods of priority 0 had left it. What was
    # refused as "preemption is not modelled" is now the source's answer:
    # on every node the first two pods put back fit beside it (4 cpu) and
    # the third, the youngest, is the one victim; priorities, sums and
    # counts tie, and the victim created last is p8: n2
    ref = reference.Reference(NODES)
    for i in range(9):
        assert ref.schedule(f"p{i}", PLAIN) == f"n{i % 3}"
    assert ref.schedule("could", {"cpu": 2, "memory": "1Gi", "priority": 10},
                        may_pend=True) is None
    assert ref.evicted == {"p8": "could"}
    assert ref.nominations == {"could": "n2"}
    assert list(ref.pending) == ["could"] and "p8" in ref.placed
    ref.delete("p8")
    assert ref.retry("could") == "n2" and ref.pending == {}
    # against pods of its own priority it can evict nothing: it pends
    ref = reference.Reference(NODES)
    high = {"cpu": 1, "memory": "1Gi", "priority": 10}
    for i in range(9):
        ref.schedule(f"p{i}", high)
    assert ref.schedule("equal", {"cpu": 2, "memory": "1Gi", "priority": 10},
                        may_pend=True) is None
    assert ref.evicted == {}
    # and a node that would admit it, added while it pends and the log has
    # no retry of it, is refused
    with pytest.raises(reference.Unmodelled, match="retry"):
        ref.add_node({"name": "n9", "zone": "zone-0", "cpu": 4000,
                      "memory": 8 << 30, "pods": 110})


# -- the driver's reading of a wave ------------------------------------------

def test_a_waves_node_events_are_known_before_its_clock_starts():
    cfg = _config()
    template = cfg["churn"]["node"]["template"]
    events = driver.wave_events("w0", 10, ["node", "pod"], template, 420)
    assert [op for op, _, _ in events] == ["node_add", "node_delete"] * 5
    # a step's delete names the node the step before it created, and a
    # node's name never comes back
    assert [name for _, name, _ in events] == [
        f"w0-churn-node-{k}" for k in (1, 1, 3, 3, 5, 5, 7, 7, 9, 9)]
    assert events[0][2] == reference.node_description(
        "w0-churn-node-1", 420, template)
    assert events[4][2] == reference.node_description(
        "w0-churn-node-5", 422, template)
    assert driver.wave_events("w0", 10, ["pod"], template, 420) == []


def test_a_waves_log_is_read_from_its_trail():
    """Pods bound up to a count, then what happened there: a node event
    where the journal had it, a churn pod where it was attempted, the churn
    pod's delete behind its node's; the restore's deletes last."""
    template = _config()["churn"]["node"]["template"]
    events = driver.wave_events("w0", 2, ["node", "pod"], template, 420)
    trail = [("bound", 3), ("node", 0), ("failed", 1), ("bound", 5),
             ("node", 1), ("bound", 6)]
    part, tried = driver.wave_log("w0", trail, events, ["w0-churn-pod-1"], 6)
    assert tried == ["w0-churn-pod-1"]
    assert [(op, name) for op, name, _ in part] == (
        [("create", f"w0-{i}") for i in range(3)]
        + [("node_add", "w0-churn-node-1"), ("create", "w0-churn-pod-1")]
        + [("create", "w0-3"), ("create", "w0-4")]
        + [("node_delete", "w0-churn-node-1"), ("delete", "w0-churn-pod-1")]
        + [("create", "w0-5")]
        + [("delete", f"w0-{i}") for i in range(6)])
    # a churn pod deleted before it was tried is in nobody's log
    part, tried = driver.wave_log(
        "w0", [("bound", 6), ("node", 0), ("node", 1)], events,
        ["w0-churn-pod-1"], 6)
    assert tried == [] and all(g != "churnPod" for _, _, g in part)


def _in_process(seed, scheduler="device", seconds=0.1):
    """The driver as `run.py --rehearse` calls it, in this process, for what
    the harness's last line does not hold: the log, the nodes, `obs`."""
    said = []
    ctx = types.SimpleNamespace(
        config=_config(), traffic=dict(_traffic(), scheduler=scheduler),
        seed=seed, seconds=seconds, trace=False, rehearse=True, root=ROOT,
        bench_dir=BENCH, out_dir=None, say=said.append, profiler=None,
        window_opens=lambda: None, window_closes=lambda: None)
    result = driver.run(ctx)
    result["said"] = said
    return result


@pytest.fixture(scope="module")
def rehearsed():
    """One in-process rehearsal a seed against the device scheduler, kept
    for the module: the controls and the two-runs test read them."""
    runs = {}

    def of(seed, again=False):
        key = (seed, again)
        if key not in runs:
            runs[key] = _in_process(seed)
        return runs[key]
    return of


@pytest.mark.parametrize("seed", SEEDS + (11,))
def test_the_controls_are_not_correct_on_the_rehearsals_log(seed, rehearsed):
    """What a cache can get wrong about a node event, and the feature's own
    control, over the log a rehearsal gave (node events under a backlog,
    where the loop took them): each must place pods elsewhere than the
    reference. `tree_order_stale` is a READING here and not a control: the
    source's nodes carry no zone, one zone is one list, and a node's turn in
    its zone IS the list's end, so `correct` cannot see the node tree's
    order in this cell (PERF.md)."""
    run = rehearsed(seed)
    nodes, templates, log = run["nodes"], run["templates"], run["log"]
    assert all(got <= limit for _, got, limit in run["guards"]), run["guards"]
    sound = reference.replay(reference.Reference(nodes), templates, log,
                             ["churnPod"])
    waves = sum(1 for op, name, _ in log if name.endswith("-churn-pod-1")
                and op == "create")
    assert waves >= 3 and len(sound) == 1005 * waves
    assert sum(1 for n in sound.values() if n is None) == 5 * waves
    assert reference.compare(sound, run["placements"])["differing"] == 0
    priority = features.load("reference", "priority")
    controls = dict(control.EVENT_CONTROLS)
    for name, broken in priority.CONTROLS.items():
        controls["priority." + name] = control._swapped("priority", broken)
    assert set(controls) == {
        "node_add_ignored", "node_delete_ignored", "tree_order_stale",
        "priority.victims_evicted", "priority.no_reprieve",
        "priority.first_candidate", "priority.room_not_held",
        "priority.bound_at_first_attempt", "priority.offset_never_advanced"}
    # READINGS here, as `tree_order_stale` is: no node holds the churn pod's
    # 9 cpu, so no search finds a candidate, and the five controls that
    # break what follows a candidate break nothing in this cell
    readings = {"tree_order_stale"} | {
        name for name in controls if name.startswith("priority.")
        and name != "priority.victims_evicted"}
    for name, broken in controls.items():
        try:
            other = reference.replay(broken(nodes), templates, log,
                                     ["churnPod"])
        except (reference.Unschedulable, reference.Unmodelled, KeyError):
            assert name not in readings, name
            continue            # it could not finish the log: not correct
        differ = sum(other[p] != node for p, node in sound.items())
        if name in readings:
            assert differ == 0, (name, seed)
        else:
            assert differ > 0, (name, seed)


def test_what_two_rehearsals_of_one_seed_share(rehearsed):
    """The pods of a wave are created beside the drain and a step is parked
    from the client's thread, so WHERE the loop takes a node event depends
    on how the two threads interleave, and two runs of one seed need not
    give one log (ISSUE 40 asked for one log; that held only while the
    driver created the pods mark by mark, which the review refused). What
    both runs hold: the same operations, every wave its ten steps in order,
    each issued at or past its mark and taken no earlier than issued, five
    churn pods a wave tried once each, and placements the reference agrees
    with on each run's own log."""
    first, second = rehearsed(11), rehearsed(11, again=True)
    for run in (first, second):
        assert all(got <= limit for _, got, limit in run["guards"])
        for w in run["obs"]["churn"]["waves"]:
            steps, taken = w["steps"], w["taken"]
            assert [s["k"] for s in steps] == list(range(1, 11))
            assert [s["mark"] for s in steps] == [90 * k for k in range(1, 11)]
            assert all(s["bound"] >= s["mark"] for s in steps)
            issued = [s["bound"] for s in steps]
            assert issued == sorted(issued) and len(taken) == 10
            assert all(t["bound"] >= s["bound"] and t["at_s"] >= s["at_s"]
                       for s, t in zip(steps, taken))
            # a step waits for the one before it to be taken
            assert all(s["at_s"] >= t["at_s"]
                       for s, t in zip(steps[1:], taken))
            assert w["failed_attempts"] == 5
    key = lambda op: (op[0], op[1])                           # noqa: E731
    waves = min(len(r["obs"]["churn"]["waves"]) for r in (first, second))
    per_wave = 1000 + 1000 + 10 + 10          # creates, deletes, nodes, pods
    upto = (2 + waves) * per_wave
    assert sorted(map(key, first["log"][:upto])) == sorted(
        map(key, second["log"][:upto]))
    assert first["nodes"] == second["nodes"]


# -- the cell through the harness's front door -------------------------------

@pytest.fixture(scope="module")
def host_bench(tmp_path_factory):
    """The cell's own files with the traffic's `scheduler` set to `host`:
    the program's sequential scheduler in the device scheduler's place."""
    bench = tmp_path_factory.mktemp("churn_host")
    for d in ("configs", "traffic", "drivers"):
        os.makedirs(bench / d)
    shutil.copy(os.path.join(BENCH, "configs", "churn-5k.json"),
                bench / "configs")
    for name in ("waves_churn.py", "waves.py"):
        shutil.copy(os.path.join(BENCH, "drivers", name), bench / "drivers")
    params = dict(_traffic(), scheduler="host")
    (bench / "traffic" / "waves-churn.json").write_text(json.dumps(params))
    manifest = {"workloads": [w for w in MANIFEST["workloads"]
                              if w["name"] == CELL],
                "end_to_end": [{"name": "pods_per_s", "unit": "pods/s"},
                               {"name": "setup_s", "unit": "s"}],
                "per_layer": []}
    (bench / "manifest.json").write_text(json.dumps(manifest))
    return str(bench)


def _rehearse(seed, trace=0, seconds=1, bench=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
           "--seed", str(seed), "--seconds", str(seconds), "--trace",
           str(trace), "--rehearse"]
    if bench:
        cmd += ["--bench-dir", bench, "--manifest",
                os.path.join(bench, "manifest.json")]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


GUARDS = {"placements_differing", "pods_unbound", "pods_unexpected",
          "evictions_differing", "nominations_differing",
          "nodes_over_allocatable", "compiles_in_window", "host_path_pods",
          "breaker_charges", "steps_short_or_over",
          "nodes_off_at_a_waves_end", "churn_pods_bound",
          "node_events_without_a_place", "churn_pods_never_attempted",
          "failed_attempts"}


def _holds(line, out, waves_at_least):
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == GUARDS
    for name, c in line["compared"].items():
        if name == "failed_attempts":
            # one attempt a churn pod, five a wave, and not one more
            assert c["value"] == c["limit"] >= 5 * (2 + waves_at_least)
        else:
            assert c["value"] == c["limit"] == 0, name
    assert line["failed"] == 0 and line["attempted"] >= 1000
    for ln in out.splitlines():
        if "] wave " in ln or "warm-up wave" in ln:
            assert "1000/1000 bound" in ln and "steps 10 issued at" in ln, ln
            assert "failed attempts 5 nodes at end 420" in ln, ln


@pytest.mark.parametrize("seed", SEEDS)
def test_the_rehearsal_is_correct_against_the_device_scheduler(seed):
    line, out = _rehearse(seed)
    _holds(line, out, 1)
    assert line["metrics"]["setup_s"]["value"] > 0
    assert "pods_per_s" in line["metrics"]
    assert "churn object 'service' left out" in out


def test_the_rehearsal_is_correct_against_the_host_scheduler(host_bench):
    line, out = _rehearse(7, bench=host_bench)
    _holds(line, out, 1)


def test_a_traced_rehearsal_reads_the_new_metrics_and_the_account_sums():
    """Traced, so that the new readers are met through the front door too:
    their values stand in the line, the new stage is in the program's
    account, and that account still sums to the wave."""
    first, out1 = _rehearse(11, trace=1, seconds=2)
    _holds(first, out1, 2)
    got = first["metrics"]
    for name in ("failed_attempt_ms", "plan_rebuild_full_per_wave",
                 "structural_rebuild_share", "cluster_event_wait_p50_ms"):
        assert got[name]["value"] >= 0, name
    # five churn pods' sessions and, between them, the plain pods': more
    # than one build a step pair, fewer than one a batch
    assert 8 <= got["plan_rebuild_full_per_wave"]["value"] <= 14
    assert "preempt_dry_run_roofline" not in got       # no chip, no share
    account = next(ln for ln in out1.splitlines()
                   if ln.startswith("[progspans]"))
    assert "'postfilter.preempt':" in account
    shares = json.loads(account.split("% of wave time ")[1].split("; under")[0]
                        .replace("'", '"'))
    unnamed = float(account.rsplit(" ", 1)[1])
    named = sum(v for k, v in shares.items() if k != "cycle")
    assert abs(named + unnamed - 100.0) < 0.2, account
    parts = next(ln for ln in out1.splitlines()
                 if ln.startswith("[churn] failed attempts"))
    for key in ("victims_ms", "plan_ms", "dispatch_ms", "fetch_ms",
                "postfilter_ms", "turn_ms", "'engine': ['device']"):
        assert key in parts, parts
    causes = next(ln for ln in out1.splitlines()
                  if ln.startswith("[churn] full builds"))
    assert "'structural': [" in causes and "'other_pod': [" in causes
    # node events were taken under a backlog: some step of a traced wave
    # was taken with pods still to bind
    waves = [ln for ln in out1.splitlines() if "] wave " in ln]
    taken = json.loads(waves[0].split("node events taken at bound ")[1]
                       .split(" +s")[0])
    assert len(taken) == 10 and taken[0] < 1000


# -- the readers, each on a small recorded obs -------------------------------

MS = 1e6            # ns


def _spans():
    """One traced wave of 100 ms with two turns: a plain session whose full
    build a node event caused, and a churn pod's with its failed attempt."""
    return {"wave_s": 0.1, "spans": [
        ["cycle", 0.0, 30 * MS, {}],
        ["plan.build", 1 * MS, 8 * MS, {"kind": "full",
                                        "cause": "structural"}],
        ["plan.adopt", 20 * MS, 4 * MS, {"kind": "full",
                                         "cause": "structural"}],
        ["cycle", 40 * MS, 25 * MS, {}],
        ["plan.build", 41 * MS, 6 * MS, {"kind": "full",
                                         "cause": "other_pod"}],
        ["postfilter.preempt", 50 * MS, 9 * MS,
         {"engine": "device", "candidates": "0", "victims_ms": "2.0",
          "plan_ms": "5.0", "dispatch_ms": "0.5", "fetch_ms": "1.0",
          "rows": "5120", "k": "8", "r": "7"}],
        ["plan.adopt", 60 * MS, 3 * MS, {"kind": "full",
                                         "cause": "other_pod"}],
    ]}


def test_failed_attempt_ms_reads_the_turn_around_the_stage():
    obs = {"churnspans": _spans()}
    assert _reader("failed_attempt_ms").read(obs) == pytest.approx(25.0)
    parts = obs["failed_attempt_parts"]
    assert parts["attempts"] == 1 and parts["engine"] == ["device"]
    assert parts["postfilter_ms"] == pytest.approx(9.0)
    assert (parts["victims_ms"], parts["plan_ms"], parts["dispatch_ms"],
            parts["fetch_ms"]) == (2.0, 5.0, 0.5, 1.0)
    assert parts["plan_build_ms"] == pytest.approx(6.0)
    assert parts["plan_adopt_ms"] == pytest.approx(3.0)
    # a program without the stage, a run without a trace: nothing
    bare = {"wave_s": 0.1, "spans": [s for s in _spans()["spans"]
                                     if s[0] != "postfilter.preempt"]}
    assert _reader("failed_attempt_ms").read({"churnspans": bare}) is None
    assert _reader("failed_attempt_ms").read({"churnspans": None}) is None
    assert _reader("failed_attempt_ms").read({}) is None


def test_structural_rebuild_share_counts_the_builds_that_say_so():
    obs = {"churnspans": _spans()}
    got = _reader("structural_rebuild_share").read(obs)
    assert got == pytest.approx(12.0)          # 8 + 4 ms of 100
    assert obs["rebuilds_by_cause"]["other_pod"][0] == 1
    assert obs["rebuilds_by_cause"]["structural"] == [1, pytest.approx(0.012)]
    # the parent's spans say no cause: nothing, and no error
    mute = {"wave_s": 0.1, "spans": [[s[0], s[1], s[2], {}]
                                     for s in _spans()["spans"]]}
    assert _reader("structural_rebuild_share").read(
        {"churnspans": mute}) is None
    assert _reader("structural_rebuild_share").read({}) is None


def test_plan_rebuild_full_per_wave_is_the_counter_over_the_waves():
    read = _reader("plan_rebuild_full_per_wave").read
    assert read({"counters": {"plan_rebuilds_full": 48},
                 "window": {"waves": 3}}) == 16.0
    assert read({"counters": {}, "window": {"waves": 3}}) is None
    assert read({}) is None


def test_cluster_event_wait_p50_ms_reads_the_node_events_alone():
    name = "scheduler_cluster_event_wait_seconds"

    def bucket(kind, le, v):
        return ((name + "_bucket", (("kind", kind), ("le", le))), v)

    series = dict([bucket("node", "0.001", 2.0), bucket("node", "0.002", 8.0),
                   bucket("node", "+Inf", 10.0),
                   bucket("other", "0.001", 0.0), bucket("other", "+Inf", 50.0)])
    got = _reader("cluster_event_wait_p50_ms").read(
        {"prom": {"scheduler": series}})
    assert got == pytest.approx(1.5)           # rank 5 of 10, inside 1-2 ms
    assert _reader("cluster_event_wait_p50_ms").read(
        {"prom": {"scheduler": {}}}) is None
    assert _reader("cluster_event_wait_p50_ms").read({}) is None


def test_preempt_dry_run_roofline_is_least_bytes_over_the_programs_time():
    import preemptcost
    rows, k, r = 5120, 8, 7
    least = preemptcost.least_bytes_per_call(rows, k, r)
    assert least == (rows * k * (r * 8 + 1) + rows * (2 * r * 8 + 8 + 1)
                     + r * 8 + rows * (1 + k))
    obs = {"churnspans": _spans(),
           "device": {"kind": "TPU v5 lite"},
           "traced": {"reduced": {"modules": {
               "jit_dry_run_preemption(1856296023565836174)": {
                   "seconds": 2e-4, "runs": 2},
               "jit_schedule_batch(18198938931156)": {
                   "seconds": 4e-2, "runs": 32}}}}}
    got = _reader("preempt_dry_run_roofline").read(obs)
    assert got == pytest.approx(100.0 * 2 * least / 819e9 / 2e-4)
    assert 0 < got < 100
    # a rehearsal has no chip; a trace without the program, spans without
    # the shapes: nothing
    assert _reader("preempt_dry_run_roofline").read(
        dict(obs, device={"kind": "cpu", "rehearsal": True})) is None
    assert _reader("preempt_dry_run_roofline").read(
        dict(obs, traced={"reduced": {"modules": {}}})) is None
    mute = {"wave_s": 0.1, "spans": [[s[0], s[1], s[2], {}]
                                     for s in _spans()["spans"]]}
    assert _reader("preempt_dry_run_roofline").read(
        dict(obs, churnspans=mute)) is None


def test_the_span_loader_keeps_the_spans_inside_the_traced_waves():
    import churnspans
    bench = [["bench.wave", 0.0, 50 * MS], ["bench.restore", 50 * MS, 5 * MS],
             ["bench.wave", 60 * MS, 40 * MS]]
    sched = [["cycle", 10 * MS, 5 * MS, {}], ["cycle", 52 * MS, 1 * MS, {}],
             ["plan.build", 61 * MS, 5 * MS, {"cause": "structural"}]]
    got = churnspans.in_waves(bench, sched, 1)
    assert got["wave_s"] == pytest.approx(0.04)
    assert [s[0] for s in got["spans"]] == ["plan.build"]
    got = churnspans.in_waves(bench, sched, 2)
    assert [s[0] for s in got["spans"]] == ["cycle", "plan.build"]
    assert churnspans.in_waves([], sched, 2) is None
