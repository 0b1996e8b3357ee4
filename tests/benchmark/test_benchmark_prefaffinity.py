"""What PR 31 added to the benchmark, on the CPU at toy size: the
configuration `prefaffinity-5k` (one preferred hostname pod-affinity term over
two namespaces, nodes that fill up) equal to the program's two schedulers with
the restore's deletes between waves, its controls, both new cells through
`run.py --rehearse` with every listed reader, the cost function of a
normalising batch against a hand count, the five new readers on canned
observations, and the manifest held to the parent's, entry for entry. No
timing is asserted."""

import collections
import hashlib
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
RUN = os.path.join(BENCH, "run.py")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import features  # noqa: E402
import ipacost  # noqa: E402
import kernelcost  # noqa: E402
import objects  # noqa: E402
import prom  # noqa: E402
import reference  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CONFIG = "prefaffinity-5k"
PREF_CELL, OPEN_CELL = "prefaffinity-5k.waves", "spread-5k.served-open"
# the per-layer metrics PR 31 listed each new cell under
PREF_LISTED = {
    "ipa_score_share", "ipa_scan_roofline", "scan_normalised_share",
    "kernel_ms_per_batch",
    "host_commit_share", "gc_pause_share", "device_wait_share",
    "plan_build_share", "hint_hit_rate", "queue_pop_share",
    "inbox_drain_share", "loop_unnamed_share", "device_dispatch_share",
    # appended by PR 38: the readers of PRs 35-37
    "commit_batch_share", "pop_run_share", "kernel_hidden_share",
    "fetch_tail_ms", "launch_gap_ms", "scan_step_us",
    "collector_pause_share", "plan_adopt_share", "cycle_self_share"}
OPEN_LISTED = {
    "generator_lag_p99_ms", "bind_tail_p99_ms", "sched_e2e_p99_ms",
    "hint_hit_rate.open", "queue_wait_p99_ms", "bind_post_p99_ms",
    "loop_idle_share.open", "gc_pause_share.open",
    "device_batch_pods_mean.open", "kernel_ms_per_batch.open",
    "inbox_oldest_wait_p50_ms.open"}              # appended by PR 38
SEEDS = (7, 3000000019)          # the driver's seeds exceed 32 signed bits
# BENCHMARK.json at the parent commit (e4e3f1e): its sha256, how many
# entries each list had, and its cells
PARENT_MANIFEST = "7177f6c0c53b8163ef4403148b9854012b8387e8d2bfdafd47368f0ff2ce19b0"
PARENT_ENTRIES = {"configs": 3, "workloads": 5, "end_to_end": 4,
                  "per_layer": 22}
PARENT_CELLS = ("spread-5k.waves", "basic-5k.waves", "basic-5k.served-open",
                "basic-5k.served-waves", "antiaffinity-5k.waves")
PREFERRED = "preferredDuringSchedulingIgnoredDuringExecution"
POD_CAP = 40                     # 4 cpu over 100m: NodeResourcesFit's limit


def _load(*parts):
    """A benchmark file as a module of this test's own, loaded by path as
    `run.py` loads drivers and readers."""
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "under_test_" + parts[-1][:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(**counts):
    cfg = objects.load_config(
        os.path.join(BENCH, "configs", CONFIG + ".json"), rehearse=True)
    for group, count in counts.items():
        cfg[group]["count"] = count
    return cfg


def _scheduler(kind):
    if kind == "host":
        from kubernetes_tpu.core import Scheduler
        return Scheduler(deterministic_ties=True)
    from kubernetes_tpu.models import TPUScheduler
    return TPUScheduler()


@pytest.mark.parametrize("kind", ("host", "device"))
@pytest.mark.parametrize("seed", SEEDS)
def test_prefaffinity_equals_the_programs_schedulers(seed, kind):
    """60 nodes of 4 cpu, 40 init pods in `sched-0`, then two waves of 80 in
    `sched-1` with the restore's deletes between them: every placement equal,
    pod for pod, the pods packed onto full nodes (40 each, the fit filter's
    refusal at the boundary) and no node over its allocatable."""
    cfg = _config(nodes=60, initPods=40, measurePods=80)
    nodes = objects.cluster(cfg, seed)
    sched = _scheduler(kind)
    cs = sched.clientset
    for d in nodes:
        cs.create_node(objects.make_node(d))
    ref = reference.Reference(nodes)
    expected = {}

    def create(group, names):
        proto = objects.make_pod_prototype(cfg[group]["template"])
        pods = [cs.create_pod(objects.stamp(proto, n)) for n in names]
        for n in names:
            expected[n] = ref.schedule(n, cfg[group]["template"])
        sched.run_until_idle()
        return pods

    create("initPods", [f"init-{i}" for i in range(40)])
    for w in range(2):
        pods = create("measurePods", [f"w{w}-{i}" for i in range(80)])
        got = {p.name: p.node_name for p in cs.pods.values()}
        cmp_ = reference.compare({n: expected[n] for n in got}, got)
        assert (cmp_["differing"], cmp_["unbound"]) == (0, 0), cmp_
        on = collections.Counter(got.values())
        # 120 pods pack onto three nodes, each full: the score decided, and
        # the fit filter refused the full ones
        assert sorted(on.values()) == [POD_CAP] * 3
        assert ref.over_allocatable() == []
        for p in pods:
            cs.delete_pod(cs.pods[p.uid])
            ref.delete(p.name)
    if kind == "device":
        assert sched.host_path_pods == 0
        # every batch rode the scan that normalises the score at each step
        engines = sched.metrics.device_batches
        assert engines.value("scan_normalised") == sched.device_batches >= 3
        # one full plan build a wave (a delete of a term-carrying pod voids
        # the plan): the init pods' over an empty cluster, where the walk
        # meets no pod and its stage stays shut, then two over the 40 init
        # pods
        assert sched.stages.counts["plan.ipa_score"] == 2
        terms = sched.metrics.plan_ipa_terms
        assert terms.value("pods_walked") == 40 + 40
        assert terms.value("score_matches") == 2 * (40 + 40)


def test_the_configuration_is_the_sources_row():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    assert (cfg["nodes"]["count"], cfg["initPods"]["count"],
            cfg["measurePods"]["count"]) == (5000, 5000, 5000)
    assert cfg["reduced"] == [] and cfg["device_path"]["min_device_batches"] == 1
    init, measured = cfg["initPods"]["template"], cfg["measurePods"]["template"]
    assert (init["namespace"], measured["namespace"]) == ("sched-0", "sched-1")
    # one pod template in the source: labels and term are the same on both
    for key in ("labels", "podAffinity", "cpu", "memory"):
        assert init[key] == measured[key]
    (weighted,) = init["podAffinity"][PREFERRED]
    assert weighted == {"weight": 1, "podAffinityTerm": {
        "labelSelector": {"matchLabels": {"color": "red"}},
        "topologyKey": "kubernetes.io/hostname",
        "namespaces": ["sched-1", "sched-0"]}}
    assert cfg["nodes"]["template"] == {"cpu": 4, "memory": "32Gi",
                                        "pods": 110, "zones": 1}
    assert (measured["cpu"], measured["memory"]) == ("100m", "500Mi")
    assert measured["labels"] == {"color": "red"}
    assert {"nodes", "pods"} <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == 3
    # nodes fill at the published size too: three waves' worth of pods is
    # 375 full nodes, and at the rehearsal's the adaptive sample is live
    # (a pod looks at 100 of 200 nodes)
    assert 15000 // POD_CAP == 375
    toy = cfg["rehearse"]
    assert reference.num_feasible_nodes_to_find(toy["nodes"]) < toy["nodes"]
    assert toy["initPods"] + toy["measurePods"] > 2 * POD_CAP


@pytest.mark.parametrize("which, differs", [
    ("podAffinity.score_dropped", True),
    ("last_maximum", True),
    ("stale_batch", True),
    # the normalised 0-200 points swamp the 0-200 of the two resource
    # scores on equal nodes, so these read 0 here and at the published size
    ("podAffinity.plugin_weight_1", False),
    ("podAffinity.symmetric_half_dropped", False),
    ("podAffinity.normalised_over_cluster", False),
    ("podAffinity.floor_not_float", False),
    ("int32", False),
])
def test_what_correct_guards_on_this_configuration(which, differs):
    cfg = _config()
    broken = {**control.CONTROLS, **control.feature_controls(cfg)}[which]
    total, differ = control.differing(cfg, 11, broken)
    assert total == cfg["initPods"]["count"] + cfg["measurePods"]["count"]
    assert (differ > 0) is differs
    if which == "podAffinity.score_dropped":
        assert differ > 0.9 * total


def test_the_reference_normalises_in_float_then_truncates():
    """scoring.go's form on the kept rows, times the plugin's weight; the
    `floor_not_float` control parts from it where the enumeration of
    tests/test_ipa_normalise_forms.py says (29 / 50: 57 against 58)."""
    feature = features.load("reference", "podAffinity")
    ref = reference.Reference([
        {"name": f"node-{i}", "zone": "zone-0", "cpu": 64000,
         "memory": 1 << 40, "pods": 110} for i in range(4)])
    tpl = {"labels": {"color": "red"}, "podAffinity": {PREFERRED: [
        {"weight": 29, "podAffinityTerm": {
            "labelSelector": {"matchLabels": {"color": "blue"}},
            "topologyKey": "kubernetes.io/hostname"}}]}}
    blue = {"labels": {"color": "blue"}}
    red, target = ref._shape(tpl), ref._shape(blue)
    state, floored = feature.State(ref), feature.CONTROLS["floor_not_float"](ref)
    state.account(1, target, +1)
    rows = np.arange(4)
    # the incoming red pod's term selects the blue pod on node 1, which has
    # no term of its own to pull with: raw (0, 29, 0, 0), normalised over the
    # kept rows and doubled by the plugin's weight
    assert list(state.raw(red)) == [0, 29, 0, 0]
    assert list(state.score(red, rows)) == [0, 200, 0, 0]
    assert list(state.raw(target)) == [0, 0, 0, 0]
    # the form itself, at the first pair where the two part
    above, span = np.array([0, 29, 50]), 50
    assert list(state.normalise(above, span)) == [0, 57, 100]
    assert list(floored.normalise(above, span)) == [0, 58, 100]
    # min == max: every row 0, and the kept rows alone set the span
    assert list(state.score(red, np.array([0, 2, 3]))) == [0, 0, 0]
    assert state.feasible(red) is None
    over = feature.CONTROLS["normalised_over_cluster"](ref)
    over.account(1, target, +1)
    assert list(over.score(red, np.array([0, 2]))) == [0, 0]
    assert over.extremes(over.raw(red), np.array([0, 2])) == (0, 29)


@pytest.mark.parametrize("value", [
    {"requiredDuringSchedulingIgnoredDuringExecution": []},
    {PREFERRED: [{"weight": 0, "podAffinityTerm": {}}]},
    {PREFERRED: [{"weight": 101, "podAffinityTerm": {}}]},
    {PREFERRED: [{"weight": 1, "podAffinityTerm": {
        "labelSelector": {"matchLabels": {"a": "b"}},
        "topologyKey": "topology.kubernetes.io/zone"}}]},
    {PREFERRED: [{"weight": 1, "podAffinityTerm": {
        "labelSelector": {"matchExpressions": []},
        "topologyKey": "kubernetes.io/hostname"}}]},
    {PREFERRED: [{"weight": 1, "podAffinityTerm": {
        "labelSelector": {"matchLabels": {}},
        "topologyKey": "kubernetes.io/hostname"}}]},
    {PREFERRED: [{"weight": 1, "podAffinityTerm": {
        "labelSelector": {"matchLabels": {"a": "b"}},
        "topologyKey": "kubernetes.io/hostname", "namespaceSelector": {}}}]},
    {PREFERRED: [{"weight": 1, "podAffinityTerm": {
        "labelSelector": {"matchLabels": {"a": "b"}},
        "topologyKey": "kubernetes.io/hostname", "matchLabelKeys": ["a"]}}]},
    {PREFERRED: [{"weight": 1, "podAffinityTerm": {
        "labelSelector": {"matchLabels": {"a": "b"}},
        "topologyKey": "kubernetes.io/hostname", "namespaces": []}}]},
    {PREFERRED: [{"weight": 1, "preference": {}, "podAffinityTerm": {}}]},
])
def test_the_reference_refuses_what_it_does_not_model(value):
    feature = features.load("reference", "podAffinity")
    with pytest.raises(reference.Unmodelled):
        feature.parse(value, {})


def test_the_object_side_carries_the_namespace_list():
    cfg = _config()
    pod = objects.make_pod_prototype(cfg["measurePods"]["template"])
    (weighted,) = pod.affinity.pod_affinity.preferred
    assert weighted.weight == 1
    assert weighted.term.namespaces == ("sched-1", "sched-0")
    assert weighted.term.topology_key == "kubernetes.io/hostname"
    assert not pod.affinity.pod_affinity.required
    assert pod.namespace == "sched-1" and pod.labels == {"color": "red"}


def _run(args, timeout=600):
    e = {k: v for k, v in os.environ.items()
         if k not in ("BENCH_RUN", "XLA_FLAGS")}
    proc = subprocess.run([sys.executable, RUN] + args, cwd=ROOT, env=e,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def _listed(cell):
    return {m["name"] for m in MANIFEST["per_layer"]
            if cell in m.get("workloads", ())}


def test_rehearsal_of_the_prefaffinity_cell_reads_every_listed_metric():
    """Also the guard of the row scatter's one width: the rehearsal's waves
    dirty a count of rows that wanders across a boundary of the parent's
    widths (3 to 8 nodes of 200 hold a wave's pods), and a width first met
    in the window is a compile there (the parent of PR 31 read 3)."""
    line, out = _run(["--workload", PREF_CELL, "--seed", "3000000019",
                      "--seconds", "1", "--trace", "1", "--rehearse"])
    compared = line["compared"]
    assert compared["compiles_in_window"]["value"] == 0
    assert line["correct"] is True and line["failed"] == 0
    # every reader PR 31 listed the cell under returns a number, but the
    # share of the chip's roofline, which needs the chip: None in a rehearsal
    assert PREF_LISTED - {"ipa_scan_roofline"} <= set(line["metrics"]) \
        <= _listed(PREF_CELL)
    assert line["metrics"]["ipa_score_share"]["value"] > 0
    # the normalising scan placed every batch of the traced waves
    assert line["metrics"]["scan_normalised_share"]["value"] == 100.0
    assert line["metrics"]["hint_hit_rate"]["value"] == 0
    assert line["metrics"]["kernel_ms_per_batch"]["value"] > 0
    assert "'plan.ipa_score'" in out     # the [progspans] line names the stage
    assert compared["host_path_pods"]["value"] == 0
    assert compared["nodes_over_allocatable"]["value"] == 0
    assert "device_batches_short_of_minimum" not in compared


def test_rehearsal_of_the_served_open_spread_cell():
    line, out = _run(["--workload", OPEN_CELL, "--seed", "12",
                      "--seconds", "1", "--trace", "1", "--rehearse"])
    assert line["correct"] is True and line["failed"] == 0
    assert OPEN_LISTED <= set(line["metrics"]) <= _listed(OPEN_CELL)
    assert line["metrics"]["kernel_ms_per_batch.open"]["value"] > 0
    # no hint can bind a hard-spread pod: the chip placed every arrival
    assert line["metrics"]["hint_hit_rate.open"]["value"] == 0
    assert line["metrics"]["device_batch_pods_mean.open"]["value"] >= 1
    assert line["device"]["busy_s"] > 0
    assert {"host_path_pods", "breaker_charges", "compiles_in_window"} <= set(
        line["compared"])
    # the judged metrics of the cell, from an untraced line's names
    e2e = {m["name"] for m in MANIFEST["end_to_end"]
           if OPEN_CELL in m.get("workloads", (OPEN_CELL,))}
    assert e2e == {"bind_p50_ms", "bind_within_200ms_share", "setup_s"}


def test_the_traffic_file_is_the_open_loops_but_for_the_rate():
    with open(os.path.join(BENCH, "traffic", "open-0.8knee.json")) as f:
        base = json.load(f)
    with open(os.path.join(BENCH, "traffic", "open-spread-0.8knee.json")) as f:
        mine = json.load(f)
    for key in ("driver", "connections", "warmup_seconds", "traced_seconds",
                "grace_seconds", "host_spans"):
        assert mine[key] == base[key]
    assert set(mine) - set(base) == {"knee_sweep"}
    assert mine["rate_pods_per_s"] == pytest.approx(
        0.8 * mine["knee_pods_per_s"])
    # the sweep that found the knee is in the file, reading by reading
    sweep = mine["knee_sweep"]
    assert len(sweep["readings"]) >= 4
    assert any(r["rate_pods_per_s"] == mine["knee_pods_per_s"]
               for r in sweep["readings"])


def test_normalising_batch_bytes_by_hand():
    # 5,000 nodes, 1,000 pods: the fit lanes are kernelcost's without zones
    fit = (5000 * (8 * 8 + 4) + 1000 * 2 * 8) + (5000 * 5 * 8 + 1000 * 4)
    assert kernelcost.least_bytes_per_batch(5000, 1000, 0) == fit == 560000
    # + per node the hostname value (int32) and ipa_base (int64), + per
    # landing axis a delta row read and written (2 x int64 a node)
    assert ipacost.ipa_least_bytes_per_batch(5000, 1000, 1) == fit + 60000 + 80000
    assert ipacost.ipa_least_bytes_per_batch(5000, 1000, 2) == fit + 60000 + 160000
    assert ipacost.ipa_least_bytes_per_batch(8, 0, 0) == 8 * (68 + 40 + 12)
    share = ipacost.ipa_hbm_roofline_share(
        kernel_s=0.1, batches=5, nodes=5000, pods=1000, axes=1,
        device_kind="TPU v5 lite")
    assert share == pytest.approx(100.0 * 5 * 700000 / 819e9 / 0.1)
    with pytest.raises(KeyError):
        ipacost.ipa_hbm_roofline_share(0.1, 5, 5000, 1000, 1, "cpu")
    cfg = _config()
    assert ipacost.landing_axes(cfg["measurePods"]["template"]) == 1
    assert ipacost.landing_axes({"cpu": "100m"}) == 0
    # a term that does not select its own pod moves nothing at a landing
    other = json.loads(json.dumps(cfg["measurePods"]["template"]))
    other["labels"] = {"color": "blue"}
    assert ipacost.landing_axes(other) == 0


def test_the_roofline_reader_finds_the_cells_template(monkeypatch):
    reader = _load("layer_metrics", "ipa_scan_roofline.py")
    obs = {"traced": {"counters": {"device_batches": 10,
                                   "device_scheduled": 10000},
                      "reduced": {"modules": {
                          "jit_schedule_batch": {"seconds": 1.3, "runs": 10}}}},
           "cluster": {"nodes": 5000, "zones": 1},
           "device": {"kind": "TPU v5 lite"}}
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", PREF_CELL,
                                      "--seed", "1"])
    assert reader.read(obs) == pytest.approx(
        100.0 * 10 * 700000 / 819e9 / 1.3)
    assert 0 < reader.read(obs) < 100
    # a cell whose pods carry no preferred term, no cell, a rehearsal, no
    # batch: None
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                      "antiaffinity-5k.waves"])
    assert reader.read(obs) is None
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert reader.read(obs) is None
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", PREF_CELL])
    assert reader.read(dict(obs, device={"kind": "cpu", "rehearsal": True})) is None
    assert reader.read(dict(obs, traced={"counters": {}})) is None


def test_the_score_reader_reads_nothing_without_the_stage():
    """The parent's program has `sched.*` spans and no `plan.ipa_score`: the
    reader leaves the metric out and does not read 0."""
    reader = _load("layer_metrics", "ipa_score_share.py")
    with_stage = {"progspans": {"wave_s": 2.0, "unnamed_s": 0.0, "self_s": {
        "plan.build": 0.5, "plan.ipa_score": 0.3}}}
    assert reader.read(with_stage) == pytest.approx(15.0)
    without = {"progspans": {"wave_s": 2.0, "unnamed_s": 0.0,
                             "self_s": {"plan.build": 0.8, "plan.ipa": 0.1}}}
    assert reader.read(without) is None
    assert reader.read({"progspans": None}) is None


@pytest.mark.parametrize("after, want", [
    # 1,200 pods bound in 40 dispatches of the carried scan and 10 of the lap
    ('scheduler_device_batches_total{engine="scan_carried"} 140.0\n'
     'scheduler_device_batches_total{engine="lap"} 10.0\n'
     'scheduler_e2e_scheduling_duration_seconds_count 6200\n', 24.0),
    # hints bound every pod of the window: no batch, nothing to read
    ('scheduler_device_batches_total{engine="scan_carried"} 100.0\n'
     'scheduler_e2e_scheduling_duration_seconds_count 6200\n', None),
    # the parent counts its batches in one series without the engine
    ('scheduler_device_batches_total 150.0\n'
     'scheduler_e2e_scheduling_duration_seconds_count 6200\n', 24.0),
    ('scheduler_device_batches_total 100.0\n'
     'scheduler_e2e_scheduling_duration_seconds_count 6200\n', None),
])
def test_pods_a_device_batch_from_a_window_delta(after, want):
    reader = _load("layer_metrics", "device_batch_pods_mean.open.py")
    before = ('scheduler_device_batches_total{engine="scan_carried"} 100.0\n'
              'scheduler_e2e_scheduling_duration_seconds_count 5000\n')
    if "engine" not in after:
        before = before.replace('{engine="scan_carried"}', "")
    obs = {"prom": {"scheduler": prom.delta(prom.parse(after),
                                            prom.parse(before))}}
    got = reader.read(obs)
    assert got is None if want is None else got == pytest.approx(want)
    assert reader.read({}) is None and reader.read({"prom": {}}) is None


def test_the_served_kernel_reader_counts_runs_where_the_seconds_are():
    reader = _load("layer_metrics", "kernel_ms_per_batch.open.py")
    obs = {"device": {"kind": "TPU v5 lite"}, "traced": {"reduced": {
        "modules": {"jit_schedule_batch": {"seconds": 2.4, "runs": 50},
                    "jit_schedule_batch_lap": {"seconds": 0.1, "runs": 50},
                    "jit__scatter_rows_impl": {"seconds": 9.0, "runs": 3}}}}}
    assert reader.read(obs) == pytest.approx(25.0)
    # hints bound the window's pods, no trace, a rehearsal: nothing
    idle = {"device": {}, "traced": {"reduced": {"modules": {
        "jit__scatter_rows_impl": {"seconds": 9.0, "runs": 3}}}}}
    assert reader.read(idle) is None
    assert reader.read({}) is None and reader.read({"traced": {}}) is None
    # a rehearsal's stand-in events are one an operation: the batches are
    # the window's `/metrics` delta there
    cpu = dict(obs, device={"rehearsal": True})
    assert reader.read(cpu) is None
    cpu["prom"] = {"scheduler": prom.parse(
        'scheduler_device_batches_total{engine="scan_carried"} 10.0\n')}
    assert reader.read(cpu) == pytest.approx(250.0)


@pytest.mark.parametrize("found, waves, want", [
    # two traced waves of five normalising batches; the warm-up's and the
    # untraced waves' dispatches lie outside them
    ([[50.0, "scan_carried"]] + [[100.0 + i, "scan_normalised"]
                                 for i in range(5)]
     + [[300.0 + i, "scan_normalised"] for i in range(5)], 2, 100.0),
    ([[100.0, "scan_normalised"], [101.0, "lap"], [300.0, "scan_carried"],
      [301.0, "scan_normalised"]], 2, 50.0),
    ([[100.0, "lap"], [300.0, "scan_normalised"]], 1, 100.0),
    # the parent's spans carry no engine; no dispatch in the waves; no wave
    ([[100.0, None], [300.0, None]], 2, None),
    ([[50.0, "scan_normalised"]], 2, None),
    ([[100.0, "scan_normalised"]], 0, None),
])
def test_the_engine_share_of_the_traced_waves_dispatches(found, waves, want):
    reader = _load("layer_metrics", "scan_normalised_share.py")
    bench = [["bench.init", 0.0, 90.0], ["bench.wave", 100.0, 50.0],
             ["bench.restore", 150.0, 20.0], ["bench.wave", 300.0, 50.0]]
    got = reader.share(bench, found, waves)
    assert got is None if want is None else got == pytest.approx(want)
    assert reader.read({}) is None
    assert reader.read({"traced": {"waves": 0}}) is None


def test_the_parents_manifest_entries_are_kept_byte_for_byte():
    """The parent's entries are a prefix of every list, and in each
    `workloads` list the parent's cells keep their order: cut back to them,
    the file is the parent's, byte for byte."""
    m = json.loads(json.dumps(MANIFEST))
    for key, n in PARENT_ENTRIES.items():
        assert len(m[key]) >= n
        m[key] = m[key][:n]
    assert tuple(w["name"] for w in m["workloads"]) == PARENT_CELLS
    for e in m["end_to_end"] + m["per_layer"]:
        listed = e.get("workloads")
        if listed is None:
            continue
        kept = [w for w in listed if w in PARENT_CELLS]
        assert listed[:len(kept)] == kept      # appended, not put in between
        e["workloads"] = kept
    text = json.dumps(m, indent=1) + "\n"
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_MANIFEST


def test_what_this_pr_appended_to_the_manifest():
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells[PREF_CELL] == dict(cells[PREF_CELL], config=CONFIG,
                                    traffic="waves", chips=1)
    assert cells[OPEN_CELL] == dict(cells[OPEN_CELL], config="spread-5k",
                                    traffic="open-spread-0.8knee", chips=1)
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [] and entry["file"] == (
        "benchmark/configs/prefaffinity-5k.json")
    metrics = {m["name"]: m for m in MANIFEST["per_layer"]}
    # the cells listed under at least what is named here, and this PR's own
    # metrics with its cell first: a later PR may append to either
    assert PREF_LISTED <= _listed(PREF_CELL)
    assert OPEN_LISTED <= _listed(OPEN_CELL)
    assert metrics["ipa_score_share"]["workloads"][:1] == [PREF_CELL]
    assert metrics["ipa_scan_roofline"]["workloads"][:1] == [PREF_CELL]
    assert metrics["device_batch_pods_mean.open"]["workloads"][:1] == [
        OPEN_CELL]
    assert metrics["kernel_ms_per_batch.open"]["workloads"][:1] == [OPEN_CELL]
    assert metrics["kernel_ms_per_batch.open"]["moves"] == "bind_p50_ms"
    assert metrics["scan_normalised_share"]["workloads"][:1] == [PREF_CELL]
    assert metrics["ipa_scan_roofline"]["unit"] == "%"
    reports = {}
    for e in MANIFEST["end_to_end"]:
        for w in e.get("workloads", list(cells)):
            reports.setdefault(w, set()).add(e["name"])
    assert reports[PREF_CELL] == {"pods_per_s", "setup_s"}
    assert reports[OPEN_CELL] == {"bind_p50_ms", "bind_within_200ms_share",
                                  "setup_s"}
    for m in MANIFEST["per_layer"]:
        for w in m.get("workloads", ()):
            assert m["moves"] in reports[w], (m["name"], w)
    for w in cells.values():
        assert len(w["why"]) <= 200
