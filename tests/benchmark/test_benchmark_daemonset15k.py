"""`daemonset-15k.waves` (PR 47): the entries and the cell pinned by name, the
configuration held to the issue, the pod feature `nodeAffinity` found by name
and refused when one of its two files is missing, what `parse` refuses, the
reference's filter on a toy cluster, the cell through `run.py --rehearse`
(`correct`, every count of `compared` at its limit, `narrowed_pods_share` 100
on a traced rehearsal), the control `nodeAffinity.pin_ignored` not correct,
and the two new readers on made-up observations. No timing is asserted."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

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
import kernelcost  # noqa: E402
import objects  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

CELL = "daemonset-15k.waves"
CONFIG = "daemonset-15k"
NAMED = "scheduler-perf-node"
NEW_METRICS = {
    "narrowed_batch_roofline": ("%", "higher", "device_trace", "kernels"),
    "narrowed_pods_share": ("%", "higher", "program_span", "device pipeline"),
}
# the accepted per-layer metrics that list every `.waves` cell and read here
ALSO_UNDER = {
    "host_commit_share", "gc_pause_share", "device_wait_share",
    "hint_hit_rate", "plan_build_share", "kernel_ms_per_batch",
    "loop_unnamed_share", "queue_pop_share", "inbox_drain_share",
    "device_dispatch_share", "commit_batch_share", "kernel_hidden_share",
    "fetch_tail_ms", "launch_gap_ms", "collector_pause_share",
    "plan_adopt_share", "cycle_self_share", "pop_run_share"}
# `backlog_at_pop_mean`'s list an accepted test holds to one cell;
# `schedule_batch_roofline` counts every row of the cluster a batch, which a
# batch over one named row has no need to move: it would read over 100 %
NOT_UNDER = {"backlog_at_pop_mean", "schedule_batch_roofline"}
SEEDS = (7, 3000000019)           # the driver's seeds exceed 32 signed bits
PIN = {"requiredDuringSchedulingIgnoredDuringExecution": {
    "nodeSelectorTerms": [{"matchFields": [
        {"key": "metadata.name", "operator": "In", "values": [NAMED]}]}]}}


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(name):
    return _module(os.path.join(BENCH, "layer_metrics", name + ".py"),
                   "daemonset_reader_" + name)


def _config(rehearse=True):
    return objects.load_config(
        os.path.join(BENCH, "configs", CONFIG + ".json"), rehearse)


# -- the manifest: what this PR appended, by name ----------------------------

def test_the_cell_its_configuration_and_the_two_entries_are_appended():
    configs = [c["name"] for c in MANIFEST["configs"]]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    metrics = [m["name"] for m in MANIFEST["per_layer"]]
    # after the parent's last, wherever a later PR has put its own
    assert configs.index(CONFIG) > configs.index("preempt-5k")
    assert cells.index(CELL) > cells.index("preempt-5k.waves")
    for name in NEW_METRICS:
        assert metrics.index(name) > metrics.index("nomination_rebuild_share")
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    assert cfg["source"] == (
        "kubernetes test/integration/scheduler_perf/misc/"
        "performance-config.yaml:119 SchedulingDaemonset/15000Nodes")
    assert cfg["reduced"] == [] and len(cfg["why"]) <= 200
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "waves-1traced", 1)
    assert len(cell["why"]) <= 200
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
    for name in NOT_UNDER:
        assert CELL not in by_name[name]["workloads"], name
    # a cell is appended to a list, never put in between
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        lists = m.get("workloads", [])
        if CELL in lists and "preempt-5k.waves" in lists:
            assert lists.index(CELL) > lists.index("preempt-5k.waves")
    # every cell takes one chip
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])
    # the harness finds the cell's files by these names: no new driver and
    # no new traffic file
    found = run.find_cell(BENCH, MANIFEST, CELL)
    assert found["driver_path"].endswith("drivers/waves.py")
    assert found["traffic"]["warmup_waves"] == 2
    assert found["traffic"]["traced_waves"] == 1
    assert os.path.isfile(found["config_path"])
    assert {m["name"] for m in found["per_layer"]} == (
        ALSO_UNDER | set(NEW_METRICS))
    assert {m["name"] for m in found["end_to_end"]} == {"pods_per_s",
                                                         "setup_s"}


def test_the_configuration_is_the_sources_with_nothing_reduced():
    cfg = _config(rehearse=False)
    assert cfg["reduced"] == []
    named, rest = cfg["nodes"]
    assert named == {"count": 1, "name": NAMED, "template": {
        "cpu": 4, "memory": "32Gi", "pods": 90000, "zones": 1}}
    assert rest == {"count": 15000, "template": {
        "cpu": 4, "memory": "32Gi", "pods": 110, "zones": 1}}
    assert cfg["initPods"]["count"] == 0
    assert cfg["measurePods"]["count"] == 30000
    for group in ("initPods", "measurePods"):
        # the pin and a pause container with NO resource requests
        assert cfg[group]["template"] == {"nodeAffinity": PIN}
    assert cfg["device_path"] == {"min_device_batches": 1}
    assert any("start index after a pinned pod is not compared" in g
               for g in cfg["guarantees"])
    assert any(g.startswith("every measured pod bound exactly once, to "
                            + NAMED) for g in cfg["guarantees"])
    for key in ("provenance", "named node", "nodes", "measurePods",
                "start index", "threshold"):
        assert key in cfg["assumed"], key
    assert "as remembered" in cfg["assumed"]["provenance"]
    assert "1,100" in cfg["assumed"]["threshold"]
    assert cfg["rehearse"] == {"nodes": [1, 420], "initPods": 0,
                               "measurePods": 4200}
    # the cluster: 15,001 nodes, the named one among them wherever the seed
    # puts it, every other `node-<i>`
    nodes = objects.cluster(cfg, 3000000019)
    assert len(nodes) == 15001
    assert sum(n["name"] == NAMED for n in nodes) == 1
    big = next(n for n in nodes if n["name"] == NAMED)
    assert (big["cpu"], big["pods"]) == (4000, 90000)
    assert {n["pods"] for n in nodes if n["name"] != NAMED} == {110}
    assert nodes != objects.cluster(cfg, 7)


# -- the pod feature, found by name -------------------------------------------

def test_the_feature_pair_is_found_by_name_and_refused_when_one_is_missing(
        tmp_path):
    assert features.load("reference", "nodeAffinity") is not None
    assert features.load("objects", "nodeAffinity") is not None
    for side, other in features.SIDES.items():
        bench = tmp_path / side
        os.makedirs(bench / features.SIDES[side])
        shutil.copy(os.path.join(BENCH, features.SIDES[side],
                                 "nodeAffinity.py"),
                    bench / features.SIDES[side] / "pinned.py")
        with pytest.raises(features.Unpaired):
            features.load("reference", "pinned", str(bench))
    # the program's pod carries the same terms
    pod = objects.make_pod_prototype({"nodeAffinity": PIN})
    import kubernetes_tpu.core  # noqa: F401  (the package's import order)
    from kubernetes_tpu.plugins.basic import NodeAffinity
    assert NodeAffinity.narrowed_node_names(pod) == {NAMED}
    assert pod.resource_request().is_zero()
    two = objects.make_pod_prototype({"nodeAffinity": {
        "requiredDuringSchedulingIgnoredDuringExecution": {
            "nodeSelectorTerms": [
                {"matchFields": [{"key": "metadata.name", "operator": "In",
                                  "values": ["a", "b"]}]},
                {"matchFields": [{"key": "metadata.name", "operator": "In",
                                  "values": ["c"]}]}]}}})
    assert NodeAffinity.narrowed_node_names(two) == {"a", "b", "c"}


def _required(*terms):
    return {"requiredDuringSchedulingIgnoredDuringExecution": {
        "nodeSelectorTerms": list(terms)}}


def _fields(values, key="metadata.name", operator="In"):
    return {"matchFields": [{"key": key, "operator": operator,
                             "values": values}]}


@pytest.mark.parametrize("value", [
    {"preferredDuringSchedulingIgnoredDuringExecution": []},
    dict(PIN, preferredDuringSchedulingIgnoredDuringExecution=[]),
    _required(),
    _required({"matchExpressions": [{"key": "disk", "operator": "In",
                                     "values": ["ssd"]}]}),
    _required(dict(_fields(["a"]), matchExpressions=[])),
    _required(_fields(["a"], operator="NotIn")),
    _required(_fields(["a"], key="metadata.namespace")),
    _required(_fields([])),
    _required({"matchFields": [_fields(["a"])["matchFields"][0]] * 2}),
    "scheduler-perf-node",
], ids=["preferred", "preferred_beside", "no_term", "expressions",
        "expressions_beside", "operator", "field", "no_value",
        "two_requirements", "a_string"])
def test_parse_refuses_what_it_does_not_model(value):
    module = features.load("reference", "nodeAffinity")
    with pytest.raises(reference.Unmodelled):
        module.parse(value, {"nodeAffinity": value})
    assert module.parse(PIN, {}) == frozenset({NAMED})


def test_the_references_filter_is_the_named_rows_and_leaves_the_index():
    cfg = _config()
    nodes = objects.cluster(cfg, 7)
    ref = reference.Reference(nodes)
    pinned, plain = {"nodeAffinity": PIN}, {"cpu": "100m", "memory": "100Mi"}
    ref.start = 5
    for i in range(3):
        assert ref.schedule(f"ds-{i}", pinned) == NAMED
    assert ref.start == 5      # the pin speaks through the mask: (5 + n) % n
    state = ref._states["nodeAffinity"]
    mask = state.feasible(ref._shape(pinned))
    assert mask.sum() == 1 and ref.names[int(np.flatnonzero(mask)[0])] == NAMED
    assert state.feasible(ref._shape(plain)) is None
    assert state.score(ref._shape(pinned), np.arange(3)) is None
    assert ref.schedule("plain-0", plain) != NAMED
    # a pin to a node that is not there finds no node
    lost = {"nodeAffinity": _required(_fields(["no-such-node"]))}
    with pytest.raises(reference.Unschedulable):
        ref.schedule("lost", lost)


def test_control_py_shows_the_pin_ignored_as_not_correct(capsys):
    cfg = _config()
    found = control.feature_controls(cfg)
    assert set(found) == {"nodeAffinity.pin_ignored"}
    for seed in SEEDS:
        total, differ = control.differing(cfg, seed, found[
            "nodeAffinity.pin_ignored"])
        assert total == 4200 and differ > 0.9 * total
        # one feasible node a pod, nothing to score: the core's controls
        # read 0 here, and `control.py` says so (exit code 1)
        assert control.differing(cfg, seed, control.CONTROLS[
            "last_maximum"]) == (4200, 0)
    rc = control.main(["--config", CONFIG, "--seeds", "7", "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "control nodeAffinity.pin_ignored config daemonset-15k seed 7" in out
    assert "control nodeAffinity.pin_ignored placed every pod" not in out
    assert "control last_maximum placed every pod" in out


# -- the cell through the front door ------------------------------------------

def _rehearse(seed, trace=0, seconds=1):
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
           "--seed", str(seed), "--seconds", str(seconds), "--trace",
           str(trace), "--rehearse"]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


GUARDS = {"placements_differing", "pods_unbound", "pods_unexpected",
          "evictions_differing", "nominations_differing",
          "nodes_over_allocatable", "compiles_in_window", "host_path_pods",
          "breaker_charges", "failed_attempts"}


def _holds(line, out):
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == GUARDS
    for name, c in line["compared"].items():
        assert c["value"] == c["limit"] == 0, name
    assert line["failed"] == 0 and line["attempted"] >= 4200
    waves = [ln for ln in out.splitlines()
             if "] wave " in ln or "warm-up wave" in ln]
    assert len(waves) >= 3
    for ln in waves:
        assert "4200/4200 bound" in ln, ln
    for ln in waves[2:]:
        # the restore outruns the journal: a full build and five lap
        # batches over the one named row, no hint
        assert "batches 5 hints 0 rebuilds full/delta/resume 1/0/0" in ln, ln
    assert "cluster: 421 nodes, 0 init pods bound" in out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_rehearsal_is_correct_and_no_pod_takes_the_host_path(seed):
    line, out = _rehearse(seed)
    _holds(line, out)
    assert line["metrics"]["setup_s"]["value"] > 0
    assert "pods_per_s" in line["metrics"]


def test_a_traced_rehearsal_reads_narrowed_pods_share_and_no_roofline():
    line, out = _rehearse(11, trace=1, seconds=2)
    _holds(line, out)
    got = line["metrics"]
    assert got["narrowed_pods_share"]["value"] == 100.0
    assert got["pop_run_share"]["value"] > 99.0
    assert got["commit_batch_share"]["value"] == 100.0
    # a rehearsal has no chip and reads no roofline
    assert "narrowed_batch_roofline" not in got
    assert "schedule_batch_roofline" not in got
    assert set(got) <= ALSO_UNDER | set(NEW_METRICS)
    assert any(ln.startswith("[timeline]") and "NOT joined" not in ln
               and " lap 1024:" in ln for ln in out.splitlines())


# -- the two readers on made-up observations ----------------------------------

BENCH_SPANS = [["bench.init", 0.0, 90.0], ["bench.wave", 100.0, 50.0],
               ["bench.restore", 150.0, 20.0], ["bench.wave", 300.0, 50.0]]


@pytest.mark.parametrize("pods, narrowed, waves, want", [
    # a wave of pinned pods: every pod its pops took
    ([[100.0, 1024], [120.0, 976], [310.0, 2000]],
     [[100.0, 1024], [120.0, 976], [310.0, 2000]], 2, 100.0),
    # pinned and plain sessions in one wave
    ([[310.0, 600], [320.0, 400]], [[310.0, 600], [320.0, 0]], 1, 60.0),
    ([[310.0, 600]], [[310.0, 0]], 1, 0.0),
    # without the stat (the parent of PR 47): nothing
    ([[310.0, 600]], [[310.0, None]], 1, None),
    # pops that took no pod, no pop inside a traced wave, no traced wave
    ([[310.0, 0]], [[310.0, 0]], 1, None),
    ([[50.0, 16]], [[50.0, 16]], 2, None),
    ([[100.0, 16]], [[100.0, 16]], 0, None),
])
def test_narrowed_pods_share_is_the_pops_stat_over_their_pods(
        pods, narrowed, waves, want):
    got = _reader("narrowed_pods_share").share(
        BENCH_SPANS, pods, narrowed, waves)
    assert got is None if want is None else got == pytest.approx(want)


def test_narrowed_batch_roofline_counts_the_named_rows_and_cannot_pass_100():
    reader = _reader("narrowed_batch_roofline")
    kind = "TPU v5 lite"
    peak = kernelcost.peaks(kind)["hbm_bytes_per_s"]
    rows, pods = [1] * 30, [1024.0] * 29 + [304.0]
    least = sum(kernelcost.least_bytes_per_batch(1, p, 0) for p in pods)
    # the kernel time IS the least time: 100, and any longer reads less
    assert reader.share(least / peak, rows, pods, kind) == pytest.approx(100.0)
    assert reader.share(50 * least / peak, rows, pods, kind) \
        == pytest.approx(2.0)
    # counted by `narrowed_rows`: not by the padded plan rows (64), not by
    # the cluster's (15,001), either of which would read far over 100
    assert least == 30 * (8 * 8 + 4 + 5 * 8) + sum(pods) * (2 * 8 + 4)
    assert kernelcost.least_bytes_per_batch(64, 1024, 0) > 1.2 * (least / 30)
    assert kernelcost.least_bytes_per_batch(15001, 1024, 0) > 70 * (least / 30)
    with pytest.raises(KeyError):
        reader.share(1.0, rows, pods, "cpu")


def test_narrowed_batch_roofline_reads_nothing_without_the_stat(monkeypatch):
    reader = _reader("narrowed_batch_roofline")
    obs = {"traced": {"waves": 1, "counters": {"device_batches": 2},
                      "reduced": {"modules": {"jit_schedule_batch": {
                          "seconds": 0.01, "runs": 2}}}},
           "device": {"kind": "TPU v5 lite"}}
    said = {}
    monkeypatch.setattr(reader.spanstats, "this_runs",
                        lambda obs, name, key: said.get(key))
    # no trace of this run to load
    assert reader.read(obs) is None
    # a program whose dispatch spans carry no `narrowed_rows` (the parent;
    # a plan that is not narrowed)
    said.update(narrowed_rows=(BENCH_SPANS, [[310.0, None], [320.0, None]]),
                batch=(BENCH_SPANS, [[310.0, 1024], [320.0, 1024]]))
    assert reader.read(obs) is None
    said["narrowed_rows"] = (BENCH_SPANS, [[310.0, 1], [320.0, 1]])
    want = reader.share(0.01, [1, 1], [1024, 1024], "TPU v5 lite")
    assert 0 < want < 100 and reader.read(obs) == pytest.approx(want)
    # a rehearsal has no chip, a run without scheduling programs no time
    assert reader.read(dict(obs, device={"kind": "cpu",
                                         "rehearsal": True})) is None
    assert reader.read(dict(obs, traced={"waves": 1, "counters": {}})) is None
