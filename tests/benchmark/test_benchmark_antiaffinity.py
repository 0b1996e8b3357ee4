"""What PR 27 added to the benchmark, on the CPU at toy size: the
configuration `antiaffinity-5k` (required hostname anti-affinity over two
namespaces) equal to the program's two schedulers with the restore's deletes
between waves, its controls, both new cells through `run.py --rehearse` with
every listed reader, the cost function of a batch with an anti lane against a
hand count, the closed loop's reading of a `/metrics` delta, and the manifest
held to the parent's, entry for entry. No timing is asserted."""

import hashlib
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
RUN = os.path.join(BENCH, "run.py")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import anticost  # noqa: E402
import control  # noqa: E402
import kernelcost  # noqa: E402
import objects  # noqa: E402
import prom  # noqa: E402
import reference  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CONFIG = "antiaffinity-5k"
NEW_CELLS = ("basic-5k.served-waves", "antiaffinity-5k.waves")
# the per-layer metrics PR 27 listed each new cell under
ANTI_LISTED = {
    "ipa_tables_share", "anti_lap_roofline", "kernel_ms_per_batch",
    "host_commit_share", "gc_pause_share", "device_wait_share",
    "plan_build_share", "hint_hit_rate", "queue_pop_share",
    "inbox_drain_share", "loop_unnamed_share", "device_dispatch_share"}
SERVED_LISTED = {
    "host_commit_share", "hint_hit_rate", "plan_build_share",
    "device_wait_share", "gc_pause_share", "queue_pop_share",
    "inbox_drain_share", "loop_unnamed_share"}
SEEDS = (7, 3000000019)          # the driver's seeds exceed 32 signed bits
# BENCHMARK.json at the parent commit (99dcdd4): its sha256, how many
# entries each list had, and its cells
PARENT_MANIFEST = "a22260c3e209289a77f152276246835300157e8dd7cde9315a2f05474ba63991"
PARENT_ENTRIES = {"configs": 2, "workloads": 3, "end_to_end": 4,
                  "per_layer": 19}
PARENT_CELLS = ("spread-5k.waves", "basic-5k.waves", "basic-5k.served-open")


def _load(*parts):
    """A benchmark file as a module of this test's own, loaded by path as
    `run.py` loads drivers and readers."""
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "under_test_" + parts[-1][:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config():
    return objects.load_config(
        os.path.join(BENCH, "configs", CONFIG + ".json"), rehearse=True)


def _scheduler(kind):
    if kind == "host":
        from kubernetes_tpu.core import Scheduler
        return Scheduler(deterministic_ties=True)
    from kubernetes_tpu.models import TPUScheduler
    return TPUScheduler()


@pytest.mark.parametrize("kind", ("host", "device"))
@pytest.mark.parametrize("seed", SEEDS)
def test_antiaffinity_equals_the_programs_schedulers(seed, kind):
    """Init pods in `sched-0`, then three waves in `sched-1` with the
    restore's deletes of term-carrying pods between them: every placement
    equal, pod for pod, and no two green pods on one node."""
    cfg = _config()
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

    create("initPods", [f"init-{i}" for i in range(cfg["initPods"]["count"])])
    for w in range(3):
        pods = create("measurePods", [
            f"w{w}-{i}" for i in range(cfg["measurePods"]["count"])])
        got = {p.name: p.node_name for p in cs.pods.values()}
        cmp_ = reference.compare({n: expected[n] for n in got}, got)
        assert (cmp_["differing"], cmp_["unbound"]) == (0, 0), cmp_
        assert len(set(got.values())) == len(got)      # the guarantee itself
        assert ref.over_allocatable() == []
        for p in pods:
            cs.delete_pod(cs.pods[p.uid])
            ref.delete(p.name)
    if kind == "device":
        assert sched.host_path_pods == 0
        # one full plan build a wave (a delete of a term-carrying pod voids
        # the plan: `exist_anti` has to fall back), each with an anti lane
        # on the lap path
        lanes = sched.metrics.plan_anti_lane
        assert lanes.value("true") >= 3 and lanes.value("false") == 0
        assert sched.stages.counts["plan.ipa"] >= 3


def test_the_configuration_is_the_sources_row():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    assert (cfg["nodes"]["count"], cfg["initPods"]["count"],
            cfg["measurePods"]["count"]) == (5000, 1000, 2000)
    assert cfg["reduced"] == [] and cfg["device_path"]["min_device_batches"] == 1
    init, measured = cfg["initPods"]["template"], cfg["measurePods"]["template"]
    assert (init["namespace"], measured["namespace"]) == ("sched-0", "sched-1")
    # one pod template in the source: labels and term are the same on both
    for key in ("labels", "podAntiAffinity", "cpu", "memory"):
        assert init[key] == measured[key]
    (term,) = init["podAntiAffinity"][anticost.REQUIRED]
    assert term == {"labelSelector": {"matchLabels": {"color": "green"}},
                    "topologyKey": "kubernetes.io/hostname",
                    "namespaces": ["sched-1", "sched-0"]}
    # the source's own templates (node-default.yaml, pod-with-pod-anti-
    # affinity.yaml), each stated with its provenance under `assumed`
    assert cfg["nodes"]["template"] == {"cpu": 4, "memory": "32Gi",
                                        "pods": 110, "zones": 1}
    assert (measured["cpu"], measured["memory"]) == ("100m", "500Mi")
    assert measured["labels"] == {"color": "green", "name": "test"}
    assert {"nodes", "pods"} <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == 4
    # the adaptive sample still matters at the rehearsal's size: a wave
    # starts with more feasible nodes than a pod looks for, and ends with
    # fewer, so both forms of the walk are met
    toy = cfg["rehearse"]
    to_find = reference.num_feasible_nodes_to_find(toy["nodes"])
    assert (toy["nodes"] - toy["initPods"] - toy["measurePods"] < to_find
            < toy["nodes"] - toy["initPods"])


@pytest.mark.parametrize("which, differs", [
    ("podAntiAffinity.filter_dropped", True),
    ("last_maximum", True),
    # every pod lands on an empty node and all candidates tie: the score
    # controls read 0 here; both templates name both namespaces, so the
    # incoming pod's own term already refuses what the symmetric half would
    ("podAntiAffinity.symmetric_half_dropped", False),
    ("int32", False),
    ("stale_batch", False),
])
def test_what_correct_guards_on_this_configuration(which, differs):
    cfg = _config()
    broken = {**control.CONTROLS, **control.feature_controls(cfg)}[which]
    total, differ = control.differing(cfg, 11, broken)
    assert total == cfg["initPods"]["count"] + cfg["measurePods"]["count"]
    assert (differ > 0) is differs


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


def test_rehearsal_of_the_antiaffinity_cell_reads_every_listed_metric():
    line, out = _run(["--workload", "antiaffinity-5k.waves", "--seed",
                      "3000000019", "--seconds", "1", "--trace", "1",
                      "--rehearse"])
    assert line["correct"] is True and line["failed"] == 0
    # every reader PR 27 listed the cell under returns a number, but the
    # share of the chip's roofline, which needs the chip: None in a rehearsal
    assert ANTI_LISTED - {"anti_lap_roofline"} <= set(line["metrics"])
    assert set(line["metrics"]) <= _listed("antiaffinity-5k.waves")
    assert "anti_lap_roofline" not in line["metrics"]
    assert line["metrics"]["ipa_tables_share"]["value"] > 0
    assert line["metrics"]["hint_hit_rate"]["value"] == 0
    assert line["metrics"]["kernel_ms_per_batch"]["value"] > 0
    assert "'plan.ipa'" in out           # the [progspans] line names the stage
    compared = line["compared"]
    assert compared["host_path_pods"]["value"] == 0
    assert "device_batches_short_of_minimum" not in compared


def test_rehearsal_of_the_served_waves_cell():
    line, out = _run(["--workload", "basic-5k.served-waves", "--seed", "11",
                      "--seconds", "1", "--trace", "0", "--rehearse"])
    assert line["correct"] is True and line["failed"] == 0
    assert {"pods_per_s", "setup_s"} <= set(line["metrics"])
    assert line["attempted"] >= 400 and line["attempted"] % 400 == 0
    assert {"host_path_pods", "breaker_charges", "breaker_open"} <= set(
        line["compared"])
    line, out = _run(["--workload", "basic-5k.served-waves", "--seed", "12",
                      "--seconds", "1", "--trace", "1", "--rehearse"])
    assert line["correct"] is True
    assert SERVED_LISTED <= set(line["metrics"]) <= _listed(
        "basic-5k.served-waves")
    # hints bind every pod of a wave; the kernel placed the init pods only,
    # which the trace holds (the configuration is traced from them on)
    assert line["metrics"]["hint_hit_rate"]["value"] == 100.0
    assert line["metrics"]["host_commit_share"]["value"] > 0
    # the traced wave is a `bench.wave` span, so the program's own stages
    # are read inside it; the collector's clock ran beside it
    assert "'hint.walk'" in out and "1 traced wave(s)" in out
    assert line["metrics"]["inbox_drain_share"]["value"] > 0
    assert line["metrics"]["gc_pause_share"]["value"] >= 0
    assert line["device"]["busy_s"] > 0


def test_the_closed_loops_counters_are_the_programs_views():
    """`closed.counters_from`: the in-process counters' names over a
    /metrics delta, stage seconds summed as TPUScheduler's views sum them."""
    closed = _load("drivers", "closed.py")
    series = prom.parse("\n".join([
        'scheduler_loop_stage_seconds_total{stage="plan.build"} 2.0',
        'scheduler_loop_stage_seconds_total{stage="plan.ipa"} 0.5',
        'scheduler_loop_stage_seconds_total{stage="host.commit"} 3.0',
        'scheduler_loop_stage_seconds_total{stage="bind.post"} 4.0',
        'scheduler_loop_stage_seconds_total{stage="device.wait"} 0.25',
        'scheduler_hint_cache_hits_total{reason="exact"} 7',
        'scheduler_hint_cache_hits_total{reason="neutral"} 2',
        'scheduler_host_path_pods_total 0']))
    got = closed.counters_from(series)
    assert got == {"plan_build_s": 2.5, "device_wait_s": 0.25,
                   "host_commit_s": 7.0, "hint_hits": 9.0,
                   "host_path_pods": 0.0}
    # a program without the stage table reads nothing, not zero
    assert closed.counters_from({}) == {}


def test_the_closed_loop_waits_for_the_watch_to_catch_up():
    """The apiserver's count of binds runs ahead of the watch: a wave it
    calls bound is read off the client's watch only once every event is
    there (asked once, a rehearsal in ten lost a pod)."""
    closed = _load("drivers", "closed.py")
    dumps = [{"bound_at": {"a": 1.0}}, {"bound_at": {"a": 1.0}},
             {"bound_at": {"a": 1.0, "b": 2.0}}]
    asked = []

    def watched():
        asked.append(1)
        return dumps[min(len(asked), len(dumps)) - 1]

    assert closed.events_of(watched, ["a", "b"], 5.0) == dumps[2]
    assert len(asked) == 3
    # a wave the apiserver does not call bound is read once, as it is
    del asked[:]
    assert closed.events_of(watched, ["a", "b"], 0.0) == dumps[0]
    assert len(asked) == 1
    # an event that never comes is waited for no longer than the lag
    assert closed.events_of(lambda: dumps[0], ["a", "b"], 0.05) == dumps[0]


def test_anti_lane_bytes_by_hand():
    # 5,000 nodes, 1,000 pods: the fit lanes are kernelcost's without zones
    fit = (5000 * (8 * 8 + 4) + 1000 * 2 * 8) + (5000 * 5 * 8 + 1000 * 4)
    assert kernelcost.least_bytes_per_batch(5000, 1000, 0) == fit == 560000
    # + per node the hostname value and exist_anti (2 x int32), + per term a
    # count row read and written (2 x int32 a node)
    assert anticost.anti_least_bytes_per_batch(5000, 1000, 1) == fit + 40000 + 40000
    assert anticost.anti_least_bytes_per_batch(5000, 1000, 3) == fit + 40000 + 120000
    assert anticost.anti_least_bytes_per_batch(8, 0, 0) == 8 * (68 + 40 + 8)
    share = anticost.anti_hbm_roofline_share(
        kernel_s=0.01, batches=2, nodes=5000, pods=1000, terms=1,
        device_kind="TPU v5 lite")
    assert share == pytest.approx(100.0 * 2 * 640000 / 819e9 / 0.01)
    with pytest.raises(KeyError):
        anticost.anti_hbm_roofline_share(0.01, 2, 5000, 1000, 1, "cpu")
    cfg = _config()
    assert anticost.required_anti_terms(cfg["measurePods"]["template"]) == 1
    assert anticost.required_anti_terms({"cpu": "100m"}) == 0


def test_the_roofline_reader_finds_the_cells_template(monkeypatch):
    reader = _load("layer_metrics", "anti_lap_roofline.py")
    obs = {"traced": {"counters": {"device_batches": 4,
                                   "device_scheduled": 4000},
                      "reduced": {"modules": {
                          "jit_schedule_batch": {"seconds": 0.04, "runs": 4}}}},
           "cluster": {"nodes": 5000, "zones": 50},
           "device": {"kind": "TPU v5 lite"}}
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                      "antiaffinity-5k.waves", "--seed", "1"])
    assert reader.read(obs) == pytest.approx(
        100.0 * 4 * 640000 / 819e9 / 0.04)
    assert 0 < reader.read(obs) < 100
    # a cell whose pods carry no term, no cell, a rehearsal, no batch: None
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "basic-5k.waves"])
    assert reader.read(obs) is None
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert reader.read(obs) is None
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                      "antiaffinity-5k.waves"])
    assert reader.read(dict(obs, device={"kind": "cpu", "rehearsal": True})) is None
    assert reader.read(dict(obs, traced={"counters": {}})) is None


def test_the_ipa_reader_reads_nothing_without_the_stage():
    """The parent's program has `sched.*` spans and no `plan.ipa`: the
    reader leaves the metric out and does not read 0."""
    reader = _load("layer_metrics", "ipa_tables_share.py")
    with_stage = {"progspans": {"wave_s": 2.0, "unnamed_s": 0.0, "self_s": {
        "plan.build": 0.5, "plan.ipa": 0.1}}}
    assert reader.read(with_stage) == pytest.approx(5.0)
    without = {"progspans": {"wave_s": 2.0, "unnamed_s": 0.0,
                             "self_s": {"plan.build": 0.6}}}
    assert reader.read(without) is None
    assert reader.read({"progspans": None}) is None


def test_the_parents_manifest_entries_are_kept_byte_for_byte():
    """The parent's entries are a prefix of every list, and in each
    `workloads` list the parent's cells keep their order: cut back to them,
    the file is the parent's, byte for byte. What this or any later PR
    appends (a configuration, a cell, a metric, a cell's name at the end of
    a `workloads` list) is not this test's to hold."""
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


def test_every_listed_cell_reports_the_metric_that_is_moved():
    """The manifest's rule that kept `basic-5k.served-waves` off the served
    metrics whose `moves` it does not report (PERF.md section 7)."""
    reports = {}
    for e in MANIFEST["end_to_end"]:
        for w in e.get("workloads", [c["name"] for c in MANIFEST["workloads"]]):
            reports.setdefault(w, set()).add(e["name"])
    for m in MANIFEST["per_layer"]:
        for w in m.get("workloads", ()):
            assert m["moves"] in reports[w], (m["name"], w)
    for cell in NEW_CELLS:
        assert {"pods_per_s", "setup_s"} <= reports[cell]
