"""What PR 33 added to the benchmark, on the CPU at toy size: the seed's
headline row, `SchedulingBasic/5000Nodes_50000Pods` (scheduler_perf
`misc/performance-config.yaml:68`: plain pods at the source's templates, ten
measured pods a node), as the configuration `basic-5k-50k`; the plain
reference equal to the program's two schedulers with the restore's deletes
between waves; what `correct` sees on the row and what it does not; the cell
`basic-5k-50k.waves` through `run.py --rehearse` with every listed reader;
its traffic file held to `waves.json`; the new reader on canned
observations; and the manifest held to the parent's, entry for entry. No
timing is asserted."""

import collections
import hashlib
import importlib.util
import json
import math
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

import control  # noqa: E402
import objects  # noqa: E402
import reference  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CONFIG, CELL, TRAFFIC = "basic-5k-50k", "basic-5k-50k.waves", "waves-1traced"
CONFIG_FILE = os.path.join(BENCH, "configs", CONFIG + ".json")
# the per-layer metrics PR 33 listed the cell under: those of
# `basic-5k.waves`, the plain lap kernel's first time and share of a
# roofline, and the one new reader
LISTED = {
    "host_commit_share", "gc_pause_share", "device_wait_share",
    "hint_hit_rate", "plan_build_share", "loop_unnamed_share",
    "queue_pop_share", "inbox_drain_share", "device_dispatch_share",
    "kernel_ms_per_batch", "schedule_batch_roofline", "backlog_at_pop_mean",
    # appended by PR 38: the readers of PRs 35-37
    "commit_batch_share", "pop_run_share", "kernel_hidden_share",
    "fetch_tail_ms", "launch_gap_ms", "collector_pause_share",
    "plan_adopt_share", "cycle_self_share"}
SEEDS = (7, 3000000019)          # the driver's seeds exceed 32 signed bits
# BENCHMARK.json at the parent commit (a7e9e14): its sha256, how many
# entries each list had, and its cells
PARENT_MANIFEST = "b636acfcfb2976142f50537dc21ffaa8a475e37857ec85a13d583296aada75f8"
PARENT_ENTRIES = {"configs": 4, "workloads": 7, "end_to_end": 4,
                  "per_layer": 27}
PARENT_CELLS = ("spread-5k.waves", "basic-5k.waves", "basic-5k.served-open",
                "basic-5k.served-waves", "antiaffinity-5k.waves",
                "prefaffinity-5k.waves", "spread-5k.served-open")
# templates/node-default.yaml and templates/pod-default.yaml by value, as
# `antiaffinity-5k` and `prefaffinity-5k` state them; one zone is one
# node-tree list
NODE = {"cpu": 4, "memory": "32Gi", "pods": 110, "zones": 1}
POD = {"cpu": "100m", "memory": "500Mi"}


def _config():
    with open(CONFIG_FILE) as f:
        return json.load(f)


def _row(nodes):
    """The configuration at the row's 1 : 1 : 10 over `nodes` nodes."""
    cfg = _config()
    for group, count in (("nodes", nodes), ("initPods", nodes),
                         ("measurePods", 10 * nodes)):
        cfg[group]["count"] = count
    return cfg


def test_the_configuration_is_the_sources_row():
    cfg = _config()
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert cfg["source"].endswith(
        "misc/performance-config.yaml:68 SchedulingBasic/5000Nodes_50000Pods")
    assert entry["file"] == "benchmark/configs/basic-5k-50k.json"
    assert entry["reduced"] == cfg["reduced"] == []
    assert cfg["nodes"] == {"count": 5000, "template": NODE}
    assert cfg["initPods"] == {"count": 5000, "template": POD}
    assert cfg["measurePods"] == {"count": 50000, "template": POD}
    # hints must not bind a whole wave; the trace opens at the first
    # measured wave
    assert cfg["device_path"] == {"min_device_batches": 1}
    with open(os.path.join(BENCH, "configs", "prefaffinity-5k.json")) as f:
        assert cfg["guarantees"] == json.load(f)["guarantees"]
    assert {"nodes", "pods", "node names", "rehearse"} <= set(cfg["assumed"])
    # the rehearsal keeps the published 1 : 1 : 10, and its restore outruns
    # the program's event journal as 50,000 deletes do
    r = cfg["rehearse"]
    assert r["nodes"] == r["initPods"] and r["measurePods"] == 10 * r["nodes"]
    from kubernetes_tpu.models import TPUScheduler
    assert r["measurePods"] > TPUScheduler().journal.cap


def _scheduler(kind):
    if kind == "host":
        from kubernetes_tpu.core import Scheduler
        return Scheduler(deterministic_ties=True)
    from kubernetes_tpu.models import TPUScheduler
    sched = TPUScheduler(max_batch=128)
    # A toy restore's 600 deletes fit the journal, the hint is patched and
    # every wave after the first would be hint-bound; at 50,000 the deletes
    # outrun it and void the hint. The toy journal is outrun as well.
    sched.journal.cap = 64
    return sched


@pytest.mark.parametrize("kind", ("host", "device"))
@pytest.mark.parametrize("seed", SEEDS)
def test_basic50k_equals_the_programs_schedulers(seed, kind):
    """60 nodes of 4 cpu with one init pod each, then three waves of 600 (ten
    a node, the row's ratio) with the restore's deletes between them: every
    placement equal, pod for pod, every node going from 1 to 11 pods, and on
    the device path a wave after a restore is one plan build and five
    chained lap batches."""
    cfg = _row(60)
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

    create("initPods", [f"init-{i}" for i in range(60)])
    for w in range(3):
        pods = create("measurePods", [f"w{w}-{i}" for i in range(600)])
        got = {p.name: p.node_name for p in cs.pods.values()}
        cmp_ = reference.compare({n: expected[n] for n in got}, got)
        assert (cmp_["differing"], cmp_["unbound"]) == (0, 0), cmp_
        on = collections.Counter(got.values())
        assert len(on) == 60 and set(on.values()) == {11}
        assert ref.over_allocatable() == []
        for p in pods:
            cs.delete_pod(cs.pods[p.uid])
            ref.delete(p.name)
    if kind == "device":
        assert sched.host_path_pods == 0
        # wave 0 rides the hint the init pods' session left (the chip's
        # first warm-up wave); the restore voids it, and waves 1 and 2 are
        # one full plan build and ceil(600 / 128) chained lap batches each
        assert sched.hint_hits == 600
        assert sched.metrics.hint_cache_invalidations.value("journal_gap") == 2
        engines = sched.metrics.device_batches
        assert engines.value("lap") == sched.device_batches == 1 + 2 * 5
        assert sched.plan_rebuilds_full == 3
        assert (sched.plan_rebuilds_delta, sched.plan_rebuilds_resume) == (0, 0)


@pytest.mark.parametrize("which, differs", [
    ("stale_batch", True),
    ("last_maximum", True),
    # equal nodes of power-of-two sizes: the precision of the score path
    # moves no placement, though nodes go eleven deep
    ("int32", False),
    ("float32", False),
])
def test_what_correct_guards_on_this_row(which, differs):
    cfg = objects.load_config(CONFIG_FILE, rehearse=True)
    broken = {**control.CONTROLS, **control.READINGS}[which]
    assert control.feature_controls(cfg) == {}      # the core models all
    for seed in (11, 12):
        total, differ = control.differing(cfg, seed, broken)
        assert total == cfg["initPods"]["count"] + cfg["measurePods"]["count"]
        assert (differ > 0) is differs
        if which == "last_maximum":
            assert differ > 0.9 * total


def _load(*parts):
    """A benchmark file as a module of this test's own, loaded by path as
    `run.py` loads drivers and readers."""
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "under_test_" + parts[-1][:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_of_the_cell_reads_every_listed_metric():
    """`run.py --rehearse --trace 1` of the cell: `correct`, every listed
    reader in the line but the share of the chip's roofline (a rehearsal has
    no chip), the new one reading a backlog of pods, and per wave at least
    the device batches that the wave's pods need at `max_batch`."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--seed", "3000000019",
         "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", ())}
    assert LISTED - {"schedule_batch_roofline"} <= set(line["metrics"]) \
        <= listed
    from kubernetes_tpu.models import TPUScheduler
    pods = _config()["rehearse"]["measurePods"]
    assert line["attempted"] % pods == 0 and line["attempted"] >= pods
    assert line["metrics"]["hint_hit_rate"]["value"] == 0.0
    assert 0 < line["metrics"]["backlog_at_pop_mean"]["value"] <= pods
    need = math.ceil(pods / TPUScheduler().max_batch)
    waves = [l for l in proc.stdout.splitlines() if "] wave " in l]
    assert waves and all(
        int(l.split(" batches ")[1].split()[0]) >= need for l in waves)
    assert all("rebuilds full/delta/resume 1/0/0" in l for l in waves)


def test_the_traffic_file_is_waves_but_for_the_traced_waves():
    with open(os.path.join(BENCH, "traffic", "waves.json")) as f:
        base = json.load(f)
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        mine = json.load(f)
    assert set(mine) == set(base)
    assert {k for k in base if mine[k] != base[k]} == {"traced_waves"}
    assert (base["traced_waves"], mine["traced_waves"]) == (2, 1)


@pytest.mark.parametrize("found, waves, want", [
    # with the stat: the pops inside the last `waves` wave spans
    ([[90.0, 7], [100.0, 4000], [120.0, 2000], [310.0, 3000]], 2, 3000.0),
    ([[100.0, 4000], [120.0, 2000], [310.0, 3000]], 1, 3000.0),
    # without it (the parent of PR 33): nothing
    ([[100.0, None], [310.0, None]], 2, None),
    # no pop inside a traced wave, no traced wave
    ([[50.0, 4000], [160.0, 12]], 2, None),
    ([[100.0, 4000]], 0, None),
])
def test_the_mean_backlog_of_the_traced_waves_pops(found, waves, want):
    reader = _load("layer_metrics", "backlog_at_pop_mean.py")
    bench = [["bench.init", 0.0, 90.0], ["bench.wave", 100.0, 50.0],
             ["bench.restore", 150.0, 20.0], ["bench.wave", 300.0, 50.0]]
    got = reader.mean(bench, found, waves)
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
    assert cells[CELL] == dict(cells[CELL], config=CONFIG, traffic=TRAFFIC,
                               chips=1)
    assert len(cells[CELL]["why"]) <= 200
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["config"] == CONFIG] == [CELL]
    listed = {m["name"] for m in MANIFEST["per_layer"]
              if CELL in m.get("workloads", ())}
    assert LISTED <= listed       # a later PR may list the cell under more
    metrics = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert metrics["backlog_at_pop_mean"] == {
        "name": "backlog_at_pop_mean", "unit": "pods", "better": "higher",
        "source": "program_span", "layer": "host scheduler loop",
        "moves": "pods_per_s", "workloads": [CELL]}
    reports = {e["name"] for e in MANIFEST["end_to_end"]
               if CELL in e.get("workloads", [CELL])}
    assert reports == {"pods_per_s", "setup_s"}
    assert all(metrics[name]["moves"] in reports for name in LISTED)
