"""`prefaffinity-pools-5k.waves` (PR 50): the entries and the cell pinned by
name, the configuration held to the issue (the pods `prefaffinity-5k`'s key
for key, four node pools and what a node of each holds), the cell through
`run.py --rehearse` (`correct`, every count of `compared` at its limit, every
listed reader reading or reading nothing for a stated reason,
`plan_node_shapes` 4.0 on a traced rehearsal), what the controls see at toy
size and what they do not, the program with its inter-pod normalise put back
to an integer floor still `correct` here (so this file says, and not only a
README, that the cell does not guard that form), and the new reader on
made-up observations. No timing is asserted."""

import importlib.util
import json
import os
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
import ipacost  # noqa: E402
import objects  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

CELL = "prefaffinity-pools-5k.waves"
CONFIG = "prefaffinity-pools-5k"
TWIN_CELL, TWIN = "prefaffinity-5k.waves", "prefaffinity-5k"
NEW_METRIC = "plan_node_shapes"
# the accepted per-layer metrics that list `prefaffinity-5k.waves`: the new
# cell shares every line of that path, so it stands under each of them
ALSO_UNDER = {
    "host_commit_share", "gc_pause_share", "device_wait_share",
    "hint_hit_rate", "plan_build_share", "kernel_ms_per_batch",
    "loop_unnamed_share", "queue_pop_share", "inbox_drain_share",
    "device_dispatch_share", "ipa_score_share", "ipa_scan_roofline",
    "scan_normalised_share", "commit_batch_share", "kernel_hidden_share",
    "fetch_tail_ms", "launch_gap_ms", "scan_step_us",
    "collector_pause_share", "plan_adopt_share", "cycle_self_share",
    "pop_run_share"}
# what a rehearsal cannot read, and why: no chip, so no share of a roofline
NOT_IN_A_REHEARSAL = {"ipa_scan_roofline"}
SEEDS = (7, 3000000019)           # the driver's seeds exceed 32 signed bits
# milli cpu, memory in Mi, pods, and the 100m / 500Mi pods a node holds
POOLS = [("3920m", "13621Mi", 110, 27, "memory"),
         ("7910m", "29022Mi", 110, 58, "memory"),
         ("15890m", "59824Mi", 110, 110, "pods"),
         ("31850m", "121428Mi", 110, 110, "pods")]


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name=CONFIG, rehearse=True):
    return objects.load_config(
        os.path.join(BENCH, "configs", name + ".json"), rehearse)


# -- the manifest: what this PR appended, by name ----------------------------

def test_the_cell_its_configuration_and_the_entry_are_appended():
    configs = [c["name"] for c in MANIFEST["configs"]]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    metrics = [m["name"] for m in MANIFEST["per_layer"]]
    # after the parent's last, wherever a later PR has put its own
    assert configs.index(CONFIG) > configs.index("daemonset-15k")
    assert cells.index(CELL) > cells.index("daemonset-15k.waves")
    assert metrics.index(NEW_METRIC) > metrics.index("narrowed_pods_share")
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    assert cfg["source"] == (
        "kubernetes test/integration/scheduler_perf/affinity/"
        "performance-config.yaml:175 SchedulingPreferredPodAffinity/"
        "5000Nodes_5000Pods; nodes: GKE docs 'Node allocatable resources', "
        "e2-standard-4/8/16/32")
    assert len(cfg["source"]) <= 200
    assert cfg["source"] == _config(rehearse=False)["source"]
    # two deployments from one public benchmark need sources that differ
    twin = next(c for c in MANIFEST["configs"] if c["name"] == TWIN)
    assert cfg["source"] != twin["source"] and cfg["file"] != twin["file"]
    assert cfg["reduced"] == [] and len(cfg["why"]) <= 200
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "waves", 1)
    assert len(cell["why"]) <= 200
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    m = by_name[NEW_METRIC]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "shapes", "higher", "program_span", "feature build and mirror",
        "pods_per_s")
    assert CELL in m["workloads"]
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                       NEW_METRIC + ".py"))
    pods_per_s = next(m for m in MANIFEST["end_to_end"]
                      if m["name"] == "pods_per_s")
    assert CELL in pods_per_s["workloads"]
    # under every metric that lists its twin, and under no other
    for m in MANIFEST["per_layer"]:
        if m["name"] != NEW_METRIC and TWIN_CELL in m.get("workloads", ()):
            assert m["name"] in ALSO_UNDER, m["name"]
    for name in ALSO_UNDER:
        assert CELL in by_name[name]["workloads"], name
        assert TWIN_CELL in by_name[name]["workloads"], name
    # a cell is appended to a list, never put in between
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        lists = m.get("workloads", [])
        if CELL in lists and "daemonset-15k.waves" in lists:
            assert lists.index(CELL) > lists.index("daemonset-15k.waves")
        if CELL in lists and TWIN_CELL in lists:
            assert lists.index(CELL) > lists.index(TWIN_CELL)
    # every cell takes one chip
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])
    # the harness finds the cell's files by these names: the driver and the
    # traffic file that are there
    found = run.find_cell(BENCH, MANIFEST, CELL)
    assert found["driver_path"].endswith("drivers/waves.py")
    assert found["traffic"]["warmup_waves"] == 2
    assert found["traffic"]["traced_waves"] == 2
    assert found["traffic"] == run.find_cell(BENCH, MANIFEST,
                                             TWIN_CELL)["traffic"]
    assert os.path.isfile(found["config_path"])
    assert {m["name"] for m in found["per_layer"]} == (
        ALSO_UNDER | {NEW_METRIC})
    assert {m["name"] for m in found["end_to_end"]} == {"pods_per_s",
                                                         "setup_s"}


def test_the_pods_are_the_twins_and_the_nodes_are_four_pools():
    cfg, twin = _config(rehearse=False), _config(TWIN, rehearse=False)
    # pods, counts, namespaces, what the device path must do, the
    # guarantees: `prefaffinity-5k`'s, key for key
    for key in ("initPods", "measurePods", "device_path", "guarantees"):
        assert cfg[key] == twin[key], key
    assert set(cfg) == set(twin)
    assert cfg["reduced"] == []
    assert [g["count"] for g in cfg["nodes"]] == [1250] * 4
    assert [g["template"] for g in cfg["nodes"]] == [
        {"cpu": cpu, "memory": mem, "pods": pods, "zones": 1}
        for cpu, mem, pods, _holds, _by in POOLS]
    # what a node of each pool holds of the 100m / 500Mi pods, and by which
    # of NodeResourcesFit's three refusals
    pod = cfg["measurePods"]["template"]
    want_cpu, want_mem = (reference.milli_cpu(pod["cpu"]),
                          reference.quantity(pod["memory"]))
    assert (want_cpu, want_mem) == (100, 500 * 2**20)
    for cpu, mem, pods, holds, by in POOLS:
        room = {"cpu": reference.milli_cpu(cpu) // want_cpu,
                "memory": reference.quantity(mem) // want_mem, "pods": pods}
        assert min(room.values()) == holds == room[by], (cpu, room)
        assert sorted(room, key=room.get)[0] == by
    # the allocatables are the formula's (GKE's documentation, as the file
    # states it under `assumed`), reckoned here again
    for (cpu, mem, _pods, _holds, _by), vcpus in zip(POOLS, (4, 8, 16, 32)):
        gib = 4 * vcpus
        reserved_gib = (0.25 * min(gib, 4) + 0.20 * min(max(gib - 4, 0), 4)
                        + 0.10 * min(max(gib - 8, 0), 8)
                        + 0.06 * min(max(gib - 16, 0), 112))
        assert mem == f"{int(gib * 1024 - reserved_gib * 1024 - 100)}Mi"
        reserved_milli = (60 + 10 * (vcpus >= 2) + 5 * min(max(vcpus - 2, 0), 2)
                          + 2.5 * max(vcpus - 4, 0))
        assert cpu == f"{int(vcpus * 1000 - reserved_milli)}m"
    for key in ("provenance", "allocatable formula", "machines", "nodes",
                "a node holds", "pods", "node names", "rehearse"):
        assert key in cfg["assumed"], key
    assert "as remembered" in cfg["assumed"]["provenance"]
    assert cfg["rehearse"] == {"nodes": [208, 208, 2, 2], "initPods": 400,
                               "measurePods": 800}
    # the cluster: 5,000 nodes, the pools interleaved by the seed
    nodes = objects.cluster(cfg, 3000000019)
    assert len(nodes) == 5000
    assert {n["name"] for n in nodes} == {f"node-{i}" for i in range(5000)}
    shapes = [(n["cpu"], n["memory"], n["pods"]) for n in nodes]
    assert len(set(shapes)) == 4
    assert len(set(shapes[:40])) == 4 and nodes != objects.cluster(cfg, 7)
    # pool 1 is node-0 ... node-1249
    small = {n["name"] for n in nodes if n["cpu"] == 3920}
    assert small == {f"node-{i}" for i in range(1250)}


def test_a_rehearsal_fills_nodes_by_memory_and_by_pod_count():
    """The reference over the rehearsal's cluster: init pods and one wave
    leave nodes of pool 2 at 58 pods (memory) and all four nodes of the two
    large pools at 110 (pod count), and the next pod is refused by both."""
    cfg = _config()
    for seed in SEEDS:
        nodes = objects.cluster(cfg, seed)
        by_name = {n["name"]: n for n in nodes}
        ref = reference.Reference(nodes)
        for i in range(cfg["initPods"]["count"]):
            ref.schedule(f"init-{i}", cfg["initPods"]["template"])
        for i in range(cfg["measurePods"]["count"]):
            ref.schedule(f"m-{i}", cfg["measurePods"]["template"])
        held = {}
        for row, name in enumerate(ref.names):
            held.setdefault(by_name[name]["cpu"], []).append(
                int(ref.n_pods[row]))
        assert sorted(held[15890]) == sorted(held[31850]) == [110, 110]
        assert held[7910].count(58) >= 10 and max(held[7910]) == 58
        assert sum(sum(v) for v in held.values()) == 1200


# -- the controls: what `correct` sees here, and what it does not -------------

SEEN = ("int32", "podAffinity.normalised_over_cluster",
        "podAffinity.plugin_weight_1")
NOT_SEEN = ("podAffinity.floor_not_float",
            "podAffinity.symmetric_half_dropped", "float32")
CONTROL_SEEDS = (11, 12, 13)


def test_the_controls_that_equal_nodes_hide_differ_at_toy_size():
    """On `prefaffinity-5k` each of these places 0 of 10,000 pods elsewhere
    than the reference (`PERF.md` section 4). On four pools they differ, at
    toy size too: `int32` and `normalised_over_cluster` on every seed.
    **`plugin_weight_1` is the one that toy size cannot show on every
    seed**: it differs on seeds 11 and 12 (550 and 753 of 1,200) and reads 0
    on seed 13, as on some three seeds in ten at this size; at full size it
    differs on 2,188, 2,963 and 4,915 of 10,000 on seeds 11-13
    (`PERF.md` section 2), and that reading stands for it. The three that
    still read 0 everywhere are what the cell does not guard."""
    cfg = _config()
    found = {**control.CONTROLS, **control.feature_controls(cfg),
             **control.READINGS}
    assert set(SEEN + NOT_SEEN) <= set(found)
    differ = {name: [control.differing(cfg, seed, found[name])
                     for seed in CONTROL_SEEDS] for name in SEEN}
    for name, counts in differ.items():
        assert all(total == 1200 for total, _ in counts)
    assert all(d >= 1 for _, d in differ["int32"])
    assert all(d >= 1 for _, d in differ["podAffinity.normalised_over_cluster"])
    weight = [d for _, d in differ["podAffinity.plugin_weight_1"]]
    assert weight[0] >= 1 and weight[1] >= 1 and sum(d >= 1 for d in weight) >= 2
    for name in NOT_SEEN:
        assert control.differing(cfg, CONTROL_SEEDS[0], found[name]) == (
            1200, 0), name
    # and on the twin's equal nodes all of them read 0, at the same counts
    twin = _config(TWIN)
    twin["nodes"]["count"] = 420
    twin["initPods"]["count"], twin["measurePods"]["count"] = 400, 800
    for name in SEEN:
        assert control.differing(twin, CONTROL_SEEDS[0], found[name]) == (
            1200, 0), name


# -- the cell through the front door ------------------------------------------

# the program as it was before PR 50: both paths floor
FLOORED = """
import sys
sys.path.insert(0, {root!r}); sys.path.insert(0, {bench!r})
import kubernetes_tpu.core
from kubernetes_tpu.ops import kernel
from kubernetes_tpu.plugins import interpodaffinity as ipa
kernel._truncated_percent = lambda a, b: kernel._bounded_div(
    kernel.MAX_NODE_SCORE * a, b, kernel._SCORE_BITS)
def floored(self, state, pod, scores):
    if not state.read(self._SKEY):
        return
    low = min(s.score for s in scores)
    span = max(s.score for s in scores) - low
    for s in scores:
        s.score = 100 * (s.score - low) // span if span > 0 else 0
ipa.InterPodAffinity.normalize_score = floored
import run
sys.exit(run.main(sys.argv[1:]))
"""


def _rehearse(seed, trace=0, seconds=1, floored=False):
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    front = ([sys.executable, "-c", FLOORED.format(root=ROOT, bench=BENCH)]
             if floored else [sys.executable, os.path.join(BENCH, "run.py")])
    cmd = front + ["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--rehearse"]
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
    assert line["failed"] == 0 and line["attempted"] >= 400 + 4 * 800
    waves = [ln for ln in out.splitlines()
             if "] wave " in ln or "warm-up wave" in ln]
    assert len(waves) >= 3
    for ln in waves:
        assert "800/800 bound" in ln, ln
    for ln in waves[2:]:
        # pods with terms are not hint-eligible and a delete of one voids
        # the plan: every wave is a full build and one batch of the scan
        assert "batches 1 hints 0 rebuilds full/delta/resume 1/0/0" in ln, ln
    assert "cluster: 420 nodes, 400 init pods bound" in out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_rehearsal_is_correct_and_no_pod_takes_the_host_path(seed):
    line, out = _rehearse(seed)
    _holds(line, out)
    assert line["metrics"]["setup_s"]["value"] > 0
    assert "pods_per_s" in line["metrics"]


def test_a_traced_rehearsal_reads_every_listed_reader_but_the_roofline():
    line, out = _rehearse(11, trace=1, seconds=2)
    _holds(line, out)
    got = line["metrics"]
    assert got[NEW_METRIC] == {"value": 4.0, "unit": "shapes"}
    assert got["scan_normalised_share"]["value"] == 100.0
    assert got["commit_batch_share"]["value"] == 100.0
    assert got["ipa_score_share"]["value"] > 0
    assert got["scan_step_us"]["value"] > 0        # a CPU's, and no rate
    # every listed reader reads; a rehearsal has no chip and no roofline
    listed = ALSO_UNDER | {NEW_METRIC}
    assert set(got) - {"pods_per_s", "setup_s"} == listed - NOT_IN_A_REHEARSAL
    assert any(ln.startswith("[timeline]") and "NOT joined" not in ln
               and " scan_normalised 800:" in ln for ln in out.splitlines())


def test_the_program_that_floors_still_rehearses_correct():
    """What the cell cannot see (`podAffinity.floor_not_float` reads 0 of
    10,000 at full size too): with both paths' NormalizeScore put back to an
    integer floor, the program as it was before PR 50, the rehearsal is
    `correct` all the same. The fullest feasible node wins whatever a
    middling node scores; `tests/test_ipa_normalise_forms.py` holds the form
    itself."""
    line, out = _rehearse(SEEDS[0], floored=True)
    _holds(line, out)


def test_ipacost_finds_the_cells_measured_template_as_it_does_the_twins(
        monkeypatch):
    import anticost
    found = {}
    for cell in (CELL, TWIN_CELL):
        monkeypatch.setattr(sys, "argv", ["run.py", "--workload", cell,
                                          "--seed", "7"])
        found[cell] = anticost.measured_template()
    assert found[CELL] == found[TWIN_CELL] is not None
    assert ipacost.landing_axes(found[CELL]) == 1
    # the same shapes, so the same least bytes a batch: 5,000 nodes, 1,000
    # pods, one landing axis
    assert ipacost.ipa_least_bytes_per_batch(5000, 1000, 1) == 700_000


# -- the new reader on made-up observations -----------------------------------

BENCH_SPANS = [["bench.init", 0.0, 90.0], ["bench.wave", 100.0, 50.0],
               ["bench.restore", 150.0, 20.0], ["bench.wave", 300.0, 50.0]]


@pytest.mark.parametrize("found, waves, want", [
    # one full build a wave on four pools
    ([[101.0, 4], [301.0, 4]], 2, 4.0),
    # a pool left between the two traced waves
    ([[101.0, 4], [301.0, 3]], 2, 3.5),
    # only the last traced wave counts; builds outside a wave do not
    ([[50.0, 1], [101.0, 1], [301.0, 4]], 1, 4.0),
    # without the stat (the parent of PR 50): nothing
    ([[101.0, None], [301.0, None]], 2, None),
    # no build inside a traced wave, no traced wave
    ([[50.0, 4]], 2, None),
    ([[101.0, 4]], 0, None),
])
def test_plan_node_shapes_is_the_mean_of_the_builds_stat(found, waves, want):
    reader = _module(os.path.join(BENCH, "layer_metrics", NEW_METRIC + ".py"),
                     "pools_reader")
    got = reader.mean(BENCH_SPANS, found, waves)
    assert got is None if want is None else got == pytest.approx(want)
    # no trace of this run to load: nothing, and no exception
    assert reader.read({"traced": {}}) is None
