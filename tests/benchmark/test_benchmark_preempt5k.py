"""`preempt-5k.waves` (PR 43): the entries and the cell pinned by name, the
configuration and the traffic held to the issue, the driver's reading of a
wave's log on a scripted trail, the cell through `run.py --rehearse` against
both of the program's schedulers (`correct`, every count of `compared` at its
limit), the toy that preempts `correct` against the UNBENT reference on the
seeds PR 42 pinned to the program's old choice of node, the controls on a
rehearsal's own log, a fault planted underneath, and each new reader on a
small recorded `obs`. No timing is asserted."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types

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
import run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

CELL = "preempt-5k.waves"
TOY_BENCH = os.path.join(HERE, "toy_bench")
NEW_METRICS = {
    "preempt_stage_share": ("%", "lower", "program_span",
                            "host scheduler loop"),
    "nominated_retry_ms": ("ms", "lower", "program_span",
                           "host scheduler loop"),
    "nomination_rebuild_share": ("%", "lower", "program_span",
                                 "feature build and mirror"),
}
# the accepted per-layer metrics whose readers find something in the cell:
# the nineteen it shares with every `.waves` cell and three of `churn-5k`'s
ALSO_UNDER = {
    "host_commit_share", "gc_pause_share", "device_wait_share",
    "hint_hit_rate", "plan_build_share", "kernel_ms_per_batch",
    "schedule_batch_roofline", "loop_unnamed_share", "queue_pop_share",
    "inbox_drain_share", "device_dispatch_share", "commit_batch_share",
    "kernel_hidden_share", "fetch_tail_ms", "launch_gap_ms",
    "collector_pause_share", "plan_adopt_share", "cycle_self_share",
    "pop_run_share", "failed_attempt_ms", "plan_rebuild_full_per_wave",
    "preempt_dry_run_roofline"}
# no node event in the cell: these two read nothing here and are left out;
# `backlog_at_pop_mean`'s list an accepted test holds to one cell
NOT_UNDER = {"structural_rebuild_share", "cluster_event_wait_p50_ms",
             "backlog_at_pop_mean"}
SEEDS = (7, 3000000019)           # the driver's seeds exceed 32 signed bits
TOY_SEEDS = (7, 11, 3000000019)
priority = features.load("reference", "priority")


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(name):
    return _module(os.path.join(BENCH, "layer_metrics", name + ".py"),
                   "preempt_reader_" + name)


driver = _module(os.path.join(BENCH, "drivers", "waves_preempt.py"),
                 "preempt_driver")
pinned = _module(os.path.join(HERE, "test_benchmark_preemption.py"),
                 "pr42_preemption_tests")


def _config(rehearse=True):
    return objects.load_config(
        os.path.join(BENCH, "configs", "preempt-5k.json"), rehearse)


def _traffic():
    with open(os.path.join(BENCH, "traffic", "waves-preempt.json")) as f:
        return json.load(f)


# -- the manifest: what this PR appended, by name ----------------------------

def test_the_cell_its_configuration_and_the_three_entries_are_appended():
    configs = [c["name"] for c in MANIFEST["configs"]]
    cells = [w["name"] for w in MANIFEST["workloads"]]
    metrics = [m["name"] for m in MANIFEST["per_layer"]]
    # after the parent's last, wherever a later PR has put its own
    assert configs.index("preempt-5k") > configs.index("churn-5k")
    assert cells.index(CELL) > cells.index("churn-5k.waves")
    for name in NEW_METRICS:
        assert metrics.index(name) > metrics.index("preempt_dry_run_roofline")
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == "preempt-5k")
    assert cfg["file"] == "benchmark/configs/preempt-5k.json"
    assert cfg["source"] == (
        "kubernetes test/integration/scheduler_perf/default_preemption/"
        "performance-config.yaml:109 PreemptionAsync/5000Nodes")
    assert cfg["reduced"] == [] and len(cfg["why"]) <= 200
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "preempt-5k", "waves-preempt", 1)
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
        if CELL in lists and "churn-5k.waves" in lists:
            assert lists.index(CELL) > lists.index("churn-5k.waves")
    # the harness finds the cell's files by these names
    found = run.find_cell(BENCH, MANIFEST, CELL)
    assert found["driver_path"].endswith("drivers/waves_preempt.py")
    assert os.path.isfile(found["driver_path"])
    assert os.path.isfile(found["config_path"])
    assert {m["name"] for m in found["per_layer"]} >= (
        set(NEW_METRICS) | ALSO_UNDER)
    assert [m["name"] for m in found["end_to_end"]] == ["pods_per_s",
                                                        "setup_s"]


def test_the_configuration_is_the_sources_and_the_traffic_the_issues():
    cfg = _config(rehearse=False)
    node = {"cpu": 4, "memory": "32Gi", "pods": 110, "zones": 1}
    assert cfg["nodes"] == {"count": 5000, "template": node}
    assert cfg["initPods"]["count"] == 20000
    low = cfg["initPods"]["template"]
    assert (low["cpu"], low["memory"]) == ("900m", "500Mi")
    assert low["priority"] < 0       # below the default pods': see `assumed`
    assert cfg["measurePods"] == {
        "count": 5000, "template": {"cpu": "100m", "memory": "500Mi"}}
    assert objects.groups(cfg, "preemptors") == [
        {"template": {"cpu": "3", "memory": "500Mi", "priority": 10}}]
    assert control.may_pend_templates(cfg) == [
        {"cpu": "3", "memory": "500Mi", "priority": 10}]
    assert cfg["reduced"] == []
    assert {"nodes", "initPods", "measurePods", "preemptors", "churn",
            "churn interval", "start times", "rehearse", "threshold"
            } <= set(cfg["assumed"])
    for key in ("initPods", "preemptors", "churn"):
        assert "unconfirmed" in cfg["assumed"][key], key
    assert "570" in cfg["assumed"]["threshold"]
    with open(os.path.join(TOY_BENCH, "configs", "preempt-toy.json")) as f:
        toy = json.load(f)
    # the toy's guarantees word for word, and two of the cell's own
    assert cfg["guarantees"][:4] == toy["guarantees"]
    assert cfg["guarantees"][4:] == [
        "no PodDisruptionBudget exists",
        "victims are deleted through the API dispatcher inline, inside the "
        "preemptor's cycle"]
    assert cfg["rehearse"] == {"nodes": 420, "initPods": 1680,
                               "measurePods": 420}
    params = _traffic()
    assert params["driver"] == "waves_preempt"
    assert (params["warmup_waves"], params["traced_waves"]) == (2, 1)
    assert params["preemptor_every_bound_pods"] == 114
    # 43 a wave: a 44th mark, 5,016, no wave reaches
    marks = driver.marks(params, 5000, rehearse=False)
    assert (len(marks), marks[0], marks[1], marks[-1]) == (43, 114, 228, 4902)
    toy_marks = driver.marks(params, 420, rehearse=True)
    assert (len(toy_marks), toy_marks[0], toy_marks[-1]) == (42, 10, 420)
    assert params["rehearse"]["max_batch"] == 100
    # the pods of a wave are created at once: nothing gates a create
    assert "slice" not in json.dumps(params)
    # four init pods fill a node to within 400m; a preemptor needs three gone
    cpu = reference.milli_cpu
    assert 4 * cpu(low["cpu"]) + 400 == 1000 * node["cpu"]
    assert cpu("3") + 1 * cpu(low["cpu"]) + cpu("100m") <= 4000
    assert cpu("3") + 2 * cpu(low["cpu"]) > 4000


# -- the driver's reading of a wave ------------------------------------------

def test_a_waves_log_is_read_from_its_trail():
    """Two measured pods bound; a preemptor's first attempt, which found no
    node, and its three evictions behind it; a second preemptor's, before
    another pod is bound; three more measured pods; the first preemptor's
    retry binds it; the second one's retry finds no node again and a third
    attempt binds it; a preemptor that a free node took at once."""
    trail = [("bound", 2), ("failed", "w0-high-1"), ("evicted", "init-5"),
             ("evicted", "init-6"), ("evicted", "init-7"),
             ("failed", "w0-high-2"), ("evicted", "init-9"), ("bound", 5),
             ("placed", "w0-high-1"), ("failed", "w0-high-2"),
             ("placed", "w0-high-2"), ("placed", "w0-high-3")]
    tried = set()
    high = driver.PREEMPTOR
    assert driver.wave_log("w0", trail, tried) == [
        ("create", "w0-0", "measurePods"), ("create", "w0-1", "measurePods"),
        ("create", "w0-high-1", high), ("delete", "init-5", None),
        ("delete", "init-6", None), ("delete", "init-7", None),
        ("create", "w0-high-2", high), ("delete", "init-9", None),
        ("create", "w0-2", "measurePods"), ("create", "w0-3", "measurePods"),
        ("create", "w0-4", "measurePods"), ("retry", "w0-high-1", None),
        ("retry", "w0-high-2", None), ("retry", "w0-high-2", None),
        ("create", "w0-high-3", high)]
    assert tried == {"w0-high-1", "w0-high-2", "w0-high-3"}
    # every operation is one the reference knows
    assert {op for op, _, _ in driver.wave_log("w0", trail, set())} <= set(
        reference.OPERATIONS)
    with pytest.raises(ValueError):
        driver.wave_log("w0", [("node", 3)], set())


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
    """One in-process rehearsal against the device scheduler, kept for the
    module: the controls and the log's shape read it."""
    return _in_process(11)


def test_the_rehearsals_log_has_every_attempt_where_it_fell(rehearsed):
    log = rehearsed["log"]
    ops = [op for op, _, _ in log]
    waves = 3                       # two of warm-up, one in a 0.1 s window
    assert ops.count("retry") == 42 * waves
    assert len(rehearsed["evictions"]) == 126 * waves
    assert len(rehearsed["nominations"]) == 42 * waves
    assert all(got <= limit for _, got, limit in rehearsed["guards"])
    # every preemptor's first attempt is a create of its own group, its
    # victims' deletes lie right behind it, and its retry comes later
    at = {(op, name): i for i, (op, name, _) in enumerate(log)}
    by_preemptor = {}
    for victim, preemptor in rehearsed["evictions"].items():
        by_preemptor.setdefault(preemptor, []).append(victim)
    for preemptor, victims in by_preemptor.items():
        first = at[("create", preemptor)]
        assert log[first][2] == driver.PREEMPTOR
        assert sorted(at[("delete", v)] for v in victims) == [
            first + 1, first + 2, first + 3]
        assert at[("retry", preemptor)] > first + 3
        node = rehearsed["nominations"][preemptor]
        assert {rehearsed["placements"][v] for v in victims} == {node}
        assert rehearsed["placements"][preemptor] == node
    # the restore creates anew as many init pods as were evicted
    anew = [name for op, name, group in log
            if op == "create" and name.startswith("init-r")]
    assert len(anew) == 126 * waves
    # start times are ordinals: the program's pods carry what the log says
    creates = [name for op, name, _ in log if op == "create"]
    assert len(creates) == len(set(creates))
    cmp_, over = run.replay(rehearsed, BENCH)
    assert over == []
    assert (cmp_["differing"], cmp_["unbound"], cmp_["unexpected"],
            cmp_["evictions_differing"], cmp_["nominations_differing"]) == (
                0, 0, 0, 0, 0), cmp_
    assert cmp_["evictions"] == 126 * waves


# -- the controls, on a rehearsal's own log and through control.py ------------

def _held_to_the_run(result, control_state):
    swapped = control._swapped("priority", control_state)(
        result["nodes"], BENCH)
    try:
        expected = reference.replay(swapped, result["templates"],
                                    result["log"], result["may_pend"])
    except (ValueError, RuntimeError, KeyError):
        return None     # the control cannot follow the log: not correct
    cmp_ = reference.compare(expected, result["placements"],
                             result["evictions"], result["nominations"])
    return (cmp_["differing"] + cmp_["evictions_differing"]
            + cmp_["nominations_differing"])


# what `correct` cannot see in this cell: the 42 preemptors of a rehearsal
# (43 at full size) are all taken AFTER the wave's measured pods (a pod add
# that another thread parks waits until the session's queue runs dry,
# PERF.md section 7), the init pods have one priority and three go on every
# candidate node, so no pod is ever placed into room that is held against it
# by a pod of HIGHER priority only
READS_ZERO = set()


@pytest.mark.parametrize("which", sorted(priority.CONTROLS))
def test_a_preemption_control_is_not_correct_on_the_rehearsals_log(
        which, rehearsed):
    differ = _held_to_the_run(rehearsed, priority.CONTROLS[which])
    if which in READS_ZERO:
        assert differ == 0, which
    else:
        assert differ is None or differ > 0, which


@pytest.mark.parametrize("which", ("stale_batch", "last_maximum"))
def test_the_cores_controls_differ_at_rehearsal_size(which):
    """`control.py --config preempt-5k --rehearse`: the plain log (init pods,
    a wave, one preemptor a third into it) under a core control."""
    total, differ = control.differing(_config(), 7, control.CONTROLS[which])
    assert total > 2000 and differ > 0


def test_control_py_finds_the_six_priority_controls_for_the_configuration():
    found = control.feature_controls(_config())
    assert {f"priority.{name}" for name in priority.CONTROLS} == set(found)
    run_ = control.preemption_log(_config(), 7)
    sound = reference.replay(reference.Reference(run_["nodes"]),
                             run_["templates"], run_["log"], run_["may_pend"])
    assert len(sound.evictions) == 3 * control.ROUNDS
    for name in ("priority.no_reprieve", "priority.first_candidate",
                 "priority.room_not_held"):
        total, differ = control.differing_on_preemption(
            _config(), 7, found[name])
        assert differ > 0, name


# -- through the front door ---------------------------------------------------

@pytest.fixture(scope="module")
def host_bench(tmp_path_factory):
    """The cell's own files with the traffic's `scheduler` set to `host`:
    the program's sequential scheduler in the device scheduler's place."""
    bench = tmp_path_factory.mktemp("preempt_host")
    for d in ("configs", "traffic", "drivers"):
        os.makedirs(bench / d)
    shutil.copy(os.path.join(BENCH, "configs", "preempt-5k.json"),
                bench / "configs")
    for name in ("waves_preempt.py", "waves.py"):
        shutil.copy(os.path.join(BENCH, "drivers", name), bench / "drivers")
    params = dict(_traffic(), scheduler="host")
    (bench / "traffic" / "waves-preempt.json").write_text(json.dumps(params))
    manifest = {"workloads": [w for w in MANIFEST["workloads"]
                              if w["name"] == CELL],
                "end_to_end": [{"name": "pods_per_s", "unit": "pods/s"},
                               {"name": "setup_s", "unit": "s"}],
                "per_layer": []}
    (bench / "manifest.json").write_text(json.dumps(manifest))
    return str(bench)


def _rehearse(seed, trace=0, seconds=1, bench=None, cell=CELL,
              manifest=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds), "--trace",
           str(trace), "--rehearse"]
    if bench:
        cmd += ["--bench-dir", bench, "--manifest",
                manifest or os.path.join(bench, "manifest.json")]
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


GUARDS = {"placements_differing", "pods_unbound", "pods_unexpected",
          "evictions_differing", "nominations_differing",
          "nodes_over_allocatable", "compiles_in_window", "host_path_pods",
          "breaker_charges", "attempts_without_a_place", "pods_never_bound",
          "waves_off_their_evictions", "waves_off_their_nominations",
          "failed_attempts_off_the_log", "failed_attempts"}


def _holds(line, out, device=True):
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == GUARDS | (
        {"dry_runs_not_on_the_device"} if device else set())
    for name, c in line["compared"].items():
        if name == "failed_attempts":
            # one failed attempt a preemptor, 42 a wave, and not one more
            assert c["value"] == c["limit"] >= 42 * 3
        else:
            assert c["value"] == c["limit"] == 0, name
    assert line["failed"] == 0 and line["attempted"] >= 420
    for ln in out.splitlines():
        if "] wave " in ln or "warm-up wave" in ln:
            assert "420/420 bound" in ln, ln
            assert "preemptors 42 issued" in ln and " 42 bound" in ln, ln
            assert "evictions 126 nominations 42" in ln, ln
            if device:
                assert "dry runs device/host 42/0" in ln, ln
                assert "bound/fell through 42/0" in ln, ln
                assert "host path 0" in ln, ln


@pytest.mark.parametrize("seed", SEEDS)
def test_the_rehearsal_is_correct_against_the_device_scheduler(seed):
    line, out = _rehearse(seed)
    _holds(line, out)
    assert line["metrics"]["setup_s"]["value"] > 0
    assert "pods_per_s" in line["metrics"]
    assert "evictions expected" in out


def test_the_rehearsal_is_correct_against_the_host_scheduler(host_bench):
    line, out = _rehearse(7, bench=host_bench)
    _holds(line, out, device=False)


def test_a_traced_rehearsal_reads_the_new_metrics_and_the_account_sums():
    """Traced, so that the new readers are met through the front door too:
    their values stand in the line, the new stages are in the program's
    account, and that account still sums to the wave."""
    line, out = _rehearse(11, trace=1, seconds=2)
    _holds(line, out)
    got = line["metrics"]
    for name in ("preempt_stage_share", "nominated_retry_ms",
                 "nomination_rebuild_share", "failed_attempt_ms",
                 "plan_rebuild_full_per_wave"):
        assert got[name]["value"] >= 0, name
    assert 0 < got["preempt_stage_share"]["value"] < 100
    assert "preempt_dry_run_roofline" not in got       # no chip, no share
    account = next(ln for ln in out.splitlines()
                   if ln.startswith("[progspans]"))
    assert "'postfilter.preempt':" in account
    assert "'nominated.eval':" in account
    shares = json.loads(account.split("% of wave time ")[1].split("; under")[0]
                        .replace("'", '"'))
    unnamed = float(account.rsplit(" ", 1)[1])
    named = sum(v for k, v in shares.items() if k != "cycle")
    assert abs(named + unnamed - 100.0) < 0.2, account
    parts = next(ln for ln in out.splitlines()
                 if ln.startswith("[preempt] attempts"))
    for key in ("select_ms", "verify_ms", "evict_ms", "'victims': 3.0",
                "'nominated': 1.0", "nom_rows", "'attempts': 42",
                "'engine': ['device']"):
        assert key in parts, parts
    retries = next(ln for ln in out.splitlines()
                   if ln.startswith("[preempt] nominated retries"))
    assert "'retries': 42" in retries and "'bound': 42" in retries
    assert any(ln.startswith("[preempt] full builds") for ln in out.splitlines())
    assert any(ln.startswith("[timeline]") and "NOT joined" not in ln
               for ln in out.splitlines())


# -- the toy that preempts, against the unbent reference ----------------------
# PR 42 pinned the program's old choice of node on these seeds
# (`test_the_toy_and_the_program_agree_on_all_but_that_line`, held to
# `PicksAsTheProgram`); these are their sound twins.

@pytest.fixture(scope="module")
def toys():
    runs = {}

    def of(kind, seed):
        if (kind, seed) not in runs:
            runs[kind, seed] = pinned._in_process(kind, seed)
        return runs[kind, seed]
    return of


@pytest.mark.parametrize("kind, seed", [("host", s) for s in TOY_SEEDS]
                         + [("device", 7)])
def test_the_toy_is_correct_against_the_sound_reference(kind, seed, toys):
    """Every placement, eviction, nomination and retry of the toy equal to
    the reference's own, the reference as `priority.py` states it. (The
    counts are the run's own: the 74 evictions PR 42 read belonged to the
    program's old choice of nodes only where they differ.)"""
    result = toys(kind, seed)
    assert all(got <= limit for _, got, limit in result["guards"])
    cmp_, over = run.replay(result, TOY_BENCH)
    assert over == []
    assert (cmp_["differing"], cmp_["unbound"], cmp_["unexpected"],
            cmp_["evictions_differing"], cmp_["nominations_differing"]) == (
                0, 0, 0, 0, 0), cmp_
    ops = [op for op, _, _ in result["log"]]
    assert ops.count("retry") == len(result["nominations"]) == 24
    assert ops.count("delete") == len(result["evictions"]) == cmp_["evictions"]
    assert cmp_["evictions"] >= 24


@pytest.mark.parametrize("kind, seed", [("host", 7), ("device", 7)])
def test_the_reference_that_picks_as_the_program_used_to_now_differs(
        kind, seed, toys):
    """`PicksAsTheProgram` (PR 42's, imported and not copied): the latest
    start among ALL of a node's victims. The mended program parts from it
    from the first preemptor with more than one victim on."""
    result = toys(kind, seed)
    as_program = control._swapped("priority", pinned.PicksAsTheProgram)(
        result["nodes"], TOY_BENCH)
    try:
        expected = reference.replay(as_program, result["templates"],
                                    result["log"], result["may_pend"])
    except ValueError as refusal:
        assert "not pending" in str(refusal) or "retry" in str(refusal)
        return
    cmp_ = reference.compare(expected, result["placements"],
                             result["evictions"], result["nominations"])
    assert (cmp_["differing"] + cmp_["evictions_differing"]
            + cmp_["nominations_differing"]) > 0


@pytest.mark.parametrize("kind", ("host", "device"))
def test_the_toy_is_correct_through_the_harness(kind):
    line, out = _rehearse(
        11, bench=TOY_BENCH, cell=f"preempt-toy.{kind}",
        manifest=os.path.join(TOY_BENCH, "manifest.json"))
    assert line["correct"] is True, line["compared"]
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert "log_refused_by_the_reference" not in line["compared"]
    if kind == "device":
        assert "pods on the host path 0" in out


# -- a fault planted underneath comes out not correct -------------------------

_lane_left_out = _module(
    os.path.join(ROOT, "tests", "test_preemption_source_rules.py"),
    "preemption_source_rules").lane_left_out


def _not_correct(result) -> bool:
    if not all(got <= limit for _, got, limit in result["guards"]):
        return True
    try:
        cmp_, over = run.replay(result, BENCH)
    except (ValueError, RuntimeError, KeyError):
        return True     # the reference cannot follow the log
    return bool(over) or (cmp_["differing"] + cmp_["evictions_differing"]
                          + cmp_["nominations_differing"]) > 0


@pytest.mark.parametrize("fault", ("a_victim_spared", "room_not_held"))
def test_a_preemption_broken_underneath_comes_out_not_correct(
        fault, monkeypatch):
    """A rehearsal with the program's preemption broken underneath it, held
    to the sound reference: a node's last victim left where it is; the
    nomination never handed to the nominator, so that the room is not
    held and the preemptors that follow take it."""
    from kubernetes_tpu.core.queue import Nominator
    from kubernetes_tpu.plugins.preemption import Evaluator
    if fault == "a_victim_spared":
        prepare = Evaluator.prepare_candidate

        def spared(self, cand, pod):
            if len(cand.victims) > 1:
                cand.victims = cand.victims[:-1]
            return prepare(self, cand, pod)
        monkeypatch.setattr(Evaluator, "prepare_candidate", spared)
    else:
        monkeypatch.setattr(Nominator, "add_nominated_pod",
                            lambda self, pi, node_name: None)
    try:
        result = _in_process(5, scheduler="host")
    except (RuntimeError, KeyError):
        return          # the wave cannot even end: not correct
    assert _not_correct(result)


def test_what_correct_cannot_see_here_the_lane_left_out_of_the_dry_run(
        monkeypatch):
    """Named as what the cell does not guard: with the nominated lane left
    out of the what-if the rehearsal still reads `correct`. Every preemptor
    is of one size, so the node the first one emptied fits the second
    WITHOUT a victim (3 cpu beside the one batch pod of 900m and at most one
    plain pod of 100m), and a node that needs no victim is no candidate with
    or without the lane: the ordinary filter, which does hold the room,
    keeps the second preemptor off it. Two preemptors of unequal size show
    the fault (tests/test_preemption_source_rules.py
    `test_with_the_lane_left_out_the_second_preemptor_takes_that_room`); the
    kernel and the host what-if are held row for row there too."""
    _lane_left_out(monkeypatch)
    result = _in_process(5)
    assert not _not_correct(result)


# -- the readers, each on a small recorded obs -------------------------------

MS = 1e6            # ns


def _spans():
    """One traced wave of 200 ms: a plain session, a turn in which two
    preemptors fail and are nominated, two turns of nominated retries (one
    bound, one that fell through), and a plain session whose kept plan only
    the nominator voided."""
    said = {"engine": "device", "candidates": "100", "victims_ms": "20.0",
            "plan_ms": "10.0", "dispatch_ms": "0.5", "fetch_ms": "1.0",
            "rows": "8192", "k": "8", "r": "7", "select_ms": "1.0",
            "verify_ms": "0.5", "evict_ms": "0.25", "victims": "3",
            "nominated": "1"}
    return {"wave_s": 0.2, "spans": [
        ["cycle", 0.0, 30 * MS, {}],
        ["plan.build", 1 * MS, 8 * MS, {"kind": "full",
                                        "cause": "other_pod"}],
        ["cycle", 40 * MS, 90 * MS, {}],
        ["plan.build", 41 * MS, 6 * MS, {"kind": "full",
                                         "cause": "other_pod"}],
        ["postfilter.preempt", 50 * MS, 40 * MS, dict(said, nom_rows="0")],
        ["postfilter.preempt", 92 * MS, 30 * MS, dict(said, nom_rows="1")],
        ["cycle", 131 * MS, 12 * MS, {}],
        ["nominated.eval", 132 * MS, 10 * MS, {"outcome": "bound",
                                               "engine": "device"}],
        ["cycle", 144 * MS, 20 * MS, {}],
        ["nominated.eval", 145 * MS, 6 * MS, {"outcome": "fell_through",
                                              "engine": "device"}],
        ["cycle", 170 * MS, 25 * MS, {}],
        ["plan.build", 171 * MS, 7 * MS, {"kind": "full",
                                          "cause": "nomination"}],
        ["plan.adopt", 190 * MS, 3 * MS, {"kind": "full",
                                          "cause": "nomination"}],
    ]}


def _mute(spans):
    return {"wave_s": spans["wave_s"],
            "spans": [[s[0], s[1], s[2], {}] for s in spans["spans"]]}


def test_preempt_stage_share_is_the_stages_clock_over_the_waves():
    obs = {"preemptspans": _spans()}
    read = _reader("preempt_stage_share").read
    assert read(obs) == pytest.approx(35.0)         # 40 + 30 ms of 200
    parts = obs["preempt_attempt_parts"]
    assert parts["attempts"] == 2 and parts["engine"] == ["device"]
    assert parts["postfilter_ms"] == pytest.approx(35.0)
    assert (parts["select_ms"], parts["verify_ms"], parts["evict_ms"],
            parts["victims"], parts["nom_rows"]) == (1.0, 0.5, 0.25, 3.0, 0.5)
    # a program whose stage does not say how the attempt ended, a run
    # without a trace: nothing, and no error
    assert read({"preemptspans": _mute(_spans())}) is None
    assert read({"preemptspans": None}) is None
    assert read({}) is None


def test_nominated_retry_ms_reads_the_turn_around_each_evaluation():
    obs = {"preemptspans": _spans()}
    read = _reader("nominated_retry_ms").read
    assert read(obs) == pytest.approx(16.0)         # turns of 12 and 20 ms
    told = obs["nominated_retries"]
    assert told["retries"] == 2
    assert told["outcomes"] == {"bound": 1, "fell_through": 1}
    assert told["eval_ms"] == pytest.approx(8.0)
    bare = {"wave_s": 0.2, "spans": [s for s in _spans()["spans"]
                                     if s[0] != "nominated.eval"]}
    assert read({"preemptspans": bare}) is None     # the parent's program
    assert read({"preemptspans": None}) is None
    assert read({}) is None


def test_nomination_rebuild_share_counts_the_builds_that_say_so():
    obs = {"preemptspans": _spans()}
    read = _reader("nomination_rebuild_share").read
    assert read(obs) == pytest.approx(5.0)          # 7 + 3 ms of 200
    assert obs["rebuilds_by_cause"]["other_pod"][0] == 2
    assert obs["rebuilds_by_cause"]["nomination"] == [
        1, pytest.approx(0.010)]
    # retries and no such build: the share is 0, not nothing
    none = {"wave_s": 0.2, "spans": [s for s in _spans()["spans"]
                                     if s[3].get("cause") != "nomination"]}
    assert read({"preemptspans": none}) == 0.0
    # the parent says no cause and has no stage: nothing, and no error
    assert read({"preemptspans": _mute(_spans())}) is None
    bare = {"wave_s": 0.2, "spans": [s for s in _spans()["spans"]
                                     if s[0] != "nominated.eval"]}
    assert read({"preemptspans": bare}) is None
    assert read({}) is None


def test_the_span_loader_keeps_the_new_stats_and_the_accepted_list_stays():
    preemptspans = _module(os.path.join(BENCH, "preemptspans.py"),
                           "preemptspans_under_test")
    churnspans = sys.modules["churnspans"]
    kept = dict(churnspans.KEPT)
    assert set(preemptspans.KEPT) == set(kept) | {"nominated.eval"}
    assert set(kept["postfilter.preempt"]) < set(
        preemptspans.KEPT["postfilter.preempt"])
    assert {"select_ms", "verify_ms", "evict_ms", "victims", "nominated",
            "nom_rows"} <= set(preemptspans.KEPT["postfilter.preempt"])
    seen = []
    own, churnspans.load = churnspans.load, lambda path: seen.append(
        dict(churnspans.KEPT)) or ([], [])
    try:
        assert preemptspans.load("no-such-file") == ([], [])
    finally:
        churnspans.load = own
    assert seen == [preemptspans.KEPT]      # swapped for the one call
    assert churnspans.KEPT == kept          # and put back
    spans = _spans()["spans"]
    assert preemptspans.turn_ms(spans, spans[7]) == pytest.approx(12.0)
    assert preemptspans.turn_ms(spans, ["x", 500 * MS, 1.0, {}]) is None
    assert len(preemptspans.stage(spans, "cycle")) == 5
