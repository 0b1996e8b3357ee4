"""The device batches' timeline (benchmark/timeline.py) and the eight readers
of PR 36: the join's arithmetic on a timeline built by hand, the CPU
stand-in's grouping of operations into program runs pinned by a recording
(benchmark/testdata/timeline_small.json), what a program without the new
stats reads (nothing, not 0), and a traced rehearsal of each cell the metrics
are listed under. No timing is asserted.

The recording: the program on the CPU at toy size (12 nodes in 4 zones, 10
hard-spread pods, `TPUScheduler(max_batch=4)`: one pipelined session of three
scan dispatches of 4, 4 and 2 steps) under the JAX profiler inside one
`bench.wave`, kept as `timeline.load` gives it, times from the wave's start,
with the stand-in's operations as `tracereduce.load` hands them over, before
grouping."""

import importlib.util
import json
import os
import random
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
RUN = os.path.join(BENCH, "run.py")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import timeline  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
NEW = ("kernel_hidden_share", "fetch_tail_ms", "launch_gap_ms",
       "scan_step_us", "collector_pause_share", "plan_adopt_share",
       "cycle_self_share", "inbox_oldest_wait_p50_ms.open")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- a timeline built by hand ------------------------------------------------

MS = 1e6   # the trace's clock is in ns


def _by_hand(seq=True, engines=("scan_carried",) * 3):
    """One wave of 100 ms, three batches through a pipeline two deep:

        dispatch 7 [1, 3]   run [2, 22]    wait [10, 24]
        dispatch 8 [3, 5]   run [22, 42]   wait [30, 41]   (ends before its run)
        dispatch 9 [24, 26] run [42, 52]   wait [50, 55]
    """
    def dispatch(k, start, dur, steps, depth):
        stats = {"engine": engines[k - 7], "batch": steps, "inflight": depth}
        if engines[k - 7] != "lap":
            stats["steps"] = steps
        if seq:
            stats["seq"] = k
        return ["device.dispatch", start * MS, dur * MS, stats]

    def wait(k, start, dur):
        return ["device.wait", start * MS, dur * MS, {"seq": k} if seq else {}]

    bench = [["bench.init", -50 * MS, 40 * MS], ["bench.wave", 0.0, 100 * MS],
             ["bench.restore", 100 * MS, 10 * MS]]
    sched = [["cycle", 0.5 * MS, 99 * MS, {}],
             dispatch(7, 1, 2, 400, 0), dispatch(8, 3, 2, 400, 1),
             wait(7, 10, 14), dispatch(9, 24, 2, 200, 1), wait(8, 30, 11),
             wait(9, 50, 5),
             # a dispatch of the init pods, outside the traced wave
             ["device.dispatch", -40 * MS, 1 * MS,
              {"seq": 1, "engine": "lap", "batch": 9, "inflight": 0}],
             ["device.wait", -30 * MS, 2 * MS, {"seq": 1}]]
    runs = [["jit_schedule_batch", -39 * MS, 5 * MS],
            ["jit_schedule_batch", 2 * MS, 20 * MS],
            ["jit_schedule_batch", 22 * MS, 20 * MS],
            ["jit_schedule_batch", 42 * MS, 10 * MS]]
    return bench, {"sched": sched, "runs": runs, "plane": "/device:TPU:0",
                   "books_pauses": seq}


def test_hidden_share_fetch_tail_and_launch_gap_of_three_batches_by_hand():
    tl = timeline.reduce(*_by_hand(), waves=1)
    assert tl["why_not"] == "" and len(tl["waves"]) == 1
    b7, b8, b9 = tl["waves"][0]
    assert [b["seq"] for b in (b7, b8, b9)] == [7, 8, 9]
    assert [b["inflight"] for b in (b7, b8, b9)] == [0, 1, 1]
    # waits cover [10, 24], [30, 41], [50, 55]
    assert b7["hidden_ms"] == pytest.approx(8.0)        # [2, 10]
    assert b8["hidden_ms"] == pytest.approx(6.0 + 1.0)  # [24, 30] [41, 42]
    assert b9["hidden_ms"] == pytest.approx(8.0)        # [42, 50]
    assert [b["kernel_ms"] for b in (b7, b8, b9)] == [20.0, 20.0, 10.0]
    assert b7["fetch_tail_ms"] == pytest.approx(2.0)    # 24 - 22
    assert b8["fetch_tail_ms"] == 0.0                   # the wait ended first
    assert b9["fetch_tail_ms"] == pytest.approx(3.0)    # 55 - 52
    assert [b["launch_gap_ms"] for b in (b7, b8, b9)] == [
        pytest.approx(1.0), pytest.approx(19.0), pytest.approx(18.0)]
    assert tl["kernel_s"] == pytest.approx(0.050)
    assert tl["hidden_s"] == pytest.approx(0.023)
    assert tl["steps"] == 1000 and tl["scan_s"] == pytest.approx(0.050)
    obs = {"timeline": tl}
    assert _reader("kernel_hidden_share")(obs) == pytest.approx(46.0)
    assert _reader("fetch_tail_ms")(obs) == pytest.approx(5.0 / 3)
    # the wave's FIRST batch alone: nothing hid its launch
    assert _reader("launch_gap_ms")(obs) == pytest.approx(1.0)
    assert _reader("scan_step_us")(obs) == pytest.approx(50.0)
    line = timeline.describe(tl)
    assert line.startswith("[timeline] 1 traced wave(s) on /device:TPU:0, "
                           "3 batch(es) joined;")
    assert "7 scan_carried 400: 2.000 / 1.000 / 20.000 / 8.000 / 2.000" in line


def test_a_wait_that_begins_after_its_program_ended_is_all_tail():
    """Host-bound: the kernel [2, 4] is long over when the loop comes to wait
    [30, 31]; the 26 ms between were the host's own work, the tail is the
    wait."""
    bench = [["bench.wave", 0.0, 100 * MS]]
    sched = [["device.dispatch", 1 * MS, 0.5 * MS,
              {"seq": 3, "engine": "lap", "batch": 8, "inflight": 0}],
             ["device.wait", 30 * MS, 1 * MS, {"seq": 3}]]
    tl = timeline.reduce(bench, {
        "sched": sched, "runs": [["jit_schedule_batch", 2 * MS, 2 * MS]],
        "plane": "/device:TPU:0", "books_pauses": True}, waves=1)
    (b,) = tl["waves"][0]
    assert b["fetch_tail_ms"] == pytest.approx(1.0)
    assert b["hidden_ms"] == pytest.approx(2.0)
    assert _reader("kernel_hidden_share")({"timeline": tl}) == 100.0


def test_unequal_counts_join_nothing_and_say_so():
    bench, trace = _by_hand()
    trace["runs"].pop()             # a program run the trace did not keep
    tl = timeline.reduce(bench, trace, waves=1)
    assert tl["waves"] == [[]] and "counts differ" in tl["why_not"]
    assert "3 dispatches, 3 waits, 2 program runs" in tl["why_not"]
    assert "NOT joined" in timeline.describe(tl)
    obs = {"timeline": tl}
    assert _reader("fetch_tail_ms")(obs) is None
    assert _reader("launch_gap_ms")(obs) is None
    # what needs no join still reads: overlap, and seconds over steps
    assert _reader("kernel_hidden_share")(obs) == pytest.approx(
        100.0 * 15 / 40)
    assert _reader("scan_step_us")(obs) == pytest.approx(40.0)


def test_a_program_that_stamps_no_seq_reads_the_two_that_need_no_join():
    """The parent of PR 36 under the new files: its dispatches carry `engine`,
    `batch` and `steps`, nothing carries `seq`, the cycle no `pauses`."""
    tl = timeline.reduce(*_by_hand(seq=False), waves=1)
    assert tl["waves"] == [[]] and "no seq on a span" in tl["why_not"]
    obs = {"timeline": tl, "progspans": {
        "wave_s": 0.1, "self_s": {"cycle": 0.01, "plan.adopt": 0.02},
        "unnamed_s": 0.01}}
    assert _reader("fetch_tail_ms")(obs) is None
    assert _reader("launch_gap_ms")(obs) is None
    assert _reader("collector_pause_share")(obs) is None      # not 0
    assert _reader("kernel_hidden_share")(obs) == pytest.approx(46.0)
    assert _reader("scan_step_us")(obs) == pytest.approx(50.0)
    assert _reader("plan_adopt_share")(obs) == pytest.approx(20.0)
    assert _reader("cycle_self_share")(obs) == pytest.approx(10.0)
    # with the sign, a wave that saw no collection reads 0
    obs["timeline"] = timeline.reduce(*_by_hand(), waves=1)
    assert _reader("collector_pause_share")(obs) == 0.0
    obs["progspans"]["self_s"]["gc.pause"] = 0.003
    assert _reader("collector_pause_share")(obs) == pytest.approx(3.0)


def test_scan_steps_beside_lap_dispatches_need_the_join():
    """A lap dispatch counts no steps: its program's seconds are no scan's."""
    engines = ("scan_carried", "lap", "scan_normalised")
    tl = timeline.reduce(*_by_hand(engines=engines), waves=1)
    assert [b["engine"] for b in tl["waves"][0]] == list(engines)
    assert tl["steps"] == 600 and tl["scan_s"] == pytest.approx(0.030)
    assert _reader("scan_step_us")({"timeline": tl}) == pytest.approx(50.0)
    unjoined = timeline.reduce(*_by_hand(seq=False, engines=engines), waves=1)
    assert unjoined["scan_s"] is None
    assert _reader("scan_step_us")({"timeline": unjoined}) is None
    laps = timeline.reduce(*_by_hand(engines=("lap",) * 3), waves=1)
    assert laps["steps"] == 0
    assert _reader("scan_step_us")({"timeline": laps}) is None
    assert _reader("kernel_hidden_share")({"timeline": laps}) == \
        pytest.approx(46.0)


def test_without_a_trace_of_waves_nothing_is_read():
    assert timeline.reduce([["bench.init", 0.0, 5.0]],
                           {"sched": [], "runs": [], "plane": "x",
                            "books_pauses": False}, waves=2) is None
    for name in NEW[:5]:
        assert _reader(name)({}) is None, name
        assert _reader(name)({"traced": {"seconds": 3}}) is None, name


def test_the_inbox_reader_takes_the_window_delta_of_the_series():
    read = _reader("inbox_oldest_wait_p50_ms.open")
    name = "scheduler_inbox_oldest_wait_seconds_bucket"
    series = {(name, (("le", "0.001"),)): 10.0,
              (name, (("le", "0.002"),)): 30.0,
              (name, (("le", "+Inf"),)): 30.0}
    assert read({"prom": {"scheduler": series}}) == pytest.approx(1.25)
    # a program without the series: nothing, not 0
    assert read({"prom": {"scheduler": {
        ("scheduler_pod_stage_duration_seconds_count", ()): 5.0}}}) is None
    assert read({}) is None


# -- the CPU stand-in's grouping, pinned by the recording --------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "testdata", "timeline_small.json")) as f:
        return json.load(f)


def test_the_standins_operations_group_into_one_run_a_dispatch(recorded):
    ops, modules = recorded["standin"]["ops"], recorded["standin"]["modules"]
    assert len(ops) == len(modules) > 300
    runs = sorted(timeline.standin_runs(ops, modules), key=lambda r: r[1])
    assert runs == recorded["runs_as_grouped_when_recorded"]
    assert len(runs) == 3 and {r[0] for r in runs} == {"jit_schedule_batch"}
    # one after the other on the one executor, each around one loop
    for a, b in zip(runs, runs[1:]):
        assert a[1] + a[2] <= b[1]
    loops = sorted((o for o in ops if o[0].startswith("while")),
                   key=lambda o: o[1])
    assert len(loops) == 3
    dispatches = sorted((e for e in recorded["sched"]
                         if e[0] == "device.dispatch"), key=lambda e: e[1])
    assert [d[3]["steps"] for d in dispatches] == [4, 4, 2]
    for run, loop, d in zip(runs, loops, dispatches):
        assert run[1] <= loop[1] and loop[1] + loop[2] <= run[1] + run[2]
        # an independent count: the loop's body ran the dispatch's steps
        body = [o for o in ops if o[0] == "copy.30"
                and loop[1] <= o[1] and o[1] + o[2] <= loop[1] + loop[2]]
        assert len(body) == d[3]["steps"]
        # the kernel began after its dispatch did
        assert run[1] > d[1]
    # every operation outside a loop lies in the run it was grouped into
    inside_a_loop = [any(lp[1] <= o[1] and o[1] + o[2] <= lp[1] + lp[2]
                         and o is not lp for lp in loops) for o in ops]
    outside = [o for o, inside in zip(ops, inside_a_loop) if not inside]
    assert len(outside) % 3 == 0 and len(outside) >= 60
    for o in outside:
        assert sum(r[1] <= o[1] and o[1] + o[2] <= r[1] + r[2]
                   for r in runs) == 1
    # whatever order the executor's threads are read in
    both = list(zip(ops, modules))
    random.Random(36).shuffle(both)
    again = timeline.standin_runs([o for o, _m in both],
                                  [m for _o, m in both])
    assert sorted(again, key=lambda r: r[1]) == runs


def test_the_recordings_batches_join_by_seq(recorded):
    trace = {"sched": recorded["sched"], "plane": timeline.STANDIN,
             "books_pauses": recorded["books_pauses"],
             "runs": timeline.standin_runs(recorded["standin"]["ops"],
                                           recorded["standin"]["modules"])}
    trace["runs"].sort(key=lambda r: r[1])
    tl = timeline.reduce(recorded["bench"], trace, waves=1)
    assert tl["why_not"] == "" and tl["books_pauses"] is True
    (wave,) = tl["waves"]
    assert [(b["seq"], b["engine"], b["pods"], b["steps"], b["inflight"])
            for b in wave] == [(4, "scan_carried", 4, 4, 0),
                               (5, "scan_carried", 4, 4, 1),
                               (6, "scan_carried", 2, 2, 1)]
    assert tl["steps"] == 10
    assert tl["scan_s"] == tl["kernel_s"] == pytest.approx(
        sum(b["kernel_ms"] for b in wave) / 1e3)
    for b in wave:
        assert 0 < b["launch_gap_ms"] and 0 <= b["fetch_tail_ms"]
        assert 0 <= b["hidden_ms"] <= b["kernel_ms"]
    obs = {"timeline": tl}
    for name in NEW[:4]:
        assert np.isfinite(_reader(name)(obs)), name
    assert 0 <= _reader("kernel_hidden_share")(obs) <= 100


# -- the cells the metrics are listed under, rehearsed -----------------------

# PR 36's five cells, and the three that PR 38 appended to the eight lists
LISTED = {
    "spread-5k.waves": set(NEW[:7]),
    "antiaffinity-5k.waves": set(NEW[:7]) - {"scan_step_us"},
    "basic-5k.waves": set(NEW[4:7]),
    "basic-5k.served-waves": set(NEW[4:7]),
    "basic-5k.served-open": {NEW[7]},
    "prefaffinity-5k.waves": set(NEW[:7]),
    "basic-5k-50k.waves": set(NEW[:7]) - {"scan_step_us"},
    "spread-5k.served-open": {NEW[7]},
}


def test_the_manifest_lists_the_eight_where_the_issue_says():
    """The eight in PR 36's order from wherever the first stands, each
    listing at least the cells named here: what a later PR appends after
    them, an entry or a cell's name, is not this test's to hold."""
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    order = [m["name"] for m in MANIFEST["per_layer"]]
    first = order.index(NEW[0])
    assert order[first:first + len(NEW)] == list(NEW)
    for name in NEW:
        cells = {c for c, names in LISTED.items() if name in names}
        assert cells <= set(entries[name]["workloads"]), name
    assert entries["scan_step_us"]["layer"] == "kernels"
    assert entries["plan_adopt_share"]["layer"] == "feature build and mirror"


@pytest.mark.parametrize("cell", ["spread-5k.waves", "antiaffinity-5k.waves",
                                  "basic-5k.served-open"])
def test_a_traced_rehearsal_reads_every_new_metric_listed_there(cell):
    env = {k: v for k, v in os.environ.items()
           if k not in ("BENCH_RUN", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", "2147483683",
         "--seconds", "1", "--trace", "1", "--rehearse"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["rehearsal"] is True
    got = line["metrics"]
    assert LISTED[cell] <= set(got)
    for name in LISTED[cell]:
        assert np.isfinite(got[name]["value"]), name
    if cell.endswith(".waves"):
        assert 0 <= got["kernel_hidden_share"]["value"] <= 100
        assert got["collector_pause_share"]["value"] >= 0
        said = [l for l in proc.stdout.splitlines()
                if l.startswith("[timeline]")]
        assert len(said) == 1 and "NOT joined" not in said[0]
        # as many batches as the traced waves dispatched
        assert " 2 batch(es) joined" in said[0]
    else:
        assert got["inbox_oldest_wait_p50_ms.open"]["value"] > 0
