"""The readers of the program's own spans and stage series
(benchmark/progspans.py and the eight layer metrics built on it), each on a
hand-made trace or `/metrics` delta: self times inside the traced waves,
what lies under no named stage, a stage that did not occur reading 0, and a
program without the spans or the series (the parent) reading nothing. No
timing is asserted."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import progspans  # noqa: E402
import prom  # noqa: E402


def _reader(metric):
    path = os.path.join(BENCH, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# A trace in nanoseconds: one warm-up wave (must not count) and two traced
# waves of 100 ns and 200 ns; `sched.host.commit` without a cycle around it
# is a named stage all the same, and the wave's first 10 ns lie under no span.
EVENTS = [
    ["bench.init", 0, 50],
    ["bench.wave", 100, 400], ["sched.cycle", 100, 400],
    ["sched.queue.pop", 100, 400],
    ["bench.wave", 1000, 100],
    ["sched.cycle", 1010, 80],                  # self: 80 - 30 - 40 = 10
    ["sched.queue.pop", 1010, 30],
    ["sched.plan.build", 1040, 40],             # self: 40 - 15 = 25
    ["sched.plan.patch", 1050, 15],
    ["sched.host.commit", 1092, 8],             # ends with the wave
    ["bench.restore", 1100, 100],
    ["sched.cycle", 1100, 100],                 # restore: outside every wave
    ["bench.wave", 2000, 200],
    ["sched.cycle", 2000, 200],                 # self: 200 - 150 = 50
    ["sched.inbox.drain", 2000, 50],
    ["sched.device.wait", 2050, 100],
]


def test_stage_self_times_inside_the_traced_waves_by_hand():
    got = progspans.reduce_stages(EVENTS, waves=2)
    assert got["wave_s"] == pytest.approx(300e-9)
    assert got["self_s"] == pytest.approx({
        "cycle": 60e-9, "queue.pop": 30e-9, "plan.build": 25e-9,
        "plan.patch": 15e-9, "host.commit": 8e-9, "inbox.drain": 50e-9,
        "device.wait": 100e-9})
    # under no span: 10 ns before the first cycle and 2 ns after it; under
    # the cycle alone: 60 ns
    assert got["unnamed_s"] == pytest.approx(72e-9)
    named = sum(v for k, v in got["self_s"].items() if k != "cycle")
    assert named + got["unnamed_s"] == pytest.approx(got["wave_s"])
    # one traced wave: the last one only
    last = progspans.reduce_stages(EVENTS, waves=1)
    assert last["wave_s"] == pytest.approx(200e-9)
    assert set(last["self_s"]) == {"cycle", "inbox.drain", "device.wait"}


def test_a_trace_without_the_programs_spans_reads_nothing():
    parent = [e for e in EVENTS if e[0].startswith("bench.")]
    assert progspans.reduce_stages(parent, waves=2) is None
    assert progspans.reduce_stages(EVENTS, waves=0) is None
    assert progspans.reduce_stages([], waves=2) is None


@pytest.mark.parametrize("metric, want", [
    ("loop_unnamed_share", 100.0 * 72 / 300),
    ("queue_pop_share", 100.0 * 30 / 300),
    ("inbox_drain_share", 100.0 * 50 / 300),
    ("device_dispatch_share", 0.0),     # did not occur: 0, not nothing
])
def test_wave_readers_on_a_hand_made_trace(monkeypatch, metric, want):
    read = _reader(metric)
    monkeypatch.setattr(progspans, "_this_runs_events", lambda obs: EVENTS)
    obs = {"traced": {"waves": 2, "reduced": {"window_s": 1.0}}}
    assert read(obs) == pytest.approx(want)
    assert read(obs) == pytest.approx(want)     # cached on obs: loaded once
    # the parent: the trace holds the benchmark's spans alone
    parent = [e for e in EVENTS if e[0].startswith("bench.")]
    monkeypatch.setattr(progspans, "_this_runs_events", lambda obs: parent)
    assert read({"traced": {"waves": 2}}) is None
    # an untraced run, or a served cell: no waves were traced
    assert read({"traced": {}}) is None and read({}) is None


def test_this_runs_trace_is_found_by_cell_seed_and_extent(tmp_path,
                                                          monkeypatch):
    """Two runs of one cell and seed share a checkout: the reader takes the
    trace whose `bench.*` extent is the one run.py reduced."""
    monkeypatch.setattr(progspans, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "c.waves",
                                      "--seed=7", "--trace", "1"])
    traces = {}
    for tag, extent in (("aaa", 500.0), ("bbb", 2200.0)):
        d = tmp_path / "benchmark_out" / f"c.waves-7-{tag}" / "trace"
        d.mkdir(parents=True)
        traces[str(d)] = [["bench.wave", 0.0, extent], ["sched.cycle", 0, 9]]
    monkeypatch.setattr(progspans.tracereduce, "newest_xplane", lambda d: d)
    monkeypatch.setattr(progspans, "host_events", lambda x: traces[x])
    obs = {"traced": {"waves": 1, "reduced": {"window_s": 2200.0 / 1e9}}}
    assert progspans._this_runs_events(obs)[0][2] == 2200.0
    obs["traced"]["reduced"]["window_s"] = 500.0 / 1e9
    assert progspans._this_runs_events(obs)[0][2] == 500.0
    obs["traced"]["reduced"]["window_s"] = 1.0      # neither: nothing
    assert progspans._this_runs_events(obs) is None
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "other",
                                      "--seed", "7"])
    assert progspans._this_runs_events(obs) is None


METRICS_BEFORE = """\
scheduler_pod_stage_duration_seconds_bucket{stage="queue.wait",le="0.001"} 10
scheduler_pod_stage_duration_seconds_bucket{stage="queue.wait",le="0.016"} 10
scheduler_pod_stage_duration_seconds_bucket{stage="queue.wait",le="+Inf"} 10
scheduler_pod_stage_duration_seconds_bucket{stage="bind.post",le="0.001"} 10
scheduler_pod_stage_duration_seconds_bucket{stage="bind.post",le="0.016"} 10
scheduler_pod_stage_duration_seconds_bucket{stage="bind.post",le="+Inf"} 10
scheduler_loop_stage_seconds_total{stage="loop.idle"} 5.0
scheduler_loop_stage_seconds_total{stage="cycle"} 1.0
scheduler_gc_pause_seconds_total{generation="0"} 0.5
scheduler_gc_pause_seconds_total{generation="2"} 1.0
"""
METRICS_AFTER = """\
scheduler_pod_stage_duration_seconds_bucket{stage="queue.wait",le="0.001"} 10
scheduler_pod_stage_duration_seconds_bucket{stage="queue.wait",le="0.016"} 110
scheduler_pod_stage_duration_seconds_bucket{stage="queue.wait",le="+Inf"} 210
scheduler_pod_stage_duration_seconds_bucket{stage="bind.post",le="0.001"} 208
scheduler_pod_stage_duration_seconds_bucket{stage="bind.post",le="0.016"} 210
scheduler_pod_stage_duration_seconds_bucket{stage="bind.post",le="+Inf"} 210
scheduler_loop_stage_seconds_total{stage="loop.idle"} 25.0
scheduler_loop_stage_seconds_total{stage="cycle"} 9.0
scheduler_gc_pause_seconds_total{generation="0"} 0.7
scheduler_gc_pause_seconds_total{generation="2"} 2.8
"""


def _served_obs(before, after):
    return {"window": {"elapsed_s": 40.0},
            "prom": {"scheduler": prom.delta(prom.parse(after),
                                             prom.parse(before))}}


@pytest.mark.parametrize("metric, want", [
    # 200 pods in the window; rank 198 lies in the last bucket, whose lower
    # edge the quantile reports (+Inf has no width)
    ("queue_wait_p99_ms", 16.0),
    # rank 198 of 200 is the last of the 198 pods in the first bucket
    ("bind_post_p99_ms", 1.0),
    ("loop_idle_share.open", 100.0 * 20.0 / 40.0),   # not the cycle's 8 s
    ("gc_pause_share.open", 100.0 * 2.0 / 40.0),     # generations summed
])
def test_served_readers_on_a_hand_made_metrics_delta(metric, want):
    read = _reader(metric)
    assert read(_served_obs(METRICS_BEFORE, METRICS_AFTER)) \
        == pytest.approx(want)
    # the parent's /metrics has none of these series: nothing, not 0
    other = "scheduler_e2e_scheduling_duration_seconds_count 5\n"
    assert read(_served_obs(other, other)) is None
    assert read({}) is None


def test_an_idle_window_reads_zero_not_nothing():
    """The series are there and did not move (a stage that did not occur)."""
    obs = _served_obs(METRICS_AFTER, METRICS_AFTER)
    assert _reader("loop_idle_share.open")(obs) == 0.0
    assert _reader("gc_pause_share.open")(obs) == 0.0
    assert _reader("queue_wait_p99_ms")(obs) is None    # no pod, no quantile
