"""The program's own account of its loop, read by the benchmark.

Two sources, both written by `kubernetes_tpu/core/spans.py` `StageLedger`:

- In process (`waves`): the `sched.<stage>` spans the program puts into the
  profiler trace beside the device operations. `run.py` hands readers only
  the reduced trace, which keeps `bench.*` host spans, so `stage_seconds`
  loads this run's `.xplane.pb` again: the run's directory is
  `benchmark_out/<workload>-<seed>-*` (`sys.argv` carries both names, and
  readers run before `run.py` removes it). Where several runs share checkout,
  cell and seed, this run's trace is the one whose `bench.*` spans have the
  extent `run.py` reduced (`obs["traced"]["reduced"]["window_s"]`).
  Self times are computed as `tracereduce._self_intervals` computes them for
  the idle gaps, inside the last `obs["traced"]["waves"]` `bench.wave` spans.
- Over HTTP (`open`): the scheduler's `/metrics` delta the driver already
  keeps in `obs["prom"]["scheduler"]`; `pod_stage_quantile_ms` and
  `counter_share` read it with `prom.py`.

A program that has no such span or series (the parent of the PR that added
them) reads None everywhere, and the metric is left out. With the spans
there, a stage that did not occur in the traced waves reads 0.
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Dict, List, Optional

import prom
import tracereduce

PREFIX = "sched."
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _argument(flag: str) -> Optional[str]:
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == flag and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith(flag + "="):
            return arg.split("=", 1)[1]
    return None


def host_events(xplane_path: str) -> List[list]:
    """`[name, start_ns, dur_ns]` of every `sched.*` and `bench.*` host span."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events
                       if e.name.startswith((PREFIX, tracereduce.SPAN_PREFIX)))
    return out


def _extent_s(events: List[list]) -> float:
    bench = [e for e in events if e[0].startswith(tracereduce.SPAN_PREFIX)]
    if not bench:
        return 0.0
    return (max(e[1] + e[2] for e in bench) - min(e[1] for e in bench)) / 1e9


def _this_runs_events(obs: dict) -> Optional[List[list]]:
    workload, seed = _argument("--workload"), _argument("--seed")
    if workload is None or seed is None:
        return None
    found = glob.glob(os.path.join(ROOT, "benchmark_out",
                                   f"{workload}-{seed}-*", "trace"))
    found.sort(key=os.path.getmtime, reverse=True)
    want = ((obs.get("traced") or {}).get("reduced") or {}).get("window_s")
    for trace_dir in found:
        try:
            xplane = tracereduce.newest_xplane(trace_dir)
            events = host_events(xplane) if xplane else None
        except OSError:          # another run's directory, removed meanwhile
            continue
        if events is not None and (len(found) == 1
                                   or _extent_s(events) == want):
            return events
    return None


def reduce_stages(events: List[list], waves: int) -> Optional[dict]:
    """Self seconds of each `sched.*` stage inside the last `waves`
    `bench.wave` spans, the summed wave seconds, and the seconds under no
    named stage (under no `sched.*` span at all, or under `sched.cycle`
    alone). None where the trace holds no `sched.*` span or no wave."""
    spans = [[e[0][len(PREFIX):], e[1], e[2]] for e in events
             if e[0].startswith(PREFIX)]
    wave_name = tracereduce.SPAN_PREFIX + "wave"
    wave_spans = sorted((e for e in events if e[0] == wave_name),
                        key=lambda e: e[1])[-waves:] if waves else []
    if not spans or not wave_spans:
        return None
    self_s: Dict[str, float] = {}
    wave_s = 0.0
    for _name, start, dur in wave_spans:
        wave_s += dur / 1e9
        for name, intervals in tracereduce._self_intervals(
                spans, start, start + dur).items():
            self_s[name] = self_s.get(name, 0.0) + sum(
                b - a for a, b in intervals) / 1e9
    named = sum(v for k, v in self_s.items() if k != "cycle")
    return {"wave_s": wave_s, "self_s": self_s, "unnamed_s": wave_s - named}


def stage_seconds(obs: dict) -> Optional[dict]:
    """`reduce_stages` of this run's trace, loaded once for all readers."""
    if "progspans" not in obs:
        traced = obs.get("traced") or {}
        events = _this_runs_events(obs) if traced.get("waves") else None
        got = reduce_stages(events, int(traced["waves"])) if events else None
        obs["progspans"] = got
        if got:
            shares = {k: round(float(100.0 * v / got["wave_s"]), 2)
                      for k, v in sorted(got["self_s"].items())}
            print(f"[progspans] {traced['waves']} traced wave(s), "
                  f"{got['wave_s']:.4f}s: self time by stage, % of wave time "
                  f"{shares}; under no named stage "
                  f"{100.0 * got['unnamed_s'] / got['wave_s']:.2f}", flush=True)
    return obs["progspans"]


def stage_share(obs: dict, stage: str) -> Optional[float]:
    """Self time of `sched.<stage>` over the traced waves' time, in %."""
    got = stage_seconds(obs)
    if not got or got["wave_s"] <= 0:
        return None
    return 100.0 * got["self_s"].get(stage, 0.0) / got["wave_s"]


def _scheduler_series(obs: dict, name: str) -> Optional[dict]:
    series = (obs.get("prom") or {}).get("scheduler") or {}
    return series if any(n.startswith(name) for n, _ in series) else None


def pod_stage_quantile_ms(obs: dict, stage: str, q: float) -> Optional[float]:
    """Quantile of `scheduler_pod_stage_duration_seconds{stage=...}` over the
    window, in milliseconds, at bucket resolution."""
    name = "scheduler_pod_stage_duration_seconds"
    series = _scheduler_series(obs, name)
    if series is None:
        return None
    mine = {k: v for k, v in series.items() if ("stage", stage) in k[1]}
    got = prom.quantile(mine, name, q)
    return None if got is None else 1e3 * got


def counter_share(obs: dict, name: str, **labels: str) -> Optional[float]:
    """Window delta of a seconds counter over the window's seconds, in %."""
    series = _scheduler_series(obs, name)
    elapsed = (obs.get("window") or {}).get("elapsed_s")
    if series is None or not elapsed:
        return None
    return 100.0 * prom.total(series, name, **labels) / elapsed
