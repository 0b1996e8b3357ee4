"""The program's spans of a churn wave, with the stats the readers of
`churn-5k.waves` want: each `sched.plan.build` and `sched.plan.adopt` with
the `cause` of a full build, each `sched.postfilter.preempt` with the dry
run's `engine`, its parts and the kernel's shapes, and the `sched.cycle`
turns around them. `run.py` hands readers the reduced trace, which keeps no
stats, so `of` finds this run's `.xplane.pb` as `spanstats.this_runs` finds
it (that function, given this module's loader in place of its own, as
`timeline.of` does), loads it ONCE a run (cached on `obs["churnspans"]`) and
keeps the spans that start inside the last `obs["traced"]["waves"]`
`bench.wave` spans. A run without a trace reads nothing; a program without
the stats (the parent of the PR that added them) gives spans whose stats
are empty, and the readers return None on those.
"""

from __future__ import annotations

from typing import List, Optional

import progspans
import spanstats

KEPT = {"plan.build": ("kind", "cause"), "plan.adopt": ("kind", "cause"),
        "postfilter.preempt": ("engine", "candidates", "victims_ms",
                               "plan_ms", "dispatch_ms", "fetch_ms",
                               "host_ms", "rows", "k", "r"),
        "cycle": ()}


def load(xplane_path: str):
    """(`bench.*` spans, `[[stage, start_ns, dur_ns, stats], ...]` of the
    stages of `KEPT`) of one trace."""
    from jax.profiler import ProfileData
    bench, sched = [], []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(spanstats.tracereduce.SPAN_PREFIX):
                    bench.append([name, float(e.start_ns),
                                  float(e.duration_ns)])
                elif name.startswith(progspans.PREFIX):
                    stage = name[len(progspans.PREFIX):]
                    if stage in KEPT:
                        got = dict(e.stats) if KEPT[stage] else {}
                        sched.append([stage, float(e.start_ns),
                                      float(e.duration_ns),
                                      {k: got[k] for k in KEPT[stage]
                                       if k in got}])
    return bench, sched


def in_waves(bench, sched, waves: int) -> Optional[dict]:
    """The kept spans that start inside the last `waves` `bench.wave`
    spans, and those waves' seconds; None where there is no wave."""
    inside = sorted((e for e in bench if e[0] == spanstats.WAVE),
                    key=lambda e: e[1])[-waves:] if waves else []
    if not inside:
        return None
    return {"wave_s": sum(d for _n, _s, d in inside) / 1e9,
            "spans": sorted((e for e in sched
                             if any(s <= e[1] < s + d for _n, s, d in inside)),
                            key=lambda e: e[1])}


def of(obs: dict) -> Optional[dict]:
    if "churnspans" not in obs:
        traced = obs.get("traced") or {}
        own, spanstats.span_stats = spanstats.span_stats, (
            lambda path, _name, _key: load(path))
        try:
            got = spanstats.this_runs(obs, None, None)
        finally:
            spanstats.span_stats = own
        obs["churnspans"] = in_waves(got[0], got[1], int(traced["waves"])) \
            if got else None
    return obs["churnspans"]


def failed_attempts(spans: List[list]) -> List[dict]:
    """Per `postfilter.preempt` span: the turn (`cycle`) around it, the
    stage itself and what it said of its parts, in ms."""
    turns = [e for e in spans if e[0] == "cycle"]
    out = []
    for stage, start, dur, stats in spans:
        if stage != "postfilter.preempt":
            continue
        around = [t for t in turns if t[1] <= start < t[1] + t[2]]
        turn = min(around, key=lambda t: t[2]) if around else None
        inner = [e for e in spans if turn and e[0] in ("plan.build",
                                                        "plan.adopt")
                 and turn[1] <= e[1] < turn[1] + turn[2]]
        part = {"turn_ms": turn[2] / 1e6 if turn else None,
                "postfilter_ms": dur / 1e6,
                "plan_build_ms": sum(e[2] for e in inner
                                     if e[0] == "plan.build") / 1e6,
                "plan_adopt_ms": sum(e[2] for e in inner
                                     if e[0] == "plan.adopt") / 1e6}
        for key, value in stats.items():
            try:
                part[key] = float(value)
            except (TypeError, ValueError):
                part[key] = value
        out.append(part)
    return out


def builds_by_cause(spans: List[list]) -> dict:
    """cause -> [builds, seconds in `plan.build` + `plan.adopt`] over the
    full builds (and the adoptions of their sessions) that say a cause."""
    out = {}
    for stage, _start, dur, stats in spans:
        if stage in ("plan.build", "plan.adopt") and stats.get("cause"):
            got = out.setdefault(str(stats["cause"]), [0, 0.0])
            got[0] += stage == "plan.build"
            got[1] += dur / 1e9
    return out
