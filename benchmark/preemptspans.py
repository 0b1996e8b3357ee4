"""The program's spans of a wave that preempts, with the stats the readers
of `preempt-5k.waves` want: each `sched.postfilter.preempt` with what the
attempt says of itself (the dry run's `engine`, parts and shapes, and since
the PR that added this file `select_ms`, `verify_ms`, `evict_ms`, `victims`,
`nominated`, `nom_rows`), each `sched.nominated.eval` (a nominated pod's own
node, first and alone: `outcome`, `engine`), each `sched.plan.build` and
`sched.plan.adopt` with the `cause` of a full build, and the `sched.cycle`
turns around them. `churnspans.py` keeps a fixed list of stages and stats;
this is the same loader over another list (`churnspans.load` reads the
module's `KEPT`, so the list is swapped for the one call), found and cached
as `churnspans.of` does it (`obs["preemptspans"]`). A run without a trace
reads nothing; a program without the stage or the stats gives spans without
them, and the readers return None on those.
"""

from __future__ import annotations

from typing import List, Optional

import churnspans
import spanstats

KEPT = {"plan.build": ("kind", "cause"), "plan.adopt": ("kind", "cause"),
        "postfilter.preempt": ("engine", "candidates", "victims_ms",
                               "plan_ms", "dispatch_ms", "fetch_ms",
                               "host_ms", "rows", "k", "r", "select_ms",
                               "verify_ms", "evict_ms", "victims",
                               "nominated", "nom_rows"),
        "nominated.eval": ("outcome", "engine"),
        "cycle": ()}


def load(xplane_path: str):
    own, churnspans.KEPT = churnspans.KEPT, KEPT
    try:
        return churnspans.load(xplane_path)
    finally:
        churnspans.KEPT = own


def of(obs: dict) -> Optional[dict]:
    if "preemptspans" not in obs:
        traced = obs.get("traced") or {}
        own, spanstats.span_stats = spanstats.span_stats, (
            lambda path, _name, _key: load(path))
        try:
            got = spanstats.this_runs(obs, None, None)
        finally:
            spanstats.span_stats = own
        obs["preemptspans"] = churnspans.in_waves(
            got[0], got[1], int(traced["waves"])) if got else None
    return obs["preemptspans"]


def stage(spans: List[list], name: str) -> List[list]:
    return [e for e in spans if e[0] == name]


def turn_ms(spans: List[list], inner: list) -> Optional[float]:
    """The `sched.cycle` turn a span lies in, in ms (the shortest that holds
    its start); None where there is none."""
    around = [t for t in stage(spans, "cycle")
              if t[1] <= inner[1] < t[1] + t[2]]
    return min(t[2] for t in around) / 1e6 if around else None


def mean_stats(spans: List[list]) -> dict:
    """The mean of every number the spans say, and how many say it."""
    sums, counts = {}, {}
    for _stage, _start, _dur, stats in spans:
        for key, value in stats.items():
            try:
                sums[key] = sums.get(key, 0.0) + float(value)
            except (TypeError, ValueError):
                continue
            counts[key] = counts.get(key, 0) + 1
    return {k: round(sums[k] / counts[k], 3) for k in sorted(sums)}
