"""Device pipeline: of the batches dispatched in the traced waves, the share
that the normalising scan placed (`ops/kernel.py` `coupling`: scores
recomputed and normalised over the kept rows at every step), in %. Read from
the program's `sched.device.dispatch` spans in this run's trace, each of
which carries the engine that the built plan's coupling chose as the stat
`engine` (`scan_carried`, `scan_normalised` or `lap`; the same label counts
`scheduler_device_batches_total`). `run.py` hands readers the reduced trace,
which keeps no stats, so the trace is found and loaded again as
`progspans.py` does it. A program whose dispatch spans carry no engine (the
parent of the PR that added the stat), a run without a trace, and traced
waves without a dispatch read nothing."""

import glob
import os

import progspans
import tracereduce

DISPATCH = progspans.PREFIX + "device.dispatch"
WAVE = tracereduce.SPAN_PREFIX + "wave"


def dispatches(xplane_path):
    """(`bench.*` spans as `progspans.host_events` gives them, and
    `[start_ns, engine or None]` of every dispatch span) of one trace."""
    from jax.profiler import ProfileData
    bench, found = [], []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracereduce.SPAN_PREFIX):
                    bench.append([e.name, float(e.start_ns),
                                  float(e.duration_ns)])
                elif e.name == DISPATCH:
                    engine = dict(e.stats).get("engine")
                    found.append([float(e.start_ns),
                                  None if engine is None else str(engine)])
    return bench, found


def _this_runs_dispatches(obs):
    workload = progspans._argument("--workload")
    seed = progspans._argument("--seed")
    if workload is None or seed is None:
        return None
    dirs = glob.glob(os.path.join(progspans.ROOT, "benchmark_out",
                                  f"{workload}-{seed}-*", "trace"))
    dirs.sort(key=os.path.getmtime, reverse=True)
    want = ((obs.get("traced") or {}).get("reduced") or {}).get("window_s")
    for trace_dir in dirs:
        try:
            xplane = tracereduce.newest_xplane(trace_dir)
            got = dispatches(xplane) if xplane else None
        except OSError:          # another run's directory, removed meanwhile
            continue
        if got is not None and (len(dirs) == 1
                                or progspans._extent_s(got[0]) == want):
            return got
    return None


def share(bench, found, waves, engine="scan_normalised"):
    """Per cent of the dispatches that start inside the last `waves`
    `bench.wave` spans and carry `engine`; None where none carries any."""
    inside = sorted((e for e in bench if e[0] == WAVE),
                    key=lambda e: e[1])[-waves:] if waves else []
    engines = [eng for start, eng in found
               if any(s <= start < s + d for _name, s, d in inside)]
    if not engines or all(eng is None for eng in engines):
        return None
    return 100.0 * sum(eng == engine for eng in engines) / len(engines)


def read(obs):
    traced = obs.get("traced") or {}
    got = _this_runs_dispatches(obs) if traced.get("waves") else None
    if not got:
        return None
    return share(got[0], got[1], int(traced["waves"]))
