"""One stat of one of the program's spans, from this run's profiler trace.
`run.py` hands readers the reduced trace, which keeps no stats, so a reader
that wants one (the attr a stage was opened with) finds the trace and loads
it again, as `progspans.py` does for the stages' times. A run without a
trace reads nothing."""

import glob
import os

import progspans
import tracereduce

WAVE = tracereduce.SPAN_PREFIX + "wave"


def span_stats(xplane_path, name, key):
    """(`bench.*` spans as `progspans.host_events` gives them, and
    `[start_ns, stat or None]` of every host span called `name`) of one
    trace."""
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
                elif e.name == name:
                    found.append([float(e.start_ns), dict(e.stats).get(key)])
    return bench, found


def this_runs(obs, name, key):
    """`span_stats` of this run's own trace; None where there is none."""
    traced = obs.get("traced") or {}
    workload = progspans._argument("--workload")
    seed = progspans._argument("--seed")
    if not traced.get("waves") or workload is None or seed is None:
        return None
    dirs = glob.glob(os.path.join(progspans.ROOT, "benchmark_out",
                                  f"{workload}-{seed}-*", "trace"))
    dirs.sort(key=os.path.getmtime, reverse=True)
    want = (traced.get("reduced") or {}).get("window_s")
    for trace_dir in dirs:
        try:
            xplane = tracereduce.newest_xplane(trace_dir)
            got = span_stats(xplane, name, key) if xplane else None
        except OSError:          # another run's directory, removed meanwhile
            continue
        if got is not None and (len(dirs) == 1
                                or progspans._extent_s(got[0]) == want):
            return got
    return None


def in_traced_waves(bench, found, waves):
    """The stats of the spans that start inside the last `waves`
    `bench.wave` spans, None for a span that carries none."""
    inside = sorted((e for e in bench if e[0] == WAVE),
                    key=lambda e: e[1])[-waves:] if waves else []
    return [stat for start, stat in found
            if any(s <= start < s + d for _name, s, d in inside)]
