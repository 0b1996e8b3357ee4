"""Who waits for whom: each device batch of the traced waves, joined to the
program's spans that launched and awaited it.

Route. The program (`kubernetes_tpu/models/tpu_scheduler.py`) opens every
`sched.device.dispatch` with the stats `seq` (the dispatch's ordinal in that
scheduler's life), `inflight` (the pipeline depth it found), `engine`, `batch`
and, on the scans, `steps`; the `sched.device.wait` that retires the batch
opens with the same `seq`; the loop's `sched.cycle` opens with `pauses` (the
collections its stage table has been charged with: the sign that it books
`sched.gc.pause` at all). `run.py` hands readers the reduced trace, which keeps
no stats and no single runs, so `of` finds this run's `.xplane.pb` as
`spanstats.this_runs` finds it (that function, given this module's loader in
place of its own), loads it ONCE a run (cached on `obs["timeline"]`), and
keeps: the `bench.*` spans, the `sched.*` spans with those stats, and the runs
of the scheduling programs (`tracereduce.SCHEDULING_PROGRAMS`) on the first
device plane. Inside the last `obs["traced"]["waves"]` `bench.wave` spans the
k-th program run is joined to the k-th dispatch (by `seq`) and to the wait of
equal `seq`; where the three counts differ, or a span carries no `seq` (the
parent of the PR that added it), nothing is joined and the `[timeline]` line
says so. Readers under `layer_metrics/`: `kernel_hidden_share` and
`scan_step_us` (no join: interval overlap, seconds over steps), `fetch_tail_ms`
and `launch_gap_ms` (the join), `collector_pause_share` (the sign).

On the chip a program's run is one event of the device plane's "XLA Modules"
line. A CPU rehearsal has no device plane: `tracereduce.load` hands over the
executor threads' operations one by one under their module's name
(`/rehearsal:CPU`), and `standin_runs` groups them into runs: an operation
that lies inside no `while` operation of its program is outside the loop body
and so occurs once a run, whatever order the executor's threads start them
in; the k-th occurrence of every such operation belongs to run k, and a run
spans from the first start to the last end among them (the `while` operations
are among them and cover the loop). `testdata/timeline_small.json` is a
recording that pins it. Never a device number.
"""

from __future__ import annotations

from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import progspans
import spanstats
import tracereduce

STANDIN = "/rehearsal:CPU"
# the stats kept, by span (everything else of a `sched.*` span is its extent)
KEPT = {"device.dispatch": ("seq", "inflight", "engine", "batch", "steps"),
        "device.wait": ("seq",)}


def load(xplane_path: str) -> Tuple[List[list], dict]:
    """(`bench.*` spans as `progspans.host_events` gives them, and
    `{"sched": [[stage, start_ns, dur_ns, stats], ...], "runs": [[program,
    start_ns, dur_ns], ...], "plane": name, "books_pauses": bool}`) of one
    trace. The pair is what `spanstats.this_runs` takes from a loader."""
    from jax.profiler import ProfileData
    bench, sched, chip = [], [], {}
    books_pauses = None
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/device:TPU:"):
            runs = chip.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Modules":       # one event a run
                    runs.extend([tracereduce._short(e.name),
                                 float(e.start_ns), float(e.duration_ns)]
                                for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name.startswith(tracereduce.SPAN_PREFIX):
                        bench.append([name, float(e.start_ns),
                                      float(e.duration_ns)])
                    elif name.startswith(progspans.PREFIX):
                        stage = name[len(progspans.PREFIX):]
                        stats = {}
                        if stage in KEPT:
                            got = dict(e.stats)
                            stats = {k: got[k] for k in KEPT[stage]
                                     if k in got}
                        elif stage == "cycle" and books_pauses is None:
                            books_pauses = "pauses" in dict(e.stats)
                        sched.append([stage, float(e.start_ns),
                                      float(e.duration_ns), stats])
    if chip:
        plane = min(chip)
        runs = chip[plane]
    else:
        plane = STANDIN
        standin = tracereduce.load(xplane_path)["devices"].get(STANDIN)
        runs = standin_runs(standin["ops"], standin["modules"]) \
            if standin else []
    runs = sorted((r for r in runs
                   if r[0].startswith(tracereduce.SCHEDULING_PROGRAMS)),
                  key=lambda r: r[1])
    return bench, {"sched": sched, "runs": runs, "plane": plane,
                   "books_pauses": bool(books_pauses)}


def standin_runs(ops: Sequence[Sequence], modules: Sequence[Sequence]
                 ) -> List[list]:
    """The CPU stand-in's operations (`tracereduce.load`: `ops` and `modules`
    side by side, one entry an operation) grouped into runs, program by
    program: `[program, start_ns, dur_ns]`. See the module's docstring."""
    by_program: Dict[str, List[Tuple[str, float, float]]] = {}
    for (op, start, dur), (program, _s, _d) in zip(ops, modules):
        by_program.setdefault(program, []).append((op, start, start + dur))
    runs = []
    for program, events in by_program.items():
        events.sort(key=lambda e: e[1])
        loops = [(s, e) for op, s, e in events if op.startswith("while")]
        outside = [ev for ev in events
                   if not any(s <= ev[1] and ev[2] <= e and (s, e) != ev[1:]
                              for s, e in loops)]
        counts = Counter(op for op, _s, _e in outside)
        if not counts:
            continue
        n_runs = Counter(counts.values()).most_common(1)[0][0]
        seen: Dict[str, int] = {}
        spans = [[np.inf, -np.inf] for _ in range(n_runs)]
        for op, start, end in outside:
            if counts[op] != n_runs:
                continue                 # cut by an edge of the trace
            k = seen.get(op, 0)
            seen[op] = k + 1
            spans[k] = [min(spans[k][0], start), max(spans[k][1], end)]
        runs.extend([program, s, e - s] for s, e in spans)
    return runs


def of(obs: dict) -> Optional[dict]:
    """This run's timeline, loaded and reduced once for all readers; None
    where the run has no trace of waves."""
    if "timeline" not in obs:
        traced = obs.get("traced") or {}
        own, spanstats.span_stats = spanstats.span_stats, (
            lambda path, _name, _key: load(path))
        try:
            got = spanstats.this_runs(obs, None, None)
        finally:
            spanstats.span_stats = own
        obs["timeline"] = reduce(got[0], got[1], int(traced["waves"])) \
            if got else None
        if obs["timeline"]:
            print(describe(obs["timeline"]), flush=True)
    return obs["timeline"]


def _inside(events: Sequence[Sequence], waves: Sequence[Sequence]
            ) -> List[List[list]]:
    """Per wave, the events that start inside it, by start."""
    return [sorted((e for e in events if s <= e[1] < s + d),
                   key=lambda e: e[1]) for _name, s, d in waves]


def _outside_s(start: float, end: float, us: np.ndarray, ue: np.ndarray
               ) -> float:
    """Nanoseconds of [start, end] outside the disjoint intervals."""
    covered = np.clip(np.minimum(ue, end) - np.maximum(us, start), 0, None)
    return (end - start) - float(covered.sum())


def _why_not(seqs: List, by_seq: dict, n_runs: int) -> str:
    """Why a wave's dispatches (their `seq`s), waits (by `seq`) and program
    runs cannot be joined one to one; empty where they can."""
    if None in seqs or None in by_seq:
        return "no seq on a span"
    if not len(seqs) == len(by_seq) == n_runs:
        return "counts differ"
    if len(set(seqs)) != len(seqs) or set(seqs) != set(by_seq):
        return "seqs differ"
    return ""


def reduce(bench: Sequence[Sequence], trace: dict, waves: int
           ) -> Optional[dict]:
    """The traced waves' timeline. `waves`: per wave the batches joined
    (empty where none could be); `kernel_s` and `hidden_s`: the scheduling
    programs' device seconds in those waves and the part outside every
    `device.wait`; `scan_s`, `steps`: the scan programs' seconds and steps
    (None where a lap dispatch shares the waves and nothing is joined).
    None where the trace holds no wave."""
    wave_spans = sorted((e for e in bench if e[0] == spanstats.WAVE),
                        key=lambda e: e[1])[-waves:] if waves else []
    if not wave_spans:
        return None
    sched = trace["sched"]
    dispatches = _inside([e for e in sched if e[0] == "device.dispatch"],
                         wave_spans)
    waits = _inside([e for e in sched if e[0] == "device.wait"], wave_spans)
    runs = _inside(trace["runs"], wave_spans)
    every_wait = [w for ws in waits for w in ws]
    us, ue = tracereduce.union(
        np.array([w[1] for w in every_wait], float),
        np.array([w[1] + w[2] for w in every_wait], float))
    out = {"plane": trace["plane"], "books_pauses": trace["books_pauses"],
           "waves": [], "why_not": "", "kernel_s": 0.0, "hidden_s": 0.0,
           "scan_s": None, "steps": 0}
    for ws in runs:
        for _n, start, dur in ws:
            out["kernel_s"] += dur / 1e9
            out["hidden_s"] += _outside_s(start, start + dur, us, ue) / 1e9
    # -- the join
    scan_s, all_joined = 0.0, True
    for k, (ds, ws, rs) in enumerate(zip(dispatches, waits, runs)):
        by_seq = {w[3].get("seq"): w for w in ws}
        why_not = _why_not([d[3].get("seq") for d in ds], by_seq, len(rs))
        if why_not:
            all_joined = False
            out["why_not"] = out["why_not"] or (
                f"wave {k}: {len(ds)} dispatches, {len(ws)} waits, "
                f"{len(rs)} program runs, {why_not}")
            out["waves"].append([])
            continue
        batches = []
        for d, r in zip(sorted(ds, key=lambda d: d[3]["seq"]), rs):
            stats, w = d[3], by_seq[d[3]["seq"]]
            run_end, wait_end = r[1] + r[2], w[1] + w[2]
            batches.append({
                "seq": int(stats["seq"]), "engine": str(stats.get("engine")),
                "pods": int(stats.get("batch", 0)),
                "inflight": int(stats.get("inflight", 0)),
                "steps": stats.get("steps"),
                "dispatch_ms": d[2] / 1e6,
                "launch_gap_ms": (r[1] - d[1]) / 1e6,
                "kernel_ms": r[2] / 1e6,
                "hidden_ms": _outside_s(r[1], run_end, us, ue) / 1e6,
                # the part of the wait after the program's end: where the
                # program ended before the wait began, the time between
                # the two was the host's own work, no loss
                "fetch_tail_ms": max(0.0, wait_end - max(run_end, w[1])) / 1e6})
            if stats.get("steps") is not None:
                scan_s += r[2] / 1e9
        out["waves"].append(batches)
    every = [d[3] for ds in dispatches for d in ds]
    out["steps"] = int(sum(int(s["steps"]) for s in every
                           if s.get("steps") is not None))
    if every and all(s.get("steps") is not None for s in every):
        out["scan_s"] = out["kernel_s"]      # every program run is a scan's
    elif all_joined and out["steps"]:
        out["scan_s"] = scan_s
    return out


def batches(tl: Optional[dict]) -> List[dict]:
    return [b for wave in (tl or {}).get("waves", ()) for b in wave]


def describe(tl: dict) -> str:
    """The `[timeline]` line: per traced wave each batch as `seq engine pods:
    dispatch / launch gap / kernel / hidden / fetch tail`, in ms."""
    parts = []
    for k, wave in enumerate(tl["waves"]):
        rows = "; ".join(
            f"{b['seq']} {b['engine']} {b['pods']}: {b['dispatch_ms']:.3f} / "
            f"{b['launch_gap_ms']:.3f} / {b['kernel_ms']:.3f} / "
            f"{b['hidden_ms']:.3f} / {b['fetch_tail_ms']:.3f}" for b in wave)
        parts.append(f"wave {k} [{rows}]")
    hidden = (f"{100.0 * tl['hidden_s'] / tl['kernel_s']:.2f}"
              if tl["kernel_s"] > 0 else "-")
    return (f"[timeline] {len(tl['waves'])} traced wave(s) on {tl['plane']}, "
            f"{len(batches(tl))} batch(es) joined"
            + (f" (NOT joined: {tl['why_not']})" if tl["why_not"] else "")
            + f"; program seconds {tl['kernel_s']:.6f}, outside every wait "
            f"{hidden} %; scan steps {tl['steps']}; batches as `seq engine "
            f"pods: dispatch / launch gap / kernel / hidden / fetch tail` "
            # " | ": the driver's own per-wave lines are found by "] wave "
            f"in ms: " + " | ".join(parts))
