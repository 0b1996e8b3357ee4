"""From a profiler trace to device busy/idle, kernel time and named idle gaps.

Two steps, so that the arithmetic can be checked on a small recorded trace
(``testdata/trace_small.json``, `tests/benchmark/test_benchmark_harness.py`):

1. ``load(xplane_path)``: the `.xplane.pb` the JAX profiler wrote, read with
   ``jax.profiler.ProfileData``, cut down to plain lists
   ``{"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
   "modules": [...]}}, "host": [[name, start_ns, dur_ns], ...]}``.
   Device planes are ``/device:TPU:<n>``: line "XLA Ops" gives the operations,
   line "XLA Modules" the jitted programs. ``host`` holds the benchmark's own
   ``jax.profiler.TraceAnnotation`` spans (names starting ``bench.``), which
   the profiler puts on the same clock. A CPU rehearsal has no device plane:
   the CPU client's executor threads stand in (events that carry an
   ``hlo_op``), labelled ``/rehearsal:CPU`` — never a device number.
2. ``reduce(events, t0_ns, t1_ns)``: busy seconds per device (union of the
   operation intervals inside the window), averaged over devices; seconds by
   operation and by program; idle gaps (the complement on the first device)
   attributed to the innermost benchmark span they fall under.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SPAN_PREFIX = "bench."
UNTRACKED = "outside_spans"


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _short(name: str) -> str:
    """An operation's event is named by its whole HLO line
    (`%fusion.80 = u32[8192]{...} fusion(...)`): keep the instruction name."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(xplane_path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices: Dict[str, dict] = {}
    host: List[list] = []
    standin = {"ops": [], "modules": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    key = "ops"
                elif line.name == "XLA Modules":
                    key = "modules"
                else:
                    continue
                dev[key].extend([_short(e.name), float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                executor = line.name.startswith(("tf_XLAPjRtCpuClient",
                                                 "tf_XLAEigen"))
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name[len(SPAN_PREFIX):],
                                     float(e.start_ns), float(e.duration_ns)])
                    elif executor and e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            standin["ops"].append(
                                [e.name, float(e.start_ns),
                                 float(e.duration_ns)])
                            standin["modules"].append(
                                [str(stats.get("hlo_module", "")),
                                 float(e.start_ns), float(e.duration_ns)])
    if not devices and standin["ops"]:
        devices["/rehearsal:CPU"] = standin
    return {"devices": devices, "host": host}


def _clip(events: Sequence[Sequence], t0: float, t1: float
          ) -> Tuple[List[str], np.ndarray, np.ndarray]:
    names, starts, ends = [], [], []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            names.append(name)
            starts.append(s)
            ends.append(e)
    return names, np.array(starts, float), np.array(ends, float)


def union(starts: np.ndarray, ends: np.ndarray
          ) -> Tuple[np.ndarray, np.ndarray]:
    """Merged, sorted, disjoint intervals covering the same points."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    first = np.concatenate(([True], s[1:] > reach[:-1]))
    last = np.concatenate((first[1:], [True]))
    return s[first], reach[last]


def _self_intervals(host: Sequence[Sequence], t0: float, t1: float
                    ) -> Dict[str, List[Tuple[float, float]]]:
    """Per span name, the intervals in which that span is the innermost one
    open (its duration minus what its children cover)."""
    names, starts, ends = _clip(host, t0, t1)
    order = sorted(range(len(names)), key=lambda i: (starts[i], -ends[i]))
    out: Dict[str, List[Tuple[float, float]]] = {}
    stack: List[Tuple[str, float]] = []     # open spans, innermost last
    cursor = t0                             # attributed up to here

    def emit(name: str, upto: float) -> float:
        if upto > cursor:
            out.setdefault(name, []).append((cursor, upto))
        return max(cursor, upto)

    for i in order:
        while stack and stack[-1][1] <= starts[i]:
            cursor = emit(*stack.pop())
        if stack:
            cursor = emit(stack[-1][0], starts[i])
        cursor = max(cursor, starts[i])
        # a span that outlives its parent (another thread) ends with it
        end = min(ends[i], stack[-1][1]) if stack else ends[i]
        stack.append((names[i], end))
    while stack:
        cursor = emit(*stack.pop())
    return out


def _overlap(gap_s: np.ndarray, gap_e: np.ndarray,
             intervals: List[Tuple[float, float]]) -> float:
    """Total length of the gaps' intersection with disjoint intervals."""
    if not intervals or len(gap_s) == 0:
        return 0.0
    iv = np.array(sorted(intervals), float)
    s, e = iv[:, 0], iv[:, 1]
    covered = np.concatenate(([0.0], np.cumsum(e - s)))

    def upto(t: np.ndarray) -> np.ndarray:
        k = np.searchsorted(s, t, side="right")      # intervals begun by t
        k0 = np.maximum(k - 1, 0)
        partial = np.where(k > 0, np.minimum(t, e[k0]) - s[k0], 0.0)
        return np.where(k > 0, covered[k0] + partial, 0.0)

    return float(np.sum(upto(gap_e) - upto(gap_s)))


def reduce(events: dict, t0_ns: Optional[float] = None,
           t1_ns: Optional[float] = None, top: int = 10) -> dict:
    """All seconds are inside [t0_ns, t1_ns] (default: the extent of the
    benchmark's spans, else of the device events)."""
    host = events["host"]
    devices = events["devices"]
    every = [ev for d in devices.values() for ev in d["ops"]]
    if t0_ns is None or t1_ns is None:
        base = host or every
        if not base:
            return {"busy_s": 0.0, "window_s": 0.0, "devices": 0,
                    "device_ops": [], "modules": {}, "idle_gaps": []}
        t0_ns = min(ev[1] for ev in base)
        t1_ns = max(ev[1] + ev[2] for ev in base)
    window_s = (t1_ns - t0_ns) / 1e9
    busy, op_seconds, modules = [], {}, {}
    gaps = None
    for plane in sorted(devices):
        names, starts, ends = _clip(devices[plane]["ops"], t0_ns, t1_ns)
        us, ue = union(starts, ends)
        busy.append(float(np.sum(ue - us)) / 1e9)
        for n, s, e in zip(names, starts, ends):
            op_seconds[n] = op_seconds.get(n, 0.0) + (e - s) / 1e9
        mnames, ms, me = _clip(devices[plane]["modules"], t0_ns, t1_ns)
        for n, s, e in zip(mnames, ms, me):
            m = modules.setdefault(n, {"seconds": 0.0, "runs": 0})
            m["seconds"] += (e - s) / 1e9
            m["runs"] += 1
        if gaps is None:
            edges_s = np.concatenate(([t0_ns], ue))
            edges_e = np.concatenate((us, [t1_ns]))
            keep = edges_e > edges_s
            gaps = (edges_s[keep], edges_e[keep])
    n_dev = max(1, len(devices))
    idle = []
    if gaps is not None:
        total = float(np.sum(gaps[1] - gaps[0]))
        named = 0.0
        for name, iv in _self_intervals(host, t0_ns, t1_ns).items():
            sec = _overlap(gaps[0], gaps[1], iv)
            if sec > 0:
                idle.append([name, sec / 1e9])
                named += sec
        if total - named > 1e3:
            idle.append([UNTRACKED, (total - named) / 1e9])
        idle.sort(key=lambda x: -x[1])
    ops = sorted(([n, s / n_dev] for n, s in op_seconds.items()),
                 key=lambda x: -x[1])
    return {"busy_s": sum(busy) / n_dev, "window_s": window_s,
            "devices": len(devices), "device_ops": ops[:top],
            "modules": modules, "idle_gaps": idle[:top]}


# The jitted scheduling programs, by the names XLA gives their modules.
SCHEDULING_PROGRAMS = ("jit_schedule_batch",)


def kernel_time(obs: dict):
    """(device seconds of the scheduling programs, device batches) over the
    traced waves of a run's observations, or None where there is no trace
    or no batch was dispatched."""
    traced = obs.get("traced") or {}
    reduced = traced.get("reduced")
    batches = traced.get("counters", {}).get("device_batches", 0)
    if not reduced or not batches:
        return None
    seconds = sum(m["seconds"] for name, m in reduced["modules"].items()
                  if name.startswith(SCHEDULING_PROGRAMS))
    return (seconds, batches) if seconds > 0 else None
