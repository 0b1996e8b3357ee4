"""Cross-process pod-lifecycle spans + the crash-safe flight recorder.

A dependency-free, OpenTelemetry-shaped span subsystem: one causal trace
per pod, stitched across every hop of the scheduling pipeline — queue
admission → queue wait → plan build (delta vs full) → device dispatch →
device wait → host commit → bind POST → apiserver WAL append → BOUND
fanout → foreign-shard observation. The reference measures the in-process
half of this with ``framework_extension_point_duration_seconds`` and
utiltrace (schedule_one.go:574); the cross-process half is Dapper-style
context propagation (PAPERS [Dapper]) over the repo's existing wire
surfaces: an ``X-Trace-Context`` header on the binding subresource, a
``tctx`` field on bulk-bind items and slim BOUND events.

Design constraints (this rides paths benchmarked at >10k pods/s):

- **Deterministic head sampling.** The 1-in-N sampling verdict is a pure
  function of the pod's uid (``sampled_uid``: a keyed CRC-32, no digest),
  and a sampled pod's trace id is a keyed hash of the same uid — so every
  process (N schedulers + the apiserver) independently agrees which pods
  are sampled and under which id with NO coordination, and the wire
  context only needs to carry the force-sample override
  (conflict/requeue/fallback/adoption paths record at 100%).
- **Lock-free recording.** Completed spans append to a per-process ring
  buffer (``collections.deque(maxlen=…)`` — append is GIL-atomic), so the
  reflector thread, the dispatcher worker, apiserver handler threads, and
  the scheduling loop all record without a lock. An unsampled pod pays the
  verdict and nothing else: one ``str.encode``, one CRC-32 and a modulo
  wherever ``context_for`` is asked about it (about 0.2 us), no digest, no
  ``SpanContext``, no row, and no memo that a wave could overflow.
- **Record-complete spans.** Almost every span is recorded retroactively
  with a known duration (``record``); live spans exist only as ``with``
  blocks (``span``) or the explicit ``start_span``/``end`` pair that the
  ``span-discipline`` analyzer checker polices (every started span must be
  ended on all paths, and neither spans nor metrics may appear inside
  jit-reachable code).

The scheduling LOOP accounts for its own time through the same recorder's
``StageLedger`` (one per scheduler): ``with stages.stage("plan.build")`` at
every boundary of the loop adds the stage's self time to a fixed table
(always on), lies in any profiler session as ``sched.<stage>`` on the
device's clock, feeds ``/metrics``, copies itself into the sampled pods'
traces, and applies the one slow-stage rule.

The flight recorder dumps the span ring plus the last-K events/errors per
process to ``<dir>/flightrec-<pid>.jsonl`` on SIGUSR2, on a slow-stage
breach, on unhandled crash (excepthook + atexit, with
``faulthandler`` covering native faults), and optionally on a periodic
timer — so a chaos ``kill -9`` (which no handler can observe) still leaves
a recent forensic artifact on disk instead of nothing.

Stage-name taxonomy (the stable contract bench/analyzer share) is pinned
in ``STAGES``/``CORE_CHAIN``; docs/OBSERVABILITY.md is the prose spec.
"""

from __future__ import annotations

import gc
import hashlib
import itertools
import json
import logging
import os
import sys
import threading
import time
import zlib
from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence

from ..compile_cache import COMPILE_EVENTS

logger = logging.getLogger("kubernetes_tpu")

TRACE_HEADER = "X-Trace-Context"

# The pinned stage names (docs/OBSERVABILITY.md). The trace analyzer CLI
# keys on these strings; renames are contract breaks.
STAGES = (
    "queue.admission",   # pod entered this scheduler's queue (event)
    "queue.wait",        # admission → pop
    "plan.build",        # session plan acquisition (attrs: kind=full|delta|resume, cause)
    "device.dispatch",   # kernel dispatch enqueue (attrs: batch, engine)
    "device.wait",       # blocked on the device result fetch
    "host.commit",       # assume/reserve/permit/bind host tail
    "bind.post",         # binding POST leaves the scheduler (attrs: bulk)
    "postfilter.preempt",  # PostFilter of a failed attempt (attrs: engine, parts)
    "nominated.eval",    # a nominated pod's own node, first and alone (attrs: outcome, engine)
    "api.bind",          # apiserver binding subresource commit
    "wal.append",        # durable WAL append of the BOUND event
    "bound.fanout",      # BOUND event fanout to watch streams
    "bound.observe",     # a watcher process decoded the BOUND event
    "pod.e2e",           # admission → bound (feeds the e2e histogram)
    "inbox.wait",        # oldest parked watch event → the drain that replays it
    # loop stages (StageLedger): the loop's own time, a tree under `cycle`
    "cycle",             # one turn of schedule_one; self time = still unnamed
    "queue.pop",         # popping + signing a batch off the active queue
    "inbox.drain",       # replaying parked watch events, journal classification
    "hint.walk",         # host-only binds off the score hint
    "hint.validate",     # a hint's journal replay + selection, per pod
    "plan.patch",        # journal delta patch of a live plan + carry
    "plan.ipa",          # required inter-pod term tables of a full plan build
    "plan.ipa_score",    # InterPodAffinity score-table walk of a full plan build
    "plan.adopt",        # session end: snapshot refresh, mirror adopts the carry
    "loop.idle",         # the binary's idle sleep and lease ticks
    "gc.settle",         # the collector policy's deliberate collect-and-freeze
    "gc.pause",          # a collection on the loop's own thread, inside a stage
)
# The ledger's fixed table: per-pod names that are also loop boundaries
# (plan.build … bind.post) keep their name, so a stage reads the same in a
# pod's trace, in the table and in a profiler trace.
LOOP_STAGES = ("cycle", "queue.pop", "inbox.drain", "hint.walk",
               "hint.validate", "plan.build", "plan.ipa", "plan.ipa_score",
               "plan.patch", "plan.adopt",
               "device.dispatch", "device.wait", "host.commit", "bind.post",
               "postfilter.preempt", "nominated.eval", "loop.idle",
               "gc.settle", "gc.pause")
# A bound pod's minimal complete chain. Device stages are optional (host-
# path pods legitimately skip them); observe spans prove the fanout landed.
CORE_CHAIN = ("queue.wait", "host.commit", "bind.post", "api.bind",
              "wal.append", "bound.fanout")
# Always-sampled forensic stages (recorded with force=True contexts).
FORCED_STAGES = ("bind.conflict", "device.fallback", "shard.adopt",
                 "trace.slow_stage", "replication.promote")
# The one slow-stage rule (schedule_one.go:574 logs any step over 100ms): a
# stage whose SELF time passes its threshold leaves a forced span and asks
# for a flight-recorder dump. The per-pod forms (leaf, annotate=False) keep
# the reference's 100ms. A batch or loop-turn stage grows with the batch and
# the cluster (at 5,000 nodes a plan build, a 10,000-event inbox replay or a
# session's adoption ordinarily takes 0.1-0.2s, a full collection inside one
# 0.1s more), so it gets a second: what that catches is a compile or cache
# load inside a dispatch, and the seconds-long device wait.
SLOW_STAGE_S = 0.1
SLOW_BATCH_STAGE_S = 1.0

_SAMPLE_ENV = "TPU_SCHED_TRACE_SAMPLE"
_ENABLE_ENV = "TPU_SCHED_TRACE"
DEFAULT_SAMPLE_N = 16


# The verdict's key (CRC-32's start value): part of the cross-process
# contract, like the digest of `trace_id_for`.
_SAMPLE_KEY = 0x5BD1E995


def sampled_uid(uid: str, sample_n: int) -> bool:
    """The head-sampling verdict: a pure function of the uid that every
    process computes alike (CRC-32 is not Python's per-process ``hash``),
    at a sixth of a digest's cost. The trace id is not needed for it."""
    return zlib.crc32(uid.encode(), _SAMPLE_KEY) % sample_n == 0


class SpanContext:
    """Trace identity + the sampling verdict of a pod that records.
    ``trace_id`` is 16 hex chars, derived from the pod uid, identical in
    every process."""

    __slots__ = ("trace_id", "sampled")

    def __init__(self, trace_id: str, sampled: bool):
        self.trace_id = trace_id
        self.sampled = sampled


def trace_id_for(uid: str) -> str:
    """Deterministic 64-bit trace id (blake2b, not Python hash() — which is
    per-process seeded and would break cross-process agreement)."""
    return hashlib.blake2b(uid.encode(), digest_size=8).hexdigest()


def format_ctx(ctx: SpanContext) -> str:
    """Wire form for X-Trace-Context / tctx fields: ``<trace_id>-<flags>``
    (flags 01 = sampled, the W3C traceparent flag octet)."""
    return f"{ctx.trace_id}-{'01' if ctx.sampled else '00'}"


def parse_ctx(wire: str) -> Optional[SpanContext]:
    tid, _, flags = wire.partition("-")
    if len(tid) != 16:
        return None
    return SpanContext(tid, flags != "00")


class Span:
    """A live span (``start_span``/``end``). Prefer ``record``/``span`` —
    this exists for non-lexical lifetimes, and the span-discipline checker
    requires every start to be ended under with/try coverage."""

    __slots__ = ("name", "ctx", "attrs", "_t0", "_wall")

    def __init__(self, name: str, ctx: SpanContext, attrs: dict):
        self.name = name
        self.ctx = ctx
        self.attrs = attrs
        self._t0 = time.perf_counter()
        self._wall = time.time()


class _ScopedSpan:
    """``with tracer.span(...)`` — records on exit, error status on raise."""

    __slots__ = ("_tracer", "_span")

    def __init__(self, tracer: "SpanRecorder", span: Optional[Span]):
        self._tracer = tracer
        self._span = span

    def __enter__(self):
        return self._span

    def __exit__(self, exc_type, _exc, _tb):
        if self._span is not None:
            if exc_type is not None:
                self._span.attrs["error"] = exc_type.__name__
            self._tracer.end(self._span)
        return False


class SpanRecorder:
    """The per-process tracer: head-sampled, ring-buffered, lock-free.
    ``context_for`` answers None for a pod outside the sample, at the cost
    of ``sampled_uid`` alone; only the sampled 1-in-N (and the forced
    forensic paths) pay ``trace_id_for``'s digest and a ``SpanContext``."""

    def __init__(self, capacity: int = 8192, sample_n: Optional[int] = None,
                 proc: str = "", enabled: Optional[bool] = None):
        if sample_n is None:
            try:
                sample_n = int(os.environ.get(_SAMPLE_ENV,
                                              str(DEFAULT_SAMPLE_N)))
            except ValueError:
                sample_n = DEFAULT_SAMPLE_N
        self.sample_n = max(1, sample_n)
        if enabled is None:
            enabled = os.environ.get(_ENABLE_ENV, "1") not in ("0", "false")
        self.enabled = enabled
        self.proc = proc or f"pid{os.getpid()}"
        self.ring: "deque" = deque(maxlen=capacity)
        self.recorded = 0  # total spans accepted (ring may have evicted)
        self._ids = itertools.count(1)
        self._proc_ctx: Optional[SpanContext] = None

    # -- contexts ----------------------------------------------------------

    def context_for(self, uid: str,
                    force: bool = False) -> Optional[SpanContext]:
        """The pod's recording context: None unless ``sampled_uid`` says
        the pod is in the sample or ``force`` overrides it. ``wants`` and
        ``record`` take the None."""
        # sampled_uid, written out: this is asked once a pod and more
        if force or zlib.crc32(uid.encode(), _SAMPLE_KEY) % self.sample_n == 0:
            return SpanContext(trace_id_for(uid), True)
        return None

    def proc_ctx(self) -> SpanContext:
        """Force-sampled process-scoped context for non-pod forensic spans
        (breaker trips, shard adoptions)."""
        if self._proc_ctx is None:
            self._proc_ctx = SpanContext(
                trace_id_for(f"proc:{self.proc}:{os.getpid()}"), True)
        return self._proc_ctx

    def wants(self, ctx: Optional[SpanContext]) -> bool:
        return self.enabled and ctx is not None and ctx.sampled

    # -- recording ---------------------------------------------------------

    def record(self, name: str, ctx: SpanContext, duration: float = 0.0,
               start: Optional[float] = None, parent: str = "",
               **attrs) -> None:
        """Append one COMPLETED span. ``start`` is wall-clock seconds
        (time.time()); None means it ended just now."""
        if not self.wants(ctx):
            return
        if start is None:
            start = time.time() - duration
        self.recorded += 1
        self.ring.append({
            "trace": ctx.trace_id,
            "span": f"{os.getpid():x}.{next(self._ids):x}",
            "parent": parent,
            "name": name,
            "proc": self.proc,
            "pid": os.getpid(),
            "ts": start,
            "dur": duration,
            "attrs": attrs,
        })

    def event(self, name: str, ctx: SpanContext, **attrs) -> None:
        self.record(name, ctx, 0.0, **attrs)

    def span(self, name: str, ctx: SpanContext, **attrs) -> _ScopedSpan:
        """Scoped live span: ``with tracer.span("api.bind", ctx): ...``."""
        live = Span(name, ctx, attrs) if self.wants(ctx) else None
        return _ScopedSpan(self, live)

    def start_span(self, name: str, ctx: SpanContext,
                   **attrs) -> Optional[Span]:
        """Open a live span for a non-lexical lifetime. The span-discipline
        checker requires a matching ``end`` reached on all paths."""
        if not self.wants(ctx):
            return None
        return Span(name, ctx, attrs)

    def end(self, span: Optional[Span]) -> None:
        if span is None:
            return
        self.record(span.name, span.ctx,
                    time.perf_counter() - span._t0, start=span._wall,
                    **span.attrs)

    # -- export ------------------------------------------------------------

    def snapshot(self) -> List[dict]:
        for _ in range(4):
            try:
                return list(self.ring)
            except RuntimeError:
                continue  # concurrent append mid-copy: retry on fresh state
        return []

    def clear(self) -> None:
        self.ring.clear()

    def dump_jsonl(self, path: str) -> str:
        """Write the ring as one span per line (atomic tmp+replace)."""
        write_jsonl(path, self.snapshot())
        return path


def write_jsonl(path: str, rows: Iterable[dict]) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    os.replace(tmp, path)


def chrome_trace(spans: Iterable[dict]) -> dict:
    """Convert span rows to the Chrome trace_event format (Perfetto/
    chrome://tracing). Processes map to integer pids with process_name
    metadata; spans are complete ('X') events in microseconds."""
    procs: Dict[str, int] = {}
    events: List[dict] = []
    for s in spans:
        proc = s.get("proc", "?")
        pid = procs.setdefault(proc, len(procs) + 1)
        events.append({
            "name": s["name"], "cat": "sched", "ph": "X",
            "ts": s["ts"] * 1e6, "dur": max(s.get("dur", 0.0), 0.0) * 1e6,
            "pid": pid, "tid": 1,
            "args": dict(s.get("attrs", {}), trace=s["trace"]),
        })
    for proc, pid in procs.items():
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": proc}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# the loop's stage ledger
# ---------------------------------------------------------------------------


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` when this process already runs JAX
    (the device-backed scheduler), else None: the JAX-free planes (host
    scheduler under a controller, apiserver) never import it for a span."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


class _Stage:
    """One open stage of a ``StageLedger`` (``with stages.stage(...)``).
    ``attrs`` may be filled while it is open (a plan's ``kind`` is known
    only once it is acquired); ``span = False`` keeps the span out of the
    pods' traces (an attempt that did not bind) while a slow stage still
    reports on the pod's trace."""

    __slots__ = ("_ledger", "name", "ctxs", "attrs", "point", "span", "_ann",
                 "_per_pod", "_t0", "_child_s", "_events0", "_parts")

    def __init__(self, ledger: "StageLedger", name: str, ctxs, point: str,
                 annotate: bool, attrs: dict):
        self._ledger = ledger
        self.name = name
        self.ctxs = ctxs
        self.attrs = attrs
        self.point = point
        self.span = True
        self._per_pod = not annotate
        ann = ledger._annotation if annotate else None
        # what is known of the stage as it opens rides the annotation as
        # the event's stats (a dispatch's engine, a batch's size)
        self._ann = (ann(ledger._ann_names[name], **attrs)
                     if ann is not None else None)
        self._child_s = 0.0
        self._parts: Optional[dict] = None  # a root's: self time by stage

    def say(self, **stats) -> None:
        """What is known of the stage only as it ends (the pods a pop
        accepted): onto ``attrs``, and onto the open annotation as further
        stats of the event."""
        self.attrs.update(stats)
        if self._ann is not None:
            self._ann.set_metadata(**stats)

    def __enter__(self) -> "_Stage":
        ledger = self._ledger
        if self._ann is not None:
            self._ann.__enter__()
        self._events0 = ledger._compile_events[0]
        if not ledger._stack:
            self._parts = {}
            ledger._thread = threading.get_ident()
        ledger._stack.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        duration = time.perf_counter() - self._t0
        ledger = self._ledger
        ledger._stack.pop()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        ledger._close(self, duration)
        return False


class StageLedger:
    """The scheduling loop's account of its own time (one per scheduler,
    scheduling thread only). Every boundary of the loop is one ``with
    stages.stage(name)`` block, and that one call site:

    1. adds the stage's SELF time (its duration minus what its child stages
       cover) and a count to the fixed per-stage table — always on;
    2. lies in any profiler session as ``sched.<name>`` on the clock of the
       device operations, the attrs given at its opening as the event's
       stats (per batch and per loop turn: ``annotate=False`` and ``leaf``
       are the per-pod forms, table only);
    3. feeds ``scheduler_loop_stage_seconds_total`` / ``_stages_total``
       (``publish``) and, where ``point`` names one, the extension-point
       histogram;
    4. copies the span into each sampled pod's trace (``ctxs``);
    5. applies the one slow-stage rule (``SLOW_STAGE_S`` per pod,
       ``SLOW_BATCH_STAGE_S`` per batch or loop turn).
    """

    def __init__(self, tracer: "SpanRecorder", metrics=None):
        self.tracer = tracer
        self.metrics = metrics
        self.seconds: Dict[str, float] = dict.fromkeys(LOOP_STAGES, 0.0)
        self.counts: Dict[str, int] = dict.fromkeys(LOOP_STAGES, 0)
        # Batches dispatched and not yet retired: the session keeps it, a
        # slow stage's span reports it.
        self.inflight = 0
        # The last root stages closed, each with its self time by stage:
        # what a loop that does not end is asked for (``report``).
        self.recent: "deque" = deque(maxlen=32)
        self._stack: List[_Stage] = []
        self._thread = 0  # the thread that opened the root stage now open
        self._annotation = _trace_annotation() if tracer.enabled else None
        self._ann_names = {n: "sched." + n for n in LOOP_STAGES}
        self._compile_events = COMPILE_EVENTS

    def stage(self, name: str, ctxs: Sequence[SpanContext] = (),
              point: str = "", annotate: bool = True, **attrs) -> _Stage:
        return _Stage(self, name, ctxs, point, annotate, attrs)

    def heard(self, name: str) -> Optional[_Stage]:
        """The innermost open stage, for code that runs under a stage
        somebody else opened (the dry run under PostFilter's) and has stats
        for it: that stage if it is ``name`` and a profiler or a recorder
        holds its annotation, else None, and the caller takes no clocks for
        stats that nobody reads or that would land on another stage."""
        if self._stack:
            st = self._stack[-1]
            if st.name == name and st._ann is not None:
                return st
        return None

    def leaf(self, name: str, seconds: float, per_pod: bool = True,
             **attrs) -> None:
        """A finished child with no children of its own, timed by the
        caller (the per-pod form: two clock reads, no object, no
        annotation). ``per_pod`` False: it carried a batch (a bulk bind
        request) and is slow where a batch stage is."""
        self._account(name, seconds, seconds)
        if seconds > (SLOW_STAGE_S if per_pod else SLOW_BATCH_STAGE_S):
            self._slow(name, (), attrs, seconds, seconds, 0)

    def collecting(self, generation: int) -> Optional[_Stage]:
        """A collection begins on the calling thread (the collector
        policy's clock asks, core/collector.py). If that is this loop's
        thread with a stage open, the pause is the stage `gc.pause`, a child
        of whatever is open: entered here, left by the clock when the
        collection ends. A collection any other thread runs, or one between
        two turns of the loop, is none of this table's."""
        if not self._stack or self._thread != threading.get_ident():
            return None
        pause = self.stage("gc.pause", generation=generation)
        pause.__enter__()
        return pause

    def _account(self, name: str, self_s: float, duration: float) -> bool:
        """One closed stage into the table, its duration onto the open
        parent's children. False when it had no parent (a root)."""
        self.seconds[name] += self_s
        self.counts[name] += 1
        stack = self._stack
        if not stack:
            return False
        stack[-1]._child_s += duration
        parts = stack[0]._parts
        parts[name] = parts.get(name, 0.0) + self_s
        return True

    def _close(self, st: _Stage, duration: float) -> None:
        name = st.name
        self_s = duration - st._child_s
        if not self._account(name, self_s, duration):
            self.recent.append((name, time.time() - duration, duration,
                                self_s, st._parts))
        if st.point:
            self.metrics.framework_extension_point_duration.observe(
                duration, st.point, "Success", "")
        if st.ctxs and st.span:
            wall = time.time() - duration
            record = self.tracer.record
            for ctx in st.ctxs:
                record(name, ctx, duration, start=wall, **st.attrs)
        if self_s > (SLOW_STAGE_S if st._per_pod else SLOW_BATCH_STAGE_S):
            self._slow(name, st.ctxs, st.attrs, duration, self_s,
                       self._compile_events[0] - st._events0)

    def _slow(self, name: str, ctxs, attrs: dict, duration: float,
              self_s: float, compiles: int) -> None:
        """The slow-stage rule: log it, leave one forced span with what an
        operator needs (on the first sampled pod's trace, else on the
        process's), and ask the flight recorder for a dump."""
        kv = " ".join(f"{k}={v}" for k, v in attrs.items())
        logger.warning(
            "slow scheduling stage: %s %s self=%.0fms total=%.0fms "
            "inflight=%d compiles=%d", name, kv, self_s * 1e3,
            duration * 1e3, self.inflight, compiles)
        tracer = self.tracer
        if tracer.enabled:
            ctx = ctxs[0] if ctxs else tracer.proc_ctx()
            # the stage's own attrs first: a dispatch says `inflight` too
            said = {k: str(v) for k, v in attrs.items()}
            said.update(stage=name, self_ms=round(self_s * 1e3, 3),
                        inflight=self.inflight, compiles=compiles)
            tracer.record("trace.slow_stage", ctx, duration, **said)
        request_dump("slow_stage")

    def publish(self) -> None:
        """Copy the table onto the two ``/metrics`` counters (at scrape
        time: the loop itself pays nothing for them)."""
        metrics = self.metrics
        for name in LOOP_STAGES:
            metrics.loop_stage_seconds.set_total(self.seconds[name], name)
            metrics.loop_stages.set_total(float(self.counts[name]), name)

    def report(self, last: int = 8) -> str:
        """The table and the last root stages, as text (a deadline's or a
        flight recorder's account of where the loop was)."""
        lines = ["stage               self_s      count"]
        for name in LOOP_STAGES:
            if self.counts[name]:
                lines.append(f"{name:<16} {self.seconds[name]:>10.4f} "
                             f"{self.counts[name]:>10d}")
        open_now = " > ".join(st.name for st in self._stack)
        lines.append(f"open now: {open_now or '-'}")
        for name, ts, duration, self_s, parts in list(self.recent)[-last:]:
            inner = " ".join(f"{k}={v * 1e3:.2f}ms"
                             for k, v in sorted(parts.items()))
            lines.append(f"{name} ts={ts:.3f} total={duration * 1e3:.2f}ms "
                         f"self={self_s * 1e3:.2f}ms {inner}")
        return "\n".join(lines)


class GcClock:
    """Seconds the interpreter's cyclic collector ran, by generation
    (``gc.callbacks``). A collection stops every thread of the process, so
    it is on ``/metrics``: the scheduler's through its collector policy
    (core/collector.py), the apiserver's through its binary's main.
    ``ledgers`` are the loops to tell (``StageLedger.collecting``): the one
    whose thread collects, inside a stage, books the pause as `gc.pause`."""

    def __init__(self, ledgers: Iterable["StageLedger"] = ()):
        self.seconds = [0.0, 0.0, 0.0]
        self.collections = [0, 0, 0]
        self.ledgers = ledgers
        self._t0 = 0.0
        self._pause: Optional[_Stage] = None

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            for ledger in self.ledgers:
                self._pause = ledger.collecting(info["generation"])
                if self._pause is not None:
                    break
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            self.seconds[g] += time.perf_counter() - self._t0
            self.collections[g] += 1
            if self._pause is not None:
                self._pause.__exit__(None, None, None)
                self._pause = None

    def install(self) -> "GcClock":
        gc.callbacks.append(self._callback)
        return self

    def close(self) -> None:
        if self._callback in gc.callbacks:
            gc.callbacks.remove(self._callback)

    def expose(self, prefix: str) -> List[str]:
        """Prometheus lines ``<prefix>_gc_pause_seconds_total{generation}``
        and ``<prefix>_gc_collections_total{generation}``."""
        out = []
        for series, values in (("gc_pause_seconds_total", self.seconds),
                               ("gc_collections_total", self.collections)):
            out.append(f"# TYPE {prefix}_{series} counter")
            out.extend(f'{prefix}_{series}{{generation="{g}"}} {float(v)}'
                       for g, v in enumerate(values))
        return out


# ---------------------------------------------------------------------------
# process-global default tracer
# ---------------------------------------------------------------------------

_DEFAULT: Optional[SpanRecorder] = None


def default_tracer() -> SpanRecorder:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = SpanRecorder()
    return _DEFAULT


def set_default_tracer(tracer: Optional[SpanRecorder]) -> None:
    """Swap the process tracer (tests; binaries label ``proc`` instead)."""
    global _DEFAULT
    _DEFAULT = tracer


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

_FLIGHT: Optional["FlightRecorder"] = None


def request_dump(reason: str) -> Optional[str]:
    """Dump through the installed flight recorder (rate-limited); no-op
    when none is installed. The seam StageLedger/ShardMember call so they
    need no direct dependency on recorder wiring."""
    if _FLIGHT is None:
        return None
    return _FLIGHT.dump(reason, rate_limited=True)


class FlightRecorder:
    """Crash-safe forensic dumps: span ring + last-K events/errors/counters
    per process, written to ``<dir>/flightrec-<pid>.jsonl``.

    Triggers: SIGUSR2, a slow-stage breach (via ``request_dump``),
    unhandled crash (sys.excepthook chain + atexit; ``faulthandler`` covers
    native faults into ``flightrec-<pid>.crash``), an optional periodic
    timer — the only trigger that survives SIGKILL chaos (``kill -9``
    leaves the last periodic artifact on disk) — and process exit when
    ``at_exit`` is set. Dumps are atomic (tmp+``os.replace``), so a crash
    mid-dump leaves the previous artifact intact."""

    MIN_DUMP_INTERVAL = 2.0  # rate limit for breach-triggered dumps

    def __init__(self, directory: str, tracer: Optional[SpanRecorder] = None,
                 recorder=None, scheduler=None, apiserver=None,
                 keep_events: int = 256):
        self.directory = directory
        self.tracer = tracer or default_tracer()
        self.recorder = recorder      # tracing.EventRecorder (optional)
        self.scheduler = scheduler    # core.Scheduler (optional)
        self.apiserver = apiserver    # core.apiserver.APIServer (optional)
        self.keep_events = keep_events
        self.path = os.path.join(directory, f"flightrec-{os.getpid()}.jsonl")
        self.dumps = 0
        self._last_dump = 0.0
        self._crashed = False
        self._prev_excepthook = None
        self._stop = threading.Event()
        self._timer: Optional[threading.Thread] = None
        # Serializes dumps across the autodump thread, request_dump callers,
        # the SIGUSR2 handler, and shutdown. Non-blocking acquire: a dump
        # already in flight makes a concurrent one redundant, and a SIGNAL
        # handler interrupting a main-thread dump must skip, not deadlock.
        self._dump_lock = threading.Lock()

    # -- triggers ----------------------------------------------------------

    def install(self, sigusr2: bool = True, on_crash: bool = True,
                at_exit: bool = False,
                autodump_interval: float = 0.0) -> "FlightRecorder":
        global _FLIGHT
        _FLIGHT = self
        os.makedirs(self.directory, exist_ok=True)
        if sigusr2:
            self._install_sigusr2()
        if on_crash:
            self._install_crash_hooks(at_exit)
        if autodump_interval > 0:
            self._timer = threading.Thread(
                target=self._autodump_loop, args=(autodump_interval,),
                name="flightrec-autodump", daemon=True)
            self._timer.start()
        return self

    def _install_sigusr2(self) -> None:
        import signal
        prev = signal.getsignal(signal.SIGUSR2)

        def handler(signum, frame):
            self.dump("sigusr2")
            if callable(prev):  # chain (the cache debugger may also listen)
                prev(signum, frame)

        try:
            signal.signal(signal.SIGUSR2, handler)
        except ValueError:
            pass  # not the main thread: signal triggers unavailable

    def _install_crash_hooks(self, at_exit: bool) -> None:
        import atexit
        import faulthandler
        import sys
        try:
            # Native faults (segfault/abort) can't run Python hooks; leave
            # the interpreter-level dump beside the JSONL artifact.
            self._crash_file = open(  # noqa: SIM115 - must outlive install
                os.path.join(self.directory,
                             f"flightrec-{os.getpid()}.crash"), "w")
            faulthandler.enable(self._crash_file)
        except (OSError, RuntimeError):
            pass
        self._prev_excepthook = sys.excepthook

        def hook(exc_type, exc, tb):
            self._crashed = True
            try:
                self.dump("crash", error=f"{exc_type.__name__}: {exc}")
            except Exception:  # noqa: BLE001 - never mask the real crash
                pass
            (self._prev_excepthook or sys.__excepthook__)(exc_type, exc, tb)

        sys.excepthook = hook
        atexit.register(self._atexit_dump, at_exit)

    def _atexit_dump(self, always: bool) -> None:
        if always or self._crashed:
            try:
                self.dump("exit" if not self._crashed else "crash-exit")
            except Exception:  # noqa: BLE001 - exiting anyway
                pass

    def _autodump_loop(self, interval: float) -> None:
        last_recorded = -1
        while not self._stop.wait(interval):
            try:
                # Skip unchanged rings: serializing an 8k-span ring costs
                # tens of ms of GIL — pointless when nothing new happened
                # (idle shard, quiet apiserver).
                if self.tracer.recorded == last_recorded:
                    continue
                last_recorded = self.tracer.recorded
                self.dump("periodic")
            except Exception:  # noqa: BLE001 - keep the timer alive
                pass

    def close(self) -> None:
        global _FLIGHT
        self._stop.set()
        if self._timer is not None:
            self._timer.join(timeout=2)
            self._timer = None
        if _FLIGHT is self:
            _FLIGHT = None

    # -- the dump ----------------------------------------------------------

    def dump(self, reason: str, rate_limited: bool = False,
             error: str = "") -> Optional[str]:
        now = time.monotonic()
        if rate_limited and now - self._last_dump < self.MIN_DUMP_INTERVAL:
            return None
        if not self._dump_lock.acquire(blocking=False):
            return None  # a dump is already being produced
        try:
            return self._dump_locked(reason, now, error)
        finally:
            self._dump_lock.release()

    def _dump_locked(self, reason: str, now: float, error: str) -> str:
        self._last_dump = now
        rows: List[dict] = [{
            "kind": "meta", "reason": reason, "pid": os.getpid(),
            "proc": self.tracer.proc, "time": time.time(),
            "dump_seq": self.dumps, "error": error,
        }]
        for span in self.tracer.snapshot():
            rows.append(dict(span, kind="span"))
        if self.recorder is not None:
            for ev in self.recorder.recent(limit=self.keep_events):
                rows.append({
                    "kind": "event", "object": ev.object_key,
                    "reason": ev.reason, "type": ev.type,
                    "message": ev.message, "count": ev.count,
                    "ts": ev.timestamp})
        rows.extend(self._scheduler_rows())
        rows.extend(self._apiserver_rows())
        os.makedirs(self.directory, exist_ok=True)
        write_jsonl(self.path, rows)
        self.dumps += 1
        return self.path

    def _scheduler_rows(self) -> List[dict]:
        s = self.scheduler
        if s is None:
            return []
        rows = [{"kind": "counters",
                 "attempts": s.attempts, "scheduled": s.scheduled,
                 "failures": s.failures,
                 "bind_conflicts": s.bind_conflicts,
                 "conflict_requeues": s.conflict_requeues,
                 "state_unwinds": s.state_unwinds,
                 "device_scheduled": getattr(s, "device_scheduled", 0),
                 "host_path_pods": getattr(s, "host_path_pods", 0)}]
        stages = getattr(s, "stages", None)
        if stages is not None:
            rows.append({"kind": "stages", "seconds": dict(stages.seconds),
                         "counts": dict(stages.counts)})
        for line in list(s.error_log)[-self.keep_events:]:
            rows.append({"kind": "error", "message": line})
        member = getattr(s, "shard_member", None)
        if member is not None:
            rows.append({"kind": "shard",
                         "owned": sorted(member.owned),
                         "adoptions": member.adoptions,
                         "handbacks": member.handbacks,
                         "renewals": member.renewals})
        return rows

    def _apiserver_rows(self) -> List[dict]:
        a = self.apiserver
        if a is None:
            return []
        return [{"kind": "counters",
                 "bind_conflicts": a.bind_conflicts,
                 "capacity_conflicts": a.capacity_conflicts,
                 "lease_conflicts": a.lease_conflicts,
                 "lease_transitions": a.lease_transitions,
                 "resumed_watches": a.resumed_watches,
                 "relisted_watches": a.relisted_watches,
                 "pods": len(a.store.pods), "nodes": len(a.store.nodes)}]
