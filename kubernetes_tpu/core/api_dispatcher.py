"""Async API dispatcher: decouples scheduling cycles from API write RTT.

Re-expresses pkg/scheduler/backend/api_dispatcher/ (APIDispatcher
api_dispatcher.go:32, relevance-merging call_queue.go) and the call
implementations in framework/api_calls/ (pod_binding.go:32 PodBindingCall,
pod_status_patch.go). Gated by SchedulerAsyncAPICalls
(kube_features.go:1048).

Execution modes:
- inline  — calls run at enqueue (deterministic; what a scheduler over the
  in-process store gets: its "API server" is a dict and there is no round
  trip to hide);
- thread  — a worker thread drains the queue, overlapping binding writes
  with the next scheduling cycle exactly like the reference's goroutine.

The scheduler picks the mode from the clientset it is given
(core/scheduler.py ``_dispatch_mode``): a clientset that says its writes
cross a socket (``remote_writes``: the HTTPClientset, also under a
RetryingClientset) gets ``thread``, every other one ``inline``; the
SchedulerAsyncAPICalls gate off means inline everywhere.

A thread-mode call is not done when it is queued. The worker stamps each
call with the instants it read (``enqueued_at``, ``sent_at``, ``acked_at``)
and hands acknowledged calls back through ``drain_done`` the way it hands
failures back through ``drain_errors``: the loop runs ``on_done`` /
``on_error`` on its own thread, so a bind is settled (counted, finished in
the cache, observed in the latency series) at its acknowledgement and by
the thread that owns cache and queue.

Merging semantics (call_queue.go): one pending slot per (call_type, object
uid); a newly enqueued call replaces a queued one when its relevance is >=
the queued call's (e.g. a binding supersedes a pending status patch).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

# Call types + relevance (api_calls/ relevances: deletion > binding > patch).
CALL_STATUS_PATCH = "pod_status_patch"
CALL_BINDING = "pod_binding"
CALL_DELETE = "pod_deletion"
RELEVANCE = {CALL_STATUS_PATCH: 1, CALL_BINDING: 2, CALL_DELETE: 3}


@dataclass
class APICall:
    call_type: str
    object_uid: str
    execute: Callable[[], None]
    on_error: Optional[Callable[[Exception], None]] = None
    # Bulk seam (DefaultBinder): a run of consecutive queued calls sharing
    # the SAME bulk_execute callable drains as one batch on the thread
    # worker — one API round-trip (and one worker GIL wakeup) per batch
    # instead of per call. bind_args carries the call's (pod, node_name)
    # for the batch executor. bulk_execute(calls) returns one
    # Optional[Exception] per call, or raises for a whole-batch transport
    # failure (retried under the same budget as single calls — safe because
    # the binding subresource answers same-node replays idempotently).
    bind_args: Optional[tuple] = None
    bulk_execute: Optional[Callable[[List["APICall"]], list]] = None
    # Wire trace context (core/spans.py format_ctx) riding the queued call:
    # a deferred call can execute well behind its enqueue on a loaded
    # shard, so failure records name the ORIGINAL pod trace (see _fail —
    # `trace=<ctx>` in the error log links an async bind failure to its
    # merged cross-process trace in the analyzer).
    trace_ctx: Optional[str] = None
    # Acknowledgement seam: the loop runs on_done(call) on its own thread
    # after the call succeeded (drain_done), with the worker's instants
    # (time.perf_counter) on the call: queued, the request that carried it
    # sent, its reply received.
    on_done: Optional[Callable[["APICall"], None]] = None
    enqueued_at: float = 0.0
    sent_at: float = 0.0
    acked_at: float = 0.0

    @property
    def relevance(self) -> int:
        return RELEVANCE.get(self.call_type, 0)

    def _fail(self, err) -> str:
        """Error-log line for a failed execution, trace-attributed."""
        tag = f" trace={self.trace_ctx}" if self.trace_ctx else ""
        return f"{self.call_type}/{self.object_uid}{tag}: {err!r}"


class APIDispatcher:
    def __init__(self, mode: str = "inline", metrics=None, retry=None):
        assert mode in ("inline", "thread")
        self.mode = mode
        self.metrics = metrics  # SchedulerMetrics (async_api_call_* series)
        # Transient-failure retry budget per call (client-go request retry):
        # a bind that hits a connection reset / 5xx replays with backoff
        # BEFORE landing in the error inbox — drain_errors only sees calls
        # that stayed broken through the whole budget. Inline mode shares
        # the config; its sleeps run on the scheduling thread, so the
        # defaults are small (RetryConfig caps well under a watch timeout).
        from .backoff import RetryConfig
        self._retry_cfg = retry or RetryConfig()
        self.retried = 0  # replays across all calls (tests/metrics)
        self._pending: Dict[Tuple[str, str], APICall] = {}
        self._order: List[Tuple[str, str]] = []
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._in_flight = 0
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self.executed = 0
        self.merged = 0
        self.errors: List[str] = []
        # Thread-mode failures land here instead of running on_error on the
        # worker thread: on_error handlers mutate cache/queue state owned by
        # the scheduling loop, so the loop drains this inbox itself
        # (drain_errors), keeping all cache/queue mutation single-threaded.
        self._error_inbox: List[Tuple[APICall, Exception]] = []
        # Acknowledged calls that carry an on_done, for the loop likewise.
        self._done_inbox: List[APICall] = []
        # Binding requests the worker sent and the pods they carried
        # (scheduler_bind_requests_total{kind}, ..._request_pods_total):
        # a retried request counts once.
        self.bind_requests = {"single": 0, "bulk": 0}
        self.bind_request_pods = 0
        if mode == "thread":
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()

    # -- enqueue (api_dispatcher.go Add) -----------------------------------

    def add(self, call: APICall) -> None:
        if self.mode == "inline":
            self._execute(call)
            return
        key = (call.call_type, call.object_uid)
        skip_key = (CALL_STATUS_PATCH, call.object_uid) \
            if call.call_type == CALL_BINDING else None
        call.enqueued_at = time.perf_counter()
        with self._cv:
            if key in self._pending:
                self.merged += 1  # replace: newest call wins its slot
                self._pending[key] = call
            else:
                self._pending[key] = call
                self._order.append(key)
            # A binding makes a queued status patch for the same pod
            # irrelevant (call_queue.go relevance merging).
            if skip_key and skip_key in self._pending:
                self._pending.pop(skip_key)
                self._order.remove(skip_key)
                self.merged += 1
            self._cv.notify_all()

    def _execute(self, call: APICall, deferred: bool = False) -> None:
        """One call, with its retry budget. ``deferred`` (the worker): the
        outcome goes to an inbox for the loop, and is not run here."""
        _t0 = time.perf_counter()
        if call.call_type == CALL_BINDING:
            self.bind_requests["single"] += 1
            self.bind_request_pods += 1
        delays = self._retry_cfg.delays()
        while True:
            try:
                call.sent_at = time.perf_counter()
                call.execute()
                call.acked_at = time.perf_counter()
                self.executed += 1
                if self.metrics is not None:
                    self.metrics.async_api_call_execution_total.inc(
                        call.call_type, "success")
                    self.metrics.async_api_call_execution_duration.observe(
                        call.acked_at - _t0, call.call_type, "success")
                if call.on_done is None:
                    return
                if deferred:
                    with self._cv:
                        self._done_inbox.append(call)
                else:
                    call.on_done(call)
                return
            except Exception as e:  # noqa: BLE001
                call.acked_at = time.perf_counter()
                if self._retry_cfg.retriable(e):
                    try:
                        delay = next(delays)
                    except StopIteration:
                        pass  # budget exhausted: fall through to the inbox
                    else:
                        self.retried += 1
                        if self.metrics is not None:
                            self.metrics.async_api_call_retries.inc(
                                call.call_type)
                        time.sleep(delay)
                        continue
                self.errors.append(call._fail(e))
                if self.metrics is not None:
                    self.metrics.async_api_call_execution_total.inc(
                        call.call_type, "error")
                    self.metrics.async_api_call_execution_duration.observe(
                        call.acked_at - _t0, call.call_type, "error")
                if call.on_error is None:
                    return
                if deferred:
                    with self._cv:
                        self._error_inbox.append((call, e))
                else:
                    call.on_error(e)
                return

    # -- worker ------------------------------------------------------------

    # Batch cap: bounds the server-side write-lock hold per bulk request
    # (~0.3ms/bind), so one shard's burst never stalls peers' binds or
    # lease renews for more than a few tens of ms.
    BULK_MAX = 128

    def _run(self) -> None:
        while not self._stop:
            with self._cv:
                call = None
                while self._order:
                    key = self._order.pop(0)
                    call = self._pending.pop(key, None)
                    if call is not None:
                        break
                if call is None:
                    self._cv.wait(timeout=0.05)
                    continue
                batch = [call]
                # Drain the run of batchable calls queued behind it (stop at
                # the first call with a different executor: cross-type FIFO
                # order is preserved — a queued status patch still lands
                # after the binds enqueued before it).
                while (call.bulk_execute is not None and self._order
                        and len(batch) < APIDispatcher.BULK_MAX):
                    nxt = self._pending.get(self._order[0])
                    if nxt is None:
                        self._order.pop(0)  # merged-away slot
                        continue
                    # == not `is`: bound methods are materialized fresh on
                    # every attribute access, so identity never matches —
                    # method equality compares (__self__, __func__).
                    if nxt.bulk_execute != call.bulk_execute:
                        break
                    self._order.pop(0)
                    self._pending.pop((nxt.call_type, nxt.object_uid), None)
                    batch.append(nxt)
                self._in_flight += 1
            try:
                if len(batch) > 1:
                    self._execute_bulk(batch)
                else:
                    self._execute(call, deferred=True)
            finally:
                with self._cv:
                    self._in_flight -= 1
                    self._cv.notify_all()

    def _execute_bulk(self, calls: List[APICall]) -> None:
        """One batch through bulk_execute, with the same transient-retry
        budget as _execute; per-item outcomes land in the two inboxes for
        the scheduling loop to drain (never run on this thread). Every call
        of the batch is stamped with the batch's request and reply."""
        _t0 = time.perf_counter()
        self.bind_requests["bulk"] += 1
        self.bind_request_pods += len(calls)
        delays = self._retry_cfg.delays()
        while True:
            sent = time.perf_counter()
            try:
                results = calls[0].bulk_execute(calls)
                break
            except Exception as e:  # noqa: BLE001 - whole-batch transport
                if self._retry_cfg.retriable(e):
                    try:
                        delay = next(delays)
                    except StopIteration:
                        pass  # budget exhausted: every call fails below
                    else:
                        self.retried += 1
                        if self.metrics is not None:
                            self.metrics.async_api_call_retries.inc(
                                calls[0].call_type)
                        time.sleep(delay)
                        continue
                results = [e] * len(calls)
                break
        acked = time.perf_counter()
        dur = acked - _t0
        if len(results) < len(calls):  # defensive: short executor response
            results = list(results) + [RuntimeError("short bulk response")] \
                * (len(calls) - len(results))
        done = []
        failed = []
        for call, err in zip(calls, results):
            call.sent_at = sent
            call.acked_at = acked
            outcome = "success" if err is None else "error"
            if self.metrics is not None:
                self.metrics.async_api_call_execution_total.inc(
                    call.call_type, outcome)
                self.metrics.async_api_call_execution_duration.observe(
                    dur / len(calls), call.call_type, outcome)
            if err is None:
                self.executed += 1
                if call.on_done is not None:
                    done.append(call)
                continue
            self.errors.append(call._fail(err))
            if call.on_error is not None:
                failed.append((call, err))
        if done or failed:
            with self._cv:
                self._done_inbox.extend(done)
                self._error_inbox.extend(failed)

    def has_errors(self) -> bool:
        """Cheap emptiness probe (list read is atomic under the GIL)."""
        return bool(self._error_inbox)

    def has_done(self) -> bool:
        return bool(self._done_inbox)

    def drain_done(self) -> List[APICall]:
        """Take the acknowledged calls that carry an on_done; the scheduling
        loop runs the handlers on its own thread, as for drain_errors."""
        with self._cv:
            out, self._done_inbox = self._done_inbox, []
        return out

    def drain_errors(self) -> List[Tuple[APICall, Exception]]:
        """Take pending (call, exception) failures. The scheduling loop calls
        this and runs on_error handlers on its own thread."""
        with self._cv:
            out, self._error_inbox = self._error_inbox, []
        return out

    def flush(self, timeout: float = 5.0) -> None:
        """True drain barrier: waits until the queue is empty AND no call is
        mid-execution on the worker (test/bench determinism barrier)."""
        if self.mode == "inline":
            return
        with self._cv:
            self._cv.wait_for(
                lambda: not self._order and self._in_flight == 0, timeout=timeout)

    def wait_for_outcome(self, timeout: float) -> None:
        """Park the loop until the worker has something for it (an
        acknowledged or a failed call to drain), or has nothing left to do,
        or ``timeout`` passed. The worker notifies at the end of every
        request, so a settle follows its acknowledgement at once."""
        if self.mode == "inline":
            return
        with self._cv:
            self._cv.wait_for(
                lambda: bool(self._done_inbox or self._error_inbox)
                or not (self._order or self._in_flight), timeout=timeout)

    def close(self) -> None:
        """Stop the worker after the request it is in. What is still queued
        is never sent (a bind stays pending at the apiserver, as after a
        crash); what was acknowledged stays in the inboxes for one last
        drain."""
        self._stop = True
        with self._cv:
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=1.0)

    def pending_count(self) -> int:
        with self._lock:
            return len(self._order)

    def idle(self) -> bool:
        """Nothing queued, nothing mid-execution and no outcome waiting for
        the loop to drain it (inline mode executes at enqueue, so it is
        always idle)."""
        if self.mode == "inline":
            return True
        with self._lock:
            return not (self._order or self._in_flight
                        or self._done_inbox or self._error_inbox)
