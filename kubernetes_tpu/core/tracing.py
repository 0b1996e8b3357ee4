"""Event recording.

The reference emits API Events per scheduling outcome (EventRecorder,
schedule_one.go:1138): a bounded in-memory event recorder the server can
expose. (Its utiltrace slow-step log, schedule_one.go:574-575, is the
stage ledger's slow-stage rule: core/spans.py.)
"""

from __future__ import annotations

import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple


class Event:
    """A minimal core/v1 Event (reason + message + involved object).

    `message` accepts either a plain string or a (fmt, args) tuple — the
    latter defers %-formatting until the message is actually read
    (EventRecorder runs once per scheduled pod on a >10k pods/s path; the
    reference buys the same headroom with an async broadcaster)."""

    __slots__ = ("object_key", "reason", "_message", "type", "count",
                 "timestamp", "evicted")

    def __init__(self, object_key: str, reason: str, message,
                 type: str = "Normal", count: int = 1,
                 timestamp: Optional[float] = None, evicted: bool = False):
        self.object_key = object_key
        self.reason = reason
        self._message = message
        self.type = type
        self.count = count
        self.timestamp = time.time() if timestamp is None else timestamp
        self.evicted = evicted

    @property
    def message(self) -> str:
        m = self._message
        if isinstance(m, tuple):
            m = m[0] % m[1]
            self._message = m
        return m

    @message.setter
    def message(self, value) -> None:
        self._message = value


class EventRecorder:
    """EventRecorder (client-go tools/record) analogue: bounded buffer with
    reference-style aggregation by (object, reason). The aggregation index
    is pruned in step with deque eviction, so memory stays O(capacity) and
    every eventf is O(1) — this runs once per scheduled pod on a path
    benchmarked at >10k pods/s."""

    def __init__(self, capacity: int = 1000):
        self.events: Deque[Event] = deque(maxlen=capacity)
        self._agg: Dict[Tuple[str, str], Event] = {}

    def eventf(self, object_key: str, event_type: str, reason: str,
               message: str) -> None:
        key = (object_key, reason)
        existing = self._agg.get(key)
        if existing is not None and not existing.evicted:
            existing.count += 1
            existing.message = message
            existing.timestamp = time.time()
            return
        ev = Event(object_key=object_key, reason=reason, message=message,
                   type=event_type)
        if self.events.maxlen and len(self.events) == self.events.maxlen:
            old = self.events[0]  # about to be evicted by the append
            old.evicted = True
            okey = (old.object_key, old.reason)
            if self._agg.get(okey) is old:
                del self._agg[okey]
        self._agg[key] = ev
        self.events.append(ev)

    def for_object(self, object_key: str) -> List[Event]:
        return [e for e in self.events if e.object_key == object_key]

    def recent(self, object_key: Optional[str] = None,
               limit: int = 256) -> List[Event]:
        """Newest-first read side. Aggregated events mutate count/timestamp
        IN PLACE (eventf), so the deque's insertion order goes stale the
        moment an aggregate re-fires — this re-sorts by the live timestamp,
        which is what the /debug/events surface and the flight recorder
        serve. O(capacity log capacity) on a read-only debug path."""
        evs: List[Event] = []
        for _ in range(4):
            try:
                evs = [e for e in self.events
                       if object_key is None or e.object_key == object_key]
                break
            except RuntimeError:
                # eventf() appended concurrently (scheduling thread vs the
                # flight-recorder/debug-endpoint reader) — deque iteration
                # raises instead of tearing; retry against the new state.
                continue
        evs.sort(key=lambda e: e.timestamp, reverse=True)
        return evs[:limit]
