"""The scheduler's charge of its process's cyclic collector.

A loaded scheduler keeps a heap that never dies (imports, nodes and NodeInfos,
mirror, plan, compiled programs) and turns over thousands of pod object trees
a second. Left alone, the interpreter runs a young collection every 700
allocations and, by its 25 % rule, a full one over the whole long-lived heap
once or twice a wave. `CollectorPolicy` does two things about that and
nothing else: while a scheduler exists `THRESHOLDS` hold, and when a
scheduler's loop goes idle after work the surviving heap is frozen
(`gc.freeze`), so that a full collection walks only what came since.

Process-wide by nature: every `Scheduler` holds `POLICY` from its construction
to its `close()` (or its own collection), and the last release restores the
thresholds found and unfreezes. No option selects any of this: it follows what
it can observe, that a loop went idle and how the unfrozen heap has grown.
"""

import gc
import threading
import weakref

from .spans import GcClock

# Generation 0 is collected once this many more tracked objects have been
# allocated than freed: of the order of a device batch's allocations, so a
# wave of 10,000 pods meets two young collections and not hundreds, and one
# whose live pods stay under it (antiaffinity-5k's 2,000) none; the older
# generations keep the interpreter's ratios. On the chip, 40 s of
# basic-5k.waves (PERF.md section 6, PR 30): 10,184 + 926 + 75 collections in
# 11.05 s at (700, 10, 10), 104 + 9 + 0 in 2.38 s with these and the freeze.
# At 50,000 pods a wave (SchedulingBasic/5000Nodes_50000Pods, the cell
# basic-5k-50k.waves; PERF.md section 6, PR 33; some 600,000 live
# tracked objects at a wave's end, 11 waves in 40 s): 101 + 9 + 0
# in 3.29 s, 8.3 % of wave time: nine young collections a wave at 18 ms and
# one of generation 1 at 0.16 s over the wave's survivors, which is nearly half
# of the pause. No full collection in any of 17 windows, and no gc.settle or
# move of scheduler_gc_freezes_total in the four read for them: the tenth
# generation-1 collection since set-up falls into a twelfth wave (counted on
# the CPU: one full collection there and one settle after it, 0.2 million
# objects frozen). The policy holds at this heap; what it leaves is S10's.
THRESHOLDS = (50_000, 10, 10)
# Frozen again once the unfrozen heap has outgrown this share of the frozen
# one: all re-freezing then walks a constant multiple of the final heap.
REFREEZE_SHARE = 0.25


class CollectorPolicy:
    def __init__(self):
        # The holders' stage ledgers: the clock tells the one whose loop
        # thread is collecting, which books the pause (stage `gc.pause`).
        self._ledgers = weakref.WeakSet()
        self.clock = GcClock(self._ledgers)
        self.freezes = 0
        self._settled = False  # a freeze of this policy's is in place
        self._lock = threading.RLock()  # a finalizer may release mid-acquire
        self._holders = 0
        self._found = ()  # the thresholds the first holder met
        self._fulls_seen = 0

    def acquire(self, scheduler) -> weakref.finalize:
        """A scheduler's share: given back by calling it, or at collection."""
        with self._lock:
            self._holders += 1
            self._ledgers.add(scheduler.stages)
            if self._holders == 1:
                self._found = gc.get_threshold()
                gc.set_threshold(*THRESHOLDS)
                self.clock.install()
        share = weakref.finalize(scheduler, self._release)
        share.atexit = False  # a frozen heap is the cheaper one to exit with
        return share

    def _release(self) -> None:
        with self._lock:
            self._holders -= 1
            if self._holders == 0:
                gc.set_threshold(*self._found)
                gc.unfreeze()
                self._settled = False
                self.clock.close()

    def idle(self, stages) -> None:
        """A loop found its queue empty after work (never inside a session).
        The first time, collect and freeze what survives. Later, O(1) unless
        a full collection has run since the last look: that walk cost more
        than the count taken here, and says the unfrozen heap has grown."""
        fulls = self.clock.collections[2]
        if self._settled and fulls == self._fulls_seen:
            return
        with self._lock:
            self._fulls_seen = fulls
            small = REFREEZE_SHARE * gc.get_freeze_count()
            if not self._holders or (
                    self._settled and len(gc.get_objects()) <= small):
                return
            with stages.stage("gc.settle"):
                gc.unfreeze()  # frozen cycles that died since go too
                gc.collect()
                gc.freeze()
            self.freezes += 1
            self._settled = True
            self._fulls_seen = self.clock.collections[2]

    def expose(self) -> list:
        """The clock's series and the policy's own, as Prometheus lines."""
        return self.clock.expose("scheduler") + [
            "# TYPE scheduler_gc_freezes_total counter",
            f"scheduler_gc_freezes_total {float(self.freezes)}",
            "# TYPE scheduler_gc_frozen_objects gauge",
            f"scheduler_gc_frozen_objects {float(gc.get_freeze_count())}"]


POLICY = CollectorPolicy()
