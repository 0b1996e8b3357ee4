"""Device pipeline: mean over the traced waves' FIRST batches (the ones that
nothing hides: the pipeline is empty when they are dispatched) of (start of
the batch's program run on the device - start of its `sched.device.dispatch`),
in ms: what a dispatch loses before its kernel begins (argument transfer,
launch). Needs the join of `timeline.py`; reads nothing where that reads
nothing (no `seq` on the spans, counts that differ, no trace)."""

import timeline


def read(obs):
    tl = timeline.of(obs)
    first = [wave[0]["launch_gap_ms"] for wave in (tl or {}).get("waves", ())
             if wave]
    return sum(first) / len(first) if first else None
