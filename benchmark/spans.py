"""Profiler spans the benchmark puts around calls into the program, for the
traced run only: the program has none of its own in the trace yet (PERF.md,
Open questions). `host_spans` of a traffic file maps a span name to the name
of a scheduler method; a method that is gone is skipped and said so."""

from __future__ import annotations


def annotate(target, host_spans: dict, say) -> None:
    """Wrap the named methods of `target` (a scheduler object, or the
    scheduler class where the object is built out of reach) so that each call
    shows in the trace as `bench.<span>`."""
    from jax.profiler import TraceAnnotation
    for span, attr in host_spans.items():
        fn = getattr(target, attr, None)
        if fn is None:
            say(f"host span {span}: no {attr} on {target!r}; not traced")
            continue

        def wrapped(*a, _fn=fn, _name="bench." + span, **kw):
            with TraceAnnotation(_name):
                return _fn(*a, **kw)
        setattr(target, attr, wrapped)
