"""What a profiler session would get from a loop's stages, without a
profiler: ``rec = StageAnnotations()``, ``ledger._annotation = rec`` (a
``StageLedger``'s stand-in for ``jax.profiler.TraceAnnotation``), and
``rec.opened`` holds ``(name, stats)`` of every annotated stage in the order
they opened. ``stats`` is what the stage opened with, plus what it said
while it was open (``_Stage.say``: a pop's ``pods`` and ``run``)."""

from typing import List, Tuple


class _Open:
    __slots__ = ("stats",)

    def __init__(self, stats: dict):
        self.stats = stats

    def set_metadata(self, **stats) -> None:
        self.stats.update(stats)

    def __enter__(self) -> "_Open":
        return self

    def __exit__(self, *exc) -> bool:
        return False


class StageAnnotations:
    def __init__(self):
        self.opened: List[Tuple[str, dict]] = []

    def __call__(self, name: str, **stats) -> _Open:
        self.opened.append((name, stats))
        return _Open(stats)
