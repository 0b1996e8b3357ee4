"""Prometheus text exposition, read the plain way: series -> value, window
deltas, and a histogram quantile by linear interpolation inside the bucket
(what `histogram_quantile` does). The benchmark's own reading of a
`/metrics` page; nothing here comes from the program."""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

Series = Tuple[str, Tuple[Tuple[str, str], ...]]
_LINE = re.compile(r"([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{([^}]*)\})?\s+(\S+)")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> Dict[Series, float]:
    out: Dict[Series, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        out[(m.group(1), labels)] = value
    return out


def delta(after: Dict[Series, float], before: Dict[Series, float]
          ) -> Dict[Series, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def total(series: Dict[Series, float], name: str, **labels: str) -> float:
    """Sum of the series called `name` whose labels include `labels`."""
    want = set(labels.items())
    return sum(v for (n, ls), v in series.items()
               if n == name and want <= set(ls))


def by_label(series: Dict[Series, float], name: str, label: str
             ) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for (n, ls), v in series.items():
        if n == name:
            key = dict(ls).get(label, "")
            out[key] = out.get(key, 0.0) + v
    return out


def quantile(series: Dict[Series, float], name: str, q: float
             ) -> Optional[float]:
    """The q-quantile of histogram `name` (its `_bucket` series, label sets
    summed per `le`); None when it holds no observation. Bucket resolution:
    the value is interpolated inside the bucket the rank falls in."""
    buckets: Dict[float, float] = {}
    for (n, ls), v in series.items():
        if n == name + "_bucket":
            le = dict(ls).get("le", "+Inf")
            edge = float("inf") if le in ("+Inf", "inf") else float(le)
            buckets[edge] = buckets.get(edge, 0.0) + v
    edges = sorted(buckets)
    if not edges or buckets[edges[-1]] <= 0:
        return None
    rank = q * buckets[edges[-1]]
    lower, below = 0.0, 0.0
    for edge in edges:
        if buckets[edge] >= rank:
            if edge == float("inf"):
                return lower
            inside = buckets[edge] - below
            return lower + (edge - lower) * ((rank - below) / inside
                                             if inside > 0 else 1.0)
        lower, below = edge, buckets[edge]
    return lower
