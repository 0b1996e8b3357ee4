"""Pod features, found by name.

A key of a pod template beyond the core's (`reference.CORE_POD_KEYS`) is a
feature, and a feature is two files named after the key:

- `reference_features/<key>.py`: what the key means, in numpy, for the plain
  reference (`reference.py` calls it; see there for the interface);
- `object_features/<key>.py`: `apply(builder, value, template) -> builder`,
  which puts the same thing on the program's own pod (`objects.py` calls it).

Both are searched under the directory the caller names first, the run's
`--bench-dir` (`run.py` hands it to the driver and to the reference, and the
open loop hands it on to its sender process), and beside this file second. A
key with one file and not the other is an error wherever it is first met: a
run must never schedule a pod that the reference would see in another form,
nor check one that the program was never handed.

Standard library only: the reference imports this module.
"""

from __future__ import annotations

import functools
import importlib.util
import os
from typing import List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
SIDES = {"reference": "reference_features", "objects": "object_features"}


class Unpaired(Exception):
    """A feature has one of its two files and lacks the other."""


def search_path(bench_dir: Optional[str]) -> List[str]:
    if not bench_dir or os.path.abspath(bench_dir) == HERE:
        return [HERE]
    return [os.path.abspath(bench_dir), HERE]


def _find(side: str, key: str, dirs: List[str]) -> Optional[str]:
    for d in dirs:
        path = os.path.join(d, SIDES[side], key + ".py")
        if os.path.isfile(path):
            return path
    return None


@functools.lru_cache(maxsize=None)
def _module(path: str):
    name = "bench_feature_" + "_".join(path.split(os.sep)[-2:])[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(side: str, key: str, bench_dir: Optional[str] = None):
    """The `side` module of feature `key`, looked for under `bench_dir` and
    then beside this file; None where the key has no file on either side
    (the caller refuses it as unmodelled)."""
    dirs = search_path(bench_dir)
    found = {s: _find(s, key, dirs) for s in SIDES}
    if not any(found.values()):
        return None
    for s, path in found.items():
        if path is None:
            raise Unpaired(
                f"pod feature {key!r} has no {SIDES[s]}/{key}.py beside its "
                f"other file (searched {dirs})")
    return _module(found[side])
