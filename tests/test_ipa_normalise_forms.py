"""InterPodAffinity's NormalizeScore: the reference scheduler computes in
float64 and truncates (`int64(100 * (float64(a) / float64(b)))`, scoring.go),
with a = raw - min and b = max - min, and so does the program on both of its
paths: the host plugin in that very form (plugins/interpodaffinity.py), the
kernel in integers (ops/kernel.py `_truncated_percent`: the floor, less one
at an exact quotient of 29, 57 or 58 per cent), because the chip has no
float64. A floor alone is another function; this file lists where the two
part, holds the integer form to the float form pair by pair, and holds the
host plugin, the kernel's lane and the benchmark's reference feature to one
another on the pairs that the benchmark's node pools can reach
(`prefaffinity-pools-5k`: nodes of 58 and 110 pods, even raws, spans to 220)."""

import os
import sys

import numpy as np
import pytest

import kubernetes_tpu.core  # noqa: F401  (the plugins import after the core)
from kubernetes_tpu.plugins.interpodaffinity import float_shortfalls

MAX_PODS = 110                       # pods a node holds at most, every config
REACH = 2 * MAX_PODS                 # a weight-1 term pulling both ways
SPAN = 3_000                         # the spans the integer form is held over

# (a, b) that the pools of `prefaffinity-pools-5k` reach (even raws, b <= 220)
# and their neighbours: (pair, scoring.go's value). ISSUE 50 asks for "58 at
# (59, 100)"; the float form reads 59 there (0.59 * 100.0 is 59.0), as a floor
# does: the pairs at which the two part are the first, third, fourth and fifth.
REACHED = [((58, 100), 57), ((59, 100), 59), ((58, 200), 28),
           ((114, 200), 56), ((116, 200), 57), ((118, 200), 59),
           ((60, 100), 60), ((56, 100), 56), ((220, 220), 100)]


def _floor(a, b):
    return 100 * a // b


def _float_then_truncate(a, b):
    return int(100.0 * (float(a) / float(b)))


def _parting(limit):
    return [(a, b) for b in range(1, limit + 1) for a in range(b + 1)
            if _floor(a, b) != _float_then_truncate(a, b)]


def test_where_floor_and_float_then_truncate_part():
    parting = _parting(REACH)
    assert parting == [(29, 50), (29, 100), (57, 100), (58, 100), (87, 150),
                       (58, 200), (114, 200), (116, 200)]
    # always by one, the float form the lower: 0.58 is not a float64
    for a, b in parting:
        assert _floor(a, b) == _float_then_truncate(a, b) + 1
    assert (_floor(29, 50), _float_then_truncate(29, 50)) == (58, 57)
    # every one of them at an exact quotient in the correction set, which is
    # what one Python function states for the kernel and for this file
    assert float_shortfalls() == (29, 57, 58)
    assert all(100 * a % b == 0 and 100 * a // b in float_shortfalls()
               for a, b in parting)
    # nodes of 58 and 110 such pods (even raws) reach four of the eight
    even = [(a, b) for a, b in parting if a % 2 == 0 and b % 2 == 0]
    assert even == [(58, 100), (58, 200), (114, 200), (116, 200)]


def test_the_integer_form_is_the_float_form_for_every_span_to_3000():
    """4,504,500 pairs, as arrays: the kernel's rule (the floor, less one
    where the remainder is 0 and the floor is in the set) against
    numpy's float64 quotient, which is Python's and Go's."""
    short = np.array(float_shortfalls())
    pairs = 0
    for b in range(1, SPAN + 1):
        a = np.arange(b + 1, dtype=np.int64)
        q, r = np.divmod(100 * a, b)
        integer = q - ((r == 0) & np.isin(q, short))
        floated = (100.0 * (a.astype(np.float64) / np.float64(b))).astype(np.int64)
        assert (integer == floated).all(), (b, a[integer != floated][:5])
        pairs += b + 1
    assert pairs == 4_504_500


def _host_plugin(raws):
    from kubernetes_tpu.core.framework import NodeScore
    from kubernetes_tpu.plugins.interpodaffinity import InterPodAffinity

    class _State:
        def read(self, key):
            return {"kubernetes.io/hostname": {"n": 1}}

    scores = [NodeScore(f"n{i}", int(r)) for i, r in enumerate(raws)]
    InterPodAffinity.normalize_score(
        InterPodAffinity.__new__(InterPodAffinity), _State(), None, scores)
    return [s.score for s in scores]


def _kernel_lane(raws):
    """The scan's `ipa` lane as the step computes it, a jitted call."""
    import jax
    import jax.numpy as jnp
    from kubernetes_tpu.ops import kernel

    @jax.jit
    def lane(raw):
        mn, diff = raw.min(), raw.max() - raw.min()
        return jnp.where(diff > 0, kernel._truncated_percent(
            raw - mn, jnp.maximum(diff, 1)), 0)

    return [int(v) for v in lane(jnp.asarray(raws, jnp.int64))]


def _reference_feature(raws):
    """benchmark/reference_features/podAffinity.py's own normalise."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        import features
        module = features.load("reference", "podAffinity")
    finally:
        sys.path.remove(bench)
    raw = np.asarray(raws, np.int64)
    low, high = int(raw.min()), int(raw.max())
    if high == low:              # `State.score`'s own guard
        return [0] * len(raw)
    sound = module.State.normalise(None, raw - low, high - low)
    # and the control that floors is the OTHER function, on these pairs too
    floored = module.CONTROLS["floor_not_float"].normalise(
        None, raw - low, high - low)
    assert [int(v) for v in floored] == [100 * int(a) // (high - low)
                                         for a in raw - low]
    return [int(v) for v in sound]


@pytest.mark.parametrize("form", ["host plugin", "kernel lane",
                                  "reference feature"])
def test_every_path_truncates_as_the_source_does(form):
    """Raws 0, a, b normalise a to scoring.go's value on every path: 57 at
    (58, 100), where a floor gives 58, and 59 at (59, 100), where both do."""
    normalise = {"host plugin": _host_plugin, "kernel lane": _kernel_lane,
                 "reference feature": _reference_feature}[form]
    for (a, b), want in REACHED:
        assert want == _float_then_truncate(a, b)
        low, mid, high = normalise([7, 7 + a, 7 + b])
        assert (low, high) == (0, 100)
        assert mid == want, (form, a, b, mid)
    assert normalise([5, 5, 5]) == [0, 0, 0]     # no span: every node 0
