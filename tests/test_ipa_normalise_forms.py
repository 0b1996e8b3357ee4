"""InterPodAffinity's NormalizeScore, two forms: the program floors
(`100 * a // b`: plugins/interpodaffinity.py, ops/kernel.py), the reference
scheduler computes in float64 and truncates (`int64(100 * (float64(a) /
float64(b)))`, scoring.go), with a = raw - min and b = max - min. They are not
the same function: this file lists where they part, and shows that the
benchmark's `prefaffinity-5k` cannot reach such a pair, so the floor is exact
THERE and only there by this argument."""

MAX_PODS = 110                       # pods a node holds at most, every config
REACH = 2 * MAX_PODS                 # a weight-1 term pulling both ways


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


def test_prefaffinity_5k_cannot_reach_a_parting_pair():
    """Every pod of `prefaffinity-5k` carries one weight-1 term that selects
    every other, so a pod on a node adds 2 to its raw score (its term and the
    incoming pod's), and 4 cpu / 100m caps a node at 40 pods: raws are even
    and max - min <= 80."""
    cap = 4000 // 100
    assert cap == 40
    reachable = {(a, b) for b in range(2, 2 * cap + 1, 2)
                 for a in range(0, b + 1, 2)}
    assert len(reachable) == 860
    assert not reachable & set(_parting(REACH))
    # and what would: the same pods on a node that holds 50 of them
    even = [(a, b) for a, b in _parting(REACH) if a % 2 == 0 and b % 2 == 0]
    assert even[0] == (58, 100)
    # odd raws (a term that pulls one way only) part first at 25 pods' span
    assert _parting(REACH)[0] == (29, 50)


def test_the_oracle_floors():
    """The host plugin's form, so that the comment there stays true."""
    from kubernetes_tpu.core.framework import NodeScore
    from kubernetes_tpu.plugins.interpodaffinity import InterPodAffinity

    class _State:
        def read(self, key):
            return {"kubernetes.io/hostname": {"n": 1}}

    scores = [NodeScore("a", 0), NodeScore("b", 29), NodeScore("c", 50)]
    InterPodAffinity.normalize_score(
        InterPodAffinity.__new__(InterPodAffinity), _State(), None, scores)
    assert [s.score for s in scores] == [0, 58, 100]
