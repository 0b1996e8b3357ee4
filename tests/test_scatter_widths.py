"""The mirror's flush scatters its dirty rows at ONE padded width, the tier of
the most rows a flush may scatter, and each width is a compiled program. How
many rows a flush finds dirty is the workload's to decide (a wave that packs
its pods onto a few nodes dirties 25 rows, the next one 250): a width that
followed the count would be first met, and compiled, where work is being
measured. The benchmark's `prefaffinity-5k` read 3 compiles inside its window
at rehearsal counts before this (tests/benchmark/
test_benchmark_prefaffinity.py holds the cell to 0). The session patch
(`patch_rows`) keeps its tiers."""

import numpy as np
import pytest

from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.models import TPUScheduler
from kubernetes_tpu.ops import device_state
from kubernetes_tpu.ops.device_state import NodeStateMirror, patch_tier
from kubernetes_tpu.testing import make_node, make_pod


def _programs(mirror):
    """Compiled programs of the scatter this mirror's flush runs (the mesh
    plane's is pinned to its shardings)."""
    fn = (device_state._scatter_rows if mirror._shardings is None
          else device_state._sharded_scatter(mirror._shardings))
    try:
        return fn._cache_size()
    except AttributeError:  # pragma: no cover - jax internals moved
        pytest.skip("jit cache size introspection unavailable")


@pytest.fixture
def widths(monkeypatch):
    """The widths the scatter's operands were padded to, in call order."""
    seen = []
    real = NodeStateMirror._dirty_payload

    def recording(self, dirty, width):
        packed, layout = real(self, dirty, width)
        # the rows the one packed buffer carries: its index's length
        (shape,) = [shape for name, _off, shape, _dt in layout
                    if name == "idx"]
        seen.append(int(shape[0]))
        return packed, layout

    monkeypatch.setattr(NodeStateMirror, "_dirty_payload", recording)
    return seen


@pytest.mark.parametrize("capacity, width", [
    (64, 32), (128, 32), (256, 256), (1024, 256), (2048, 2048),
    (8192, 2048), (16384, 4096), (65536, 16384),
])
def test_a_flush_scatters_at_the_tier_of_its_threshold(capacity, width,
                                                       widths):
    """One dirty row or as many as the threshold lets through: the same
    width, which holds them all."""
    mirror = NodeStateMirror(node_capacity=capacity)
    mirror.flush()
    most = int(mirror.scatter_threshold * capacity)
    assert patch_tier(most) == width >= most
    for count in (1, most):
        mirror._dirty.update(range(count))
        mirror.flush()
    assert widths == [width, width]


@pytest.fixture(scope="module")
def cluster():
    cs = FakeClientset()
    sched = TPUScheduler(clientset=cs)
    for i in range(400):
        cs.create_node(make_node().name(f"n{i}").capacity(
            {"cpu": "4", "memory": "32Gi", "pods": 110}).obj())
    cs.create_pod(make_pod().name("first").req({"cpu": "100m"}).obj())
    sched.run_until_idle()
    assert sched.mirror.np_cap == 512
    return cs, sched, {}


@pytest.mark.parametrize("count", [3, 100, 40, 128, 1])
def test_no_flush_after_the_first_meets_a_program(cluster, count, widths):
    """400 nodes stage 512 rows, a quarter of which may be scattered: width
    256 whatever the count, one program, met by the first flush that
    scatters; every later one leaves the device equal to staging and the
    jit's cache as it was."""
    cs, sched, met = cluster
    # pods bound behind the scheduler's back dirty `count` node rows
    for i in range(count):
        cs.create_pod(make_pod().name(f"w{count}-{i}").node(f"n{i}")
                      .req({"cpu": "100m"}).obj())
    # a request no score hint has seen: a plan build, and its flush
    cs.create_pod(make_pod().name(f"probe{count}").req(
        {"cpu": f"{200 + count}m"}).obj())
    sched.run_until_idle()
    assert widths and set(widths) == {256}
    mirror = sched.mirror
    programs = _programs(mirror)
    assert met.setdefault("programs", programs) == programs, (
        f"a flush of {count} dirty rows met a scatter width for the first "
        "time: a compile inside a measured window on the chip")
    assert sched.host_path_pods == 0
    state = mirror.flush()
    n = len(cs.nodes)
    assert np.array_equal(np.asarray(state.pod_count)[:n],
                          mirror.h_pod_count[:n])
    assert np.array_equal(np.asarray(state.req_r)[:n], mirror.h_req_r[:n])
