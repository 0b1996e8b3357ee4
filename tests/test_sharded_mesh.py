"""Multi-chip sharding in the PRODUCTION path: with >1 device visible (the
8-device virtual CPU mesh in conftest), TPUScheduler automatically shards
the node axis over a ("cells", "nodes") mesh and the kernel compiles SPMD —
every test in test_device_equivalence.py therefore runs sharded≡host. These
tests pin the activation so it cannot silently regress to single-device.

The two SPMD-asserting tests (chained sessions, multihost mesh) are live
again: the environment's GSPMD s64/s32 miscompile was fixed at the source
(uniform-int32 scan index/carry in ops/kernel.py — see ROADMAP), so a
breaker-driven fallback to the host path here is a REGRESSION, not an
environment fact."""

import jax
import numpy as np

from kubernetes_tpu.core import FakeClientset
from kubernetes_tpu.core.scheduler import Scheduler
from kubernetes_tpu.models.tpu_scheduler import TPUScheduler
from kubernetes_tpu.testing.wrappers import make_node, make_pod

ZONE = "topology.kubernetes.io/zone"


def test_mesh_auto_activates_with_multiple_devices():
    assert len(jax.devices()) == 8, "conftest must provide the virtual mesh"
    s = TPUScheduler()
    assert s.mesh is not None
    assert dict(s.mesh.shape) == {"cells": 1, "nodes": 8}


def test_state_actually_sharded_across_devices():
    cs = FakeClientset()
    s = TPUScheduler(clientset=cs)
    for i in range(40):
        cs.create_node(make_node().name(f"n{i}")
                       .capacity({"cpu": 8, "memory": "32Gi", "pods": 110})
                       .zone(f"z{i % 4}").obj())
    for i in range(16):
        cs.create_pod(make_pod().name(f"p{i}").req({"cpu": "500m"}).obj())
    s.run_until_idle()
    assert s.scheduled == 16 and s.host_path_pods == 0
    fw = s.framework_for_pod(make_pod().name("probe").req({"cpu": "1"}).obj())
    state, plan = s.build_plan(fw, make_pod().name("probe").req({"cpu": "1"}).obj(), 8)
    # the node axis must physically span all 8 devices
    assert len(state.alloc_r.sharding.device_set) == 8
    assert len(plan.features.sel_match.sharding.device_set) == 8


def test_sharded_chained_sessions_match_host():
    """Multi-batch chained-carry sessions (the depth-2 pipeline) under the
    mesh produce identical assignments to the host oracle."""
    def build(cls):
        cs = FakeClientset()
        kw = {"max_batch": 32} if cls is TPUScheduler else {"deterministic_ties": True}
        s = cls(clientset=cs, **kw)
        for i in range(60):
            cs.create_node(make_node().name(f"n{i}")
                           .capacity({"cpu": 16, "memory": "64Gi", "pods": 110})
                           .zone(f"z{i % 5}").obj())
        for i in range(90):  # 3 chained batches of 32
            cs.create_pod(make_pod().name(f"p{i}").req({"cpu": "250m"})
                          .label("app", "s")
                          .spread_constraint(1, ZONE, "DoNotSchedule", {"app": "s"}).obj())
        s.run_until_idle()
        return {p.name: p.node_name for p in cs.pods.values()}, s
    host_asg, _ = build(Scheduler)
    dev_asg, dev = build(TPUScheduler)
    assert dev.mesh is not None and dev.device_batches >= 3
    assert host_asg == dev_asg


def test_a_short_dispatch_under_the_mesh_equals_the_single_device_run():
    """The scan's bound `n_active` is a replicated scalar under GSPMD, so
    every device leaves the loop at the same trip: a dispatch of 5 pods on a
    64-wide plan, fresh and chained, returns what one device returns,
    results and carry, and writes nothing past the fifth column."""
    def run(mesh):
        cs = FakeClientset()
        s = TPUScheduler(clientset=cs, mesh=mesh, max_batch=64)
        for i in range(40):
            cs.create_node(make_node().name(f"n{i}")
                           .capacity({"cpu": 8 + i % 3, "memory": "32Gi",
                                      "pods": 110})
                           .zone(f"z{i % 4}").obj())
        pod = (make_pod().name("probe").req({"cpu": "250m"}).label("app", "s")
               .spread_constraint(1, ZONE, "DoNotSchedule", {"app": "s"}).obj())
        state, plan = s.build_plan(s.framework_for_pod(pod), pod, 64)
        assert plan.engine == "scan_carried" and plan.batch_pad == 64
        assert s._shard_map_fn(plan) is None   # the GSPMD schedule_batch
        fresh, carry = s._dispatch(state, plan, 5, None)
        fresh = np.asarray(fresh)   # before the chained call donates the carry
        chained, carry = s._dispatch(state, plan, 7, carry)
        return s, fresh, np.asarray(chained), [np.asarray(x) for x in carry]

    sharded, fresh, chained, carry = run("auto")
    _single, fresh1, chained1, carry1 = run(None)
    assert sharded.mesh is not None and _single.mesh is None
    assert (fresh == fresh1).all() and (chained == chained1).all()
    assert (fresh[0, :5] >= 0).all() and (fresh[:, 5:] == -1).all()
    assert (chained[0, :7] >= 0).all() and (chained[:, 7:] == -1).all()
    for got, want in zip(carry, carry1):
        assert got.dtype == want.dtype and (got == want).all()


def test_two_cells_schedule_independently():
    """The "cells" mesh axis (parallel/mesh.py sharded_schedule_batch):
    n_cells=2 vmaps the kernel over two INDEPENDENT scheduling cells
    (separate clusters scheduled data-parallel, 4-way node sharding each);
    every cell's assignments equal its own single-device run."""
    import jax.numpy as jnp

    from kubernetes_tpu.ops.kernel import schedule_batch
    from kubernetes_tpu.parallel import make_mesh
    from kubernetes_tpu.parallel.mesh import sharded_schedule_batch

    def cell_inputs(seed: int):
        cs = FakeClientset()
        s = TPUScheduler(clientset=cs, mesh=None)
        for i in range(32):
            cs.create_node(make_node().name(f"c{seed}-n{i}")
                           .capacity({"cpu": 8 + (i + seed) % 4,
                                      "memory": "32Gi", "pods": 110})
                           .zone(f"z{i % 4}").obj())
        pod = (make_pod().name(f"c{seed}-p").req({"cpu": "500m"})
               .labels({"app": f"cell{seed}"}).obj())
        fw = s.framework_for_pod(pod)
        state, plan = s.build_plan(fw, pod, 8)
        return state, plan

    s0, p0 = cell_inputs(0)
    s1, p1 = cell_inputs(1)
    assert p0.batch_pad == p1.batch_pad and p0.vmax == p1.vmax

    # single-device truth per cell
    r0, _ = schedule_batch(s0, p0.features, p0.batch_pad, p0.fit_strategy,
                           p0.vmax, n_active=np.int32(8))
    r1, _ = schedule_batch(s1, p1.features, p1.batch_pad, p1.fit_strategy,
                           p1.vmax, n_active=np.int32(8))

    mesh = make_mesh(n_cells=2)
    assert dict(mesh.shape) == {"cells": 2, "nodes": 4}
    stack = lambda a, b: jax.tree_util.tree_map(  # noqa: E731
        lambda x, y: jnp.stack([x, y]), a, b)
    run = sharded_schedule_batch(mesh, p0.batch_pad, p0.fit_strategy, p0.vmax)
    out, _carry = run(stack(s0, s1), stack(p0.features, p1.features))
    out = np.asarray(out)
    assert (out[0] == np.asarray(r0)).all()
    assert (out[1] == np.asarray(r1)).all()


def test_multihost_mesh_matches_single_device():
    """(dcn, ici) mesh: the node axis spans hosts; assignments must equal
    the single-device run and the compiled step must contain collectives
    classified per axis (round-4 VERDICT item 7)."""
    import jax
    from kubernetes_tpu.core import FakeClientset
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.parallel import collective_report, make_multihost_mesh
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    if len(jax.devices()) < 4:
        import pytest
        pytest.skip("needs 4 virtual devices")
    devs = jax.devices()[:4]
    mesh = make_multihost_mesh(2, devices=devs)

    def run(mesh_arg):
        cs = FakeClientset()
        s = TPUScheduler(clientset=cs, mesh=mesh_arg, max_batch=32)
        for i in range(32):
            cs.create_node(make_node().name(f"n{i}")
                           .capacity({"cpu": "8", "memory": "16Gi",
                                      "pods": 110})
                           .zone(f"z{i % 4}").obj())
        proto = make_pod().name("proto").req(
            {"cpu": "250m", "memory": "128Mi"}).labels({"a": "b"}).obj()
        for i in range(64):
            cs.create_pod(proto.clone_from_template(f"p{i}"))
        s.run_until_idle()
        return {p.name: p.node_name for p in cs.pods.values()}, s

    single, _s1 = run(None)
    multi, s2 = run(mesh)
    assert single == multi
    assert s2.scheduled == 64

    from kubernetes_tpu.ops.kernel import schedule_batch
    fw = next(iter(s2.profiles.values()))
    state, plan = s2.build_plan(
        fw, make_pod().name("probe").req({"cpu": "250m"}).obj(), 32)
    lowered = schedule_batch.lower(
        state, plan.features, plan.batch_pad, plan.fit_strategy, plan.vmax,
        n_active=32, carry_in=None, has_pns=plan.has_pns,
        has_ipa_base=plan.has_ipa_base, anti_rowlocal=plan.anti_rowlocal,
        has_na_pref=plan.has_na_pref, port_selfblock=plan.port_selfblock,
        has_aux=plan.has_aux)
    report = collective_report(lowered.compile().as_text(), 2, 2)
    assert report["total"], "no collectives in the multi-host step"


def test_shard_map_is_production_dispatch_for_row_local_plans():
    """Row-local plans at production batch tiers (>64) dispatch through the
    EXPLICIT shard_map lap kernel (parallel/mesh.py sharded_lap_schedule) —
    hand-placed minimal collectives instead of GSPMD inference — and the
    chained multi-batch session stays bit-identical to the host oracle."""
    def build(cls):
        cs = FakeClientset()
        kw = ({"max_batch": 128} if cls is TPUScheduler
              else {"deterministic_ties": True})
        s = cls(clientset=cs, **kw)
        for i in range(96):
            cs.create_node(make_node().name(f"n{i}")
                           .capacity({"cpu": 16, "memory": "64Gi",
                                      "pods": 110})
                           .zone(f"z{i % 5}").obj())
        proto = (make_pod().name("proto")
                 .req({"cpu": "250m", "memory": "128Mi"})
                 .labels({"app": "rl"}).obj())
        for i in range(300):  # 3 chained dispatches of 128
            cs.create_pod(proto.clone_from_template(f"p{i}"))
        s.run_until_idle()
        return {p.name: p.node_name for p in cs.pods.values()}, s
    host_asg, _ = build(Scheduler)
    dev_asg, dev = build(TPUScheduler)
    assert dev.mesh is not None
    assert dev.shard_map_dispatches >= 3, (
        "row-local plan did not ride the shard_map lap kernel")
    assert host_asg == dev_asg
    assert dev.host_path_pods == 0


def test_shard_map_collectives_at_or_below_gspmd_baseline():
    """The collective budget (counts of compiled programs on the virtual
    CPU mesh, not a speed): per step, the
    explicit shard_map path must not exceed the GSPMD-compiled baseline in
    any op class total, and should drive the overall count DOWN."""
    import numpy as np
    from kubernetes_tpu.ops.kernel import schedule_batch
    from kubernetes_tpu.parallel.mesh import (collective_report,
                                              mesh_host_split)

    cs = FakeClientset()
    s = TPUScheduler(clientset=cs, max_batch=128)
    for i in range(96):
        cs.create_node(make_node().name(f"n{i}")
                       .capacity({"cpu": 16, "memory": "64Gi", "pods": 110})
                       .zone(f"z{i % 4}").obj())
    probe = make_pod().name("probe").req({"cpu": "250m"}).obj()
    rep = s.collective_counts(probe)
    assert rep is not None and rep["path"] == "shard_map", rep
    assert rep["total"], "shard_map step compiled with no collectives"
    # GSPMD baseline of the SAME plan
    fw = s.framework_for_pod(probe)
    state, plan = s.build_plan(fw, probe, 128)
    lowered = schedule_batch.lower(
        state, plan.features, plan.batch_pad, plan.fit_strategy, plan.vmax,
        n_active=np.int32(128), carry_in=None, has_pns=plan.has_pns,
        has_ipa_base=plan.has_ipa_base, anti_rowlocal=plan.anti_rowlocal,
        has_na_pref=plan.has_na_pref, port_selfblock=plan.port_selfblock,
        has_aux=plan.has_aux)
    n_hosts, per_host = mesh_host_split(s.mesh)
    base = collective_report(lowered.compile().as_text(), n_hosts, per_host)
    assert sum(rep["total"].values()) <= sum(base["total"].values()), (
        rep["total"], base["total"])


def test_sidecar_over_uds_matches_in_process():
    """The UDS sidecar prototype (docs/SIDECAR.md): a separate OS process
    owns the device path; scheduling a batch over the socket produces the
    in-process scheduler's assignments."""
    import os
    import re
    import subprocess
    import sys
    import tempfile
    import time

    from kubernetes_tpu.core import FakeClientset, Scheduler
    from kubernetes_tpu.parallel.sidecar import SidecarClient
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    def nodes():
        return [make_node().name(f"n{i}")
                .capacity({"cpu": "8", "memory": "16Gi", "pods": 110})
                .zone(f"z{i % 2}").obj() for i in range(6)]

    def pods():
        proto = make_pod().name("proto").req(
            {"cpu": "500m", "memory": "256Mi"}).labels({"a": "b"}).obj()
        return [proto.clone_from_template(f"p{i}") for i in range(20)]

    # in-process oracle
    cs = FakeClientset()
    host = Scheduler(clientset=cs, deterministic_ties=True)
    for n in nodes():
        cs.create_node(n)
    oracle_pods = pods()
    for p in oracle_pods:
        cs.create_pod(p)
    host.run_until_idle()
    oracle = [cs.bindings.get(p.uid) for p in oracle_pods]

    sock_path = os.path.join(tempfile.mkdtemp(), "sidecar.sock")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubernetes_tpu.parallel.sidecar",
         "--socket", sock_path, "--platform", "cpu"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        deadline = time.monotonic() + 120
        line = ""
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if re.search("serving on", line):
                break
        client = SidecarClient(sock_path)
        assert client.ping()
        client.sync_nodes(nodes())
        # two batches: the second sees the first's load (mirror continuity)
        batch = pods()
        got = client.schedule(batch[:10]) + client.schedule(batch[10:])
        assert got == oracle, list(zip(got, oracle))
        client.shutdown_server()
        client.close()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
