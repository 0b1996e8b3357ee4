"""Compile-only guards for the v5e (on-chip-measurement guide §2): the
scheduling kernels' loop bodies, compiled in the sandbox by the TPU's own
compiler for a chip that is described and not attached. Counts of optimized-HLO
instructions, never times. XLA:TPU expands a general int64 `//` into a 64-step
long division (~1,900 scalar instructions each); ops/kernel.py's
`_bounded_divmod` is what keeps that out of the loops, and these tests are what
keeps it so.

All of it in this one file, behind one module-scoped fixture: only one process
may hold the TPU's library."""

import collections
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import SingleDeviceSharding

# Ceilings, with what was counted when they were set (PR 25; the parent of
# that PR in brackets).
RESOURCE_EVAL_CEILING = 2_500   # 1,766 LeastAllocated, 1,758 Most [11,493]
# PR 32 (the scan loops `n_active` times, a `while` with no trip count known
# to the compiler): 2,093 and 2,440.
SCAN_BODY_CEILING = 3_000       # 2,095 [13,575]
LAP_BODY_CEILING = 3_500        # 2,385 [21,147]
NORMALISING_BODY_CEILING = 3_500  # 2,434 (PR 31: the scan that normalises)

_INSTRUCTION = re.compile(r"\s+(ROOT )?%?[\w.\-]+ = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# In the lowered module: the program's `n_active` argument, what the loop
# binds it to, and the one comparison its condition makes.
_N_ACTIVE_ARG = re.compile(r'(%arg\d+): tensor<i32>[^%]*? loc\("n_active"\)')
_LOOP_COND = re.compile(
    r"stablehlo\.while\((?P<binds>.*?)\)[^\n]*\n\s*cond \{\s*"
    r"%\d+ = stablehlo\.compare\s+LT, (?P<count>%\w+), (?P<bound>%\w+),"
    r"[^\n]*\n\s*stablehlo\.return")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the missing compiler raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _instructions(hlo_text, under=""):
    return [l for l in hlo_text.splitlines()
            if _INSTRUCTION.match(l) and under in l]


def _primitives(lines):
    """The JAX primitive each instruction came from (last part of op_name)."""
    names = (_OP_NAME.search(l) for l in lines)
    return collections.Counter(m.group(1).rsplit("/", 1)[-1] for m in names if m)


@pytest.mark.parametrize("fit_strategy", [0, 1], ids=["LeastAllocated", "MostAllocated"])
def test_landed_row_resource_eval_stays_small(one_chip, fit_strategy):
    """The scan's per-step scalar work: `_resource_eval` on the one landed
    row, inside a loop, as `step` calls it."""
    from kubernetes_tpu.ops.kernel import _resource_eval
    from kubernetes_tpu.ops.features import BatchFeatures

    NP, R, STEPS = 8192, 8, 1024

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fields = dict(request=S((R,), jnp.int64), has_request=S((), jnp.int64),
                  enable=S((5,), jnp.int32), nz_request=S((2,), jnp.int64),
                  fit_slots=S((2,), jnp.int32), fit_weights=S((2,), jnp.int64),
                  ba_skip=S((), jnp.int64))
    f = BatchFeatures(**{k: fields.get(k) for k in BatchFeatures._fields})

    def loop(f, alloc_r, alloc_pods, req_r, nonzero, pod_count, rows):
        def body(i, c):
            req_r, nonzero, pod_count, ok, sc, ba = c
            row = rows[i]
            req_r = req_r.at[row].add(f.request)
            nonzero = nonzero.at[row].add(f.nz_request)
            pod_count = pod_count.at[row].add(1)
            with jax.named_scope("resource_fit"):
                r_ok, r_sc, r_ba = _resource_eval(
                    f, fit_strategy, alloc_r[row], alloc_pods[row],
                    req_r[row], nonzero[row], pod_count[row])
            return (req_r, nonzero, pod_count, ok.at[row].set(r_ok),
                    sc.at[row].set(r_sc), ba.at[row].set(r_ba))
        z = jnp.zeros(NP, jnp.int64)
        return lax.fori_loop(0, STEPS, body,
                             (req_r, nonzero, pod_count, jnp.zeros(NP, bool), z, z))

    hlo = jax.jit(loop).lower(
        f, S((NP, R), jnp.int64), S((NP,), jnp.int64), S((NP, R), jnp.int64),
        S((NP, 2), jnp.int64), S((NP,), jnp.int32), S((STEPS,), jnp.int32),
    ).compile().as_text()
    scoped = _instructions(hlo, under="resource_fit")
    assert 200 < len(scoped) <= RESOURCE_EVAL_CEILING, len(scoped)
    prims = _primitives(scoped)
    assert prims["div"] == 0 and prims["rem"] == 0, prims


def _small_plan(batch, spread, preferred=False):
    from kubernetes_tpu.core import FakeClientset
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    cs = FakeClientset()
    s = TPUScheduler(clientset=cs, max_batch=batch)
    for i in range(48):
        cs.create_node(make_node().name(f"node-{i}").capacity(
            {"cpu": "32", "memory": "256Gi", "pods": 110}).zone(f"zone-{i % 4}").obj())
    pod = make_pod().name("probe").req({"cpu": "100m", "memory": "128Mi"}).labels(
        {"app": "spread"})
    if spread:
        pod = pod.spread_constraint(1, "topology.kubernetes.io/zone",
                                    "DoNotSchedule", {"app": "spread"})
    if preferred:
        pod = pod.pod_affinity("kubernetes.io/hostname", {"app": "spread"},
                               weight=1)
    return s.build_plan(next(iter(s.profiles.values())), pod.obj(), batch)


@pytest.mark.parametrize("kernel,batch,spread,ceiling", [
    ("scan", 8, True, SCAN_BODY_CEILING),      # spread-5k.waves' program
    ("lap", 128, False, LAP_BODY_CEILING),     # basic-5k.waves' program
    # prefaffinity-5k.waves' program: a preferred inter-pod term, so every
    # score is recomputed and normalised over the kept rows at each step
    ("scan_normalised", 128, False, NORMALISING_BODY_CEILING),
])
def test_loop_body_holds_no_division_expansion(one_chip, kernel, batch, spread, ceiling):
    """`schedule_batch` as the wave cells run it, at a small cluster's
    shapes (the loop body's scalar code does not depend on them): nothing in
    the loop comes from a `div` or `rem`, the body stays under its
    ceiling, and the loop runs until a counter reaches the `n_active`
    argument: no program's trip count is its `batch_pad`."""
    from kubernetes_tpu.ops.kernel import schedule_batch

    state, plan = _small_plan(batch, spread,
                              preferred=kernel == "scan_normalised")
    assert (plan.batch_pad > 64) == (kernel != "scan")
    assert plan.engine == {"scan": "scan_carried"}.get(kernel, kernel)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    lowered = schedule_batch.lower(
        jax.tree_util.tree_map(sds, state), jax.tree_util.tree_map(sds, plan.features),
        plan.batch_pad, plan.fit_strategy, plan.vmax,
        n_active=jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        carry_in=None, has_pns=plan.has_pns, has_ipa_base=plan.has_ipa_base,
        anti_rowlocal=plan.anti_rowlocal, has_na_pref=plan.has_na_pref,
        port_selfblock=plan.port_selfblock, has_aux=plan.has_aux,
        has_nom=plan.has_nom)
    module = lowered.as_text(debug_info=True)
    n_active = _N_ACTIVE_ARG.search(module).group(1)
    (loop,) = _LOOP_COND.finditer(module)
    assert f"{loop['bound']} = {n_active}" in re.split(r",\s*", loop["binds"]), (
        "the loop's condition does not compare with the n_active argument")
    hlo = lowered.compile().as_text()
    (loop_line,) = [l for l in _instructions(hlo) if " while(" in l]
    assert "known_trip_count" not in loop_line
    body = _instructions(hlo, under="/while/body/")
    prims = _primitives(body)
    assert prims["div"] == 0 and prims["rem"] == 0, prims
    assert 200 < len(body) <= ceiling, len(body)
    assert any("resource_fit" in l for l in body), "the scope names left the HLO"


def test_the_preemption_cells_programs_compile_for_the_chip(one_chip):
    """`preempt-5k.waves` (PR 43) meets two programs no cell met before: the
    dry run with a nominated lane in its fit (one program for an empty and
    a filled lane: the lane is always at full width) and the lap kernel of a
    plan built under nominations (`has_nom`). Both compile for the chip, and
    the dry run's reprieve loop holds no division expansion either."""
    from kubernetes_tpu.ops.kernel import dry_run_preemption, schedule_batch

    state, plan = _small_plan(128, False)
    NP, R, K = state.valid.shape[0], plan.features.request.shape[0], 8
    assert not plan.has_nom and plan.features.nom_req.shape[0] == 0

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def sds(x):
        return S(x.shape, x.dtype)

    f = jax.tree_util.tree_map(sds, plan.features)._replace(
        nom_req=S((NP, R), jnp.int64), nom_pods=S((NP,), jnp.int32))
    state = jax.tree_util.tree_map(sds, state)
    hlo = dry_run_preemption.lower(
        state, f, S((NP, K, R), jnp.int64), S((NP, K), jnp.bool_), K,
    ).compile().as_text()
    body = _instructions(hlo, under="/while/body/") or _instructions(hlo)
    prims = _primitives(body)
    assert prims["div"] == 0 and prims["rem"] == 0, prims
    assert any("resource_fit" in l or "sub" in l for l in body)
    lap = schedule_batch.lower(
        state, f, plan.batch_pad, plan.fit_strategy, plan.vmax,
        n_active=S((), jnp.int32), carry_in=None, has_pns=plan.has_pns,
        has_ipa_base=plan.has_ipa_base, anti_rowlocal=plan.anti_rowlocal,
        has_na_pref=plan.has_na_pref, port_selfblock=plan.port_selfblock,
        has_aux=plan.has_aux, has_nom=True).compile().as_text()
    body = _instructions(lap, under="/while/body/")
    prims = _primitives(body)
    assert prims["div"] == 0 and prims["rem"] == 0, prims
    assert 200 < len(body) <= LAP_BODY_CEILING + 500, len(body)


def test_the_daemonset_cells_programs_compile_for_the_chip(one_chip):
    """`daemonset-15k.waves` (PR 47) meets two shapes no cell met before:
    the lap kernel of a plan over a narrowed row set (64 rows for the one
    named node, batches of 1,024) and every program of the node state at
    the 16,384-row tier (the lap kernel of a plain template, which
    `warm_for` and the full build of a wave run, and the flush's one
    scatter, 4,096 rows wide there). All compile for the chip; the narrowed
    lap's body holds no division expansion and is no larger than the lap's
    at any other tier."""
    from kubernetes_tpu.core import FakeClientset
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.ops.device_state import (_scatter_rows, _unpacked,
                                                 pack, patch_tier)
    from kubernetes_tpu.ops.kernel import schedule_batch
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def sds(x):
        return S(x.shape, x.dtype)

    cs = FakeClientset()
    s = TPUScheduler(clientset=cs, max_batch=1024, mesh=None)
    for i in range(6):
        cs.create_node(make_node().name(f"node-{i}").capacity(
            {"cpu": "4", "memory": "32Gi", "pods": 110}).obj())
    pinned = make_pod().name("ds").node_affinity_name("node-3").obj()
    state, plan = s.build_plan(next(iter(s.profiles.values())), pinned, 1024)
    assert plan.rows == (3,) and state.valid.shape == (64,)
    assert plan.rides_lap and plan.batch_pad == 1024

    def lap(state, plan, rows):
        def at(x):  # a per-row array at another row tier
            return S((rows,) + x.shape[1:], x.dtype)
        f = jax.tree_util.tree_map(sds, plan.features)
        from kubernetes_tpu.ops.features import ROW_FIELDS
        f = f._replace(**{n: at(getattr(f, n)) for n in ROW_FIELDS})
        st = jax.tree_util.tree_map(at, state)._replace(
            topo=S((state.topo.shape[0], rows), state.topo.dtype))
        return schedule_batch.lower(
            st, f, plan.batch_pad, plan.fit_strategy, plan.vmax,
            n_active=S((), jnp.int32), carry_in=None, has_pns=plan.has_pns,
            has_ipa_base=plan.has_ipa_base, anti_rowlocal=plan.anti_rowlocal,
            has_na_pref=plan.has_na_pref, port_selfblock=plan.port_selfblock,
            has_aux=plan.has_aux, has_nom=plan.has_nom).compile().as_text(), \
            st, f

    for rows in (64, 16384):
        hlo, st, f16k = lap(state, plan, rows)
        body = _instructions(hlo, under="/while/body/")
        prims = _primitives(body)
        assert prims["div"] == 0 and prims["rem"] == 0, (rows, prims)
        assert 200 < len(body) <= LAP_BODY_CEILING, (rows, len(body))
    # the mirror's flush at the 16,384-row tier: one width, 4,096 rows
    width = patch_tier(int(0.25 * 16384))
    assert width == 4096
    # (its operand is one packed buffer since PR 48: the index and every
    # column's rows, taken apart inside the scatter's program)
    rows_of = {name: np.zeros((width,) + x.shape[1:], x.dtype)
               for name, x in zip(st._fields[:-1], st[:-1])}
    rows_of["topo"] = np.zeros((st.topo.shape[0], width), st.topo.dtype)
    buf, layout = pack({"idx": np.zeros(width, np.int32), **rows_of})
    _scatter_rows.lower(st, S(buf.shape, buf.dtype), layout=layout).compile()
    # and the unpack of a full build's features at that tier
    buf, layout = pack({name: np.zeros(x.shape, x.dtype) for name, x in
                        zip(f16k._fields, f16k)})
    _unpacked.lower(S(buf.shape, buf.dtype), layout=layout).compile()
