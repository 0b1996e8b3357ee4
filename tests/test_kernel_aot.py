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
from jax.sharding import SingleDeviceSharding

# Ceilings, with what was counted when they were set (PR 49, the landed row
# kept on the vector side; in brackets the parent of that PR, whose own
# brackets were PR 25's parent: 11,493, 13,575, 21,147).
# `resource_fit` inside the scan's step: the landed row's evaluation at
# LANES columns with the two reductions that bring its inputs down.
RESOURCE_EVAL_CEILING = 2_500   # 2,144 LeastAllocated, 2,136 Most [1,766 / 1,758: one row, scalar]
SCAN_BODY_CEILING = 3_000       # 2,570 [2,095]
LAP_BODY_CEILING = 3_500        # 2,385 [2,385: the lap kernel is as it was]
NORMALISING_BODY_CEILING = 3_500  # 3,058 [2,434]
# Program events a step (instructions at the top of the loop body that the
# chip runs as an event each: fusions, reductions, copies), at the two scan
# cells' own shapes. Each costs about as much as the next whatever its size
# (PERF.md section 5), so this is the count that keeps the step short.
STEP_EVENTS_CEILING = {"spread-5k": 27, "prefaffinity-5k": 26}  # 22, 21 [58, 54]

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


def _lower_plan(one_chip, state, plan, **statics):
    """`schedule_batch` lowered for the described chip at a built plan's
    shapes and flags (`statics` override the plan's)."""
    from kubernetes_tpu.ops.kernel import schedule_batch

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    flags = dict(has_pns=plan.has_pns, has_ipa_base=plan.has_ipa_base,
                 anti_rowlocal=plan.anti_rowlocal, has_na_pref=plan.has_na_pref,
                 port_selfblock=plan.port_selfblock, has_aux=plan.has_aux,
                 has_nom=plan.has_nom, fit_strategy=plan.fit_strategy)
    flags.update(statics)
    fit_strategy = flags.pop("fit_strategy")
    return schedule_batch.lower(
        jax.tree_util.tree_map(sds, state), jax.tree_util.tree_map(sds, plan.features),
        plan.batch_pad, fit_strategy, plan.vmax,
        n_active=jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        carry_in=None, **flags)


@pytest.mark.parametrize("fit_strategy", [0, 1], ids=["LeastAllocated", "MostAllocated"])
def test_landed_row_resource_eval_stays_small(one_chip, fit_strategy):
    """The scan's per-step resource work as `step` does it since PR 49: the
    landed row's inputs brought down their columns under the mask and ONE
    evaluation at LANES columns, in place of the one-row scalar
    `_resource_eval` (which the step no longer holds). Counted in the real
    program, under its `resource_fit` scope."""
    state, plan = _small_plan(8, True)
    hlo = _lower_plan(one_chip, state, plan, fit_strategy=fit_strategy).compile().as_text()
    scoped = _instructions(hlo, under="/while/body/carry_update/resource_fit")
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
    state, plan = _small_plan(batch, spread,
                              preferred=kernel == "scan_normalised")
    assert (plan.batch_pad > 64) == (kernel != "scan")
    assert plan.engine == {"scan": "scan_carried"}.get(kernel, kernel)
    lowered = _lower_plan(one_chip, state, plan)
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


def _cell_plan(cell):
    """The plan of a scan cell's measured pods at its published size: 5,000
    nodes (8,192 rows), batches of 1,024 (`benchmark/configs/<cell>.json`)."""
    from kubernetes_tpu.core import FakeClientset
    from kubernetes_tpu.models import TPUScheduler
    from kubernetes_tpu.testing.wrappers import make_node, make_pod

    cs = FakeClientset()
    s = TPUScheduler(clientset=cs, max_batch=1024, mesh=None)
    spread = cell == "spread-5k"
    capacity = ({"cpu": "32", "memory": "256Gi", "pods": 110} if spread
                else {"cpu": "4", "memory": "32Gi", "pods": 110})
    for i in range(5000):
        cs.create_node(make_node().name(f"node-{i}").capacity(capacity).zone(
            f"zone-{i % 50 if spread else 0}").obj())
    if spread:
        pod = make_pod().name("probe").req({"cpu": "100m", "memory": "128Mi"}).labels(
            {"app": "spread"}).spread_constraint(
                1, "topology.kubernetes.io/zone", "DoNotSchedule", {"app": "spread"})
    else:
        pod = make_pod().name("probe").req({"cpu": "100m", "memory": "500Mi"}).labels(
            {"color": "red"}).pod_affinity("kubernetes.io/hostname", {"color": "red"},
                                           weight=1)
    return s.build_plan(next(iter(s.profiles.values())), pod.obj(), 1024)


# What the chip runs as an event of its own at the top of a loop body.
_EVENT = re.compile(r" (fusion|reduce|reduce-window|copy|sort|gather|scatter|"
                    r"dynamic-slice|dynamic-update-slice|convolution|transpose)\(")
_ADDRESSED = ("dynamic-slice", "dynamic-update-slice", "gather", "scatter")


@pytest.mark.parametrize("cell,engine", [
    ("spread-5k", "scan_carried"),        # hard spread: feasibility + cumsum a step
    ("prefaffinity-5k", "scan_normalised"),  # incremental feasibility, two rounds
])
def test_scan_step_addresses_nothing_by_a_value_of_its_own(one_chip, cell, engine):
    """The two scan cells' programs at their own shapes (8,192 rows,
    `batch_pad` 1,024): the compiled loop body holds NO dynamic slice,
    dynamic update, gather or scatter at all (so none addressed by a value
    the body computed: the landed row is a mask, its index only a value for
    the results), no `div` / `rem`, and no more program events a step than
    the ceiling."""
    state, plan = _cell_plan(cell)
    assert plan.engine == engine and state.valid.shape == (8192,)
    assert plan.batch_pad == 1024
    hlo = _lower_plan(one_chip, state, plan).compile().as_text()
    body = _instructions(hlo, under="/while/body/")
    assert len(body) > 200, len(body)
    addressed = [l.strip()[:160] for l in body
                 if any(f" {op}(" in l for op in _ADDRESSED)]
    assert not addressed, addressed
    prims = _primitives(body)
    assert prims["div"] == 0 and prims["rem"] == 0, prims
    # no float64 on the chip: the inter-pod normalise truncates as the
    # source's float form does, in integers (`_truncated_percent`, PR 50)
    assert not [l.strip()[:160] for l in body if "f64" in l]
    # the loop body's own computation: the events of one step
    (loop_line,) = [l for l in _instructions(hlo) if " while(" in l]
    name = re.search(r"body=%?([\w.\-]+)", loop_line).group(1)
    text = re.search(r"^%?" + re.escape(name) + r" [^\n]*\{\n(.*?)^\}", hlo,
                     re.S | re.M).group(1)
    events = [l for l in text.splitlines() if _EVENT.search(l)]
    assert 10 <= len(events) <= STEP_EVENTS_CEILING[cell], len(events)


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
