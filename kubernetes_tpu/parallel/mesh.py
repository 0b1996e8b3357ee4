"""Mesh construction + sharded dispatch of the batch scheduling kernel.

Sharding layout (scaling-book recipe: pick a mesh, annotate shardings, let
XLA insert collectives):

- `DeviceNodeState` row-major arrays shard their node dimension over the
  `"nodes"` mesh axis (`topo` is [K, NP] → shard dim 1).
- Per-node feature arrays (`exist_anti`, `ipa_base`) shard the same way;
  count tables ([C, VMAX]) and pod-level features replicate.
- An optional leading `"cells"` axis runs independent scheduling cells
  (separate clusters / Borg cells) data-parallel: every leaf gains a leading
  cell dimension and the kernel is vmapped over it.

The kernel's cross-node reductions (rotation cumsum, masked max/min, argmax
select) become XLA collectives over ICI; the scan carry's scatter updates
land on whichever shard owns the chosen row.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.device_state import DeviceNodeState
from ..ops.features import BatchFeatures, _pow2
from ..ops.kernel import (LAP_MAX, MAX_NODE_SCORE, ScanCarry, _LAP_BITS,
                          _bounded_div, _bounded_divmod,
                          _resource_eval, _static_masks, _unwrap, _wrap,
                          entry_name, schedule_batch)


def make_mesh(
    n_cells: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Mesh over all (or given) devices: ("cells", "nodes"). With n_cells=1
    every chip shards the node axis of one cluster."""
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if n % max(n_cells, 1) != 0:
        raise ValueError(f"{n} devices not divisible into {n_cells} cells")
    arr = np.array(devs).reshape(n_cells, n // n_cells)
    return Mesh(arr, axis_names=("cells", "nodes"))


def make_multihost_mesh(
    n_hosts: int,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Multi-HOST mesh ("dcn", "ici"): the outer axis spans hosts (data-
    center network), the inner axis a host's chips (ICI). The cluster-state
    node axis shards over BOTH axes jointly (P(("dcn", "ici"))), so one
    cluster's node tensors span every chip of every host; GSPMD then
    decomposes cross-node reductions into an intra-host ICI stage and a
    cross-host DCN stage — the scaling-book recipe for axes that cross the
    slice boundary (SURVEY §2.4 row 9's multi-host story). On real
    multi-host TPU the outer axis must follow the process/host grid
    (jax.devices() orders by process); virtual CPU devices validate the
    sharding + collective decomposition without N real hosts."""
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if n_hosts <= 0 or n % n_hosts != 0:
        raise ValueError(f"{n} devices not divisible into {n_hosts} hosts")
    arr = np.array(devs).reshape(n_hosts, n // n_hosts)
    return Mesh(arr, axis_names=("dcn", "ici"))


def _node_axis_of(mesh: Mesh):
    """The spec entry for the cluster-state node dimension on this mesh:
    "nodes" on a ("cells", "nodes") mesh, the composite ("dcn", "ici") on a
    multi-host mesh."""
    return ("dcn", "ici") if "dcn" in mesh.axis_names else "nodes"


def _state_specs(axis) -> DeviceNodeState:
    return DeviceNodeState(
        alloc_r=P(axis, None), alloc_pods=P(axis), req_r=P(axis, None),
        nonzero=P(axis, None), pod_count=P(axis),
        taint_key=P(axis, None), taint_val=P(axis, None), taint_eff=P(axis, None),
        unsched=P(axis), valid=P(axis), name_id=P(axis),
        topo=P(None, axis),
    )


def _feature_specs(axis="nodes") -> BatchFeatures:
    """Per-node feature arrays shard over the node axis; the rest replicate."""
    specs = {name: P() for name in BatchFeatures._fields}
    for per_node in ("exist_anti", "ipa_base", "sel_match", "extra_ok",
                     "il_score", "na_raw", "aux_room", "nom_pods"):
        specs[per_node] = P(axis)
    specs["nom_req"] = P(axis, None)
    return BatchFeatures(**specs)


# Backwards-compatible single-host specs.
_STATE_SPECS = _state_specs("nodes")


_MESH_STATE_SHARDINGS_CACHE: dict = {}


def mesh_state_shardings(mesh: Mesh) -> DeviceNodeState:
    """The NamedShardings shard_node_state commits the state to, as one
    cached pytree — handed to the delta row patch (ops/device_state.py
    patch_rows / ops/kernel.py patch_carry_rows_pinned) as explicit
    `out_shardings`, so a patched state stays committed to the session
    kernel's input shardings and the next dispatch does not retrace.
    Cached per mesh: the pytree doubles as the jit-cache key over there."""
    got = _MESH_STATE_SHARDINGS_CACHE.get(mesh)
    if got is None:
        got = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            _state_specs(_node_axis_of(mesh)),
            is_leaf=lambda x: isinstance(x, P))
        _MESH_STATE_SHARDINGS_CACHE[mesh] = got
    return got


def shard_node_state(state: DeviceNodeState, mesh: Mesh) -> DeviceNodeState:
    """Place a cell's node state onto the mesh's node axis (ICI on a
    single-host mesh; ICI within hosts + DCN across hosts on a multi-host
    mesh)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        state, _state_specs(_node_axis_of(mesh)))


def shard_features(feats: BatchFeatures, mesh: Mesh) -> BatchFeatures:
    """Place batch features: per-node vectors shard over the node axis,
    count tables and pod-level scalars replicate. With the inputs committed
    to these shardings, the ordinary jitted kernel compiles SPMD over the
    mesh (GSPMD propagation; cross-node reductions become ICI — and on a
    multi-host mesh, ICI+DCN — collectives): the production TPUScheduler
    path needs no separate sharded kernel."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        feats, _feature_specs(_node_axis_of(mesh)))


def collective_report(compiled_text: str, n_hosts: int, per_host: int) -> dict:
    """Classify every collective in compiled HLO by the mesh axis it rides:
    a replica group whose members all live on ONE host is an ICI collective;
    a group spanning hosts rides the DCN. Device id -> host is id//per_host
    (the ("dcn", "ici") mesh lays devices out host-major). Returns
    {"ici": {op: n}, "dcn": {op: n}, "total": {op: n}} — the per-axis
    breakdown the multi-host dryrun prints so the DCN traffic of a sharding
    choice is visible, not guessed."""
    import re

    out = {"ici": {}, "dcn": {}, "total": {}}

    def classify(groups):
        spans_hosts = any(
            len({d // per_host for d in g}) > 1 for g in groups if g)
        return "dcn" if spans_hosts else "ici"

    for m in re.finditer(
            r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(-start)?[^\n]*", compiled_text):
        line = m.group(0)
        op = m.group(1)
        groups = []
        # Match the FULL braced list: a non-greedy `\{(.*?)\}` would stop at
        # the first '}' of nested groups like {{0,1},{2,3}} and classify
        # only the first replica group — a collective whose later groups
        # span hosts would be misreported as ICI.
        rg = re.search(
            r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*|[^{}]*)\}", line)
        if rg is not None:
            inner = rg.group(1)
            if "{" in inner:
                groups = [[int(x) for x in g.split(",") if x.strip()]
                          for g in re.findall(r"\{([\d,]*)\}", inner)]
            elif inner.strip():
                # flat form: replica_groups={0,1,2,3} — one group
                groups = [[int(x) for x in inner.split(",") if x.strip()]]
        stp = re.search(r"source_target_pairs=\{(.*)?\}", line)
        if stp is not None:
            groups = [[int(x) for x in pair.split(",")]
                      for pair in re.findall(r"\{(\d+,\d+)\}", stp.group(0))]
        axis = classify(groups) if groups else "ici"
        out[axis][op] = out[axis].get(op, 0) + 1
        out["total"][op] = out["total"].get(op, 0) + 1
    return out


def mesh_shard_count(mesh: Mesh) -> int:
    """Shards along the cluster-state node axis (the state's row dimension
    must divide by this for the explicit shard_map kernel)."""
    axis = _node_axis_of(mesh)
    names = axis if isinstance(axis, tuple) else (axis,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def mesh_host_split(mesh: Mesh):
    """(n_hosts, per_host) for collective_report: a ("dcn", "ici") mesh
    spans hosts on its outer axis; a ("cells", "nodes") mesh is one host —
    every collective (cells-spanning groups included) rides ICI, so
    per_host must cover ALL the mesh's devices, not just the node axis."""
    if "dcn" in mesh.axis_names:
        return mesh.shape["dcn"], mesh.shape["ici"]
    total = 1
    for n in mesh.axis_names:
        total *= mesh.shape[n]
    return 1, total


def _carry_specs(axis) -> ScanCarry:
    """shard_map specs for a row-local session carry: per-node lanes shard
    the node axis, the (empty, [0, V]) count tables and the rotation scalar
    replicate."""
    return ScanCarry(
        req_r=P(axis, None), nonzero=P(axis, None), pod_count=P(axis),
        fit_ok=P(axis), fit_sc=P(axis), ba=P(axis),
        dns_counts=P(), sa_counts=P(), anti_counts=P(), aff_counts=P(),
        ipa_delta=P(), start=P(), blocked=P(axis), aux_cnt=P(axis))


def _lap_body(state: DeviceNodeState, f: BatchFeatures, n_active, ext0,
              *, batch_pad: int, fit_strategy: int, axis_sizes,
              n_shards: int):
    """Per-shard body of the explicit shard_map lap kernel: the row-local
    (scores_carried ∧ incremental_feas) greedy assignment of
    ops/kernel.py:_lap_schedule, restated so every cross-shard exchange is
    a VISIBLE collective — exactly two small ones per lap:

    1. one ``all_gather`` of an i32 pair per shard — the shard's feasible
       count (global prefix-sum offsets + total_feas) and its contribution
       to F[start-1] (the rotation-rank origin);
    2. one packed ``all_gather`` of [2·LAP_MAX] i64 lanes, max-reduced
       locally — the per-window max-score-then-min-rotation selection keys
       and (negated) the per-window evaluated boundaries. (Not ``pmax``:
       XLA:TPU lowers a 64-bit integer all-reduce for sum only, and the
       packed keys are i64 — established on the v5e, PR 21.)

    Everything else — fit/BA re-eval, window segmentation, the landed-row
    aggregate updates — touches only shard-local rows. Integer arithmetic
    is exactly associative, so results are bit-identical to the
    single-device lap (and therefore to the scan and the host oracle).
    GSPMD compiles the same math from sharding propagation but inserts
    ~2× the collectives per step because it cannot prove the carried
    per-node lanes stay shard-local (collective counts of the two compiled
    programs on a virtual CPU mesh, tests/test_sharded_mesh.py — a count,
    not a speed)."""
    NPl = state.valid.shape[0]
    RADIX = _pow2(NPl * n_shards)  # of the packed selection key
    SHARD_BITS = max(n_shards - 1, 1).bit_length()
    B = batch_pad
    names = tuple(n for n, _s in axis_sizes)
    gather_axis = names if len(names) > 1 else names[0]
    # Flattened shard index, outer-axis-major — matching the host-major
    # device layout of make_multihost_mesh so global row ids line up with
    # the committed sharding's block order.
    shard = None
    for name, size in axis_sizes:
        ai = lax.axis_index(name)
        shard = ai if shard is None else shard * jnp.int32(size) + ai
    gidx = (shard * NPl + jnp.arange(NPl, dtype=jnp.int32)).astype(jnp.int32)
    num = jnp.maximum(f.num_nodes, 1)
    tf = jnp.maximum(f.to_find, 1)
    lanes = jnp.arange(LAP_MAX, dtype=jnp.int32)
    svec = jnp.arange(n_shards, dtype=jnp.int32)
    n_act = n_active.astype(jnp.int32)

    taint_ok, _pns, sel_ok, name_ok, unsched_ok, exist_anti_ok = \
        _static_masks(state, f)
    static_ok = (state.valid & name_ok & unsched_ok & taint_ok & sel_ok
                 & exist_anti_ok & f.extra_ok)
    w_tt, w_fit, _w_pts, _w_ipa, w_ba, _w_na, w_il = (
        f.weights[i] for i in range(7))
    il_term = w_il * f.il_score

    def cond(c):
        return c[0] < n_act

    def body(c):
        done, req_r, nonzero, pod_count, start, out = c
        fit_ok, fit_sc, ba = _resource_eval(
            f, fit_strategy, state.alloc_r, state.alloc_pods,
            req_r, nonzero, pod_count)
        okd = static_ok & fit_ok & (gidx < num)
        Fl = jnp.cumsum(okd.astype(jnp.int32))
        total = (w_tt * jnp.int64(MAX_NODE_SCORE) + w_fit * fit_sc
                 + w_ba * ba + il_term)
        # ---- collective 1: shard feasible-counts + F[start-1] origin -----
        sidx = start - jnp.int32(1)
        own = (start > 0) & (sidx >= shard * NPl) & (sidx < (shard + 1) * NPl)
        lpos = jnp.clip(sidx - shard * NPl, 0, NPl - 1)
        pair = jnp.stack([Fl[-1], jnp.where(own, Fl[lpos], 0)])
        g = lax.all_gather(pair, gather_axis)
        tots = g[:, 0]                                       # [S]
        total_feas = tots.sum()
        F = Fl + jnp.where(svec < shard, tots, 0).sum()      # global prefix
        owner = jnp.clip(_bounded_div(sidx, jnp.int32(NPl), SHARD_BITS),
                         0, n_shards - 1)
        f_start = jnp.where(
            start > 0,
            jnp.where(svec < owner, tots, 0).sum() + g[owner, 1], 0)
        rank = jnp.where(gidx >= start, F - f_start,
                         F + total_feas - f_start)
        rot = _unwrap(gidx - start, num)
        l_full = _bounded_div(total_feas, tf, _LAP_BITS)
        L = jnp.clip(jnp.minimum(l_full, n_act - done),
                     1, LAP_MAX).astype(jnp.int32)
        # one division for the window and its end boundary, as in
        # ops/kernel.py _lap_schedule
        w, rem = _bounded_divmod(rank - 1, tf, _LAP_BITS)
        w = jnp.minimum(w, LAP_MAX)
        seg = jnp.where(okd & (w < L), w, LAP_MAX)
        in_w = seg[None, :] == lanes[:, None]                # [LAP_MAX, NPl]
        key = total * RADIX + (jnp.int32(RADIX - 1) - rot)
        key_w_l = jnp.max(jnp.where(in_w, key[None, :], -1), axis=1)
        is_b = okd & (rem == tf - 1)
        seg_b = jnp.where(is_b, w, LAP_MAX)
        in_b = seg_b[None, :] == lanes[:, None]
        ev_w_l = jnp.min(jnp.where(in_b, rot[None, :] + 1, num), axis=1)
        # ---- collective 2: packed per-window reduction (mins negated) ----
        packed = jnp.concatenate([key_w_l, -ev_w_l.astype(jnp.int64)])
        red = lax.all_gather(packed, gather_axis).max(axis=0)
        key_w = red[:LAP_MAX]
        ev_w = (-red[LAP_MAX:]).astype(jnp.int32)
        has_w = (lanes < L) & (key_w >= 0)
        rot_w = jnp.int32(RADIX - 1) - (key_w & (RADIX - 1)).astype(jnp.int32)
        row_w = jnp.where(has_w, _wrap(start + rot_w, num), -1).astype(jnp.int32)
        start_w = _wrap(start + ev_w, num)
        # ---- apply the landings: shard-local one-hot updates -------------
        chosen_1h = (gidx[None, :] == row_w[:, None]) & has_w[:, None]
        cnt = chosen_1h.any(axis=0)
        c64 = cnt.astype(jnp.int64)
        req_r = req_r + f.request[None, :] * c64[:, None]
        nonzero = nonzero + f.nz_request[None, :] * c64[:, None]
        pod_count = pod_count + cnt.astype(jnp.int32)
        chosen_w = jnp.where(has_w, row_w, -1)
        block = jnp.stack([chosen_w, start_w.astype(jnp.int32)])
        out = lax.dynamic_update_slice(out, block, (jnp.int32(0), done))
        start = start_w[jnp.maximum(L - 1, 0)]
        return (done + L, req_r, nonzero, pod_count, start, out)

    out0 = jnp.full((2, B + LAP_MAX), -1, jnp.int32)
    c0 = (jnp.int32(0), ext0.req_r, ext0.nonzero, ext0.pod_count,
          ext0.start, out0)
    (_done, req_r, nonzero, pod_count, start, out) = lax.while_loop(
        cond, body, c0)
    fit_ok, fit_sc, ba = _resource_eval(
        f, fit_strategy, state.alloc_r, state.alloc_pods,
        req_r, nonzero, pod_count)
    carry = ScanCarry(req_r, nonzero, pod_count, fit_ok, fit_sc, ba,
                      ext0.dns_counts, ext0.sa_counts, ext0.anti_counts,
                      ext0.aff_counts, ext0.ipa_delta, start,
                      ext0.blocked, ext0.aux_cnt)
    return out[:, :B], carry


class _ShardedLap:
    """The compiled explicit-collectives lap kernel for one (mesh,
    batch_pad, fit_strategy, vmax): ``__call__(state, feats, n_active,
    carry_in)`` mirrors TPUScheduler._dispatch's schedule_batch contract —
    fresh (carry_in=None) and chained traces are separate jits, and the
    chained trace DONATES carry_in exactly like schedule_batch does."""

    def __init__(self, mesh: Mesh, batch_pad: int, fit_strategy: int,
                 vmax: int):
        self.mesh = mesh
        axis = _node_axis_of(mesh)
        names = axis if isinstance(axis, tuple) else (axis,)
        axis_sizes = tuple((a, mesh.shape[a]) for a in names)
        n_shards = mesh_shard_count(mesh)
        state_specs = _state_specs(axis)
        feat_specs = _feature_specs(axis)
        carry_specs = _carry_specs(axis)

        def body(state, f, n_active, ext0):
            return _lap_body(state, f, n_active, ext0,
                             batch_pad=batch_pad, fit_strategy=fit_strategy,
                             axis_sizes=axis_sizes, n_shards=n_shards)

        def fresh(state, f, n_active):
            fit_ok0, fit_sc0, ba0 = _resource_eval(
                f, fit_strategy, state.alloc_r, state.alloc_pods,
                state.req_r, state.nonzero, state.pod_count)
            npl = state.valid.shape[0]
            ext0 = ScanCarry(state.req_r, state.nonzero, state.pod_count,
                             fit_ok0, fit_sc0, ba0,
                             f.dns_counts, f.sa_counts, f.anti_counts,
                             f.aff_counts,
                             jnp.zeros((0, vmax), jnp.int64), f.start_index,
                             jnp.zeros(npl, bool), jnp.zeros(npl, jnp.int32))
            return body(state, f, n_active, ext0)

        def chained(state, f, n_active, carry_in):
            return body(state, f, n_active, carry_in)

        # Named under jit_schedule_batch*, where the trace reduction looks
        # for the scheduling programs.
        entry_name("schedule_batch_sharded_lap_fresh")(fresh)
        entry_name("schedule_batch_sharded_lap_chained")(chained)
        self.fresh = jax.jit(jax.shard_map(
            fresh, mesh=mesh,
            in_specs=(state_specs, feat_specs, P()),
            out_specs=(P(), carry_specs), check_vma=False))
        self.chained = jax.jit(jax.shard_map(
            chained, mesh=mesh,
            in_specs=(state_specs, feat_specs, P(), carry_specs),
            out_specs=(P(), carry_specs), check_vma=False),
            donate_argnums=3)

    def __call__(self, state, feats, n_active, carry_in=None):
        if carry_in is None:
            return self.fresh(state, feats, n_active)
        return self.chained(state, feats, n_active, carry_in)

    def lower(self, state, feats, n_active, carry_in=None):
        if carry_in is None:
            return self.fresh.lower(state, feats, n_active)
        return self.chained.lower(state, feats, n_active, carry_in)


_SHARDED_LAP_CACHE: dict = {}


def sharded_lap_schedule(mesh: Mesh, batch_pad: int, fit_strategy: int,
                         vmax: int) -> _ShardedLap:
    """Cached _ShardedLap per (mesh, statics) — the production dispatch's
    row-local path under a mesh (TPUScheduler._dispatch)."""
    key = (mesh, batch_pad, fit_strategy, vmax)
    fn = _SHARDED_LAP_CACHE.get(key)
    if fn is None:
        fn = _ShardedLap(mesh, batch_pad, fit_strategy, vmax)
        _SHARDED_LAP_CACHE[key] = fn
    return fn


def sharded_schedule_batch(mesh: Mesh, batch_pad: int, fit_strategy: int, vmax: int):
    """Build the mesh-sharded (and, when the mesh has >1 cell, cell-vmapped)
    compiled kernel. Call with (state, feats) whose leaves carry a leading
    cell dimension iff n_cells > 1."""
    n_cells = mesh.shape["cells"]
    kernel = partial(schedule_batch, batch_pad=batch_pad,
                     fit_strategy=fit_strategy, vmax=vmax)

    def run(state: DeviceNodeState, feats: BatchFeatures):
        return kernel(state, feats)

    if n_cells > 1:
        run = jax.vmap(run)

    def add_cells(spec: P) -> P:
        return P("cells", *spec) if n_cells > 1 else spec

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    state_specs = jax.tree_util.tree_map(add_cells, _STATE_SPECS, is_leaf=is_spec)
    feat_specs = jax.tree_util.tree_map(add_cells, _feature_specs(), is_leaf=is_spec)
    in_shardings = (
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), state_specs, is_leaf=is_spec),
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), feat_specs, is_leaf=is_spec),
    )
    # jit built ONCE: repeated calls hit the dispatch cache.
    return jax.jit(run, in_shardings=in_shardings)
