"""Device mirror of the scheduler snapshot: fixed-capacity SoA node tensors.

This is the TPU-era equivalent of the reference's incremental snapshot refresh
(pkg/scheduler/backend/cache/cache.go:206 UpdateSnapshot, generation walk at
:236-262): the mirror keeps one row per node in `snapshot.node_info_list`
order, brings in line only rows whose NodeInfo.generation advanced (or whose
list position changed), and flushes them to device with a scatter when few rows
are dirty, a full upload otherwise. A row is encoded whole (`_encode_row`) when
its node changed (NodeInfo.node_generation); a row of which only the pods moved
has its three dynamic columns written, many rows in one array pass
(`_write_pod_columns`).

Row order == snapshot list order, so the kernel's rotation arithmetic
(schedule_one.go:816 nextStartNodeIndex) operates directly on row indices.

All quantities are int64: resource units are integers by construction
(api/resource.py canonicalises CPU to millicores, memory to bytes), and the
kernel's score math is specified in exact integer arithmetic so host oracle
and device agree bit-for-bit (see ops/kernel.py).
"""

from __future__ import annotations

import math
from typing import (Dict, List, Mapping, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

import os

import jax

jax.config.update("jax_enable_x64", True)


def enable_persistent_compilation_cache() -> None:
    """Persistent XLA compilation cache: a cold kernel compile is the
    dominant start-up cost on an accelerator, and the perf/bench harnesses
    start fresh processes per run — without this every process pays every
    compile again. Called from TPUScheduler.__init__ (constructing the
    device-backed scheduler is the opt-in; merely importing the library
    must not redirect an embedding application's JAX caching). Placement
    is compile_cache.cache_dir()'s one rule: JAX_COMPILATION_CACHE_DIR when
    set (JAX configures itself from it — nothing is set here), else
    <checkout>/.jax_cache."""
    if jax.config.jax_compilation_cache_dir:
        return  # the environment or the application already placed it
    from ..compile_cache import cache_dir
    path = cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:  # read-only checkout: run uncached, and say so
        import logging
        logging.getLogger(__name__).warning(
            "no persistent compile cache (%s); set JAX_COMPILATION_CACHE_DIR "
            "to a writable directory", e)
        return
    jax.config.update("jax_compilation_cache_dir", path)


import jax.numpy as jnp  # noqa: E402

from ..api import resource as res  # noqa: E402
from ..core.metrics import Counter  # noqa: E402
from ..core.node_info import NodeInfo  # noqa: E402
from .codebook import EFFECT_IDS, Codebook  # noqa: E402

# Resource slot layout: [cpu_milli, memory, ephemeral_storage, *scalar_slots].
BASE_RESOURCES = 3
SLOT_CPU = 0
SLOT_MEMORY = 1
SLOT_EPHEMERAL = 2


class DeviceNodeState(NamedTuple):
    """The pytree of node tensors the kernel consumes."""

    alloc_r: jnp.ndarray      # [NP, R] i64 allocatable per resource slot
    alloc_pods: jnp.ndarray   # [NP]    i64 allocatable pod count
    req_r: jnp.ndarray        # [NP, R] i64 requested (assumed+bound pods)
    nonzero: jnp.ndarray      # [NP, 2] i64 non-zero-default cpu/mem aggregate
    pod_count: jnp.ndarray    # [NP]    i32
    taint_key: jnp.ndarray    # [NP, T] i32 interned taint keys (0 pad)
    taint_val: jnp.ndarray    # [NP, T] i32
    taint_eff: jnp.ndarray    # [NP, T] i32 (EFFECT_* ids; 0 pad = inert)
    unsched: jnp.ndarray      # [NP]    bool node.spec.unschedulable
    valid: jnp.ndarray        # [NP]    bool row holds a live node
    name_id: jnp.ndarray      # [NP]    i32 interned node name
    topo: jnp.ndarray         # [K, NP] i32 per-axis topology value ids (0 = absent)


def patch_tier(n: int) -> int:
    """Dirty-row scatter/patch tiers: {32, 256, pow2 from 2048}. Each
    distinct padded length is a separate XLA compile of the patch jits
    (row scatter + carry re-eval), and event-driven patch waves — peer
    shards' bind bursts above all — arrive in near-arbitrary sizes, so
    pow2 tiers from 1 put ~10 compiles inside a sharded run's measured
    window. Padding repeats a real index; duplicate scatter indices write
    identical values, so a coarse tier is exact (just a few wasted rows
    of device work)."""
    if n <= 32:
        return 32
    if n <= 256:
        return 256
    return _pow2(n, 2048)


def _pow2(n: int, floor: int) -> int:
    c = floor
    while c < n:
        c *= 2
    return c


# -- one transfer a payload ---------------------------------------------------
#
# A host-to-device call costs the host its quarter of a millisecond whether it
# carries a scalar or 8,192 rows, so a payload of many arrays (a plan's
# features, a flush's dirty rows) goes up as ONE buffer and is taken apart on
# the device: `pack` lays the named host arrays end to end, `unpack` (traced:
# static offsets, slices, reshapes, casts) hands back the same names with the
# same shapes and dtypes. Every value is a bool or an integer of at most 64
# signed bits, so one int64 buffer holds them all and the casts are exact.


def pack(named: Dict[str, np.ndarray]) -> Tuple[np.ndarray, tuple]:
    """(buffer, layout) of the named host arrays (or numpy scalars): one
    contiguous int64 buffer, and per array its (name, offset, shape, dtype
    name): hashable, the static half of `unpack`."""
    arrays = [np.asarray(a) for a in named.values()]
    layout, size = [], 0
    for name, a in zip(named, arrays):
        if a.dtype.kind not in "biu" or a.dtype == np.uint64:
            raise TypeError(f"{name}: {a.dtype} does not pack into int64")
        layout.append((name, size, a.shape, a.dtype.name))
        size += a.size
    buf = np.empty(size, np.int64)
    for (_name, off, _shape, _dtype), a in zip(layout, arrays):
        buf[off:off + a.size] = a.reshape(-1)
    return buf, tuple(layout)


def unpack(buf, layout: tuple) -> Dict[str, jnp.ndarray]:
    """The named arrays of a packed buffer (traced; `layout` static)."""
    out = {}
    for name, off, shape, dtype in layout:
        size = math.prod(shape)
        a = jax.lax.slice(buf, (off,), (off + size,)).reshape(shape)
        out[name] = a if dtype == "int64" else a.astype(dtype)
    return out


_unpacked = jax.jit(unpack, static_argnames=("layout",))


class TopoAxis:
    """One registered topology key (e.g. topology.kubernetes.io/zone):
    per-key value codebook + its row in the mirror's `topo` tensor.

    Value id 0 means "key absent"; a label present with an EMPTY value (legal
    in Kubernetes, and a real domain for topology spreading) is interned under
    a private token so it gets a distinct non-zero id."""

    __slots__ = ("key", "index", "values")

    _EMPTY_TOKEN = "\x00empty"

    def __init__(self, key: str, index: int):
        self.key = key
        self.index = index
        self.values = Codebook()

    def intern_value(self, val: str) -> int:
        return self.values.intern(val if val != "" else self._EMPTY_TOKEN)

    def lookup_value(self, val: str) -> int:
        return self.values.lookup(val if val != "" else self._EMPTY_TOKEN)


def _scatter_rows_impl(state: DeviceNodeState, packed, layout: tuple) -> DeviceNodeState:
    """Dirty-row scatter as ONE compiled executable: the packed payload
    (NodeStateMirror._dirty_payload: the row index and every column's rows
    in one buffer) taken apart and 13 per-array scatters, fused; a program
    of its own for the unpack, or a jit per array, would compile as many
    executables per tier."""
    rows = unpack(packed, layout)
    idx = rows["idx"]
    updated = [arr.at[idx].set(rows[name])
               for name, arr in zip(state._fields[:-1], state[:-1])]
    topo = state.topo.at[:, idx].set(rows["topo"])
    return DeviceNodeState(*updated, topo)


_scatter_rows = jax.jit(_scatter_rows_impl, static_argnames=("layout",))

# Mesh variant: one jitted scatter per (out_shardings pytree, donation) —
# parallel/mesh.py mesh_state_shardings caches the pytree, NamedSharding
# hashes, so the pytree itself is the cache key.
_SHARDED_SCATTER_CACHE: dict = {}


def _sharded_scatter(out_shardings, donate: bool = False):
    """_scatter_rows with explicit out_shardings: a mesh session's state is
    committed to the mirror's placement and the session kernel's jit keys
    on those input shardings — an unconstrained scatter would hand back
    GSPMD-chosen placements and retrace the kernel on next dispatch.

    ``donate=True`` additionally donates the OLD state buffers into the
    scatter (the session patch seam): the patched state replaces the old
    one in-place on device instead of allocating a full sharded copy per
    patch wave. Callers must rebind every live reference to the returned
    pytree — the mirror resident and the session's _SessionDelta.state are
    the only two, both rebound at the patch_rows call site."""
    key = (out_shardings, donate)
    fn = _SHARDED_SCATTER_CACHE.get(key)
    if fn is None:
        fn = jax.jit(_scatter_rows_impl, static_argnames=("layout",),
                     out_shardings=out_shardings,
                     donate_argnums=(0,) if donate else ())
        _SHARDED_SCATTER_CACHE[key] = fn
    return fn


class NodeStateMirror:
    """Host-side staging + device flush for DeviceNodeState."""

    def __init__(
        self,
        node_capacity: int = 64,
        taint_capacity: int = 4,
        label_capacity: int = 32,
        scalar_capacity: int = 4,
        axis_capacity: int = 4,
        scatter_threshold: float = 0.25,
    ):
        self.np_cap = node_capacity
        self.t_cap = taint_capacity
        self.l_cap = label_capacity
        self.s_cap = scalar_capacity
        self.k_cap = axis_capacity
        self.scatter_threshold = scatter_threshold

        self.keys = Codebook()        # taint keys (shared with tolerations)
        self.vals = Codebook()        # taint values
        self.names = Codebook()       # node names
        self.scalar_slots: Dict[str, int] = {}  # scalar resource -> slot >= BASE_RESOURCES
        self.axes: Dict[str, TopoAxis] = {}

        self._alloc_storage()
        self._row_names: List[str] = []
        self._row_gen: List[int] = []
        # the NodeInfo.node_generation each row's whole encode was of
        self._row_node: List[int] = []
        # the census of allocatable shapes: (milli cpu, memory, pods) ->
        # rows that hold a node of that shape, and each row's own; moved in
        # `_note_shape` alone, where a row is encoded or leaves
        self.shapes: Dict[tuple, int] = {}
        self._row_shape: List[Optional[tuple]] = []
        self._dirty: set = set()
        self._full_flush = True
        self._device: Optional[DeviceNodeState] = None
        # Shardings the resident device copy is COMMITTED to (None =
        # single-device). Under a mesh, flush() uploads host staging
        # straight to the sharded placement and dirty-row scatters ride a
        # jit pinned to these shardings — the sharded state IS the resident
        # (mesh-first), not a per-session device_put round-trip of a
        # single-device copy.
        self._shardings = None
        self.num_nodes = 0
        # host-to-device transfers by payload kind, counted in `send`; the
        # scheduler that owns the mirror puts its registry's series here
        self.transfers = Counter(
            "scheduler_host_to_device_transfers_total", "", ("payload",))
        # rows brought in line with their NodeInfo, by how (`encoded` whole,
        # `by_column` at a sync, `adopted` at a session's end); the owner's
        # registry likewise
        self.rows = Counter("scheduler_mirror_rows_total", "", ("how",))

    # -- one transfer a payload --------------------------------------------

    def send(self, payload: str, named: Dict[str, np.ndarray]):
        """(device buffer, layout) of the named host arrays, packed (`pack`)
        and sent in ONE transfer, counted under `payload`; `unpack` inside
        the program that reads it takes it apart."""
        buf, layout = pack(named)
        self.transfers.inc(payload)
        return jnp.asarray(buf), layout

    def upload(self, payload: str,
               named: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        """The named host arrays as device arrays of the same shapes and
        dtypes: one transfer (`send`) and one jitted unpack, a program a
        layout."""
        buf, layout = self.send(payload, named)
        return _unpacked(buf, layout=layout)

    # -- storage -----------------------------------------------------------

    @property
    def r_slots(self) -> int:
        return BASE_RESOURCES + self.s_cap

    def _alloc_storage(self) -> None:
        npc, t, l, r, k = self.np_cap, self.t_cap, self.l_cap, self.r_slots, self.k_cap
        self.h_alloc_r = np.zeros((npc, r), np.int64)
        self.h_alloc_pods = np.zeros(npc, np.int64)
        self.h_req_r = np.zeros((npc, r), np.int64)
        self.h_nonzero = np.zeros((npc, 2), np.int64)
        self.h_pod_count = np.zeros(npc, np.int32)
        self.h_taint_key = np.zeros((npc, t), np.int32)
        self.h_taint_val = np.zeros((npc, t), np.int32)
        self.h_taint_eff = np.zeros((npc, t), np.int32)
        self.h_unsched = np.zeros(npc, bool)
        self.h_valid = np.zeros(npc, bool)
        self.h_name_id = np.zeros(npc, np.int32)
        self.h_topo = np.zeros((k, npc), np.int32)

    def _grow(self, node_capacity=None, taint_capacity=None, label_capacity=None,
              scalar_capacity=None, axis_capacity=None) -> None:
        """Capacity tier change: reallocate staging and force a full re-encode
        + full flush (shape change ⇒ the kernel recompiles once per tier,
        SURVEY.md §7 'padding + capacity tiers and a recompile policy')."""
        self.np_cap = node_capacity or self.np_cap
        self.t_cap = taint_capacity or self.t_cap
        self.l_cap = label_capacity or self.l_cap
        self.s_cap = scalar_capacity or self.s_cap
        self.k_cap = axis_capacity or self.k_cap
        self._alloc_storage()
        self._row_names = []
        self._row_gen = []
        self._row_node = []
        self.shapes = {}
        self._row_shape = []
        self._full_flush = True
        self._device = None

    # -- axes / scalar slots ----------------------------------------------

    def ensure_axis(self, key: str) -> TopoAxis:
        ax = self.axes.get(key)
        if ax is not None:
            return ax
        if len(self.axes) >= self.k_cap:
            self._grow(axis_capacity=self.k_cap * 2)
            # staging was reset; existing axes refill on next sync
        ax = TopoAxis(key, len(self.axes))
        self.axes[key] = ax
        # Existing rows lack the new axis column: force re-encode on next sync.
        self._full_flush = True
        self._forget_rows()
        return ax

    def scalar_slot(self, resource_name: str) -> int:
        slot = self.scalar_slots.get(resource_name)
        if slot is not None:
            return slot
        if len(self.scalar_slots) >= self.s_cap:
            self._grow(scalar_capacity=self.s_cap * 2)
        slot = BASE_RESOURCES + len(self.scalar_slots)
        self.scalar_slots[resource_name] = slot
        return slot

    # -- row encoding ------------------------------------------------------

    def _resource_vec(self, r: "res.Resource", out: np.ndarray) -> None:
        out[:] = 0
        out[SLOT_CPU] = r.milli_cpu
        out[SLOT_MEMORY] = r.memory
        out[SLOT_EPHEMERAL] = r.ephemeral_storage
        for name, amount in r.scalar_resources.items():
            slot = self.scalar_slot(name)
            if slot >= out.shape[0]:
                # scalar_slot grew the capacity tier and reallocated staging;
                # `out` points into the orphaned old arrays — re-walk.
                raise _Regrown()
            out[slot] = amount

    def _note_shape(self, i: int, shape: Optional[tuple]) -> None:
        """Row `i` holds a node of allocatable `shape` (None: no node) from
        now on: the census moves only where that is news."""
        known = self._row_shape
        if i >= len(known):
            known.extend([None] * (i + 1 - len(known)))
        was = known[i]
        if was == shape:
            return
        known[i] = shape
        shapes = self.shapes
        if was is not None:
            left = shapes[was] - 1
            if left:
                shapes[was] = left
            else:
                del shapes[was]
        if shape is not None:
            shapes[shape] = shapes.get(shape, 0) + 1

    def _encode_row(self, i: int, ni: NodeInfo) -> None:
        node = ni.node
        alloc = ni.allocatable
        self._resource_vec(alloc, self.h_alloc_r[i])
        self.h_alloc_pods[i] = alloc.allowed_pod_number
        self._note_shape(i, None if node is None else (
            alloc.milli_cpu, alloc.memory, alloc.allowed_pod_number))
        self._resource_vec(ni.requested, self.h_req_r[i])
        self.h_nonzero[i, 0] = ni.non_zero_requested.milli_cpu
        self.h_nonzero[i, 1] = ni.non_zero_requested.memory
        self.h_pod_count[i] = len(ni.pods)
        taints = node.taints if node else []
        if len(taints) > self.t_cap:
            self._grow(taint_capacity=_pow2(len(taints), self.t_cap * 2))
            raise _Regrown()
        self.h_taint_key[i] = 0
        self.h_taint_val[i] = 0
        self.h_taint_eff[i] = 0
        for j, t in enumerate(taints):
            self.h_taint_key[i, j] = self.keys.intern(t.key)
            self.h_taint_val[i, j] = self.vals.intern(t.value)
            self.h_taint_eff[i, j] = EFFECT_IDS.get(t.effect, 0)
        self.h_unsched[i] = bool(node and node.unschedulable)
        self.h_valid[i] = node is not None
        self.h_name_id[i] = self.names.intern(node.name) if node else 0
        labels = node.labels if node else {}
        for ax in self.axes.values():
            val = labels.get(ax.key)
            self.h_topo[ax.index, i] = ax.intern_value(val) if val is not None else 0

    # -- sync --------------------------------------------------------------

    def sync(self, node_info_list: Sequence[NodeInfo]) -> None:
        """Re-encode rows whose generation or position changed (the device
        analogue of cache.go:236-262's generation walk)."""
        n = len(node_info_list)
        if n > self.np_cap:
            self._grow(node_capacity=_pow2(n, self.np_cap * 2))
        while True:
            try:
                self._sync_rows(node_info_list)
                break
            except _Regrown:
                continue  # capacity tier changed: staging reset, re-walk
        self.num_nodes = n

    def _sync_rows(self, node_info_list: Sequence[NodeInfo]) -> None:
        n = len(node_info_list)
        names, gens, nodes = self._row_names, self._row_gen, self._row_node
        known = len(names)
        moved_rows, moved = [], []  # the node is the one encoded: pods moved
        encoded = 0
        for i, ni in enumerate(node_info_list):
            if i < known and names[i] == ni.name:
                if gens[i] == ni.generation:
                    continue
                if nodes[i] == ni.node_generation:
                    moved_rows.append(i)
                    moved.append(ni)
                    continue
            self._encode_row(i, ni)
            if i < known:
                names[i] = ni.name
                gens[i] = ni.generation
                nodes[i] = ni.node_generation
            else:
                names.append(ni.name)
                gens.append(ni.generation)
                nodes.append(ni.node_generation)
            self._dirty.add(i)
            encoded += 1
        if moved_rows:
            self._write_pod_columns(moved_rows, moved)
            self._dirty.update(moved_rows)
            self.rows.inc("by_column", value=float(len(moved_rows)))
        if encoded:
            self.rows.inc("encoded", value=float(encoded))
        if len(names) > n:  # shrink: invalidate tail rows
            for i in range(n, len(names)):
                self.h_valid[i] = False
                self._note_shape(i, None)
                self._dirty.add(i)
            del names[n:]
            del gens[n:]
            del nodes[n:]

    def _write_pod_columns(self, rows: List[int],
                           infos: List[NodeInfo]) -> None:
        """Staging rows `rows` brought in line with their NodeInfos' PODS:
        the three columns that a pod's arrival or departure moves (`h_req_r`,
        `h_nonzero`, `h_pod_count`), each written in one indexed assignment
        with what `_encode_row` writes there, and the rows' generations
        taken. The rest of each row is its node's already: the caller has
        seen to that (`_row_node`). A requested vector with scalar resources
        takes `_resource_vec`, which may raise `_Regrown`."""
        if not rows:
            return
        idx = np.asarray(rows, np.intp)
        requested = [ni.requested for ni in infos]
        req = np.zeros((len(rows), self.r_slots), np.int64)
        req[:, SLOT_CPU] = [r.milli_cpu for r in requested]
        req[:, SLOT_MEMORY] = [r.memory for r in requested]
        req[:, SLOT_EPHEMERAL] = [r.ephemeral_storage for r in requested]
        self.h_req_r[idx] = req
        for i, r in zip(rows, requested):
            if r.scalar_resources:
                self._resource_vec(r, self.h_req_r[i])
        non_zero = [ni.non_zero_requested for ni in infos]
        self.h_nonzero[idx, 0] = [r.milli_cpu for r in non_zero]
        self.h_nonzero[idx, 1] = [r.memory for r in non_zero]
        self.h_pod_count[idx] = [len(ni.pods) for ni in infos]
        gens = self._row_gen
        for i, ni in zip(rows, infos):
            gens[i] = ni.generation

    def _forget_rows(self) -> None:
        """Every row is encoded whole at the next sync."""
        self._row_gen = [-1] * len(self._row_gen)
        self._row_node = [-1] * len(self._row_node)

    # -- flush -------------------------------------------------------------

    def _arrays(self):
        return (
            self.h_alloc_r, self.h_alloc_pods, self.h_req_r, self.h_nonzero,
            self.h_pod_count, self.h_taint_key, self.h_taint_val,
            self.h_taint_eff, self.h_unsched, self.h_valid, self.h_name_id,
        )

    def _staged_rows(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        """Staging rows `idx` of every column, by DeviceNodeState field."""
        named = {name: a[idx] for name, a in
                 zip(DeviceNodeState._fields, self._arrays())}
        named["topo"] = self.h_topo[:, idx]
        return named

    def _dirty_payload(self, dirty, width: int):
        """(packed, layout) scatter operand for the given staging rows: the
        row index (`idx`) and every column's rows in one buffer, ONE
        transfer (`send`), padded to `width` by repeating the last index
        (scatter-set with duplicate indices writes the same value): the
        jitted scatter compiles once per width, not once per dirty-count."""
        idx = np.full(width, dirty[-1], np.int32)
        idx[:len(dirty)] = dirty
        return self.send("flush", {"idx": idx, **self._staged_rows(idx)})

    def commit_shardings(self, out_shardings) -> None:
        """Commit the resident device copy to these NamedShardings (None =
        single-device). Called by build_plan before sync/flush; a changed
        commitment forces a full re-upload at the new placement. Identity
        comparison is exact: parallel/mesh.py mesh_state_shardings caches
        one pytree per mesh."""
        if out_shardings is not self._shardings:
            self._shardings = out_shardings
            self._device = None
            self._full_flush = True

    def _upload(self) -> DeviceNodeState:
        """Full host→device upload of staging, straight to the committed
        placement (one transfer per array; no intermediate single-device
        copy when sharded)."""
        if self._shardings is None:
            return DeviceNodeState(
                *[jnp.asarray(a) for a in self._arrays()],
                jnp.asarray(self.h_topo))
        return DeviceNodeState(
            *[jax.device_put(a, s) for a, s in
              zip(self._arrays() + (self.h_topo,), self._shardings)])

    def _resident_deleted(self) -> bool:
        """True when the resident arrays came from a session carry (adopt)
        that was later DONATED back to the kernel or a patch jit. adopt and
        the patch seam keep host staging in line, so a full upload from
        staging reproduces the exact device truth."""
        return self._device is not None and self._device.req_r.is_deleted()

    def _scatter_dirty(self, dirty) -> DeviceNodeState:
        """Scatter the given staging rows into the resident device state:
        one transfer (`_dirty_payload`) and one program, at the ONE width a
        flush has: the tier of the most rows it may scatter. How many rows
        a flush finds dirty is the workload's to decide (a wave that packs
        its pods onto a few nodes dirties 25 rows and the next one 250);
        were the width to follow that count, a width first met mid-run
        would compile where work is being measured."""
        packed, layout = self._dirty_payload(
            dirty, patch_tier(int(self.scatter_threshold * self.np_cap)))
        if self._shardings is not None:
            return _sharded_scatter(self._shardings)(
                self._device, packed, layout=layout)
        return _scatter_rows(self._device, packed, layout=layout)

    def flush(self) -> DeviceNodeState:
        """Upload pending changes; returns the device pytree (committed to
        `commit_shardings`' placement). Scatter when the dirty fraction is
        small, full upload otherwise."""
        if not self._full_flush and self._resident_deleted():
            self._full_flush = True
        if self._device is None or self._full_flush:
            self._device = self._upload()
        elif self._dirty:
            if len(self._dirty) > self.scatter_threshold * self.np_cap:
                self._device = self._upload()
            else:
                self._device = self._scatter_dirty(sorted(self._dirty))
        self._dirty.clear()
        self._full_flush = False
        return self._device


    def rows_state(self, idx: np.ndarray, n: int) -> DeviceNodeState:
        """The device state of a plan over a narrowed row set
        (ops/features.py KeptPlan.derive `rows`): staging rows `idx` (the
        first `n` the rows themselves, the rest padding) sent in one
        transfer (`upload`) as a state of their own, `valid` only in the
        first `n`. Staging is synced to the snapshot by then
        (TPUScheduler._sync_mirror), so this is what a flush holds in those
        rows; the resident copy is neither read nor touched."""
        named = self._staged_rows(idx)
        named["valid"][n:] = False  # a gather is a copy
        return DeviceNodeState(**self.upload("rows_state", named))

    def patch_rows(self, updates, sharded_state=None,
                   out_shardings=None,
                   donate: bool = True) -> Optional[DeviceNodeState]:
        """Event-delta row flush: re-encode the given (row, NodeInfo) pairs
        from the LIVE cache NodeInfos and scatter them into the resident
        device state WITHOUT a snapshot refresh — the journal-driven
        analogue of sync+flush for a session that stays on device. Returns
        the patched DeviceNodeState, or None when a row patch can't apply
        (no resident device copy / full upload pending, a capacity tier grew
        mid-encode, row out of range or name mismatch) — callers fall back
        to the full rebuild path, which recovers from every one of those.

        Mesh sessions pass `sharded_state` (their mesh-committed state) plus
        `out_shardings` (parallel/mesh.py mesh_state_shardings): the dirty
        rows scatter through a jit pinned to those shardings, so the
        patched pytree keeps the exact placement the session kernel's
        traces key on. When the session state IS the mirror's resident
        (the mesh-first steady state — build_plan commits the resident to
        the mesh placement), ONE donated scatter updates both: the old
        buffers are donated into the patch jit and every live reference
        (resident + _SessionDelta.state) is rebound to the result."""
        if self._device is None or self._full_flush:
            return None
        if self._resident_deleted():
            # The resident was donated back to a kernel/patch jit (session
            # resume chain); staging is authoritative — full upload path.
            self._full_flush = True
            return None
        # Validate EVERY row before encoding ANY: a late-row guard failure
        # after earlier rows hit staging would leave those rows encoded with
        # current generations but never scattered — the fallback's sync
        # would then skip them and the device copy would stay stale forever.
        # (_Regrown mid-encode is safe: _grow resets staging + generations
        # and pends a full upload.)
        for row, ni in updates:
            if (row >= self.np_cap or row >= len(self._row_names)
                    or ni.name != self._row_names[row]):
                return None
        try:
            for row, ni in updates:
                self._encode_row(row, ni)
                self._row_gen[row] = ni.generation
                self._row_node[row] = ni.node_generation
        except _Regrown:
            return None  # staging reset: next flush rebuilds everything
        self.rows.inc("encoded", value=float(len(updates)))
        dirty = sorted({row for row, _ in updates})
        packed, layout = self._dirty_payload(dirty, patch_tier(len(dirty)))
        if sharded_state is not None and sharded_state is self._device:
            # Mesh-first steady state: session state == resident. One
            # pinned scatter patches it — DONATED (in-place buffer reuse)
            # unless the caller's dispatch pipeline still holds in-flight
            # reads of the old state (`donate=False`, the busy-patch seam).
            self._device = _sharded_scatter(out_shardings, donate=donate)(
                sharded_state, packed, layout=layout)
            self._dirty.difference_update(dirty)
            return self._device
        self._device = (_sharded_scatter(self._shardings)(
            self._device, packed, layout=layout)
            if self._shardings is not None
            else _scatter_rows(self._device, packed, layout=layout))
        self._dirty.difference_update(dirty)
        if sharded_state is not None:
            return _sharded_scatter(out_shardings)(
                sharded_state, packed, layout=layout)
        return self._device

    def invalidate(self) -> None:
        """Force a full staging re-encode + full upload on the next
        sync/flush (used when a device session diverged from the host: the
        carry can no longer be trusted as the device truth)."""
        self._full_flush = True
        self._forget_rows()

    # -- carry adoption (device-resident steady state) ---------------------

    def adopt(
        self,
        nodes: Mapping[str, NodeInfo],
        rows: Sequence[int],
        req_r: jnp.ndarray,
        nonzero: jnp.ndarray,
        pod_count: jnp.ndarray,
        dirty_rows: Sequence[int] = (),
    ) -> int:
        """After a device batch: the kernel's final carry already holds the
        updated per-node aggregates, so install those arrays directly and
        bring the host staging + generations in line WITHOUT marking rows
        dirty — the next flush() then uploads nothing. Rows whose host commit
        failed (carry diverged from cache) go through the normal dirty path.

        `nodes`: node name -> the NodeInfo that holds the truth of its pods,
        the LIVE cache's (`Cache.nodes`) as `patch_rows` reads it, with no
        snapshot refresh: a clone made later carries the generation taken
        here, so the next sync skips the row unless something moved it. Only
        the pod columns are written (`_write_pod_columns`): taints, labels
        and topology cannot have moved without a node event, which a session
        patches whole (`patch_rows`); a row whose node is not the one it
        encoded is left to the next sync.

        This is the device-resident analogue of cache.go's incremental
        UpdateSnapshot: in steady state the only node changes are the batch's
        own placements, which the device already has. Returns the rows whose
        staging it brought in line."""
        if self._device is None or self._full_flush:
            return 0  # a full upload from (authoritative) staging is pending
        names, known = self._row_names, self._row_node
        landed, infos = [], []
        for i in set(rows).intersection(range(len(names))):
            ni = nodes.get(names[i])
            if ni is not None and ni.node_generation == known[i]:
                landed.append(i)
                infos.append(ni)
        try:
            self._write_pod_columns(landed, infos)
        except _Regrown:
            return 0  # staging reset; full flush will rebuild everything
        self.rows.inc("adopted", value=float(len(landed)))
        self._device = self._device._replace(
            req_r=req_r, nonzero=nonzero, pod_count=pod_count)
        self._dirty.update(dirty_rows)
        return len(landed)


class _Regrown(Exception):
    """Internal: a capacity tier changed mid-encode; re-walk the snapshot."""
