"""Mesh-sharded train/predict steps via shard_map.

The distributed trainer, TPU-first: one jitted SPMD program per step over a
('data', 'row') mesh replaces the reference's ps/worker cluster
(`renyi533/fast_tffm` :: dist trainer: between-graph replication,
Supervisor, asynchronous Hogwild scatter-adds over gRPC).  Per step:

  gather:   ids all_gathered + rows psum_scattered over ROW_AXIS
            (parallel/embedding) — each parameter row crosses ICI once
  compute:  fused FM scorer + loss; the batch is split over BOTH mesh
            axes, so every chip scores a distinct micro-batch (no
            redundant compute on the row axis)
  combine:  all_gather over both axes of deduped sparse row grads +
            psum of dense grads — deterministic sync replacing Hogwild
            races
  update:   each row shard applies sparse Adagrad to its own rows

Semantics match trainer.py's single-device step exactly (tested on the
virtual 8-device CPU mesh), which is the determinism the reference gave up.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fast_tffm_tpu.models.base import Batch
from fast_tffm_tpu.optim import AdagradState, dense_adagrad_update
from fast_tffm_tpu.parallel.embedding import (
    shard_lookup_form,
    sharded_gather,
    sharded_sparse_adagrad_update,
)
from fast_tffm_tpu.parallel.exchange import exchange_scope
from fast_tffm_tpu.parallel.mesh import (
    DATA_AXIS,
    ROW_AXIS,
    pad_vocab,
    replicated,
    table_sharding,
)
from fast_tffm_tpu.trainer import TrainState, init_dense_state, init_table_state

__all__ = [
    "init_sharded_state",
    "packed_shard_meta",
    "pack_sharded_on_device",
    "unpack_sharded_to_logical",
    "unpack_sharded_on_device",
    "make_sharded_train_step",
    "shard_tail_ids",
    "shard_lookup_ids",
    "make_sharded_predict_step",
    "make_global_batch",
    "make_global_superbatch",
    "make_replicator",
    "local_mesh_devices",
    "WireGlobalConverter",
]


def make_global_batch(mesh: Mesh, parsed, w, *, with_fields: bool = True) -> Batch:
    """Assemble a GLOBAL batch from this process's local input shard.

    Multi-host input sharding: each process parses only rows
    [p·B_local, (p+1)·B_local) of every global batch (pipeline
    ``shard_block`` = B_local), then this stitches the per-process chunks
    into one global jax.Array per field — each process contributes exactly
    its addressable devices' slice, no cross-host data movement.  Works
    because make_mesh lays devices process-contiguously in (data, row)
    row-major order, so a process's slice of the leading batch dim is
    contiguous.
    """
    import numpy as np

    vec = NamedSharding(mesh, P(_BOTH))
    mat = NamedSharding(mesh, P(_BOTH, None))
    mk = jax.make_array_from_process_local_data
    fields = (
        np.ascontiguousarray(parsed.fields)
        if with_fields
        else np.zeros((parsed.fields.shape[0], 0), np.int32)
    )
    return Batch(
        labels=mk(vec, np.ascontiguousarray(parsed.labels)),
        ids=mk(mat, np.ascontiguousarray(parsed.ids.astype(np.int32, copy=False))),
        vals=mk(mat, np.ascontiguousarray(parsed.vals)),
        fields=mk(mat, fields),
        weights=mk(vec, np.ascontiguousarray(w)),
    )


def make_global_superbatch(mesh: Mesh, parsed_seq, w_seq, *, with_fields: bool = True) -> Batch:
    """make_global_batch for K stacked micro-batches: each process stacks
    ITS local chunks of K consecutive global batches into [K, B_local, ...]
    host arrays, then contributes them as its slice of the [K, B, ...]
    global superbatch (batch dim 1 sharded over both mesh axes, micro-step
    dim 0 unsharded — the scanned SPMD step slices dim 0 on device).  One
    stitch per K steps is the multi-host analog of the local path's one
    H2D per K steps."""
    import numpy as np

    vec = NamedSharding(mesh, P(None, _BOTH))
    mat = NamedSharding(mesh, P(None, _BOTH, None))
    mk = jax.make_array_from_process_local_data
    b_local = parsed_seq[0].labels.shape[0]
    fields = (
        np.stack([np.asarray(p.fields) for p in parsed_seq])
        if with_fields
        else np.zeros((len(parsed_seq), b_local, 0), np.int32)
    )
    return Batch(
        labels=mk(vec, np.stack([np.asarray(p.labels) for p in parsed_seq])),
        ids=mk(
            mat,
            np.stack(
                [p.ids.astype(np.int32, copy=False) for p in parsed_seq]
            ),
        ),
        vals=mk(mat, np.stack([np.asarray(p.vals) for p in parsed_seq])),
        fields=mk(mat, fields),
        weights=mk(vec, np.stack([np.asarray(w) for w in w_seq])),
    )


def make_replicator(mesh: Mesh):
    """Jitted identity gathering a (sharded) pytree to a fully-replicated
    layout — every process ends up holding the complete arrays.  This is
    what makes the npz single-writer checkpoint protocol possible on a
    multi-host pod: the sharded state replicates (one collective), then
    process 0 alone streams it to disk.  The memory bill is the full
    logical table per host, so it is the MODEST-table path — orbax stays
    the answer where the table exceeds one host (DESIGN §8)."""
    rep = NamedSharding(mesh, P())
    # ONE jitted identity per tree structure: a fresh jit per call would
    # recompile at every save boundary (a steady-state recompile the
    # telemetry sentinel rightly flags).
    cache: dict = {}

    def _replicate(tree):
        leaves, treedef = jax.tree.flatten(tree)
        fn = cache.get(treedef)
        if fn is None:
            fn = jax.jit(lambda *ls: ls, out_shardings=rep)
            cache[treedef] = fn
        return jax.tree.unflatten(treedef, fn(*leaves))

    return _replicate


def local_mesh_devices(mesh: Mesh) -> list:
    """This process's devices in GLOBAL mesh order, verified contiguous.

    The batch dim shards over (data, row) in mesh-flat order, so process
    p's addressable slice of a global batch is rows
    [p·B/P, (p+1)·B/P) exactly when its devices form one contiguous run
    of ``mesh.devices.flat`` — the layout make_mesh produces from jax's
    process-major device order, and the same assumption make_global_batch
    documents.  Raises loudly on exotic layouts rather than silently
    scrambling rows."""
    flat = list(mesh.devices.flat)
    pid = jax.process_index()
    idxs = [i for i, d in enumerate(flat) if d.process_index == pid]
    if not idxs or idxs != list(range(idxs[0], idxs[0] + len(idxs))):
        raise ValueError(
            "this process's devices are not contiguous in the mesh — "
            "host-local wire staging needs the process-contiguous layout "
            "make_mesh produces (use wire_format = arrays here)"
        )
    return [flat[i] for i in idxs]


class WireGlobalConverter:
    """Host-local packed-wire staging for the multi-host streamed path.

    Each host packs ITS local rows of every global (super)batch into one
    coalesced wire buffer, unpacks it on its own devices (PR 3's packed
    wire — per-host by construction), then donates the per-device shards
    straight into a global jax.Array (``make_array_from_single_device_
    arrays``) — the multi-host analog of make_global_batch with ~2-3×
    fewer H2D bytes per host and zero cross-host data movement.

    ``to_batch``-compatible (wraps data/wire.WireConverter, so the wire
    byte accounting feeds kind=input records unchanged).
    """

    def __init__(self, mesh: Mesh, spec, verify_ids: bool = True):
        import numpy as np

        from fast_tffm_tpu.data.wire import WireConverter

        self._mesh = mesh
        self._wire = WireConverter(spec, verify_ids)
        self._local_devs = local_mesh_devices(mesh)
        self._lmesh = Mesh(
            np.asarray(self._local_devs).reshape(len(self._local_devs)), ("b",)
        )
        self._nproc = jax.process_count()

    # WireConverter duck-type (training's InputStats reads these).
    @property
    def last_nbytes(self):
        return self._wire.last_nbytes

    @property
    def wire_bytes(self):
        return self._wire.wire_bytes

    @property
    def calls(self):
        return self._wire.calls

    def _globalize_leaf(self, x, batch_axis: int):
        lspec = [None] * x.ndim
        lspec[batch_axis] = "b"
        gspec = [None] * x.ndim
        gspec[batch_axis] = (DATA_AXIS, ROW_AXIS)
        lx = jax.device_put(x, NamedSharding(self._lmesh, P(*lspec)))
        by_dev = {s.device: s.data for s in lx.addressable_shards}
        gshape = list(x.shape)
        gshape[batch_axis] *= self._nproc
        return jax.make_array_from_single_device_arrays(
            tuple(gshape),
            NamedSharding(self._mesh, P(*gspec)),
            [by_dev[d] for d in self._local_devs],
        )

    def __call__(self, parsed, w):
        local = self._wire(parsed, w)  # local-device Batch ([B] or [K, B])
        batch_axis = 1 if isinstance(parsed, list) else 0
        return jax.tree.map(
            lambda x: self._globalize_leaf(x, batch_axis), local
        )


def _state_specs():
    return TrainState(
        table=P(ROW_AXIS, None),
        table_opt=AdagradState(P(ROW_AXIS, None)),
        dense=None,  # filled per-model (replicated)
        dense_opt=None,
        step=P(),
    )


_BOTH = (DATA_AXIS, ROW_AXIS)


def _batch_specs() -> Batch:
    # The batch splits over every chip (both mesh axes): compute is fully
    # data-parallel; only the table is row-sharded.
    return Batch(
        labels=P(_BOTH),
        ids=P(_BOTH, None),
        vals=P(_BOTH, None),
        fields=P(_BOTH, None),
        weights=P(_BOTH),
    )


def _pad_model_vocab(model, mesh: Mesh, pack: int = 1):
    """Round the table up so ROW_AXIS shards are equal (padded rows inert).

    ``pack`` > 1 additionally rounds each shard to a multiple of the
    lane-packing factor rows_per_tile(D), so per-shard packing equals a
    row-block of the globally packed table (checkpoints stay layout-free
    and the packed shard's physical rows divide exactly)."""
    import dataclasses

    rows = mesh.shape[ROW_AXIS] * pack
    padded = pad_vocab(model.vocabulary_size, rows)
    if padded == model.vocabulary_size:
        return model
    return dataclasses.replace(model, vocabulary_size=padded)


def _sharded_table_init(model, mesh: Mesh, init_accumulator_value: float, accumulator: str):
    """The jitted ``table key -> (table, AdagradState)`` of
    ``init_sharded_state``: ``trainer.init_table_state`` with both arrays born
    row-sharded (``model`` already padded to equal shards).  Its own function
    so that a test or a rehearsal can compile it and read what a device is
    asked to hold."""
    ts = table_sharding(mesh)
    return jax.jit(
        lambda key: init_table_state(model, key, init_accumulator_value, accumulator),
        out_shardings=(ts, AdagradState(ts)),
    )


def init_sharded_state(
    model, mesh: Mesh, key, init_accumulator_value: float = 0.1,
    accumulator: str = "element", table_layout: str = "rows",
):
    """init_state with row-sharded table and replicated dense params, each
    shard DRAWN ON THE DEVICE THAT HOLDS IT: table and accumulator come out
    of one jitted construction whose ``out_shardings`` are theirs, so that no
    device ever holds a whole [V, D] array (at 2^27 x 17 the table is 8.5
    GiB and the accumulator as much again: no one chip's).  The values are
    those of ``trainer.init_state`` on the padded model element for element
    (the partitionable threefry draws a shard of the one-device draw; the
    small dense leaves are drawn as there, outside the jit, and replicated).

    ``table_layout='packed'`` stores the shards lane-packed
    ([VP_shard, 128] each — ops/packed_table.py), packed per shard on its
    own device (``pack_sharded_on_device``); the shard-aligned vocab
    padding makes the global packed array exactly the concatenation of the
    per-shard packings.  ``accumulator='row'`` with the packed layout
    packs the [V, 1] accumulator as [VP_shard, P] scalar slots;
    ``accumulator='fused'`` stores the row accumulator inside the table's
    own tile rows ([VPf_shard, 128], stride D+1 — the 2-random-op RMW)."""
    packed = table_layout == "packed"
    fused = accumulator == "fused"
    if packed:
        padded, _, _ = packed_shard_meta(model, mesh, fused=fused)
    else:
        padded = _pad_model_vocab(model, mesh)
    k_table, k_dense = jax.random.split(key)  # init_state's two keys
    table, table_opt = _sharded_table_init(
        padded, mesh, init_accumulator_value, accumulator
    )(k_table)
    dense, dense_opt = jax.device_put(
        init_dense_state(padded, k_dense, init_accumulator_value), replicated(mesh)
    )
    state = TrainState(
        table=table, table_opt=table_opt, dense=dense, dense_opt=dense_opt,
        step=jax.device_put(jnp.zeros((), jnp.int32), replicated(mesh)),
    )
    if packed:
        state = pack_sharded_on_device(
            state, model, mesh, init_accumulator_value, fused=fused
        )
    return state


def packed_shard_meta(model, mesh: Mesh, fused: bool = False):
    """(padded_model, shard_logical_rows, rows_per_tile) for the packed
    sharded layout — the one place its padding arithmetic lives.
    ``fused`` switches to the fused tile-row pack factor (stride D+1)."""
    from fast_tffm_tpu.ops.packed_table import fused_rows_per_tile, rows_per_tile

    p = fused_rows_per_tile(model.row_dim) if fused else rows_per_tile(model.row_dim)
    padded = _pad_model_vocab(model, mesh, pack=p)
    return padded, padded.vocabulary_size // mesh.shape[ROW_AXIS], p


def unpack_sharded_to_logical(state: TrainState, model, mesh: Mesh) -> TrainState:
    """Lane-packed row-sharded state -> host LOGICAL [V, D] arrays
    (per-shard unpack; checkpoints always hold the logical layout).

    The unpack itself runs in PURE NUMPY on the fetched host copy — the
    whole point of this path (the single-process save route, ADVICE r4)
    is to avoid device-memory transients next to the live packed state,
    so nothing here may round-trip through jnp.  The FUSED layout is
    recognized by its empty-accumulator sentinel (pack_state) and
    unpacks to the logical ([V, D] table, [V, 1] accumulator) pair."""
    import numpy as np

    from fast_tffm_tpu.ops.packed_table import LANES, rows_per_tile

    R = mesh.shape[ROW_AXIS]
    d = model.row_dim
    fused = state.table_opt.accum.size == 0
    _, shard_logical, p = packed_shard_meta(model, mesh, fused=fused)

    def shards(arr):
        a = np.asarray(arr)
        per = a.shape[0] // R
        return [a[r * per : (r + 1) * per] for r in range(R)]

    if fused:
        d1 = d + 1
        tabs, accs = [], []
        for a in shards(state.table):  # numpy twin of unpack_fused
            flat = a[:, : p * d1].reshape(a.shape[0] * p, d1)[:shard_logical]
            tabs.append(flat[:, :d])
            accs.append(flat[:, d:])
        return state._replace(
            table=np.concatenate(tabs),
            table_opt=state.table_opt._replace(accum=np.concatenate(accs)),
        )

    def unp_table(a):  # numpy twin of ops.packed_table.unpack_table
        return a[:, : p * d].reshape(a.shape[0] * p, d)[:shard_logical]

    def unp_accum(a):  # numpy twin of unpack_accum_any (same trailing-dim sniff)
        if a.shape[-1] == LANES and rows_per_tile(d) != LANES:
            return unp_table(a)
        q = a.shape[-1]
        return a.reshape(a.shape[0] * q, 1)[:shard_logical]

    return state._replace(
        table=np.concatenate([unp_table(a) for a in shards(state.table)]),
        table_opt=state.table_opt._replace(
            accum=np.concatenate(
                [unp_accum(a) for a in shards(state.table_opt.accum)]
            )
        ),
    )


from functools import lru_cache


@lru_cache(maxsize=32)
def _packed_io_fns(
    mesh: Mesh, shard_logical: int, d: int, init_value: float,
    fused: bool = False,
):
    """Jitted per-shard pack/unpack transforms for one (mesh, layout)
    combination, built ONCE and cached: dist_saveable calls the unpack at
    every checkpoint save, and rebuilding shard_map around fresh lambdas
    each time would retrace and recompile per save.  Mesh is hashable;
    the cache key pins everything the traces close over."""
    from fast_tffm_tpu.ops.packed_table import (
        pack_accum_any,
        pack_fused,
        pack_table,
        unpack_accum_any,
        unpack_fused,
        unpack_table,
    )

    spec = P(ROW_AXIS, None)

    def mapped(fn, n_in=1, n_out=1):
        return jax.jit(
            shard_map(
                fn, mesh=mesh,
                in_specs=spec if n_in == 1 else (spec,) * n_in,
                out_specs=spec if n_out == 1 else (spec,) * n_out,
                check_vma=False,
            )
        )

    if fused:
        return {
            "unpack_fused": mapped(
                lambda s: unpack_fused(s, shard_logical, d), n_out=2
            ),
            "pack_fused": mapped(
                lambda t, a: pack_fused(t, a, init_value), n_in=2
            ),
        }
    return {
        "unpack_table": mapped(lambda s: unpack_table(s, shard_logical, d)),
        "unpack_accum": mapped(lambda s: unpack_accum_any(s, shard_logical, d)),
        "pack_table": mapped(pack_table),
        "pack_accum": mapped(lambda s: pack_accum_any(s, d, init_value)),
    }


def unpack_sharded_on_device(state: TrainState, model, mesh: Mesh) -> TrainState:
    """Lane-packed row-sharded state -> LOGICAL row-sharded state, each
    shard unpacked ON ITS OWN DEVICES under shard_map — no host gather,
    so it works on multi-host meshes where ``unpack_sharded_to_logical``
    cannot (its np.asarray would touch non-addressable shards).  The
    result's logical table is [Vpad, D] row-sharded with the same mesh
    placement, ready for the sharded (orbax) checkpoint writer: every
    host saves only its own unpacked shards, which is exactly the
    per-process logical<->packed checkpoint assembly multi-host packed
    runs need.  Shard-aligned padding (packed_shard_meta) makes the
    concatenation of per-shard unpacks equal the global unpack.  A FUSED
    state (empty-accumulator sentinel) unpacks through unpack_fused."""
    fused = state.table_opt.accum.size == 0
    _, shard_logical, _ = packed_shard_meta(model, mesh, fused=fused)
    fns = _packed_io_fns(mesh, shard_logical, model.row_dim, 0.0, fused=fused)
    if fused:
        t, a = fns["unpack_fused"](state.table)
        return state._replace(
            table=t, table_opt=state.table_opt._replace(accum=a)
        )
    return state._replace(
        table=fns["unpack_table"](state.table),
        table_opt=state.table_opt._replace(
            accum=fns["unpack_accum"](state.table_opt.accum)
        ),
    )


def pack_sharded_on_device(
    logical: TrainState, model, mesh: Mesh, init_accumulator_value: float = 0.1,
    fused: bool = False,
) -> TrainState:
    """Inverse of ``unpack_sharded_on_device``: a LOGICAL row-sharded
    state (e.g. a checkpoint restored in place onto the packed-aligned
    padding — see ``packed_shard_meta``) -> lane-packed row-sharded
    state, packed per shard on its own devices.  Multi-host safe for the
    same reason: no host materialization of the global table.  ``fused``
    packs into the fused tile-row layout (the caller knows the target
    layout from its config; the logical input looks identical either way)."""
    _, shard_logical, _ = packed_shard_meta(model, mesh, fused=fused)
    if logical.table.shape[0] != shard_logical * mesh.shape[ROW_AXIS]:
        raise ValueError(
            f"pack_sharded_on_device needs the packed-aligned padded vocab "
            f"({shard_logical * mesh.shape[ROW_AXIS]} rows), got "
            f"{logical.table.shape[0]} — restore onto a template built from "
            "packed_shard_meta's padded model"
        )
    fns = _packed_io_fns(
        mesh, shard_logical, model.row_dim, float(init_accumulator_value),
        fused=fused,
    )
    if fused:
        return logical._replace(
            table=fns["pack_fused"](logical.table, logical.table_opt.accum),
            table_opt=logical.table_opt._replace(
                accum=jnp.zeros((0, 1), logical.table.dtype)
            ),
        )
    return logical._replace(
        table=fns["pack_table"](logical.table),
        table_opt=logical.table_opt._replace(
            accum=fns["pack_accum"](logical.table_opt.accum)
        ),
    )


def _make_gather(
    mesh: Mesh, local_ids_shape, lookup: str, capacity_factor: float,
    packed_meta=None, fused: bool = False,
):
    """Pick the lookup collective: all-gather (default) or all-to-all routing.

    ``local_ids_shape`` is the PER-CHIP [B_local, N] shape (this is called
    from inside the shard_map body at trace time).  ``packed_meta`` is
    ``(d_row, shard_logical_rows)`` when the shards are lane-packed
    (``fused``: the fused tile-row layout) —
    routing is identical, only the local serve reads the packed layout.
    Returns ``(gather_fn, capacity, can_overflow)`` — capacity is None on
    the all-gather path and is THE single sizing both all-to-all
    directions share (the routed update must use the same value);
    ``can_overflow`` is False when the capacity caps at M = ids-per-chip
    (every id fits one bucket, so overflow is statically impossible and
    callers may skip the per-step routing_overflow check and its lax.cond
    dual-compile)."""
    if lookup == "allgather":
        if packed_meta is not None:
            from fast_tffm_tpu.parallel.embedding import (
                fused_sharded_gather,
                packed_sharded_gather,
            )

            d_row, slr = packed_meta
            g = fused_sharded_gather if fused else packed_sharded_gather
            return (lambda table, ids: g(table, ids, d_row, slr)), None, False
        return (lambda table, ids: sharded_gather(table, ids)[0]), None, False
    if lookup != "alltoall":
        raise ValueError(f"unknown lookup {lookup!r} (allgather | alltoall)")
    from fast_tffm_tpu.parallel.alltoall import capacity_for, routed_gather

    b_local, n = local_ids_shape
    m = b_local * n
    cap = capacity_for(m, mesh.shape[ROW_AXIS], capacity_factor)
    if packed_meta is not None:
        d_row, slr = packed_meta
        return (
            lambda table, ids: routed_gather(
                table, ids, cap, d=d_row, shard_logical_rows=slr, fused=fused
            )
        ), cap, cap < m
    return (lambda table, ids: routed_gather(table, ids, cap)), cap, cap < m


def shard_lookup_ids(mesh: Mesh, ids_per_chip: int, capacity_factor: float) -> int:
    """How many of the slots its row peers hand it a row shard's lookup
    (``embedding.sharded_gather``) may own before it reads the whole list,
    where a chip's micro-batch holds ``ids_per_chip`` ids: from each row peer
    the ``capacity`` slots one row peer may send another
    (``alltoall.capacity_for``), the bound ``shard_tail_ids`` takes from every
    chip.  The same config key, ``lookup_capacity_factor``, and never more
    than the ``row * ids_per_chip`` slots the lookup is handed."""
    from fast_tffm_tpu.parallel.alltoall import capacity_for

    return mesh.shape[ROW_AXIS] * capacity_for(ids_per_chip, mesh.shape[ROW_AXIS], capacity_factor)


def shard_tail_ids(mesh: Mesh, ids_per_chip: int, capacity_factor: float) -> int:
    """How many (id, gradient) slots the rows layout's shard tail
    (``embedding.apply_shard_adagrad``) takes a step, where a chip's
    micro-batch holds ``ids_per_chip`` ids: from every chip the ``capacity``
    slots one row peer may send another (``alltoall.capacity_for``: the
    uniform share times ``lookup_capacity_factor``, and the binomial tail's
    room).  Under the routed update those ARE the slots a shard receives
    (``_make_gather``'s sizing); under the all-gather update, which hands
    every shard every chip's ``ids_per_chip``, they are the static bound the
    tail keeps of the sorted list, with the whole list as the counted
    fallback.  The same number under both lookups, and never more than the
    slots handed in (``capacity_for`` caps at ``ids_per_chip``: one row
    shard, or a factor of ``row`` and up, bound nothing).  It is the ``m``
    ``optim.rows_tail_form`` sees at the shard, for whoever says the tail's
    form aloud (training.dist_train)."""
    return mesh.shape[DATA_AXIS] * shard_lookup_ids(mesh, ids_per_chip, capacity_factor)


def make_sharded_train_step(
    model, learning_rate: float, mesh: Mesh, *, lookup: str = "allgather",
    capacity_factor: float = 2.0, overflow_mode: str = "abort",
    table_layout: str = "rows", packed_update: str = "auto",
    accumulator: str = "element", compact_cap: int = 0,
    steps_per_call: int = 1, adagrad_decay: float = 1.0,
    count_full_tails: bool = False, count_full_lookups: bool = False,
):
    """Returns jitted SPMD ``step(state, batch) -> (state, global mean loss)``.

    ``steps_per_call`` > 1 returns the scan-fused form instead:
    ``step(state, superbatch) -> (state, losses [K])`` where every
    ``superbatch`` field carries a leading micro-step dim ([K, B], ...;
    make_global_superbatch builds it) and ``lax.scan`` wraps the SAME
    shard_map body — one dispatch launches K SPMD steps, so pod runs
    amortize per-step dispatch exactly like the local paths.  K is read
    from the input shape (the epoch-tail remainder superbatch compiles its
    own executable).  Under ``fallback`` the return is
    ``(state, losses [K], overflow_steps)`` with the per-step flags SUMMED
    into one replicated int32 (drivers only count them; every counter the
    step returns is summed so).  Per-step losses
    and the final state are bit-identical to K sequential K=1 steps
    (test-pinned).

    Batch arrays must have leading dim divisible by the total device count
    (the batch splits over both mesh axes).  ``lookup`` picks the embedding
    collective for BOTH directions: ``allgather`` (default; robust to any
    id skew) or ``alltoall`` (SparseCore-style routing for the lookup AND
    the gradient update — ~R× fewer ICI bytes each way; needs
    near-uniform ids, see parallel/alltoall.py).

    ``overflow_mode`` (alltoall only) decides what a capacity overflow
    does.  ``abort``: affected rows NaN-poison and the loss goes NaN (the
    caller stops before checkpointing).  ``fallback``: the whole step
    reruns through the allgather collectives under ``lax.cond`` — the
    overflow flag is psum'd, so every chip takes the same branch, the
    step's result is exactly the allgather step's, and training continues
    deterministically; the step then returns ``(state, loss, overflowed)``
    with a replicated int32 flag so the driver can count skew events.

    The rows layout's shard tail (``embedding.apply_shard_adagrad``) keeps
    the first ``shard_tail_ids`` slots of its sorted list under BOTH lookups:
    ``capacity_factor`` is how far over its uniform share a shard's traffic
    may run before the step takes the slower exact path, whichever exchange
    fed it (the routed lookup reruns through the all-gather collectives, the
    all-gather update's tail takes the whole list; neither drops anything).
    ``count_full_tails`` appends to the step's return a replicated int32:
    the row shards that took the whole list this step (0 where the shapes
    make that impossible; summed over the K steps of a fused call), for the
    driver to count (``shard_tail_full_steps``).

    The rows layout's lookup (``embedding.sharded_gather``) is bounded the
    same way where ``embedding.shard_lookup_form`` says ``sweep``: a shard
    reads the first ``shard_lookup_ids`` slots of the sorted list its row
    peers hand it, and the whole list where it owns more.
    ``count_full_lookups`` appends after the tail's count another replicated
    int32: the chips whose lookup read the whole list this step (a chip's
    lookup serves its own row peers, so data replicas count apart; 0 where
    the form is the masked row gather), ``shard_lookup_full_steps``.

    Note the defaults differ by layer on purpose: the CONFIG default
    (``lookup_overflow = fallback``, what the train/predict drivers pass)
    is the operationally-kind choice, while this bare function defaults to
    ``abort`` so direct library callers keep the uniform
    ``(state, loss)`` return signature unless they opt into the flagged
    3-tuple.
    """
    packed = table_layout == "packed"
    fused = accumulator == "fused"
    if fused and not packed:
        raise ValueError("accumulator='fused' requires table_layout='packed'")
    if packed:
        model, shard_logical_rows, _ = packed_shard_meta(model, mesh, fused=fused)
    else:
        model = _pad_model_vocab(model, mesh)
        shard_logical_rows = model.vocabulary_size // mesh.shape[ROW_AXIS]
    num_rows_global = model.vocabulary_size
    d_row = model.row_dim
    if overflow_mode not in ("abort", "fallback"):
        raise ValueError(f"unknown overflow_mode {overflow_mode!r} (abort | fallback)")
    fallback = lookup == "alltoall" and overflow_mode == "fallback"
    packed_meta = (d_row, shard_logical_rows) if packed else None
    # [Online] adagrad_decay: touched-row accumulator decay, rows layout
    # only (config.validate enforces the restriction — the packed tile-row
    # RMWs rely on the zero-grad identity a lane-blind decay would break).
    # γ=1.0 is a trace-time no-op, so the default program is unchanged.
    decay = float(adagrad_decay)
    if decay != 1.0 and (packed or fused):
        raise ValueError(
            "adagrad_decay != 1.0 requires table_layout = rows (the packed "
            "tile-row updates rely on the zero-grad accumulator identity)"
        )

    def shard_body(table, accum, dense, dense_acc, batch: Batch):
        # Built per trace: the capacity is sized from THIS trace's batch
        # shape (a cached closure would pin a stale capacity across jit
        # retraces with bigger batches and spuriously overflow).
        gather, cap, can_overflow = _make_gather(
            mesh, batch.ids.shape, lookup, capacity_factor, packed_meta,
            fused=fused,
        )

        def loss_fn(rows, dense):
            scores = model.score(rows, dense, batch)
            with jax.named_scope("fm.loss"):
                per = (
                    jnp.maximum(scores, 0.0)
                    - scores * batch.labels
                    + jnp.log1p(jnp.exp(-jnp.abs(scores)))
                )
                weight = jnp.sum(batch.weights)
                with exchange_scope():
                    weight = lax.psum(weight, _BOTH)
                denom = jnp.maximum(weight, 1.0)
                data_loss = jnp.sum(per * batch.weights) / denom
                reg = model.regularization(rows, dense, batch)
                return data_loss + reg, data_loss

        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
        no_flag = jnp.zeros((), jnp.int32)
        # The rows layout's shard tail keeps this many of the all-gather
        # update's slots; whether that is fewer than it is handed is a
        # trace-time fact, as ``can_overflow`` is.
        tail_bound, may_fill = None, False
        lookup_bound, lookup_form = None, "rows"
        if not packed and (lookup == "allgather" or (fallback and can_overflow)):
            ids_per_chip = batch.ids.shape[0] * batch.ids.shape[1]
            tail_bound = shard_tail_ids(mesh, ids_per_chip, capacity_factor)
            may_fill = tail_bound < mesh.size * ids_per_chip
            if mesh.shape[ROW_AXIS] > 1:
                # So is the lookup's form, from the shard's shapes.
                lookup_bound = shard_lookup_ids(mesh, ids_per_chip, capacity_factor)
                lookup_form = shard_lookup_form(
                    table.shape[0], mesh.shape[ROW_AXIS] * ids_per_chip, d_row, lookup_bound
                )

        def allgather_branch():
            if fused:
                from fast_tffm_tpu.ops.packed_table import resolve_fused_update
                from fast_tffm_tpu.parallel.embedding import (
                    fused_sharded_gather,
                    fused_sharded_update,
                )

                rows = fused_sharded_gather(
                    table, batch.ids, d_row, shard_logical_rows
                )
                (_, dl), (g_rows, g_dense) = grad_fn(rows, dense)
                fmode = resolve_fused_update(packed_update, table.shape[0])
                with jax.named_scope("fm.tail"):
                    t2 = fused_sharded_update(
                        table, batch.ids, g_rows, learning_rate,
                        shard_logical_rows, mode=fmode, k_cap=compact_cap,
                    )
                return t2, accum, g_dense, dl, no_flag, no_flag
            if packed:
                from fast_tffm_tpu.ops.packed_table import resolve_packed_update
                from fast_tffm_tpu.parallel.embedding import (
                    packed_sharded_dense_update,
                    packed_sharded_gather,
                    packed_sharded_update,
                )

                rows = packed_sharded_gather(
                    table, batch.ids, d_row, shard_logical_rows
                )
                (_, dl), (g_rows, g_dense) = grad_fn(rows, dense)
                mode = resolve_packed_update(
                    packed_update, table.shape[0], accum.shape[-1]
                )
                with jax.named_scope("fm.tail"):
                    if mode in ("dense", "compact"):
                        t2, a2 = packed_sharded_dense_update(
                            table, accum, batch.ids, g_rows, learning_rate,
                            shard_logical_rows, mode=mode,
                        )
                    else:
                        t2, a2 = packed_sharded_update(
                            table, accum, batch.ids, g_rows, learning_rate,
                            num_rows_global, shard_logical_rows,
                        )
                return t2, a2, g_dense, dl, no_flag, no_flag
            rows, read_whole = sharded_gather(
                table, batch.ids, bound=lookup_bound, form=lookup_form
            )
            (_, dl), (g_rows, g_dense) = grad_fn(rows, dense)
            t2, a2, whole = sharded_sparse_adagrad_update(
                table, accum, batch.ids, g_rows, learning_rate,
                num_rows_global, decay=decay, bound=tail_bound,
            )
            return (
                t2, a2, g_dense, dl, whole if may_fill else no_flag,
                no_flag if read_whole is None else read_whole,
            )

        if lookup == "alltoall":
            from fast_tffm_tpu.parallel.alltoall import routed_update, routing_overflow

            def routed_branch():
                rows = gather(table, batch.ids)
                (_, dl), (g_rows, g_dense) = grad_fn(rows, dense)
                if fused:
                    from fast_tffm_tpu.ops.packed_table import resolve_fused_update

                    fmode = resolve_fused_update(packed_update, table.shape[0])
                    t2, a2, overflow = routed_update(
                        table, accum, batch.ids, g_rows, learning_rate,
                        num_rows_global, cap,
                        shard_logical_rows=shard_logical_rows, packed_mode=fmode,
                        fused=True, compact_cap=compact_cap,
                    )
                elif packed:
                    from fast_tffm_tpu.ops.packed_table import resolve_packed_update

                    pmode = resolve_packed_update(
                        packed_update, table.shape[0], accum.shape[-1]
                    )
                    t2, a2, overflow = routed_update(
                        table, accum, batch.ids, g_rows, learning_rate,
                        num_rows_global, cap,
                        shard_logical_rows=shard_logical_rows, packed_mode=pmode,
                    )
                else:
                    t2, a2, overflow = routed_update(
                        table, accum, batch.ids, g_rows, learning_rate,
                        num_rows_global, cap, decay=decay,
                    )
                if not fallback:
                    # A dropped contribution must never persist silently:
                    # NaN the loss so the training loop aborts before
                    # checkpointing.
                    dl = jnp.where(overflow, jnp.nan, dl)
                return t2, a2, g_dense, dl, no_flag, no_flag

            # When overflow is statically impossible, emit the routed branch
            # alone — no bincount, no dual compile (HLO-pinned by
            # test_impossible_overflow_skips_cond).
            if fallback and can_overflow:
                # shard_logical_rows == table.shape[0] for the rows layout;
                # for packed shards the table's leading dim is PHYSICAL, so
                # the closure's logical count is the correct one either way.
                overflowed = routing_overflow(batch.ids, shard_logical_rows, cap)
                table, accum, g_dense, data_loss_local, whole, read_whole = lax.cond(
                    overflowed, allgather_branch, routed_branch
                )
            else:
                table, accum, g_dense, data_loss_local, whole, read_whole = routed_branch()
                overflowed = jnp.asarray(False)
        else:
            table, accum, g_dense, data_loss_local, whole, read_whole = allgather_branch()
            overflowed = jnp.asarray(False)
        if jax.tree.leaves(dense):
            with exchange_scope("fm.tail"):
                g_dense = lax.psum(g_dense, _BOTH)
            dense, dense_acc = dense_adagrad_update(
                dense, AdagradState(dense_acc), g_dense, learning_rate,
                decay=decay,
            )
            dense_acc = dense_acc.accum
        # The flags ride the loss's all-reduce.  A row shard's data replicas
        # are handed the same slots by the update and decide alike; each
        # chip's lookup serves its own row peers.
        counted = {}
        if count_full_tails and may_fill:
            counted["tail"] = whole
        if count_full_lookups and lookup_form == "sweep":
            counted["lookup"] = read_whole
        with exchange_scope("fm.loss"):
            data_loss, counted = lax.psum((data_loss_local, counted), _BOTH)
        whole = counted["tail"] // mesh.shape[DATA_AXIS] if "tail" in counted else no_flag
        read_whole = counted.get("lookup", no_flag)
        return (
            table, accum, dense, dense_acc, data_loss, overflowed.astype(jnp.int32),
            whole, read_whole,
        )

    dense_spec = jax.tree.map(lambda _: P(), model.init_dense(jax.random.key(0)))
    mapped = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(ROW_AXIS, None),
            P(ROW_AXIS, None),
            dense_spec,
            dense_spec,
            _batch_specs(),
        ),
        out_specs=(
            P(ROW_AXIS, None), P(ROW_AXIS, None), dense_spec, dense_spec, P(), P(), P(), P(),
        ),
        check_vma=False,
    )

    def _apply(state: TrainState, batch: Batch):
        table, accum, dense, dense_acc, loss, overflowed, whole, read_whole = mapped(
            state.table, state.table_opt.accum, state.dense, state.dense_opt.accum, batch
        )
        new = TrainState(
            table, AdagradState(accum), dense, AdagradState(dense_acc), state.step + 1
        )
        # The counters the caller asked for, in the order the return names them.
        return new, loss, (
            (overflowed,) * fallback + (whole,) * count_full_tails
            + (read_whole,) * count_full_lookups
        )

    if steps_per_call <= 1:

        @partial(jax.jit, donate_argnums=(0,))
        def step(state: TrainState, batch: Batch):
            new, loss, counts = _apply(state, batch)
            return (new, loss, *counts)

    else:

        @partial(jax.jit, donate_argnums=(0,))
        def step(state: TrainState, superbatch: Batch):
            def one(st, b):
                new, loss, counts = _apply(st, b)
                return new, (loss, counts)

            state, (losses, counts) = lax.scan(one, state, superbatch)
            return (state, losses, *(jnp.sum(c) for c in counts))

    return step


def make_sharded_predict_step(
    model, mesh: Mesh, *, lookup: str = "allgather", capacity_factor: float = 2.0,
    overflow_mode: str = "abort", table_layout: str = "rows",
    accumulator: str = "element",
):
    """Returns jitted SPMD ``predict(state, batch) -> sigmoid scores [B]``.

    ``overflow_mode='fallback'`` (alltoall only) reruns an overflowing
    batch's lookup through the allgather collective instead of NaN-ing the
    scores — same ``lax.cond`` scheme as the train step.
    ``accumulator='fused'`` reads the fused tile-row table (the state a
    fused dist_train holds mid-run); _make_gather routes both lookups."""
    packed = table_layout == "packed"
    fused = accumulator == "fused"
    if packed:
        model, shard_logical_rows, _ = packed_shard_meta(model, mesh, fused=fused)
    else:
        model = _pad_model_vocab(model, mesh)
        shard_logical_rows = model.vocabulary_size // mesh.shape[ROW_AXIS]
    d_row = model.row_dim
    fallback = lookup == "alltoall" and overflow_mode == "fallback"
    packed_meta = (d_row, shard_logical_rows) if packed else None

    def shard_body(table, dense, batch: Batch):
        gather, cap, can_overflow = _make_gather(
            mesh, batch.ids.shape, lookup, capacity_factor, packed_meta,
            fused=fused,
        )
        if fallback and can_overflow:
            from fast_tffm_tpu.parallel.alltoall import routing_overflow

            # The allgather fallback is exactly _make_gather's allgather
            # selection (packed-aware) — build it there, not by hand.
            ag_gather, _, _ = _make_gather(
                mesh, batch.ids.shape, "allgather", capacity_factor, packed_meta,
                fused=fused,
            )
            rows = lax.cond(
                routing_overflow(batch.ids, shard_logical_rows, cap),
                lambda: ag_gather(table, batch.ids),
                lambda: gather(table, batch.ids),
            )
        else:
            rows = gather(table, batch.ids)
        scores = jax.nn.sigmoid(model.score(rows, dense, batch))
        # Replicate the (tiny, [B]) score vector so the result is fetchable
        # on every process of a multi-host mesh — a P(('data','row'))-sharded
        # output would span non-addressable devices there.
        with exchange_scope():
            return lax.all_gather(scores, _BOTH, tiled=True)

    dense_spec = jax.tree.map(lambda _: P(), model.init_dense(jax.random.key(0)))
    mapped = shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(ROW_AXIS, None), dense_spec, _batch_specs()),
        out_specs=P(),
        check_vma=False,
    )

    @jax.jit
    def predict(state: TrainState, batch: Batch):
        return mapped(state.table, state.dense, batch)

    return predict
