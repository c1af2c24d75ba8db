"""Device-resident dataset mode: load the epoch ONCE, slice batches on-chip.

The reference streams every batch from the host every epoch — it had to,
being CPU-only (`renyi533/fast_tffm` :: py/ input queues feeding the
session loop).  On a TPU every streamed step also pays a host→device
transfer of its batch — so for any dataset whose packed arrays fit HBM
**beside the table**, per-step H2D transfer is pure overhead the
framework can eliminate entirely.

``device_cache = true`` ([Train]) does that: the FMB-backed input is
assembled into flat row-major device arrays ``[batches·B, ...]`` ONE time,
and every train step slices its batch out with ``lax.dynamic_slice``
inside the SAME jitted program as the model step — zero host↔device bytes
per step, zero extra dispatches.  Epochs re-visit the resident arrays; a
per-epoch ``shuffle`` uploads one [rows] permutation (the identical
permutation the streamed path draws — bit-parity holds shuffled too) and
the step gathers its batch through it.

Bit-identity with the streamed path is BY CONSTRUCTION: the resident
arrays are assembled by ``fmb_batch_stream`` itself (same padding, width
clamping, per-file weights, header validation), and the step applies
``trainer.train_step_body`` — the same function the streamed step jits —
to the same values (test-pinned in tests/test_device_cache.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fast_tffm_tpu.models.base import Batch
from fast_tffm_tpu.trainer import TrainState, train_step_body

__all__ = [
    "DeviceDataset",
    "load_device_dataset",
    "epoch_permutation",
    "full_epoch_perm",
    "make_cached_train_step",
    "make_cached_scan_train_step",
    "make_cached_touched_marker",
    "epoch_index_chunks",
]


class DeviceDataset(NamedTuple):
    """Flat row-major device-resident arrays: leading dim [batches·B]
    (ONE copy serves both the sequential slice and the shuffled gather —
    a second batch-major copy would halve the max cacheable dataset)."""

    labels: Any  # f32 [batches·B]
    ids: Any  # i32 [batches·B, N]
    vals: Any  # f32 [batches·B, N]
    fields: Any  # i32 [batches·B, N] (or [batches·B, 0] when unused)
    weights: Any  # f32 [batches·B]  (0.0 on tail-padding rows)
    batches: int
    batch_size: int
    n_rows: int  # real (unpadded) rows

    @property
    def nbytes(self) -> int:
        return sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for a in (self.labels, self.ids, self.vals, self.fields, self.weights)
        )


def _load_host_arrays(
    files,
    *,
    batch_size: int,
    vocabulary_size: int,
    hash_feature_id: bool = False,
    max_nnz: int | None = None,
    weights=None,
    with_fields: bool = True,
    shard_index: int = 0,
    shard_count: int = 1,
):
    """Flat host staging arrays via fmb_batch_stream (shared by the local
    and mesh-sharded loaders — the sharded one uploads straight from
    host to its mesh placement, never bouncing through one device).

    ``shard_count`` > 1 loads only this PROCESS's block-cyclic shard of
    every global batch (rows [p·B/P, (p+1)·B/P), the multi-host input
    scheme dist_train's streamed path uses): ``batch_size`` stays the
    GLOBAL batch, the staged arrays hold batch_size/shard_count rows per
    batch, and the emitted ``batches`` count is the global one (every
    process stages the same number of per-batch slices).
    """
    from fast_tffm_tpu.data.binary import fmb_batch_stream, open_fmb

    files = [str(f) for f in files]
    n_rows = sum(open_fmb(f).n_rows for f in files)
    if n_rows == 0:
        raise ValueError(f"device_cache: no rows in {files}")
    if batch_size % shard_count:
        raise ValueError(
            f"device_cache: global batch_size {batch_size} not divisible "
            f"by {shard_count} processes"
        )
    batches = -(-n_rows // batch_size)  # ceil; tail pads with weight-0 rows
    local_bs = batch_size // shard_count
    flat = batches * local_bs
    # Preallocate the flat host staging arrays (shapes are known upfront)
    # and fill per-batch slices — a list-then-concatenate would hold the
    # whole dataset on the host TWICE, OOMing exactly the near-HBM-sized
    # datasets this mode exists for.
    host = None
    lo = 0
    for parsed, w in fmb_batch_stream(
        files,
        batch_size=local_bs,
        vocabulary_size=vocabulary_size,
        hash_feature_id=hash_feature_id,
        max_nnz=max_nnz,
        epochs=1,
        weights=weights,
        shard_index=shard_index,
        shard_count=shard_count,
        shard_block=local_bs if shard_count > 1 else 1,
        pad_to_batches=batches if shard_count > 1 else None,
    ):
        if host is None:
            width = parsed.ids.shape[1]
            host = dict(
                labels=np.zeros(flat, np.float32),
                ids=np.zeros((flat, width), np.int32),
                vals=np.zeros((flat, width), np.float32),
                fields=np.zeros((flat, width if with_fields else 0), np.int32),
                weights=np.zeros(flat, np.float32),
            )
        hi = lo + parsed.labels.shape[0]
        host["labels"][lo:hi] = parsed.labels
        host["ids"][lo:hi] = parsed.ids
        host["vals"][lo:hi] = parsed.vals
        if with_fields:
            host["fields"][lo:hi] = parsed.fields
        host["weights"][lo:hi] = w
        lo = hi
    return host, batches, n_rows


def load_device_dataset(
    files,
    *,
    batch_size: int,
    vocabulary_size: int,
    hash_feature_id: bool = False,
    max_nnz: int | None = None,
    weights=None,
    with_fields: bool = True,
    device=None,
) -> DeviceDataset:
    """Assemble FMB files into one device-resident DeviceDataset.

    Every row goes through ``fmb_batch_stream`` — the exact batches the
    streamed trainer would see (same order, padding, weights, header
    validation) — then the concatenated arrays transfer to the device
    once, COMMITTED to ``device`` (default: the first device) so nothing
    moves them implicitly later.
    """
    host, batches, n_rows = _load_host_arrays(
        files,
        batch_size=batch_size,
        vocabulary_size=vocabulary_size,
        hash_feature_id=hash_feature_id,
        max_nnz=max_nnz,
        weights=weights,
        with_fields=with_fields,
    )
    put = partial(jax.device_put, device=device or jax.devices()[0])
    return DeviceDataset(
        labels=put(host["labels"]),
        ids=put(host["ids"]),
        vals=put(host["vals"]),
        fields=put(host["fields"]),
        weights=put(host["weights"]),
        batches=batches,
        batch_size=batch_size,
        n_rows=n_rows,
    )


def epoch_permutation(shuffle_seed: int, epoch: int, n_rows: int) -> np.ndarray:
    """THE permutation the streamed path draws for this epoch: the driver
    folds the epoch into the seed (fold_epoch_seed) and the per-epoch
    stream draws its epoch-0 permutation — both through binary.py's shared
    helpers, so device-cached shuffling is STRUCTURALLY bit-identical to
    streamed shuffling (one definition, not three synchronized copies)."""
    from fast_tffm_tpu.data.binary import draw_permutation, fold_epoch_seed

    return draw_permutation(fold_epoch_seed(shuffle_seed, epoch), 0, n_rows)


def full_epoch_perm(data: DeviceDataset, shuffle_seed: int, epoch: int) -> np.ndarray:
    """Flat-row index order for one shuffled epoch: the streamed-path
    permutation over the real rows, then the tail-padding rows in place
    (they sit at flat positions [n_rows, batches·B) and always land in the
    final batch, exactly like the streamed tail)."""
    flat_rows = data.batches * data.batch_size
    return np.concatenate(
        [
            epoch_permutation(shuffle_seed, epoch, data.n_rows),
            np.arange(data.n_rows, flat_rows, dtype=np.int64),
        ]
    ).astype(np.int32)


def make_cached_train_step(model, learning_rate: float, data: DeviceDataset, body=None):
    """Returns jitted ``step(state, i) -> (state, data_loss)`` over the
    resident arrays — and ``step_shuffled(state, perm, i)`` whose batch
    rows come through a device-resident [rows] permutation.

    ``i`` is a traced scalar (one executable serves every step; a Python
    int would retrace per step).  The resident arrays are EXPLICIT jit
    arguments, never closure captures: a closure-captured jax.Array
    becomes an embedded constant, and this backend re-materializes
    embedded constants per call — measured 217 ms/step vs 32 µs with the
    same arrays passed as arguments (an 8000× cliff; see DESIGN §6).
    One dispatch per step; XLA fuses the batch slice into the model
    program, so the slice costs O(B·N) HBM reads, not a transfer.
    """
    B = data.batch_size
    arrays = (data.labels, data.ids, data.vals, data.fields, data.weights)
    body = body or train_step_body  # packed layout passes its own body

    @partial(jax.jit, donate_argnums=(0,))
    def _step(state: TrainState, arrs, i):
        sl = lambda a: lax.dynamic_slice_in_dim(a, i * B, B, axis=0)
        b = Batch(*map(sl, arrs))
        return body(model, learning_rate, state, b)

    @partial(jax.jit, donate_argnums=(0,))
    def _step_shuffled(state: TrainState, arrs, perm, i):
        idx = lax.dynamic_slice_in_dim(perm, i * B, B)
        b = Batch(*(jnp.take(a, idx, axis=0) for a in arrs))
        return body(model, learning_rate, state, b)

    def step(state, i):
        return _step(state, arrays, i)

    def step_shuffled(state, perm, i):
        return _step_shuffled(state, arrays, perm, i)

    # Measured-cost hooks (profiling.CostLedger): the closures stay
    # profileable by delegating .lower to the inner jit with the resident
    # arrays bound — lowering only, never a second backend compile.
    # analysis: ok recompile-hazard delegated CostLedger .lower hook, not a second compile
    step.lower = lambda st, i: _step.lower(st, arrays, i)
    # analysis: ok recompile-hazard delegated CostLedger .lower hook, not a second compile
    step_shuffled.lower = lambda st, perm, i: _step_shuffled.lower(
        st, arrays, perm, i
    )

    return step, step_shuffled


def make_cached_touched_marker(data: DeviceDataset):
    """Touched-row bitmap markers for the delta-checkpoint subsystem on
    the device-cache path, where the driver's per-step "batch" is a
    resident batch index (scalar) or a [K] scan chunk — the ids live on
    device, so the mark slices them there (``(mark, mark_shuffled)``;
    the shuffled variant routes through the epoch permutation exactly as
    the shuffled step gathers its rows).  Resident arrays are EXPLICIT
    jit arguments, never closure captures (the embedded-constant cliff,
    DESIGN §6)."""
    B = data.batch_size

    def _rows(i):
        starts = i.reshape(-1).astype(jnp.int32)
        return (
            starts[:, None] * B + jnp.arange(B, dtype=jnp.int32)[None, :]
        ).reshape(-1)

    @partial(jax.jit, donate_argnums=(0,))
    def _mark(bitmap, ids_arr, i):
        return bitmap.at[ids_arr[_rows(i)].reshape(-1)].set(True, mode="drop")

    @partial(jax.jit, donate_argnums=(0,))
    def _mark_shuffled(bitmap, ids_arr, perm, i):
        return bitmap.at[ids_arr[perm[_rows(i)]].reshape(-1)].set(
            True, mode="drop"
        )

    def mark(bitmap, i):
        return _mark(bitmap, data.ids, i)

    def mark_shuffled(bitmap, perm, i):
        return _mark_shuffled(bitmap, data.ids, perm, i)

    return mark, mark_shuffled


def make_cached_ids_slicer(data: DeviceDataset):
    """``ids_fn(batch_index) -> ids`` for the datastats collector on the
    device-cache path, where the driver's per-step "batch" is a resident
    batch index (scalar) or a [K] scan chunk: the sampled window's ids
    are sliced ON DEVICE from the resident array — no host round-trip.
    Same explicit-argument jit discipline as the touched marker above."""
    B = data.batch_size

    @jax.jit
    def _slice(ids_arr, i):
        starts = i.reshape(-1).astype(jnp.int32)
        rows = (
            starts[:, None] * B + jnp.arange(B, dtype=jnp.int32)[None, :]
        ).reshape(-1)
        return ids_arr[rows]

    def ids_at(b):
        return _slice(data.ids, jnp.asarray(b))

    return ids_at


def epoch_index_chunks(batches: int, k: int, start: int = 0):
    """Pre-placed device index vectors for one scan-fused epoch: [K]-long
    chunks of the batch indices, plus one [batches % K] remainder — the
    per-call "input" of the scanned cached step.  Placed on device ONCE
    (the same vectors serve every epoch), so an epoch is ``ceil(batches/K)``
    dispatches with zero host involvement in between.  At most two distinct
    lengths exist (K and the remainder), so the scanned step compiles at
    most twice.

    ``start`` > 0 is the exact-position-resume seek: chunks stay aligned
    to the SAME K-grid an uninterrupted epoch uses (so every full chunk
    re-hits the already-compiled shapes) and the first chunk is clipped
    to begin at ``start`` — at most one extra compiled length when a
    resume lands mid-chunk (save boundaries are K-aligned, so normally
    none)."""
    lo0 = (max(0, start) // k) * k
    out = []
    for lo in range(lo0, batches, k):
        a, b = max(lo, start), min(lo + k, batches)
        if a < b:
            out.append(jax.device_put(np.arange(a, b, dtype=np.int32)))
    return out


def make_cached_scan_train_step(model, learning_rate: float, data: DeviceDataset, body=None):
    """Scan-fused twins of ``make_cached_train_step``'s steps: jitted
    ``step(state, idxs [K]) -> (state, losses [K])`` running K consecutive
    batch slices through ONE dispatch via ``lax.scan`` (and
    ``step_shuffled(state, perm, idxs)`` gathering through the epoch
    permutation).  The scan body applies the SAME ``body`` to the SAME
    slices the per-step functions would, so K>1 is bit-identical to K
    sequential calls (test-pinned).  K is read from ``idxs``' shape —
    epoch_index_chunks' remainder vector reuses this function and compiles
    its own (single) executable.  Resident arrays stay EXPLICIT jit
    arguments (the embedded-constant cliff, DESIGN §6); the donated state
    threads through the scan carry, so the table still updates in place.
    """
    B = data.batch_size
    arrays = (data.labels, data.ids, data.vals, data.fields, data.weights)
    body = body or train_step_body

    @partial(jax.jit, donate_argnums=(0,))
    def _scan_step(state: TrainState, arrs, idxs):
        def one(st, i):
            sl = lambda a: lax.dynamic_slice_in_dim(a, i * B, B, axis=0)
            return body(model, learning_rate, st, Batch(*map(sl, arrs)))

        return lax.scan(one, state, idxs)

    @partial(jax.jit, donate_argnums=(0,))
    def _scan_step_shuffled(state: TrainState, arrs, perm, idxs):
        def one(st, i):
            idx = lax.dynamic_slice_in_dim(perm, i * B, B)
            b = Batch(*(jnp.take(a, idx, axis=0) for a in arrs))
            return body(model, learning_rate, st, b)

        return lax.scan(one, state, idxs)

    def step(state, idxs):
        return _scan_step(state, arrays, idxs)

    def step_shuffled(state, perm, idxs):
        return _scan_step_shuffled(state, arrays, perm, idxs)

    # Same measured-cost .lower delegation as make_cached_train_step's.
    # analysis: ok recompile-hazard delegated CostLedger .lower hook, not a second compile
    step.lower = lambda st, idxs: _scan_step.lower(st, arrays, idxs)
    # analysis: ok recompile-hazard delegated CostLedger .lower hook, not a second compile
    step_shuffled.lower = lambda st, perm, idxs: _scan_step_shuffled.lower(
        st, arrays, perm, idxs
    )

    return step, step_shuffled


def load_sharded_device_dataset(
    files,
    *,
    mesh,
    batch_size: int,
    vocabulary_size: int,
    hash_feature_id: bool = False,
    max_nnz: int | None = None,
    weights=None,
    with_fields: bool = True,
) -> DeviceDataset:
    """Device-resident dataset SHARDED over a ('data','row') mesh.

    Layout is batch-major ``[batches, B, ...]`` with the BATCH dim sharded
    over both mesh axes (P(None, ('data','row'))): every step's
    ``dynamic_slice`` runs on the unsharded batches axis — trivially
    SPMD-partitionable — and each chip holds exactly its micro-batch slice
    of every batch, so per-chip HBM cost is total/n_devices.

    MULTI-HOST meshes work the same way the streamed input path does:
    each process stages only ITS rows of every global batch (block-cyclic
    shard, the make_global_batch scheme) and contributes exactly its
    addressable devices' slice via
    ``jax.make_array_from_process_local_data`` — no process ever holds
    (or transfers) another host's shard.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fast_tffm_tpu.parallel.mesh import DATA_AXIS, ROW_AXIS

    nproc = jax.process_count()
    host, batches, n_rows = _load_host_arrays(
        files,
        batch_size=batch_size,
        vocabulary_size=vocabulary_size,
        hash_feature_id=hash_feature_id,
        max_nnz=max_nnz,
        weights=weights,
        with_fields=with_fields,
        shard_index=jax.process_index() if nproc > 1 else 0,
        shard_count=nproc,
    )

    def shard(a):
        # Upload straight from the host staging array to the mesh
        # placement: each chip receives only its shard, so a dataset
        # sized for AGGREGATE mesh HBM never has to fit one device (and
        # multi-host, never has to fit one HOST either).
        local_rows = a.shape[0] // batches
        bm = np.ascontiguousarray(
            a.reshape((batches, local_rows) + a.shape[1:])
        )
        spec = P(None, (DATA_AXIS, ROW_AXIS), *([None] * (bm.ndim - 2)))
        sharding = NamedSharding(mesh, spec)
        if nproc > 1:
            return jax.make_array_from_process_local_data(sharding, bm)
        return jax.device_put(bm, sharding)

    return DeviceDataset(
        labels=shard(host["labels"]),
        ids=shard(host["ids"]),
        vals=shard(host["vals"]),
        fields=shard(host["fields"]),
        weights=shard(host["weights"]),
        batches=batches,
        batch_size=batch_size,
        n_rows=n_rows,
    )


def make_cached_sharded_train_step(
    sharded_step, data: DeviceDataset, steps_per_call: int = 1,
):
    """Wrap a ``make_sharded_train_step`` step so each call slices batch
    ``i`` out of the mesh-sharded resident arrays on-device (sequential
    order only — a shuffled gather across the sharded batch dim would be
    per-step cross-chip traffic, exactly what this mode exists to avoid).

    Same closure rule as the local cached step: resident arrays travel as
    explicit jit arguments (embedded-constant cliff, DESIGN §6).

    ``steps_per_call`` > 1 returns the scan-fused form instead:
    ``step(state, idxs [K]) -> (state, losses [K])`` runs K consecutive
    resident batches through ONE dispatch, the SPMD body scanning on
    device (epoch_index_chunks supplies the pre-placed index vectors,
    remainder included).  A sharded step that returns counters after its
    loss (the alltoall ``fallback``'s overflow flag, ``count_full_tails``)
    scans transparently: per-step losses stay [K] and each counter's
    per-step values SUM into one replicated int32 (the driver only ever
    counts them, so K-granularity is not lost — the count is exact).
    """
    from fast_tffm_tpu.models.base import Batch as _Batch

    arrays = (data.labels, data.ids, data.vals, data.fields, data.weights)

    if steps_per_call <= 1:

        @partial(jax.jit, donate_argnums=(0,))
        def _step(state, arrs, i):
            sl = lambda a: lax.dynamic_slice_in_dim(a, i, 1, axis=0)[0]
            return sharded_step(state, _Batch(*map(sl, arrs)))

        def step(state, i):
            return _step(state, arrays, i)

        return step

    @partial(jax.jit, donate_argnums=(0,))
    def _scan_step(state, arrs, idxs):
        def one(st, i):
            sl = lambda a: lax.dynamic_slice_in_dim(a, i, 1, axis=0)[0]
            st, loss, *counts = sharded_step(st, _Batch(*map(sl, arrs)))
            return st, (loss, counts)

        state, (losses, counts) = lax.scan(one, state, idxs)
        return (state, losses, *(jnp.sum(c) for c in counts))

    def step_k(state, idxs):
        return _scan_step(state, arrays, idxs)

    return step_k
