"""Lane-packed embedding table: P logical [D] rows per 128-lane tile row.

WHY (measured on this environment's chip, DESIGN §6 round-3 correction):
a TPU f32 array is tiled (8, 128); a narrow embedding row (D = 1+k = 9
for the flagship FM) occupies 9 of a tile row's 128 lanes, so every
random-row scatter is a masked partial-lane read-modify-write — measured
~104 ns/row (~0.35 GB/s payload), 7.5× slower than scattering full
128-lane rows and ~70× slower than 1-D scatters.  The sparse Adagrad
update, not compute, dominates the train step.

The fix is physical layout, not a new algorithm: store the table as
``[ceil(V/P), 128]`` with ``P = 128 // D`` logical rows packed per
physical row (P=14 at D=9 → 126/128 lanes used).  Then:

  * the LOOKUP gathers full 128-lane physical rows (measured ~271 GB/s
    vs ~6 GB/s for narrow rows) and extracts each id's D-lane slice with
    P static masked slices (dense VPU work);
  * the UPDATE dedups ONCE at physical-row granularity *in lane space*:
    per-occurrence grads are inserted into their slot lanes, sorted by
    id (ids sorted ⇒ physical rows sorted), segment-summed at full 128
    width, and applied with one wide gather + one wide scatter per
    array.  Element-wise Adagrad with a zero gradient is the identity,
    so writing whole 128-lane rows is EXACT — untouched neighbors in a
    shared tile row read and write back their current values.

Semantics are identical to the rows layout (same sums in the same
order — test-pinned exactly); only bytes move differently.  Reference
capability parity: this replaces the same TF sparse-Adagrad scatter the
rows layout replaces (`renyi533/fast_tffm` :: graph builder's
AdagradOptimizer sparse path); the layout itself has no reference analog
because CPUs don't have lane tiles.

Constraints: element-granularity accumulator (it packs identically and
zero-grad identity makes whole-row RMW exact); D ≤ 128 (64 < D ≤ 128
degrades to P=1 — one padded row per tile row, memory ×128/D, still the
fast full-width scatter path; FFM at 22 fields × k=4 has D=89).
Checkpoints always store the LOGICAL [V, D] table (pack/unpack below),
so packed and rows checkpoints are interchangeable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "LANES",
    "DENSE_G_MAX_BYTES",
    "rows_per_tile",
    "packed_rows",
    "pack_table",
    "pack_accum",
    "pack_accum_rows",
    "pack_accum_any",
    "unpack_table",
    "unpack_accum_rows",
    "unpack_accum_any",
    "packed_gather",
    "packed_accum_gather_any",
    "fused_accum_gather",
    "scatter_logical_rows",
    "packed_dense_grad",
    "packed_dense_adagrad_update",
    "packed_compact_adagrad_update",
    "packed_sparse_adagrad_update",
    "resolve_packed_update",
    "PACKED_UPDATE_FNS",
    "fused_rows_per_tile",
    "fused_packed_rows",
    "pack_fused",
    "unpack_fused",
    "fused_gather",
    "fused_dense_adagrad_update",
    "fused_compact_adagrad_update",
    "resolve_fused_update",
    "apply_fused_update",
    "FUSED_UPDATE_FNS",
]

LANES = 128


def rows_per_tile(d: int) -> int:
    """Logical rows per 128-lane physical row.  P >= 2 packs multiple
    rows per tile row; 64 < D <= 128 degrades to P = 1 — one logical row
    padded to the full tile row (memory ×128/D, e.g. 1.44× for FFM's
    D=89) which still converts every partial-lane scatter into the fast
    full-width path."""
    if d > LANES:
        raise ValueError(f"packed layout needs D <= {LANES}, got {d}")
    return max(1, LANES // d)


def packed_rows(vocab: int, d: int) -> int:
    return -(-vocab // rows_per_tile(d))


_CHUNK_LOGICAL_ROWS = 1 << 21  # chunked packing granularity (rounded to P)


def _pack_block(block: jax.Array, p: int, pad_value: float) -> jax.Array:
    """[n·P, D] logical rows -> [n, 128] packed rows (spare lanes carry
    ``pad_value``)."""
    n = block.shape[0] // p
    d = block.shape[1]
    out = jnp.full((n, LANES), pad_value, block.dtype)
    return out.at[:, : p * d].set(block.reshape(n, p * d))


from functools import partial as _partial


@_partial(jax.jit, donate_argnums=(0,), static_argnums=(4,))
def _chunk_write(buf, block, start_phys, pad_value, p):
    """One donated chunk write.  ``start_phys`` and ``pad_value`` are
    traced (ONE compile covers every full-size chunk; the ragged tail's
    different block shape costs a second) — a static start would
    recompile per chunk, ~112 times at a 235M-row table."""
    return jax.lax.dynamic_update_slice_in_dim(
        buf, _pack_block(block, p, pad_value), start_phys, axis=0
    )


def pack_table(table: jax.Array, pad_value: float = 0.0) -> jax.Array:
    """[V, D] logical -> [VP, 128] packed (pad lanes/rows = pad_value).

    Large tables pack in chunks through a donated accumulator so the
    transient device-memory peak stays ~logical+packed (measured: the
    whole-array path's extra flat copy OOMs 16M-row vocabs on a busy
    shared chip)."""
    v, d = table.shape
    p = rows_per_tile(d)
    vp = packed_rows(v, d)
    chunk = (_CHUNK_LOGICAL_ROWS // p) * p
    if v <= chunk:
        flat = jnp.full((vp * p, d), pad_value, table.dtype).at[:v].set(table)
        return _pack_block(flat, p, pad_value)
    packed = jnp.full((vp, LANES), pad_value, table.dtype)
    for lo in range(0, v, chunk):
        hi = min(lo + chunk, v)
        block = table[lo:hi]
        if (hi - lo) % p:
            pad = p - (hi - lo) % p
            block = jnp.concatenate(
                [block, jnp.full((pad, d), pad_value, table.dtype)]
            )
        packed = _chunk_write(
            packed, block, jnp.int32(lo // p), jnp.asarray(pad_value, table.dtype), p
        )
    return packed


def pack_accum(accum: jax.Array, init_value: float) -> jax.Array:
    """pack_table for ACCUMULATORS: padding lanes/rows carry
    ``init_value``, never zero — the whole-tile-row Adagrad RMW divides
    by sqrt(acc), and a zero pad would turn 0/sqrt(0) into NaN the first
    time a partially-used physical row updates."""
    return pack_table(accum, pad_value=init_value)


def unpack_table(packed: jax.Array, vocab: int, d: int) -> jax.Array:
    """[VP, 128] packed -> [V, D] logical."""
    p = rows_per_tile(d)
    vp = packed.shape[0]
    return packed[:, : p * d].reshape(vp * p, d)[:vocab]


def packed_gather(packed: jax.Array, ids: jax.Array, d: int) -> jax.Array:
    """rows[..., D] for logical ``ids`` from a packed table.

    One wide gather of [M, 128] physical rows, then P static masked
    slices sum into the [..., D] result (each id has exactly one live
    slot, so the sum just selects)."""
    p = rows_per_tile(d)
    with jax.named_scope("fm.gather"):
        phys = ids // p
        slot = ids % p
        rows128 = packed[phys]  # [..., 128] full-tile-row gather
        out = jnp.zeros(ids.shape + (d,), packed.dtype)
        for s in range(p):
            piece = rows128[..., s * d : (s + 1) * d]
            out = out + jnp.where((slot == s)[..., None], piece, 0)
    return out


def packed_accum_gather_any(
    acc_packed: jax.Array, ids: jax.Array, d: int
) -> jax.Array:
    """Logical accumulator rows for ``ids`` from a packed accumulator of
    either granularity: [VP, 128] element → [..., D] (same packing as the
    table, so the table gather serves it), [VP, P] row → [..., 1] slot
    scalars.  The checkpoint delta writer's accumulator twin of
    ``packed_gather`` — deltas store LOGICAL rows, so packed and rows
    checkpoints stay interchangeable link by link."""
    p = rows_per_tile(d)
    if acc_packed.shape[-1] == LANES and p != LANES:
        return packed_gather(acc_packed, ids, d)
    return acc_packed[ids // p, ids % p][..., None]


def fused_accum_gather(fused: jax.Array, ids: jax.Array, d: int) -> jax.Array:
    """[..., 1] row-accumulator scalars for logical ``ids`` from a FUSED
    tile-row table (the accumulator lane at slot offset s·(D+1)+D)."""
    p = fused_rows_per_tile(d)
    d1 = d + 1
    phys = ids // p
    slot = ids % p
    rows128 = fused[phys]
    out = jnp.zeros(ids.shape, fused.dtype)
    for s in range(p):
        out = out + jnp.where(slot == s, rows128[..., s * d1 + d], 0)
    return out[..., None]


def scatter_logical_rows(
    packed: jax.Array, ids: jax.Array, rows: jax.Array, d: int
) -> jax.Array:
    """Write logical rows INTO a packed table: the inverse of
    ``packed_gather``, used by the serving hot-reload watcher to apply a
    checkpoint delta in place instead of re-reading the full table.

    ``ids`` must be sorted ascending and unique (delta files store
    ``np.flatnonzero`` output, which is both by construction).  Logical
    rows sharing a physical tile row occupy DISJOINT lane ranges, so a
    segment-SUM of per-occurrence (mask, payload) lane images merges them
    exactly; untouched neighbor lanes keep their current values through
    the mask.  One wide gather + one wide scatter (unique + sorted
    indices by construction — the round-5 declaration that skips XLA's
    sort-based scatter dedup)."""
    p = rows_per_tile(d)
    vp = packed.shape[0]
    flat = ids.reshape(-1).astype(jnp.int32)
    m = flat.shape[0]
    r = rows.reshape(m, d).astype(packed.dtype)
    slot = (flat % p).astype(jnp.int32)
    phys = jnp.minimum((flat // p).astype(jnp.int32), vp)
    pay128 = lane_spread(r, slot, p, d)
    mask128 = lane_spread(jnp.ones_like(r), slot, p, d)
    # Segment per physical row (ids sorted ⇒ phys sorted): disjoint-lane
    # sums merge the row's occupants; representatives get unique ascending
    # uphys exactly as packed_sparse_adagrad_update builds them.
    is_new = jnp.concatenate([jnp.ones((1,), bool), phys[1:] != phys[:-1]])
    seg = jnp.cumsum(is_new) - 1
    paysum = jax.ops.segment_sum(pay128, seg, num_segments=m)
    masksum = jax.ops.segment_sum(mask128, seg, num_segments=m)
    uphys = (jnp.int32(vp) + jnp.arange(m, dtype=jnp.int32)).at[seg].set(phys)
    cur = packed[jnp.minimum(uphys, vp - 1)]
    new = cur * (1 - masksum) + paysum
    return packed.at[uphys].set(
        new, mode="drop", unique_indices=True, indices_are_sorted=True
    )


def lane_spread(row_grads: jax.Array, slot: jax.Array, p: int, d: int) -> jax.Array:
    """[M, D] per-occurrence values -> [M, 128] tile rows with each
    value's D lanes at its slot offset — ONE one-hot broadcast pass
    ([M, P] ⊗ [M, D] reshaped), not P masked-slice passes over [M, 128]
    (measured: the slice-per-slot build is a visible share of the packed
    step at P=14)."""
    m = row_grads.shape[0]
    oh = jax.nn.one_hot(slot, p, dtype=row_grads.dtype)  # [M, P]
    g128 = (oh[:, :, None] * row_grads[:, None, :]).reshape(m, p * d)
    if p * d < LANES:
        g128 = jnp.pad(g128, ((0, 0), (0, LANES - p * d)))
    return g128


def packed_dense_grad(vp: int, ids: jax.Array, row_grads: jax.Array) -> jax.Array:
    """Dense [VP, 128] occurrence-summed gradient via ONE wide scatter-add.

    Duplicate ids sum in the scatter (in flat-occurrence order — the
    same order the stable-sorted segment-sum uses, so sums are
    bit-identical to the sorted path's); ids at or past vp·P act as drop
    sentinels.  This trades the sorted pipeline's 5 sparse M-row ops
    (argsort, permutation gather, segment-sum, RMW gather, second
    scatter) for one M-row scatter-add plus O(VP·128) dense traffic —
    measured 3.5× faster on the whole step at vocab 2^24 (DESIGN §6
    round-4 entry).
    """
    d = row_grads.shape[-1]
    p = rows_per_tile(d)
    flat = ids.reshape(-1)
    g = row_grads.reshape(flat.shape[0], d)
    slot = (flat % p).astype(jnp.int32)
    phys = (flat // p).astype(jnp.int32)
    g128 = lane_spread(g, slot, p, d)
    return jnp.zeros((vp, LANES), g.dtype).at[phys].add(g128, mode="drop")


def _adagrad_apply(cur, acc, G, lr, p: int, d: int):
    """(new_rows, new_acc) for one Adagrad application of occurrence-summed
    wide grads ``G`` to tile rows ``cur`` with accumulator ``acc`` of either
    granularity (trailing dim 128 = element, P = row).  The ONE place the
    packed Adagrad formulas live — the dense sweep and the compact RMW both
    call it, so their results are bit-identical by construction."""
    if acc.shape[-1] == LANES:  # element granularity
        acc2 = acc + G * G
        return cur - lr * G / jnp.sqrt(acc2), acc2
    if acc.shape[-1] != p:
        raise ValueError(
            f"accumulator trailing dim {acc.shape[-1]} is neither "
            f"{LANES} (element) nor P={p} (row)"
        )
    grow = G[:, : p * d].reshape(-1, p, d)
    acc2 = acc + jnp.sum(grow * grow, axis=-1)  # [*, P]
    # (lr·G)/sqrt — the same association order as optim's row-mode update,
    # so results are bit-identical, not just close.  Pad lanes divide by 1.
    denom = jnp.sqrt(acc2)[:, :, None] * jnp.ones((1, 1, d), cur.dtype)
    denom128 = jnp.pad(
        denom.reshape(-1, p * d), ((0, 0), (0, LANES - p * d)),
        constant_values=1.0,
    )
    return cur - lr * G / denom128, acc2


def packed_dense_adagrad_update(
    packed: jax.Array,
    accum_packed: jax.Array,
    ids: jax.Array,
    row_grads: jax.Array,
    lr: float,
):
    """Sparse Adagrad on the packed table via a DENSE gradient buffer.

    One wide scatter-add builds the occurrence-summed [VP, 128] gradient
    G, then a dense elementwise sweep applies Adagrad to the WHOLE
    table: untouched elements see G == 0 — `accum += 0²; param -= lr·0`
    is the exact identity — so the dense sweep changes nothing it
    shouldn't (the same zero-grad identity that makes whole-tile-row
    writes exact makes the whole-TABLE write exact).  O(VP·128) dense
    traffic replaces the sorted pipeline's sparse tail; use
    ``resolve_packed_update`` to fall back to the compact path when VP
    is so large the dense sweep (and the G buffer's memory) stops
    paying.

    ``accum_packed`` granularity is declared by its trailing dim:
    128 lanes = element accumulator (``pack_accum``), P slots = per-ROW
    scalar accumulator (``pack_accum_rows``) — `accum += ‖ΣG_row‖²`,
    one sqrt per logical row, the D×-smaller optimizer state the 10B-row
    regime needs (optim.py row mode; semantics matched exactly).
    """
    d = row_grads.shape[-1]
    p = rows_per_tile(d)
    G = packed_dense_grad(packed.shape[0], ids, row_grads)
    return _adagrad_apply(packed, accum_packed, G, lr, p, d)


def packed_compact_adagrad_update(
    packed: jax.Array,
    accum_packed: jax.Array,
    ids: jax.Array,
    row_grads: jax.Array,
    lr: float,
):
    """Sparse Adagrad via SORT-FREE compaction of the touched physical rows.

    The giant-vocab middle path between the dense sweep and the sorted
    tail (DESIGN §6 round-5 entry): the sorted tail pays an argsort over
    M occurrences plus a segment pipeline (measured 98.9k ex/s at vocab
    201M — descriptor-bound, 0.09% of HBM bandwidth), while the dense
    sweep pays a table-sized G buffer and O(VP·128) traffic (dies past
    DENSE_G_MAX_BYTES).  This path keeps the dense tail's scatter-ADD
    dedup but compacts the gradient buffer to K = min(VP, M) tile rows
    using a touched-row bitmap + prefix sum over [VP] — O(VP) 1-byte/4-byte
    1-D traffic, 128× less than the dense sweep — and NO sort:

      touched[phys] = 1                      1-D int8 scatter over [VP]
      slot = cumsum(touched)[phys] - 1       each touched row → dense slot
      G[slot] += g128                        wide scatter-add; duplicates
                                             sum in flat occurrence order,
                                             exactly as the dense G does
      RMW rows uphys[slot]                   wide gather → Adagrad → scatter

    ids at or past VP·P act as drop sentinels (slot = K, dropped), the
    same convention as the dense and sorted paths.  Works with BOTH
    accumulator granularities — element [VP, 128] and row [VP, P] — which
    makes it the giant-vocab path for row mode (the sorted tail cannot
    serve row mode).  The Adagrad formulas are shared with the dense
    sweep (``_adagrad_apply``), so results are bit-identical to
    ``packed_dense_adagrad_update`` on the same inputs (test-pinned).
    """
    d = row_grads.shape[-1]
    p = rows_per_tile(d)
    vp = packed.shape[0]
    flat = ids.reshape(-1)
    m = flat.shape[0]
    g = row_grads.reshape(m, d)
    slot_lane = (flat % p).astype(jnp.int32)
    phys = (flat // p).astype(jnp.int32)
    g128 = lane_spread(g, slot_lane, p, d)

    k = min(vp, m)  # exact worst case: every occurrence touches a new row
    touched = jnp.zeros((vp,), jnp.int8).at[phys].set(1, mode="drop")
    csum = jnp.cumsum(touched, dtype=jnp.int32)
    valid = phys < vp
    # Valid occurrences: csum[phys] ∈ [1, #touched] and #touched <= K, so
    # slot <= K-1.  Sentinels get slot K and drop from every scatter below.
    slot = jnp.where(valid, csum[jnp.minimum(phys, vp - 1)] - 1, k)
    G = jnp.zeros((k, LANES), g.dtype).at[slot].add(g128, mode="drop")
    # Slot s is the s-th touched physical row in ASCENDING phys order (csum
    # is monotone), and unused trailing slots get vp + s — so uphys is
    # strictly ascending and duplicate-free BY CONSTRUCTION.  Telling XLA
    # so (unique + sorted) skips the sort-based dedup it otherwise wraps
    # around every scatter (visible as a fused sort in the step's HLO —
    # DESIGN §6 round 5), which is most of the sorted tail's cost.
    uphys = (jnp.int32(vp) + jnp.arange(k, dtype=jnp.int32)).at[slot].set(
        phys, mode="drop"
    )
    safe = jnp.minimum(uphys, vp - 1)
    new, acc2 = _adagrad_apply(packed[safe], accum_packed[safe], G, lr, p, d)
    packed = packed.at[uphys].set(
        new, mode="drop", unique_indices=True, indices_are_sorted=True
    )
    accum_packed = accum_packed.at[uphys].set(
        acc2, mode="drop", unique_indices=True, indices_are_sorted=True
    )
    return packed, accum_packed


# Default ceiling for the dense-G buffer: beyond this the O(VP·128)
# sweep + the extra table-sized temporary lose to the sorted sparse
# tail (and to HBM).  2 GiB ≈ 4.2M physical rows ≈ 58M logical rows at
# P=14 — far above every benchmark config; the 134M+-row single-chip
# regime stays on the sorted path unless forced.
DENSE_G_MAX_BYTES = 2 << 30


def resolve_packed_update(update: str, vp: int, accum_trailing: int) -> str:
    """'auto' | 'dense' | 'compact' | 'sorted' -> the concrete update.

    auto: dense while the G buffer stays under DENSE_G_MAX_BYTES (the
    fastest tail where its O(VP·128) sweep fits — measured 3.5× sorted at
    vocab 2^24), else compact (sort-free touched-row compaction: O(M)
    buffers, O(VP) bitmap traffic — measured ~5× sorted at vocab 201M).
    Both serve BOTH accumulator granularities.  'sorted' stays available
    explicitly (element accumulator only) as the bit-parity reference and
    for A/B probes; auto never picks it."""
    if update not in ("auto", "dense", "compact", "sorted"):
        raise ValueError(
            f"unknown packed update {update!r} (auto | dense | compact | sorted)"
        )
    if update == "sorted":
        if accum_trailing != LANES:
            raise ValueError("packed_update=sorted requires the element accumulator")
        return "sorted"
    if update in ("dense", "compact"):
        return update
    return "dense" if vp * LANES * 4 <= DENSE_G_MAX_BYTES else "compact"


def pack_accum_rows(accum: jax.Array, d: int, init_value: float) -> jax.Array:
    """[V, 1] ROW-granularity accumulator -> [VP, P] (one scalar slot per
    logical row; pad slots carry ``init_value``, never zero — the dense
    sweep divides by sqrt of every slot)."""
    p = rows_per_tile(d)
    v = accum.shape[0]
    vp = packed_rows(v, d)
    flat = jnp.full((vp * p, 1), init_value, accum.dtype).at[:v].set(accum)
    return flat.reshape(vp, p)


def unpack_accum_rows(acc_packed: jax.Array, vocab: int, d: int) -> jax.Array:
    """[VP, P] packed row accumulator -> [V, 1] logical."""
    p = rows_per_tile(d)
    return acc_packed.reshape(acc_packed.shape[0] * p, 1)[:vocab]


def pack_accum_any(accum: jax.Array, d: int, init_value: float) -> jax.Array:
    """Pack a LOGICAL accumulator of either granularity — [V, D] element
    (→ [VP, 128]) or [V, 1] row (→ [VP, P]).  The trailing-dim sniff
    lives HERE, next to the packers whose convention it encodes; callers
    (trainer.pack_state, train_step.pack_sharded_on_device, ...) must
    not re-implement it."""
    if accum.shape[-1] == 1:
        return pack_accum_rows(accum, d, init_value)
    return pack_accum(accum, init_value)


def unpack_accum_any(acc_packed: jax.Array, vocab: int, d: int) -> jax.Array:
    """Inverse of pack_accum_any: [VP, 128] → [V, D] or [VP, P] → [V, 1].

    NOTE d == 1 makes P == LANES and the two conventions coincide — then
    both branches compute the same reshape-and-slice, so the ambiguity is
    harmless by construction, not by luck."""
    if acc_packed.shape[-1] == LANES and rows_per_tile(d) != LANES:
        return unpack_table(acc_packed, vocab, d)
    return unpack_accum_rows(acc_packed, vocab, d)


def packed_sparse_adagrad_update(
    packed: jax.Array,
    accum_packed: jax.Array,
    ids: jax.Array,
    row_grads: jax.Array,
    lr: float,
):
    """Sparse Adagrad on the packed table — one-pass lane-space dedup.

    ids: [...] logical ids (ids >= packed.shape[0] * rows_per_tile(D) act
    as drop sentinels — their physical row lands past the last packed row
    and the scatter drops it; the sharded update relies on this for
    unowned ids).  Returns (packed, accum_packed).  Per-element semantics match
    optim.sparse_adagrad_update with the element accumulator: every
    element sees the occurrence-summed gradient exactly once
    (duplicate ids land in the same lanes of the same physical segment
    and sum there); untouched elements see gradient 0 — the Adagrad
    identity — so whole-row writes are exact.
    """
    d = row_grads.shape[-1]
    p = rows_per_tile(d)
    vp = packed.shape[0]
    flat_ids = ids.reshape(-1)
    m = flat_ids.shape[0]
    g = row_grads.reshape(m, d)

    # Insert each occurrence's grad into its slot lanes: [M, 128].
    slot = (flat_ids % p).astype(jnp.int32)
    g128 = lane_spread(g, slot, p, d)

    # Sort occurrences by id => physical rows grouped; WIDE permutation
    # gather moves the [M, 128] payload (full-lane rows, fast path).
    # Sentinel phys CLAMPS to exactly vp: distinct far sentinels would
    # otherwise form separate segments whose written uphys values could
    # collide with the vp+slot trailing fill below, breaking the
    # unique+sorted declaration on the RMW scatters (undefined behavior).
    order = jnp.argsort(flat_ids)
    sphys = jnp.minimum((flat_ids[order] // p).astype(jnp.int32), vp)
    g128 = g128[order]

    # Segment-sum per physical row at full width.
    is_new = jnp.concatenate([jnp.ones((1,), bool), sphys[1:] != sphys[:-1]])
    seg = jnp.cumsum(is_new) - 1
    gsum = jax.ops.segment_sum(g128, seg, num_segments=m)  # [M, 128]
    # Segment representative WITHOUT segment_max (measured ~9 ms as a 1-D
    # scatter-max): every occurrence in a segment writes the SAME sphys
    # value, so a plain scatter-set is correct regardless of which
    # duplicate wins; unwritten trailing slots get vp + slot — ascending
    # past-the-end sentinels, so uphys is strictly ascending and
    # duplicate-free (seg is monotone over sorted sphys) and the RMW
    # scatters can declare unique + sorted indices, skipping XLA's
    # sort-based scatter dedup (DESIGN §6 round 5).
    uphys = (jnp.int32(vp) + jnp.arange(m, dtype=jnp.int32)).at[seg].set(sphys)

    # RMW: one wide gather + elementwise Adagrad + one wide scatter each.
    # No validity masking needed: sentinel slots carry gsum == 0 (the
    # Adagrad identity, new == cur) and their scatter drops anyway.
    safe = jnp.minimum(uphys, vp - 1)
    cur = packed[safe]
    acc = accum_packed[safe]
    acc2 = acc + gsum * gsum
    new = cur - lr * gsum / jnp.sqrt(acc2)
    packed = packed.at[uphys].set(
        new, mode="drop", unique_indices=True, indices_are_sorted=True
    )
    accum_packed = accum_packed.at[uphys].set(
        acc2, mode="drop", unique_indices=True, indices_are_sorted=True
    )
    return packed, accum_packed


# Concrete update strategy -> implementation.  The ONE mapping every
# dispatcher uses (trainer, sharded allgather, routed alltoall) — its keys
# are exactly resolve_packed_update's outputs, so a new strategy is added
# here and in the resolver, nowhere else.
PACKED_UPDATE_FNS = {
    "dense": packed_dense_adagrad_update,
    "compact": packed_compact_adagrad_update,
    "sorted": packed_sparse_adagrad_update,
}


# --- fused row-accumulator layout (round 5) -------------------------------
#
# WHY (the update-ops probe of round 5, old installation): random wide gathers/scatters on this chip are
# DESCRIPTOR-bound — a [K, 256] gather costs the same as [K, 128] (10.5 vs
# 10.0 ms at K=639k) — so the sparse tail's cost is the NUMBER of random
# row ops, not their bytes.  The separate-accumulator RMW needs 4 of them
# (gather cur, gather acc, scatter new, scatter acc2); fusing the ROW
# accumulator scalar into each logical row's own tile-row slot (stride
# D+1: D row lanes + 1 accumulator lane per slot, P = 128 // (D+1) slots)
# collapses the RMW to ONE gather + ONE scatter over a single array, and
# shrinks total optimizer+param state to ~(D+1)/D of the table (the 10B-row
# regime's pairing).  Semantics are EXACTLY the row-granularity Adagrad
# (optim.py row mode: accum += ||sum-G row||², one sqrt per row) — only the
# storage address of the scalar moved.  Checkpoints stay LOGICAL ([V, D]
# table + [V, 1] accumulator), so fused runs interchange checkpoints with
# rows-layout and packed row-mode runs.


def fused_rows_per_tile(d: int) -> int:
    """Slots per 128-lane row in the fused layout: P = 128 // (D + 1)."""
    if d + 1 > LANES:
        raise ValueError(f"fused layout needs D + 1 <= {LANES}, got D={d}")
    return LANES // (d + 1)


def fused_packed_rows(vocab: int, d: int) -> int:
    return -(-vocab // fused_rows_per_tile(d))


def pack_fused(
    table: jax.Array, accum: jax.Array, init_value: float
) -> jax.Array:
    """[V, D] table + [V, 1] row accumulator -> [VPf, 128] fused rows.

    Slot s of a physical row occupies lanes [s·(D+1), s·(D+1)+D) for the
    parameter row and lane s·(D+1)+D for its accumulator scalar.  Pad
    slots and tail lanes carry ``init_value`` in the accumulator position
    and 0 in row positions (the dense sweep divides by sqrt of every
    accumulator lane, and zero-grad identity keeps pads inert)."""
    if accum.shape[-1] != 1:
        raise ValueError(
            f"fused layout packs a ROW accumulator [V, 1], got {accum.shape}"
        )
    merged = jnp.concatenate([table, accum.astype(table.dtype)], axis=-1)
    d1 = merged.shape[-1]
    p = fused_rows_per_tile(table.shape[-1])  # raises the clear D+1 > 128 error
    vp = -(-table.shape[0] // p)
    flat = jnp.full((vp * p, d1), 0.0, table.dtype).at[:, d1 - 1].set(init_value)
    flat = flat.at[: table.shape[0]].set(merged)
    out = jnp.full((vp, LANES), init_value, table.dtype)
    return out.at[:, : p * d1].set(flat.reshape(vp, p * d1))


def unpack_fused(fused: jax.Array, vocab: int, d: int):
    """[VPf, 128] fused -> ([V, D] table, [V, 1] accumulator)."""
    p = fused_rows_per_tile(d)
    d1 = d + 1
    flat = fused[:, : p * d1].reshape(fused.shape[0] * p, d1)[:vocab]
    return flat[:, :d], flat[:, d:]


def fused_gather(fused: jax.Array, ids: jax.Array, d: int) -> jax.Array:
    """rows[..., D] for logical ``ids`` from a fused table (wide gather +
    static masked slot extraction, accumulator lanes skipped)."""
    p = fused_rows_per_tile(d)
    d1 = d + 1
    with jax.named_scope("fm.gather"):
        phys = ids // p
        slot = ids % p
        rows128 = fused[phys]
        out = jnp.zeros(ids.shape + (d,), fused.dtype)
        for s in range(p):
            piece = rows128[..., s * d1 : s * d1 + d]
            out = out + jnp.where((slot == s)[..., None], piece, 0)
    return out


def _fused_apply(cur128, G128, lr, p: int, d: int):
    """One row-granularity Adagrad application on fused tile rows.

    cur128/G128: [*, 128] (G's accumulator lanes are zero by
    construction).  Returns the updated [*, 128] rows.  Formulas match
    optim.py row mode exactly: acc2 = acc + Σ g²; new = row − lr·g/√acc2."""
    d1 = d + 1
    used = p * d1
    view = cur128[..., :used].reshape(cur128.shape[:-1] + (p, d1))
    gview = G128[..., :used].reshape(G128.shape[:-1] + (p, d1))
    grow = gview[..., :d]
    acc2 = view[..., d] + jnp.sum(grow * grow, axis=-1)
    new_rows = view[..., :d] - lr * grow / jnp.sqrt(acc2)[..., None]
    new = jnp.concatenate([new_rows, acc2[..., None]], axis=-1)
    new = new.reshape(cur128.shape[:-1] + (used,))
    return jnp.concatenate([new, cur128[..., used:]], axis=-1)


def fused_grad128(ids: jax.Array, row_grads: jax.Array, p: int):
    """Per-occurrence [M, 128] tile rows with grads at fused slot offsets
    (accumulator lanes zero), plus the physical row per occurrence."""
    d = row_grads.shape[-1]
    flat = ids.reshape(-1)
    g = row_grads.reshape(flat.shape[0], d)
    slot = (flat % p).astype(jnp.int32)
    phys = (flat // p).astype(jnp.int32)
    gpad = jnp.pad(g, ((0, 0), (0, 1)))  # zero accumulator lane
    return lane_spread(gpad, slot, p, d + 1), phys


def fused_dense_adagrad_update(
    fused: jax.Array, ids: jax.Array, row_grads: jax.Array, lr: float
) -> jax.Array:
    """Fused-layout Adagrad via the dense-G sweep (small-vocab regime):
    one wide scatter-add into [VPf, 128], one contiguous pass over the
    fused array.  Zero-grad slots see acc2 == acc and row − 0 — the exact
    identity, so sweeping everything is exact (pad accumulator lanes hold
    init_value > 0, never 0)."""
    d = row_grads.shape[-1]
    p = fused_rows_per_tile(d)
    vp = fused.shape[0]
    g128, phys = fused_grad128(ids, row_grads, p)
    G = jnp.zeros((vp, LANES), g128.dtype).at[phys].add(g128, mode="drop")
    return _fused_apply(fused, G, lr, p, d)


def _fused_compact_k(fused, g128, phys, csum, lr, p, d, k):
    """The compaction + RMW for one static capacity ``k``: slots beyond
    k-1 drop from every scatter (only reachable when #touched > k — the
    caller's overflow cond guarantees the exact-capacity branch runs)."""
    vp = fused.shape[0]
    valid = phys < vp
    slot = jnp.where(valid, csum[jnp.minimum(phys, vp - 1)] - 1, k)
    slot = jnp.minimum(slot, k)  # overflow slots -> drop sentinel
    G = jnp.zeros((k, LANES), g128.dtype).at[slot].add(g128, mode="drop")
    uphys = (jnp.int32(vp) + jnp.arange(k, dtype=jnp.int32)).at[slot].set(
        phys, mode="drop"
    )
    cur = fused[jnp.minimum(uphys, vp - 1)]
    new = _fused_apply(cur, G, lr, p, d)
    return fused.at[uphys].set(
        new, mode="drop", unique_indices=True, indices_are_sorted=True
    )


def fused_compact_adagrad_update(
    fused: jax.Array, ids: jax.Array, row_grads: jax.Array, lr: float,
    k_cap: int = 0,
) -> jax.Array:
    """Fused-layout Adagrad via sort-free touched-row compaction — the
    giant-vocab production tail: bitmap + prefix-sum compaction (as
    packed_compact_adagrad_update), then ONE wide gather + ONE wide
    scatter (unique + sorted indices by construction) instead of the
    separate-accumulator path's four random row ops.

    ``k_cap`` > 0 additionally CAPS the compacted buffer below the exact
    worst case min(VP, M): the RMW then processes k_cap rows instead of M
    (CTR ids are Zipf — measured ~170k unique physical rows per 639k
    occurrences — so the exact cap wastes ~3× the RMW's descriptor-bound
    row ops).  Correctness is unconditional: the touched count is known
    from the prefix sum, and a batch that overflows the cap takes the
    exact-capacity branch under ``lax.cond`` — never a dropped update.
    Skew helps, uniform ids just fall back every step (the cond prices
    one compare + both compiled branches, not wrong results).  Results
    are numerically (not bitwise) equal to k_cap=0: XLA's scatter-add
    associates duplicate contributions in a shape-dependent order, so a
    smaller G buffer can sum the same addends differently (~1e-5;
    test-pinned allclose)."""
    d = row_grads.shape[-1]
    p = fused_rows_per_tile(d)
    vp = fused.shape[0]
    g128, phys = fused_grad128(ids, row_grads, p)
    m = phys.shape[0]

    k_full = min(vp, m)
    touched = jnp.zeros((vp,), jnp.int8).at[phys].set(1, mode="drop")
    csum = jnp.cumsum(touched, dtype=jnp.int32)
    if k_cap <= 0 or k_cap >= k_full:
        return _fused_compact_k(fused, g128, phys, csum, lr, p, d, k_full)
    n_touched = csum[-1]
    return jax.lax.cond(
        n_touched <= k_cap,
        lambda f: _fused_compact_k(f, g128, phys, csum, lr, p, d, k_cap),
        lambda f: _fused_compact_k(f, g128, phys, csum, lr, p, d, k_full),
        fused,
    )


def resolve_fused_update(update: str, vp: int) -> str:
    """'auto' | 'dense' | 'compact' -> the concrete fused-layout tail.

    Same size rule as resolve_packed_update; 'sorted' has no fused
    implementation (the compact path subsumes it — no sort to keep)."""
    if update == "sorted":
        raise ValueError(
            "packed_update=sorted has no fused-layout implementation "
            "(use auto, dense or compact with adagrad_accumulator=fused)"
        )
    if update not in ("auto", "dense", "compact"):
        raise ValueError(
            f"unknown packed update {update!r} (auto | dense | compact)"
        )
    if update != "auto":
        return update
    return "dense" if vp * LANES * 4 <= DENSE_G_MAX_BYTES else "compact"


FUSED_UPDATE_FNS = {
    "dense": fused_dense_adagrad_update,
    "compact": fused_compact_adagrad_update,
}


def apply_fused_update(
    fused: jax.Array, ids: jax.Array, row_grads: jax.Array, lr: float,
    mode: str, k_cap: int = 0,
) -> jax.Array:
    """The ONE fused-tail dispatch (mode -> dense | compact with its cap).
    Every fused apply site (local trainer, allgather shard update, routed
    alltoall update) calls this, so the tails cannot silently diverge."""
    if mode == "compact":
        return fused_compact_adagrad_update(fused, ids, row_grads, lr, k_cap)
    if mode != "dense":
        raise ValueError(f"unknown fused update mode {mode!r} (dense | compact)")
    return fused_dense_adagrad_update(fused, ids, row_grads, lr)
