"""Adagrad with a sparse, dedup-on-device update path.

Capability parity with the reference's training update
(`renyi533/fast_tffm` :: model-graph builder: `tf.train.AdagradOptimizer`
whose sparse gradient path scatter-adds into the block-partitioned
parameter variables).  Semantics mirror TF Adagrad:

    accum += g²          (accum initialized to init_accumulator_value)
    param -= lr * g / sqrt(accum)

The sparse step is the BASELINE.json "dense-over-sparse optimizer step":
gradients arrive per *gathered occurrence* ``[batch, nnz, D]``; occurrences
of the same row id are summed on device (sort + segment-sum — static
shapes, no `jnp.unique`), then each unique row is touched exactly once:
the accumulator's row is gathered, updated and scatter-set, and the
table's row takes ``-lr·g/√accum`` by ONE scatter-add (its old value is
never gathered).  Touching each row once matters: Adagrad is not linear in
g (accum += g² must see the *summed* gradient, and duplicate scatter
targets would race).  Where the batch touches so many sub-tile rows that
streaming the whole table costs less (``rows_tail_form``), the same update
runs as one in-place kernel sweep instead (ops/pallas_tail.py, PR 30).
The form is chosen BEFORE the dedup (PR 32), because the two want different
inputs of one algorithm: row operations want unique ids with one summed
gradient each (``dedup_rows``); the sweep's contraction sums a row's
occurrences itself and wants them in id order, no more
(``occurrences_by_id``).  They share the sort (``sort_ids``).

Row descriptors (PR 27; PERF.md §6 has the chip's readings).  On the TPU a
row narrower than a 128-lane tile is laid out with the ROW INDEX along the
lanes, so a scatter of one such row is a masked read-modify-write of single
lanes, four times a gather of the same row.  Where this module chooses the
buffer's shape (``dedup_rows``' segment sum) it therefore works on rows
padded to whole tiles; where it does not (the ``[V, D]`` table and
accumulator of the rows layout, whose shapes checkpoints and the serving
replica share) it has two forms.  Row by row it issues the fewest row
operations the math allows and declares what it knows about the indices —
ascending, unique — which costs nothing on narrow rows (100 ns a scatter
whatever is declared) and spares XLA a hidden sort on wide ones.  As a
sweep (PR 30) it never addresses a row: the lane-major ``[V, D]`` buffer IS
its transpose ``[D, V]`` row-major, a Pallas kernel walks that view block
by block in place and writes whole tile columns, and the choice between
the two is ``rows_tail_form``'s, from the shapes.

Accumulator granularity: the accumulator array's trailing dim selects the
variant — ``[V, D]`` is TF-Adagrad's per-element accumulator (parity
default), ``[V, 1]`` is a per-ROW scalar accumulator
(``accum += ‖g_row‖²``, one sqrt per row, broadcast over the row; the cfg
``adagrad_accumulator = row`` opt-in).  What the row variant buys is
OPTIMIZER-STATE MEMORY: accumulator HBM shrinks D× (at a 10B-parameter
table the element accumulator doubles memory; row cuts the optimizer
state to ~1/(1+k)).  Measured speed-neutral on one chip — the update's
gathers are descriptor-bound, not byte-bound (DESIGN.md §6) — and the
step size is coarser (grouped-AdaGrad-style), so element stays the
default.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "AdagradState",
    "init_adagrad",
    "dense_adagrad_update",
    "sparse_adagrad_update",
    "dedup_rows",
    "distinct_sentinels",
    "segment_sum_lanes",
    "rows_tail_form",
    "describe_rows_tail",
    "rows_tail_profile",
    "sort_ids",
    "occurrences_by_id",
    "occurrences_permutation",
]


class AdagradState(NamedTuple):
    accum: Any  # pytree mirroring the tracked parameter pytree


def init_adagrad(param, init_accumulator_value: float) -> AdagradState:
    return AdagradState(
        jax.tree.map(lambda p: jnp.full_like(p, init_accumulator_value), param)
    )


def init_table_adagrad(
    table: jax.Array, init_accumulator_value: float, accumulator: str = "element"
) -> AdagradState:
    """Accumulator for the sparse table: ``element`` ([V, D], TF parity) or
    ``row`` ([V, 1], grouped accumulator — see module docstring)."""
    if accumulator in ("row", "fused"):
        # "fused" has row-granularity SEMANTICS; the fused STORAGE happens
        # at pack time (ops.packed_table.pack_fused) — logically it is the
        # same [V, 1] accumulator.
        return AdagradState(
            jnp.full((table.shape[0], 1), init_accumulator_value, table.dtype)
        )
    if accumulator != "element":
        raise ValueError(
            f"unknown adagrad accumulator {accumulator!r} (element | row | fused)"
        )
    return init_adagrad(table, init_accumulator_value)


def accum_sq(accum: jax.Array, gsum: jax.Array) -> jax.Array:
    """g² in the granularity the accumulator's shape declares."""
    if accum.shape[-1] == 1 and gsum.shape[-1] != 1:
        return jnp.sum(gsum * gsum, axis=-1, keepdims=True)  # row mode
    return gsum * gsum  # element mode


def dense_adagrad_update(param, state: AdagradState, grad, lr: float, decay: float = 1.0):
    """Plain Adagrad over a parameter pytree (DeepFM's MLP head).

    ``decay`` γ < 1 is time-decayed Adagrad (``accum = γ·accum + g²``,
    RMSProp-shaped) — the online-learning knob that keeps old gradient
    history from freezing the step size on a moving distribution.  γ=1.0
    is a TRACE-TIME branch back to the exact classic expression, so the
    default path's XLA program (and its bits) are untouched."""
    if decay != 1.0:
        accum = jax.tree.map(
            lambda a, g: decay * a + g * g, state.accum, grad
        )
    else:
        accum = jax.tree.map(lambda a, g: a + g * g, state.accum, grad)
    new_param = jax.tree.map(
        lambda p, g, a: p - lr * g / jnp.sqrt(a), param, grad, accum
    )
    return new_param, AdagradState(accum)


# One vreg row: a [m, w] f32 buffer with w a multiple of this is laid out
# row-major on the TPU and a row is a whole-tile descriptor; any narrower w
# is laid out with m along the lanes, and a scatter of one row is a masked
# read-modify-write of single lanes (PERF.md §6, PR 27: the segment sum of
# 2.56M rows took 280 ms at 9 lanes, 284 / 333 / 457 at 16 / 32 / 64, and
# 25 at 128).
_LANES = 128
# The wide segment sum holds two [m, lanes] temporaries (the padded rows
# and their sums).  A quarter of the smallest HBM this runs on (16 GiB, one
# v5e chip) is what a step's temporaries may take beside a table and an
# accumulator that fill it by half; past that the narrow form stays.
_WIDE_SEGMENT_SUM_MAX_BYTES = 4 << 30


def segment_sum_lanes(m: int, d: int) -> int:
    """Row width ``dedup_rows`` sums ``m`` float32 rows of ``d`` at: ``d``
    padded to whole 128-lane rows, or ``d`` itself where the rows are
    tile-wide already or the two padded temporaries would pass the ceiling
    above."""
    lanes = -(-d // _LANES) * _LANES
    if lanes == d or 2 * m * lanes * 4 > _WIDE_SEGMENT_SUM_MAX_BYTES:
        return d
    return lanes


def distinct_sentinels(num_rows: int, m: int, dtype=jnp.int32) -> bool:
    """Whether ``dedup_rows``' trailing slots can each carry a drop id of
    their own (``num_rows + slot`` must fit the id type).  Only then are its
    ``uids`` UNIQUE as well as ascending, which is what a tail may tell its
    scatters (``unique_indices=True`` over repeated sentinels would be a
    false statement, dropped rows or not)."""
    return num_rows + m - 1 <= jnp.iinfo(dtype).max


# What the rows layout's tail costs in its two forms on a TPU v5e, read on
# 2^26 rows of 9 floats under batches of 80K to 1.28M ids (PERF.md §6, PR 32,
# each side with the front it now has: the rows' with its segment sum, the
# sweep's without; PR 27 read the row primitives alone: scatter-set 100 ns,
# scatter-add 100, gather 23 a row).  Row by row the tail costs 224 ns an id
# more than the sweep does (19 / 37 / 75 / 149 / 302 ms against 32 / 33 / 34
# / 38 / 46 at 80K / 160K / 319K / 639K / 1.28M ids; PR 30 read 208 with the
# segment sum on both sides).  The sweep pays for the table instead: both
# arrays read and written once in their padded lane-major layout, 17.2 GB in
# 30.7 ms with few ids (560 GB/s; an elementwise XLA pass over the same
# bytes takes 27, the HBM's 819 GB/s would take 21).
_ROWS_OVER_SWEEP_NS = 224
_SWEEP_BYTES_PER_S = 560e9
# Columns the sweep's permutation carries through the sort of the ids as
# operands (PERF.md §6, PR 32, 2,555,904 ids on a TPU v5e, the whole path
# from the row gradients to the kernel's operand).  Nine columns: the stable
# sort of key and columns 25.2 ms (22.2 unstable), against 64.1 for the
# 2-operand sort and a gather of the 36-byte rows by its order (23 ns a row:
# nine single-lane accesses) and 39.9 for a gather of rows padded to a whole
# tile (2.6 GB of temporaries).  Seventeen: 44.7 against 87.0.  But the sort
# COMPILES in 19 s with 2 operands, 115 s with 10 (72 unstable) and 284 s
# with 18, once for every batch shape, so it carries up to one 16-row group
# of the kernel's operand.
_SORT_OPERAND_COLUMNS = 16
# Wider rows are gathered in the order of one sort of the ids with their
# positions.  A ``[M, D]`` float32 buffer narrower than a tile is held
# lane-major, ``D`` rounded up to sublanes of 8 by ``M`` lanes, so a row is
# ``D`` single lanes: cheap where XLA keeps the buffer in the VMEM
# (``fm3_k30_kdd12``'s 720,896 rows of 31, 92 MB, compiled for a v5e into
# memory space 1: 9.8 ns a row in its step) and not where it is past the
# VMEM's 128 MiB and stays in HBM (``fm16_criteo_tiered``'s 2,555,904 rows
# of 17, 245 MB: 30.4 ns a row).  There the rows are padded to one 128-lane
# tile, held row-major, and a gather moves each as one 512-byte row.  Read
# standalone on a TPU v5e (PERF.md §6; medians of eight): 2,555,904
# rows of 17 in 42.1 ms with the sort, against 83.0 narrow; the first
# 1,284,384 of them (a row shard's bounded tail) 19.1 against 27.2; but
# 720,896 rows of 31 12.5 against 9.0.  Sorts that carried the 17 columns
# ran in 40.9-41.1 ms whether one, two of 9 and 8 or three of 6: XLA merges
# sorts on one key into one, which compiles in 282-304 s.
_VMEM_BYTES = 128 * 2**20


def occurrences_permutation(d: int, m: int) -> str:
    """How ``occurrences_by_id`` brings the ``m`` gradients ``d`` wide that
    one sort of the ids orders to id order: ``"sort operands"`` (the columns
    ride that sort), ``"tile-wide row gather"`` (the rows, padded to one
    128-lane tile, gathered in its order) or ``"row gather"`` (the rows as
    they are, which ``dedup_rows`` does at every width: its segment sum
    wants rows).  A trace-time function of the shapes, as ``rows_tail_form``
    is: ``fm8_criteo`` (9) and ``deepfm10_criteo`` (11) take the sort
    operands, ``fm16_criteo_tiered`` and ``fm16_criteo_row4``'s shard tails
    (17 by 2,555,904: 245 MB) the tile-wide gather, ``fm3_k30_kdd12`` (31
    by 720,896: 92 MB) the row gather."""
    if d <= _SORT_OPERAND_COLUMNS:
        return "sort operands"
    if d < _LANES and -(-d // 8) * 8 * m * 4 > _VMEM_BYTES:
        return "tile-wide row gather"
    return "row gather"


def rows_tail_form(
    num_rows: int, m: int, d: int, accum_cols: int, backend: str | None = None
) -> str:
    """Which form ``sparse_adagrad_update`` takes at these shapes when
    nobody says: ``"sweep"`` (ops.pallas_tail.sweep_adagrad_update, one
    in-place pass over table and accumulator) or ``"rows"`` (the XLA row
    operations).  A trace-time function of the shapes and the backend:

      * only a TPU takes the sweep (anywhere else the kernel would run
        interpreted inside every train step);
      * only rows under one 128-lane tile: those are held lane-major, a row
        operation on them is a masked single-lane access, and the transposed
        view the kernel takes is a bitcast.  From 128 lanes up a row is a
        whole-tile descriptor at 12-15 ns and the row operations stay;
      * only where the sweep's bytes (both arrays, read and written, rows
        padded to whole sublanes of 8) take less time at the rate the sweep
        reaches than the batch's ``m`` ids pay for going row by row.
        ``fm8_criteo`` (2^26 rows of 9, 65,536 x 39 ids): 31 ms against 570,
        the sweep; the same table under 1,024 x 39 ids: 31 against 9, the
        rows; the two cross at 137K ids, as read (136K);
      * only where the sweep's work list fits the scalar memory
        (ops.pallas_tail.sweep_fits: past about 15M ids a batch it does not).
    """
    if (backend or jax.default_backend()) != "tpu" or d >= _LANES:
        return "rows"
    from fast_tffm_tpu.ops.pallas_tail import sweep_fits

    if not sweep_fits(num_rows, d, m):
        return "rows"
    sublanes = lambda c: -(-c // 8) * 8
    sweep_s = 2 * num_rows * 4 * (sublanes(d) + sublanes(accum_cols)) / _SWEEP_BYTES_PER_S
    return "sweep" if sweep_s < m * _ROWS_OVER_SWEEP_NS * 1e-9 else "rows"


def rows_tail_profile(
    num_rows: int, m: int, d: int, form: str = "rows", sorted_ids: int | None = None
) -> dict:
    """The tail's trace-time choices at these shapes, as the step's
    ``kind=profile`` record carries them: ``tail_form``; how a row's
    duplicates are summed (``tail_duplicates``: ``segment_sum`` on rows
    ``segment_sum_lanes`` wide ahead of the row operations, or ``kernel``:
    the sweep's own contraction, and then no segment sum runs and its lanes
    are null); how the gradients reach id order (``tail_permutation``:
    ``occurrences_permutation``); the sweep's ``tail_block_lanes``.
    ``sorted_ids``: the ids sorted, where the tail keeps the first ``m`` of
    them (a row shard's bounded tail, ``sparse_adagrad_update``'s
    ``keep``); ``None``: ``m``."""
    if form == "sweep":
        from fast_tffm_tpu.ops.pallas_tail import sweep_block_lanes

        return dict(
            tail_form="sweep", tail_duplicates="kernel", segment_sum_lanes=None,
            tail_permutation=occurrences_permutation(d, m if sorted_ids is None else sorted_ids),
            tail_block_lanes=sweep_block_lanes(num_rows, d),
        )
    return dict(
        tail_form="rows", tail_duplicates="segment_sum",
        segment_sum_lanes=segment_sum_lanes(m, d), tail_permutation="row gather",
        tail_block_lanes=None,
    )


def describe_rows_tail(
    num_rows: int, m: int, d: int, form: str = "rows", sorted_ids: int | None = None
) -> str:
    """The form ``sparse_adagrad_update`` takes at these shapes, for the
    trainer's start-up log (it is a trace-time choice, so it is said once)."""
    p = rows_tail_profile(num_rows, m, d, form, sorted_ids)
    if form == "sweep":
        block = p["tail_block_lanes"]
        return (
            f"pallas rows sweep (block {block} lanes, {-(-num_rows // block)} "
            f"blocks; duplicates summed in the kernel, occurrences brought to "
            f"id order as {p['tail_permutation']}, row width {d})"
        )
    hints = "sorted+unique" if distinct_sentinels(num_rows, m) else "sorted"
    return (
        f"xla rows (segment sum on {p['segment_sum_lanes']}-lane rows (row width {d}), "
        f"table updated by one scatter-add, row ops declared {hints})"
    )


def sort_ids(ids: jax.Array, num_rows: int, keep: int | None = None):
    """The first half of both forms of the tail: ONE stable sort of the
    batch's ids, clamped to ``num_rows`` (every drop id becomes the first one).

    Returns ``(sid [M], order [M])``: the ids ascending, repeats and all, and
    each one's position in ``ids`` (``ids[argsort(ids)]`` would be an 18 ms
    gather of 2.56M ints more).  Stable, so occurrences of one id keep the
    batch's order and every sum over them is the same from run to run.

    ``keep``: only the first ``keep`` entries of the order are returned, so
    that nothing after the sort moves a row past them.  The drop ids sort
    last: a caller who knows that at most ``keep`` of its ids are under
    ``num_rows`` (a row shard among all the chips' slots,
    parallel.embedding.apply_shard_adagrad) loses nothing but drop ids.  The
    ONE place the rows' forms honour it; ``None`` is the program without it."""
    sid, order = lax.sort_key_val(
        jnp.minimum(ids, num_rows), jnp.arange(ids.shape[0], dtype=jnp.int32),
        is_stable=True,
    )
    if keep is not None:
        sid, order = sid[:keep], order[:keep]
    return sid, order


def dedup_rows(
    ids: jax.Array, row_grads: jax.Array, num_rows: int, keep: int | None = None
):
    """Sum per-occurrence row gradients over duplicate ids.

    Args:
      ids:       [M] int row ids (flattened batch×nnz), may repeat; any id
                 ``>= num_rows`` is a caller's drop sentinel (the sharded
                 updates dedup all-gathered ``uids`` a second time).
      row_grads: [M, D] gradient per occurrence.
      num_rows:  table row count V (the first drop id).
      keep:      ``sort_ids``' bound: every ``M`` below is ``keep`` then.

    Returns:
      (uids [M], gsum [M, D]): unique ids, ASCENDING, with their summed
      gradients in the leading segments.  Trailing slots carry zero
      gradients and out-of-range ids (→ scattered with mode='drop'):
      ``num_rows + slot``, distinct and still ascending, where that fits
      the id type (``distinct_sentinels``), else ``num_rows`` repeated.  The
      callers' own sentinels collapse into ONE segment with id ``num_rows``,
      which sorts before every trailing slot's id, so the whole of ``uids``
      is sorted and (where distinct) unique.

    What row operations need (the XLA rows tail, the shards that exchange
    unique sums); the sweep sums duplicates itself and takes
    ``occurrences_by_id``.  No row operation here is a partial-lane scatter:
    the segment sum runs on rows padded to whole 128-lane tiles
    (``segment_sum_lanes``; same op, same addends in the same order as the
    ``D``-wide form, so the sums are the same floats), and ``uids`` comes
    from a second sort of the ids, not from a scatter-set by segment (5 ms
    against 13, PERF.md §6, PR 27).
    """
    d = row_grads.shape[1]
    with jax.named_scope("fm.dedup"):
        sid, order = sort_ids(ids, num_rows, keep)
        m = sid.shape[0]
        sg = row_grads[order]
        is_new = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
        seg = jnp.cumsum(is_new) - 1  # [M] segment index per occurrence
        lanes = segment_sum_lanes(m, d)
        if lanes != d:
            sg = jnp.pad(sg, ((0, 0), (0, lanes - d)))
        gsum = jax.ops.segment_sum(
            sg, seg, num_segments=m, indices_are_sorted=True
        )[:, :d]
        # Each segment's first occurrence keeps its id and every other slot
        # takes a drop id above all of them: sorted, the unique ids lead.
        drop = jnp.asarray(num_rows, sid.dtype)
        if distinct_sentinels(num_rows, m, sid.dtype):
            drop = drop + jnp.arange(m, dtype=sid.dtype)
        uids = jnp.sort(jnp.where(is_new, sid, drop))
    return uids, gsum


def occurrences_by_id(
    ids: jax.Array, row_grads: jax.Array, num_rows: int, keep: int | None = None
):
    """The batch's occurrences in id order, duplicates NOT summed: what the
    sweep takes (ops.pallas_tail.sweep_adagrad_update sums a row's
    occurrences in its own contraction, so the segment sum, its two
    128-lane temporaries and the second sort of ``dedup_rows`` have nothing
    to do here).

    Returns ``(sid [M], gt [D, M])``: ``sort_ids``' ids (ascending, repeats
    and all, drop ids clamped to ``num_rows``) and the gradients in that
    order, COLUMN BY COLUMN along the lanes — the layout the kernel reads
    and the one a ``[M, D]`` float32 buffer with ``D`` under a tile has on
    the TPU anyway.  So nothing here needs an ``[M, D]`` row, and the
    permutation has three forms (``occurrences_permutation``): the ``D``
    columns ride the ONE stable sort of the ids as its operands, or the rows
    are gathered in the order that sort returns, padded to one whole tile
    first or as they are.  ``keep`` (``sort_ids``): ``M`` is ``keep``, the
    gather stops there (the sorted columns are cut there)."""
    d = row_grads.shape[1]
    form = occurrences_permutation(d, ids.shape[0])
    with jax.named_scope("fm.dedup"):
        if form == "sort operands":
            sid, *cols = lax.sort(
                (jnp.minimum(ids, num_rows), *row_grads.T), num_keys=1, is_stable=True
            )
            gt = jnp.stack(cols)
            return (sid, gt) if keep is None else (sid[:keep], gt[:, :keep])
        sid, order = sort_ids(ids, num_rows, keep)
        if form == "tile-wide row gather":
            # XLA folds a pad, the gather and the slice back into the narrow
            # gather; the barrier keeps the tile-wide rows it is to read.
            wide = lax.optimization_barrier(jnp.pad(row_grads, ((0, 0), (0, _LANES - d))))
            return sid, wide[order][:, :d].T
        return sid, row_grads[order].T


def sparse_adagrad_update(
    table: jax.Array,
    state: AdagradState,
    ids: jax.Array,
    row_grads: jax.Array,
    lr: float,
    decay: float = 1.0,
    form: str | None = None,
    keep: int | None = None,
):
    """Sparse Adagrad step on a ``[V, D]`` table.

    ids: [...] int ids; row_grads: [..., D] matching occurrence grads.
    ``form`` ``"rows"``: only the unique touched rows are read and written:
    one gather and one scatter-set of the accumulator's rows (the new value
    sets the step size), ONE scatter-add into the table (``p + (-x)`` is
    ``p - x``; the old row is never gathered).  The three declare what
    ``dedup_rows`` guarantees about ``uids`` — ascending, and unique where
    the trailing drop ids are distinct (a trace-time test on shapes).
    ``form`` ``"sweep"``: the same update as one in-place kernel pass over
    table and accumulator (ops.pallas_tail.sweep_adagrad_update), which
    sums a row's occurrences itself: no ``dedup_rows`` runs, the batch's
    occurrences go in in id order (``occurrences_by_id``).  ``None`` (every
    driver; a test or ``chip_smoke.py`` names a form to run it on any
    backend): ``rows_tail_form``, asked before anything is sorted.

    ``keep`` (a row shard's tail; ``None`` everywhere else, and then the
    program is the one without it): the caller's word that at most ``keep``
    of the ids are rows of this table, the rest drop ids from ``V`` up.  Only
    the first ``keep`` entries of the sort's order are permuted, summed and
    handed on (``sort_ids``), and ``rows_tail_form`` is asked at that many.

    ``decay`` γ < 1 decays the accumulator LAZILY — only the rows a step
    touches pay ``accum = γ·accum + g²`` (an untouched row's history is
    also its recency: decaying it would shrink step sizes for rows that
    saw no data, the opposite of what a moving distribution needs, and a
    per-step O(V) sweep would erase the sparse update's whole point).
    γ=1.0 is a trace-time branch to the exact classic expression — same
    XLA program, bit-identical results (test-pinned on all three train
    paths)."""
    D = table.shape[-1]
    flat, row_grads = ids.reshape(-1), row_grads.reshape(-1, D)
    m = flat.shape[0] if keep is None else keep
    if form is None:
        form = rows_tail_form(table.shape[0], m, D, state.accum.shape[-1])
    if form == "sweep":
        from fast_tffm_tpu.ops.pallas_tail import sweep_adagrad_update

        sid, gt = occurrences_by_id(flat, row_grads, table.shape[0], keep)
        with jax.named_scope("fm.tail"):
            table, accum = sweep_adagrad_update(
                table, state.accum, sid, gt, lr, decay=decay
            )
        return table, AdagradState(accum)
    uids, gsum = dedup_rows(flat, row_grads, table.shape[0], keep)
    known = dict(
        indices_are_sorted=True,
        unique_indices=distinct_sentinels(table.shape[0], m, uids.dtype),
    )
    with jax.named_scope("fm.tail"):
        acc_prev = state.accum.at[uids].get(mode="clip", **known)
        if decay != 1.0:
            acc_prev = decay * acc_prev
        acc_rows = acc_prev + accum_sq(state.accum, gsum)  # sentinel lanes
        step = -(lr * gsum / jnp.sqrt(acc_rows))  # dropped below
        accum = state.accum.at[uids].set(acc_rows, mode="drop", **known)
        table = table.at[uids].add(step, mode="drop", **known)
    return table, AdagradState(accum)
