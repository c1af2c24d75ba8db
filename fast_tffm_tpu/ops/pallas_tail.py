"""The Pallas rows sweep: the Adagrad update of the touched rows as one
in-place kernel pass.

The XLA sparse tail is a CHAIN of programs — dedup (sort, permutation,
segment sum, a second sort), an accumulator gather, two table-shaped
scatters — each of which walks its own descriptor stream over the same
touched rows.  The kernel here takes the place of everything after the
FIRST sort (optim.sort_ids, which both forms share): it is handed the
batch's occurrences in id order (optim.occurrences_by_id), duplicates and
all, and sums a row's occurrences itself (PR 32; until then it ran after
optim.dedup_rows' segment sum and saw every row once).  The gradient
columns reach that order as operands of the ids' sort up to 16 of them;
wider rows are gathered in the sort's order, padded to one 128-lane tile
first where their lane-major buffer is too large for the VMEM (a row-major
row, where a 17-float row is 17 lanes of that buffer in HBM: the narrow
gather took 78 ms of ``fm16_criteo_tiered``'s step on a TPU v5e), as they
are where it fits or where a row shard keeps a prefix of the order
(optim.occurrences_permutation).

``rows_tail_adagrad_update`` / ``sweep_adagrad_update`` — the **rows sweep**
(PR 30) — serve a plain ``[V, D]`` table with a separate ``[V, D]``
(element) or ``[V, 1]`` (row) accumulator: the resident rows layout AND the
tiered paramstore's compact ``[C, D]`` device table.  The sweep never
addresses a row.  A ``[V, D]`` float32 buffer with ``D < 128`` is held
lane-major on the TPU (``{0,1:T(8,128)}``: the row index along the lanes),
which IS the row-major layout of its transpose, so the kernel takes
``table.T`` / ``accum.T`` (bitcasts in the compiled step), walks them block
by block IN PLACE (``input_output_aliases``) and writes whole tile columns.
The batch's dense delta never exists in HBM: a work list computed from the
sorted ids (scalar prefetch) pairs every block with the 256-id chunks that
fall in it, and the kernel builds the block's gradient in VMEM as a one-hot
of the ids against the row index, contracted with the gradients on the MXU.
A contraction over a chunk sums every id that matches the same row, and the
scratch ``gacc`` carries a row across the chunks of its block, so an output
is the float32 sum (the MXU's accumulator) over the row's occurrences of
each of three bfloat16 parts of the gradient, then ``(Σhi + Σmid) + Σlo``.
No bfloat16 rounding of any gradient enters: each part is exact in bfloat16
(the three sum back to the float32 value bit for bit) and its product with
1.0 is exact.  That is ``segment_sum``'s mathematics at its precision in
another order of addition: bit-equal to it on a row that occurs once, within
float32 summation error on a row that occurs more often (PERF.md §6, PR 32,
has the chip's reading).  A row of ones in the gradients returns the
occurrence count, whose ``> 0.5`` is the hit mask.  Adagrad is then the
classic expressions on the block, SELECTED by the hit mask: an untouched row
comes out bit for bit whatever its accumulator holds (0 included), and a
lazily decayed accumulator decays only where touched.  Blocks no id falls
in are not visited.  A non-finite gradient spreads NaN over the touched
rows of its chunk's tiles (0·inf in the contraction); the step's loss is
non-finite then and the trainer's ``on_nan`` policy has it.

Decay-γ (``[Online] adagrad_decay``) threads through exactly like
``trainer.make_decayed_body``: γ=1.0 is a TRACE-TIME branch back to the
classic expression (``accum += g²``); γ<1 decays lazily, touched rows only.

It runs under ``interpret=`` for CPU tier-1 (ops.pallas_common resolves the
flag, same pattern as ops/pallas_anova.py).

STATUS ON THE CHIP (TPU v5 lite, jax 0.9.0, libtpu 0.0.34).  The sweep
compiles and runs (PR 30; tests/test_pallas_tail_chip_compile.py compiles
it for a described v5e at the train cells' shapes): at ``fm8_criteo``'s
(2^26 rows of 9, 2,555,904 ids a step, 2.3M distinct) the kernel takes 38
ms inside the step (45 alone, with its work list) where the XLA row
operations took 570 (PERF.md §6 has the bitwise reading on a batch without
repeats); at D = 17 (PR 36: ``fm16_criteo_row4``'s row SHARD, 2^25 rows of 17 under all four chips' 2,555,904 slots, a quarter of them the shard's) 25.2 ms on each of four chips where the shard's gather-and-set took 770.  ``optim.sparse_adagrad_update`` takes
it on a TPU where ``optim.rows_tail_form`` says the sweep costs less than
the batch's row operations.  There is no kernel that moves a touched row by
a DMA of its own (two stood here, for the rows and the fused layouts, until
PRs 30 and 31): Mosaic refuses it, "Slice shape along dimension 1 must be
aligned to tiling (128), but is 9" — an HBM row is stored 128 lanes wide and
a DMA window covers whole tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fast_tffm_tpu.optim import occurrences_by_id
from fast_tffm_tpu.ops.pallas_common import resolve_interpret

__all__ = [
    "rows_tail_adagrad_update",
    "sweep_adagrad_update",
    "sweep_block_lanes",
    "sweep_fits",
]

# The rows sweep (readings: PERF.md §6, PR 30, at fm8_criteo's shapes).
# A block of the table is this many bytes in VMEM (its rows padded to whole
# sublanes); eight such buffers are in flight (table and accumulator, in
# and out, double-buffered).  512 KiB is 8,192 rows of 9 floats: blocks of
# 4,096 / 8,192 / 16,384 rows read 57.1 / 55.9 / 55.8 ms (tiles of 512).
_BLOCK_BYTES = 512 << 10
# Table rows one one-hot contraction covers.  The loop over a chunk's tiles
# costs more than the one-hot wasted at its ends: 128 / 256 / 512 / 1,024
# rows read 105 / 73 / 57 / 50 ms (chunks of 128).
_TILE = 1024
# Updates one contraction runs over (two passes of the MXU's depth of 128):
# fewer, longer work items; 128 / 256 read 50.4 / 46.3 ms.
_CHUNK = 256


def sweep_block_lanes(v: int, d: int, block_lanes: int | None = None) -> int:
    """Table rows (lanes of the transposed view) a block of the sweep holds:
    ``block_lanes`` if given, else what ``_BLOCK_BYTES`` holds of rows ``d``
    wide in whole tiles of ``_TILE``; never more than the table itself
    rounded up to whole 128-lane tiles."""
    if block_lanes is None:
        fit = _BLOCK_BYTES // (4 * -(-d // 8) * 8)
        block_lanes = max(_TILE, fit // _TILE * _TILE)
    return min(block_lanes, -(-v // 128) * 128)


def sweep_fits(v: int, d: int, m: int) -> bool:
    """Whether the sweep's work list for ``m`` ids on ``v`` rows of ``d`` —
    three int32 a (block, chunk) item, scalar-prefetched — fits the scalar
    memory: 1 MiB on a v5e ("Used 1.92M of 1.00M smem", the compiler on a
    list of 176K items), of which three quarters are taken as the room:
    65,536 items, about 15M ids a batch on 2^26 rows of 9."""
    items = -(-v // sweep_block_lanes(v, d)) + -(-m // _CHUNK)
    return 12 * items <= 768 << 10


def _sweep_plan(sid, v: int, tb: int, tile: int):
    """The sweep's work list, from the ascending ids ``sid`` alone, repeated
    or not (XLA, a few arrays of ``nb + nchunks`` ints).

    One item is one (block of ``tb`` table rows, chunk of ``_CHUNK``
    updates) pair that overlap: block ``b`` owns ``sid[off[b]:off[b+1]]``
    (``off = searchsorted(sid, b·tb)``), which lies in the chunks
    ``off[b] // _CHUNK .. (off[b+1]-1) // _CHUNK``.  Blocks no update falls
    in get no item — they are never read or written.  The list has a static
    length (every block once plus every chunk boundary once); slots past the
    real items repeat the last one's block and chunk with no tiles and no
    flags, so the pipeline moves nothing and the kernel does nothing for
    them.  At least one item is real (block and chunk of its own, possibly
    matching nothing), so the output block the grid ends on has always been
    written.

    Returns int32 ``[w]`` arrays: the item's block, its chunk, and
    ``meta`` = first tile | last tile << 10 | first-of-block << 20 |
    last-of-block << 21, the tiles (``tile`` rows) of the block that the
    chunk's ids in it span (none: first 1, last 0).
    """
    m_pad = sid.shape[0]
    nb, nchunks = -(-v // tb), m_pad // _CHUNK
    w = nb + nchunks
    bounds = jnp.minimum(jnp.arange(nb + 1, dtype=jnp.int32) * tb, v)
    off = jnp.searchsorted(sid, bounds, method="scan_unrolled").astype(jnp.int32)
    lo, hi = off[:-1], off[1:]
    c0 = lo // _CHUNK
    n_items = jnp.where(hi > lo, (hi - 1) // _CHUNK - c0 + 1, 0)
    end = jnp.cumsum(n_items)
    start = end - n_items
    total = jnp.maximum(end[-1], 1)
    i = jnp.arange(w, dtype=jnp.int32)
    real = i < total
    i_eff = jnp.minimum(i, total - 1)
    blk = jnp.minimum(
        jnp.searchsorted(end, i_eff, side="right", method="scan_unrolled").astype(jnp.int32),
        nb - 1,
    )
    ch = jnp.minimum(c0[blk] + i_eff - start[blk], nchunks - 1)
    first = real & (i_eff == start[blk])
    last = real & (i_eff >= end[blk] - 1)
    s = jnp.maximum(lo[blk], ch * _CHUNK)
    e = jnp.minimum(hi[blk], (ch + 1) * _CHUNK) - 1
    some = real & (e >= s)  # a real item lacks ids only if the batch drops all
    t0 = jnp.where(some, (sid[jnp.minimum(s, m_pad - 1)] - blk * tb) // tile, 1)
    t1 = jnp.where(some, (sid[jnp.clip(e, 0, m_pad - 1)] - blk * tb) // tile, 0)
    meta = (
        t0 | (t1 << 10) | (first.astype(jnp.int32) << 20)
        | (last.astype(jnp.int32) << 21)
    )
    return blk, ch, meta.astype(jnp.int32)


def _split3(gt: jax.Array) -> jax.Array:
    """``[dp, m]`` float32 → ``[3·dp, m]`` bfloat16 whose three row groups
    (top 8 significand bits, the next 8, the last 8) sum back to the float32
    values exactly.  By masks, not by convert pairs: the TPU compiler may
    drop a float32 → bfloat16 → float32 round trip as excess precision, and
    ``g − hi`` would then be zero.  (Gradients under about 1e-33, whose last
    part is subnormal, lose it to the flush.)"""

    def top(x):
        bits = lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return lax.bitcast_convert_type(bits, jnp.float32)

    hi = top(gt)
    r = gt - hi
    mid = top(r)
    return jnp.concatenate([hi, mid, r - mid], axis=0).astype(jnp.bfloat16)


def _sweep_kernel(
    blk_ref, ch_ref, meta_ref, u_ref, g3_ref, t_ref, a_ref, t_out, a_out, gacc,
    *, lr: float, decay: float, d: int, dp: int, tb: int, tile: int,
):
    i = pl.program_id(0)
    meta = meta_ref[i]
    base = blk_ref[i] * tb

    @pl.when(((meta >> 20) & 1) == 1)
    def _():
        gacc[...] = jnp.zeros_like(gacc)

    u = u_ref[...]  # [1, _CHUNK] ids, ascending
    g3 = g3_ref[...]  # [3·dp, _CHUNK] the gradients' three parts
    row = lax.broadcasted_iota(jnp.int32, (tile, _CHUNK), 0)

    def body(t, carry):
        # One-hot of the chunk's ids against this tile's rows; ids of other
        # tiles, other blocks and the drop ids match no row.
        hot = jnp.where(row == u - (base + t * tile), 1.0, 0.0)
        r = lax.dot_general(
            g3, hot.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [3·dp, tile]: a row's occurrences in the chunk, summed in float32
        gacc[t] += (r[:dp] + r[dp:2 * dp]) + r[2 * dp:]
        return carry

    lax.fori_loop(meta & 1023, ((meta >> 10) & 1023) + 1, body, 0)

    @pl.when(((meta >> 21) & 1) == 1)
    def _():
        for t in range(tb // tile):
            cols = slice(t * tile, (t + 1) * tile)
            g = gacc[t, :d, :]
            hit = gacc[t, d:d + 1, :] > 0.5  # the row of ones: occurrences
            w, acc = t_ref[:, cols], a_ref[:, cols]
            asq = g * g
            if acc.shape[0] == 1 and d != 1:  # row-granularity accumulator
                asq = jnp.sum(asq, axis=0, keepdims=True)
            acc2 = (acc if decay == 1.0 else decay * acc) + asq
            # ``lr·g/√acc'`` as XLA compiles it on the TPU: a multiply by the
            # reciprocal root.  Mosaic's own divide and root are XLA's bit
            # for bit, but ``x / sqrt(y)`` kept as written rounds differently
            # from the classic tail in two elements of three (PERF.md §6).
            t_out[:, cols] = jnp.where(hit, w - lr * g * lax.rsqrt(acc2), w)
            a_out[:, cols] = jnp.where(hit, acc2, acc)


def sweep_adagrad_update(
    table: jax.Array,
    accum: jax.Array,
    sid: jax.Array,
    gt: jax.Array,
    lr: float,
    *,
    decay: float = 1.0,
    interpret: bool | None = None,
    block_lanes: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """The sweep on ``optim.occurrences_by_id``'s output: ``sid [M]`` the
    batch's ids ASCENDING, repeated as often as they occur (anything from
    ``V`` up is dropped), ``gt [D, M]`` the occurrences' gradients in that
    order, column by column along the lanes.  Unique ids with summed
    gradients (``optim.dedup_rows``) are a case of it.

    A ``[V, D]`` float32 array with ``D < 128`` is held lane-major on the
    TPU (the row index along the lanes), which is the row-major layout of
    its transpose: the kernel takes ``table.T``, ``accum.T`` (bitcasts) in
    blocks of ``block_lanes`` rows, aliased to its outputs, builds each
    block's dense gradient in VMEM from the slice of the ids that falls in
    it (a one-hot against the row index, contracted with the gradients on
    the MXU: a row's occurrences are summed there, in float32) and writes
    ``w − lr·g/√acc'`` and ``acc'`` where the one-hot hit, the old values
    elsewhere.  Blocks without an update are not visited.
    """
    interpret = resolve_interpret(interpret)
    v, d = table.shape
    a = accum.shape[-1]
    m = sid.shape[0]
    tb = sweep_block_lanes(v, d, block_lanes)
    tile = _TILE
    while tb % tile:  # a table shorter than a block: the tile that divides it
        tile //= 2
    if tb // tile > 1024:
        raise ValueError(f"block_lanes {tb} is more than 1024 tiles of {tile} rows")
    dp = -(-(d + 1) // 16) * 16  # whole bfloat16 tiles, room for the ones
    m_pad = -(-m // _CHUNK) * _CHUNK
    sid = jnp.pad(
        sid.astype(jnp.int32), (0, m_pad - m),
        constant_values=jnp.iinfo(jnp.int32).max,
    )
    gt = jnp.pad(gt, ((0, dp - d), (0, m_pad - m))).at[d].set(1.0)
    blk, ch, meta = _sweep_plan(sid, v, tb, tile)
    by_block = lambda i, blk, ch, meta: (0, blk[i])
    by_chunk = lambda i, blk, ch, meta: (0, ch[i])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(blk.shape[0],),
        in_specs=[
            pl.BlockSpec((1, _CHUNK), by_chunk),
            pl.BlockSpec((3 * dp, _CHUNK), by_chunk),
            pl.BlockSpec((d, tb), by_block),
            pl.BlockSpec((a, tb), by_block),
        ],
        out_specs=[
            pl.BlockSpec((d, tb), by_block),
            pl.BlockSpec((a, tb), by_block),
        ],
        scratch_shapes=[pltpu.VMEM((tb // tile, dp, tile), jnp.float32)],
    )
    kernel = functools.partial(
        _sweep_kernel, lr=float(lr), decay=float(decay), d=d, dp=dp, tb=tb,
        tile=tile,
    )
    table_t, accum_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((d, v), table.dtype),
            jax.ShapeDtypeStruct((a, v), accum.dtype),
        ),
        input_output_aliases={5: 0, 6: 1},  # table and accum in place
        interpret=interpret,
    )(blk, ch, meta, sid[None, :], _split3(gt), table.T, accum.T)
    return table_t.T, accum_t.T


def rows_tail_adagrad_update(
    table: jax.Array,
    accum: jax.Array,
    ids: jax.Array,
    row_grads: jax.Array,
    lr: float,
    *,
    decay: float = 1.0,
    interpret: bool | None = None,
    block_lanes: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``optim.sparse_adagrad_update``'s ``sweep`` form on a whole batch (it
    makes these two calls itself): the occurrences brought to id order
    (``optim.occurrences_by_id``: one sort, one permutation, no sums), then
    ``sweep_adagrad_update``; same accumulator expressions and lazy-decay
    semantics as the rows."""
    d = table.shape[-1]
    sid, gt = occurrences_by_id(ids.reshape(-1), row_grads.reshape(-1, d), table.shape[0])
    with jax.named_scope("fm.tail"):
        return sweep_adagrad_update(
            table, accum, sid, gt, lr, decay=decay, interpret=interpret,
            block_lanes=block_lanes,
        )
