"""The forward gather of a lane-major table as one sweep over its transpose.

A ``[V, D]`` float32 table with ``D < 128`` is held lane-major on the TPU
(``{0,1:T(8,128)}``: the row index along the lanes), so ``table[ids]`` reads
a row as ``D`` single-lane accesses ``V`` floats apart: 22 ns a row of 9
floats, 39 a row of 31, 1.6 GB/s on a chip whose sweeps stream hundreds
(PERF.md §5 and §6, PR 27 and PR 40).  A row is therefore never ADDRESSED
on such a table, in either direction: the Adagrad tail walks the transposed
view block by block and writes whole tile columns (ops/pallas_tail.py,
PR 30), and ``sweep_gather`` here is that sweep the other way round.  It is
handed the batch's ids ASCENDING, repeats and all (``optim.sort_ids``),
reads ``table.T`` (a bitcast of the lane-major buffer) once, block by block,
over the tail's own work list (``pallas_tail._sweep_plan``: every block of
the table paired with the 256-id chunks that fall in it, blocks without an
id never read), and returns ``[D, M]``: the ids' rows column by column, in
id order.  ``trainer.gather_rows`` sorts the ids before it and brings the
columns back to batch order after it.

Exactness.  The kernel selects, it does not add up: a table row sits at
lane ``id & 127`` of group ``id >> 7`` of its block.  Per work item the
one-hot of the chunk's lanes, ``[128, 256]`` in bfloat16, is built ONCE and
contracted on the MXU with ``_GROUPS`` groups of the block stacked along the
sublanes (``[groups * 3 * dp, 128] x [128, 256]``: every group's value at
each id's lane), and the id's own group is then SELECTED (``jnp.where`` on
``id >> 7``).  A block's float32 values go in as their three exact bfloat16
parts (``pallas_tail._split3``: top 8 significand bits, the next 8, the
last 8; laid out once a block in a VMEM scratch): a product is 1.0 times a
part or 0 times one, every output column of a group holds exactly ONE
non-zero product a part, the MXU's float32 accumulator adds zeros to it, and
``(hi + mid) + lo`` is the float32 value again (``hi + mid`` has 16
significant bits).  So the result is ``table[ids]`` BIT FOR BIT and no value
is ever rounded to bfloat16.  The limits are the tail's: a value under about
1e-33 loses its last part to the flush of subnormals; -0.0 comes back as
0.0; a non-finite table value turns the other rows of its 128-row group
that the same chunk reads into NaN (0 x inf in the contraction), and is
itself returned as NaN.

Why this contraction and not the tail's.  The tail's kernel builds a
``[1024, 256]`` one-hot a tile and contracts it with 48 rows of gradients:
the one-hot is the MXU's stationary operand and 48 rows stream past 16
weight tiles.  Read that way round for the gather, kernel and work list take
29.1 ms at ``fm8_criteo``'s shapes; with the one-hot over the 128 lanes of a
group held and 16 groups of table streamed past it, 26.7 (work list 4.8 of
each; TPU v5e, PERF.md §6, PR 40).  A gather along the lanes
(``jnp.take_along_axis`` = ``tpu.dynamic_gather``), which needs no parts and
no MXU, compiles and reads 54.2.  What binds the kernel is its grid (0.6 us
an item, 18,176 items) and the contraction (7.8 ms), not the table's 4.3 GB
(more DMA streams a block changed nothing).

STATUS ON THE CHIP (TPU v5 lite, jax 0.9.0, libtpu 0.0.34; each piece alone,
medians of eight calls).  2^26 x 9 under 2,555,904 uniform ids: ``table[ids]``
56.9 ms; sort with positions 5.4, kernel and work list 26.7, nine columns back
as operands of one sort 20.0: 52.1.  2^25 x 31 under 720,896: 28.1 against
1.9 + 20.0 + 8.5 (rows gathered back by the inverse permutation); 2^25 x 17
under 2,555,904: 78.7 against 5.5 + 26.7 + 81.9, so ``trainer.gather_form``
keeps ``table[ids]`` past 9 floats.  A row SHARD's lookup has a tile-wide way
back (parallel/embedding.sharded_gather): a [2^25, 17] shard owning 639,628 of
2,555,904 slots reads them all masked in 105.7 ms; sort 4.9, the owned prefix
swept 19.9, laid out as 128-lane rows 3.9, scattered to its slots 21.8: 45.3.
tests/test_pallas_tail_chip_compile.py compiles the kernel for a described
v5e at both shapes; under ``interpret=`` it runs on the CPU (pallas_common).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fast_tffm_tpu.ops import pallas_tail as _tail
from fast_tffm_tpu.ops.pallas_common import resolve_interpret

__all__ = ["sweep_gather", "sweep_gather_items"]

# Table rows one lane of the one-hot stands for: the contraction runs over
# the 128 rows of a group, and ``_GROUPS`` groups ride one pass of the MXU
# stacked along the sublanes.
_GROUP = 128
_GROUPS = 16


def sweep_gather_items(v: int, d: int, m: int) -> int:
    """The kernel's grid length for ``m`` ids on ``v`` rows of ``d``: the
    work list's static length, every block once plus every chunk once."""
    return -(-v // _tail.sweep_block_lanes(v, d)) + -(-m // _tail._CHUNK)


def _gather_kernel(
    blk_ref, ch_ref, meta_ref, u_ref, t_ref, out_ref, xpad, parts,
    *, v, d, dp, tb, groups, chunk,
):
    i = pl.program_id(0)
    meta = meta_ref[i]
    base = blk_ref[i] * tb
    rows = 3 * dp  # a group's three parts

    @pl.when(i == 0)
    def _():
        xpad[...] = jnp.zeros_like(xpad)

    @pl.when(((meta >> 20) & 1) == 1)
    def _():
        # A block's first item: its three exact bfloat16 parts, group by
        # group along the sublanes.  What the last block holds past the
        # table's end must not reach a product.
        col = lax.broadcasted_iota(jnp.int32, (d, tb), 1)
        xpad[0:d, :] = jnp.where(col < v - base, t_ref[...], 0.0)
        for g in range(tb // _GROUP):
            parts[g * rows:(g + 1) * rows, :] = _tail._split3(
                xpad[:, g * _GROUP:(g + 1) * _GROUP]
            )

    @pl.when(((meta >> 22) & 1) == 1)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    rel = u_ref[...] - base  # [1, chunk]: the id's row in this block
    lane = lax.broadcasted_iota(jnp.int32, (_GROUP, chunk), 0)
    hot = jnp.where(lane == (rel & (_GROUP - 1)), 1.0, 0.0).astype(jnp.bfloat16)
    # Under 0 or from tb/128 up: another block's id, or the padding's.
    grp = jnp.broadcast_to(rel >> 7, (dp, chunk))

    def body(t, acc):
        lhs = parts[pl.ds(pl.multiple_of(t * (groups * rows), groups * rows), groups * rows), :]
        r = lax.dot_general(
            lhs, hot, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [groups * 3dp, chunk]: every group's value at the id's lane
        for k in range(groups):
            o = k * rows
            val = (r[o:o + dp] + r[o + dp:o + 2 * dp]) + r[o + 2 * dp:o + rows]
            acc = jnp.where(grp == t * groups + k, val, acc)
        return acc

    acc = lax.fori_loop(
        meta & 1023, ((meta >> 10) & 1023) + 1, body, jnp.zeros((dp, chunk), jnp.float32)
    )
    out_ref[...] += acc


def sweep_gather(
    table: jax.Array,
    sid: jax.Array,
    *,
    interpret: bool | None = None,
    block_lanes: int | None = None,
) -> jax.Array:
    """``table[sid].T`` for ASCENDING ids ``sid [M]`` in ``[0, V)``, repeats
    and all (``optim.sort_ids``' first result on ids brought into the
    table): ``[D, M]`` float32, the ids' rows column by column along the
    lanes, bit for bit (the module docstring has the argument and its
    limits)."""
    interpret = resolve_interpret(interpret)
    v, d = table.shape
    m = sid.shape[0]
    chunk = _tail._CHUNK
    tb = _tail.sweep_block_lanes(v, d, block_lanes)
    tile = _GROUP * _GROUPS
    while tb % tile:  # a table shorter than a block: the groups that divide it
        tile //= 2
    if tb // tile > 1024:
        raise ValueError(f"block_lanes {tb} is more than 1024 tiles of {tile} rows")
    dp = -(-d // 16) * 16  # whole bfloat16 tiles
    m_pad = -(-m // chunk) * chunk
    sid = jnp.pad(
        sid.astype(jnp.int32), (0, m_pad - m), constant_values=jnp.iinfo(jnp.int32).max
    )
    blk, ch, meta = _tail._sweep_plan(sid, v, tb, tile)
    # A chunk's items are adjacent (items go by block, and so do the ids):
    # its output block stays resident from its first item, which zeroes it.
    fresh = jnp.concatenate([jnp.ones((1,), bool), ch[1:] != ch[:-1]])
    meta = meta | (fresh.astype(jnp.int32) << 22)
    by_block = lambda i, blk, ch, meta: (0, blk[i])
    by_chunk = lambda i, blk, ch, meta: (0, ch[i])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(blk.shape[0],),
        in_specs=[pl.BlockSpec((1, chunk), by_chunk), pl.BlockSpec((d, tb), by_block)],
        out_specs=pl.BlockSpec((dp, chunk), by_chunk),
        scratch_shapes=[
            pltpu.VMEM((dp, tb), jnp.float32),
            pltpu.VMEM((tb // _GROUP * 3 * dp, _GROUP), jnp.bfloat16),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _gather_kernel, v=v, d=d, dp=dp, tb=tb, groups=tile // _GROUP, chunk=chunk
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((dp, m_pad), table.dtype),
        interpret=interpret,
    )(blk, ch, meta, sid[None, :], table.T)
    return out[:d, :m]
