"""Fused Pallas sparse tail: one-pass gather→Adagrad→scatter.

The XLA sparse tail is a CHAIN of programs — grad lane-spread, bitmap/
cumsum (or sort) compaction, RMW gather, RMW scatter — each of which
walks its own descriptor stream over the same touched rows.  This module
replaces the tail with ONE Pallas TPU kernel per table layout:

  * dedup ONCE at **logical-row** granularity (optim.dedup_rows — the
    sort/segment-sum pipeline the rows-layout classic update already
    uses, so the compacted gradients are bit-identical to it), then
  * a single kernel pass: per deduped row, DMA **only the touched
    lanes** HBM→VMEM (for the fused ``[VPf, 128]`` layout that is the
    row's own ``D+1``-lane slot — params + its in-row accumulator — not
    the whole 128-lane tile row), apply the Adagrad update in VMEM, and
    DMA the result straight back.  Gather and scatter ride the same
    pass, double-buffered two row-blocks deep: block ``i+1``'s gather
    DMAs issue while block ``i`` computes, and block ``i``'s scatter
    DMAs drain while ``i+1`` computes.
  * the output aliases the table operand (``input_output_aliases``), so
    the update is in place — untouched rows are never read or written.

Decay-γ (``[Online] adagrad_decay``) threads through exactly like
``trainer.make_decayed_body``: γ=1.0 is a TRACE-TIME branch back to the
classic expression (``accum += g²``), so the default program — and its
bits — are untouched; γ<1 decays lazily, and *only the deduped touched
rows* ever reach the kernel, which is precisely the lazy-decay contract.
Correctness of the slot-slice RMW rests on the zero-grad identity: a row
(or lane) with zero summed gradient maps to exactly itself
(``acc+0 = acc``; ``w − lr·0/√acc = w``), so rows the batch doesn't
touch can simply never enter the kernel.

Layouts served:

  * ``fused_tail_adagrad_update`` — the resident fused layout
    (``ops.packed_table.pack_fused``, ``[VPf, 128]``, P = 128//(D+1)
    logical rows per tile row; accumulator in lane ``s·(D+1)+D``).
  * ``rows_tail_adagrad_update`` — a plain ``[V, D]`` table with a
    separate ``[V, D]`` (element) or ``[V, 1]`` (row) accumulator: the
    resident rows layout AND the tiered paramstore's compact ``[C, D]``
    device table (the staging region already holds exactly the operand
    shape the kernel wants — remapped slot ids against a compact table).

Both run under ``interpret=`` for CPU tier-1 (ops.pallas_common resolves
the flag, same pattern as ops/pallas_anova.py).

STATUS ON THE CHIP (PR 22; TPU v5 lite, jax 0.9.0, libtpu 0.0.34, at
baseline #1's width — M = 16384×39 ids, D = 9 / D+1 = 9 lanes): neither
kernel compiles.  Mosaic refuses the per-row DMA both are built on::

    INTERNAL: Mosaic failed to compile TPU kernel: Slice shape along
    dimension 1 must be aligned to tiling (128), but is 9.
      "tpu.memref_slice"(...) : (memref<1048576x128xf32,
      #tpu.tiled<(1,128),[1,1]>, #tpu.memory_space<hbm>>, i32, i32)
      -> memref<1x9xf32, #tpu.tiled<(1,128),[1,1]>, ...<hbm>>

(rows layout: ``table_ref.at[row]``; fused layout: ``pl.ds(lane0, d+1)``,
same message against ``memref<74904x128xf32>``).  An HBM row is stored
128 lanes wide and a DMA window must cover whole tiles, so a 9-lane
window does not exist; a whole-tile-row window would make two logical
rows of one tile row overwrite each other — a different algorithm (dedup
at tile-row granularity), not a repair.  So ``tail = auto`` resolves to
the XLA tail (ops.pallas_common.resolve_tail), ``tail = pallas`` raises
the message above on a TPU, and ROADMAP S4 decides whether this module
stays.  The kernels still run interpreted on the CPU test mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fast_tffm_tpu.optim import dedup_rows
from fast_tffm_tpu.ops.pallas_common import resolve_interpret

__all__ = [
    "fused_tail_adagrad_update",
    "rows_tail_adagrad_update",
    "DEFAULT_BLOCK_ROWS",
]

DEFAULT_BLOCK_ROWS = 256  # rows per grid step; 2 buffers × 256 × ≤128 lanes


def _nblocks(k: int, blk: int) -> int:
    return max(1, -(-k // blk))


def _pad_ids(uids: jax.Array, total: int, sentinel: int) -> jax.Array:
    k = uids.shape[0]
    if total == k:
        return uids
    return jnp.pad(uids, (0, total - k), constant_values=sentinel)


def _schedule(i, nblocks, start_in, wait_in, start_out, wait_out, compute):
    """The shared double-buffer schedule for one grid step ``i``.

    Slot ``i % 2`` holds block ``i``; while it computes, block ``i+1``
    gathers into the other slot, whose previous occupant's (block
    ``i−1``'s) scatter DMAs are drained first.  All four DMA phases are
    per-row-predicated identically, so semaphore starts and waits always
    pair up."""
    slot = lax.rem(i, 2)
    other = lax.rem(i + 1, 2)

    @pl.when(i == 0)
    def _():
        start_in(i, slot)

    @pl.when(i >= 1)
    def _():
        wait_out(i - 1, other)

    @pl.when(i + 1 < nblocks)
    def _():
        start_in(i + 1, other)

    wait_in(i, slot)
    compute(slot)
    start_out(i, slot)

    @pl.when(i == nblocks - 1)
    def _():
        wait_out(i, slot)


# --------------------------------------------------------------------------
# fused [VPf, 128] layout (ops.packed_table.pack_fused)
# --------------------------------------------------------------------------


def _fused_kernel(
    uids_ref, nrows_ref, g_ref, fused_ref, out_ref, buf, in_sem, out_sem,
    *, lr: float, decay: float, p: int, d: int, blk: int, nblocks: int,
    vmax: int,
):
    i = pl.program_id(0)
    nrows = nrows_ref[0]
    d1 = d + 1

    def slot_slice(row):
        """Touched-lane address of deduped logical row ``row``: the
        (tile row, first lane) of its D+1-lane slot."""
        lid = jnp.minimum(uids_ref[row], vmax - 1)  # clamp pad sentinels
        return lid // p, (lid % p) * d1

    def _run(block, slot, *, outward, wait):
        base = block * blk

        def body(j, _):
            @pl.when(base + j < nrows)
            def _():
                phys, lane0 = slot_slice(base + j)
                vref = buf.at[slot, j]
                href = (out_ref if outward else fused_ref).at[
                    phys, pl.ds(lane0, d1)
                ]
                src, dst = (vref, href) if outward else (href, vref)
                cp = pltpu.make_async_copy(
                    src, dst, (out_sem if outward else in_sem).at[slot]
                )
                cp.wait() if wait else cp.start()
            return 0

        @pl.when(base < nrows)
        def _():
            lax.fori_loop(0, blk, body, 0)

    def compute(slot):
        cur = buf[slot]  # [blk, d+1]: d params + the row accumulator
        g = g_ref[...]  # [blk, d] deduped summed gradients
        w, acc0 = cur[:, :d], cur[:, d]
        gsq = jnp.sum(g * g, axis=-1)
        if decay == 1.0:  # trace-time: the exact classic program
            acc2 = acc0 + gsq
        else:  # lazy decay — every deduped row here WAS touched
            acc2 = decay * acc0 + gsq
        new_w = w - lr * g / jnp.sqrt(acc2)[:, None]
        buf[slot] = jnp.concatenate([new_w, acc2[:, None]], axis=-1)

    _schedule(
        i, nblocks,
        start_in=lambda b, s: _run(b, s, outward=False, wait=False),
        wait_in=lambda b, s: _run(b, s, outward=False, wait=True),
        start_out=lambda b, s: _run(b, s, outward=True, wait=False),
        wait_out=lambda b, s: _run(b, s, outward=True, wait=True),
        compute=compute,
    )


def _fused_rmw(fused, uids, nrows, gsum, *, lr, decay, p, d, interpret, blk):
    """One-pass RMW over ``K = uids.shape[0]`` deduped logical rows."""
    k = uids.shape[0]
    nblocks = _nblocks(k, blk)
    vmax = fused.shape[0] * p  # any lid ≥ vmax is a pad sentinel
    uids = _pad_ids(uids.astype(jnp.int32), nblocks * blk, vmax)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((blk, d), lambda i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, blk, d + 1), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _fused_kernel, lr=float(lr), decay=float(decay), p=p, d=d, blk=blk,
        nblocks=nblocks, vmax=vmax,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(fused.shape, fused.dtype),
        input_output_aliases={3: 0},  # fused table updates in place
        interpret=interpret,
    )(uids, nrows, gsum, fused)


def fused_tail_adagrad_update(
    fused: jax.Array,
    ids: jax.Array,
    row_grads: jax.Array,
    lr: float,
    *,
    decay: float = 1.0,
    k_cap: int = 0,
    interpret: bool | None = None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> jax.Array:
    """Adagrad over the fused ``[VPf, 128]`` layout in one kernel pass.

    Semantically ``ops.packed_table.apply_fused_update`` (row-granularity
    accumulator): dedup to unique logical rows, ``acc ← γ·acc + ‖g‖²``,
    ``w ← w − lr·g/√acc``.  The dedup is ``optim.dedup_rows`` — the SAME
    sort/segment pipeline the rows-layout classic update uses, so at
    γ=1.0 the result is ``sparse_adagrad_update``'s with a row accumulator
    on the logical arrays, bit for bit where both run the same
    expressions and within a few ULP inside the ``k_cap`` fallback
    (test-pinned; the classic tail scatter-ADDS ``-lr·g/√acc`` since
    PR 27); against the scatter-add-built XLA fused tails it is allclose
    (summation order).

    ``k_cap`` mirrors ``packed_compact_cap``: cap the kernel's deduped
    row span, with an exact full-span ``lax.cond`` fallback when a batch
    touches more rows — never silent truncation.
    """
    interpret = resolve_interpret(interpret)
    d = row_grads.shape[-1]
    p = 128 // (d + 1)
    v = fused.shape[0] * p
    flat = ids.reshape(-1)
    uids, gsum = dedup_rows(flat, row_grads.reshape(-1, d), v)
    m = uids.shape[0]
    nrows = jnp.sum(uids < v).astype(jnp.int32)[None]
    blk = max(8, min(block_rows, m))
    run = functools.partial(
        _fused_rmw, lr=lr, decay=decay, p=p, d=d, interpret=interpret,
        blk=blk,
    )
    if k_cap and k_cap < m:
        # Exact-capacity fallback, same shape as the XLA compact tail's:
        # overflowing batches pay the full span, never lose updates.
        return lax.cond(
            nrows[0] <= k_cap,
            lambda f: run(f, uids[:k_cap], nrows, gsum[:k_cap]),
            lambda f: run(f, uids, nrows, gsum),
            fused,
        )
    return run(fused, uids, nrows, gsum)


# --------------------------------------------------------------------------
# rows [V, D] (+ separate [V, A] accumulator) layout — resident rows path
# and the tiered paramstore's compact [C, D] device table
# --------------------------------------------------------------------------


def _rows_kernel(
    uids_ref, nrows_ref, g_ref, table_ref, accum_ref, t_out_ref, a_out_ref,
    tbuf, abuf, tin_sem, ain_sem, tout_sem, aout_sem,
    *, lr: float, decay: float, d: int, a: int, blk: int, nblocks: int,
    vmax: int,
):
    i = pl.program_id(0)
    nrows = nrows_ref[0]

    def _run(block, slot, *, outward, wait):
        base = block * blk

        def body(j, _):
            @pl.when(base + j < nrows)
            def _():
                row = jnp.minimum(uids_ref[base + j], vmax - 1)
                for hbm_in, hbm_out, vbuf, isem, osem in (
                    (table_ref, t_out_ref, tbuf, tin_sem, tout_sem),
                    (accum_ref, a_out_ref, abuf, ain_sem, aout_sem),
                ):
                    vref = vbuf.at[slot, j]
                    href = (hbm_out if outward else hbm_in).at[row]
                    src, dst = (vref, href) if outward else (href, vref)
                    cp = pltpu.make_async_copy(
                        src, dst, (osem if outward else isem).at[slot]
                    )
                    cp.wait() if wait else cp.start()
            return 0

        @pl.when(base < nrows)
        def _():
            lax.fori_loop(0, blk, body, 0)

    def compute(slot):
        w = tbuf[slot]  # [blk, d]
        acc = abuf[slot]  # [blk, a]
        g = g_ref[...]  # [blk, d]
        if a == 1:  # row-granularity accumulator
            asq = jnp.sum(g * g, axis=-1, keepdims=True)
        else:  # element granularity (TF-Adagrad parity)
            asq = g * g
        acc_prev = acc if decay == 1.0 else decay * acc
        acc2 = acc_prev + asq
        tbuf[slot] = w - lr * g / jnp.sqrt(acc2)
        abuf[slot] = acc2

    _schedule(
        i, nblocks,
        start_in=lambda b, s: _run(b, s, outward=False, wait=False),
        wait_in=lambda b, s: _run(b, s, outward=False, wait=True),
        start_out=lambda b, s: _run(b, s, outward=True, wait=False),
        wait_out=lambda b, s: _run(b, s, outward=True, wait=True),
        compute=compute,
    )


def rows_tail_adagrad_update(
    table: jax.Array,
    accum: jax.Array,
    ids: jax.Array,
    row_grads: jax.Array,
    lr: float,
    *,
    decay: float = 1.0,
    interpret: bool | None = None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> tuple[jax.Array, jax.Array]:
    """``optim.sparse_adagrad_update`` as one kernel pass.

    Same dedup (``optim.dedup_rows``), same accumulator expressions, same
    lazy-decay semantics — the accumulator bit-identical, the table
    bit-identical with the row accumulator and within a few ULP with the
    element one (test-pinned: the classic tail rounds ``-lr·g/√acc``
    before its one scatter-add, the kernel's ``w − lr·g/√acc`` fuses);
    what changes is HOW the unique rows move: one double-buffered DMA
    pass instead of the gather program + scatter program pair.
    """
    interpret = resolve_interpret(interpret)
    v, d = table.shape
    a = accum.shape[-1]
    uids, gsum = dedup_rows(ids.reshape(-1), row_grads.reshape(-1, d), v)
    m = uids.shape[0]
    nrows = jnp.sum(uids < v).astype(jnp.int32)[None]
    blk = max(8, min(block_rows, m))
    nblocks = _nblocks(m, blk)
    uids = _pad_ids(uids.astype(jnp.int32), nblocks * blk, v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((blk, d), lambda i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, blk, d), jnp.float32),
            pltpu.VMEM((2, blk, a), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _rows_kernel, lr=float(lr), decay=float(decay), d=d, a=a, blk=blk,
        nblocks=nblocks, vmax=v,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(table.shape, table.dtype),
            jax.ShapeDtypeStruct(accum.shape, accum.dtype),
        ),
        input_output_aliases={3: 0, 4: 1},  # table and accum in place
        interpret=interpret,
    )(uids, nrows, gsum, table, accum)
