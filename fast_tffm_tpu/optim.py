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
shapes, no `jnp.unique`), then a single gather→update→scatter touches each
unique row exactly once.  Touching each row once matters: Adagrad is not
linear in g (accum += g² must see the *summed* gradient, and duplicate
scatter targets would race).

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

__all__ = ["AdagradState", "init_adagrad", "dense_adagrad_update", "sparse_adagrad_update", "dedup_rows"]


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


def dedup_rows(ids: jax.Array, row_grads: jax.Array, num_rows: int):
    """Sum per-occurrence row gradients over duplicate ids.

    Args:
      ids:       [M] int row ids (flattened batch×nnz), may repeat.
      row_grads: [M, D] gradient per occurrence.
      num_rows:  table row count V (used as the drop sentinel).

    Returns:
      (uids [M], gsum [M, D]): unique ids with their summed gradients in the
      leading segments; trailing slots carry the sentinel id ``num_rows``
      (out of range → scattered with mode='drop') and zero gradients.
    """
    m = ids.shape[0]
    with jax.named_scope("fm.dedup"):
        order = jnp.argsort(ids)
        sid = ids[order]
        sg = row_grads[order]
        is_new = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
        seg = jnp.cumsum(is_new) - 1  # [M] segment index per occurrence
        gsum = jax.ops.segment_sum(sg, seg, num_segments=m)
        # Segment representative via scatter-SET, not segment_max (measured
        # ~9 ms slower as a 1-D scatter-max on this backend): every
        # occurrence in a segment writes the SAME sid, so any duplicate
        # winning is correct; unwritten trailing slots keep the sentinel
        # ``num_rows`` (out of range → scattered with mode='drop').
        uids = jnp.full((m,), num_rows, sid.dtype).at[seg].set(sid)
    return uids, gsum


def sparse_adagrad_update(
    table: jax.Array,
    state: AdagradState,
    ids: jax.Array,
    row_grads: jax.Array,
    lr: float,
    decay: float = 1.0,
):
    """Sparse Adagrad step on a ``[V, D]`` table.

    ids: [...] int ids; row_grads: [..., D] matching occurrence grads.
    Only the unique touched rows are read and written.

    ``decay`` γ < 1 decays the accumulator LAZILY — only the rows a step
    touches pay ``accum = γ·accum + g²`` (an untouched row's history is
    also its recency: decaying it would shrink step sizes for rows that
    saw no data, the opposite of what a moving distribution needs, and a
    per-step O(V) sweep would erase the sparse update's whole point).
    γ=1.0 is a trace-time branch to the exact classic expression — same
    XLA program, bit-identical results (test-pinned on all three train
    paths)."""
    D = table.shape[-1]
    uids, gsum = dedup_rows(ids.reshape(-1), row_grads.reshape(-1, D), table.shape[0])
    with jax.named_scope("fm.tail"):
        acc_prev = state.accum[uids]
        if decay != 1.0:
            acc_prev = decay * acc_prev
        acc_rows = acc_prev + accum_sq(state.accum, gsum)  # sentinel lanes
        upd_rows = table[uids] - lr * gsum / jnp.sqrt(acc_rows)  # dropped below
        accum = state.accum.at[uids].set(acc_rows, mode="drop")
        table = table.at[uids].set(upd_rows, mode="drop")
    return table, AdagradState(accum)
