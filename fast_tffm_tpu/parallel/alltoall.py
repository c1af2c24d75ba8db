"""All-to-all routed embedding lookup (SparseCore-style id routing).

Optional alternative to ``embedding.sharded_gather`` (config key
``lookup = alltoall``).  The default all-gather scheme ships every chip's
full masked ``[R·B_local, N, D]`` contribution through ``psum_scatter`` —
R× the minimal bytes, because each row has exactly one owner.  Here each
chip instead routes its ids to their home shards and gets back only its
own rows:

  1. owner = id // shard_rows (contiguous row shards, same layout as the
     all-gather path — checkpoints are interchangeable);
  2. ids sort by owner into a ``[R, C]`` send buffer (C = capacity per
     destination), `lax.all_to_all` delivers each shard its requests;
  3. each shard serves its rows locally and a second all_to_all returns
     them; an inverse permutation restores batch order.

ICI bytes per chip: ~2·R·C·D ≈ 2·slack·M·D instead of R·M·D — an
~(R/2·slack)× reduction that grows with the mesh (R=64 on a v5e-64).

**Capacity and skew.**  Static shapes force a fixed per-destination
capacity C = ceil(capacity_factor · M / R).  With ``hash_feature_id``
(the 10B-row regime this path exists for) ids are uniform and
capacity_factor=2 overflows with negligible probability.  Zipf-skewed
RAW ids on contiguous shards can overflow; overflow is NEVER silent.
What happens next is the caller's ``lookup_overflow`` choice
(train_step.py): ``fallback`` (default) reruns the whole step through
the allgather collectives under ``lax.cond`` — deterministic, exactly
the allgather result, counted in the metrics — while ``abort`` poisons
every affected row to NaN so the loss goes NaN on the first overflowing
step and the run stops before checkpointing (both test-pinned).
``routing_overflow`` below is the globally-agreed predicate the
fallback branches on.

These functions run INSIDE a shard_map body (parallel/train_step.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from fast_tffm_tpu.parallel.exchange import exchange_scope
from fast_tffm_tpu.parallel.mesh import DATA_AXIS, ROW_AXIS, axis_size

__all__ = ["routed_gather", "routed_update", "routing_overflow", "capacity_for"]


def routing_overflow(ids: jnp.ndarray, shard_rows: int, capacity: int):
    """GLOBAL flag: would routing this batch overflow any destination?

    Computed from the gather-direction bucket counts alone: the update
    direction buckets the DEDUPED ids, and per-owner unique counts can
    never exceed per-owner occurrence counts, so (with the shared
    capacity) "gather fits" implies "update fits".  The psum makes every
    chip agree — the caller can branch on it (lax.cond) without risking
    divergent collectives.
    """
    R = axis_size(ROW_AXIS)
    counts = jnp.bincount(ids.reshape(-1) // shard_rows, length=R)
    local = jnp.any(counts > capacity).astype(jnp.int32)
    with exchange_scope("fm.gather"):
        chips_over = lax.psum(local, (DATA_AXIS, ROW_AXIS))
    return chips_over > 0


def capacity_for(ids_per_chip: int, row_parallel: int, capacity_factor: float) -> int:
    """Per-destination slot count for M ids over R destinations.

    factor·M/R covers systematic imbalance; the additive 4·√(M/R) + 8 term
    covers the binomial tail, which dominates when M/R is small (without
    it, even uniform ids overflow a thin bucket with noticeable
    probability at toy sizes).  Rounded to a multiple of 8, capped at M
    (C = M can never overflow)."""
    mean = ids_per_chip / row_parallel
    c = int(capacity_factor * mean + 4.0 * mean**0.5 + 8.0)
    c = ((c + 7) // 8) * 8
    return max(8, min(c, ids_per_chip))


def _bucketize(owner: jnp.ndarray, n_buckets: int, capacity: int):
    """Stable-sort elements by ``owner`` and assign each a send-buffer slot.

    Shared by the lookup and update routes (they must agree exactly —
    both directions use one capacity).  Owners >= n_buckets (sentinels)
    are excluded from counts and land on out-of-range scatter indices.

    Returns (order, sorted_owner, send_pos, in_cap_sorted, overflow):
    ``order`` is the sort permutation; element ``order[j]`` goes to slot
    ``[sorted_owner[j], send_pos[j]]`` (send_pos == capacity → caller
    scatters with mode='drop'); ``overflow`` is True when any bucket
    exceeded capacity."""
    m = owner.shape[0]
    order = jnp.argsort(owner, stable=True)
    sorted_owner = owner[order]
    counts = jnp.bincount(owner, length=n_buckets)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(m) - starts[jnp.minimum(sorted_owner, n_buckets - 1)]
    in_cap = pos < capacity
    send_pos = jnp.where(in_cap, pos, capacity)
    overflow = jnp.any(counts > capacity)
    return order, sorted_owner, send_pos, in_cap, overflow


@jax.named_scope("fm.gather")
def routed_gather(
    table_shard: jnp.ndarray,
    ids: jnp.ndarray,
    capacity: int,
    *,
    d: int | None = None,
    shard_logical_rows: int | None = None,
    fused: bool = False,
) -> jnp.ndarray:
    """Assemble this chip's rows via all-to-all id routing.

    table_shard: [V/R, D] contiguous row shard — or, when ``d`` is given,
                 a lane-packed [VPs, 128] shard (ops/packed_table.py) of
                 ``shard_logical_rows`` logical rows (``fused=True``: the
                 fused tile-row layout, accumulator lanes in-slot).  The
                 routing math is identical either way (ids are LOGICAL
                 everywhere); only the local serve step reads the layout,
                 via a wide full-tile-row gather instead of a narrow one.
    ids:         [B_local, N] global row ids for THIS chip's micro-batch.
    capacity:    static per-destination slot count (see capacity_for).
    Returns:     [B_local, N, D] rows (NaN-poisoned if any destination
                 overflowed its capacity — never silently wrong).
    """
    packed = d is not None
    shard_rows = shard_logical_rows if packed else table_shard.shape[0]
    base = lax.axis_index(ROW_AXIS) * shard_rows
    R = axis_size(ROW_AXIS)
    B, N = ids.shape
    M = B * N
    flat = ids.reshape(M)
    owner = flat // shard_rows  # [M] in [0, R)
    order, sorted_owner, send_pos, in_cap, overflow = _bucketize(owner, R, capacity)
    sorted_ids = flat[order]

    # Scatter into the [R, C] send buffer; slots beyond capacity drop (their
    # rows are poisoned below), unused slots carry an out-of-range sentinel.
    sentinel = jnp.int32(shard_rows * R)
    send_ids = jnp.full((R, capacity), sentinel, dtype=flat.dtype)
    send_ids = send_ids.at[sorted_owner, send_pos].set(sorted_ids, mode="drop")

    # Exchange requests; serve locally; exchange answers.
    with exchange_scope():
        recv_ids = lax.all_to_all(send_ids, ROW_AXIS, 0, 0, tiled=True)  # [R, C]
    local = recv_ids - base
    ok = (local >= 0) & (local < shard_rows)  # sentinels fail
    safe = jnp.where(ok, local, 0)
    if packed:
        from fast_tffm_tpu.ops.packed_table import fused_gather, packed_gather

        served = (fused_gather if fused else packed_gather)(table_shard, safe, d)
    else:
        served = table_shard[safe]
    served = served * ok[..., None].astype(served.dtype)
    with exchange_scope():
        recv_rows = lax.all_to_all(served, ROW_AXIS, 0, 0, tiled=True)  # [R, C, D]

    # recv_rows[s, c] answers MY request in send slot [s, c]; invert the
    # bucket placement, then the sort.
    mine_sorted = recv_rows[sorted_owner, jnp.minimum(send_pos, capacity - 1)]
    mine_sorted = mine_sorted * in_cap[:, None].astype(mine_sorted.dtype)
    out = jnp.zeros((M, served.shape[-1]), served.dtype).at[order].set(mine_sorted)
    out = jnp.where(overflow, jnp.nan, out)
    return out.reshape(B, N, -1)


def routed_update(
    table_shard: jnp.ndarray,
    accum_shard: jnp.ndarray,
    ids: jnp.ndarray,
    row_grads: jnp.ndarray,
    lr: float,
    num_rows_global: int,
    capacity: int,
    *,
    shard_logical_rows: int | None = None,
    packed_mode: str | None = None,
    fused: bool = False,
    compact_cap: int = 0,
    decay: float = 1.0,
):
    """Sparse Adagrad update via routed gradients (the all-to-all analog of
    ``embedding.sharded_sparse_adagrad_update``).

    When ``shard_logical_rows`` is given the shards are LANE-PACKED
    ([VPs, 128] — ops/packed_table.py; ``fused=True``: the fused tile-row
    layout, whose apply is table-only and returns ``accum_shard``
    untouched) and ``packed_mode`` picks the
    packed tail ('dense' | 'compact' | 'sorted'); the routing is unchanged
    (deduped logical ids + summed grads ride the same all_to_all), only
    the final per-shard apply reads/writes the packed layout.

    Per chip: dedup local occurrences, route each (id, summed grad) to its
    home shard over ROW (all_to_all, capacity C per destination), then
    all_gather the received buffers over DATA only — every replica of a
    row shard sees the identical union of contributions and applies Adagrad
    exactly once per row: the rows layout through
    ``embedding.apply_shard_adagrad``, whose tail sums a row's contributions
    itself; the packed layouts after one more dedup of the union.  ICI bytes
    ~ data·(R·C)·D ≈ data·slack·M·D instead of data·row·M·D.  Scopes: the
    dedups stand under ``fm.dedup`` alone, the routing, its collectives
    (``fm.tail/fm.exchange``) and the apply under ``fm.tail``.

    Returns (table, accum, overflow) — ``overflow`` is a GLOBAL flag
    (psum over both axes): any chip that had to drop contributions raises
    it, and the caller must poison its loss with it so the run aborts
    before a silently-partial update is ever checkpointed.  (Dropped
    entries leave the tables CONSISTENT across replicas — every replica
    sees the same post-drop union — just not the full-batch update.)
    """
    from fast_tffm_tpu.optim import dedup_rows

    packed = shard_logical_rows is not None
    if packed and not fused and packed_mode not in ("dense", "compact", "sorted"):
        raise ValueError(
            f"packed routed_update needs packed_mode 'dense', 'compact' or "
            f"'sorted', got {packed_mode!r} (pass resolve_packed_update's result)"
        )
    if fused and packed_mode not in ("dense", "compact"):
        raise ValueError(
            f"fused routed_update needs packed_mode 'dense' or 'compact', "
            f"got {packed_mode!r} (pass resolve_fused_update's result)"
        )
    if fused and shard_logical_rows is None:
        # Without the logical shard size the routing would divide by the
        # PHYSICAL fused row count and send ids to the wrong shards —
        # wrong-but-finite results, so refuse loudly instead.
        raise ValueError("fused routed_update requires shard_logical_rows")
    D = row_grads.shape[-1]
    shard_rows = shard_logical_rows if packed else table_shard.shape[0]
    R = axis_size(ROW_AXIS)
    uids, gsum = dedup_rows(ids.reshape(-1), row_grads.reshape(-1, D), num_rows_global)
    with jax.named_scope("fm.tail"):
        # Sentinel uids (>= num_rows_global) route to owner R: excluded from
        # counts (bincount length R) and dropped by the out-of-range scatter.
        owner = jnp.where(uids >= num_rows_global, R, uids // shard_rows)
        order, sorted_owner, send_pos, _in_cap, overflow = _bucketize(owner, R, capacity)
        sorted_ids = uids[order]
        sorted_g = gsum[order]

        sentinel = jnp.asarray(num_rows_global, uids.dtype)
        send_ids = jnp.full((R, capacity), sentinel, dtype=uids.dtype)
        send_g = jnp.zeros((R, capacity, D), gsum.dtype)
        send_ids = send_ids.at[sorted_owner, send_pos].set(sorted_ids, mode="drop")
        send_g = send_g.at[sorted_owner, send_pos].set(sorted_g, mode="drop")

        with exchange_scope():
            recv_ids = lax.all_to_all(send_ids, ROW_AXIS, 0, 0, tiled=True)  # [R, C]
            recv_g = lax.all_to_all(send_g, ROW_AXIS, 0, 0, tiled=True)  # [R, C, D]
        # Data-axis union: every replica of this row shard must apply the SAME
        # update, so gather all data-peers' received contributions.
        recv_ids, recv_g = recv_ids.reshape(-1), recv_g.reshape(-1, D)
        with exchange_scope():
            all_ids = lax.all_gather(recv_ids, DATA_AXIS, tiled=True)
            all_g = lax.all_gather(recv_g, DATA_AXIS, tiled=True)
        overflow = overflow.astype(jnp.int32)
        with exchange_scope():
            chips_over = lax.psum(overflow, (DATA_AXIS, ROW_AXIS))

    if not packed:
        # The rows layout: the shard's tail sums what several chips sent for
        # one row itself, under the scopes it names (fm.dedup, fm.tail).
        from fast_tffm_tpu.parallel.embedding import apply_shard_adagrad

        # ``R x C`` slots from every data peer: what the all-gather update's
        # tail is bounded to (train_step.shard_tail_ids), by construction.
        table_shard, accum_shard, _ = apply_shard_adagrad(
            table_shard, accum_shard, all_ids, all_g, lr, decay=decay
        )
        return table_shard, accum_shard, chips_over > 0

    from fast_tffm_tpu.parallel.embedding import owned_local_ids

    guids, ggsum = dedup_rows(all_ids, all_g, num_rows_global)
    with jax.named_scope("fm.tail"):
        if fused:
            from fast_tffm_tpu.ops.packed_table import (
                apply_fused_update,
                fused_rows_per_tile,
            )

            p = fused_rows_per_tile(D)
            local, _ = owned_local_ids(guids, shard_rows, table_shard.shape[0] * p)
            table_shard = apply_fused_update(
                table_shard, local, ggsum, lr, packed_mode, compact_cap
            )
        else:
            from fast_tffm_tpu.ops.packed_table import PACKED_UPDATE_FNS, rows_per_tile

            p = rows_per_tile(D)
            # Unowned and sentinel ids map past the last physical row → drop.
            local, _ = owned_local_ids(guids, shard_rows, table_shard.shape[0] * p)
            update_fn = PACKED_UPDATE_FNS[packed_mode]
            table_shard, accum_shard = update_fn(
                table_shard, accum_shard, local, ggsum, lr
            )
    return table_shard, accum_shard, chips_over > 0
