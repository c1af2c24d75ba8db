"""Model-agnostic jitted train/predict steps (single device).

The TPU-native analog of the reference's session step loop
(`renyi533/fast_tffm` :: local trainer: sess.run(train_op) over the graph
parser → gather → scorer → loss → Adagrad scatter-add).  Here one jitted
function fuses gather (row by row, or, where a big batch reads a table of
sub-tile rows on a TPU, one kernel sweep of its transposed view with the
result sorted back to batch order: gather_form) → fused scorer (custom VJP)
→ loss → dedup (one sort
of the ids, the gradients brought to that order; ahead of row operations
also a segment sum on tile-wide rows) → sparse Adagrad tail (row by row:
accumulator gather and scatter-set, table scatter-add; or, where the batch
touches most of a table of sub-tile rows on a TPU, one in-place kernel
sweep that sums duplicates itself: optim.rows_tail_form, asked before the
dedup); XLA compiles the whole step into a single
program whose ops carry the stage's name (``fm.gather``,
``fm.interaction``, ``fm.loss``, ``fm.dedup``, ``fm.tail``).

The mesh-sharded variant lives in parallel/train_step.py and reuses these
loss pieces; this module is also its single-shard reference semantics.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from fast_tffm_tpu.models.base import Batch, logistic_loss
from fast_tffm_tpu.optim import (
    AdagradState,
    dense_adagrad_update,
    init_adagrad,
    init_table_adagrad,
    sort_ids,
    sparse_adagrad_update,
)

__all__ = [
    "TrainState",
    "init_state",
    "gather_form",
    "gather_profile",
    "describe_gather",
    "gather_rows",
    "train_step_body",
    "make_train_step",
    "make_decayed_body",
    "make_dedup_body",
    "make_accum_restart",
    "make_scanned_train_step",
    "make_predict_step",
    "pack_state",
    "init_packed_state",
    "packed_train_step_body",
    "make_packed_train_step",
    "make_packed_predict_step",
]


class TrainState(NamedTuple):
    table: jax.Array  # [V, D] sparse parameter table
    table_opt: AdagradState
    dense: Any  # dense params pytree ({} for FM/FFM)
    dense_opt: Any
    step: jax.Array  # i64 scalar


def init_table_state(model, key: jax.Array, init_accumulator_value: float, accumulator: str):
    """(table, its Adagrad state) of ``init_state``, from ITS table key: the
    half the sharded init draws shard by shard under one jit
    (parallel/train_step.init_sharded_state)."""
    table = model.init_table(key)
    return table, init_table_adagrad(table, init_accumulator_value, accumulator)


def init_dense_state(model, key: jax.Array, init_accumulator_value: float):
    """(dense params, their Adagrad state) of ``init_state``, from its dense key."""
    dense = model.init_dense(key)
    return dense, init_adagrad(dense, init_accumulator_value)


def init_state(
    model,
    key: jax.Array,
    init_accumulator_value: float = 0.1,
    accumulator: str = "element",
) -> TrainState:
    """``accumulator``: table-accumulator granularity — ``element`` ([V, D],
    TF-Adagrad parity) or ``row`` ([V, 1], D×-smaller optimizer state;
    measured speed-neutral — see optim.py).  The dense (MLP) path is
    always element-wise."""
    k1, k2 = jax.random.split(key)
    table, table_opt = init_table_state(model, k1, init_accumulator_value, accumulator)
    dense, dense_opt = init_dense_state(model, k2, init_accumulator_value)
    return TrainState(
        table=table,
        table_opt=table_opt,
        dense=dense,
        dense_opt=dense_opt,
        step=jnp.zeros((), jnp.int32),
    )


# What the forward gather costs in its two forms on a TPU v5e (PERF.md §6,
# PR 40: every piece alone, medians of eight calls, at 2^26 x 9 under
# 2,555,904 uniform ids, 2^25 x 31 under 720,896 and 2^25 x 17 under
# 2,555,904).  Row by row, a lane-major row is one single-lane read a float:
# 22.3 / 30.8 / 39.0 ns a row of 9 / 17 / 31 floats, which is 5.6 ns and
# 8.35 for every group of 8 sublanes the row spans.  As a sweep the table's
# padded bytes pass through the kernel in 17.9 ms of its 22.1 and 16.2 at
# the first two shapes (4.3 GB both: 240 GB/s, where the tail's sweep
# streams 560; its grid and its contraction bind it, not the HBM), and an id
# of a row of 9 pays 13.5 ns: 2.1 for its share of the sort with positions,
# 3.6 for the work list and its chunk's grid step, 7.8 on the way back to
# batch order as one of the ten operands of a sort: the sort's cost, which
# grows faster than its operands (18 read 44.7 ms where ten read 20.0, PR
# 32); gathered row by row instead, the way back lost at both shapes read
# (2^25 x 31: 30.3 ms against 28.1; 2^25 x 17, 2,555,904 ids: 114 against
# 78.7).  So the rule says ``rows`` past ``_SWEEP_MAX_D`` floats, the widest
# way back read.  A row shard's lookup has a tile-wide way back of its own
# (parallel/embedding.shard_lookup_form; it wins at 17 floats there).
_ROW_NS = 5.6
_ROW_TILE_NS = 8.35
_GATHER_SWEEP_BYTES_PER_S = 240e9
_SWEEP_ID_NS = 13.5
_SWEEP_MAX_D = 9


def gather_form(num_rows: int, m: int, d: int, backend: str | None = None) -> str:
    """Which form ``gather_rows`` takes for ``m`` ids on ``num_rows`` rows of
    ``d`` when nobody says: ``"sweep"`` (ops.pallas_gather.sweep_gather, the
    table read once through its transposed view, the result sorted back to
    batch order) or ``"rows"`` (XLA's row gather).  A trace-time function of
    the shapes and the backend, as ``optim.rows_tail_form`` is the tail's:

      * only a TPU takes the sweep (anywhere else the kernel would run
        interpreted inside every step);
      * only rows of at most ``_SWEEP_MAX_D`` floats: those are held
        lane-major (a row read is one single-lane access a float, the
        transposed view the kernel takes is a bitcast), and their way back
        to batch order is the one the chip has read.  A wider row keeps the
        row gather, which won wherever the two were read;
      * only where the sweep's work list fits the scalar memory
        (ops.pallas_tail.sweep_fits);
      * only where the table's bytes, once, at the rate the kernel reaches
        plus what an id costs on its way through the sort, the kernel and
        back take less time than the ids' row reads.  ``fm8_criteo`` (2^26
        rows of 9, 65,536 x 39 ids): 17.9 + 34.5 ms against 57.0, the sweep
        (the two cross at 2.03M ids); the same table under a serving flush
        of at most 512 x 39 ids: 17.9 ms against 0.45, the rows.
    """
    if (backend or jax.default_backend()) != "tpu" or d > _SWEEP_MAX_D:
        return "rows"
    from fast_tffm_tpu.ops.pallas_tail import sweep_fits

    if not sweep_fits(num_rows, d, m):
        return "rows"
    tiles = -(-d // 8)
    sweep_s = num_rows * 4 * 8 * tiles / _GATHER_SWEEP_BYTES_PER_S + m * _SWEEP_ID_NS * 1e-9
    return "sweep" if sweep_s < m * (_ROW_NS + _ROW_TILE_NS * tiles) * 1e-9 else "rows"


def gather_profile(num_rows: int, m: int, d: int, form: str = "rows") -> dict:
    """The forward gather's trace-time choice as a step's ``kind=profile``
    record carries it: ``gather_form`` and, under the sweep,
    ``gather_items`` = the kernel's grid length a step (static: every block
    of the table once plus every chunk of the ids once); null under the
    rows."""
    items = None
    if form == "sweep":
        from fast_tffm_tpu.ops.pallas_gather import sweep_gather_items

        items = sweep_gather_items(num_rows, d, m)
    return dict(gather_form=form, gather_items=items)


def describe_gather(num_rows: int, m: int, d: int, form: str = "rows") -> str:
    """``gather_profile`` as one start-up line (a trace-time choice, so it
    is said once)."""
    if form == "sweep":
        return (
            f"pallas sweep of table.T ({gather_profile(num_rows, m, d, form)['gather_items']} "
            f"grid items a step; ids sorted once, columns brought back to batch "
            f"order as sort operands, row width {d})"
        )
    return f"xla row gather ({m} rows of {d} a step)"


def gather_rows(table: jax.Array, ids: jax.Array, form: str | None = None) -> jax.Array:
    """``table[ids]``: the rows-layout gather of touched rows only, under
    the step's ``fm.gather`` scope (the packed and sharded gathers carry
    the same name where they live), in one of two forms that ``gather_form``
    chooses between from the shapes (``form``: a test names one to run it on
    any backend).  ``rows``: XLA's gather, a row at a time.  ``sweep``: the
    ids are sorted once with their positions (``optim.sort_ids``), the table
    is read once through its transposed view, block by block, and every id
    takes its row's values through a one-hot contraction of exact bfloat16
    parts (ops.pallas_gather.sweep_gather: ``[D, M]`` in id order), and the
    ``D`` columns go back to batch order as the operands of ONE sort keyed
    by the positions.  Nothing is rounded: the result is ``table[ids]`` bit
    for bit but for the kernel's three stated limits (-0.0 comes back as
    0.0, a value under about 1e-33 loses its last part, a non-finite value
    reaches its 128-row group's other rows that the same chunk reads), ids
    outside ``[0, V)`` included (brought to the row XLA's gather reads,
    before the sort)."""
    v, d = table.shape
    flat = ids.reshape(-1)
    if form is None:
        form = gather_form(v, flat.shape[0], d)
    with jax.named_scope("fm.gather"):
        if form != "sweep":
            return table[ids]
        from fast_tffm_tpu.ops.pallas_gather import sweep_gather

        # What ``table[ids]`` reads for an id outside [0, V): a negative id
        # counts from the end, then the nearest row.
        flat = jnp.clip(jnp.where(flat < 0, flat + v, flat), 0, v - 1)
        sid, order = sort_ids(flat, v)
        cols = sweep_gather(table, sid)  # [D, M], in id order
        # The positions are unique: an unstable sort loses nothing.
        _, *cols = lax.sort((order, *cols), num_keys=1, is_stable=False)
        return jnp.stack(cols, axis=-1).reshape(*ids.shape, d)


def batch_loss(model, table_rows, dense, batch: Batch):
    """(total loss with L2, plain data loss).  The scorer names itself
    ``fm.interaction`` (ops/fm.py); its backward arrives in the compiled
    step as ``transpose(jvp(fm.interaction))``."""
    scores = model.score(table_rows, dense, batch)
    with jax.named_scope("fm.loss"):
        data_loss = logistic_loss(scores, batch.labels, batch.weights)
        reg = model.regularization(table_rows, dense, batch)
        return data_loss + reg, data_loss


def train_step_body(
    model, learning_rate: float, state: TrainState, batch: Batch,
    decay: float = 1.0, gather=gather_rows,
):
    """The (unjitted) single-device step, by the scope its ops carry:
    ``fm.gather`` (the batch's rows, in the form ``gather_form`` chooses) →
    ``fm.interaction`` (fused scorer and its backward; DeepFM's perceptron:
    ``deepfm.feed``, ``deepfm.mlp``) → ``fm.loss`` → ``fm.dedup`` → ``fm.tail``
    → ``deepfm.dense_update`` (a model with dense leaves).  The tail takes one
    of two forms (``optim.rows_tail_form``, before it dedups).  ``rows``: ``fm.dedup`` is
    one sort for ids and order, the permutation gather, a segment sum on
    128-lane rows and the unique ids by a second sort; ``fm.tail`` one
    gather and one scatter-set of the accumulator and one scatter-add into
    the table, all declared sorted and unique.  ``sweep``: ``fm.dedup`` is
    the sort and the permutation gather alone (the occurrences in id order,
    duplicates not summed); ``fm.tail`` the in-place kernel pass, whose
    contraction sums a row's occurrences.
    Shared verbatim by ``make_train_step`` and the device-cache step
    (data/device_cache.py) so the two paths are the SAME math on the same
    values — the bit-identity their parity test pins.

    ``decay`` is the online-learning ``[Online] adagrad_decay`` γ (lazy
    touched-row accumulator decay — optim.sparse_adagrad_update); γ=1.0
    branches back to the exact classic program at trace time.  ``gather``
    reads the batch's rows ``[B, N, D]`` from the table
    (``make_dedup_body`` passes another)."""
    rows = gather(state.table, batch.ids)  # [B, N, D]

    grad_fn = jax.value_and_grad(
        partial(batch_loss, model), argnums=(0, 1), has_aux=True
    )
    (_, data_loss), (g_rows, g_dense) = grad_fn(rows, state.dense, batch)

    table, table_opt = sparse_adagrad_update(
        state.table, state.table_opt, batch.ids, g_rows, learning_rate,
        decay=decay,
    )
    dense, dense_opt = state.dense, state.dense_opt
    if jax.tree.leaves(state.dense):
        with jax.named_scope("deepfm.dense_update"):
            leaves = (state.dense, state.dense_opt, g_dense, learning_rate)
            dense, dense_opt = dense_adagrad_update(*leaves, decay=decay)
    return (
        TrainState(table, table_opt, dense, dense_opt, state.step + 1),
        data_loss,
    )


def make_train_step(model, learning_rate: float, decay: float = 1.0, body=None):
    """Returns jitted ``step(state, batch) -> (state, data_loss)``.

    The state is donated: the table/accumulator buffers update in place
    (XLA aliases input and output), so a step never copies the [V, D]
    table — the difference between O(nnz) and O(V) HBM traffic per step.
    Callers must rebind ``state`` to the returned value (all drivers do).

    ``body`` overrides the step body (same ``(model, lr, state, batch)``
    contract as the scanned/device-cache factories) — the dedup-gather
    variant plugs in here.
    """
    body = body or (
        lambda m, lr, st, b: train_step_body(m, lr, st, b, decay)
    )

    @partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, batch: Batch):
        return body(model, learning_rate, state, batch)

    return step


def make_decayed_body(decay: float):
    """``train_step_body`` with ``[Online] adagrad_decay`` γ baked in — the
    ``body`` shape the scanned and device-cache step factories take."""

    def body(model, learning_rate, state, batch):
        return train_step_body(model, learning_rate, state, batch, decay)

    return body


def make_dedup_body(cap: int, decay: float = 1.0):
    """Device-side dedup-before-gather (ROADMAP D12): ``train_step_body``
    whose forward gather reads each of the batch's ≤ ``cap`` UNIQUE rows
    from the [V, D] table exactly once; per-slot re-reads index a compact
    ``[cap, D]`` buffer instead of HBM.  Gathered VALUES are identical to
    the direct gather, so the loss/grad pipeline — and the unchanged sparse
    Adagrad update — produce bit-identical results (test-pinned).

    ``cap`` must bound the batch's unique-id count; the input stream
    VERIFIES that per batch before shipping (training._stream's dedup
    guard), so a too-small cap is a loud error, never silent truncation
    (``jnp.unique(size=...)`` would otherwise drop the largest ids).
    Same ``body`` contract as the scanned/device-cache factories."""

    def gather(table, ids):
        v, d = table.shape
        flat = ids.reshape(-1)
        # Sorted unique ids padded with the out-of-range sentinel ``v``
        # (the gather clamps it to a row whose value is never used).
        with jax.named_scope("fm.gather"):
            uids = jnp.unique(flat, size=cap, fill_value=v)
            compact = table[jnp.minimum(uids, v - 1)]
            inv = jnp.searchsorted(uids, flat)
            return compact[inv].reshape(*ids.shape, d)

    def body(model, learning_rate, state: TrainState, batch: Batch):
        return train_step_body(
            model, learning_rate, state, batch, decay, gather
        )

    return body


def make_accum_restart(init_accumulator_value: float):
    """Jitted ``state -> state`` resetting every Adagrad accumulator to
    the init value — the window-restart alternative to ``adagrad_decay``
    (``[Online] accum_restart_steps``): on a moving distribution, a hard
    periodic restart re-opens the step size for EVERY row at once.

    Exact for the rows layout and for packed element/row accumulators
    alike: ``pack_accum*`` fills padding slots with the init value, so a
    full ``full_like(accum, init)`` reproduces the packed init state
    bit-for-bit.  (The fused layout stores its accumulator inside the
    table's own tile rows — config.validate rejects the combination.)
    Donated, so the reset is an in-place sweep, no table copy."""

    @partial(jax.jit, donate_argnums=(0,))
    def reset(state: TrainState):
        table_acc = jnp.full_like(
            state.table_opt.accum, init_accumulator_value
        )
        dense_acc = jax.tree.map(
            lambda a: jnp.full_like(a, init_accumulator_value),
            state.dense_opt.accum,
        )
        return state._replace(
            table_opt=state.table_opt._replace(accum=table_acc),
            dense_opt=state.dense_opt._replace(accum=dense_acc),
        )

    return reset


def make_scanned_train_step(model, learning_rate: float, body=None):
    """Returns jitted ``step(state, superbatch) -> (state, losses [K])``
    fusing K consecutive train steps into ONE dispatch via ``lax.scan``.

    ``superbatch`` is a Batch whose every field carries a leading micro-step
    dim ([K, B], [K, B, N], ...) — Batch.stack_parsed's output.  K is read
    from the input shape, so one Python function serves both the main fused
    call and the epoch-tail remainder (batches % K) — each K compiles once.
    The scan body is ``body`` (default trainer.train_step_body; the packed
    driver passes its packed body), i.e. the SAME function the K=1 step
    jits, applied to the same values in the same order — per-step losses
    and the final state are bit-identical to K sequential K=1 steps
    (test-pinned in tests/test_steps_per_call.py).  State donation is
    preserved: the scan carry aliases the donated input buffers, so the
    [V, D] table still updates in place across all K micro-steps.
    """
    body = body or train_step_body

    @partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, superbatch: Batch):
        def one(st, b):
            st, loss = body(model, learning_rate, st, b)
            return st, loss

        return lax.scan(one, state, superbatch)

    return step


def make_predict_step(model):
    """Returns jitted ``predict(state, batch) -> sigmoid scores [B]``."""

    @jax.jit
    def predict(state: TrainState, batch: Batch):
        rows = gather_rows(state.table, batch.ids)
        return jax.nn.sigmoid(model.score(rows, state.dense, batch))

    return predict


# --- lane-packed table variants (ops/packed_table.py; DESIGN §6) ---------


def pack_state(
    state: TrainState, init_accumulator_value: float = 0.1, fused: bool = False
) -> TrainState:
    """Lane-pack a LOGICAL TrainState (table via pack_table; the
    accumulator via pack_accum for element granularity [V, D] or
    pack_accum_rows for row granularity [V, 1] — padding slots hold the
    init value so packed Adagrad never divides by sqrt(0)).  Shared by
    init, resume, and the packed predict driver.  Packs ONE array at a
    time, dropping each logical original before the next — the transient
    device-memory peak is what OOMs big vocabs on a shared chip.

    ``fused=True`` (adagrad_accumulator = fused) stores the [V, 1] ROW
    accumulator inside each row's own tile-row slot (stride D+1 —
    ops.packed_table fused layout); ``table`` then holds the single fused
    array and ``table_opt.accum`` a [0, 1] sentinel whose emptiness IS the
    fused-state marker the step/predict/save paths dispatch on."""
    from fast_tffm_tpu.ops.packed_table import pack_accum_any, pack_fused, pack_table

    d = state.table.shape[-1]
    if fused:
        fused_arr = pack_fused(
            state.table, state.table_opt.accum, init_accumulator_value
        )
        return state._replace(
            table=fused_arr,
            table_opt=state.table_opt._replace(
                accum=jnp.zeros((0, 1), state.table.dtype)
            ),
        )
    state = state._replace(table=pack_table(state.table))
    packed_acc = pack_accum_any(state.table_opt.accum, d, init_accumulator_value)
    return state._replace(table_opt=state.table_opt._replace(accum=packed_acc))


def init_packed_state(
    model,
    key: jax.Array,
    init_accumulator_value: float = 0.1,
    accumulator: str = "element",
) -> TrainState:
    """init_state with the table and accumulator lane-packed.

    The packed layout keeps the logical init EXACTLY (pack of the same
    init_table draw), so packed and rows runs start from identical
    parameters.  ``accumulator`` follows init_state: ``element`` packs
    [V, D] → [VP, 128]; ``row`` packs [V, 1] → [VP, P]; ``fused`` stores
    the row accumulator inside the table's own tile rows ([VPf, 128],
    stride D+1 — the 2-random-op RMW layout, DESIGN §6 round 5)."""
    return pack_state(
        init_state(model, key, init_accumulator_value, accumulator),
        init_accumulator_value,
        fused=accumulator == "fused",
    )


def packed_train_step_body(
    model, learning_rate: float, state: TrainState, batch: Batch,
    update: str = "auto", compact_cap: int = 0,
):
    """train_step_body on a lane-packed table: identical math, tile-row
    physical movement (the narrow-scatter cliff fix — DESIGN §6).
    Shared by make_packed_train_step and the device-cache step.

    ``update`` picks the sparse-tail strategy (resolve_packed_update):
    ``dense`` — one wide scatter-add into a [VP, 128] gradient buffer +
    a dense Adagrad sweep (measured 3.5× the sorted path at vocab 2^24);
    ``compact`` — sort-free touched-row compaction, O(M) buffers (the
    giant-vocab path); ``sorted`` — sort/segment-sum/RMW (bit-parity
    reference); ``auto`` — dense under DENSE_G_MAX_BYTES, else compact."""
    from fast_tffm_tpu.ops.packed_table import (
        FUSED_UPDATE_FNS,
        PACKED_UPDATE_FNS,
        apply_fused_update,
        fused_gather,
        packed_gather,
        resolve_fused_update,
        resolve_packed_update,
    )

    d = model.row_dim
    acc = state.table_opt.accum
    fused = acc.size == 0  # pack_state's fused-state marker
    if fused:
        rows = fused_gather(state.table, batch.ids, d)
    else:
        rows = packed_gather(state.table, batch.ids, d)

    grad_fn = jax.value_and_grad(
        partial(batch_loss, model), argnums=(0, 1), has_aux=True
    )
    (_, data_loss), (g_rows, g_dense) = grad_fn(rows, state.dense, batch)

    # Every packed tail (dense / compact / sorted / fused) runs under the
    # rows layout's name for the same stage.
    with jax.named_scope("fm.tail"):
        if fused:
            mode = resolve_fused_update(update, state.table.shape[0])
            table = apply_fused_update(
                state.table, batch.ids, g_rows, learning_rate, mode,
                compact_cap,
            )
            accum = acc
        else:
            mode = resolve_packed_update(update, state.table.shape[0], acc.shape[-1])
            update_fn = PACKED_UPDATE_FNS[mode]
            table, accum = update_fn(
                state.table, acc, batch.ids, g_rows, learning_rate
            )
    dense, dense_opt = state.dense, state.dense_opt
    if jax.tree.leaves(state.dense):
        with jax.named_scope("deepfm.dense_update"):
            leaves = (state.dense, state.dense_opt, g_dense, learning_rate)
            dense, dense_opt = dense_adagrad_update(*leaves)
    return (
        TrainState(table, AdagradState(accum), dense, dense_opt, state.step + 1),
        data_loss,
    )


def make_packed_train_step(
    model, learning_rate: float, update: str = "auto", compact_cap: int = 0,
):
    """``compact_cap`` (fused compact tail only): cap the compacted-row
    buffer below the exact worst case, with an exact-capacity lax.cond
    fallback when a batch touches more rows (config: packed_compact_cap)."""

    @partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, batch: Batch):
        return packed_train_step_body(
            model, learning_rate, state, batch, update, compact_cap
        )

    return step


def make_packed_predict_step(model, fused: bool = False):
    """``fused`` selects the fused-layout gather (adagrad_accumulator =
    fused) — the state's table is then the [VPf, 128] fused array."""
    from fast_tffm_tpu.ops.packed_table import fused_gather, packed_gather

    d = model.row_dim
    gather = fused_gather if fused else packed_gather

    @jax.jit
    def predict(state: TrainState, batch: Batch):
        rows = gather(state.table, batch.ids, d)
        return jax.nn.sigmoid(model.score(rows, state.dense, batch))

    return predict
