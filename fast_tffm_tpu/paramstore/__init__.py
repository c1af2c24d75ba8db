"""Tiered host/device parameter store (ISSUE 12 tentpole; ROADMAP 3).

Subsystem layout:

  * ``store.py``     — the cold tier: memmap-backed full logical table
                       (sparse files + lazy row init, so 2^30+ rows cost
                       disk/RAM only for rows actually touched);
  * ``residency.py`` — hot-set selection (PR-9 heavy-hitter twin) and
                       the per-batch id resolution / remap;
  * ``tiered.py``    — the runtime: TieredParamServer (staging,
                       writeback, pending overlay, coherency),
                       TieredConverter (prefetch-thread resolve + packed
                       wire shipping);
  * ``ckpt.py``      — both tiers through the one atomic-publish chain
                       (crash-consistency invariant 7).

``open_tiered_run`` is the driver entry: it builds (server, compact
TrainState, resume cursor) for training.py's tiered branch."""

from __future__ import annotations

import os
from functools import partial

import numpy as np

from fast_tffm_tpu.paramstore.ckpt import (
    is_tiered_checkpoint,
    restore_tiered,
    write_tiered_full,
)
from fast_tffm_tpu.paramstore.residency import ResidencyMap, choose_hot_ids
from fast_tffm_tpu.paramstore.store import ColdStore, hashed_uniform_rows
from fast_tffm_tpu.paramstore.tiered import (
    TieredBatch,
    TieredConverter,
    TieredParamServer,
)

__all__ = [
    "ColdStore",
    "ResidencyMap",
    "TieredBatch",
    "TieredConverter",
    "TieredParamServer",
    "choose_hot_ids",
    "hashed_uniform_rows",
    "is_tiered_checkpoint",
    "open_tiered_run",
    "restore_tiered",
    "write_tiered_full",
]

# auto-materialize threshold: vocabs at or under this row count write the
# exact jax init draw into the store (bit-identity with the resident
# path); larger vocabs stay lazy (hashed per-row init — the resident
# path cannot exist there anyway).
MATERIALIZE_MAX_ROWS = 1 << 21


def _sample_ids(cfg, max_nnz: int, n_batches: int):
    """First N parsed train batches' id arrays — the exact-frequency
    sample the default residency policy counts (deterministic for a
    fixed file set)."""
    from fast_tffm_tpu.data.native import best_parser
    from fast_tffm_tpu.data.pipeline import batch_stream

    raw = batch_stream(
        tuple(cfg.train_files),
        batch_size=cfg.batch_size,
        vocabulary_size=cfg.vocabulary_size,
        hash_feature_id=cfg.hash_feature_id,
        max_nnz=max_nnz,
        epochs=1,
        parser=best_parser(cfg.thread_num),
    )
    for i, (p, _w) in enumerate(raw):
        if i >= n_batches:
            break
        yield p.ids


def open_tiered_run(cfg, model, max_nnz: int, *, resume: bool, log=print):
    """(server, compact TrainState, start_cursor) for a tiered run.

    Fresh runs (re)create the store — materialized with the exact
    ``init_state`` draw at small vocab, lazy beyond — and choose the hot
    set per ``[ParamStore] residency``.  Resume restores BOTH tiers from
    the chain (paramstore.ckpt.restore_tiered) and takes residency from
    the checkpoint, so a resumed run's remapping (and loss sequence) is
    identical to the uninterrupted run's."""
    import jax
    import jax.numpy as jnp

    from fast_tffm_tpu.checkpoint import read_input_cursor
    from fast_tffm_tpu.optim import AdagradState, init_adagrad
    from fast_tffm_tpu.trainer import TrainState, init_state

    vocab = int(cfg.vocabulary_size)
    accum_width = model.row_dim if cfg.adagrad_accumulator == "element" else 1
    store_dir = cfg.paramstore_dir or cfg.model_file + ".store"
    miss_rows = cfg.paramstore_miss_rows or (
        cfg.batch_size * max_nnz * cfg.steps_per_call
    )
    init_acc = float(cfg.init_accumulator_value)

    if resume and not os.path.isfile(cfg.model_file):
        # Mirror dist_train's stance: a supervised relaunch can race a
        # crash before the first publish — same absence, same fresh start.
        log(
            f"warning: --resume but no checkpoint at {cfg.model_file} — "
            "starting fresh (crash before the first publish?)"
        )
        resume = False
    if resume:
        store = ColdStore.open(store_dir)
        # Dense template: leaf count + treedef for reassembly.
        _k1, k2 = jax.random.split(jax.random.key(0))
        dense_tpl = model.init_dense(k2)
        leaves_tpl, treedef = jax.tree.flatten(dense_tpl)
        rec = restore_tiered(cfg.model_file, store, len(leaves_tpl))
        hot_ids = rec["hot_ids"]
        if int(hot_ids.size) != int(cfg.paramstore_hot_rows):
            log(
                f"note: resuming with the checkpoint's residency "
                f"({hot_ids.size} hot rows; [ParamStore] hot_rows = "
                f"{cfg.paramstore_hot_rows} ignored for this run)"
            )
        server = TieredParamServer(
            store, hot_ids, miss_rows, model, init_accum=init_acc,
            residency_policy="the checkpoint's",
        )
        dense = jax.tree.unflatten(treedef, [jnp.asarray(x) for x in rec["dense"]])
        dense_acc = jax.tree.unflatten(
            treedef, [jnp.asarray(x) for x in rec["dense_accum"]]
        )

        def hot(t, a):
            t[:], a[:] = rec["hot_t"], rec["hot_a"]

        state = _compact_state(
            server, hot, dense, AdagradState(dense_acc), int(rec["step"]),
            init_acc,
        )
        log(
            f"resumed tiered run from {cfg.model_file} at step "
            f"{int(rec['step'])} (hot {server.hot_rows} rows, store "
            f"{store.vocab} rows)"
        )
        return server, state, read_input_cursor(cfg.model_file)

    materialize = cfg.paramstore_materialize == "always" or (
        cfg.paramstore_materialize == "auto" and vocab <= MATERIALIZE_MAX_ROWS
    )
    if materialize:
        logical = init_state(
            model, jax.random.key(0), init_acc, cfg.adagrad_accumulator
        )
        store = ColdStore.create(
            store_dir,
            vocab=vocab, row_dim=model.row_dim, accum_width=accum_width,
            seed=0, init_range=float(getattr(model, "init_value_range", 0.01)),
            init_accum=init_acc,
            init_table=np.asarray(logical.table),
            init_accum_arr=np.asarray(logical.table_opt.accum),
        )
        dense, dense_opt = logical.dense, logical.dense_opt
        step0 = int(logical.step)
        del logical
    else:
        store = ColdStore.create(
            store_dir,
            vocab=vocab, row_dim=model.row_dim, accum_width=accum_width,
            seed=0, init_range=float(getattr(model, "init_value_range", 0.01)),
            init_accum=init_acc,
        )
        # Dense init must still match init_state's key split exactly.
        _k1, k2 = jax.random.split(jax.random.key(0))
        dense = model.init_dense(k2)
        dense_opt = init_adagrad(dense, init_acc)
        step0 = 0
        log(
            f"paramstore: lazy cold store for {vocab} rows "
            f"(beyond the {MATERIALIZE_MAX_ROWS}-row materialize bound; "
            "rows init on first touch)"
        )
    policy = cfg.paramstore_residency
    hot_ids = choose_hot_ids(
        policy, cfg.paramstore_hot_rows, vocab,
        sample_batches=(
            _sample_ids(cfg, max_nnz, cfg.paramstore_sample_batches)
            if policy == "sample"
            else None
        ),
    )
    server = TieredParamServer(
        store, hot_ids, miss_rows, model, init_accum=init_acc,
        residency_policy=(
            f"sample of {cfg.paramstore_sample_batches} batches"
            if policy == "sample" else policy
        ),
    )
    state = _compact_state(
        server, partial(_read_hot, store, server.residency.hot_ids), dense,
        dense_opt, step0, init_acc,
    )
    row_bytes = 4 * (model.row_dim + accum_width)
    log(
        f"paramstore: {vocab} logical rows on the host "
        f"({vocab * row_bytes / 2**30:.2f} GiB of table and accumulator, "
        f"{'materialized' if materialize else 'lazy'} cold store at "
        f"{store_dir}); on the device {server.hot_rows} hot + "
        f"{server.miss_rows} staging slots "
        f"({server.capacity * row_bytes / 2**30:.2f} GiB); residency "
        f"{server.residency_policy}"
    )
    return server, state, None


# Rows a set-up read takes at a time: its uint64 hash temporaries stay a
# few tens of MB per worker, whatever the hot tier's size.
_HOT_READ_CHUNK = 1 << 18


def _read_hot(store, hot_ids, t, a):
    """Fill ``t`` [H, D] and ``a`` [H, A] with the store's rows of
    ``hot_ids``, a chunk at a time over a few threads (numpy releases the
    interpreter lock in the hash and the copies)."""
    from concurrent.futures import ThreadPoolExecutor

    def one(lo):
        hi = min(hot_ids.size, lo + _HOT_READ_CHUNK)
        t[lo:hi], a[lo:hi] = store.read_rows(hot_ids[lo:hi])

    starts = range(0, hot_ids.size, _HOT_READ_CHUNK)
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(one, starts))


def _compact_state(server, fill_hot, dense, dense_opt, step, init_acc):
    """The device ``TrainState`` over the compact ``[C, D]`` table: the hot
    rows that ``fill_hot(table[:H], accum[:H])`` writes in place, then
    the staging slots (zero rows, the initial accumulator)."""
    import jax.numpy as jnp

    from fast_tffm_tpu.optim import AdagradState
    from fast_tffm_tpu.trainer import TrainState

    c, d, a, h = server.capacity, server.row_dim, server.accum_width, server.hot_rows
    table = np.empty((c, d), np.float32)
    accum = np.empty((c, a), np.float32)
    fill_hot(table[:h], accum[:h])
    table[h:] = 0.0
    accum[h:] = np.float32(init_acc)
    table_d = jnp.asarray(table)
    del table
    accum_d = jnp.asarray(accum)
    del accum
    return TrainState(
        table=table_d,
        table_opt=AdagradState(accum_d),
        dense=dense,
        dense_opt=dense_opt,
        step=jnp.asarray(np.int32(step)),
    )
