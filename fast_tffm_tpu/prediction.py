"""Prediction drivers: restore a model and write scores for input files.

Capability parity with the reference's predict/dist_predict entrypoints
(`renyi533/fast_tffm` :: py/ predictor: Saver.restore → stream the predict
file through parser+scorer → write sigmoid scores, one per line, to the
score path; dist variant shards input across workers).
"""

from __future__ import annotations

import time
from typing import Any, Callable, NamedTuple

import jax
import numpy as np

from fast_tffm_tpu.checkpoint import restore_checkpoint
from fast_tffm_tpu.config import Config, build_model
from fast_tffm_tpu.models.base import Batch
from fast_tffm_tpu.telemetry import RunMonitor, log_device
from fast_tffm_tpu.training import (
    _batch_converter,
    _say_interaction,
    _stream,
    scan_max_nnz,
)
from fast_tffm_tpu.trainer import init_state, make_predict_step

__all__ = [
    "ScoreFn",
    "load_scoring_state",
    "make_score_fn",
    "predict",
    "dist_predict",
]


class ScoreFn(NamedTuple):
    """A jitted scoring function plus the static facts its callers need.

    ``fn(state, batch) -> sigmoid scores [B]`` is the ONE single-host
    scoring definition: the offline predict driver streams files through
    it and the serving engine (serving/engine.py) dispatches micro-batches
    to it — score parity between the two paths is structural, not tested
    into existence (though tests/test_serving.py pins it anyway).
    """

    fn: Callable  # jitted (state, Batch) -> [B] sigmoid scores
    model: Any  # built model (uses_fields, row_dim)
    max_nnz: int  # static feature width every batch must carry

    def __call__(self, state, batch: Batch):
        return self.fn(state, batch)

    @property
    def uses_fields(self) -> bool:
        return self.model.uses_fields

    def cache_size(self) -> int | None:
        """Compiled-program count (one per distinct batch shape) — how the
        serving bucket ladder pins "zero steady-state recompiles"; None
        when the JAX runtime doesn't expose the jit cache."""
        f = getattr(self.fn, "_cache_size", None)
        try:
            return int(f()) if f is not None else None
        except Exception:
            return None


def load_scoring_state(cfg: Config, log=print):
    """Build the model and restore ``cfg.model_file`` into the configured
    single-host inference layout: checkpoints hold LOGICAL arrays, so a
    packed config lane-packs after the restore (plain packed, never the
    fused RMW layout — scoring only gathers, and the plain gather serves
    any checkpoint regardless of the accumulator it was trained with).

    The one definition of "load a model for inference", shared by
    ``predict()`` and the serving engine's startup AND hot reload — a
    reload can never restore into a different layout than startup did.
    """
    model = build_model(cfg)
    state = init_state(
        model, jax.random.key(0), cfg.init_accumulator_value, cfg.adagrad_accumulator
    )
    state = restore_checkpoint(
        cfg.model_file, state, chunk_bytes=cfg.checkpoint_chunk_mb << 20
    )
    log(f"restored {cfg.model_file} at step {int(state.step)}")
    if cfg.table_layout == "packed":
        from fast_tffm_tpu.trainer import pack_state

        state = pack_state(state, cfg.init_accumulator_value)
    return model, state


def make_score_fn(cfg: Config, state, max_nnz: int, model=None) -> ScoreFn:
    """The single-host scoring step for ``state``'s layout.

    ``cfg.table_layout`` picks rows vs packed; ``state`` itself supplies
    the fused evidence (pack_state's empty-accum marker), so a live
    fused-packed trainer state scores through the fused gather without
    any extra flag.  ``model`` avoids a rebuild when the caller already
    has one; a rebuilt model is identical (pure function of cfg).
    """
    if model is None:
        model = build_model(cfg)
    if cfg.table_layout == "packed":
        from fast_tffm_tpu.trainer import make_packed_predict_step

        fused = state.table_opt.accum.size == 0
        fn = make_packed_predict_step(model, fused=fused)
    else:
        fn = make_predict_step(model)
    return ScoreFn(fn=fn, model=model, max_nnz=int(max_nnz))


def _run_predict(
    cfg: Config, state, predict_step, max_nnz, log=print, mesh=None, with_fields=True,
    *, model,
) -> str:
    if not cfg.predict_files:
        raise ValueError("no predict_files configured")
    # The interaction's form on a chip's share of a batch, said once and
    # carried by the predict program's kind=profile record (forward only).
    interaction = _say_interaction(
        log, model, cfg.batch_size // (mesh.size if mesh is not None else 1), backward=False,
    )
    # So with the forward gather's form (trainer.gather_rows: the
    # single-device rows layout; the packed and sharded gathers have one).
    gather = {}
    if mesh is None and cfg.table_layout != "packed":
        from fast_tffm_tpu.trainer import describe_gather, gather_form, gather_profile

        shape = (*state.table.shape[:1], cfg.batch_size * max_nnz, state.table.shape[1])
        gather = gather_profile(*shape, gather_form(*shape))
        log("forward gather: " + describe_gather(*shape, gather["gather_form"]))
    # Multi-host: the sharded predict step is ONE SPMD program over the
    # global mesh; replicated scores come back on every process and process
    # 0 writes them.  When the batch size divides evenly, the INPUT is also
    # sharded — process p parses only rows [p·B/P, (p+1)·B/P) of each
    # global batch (the reference's dist_predict spread input files across
    # workers; here parse throughput scales with the host count the same
    # way).  Otherwise every process parses identical full batches and the
    # mesh still shards the compute at chip granularity.
    nproc = jax.process_count()
    is_lead = jax.process_index() == 0
    shard_input = mesh is not None and nproc > 1 and cfg.batch_size % nproc == 0
    stream_kw = {}
    # The local converter (uses_fields-marked) — scoring rides the same
    # packed-wire staging as training when wire_format = packed and the
    # input is FMB-backed (one coalesced H2D buffer per batch).
    to_batch = _batch_converter(with_fields)
    remaining = None
    bs = cfg.batch_size  # per-process stream batch size
    if shard_input:
        from fast_tffm_tpu.data.native import count_lines
        from fast_tffm_tpu.parallel import make_global_batch

        total = count_lines(cfg.predict_files)
        bs = cfg.batch_size // nproc
        # The stream's batch size MUST equal shard_block: block-cyclic line
        # selection is aligned to global batch slots only at that size.
        stream_kw = dict(
            shard_index=jax.process_index(),
            shard_count=nproc,
            shard_block=bs,
            pad_to_batches=-(-total // cfg.batch_size),  # ceil
        )
        to_batch = lambda parsed, w: make_global_batch(mesh, parsed, w, with_fields=with_fields)
        # uses_fields without wire_capable: honest kind=input byte
        # estimates, packed wire off (the global stitch ships arrays).
        to_batch.uses_fields = with_fields
        # Padding (short final batch + all-empty tail batches) sits strictly
        # after the data rows, so the real scores are exactly the first
        # `total` of the concatenated stream — no global weight mask needed.
        remaining = total
        if is_lead:
            log(f"predict input sharding: {total} rows over {nproc} processes")
    n = 0
    batches = 0
    # Same envelope/sentinels as training, tagged source=predict: a
    # steady-state recompile or a parse stall in a backfill surfaces in
    # the same JSONL stream tools/report.py reads.
    monitor = RunMonitor(
        cfg.metrics_path if is_lead else None,
        run_id=cfg.telemetry_run_id,
        source="predict",
        stall_timeout_s=cfg.telemetry_stall_timeout_s,
        mem_every_s=cfg.telemetry_mem_every_s,
        log=log,
        device=log_device(log, "predict"),
    )
    # Measured cost ledger (profiling.py): ONE kind=profile record for
    # the predict program — bytes accessed / FLOPs from XLA cost
    # analysis, emitted after the first dispatch compiled it.
    ledger = None
    if cfg.telemetry_profile_costs:
        from fast_tffm_tpu.profiling import CostLedger

        ledger = CostLedger(monitor, source="predict")
    t_start = time.perf_counter()
    out = None
    try:
        # Inside the try: an unwritable score_path must still close the
        # monitor (summary record, watchdog thread) on the way out.
        out = open(cfg.score_path, "w") if is_lead else None
        # _stream owns the prefetch wiring AND the conversion-placement
        # policy (H2D in the prefetch thread iff the input is FMB-backed);
        # a None batch means convert here in the consumer (text input).
        stream = _stream(
            cfg,
            cfg.predict_files,
            max_nnz,
            epochs=1,
            batch_size=bs,
            weights=None,
            to_batch=to_batch,
            **stream_kw,
        )
        monitor.set_queue_depth_fn(getattr(stream, "queue_depth", None))
        for b, parsed, w in stream:
            if b is None:
                b = to_batch(parsed, w)
            if ledger is not None and ledger.want("predict_step"):
                ledger.stage(
                    "predict_step", predict_step, (state, b),
                    examples=int(getattr(b.labels, "shape", (0,))[0] or 0) or None,
                    **interaction, **gather,
                )
            scores = np.asarray(predict_step(state, b))
            batches += 1
            monitor.on_dispatch(batches, warmup=(batches == 1))
            if ledger is not None:
                ledger.flush(batches)
            if not np.isfinite(scores).all():
                # Under lookup_overflow=fallback an overflow cannot
                # poison scores (the lookup reran via allgather).
                cause = (
                    "an alltoall-lookup capacity overflow (raise "
                    "lookup_capacity_factor, set lookup_overflow = "
                    "fallback, or use lookup=allgather) or a diverged model"
                    if cfg.lookup == "alltoall" and cfg.lookup_overflow == "abort"
                    else "a diverged model (non-finite weights)"
                )
                monitor.emit_anomaly(
                    batches, None, event="nonfinite_scores", state=state
                )
                raise RuntimeError(
                    f"non-finite scores — {cause}; refusing to write a "
                    f"poisoned score file to {cfg.score_path}"
                )
            if remaining is not None:
                take = min(remaining, len(scores))
                remaining -= take
                real = np.arange(len(scores)) < take
            else:
                real = w > 0  # drop batch-size padding rows
            if out is not None:
                for s in scores[real]:
                    out.write(f"{s:.6f}\n")
            n += int(real.sum())
        dt = time.perf_counter() - t_start
        stats = getattr(stream, "stats", None)
        if stats is not None:
            rec = stats.drain()
            if rec:
                monitor.emit("input", step=batches, **rec)
        monitor.emit(
            "predict",
            step=batches,
            examples=n,
            examples_per_sec=round(n / dt, 1) if dt > 0 else None,
        )
    finally:
        if out is not None:
            out.close()
        monitor.close()
    if is_lead:
        log(f"wrote {n} scores -> {cfg.score_path}")
    return cfg.score_path


def predict(cfg: Config, log=print) -> str:
    """Single-device prediction — the reference's `predict` mode."""
    model, state = load_scoring_state(cfg, log)
    score = make_score_fn(cfg, state, scan_max_nnz(cfg), model=model)
    return _run_predict(
        cfg, state, score.fn, score.max_nnz, log, with_fields=score.uses_fields,
        model=model,
    )


def dist_predict(cfg: Config, log=print, mesh=None) -> str:
    """Mesh-sharded prediction — the reference's `dist_predict` mode."""
    from fast_tffm_tpu.parallel import (
        check_batch_divides,
        init_sharded_state,
        make_mesh,
        make_sharded_predict_step,
    )
    from fast_tffm_tpu.distributed import initialize_runtime

    initialize_runtime(cfg, log=log)
    model = build_model(cfg)
    max_nnz = scan_max_nnz(cfg)
    if mesh is None:
        row = cfg.row_parallel or cfg.vocabulary_block_num
        data = cfg.data_parallel or None
        mesh = make_mesh(data, row)
    check_batch_divides(cfg.batch_size, mesh)
    if cfg.table_layout == "packed":
        # Checkpoints hold logical arrays; restore into a rows-layout
        # template on the PACKED padding and convert per shard on device
        # (multi-host safe — no host gather; same scheme as dist_train's
        # packed resume).
        from fast_tffm_tpu.parallel import pack_sharded_on_device
        from fast_tffm_tpu.parallel.train_step import packed_shard_meta

        # A fused-trained checkpoint is padded with the FUSED pack factor
        # (stride D+1), which differs from the plain packed padding —
        # the template must match or the multi-host restore (which cannot
        # re-pad) raises on the shape.  The predict step then reads the
        # same layout the state was packed into.
        fused_acc = cfg.adagrad_accumulator == "fused"
        padded_model, _, _ = packed_shard_meta(model, mesh, fused=fused_acc)
        logical = restore_checkpoint(
            cfg.model_file,
            init_sharded_state(
                padded_model, mesh, jax.random.key(0),
                cfg.init_accumulator_value, cfg.adagrad_accumulator,
            ),
            chunk_bytes=cfg.checkpoint_chunk_mb << 20,
        )
        state = pack_sharded_on_device(
            logical, model, mesh, cfg.init_accumulator_value, fused=fused_acc
        )
    else:
        state = init_sharded_state(
            model, mesh, jax.random.key(0), cfg.init_accumulator_value,
            cfg.adagrad_accumulator,
        )
        state = restore_checkpoint(
            cfg.model_file, state, chunk_bytes=cfg.checkpoint_chunk_mb << 20
        )
    return _run_predict(
        cfg,
        state,
        make_sharded_predict_step(
            model, mesh, lookup=cfg.lookup,
            capacity_factor=cfg.lookup_capacity_factor,
            overflow_mode=cfg.lookup_overflow, table_layout=cfg.table_layout,
            accumulator=cfg.adagrad_accumulator,
        ),
        max_nnz,
        log,
        mesh=mesh,
        with_fields=model.uses_fields,
        model=model,
    )
