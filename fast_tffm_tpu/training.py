"""Training drivers: local and mesh-distributed.

Capability parity with the reference's train/dist_train entrypoints
(`renyi533/fast_tffm` :: py/ trainer: session loop over sess.run(train_op),
periodic loss logging, Saver checkpoints; dist variant on a ps/worker
cluster with async Hogwild updates).  Differences, all TPU-first:

  * one jitted step (gather → fused scorer → loss → sparse Adagrad) instead
    of a TF graph; host parsing overlaps device compute via prefetch;
  * dist_train is the SAME program on a ('data','row') mesh — synchronous
    deterministic updates over ICI replace Hogwild (SURVEY.md §5);
  * metrics: step loss, examples/sec (/chip), validation AUC per epoch.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time

import jax
import numpy as np

from fast_tffm_tpu.checkpoint import read_input_cursor, restore_checkpoint
from fast_tffm_tpu.config import Config, build_model
from fast_tffm_tpu.data.native import best_parser
from fast_tffm_tpu.data.pipeline import batch_stream
from fast_tffm_tpu.metrics import StreamingAUC, Throughput
from fast_tffm_tpu.models.base import Batch
from fast_tffm_tpu.resilience import (
    NonFiniteLossError,
    active_faults,
    drain_fault_counters,
    drain_fault_events,
)
from fast_tffm_tpu.telemetry import RunMonitor, log_device
from fast_tffm_tpu.trainer import init_state, make_predict_step, make_train_step
from fast_tffm_tpu.utils.prefetch import PrefetchError, prefetch
from fast_tffm_tpu.utils.tracing import span, step_trace

__all__ = ["train", "dist_train", "scan_max_nnz"]


def scan_max_nnz(cfg: Config) -> int:
    """Fix the static feature width: cfg.max_nnz, or a scan of the files
    (one C++ streaming pass per file when the native parser is built).
    FMS stream files (online follow input — data/stream.py) contribute
    their header width instead of a scan: an append-only stream's widest
    FUTURE row is unknowable, so the writer-declared width is the bound."""
    if cfg.max_nnz > 0:
        return cfg.max_nnz
    from fast_tffm_tpu.data.native import scan_files
    from fast_tffm_tpu.data.stream import is_fms, read_fms_header

    paths = (*cfg.train_files, *cfg.validation_files, *cfg.predict_files)
    fms_widths = [read_fms_header(p)["width"] for p in paths if is_fms(p)]
    rest = tuple(p for p in paths if not is_fms(p))
    widest = scan_files(rest)[1] if rest else 0
    return max(1, widest, *fms_widths)


def _check_finite(
    loss: float, cfg: Config, monitor=None, step=0, state=None, cursor=None
) -> None:
    """Abort on a non-finite loss instead of training on (and eventually
    checkpointing) poisoned state.  With a ``monitor``, the divergence
    lands in the telemetry stream as a structured ``kind=anomaly`` record
    (step, loss, first non-finite tensor path) BEFORE the raise, so
    tools/report.py can flag the run without log-grepping.

    Raises ``NonFiniteLossError`` carrying the input ``cursor`` at
    detection time: under ``on_nan = rollback`` the driver restores the
    last checkpoint and resumes input AT this cursor — skipping the
    window whose data diverged instead of replaying it."""
    if not np.isfinite(loss):
        if monitor is not None:
            monitor.emit_anomaly(step, loss, state=state)
        # Under lookup_overflow=fallback an overflow cannot produce NaN
        # (the step reran via allgather) — divergence is the only cause.
        hint = (
            "an alltoall-lookup capacity overflow — raise "
            "lookup_capacity_factor, set lookup_overflow = fallback, or "
            "use lookup=allgather"
            if cfg.lookup == "alltoall" and cfg.lookup_overflow == "abort"
            else "a diverged model — lower learning_rate"
        )
        raise NonFiniteLossError(
            f"training loss is {loss}; likely {hint}.  Aborting before the "
            "next checkpoint overwrites the last good state.",
            step=int(step),
            loss=float(loss),
            cursor=cursor,
        )


_TRAIN_WEIGHTS = object()  # sentinel: apply cfg.weight_files (train files only)


def _batch_converter(uses_fields: bool):
    """The drivers' host→device batch assembly: a single ParsedBatch
    converts via ``Batch.from_parsed``; a LIST of K grouped batches
    (steps_per_call > 1 streams) stacks into one [K, B, ...] superbatch.
    One definition shared by train() and dist_train() so the stacking
    rule cannot diverge between the local and distributed drivers.

    ``wire_capable`` marks converters the packed wire format can feed —
    this local one ships one coalesced buffer to the local device; the
    multi-host global-batch closures additionally carry
    ``make_wire_converter`` so _stream builds the host-local
    pack/unpack + global-assembly shipper instead
    (parallel.WireGlobalConverter)."""

    def to_batch(parsed, w):
        if isinstance(parsed, list):
            return Batch.stack_parsed(parsed, w, with_fields=uses_fields)
        return Batch.from_parsed(parsed, w, with_fields=uses_fields)

    to_batch.uses_fields = uses_fields
    to_batch.wire_capable = True
    return to_batch


def binary_input(files) -> bool:
    """True when every file in the (cache-resolved) list is FMB — i.e. the
    stream will be memmap-backed, not parsed."""
    from fast_tffm_tpu.data.binary import is_fmb

    return bool(files) and all(is_fmb(f) for f in files)


def _stream(
    cfg: Config,
    files,
    max_nnz,
    epochs,
    batch_size=None,
    weights=_TRAIN_WEIGHTS,
    to_batch=None,
    shuffle_epoch=None,
    steps_per_call=1,
    skip_batches=0,
    dedup_guard=False,
    **shard_kw,
):
    """Prefetched input stream yielding ``(batch_or_None, parsed, w)``.

    ``skip_batches`` reopens the stream mid-epoch at that batch offset
    (the exact-position resume seek — cursors count batches, and the
    underlying streams seek in rows); with ``steps_per_call`` > 1 the
    skip is applied BEFORE grouping, so a K-aligned resume reproduces
    the uninterrupted run's superbatch boundaries exactly.

    With FMB-backed input and a ``to_batch``, the host→device conversion
    runs INSIDE the prefetch thread, overlapping the transfer with the
    consumer's step dispatch (measured ~3× end-to-end on a transfer-bound
    host — the memmap producer is cheap, unlike the text parse, which
    needs the thread to itself and keeps conversion in the consumer; see
    DESIGN.md §6).  Callers convert when the first element is None.

    ``steps_per_call`` > 1 groups K consecutive batches per item: ``parsed``
    and ``w`` become LISTS of K entries (epoch tail shorter), and the
    drivers' list-aware ``to_batch`` stacks them into one [K, B, ...]
    superbatch — ONE H2D transfer and one fused-step dispatch per K steps.
    The grouping (and, for FMB input, the stacking + transfer) runs inside
    the prefetch thread, exactly like the single-batch conversion above.
    """
    if weights is _TRAIN_WEIGHTS:
        weights = cfg.weight_files if cfg.weight_files else None
    files = tuple(files)
    parser = best_parser(cfg.thread_num)
    if cfg.binary_cache:
        # Resolve the cache HERE (not inside batch_stream) so the
        # conversion-placement decision below sees the actual outcome:
        # an unwritable cache falls back to text files, and text input
        # must keep the prefetch thread for the parse.
        from fast_tffm_tpu.data.binary import ensure_fmb_cache

        files = ensure_fmb_cache(
            files,
            vocabulary_size=cfg.vocabulary_size,
            hash_feature_id=cfg.hash_feature_id,
            max_nnz=max_nnz,
            parser=parser,
            # Pod etiquette: on a shared filesystem only the lead process
            # builds a stale cache; the rest wait for it (and build their
            # own copy after the timeout when disks are host-local).
            # Shard-disjoint file assignment is the exception: each host
            # OWNS its files, so waiting for a peer build would stall a
            # non-lead host for the whole timeout on a cache nobody else
            # will ever write.
            wait_for_peer=(
                cfg.binary_cache_wait
                if jax.process_index() != 0 and cfg.input_assignment != "files"
                else 0.0
            ),
        )
    # Per-epoch shuffle (train streams only — drivers create one stream per
    # epoch and pass its index).  The seed folds the epoch so every epoch
    # draws a fresh permutation, identically on every process.
    from fast_tffm_tpu.data.binary import fold_epoch_seed

    shuffle_seed = (
        fold_epoch_seed(cfg.shuffle_seed, shuffle_epoch)
        if cfg.shuffle and shuffle_epoch is not None
        else None
    )
    if shuffle_seed is not None and cfg.binary_cache and not binary_input(files):
        if jax.process_count() > 1:
            # The fallback decision is PER-PROCESS (host-local disks can
            # fail on some hosts only).  A process streaming its shard
            # sequentially while its peers follow the epoch permutation
            # would let make_global_batch stitch shards drawn from
            # different row orderings into one global batch — silently
            # duplicating/dropping examples for the whole run.  Die loudly
            # instead; every process either shuffles or none do.
            raise RuntimeError(
                "shuffle with binary_cache on a multi-process run: this "
                "process could not build/reach the binary cache (text "
                "fallback), and a per-host shuffle fallback would silently "
                "misalign the global batches — fix the cache location on "
                "every host (or pre-convert the files, or disable shuffle)"
            )
        # Single process: the cache fell back to text (unwritable
        # location); binary_cache is an accelerator and must keep
        # degrading gracefully — drop the shuffle for this run rather
        # than dying on batch_stream's "set binary_cache = true" (which
        # the user already did).
        import warnings

        warnings.warn(
            "shuffle disabled: the binary cache is unavailable (text "
            "fallback) and text streaming cannot reorder rows",
            RuntimeWarning,
            stacklevel=2,
        )
        shuffle_seed = None
    bs = batch_size if batch_size is not None else cfg.batch_size
    raw = batch_stream(
        files,
        batch_size=bs,
        vocabulary_size=cfg.vocabulary_size,
        hash_feature_id=cfg.hash_feature_id,
        max_nnz=max_nnz,
        epochs=epochs,
        weights=weights,
        parser=parser,
        shuffle_seed=shuffle_seed,
        skip_rows=skip_batches * bs,
        io_retries=cfg.io_retries,
        io_retry_backoff_s=cfg.io_retry_backoff_s,
        **shard_kw,
    )
    if dedup_guard and cfg.dedup_gather_rows > 0:
        # Verified-never-trusted (the wire packer's stance): the jitted
        # dedup gather (trainer.make_dedup_body) silently TRUNCATES a
        # unique set past its static cap, so every batch is checked on
        # the host before it ships — a too-small cap is a loud error
        # naming the knob, never corrupted training.
        raw = _dedup_cap_guard(raw, cfg.dedup_gather_rows)
    if steps_per_call > 1:
        from fast_tffm_tpu.utils.prefetch import grouped_pairs

        raw = grouped_pairs(raw, steps_per_call)
    from fast_tffm_tpu.data.wire import InputStats
    from fast_tffm_tpu.utils.prefetch import InputStream

    convert = None
    if to_batch is not None and binary_input(files):
        convert = to_batch
        wire_ok = getattr(to_batch, "wire_capable", False)
        if cfg.wire_format == "packed" and wire_ok and max_nnz:
            # Packed wire: ONE coalesced byte buffer per (super)batch with
            # device-side reconstruction, instead of one device_put per
            # tensor.  Elision decisions are PER STREAM, from facts about
            # THESE files: all-ones vals come off the FMB v2 header flags
            # (ANDed; verified again per batch by the packer), fields
            # follow the model's uses_fields rule, weights elide when the
            # per-file example weights are uniform.
            from fast_tffm_tpu.data.binary import fmb_wire_flags
            from fast_tffm_tpu.data.wire import WireConverter, make_spec

            all_ones, _ = fmb_wire_flags(files)
            uniform_w = weights is None or all(float(x) == 1.0 for x in weights)
            spec = make_spec(
                cfg.vocabulary_size,
                max_nnz,
                with_vals=not all_ones,
                with_fields=to_batch.uses_fields,
                with_weights=not uniform_w,
            )
            # Multi-host converters supply their own wire shipper (the
            # host-local pack + per-device unpack + global assembly —
            # parallel.WireGlobalConverter); local converters take the
            # plain single-device one.
            maker = getattr(to_batch, "make_wire_converter", None)
            convert = maker(spec) if maker is not None else WireConverter(spec)
    stats = InputStats()
    gen = stats.timed(raw, convert)
    # Each queued item holds steps_per_call batches, so scale the depth
    # down to keep the in-flight memory (device superbatches for FMB
    # input, host staging for text) at the K=1 level — one or two
    # superbatches in flight already keep the consumer overlapped.
    depth = max(1, cfg.queue_size // max(1, steps_per_call))
    return InputStream(prefetch(gen, depth=depth, stats=stats), stats)


def _dedup_cap_guard(raw, cap: int):
    """Per-batch unique-id bound check for ``dedup_gather_rows`` (runs in
    the prefetch thread — overlapped like the parse it rides)."""
    for p, w in raw:
        u = int(np.unique(p.ids).size)
        if u > cap:
            raise ValueError(
                f"dedup_gather_rows = {cap} but a batch carries {u} unique "
                "ids — the jitted dedup gather would silently drop rows.  "
                "Raise dedup_gather_rows (or set 0 to disable)."
            )
        yield p, w


def _evaluate(
    cfg: Config, predict_step, state, files, max_nnz, stream=None, to_batch=None, fetch=None
) -> float:
    """AUC over ``files``.  ``stream``/``to_batch``/``fetch`` parameterize the
    multi-host sharded path (sharded input, global-array stitching, device
    all-gather of the label/weight vectors); defaults are the local path.

    Bounded memory: per-batch scores fold into a fixed-bucket streaming
    AUC (metrics.StreamingAUC) instead of accumulating every score/label
    on the host — a Criteo-scale validation split evaluates in O(bins).

    weight_files aligns with TRAIN files; validation examples weigh 1.0
    (only batch-padding rows carry 0, and the AUC drops them)."""
    if to_batch is None:
        to_batch = Batch.from_parsed
    if stream is None:
        stream = _stream(cfg, files, max_nnz, epochs=1, weights=None, to_batch=to_batch)
    if fetch is None:
        fetch = lambda b, parsed, w: (parsed.labels, w)
    meter = StreamingAUC()
    for b, parsed, w in stream:
        if b is None:
            b = to_batch(parsed, w)
        scores = np.asarray(predict_step(state, b))
        lab, ww = fetch(b, parsed, w)
        meter.add(lab, scores, ww)
    return meter.value()


def _follow_stream(cfg: Config, files, max_nnz, to_batch, skip_batches=0, stop=None):
    """Tail-following input stream for ``[Online] follow = true``: the
    FMS reader (data/stream.py) polls the append-only train file for
    growth at EOF instead of ending the epoch; conversion runs in the
    prefetch thread (the memmap-cheap producer, like FMB input), and the
    stream's idle Event feeds the stall watchdog so a starved loop
    classifies ``input-starved (stream-idle)``.  ``skip_batches`` is the
    exact-position resume seek — one O(1) file seek."""
    from fast_tffm_tpu.data.stream import fms_follow_stream
    from fast_tffm_tpu.data.wire import InputStats
    from fast_tffm_tpu.utils.prefetch import InputStream, prefetch

    idle = threading.Event()
    raw = fms_follow_stream(
        files[0],
        batch_size=cfg.batch_size,
        vocabulary_size=cfg.vocabulary_size,
        hash_feature_id=cfg.hash_feature_id,
        max_nnz=max_nnz,
        poll_s=cfg.online_poll_s,
        idle_timeout_s=cfg.online_idle_timeout_s,
        max_batches=cfg.online_max_batches,
        skip_batches=skip_batches,
        idle_flag=idle,
        # The driver's SIGTERM handler sets this: an UNBOUNDED follow
        # stream (idle_timeout_s = 0) must end at the next poll so the
        # graceful checkpoint-and-exit path actually runs — without it a
        # stop request while the stream is idle would block forever on
        # an empty prefetch queue.
        stop=stop,
    )
    if cfg.steps_per_call > 1:
        from fast_tffm_tpu.utils.prefetch import grouped_pairs

        raw = grouped_pairs(raw, cfg.steps_per_call)
    stats = InputStats()
    stats.bind_stream_idle(idle)
    gen = stats.timed(raw, to_batch)
    depth = max(1, cfg.queue_size // max(1, cfg.steps_per_call))
    return InputStream(prefetch(gen, depth=depth, stats=stats), stats)


def _files_fingerprint(files) -> str:
    """Input-dataset identity for the resume cursor: the train file list
    plus each file's size.  A cursor's batch offset only means something
    against the exact data it was saved over — if the files changed (the
    online-append scenario: rows landing between crash and resume shift
    every later row, and a shuffled epoch's permutation is drawn over
    the TOTAL row count), resuming at the old offset would silently
    misalign data and weights.  Size is the cheap stat-only proxy:
    append/truncate/replace all move it; a byte-for-byte same-size edit
    does not, but that is not a failure mode a crash produces."""
    import hashlib

    h = hashlib.md5()
    for p in files:
        try:
            size = os.path.getsize(p)
        except OSError:
            size = -1
        h.update(f"{p}:{size}\n".encode())
    return h.hexdigest()[:16]


def _resolve_cursor(cfg: Config, cursor, log) -> tuple[int, int]:
    """(start_epoch, start_batch) from a restored input cursor.

    The cursor must describe THIS run's input identity (batch size,
    shuffle settings, train-file fingerprint) — anything else falls
    back, with a warning, to the legacy start-of-data behavior rather
    than resuming at a position that means something different now.  A
    cursor at or past ``epoch_num`` is a COMPLETED run: resume then
    keeps its historical meaning of "train epoch_num more epochs"
    (test-pinned), so it also starts at (0, 0)."""
    if not cursor:
        return 0, 0
    exact = bool(cursor.pop("_exact", False))
    if int(cursor.get("version", 0)) > 1:
        log(
            "warning: checkpoint input cursor has a newer version "
            f"({cursor.get('version')}) than this build understands — "
            "resuming at the start of the data (legacy behavior)"
        )
        return 0, 0
    # Multi-host cursor vector: the chain head carries every host's exact
    # position (hosts[p]); resume hands each host back ITS entry.  A
    # topology change (different process count) or an internally
    # disagreeing vector cannot be resumed exactly — loud legacy fallback.
    hosts = cursor.pop("hosts", None)
    saved_pcount = cursor.pop("process_count", None)
    if hosts is not None:
        pcount, p = jax.process_count(), jax.process_index()
        if (saved_pcount or len(hosts)) != pcount or p >= len(hosts):
            log(
                "warning: checkpoint cursor vector was saved by "
                f"{saved_pcount or len(hosts)} host(s), this run has "
                f"{pcount} — resuming at the start of the data (legacy "
                "behavior)"
            )
            return 0, 0
        entries = [
            ((h or {}).get("epoch"), (h or {}).get("batch_in_epoch")) for h in hosts
        ]
        if any(e != entries[0] for e in entries[1:]):
            log(
                "warning: checkpoint cursor vector disagrees across hosts "
                f"({entries}) — resuming at the start of the data (legacy "
                "behavior)"
            )
            return 0, 0
        mine = hosts[p] or {}
        if mine.get("epoch") is not None:
            cursor["epoch"] = int(mine["epoch"])
            cursor["batch_in_epoch"] = int(mine.get("batch_in_epoch") or 0)
    follow = bool(cfg.online_follow)
    if follow and cursor.get("follow"):
        # Append-only stream identity is PREFIX-based (growth is the
        # normal case — data/stream.py): re-hash exactly the prefix
        # window the cursor recorded.  A mismatch means the file was
        # REPLACED, rewritten, or truncated: the cursor's batch offset
        # now points into different data, and "resume at the start"
        # would silently re-train the whole stream — fail LOUDLY instead
        # (unlike the batch paths' warn-and-restart, there is no safe
        # fallback here).
        from fast_tffm_tpu.data.stream import stream_prefix_matches

        if cursor.get("files") is not None and not stream_prefix_matches(
            cfg.train_files, cursor["files"]
        ):
            raise ValueError(
                "online resume: the train stream's PREFIX changed since "
                "this cursor was saved (file replaced/rewritten/truncated, "
                "not appended?) — the saved batch offset no longer names "
                "the same data.  Start fresh (drop --resume) or restore "
                "the original stream file."
            )
        # Prefix verified; exclude "files" from the equality table below
        # (the re-hash IS the check — fingerprints of a grown file
        # legitimately differ).
        want_files = cursor.get("files")
    elif follow:
        # A batch-run cursor under a follow config (mode switch): the
        # fingerprint flavors can never match — legacy fallback below.
        want_files = object()
    else:
        want_files = _files_fingerprint(cfg.train_files)
    mismatched = [
        f"{key} {cursor.get(key)!r} != {want!r}"
        for key, want in (
            ("batch_size", int(cfg.batch_size)),
            ("shuffle", bool(cfg.shuffle)),
            ("shuffle_seed", int(cfg.shuffle_seed) if cfg.shuffle else cursor.get("shuffle_seed")),
            ("follow", follow if "follow" in cursor else follow or None),
            ("files", want_files),
        )
        if cursor.get(key) != want
    ]
    if mismatched:
        log(
            "warning: checkpoint input cursor does not match this config "
            f"({'; '.join(mismatched)}) — resuming at the start of the "
            "data (legacy behavior)"
        )
        return 0, 0
    e = max(0, int(cursor.get("epoch", 0)))
    b = max(0, int(cursor.get("batch_in_epoch", 0)))
    if e >= cfg.epoch_num:
        if exact:
            # A rollback cursor is a literal position, never "train more
            # epochs": at/past the end it means "no input left" (the run
            # finishes with the final save alone).
            return cfg.epoch_num, 0
        return 0, 0
    log(f"resuming input at epoch {e}, batch {b} (exact-position cursor)")
    return e, b


def _timed_next(stream, clocks: dict):
    """Iterate ``stream``, adding the time the caller spends inside its
    ``next()`` — blocked on the input — to ``clocks["wait_s"]``."""
    it = iter(stream)
    while True:
        t0 = time.perf_counter()
        try:
            item = next(it)
        except StopIteration:
            return
        finally:
            clocks["wait_s"] += time.perf_counter() - t0
        yield item


def _window_clocks(clocks: dict, steps: int, sync_s: float) -> dict:
    """The kind=train record's stage fields, and the next window opened.
    Per step of the log window: ``wait_ms`` (in the input's ``next()``),
    ``dispatch_ms`` (inside ``step_fn``), ``host_ms`` (the rest of the
    loop thread's time); ``sync_ms`` is the one loss fetch at the
    boundary — the host's slack, since it waits for the device there.
    wait + dispatch + host + sync/steps is the wall time a step."""
    now = time.perf_counter()
    wall = now - clocks["t0"]
    wait_s, dispatch_s = clocks["wait_s"], clocks["dispatch_s"]
    clocks.update(wait_s=0.0, dispatch_s=0.0, t0=now)
    per_step = lambda s: round(1e3 * s / steps, 4)
    return {
        "wait_ms": per_step(wait_s),
        "dispatch_ms": per_step(dispatch_s),
        "host_ms": per_step(wall - wait_s - dispatch_s - sync_s),
        "sync_ms": round(1e3 * sync_s, 4),
    }


def _say_interaction(log, model, batch_rows: int, backward: bool = True) -> dict:
    """The form the model's interaction takes on ``batch_rows`` rows (a chip's
    share of a step), said once at start-up and returned as the step's
    ``kind=profile`` fields.  ``fm_score``'s choice at trace time
    (``ops.fm.interaction_form``), or the field-aware model's one form, the
    pair tensor of ``models/ffm.py`` (``interaction_form = ffm_pair_tensor``,
    ``order`` 2); the plain FM of order 2 and DeepFM's FM half score by the
    closed form of order 2.  A model with a perceptron over the gathered rows
    (DeepFM) says that too, and the record carries its
    ``models.deepfm.PERCEPTRON_FIELDS`` (null for every other model)."""
    from fast_tffm_tpu.models.deepfm import describe_perceptron, perceptron_profile
    from fast_tffm_tpu.ops.fm import describe_interaction, interaction_profile

    shape = (getattr(model, "order", 2), batch_rows, model.factor_num)
    form = getattr(model, "interaction_form", None)  # a model with a form of its own says it
    if form is None:
        said = describe_interaction(*shape, backward=backward)
    else:
        said = model.describe_interaction(backward=backward)
    log("interaction: " + said)
    head = perceptron_profile(model, batch_rows, backward=backward)
    if head["dense_params"] is not None:
        log("perceptron: " + describe_perceptron(model))
    return {**interaction_profile(*shape, backward=backward, form=form), **head}


def _run_training(
    cfg: Config,
    state,
    step_fn,
    predict_step,
    max_nnz,
    log=print,
    train_stream=None,
    to_batch=None,
    examples_per_step=None,
    evaluate=None,
    extra_metrics=None,
    saveable=None,
    step_hook=None,
    row_dim=0,
    tail_profile=None,
    exchange_profile=None,
    interaction_profile=None,
    mark_touched=None,
    start_cursor=None,
    rollback=None,
    runtime=None,
    mesh=None,
    datastats_ids=None,
    accum_restart=None,
    stream_stop=None,
    paramstore=None,
    tier_profile=None,
):
    """Shared step loop.  ``train_stream(epoch)`` overrides the per-epoch
    input stream, ``to_batch(parsed, w)`` the host→device batch assembly,
    and ``evaluate`` the validation pass — the multi-host path plugs in
    sharded input + global-array stitching here without forking the loop.

    ``step_hook(step_num)`` (optional) runs in the LOOP THREAD after every
    dispatch, before the graceful-stop check — a deterministic injection
    point for tests (e.g. raising SIGTERM at an exact step instead of
    racing a wall-clock timer) and for external schedulers.  It must be
    cheap: it sits on the hot path.

    Step fusion (``steps_per_call`` > 1) needs no fork either: a fused
    ``step_fn`` returns a PER-MICRO-STEP loss vector [K] instead of a
    scalar, and the loop reads K off the loss shape — step counting,
    throughput accounting, loss logging, and the NaN check all keep
    per-step granularity (every micro-step loss lands in the log window's
    mean).  The graceful-stop signal and the log cadence are only CHECKED
    between dispatches, so stop/checkpoint boundaries and log-window edges
    become K-step-aligned — the documented cost of fusing away the
    per-step host round-trip.
    ``extra_metrics()`` (optional) is drained at every log point and its
    dict merged into the stdout line and the JSONL record (dist_train uses
    it to report alltoall overflow-fallback step counts and the row shards
    whose tail took the whole exchanged list, ``shard_tail_full_steps``, and
    the chips whose lookup read the whole list, ``shard_lookup_full_steps``).  ``saveable``
    (optional) converts the live state to its checkpoint form before
    every save — the packed table layout uses it to store LOGICAL [V, D]
    arrays, keeping packed and rows checkpoints interchangeable.
    ``row_dim`` (the model's logical row width) and ``mark_touched`` (an
    optional custom touched-row bitmap marker — the device-cache drivers
    mark from their resident id arrays) parameterize the async/delta
    checkpoint subsystem (checkpoint_async.AsyncCheckpointer).
    ``tail_profile`` (the rows layout's trace-time choices,
    ``optim.rows_tail_profile``: ``tail_form`` ``sweep`` | ``rows``; how
    duplicates are summed, ``tail_duplicates`` ``kernel`` | ``segment_sum``,
    the latter on rows ``segment_sum_lanes`` wide; ``tail_permutation``; the
    sweep's ``tail_block_lanes``; dist_train's ``tail_slots``, the exchanged
    slots a row shard's tail takes, ``parallel.train_step.shard_tail_ids``,
    and its lookup's ``lookup_form`` ``sweep`` | ``rows``
    (``parallel.embedding.shard_lookup_form``) with ``lookup_slots``, the
    slots the shard's local gather reads a step;
    empty on every other layout; and the forward gather's,
    ``trainer.gather_profile``: ``gather_form`` ``sweep`` | ``rows`` and the
    sweep kernel's ``gather_items``, its grid length a step) rides the
    step's ``kind=profile`` record beside ``row_dim``.
    ``exchange_profile`` (dist_train's: ``mesh`` {data, row}, ``shard_rows``,
    ``lookup``, ``exchange_bytes_per_step`` = the payload bytes a chip sends
    and receives in the step's collectives, parallel/exchange.py) rides that
    record too, and its byte count every ``kind=train`` record.
    ``interaction_profile`` (``ops.fm.interaction_profile``: ``order``,
    ``interaction_form`` ``order2`` | ``pallas_anova`` | ``scan``, or the
    field-aware model's ``ffm_pair_tensor``, and the kernel's
    ``anova_programs_per_step``, null for the other forms) rides it as well,
    and so does ``tier_profile`` (the tiered store's ``hot_rows``,
    ``miss_rows`` and ``residency``, ``TieredParamServer.profile``).

    ``datastats_ids`` (optional ``batch -> device ids``) lets the sampled
    id-statistics collector read a device-cache batch's ids straight off
    the resident arrays; streamed paths feed it the host-side ``parsed``
    rows instead (profiling.DataStatsCollector).

    ``start_cursor`` (a dict from checkpoint.read_input_cursor) resumes
    the INPUT at the exact saved position: the epoch loop starts at the
    cursor's epoch and the first stream opens at its batch offset, so a
    resumed run consumes precisely the batches an uninterrupted run
    would have — its loss sequence matches (bit-identically when the
    XLA program is the same).  Every save boundary embeds the live
    cursor back into the checkpoint.  ``train_stream(epoch,
    skip_batches)`` must honor the skip.  ``rollback`` (a note dict from
    the on_nan=rollback driver loop) is recorded as a kind=anomaly
    event=rollback at run start."""
    if saveable is None:
        saveable = lambda st: st
    if train_stream is None:
        train_stream = lambda epoch, skip_batches=0: _stream(
            cfg, cfg.train_files, max_nnz, epochs=1, to_batch=to_batch,
            shuffle_epoch=epoch, steps_per_call=cfg.steps_per_call,
            skip_batches=skip_batches, dedup_guard=True,
        )
    if to_batch is None:
        to_batch = Batch.from_parsed
    if evaluate is None:
        # Validation ships batches the same way training does (in particular
        # the fields-skipping transfer for models that never read fields).
        def evaluate(cfg, predict_step, state, files, max_nnz):
            return _evaluate(cfg, predict_step, state, files, max_nnz, to_batch=to_batch)
    n_chips = jax.device_count()
    meter = Throughput()
    losses = []
    pending_steps = 0  # micro-steps since the last log point
    start_step = step_num = int(state.step)
    # On multi-host pods every process runs this loop; process 0 owns the
    # profiler trace, and each host writes its OWN telemetry file
    # (host_metrics_path — tools/report.py merges them per run_id).
    is_lead = jax.process_index() == 0
    ckpt_format = cfg.checkpoint_format
    if ckpt_format == "npz" and os.path.isdir(cfg.model_file):
        # model_file already holds an orbax directory (e.g. an earlier
        # orbax run): an npz os.replace onto it would crash at save
        # time, after training.  Stay in the format the path already has.
        log(f"note: {cfg.model_file} is an orbax checkpoint dir — keeping orbax format")
        ckpt_format = "orbax"
    elif jax.process_count() > 1 and ckpt_format == "npz":
        # Multi-host npz runs the single-writer protocol: the state
        # replicates to every host (dist_train supplies the replicating
        # saveable), process 0 alone publishes full+delta files, and every
        # other host synchronizes on the published content signature.
        # The memory bill is the full logical table per host — orbax stays
        # the format for beyond-host tables (DESIGN §8).
        log(
            "note: multi-host npz checkpoints — process 0 is the sole "
            "writer; peers barrier on each publish's content signature"
        )
    # Unified telemetry: every record (train/input/validation/compile/mem/
    # stall/anomaly/summary) shares one run_id and the envelope schema
    # (telemetry.SCHEMAS); the compile sentinel drains per dispatch, the
    # liveness watchdog fires kind=stall with thread stacks when the loop
    # wedges, and the close() record documents the run's totals.
    from fast_tffm_tpu.distributed import host_metrics_path

    run_id = cfg.telemetry_run_id
    if runtime is not None and runtime.active and not run_id:
        # One run identity across the pod: the lead draws it, everyone
        # else adopts it — tools/report.py groups per-host files by it.
        from fast_tffm_tpu.telemetry import new_run_id

        run_id = runtime.broadcast("run_id", new_run_id() if runtime.is_lead else None)
    monitor = RunMonitor(
        host_metrics_path(cfg.metrics_path) if cfg.metrics_path else None,
        run_id=run_id,
        source="train",
        stall_timeout_s=cfg.telemetry_stall_timeout_s,
        mem_every_s=cfg.telemetry_mem_every_s,
        log=log,
        device=log_device(log, "train"),
    )
    # Deep observability (profiling.py): the on-demand step-window trace,
    # the per-compiled-program measured cost ledger (kind=profile — the
    # evidence column next to the modeled HBM floor), and the sampled
    # id-traffic statistics (kind=datastats — the dedup/heavy-hitter
    # numbers ROADMAP item 3 sizes against).  All compiles these issue
    # attribute as warmup; the trace is lead-host-only.
    from fast_tffm_tpu.profiling import (
        CostLedger,
        DataStatsCollector,
        StepProfiler,
        modeled_step_bytes,
    )

    profile_steps = cfg.telemetry_profile_steps
    if cfg.trace_dir and not profile_steps:
        # ``trace_dir`` alone asks for the default window: ``trace_steps``
        # steps, past the compile and five steps of warm-up.
        profile_steps = f"{start_step + 5}:{start_step + 5 + max(1, cfg.trace_steps)}"
    profiler = StepProfiler(
        profile_steps if is_lead else "",
        cfg.trace_dir or (cfg.model_file + ".profile"),
        monitor=monitor,
        log=log,
    )
    ledger = CostLedger(monitor, source="train") if cfg.telemetry_profile_costs else None
    datastats = None
    if cfg.telemetry_datastats_every_steps > 0:
        datastats = DataStatsCollector(
            monitor,
            vocab=cfg.vocabulary_size,
            row_dim=max(1, row_dim),
            every_steps=cfg.telemetry_datastats_every_steps,
            heavy_hitter_k=cfg.telemetry_heavy_hitter_k,
            ids_fn=datastats_ids,
        )
    accum_cols = max(1, row_dim) if cfg.adagrad_accumulator == "element" else 1
    exchange_counter = (
        {"exchange_bytes_per_step": exchange_profile["exchange_bytes_per_step"]}
        if exchange_profile else {}
    )

    def _stage_step_profile(b, parsed):
        """First-dispatch capture: abstract shapes (before donation) plus
        the modeled HBM floor for THIS batch's ids — measured and modeled
        land on one kind=profile record."""
        modeled = None
        ex = None
        if isinstance(parsed, list):
            ex = sum(p.batch_size for p in parsed)
            modeled = sum(
                modeled_step_bytes(p.ids, max(1, row_dim), accum_cols)[0]
                for p in parsed
            )
        elif parsed is not None and hasattr(parsed, "ids"):
            ex = parsed.batch_size
            modeled, _ = modeled_step_bytes(parsed.ids, max(1, row_dim), accum_cols)
        elif examples_per_step is not None:
            k_hint = 1
            shape = tuple(getattr(b, "shape", ()) or ())
            if shape:
                k_hint = int(np.prod(shape))
            ex = examples_per_step * k_hint
            if datastats_ids is not None:
                try:
                    # One-time D2H of one batch's ids: the modeled floor
                    # needs the host-side unique count (setup cost only).
                    # The slicer returns the whole dispatch's rows (all K
                    # batches of a scan chunk); whole-window unique only
                    # UNDERSTATES the per-batch RMW term — still a floor.
                    ids_host = np.asarray(datastats_ids(b))
                    modeled, _ = modeled_step_bytes(
                        ids_host, max(1, row_dim), accum_cols
                    )
                except Exception:
                    modeled = None
        ledger.stage(
            "train_step", step_fn, (state, b), examples=ex, modeled_bytes=modeled,
            row_dim=max(1, row_dim),
            **{
                "segment_sum_lanes": None, "tail_form": None,
                "tail_duplicates": None, "tail_permutation": None,
                "tail_block_lanes": None, "tail_slots": None,
                "gather_form": None, "gather_items": None,
                "lookup_form": None, "lookup_slots": None,
                **(tail_profile or {}),
            },
            **(exchange_profile or {}),
            **(interaction_profile or {}),
            **(tier_profile or {}),
        )

    # Pod liveness: this host's heartbeat (armed at bring-up) starts
    # carrying the step counter, and a peer-heartbeat monitor classifies a
    # stale host as a host-level kind=stall long before jax's own
    # coordination-service timeout would notice.
    heartbeat = getattr(runtime, "heartbeat", None) if runtime is not None else None
    host_monitor = None
    if (
        runtime is not None
        and runtime.process_count > 1
        and getattr(runtime, "runtime_dir", None)
        and cfg.host_stall_timeout_s > 0
    ):
        from fast_tffm_tpu.distributed import HostMonitor

        def _on_host_stall(peer, classification, detail):
            monitor.emit(
                "stall",
                step=step_num,
                deadline_s=cfg.host_stall_timeout_s,
                since_last_step_s=detail.get("age_s"),
                classification=classification,
                prefetch_queue_depth=None,
                stacks={},
                peer=peer,
                peer_last_step=detail.get("last_step"),
            )

        host_monitor = HostMonitor(
            runtime.runtime_dir,
            runtime.process_index,
            runtime.process_count,
            cfg.host_stall_timeout_s,
            _on_host_stall,
            poll_s=min(1.0, cfg.host_stall_timeout_s / 4.0),
        )
    if rollback is not None:
        # The failed attempt's monitor already recorded the non-finite
        # loss; THIS record documents the recovery decision (restored
        # step, skipped-to position, rollback ordinal) in the new run.
        monitor.emit_anomaly(
            int(rollback.get("step", 0)), rollback.get("loss"),
            event="rollback", **{k: v for k, v in rollback.items()
                                 if k not in ("step", "loss")},
        )
    # Deterministic fault injection (resilience.py): a CLI-armed plan
    # kills via the step_hook the driver already passed; nan faults
    # poison the loss below; io/torn faults fire inside the reader and
    # checkpoint writer.  ``faults`` is None on every normal run.
    faults = active_faults()
    # Accumulator window-restart grid: ABSOLUTE step multiples of N, so a
    # crash-resumed run fires its resets at the same steps the
    # uninterrupted run would have (an anchor relative to the resumed
    # start would shift every later reset).  K-aligned like every other
    # boundary: the reset fires at the first dispatch crossing a multiple.
    if accum_restart is not None:
        _n = max(1, int(cfg.online_accum_restart_steps))
        next_restart = (start_step // _n + 1) * _n
    else:
        next_restart = None
    # Exact-position input cursor: tracked per dispatch, embedded in
    # every checkpoint (full, delta, final) so a crash-resume reopens
    # the input mid-epoch at the precise saved batch.
    start_epoch, start_batch = _resolve_cursor(cfg, start_cursor, log)
    cur = {"epoch": start_epoch, "batch": start_batch}
    # Dataset identity, stamped once: cursors saved by this run describe
    # THIS file set; a resume against changed files must not trust them.
    # Follow mode uses the append-stable PREFIX fingerprint (growth is
    # the normal case); the batch paths keep the size-based one.
    if cfg.online_follow:
        from fast_tffm_tpu.data.stream import stream_prefix_fingerprint

        files_fp = stream_prefix_fingerprint(cfg.train_files)
    else:
        files_fp = _files_fingerprint(cfg.train_files)

    def input_cursor() -> dict:
        c = {
            "version": 1,
            "epoch": int(cur["epoch"]),
            "batch_in_epoch": int(cur["batch"]),
            "batch_size": int(cfg.batch_size),
            "shuffle": bool(cfg.shuffle),
            "shuffle_seed": int(cfg.shuffle_seed),
            "steps_per_call": int(cfg.steps_per_call),
            "files": files_fp,
        }
        if cfg.online_follow:
            c["follow"] = True
        return c
    # Save boundaries (full + delta) go through ONE owner: async full saves
    # snapshot on device and hand the convert/D2H/write to a writer thread
    # (at most one in flight, back-pressure counted); delta saves ship only
    # the touched-row window; every save emits a kind=ckpt record.  The
    # SIGTERM/final paths below stay synchronous (sync=True), so the
    # last-good-state guarantee is exactly the old one.
    from fast_tffm_tpu.checkpoint_async import AsyncCheckpointer

    if cfg.delta_every_steps > 0 and ckpt_format != "npz":
        raise ValueError(
            "delta_every_steps > 0 requires npz checkpoints — this run "
            "resolved checkpoint_format to orbax (model_file already "
            "holds an orbax dir); disable delta saves or point "
            "model_file at a fresh npz path"
        )
    if cfg.async_save and ckpt_format != "npz":
        log("note: async_save applies to npz checkpoints — orbax saves stay synchronous")
    ckpt = AsyncCheckpointer(
        cfg.model_file,
        ckpt_format,
        monitor=monitor,
        log=log,
        chunk_bytes=cfg.checkpoint_chunk_mb << 20,
        async_save=cfg.async_save,
        delta_every_steps=cfg.delta_every_steps,
        delta_chain_max=cfg.delta_chain_max,
        full_every_s=cfg.delta_full_every_s,
        chain_max_bytes=cfg.delta_chain_max_bytes,
        # Tiered runs size the touched-row bitmap at the COMPACT device
        # capacity (slots), not the logical vocab — a 2^30-row bitmap
        # would itself be a gigabyte.
        vocab=(paramstore.capacity if paramstore is not None else cfg.vocabulary_size),
        paramstore=paramstore,
        table_layout=cfg.table_layout,
        row_dim=row_dim,
        mark_fn=mark_touched,
        start_step=start_step,
        cursor_fn=input_cursor,
        runtime=runtime,
        mesh=mesh,
    )
    # Preemption-safe shutdown (the reference's only recovery story was
    # Supervisor restart-from-checkpoint; cloud TPU maintenance sends
    # SIGTERM): first signal finishes the current step, checkpoints, and
    # exits cleanly; a second signal falls through to the default handler.
    stop_requested = threading.Event()
    restore_handlers = {}
    if threading.current_thread() is threading.main_thread():
        def _on_signal(signum, frame):
            log(f"received signal {signum}: checkpointing after current step")
            stop_requested.set()
            if stream_stop is not None:
                # Unbounded follow streams end at their next poll so the
                # loop (blocked on an idle stream's empty queue) wakes up
                # to take the graceful checkpoint-and-exit path.
                # ``stream_stop`` is a one-slot holder of the LIVE
                # stream's Event (a fresh one per stream — see train()).
                stream_stop[0].set()
            signal.signal(signum, restore_handlers[signum])

        for sig in (signal.SIGTERM, signal.SIGINT):
            restore_handlers[sig] = signal.signal(sig, _on_signal)
    # The loop thread's clocks over the open log window (kind=train):
    # blocked on the input, inside step_fn, and the window's start; what
    # is left of the wall time is the host's own (hooks, sentinel drain,
    # checkpoint notes, validation).
    clocks = {"wait_s": 0.0, "dispatch_s": 0.0, "t0": time.perf_counter()}
    try:
        for epoch in range(start_epoch, cfg.epoch_num):
            if stop_requested.is_set():
                break
            # A resumed first epoch reopens mid-stream at the cursor's
            # batch offset; every later epoch starts at 0 as usual.
            epoch_stream = train_stream(
                epoch, cur["batch"] if epoch == start_epoch else 0
            )
            # Streamed inputs carry per-stream InputStats (wire bytes,
            # parse/H2D ms, prefetch depth — data/wire.py); drained into
            # kind=input records at every log point.  Device-cached
            # streams are bare generators (no stats — no per-step wire).
            input_stats = getattr(epoch_stream, "stats", None)
            # Each epoch's stream owns a fresh prefetch queue; point the
            # stall watchdog's depth + producer-liveness probes at it.
            monitor.set_queue_depth_fn(getattr(epoch_stream, "queue_depth", None))
            monitor.set_producer_alive_fn(
                getattr(epoch_stream, "producer_alive", None)
            )
            monitor.set_stream_idle_fn(getattr(epoch_stream, "stream_idle", None))
            for b, parsed, w in _timed_next(epoch_stream, clocks):
                if b is None:
                    b = to_batch(parsed, w)
                if ledger is not None and ledger.want("train_step"):
                    # Abstract shapes must be captured BEFORE the dispatch
                    # donates the state buffers.
                    _stage_step_profile(b, parsed)
                t_dispatch = time.perf_counter()
                with step_trace("train", step_num):
                    state, loss = step_fn(state, b)
                clocks["dispatch_s"] += time.perf_counter() - t_dispatch
                # A fused call returns per-micro-step losses [K]; K=1
                # returns the classic scalar.  The shape is static — no
                # device sync happens here.
                k = int(loss.shape[0]) if getattr(loss, "ndim", 0) else 1
                first_call = step_num == start_step
                step_num += k
                cur["batch"] += k  # cursor: k micro-batches consumed
                if first_call:
                    # Call 1 paid the XLA compile; a meter window that
                    # includes it reads as a throughput collapse.
                    jax.block_until_ready(loss)
                    meter.reset()
                # Heartbeat + compile-sentinel drain + due mem sample.
                # The FIRST epoch this process runs (epoch 0, or the
                # cursor's epoch on a resume — a fresh process pays its
                # XLA compiles regardless of where the input reopens) is
                # the shape-discovery pass: the first dispatch AND the
                # epoch-tail remainder shape (steps_per_call > 1 ships a
                # shorter [K', B, ...] superbatch) legitimately compile
                # once — all priced in as warmup.  Every shape recurs
                # identically from the next epoch on, so any later
                # kind=compile event is a steady-state recompile — the
                # thing the serving bucket ladder pins to zero, now
                # visible on the train path too.
                monitor.on_dispatch(step_num, warmup=(epoch == start_epoch))
                if heartbeat is not None:
                    heartbeat.set_step(step_num)
                # Deep-observability hooks, all cheap no-ops when idle:
                # the trace window check, the (once-per-program) measured
                # cost flush, and the sampled id-stats reducer.
                profiler.on_step(step_num)
                if ledger is not None:
                    ledger.flush(step_num)
                if datastats is not None:
                    datastats.note(step_num, parsed=parsed, batch=b)
                if next_restart is not None and step_num >= next_restart:
                    # Window restart ([Online] accum_restart_steps): reset
                    # every Adagrad accumulator to the init value.  The
                    # reset program's one-time compile is priced as warmup.
                    next_restart = (step_num // _n + 1) * _n
                    with monitor.warmup_window():
                        state = accum_restart(state)
                if ckpt.delta_enabled:
                    # OR this batch's rows into the device bitmap; at a
                    # delta boundary, ship the touched window (writer
                    # thread) and resume immediately.
                    ckpt.note_batch(b)
                    if ckpt.delta_due(step_num) and not stop_requested.is_set():
                        with monitor.suspended():
                            ckpt.delta_boundary(state, saveable, step_num)
                losses.append(loss)  # device value(s); only sync at log points
                if faults is not None and faults.nan_due(step_num):
                    # Chaos: poison this window's loss so the finite
                    # check (and the on_nan policy) fire deterministically.
                    losses[-1] = np.float32("nan")
                pending_steps += k
                if examples_per_step is not None:
                    meter.add(examples_per_step * k)
                elif isinstance(parsed, list):
                    meter.add(sum(p.batch_size for p in parsed))
                else:
                    meter.add(parsed.batch_size)
                if step_hook is not None:
                    # Before the stop check: a hook that raises a signal
                    # here is honored on THIS iteration (the handler sets
                    # stop_requested in this same thread).
                    step_hook(step_num)
                if stop_requested.is_set():
                    break
                if pending_steps >= cfg.log_every:
                    rate = meter.rate()
                    t_sync = time.perf_counter()
                    with span("train.sync"):
                        mean_loss = float(
                            np.mean(
                                np.concatenate(
                                    [np.atleast_1d(np.asarray(l)) for l in losses]
                                )
                            )
                        )
                    # The window's clocks close here, at the fetch: what
                    # follows (the caller's ``log``, the records) is the
                    # next window's host time.
                    stage_ms = _window_clocks(
                        clocks, pending_steps, time.perf_counter() - t_sync
                    )
                    # What the host lost all at once in this window (freezes
                    # of the interpreter, the collector's pauses): inside
                    # the stage fields above, not beside them.
                    stage_ms.update(monitor.drain_host_clock())
                    pending_steps = 0
                    _check_finite(
                        mean_loss, cfg, monitor=monitor,
                        step=int(state.step), state=state,
                        cursor=input_cursor(),
                    )
                    for ev in drain_fault_events():
                        monitor.emit("fault", step=int(state.step), **ev)
                    extra = extra_metrics() if extra_metrics is not None else {}
                    extra_txt = "".join(f" {k} {v}" for k, v in extra.items() if v)
                    log(
                        f"step {int(state.step)} epoch {epoch} "
                        f"loss {mean_loss:.5f} "
                        f"examples/sec {rate:,.0f} (/chip {rate / n_chips:,.0f})"
                        f"{extra_txt}"
                    )
                    monitor.emit(
                        "train",
                        step=int(state.step),
                        epoch=epoch,
                        loss=round(float(mean_loss), 6),
                        examples_per_sec=round(rate, 1),
                        examples_per_sec_per_chip=round(rate / n_chips, 1),
                        **stage_ms,
                        **extra,
                        **exchange_counter,
                    )
                    if input_stats is not None:
                        rec = input_stats.drain()
                        if rec:
                            monitor.emit(
                                "input", step=int(state.step), epoch=epoch, **rec
                            )
                    if paramstore is not None:
                        trec = paramstore.stats.drain(
                            paramstore.pending_rows, paramstore.hot_rows
                        )
                        if trec:
                            monitor.emit(
                                "tiering", step=int(state.step), epoch=epoch,
                                **trec,
                            )
                    losses.clear()
                    meter.reset()
            if stop_requested.is_set():
                break
            # Epoch complete: the cursor now names the NEXT epoch's start
            # (the position the epoch-end save below must embed).  Follow
            # mode is the exception: its one endless epoch never
            # "completes" — the stream merely went quiet (idle timeout /
            # max_batches bound), and the cursor must keep naming the
            # batch offset so the next ``--resume`` continues EXACTLY
            # where this run stopped once more rows land.
            if not cfg.online_follow:
                cur["epoch"], cur["batch"] = epoch + 1, 0
            if input_stats is not None:
                # Epoch-tail drain: the stream (and its stats) dies here,
                # and a run (or tail) shorter than log_every would
                # otherwise never emit its kind=input record at all.
                rec = input_stats.drain()
                if rec:
                    monitor.emit("input", step=int(state.step), epoch=epoch, **rec)
            if paramstore is not None:
                # Same epoch-tail rule for the tiering record.
                trec = paramstore.stats.drain(
                    paramstore.pending_rows, paramstore.hot_rows
                )
                if trec:
                    monitor.emit(
                        "tiering", step=int(state.step), epoch=epoch, **trec
                    )
            if losses:
                # Epoch boundary syncs anyway (validation / checkpoint); a
                # poisoned state must abort BEFORE the save below replaces
                # the last good checkpoint.  Check the whole unlogged
                # tail window (it is at most log_every entries, once per
                # epoch): a REAL NaN propagates into every later loss,
                # but an INJECTED one poisons a single host-side entry —
                # the last entry alone would miss it mid-window.
                _check_finite(
                    float(
                        np.mean(
                            np.concatenate(
                                [np.atleast_1d(np.asarray(l)) for l in losses]
                            )
                        )
                    ),
                    cfg, monitor=monitor, step=int(state.step), state=state,
                    cursor=input_cursor(),
                )
            if cfg.validation_files:
                # No train dispatches complete during validation — a long
                # pass must not read as a stall (watchdog suspended).
                with monitor.suspended():
                    val_auc = evaluate(
                        cfg, predict_step, state, cfg.validation_files, max_nnz
                    )
                log(f"epoch {epoch} validation auc {val_auc:.5f}")
                monitor.emit(
                    "validation",
                    step=int(state.step),
                    epoch=epoch,
                    validation_auc=round(val_auc, 6),
                )
                # Drain the validation pass's compiles: this process's
                # first epoch's predict compile is priced in (warmup); a
                # LATER epoch compiling again is a genuine steady-state
                # recompile.
                monitor.on_dispatch(int(state.step), warmup=(epoch == start_epoch))
            if cfg.save_every_epochs and (epoch + 1) % cfg.save_every_epochs == 0:
                with monitor.suspended():  # the loop dispatches nothing here
                    # Async mode: snapshot + hand off to the writer; the
                    # loop resumes while the save converts/transfers/writes.
                    ckpt.save_boundary(state, saveable, int(state.step))
                log(f"epoch {epoch} checkpoint -> {cfg.model_file}")
    except PrefetchError as e:
        # The prefetch producer died: surface it as a structured anomaly
        # (the supervisor's restart is the recovery path) — the loud,
        # named failure the old silent wedge never produced.
        monitor.emit_anomaly(
            step_num, None, event="input_pipeline_failure", error=str(e)
        )
        raise
    finally:
        if stream_stop is not None:
            # Abandoned follow producers (exception paths) must stop
            # polling/producing rather than linger for the process's life.
            stream_stop[0].set()
        summary_extra = {}
        if extra_metrics is not None:
            # Drain events from the final partial log window (run end,
            # SIGTERM stop, abort) — a skew burst at the end must still
            # reach the metrics file; it rides the kind=summary record.
            summary_extra = {k: v for k, v in extra_metrics().items() if v}
        # Join any in-flight async write BEFORE the final sync save below:
        # an older publish must never land after (and clobber) a newer one.
        ckpt.finalize()
        summary_extra.update(ckpt.summary())
        # Fault events from the final partial window (io retries, injected
        # faults) + their per-run counter totals onto the summary record.
        for ev in drain_fault_events():
            try:
                monitor.emit("fault", step=int(state.step), **ev)
            except Exception:
                pass
        summary_extra.update(
            {f"fault_{k}": v for k, v in drain_fault_counters().items() if v}
        )
        if ledger is not None:
            summary_extra.update(ledger.summary())
        if datastats is not None:
            summary_extra.update(datastats.summary())
        if paramstore is not None:
            summary_extra.update(paramstore.summary())
        profiler.close(step_num)
        if host_monitor is not None:
            host_monitor.close()
        monitor.close(**summary_extra)
        for sig, handler in restore_handlers.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, TypeError):
                pass
    # The last save is SYNCHRONOUS regardless of async_save: SIGTERM stop,
    # run end — when this returns, the state on disk IS the state returned.
    ckpt.save_boundary(state, saveable, int(state.step), sync=True, emit=False)
    if stop_requested.is_set():
        log(
            f"stopped on signal at step {int(state.step)}, model -> {cfg.model_file} "
            "(resume with --resume)"
        )
    else:
        log(f"training done: steps {start_step}->{int(state.step)}, model -> {cfg.model_file}")
    return state


def train(cfg: Config, *, resume: bool = False, log=print, step_hook=None):
    """Local (single-device) training — the reference's `train` mode."""
    if not cfg.train_files:
        raise ValueError("no train_files configured")
    if cfg.weight_files and len(cfg.weight_files) != len(cfg.train_files):
        # Checked here, not in Config.validate: a shared config must still
        # LOAD on predict-only machines where train-file globs match
        # differently (or not at all).
        raise ValueError(
            f"weight_files has {len(cfg.weight_files)} entries for "
            f"{len(cfg.train_files)} train_files (they align per-file)"
        )
    if cfg.paramstore:
        # Beyond-HBM tables: the tiered host/device parameter store
        # (paramstore/) — its own driver branch because the input path,
        # the step, validation scoring, and every checkpoint boundary
        # are residency-aware.
        return _tiered_train(cfg, resume=resume, log=log, step_hook=step_hook)
    model = build_model(cfg)
    max_nnz = scan_max_nnz(cfg)
    packed = cfg.table_layout == "packed"
    fused = cfg.adagrad_accumulator == "fused"
    saveable = None
    if packed:
        from fast_tffm_tpu.ops.packed_table import (
            unpack_accum_any,
            unpack_fused,
            unpack_table,
        )
        from fast_tffm_tpu.trainer import (
            init_packed_state,
            make_packed_predict_step,
            make_packed_train_step,
            packed_train_step_body,
        )

        v, d = model.vocabulary_size, model.row_dim

        def saveable(st):
            # Checkpoints always hold the LOGICAL arrays ([V, D] table;
            # [V, D] or [V, 1] accumulator by granularity), so packed,
            # fused and rows runs restore each other's models freely.
            if fused:
                t, a = unpack_fused(st.table, v, d)
                return st._replace(
                    table=t, table_opt=st.table_opt._replace(accum=a)
                )
            return st._replace(
                table=unpack_table(st.table, v, d),
                table_opt=st.table_opt._replace(
                    accum=unpack_accum_any(st.table_opt.accum, v, d)
                ),
            )

    def restore_state():
        """model_file -> this run's live layout.  Shared by --resume and
        the on_nan=rollback recovery below.  Packed runs restore the
        LOGICAL checkpoint first and pack it — branching BEFORE
        allocating a fresh packed state, which would peak at packed + 2x
        logical on exactly the large vocabs where OOMs were measured
        (dist_train's packed resume is structured the same way)."""
        logical = restore_checkpoint(
            cfg.model_file,
            init_state(
                model, jax.random.key(0), cfg.init_accumulator_value,
                cfg.adagrad_accumulator,
            ),
            chunk_bytes=cfg.checkpoint_chunk_mb << 20,
        )
        if packed:
            from fast_tffm_tpu.trainer import pack_state

            return pack_state(logical, cfg.init_accumulator_value, fused=fused)
        return logical

    start_cursor = None
    if resume:
        state = restore_state()
        log(
            f"resumed from {cfg.model_file} at step {int(state.step)}"
            + (" (packed)" if packed else "")
        )
        # Exact-position resume: the chain head's input cursor names the
        # batch the restored state stopped at; without one (a pre-cursor
        # checkpoint) the input restarts at the first file, as it always
        # did — forward compatibility, warned about, never an error.
        start_cursor = read_input_cursor(cfg.model_file)
        if start_cursor is None:
            log(
                "note: checkpoint carries no input cursor (pre-resilience "
                "format) — input restarts at the first file (legacy resume)"
            )
    elif packed:
        state = init_packed_state(
            model, jax.random.key(0), cfg.init_accumulator_value,
            cfg.adagrad_accumulator,
        )
    else:
        state = init_state(
            model, jax.random.key(0), cfg.init_accumulator_value, cfg.adagrad_accumulator
        )
    tail_profile = {}  # what the rows layout's tail says of itself, once
    if packed:
        predict_step = make_packed_predict_step(model, fused=fused)
        step_body = lambda mdl, lr, st, b: packed_train_step_body(
            mdl, lr, st, b, cfg.packed_update, cfg.packed_compact_cap
        )
        step_fn = make_packed_train_step(
            model, cfg.learning_rate, cfg.packed_update,
            compact_cap=cfg.packed_compact_cap,
        )
    else:
        predict_step = make_predict_step(model)
        # [Online] adagrad_decay: γ bakes into the step at trace time
        # (γ=1.0 leaves the classic program byte-for-byte — the
        # bit-identity the online tests pin).  Packed layouts reject
        # γ < 1 at config.validate, so the packed bodies stay untouched.
        decay = float(cfg.online_adagrad_decay)
        from fast_tffm_tpu.optim import (
            describe_rows_tail,
            rows_tail_form,
            rows_tail_profile,
        )
        from fast_tffm_tpu.trainer import (
            describe_gather,
            gather_form,
            gather_profile,
            make_decayed_body,
            make_dedup_body,
        )

        if cfg.dedup_gather_rows > 0:
            # Device-side dedup-before-gather (ROADMAP D12): the
            # forward gather touches each unique row once; the stream's
            # host-side guard (_dedup_cap_guard) pins the cap.  Values —
            # and therefore losses — are bit-identical (test-pinned).
            step_body = make_dedup_body(cfg.dedup_gather_rows, decay)
        elif decay != 1.0:
            step_body = make_decayed_body(decay)
        else:
            step_body = None  # train_step_body
        # The form the step's tail takes is optim.sparse_adagrad_update's
        # choice at trace time; asked here only to say it once.
        m_ids = cfg.batch_size * max_nnz
        num_rows, row_dim = state.table.shape
        tail_form = rows_tail_form(
            num_rows, m_ids, row_dim, state.table_opt.accum.shape[-1]
        )
        tail_profile = rows_tail_profile(num_rows, m_ids, row_dim, tail_form)
        log("sparse tail: " + describe_rows_tail(num_rows, m_ids, row_dim, tail_form))
        # So is the forward gather's (trainer.gather_rows; the dedup body
        # brings its own gather and asks nobody).
        if cfg.dedup_gather_rows > 0:
            log(f"forward gather: xla rows of the batch's unique ids (dedup_gather_rows = {cfg.dedup_gather_rows})")
        else:
            gather_kind = gather_form(num_rows, m_ids, row_dim)
            tail_profile.update(gather_profile(num_rows, m_ids, row_dim, gather_kind))
            log("forward gather: " + describe_gather(num_rows, m_ids, row_dim, gather_kind))
        step_fn = make_train_step(
            model, cfg.learning_rate, decay=decay, body=step_body
        )
    if cfg.steps_per_call > 1 and not cfg.device_cache:
        # Streamed step fusion: ONE dispatch (and one H2D superbatch
        # transfer) per K steps.  The scan body is the same step body the
        # K=1 jit uses (packed or rows) — bit-identical per-step results.
        from fast_tffm_tpu.trainer import make_scanned_train_step

        step_fn = make_scanned_train_step(model, cfg.learning_rate, body=step_body)
    to_batch = _batch_converter(model.uses_fields)
    run_kwargs = dict(
        to_batch=to_batch, saveable=saveable, step_hook=step_hook,
        row_dim=model.row_dim, tail_profile=tail_profile,
        interaction_profile=_say_interaction(log, model, cfg.batch_size),
    )
    if cfg.online_accum_restart_steps > 0:
        from fast_tffm_tpu.trainer import make_accum_restart

        run_kwargs["accum_restart"] = make_accum_restart(
            cfg.init_accumulator_value
        )
    if cfg.online_follow:
        # Tail-following online mode: the train file is an append-only
        # FMS stream (data/stream.py) — at EOF the reader polls for
        # growth instead of ending the epoch; bounded by
        # [Online] max_batches / idle_timeout_s, or by SIGTERM.
        from fast_tffm_tpu.data.stream import is_fms

        if len(cfg.train_files) != 1:
            raise ValueError(
                "[Online] follow = true takes exactly ONE train file (an "
                f"append-only FMS stream), got {len(cfg.train_files)}"
            )
        if not is_fms(cfg.train_files[0]):
            raise ValueError(
                f"[Online] follow = true needs an FMS stream file; "
                f"{cfg.train_files[0]!r} is not one (create and append "
                "with fast_tffm_tpu.data.stream.StreamWriter)"
            )
        # One stop Event PER STREAM, published through a shared holder:
        # the signal handler sets whichever stream is live, and an
        # abandoned stream (rollback re-entry) keeps its own latched
        # event — no clear() that could race the old producer's next
        # check.
        follow_stop_ref = [threading.Event()]
        run_kwargs["stream_stop"] = follow_stop_ref

        def _follow_train_stream(epoch, skip_batches=0):
            follow_stop_ref[0] = threading.Event()
            return _follow_stream(
                cfg, cfg.train_files, max_nnz, to_batch, skip_batches,
                stop=follow_stop_ref[0],
            )

        run_kwargs["train_stream"] = _follow_train_stream
    if cfg.device_cache:
        step_fn, train_stream, examples_per_step, mark_touched, ids_fn = (
            _device_cached_input(cfg, model, max_nnz, log, body=step_body)
        )
        run_kwargs.update(
            train_stream=train_stream, examples_per_step=examples_per_step,
            mark_touched=mark_touched, datastats_ids=ids_fn,
        )
    # on_nan = rollback: a non-finite loss restores the last checkpoint
    # and resumes input AT the detection cursor — the diverged window's
    # data is skipped, not replayed (bounded by max_rollbacks; abort mode
    # and a run with no checkpoint yet keep the loud-raise behavior).
    rollbacks = 0
    rollback_note = None
    while True:
        try:
            return _run_training(
                cfg, state, step_fn, predict_step, max_nnz, log,
                start_cursor=start_cursor, rollback=rollback_note,
                **run_kwargs,
            )
        except NonFiniteLossError as e:
            from fast_tffm_tpu.checkpoint import latest_step

            if (
                cfg.on_nan != "rollback"
                or rollbacks >= cfg.max_rollbacks
                or e.cursor is None
                or latest_step(cfg.model_file) is None
            ):
                raise
            rollbacks += 1
            state = restore_state()
            start_cursor = dict(e.cursor, _exact=True)
            rollback_note = {
                "step": e.step,
                "loss": e.loss,
                "rollback_n": rollbacks,
                "restored_step": int(state.step),
                "skip_to_epoch": int(e.cursor.get("epoch", 0)),
                "skip_to_batch": int(e.cursor.get("batch_in_epoch", 0)),
            }
            log(
                f"on_nan = rollback: non-finite loss at step {e.step}; "
                f"restored {cfg.model_file} (step {int(state.step)}), "
                f"skipping input to epoch {rollback_note['skip_to_epoch']} "
                f"batch {rollback_note['skip_to_batch']} "
                f"(rollback {rollbacks}/{cfg.max_rollbacks})"
            )


def _tiered_train(cfg: Config, *, resume: bool, log=print, step_hook=None):
    """[ParamStore] driver: local training over the two-tier parameter
    store (paramstore/) — a device-resident hot tier + the full logical
    table in a memmap-backed host cold store.  The jitted step is the
    UNCHANGED trainer step over the compact [C, D] table; everything
    tiered happens around it: the prefetch thread resolves each
    superbatch (dedup → hit/miss split → remap; paramstore.residency),
    miss rows ride the packed wire alongside the batch
    (paramstore.TieredConverter), updated staging rows write back through
    the pending overlay, and every checkpoint boundary spans both tiers
    (checkpoint_async + paramstore.ckpt).  Converts the scale ladder from
    "what fits in HBM" to "what fits on the host": 2^30+ rows on one
    chip, bit-identical to the resident path at overlapping vocab."""
    from fast_tffm_tpu.data.wire import make_spec
    from fast_tffm_tpu.paramstore import TieredConverter, open_tiered_run
    from fast_tffm_tpu.trainer import (
        make_decayed_body,
        make_scanned_train_step,
        make_train_step,
    )

    model = build_model(cfg)
    max_nnz = scan_max_nnz(cfg)
    server, state, start_cursor = open_tiered_run(
        cfg, model, max_nnz, resume=resume, log=log
    )
    decay = float(cfg.online_adagrad_decay)
    # The tiered inner step already runs over the compact [C, D] staging
    # table with remapped slot ids — exactly the rows-layout operands, so
    # the SAME bodies serve both tiers.
    body = make_decayed_body(decay) if decay != 1.0 else None
    if cfg.steps_per_call > 1:
        inner = make_scanned_train_step(model, cfg.learning_rate, body=body)
    else:
        inner = make_train_step(model, cfg.learning_rate, decay=decay, body=body)
    step_fn = server.wrap_step(inner)
    # The inner step's forms over the compact table, said as train() says
    # them for a resident one.
    from fast_tffm_tpu.optim import describe_rows_tail, rows_tail_form, rows_tail_profile
    from fast_tffm_tpu.trainer import describe_gather, gather_form, gather_profile

    m_ids = cfg.batch_size * max_nnz
    num_rows, row_dim = state.table.shape
    tail_form = rows_tail_form(num_rows, m_ids, row_dim, state.table_opt.accum.shape[-1])
    tail_profile = rows_tail_profile(num_rows, m_ids, row_dim, tail_form)
    log("sparse tail: " + describe_rows_tail(num_rows, m_ids, row_dim, tail_form))
    gather_kind = gather_form(num_rows, m_ids, row_dim)
    tail_profile.update(gather_profile(num_rows, m_ids, row_dim, gather_kind))
    log("forward gather: " + describe_gather(num_rows, m_ids, row_dim, gather_kind))
    # The wire spec lives at the COMPACT capacity: ids narrow to the
    # local slot range (e.g. 3 bytes for a 2^30 logical vocab whose
    # compact tier holds < 2^24 slots).
    spec = make_spec(
        server.capacity, max_nnz,
        with_vals=True, with_fields=model.uses_fields, with_weights=True,
    )
    to_batch = TieredConverter(server, spec)

    def train_stream(epoch, skip_batches=0):
        return _stream(
            cfg, cfg.train_files, max_nnz, epochs=1, to_batch=to_batch,
            shuffle_epoch=epoch, steps_per_call=cfg.steps_per_call,
            skip_batches=skip_batches,
        )

    def evaluate(cfg_, _predict_step, st, files, max_nnz_):
        # Residency-aware scoring: hot rows off the live compact state,
        # miss rows staged read-only through the pending overlay — no
        # state mutation, so the train state threads through untouched.
        server.flush_writeback(st)
        stream = _stream(cfg_, files, max_nnz_, epochs=1, weights=None)
        meter = StreamingAUC()
        for _b, parsed, w in stream:
            scores = np.asarray(server.predict(st, parsed, w))
            ww = np.ones_like(parsed.labels) if w is None else np.asarray(w)
            meter.add(parsed.labels, scores, ww)
        return meter.value()

    def predict_step(_state, _batch):  # pragma: no cover - guard only
        raise RuntimeError(
            "tiered runs score through the residency-aware evaluate path"
        )

    try:
        return _run_training(
            cfg, state, step_fn, predict_step, max_nnz, log,
            train_stream=train_stream, to_batch=to_batch, evaluate=evaluate,
            step_hook=step_hook, row_dim=model.row_dim,
            start_cursor=start_cursor, paramstore=server,
            tail_profile=tail_profile,
            interaction_profile=_say_interaction(log, model, cfg.batch_size),
            tier_profile=server.profile(),
        )
    finally:
        to_batch.close()


def _device_cached_input(cfg: Config, model, max_nnz: int, log, body=None):
    """device_cache = true: the train set becomes device-resident arrays
    sliced on-chip per step — zero per-step host→device bytes (the
    streamed alternative moves every batch through the host every epoch;
    on the bench regime that is a ~300× throughput gap, README
    "Benchmarks").  Input must be FMB-backed: .fmb train_files directly,
    or binary_cache = true to convert text once.  Returns
    ``(step_fn, train_stream, examples_per_step)`` for _run_training; the
    emitted "batch" is a device batch-index scalar and the jitted step
    fuses the batch slice (or the shuffled gather) with the model step.
    """
    from fast_tffm_tpu.data.device_cache import (
        epoch_index_chunks,
        full_epoch_perm,
        load_device_dataset,
        make_cached_ids_slicer,
        make_cached_scan_train_step,
        make_cached_touched_marker,
        make_cached_train_step,
    )

    files = tuple(cfg.train_files)
    if cfg.binary_cache:
        from fast_tffm_tpu.data.binary import ensure_fmb_cache

        files = ensure_fmb_cache(
            files,
            vocabulary_size=cfg.vocabulary_size,
            hash_feature_id=cfg.hash_feature_id,
            max_nnz=max_nnz,
            parser=best_parser(cfg.thread_num),
        )
    if not binary_input(files):
        raise ValueError(
            "device_cache = true needs FMB-backed input: list .fmb files in "
            "train_files, or set binary_cache = true to convert text once"
        )
    data = load_device_dataset(
        files,
        batch_size=cfg.batch_size,
        vocabulary_size=cfg.vocabulary_size,
        hash_feature_id=cfg.hash_feature_id,
        max_nnz=max_nnz,
        weights=cfg.weight_files if cfg.weight_files else None,
        with_fields=model.uses_fields,
    )
    log(
        f"device cache: {data.n_rows} rows resident "
        f"({data.nbytes / 2**20:.1f} MiB, {data.batches} batches/epoch)"
    )
    perm_ref = [None]

    def _maybe_draw_perm(epoch):
        if cfg.shuffle:
            perm_ref[0] = jax.device_put(
                full_epoch_perm(data, cfg.shuffle_seed, epoch)
            )

    # Delta-checkpoint touched-row marking: the per-step "batch" here is a
    # resident index (scalar or [K] chunk), so the marker slices the ids
    # ON DEVICE (through the epoch permutation when shuffled) — handles
    # both the per-step and the scan-fused stream shapes.
    _mark, _mark_shuffled = make_cached_touched_marker(data)

    def mark_touched(bitmap, i):
        if perm_ref[0] is not None:
            return _mark_shuffled(bitmap, perm_ref[0], i)
        return _mark(bitmap, i)

    if cfg.steps_per_call > 1:
        # Scan-fused epochs: the per-call "input" is a pre-placed [K]
        # index vector (remainder-tail vector included), so an epoch is
        # ceil(batches/K) dispatches with zero host involvement between
        # the K resident-slice steps inside each one.
        stepk, stepk_shuffled = make_cached_scan_train_step(
            model, cfg.learning_rate, data, body=body
        )
        chunks = epoch_index_chunks(data.batches, cfg.steps_per_call)

        def train_stream(epoch, skip_batches=0):
            _maybe_draw_perm(epoch)
            # Resume seek: regenerate the chunk list from the cursor's
            # batch (same K-grid, so full chunks re-hit compiled shapes).
            use = (
                chunks
                if not skip_batches
                else epoch_index_chunks(
                    data.batches, cfg.steps_per_call, start=skip_batches
                )
            )
            return ((c, None, None) for c in use)

        def step_fn(state, idxs):
            if perm_ref[0] is not None:
                return stepk_shuffled(state, perm_ref[0], idxs)
            return stepk(state, idxs)

        def _lower_k(st, idxs):
            # Measured-cost hook (profiling.CostLedger): expose the inner
            # jit's .lower so the closure stays profileable.
            if perm_ref[0] is not None:
                return stepk_shuffled.lower(st, perm_ref[0], idxs)  # analysis: ok recompile-hazard this IS the ledger's delegated .lower hook
            return stepk.lower(st, idxs)  # analysis: ok recompile-hazard this IS the ledger's delegated .lower hook

        step_fn.lower = _lower_k
        return (
            step_fn, train_stream, cfg.batch_size, mark_touched,
            make_cached_ids_slicer(data),
        )

    cached_step, cached_step_shuffled = make_cached_train_step(
        model, cfg.learning_rate, data, body=body
    )
    # Batch indices as pre-placed device scalars: the per-step "input" is
    # an index that is already on device — no per-step H2D at all.
    idx = [jax.device_put(np.int32(i)) for i in range(data.batches)]

    def train_stream(epoch, skip_batches=0):
        _maybe_draw_perm(epoch)
        return ((idx[i], None, None) for i in range(skip_batches, data.batches))

    def step_fn(state, i):
        if perm_ref[0] is not None:
            return cached_step_shuffled(state, perm_ref[0], i)
        return cached_step(state, i)

    def _lower(st, i):
        if perm_ref[0] is not None:
            return cached_step_shuffled.lower(st, perm_ref[0], i)  # analysis: ok recompile-hazard this IS the ledger's delegated .lower hook
        return cached_step.lower(st, i)  # analysis: ok recompile-hazard this IS the ledger's delegated .lower hook

    step_fn.lower = _lower
    return (
        step_fn, train_stream, cfg.batch_size, mark_touched,
        make_cached_ids_slicer(data),
    )


def _exchange_profile(cfg, model, mesh, step_fn, state, max_nnz, steps_per_call=1) -> dict:
    """What the sharded step says of its exchange, once: the mesh, the rows a
    shard holds, the lookup, and the payload bytes a chip sends and receives
    in one step's collectives (``parallel.exchange.exchange_bytes`` of the
    step traced at the run's shapes; a fused call's K steps divided out)."""
    from fast_tffm_tpu.parallel.exchange import exchange_bytes
    from fast_tffm_tpu.parallel.mesh import ROW_AXIS
    from fast_tffm_tpu.parallel.train_step import packed_shard_meta

    lead = (steps_per_call,) if steps_per_call > 1 else ()
    b = cfg.batch_size
    spec = lambda dtype, *shape: jax.ShapeDtypeStruct(lead + shape, dtype)
    batch = Batch(
        labels=spec(np.float32, b), ids=spec(np.int32, b, max_nnz),
        vals=spec(np.float32, b, max_nnz),
        fields=spec(np.int32, b, max_nnz if model.uses_fields else 0),
        weights=spec(np.float32, b),
    )
    abstract_state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    if cfg.table_layout == "packed":  # the table's leading dim is physical there
        _, shard_rows, _ = packed_shard_meta(
            model, mesh, fused=cfg.adagrad_accumulator == "fused"
        )
    else:
        shard_rows = state.table.shape[0] // mesh.shape[ROW_AXIS]
    return dict(
        mesh={k: int(v) for k, v in mesh.shape.items()},
        shard_rows=int(shard_rows),
        lookup=cfg.lookup,
        exchange_bytes_per_step=exchange_bytes(step_fn, abstract_state, batch) // max(1, steps_per_call),
    )


def dist_train(cfg: Config, *, resume: bool = False, log=print, mesh=None, step_hook=None):
    """Mesh-distributed training — the reference's `dist_train` mode.

    One SPMD program over all visible chips; no job_name/task_index because
    there is no ps/worker split to schedule — the mesh IS the cluster.

    Multi-host pods additionally shard the INPUT: process p parses only
    rows [p·B/P, (p+1)·B/P) of each global batch (block-cyclic line
    sharding), and the per-process chunks are stitched into global arrays —
    host parse throughput scales with the host count, the way the
    reference spread input files across its workers.  The global non-blank
    line count is taken up front so every process runs the same number of
    collective steps per epoch (short shards pad with weight-0 batches).
    """
    from fast_tffm_tpu.parallel import (
        check_batch_divides,
        init_sharded_state,
        make_global_batch,
        make_mesh,
        make_replicator,
        make_sharded_predict_step,
        make_sharded_train_step,
    )
    from fast_tffm_tpu.distributed import initialize_runtime

    if not cfg.train_files:
        raise ValueError("no train_files configured")
    if cfg.weight_files and len(cfg.weight_files) != len(cfg.train_files):
        # Checked here, not in Config.validate: a shared config must still
        # LOAD on predict-only machines where train-file globs match
        # differently (or not at all).
        raise ValueError(
            f"weight_files has {len(cfg.weight_files)} entries for "
            f"{len(cfg.train_files)} train_files (they align per-file)"
        )
    # Pod bring-up: jax.distributed initialize (config keys, TPU metadata,
    # or the supervisor's generation file), gloo CPU collectives, the
    # coordination runtime (KV + barriers), heartbeats, and — under the
    # pod supervisor — the generation watcher that re-execs this host into
    # the next pod incarnation when a peer is replaced.
    runtime = initialize_runtime(cfg, log=log)
    if cfg.paramstore:
        # The tiered store's residency/writeback protocol is single-host
        # (the pending overlay and the cold store live on ONE host);
        # sharding the hot tier over a mesh is ROADMAP follow-up work.
        raise ValueError(
            "[ParamStore] is local-train only; dist_train shards the "
            "table over the mesh instead (drop [ParamStore] enabled, or "
            "run `train`)"
        )
    if cfg.dedup_gather_rows > 0:
        # The sharded step's gather happens inside the lookup collectives
        # (allgather/alltoall) — the local dedup body does not apply.
        raise ValueError(
            "dedup_gather_rows is local-train only (the sharded lookup "
            "collectives have their own dedup story)"
        )
    if cfg.online_follow:
        # The follow reader is single-process by construction: an
        # append-only stream has no stable row count to shard, and the
        # fixed-steps-per-epoch padding multi-host input relies on cannot
        # exist for a file that grows.  (ROADMAP item 5's per-tenant delta
        # streams are the multi-host follow-up.)
        raise ValueError(
            "[Online] follow = true is single-process (train); dist_train "
            "cannot shard an append-only stream"
        )
    if cfg.online_accum_restart_steps > 0:
        # The reset program's output sharding is not pinned to the mesh
        # layout yet — reject loudly rather than risk a silent reshard.
        raise ValueError(
            "[Online] accum_restart_steps is single-process (train) for "
            "now; use adagrad_decay on pods"
        )
    if cfg.device_cache and cfg.shuffle:
        # A shuffled gather across the mesh-sharded batch dim would move
        # rows between chips every step — exactly the per-step traffic
        # this mode exists to eliminate.  (Local `train` shuffles fine.)
        raise ValueError(
            "device_cache with shuffle is local-train only; dist_train "
            "slices the resident epoch sequentially (drop shuffle, or "
            "pre-shuffle at convert time)"
        )
    model = build_model(cfg)
    max_nnz = scan_max_nnz(cfg)
    if mesh is None:
        row = cfg.row_parallel or cfg.vocabulary_block_num
        data = cfg.data_parallel or None
        mesh = make_mesh(data, row)
    log(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} on {mesh.devices.size} devices")
    check_batch_divides(cfg.batch_size, mesh)
    def restore_state():
        """model_file -> this run's live sharded layout.  Shared by
        --resume and the on_nan=rollback recovery loop below.  Packed
        runs restore the LOGICAL checkpoint into a rows-layout template
        and convert per shard ON DEVICE — no throwaway packed random
        init, no host gather (multi-host packed resume works: each
        process restores and packs only its own shards).  The template
        uses the PACKED padding so a same-mesh packed checkpoint
        restores in place; other paddings go through restore's re-pad
        path (single-host) or its loud multi-host shape error."""
        if cfg.table_layout == "packed":
            from fast_tffm_tpu.parallel import pack_sharded_on_device
            from fast_tffm_tpu.parallel.train_step import packed_shard_meta

            fused_acc = cfg.adagrad_accumulator == "fused"
            padded_model, _, _ = packed_shard_meta(model, mesh, fused=fused_acc)
            logical = restore_checkpoint(
                cfg.model_file,
                init_sharded_state(
                    padded_model, mesh, jax.random.key(0), cfg.init_accumulator_value,
                    cfg.adagrad_accumulator,
                ),
                chunk_bytes=cfg.checkpoint_chunk_mb << 20,
            )
            return pack_sharded_on_device(
                logical, model, mesh, cfg.init_accumulator_value, fused=fused_acc
            )
        return restore_checkpoint(
            cfg.model_file,
            init_sharded_state(
                model, mesh, jax.random.key(0), cfg.init_accumulator_value,
                cfg.adagrad_accumulator, table_layout=cfg.table_layout,
            ),
            chunk_bytes=cfg.checkpoint_chunk_mb << 20,
        )

    if resume and not (
        os.path.isfile(cfg.model_file) or os.path.isdir(cfg.model_file)
    ):
        # A pod relaunch/re-exec forces --resume unconditionally, but a
        # crash DURING the very first publish legitimately leaves no
        # checkpoint at all (only a tmp file) — every host observes the
        # same absence on the shared filesystem and starts fresh; the
        # restore agreement below pins that they all did.
        log(
            f"warning: --resume but no checkpoint at {cfg.model_file} — "
            "starting fresh (crash before the first publish?)"
        )
        resume = False
    start_cursor = None
    if resume:
        state = restore_state()
        log(f"resumed from {cfg.model_file} at step {int(state.step)}")
        # Exact-position resume (every process reads the same shared
        # cursor vector, so all shards reopen at the same global batch).
        start_cursor = read_input_cursor(cfg.model_file)
        if start_cursor is None:
            log(
                "note: checkpoint carries no input cursor (pre-resilience "
                "format) — input restarts at the first file (legacy resume)"
            )
    else:
        state = init_sharded_state(
            model, mesh, jax.random.key(0), cfg.init_accumulator_value,
            cfg.adagrad_accumulator, table_layout=cfg.table_layout,
        )
    ckpt_is_npz = cfg.checkpoint_format == "npz" and not os.path.isdir(cfg.model_file)
    if runtime.active:
        # Restore barrier: no host proceeds into collectives until every
        # host holds the SAME restored step, chain head, and cursor — a
        # desynced pod must die here, loudly, not train garbage.
        head = None
        if ckpt_is_npz and resume:
            from fast_tffm_tpu.checkpoint import read_delta_chain

            try:
                base_sig, chain = read_delta_chain(cfg.model_file)
                head = chain[-1]["save_id"] if chain else base_sig
            except (ValueError, OSError):
                head = None
        runtime.agree(
            "restore",
            {
                "step": int(state.step),
                "head": head,
                "cursor": [
                    (start_cursor or {}).get("epoch"),
                    (start_cursor or {}).get("batch_in_epoch"),
                ],
            },
        )
    # With device_cache the scan lives in the cached wrapper below
    # (it slices resident batches); the raw SPMD step stays per-batch.
    step_k = 1 if cfg.device_cache else cfg.steps_per_call
    step_fn = make_sharded_train_step(
        model, cfg.learning_rate, mesh,
        lookup=cfg.lookup, capacity_factor=cfg.lookup_capacity_factor,
        overflow_mode=cfg.lookup_overflow, table_layout=cfg.table_layout,
        packed_update=cfg.packed_update,
        accumulator=cfg.adagrad_accumulator,
        compact_cap=cfg.packed_compact_cap,
        steps_per_call=step_k,
        adagrad_decay=cfg.online_adagrad_decay,
        count_full_tails=cfg.table_layout == "rows",
        count_full_lookups=cfg.table_layout == "rows",
    )
    predict_step = make_sharded_predict_step(
        model, mesh, lookup=cfg.lookup, capacity_factor=cfg.lookup_capacity_factor,
        overflow_mode=cfg.lookup_overflow, table_layout=cfg.table_layout,
        accumulator=cfg.adagrad_accumulator,
    )
    exchange_profile = _exchange_profile(
        cfg, model, mesh, step_fn, state, max_nnz, steps_per_call=step_k
    )
    log(
        "exchange: {lookup} lookup, {shard_rows} rows a shard, "
        "{exchange_bytes_per_step} bytes a chip sends and receives a step".format(
            **exchange_profile
        )
    )
    tail_profile = {}  # what the rows layout's shard tail says of itself, once
    if cfg.table_layout == "rows":
        # The form is optim.sparse_adagrad_update's choice at trace time, from
        # the SHARD's shapes (embedding.apply_shard_adagrad); asked here only
        # to say it once, as train does.
        from fast_tffm_tpu.optim import (
            describe_rows_tail,
            rows_tail_form,
            rows_tail_profile,
        )
        from fast_tffm_tpu.parallel.embedding import shard_lookup_form
        from fast_tffm_tpu.parallel.mesh import ROW_AXIS
        from fast_tffm_tpu.parallel.train_step import shard_lookup_ids, shard_tail_ids

        shard_rows = exchange_profile["shard_rows"]
        ids_per_chip = cfg.batch_size // mesh.size * max_nnz
        # The slots the tail takes (its bound under both lookups) of those
        # the update's exchange hands every shard.
        m_ids = shard_tail_ids(mesh, ids_per_chip, cfg.lookup_capacity_factor)
        handed = m_ids if cfg.lookup == "alltoall" else mesh.size * ids_per_chip
        tail_form = rows_tail_form(
            shard_rows, m_ids, model.row_dim, state.table_opt.accum.shape[-1]
        )
        tail_profile = dict(
            rows_tail_profile(shard_rows, m_ids, model.row_dim, tail_form, handed),
            tail_slots=m_ids,
        )
        log(
            "sparse tail: "
            + describe_rows_tail(shard_rows, m_ids, model.row_dim, tail_form, handed)
            + f"; the shard's first {m_ids} of {handed} exchanged slots"
        )
        if cfg.lookup == "allgather":
            # So is the lookup's (embedding.sharded_gather at the shard's
            # shapes), and the slots its local gather reads a step.
            slots = mesh.shape[ROW_AXIS] * ids_per_chip
            bound = shard_lookup_ids(mesh, ids_per_chip, cfg.lookup_capacity_factor)
            lookup_form = "rows"
            if mesh.shape[ROW_AXIS] > 1:
                lookup_form = shard_lookup_form(shard_rows, slots, model.row_dim, bound)
            tail_profile.update(
                lookup_form=lookup_form, lookup_slots=bound if lookup_form == "sweep" else slots
            )
            log("shard lookup: " + (
                f"pallas sweep of the shard's table.T over the first {bound} of {slots} "
                f"sorted slots, rows back tile-wide (row width {model.row_dim}); the "
                "whole list where a shard owns more"
                if lookup_form == "sweep" else
                f"xla masked row gather ({slots} slots of {model.row_dim} a step)"
            ))
    dist_saveable = None
    if cfg.table_layout == "packed":
        # Checkpoints hold LOGICAL [V, D] arrays.  Multi-process: unpack
        # per shard ON DEVICE — the result is a row-sharded logical state
        # orbax writes per host in parallel (no host gather of
        # non-addressable shards).  Single-process: unpack through HOST
        # RAM instead — the on-device unpack would materialize a full
        # logical copy of table+accumulator NEXT TO the live packed state
        # at every save, a ~2× transient HBM peak that OOMs exactly the
        # big-table runs (ADVICE r4).
        from fast_tffm_tpu.parallel import (
            unpack_sharded_on_device,
            unpack_sharded_to_logical,
        )

        if jax.process_count() > 1:
            def dist_saveable(st):
                return unpack_sharded_on_device(st, model, mesh)
        else:
            def dist_saveable(st):
                return unpack_sharded_to_logical(st, model, mesh)

    if jax.process_count() > 1 and ckpt_is_npz:
        # Multi-host npz single-writer protocol: the saveable additionally
        # REPLICATES the logical state (one collective every host
        # dispatches) so process 0 holds complete arrays to stream to
        # disk.  Full-table-per-host memory — the modest-table path; use
        # orbax beyond that (DESIGN §8).
        replicate = make_replicator(mesh)
        inner_saveable = dist_saveable

        if inner_saveable is not None:
            def dist_saveable(st, _inner=inner_saveable):
                return replicate(_inner(st))
        else:
            dist_saveable = replicate

    cached_data = None
    if cfg.device_cache:
        # Mesh-sharded resident dataset: same zero-per-step-H2D contract
        # as the local path, with each batch's rows sharded over every
        # chip and the slice fused into the SPMD step.  Wraps the RAW
        # jitted step (the slice traces inside jit); the overflow
        # accumulator below then wraps at the Python level as usual.
        from fast_tffm_tpu.data.device_cache import (
            load_sharded_device_dataset,
            make_cached_sharded_train_step,
        )

        files = tuple(cfg.train_files)
        if cfg.binary_cache:
            from fast_tffm_tpu.data.binary import ensure_fmb_cache

            files = ensure_fmb_cache(
                files,
                vocabulary_size=cfg.vocabulary_size,
                hash_feature_id=cfg.hash_feature_id,
                max_nnz=max_nnz,
                parser=best_parser(cfg.thread_num),
            )
        if not binary_input(files):
            raise ValueError(
                "device_cache = true needs FMB-backed input: list .fmb "
                "files in train_files, or set binary_cache = true"
            )
        cached_data = load_sharded_device_dataset(
            files,
            mesh=mesh,
            batch_size=cfg.batch_size,
            vocabulary_size=cfg.vocabulary_size,
            hash_feature_id=cfg.hash_feature_id,
            max_nnz=max_nnz,
            weights=cfg.weight_files if cfg.weight_files else None,
            with_fields=model.uses_fields,
        )
        log(
            f"device cache: {cached_data.n_rows} rows resident, sharded "
            f"over {mesh.devices.size} devices "
            f"({cached_data.nbytes / 2**20:.1f} MiB total, "
            f"{cached_data.batches} batches/epoch)"
        )
        step_fn = make_cached_sharded_train_step(
            step_fn, cached_data, steps_per_call=cfg.steps_per_call
        )

    mark_touched = None
    if cached_data is not None and cfg.delta_every_steps > 0:
        # Delta checkpoints on the resident path mark touched rows from
        # the sharded id arrays on device (dist_train disallows shuffle,
        # so the plain sequential-slice marker is the only one needed).
        from fast_tffm_tpu.data.device_cache import make_cached_touched_marker

        mark_touched, _ = make_cached_touched_marker(cached_data)

    # The counters the step returns after its loss, in its order: the routed
    # lookup's fallback steps, and (rows layout) the row shards whose tail
    # took the whole exchanged list and not its bounded prefix, and the
    # chips whose lookup read the whole list its row peers handed it.
    counted = ["lookup_overflow_steps"] * (
        cfg.lookup == "alltoall" and cfg.lookup_overflow == "fallback"
    ) + ["shard_tail_full_steps", "shard_lookup_full_steps"] * (cfg.table_layout == "rows")
    extra_metrics = None
    if counted:
        # Fold each into ONE running device scalar (no host sync, no per-step
        # buffer — a pending list would pin a live device scalar per step
        # between log points) and fetch/reset them only at log points.
        raw_step = step_fn
        running = [None]

        def step_fn(state, b):
            state, loss, *counts = raw_step(state, b)
            running[0] = (
                counts if running[0] is None
                else [a + c for a, c in zip(running[0], counts)]
            )
            return state, loss

        if hasattr(raw_step, "lower"):
            # Keep the wrapped step profileable (measured cost ledger).
            step_fn.lower = raw_step.lower

        def extra_metrics():
            sums, running[0] = running[0] or [0] * len(counted), None
            return {name: int(n) for name, n in zip(counted, sums)}

    train_stream = examples_per_step = evaluate = None
    to_batch = _batch_converter(model.uses_fields)
    if cached_data is not None:
        if cfg.steps_per_call > 1:
            # Per-call "input" is a pre-placed [K] index vector (tail
            # remainder included) — epoch_index_chunks as on the local
            # cached path.
            from fast_tffm_tpu.data.device_cache import epoch_index_chunks

            chunks = epoch_index_chunks(cached_data.batches, cfg.steps_per_call)

            def train_stream(epoch, skip_batches=0):
                use = (
                    chunks
                    if not skip_batches
                    else epoch_index_chunks(
                        cached_data.batches, cfg.steps_per_call,
                        start=skip_batches,
                    )
                )
                return ((c, None, None) for c in use)

        else:
            # Per-step "input" is a pre-placed device index scalar.
            idx = [jax.device_put(np.int32(i)) for i in range(cached_data.batches)]

            def train_stream(epoch, skip_batches=0):
                return (
                    (idx[i], None, None)
                    for i in range(skip_batches, cached_data.batches)
                )

        examples_per_step = cfg.batch_size
    nproc = jax.process_count()
    if nproc > 1:
        from fast_tffm_tpu.data.native import count_lines

        if cfg.batch_size % nproc:
            raise ValueError(
                f"batch_size {cfg.batch_size} not divisible by "
                f"{nproc} processes (it is the GLOBAL batch)"
            )
        local_bs = cfg.batch_size // nproc
        pid = jax.process_index()

        if cached_data is None:
            # (device_cache keeps its resident index stream — each
            # process already staged only its rows at load time; only
            # the STREAMED path shards the text/FMB stream per step, and
            # only it needs the up-front row counts for the fixed
            # steps-per-epoch padding.)
            if cfg.input_assignment == "files":
                # Shard-disjoint FILE assignment: host p streams files
                # [p::P] whole — each host opens and reads only its own
                # files (no cross-file seeking through the peers' data),
                # the pod-scale input shape.  Global batch k is the
                # stitch of every host's k-th local batch; short hosts
                # pad the epoch tail with weight-0 batches so every host
                # runs the same number of collective steps.
                files_all = tuple(cfg.train_files)
                if len(files_all) < nproc:
                    raise ValueError(
                        f"input_assignment = files needs at least one train "
                        f"file per process ({len(files_all)} files, {nproc} "
                        "processes) — split the dataset or use "
                        "input_assignment = rows"
                    )
                my_files = files_all[pid::nproc]
                # Per-file example weights align with the FULL train file
                # list; this host's stream sees only its own files, so the
                # weights slice with the same stride.
                my_weights = (
                    tuple(cfg.weight_files)[pid::nproc]
                    if cfg.weight_files
                    else None
                )
                # Each host counts only ITS files (the mode's whole point
                # is not touching the peers' data) and the per-host row
                # counts meet through the pod KV store; without a
                # coordination backend, fall back to counting everything.
                if runtime.active:
                    per_host_rows = [
                        int(r)
                        for r in runtime.allgather(
                            "files-rows", count_lines(my_files)
                        )
                    ]
                else:
                    per_host_rows = [
                        count_lines(files_all[p::nproc]) for p in range(nproc)
                    ]
                steps_per_epoch = max(-(-r // local_bs) for r in per_host_rows)
                log(
                    "input sharding: shard-disjoint files — host "
                    f"{pid} owns {len(my_files)} file(s) / "
                    f"{per_host_rows[pid]} rows, {steps_per_epoch} "
                    f"steps/epoch, {local_bs} rows/process/step"
                )

                def train_stream(epoch, skip_batches=0):
                    return _stream(
                        cfg,
                        my_files,
                        max_nnz,
                        epochs=1,
                        batch_size=local_bs,
                        weights=my_weights,
                        pad_to_batches=steps_per_epoch,
                        to_batch=to_batch,
                        shuffle_epoch=epoch,
                        steps_per_call=cfg.steps_per_call,
                        skip_batches=skip_batches,
                    )

            else:
                total = count_lines(cfg.train_files)
                steps_per_epoch = -(-total // cfg.batch_size)  # ceil
                log(
                    f"input sharding: {total} rows over {nproc} processes, "
                    f"{steps_per_epoch} steps/epoch, {local_bs} rows/process/step"
                )

                def train_stream(epoch, skip_batches=0):
                    return _stream(
                        cfg,
                        cfg.train_files,
                        max_nnz,
                        epochs=1,
                        batch_size=local_bs,
                        shard_index=pid,
                        shard_count=nproc,
                        shard_block=local_bs,
                        pad_to_batches=steps_per_epoch,
                        to_batch=to_batch,
                        shuffle_epoch=epoch,
                        steps_per_call=cfg.steps_per_call,
                        skip_batches=skip_batches,
                    )

        def to_batch(parsed, w):
            if isinstance(parsed, list):  # K local chunks -> [K, B, ...] global
                from fast_tffm_tpu.parallel import make_global_superbatch

                return make_global_superbatch(
                    mesh, parsed, w, with_fields=model.uses_fields
                )
            return make_global_batch(mesh, parsed, w, with_fields=model.uses_fields)

        to_batch.uses_fields = model.uses_fields
        # Host-local packed-wire staging (PR 3's wire, already per-host by
        # construction): when the stream is FMB-backed and wire_format =
        # packed, _stream swaps this stitch for a WireGlobalConverter —
        # each host ships ONE coalesced buffer to its own devices and the
        # per-device shards assemble straight into the global batch.
        to_batch.wire_capable = True

        def _make_wire(spec):
            from fast_tffm_tpu.parallel import WireGlobalConverter

            return WireGlobalConverter(mesh, spec)

        to_batch.make_wire_converter = _make_wire

        examples_per_step = cfg.batch_size

        # Validation is sharded the same way.  Scores come back replicated
        # from the sharded predict step; the (tiny, [B]) label/weight
        # vectors are resharded to replicated on device so every process
        # can compute the GLOBAL AUC (weight-0 padding rows drop out).
        from jax.sharding import NamedSharding, PartitionSpec

        replicate = jax.jit(
            lambda x: x, out_shardings=NamedSharding(mesh, PartitionSpec())
        )
        val_steps = (
            -(-count_lines(cfg.validation_files) // cfg.batch_size)
            if cfg.validation_files
            else 0
        )

        def evaluate(cfg, predict_step, state, files, max_nnz):
            return _evaluate(
                cfg,
                predict_step,
                state,
                files,
                max_nnz,
                stream=_stream(
                    cfg,
                    files,
                    max_nnz,
                    epochs=1,
                    weights=None,
                    batch_size=local_bs,
                    shard_index=pid,
                    shard_count=nproc,
                    shard_block=local_bs,
                    pad_to_batches=val_steps,
                    to_batch=to_batch,
                ),
                to_batch=to_batch,
                fetch=lambda b, parsed, w: (
                    np.asarray(replicate(b.labels)),
                    np.asarray(replicate(b.weights)),
                ),
            )

    run_kwargs = dict(
        train_stream=train_stream,
        to_batch=to_batch,
        examples_per_step=examples_per_step,
        evaluate=evaluate,
        extra_metrics=extra_metrics,
        saveable=dist_saveable,
        step_hook=step_hook,
        row_dim=model.row_dim,
        tail_profile=tail_profile,
        exchange_profile=exchange_profile,
        interaction_profile=_say_interaction(log, model, cfg.batch_size // mesh.size),
        mark_touched=mark_touched,
        runtime=runtime,
        mesh=mesh,
    )
    # on_nan = rollback, now legal under dist_train: the loss every host
    # checks is REPLICATED (identical), so every host raises
    # NonFiniteLossError at the same step with the same cursor; the
    # rollback barrier below makes the agreement explicit before any host
    # touches the checkpoint, then all processes restore the same chain
    # head and resume input at the same cursor vector.
    rollbacks = 0
    rollback_note = None
    while True:
        try:
            return _run_training(
                cfg, state, step_fn, predict_step, max_nnz, log,
                start_cursor=start_cursor, rollback=rollback_note,
                **run_kwargs,
            )
        except NonFiniteLossError as e:
            from fast_tffm_tpu.checkpoint import latest_step

            if (
                cfg.on_nan != "rollback"
                or rollbacks >= cfg.max_rollbacks
                or e.cursor is None
                or latest_step(cfg.model_file) is None
            ):
                raise
            rollbacks += 1
            # The cross-process rollback barrier: rendezvous BEFORE the
            # restore so no host can re-enter collectives against peers
            # still unwinding the failed attempt.
            runtime.barrier(f"rollback-{rollbacks}")
            state = restore_state()
            head = None
            if ckpt_is_npz:
                from fast_tffm_tpu.checkpoint import read_delta_chain

                try:
                    base_sig, chain = read_delta_chain(cfg.model_file)
                    head = chain[-1]["save_id"] if chain else base_sig
                except (ValueError, OSError):
                    head = None
            runtime.agree(
                f"rollback-head-{rollbacks}",
                {
                    "step": int(state.step),
                    "head": head,
                    "cursor": [
                        e.cursor.get("epoch"),
                        e.cursor.get("batch_in_epoch"),
                    ],
                },
            )
            # Fresh KV namespace: the next attempt's checkpoint boundary
            # ordinals must not collide with the aborted attempt's keys.
            runtime.advance_namespace()
            start_cursor = dict(e.cursor, _exact=True)
            rollback_note = {
                "step": e.step,
                "loss": e.loss,
                "rollback_n": rollbacks,
                "restored_step": int(state.step),
                "skip_to_epoch": int(e.cursor.get("epoch", 0)),
                "skip_to_batch": int(e.cursor.get("batch_in_epoch", 0)),
            }
            log(
                f"on_nan = rollback: non-finite loss at step {e.step}; "
                f"restored {cfg.model_file} (step {int(state.step)}), "
                f"skipping input to epoch {rollback_note['skip_to_epoch']} "
                f"batch {rollback_note['skip_to_batch']} "
                f"(rollback {rollbacks}/{cfg.max_rollbacks})"
            )
