"""INI config schema honoring the reference's key vocabulary.

Capability parity with `renyi533/fast_tffm` :: sample.cfg + the
ConfigParser reads inside its train/predict modules: General (factor_num,
vocabulary_size, vocabulary_block_num, hash_feature_id, model_file), Train
(files, epoch_num, batch_size, learning_rate, init_value_range,
factor_lambda, bias_lambda, ...), Predict (input + score path).  New,
TPU-specific keys are additive: [General] model/order/num_fields for the
model zoo, [Distributed] data_parallel/row_parallel for the mesh (the
reference's ps_hosts/worker_hosts cluster section has no meaning under
single-program SPMD — vocabulary_block_num maps to row_parallel).
"""

from __future__ import annotations

import configparser
import dataclasses


@dataclasses.dataclass
class Config:
    # [General]
    model: str = "fm"  # fm | ffm | deepfm
    factor_num: int = 8
    order: int = 2
    num_fields: int = 0  # required for ffm/deepfm
    hidden_dims: tuple[int, ...] = (400, 400, 400)  # deepfm MLP head
    compute_dtype: str = "float32"  # MXU input precision: deepfm MLP matmuls
    #   and ffm interaction einsums (float32 | bfloat16; accumulation stays f32)
    vocabulary_size: int = 1 << 20
    vocabulary_block_num: int = 1  # reference key; default row_parallel
    hash_feature_id: bool = False
    table_layout: str = "rows"  # rows ([V,D]) | packed (lane-packed [V/P,128]
    #   tile rows — fixes the partial-lane scatter cliff, DESIGN §6; composes
    #   with both accumulator granularities and both lookup collectives;
    #   dist shards it, incl. multi-host)
    model_file: str = "model.ckpt"
    checkpoint_format: str = "npz"  # npz | orbax (orbax = sharded, pod-scale)
    # [Checkpoint] — async/incremental saves (checkpoint_async.py; npz only)
    async_save: bool = False  # take full saves off the train loop: on-device
    #   snapshot at the boundary, a writer thread does convert/D2H/write;
    #   at most one in flight (next boundary blocks if the writer lags);
    #   SIGTERM/final saves stay synchronous (last-good-state unchanged)
    delta_every_steps: int = 0  # >0: between full saves, write a delta-NNNN
    #   file every N steps carrying ONLY the rows the window touched (the
    #   on-device touched-row bitmap) + dense leaves, content-signature
    #   chained to the base; restore replays base+chain; 0 = off
    delta_chain_max: int = 16  # deltas per chain before the next boundary
    #   promotes itself to a full save (bounds restore replay length)
    delta_full_every_s: float = 0.0  # [Checkpoint] full_every_s: AGE-based
    #   chain compaction — a delta boundary promotes itself to a full save
    #   once this many seconds passed since the last full publish, so an
    #   hours-long online run compacts (full saves unlink old deltas) even
    #   when the chain count stays under delta_chain_max (0 = off)
    delta_chain_max_bytes: int = 0  # [Checkpoint] chain_max_bytes: SIZE-based
    #   chain compaction — promote to full once the current chain's delta
    #   files total this many bytes (0 = off); together with full_every_s
    #   this bounds the delta chain's disk footprint for unbounded runs
    checkpoint_chunk_mb: int = 64  # save/restore host-staging bound: arrays
    #   stream D2H/disk in this many MB per slice (never 2x table on host)
    # [Train]
    train_files: tuple[str, ...] = ()
    weight_files: tuple[float, ...] = ()  # per-file example weights
    validation_files: tuple[str, ...] = ()
    epoch_num: int = 1
    batch_size: int = 1024
    max_nnz: int = 0  # 0 = infer from first batch file scan
    learning_rate: float = 0.01
    init_value_range: float = 0.01
    factor_lambda: float = 0.0
    bias_lambda: float = 0.0
    init_accumulator_value: float = 0.1
    adagrad_accumulator: str = "element"  # element (TF parity) | row (D×-smaller
    #   state) | fused (row semantics, accumulator stored inside the packed
    #   table's tile rows — 2-random-op RMW; requires table_layout=packed)
    packed_compact_cap: int = 0  # fused compact tail: cap the compacted-row
    #   buffer (0 = exact min(VP, M)); overflowing batches take an exact
    #   lax.cond fallback, so skewed (Zipf/CTR) ids get a ~3x smaller RMW
    #   with no correctness risk (ops/packed_table.py round-5 entry)
    packed_update: str = "auto"  # packed sparse tail: auto | dense | compact | sorted
    #   (dense = wide scatter-add into a [VP,128] grad buffer + dense Adagrad
    #   sweep, measured 3.5× the sorted pipeline; compact = sort-free
    #   touched-row compaction, O(M) buffers — the giant-vocab path; sorted =
    #   the bit-parity reference pipeline; auto picks dense/compact by size)
    thread_num: int = 0  # host-side parse workers; 0 = all cores (reference: queue threads)
    binary_cache: bool = False  # parse text once into <file>.fmb, stream that
    binary_cache_wait: float = 600.0  # multi-host: non-lead wait for lead's build (s)
    shuffle: bool = False  # per-epoch global shuffle of train rows (FMB input only)
    shuffle_seed: int = 0
    device_cache: bool = False  # load the (FMB) train set to device HBM once,
    #   slice batches on-chip — zero per-step host→device bytes; dist_train
    #   shards the resident arrays over the mesh, per-process assembly
    #   multi-host (no shuffle on dist)
    steps_per_call: int = 1  # fuse K train steps into ONE jitted dispatch
    #   (lax.scan over K micro-batches).  1 = one dispatch per batch (the
    #   classic loop); K>1 amortizes per-step dispatch/H2D overhead on every
    #   path: streamed input ships [K, B, ...] superbatches (one transfer
    #   per K steps), device_cache scans K resident batch slices with zero
    #   host involvement in between, dist_train scans around the SPMD body.
    #   Per-step losses keep full granularity; stop/checkpoint boundaries
    #   become K-step-aligned (DESIGN.md "Step fusion").
    dedup_gather_rows: int = 0  # device-side dedup-before-gather on the
    #   streamed path (ROADMAP item 2(a)): >0 caps the per-batch unique-id
    #   set at N — the forward gather reads at most N table rows (one HBM
    #   read per unique row; per-slot re-reads hit the compact buffer),
    #   cashing in the measured 0.291 dedup ratio.  Values are identical
    #   to the direct gather, so losses stay BIT-IDENTICAL (test-pinned).
    #   The stream VERIFIES each batch fits N before it ships (loud error,
    #   never silent truncation).  0 = off; rows layout, streamed local
    #   train only
    wire_format: str = "packed"  # streamed H2D staging: packed (ONE coalesced
    #   byte buffer per superbatch, with device-side reconstruction of
    #   elidable tensors — all-ones vals, unused fields, uniform weights,
    #   narrow ids; bit-identical batches, ~2-3x fewer wire bytes on CTR
    #   libsvm) | arrays (classic one-device_put-per-tensor staging).
    #   Engages on FMB-backed streams; text input always ships arrays.
    queue_size: int = 8  # prefetch depth
    log_every: int = 100
    save_every_epochs: int = 1
    trace_dir: str = ""  # jax.profiler trace output (TensorBoard/XProf)
    trace_steps: int = 20  # bounded trace window length (after warmup)
    metrics_path: str = ""  # JSONL telemetry sink (enveloped records; see
    #   telemetry.py SCHEMAS and tools/report.py)
    # [Telemetry] — the RunMonitor knobs (records go to metrics_path)
    telemetry_run_id: str = ""  # envelope run id; empty = auto-generated
    telemetry_mem_every_s: float = 30.0  # kind=mem watermark cadence
    #   (0 = only the guaranteed final record at close)
    telemetry_stall_timeout_s: float = 0.0  # liveness watchdog: dump thread
    #   stacks + prefetch depth as kind=stall when no step completes for
    #   this many seconds (0 = watchdog off)
    telemetry_compilation_cache_dir: str = ""  # persistent XLA compilation
    #   cache directory.  The cache is always on (CLI runs and replica
    #   workers share it; the compile sentinel marks cache hits
    #   distinctly).  Precedence: JAX_COMPILATION_CACHE_DIR in the
    #   environment, else this key, else "" = <checkout>/.jax_cache
    telemetry_profile_steps: str = ""  # "A:B" captures a jax.profiler trace
    #   over steps [A, B) (rounded to dispatch boundaries under step
    #   fusion) into <model_file>.profile (trace_dir overrides); start/
    #   stop land as kind=profile event records ("" = no trace)
    telemetry_profile_costs: bool = True  # per-compiled-program MEASURED
    #   cost ledger (XLA cost analysis: bytes accessed, FLOPs) emitted as
    #   ONE kind=profile record per program on train/predict/serving —
    #   one re-lowering each, no second backend compile, no hot-path work
    telemetry_datastats_every_steps: int = 0  # sample device-side id-traffic
    #   statistics (unique/dedup ratio, heavy-hitter sketch, rows-seen)
    #   every N steps as kind=datastats records (0 = off; the sampled
    #   batch pays one O(M log M) device sort per window)
    telemetry_heavy_hitter_k: int = 16  # top-K buckets of the datastats
    #   heavy-hitter sketch reported per record (sizes ROADMAP item 3's
    #   hot-id cache; bucket collisions overstate mass — an upper bound)
    # [Predict]
    predict_files: tuple[str, ...] = ()
    score_path: str = "scores.txt"
    # [Serving] — the online engine (serving/; `serve` CLI verb)
    serve_buckets: tuple[int, ...] = (1, 8, 64, 512)  # compile-ladder batch
    #   sizes; every flush pads to the nearest rung so steady state never
    #   recompiles (warmed once at startup)
    serve_max_batch: int = 0  # collector flush size; 0 = largest bucket
    serve_flush_deadline_ms: float = 5.0  # max micro-batching wait for the
    #   oldest pending request (latency/occupancy knob; 0 = flush instantly)
    serve_queue_size: int = 4096  # bounded admission queue — the ONLY
    #   elastic buffer, so overload memory is capped here
    serve_overload: str = "block"  # queue-full policy: block (backpressure)
    #   | reject (raise OverloadError to the submitter — shed load)
    serve_reload_interval_s: float = 0.0  # hot checkpoint reload poll; the
    #   watcher restores changed model_file checkpoints off the hot path
    #   and the collector swaps them in between flushes (0 = no watcher)
    serve_metrics_every_s: float = 10.0  # serving-metrics JSONL cadence
    #   (written to metrics_path, tagged kind=serving; 0 = final record only)
    serve_reload_max_retries: int = 8  # consecutive reload failures on ONE
    #   checkpoint signature before the watcher gives up on it (counted as
    #   reload_giveups + a kind=anomaly record; retries back off
    #   exponentially from reload_interval_s; a NEW write resets)
    serve_port: int = 0  # socket front end (serving/frontend.py): TCP port
    #   the `serve` verb listens on; 0 = stdin/stdout mode (the historical
    #   pipe path) unless the CLI passes --port (0 there = ephemeral,
    #   introspected and printed — what tests use)
    serve_replicas: int = 1  # engine replica WORKER PROCESSES behind the
    #   router (shared-nothing: per-replica jit caches and admission
    #   queues); 1 still runs the full router path when the front end is up
    serve_deadline_ms: float = 0.0  # default per-request deadline budget
    #   (submit -> scored); an expired request is shed BEFORE padding a
    #   bucket (typed `deadline`, counted as deadline_drops).  0 = none;
    #   a request's own deadline_ms field overrides
    serve_classes: tuple[tuple[str, int], ...] = ()  # tiered admission:
    #   client class -> tier ("gold:2,std:1"); under overload the queue
    #   sheds strictly-lower tiers first (oldest of the lowest present),
    #   so degradation follows priority.  Unknown/absent class = tier 0
    serve_wire: str = "binary"  # DATA-plane wire a client may negotiate
    #   via {"op":"hello"}: "binary" allows the batched frame protocol
    #   (protocol.py DATA frames; JSONL stays the fallback), "jsonl"
    #   refuses the upgrade so every data connection stays line-oriented
    serve_affinity: bool = True  # hello hands the client a healthy
    #   replica's port to pin its DATA connection to (replica answers
    #   directly; router keeps health/reload/placement/failover only).
    #   False: hello returns no placement and data stays on the front end
    # [Online] — online learning from an append-only event stream
    online_follow: bool = False  # tail-follow the FMS train stream: at EOF
    #   the reader polls for growth instead of ending the epoch
    #   (data/stream.py; train only, one FMS train file, epoch_num = 1)
    online_poll_s: float = 0.2  # bounded EOF poll interval (seconds)
    online_idle_timeout_s: float = 0.0  # >0: end the stream after this much
    #   continuous writer silence (bounded tools/tests); 0 = follow until
    #   the process is stopped (SIGTERM checkpoints + exits as usual)
    online_max_batches: int = 0  # >0: end the stream once the TOTAL emitted
    #   batch index reaches N (resume-skipped batches count — the
    #   pad_to_batches convention, so --resume composes); 0 = unbounded
    online_adagrad_decay: float = 1.0  # γ: touched-row accumulator decay
    #   (accum = γ·accum + g²) so old gradient history can't freeze the
    #   step size on a moving distribution; 1.0 = classic Adagrad,
    #   bit-identical program; γ < 1 requires table_layout = rows
    online_accum_restart_steps: int = 0  # window-restart alternative to
    #   decay: every N steps (K-aligned) reset EVERY accumulator to
    #   init_accumulator_value; 0 = off; exclusive with adagrad_decay < 1
    # [ParamStore] — tiered host/device parameter store (paramstore/):
    # beyond-HBM tables — a device-resident hot tier (top-K rows) + the
    # full logical table in a memmap-backed host cold store; the prefetch
    # thread resolves each superbatch's ids ahead of dispatch and miss
    # rows ride the packed wire alongside the batch
    paramstore: bool = False  # enable the tiered store (local train only;
    #   table_layout = rows, npz checkpoints)
    paramstore_hot_rows: int = 4096  # device-resident hot rows (the PR-9
    #   coverage curve: top-4096 absorb 59% of gathers at the Zipf(1.1)
    #   scale shape)
    paramstore_miss_rows: int = 0  # staging capacity for one superbatch's
    #   unique non-resident rows; 0 = auto (batch_size * max_nnz *
    #   steps_per_call — the can't-overflow bound); a tighter cap shrinks
    #   device memory and fails LOUDLY if a batch exceeds it
    paramstore_dir: str = ""  # cold-store directory; "" = <model_file>.store
    paramstore_residency: str = "sample"  # hot-set policy: sample (exact
    #   frequency count over the first sample_batches of the train stream,
    #   top-K — the heavy-hitter telemetry's exact twin) | first (ids
    #   [0, K)) | file:PATH (id list exported from telemetry)
    paramstore_sample_batches: int = 8  # batches the sample policy counts
    paramstore_materialize: str = "auto"  # cold-store init: auto
    #   (materialize the exact jax init draw at small vocab — the
    #   bit-identity-with-resident path — lazy hashed init beyond) |
    #   always | never
    # [Resilience] — crash recovery + fault handling (resilience.py)
    on_nan: str = "abort"  # non-finite loss policy: abort (raise before the
    #   next save overwrites good state — the historical behavior) |
    #   rollback (restore the last checkpoint, SKIP the diverged window's
    #   input via the saved cursor, continue; local train only)
    max_rollbacks: int = 2  # rollback budget per run; exhausted -> abort
    io_retries: int = 3  # FMB reader: transient-OSError retries per read op
    io_retry_backoff_s: float = 0.05  # first retry backoff (doubles per try)
    restart_max: int = 5  # supervisor (train --supervised): bounded restarts
    restart_backoff_s: float = 1.0  # supervisor backoff base (doubles)
    restart_backoff_max_s: float = 30.0  # supervisor backoff cap
    # [Distributed]
    data_parallel: int = 0  # 0 = all devices / row_parallel
    row_parallel: int = 0  # 0 = vocabulary_block_num
    lookup: str = "allgather"  # embedding lookup collective (| alltoall)
    # How far over its uniform share (a chip's ids / row_parallel) a row
    # shard's traffic may run before the step takes the slower exact path,
    # under BOTH exchanges: the alltoall's per-destination slots (past them
    # lookup_overflow decides), and the slots a shard's Adagrad tail keeps of
    # the allgather update's list (past them it takes the whole list, counted
    # as shard_tail_full_steps; nothing is dropped).
    lookup_capacity_factor: float = 2.0
    lookup_overflow: str = "fallback"  # fallback (retry step via allgather) | abort
    coordinator_address: str = ""  # multi-host: host:port of process 0
    num_processes: int = 0  # multi-host: total process count
    process_id: int = -1  # multi-host: this process's index
    input_assignment: str = "rows"  # multi-host streamed input split: rows
    #   (block-cyclic line sharding of every file — the historical mode) |
    #   files (shard-disjoint file assignment: host p streams files
    #   [p::P] whole, so each host touches only its own files; short
    #   hosts pad the epoch tail with weight-0 batches)
    runtime_dir: str = ""  # shared coordination dir for the pod runtime
    #   (heartbeats, generation file, file-KV fallback); "" = off for
    #   plain runs, defaults to <model_file>.dist under the pod
    #   supervisor (dist_train --supervised with num_processes > 1)
    heartbeat_s: float = 2.0  # per-host heartbeat cadence into runtime_dir
    host_stall_timeout_s: float = 0.0  # peer-heartbeat staleness that
    #   classifies a host-level kind=stall (host-heartbeat-lost); the pod
    #   supervisor also uses it for straggler kills (0 = monitor off)
    barrier_timeout_s: float = 120.0  # cross-process barrier / signature
    #   / cursor-gather wait budget; a timeout means a peer is gone
    #   (PeerLostError -> exit PEER_LOST_EXIT under the supervisor)

    def validate(self) -> "Config":
        if self.model not in ("fm", "ffm", "deepfm"):
            raise ValueError(f"unknown model {self.model!r}")
        if self.model in ("ffm", "deepfm") and self.num_fields <= 0:
            raise ValueError(f"{self.model} requires num_fields > 0")
        if self.model == "fm" and self.order < 2:
            raise ValueError("order must be >= 2")
        if self.vocabulary_size <= 0 or self.batch_size <= 0:
            raise ValueError("vocabulary_size and batch_size must be positive")
        if self.vocabulary_size > 2**31 - 1:
            # Device feature ids are int32 (TPU gathers index with int32);
            # a larger vocabulary would silently wrap when batches narrow
            # to the device dtype.  Hash mode folds any id space into range.
            raise ValueError(
                f"vocabulary_size {self.vocabulary_size} exceeds int32 "
                "(2**31 - 1), the device feature-id dtype"
            )
        if self.checkpoint_format not in ("npz", "orbax"):
            raise ValueError(f"unknown checkpoint_format {self.checkpoint_format!r}")
        if self.delta_every_steps < 0:
            raise ValueError(
                f"delta_every_steps must be >= 0 (0 = off), got {self.delta_every_steps}"
            )
        if self.delta_every_steps > 0 and self.checkpoint_format == "orbax":
            # The delta container is an npz sibling file chained by content
            # signature; orbax directories have no such sidecar format (and
            # orbax's own async machinery is the pod-scale answer there).
            raise ValueError(
                "delta_every_steps > 0 requires checkpoint_format = npz "
                "(the delta chain is an npz sidecar format)"
            )
        if self.delta_chain_max < 1:
            raise ValueError(
                f"delta_chain_max must be >= 1, got {self.delta_chain_max}"
            )
        if self.checkpoint_chunk_mb < 1:
            raise ValueError(
                f"checkpoint_chunk_mb must be >= 1, got {self.checkpoint_chunk_mb}"
            )
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"unknown compute_dtype {self.compute_dtype!r}")
        if self.lookup not in ("allgather", "alltoall"):
            raise ValueError(f"unknown lookup {self.lookup!r} (allgather | alltoall)")
        if self.lookup_overflow not in ("fallback", "abort"):
            raise ValueError(
                f"unknown lookup_overflow {self.lookup_overflow!r} (fallback | abort)"
            )
        if self.steps_per_call < 1:
            raise ValueError(
                f"steps_per_call must be >= 1, got {self.steps_per_call}"
            )
        if self.wire_format not in ("packed", "arrays"):
            raise ValueError(
                f"unknown wire_format {self.wire_format!r} (packed | arrays)"
            )
        if self.thread_num < 0:
            raise ValueError(
                f"thread_num must be >= 0 (0 = all cores), got {self.thread_num}"
            )
        if self.shuffle_seed < 0:
            # numpy SeedSequence rejects negatives — fail at the config,
            # not deep inside the prefetch thread.
            raise ValueError(f"shuffle_seed must be >= 0, got {self.shuffle_seed}")
        if self.adagrad_accumulator not in ("element", "row", "fused"):
            raise ValueError(
                f"unknown adagrad_accumulator {self.adagrad_accumulator!r} "
                "(element | row | fused)"
            )
        if self.packed_compact_cap < 0:
            raise ValueError(
                f"packed_compact_cap must be >= 0, got {self.packed_compact_cap}"
            )
        if self.packed_compact_cap > 0 and self.adagrad_accumulator != "fused":
            # The cap only exists on the fused compact tail; silently inert
            # knobs corrupt A/B comparisons (packed_update rationale above).
            raise ValueError(
                "packed_compact_cap > 0 requires adagrad_accumulator = fused "
                "(it sizes the fused compact tail's row buffer)"
            )
        if self.adagrad_accumulator == "fused" and self.table_layout != "packed":
            # Fused is a PHYSICAL layout choice (row accumulator stored in
            # the table's own tile rows); it only exists packed.
            raise ValueError(
                "adagrad_accumulator = fused requires table_layout = packed"
            )
        if self.table_layout not in ("rows", "packed"):
            raise ValueError(
                f"unknown table_layout {self.table_layout!r} (rows | packed)"
            )
        if self.init_accumulator_value <= 0:
            # TF AdagradOptimizer requires a positive initial accumulator
            # for the same reason: a zero accumulator makes the first
            # update of any element with zero summed gradient compute
            # 0/sqrt(0) = NaN (rows layout: zero-grad elements of touched
            # rows; packed layout: untouched logical rows sharing a tile
            # row), silently corrupting the table.
            raise ValueError(
                f"init_accumulator_value must be > 0, got {self.init_accumulator_value}"
            )
        self.serve_buckets = validate_buckets(self.serve_buckets)
        if self.serve_max_batch < 0:
            raise ValueError(
                f"serve_max_batch must be >= 0 (0 = largest bucket), "
                f"got {self.serve_max_batch}"
            )
        if self.serve_max_batch > self.serve_buckets[-1]:
            raise ValueError(
                f"serve_max_batch {self.serve_max_batch} exceeds the largest "
                f"bucket {self.serve_buckets[-1]} — a flush that size would "
                "have no compiled shape (raise serve_buckets or lower it)"
            )
        if self.serve_flush_deadline_ms < 0:
            raise ValueError(
                f"serve_flush_deadline_ms must be >= 0, got {self.serve_flush_deadline_ms}"
            )
        if self.serve_queue_size < 1:
            raise ValueError(
                f"serve_queue_size must be >= 1, got {self.serve_queue_size}"
            )
        if self.serve_overload not in ("block", "reject"):
            raise ValueError(
                f"unknown serve_overload {self.serve_overload!r} (block | reject)"
            )
        if self.serve_reload_interval_s < 0 or self.serve_metrics_every_s < 0:
            raise ValueError(
                "serve_reload_interval_s and serve_metrics_every_s must be >= 0"
            )
        if self.serve_reload_max_retries < 1:
            raise ValueError(
                f"serve_reload_max_retries must be >= 1, got "
                f"{self.serve_reload_max_retries}"
            )
        if not (0 <= self.serve_port <= 65535):
            raise ValueError(f"serve_port must be in [0, 65535], got {self.serve_port}")
        if self.serve_replicas < 1:
            raise ValueError(
                f"serve_replicas must be >= 1, got {self.serve_replicas}"
            )
        if self.serve_deadline_ms < 0:
            raise ValueError(
                f"serve_deadline_ms must be >= 0 (0 = none), got "
                f"{self.serve_deadline_ms}"
            )
        self.serve_classes = validate_classes(self.serve_classes)
        if self.serve_wire not in ("binary", "jsonl"):
            raise ValueError(
                f"unknown serve_wire {self.serve_wire!r} (binary | jsonl)"
            )
        if self.online_poll_s <= 0:
            raise ValueError(f"[Online] poll_s must be > 0, got {self.online_poll_s}")
        if self.online_idle_timeout_s < 0 or self.online_max_batches < 0:
            raise ValueError(
                "[Online] idle_timeout_s and max_batches must be >= 0 (0 = off)"
            )
        if not (0.0 < self.online_adagrad_decay <= 1.0):
            raise ValueError(
                f"[Online] adagrad_decay must be in (0, 1], got "
                f"{self.online_adagrad_decay}"
            )
        if self.online_adagrad_decay != 1.0 and self.table_layout != "rows":
            # The packed tile-row RMWs rely on the zero-grad accumulator
            # identity (untouched logical rows sharing a tile row must not
            # change); a lane-blind decay would break it silently.
            raise ValueError(
                "[Online] adagrad_decay < 1 requires table_layout = rows"
            )
        if self.online_accum_restart_steps < 0:
            raise ValueError(
                f"[Online] accum_restart_steps must be >= 0, got "
                f"{self.online_accum_restart_steps}"
            )
        if self.online_accum_restart_steps > 0 and self.adagrad_accumulator == "fused":
            # The fused layout stores the accumulator inside the table's
            # own tile rows — there is no separate array to reset.
            raise ValueError(
                "[Online] accum_restart_steps requires adagrad_accumulator "
                "= element or row (the fused layout has no separate "
                "accumulator array to reset)"
            )
        if self.online_accum_restart_steps > 0 and self.delta_every_steps > 0:
            # The reset rewrites EVERY accumulator row, but delta saves
            # ship only the touched-row window — a crash-resume would
            # replay PRE-reset accumulators for every untouched row,
            # silently breaking the exact-position-resume invariant.
            raise ValueError(
                "[Online] accum_restart_steps cannot combine with "
                "delta_every_steps: a global accumulator reset is not "
                "representable in a touched-row delta (resume would "
                "restore stale accumulators) — use full saves, or "
                "adagrad_decay"
            )
        if self.online_accum_restart_steps > 0 and self.online_adagrad_decay != 1.0:
            # Two competing forgetting mechanisms make every A/B reading
            # ambiguous — pick one per run.
            raise ValueError(
                "[Online] adagrad_decay < 1 and accum_restart_steps > 0 are "
                "exclusive — choose one forgetting mechanism"
            )
        if self.online_follow:
            if self.shuffle:
                raise ValueError(
                    "[Online] follow = true cannot shuffle: an append-only "
                    "stream has no fixed row count to permute"
                )
            if self.device_cache:
                raise ValueError(
                    "[Online] follow = true is a streamed input mode — "
                    "device_cache loads a FIXED dataset to HBM once"
                )
            if self.epoch_num != 1:
                raise ValueError(
                    "[Online] follow = true runs ONE endless epoch — set "
                    f"epoch_num = 1 (got {self.epoch_num})"
                )
        if self.dedup_gather_rows < 0:
            raise ValueError(
                f"dedup_gather_rows must be >= 0 (0 = off), got "
                f"{self.dedup_gather_rows}"
            )
        if self.dedup_gather_rows > 0:
            if self.table_layout != "rows":
                # The dedup body gathers/indexes the plain [V, D] table;
                # the packed layouts have their own compaction story
                # (packed_update = compact).
                raise ValueError(
                    "dedup_gather_rows > 0 requires table_layout = rows"
                )
            if self.device_cache:
                raise ValueError(
                    "dedup_gather_rows applies to the STREAMED path; "
                    "device_cache slices resident batches (drop one)"
                )
            if self.paramstore:
                raise ValueError(
                    "dedup_gather_rows is redundant under [ParamStore] "
                    "(tiered resolution already dedups before the gather) "
                    "— drop one"
                )
            if self.online_follow:
                # The follow stream (_follow_stream) does not run the
                # per-batch cap guard; without it an over-cap appended
                # batch would truncate silently inside the jitted dedup.
                raise ValueError(
                    "dedup_gather_rows with [Online] follow is not "
                    "supported: the tail-following stream has no "
                    "per-batch cap verification yet"
                )
        if self.paramstore:
            if self.table_layout != "rows":
                raise ValueError(
                    "[ParamStore] requires table_layout = rows (the "
                    "compact device tier is a plain [C, D] table)"
                )
            if self.checkpoint_format != "npz":
                raise ValueError(
                    "[ParamStore] requires checkpoint_format = npz (both "
                    "tiers publish through the npz chain)"
                )
            if self.device_cache:
                raise ValueError(
                    "[ParamStore] and device_cache are exclusive: the "
                    "tiered store IS the residency decision"
                )
            if self.online_follow:
                raise ValueError(
                    "[ParamStore] with [Online] follow is not supported "
                    "yet (ROADMAP item 4 composes them)"
                )
            if self.async_save:
                raise ValueError(
                    "[ParamStore] saves are synchronous (the post-publish "
                    "store apply must order after the npz publish) — drop "
                    "async_save"
                )
            if self.adagrad_accumulator == "fused":
                raise ValueError(
                    "[ParamStore] supports adagrad_accumulator = element "
                    "or row (fused is a packed-layout storage choice)"
                )
            if self.on_nan == "rollback":
                raise ValueError(
                    "[ParamStore] with on_nan = rollback is not supported "
                    "yet — use abort (the tiered restore path does not "
                    "plug into the in-process rollback loop)"
                )
            if self.online_accum_restart_steps > 0:
                raise ValueError(
                    "[ParamStore] cannot combine with accum_restart_steps: "
                    "a global accumulator reset cannot reach the cold "
                    "tier's rows — use adagrad_decay"
                )
            if self.paramstore_hot_rows < 1:
                raise ValueError(
                    f"[ParamStore] hot_rows must be >= 1, got "
                    f"{self.paramstore_hot_rows}"
                )
            if self.paramstore_miss_rows < 0:
                raise ValueError(
                    "[ParamStore] miss_rows must be >= 0 (0 = auto), got "
                    f"{self.paramstore_miss_rows}"
                )
            if self.paramstore_sample_batches < 1:
                raise ValueError(
                    "[ParamStore] sample_batches must be >= 1, got "
                    f"{self.paramstore_sample_batches}"
                )
            if self.paramstore_residency not in ("sample", "first") and not (
                self.paramstore_residency.startswith("file:")
                and len(self.paramstore_residency) > 5
            ):
                raise ValueError(
                    f"unknown [ParamStore] residency "
                    f"{self.paramstore_residency!r} (sample | first | "
                    "file:PATH)"
                )
            if self.paramstore_materialize not in ("auto", "always", "never"):
                raise ValueError(
                    f"unknown [ParamStore] materialize "
                    f"{self.paramstore_materialize!r} (auto | always | never)"
                )
        if self.delta_full_every_s < 0 or self.delta_chain_max_bytes < 0:
            raise ValueError(
                "[Checkpoint] full_every_s and chain_max_bytes must be >= 0 "
                "(0 = off)"
            )
        if self.on_nan not in ("abort", "rollback"):
            raise ValueError(f"unknown on_nan {self.on_nan!r} (abort | rollback)")
        if self.max_rollbacks < 0:
            raise ValueError(f"max_rollbacks must be >= 0, got {self.max_rollbacks}")
        if self.io_retries < 0:
            raise ValueError(f"io_retries must be >= 0, got {self.io_retries}")
        if self.io_retry_backoff_s < 0:
            raise ValueError(
                f"io_retry_backoff_s must be >= 0, got {self.io_retry_backoff_s}"
            )
        if self.restart_max < 0:
            raise ValueError(f"restart_max must be >= 0, got {self.restart_max}")
        if self.restart_backoff_s < 0 or self.restart_backoff_max_s < 0:
            raise ValueError(
                "restart_backoff_s and restart_backoff_max_s must be >= 0"
            )
        if self.input_assignment not in ("rows", "files"):
            raise ValueError(
                f"unknown input_assignment {self.input_assignment!r} (rows | files)"
            )
        if self.heartbeat_s <= 0:
            raise ValueError(f"heartbeat_s must be > 0, got {self.heartbeat_s}")
        if self.host_stall_timeout_s < 0:
            raise ValueError(
                f"host_stall_timeout_s must be >= 0 (0 = off), got "
                f"{self.host_stall_timeout_s}"
            )
        if self.barrier_timeout_s <= 0:
            raise ValueError(
                f"barrier_timeout_s must be > 0, got {self.barrier_timeout_s}"
            )
        if self.telemetry_mem_every_s < 0 or self.telemetry_stall_timeout_s < 0:
            raise ValueError(
                "telemetry_mem_every_s and telemetry_stall_timeout_s must be "
                ">= 0 (0 disables)"
            )
        if self.telemetry_profile_steps:
            # Parse-validate at config time, not at step N of a long run.
            from fast_tffm_tpu.profiling import parse_profile_steps

            parse_profile_steps(self.telemetry_profile_steps)
        if self.telemetry_datastats_every_steps < 0:
            raise ValueError(
                "telemetry_datastats_every_steps must be >= 0 (0 = off), got "
                f"{self.telemetry_datastats_every_steps}"
            )
        if self.telemetry_heavy_hitter_k < 1:
            raise ValueError(
                f"telemetry_heavy_hitter_k must be >= 1, got "
                f"{self.telemetry_heavy_hitter_k}"
            )
        if self.packed_update not in ("auto", "dense", "compact", "sorted"):
            raise ValueError(
                f"unknown packed_update {self.packed_update!r} "
                "(auto | dense | compact | sorted)"
            )
        if self.packed_update != "auto" and self.table_layout != "packed":
            # Silently inert knobs corrupt A/B comparisons: a run that
            # pins the update strategy but forgets the layout would
            # measure the rows layout and call it dense/sorted.
            raise ValueError(
                f"packed_update = {self.packed_update} requires "
                "table_layout = packed (it selects the packed layout's "
                "sparse-tail strategy)"
            )
        if (
            self.table_layout == "packed"
            and self.adagrad_accumulator in ("row", "fused")
            and self.packed_update == "sorted"
        ):
            # The sorted packed update's whole-tile-row RMW is exact only
            # with the element accumulator (zero-grad identity per LANE);
            # the row accumulator's [VP, P] scalar slots need a scatter-add
            # tail (dense or compact — both handle both granularities).
            raise ValueError(
                "table_layout = packed with adagrad_accumulator = row "
                "requires packed_update = auto, dense or compact (the "
                "sorted whole-tile-row RMW needs the element accumulator)"
            )
        return self


def validate_buckets(buckets) -> tuple[int, ...]:
    """Normalize a serve_buckets spec: positive ints, sorted, deduped,
    non-empty.  Lives here (not serving/) so config validation stays
    jax-free — serving/buckets.py imports it back."""
    try:
        out = tuple(sorted({int(b) for b in buckets}))
    except (TypeError, ValueError) as e:
        raise ValueError(f"serve_buckets must be integers, got {buckets!r}") from e
    if not out or out[0] < 1:
        raise ValueError(f"serve_buckets must be positive and non-empty, got {buckets!r}")
    return out


def validate_classes(classes) -> tuple[tuple[str, int], ...]:
    """Normalize a serve_classes spec: a ``"gold:2,std:1"`` string or an
    iterable of (name, tier) pairs → sorted tuple of (name, tier).  Tiers
    are non-negative ints; names non-empty and unique.  Lives here (like
    validate_buckets) so config validation stays jax-free."""
    if isinstance(classes, str):
        pairs = []
        for tok in _split(classes):
            name, sep, tier = tok.partition(":")
            if not sep or not name:
                raise ValueError(
                    f"serve_classes entries are name:tier, got {tok!r}"
                )
            pairs.append((name, tier))
        classes = pairs
    out = []
    try:
        for name, tier in classes:
            name, tier = str(name), int(tier)
            if not name or tier < 0:
                raise ValueError
            out.append((name, tier))
    except (TypeError, ValueError):
        raise ValueError(
            f"serve_classes must be name:tier pairs with tier >= 0, got {classes!r}"
        ) from None
    # Outside the try: the generic format message must not swallow the
    # far more actionable duplicate-name diagnosis.
    seen = set()
    for name, _ in out:
        if name in seen:
            raise ValueError(f"duplicate serve_classes name {name!r}")
        seen.add(name)
    return tuple(sorted(out))


def _split(s: str) -> tuple[str, ...]:
    return tuple(x for x in (t.strip() for t in s.replace(",", " ").split()) if x)


def _split_files(s: str) -> tuple[str, ...]:
    """File list with glob expansion: `train_files = data/part-*.libsvm`.

    Matches expand sorted (stable shard order across workers); a pattern
    with no match is kept literally so the missing-file error names the
    user's path, not a silently empty list.
    """
    import glob as _glob

    out: list[str] = []
    for tok in _split(s):
        if any(c in tok for c in "*?["):
            out.extend(sorted(_glob.glob(tok)) or [tok])
        else:
            out.append(tok)
    return tuple(out)


def load_config(path: str) -> Config:
    """Parse an INI file into a validated Config."""
    # The reference's sample.cfg style annotates values in place
    # ("key = value  ; comment"); ConfigParser keeps inline comments unless
    # told otherwise, which would corrupt every annotated value.
    ini = configparser.ConfigParser(inline_comment_prefixes=(";", "#"))
    with open(path) as f:
        ini.read_file(f)
    cfg = Config()

    def get(section, key, conv, default):
        if ini.has_option(section, key):
            raw = ini.get(section, key)
            return conv(raw)
        return default

    g = "General"
    cfg.model = get(g, "model", str, cfg.model).lower()
    cfg.factor_num = get(g, "factor_num", int, cfg.factor_num)
    cfg.order = get(g, "order", int, cfg.order)
    cfg.num_fields = get(g, "num_fields", int, cfg.num_fields)
    cfg.hidden_dims = get(
        g, "hidden_dims", lambda s: tuple(int(x) for x in _split(s)), cfg.hidden_dims
    )
    cfg.compute_dtype = get(g, "compute_dtype", str, cfg.compute_dtype).lower()
    cfg.vocabulary_size = get(g, "vocabulary_size", int, cfg.vocabulary_size)
    cfg.vocabulary_block_num = get(g, "vocabulary_block_num", int, cfg.vocabulary_block_num)
    cfg.hash_feature_id = get(g, "hash_feature_id", ini._convert_to_boolean, cfg.hash_feature_id)
    cfg.table_layout = get(g, "table_layout", str, cfg.table_layout).lower()
    cfg.model_file = get(g, "model_file", str, cfg.model_file)
    cfg.checkpoint_format = get(g, "checkpoint_format", str, cfg.checkpoint_format).lower()

    t = "Train"
    cfg.train_files = get(t, "train_files", _split_files, cfg.train_files)
    cfg.weight_files = get(
        t, "weight_files", lambda s: tuple(float(x) for x in _split(s)), cfg.weight_files
    )
    cfg.validation_files = get(t, "validation_files", _split_files, cfg.validation_files)
    cfg.epoch_num = get(t, "epoch_num", int, cfg.epoch_num)
    cfg.batch_size = get(t, "batch_size", int, cfg.batch_size)
    cfg.max_nnz = get(t, "max_nnz", int, cfg.max_nnz)
    cfg.learning_rate = get(t, "learning_rate", float, cfg.learning_rate)
    cfg.init_value_range = get(t, "init_value_range", float, cfg.init_value_range)
    cfg.factor_lambda = get(t, "factor_lambda", float, cfg.factor_lambda)
    cfg.bias_lambda = get(t, "bias_lambda", float, cfg.bias_lambda)
    cfg.init_accumulator_value = get(
        t, "init_accumulator_value", float, cfg.init_accumulator_value
    )
    cfg.adagrad_accumulator = get(
        t, "adagrad_accumulator", str, cfg.adagrad_accumulator
    ).lower()
    cfg.packed_update = get(t, "packed_update", str, cfg.packed_update).lower()
    cfg.packed_compact_cap = get(
        t, "packed_compact_cap", int, cfg.packed_compact_cap
    )
    cfg.thread_num = get(t, "thread_num", int, cfg.thread_num)
    cfg.binary_cache = get(t, "binary_cache", ini._convert_to_boolean, cfg.binary_cache)
    cfg.binary_cache_wait = get(t, "binary_cache_wait", float, cfg.binary_cache_wait)
    cfg.shuffle = get(t, "shuffle", ini._convert_to_boolean, cfg.shuffle)
    cfg.shuffle_seed = get(t, "shuffle_seed", int, cfg.shuffle_seed)
    cfg.device_cache = get(t, "device_cache", ini._convert_to_boolean, cfg.device_cache)
    cfg.dedup_gather_rows = get(
        t, "dedup_gather_rows", int, cfg.dedup_gather_rows
    )
    cfg.steps_per_call = get(t, "steps_per_call", int, cfg.steps_per_call)
    cfg.wire_format = get(t, "wire_format", str, cfg.wire_format).lower()
    cfg.queue_size = get(t, "queue_size", int, cfg.queue_size)
    cfg.log_every = get(t, "log_every", int, cfg.log_every)
    cfg.save_every_epochs = get(t, "save_every_epochs", int, cfg.save_every_epochs)
    cfg.trace_dir = get(t, "trace_dir", str, cfg.trace_dir)
    cfg.trace_steps = get(t, "trace_steps", int, cfg.trace_steps)
    cfg.metrics_path = get(t, "metrics_path", str, cfg.metrics_path)

    te = "Telemetry"
    cfg.telemetry_run_id = get(te, "run_id", str, cfg.telemetry_run_id)
    cfg.telemetry_mem_every_s = get(te, "mem_every_s", float, cfg.telemetry_mem_every_s)
    cfg.telemetry_stall_timeout_s = get(
        te, "stall_timeout_s", float, cfg.telemetry_stall_timeout_s
    )
    cfg.telemetry_compilation_cache_dir = get(
        te, "compilation_cache_dir", str, cfg.telemetry_compilation_cache_dir
    )
    cfg.telemetry_profile_steps = get(
        te, "profile_steps", str, cfg.telemetry_profile_steps
    )
    cfg.telemetry_profile_costs = get(
        te, "profile_costs", ini._convert_to_boolean, cfg.telemetry_profile_costs
    )
    cfg.telemetry_datastats_every_steps = get(
        te, "datastats_every_steps", int, cfg.telemetry_datastats_every_steps
    )
    cfg.telemetry_heavy_hitter_k = get(
        te, "heavy_hitter_k", int, cfg.telemetry_heavy_hitter_k
    )

    c = "Checkpoint"
    cfg.async_save = get(c, "async_save", ini._convert_to_boolean, cfg.async_save)
    cfg.delta_every_steps = get(c, "delta_every_steps", int, cfg.delta_every_steps)
    cfg.delta_chain_max = get(c, "delta_chain_max", int, cfg.delta_chain_max)
    cfg.delta_full_every_s = get(c, "full_every_s", float, cfg.delta_full_every_s)
    cfg.delta_chain_max_bytes = get(
        c, "chain_max_bytes", int, cfg.delta_chain_max_bytes
    )
    cfg.checkpoint_chunk_mb = get(c, "chunk_mb", int, cfg.checkpoint_chunk_mb)

    p = "Predict"
    cfg.predict_files = get(p, "predict_files", _split_files, cfg.predict_files)
    cfg.score_path = get(p, "score_path", str, cfg.score_path)

    s = "Serving"
    cfg.serve_buckets = get(
        s, "buckets", lambda v: tuple(int(x) for x in _split(v)), cfg.serve_buckets
    )
    cfg.serve_max_batch = get(s, "max_batch", int, cfg.serve_max_batch)
    cfg.serve_flush_deadline_ms = get(
        s, "flush_deadline_ms", float, cfg.serve_flush_deadline_ms
    )
    cfg.serve_queue_size = get(s, "queue_size", int, cfg.serve_queue_size)
    cfg.serve_overload = get(s, "overload", str, cfg.serve_overload).lower()
    cfg.serve_reload_interval_s = get(
        s, "reload_interval_s", float, cfg.serve_reload_interval_s
    )
    cfg.serve_metrics_every_s = get(
        s, "metrics_every_s", float, cfg.serve_metrics_every_s
    )
    cfg.serve_reload_max_retries = get(
        s, "reload_max_retries", int, cfg.serve_reload_max_retries
    )
    cfg.serve_port = get(s, "port", int, cfg.serve_port)
    cfg.serve_replicas = get(s, "replicas", int, cfg.serve_replicas)
    cfg.serve_deadline_ms = get(s, "deadline_ms", float, cfg.serve_deadline_ms)
    cfg.serve_classes = get(s, "classes", str, cfg.serve_classes)
    cfg.serve_wire = get(s, "wire", str, cfg.serve_wire).lower()
    cfg.serve_affinity = get(
        s, "affinity", ini._convert_to_boolean, cfg.serve_affinity
    )

    o = "Online"
    cfg.online_follow = get(o, "follow", ini._convert_to_boolean, cfg.online_follow)
    cfg.online_poll_s = get(o, "poll_s", float, cfg.online_poll_s)
    cfg.online_idle_timeout_s = get(
        o, "idle_timeout_s", float, cfg.online_idle_timeout_s
    )
    cfg.online_max_batches = get(o, "max_batches", int, cfg.online_max_batches)
    cfg.online_adagrad_decay = get(
        o, "adagrad_decay", float, cfg.online_adagrad_decay
    )
    cfg.online_accum_restart_steps = get(
        o, "accum_restart_steps", int, cfg.online_accum_restart_steps
    )

    ps = "ParamStore"
    cfg.paramstore = get(ps, "enabled", ini._convert_to_boolean, cfg.paramstore)
    cfg.paramstore_hot_rows = get(ps, "hot_rows", int, cfg.paramstore_hot_rows)
    cfg.paramstore_miss_rows = get(ps, "miss_rows", int, cfg.paramstore_miss_rows)
    cfg.paramstore_dir = get(ps, "store_dir", str, cfg.paramstore_dir)
    cfg.paramstore_residency = get(ps, "residency", str, cfg.paramstore_residency)
    cfg.paramstore_sample_batches = get(
        ps, "sample_batches", int, cfg.paramstore_sample_batches
    )
    cfg.paramstore_materialize = get(
        ps, "materialize", str, cfg.paramstore_materialize
    ).lower()

    r = "Resilience"
    cfg.on_nan = get(r, "on_nan", str, cfg.on_nan).lower()
    cfg.max_rollbacks = get(r, "max_rollbacks", int, cfg.max_rollbacks)
    cfg.io_retries = get(r, "io_retries", int, cfg.io_retries)
    cfg.io_retry_backoff_s = get(
        r, "io_retry_backoff_s", float, cfg.io_retry_backoff_s
    )
    cfg.restart_max = get(r, "restart_max", int, cfg.restart_max)
    cfg.restart_backoff_s = get(r, "restart_backoff_s", float, cfg.restart_backoff_s)
    cfg.restart_backoff_max_s = get(
        r, "restart_backoff_max_s", float, cfg.restart_backoff_max_s
    )

    d = "Distributed"
    cfg.data_parallel = get(d, "data_parallel", int, cfg.data_parallel)
    cfg.row_parallel = get(d, "row_parallel", int, cfg.row_parallel)
    cfg.lookup = get(d, "lookup", str, cfg.lookup).lower()
    cfg.lookup_overflow = get(d, "lookup_overflow", str, cfg.lookup_overflow).lower()
    cfg.lookup_capacity_factor = get(
        d, "lookup_capacity_factor", float, cfg.lookup_capacity_factor
    )
    cfg.coordinator_address = get(d, "coordinator_address", str, cfg.coordinator_address)
    cfg.num_processes = get(d, "num_processes", int, cfg.num_processes)
    cfg.process_id = get(d, "process_id", int, cfg.process_id)
    cfg.input_assignment = get(d, "input_assignment", str, cfg.input_assignment).lower()
    cfg.runtime_dir = get(d, "runtime_dir", str, cfg.runtime_dir)
    cfg.heartbeat_s = get(d, "heartbeat_s", float, cfg.heartbeat_s)
    cfg.host_stall_timeout_s = get(
        d, "host_stall_timeout_s", float, cfg.host_stall_timeout_s
    )
    cfg.barrier_timeout_s = get(d, "barrier_timeout_s", float, cfg.barrier_timeout_s)

    return cfg.validate()


def build_model(cfg: Config):
    """Instantiate the configured model (the reference's graph-builder role)."""
    from fast_tffm_tpu.models import DeepFMModel, FFMModel, FMModel

    if cfg.model == "fm":
        return FMModel(
            vocabulary_size=cfg.vocabulary_size,
            factor_num=cfg.factor_num,
            order=cfg.order,
            init_value_range=cfg.init_value_range,
            factor_lambda=cfg.factor_lambda,
            bias_lambda=cfg.bias_lambda,
        )
    if cfg.model == "ffm":
        return FFMModel(
            vocabulary_size=cfg.vocabulary_size,
            num_fields=cfg.num_fields,
            factor_num=cfg.factor_num,
            init_value_range=cfg.init_value_range,
            factor_lambda=cfg.factor_lambda,
            bias_lambda=cfg.bias_lambda,
            compute_dtype=cfg.compute_dtype,
        )
    return DeepFMModel(
        vocabulary_size=cfg.vocabulary_size,
        num_fields=cfg.num_fields,
        factor_num=cfg.factor_num,
        hidden_dims=cfg.hidden_dims,
        init_value_range=cfg.init_value_range,
        factor_lambda=cfg.factor_lambda,
        bias_lambda=cfg.bias_lambda,
        compute_dtype=cfg.compute_dtype,
    )
