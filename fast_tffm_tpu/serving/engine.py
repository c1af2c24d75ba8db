"""The in-process serving engine: admission → micro-batch → bucket → score.

Request lifecycle:

  1. ``submit_line`` / ``submit`` parses the request to the static
     ``max_nnz`` width and enqueues it on the BOUNDED admission queue.
     Overload policy (``serve_overload``): ``block`` applies
     backpressure to the caller; ``reject`` raises OverloadError
     immediately — the queue is the only elastic buffer, so memory under
     overload is capped at ``serve_queue_size`` requests either way.
  2. The collector thread gathers requests and flushes when
     ``serve_max_batch`` fills OR ``serve_flush_deadline_ms`` expires
     for the oldest pending request — whichever first.  The deadline is
     the latency/occupancy knob: 0 serves every request the moment it is
     seen (occupancy→1/bucket), large values fill buckets (throughput).
  3. A flush pads up to the nearest compile-ladder bucket
     (buckets.BucketLadder — no steady-state XLA compiles), scores,
     slices the padding off, and resolves per-request futures.
  4. A watcher thread polls ``model_file``; a changed checkpoint is
     restored OFF the hot path into a fresh state and staged; the
     collector swaps it in ATOMICALLY between flushes — no flush ever
     sees half-old half-new weights, and a torn/partial checkpoint write
     fails the stage (counted, retried next tick) without touching the
     serving state.

Single-device by design: one process, one chip (or CPU), the deployment
unit a load balancer replicates.  The mesh-sharded offline path
(dist_predict) stays the batch tool for backfills.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field

import numpy as np

from fast_tffm_tpu.checkpoint import (
    checkpoint_save_id,
    checkpoint_signature,
    load_delta,
    read_delta_chain,
    read_publish_time,
)
from fast_tffm_tpu.config import Config
from fast_tffm_tpu.data.libsvm import parse_lines
from fast_tffm_tpu.serving.admission import AdmissionQueue
from fast_tffm_tpu.serving.buckets import BucketLadder
from fast_tffm_tpu.serving.metrics import ServingMetrics
from fast_tffm_tpu.serving.protocol import FRAME_STATUS_CODES, DeadlineExceeded
from fast_tffm_tpu.telemetry import RunMonitor, log_device, log_quietly
from fast_tffm_tpu.utils.tracing import span

__all__ = [
    "ServingEngine",
    "OverloadError",
    "DeadlineExceeded",
    "EngineClosed",
    "serve_lines",
]


class OverloadError(RuntimeError):
    """Admission queue full under serve_overload = reject, or a queued
    request evicted by a higher-class arrival (tiered admission)."""


class EngineClosed(RuntimeError):
    """Request submitted to (or unresolved inside) a closed engine."""


_CLOSE = object()  # collector shutdown sentinel

# Per-row status bytes for block (frame) responses — indices into
# protocol.FRAME_STATUS_CODES, so the wire and the engine agree by
# construction.
_ST_OK = 0
_ST_OVERLOADED = FRAME_STATUS_CODES.index("overloaded")
_ST_DEADLINE = FRAME_STATUS_CODES.index("deadline")
_ST_BAD_REQUEST = FRAME_STATUS_CODES.index("bad_request")
_ST_UNAVAILABLE = FRAME_STATUS_CODES.index("unavailable")


@dataclass
class _Request:
    row: tuple  # (ids [max_nnz] i32, vals [max_nnz] f32, fields [max_nnz] i32)
    future: Future = field(default_factory=Future)
    t_submit: float = field(default_factory=time.perf_counter)
    klass: str = ""  # client class name ("" = default tier)
    tier: int = 0  # admission tier (higher sheds later; from serve_classes)
    deadline_t: float | None = None  # perf_counter deadline; None = none

    n_rows = 1  # admission/flush row accounting (blocks carry many)


@dataclass
class _Block:
    """A whole decoded REQUEST frame admitted as ONE unit: one queue
    slot, one decode, one coalesced placement, one response.  ``future``
    resolves to ``(statuses u8[n], scores f32[n])`` — nonzero statuses
    index FRAME_STATUS_CODES, so per-row typed errors survive batching;
    once a flush has claimed the block, ``future.flush_seq`` is the flush
    it rode (``serve.flush`` carries the same number in the trace).
    Tier is the MINIMUM over its rows: under tiered overload a mixed
    frame sheds as its weakest member (a frame is one delivery unit; a
    caller who needs gold treatment must not staple gold rows to std
    ones)."""

    ids: np.ndarray  # (n, max_nnz) i32
    vals: np.ndarray  # (n, max_nnz) f32
    fields: np.ndarray | None  # (n, max_nnz) i32, or None
    deadline_t: np.ndarray  # (n,) f64 perf_counter deadlines; +inf = none
    statuses: np.ndarray  # (n,) u8; nonzero = decided before scoring
    klasses: list  # per-row class names (metrics attribution)
    future: Future = field(default_factory=Future)
    t_submit: float = field(default_factory=time.perf_counter)
    klass: str = ""  # representative class ("" when mixed)
    tier: int = 0

    @property
    def n_rows(self) -> int:
        return int(self.ids.shape[0])


class ServingEngine:
    """See module docstring.  Construct with a validated Config whose
    ``model_file`` holds a restorable checkpoint; scoring runs through
    the same ScoreFn as ``prediction.predict`` — bit-identical per batch
    shape (pinned by tests/test_serving.py); against predict's own
    differently-shaped batches, agreement is within a few float32 ULPs
    on backends where XLA programs of different shapes round apart."""

    def __init__(
        self, cfg: Config, log=print, state=None, model=None, replica: int | None = None
    ):
        from fast_tffm_tpu.prediction import load_scoring_state, make_score_fn
        from fast_tffm_tpu.training import scan_max_nnz

        self._cfg = cfg
        self._log = log
        if cfg.max_nnz <= 0 and not (
            cfg.train_files or cfg.validation_files or cfg.predict_files
        ):
            raise ValueError(
                "serving needs a static feature width: set max_nnz in [Train], "
                "or configure data files for the width scan"
            )
        max_nnz = scan_max_nnz(cfg)
        if state is None:
            # Baseline reload signature BEFORE the (possibly multi-second)
            # restore: a trainer save landing mid-restore must read as
            # "new" to the watcher, not as already-loaded — worst case it
            # redundantly reloads the checkpoint we started from.
            self._loaded_sig = checkpoint_signature(cfg.model_file)
            # Delta bookkeeping, also PRE-restore (under-counting is the
            # safe direction: re-applying an already-applied delta suffix
            # in order is idempotent; skipping one is not).
            self._loaded_save_id, self._applied_deltas = self._chain_baseline()
            model, state = load_scoring_state(cfg, log)
        else:
            # Injected state: the on-disk checkpoint was NEVER loaded, so
            # no signature is "already loaded" — whatever model_file holds
            # (even something older than this baseline) is news to us.
            self._loaded_sig = None
            self._loaded_save_id, self._applied_deltas = None, 0
        self._state = state
        self._score = make_score_fn(cfg, state, max_nnz, model=model)
        if (
            cfg.serve_reload_interval_s > 0
            and cfg.table_layout == "packed"
            and state.table_opt.accum.size == 0
        ):
            # An injected FUSED-packed state (empty-accum marker) compiled
            # a fused-gather ScoreFn, but the watcher's load_scoring_state
            # restores plain-packed — a swap would feed a D-stride table
            # to D+1-stride tile arithmetic: clamped gathers, confidently
            # wrong scores, no error.  Refuse the combination up front.
            raise ValueError(
                "hot reload (serve_reload_interval_s > 0) cannot re-pack "
                "checkpoints into an injected fused-packed state's layout — "
                "pass a plain-packed/rows state, or disable the watcher"
            )
        self._ladder = BucketLadder(
            self._score,
            cfg.serve_buckets,
            wire_format=cfg.wire_format,
            vocabulary_size=cfg.vocabulary_size,
        )
        self.max_batch = cfg.serve_max_batch or self._ladder.max_batch
        if self.max_batch > self._ladder.max_batch:
            raise ValueError(
                f"serve_max_batch {self.max_batch} exceeds the largest bucket "
                f"{self._ladder.max_batch} — a flush that size has no compiled shape"
            )
        self.deadline_s = cfg.serve_flush_deadline_ms / 1e3
        self._policy = cfg.serve_overload
        self._q = AdmissionQueue(cfg.serve_queue_size)
        # Tiered admission (serve_classes): class name -> tier; unknown /
        # absent classes land at tier 0 (shed first).  Per-request
        # deadlines default to serve_deadline_ms (0 = none) unless the
        # submit carries its own.
        self._tiers = dict(cfg.serve_classes)
        self._default_deadline_s = (
            cfg.serve_deadline_ms / 1e3 if cfg.serve_deadline_ms > 0 else None
        )
        # Chaos/latency injection (tools/chaos.py replica_slow@N:ms): the
        # next `_slow_flushes` flushes sleep `_slow_ms` before dispatch.
        self._slow_ms = 0.0
        self._slow_flushes = 0
        self._last_flush_t = time.perf_counter()
        self.metrics = ServingMetrics()
        # kind=serving records ride the same telemetry envelope as the
        # train/predict drivers (shared run_id per engine lifetime); the
        # compile sentinel turns any steady-state flush compile into a
        # kind=compile event — the bucket-ladder pin, now observable.
        # No stall deadline here: an idle engine is healthy, not stalled;
        # the monitor's thread runs as the host clock alone (freeze_ms,
        # gc_ms on every kind=serving record, ``ServingMetrics.log_to``).
        self._monitor = RunMonitor(
            cfg.metrics_path,
            run_id=cfg.telemetry_run_id,
            source="serving",
            mem_every_s=cfg.telemetry_mem_every_s,
            replica=replica,
            log=log,
            device=log_device(log, "serving"),
        )
        self._flush_seq = 0  # telemetry step for serving = flush ordinal
        self._metrics_every = cfg.serve_metrics_every_s
        self._wait_s = 0.0  # collector: blocked in q.get since the last flush
        # Held by the collector from a flush's start until it is accounted:
        # ``metrics_snapshot`` takes it, so a caller whose future resolved
        # (on the collector, mid-flush) reads counters that hold its flush.
        self._flush_lock = threading.Lock()
        self._closed = False  # no new submits (set by close AND by a
        #   collector crash — see _collect's exception handler)
        self._close_done = False  # close() finalization ran (separate
        #   flag: a crash sets _closed, but close() must still write the
        #   final metrics record and join the watcher afterwards)
        self._stop = threading.Event()
        # Hot-reload handoff: the watcher STAGES a fully-restored state
        # here; the collector SWAPS it in between flushes.  One lock, two
        # one-line critical sections.
        self._reload_lock = threading.Lock()
        # Reload ticks SERIALIZE on this engine-level lock: under
        # continuous publish, a delta landing while a tick is mid-apply of
        # its PARENT can trigger a second reload_once from another thread
        # (a router reconnect's fresh control connection, a poll tick
        # racing a router command) — two concurrent ticks would both pass
        # the staged-state check and race _applied_deltas/_loaded_sig,
        # applying the chain out of order.  A blocking lock makes the
        # second caller QUEUE: it re-reads the (advanced) signature after
        # the first apply completes, so deltas apply strictly in chain
        # order (test-pinned under concurrent publish).
        self._tick_lock = threading.Lock()
        self._staged_state = None
        self._staged_step = None
        self._staged_is_delta = False
        # Freshness SLO bookkeeping: the staged checkpoint's publish
        # timestamp (stamped into the npz by the writer — wall clock, so
        # cross-host skew applies and negatives clamp to 0) travels with
        # the stage; the swap records publish→applied and the first
        # successful score after it completes publish→first-scored.
        self._staged_pub_t = None
        self._pending_fresh = None
        # Reload failure discipline for ONE observed signature (shared by
        # the polling watcher thread and router-driven reload_once calls):
        # retries back off exponentially, and after serve_reload_max_retries
        # consecutive failures the engine GIVES UP on that signature until
        # a NEW write lands.
        self._fail_sig = None
        self._fail_count = 0
        self._gave_up = False
        self._next_retry_t = 0.0

        n = self._ladder.warmup(self._state)
        if cfg.telemetry_profile_costs:
            # Measured cost ledger: one kind=profile record per bucket's
            # score program (bytes/FLOPs from XLA cost analysis).  Pure
            # re-lowering at the warmed shapes — no extra backend compile,
            # and it runs inside startup, never on the flush path.
            from fast_tffm_tpu.profiling import CostLedger

            ledger = CostLedger(self._monitor, source="serving")
            for bkt in self._ladder.buckets:
                ledger.stage(
                    f"serve_score_b{bkt}",
                    self._score.fn,
                    (self._state, self._ladder.example_batch(bkt)),
                    examples=bkt,
                )
            ledger.flush(0)
        # Attribute every startup compile (ladder rungs + unpackers) to
        # warmup; anything the sentinel sees after this is steady-state.
        self._monitor.on_dispatch(0, warmup=True)
        log(
            f"serving: warmed buckets {self._ladder.buckets} "
            f"(max_nnz {max_nnz}, {n if n >= 0 else '?'} compiled programs, "
            f"flush deadline {cfg.serve_flush_deadline_ms}ms, "
            f"queue {cfg.serve_queue_size} {self._policy})"
        )
        self._collector = threading.Thread(
            target=self._collect, name="serve-collector", daemon=True
        )
        self._collector.start()
        self._watcher = None
        if cfg.serve_reload_interval_s > 0:
            self._watcher = threading.Thread(
                target=self._watch, name="serve-reload", daemon=True
            )
            self._watcher.start()

    # -- submission ------------------------------------------------------

    @property
    def buckets(self) -> tuple[int, ...]:
        return self._ladder.buckets

    @property
    def step(self) -> int:
        """Step of the state CURRENTLY serving (advances at the first
        flush after a reload swap, not when the watcher stages)."""
        return int(self._state.step)

    @property
    def run_id(self) -> str:
        """Telemetry run id of this engine's monitor — the join key
        bench/probe artifacts stamp so they are joinable to the JSONL."""
        return self._monitor.run_id

    @property
    def device(self) -> dict:
        """``platform`` / ``device_kind`` / ``device_count`` this engine
        scores on (telemetry.log_device) — what REPLICA_READY announces."""
        return self._monitor.device

    def compile_count(self) -> int | None:
        return self._ladder.compile_count()

    @property
    def max_nnz(self) -> int:
        """Static per-row feature width — what a binary-wire client must
        pack frames at (advertised in the hello ack)."""
        return self._score.max_nnz

    @property
    def uses_fields(self) -> bool:
        """Whether the model reads the fields section (ffm/fwfm)."""
        return bool(self._score.uses_fields)

    def submit_line(
        self,
        line: str,
        *,
        klass: str = "",
        deadline_ms: float | None = None,
        deadline_at: float | None = None,
    ) -> Future:
        """Submit one libsvm/libffm line (``label feat:val ...`` — the
        label is required by the grammar and ignored, the exact format of
        predict_files).  Returns a Future resolving to the float score.
        Malformed lines and rows wider than max_nnz raise ValueError in
        the caller (admission is never charged for parse errors).

        ``klass`` names the client class (tier from serve_classes;
        unknown = tier 0, shed first).  ``deadline_ms`` is THIS request's
        deadline from submit time (None = serve_deadline_ms; 0 disables):
        a request still unscored when it expires is shed pre-padding with
        DeadlineExceeded and counted as a deadline_drop.  ``deadline_at``
        (a ``time.monotonic()`` timestamp, same host) wins over both —
        it is how the socket front end anchors the budget at WIRE receipt
        so time spent in TCP buffers and reader backlog counts too; the
        engine converts it to a remaining budget at ingest, so the two
        clocks never need a shared epoch."""
        parsed = parse_lines(
            [line],
            vocabulary_size=self._cfg.vocabulary_size,
            hash_feature_id_flag=self._cfg.hash_feature_id,
            max_nnz=self._score.max_nnz,
        )
        return self._submit_row(
            (
                parsed.ids[0].astype(np.int32, copy=False),
                parsed.vals[0],
                parsed.fields[0],
            ),
            klass=klass,
            deadline_ms=deadline_ms,
            deadline_at=deadline_at,
        )

    def submit(
        self,
        ids,
        vals,
        fields=None,
        *,
        klass: str = "",
        deadline_ms: float | None = None,
        deadline_at: float | None = None,
    ) -> Future:
        """Submit one pre-parsed example (1-D ids/vals[/fields], up to
        max_nnz entries; zero-padded here).  The programmatic twin of
        submit_line for callers that skip text."""
        ids = np.asarray(ids, np.int32).reshape(-1)
        vals = np.asarray(vals, np.float32).reshape(-1)
        w = self._score.max_nnz
        if ids.shape != vals.shape or ids.size > w:
            raise ValueError(
                f"ids/vals must match and carry <= max_nnz={w} entries, "
                f"got {ids.shape} / {vals.shape}"
            )
        v = self._cfg.vocabulary_size
        if ids.size and (int(ids.min()) < 0 or int(ids.max()) >= v):
            # Same range invariant parse_lines enforces on the text path:
            # the jitted gather CLAMPS out-of-bounds ids, which would turn
            # a caller bug into a confidently wrong score from an
            # unrelated embedding row.
            raise ValueError(
                f"feature ids must lie in [0, {v}); got "
                f"[{int(ids.min())}, {int(ids.max())}]"
            )
        fields = (
            np.zeros(ids.shape, np.int32)
            if fields is None
            else np.asarray(fields, np.int32).reshape(-1)
        )
        if fields.shape != ids.shape:
            raise ValueError(f"fields shape {fields.shape} != ids shape {ids.shape}")
        pad = w - ids.size
        if pad:
            ids = np.pad(ids, (0, pad))
            vals = np.pad(vals, (0, pad))
            fields = np.pad(fields, (0, pad))
        return self._submit_row(
            (ids, vals, fields),
            klass=klass,
            deadline_ms=deadline_ms,
            deadline_at=deadline_at,
        )

    def _shed_evicted(self, evicted: "_Request | _Block | None") -> None:
        """Fail an evicted request's future with the typed overload error
        — the no-silent-drop half of tiered admission.  An evicted BLOCK
        resolves (never raises): its per-row statuses flip to overloaded
        so the frame's response stays row-typed."""
        if evicted is None:
            return
        if isinstance(evicted, _Block):
            if evicted.future.set_running_or_notify_cancel():
                st = evicted.statuses.copy()
                st[st == _ST_OK] = _ST_OVERLOADED
                evicted.future.set_result(
                    (st, np.zeros(evicted.n_rows, np.float32))
                )
            for k in evicted.klasses:
                self.metrics.on_evict(k)
            return
        if evicted.future.set_running_or_notify_cancel():
            evicted.future.set_exception(
                OverloadError(
                    f"shed: evicted by a higher-class arrival under overload "
                    f"(class {evicted.klass or 'default'!r}, tier {evicted.tier})"
                )
            )
        self.metrics.on_evict(evicted.klass)

    def submit_block(
        self,
        ids,
        vals,
        fields=None,
        *,
        deadlines_ms=None,
        classes=None,
    ) -> Future:
        """Submit a whole decoded REQUEST frame as ONE admission unit
        (ISSUE 16: one decode, one queue slot, one coalesced placement).

        ``ids``/``vals`` (and optional ``fields``) are (n, width) arrays
        with width <= max_nnz (column-padded here); ``deadlines_ms`` are
        per-row RELATIVE budgets anchored now (0 = serve_deadline_ms
        default).  Returns a Future resolving to ``(statuses, scores)``
        — u8 codes into FRAME_STATUS_CODES and float32 rows.  Frame-level
        shape bugs raise ValueError (a typed bad_request at the wire);
        rows with out-of-range ids fail per-row with bad_request status
        instead of poisoning their whole frame.
        """
        ids = np.asarray(ids, np.int32)
        vals = np.asarray(vals, np.float32)
        w = self._score.max_nnz
        if ids.ndim != 2 or vals.shape != ids.shape:
            raise ValueError(
                f"block ids/vals must be matching (n, width) arrays, got "
                f"{ids.shape} / {vals.shape}"
            )
        n, width = ids.shape
        if n < 1:
            raise ValueError("empty block")
        if n > self.max_batch:
            raise ValueError(
                f"block of {n} rows exceeds max_batch {self.max_batch} — "
                "honor the negotiated max_frame_rows"
            )
        if width > w:
            raise ValueError(f"block width {width} exceeds max_nnz {w}")
        if fields is not None:
            fields = np.asarray(fields, np.int32)
            if fields.shape != ids.shape:
                raise ValueError(
                    f"fields shape {fields.shape} != ids shape {ids.shape}"
                )
        if width < w:
            pad = ((0, 0), (0, w - width))
            ids = np.pad(ids, pad)
            vals = np.pad(vals, pad)
            if fields is not None:
                fields = np.pad(fields, pad)
        v = self._cfg.vocabulary_size
        bad = ((ids < 0) | (ids >= v)).any(axis=1)
        statuses = np.where(bad, np.uint8(_ST_BAD_REQUEST), np.uint8(_ST_OK))
        if classes is None:
            klasses = [""] * n
        else:
            klasses = [str(c or "") for c in classes]
            if len(klasses) != n:
                raise ValueError(f"classes carries {len(klasses)} entries for {n} rows")
        t_submit = time.perf_counter()
        base = self._default_deadline_s
        base_t = (t_submit + base) if (base is not None and base > 0) else np.inf
        if deadlines_ms is None:
            deadline_t = np.full(n, base_t)
        else:
            d = np.asarray(deadlines_ms, np.float64).reshape(-1)
            if d.shape != (n,):
                raise ValueError(f"deadlines_ms carries {d.shape} entries for {n} rows")
            deadline_t = np.where(d > 0, t_submit + d / 1e3, base_t)
        tiers = [self._tiers.get(k, 0) for k in klasses]
        block = _Block(
            ids=ids,
            vals=vals,
            fields=fields,
            deadline_t=deadline_t,
            statuses=statuses,
            klasses=klasses,
            t_submit=t_submit,
            klass=klasses[0] if len(set(klasses)) == 1 else "",
            tier=min(tiers),
        )
        if self._closed:
            raise EngineClosed("engine is closed")
        if self._policy == "reject":
            try:
                self._shed_evicted(self._q.put_nowait(block, tier=block.tier))
            except queue.Full:
                self.metrics.on_submit_many(n, accepted=False, klasses=klasses)
                raise OverloadError(
                    f"admission queue full ({self._q.maxsize} pending) — "
                    "overload; shed load or raise serve_queue_size / switch "
                    "serve_overload to block"
                ) from None
        else:
            while True:
                if self._closed:
                    raise EngineClosed("engine closed while blocked on admission")
                try:
                    self._shed_evicted(self._q.put(block, tier=block.tier, timeout=0.1))
                    break
                except queue.Full:
                    continue
        self.metrics.on_submit_many(n, accepted=True)
        # Same close-race epilogue as _submit_row: see the comment there.
        if self._closed and not self._collector.is_alive():
            self._drain_with_exception(EngineClosed("engine closed"))
        return block.future

    def _submit_row(
        self,
        row,
        *,
        klass: str = "",
        deadline_ms: float | None = None,
        deadline_at: float | None = None,
    ) -> Future:
        req = _Request(row, klass=klass, tier=self._tiers.get(klass, 0))
        if deadline_at is not None:
            # Wire-anchored absolute deadline: convert the REMAINING
            # monotonic budget into this engine's perf_counter terms (one
            # clock read; no shared epoch assumed).  May be <= 0 already —
            # the flush sheds it before padding, which is the point:
            # backlog time upstream of admission counts.
            req.deadline_t = req.t_submit + (deadline_at - time.monotonic())
        else:
            dl = self._default_deadline_s if deadline_ms is None else deadline_ms / 1e3
            if dl is not None and dl > 0:
                req.deadline_t = req.t_submit + dl
        if self._closed:
            raise EngineClosed("engine is closed")
        if self._policy == "reject":
            try:
                self._shed_evicted(self._q.put_nowait(req, tier=req.tier))
            except queue.Full:
                self.metrics.on_submit(accepted=False, klass=klass)
                raise OverloadError(
                    f"admission queue full ({self._q.maxsize} pending) — "
                    "overload; shed load or raise serve_queue_size / switch "
                    "serve_overload to block"
                ) from None
        else:  # block: backpressure, re-checking closure so a shutdown
            # mid-overload can't strand the caller forever.  (A strictly
            # lower-tier queued request is still evicted rather than
            # blocking the higher-class arrival behind shed-able traffic.)
            while True:
                if self._closed:
                    raise EngineClosed("engine closed while blocked on admission")
                try:
                    self._shed_evicted(self._q.put(req, tier=req.tier, timeout=0.1))
                    break
                except queue.Full:
                    continue
        self.metrics.on_submit(accepted=True, klass=klass)
        # Close-race epilogue: if close() finished its drain between our
        # closed-check and our enqueue, nobody will ever pop this request.
        # _closed is set BEFORE close joins/drains, so observing it here
        # (after the put) and draining ourselves closes the window — the
        # drain fails our own future with EngineClosed instead of
        # stranding the caller.
        if self._closed and not self._collector.is_alive():
            self._drain_with_exception(EngineClosed("engine closed"))
        return req.future

    # -- collector -------------------------------------------------------

    def _collect(self) -> None:
        pending: list[_Request | _Block] = []
        rows = 0  # real rows across `pending` (a block counts its n)
        deadline = 0.0
        draining = False
        try:
            while True:
                if pending and rows >= self.max_batch:
                    self._flush(pending, deadline_fired=False)
                    pending = []
                    rows = 0
                    continue
                timeout = None
                if pending:
                    timeout = deadline - time.perf_counter()
                    if timeout <= 0:
                        # Deadline expired: top up with already-QUEUED
                        # requests first.  Under backlog the oldest
                        # request's deadline is often already past when
                        # it is popped; flushing it alone would collapse
                        # micro-batching to singleton dispatches exactly
                        # when load is highest.
                        while rows < self.max_batch:
                            try:
                                extra = self._q.get_nowait()
                            except queue.Empty:
                                break
                            if extra is _CLOSE:
                                draining = True
                                break
                            pending.append(extra)
                            rows += extra.n_rows
                        self._flush(
                            pending,
                            deadline_fired=rows < self.max_batch,
                        )
                        pending = []
                        rows = 0
                        continue
                elif draining:
                    # Close requested and everything flushed: done.
                    return
                t_wait = time.perf_counter()
                try:
                    with span("serve.collect_wait"):
                        item = self._q.get(timeout=timeout)
                except queue.Empty:
                    continue
                finally:
                    self._wait_s += time.perf_counter() - t_wait
                if item is _CLOSE:
                    # Flush what's pending plus anything still queued, in
                    # max_batch groups, then exit.
                    draining = True
                    deadline = time.perf_counter()  # expire immediately
                    continue
                if not pending:
                    # Deadline anchors at the oldest request's SUBMIT
                    # time (the documented contract), so time it spent in
                    # the admission queue behind a busy flush counts
                    # against the budget — not just time in `pending`.
                    deadline = item.t_submit + self.deadline_s
                pending.append(item)
                rows += item.n_rows
        except BaseException as e:  # never strand submitted futures
            # Mark the engine closed FIRST: with a dead collector, a
            # block-policy submit would otherwise spin on the full queue
            # forever (nothing consumes, nothing raises).  Stop the
            # watcher too — it would keep doing full restores every tick
            # on an engine that can no longer serve.
            self._closed = True
            self._stop.set()
            for r in pending:
                if not r.future.done():
                    r.future.set_exception(e)
            self._drain_with_exception(e)
            raise
        finally:
            self._drain_with_exception(EngineClosed("engine closed"))

    def _drain_with_exception(self, exc: BaseException) -> None:
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return
            if item is not _CLOSE and not item.future.done():
                item.future.set_exception(exc)

    def _flush(self, pending: list[_Request], deadline_fired: bool) -> None:
        # Atomic reload swap: flushes are the only reader of _state, so
        # swapping here means every request in THIS flush (and all later
        # ones) scores against one consistent checkpoint.
        with self._reload_lock:
            staged, self._staged_state = self._staged_state, None
            staged_step = self._staged_step
            staged_is_delta = self._staged_is_delta
            staged_pub_t = self._staged_pub_t
        if staged is not None:
            self._state = staged
            if staged_pub_t is not None:
                # publish→applied is sealed HERE (the swap is the apply);
                # publish→first-scored completes when this (or, if this
                # flush is all-shed/fails, a later) flush resolves scores.
                self._pending_fresh = {
                    "published_at": staged_pub_t,
                    "applied_ms": max(0.0, (time.time() - staged_pub_t) * 1e3),
                    "step": staged_step,
                    "mode": "delta" if staged_is_delta else "full",
                }
            if not staged_is_delta:
                # Delta swaps are already counted (per FILE) by
                # on_delta_reload — keeping them out of `reloads` keeps
                # the two counters independent: reloads = full re-reads.
                self.metrics.on_reload(ok=True)
            log_quietly(self._log, f"serving: swapped in checkpoint step {staged_step}")
        # Blocks make `pending` row counts lumpy: a close-time drain (or
        # a block-heavy top-up) can exceed max_batch rows, which has no
        # compiled shape.  Partition into <=max_batch-row groups; a
        # single block never exceeds max_batch (submit_block enforces).
        chunk: list[_Request | _Block] = []
        chunk_rows = 0
        for item in pending:
            if chunk and chunk_rows + item.n_rows > self.max_batch:
                self._flush_units(chunk, chunk_rows, deadline_fired)
                chunk = []
                chunk_rows = 0
            chunk.append(item)
            chunk_rows += item.n_rows
        if chunk:
            self._flush_units(chunk, chunk_rows, deadline_fired)

    def _flush_units(
        self, pending: "list[_Request | _Block]", rows: int, deadline_fired: bool
    ) -> None:
        """One dispatch: ``serve.flush`` with its four stages as children,
        then the bookkeeping (which is the collector's time but no part of
        any request's latency, so outside the span)."""
        with self._flush_lock:
            t_start = time.perf_counter()
            with span(
                "serve.flush",
                flush_seq=self._flush_seq + 1,
                rows=rows,
                deadline_fired=int(deadline_fired),
            ):
                scored = self._score_units(pending, t_start)
            if scored is not None:
                self._account_flush(t_start, deadline_fired, *scored)

    def _shed(self, pending: "list[_Request | _Block]", now: float):
        """Claim the futures and shed what has already missed its own
        deadline; (live per-row requests in order, (block, alive idx)
        pairs, live rows)."""
        # Claim the futures: a pending Future is always cancellable, and
        # resolving a cancelled one raises InvalidStateError — which,
        # unguarded, would kill the collector over ONE impatient caller.
        # set_running_or_notify_cancel() both blocks late cancels and
        # filters already-cancelled requests out of the batch.
        pending = [r for r in pending if r.future.set_running_or_notify_cancel()]
        # Deadline shed BEFORE padding: a request whose own deadline has
        # already expired cannot be answered in time — scoring it would
        # only inflate the bucket (and the batch's latency) for an answer
        # nobody is waiting for.  Shedding first can also shrink the
        # bucket the survivors pad to (the bucket is picked AFTER the
        # shed, over the whole coalesced flush).
        seq = self._flush_seq + 1
        reqs: list[_Request] = []
        blocks: list[tuple[_Block, np.ndarray]] = []
        n_alive = 0
        for r in pending:
            if isinstance(r, _Block):
                r.future.flush_seq = seq
                st = r.statuses
                expired = (now >= r.deadline_t) & (st == _ST_OK)
                if expired.any():
                    st[expired] = _ST_DEADLINE
                    for i in np.flatnonzero(expired):
                        self.metrics.on_deadline_drop(r.klasses[int(i)])
                alive = np.flatnonzero(st == _ST_OK)
                blocks.append((r, alive))
                n_alive += int(alive.size)
                continue
            if r.deadline_t is not None and now >= r.deadline_t:
                r.future.set_exception(
                    DeadlineExceeded(
                        f"deadline expired {1e3 * (now - r.deadline_t):.1f}ms "
                        f"before scoring (waited {1e3 * (now - r.t_submit):.1f}ms)"
                    )
                )
                self.metrics.on_deadline_drop(r.klass)
            else:
                reqs.append(r)
                n_alive += 1
        return reqs, blocks, n_alive

    def _fail_flush(self, reqs, blocks, e: BaseException) -> None:
        """A flush that raised is answered typed, never stranded."""
        for r in reqs:
            if not r.future.done():
                r.future.set_exception(e)
        for b, alive in blocks:
            if not b.future.done():
                # Blocks resolve, never raise: already-decided rows
                # (deadline/bad_request) keep their codes; only the
                # would-have-scored rows become unavailable.
                st = b.statuses.copy()
                st[alive] = _ST_UNAVAILABLE
                b.future.set_result((st, np.zeros(b.n_rows, np.float32)))
        log_quietly(self._log, f"serving: flush failed: {e!r}")
        self._last_flush_t = time.perf_counter()  # answered = progress

    def _score_units(self, pending: "list[_Request | _Block]", t_start: float):
        """Shed and assemble, dispatch, fetch, reply: four stages that tile
        ``t_start`` → ``t_resolved``, each a span and a clock.  Returns what
        ``_account_flush`` records, or None when nothing was scored (all
        rows shed, or the flush failed and was answered typed)."""
        # The shed, the chaos sleep, packing, the one H2D put and the unpack
        # program's dispatch (packed wire: buckets._finalize).
        with span("serve.assemble"):
            reqs, blocks, n_alive = self._shed(pending, t_start)
            if n_alive == 0:
                # Every row shed — blocks still owe their ONE response (the
                # shed rows' typed codes travel in it).  Still PROGRESS: the
                # collector drained (and answered) work — an all-shed flush
                # must advance the liveness clock or a tight-deadline
                # overload reads as a wedged collector to the router's
                # health checks.
                for b, _ in blocks:
                    b.future.set_result((b.statuses, np.zeros(b.n_rows, np.float32)))
                self._last_flush_t = time.perf_counter()
                return None
            if self._slow_flushes > 0:  # injected latency (chaos replica_slow)
                self._slow_flushes -= 1
                time.sleep(self._slow_ms / 1e3)
            try:
                parts = [(r.row[0][None], r.row[1][None], r.row[2][None]) for r in reqs]
                parts += [
                    (
                        b.ids[alive],
                        b.vals[alive],
                        b.fields[alive] if b.fields is not None else None,
                    )
                    for b, alive in blocks
                ]
                batch, bucket = self._ladder.assemble_parts(parts)
            except BaseException as e:
                return self._fail_flush(reqs, blocks, e)
        t_dispatch = time.perf_counter()
        try:
            with span("serve.dispatch", bucket=bucket, rows=n_alive):
                on_device = self._ladder.score(self._state, batch)
            t_fetch = time.perf_counter()
            with span("serve.fetch"):
                scores = np.asarray(on_device)
                del on_device  # the device buffer goes now, not after the reply
        except BaseException as e:
            return self._fail_flush(reqs, blocks, e)
        t_done = time.perf_counter()
        # Resolving a future runs its done-callbacks HERE, on this thread:
        # for a replica that is the SCORES packing and the socket send.
        with span("serve.reply"):
            pos = 0
            for r in reqs:
                r.future.set_result(float(scores[pos]))
                pos += 1
            for b, alive in blocks:
                out = np.zeros(b.n_rows, np.float32)
                out[alive] = scores[pos : pos + alive.size]
                pos += int(alive.size)
                b.future.set_result((b.statuses, out))
        t_resolved = time.perf_counter()
        stages = (t_dispatch - t_start, t_fetch - t_dispatch, t_done - t_fetch, t_resolved - t_done)
        return reqs, blocks, n_alive, bucket, t_resolved, stages

    def _account_flush(
        self, t_start, deadline_fired, reqs, blocks, n_alive, bucket, t_resolved, stages
    ) -> None:
        self._flush_seq += 1
        if self._pending_fresh is not None:
            self._emit_freshness()
        try:
            self._monitor.on_dispatch(self._flush_seq)
        except (OSError, ValueError):
            # Same stance as the metrics writes below: a telemetry I/O
            # failure (ENOSPC mem record) degrades to a lost record —
            # it must NEVER kill the collector.
            pass
        self._last_flush_t = t_resolved
        if self.metrics.interval_age(t_resolved) == 0.0:
            # This flush opens the next record's interval: what the host
            # lost while the engine sat idle (start-up, a quiet server) is
            # no frame's, so the record's freeze_ms / gc_ms count from
            # here, as its stage fields do.  The kind=freeze events and the
            # summary keep all of it.
            self._monitor.drain_host_clock()
        # One metrics group per request plus one per BLOCK: a frame's
        # rows share submit/resolve instants, so its group carries a row
        # count instead of n duplicate histogram insertions.
        self.metrics.on_flush(
            bucket,
            n_alive,
            queue_waits=[t_start - r.t_submit for r in reqs]
            + [t_start - b.t_submit for b, _ in blocks],
            compute_s=stages[1] + stages[2],
            total_s=[t_resolved - r.t_submit for r in reqs]
            + [t_resolved - b.t_submit for b, _ in blocks],
            deadline_fired=deadline_fired,
            classes=[r.klass for r in reqs] + [b.klass for b, _ in blocks],
            counts=[1] * len(reqs) + [int(alive.size) for _, alive in blocks],
            t_start=t_start,
            t_resolved=t_resolved,
            stages=stages,
            wait_s=self._wait_s,
        )
        self._wait_s = 0.0
        # A record is due ``serve_metrics_every_s`` after its interval
        # opened (the first flush since the previous record), so a record
        # never holds just the one row that woke an idle server.
        if (
            self._metrics_every > 0
            and self.metrics.interval_age(t_resolved) >= self._metrics_every
        ):
            try:
                self.metrics.log_to(self._monitor)
            except (OSError, ValueError):
                # A full metrics disk (ENOSPC) must degrade to lost
                # metrics records, never to a dead collector: every
                # request behind a dead collector hangs or blocks.
                pass

    def _emit_freshness(self) -> None:
        """Seal one reload's freshness SLO: publish→applied was measured
        at the swap; publish→first-scored-with-new-rows completes now,
        at the first flush that RESOLVED scores against the new state.
        Collector thread only (it owns _pending_fresh after the swap)."""
        f, self._pending_fresh = self._pending_fresh, None
        scored_ms = max(0.0, (time.time() - f["published_at"]) * 1e3)
        self.metrics.on_freshness(f["applied_ms"] / 1e3, scored_ms / 1e3)
        try:
            self._monitor.emit(
                "freshness",
                step=self._flush_seq,
                publish_step=f["step"],
                publish_to_applied_ms=round(f["applied_ms"], 3),
                publish_to_first_scored_ms=round(scored_ms, 3),
                mode=f["mode"],
            )
        except (OSError, ValueError):
            pass  # a full metrics disk must not kill the collector

    # -- hot reload ------------------------------------------------------

    def _chain_baseline(self) -> tuple[str | None, int]:
        """(base save_id, delta-chain length) of the on-disk checkpoint,
        tolerant of anything unreadable (None/0 just means the in-place
        delta path stays off until the next full reload)."""
        import os as _os

        path = self._cfg.model_file
        if _os.path.isdir(path):
            return None, 0
        try:
            sid = checkpoint_save_id(path)
            _, chain = read_delta_chain(path)
            return sid, len(chain)
        except (ValueError, OSError):
            return checkpoint_save_id(path), 0

    def _apply_delta_state(self, state, delta):
        """Functional in-place apply of ONE delta to a serving state:
        scatter the logical rows into the (rows or plain-packed) table,
        swap the dense leaves, advance the step.  Never donates — the
        collector may be mid-flush on the current buffers.  (Optimizer
        accumulators are not updated: scoring never reads them, and the
        next full reload replaces them.)"""
        import jax
        import jax.numpy as jnp

        idx = delta["idx"]
        table = state.table
        if idx.size:
            i32 = jnp.asarray(idx.astype(np.int32))
            rows = jnp.asarray(delta["table_rows"])
            if self._cfg.table_layout == "packed":
                from fast_tffm_tpu.ops.packed_table import scatter_logical_rows

                table = scatter_logical_rows(
                    table, i32, rows, self._score.model.row_dim
                )
            else:
                table = table.at[i32].set(rows, mode="drop")
        dense = state.dense
        leaves, ddef = jax.tree.flatten(state.dense)
        if leaves:
            dense = jax.tree.unflatten(
                ddef, [jnp.asarray(x) for x in delta["dense"]]
            )
        return state._replace(
            table=table, dense=dense, step=jnp.asarray(delta["step"])
        )

    def _try_apply_deltas(self):
        """In-place incremental reload: when the on-disk base is STILL the
        one this engine loaded and only new delta files landed, apply the
        unapplied suffix to the current state and return (staged_state,
        n_applied) — no full-table re-read.  Returns None when the base
        changed (full reload required) and (None, 0) when nothing new."""
        import jax

        base_sig, chain = read_delta_chain(self._cfg.model_file)
        if (
            self._loaded_save_id is None
            or base_sig != self._loaded_save_id
        ):
            return None  # new (or unsigned) base: take the full-reload path
        new = chain[self._applied_deltas :]
        if not new:
            return (None, 0)
        state = self._state
        n_dense = len(jax.tree.leaves(state.dense))
        for meta in new:
            state = self._apply_delta_state(
                state, load_delta(meta["path"], n_dense)
            )
        return (state, len(new))

    def _note_reload_failure(self, sig, what, exc) -> None:
        """Failure discipline for ONE observed signature: retries back
        off exponentially from the poll interval, and after
        serve_reload_max_retries consecutive failures the engine GIVES
        UP on that signature (reload_giveups counter + kind=anomaly
        record) instead of hot-spinning reload_failures forever on a
        persistently corrupt file.  Any NEW write (signature change)
        resets the state and retries immediately."""
        self.metrics.on_reload(ok=False)
        if sig != self._fail_sig:
            self._fail_sig, self._fail_count, self._gave_up = sig, 0, False
        self._fail_count += 1
        backoff = min(
            max(self._cfg.serve_reload_interval_s, 0.01) * (2.0 ** self._fail_count),
            60.0,
        )
        self._next_retry_t = time.monotonic() + backoff
        self._log(
            f"serving: {what} of {self._cfg.model_file} failed "
            f"(attempt {self._fail_count}/{self._cfg.serve_reload_max_retries}, "
            f"next retry in {backoff:.2f}s): {exc!r}"
        )
        if self._fail_count >= self._cfg.serve_reload_max_retries:
            self._gave_up = True
            self.metrics.on_reload_giveup()
            try:
                self._monitor.emit_anomaly(
                    self.step, None, event="reload_giveup",
                    path=self._cfg.model_file, error=repr(exc),
                    attempts=self._fail_count,
                )
            except (OSError, ValueError):
                pass  # a full metrics disk must not kill the watcher
            self._log(
                f"serving: giving up on this checkpoint write after "
                f"{self._fail_count} failed reloads — persistently corrupt? "
                "serving continues on the loaded state; a NEW write "
                "will be retried"
            )

    def _reload_tick(self) -> str:
        """One reload attempt: check the signature, stage a new state if
        one landed.  Called by the polling watcher thread (its loop body)
        and by ``reload_once`` (a router fanning out ONE reload command
        to every replica).  Returns the outcome for the caller's ack:
        ``noop`` | ``staged`` | ``staged_delta`` | ``failed`` |
        ``backoff`` | ``busy``.

        Whole-tick serialization (``_tick_lock``): a second caller landing
        while a tick is mid-apply BLOCKS until that apply completes, then
        observes the advanced chain state — a delta published while the
        watcher is mid-apply of its parent QUEUES behind it instead of
        racing the bookkeeping (apply-in-order under continuous publish)."""
        with self._tick_lock:
            with self._reload_lock:
                if self._staged_state is not None:
                    # The collector hasn't swapped the previous stage yet;
                    # applying deltas onto _state now would drop that stage.
                    return "busy"
            sig = checkpoint_signature(self._cfg.model_file)
            if sig is None or sig == self._loaded_sig:
                return "noop"
            if sig == self._fail_sig:
                if self._gave_up or time.monotonic() < self._next_retry_t:
                    return "backoff"  # backing off / abandoned until a new write
            else:
                self._fail_sig, self._fail_count, self._gave_up = None, 0, False
            with self._monitor.warmup_window():
                return self._reload_attempt(sig)

    def _reload_attempt(self, sig) -> str:
        """The actual restore/apply work of one reload tick.  Runs inside
        a telemetry warmup_window: the chunked-restore and delta-apply
        programs it may compile execute OFF the hot path (the collector
        keeps flushing the old state), so they must not read as
        steady-state score recompiles."""
        import os as _os

        from fast_tffm_tpu.prediction import load_scoring_state

        # Freshness stamp captured BEFORE the (possibly multi-second)
        # restore: it names the chain head observed at attempt start.  A
        # publish landing mid-restore can only make the measured latency
        # OVERSTATE staleness (older stamp vs whatever got restored) —
        # the safe error direction for an SLO; reading after the restore
        # would attribute the staged (older) state to the newer publish.
        pub_t = read_publish_time(self._cfg.model_file)
        state = None
        applied = 0
        if not _os.path.isdir(self._cfg.model_file):
            try:
                got = self._try_apply_deltas()
            except Exception as e:
                # Torn/mid-write delta: count, keep serving, retry with
                # backoff (signature not advanced, so a complete write
                # still reloads).
                self._note_reload_failure(sig, "delta reload", e)
                return "failed"
            if got == (None, 0):
                # Signature moved without new chain content (e.g. a
                # same-base rewrite mid-observation) — nothing to do.
                self._loaded_sig = sig
                return "noop"
            if got is not None:
                state, applied = got
        if state is None:
            # Full restore OFF the hot path: the collector keeps serving
            # the old state while this loads.  Chain baseline is read
            # PRE-restore (under-count = safe, see above).
            new_sid, new_applied = self._chain_baseline()
            try:
                _, state = load_scoring_state(self._cfg, log=lambda *_: None)
            except Exception as e:
                # Torn write (non-atomic writer, or a checkpoint
                # mid-copy): count it, keep serving, back off.
                self._note_reload_failure(sig, "reload", e)
                return "failed"
            self._loaded_save_id = new_sid
            self._applied_deltas = new_applied
        else:
            self._applied_deltas += applied
            self.metrics.on_delta_reload(applied)
        self._fail_sig, self._fail_count, self._gave_up = None, 0, False
        self._loaded_sig = sig
        with self._reload_lock:
            self._staged_state = state
            self._staged_step = int(state.step)
            self._staged_is_delta = applied > 0
            self._staged_pub_t = pub_t
        return "staged_delta" if applied > 0 else "staged"

    def reload_once(self) -> dict:
        """Router-driven reload: one watcher tick on the CALLER's thread
        (the replica worker runs it off its reader loop).  The in-process
        polling watcher stays off (serve_reload_interval_s = 0) when a
        router owns reload fan-out — exactly one of the two drives
        reloads, so a delta is applied exactly once per replica."""
        status = self._reload_tick()
        return {"status": status, "step": self.step}

    def _watch(self) -> None:
        while not self._stop.wait(self._cfg.serve_reload_interval_s):
            self._reload_tick()

    # -- health / chaos ----------------------------------------------------

    def inject_slow(self, ms: float, flushes: int = 1) -> None:
        """Chaos hook (FaultPlan replica_slow@N:ms): make the next
        ``flushes`` flushes sleep ``ms`` before dispatch — a degraded or
        wedged replica, without touching real scoring."""
        self._slow_ms = float(ms)
        self._slow_flushes = int(flushes)

    def health(self) -> dict:
        """O(1) liveness probe for routers/load balancers: queue depth,
        age of the oldest QUEUED request (keeps growing when the
        collector wedges — the router's wedge signal), time since the
        last completed flush, and whether the engine still accepts."""
        now = time.perf_counter()
        oldest = self._q.oldest_wait_s(now)
        return {
            "ok": not self._closed,
            "closed": self._closed,
            "step": self.step,
            "queue_depth": self._q.qsize(),
            "oldest_wait_s": round(oldest, 4) if oldest is not None else None,
            "last_flush_age_s": round(now - self._last_flush_t, 4),
            "steady_compiles": self._monitor.compiles_steady,
        }

    # -- shutdown --------------------------------------------------------

    def metrics_snapshot(self) -> dict:
        with self._flush_lock:
            return self.metrics.snapshot()

    def close(self, timeout: float = 30.0) -> None:
        """Stop accepting, flush everything already admitted, stop the
        threads, write the final metrics record.  Idempotent."""
        if self._close_done:
            return
        self._close_done = True
        self._closed = True
        self._stop.set()
        # The sentinel bypasses the admission bound (put_sentinel), so a
        # full queue — or a dead collector behind one — can never block
        # close(); a dead collector's exit drain clears it regardless.
        self._q.put_sentinel(_CLOSE)
        self._collector.join(timeout=timeout)
        # A submit that passed the closed-check concurrently with this
        # close can enqueue AFTER the collector's exit drain — fail its
        # future rather than strand the caller (submit re-checks too).
        self._drain_with_exception(EngineClosed("engine closed"))
        if self._watcher is not None:
            self._watcher.join(timeout=timeout)
        try:
            # Same stance as the in-flush writes: a metrics I/O failure
            # (ENOSPC) degrades to a lost record, it must not turn an
            # otherwise-successful serve run into a nonzero exit.
            self.metrics.log_to(self._monitor)
        except (OSError, ValueError):
            pass
        finally:
            try:
                self._monitor.close()
            except (OSError, ValueError):
                pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def serve_lines(cfg: Config, lines=None, out=None, log=print) -> int:
    """The ``serve`` CLI verb: stream libsvm lines (default stdin) through
    a ServingEngine, writing one ``%.6f`` score per input line in input
    order — wire-compatible with predict's score file, but micro-batched
    through the online path.  A bounded future window keeps memory flat on
    arbitrarily long input; under serve_overload = reject the writer is
    its own load-shedder (drains a result, retries) so file-fed serving
    never drops a line."""
    import sys
    from collections import deque

    lines = sys.stdin if lines is None else lines
    out = sys.stdout if out is None else out
    window: deque = deque()
    n = 0

    def write_next(block: bool = True) -> bool:
        """Pop-and-write the oldest future; False when it isn't done yet
        (non-blocking mode) or nothing is in flight."""
        nonlocal n
        if not window or (not block and not window[0].done()):
            return False
        out.write(f"{window.popleft().result():.6f}\n")
        n += 1
        return True

    with ServingEngine(cfg, log=log) as engine:
        cap = max(4 * engine.max_batch, 1024)
        for line in lines:
            line = line.strip()
            if not line:
                continue
            while True:
                try:
                    window.append(engine.submit_line(line))
                    break
                except OverloadError:
                    if not write_next():  # nothing of ours in flight:
                        time.sleep(engine.deadline_s or 0.001)
            # Opportunistic in-order drain: a LIVE stream (slow stdin
            # producer) must see each score as soon as it resolves, not
            # in cap-sized bursts at EOF.
            wrote = False
            while write_next(block=False):
                wrote = True
            while len(window) >= cap:  # bound memory on a fast producer
                wrote = write_next() or wrote
            if wrote:
                out.flush()
        while write_next():
            pass
        out.flush()
        snap = engine.metrics_snapshot()
    log(
        f"served {n} scores: occupancy {snap['batch_occupancy']}, "
        f"p50/p99 total {snap['total_ms'].get('p50')}/"
        f"{snap['total_ms'].get('p99')}ms, reloads {snap['reloads']}"
    )
    return 0
