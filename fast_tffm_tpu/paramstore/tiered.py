"""Two-tier parameter server: device-resident hot rows + host cold store.

The tentpole of ISSUE 12 (ROADMAP item 3): the device holds a COMPACT
``[C, D]`` table (C = hot_rows + miss_rows), the host holds the full
logical table (paramstore/store.py), and every (super)batch is resolved
ahead of dispatch:

  1. **resolve** (prefetch thread) — dedup the batch's logical ids,
     split hit/miss against the residency map (paramstore/residency.py),
     remap every id to a device slot: hot ids to their rank slot in
     ``[0, H)``, each unique missed id to a staging slot ``[H, C)``.
     Dedup-before-gather falls out here for free: the 0.291 dedup ratio
     measured on Zipf(1.1) ids (round 9's id statistics) means ~71% of
     would-be gather bytes never exist as wire or staging traffic.
  2. **ship** — the remapped batch packs onto the EXISTING packed wire
     (data/wire.py, spec'd at the capacity C so ids narrow to the
     compact range), and the missed rows' table+accumulator values ride
     the SAME coalesced buffer; one ``device_put``, one jitted unpack.
  3. **stage + step** — a donated ``dynamic_update_slice`` drops the
     miss rows into the staging region, then the UNCHANGED jitted train
     step (trainer.train_step_body over the compact table with remapped
     ids) runs — the math is the resident path's math on the same
     values, which is why tiered-vs-resident losses pin bit-identical at
     overlapping vocab.
  4. **writeback** (next dispatch) — the staging region's updated rows
     are fetched D2H and recorded in the PENDING overlay (host RAM).
     Pending rows reach the cold store only at checkpoint boundaries,
     AFTER the boundary's npz (which carries the same rows) publishes —
     every store write is chain-replayable redo, so no update is ever
     lost to a crash (crash-consistency invariant 7, DESIGN).

Coherency: resolution happens in the prefetch thread against a
versioned snapshot of pending; if a writeback lands between a payload's
resolution and its dispatch for one of ITS miss ids, the dispatch-side
check re-reads just that payload's values (a counted ``restage``) —
the fast path stays fully producer-resolved, the slow path stays
correct.  The hot tier absorbs repeats by construction, so restages are
rare exactly when the residency policy is doing its job."""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import NamedTuple

import numpy as np

from fast_tffm_tpu.paramstore.residency import ResidencyMap
from fast_tffm_tpu.paramstore.store import ColdStore

__all__ = ["TieredParamServer", "TieredBatch", "TieredConverter"]


class _RemappedParsed(NamedTuple):
    """ParsedBatch shim with remapped (local-slot) ids — what the packed
    wire packer consumes."""

    batch_size: int
    max_nnz: int
    labels: np.ndarray
    nnz: np.ndarray
    ids: np.ndarray
    vals: np.ndarray
    fields: np.ndarray


def _remap(parsed, local_ids: np.ndarray) -> _RemappedParsed:
    return _RemappedParsed(
        batch_size=parsed.batch_size,
        max_nnz=parsed.max_nnz,
        labels=parsed.labels,
        nnz=parsed.nnz,
        ids=local_ids,
        vals=parsed.vals,
        fields=parsed.fields,
    )


class TieredBatch(NamedTuple):
    """One resolved dispatch payload: the remapped device batch plus the
    staged miss rows and the host-side bookkeeping the step wrapper and
    the delta machinery need.  ``.ids`` mirrors Batch so the
    touched-row marker (AsyncCheckpointer.note_batch) works unchanged."""

    batch: object  # device Batch (remapped local ids), [K, B, ...] or [B, ...]
    miss_t: object  # [M, D] staged table rows (device)
    miss_a: object  # [M, A] staged accumulator rows (device)
    miss_ids: np.ndarray  # [m] unique missed LOGICAL ids (host, sorted)
    version: int  # pending-overlay version the values were read at

    @property
    def ids(self):
        return self.batch.ids


@functools.lru_cache(maxsize=None)
def _make_tiered_unpacker(spec, miss_rows: int, row_dim: int, accum_width: int):
    """Jitted ``unpack(buf) -> (Batch, miss_t, miss_a)`` for ONE combined
    uint8 buffer: ``[K*L batch wire section][M*D f32][M*A f32]``.  The
    batch section reuses the packed-wire unpacker verbatim; K is read
    off the buffer length (one compiled program per (K, L) shape —
    epoch-tail K' compiles once, priced as warmup like every tail)."""
    import jax
    import jax.numpy as jnp

    from fast_tffm_tpu.data.wire import make_unpacker

    inner = make_unpacker(spec)
    mt_bytes = miss_rows * row_dim * 4
    ma_bytes = miss_rows * accum_width * 4

    def as_f32(x, rows, cols):
        u8 = x.reshape(-1, 4).astype(jnp.uint32)
        u32 = (
            u8[:, 0]
            | (u8[:, 1] << 8)
            | (u8[:, 2] << 16)
            | (u8[:, 3] << 24)
        )
        return jax.lax.bitcast_convert_type(u32, jnp.float32).reshape(rows, cols)

    @functools.partial(jax.jit, static_argnums=(1,))
    def unpack(buf, k: int):
        total = buf.shape[0]
        batch_bytes = total - mt_bytes - ma_bytes
        bsec = jax.lax.slice_in_dim(buf, 0, batch_bytes, axis=0)
        if k > 0:  # superbatch: [K, L] -> Batch [K, B, ...]
            b = inner(bsec.reshape(k, batch_bytes // k))
        else:  # single batch: [L] -> Batch [B, ...]
            b = inner(bsec)
        mt = as_f32(
            jax.lax.slice_in_dim(buf, batch_bytes, batch_bytes + mt_bytes, axis=0),
            miss_rows, row_dim,
        )
        ma = as_f32(
            jax.lax.slice_in_dim(buf, batch_bytes + mt_bytes, total, axis=0),
            miss_rows, accum_width,
        )
        return b, mt, ma

    return unpack


class _TierStats:
    """Per-run tiering counters, drained into ``kind=tiering`` records at
    every log point (and totals onto kind=summary)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._reset()
        # Run totals (never reset).
        self.total_miss_rows = 0
        self.total_writeback_rows = 0
        self.total_restages = 0

    def _reset(self):
        self.steps = 0
        self.hit_slots = 0
        self.total_slots = 0
        self.unique_ids = 0
        self.miss_rows = 0
        self.miss_bytes = 0
        self.wire_bytes = 0
        self.resolve_s = 0.0
        self.writeback_rows = 0
        self.writeback_bytes = 0
        self.writeback_s = 0.0
        self.restages = 0
        self.apply_rows = 0
        self.apply_s = 0.0

    def note_resolve(self, res, wire_bytes, miss_bytes, seconds, steps):
        with self._lock:
            self.steps += steps
            self.hit_slots += res.hit_slots
            self.total_slots += res.total_slots
            self.unique_ids += res.unique_ids
            self.miss_rows += int(res.miss_ids.size)
            self.miss_bytes += miss_bytes
            self.wire_bytes += wire_bytes
            self.resolve_s += seconds
            self.total_miss_rows += int(res.miss_ids.size)

    def note_writeback(self, rows, nbytes, seconds):
        with self._lock:
            self.writeback_rows += rows
            self.writeback_bytes += nbytes
            self.writeback_s += seconds
            self.total_writeback_rows += rows

    def note_restage(self):
        with self._lock:
            self.restages += 1
            self.total_restages += 1

    def note_apply(self, rows, seconds):
        with self._lock:
            self.apply_rows += rows
            self.apply_s += seconds

    def drain(self, pending_rows: int, hot_rows: int) -> dict:
        with self._lock:
            if not self.steps:
                return {}
            out = {
                "hit_rate": round(self.hit_slots / max(1, self.total_slots), 4),
                "miss_rows": self.miss_rows,
                "miss_rows_per_step": round(self.miss_rows / self.steps, 1),
                "miss_bytes_per_step": int(self.miss_bytes / self.steps),
                "wire_bytes_per_step": int(self.wire_bytes / self.steps),
                "dedup_ratio": round(
                    self.unique_ids / max(1, self.total_slots), 4
                ),
                "writeback_rows": self.writeback_rows,
                "writeback_ms": round(1e3 * self.writeback_s, 3),
                "resolve_ms": round(1e3 * self.resolve_s, 3),
                "restages": self.restages,
                "pending_rows": pending_rows,
                "hot_rows": hot_rows,
                "apply_rows": self.apply_rows,
                "apply_ms": round(1e3 * self.apply_s, 3),
            }
            self._reset()
        return out


class TieredParamServer:
    """Owns one run's residency map, cold store, pending overlay, and the
    device staging/fetch programs (see module docstring)."""

    def __init__(
        self,
        store: ColdStore,
        hot_ids: np.ndarray,
        miss_rows: int,
        model,
        *,
        init_accum: float,
    ):
        self.store = store
        self.residency = ResidencyMap(hot_ids)
        self.hot_rows = self.residency.hot_rows
        self.miss_rows = max(1, int(miss_rows))
        self.capacity = self.hot_rows + self.miss_rows
        self.model = model
        self.row_dim = int(model.row_dim)
        self.accum_width = store.accum_width
        self.init_accum = float(init_accum)
        self.stats = _TierStats()
        # Pending writeback overlay: logical id -> (table row, accum row)
        # host arrays; versioned so producer-side resolution can be
        # checked for staleness at dispatch.  _recent keeps the last few
        # writeback id-sets for that check (older payloads restage
        # conservatively — the queue depth bounds how old one can be).
        self._pending: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        self._version = 0
        self._recent: deque = deque(maxlen=64)
        self._lock = threading.Lock()
        self._last_staged: np.ndarray | None = None
        self._applies = 0
        self._jits_built = False

    # -- device programs ---------------------------------------------------

    def _build_jits(self):
        if self._jits_built:
            return
        import jax
        from functools import partial

        h, m = self.hot_rows, self.miss_rows

        @partial(jax.jit, donate_argnums=(0,))
        def stage(state, mt, ma):
            table = jax.lax.dynamic_update_slice(state.table, mt, (h, 0))
            accum = jax.lax.dynamic_update_slice(
                state.table_opt.accum, ma, (h, 0)
            )
            return state._replace(
                table=table, table_opt=state.table_opt._replace(accum=accum)
            )

        @jax.jit
        def fetch(state):
            return state.table[h : h + m], state.table_opt.accum[h : h + m]

        @jax.jit
        def hot_slice(state):
            return state.table[:h], state.table_opt.accum[:h]

        model = self.model

        @jax.jit
        def predict(state, batch, mt):
            import jax.numpy as jnp

            ids = batch.ids
            hot_g = state.table[jnp.minimum(ids, max(0, h - 1))]
            miss_g = mt[jnp.clip(ids - h, 0, m - 1)]
            rows = jnp.where((ids < h)[..., None], hot_g, miss_g)
            return jax.nn.sigmoid(model.score(rows, state.dense, batch))

        self._stage, self._fetch = stage, fetch
        self._hot_slice, self._predict_jit = hot_slice, predict
        self._jits_built = True

    # -- pending overlay ---------------------------------------------------

    def read_latest(self, ids: np.ndarray) -> tuple[np.ndarray, np.ndarray, int]:
        """(table rows, accum rows, version) for logical ``ids`` — the
        pending overlay over the cold store.  Thread-safe (called from
        the prefetch thread on the fast path, the loop thread on
        restage)."""
        ids = np.asarray(ids, np.int64)
        with self._lock:
            version = self._version
            hits = [self._pending.get(int(i)) for i in ids]
        cold = np.array([r is None for r in hits], bool)
        t = np.empty((ids.size, self.row_dim), np.float32)
        a = np.empty((ids.size, self.accum_width), np.float32)
        if cold.any():
            # Only the rows the overlay does NOT cover touch the store —
            # a high-pending window would otherwise pay a discarded
            # memmap/lazy-init read per overlaid row.
            t[cold], a[cold] = self.store.read_rows(ids[cold])
        for j, row in enumerate(hits):
            if row is not None:
                t[j], a[j] = row
        return t, a, version

    @property
    def pending_rows(self) -> int:
        with self._lock:
            return len(self._pending)

    def flush_writeback(self, state) -> None:
        """Fetch the previous dispatch's staged rows D2H into the pending
        overlay.  Called before every staging (the slots are about to be
        reused) and at every checkpoint boundary (pending must name the
        latest value of every non-resident touched row)."""
        ids = self._last_staged
        if ids is None or ids.size == 0:
            self._last_staged = None
            return
        self._build_jits()
        t0 = time.perf_counter()
        mt, ma = self._fetch(state)
        n = int(ids.size)
        mt = np.asarray(mt)[:n]
        ma = np.asarray(ma)[:n]
        with self._lock:
            self._version += 1
            for j, lid in enumerate(ids.tolist()):
                self._pending[lid] = (mt[j], ma[j])
            self._recent.append((self._version, ids))
        self._last_staged = None
        self.stats.note_writeback(
            n, n * 4 * (self.row_dim + self.accum_width),
            time.perf_counter() - t0,
        )

    def _stale(self, tb: TieredBatch) -> bool:
        if tb.miss_ids.size == 0:
            return False
        with self._lock:
            if tb.version == self._version:
                return False
            oldest = self._recent[0][0] if self._recent else self._version
            if tb.version < oldest - 1:
                return True  # too old to check precisely — be conservative
            newer = [ids for v, ids in self._recent if v > tb.version]
        for ids in newer:
            # Both sorted & unique — intersect cheaply.
            if np.intersect1d(tb.miss_ids, ids, assume_unique=True).size:
                return True
        return False

    # -- step wrapping -----------------------------------------------------

    def wrap_step(self, inner_step):
        """The residency-aware step: flush previous writeback, stage this
        payload's miss rows (re-read fresh on a coherency miss), run the
        UNCHANGED inner jitted step on the remapped batch."""
        import jax

        self._build_jits()

        def step(state, tb: TieredBatch):
            self.flush_writeback(state)
            mt, ma = tb.miss_t, tb.miss_a
            if self._stale(tb):
                # A writeback since resolution changed one of this
                # payload's rows: re-read the latest values (pending
                # overlay) and restage — correctness over the fast path.
                self.stats.note_restage()
                t, a, _ = self.read_latest(tb.miss_ids)
                mt = jax.device_put(_pad_rows(t, self.miss_rows))
                ma = jax.device_put(_pad_rows(a, self.miss_rows, self.init_accum))
            state = self._stage(state, mt, ma)
            state, loss = inner_step(state, tb.batch)
            self._last_staged = tb.miss_ids
            return state, loss

        if hasattr(inner_step, "lower"):
            # analysis: ok recompile-hazard delegated CostLedger .lower hook, not a second compile
            step.lower = lambda st, tb: inner_step.lower(st, tb.batch)
        return step

    def predict(self, state, parsed, w):
        """Residency-aware scoring for validation: resolve (read-only),
        gather hot rows from the live state and miss rows from a staged
        side buffer — no state mutation, no donation.  Call
        ``flush_writeback(state)`` once before an evaluation pass."""
        import jax

        from fast_tffm_tpu.models.base import Batch

        self._build_jits()
        res = self.residency.resolve([parsed.ids], self.miss_rows)
        t, _a, _v = self.read_latest(res.miss_ids)
        mt = jax.device_put(_pad_rows(t, self.miss_rows))
        b = Batch.from_parsed(
            _remap(parsed, res.remapped[0]), w,
            with_fields=self.model.uses_fields,
        )
        return self._predict_jit(state, b, mt)

    # -- checkpoint integration (called by AsyncCheckpointer) --------------

    def hot_logical_ids(self, slots: np.ndarray) -> np.ndarray:
        """Device slots (< hot_rows) -> logical ids."""
        return self.residency.hot_ids[np.asarray(slots, np.int64)]

    def pending_snapshot(self):
        """(ids [n], table rows [n, D], accum rows [n, A]) of the pending
        overlay, sorted by id — the cold half of every boundary save."""
        with self._lock:
            items = sorted(self._pending.items())
        if not items:
            return (
                np.zeros((0,), np.int64),
                np.zeros((0, self.row_dim), np.float32),
                np.zeros((0, self.accum_width), np.float32),
            )
        ids = np.array([i for i, _ in items], np.int64)
        t = np.stack([r[0] for _, r in items])
        a = np.stack([r[1] for _, r in items])
        return ids, t, a

    def apply_pending(self, save_id: str) -> None:
        """Post-publish apply: move the pending overlay into the cold
        store (redo the chain can replay) and stamp the boundary.  The
        chaos hook fires BETWEEN chunks — a kill here must leave the
        chain loadable with no lost or stale rows (test-pinned)."""
        from fast_tffm_tpu.resilience import maybe_writeback_fault

        t0 = time.perf_counter()
        ids, t, a = self.pending_snapshot()
        self._applies += 1
        n = int(ids.size)
        if n:
            chunk = max(1, (16 << 20) // max(1, self.row_dim * 4))
            first = True
            for lo in range(0, n, chunk):
                hi = min(n, lo + chunk)
                self.store.write_rows(ids[lo:hi], t[lo:hi], a[lo:hi])
                if first:
                    # The kill-during-eviction-writeback window: some
                    # store pages dirty, the boundary not yet stamped.
                    maybe_writeback_fault(self._applies)
                    first = False
            if first:
                maybe_writeback_fault(self._applies)
        else:
            maybe_writeback_fault(self._applies)
        self.store.flush()
        self.store.set_applied(save_id)
        with self._lock:
            for lid in ids.tolist():
                self._pending.pop(lid, None)
        self.stats.note_apply(n, time.perf_counter() - t0)

    def hot_rows_host(self, state) -> tuple[np.ndarray, np.ndarray]:
        """(hot table [H, D], hot accum [H, A]) fetched D2H — the hot half
        of a full boundary save."""
        self._build_jits()
        t, a = self._hot_slice(state)
        return np.asarray(t), np.asarray(a)

    def summary(self) -> dict:
        s = self.stats
        return {
            k: v
            for k, v in {
                "tier_miss_rows": s.total_miss_rows,
                "tier_writeback_rows": s.total_writeback_rows,
                "tier_restages": s.total_restages,
                "tier_pending_rows": self.pending_rows,
            }.items()
            if v
        }


def _pad_rows(rows: np.ndarray, cap: int, fill: float = 0.0) -> np.ndarray:
    out = np.full((cap, rows.shape[1]), np.float32(fill), np.float32)
    out[: rows.shape[0]] = rows
    return out


class TieredConverter:
    """``to_batch``-compatible resolver+shipper (prefetch thread): remap
    ids, read miss values through the pending overlay, pack the remapped
    batch on the packed wire WITH the miss rows in the same buffer, ship
    with ONE device_put, unpack jitted.  Mirrors WireConverter's
    accounting contract (last_nbytes / calls) so kind=input stays
    truthful."""

    def __init__(self, server: TieredParamServer, spec):
        import jax

        self.server = server
        self.spec = spec
        self._put = jax.device_put
        self._unpack = _make_tiered_unpacker(
            spec, server.miss_rows, server.row_dim, server.accum_width
        )
        self.uses_fields = server.model.uses_fields
        self.wire_capable = False  # _stream must NOT swap in WireConverter
        self.last_nbytes = 0
        self.calls = 0

    def __call__(self, parsed, w) -> TieredBatch:
        from fast_tffm_tpu.data.wire import pack_batch, pack_superbatch

        t0 = time.perf_counter()
        srv = self.server
        seq = parsed if isinstance(parsed, list) else [parsed]
        res = srv.residency.resolve([p.ids for p in seq], srv.miss_rows)
        t, a, version = srv.read_latest(res.miss_ids)
        mt = _pad_rows(t, srv.miss_rows)
        ma = _pad_rows(a, srv.miss_rows, srv.init_accum)
        remapped = [_remap(p, r) for p, r in zip(seq, res.remapped)]
        if isinstance(parsed, list):
            wire = pack_superbatch(
                self.spec, remapped, w, verify_ids=False
            ).reshape(-1)
            k = len(seq)
        else:
            ww = (
                np.ones((parsed.batch_size,), np.float32) if w is None else w
            )
            wire = pack_batch(self.spec, remapped[0], ww, verify_ids=False)
            k = 0
        buf = np.concatenate(
            [wire, mt.view(np.uint8).reshape(-1), ma.view(np.uint8).reshape(-1)]
        )
        b, mt_d, ma_d = self._unpack(self._put(buf), k)
        miss_bytes = int(res.miss_ids.size) * 4 * (srv.row_dim + srv.accum_width)
        self.last_nbytes = int(buf.nbytes)
        self.calls += 1
        srv.stats.note_resolve(
            res, int(buf.nbytes), miss_bytes, time.perf_counter() - t0, len(seq)
        )
        return TieredBatch(
            batch=b, miss_t=mt_d, miss_a=ma_d,
            miss_ids=res.miss_ids, version=version,
        )
