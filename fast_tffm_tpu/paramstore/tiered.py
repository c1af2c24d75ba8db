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
     compact range) and unpacks there as any batch does; the missed
     rows' table and accumulator values are read and shipped on a
     thread of their own (``TieredConverter``'s reader), one batch
     behind the resolve, as ONE flat float32 array that needs no unpack:
     the columns of ``[S, D + A]`` one after another, which is how the
     chip lays such rows out (along its 128 lanes), so the host
     transposes them itself, a block at a time, and the runtime copies
     the flat array as it is.  (A 2-D array crosses as fast, but the
     runtime re-lays it one ``(8, 128)`` tile at a time and a profiler
     records each tile: some 70,000 host events a transfer.)  Its row
     count is the miss count rounded up to an eighth of the staging
     capacity, so a step ships and stages about the rows it missed and a
     run compiles at most eight staging shapes, all in its first epoch.
  3. **stage + step** — two donated ``dynamic_update_slice`` drop the
     miss rows into the staging region, then the UNCHANGED jitted train
     step (trainer.train_step_body over the compact table with remapped
     ids) runs — the math is the resident path's math on the same
     values, which is why tiered-vs-resident losses pin bit-identical at
     overlapping vocab.
  4. **writeback** — the staging rows are fetched (one flat array, as
     they were shipped) as the step is dispatched, and copied to the
     host and written into the PENDING overlay (host RAM, ``_Overlay``)
     after the NEXT step is dispatched, so the host writes while the
     chip computes.
     Pending rows reach the cold store only at checkpoint boundaries,
     AFTER the boundary's npz (which carries the same rows) publishes —
     every store write is chain-replayable redo, so no update is ever
     lost to a crash (crash-consistency invariant 7, DESIGN).

Coherency: resolution happens in the prefetch thread against a
versioned snapshot of pending; if a writeback lands between a payload's
resolution and its dispatch for one of ITS miss ids, or the previous
step (whose rows are not written back yet) staged one of them, the
dispatch-side check — one gather of the payload's miss ids from the
id-to-last-writeback map, ``int32[V + 1]`` — writes that back and
re-reads just that payload's values (a counted ``restage``) — the fast path stays fully
producer-resolved, the slow path stays correct.  The hot tier absorbs
repeats by construction, so restages are rare exactly when the residency
policy is doing its job."""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

from fast_tffm_tpu.paramstore.residency import ResidencyMap
from fast_tffm_tpu.paramstore.store import ColdStore
from fast_tffm_tpu.utils.tracing import span

__all__ = ["TieredParamServer", "TieredBatch", "TieredConverter"]

# The staging capacity is shipped, staged and fetched in eighths: a step
# moves its misses rounded up to the next eighth.
STAGING_PARTS = 8
# Table bytes a checkpoint boundary's apply writes to the store between two
# chances of the writeback fault (resilience.maybe_writeback_fault).
APPLY_CHUNK_BYTES = 16 << 20
# The last-writeback version of an id staged by the last step and not
# written back yet: later than any version.
_IN_FLIGHT = np.iinfo(np.int32).max
# A gather of pending rows is split over up to this many threads, each at
# least _PART_ROWS rows: numpy's row gather releases the interpreter lock,
# and one thread alone mostly waits on memory.
_PARTS = 4
_PART_ROWS = 1 << 16
# Rows of the long axis a block of ``_transposed`` copies.
_T_BLOCK = 8192


def _in_parts(fn, n: int) -> None:
    """``fn(lo, hi)`` over contiguous slices of ``range(n)``, on threads."""
    k = max(1, min(_PARTS, os.cpu_count() or 1, n // _PART_ROWS))
    if k == 1:
        fn(0, n)
        return
    bounds = np.linspace(0, n, k + 1).astype(np.int64)
    with ThreadPoolExecutor(k) as pool:
        list(pool.map(fn, bounds[:-1], bounds[1:]))


def _transposed(a: np.ndarray) -> np.ndarray:
    """``a.T`` of a 2-D array, contiguous: copied a block of the long
    axis at a time, so that each block's reads and writes stay in cache,
    on threads (the staged rows' way to the chip and back)."""
    rows_long = a.shape[0] >= a.shape[1]
    out = np.empty(a.shape[::-1], a.dtype)

    def part(lo, hi):
        for s in range(lo, hi, _T_BLOCK):
            e = min(hi, s + _T_BLOCK)
            if rows_long:
                out[:, s:e] = a[s:e].T
            else:
                out[s:e] = a[:, s:e].T

    _in_parts(part, max(a.shape))
    return out


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
    miss_ids: np.ndarray  # [m] unique missed LOGICAL ids (host, sorted)
    # -> (the [S, D + A] table | accumulator rows on the device as flat
    # columns, S = staged_rows(m); the pending-overlay version they were
    # read at)
    staged: Future

    @property
    def ids(self):
        return self.batch.ids


class _TierStats:
    """Per-run tiering counters, drained into ``kind=tiering`` records at
    every log point (and totals onto kind=summary).  The three host spans
    ride the record as ms per step: ``tier.resolve`` and ``tier.read`` per
    batch the prefetch thread resolved (``steps``, which runs ahead of the
    loop by the queue's depth; a restage's read counts too),
    ``tier.writeback`` per writeback."""

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
        self.miss_rows = 0
        self.miss_bytes = 0
        self.wire_bytes = 0
        self.resolve_s = 0.0
        self.read_s = 0.0
        self.writebacks = 0
        self.writeback_rows = 0
        self.writeback_bytes = 0
        self.writeback_s = 0.0
        self.restages = 0
        self.apply_rows = 0
        self.apply_s = 0.0

    def note_resolve(self, res, wire_bytes, miss_bytes, resolve_s, steps):
        with self._lock:
            self.steps += steps
            self.hit_slots += res.hit_slots
            self.total_slots += res.total_slots
            self.miss_rows += int(res.miss_ids.size)
            self.miss_bytes += miss_bytes
            self.wire_bytes += wire_bytes
            self.resolve_s += resolve_s
            self.total_miss_rows += int(res.miss_ids.size)

    def note_read(self, seconds):
        with self._lock:
            self.read_s += seconds

    def note_writeback(self, rows, nbytes, seconds):
        with self._lock:
            self.writebacks += 1
            self.writeback_rows += rows
            self.writeback_bytes += nbytes
            self.writeback_s += seconds
            self.total_writeback_rows += rows

    def note_restage(self, seconds):
        with self._lock:
            self.restages += 1
            self.total_restages += 1
            self.read_s += seconds

    def note_apply(self, rows, seconds):
        with self._lock:
            self.apply_rows += rows
            self.apply_s += seconds

    def drain(self, pending_rows: int, hot_rows: int) -> dict:
        with self._lock:
            if not self.steps:
                return {}
            per_step = lambda s: round(1e3 * s / self.steps, 3)
            out = {
                "steps": self.steps,
                "hit_rate": round(self.hit_slots / max(1, self.total_slots), 4),
                "miss_rows": self.miss_rows,
                "miss_rows_per_step": round(self.miss_rows / self.steps, 1),
                "miss_bytes_per_step": int(self.miss_bytes / self.steps),
                "wire_bytes_per_step": int(self.wire_bytes / self.steps),
                "writeback_rows": self.writeback_rows,
                "resolve_ms": per_step(self.resolve_s),
                "read_ms": per_step(self.read_s),
                "writeback_ms": round(1e3 * self.writeback_s / max(1, self.writebacks), 3),
                "restages": self.restages,
                "pending_rows": pending_rows,
                "hot_rows": hot_rows,
                "apply_rows": self.apply_rows,
                "apply_ms": round(1e3 * self.apply_s, 3),
            }
            self._reset()
        return out


class _Overlay:
    """The pending writeback overlay: logical id -> its latest (table,
    accumulator) row, for rows the device no longer stages and the store
    does not hold yet.

    ``_where`` is an ``int32[V + 1]`` map, pool row + 1 per pending id and 0
    elsewhere (``np.zeros``: pages untouched by a pending id cost nothing);
    ``_pool`` is ``[cap, D + A]`` float32 rows and ``_ids`` their ids, the
    first ``n`` in use.  The pool doubles when full and is rebuilt at its
    first capacity when ``apply`` empties it.  Every operation is a few
    vectorized numpy calls over the ids it is given.

    One writer (the loop thread: ``write``, ``apply``), any readers.  A
    writer changes a pool row in place only for an id already pending,
    writes a new id's row before publishing it in ``_where``, and grows or
    compacts into a NEW array; a reader takes the map entries, the pool
    and the version under the lock and gathers outside it.  A row it
    gathers can therefore be torn only when a writeback newer than its
    version holds that id — exactly what ``TieredParamServer._stale``
    looks for, so a torn row is never staged."""

    def __init__(self, vocab: int, width: int, capacity: int):
        self._where = np.zeros(int(vocab) + 1, np.int32)
        self._width = int(width)
        self._cap0 = max(1024, int(capacity))
        self._pool = np.empty((self._cap0, self._width), np.float32)
        self._ids = np.empty(self._cap0, np.int64)
        self.n = 0
        self.version = 0
        self.lock = threading.Lock()

    def read_into(self, ids: np.ndarray, out: np.ndarray):
        """Write the pending rows of ``ids`` into their rows of ``out``
        [n, D + A]; returns (positions of the ids not pending, version)."""
        with self.lock:
            where = self._where.take(ids, mode="clip")
            pool, version = self._pool, self.version
        where -= 1
        found = np.flatnonzero(where >= 0)

        def part(lo, hi):
            out[found[lo:hi]] = pool.take(where[found[lo:hi]], axis=0)

        _in_parts(part, found.size)
        return np.flatnonzero(where < 0), version

    def write(self, ids: np.ndarray, rows: np.ndarray) -> int:
        """Record ``rows`` [n, D + A] as the latest of unique ``ids``;
        returns the new version."""
        where = self._where[ids]
        new = np.flatnonzero(where == 0)
        n0, n1 = self.n, self.n + new.size
        if n1 > self._pool.shape[0]:
            cap = self._pool.shape[0]
            while cap < n1:
                cap *= 2
            pool = np.empty((cap, self._width), np.float32)
            pool[:n0] = self._pool[:n0]
            pids = np.empty(cap, np.int64)
            pids[:n0] = self._ids[:n0]
            with self.lock:
                self._pool, self._ids = pool, pids
        where[new] = np.arange(n0 + 1, n1 + 1, dtype=np.int32)
        self._ids[n0:n1] = ids[new]
        self._pool[where - 1] = rows
        with self.lock:
            self._where[ids[new]] = where[new]
            self.n = n1
            self.version += 1
            return self.version

    def snapshot(self):
        """(ids [n] sorted, rows [n, D + A]) of every pending row."""
        with self.lock:
            ids = self._ids[: self.n].copy()
            rows = self._pool[: self.n].copy()
        order = np.argsort(ids, kind="stable")
        return ids[order], rows[order]

    def drop(self, ids: np.ndarray) -> None:
        """Forget pending ``ids`` (applied to the store) and compact the
        pool into a new array of the rows left."""
        at = self._where[ids] - 1
        keep = np.ones(self.n, bool)
        keep[at[at >= 0]] = False
        rest = np.flatnonzero(keep)
        cap = self._cap0
        while cap < rest.size:
            cap *= 2
        pool = np.empty((cap, self._width), np.float32)
        pool[: rest.size] = self._pool[rest]
        pids = np.empty(cap, np.int64)
        pids[: rest.size] = self._ids[rest]
        with self.lock:
            self._where[ids] = 0
            self._where[pids[: rest.size]] = np.arange(1, rest.size + 1, dtype=np.int32)
            self._pool, self._ids, self.n = pool, pids, int(rest.size)


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
        residency_policy: str = "",
    ):
        self.store = store
        self.residency = ResidencyMap(hot_ids, store.vocab)
        self.hot_rows = self.residency.hot_rows
        self.miss_rows = max(1, int(miss_rows))
        self.capacity = self.hot_rows + self.miss_rows
        self.model = model
        self.row_dim = int(model.row_dim)
        self.accum_width = store.accum_width
        self.init_accum = float(init_accum)
        self.stats = _TierStats()
        # Pending writeback overlay, versioned so producer-side resolution
        # can be checked for staleness at dispatch.
        self._overlay = _Overlay(store.vocab, self.row_dim + self.accum_width, self.miss_rows)
        # Per id, the version of the last writeback that held it, or
        # _IN_FLIGHT while the last step stages it (loop thread only).
        self._written_at = np.zeros(store.vocab + 1, np.int32)
        self._last_staged: np.ndarray | None = None  # the last step's misses, not written back yet
        self._fetched = None  # their staging rows, [S, D + A] on the device
        self._applies = 0
        self._jits_built = False
        self.residency_policy = residency_policy  # how the hot set was chosen

    def profile(self) -> dict:
        """The tiering's facts on the step's ``kind=profile`` record."""
        return {
            "hot_rows": self.hot_rows,
            "miss_rows": self.miss_rows,
            "residency": self.residency_policy,
        }

    def staged_rows(self, m: int) -> int:
        """Rows a step with ``m`` missed rows ships, stages and fetches:
        ``m`` rounded up to the next eighth of the staging capacity."""
        part = -(-self.miss_rows // STAGING_PARTS)
        return min(self.miss_rows, max(1, -(-int(m) // part)) * part)

    # -- device programs ---------------------------------------------------

    def _build_jits(self):
        if self._jits_built:
            return
        import jax
        from functools import partial

        h, m = self.hot_rows, self.miss_rows

        d = self.row_dim
        w = d + self.accum_width

        @partial(jax.jit, donate_argnums=(0,))
        @jax.named_scope("tier.stage")
        def stage(state, flat):
            cols = flat.reshape(w, -1)
            table = jax.lax.dynamic_update_slice(state.table, cols[:d].T, (h, 0))
            accum = jax.lax.dynamic_update_slice(
                state.table_opt.accum, cols[d:].T, (h, 0)
            )
            return state._replace(
                table=table, table_opt=state.table_opt._replace(accum=accum)
            )

        @partial(jax.jit, static_argnums=(1,))
        @jax.named_scope("tier.fetch")
        def fetch(state, rows: int):
            """The first ``rows`` staging slots as the columns of ``[rows,
            D + A]`` (the overlay's row layout), flat, one transfer."""
            import jax.numpy as jnp

            return jnp.concatenate(
                [state.table[h : h + rows], state.table_opt.accum[h : h + rows]],
                axis=1,
            ).T.reshape(-1)

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

    def _read_into(self, ids: np.ndarray, out: np.ndarray) -> int:
        """Fill ``out`` [n, D + A] with the latest rows of logical ``ids``
        (the pending overlay over the cold store); returns the overlay
        version they were read at."""
        cold, version = self._overlay.read_into(ids, out)
        if cold.size:
            # Only the rows the overlay does NOT cover touch the store —
            # a high-pending window would otherwise pay a discarded
            # memmap/lazy-init read per overlaid row.
            t, a = self.store.read_rows(ids[cold])
            out[cold, : self.row_dim], out[cold, self.row_dim :] = t, a
        return version

    def read_latest(self, ids: np.ndarray) -> tuple[np.ndarray, np.ndarray, int]:
        """(table rows, accum rows, version) for logical ``ids`` (repeats
        allowed) — the pending overlay over the cold store.  Thread-safe
        (called from the prefetch thread on the fast path, the loop thread
        on restage)."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        rows = np.empty((ids.size, self.row_dim + self.accum_width), np.float32)
        version = self._read_into(ids, rows)
        return rows[:, : self.row_dim], rows[:, self.row_dim :], version

    def read_staged(self, ids: np.ndarray) -> tuple[np.ndarray, int]:
        """(rows, version): ``read_latest`` of a payload's unique missed
        ``ids`` laid out as the device stages them, ``[staged_rows(n),
        D + A]``; the unused rows a zero row with the initial accumulator."""
        n = int(ids.size)
        rows = np.empty((self.staged_rows(n), self.row_dim + self.accum_width), np.float32)
        version = self._read_into(np.asarray(ids, np.int64), rows[:n])
        rows[n:, : self.row_dim] = 0.0
        rows[n:, self.row_dim :] = self.init_accum
        return rows, version

    @property
    def pending_rows(self) -> int:
        return self._overlay.n

    @property
    def _version(self) -> int:
        return self._overlay.version

    def flush_writeback(self, state) -> None:
        """Write the last step's staged rows into the pending overlay.
        Called once the NEXT step is dispatched (so the host writes while
        the device runs that one), before staging where a payload is
        stale, at every checkpoint boundary and before an evaluation
        (pending must name the latest value of every non-resident touched
        row).  The rows were fetched as that step was dispatched
        (``_fetch_staged``), so ``state`` — that step's or a later one —
        is not read."""
        del state
        ids = self._last_staged
        if ids is None or ids.size == 0:
            self._last_staged = self._fetched = None
            return
        t0 = time.perf_counter()
        n = int(ids.size)
        with span("tier.writeback", rows=n):
            cols = np.asarray(self._fetched).reshape(self.row_dim + self.accum_width, -1)
            self._written_at[ids] = self._overlay.write(ids, _transposed(cols[:, :n]))
        self._last_staged = self._fetched = None
        self.stats.note_writeback(
            n, n * 4 * (self.row_dim + self.accum_width),
            time.perf_counter() - t0,
        )

    def _fetch_staged(self, state, ids: np.ndarray) -> None:
        """Dispatch the fetch of the staging slots that ``ids`` (this step's
        misses) occupy in ``state``; the next step's writeback copies it to
        the host."""
        self._last_staged = ids
        if ids.size:
            self._written_at[ids] = _IN_FLIGHT
            self._fetched = self._fetch(state, self.staged_rows(ids.size))

    def _stale(self, miss_ids: np.ndarray, version: int) -> bool:
        """Whether a row a payload read at ``version`` may have changed
        since: one of its ``miss_ids`` is staged by the last step and not
        written back yet, or written back after that version."""
        return bool(miss_ids.size) and bool(
            (self._written_at[miss_ids] > version).any()
        )

    # -- step wrapping -----------------------------------------------------

    def wrap_step(self, inner_step):
        """The residency-aware step: stage this payload's miss rows
        (re-read fresh on a coherency miss), run the UNCHANGED inner jitted
        step on the remapped batch, then write the previous step's rows
        back while the device runs this one."""
        import jax

        self._build_jits()

        def step(state, tb: TieredBatch):
            staged, version = tb.staged.result()
            if self._stale(tb.miss_ids, version):
                # A writeback since resolution, or the previous step's
                # still on its way, holds one of this payload's rows:
                # write it, re-read the latest values (pending overlay)
                # and restage — correctness over the fast path.
                self.flush_writeback(state)
                t0 = time.perf_counter()
                with span("tier.read", restage=1):
                    rows, _ = self.read_staged(tb.miss_ids)
                staged = jax.device_put(_transposed(rows).reshape(-1))
                self.stats.note_restage(time.perf_counter() - t0)
            state = self._stage(state, staged)
            state, loss = inner_step(state, tb.batch)
            self.flush_writeback(state)
            self._fetch_staged(state, tb.miss_ids)
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
        ids, rows = self._overlay.snapshot()
        d = self.row_dim
        return ids, np.ascontiguousarray(rows[:, :d]), np.ascontiguousarray(rows[:, d:])

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
        chunk = max(1, APPLY_CHUNK_BYTES // max(1, self.row_dim * 4))
        for lo in range(0, n, chunk):
            hi = min(n, lo + chunk)
            self.store.write_rows(ids[lo:hi], t[lo:hi], a[lo:hi])
            if lo == 0:
                # The kill-during-eviction-writeback window: some
                # store pages dirty, the boundary not yet stamped.
                maybe_writeback_fault(self._applies)
        if not n:
            maybe_writeback_fault(self._applies)
        self.store.flush()
        self.store.set_applied(save_id)
        self._overlay.drop(ids)
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
    ids, pack the remapped batch on the packed wire, ship it and unpack it
    jitted; the missed rows are read through the pending overlay and
    shipped by the converter's reader thread, one payload behind, so that
    a batch's read overlaps the next one's resolve.  Mirrors
    WireConverter's accounting contract (last_nbytes / calls) so
    kind=input stays truthful.  ``close()`` stops the reader."""

    def __init__(self, server: TieredParamServer, spec):
        import jax

        from fast_tffm_tpu.data.wire import make_unpacker

        self.server = server
        self.spec = spec
        self._put = jax.device_put
        self._unpack = make_unpacker(spec)
        self._reader = ThreadPoolExecutor(1, thread_name_prefix="tier-read")
        self.uses_fields = server.model.uses_fields
        self.wire_capable = False  # _stream must NOT swap in WireConverter
        self.last_nbytes = 0
        self.calls = 0

    def close(self) -> None:
        self._reader.shutdown(wait=True, cancel_futures=True)

    def _read(self, miss_ids: np.ndarray):
        """(the staged rows on the device as flat columns, the version
        they were read at)."""
        t0 = time.perf_counter()
        with span("tier.read", rows=int(miss_ids.size)):
            rows, version = self.server.read_staged(miss_ids)
            staged = self._put(_transposed(rows).reshape(-1))
        self.server.stats.note_read(time.perf_counter() - t0)
        return staged, version

    def __call__(self, parsed, w) -> TieredBatch:
        from fast_tffm_tpu.data.wire import pack_batch, pack_superbatch

        srv = self.server
        seq = parsed if isinstance(parsed, list) else [parsed]
        t0 = time.perf_counter()
        with span("tier.resolve"):
            res = srv.residency.resolve([p.ids for p in seq], srv.miss_rows)
        t1 = time.perf_counter()
        staged = self._reader.submit(self._read, res.miss_ids)
        remapped = [_remap(p, r) for p, r in zip(seq, res.remapped)]
        if isinstance(parsed, list):
            wire = pack_superbatch(self.spec, remapped, w, verify_ids=False)
        else:
            ww = (
                np.ones((parsed.batch_size,), np.float32) if w is None else w
            )
            wire = pack_batch(self.spec, remapped[0], ww, verify_ids=False)
        b = self._unpack(self._put(wire))
        width = 4 * (srv.row_dim + srv.accum_width)
        nbytes = int(wire.nbytes) + srv.staged_rows(res.miss_ids.size) * width
        self.last_nbytes = nbytes
        self.calls += 1
        srv.stats.note_resolve(res, nbytes, int(res.miss_ids.size) * width, t1 - t0, len(seq))
        return TieredBatch(batch=b, miss_ids=res.miss_ids, staged=staged)
