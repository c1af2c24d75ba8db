"""Serving-side metrics: latency histograms, occupancy, reload counters.

Online latency is a distribution, not a mean — an overloaded collector
shows up at p99 long before it moves the average.  ``LatencyHistogram``
keeps fixed log-spaced bins (O(bins) memory for any request count, the
same bounded-memory stance as metrics.StreamingAUC) and interpolates
quantiles inside the hit bin; ``ServingMetrics`` aggregates the per-stage
histograms plus the engine's counters and renders one flat JSONL record
for utils.tracing.MetricsLogger.
"""

from __future__ import annotations

import math
import threading

import numpy as np

__all__ = ["LatencyHistogram", "ServingMetrics"]


class LatencyHistogram:
    """Fixed log-spaced latency histogram with interpolated quantiles.

    Bins span [lo, hi) seconds geometrically (default 10µs..100s, 120
    bins → ~13% resolution per bin, tighter than any SLO anyone sets);
    samples outside clamp to the edge bins, and exact min/max/sum ride
    along so the snapshot never lies about the tails' extremes.
    """

    def __init__(self, lo: float = 1e-5, hi: float = 100.0, bins: int = 120):
        if not (0 < lo < hi) or bins < 2:
            raise ValueError(f"bad histogram spec lo={lo} hi={hi} bins={bins}")
        self._edges = np.geomspace(lo, hi, bins + 1)
        self._lo = lo
        self._bins_per_log = bins / math.log(hi / lo)
        self._counts = [0] * bins  # a list: adds are per flush, reads are rare
        self._n = 0
        self._sum = 0.0
        self._min = float("inf")
        self._max = 0.0

    def add(self, seconds: float) -> None:
        self.add_many(seconds, 1)

    def add_many(self, seconds: float, k: int) -> None:
        """``k`` samples of the same value in one bin update — how a
        whole-frame flush records its rows without k searchsorted calls."""
        if k <= 0:
            return
        # The bin by arithmetic, not by a search of the edges: a flush adds a
        # dozen samples on the collector's thread, and there a microsecond
        # is four of latency (PERF.md, PR 26).
        i = int(math.log(seconds / self._lo) * self._bins_per_log) if seconds > self._lo else 0
        self._counts[min(i, len(self._counts) - 1)] += k
        self._n += k
        self._sum += seconds * k
        self._min = min(self._min, seconds)
        self._max = max(self._max, seconds)

    @property
    def count(self) -> int:
        return self._n

    def _interpolated(self, counts: np.ndarray, n: int, q: float) -> float:
        """Quantile ``q`` of ``n`` samples binned as ``counts``,
        log-interpolated inside the hit bin."""
        target = q * n
        cum = np.cumsum(counts)
        i = int(np.searchsorted(cum, target, side="left"))
        i = min(i, counts.size - 1)
        prev = float(cum[i - 1]) if i > 0 else 0.0
        inbin = float(counts[i])
        frac = (target - prev) / inbin if inbin > 0 else 0.0
        lo, hi = self._edges[i], self._edges[i + 1]
        return float(lo * (hi / lo) ** min(max(frac, 0.0), 1.0))

    def quantile(self, q: float) -> float:
        """Value at quantile ``q`` (log-interpolated inside the hit bin);
        nan when empty.  Clamped by the exact min/max so a one-sample
        histogram reports the sample, not its bin edge."""
        if self._n == 0:
            return float("nan")
        v = self._interpolated(self.counts(), self._n, q)
        return min(max(v, self._min), self._max)

    def counts(self) -> np.ndarray:
        """The bin counts as an array of their own: what ``quantile_since``
        differences."""
        return np.array(self._counts, np.int64)

    def quantile_since(self, prev_counts: np.ndarray, q: float) -> float:
        """Quantile ``q`` over the samples added since ``prev_counts`` was
        taken; nan when none were.  The exact min/max are cumulative, so
        here only the bin edges bound the answer (~13% a bin)."""
        counts = self.counts() - prev_counts
        n = int(counts.sum())
        if n == 0:
            return float("nan")
        return self._interpolated(counts, n, q)

    def snapshot(self) -> dict:
        """{count, mean, p50, p95, p99, max} in MILLISECONDS (the unit
        every serving dashboard speaks; raw seconds would misread 1000x)."""
        if self._n == 0:
            return {"count": 0}
        ms = 1e3
        return {
            "count": self._n,
            "mean": round(self._sum / self._n * ms, 3),
            "p50": round(self.quantile(0.50) * ms, 3),
            "p95": round(self.quantile(0.95) * ms, 3),
            "p99": round(self.quantile(0.99) * ms, 3),
            "max": round(self._max * ms, 3),
        }


class ServingMetrics:
    """Aggregate serving counters + per-stage latency histograms.

    Writers: ``submit`` callers (requests/rejected) and the collector
    thread (everything else) — one lock covers both; every op is O(1) so
    contention is noise next to a flush's device dispatch.

    Stages: ``queue`` (submit → flush start: micro-batching wait +
    deadline), ``compute`` (device dispatch → scores on host, whole
    flush), ``total`` (submit → future resolved, what a caller feels).

    Everything is cumulative from construction, and ``snapshot()`` only
    reads.  ``log_to()`` also differences: beside the counters it keeps
    their state at the previous record (``_mark``), and each record adds
    flat fields over the interval since — the window's own p50s, the
    stage clocks of engine.py and replica.py (``serve.*`` spans, same
    names on the profiler's host plane) and the collector's busy share.
    An interval opens at the first flush after a record and ends at the
    newest flush, so idle time before, between and after traffic is in
    no interval and a record with no flush behind it carries no fields.
    """

    STAGES = ("assemble", "dispatch", "fetch", "reply")

    def __init__(self):
        self._lock = threading.Lock()
        self.queue = LatencyHistogram()
        self.compute = LatencyHistogram()
        self.total = LatencyHistogram()
        self.requests = 0
        self.rejected = 0
        self.flushes = 0
        self.deadline_drops = 0  # requests shed at flush because their OWN
        #   deadline expired before scoring (pre-padding; typed `deadline`)
        self.drops_by_class: dict[str, int] = {}  # deadline drops per class
        self.sheds_by_class: dict[str, int] = {}  # overload sheds per class:
        #   submit-side rejects AND tiered evictions (typed `overloaded`)
        self.evicted = 0  # queued requests evicted by a higher-class arrival
        #   (a subset of the sheds — says tiering, not just pressure, fired)
        self.class_total: dict[str, LatencyHistogram] = {}  # per-class
        #   submit→resolved latency (the per-class p50/p99 the SLO gate reads)
        self.flushes_deadline = 0  # timer fired before max_batch filled
        self.flushes_full = 0  # max_batch filled before the timer
        self.rows = 0  # real rows scored (excl. bucket padding)
        self.padded_rows = 0  # bucket-padding rows scored and discarded
        self.reloads = 0  # FULL checkpoint re-reads swapped in
        self.reload_failures = 0  # watcher restore attempts that raised
        self.reload_giveups = 0  # checkpoint signatures abandoned after
        #   reload_max_retries consecutive failures (a persistently corrupt
        #   file; the watcher stops retrying it until a NEW write lands)
        self.delta_reloads = 0  # delta FILES applied in place (a delta
        #   swap does NOT also bump `reloads` — the counters are disjoint)
        self.bucket_rows: dict[int, int] = {}  # bucket size -> real rows
        self.bucket_padded: dict[int, int] = {}  # bucket size -> padding
        #   rows (per-bucket occupancy = rows / (rows + padded): WHERE the
        #   padding waste lives, not just that it exists)
        # Freshness SLO distributions (ISSUE 9): one sample per reload
        # swap — checkpoint publish → state applied (collector swap) and
        # publish → first score resolved against the new state.  Wall
        # clocks on both ends (the publisher stamps, this process reads),
        # so cross-host skew is the documented error bar.
        self.fresh_applied = LatencyHistogram()
        self.fresh_scored = LatencyHistogram()
        # Stage clocks: seconds summed where the work happens.
        self.frames = 0  # REQUEST frames a replica reader took in
        self.frame_in_s = 0.0  # header read → submit_block returned
        self.stage_s = [0.0] * len(self.STAGES)  # per flush, STAGES order
        # The open interval: its first flush's start, its newest flush's
        # end, and the collector's q.get waits between those two.
        self._open_t = None
        self._last_t = 0.0
        self._wait_s = 0.0
        self._mark = self._totals()

    def _totals(self) -> dict:
        """What ``log_to`` differences (caller holds the lock, or is
        ``__init__``)."""
        return {
            "flushes": self.flushes,
            "flushes_deadline": self.flushes_deadline,
            "frames": self.frames,
            "frame_in_s": self.frame_in_s,
            "stage_s": tuple(self.stage_s),
            "queue": self.queue.counts(),
            "compute": self.compute.counts(),
            "total": self.total.counts(),
        }

    @staticmethod
    def _class_key(klass: str) -> str:
        return klass or "default"

    def on_submit(self, accepted: bool, klass: str = "") -> None:
        with self._lock:
            self.requests += 1
            if not accepted:
                self.rejected += 1
                k = self._class_key(klass)
                self.sheds_by_class[k] = self.sheds_by_class.get(k, 0) + 1

    def on_submit_many(self, n: int, accepted: bool, klasses=None) -> None:
        """A whole frame admitted (or rejected) as one unit still counts
        as its n requests — QPS math must not depend on the wire."""
        with self._lock:
            self.requests += n
            if not accepted:
                self.rejected += n
                for klass in klasses if klasses is not None else [""] * n:
                    k = self._class_key(klass)
                    self.sheds_by_class[k] = self.sheds_by_class.get(k, 0) + 1

    def on_evict(self, klass: str = "") -> None:
        """A QUEUED request was shed to admit a higher-class arrival."""
        with self._lock:
            self.evicted += 1
            k = self._class_key(klass)
            self.sheds_by_class[k] = self.sheds_by_class.get(k, 0) + 1

    def on_deadline_drop(self, klass: str = "") -> None:
        """A request's own deadline expired before scoring — shed at the
        flush, BEFORE it could pad a bucket."""
        with self._lock:
            self.deadline_drops += 1
            k = self._class_key(klass)
            self.drops_by_class[k] = self.drops_by_class.get(k, 0) + 1

    def on_flush(
        self,
        bucket: int,
        n_rows: int,
        queue_waits: list[float],
        compute_s: float,
        total_s: list[float],
        deadline_fired: bool,
        classes: list[str] | None = None,
        counts: list[int] | None = None,
        *,
        t_start: float,
        t_resolved: float,
        stages: tuple[float, ...],
        wait_s: float,
    ) -> None:
        """``queue_waits``/``total_s``/``classes`` are parallel per-GROUP
        lists; ``counts[i]`` is how many rows share entry i (a whole
        frame's rows enter as one group — None = every group is 1 row,
        the per-request path).  ``stages`` are this flush's seconds in
        STAGES order (they tile ``t_start`` → ``t_resolved``); ``wait_s``
        is what the collector spent blocked in ``q.get`` since the
        previous flush."""
        with self._lock:
            if self._open_t is None:
                self._open_t = t_start  # the wait before it is idle time
            else:
                self._wait_s += wait_s
            self._last_t = t_resolved
            for i, s in enumerate(stages):
                self.stage_s[i] += s
            self.flushes += 1
            if deadline_fired:
                self.flushes_deadline += 1
            else:
                self.flushes_full += 1
            self.rows += n_rows
            self.padded_rows += bucket - n_rows
            self.bucket_rows[bucket] = self.bucket_rows.get(bucket, 0) + n_rows
            self.bucket_padded[bucket] = self.bucket_padded.get(bucket, 0) + (
                bucket - n_rows
            )
            self.compute.add(compute_s)
            if counts is None:
                counts = [1] * len(total_s)
            for w, c in zip(queue_waits, counts):
                self.queue.add_many(w, c)
            for i, t in enumerate(total_s):
                c = counts[i]
                self.total.add_many(t, c)
                if classes is not None:
                    k = self._class_key(classes[i])
                    h = self.class_total.get(k)
                    if h is None:
                        h = self.class_total[k] = LatencyHistogram()
                    h.add_many(t, c)

    def on_frame_in(self, seconds: float) -> None:
        """One REQUEST frame taken in by a replica reader thread (header
        read → ``submit_block`` returned, admitted or not)."""
        with self._lock:
            self.frames += 1
            self.frame_in_s += seconds

    def interval_age(self, now: float) -> float:
        """Seconds since the open interval's first flush began; 0.0 when
        no flush has followed the previous record."""
        t = self._open_t
        return 0.0 if t is None else now - t

    def on_reload(self, ok: bool) -> None:
        with self._lock:
            if ok:
                self.reloads += 1
            else:
                self.reload_failures += 1

    def on_reload_giveup(self) -> None:
        with self._lock:
            self.reload_giveups += 1

    def on_freshness(self, applied_s: float, scored_s: float) -> None:
        """One reload swap's freshness pair (seconds since publish)."""
        with self._lock:
            self.fresh_applied.add(max(0.0, applied_s))
            self.fresh_scored.add(max(0.0, scored_s))

    def on_delta_reload(self, n_deltas: int) -> None:
        """The watcher applied ``n_deltas`` incremental checkpoint files in
        place (no full-table re-read) — counted separately from full
        reloads so a dashboard can see the cheap path is the one firing."""
        with self._lock:
            self.delta_reloads += n_deltas

    def snapshot(self) -> dict:
        """One flat dict (JSONL-ready).  Latencies in ms, keyed per stage;
        occupancy in [0, 1]; bucket_rows keyed by stringified bucket size
        (JSON objects take string keys)."""
        with self._lock:
            scored = self.rows + self.padded_rows
            return {
                "requests": self.requests,
                "rejected": self.rejected,
                "deadline_drops": self.deadline_drops,
                "deadline_drops_by_class": dict(sorted(self.drops_by_class.items())),
                "sheds_by_class": dict(sorted(self.sheds_by_class.items())),
                "evicted": self.evicted,
                "class_total_ms": {
                    k: h.snapshot() for k, h in sorted(self.class_total.items())
                },
                "frames": self.frames,
                "flushes": self.flushes,
                "flushes_deadline": self.flushes_deadline,
                "flushes_full": self.flushes_full,
                "rows": self.rows,
                "padded_rows": self.padded_rows,
                "batch_occupancy": round(self.rows / scored, 4) if scored else None,
                "reloads": self.reloads,
                "reload_failures": self.reload_failures,
                "reload_giveups": self.reload_giveups,
                "delta_reloads": self.delta_reloads,
                "bucket_rows": {str(k): v for k, v in sorted(self.bucket_rows.items())},
                "bucket_padded_rows": {
                    str(k): v for k, v in sorted(self.bucket_padded.items())
                },
                "bucket_occupancy": {
                    str(k): round(
                        self.bucket_rows.get(k, 0)
                        / (self.bucket_rows.get(k, 0) + v),
                        4,
                    )
                    for k, v in sorted(self.bucket_padded.items())
                    if self.bucket_rows.get(k, 0) + v
                },
                "queue_ms": self.queue.snapshot(),
                "compute_ms": self.compute.snapshot(),
                "total_ms": self.total.snapshot(),
                "freshness_applied_ms": self.fresh_applied.snapshot(),
                "freshness_scored_ms": self.fresh_scored.snapshot(),
            }

    def _close_interval(self) -> dict:
        """The flat fields over the interval since the previous record
        ({} when no flush followed it), and the new mark."""
        with self._lock:
            cur = self._totals()
            prev, self._mark = self._mark, cur
            open_t, self._open_t = self._open_t, None
            wait_s, self._wait_s = self._wait_s, 0.0
            if open_t is None:
                return {}
            interval_s = self._last_t - open_t
            p50 = {
                f"{k}_ms_p50_interval": round(1e3 * h.quantile_since(prev[k], 0.5), 4)
                for k, h in (("queue", self.queue), ("compute", self.compute), ("total", self.total))
            }
        flushes = cur["flushes"] - prev["flushes"]
        frames = cur["frames"] - prev["frames"]
        mean_ms = lambda s, n: round(1e3 * s / n, 4) if n else None
        return {
            "interval_s": round(interval_s, 4),
            "interval_flushes": flushes,
            "interval_frames": frames,
            **p50,
            "frame_in_ms": mean_ms(cur["frame_in_s"] - prev["frame_in_s"], frames),
            **{
                f"{name}_ms": mean_ms(c - p, flushes)
                for name, c, p in zip(self.STAGES, cur["stage_s"], prev["stage_s"])
            },
            # The collector's time outside q.get: flushes AND their
            # bookkeeping (histograms, compile sentinel, this record).
            "collector_busy_share": (
                round(1.0 - wait_s / interval_s, 4) if interval_s > 0 else None
            ),
            "deadline_flush_share": round(
                (cur["flushes_deadline"] - prev["flushes_deadline"]) / flushes, 4
            ),
        }

    def log_to(self, sink) -> None:
        """Append the snapshot as a ``kind=serving`` record, with the
        interval's fields, and close the interval.  ``sink`` is
        a telemetry.RunMonitor (the engine's — records get the shared
        envelope) or, for bare callers, a utils.tracing.MetricsLogger
        (no-op logger ⇒ no-op here)."""
        snap = {**self.snapshot(), **self._close_interval()}
        # The host clock's fields over the same interval (a RunMonitor's;
        # a bare logger runs no clock and the record lacks them).
        drain = getattr(sink, "drain_host_clock", None)
        if drain is not None:
            snap.update(drain())
        emit = getattr(sink, "emit", None)
        if emit is not None:
            emit("serving", **snap)
        else:
            sink.log(kind="serving", **snap)
