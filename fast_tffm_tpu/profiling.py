"""Deep observability: step-phase traces, measured cost ledger, id stats.

Three instruments, all emitting through the PR-4 ``RunMonitor`` envelope
(telemetry.SCHEMAS) rather than growing a second telemetry system:

  * **Step-phase trace capture** (``StepProfiler``) — on-demand
    ``jax.profiler`` traces over an exact step window (``[Telemetry]
    profile_steps = A:B`` / ``--profile-steps A:B``): the trace starts at
    the first dispatch completing step >= A and stops at the first
    completing step >= B (step-fused runs round to K-step boundaries —
    the dispatch grain, documented in DESIGN).  Start/stop land as
    ``kind=profile`` event records so a trace is joinable to its run.
  * **Measured cost ledger** (``CostLedger``) — per-compiled-program XLA
    cost analysis (bytes accessed, FLOPs) via ``Lowered.cost_analysis``:
    re-lowering an already-compiled jit at its abstract argument shapes
    costs one trace, NO second backend compile, and no hot-path work.
    Each program emits ONE ``kind=profile`` record carrying measured
    bytes next to the driver's *modeled* HBM floor, so DESIGN §8.5's
    "re-measure only with evidence" finally has the evidence column —
    tools/report.py renders measured-vs-modeled side by side and
    ``--compare --strict`` gates on measured bytes/example regression.
  * **Id-traffic statistics** (``DataStatsCollector``) — a jitted
    device-side reducer sampled every ``datastats_every_steps`` steps:
    per-batch unique-id count (the dedup-before-gather factor ROADMAP
    item 3 sizes against), dedup ratio (unique/slots), a top-K
    heavy-hitter frequency sketch over ``2^12`` hashed buckets
    (multiplicative hashing; collisions only OVERSTATE a bucket's mass,
    so the reported top-K mass is an upper bound on the true top-K id
    mass — the sketch's documented accuracy bound), and a cumulative
    rows-seen bitmap (hot-set coverage).  Padding slots (id 0) are
    counted on purpose: the gather reads them too, so they are real
    traffic — and they dedup to one row exactly as on device.

All three attribute their (rare, off-hot-path) XLA compiles as warmup
via ``RunMonitor.warmup_window`` — the zero-steady-state-recompiles pin
holds on every instrumented path.  Multi-host runs sample host-local ids
(each host's monitor stamps ``process_index``), so records are per-host
with no new collectives.
"""

from __future__ import annotations

import os
import time

import numpy as np

__all__ = [
    "parse_profile_steps",
    "StepProfiler",
    "abstractify",
    "program_cost",
    "CostLedger",
    "modeled_step_bytes",
    "DataStatsCollector",
]


def parse_profile_steps(spec: str) -> tuple[int, int] | None:
    """``"A:B"`` -> (A, B) with 0 <= A < B; ""/None -> None (disabled)."""
    if not spec:
        return None
    a, sep, b = str(spec).partition(":")
    try:
        if not sep:
            raise ValueError
        lo, hi = int(a), int(b)
        if lo < 0 or hi <= lo:
            raise ValueError
    except ValueError:
        raise ValueError(
            f"profile_steps must be 'A:B' with 0 <= A < B, got {spec!r}"
        ) from None
    return lo, hi


class StepProfiler:
    """Bounded jax.profiler trace over a step window (see module doc).

    ``on_step(step)`` is called once per completed dispatch with the
    post-dispatch step counter; it is a no-op (two comparisons) outside
    the window.  ``monitor`` (optional) gets ``kind=profile`` event
    records at start/stop; ``close()`` stops a still-open trace so a
    window past the run's end still yields a usable trace.
    """

    def __init__(self, spec: str, out_dir: str, *, monitor=None, log=None):
        self._range = parse_profile_steps(spec)
        self._dir = out_dir
        self._monitor = monitor
        self._log = log
        self._active = False
        self._done = self._range is None
        self._t0 = 0.0

    @property
    def enabled(self) -> bool:
        return self._range is not None

    def _emit(self, step: int, event: str, **extra) -> None:
        if self._monitor is None:
            return
        try:
            self._monitor.emit(
                "profile", step=step, program="trace", flops=None,
                bytes_accessed=None, event=event, trace_dir=self._dir, **extra,
            )
        except Exception:
            pass  # a full metrics disk must not kill the trace

    def on_step(self, step: int) -> None:
        if self._done:
            return
        lo, hi = self._range
        if not self._active and step >= lo:
            try:
                import jax

                os.makedirs(self._dir, exist_ok=True)
                jax.profiler.start_trace(self._dir)
            except Exception as e:
                self._done = True
                if self._log is not None:
                    self._log(f"profile trace failed to start: {e!r}")
                return
            self._active = True
            self._t0 = time.perf_counter()
            if self._log is not None:
                self._log(
                    f"profiling: trace started at step {step} -> {self._dir} "
                    f"(stops at step >= {hi})"
                )
            self._emit(step, "trace_start")
            # Never stop in the SAME call: a fused run whose K-step jump
            # spans the whole window must still capture >= one dispatch.
            return
        if self._active and step >= hi:
            self._stop(step)

    def _stop(self, step: int) -> None:
        self._active = False
        self._done = True
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:
            if self._log is not None:
                self._log(f"profile trace failed to stop cleanly: {e!r}")
            return
        dt = time.perf_counter() - self._t0
        if self._log is not None:
            self._log(
                f"profiling: trace stopped at step {step} "
                f"({dt:.2f}s captured) -> {self._dir}"
            )
        self._emit(step, "trace_stop", trace_s=round(dt, 3))

    def close(self, step: int = 0) -> None:
        if self._active:
            self._stop(step)


# -- measured cost ledger -------------------------------------------------


def abstractify(tree):
    """Pytree of ShapeDtypeStructs mirroring ``tree`` — captures the
    shapes of a dispatch's arguments WITHOUT holding the buffers (the
    train step donates its state; avals must be taken before the call)."""
    import jax

    def one(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sharding = getattr(x, "sharding", None)
            try:
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
            except Exception:
                return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return x

    return jax.tree.map(one, tree)


def program_cost(fn, args) -> dict | None:
    """XLA cost analysis for jitted ``fn`` at (abstract) ``args``:
    {"flops", "bytes_accessed", ...} or None when the runtime can't say.

    Uses ``fn.lower(...).cost_analysis()`` — tracing + StableHLO
    lowering only, NO second backend compile (verified: the compile
    sentinel sees nothing), so measuring a program costs one re-trace,
    once, off the hot path."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return None
    try:
        ca = lower(*args).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            return None
        out = {}
        flops = ca.get("flops")
        touched = ca.get("bytes accessed")
        out["flops"] = int(flops) if flops is not None else None
        out["bytes_accessed"] = int(touched) if touched is not None else None
        t = ca.get("transcendentals")
        if t is not None:
            out["transcendentals"] = int(t)
        return out
    except Exception:
        return None


def modeled_step_bytes(ids: np.ndarray, row_dim: int, accum_cols: int) -> tuple[int, int]:
    """LOWER-BOUND HBM bytes for ONE order-2 sparse train dispatch over
    host ``ids`` — the single-batch twin of bench.modeled_step_bytes
    (same itemization: ids read, gather, backward re-read, row-grad +
    segsum writes, unique-row table/accumulator RMW; dedup-sort passes
    and XLA temporaries excluded, so this is a floor).  Returns
    (modeled_bytes, unique_ids).  Packed/fused layouts move different
    physical bytes; the rows-equivalent floor is still the comparable
    "necessary traffic" number the measured column is read against
    (DESIGN "Profiling & data statistics")."""
    ids = np.asarray(ids)
    m = int(ids.size)
    uniq = int(np.unique(ids).size)
    row = int(row_dim) * 4
    total = (
        m * 4  # ids read
        + m * row  # forward gather
        + m * row  # backward re-read
        + m * row  # row-grad write
        + m * row  # segment-sum write
        + 2 * uniq * row  # table RMW over unique rows
        + 2 * uniq * int(accum_cols) * 4  # accumulator RMW
    )
    return int(total), uniq


class CostLedger:
    """One ``kind=profile`` record per distinct compiled program.

    Drivers ``stage()`` a program's (fn, args) — capturing abstract
    shapes BEFORE the dispatch donates the buffers — and ``flush()``
    after a dispatch completes: the lowering runs inside the monitor's
    warmup window (it compiles nothing, but any concurrent stats/unpack
    compile must not read as steady-state) and the record lands with
    measured bytes/FLOPs (null where the backend cannot analyse a
    lowering) next to whatever modeled floor the driver
    supplied.  Each name measures once per run; un-lowerable callables
    (driver closures that chose not to expose ``.lower``) are skipped
    silently — measurement is additive, never required."""

    def __init__(self, monitor, source: str = "train"):
        self._monitor = monitor
        self._source = source
        self._pending: dict[str, tuple] = {}
        self._done: set[str] = set()
        self.measured: dict[str, dict] = {}  # program -> emitted record body

    def want(self, name: str) -> bool:
        return name not in self._done and name not in self._pending

    def stage(
        self, name: str, fn, args, *, examples: int | None = None,
        modeled_bytes: int | None = None, **meta,
    ) -> None:
        """Queue ``name`` for measurement at the next flush().  ``args``
        may be live arrays (abstractified here) or ShapeDtypeStructs."""
        if name in self._done or name in self._pending:
            return
        if getattr(fn, "lower", None) is None:
            self._done.add(name)
            return
        try:
            absargs = abstractify(args)
        except Exception:
            self._done.add(name)
            return
        self._pending[name] = (fn, absargs, examples, modeled_bytes, meta)

    def flush(self, step: int = 0) -> None:
        """Measure + emit everything staged.  Call right after a dispatch
        (the program is compiled and the loop is between steps); no-op
        when nothing is pending."""
        if not self._pending:
            return
        pending, self._pending = self._pending, {}
        ctx = getattr(self._monitor, "warmup_window", None)
        import contextlib

        with (ctx() if ctx is not None else contextlib.nullcontext()):
            for name, (fn, absargs, examples, modeled, meta) in pending.items():
                self._done.add(name)
                # None where the backend has no cost analysis of a lowering
                # (the TPU's PJRT client): the record is written all the
                # same, measured fields null, so that what was dispatched
                # (examples, the modeled floor, the driver's ``meta``) is on
                # record on the chip too.
                cost = program_cost(fn, absargs) or {}
                body = dict(
                    program=name,
                    flops=cost.get("flops"),
                    bytes_accessed=cost.get("bytes_accessed"),
                    examples=examples,
                    bytes_per_example=(
                        round(cost["bytes_accessed"] / examples, 1)
                        if cost.get("bytes_accessed") is not None and examples
                        else None
                    ),
                    modeled_hbm_bytes=modeled,
                    **meta,
                )
                self.measured[name] = body
                try:
                    self._monitor.emit("profile", step=step, **body)
                except Exception:
                    pass  # a full metrics disk must not kill the driver

    def summary(self) -> dict:
        out = {"profile_programs": len(self.measured)}
        t = self.measured.get("train_step")
        if t and t.get("bytes_per_example") is not None:
            out["profile_train_bytes_per_example"] = t["bytes_per_example"]
        return out if self.measured else {}


# -- device-side id-traffic statistics ------------------------------------

_HH_BUCKETS = 1 << 12  # heavy-hitter sketch width (collisions overstate mass)
_HASH_MULT = np.uint32(2654435761)  # Knuth multiplicative hash


class DataStatsCollector:
    """Sampled id-traffic statistics (see module doc).

    ``note(step, parsed=parsed, batch=b)`` after every dispatch; at each
    ``every_steps`` boundary it runs the jitted reducer on THAT
    dispatch's ids (a sample — per-step accumulation would put an
    O(M log M) sort on every step) and emits one ``kind=datastats``
    record.  Ids come from ``parsed`` (streamed paths: the host-side
    ParsedBatch, or the K-list of a fused superbatch — per-host local
    rows on pods) or from ``ids_fn(batch)`` (device-cache paths: a
    jitted resident-array slicer).  The heavy-hitter bucket counts and
    the rows-seen bitmap accumulate across samples; unique/dedup are
    per-dispatch (the gather's own granularity).  Shuffled device-cache
    epochs sample the unpermuted slice — the id population over a window
    is identical, only the batch boundaries differ."""

    def __init__(
        self,
        monitor,
        *,
        vocab: int,
        row_dim: int,
        every_steps: int,
        heavy_hitter_k: int = 16,
        ids_fn=None,
    ):
        self._monitor = monitor
        self._vocab = int(vocab)
        self._row_bytes = int(row_dim) * 4
        self._every = int(every_steps)
        self._k = max(1, int(heavy_hitter_k))
        self._ids_fn = ids_fn
        self._last_step = None
        self._reduce = None
        self._bitmap = None
        self._counts = np.zeros((_HH_BUCKETS,), np.int64)
        self.samples = 0
        self.ids_total = 0
        self.unique_total = 0
        self.rows_seen = 0

    @property
    def enabled(self) -> bool:
        return self._every > 0

    def _build(self):
        import jax
        import jax.numpy as jnp
        from functools import partial

        shift = 32 - int(np.log2(_HH_BUCKETS))

        @partial(jax.jit, donate_argnums=(0,))
        def reduce(bitmap, ids):
            flat = ids.reshape(-1).astype(jnp.int32)
            s = jnp.sort(flat)
            uniq = jnp.asarray(1, jnp.int32) + (s[1:] != s[:-1]).sum(dtype=jnp.int32)
            h = ((flat.astype(jnp.uint32) * _HASH_MULT) >> shift).astype(jnp.int32)
            counts = jnp.zeros((_HH_BUCKETS,), jnp.int32).at[h].add(1)
            bitmap = bitmap.at[jnp.clip(flat, 0, bitmap.shape[0] - 1)].set(True)
            return bitmap, uniq, counts, bitmap.sum(dtype=jnp.int32)

        self._reduce = reduce
        self._bitmap = jnp.zeros((self._vocab,), bool)

    def _extract_ids(self, parsed, batch):
        if isinstance(parsed, list):
            return np.concatenate([np.asarray(p.ids) for p in parsed], axis=0)
        if parsed is not None and hasattr(parsed, "ids"):
            return np.asarray(parsed.ids)
        if self._ids_fn is not None:
            return self._ids_fn(batch)  # device array, already on-chip
        return None

    def note(self, step: int, parsed=None, batch=None) -> None:
        if self._every <= 0:
            return
        if self._last_step is None:
            self._last_step = int(step)  # arm at the first dispatch
            return
        if step - self._last_step < self._every:
            return
        window = int(step - self._last_step)
        self._last_step = int(step)
        ids = self._extract_ids(parsed, batch)
        if ids is None:
            return
        ctx = getattr(self._monitor, "warmup_window", None)
        import contextlib

        try:
            # The reducer compiles once per distinct ids shape (main +
            # epoch-tail); attribute those compiles — and nothing else on
            # the hot path — as warmup, like the serving reload programs.
            with (ctx() if ctx is not None else contextlib.nullcontext()):
                if self._reduce is None:
                    self._build()
                self._bitmap, uniq, counts, seen = self._reduce(self._bitmap, ids)
                uniq = int(uniq)
                counts = np.asarray(counts, np.int64)
                seen = int(seen)
        except Exception:
            return  # stats are additive; a reducer failure costs a sample
        n = int(ids.size)  # shape metadata only — never a device fetch
        self._counts += counts
        self.samples += 1
        self.ids_total += n
        self.unique_total += uniq
        self.rows_seen = seen
        top = np.sort(self._counts)[::-1][: self._k]
        hh_mass = float(top.sum() / max(1, self._counts.sum()))
        dedup = round(uniq / n, 4) if n else None
        try:
            self._monitor.emit(
                "datastats",
                step=step,
                window_steps=window,
                ids=n,
                unique=uniq,
                dedup_ratio=dedup,
                rows_seen=seen,
                rows_seen_frac=round(seen / self._vocab, 6) if self._vocab else None,
                hh_k=self._k,
                hh_topk_mass=round(hh_mass, 4),
                hh_top_counts=[int(x) for x in top[: min(self._k, 8)]],
                gather_bytes=n * self._row_bytes,
                dedup_gather_bytes=uniq * self._row_bytes,
                projected_gather_savings_frac=(
                    round(1.0 - uniq / n, 4) if n else None
                ),
            )
        except Exception:
            pass  # a full metrics disk must not kill the driver

    def summary(self) -> dict:
        if not self.samples:
            return {}
        top = np.sort(self._counts)[::-1][: self._k]
        return {
            "datastats_samples": self.samples,
            "datastats_dedup_ratio": round(
                self.unique_total / max(1, self.ids_total), 4
            ),
            "datastats_rows_seen": self.rows_seen,
            "datastats_hh_topk_mass": round(
                float(top.sum() / max(1, self._counts.sum())), 4
            ),
        }
