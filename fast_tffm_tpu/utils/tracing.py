"""Profiling, step annotation, and structured metrics.

The reference had no tracing beyond periodic loss prints (SURVEY.md §5:
TF-1.x RunMetadata existed but was never wired).  The TPU build makes the
profiler a config key away:

  * ``span(name, **ids)`` — one stage of the host program as a
    TraceAnnotation (input wait, the serving collector's stages, ...);
  * ``step_trace(name, step)`` — per-step TraceAnnotation so device steps
    line up with host timeline rows (the trace WINDOW itself is
    profiling.StepProfiler's: ``trace_dir`` / ``--profile-steps``);
  * ``MetricsLogger`` — optional JSONL sink for step metrics (loss,
    examples/sec, AUC) next to the stdout log, one object per line.

jax imports stay inside the profiler helpers: ``MetricsLogger`` is the
sink under telemetry.RunMonitor, whose module must be importable before
``import jax`` (the hang-exit watchdog contract — see telemetry.py).
"""

from __future__ import annotations

import json
import math
import os
import threading
import time

__all__ = ["span", "step_trace", "MetricsLogger"]


def _jsonsafe(v):
    """Non-finite floats become their string names ('nan'/'inf'/'-inf'):
    Python's json would emit bare NaN/Infinity tokens, which strict JSON
    readers (jq, JSON.parse) reject — and the records carrying them
    (anomaly losses, single-class validation AUCs) are exactly the ones
    an external dashboard most wants.  float(...) round-trips the
    strings for numeric consumers."""
    if isinstance(v, float) and not math.isfinite(v):
        return str(v)
    if isinstance(v, dict):
        return {k: _jsonsafe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonsafe(x) for x in v]
    return v


def span(name: str, **ids):
    """One stage of the program on the profiler's host plane.

    THE rule of the stage clocks: where a stage begins the program reads
    ``time.perf_counter()`` once, adds the stage's duration to a counter
    that a telemetry record it already writes carries as a flat field,
    and opens this annotation under the same name — so under any profiler
    session (``--profile-steps``, ``trace_dir``, the benchmark's
    ``--trace 1``) the stage lies on the same xplane, on the same clock,
    as the device ops it waited for or fed.  ``ids`` (ints, short strings)
    ride the event as stats.  Off a profiler session this is a C++ flag
    check: ~0.5 us, 0.8 with ids.

    Two names are not stages but time the host lost all at once, inside
    whichever stage was open (telemetry's host clock): ``host.gc`` over a
    full collection of Python's collector, on the thread that paid it, and
    ``host.freeze``, a marker where the clock thread woke late."""
    import jax

    return jax.profiler.TraceAnnotation(name, **ids)


def step_trace(name: str, step: int):
    """Annotate one train/eval step on the profiler timeline."""
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=step)


class MetricsLogger:
    """Append-only JSONL metrics sink (no-op when path is empty).

    Thread-safe: the telemetry watchdog and memory sampler write from
    their own threads concurrently with the driver loop's records, and
    two interleaved half-lines would corrupt the JSONL for every reader
    downstream (tools/report.py).
    """

    def __init__(self, path: str | None):
        self._f = None
        self._lock = threading.Lock()
        if path:
            dirpart = os.path.dirname(path)
            if dirpart:
                os.makedirs(dirpart, exist_ok=True)
            self._f = open(path, "a", buffering=1)

    @property
    def active(self) -> bool:
        return self._f is not None

    def log(self, **fields) -> None:
        if self._f is None:  # cheap no-op path; re-checked under the lock
            return
        fields.setdefault("ts", round(time.time(), 3))
        line = json.dumps(_jsonsafe(fields), allow_nan=False) + "\n"
        with self._lock:
            if self._f is None:
                return
            self._f.write(line)

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
