"""Online serving subsystem: micro-batched, bucket-compiled inference,
replicated behind a socket front end.

The offline drivers (prediction.py) stream whole files; this package is
the low-latency ONLINE path the ROADMAP north star ("serves heavy traffic
from millions of users") asks for.

Pieces (DESIGN.md "Serving" + "Serving resilience"):

  * ``BucketLadder`` (buckets.py) — predict functions pre-compiled at a
    ladder of batch sizes; requests pad up to the nearest bucket so no
    request ever triggers a fresh XLA compile in steady state;
  * ``ServingEngine`` (engine.py) — micro-batching collector (flush on
    ``serve_max_batch`` or the ``serve_flush_deadline_ms`` timer),
    tiered admission (admission.py: shed-by-class eviction under
    overload), per-request deadlines shed before bucket padding, hot
    checkpoint reload with atomic swap between flushes;
  * ``ServingMetrics`` (metrics.py) — queue/compute latency histograms
    (p50/p95/p99, per client class), occupancy, shed/drop/reload
    counters, exported through the telemetry JSONL path;
  * the replicated tier (protocol.py, replica.py, router.py,
    frontend.py) — a TCP front end (`serve --port`) multiplexing onto N
    engine worker processes behind a health-checked router: failover
    with one bit-identical retry, bounded-backoff replica restart with
    MTTR telemetry, one checkpoint watcher fanning reloads to all
    replicas, typed wire errors (overloaded | deadline | bad_request |
    unavailable) — never a silently dropped connection.

``tools/loadgen.py`` drives either transport (in-process, or the socket
tier via --connect/--spawn) and emits a BENCH_SERVE JSON; ``tools/
chaos.py --serve`` kills/slows/corrupts replicas under live traffic and
pins the no-hung-client + bit-identical-scores acceptance.
"""

import importlib

# PEP 562 lazy exports (same pattern as the package root): the engine pulls
# in jax, but the jax-free members of this package — protocol.py,
# client.py, and through them chip_smoke.py's parent process — must be
# importable without it.  A process that holds no chip should not load the
# library that takes one.
_EXPORTS = {
    "AdmissionQueue": "fast_tffm_tpu.serving.admission",
    "BadRequest": "fast_tffm_tpu.serving.protocol",
    "BucketLadder": "fast_tffm_tpu.serving.buckets",
    "DeadlineExceeded": "fast_tffm_tpu.serving.engine",
    "EngineClosed": "fast_tffm_tpu.serving.engine",
    "LatencyHistogram": "fast_tffm_tpu.serving.metrics",
    "Overloaded": "fast_tffm_tpu.serving.protocol",
    "OverloadError": "fast_tffm_tpu.serving.engine",
    "ServingEngine": "fast_tffm_tpu.serving.engine",
    "ServingMetrics": "fast_tffm_tpu.serving.metrics",
    "Unavailable": "fast_tffm_tpu.serving.protocol",
    "WireError": "fast_tffm_tpu.serving.protocol",
    "serve_lines": "fast_tffm_tpu.serving.engine",
    "validate_buckets": "fast_tffm_tpu.serving.buckets",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is not None:
        value = getattr(importlib.import_module(mod), name)
    else:
        # Submodules used to be bound by the eager imports
        # (`serving.engine.X`-style access) — keep that working lazily.
        try:
            value = importlib.import_module(f"{__name__}.{name}")
        except ModuleNotFoundError as e:
            if e.name != f"{__name__}.{name}":
                raise  # the submodule EXISTS but one of its deps is missing
            raise AttributeError(
                f"module {__name__!r} has no attribute {name!r}"
            ) from None
    globals()[name] = value  # cache: resolve each name once
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
