"""Host-side pipeline concurrency: background prefetch.

The JAX-era replacement for the reference's TF queue runners
(`renyi533/fast_tffm` :: trainer module: filename/string queues with
cfg-driven thread and queue sizes): ``prefetch(it, depth)`` runs an
iterator in a daemon thread with a bounded queue so host parsing overlaps
device steps.  Parse-thread parallelism (the cfg ``thread_num``) lives
inside the C++ kernel's std::thread pool (csrc/libsvm_parser.cpp), not in
Python — a Python-side thread map cannot beat the GIL for the pure-Python
fallback parser and is redundant for the GIL-releasing native one.
"""

from __future__ import annotations

import queue
import threading
import time
from collections.abc import Iterable, Iterator

from fast_tffm_tpu.utils.tracing import span

__all__ = ["prefetch", "chunk", "grouped_pairs", "InputStream", "PrefetchError"]

_SENTINEL = object()


class PrefetchError(RuntimeError):
    """The prefetch producer thread failed (or died without signaling).

    The loud, NAMED form of an input-pipeline death: before this, a
    producer exception only surfaced after the queue's buffered items
    drained, and a producer that died without its sentinel (interpreter
    teardown, a kill landing mid-put) left the consumer blocked on
    ``q.get()`` forever — a wedge the stall watchdog could only report,
    not break.  The original exception rides as ``__cause__``."""


class InputStream:
    """An input iterator plus its InputStats (data/wire.py): the driver
    iterates it like the bare generator it wraps, drains ``.stats`` into
    kind=input metrics records at log points, and hands
    ``.queue_depth`` / ``.producer_alive`` to the telemetry stall
    watchdog (live occupancy + thread liveness — readable mid-stall,
    when the consumer loop itself is frozen)."""

    def __init__(self, it: Iterable, stats):
        self._it = it
        self.stats = stats

    def __iter__(self) -> Iterator:
        return iter(self._it)

    def queue_depth(self) -> int | None:
        return self.stats.queue_depth() if self.stats is not None else None

    def producer_alive(self) -> bool | None:
        """Liveness of the prefetch producer thread (None before the
        first iteration binds one) — the watchdog's 'is input-starved
        because the producer is DEAD' signal."""
        fn = getattr(self.stats, "producer_alive", None)
        return fn() if fn is not None else None

    def stream_idle(self) -> bool | None:
        """Whether a tail-following input stream is idle-polling a quiet
        append-only file (None for non-follow streams) — the watchdog's
        'input-starved (stream-idle)' signal (data/stream.py)."""
        fn = getattr(self.stats, "stream_idle", None)
        return fn() if fn is not None else None


def chunk(it: Iterable, k: int) -> Iterator[list]:
    """Group consecutive items into lists of length ``k`` (the final list
    may be shorter — the epoch-tail remainder).

    The step-fusion staging primitive (``steps_per_call``): composed UNDER
    ``prefetch`` by the input streams, the grouping — and any superbatch
    stacking mapped over it — runs inside the prefetch thread, overlapping
    the K-batch assembly with the consumer's fused-step dispatch.
    """
    if k < 1:
        raise ValueError(f"chunk size must be >= 1, got {k}")
    buf: list = []
    for item in it:
        buf.append(item)
        if len(buf) == k:
            yield buf
            buf = []
    if buf:
        yield buf


def grouped_pairs(pairs: Iterable, k: int) -> Iterator[tuple[list, list]]:
    """Group a ``(parsed, weights)`` stream into ``([parsed]*k, [w]*k)``
    lists — THE steps_per_call grouping rule, shared by every input
    stream builder (batch _stream and the online follow stream) so the
    superbatch pairing cannot diverge between them."""
    for items in chunk(pairs, k):
        yield [p for p, _ in items], [w for _, w in items]


def prefetch(it: Iterable, depth: int = 8, stats=None) -> Iterator:
    """Iterate ``it`` in a background thread, keeping ``depth`` items ready.

    ``stats`` (an object with ``on_queue_depth(int)``) samples the queue
    occupancy at every consumer pop — the overlap-efficiency signal the
    kind=input metrics records carry (depth ~0 = producer-bound, depth at
    the cap = consumer-bound) — and, with ``on_wait(seconds)``, is told how
    long each pop BLOCKED (the ``input.wait`` span; ``wait_ms`` of the
    same records: what the proxy above stands for).  The queue — and the producer THREAD —
    are also bound onto ``stats`` (``bind_queue`` / ``bind_producer``)
    so the telemetry watchdog can read the LIVE depth and the thread's
    liveness from its own thread while the consumer is wedged.

    Failure contract: a producer exception surfaces in the consumer as a
    ``PrefetchError`` (the original as ``__cause__``) naming the thread —
    a loud, attributable input-pipeline death instead of a wedge.  The
    consumer polls with a timeout, so even a producer that dies WITHOUT
    reaching its sentinel (interpreter teardown, a signal mid-put) is
    detected within ~1s rather than blocking ``q.get()`` forever."""
    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    if stats is not None and hasattr(stats, "bind_queue"):
        stats.bind_queue(q)
    err: list[BaseException] = []

    def worker():
        try:
            for item in it:
                q.put(item)
        except BaseException as e:  # propagate into the consumer
            err.append(e)
        finally:
            q.put(_SENTINEL)

    t = threading.Thread(target=worker, name="input-prefetch", daemon=True)
    if stats is not None and hasattr(stats, "bind_producer"):
        stats.bind_producer(t)
    t.start()

    def fail(reason: str):
        e = PrefetchError(
            f"input pipeline failed: prefetch producer thread "
            f"{t.name!r} {reason}"
        )
        e.__cause__ = err[0] if err else None
        return e

    on_wait = getattr(stats, "on_wait", None)
    need_sample = True
    while True:
        if stats is not None and need_sample:
            # ONE depth sample per consumer pop (the pre-pop occupancy
            # the overlap metric is defined over) — not one per 1s
            # timeout retry, which would flood the average with zeros
            # exactly when the producer is slow and skew the
            # producer-bound signal.
            stats.on_queue_depth(q.qsize())
            need_sample = False
        t_wait = time.perf_counter()
        try:
            with span("input.wait"):
                item = q.get(timeout=1.0)
        except queue.Empty:
            if on_wait is not None:
                on_wait(time.perf_counter() - t_wait)
            if not t.is_alive() and q.empty():
                # Died without its sentinel: the finally was never
                # reached (teardown/kill).  Without this check the
                # consumer blocks forever — the wedge this fixes.
                raise fail(
                    f"raised {err[0]!r}" if err else "died without signaling"
                )
            continue
        if on_wait is not None:
            on_wait(time.perf_counter() - t_wait)
        need_sample = True
        if item is _SENTINEL:
            if err:
                raise fail(f"raised {err[0]!r}")
            return
        yield item


