"""Packed wire format: one contiguous H2D buffer per (super)batch.

The streamed input path pays a host→device transfer per batch (what it
costs on a plain TPU host is ROADMAP S3 — not measured yet), and the
classic staging ships every batch as
five separate host arrays (labels/ids/vals/fields/weights — one
``device_put`` each).  This module cuts the wire two ways:

  * **coalescing** — every tensor of a (super)batch lands in ONE flat
    little-endian byte buffer, shipped with a single ``device_put``;
  * **elision** — tensors that are reconstructible on device are not
    shipped at all, and a jitted unpack (slice → byte-combine → bitcast
    → broadcast) rebuilds the exact ``Batch``:
      - ``vals`` when the stream is all-ones (the dominant CTR libsvm
        case, flagged per-file in the FMB v2 header): rebuilt as
        ``arange(N) < nnz`` — exactly the 1.0f/0.0f pattern the parser
        produced, so losses stay BIT-IDENTICAL;
      - ``fields`` for models that never read it (plain FM/DeepFM —
        the existing ``uses_fields`` rule, now saving wire bytes too);
      - ``weights`` when per-file example weights are uniform (1.0):
        rebuilt from a 4-byte per-batch real-row count (padding rows
        are always a weight-0 suffix);
      - ``ids`` ship at the minimal byte width for the vocabulary
        (3 bytes for a 2^24 Criteo-hash table instead of 4);
      - ``labels`` ship as one byte ({0, 1} is the parser contract) and
        ``nnz`` at the minimal width for ``max_nnz``.

Per micro-batch the flat layout is (all sections little-endian)::

    n_real   u32                1        weight-carrying row count
    labels   u8                 B
    nnz      u8|u16|u32         B        only when NOT with_vals (the
                                         elided-vals rebuild's input;
                                         dead bytes otherwise)
    weights  f32                B        only when with_weights
    ids      u8 x id_bytes      B*N
    vals     f32                B*N      only when with_vals
    fields   i32                B*N      only when with_fields

A superbatch is ``[K, L]`` (one such vector per micro-step); the
unpacker is shape-polymorphic over leading dims, so the same spec
serves K=1 batches, fused [K, B, ...] superbatches, and every serving
bucket.  Exactness is defensive, not assumed: the packer VERIFIES each
elision's reconstruction pattern against the host arrays and raises on
any mismatch, so a wrong per-file flag can never corrupt training.
"""

from __future__ import annotations

import functools
import sys
import time
from typing import NamedTuple

import numpy as np

from fast_tffm_tpu.utils.tracing import span

__all__ = [
    "WireSpec",
    "make_spec",
    "bytes_for",
    "vals_all_ones",
    "pack_batch",
    "pack_superbatch",
    "make_unpacker",
    "WireConverter",
    "arrays_nbytes",
]

# The packed wire assumes a little-endian host (every TPU/GPU host is).
# Checked in make_spec — the pack-path gate — NOT at import time: this
# module also carries InputStats and the convert-time detection helpers,
# which training.py/binary.py import regardless of wire_format, and a
# module-level raise would make the "set wire_format = arrays" escape
# hatch itself crash on a big-endian host.
_LITTLE_ENDIAN = sys.byteorder == "little"


class WireSpec(NamedTuple):
    """Static facts of one packed-wire stream (one XLA unpack per spec
    per shape).  Shape-free on purpose: B and K come off the buffer."""

    nnz: int  # N, the static feature width of every batch
    id_bytes: int  # 1..4, minimal LE width for vocabulary_size - 1
    nnz_bytes: int  # 1..4, minimal LE width for nnz
    with_vals: bool  # False = all-ones stream, vals rebuilt on device
    with_fields: bool  # False = model never reads fields (FM/DeepFM)
    with_weights: bool  # False = uniform file weights, rebuilt from n_real

    @property
    def with_nnz(self) -> bool:
        """The nnz section rides the wire ONLY when something on device
        reconstructs from it (the elided-vals rebuild) — explicit-vals
        wires would ship dead bytes."""
        return not self.with_vals

    @property
    def row_bytes(self) -> int:
        n = self.nnz
        return (
            1  # label u8
            + (self.nnz_bytes if self.with_nnz else 0)
            + (4 if self.with_weights else 0)
            + n * self.id_bytes
            + (4 * n if self.with_vals else 0)
            + (4 * n if self.with_fields else 0)
        )

    def batch_nbytes(self, batch_size: int) -> int:
        """Wire bytes of one micro-batch (the 4-byte n_real included)."""
        return 4 + batch_size * self.row_bytes


def bytes_for(maxval: int) -> int:
    """Minimal little-endian byte width holding ``maxval`` (1..4)."""
    for k in (1, 2, 3):
        if maxval < 1 << (8 * k):
            return k
    return 4


def make_spec(
    vocabulary_size: int,
    max_nnz: int,
    *,
    with_vals: bool,
    with_fields: bool,
    with_weights: bool = False,
) -> WireSpec:
    if not _LITTLE_ENDIAN:  # pragma: no cover - no BE hosts in practice
        raise ValueError(
            "the packed wire format assumes a little-endian host (all "
            "TPU/GPU hosts are); set wire_format = arrays on this platform"
        )
    return WireSpec(
        nnz=int(max_nnz),
        id_bytes=bytes_for(max(1, int(vocabulary_size) - 1)),
        nnz_bytes=bytes_for(max(1, int(max_nnz))),
        with_vals=bool(with_vals),
        with_fields=bool(with_fields),
        with_weights=bool(with_weights),
    )


def arrays_nbytes(batch_size: int, nnz: int, with_fields: bool) -> int:
    """H2D bytes the classic array staging ships for the same batch
    (labels f32 + ids i32 + vals f32 + weights f32 [+ fields i32]) —
    the packed format's comparison baseline."""
    per_row = 4 + 4 * nnz + 4 * nnz + 4 + (4 * nnz if with_fields else 0)
    return batch_size * per_row


def vals_all_ones(vals, nnz) -> bool:
    """True when ``vals`` is exactly the all-ones pattern its ``nnz``
    implies: 1.0 in the first nnz[i] slots of row i, 0.0 beyond.  The
    reconstruction-eligibility check shared by the FMB converter
    (header flag), the packer's defensive verify, and --stats."""
    vals = np.asarray(vals, np.float32)
    nnz = np.asarray(nnz).reshape(-1, 1)
    expect = (np.arange(vals.shape[1]) < nnz).astype(np.float32)
    return bool(np.array_equal(vals, expect))


def _narrow_uint(a, k: int) -> np.ndarray:
    """Integer array → its ``k`` low little-endian bytes per element."""
    a32 = np.ascontiguousarray(a, dtype="<u4")
    b = a32.view(np.uint8).reshape(*a32.shape, 4)
    return b if k == 4 else np.ascontiguousarray(b[..., :k])


def _pack_one(spec: WireSpec, out: np.ndarray, parsed, w, verify_ids=True) -> None:
    """Fill one micro-batch's flat byte vector ``out`` (len row math).

    ``verify_ids=False`` skips the id-range scan for callers whose rows
    were ALREADY range-validated at admission (the serving engine's
    submit paths) — everything else about the verified-never-trusted
    stance (labels, weights, vals) stays on."""
    b, n = parsed.batch_size, spec.nnz
    if parsed.max_nnz != n:
        raise ValueError(
            f"packed wire: batch width {parsed.max_nnz} != spec nnz {n}"
        )
    labels = np.asarray(parsed.labels, np.float32)
    w = np.asarray(w, np.float32)
    n_real = int(np.count_nonzero(w))
    o = 0

    def put(a):
        nonlocal o
        flat = np.ascontiguousarray(a).view(np.uint8).reshape(-1)
        out[o : o + flat.size] = flat
        o += flat.size

    if not spec.with_weights and not np.array_equal(
        w, (np.arange(b) < n_real).astype(np.float32)
    ):
        raise ValueError(
            "packed wire: example weights are not the uniform 1.0-prefix "
            "pattern this spec elides (non-uniform weight_files need "
            "with_weights=True)"
        )
    put(np.array([n_real], "<u4"))
    lab8 = labels.astype(np.uint8)
    if not np.array_equal(lab8.astype(np.float32), labels):
        raise ValueError(
            "packed wire: labels outside {0, 1} — the parser contract the "
            "1-byte label section relies on"
        )
    put(lab8)
    if spec.with_nnz:
        put(_narrow_uint(parsed.nnz, spec.nnz_bytes))
    if spec.with_weights:
        put(w)
    if verify_ids and spec.id_bytes < 4 and parsed.ids.size:
        # Same verified-never-trusted stance as the elided sections: a
        # spec built for a smaller vocabulary than the ids actually
        # present must raise, not silently truncate onto a DIFFERENT
        # valid row.  (id_bytes == 4 round-trips any int32 bitwise.)
        lo, hi = int(parsed.ids.min()), int(parsed.ids.max())
        if lo < 0 or hi >= 1 << (8 * spec.id_bytes):
            raise ValueError(
                f"packed wire: ids span [{lo}, {hi}] but the spec's "
                f"id_bytes={spec.id_bytes} only holds "
                f"[0, {1 << (8 * spec.id_bytes)}) — spec built for the "
                "wrong vocabulary_size?"
            )
    put(_narrow_uint(parsed.ids, spec.id_bytes))
    if spec.with_vals:
        put(np.asarray(parsed.vals, np.float32))
    elif not vals_all_ones(parsed.vals, parsed.nnz):
        # Elision is VERIFIED, never trusted: a stale per-file flag (file
        # swapped under a fresh-looking header) must fail loudly here, not
        # train on reconstructed garbage.
        raise ValueError(
            "packed wire: vals are not the all-ones pattern this spec "
            "elides — re-convert the file (tools/convert_dataset.py) or "
            "set wire_format = arrays"
        )
    if spec.with_fields:
        put(np.ascontiguousarray(parsed.fields, dtype="<i4"))
    if o != out.size:
        raise AssertionError(f"wire layout mismatch: wrote {o} of {out.size}")


def pack_batch(spec: WireSpec, parsed, w, verify_ids=True) -> np.ndarray:
    """One ParsedBatch → flat uint8 wire vector ``[L]``."""
    out = np.empty(spec.batch_nbytes(parsed.batch_size), np.uint8)
    _pack_one(spec, out, parsed, w, verify_ids)
    return out


def pack_superbatch(spec: WireSpec, parsed_seq, w_seq, verify_ids=True) -> np.ndarray:
    """K ParsedBatches → ``[K, L]`` wire matrix (one row per micro-step;
    the epoch-tail group is simply shorter in K)."""
    k = len(parsed_seq)
    b = parsed_seq[0].batch_size
    out = np.empty((k, spec.batch_nbytes(b)), np.uint8)
    if w_seq is None:
        w_seq = [None] * k
    for i, (p, w) in enumerate(zip(parsed_seq, w_seq)):
        _pack_one(
            spec, out[i], p,
            np.ones((b,), np.float32) if w is None else w, verify_ids,
        )
    return out


@functools.lru_cache(maxsize=None)
def make_unpacker(spec: WireSpec):
    """Jitted ``unpack(buf uint8[..., L]) -> Batch`` — the device-side
    reconstruction.  Leading dims pass through ([L] → [B, ...] batch,
    [K, L] → [K, B, ...] superbatch), so the scanned train step consumes
    the output exactly like Batch.stack_parsed's.  Every rebuild is
    bit-exact: f32 sections round-trip by bitcast, elided vals/weights
    rebuild the verified 1.0/0.0 patterns, labels come back from the
    {0, 1} bytes.

    Memoized per spec: drivers build one stream (and one WireConverter)
    PER EPOCH, and a fresh jit function per epoch would re-trace and
    XLA-recompile the same unpack program every time — the cache keys on
    the (hashable) spec so every epoch reuses the compiled programs."""
    import jax
    import jax.numpy as jnp

    from fast_tffm_tpu.models.base import Batch

    n = spec.nnz
    rb = spec.row_bytes

    def combine(x, k):
        # uint8 [..., m*k] -> uint32 [..., m], little-endian: byte i of every
        # word by one strided slice.  Written as a reshape to [..., m, k] the
        # combine is hoisted by XLA over the whole wire buffer, and with
        # k = 3 (ids of a table of up to 2^24 rows) the TPU compiler takes
        # minutes over it where m is a million (12.7 min for 32,768 x 39 ids
        # with fields).  On a v5e, both forms at 65,536 x 39 4-byte ids and
        # values: reshape 8.9 ms and 1.25 GiB of temporaries, slices 6.5 ms
        # and none; at the serving buckets 0.8 ms either way (PERF.md §6,
        # PR 29).
        planes = [
            jax.lax.slice_in_dim(x, i, x.shape[-1], stride=k, axis=-1)
            for i in range(k)
        ]
        out = planes[0].astype(jnp.uint32)
        for i in range(1, k):
            out = out | (planes[i].astype(jnp.uint32) << (8 * i))
        return out

    def as_f32(x):
        return jax.lax.bitcast_convert_type(combine(x, 4), jnp.float32)

    def as_i32(x, k):
        u = combine(x, k)
        if k == 4:  # a full word may carry a sign bit — bitcast, not cast
            return jax.lax.bitcast_convert_type(u, jnp.int32)
        return u.astype(jnp.int32)

    @jax.jit
    @jax.named_scope("score.unpack")
    def unpack(buf):
        *lead, length = buf.shape
        lead = tuple(lead)
        b = (length - 4) // rb
        o = 0

        def take(nbytes):
            nonlocal o
            s = jax.lax.slice_in_dim(buf, o, o + nbytes, axis=-1)
            o += nbytes
            return s

        n_real = combine(take(4), 4).reshape(lead)
        labels = take(b).astype(jnp.float32)
        if spec.with_nnz:
            nnz = as_i32(take(b * spec.nnz_bytes), spec.nnz_bytes)
        if spec.with_weights:
            weights = as_f32(take(4 * b))
        else:
            weights = (jnp.arange(b) < n_real[..., None]).astype(jnp.float32)
        ids = as_i32(take(b * n * spec.id_bytes), spec.id_bytes).reshape(
            *lead, b, n
        )
        if spec.with_vals:
            vals = as_f32(take(4 * b * n)).reshape(*lead, b, n)
        else:
            vals = (jnp.arange(n) < nnz[..., None]).astype(jnp.float32)
        if spec.with_fields:
            fields = as_i32(take(4 * b * n), 4).reshape(*lead, b, n)
        else:
            fields = jnp.zeros((*lead, b, 0), jnp.int32)
        return Batch(
            labels=labels, ids=ids, vals=vals, fields=fields, weights=weights
        )

    return unpack


class WireConverter:
    """``to_batch``-compatible packed-wire shipper: pack on host, ONE
    ``device_put``, jitted unpack.  Accepts a single ParsedBatch or the
    step-fusion K-list, mirroring training._batch_converter's contract.
    Per-call byte/time accounting feeds the kind=input metrics records.
    """

    def __init__(self, spec: WireSpec, verify_ids: bool = True):
        import jax

        self.spec = spec
        self.verify_ids = verify_ids
        self._put = jax.device_put
        self._unpack = make_unpacker(spec)
        self.last_nbytes = 0  # wire bytes of the most recent call
        self.wire_bytes = 0  # cumulative
        self.calls = 0

    def pack(self, parsed, w) -> np.ndarray:
        if isinstance(parsed, list):
            return pack_superbatch(self.spec, parsed, w, self.verify_ids)
        return pack_batch(
            self.spec,
            parsed,
            np.ones((parsed.batch_size,), np.float32) if w is None else w,
            self.verify_ids,
        )

    def __call__(self, parsed, w):
        buf = self.pack(parsed, w)
        self.last_nbytes = buf.nbytes
        self.wire_bytes += buf.nbytes
        self.calls += 1
        return self._unpack(self._put(buf))


class InputStats:
    """Per-stream input-path accounting: parse/convert wall time, wire
    bytes, prefetch-queue depth.  The producer (prefetch thread) updates
    under a lock; the driver drains a snapshot at every log point into a
    ``kind=input`` JSONL record — overlap efficiency becomes first-class
    telemetry instead of probe-only archaeology (ISSUE 3 satellite)."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self._queue = None  # bound by prefetch(); live-depth probe
        self._producer = None  # bound by prefetch(); liveness probe
        self._stream_idle = None  # bound by follow streams; idle probe
        self.last_depth = None  # most recent consumer-pop sample
        self._reset()

    def bind_queue(self, q) -> None:
        """prefetch() hands over its queue so ``queue_depth`` can read
        LIVE occupancy (the stall watchdog asks from another thread,
        exactly when the consumer has stopped sampling)."""
        self._queue = q

    def bind_producer(self, thread) -> None:
        """prefetch() hands over its producer thread so the stall
        watchdog can distinguish 'input-starved because the producer is
        slow' from 'input-starved because the producer is DEAD'."""
        self._producer = thread

    def bind_stream_idle(self, event) -> None:
        """Follow-mode streams (data/stream.py) hand over their idle
        Event so a starved loop can classify as
        'input-starved (stream-idle)': producer alive, upstream writer
        quiet — wait, don't restart."""
        self._stream_idle = event

    def stream_idle(self) -> bool | None:
        e = self._stream_idle
        return e.is_set() if e is not None else None

    def producer_alive(self) -> bool | None:
        t = self._producer
        return t.is_alive() if t is not None else None

    def queue_depth(self) -> int | None:
        q = self._queue
        if q is not None:
            try:
                return int(q.qsize())
            except Exception:
                pass
        return self.last_depth

    def _reset(self):
        self.items = 0  # queue items (superbatch = 1 item)
        self.converted = 0  # items whose conversion ran in the producer
        self.steps = 0  # micro-steps covered
        self.examples = 0
        self.parse_s = 0.0  # producing (parse / memmap-assemble) time
        self.convert_s = 0.0  # pack + device_put + unpack dispatch time
        self.wire_bytes = 0
        self.q_depth_sum = 0
        self.q_samples = 0
        self.wait_s = 0.0  # the consumer blocked in prefetch's q.get

    def timed(self, raw, convert):
        """Wrap the (parsed, w) stream, timing production and conversion.
        ``convert`` None keeps conversion in the consumer (text input) —
        parse time and queue depth still get measured.  Both stages are
        spans on the producer's thread too (``input.parse``, ``input.h2d``)."""
        raw = iter(raw)
        done = object()
        while True:
            t0 = time.perf_counter()
            with span("input.parse"):
                item = next(raw, done)
            if item is done:
                return
            p, w = item
            t1 = time.perf_counter()
            if convert is None:
                b, nbytes, t2 = None, 0, t1
            else:
                with span("input.h2d"):
                    b = convert(p, w)
                t2 = time.perf_counter()
                nbytes = getattr(convert, "last_nbytes", 0)
                if not nbytes:  # arrays converter: estimate from the host arrays
                    ps = p if isinstance(p, list) else [p]
                    # What actually ships depends on the CONVERTER's fields
                    # rule (from_parsed sends a [B, 0] placeholder when the
                    # model ignores fields), not on the parsed width.
                    wf = getattr(convert, "uses_fields", None)
                    nbytes = sum(
                        arrays_nbytes(
                            q.batch_size,
                            q.max_nnz,
                            bool(q.fields.shape[1]) if wf is None else wf,
                        )
                        for q in ps
                    )
            k = len(p) if isinstance(p, list) else 1
            ex = (
                sum(q.batch_size for q in p)
                if isinstance(p, list)
                else p.batch_size
            )
            with self._lock:
                self.items += 1
                self.converted += b is not None
                self.steps += k
                self.examples += ex
                self.parse_s += t1 - t0
                self.convert_s += t2 - t1
                self.wire_bytes += nbytes
            yield b, p, w

    def on_queue_depth(self, depth: int) -> None:
        with self._lock:
            self.last_depth = depth
            self.q_depth_sum += depth
            self.q_samples += 1

    def on_wait(self, seconds: float) -> None:
        """The consumer's pop blocked this long (``input.wait``)."""
        with self._lock:
            self.wait_s += seconds

    def drain(self) -> dict:
        """Snapshot-and-reset; {} when nothing flowed since last drain."""
        with self._lock:
            if not self.items:
                return {}
            # h2d/wire keys are None — not a misleading 0.0 — when
            # conversion ran in the CONSUMER (text input) and was simply
            # never measured here.
            measured = self.converted > 0
            out = {
                "input_items": self.items,
                "input_steps": self.steps,
                "input_examples": self.examples,
                "parse_ms": round(1e3 * self.parse_s / self.items, 3),
                "h2d_ms": (
                    round(1e3 * self.convert_s / self.items, 3)
                    if measured
                    else None
                ),
                "wire_bytes_per_step": (
                    int(self.wire_bytes / self.steps)
                    if measured and self.steps
                    else None
                ),
                "prefetch_queue_depth": (
                    round(self.q_depth_sum / self.q_samples, 2)
                    if self.q_samples
                    else None
                ),
                # Mean a pop the consumer blocked; None with no prefetch
                # queue in front of this stream.
                "wait_ms": (
                    round(1e3 * self.wait_s / self.q_samples, 3)
                    if self.q_samples
                    else None
                ),
            }
            self._reset()
        return out
