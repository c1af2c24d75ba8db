"""Inputs from the seed: Criteo-shaped rows (one Zipf-drawn id per field, as
``tools/gen_synthetic.py`` draws them, scattered over the field's hash range)
written straight into the FMB layout of ``fast_tffm_tpu/data/binary.py``."""

from __future__ import annotations

import struct

import numpy as np

# Copy of data/binary.py's header: magic, version, n_rows, width, vocab,
# hashed, ids itemsize, flags, src_size, src_mtime_ns, widest row.
_HEADER = struct.Struct("<4sIqqqBBB5xqqq")
_ALIGN = 64
_FLAG_FIELDS_ALL_ZERO = 2
_SCATTER = 1999  # prime, and coprime with every field span (asserted): the scatter is a bijection on a field's range
_CHUNKS = 8  # row chunks, each with its own stream of the seed, drawn on threads


def _chunk(seed, c, n_rows, lo, span, alpha_half):
    rng = np.random.default_rng([int(seed), c])
    u = rng.random((n_rows, lo.size), dtype=np.float32)
    p = u * u * np.sqrt(u) if alpha_half else u * u
    ranks = np.minimum((p * span.astype(np.float32)).astype(np.uint32), span - 1)
    if int(span.max()) * _SCATTER < 2**32:
        ids = (lo + (ranks * np.uint32(_SCATTER)) % span).astype(np.int32)
    else:  # the product passes uint32 (a span over 2,148,557: 2^27 rows / 39 fields): taken in uint64
        scattered = (ranks.astype(np.uint64) * np.uint64(_SCATTER)) % span.astype(np.uint64)
        ids = (lo + scattered.astype(np.uint32)).astype(np.int32)
    vals = np.abs(rng.standard_normal((n_rows, lo.size), dtype=np.float32) * 0.35 + 0.5) + 0.05
    vals = np.round(vals, 4)
    # Labels from a cheap hidden per-id bias, so that clicks depend on ids.
    score = (((ids & 1023).astype(np.float32) / 1024.0 - 0.5) * vals).sum(axis=1) * 1.5 - 1.0
    labels = (rng.random(n_rows, dtype=np.float32) < 1.0 / (1.0 + np.exp(-score))).astype(np.float32)
    return labels, ids, vals


def rows_from_seed(seed: int, n_rows: int, fields: int, vocab: int, alpha: float = 2.5):
    """(labels f32[n], ids i32[n, fields], vals f32[n, fields]).  Field f owns
    [f*vocab/fields, (f+1)*vocab/fields); rank ~ span * u**alpha is the
    generator's heavy tail (alpha 2.5 or 2), and the multiplicative scatter
    stands for the feature hash, so hot ids are not neighbours in the table."""
    from concurrent.futures import ThreadPoolExecutor
    from math import gcd

    bounds = np.linspace(0, vocab, fields + 1).astype(np.int64)
    lo = bounds[:-1].astype(np.uint32)[None, :]
    span = (bounds[1:] - bounds[:-1]).astype(np.uint32)[None, :]
    # Ids are int32 in FMB and on the wire; a span that shares a factor with _SCATTER would fold hot ids together.
    assert 0 < vocab <= 2**31 - 1 and all(gcd(int(s), _SCATTER) == 1 for s in span[0])
    assert alpha in (2.0, 2.5)
    cuts = np.linspace(0, n_rows, _CHUNKS + 1).astype(int)
    with ThreadPoolExecutor(_CHUNKS) as pool:
        parts = list(
            pool.map(
                lambda c: _chunk(seed, c, cuts[c + 1] - cuts[c], lo, span, alpha == 2.5),
                range(_CHUNKS),
            )
        )
    return tuple(np.concatenate([p[i] for p in parts]) for i in range(3))


def column_fields(ids: np.ndarray) -> np.ndarray:
    """The field of every id of ``rows_from_seed``: column f is field f."""
    return np.broadcast_to(np.arange(ids.shape[-1], dtype=np.int32), ids.shape)


def write_fmb(path: str, labels, ids, vals, vocab: int, fields=None) -> int:
    """One FMB v2 file; returns the bytes written.  Without ``fields`` (a
    model that reads none) every field id is zero and the header says so."""
    n, width = ids.shape
    sections = [
        labels.astype("<f4"),
        np.full(n, width, "<i4"),
        ids.astype("<i4"),
        vals.astype("<f4"),
        np.zeros((n, width), "<i4") if fields is None else fields.astype("<i4"),
    ]
    flags = _FLAG_FIELDS_ALL_ZERO if fields is None else 0
    with open(path, "wb") as f:
        f.write(_HEADER.pack(b"FMB1", 2, n, width, vocab, 0, 4, flags, 0, 0, width))
        for a in sections:
            f.seek(-(-f.tell() // _ALIGN) * _ALIGN)
            f.write(a.tobytes())
        f.truncate(-(-f.tell() // _ALIGN) * _ALIGN)
        return f.tell()
