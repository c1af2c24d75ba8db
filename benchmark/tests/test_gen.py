"""The seeded id draw: bit for bit the draw the cells have always had where a
field's span times the scatter fits uint32, and no wrapped id where it does
not (2^27 rows over 39 fields and up: the product is taken in uint64)."""

import json
import os
from math import gcd

import numpy as np
import pytest

from harness import cells, gen

SCATTER = 1999
CONFIGS = sorted(fn[: -len(".json")] for fn in os.listdir(os.path.join(cells.BENCH_DIR, "configs")) if fn.endswith(".json"))


def _spans(fields, vocab):
    bounds = np.linspace(0, vocab, fields + 1).astype(np.int64)
    return bounds[:-1], bounds[1:] - bounds[:-1]


def _chunk_as_pr25_wrote_it(seed, c, n_rows, lo, span, alpha_half):
    """``gen._chunk`` of the parent of PR 34, a literal copy: the draw of every
    reading the ledger holds.  Not to be edited with ``gen.py``."""
    rng = np.random.default_rng([int(seed), c])
    u = rng.random((n_rows, lo.size), dtype=np.float32)
    p = u * u * np.sqrt(u) if alpha_half else u * u
    ranks = np.minimum((p * span.astype(np.float32)).astype(np.uint32), span - 1)
    ids = (lo + (ranks * np.uint32(1999)) % span).astype(np.int32)
    vals = np.abs(rng.standard_normal((n_rows, lo.size), dtype=np.float32) * 0.35 + 0.5) + 0.05
    vals = np.round(vals, 4)
    score = (((ids & 1023).astype(np.float32) / 1024.0 - 0.5) * vals).sum(axis=1) * 1.5 - 1.0
    labels = (rng.random(n_rows, dtype=np.float32) < 1.0 / (1.0 + np.exp(-score))).astype(np.float32)
    return labels, ids, vals, ranks


def _rows_as_pr25_wrote_them(seed, n_rows, fields, vocab, alpha):
    lo, span = _spans(fields, vocab)
    lo, span = lo.astype(np.uint32)[None, :], span.astype(np.uint32)[None, :]
    cuts = np.linspace(0, n_rows, 8 + 1).astype(int)
    parts = [_chunk_as_pr25_wrote_it(seed, c, cuts[c + 1] - cuts[c], lo, span, alpha == 2.5) for c in range(8)]
    return tuple(np.concatenate([p[i] for p in parts]) for i in range(4))


def _in_range(ids, fields, vocab):
    lo, span = _spans(fields, vocab)
    return ids.dtype == np.int32 and bool((ids >= lo).all() and (ids < lo + span).all() and (ids >= 0).all())


CASES = [("same", v, s, a) for v in (2**20, 2**26) for s in (7, 3000003401) for a in (2.0, 2.5)]
CASES += [("wide", v, s, 2.5) for v in (2**27, 2**30, 2**31 - 1) for s in (7, 3000003401)]


@pytest.mark.parametrize("what,vocab,seed,alpha", CASES, ids=str)
def test_the_draw(what, vocab, seed, alpha):
    n, fields = 3001, 39  # chunks of unequal length
    labels, ids, vals = gen.rows_from_seed(seed, n, fields, vocab, alpha)
    want_labels, want_ids, want_vals, ranks = _rows_as_pr25_wrote_them(seed, n, fields, vocab, alpha)
    assert labels.shape == (n,) and ids.shape == vals.shape == (n, fields) and _in_range(ids, fields, vocab)
    lo, span = _spans(fields, vocab)
    if what == "same":
        assert int(span.max()) * SCATTER < 2**32
        for got, want in ((labels, want_labels), (ids, want_ids), (vals, want_vals)):
            assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
        return
    # Past the uint32 product the parent's expression wraps (its assertion was honest) ...
    assert int(span.max()) * SCATTER >= 2**32 and not np.array_equal(ids, want_ids)
    # ... the values are the same stream, and every id is lo + (rank * 1999) % span in Python's integers.
    assert vals.tobytes() == want_vals.tobytes()
    rng = np.random.default_rng(seed)
    for r, f in zip(rng.integers(0, n, 10_000), rng.integers(0, fields, 10_000)):
        assert int(ids[r, f]) == int(lo[f]) + (int(ranks[r, f]) * SCATTER) % int(span[f])
    # The scatter is a bijection on a field's range: distinct ranks, distinct ids.
    for f in range(fields):
        assert gcd(int(span[f]), SCATTER) == 1
        assert np.unique(ids[:, f]).size == np.unique(ranks[:, f]).size


def test_a_vocabulary_past_int32_is_refused():
    with pytest.raises(AssertionError):
        gen.rows_from_seed(1, 8, 39, 2**31)


@pytest.mark.parametrize("config", CONFIGS)
def test_every_shipped_configuration_has_an_input(config):
    """A configuration file the harness cannot draw rows for cannot become a
    cell (``fm16_criteo_row4`` sat so for nine PRs): the check's three batches
    at the configuration's own size."""
    c = json.load(open(os.path.join(cells.BENCH_DIR, "configs", config + ".json")))
    fields, vocab, n = int(c["fields"]), int(c["vocabulary_size"]), 3 * int(c["batch_size"])
    assert vocab == int(c["ini"]["General"]["vocabulary_size"]) and fields == int(c["ini"]["Train"]["max_nnz"])
    labels, ids, vals = gen.rows_from_seed(3000003402, n, fields, vocab)
    assert ids.shape == (n, fields) and _in_range(ids, fields, vocab)
    assert set(np.unique(labels)) <= {0.0, 1.0} and (vals > 0).all()
