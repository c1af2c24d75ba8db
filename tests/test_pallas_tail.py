"""The Pallas rows sweep (ISSUE 30; on occurrences since ISSUE 32) vs its
XLA oracles.

Runs the kernel in the Pallas interpreter on the CPU mesh (resolve
auto-detects the backend, so no per-test plumbing); the sweep's compile
for the chip at the cell's shapes and its chip readings are PERF.md's.

Parity contract (acceptance criteria):
  * the rows sweep against the classic XLA program (same sort of the ids,
    same update expressions, compared inside jax.jit exactly as training
    runs them).  The sweep takes the batch's OCCURRENCES in id order and
    sums a row's duplicates in its own contraction, the rows take
    ``optim.dedup_rows``' segment sums: the same float32 addends in another
    order.  So the dense gradient the kernel builds in VMEM is the
    gradient BIT FOR BIT on a row hit once and wherever the sums are exact
    (``test_the_blocks_gradient_is_the_summed_gradient``,
    ``test_sweep_sums_the_occurrences_of_a_row`` on dyadic gradients, and
    ``_split3``'s own test) and within float32 summation error elsewhere;
    untouched rows come out bit for bit, and touched rows within a few
    float32 ULP, γ = 1 or not, either accumulator: on the CPU XLA
    contracts ``acc + g·g`` and ``w − lr·g/√acc`` into FMAs in one program
    and not in the other (the interpreted kernel body is a different
    fusion), and the row accumulator's Σg² runs over sublanes in the kernel
    (``_assert_few_ulp``; on the chip the accumulator read bit-equal on a
    batch without repeats, PERF.md §6, PR 30 and 32);
  * remainder blocks and K-step scans are exact, and the tiered /
    device-cache / streamed drivers log identical losses end to end when
    ``optim.rows_tail_form`` says the sweep (patched: on the CPU it says
    the rows).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.config import Config
from fast_tffm_tpu.models import Batch, FMModel
from fast_tffm_tpu.ops.pallas_tail import rows_tail_adagrad_update
from fast_tffm_tpu.optim import AdagradState, sparse_adagrad_update
from fast_tffm_tpu import trainer as tr

V, D = 64, 7


def _operands(seed=0, m=40, v=V, d=D):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.integers(0, v, size=(m,)), jnp.int32),
        jnp.asarray(rng.standard_normal((m, d)), jnp.float32),
        jnp.asarray(rng.standard_normal((v, d)), jnp.float32),
        jnp.asarray(rng.uniform(0.05, 2.0, (v, 1)), jnp.float32),
        jnp.asarray(rng.uniform(0.05, 2.0, (v, d)), jnp.float32),
    )


def _assert_few_ulp(got, want, scale=1.0, ulps=4):
    """``got`` within ``ulps`` float32 ULP of ``want`` at magnitude ``scale``
    (the larger operand of the tail's ``w + (−x)``: |x| < lr, |w| ≲ 4 for
    the standard-normal tables here).  The one difference allowed between
    the classic tail and a path that does not share its table update: one
    rounds ``x`` before the add, the other fuses multiply and subtract."""
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=0,
        atol=ulps * float(np.finfo(np.float32).eps) * scale,
    )


def _classic(table, accum, ids, g, lr, decay=1.0):
    return jax.jit(
        lambda t, a: sparse_adagrad_update(
            t, AdagradState(a), ids, g, lr, decay=decay
        )
    )(table, accum)


def _kernel(table, accum, ids, g, lr, decay=1.0, **kw):
    return jax.jit(
        lambda t, a: rows_tail_adagrad_update(
            t, a, ids, g, lr, decay=decay, **kw
        )
    )(table, accum)


def test_rows_tail_matches_numpy_oracle():
    ids, g, table, accum_row, accum_elem = _operands()
    lr = 0.13
    for acc in (accum_row, accum_elem):
        t2, a2 = _kernel(table, acc, ids, g, lr)
        dense_g = np.zeros((V, D), np.float64)
        np.add.at(dense_g, np.asarray(ids), np.asarray(g, np.float64))
        if acc.shape[-1] == 1:
            sq = (dense_g**2).sum(-1, keepdims=True)
        else:
            sq = dense_g**2
        accn = np.asarray(acc, np.float64) + sq
        want = np.asarray(table, np.float64) - lr * dense_g / np.sqrt(accn)
        touched = np.zeros(V, bool)
        touched[np.unique(np.asarray(ids))] = True
        np.testing.assert_allclose(  # atol: entries where w − step nearly cancels
            np.asarray(t2)[touched], want[touched], rtol=1e-5, atol=1e-7
        )
        np.testing.assert_allclose(
            np.asarray(a2)[touched], accn[touched], rtol=1e-5
        )
        # Untouched rows never enter the kernel — preserved bitwise.
        np.testing.assert_array_equal(
            np.asarray(t2)[~touched], np.asarray(table)[~touched]
        )


def _assert_accum_few_ulp(got, want, ulps=4):
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want),
        rtol=ulps * float(np.finfo(np.float32).eps),
    )


@pytest.mark.parametrize("acc_kind", ["row", "element"])
def test_rows_tail_bit_identical_to_classic(acc_kind):
    ids, g, table, accum_row, accum_elem = _operands(1)
    acc = accum_row if acc_kind == "row" else accum_elem
    rt, rs = _classic(table, acc, ids, g, 0.13)
    kt, ka = _kernel(table, acc, ids, g, 0.13)
    _assert_accum_few_ulp(ka, rs.accum)
    _assert_few_ulp(kt, rt)
    untouched = np.setdiff1d(np.arange(V), np.asarray(ids))
    assert jnp.all(kt[untouched] == table[untouched])
    assert jnp.all(ka[untouched] == acc[untouched])


@pytest.mark.parametrize("acc_kind", ["row", "element"])
def test_rows_tail_decay_parity(acc_kind):
    ids, g, table, accum_row, accum_elem = _operands(2)
    acc = accum_row if acc_kind == "row" else accum_elem
    rt, rs = _classic(table, acc, ids, g, 0.13, decay=0.9)
    kt, ka = _kernel(table, acc, ids, g, 0.13, decay=0.9)
    # Decayed expressions land in different XLA fusion clusters (FMA
    # contraction) — ULP drift, rtol-pinned (atol floors the near-zero
    # entries where 1 ULP is a big ratio).
    np.testing.assert_allclose(kt, rt, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(ka, rs.accum, rtol=1e-5, atol=1e-7)


def test_zero_grad_rows_are_exact_fixed_points():
    ids, g, table, accum_row, _ = _operands(3)
    z = jnp.zeros_like(g)
    kt, ka = _kernel(table, accum_row, ids, z, 0.13)
    # acc + 0 = acc and w − lr·0/√acc = w: a touched row whose gradients
    # sum to nothing is written back as it was.
    assert jnp.all(kt == table) and jnp.all(ka == accum_row)


# -- the sweep's own cases (ISSUE 30) ---------------------------------------


def _oracle(table, acc, ids, g, lr, decay=1.0):
    """The dense NumPy oracle of tests/test_optim_trainer.py, float64: (table,
    accumulator, touched) with ids >= V dropped."""
    v, d = table.shape
    ids, g = np.asarray(ids), np.asarray(g, np.float64)
    keep = ids < v
    dense = np.zeros((v, d), np.float64)
    np.add.at(dense, ids[keep], g[keep])
    sq = dense**2 if acc.shape[-1] == d else (dense**2).sum(-1, keepdims=True)
    touched = np.zeros(v, bool)
    touched[ids[keep]] = True
    accn = np.asarray(acc, np.float64).copy()
    accn[touched] = decay * accn[touched] + sq[touched]
    want = np.asarray(table, np.float64) - lr * dense / np.sqrt(np.where(accn > 0, accn, 1.0))
    return want, accn, touched


def _check_against_oracle(table, acc, ids, g, lr=0.13, decay=1.0, **kw):
    kt, ka = _kernel(table, acc, ids, g, lr, decay=decay, **kw)
    want, accn, touched = _oracle(table, acc, ids, g, lr, decay)
    kt, ka = np.asarray(kt), np.asarray(ka)
    # float32 sums of repeated ids that cancel are good to about 1e-5.
    np.testing.assert_allclose(kt[touched], want[touched], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ka[touched], accn[touched], rtol=1e-4)
    np.testing.assert_array_equal(kt[~touched], np.asarray(table)[~touched])
    np.testing.assert_array_equal(ka[~touched], np.asarray(acc)[~touched])
    assert np.isfinite(kt).all() and np.isfinite(ka).all()
    return kt, ka, touched


def _case(name):
    """(table, accumulator, ids, gradients, block_lanes) of a named case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    v, d, bl = 1000, 9, 256
    if name == "v_not_a_multiple_of_the_block":  # 1000 = 3 x 256 + 232
        ids = rng.integers(0, v, 300)
    elif name == "a_block_full_and_a_block_empty":  # block 0 whole, block 2 none
        v = 512
        bl = 128
        ids = np.concatenate([np.arange(128), rng.integers(384, 512, 20)])
    elif name == "more_updates_in_a_block_than_a_chunk":  # 700 > 256 in one block
        v, bl = 4096, 1024
        ids = np.concatenate([rng.permutation(1024)[:700] + 1024, rng.integers(0, v, 50)])
    elif name == "drop_ids_and_repeats":  # caller's sentinels and ids past V, and runs
        ids = np.concatenate([np.full(40, 7), np.full(9, v), np.full(3, v + 5), rng.integers(0, v, 200), [v - 1] * 4])
    else:
        raise AssertionError(name)
    ids = rng.permutation(ids).astype(np.int32)
    g = rng.standard_normal((ids.size, d)).astype(np.float32)
    table = rng.standard_normal((v, d)).astype(np.float32)
    acc = rng.uniform(0.05, 2.0, (v, d)).astype(np.float32)
    return jnp.asarray(table), jnp.asarray(acc), jnp.asarray(ids), jnp.asarray(g), bl


@pytest.mark.parametrize(
    "name",
    [
        "v_not_a_multiple_of_the_block",
        "a_block_full_and_a_block_empty",
        "more_updates_in_a_block_than_a_chunk",
        "drop_ids_and_repeats",
    ],
)
def test_sweep_block_and_chunk_edges(name):
    table, acc, ids, g, bl = _case(name)
    _kt, _ka, touched = _check_against_oracle(table, acc, ids, g, block_lanes=bl)
    if name == "a_block_full_and_a_block_empty":
        assert touched[:128].all() and not touched[128:384].any()
    if name == "drop_ids_and_repeats":
        assert touched[7] and touched[-1]


@pytest.mark.parametrize("acc_kind", ["row", "element"])
@pytest.mark.parametrize("d", [9, 17, 31, 89])
def test_sweep_matches_dense_oracle_over_widths(d, acc_kind):
    rng = np.random.default_rng(d)
    v = 700
    ids = jnp.asarray(rng.integers(0, v, 400), jnp.int32)
    g = jnp.asarray(rng.standard_normal((400, d)), jnp.float32)
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    acc = jnp.full((v, d if acc_kind == "element" else 1), 0.1, jnp.float32)
    _check_against_oracle(table, acc, ids, g, lr=0.5, block_lanes=256)


@pytest.mark.parametrize("acc_kind", ["row", "element"])
def test_an_untouched_row_with_a_zero_accumulator_stays_as_it_is(acc_kind):
    """``0/√0`` is never computed into a row the batch did not touch: the
    update is selected by the one-hot's hit, not multiplied by it."""
    ids, g, table, accum_row, accum_elem = _operands(8)
    acc = jnp.zeros_like(accum_row if acc_kind == "row" else accum_elem)
    kt, ka = _kernel(table, acc, ids, g, 0.13)
    kt, ka = np.asarray(kt), np.asarray(ka)
    untouched = np.setdiff1d(np.arange(V), np.asarray(ids))
    assert untouched.size and np.isfinite(kt).all() and np.isfinite(ka).all()
    np.testing.assert_array_equal(kt[untouched], np.asarray(table)[untouched])
    assert not ka[untouched].any() and (ka[np.asarray(ids)] > 0).all()


@pytest.mark.parametrize("acc_kind", ["row", "element"])
def test_decay_reaches_touched_rows_only(acc_kind):
    ids, g, table, accum_row, accum_elem = _operands(9)
    acc = accum_row if acc_kind == "row" else accum_elem
    _kt, ka, touched = _check_against_oracle(table, acc, ids, g, decay=0.9)
    # A touched row whose gradient is zero still decays (the classic lazy
    # decay's contract); an untouched one does not.
    kt0, ka0 = _kernel(table, acc, ids, jnp.zeros_like(g), 0.13, decay=0.9)
    np.testing.assert_allclose(np.asarray(ka0)[touched], 0.9 * np.asarray(acc)[touched], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ka0)[~touched], np.asarray(acc)[~touched])
    assert jnp.all(kt0 == table)


def test_split3_sums_back_to_the_float32_value():
    from fast_tffm_tpu.ops.pallas_tail import _split3

    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 256)).astype(np.float32) * np.float32(10.0) ** rng.integers(-20, 20, (16, 256)).astype(np.float32)
    x[0, :4] = [0.0, -0.0, 1.0, -1.5]
    parts = np.asarray(jax.jit(_split3)(jnp.asarray(x)).astype(jnp.float32))
    hi, mid, lo = parts[:16], parts[16:32], parts[32:]
    np.testing.assert_array_equal((hi + mid) + lo, x)


def test_the_blocks_gradient_is_the_summed_gradient():
    """The one-hot contraction returns the float32 summed gradient, not its
    bfloat16 rounding: read back through the element accumulator, whose new
    value under γ = 0 is ``0·acc + g·g``, one rounding of g² with or without
    an FMA.  Bit for bit on a row hit once; on a row hit more than once the
    kernel adds the same float32 addends in another order than
    ``segment_sum`` (the MXU's accumulator, each bfloat16 part apart), so
    within what n float32 additions can differ by."""
    from fast_tffm_tpu.optim import dedup_rows

    ids, g, table, _accum_row, accum_elem = _operands(10)
    _t, ka = _kernel(table, accum_elem, ids, g, 0.13, decay=0.0)
    uids, gsum = jax.jit(lambda i, r: dedup_rows(i, r, V))(ids, g)
    n = int(jnp.sum(uids < V))
    uids, gsum = np.asarray(uids[:n]), np.asarray(gsum[:n])
    got, want = np.asarray(ka)[uids], gsum**2  # 0·acc + g·g, one rounding either way
    count = np.bincount(np.asarray(ids), minlength=V)[uids]
    assert (count == 1).sum() > 5 and (count > 1).sum() > 5
    np.testing.assert_array_equal(got[count == 1], want[count == 1])
    mass = np.zeros((V, D))
    np.add.at(mass, np.asarray(ids), np.abs(np.asarray(g, np.float64)))
    eps = float(np.finfo(np.float32).eps)
    slack = 2 * np.abs(gsum) * (count[:, None] * eps * mass[uids]) + eps * want  # d(g²) = 2|g|·dg
    assert (np.abs(got - want) <= slack)[count > 1].all()


# -- the sweep on occurrences (ISSUE 32) --------------------------------------


def _occurrence_case(name):
    """(ids, gradients, the row to look at) of a named case on 2,048 rows of
    9 in blocks of 512: every gradient is a multiple of 1/256 under 4, so
    any float32 sum of up to 2^13 of them is exact in any order and the
    sweep's summed gradient IS ``dedup_rows``' bit for bit."""
    rng = np.random.default_rng(sum(map(ord, name)))
    v, d, bl = 2048, 9, 512
    look = 700
    if name == "one_id_10000_times_across_many_chunks":  # 40 chunks, one row
        ids = np.concatenate([np.full(10_000, look), rng.integers(0, v, 300)])
    elif name == "a_run_across_a_chunk_edge":  # sorted slots 200..319 hold one id
        ids = np.concatenate([np.arange(200), np.full(120, look), rng.integers(look + 1, v, 100)])
    elif name == "runs_on_both_sides_of_a_block_edge":  # one chunk, two blocks, both repeated
        look = bl - 1
        ids = np.concatenate([np.full(150, bl - 1), np.full(150, bl), rng.integers(bl + 1, v, 60)])
    elif name == "duplicates_that_cancel":  # g and -g: hit, and nothing to add
        ids = np.concatenate([np.full(64, look), rng.integers(0, look, 150)])
    elif name == "drop_ids_among_the_occurrences":  # the caller's sentinel, ids past V, repeated
        ids = np.concatenate([np.full(30, v), np.full(7, v + 5), np.full(25, look), rng.integers(0, v, 200)])
    else:
        raise AssertionError(name)
    g = rng.integers(-1023, 1024, (ids.size, d)).astype(np.float32) / np.float32(256.0)
    if name == "duplicates_that_cancel":
        g[32:64] = -g[:32]
    perm = rng.permutation(ids.size)
    return jnp.asarray(ids[perm], jnp.int32), jnp.asarray(g[perm]), (v, d, bl, look)


@pytest.mark.parametrize("decay", [1.0, 0.9], ids=["classic", "decayed"])
@pytest.mark.parametrize("acc_kind", ["row", "element"])
@pytest.mark.parametrize(
    "name",
    [
        "one_id_10000_times_across_many_chunks",
        "a_run_across_a_chunk_edge",
        "runs_on_both_sides_of_a_block_edge",
        "duplicates_that_cancel",
        "drop_ids_among_the_occurrences",
    ],
)
def test_sweep_sums_the_occurrences_of_a_row(name, acc_kind, decay):
    """The sweep takes sorted ids that REPEAT: a chunk's contraction sums the
    ids that match one row, ``gacc`` carries the row across chunks and
    blocks, the hit is a count above a half.  Against the float64 dense
    oracle, and against the rows form (unique sums from ``dedup_rows``) to a
    few ULP: the gradients' sums are exact here, so what is left is the FMA
    contraction of the module docstring."""
    ids, g, (v, d, bl, look) = _occurrence_case(name)
    rng = np.random.default_rng(5)
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    acc = jnp.asarray(rng.uniform(0.05, 2.0, (v, d if acc_kind == "element" else 1)), jnp.float32)
    kt, ka, touched = _check_against_oracle(table, acc, ids, g, decay=decay, block_lanes=bl)
    assert touched[look] and touched.sum() < v
    rt, rs = _classic(table, acc, ids, g, 0.13, decay=decay)
    _assert_few_ulp(kt, rt)
    _assert_accum_few_ulp(ka, rs.accum)
    if name == "duplicates_that_cancel":  # hit, and out as it went in
        np.testing.assert_array_equal(kt[look], np.asarray(table)[look])
        if decay == 1.0:
            np.testing.assert_array_equal(ka[look], np.asarray(acc)[look])
        else:  # a touched row decays whatever its gradient
            _assert_accum_few_ulp(ka[look], decay * np.asarray(acc)[look])


@pytest.mark.parametrize(
    "d, keep, form",
    [
        (9, None, "sort operands"),
        (16, None, "sort operands"),
        (17, None, "tile-wide row gather"),
        (17, 300, "tile-wide row gather"),  # half of the 600 kept: the gather of those alone
        (17, 300, "row gather"),
        (18, None, "row gather"),
        (31, None, "tile-wide row gather"),
        (31, None, "row gather"),
        (89, None, "row gather"),
    ],
    ids=["9", "16", "17", "17_keep_half", "17_keep_half_narrow", "18", "31_tile_wide", "31", "89"],
)
def test_occurrences_reach_id_order_the_same_way_in_both_forms(monkeypatch, d, keep, form):
    """``occurrences_by_id``: the ids ascending with drop ids clamped to V,
    the gradients column by column in that order, ties in the batch's order
    (a stable sort), whether the columns ride the sort or the rows are
    gathered by its order, padded to a tile or not: every form is
    ``row_grads[order].T`` bit for bit, ``-0.0``, a NaN and an infinity
    among the values.  The rows here are few, so the tile-wide gather, which
    ``occurrences_permutation`` takes for buffers past the VMEM, is forced."""
    from fast_tffm_tpu import optim

    m, v = 600, 50
    if form == "tile-wide row gather":
        assert optim.occurrences_permutation(d, 2_555_904) == form
        monkeypatch.setattr(optim, "occurrences_permutation", lambda *a: form)
    assert optim.occurrences_permutation(d, m) == form
    rng = np.random.default_rng(d)
    ids = rng.integers(0, v + 8, m).astype(np.int32)  # repeats, and ids past V
    g = rng.standard_normal((m, d)).astype(np.float32)
    g[3, d - 1], g[7, 0], g[11, d // 2] = -0.0, np.nan, np.float32(np.inf)
    sid, gt = jax.jit(lambda i, r: optim.occurrences_by_id(i, r, v, keep))(ids, g)
    order = np.argsort(np.minimum(ids, v), kind="stable")[:keep]
    np.testing.assert_array_equal(np.asarray(sid), np.minimum(ids, v)[order])
    np.testing.assert_array_equal(np.asarray(gt).view(np.uint32), g[order].T.view(np.uint32))


def _cell_shapes(config, shards=1):
    """``rows_tail_form``'s arguments at a benchmark configuration's shapes
    (``benchmark/configs/<config>.json``): rows (a chip's share of them),
    ids a step, row width, accumulator columns."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", config + ".json")
    with open(path) as f:
        c = json.load(f)
    train = c["ini"]["Train"]
    d = c.get("row_dim", 1 + c["factor_num"])
    cols = 1 if train.get("adagrad_accumulator") == "row" else d
    return c["vocabulary_size"] // shards, train["batch_size"] * train["max_nnz"], d, cols


@pytest.mark.parametrize(
    "shapes, backend, form",
    [
        ((2**26, 65536 * 39, 9, 9), "tpu", "sweep"),  # fm8_criteo.train_fmb: 31 ms against 570
        ((2**20, 32768 * 39, 157, 157), "tpu", "rows"),  # ffm4_criteo: rows past one tile
        ((2**26, 1024 * 39, 9, 9), "tpu", "rows"),  # a small batch on the same table: 31 ms against 9
        # The crossing as re-read in PR 32 (136K ids on this table; the rule's constants put it at 137K).
        ((2**26, 3456 * 39, 9, 9), "tpu", "rows"),  # 134,784 ids
        ((2**26, 3584 * 39, 9, 9), "tpu", "sweep"),  # 139,776 ids
        ((2**26, 65536 * 39, 9, 9), "cpu", "rows"),  # no kernel interpreted inside a train step
        # The benchmark's own configurations, read from their files: each
        # side of the choice has a cell (PERF.md §4).
        (("fm8_criteo",), "tpu", "sweep"),
        (("ffm4_criteo",), "tpu", "rows"),
        (("fm8_criteo_rowacc",), "tpu", "sweep"),  # the row accumulator: fewer bytes to sweep
        (("fm16_criteo_row4", 4), "tpu", "sweep"),  # a chip's 2^25 rows of 17 under the global batch
        (("fm3_k30_kdd12",), "tpu", "sweep"),  # 2^25 rows of 31 (32 sublanes, fm8's bytes) under 65,536 x 11 ids
    ],
    ids=[
        "fm8_on_tpu", "d157", "b1024", "under_the_crossing", "over_the_crossing", "cpu",
        "cell_fm8_criteo", "cell_ffm4_criteo", "cell_fm8_criteo_rowacc", "cell_fm16_criteo_row4",
        "cell_fm3_k30_kdd12",
    ],
)
def test_auto_chooses_the_form_from_shapes_and_backend(shapes, backend, form):
    from fast_tffm_tpu.optim import rows_tail_form

    if isinstance(shapes[0], str):
        shapes = _cell_shapes(*shapes)
    assert rows_tail_form(*shapes, backend=backend) == form
    if backend == "cpu":  # what this suite's train steps get when nobody says
        assert rows_tail_form(*shapes) == "rows"


@pytest.mark.parametrize(
    "config, keep, form",
    [
        ("fm8_criteo", None, "sort operands"),  # nine columns ride the sort, as before
        ("deepfm10_criteo", None, "sort operands"),  # eleven
        ("fm16_criteo_tiered", None, "tile-wide row gather"),  # 17 by 2,555,904: 245 MB lane-major
        ("fm16_criteo_row4", None, "tile-wide row gather"),  # the row shard's whole-list branch: the same
        ("fm16_criteo_row4", 1284384, "tile-wide row gather"),  # its bounded branch: the first half of them
        ("fm3_k30_kdd12", None, "row gather"),  # 31 by 720,896: 92 MB, kept in the VMEM
    ],
    ids=["fm8_criteo", "deepfm10_criteo", "fm16_criteo_tiered", "fm16_criteo_row4_whole", "fm16_criteo_row4",
         "fm3_k30_kdd12"],
)
def test_the_permutation_is_chosen_from_the_shapes(config, keep, form):
    """``occurrences_permutation`` at the shipped cells' shapes (from their
    files; a row shard's tail sorts every chip's 2,555,904 slots and may
    keep the first 1,284,384, ``parallel.train_step.shard_tail_ids``), and
    what the step's profile record and start-up line say of it."""
    from fast_tffm_tpu.optim import describe_rows_tail, occurrences_permutation, rows_tail_profile

    v, m, d, _cols = _cell_shapes(config)
    assert occurrences_permutation(d, m) == form
    said = rows_tail_profile(v, keep or m, d, "sweep", m)["tail_permutation"]
    assert said == form
    assert f"occurrences brought to id order as {said}, row width {d})" in describe_rows_tail(v, keep or m, d, "sweep", m)


def test_the_tile_wide_gather_starts_past_the_vmem():
    """The lane-major buffer of ``m`` rows of 17 is 24 sublanes x ``m``
    floats: past 128 MiB at 1,398,102 rows, the tile-wide gather."""
    from fast_tffm_tpu.optim import occurrences_permutation

    assert occurrences_permutation(17, 1_398_101) == "row gather"
    assert occurrences_permutation(17, 1_398_102) == "tile-wide row gather"
    assert occurrences_permutation(127, 2_555_904) == "tile-wide row gather"
    assert occurrences_permutation(128, 2_555_904) == "row gather"  # a whole tile already


@pytest.mark.parametrize("form", ["tile-wide row gather", "sort operands"])
def test_train_step_at_row_width_17_is_the_same_in_every_permutation(monkeypatch, sweep_form, form):
    """A toy FM of row width 17 (k = 16) through the sweep, its gradients
    brought to id order by the narrow row gather and by another form: three
    steps, and the losses, table and accumulator are the same bits (the
    permutation moves values and computes nothing)."""
    from fast_tffm_tpu import optim

    model = FMModel(vocabulary_size=100, factor_num=16, order=2)
    batches = _batches()

    def run(forced):
        monkeypatch.setattr(optim, "occurrences_permutation", lambda *a: forced)
        state, losses = tr.init_state(model, jax.random.key(0), 0.1, "element"), []
        step = tr.make_train_step(model, 0.05)
        for b in batches:
            state, loss = step(state, b)
            losses.append(float(loss))
        return state, losses

    s0, l0 = run("row gather")
    s1, l1 = run(form)
    assert sweep_form == [(100, 16 * 6, 17, 17)] * 2
    assert l1 == l0
    np.testing.assert_array_equal(np.asarray(s1.table).view(np.uint32), np.asarray(s0.table).view(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(s1.table_opt.accum).view(np.uint32), np.asarray(s0.table_opt.accum).view(np.uint32)
    )


def test_remainder_tail_small_blocks():
    # The rows sweep's remainder: a block of 128 lanes over 64 rows.
    ids, g, table, accum_row, _ = _operands(7)
    rt, rs = _classic(table, accum_row, ids, g, 0.13)
    t2, a2 = _kernel(table, accum_row, ids, g, 0.13, block_lanes=128)
    _assert_few_ulp(t2, rt)
    _assert_accum_few_ulp(a2, rs.accum)


# -- trainer-level wiring -------------------------------------------------


def _batches(n=3, B=16, N=6, v=100, seed=1):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        out.append(
            Batch(
                labels=jnp.asarray((rng.random(B) < 0.5).astype(np.float32)),
                ids=jnp.asarray(rng.integers(0, v, (B, N)).astype(np.int32)),
                vals=jnp.asarray(
                    np.abs(rng.normal(size=(B, N)).astype(np.float32))
                ),
                fields=jnp.zeros((B, N), jnp.int32),
                weights=jnp.ones((B,), jnp.float32),
            )
        )
    return out


def test_train_step_pallas_body_bit_identical(request):
    model = FMModel(vocabulary_size=100, factor_num=4, order=2)
    batches = _batches()

    def run():
        state, losses = tr.init_state(model, jax.random.key(0), 0.1, "element"), []
        step = tr.make_train_step(model, 0.05)
        for b in batches:
            state, loss = step(state, b)
            losses.append(loss)
        return state, losses

    s0, l0 = run()  # the rows: what the rule says on the CPU
    asked = request.getfixturevalue("sweep_form")  # from here on
    s1, l1 = run()
    assert asked == [(100, 16 * 6, 5, 5)]
    # The first loss sees the same table; later ones a table a few ULP
    # apart (module docstring), and the gap compounds over the steps.
    assert l1[0] == l0[0]
    np.testing.assert_allclose(l1, l0, rtol=1e-6)
    _assert_few_ulp(s1.table, s0.table, ulps=16)
    np.testing.assert_allclose(s1.table_opt.accum, s0.table_opt.accum, rtol=1e-6)


def test_scanned_pallas_body_matches_sequential(sweep_form):
    model = FMModel(vocabulary_size=100, factor_num=4, order=2)
    batches = _batches()
    s1 = tr.init_state(model, jax.random.key(0), 0.1, "element")
    step_p = tr.make_train_step(model, 0.05)
    for b in batches:
        s1, _ = step_p(s1, b)
    stack = lambda f: jnp.stack([getattr(b, f) for b in batches])
    sb = Batch(
        labels=stack("labels"), ids=stack("ids"), vals=stack("vals"),
        fields=stack("fields"), weights=stack("weights"),
    )
    s4 = tr.init_state(model, jax.random.key(0), 0.1, "element")
    scan_p = tr.make_scanned_train_step(model, 0.05)
    s4, _losses = scan_p(s4, sb)
    assert sweep_form == [(100, 16 * 6, 5, 5)] * 2  # both steps traced as the sweep
    assert jnp.all(s4.table == s1.table)
    assert jnp.all(s4.table_opt.accum == s1.table_opt.accum)


# -- end-to-end drivers (streamed / device-cache / tiered) ----------------


def _write_dataset(path, n=120, vocab=200, nnz=5, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n):
            ids = rng.choice(vocab, size=nnz, replace=False)
            vals = np.round(np.abs(rng.normal(size=nnz)) + 0.1, 4)
            y = int(rng.random() < 0.5)
            f.write(
                f"{y} " + " ".join(f"{i}:{v}" for i, v in zip(ids, vals)) + "\n"
            )


def _cfg(tmp_path, name, **kw):
    c = Config()
    c.model = "fm"
    c.factor_num = 4
    c.vocabulary_size = 200
    c.train_files = (str(tmp_path / "train.libsvm"),)
    c.epoch_num = 1
    c.batch_size = 32
    c.learning_rate = 0.1
    c.log_every = 1
    c.model_file = str(tmp_path / f"{name}.ckpt")
    for k, v in kw.items():
        setattr(c, k, v)
    return c.validate()


def _losses(logs):
    return [float(l.split("loss ")[1].split()[0]) for l in logs if "loss " in l]


def _run(cfg):
    from fast_tffm_tpu.training import train

    logs = []
    state = train(cfg, log=lambda *a: logs.append(" ".join(map(str, a))))
    return state, logs


@pytest.mark.parametrize(
    "driver, kw",
    [
        ("streamed", {}),
        ("device_cache", dict(device_cache=True, binary_cache=True)),
        ("tiered", dict(paramstore=True, paramstore_hot_rows=48)),
    ],
)
def test_drivers_pallas_tail_bit_identical(tmp_path, request, driver, kw):
    """Each driver (streamed, device-cached, tiered), when ``rows_tail_form``
    says the sweep, logs the loss sequence the streamed driver logs with the
    XLA rows (rows layout, γ=1): the only tier-1 run of the sweep THROUGH
    the drivers.  Equal as logged, to five decimals: these batches repeat
    ids (160 draws from 200 rows), the sweep sums a row's occurrences in
    another order than ``segment_sum`` does, and the tables drift by a few
    ULP as in ``test_train_step_pallas_body_bit_identical``."""
    _write_dataset(str(tmp_path / "train.libsvm"))
    _s, xla_logs = _run(_cfg(tmp_path, "xla"))
    assert any(l.startswith("sparse tail: xla rows (") for l in xla_logs)
    asked = request.getfixturevalue("sweep_form")  # from here on
    _s, pal_logs = _run(_cfg(tmp_path, driver, **kw))
    assert asked and len(_losses(xla_logs)) > 2
    np.testing.assert_allclose(_losses(pal_logs), _losses(xla_logs), rtol=0, atol=1.5e-5)
    if driver != "tiered":  # the tiered driver says nothing of its compact tier's tail
        assert any(l.startswith("sparse tail: pallas rows sweep (block 256 lanes, 1 blocks") for l in pal_logs)
