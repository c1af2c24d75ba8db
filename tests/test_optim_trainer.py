"""Sparse Adagrad correctness vs a dense oracle; end-to-end training smoke."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.metrics import auc
from fast_tffm_tpu.models import Batch, DeepFMModel, FFMModel, FMModel
from fast_tffm_tpu.optim import (
    AdagradState,
    dedup_rows,
    distinct_sentinels,
    init_adagrad,
    init_table_adagrad,
    segment_sum_lanes,
    sparse_adagrad_update,
)
from fast_tffm_tpu.trainer import init_state, make_predict_step, make_train_step


def test_dedup_rows_sums_duplicates():
    ids = jnp.asarray([3, 1, 3, 7, 1, 3], jnp.int32)
    g = jnp.arange(6, dtype=jnp.float32)[:, None] + 1.0  # [6, 1]
    uids, gsum = dedup_rows(ids, g, num_rows=10)
    got = {int(u): float(s) for u, s in zip(uids, gsum[:, 0]) if int(u) < 10}
    assert got == {1: 2.0 + 5.0, 3: 1.0 + 3.0 + 6.0, 7: 4.0}


def test_sparse_adagrad_matches_dense_oracle():
    """Sparse step == dense Adagrad applied to the summed scatter gradient."""
    rng = np.random.default_rng(0)
    V, D = 20, 3
    table = jnp.asarray(rng.normal(size=(V, D)).astype(np.float32))
    state = init_adagrad(table, 0.1)
    ids = jnp.asarray(rng.integers(0, V, size=(4, 5)).astype(np.int32))
    g = jnp.asarray(rng.normal(size=(4, 5, D)).astype(np.float32))

    new_table, new_state = sparse_adagrad_update(table, state, ids, g, lr=0.5)

    dense_g = np.zeros((V, D), np.float64)
    np.add.at(dense_g, np.asarray(ids).ravel(), np.asarray(g, np.float64).reshape(-1, D))
    accum = 0.1 + dense_g**2
    want = np.asarray(table, np.float64) - 0.5 * dense_g / np.sqrt(accum)
    touched = np.zeros(V, bool)
    touched[np.unique(np.asarray(ids))] = True
    np.testing.assert_allclose(np.asarray(new_table)[touched], want[touched], rtol=1e-5)
    # Untouched rows unchanged (sparse property).
    np.testing.assert_array_equal(
        np.asarray(new_table)[~touched], np.asarray(table)[~touched]
    )
    np.testing.assert_allclose(np.asarray(new_state.accum)[touched], accum[touched], rtol=1e-5)


# --- the rows tail's row descriptors (PR 27) ------------------------------
#
# Widths: FM k=8 and k=16 rows, an FFM row, a row that is tile-wide already
# (takes the narrow segment sum) and one past a tile (pads to 256 lanes).
_WIDTHS = [1 + 8, 1 + 16, 89, 128, 130]
_V = 2000


def _pattern_ids(pattern: str) -> np.ndarray:
    rng = np.random.default_rng(7)
    if pattern == "all_unique":
        return rng.permutation(_V)[:96].astype(np.int32)
    if pattern == "one_id":
        return np.full((64,), 7, np.int32)
    if pattern == "run_over_256":  # deeper than any doubling bound guessed from the cell (250)
        ids = np.concatenate([np.full((300,), 5), rng.integers(0, _V, 400)])
        return rng.permutation(ids).astype(np.int32)
    if pattern == "runs_at_both_ends":  # sorted, a run opens the array and another closes it
        ids = np.concatenate([np.zeros(3), np.full((5,), _V - 1), rng.integers(1, _V - 1, 42)])
        return rng.permutation(ids).astype(np.int32)
    assert pattern == "m_not_pow2"
    return rng.integers(0, 300, 1037).astype(np.int32)  # every id about 3.5 times


_PATTERNS = ["all_unique", "one_id", "run_over_256", "runs_at_both_ends", "m_not_pow2"]


def _dense_grad(ids, g, d):
    dense = np.zeros((_V, d), np.float64)
    np.add.at(dense, ids, np.asarray(g, np.float64))
    return dense


@pytest.mark.parametrize("pattern", _PATTERNS)
@pytest.mark.parametrize("d", _WIDTHS)
def test_dedup_rows_matches_dense_oracle(d, pattern):
    ids = _pattern_ids(pattern)
    g = np.random.default_rng(d).normal(size=(ids.size, d)).astype(np.float32)
    uids, gsum = jax.jit(lambda i, r: dedup_rows(i, r, _V))(ids, g)
    uids, gsum = np.asarray(uids), np.asarray(gsum)
    want_ids = np.unique(ids)
    n = want_ids.size
    np.testing.assert_array_equal(uids[:n], want_ids)
    np.testing.assert_allclose(gsum[:n], _dense_grad(ids, g, d)[want_ids], rtol=1e-5, atol=1e-5)
    assert (uids[n:] >= _V).all() and (np.diff(uids) > 0).all()
    assert not gsum[n:].any()


@pytest.mark.parametrize("pattern", _PATTERNS)
@pytest.mark.parametrize("decay", [1.0, 0.9])
@pytest.mark.parametrize("accumulator", ["element", "row"])
@pytest.mark.parametrize("d", _WIDTHS)
def test_sparse_adagrad_matches_dense_oracle_over_widths(d, accumulator, decay, pattern):
    ids = _pattern_ids(pattern)
    rng = np.random.default_rng(d)
    g = rng.normal(size=(ids.size, d)).astype(np.float32)
    table = rng.normal(size=(_V, d)).astype(np.float32)
    state = init_table_adagrad(jnp.asarray(table), 0.1, accumulator)
    lr = 0.5
    new_table, new_state = jax.jit(
        lambda t, s, i, r: sparse_adagrad_update(t, s, i, r, lr, decay=decay)
    )(jnp.asarray(table), state, ids, g)

    dense = _dense_grad(ids, g, d)
    sq = dense**2 if accumulator == "element" else (dense**2).sum(-1, keepdims=True)
    touched = np.zeros(_V, bool)
    touched[ids] = True
    acc = np.full(sq.shape, 0.1)
    acc[touched] = decay * acc[touched] + sq[touched]
    want = table.astype(np.float64) - lr * dense / np.sqrt(acc)
    # float32 sums of up to 300 addends that cancel (a sum of 0.8 from terms
    # of size 1) are good to about 1e-5 of the sum, and g² doubles that.
    np.testing.assert_allclose(np.asarray(new_table)[touched], want[touched], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_state.accum), acc, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(new_table)[~touched], table[~touched])


def _hlo_ops(lowered, kind):
    """(scope, line) of every ``kind`` op in the lowered step, the scope being
    the ``jax.named_scope`` path in the op's ``op_name``."""
    out = []
    for line in lowered.as_text(dialect="hlo", debug_info=True).splitlines():
        if re.search(rf" {kind}\(", line):
            out.append((re.search(r'op_name="([^"]*)"', line).group(1), line))
    return out


def _lower_rows_tail(v, d, m):
    sd = jax.ShapeDtypeStruct
    return jax.jit(
        lambda t, a, i, r: sparse_adagrad_update(t, AdagradState(a), i, r, 0.05)
    ).lower(sd((v, d), jnp.float32), sd((v, d), jnp.float32), sd((m,), jnp.int32), sd((m, d), jnp.float32))


def test_drop_ids_are_distinct_ascending_and_guarded_by_int32():
    ids = jnp.asarray([3, 1, 3, 7, 1, 3], jnp.int32)
    uids, _ = dedup_rows(ids, jnp.ones((6, 2)), num_rows=10)
    uids = np.asarray(uids)
    np.testing.assert_array_equal(uids[:3], [1, 3, 7])
    assert (uids[3:] >= 10).all() and (np.diff(uids) > 0).all()
    # A caller's own drop ids (the sharded updates dedup all-gathered uids a
    # second time) collapse into ONE segment below every trailing slot's id.
    again, gsum = dedup_rows(jnp.asarray([12, 1, 15, 10, 1, 13], jnp.int32), jnp.ones((6, 2)), num_rows=10)
    again = np.asarray(again)
    np.testing.assert_array_equal(again[:2], [1, 10])
    assert (again[1:] >= 10).all() and (np.diff(again) > 0).all()
    np.testing.assert_array_equal(np.asarray(gsum)[0], [2.0, 2.0])

    # num_rows + slot must fit the id type: the cell's shapes do, a table a
    # few rows under 2^31 does not, and its tail then says "sorted" only.
    assert distinct_sentinels(2**26, 65536 * 39)
    v = 2**31 - 4
    assert distinct_sentinels(v, 4) and not distinct_sentinels(v, 8)
    tail = [line for scope, line in _hlo_ops(_lower_rows_tail(v, 1, 8), "scatter") if "fm.tail" in scope]
    assert len(tail) == 2
    assert all("indices_are_sorted=true" in line and "unique_indices=true" not in line for line in tail)


def test_rows_step_declares_its_row_descriptors():
    """The lowered rows step (the benchmark's train cell at a small size):
    no partial-lane scatter under ``fm.dedup``, and under ``fm.tail`` two
    table-shaped scatters that say sorted + unique and ONE table-shaped
    gather (the accumulator's; the table's old rows are never read)."""
    v, k, b, n = 4096, 8, 64, 5
    model = FMModel(vocabulary_size=v, factor_num=k, order=2)
    sd = jax.ShapeDtypeStruct
    state = jax.eval_shape(lambda: init_state(model, jax.random.key(0)))
    batch = Batch(
        labels=sd((b,), jnp.float32), ids=sd((b, n), jnp.int32), vals=sd((b, n), jnp.float32),
        fields=sd((b, 0), jnp.int32), weights=sd((b,), jnp.float32),
    )
    lowered = make_train_step(model, 0.05).lower(state, batch)
    table_shape = f"f32[{v},{1 + k}]"

    scatters = _hlo_ops(lowered, "scatter")
    tail = [line for scope, line in scatters if "fm.tail" in scope]
    assert len(tail) == 2 and all(line.split(" = ")[1].startswith(table_shape) for line in tail)
    assert all("indices_are_sorted=true" in line and "unique_indices=true" in line for line in tail)
    dedup = [line for scope, line in scatters if "fm.dedup" in scope]
    assert len(dedup) == 1 and dedup[0].split(" = ")[1].startswith(f"f32[{b * n},128]")
    assert "indices_are_sorted=true" in dedup[0]
    assert {scope.split("/")[1] for scope, _ in scatters} == {"fm.dedup", "fm.tail"}

    from_table = [
        (scope, line) for scope, line in _hlo_ops(lowered, "gather")
        if "slice_sizes={1,9}" in line and "fm.dedup" not in scope
    ]
    assert sorted(scope.split("/")[1] for scope, _ in from_table) == ["fm.gather", "fm.tail"]
    assert all("indices_are_sorted=true" in line for scope, line in from_table if "fm.tail" in scope)


def test_segment_sum_width_is_chosen_from_shapes():
    assert [segment_sum_lanes(16384 * 39, d) for d in _WIDTHS] == [128, 128, 128, 128, 256]
    # Two padded temporaries past a quarter of a v5e's memory: the narrow form.
    m = 65536 * 39  # the train cell's batch: 2.6e9 bytes at 128 lanes
    assert [segment_sum_lanes(m, 9), segment_sum_lanes(m, 130)] == [128, 130]
    assert [segment_sum_lanes(k * m, 9) for k in (1, 2, 4)] == [128, 9, 9]


def _synthetic_batches(rng, model_cls_hint, n_batches=30, B=64, N=6, V=100, F=4):
    """Linearly separable-ish synthetic CTR data: some ids are 'good'."""
    good = rng.permutation(V)[: V // 4]
    out = []
    for _ in range(n_batches):
        ids = rng.integers(0, V, size=(B, N)).astype(np.int32)
        vals = np.abs(rng.normal(size=(B, N)).astype(np.float32)) + 0.1
        fields = (np.arange(N)[None, :] % F * np.ones((B, 1))).astype(np.int32)
        signal = np.isin(ids, good).astype(np.float32)
        p = 1.0 / (1.0 + np.exp(-(2.0 * (signal * vals).sum(1) - vals.sum(1))))
        labels = (rng.random(B) < p).astype(np.float32)
        out.append(
            Batch(
                labels=jnp.asarray(labels),
                ids=jnp.asarray(ids),
                vals=jnp.asarray(vals),
                fields=jnp.asarray(fields),
                weights=jnp.ones((B,), jnp.float32),
            )
        )
    return out


@pytest.mark.parametrize(
    "model",
    [
        FMModel(vocabulary_size=100, factor_num=4, order=2, factor_lambda=1e-5, bias_lambda=1e-5),
        FMModel(vocabulary_size=100, factor_num=4, order=3),
        FFMModel(vocabulary_size=100, num_fields=4, factor_num=2),
        DeepFMModel(vocabulary_size=100, num_fields=6, factor_num=4, hidden_dims=(16, 16, 16)),
    ],
    ids=["fm2", "fm3", "ffm", "deepfm"],
)
def test_training_learns(model):
    rng = np.random.default_rng(42)
    batches = _synthetic_batches(rng, model)
    state = init_state(model, jax.random.key(0))
    step = make_train_step(model, learning_rate=0.1)
    predict = make_predict_step(model)

    first_losses, last_losses = [], []
    for epoch in range(3):
        for b in batches:
            state, loss = step(state, b)
            (first_losses if epoch == 0 else last_losses).append(float(loss))
    assert np.mean(last_losses) < np.mean(first_losses) * 0.98

    scores = np.concatenate([np.asarray(predict(state, b)) for b in batches])
    labels = np.concatenate([np.asarray(b.labels) for b in batches])
    assert auc(labels, scores) > 0.6


def test_auc_metric():
    labels = np.asarray([1, 0, 1, 0, 1])
    perfect = np.asarray([0.9, 0.1, 0.8, 0.2, 0.7])
    assert auc(labels, perfect) == 1.0
    assert auc(labels, 1 - perfect) == 0.0
    assert abs(auc(labels, np.full(5, 0.5)) - 0.5) < 1e-9
    w = np.asarray([1, 1, 0, 1, 1], np.float32)
    assert auc(labels, perfect, w) == 1.0


def test_auc_matches_bruteforce_pairwise_with_ties():
    # auc = (#[s_pos > s_neg] + 0.5 #[s_pos == s_neg]) / (n_pos n_neg);
    # integer scores force heavy ties through the average-rank path.
    rng = np.random.default_rng(123)
    for _ in range(5):
        labels = (rng.random(200) < 0.3).astype(np.float32)
        scores = rng.integers(0, 10, size=200).astype(np.float32)
        if labels.sum() in (0, 200):
            continue
        p, n = scores[labels > 0.5], scores[labels <= 0.5]
        brute = ((p[:, None] > n[None, :]).sum() + 0.5 * (p[:, None] == n[None, :]).sum()) / (
            len(p) * len(n)
        )
        np.testing.assert_allclose(auc(labels, scores), brute, rtol=1e-12)


class TestRowAccumulator:
    """adagrad_accumulator = row: [V, 1] grouped accumulator
    (accum += ||g_row||^2, one step size per row)."""

    def test_matches_numpy_oracle(self):
        from fast_tffm_tpu.optim import init_table_adagrad

        V, D, lr = 16, 3, 0.1
        rng = np.random.default_rng(0)
        table = jnp.asarray(rng.normal(size=(V, D)).astype(np.float32))
        state = init_table_adagrad(table, 0.5, "row")
        assert state.accum.shape == (V, 1)
        ids = jnp.asarray([3, 7, 3, 0], np.int32)  # id 3 repeats
        grads = jnp.asarray(rng.normal(size=(4, D)).astype(np.float32))

        new_table, new_state = sparse_adagrad_update(table, state, ids, grads, lr)

        exp_t = np.asarray(table).copy()
        exp_a = np.full((V, 1), 0.5, np.float32)
        for uid in (0, 3, 7):
            g = np.asarray(grads)[np.asarray(ids) == uid].sum(axis=0)
            exp_a[uid] += np.sum(g * g)
            exp_t[uid] -= lr * g / np.sqrt(exp_a[uid])
        np.testing.assert_allclose(np.asarray(new_table), exp_t, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(new_state.accum), exp_a, rtol=1e-6)

    def test_init_rejects_unknown_mode(self):
        from fast_tffm_tpu.optim import init_table_adagrad

        with pytest.raises(ValueError, match="element | row"):
            init_table_adagrad(jnp.zeros((4, 2)), 0.1, "banana")

    def test_training_learns_with_row_accumulator(self):
        model = FMModel(vocabulary_size=64, factor_num=4, order=2)
        state = init_state(model, jax.random.key(0), accumulator="row")
        assert state.table_opt.accum.shape == (64, 1)
        step = make_train_step(model, 0.1)
        rng = np.random.default_rng(1)
        ids = rng.integers(0, 64, size=(256, 5)).astype(np.int32)
        planted = rng.normal(size=64)  # linear signal: FM bias terms fit it
        labels = (planted[ids].sum(axis=1) > 0).astype(np.float32)
        batch = Batch(
            labels=jnp.asarray(labels),
            ids=jnp.asarray(ids),
            vals=jnp.ones((256, 5), jnp.float32),
            fields=jnp.zeros((256, 0), jnp.int32),
            weights=jnp.ones((256,), jnp.float32),
        )
        losses = []
        for _ in range(60):
            state, loss = step(state, batch)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.8  # actually learning

    @pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8-device mesh")
    @pytest.mark.parametrize("lookup", ["allgather", "alltoall"])
    def test_sharded_matches_single_device(self, lookup):
        from fast_tffm_tpu.parallel import (
            init_sharded_state,
            make_mesh,
            make_sharded_train_step,
        )

        model = FMModel(vocabulary_size=64, factor_num=4, order=2)
        rng = np.random.default_rng(2)
        B, N = 16, 4
        batch = Batch(
            labels=jnp.asarray(rng.integers(0, 2, size=(B,)).astype(np.float32)),
            ids=jnp.asarray(rng.integers(0, 64, size=(B, N)).astype(np.int32)),
            vals=jnp.asarray(rng.normal(size=(B, N)).astype(np.float32)),
            fields=jnp.zeros((B, 0), jnp.int32),
            weights=jnp.ones((B,), jnp.float32),
        )
        single = init_state(model, jax.random.key(0), accumulator="row")
        single, sloss = make_train_step(model, 0.05)(single, batch)

        mesh = make_mesh(4, 2)
        sharded = init_sharded_state(model, mesh, jax.random.key(0), accumulator="row")
        step = make_sharded_train_step(model, 0.05, mesh, lookup=lookup)
        sharded, mloss = step(sharded, batch)
        np.testing.assert_allclose(float(sloss), float(mloss), rtol=1e-6)
        # Few-ULP tolerance, not bit-identity: the single-device jit and
        # the shard_map SPMD step are DIFFERENT XLA programs, and on the
        # installed jax 0.4.37 CPU backend the row-mode Adagrad's
        # sum(g²)·rsqrt sequence fuses/rounds differently between them
        # (observed drift: 1/320 elements, 3.5e-10 abs ≈ 3 ULP at 1e-3).
        # Bit-identity IS still pinned where one program serves both
        # paths (tests/test_steps_per_call.py, device-cache parity).
        np.testing.assert_allclose(
            np.asarray(jax.device_get(single.table)),
            np.asarray(jax.device_get(sharded.table))[:64],
            rtol=1e-6, atol=1e-9,
        )
        np.testing.assert_allclose(
            np.asarray(jax.device_get(single.table_opt.accum)),
            np.asarray(jax.device_get(sharded.table_opt.accum))[:64],
            rtol=1e-6, atol=1e-9,
        )

    def test_restore_rejects_accumulator_mode_mismatch(self, tmp_path):
        from fast_tffm_tpu.checkpoint import restore_checkpoint, save_checkpoint

        model = FMModel(vocabulary_size=32, factor_num=4)
        elem = init_state(model, jax.random.key(0))
        path = str(tmp_path / "m.ckpt")
        save_checkpoint(path, elem, "npz")
        row_like = init_state(model, jax.random.key(0), accumulator="row")
        with pytest.raises(ValueError, match="adagrad_accumulator"):
            restore_checkpoint(path, row_like)
        # And the matching mode restores fine.
        restored = restore_checkpoint(path, elem)
        assert restored.table_opt.accum.shape == (32, 5)
