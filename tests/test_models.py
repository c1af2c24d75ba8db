"""Model scoring oracles (FFM O(N²) brute force, DeepFM composition)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.models import Batch, DeepFMModel, FFMModel, FMModel
from fast_tffm_tpu.ops.fm import fm_score


def _batch(rng, B=4, N=5, pad_tail=1, num_fields=3):
    ids = rng.integers(0, 50, size=(B, N)).astype(np.int32)
    vals = rng.normal(size=(B, N)).astype(np.float32)
    fields = rng.integers(0, num_fields, size=(B, N)).astype(np.int32)
    if pad_tail:
        vals[:, -pad_tail:] = 0.0
    return Batch(
        labels=jnp.asarray(rng.integers(0, 2, size=(B,)).astype(np.float32)),
        ids=jnp.asarray(ids),
        vals=jnp.asarray(vals),
        fields=jnp.asarray(fields),
        weights=jnp.ones((B,), jnp.float32),
    )


def _ffm_oracle(rows, batch, F, k):
    """The O(N²) pair sum in float64.  A slot whose field id lies outside
    [0, F) keeps its linear term and joins no pair (its one-hot row is zero)."""
    rows = np.asarray(rows, np.float64)
    vals = np.asarray(batch.vals, np.float64)
    fields = np.asarray(batch.fields)
    B, N = vals.shape
    out = np.zeros(B)
    for b in range(B):
        w = rows[b, :, 0]
        v = rows[b, :, 1:].reshape(N, F, k)
        s = float(np.dot(w, vals[b]))
        known = [i for i in range(N) if 0 <= fields[b, i] < F]
        for i in known:
            for j in known:
                if i < j:
                    s += float(
                        np.dot(v[i, fields[b, j]], v[j, fields[b, i]])
                        * vals[b, i]
                        * vals[b, j]
                    )
        out[b] = s
    return out


def _ffm_oracle_jnp(rows, vals, fields, F, k):
    """The same pair sum pair by pair in float32 ``jax.numpy``, for ``jax.grad``
    (``fields`` is a numpy array: the gathers are static)."""
    B, N = fields.shape
    v = rows[..., 1:].reshape(B, N, F, k)
    known = (fields >= 0) & (fields < F)
    to = np.clip(fields, 0, F - 1)
    bi = np.arange(B)
    s = jnp.sum(rows[..., 0] * vals, axis=-1)
    for i in range(N):
        for j in range(i + 1, N):
            dot = jnp.sum(v[bi, i, to[:, j]] * v[bi, j, to[:, i]], axis=-1)
            s = s + jnp.asarray(known[:, i] & known[:, j], jnp.float32) * dot * vals[:, i] * vals[:, j]
    return s


# (F, N, k, the batch's field ids or None for random ones, padded slots at the tail)
_FFM_CASES = {
    "one_feature_a_field": (5, 5, 4, np.arange(5), 0),
    "two_features_of_one_field": (3, 5, 4, np.array([0, 1, 1, 2, 0]), 0),
    "padded_slots_and_a_field_outside": (4, 6, 4, np.array([2, 7, 0, -1, 3, 1]), 2),
    "k8_random_fields": (3, 5, 8, None, 1),
}
_FFM_TOLERANCE = {"float32": 1e-6, "bfloat16": 2e-2}  # a gradient row's, of the gradient's norm
_FFM_SCORE_TOLERANCE = {"float32": 1e-5, "bfloat16": 2e-2}  # of the scores' norm


def _ffm_case(name, compute_dtype, B=8):
    F, N, k, fields, pad_tail = _FFM_CASES[name]
    rng = np.random.default_rng(sorted(_FFM_CASES).index(name))
    model = FFMModel(vocabulary_size=50, num_fields=F, factor_num=k, compute_dtype=compute_dtype)
    batch = _batch(rng, B=B, N=N, pad_tail=pad_tail, num_fields=F)
    if fields is not None:
        batch = dataclasses.replace(batch, fields=jnp.asarray(np.broadcast_to(fields, (B, N)).astype(np.int32)))
    # Random rows (init factors are tiny; use bigger values to exercise math).
    rows = jnp.asarray(rng.normal(size=(B, N, model.row_dim)).astype(np.float32))
    return model, batch, rows


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_FFM_CASES))
def test_ffm_matches_bruteforce(case, compute_dtype):
    model, batch, rows = _ffm_case(case, compute_dtype)
    got = np.asarray(model.score(rows, {}, batch))
    want = _ffm_oracle(rows, batch, model.num_fields, model.factor_num)
    assert np.linalg.norm(got - want) <= _FFM_SCORE_TOLERANCE[compute_dtype] * np.linalg.norm(want)
    assert model.init_table(jax.random.key(0)).shape == (50, model.row_dim)


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_FFM_CASES))
def test_ffm_gradient_matches_the_oracles(case, compute_dtype):
    """``jax.grad`` through the hand-written backward of ``models/ffm.py``
    against ``jax.grad`` of the pair-by-pair sum, row by row of the gathered
    rows (and by slot for the values): no row is farther off than the
    tolerance times the whole gradient's norm."""
    model, batch, rows = _ffm_case(case, compute_dtype)
    F, k = model.num_fields, model.factor_num
    fields = np.asarray(batch.fields)
    g_out = jnp.asarray(np.random.default_rng(7).normal(size=rows.shape[:1]).astype(np.float32))
    np.testing.assert_allclose(  # the two oracles are one function
        np.asarray(_ffm_oracle_jnp(rows, batch.vals, fields, F, k)), _ffm_oracle(rows, batch, F, k), rtol=1e-4, atol=1e-4
    )
    ours = lambda r, x: jnp.vdot(model.score(r, {}, dataclasses.replace(batch, vals=x)), g_out)
    plain = lambda r, x: jnp.vdot(_ffm_oracle_jnp(r, x, fields, F, k), g_out)
    got = jax.grad(ours, argnums=(0, 1))(rows, batch.vals)
    want = jax.grad(plain, argnums=(0, 1))(rows, batch.vals)
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        by_row = np.linalg.norm((g - w).reshape(g.shape[0], g.shape[1], -1), axis=-1)
        assert by_row.max() <= _FFM_TOLERANCE[compute_dtype] * np.linalg.norm(w), (by_row.max(), np.linalg.norm(w))
    pad = np.asarray(batch.vals) == 0
    assert not np.asarray(got[0])[pad].any()  # a padded slot's row gets no gradient at all


def test_deepfm_is_fm_plus_mlp():
    rng = np.random.default_rng(1)
    model = DeepFMModel(vocabulary_size=50, num_fields=5, factor_num=4, hidden_dims=(8, 8, 8))
    batch = _batch(rng, N=5, pad_tail=0)
    rows = jnp.asarray(rng.normal(size=(4, 5, model.row_dim)).astype(np.float32))
    dense = model.init_dense(jax.random.key(1))
    got = np.asarray(model.score(rows, dense, batch))
    fm_part = np.asarray(fm_score(rows, batch.vals, order=2))
    emb = np.asarray(rows[..., 1:] * batch.vals[..., None]).reshape(4, -1)
    x = emb
    for li in range(4):
        x = x @ np.asarray(dense[f"w{li}"]) + np.asarray(dense[f"b{li}"])
        if li < 3:
            x = np.maximum(x, 0.0)
    np.testing.assert_allclose(got, fm_part + x[:, 0], rtol=1e-4)


def test_fm_model_score_uses_kernel():
    rng = np.random.default_rng(2)
    model = FMModel(vocabulary_size=50, factor_num=4, order=3)
    batch = _batch(rng)
    table = model.init_table(jax.random.key(0))
    rows = table[batch.ids]
    got = np.asarray(model.score(rows, {}, batch))
    want = np.asarray(fm_score(rows, batch.vals, order=3))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_regularization_masks_padding():
    rng = np.random.default_rng(3)
    model = FMModel(vocabulary_size=50, factor_num=4, factor_lambda=0.1, bias_lambda=0.2)
    batch = _batch(rng, pad_tail=2)
    rows = jnp.asarray(rng.normal(size=(4, 5, model.row_dim)).astype(np.float32))
    reg = float(model.regularization(rows, {}, batch))
    mask = np.asarray(batch.vals) != 0
    r = np.asarray(rows)
    want = 0.2 * (r[..., 0][mask] ** 2).sum() + 0.1 * ((r[..., 1:] ** 2).sum(-1)[mask]).sum()
    np.testing.assert_allclose(reg, want, rtol=1e-5)


def test_deepfm_bfloat16_compute_close_to_f32():
    # bf16 is a COMPUTE dtype only: params stay f32, matmuls accumulate f32.
    # Scores must track the f32 model within bf16 rounding, and gradients
    # must stay finite f32 (the optimizer never sees bf16).
    rng = np.random.default_rng(4)
    kw = dict(vocabulary_size=50, num_fields=5, factor_num=4, hidden_dims=(16, 16, 16))
    m32 = DeepFMModel(**kw)
    m16 = DeepFMModel(**kw, compute_dtype="bfloat16")
    batch = _batch(rng, N=5, pad_tail=0)
    rows = jnp.asarray(rng.normal(size=(4, 5, m32.row_dim)).astype(np.float32))
    dense = m32.init_dense(jax.random.key(1))
    s32 = np.asarray(m32.score(rows, dense, batch))
    s16 = np.asarray(m16.score(rows, dense, batch))
    assert s16.dtype == np.float32
    np.testing.assert_allclose(s16, s32, rtol=3e-2, atol=3e-2)

    g = jax.grad(lambda d: jnp.sum(m16.score(rows, d, batch)))(dense)
    for leaf in jax.tree.leaves(g):
        assert leaf.dtype == jnp.float32
        assert bool(jnp.all(jnp.isfinite(leaf)))
