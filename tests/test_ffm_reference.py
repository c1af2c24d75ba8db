"""The field-aware model at libffm's Criteo widths against a plain reference.

The reference is the double sum over pairs i < j with the bias term, the
logistic loss with masked L2, ``jax.grad`` of it and one dense Adagrad step,
in float32 ``jax.numpy``.  It shares no code with ``models/ffm.py`` (which
re-associates the sum into a one-hot einsum) or ``optim.py`` (which dedups
and scatters): only the initial table is the program's.

At the PUBLISHED widths: 39 fields, k = 4, rows of 157 float32, which is past
one 128-lane tile, so ``dedup_rows`` takes its 256-lane segment sum.  The
vocabulary (2^12) and the batch (64) are small.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.models import Batch, FFMModel
from fast_tffm_tpu.optim import segment_sum_lanes
from fast_tffm_tpu.trainer import init_state, make_train_step

F, K, V, B = 39, 4, 1 << 12, 64
LR, LAM, ACC0 = 0.2, 2e-5, 1.0  # libffm's eta and lambda, accumulators started at 1
EPS = float(np.finfo(np.float32).eps)


def _pair_sum_score(rows, vals, fields):
    """score[b] = sum_i w_i x_i + sum_{i<j} <v_{i, f_j}, v_{j, f_i}> x_i x_j."""
    b, n = vals.shape
    v = rows[..., 1:].reshape(b, n, F, K)
    bi = jnp.arange(b)[:, None, None]
    ii = jnp.arange(n)[None, :, None]
    toward = v[bi, ii, fields[:, None, :]]  # toward[b, i, j] = v_{i, f_j}
    dots = jnp.sum(toward * jnp.swapaxes(toward, 1, 2), axis=-1) * vals[:, :, None] * vals[:, None, :]
    return jnp.sum(rows[..., 0] * vals, axis=-1) + jnp.sum(jnp.triu(dots, 1), axis=(1, 2))


def _reference_loss(table, ids, vals, fields, labels):
    rows = table[ids]
    s = _pair_sum_score(rows, vals, fields)
    data = jnp.mean(jnp.maximum(s, 0) - s * labels + jnp.log1p(jnp.exp(-jnp.abs(s))))
    seen = rows * (vals != 0)[..., None]  # padding slots gather a row that is not penalised
    return data + LAM * jnp.sum(seen[..., 0] ** 2) + LAM * jnp.sum(seen[..., 1:] ** 2), data


@jax.jit
def _reference_step(table, accum, ids, vals, fields, labels):
    (_, data), g = jax.value_and_grad(_reference_loss, has_aux=True)(table, ids, vals, fields, labels)
    accum = accum + g * g
    return data, table - LR * g / jnp.sqrt(accum), accum


def _batches(seed, steps=3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        ids = rng.integers(0, V, (B, F)).astype(np.int32)
        ids[:, :4] = rng.integers(0, 8, (B, 4))  # hot rows: duplicates inside a batch and across steps
        vals = np.round(np.abs(rng.normal(0.5, 0.35, (B, F))) + 0.05, 4).astype(np.float32)
        vals[:, -2:] = 0.0  # two padding slots a row
        fields = np.broadcast_to(np.arange(F, dtype=np.int32), (B, F)).copy()
        fields[:, 5] = fields[:, 6]  # a field that repeats within a row
        labels = (rng.random(B) < 0.3).astype(np.float32)
        out.append((ids, vals, fields, labels))
    return out


def _follow(seed, compute_dtype):
    """[(loss gap, widest table gap, widest accumulator gap)] after steps 1, 2, 3, the gaps in
    units of one float32 ULP of the array's largest entry; and the largest change of a parameter."""
    model = FFMModel(vocabulary_size=V, num_fields=F, factor_num=K, factor_lambda=LAM, bias_lambda=LAM,
                     compute_dtype=compute_dtype)
    state = init_state(model, jax.random.key(0), ACC0)
    t0 = np.asarray(state.table)
    table, accum = jnp.asarray(t0), jnp.asarray(np.asarray(state.table_opt.accum))  # the step donates its state
    step = make_train_step(model, LR)
    gaps = []
    for ids, vals, fields, labels in _batches(seed):
        batch = Batch(labels=jnp.asarray(labels), ids=jnp.asarray(ids), vals=jnp.asarray(vals),
                      fields=jnp.asarray(fields), weights=jnp.ones(B, jnp.float32))
        state, loss = step(state, batch)
        want_loss, table, accum = _reference_step(table, accum, ids, vals, fields, labels)
        ulps = lambda got, want: float(np.max(np.abs(np.asarray(got) - np.asarray(want))) / (EPS * np.max(np.abs(np.asarray(want)))))
        gaps.append((abs(float(loss) - float(want_loss)) / (EPS * float(want_loss)),
                     ulps(state.table, table), ulps(state.table_opt.accum, accum)))
    return gaps, float(np.max(np.abs(np.asarray(table) - t0)))


# What rounding leaves between two float32 evaluations of one sum in two
# orders (the one-hot einsum against the pair loop; the segment sum over
# sorted occurrences against autodiff's scatter-add), over seeds 0-4: the
# loss and the accumulator equal to the bit, the table within 1.42 ULP of its
# largest entry; the limits leave about three times that.  With the
# interaction's inputs rounded to bfloat16 the table reads 65 to 220 ULP (the
# loss hardly moves: at the initial factors the interaction is a thousandth of
# the score), so the table is what tells the two apart.
LOSS_ULPS, TABLE_ULPS, ACCUM_ULPS = 2, 4, 2


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ffm_train_step_follows_the_plain_reference_at_criteo_widths(seed):
    assert FFMModel(vocabulary_size=V, num_fields=F, factor_num=K).row_dim == 157
    assert segment_sum_lanes(B * F, 157) == 256  # the wide route of dedup_rows runs
    gaps, moved = _follow(seed, "float32")
    assert moved > 1e3 * EPS * 0.01  # the steps moved parameters by far more than the tolerance
    for loss_gap, table_gap, accum_gap in (gaps[0], gaps[2]):  # after one step and after three
        assert loss_gap <= LOSS_ULPS and table_gap <= TABLE_ULPS and accum_gap <= ACCUM_ULPS

    control, _ = _follow(seed, "bfloat16")
    assert all(table_gap > 10 * TABLE_ULPS for _, table_gap, _ in (control[0], control[2]))
