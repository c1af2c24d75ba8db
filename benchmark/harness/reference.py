"""The plain reference: a 2nd-order factorization machine in straightforward
``jax.numpy``, float32, no kernels, no dedup, no packing.  It imports nothing
of the program and takes nothing the program has made: the initial table is
drawn here by the recipe the program documents (uniform factors from
``split(key(0))[0]``, zero biases), the rows come from the harness's generator.

Row layout: column 0 the bias w_i, columns 1: the factors v_i.

    score = sum_i w_i x_i + 1/2 sum_f [(sum_i v_if x_i)^2 - sum_i (v_if x_i)^2]
    loss  = mean log(1 + exp(-y' score)) + bias_lambda |w|^2 + factor_lambda |v|^2
            (L2 over the gathered occurrences, padding masked)
    Adagrad: accum += g^2 ; param -= lr * g / sqrt(accum), g summed per row.

``dtype`` is the control's knob: bfloat16 is the nearest precision below the
float32 the configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def init_rows(vocab: int, factor_num: int, init_range: float, rows: np.ndarray) -> jax.Array:
    """Rows ``rows`` of the initial [vocab, 1 + factor_num] table."""
    k1, _ = jax.random.split(jax.random.key(0))

    @jax.jit
    def draw(idx):
        factors = jax.random.uniform(
            k1, (vocab, factor_num), minval=-init_range, maxval=init_range, dtype=jnp.float32
        )
        f = factors[idx]
        return jnp.concatenate([jnp.zeros((f.shape[0], 1), jnp.float32), f], axis=-1)

    return draw(jnp.asarray(rows, jnp.int32))


def fm_score(rows, vals):
    bias, v = rows[..., 0], rows[..., 1:]
    vx = v * vals[..., None]
    s1 = jnp.sum(vx, axis=1)
    s2 = jnp.sum(vx * vx, axis=1)
    return jnp.sum(bias * vals, axis=-1) + 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)


def _loss(table, idx, vals, labels, bias_lambda, factor_lambda, batch=None):
    rows = table[idx]
    s = fm_score(rows, vals)
    per = jnp.maximum(s, 0) - s * labels + jnp.log1p(jnp.exp(-jnp.abs(s)))
    data = jnp.sum(per) / (batch or labels.shape[0])
    masked = rows * (vals != 0).astype(rows.dtype)[..., None]
    reg = bias_lambda * jnp.sum(masked[..., 0] ** 2) + factor_lambda * jnp.sum(masked[..., 1:] ** 2)
    return data + reg, data


def train_steps(table0, batches, lr, accum0, bias_lambda, factor_lambda, dtype=jnp.float32, owner=None):
    """Follow ``batches`` = [(idx[B,N] into table0, vals, labels)] with dense
    autodiff and dense Adagrad over the compact table.  Returns per step
    (data_loss, table, accum).

    ``owner`` (the shard that holds each row of table0, [rows]) plants the
    fault of a row-sharded step whose exchange between chips is left out: the
    batch is cut into as many micro-batches as there are shards, and shard r
    applies to the rows it owns only what its own micro-batch contributes."""
    lam = (jnp.asarray(bias_lambda, dtype), jnp.asarray(factor_lambda, dtype))

    def grad(table, idx, vals, labels):
        if owner is None:
            return jax.value_and_grad(_loss, has_aux=True)(table, idx, vals, labels, *lam)
        shards, n = int(owner.max()) + 1, labels.shape[0]
        data, g = 0.0, jnp.zeros_like(table)
        for r, part in enumerate(zip(*(jnp.split(a, shards) for a in (idx, vals, labels)))):
            (_, d), gr = jax.value_and_grad(_loss, has_aux=True)(table, *part, *lam, batch=n)
            data, g = data + d, g + jnp.where((jnp.asarray(owner) == r)[:, None], gr, 0)
        return (None, data), g

    @jax.jit
    def step(table, accum, idx, vals, labels):
        (_, data), g = grad(table, idx, vals.astype(dtype), labels.astype(dtype))
        accum = accum + g * g
        return data, table - jnp.asarray(lr, dtype) * g / jnp.sqrt(accum), accum

    table = jnp.asarray(table0).astype(dtype)
    accum = jnp.full_like(table, accum0)
    out = []
    with jax.default_matmul_precision("highest"):
        for idx, vals, labels in batches:
            data, table, accum = step(table, accum, jnp.asarray(idx), jnp.asarray(vals), jnp.asarray(labels))
            out.append((data, table, accum))
    return out


def score_rows(table_rows, idx, vals, dtype=jnp.float32):
    """Served score of each row: sigmoid(fm_score) over rows gathered from the
    compact ``table_rows`` by ``idx``."""
    with jax.default_matmul_precision("highest"):
        rows = jnp.asarray(table_rows).astype(dtype)[jnp.asarray(idx)]
        return jax.nn.sigmoid(fm_score(rows, jnp.asarray(vals).astype(dtype))).astype(jnp.float32)
