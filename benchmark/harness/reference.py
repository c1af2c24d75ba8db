"""The plain reference, what every model shares of it: straightforward
``jax.numpy``, float32, no kernels, no dedup, no packing.  It imports nothing
of the program and takes nothing the program has made: the initial rows and
the score of gathered rows are the cell's ``model`` (``models/<name>.py``), the
rows come from the harness's generator.

Row layout: column 0 the bias w_i, the other columns the factors v_i.

    loss  = mean log(1 + exp(-y' score)) + bias_lambda |w|^2 + factor_lambda |v|^2
            (L2 over the gathered occurrences, padding masked)
    Adagrad: accum += g^2 ; param -= lr * g / sqrt(accum), g summed per row.

``dtype`` is the control's knob: bfloat16 is the nearest precision below the
float32 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _loss(score, table, idx, vals, fields, labels, bias_lambda, factor_lambda, batch=None):
    rows = table[idx]
    s = score(rows, vals, fields)
    per = jnp.maximum(s, 0) - s * labels + jnp.log1p(jnp.exp(-jnp.abs(s)))
    data = jnp.sum(per) / (batch or labels.shape[0])
    masked = rows * (vals != 0).astype(rows.dtype)[..., None]
    reg = bias_lambda * jnp.sum(masked[..., 0] ** 2) + factor_lambda * jnp.sum(masked[..., 1:] ** 2)
    return data + reg, data


def train_steps(score, table0, batches, lr, accum0, bias_lambda, factor_lambda, dtype=jnp.float32, owner=None):
    """Follow ``batches`` = [(idx[B,N] into table0, vals, fields, labels)] with dense
    autodiff and dense Adagrad over the compact table.  Returns per step
    (data_loss, table, accum).

    ``owner`` (the shard that holds each row of table0, [rows]) plants the
    fault of a row-sharded step whose exchange between chips is left out: the
    batch is cut into as many micro-batches as there are shards, and shard r
    applies to the rows it owns only what its own micro-batch contributes."""
    lam = (jnp.asarray(bias_lambda, dtype), jnp.asarray(factor_lambda, dtype))
    loss = jax.value_and_grad(functools.partial(_loss, score), has_aux=True)

    def grad(table, idx, vals, fields, labels):
        if owner is None:
            return loss(table, idx, vals, fields, labels, *lam)
        shards, n = int(owner.max()) + 1, labels.shape[0]
        data, g = 0.0, jnp.zeros_like(table)
        for r, part in enumerate(zip(*(jnp.split(a, shards) for a in (idx, vals, fields, labels)))):
            (_, d), gr = loss(table, *part, *lam, batch=n)
            data, g = data + d, g + jnp.where((jnp.asarray(owner) == r)[:, None], gr, 0)
        return (None, data), g

    @jax.jit
    def step(table, accum, idx, vals, fields, labels):
        (_, data), g = grad(table, idx, vals.astype(dtype), fields, labels.astype(dtype))
        accum = accum + g * g
        return data, table - jnp.asarray(lr, dtype) * g / jnp.sqrt(accum), accum

    table = jnp.asarray(table0).astype(dtype)
    accum = jnp.full_like(table, accum0)
    out = []
    with jax.default_matmul_precision("highest"):
        for batch in batches:
            data, table, accum = step(table, accum, *map(jnp.asarray, batch))
            out.append((data, table, accum))
    return out


def score_rows(score, table_rows, idx, vals, fields, dtype=jnp.float32):
    """Served score of each row: sigmoid(score) over rows gathered from the
    compact ``table_rows`` by ``idx``."""
    with jax.default_matmul_precision("highest"):
        rows = jnp.asarray(table_rows).astype(dtype)[jnp.asarray(idx)]
        return jax.nn.sigmoid(score(rows, jnp.asarray(vals).astype(dtype), jnp.asarray(fields))).astype(jnp.float32)
