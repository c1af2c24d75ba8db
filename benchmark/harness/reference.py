"""The plain reference, what every model shares of it: straightforward
``jax.numpy``, float32, no kernels, no dedup, no packing.  It imports nothing
of the program and takes nothing the program has made: the initial rows, the
dense leaves (where a model has any) and the score of gathered rows are the
cell's ``model`` (``models/<name>.py``), the rows come from the harness's
generator.

Row layout: column 0 the bias w_i, the other columns the factors v_i.

    loss  = mean log(1 + exp(-y' score)) + bias_lambda |w|^2 + factor_lambda |v|^2
            (L2 over the gathered occurrences, padding masked)
    Adagrad: accum += g^2 ; param -= lr * g / sqrt(accum), g summed per row.

A model's dense leaves (parameters outside the table: ``models/__init__.py``)
are differentiated with the table by the one ``_loss`` and take the SAME
Adagrad, element-wise, their accumulators started at ``init_accumulator_value``
as the table's are: the expression of the program's
``optim.dense_adagrad_update`` (``optim.py:118``, at ``decay`` 1; the state
from ``trainer.init_dense_state``, ``trainer.py:80``).  The L2 term stays over
the gathered rows alone, as the program's ``regularization`` has it
(``models/deepfm.py``: "regularizes only the FM parameters").

``dtype`` is the control's knob: bfloat16 is the nearest precision below the
float32 the configurations state; it covers table and dense leaves alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _loss(score, table, dense, idx, vals, fields, labels, bias_lambda, factor_lambda, batch=None):
    rows = table[idx]
    s = score(rows, vals, fields, dense) if dense else score(rows, vals, fields)
    per = jnp.maximum(s, 0) - s * labels + jnp.log1p(jnp.exp(-jnp.abs(s)))
    data = jnp.sum(per) / (batch or labels.shape[0])
    masked = rows * (vals != 0).astype(rows.dtype)[..., None]
    reg = bias_lambda * jnp.sum(masked[..., 0] ** 2) + factor_lambda * jnp.sum(masked[..., 1:] ** 2)
    return data + reg, data


def train_steps(score, table0, batches, lr, accum0, bias_lambda, factor_lambda, dtype=jnp.float32, owner=None,
                dense0=None, dense_frozen=False):
    """Follow ``batches`` = [(idx[B,N] into table0, vals, fields, labels)] with dense
    autodiff and dense Adagrad over the compact table and over the dense
    leaves ``dense0`` (a dict; none: ``score`` takes three arguments).
    Returns per step (data_loss, table, accum, dense, dense_accum).

    ``owner`` (the shard that holds each row of table0, [rows]) plants the
    fault of a row-sharded step whose exchange between chips is left out: the
    batch is cut into as many micro-batches as there are shards, and shard r
    applies to the rows it owns only what its own micro-batch contributes;
    every shard applies the whole gradient of a dense leaf, as a replicated
    leaf's all-reduce would.  ``dense_frozen`` plants the fault of a step that
    drops the dense leaves' gradient: they and their accumulators stay as
    they began."""
    lam = (jnp.asarray(bias_lambda, dtype), jnp.asarray(factor_lambda, dtype))
    loss = jax.value_and_grad(functools.partial(_loss, score), argnums=(0, 1), has_aux=True)

    def grad(table, dense, idx, vals, fields, labels):
        if owner is None:
            return loss(table, dense, idx, vals, fields, labels, *lam)
        shards, n = int(owner.max()) + 1, labels.shape[0]
        data, g, gd = 0.0, jnp.zeros_like(table), jax.tree.map(jnp.zeros_like, dense)
        for r, part in enumerate(zip(*(jnp.split(a, shards) for a in (idx, vals, fields, labels)))):
            (_, d), (gr, gdr) = loss(table, dense, *part, *lam, batch=n)
            data, g = data + d, g + jnp.where((jnp.asarray(owner) == r)[:, None], gr, 0)
            gd = jax.tree.map(jnp.add, gd, gdr)
        return (None, data), (g, gd)

    @jax.jit
    def step(table, accum, dense, dense_accum, idx, vals, fields, labels):
        (_, data), (g, gd) = grad(table, dense, idx, vals.astype(dtype), fields, labels.astype(dtype))
        accum = accum + g * g
        table = table - jnp.asarray(lr, dtype) * g / jnp.sqrt(accum)
        if not dense_frozen:
            dense_accum = jax.tree.map(lambda a, g: a + g * g, dense_accum, gd)
            dense = jax.tree.map(lambda p, g, a: p - jnp.asarray(lr, dtype) * g / jnp.sqrt(a), dense, gd, dense_accum)
        return data, table, accum, dense, dense_accum

    table = jnp.asarray(table0).astype(dtype)
    accum = jnp.full_like(table, accum0)
    dense = {k: jnp.asarray(v).astype(dtype) for k, v in (dense0 or {}).items()}
    dense_accum = jax.tree.map(lambda p: jnp.full_like(p, accum0), dense)
    out = []
    with jax.default_matmul_precision("highest"):
        for batch in batches:
            data, table, accum, dense, dense_accum = step(table, accum, dense, dense_accum, *map(jnp.asarray, batch))
            out.append((data, table, accum, dense, dense_accum))
    return out


def score_rows(score, table_rows, idx, vals, fields, dtype=jnp.float32, dense=None):
    """Served score of each row: sigmoid(score) over rows gathered from the
    compact ``table_rows`` by ``idx``, under the dense leaves ``dense`` where
    the model has any."""
    with jax.default_matmul_precision("highest"):
        rows = jnp.asarray(table_rows).astype(dtype)[jnp.asarray(idx)]
        more = ({k: jnp.asarray(v).astype(dtype) for k, v in dense.items()},) if dense else ()
        return jax.nn.sigmoid(score(rows, jnp.asarray(vals).astype(dtype), jnp.asarray(fields), *more)).astype(jnp.float32)
