"""What a window knows about the model, one module a model.

A configuration's file names its module (``"harness_model": "fm2"``) and
``cells.load_cell`` builds that module's ``Model`` from the cell's INI.  A
module is plain ``jax.numpy`` in float32 and imports nothing of the program:

    Model(ini)                       the cell's INI sections, as a dict
    .reads_fields                    whether the score reads field ids
    .row_dim                         float32 columns of a table row; column 0 is
                                     the bias, the rest are factors
    .init_rows(rows)                 those rows of the initial table, by the
                                     recipe the program documents
    .score(rows, vals, fields)       [B, N, row_dim], [B, N], i32[B, N] -> [B]
    .step_bytes(ids)                 (bytes, unique ids) one train step must move
    .step_flops(rows, nnz, uniq)     FLOPs one train step must make
    .score_bytes(rows, nnz)          bytes that scoring ``rows`` rows must move

A model with parameters OUTSIDE the table (dense leaves: an MLP's weights)
also defines

    .init_dense()                    {name: float32 array}, the initial leaves
                                     by the recipe the program documents
                                     (``trainer.init_state``: ``k1, k2 =
                                     split(key(0))``, the dense leaves from k2)
    .score(rows, vals, fields, dense)    the leaves are the FOURTH argument

and both windows then carry them: the train window reads the program's
``state.dense`` back beside the table's rows and holds it to the reference by
two numbers of its own (``train.compare``), the serve window saves them in the
model file it writes and scores the pool under them.  A module that defines no
``init_dense`` has no dense leaves, its ``score`` takes three arguments and
nothing of the above is done for it (``dense_leaves`` below is how a window
asks).  ``fm2``, ``fm2_hashed``, ``hofm``, ``ffm`` and ``ffm_f32`` have none;
``deepfm`` has.

The loss, the L2 term (over the gathered rows alone), autodiff, Adagrad (the
table's and the dense leaves' alike) and the planted faults are common
(``reference.py``); the peaks and ``least_seconds`` are ``peaks.py``.
"""

from __future__ import annotations

import numpy as np


def dense_leaves(model) -> dict:
    """The model's initial dense leaves, ``{}`` for a module without any."""
    init = getattr(model, "init_dense", None)
    return dict(init()) if init else {}


def uniform_factor_rows(vocab: int, cols: int, init_range: float, rows: np.ndarray):
    """Rows ``rows`` of the initial [vocab, 1 + cols] table every shipped
    model draws: uniform factors from ``split(key(0))[0]``, zero biases."""
    import jax
    import jax.numpy as jnp

    k1, _ = jax.random.split(jax.random.key(0))

    @jax.jit
    def draw(idx):
        factors = jax.random.uniform(k1, (vocab, cols), minval=-init_range, maxval=init_range, dtype=jnp.float32)
        f = factors[idx]
        return jnp.concatenate([jnp.zeros((f.shape[0], 1), jnp.float32), f], axis=-1)

    return draw(jnp.asarray(rows, jnp.int32))


def sparse_step_bytes(ids: np.ndarray, row_dim: int, accum_cols: int) -> tuple[int, int]:
    """Copy of ``profiling.modeled_step_bytes``: the HBM bytes one sparse
    train step cannot avoid (ids read, gather, backward re-read, row-gradient
    and segment-sum writes, table and accumulator read-modify-write over the
    unique rows).  Returns (bytes, unique ids)."""
    ids = np.asarray(ids)
    m = int(ids.size)
    uniq = int(np.unique(ids).size)
    row = int(row_dim) * 4
    total = m * 4 + 4 * m * row + 2 * uniq * row + 2 * uniq * int(accum_cols) * 4
    return int(total), uniq
