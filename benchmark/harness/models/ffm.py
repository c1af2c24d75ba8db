"""The field-aware factorization machine, as ``fast_tffm_tpu/models/ffm.py``'s
docstring states it, written as the plain double sum over pairs (not the
re-associated one-hot form the program computes).  Row [1 + F*k]: column 0 the
bias w_i, then F blocks of k: v_{i,f} is the factor that feature i keeps for
partners of field f.

    score = sum_i w_i x_i + sum_{i<j} <v_{i, f_j}, v_{j, f_i}> x_i x_j
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import sparse_step_bytes, uniform_factor_rows


class Model:
    reads_fields = True

    def __init__(self, ini: dict):
        self.vocab = int(ini["General"]["vocabulary_size"])
        self.k = int(ini["General"]["factor_num"])
        self.fields = int(ini["General"]["num_fields"])
        self.init_range = float(ini["Train"].get("init_value_range", 0.01))
        self.row_dim = 1 + self.fields * self.k

    def init_rows(self, rows):
        return uniform_factor_rows(self.vocab, self.fields * self.k, self.init_range, rows)

    def score(self, rows, vals, fields):
        b, n = vals.shape
        v = rows[..., 1:].reshape(b, n, self.fields, self.k)
        # toward[b, i, j] = v_{i, f_j}: the factor that i keeps for j's field.
        toward = v[jnp.arange(b)[:, None, None], jnp.arange(n)[None, :, None], fields[:, None, :]]
        dots = jnp.sum(toward * jnp.swapaxes(toward, 1, 2), axis=-1)
        pairs = dots * vals[:, :, None] * vals[:, None, :]
        i_before_j = jnp.triu(jnp.ones((n, n), pairs.dtype), 1)
        return jnp.sum(rows[..., 0] * vals, axis=-1) + jnp.sum(pairs * i_before_j, axis=(1, 2))

    def step_bytes(self, ids) -> tuple[int, int]:
        """The sparse step's bytes at this row width, element-wise
        accumulator, and the field ids read."""
        total, uniq = sparse_step_bytes(ids, self.row_dim, self.row_dim)
        return total + 4 * int(np.asarray(ids).size), uniq

    def step_flops(self, rows: int, nnz: int, uniq: int) -> int:
        """A pair's product forward (a dot of k, two values, one sum: 2k + 3)
        and twice that backward; the bias term 4 an occurrence; Adagrad 6 an
        element of the unique rows."""
        pairs = nnz * (nnz - 1) // 2
        return int(rows * (pairs * 3 * (2 * self.k + 3) + nnz * 4) + uniq * self.row_dim * 6)

    def score_bytes(self, rows: int, nnz: int) -> int:
        """A scored row's ids, values and field ids read, its table rows
        gathered, one score written."""
        return int(rows * (nnz * (4 + 4 + 4 + self.row_dim * 4) + 4))
