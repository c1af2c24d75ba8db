"""The 2nd-order factorization machine.  Row [1 + factor_num]: column 0 the
bias w_i, columns 1: the factors v_i.

    score = sum_i w_i x_i + 1/2 sum_f [(sum_i v_if x_i)^2 - sum_i (v_if x_i)^2]
"""

from __future__ import annotations

import jax.numpy as jnp

from . import sparse_step_bytes, uniform_factor_rows


class Model:
    reads_fields = False

    def __init__(self, ini: dict):
        self.vocab = int(ini["General"]["vocabulary_size"])
        self.k = int(ini["General"]["factor_num"])
        self.init_range = float(ini["Train"].get("init_value_range", 0.01))
        self.row_dim = 1 + self.k

    def init_rows(self, rows):
        return uniform_factor_rows(self.vocab, self.k, self.init_range, rows)

    def score(self, rows, vals, fields):
        del fields
        bias, v = rows[..., 0], rows[..., 1:]
        vx = v * vals[..., None]
        s1 = jnp.sum(vx, axis=1)
        s2 = jnp.sum(vx * vx, axis=1)
        return jnp.sum(bias * vals, axis=-1) + 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)

    def step_bytes(self, ids) -> tuple[int, int]:
        """An element-wise accumulator, as the train cell's configuration has it."""
        return sparse_step_bytes(ids, self.row_dim, self.row_dim)

    def step_flops(self, rows: int, nnz: int, uniq: int) -> int:
        """Forward and backward of the interaction per occurrence (about 7 per
        factor and 4 for the bias) and 6 per element of Adagrad."""
        return int(rows * nnz * (7 * self.k + 4) + uniq * self.row_dim * 6)

    def score_bytes(self, rows: int, nnz: int) -> int:
        """A scored row's ids and values read, its table rows gathered, one
        score written."""
        return int(rows * (nnz * (4 + 4 + self.row_dim * 4) + 4))
