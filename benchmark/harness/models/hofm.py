"""The higher-order factorization machine, every degree 2..order over ONE set
of factors.  Row [1 + factor_num]: column 0 the bias w_i, columns 1: the
factors v_i.

    z_if  = v_if x_i
    score = sum_i w_i x_i + sum_f sum_{m=2..order} A^m(z_.f)
    A^m   = the degree-m ANOVA kernel (elementary symmetric polynomial) of the
            row's N values z_1f..z_Nf, by the dynamic program over the features
            j = 1..N, degrees descending:  a_0 = 1;  a_m <- a_m + z_jf a_{m-1}

The program is unrolled over the N features in plain float32 ``jax.numpy``: no
kernel, no scan, N x order multiply-adds on [B, k] arrays that autodiff
follows as it finds them.  (By power sums p_t = sum_i z_if^t: A^2 = (p_1^2 -
p_2) / 2, A^3 = (p_1^3 - 3 p_1 p_2 + 2 p_3) / 6, which the tests hold it to.)
"""

from __future__ import annotations

import jax.numpy as jnp

from . import fm2


class Model(fm2.Model):
    """``fm2``'s row, initial rows and byte counts; the score and its FLOPs
    by order, and the batch's shape for the kernel's work model."""

    def __init__(self, ini: dict):
        super().__init__(ini)
        self.order = int(ini["General"].get("order", 2))
        self.batch = int(ini["Train"]["batch_size"])
        self.nnz = int(ini["Train"]["max_nnz"])
        if self.order < 2:
            raise SystemExit(f"a factorization machine's order is at least 2, not {self.order}")

    def score(self, rows, vals, fields):
        del fields
        z = rows[..., 1:] * vals[..., None]  # [B, N, k]
        a = [jnp.ones_like(z[:, 0, :])] + [jnp.zeros_like(z[:, 0, :])] * self.order
        for j in range(z.shape[1]):
            for m in range(self.order, 0, -1):
                a[m] = a[m] + z[:, j, :] * a[m - 1]
        return jnp.sum(rows[..., 0] * vals, axis=-1) + jnp.sum(sum(a[2:]), axis=-1)

    def step_flops(self, rows: int, nnz: int, uniq: int) -> int:
        """Per occurrence and factor: ``order`` fused multiply-adds of the
        dynamic program forward (2 FLOPs each) and twice that backward, 18 at
        order 3; 4 for the bias; 6 per element of Adagrad, as ``fm2`` counts."""
        return int(rows * nnz * (6 * self.order * self.k + 4) + uniq * self.row_dim * 6)
