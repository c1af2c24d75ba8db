"""DeepFM: the 2nd-order factorization machine and a multi-layer perceptron
over the SAME table rows, as ``fast_tffm_tpu/models/deepfm.py``'s docstring
states it (Guo, Tang, Ye, Li, He: DeepFM, IJCAI 2017).  Row [1 + factor_num]
as ``fm2``'s.  The dense leaves are the perceptron's: ``w{i}`` [d_in, d_out]
and ``b{i}`` [d_out] for the layers num_fields * factor_num -> hidden_dims ->
1, the first module to use that half of the seam (``models/__init__.py``).

    e     = reshape(v_i x_i, [B, N k])          N = num_fields = max_nnz
    score = fm2 score + MLP(e)                  ReLU between layers, none after the last

ReLU's derivative at exactly 0 is 0, as the program's ``jax.nn.relu`` and every
framework have it, hence ``where(x > 0, x, 0)``: ``maximum(x, 0)`` splits the
tie and gives 0.5.  The tie is met: an example whose first layer is inactive
in every unit has pre-activations of exactly 0 in every later layer while the
biases are 0 (at 16 units one example in 65,536; at 400 none).

Initial leaves, by the recipe the program documents (``trainer.init_state``,
``DeepFMModel.init_dense``): ``_, key = split(key(0))``; a layer:
``key, wk = split(key)``, ``w = normal(wk) * sqrt(2 / d_in)`` (He), ``b = 0``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import fm2


class Model(fm2.Model):
    """``fm2``'s row, initial rows and FM half; the perceptron's leaves, its
    score and its share of the work model."""

    def __init__(self, ini: dict):
        super().__init__(ini)
        self.fields = int(ini["General"]["num_fields"])
        self.hidden = tuple(int(x) for x in str(ini["General"].get("hidden_dims", "400 400 400")).replace(",", " ").split())
        nnz = int(ini["Train"]["max_nnz"])
        if nnz != self.fields:
            raise SystemExit(f"DeepFM's perceptron reads one slot a field: max_nnz ({nnz}) must be num_fields ({self.fields})")
        self.dims = (self.fields * self.k, *self.hidden, 1)

    def init_dense(self) -> dict:
        _, key = jax.random.split(jax.random.key(0))
        leaves = {}
        for li, (d_in, d_out) in enumerate(zip(self.dims[:-1], self.dims[1:])):
            key, wk = jax.random.split(key)
            leaves[f"w{li}"] = jax.random.normal(wk, (d_in, d_out), jnp.float32) * jnp.sqrt(2.0 / d_in)
            leaves[f"b{li}"] = jnp.zeros((d_out,), jnp.float32)
        return leaves

    def score(self, rows, vals, fields, dense):
        b, n = vals.shape
        x = (rows[..., 1:] * vals[..., None]).reshape(b, n * self.k)
        layers = len(self.dims) - 1
        for li in range(layers):
            x = x @ dense[f"w{li}"] + dense[f"b{li}"]
            if li < layers - 1:
                x = jnp.where(x > 0, x, 0.0)
        return super().score(rows, vals, fields) + x[..., 0]

    @property
    def weights(self) -> int:
        return sum(a * b for a, b in zip(self.dims[:-1], self.dims[1:]))

    @property
    def dense_elements(self) -> int:
        return self.weights + sum(self.dims[1:])

    def step_bytes(self, ids) -> tuple[int, int]:
        """``fm2``'s, and every dense leaf and its accumulator read once and
        written once."""
        total, uniq = super().step_bytes(ids)
        return total + 4 * 4 * self.dense_elements, uniq

    def step_flops(self, rows: int, nnz: int, uniq: int) -> int:
        """``fm2``'s; 6 a weight an example (a multiply-add forward, two
        backward: by the input and by the weight); 6 per dense element of
        Adagrad, as for the table's."""
        return super().step_flops(rows, nnz, uniq) + 6 * self.weights * rows + 6 * self.dense_elements

    def score_bytes(self, rows: int, nnz: int) -> int:
        """``fm2``'s, and the dense leaves read once."""
        return super().score_bytes(rows, nnz) + 4 * self.dense_elements
