"""DeepFM: shared-embedding FM + MLP head (BASELINE.json config #4).

An extension target in the reference project's lineage, built natively: the
FM half is the fused order-2 kernel over the shared embedding table; the
deep half is a 3-layer MLP over the value-weighted embedding vectors of the
example's (fixed-count) feature slots — dense XLA matmuls that land on the
MXU.  Both halves read the SAME table rows, so one gather and one sparse
Adagrad scatter serve both (the SparseCore-lookup + dense-XLA-MLP split in
BASELINE.json's config #4).

Requires a fixed slot count per example (max_nnz = field count, the Criteo
shape); padding slots contribute zero embeddings.

By scope in the compiled step (``jax.named_scope``; the backward of each is
``transpose(jvp(...))`` of it): ``deepfm.feed`` — the value-weighted
embeddings and their reshape to ``[B, num_fields * factor_num]``;
``deepfm.mlp`` — the perceptron's matmuls, biases and ReLUs.  The FM half
stays under ``fm.interaction`` (ops/fm.py), so nothing stands under two.

``compute_dtype`` says what the perceptron's matmuls compute: ``bfloat16``
— operands cast to bfloat16, products summed in float32 (one MXU pass);
``float32`` — float32 operands at ``Precision.HIGHEST`` (on a TPU a float32
matmul at the default precision is that same one bfloat16 pass, which made
the two settings train the same step to the digit until PR 43; models/ffm.py
asks the same since PR 29).  Master weights and accumulators are float32
under both.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from fast_tffm_tpu.models.base import Batch, masked_l2
from fast_tffm_tpu.ops.fm import fm_score

# What a step's ``kind=profile`` record says of a perceptron; every field
# null for a model without one (``perceptron_profile``).
PERCEPTRON_FIELDS = ("dense_params", "hidden_dims", "compute_dtype", "mlp_flops_per_step")


def perceptron_profile(model, batch_rows: int, *, backward: bool = True) -> dict:
    """``PERCEPTRON_FIELDS`` of ``model`` on ``batch_rows`` rows a step:
    weights and biases, the hidden widths, the matmuls' operand type and
    their FLOPs (a multiply-add a weight a row forward; twice that again
    backward, by the input and by the weight)."""
    dims = getattr(model, "mlp_dims", None)
    if dims is None:
        return dict.fromkeys(PERCEPTRON_FIELDS)
    weights = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    return dict(
        dense_params=weights + sum(dims[1:]),
        hidden_dims=list(dims[1:-1]),
        compute_dtype=model.compute_dtype,
        mlp_flops_per_step=(6 if backward else 2) * weights * batch_rows,
    )


def describe_perceptron(model) -> str:
    """``perceptron_profile`` as one start-up line."""
    p = perceptron_profile(model, 0)
    return (
        f"{'-'.join(str(d) for d in model.mlp_dims)}, {p['dense_params']:,} parameters, "
        f"{p['compute_dtype']} operands"
    )


@dataclasses.dataclass(frozen=True)
class DeepFMModel:
    vocabulary_size: int
    num_fields: int  # fixed feature slots per example (= max_nnz)
    factor_num: int = 8
    hidden_dims: tuple[int, ...] = (400, 400, 400)  # 3-layer MLP head
    init_value_range: float = 0.01
    factor_lambda: float = 0.0
    bias_lambda: float = 0.0
    # MXU-native precision for the MLP matmuls: params/optimizer state stay
    # float32 (master weights); activations and weights are cast per-matmul
    # and products accumulate in float32 (preferred_element_type).  The FM
    # half and the embedding table are untouched — they are HBM-bound
    # gathers + VPU elementwise work, not MXU work.
    compute_dtype: str = "float32"  # float32 | bfloat16

    uses_fields = False  # slots are positional (num_fields = max_nnz)

    @property
    def row_dim(self) -> int:
        return 1 + self.factor_num

    def init_table(self, key: jax.Array) -> jax.Array:
        factors = jax.random.uniform(
            key,
            (self.vocabulary_size, self.factor_num),
            minval=-self.init_value_range,
            maxval=self.init_value_range,
            dtype=jnp.float32,
        )
        bias = jnp.zeros((self.vocabulary_size, 1), jnp.float32)
        return jnp.concatenate([bias, factors], axis=-1)

    @property
    def mlp_dims(self) -> tuple[int, ...]:
        return (self.num_fields * self.factor_num, *self.hidden_dims, 1)

    def init_dense(self, key: jax.Array):
        dims = self.mlp_dims
        params = {}
        for li, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
            key, wk = jax.random.split(key)
            # He init for the ReLU stack.
            params[f"w{li}"] = jax.random.normal(wk, (d_in, d_out), jnp.float32) * jnp.sqrt(
                2.0 / d_in
            )
            params[f"b{li}"] = jnp.zeros((d_out,), jnp.float32)
        return params

    def _mlp(self, dense, x: jax.Array) -> jax.Array:
        n_layers = len(self.hidden_dims) + 1
        dt = jnp.dtype(self.compute_dtype)
        # A float32 matmul at the TPU's default precision is one bfloat16
        # pass: float32 has to ask for more (module docstring).
        prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
        for li in range(n_layers):
            x = jnp.dot(
                x.astype(dt),
                dense[f"w{li}"].astype(dt),
                precision=prec,
                preferred_element_type=jnp.float32,
            ) + dense[f"b{li}"]
            if li < n_layers - 1:
                x = jax.nn.relu(x)
        return x[..., 0]  # [B]

    def score(self, rows: jax.Array, dense, batch: Batch) -> jax.Array:
        B, N = batch.vals.shape
        fm_part = fm_score(rows, batch.vals, order=2)
        with jax.named_scope("deepfm.feed"):
            emb = rows[..., 1:] * batch.vals[..., None]  # [B, N, k] value-weighted
            feed = emb.reshape(B, N * self.factor_num)
        with jax.named_scope("deepfm.mlp"):
            deep_part = self._mlp(dense, feed)
        return fm_part + deep_part

    def regularization(self, rows: jax.Array, dense, batch: Batch) -> jax.Array:
        del dense  # reference regularizes only the FM parameters
        return masked_l2(rows, batch.vals, self.bias_lambda, self.factor_lambda)
