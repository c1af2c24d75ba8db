"""Field-aware factorization machine (BASELINE.json config #3).

An extension target named by the reference project's roadmap (FFM support in
`renyi533/fast_tffm`'s lineage); built here natively.  Each feature i keeps
one factor vector *per field*: score =

    Σᵢ wᵢxᵢ + Σ_{i<j} ⟨v_{i, field_j}, v_{j, field_i}⟩ xᵢxⱼ

Row layout [1 + num_fields·k]: col 0 bias, then the per-field factor blocks.

TPU-first evaluation: the pairwise sum is re-associated into a field-pair
tensor  T[a, b] = Σ_{i: fᵢ=a} z_i[b]  (z = v·x) so the double sum becomes

    ½ (Σ_{a,b} ⟨T[a,b], T[b,a]⟩ − Σᵢ ⟨z_i[fᵢ], z_i[fᵢ]⟩)

— one one-hot einsum (an MXU matmul) + elementwise math, instead of an
O(N²) gather loop.  Padding (x=0) contributes z=0 and is exactly neutral.

The vals factor x folds into the ONE-HOT operand (w[b,n,a] = x·1[f=a]),
not into v: z = v·x as a separate [B, N, F, k] array is ~0.5 GB written
+ read per direction at the benchmark shape (B=65536, 22 fields), and
the fold removes that HBM round-trip while computing the identical
per-term products (measured r5 — the cfg3p gap driver, VERDICT r4 #4).

``compute_dtype='bfloat16'`` additionally runs the interaction einsums
with bf16 INPUTS and f32 MXU accumulation (preferred_element_type):
halves the bytes of the dominant [B, N, F, k] reads.  Scores move by
O(1e-3) relative — fine for CTR ranking, so it is the bench's choice —
while the default stays float32 (bit-parity with the oracle tests).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from fast_tffm_tpu.models.base import Batch, masked_l2


@dataclasses.dataclass(frozen=True)
class FFMModel:
    vocabulary_size: int
    num_fields: int
    factor_num: int = 4
    init_value_range: float = 0.01
    factor_lambda: float = 0.0
    bias_lambda: float = 0.0
    compute_dtype: str = "float32"  # interaction einsum inputs (float32|bfloat16)

    uses_fields = True  # score() one-hots batch.fields per slot

    @property
    def row_dim(self) -> int:
        return 1 + self.num_fields * self.factor_num

    def init_table(self, key: jax.Array) -> jax.Array:
        factors = jax.random.uniform(
            key,
            (self.vocabulary_size, self.num_fields * self.factor_num),
            minval=-self.init_value_range,
            maxval=self.init_value_range,
            dtype=jnp.float32,
        )
        bias = jnp.zeros((self.vocabulary_size, 1), jnp.float32)
        return jnp.concatenate([bias, factors], axis=-1)

    def init_dense(self, key: jax.Array):
        return {}

    @jax.named_scope("fm.interaction")
    def score(self, rows: jax.Array, dense, batch: Batch) -> jax.Array:
        del dense
        B, N = batch.vals.shape
        F, k = self.num_fields, self.factor_num
        bias = rows[..., 0]
        v = rows[..., 1:].reshape(B, N, F, k)  # v[b, i, partner_field, :]
        linear = jnp.sum(bias * batch.vals, axis=-1)
        dt = jnp.dtype(self.compute_dtype)
        vc = v.astype(dt)
        # x folds into the one-hot operand (w = x·1[f=a]) so z = v·x never
        # materializes as [B, N, F, k]; same per-term products (module doc).
        woh = jax.nn.one_hot(batch.fields, F, dtype=dt) * batch.vals[
            ..., None
        ].astype(dt)
        # T[b, a, g, :] = Σ_{i: field_i = a} x_i · v[b, i, g, :]
        T = jnp.einsum(
            "bna,bngk->bagk", woh, vc, preferred_element_type=jnp.float32
        )
        cross = jnp.einsum("bagk,bgak->b", T, T)
        # Diagonal (i == j) correction: z_i[f_i] per nonzero.
        z_self = jnp.einsum(
            "bnfk,bnf->bnk", vc, woh, preferred_element_type=jnp.float32
        )
        diag = jnp.sum(z_self * z_self, axis=(1, 2))
        return linear + 0.5 * (cross - diag)

    def regularization(self, rows: jax.Array, dense, batch: Batch) -> jax.Array:
        del dense
        return masked_l2(rows, batch.vals, self.bias_lambda, self.factor_lambda)
