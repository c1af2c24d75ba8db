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
not into v, so that z = v·x is never a [B, N, F, k] array of its own.

In the compiled step the three parts carry scopes of their own under the
``fm.interaction`` the scorer shares with the order-2 model: ``ffm.fieldsum``
(the one-hot operand and the einsum into T), ``ffm.pairdot`` (⟨T[a,b],
T[b,a]⟩) and ``ffm.diag`` (the i = j term); the backward arrives as
``transpose(jvp(ffm.fieldsum))`` and so on.  What the chip read of each at
libffm's Criteo shapes (39 fields, k = 4, rows of 157 floats, B = 32,768) is
PERF.md §5, ``ffm4_criteo.train_fmb_fields``.

Precision.  With ``compute_dtype = float32`` (the default) the three
contractions ask for ``Precision.HIGHEST``: on a TPU a float32 matmul at
the default precision is ONE bfloat16 pass, which made the float32
configuration compute what ``compute_dtype = bfloat16`` states (the values x
in the one-hot operand are not bfloat16 numbers): before PR 29 the score
was 6e-3 of its size off the plain pair sum on the chip, with it 1e-9 in the
benchmark's check.  It costs 2% of the step there (a bare loop over the
compiled step reads 197.6 ms at the default precision, 201.5-201.7 at
``HIGH`` and ``HIGHEST`` alike; a fused multiply-and-reduce off the MXU reads
210.0), and on the CPU the setting changes nothing.
``compute_dtype = bfloat16`` runs the contractions with bfloat16 INPUTS and
float32 accumulation (preferred_element_type): scores move by O(1e-3)
relative, and the benchmark's check tells the two apart.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from fast_tffm_tpu.models.base import Batch, masked_l2


def _dot_inputs(a: jax.Array, b: jax.Array):
    """The operands of a batched contraction as the backend can take them.
    XLA's CPU backend has no batched bfloat16 x bfloat16 -> float32 dot
    (``Unsupported element type for DotThunk``), so there the inputs, rounded
    to bfloat16 already, go in as float32: the same products, each exact in
    float32, summed in float32."""
    if a.dtype == jnp.bfloat16 and jax.default_backend() == "cpu":
        return a.astype(jnp.float32), b.astype(jnp.float32)
    return a, b


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
        # A float32 contraction at the TPU's default precision is one
        # bfloat16 pass (module doc); bfloat16 inputs need no more than that.
        prec = lax.Precision.HIGHEST if dt == jnp.float32 else None
        vc = v.astype(dt)
        with jax.named_scope("ffm.fieldsum"):
            # x folds into the one-hot operand (w = x·1[f=a]) so z = v·x never
            # materializes as [B, N, F, k]; same per-term products (module doc).
            woh = jax.nn.one_hot(batch.fields, F, dtype=dt) * batch.vals[
                ..., None
            ].astype(dt)
            # T[b, a, g, :] = Σ_{i: field_i = a} x_i · v[b, i, g, :]
            T = jnp.einsum(
                "bna,bngk->bagk", *_dot_inputs(woh, vc), precision=prec,
                preferred_element_type=jnp.float32,
            )
        with jax.named_scope("ffm.pairdot"):
            cross = jnp.einsum("bagk,bgak->b", T, T, precision=prec)
        with jax.named_scope("ffm.diag"):
            # Diagonal (i == j) correction: z_i[f_i] per nonzero.
            z_self = jnp.einsum(
                "bnfk,bnf->bnk", vc, woh, precision=prec,
                preferred_element_type=jnp.float32,
            )
            diag = jnp.sum(z_self * z_self, axis=(1, 2))
        return linear + 0.5 * (cross - diag)

    def regularization(self, rows: jax.Array, dense, batch: Batch) -> jax.Array:
        del dense
        return masked_l2(rows, batch.vals, self.bias_lambda, self.factor_lambda)
