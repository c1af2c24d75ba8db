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

The form (``interaction_form = ffm_pair_tensor``; PR 44).  The pair tensor
has ONE layout, the gathered row's own, and the score carries a hand-written
backward (``jax.custom_vjp``, as ``ops/fm.py``'s order-2 and ANOVA forms do):

* ``ffm.fieldsum``: ``T[b, a, :] = Σ_n w[b, n, a] · rows[b, n, 1:]``, a
  ``[F x N] @ [N x F·k]`` matmul a row of the batch with the lanes as the
  table's row has them (the bias lane is cut off the matmul's OPERAND, which
  the compiler fuses into it; cut off its result it is a lane shift of the
  whole array).  The vals factor x folds into the ONE-HOT operand
  (``w[b,n,a] = x·1[f=a]``), not into v.  Never a ``[.., F, k]`` array with
  k in the lanes: that reshape, and autodiff's pad of an F·k-wide gradient
  back to a row, were two fifths of the interaction before this form.
* the one relayout the mathematics needs, made once and kept for the
  backward: ``Tt[b, g, k·a + j] = T[b, a, k·g + j]``, the swap of the two
  majors of a row's ``[F, F, k]`` block.
* ``ffm.pairdot``: ``cross = Σ T · Tt``, one fused multiply-and-reduce.
  Since ``∂(½ cross)/∂T = Tt``, the backward needs no second transpose.
* ``ffm.diag``: a slot has one field, so the i = j term is
  ``Σ (M · rows)²`` with ``M[b, n, c] = x[b,n] · 1[(c-1) // k == field[b,n]]``
  for lanes c >= 1, a mask made from an iota inside the fusion; the same
  masked pass over the rows takes the bias lane's ``w·x``, the linear term.
  Padding (``x = 0``) and a field id outside ``[0, F)`` give ``M = 0``, as
  the one-hot does: such a slot keeps its linear term and joins no pair.
* the backward: ``toward[b, i, :] = Tt[b, field_i, :]`` is the one matmul
  (``[N x F] @ [F x D]`` with the plain one-hot; ``Tt`` carries a zero lane 0
  so that it is a row wide), and one elementwise pass makes
  ``dRows = g · x · (toward − M-masked x·rows)`` with ``g · x`` in the bias
  lane, ``D = 1 + F·k`` lanes wide from the start; the values' gradient
  falls out of the same two arrays.

The form holds for any F, k, N and both ``compute_dtype``s and adapts by
shape alone; field ids are data, and two features of one field, ``N ≠ F`` and
padded slots are exact.  In the compiled step every op of the score stands
under ``ffm.fieldsum``, ``ffm.pairdot`` or ``ffm.diag`` inside the
``fm.interaction`` the scorer shares with the order-2 model, forward as
``jvp(fm.interaction)/ffm.*`` and backward as
``transpose(jvp(fm.interaction))/ffm.*`` (the scopes are opened by hand in the
``custom_vjp``'s two functions).  In the BACKWARD the three names are labels,
not layers: the TPU's compiler makes the matmul and the whole elementwise
pass one fusion, which takes the name of its root (``ffm.fieldsum``; the
other two read 0 there), so only the union under ``ffm.`` means anything on
the way back, and forward the ``ffm.diag`` pass carries what fuses with a
read of the rows, ``fm.loss``'s masked-L2 multiply among it.  What the chip
read of each at libffm's Criteo shapes (39 fields, k = 4, rows of 157 floats,
B = 32,768) is PERF.md §5, ``ffm4_criteo.train_fmb_fields``.

The model says its form itself: ``FFMModel.interaction_form`` and
``FFMModel.describe_interaction`` are what ``training._say_interaction`` puts
into ``kind=profile`` and the start-up line, as it reads ``order`` off the
plain FM and ``mlp_dims`` off DeepFM.

Precision.  With ``compute_dtype = float32`` (the default) the two matmuls
ask for ``Precision.HIGHEST``: on a TPU a float32 matmul at
the default precision is ONE bfloat16 pass, which made the float32
configuration compute what ``compute_dtype = bfloat16`` states (the values x
in the one-hot operand are not bfloat16 numbers): before PR 29 the score
was 6e-3 of its size off the plain pair sum on the chip, with it 1e-9 in the
benchmark's check.  ``pairdot`` and ``diag`` are float32 multiply-and-reduce
passes off the MXU (PR 29 read ALL the contractions off the MXU 4% slower
than on it; the two matmuls stay there), and on the CPU the setting changes
nothing.
``compute_dtype = bfloat16`` runs the matmuls with bfloat16 INPUTS and
float32 accumulation (preferred_element_type) and takes the i = j term from
the same rounded inputs: scores move by O(1e-3) relative, and the
benchmark's check tells the two apart.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

from fast_tffm_tpu.models.base import Batch, masked_l2


def _field_matmul(spec: str, a: jax.Array, b: jax.Array, dt) -> jax.Array:
    """The batched contraction ``spec`` with inputs of ``compute_dtype`` and
    float32 sums.  A float32 contraction at the TPU's default precision is one
    bfloat16 pass (module doc), so float32 asks for the highest; bfloat16
    inputs need no more than the default.  XLA's CPU backend has no batched
    bfloat16 x bfloat16 -> float32 dot (``Unsupported element type for
    DotThunk``), so there the inputs, rounded to bfloat16 already, go in as
    float32: the same products, each exact in float32, summed in float32."""
    a, b = a.astype(dt), b.astype(dt)
    if dt == jnp.bfloat16 and jax.default_backend() == "cpu":
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.einsum(
        spec, a, b, precision=lax.Precision.HIGHEST if dt == jnp.float32 else None,
        preferred_element_type=jnp.float32,
    )


def _lanes(fields: jax.Array, row_dim: int, factor_num: int):
    """(bias lane [1, 1, D], own lanes [B, N, D]), both bool: lane 0 of a row
    is its bias, lane c >= 1 belongs to field (c - 1) // k, and a slot owns its
    own field's block.  A field id outside [0, F) owns no lane, as its one-hot
    row is zero.  Made from an iota inside whatever fusion reads them, never
    arrays of their own."""
    lane = lax.broadcasted_iota(jnp.int32, (1, 1, row_dim), 2)
    return lane == 0, (lane >= 1) & ((lane - 1) // factor_num == fields[..., None])


def _rounded(rows, vals, dt):
    """(rows, vals) as the float32 numbers the matmuls' inputs hold, so that
    the i = j term taken off is the one the pair sum put in."""
    return rows.astype(dt).astype(jnp.float32), vals.astype(dt).astype(jnp.float32)


def _pair_forward(rows, vals, fields, num_fields: int, factor_num: int, compute_dtype: str):
    """(score [B], Tt [B, F, D]): the whole score, and beside its inputs the
    one array the backward needs (module doc)."""
    B, _, D = rows.shape
    F, k = num_fields, factor_num
    dt = jnp.dtype(compute_dtype)
    with jax.named_scope("ffm.diag"):
        # A slot's own terms in ONE masked pass over the rows: the bias lane's
        # w·x and, off the pair sum, the i = j term (x·v[own field])².
        rc, xc = _rounded(rows, vals, dt)
        bias_lane, own_lanes = _lanes(fields, D, k)
        z = xc[..., None] * rc
        own = jnp.where(own_lanes, -0.5 * z * z, 0.0)
        own_terms = jnp.sum(jnp.where(bias_lane, vals[..., None] * rows, own), axis=(1, 2))
    with jax.named_scope("ffm.fieldsum"):
        # x folds into the one-hot operand (w = x·1[f=a]) so z = v·x never goes
        # through the MXU; T[b, a, :] = Σ_{i: field_i = a} x_i · v[b, i, :, :].
        woh = jax.nn.one_hot(fields, F, dtype=dt) * vals[..., None].astype(dt)
        T = _field_matmul("bna,bnd->bad", woh, rows[..., 1:], dt)
        # The one relayout: Tt[b, g, k·a + j] = T[b, a, k·g + j]; for the
        # backward's matmul under a zero lane 0, as wide as a row.
        Tt = T.reshape(B, F, F, k).swapaxes(1, 2).reshape(B, F, F * k)
        Tt_row = jnp.pad(Tt, ((0, 0), (0, 0), (1, 0)))
    with jax.named_scope("ffm.pairdot"):
        score = own_terms + 0.5 * jnp.sum(T * Tt, axis=(1, 2))
    return score, Tt_row


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ffm_score(rows, vals, fields, num_fields, factor_num, compute_dtype):
    return _pair_forward(rows, vals, fields, num_fields, factor_num, compute_dtype)[0]


def _ffm_score_fwd(rows, vals, fields, num_fields, factor_num, compute_dtype):
    score, Tt = _pair_forward(rows, vals, fields, num_fields, factor_num, compute_dtype)
    return score, (rows, vals, fields, Tt)


def _ffm_score_bwd(num_fields, factor_num, compute_dtype, res, g):
    """∂(½ cross)/∂T = Tt, so slot i's row is pulled toward Tt[field_i] less its
    own block's x·v (the i = j term), times x; its bias lane's gradient is x.
    One matmul and one elementwise pass, the row's 1 + F·k lanes wide from the
    start; Tt carries a zero lane 0 so that the matmul's result is that wide."""
    rows, vals, fields, Tt = res
    dt = jnp.dtype(compute_dtype)
    with jax.named_scope("ffm.fieldsum"):
        # toward[b, i, :] = Tt[b, field_i, :]
        toward = _field_matmul("bna,bad->bnd", jax.nn.one_hot(fields, num_fields, dtype=dt), Tt, dt)
    with jax.named_scope("ffm.diag"):
        rc, xc = _rounded(rows, vals, dt)
        bias_lane, own_lanes = _lanes(fields, rows.shape[-1], factor_num)
        pull = toward - jnp.where(own_lanes, xc[..., None] * rc, 0.0)
    with jax.named_scope("ffm.pairdot"):
        d_rows = g[:, None, None] * jnp.where(bias_lane, vals[..., None], xc[..., None] * pull)
        d_vals = g[:, None] * (rows[..., 0] + jnp.sum(rc * pull, axis=-1))
    return d_rows, d_vals, None


_ffm_score.defvjp(_ffm_score_fwd, _ffm_score_bwd)


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
    # What ``kind=profile`` (``train_step``, ``predict_step``) carries as
    # ``interaction_form`` for this model, beside ``ops.fm.interaction_form``'s
    # names for the plain FM's forms; ``training._say_interaction`` reads it
    # off the model, as it reads ``order`` and ``mlp_dims``.
    interaction_form = "ffm_pair_tensor"

    @property
    def row_dim(self) -> int:
        return 1 + self.num_fields * self.factor_num

    def describe_interaction(self, *, backward: bool = True) -> str:
        """The form as one start-up line (fixed by the model, so it is said once)."""
        passes = "hand-written backward" if backward else "forward only"
        return (
            f"field-aware pair tensor ({self.num_fields} fields x {self.factor_num} "
            f"factors, one block transpose a step, {passes})"
        )

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
        return _ffm_score(
            rows, batch.vals, batch.fields, self.num_fields, self.factor_num, self.compute_dtype
        )

    def regularization(self, rows: jax.Array, dense, batch: Batch) -> jax.Array:
        del dense
        return masked_l2(rows, batch.vals, self.bias_lambda, self.factor_lambda)
