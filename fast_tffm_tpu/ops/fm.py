"""Fused factorization-machine scoring kernels with hand-written backward passes.

TPU-native replacement for the reference's native scorer/grad op pair
(`renyi533/fast_tffm` :: cc/ FmScorer + FmGrad kernels, loaded through
py/fm_ops.py's RegisterGradient glue).  Instead of a C++ CPU kernel driven by
a TF graph, the score is a pure jnp function compiled by XLA, with the
backward pass supplied explicitly through `jax.custom_vjp` — mirroring the
reference's hand-written FmGrad op rather than relying on autodiff.

Batch layout (the "narrow waist" of the framework, see SURVEY.md §2):
instead of the reference's flat CSR (flat ids/vals + row offsets), batches
are *padded dense* ``[batch, max_nnz]`` — static shapes are what XLA/TPU
want, and FM score terms all scale multiplicatively with the feature value
``x_i``, so zero-valued padding slots are exactly neutral in both the
forward and the backward pass (no masks needed).

Parameters arrive *gathered*: ``rows[batch, max_nnz, 1 + k]`` where column 0
is the per-feature bias w_i and columns 1: are the factor vector v_i.  The
caller (model layer) does the gather/scatter; these kernels are dense math
only — the same separation the reference draws between its embedding
lookup and its scorer op.

Math:
  order 2:   score = Σᵢ wᵢxᵢ + ½ Σ_f [(Σᵢ vᵢf xᵢ)² − Σᵢ (vᵢf xᵢ)²]
  order t≥3: score = Σᵢ wᵢxᵢ + Σ_{m=2}^{t} Σ_f ANOVA_m(z·f)  where z = v·x,
             ANOVA via the dynamic program  a[j][m] = a[j-1][m] + z_j·a[j-1][m-1]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "fm_score",
    "anova_kernel",
    "fm_score_order2_raw",
    "fm_score_anova_raw",
    "interaction_form",
    "interaction_profile",
    "describe_interaction",
]


# ---------------------------------------------------------------------------
# Order-2: the (Σv)² − Σv² trick
# ---------------------------------------------------------------------------


def _order2_fwd_math(rows: jax.Array, vals: jax.Array):
    """Shared forward math. rows: [B, N, 1+k], vals: [B, N] → scores [B]."""
    bias = rows[..., 0]  # [B, N]
    v = rows[..., 1:]  # [B, N, k]
    linear = jnp.sum(bias * vals, axis=-1)  # [B]
    vx = v * vals[..., None]  # [B, N, k]
    s1 = jnp.sum(vx, axis=1)  # [B, k]
    s2 = jnp.sum(vx * vx, axis=1)  # [B, k]
    pairwise = 0.5 * jnp.sum(s1 * s1 - s2, axis=-1)  # [B]
    return linear + pairwise, (bias, v, vx, s1)


@jax.custom_vjp
def _fm_score_order2(rows: jax.Array, vals: jax.Array) -> jax.Array:
    return _order2_fwd_math(rows, vals)[0]


def _fm_score_order2_fwd(rows, vals):
    score, (bias, v, _vx, s1) = _order2_fwd_math(rows, vals)
    # vx is one fused multiply away from (v, vals); recompute in bwd rather
    # than holding a second [B, N, k] residual across the fwd→bwd gap.
    return score, (bias, v, s1, vals)


def _fm_score_order2_bwd(res, g):
    """Hand-derived backward (the reference's FmGrad, order 2).

    ∂score/∂wᵢ   = xᵢ
    ∂score/∂vᵢ   = xᵢ · (s1 − vᵢxᵢ)
    ∂score/∂xᵢ   = wᵢ + vᵢ·(s1 − vᵢxᵢ)
    """
    bias, v, s1, vals = res
    vx = v * vals[..., None]
    g_ = g[:, None]  # [B, 1]
    d_bias = g_ * vals  # [B, N]
    resid = s1[:, None, :] - vx  # [B, N, k]
    d_v = g_[..., None] * vals[..., None] * resid  # [B, N, k]
    d_rows = jnp.concatenate([d_bias[..., None], d_v], axis=-1)
    d_vals = g_ * (bias + jnp.sum(v * resid, axis=-1))  # [B, N]
    return d_rows, d_vals


_fm_score_order2.defvjp(_fm_score_order2_fwd, _fm_score_order2_bwd)


def fm_score_order2_raw(rows: jax.Array, vals: jax.Array) -> jax.Array:
    """Order-2 forward without the custom VJP (autodiff reference for tests)."""
    return _order2_fwd_math(rows, vals)[0]


# ---------------------------------------------------------------------------
# Arbitrary order: ANOVA-kernel dynamic program
# ---------------------------------------------------------------------------


def _anova_scan_fwd(z: jax.Array, order: int):
    """Forward DP.  z: [B, N, k] → a_final [B, order+1, k], a_prevs [N, B, order+1, k].

    Carry a[m] = ANOVA kernel of degree m over the features consumed so far
    (per batch row, per factor dim).  a[0] ≡ 1.
    """
    B, N, k = z.shape
    a0 = jnp.zeros((B, order + 1, k), z.dtype).at[:, 0, :].set(1.0)

    def step(a, z_j):  # z_j: [B, k]
        # a_new[m] = a[m] + z_j * a[m-1]  (m >= 1); shift-and-fma.
        shifted = jnp.roll(a, 1, axis=1).at[:, 0, :].set(0.0)
        a_new = a + z_j[:, None, :] * shifted
        return a_new, a  # store the *pre-step* carry for the backward DP

    a_final, a_prevs = lax.scan(step, a0, jnp.moveaxis(z, 1, 0))
    return a_final, a_prevs


def anova_kernel(z: jax.Array, order: int) -> jax.Array:
    """Σ over factor dims of the degree-``order`` ANOVA kernel.  z: [B,N,k] → [B]."""
    a_final, _ = _anova_scan_fwd(z, order)
    return jnp.sum(a_final[:, order, :], axis=-1)


def _anova_fwd_math(rows: jax.Array, vals: jax.Array, order: int):
    bias = rows[..., 0]
    v = rows[..., 1:]
    linear = jnp.sum(bias * vals, axis=-1)
    z = v * vals[..., None]  # [B, N, k]
    a_final, a_prevs = _anova_scan_fwd(z, order)
    # Sum of all interaction degrees 2..order (reference: arbitrary-order FM
    # evaluates every degree with the single shared factor set).
    inter = jnp.sum(a_final[:, 2:, :], axis=(1, 2))
    return linear + inter, (bias, v, z, a_prevs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _fm_score_anova(rows: jax.Array, vals: jax.Array, order: int) -> jax.Array:
    return _anova_fwd_math(rows, vals, order)[0]


def _fm_score_anova_fwd(rows, vals, order):
    score, res = _anova_fwd_math(rows, vals, order)
    return score, (*res, vals)


def _fm_score_anova_bwd(order, res, g):
    """Hand-written adjoint of the ANOVA DP (the reference's FmGrad, general order).

    Reverse scan over features.  ā is the cotangent of the DP carry:
      z̄_j    = Σ_m ā[m] · a_prev_j[m-1]
      ā[m-1] += ā[m] · z_j           (i.e. ā ← ā + shift⁻¹(ā)·z_j)
    seeded with ā[m] = g for m ∈ [2, order] (every degree contributes to the
    score with unit weight).
    """
    bias, v, z, a_prevs, vals = res
    B, N, k = z.shape
    abar0 = jnp.zeros((B, order + 1, k), z.dtype)
    abar0 = abar0.at[:, 2:, :].set(g[:, None, None])

    def step(abar, xs):
        z_j, a_prev = xs  # [B, k], [B, order+1, k]
        shifted_prev = jnp.roll(a_prev, 1, axis=1).at[:, 0, :].set(0.0)
        zbar_j = jnp.sum(abar * shifted_prev, axis=1)  # [B, k]
        # ā[m-1] += ā[m] * z_j  → add the down-shifted ā scaled by z_j.
        down = jnp.roll(abar, -1, axis=1).at[:, -1, :].set(0.0)
        abar_new = abar + down * z_j[:, None, :]
        return abar_new, zbar_j

    _, zbars = lax.scan(step, abar0, (jnp.moveaxis(z, 1, 0), a_prevs), reverse=True)
    zbar = jnp.moveaxis(zbars, 0, 1)  # [B, N, k]

    d_bias = g[:, None] * vals
    d_v = zbar * vals[..., None]
    d_rows = jnp.concatenate([d_bias[..., None], d_v], axis=-1)
    d_vals = g[:, None] * bias + jnp.sum(zbar * v, axis=-1)
    return d_rows, d_vals


_fm_score_anova.defvjp(_fm_score_anova_fwd, _fm_score_anova_bwd)


def fm_score_anova_raw(rows: jax.Array, vals: jax.Array, order: int) -> jax.Array:
    """General-order forward without the custom VJP (autodiff reference)."""
    return _anova_fwd_math(rows, vals, order)[0]


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def interaction_form(
    order: int, use_pallas: bool | None = None, backend: str | None = None
) -> str:
    """The form ``fm_score`` takes, a trace-time function of the order and the
    backend: ``order2`` (the closed form, any backend), ``pallas_anova`` (the
    ANOVA dynamic program in ops/pallas_anova.py's kernel: on a TPU, or
    wherever ``use_pallas=True`` asks for it) or ``scan`` (the same program
    as a ``lax.scan``, anywhere else)."""
    if order == 2:
        return "order2"
    if use_pallas is None:
        use_pallas = (backend or jax.default_backend()) == "tpu"
    return "pallas_anova" if use_pallas else "scan"


def interaction_profile(
    order: int, batch_rows: int, factor_num: int, *, backward: bool = True,
    form: str | None = None,
) -> dict:
    """The interaction's trace-time choices as a step's ``kind=profile``
    record carries them: ``order``, ``interaction_form`` and, under the
    kernel, ``anova_programs_per_step`` = the grid programs of one step on
    ``batch_rows`` rows (forward, and as many again where the step runs the
    backward kernel); null for the other forms."""
    form = form or interaction_form(order)
    programs = None
    if form == "pallas_anova":
        from fast_tffm_tpu.ops.pallas_anova import grid_programs

        programs = (2 if backward else 1) * grid_programs(batch_rows, factor_num)
    return dict(order=order, interaction_form=form, anova_programs_per_step=programs)


def describe_interaction(
    order: int, batch_rows: int, factor_num: int, *, backward: bool = True,
    form: str | None = None,
) -> str:
    """``interaction_profile`` as one start-up line (a trace-time choice, so
    it is said once)."""
    p = interaction_profile(order, batch_rows, factor_num, backward=backward, form=form)
    if p["interaction_form"] == "order2":
        return "order 2, closed form"
    if p["interaction_form"] == "scan":
        return f"order {order}, ANOVA dynamic program as a lax.scan over the row's features"
    passes = "forward and backward" if backward else "forward"
    return (
        f"order {order}, pallas ANOVA kernel ({p['anova_programs_per_step']} grid "
        f"programs a step, {passes}; {batch_rows} rows in tiles of 128 x "
        f"{factor_num} factors)"
    )


def fm_score(
    rows: jax.Array, vals: jax.Array, order: int = 2, *, use_pallas: bool | None = None
) -> jax.Array:
    """FM score for a padded batch.

    Args:
      rows:  [batch, max_nnz, 1 + factor_num] gathered parameter rows
             (col 0 = bias wᵢ, cols 1: = factors vᵢ).
      vals:  [batch, max_nnz] feature values; 0.0 marks padding slots.
      order: interaction order ≥ 2.  order=2 uses the fused (Σv)²−Σv² path;
             order≥3 the ANOVA dynamic program.  Both carry hand-written VJPs.
      use_pallas: route the order≥3 interaction DP through the Pallas TPU
             kernel (ops/pallas_anova.py).  None = auto (``interaction_form``):
             on a TPU backend only — the kernel compiled and matched this
             path there at B=16384 N=11 k=8 (PR 22) and, since PR 37, runs
             in a measured cell at B=65536 N=11 k=30, order 3
             (``fm3_k30_kdd12.train_fmb_order3``: 512 x 30 grid programs
             forward and as many backward).  True is honored anywhere and
             never drops back: a compiler refusal raises.

    Returns:
      [batch] raw (pre-sigmoid) scores.
    """
    if order < 2:
        raise ValueError(f"FM order must be >= 2, got {order}")
    # Every caller's interaction (train step, scorer, any layout, the
    # sharded step) carries this name in the compiled program; the ANOVA
    # dynamic program, in either form, ``fm.anova`` inside it (forward
    # ``jvp(fm.interaction)/fm.anova``, backward
    # ``transpose(jvp(fm.interaction))/fm.anova``).
    with jax.named_scope("fm.interaction"):
        form = interaction_form(order, use_pallas)
        if form == "order2":
            return _fm_score_order2(rows, vals)
        if form == "pallas_anova":
            from fast_tffm_tpu.ops.pallas_anova import anova_inter
            from fast_tffm_tpu.ops.pallas_common import default_interpret

            # Only the DP carries a hand-written (kernel) VJP; the linear term
            # and z = v·x are cheap elementwise ops XLA autodiff handles best.
            # On the CPU test mesh an explicit use_pallas=True runs in the
            # Pallas interpreter (ops.pallas_common).
            linear = jnp.sum(rows[..., 0] * vals, axis=-1)
            z = rows[..., 1:] * vals[..., None]
            with jax.named_scope("fm.anova"):  # the kernels and their layout transposes
                inter = anova_inter(z, order, default_interpret())
            return linear + inter
        with jax.named_scope("fm.anova"):
            return _fm_score_anova(rows, vals, order)
