"""Pallas TPU kernel for the arbitrary-order ANOVA interaction sum.

The BASELINE config #5 component: the order-k ANOVA-kernel dynamic program
of the reference's scorer/grad op pair (`renyi533/fast_tffm` :: cc/ scorer:
per-example DP a[m] += z_j * a[m-1] over the example's nonzeros, and the
hand-written reverse DP in its grad op), as a TPU kernel instead of a C++
CPU loop.

Why a kernel at all: the lax.scan formulation materializes the per-step
carries ``[N, B, order+1, k]`` to HBM for the backward pass and runs N tiny
fused ops per batch.  Here the whole DP lives in VMEM:

  * layout — z is transposed to ``[k, N, B]`` so the *batch* dimension is
    the 128-lane vector axis (k is small — 4..16 — and would waste 15/16
    lanes); the DP state is an ``[8, 128]`` tile: degree on sublanes,
    examples on lanes, one shift-and-fma per consumed feature;
  * grid ``(B/128, k)`` with k innermost, so each output tile stays
    resident in VMEM while all k factor dims accumulate into it;
  * the backward kernel RECOMPUTES the forward carries into a VMEM scratch
    (N·8·128 floats ≈ 160 KB) instead of reading them from HBM — the DP is
    a few fma's per element, far cheaper than the round-trip.

Padded lanes (batch rows beyond B) and padded degree sublanes (beyond
``order``) carry zeros/ignored values and are sliced away outside.

Only the DP itself is custom-VJP'd; the cheap surrounding math (z = v·x,
linear term) stays in plain jnp where XLA's autodiff is already optimal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["anova_inter", "anova_inter_reference", "grid_programs"]

_LANES = 128


def _rows_for(order: int) -> int:
    """Sublane count for the DP state: degrees 0..order, padded to 8k."""
    return max(8, ((order + 1 + 7) // 8) * 8)


def grid_programs(batch_rows: int, factor_num: int) -> int:
    """Grid programs of ONE pass (the forward kernel; the backward kernel runs
    as many): a tile of 128 examples times a factor dimension each."""
    return -(-batch_rows // _LANES) * factor_num


def _row_iota(rows: int) -> jax.Array:
    # In-kernel .at[].set lowers to an unsupported scatter on TPU, so all
    # row masking is done with broadcasted-iota compares instead.
    return lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)


def _shift_up(a: jax.Array) -> jax.Array:
    """shifted[m] = a[m-1], shifted[0] = 0  (degree-raising shift)."""
    return jnp.where(_row_iota(a.shape[0]) == 0, 0.0, jnp.roll(a, 1, axis=0))


def _shift_down(a: jax.Array) -> jax.Array:
    """down[m] = a[m+1], down[-1] = 0  (adjoint of _shift_up)."""
    return jnp.where(_row_iota(a.shape[0]) == a.shape[0] - 1, 0.0, jnp.roll(a, -1, axis=0))


def _fwd_kernel(z_ref, out_ref, *, order: int, rows: int):
    """One (batch-tile, factor-dim) program: run the DP, accumulate degrees."""
    f = pl.program_id(1)
    n = z_ref.shape[1]
    ri = _row_iota(rows)
    a0 = jnp.where(ri == 0, 1.0, 0.0)

    def body(j, a):
        z_j = z_ref[0, j, :]  # [LANES]
        return a + _shift_up(a) * z_j[None, :]

    a = lax.fori_loop(0, n, body, a0)
    # Degrees 2..order, [LANES] (masked sum — static slices of odd heights
    # re-tile poorly on TPU).
    part = jnp.sum(jnp.where((ri >= 2) & (ri <= order), a, 0.0), axis=0)

    @pl.when(f == 0)
    def _():
        out_ref[0, :] = part

    @pl.when(f > 0)
    def _():
        out_ref[0, :] = out_ref[0, :] + part


def _bwd_kernel(z_ref, g_ref, zbar_ref, aprev_ref, *, order: int, rows: int):
    """Recompute the forward carries in VMEM, then run the reverse DP.

    Reverse recurrence (the reference FmGrad's general-order adjoint):
      z̄_j  = Σ_m ā[m] · a_prev_j[m-1]
      ā    ← ā + shift_down(ā) · z_j
    seeded with ā[m] = g for m ∈ [2, order].
    """
    n = z_ref.shape[1]
    ri = _row_iota(rows)
    a0 = jnp.where(ri == 0, 1.0, 0.0)

    def fwd_body(j, a):
        aprev_ref[j, :, :] = a
        z_j = z_ref[0, j, :]
        return a + _shift_up(a) * z_j[None, :]

    lax.fori_loop(0, n, fwd_body, a0)

    g = g_ref[0, :]  # [LANES]
    abar0 = jnp.where((ri >= 2) & (ri <= order), g[None, :], 0.0)

    def bwd_body(t, abar):
        j = n - 1 - t
        z_j = z_ref[0, j, :]
        a_prev = aprev_ref[j, :, :]
        zbar_ref[0, j, :] = jnp.sum(abar * _shift_up(a_prev), axis=0)
        return abar + _shift_down(abar) * z_j[None, :]

    lax.fori_loop(0, n, bwd_body, abar0)


def _pad_transpose(z: jax.Array) -> tuple[jax.Array, int]:
    """[B, N, k] → ([k, N, B_padded], B_padded)."""
    b = z.shape[0]
    bp = ((b + _LANES - 1) // _LANES) * _LANES
    if bp != b:
        z = jnp.pad(z, ((0, bp - b), (0, 0), (0, 0)))
    return jnp.transpose(z, (2, 1, 0)), bp


def _fwd_impl(z: jax.Array, order: int, interpret: bool) -> jax.Array:
    b, n, k = z.shape
    rows = _rows_for(order)
    z_t, bp = _pad_transpose(z.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, order=order, rows=rows),
        grid=(bp // _LANES, k),
        in_specs=[
            pl.BlockSpec((1, n, _LANES), lambda i, f: (f, 0, i), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((1, _LANES), lambda i, f: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, bp), jnp.float32),
        interpret=interpret,
    )(z_t)
    return out[0, :b]


def _bwd_impl(z: jax.Array, g: jax.Array, order: int, interpret: bool) -> jax.Array:
    b, n, k = z.shape
    rows = _rows_for(order)
    z_t, bp = _pad_transpose(z.astype(jnp.float32))
    g_p = jnp.pad(g.astype(jnp.float32), (0, bp - b))[None, :]  # [1, BP]
    zbar_t = pl.pallas_call(
        functools.partial(_bwd_kernel, order=order, rows=rows),
        grid=(bp // _LANES, k),
        in_specs=[
            pl.BlockSpec((1, n, _LANES), lambda i, f: (f, 0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _LANES), lambda i, f: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, n, _LANES), lambda i, f: (f, 0, i), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((k, n, bp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, rows, _LANES), jnp.float32)],
        interpret=interpret,
    )(z_t, g_p)
    return jnp.transpose(zbar_t, (2, 1, 0))[:b]  # [B, N, k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def anova_inter(z: jax.Array, order: int, interpret: bool = False) -> jax.Array:
    """Σ_{m=2..order} Σ_f ANOVA_m(z[·, ·, f]) per example.  z: [B, N, k] → [B].

    ``interpret=True`` runs the kernels in the Pallas interpreter (CPU
    testing); on TPU leave it False.
    """
    return _fwd_impl(z, order, interpret)


def _anova_inter_fwd(z, order, interpret):
    return _fwd_impl(z, order, interpret), z


def _anova_inter_bwd(order, interpret, z, g):
    return (_bwd_impl(z, g, order, interpret),)


anova_inter.defvjp(_anova_inter_fwd, _anova_inter_bwd)


def anova_inter_reference(z: jax.Array, order: int) -> jax.Array:
    """Brute-force oracle: sum over all m-subsets, for tests (O(N^order))."""
    import itertools

    import numpy as np

    z = np.asarray(z, np.float64)
    b, n, k = z.shape
    out = np.zeros(b)
    for m in range(2, order + 1):
        for subset in itertools.combinations(range(n), m):
            out += np.prod(z[:, subset, :], axis=1).sum(-1)
    return out
