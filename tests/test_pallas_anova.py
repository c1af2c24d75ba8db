"""Pallas ANOVA kernel vs. brute-force oracle and the lax.scan path.

Runs the kernels in the Pallas interpreter on the CPU mesh; real-TPU
compilation of the same kernels is exercised by chip_smoke.py on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.ops.fm import _anova_scan_fwd, fm_score
from fast_tffm_tpu.ops.pallas_anova import anova_inter, anova_inter_reference


def _z(rng, B, N, k, scale=0.4):
    return jnp.asarray(rng.normal(size=(B, N, k)).astype(np.float32)) * scale


@pytest.mark.parametrize("order", [3, 4, 5])
def test_forward_matches_oracle(order):
    rng = np.random.default_rng(order)
    z = _z(rng, 9, 6, 3)
    got = np.asarray(anova_inter(z, order, True))
    want = anova_inter_reference(z, order)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_forward_matches_scan_nonaligned_batch():
    # B=130 exercises the 128-lane padding path; order above N exercises the
    # degenerate degrees (ANOVA_m = 0 for m > N).
    rng = np.random.default_rng(0)
    z = _z(rng, 130, 5, 8)
    for order in (3, 6):
        a_final, _ = _anova_scan_fwd(z, order)
        want = np.asarray(jnp.sum(a_final[:, 2 : order + 1, :], axis=(1, 2)))
        got = np.asarray(anova_inter(z, order, True))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("order", [3, 4])
def test_backward_matches_scan(order):
    rng = np.random.default_rng(10 + order)
    z = _z(rng, 17, 7, 4)
    w = jnp.asarray(rng.normal(size=(17,)).astype(np.float32))

    def f_pallas(z):
        return jnp.sum(anova_inter(z, order, True) * w)

    def f_scan(z):
        a_final, _ = _anova_scan_fwd(z, order)
        return jnp.sum(jnp.sum(a_final[:, 2 : order + 1, :], axis=(1, 2)) * w)

    g1 = np.asarray(jax.grad(f_pallas)(z))
    g2 = np.asarray(jax.grad(f_scan)(z))
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


def test_padding_is_neutral():
    # Zero-valued z slots (feature padding) must not change score or grad.
    rng = np.random.default_rng(3)
    z = _z(rng, 8, 4, 3)
    z_pad = jnp.concatenate([z, jnp.zeros((8, 3, 3), jnp.float32)], axis=1)
    np.testing.assert_allclose(
        np.asarray(anova_inter(z, 3, True)),
        np.asarray(anova_inter(z_pad, 3, True)),
        rtol=1e-5,
    )
    g = jax.grad(lambda z: jnp.sum(anova_inter(z, 3, True)))(z_pad)
    assert np.asarray(g).shape == (8, 7, 3)


def test_fm_score_pallas_route_matches_scan_route():
    rng = np.random.default_rng(5)
    B, N, k, order = 12, 6, 4, 3
    rows = jnp.asarray(rng.normal(size=(B, N, 1 + k)).astype(np.float32)) * 0.5
    vals = jnp.asarray(rng.normal(size=(B, N)).astype(np.float32))
    want = np.asarray(fm_score(rows, vals, order=order, use_pallas=False))
    # Off-TPU the public pallas route auto-selects the Pallas interpreter.
    got = np.asarray(fm_score(rows, vals, order=order, use_pallas=True))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)

    g1 = jax.grad(lambda r: jnp.sum(fm_score(r, vals, order=order, use_pallas=True)))(rows)
    g2 = jax.grad(lambda r: jnp.sum(fm_score(r, vals, order=order, use_pallas=False)))(rows)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-5)
