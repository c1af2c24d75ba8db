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


# -- the measured cell's shapes (fm3_k30_kdd12: 11 ids a row, k = 30, order 3) --


@pytest.mark.parametrize("batch", [128, 200], ids=["one_tile", "two_tiles_padded"])
def test_forward_at_the_cells_width_matches_oracle_and_scan(batch):
    rng = np.random.default_rng(batch)
    z = _z(rng, batch, 11, 30, scale=0.3)
    got = np.asarray(anova_inter(z, 3, True))
    np.testing.assert_allclose(got, anova_inter_reference(z, 3), rtol=1e-4, atol=1e-5)
    a_final, _ = _anova_scan_fwd(z, 3)
    np.testing.assert_allclose(got, np.asarray(jnp.sum(a_final[:, 2:, :], axis=(1, 2))), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("batch", [128, 200], ids=["one_tile", "two_tiles_padded"])
def test_backward_at_the_cells_width_matches_the_power_sums(batch):
    """d/dz of A^2 + A^3 by power sums p_t = sum_i z_i^t, in float64:
    dA^2/dz_j = p_1 - z_j ; dA^3/dz_j = (p_1^2 - p_2)/2 - z_j p_1 + z_j^2."""
    rng = np.random.default_rng(30 + batch)
    z = _z(rng, batch, 11, 30, scale=0.3)
    w = jnp.asarray(rng.normal(size=(batch,)).astype(np.float32))
    got = np.asarray(jax.grad(lambda z: jnp.sum(anova_inter(z, 3, True) * w))(z))
    z64 = np.asarray(z, np.float64)
    p1, p2 = z64.sum(1, keepdims=True), (z64**2).sum(1, keepdims=True)
    want = ((p1 - z64) + (p1**2 - p2) / 2 - z64 * p1 + z64**2) * np.asarray(w, np.float64)[:, None, None]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_grid_is_a_tile_of_128_rows_times_a_factor():
    from fast_tffm_tpu.ops.pallas_anova import grid_programs

    assert grid_programs(65536, 30) == 15360 and grid_programs(130, 8) == 16 and grid_programs(128, 1) == 1


@pytest.mark.parametrize(
    "order, use_pallas, backend, form",
    [
        (2, None, "tpu", "order2"), (2, True, "cpu", "order2"), (3, None, "tpu", "pallas_anova"),
        (3, None, "cpu", "scan"), (3, True, "cpu", "pallas_anova"), (4, False, "tpu", "scan"),
    ],
)
def test_the_form_is_chosen_from_the_order_and_the_backend(order, use_pallas, backend, form):
    from fast_tffm_tpu.ops.fm import describe_interaction, interaction_form, interaction_profile

    assert interaction_form(order, use_pallas, backend) == form
    profile = interaction_profile(order, 65536, 30, form=form)
    programs = 30720 if form == "pallas_anova" else None
    assert profile == {"order": order, "interaction_form": form, "anova_programs_per_step": programs}
    forward_only = interaction_profile(order, 65536, 30, backward=False, form=form)["anova_programs_per_step"]
    assert forward_only == (15360 if programs else None)
    said = describe_interaction(order, 65536, 30, form=form)
    assert said.startswith(f"order {order}, ") and ("30720 grid programs" in said) == (form == "pallas_anova")
    if order >= 3 and backend == "cpu" and use_pallas is None:
        assert interaction_form(order) == "scan"  # what this suite's steps get when nobody says


@pytest.mark.parametrize("form", ["pallas_anova", "scan"])
def test_the_lowered_step_names_fm_anova_forward_and_backward(form, monkeypatch):
    """Both forms of the ANOVA interaction stand under ``fm.anova`` inside
    ``fm.interaction`` in the train step's HLO, the backward as
    ``transpose(jvp(...))``: what ``benchmark/harness/scopes.py`` reads
    ``fm.anova_ms`` from.  Order 2 names no such scope."""
    import re

    from fast_tffm_tpu.models import FMModel
    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.ops import fm
    from fast_tffm_tpu.trainer import init_state, make_train_step

    def names(order):
        model = FMModel(vocabulary_size=256, factor_num=30, order=order)
        state = init_state(model, jax.random.key(0), 0.1, "element")
        b, n = 128, 11
        batch = Batch(
            labels=jnp.zeros((b,)), ids=jnp.zeros((b, n), jnp.int32), vals=jnp.ones((b, n)),
            fields=jnp.zeros((b, 0), jnp.int32), weights=jnp.ones((b,)),
        )
        text = make_train_step(model, 0.05).lower(state, batch).as_text(dialect="hlo", debug_info=True)
        return set(re.findall(r'op_name="jit\(step\)/([^"]*)"', text))

    monkeypatch.setattr(fm, "interaction_form", lambda order, use_pallas=None, backend=None: form if order > 2 else "order2")
    got = names(3)
    forward = {n for n in got if n.startswith("jvp(fm.interaction)/fm.anova/")}
    backward = {n for n in got if n.startswith("transpose(jvp(fm.interaction))/fm.anova/")}
    assert forward and backward
    if form == "pallas_anova":  # interpreted here: the grid is a loop; the layout transposes are the kernel's, under its scope
        assert any(n.endswith("/transpose") for n in forward) and any(n.endswith("/transpose") for n in backward)
        assert any("/while/body/" in n for n in forward) and any("/while/body/" in n for n in backward)
    assert not any("fm.anova" in n for n in names(2))
