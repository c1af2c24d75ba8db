"""The cell ``fm3_k30_kdd12.train_fmb_order3`` at toy size on the CPU (2^14 rows,
batch 512: ``conftest.toy_bench``), its two controls, the module its configuration
names, and the kernel's work model and roofline reader."""

import json
import os
import time

import numpy as np
import pytest

from harness import anova, cells, common, scopes, train
from harness.models import fm2, hofm

CELL = "fm3_k30_kdd12.train_fmb_order3"
SEEDS = [11, 3000003701, 3000003702]
NAMES = ("loss_gap", "grad1_norm_gap", "delta3_norm_gap")


def _run(bench, tmp_path, seed, before=lambda cell: None):
    cell = cells.load_cell(CELL, bench)
    m = cell["model"]
    assert isinstance(m, hofm.Model) and (m.row_dim, m.order, m.k, m.nnz, m.reads_fields) == (31, 3, 30, 11, False)
    before(cell)
    return train.run(cell, seed, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_toy_cell_is_correct_and_its_two_controls_are_not(toy_bench, tmp_path, monkeypatch, seed):
    r = _run(toy_bench, tmp_path, seed)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 4
    assert set(r["compared"]) == set(NAMES)
    sound = {k: c["value"] for k, c in r["compared"].items()}
    assert max(sound.values()) < 1e-6  # float32 against float32: rounding, under a tenth of each limit
    assert all(10 * sound[k] < c["limit"] for k, c in r["compared"].items())

    cell = cells.load_cell(CELL, toy_bench)
    ok, control = common.decide(train.planted(cell, seed, "control"), cell["traffic"]["limits"])
    assert ok is False and all(c["value"] > c["limit"] for c in control.values())  # bfloat16 fails all three

    # The reference without the third-order term (the order-2 score: the bias term and A^2) where the program
    # computes it.  At the toy's batch the gap is wider than at the cell's (CHIP_READINGS below), so the limits
    # are met with more room here than there.
    no_a3 = _run(toy_bench, tmp_path, seed, before=lambda cell: monkeypatch.setattr(hofm.Model, "score", fm2.Model.score))
    assert no_a3["correct"] is False
    assert max(no_a3["compared"][k]["value"] / no_a3["compared"][k]["limit"] for k in NAMES) > 5


def test_the_dynamic_program_is_the_power_sums_and_the_subsets():
    import itertools

    model = hofm.Model({"General": {"vocabulary_size": 64, "factor_num": 30, "order": 3}, "Train": {"batch_size": 8, "max_nnz": 11}})
    rng = np.random.default_rng(7)
    rows = rng.uniform(-0.5, 0.5, (8, 11, 31)).astype(np.float32)
    vals = rng.uniform(0.05, 1.5, (8, 11)).astype(np.float32)
    vals[:, -2:] = 0.0  # padding is neutral
    got = np.asarray(model.score(rows, vals, None), np.float64)
    z = rows[..., 1:].astype(np.float64) * vals[..., None]
    p1, p2, p3 = (np.sum(z**t, axis=1) for t in (1, 2, 3))
    linear = np.sum(rows[..., 0].astype(np.float64) * vals, axis=-1)
    sums = linear + np.sum((p1**2 - p2) / 2 + (p1**3 - 3 * p1 * p2 + 2 * p3) / 6, axis=-1)
    brute = linear.copy()
    for m in (2, 3):
        for subset in itertools.combinations(range(11), m):
            brute += np.prod(z[:, subset, :], axis=1).sum(-1)
    assert np.allclose(sums, brute, rtol=1e-12)
    assert np.allclose(got, brute, rtol=2e-6, atol=1e-6)
    # order 2 is fm2's score, and the third-order term is the difference
    model.order = 2
    assert np.allclose(np.asarray(model.score(rows, vals, None)), np.asarray(fm2.Model.score(model, rows, vals, None)), rtol=2e-6, atol=1e-6)


def test_a_checkout_without_the_configuration_says_unknown_workload_at_once(toy_bench):
    os.remove(os.path.join(toy_bench, "configs", "fm3_k30_kdd12.json"))
    with pytest.raises(SystemExit, match="unknown workload 'fm3_k30_kdd12.train_fmb_order3'"):
        cells.load_cell(CELL, toy_bench)


def test_the_work_model_is_the_degree_steps_and_the_rows_once_each_way():
    flops, hbm = anova.anova_work(65536, 11, 30, 3)
    assert flops == 65536 * 11 * 30 * 18 and hbm == 2 * 65536 * 11 * 30 * 4
    model = cells.load_cell(CELL)["model"]
    assert (model.batch, model.nnz, model.k, model.order, model.vocab) == (65536, 11, 30, 3, 1 << 25)
    uniq = 500000
    assert model.step_flops(65536, 11, uniq) == 65536 * 11 * (18 * 30 + 4) + uniq * 31 * 6
    ids = np.random.default_rng(0).integers(0, 5000, size=(256, 11))
    from fast_tffm_tpu.profiling import modeled_step_bytes

    assert model.step_bytes(ids) == modeled_step_bytes(ids, 31, 31)
    assert model.score_bytes(128, 11) == 128 * (11 * (8 + 124) + 4)


def _ops(*events):
    return {"/device:TPU:0": list(events)}


def test_the_roofline_reads_fm_anova_forward_and_backward_and_nothing_is_none():
    ops = _ops(
        ("fm.anova.2", "jit(step)/jvp(fm.interaction)/fm.anova/pallas_call:", 0.0, 0.004),
        ("fusion.77", "jit(step)/transpose(jvp(fm.interaction))/fm.anova/transpose:", 1.0, 0.001),
        ("fm.anova.3", "jit(step)/transpose(jvp(fm.interaction))/fm.anova/pallas_call:", 1.001, 0.005),
        ("fusion.81", "jit(step)/jvp(fm.interaction)/mul:", 2.0, 1.0),  # the linear term: not the kernel's
        ("fm.tail.1", "jit(step)/fm.tail/pallas_call:", 4.0, 2.0),
    )
    model = hofm.Model.__new__(hofm.Model)
    ctx = {"trace": {"busy_s": 5.0}, "scoped_ops": ops, "n_steps": 2, "device_kind": "TPU v5e", "model": model}
    m = json.load(open(os.path.join(cells.BENCH_DIR, "metrics", "fm.anova_roofline.json")))
    ms = json.load(open(os.path.join(cells.BENCH_DIR, "metrics", "fm.anova_ms.json")))
    assert anova.roofline(m, ctx) is None  # a model that states no batch: nothing to read
    model.__dict__.update(batch=65536, nnz=11, k=30, order=3)
    least = 2 * 65536 * 11 * 30 * 4 / 819e9  # HBM bounds it (0.21 ms; the FLOPs would take 0.002)
    assert scopes.scope_ms(ms, ctx) == pytest.approx(5.0)
    assert anova.roofline(m, ctx) == pytest.approx(100 * least * 2 / 0.010)
    # a program from before PR 37 names no fm.anova: both readers give None and the line leaves them out
    old = dict(ctx, scoped_ops=_ops(("fusion.81", "jit(step)/jvp(fm.interaction)/mul:", 2.0, 1.0)))
    assert anova.roofline(m, old) is None and scopes.scope_ms(ms, old) is None
    assert anova.roofline(m, {"trace": None, "n_steps": 2, "trace_dir": "/nowhere", "model": model}) is None


def test_the_programs_counter_is_read_from_the_profile_record_where_there_is_one():
    from harness import readers

    m = json.load(open(os.path.join(cells.BENCH_DIR, "metrics", "fm.anova_programs_per_step.json")))
    records = [
        {"kind": "profile", "step": 1, "program": "train_step", "order": 3, "interaction_form": "pallas_anova", "anova_programs_per_step": 30720},
        {"kind": "profile", "step": 5, "program": "trace", "flops": None},
        {"kind": "train", "step": 8, "anova_programs_per_step": 7},
    ]
    assert readers.reader(m["reader"])(m, {"records": records, "steps": (4, 8)}) == 30720  # written at step 1: phase all
    assert readers.reader(m["reader"])(m, {"records": [dict(records[0], anova_programs_per_step=None)], "steps": (4, 8)}) is None
    assert readers.reader(m["reader"])(m, {"records": records[1:], "steps": (4, 8)}) is None


# What ``train.compare`` read on the chip at the cell's own size, B = 65,536 x 11 (my chip runs, PR 37; PERF.md §6):
# (loss_gap, grad1_norm_gap, delta3_norm_gap): the sound program's worst by number over 14 seeds; every seed of the
# reference without the third-order term (``chip_order3_readings.py``); the bfloat16 control's extremes.
CHIP_READINGS = {
    "sound": [(0.0, 3.01e-8, 1.36e-8)],
    "no_a3": [
        (0.0, 2.46e-5, 2.88e-5), (1.72e-7, 1.59e-5, 2.14e-5), (0.0, 2.76e-5, 2.43e-5), (0.0, 2.77e-5, 1.27e-5),
        (8.6e-8, 7.99e-7, 1.04e-5), (0.0, 1.69e-5, 2.21e-5), (8.6e-8, 3.12e-5, 3.55e-5), (8.6e-8, 3.39e-5, 1.93e-5),
        (1.72e-7, 4.14e-5, 4.34e-5), (1.72e-7, 2.19e-5, 2.68e-5),
    ],
    "bfloat16": [(2.5103e-3, 1042.07, 1010.28), (2.5122e-3, 1046.02, 1012.45)],
}


def test_the_mix_is_train_fmb_under_limits_that_see_the_third_order_term():
    mix = json.load(open(os.path.join(cells.BENCH_DIR, "traffic", "train_fmb_order3.json")))
    plain = json.load(open(os.path.join(cells.BENCH_DIR, "traffic", "train_fmb.json")))
    # Key for key but for the loss fetch's cadence: 8 steps, so that a host stall finds the chip fed (PERF.md section 2).
    cadence = lambda m: m["ini"]["Train"]["log_every"]
    same = lambda m: {k: v for k, v in m.items() if k not in ("what", "limits", "limits_from", "ini")} | {
        "ini": {**m["ini"], "Train": {k: v for k, v in m["ini"]["Train"].items() if k != "log_every"}}}
    assert same(mix) == same(plain) and set(mix["limits"]) == set(plain["limits"]) and mix["limits_from"]
    assert (cadence(mix), cadence(plain)) == (8, 4)
    decide = lambda reading, limits: common.decide(dict(zip(NAMES, reading)), limits)
    for what, readings in CHIP_READINGS.items():
        for reading in readings:
            ok, compared = decide(reading, mix["limits"])
            over = [c["value"] / c["limit"] for c in compared.values()]
            if what == "sound":
                assert ok and max(over) <= 0.1, (what, reading)  # a tenth of each limit at most
            elif what == "no_a3":
                assert not ok and max(over) >= 5, (what, reading)  # at least one limit fivefold
                assert decide(reading, plain["limits"])[0]  # why train_fmb's limits will not do
            else:
                assert not ok and min(over) > 1, (what, reading)  # bfloat16: all three
