"""The train cell's ``correct``: true on the sound program, false with the
timed path broken underneath, false for the lower-precision control."""

import time

import pytest

from harness import cells, train


def _run(toy_bench, tmp_path, seed=11, workload="fm8_criteo.train_fmb"):
    cell = cells.load_cell(workload, toy_bench)
    return train.run(cell, seed, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))


def test_sound_program_is_correct(toy_bench, tmp_path):
    r = _run(toy_bench, tmp_path)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] % 4 == 0
    assert set(r["metrics"]) == {"train_examples_per_s_per_chip", "setup_s"}
    assert list(r)[-1] == "compared" and set(r["compared"]) == {"loss_gap", "grad1_norm_gap", "delta3_norm_gap"}


def _break(monkeypatch, wrap):
    from fast_tffm_tpu import training

    def broken(model, lr, **kw):
        import jax

        from fast_tffm_tpu.trainer import train_step_body

        plain = jax.jit(lambda st, b: train_step_body(model, lr, st, b))
        return lambda state, batch: wrap(plain, state, batch)

    monkeypatch.setattr(training, "make_train_step", broken)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(toy_bench, tmp_path, monkeypatch):
    def wrap(step, state, batch):
        new, loss = step(state, batch)
        return state._replace(step=new.step), loss

    _break(monkeypatch, wrap)
    r = _run(toy_bench, tmp_path)
    assert r["correct"] is False
    assert r["compared"]["delta3_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(toy_bench, tmp_path, monkeypatch):
    import dataclasses

    def wrap(step, state, batch):
        half = batch.weights.shape[0] // 2
        return step(state, dataclasses.replace(batch, weights=batch.weights.at[half:].set(0.0)))

    _break(monkeypatch, wrap)
    r = _run(toy_bench, tmp_path)
    assert r["correct"] is False
    assert r["compared"]["grad1_norm_gap"]["value"] > 10 * r["compared"]["grad1_norm_gap"]["limit"]


DIST = "fm16_criteo_row4.dist_train_fmb"


@pytest.fixture
def four_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("XLA_FLAGS was set without four host devices")


def test_the_row_sharded_program_is_correct(toy_bench, tmp_path, four_devices):
    r = _run(toy_bench, tmp_path, workload=DIST)
    assert r["correct"] is True and r["device"]["count"] == 4


def test_the_exchange_between_chips_left_out_is_not_correct(toy_bench, tmp_path, monkeypatch, four_devices):
    import math

    import jax.numpy as jnp
    from jax import lax

    from fast_tffm_tpu.parallel import embedding

    class NoExchange:
        """``lax`` as the update sees it, its gather over both mesh axes
        handing each chip its own part and nothing of its peers'."""

        def __getattr__(self, name):
            return getattr(lax, name)

        def all_gather(self, x, axis_name, **kw):
            if not isinstance(axis_name, tuple):  # the lookup's exchange of ids stays
                return lax.all_gather(x, axis_name, **kw)
            peers = math.prod(lax.axis_size(a) for a in axis_name) - 1
            return jnp.concatenate([x] + [jnp.zeros_like(x)] * peers)

    monkeypatch.setattr(embedding, "lax", NoExchange())
    r = _run(toy_bench, tmp_path, workload=DIST)
    assert r["correct"] is False
    assert r["compared"]["grad1_norm_gap"]["value"] > 10 * r["compared"]["grad1_norm_gap"]["limit"]


@pytest.mark.parametrize("workload,what", [("fm8_criteo.train_fmb", "control"), (DIST, "control"), (DIST, "no_exchange")])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bfloat16_control_and_the_planted_exchange_fail(toy_bench, seed, workload, what):
    from harness import common

    cell = cells.load_cell(workload, toy_bench)
    numbers = train.planted(cell, seed, what)
    ok, compared = common.decide(numbers, cell["traffic"]["limits"])
    assert ok is False and all(c["value"] > 3 * c["limit"] for c in compared.values())


def test_off_the_chip_the_harness_refuses(toy_bench, tmp_path):
    cell = cells.load_cell("fm8_criteo.train_fmb", toy_bench)
    with pytest.raises(SystemExit):
        train.run(cell, 1, 0.2, False, time.time(), workroot=str(tmp_path))
