"""The serve cell: its population, its schedule, its ``correct``."""

import time

import numpy as np
import pytest

from harness import cells, common, loadgen, serve


def _res(done, status, due=None, n_warm=1):
    due = np.array([0.0, 1.0, 1.1, 1.2, 1.3]) if due is None else due
    return {"n_warm": n_warm, "due": due, "sent": due + 0.001, "done": np.array(done, float),
            "status": np.array(status, np.uint8), "t_open": 1.0, "t_close": 2.0, "t_drained": 9.0,
            "which": np.zeros(due.size, int), "score": np.zeros((due.size, 2), np.float32)}


def test_frames_are_timed_from_when_they_were_due():
    res = _res([0.5, 1.010, 1.120, 1.230, 1.340], [[1, 1]] * 5)
    pop = serve.population(res, 1.0)
    assert pop["attempted"] == 4 and pop["failed"] == 0  # the warm-up frame is not of the window
    assert pop["serve_p50_ms"] == pytest.approx(np.percentile([10, 20, 30, 40], 50))
    assert pop["late_ms_p99"] == pytest.approx(1.0)
    assert pop["serve_rows_per_s"] == 8.0


def test_a_late_answer_is_answered_and_a_shed_one_is_failed():
    # frame 2 answers after the window closed: late, not failed, and its rows
    # are not of the rows answered inside the window; frame 3 was refused;
    # frame 4 never answered: failed, timed to the end of the drain.
    res = _res([0.5, 1.010, 2.500, 1.230, np.nan], [[1, 1], [1, 1], [1, 1], [2, 2], [0, 0]])
    pop = serve.population(res, 1.0)
    assert pop["attempted"] == 4 and pop["failed"] == 2
    assert pop["unanswered_rows"] == 2.0
    assert pop["serve_rows_per_s"] == 2.0
    assert pop["latency_ms_p99"] > 7000  # 9.0 - 1.3, the unanswered frame's wait


@pytest.mark.parametrize("seed", [1, 2, 3000000019])
def test_every_seed_sends_the_same_number_of_frames(seed):
    due, which, n_warm = loadgen.schedule(seed, 100.0, 2.0, 5.0, 64)
    assert (n_warm, due.size) == (200, 700)
    assert (np.diff(due[:n_warm]) >= 0).all() and (np.diff(due[n_warm:]) >= 0).all()
    assert due[n_warm - 1] < 2.0 <= due[n_warm] and due[-1] < 7.0 and which.max() < 64


def test_every_seed_sends_the_same_arrivals_in_another_order():
    """The seed must not change the work: one Poisson stream, the window's
    half-second blocks of it in the seed's order, a last partial block last."""
    (a, _, n), (b, _, _) = (loadgen.schedule(s, 100.0, 2.0, 5.2, 64) for s in (1, 3000000019))
    assert (a[:n] == b[:n]).all() and (a[n:] != b[n:]).any()

    def blocks(due):
        w = due[n:] - 2.0
        k = (w // loadgen.BLOCK_S).astype(int)
        return [tuple(np.round(w[k == i] - i * loadgen.BLOCK_S, 9)) for i in range(11)]

    ba, bb = blocks(a), blocks(b)
    assert sorted(ba[:10]) == sorted(bb[:10]) and ba[:10] != bb[:10] and ba[10] == bb[10]
    assert sum(map(len, ba)) == 520


def _run(toy_bench, tmp_path, seed=5):
    cell = cells.load_cell("fm8_criteo.serve_steady", toy_bench)
    return serve.run(cell, seed, 1.0, False, time.time(), require_chip=False, workroot=str(tmp_path))


def test_sound_server_is_correct(toy_bench, tmp_path):
    r = _run(toy_bench, tmp_path)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] == 50
    assert set(r["metrics"]) == {"serve_p50_ms", "serve_rows_per_s", "setup_s"}
    assert list(r)[-1] == "compared"


def test_an_answer_altered_where_it_is_produced_is_not_correct(toy_bench, tmp_path, monkeypatch):
    from fast_tffm_tpu import prediction

    real = prediction.make_predict_step

    def altered(model):
        step = real(model)
        return lambda state, batch: step(state, batch).at[0].add(1e-3)

    monkeypatch.setattr(prediction, "make_predict_step", altered)
    r = _run(toy_bench, tmp_path)
    assert r["correct"] is False
    assert r["compared"]["score_gap"]["value"] > 10 * r["compared"]["score_gap"]["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bfloat16_control_fails(toy_bench, seed):
    cell = cells.load_cell("fm8_criteo.serve_steady", toy_bench)
    ok, compared = common.decide(serve.planted(cell, seed, "control"), cell["traffic"]["limits"])
    assert ok is False and compared["score_gap"]["value"] > 3 * compared["score_gap"]["limit"]
