"""The seam's other half: a model with parameters outside the table
(``harness/models/deepfm.py``, named by no shipped configuration) enters
through new files alone, both windows carry its dense leaves, and the check
holds the program to them by two numbers of their own.

Limits of the toy mix (``conftest.DENSE_LIMITS`` beside ``train_fmb``'s 1e-4
each), from readings at the toy's own size on the CPU, seeds 1-12: the sound
program at most 9.1e-8 / 1.2e-8 / 1.3e-8 / 1.6e-7 / 1.4e-7; the bfloat16 control
at least 7.3e-4 / 0.196 / 0.049 / 0.83 / 0.23; ``dense_frozen`` 1.0 on both dense
numbers.  They are no chip's readings and no cell's limits."""

import hashlib
import json
import os
import time

import numpy as np
import pytest

from harness import cells, common, gen, loadgen, reference, serve, train
from harness.models import deepfm, dense_leaves, fm2

CELL = "deepfm_toy.train_fmb_dense_toy"
FIVE = {"loss_gap", "grad1_norm_gap", "delta3_norm_gap", "dense_grad1_norm_gap", "dense_delta3_norm_gap"}


def _ini(hidden="16 16 16", dtype="float32", fields=39, k=10):
    from conftest import DEEPFM_TOY

    ini = {s: dict(kv) for s, kv in DEEPFM_TOY["ini"].items()}
    ini["General"].update(hidden_dims=hidden, compute_dtype=dtype, num_fields=fields, factor_num=k)
    ini["Train"]["max_nnz"] = fields
    return ini


def _program_model(ini, tmp_path):
    from fast_tffm_tpu.config import build_model, load_config

    return build_model(load_config(cells.write_ini(str(tmp_path / "cell.cfg"), ini)))


# --- the module ----------------------------------------------------------------


def test_the_score_is_the_fm_score_plus_a_hand_written_perceptron():
    rng = np.random.default_rng(0)
    model = deepfm.Model(_ini(hidden="5 4 3", fields=3, k=2))
    rows = rng.uniform(-0.5, 0.5, (4, 3, 3)).astype(np.float32)
    vals = rng.uniform(0.1, 1.5, (4, 3)).astype(np.float32)
    dense = {k: rng.uniform(-1, 1, np.shape(v)).astype(np.float32) for k, v in model.init_dense().items()}
    assert sorted(dense) == ["b0", "b1", "b2", "b3", "w0", "w1", "w2", "w3"]
    want = np.asarray(fm2.Model.score(model, rows, vals, None), np.float64)
    for b in range(4):
        x = np.array([rows[b, i, 1 + f] * vals[b, i] for i in range(3) for f in range(2)], np.float64)  # slot-major, then factor
        for li in range(4):
            x = x @ dense[f"w{li}"].astype(np.float64) + dense[f"b{li}"]
            if li < 3:
                x = np.where(x > 0, x, 0.0)
        assert x.shape == (1,)
        want[b] += x[0]
    got = np.asarray(model.score(rows, vals, np.zeros((4, 3), np.int32), dense))
    assert np.max(np.abs(got - want)) < 1e-5


@pytest.mark.parametrize("hidden,dtype", [("16 16 16", "float32"), ("16 16 16", "bfloat16"), ("24, 8", "float32"), ("24, 8", "bfloat16")])
def test_the_module_is_the_programs_model_row_fields_and_dense_leaves(tmp_path, hidden, dtype):
    """Where a program PR renames or reshapes a dense leaf, or draws it
    otherwise, the plain reference meets it here: names, shapes and values of
    ``init_dense()`` against ``trainer.init_state(...).dense``, exactly."""
    import jax

    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.trainer import init_state

    ini = _ini(hidden, dtype)
    model, program = deepfm.Model(ini), _program_model(ini, tmp_path)
    assert model.row_dim == program.row_dim == 11
    assert model.reads_fields == bool(getattr(program, "uses_fields", False))
    state = init_state(program, jax.random.key(0))
    ours = model.init_dense()
    assert sorted(ours) == sorted(state.dense) and len(ours) == 2 * (len(model.hidden) + 1)
    for name, leaf in state.dense.items():
        assert ours[name].shape == leaf.shape and ours[name].dtype == leaf.dtype, name
        assert np.array_equal(np.asarray(ours[name]), np.asarray(leaf)), name
    assert np.array_equal(np.asarray(state.table[:64]), np.asarray(model.init_rows(np.arange(64))))
    if dtype == "float32":
        rng = np.random.default_rng(1)
        rows = jax.numpy.asarray(rng.uniform(-0.3, 0.3, (8, 39, 11)), jax.numpy.float32)
        vals = rng.uniform(0.05, 1.5, (8, 39)).astype(np.float32)
        batch = Batch(labels=jax.numpy.zeros(8), ids=jax.numpy.zeros((8, 39), jax.numpy.int32), vals=jax.numpy.asarray(vals),
                      fields=jax.numpy.zeros((8, 39), jax.numpy.int32), weights=jax.numpy.ones(8))
        want = np.asarray(program.score(rows, state.dense, batch))
        assert np.max(np.abs(np.asarray(model.score(rows, vals, None, ours)) - want)) < 1e-5


def test_a_module_without_init_dense_has_no_dense_leaves():
    model = fm2.Model(_ini())
    assert dense_leaves(model) == {} and not hasattr(model, "init_dense")


def test_the_perceptrons_share_of_the_work_model():
    model = deepfm.Model(_ini(hidden="400 400 400"))
    base = fm2.Model(_ini())
    assert model.weights == 390 * 400 + 400 * 400 + 400 * 400 + 400 == 476_400
    assert model.dense_elements == 476_400 + 1_201
    ids = np.random.default_rng(2).integers(0, 5000, size=(256, 39))
    total, uniq = base.step_bytes(ids)
    assert model.step_bytes(ids) == (total + 16 * 477_601, uniq)
    assert model.step_flops(256, 39, uniq) == base.step_flops(256, 39, uniq) + 6 * 476_400 * 256 + 6 * 477_601
    assert model.score_bytes(10, 39) == base.score_bytes(10, 39) + 4 * 477_601


def test_more_slots_than_fields_is_an_error():
    ini = _ini()
    ini["Train"]["max_nnz"] = 40
    with pytest.raises(SystemExit, match="max_nnz"):
        deepfm.Model(ini)


# --- the train window ------------------------------------------------------------


def _run(bench, tmp_path, seed=11, workload=CELL):
    cell = cells.load_cell(workload, bench)
    assert isinstance(cell["model"], deepfm.Model)
    return train.run(cell, seed, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))


def test_a_model_with_dense_leaves_is_correct_through_new_files_alone(dense_bench, tmp_path):
    r = _run(dense_bench, tmp_path)
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["compared"]) == FIVE and list(r)[-1] == "compared"
    assert all(c["value"] < 0.1 * c["limit"] for c in r["compared"].values())


def _break(monkeypatch, wrap):
    from fast_tffm_tpu import training

    def broken(model, lr, **kw):
        import jax

        from fast_tffm_tpu.trainer import train_step_body

        plain = jax.jit(lambda st, b: train_step_body(model, lr, st, b))
        return lambda state, batch: wrap(state, *plain(state, batch))

    monkeypatch.setattr(training, "make_train_step", broken)


def test_a_step_that_drops_the_dense_gradient_is_not_correct(dense_bench, tmp_path, monkeypatch):
    _break(monkeypatch, lambda old, new, loss: (new._replace(dense=old.dense, dense_opt=old.dense_opt), loss))
    r = _run(dense_bench, tmp_path)
    assert r["correct"] is False
    assert r["compared"]["dense_grad1_norm_gap"]["value"] == pytest.approx(1.0)
    assert r["compared"]["dense_delta3_norm_gap"]["value"] == pytest.approx(1.0)
    assert r["compared"]["grad1_norm_gap"]["value"] < r["compared"]["grad1_norm_gap"]["limit"]  # the table's first step is sound


def test_a_step_that_drops_the_tables_update_is_not_correct_by_the_tables_numbers(dense_bench, tmp_path, monkeypatch):
    _break(monkeypatch, lambda old, new, loss: (new._replace(table=old.table, table_opt=old.table_opt), loss))
    r = _run(dense_bench, tmp_path)
    assert r["correct"] is False
    assert r["compared"]["delta3_norm_gap"]["value"] == pytest.approx(1.0)
    # The dense leaves' first step saw the sound table: their first gradient
    # is inside.  Their steps 2 and 3 read rows that never moved, which shows
    # in their change (0.011 here), a hundredth of what the table's own number reads.
    assert r["compared"]["dense_grad1_norm_gap"]["value"] < r["compared"]["dense_grad1_norm_gap"]["limit"]
    assert r["compared"]["dense_delta3_norm_gap"]["value"] < 0.05


def test_a_program_without_dense_leaves_under_a_model_that_has_them_reads_infinity(dense_bench, tmp_path, monkeypatch):
    """The program's state says ``dense = {}`` (what ``model = fm`` trains)
    where the harness's model has leaves: nothing is captured, and the two
    dense numbers are infinite, never absent."""
    cell = cells.load_cell(CELL, dense_bench)
    cell["ini"]["General"]["model"] = "fm"
    r = train.run(cell, 11, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))
    assert r["correct"] is False
    assert all(r["compared"][n]["value"] == float("inf") for n in train.DENSE_NUMBERS)


@pytest.mark.parametrize("what", ["control", "dense_frozen"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bfloat16_control_and_the_frozen_dense_leaves_fail(dense_bench, seed, what):
    cell = cells.load_cell(CELL, dense_bench)
    numbers = train.planted(cell, seed, what)
    ok, compared = common.decide(numbers, cell["traffic"]["limits"])
    assert ok is False and set(compared) == FIVE
    assert all(compared[n]["value"] > 3 * compared[n]["limit"] for n in train.DENSE_NUMBERS)
    if what == "dense_frozen":
        assert numbers["dense_grad1_norm_gap"] == numbers["dense_delta3_norm_gap"] == 1.0
        assert numbers["grad1_norm_gap"] < 1e-8  # the table's first step is the sound one


def test_frozen_dense_leaves_cannot_be_planted_in_a_model_that_has_none(toy_bench):
    with pytest.raises(SystemExit, match="no dense leaves"):
        train.planted(cells.load_cell("fm8_criteo.train_fmb", toy_bench), 1, "dense_frozen")


@pytest.mark.parametrize("lacking", [("dense_grad1_norm_gap",), ("dense_delta3_norm_gap",), train.DENSE_NUMBERS])
def test_a_dense_model_under_a_mix_without_dense_limits_exits(dense_bench, tmp_path, lacking):
    path = os.path.join(dense_bench, "traffic", "train_fmb_dense_toy.json")
    mix = json.load(open(path))
    for name in lacking:
        del mix["limits"][name]
    json.dump(mix, open(path, "w"))
    with pytest.raises(SystemExit, match="states no limit for " + " or ".join(lacking)):
        _run(dense_bench, tmp_path)
    with pytest.raises(SystemExit, match="states no limit"):
        _run(dense_bench, tmp_path, workload="deepfm_toy.train_fmb")  # the shipped mix, which states neither


def test_a_dense_leaf_whose_reference_norm_is_zero_is_an_error_that_names_it():
    leaf = lambda v: {"w0": np.full((2, 2), v, np.float32), "b0": np.full((2,), v, np.float32)}
    rows = np.ones((3, 2), np.float32)
    side = {"losses": [1.0], "t0": rows * 0, "at1": np.arange(3), "t1": rows, "a1": rows, "t3": rows,
            "d0": leaf(0.0), "d1": leaf(1.0), "da1": leaf(1.0), "d3": leaf(1.0)}
    assert train.compare(side, side, 0.05)["dense_grad1_norm_gap"] == 0.0
    still = dict(side, d3=dict(leaf(1.0), b0=np.zeros((2,), np.float32)))
    with pytest.raises(SystemExit, match="change after three steps of the dense leaf 'b0'"):
        train.compare(side, still, 0.05)
    renamed = dict(side, d1={"w0": side["d1"]["w0"], "bias0": side["d1"]["b0"]})
    got = train.compare(renamed, side, 0.05)
    assert got["dense_grad1_norm_gap"] == float("inf") and got["grad1_norm_gap"] == 0.0


# --- the serve window ------------------------------------------------------------


def _contents_sha256(path):
    """A model file's arrays, names, types and shapes; not its time stamp nor
    the random id of the save, which differ between two writes of one state."""
    z, h = np.load(path, allow_pickle=False), hashlib.sha256()
    for k in sorted(z.files):
        if k not in ("save_id", "published_at"):
            a = z[k]
            h.update(f"{k} {a.dtype} {a.shape} ".encode() + np.ascontiguousarray(a).tobytes())
    return sorted(z.files), h.hexdigest()


# Recorded from the parent commit (f984669, PR 40): the toy ``fm8_criteo_rowacc.serve_steady``'s model file.
ROWACC_FILE = {5: "c95ad66bff222e97a9c69c62b4a67952e9cb3a3dfd50eabe4afd54dcd292098d",
               3000000019: "a9bd015aa9a5d88ac44024e967425ec6632092f924cbc617f4f92adee60d7e19"}


@pytest.mark.parametrize("seed", sorted(ROWACC_FILE))
def test_a_model_without_dense_leaves_is_served_from_the_file_it_was(toy_bench, tmp_path, seed):
    cell = cells.load_cell("fm8_criteo_rowacc.serve_steady", toy_bench)
    work, cfg = common.configured(cell, cell["name"], str(tmp_path))
    serve.write_model_file(cfg, seed, cell["model"].row_dim, dense_leaves(cell["model"]))
    names, sha = _contents_sha256(cfg.model_file)
    assert names == ["published_at", "save_id", "step", "table", "table_accum"]
    assert sha == ROWACC_FILE[seed]


def test_a_dense_models_file_is_restored_and_scored_by_the_program_as_the_reference_scores_it(dense_bench, tmp_path):
    import jax.numpy as jnp

    from fast_tffm_tpu import prediction
    from fast_tffm_tpu.models.base import Batch

    cell = cells.load_cell("deepfm_toy.serve_steady", dense_bench)
    model, seed = serve.served_model(cell), 7
    work, cfg = common.configured(cell, cell["name"], str(tmp_path))
    serve.write_model_file(cfg, seed, model.row_dim, dense_leaves(model))
    names, _ = _contents_sha256(cfg.model_file)
    assert [n for n in names if n.startswith("dense")] == [f"dense_{i}" for i in range(8)] + [f"dense_accum_{i}" for i in range(8)]
    z = np.load(cfg.model_file)
    ours = model.init_dense()
    for i, name in enumerate(sorted(ours)):  # the program flattens a dict by sorted key
        assert np.array_equal(z[f"dense_{i}"], np.asarray(ours[name])), name
        assert np.all(z[f"dense_accum_{i}"] == np.float32(0.1)) and z[f"dense_accum_{i}"].shape == ours[name].shape

    spec = serve._spec(cell, seed, 1.0, 0, "")
    want = serve.pool_reference(seed, spec, model)
    program, state = prediction.load_scoring_state(cfg, log=lambda *a: None)
    score = prediction.make_score_fn(cfg, state, cfg.max_nnz, model=program)
    _, ids, vals = loadgen.pool_rows(seed, spec)
    n = 4 * spec["frame_rows"]
    batch = Batch(labels=jnp.zeros(n), ids=jnp.asarray(ids[:n], jnp.int32), vals=jnp.asarray(vals[:n]),
                  fields=jnp.asarray(gen.column_fields(ids[:n]), jnp.int32), weights=jnp.ones(n))
    got = np.asarray(score.fn(state, batch))
    assert np.max(np.abs(got - want[:4].reshape(-1))) < cell["traffic"]["limits"]["score_gap"]
    # and the leaves matter: the same rows under the FM half alone read otherwise
    fm_only = np.asarray(reference.score_rows(fm2.Model.score.__get__(model), serve.seed_table(seed, spec["vocab"], model.row_dim), ids[:n], vals[:n], gen.column_fields(ids[:n])))
    assert np.max(np.abs(fm_only - want[:4].reshape(-1))) > 100 * cell["traffic"]["limits"]["score_gap"]
    assert serve.planted(cell, seed, "control")["score_gap"] > 3 * cell["traffic"]["limits"]["score_gap"]


def test_the_serve_window_serves_a_dense_model(dense_bench, tmp_path):
    cell = cells.load_cell("deepfm_toy.serve_steady", dense_bench)
    r = serve.run(cell, 5, 1.0, False, time.time(), require_chip=False, workroot=str(tmp_path))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] == 50
