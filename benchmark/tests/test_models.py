"""The seam between the windows and a model: ``fm2`` reads what the harness
read before the move, a second module enters through new files alone, and a
configuration or a mix that the seam cannot serve says so."""

import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest

from harness import cells, gen, serve, train
from harness.models import ffm, fm2

BENCH = cells.BENCH_DIR

# Recorded from the parent commit (66a3c31, PR 27) before anything moved: the
# toy ``fm8_criteo.train_fmb`` (2^14 rows, batch 512, 8 file batches), the
# first batch's work, 1,000 scored rows, the reference's three losses.
PINS = {
    11: {"fmb_sha256": "afab8b159e3d64e58808309d7c419a744000eb30f8adea5e139984bc2fc1500d",
         "step_bytes": (4323264, 9500), "step_flops": 1711080, "score_bytes": 1720000,
         "losses": [0.6930736303329468, 0.6923046708106995, 0.6913090944290161],
         "t3_sum": 589.9286766754922, "score_sum": 256.0067788362503},
    3000000019: {"fmb_sha256": "cca340c7b153db0b01e2cfd0df29197967e12eef3215a67760eee0742a443bd7",
                 "step_bytes": (4322688, 9496), "step_flops": 1710864, "score_bytes": 1720000,
                 "losses": [0.6931697130203247, 0.6925042271614075, 0.6916831731796265],
                 "t3_sum": 589.6474520888949, "score_sum": 255.9951153099537},
}


@pytest.mark.parametrize("seed", sorted(PINS))
def test_fm2_reads_what_the_harness_read_before_the_move(toy_bench, tmp_path, seed):
    from harness import reference

    pin = PINS[seed]
    cell = cells.load_cell("fm8_criteo.train_fmb", toy_bench)
    model, h = cell["model"], train._hyper(cell["ini"])
    assert isinstance(model, fm2.Model) and model.row_dim == 9 and not model.reads_fields
    labels, ids, vals = gen.rows_from_seed(seed, 8 * 512, 39, 1 << 14, 2.5)
    path = str(tmp_path / "t.fmb")
    gen.write_fmb(path, labels, ids, vals, 1 << 14)
    assert hashlib.sha256(open(path, "rb").read()).hexdigest() == pin["fmb_sha256"]
    assert model.step_bytes(ids[:512]) == pin["step_bytes"]
    assert model.step_flops(512, 39, pin["step_bytes"][1]) == pin["step_flops"]
    assert model.score_bytes(1000, 39) == pin["score_bytes"]
    first = ids[:1536].reshape(3, 512, 39)
    u, u1 = np.unique(first), np.unique(first[0])
    fields = gen.column_fields(first)
    ref = train.followed(h, model, first, vals[:1536].reshape(first.shape), fields, labels[:1536].reshape(3, 512), u, u1)
    assert ref["losses"] == pin["losses"]
    assert float(np.abs(ref["t3"].astype(np.float64)).sum()) == pin["t3_sum"]
    scores = reference.score_rows(model.score, model.init_rows(u), np.searchsorted(u, first[0]), vals[:512], fields[0])
    assert float(np.asarray(scores).astype(np.float64).sum()) == pin["score_sum"]


@pytest.mark.parametrize("config", sorted(fn[:-5] for fn in os.listdir(os.path.join(BENCH, "configs"))))
def test_a_shipped_configurations_module_has_the_programs_row_width(config, tmp_path):
    """Where a program PR changes a row's layout, the harness's model meets it here."""
    from fast_tffm_tpu.config import build_model, load_config

    cell = cells.load_cell(f"{config}.train_fmb")
    program = build_model(load_config(cells.write_ini(str(tmp_path / "cell.cfg"), cell["ini"])))
    assert cell["model"].row_dim == program.row_dim
    assert cell["model"].reads_fields == bool(getattr(program, "uses_fields", False))
    # ... and its dense leaves, the same names and shapes on both sides (the program's by shape alone: the table is not drawn).
    import jax

    from fast_tffm_tpu.trainer import init_state
    from harness.models import dense_leaves

    state = jax.eval_shape(lambda: init_state(program, jax.random.key(0)))
    shapes = lambda tree: {name: tuple(leaf.shape) for name, leaf in tree.items()}
    assert shapes(dense_leaves(cell["model"])) == shapes(state.dense) == shapes(state.dense_opt.accum)


FFM_TOY = {
    "name": "ffm4_toy", "harness_model": "ffm", "chips": 1, "reduced": [],
    "source": "a toy for the harness's own tests: the field-aware model at 39 fields, k = 4",
    "ini": {
        "General": {"model": "ffm", "factor_num": 4, "num_fields": 39, "vocabulary_size": 1 << 14, "hash_feature_id": "false"},
        "Train": {"batch_size": 512, "max_nnz": 39, "learning_rate": 0.05, "factor_lambda": 1e-7, "bias_lambda": 1e-7,
                  "init_accumulator_value": 0.1, "thread_num": 2, "queue_size": 8},
    },
}


@pytest.fixture
def ffm_bench(tmp_path):
    """A benchmark directory made of new files alone: a configuration that
    names ``ffm``, the shipped mixes (the train file shortened) and metrics."""
    root = tmp_path / "ffm_bench"
    shutil.copytree(os.path.join(BENCH, "metrics"), root / "metrics")
    shutil.copytree(os.path.join(BENCH, "traffic"), root / "traffic")
    mix = json.load(open(root / "traffic" / "train_fmb.json"))
    mix["file_batches"] = 8
    json.dump(mix, open(root / "traffic" / "train_fmb.json", "w"))
    (root / "configs").mkdir()
    json.dump(FFM_TOY, open(root / "configs" / "ffm4_toy.json", "w"))
    return str(root)


def _run(bench, tmp_path, seed=11):
    cell = cells.load_cell("ffm4_toy.train_fmb", bench)
    assert isinstance(cell["model"], ffm.Model) and cell["model"].row_dim == 157
    return train.run(cell, seed, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))


def test_a_second_model_is_correct_through_new_files_alone(ffm_bench, tmp_path):
    r = _run(ffm_bench, tmp_path)
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["compared"]) == {"loss_gap", "grad1_norm_gap", "delta3_norm_gap"}


def test_a_file_written_with_every_field_zero_is_not_correct(ffm_bench, tmp_path, monkeypatch):
    real = gen.write_fmb
    monkeypatch.setattr(gen, "write_fmb", lambda *a: real(*a[:5]))
    r = _run(ffm_bench, tmp_path)
    assert r["correct"] is False
    assert r["compared"]["grad1_norm_gap"]["value"] > 10 * r["compared"]["grad1_norm_gap"]["limit"]


def test_the_order_2_reference_in_the_field_aware_models_place_is_not_correct(ffm_bench, tmp_path, monkeypatch):
    monkeypatch.setattr(ffm.Model, "score", fm2.Model.score)
    r = _run(ffm_bench, tmp_path)
    assert r["correct"] is False
    assert r["compared"]["grad1_norm_gap"]["value"] > 10 * r["compared"]["grad1_norm_gap"]["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_field_aware_models_bfloat16_control_fails(ffm_bench, seed):
    from harness import common

    cell = cells.load_cell("ffm4_toy.train_fmb", ffm_bench)
    ok, compared = common.decide(train.planted(cell, seed, "control"), cell["traffic"]["limits"])
    assert ok is False and any(c["value"] > 3 * c["limit"] for c in compared.values())


@pytest.mark.parametrize("seed", [0, 1])
def test_the_pair_sum_equals_the_programs_field_aware_score(seed):
    import jax
    import jax.numpy as jnp

    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.models.ffm import FFMModel

    rng = np.random.default_rng(seed)
    b, n, f, k = 64, 39, 39, 4
    model = ffm.Model(FFM_TOY["ini"])
    rows = jnp.asarray(rng.uniform(-0.3, 0.3, (b, n, model.row_dim)), jnp.float32)
    vals = rng.uniform(0.05, 1.5, (b, n)).astype(np.float32)
    vals[:, -3:] = 0.0  # padding is neutral on both sides
    fields = rng.integers(0, f, (b, n)).astype(np.int32)  # repeated fields too, not only column f = field f
    program = FFMModel(vocabulary_size=1 << 14, num_fields=f, factor_num=k)
    batch = Batch(labels=jnp.zeros(b), ids=jnp.zeros((b, n), jnp.int32), vals=jnp.asarray(vals),
                  fields=jnp.asarray(fields), weights=jnp.ones(b))
    want = np.asarray(program.score(rows, {}, batch))
    got = np.asarray(model.score(rows, jnp.asarray(vals), jnp.asarray(fields)))
    assert np.max(np.abs(got - want)) < 1e-5
    assert program.row_dim == model.row_dim
    table = program.init_table(jax.random.split(jax.random.key(0))[0])  # as ``trainer.init_state`` draws it
    assert np.array_equal(np.asarray(table[:64]), np.asarray(model.init_rows(np.arange(64))))


def test_the_field_aware_work_model_is_the_programs_at_its_width_and_the_fields():
    from fast_tffm_tpu.profiling import modeled_step_bytes

    ids = np.random.default_rng(4).integers(0, 5000, size=(256, 39))
    model = ffm.Model(FFM_TOY["ini"])
    total, uniq = modeled_step_bytes(ids, 157, 157)
    assert model.step_bytes(ids) == (total + 4 * ids.size, uniq)
    assert model.score_bytes(10, 39) == 10 * (39 * (12 + 157 * 4) + 4)
    assert model.step_flops(256, 39, uniq) == 256 * (741 * 33 + 156) + uniq * 157 * 6


def test_a_configuration_that_names_no_harness_model_or_a_missing_one_is_an_error(ffm_bench):
    for name, sentence in ((None, "names no harness_model"), ("no_such_model", "there is no harness/models/no_such_model.py")):
        c = dict(FFM_TOY, harness_model=name)
        json.dump(c, open(os.path.join(ffm_bench, "configs", "ffm4_toy.json"), "w"))
        with pytest.raises(SystemExit, match=sentence):
            cells.load_cell("ffm4_toy.train_fmb", ffm_bench)


def test_a_serve_mix_over_a_model_that_reads_fields_says_so(ffm_bench, tmp_path):
    cell = cells.load_cell("ffm4_toy.serve_steady", ffm_bench)
    with pytest.raises(SystemExit, match="carry none"):
        serve.run(cell, 1, 1.0, False, time.time(), require_chip=False, workroot=str(tmp_path))
    with pytest.raises(SystemExit, match="carry none"):
        serve.planted(cell, 1, "control")
