"""Every configuration the benchmark ships names a harness model
(``benchmark/harness/models/<name>.py``) that loads and that agrees with the
program's own model on what a table row is: its width and whether the score
reads field ids.  A program PR that changes a row's layout meets the
benchmark's plain reference here, in the tests the driver runs, and not first
on the chip."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIGS = sorted(fn[: -len(".json")] for fn in os.listdir(os.path.join(BENCH, "configs")) if fn.endswith(".json"))


@pytest.fixture
def cells():
    sys.path.insert(0, BENCH)  # the harness is a script's package, not an installed one
    try:
        from harness import cells as module

        yield module
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("config", CONFIGS)
def test_the_named_harness_model_has_the_programs_row(config, cells, tmp_path):
    from fast_tffm_tpu.config import build_model, load_config

    cell = cells.load_cell(f"{config}.train_fmb")
    assert cell["config"]["name"] == config and cell["config"]["harness_model"]
    program = build_model(load_config(cells.write_ini(str(tmp_path / "cell.cfg"), cell["ini"])))
    assert cell["model"].row_dim == program.row_dim
    assert cell["model"].reads_fields == bool(getattr(program, "uses_fields", False))



@pytest.mark.parametrize("config", CONFIGS)
def test_the_harness_draws_an_input_for_every_shipped_configuration(config, cells):
    """``gen.rows_from_seed`` at the configuration's own vocabulary (2^27 rows
    at ``fm16_criteo_row4``, where the draw's product passes 32 bits): 64 rows
    are drawn, every id inside its field's range and so inside the table."""
    import json

    import numpy as np
    from harness import gen

    cfg = json.load(open(os.path.join(BENCH, "configs", config + ".json")))
    fields, vocab = int(cfg["fields"]), int(cfg["vocabulary_size"])
    assert vocab == int(cfg["ini"]["General"]["vocabulary_size"]) and fields == int(cfg["ini"]["Train"]["max_nnz"])
    labels, ids, vals = gen.rows_from_seed(3000003599, 64, fields, vocab)
    assert ids.shape == vals.shape == (64, fields) and labels.shape == (64,) and ids.dtype == np.int32
    bounds = np.linspace(0, vocab, fields + 1).astype(np.int64)
    assert (ids >= bounds[:-1]).all() and (ids < bounds[1:]).all()
    assert np.isfinite(vals).all() and set(np.unique(labels)) <= {0.0, 1.0}


class _NoExchange:
    """``lax`` as parallel/embedding sees it, its gather over both mesh axes
    handing each chip its own part and nothing of its peers': the update's
    exchange of unique ids and summed gradients left out."""

    def __getattr__(self, name):
        from jax import lax

        return getattr(lax, name)

    def all_gather(self, x, axis_name, **kw):
        import math

        import jax.numpy as jnp
        from jax import lax

        if not isinstance(axis_name, tuple):  # the lookup's exchange of ids stays
            return lax.all_gather(x, axis_name, **kw)
        peers = math.prod(lax.axis_size(a) for a in axis_name) - 1
        return jnp.concatenate([x] + [jnp.zeros_like(x)] * peers)


@pytest.mark.parametrize("exchange", ["sound", "left_out"])
def test_three_steps_of_dist_train_follow_the_plain_reference(exchange, cells, tmp_path, monkeypatch):
    """Steps 1-3 of ``training.dist_train`` on a {data: 1, row: 4} mesh of
    virtual devices against ``harness/models/fm2`` + ``reference.train_steps``
    on rows drawn from a seed, at a toy size of ``fm16_criteo_row4``: the three
    gaps are under the mix's limits; with the gradients' exchange between the
    shards left out they are over them."""
    import json
    import shutil
    import time

    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    from harness import train

    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "metrics"), bench / "metrics")
    for d in ("configs", "traffic"):
        (bench / d).mkdir()
    cfg = json.load(open(os.path.join(BENCH, "configs", "fm16_criteo_row4.json")))
    cfg["ini"]["General"]["vocabulary_size"] = 1 << 14
    cfg["ini"]["Train"].update(batch_size=512, thread_num=2)
    json.dump(cfg, open(bench / "configs" / "fm16_criteo_row4.json", "w"))
    mix = json.load(open(os.path.join(BENCH, "traffic", "dist_train_fmb.json")))
    mix["file_batches"] = 8
    json.dump(mix, open(bench / "traffic" / "dist_train_fmb.json", "w"))
    if exchange == "left_out":
        from fast_tffm_tpu.parallel import embedding

        monkeypatch.setattr(embedding, "lax", _NoExchange())
    cell = cells.load_cell("fm16_criteo_row4.dist_train_fmb", str(bench))
    r = train.run(cell, 3000003511, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))
    assert r["device"]["count"] == 4 and set(r["compared"]) == {"loss_gap", "grad1_norm_gap", "delta3_norm_gap"}
    over = {k: c["value"] > c["limit"] for k, c in r["compared"].items()}
    if exchange == "sound":
        assert r["correct"] is True and r["failed"] == 0 and not any(over.values())
    else:
        assert r["correct"] is False and over["grad1_norm_gap"] and over["delta3_norm_gap"]
        assert r["compared"]["grad1_norm_gap"]["value"] > 10 * r["compared"]["grad1_norm_gap"]["limit"]


def _toy_fm3(cells, tmp_path):
    """``fm3_k30_kdd12.train_fmb_order3`` with every width as shipped (order 3,
    k = 30, 11 ids a row) and the scale a test can run: 2^14 rows, batch 512."""
    cell = _toy(cells, tmp_path, "fm3_k30_kdd12", "train_fmb_order3")
    cfg = cell["config"]
    assert (cfg["order"], cfg["factor_num"], cfg["fields"], cfg["row_dim"], cfg["reduced"]) == (3, 30, 11, 31, ["vocabulary_size"])
    return cell


@pytest.mark.parametrize("program", ["scan", "pallas_anova", "third_order_left_out"])
def test_three_steps_of_train_at_order_3_follow_the_plain_reference(program, cells, tmp_path, monkeypatch):
    """Steps 1-3 of ``training.train`` (order 3, k = 30, 11 ids a row) against
    ``harness/models/hofm`` + ``reference.train_steps`` on rows drawn from a
    seed: each loss, the first gradient's norm and the three-step change are
    under the mix's limits in both forms of the program's interaction (the
    ``lax.scan`` and, interpreted, the kernel); a program that computes the
    order-2 score where the configuration says order 3 is over them."""
    import time

    from fast_tffm_tpu.ops import fm
    from harness import train

    form = "order2" if program == "third_order_left_out" else program
    monkeypatch.setattr(fm, "interaction_form", lambda order, use_pallas=None, backend=None: form)
    cell = _toy_fm3(cells, tmp_path)
    r = train.run(cell, 3000003711, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))
    assert set(r["compared"]) == {"loss_gap", "grad1_norm_gap", "delta3_norm_gap"}
    over = {k: c["value"] / c["limit"] for k, c in r["compared"].items()}
    if program == "third_order_left_out":
        assert r["correct"] is False and max(over.values()) > 5
    else:
        assert r["correct"] is True and r["failed"] == 0 and max(over.values()) < 0.1


def test_predict_at_order_3_scores_as_the_plain_reference(cells, tmp_path):
    """``predict``'s scores on a libsvm file (order 3, k = 30, 11 ids a row)
    equal ``reference.score_rows`` with ``hofm.Model.score`` over the saved
    table's rows."""
    import numpy as np
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.prediction import load_scoring_state, predict
    from fast_tffm_tpu.training import train as program_train
    from harness import gen, reference

    cell = _toy_fm3(cells, tmp_path)
    vocab, n = 1 << 14, 11
    labels, ids, vals = gen.rows_from_seed(3000003712, 1024, n, vocab)
    data = tmp_path / "rows.libsvm"
    data.write_text("".join(
        f"{int(y)} " + " ".join(f"{i}:{v:.6f}" for i, v in zip(r, x)) + "\n" for y, r, x in zip(labels, ids, vals)
    ))
    ini = {s: dict(kv) for s, kv in cell["ini"].items()}
    ini["General"]["model_file"] = str(tmp_path / "fm3.npz")
    ini["Train"].update(train_files=str(data), epoch_num=1, save_every_epochs=1)
    ini["Predict"] = {"predict_files": str(data), "score_path": str(tmp_path / "scores.txt")}
    cfg = load_config(cells.write_ini(str(tmp_path / "fm3.cfg"), ini))
    program_train(cfg, log=lambda *_: None)  # two steps move the factors off their draw
    predict(cfg, log=lambda *_: None)
    got = np.loadtxt(cfg.score_path)
    _, state = load_scoring_state(cfg, log=lambda *_: None)
    u = np.unique(ids)
    shown = np.float32([[float(f"{v:.6f}") for v in row] for row in vals])  # the values as the file holds them
    want = np.asarray(reference.score_rows(cell["model"].score, np.asarray(state.table)[u], np.searchsorted(u, ids), shown, np.zeros_like(ids)))
    assert got.shape == want.shape == (1024,) and np.ptp(want) > 1e-3
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


# --- parameters outside the table: the dense leaves of a shipped configuration ---
#
# The benchmark's own tests hold these too (benchmark/tests/test_dense_seam.py,
# test_models.py), where the driver's tier-1 command does not run them: a
# program PR that renames, reshapes or redraws a dense leaf meets the plain
# reference here.


def _toy(cells, tmp_path, config, mix):
    """The shipped ``config`` and ``mix`` with every width as shipped and the
    scale a test can run: 2^14 rows, batch 512, a file of 8 batches."""
    import json
    import shutil

    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "metrics"), bench / "metrics")
    for d in ("configs", "traffic"):
        (bench / d).mkdir()
    cfg = json.load(open(os.path.join(BENCH, "configs", config + ".json")))
    cfg["ini"]["General"]["vocabulary_size"] = 1 << 14
    cfg["ini"]["Train"].update(batch_size=512, thread_num=2)
    json.dump(cfg, open(bench / "configs" / (config + ".json"), "w"))
    traffic = json.load(open(os.path.join(BENCH, "traffic", mix + ".json")))
    traffic["file_batches"] = 8
    json.dump(traffic, open(bench / "traffic" / (mix + ".json"), "w"))
    return cells.load_cell(f"{config}.{mix}", str(bench))


@pytest.mark.parametrize("config", CONFIGS)
def test_a_shipped_configurations_dense_leaves_are_the_programs(config, cells, tmp_path):
    """Names, shapes and values of the harness model's ``init_dense()``
    against ``trainer.init_state(...).dense`` at a toy vocabulary: none on
    either side for every configuration but ``deepfm10_criteo``, whose
    perceptron is 390-400-400-400-1."""
    import jax
    import numpy as np
    from fast_tffm_tpu.config import build_model, load_config
    from fast_tffm_tpu.trainer import init_state
    from harness.models import dense_leaves

    cell = _toy(cells, tmp_path, config, "train_fmb")
    program = build_model(load_config(cells.write_ini(str(tmp_path / "cell.cfg"), cell["ini"])))
    state = init_state(program, jax.random.key(0), 0.1)
    ours = dense_leaves(cell["model"])
    assert sorted(ours) == sorted(state.dense)
    if config != "deepfm10_criteo":
        assert ours == {} and not hasattr(cell["model"], "init_dense")
        return
    shapes = {"w0": (390, 400), "b0": (400,), "w1": (400, 400), "b1": (400,), "w2": (400, 400), "b2": (400,), "w3": (400, 1), "b3": (1,)}
    assert {k: v.shape for k, v in ours.items()} == shapes
    for name, leaf in state.dense.items():
        assert ours[name].dtype == leaf.dtype and np.array_equal(np.asarray(ours[name]), np.asarray(leaf)), name
        assert np.all(np.asarray(state.dense_opt.accum[name]) == np.float32(0.1))
    m = cell["model"]
    assert (m.weights, m.dense_elements, m.dims) == (476400, 477601, (390, 400, 400, 400, 1))


def _deepfm_ini(hidden, dtype, vocab=1 << 12, fields=39, k=10, batch=64):
    return {
        "General": {"model": "deepfm", "factor_num": k, "num_fields": fields, "hidden_dims": hidden, "compute_dtype": dtype,
                    "vocabulary_size": vocab, "hash_feature_id": "false"},
        "Train": {"batch_size": batch, "max_nnz": fields, "learning_rate": 0.05, "factor_lambda": 1e-7, "bias_lambda": 1e-7,
                  "init_accumulator_value": 0.1},
    }


@pytest.mark.parametrize("hidden,dtype", [("16 16 16", "float32"), ("16 16 16", "bfloat16"), ("24, 8", "float32"), ("24, 8", "bfloat16")])
def test_the_deepfm_module_is_the_programs_model(cells, tmp_path, hidden, dtype):
    """``harness/models/deepfm.py`` under a toy INI: row width, whether the
    score reads field ids, and names, shapes and values of ``init_dense()``
    against the program's ``init_state``, exactly; under float32 the two
    scores of random rows agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fast_tffm_tpu.config import build_model, load_config
    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.trainer import init_state
    from harness.models import deepfm

    ini = _deepfm_ini(hidden, dtype)
    model = deepfm.Model(ini)
    program = build_model(load_config(cells.write_ini(str(tmp_path / "toy.cfg"), ini)))
    assert model.row_dim == program.row_dim == 11 and program.compute_dtype == dtype
    assert model.reads_fields == bool(getattr(program, "uses_fields", False)) is False
    state = init_state(program, jax.random.key(0))
    ours = model.init_dense()
    widths = [int(x) for x in hidden.replace(",", " ").split()]
    assert sorted(ours) == sorted(state.dense) and len(ours) == 2 * (len(widths) + 1)
    assert [ours[f"w{i}"].shape for i in range(len(widths) + 1)] == list(zip([390, *widths], [*widths, 1]))
    for name, leaf in state.dense.items():
        assert ours[name].shape == leaf.shape and ours[name].dtype == leaf.dtype, name
        assert np.array_equal(np.asarray(ours[name]), np.asarray(leaf)), name
    assert np.array_equal(np.asarray(state.table[:64]), np.asarray(model.init_rows(np.arange(64))))
    if dtype == "float32":
        rng = np.random.default_rng(1)
        rows = jnp.asarray(rng.uniform(-0.3, 0.3, (8, 39, 11)), jnp.float32)
        vals = rng.uniform(0.05, 1.5, (8, 39)).astype(np.float32)
        batch = Batch(labels=jnp.zeros(8), ids=jnp.zeros((8, 39), jnp.int32), vals=jnp.asarray(vals),
                      fields=jnp.zeros((8, 39), jnp.int32), weights=jnp.ones(8))
        want = np.asarray(program.score(rows, state.dense, batch))
        assert np.max(np.abs(np.asarray(model.score(rows, vals, None, ours)) - want)) < 1e-5


@pytest.mark.parametrize("layout", ["rows", "packed"])
@pytest.mark.parametrize("seed", [3000004301, 3000004302])
def test_three_deepfm_steps_and_predict_follow_the_plain_reference(layout, seed, cells, tmp_path):
    """The program's DeepFM step in both step bodies (``train_step_body`` on
    the rows layout, ``packed_train_step_body`` on the lane-packed one) and
    its predict step against ``reference.train_steps`` / ``score_rows`` with
    ``harness/models/deepfm`` from SEEDED RANDOM dense leaves (biases too, so
    no ReLU sits on a tie): three steps, all five numbers of ``train.compare``
    and the served scores under 1e-5 on the CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from fast_tffm_tpu.config import build_model, load_config
    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.ops.packed_table import unpack_accum_any, unpack_table
    from fast_tffm_tpu.trainer import (
        init_packed_state, init_state, make_packed_predict_step, make_packed_train_step, make_predict_step, make_train_step,
    )
    from harness import gen, reference, train
    from harness.models import deepfm

    vocab, n, k, b = 1 << 12, 6, 4, 128
    ini = _deepfm_ini("16 8", "float32", vocab=vocab, fields=n, k=k, batch=b)
    if layout == "packed":
        ini["Train"]["table_layout"] = "packed"
    cfg = load_config(cells.write_ini(str(tmp_path / "toy.cfg"), ini))
    program, model = build_model(cfg), deepfm.Model(ini)
    rng = np.random.default_rng(seed)
    leaves = {name: rng.normal(0, 0.3, a.shape).astype(np.float32) for name, a in model.init_dense().items()}
    model.init_dense = lambda: dict(leaves)  # the reference starts from the same leaves
    labels, ids, vals = gen.rows_from_seed(seed, 3 * b, n, vocab)
    first, v3, y3 = ids.reshape(3, b, n), vals.reshape(3, b, n), labels.reshape(3, b)
    fields = gen.column_fields(first)
    u, u1 = np.unique(first), np.unique(first[0])

    packed = layout == "packed"
    init, make_step, make_predict = (
        (init_packed_state, make_packed_train_step, make_packed_predict_step) if packed else (init_state, make_train_step, make_predict_step)
    )
    state = init(program, jax.random.key(0), cfg.init_accumulator_value, cfg.adagrad_accumulator)
    state = state._replace(dense={name: jnp.asarray(a) for name, a in leaves.items()})
    step = make_step(program, cfg.learning_rate)
    d = program.row_dim
    table = lambda s: np.asarray(unpack_table(s.table, vocab, d) if packed else s.table)
    accum = lambda s: np.asarray(unpack_accum_any(s.table_opt.accum, vocab, d) if packed else s.table_opt.accum)
    host = lambda tree: {name: np.asarray(a) for name, a in tree.items()}
    got = {"losses": []}
    for i in range(3):
        batch = Batch(labels=jnp.asarray(y3[i]), ids=jnp.asarray(first[i]), vals=jnp.asarray(v3[i]),
                      fields=jnp.zeros((b, 0), jnp.int32), weights=jnp.ones((b,), jnp.float32))
        state, loss = step(state, batch)
        got["losses"].append(float(loss))
        if i == 0:
            got.update(t1=table(state)[u1], a1=accum(state)[u1], d1=host(state.dense), da1=host(state.dense_opt.accum))
    got.update(t3=table(state)[u], d3=host(state.dense))
    h = train._hyper(ini)
    ref = train.followed(h, model, first, v3, fields, y3, u, u1)
    numbers = train.compare(got, ref, h["lr"])
    assert set(numbers) == {"loss_gap", "grad1_norm_gap", "delta3_norm_gap", *train.DENSE_NUMBERS}
    assert max(numbers.values()) < 1e-5, numbers

    # the predict step scores the trained state as the reference scores the reference's
    scores = np.asarray(make_predict(program, fused=False)(state, batch) if packed else make_predict(program)(state, batch))
    want = np.asarray(reference.score_rows(model.score, table(state)[u], np.searchsorted(u, first[2]), v3[2], fields[2], dense=host(state.dense)))
    assert scores.shape == want.shape == (b,) and np.ptp(want) > 1e-3
    assert np.max(np.abs(scores - want)) < 1e-5

    # and a step that drops the dense gradient is caught by the two dense numbers
    frozen = dict(got, d1=leaves, d3=leaves, da1={name: np.full_like(a, h["accum0"]) for name, a in leaves.items()})
    bad = train.compare(frozen, ref, h["lr"])
    assert bad["dense_grad1_norm_gap"] == 1.0 and bad["dense_delta3_norm_gap"] == 1.0
