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
    import json
    import shutil

    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "metrics"), bench / "metrics")
    for d in ("configs", "traffic"):
        (bench / d).mkdir()
    cfg = json.load(open(os.path.join(BENCH, "configs", "fm3_k30_kdd12.json")))
    assert (cfg["order"], cfg["factor_num"], cfg["fields"], cfg["row_dim"], cfg["reduced"]) == (3, 30, 11, 31, ["vocabulary_size"])
    cfg["ini"]["General"]["vocabulary_size"] = 1 << 14
    cfg["ini"]["Train"].update(batch_size=512, thread_num=2)
    json.dump(cfg, open(bench / "configs" / "fm3_k30_kdd12.json", "w"))
    mix = json.load(open(os.path.join(BENCH, "traffic", "train_fmb_order3.json")))
    mix["file_batches"] = 8
    json.dump(mix, open(bench / "traffic" / "train_fmb_order3.json", "w"))
    return cells.load_cell("fm3_k30_kdd12.train_fmb_order3", str(bench))


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
