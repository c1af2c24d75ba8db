"""The train check reads LOGICAL rows through a ``StepView``, whatever the
run's table layout and tier: the resident ``[V, D]`` table, the packed
``[V/P, 128]`` tiles (the accumulator packed alike or fused into them), the
tiered store's device hot tier over its host cold store, and the row-sharded
table of ``dist_train``.  Toy sizes on the CPU."""

import sys
import time

import numpy as np
import pytest

from harness import cells, common, gen, train

FM8 = "fm8_criteo"
PACKED = {"General": {"table_layout": "packed"}}
FUSED = {"General": {"table_layout": "packed"}, "Train": {"adagrad_accumulator": "fused"}}
HOT_ROWS = 1024  # of the toy's 2^14: the first three batches touch hot and cold rows alike
TIERED = {"General": {"checkpoint_format": "npz"}, "ParamStore": {"enabled": "true", "hot_rows": HOT_ROWS}}
LAZY = dict(TIERED, ParamStore=dict(TIERED["ParamStore"], materialize="never"))  # the cold store of a vocabulary past 2^21 rows


class _Stop(Exception):
    pass


def _cell(bench, ini, workload=FM8 + ".train_fmb"):
    cell = cells.load_cell(workload, bench)
    for section, kv in ini.items():
        cell["ini"].setdefault(section, {}).update(kv)
    return cell


def _configured(cell, tmp_path, name, seed=5, **train_keys):
    """The cell's INI with ``train_keys`` under ``[Train]``, over an FMB file of
    8 batches from ``seed``: (Config, ids of the file)."""
    cell = dict(cell, ini={s: dict(kv) for s, kv in cell["ini"].items()})
    cell["ini"]["Train"].update(train_keys)
    batch, nnz = int(cell["ini"]["Train"]["batch_size"]), int(cell["ini"]["Train"]["max_nnz"])
    vocab = int(cell["ini"]["General"]["vocabulary_size"])
    labels, ids, vals = gen.rows_from_seed(seed, 8 * batch, nnz, vocab)
    _work, cfg = common.configured(cell, name, str(tmp_path), train_file="train.fmb")
    gen.write_fmb(cfg.train_files[0], labels, ids, vals, vocab)
    return cfg, ids


def _entry(cell):
    from fast_tffm_tpu import training

    return training.dist_train if cell["kind"] == "dist_train" else training.train


def _at_step(cell, cfg, step, look):
    """Run the program until ``step`` and return ``look(view, frame)`` there."""
    read = train.row_reader(cfg.table_layout, cell["model"].row_dim)
    out = {}

    def hook(step_num):
        if step_num == step:
            frame = sys._getframe(1).f_locals
            out["got"] = look(train._loop_view(frame, step_num, read), frame)
            raise _Stop

    with pytest.raises(_Stop):
        _entry(cell)(cfg, log=lambda *a: None, step_hook=hook)
    return out["got"]


def _every_row(cell, cfg, step, truth):
    """(the view's rows of every logical id, ``truth(state)``) after ``step``."""
    vocab = int(cell["ini"]["General"]["vocabulary_size"])
    every = np.arange(vocab, dtype=np.int32)

    def look(view, frame):
        got = tuple(np.asarray(a) for a in view.rows(every))
        return got, tuple(np.asarray(a) for a in truth(view.state))

    return _at_step(cell, cfg, step, look)


def test_the_rows_layout_reads_the_table_itself(toy_bench, tmp_path):
    cell = _cell(toy_bench, {})
    cfg, _ = _configured(cell, tmp_path, "rows")
    got, want = _every_row(cell, cfg, 2, lambda st: (st.table, st.table_opt.accum))
    assert all(np.array_equal(g, w) for g, w in zip(got, want))


@pytest.mark.parametrize("ini,unpack", [
    (PACKED, lambda st, v, d: (_pt().unpack_table(st.table, v, d), _pt().unpack_accum_any(st.table_opt.accum, v, d))),
    (FUSED, lambda st, v, d: _pt().unpack_fused(st.table, v, d)),
], ids=["packed", "fused"])
def test_the_packed_layouts_read_the_logical_rows(toy_bench, tmp_path, ini, unpack):
    cell = _cell(toy_bench, ini)
    cfg, _ = _configured(cell, tmp_path, "packed")
    vocab, d = cfg.vocabulary_size, cell["model"].row_dim
    got, want = _every_row(cell, cfg, 2, lambda st: unpack(st, vocab, d))
    assert got[0].shape == (vocab, d) and got[1].shape == want[1].shape
    assert all(np.array_equal(g, w) for g, w in zip(got, want))


def _pt():
    from fast_tffm_tpu.ops import packed_table

    return packed_table


def test_the_tiered_store_reads_hot_pending_staged_and_cold_rows_as_the_resident_table_holds_them(toy_bench, tmp_path):
    """The tiered program is bit-identical to the resident one where the cold
    store starts from the same draw, so the resident table after step 2 is
    the truth for every id: hot (the state's slots), missed by step 1 (the
    pending overlay), missed by step 2 (still in its staging slots) and never
    touched (the cold store)."""
    resident, tiered = _cell(toy_bench, {}), _cell(toy_bench, TIERED)
    cfg_r, ids = _configured(resident, tmp_path / "r", "resident")
    cfg_t, _ = _configured(tiered, tmp_path / "t", "tiered")
    _, want = _every_row(resident, cfg_r, 2, lambda st: (st.table, st.table_opt.accum))
    every = np.arange(cfg_t.vocabulary_size, dtype=np.int32)

    def look(view, frame):
        staged = frame["paramstore"]._last_staged  # step 2's misses, before the view fetches them
        return frame["paramstore"].residency.hot_ids, staged, tuple(np.asarray(a) for a in view.rows(every))

    hot, staged, got = _at_step(tiered, cfg_t, 2, look)
    step1 = np.setdiff1d(np.unique(ids[: cfg_t.batch_size]), hot)
    assert hot.size == HOT_ROWS and staged.size and np.setdiff1d(step1, staged).size
    assert np.setdiff1d(every, np.concatenate([hot, step1, staged])).size
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])


@pytest.mark.parametrize("ini", [PACKED, TIERED], ids=["packed", "tiered"])
def test_a_toy_run_through_the_check_is_correct(toy_bench, tmp_path, ini):
    r = train.run(_cell(toy_bench, ini), 11, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))
    assert r["correct"] is True and r["failed"] == 0, r["compared"]


def test_the_parents_read_by_slot_is_not_correct_on_the_tiered_store(toy_bench, tmp_path, monkeypatch):
    """What the check read before it read through the view, ``state.table[ids]``,
    is slot ``ids`` of the compact table, not the rows of those ids."""

    def by_slot(server, state, ids, accum, take):
        return take(state.table, ids), (take(state.table_opt.accum, ids) if accum else None)

    monkeypatch.setattr(train, "_tiered_rows", by_slot)
    r = train.run(_cell(toy_bench, TIERED), 11, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))
    assert r["correct"] is False


def _tiered_logical(cfg):
    """The whole logical (table, accumulator) of a finished tiered run: its
    cold store, then the last save's pending rows and hot tier over it."""
    from fast_tffm_tpu.paramstore import ColdStore

    z = np.load(cfg.model_file)
    store = ColdStore.open(cfg.paramstore_dir or cfg.model_file + ".store")
    t, a = store.read_rows(np.arange(cfg.vocabulary_size))
    cold = np.asarray(z["tier_cold_idx"], np.int64)
    t[cold], a[cold] = z["tier_cold_rows"], z["tier_cold_accum"]
    hot = np.asarray(z["tier_hot_ids"], np.int64)
    t[hot], a[hot] = z["table"], z["table_accum"]
    return t, a


def test_reading_rows_after_steps_one_and_three_changes_nothing_the_tiered_program_computes(toy_bench, tmp_path):
    tiered = _cell(toy_bench, TIERED)
    read = train.row_reader("rows", tiered["model"].row_dim)
    outcomes = []
    for reads in (True, False):
        cfg, ids = _configured(tiered, tmp_path / str(reads), "tiered", epoch_num=2, log_every=1, save_every_epochs=1)
        u = np.unique(ids[: 3 * cfg.batch_size])
        losses = []

        def hook(step_num):
            if reads and step_num in (1, 3):
                train._loop_view(sys._getframe(1).f_locals, step_num, read).rows(u)

        def log(msg):
            if str(msg).startswith("step "):
                losses.append(str(msg).split()[5])

        _entry(tiered)(cfg, log=log, step_hook=hook)
        outcomes.append((losses, _tiered_logical(cfg)))
    (l1, (t1, a1)), (l0, (t0, a0)) = outcomes
    assert len(l1) == 16 and l1 == l0
    assert np.array_equal(t1, t0) and np.array_equal(a1, a0)


DIST = "fm16_criteo_row4.dist_train_fmb"


def test_the_row_sharded_table_reads_its_global_rows(toy_bench, tmp_path):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("XLA_FLAGS was set without four host devices")
    cell = _cell(toy_bench, {}, DIST)
    cfg, _ = _configured(cell, tmp_path, "dist")
    got, want = _every_row(cell, cfg, 2, lambda st: (st.table, st.table_opt.accum))
    assert got[0].shape == (cfg.vocabulary_size, cell["model"].row_dim)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))


def test_the_hashed_modules_rows_are_the_lazy_cold_stores():
    from fast_tffm_tpu.paramstore import hashed_uniform_rows
    from harness.models import fm2_hashed

    ids = np.concatenate([np.arange(64), np.random.default_rng(0).integers(0, 2**31 - 1, 4096)])
    for d, r in ((9, 0.01), (17, 0.05)):
        assert np.array_equal(fm2_hashed.hashed_rows(ids, d, 0, r), hashed_uniform_rows(ids, d, 0, r))


@pytest.mark.parametrize("module,correct", [("fm2_hashed", True), ("fm2", False)])
def test_a_toy_run_over_a_lazy_cold_store_is_correct_under_its_own_initial_rows(toy_bench, tmp_path, module, correct):
    import importlib

    cell = _cell(toy_bench, LAZY)
    cell["model"] = importlib.import_module(f"harness.models.{module}").Model(cell["ini"])
    r = train.run(cell, 11, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))
    assert r["correct"] is correct, r["compared"]
