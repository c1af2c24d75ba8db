"""The cell ``deepfm10_criteo.train_fmb_dense`` at toy size on the CPU (2^14 rows,
batch 512, the perceptron at its shipped 390-400-400-400-1: ``conftest.toy_bench``),
its planted faults, its configuration's arithmetic, and the perceptron's work model
and roofline reader (``harness/dense.py``)."""

import json
import os
import re
import time

import pytest

from harness import cells, common, dense, readers, scopes, train
from harness.models import deepfm, fm2

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "deepfm10_criteo.train_fmb_dense"
NAMES = ("loss_gap", "grad1_norm_gap", "delta3_norm_gap", "dense_grad1_norm_gap", "dense_delta3_norm_gap")


def _shipped(kind, name):
    return json.load(open(os.path.join(cells.BENCH_DIR, kind, name + ".json")))


def test_the_configuration_is_the_papers_shapes_with_nothing_reduced():
    cfg = _shipped("configs", "deepfm10_criteo")
    assert cfg["reduced"] == [] and cfg["chips"] == 1 and cfg["harness_model"] == "deepfm" and len(cfg["source"]) <= 200
    assert all(cfg.get(k) for k in ("source", "precision", "assumed", "departures", "deployment"))
    g, t = cfg["ini"]["General"], cfg["ini"]["Train"]
    assert (g["model"], g["factor_num"], g["num_fields"], g["hidden_dims"], g["compute_dtype"]) == ("deepfm", 10, 39, "400 400 400", "bfloat16")
    assert (g["vocabulary_size"], t["batch_size"], t["max_nnz"]) == (1 << 26, 65536, 39)
    assert (cfg["factor_num"], cfg["fields"], cfg["row_dim"], cfg["hidden_dims"], cfg["vocabulary_size"], cfg["batch_size"]) == (10, 39, 11, [400, 400, 400], 1 << 26, 65536)
    # fm8_criteo's file with the model changed: every other key of the INI is that cell's
    fm8 = _shipped("configs", "fm8_criteo")["ini"]
    assert t == fm8["Train"]
    assert {k: v for k, v in g.items() if k in fm8["General"] and k not in ("model", "factor_num")} == {k: v for k, v in fm8["General"].items() if k not in ("model", "factor_num", "order")}
    model = cells.load_cell(CELL)["model"]
    assert isinstance(model, deepfm.Model) and (model.row_dim, model.k, model.fields, model.reads_fields) == (11, 10, 39, False)
    assert model.dims == (390, 400, 400, 400, 1) and (model.weights, model.dense_elements) == (476400, 477601) == (476400, cfg["dense_params"])


def test_the_files_bytes_are_the_tables_and_the_lane_major_layouts():
    cfg = _shipped("configs", "deepfm10_criteo")
    rows, width = cfg["vocabulary_size"], cfg["row_dim"]
    logical = 2 * rows * width * 4  # table and element accumulator
    sublanes = -(-width // 8) * 8  # a lane-major row takes whole 8-sublane tiles
    laid_out = 2 * rows * sublanes * 4
    assert (width * 4, sublanes) == (44, 16)
    assert f"{logical / 1e9:.2f} GB logical" in cfg["deployment"] and logical == 5905580032
    assert laid_out == 8 * 2**30 and "8.0 GiB as laid out" in cfg["deployment"] and "16 sublanes" in cfg["deployment"]
    assert laid_out / 16e9 > 0.25  # the driver's floor, met by the state alone
    assert 4 * 2 * cfg["dense_params"] == 3820808 and "3.8 MB" in cfg["deployment"]  # the leaves and their accumulators


def test_the_mix_is_train_fmb_under_five_limits():
    mix, plain = _shipped("traffic", "train_fmb_dense"), _shipped("traffic", "train_fmb")
    own = ("what", "limits", "limits_from")
    assert {k: v for k, v in mix.items() if k not in own} == {k: v for k, v in plain.items() if k not in own}
    assert tuple(mix["limits"]) == NAMES and all(0 < v < 1e-2 for v in mix["limits"].values())
    assert set(plain["limits"]) == set(NAMES[:3])  # why the mix is a file of its own: train_fmb states none for the dense two
    assert mix["limits_from"] and "PLACEHOLDER" not in mix["limits_from"]


@pytest.mark.parametrize("seed", [11, 3000004311])
def test_the_toy_cell_is_correct_and_its_planted_faults_are_not(toy_bench, tmp_path, seed):
    cell = cells.load_cell(CELL, toy_bench)
    r = train.run(cell, seed, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 4
    assert tuple(r["compared"]) == NAMES
    # bfloat16 operands against a float32 reference on the CPU: the table's numbers at rounding, the dense two
    # where a bfloat16 pass puts them, all under the chip's limits
    assert all(c["value"] < c["limit"] for c in r["compared"].values())
    limits = cell["traffic"]["limits"]
    for what, caught_by in (("control", NAMES[1:]), ("dense_frozen", NAMES[3:])):
        ok, numbers = common.decide(train.planted(cell, seed, what), limits)
        assert ok is False and max(numbers[k]["value"] / numbers[k]["limit"] for k in caught_by) > 3, what
    frozen = train.planted(cell, seed, "dense_frozen")
    assert (frozen["dense_grad1_norm_gap"], frozen["dense_delta3_norm_gap"]) == (1.0, 1.0) and frozen["grad1_norm_gap"] < 1e-7  # the first step's table gradient is the sound one


@pytest.mark.parametrize("lacking", [("dense_grad1_norm_gap",), ("dense_delta3_norm_gap",), train.DENSE_NUMBERS])
def test_the_cell_exits_with_a_sentence_without_a_dense_limit(toy_bench, tmp_path, lacking):
    path = os.path.join(toy_bench, "traffic", "train_fmb_dense.json")
    mix = json.load(open(path))
    for name in lacking:
        del mix["limits"][name]
    json.dump(mix, open(path, "w"))
    with pytest.raises(SystemExit, match="states no limit for " + " or ".join(lacking)):
        train.run(cells.load_cell(CELL, toy_bench), 11, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))


def test_the_perceptrons_work_is_six_flops_a_weight_a_row_and_the_feed_once_each_way():
    model = cells.load_cell(CELL)["model"]
    flops, hbm = dense.perceptron_work(65536, model.dims[0], model.weights, model.dense_elements)
    assert flops == 6 * 476400 * 65536 == 187328102400
    assert hbm == 2 * 65536 * 390 * 4 + 4 * 477601 * 4 == 212113936
    # the part of the model's own step work that is the perceptron's is the same count
    base = fm2.Model.step_flops(model, 65536, 39, 10**6)
    assert model.step_flops(65536, 39, 10**6) - base == flops + 6 * model.dense_elements
    least, bound = dense.peaks.least_seconds(flops, hbm, "TPU v5e")
    assert bound == "flops" and least == pytest.approx(0.951e-3, rel=1e-3)  # 0.95 ms at 197 TFLOP/s; the bytes 0.26


def _metric(name):
    m = _shipped("metrics", name)
    m["name"] = name
    return m


def _ops(*events):
    return {"/device:TPU:0": list(events)}


def test_the_roofline_reads_the_perceptron_and_its_update_together_and_nothing_is_none():
    ops = _ops(
        ("fusion.222", "jit(step)/jvp(deepfm.feed)/mul:", 0.0, 0.003),
        ("convolution_add_fusion", "jit(step)/jvp(deepfm.mlp)/dot_general:", 0.010, 0.0005),
        ("convolution_add_fusion.1", "jit(step)/transpose(jvp(deepfm.mlp))/dot_general:", 0.020, 0.0007),
        ("fusion.9", "jit(step)/deepfm.dense_update/sub:", 0.0205, 0.0005),  # overlaps the op before it by 0.2 ms: counted once
        ("fusion.36", "jit(step)/fm.gather/gather:", 1.0, 0.057),
        ("fusion.81", "jit(step)/jvp(fm.interaction)/mul:", 2.0, 1.0),
    )
    model = deepfm.Model.__new__(deepfm.Model)
    records = [{"kind": "profile", "program": "train_step", "step": 1, "examples": 65536, "dense_params": 477601}]
    ctx = {"trace": {"busy_s": 5.0}, "scoped_ops": ops, "n_steps": 1, "device_kind": "TPU v5e", "model": model, "records": records, "steps": (4, 8)}
    roof = _metric("deepfm.mlp_roofline")
    assert roof["scopes"] == ["deepfm.mlp", "deepfm.dense_update"] and roof["reader"] == "dense:roofline"
    assert readers.reader(roof["reader"]) is dense.roofline
    assert dense.roofline(roof, ctx) is None  # a model that states no perceptron: nothing to read
    model.__dict__.update(dims=(390, 400, 400, 400, 1), k=10, row_dim=11, fields=39, hidden=(400, 400, 400))
    under = 0.0005 + 0.0007 + 0.0003
    assert dense.seconds_under(ops, roof["scopes"]) == pytest.approx(under)
    assert dense.roofline(roof, ctx) == pytest.approx(100 * (187328102400 / 197e12) / under)
    assert scopes.scope_ms(_metric("deepfm.mlp_ms"), ctx) == pytest.approx(1.2)
    assert scopes.scope_ms(_metric("deepfm.feed_ms"), ctx) == pytest.approx(3.0)
    assert scopes.scope_ms(_metric("deepfm.dense_update_ms"), ctx) == pytest.approx(0.5)
    params = _metric("deepfm.dense_params")
    assert readers.reader(params["reader"])(params, ctx) == 477601
    # a program from before PR 43 names none of the three and writes no dense_params: every reader gives None
    old = dict(ctx, scoped_ops=_ops(("fusion.36", "jit(step)/fm.gather/gather:", 1.0, 0.057)), records=[dict(records[0], dense_params=None)])
    assert dense.roofline(roof, old) is None and readers.reader(params["reader"])(params, old) is None
    assert all(scopes.scope_ms(_metric(f"deepfm.{n}_ms"), old) is None for n in ("mlp", "feed", "dense_update"))
    assert dense.roofline(roof, dict(ctx, records=[])) is None  # no profile record: the rows a step are not known
    assert dense.roofline(roof, {"trace": None, "n_steps": 1, "trace_dir": "/nowhere", "model": model, "records": records}) is None
    # a model without dense leaves (another cell's) under the same files: None, not an error
    assert dense.roofline(roof, dict(ctx, model=fm2.Model.__new__(fm2.Model))) is None


def test_the_five_metric_files_name_the_cell_and_the_layer_and_benchmark_json_says_the_same():
    bench = json.load(open(os.path.join(cells.CHECKOUT, "BENCHMARK.json")))
    listed = {m["name"]: m for m in bench["per_layer"]}
    names = ["deepfm.mlp_ms", "deepfm.feed_ms", "deepfm.dense_update_ms", "deepfm.dense_params", "deepfm.mlp_roofline"]
    assert set(names) <= set(listed)
    for name in names:
        m = _metric(name)
        assert m["workloads"] == [CELL] and m["layer"] == "dense head (models/deepfm)" and m["kinds"] == ["train"]
        assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves", "workloads")} == {k: v for k, v in listed[name].items() if k != "name"}
    read = {m["name"] for m in cells.load_metrics("train", workload=CELL)}
    assert set(names) <= read
    # every metric this cell's traced line can hold lists the cell in BENCHMARK.json, and no other cell gained one of the five
    assert all(CELL in listed[n]["workloads"] for n in read)
    assert not set(names) & {m["name"] for m in cells.load_metrics("train", workload="fm8_criteo.train_fmb")}
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == "deepfm10_criteo"
    assert bench["configs"][-1]["source"] == _shipped("configs", "deepfm10_criteo")["source"] and bench["configs"][-1]["reduced"] == []


# --- the readers on the chip's own trace --------------------------------------
#
# ``recorded_deepfm_scopes.json``: the first 700 ``XLA Ops`` events of a traced window of this cell on a TPU v5 lite
# (my chip run, PR 43, seed 3000004350, the final tree's archive on an empty compile cache), as ``scopes.dump_ops``
# keeps them: [op and shape, tf_op, start, seconds].  A step is 512 events; the head holds one whole step and the
# first 188 events of the next.


@pytest.fixture(scope="module")
def recorded():
    d = json.load(open(os.path.join(HERE, "recorded_deepfm_scopes.json")))
    return {p: [tuple(e) for e in ev] for p, ev in d.items()}


def test_the_recorded_step_reads_the_three_scopes_and_the_roofline(recorded):
    ((plane, events),) = recorded.items()
    assert len(events) == 700 and [i for i, e in enumerate(events) if e[0] == events[0][0]] == [0, 512]
    step = {plane: events[:512]}
    assert 1e3 * sum(e[3] for e in events[:512]) == pytest.approx(150.369, rel=1e-4)  # the step's ops, end to end
    ms = lambda prefix: 1e3 * scopes.scope_seconds(step, prefix)
    assert ms("deepfm.mlp") == pytest.approx(2.5609, rel=1e-3)  # forward 1.054 and backward 1.507
    assert ms("deepfm.feed") == pytest.approx(3.3894, rel=1e-3)  # all of it the backward's: XLA fuses the forward away
    assert ms("deepfm.dense_update") == pytest.approx(0.0012, abs=2e-4)  # the biases' leftovers: a weight's update is fused into its gradient matmul
    assert (ms("fm.gather"), ms("fm.tail"), ms("fm.dedup")) == pytest.approx((56.443, 43.736, 25.690), rel=1e-3)
    assert scopes.scope_seconds(step, "deepfm.absent") is None
    # no op of the step stands under two of the cell's scopes
    names = ("deepfm.feed", "deepfm.mlp", "deepfm.dense_update", "fm.gather", "fm.tail", "fm.dedup", "fm.interaction", "fm.loss")
    assert max(sum(n in e[1] for n in names) for e in events) == 1
    # every op shaped like the perceptron's is under deepfm.mlp, but for 0.005 ms: the biases' updates under
    # deepfm.dense_update and the weights' copy-start / copy-done pairs, nanoseconds each, which XLA names nothing
    shaped = [e for e in events[:512] if re.search(r"\[(65536,400|400,400|390,400|400,1|400)\]", e[0])]
    assert 1e3 * sum(e[3] for e in shaped if "deepfm.mlp" in e[1]) > 1.2
    assert 1e3 * sum(e[3] for e in shaped if "deepfm.mlp" not in e[1]) < 0.01
    model = cells.load_cell(CELL)["model"]
    ctx = {"trace": {"busy_s": 1.0}, "scoped_ops": step, "n_steps": 1, "device_kind": "TPU v5 lite", "model": model, "steps": (4, 8),
           "records": [{"kind": "profile", "program": "train_step", "step": 1, "examples": 65536, "dense_params": 477601}]}
    roof = dense.roofline(_metric("deepfm.mlp_roofline"), ctx)
    assert roof == pytest.approx(100 * 0.95090 / 2.5621, rel=1e-3) and 30 < roof < 50  # 37.1% of the bfloat16 matrix peak
    assert scopes.scope_ms(_metric("deepfm.mlp_ms"), ctx) == pytest.approx(2.5609, rel=1e-3)
    table = scopes.by_scope(step)
    assert [r[0] for r in table[:4]] == ["fm.gather", "fm.tail", "fm.dedup", "(no scope)"]
    assert 1e3 * table[3][1] == pytest.approx(15.3, abs=0.3)  # PERF.md section 5 names them op by op
