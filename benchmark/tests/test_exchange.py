"""``harness/exchange.py``: the necessary bytes of a row-sharded step's
exchange from a literal id set, the roofline against a recorded head of a
trace (``[op, tf_op, start, seconds]`` as ``scopes.dump_ops`` keeps them, the
names as a TPU v5 lite gave them in PR 35), and what a program without the
scope or the counter gives: nothing."""

import numpy as np
import pytest

from harness import exchange, gen, scopes

MESH = {"data": 1, "row": 4}
# Two batches of 8 rows x 3 ids over a table of 4 shards x 10 rows; chip c scores rows [2c, 2c + 2) of a batch and owns ids [10c, 10c + 10).
IDS = np.array(
    [
        [0, 11, 21], [0, 11, 35],      # chip 0: distinct {0, 11, 21, 35}, owns 0: 3 remote
        [10, 12, 12], [19, 10, 10],    # chip 1: {10, 12, 19}, all its own: 0 remote
        [5, 6, 7], [5, 6, 7],          # chip 2: {5, 6, 7}, none its own: 3 remote
        [30, 31, 1], [32, 1, 1],       # chip 3: {1, 30, 31, 32}: 1 remote
        [0, 1, 2], [3, 4, 5],          # second batch, chip 0: 0 remote
        [0, 1, 2], [3, 4, 5],          # chip 1: 6 remote
        [20, 20, 20], [20, 20, 39],    # chip 2: {20, 39}: 1 remote
        [39, 38, 37], [9, 19, 29],     # chip 3: {9, 19, 29, 37, 38, 39}: 3 remote
    ],
    np.int32,
)
REMOTE_MEAN = (3 + 0 + 3 + 1 + 0 + 6 + 1 + 3) / 8
EX = "jit(step)/shard_map/fm.gather/fm.exchange/all_gather:"
EX_TAIL = "jit(step)/shard_map/fm.tail/fm.exchange/all_gather:"
RECORDED = {
    "/device:TPU:0": [
        ("all-gather.2 s32[32,3]", EX, 0.0, 0.001),
        ("fusion.1 f32[32,3,5]", "jit(step)/shard_map/fm.gather/gather:", 0.001, 0.004),
        ("all-reduce.8 f32[32,3,5]", "jit(step)/shard_map/fm.gather/fm.exchange/reduce_scatter:", 0.005, 0.002),
        ("all-gather.14 f32[32,5]", EX_TAIL, 0.010, 0.003),
    ],
    "/device:TPU:1": [
        ("all-gather.2 s32[32,3]", EX, 0.0, 0.002),
        ("all-reduce.8 f32[32,3,5]", "jit(step)/shard_map/fm.gather/fm.exchange/reduce_scatter:", 0.005, 0.003),
        ("all-gather.14 f32[32,5]", EX_TAIL, 0.010, 0.003),
    ],
}
PROFILE = {"kind": "profile", "program": "train_step", "mesh": MESH, "shard_rows": 10, "row_dim": 5, "examples": 8}


def test_necessary_bytes_count_each_distinct_remote_id_once_each_way():
    assert exchange.necessary_bytes(IDS, 8, MESH, 10, 5) == REMOTE_MEAN * 5 * 4 * 2
    # One row shard owns everything: nothing has to cross.
    assert exchange.necessary_bytes(IDS, 8, {"data": 4, "row": 1}, 40, 5) == 0.0
    assert exchange.necessary_bytes(IDS[:0], 8, MESH, 10, 5) == 0.0


def _ctx(tmp_path, **over):
    work = tmp_path / "work"
    (work / "trace").mkdir(parents=True, exist_ok=True)
    n = IDS.shape[0]
    gen.write_fmb(str(work / "train.fmb"), np.zeros(n, np.float32), IDS, np.ones(IDS.shape, np.float32), 40)
    ctx = {"records": [PROFILE], "trace": {"busy_s": 1.0}, "trace_dir": str(work / "trace"), "scoped_ops": RECORDED,
           "n_steps": 2, "device_kind": "TPU v5 lite", "steps": (4, 8)}
    ctx.update(over)
    return ctx


def test_the_written_file_gives_its_ids_back(tmp_path):
    ctx = _ctx(tmp_path)
    np.testing.assert_array_equal(exchange.fmb_ids(str(tmp_path / "work" / "train.fmb")), IDS)
    assert ctx["n_steps"] == 2


def test_the_roofline_is_the_hand_worked_share(tmp_path):
    # Under fm.exchange: plane 0 0.001 + 0.002 + 0.003, plane 1 0.002 + 0.003 + 0.003: the mean 0.007 s over 2 steps.
    assert scopes.scope_seconds(RECORDED, "fm.exchange") == pytest.approx(0.007)
    least = REMOTE_MEAN * 40 / 200e9
    assert exchange.roofline({}, _ctx(tmp_path)) == pytest.approx(100 * least * 2 / 0.007, rel=1e-9)
    assert 0 < exchange.roofline({}, _ctx(tmp_path)) < 100


@pytest.mark.parametrize(
    "over",
    [
        {"scoped_ops": {p: [e for e in ev if "fm.exchange" not in e[1]] for p, ev in RECORDED.items()}},  # a trace with no such op
        {"scoped_ops": {p: [(n, sc, s, 0.0) for n, sc, s, _ in ev] for p, ev in RECORDED.items()}},  # its ops took no time
        {"scoped_ops": {}},
        {"records": []},  # a program that does not say its mesh (before PR 35)
        {"n_steps": 0},
        {"trace": None},
        {"device_kind": "TPU v9"},  # no published interconnect in the table
        {"trace_dir": "/nonexistent/trace"},
    ],
    ids=["no_op", "zero_time", "no_trace_ops", "no_profile", "no_steps", "untraced", "unknown_chip", "no_file"],
)
def test_with_nothing_to_read_the_roofline_is_left_out(tmp_path, over):
    assert exchange.roofline({}, _ctx(tmp_path, **over)) is None


def test_mb_per_step_reads_the_programs_counter_in_the_window(tmp_path):
    recs = [{"kind": "train", "step": s, "exchange_bytes_per_step": 552075288} for s in (4, 8, 12)]
    assert exchange.mb_per_step({}, {"records": recs, "steps": (4, 12)}) == pytest.approx(552.075288)
    assert exchange.mb_per_step({}, {"records": [{"kind": "train", "step": 8, "loss": 0.5}], "steps": (4, 12)}) is None


def test_the_cells_metric_files_name_these_readers():
    from harness import cells, readers

    named = {m["name"]: m for m in cells.load_metrics("dist_train", workload="fm16_criteo_row4.dist_train_fmb")}
    assert readers.reader(named["fm.exchange_roofline"]["reader"]) is exchange.roofline
    assert readers.reader(named["fm.exchange_mb_per_step"]["reader"]) is exchange.mb_per_step
    assert named["fm.exchange_ms"]["scope"] == "fm.exchange" and readers.reader(named["fm.exchange_ms"]["reader"]) is scopes.scope_ms
    assert not [m for m in cells.load_metrics("train") if m["name"].startswith(("fm.exchange", "collective."))]
