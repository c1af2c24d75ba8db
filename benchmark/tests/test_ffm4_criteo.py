"""The cell ``ffm4_criteo.train_fmb_fields`` at toy size on the CPU (2^14 rows, batch
512: ``conftest.toy_bench``), its three controls, the module its configuration
names, and the by-scope reader on a head of the cell's own trace."""

import json
import os
import time

import numpy as np
import pytest

from harness import cells, common, gen, scopes, train
from harness.models import ffm, ffm_f32, fm2

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ffm4_criteo.train_fmb_fields"
SEEDS = [11, 3000002901, 3000002902]


def _run(bench, tmp_path, seed, before=lambda cell: None):
    cell = cells.load_cell(CELL, bench)
    assert isinstance(cell["model"], ffm_f32.Model) and cell["model"].row_dim == 157 and cell["model"].reads_fields
    before(cell)
    return train.run(cell, seed, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_toy_cell_is_correct_and_its_three_controls_are_not(toy_bench, tmp_path, monkeypatch, seed):
    r = _run(toy_bench, tmp_path, seed)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 4
    assert set(r["compared"]) == {"loss_gap", "grad1_norm_gap", "delta3_norm_gap"}
    sound = {k: c["value"] for k, c in r["compared"].items()}
    assert max(sound.values()) < 1e-6  # float32 against float32: rounding, two orders under the limits

    cell = cells.load_cell(CELL, toy_bench)
    ok, control = common.decide(train.planted(cell, seed, "control"), cell["traffic"]["limits"])
    assert ok is False and all(c["value"] > 10 * sound[k] for k, c in control.items())

    real = gen.write_fmb
    # A file whose fields are all zero (at the cell's own size: ``test_the_mix_sees_field_ids_at_the_cells_size``).
    with monkeypatch.context() as mp:
        mp.setattr(gen, "write_fmb", lambda *a: real(*a[:5]))
        zero = _run(toy_bench, tmp_path, seed)
    assert zero["correct"] is False and zero["compared"]["grad1_norm_gap"]["value"] > 10 * 1e-4

    # the order-2 score in the field-aware reference's place (after the load: the module probes with its own)
    order2 = _run(toy_bench, tmp_path, seed, before=lambda cell: monkeypatch.setattr(ffm.Model, "score", fm2.Model.score))
    assert order2["correct"] is False and order2["compared"]["grad1_norm_gap"]["value"] > 10 * 1e-4


# What ``train.compare`` read on the chip at the cell's own size, B = 32,768 x 39 (my chip runs, PR 29; PERF.md §6):
# (loss_gap, grad1_norm_gap, delta3_norm_gap), the worst of 18 seeds for the sound program, each seed of a planted fault.
CHIP_READINGS = {
    "sound": [(0.0, 7.0e-9, 4.6e-9)],
    "zero_fields": [(3.2e-6, 2.2e-6, 2.7e-5), (2.7e-6, 8.1e-5, 5.9e-5)],
    "order2": [(1.3e-5, 0.0278, 0.0099), (2.2e-5, 0.0284, 0.0102)],
    "bfloat16": [(2.51e-3, 35.9, 14.7), (2.51e-3, 35.8, 14.6)],
}


def test_the_mix_sees_field_ids_at_the_cells_size():
    """``train_fmb``'s limits (1e-4 each) pass an FMB file whose field ids are all zero at full size: the check
    compares norms by leaf, and at B = 32,768 the field-blind L2 term carries the factors' gradient norm.  This
    cell's mix is ``train_fmb`` key for key with limits between the sound readings and that fault's."""
    mix = json.load(open(os.path.join(cells.BENCH_DIR, "traffic", "train_fmb_fields.json")))
    plain = json.load(open(os.path.join(cells.BENCH_DIR, "traffic", "train_fmb.json")))
    same = lambda m: {k: v for k, v in m.items() if k not in ("what", "limits", "limits_from")}
    assert same(mix) == same(plain) and set(mix["limits"]) == set(plain["limits"])
    names = ("loss_gap", "grad1_norm_gap", "delta3_norm_gap")
    decide = lambda reading, limits: common.decide(dict(zip(names, reading)), limits)[0]
    for what, readings in CHIP_READINGS.items():
        for reading in readings:
            assert decide(reading, mix["limits"]) is (what == "sound"), (what, reading)
    assert all(decide(r, plain["limits"]) for r in CHIP_READINGS["zero_fields"])  # why train_fmb's limits will not do
    for name, sound, fault in zip(names, CHIP_READINGS["sound"][0], map(min, zip(*CHIP_READINGS["zero_fields"]))):
        assert 10 * sound < mix["limits"][name] < plain["limits"][name], name  # room over the sound readings
        if name != "loss_gap":
            assert 5 * mix["limits"][name] < fault, name  # and under the fault's, by each norm


def test_the_compact_table_is_capped_at_the_vocabulary(toy_bench):
    model = cells.load_cell(CELL, toy_bench)["model"]
    u = np.arange(0, 1 << 14, 3)
    padded = train._pad(u, 3 * 512 * 39)  # 59,904 slots for a table of 16,384 rows
    rows = model.init_rows(padded)
    assert rows.shape == (1 << 14, 157)
    assert np.array_equal(np.asarray(rows[: u.size]), np.asarray(ffm.Model.init_rows(model, u)))
    assert model.init_rows(u[:100]).shape == (100, 157)  # fewer rows than the table has: as asked


def test_the_pair_sum_in_blocks_is_the_pair_sum(toy_bench):
    import jax
    import jax.numpy as jnp

    model = cells.load_cell(CELL, toy_bench)["model"]
    rng = np.random.default_rng(5)
    b, n = 2 * ffm_f32._BLOCK_ROWS, 39
    rows = jnp.asarray(rng.uniform(-0.3, 0.3, (b, n, 157)), jnp.float32)
    vals = jnp.asarray(rng.uniform(0.05, 1.5, (b, n)), jnp.float32)
    fields = jnp.asarray(rng.integers(0, 39, (b, n)), jnp.int32)
    whole = lambda r: jnp.sum(ffm.Model.score(model, r, vals, fields) ** 2)
    blocked = lambda r: jnp.sum(model.score(r, vals, fields) ** 2)
    sw, sb = ffm.Model.score(model, rows, vals, fields), model.score(rows, vals, fields)
    assert float(jnp.max(jnp.abs(sw - sb))) <= 1e-6 * float(jnp.max(jnp.abs(sw)))  # the same sum, another order of lanes
    gw, gb = jax.grad(whole)(rows), jax.grad(blocked)(rows)
    assert float(jnp.max(jnp.abs(gw - gb))) <= 1e-6 * float(jnp.max(jnp.abs(gw)))
    assert model.score(rows[:100], vals[:100], fields[:100]).shape == (100,)  # not a whole number of blocks: at once


def test_a_program_that_contracts_in_bfloat16_cannot_load_the_cell(toy_bench):
    path = os.path.join(toy_bench, "configs", "ffm4_criteo.json")
    c = json.load(open(path))
    c["ini"]["General"]["compute_dtype"] = "bfloat16"
    json.dump(c, open(path, "w"))
    with pytest.raises(SystemExit, match="not float32 on this device"):
        cells.load_cell(CELL, toy_bench)


def test_a_checkout_without_the_configuration_says_unknown_workload_at_once(toy_bench):
    os.remove(os.path.join(toy_bench, "configs", "ffm4_criteo.json"))
    with pytest.raises(SystemExit, match="unknown workload 'ffm4_criteo.train_fmb_fields'"):
        cells.load_cell(CELL, toy_bench)


def test_the_interactions_necessary_work_is_the_pairs_and_the_rows_once_each_way():
    flops, hbm = scopes.pair_interaction_work(32768, 39, 4, 157)
    assert flops == 32768 * 741 * 3 * 11 and hbm == 2 * 1277952 * 157 * 4


def _ops(*events):
    return {"/device:TPU:0": [(n, sc, s, d) for n, sc, s, d in events]}


def test_seconds_by_scope_are_a_union_and_nothing_to_read_is_none():
    ops = _ops(
        ("fusion.1", "jit(step)/jvp(fm.interaction)/ffm.fieldsum/bna,bngk->bagk/dot_general:", 0.0, 1.0),
        ("while.2", "jit(step)/transpose(jvp(fm.interaction))/ffm.pairdot/while:", 2.0, 1.0),
        ("body.3", "jit(step)/transpose(jvp(fm.interaction))/ffm.pairdot/mul:", 2.2, 0.5),  # inside while.2
        ("fusion.4", "jit(step)/transpose(jvp(ffm.diag))/mul:", 4.0, 0.25),
        ("fusion.5", "jit(step)/fm.tail/scatter-add:", 5.0, 2.0),
        ("fusion.6", "jit(step)/xffm.other/mul:", 8.0, 1.0),
        ("copy-start.7", "", 9.0, 1.0),
    )
    assert scopes.scope_seconds(ops, "ffm.") == pytest.approx(2.25)
    assert scopes.scope_seconds(ops, "ffm.pairdot") == pytest.approx(1.0)
    assert scopes.scope_seconds(ops, "fm.") == pytest.approx(4.0)  # under fm.interaction or fm.tail; not ffm.diag alone, not xffm
    assert scopes.scope_seconds(ops, "fm.dedup") is None and scopes.scope_seconds({}, "ffm.") is None
    ctx = {"trace": {"busy_s": 9.0}, "scoped_ops": ops, "n_steps": 2, "device_kind": "TPU v5e",
           "model": ffm_f32.Model.__new__(ffm_f32.Model)}
    m = {"scope": "ffm.", "work": "pair_interaction"}
    assert scopes.scope_ms(m, ctx) == pytest.approx(1125.0)
    assert scopes.scope_roofline(m, ctx) is None  # a model that states no batch: nothing to read
    ctx["model"].__dict__.update(batch=32768, nnz=39, k=4, row_dim=157)
    least = 2 * 1277952 * 157 * 4 / 819e9  # HBM bounds it: 1.96 ms
    assert scopes.scope_roofline(m, ctx) == pytest.approx(100 * least * 2 / 2.25)
    assert scopes.scope_ms({"scope": "fm.dedup"}, ctx) is None
    assert scopes.scope_ms(m, {"trace": None, "n_steps": 2, "trace_dir": "/nowhere"}) is None
    table = scopes.by_scope(ops)
    assert table[0][0] == "fm.tail" and table[0][1] == pytest.approx(2.0)
    assert [r[0] for r in table if "pairdot" in r[0]] == ["transpose(jvp(fm.interaction))/ffm.pairdot"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        return SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_the_cells_wire_unpack_compiles_for_the_chip_in_seconds(one_chip):
    """3-byte ids, values and fields of 32,768 x 39: written as reshapes to [..., m, k] the TPU compiler took 12.7
    minutes over this program (here, and 16 on the chip: the cell's first step on a cold cache); read by strided
    slices, under two seconds."""
    import jax
    import jax.numpy as jnp

    from fast_tffm_tpu.data import wire

    cell = cells.load_cell(CELL)
    b, n, vocab = cell["model"].batch, cell["model"].nnz, cell["model"].vocab
    spec = wire.make_spec(vocab, n, with_vals=True, with_fields=True)
    assert (spec.id_bytes, b, n) == (3, 32768, 39)
    buf = jax.ShapeDtypeStruct((4 + b * spec.row_bytes,), jnp.uint8, sharding=one_chip)
    t = time.time()
    compiled = wire.make_unpacker(spec).lower(buf).compile()
    assert time.time() - t < 60 and "fusion" in compiled.as_text()


# --- the by-scope reader on the chip's own trace ------------------------------
#
# ``recorded_scopes.json``: the first 700 ``XLA Ops`` events (five steps) of a traced window of this cell on
# a TPU v5 lite (PR 29, seed 3000002911), as ``scopes.dump_ops`` keeps them: [op and shape, tf_op, start, seconds].


@pytest.fixture(scope="module")
def recorded():
    d = json.load(open(os.path.join(HERE, "recorded_scopes.json")))
    return {p: [tuple(e) for e in ev] for p, ev in d.items()}


def test_the_recorded_head_reads_the_interaction_and_the_shared_scopes(recorded):
    (events,) = recorded.values()
    steps = sum(1 for e in events if e[1].startswith("jit(step)/fm.tail/scatter-add"))
    assert len(events) == 700 and steps == 5
    per_step = lambda prefix: 1e3 * scopes.scope_seconds(recorded, prefix) / steps
    assert per_step("ffm.") == pytest.approx(49.598, rel=1e-4)
    assert per_step("ffm.fieldsum") == pytest.approx(34.535, rel=1e-3)  # forward and backward
    assert per_step("fm.tail") == pytest.approx(45.354, rel=1e-4)
    assert per_step("fm.dedup") == pytest.approx(32.92, rel=1e-3)
    assert scopes.scope_seconds(recorded, "fm.absent") is None
    ctx = {"trace": {"busy_s": 1.0}, "scoped_ops": recorded, "n_steps": steps, "device_kind": "TPU v5 lite",
           "model": ffm_f32.Model.__new__(ffm_f32.Model)}
    ctx["model"].__dict__.update(batch=32768, nnz=39, k=4, row_dim=157)
    m = {"scope": "ffm.", "work": "pair_interaction"}
    assert scopes.scope_ms(m, ctx) == pytest.approx(49.598, rel=1e-4)
    assert scopes.scope_roofline(m, ctx) == pytest.approx(100 * 1.9598 / 49.598, rel=1e-3)  # 3.95%: HBM bounds it
    table = scopes.by_scope(recorded)
    assert [r[0] for r in table[:4]] == ["fm.tail", "fm.dedup", "fm.gather", "jvp(fm.interaction)/ffm.fieldsum"]
    assert max(table[0][2], key=table[0][2].get) == "fusion.2 f32[1277952,157]"  # the accumulator's row gather


def _msg(*fields):
    """A protobuf message from (field number, int | bytes | str) pairs."""
    def varint(x):
        out = bytearray()
        while True:
            out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
            x >>= 7
            if not x:
                return bytes(out)
    out = b""
    for no, v in fields:
        if isinstance(v, int):
            out += varint(no << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(no << 3 | 2) + varint(len(v)) + v
    return out


def test_the_xplane_is_read_as_wire_format_scope_and_all(tmp_path):
    stat_md = lambda i, name: _msg((1, i), (2, _msg((1, i), (2, name))))  # map entry: id -> XStatMetadata
    event_md = lambda i, name, *stats: _msg((1, i), (2, _msg((1, i), (2, name), *[(5, st) for st in stats])))
    event = lambda mid, offset_ps, dur_ps: (4, _msg((1, mid), (2, offset_ps), (3, dur_ps)))
    ops_line = _msg((2, "XLA Ops"), (3, 1000), event(10, 5_000_000, 2_000_000), event(11, 9_000_000, 1_000_000), event(12, 0, 500))
    steps_line = _msg((2, "Steps"), (3, 1000), event(10, 0, 1))
    plane = _msg(
        (1, 7), (2, "/device:TPU:0"),
        (5, stat_md(1, "tf_op")), (5, stat_md(2, "flops")), (5, stat_md(3, "jit(step)/fm.tail/scatter:")),
        (4, event_md(10, "%fusion.4 = f32[64,157]{1,0} fusion(...)", _msg((1, 2), (3, 99)), _msg((1, 1), (5, "jit(step)/ffm.diag/mul:")))),
        (4, event_md(11, "%fusion.5 = f32[64,157]{1,0} fusion(...)", _msg((1, 1), (7, 3)))),  # the string by reference
        (4, event_md(12, "%copy-start.1 = (f32[8]) copy-start(...)")),
        (3, ops_line), (3, steps_line),
    )
    host = _msg((1, 8), (2, "/host:CPU"), (3, _msg((2, "main"), (4, _msg((1, 10), (3, 1))))))
    d = tmp_path / "plugins" / "profile" / "2026_10_01"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(_msg((1, plane), (1, host)))
    ops = scopes.read_ops(str(tmp_path))
    assert list(ops) == ["/device:TPU:0"]
    assert ops["/device:TPU:0"] == [
        ("%fusion.4 = f32[64,157]{1,0} fusion(...)", "jit(step)/ffm.diag/mul:", pytest.approx(1e-6 + 5e-6), pytest.approx(2e-6)),
        ("%fusion.5 = f32[64,157]{1,0} fusion(...)", "jit(step)/fm.tail/scatter:", pytest.approx(1e-6 + 9e-6), pytest.approx(1e-6)),
        ("%copy-start.1 = (f32[8]) copy-start(...)", "", pytest.approx(1e-6), pytest.approx(5e-10)),
    ]
    assert scopes.scope_seconds(ops, "ffm.") == pytest.approx(2e-6)
    assert scopes.read_ops(str(tmp_path / "nothing")) == {}
    out = tmp_path / "head.json"
    scopes.dump_ops(ops, str(out))
    assert json.load(open(out))["/device:TPU:0"][0][:2] == ["copy-start.1 f32[8]", ""]
