"""Deep-observability layer (ISSUE 9): step-phase profiling, id-traffic
statistics, and freshness SLOs — end to end on real runs.

The acceptance pins: kind=profile carries MEASURED bytes next to the
modeled floor on the streamed AND device-cached paths, kind=datastats
carries the dedup/heavy-hitter numbers, kind=freshness pins
publish→applied on a live engine reload — and every instrumented path
keeps ZERO steady-state recompiles (the stats/profiling programs
attribute as warmup).
"""

import json
import os
import time

import numpy as np
import pytest

from fast_tffm_tpu.config import Config
from fast_tffm_tpu.profiling import (
    DataStatsCollector,
    modeled_step_bytes,
    parse_profile_steps,
)
from fast_tffm_tpu.telemetry import ENVELOPE_FIELDS, SCHEMAS
from fast_tffm_tpu.training import train

V = 200
NNZ = 8


def _read(path):
    return [json.loads(l) for l in open(path).read().splitlines() if l.strip()]


def _write_dataset(path, rng, n=320, vocab=V, nnz=NNZ):
    lines = []
    for _ in range(n):
        ids = rng.choice(vocab, size=nnz, replace=False)
        vals = np.round(np.abs(rng.normal(size=nnz)) + 0.1, 4)
        lines.append(
            f"{int(rng.random() < 0.5)} "
            + " ".join(f"{i}:{v}" for i, v in zip(ids, vals))
        )
    path.write_text("\n".join(lines) + "\n")


def _cfg(tmp_path, tag="run", **kw):
    base = dict(
        model="fm",
        factor_num=4,
        vocabulary_size=V,
        model_file=str(tmp_path / f"model_{tag}.npz"),
        train_files=(str(tmp_path / "train.libsvm"),),
        epoch_num=2,
        batch_size=32,
        learning_rate=0.1,
        log_every=4,
        metrics_path=str(tmp_path / f"m_{tag}.jsonl"),
    )
    base.update(kw)
    return Config(**base).validate()


@pytest.fixture
def dataset(tmp_path):
    _write_dataset(tmp_path / "train.libsvm", np.random.default_rng(0))
    return tmp_path


def _assert_schema(records):
    for r in records:
        assert all(f in r for f in ENVELOPE_FIELDS), r
        assert all(k in r for k in SCHEMAS[r["kind"]]), r


def _steady(records):
    return [r for r in records if r["kind"] == "compile" and not r["warmup"]]


# -- measured cost ledger + datastats, per data path ----------------------


def test_streamed_profile_and_datastats(dataset):
    cfg = _cfg(dataset, tag="st", telemetry_datastats_every_steps=3)
    train(cfg, log=lambda *_: None)
    records = _read(cfg.metrics_path)
    _assert_schema(records)
    assert _steady(records) == []  # the instrumented-path pin

    (prof,) = [
        r for r in records if r["kind"] == "profile" and r["program"] == "train_step"
    ]
    assert prof["bytes_accessed"] > 0 and prof["flops"] > 0
    assert prof["examples"] == cfg.batch_size
    assert prof["bytes_per_example"] == pytest.approx(
        prof["bytes_accessed"] / cfg.batch_size, rel=0.01
    )
    # measured next to modeled: the evidence column DESIGN §8.5 wants
    assert prof["modeled_hbm_bytes"] > 0

    ds = [r for r in records if r["kind"] == "datastats"]
    assert ds, "no datastats records on a sampled run"
    for r in ds:
        assert r["ids"] == cfg.batch_size * NNZ
        assert 0 < r["unique"] <= r["ids"]
        assert r["dedup_ratio"] == pytest.approx(r["unique"] / r["ids"], abs=1e-3)
        assert 0 < r["rows_seen"] <= V
        assert 0.0 < r["hh_topk_mass"] <= 1.0
    # rows_seen is cumulative — monotone across samples
    seen = [r["rows_seen"] for r in ds]
    assert seen == sorted(seen)
    (summary,) = [r for r in records if r["kind"] == "summary"]
    assert summary["datastats_samples"] == len(ds)
    assert summary["profile_train_bytes_per_example"] == prof["bytes_per_example"]


@pytest.mark.parametrize(
    "kw, row_dim, lanes, form, block",
    [
        (dict(model="fm", factor_num=4), 5, 128, "rows", None),  # under a tile: padded to one
        (dict(model="ffm", factor_num=4, num_fields=39), 157, 256, "rows", None),  # libffm's Criteo row: two tiles
        (dict(model="fm", factor_num=127), 128, 128, "rows", None),  # tile-wide already
        (dict(model="fm", factor_num=4), 5, None, "sweep", 256),  # the rule says so (patched): 200 rows, two tiles; no segment sum
        (dict(model="fm", factor_num=4, table_layout="packed"), 5, None, None, None),  # no rows-layout tail
    ],
    ids=["fm_k4", "ffm_39x4", "fm_k127", "fm_k4_sweep", "packed"],
)
def test_the_steps_profile_record_says_the_row_width_and_the_tails_lanes(dataset, request, kw, row_dim, lanes, form, block):
    """What only the start-up log line said (``describe_rows_tail``): the row
    width, the form the tail took (off a TPU the rows, unless
    ``rows_tail_form`` is made to say the sweep) and how it sums a row's
    duplicates: a segment sum on rows so many lanes wide ahead of the row
    operations, or the sweep's own contraction, and then no segment sum
    runs and its lanes are null.  Trace-time choices all
    (``optim.rows_tail_profile``)."""
    if form == "sweep":
        request.getfixturevalue("sweep_form")
    cfg = _cfg(dataset, tag="lanes", epoch_num=1, **kw)
    logs = []
    train(cfg, log=lambda *a: logs.append(" ".join(map(str, a))))
    (prof,) = [r for r in _read(cfg.metrics_path) if r["kind"] == "profile" and r["program"] == "train_step"]
    assert (prof["row_dim"], prof["segment_sum_lanes"]) == (row_dim, lanes)
    assert (prof["tail_form"], prof["tail_block_lanes"]) == (form, block)
    duplicates = {"sweep": "kernel", "rows": "segment_sum", None: None}[form]
    permutation = {"sweep": "sort operands", "rows": "row gather", None: None}[form]  # 5 columns ride the sort
    assert (prof["tail_duplicates"], prof["tail_permutation"]) == (duplicates, permutation)
    said = [l for l in logs if l.startswith("sparse tail: ")]
    if form == "sweep":
        assert said == [
            "sparse tail: pallas rows sweep (block 256 lanes, 1 blocks; duplicates summed in the kernel, "
            f"occurrences brought to id order as sort operands, row width {row_dim})"
        ]
    elif form == "rows":
        assert len(said) == 1 and said[0].startswith(f"sparse tail: xla rows (segment sum on {lanes}-lane rows")
    else:
        assert not said


@pytest.mark.parametrize(
    "kw, form, programs",
    [
        (dict(model="fm", factor_num=4, order=2), "order2", None),
        (dict(model="ffm", factor_num=4, num_fields=39), "ffm_pair_tensor", None),  # models/ffm.py's one form
        (dict(model="fm", factor_num=30, order=3), "scan", None),  # off a TPU the lax.scan
        (dict(model="fm", factor_num=30, order=3), "pallas_anova", 2 * 30),  # made to say the kernel: one tile of 128 rows x 30 factors
    ],
    ids=["fm_order2", "ffm", "fm_order3_scan", "fm_order3_kernel"],
)
def test_the_profile_records_say_the_interactions_order_form_and_grid(dataset, monkeypatch, kw, form, programs):
    """``order``, ``interaction_form`` and ``anova_programs_per_step`` (the
    kernel's grid programs, forward and backward; null for the other forms)
    ride the step's ``kind=profile`` record beside the tail's fields, and the
    predict program's (forward only); the start-up line ``interaction: ...``
    says the same.  Trace-time choices (``ops.fm.interaction_form``)."""
    from fast_tffm_tpu.ops import fm
    from fast_tffm_tpu.prediction import predict

    if form == "pallas_anova":
        monkeypatch.setattr(fm, "interaction_form", lambda order, use_pallas=None, backend=None: "pallas_anova")
    extra = dict(predict_files=(str(dataset / "train.libsvm"),), score_path=str(dataset / "scores.txt"))
    cfg = _cfg(dataset, tag="inter", epoch_num=1, **extra, **kw)
    logs = []
    train(cfg, log=lambda *a: logs.append(" ".join(map(str, a))))
    (prof,) = [r for r in _read(cfg.metrics_path) if r["kind"] == "profile" and r["program"] == "train_step"]
    order = kw.get("order", 2)
    assert (prof["order"], prof["interaction_form"], prof["anova_programs_per_step"]) == (order, form, programs)
    assert "tail_form" in prof and "row_dim" in prof  # beside the tail's fields, which keep their names
    (said,) = [l for l in logs if l.startswith("interaction: ")]
    # (a model with a form of its own words its own line: the lowering test below holds the field-aware model's)
    assert said.startswith(f"interaction: order {order}, ") == (form != "ffm_pair_tensor")
    assert (f"{programs} grid programs a step, forward and backward" in said) == (programs is not None)

    pcfg = _cfg(dataset, tag="inter_p", model_file=cfg.model_file, metrics_path=str(dataset / "m_inter_p.jsonl"), **extra, **kw)
    logs.clear()
    predict(pcfg, log=lambda *a: logs.append(" ".join(map(str, a))))
    (prof,) = [r for r in _read(pcfg.metrics_path) if r["kind"] == "profile" and r["program"] == "predict_step"]
    assert (prof["order"], prof["interaction_form"]) == (order, form)
    assert prof["anova_programs_per_step"] == (programs // 2 if programs else None)  # no backward pass
    assert len([l for l in logs if l.startswith("interaction: ")]) == 1


def test_a_backend_without_cost_analysis_still_records_what_was_dispatched(dataset, monkeypatch):
    """The TPU's PJRT client analyses no lowering (``Lowered.cost_analysis``
    is None there): the record is written with the measured fields null."""
    from fast_tffm_tpu import profiling

    monkeypatch.setattr(profiling, "program_cost", lambda fn, args: None)
    cfg = _cfg(dataset, tag="nocost", epoch_num=1)
    train(cfg, log=lambda *_: None)
    records = _read(cfg.metrics_path)
    _assert_schema(records)
    (prof,) = [r for r in records if r["kind"] == "profile" and r["program"] == "train_step"]
    assert prof["flops"] is None and prof["bytes_accessed"] is None and prof["bytes_per_example"] is None
    assert prof["examples"] == cfg.batch_size and prof["modeled_hbm_bytes"] > 0
    assert (prof["row_dim"], prof["segment_sum_lanes"]) == (5, 128)
    (summary,) = [r for r in records if r["kind"] == "summary"]
    assert "profile_train_bytes_per_example" not in summary


def test_device_cache_profile_and_datastats(dataset):
    """The device-cached path (scan-fused): the cached step closures
    delegate .lower to the inner jit, so the ledger still measures, and
    the ids slicer feeds the stats reducer straight off the resident
    arrays."""
    cfg = _cfg(
        dataset, tag="dc", device_cache=True, binary_cache=True,
        steps_per_call=4, telemetry_datastats_every_steps=2,
    )
    train(cfg, log=lambda *_: None)
    records = _read(cfg.metrics_path)
    _assert_schema(records)
    assert _steady(records) == []

    (prof,) = [
        r for r in records if r["kind"] == "profile" and r["program"] == "train_step"
    ]
    assert prof["bytes_accessed"] > 0 and prof["modeled_hbm_bytes"] > 0
    assert prof["examples"] == cfg.batch_size * cfg.steps_per_call

    ds = [r for r in records if r["kind"] == "datastats"]
    assert ds
    # The scan dispatch samples a whole [K·B, N] window of resident ids.
    assert ds[0]["ids"] == cfg.batch_size * cfg.steps_per_call * NNZ


def test_predict_profile_record(dataset):
    cfg = _cfg(
        dataset, tag="pr",
        predict_files=(str(dataset / "train.libsvm"),),
        score_path=str(dataset / "scores.txt"),
    )
    train(cfg, log=lambda *_: None)
    from fast_tffm_tpu.prediction import predict

    pcfg = _cfg(
        dataset, tag="pr2",
        model_file=cfg.model_file,
        predict_files=(str(dataset / "train.libsvm"),),
        score_path=str(dataset / "scores.txt"),
        metrics_path=str(dataset / "m_predict.jsonl"),
    )
    predict(pcfg, log=lambda *_: None)
    records = _read(pcfg.metrics_path)
    _assert_schema(records)
    (prof,) = [
        r
        for r in records
        if r["kind"] == "profile" and r["program"] == "predict_step"
    ]
    assert prof["bytes_accessed"] > 0 and prof["flops"] > 0


# -- trace capture --------------------------------------------------------


def test_profile_steps_trace_window(dataset):
    cfg = _cfg(dataset, tag="tr", telemetry_profile_steps="2:6")
    train(cfg, log=lambda *_: None)
    records = _read(cfg.metrics_path)
    events = [
        r for r in records if r["kind"] == "profile" and r["program"] == "trace"
    ]
    assert [e["event"] for e in events] == ["trace_start", "trace_stop"]
    assert events[0]["step"] >= 2 and events[1]["step"] >= 6
    trace_dir = cfg.model_file + ".profile"
    assert events[0]["trace_dir"] == trace_dir
    # jax wrote an actual trace under the dir
    assert os.path.isdir(trace_dir) and any(os.walk(trace_dir))
    assert _steady(records) == []


def test_parse_profile_steps_validation():
    assert parse_profile_steps("") is None
    assert parse_profile_steps("2:6") == (2, 6)
    for bad in ("6", "6:2", "-1:4", "a:b", "3:3"):
        with pytest.raises(ValueError, match="profile_steps"):
            parse_profile_steps(bad)
    with pytest.raises(ValueError, match="profile_steps"):
        Config(telemetry_profile_steps="9:1").validate()


# -- datastats unit behavior ----------------------------------------------


def test_modeled_step_bytes_floor_counts_unique_rmw():
    ids = np.array([[1, 1, 2], [2, 3, 3]], np.int32)  # m=6, uniq=3
    row_dim, accum_cols = 5, 1
    total, uniq = modeled_step_bytes(ids, row_dim, accum_cols)
    assert uniq == 3
    row = row_dim * 4
    assert total == 6 * 4 + 4 * 6 * row + 2 * 3 * row + 2 * 3 * accum_cols * 4


def test_datastats_collector_skews_toward_heavy_hitters(tmp_path):
    """A Zipf-skewed stream must show low dedup ratio (few unique rows
    per batch) and high top-K sketch mass — the two numbers that size
    ROADMAP item 3's dedup-before-gather and hot-id cache."""
    from fast_tffm_tpu.telemetry import RunMonitor

    path = str(tmp_path / "ds.jsonl")
    mon = RunMonitor(path)
    col = DataStatsCollector(
        mon, vocab=1 << 14, row_dim=8, every_steps=1, heavy_hitter_k=16
    )
    rng = np.random.default_rng(0)

    class P:
        def __init__(self, ids):
            self.ids = ids

    zipf = np.minimum(rng.zipf(1.1, size=(8, 256, 16)) - 1, (1 << 14) - 1)
    uni = rng.integers(0, 1 << 14, size=(8, 256, 16))
    for i in range(8):
        col.note(i + 1, parsed=P(zipf[i].astype(np.int32)))
    zipf_summary = col.summary()
    col2 = DataStatsCollector(
        mon, vocab=1 << 14, row_dim=8, every_steps=1, heavy_hitter_k=16
    )
    for i in range(8):
        col2.note(i + 1, parsed=P(uni[i].astype(np.int32)))
    uni_summary = col2.summary()
    mon.close()
    # Skew compresses uniques and concentrates sketch mass.
    assert zipf_summary["datastats_dedup_ratio"] < uni_summary["datastats_dedup_ratio"]
    assert zipf_summary["datastats_hh_topk_mass"] > uni_summary["datastats_hh_topk_mass"]
    records = [r for r in _read(path) if r["kind"] == "datastats"]
    # note() arms on the first call, then samples every step
    assert len(records) == 14
    _assert_schema(records)


# -- freshness SLO on a live engine reload --------------------------------


def test_freshness_pinned_on_live_engine_reload(tmp_path):
    """The satellite's e2e pin: a published checkpoint reaches a LIVE
    engine via the watcher, and the swap emits kind=freshness whose
    publish→applied and publish→first-scored both measure the real
    publish→serve pipe (applied <= first-scored, both sane)."""
    import jax

    from fast_tffm_tpu.checkpoint import read_publish_time, save_checkpoint
    from fast_tffm_tpu.config import build_model
    from fast_tffm_tpu.serving.engine import ServingEngine
    from fast_tffm_tpu.trainer import init_state

    cfg = Config(
        model="fm",
        factor_num=4,
        vocabulary_size=V,
        max_nnz=NNZ,
        model_file=str(tmp_path / "m.ckpt"),
        serve_buckets=(1, 4),
        serve_flush_deadline_ms=2.0,
        serve_reload_interval_s=0.05,
        metrics_path=str(tmp_path / "serve.jsonl"),
    ).validate()
    model = build_model(cfg)
    state = init_state(model, jax.random.key(0), cfg.init_accumulator_value)
    save_checkpoint(cfg.model_file, state)
    assert read_publish_time(cfg.model_file) == pytest.approx(time.time(), abs=60)

    line = "0 1:1.0"
    with ServingEngine(cfg, log=lambda *_: None) as eng:
        s0 = eng.submit_line(line).result(timeout=20)
        state = state._replace(table=state.table.at[1].add(0.5), step=state.step + 1)
        save_checkpoint(cfg.model_file, state)
        t_pub = time.time()
        deadline = time.time() + 20
        s1 = s0
        while time.time() < deadline and s1 == s0:
            s1 = eng.submit_line(line).result(timeout=20)
            time.sleep(0.01)
        assert s1 != s0, "published checkpoint never reached scoring"
        snap = eng.metrics_snapshot()
    records = _read(cfg.metrics_path)
    _assert_schema(records)
    (fresh,) = [r for r in records if r["kind"] == "freshness"]
    assert fresh["publish_step"] == 1
    assert 0 <= fresh["publish_to_applied_ms"] <= fresh["publish_to_first_scored_ms"]
    # sane upper bound: within the watcher poll + restore + test slack
    assert fresh["publish_to_first_scored_ms"] <= (time.time() - t_pub + 25) * 1e3
    # the snapshot carries the histograms the stats op / report read
    assert snap["freshness_applied_ms"]["count"] == 1
    assert snap["freshness_scored_ms"]["count"] == 1


def test_read_publish_time_degrades_to_none(tmp_path):
    from fast_tffm_tpu.checkpoint import read_publish_time

    assert read_publish_time(str(tmp_path / "missing.npz")) is None
    d = tmp_path / "dir.orbax"
    d.mkdir()
    assert read_publish_time(str(d)) is None
    # pre-PR-9 npz (no published_at member): degrade, never raise
    np.savez(tmp_path / "old.npz", step=np.int32(3), table=np.zeros((2, 2)))
    assert read_publish_time(str(tmp_path / "old.npz")) is None


# -- report rendering + gates ---------------------------------------------


def _load_report_module():
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "report_tool", os.path.join(repo, "tools", "report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _synth_run(path, *, fresh_p99=50.0, bytes_per_example=100.0, rate=1000.0):
    from fast_tffm_tpu.telemetry import RunMonitor, new_run_id

    mon = RunMonitor(str(path), run_id=new_run_id())
    for i in range(1, 6):
        mon.emit(
            "train", step=i * 4, epoch=0, loss=0.7 - 0.01 * i,
            examples_per_sec=rate, examples_per_sec_per_chip=rate,
        )
    mon.emit(
        "profile", step=4, program="train_step", flops=1000,
        bytes_accessed=int(bytes_per_example * 32), examples=32,
        bytes_per_example=bytes_per_example, modeled_hbm_bytes=1000,
    )
    mon.emit(
        "datastats", step=4, window_steps=4, ids=256, unique=100,
        dedup_ratio=0.39, rows_seen=150, rows_seen_frac=0.1, hh_k=16,
        hh_topk_mass=0.4, gather_bytes=8192, dedup_gather_bytes=3200,
        projected_gather_savings_frac=0.61,
    )
    for ms in (fresh_p99 * 0.5, fresh_p99):
        mon.emit(
            "freshness", step=5, publish_step=7,
            publish_to_applied_ms=ms * 0.9, publish_to_first_scored_ms=ms,
        )
    mon.close()
    return str(path)


def test_report_renders_and_gates_observability(tmp_path):
    import subprocess
    import sys

    report = _load_report_module()
    base = _synth_run(tmp_path / "base.jsonl")
    same = _synth_run(tmp_path / "same.jsonl")
    stale = _synth_run(tmp_path / "stale.jsonl", fresh_p99=500.0)
    fat = _synth_run(tmp_path / "fat.jsonl", bytes_per_example=300.0)

    s = report.summarize(report.load_run(base))
    assert s["measured_bytes_per_example"] == 100.0
    assert s["freshness_p99_ms"] == 50.0
    assert s["dedup_ratio_mean"] == 0.39
    text = report.render(s)
    for needle in (
        "Profiling (measured vs modeled)",
        "Id-traffic statistics",
        "Freshness (publish",
        "train_step",
        "dedup",
    ):
        assert needle in text, f"{needle} missing:\n{text}"

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "report.py")

    def run(*args):
        return subprocess.run(
            [sys.executable, tool, *args], capture_output=True, text=True
        )

    # non-strict: freshness/bytes regressions do not gate
    assert run(stale, "--compare", base).returncode == 0
    # strict: each regression gates independently
    assert run(same, "--compare", base, "--strict").returncode == 0
    r = run(stale, "--compare", base, "--strict")
    assert r.returncode == 1 and "freshness p99" in r.stdout
    r = run(fat, "--compare", base, "--strict")
    assert r.returncode == 1 and "bytes/example" in r.stdout


@pytest.mark.parametrize(
    "kw, form",
    [
        (dict(model="fm", factor_num=4), "rows"),  # off a TPU nobody takes the sweep
        (dict(model="fm", factor_num=4), "sweep"),  # the rule says so (patched): 200 rows, one block; 32 x 8 ids, one chunk
        (dict(model="fm", factor_num=4, dedup_gather_rows=256), "dedup"),  # the dedup body brings its own gather
        (dict(model="fm", factor_num=4, table_layout="packed"), None),  # no rows-layout gather
    ],
    ids=["rows", "sweep", "dedup_gather_rows", "packed"],
)
def test_the_profile_records_say_the_forward_gathers_form_and_grid(dataset, monkeypatch, kw, form):
    """``gather_form`` and ``gather_items`` (the sweep kernel's grid length a
    step; null under the rows) ride the step's ``kind=profile`` record beside
    the tail's fields, and the predict program's; the start-up line ``forward
    gather: ...`` says the same.  A trace-time choice (``trainer.gather_form``),
    asked once for the record and once by ``gather_rows`` when the step is
    traced: of the same shapes, so the record names the form the program took."""
    from fast_tffm_tpu import trainer
    from fast_tffm_tpu.prediction import predict

    rule, asked = trainer.gather_form, []
    monkeypatch.setattr(
        trainer, "gather_form", lambda *a, **k: asked.append(a) or ("sweep" if form == "sweep" else rule(*a, **k))
    )
    extra = dict(predict_files=(str(dataset / "train.libsvm"),), score_path=str(dataset / "scores.txt"))
    cfg = _cfg(dataset, tag="gather", epoch_num=1, **extra, **kw)
    logs = []
    train(cfg, log=lambda *a: logs.append(" ".join(map(str, a))))
    (prof,) = [r for r in _read(cfg.metrics_path) if r["kind"] == "profile" and r["program"] == "train_step"]
    said = [l for l in logs if l.startswith("forward gather: ")]
    items = 1 + 1  # one block of 256 lanes, one chunk of 256 ids
    if form == "sweep":
        assert (prof["gather_form"], prof["gather_items"]) == ("sweep", items)
        assert said == [
            f"forward gather: pallas sweep of table.T ({items} grid items a step; ids sorted once, "
            "columns brought back to batch order as sort operands, row width 5)"
        ]
    elif form == "rows":
        assert (prof["gather_form"], prof["gather_items"]) == ("rows", None)
        assert said == [f"forward gather: xla row gather ({32 * NNZ} rows of 5 a step)"]
    else:
        assert (prof["gather_form"], prof["gather_items"]) == (None, None)
        assert len(said) == (1 if form == "dedup" else 0)
    assert "tail_form" in prof and "row_dim" in prof
    if form in ("rows", "sweep"):
        assert len(asked) >= 2 and set(asked) == {(200, 32 * NNZ, 5)}  # the record's shapes are the traced step's
        asked.clear()
        pcfg = _cfg(dataset, tag="gather_p", model_file=cfg.model_file, metrics_path=str(dataset / "m_gather_p.jsonl"), **extra, **kw)
        logs.clear()
        predict(pcfg, log=lambda *a: logs.append(" ".join(map(str, a))))
        (prof,) = [r for r in _read(pcfg.metrics_path) if r["kind"] == "profile" and r["program"] == "predict_step"]
        assert (prof["gather_form"], prof["gather_items"]) == (form, items if form == "sweep" else None)
        assert len([l for l in logs if l.startswith("forward gather: ")]) == 1
        assert len(asked) >= 2 and set(asked) == {(200, 32 * NNZ, 5)}


# --- the dense head (DeepFM's perceptron): its scopes and its profile fields ---

_HEAD_SCOPES = ("deepfm.feed", "deepfm.mlp", "deepfm.dense_update")


@pytest.mark.parametrize(
    "kw, dense_params, hidden, dtype",
    [
        (dict(model="deepfm", factor_num=4, num_fields=NNZ, max_nnz=NNZ, hidden_dims=(16, 8), compute_dtype="bfloat16"), 673, [16, 8], "bfloat16"),
        (dict(model="deepfm", factor_num=4, num_fields=NNZ, max_nnz=NNZ, hidden_dims=(12,), compute_dtype="float32"), 409, [12], "float32"),
        (dict(model="fm", factor_num=4), None, None, None),
        (dict(model="ffm", factor_num=4, num_fields=39), None, None, None),
    ],
    ids=["deepfm_16_8_bf16", "deepfm_12_f32", "fm", "ffm"],
)
def test_the_profile_records_say_the_perceptron(dataset, kw, dense_params, hidden, dtype):
    """``dense_params``, ``hidden_dims``, ``compute_dtype`` and
    ``mlp_flops_per_step`` (6 a weight a row for the train step, 2 for the
    predict step) ride the step's ``kind=profile`` record beside the
    interaction's fields; null for a model without a perceptron.  The
    start-up line ``perceptron: ...`` says the same, and only such a model
    says it."""
    from fast_tffm_tpu.prediction import predict

    extra = dict(predict_files=(str(dataset / "train.libsvm"),), score_path=str(dataset / "scores.txt"))
    cfg = _cfg(dataset, tag="head", epoch_num=1, **extra, **kw)
    logs = []
    train(cfg, log=lambda *a: logs.append(" ".join(map(str, a))))
    (prof,) = [r for r in _read(cfg.metrics_path) if r["kind"] == "profile" and r["program"] == "train_step"]
    weights = None if dense_params is None else dense_params - sum(hidden) - 1
    flops = lambda per_weight: None if weights is None else per_weight * weights * cfg.batch_size
    assert (prof["dense_params"], prof["hidden_dims"], prof["compute_dtype"], prof["mlp_flops_per_step"]) == (dense_params, hidden, dtype, flops(6))
    assert "interaction_form" in prof and "tail_form" in prof  # beside the fields that were there
    said = [l for l in logs if l.startswith("perceptron: ")]
    if dense_params is None:
        assert not said
    else:
        dims = "-".join(map(str, [NNZ * 4, *hidden, 1]))
        assert said == [f"perceptron: {dims}, {dense_params:,} parameters, {dtype} operands"]

    pcfg = _cfg(dataset, tag="head_p", model_file=cfg.model_file, metrics_path=str(dataset / "m_head_p.jsonl"), **extra, **kw)
    logs.clear()
    predict(pcfg, log=lambda *a: logs.append(" ".join(map(str, a))))
    (prof,) = [r for r in _read(pcfg.metrics_path) if r["kind"] == "profile" and r["program"] == "predict_step"]
    assert (prof["dense_params"], prof["hidden_dims"], prof["compute_dtype"], prof["mlp_flops_per_step"]) == (dense_params, hidden, dtype, flops(2))
    assert len([l for l in logs if l.startswith("perceptron: ")]) == (0 if dense_params is None else 1)


def _step_op_names(model, packed=False):
    """The ``jax.named_scope`` paths in the ``op_name``s of the lowered train
    step and of the lowered predict step, at a toy size."""
    import re

    import jax
    import jax.numpy as jnp

    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.trainer import (
        init_packed_state, init_state, make_packed_predict_step, make_packed_train_step, make_predict_step, make_train_step,
    )

    b, n = 32, NNZ
    batch = Batch(labels=jnp.zeros((b,)), ids=jnp.zeros((b, n), jnp.int32), vals=jnp.ones((b, n)),
                  fields=jnp.zeros((b, n if getattr(model, "uses_fields", False) else 0), jnp.int32), weights=jnp.ones((b,)))
    if packed:
        state, step, predict = init_packed_state(model, jax.random.key(0)), make_packed_train_step(model, 0.05), make_packed_predict_step(model)
    else:
        state, step, predict = init_state(model, jax.random.key(0)), make_train_step(model, 0.05), make_predict_step(model)
    names = lambda fn, *args: set(re.findall(r'op_name="jit\(\w+\)/([^"]*)"', fn.lower(*args).as_text(dialect="hlo", debug_info=True)))
    return names(step, state, batch), names(predict, state, batch)


@pytest.mark.parametrize("body", ["rows", "packed"])
def test_a_deepfm_step_names_its_feed_its_perceptron_and_its_dense_update(body):
    """In the compiled step's ``op_name``s: ``deepfm.feed`` and ``deepfm.mlp``
    forward (``jvp(...)``) and backward (``transpose(jvp(...))``), the
    leaves' Adagrad under ``deepfm.dense_update``, in both step bodies; no op
    under two of them, none of the perceptron's matmuls outside
    ``deepfm.mlp``, and the FM half still under ``fm.interaction``.  The
    predict step holds the first two, forward only."""
    from fast_tffm_tpu.models import DeepFMModel

    model = DeepFMModel(vocabulary_size=V, num_fields=NNZ, factor_num=4, hidden_dims=(16, 8))
    step, predict = _step_op_names(model, packed=body == "packed")
    for scope in ("deepfm.feed", "deepfm.mlp"):
        assert any(n.startswith(f"jvp({scope})/") for n in step), scope
        assert any(n.startswith(f"transpose(jvp({scope}))/") for n in step), scope
        assert any(n.startswith(f"{scope}/") for n in predict), scope
    assert any(n.startswith("deepfm.dense_update/") for n in step)
    assert not any("deepfm.dense_update" in n or "jvp(" in n for n in predict)
    assert all(sum(s in n for s in _HEAD_SCOPES) <= 1 for n in step | predict)  # nothing under two
    assert all("deepfm.mlp" in n for n in step | predict if n.endswith("/dot_general"))  # every matmul is the perceptron's
    assert any(n.startswith("jvp(fm.interaction)/") for n in step) and not any("fm.interaction" in n and "deepfm." in n for n in step)


@pytest.mark.parametrize("num_fields, row_dim", [(39, 157), (22, 89)], ids=["criteo_39x4", "avazu_22x4"])
def test_the_ffm_step_keeps_its_pair_interaction_under_its_own_scopes(num_fields, row_dim):
    """The lowered field-aware train step at libffm's Criteo row (39 fields,
    k = 4) and at Avazu's (22): every op of ``fm.interaction`` stands under
    one of ``ffm.fieldsum``, ``ffm.pairdot``, ``ffm.diag``, forward
    (``jvp``) and backward (``transpose(jvp)``), the hand-written backward
    among them; the row gradient leaves 1 + F·k lanes wide, so the backward
    pads no F·k-wide array to that; and the form says its name."""
    import re

    import jax
    import jax.numpy as jnp

    from fast_tffm_tpu.models import FFMModel
    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.trainer import init_state, make_train_step
    from fast_tffm_tpu.training import _say_interaction

    model = FFMModel(vocabulary_size=V, num_fields=num_fields, factor_num=4)
    assert model.row_dim == row_dim
    b, n = 16, num_fields
    batch = Batch(labels=jnp.zeros((b,)), ids=jnp.zeros((b, n), jnp.int32), vals=jnp.ones((b, n)),
                  fields=jnp.zeros((b, n), jnp.int32), weights=jnp.ones((b,)))
    text = make_train_step(model, 0.05).lower(init_state(model, jax.random.key(0)), batch).as_text(dialect="hlo", debug_info=True)
    ops = [(m.group(1), line) for line in text.splitlines() if (m := re.search(r'op_name="jit\(step\)/([^"]*)"', line))]
    # (``add_any`` is autodiff's sum of the rows' two cotangents, the score's and the L2 term's.)
    inside = [(name, line) for name, line in ops if "fm.interaction" in name and not name.endswith("/add_any")]
    assert inside and all("/ffm." in name for name, _ in inside), [n for n, _ in inside if "/ffm." not in n][:5]
    for way in ("jvp(fm.interaction)", "transpose(jvp(fm.interaction))"):
        for scope in ("ffm.fieldsum", "ffm.pairdot", "ffm.diag"):
            assert any(name.startswith(f"{way}/{scope}/") for name, _ in inside), (way, scope)
    backward = [line for name, line in inside if name.startswith("transpose(")]
    assert sum(" dot(" in line for line in backward) == 1  # the one backward matmul
    assert not any(re.search(r"\[\d+,\d+,%d\]\S* pad\(" % row_dim, line) for line in backward)
    assert not any("ffm." in name and "fm.interaction" not in name for name, _ in ops)  # and none of it outside

    # The model says its form itself (``interaction_form``, ``describe_interaction``), as ``order`` and ``mlp_dims``
    # are said: the launcher reads it and asks for no class.
    for backward, passes in ((True, "hand-written backward"), (False, "forward only")):
        logs = []
        profile = _say_interaction(logs.append, model, b, backward=backward)
        assert profile["interaction_form"] == model.interaction_form == "ffm_pair_tensor" and profile["order"] == 2
        assert logs == [f"interaction: field-aware pair tensor ({num_fields} fields x 4 factors, one block transpose a step, {passes})"]


@pytest.mark.parametrize("kind", ["fm", "fm_order3", "ffm"])
def test_a_step_without_a_perceptron_names_none_of_the_three(kind):
    from fast_tffm_tpu.models import FFMModel, FMModel

    model = {
        "fm": lambda: FMModel(vocabulary_size=V, factor_num=4, order=2),
        "fm_order3": lambda: FMModel(vocabulary_size=V, factor_num=4, order=3),
        "ffm": lambda: FFMModel(vocabulary_size=V, num_fields=NNZ, factor_num=4),
    }[kind]()
    step, predict = _step_op_names(model)
    assert step and predict and not any(s in n for n in step | predict for s in _HEAD_SCOPES)
