"""Tracing/metrics subsystem: JSONL sink, profiler trace dir, annotations."""

import json
import os

import numpy as np

from fast_tffm_tpu.config import load_config
from fast_tffm_tpu.training import train
from fast_tffm_tpu.profiling import StepProfiler
from fast_tffm_tpu.utils.tracing import MetricsLogger, span, step_trace
from tests.test_e2e import _write_cfg, _write_dataset


def test_metrics_logger_writes_jsonl(tmp_path):
    p = tmp_path / "m.jsonl"
    with MetricsLogger(str(p)) as m:
        m.log(step=1, loss=0.5)
        m.log(step=2, loss=0.4, validation_auc=0.7)
    rows = [json.loads(l) for l in p.read_text().splitlines()]
    assert [r["step"] for r in rows] == [1, 2]
    assert rows[1]["validation_auc"] == 0.7
    assert all("ts" in r for r in rows)


def test_metrics_logger_noop_without_path():
    with MetricsLogger("") as m:
        m.log(step=1)  # must not raise or create files


def test_step_trace_and_step_profiler_noop(tmp_path):
    """No window asked for: the one tracer call a step does nothing, and
    the annotations cost nothing outside a profiler session."""
    prof = StepProfiler("", str(tmp_path / "trace"))
    assert not prof.enabled
    for step in range(1, 4):
        with step_trace("train", step):
            with span("input.wait", item=step):
                pass
        prof.on_step(step)
    prof.close(3)
    assert not (tmp_path / "trace").exists()


def test_train_emits_trace_and_metrics(tmp_path):
    rng = np.random.default_rng(0)
    _write_dataset(tmp_path / "train.libsvm", rng, n=100)
    _write_dataset(tmp_path / "valid.libsvm", rng, n=50)
    extra = (
        f"trace_dir = {tmp_path}/trace\n"
        f"metrics_path = {tmp_path}/metrics.jsonl\n"
    )
    cfgfile = tmp_path / "run.cfg"
    _write_cfg(cfgfile, tmp_path)
    # Append the new [Train] keys to the existing Train section.
    text = cfgfile.read_text().replace("log_every = 5", "log_every = 2\n" + extra)
    cfgfile.write_text(text)
    cfg = load_config(str(cfgfile))
    train(cfg, log=lambda *_: None)

    rows = [json.loads(l) for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    assert any("loss" in r for r in rows)
    assert any("validation_auc" in r for r in rows)
    # ``trace_dir`` alone is StepProfiler's default window (past five
    # steps of warm-up): its start and stop are kind=profile events.
    events = [r["event"] for r in rows if r.get("kind") == "profile" and r.get("program") == "trace"]
    assert events == ["trace_start", "trace_stop"]
    # jax.profiler.trace wrote its TensorBoard plugin layout.
    assert os.path.isdir(tmp_path / "trace")
    found = []
    for root, _dirs, files in os.walk(tmp_path / "trace"):
        found += files
    assert found, "profiler trace produced no files"
