"""Telemetry-layer tests: envelope schema, sentinels, watchdog, report tool.

The schema test is the drift tripwire the ISSUE asks for: every ``kind``
the system can emit must carry the envelope fields and its documented
required keys — an emitter that drops a key (or invents an unregistered
kind) fails here, not in somebody's dashboard.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from fast_tffm_tpu.config import Config
from fast_tffm_tpu.metrics import Throughput
from fast_tffm_tpu.telemetry import (
    ENVELOPE_FIELDS,
    SCHEMAS,
    CompileSentinel,
    RunMonitor,
    classify_stall,
    new_run_id,
    thread_stacks,
)
from fast_tffm_tpu.training import train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_report_module():
    spec = importlib.util.spec_from_file_location(
        "report_tool", os.path.join(REPO, "tools", "report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read(path):
    return [json.loads(l) for l in open(path).read().splitlines() if l.strip()]


# -- schema ---------------------------------------------------------------

# Driver-shaped payloads for the kinds the monitor does not emit itself.
# (compile/mem/stall/anomaly/summary are produced organically below, so
# the test pins the REAL emitters, not hand-rolled imitations.)
_DRIVER_PAYLOADS = {
    "train": dict(
        epoch=0, loss=0.69, examples_per_sec=1000.0, examples_per_sec_per_chip=1000.0
    ),
    "validation": dict(epoch=0, validation_auc=0.75),
    "input": dict(input_items=4, input_steps=4, input_examples=128, parse_ms=0.2),
    "predict": dict(examples=100, examples_per_sec=5000.0),
    "serving": dict(
        requests=10, flushes=3, rows=10, queue_ms={}, compute_ms={}, total_ms={}
    ),
    "ckpt": dict(
        mode="full", snapshot_ms=1.0, convert_ms=2.0, d2h_ms=3.0,
        write_ms=4.0, bytes=1024, rows_written=7, train_stall_ms=1.0,
    ),
    # Resilience layer (resilience.py): the training loop emits fault
    # records by splatting injector/retry event dicts; the supervisor
    # emits restart records with the measured MTTR (null until a step).
    "fault": dict(event="crash", exit_code=-9, signal=9),
    "restart": dict(attempt=1, exit_code=-9, backoff_s=0.5, mttr_s=2.1),
    # Deep observability (profiling.py / serving freshness, ISSUE 9).
    # profile's bytes/flops may be null only on trace event records;
    # freshness nulls first_scored where the emitter cannot see scoring
    # (the router's fleet_staged aggregate).
    "profile": dict(
        program="train_step", flops=99361, bytes_accessed=646295,
        examples=32, bytes_per_example=20196.7, modeled_hbm_bytes=33584,
    ),
    "datastats": dict(
        window_steps=3, ids=256, unique=147, dedup_ratio=0.5742,
        rows_seen=147, hh_k=16, hh_topk_mass=0.23,
        projected_gather_savings_frac=0.43,
    ),
    "freshness": dict(
        publish_step=12, publish_to_applied_ms=41.2,
        publish_to_first_scored_ms=44.8, mode="delta",
    ),
    # Online-learning loop (ISSUE 11): the rolling backtest's per-hour
    # AUC pair (tools/backtest.py) and the soak harness's sentinel tick
    # (tools/soak.py).
    "quality": dict(
        hour=3, auc_online=0.8312, auc_batch=0.8297, auc_gap=-0.0015,
    ),
    "soak": dict(
        phase="steady", elapsed_s=61.2, ok=True, unanswered=0,
        freshness_scored_p99_ms=212.4, chain_len=5, disk_bytes=1048576,
    ),
    # The host clock's event (ISSUE 39): the monitor writes it itself, but
    # only when the interpreter really froze for 50 ms — the table cannot
    # wait for that, so the row is the emitter's own payload shape.
    "freeze": dict(
        late_ms=312.4, freeze_ms=312.4, classification="gil", gc_ms=0.0,
        cpu_ms=309.8, beats=0, os_delta={"nivcsw": 2}, stacks={},
    ),
    # Tiered parameter store (ISSUE 12): the per-log-window residency
    # record the training loop drains from paramstore stats.
    "tiering": dict(
        steps=4, hit_rate=0.6103, miss_rows=812, miss_rows_per_step=203.0,
        miss_bytes_per_step=58464, wire_bytes_per_step=23040,
        writeback_rows=812, writeback_ms=0.475, resolve_ms=0.8,
        read_ms=0.4, restages=0, pending_rows=812, hot_rows=4096,
        apply_rows=0, apply_ms=0.0,
    ),
}


def test_every_kind_carries_envelope_and_required_keys(tmp_path):
    """Table-driven over telemetry.SCHEMAS: each kind is emitted once
    (organically where the monitor owns the emitter) and every record
    must carry the envelope + its kind's required keys."""
    import jax
    import jax.numpy as jnp

    path = str(tmp_path / "m.jsonl")
    mon = RunMonitor(
        path, source="train", stall_timeout_s=0.15, mem_every_s=0.001,
        queue_depth_fn=lambda: 2,
    )
    for kind, payload in _DRIVER_PAYLOADS.items():
        mon.emit(kind, step=1, **payload)
    # compile: force a fresh XLA program through the sentinel's listener.
    jax.jit(lambda x: x * 3.0)(jnp.ones(int(time.time()) % 7 + 2))
    mon.on_dispatch(2, warmup=False)
    mon.emit_mem(step=2)
    mon.emit_anomaly(3, float("nan"), state={"w": np.array([np.nan])})
    # stall: freeze the heartbeat past the deadline.
    time.sleep(0.5)
    mon.close()

    records = _read(path)
    seen = {r["kind"] for r in records}
    assert seen == set(SCHEMAS), f"kinds emitted {seen} != documented {set(SCHEMAS)}"
    assert len({r["run_id"] for r in records}) == 1
    for r in records:
        missing = [f for f in ENVELOPE_FIELDS if f not in r]
        assert not missing, f"{r['kind']} record missing envelope {missing}: {r}"
        assert r["schema_version"] == 1
        required = SCHEMAS[r["kind"]]
        missing = [k for k in required if k not in r]
        assert not missing, f"kind={r['kind']} missing required {missing}: {r}"
    # monotonic t within the run
    ts = [r["t"] for r in records]
    assert ts == sorted(ts)


def test_unknown_kind_raises(tmp_path):
    mon = RunMonitor(str(tmp_path / "m.jsonl"))
    with pytest.raises(ValueError, match="unknown telemetry kind"):
        mon.emit("nope", step=0)
    mon.close()


def test_anomaly_names_first_nonfinite_tensor(tmp_path):
    path = str(tmp_path / "m.jsonl")
    mon = RunMonitor(path)
    state = {"table": np.ones(3, np.float32), "accum": np.array([1.0, np.inf])}
    mon.emit_anomaly(7, float("nan"), state=state)
    mon.close()
    (rec,) = [r for r in _read(path) if r["kind"] == "anomaly"]
    assert rec["step"] == 7
    assert "accum" in rec["first_nonfinite"]


def test_check_telemetry_conformance():
    """The conformance tripwire: the telemetry rule of the static
    analysis suite (tools/analysis/ — absorbed the old standalone
    check_telemetry.py) must pass on the committed tree — schema drift
    fails tier-1 loudly instead of silently forking the envelope.
    (tests/test_analysis.py runs the FULL five-checker suite; this
    checks the telemetry rule alone stays green even if another rule's
    baseline churns.)"""
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "tools", "analysis", "run.py"),
            "--rules", "telemetry", "--strict",
        ],
        capture_output=True,
        text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr


# -- compile sentinel -----------------------------------------------------

def test_compile_sentinel_counts_only_new_programs():
    import jax
    import jax.numpy as jnp

    s = CompileSentinel()
    f = jax.jit(lambda x: x - 0.5)
    f(jnp.ones(11))
    assert s.drain() >= 1
    f(jnp.ones(11))  # cached: no compile
    assert s.drain() == 0
    f(jnp.ones(13))  # new shape: recompile
    assert s.drain() >= 1


# -- stall watchdog -------------------------------------------------------

def test_watchdog_fires_once_per_episode_with_stacks_and_depth(tmp_path):
    path = str(tmp_path / "m.jsonl")
    mon = RunMonitor(
        path, stall_timeout_s=0.15, queue_depth_fn=lambda: 3, log=lambda *_: None
    )
    mon.heartbeat(5)
    time.sleep(0.5)  # episode 1: exactly one event despite 3+ polls
    mon.heartbeat(6)  # recover
    time.sleep(0.5)  # episode 2
    mon.close()
    stalls = [r for r in _read(path) if r["kind"] == "stall"]
    assert len(stalls) == 2
    first = stalls[0]
    assert first["step"] == 5 and stalls[1]["step"] == 6
    assert first["deadline_s"] == 0.15
    assert first["since_last_step_s"] >= 0.15
    assert first["prefetch_queue_depth"] == 3
    # data was queued, so the consumer/device side is the suspect
    assert first["classification"] == "device-bound"
    # forensics: the sleeping main thread's stack is in the dump
    assert any("time.sleep" in s or "sleep" in s for s in first["stacks"].values())
    assert "telemetry-watchdog" not in first["stacks"]


def test_classify_stall():
    assert classify_stall(0, {}) == "input-starved"
    assert classify_stall(4, {"MainThread": "x"}) == "device-bound"
    assert classify_stall(None, {"MainThread": "in block_until_ready"}) == "device-bound"
    assert classify_stall(None, {"MainThread": "plain python"}) == "unknown"
    assert "MainThread" in thread_stacks()


def test_watchdog_defers_while_compiling():
    """A stack inside a jit cache miss (trace/lower/XLA compile) must
    defer the watchdog — a slow warmup compile is not a stall."""
    from fast_tffm_tpu.telemetry import compiling_now

    assert compiling_now({"MainThread": "... in backend_compile\n"})
    assert compiling_now({"MainThread": "... in cache_miss\n"})
    assert not compiling_now({"MainThread": "... in time.sleep\n"})


# -- end-to-end: instrumented train runs ---------------------------------

def _write_dataset(path, rng, n=320, vocab=200, nnz=8):
    lines = []
    for _ in range(n):
        ids = rng.choice(vocab, size=nnz, replace=False)
        vals = np.round(np.abs(rng.normal(size=nnz)) + 0.1, 4)
        y = int(rng.random() < 0.5)
        lines.append(f"{y} " + " ".join(f"{i}:{v}" for i, v in zip(ids, vals)))
    path.write_text("\n".join(lines) + "\n")


def _train_cfg(tmp_path, tag="run", **kw):
    base = dict(
        model="fm",
        factor_num=4,
        vocabulary_size=200,
        model_file=str(tmp_path / f"model_{tag}.npz"),
        train_files=(str(tmp_path / "train.libsvm"),),
        epoch_num=2,
        batch_size=32,
        learning_rate=0.1,
        log_every=4,
        metrics_path=str(tmp_path / f"m_{tag}.jsonl"),
        telemetry_mem_every_s=0.001,
    )
    base.update(kw)
    return Config(**base).validate()


@pytest.fixture
def dataset(tmp_path):
    _write_dataset(tmp_path / "train.libsvm", np.random.default_rng(0))
    return tmp_path


def test_streamed_train_telemetry_schema_and_zero_steady_compiles(dataset):
    """The acceptance pin: a streamed CPU train run with telemetry on
    yields kind ∈ {train, input, compile, mem} records sharing one
    run_id, with ZERO steady-state kind=compile events after warmup."""
    cfg = _train_cfg(dataset, telemetry_stall_timeout_s=30.0)
    logged = []
    train(cfg, log=logged.append)
    # ...and says so once at start, in the one format a jax-free parent
    # (chip_smoke.py) parses
    (line,) = [l for l in logged if "device platform=" in l]
    assert line.startswith("train: device platform=cpu device_kind=")
    assert " pallas=interpreted parser=" in line
    records = _read(cfg.metrics_path)
    kinds = {r["kind"] for r in records}
    assert {"train", "input", "compile", "mem", "summary"} <= kinds
    assert len({r["run_id"] for r in records}) == 1
    for r in records:  # schema holds on organic driver output too
        assert all(f in r for f in ENVELOPE_FIELDS)
        assert all(k in r for k in SCHEMAS[r["kind"]])
    steady = [r for r in records if r["kind"] == "compile" and not r["warmup"]]
    assert steady == [], f"steady-state recompiles: {steady}"
    (summary,) = [r for r in records if r["kind"] == "summary"]
    assert summary["steady_compiles"] == 0
    assert summary["total_compiles"] >= 1  # warmup compile was seen
    assert summary["stalls"] == 0 and summary["anomalies"] == 0
    # the run names its device: what jax reported to the driver
    import jax

    assert summary["platform"] == "cpu"
    assert summary["device_kind"] == jax.devices()[0].device_kind
    assert summary["device_count"] == jax.device_count()
    # the windowed meter fed real rates into the telemetry field
    assert all(
        r["examples_per_sec"] > 0 for r in records if r["kind"] == "train"
    )


def test_fused_tail_superbatch_compiles_are_warmup(dataset):
    """steps_per_call=8 over 10 steps/epoch leaves a ragged [2, B, ...]
    epoch-tail superbatch — a second XLA program that must land in epoch
    0's warmup budget, not as a false steady-state recompile."""
    cfg = _train_cfg(dataset, tag="k8", steps_per_call=8)
    train(cfg, log=lambda *_: None)
    records = _read(cfg.metrics_path)
    compiles = [r for r in records if r["kind"] == "compile"]
    assert sum(r["compiles"] for r in compiles) >= 2  # full-K + tail-K'
    assert all(r["warmup"] for r in compiles), compiles
    (summary,) = [r for r in records if r["kind"] == "summary"]
    assert summary["steady_compiles"] == 0


def test_watchdog_suspended_during_no_dispatch_phases(tmp_path):
    """A long validation pass / checkpoint save completes no dispatches;
    monitor.suspended() must keep the watchdog quiet through it and
    re-arm cleanly after."""
    path = str(tmp_path / "m.jsonl")
    mon = RunMonitor(path, stall_timeout_s=0.15, log=lambda *_: None)
    mon.heartbeat(3)
    with mon.suspended():
        time.sleep(0.5)  # would have fired 3x unsuspended
    time.sleep(0.1)  # post-resume: clock restarted, still inside deadline
    mon.heartbeat(4)
    time.sleep(0.5)  # genuine stall after resume still fires
    mon.close()
    stalls = [r for r in _read(path) if r["kind"] == "stall"]
    assert len(stalls) == 1 and stalls[0]["step"] == 4


def test_watchdog_quiet_across_validation_epoch_boundary(dataset):
    """Integration: validation per epoch with a tight deadline — the
    suspended() wrapping keeps a healthy run stall-free."""
    _write_dataset(dataset / "valid.libsvm", np.random.default_rng(1), n=96)
    cfg = _train_cfg(
        dataset, tag="valwd",
        validation_files=(str(dataset / "valid.libsvm"),),
        telemetry_stall_timeout_s=0.25,
    )
    train(cfg, log=lambda *_: None)
    records = _read(cfg.metrics_path)
    assert [r for r in records if r["kind"] == "stall"] == []
    assert [r for r in records if r["kind"] == "validation"]
    steady = [r for r in records if r["kind"] == "compile" and not r["warmup"]]
    assert steady == []  # validation predict compile priced into epoch 0


def test_package_stays_jax_free_and_submodule_access_works():
    """The arm-before-import-jax contract AND the documented
    `fast_tffm_tpu.training.foo` module-attribute access."""
    r = subprocess.run(
        [
            sys.executable,
            "-c",
            "import sys; sys.path.insert(0, sys.argv[1]); "
            "import fast_tffm_tpu.telemetry; "
            "assert 'jax' not in sys.modules, 'telemetry dragged in jax'; "
            "import fast_tffm_tpu; "
            "assert 'jax' not in sys.modules, 'package import dragged in jax'; "
            "fast_tffm_tpu.telemetry.arm_hang_exit(60, 'x').cancel(); "
            "print('ok')",
            REPO,
        ],
        capture_output=True,
        text=True,
    )
    assert r.returncode == 0 and "ok" in r.stdout, r.stderr
    import fast_tffm_tpu

    assert callable(fast_tffm_tpu.training.scan_max_nnz)  # lazy submodule
    assert callable(fast_tffm_tpu.train)  # lazy function export
    with pytest.raises(AttributeError):
        fast_tffm_tpu.does_not_exist


def test_watchdog_fires_on_frozen_step_hook(dataset):
    """Deterministic stall injection via the existing step_hook: freeze
    the loop past the deadline at one step; the kind=stall record must
    carry thread stacks and the prefetch queue depth."""
    cfg = _train_cfg(dataset, tag="frozen", telemetry_stall_timeout_s=0.2)
    frozen = []

    def hook(step):
        if not frozen and step >= 8:
            frozen.append(step)
            time.sleep(0.7)

    train(cfg, log=lambda *_: None, step_hook=hook)
    records = _read(cfg.metrics_path)
    stalls = [r for r in records if r["kind"] == "stall"]
    assert len(stalls) == 1, stalls
    s = stalls[0]
    assert s["step"] == frozen[0]
    assert s["since_last_step_s"] >= 0.2
    assert s["classification"] in ("input-starved", "device-bound", "unknown")
    assert s["prefetch_queue_depth"] is not None  # streamed input: live depth
    assert s["stacks"] and any("hook" in v or "sleep" in v for v in s["stacks"].values())
    (summary,) = [r for r in records if r["kind"] == "summary"]
    assert summary["stalls"] == 1


def test_nan_divergence_emits_anomaly_record(dataset):
    """lr large enough to blow up the sample problem: the abort must be
    preceded by a structured kind=anomaly record report.py can flag."""
    cfg = _train_cfg(dataset, tag="nan", learning_rate=float("inf"), epoch_num=1)
    with pytest.raises(RuntimeError, match="loss is"):
        train(cfg, log=lambda *_: None)
    records = _read(cfg.metrics_path)
    anomalies = [r for r in records if r["kind"] == "anomaly"]
    assert anomalies, "divergence did not emit kind=anomaly"
    assert anomalies[0]["event"] == "nonfinite_loss"
    # non-finite floats ship as 'nan'/'inf' STRINGS (strict-JSON-safe;
    # float() round-trips them) — and the line must parse under a strict
    # reader, which json.loads with parse_constant verifies.
    assert not np.isfinite(float(anomalies[0]["loss"]))
    assert "table" in anomalies[0]["first_nonfinite"]  # names the tensor
    def _strict(const):
        raise ValueError(f"bare {const} token in JSONL")
    for line in open(cfg.metrics_path):
        json.loads(line, parse_constant=_strict)
    (summary,) = [r for r in records if r["kind"] == "summary"]
    assert summary["anomalies"] >= 1


# -- report tool ----------------------------------------------------------

def test_report_renders_run(dataset):
    cfg = _train_cfg(dataset, tag="rep")
    train(cfg, log=lambda *_: None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "report.py"), cfg.metrics_path],
        capture_output=True,
        text=True,
    )
    assert r.returncode == 0, r.stderr
    for needle in ("Throughput", "Loss", "Events", "steady-state", "Memory"):
        assert needle in r.stdout, f"{needle} missing from report:\n{r.stdout}"


def test_report_compare_gates_throughput_regression(tmp_path):
    """--compare exits nonzero iff throughput degraded past threshold."""
    report = _load_report_module()

    def synth(path, rate, stalls=0):
        mon = RunMonitor(str(path), run_id=new_run_id())
        for i in range(1, 6):
            mon.emit(
                "train", step=i * 4, epoch=0, loss=0.7 - 0.01 * i,
                examples_per_sec=rate, examples_per_sec_per_chip=rate,
            )
        for _ in range(stalls):
            mon.emit(
                "stall", step=8, deadline_s=1, since_last_step_s=2,
                classification="unknown", prefetch_queue_depth=0, stacks={},
            )
        mon.close()
        return str(path)

    base = synth(tmp_path / "base.jsonl", 1000.0)
    slow = synth(tmp_path / "slow.jsonl", 700.0)
    stally = synth(tmp_path / "stall.jsonl", 1000.0, stalls=1)
    tool = os.path.join(REPO, "tools", "report.py")

    def run(*args):
        return subprocess.run(
            [sys.executable, tool, *args], capture_output=True, text=True
        )

    assert run(base, "--compare", base).returncode == 0
    r = run(slow, "--compare", base, "--threshold", "0.15")
    assert r.returncode == 1
    assert "REGRESSED" in r.stdout
    # within threshold: 30% drop tolerated at 0.5
    assert run(slow, "--compare", base, "--threshold", "0.5").returncode == 0
    # --strict gates new stalls even at equal throughput
    assert run(stally, "--compare", base).returncode == 0
    assert run(stally, "--compare", base, "--strict").returncode == 1
    # sanity on the library-level summarize too
    s = report.summarize(report.load_run(base))
    assert s["throughput_median"] == 1000.0 and s["stalls"] == 0

    # gate hole guards: a run with NO throughput records must REGRESS
    # against a base that has them (crashed-before-first-window runs
    # cannot pass the gate)...
    empty = tmp_path / "empty.jsonl"
    RunMonitor(str(empty)).close()  # mem + summary only
    # a monitor no driver handed a device (router, supervisor) says null
    (summ,) = [x for x in _read(str(empty)) if x["kind"] == "summary"]
    assert summ["platform"] is None and summ["device_count"] is None
    assert all(k in summ for k in SCHEMAS["summary"])
    r = run(str(empty), "--compare", base)
    assert r.returncode == 1 and "no train throughput" in r.stdout
    # ...and appended back-to-back runs report only the LAST run
    both = tmp_path / "both.jsonl"
    both.write_text(
        open(base).read() + open(slow).read()
    )
    s2 = report.summarize(report.load_run(str(both)))
    assert s2["runs_in_file"] == 2
    assert s2["throughput_median"] == 700.0  # the later (slow) run only


# -- throughput meter (satellite) ----------------------------------------

def test_throughput_sliding_window():
    """The meter now honors its contract: old samples age out of the
    window instead of being averaged in forever."""
    t = [0.0]
    m = Throughput(window_s=10.0, clock=lambda: t[0])
    m.add(100)
    t[0] = 5.0
    m.add(100)
    assert m.rate() == pytest.approx(40.0)  # 200 examples over 5s
    t[0] = 12.0  # the t=0 sample ages out; window is [2, 12]
    assert m.rate() == pytest.approx(10.0)  # 100 examples over 10s
    t[0] = 30.0  # everything aged out
    assert m.rate() == 0.0
    m.reset()
    m.add(50)
    t[0] = 31.0
    assert m.rate() == pytest.approx(50.0)


def test_throughput_bounded_memory():
    t = [0.0]
    m = Throughput(window_s=1e9, max_samples=16, clock=lambda: t[0])
    for i in range(1000):
        t[0] = float(i)
        m.add(1)
    assert len(m._samples) <= 16
    t[0] = 1000.0
    assert m.rate() == pytest.approx(1.0)  # totals stay exact after merging
