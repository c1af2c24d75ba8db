"""Stage clocks and profiler spans (utils.tracing.span): every stage is one
clock reading summed into a field of a record the program already writes,
and one annotation of the same name on the profiler's host plane."""

import contextlib
import gc
import glob
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from fast_tffm_tpu.config import load_config
from fast_tffm_tpu.models.base import Batch
from fast_tffm_tpu.models.fm import FMModel
from fast_tffm_tpu.serving import ServingEngine
from fast_tffm_tpu.telemetry import CLOCK_PERIOD_S, FREEZE_S
from fast_tffm_tpu.trainer import init_state, make_predict_step, make_train_step
from fast_tffm_tpu.training import train
from fast_tffm_tpu.utils.prefetch import prefetch
from tests.test_e2e import _write_cfg, _write_dataset
from tests.test_serving import NNZ, V, _cfg, _checkpoint

INTERVAL_FIELDS = (
    "interval_s", "interval_flushes", "interval_frames",
    "queue_ms_p50_interval", "compute_ms_p50_interval", "total_ms_p50_interval",
    "assemble_ms", "dispatch_ms", "fetch_ms", "reply_ms",
    "collector_busy_share", "deadline_flush_share",
)
STAGES = ("assemble_ms", "dispatch_ms", "fetch_ms", "reply_ms")
# The host clock's fields (telemetry.RunMonitor.drain_host_clock): time the
# host lost all at once, INSIDE whichever stage was open, never beside them.
HOST_CLOCK_FIELDS = ("freeze_ms", "freezes", "freeze_max_ms", "gc_ms", "gc_collections")


class _Sink:
    """What ``ServingMetrics.log_to`` writes to, kept; the host clock is the
    engine's own monitor's."""

    def __init__(self, monitor=None):
        self.records = []
        if monitor is not None:
            self.drain_host_clock = monitor.drain_host_clock

    def emit(self, kind, **fields):
        self.records.append({"kind": kind, **fields})


def _assert_host_clock_fields(rec, wall_ms):
    """The five fields are there, and what they count lies inside the wall
    time of the record's interval (a loaded machine may freeze for real)."""
    for k in HOST_CLOCK_FIELDS:
        assert isinstance(rec[k], (int, float)) and rec[k] >= 0, (k, rec)
    assert rec["freeze_max_ms"] <= rec["freeze_ms"] <= wall_ms
    assert (rec["freezes"] == 0) == (rec["freeze_ms"] == 0.0)


def _slow_stages(eng, flush_times, assemble_s=0.004, score_s=0.008):
    """Make a flush long against its bookkeeping (as it is on the chip) and
    time every flush from outside the program's own clocks."""
    assemble, score, units = eng._ladder.assemble_parts, eng._ladder._score, eng._score_units

    def slow_assemble(parts):
        time.sleep(assemble_s)
        return assemble(parts)

    def slow_score(state, batch):
        time.sleep(score_s)
        return score(state, batch)

    def timed_units(pending, t_start):
        out = units(pending, t_start)
        flush_times.append(time.perf_counter() - t_start)
        return out

    eng._ladder.assemble_parts, eng._ladder._score, eng._score_units = slow_assemble, slow_score, timed_units


def _drive(eng, rng, n_blocks, rows=4, per_flush=4):
    """``n_blocks`` frames, ``per_flush`` at a time with a pause between, so
    the collector both flushes and waits.  Returns the wall time."""
    t0 = time.perf_counter()
    for _ in range(n_blocks // per_flush):
        futs = [
            eng.submit_block(rng.integers(0, V, (rows, NNZ)), rng.random((rows, NNZ), np.float32))
            for _ in range(per_flush)
        ]
        for f in futs:
            statuses, scores = f.result(timeout=30)
            assert not statuses.any() and scores.shape == (rows,)
            assert f.flush_seq > 0  # the block recorded the flush it rode
        time.sleep(0.003)
    return time.perf_counter() - t0


def test_serving_interval_records_tile_the_collectors_time(tmp_path):
    cfg = _cfg(tmp_path, serve_buckets=(1, 4, 16), serve_flush_deadline_ms=1.0, serve_metrics_every_s=0.0,
               metrics_path=str(tmp_path / "serve.jsonl"))  # a sink: the engine's monitor runs its clock
    _checkpoint(cfg)
    rng = np.random.default_rng(0)
    flush_times = []
    with ServingEngine(cfg, log=lambda *_: None) as eng:
        sink = _Sink(eng._monitor)
        sink.drain_host_clock()
        _slow_stages(eng, flush_times)
        walls, seen = [], 0
        for n_blocks in (120, 160):
            walls.append(_drive(eng, rng, n_blocks))
            mid = eng.metrics_snapshot()  # a pure read: closes nothing
            assert "interval_s" not in mid
            eng.metrics.log_to(sink)
            rec = sink.records[-1]
            n = rec["interval_flushes"]
            outside = sum(flush_times[seen : seen + n])
            seen += n
            for k in INTERVAL_FIELDS:
                assert isinstance(rec[k], (int, float)) and np.isfinite(rec[k]), (k, rec[k])
            assert rec["frame_in_ms"] is None and rec["interval_frames"] == 0  # no replica reader here
            _assert_host_clock_fields(rec, 1e3 * walls[-1] + 50.0)
            # The four stages tile a flush: their means sum to the flush
            # time as timed from outside, within 2%.
            staged = sum(rec[k] for k in STAGES) * n / 1e3
            assert abs(staged - outside) <= 0.02 * outside, (staged, outside)
            assert rec["assemble_ms"] >= 4.0 and rec["dispatch_ms"] >= 8.0
            # Busy (flushes and their bookkeeping) + wait = the interval, which
            # runs from the first flush to the last: busy holds the flushes
            # and little else, the interval is the drive less its edges.
            busy = rec["collector_busy_share"] * rec["interval_s"]
            assert staged <= busy <= staged + 0.05 * rec["interval_s"], (staged, busy, rec["interval_s"])
            assert 0.3 < rec["collector_busy_share"] < 0.98
            assert 0.85 * walls[-1] <= rec["interval_s"] <= walls[-1]
            # The interval's own p50s: queue under the 1 ms deadline plus a
            # flush ahead, compute = dispatch + fetch of this interval alone.
            assert 8.0 <= rec["compute_ms_p50_interval"] <= 1.3 * (rec["dispatch_ms"] + rec["fetch_ms"])
            assert rec["total_ms_p50_interval"] >= rec["compute_ms_p50_interval"]
            assert 0.0 <= rec["deadline_flush_share"] <= 1.0
        snap = eng.metrics_snapshot()
        # Two consecutive intervals add up to the cumulative counters.
        assert sum(r["interval_flushes"] for r in sink.records) == snap["flushes"] == len(flush_times)
        assert sink.records[-1]["flushes"] == snap["flushes"] and sink.records[-1]["rows"] == (120 + 160) * 4
        # No flush since the last record: the next one carries no interval.
        eng.metrics.log_to(sink)
        assert "interval_s" not in sink.records[-1] and sink.records[-1]["flushes"] == snap["flushes"]
    # The engine's own records (the close record here) carry the fields too.
    own = [json.loads(l) for l in open(tmp_path / "serve.jsonl")]
    own = [r for r in own if r.get("kind") == "serving"]
    assert own and all(set(HOST_CLOCK_FIELDS) <= set(r) for r in own)


def test_histogram_bins_by_arithmetic_as_by_search():
    """``add_many`` works the bin out with a logarithm (it runs a dozen times
    a flush on the collector's thread); the search of the edges it replaced
    is the reference: the same bin, but for a value within rounding of an edge."""
    from fast_tffm_tpu.serving import LatencyHistogram

    rng = np.random.default_rng(3)
    xs = np.concatenate([np.exp(rng.uniform(np.log(2e-6), np.log(300.0), 4000)), [0.0, -1.0, 1e-5, 100.0, 1e9]])
    h = LatencyHistogram()
    want = np.zeros(120, np.int64)
    off_by_one = 0
    for x in xs:
        before = h.counts()
        h.add(float(x))
        got = int(np.flatnonzero(h.counts() - before)[0])
        ref = min(max(int(np.searchsorted(h._edges, x, side="right")) - 1, 0), 119)
        if got != ref:
            assert abs(got - ref) == 1 and min(abs(x / h._edges[max(got, ref)] - 1.0), 1.0) < 1e-9, (x, got, ref)
            off_by_one += 1
        want[ref] += 1
    assert off_by_one <= 5 and h.count == xs.size
    assert np.abs(h.counts() - want).sum() <= 2 * off_by_one


def test_idle_engine_writes_no_interval_record(tmp_path):
    cfg = _cfg(tmp_path, metrics_path=str(tmp_path / "serve.jsonl"))
    _checkpoint(cfg)
    with ServingEngine(cfg, log=lambda *_: None):
        pass
    recs = [json.loads(l) for l in open(tmp_path / "serve.jsonl")]
    serving = [r for r in recs if r.get("kind") == "serving"]
    assert len(serving) == 1 and serving[0]["flushes"] == 0  # the close record
    assert not set(INTERVAL_FIELDS) & set(serving[0])


def _train_cfg(tmp_path):
    rng = np.random.default_rng(0)
    _write_dataset(tmp_path / "train.libsvm", rng, n=400)
    _write_dataset(tmp_path / "valid.libsvm", rng, n=50)
    cfgfile = tmp_path / "run.cfg"
    _write_cfg(cfgfile, tmp_path)
    text = cfgfile.read_text().replace(
        "log_every = 5", f"log_every = 4\nmetrics_path = {tmp_path}/metrics.jsonl\n"
    )
    cfgfile.write_text(text)
    return load_config(str(cfgfile))


def test_train_records_split_the_wall_time_of_a_step(tmp_path):
    cfg = _train_cfg(tmp_path)
    log_t, frames = [], []

    def log(msg):
        if str(msg).startswith("step "):
            log_t.append(time.perf_counter())

    def hook(step_num):
        # The benchmark's train window reads the state and the loss of a
        # step from this frame (benchmark/harness/train.py) until a
        # ``benchmark`` issue hands them to ``step_hook``: the timers go
        # around the dispatch without renaming either.
        f = sys._getframe(1)
        frames.append((f.f_code.co_name, "state" in f.f_locals, "loss" in f.f_locals))

    train(cfg, log=log, step_hook=hook)
    assert frames and set(frames) == {("_run_training", True, True)}
    rows = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    trains = [r for r in rows if r.get("kind") == "train"]
    assert len(trains) == len(log_t) >= 4
    for r in trains:
        for k in ("wait_ms", "dispatch_ms", "host_ms", "sync_ms"):
            assert isinstance(r[k], float) and r[k] >= 0.0, (k, r)
    # From one log point to the next the four fields are the wall time.
    total = wall = 0.0
    for prev, r, t0, t1 in zip(trains, trains[1:], log_t, log_t[1:]):
        steps = r["step"] - prev["step"]
        total += steps * (r["wait_ms"] + r["dispatch_ms"] + r["host_ms"]) + r["sync_ms"]
        wall += 1e3 * (t1 - t0)
        # A freeze is inside the four fields (it stretches whichever was
        # open), so they still tile the window with the clock's beside them.
        _assert_host_clock_fields(r, 1e3 * (t1 - t0) + 50.0)
    assert abs(total - wall) <= 0.05 * wall, (total, wall)
    inputs = [r for r in rows if r.get("kind") == "input"]
    assert inputs and all(isinstance(r["wait_ms"], float) and r["wait_ms"] >= 0.0 for r in inputs)


def _fm_step_inputs(b=32, n=7, vocab=1 << 10, k=4):
    model = FMModel(vocabulary_size=vocab, factor_num=k)
    rng = np.random.default_rng(1)
    batch = Batch(
        labels=jnp.asarray(rng.integers(0, 2, (b,)), jnp.float32),
        ids=jnp.asarray(rng.integers(0, vocab, (b, n)), jnp.int32),
        vals=jnp.asarray(rng.random((b, n)), jnp.float32),
        fields=jnp.zeros((b, 0), jnp.int32),
        weights=jnp.ones((b,), jnp.float32),
    )
    return model, batch


def test_compiled_step_and_scorer_name_their_stages(monkeypatch):
    model, batch = _fm_step_inputs()
    state = init_state(model, jax.random.key(0))
    step = make_train_step(model, 0.05)
    hlo = step.lower(state, batch).compile().as_text()
    for scope in ("fm.gather", "fm.interaction", "fm.loss", "fm.dedup", "fm.tail"):
        assert f"/{scope}" in hlo or f"({scope})" in hlo, scope
    assert "transpose(jvp(fm.interaction))" in hlo  # the backward, by name
    score_hlo = make_predict_step(model).lower(state, batch).compile().as_text()
    assert "fm.gather" in score_hlo and "fm.interaction" in score_hlo

    from fast_tffm_tpu.data.wire import make_spec, make_unpacker

    spec = make_spec(1 << 10, 7, with_vals=True, with_fields=False)
    buf = jnp.zeros((spec.batch_nbytes(8),), jnp.uint8)
    assert "score.unpack" in make_unpacker(spec).lower(buf).compile().as_text()

    # Scopes are metadata: the same step traced with them taken away gives
    # the same bits.
    named_state, named_loss = step(state, batch)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = make_train_step(model, 0.05)
    assert "fm.tail" not in bare.lower(init_state(model, jax.random.key(0)), batch).compile().as_text()
    bare_state, bare_loss = bare(init_state(model, jax.random.key(0)), batch)
    assert np.asarray(named_loss).tobytes() == np.asarray(bare_loss).tobytes()
    for a, b in zip(jax.tree.leaves(named_state), jax.tree.leaves(bare_state)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _host_events(trace_dir):
    """{thread line: [(name, start_ns, end_ns)]} of the xplane's host plane."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert paths, "the profiler wrote no xplane"
    lines = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):  # one line a thread
                lines.setdefault((plane.name, i, line.name), []).extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events
                )
    return lines


def test_spans_lie_on_the_profilers_host_plane(tmp_path):
    """Under a profiler session the replica's reader, the collector's stages
    and the input wait are events of the xplane's host plane, children
    inside their parents; the replica's close record holds the reader's clock."""
    import socket

    from fast_tffm_tpu.serving.protocol import (
        decode, encode, pack_request_frame, read_frame, unpack_scores_frame,
    )
    from fast_tffm_tpu.serving.replica import run_replica

    cfg = _cfg(tmp_path, metrics_path=str(tmp_path / "serve.jsonl"), serve_flush_deadline_ms=1.0)
    _checkpoint(cfg)
    ready = type("Ready", (), {"port": None, "write": lambda self, s: setattr(
        self, "port", int(dict(kv.split("=", 1) for kv in s.split() if "=" in kv)["port"])) if "port=" in s else None,
        "flush": lambda self: None})()
    replica = threading.Thread(target=run_replica, args=(cfg,), kwargs={"log": lambda *_: None, "ready_out": ready}, daemon=True)
    replica.start()
    deadline = time.time() + 120
    while ready.port is None and time.time() < deadline:
        time.sleep(0.02)
    assert ready.port, "the replica did not come up"

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        rng = np.random.default_rng(2)
        with socket.create_connection(("127.0.0.1", ready.port), timeout=30) as sock:
            rf = sock.makefile("rb")
            sock.sendall(encode({"id": 0, "op": "hello", "wire": "binary"}))
            assert decode(rf.readline())["wire"] == "binary"
            for i in range(6):
                req = np.arange(4, dtype=np.uint32) + 100 * (i + 1)
                sock.sendall(pack_request_frame(req, rng.integers(0, V, (4, NNZ)), rng.random((4, NNZ), np.float32)))
                kind, _, count, _, payload = read_frame(rf)
                got, statuses, _ = unpack_scores_frame(count, payload)
                assert list(got) == list(req) and not statuses.any()
        assert list(prefetch(iter(range(5)), depth=2)) == list(range(5))
        # What the host loses all at once, made for real: a full collection,
        # and one C call that holds the interpreter lock past the clock's
        # threshold by a period and more (the replica's monitor has a sink, so
        # its clock runs).
        gc.collect()
        xs = list(np.random.default_rng(3).random(400_000))
        t_end = time.perf_counter() + 20.0
        while True:
            t0 = time.perf_counter()
            sorted(xs)
            if time.perf_counter() - t0 >= FREEZE_S + 2 * CLOCK_PERIOD_S or time.perf_counter() > t_end:
                break
            xs = xs + xs
        time.sleep(4 * CLOCK_PERIOD_S)
    finally:
        jax.profiler.stop_trace()
    with socket.create_connection(("127.0.0.1", ready.port), timeout=30) as sock:
        sock.sendall(encode({"id": 1, "op": "close"}))
        sock.makefile("rb").readline()
    replica.join(timeout=60)
    assert not replica.is_alive()

    lines = _host_events(trace_dir)
    names = {n for evs in lines.values() for n, _, _ in evs}
    for want in ("serve.frame_in", "serve.collect_wait", "serve.flush", "serve.assemble", "serve.dispatch",
                 "serve.fetch", "serve.reply", "input.wait", "host.gc", "host.freeze"):
        assert want in names, (want, sorted(n for n in names if "." in n)[:40])
    collector = next(evs for evs in lines.values() if any(n == "serve.flush" for n, _, _ in evs))
    flushes = [(s, e) for n, s, e in collector if n == "serve.flush"]
    for child in ("serve.assemble", "serve.dispatch", "serve.fetch", "serve.reply"):
        spans = [(s, e) for n, s, e in collector if n == child]
        assert spans and all(any(fs <= s and e <= fe for fs, fe in flushes) for s, e in spans), child
    assert not any(n == "serve.frame_in" for n, _, _ in collector)  # the reader's thread, not the collector's

    close = [json.loads(l) for l in open(str(tmp_path / "serve.jsonl") + ".r0")]
    close = [r for r in close if r.get("kind") == "serving"][-1]
    assert close["frames"] == close["interval_frames"] == 6 and close["frame_in_ms"] > 0.0
    assert close["interval_flushes"] == close["flushes"] == 6
