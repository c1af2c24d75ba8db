"""Replicated serving tier (ISSUE 8): router failover, tiered shed,
deadline drops, reload fan-out, and the socket protocol.

The deterministic (not-slow) tests drive the REAL Router against FAKE
replica workers — tiny thread-backed socket servers with deterministic
scoring and scriptable deaths — so failover ordering, retry-once, and
fan-out counts are exact, with no jax and no subprocesses.  Engine-level
admission behavior (tiered eviction, deadline shed before padding) runs
a real single engine.  The slow e2e test at the bottom SIGKILLs a real
replica process behind a real front end.
"""

import json
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from fast_tffm_tpu.config import Config, validate_classes
from fast_tffm_tpu.resilience import FaultPlan
from fast_tffm_tpu.serving import AdmissionQueue, OverloadError
from fast_tffm_tpu.serving.protocol import (
    BadRequest,
    DeadlineExceeded,
    Unavailable,
    decode,
    encode,
    error_response,
    exc_code,
)
from fast_tffm_tpu.serving.router import Router

V = 128
NNZ = 6


def _cfg(tmp_path, **kw):
    kw.setdefault("model", "fm")
    kw.setdefault("factor_num", 4)
    kw.setdefault("vocabulary_size", V)
    kw.setdefault("max_nnz", NNZ)
    kw.setdefault("model_file", str(tmp_path / "m.ckpt"))
    kw.setdefault("serve_buckets", (1, 4, 16))
    kw.setdefault("serve_flush_deadline_ms", 20.0)
    return Config(**kw).validate()


def _checkpoint(cfg, shift=0.5, step=0):
    import jax

    from fast_tffm_tpu.checkpoint import save_checkpoint
    from fast_tffm_tpu.config import build_model
    from fast_tffm_tpu.trainer import init_state

    model = build_model(cfg)
    state = init_state(model, jax.random.key(0), cfg.init_accumulator_value)
    state = state._replace(table=state.table + shift, step=state.step + step)
    save_checkpoint(cfg.model_file, state)
    return state


# ---------------------------------------------------------------------------
# protocol + config units
# ---------------------------------------------------------------------------


def test_wire_codes_and_error_mapping():
    assert exc_code(DeadlineExceeded("late")) == "deadline"
    assert exc_code(Unavailable("gone")) == "unavailable"
    assert exc_code(BadRequest("bad")) == "bad_request"
    assert exc_code(OverloadError("full")) == "overloaded"  # by name, no import
    assert exc_code(ValueError("parse")) == "bad_request"
    assert exc_code(RuntimeError("boom")) == "unavailable"
    r = error_response(7, DeadlineExceeded("late"))
    assert r == {"id": 7, "code": "deadline", "error": "late"}
    assert decode(encode({"id": 1, "line": "x"})) == {"id": 1, "line": "x"}
    with pytest.raises(BadRequest):
        decode(b"not json")
    with pytest.raises(BadRequest):
        decode(b"[1, 2]")


def test_serve_classes_config_parsing_and_validation():
    assert validate_classes("gold:2,std:1") == (("gold", 2), ("std", 1))
    assert validate_classes("") == ()
    assert validate_classes((("a", 1),)) == (("a", 1),)
    for bad in ("gold", "gold:-1", "gold:x", ":1", "gold:1,gold:2"):
        with pytest.raises(ValueError):
            validate_classes(bad)
    with pytest.raises(ValueError):
        Config(serve_port=70000).validate()
    with pytest.raises(ValueError):
        Config(serve_replicas=0).validate()
    with pytest.raises(ValueError):
        Config(serve_deadline_ms=-1).validate()


def test_serving_fault_kinds_parse_and_pin():
    plan = FaultPlan.parse("replica_kill@0,replica_slow@1:150,reload_corrupt@0")
    # Events sort by (at, kind) — the schedule is deterministic.
    assert plan.serving_events() == [
        {"kind": "reload_corrupt", "at": 0},
        {"kind": "replica_kill", "at": 0},
        {"kind": "replica_slow", "at": 1, "until": 150},
    ]
    # replica indices may be 0; training kinds still start at 1.
    with pytest.raises(ValueError):
        FaultPlan.parse("kill@0")
    with pytest.raises(ValueError):
        FaultPlan.parse("replica_slow@1")  # latency is mandatory
    with pytest.raises(ValueError):
        FaultPlan.parse("replica_kill@1:5")  # no window for kills
    # Seeded schedules stay byte-identical per seed (appended kinds).
    a = FaultPlan.parse("random:replica_kill=1,replica_slow=1", seed=9).to_json()
    b = FaultPlan.parse("random:replica_kill=1,replica_slow=1", seed=9).to_json()
    assert a == b


# ---------------------------------------------------------------------------
# admission queue units (tiered shed ordering)
# ---------------------------------------------------------------------------


class _Item:
    def __init__(self, name, t_submit=None):
        self.name = name
        self.t_submit = time.perf_counter() if t_submit is None else t_submit

    def __repr__(self):
        return f"_Item({self.name})"


def test_admission_queue_fifo_and_bounds():
    q = AdmissionQueue(2)
    q.put_nowait(_Item("a"), tier=0)
    q.put_nowait(_Item("b"), tier=0)
    with pytest.raises(queue.Full):
        q.put_nowait(_Item("c"), tier=0)  # equal tier never evicts
    assert q.get_nowait().name == "a"  # FIFO
    assert q.get_nowait().name == "b"
    with pytest.raises(queue.Empty):
        q.get_nowait()


def test_admission_queue_evicts_lowest_tier_oldest_first():
    q = AdmissionQueue(3)
    q.put_nowait(_Item("std-old"), tier=1)
    q.put_nowait(_Item("free"), tier=0)
    q.put_nowait(_Item("std-new"), tier=1)
    # Full.  A gold arrival evicts the LOWEST tier present (free), not
    # the oldest overall.
    evicted = q.put_nowait(_Item("gold1"), tier=2)
    assert evicted.name == "free"
    # Next gold: lowest tier present is now 1; oldest of it goes first.
    evicted = q.put_nowait(_Item("gold2"), tier=2)
    assert evicted.name == "std-old"
    # A std arrival cannot evict gold or its own tier -> Full.
    with pytest.raises(queue.Full):
        q.put_nowait(_Item("std-late"), tier=1)
    # Service order is arrival order of the survivors (tiers never jump
    # the line — they only decide who gets shed).
    assert [q.get_nowait().name for _ in range(3)] == ["std-new", "gold1", "gold2"]


def test_admission_queue_sentinel_bypasses_bound():
    q = AdmissionQueue(1)
    q.put_nowait(_Item("a"), tier=5)
    q.put_sentinel("CLOSE")  # always admitted, never evicted
    assert q.qsize() == 2
    assert q.get_nowait().name == "a"
    assert q.get_nowait() == "CLOSE"


def test_admission_queue_blocking_put_evicts_lower_tier():
    q = AdmissionQueue(1)
    q.put_nowait(_Item("free"), tier=0)
    evicted = q.put(_Item("gold"), tier=2, timeout=0.5)  # no block needed
    assert evicted.name == "free"
    with pytest.raises(queue.Full):
        q.put(_Item("gold2"), tier=2, timeout=0.05)  # equal tier blocks


# ---------------------------------------------------------------------------
# engine-level: tiered shed + deadline shed before padding
# ---------------------------------------------------------------------------


def _slow_engine(cfg, delay):
    """Engine whose flush sleeps: a submit burst deterministically
    outruns the collector and fills the admission queue."""
    from fast_tffm_tpu.serving import ServingEngine

    eng = ServingEngine(cfg, log=lambda *_: None)
    orig = eng._ladder._score

    def slow(state, batch):
        time.sleep(delay)
        return orig(state, batch)

    eng._ladder._score = slow
    return eng


def test_tiered_shed_evicts_lowest_class_first(tmp_path):
    """Queue full of std traffic + one gold arrival: a std request is
    shed with a typed OverloadError, the gold request is admitted and
    scored — overload degrades by priority, not uniformly."""
    cfg = _cfg(
        tmp_path,
        serve_queue_size=2,
        serve_overload="reject",
        serve_classes="gold:2,std:1",
        serve_flush_deadline_ms=0.0,
    )
    _checkpoint(cfg)
    eng = _slow_engine(cfg, delay=0.05)
    try:
        first = eng.submit_line("1 1:1.0", klass="std")  # occupies the collector
        time.sleep(0.01)
        std = [eng.submit_line(f"1 {i + 2}:1.0", klass="std") for i in range(2)]
        gold = eng.submit_line("1 9:1.0", klass="gold")  # evicts std[0]
        with pytest.raises(OverloadError):
            eng.submit_line("1 20:1.0", klass="std")  # std cannot evict std
        assert isinstance(gold.result(timeout=10), float)
        assert isinstance(first.result(timeout=10), float)
        with pytest.raises(OverloadError):
            std[0].result(timeout=10)  # the evicted one, typed
        assert isinstance(std[1].result(timeout=10), float)
        snap = eng.metrics_snapshot()
        assert snap["evicted"] == 1
        assert snap["sheds_by_class"] == {"std": 2}  # 1 evicted + 1 rejected
    finally:
        eng.close()


def test_deadline_shed_before_padding(tmp_path):
    """Expired requests are shed BEFORE the bucket is chosen: 3 expired +
    1 live flush as a 1-bucket (not 4), the expired futures fail typed,
    and deadline_drops counts them per class."""
    cfg = _cfg(tmp_path, serve_flush_deadline_ms=0.0, serve_classes="gold:1")
    _checkpoint(cfg)
    eng = _slow_engine(cfg, delay=0.08)
    try:
        first = eng.submit_line("1 1:1.0")  # occupies the collector ~80ms
        time.sleep(0.01)
        doomed = [
            eng.submit_line(f"1 {i + 2}:1.0", klass="gold", deadline_ms=1.0)
            for i in range(3)
        ]
        live = eng.submit_line("1 9:1.0")  # no deadline
        assert isinstance(first.result(timeout=10), float)
        for f in doomed:
            with pytest.raises(DeadlineExceeded):
                f.result(timeout=10)
        assert isinstance(live.result(timeout=10), float)
        snap = eng.metrics_snapshot()
        assert snap["deadline_drops"] == 3
        assert snap["deadline_drops_by_class"] == {"gold": 3}
        # Shed-before-padding: the surviving request flushed alone in the
        # 1-bucket; had the expired ones padded the batch it would be 4.
        assert snap["bucket_rows"] == {"1": 2}  # first + live, one row each
        assert snap["rows"] == 2
    finally:
        eng.close()


def test_default_deadline_from_config(tmp_path):
    """serve_deadline_ms applies when a submit carries no deadline, and a
    per-request deadline_ms=0 opts out."""
    cfg = _cfg(tmp_path, serve_flush_deadline_ms=0.0, serve_deadline_ms=1.0)
    _checkpoint(cfg)
    eng = _slow_engine(cfg, delay=0.08)
    try:
        first = eng.submit_line("1 1:1.0", deadline_ms=0)  # opted out
        time.sleep(0.01)
        doomed = eng.submit_line("1 2:1.0")  # inherits 1ms default
        opted_out = eng.submit_line("1 3:1.0", deadline_ms=0)
        assert isinstance(first.result(timeout=10), float)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        assert isinstance(opted_out.result(timeout=10), float)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# router failover against fake replicas (deterministic, no jax)
# ---------------------------------------------------------------------------


def _fake_score(line: str) -> float:
    """Deterministic, replica-independent scoring stand-in."""
    return float(sum(line.encode()) % 1000) / 1000.0


class FakeReplica:
    """Thread-backed replica worker double.  ``die_at_request=N`` makes
    it close the connection upon RECEIVING its Nth score request without
    answering — a death mid-flight."""

    def __init__(
        self, index: int, die_at_request: int | None = None, wedged: bool = False
    ):
        self.index = index
        self.die_at_request = die_at_request
        self.wedged = wedged  # receive scores, never answer; pings report
        #   a stuck collector (no flush progress) — the wedge conjunction
        self.reloads = 0
        self.pings = 0
        self.scored = 0
        self.received = 0
        self.dead = False
        self.pid = None
        self._srv = socket.create_server(("127.0.0.1", 0))
        self.port = self._srv.getsockname()[1]
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()

    # -- ReplicaProcess duck-type -----------------------------------------
    @property
    def returncode(self):
        return -9 if self.dead else None

    def alive(self):
        return not self.dead

    def kill(self):
        self.dead = True
        try:
            self._srv.close()
        except OSError:
            pass

    def wait(self, timeout=None):
        pass

    # -- the fake wire ----------------------------------------------------
    def _serve(self):
        # Thread per connection, like the real worker: the router opens a
        # DATA and a CONTROL connection per replica.
        def one(conn):
            try:
                self._handle(conn)
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

        try:
            while not self.dead:
                try:
                    conn, _ = self._srv.accept()
                except OSError:
                    return
                threading.Thread(target=one, args=(conn,), daemon=True).start()
        except Exception:
            pass

    def _handle(self, conn):
        f = conn.makefile("rb")
        for raw in f:
            raw = raw.strip()
            if not raw:
                continue
            msg = json.loads(raw)
            if "line" in msg:
                self.received += 1
                if self.wedged:
                    continue  # swallowed: the collector never answers
                if (
                    self.die_at_request is not None
                    and self.received >= self.die_at_request
                ):
                    self.kill()
                    return  # close without answering: death mid-flight
                self.scored += 1
                conn.sendall(
                    encode({"id": msg["id"], "score": _fake_score(msg["line"])})
                )
            elif msg.get("op") == "ping":
                self.pings += 1
                conn.sendall(
                    encode(
                        {
                            "id": msg["id"],
                            "ok": True,
                            "op": "ping",
                            "oldest_wait_s": None,
                            "queue_depth": 1 if self.wedged else 0,
                            "last_flush_age_s": 99.0 if self.wedged else 0.01,
                        }
                    )
                )
            elif msg.get("op") == "reload":
                self.reloads += 1
                conn.sendall(
                    encode(
                        {"id": msg["id"], "ok": True, "op": "reload", "status": "staged"}
                    )
                )
            elif msg.get("op") == "stats":
                conn.sendall(
                    encode(
                        {
                            "id": msg["id"],
                            "ok": True,
                            "op": "stats",
                            "scored": self.scored,
                        }
                    )
                )
            elif msg.get("op") == "close":
                conn.sendall(encode({"id": msg.get("id"), "ok": True, "op": "close"}))
                return


def _fake_router(cfg, fakes_log, plan, **kw):
    """Router over FakeReplica launches.  ``plan[index]`` is a list of
    constructor kwargs consumed launch by launch (relaunches pop on)."""

    def launcher(index):
        kws = plan.get(index, [{}])
        kw_i = kws.pop(0) if kws else {}
        fake = FakeReplica(index, **kw_i)
        fakes_log.append(fake)
        return fake

    kw.setdefault("health_interval_s", 0.1)
    kw.setdefault("ping_timeout_s", 1.0)
    kw.setdefault("log", lambda *a: None)
    return Router(cfg, launcher=launcher, **kw)


def test_router_failover_rescored_identically(tmp_path):
    """Replica 0 dies upon receiving a request: the router retries it
    ONCE on replica 1 and the caller sees the SAME score replica 0 would
    have produced — plus a restart with measured MTTR."""
    cfg = _cfg(tmp_path, serve_replicas=2, restart_backoff_s=0.01)
    fakes: list[FakeReplica] = []
    router = _fake_router(
        cfg, fakes, {0: [dict(die_at_request=2), dict()], 1: [dict()]}
    )
    try:
        lines = [f"1 {i + 1}:1.0" for i in range(8)]
        # Round-robin order is deterministic but the victim request isn't
        # known a priori; every future must resolve to the deterministic
        # score either way — the failover is invisible to callers.
        futs = [router.submit(ln) for ln in lines]
        for ln, fut in zip(lines, futs):
            assert fut.result(timeout=10) == pytest.approx(_fake_score(ln)), ln
        snap = router.snapshot()
        # At least the in-flight victim failed over; pipelined requests
        # sent before the EOF was noticed ride the same path (1..3 here).
        assert 1 <= snap["failovers"] <= 3
        assert snap["failed_unanswerable"] == 0
        # The dead fake answered nothing after its death point.
        dead = fakes[0] if fakes[0].dead else fakes[1]
        assert dead.scored < dead.received
        # Restart: a fresh fake took slot 0 and went healthy, MTTR on the
        # books.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if all(s.state == "healthy" for s in router.slots):
                break
            time.sleep(0.05)
        snap = router.snapshot()
        assert [s["state"] for s in snap["replicas"]] == ["healthy", "healthy"]
        assert snap["replicas"][0]["restarts"] == 1
        assert len(snap["mttr_s"]) == 1 and snap["mttr_s"][0] > 0
        # And the tier keeps scoring after recovery.
        assert router.submit("1 50:1.0").result(timeout=10) == pytest.approx(
            _fake_score("1 50:1.0")
        )
    finally:
        router.close()


def test_router_retry_is_once_then_typed_unavailable(tmp_path):
    """Both replicas die on arrival: the request is retried exactly once
    and then fails TYPED (unavailable) — never a hang."""
    cfg = _cfg(tmp_path, serve_replicas=2, restart_max=0)
    fakes: list[FakeReplica] = []
    router = _fake_router(
        cfg,
        fakes,
        {0: [dict(die_at_request=1)], 1: [dict(die_at_request=1)]},
    )
    try:
        fut = router.submit("1 1:1.0")
        with pytest.raises(Unavailable):
            fut.result(timeout=10)
        snap = router.snapshot()
        assert snap["failed_unanswerable"] >= 1
    finally:
        router.close()


def test_router_no_healthy_replica_fails_fast(tmp_path):
    cfg = _cfg(tmp_path, serve_replicas=1, restart_max=0)
    fakes: list[FakeReplica] = []
    router = _fake_router(cfg, fakes, {0: [dict()]})
    try:
        fakes[0].kill()
        deadline = time.monotonic() + 10
        while router.slots[0].state == "healthy" and time.monotonic() < deadline:
            time.sleep(0.05)
        fut = router.submit("1 1:1.0")
        with pytest.raises(Unavailable):
            fut.result(timeout=5)
    finally:
        router.close()


def test_router_restart_budget_gives_up(tmp_path):
    """restart_max bounds relaunches; the slot parks in `failed` and the
    survivor keeps serving."""
    cfg = _cfg(tmp_path, serve_replicas=2, restart_max=0)
    fakes: list[FakeReplica] = []
    router = _fake_router(
        cfg, fakes, {0: [dict(die_at_request=1)], 1: [dict()]}
    )
    try:
        fut = router.submit("1 1:1.0")
        assert fut.result(timeout=10) == pytest.approx(_fake_score("1 1:1.0"))
        deadline = time.monotonic() + 10
        while router.slots[0].state != "failed" and time.monotonic() < deadline:
            time.sleep(0.05)
        assert router.slots[0].state == "failed"
        assert len(fakes) == 2  # no relaunch happened
        assert router.submit("1 2:1.0").result(timeout=10) == pytest.approx(
            _fake_score("1 2:1.0")
        )
    finally:
        router.close()


def test_router_kills_wedged_replica_and_fails_typed(tmp_path):
    """A collector hung AFTER popping its requests (socket chatty, no
    flush progress, router holding unanswered scores) is declared wedged
    — killed, its requests fail TYPED, and a restart brings a healthy
    replacement.  Neither signal alone may fire: old pendings under
    overload or a big flush age on an idle replica are healthy."""
    cfg = _cfg(tmp_path, serve_replicas=1, restart_backoff_s=0.01)
    fakes: list[FakeReplica] = []
    router = _fake_router(
        cfg,
        fakes,
        {0: [dict(wedged=True), dict()]},
        wedge_timeout_s=0.3,
    )
    try:
        fut = router.submit("1 1:1.0")
        with pytest.raises(Unavailable):
            fut.result(timeout=10)  # answered typed, never hung
        assert fakes[0].dead  # the health check SIGKILLed the wedge
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if router.slots[0].state == "healthy":
                break
            time.sleep(0.05)
        assert router.slots[0].state == "healthy"
        assert router.submit("1 2:1.0").result(timeout=10) == pytest.approx(
            _fake_score("1 2:1.0")
        )
    finally:
        router.close()


def test_watcher_fans_out_one_reload_per_write_per_replica(tmp_path):
    """One checkpoint write → exactly ONE reload command on EACH replica
    (the single-watcher contract: deltas apply exactly once per replica,
    not once per racing watcher)."""
    cfg = _cfg(tmp_path, serve_replicas=2, serve_reload_interval_s=0.05)
    _checkpoint(cfg, shift=0.5, step=0)
    fakes: list[FakeReplica] = []
    router = _fake_router(cfg, fakes, {0: [dict()], 1: [dict()]})
    try:
        time.sleep(0.2)  # several watcher ticks: no write, no fan-out
        assert router.reload_fanouts == 0
        assert [f.reloads for f in fakes] == [0, 0]
        _checkpoint(cfg, shift=0.7, step=10)  # ONE new publish
        deadline = time.monotonic() + 10
        while router.reload_fanouts < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        deadline = time.monotonic() + 10
        while (
            any(f.reloads < 1 for f in fakes) and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        time.sleep(0.3)  # several more ticks: still exactly once
        assert router.reload_fanouts == 1
        assert [f.reloads for f in fakes] == [1, 1]
        assert [s.reload_acks for s in router.slots] == [1, 1]
    finally:
        router.close()


# ---------------------------------------------------------------------------
# e2e: real front end + 2 real replicas + SIGKILL (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_e2e_socket_frontend_survives_replica_sigkill(tmp_path):
    """The full production shape, for real: spawn the socket front end
    with 2 replica worker processes, score over TCP, SIGKILL one
    replica mid-traffic, and require (a) every request answered, (b)
    every delivered score bit-identical to the pre-kill score for the
    same line, (c) the replica restarted with a recorded MTTR, (d) zero
    steady-state recompiles on the survivors."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg_path = tmp_path / "run.cfg"
    cfg = _cfg(tmp_path, serve_replicas=2)
    _checkpoint(cfg)
    cfg_path.write_text(
        f"""
[General]
model = fm
factor_num = 4
vocabulary_size = {V}
model_file = {cfg.model_file}

[Train]
max_nnz = {NNZ}

[Serving]
buckets = 1 4 16
flush_deadline_ms = 2
replicas = 2
"""
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "fast_tffm.py"), "serve",
         str(cfg_path), "--port", "0"],
        stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        text=True,
        env=env,
        cwd=repo,
    )
    try:
        port = None
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if line.startswith("SERVE_READY"):
                port = int(line.split("port=")[1].split()[0])
                break
            if proc.poll() is not None:
                break
        assert port is not None, "front end never became ready"
        s = socket.create_connection(("127.0.0.1", port), timeout=30)
        fp = s.makefile("rb")

        def ask(obj, timeout=30.0):
            s.settimeout(timeout)
            s.sendall(encode(obj))
            return json.loads(fp.readline())

        lines = [f"1 {i + 1}:1.0 {i + 10}:2.0" for i in range(12)]
        baseline = {}
        for i, ln in enumerate(lines):
            r = ask({"id": i, "line": ln})
            baseline[r["id"]] = r["score"]
        stats = ask({"id": "s", "op": "stats"})
        pid0 = stats["replicas"][0]["pid"]
        os.kill(pid0, signal.SIGKILL)
        # Pipelined burst across the death: every request must come back,
        # answered (score or typed code), within the timeout.
        n = 40
        for i in range(n):
            s.sendall(
                encode({"id": 1000 + i, "line": lines[i % len(lines)]})
            )
            time.sleep(0.01)
        answered = {}
        s.settimeout(60)
        while len(answered) < n:
            r = json.loads(fp.readline())
            if isinstance(r.get("id"), int) and r["id"] >= 1000:
                answered[r["id"]] = r
        assert len(answered) == n  # zero hung / unanswered
        for rid, r in answered.items():
            if "score" in r:  # every DELIVERED score is bit-identical
                assert r["score"] == baseline[(rid - 1000) % len(lines)], rid
            else:
                assert r["code"] in ("overloaded", "deadline", "unavailable")
        # Replica restarts; MTTR lands in the ping snapshot.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            r = ask({"id": "p", "op": "ping"}, timeout=30)
            if all(rep["state"] == "healthy" for rep in r["replicas"]):
                break
            time.sleep(0.5)
        assert all(rep["state"] == "healthy" for rep in r["replicas"])
        assert len(r["mttr_s"]) == 1 and r["mttr_s"][0] > 0
        stats = ask({"id": "s2", "op": "stats"}, timeout=60)
        for idx, eng in stats["engines"].items():
            assert eng["steady_compiles"] == 0, idx
        s.close()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()


# ---------------------------------------------------------------------------
# binary DATA plane: negotiation, torn frames, block submits (ISSUE 16)
# ---------------------------------------------------------------------------


class _StubBlockEngine:
    """submit_block stand-in for _Conn wire tests: scores row i as
    sum(vals[i]), statuses all ok — deterministic, no jax."""

    max_batch = 64
    max_nnz = 6
    uses_fields = False

    def __init__(self):
        from fast_tffm_tpu.serving.metrics import ServingMetrics

        self.metrics = ServingMetrics()  # the reader's frame_in clock lands here

    def submit_block(self, ids, vals, fields=None, *, deadlines_ms=None, classes=None):
        import concurrent.futures

        vals = np.asarray(vals, np.float32)
        fut: concurrent.futures.Future = concurrent.futures.Future()
        fut.set_result(
            (np.zeros(len(vals), np.uint8), vals.sum(axis=1).astype(np.float32))
        )
        return fut


def _conn_pair(engine, wire="binary"):
    """A replica _Conn served on a thread over a socketpair; returns the
    client socket + its buffered reader + the serve thread."""
    from fast_tffm_tpu.serving.replica import _Conn

    server, client = socket.socketpair()
    conn = _Conn(server, engine, lambda *_: None, wire=wire)
    t = threading.Thread(target=conn.serve, daemon=True)
    t.start()
    client.settimeout(30)
    return client, client.makefile("rb"), t


def test_conn_hello_upgrades_to_frames():
    from fast_tffm_tpu.serving.protocol import (
        FRAME_KIND_SCORES,
        decode,
        encode,
        pack_request_frame,
        read_frame,
        unpack_scores_frame,
    )

    client, rf, _ = _conn_pair(_StubBlockEngine())
    client.sendall(encode({"id": 1, "op": "hello", "wire": "binary"}))
    ack = decode(rf.readline())
    assert ack["wire"] == "binary"
    assert ack["max_frame_rows"] == 64 and ack["max_nnz"] == 6
    vals = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    client.sendall(
        pack_request_frame(
            np.array([7, 8], np.uint32), np.zeros((2, 2), np.int32), vals
        )
    )
    kind, _, count, _, payload = read_frame(rf)
    assert kind == FRAME_KIND_SCORES
    req, st, sc = unpack_scores_frame(count, payload)
    assert list(req) == [7, 8] and list(st) == [0, 0]
    assert list(sc) == [3.0, 7.0]
    client.close()


def test_conn_jsonl_pin_refuses_upgrade():
    """A server pinned serve_wire=jsonl acks the hello WITHOUT the
    upgrade and the connection keeps speaking lines — the negotiated
    fallback the client maps to WireRefused."""
    from fast_tffm_tpu.serving.protocol import decode, encode

    client, rf, _ = _conn_pair(_StubBlockEngine(), wire="jsonl")
    client.sendall(encode({"id": 1, "op": "hello", "wire": "binary"}))
    assert decode(rf.readline())["wire"] == "jsonl"
    client.sendall(encode({"id": 2, "op": "close"}))  # still JSONL: op works
    assert decode(rf.readline())["op"] == "close"
    client.close()


def test_conn_torn_frame_typed_error_never_hung():
    """Payload-level tear (header intact): ERROR frame, stream continues.
    Header-level tear (framing lost): ERROR frame, then the server
    closes — never a hung socket, never a silent drop."""
    from fast_tffm_tpu.serving.protocol import (
        FRAME_HEADER,
        FRAME_KIND_ERROR,
        FRAME_KIND_REQUEST,
        FRAME_KIND_SCORES,
        FRAME_MAGIC,
        FRAME_VERSION,
        decode,
        encode,
        pack_request_frame,
        read_frame,
        unpack_error_frame,
    )

    engine = _StubBlockEngine()
    client, rf, _ = _conn_pair(engine)
    client.sendall(encode({"id": 1, "op": "hello", "wire": "binary"}))
    decode(rf.readline())
    # Header says count=9 rows but the payload bytes can't hold them.
    short = b"\x00" * 32
    client.sendall(
        FRAME_HEADER.pack(
            FRAME_MAGIC, FRAME_VERSION, FRAME_KIND_REQUEST, 0, 9, 4, len(short)
        )
        + short
    )
    kind, _, _, _, payload = read_frame(rf)
    assert kind == FRAME_KIND_ERROR
    assert unpack_error_frame(payload)[0] == "bad_request"
    # Stream still synced: a good frame after the bad payload scores.
    client.sendall(
        pack_request_frame(
            np.array([5], np.uint32),
            np.zeros((1, 2), np.int32),
            np.ones((1, 2), np.float32),
        )
    )
    kind, *_ = read_frame(rf)
    assert kind == FRAME_KIND_SCORES
    # Bad magic = framing lost: typed ERROR, then EOF (connection closed).
    client.sendall(b"GARBAGE!" * 4)
    kind, _, _, _, payload = read_frame(rf)
    assert kind == FRAME_KIND_ERROR
    assert unpack_error_frame(payload)[0] == "bad_request"
    assert read_frame(rf) is None
    client.close()
    # Two frames had a header (the torn payload, the good one), each timed
    # from it; the garbage never became a frame.
    assert engine.metrics.frames == 2 and engine.metrics.frame_in_s > 0.0


def test_frame_connection_wire_refused_falls_back():
    """A front end that won't grant binary+affinity raises WireRefused
    (carrying the ack) instead of limping — the caller's cue to fall
    back to the JSONL ServeConnection."""
    from fast_tffm_tpu.serving.client import FrameConnection, WireRefused
    from fast_tffm_tpu.serving.protocol import decode, encode

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def frontend():
        c, _ = srv.accept()
        msg = decode(c.makefile("rb").readline())
        c.sendall(
            encode({"id": msg.get("id"), "ok": True, "op": "hello",
                    "wire": "jsonl", "affinity": False})
        )
        c.close()

    t = threading.Thread(target=frontend, daemon=True)
    t.start()
    with pytest.raises(WireRefused) as ei:
        FrameConnection(port)
    assert ei.value.ack["wire"] == "jsonl"
    t.join(10)
    srv.close()


def test_submit_block_matches_per_row_submits(tmp_path):
    """One coalesced block == n per-row submits, bitwise: same scores
    for the same rows, with per-row bad ids isolated to their row
    instead of poisoning the frame."""
    from fast_tffm_tpu.data.libsvm import parse_lines
    from fast_tffm_tpu.serving import ServingEngine
    from fast_tffm_tpu.serving.protocol import FRAME_STATUS_CODES

    cfg = _cfg(tmp_path)
    _checkpoint(cfg)
    eng = ServingEngine(cfg, log=lambda *_: None)
    try:
        lines = [f"1 {i + 1}:1.0 {i + 10}:0.5" for i in range(6)]
        per_row = [eng.submit_line(ln).result(timeout=30) for ln in lines]
        pb = parse_lines(lines, vocabulary_size=V, max_nnz=NNZ)
        st, sc = eng.submit_block(
            pb.ids, pb.vals, pb.fields if eng.uses_fields else None
        ).result(timeout=30)
        assert list(st) == [0] * 6
        assert [float(s) for s in sc] == per_row  # bit-identical
        # Row 2 carries an out-of-vocab id: ONLY that row fails, typed.
        bad_ids = pb.ids.copy()
        bad_ids[2, 0] = V + 99
        st2, sc2 = eng.submit_block(bad_ids, pb.vals).result(timeout=30)
        assert FRAME_STATUS_CODES[st2[2]] == "bad_request"
        ok_rows = [i for i in range(6) if i != 2]
        assert [int(st2[i]) for i in ok_rows] == [0] * 5
    finally:
        eng.close()


def test_submit_block_bucket_after_coalesce(tmp_path):
    """Two blocks queued within one flush window coalesce into ONE
    bucket sized for their sum — the occupancy fix.  Per-bucket
    padded_rows/occupancy land in the serving snapshot."""
    from fast_tffm_tpu.serving import ServingEngine

    cfg = _cfg(tmp_path, serve_flush_deadline_ms=200.0)
    _checkpoint(cfg)
    eng = ServingEngine(cfg, log=lambda *_: None)
    try:
        ids = np.arange(1, 7, dtype=np.int32).reshape(3, 2)
        vals = np.ones((3, 2), np.float32)
        f1 = eng.submit_block(ids, vals)
        f2 = eng.submit_block(ids + 10, vals)
        f1.result(timeout=30), f2.result(timeout=30)
        snap = eng.metrics_snapshot()
        # 6 rows in one 16-bucket flush — not two 4-bucket flushes.
        assert snap["flushes"] == 1
        assert snap["bucket_rows"] == {"16": 6}
        assert snap["bucket_padded_rows"] == {"16": 10}
        assert snap["bucket_occupancy"] == {"16": round(6 / 16, 4)}
    finally:
        eng.close()


@pytest.mark.slow
def test_e2e_affinity_failover_scores_bit_identical(tmp_path):
    """The r16 data plane end to end: hello → replica pin → frames
    answered directly by the replica; JSONL and frame scores bitwise
    equal; SIGKILL of the pinned replica → client-driven retry-once-on-
    peer → every re-driven row re-scored BIT-IDENTICALLY, zero hung."""
    from fast_tffm_tpu.data.libsvm import parse_lines
    from fast_tffm_tpu.serving.client import (
        FrameConnection,
        ServeConnection,
        spawn_serve,
    )

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = _cfg(tmp_path, serve_replicas=2)
    _checkpoint(cfg)
    cfg_path = tmp_path / "run.cfg"
    cfg_path.write_text(
        f"""
[General]
model = fm
factor_num = 4
vocabulary_size = {V}
model_file = {cfg.model_file}

[Train]
max_nnz = {NNZ}

[Serving]
buckets = 1 4 16
flush_deadline_ms = 2
replicas = 2
"""
    )
    proc, port = spawn_serve(str(cfg_path), timeout_s=300)
    fc = None
    ops = None
    try:
        lines = [f"1 {i + 1}:1.0 {i + 10}:2.0" for i in range(12)]
        ops = ServeConnection(port)
        base = {
            i: ops.request({"id": 1000 + i, "line": ln}, timeout=60)["score"]
            for i, ln in enumerate(lines)
        }
        pb = parse_lines(lines, vocabulary_size=V, max_nnz=NNZ)
        fc = FrameConnection(port)
        assert fc.replica is not None and fc.replica_port  # affinity granted
        fields = pb.fields if fc.uses_fields else None
        fc.send_batch(np.arange(12, dtype=np.uint32), pb.ids, pb.vals, fields=fields)
        assert not fc.wait_answered(range(12), 120)
        for i in range(12):
            assert fc.results[i] == ("ok", base[i]), i  # bitwise vs JSONL
        # Kill the PINNED replica: the next frame's rows must all resolve
        # via exactly one failover to the peer, scores unchanged.
        stats = ops.request({"id": "s", "op": "stats"}, timeout=60)
        os.kill(stats["replicas"][fc.replica]["pid"], signal.SIGKILL)
        time.sleep(0.2)
        fc.send_batch(
            np.arange(100, 112, dtype=np.uint32), pb.ids, pb.vals, fields=fields
        )
        assert not fc.wait_answered(range(100, 112), 120)  # zero hung
        assert fc.failovers == 1
        for i in range(12):
            assert fc.results[100 + i] == ("ok", base[i]), i
    finally:
        if fc is not None:
            fc.close()
        if ops is not None:
            ops.close()
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
