"""Replica worker: one ServingEngine behind a socket, spoken to by the
router.

The deployment unit of the replicated serving tier: a process that owns
ONE engine (its own jit cache, admission queue, telemetry monitor) and
answers the wire protocol (protocol.py) on a TCP socket.  Launched by
serving/router.py (or by hand for debugging):

    python -m fast_tffm_tpu.serving.replica run.cfg --replica 0 --port 0

On startup it binds (``--port 0`` = ephemeral), warms the bucket ladder,
and only THEN prints the readiness line the router blocks on::

    REPLICA_READY port=<port> pid=<pid> platform=<platform> chip=<chip>

so a replica is never routed to before its compile ladder is warm (a
cold replica would pay XLA compiles at p99), and the jax-free router can
say which platform — and which chip — each of its workers landed on
(``chip`` is the ``TPU_VISIBLE_CHIPS`` the router pinned it with, ``all``
when unpinned: a pinned worker sees a one-device world in which every
chip calls itself device 0 at (0,0,0), so jax cannot tell them apart).
Ops beyond ``score``:

  * ``ping``   → engine.health() (queue depth, oldest queued wait — the
    router's wedge signal — last flush age, steady compiles);
  * ``reload`` → one engine.reload_once() tick, run on a dedicated
    thread so scoring keeps flowing during a multi-second full restore;
    the ack carries the outcome (noop/staged/staged_delta/failed);
  * ``stats``  → engine.metrics_snapshot() + compile counts;
  * ``slow``   → engine.inject_slow (chaos replica_slow@N:ms);
  * ``close``  → drain and exit 0.

The engine's own reload watcher is forced OFF here
(serve_reload_interval_s = 0): the router owns the ONE checkpoint
watcher and fans reload commands out, so each published delta is applied
exactly once per replica instead of N watchers racing the filesystem.

Every admitted request gets exactly one response line — scoring errors,
overload, deadline expiry, and parse errors all map to typed codes
(protocol.error_response); the socket is never just dropped.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import socket
import sys
import threading
import time

import numpy as np

from fast_tffm_tpu.serving.protocol import (
    FRAME_KIND_REQUEST,
    FRAME_STATUS_CODES,
    REPLICA_READY_PREFIX,
    BadRequest,
    decode,
    encode,
    error_response,
    exc_code,
    pack_error_frame,
    pack_scores_frame,
    read_frame_header,
    read_frame_payload,
    unpack_request_frame,
)
from fast_tffm_tpu.utils.tracing import span

__all__ = ["run_replica", "main"]


class _Conn:
    """One connection (router, or an affinity-pinned client): reader loop
    + a write lock (score futures resolve on the collector thread, acks
    on the reader/reload threads — whole writes must not interleave).

    A connection starts in JSONL mode; a ``{"op": "hello", "wire":
    "binary"}`` line upgrades it to the binary DATA frame protocol
    (protocol.py) when ``serve_wire`` allows — the negotiated-fallback
    contract: a server pinned to jsonl acks the hello WITHOUT the
    upgrade and the client keeps speaking lines."""

    def __init__(self, sock: socket.socket, engine, log, wire: str = "binary"):
        self._sock = sock
        self._engine = engine
        self._log = log
        self._wire = wire
        self._upgraded = False
        self._wlock = threading.Lock()
        self._reload_lock = threading.Lock()  # one reload at a time

    def send(self, obj: dict) -> None:
        self.send_bytes(encode(obj))

    def send_bytes(self, data: bytes) -> None:
        try:
            with self._wlock:
                # analysis: ok blocking-under-lock the peer is the ROUTER, which reads eagerly on a dedicated reader thread; if it wedges, its own health layer SIGKILLs this replica (wedge conjunction) or closes the socket, which unblocks sendall with OSError — a settimeout here would also bound the reader loop sharing this socket
                self._sock.sendall(data)
        except OSError:
            pass  # router gone; its reconnect (or our exit) handles it

    def _score(self, msg: dict) -> None:
        req_id = msg.get("id")
        fut = self._engine.submit_line(
            str(msg["line"]),
            klass=str(msg.get("class", "") or ""),
            deadline_ms=msg.get("deadline_ms"),
            deadline_at=msg.get("deadline_at"),
        )

        def done(f, req_id=req_id):
            exc = f.exception()
            if exc is None:
                self.send({"id": req_id, "score": float(f.result())})
            else:
                self.send(error_response(req_id, exc))

        fut.add_done_callback(done)

    def _reload(self, msg: dict) -> None:
        def work():
            with self._reload_lock:
                try:
                    out = self._engine.reload_once()
                except Exception as e:  # a reload crash must not kill the worker
                    out = {"status": "failed", "error": repr(e)}
            self.send({"id": msg.get("id"), "ok": True, "op": "reload", **out})

        threading.Thread(target=work, name="replica-reload", daemon=True).start()

    def handle(self, msg: dict) -> bool:
        """Dispatch one request; False = close this worker."""
        req_id = msg.get("id")
        if "line" in msg:
            self._score(msg)
            return True
        op = msg.get("op")
        if op == "hello":
            want = str(msg.get("wire", "jsonl") or "jsonl").lower()
            granted = "binary" if (want == "binary" and self._wire == "binary") else "jsonl"
            self.send(
                {
                    "id": req_id,
                    "ok": True,
                    "op": "hello",
                    "wire": granted,
                    "max_frame_rows": self._engine.max_batch,
                    "max_nnz": self._engine.max_nnz,
                    "fields": self._engine.uses_fields,
                }
            )
            if granted == "binary":
                # The ack is the LAST JSONL on this connection; everything
                # after it is frames (serve() switches reader loops).
                self._upgraded = True
        elif op == "ping":
            self.send({"id": req_id, "ok": True, "op": "ping", **self._engine.health()})
        elif op == "stats":
            self.send(
                {
                    "id": req_id,
                    "ok": True,
                    "op": "stats",
                    "pid": os.getpid(),
                    "engine": self._engine.metrics_snapshot(),
                    "compile_count": self._engine.compile_count(),
                    **self._engine.health(),
                }
            )
        elif op == "slow":
            self._engine.inject_slow(
                float(msg.get("ms", 0.0)), int(msg.get("flushes", 1))
            )
            self.send({"id": req_id, "ok": True, "op": "slow"})
        elif op == "reload":
            self._reload(msg)
        elif op == "close":
            self.send({"id": req_id, "ok": True, "op": "close"})
            return False
        else:
            self.send(error_response(req_id, ValueError(f"unknown op {op!r}")))
        return True

    def serve(self) -> bool:
        """Read until EOF; True = a ``close`` op asked the worker to exit."""
        buf = self._sock.makefile("rb")
        for line in buf:
            line = line.strip()
            if not line:
                continue
            try:
                msg = decode(line)
            except Exception as e:
                self.send(error_response(None, e))
                continue
            try:
                if not self.handle(msg):
                    return True
            except Exception as e:
                # submit_line raising (overload, parse, closed engine) —
                # typed response, never a dropped line.
                self.send(error_response(msg.get("id"), e))
            if self._upgraded:
                return self._serve_frames(buf)
        return False

    def _answer_all(self, req_ids: np.ndarray, code: str) -> None:
        """One SCORES frame failing every row of a frame with ``code`` —
        how whole-frame errors (overload, closed engine, a died flush)
        stay typed and per-request on the binary wire."""
        n = int(req_ids.size)
        self.send_bytes(
            pack_scores_frame(
                req_ids,
                np.full(n, FRAME_STATUS_CODES.index(code), np.uint8),
                np.zeros(n, np.float32),
            )
        )

    def _serve_frames(self, buf) -> bool:
        """Binary DATA loop (post-hello).  Torn input never hangs or
        silently drops the socket: an undecodable PAYLOAD (header intact,
        stream still synced) gets an ERROR frame and the loop continues;
        a broken HEADER or a payload cut short (framing lost — resync is
        impossible on a byte stream) gets an ERROR frame and THEN the
        connection closes.

        The wait for a header is idle time; from the header on the frame
        is ``serve.frame_in`` (payload read, decode, admission), a span
        and a clock of the engine's metrics."""
        while True:
            try:
                hdr = read_frame_header(buf)
            except BadRequest as e:
                self.send_bytes(pack_error_frame("bad_request", str(e)))
                return False
            if hdr is None:
                return False  # clean EOF at a frame boundary
            t_in = time.perf_counter()
            with span("serve.frame_in", req_id=_first_req_id(buf, hdr[4]), rows=hdr[2]):
                synced = self._take_frame(buf, *hdr)
            self._engine.metrics.on_frame_in(time.perf_counter() - t_in)
            if not synced:
                return False

    def _take_frame(self, buf, kind, flags, count, width, payload_len) -> bool:
        """Read, decode and admit one frame; False when framing is lost."""
        try:
            payload = read_frame_payload(buf, payload_len)
        except BadRequest as e:
            self.send_bytes(pack_error_frame("bad_request", str(e)))
            return False
        if kind != FRAME_KIND_REQUEST:
            self.send_bytes(
                pack_error_frame("bad_request", f"unexpected frame kind {kind}")
            )
            return True
        try:
            d = unpack_request_frame(flags, count, width, payload)
        except BadRequest as e:
            self.send_bytes(pack_error_frame("bad_request", str(e)))
            return True
        req_ids = d["req_ids"]
        try:
            fut = self._engine.submit_block(
                d["ids"],
                d["vals"],
                d["fields"],
                deadlines_ms=d["deadlines_ms"],
                classes=d["classes"],
            )
        except Exception as e:
            self._answer_all(req_ids, exc_code(e))
            return True

        def done(f):  # on the collector's thread, inside ``serve.reply``
            exc = f.exception()
            if exc is None:
                statuses, scores = f.result()
                self.send_bytes(pack_scores_frame(req_ids, statuses, scores))
            else:
                self._answer_all(req_ids, exc_code(exc))

        fut.add_done_callback(done)
        return True


def _first_req_id(buf, payload_len: int) -> int:
    """The first row's request id, read ahead of the payload (its first
    four bytes) so that ``serve.frame_in`` can carry it from its start;
    -1 when the reader has not buffered that far."""
    if payload_len < 4:
        return -1
    head = buf.peek(4)
    return int.from_bytes(head[:4], "little") if len(head) >= 4 else -1


def run_replica(
    cfg,
    *,
    replica: int = 0,
    port: int = 0,
    host: str = "127.0.0.1",
    log=None,
    ready_out=None,
) -> int:
    """Build the engine, bind, announce readiness, serve until the
    router sends ``close`` (or the process is killed — that IS a chaos
    scenario the router recovers from)."""
    from fast_tffm_tpu.serving.engine import ServingEngine

    log = log or (lambda *a: print(f"replica {replica}:", *a, file=sys.stderr))
    ready_out = ready_out or sys.stdout
    # Router owns reload fan-out (one watcher, N appliers), and the
    # socket tier always SHEDS under overload: a block-policy submit
    # would wedge the reader thread (pings included), making an
    # overloaded replica indistinguishable from a dead one to the
    # router's health checks.  The typed `overloaded` response IS the
    # backpressure signal on the wire; `block` remains the pipe-mode
    # (stdin serve_lines) policy.
    overrides = {"serve_reload_interval_s": 0.0, "serve_overload": "reject"}
    if cfg.metrics_path:
        # Per-replica JSONL sibling: cross-process appends to one file
        # interleave partial lines; report.py merges the siblings instead.
        overrides["metrics_path"] = f"{cfg.metrics_path}.r{replica}"
    cfg = dataclasses.replace(cfg, **overrides)
    srv = socket.create_server((host, port))
    engine = ServingEngine(cfg, log=log, replica=replica)
    actual = srv.getsockname()[1]
    print(
        f"{REPLICA_READY_PREFIX}port={actual} pid={os.getpid()} "
        f"platform={engine.device['platform']} "
        f"chip={os.environ.get('TPU_VISIBLE_CHIPS') or 'all'}",
        file=ready_out,
        flush=True,
    )
    log(f"listening on {host}:{actual}")
    close_evt = threading.Event()
    try:
        srv.settimeout(0.5)
        # Thread per connection: the router holds TWO — a DATA connection
        # (scores) and a CONTROL connection (ping/reload/slow/stats) — so
        # health checks are never queued behind a score-parse backlog; an
        # overloaded replica answers pings promptly and sheds typed
        # instead of reading as wedged.
        def serve_conn(conn):
            try:
                if _Conn(conn, engine, log, wire=cfg.serve_wire).serve():
                    close_evt.set()
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

        while not close_evt.is_set():
            try:
                conn, peer = srv.accept()
            except TimeoutError:
                continue
            except OSError:
                break
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(
                target=serve_conn, args=(conn,), daemon=True
            ).start()
    finally:
        try:
            srv.close()
        finally:
            engine.close()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="fast_tffm_tpu.serving.replica",
        description="serving replica worker (spawned by the router)",
    )
    ap.add_argument("config", help="INI config file")
    ap.add_argument("--replica", type=int, default=0, metavar="N")
    ap.add_argument("--port", type=int, default=0, metavar="P",
                    help="listen port (0 = ephemeral, announced on stdout)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--run-id", default=None, metavar="ID")
    ap.add_argument("--metrics-path", default=None, metavar="PATH")
    args = ap.parse_args(argv)

    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.telemetry import enable_compilation_cache

    cfg = load_config(args.config)
    # Same helper, same precedence as cli.main: the replica's ladder warmup
    # reads the programs an earlier replica (or CLI run) already compiled.
    enable_compilation_cache(cfg.telemetry_compilation_cache_dir)
    if args.metrics_path is not None:
        cfg.metrics_path = args.metrics_path
    if args.run_id is not None:
        cfg.telemetry_run_id = args.run_id
    return run_replica(cfg, replica=args.replica, port=args.port, host=args.host)


if __name__ == "__main__":
    sys.exit(main())
