"""Multi-process SPMD runtime: bring-up, coordination, fault tolerance.

The reference ran a ps/worker TF cluster where any worker could die and
the Supervisor restarted it from the last Saver checkpoint; the SPMD
translation (SNIPPETS.md [3]: "on TPU pods, pjit can run computations
across all available devices across processes") replaces the cluster
with N identical processes driving one global mesh — which makes the
FAILURE story harder, not easier: one host dying must not corrupt the
shared checkpoint chain or desync the survivors.  This module is the
coordination layer that makes the pod survivable:

  * **bring-up** — ``initialize_runtime`` wires the process into the
    pod: ``jax.distributed.initialize`` from either the classic config
    keys (coordinator_address / num_processes / process_id, or TPU
    metadata) or a supervisor-owned *generation file* (see below).  CPU
    pods get gloo collectives switched on automatically — without them
    the CPU backend refuses multi-process computations outright.

  * **DistributedRuntime** — barriers and a tiny cross-process KV store
    (jax's coordination-service store when the distributed client is up,
    a shared-filesystem fallback otherwise, no-ops single-process).
    This is what the checkpoint layer uses for the single-writer publish
    protocol (process 0 writes, everyone barriers on the content
    signature — DESIGN.md invariant 6), what resume uses to verify every
    host restored the same chain head and cursor vector, and what
    finally legalizes ``on_nan = rollback`` under dist_train (the
    rollback barrier: all processes agree, restore the same head, resume
    at the same cursor).

  * **heartbeats + HostMonitor** — every host writes a heartbeat file
    under the shared runtime dir; a monitor thread classifies a stale
    peer as a host-level ``kind=stall`` (heartbeat-lost vs straggler)
    long before jax's own ~100 s coordination-service timeout notices.

  * **generation protocol** — crash recovery for the pod.  jax's
    coordination service cannot re-admit a relaunched process into a
    live cluster (and a dead process 0 takes the coordinator with it),
    so recovery is *generational*: the pod supervisor
    (resilience.Supervisor with ``processes = N``) owns a
    ``generation.json`` naming {generation, coordinator, num_processes}.
    When ONE host dies the supervisor relaunches ONLY that host and
    bumps the generation with a fresh coordinator port; every survivor's
    ``GenerationWatcher`` thread notices the bump and **re-execs the
    process in place** (``os.execv`` — same PID, fresh image, forced
    ``--resume``).  exec-from-a-thread is the one escape hatch that
    works even while the main thread is wedged inside a collective whose
    peer is gone — the standard failure posture of a survivor.  All N
    processes of the new generation then park at the
    ``jax.distributed.initialize`` rendezvous (the restore barrier),
    restore the same chain head, verify signatures + cursor vector
    agreement, and resume — bit-identically, which the pod chaos tests
    pin.

Like resilience.py, this module must import WITHOUT jax (the supervisor
process never touches a device); all jax use is lazy.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time

from fast_tffm_tpu.telemetry import log_quietly

__all__ = [
    "PEER_LOST_EXIT",
    "PeerLostError",
    "DistributedRuntime",
    "FileKV",
    "initialize_runtime",
    "host_metrics_path",
    "free_port",
    "read_generation",
    "write_generation",
    "wait_for_generation",
    "GENERATION_FILE",
    "HeartbeatWriter",
    "HostMonitor",
    "GenerationWatcher",
    "reexec_argv",
    "process_identity",
]

# Exit code a trainer uses when it deliberately dies because a PEER was
# lost (coordination timeout, failed barrier): the supervisor treats it
# as collateral of the incident, not a fresh crash of this host.
PEER_LOST_EXIT = 17

GENERATION_FILE = "generation.json"

# Environment contract between the pod supervisor and its children
# (resilience.Supervisor sets these; initialize_runtime reads them).
ENV_RUNTIME_DIR = "FM_DIST_RUNTIME_DIR"
ENV_PROCESS_ID = "FM_DIST_PROCESS_ID"
ENV_PROCESSES = "FM_DIST_PROCESSES"
ENV_GENERATION = "FM_DIST_GENERATION"


class PeerLostError(RuntimeError):
    """A cross-process barrier / KV wait timed out: a peer host is gone
    (or wedged past the deadline).  The caller should exit with
    PEER_LOST_EXIT — under the pod supervisor the generation bump will
    already be on its way."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def process_identity() -> tuple[int, int]:
    """(process_index, process_count) without forcing a jax backend up:
    jax answers when it is already imported (trainers), the supervisor
    env contract answers for device-free processes, (0, 1) otherwise."""
    if "jax" in sys.modules:
        try:
            import jax
            from jax._src import distributed as _jax_dist

            if _jax_dist.global_state.client is not None:
                return jax.process_index(), jax.process_count()
        # analysis: ok exception-hygiene jax-internal probe: any failure here means "not in a distributed runtime" and the env-var fallback below answers
        except Exception:
            pass
    try:
        return (
            int(os.environ.get(ENV_PROCESS_ID, "0")),
            int(os.environ.get(ENV_PROCESSES, "1")),
        )
    except ValueError:
        return 0, 1


def host_metrics_path(path: str, process_index: int | None = None) -> str:
    """Per-host telemetry JSONL path: the lead keeps ``path`` unchanged
    (every existing reader keeps working), host p > 0 writes
    ``path`` with a ``.p<N>`` inserted before the extension —
    ``run.jsonl`` -> ``run.p1.jsonl``.  tools/report.py merges them."""
    if not path:
        return path
    p = process_identity()[0] if process_index is None else int(process_index)
    if p == 0:
        return path
    root, ext = os.path.splitext(path)
    return f"{root}.p{p}{ext or ''}"


# ---------------------------------------------------------------------------
# generation file (supervisor <-> children)
# ---------------------------------------------------------------------------


def write_generation(runtime_dir: str, info: dict) -> str:
    """Atomically publish a generation record ({generation, coordinator,
    num_processes, cause}) — the supervisor's single source of truth for
    which pod incarnation is current."""
    os.makedirs(runtime_dir, exist_ok=True)
    path = os.path.join(runtime_dir, GENERATION_FILE)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(info, f, sort_keys=True)
    os.replace(tmp, path)
    return path


def read_generation(runtime_dir: str) -> dict | None:
    try:
        with open(os.path.join(runtime_dir, GENERATION_FILE)) as f:
            out = json.load(f)
        return out if isinstance(out, dict) else None
    except (OSError, ValueError):
        return None


def wait_for_generation(
    runtime_dir: str, at_least: int, timeout_s: float = 120.0, poll_s: float = 0.1
) -> dict:
    """Block until the generation file names generation >= ``at_least``
    (a relaunched/re-exec'd child parking until the supervisor has
    published the incarnation it belongs to)."""
    deadline = time.monotonic() + timeout_s
    while True:
        info = read_generation(runtime_dir)
        if info is not None and int(info.get("generation", -1)) >= at_least:
            return info
        if time.monotonic() > deadline:
            raise PeerLostError(
                f"no generation >= {at_least} appeared in {runtime_dir} "
                f"within {timeout_s:.0f}s (supervisor gone?)"
            )
        time.sleep(poll_s)


# ---------------------------------------------------------------------------
# KV backends
# ---------------------------------------------------------------------------


class FileKV:
    """Shared-filesystem KV + barrier: one file per key under ``root``.
    The fallback (and unit-test) backend — the pod's checkpoint chain
    already assumes a shared filesystem, so this adds no new
    requirement.  Barrier = every process publishes a marker and polls
    for all P of them."""

    def __init__(self, root: str, poll_s: float = 0.05):
        self._root = root
        self._poll = poll_s
        os.makedirs(root, exist_ok=True)

    def _path(self, key: str) -> str:
        # Keys are runtime-generated (no user input); keep them readable.
        return os.path.join(self._root, key.replace("/", "_"))

    def set(self, key: str, value: str) -> None:
        path = self._path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(value)
        os.replace(tmp, path)

    def get(self, key: str, timeout_s: float) -> str:
        path = self._path(key)
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                with open(path) as f:
                    return f.read()
            except OSError:
                pass
            if time.monotonic() > deadline:
                raise TimeoutError(f"key {key!r} did not appear in {timeout_s:.0f}s")
            time.sleep(self._poll)

    def barrier(
        self, name: str, timeout_s: float, process_count: int, process_index: int
    ) -> None:
        self.set(f"{name}.{process_index}", "1")
        for p in range(process_count):
            self.get(f"{name}.{p}", timeout_s)


class _JaxKV:
    """jax coordination-service KV + native barrier (multi-host pods —
    no shared-FS round-trips on the hot path)."""

    def __init__(self, client):
        self._client = client

    def set(self, key: str, value: str) -> None:
        self._client.key_value_set(key, value)

    def get(self, key: str, timeout_s: float) -> str:
        try:
            return self._client.blocking_key_value_get(
                key, int(timeout_s * 1000)
            )
        except Exception as e:  # xla raises its own rpc error types
            raise TimeoutError(str(e)) from e

    def barrier(
        self, name: str, timeout_s: float, process_count: int, process_index: int
    ) -> None:
        try:
            self._client.wait_at_barrier(name, int(timeout_s * 1000))
        except Exception as e:
            raise TimeoutError(str(e)) from e


# ---------------------------------------------------------------------------
# the runtime (barriers / signatures / cursor vectors)
# ---------------------------------------------------------------------------


_RUNTIME_ORDINAL = [0]  # process-global DistributedRuntime construction count


class DistributedRuntime:
    """Cross-process coordination for one trainer run.

    Inactive (every method a cheap no-op returning None) when
    single-process or no KV backend is reachable — drivers call it
    unconditionally.  All methods must be called in the SAME order on
    every process (they are: every call site is step/boundary
    deterministic); keys self-namespace with per-tag counters plus an
    epoch namespace (``advance_namespace`` — bumped between rollback
    attempts so a fresh AsyncCheckpointer's sequence numbers can never
    collide with the aborted attempt's).

    A timed-out wait raises :class:`PeerLostError` — under the pod
    supervisor the survivor is normally re-exec'd before ever seeing it.
    """

    # Bring-up attachments (initialize_runtime sets them when present).
    runtime_dir: str | None = None
    heartbeat = None
    watcher = None

    def __init__(
        self,
        process_index: int = 0,
        process_count: int = 1,
        kv=None,
        *,
        barrier_timeout_s: float = 120.0,
        log=print,
        instance: int | None = None,
    ):
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self._kv = kv
        self.barrier_timeout_s = float(barrier_timeout_s)
        self._log = log
        self._ns = 0
        self._counters: dict[str, int] = {}
        # KV keys are write-once; a process may construct several runtimes
        # against ONE coordination service (dist_train then dist_predict,
        # or a resume in the same process).  Runtime construction is a
        # lock-step SPMD event, so a process-global instance ordinal keeps
        # every instance's keyspace disjoint AND matched across hosts.
        # (Tests simulating several hosts in one process pass ``instance``
        # explicitly.)
        if instance is None:
            _RUNTIME_ORDINAL[0] += 1
            instance = _RUNTIME_ORDINAL[0]
        self._instance = int(instance)

    # -- construction ------------------------------------------------------

    @classmethod
    def create(
        cls, *, barrier_timeout_s: float = 120.0, runtime_dir: str | None = None, log=print
    ) -> "DistributedRuntime":
        """The driver-facing constructor: jax KV when the distributed
        client is up, FileKV under ``runtime_dir`` otherwise, inert for
        single-process runs."""
        import jax

        n = jax.process_count()
        if n <= 1:
            return cls(0, 1, None, barrier_timeout_s=barrier_timeout_s, log=log)
        try:
            from jax._src import distributed as _jax_dist

            client = _jax_dist.global_state.client
        # analysis: ok exception-hygiene jax-internal probe: no coordination client means the FileKV fallback below takes over
        except Exception:
            client = None
        if client is not None:
            kv = _JaxKV(client)
        elif runtime_dir:
            kv = FileKV(os.path.join(runtime_dir, "kv"))
        else:
            kv = None
        if kv is None:
            log(
                "warning: multi-process run with no coordination backend — "
                "save-signature barriers disabled (set [Distributed] "
                "runtime_dir for the shared-filesystem fallback)"
            )
        return cls(
            jax.process_index(), n, kv, barrier_timeout_s=barrier_timeout_s, log=log
        )

    @property
    def active(self) -> bool:
        return self.process_count > 1 and self._kv is not None

    @property
    def is_lead(self) -> bool:
        return self.process_index == 0

    def advance_namespace(self) -> None:
        self._ns += 1
        self._counters.clear()

    def _next(self, tag: str) -> int:
        n = self._counters.get(tag, 0)
        self._counters[tag] = n + 1
        return n

    def _key(self, *parts) -> str:
        return "/".join(
            (f"fmr{self._instance}", str(self._ns), *map(str, parts))
        )

    # -- primitives --------------------------------------------------------

    def barrier(self, tag: str) -> None:
        """Rendezvous: returns once every process has called the same
        (order-matched) barrier."""
        if not self.active:
            return
        name = self._key("b", tag, self._next(f"b:{tag}"))
        try:
            self._kv.barrier(
                name, self.barrier_timeout_s, self.process_count, self.process_index
            )
        except TimeoutError as e:
            raise PeerLostError(f"barrier {tag!r}: {e}") from e

    def publish_signature(self, seq: int, sig: str | None, meta: str = "") -> None:
        """Lead-writer side of a checkpoint publish: record that save
        boundary ``seq``'s content signature ``sig`` is DURABLE (called
        only after the atomic rename returned).  ``sig=None`` with
        ``meta="failed"`` records a failed write — peers mirror the
        lead's promote-to-full recovery instead of timing out."""
        if not self.active:
            return
        self._kv.set(self._key("sig", seq), json.dumps({"sig": sig, "meta": meta}))

    def await_signature(self, seq: int) -> dict | None:
        """Non-writer side: block until the lead published save boundary
        ``seq`` (the save barrier — no host proceeds past it before the
        signature it observed is durable).  Returns the publish payload
        ``{"sig": ..., "meta": "full" | "delta" | "failed"}``."""
        if not self.active:
            return None
        try:
            raw = self._kv.get(self._key("sig", seq), self.barrier_timeout_s)
        except TimeoutError as e:
            raise PeerLostError(f"awaiting save signature {seq}: {e}") from e
        return json.loads(raw)

    def share_cursor(self, seq: int, cursor: dict | None) -> list[dict | None] | None:
        """Every host posts its input cursor for save boundary ``seq``;
        the LEAD returns the gathered per-host cursor vector (index =
        process), everyone else returns None.  The vector travels inside
        the lead's atomic publish, so resume can hand each host back its
        exact position."""
        if not self.active:
            return None
        self._kv.set(
            self._key("cur", seq, self.process_index), json.dumps(cursor)
        )
        if not self.is_lead:
            return None
        out = []
        for p in range(self.process_count):
            try:
                out.append(
                    json.loads(self._kv.get(self._key("cur", seq, p), self.barrier_timeout_s))
                )
            except TimeoutError as e:
                raise PeerLostError(f"gathering cursor {seq} from host {p}: {e}") from e
        return out

    def broadcast(self, tag: str, value):
        """Lead's ``value`` to every host (non-leads pass anything; they
        receive the lead's).  Used for run identity: one auto-generated
        telemetry run_id must cover every host's records."""
        if not self.active:
            return value
        key = self._key("bc", tag, self._next(f"bc:{tag}"))
        if self.is_lead:
            self._kv.set(key, json.dumps(value))
        try:
            raw = self._kv.get(key, self.barrier_timeout_s)
        except TimeoutError as e:
            raise PeerLostError(f"broadcast {tag!r}: {e}") from e
        return json.loads(raw)

    def allgather(self, tag: str, value) -> list:
        """Every host posts ``value``; every host returns the full
        per-process list (index = process).  The values may legitimately
        differ — use :meth:`agree` when they must not."""
        if not self.active:
            return [value]
        n = self._next(f"ag:{tag}")
        self._kv.set(self._key("ag", tag, n, self.process_index), json.dumps(value))
        out = []
        for p in range(self.process_count):
            try:
                out.append(
                    json.loads(
                        self._kv.get(self._key("ag", tag, n, p), self.barrier_timeout_s)
                    )
                )
            except TimeoutError as e:
                raise PeerLostError(f"allgather {tag!r}: waiting on host {p}: {e}") from e
        return out

    def agree(self, tag: str, value) -> list:
        """Every host posts ``value``; every host reads all P values and
        raises (loudly, naming the hosts) unless they are identical.
        The restore-consistency check: same chain head, same cursor."""
        if not self.active:
            return [value]
        n = self._next(f"a:{tag}")
        self._kv.set(
            self._key("agree", tag, n, self.process_index), json.dumps(value)
        )
        vals = []
        for p in range(self.process_count):
            try:
                vals.append(
                    json.loads(
                        self._kv.get(self._key("agree", tag, n, p), self.barrier_timeout_s)
                    )
                )
            except TimeoutError as e:
                raise PeerLostError(f"agree {tag!r}: waiting on host {p}: {e}") from e
        if any(v != vals[0] for v in vals[1:]):
            detail = ", ".join(f"host {p}: {v!r}" for p, v in enumerate(vals))
            raise RuntimeError(
                f"hosts disagree on {tag} — {detail}.  Refusing to train on "
                "desynced state (is every host reading the same checkpoint "
                "chain / dataset?)"
            )
        return vals


# ---------------------------------------------------------------------------
# heartbeats + host monitor
# ---------------------------------------------------------------------------


def _hb_path(runtime_dir: str, process_index: int) -> str:
    return os.path.join(runtime_dir, f"hb-{process_index}.json")


class HeartbeatWriter:
    """Daemon thread: publish this host's liveness + training position
    (``{process, step, wall}``) every ``interval_s`` under the shared
    runtime dir.  Freshness is judged by file mtime (wall clocks across
    hosts need not agree); the step payload feeds straggler detection."""

    def __init__(self, runtime_dir: str, process_index: int, interval_s: float = 2.0):
        self._path = _hb_path(runtime_dir, process_index)
        self._process = int(process_index)
        self._interval = float(interval_s)
        self._step = 0
        self._stop = threading.Event()
        os.makedirs(runtime_dir, exist_ok=True)
        self._thread = threading.Thread(
            target=self._run, name="dist-heartbeat", daemon=True
        )
        self._thread.start()

    def set_step(self, step: int) -> None:
        self._step = int(step)

    def _write(self) -> None:
        tmp = f"{self._path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(
                    {"process": self._process, "step": self._step, "wall": time.time()},
                    f,
                )
            os.replace(tmp, self._path)
        except OSError:
            pass  # a full/unwritable runtime dir must not kill training

    def _run(self) -> None:
        self._write()
        while not self._stop.wait(self._interval):
            self._write()

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)


def read_heartbeat(runtime_dir: str, process_index: int) -> tuple[dict | None, float | None]:
    """(payload, seconds-since-last-write) for one host's heartbeat file
    (None, None when it does not exist / is unreadable)."""
    path = _hb_path(runtime_dir, process_index)
    try:
        age = time.time() - os.path.getmtime(path)
        with open(path) as f:
            payload = json.load(f)
        return (payload if isinstance(payload, dict) else None), age
    except (OSError, ValueError):
        return None, None


class HostMonitor:
    """Daemon thread watching PEER heartbeats: a peer whose file goes
    stale past ``timeout_s`` triggers ``on_event(peer, classification,
    detail)`` once per episode (re-armed when the peer freshens).  The
    classifications are host-level: ``host-heartbeat-lost`` (no write —
    dead or wedged before entering a collective) and ``host-straggler``
    (still writing, but ``straggler_steps`` behind us — the
    collective-entry timeout precursor).  Used by trainers (events land
    as kind=stall telemetry) and by the pod supervisor (straggler
    kills)."""

    def __init__(
        self,
        runtime_dir: str,
        process_index: int,
        process_count: int,
        timeout_s: float,
        on_event,
        *,
        my_step=None,
        straggler_steps: int = 0,
        poll_s: float = 1.0,
    ):
        self._dir = runtime_dir
        self._process = int(process_index)
        self._count = int(process_count)
        self._timeout = float(timeout_s)
        self._on_event = on_event
        self._my_step = my_step  # callable -> int, or None
        self._straggler_steps = int(straggler_steps)
        self._poll = float(poll_s)
        self._fired: dict[tuple[int, str], bool] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="dist-hostmonitor", daemon=True
        )
        self._thread.start()

    def _emit_once(self, peer: int, classification: str, detail: dict) -> None:
        key = (peer, classification)
        if self._fired.get(key):
            return
        self._fired[key] = True
        try:
            self._on_event(peer, classification, detail)
        # analysis: ok exception-hygiene owner-injected event callback; the monitor thread must survive any callback bug (the host-stall classification already fired)
        except Exception:
            pass

    def _run(self) -> None:
        while not self._stop.wait(self._poll):
            for p in range(self._count):
                if p == self._process:
                    continue
                payload, age = read_heartbeat(self._dir, p)
                if age is None:
                    continue  # never seen: peer still in bring-up
                if age > self._timeout:
                    self._emit_once(
                        p,
                        "host-heartbeat-lost",
                        {"age_s": round(age, 3), "last_step": (payload or {}).get("step")},
                    )
                    continue
                self._fired.pop((p, "host-heartbeat-lost"), None)
                if self._straggler_steps > 0 and self._my_step is not None and payload:
                    try:
                        behind = int(self._my_step()) - int(payload.get("step", 0))
                    except (TypeError, ValueError):
                        continue  # malformed heartbeat payload: no straggler verdict this poll
                    if behind >= self._straggler_steps:
                        self._emit_once(
                            p,
                            "host-straggler",
                            {"steps_behind": behind, "age_s": round(age, 3)},
                        )
                    else:
                        self._fired.pop((p, "host-straggler"), None)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)


# ---------------------------------------------------------------------------
# generation watcher (survivor-side recovery)
# ---------------------------------------------------------------------------


def reexec_argv(argv: list[str]) -> list[str]:
    """The argv a survivor re-execs with: ``--resume`` forced (the whole
    point is restoring the shared chain head) and any armed fault plan
    stripped (chaos plans fire on the FIRST incarnation only — a kill
    fault that re-armed on every re-exec would crash-loop the pod)."""
    out: list[str] = []
    skip = 0
    for a in argv:
        if skip:
            skip -= 1
            continue
        if a in ("--fault-plan", "--fault-seed", "--fault-horizon", "--fault-process"):
            skip = 1
            continue
        if a.startswith("--fault-"):
            continue
        if a == "--resume":
            continue  # re-added once below
        out.append(a)
    out.append("--resume")
    return out


class GenerationWatcher:
    """Daemon thread: when the supervisor bumps ``generation.json`` past
    this process's incarnation, re-exec in place (same PID, fresh image,
    ``--resume``) so this host joins the new pod generation.  exec from
    a side thread is deliberate: the main thread is typically wedged in
    a collective whose peer just died, and no Python-level signal or
    exception can reach it there."""

    def __init__(
        self,
        runtime_dir: str,
        generation: int,
        *,
        argv: list[str] | None = None,
        poll_s: float = 0.25,
        log=print,
        exec_fn=None,
    ):
        self._dir = runtime_dir
        self._generation = int(generation)
        self._argv = list(argv if argv is not None else sys.argv)
        self._poll = float(poll_s)
        self._log = log
        self._exec = exec_fn if exec_fn is not None else self._do_exec
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="dist-genwatcher", daemon=True
        )
        self._thread.start()

    def _do_exec(self, new_generation: int, argv: list[str]) -> None:
        os.environ[ENV_GENERATION] = str(new_generation)
        sys.stdout.flush()
        sys.stderr.flush()
        os.execv(sys.executable, [sys.executable, *argv])

    def _run(self) -> None:
        while not self._stop.wait(self._poll):
            info = read_generation(self._dir)
            if info is None:
                continue
            gen = int(info.get("generation", -1))
            if gen > self._generation:
                log_quietly(
                    self._log,
                    f"distributed: generation {self._generation} -> {gen} "
                    f"(cause: {info.get('cause', '?')}) — re-exec'ing into "
                    "the new pod generation with --resume",
                )
                self._exec(gen, reexec_argv(self._argv))
                return  # only reachable with an injected exec_fn (tests)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)


# ---------------------------------------------------------------------------
# bring-up
# ---------------------------------------------------------------------------


def enable_cpu_collectives() -> None:
    """Switch the CPU backend's cross-process collectives on (gloo) —
    without this a multi-process CPU mesh fails every computation with
    "Multiprocess computations aren't implemented on the CPU backend".
    Must run before backend init (a TPU backend ignores it)."""
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def initialize_runtime(cfg, log=print, argv: list[str] | None = None):
    """Pod bring-up for dist_train / dist_predict.  Returns a
    :class:`DistributedRuntime` (inert for single-process runs).

    Two paths in:

      * **supervised pod** (``FM_DIST_GENERATION`` env set by
        resilience.Supervisor): park until the supervisor's generation
        file names OUR generation (the restore barrier for relaunched /
        re-exec'd hosts), then ``jax.distributed.initialize`` against
        the generation's coordinator, and arm the GenerationWatcher +
        this host's HeartbeatWriter.
      * **classic** (config keys / env / TPU metadata): exactly the old
        parallel.multihost behavior — including "already initialized by
        the caller" (the multi-process tests initialize directly).
    """
    import jax

    runtime_dir = os.environ.get(ENV_RUNTIME_DIR, "") or getattr(cfg, "runtime_dir", "")
    gen_env = os.environ.get(ENV_GENERATION)
    watcher = heartbeat = None
    if gen_env is not None and runtime_dir:
        my_gen = int(gen_env)
        pid = int(os.environ.get(ENV_PROCESS_ID, "0"))
        info = wait_for_generation(
            runtime_dir, my_gen, timeout_s=float(cfg.barrier_timeout_s)
        )
        my_gen = int(info["generation"])
        os.environ[ENV_GENERATION] = str(my_gen)
        # The watcher goes up BEFORE the (blocking) initialize: a peer
        # that dies during bring-up itself must still be recoverable.
        watcher = GenerationWatcher(runtime_dir, my_gen, argv=argv, log=log)
        enable_cpu_collectives()
        log(
            f"distributed: joining pod generation {my_gen} as process "
            f"{pid}/{info['num_processes']} (coordinator {info['coordinator']})"
        )
        jax.distributed.initialize(
            info["coordinator"],
            num_processes=int(info["num_processes"]),
            process_id=pid,
            initialization_timeout=max(10, int(cfg.barrier_timeout_s)),
        )
        heartbeat = HeartbeatWriter(runtime_dir, pid, interval_s=cfg.heartbeat_s)
    else:
        from fast_tffm_tpu.parallel.multihost import maybe_initialize_distributed

        if cfg.coordinator_address or int(cfg.num_processes or 0) > 1:
            # Explicitly-configured multi-process bring-up: CPU meshes
            # need gloo before the backend comes up (TPU ignores it).
            enable_cpu_collectives()
        maybe_initialize_distributed(
            cfg.coordinator_address, cfg.num_processes, cfg.process_id
        )
        if jax.process_count() > 1 and runtime_dir:
            heartbeat = HeartbeatWriter(
                runtime_dir, jax.process_index(), interval_s=cfg.heartbeat_s
            )
    runtime = DistributedRuntime.create(
        barrier_timeout_s=cfg.barrier_timeout_s,
        runtime_dir=runtime_dir or None,
        log=log,
    )
    runtime.runtime_dir = runtime_dir or None
    runtime.heartbeat = heartbeat
    runtime.watcher = watcher
    return runtime
