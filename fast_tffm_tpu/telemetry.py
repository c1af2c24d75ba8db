"""Run telemetry: one envelope, three sentinels, one sink.

The reference's only observability was periodic loss prints (SURVEY.md
§5: TF RunMetadata existed but was never wired), and this repo had grown
three UNRELATED emitters on top of that — per-window ``kind=input``
stats, ``kind=serving`` histograms, and bare step records — sharing no
schema and carrying no run identity.  ``RunMonitor`` unifies them:

  * **envelope** — every record carries ``run_id`` (one per driver run),
    ``schema_version``, ``kind``, a monotonic ``step``, ``t`` (monotonic
    seconds since the run started — immune to wall-clock jumps) and
    ``ts`` (wall clock, for humans).  Per-kind required keys live in
    ``SCHEMAS`` and are pinned by tests/test_telemetry.py — schema drift
    is a test failure, not a silently broken dashboard.
  * **compile sentinel** — a process-wide ``jax.monitoring`` listener
    counts XLA backend compiles; ``on_dispatch`` drains the delta each
    driver dispatch, so a steady-state recompile in train/predict/serving
    surfaces as a ``kind=compile`` event (the generalization of the
    serving bucket-ladder's flat-jit-cache pin).  Compiles issued from
    the prefetch thread (packed-wire unpack programs) attribute to the
    next dispatch that drains.
  * **memory watermarks** — periodic ``kind=mem`` records with host RSS
    (/proc, with ru_maxrss as the peak floor) and device live-buffer
    bytes (``memory_stats`` where the runtime exposes it, live-array sum
    otherwise), plus peak-so-far; one final record is always emitted at
    close so every run documents its high-water mark.
  * **liveness watchdog and host clock** — ONE daemon thread a monitor
    (none where there is neither a sink nor a ``stall_timeout_s``), two
    duties.  *Liveness*: when no dispatch completes for
    ``stall_timeout_s``, it dumps every Python thread's stack and the
    prefetch queue depth as a ``kind=stall`` event, classified
    input-starved (empty queue: the producer is the bottleneck) vs
    device-bound (data ready, the consumer/device is wedged).  Armed by
    the first completed dispatch; suspended (``suspended()``) through
    phases that legitimately dispatch nothing (validation, checkpoint
    saves); defers while a stack shows an XLA compile in progress (slow,
    not stuck — up to 10x the deadline, then fires classified
    "compiling").  One event per stall episode; a recovered-then-stalled
    run fires again.  *Host clock*: the thread only sleeps, 20 ms at a
    time on the monotonic clock, so how late it wakes is how long NO
    Python thread of the process ran — a freeze, which stretches whichever
    span is open on every thread and which no stage clock can name.
    Lateness past 50 ms is summed into ``freeze_ms`` / ``freezes`` /
    ``freeze_max_ms`` of the next ``kind=train`` / ``kind=serving``
    record (``drain_host_clock``) beside the exact pauses of Python's
    cyclic collector (``gc_ms``, ``gc_collections``: a process-wide pair
    of ``gc.callbacks`` entries, around every other library's, whose
    totals monitors difference), and each writes a
    ``kind=freeze`` event (at most 20 a run) with the evidence that
    classifies it: ``gc`` | ``off-cpu`` | ``gil`` | ``clock-late``
    (``_classify_freeze``).

``arm_hang_exit`` is the hard os._exit timer the batch tools (tools/) arm
BEFORE ``import jax``: a batch tool must not hang.  That contract is why
this module — and the package ``__init__`` — must import without jax;
everything jax-touching here is lazy.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import threading
import time
import traceback

from fast_tffm_tpu.utils.tracing import MetricsLogger, span

__all__ = [
    "SCHEMA_VERSION",
    "ENVELOPE_FIELDS",
    "SCHEMAS",
    "RunMonitor",
    "CompileSentinel",
    "new_run_id",
    "artifact_stamp",
    "write_json_artifact",
    "thread_stacks",
    "classify_stall",
    "gc_pause_totals",
    "first_nonfinite_leaf",
    "arm_hang_exit",
    "enable_compilation_cache",
    "log_device",
    "global_cache_hit_count",
]

SCHEMA_VERSION = 1

# Fields every record carries (ts is stamped by MetricsLogger).
# process_index/process_count identify the EMITTING host on multi-process
# pods (0/1 on single-process runs and device-free emitters like the
# supervisor), so tools/report.py can merge per-host JSONL files for one
# run_id into per-host columns.
ENVELOPE_FIELDS = (
    "run_id",
    "schema_version",
    "kind",
    "step",
    "t",
    "ts",
    "process_index",
    "process_count",
)

# kind -> keys REQUIRED on every record of that kind (beyond the
# envelope).  Values may be null when a source genuinely cannot measure
# them (e.g. device_bytes without a backend), but the key must be there —
# a missing key means the emitter and the readers have drifted.
# Extra keys are allowed (extra_metrics merges, serving counters).
SCHEMAS: dict[str, tuple[str, ...]] = {
    "train": ("epoch", "loss", "examples_per_sec", "examples_per_sec_per_chip"),
    "validation": ("epoch", "validation_auc"),
    "input": ("input_items", "input_steps", "input_examples", "parse_ms"),
    "predict": ("examples", "examples_per_sec"),
    "serving": ("requests", "flushes", "rows", "queue_ms", "compute_ms", "total_ms"),
    "compile": ("source", "compiles", "total_compiles", "warmup"),
    "mem": (
        "host_rss_bytes",
        "host_rss_peak_bytes",
        "device_bytes",
        "device_peak_bytes",
        "sample_ms",  # what the sample cost the thread that took it
    ),
    "stall": (
        "deadline_s",
        "since_last_step_s",
        "classification",
        "prefetch_queue_depth",
        "stacks",
    ),
    # The host clock (RunMonitor._watch): one event a freeze — for late_ms
    # (50 or more) the clock thread, which only sleeps, did not wake.
    # freeze_ms is the part of it during which no heartbeat() arrived
    # either (0 under classification clock-late); gc_ms and cpu_ms are the
    # collector's pause and all threads' CPU time inside the tick.  NOT a
    # stall: report.py counts those and --strict gates on the count.
    "freeze": (
        "late_ms",
        "freeze_ms",
        "classification",
        "gc_ms",
        "cpu_ms",
        "beats",
        "stacks",
    ),
    "anomaly": ("event", "loss"),
    # Resilience layer (resilience.py): injected/observed faults (crash,
    # io_retry, injected_*, chain_repair) and supervised relaunches
    # (attempt ordinal, crashed child's exit code, backoff slept, MTTR =
    # crash -> first new training progress; null until measurable).
    "fault": ("event",),
    "restart": ("attempt", "exit_code", "backoff_s", "mttr_s"),
    "ckpt": (
        "mode",  # full (async) | delta | sync
        "snapshot_ms",
        "convert_ms",
        "d2h_ms",
        "write_ms",
        "bytes",
        "rows_written",
        "train_stall_ms",
    ),
    # Deep observability (profiling.py).  profile: one record per
    # measured compiled program (XLA cost analysis — bytes/flops null
    # for trace start/stop event records, program="trace", and where the
    # backend has no analysis of a lowering, as the TPU's PJRT client);
    # datastats: sampled device-side id-traffic statistics (dedup ratio,
    # heavy-hitter sketch mass, cumulative rows seen); freshness: the
    # publish→applied / publish→first-scored-with-new-rows SLO measured
    # at a serving reload swap (engine) or aggregated across a reload
    # fan-out (router — applied/scored keys null where it cannot see).
    "profile": ("program", "flops", "bytes_accessed"),
    "datastats": (
        "window_steps",
        "ids",
        "unique",
        "dedup_ratio",
        "rows_seen",
        "hh_k",
        "hh_topk_mass",
    ),
    "freshness": (
        "publish_step",
        "publish_to_applied_ms",
        "publish_to_first_scored_ms",
    ),
    # Online-learning loop (ISSUE 11).  quality: one record per replayed
    # backtest hour — the online trainer's held-out AUC next to the
    # batch-retrain reference's on the same hour (tools/backtest.py;
    # report.py --compare --strict gates on the gap).  soak: one record
    # per soak-harness sentinel tick — phase names the check window, ok
    # is the conjunction of that tick's sentinels (tools/soak.py).
    "quality": ("hour", "auc_online", "auc_batch"),
    "soak": ("phase", "elapsed_s", "ok"),
    # Tiered parameter store (ISSUE 12; paramstore/): one record per log
    # window — hot-tier hit rate over gather slots, staged miss rows and
    # their wire bytes, the three host spans as ms per step of the
    # window's ``steps`` (``tier.resolve``: residency lookup and the miss
    # set; ``tier.read``: the missed rows through the overlay and the
    # store; ``tier.writeback``: staging D2H -> pending overlay),
    # coherency restages, and the pending-overlay depth (rows awaiting
    # their post-publish store apply).
    "tiering": (
        "steps",
        "hit_rate",
        "miss_rows",
        "miss_rows_per_step",
        "miss_bytes_per_step",
        "writeback_rows",
        "writeback_ms",
        "resolve_ms",
        "read_ms",
        "restages",
        "pending_rows",
    ),
    # platform/device_kind/device_count: what jax reported to the driver
    # that held the device (null from the jax-free router/supervisor).
    "summary": (
        "total_compiles",
        "steady_compiles",
        "stalls",
        "freezes",
        "freeze_ms",
        "anomalies",
        "platform",
        "device_kind",
        "device_count",
    ),
}


def new_run_id() -> str:
    """Sortable-by-start-time and collision-safe across processes."""
    return f"{time.strftime('%Y%m%d-%H%M%S')}-{os.getpid():x}-{os.urandom(3).hex()}"


def artifact_stamp(run_id: str = "") -> dict:
    """The join keys every committed JSON artifact of a tool must carry so a
    bench artifact is joinable to the telemetry JSONL stream(s) it was
    measured from: the envelope ``run_id`` (pass the run's; a fresh one
    is drawn for tools that never started a monitored run) and the
    envelope ``schema_version`` the emitters wrote under."""
    return {"run_id": run_id or new_run_id(), "schema_version": SCHEMA_VERSION}


def write_json_artifact(path, obj, *, indent: int = 1, sort_keys: bool = True) -> None:
    """Atomically publish a tool's JSON artifact:
    full payload to a sibling tmp, then ``os.replace`` onto ``path`` — the
    same complete-or-previous contract every checkpoint publish honors
    (DESIGN crash-consistency invariant 1; gated by the atomic-publish
    checker).  A reader — a compare gate, a dashboard poller, a human
    mid-run — never sees a torn verdict."""
    tmp = f"{path}.{os.getpid():x}.tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=indent, sort_keys=sort_keys)
        f.write("\n")
    os.replace(tmp, path)


def log_quietly(log, msg: str) -> None:
    """Deliver ``msg`` to a caller-provided log callback, absorbing ANY
    failure the callback raises.  The one sanctioned sink for the
    "logging must never kill the worker" contract (collector threads,
    checkpoint writers, watchdogs): callbacks are injected by drivers and
    tests, so their failure surface is unknowable — and the message is
    always best-effort context for a diagnosis already recorded through
    a typed path (counter, typed error, telemetry record)."""
    if log is None:
        return
    try:
        log(msg)
    # analysis: ok exception-hygiene the sanctioned raising-log-callback sink — the diagnosis already traveled a typed path; see docstring
    except Exception:
        pass


# -- compile sentinel -----------------------------------------------------

# One process-wide counter fed by one jax.monitoring listener: jax has no
# listener UNregistration in its public API, so per-monitor listeners
# would leak across every test/run in a process.  Sentinels snapshot the
# counter instead.
_compile_lock = threading.Lock()
_compile_count = 0
_cache_hit_count = 0
_listener_registered = False

# jax times compile_or_get_cached under this event, so it fires once per
# program that reached the compile path — served from the persistent
# cache or not (seen on jax 0.9.0: a warm run reports compiles=N
# cache_hits=N).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Fired by jax's persistent compilation cache on every read hit.  Counted
# separately so a kind=compile record can say "this 'compile' was served
# from the on-disk cache" — a cold serving warmup with a warm cache shows
# compiles=N cache_hits=N instead of looking like N real XLA compiles.
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_duration_event(event: str, duration: float, **kw) -> None:
    global _compile_count
    if event == _COMPILE_EVENT:
        with _compile_lock:
            _compile_count += 1


def _on_event(event: str, **kw) -> None:
    global _cache_hit_count
    if event == _CACHE_HIT_EVENT:
        with _compile_lock:
            _cache_hit_count += 1


def _ensure_compile_listener() -> None:
    global _listener_registered
    with _compile_lock:
        if _listener_registered:
            return
        _listener_registered = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration_event)
    jax.monitoring.register_event_listener(_on_event)


def global_cache_hit_count() -> int:
    """Persistent-compilation-cache read hits observed process-wide."""
    with _compile_lock:
        return _cache_hit_count


# The one fixed persistent-cache location inside the checkout (gitignored).
# The path is part of jax's cache key, so it must never carry a pid, a
# time or a temporary name — a directory that moves never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compilation_cache(config_dir: str = "") -> str:
    """Turn on jax's persistent XLA compilation cache for this process and
    return the directory it lives in.  Called by ``cli.main`` and
    ``serving.replica.main`` before anything compiles, so a CLI run and
    the replica workers it spawns share one cache.

    Precedence: ``JAX_COMPILATION_CACHE_DIR`` in the environment (jax
    reads it itself — this code then sets no directory, so a cache placed
    from outside is found again by the next process); else ``config_dir``
    (``[Telemetry] compilation_cache_dir``); else ``CHECKOUT_CACHE_DIR``.
    The thresholds drop to zero so every program caches, small ones
    included — ``cache_hits`` on kind=compile records is how a run proves
    the cache worked."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = config_dir or CHECKOUT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


NO_DEVICE = {"platform": None, "device_kind": None, "device_count": None}


def log_device(log, source: str) -> dict:
    """The one line every device-holding entry point logs at start, and
    the ``platform`` / ``device_kind`` / ``device_count`` triple (as jax
    reports them) it hands its RunMonitor for the kind=summary record —
    what lets a jax-free parent (chip_smoke.py) refuse a child that ran
    somewhere else.  Initialises the backend: only processes that are
    about to compute call this (never the router or the supervisor)."""
    import jax

    from fast_tffm_tpu.data.native import parser_name
    from fast_tffm_tpu.ops.pallas_common import default_interpret

    devices = jax.devices()
    ident = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    log(
        f"{source}: device platform={ident['platform']} "
        f"device_kind={json.dumps(ident['device_kind'])} "
        f"device_count={ident['device_count']} "
        f"pallas={'interpreted' if default_interpret() else 'compiled'} "
        f"parser={parser_name()}"
    )
    return ident


def global_compile_count() -> int:
    """XLA backend compiles observed process-wide since the first
    sentinel was created (0 before that)."""
    with _compile_lock:
        return _compile_count


class CompileSentinel:
    """Per-consumer view of the process-wide compile counter.

    ``drain()`` returns how many XLA backend compiles happened since the
    previous drain (or construction).  Concurrent consumers (a trainer
    and a serving engine in one process) each see every compile — the
    counter is global, attribution is the caller's framing.
    """

    def __init__(self):
        _ensure_compile_listener()
        self._seen = global_compile_count()
        self._seen_hits = global_cache_hit_count()

    def drain(self) -> int:
        n = global_compile_count()
        delta = n - self._seen
        self._seen = n
        return delta

    def drain_cache_hits(self) -> int:
        """Persistent-cache hits since the last drain — how many of the
        drained compiles were served from the on-disk cache instead of
        running XLA."""
        n = global_cache_hit_count()
        delta = n - self._seen_hits
        self._seen_hits = n
        return delta


# -- memory watermarks ----------------------------------------------------


def host_rss_bytes() -> int | None:
    """Current resident set size (linux /proc; None where unreadable)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return None


def _ru_maxrss_bytes() -> int | None:
    try:
        import resource

        v = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # Linux reports KiB; macOS reports bytes.
        return int(v) if sys.platform == "darwin" else int(v) * 1024
    # analysis: ok exception-hygiene resource probe degrades to None by documented contract ("None where unreadable")
    except Exception:
        return None


def device_live_bytes() -> int | None:
    """Live device-buffer bytes: runtime memory_stats where exposed
    (real TPU/GPU backends), falling back to summing live jax arrays
    (CPU backend exposes no allocator stats).  None without jax."""
    if "jax" not in sys.modules:
        # Never the import that drags the backend up — telemetry observes.
        return None
    try:
        import jax

        total, had_stats = 0, False
        for d in jax.local_devices():
            ms = getattr(d, "memory_stats", None)
            stats = ms() if callable(ms) else None
            if stats and "bytes_in_use" in stats:
                total += int(stats["bytes_in_use"])
                had_stats = True
        if had_stats:
            return total
        return int(sum(int(x.nbytes) for x in jax.live_arrays()))
    # analysis: ok exception-hygiene resource probe degrades to None by documented contract ("None where unreadable")
    except Exception:
        return None


class _MemWatermarks:
    """Sample-and-track-peaks; ru_maxrss floors the host peak so the
    watermark is honest even when sampling missed the actual spike."""

    def __init__(self):
        self._host_peak = 0
        self._dev_peak = 0

    def sample(self) -> dict:
        t0 = time.perf_counter()
        host = host_rss_bytes()
        dev = device_live_bytes()
        if host is not None:
            self._host_peak = max(self._host_peak, host)
        maxrss = _ru_maxrss_bytes()
        if maxrss is not None:
            self._host_peak = max(self._host_peak, maxrss)
        if dev is not None:
            self._dev_peak = max(self._dev_peak, dev)
        return {
            "host_rss_bytes": host,
            "host_rss_peak_bytes": self._host_peak or None,
            "device_bytes": dev,
            "device_peak_bytes": self._dev_peak if dev is not None else None,
            # The sample runs on whichever thread is due (on_dispatch: the
            # train loop, the serve collector), so it says what it cost whom.
            "sample_ms": round(1e3 * (time.perf_counter() - t0), 3),
            "thread": threading.current_thread().name,
        }


# -- host clock -----------------------------------------------------------

# The clock thread sleeps this long at a time; well over the interpreter's
# 5 ms switch interval, so a thread that merely has to be handed the
# interpreter lock is never late by a tick.  A freeze reads up to a period
# short (the clock was due that far into it).  The period is not what the
# clock costs: on the chip the scorer's p50 read the same at 20 ms and at
# 50 ms (PERF.md section 6, PR 39), so the finer one is kept.
CLOCK_PERIOD_S = 0.02
# Lateness of a wake past this is a freeze (ten switch intervals: on a
# loaded test machine with more workers than cores the clock reads 5-25 ms
# late, tests/test_host_clock.py), and each writes a kind=freeze event, at
# most so many a run (the counters go on counting).
FREEZE_S = 0.05
FREEZE_RECORDS_MAX = 20
_OS_SAMPLE_EVERY_S = 1.0

# Process-wide gc.callbacks entries, installed once: like jax.monitoring's
# listeners they are never taken out again, so monitors difference the
# totals (pause seconds, collections, generation-2 seconds, when the
# collection in progress began or None) instead of each adding entries.  One
# tuple, replaced whole: a reader on another thread sees one collection's
# state.  TWO entries, the first of the list and the last: the pause a
# thread pays for a collection holds every other library's callbacks too,
# and jax's own (``xla_client._xla_gc_callback``: the runtime's deferred
# frees, under the interpreter lock, on both phases) is most of a long one.
_gc_state = (0.0, 0, 0.0, None)
_gc_span = None
_gc_installed = False


def _on_gc_start(phase: str, info: dict) -> None:
    global _gc_state, _gc_span
    if phase != "start":
        return
    total, n, gen2, _ = _gc_state
    _gc_state = (total, n, gen2, time.perf_counter())
    # A full collection is the one long enough to make an idle gap on the
    # device: under a profiler session it lies on the host plane, on the
    # thread that paid it.  Only where jax is already loaded: the router's
    # and the supervisor's monitors stay jax-free.
    if info.get("generation") == 2 and "jax" in sys.modules:
        _gc_span = span("host.gc", generation=2)
        _gc_span.__enter__()


def _on_gc_stop(phase: str, info: dict) -> None:
    global _gc_state, _gc_span
    total, n, gen2, began = _gc_state
    if phase != "stop" or began is None:
        return
    dt = time.perf_counter() - began
    is2 = info.get("generation") == 2
    _gc_state = (total + dt, n + 1, gen2 + dt if is2 else gen2, None)
    if _gc_span is not None:
        ann, _gc_span = _gc_span, None
        ann.__exit__(None, None, None)


def _ensure_gc_listener() -> None:
    global _gc_installed
    with _compile_lock:
        if _gc_installed:
            return
        _gc_installed = True
    gc.callbacks.insert(0, _on_gc_start)
    gc.callbacks.append(_on_gc_stop)


def gc_pause_totals() -> tuple[float, int, float]:
    """Process-wide (seconds paused, collections, seconds in generation 2)
    of Python's cyclic collector, the callbacks of ``gc.callbacks`` between
    ours included, since the first monitor was created.  A
    collection in progress on another thread counts up to now: its ``stop``
    callback is Python code, and the interpreter lock can be handed to the
    reader before it has run."""
    total, n, gen2, began = _gc_state
    if began is not None:
        total += max(0.0, time.perf_counter() - began)
    return total, n, gen2


_cpu_stat_path = None  # the cgroup's cpu.stat once found; "" where there is none


def _find_cpu_stat() -> str:
    """This process's cgroup ``cpu.stat``: the v2 root and v1's ``cpu``
    mount as a container sees them, then the paths /proc/self/cgroup names
    (a host's view, a nested group, ``cpu,cpuacct``).  Looked for once."""
    paths = ["/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"]
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                _, ctrls, path = line.rstrip("\n").split(":", 2)
                if not ctrls:  # v2: "0::/path"
                    paths += [f"/sys/fs/cgroup{path}/cpu.stat", f"/sys/fs/cgroup/unified{path}/cpu.stat"]
                elif "cpu" in ctrls.split(","):
                    paths += [f"/sys/fs/cgroup/{ctrls}{path}/cpu.stat", f"/sys/fs/cgroup/cpu{path}/cpu.stat",
                              f"/sys/fs/cgroup/{ctrls}/cpu.stat"]
    except (OSError, ValueError):
        pass
    for path in paths:
        try:
            with open(path) as f:
                if "nr_throttled" in f.read():
                    return path
        except OSError:
            continue
    return ""


_OS_COUNTERS = (
    "throttled_usec",
    "nr_throttled",
    "psi_cpu_usec",
    "psi_memory_usec",
    "psi_io_usec",
    "steal_ticks",
    "majflt",
    "nivcsw",
)


def os_counters() -> dict:
    """What the OS counts of a process kept off the CPU, each None where
    its file is not there: the cgroup's CPU-quota throttling
    (``_find_cpu_stat``: v2 ``throttled_usec``, v1's ``throttled_time`` in
    ns), the
    pressure-stall totals of /proc/pressure (``some``: microseconds in which
    a task waited for the resource), the machine's stolen time (/proc/stat,
    clock ticks the hypervisor ran something else), and this process's
    major page faults and involuntary context switches.  Read by the clock
    thread, never by a hot thread."""
    global _cpu_stat_path
    out = dict.fromkeys(_OS_COUNTERS)
    if _cpu_stat_path is None:
        _cpu_stat_path = _find_cpu_stat()
    if _cpu_stat_path:
        try:
            with open(_cpu_stat_path) as f:
                kv = dict(line.split()[:2] for line in f if line.strip())
            if "throttled_usec" in kv:
                out["throttled_usec"] = int(kv["throttled_usec"])
            elif "throttled_time" in kv:
                out["throttled_usec"] = int(kv["throttled_time"]) // 1000
            if "nr_throttled" in kv:
                out["nr_throttled"] = int(kv["nr_throttled"])
        except (OSError, ValueError):
            pass
    for res in ("cpu", "memory", "io"):
        try:
            with open(f"/proc/pressure/{res}") as f:
                out[f"psi_{res}_usec"] = int(f.readline().rsplit("total=", 1)[1])
        except (OSError, ValueError, IndexError):
            pass
    try:
        with open("/proc/stat") as f:
            out["steal_ticks"] = int(f.readline().split()[8])
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF)
        out["majflt"], out["nivcsw"] = int(ru.ru_majflt), int(ru.ru_nivcsw)
    # analysis: ok exception-hygiene resource probe degrades to None by documented contract ("None where the file is not there")
    except Exception:
        pass
    return out


def _classify_freeze(frozen_s: float, gc_s: float, cpu_s: float) -> str:
    """What a late wake of the clock was.  ``frozen_s`` is the part of the
    lateness before any ``heartbeat()`` arrived: under ``FREEZE_S`` of it
    Python ran meanwhile and the lateness was the clock thread's own (the
    scheduler's): **clock-late**.  Else **gc** where the collector's pause
    covers at least half of it; else **off-cpu** where all threads' CPU
    time over the late part is under a quarter of it (the process as a
    whole did not run: throttled, stolen, paging — the OS deltas say
    which); else **gil**: the process ran and Python did not, one thread
    held the interpreter lock in a C call (the stacks say whose)."""
    if frozen_s < FREEZE_S:
        return "clock-late"
    if gc_s >= 0.5 * frozen_s:
        return "gc"
    if cpu_s < 0.25 * frozen_s:
        return "off-cpu"
    return "gil"


# -- stall forensics ------------------------------------------------------


def thread_stacks(max_frames: int = 25) -> dict[str, str]:
    """Formatted stack of every live Python thread (deepest frames kept),
    keyed by thread name — the watchdog's core forensic payload."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        name = names.get(ident) or f"thread-{ident}"
        lines = traceback.format_stack(frame)
        out[name] = "".join(lines[-max_frames:])
    return out


_DEVICE_MARKERS = (
    "block_until_ready",
    "backend_compile",
    "jaxlib",
    "_xla",
    "device_put",
)

# Frames on the dispatching thread's stack while a jit cache miss is being
# traced/lowered/XLA-compiled — sampled on a TPU v5 lite under jax 0.9.0
# through the 72.9 s first compile of baseline #1's train step: each of
# these was on the stack in 1380 of 1385 samples (backend_compile matches
# backend_compile_and_load).  A compile is SLOW, not stuck — the same
# reasoning that prices the first dispatch into warmup — so the watchdog
# defers while one is on a stack (up to a 10x-deadline cap: a compile
# that long is worth an event, classified "compiling").
_COMPILING_MARKERS = (
    "backend_compile",
    "compile_or_get_cached",
    "cache_miss",
    "_run_python_pjit",
)


def compiling_now(stacks: dict[str, str]) -> bool:
    blob = "\n".join(stacks.values())
    return any(m in blob for m in _COMPILING_MARKERS)


def classify_stall(
    queue_depth: int | None, stacks: dict[str, str], producer_alive=None,
    stream_idle=None,
) -> str:
    """input-starved: the prefetch queue is empty, so the producer (parse
    / disk / conversion) is what everyone is waiting on — and when the
    producer THREAD is known dead, the classification says so (a dead
    producer is a fault to restart from, not a slow parse to wait out).
    ``stream_idle`` True (a tail-following input stream polling a quiet
    append-only file — data/stream.py) is the third flavor: the producer
    is alive and healthy, the UPSTREAM WRITER is what stopped — wait (or
    page whoever owns the event feed), don't restart.
    device-bound: data is ready (or there is no input queue) and a thread
    is inside the device runtime — the dispatch/compile/transfer is
    what's wedged."""
    if queue_depth == 0:
        if producer_alive is False:
            return "input-starved (producer-thread dead)"
        if stream_idle:
            return "input-starved (stream-idle)"
        return "input-starved"
    blob = "\n".join(stacks.values())
    if any(m in blob for m in _DEVICE_MARKERS):
        return "device-bound"
    if queue_depth is not None and queue_depth > 0:
        return "device-bound"
    return "unknown"


def first_nonfinite_leaf(tree) -> str | None:
    """Path of the first pytree leaf holding a NaN/Inf, or None.  "Cheap"
    only relative to an abort (it syncs every leaf to host) — call it on
    the way down, never on the hot path."""
    try:
        import jax
        import numpy as np

        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            arr = np.asarray(leaf)
            if arr.dtype.kind == "f" and arr.size and not np.isfinite(arr).all():
                return jax.tree_util.keystr(path)
    # analysis: ok exception-hygiene forensic probe on the way down to an abort — None just means "leaf unnamed", the anomaly record still lands
    except Exception:
        return None
    return None


# -- the monitor ----------------------------------------------------------


class RunMonitor:
    """Owns the MetricsLogger and stamps the shared envelope on every
    record; hosts the compile sentinel, the memory sampler, and the one
    thread that is liveness watchdog and host clock.  Thread-safe: drivers
    emit from their loop thread, the watchdog from its own.

    ``source`` names the driver (train / predict / serving) on compile
    events.  ``queue_depth_fn`` (settable later via
    ``set_queue_depth_fn``) lets the stall classifier read the live
    prefetch-queue depth.  ``stall_timeout_s`` 0 disables the liveness
    check (the thread still runs as the host clock wherever records reach a
    file; with neither there is no thread);
    ``mem_every_s`` 0 reduces kind=mem to the one guaranteed close()
    record.  ``device`` is ``log_device()``'s triple, stamped on the
    kind=summary record.
    """

    def __init__(
        self,
        path: str | None = None,
        *,
        run_id: str = "",
        source: str = "train",
        stall_timeout_s: float = 0.0,
        mem_every_s: float = 0.0,
        queue_depth_fn=None,
        logger: MetricsLogger | None = None,
        replica: int | None = None,
        log=None,
        device: dict | None = None,
    ):
        self._logger = logger if logger is not None else MetricsLogger(path)
        # log_device()'s triple from a device-holding driver; NO_DEVICE for
        # the jax-free emitters (router, supervisor) — stamped on summary.
        self.device = dict(NO_DEVICE if device is None else device)
        self._own_logger = logger is None
        self.run_id = run_id or new_run_id()
        self.source = source
        # Serving replica ordinal (None outside the replicated serving
        # tier): stamped on every record like process_index, so report.py
        # can split one run's stream into per-replica columns.
        self.replica = replica
        # Stamped once at construction: the monitor outlives any single
        # dispatch, and a host's identity cannot change mid-run.
        from fast_tffm_tpu.distributed import process_identity

        self.process_index, self.process_count = process_identity()
        self._log = log
        self._t0 = time.monotonic()
        self._lock = threading.Lock()
        self._step = 0
        self._closed = False

        self._sentinel = CompileSentinel()
        self.compiles_total = 0
        self.compiles_steady = 0  # compiles NOT marked warmup
        self._last_warmup = True  # nothing dispatched yet = startup/warmup
        self._warmup_depth = 0  # >0: inside a warmup_window() — compiles
        #   drained by ANY thread attribute as warmup (e.g. a serving
        #   reload's restore/apply programs, which run off the hot path
        #   and must not read as steady-state score-ladder recompiles)

        self._mem = _MemWatermarks()
        self._mem_every_s = float(mem_every_s)
        self._last_mem = self._t0

        self.stalls = 0
        self.anomalies = 0
        self._stall_timeout = float(stall_timeout_s)
        self._queue_depth_fn = queue_depth_fn
        self._producer_alive_fn = None
        self._stream_idle_fn = None
        # Armed by the FIRST heartbeat: the gap before dispatch 1 is
        # dominated by XLA compile (legitimately >> any stall deadline),
        # and startup hangs are arm_hang_exit's department.
        self._last_beat = None
        self._stall_fired = False
        self._suspended = 0
        # The host clock's side of heartbeat(): when the clock's next wake
        # is due, and the heartbeats that arrived after it was (Python ran
        # while the clock did not: its lateness is not a freeze).
        self._due = float("inf")
        self._late_beats = 0
        self._late_beat_t = None
        # Freezes since the last drain_host_clock(): ms, count, longest;
        # the collector's totals as that drain saw them; the run's totals.
        self._fz = [0.0, 0, 0.0]
        self._gc_seen = (0.0, 0, 0.0)
        self.freezes = 0
        self.freeze_ms = 0.0
        self._freeze_records = 0
        self._stop = threading.Event()
        self._watchdog = None
        if self._stall_timeout > 0 or self._logger.active:
            _ensure_gc_listener()
            self._gc_seen = gc_pause_totals()
            self._watchdog = threading.Thread(
                target=self._watch, name="telemetry-watchdog", daemon=True
            )
            self._watchdog.start()

    @property
    def active(self) -> bool:
        """Whether records reach a file (sentinels run regardless)."""
        return self._logger.active

    def set_queue_depth_fn(self, fn) -> None:
        """Swap the prefetch-depth probe (drivers rebuild streams per
        epoch; the watchdog should read the CURRENT one)."""
        self._queue_depth_fn = fn

    def set_producer_alive_fn(self, fn) -> None:
        """Swap the prefetch-producer liveness probe (same per-epoch
        cadence as the depth probe): lets a stall classify as
        'input-starved (producer-thread dead)' instead of merely depth 0."""
        self._producer_alive_fn = fn

    def set_stream_idle_fn(self, fn) -> None:
        """Swap the tail-follow idleness probe (follow-mode input streams
        only — data/stream.py): a starved loop whose stream is idle-
        polling a quiet append-only file classifies as
        'input-starved (stream-idle)' — wait for the writer, don't
        restart the producer."""
        self._stream_idle_fn = fn

    # -- emission ---------------------------------------------------------

    def emit(self, kind: str, step: int | None = None, **fields) -> None:
        """Append one enveloped record.  ``kind`` must be registered in
        SCHEMAS — an unknown kind is a programming error the schema test
        could never catch, so it raises here."""
        if kind not in SCHEMAS:
            raise ValueError(f"unknown telemetry kind {kind!r} (register it in SCHEMAS)")
        envelope = dict(
            run_id=self.run_id,
            schema_version=SCHEMA_VERSION,
            kind=kind,
            step=self._step if step is None else int(step),
            t=round(time.monotonic() - self._t0, 3),
            process_index=self.process_index,
            process_count=self.process_count,
        )
        if self.replica is not None and "replica" not in fields:
            envelope["replica"] = self.replica
        self._logger.log(**envelope, **fields)

    def heartbeat(self, step: int) -> None:
        """The liveness signal: call whenever a dispatch completes."""
        with self._lock:
            self._step = int(step)
            self._last_beat = now = time.monotonic()
            self._stall_fired = False
            if now > self._due:
                self._late_beats += 1
                if self._late_beat_t is None:
                    self._late_beat_t = now

    @contextlib.contextmanager
    def suspended(self):
        """Suspend the liveness watchdog for a phase that legitimately
        completes no dispatches (a long validation pass, a checkpoint
        save) — otherwise a healthy epoch boundary reads as a stall,
        misclassified input-starved because the drained train stream's
        queue depth is 0.  Re-entrant; the heartbeat clock restarts on
        exit."""
        with self._lock:
            self._suspended += 1
        try:
            yield
        finally:
            with self._lock:
                self._suspended -= 1
                if self._last_beat is not None:
                    self._last_beat = time.monotonic()
                self._stall_fired = False

    @contextlib.contextmanager
    def warmup_window(self):
        """Mark a window whose compiles are EXPECTED and off the hot path
        (a serving reload's restore/delta-apply programs): any compile
        drained while a thread is inside — including by a concurrent
        dispatch on another thread — attributes as warmup, not as a
        steady-state recompile.  The trailing drain on exit catches
        compiles nobody dispatched over.  (A genuine steady recompile
        landing inside the window is misattributed — accepted: windows
        are rare and short, and the alternative is a false alarm on
        every hot reload.)"""
        with self._lock:
            self._warmup_depth += 1
        try:
            yield
        finally:
            try:
                self.on_dispatch(self._step, warmup=True)
            except (OSError, ValueError):
                pass  # a failed drain is a lost record, not a broken window
            with self._lock:
                self._warmup_depth -= 1

    def on_dispatch(self, step: int, warmup: bool = False) -> None:
        """Per-dispatch hook for driver loops: heartbeat + compile drain +
        due memory sample.  ``warmup`` marks dispatches where a compile
        is EXPECTED (first call, bucket warmup) so steady-state recompiles
        are separable from the priced-in ones."""
        with self._lock:
            warmup = warmup or self._warmup_depth > 0
        self.heartbeat(step)
        delta = self._sentinel.drain()
        hits = self._sentinel.drain_cache_hits()
        self._last_warmup = bool(warmup)
        if delta or hits:
            # Persistent-cache hits ride the record distinctly: how many
            # of these compiles were read back from the on-disk cache
            # (real XLA compiles = compiles - cache_hits).
            with self._lock:
                self.compiles_total += delta
                if not warmup:
                    self.compiles_steady += delta
            self.emit(
                "compile",
                step=step,
                source=self.source,
                compiles=delta,
                total_compiles=self.compiles_total,
                warmup=bool(warmup),
                cache_hits=hits,
            )
        if self._mem_every_s > 0:
            now = time.monotonic()
            if now - self._last_mem >= self._mem_every_s:
                self._last_mem = now
                self.emit_mem(step=step)

    def emit_mem(self, step: int | None = None) -> None:
        self.emit("mem", step=step, **self._mem.sample())

    def emit_anomaly(
        self, step: int, loss, event: str = "nonfinite_loss", state=None, **fields
    ) -> None:
        """Structured divergence record (the satellite): step, loss, and —
        when a state pytree is handed over — the first non-finite tensor's
        path, so report.py can say WHICH table diverged."""
        with self._lock:
            self.anomalies += 1
        if state is not None and "first_nonfinite" not in fields:
            fields["first_nonfinite"] = first_nonfinite_leaf(state)
        self.emit(
            "anomaly",
            step=step,
            event=event,
            loss=None if loss is None else float(loss),
            **fields,
        )

    # -- watchdog ---------------------------------------------------------

    def drain_host_clock(self) -> dict:
        """The host clock's flat fields over the interval since this
        monitor's previous drain, for the record that closes it (a log
        window's kind=train, an interval's kind=serving): ``freeze_ms``
        (lateness of the clock's wakes past ``FREEZE_S``, summed),
        ``freezes``, ``freeze_max_ms``; ``gc_ms``, ``gc_collections``,
        ``gc_gen2_ms`` (the collector's pauses, exact).  A zero is
        "watched, none seen"; {} from a monitor that runs no clock."""
        if self._watchdog is None:
            return {}
        gc_now = gc_pause_totals()
        with self._lock:
            (ms, n, longest), self._fz = self._fz, [0.0, 0, 0.0]
            seen, self._gc_seen = self._gc_seen, gc_now
        return {
            "freeze_ms": round(ms, 3),
            "freezes": n,
            "freeze_max_ms": round(longest, 3),
            "gc_ms": round(1e3 * (gc_now[0] - seen[0]), 3),
            "gc_collections": gc_now[1] - seen[1],
            "gc_gen2_ms": round(1e3 * (gc_now[2] - seen[2]), 3),
        }

    def _watch(self) -> None:
        """The monitor's one thread.  It sleeps ``CLOCK_PERIOD_S`` at a
        time and does nothing else that can block, so ``now - due`` at a
        wake is time in which no Python thread of the process ran
        (``_on_late_wake``); once a second it samples the OS counters a
        freeze record differences; every ``poll`` it checks liveness."""
        poll = max(0.02, min(self._stall_timeout / 4.0, 1.0))
        now = time.monotonic()
        next_stall = now + poll if self._stall_timeout > 0 else float("inf")
        next_os = now
        os_last = {}
        # All threads' CPU time and the collector's pauses at the last wake,
        # and the CPU time of the last tick that was on time.
        cpu_last, gc_last, cpu_tick = time.process_time(), gc_pause_totals()[0], 0.0
        due = now + CLOCK_PERIOD_S
        with self._lock:
            self._due = due
        while not self._stop.wait(max(0.0, due - time.monotonic())):
            now = time.monotonic()
            late = now - due
            cpu, gc_s = time.process_time(), gc_pause_totals()[0]
            with self._lock:
                beats, beat_t = self._late_beats, self._late_beat_t
            frozen_wake = late >= FREEZE_S
            stacks = None
            if frozen_wake and self._freeze_records < FREEZE_RECORDS_MAX:
                # First of what can hand the interpreter lock back: whoever
                # held it is still in the frame that did.
                stacks = thread_stacks()
                stacks.pop("telemetry-watchdog", None)  # our own frame is noise
            if frozen_wake or now >= next_os:
                os_now = os_counters()
                os_delta = {
                    k: v - os_last[k] for k, v in os_now.items()
                    if v is not None and os_last.get(k) is not None
                }
                os_last, next_os = os_now, now + _OS_SAMPLE_EVERY_S
            if frozen_wake:
                # Frozen until the first sign that Python ran: a heartbeat
                # past the due time, or this wake.
                frozen = late if beat_t is None else min(late, max(0.0, beat_t - due))
                self._on_late_wake(
                    late, frozen, beats, gc_s - gc_last, cpu - cpu_last, cpu_tick,
                    os_delta, stacks,
                )
            else:
                cpu_tick = cpu - cpu_last
            cpu_last, gc_last = cpu, gc_s
            if now >= next_stall:
                next_stall = now + poll
                self._check_stall()
            # The next wake is due a period from HERE: what this wake's own
            # work took (stacks, file reads, a record) is not lateness.
            due = time.monotonic() + CLOCK_PERIOD_S
            with self._lock:
                self._late_beats, self._late_beat_t, self._due = 0, None, due

    def _on_late_wake(
        self, late: float, frozen: float, beats: int, gc_s: float,
        cpu_s: float, cpu_tick_s: float, os_delta: dict, stacks: dict | None,
    ) -> None:
        """One wake of the clock ``late`` seconds past its due time.  The CPU
        time of the late part is the tick's less what an on-time tick (the
        ``CLOCK_PERIOD_S`` before the due time) used last."""
        cls = _classify_freeze(frozen, gc_s, max(0.0, cpu_s - cpu_tick_s))
        counted = 0.0 if cls == "clock-late" else 1e3 * frozen
        if counted:
            with self._lock:
                self._fz[0] += counted
                self._fz[1] += 1
                self._fz[2] = max(self._fz[2], counted)
                self.freezes += 1
                self.freeze_ms += counted
            if "jax" in sys.modules:
                # A marker on the profiler's host plane: its start less
                # late_ms is where the freeze began.
                with span("host.freeze", late_ms=round(1e3 * late, 1)):
                    pass
        if stacks is None:
            return  # the run has had its events
        self._freeze_records += 1
        try:
            self.emit(
                "freeze",
                late_ms=round(1e3 * late, 3),
                freeze_ms=round(counted, 3),
                classification=cls,
                gc_ms=round(1e3 * gc_s, 3),
                cpu_ms=round(1e3 * cpu_s, 3),
                cpu_prev_tick_ms=round(1e3 * cpu_tick_s, 3),
                beats=beats,
                os_delta=os_delta,
                stacks=stacks,
            )
        except (OSError, ValueError):
            pass  # a full metrics disk must not kill the clock

    def _check_stall(self) -> None:
        with self._lock:
            if self._last_beat is None or self._suspended:
                return  # not armed yet / in a no-dispatch phase
            since = time.monotonic() - self._last_beat
            fired = self._stall_fired
            step = self._step
        if since < self._stall_timeout or fired:
            return
        stacks = thread_stacks()
        stacks.pop("telemetry-watchdog", None)  # our own frame is noise
        compiling = compiling_now(stacks)
        if compiling and since < 10.0 * self._stall_timeout:
            # An XLA compile in progress (e.g. a new shape's warmup
            # program) is slow, not wedged — don't fire, don't latch;
            # re-check next poll.  Past 10x the deadline it IS worth
            # an event, classified "compiling".
            return
        with self._lock:
            self._stall_fired = True
            self.stalls += 1
        depth = None
        if self._queue_depth_fn is not None:
            try:
                depth = self._queue_depth_fn()
            # analysis: ok exception-hygiene driver-injected probe; the watchdog must survive any probe bug — depth=None still classifies
            except Exception:
                depth = None
        alive = None
        if self._producer_alive_fn is not None:
            try:
                alive = self._producer_alive_fn()
            # analysis: ok exception-hygiene driver-injected probe; the watchdog must survive any probe bug — alive=None still classifies
            except Exception:
                alive = None
        s_idle = None
        if self._stream_idle_fn is not None:
            try:
                s_idle = self._stream_idle_fn()
            # analysis: ok exception-hygiene driver-injected probe; the watchdog must survive any probe bug — s_idle=None still classifies
            except Exception:
                s_idle = None
        cls = (
            "compiling"
            if compiling
            else classify_stall(depth, stacks, alive, s_idle)
        )
        try:
            self.emit(
                "stall",
                step=step,
                deadline_s=self._stall_timeout,
                since_last_step_s=round(since, 3),
                classification=cls,
                prefetch_queue_depth=depth,
                producer_alive=alive,
                stacks=stacks,
            )
        except (OSError, ValueError):
            pass  # a full metrics disk must not kill stall detection
        log_quietly(
            self._log,
            f"telemetry watchdog: no step for {since:.1f}s "
            f"(deadline {self._stall_timeout:.1f}s) at step {step} — "
            f"{cls}; thread stacks -> kind=stall record",
        )

    # -- shutdown ---------------------------------------------------------

    def close(self, **summary_fields) -> None:
        """Final drain: any unattributed compiles, the guaranteed last
        memory watermark, and the kind=summary totals (the compile
        sentinel's "final count").  Extra keyword fields merge into the
        summary record (drivers pass their end-of-run counters).
        Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        if self._watchdog is not None:
            self._watchdog.join(timeout=2.0)
        delta = self._sentinel.drain()
        hits = self._sentinel.drain_cache_hits()
        if delta or hits:
            # Compiles landing between the last dispatch and close (e.g.
            # the prefetch thread mid-compiling an unpack program when a
            # SIGTERM stopped the loop) inherit the last dispatch's
            # warmup framing — a warmup-era run must not report them as
            # steady-state recompiles.
            warm = self._last_warmup
            with self._lock:
                self.compiles_total += delta
                if not warm:
                    self.compiles_steady += delta
            self.emit(
                "compile",
                source=self.source,
                compiles=delta,
                total_compiles=self.compiles_total,
                warmup=warm,
                cache_hits=hits,
            )
        self.emit_mem()
        self.emit(
            "summary",
            total_compiles=self.compiles_total,
            steady_compiles=self.compiles_steady,
            stalls=self.stalls,
            freezes=self.freezes,
            freeze_ms=round(self.freeze_ms, 3),
            anomalies=self.anomalies,
            **self.device,
            **summary_fields,
        )
        if self._own_logger:
            self._logger.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- the hang-exit watchdog -----------------------------------------------

DEFAULT_HANG_EXIT_SECS = 600.0


def arm_hang_exit(seconds: float = DEFAULT_HANG_EXIT_SECS, what: str = "bench"):
    """Hard hang watchdog for batch tools: os._exit(2) with a stderr note
    if not cancelled within ``seconds``.

    A batch tool must not hang: a hung benchmark is worse than a missing
    one, because it stalls the whole harness.  The scripts under tools/ arm
    this BEFORE importing jax/fast_tffm_tpu (backend initialization is
    itself a place a process can block) and cancel it once their last
    result line is printed — which is why this module (and the package
    __init__) must import jax-free.

    Unlike RunMonitor's liveness watchdog (observe, classify, keep
    running), this one KILLS: batch tools have nothing to salvage from a
    wedged backend.  Returns the armed ``threading.Timer`` (call
    ``.cancel()`` on success).
    """

    def fire():
        print(
            f"{what} watchdog: no result after {seconds:.0f}s — device "
            "backend appears hung; aborting without a number",
            file=sys.stderr,
            flush=True,
        )
        os._exit(2)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t
