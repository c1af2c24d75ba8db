"""The host clock (telemetry.RunMonitor._watch): the monitor's one thread
only sleeps, so how late it wakes is how long no Python thread of the
process ran.  Each test makes one kind of lost time for real (a C call that
holds the interpreter lock, a pause of the collector, a stopped process, a
clock thread the scheduler left asleep) and reads what the clock wrote.

Steady under ``-n 6 --dist loadfile`` on a machine with fewer cores than
threads: every bound is one-sided against what the test made itself, and a
freeze the loaded machine adds of its own is allowed for, never asserted
absent."""

import gc
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

import pytest

from fast_tffm_tpu import telemetry
from fast_tffm_tpu.telemetry import SCHEMAS, RunMonitor, new_run_id

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOST_CLOCK_FIELDS = ("freeze_ms", "freezes", "freeze_max_ms", "gc_ms", "gc_collections")


def _read(path, kind=None):
    rows = [json.loads(l) for l in open(path).read().splitlines() if l.strip()]
    return rows if kind is None else [r for r in rows if r["kind"] == kind]


def _settle(seconds=4 * telemetry.CLOCK_PERIOD_S):
    """A few ticks of the clock: what was made is seen and written."""
    time.sleep(seconds)


def _floats_sorting_for(seconds):
    """A list whose ``sorted`` (ONE C call, the interpreter lock held
    throughout) lasts about ``seconds`` on this machine as loaded now."""
    rng = random.Random(7)
    n, took = 200_000, 0.0
    while True:
        xs = [rng.random() for _ in range(n)]
        t0 = time.perf_counter()
        sorted(xs)
        took = time.perf_counter() - t0
        if took >= seconds or n >= 12_800_000:
            return xs, took
        n = min(12_800_000, int(n * max(2.0, 1.3 * seconds / max(took, 1e-3))))


def _hold_the_interpreter_lock(xs, released):
    sorted(xs)
    released.wait(2.0)  # still in this frame when the clock looks


def test_a_c_call_holding_the_interpreter_lock_is_one_gil_freeze(tmp_path):
    xs, _ = _floats_sorting_for(0.35)
    path = str(tmp_path / "m.jsonl")
    mon = RunMonitor(path)
    try:
        _settle()
        mon.drain_host_clock()
        released = threading.Event()
        holder = threading.Thread(target=_hold_the_interpreter_lock, args=(xs, released), name="holder")
        t0 = time.perf_counter()
        holder.start()
        while holder.is_alive() and not mon.freezes:
            time.sleep(0.01)  # this thread too is frozen while the sort runs
        held = time.perf_counter() - t0
        _settle()
        released.set()
        holder.join(timeout=10)
        assert not holder.is_alive()
        drained = mon.drain_host_clock()
    finally:
        mon.close()
    freezes = _read(path, "freeze")
    assert freezes, "a sort of %.2f s wrote no kind=freeze" % held
    worst = max(freezes, key=lambda r: r["late_ms"])
    assert all(k in worst for k in SCHEMAS["freeze"])
    # Of the order of the hold: the clock may have been due up to a period
    # into it, and the machine may add to it.
    assert 0.35e3 - 1.5e3 * telemetry.CLOCK_PERIOD_S <= worst["late_ms"] <= 1e3 * held + 500.0, (worst["late_ms"], held)
    assert worst["classification"] == "gil" and worst["freeze_ms"] == worst["late_ms"]
    assert worst["cpu_ms"] >= 0.25 * worst["late_ms"] and worst["gc_ms"] < 0.5 * worst["late_ms"]
    assert worst["beats"] == 0
    assert "_hold_the_interpreter_lock" in worst["stacks"]["holder"]
    assert not any("telemetry-watchdog" in name for name in worst["stacks"])
    for k in ("majflt", "nivcsw"):  # getrusage is everywhere; the files may not be
        assert isinstance(worst["os_delta"][k], int)
    assert drained["freezes"] >= 1 and drained["freeze_max_ms"] == pytest.approx(worst["freeze_ms"], abs=0.01)
    assert drained["freeze_ms"] >= worst["freeze_ms"] - 0.01
    (summary,) = _read(path, "summary")
    assert summary["freezes"] >= 1 and summary["freeze_ms"] >= worst["freeze_ms"] - 0.01 and summary["stalls"] == 0


class _Cyclic:
    __slots__ = ("ref",)


def test_a_pause_of_the_collector_is_timed_exactly_and_classified_gc(tmp_path):
    path = str(tmp_path / "m.jsonl")
    mon = RunMonitor(path)
    was_enabled = gc.isenabled()
    gc.disable()  # no collection but the one below, on this thread or another
    try:
        objs = [_Cyclic() for _ in range(2_000_000)]
        for o in objs:
            o.ref = o
        del objs, o  # two million unreachable cycles
        _settle()
        before = mon.drain_host_clock()
        assert set(HOST_CLOCK_FIELDS) <= set(before)
        t0 = time.perf_counter()
        gc.collect()
        pause_ms = 1e3 * (time.perf_counter() - t0)
        _settle()
        drained = mon.drain_host_clock()
    finally:
        if was_enabled:
            gc.enable()
        mon.close()
    assert pause_ms >= 100.0, f"the collection took {pause_ms:.0f} ms: too short to be an event"
    assert drained["gc_collections"] == 1
    assert abs(drained["gc_ms"] - pause_ms) <= 0.10 * pause_ms, (drained, pause_ms)
    assert drained["gc_gen2_ms"] == drained["gc_ms"]  # gc.collect() is a full collection
    # The clock was due at most a period into the pause (and may have split it).
    assert drained["freezes"] >= 1 and drained["freeze_ms"] >= pause_ms - 1.5e3 * telemetry.CLOCK_PERIOD_S
    gcs = [r for r in _read(path, "freeze") if r["classification"] == "gc"]
    assert gcs, _read(path, "freeze")
    rec = max(gcs, key=lambda r: r["gc_ms"])
    assert rec["gc_ms"] >= 0.5 * rec["freeze_ms"] and rec["gc_ms"] >= 0.6 * pause_ms
    assert mon.drain_host_clock()["gc_collections"] == 0  # drained once, not twice


_CHILD = """
import sys, time
sys.path.insert(0, {repo!r})
from fast_tffm_tpu.telemetry import RunMonitor
mon = RunMonitor({path!r})
time.sleep(0.2)
print("READY", flush=True)
sys.stdin.readline()
mon.close()
"""


def test_a_stopped_process_is_an_off_cpu_freeze(tmp_path):
    path = str(tmp_path / "child.jsonl")
    child = subprocess.Popen(
        [sys.executable, "-c", _CHILD.format(repo=REPO, path=path)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
    )
    try:
        assert child.stdout.readline().strip() == "READY"
        os.kill(child.pid, signal.SIGSTOP)
        time.sleep(0.4)
        os.kill(child.pid, signal.SIGCONT)
        time.sleep(0.3)
        child.stdin.write("done\n")
        child.stdin.flush()
        assert child.wait(timeout=60) == 0
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    freezes = _read(path, "freeze")
    assert freezes, "0.4 s stopped and no kind=freeze"
    rec = max(freezes, key=lambda r: r["late_ms"])
    assert rec["late_ms"] >= 300.0
    assert rec["classification"] == "off-cpu", rec
    assert rec["cpu_ms"] < 0.25 * rec["late_ms"] and rec["gc_ms"] < 0.5 * rec["late_ms"]
    (summary,) = _read(path, "summary")
    assert summary["freezes"] >= 1 and summary["freeze_ms"] >= 300.0


class _Oversleeping(threading.Event):
    """The monitor's stop event, whose next wait lasts ``extra`` seconds
    longer than asked: a clock thread the scheduler did not wake, while
    every other thread ran."""

    extra = 0.0

    def wait(self, timeout=None):
        extra, self.extra = self.extra, 0.0
        return super().wait(timeout + extra)


def test_a_late_clock_with_heartbeats_meanwhile_is_not_a_freeze(tmp_path):
    path = str(tmp_path / "m.jsonl")
    mon = RunMonitor(path)
    try:
        mon._stop = _Oversleeping()
        _settle()
        mon.drain_host_clock()
        mon._stop.extra = 0.25
        t_end = time.perf_counter() + 0.45
        step = 0
        while time.perf_counter() < t_end:
            step += 1
            mon.heartbeat(step)  # Python runs, a flush or a step at a time
            time.sleep(0.004)
        _settle()
        drained = mon.drain_host_clock()
    finally:
        mon.close()
    records = _read(path, "freeze")
    late = [r for r in records if r["classification"] == "clock-late"]
    assert late, records
    rec = max(late, key=lambda r: r["late_ms"])
    assert rec["late_ms"] >= 200.0 and rec["freeze_ms"] == 0.0 and rec["beats"] >= 10
    # Its time stays out of freeze_ms: whatever the loaded machine froze for
    # real besides is in the other records, to the rounding.
    assert len(records) < telemetry.FREEZE_RECORDS_MAX
    real = sum(r["freeze_ms"] for r in records if r["classification"] != "clock-late")
    assert abs(drained["freeze_ms"] - real) < 0.05, (drained, records)


def test_two_monitors_each_drain_their_own_interval(tmp_path):
    a = RunMonitor(str(tmp_path / "a.jsonl"))
    b = RunMonitor(str(tmp_path / "b.jsonl"))
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        _settle()
        a.drain_host_clock()
        b.drain_host_clock()
        junk = [_Cyclic() for _ in range(100_000)]
        gc.collect()
        first_a = a.drain_host_clock()  # a's interval holds the collection...
        gc.collect()
        second_a, both_b = a.drain_host_clock(), b.drain_host_clock()  # ...b's holds both
        del junk
    finally:
        if was_enabled:
            gc.enable()
        a.close()
        b.close()
    assert first_a["gc_collections"] == 1 and second_a["gc_collections"] == 1
    assert both_b["gc_collections"] == 2
    assert both_b["gc_ms"] == pytest.approx(first_a["gc_ms"] + second_a["gc_ms"], abs=0.01)
    assert first_a["gc_ms"] > 0.0
    # One pair of process-wide callbacks however many monitors there were,
    # around every other library's (jax's own frees the runtime's garbage).
    assert gc.callbacks.count(telemetry._on_gc_start) == gc.callbacks.count(telemetry._on_gc_stop) == 1
    assert gc.callbacks[0] is telemetry._on_gc_start and gc.callbacks[-1] is telemetry._on_gc_stop


def test_no_sink_and_no_deadline_start_no_thread_and_close_joins_the_clock(tmp_path):
    before = threading.active_count()
    idle = RunMonitor(None)
    assert idle._watchdog is None and threading.active_count() == before
    assert idle.drain_host_clock() == {}  # no clock: the record lacks the fields
    idle.close()

    mon = RunMonitor(str(tmp_path / "m.jsonl"))
    assert mon._watchdog.is_alive() and threading.active_count() == before + 1  # one thread a monitor
    t0 = time.perf_counter()
    mon.close()
    assert time.perf_counter() - t0 < 2.0 and not mon._watchdog.is_alive()

    bare = RunMonitor(None, stall_timeout_s=5.0)  # a deadline alone still runs the one thread, as before
    assert bare._watchdog.is_alive()
    bare.close()
    assert not bare._watchdog.is_alive()


def test_the_memory_sample_times_itself(tmp_path):
    path = str(tmp_path / "m.jsonl")
    mon = RunMonitor(path, mem_every_s=0.001)
    time.sleep(0.01)
    sampler = threading.Thread(target=mon.on_dispatch, args=(1,), name="the-loop")
    sampler.start()
    sampler.join(timeout=60)
    mon.close()
    mems = _read(path, "mem")
    assert len(mems) == 2  # the due sample and the close record
    assert mems[0]["thread"] == "the-loop" and mems[1]["thread"] == threading.current_thread().name
    for r in mems:
        assert isinstance(r["sample_ms"], float) and 0.0 < r["sample_ms"] < 60e3


@pytest.mark.parametrize(
    "frozen, gc_s, cpu_s, want",
    [
        (0.30, 0.29, 0.30, "gc"),        # the collector covers it
        (0.30, 0.15, 0.00, "gc"),        # half is enough, whatever the CPU did
        (0.30, 0.10, 0.02, "off-cpu"),   # the process did not run
        (0.30, 0.00, 0.074, "off-cpu"),
        (0.30, 0.00, 0.076, "gil"),      # the process ran, Python did not
        (0.30, 0.10, 1.90, "gil"),       # many native threads busy
        (0.04, 0.30, 0.00, "clock-late"),  # a heartbeat arrived inside the threshold
    ],
)
def test_the_classification_rule(frozen, gc_s, cpu_s, want):
    assert telemetry._classify_freeze(frozen, gc_s, cpu_s) == want


def test_os_counters_are_numbers_or_null():
    c = telemetry.os_counters()
    assert set(c) == set(telemetry._OS_COUNTERS)
    assert all(v is None or (isinstance(v, int) and v >= 0) for v in c.values()), c
    assert isinstance(c["majflt"], int) and isinstance(c["nivcsw"], int)


def test_report_prints_freezes_apart_from_stalls_and_strict_passes(tmp_path):
    def synth(path, freeze):
        mon = RunMonitor(str(path), run_id=new_run_id())
        for i in range(1, 6):
            mon.emit("train", step=i * 4, epoch=0, loss=0.7 - 0.01 * i,
                     examples_per_sec=1000.0, examples_per_sec_per_chip=1000.0)
        if freeze:
            mon._on_late_wake(0.35, 0.35, 0, 0.0, 0.3, 0.0, {"nivcsw": 3}, {"MainThread": "..."})
        mon.close()
        return str(path)

    base, frozen = synth(tmp_path / "base.jsonl", False), synth(tmp_path / "frozen.jsonl", True)
    (rec,) = _read(frozen, "freeze")
    assert rec["classification"] == "gil" and rec["late_ms"] == 350.0
    tool = os.path.join(REPO, "tools", "report.py")
    run = lambda *args: subprocess.run([sys.executable, tool, *args], capture_output=True, text=True)
    r = run(frozen)
    assert r.returncode == 0, r.stderr
    assert "- stalls: 0" in r.stdout and "- freezes: 1 (350" in r.stdout
    assert "gil, clock 350.0 ms late" in r.stdout
    # A freeze is not a stall: the strict gate counts stalls and passes.
    r = run(frozen, "--compare", base, "--strict")
    assert r.returncode == 0, r.stdout + r.stderr
