"""The host clock's readers on hand-written records: a sum, a largest, and
None (the metric left out of the line) where no record carries the field,
which is what the parent commit's records look like under these files."""

from harness import cells, hostclock, readers

TRAIN = [
    {"kind": "train", "step": 4, "freeze_ms": 900.0, "gc_ms": 3.0},   # set-up's: the window opens at step 4
    {"kind": "train", "step": 8, "freeze_ms": 0.0, "gc_ms": 1.5},
    {"kind": "train", "step": 12, "freeze_ms": 127.25, "gc_ms": 0.25},
    {"kind": "train", "step": 16, "freeze_ms": 61.0, "gc_ms": 0.0},
    {"kind": "train", "step": 20, "freeze_ms": 5000.0, "gc_ms": 9.0},  # past the window's close
    {"kind": "mem", "step": 2, "sample_ms": 0.9},
    {"kind": "mem", "step": 12, "sample_ms": 2.75},
    {"kind": "mem", "step": 17, "sample_ms": 1.1},                     # the close record
    {"kind": "input", "step": 12, "freeze_ms": 77.0},                   # another kind
]
SERVE = [
    {"kind": "serving", "freeze_ms": 0.0, "gc_ms": 2.0},
    {"kind": "serving", "freeze_ms": 2310.5, "gc_ms": 0.5},
    {"kind": "serving", "freeze_ms": 0.0, "gc_ms": 0.0},
    {"kind": "mem", "sample_ms": 1.9},
    {"kind": "mem", "sample_ms": 0.6},
]


def _metric(name, kind):
    (m,) = [m for m in cells.load_metrics(kind) if m["name"] == name]
    return m


def test_sum_over_the_windows_records_and_largest_of_the_runs():
    ctx = {"records": TRAIN, "steps": (4, 16)}
    assert hostclock.window_sum(_metric("host.freeze_ms", "train"), ctx) == 188.25
    assert hostclock.window_sum(_metric("host.gc_ms", "dist_train"), ctx) == 1.75
    assert hostclock.window_max(_metric("host.mem_sample_ms", "train"), ctx) == 2.75  # every sample of the run
    ctx = {"records": SERVE, "steps": "warmup_flag"}
    assert hostclock.window_sum(_metric("serve.freeze_ms", "serve"), ctx) == 2310.5
    assert hostclock.window_sum(_metric("serve.gc_ms", "serve"), ctx) == 2.5
    assert hostclock.window_max(_metric("serve.mem_sample_ms", "serve"), ctx) == 1.9


def test_a_quiet_window_reads_zero_and_a_program_without_the_clock_reads_nothing():
    quiet = [{"kind": "train", "step": s, "freeze_ms": 0.0, "gc_ms": 0.0} for s in (8, 12)]
    assert hostclock.window_sum(_metric("host.freeze_ms", "train"), {"records": quiet, "steps": (4, 12)}) == 0.0
    # The parent's records: the kinds are there, the fields are not.
    strip = lambda rs: [{k: v for k, v in r.items() if k not in ("freeze_ms", "gc_ms", "sample_ms")} for r in rs]
    for kind, recs, steps in (("train", TRAIN, (4, 16)), ("serve", SERVE, "warmup_flag")):
        host = [m for m in cells.load_metrics(kind) if m["layer"] == "host (telemetry)"]
        assert len(host) == 3
        assert readers.read_all(host, {"records": strip(recs), "steps": steps}) == {}
        assert readers.read_all(host, {"records": [], "steps": steps}) == {}
        full = readers.read_all(host, {"records": recs, "steps": steps})
        assert set(full) == {m["name"] for m in host} and all(v["unit"] == "ms" for v in full.values())
    # A flag is not a number, and a null is "could not be read".
    odd = [{"kind": "train", "step": 8, "freeze_ms": None, "gc_ms": True}]
    assert hostclock.window_sum(_metric("host.freeze_ms", "train"), {"records": odd, "steps": (4, 8)}) is None
    assert hostclock.window_sum(_metric("host.gc_ms", "train"), {"records": odd, "steps": (4, 8)}) is None
