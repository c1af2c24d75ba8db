"""What every kind of cell shares: the look for the chip, the profiler, the
memory reading, the result line."""

from __future__ import annotations

import json
import math
import os
import sys

from . import peaks


def device_info(chips: int, require_chip: bool = True) -> dict:
    """The device as JAX reports it.  Off the TPU, or with fewer chips than
    the cell asks for, or on a chip the peaks table lacks: exit non-zero."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}
    if require_chip:
        if info["platform"] != "tpu":
            raise SystemExit(f"no accelerator: JAX reports platform {info['platform']!r}")
        peaks.peaks_for(info["kind"])
        if len(devs) < chips:
            raise SystemExit(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return info


def configured(cell: dict, name: str, workroot: str, train_file: str = ""):
    """An emptied work directory holding the cell's INI file, with the model
    file, the telemetry and (for a train cell) the input file ``train_file``
    pointed into it, and the compile cache where the program keeps it;
    (directory, loaded Config)."""
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.telemetry import enable_compilation_cache

    from . import cells

    work = cells.fresh_workdir(name, workroot)
    ini = {s: dict(kv) for s, kv in cell["ini"].items()}
    ini["General"]["model_file"] = os.path.join(work, "model.ckpt")
    ini["Train"]["metrics_path"] = os.path.join(work, "metrics.jsonl")
    if train_file:
        ini["Train"]["train_files"] = os.path.join(work, train_file)
    cfg = load_config(cells.write_ini(os.path.join(work, "cell.cfg"), ini))
    enable_compilation_cache(cfg.telemetry_compilation_cache_dir)
    return work, cfg


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def start_trace(trace_dir: str) -> None:
    """Device and host TraceMe events; the Python tracer stays off, since it
    slows exactly the host paths the serve cell measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def reduce_trace(result: dict, trace_dir: str, keep_events=None) -> dict | None:
    """Read and reduce the traced window; put busy and window seconds and the
    breakdown into ``result``.  Returns the reduction (None: no device op)."""
    from . import trace

    events = trace.read_xplane(trace_dir)
    if keep_events:
        trace.dump_events(events, keep_events)
    red = trace.reduce(events)
    if red:
        result["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    return red


def decide(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit; correct when every one that has
    a limit is finite and within it."""
    compared = {}
    ok = bool(numbers)
    for name, value in numbers.items():
        limit = limits.get(name)
        if limit is None:
            continue
        compared[name] = {"value": value, "limit": limit}
        ok = ok and math.isfinite(value) and value <= limit
    return ok and bool(compared), compared


def emit(result: dict) -> None:
    """Compared numbers as the last lines of stderr, the result as the last
    line of stdout with ``compared`` as its last key."""
    compared = result.pop("compared", {})
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    result["compared"] = compared
    print(json.dumps(result), flush=True)


def phases(t_start: float):
    """``phase(name)`` prints how long after the process's start it was
    reached (where a run's time goes, on stderr) and returns those seconds."""
    import time

    def phase(name):
        at = time.time() - t_start
        print(f"phase {name}: {at:.1f} s", file=sys.stderr, flush=True)
        return at

    return phase


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def remove_tree(path: str) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)
    parent = os.path.dirname(path)
    try:
        os.rmdir(parent)
    except OSError:
        pass
