"""The few generic readers a per-layer metric file can name, and the look-up
of one that a later file brings: ``"reader": "<module>:<function>"`` is that
function of ``harness/<module>.py``, called as these are, ``(metric file,
context)``; the context is what the window gathered: the program's
``records``, the ``trace`` reduction and the ``trace_dir`` it was read from, the
window's ``steps`` and ``values``, the cell's ``model``.  A reader that finds nothing to read returns None and the
metric is left out of the line."""

from __future__ import annotations

import importlib
import json
import os
import statistics

from . import common, peaks


def read_jsonl(path: str) -> list[dict]:
    out = []
    if path and os.path.isfile(path):
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
    return out


def _in_phase(rec, phase, steps):
    if steps == "warmup_flag":  # serving: the program marks what it warms
        return phase == "all" or bool(rec.get("warmup")) == (phase == "setup")
    if phase == "window":
        return steps[0] < rec.get("step", -1) <= steps[1]
    if phase == "setup":
        return rec.get("step", -1) <= steps[0]
    return True


def telemetry_field(m, ctx):
    """mean | sum | median of one field of the program's telemetry records of
    one kind, over the window's steps or over set-up."""
    xs = [
        r[m["field"]]
        for r in ctx.get("records", [])
        if r.get("kind") == m["kind"] and _in_phase(r, m.get("phase", "window"), ctx["steps"])
        and isinstance(r.get(m["field"]), (int, float))
    ]
    if not xs and m.get("reduce") != "sum":
        return None
    return {"mean": statistics.fmean, "sum": sum, "median": statistics.median}[m.get("reduce", "mean")](xs)


def real_compiles(m, ctx):
    """Compile events minus persistent-cache hits, in set-up or in the window."""
    if "records" not in ctx:
        return None
    rs = [r for r in ctx["records"] if r.get("kind") == "compile" and _in_phase(r, m["phase"], ctx["steps"])]
    return float(sum(r.get("compiles", 0) - r.get("cache_hits", 0) for r in rs))


def trace_per_step(m, ctx):
    """One of the trace reduction's totals, in ms, over the window's steps."""
    red = ctx.get("trace")
    if not red or not ctx.get("n_steps") or not red.get(m["field"]):
        return None
    return 1e3 * red[m["field"]] / ctx["n_steps"]


def step_mfu(m, ctx):
    """The least time the chip needs for the step's necessary work (the larger
    of FLOPs over peak FLOP/s and bytes over peak HBM bytes/s; HBM bounds it
    here) over the device's busy time a step, in percent.  The step's work is
    that of all the cell's chips, the busy time the mean over them."""
    red = ctx.get("trace")
    if not red or not ctx.get("n_steps") or not red["busy_s"]:
        return None
    least, _ = peaks.least_seconds(ctx["step_flops"], ctx["step_bytes"], ctx["device_kind"])
    return 100.0 * least * ctx["n_steps"] / (red["busy_s"] * ctx.get("chips", 1))


def context_value(m, ctx):
    """A number the window itself worked out (serve latencies, lateness)."""
    v = ctx.get("values", {}).get(m["field"])
    return None if v is None else float(v)


READERS = {
    "telemetry_field": telemetry_field,
    "real_compiles": real_compiles,
    "trace_per_step": trace_per_step,
    "step_mfu": step_mfu,
    "context_value": context_value,
}


def reader(name: str):
    if name in READERS:
        return READERS[name]
    module, _, function = name.partition(":")
    if not function:
        raise SystemExit(f"no reader {name!r}: not one of {sorted(READERS)} and not <module>:<function>")
    return getattr(importlib.import_module(f"harness.{module}"), function)


def read_all(metrics: list[dict], ctx: dict) -> dict:
    out = {}
    for m in metrics:
        v = reader(m["reader"])(m, ctx)
        if v is not None:
            out[m["name"]] = common.metric(v, m["unit"])
    return out
