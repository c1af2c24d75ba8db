"""The tiered parameter store's device work: its time by scope, and its share
of the roofline, from a traced window.

Each step of a tiered run stages its missed rows into the compact table's
staging slots under ``jax.named_scope("tier.stage")`` and fetches them back,
after the step, under ``tier.fetch`` (``paramstore/tiered.py``).  The rows a
step missed are the program's own count, ``kind=tiering``
``miss_rows_per_step``.  A program without those scopes or that record
gives None: the metric is left out."""

from __future__ import annotations

from . import dense, peaks, readers, scopes

_MISSES = {"kind": "tiering", "field": "miss_rows_per_step", "phase": "window"}


def stage_work(miss_rows: float, row_dim: int, accum_cols: int) -> int:
    """HBM bytes a step's staging cannot avoid: each missed row's table and
    accumulator columns read once from the shipped buffer and written once
    into the compact table, then read once more by the fetch."""
    return int(3 * miss_rows * 4 * (row_dim + accum_cols))


def _seconds(m, ctx):
    if not ctx.get("n_steps"):
        return None
    return dense.seconds_under(scopes._ops_of(ctx), m["scopes"])


def stage_ms(m, ctx):
    """Device ms a step under any of the scopes the metric file names
    (``scopes``), their intervals' union."""
    s = _seconds(m, ctx)
    return None if s is None else 1e3 * s / ctx["n_steps"]


def roofline(m, ctx):
    """The least time the chip needs for ``stage_work`` (HBM bounds it) over
    the device time under the scopes, in percent.  An element-wise
    accumulator, as the cell's configuration has it."""
    model, s = ctx.get("model"), _seconds(m, ctx)
    misses = readers.telemetry_field(_MISSES, ctx)
    if s is None or not misses or getattr(model, "row_dim", None) is None:
        return None
    least, _ = peaks.least_seconds(0, stage_work(misses, model.row_dim, model.row_dim), ctx["device_kind"])
    return 100.0 * least * ctx["n_steps"] / s
