"""What the host lost all at once, as the program's own clock counted it.

``telemetry.RunMonitor``'s one thread only sleeps, so how late it wakes is
how long no Python thread of the process ran; every ``kind=train`` and
``kind=serving`` record carries the lateness past the threshold summed over
its interval (``freeze_ms``) beside the exact pauses of Python's collector
(``gc_ms``), and every ``kind=mem`` record what its sample cost the thread
that took it (``sample_ms``).  These readers are ``readers.telemetry_field``
with one difference: where NO record of the phase carries the field (a
program before the host clock) they return None and the metric is left out
of the line, where ``telemetry_field`` reads the 0 of an empty sum.
"""

from __future__ import annotations

from . import readers


def _values(m, ctx) -> list[float]:
    """The field of every record of the metric's kind in its phase
    (``readers._in_phase``: a train window by step; a serve cell's records
    carry no warm-up flag, so every one counts, as for every other serve
    metric; ``"phase": "all"`` takes the whole run)."""
    return [
        float(r[m["field"]])
        for r in ctx.get("records", [])
        if r.get("kind") == m["kind"]
        and readers._in_phase(r, m.get("phase", "window"), ctx["steps"])
        and isinstance(r.get(m["field"]), (int, float))
        and not isinstance(r[m["field"]], bool)
    ]


def window_sum(m, ctx):
    xs = _values(m, ctx)
    return sum(xs) if xs else None


def window_max(m, ctx):
    xs = _values(m, ctx)
    return max(xs) if xs else None
