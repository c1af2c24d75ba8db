"""Device time BY SCOPE: which ``jax.named_scope`` of the program each device
op of a traced window ran under, and the per-layer metrics read from that.

The profiler keeps an op's scope where ``jax.profiler.ProfileData`` does not
show it: in the METADATA of the ``XLA Ops`` event, stat ``tf_op``
(``jit(step)/transpose(jvp(fm.interaction))/ffm.fieldsum/.../dot_general:``).
So the ``.xplane.pb`` is read here as protobuf wire format, the few fields of
XSpace / XPlane / XLine / XEvent / XEventMetadata / XStat that hold it and no
more.  A metric file names the scope by a prefix of one path component
(``"scope": "ffm."`` reads ``ffm.fieldsum``, ``jvp(ffm.diag)``, ...).  A trace
with no op of that scope (another model's cell, an executable served from a
cache that was compiled from unnamed source) gives None: the metric is left
out of the line.
"""

from __future__ import annotations

import glob
import json
import os
import re

from . import peaks, trace


# --- protobuf wire format, as much of it as an xplane needs ----------------


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i : i + ln], i + ln
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(buf):
    """(key, value message) of a ``map<int64, Message>`` entry."""
    k, msg = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            k = v
        elif f == 2:
            msg = v
    return k, msg


def _plane_ops(plane) -> tuple[str, list]:
    """(plane name, [(op name, tf_op, start_s, dur_s)] of its ``XLA Ops`` line)."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for f, v in _fields(plane):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            k, msg = _map_entry(v)
            event_meta[k] = msg
        elif f == 5:
            k, msg = _map_entry(v)
            stat_names[k] = next((_text(x) for g, x in _fields(msg) if g == 2), "")
    if not (name.startswith("/device:") and "TPU" in name):
        return name, []
    tf_op_ids = {k for k, n in stat_names.items() if n == "tf_op"}
    named = {}  # event metadata id -> (name, tf_op)

    def describe(mid):
        if mid not in named:
            nm, scope = "", ""
            for f, v in _fields(event_meta.get(mid, b"")):
                if f == 2:
                    nm = _text(v)
                elif f == 5:  # XStat: metadata_id = 1, str_value = 5, ref_value = 7
                    st = dict((g, x) for g, x in _fields(v) if g in (1, 5, 7))
                    if st.get(1) in tf_op_ids:
                        scope = _text(st[5]) if 5 in st else stat_names.get(st.get(7), "")
            named[mid] = (nm, scope)
        return named[mid]

    ops = []
    for line in lines:
        lname, t0_ns, events = "", 0, []
        for f, v in _fields(line):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        if lname != trace._OPS_LINE:
            continue
        for ev in events:
            mid = off_ps = dur_ps = 0
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off_ps = v
                elif f == 3:
                    dur_ps = v
            nm, scope = describe(mid)
            ops.append((nm, scope, t0_ns * 1e-9 + off_ps * 1e-12, dur_ps * 1e-12))
    return name, ops


def read_ops(trace_dir: str) -> dict:
    """{device plane: [(op name, tf_op, start_s, dur_s)]} of the newest
    ``.xplane.pb`` under ``trace_dir``; {} where there is none."""
    paths = sorted(glob.glob(os.path.join(trace_dir or "", "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {}
    with open(paths[-1], "rb") as f:
        space = memoryview(f.read())
    out = {}
    for f, plane in _fields(space):
        if f == 1:
            name, ops = _plane_ops(plane)
            if ops:
                out[name] = ops
    return out


def dump_ops(ops: dict, path: str, per_plane: int = 700) -> None:
    """The head of a trace's scoped ops as JSON: what this module's test reads."""
    t0 = min((s for ev in ops.values() for _, _, s, _ in ev), default=0.0)
    head = {p: [[_short(n), sc, s - t0, d] for n, sc, s, d in sorted(ev, key=lambda e: e[2])[:per_plane]] for p, ev in ops.items()}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(head, f)


def _short(name: str) -> str:
    """``fusion.7 f32[67108864,9]``, as ``trace._op_name`` names an event."""
    m = trace._HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name.split(" = ")[0].lstrip("%")[:80]


# --- seconds by scope --------------------------------------------------------


def _under(prefix: str):
    """Matches a ``tf_op`` with a path component that is the scope or a
    transform of it: ``ffm.`` finds ``.../ffm.diag/...`` and
    ``.../transpose(jvp(ffm.diag))/...``, not ``.../xffm.diag/...``."""
    return re.compile(r"(?:^|[/(])" + re.escape(prefix)).search


def scope_seconds(ops: dict, prefix: str) -> float | None:
    """Seconds in which an op under ``prefix`` ran (the union of their
    intervals, so that an op and the ops of its body count once), the mean
    over device planes; None where no op is under it."""
    under = _under(prefix)
    per_plane = [trace._length(trace._union((s, s + d) for _, sc, s, d in ev if under(sc))) for ev in ops.values()]
    if not per_plane or not any(per_plane):
        return None
    return sum(per_plane) / len(per_plane)


def by_scope(ops: dict) -> list:
    """[(scope, seconds, {op: seconds})], longest first, for PERF.md's table:
    every op filed under its ``tf_op`` from after ``jit(step)`` to its last
    component that holds a dotted scope name (``fm.tail``,
    ``transpose(jvp(ffm.diag))``); durations summed, the mean over planes."""
    dotted = re.compile(r"[a-z]\.[a-z]")
    table = {}
    for ev in ops.values():
        for name, sc, _, d in ev:
            parts = sc.rstrip(":").split("/")
            keep = max((i for i, p in enumerate(parts) if dotted.search(p)), default=0)
            scope = "/".join(parts[1 : keep + 1]) or "(no scope)"
            row, op = table.setdefault(scope, [0.0, {}]), _short(name)
            row[0] += d / len(ops)
            row[1][op] = row[1].get(op, 0.0) + d / len(ops)
    return sorted(((k, v[0], v[1]) for k, v in table.items()), key=lambda r: -r[1])


# --- the readers metric files name -------------------------------------------


def _ops_of(ctx) -> dict:
    if "scoped_ops" not in ctx:  # read once a line, shared by the metrics of a run
        ctx["scoped_ops"] = read_ops(ctx.get("trace_dir")) if ctx.get("trace") else {}
    return ctx["scoped_ops"]


def scope_ms(m, ctx):
    """Device ms a step under the scope the metric file names (``scope``)."""
    if not ctx.get("n_steps"):
        return None
    s = scope_seconds(_ops_of(ctx), m["scope"])
    return None if s is None else 1e3 * s / ctx["n_steps"]


def pair_interaction_work(rows: int, nnz: int, k: int, row_dim: int) -> tuple[int, int]:
    """(FLOPs, HBM bytes) a train step's field-aware pair interaction cannot
    avoid, fixed by the mathematics and not by the form computed: every pair
    i < j is a dot of k with two values and one sum forward (2k + 3) and twice
    that backward; the gathered rows are read once and their gradient is
    written once."""
    pairs = nnz * (nnz - 1) // 2
    return rows * pairs * 3 * (2 * k + 3), 2 * rows * nnz * row_dim * 4


WORK = {"pair_interaction": pair_interaction_work}


def scope_roofline(m, ctx):
    """The least time the chip needs for the necessary work of the layer the
    metric file names (``work``: a function of this module, given the cell's
    model) over the device time under its ``scope``, in percent."""
    model = ctx.get("model")
    shape = [getattr(model, a, None) for a in ("batch", "nnz", "k", "row_dim")]
    if not ctx.get("n_steps") or None in shape:
        return None
    s = scope_seconds(_ops_of(ctx), m["scope"])
    if s is None:
        return None
    least, _ = peaks.least_seconds(*WORK[m["work"]](*shape), ctx["device_kind"])
    return 100.0 * least * ctx["n_steps"] / s
