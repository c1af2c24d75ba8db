"""Reduction of a profiler trace to numbers.  Two stages: ``read_xplane``
turns the ``.xplane.pb`` into plain events, ``reduce`` turns events into busy
time, idle share, op totals, collective time and idle gaps.  The second stage
is checked on a recorded trace kept in ``tests/``."""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(r"all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute")
_OPS_LINE = "XLA Ops"


def read_xplane(trace_dir: str) -> dict:
    """{"device": {plane: [(name, start_s, dur_s)]}, "host": [(name, start_s, dur_s)]}.
    Device events are those of each device plane's ``XLA Ops`` line; host
    events those of every host thread."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"device": {}, "host": []}
    data = ProfileData.from_file(paths[-1])
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (_op_name(ev), ev.start_ns * 1e-9, ev.duration_ns * 1e-9) for ev in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9) for ev in line.events)
    return {"device": device, "host": host}


_HLO = re.compile(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])")


def _op_name(ev) -> str:
    """``fusion.7 f32[67108864,9]``: the op with its result shape, so that a
    fusion over the table can be told from one over a batch.  The trace names
    an op by its whole HLO line."""
    m = _HLO.match(ev.name)
    return f"{m.group(1)} {m.group(2)}" if m else ev.name.split(" = ")[0].lstrip("%")[:80]


def _union(intervals):
    """Merged, sorted [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return sum(e - s for s, e in merged)


def _subtract(a, b) -> float:
    """Length of merged ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def reduce(events: dict, top: int = 10) -> dict | None:
    """Busy seconds (union of op intervals, averaged over devices), the window
    (first op start to last op end over all devices), op totals, collective
    seconds and their exposed part, and idle gaps named by the host event that
    covers most of each.  None where no device op was traced."""
    planes = {p: ev for p, ev in events["device"].items() if ev}
    if not planes:
        return None
    t0 = min(s for ev in planes.values() for _, s, _ in ev)
    t1 = max(s + d for ev in planes.values() for _, s, d in ev)
    busy, coll, exposed, ops = [], [], [], {}
    for ev in planes.values():
        merged = _union((s, s + d) for _, s, d in ev)
        busy.append(_length(merged))
        c = _union((s, s + d) for n, s, d in ev if COLLECTIVE.search(n))
        other = _union((s, s + d) for n, s, d in ev if not COLLECTIVE.search(n))
        coll.append(_length(c))
        exposed.append(_subtract(c, other))
        for n, _, d in ev:
            ops[n] = ops.get(n, 0.0) + d / len(planes)
    first = next(iter(planes.values()))
    merged = _union((s, s + d) for _, s, d in first)
    edges = [t0] + [x for se in merged for x in se] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n = len(planes)
    return {
        "busy_s": sum(busy) / n,
        "window_s": t1 - t0,
        "collective_s": sum(coll) / n,
        "collective_exposed_s": sum(exposed) / n,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": _name_gaps(gaps, events["host"], top),
    }


def _name_gaps(gaps, host, top):
    """Total idle seconds by the host event that overlaps each gap most (the
    shortest such event on a tie); the many short gaps go into one bucket."""
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    named, long_gaps = {}, gaps[:200]
    rest = gaps[200:]
    host = sorted(host, key=lambda e: e[1])
    for s, e in long_gaps:
        best, best_key = "no_host_span", (0.0, 0.0)
        for n, hs, hd in host:
            if hs >= e:
                break
            ov = min(e, hs + hd) - max(s, hs)
            if ov > 0 and (ov, -hd) > best_key:
                best, best_key = n, (ov, -hd)
        named[best] = named.get(best, 0.0) + (e - s)
    if rest:
        named[f"{len(rest)}_gaps_under_{(rest[0][1] - rest[0][0]) * 1e3:.3f}_ms"] = sum(e - s for s, e in rest)
    return sorted(named.items(), key=lambda kv: -kv[1])[:top]


def dump_events(events: dict, path: str, per_plane: int = 400, host: int = 400) -> None:
    """A short head of a trace's events as JSON: the recorded trace that the
    reduction's test reads."""
    import json

    t0 = min((s for ev in events["device"].values() for _, s, _ in ev), default=0.0)
    cut = lambda evs, n: [[nm, s - t0, d] for nm, s, d in sorted(evs, key=lambda e: e[1])[:n]]
    t_end = max((s + d for ev in events["device"].values() for _, s, d in sorted(ev, key=lambda e: e[1])[:per_plane]), default=0.0)
    out = {
        "device": {p: cut(ev, per_plane) for p, ev in events["device"].items()},
        "host": cut([e for e in events["host"] if t0 <= e[1] <= t_end], host),
    }
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f)
