#!/usr/bin/env python3
"""The open-loop load generator: processes of their own that never import jax.

    python3 loadgen.py <spec.json> <shard>

Frames of ``frame_rows`` x ``nnz`` over ``connections`` pipelined FMD1
connections (the program's public ``FrameConnection``), Poisson arrivals at a
rate fixed in the spec: ``round(rate * seconds)`` arrival times, sorted.  They
are ONE stream for every seed: the seed draws only the order of the window's
half-second blocks of it, so every seed sends the same arrivals in another
order and the queue wait they make is the same work (two runs of one seed read
``serve_p50_ms`` 0.05% apart where three seeds read 1.8% apart, PERF.md
section 2).  A frame is timed from when it was DUE to its last reply row.
Frames are drawn from a pool made from the seed, in an order drawn from the
seed.

The schedule is one; ``processes`` shards send it, shard p the frames
i = p mod processes over its share of the connections, because one Python
process answering 100k rows a second runs tens of milliseconds late.  All
shards read the wall clock.  stdout protocol: ``READY``, then (after
``GO <unix time of the schedule's zero>`` on stdin) ``DONE`` after the drain;
results go to ``spec["out"] + ".<shard>.npz"``.
"""

import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


BLOCK_S = 0.5


def _blocks_reordered(t, span: float, rng):
    """The sorted times ``t`` in [0, span) with their whole blocks of BLOCK_S
    in the order ``rng`` draws; what is left past the last whole block stays
    last.  A time keeps its place inside its block."""
    k = int(span // BLOCK_S)
    if k < 2:
        return t
    block = np.minimum((t // BLOCK_S).astype(int), k)
    place = np.arange(k + 1)
    place[rng.permutation(k)] = np.arange(k)
    return np.sort(place[block] * BLOCK_S + (t - block * BLOCK_S))


def schedule(seed: int, rate_frames: float, warm_s: float, seconds: float, pool: int):
    """(due times [n], pool index [n], n_warm).  The warm-up's frames come
    first, then the window's, one Poisson stream with a fixed count each: the
    same stream whatever the seed, which orders the window's blocks of it and
    draws the frames sent."""
    stream, rng = np.random.default_rng(7), np.random.default_rng([int(seed), 7])
    n_warm, n_win = int(round(rate_frames * warm_s)), int(round(rate_frames * seconds))
    warm, window = np.sort(stream.random(n_warm)) * warm_s, np.sort(stream.random(n_win)) * seconds
    due = np.concatenate([warm, warm_s + _blocks_reordered(window, seconds, rng)])
    return due, rng.integers(0, pool, size=due.size), n_warm


def pool_rows(seed: int, spec: dict):
    from harness import gen

    return gen.rows_from_seed(seed, spec["pool_frames"] * spec["frame_rows"], spec["nnz"], spec["vocab"], spec.get("zipf_alpha", 2.5))


def main(argv=None) -> int:
    from fast_tffm_tpu.serving.client import FrameConnection
    from fast_tffm_tpu.serving.protocol import FRAME_HEADER, pack_request_frame

    argv = argv or sys.argv[1:]
    spec, shard = json.load(open(argv[0])), int(argv[1])
    rows, nnz, pool, procs = spec["frame_rows"], spec["nnz"], spec["pool_frames"], spec["processes"]
    _, ids, vals = pool_rows(spec["seed"], spec)
    ids, vals = ids.reshape(pool, rows, nnz), vals.reshape(pool, rows, nnz)
    zero = np.zeros(rows, np.uint32)
    packed = [bytearray(pack_request_frame(zero, ids[p], vals[p])) for p in range(pool)]
    head = FRAME_HEADER.size
    due, which, n_warm = schedule(spec["seed"], spec["rate_rows_per_s"] / rows, spec["warm_seconds"], spec["seconds"], pool)
    mine = list(range(shard, due.size, procs))
    left = {i: rows for i in mine}
    done, sent = {}, {}
    clock = time.time

    def on_result(rid, st, sc):  # per row, under the connection's lock: keep it short
        f = rid // rows
        left[f] -= 1
        if not left[f]:
            done[f] = clock()

    conns = [FrameConnection(spec["port"], on_result=on_result) for _ in range(max(1, spec["connections"] // procs))]
    if min(c.max_frame_rows for c in conns) < rows:
        raise SystemExit(f"the replica takes frames of {conns[0].max_frame_rows} rows, the mix sends {rows}")
    print("READY", flush=True)
    go = sys.stdin.readline().split()
    if not go or go[0] != "GO":
        return 2
    t0 = float(go[1])
    for n, i in enumerate(mine):
        at = t0 + due[i]
        wait = at - clock()
        if wait > 0.002:
            time.sleep(wait - 0.001)
        while clock() < at:
            pass
        buf = packed[which[i]]
        req = np.arange(i * rows, (i + 1) * rows, dtype=np.uint32)
        buf[head : head + 4 * rows] = req.tobytes()
        sent[i] = clock()
        conns[n % len(conns)].send_packed(bytes(buf), req)
    t_end = clock() + spec["drain_seconds"]
    while len(done) < len(mine) and clock() < t_end:
        time.sleep(0.01)
    t_drained = clock()
    status = np.zeros((len(mine), rows), np.uint8)  # 0 none, 1 ok, 2 refused or errored
    score = np.zeros((len(mine), rows), np.float32)
    results = {}
    for c in conns:
        c.close()
        results.update(c.results)
    for n, i in enumerate(mine):
        for j in range(rows):
            r = results.get(i * rows + j)
            if r is not None:
                status[n, j], score[n, j] = (1 if r[0] == "ok" else 2), r[1]
    np.savez(
        f"{spec['out']}.{shard}.npz", frame=np.array(mine), status=status, score=score, t_drained=t_drained,
        sent=np.array([sent[i] for i in mine]), done=np.array([done.get(i, np.nan) for i in mine]),
    )
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
