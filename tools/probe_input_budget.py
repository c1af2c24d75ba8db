#!/usr/bin/env python
"""Input→chip budget: per-stage rates at the headline batch (VERDICT r4 #5).

Separates the end-to-end streamed path into its stages, each measured in
isolation at the headline shape (B=65536, nnz=39, vocab 2^24, FMB input):

  fmb_read_rows_s    memmap FMB → numpy batch arrays (host only)
  h2d_bytes_s        device_put of one pre-read batch, value-synced
  step_rate          the device-only train step (same shapes)
  e2e_rate           stream → H2D → step with prefetch (the real path)

Plus the 2-PROCESS input-scaling artifact: the same sharded-input
global-batch assembly dist_train uses (block-cyclic line shards →
make_global_batch) driven by 1 vs 2 real OS processes over a localhost
jax.distributed CPU mesh, NO train step — the measured quantity is
parse+assembly throughput, which must scale with processes.

Writes PROBE_INPUT.json.  Usage:
  python tools/probe_input_budget.py [--skip-tpu] [--rows 400000]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import textwrap
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BATCH = 65536
NNZ = 39
VOCAB = 1 << 24


def tpu_stages(res: dict, rows: int) -> None:
    import jax
    import numpy as np

    import bench
    from fast_tffm_tpu.data.binary import fmb_batch_stream
    from fast_tffm_tpu.models import Batch, FMModel
    from fast_tffm_tpu.trainer import init_packed_state, make_packed_train_step

    path = bench.ensure_scale_fmb(VOCAB, rows=rows)

    def read_all():
        n = 0
        for parsed, w in fmb_batch_stream(
            [path], batch_size=BATCH, vocabulary_size=VOCAB,
            hash_feature_id=True, max_nnz=NNZ, epochs=1, drop_remainder=True,
        ):
            n += parsed.ids.shape[0]
        return n

    n = read_all()  # warm page cache
    t0 = time.perf_counter()
    n = read_all()
    res["fmb_read_rows_s"] = round(n / (time.perf_counter() - t0), 1)

    # One batch, H2D isolated (value-synced by fetching a corner element).
    parsed, w = next(iter(fmb_batch_stream(
        [path], batch_size=BATCH, vocabulary_size=VOCAB, hash_feature_id=True,
        max_nnz=NNZ, epochs=1, drop_remainder=True,
    )))
    host_arrays = [
        np.ascontiguousarray(parsed.ids.astype(np.int32)),
        np.ascontiguousarray(parsed.vals),
        np.ascontiguousarray(parsed.labels),
        np.ascontiguousarray(w),
    ]
    bytes_per_batch = sum(a.nbytes for a in host_arrays)
    res["h2d_bytes_per_batch"] = bytes_per_batch

    def h2d_once():
        devs = [jax.device_put(a) for a in host_arrays]
        for d in devs:
            np.asarray(d[..., :1] if d.ndim else d)  # force
        return devs

    h2d_once()
    times = []
    for _ in range(6):
        t0 = time.perf_counter()
        h2d_once()
        times.append(time.perf_counter() - t0)
    res["h2d_bytes_s"] = round(bytes_per_batch / min(times), 1)
    res["h2d_batch_ms_best"] = round(min(times) * 1e3, 2)
    res["h2d_batch_ms_median"] = round(sorted(times)[len(times) // 2] * 1e3, 2)

    # Device-only step rate at the same shapes (the bench headline regime).
    model = FMModel(vocabulary_size=VOCAB, factor_num=8, order=2)
    state = init_packed_state(model, jax.random.key(0), accumulator="row")
    step = make_packed_train_step(model, 0.01, "auto")
    rng = np.random.default_rng(0)
    batches = [
        bench.make_batch(bench.zipf_ids(rng, (BATCH, NNZ), VOCAB), i)
        for i in range(4)
    ]
    state, rate = bench.measure(step, state, batches, iters=10, batch_size=BATCH)
    res["step_rate"] = round(rate, 1)

    # End-to-end: stream → H2D → step, prefetch depth 8.
    from fast_tffm_tpu.utils.prefetch import prefetch

    def stream():
        raw = fmb_batch_stream(
            [path], batch_size=BATCH, vocabulary_size=VOCAB,
            hash_feature_id=True, max_nnz=NNZ, epochs=1, drop_remainder=True,
        )
        return prefetch(
            (Batch.from_parsed(p, w, with_fields=False) for p, w in raw), depth=8
        )

    count = 0
    for b in stream():  # warm
        state, _ = step(state, b)
        count += 1
    bench.forced_sync(state)
    t0 = time.perf_counter()
    for b in stream():
        state, _ = step(state, b)
    bench.forced_sync(state)
    dt = time.perf_counter() - t0
    res["e2e_rate"] = round(count * BATCH / dt, 1)


_WORKER = textwrap.dedent(
    """
    import sys, time, json
    pid, nproc, port, path, batch, nnz = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4],
        int(sys.argv[5]), int(sys.argv[6]))
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)
    if nproc > 1:
        jax.distributed.initialize(
            f"127.0.0.1:{{port}}", num_processes=nproc, process_id=pid)
    import numpy as np
    from fast_tffm_tpu.data.binary import fmb_batch_stream
    from fast_tffm_tpu.parallel import make_global_batch, make_mesh
    from fast_tffm_tpu.utils.prefetch import prefetch

    mesh = make_mesh(None, 1)  # [2*nproc, 1] global mesh
    local_bs = batch // nproc

    def stream():
        raw = fmb_batch_stream(
            [path], batch_size=local_bs, vocabulary_size={vocab},
            hash_feature_id=True, max_nnz=nnz, epochs=1,
            shard_index=pid, shard_count=nproc, shard_block=local_bs,
            drop_remainder=True,
        )
        return prefetch(
            ((make_global_batch(mesh, p, w, with_fields=False), p) for p, w in raw),
            depth=8,
        )

    n = 0
    for b, p in stream():  # warm (page cache, jit of stitching)
        n += 1
    t0 = time.perf_counter()
    m = 0
    for b, p in stream():
        # Force this process's shard of the assembled global array (a full
        # np.asarray would need non-addressable shards on nproc > 1).
        float(np.asarray(b.labels.addressable_shards[0].data)[0])
        m += 1
    dt = time.perf_counter() - t0
    print(json.dumps({{"pid": pid, "batches": m,
                       "rows_s": m * batch / dt / 1.0}}), flush=True)
    """
).format(repo=REPO, vocab=VOCAB)


def input_scaling(res: dict, rows: int) -> None:
    """1-process vs 2-process sharded parse+assembly (CPU mesh, no step)."""
    import bench

    path = bench.ensure_scale_fmb(VOCAB, rows=rows)
    out = {}
    for nproc in (1, 2):
        port = _free_port()
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _WORKER, str(pid), str(nproc), str(port),
                 path, str(BATCH), str(NNZ)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            for pid in range(nproc)
        ]
        rates = []
        try:
            for p in procs:
                o, e = p.communicate(timeout=900)
                if p.returncode:
                    out[f"p{nproc}_error"] = (e or o).strip().splitlines()[-1][-300:]
                    break
                rates.append(json.loads(o.strip().splitlines()[-1])["rows_s"])
            else:
                # Each process iterates the SAME global batches; the global
                # assembly rate is the slowest participant's.
                out[f"p{nproc}_rows_s"] = round(min(rates), 1)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()  # a timed-out/odd-exit peer must not linger
                    p.wait(timeout=5)  # reap — no zombies holding the port
    if "p1_rows_s" in out and "p2_rows_s" in out:
        out["scaling_x"] = round(out["p2_rows_s"] / out["p1_rows_s"], 2)
    res["input_scaling"] = out


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 19)
    ap.add_argument("--skip-tpu", action="store_true")
    ap.add_argument("--out", default=os.path.join(REPO, "PROBE_INPUT.json"))
    args = ap.parse_args(argv)

    res = {"batch": BATCH, "nnz": NNZ, "vocab": VOCAB, "fmb_rows": args.rows}
    if not args.skip_tpu:
        tpu_stages(res, args.rows)
        print("tpu stages ->", {k: v for k, v in res.items() if "rate" in k or "h2d" in k or "read" in k}, flush=True)
    input_scaling(res, args.rows)
    print("input scaling ->", res["input_scaling"], flush=True)
    from fast_tffm_tpu.telemetry import write_json_artifact

    write_json_artifact(args.out, res, sort_keys=False)
    print("wrote", args.out)
    return 0


if __name__ == "__main__":
    from fast_tffm_tpu.telemetry import arm_hang_exit

    arm_hang_exit(seconds=2700, what="probe_input_budget.py")
    raise SystemExit(main())
