#!/usr/bin/env python
"""Packed-wire acceptance probe (ISSUE 3): bytes cut + bitwise parity.

Two halves, one JSON:

  wire_bytes   the headline-shape (B=65536, nnz=39, vocab 2^24) all-ones
               FM workload streamed through BOTH wire formats, counting
               the ACTUAL bytes each format ships per step (packed: the
               coalesced buffer's nbytes; arrays: the sum of the five
               staged host arrays) and timing the per-batch staging call.
               The ≥2.5x cut criterion reads off `wire_cut_x`.
  parity       driver-level train runs, wire_format packed vs arrays, on
               an all-ones FMB set: streamed (K=1 and K=8 superbatch),
               device-cached, and sharded/SPMD (8-device virtual mesh) —
               final states compared BITWISE, logged losses record for
               record.  Runs in a CPU subprocess (the mesh paths need 8
               devices; parity is platform-independent logic).

The staging half runs on the default backend in a subprocess with a
timeout and records the platform it ran on; if that backend cannot start,
the probe fails — a staging time from another platform is not the number
this probe exists to take.

Writes PROBE_WIRE.json.  Usage:
  python tools/probe_wire.py [--rows 262144]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BATCH = 65536
NNZ = 39
VOCAB = 1 << 24

_STAGE_WORKER = textwrap.dedent(
    """
    import json, sys, time
    sys.path.insert(0, {repo!r})
    rows = int(sys.argv[1])
    import jax
    import numpy as np
    import bench
    from fast_tffm_tpu.data.binary import fmb_batch_stream, fmb_wire_flags
    from fast_tffm_tpu.data.wire import WireConverter, make_spec
    from fast_tffm_tpu.models import Batch

    B, N, V = {batch}, {nnz}, {vocab}
    path = bench.ensure_scale_fmb(V, rows=rows, all_ones=True)
    all_ones, _ = fmb_wire_flags([path])
    assert all_ones, "synthetic all-ones file must carry the v2 flag"

    def batches():
        return fmb_batch_stream(
            [path], batch_size=B, vocabulary_size=V, hash_feature_id=True,
            max_nnz=N, epochs=1, drop_remainder=True,
        )

    conv = WireConverter(make_spec(V, N, with_vals=False, with_fields=False))
    out = {{"platform": jax.default_backend(),
            "device_kind": getattr(jax.devices()[0], "device_kind", "cpu")}}

    def force(b):
        np.asarray(b.labels[:1])  # value dependency: staging really landed

    times = {{"packed": [], "arrays": []}}
    packed_bytes = arrays_bytes = steps = 0
    warm = True
    for _ in range(2):  # pass 1 warms page cache + compiles, pass 2 times
        for p, w in batches():
            t0 = time.perf_counter()
            bp = conv(p, w)
            force(bp)
            t1 = time.perf_counter()
            ba = Batch.from_parsed(p, w, with_fields=False)
            force(ba)
            t2 = time.perf_counter()
            if not warm:
                times["packed"].append(1e3 * (t1 - t0))
                times["arrays"].append(1e3 * (t2 - t1))
                packed_bytes += conv.last_nbytes
                arrays_bytes += (
                    ba.labels.nbytes + ba.ids.nbytes + ba.vals.nbytes
                    + ba.fields.nbytes + ba.weights.nbytes
                )
                steps += 1
        warm = False
    med = lambda xs: sorted(xs)[len(xs) // 2]
    out.update(
        steps=steps,
        packed_wire_bytes_per_step=packed_bytes // steps,
        arrays_wire_bytes_per_step=arrays_bytes // steps,
        wire_cut_x=round(arrays_bytes / packed_bytes, 3),
        packed_h2d_stage_ms_median=round(med(times["packed"]), 3),
        arrays_h2d_stage_ms_median=round(med(times["arrays"]), 3),
    )
    if out["platform"] == "cpu":
        out["staging_ms_note"] = (
            "on the cpu backend device_put is ~free (often zero-copy), so "
            "arrays 'staging' measures nothing while packed pays real host "
            "pack+verify cpu time; the stage-ms comparison only means "
            "something where an actual wire exists (PCIe) — the "
            "BYTE counts are the platform-independent acceptance metric, "
            "and the pack cost runs inside the prefetch thread, overlapped"
        )
    print("PROBE_JSON " + json.dumps(out), flush=True)
    """
).format(repo=REPO, batch=BATCH, nnz=NNZ, vocab=VOCAB)


_PARITY_WORKER = textwrap.dedent(
    """
    import json, os, sys, tempfile
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import numpy as np
    import jax
    from fast_tffm_tpu.config import Config
    from fast_tffm_tpu.data.binary import write_fmb
    from fast_tffm_tpu.training import dist_train, train
    from fast_tffm_tpu.parallel import make_mesh

    tmp = tempfile.mkdtemp()
    rng = np.random.default_rng(42)
    files = []
    for name, rows in (("a", 83), ("b", 41)):
        src = os.path.join(tmp, name + ".libsvm")
        with open(src, "w") as f:
            for _ in range(rows):
                nnz = rng.integers(1, 8)
                toks = [f"{{rng.integers(0, 1000)}}:1" for _ in range(nnz)]
                f.write(f"{{rng.integers(0, 2)}} {{' '.join(toks)}}\\n")
        files.append(write_fmb(src, src + ".fmb", vocabulary_size=1000))

    def cfg(tag, **kw):
        base = dict(
            model="fm", factor_num=4, vocabulary_size=1000,
            model_file=os.path.join(tmp, f"m_{{tag}}.ckpt"),
            train_files=tuple(files), epoch_num=2, batch_size=32,
            learning_rate=0.05, log_every=2,
            metrics_path=os.path.join(tmp, f"m_{{tag}}.jsonl"),
        )
        base.update(kw)
        return Config(**base).validate()

    def losses(tag):
        path = os.path.join(tmp, f"m_{{tag}}.jsonl")
        return [json.loads(l)["loss"] for l in open(path) if "loss" in json.loads(l)]

    def state_bits(st):
        return (np.asarray(st.table).tobytes(),
                np.asarray(st.table_opt.accum).tobytes(), int(st.step))

    silent = lambda *a: None
    out = {{}}
    runs = {{}}
    runs["streamed_arrays"] = train(cfg("sa", wire_format="arrays"), log=silent)
    runs["streamed_packed"] = train(cfg("sp", wire_format="packed"), log=silent)
    runs["streamed_packed_k8"] = train(
        cfg("sp8", wire_format="packed", steps_per_call=8), log=silent)
    runs["streamed_arrays_k8"] = train(
        cfg("sa8", wire_format="arrays", steps_per_call=8), log=silent)
    runs["device_cached"] = train(cfg("dc", device_cache=True), log=silent)
    runs["sharded_arrays"] = dist_train(
        cfg("da", wire_format="arrays"), log=silent, mesh=make_mesh(2, 4))
    runs["sharded_packed"] = dist_train(
        cfg("dp", wire_format="packed"), log=silent, mesh=make_mesh(2, 4))

    ref = state_bits(runs["streamed_arrays"])
    for name, st in runs.items():
        if name.startswith("sharded"):
            continue  # sharded compares packed-vs-arrays against itself below
        out[f"{{name}}_bitwise_vs_streamed_arrays"] = state_bits(st) == ref
    out["sharded_packed_bitwise_vs_sharded_arrays"] = (
        state_bits(runs["sharded_packed"]) == state_bits(runs["sharded_arrays"]))
    out["streamed_losses_match"] = losses("sa") == losses("sp")
    out["streamed_k8_losses_match"] = losses("sa8") == losses("sp8")
    out["sharded_losses_match"] = losses("da") == losses("dp")
    inrec = [json.loads(l) for l in open(os.path.join(tmp, "m_sp.jsonl"))]
    inrec = [r for r in inrec if r.get("kind") == "input"]
    if inrec:
        out["small_run_packed_wire_bytes_per_step"] = inrec[0]["wire_bytes_per_step"]
    print("PROBE_JSON " + json.dumps(out), flush=True)
    """
).format(repo=REPO)


def _run_worker(code, args=(), env=None, timeout=1500):
    r = subprocess.run(
        [sys.executable, "-c", code, *map(str, args)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**os.environ, **(env or {})},
    )
    for line in reversed((r.stdout or "").strip().splitlines()):
        if line.startswith("PROBE_JSON "):
            return json.loads(line[len("PROBE_JSON "):])
    tail = (r.stderr or r.stdout or "no output").strip().splitlines()
    raise RuntimeError("; ".join(tail[-3:])[-300:])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 18)
    ap.add_argument("--out", default=os.path.join(REPO, "PROBE_WIRE.json"))
    args = ap.parse_args(argv)

    res = {"batch": BATCH, "nnz": NNZ, "vocab": VOCAB, "fmb_rows": args.rows}

    # Staging A/B on the default backend; a backend that cannot start
    # raises out of the probe (no platform fallback).
    res["wire_bytes"] = _run_worker(_STAGE_WORKER, [args.rows], timeout=1500)
    print("wire_bytes ->", res.get("wire_bytes"), flush=True)

    try:
        res["parity"] = _run_worker(_PARITY_WORKER, timeout=1500)
    except (RuntimeError, subprocess.TimeoutExpired) as e:
        res["parity_error"] = str(e)[:300]
    print("parity ->", res.get("parity"), flush=True)

    wb = res.get("wire_bytes", {})
    par = res.get("parity", {})
    res["acceptance"] = {
        "wire_cut_x_ge_2p5": bool(wb.get("wire_cut_x", 0) >= 2.5),
        "all_parity_bitwise": bool(par) and all(
            v for k, v in par.items() if isinstance(v, bool)
        ),
    }
    from fast_tffm_tpu.telemetry import write_json_artifact

    write_json_artifact(args.out, res, sort_keys=False)
    print("wrote", args.out)
    return 0


if __name__ == "__main__":
    from fast_tffm_tpu.telemetry import arm_hang_exit

    arm_hang_exit(seconds=3300, what="probe_wire.py")
    raise SystemExit(main())
