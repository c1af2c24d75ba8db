#!/usr/bin/env python
"""Per-op roofline evidence for the scale-regime train step.

Answers two VERDICT-r2 questions with measurements, not prose:

1. Where does the 25 µs step actually go?  ``jax.profiler`` traces a few
   steps at the 235M-row regime and this script aggregates the device-side
   ("XLA Ops" thread) op durations — the itemized evidence behind the
   modeled-bytes keys bench.py emits.
2. Is "uniform ids faster than Zipf" a real effect or window-to-window drift?
   The two id distributions run through the SAME executable in
   INTERLEAVED windows (Z/U/Z/U/...), so any window-scale drift hits both
   equally; the per-distribution spread vs the cross-distribution gap
   separates measurement noise from a physical effect.

Prints one JSON object; run on the real chip.  Results land in DESIGN §6.
"""

import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fast_tffm_tpu.telemetry import arm_hang_exit

_watchdog = arm_hang_exit(seconds=2400, what="roofline.py")

import jax  # noqa: E402
import numpy as np  # noqa: E402

import bench as B  # noqa: E402  (reuses the rung size, batch maker, state builder)
from fast_tffm_tpu.models import FMModel  # noqa: E402
from fast_tffm_tpu.trainer import make_train_step  # noqa: E402


def window(step, state, batches, iters=20):
    """Marginal us/step, VALUE-SYNCED (bench.forced_sync): every wall
    rate closes over a fetch that depends on the final table."""
    t0 = time.perf_counter()
    for i in range(iters):
        state, loss = step(state, batches[i % len(batches)])
    B.forced_sync(state)
    return state, (time.perf_counter() - t0) / iters * 1e6  # us/step


def trace_steps(tag, step, state, batches, n=3):
    out_dir = f"/tmp/roofline_trace/{tag}"
    jax.profiler.start_trace(out_dir)
    for i in range(n):
        state, loss = step(state, batches[i % len(batches)])
    jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{out_dir}/plugins/profile/*/*.trace.json.gz"))[-1]
    d = json.loads(gzip.open(path).read())
    # Map (pid, tid) -> thread name, keep only the device "XLA Ops" rows.
    tids = {}
    for e in d.get("traceEvents", []):
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tids[(e.get("pid"), e.get("tid"))] = e["args"]["name"]
    ops = {}
    for e in d.get("traceEvents", []):
        if e.get("ph") == "X" and tids.get((e.get("pid"), e.get("tid"))) == "XLA Ops":
            ops.setdefault(e["name"], [0.0, 0])
            ops[e["name"]][0] += e.get("dur", 0.0)
            ops[e["name"]][1] += 1
    total = sum(v[0] for v in ops.values())
    top = sorted(ops.items(), key=lambda kv: -kv[1][0])[:12]
    return state, {
        "per_step_device_us": round(total / max(n, 1), 1),
        "ops": [
            {"op": k[:70], "us_per_step": round(v[0] / n, 1), "calls": v[1]}
            for k, v in top
        ],
    }


def setup(vocab_ladder, rng):
    for cand in vocab_ladder:
        model = FMModel(vocabulary_size=cand, factor_num=B.SCALE_K, order=2)
        step = make_train_step(model, learning_rate=0.01)
        zipf = [
            B.make_batch(B.zipf_ids(rng, (B.BATCH, B.NNZ), cand), i)
            for i in range(8)
        ]
        try:
            state = B.scale_state(cand, B.SCALE_K)
            state, loss = step(state, zipf[0])
            jax.block_until_ready(loss)
            return cand, step, state, zipf
        except Exception as e:
            print(f"# rung {cand} failed: {str(e)[:90]}", file=sys.stderr)
    raise SystemExit("no rung compiled")


def main():
    rng = np.random.default_rng(0)
    out = {"batch": B.BATCH, "nnz": B.NNZ, "device": str(jax.devices()[0])}

    def emit():
        print(json.dumps(out, indent=1), flush=True)

    # Id-distribution statistics from HOST-side draws (fetching device
    # ids next to the full-HBM state OOMs the transfer staging buffer —
    # measured RESOURCE_EXHAUSTED).
    stat_rng = np.random.default_rng(123)
    out["unique_ids_per_batch"] = {
        "zipf": int(np.unique(B.zipf_ids(stat_rng, (B.BATCH, B.NNZ), B.SCALE_VOCAB)).size),
        "uniform": int(np.unique(stat_rng.integers(0, B.SCALE_VOCAB, (B.BATCH, B.NNZ))).size),
    }
    emit()

    # --- interleaved A/B at the LARGEST rung (the headline regime) ---
    vocab, step, state, zipf = setup((B.SCALE_VOCAB,), rng)
    uni = [
        B.make_batch(rng.integers(0, vocab, size=(B.BATCH, B.NNZ)).astype(np.int32), 100 + i)
        for i in range(8)
    ]
    out["vocab"] = vocab
    state, _ = window(step, state, zipf, iters=30)  # warm both
    state, _ = window(step, state, uni, iters=30)
    inter = {"zipf": [], "uniform": []}
    for _ in range(5):
        state, us = window(step, state, zipf)
        inter["zipf"].append(round(us, 2))
        state, us = window(step, state, uni)
        inter["uniform"].append(round(us, 2))
    out["interleaved_us_per_step"] = inter
    emit()
    del state, step, zipf, uni

    # --- per-op traces at the 2^27 rung: the profiler needs HBM for its
    #     own buffers and OOMs next to the 8.9 GB headline state
    #     (measured); the step's op structure is identical, only the
    #     table rows differ. ---
    vocab_t, step, state, zipf = setup([1 << 27], rng)
    uni = [
        B.make_batch(rng.integers(0, vocab_t, size=(B.BATCH, B.NNZ)).astype(np.int32), 100 + i)
        for i in range(8)
    ]
    state, _ = window(step, state, zipf, iters=30)
    state, _ = window(step, state, uni, iters=30)
    out["trace_vocab"] = vocab_t
    for tag, bats in (("zipf", zipf), ("uniform", uni)):
        try:
            state, prof = trace_steps(f"{tag}_{vocab_t}", step, state, bats)
            out[f"profile_{tag}"] = prof
        except Exception as e:
            out[f"profile_{tag}"] = {"error": str(e)[:140]}
        emit()


if __name__ == "__main__":
    main()
