#!/usr/bin/env python
"""A/B probe: packed sparse-tail strategies at the giant-vocab scale rung.

Measures, each config in its OWN subprocess (one state per process: the
element-accumulator variants are expected to exhaust device memory):

  rows      the r4 scale-rung step (rows layout, row accumulator) — baseline
  compact   lane-packed table + sort-free touched-row compaction
            (ops/packed_table.py:packed_compact_adagrad_update), row accum
  compact-element / sorted-element
            element-accumulator variants (packed element accum is a second
            table-sized array — expected to OOM at the 201M rung; recorded)

Writes PROBE_COMPACT_r05.json at the repo root.  Usage:
  python tools/probe_compact.py                 # full ladder
  python tools/probe_compact.py --one CFG VOCAB BATCH   # one config, one line
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VOCAB = 201_326_592
BATCH = 16384
K = 8


def _one(cfg: str, vocab: int, batch: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from fast_tffm_tpu.models import FMModel
    from fast_tffm_tpu.optim import AdagradState
    from fast_tffm_tpu.trainer import TrainState, make_train_step, make_packed_train_step
    from fast_tffm_tpu.ops.packed_table import LANES, packed_rows, rows_per_tile

    rng = np.random.default_rng(0)
    model = FMModel(vocabulary_size=vocab, factor_num=K, order=2)
    batches = [
        bench.make_batch(bench.zipf_ids(rng, (batch, bench.NNZ), vocab), i)
        for i in range(4)
    ]

    if cfg == "rows":
        step = make_train_step(model, learning_rate=0.01)
        state = bench.scale_state(vocab, K)
    elif cfg in ("fused", "fused-dense", "fused-capped"):
        # The ONE fused-state builder lives in bench.py — duplicating the
        # stride-(d+1) lane init here would let the probe drift from what
        # the bench actually measures.
        state = bench.fused_scale_state(vocab, K)
        step = make_packed_train_step(
            model, learning_rate=0.01,
            update="dense" if cfg == "fused-dense" else "compact",
            # Zipf(1.1) at B=65536 measures ~0.5M unique physical rows;
            # cap at 2^20 with the exact lax.cond fallback.
            compact_cap=(1 << 20) if cfg == "fused-capped" else 0,
        )
    else:
        update, accum = {
            "compact": ("compact", "row"),
            "compact-element": ("compact", "element"),
            "sorted-element": ("sorted", "element"),
            "dense": ("dense", "row"),
        }[cfg]
        d = 1 + K
        p = rows_per_tile(d)
        vp = packed_rows(vocab, d)

        from functools import partial

        @partial(jax.jit, static_argnums=(1, 2))
        def mk(key, n, c):
            return jax.random.uniform(key, (n, c), jnp.float32, -0.01, 0.01)

        acc_cols = p if accum == "row" else LANES
        state = TrainState(
            table=mk(jax.random.key(0), vp, LANES),
            table_opt=AdagradState(jnp.full((vp, acc_cols), 0.1, jnp.float32)),
            dense={},
            dense_opt=AdagradState({}),
            step=jnp.zeros((), jnp.int32),
        )
        step = make_packed_train_step(model, learning_rate=0.01, update=update)

    state, rate = bench.measure(step, state, batches, iters=20, batch_size=batch)
    print(json.dumps({"cfg": cfg, "vocab": vocab, "batch": batch,
                      "rate_per_chip": round(rate / jax.device_count(), 1)}))


def main() -> None:
    results = {"vocab": VOCAB, "batch": BATCH, "configs": {}}
    for cfg in ("compact", "rows", "compact-element", "sorted-element"):
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one", cfg,
                 str(VOCAB), str(BATCH)],
                capture_output=True, text=True, timeout=1500,
            )
        except subprocess.TimeoutExpired:
            results["configs"][cfg] = {"error": "timeout (1500s)"}
            continue
        line = (r.stdout or "").strip().splitlines()
        if r.returncode == 0 and line:
            results["configs"][cfg] = json.loads(line[-1])
        else:
            err = [l for l in (r.stderr or "").strip().splitlines() if l][-3:]
            results["configs"][cfg] = {"error": " | ".join(err)[-400:]}
        print(cfg, "->", results["configs"][cfg], flush=True)
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "PROBE_COMPACT_r05.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote", out)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--one":
        _one(sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
    else:
        main()
