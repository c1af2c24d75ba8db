#!/usr/bin/env python
"""Bisect the 235M-row rung regression (VERDICT r3 weak #1 / next #5).

BENCH_r02 measured a 234,881,024-row table; BENCH_r03's fresh-subprocess
probe got RESOURCE_EXHAUSTED at the same size.  This tool isolates WHICH
stage fails, each stage in its OWN fresh subprocess (one allocation
attempt per process, so no stage inherits another's buffers):

  alloc      build the [V, 9] table + [V, 1] row accumulator, value-sync
  alloc_el   same with the ELEMENT [V, 9] accumulator (2.2 GB more)
  step       alloc + compile + run one donated train step (the r02 regime)
  step_nodon step without donation (XLA must double-buffer the table)
  step_b4096 donated step at BATCH=4096 (VERDICT r4 #6: smaller per-step
             transients — isolates batch-sized temporaries from the table)
  step_packed lane-packed table + row accumulator + the sort-free COMPACT
             update (r5): [VP, 128] layout, O(M) transients — the scale
             regime's intended production path

Run with no args for the driver sweep over sizes around the regression;
`python tools/probe_scale_rung.py <stage> <vocab>` runs one stage.  The
sweep records the XLA_FLAGS in effect so flag-variation retries are
distinguishable artifacts (VERDICT r4 #6).  Prints one JSON dict.
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STAGES = ("alloc", "alloc_el", "step", "step_nodon", "step_b4096", "step_packed")
SIZES = (1 << 27, 201_326_592, 234_881_024, 251_658_240, 1 << 28)


def run_stage(stage: str, vocab: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial

    from bench import BATCH, NNZ, SCALE_K, forced_sync, make_batch, scale_state, zipf_ids
    from fast_tffm_tpu.models import FMModel
    from fast_tffm_tpu.optim import AdagradState
    from fast_tffm_tpu.trainer import TrainState, train_step_body

    t0 = time.perf_counter()
    if stage in ("alloc", "alloc_el"):
        state = scale_state(vocab, SCALE_K)
        if stage == "alloc_el":
            state = TrainState(
                state.table,
                AdagradState(jnp.full((vocab, 1 + SCALE_K), 0.1, jnp.float32)),
                {}, AdagradState({}), state.step,
            )
        forced_sync(state)
    elif stage == "step_packed":
        from fast_tffm_tpu.ops.packed_table import LANES, packed_rows, rows_per_tile
        from fast_tffm_tpu.trainer import make_packed_train_step

        rng = np.random.default_rng(0)
        model = FMModel(vocabulary_size=vocab, factor_num=SCALE_K, order=2)
        d = 1 + SCALE_K
        vp = packed_rows(vocab, d)
        table = jax.jit(
            lambda k: jax.random.uniform(k, (vp, LANES), jnp.float32, -0.01, 0.01)
        )(jax.random.key(0))
        state = TrainState(
            table, AdagradState(jnp.full((vp, rows_per_tile(d)), 0.1, jnp.float32)),
            {}, AdagradState({}), jnp.zeros((), jnp.int32),
        )
        step = make_packed_train_step(model, 0.01, "compact")
        b = make_batch(zipf_ids(rng, (BATCH, NNZ), vocab), 0)
        state, _ = step(state, b)
        forced_sync(state)
    else:
        rng = np.random.default_rng(0)
        model = FMModel(vocabulary_size=vocab, factor_num=SCALE_K, order=2)
        donate = () if stage == "step_nodon" else (0,)
        batch_size = 4096 if stage == "step_b4096" else BATCH
        step = jax.jit(
            partial(train_step_body, model, 0.01), donate_argnums=donate
        )
        b = make_batch(zipf_ids(rng, (batch_size, NNZ), vocab), 0)
        state = scale_state(vocab, SCALE_K)
        state, _ = step(state, b)
        forced_sync(state)
    print(f"OK {stage} vocab={vocab} {time.perf_counter() - t0:.1f}s", flush=True)
    raise SystemExit(0)


def main() -> None:
    res = {"xla_flags": os.environ.get("XLA_FLAGS", "")}
    for vocab in SIZES:
        for stage in STAGES:
            key = f"{stage}@{vocab}"
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), stage, str(vocab)],
                    capture_output=True, text=True, timeout=600,
                )
            except subprocess.TimeoutExpired:
                res[key] = "timeout600"
                print(json.dumps({key: res[key]}), flush=True)
                continue
            if r.returncode == 0:
                line = [l for l in r.stdout.splitlines() if l.startswith("OK")]
                res[key] = line[-1] if line else "ok"
            else:
                lines = [
                    l.strip() for l in (r.stderr or r.stdout).splitlines() if l.strip()
                ]
                err = next(
                    (l for l in reversed(lines) if "Error" in l or "error" in l),
                    lines[-1] if lines else "?",
                )
                res[key] = f"FAIL {err[:140]}"
            print(json.dumps({key: res[key]}), flush=True)
        # Stop probing bigger sizes once even the bare alloc fails — the
        # later stages are strictly harder.
        if str(res.get(f"alloc@{vocab}", "")).startswith(("FAIL", "timeout")):
            break
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] in STAGES:
        run_stage(sys.argv[1], int(sys.argv[2]))
    main()
