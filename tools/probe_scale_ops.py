#!/usr/bin/env python
"""Decompose the giant-vocab (201M-row) packed train step op by op.

Round-5 question: at vocab 2^24 the packed dense step runs 478k ex/s at
B=16384 (34 ms), but at 201M rows EVERY tail strategy — rows, sorted,
dense-G's compact successor — lands at 79-105k (160-200 ms).  The update
strategy barely matters, so something else scales with VP.  This probe
times marginal fori_loop slopes (bench.forced_sync methodology) for each
stage at the scale shape and the headline shape in the SAME session:

  gather     packed_gather [M, 128] wide gather + slice extraction
  fwdbwd     full forward + backward, NO table update
  bitmap     touched scatter + cumsum over [VP] + slot gather (compact's
             VP-dependent piece)
  update     full packed_compact_adagrad_update
  step       the whole jitted train step (compact), bench-measured

All device arrays are passed as jit ARGUMENTS — a closed-over table would
embed GB-sized constants in the HLO.  Writes PROBE_SCALE_OPS_r05.json.
"""

import json
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fast_tffm_tpu.telemetry import arm_hang_exit

_watchdog = arm_hang_exit(seconds=3000, what="probe_scale_ops.py")

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import make_batch, zipf_ids
from fast_tffm_tpu.models import FMModel
from fast_tffm_tpu.optim import AdagradState
from fast_tffm_tpu.ops.packed_table import (
    LANES,
    packed_compact_adagrad_update,
    packed_gather,
    packed_rows,
    rows_per_tile,
)
from fast_tffm_tpu.trainer import TrainState, batch_loss, make_packed_train_step

BATCH = 16384
NNZ = 39
K = 8
D = 1 + K
P = rows_per_tile(D)


def slope_ms(jfn, args, k_lo=2, k_hi=8, reps=3):
    """Marginal ms per application: jfn(k, *args) chains k applications
    behind a value dependency; slope = (t_hi − t_lo) / (k_hi − k_lo)."""
    float(jfn(k_lo, *args))  # compile both
    float(jfn(k_hi, *args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(jfn(k_lo, *args))
        t_lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(jfn(k_hi, *args))
        t_hi = time.perf_counter() - t0
        best = min(best, (t_hi - t_lo) / (k_hi - k_lo))
    return round(best * 1e3, 3)


def probe_vocab(vocab: int) -> dict:
    rng = np.random.default_rng(0)
    model = FMModel(vocabulary_size=vocab, factor_num=K, order=2)
    vp = packed_rows(vocab, D)
    m = BATCH * NNZ
    k_cap = min(vp, m)

    table = jax.jit(
        lambda key: jax.random.uniform(key, (vp, LANES), jnp.float32, -0.01, 0.01)
    )(jax.random.key(0))
    accum = jnp.full((vp, P), 0.1, jnp.float32)
    batch = make_batch(zipf_ids(rng, (BATCH, NNZ), vocab), 0)
    ids = batch.ids
    g_rows = jnp.asarray(
        np.random.default_rng(1).normal(size=(BATCH, NNZ, D)).astype(np.float32)
        * 1e-3
    )

    out = {"vocab": vocab, "vp": vp, "m": m}

    @partial(jax.jit, static_argnums=(0,))
    def chain_gather(k, table, ids):
        def body(i, s):
            rows = packed_gather(table, jnp.bitwise_xor(ids, i), D)
            return s + rows[0, 0, 0]
        return jax.lax.fori_loop(0, k, body, jnp.float32(0))

    out["gather_ms"] = slope_ms(chain_gather, (table, ids))
    print(vocab, "gather_ms", out["gather_ms"], flush=True)

    @partial(jax.jit, static_argnums=(0,))
    def chain_fwdbwd(k, table, batch):
        def body(i, s):
            rows = packed_gather(table, jnp.bitwise_xor(batch.ids, i), D)
            (_, dl), (gr, _) = jax.value_and_grad(
                partial(batch_loss, model), argnums=(0, 1), has_aux=True
            )(rows, {}, batch)
            return s + gr[0, 0, 0] + dl
        return jax.lax.fori_loop(0, k, body, jnp.float32(0))

    out["fwdbwd_ms"] = slope_ms(chain_fwdbwd, (table, batch))
    print(vocab, "fwdbwd_ms", out["fwdbwd_ms"], flush=True)

    flat = ids.reshape(-1)

    @partial(jax.jit, static_argnums=(0,))
    def chain_bitmap(k, flat):
        def body(i, s):
            fl = jnp.bitwise_xor(flat, i)
            phys = (fl // P).astype(jnp.int32)
            touched = jnp.zeros((vp,), jnp.int8).at[phys].set(1, mode="drop")
            csum = jnp.cumsum(touched, dtype=jnp.int32)
            slot = csum[jnp.minimum(phys, vp - 1)] - 1
            return s + jnp.float32(slot[0])
        return jax.lax.fori_loop(0, k, body, jnp.float32(0))

    out["bitmap_cumsum_ms"] = slope_ms(chain_bitmap, (flat,))
    print(vocab, "bitmap_cumsum_ms", out["bitmap_cumsum_ms"], flush=True)

    @partial(jax.jit, static_argnums=(0,))
    def chain_update(k, table, accum, ids, g_rows):
        def body(i, carry):
            t, a, s = carry
            t, a = packed_compact_adagrad_update(
                t, a, jnp.bitwise_xor(ids, i), g_rows, 0.01
            )
            return t, a, s + t[0, 0]
        t, a, s = jax.lax.fori_loop(0, k, body, (table, accum, jnp.float32(0)))
        return s + a[0, 0]

    out["compact_update_ms"] = slope_ms(chain_update, (table, accum, ids, g_rows))
    print(vocab, "compact_update_ms", out["compact_update_ms"], flush=True)

    # Whole step, bench-measured for the same-session anchor.
    import bench

    state = TrainState(table=table, table_opt=AdagradState(accum), dense={},
                       dense_opt=AdagradState({}), step=jnp.zeros((), jnp.int32))
    step = make_packed_train_step(model, 0.01, "compact")
    batches = [make_batch(zipf_ids(rng, (BATCH, NNZ), vocab), i) for i in range(4)]
    state, rate = bench.measure(step, state, batches, iters=20, batch_size=BATCH)
    out["step_rate_per_chip"] = round(rate / jax.device_count(), 1)
    out["step_ms"] = round(BATCH / rate * 1e3 * jax.device_count(), 2)
    del state, table, accum
    return out


def main():
    res = {}
    for vocab in (1 << 24, 201_326_592):
        res[str(vocab)] = probe_vocab(vocab)
        print(vocab, "->", res[str(vocab)], flush=True)
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "PROBE_SCALE_OPS_r05.json")
    with open(out, "w") as f:
        json.dump(res, f, indent=1)
    print("wrote", out)


if __name__ == "__main__":
    main()
