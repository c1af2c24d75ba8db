#!/usr/bin/env python
"""Round-4 re-measurement of the artifact-era non-decisions (VERDICT r3 #2).

Every DESIGN §6/§8.5 bullet measured before the round-3 methodology
correction is re-stamped here with value-synced, same-window INTERLEAVED
A/B timing (bench.forced_sync closes every window):

  1. bf16 parameter table — rows layout (the original "2× slower" claim)
     and the packed layout, where bf16 halves table bytes on both the
     wide gather and the dense Adagrad sweep.
  2. dedup-before-forward-gather — plus the structural note: under jit
     the unique-row count must be a STATIC shape, so "gather fewer rows"
     is only realizable as gather-same-count-sorted; the measurable
     lever is sorted-id locality, which is what we time.
  3. [V, 2D] (and packed [VP, 256]) table+accum interleave for the
     sorted sparse tail's RMW.
  4. XLA wide-gather effective bandwidth (the "Pallas gather has no
     headroom" input: if XLA's gather already rides the HBM roof there
     is no headroom; if not, the gap IS the Pallas headroom).

Prints one JSON dict; partial results flush on exit.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fast_tffm_tpu.telemetry import arm_hang_exit

_watchdog = arm_hang_exit(seconds=3000, what="probe_nondecisions.py")

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

from bench import make_batch, zipf_ids
from fast_tffm_tpu.models import FMModel
from fast_tffm_tpu.optim import AdagradState, sparse_adagrad_update
from fast_tffm_tpu.ops.packed_table import (
    LANES,
    packed_dense_adagrad_update,
    packed_gather,
    rows_per_tile,
)
from fast_tffm_tpu.trainer import (
    TrainState,
    batch_loss,
    init_packed_state,
    make_packed_train_step,
)

NNZ = 39
K = 8
B = 16384


def _sync(state):
    """forced_sync for TrainState OR (table, ...) tuples: value-fetch a
    slice of the first table-like array so the chained updates must have
    landed (bench.forced_sync rationale)."""
    t = state.table if hasattr(state, "table") else state[0]
    return float(jnp.sum(jax.lax.dynamic_slice_in_dim(t, 0, 2, axis=0)))


def interleaved(step_a, state_a, step_b, state_b, batches, iters, rounds=3):
    """Median per-step seconds for A and B, timed in ALTERNATING windows
    of the same session (A B A B ...), each window closed by a value
    fetch that depends on the final table (forced_sync)."""
    state_a, _ = step_a(state_a, batches[0])
    _sync(state_a)
    state_b, _ = step_b(state_b, batches[0])
    _sync(state_b)
    ta, tb = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for i in range(iters):
            state_a, _ = step_a(state_a, batches[i % len(batches)])
        _sync(state_a)
        ta.append((time.perf_counter() - t0) / iters)
        t0 = time.perf_counter()
        for i in range(iters):
            state_b, _ = step_b(state_b, batches[i % len(batches)])
        _sync(state_b)
        tb.append((time.perf_counter() - t0) / iters)
    return float(np.median(ta)), float(np.median(tb)), state_a, state_b


def main():
    rng = np.random.default_rng(0)
    res = {"device": jax.devices()[0].device_kind}
    import atexit

    atexit.register(lambda: print(json.dumps(res), flush=True))

    def mark(name):
        print(f"# section {name} @ {time.strftime('%H:%M:%S')}", file=sys.stderr, flush=True)

    only = os.environ.get("PROBE_ONLY", "").split(",")
    only = [x for x in only if x]

    def want(name):
        return not only or name in only

    def flush():
        # Incremental flush: a hung backend call can eat SIGINT/SIGTERM
        # before atexit runs (observed: section-3 scatter hang lost every
        # completed section's numbers) — persist after EVERY section.
        print(json.dumps(res), flush=True)

    if want("1a"):
        run_1a(res, rng, mark, flush)
    if want("1b"):
        run_1b(res, rng, mark, flush)
    if want("2") or want("4") or want("3"):
        run_24(res, rng, mark, flush, want)
    return


def run_1a(res, rng, mark, flush):
    mark("1a rows_bf16")
    # ---------------- 1a. bf16 table, rows layout ----------------
    # Mini-step isolating what the original claim was about: the [V, D]
    # gather + RMW sparse-Adagrad path with the table stored bf16 vs f32
    # (accumulator stays f32 in both arms — Adagrad accumulation in bf16
    # would change semantics, not just layout).
    vocab = 1 << 20
    d = 1 + K
    key = jax.random.key(0)
    table_f32 = jax.random.normal(key, (vocab, d), jnp.float32) * 0.01

    def mini_step(state, batch):
        # The two arms differ ONLY in the stored table dtype (carried by
        # the state); compute is f32 in both — the same jitted callable
        # retraces per input dtype.
        table, acc = state
        rows = table[batch.ids].astype(jnp.float32)  # [B, N, D]
        g_rows = rows * batch.vals[..., None]  # cheap stand-in gradient
        new_table, opt = sparse_adagrad_update(
            table.astype(jnp.float32), AdagradState(acc), batch.ids, g_rows, 0.01
        )
        return (new_table.astype(table.dtype), opt.accum), jnp.sum(rows[0, 0])

    step_f32 = step_bf16 = jax.jit(mini_step, donate_argnums=(0,))
    batches = [make_batch(zipf_ids(rng, (B, NNZ), vocab), i) for i in range(8)]
    sa = (table_f32, jnp.full((vocab, d), 0.1, jnp.float32))
    sb = (table_f32.astype(jnp.bfloat16), jnp.full((vocab, d), 0.1, jnp.float32))
    f32_s, bf16_s, sa, sb = interleaved(step_f32, sa, step_bf16, sb, batches, 6)
    res["rows_bf16"] = {
        "f32_ms": round(f32_s * 1e3, 2),
        "bf16_ms": round(bf16_s * 1e3, 2),
        "bf16_over_f32": round(bf16_s / f32_s, 3),
    }
    flush()
    del sa, sb



def run_1b(res, rng, mark, flush):
    mark("1b packed_bf16")
    # ---------------- 1b. bf16 table, packed layout, dense update -------
    # The packed table in bf16 halves the bytes of the wide forward
    # gather AND the dense sweep's table read/write; G and the
    # accumulator stay f32 (same Adagrad semantics).
    vocab = 1 << 24
    d = 1 + K
    model = FMModel(vocabulary_size=vocab, factor_num=K, order=2)
    batches = [make_batch(zipf_ids(rng, (B, NNZ), vocab), 100 + i) for i in range(8)]

    def packed_bf16_body(state, batch):
        rows = packed_gather(state.table, batch.ids, d).astype(jnp.float32)
        grad_fn = jax.value_and_grad(
            partial(batch_loss, model), argnums=(0, 1), has_aux=True
        )
        (_, data_loss), (g_rows, _) = grad_fn(rows, state.dense, batch)
        table_f32, accum = packed_dense_adagrad_update(
            state.table.astype(jnp.float32),
            state.table_opt.accum,
            batch.ids,
            g_rows,
            0.01,
        )
        return (
            TrainState(
                table_f32.astype(jnp.bfloat16),
                AdagradState(accum),
                state.dense,
                state.dense_opt,
                state.step + 1,
            ),
            data_loss,
        )

    step_f32 = make_packed_train_step(model, 0.01, "dense")
    step_bf16 = jax.jit(packed_bf16_body, donate_argnums=(0,))
    sa = init_packed_state(model, jax.random.key(0))
    sb0 = init_packed_state(model, jax.random.key(0))
    sb = TrainState(
        sb0.table.astype(jnp.bfloat16),
        sb0.table_opt,
        sb0.dense,
        sb0.dense_opt,
        sb0.step,
    )
    del sb0
    f32_s, bf16_s, sa, sb = interleaved(step_f32, sa, step_bf16, sb, batches, 6)
    res["packed_bf16_dense"] = {
        "f32_ms": round(f32_s * 1e3, 2),
        "bf16_ms": round(bf16_s * 1e3, 2),
        "bf16_over_f32": round(bf16_s / f32_s, 3),
        "f32_ex_s": round(B / f32_s, 1),
        "bf16_ex_s": round(B / bf16_s, 1),
    }
    flush()
    del sa, sb



def run_24(res, rng, mark, flush, want):
    # Shared setup for sections 2/4/3 (same packed array + slope helper).
    vocab = 1 << 24
    d = 1 + K
    p = rows_per_tile(d)
    vp = -(-vocab // p)
    packed = jax.random.normal(jax.random.key(1), (vp, LANES), jnp.float32)
    flat = zipf_ids(rng, (B * NNZ,), vocab).astype(np.int32)

    def slope_ms(fn, arrays, k_lo=2, k_hi=10, reps=3):
        """Marginal ms per op: k applications carry-chained inside ONE
        jit, cost from the (k_hi - k_lo) difference — a single-shot
        timing includes the result fetch's round trip, which swamps a
        millisecond op (an early version of section 2 "measured" a
        1.6 TB/s gather that way)."""
        jfn = jax.jit(fn, static_argnums=(1,))
        for k in (k_lo, k_hi):
            float(jfn(arrays, k))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(jfn(arrays, k_lo))
            t_lo = time.perf_counter() - t0
            t0 = time.perf_counter()
            float(jfn(arrays, k_hi))
            t_hi = time.perf_counter() - t0
            best = min(best, (t_hi - t_lo) / (k_hi - k_lo))
        return best * 1e3

    if want("2"):
        mark("2 gather locality")
        # ------------ 2. dedup / sorted-id locality on the wide gather --
        # Under jit the unique count is dynamic => a real dedup cannot
        # shrink the gather's static shape.  The realizable lever is
        # LOCALITY: gather the same M rows with ids pre-sorted
        # (duplicates adjacent) vs raw order.
        phys_raw = jnp.asarray(flat // p)
        phys_sorted = jnp.asarray(np.sort(flat // p))

        def gather_k(arrays, k):
            table, phys = arrays

            def body(i, acc):
                return acc + jnp.sum(table[(phys + i) % vp])  # shift kills caching

            return jax.lax.fori_loop(0, k, body, jnp.float32(0))

        raw_ms = slope_ms(gather_k, (packed, phys_raw))
        sorted_ms = slope_ms(gather_k, (packed, phys_sorted))
        res["gather_sorted_locality"] = {
            "raw_ms": round(raw_ms, 2),
            "sorted_ms": round(sorted_ms, 2),
            "sorted_over_raw": round(sorted_ms / raw_ms, 3),
            "rows": int(flat.size),
            "unique_rows": int(np.unique(flat // p).size),
            "payload_mb": round(flat.size * LANES * 4 / 1e6, 1),
            "raw_gbps": round(flat.size * LANES * 4 / (raw_ms / 1e3) / 1e9, 1),
            "sorted_gbps": round(flat.size * LANES * 4 / (sorted_ms / 1e3) / 1e9, 1),
        }
        flush()

    if want("4"):
        mark("4 dense copy")
        # ------------ 4. Pallas-gather headroom input -------------------
        # (same slope method): dense elementwise GB/s vs the gather's
        # GB/s — the gap is the most a hand gather kernel could recover.
        def sweep_k(arrays, k):
            (x,) = arrays

            def body(i, acc):
                # Barrier: without it XLA folds the k multiplies into one
                # pass over memory (measured: a NEGATIVE slope), and the
                # "k sweeps" measure one.
                return jax.lax.optimization_barrier(acc * 1.000001)

            return jnp.sum(jax.lax.fori_loop(0, k, body, x)[0])

        x = jax.random.normal(jax.random.key(2), (vp, LANES), jnp.float32)
        sweep_ms = slope_ms(sweep_k, (x,))
        dense_gbps = 2 * vp * LANES * 4 / (sweep_ms / 1e3) / 1e9
        res["dense_sweep_ms"] = round(sweep_ms, 2)
        res["dense_copy_gbps"] = round(dense_gbps, 1)
        raw_gbps = res.get("gather_sorted_locality", {}).get("raw_gbps")
        if raw_gbps:
            res["gather_headroom_x"] = round(dense_gbps / raw_gbps, 2)
        flush()
        del x

    if want("3"):
        if os.environ.get("PROBE_MERGED") != "1":
            # Section 3 hangs this backend (a [M, 256]-lane scatter-set
            # at M=639k wedged the device >15 min, unkillable mid-call)
            # — opt in with PROBE_MERGED=1 after the hang is understood.
            return
        mark("3 merged rmw")
        # ------------ 3. merged table+accum interleave ------------------
        # Sorted sparse tail: split [VP,128]+[VP,128] (2 RMW gathers + 2
        # scatters) vs ONE merged [VP,256] array (1 gather + 1 scatter
        # of 256-lane rows).  Mini-kernel isolating just the RMW tail.
        m = 160_000  # small: the full 639k wedged the backend (see gate)
        ids_b = [jnp.asarray(zipf_ids(rng, (m,), vocab) // p) for i in range(4)]
        gsum = jax.random.normal(jax.random.key(2), (m, LANES), jnp.float32) * 1e-3

        def rmw_split(state, uphys):
            tab, acc = state
            cur = tab[uphys]
            a = acc[uphys]
            a2 = a + gsum * gsum
            new = cur - 0.01 * gsum / jnp.sqrt(a2)
            return (tab.at[uphys].set(new), acc.at[uphys].set(a2)), new[0, 0]

        def rmw_merged(merged, uphys):
            cur = merged[uphys]  # [M, 256]
            a2 = cur[:, LANES:] + gsum * gsum
            new = cur[:, :LANES] - 0.01 * gsum / jnp.sqrt(a2)
            return merged.at[uphys].set(jnp.concatenate([new, a2], -1)), new[0, 0]

        js = jax.jit(rmw_split, donate_argnums=(0,))
        jm = jax.jit(rmw_merged, donate_argnums=(0,))
        ss = (
            jax.random.normal(jax.random.key(3), (vp, LANES), jnp.float32),
            jnp.full((vp, LANES), 0.1, jnp.float32),
        )
        sm = jnp.concatenate(
            [
                jax.random.normal(jax.random.key(3), (vp, LANES), jnp.float32),
                jnp.full((vp, LANES), 0.1, jnp.float32),
            ],
            -1,
        )
        ts_, tm_ = [], []
        ss, _ = js(ss, ids_b[0])  # compile (donated input rebinds to output)
        float(ss[0][0, 0])
        sm, _ = jm(sm, ids_b[0])
        float(sm[0, 0])
        for _ in range(5):
            t0 = time.perf_counter()
            for i in range(4):
                ss, v = js(ss, ids_b[i])
            float(ss[0][0, 0])
            ts_.append((time.perf_counter() - t0) / 4)
            t0 = time.perf_counter()
            for i in range(4):
                sm, v = jm(sm, ids_b[i])
            float(sm[0, 0])
            tm_.append((time.perf_counter() - t0) / 4)
        split_s, merged_s = float(np.median(ts_)), float(np.median(tm_))
        res["merged_rmw"] = {
            "split_ms": round(split_s * 1e3, 2),
            "merged_ms": round(merged_s * 1e3, 2),
            "merged_over_split": round(merged_s / split_s, 3),
        }
        flush()


if __name__ == "__main__":
    main()
