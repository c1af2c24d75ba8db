#!/usr/bin/env python
"""Benchmark: train-step throughput, value-synced, roofline-annotated.

Headline (the printed line's "value", round 4): the full jitted train
step at the FLAGSHIP operating point — **lane-packed table + dense-G
Adagrad** (ops/packed_table.py) on a 2^24-row table (Criteo-hash scale)
with **Zipf(1.1)-skewed ids** at the measured knee batch 65536, where
the per-step dense sweep amortizes (tools/probe_knee.py).  The metric
string names the exact config.  The bench measures the chip: it exits
non-zero without a result when the backend is not a TPU, and any section
whose exception is caught (recorded on the line as ``*_error``) makes the
exit code non-zero after the line is printed.

Extra keys on the same line:
  scale_value         the memory-filling table (201M rows) through the FUSED
                      tile-row layout + capped compact tail at B=65536
                      (round 5: 3× the r4 rows-layout rung) — the
                      single-chip analog of the 10B-row target, with its
                      own roofline keys (scale_*; scale_b16384_value
                      keeps the r4-comparable batch)
  zipf_interleaved_value / uniform_ids_value
                      same executable, ids Zipf vs uniform, timed in ONE
                      interleaved window set (ordering claims need
                      same-session A/B on this shared chip)
  sharded_value       same shapes through the mesh-sharded SPMD step
                      (dist_train's program) on the visible mesh
  fmb_streamed_value  end-to-end file → memmap-stream → H2D → step through
                      the real FMB input path
  toy_vocab1m_value   the r1 microbench (vocab=1M, uniform ids) for
                      round-over-round continuity

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "examples/sec/chip", "vs_baseline": N, ...}
vs_baseline is against the BASELINE.json north-star ≥500k examples/sec/chip.
"""

import json
import os
import time

# telemetry's hang-exit watchdog is importable WITHOUT jax (the package
# __init__ is lazy for exactly this): armed before the jax import below.
from fast_tffm_tpu.telemetry import arm_hang_exit, write_json_artifact

# Armed before jax/backend init (a batch tool must not hang).  The budget
# covers the honest synced measurement: steps genuinely cost 0.1-0.7 s
# each at these table sizes, so windows take real time.
if __name__ == "__main__":
    _watchdog = arm_hang_exit(seconds=3300, what="bench.py")
else:
    # Imported as a library (bench_all / tools reuse forced_sync etc.):
    # arming here would plant a stray os._exit timer inside the importer's
    # own watchdog budget.
    class _NoWatchdog:
        cancel = staticmethod(lambda: None)

    _watchdog = _NoWatchdog()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from fast_tffm_tpu.models import Batch, FMModel
from fast_tffm_tpu.optim import AdagradState
from fast_tffm_tpu.trainer import (
    TrainState,
    init_state,
    make_packed_train_step,
    make_train_step,
)

BASELINE_EXAMPLES_PER_SEC_PER_CHIP = 500_000.0

# The memory-filling rung: 201,326,592 rows (fused state 6.75 GiB of the
# chip's 16 GB) — the largest size the r4 bisect measured allocating AND
# stepping at both batches.  One fixed size, run in this process: a
# RESOURCE_EXHAUSTED leaves the process able to allocate and step again
# (checked on a TPU v5 lite, jax 0.9.0 — CHANGES.md PR 22), so there is
# no subprocess ladder to probe for "the largest rung that works".
SCALE_VOCAB = 201_326_592
SCALE_K = 8
NNZ = 39  # Criteo field count
BATCH = 16384


def zipf_ids(rng, shape, vocab):
    """Zipf(1.1) ids folded onto [0, vocab): a hot head (the same few ids
    recur across every batch) plus a tail spread uniformly over the whole
    table by the modulo — worst case for row-reuse in the gather and for
    locality in the update scatter."""
    z = rng.zipf(1.1, size=shape)
    return ((z - 1) % vocab).astype(np.int32)


def make_batch(ids, idx=0):
    # Seeded by an explicit per-batch index (NOT ids[0,0]: Zipf's hot head
    # collides on small values, giving several batches identical
    # labels/vals).
    rng = np.random.default_rng((idx, 0xB37C4))
    b, n = ids.shape
    return Batch(
        labels=jnp.asarray(rng.integers(0, 2, size=(b,)).astype(np.float32)),
        ids=jnp.asarray(ids),
        vals=jnp.asarray(np.abs(rng.normal(size=(b, n)).astype(np.float32)) + 0.1),
        fields=jnp.zeros((b, n), jnp.int32),
        weights=jnp.ones((b,), jnp.float32),
    )


NOMINAL_HBM_GBPS = {
    # Nominal HBM bandwidth by device_kind, GB/s (public spec sheets).
    "TPU v5 lite": 819.0,  # v5e
    "TPU v5": 2765.0,  # v5p
    "TPU v4": 1228.0,
    "TPU v6 lite": 1640.0,  # v6e / Trillium
}


def nominal_hbm_gbps(device_kind: str) -> float:
    """Peak HBM bandwidth for ``device_kind``; a device that is not in
    the table is an error, not a default."""
    if device_kind not in NOMINAL_HBM_GBPS:
        raise KeyError(
            f"no HBM peak listed for device_kind {device_kind!r} — add it "
            f"to NOMINAL_HBM_GBPS with its source (have: "
            f"{sorted(NOMINAL_HBM_GBPS)})"
        )
    return NOMINAL_HBM_GBPS[device_kind]


def modeled_step_bytes(ids_batches, d_cols, accum_cols):
    """LOWER-BOUND HBM bytes/step for the order-2 sparse train step, from
    the ACTUAL benchmark batches (mean unique ids measured, not assumed).

    Irreducible data movement only — ids read, touched-row gather, rows
    re-read in the backward, per-occurrence row-grad write, segment-sum
    write, unique-row table/accumulator read-modify-write.  The dedup
    sort's passes over [M] keys and any XLA temporaries are EXCLUDED (they
    only add traffic), so ``implied_gbps`` computed from this model is a
    floor on the bandwidth the measured rate would require.  Emitting it
    makes the headline physically checkable against the device's nominal
    bandwidth (VERDICT r2 #1).
    """
    m = ids_batches[0].shape[0] * ids_batches[0].shape[1]
    uniq = float(np.mean([np.unique(np.asarray(b)).size for b in ids_batches]))
    row = d_cols * 4
    parts = {
        "ids_read": m * 4,
        "rows_gather_read": m * row,
        "rows_reread_bwd": m * row,
        "row_grads_write": m * row,
        "segsum_write": m * row,
        "table_update_rw": int(2 * uniq * row),
        "accum_rw": int(2 * uniq * accum_cols * 4),
    }
    return parts, int(sum(parts.values())), uniq


def modeled_fused_step_bytes(ids_batches, d, vocab, cap, batch_scale=1):
    """LOWER-BOUND HBM bytes/step for the FUSED-layout compact train step
    (modeled_step_bytes's round-5 twin): fwd wide gather, per-occurrence
    [M, 128] grad-row build, compacted G scatter-add, the [VPf] bitmap +
    prefix sum, and the 2-op RMW over the capped row buffer.  Mean unique
    PHYSICAL rows come from the actual batches.  ``batch_scale`` scales
    the M-proportional parts when the measured batch is a multiple of the
    modeled batches' size (the VP-proportional bitmap does not scale).
    NOTE the uniques term makes the "floor" APPROXIMATE when
    ``batch_scale > 1``: per-batch unique counts scale sub-linearly (the
    unions of B-batch uniques overlap), so the scaled ``uniq_phys`` is an
    upper bound on the true unique count and the modeled RMW bytes — and
    hence ``implied_gbps`` — can slightly overstate the floor at scaled
    batches (ADVICE r5)."""
    p = 128 // (d + 1)
    vpf = -(-vocab // p)
    m = ids_batches[0].shape[0] * ids_batches[0].shape[1] * batch_scale
    uniq = float(np.mean([np.unique(np.asarray(b)).size for b in ids_batches]))
    uniq_phys = float(
        np.mean([np.unique(np.asarray(b) // p).size for b in ids_batches])
    ) * batch_scale  # upper bound: unions overlap (see docstring note)
    k_rows = min(cap if cap > 0 else m, min(vpf, m), int(uniq_phys) or m)
    row_b = 128 * 4
    parts = {
        "ids_read": m * 4,
        "fwd_gather_read": m * row_b,
        "grad_rows_write": m * row_b,
        "gbuild_scatter_rw": m * row_b + k_rows * row_b,
        "bitmap_cumsum_rw": vpf * (1 + 1 + 4 + 4),  # int8 w+r, int32 w+r
        "rmw_gather_read": k_rows * row_b,
        "rmw_scatter_write": k_rows * row_b,
    }
    return parts, int(sum(parts.values())), uniq


def modeled_pallas_tail_step_bytes(ids_batches, d, vocab, cap, batch_scale=1):
    """LOWER-BOUND HBM bytes/step for the fused layout under the PALLAS
    one-pass tail (ops/pallas_tail.py): same forward as the XLA fused
    program (ids read, wide tile-row gather, per-occurrence grad rows),
    but the tail's ``gbuild_scatter_rw`` and ``bitmap_cumsum_rw`` terms
    are GONE — the kernel dedups at logical granularity ([M, D] grads
    through the sort/segment-sum pipeline, whose [M]-key passes are
    excluded by the same convention as modeled_step_bytes) and then moves
    each touched row's D+1-lane slot exactly twice: ONE gather read and
    ONE scatter write over the merged table+accumulator columns, instead
    of the grad-build/bitmap/cumsum/RMW-gather/RMW-scatter chain."""
    m = ids_batches[0].shape[0] * ids_batches[0].shape[1] * batch_scale
    uniq = (
        float(np.mean([np.unique(np.asarray(b)).size for b in ids_batches]))
        * batch_scale  # upper bound at batch_scale > 1 (unions overlap)
    )
    k_rows = min(cap if cap > 0 else m, m, int(uniq) or m)
    row_b = 128 * 4
    slot_b = (d + 1) * 4  # the row's merged params+accumulator lanes
    parts = {
        "ids_read": m * 4,
        "fwd_gather_read": m * row_b,
        "grad_rows_write": m * d * 4,
        "segsum_write": m * d * 4,
        "tail_gather_read": int(k_rows * slot_b),
        "tail_scatter_write": int(k_rows * slot_b),
    }
    return parts, int(sum(parts.values())), uniq


def scale_state(vocab, k):
    """TrainState with a [V, 1+k] table + ROW-mode accumulator, built
    in-place on device (init_state's bias/factor concat would peak at 2×
    the table — too much next to 16 GB HBM)."""
    from functools import partial

    @partial(jax.jit, static_argnums=(1, 2))
    def mk_table(key, v, d):
        t = jax.random.uniform(key, (v, d), jnp.float32, -0.01, 0.01)
        return t.at[:, 0].set(0.0)  # bias column starts at zero

    return TrainState(
        table=mk_table(jax.random.key(0), vocab, 1 + k),
        table_opt=AdagradState(jnp.full((vocab, 1), 0.1, jnp.float32)),
        dense={},
        dense_opt=AdagradState({}),
        step=jnp.zeros((), jnp.int32),
    )


# Fused compact tail: cap the compacted-row buffer (exact lax.cond
# fallback on overflow) — Zipf batches at B=65536 touch ~0.5-0.7M unique
# physical rows, so 2^20 holds with slack while the RMW shrinks ~2.5×
# (PROBE_UPDATE_OPS_r05; ops/packed_table.py round-5 entry).
SCALE_CAP = 1 << 20
SCALE_BATCH_BIG = 65536


def fused_scale_state(vocab, k):
    """TrainState in the FUSED tile-row layout ([VPf, 128]: D row lanes +
    1 row-accumulator lane per slot), built in-place on device — the
    scale-regime operating point (2-random-op RMW, ~(D+1)/D of the table
    in total state)."""
    from functools import partial

    from fast_tffm_tpu.ops.packed_table import LANES, fused_packed_rows

    d = 1 + k
    vpf = fused_packed_rows(vocab, d)

    @partial(jax.jit, static_argnums=(1,))
    def mk_fused(key, n):
        f = jax.random.uniform(key, (n, LANES), jnp.float32, -0.01, 0.01)
        p = LANES // (d + 1)
        lanes = jnp.arange(LANES)
        is_acc = (lanes < p * (d + 1)) & (lanes % (d + 1) == d)
        return jnp.where(
            is_acc[None, :] | (lanes >= p * (d + 1))[None, :], 0.1, f
        )

    return TrainState(
        table=mk_fused(jax.random.key(0), vpf),
        table_opt=AdagradState(jnp.zeros((0, 1), jnp.float32)),
        dense={},
        dense_opt=AdagradState({}),
        step=jnp.zeros((), jnp.int32),
    )


@jax.jit
def _peek_table(t):
    return jnp.sum(jax.lax.dynamic_slice_in_dim(t, 0, 2, axis=0))


def forced_sync(state) -> float:
    """Synchronize by VALUE DEPENDENCY on the final state: fetch a tiny
    slice of the final table, which the runtime cannot produce before
    every chained update has landed.

    On this machine ``block_until_ready`` waits just as long (PR 22, TPU
    v5 lite, jax 0.9.0: 20 donated rows-layout steps at baseline #1's
    width closed by ``block_until_ready`` 4.346 s ×3, by the value fetch
    4.348 s ×2 after its one-time compile; enqueueing alone returned in
    5.8 ms), so the two closes are interchangeable here.  The value fetch
    stays as the benchmarks' close because it cannot under-count on any
    backend — rounds 1–2 were taken on an installation whose barrier did
    not wait.  (``_peek_table`` is module-level so its one compile
    happens at the first warm sync, never inside a timed window.)
    """
    return float(_peek_table(state.table))


def measure(step, state, batches, iters, windows=3, batch_size=None):
    """(final state, best-window examples/sec), VALUE-SYNCED.

    Timing is the marginal cost of ``iters`` extra steps between two
    forced syncs — best of ``windows`` (min time: contention only ever
    slows a window down, never speeds it up; the sync itself cannot
    under-count, see forced_sync).  ``batch_size`` defaults to the module
    BATCH; callers measuring a different shape pass theirs explicitly
    (no globals() mutation — batches may be opaque index handles on the
    device-cache path, so the size cannot be derived from them)."""
    bsz = BATCH if batch_size is None else batch_size
    state, loss = step(state, batches[0])  # compile
    forced_sync(state)
    for i in range(1, 4):  # short warm
        state, loss = step(state, batches[i % len(batches)])
    forced_sync(state)
    best_dt = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for i in range(iters):
            state, loss = step(state, batches[i % len(batches)])
        forced_sync(state)
        best_dt = min(best_dt, time.perf_counter() - t0)
    return state, bsz * iters / best_dt


def interleaved_measure(step, state, batches_a, batches_b, iters, rounds=4, batch=None):
    """((rate_a, rate_b), final state) — the A and B batch sets timed in
    ALTERNATING same-session windows (A B A B ...), each window closed by
    forced_sync, medians per side.

    This is the ordering-dispute killer (VERDICT r3 weak #3): two
    sections timed in separate windows can disagree from drift alone, so
    any A-vs-B claim (Zipf vs
    uniform, layout A vs layout B) must come from one interleaved window
    set, not two adjacent sections."""
    b = batch or BATCH
    state, _ = step(state, batches_a[0])
    forced_sync(state)
    state, _ = step(state, batches_b[0])
    forced_sync(state)
    ta, tb = [], []
    for _ in range(rounds):
        for batches, acc in ((batches_a, ta), (batches_b, tb)):
            t0 = time.perf_counter()
            for i in range(iters):
                state, _ = step(state, batches[i % len(batches)])
            forced_sync(state)
            acc.append(time.perf_counter() - t0)
    import statistics

    return (
        b * iters / statistics.median(ta),
        b * iters / statistics.median(tb),
    ), state


def ensure_scale_fmb(vocab, rows=1 << 19, seed=7, all_ones=False):
    """Synthesize (once, cached) an FMB file of Zipf-id rows at the scale
    vocab — built directly in the FMB layout (the text→FMB converter would
    spend minutes parsing 250 MB of synthetic text for no extra fidelity;
    the STREAM under test is identical either way).  ``all_ones`` writes
    1.0 values with the v2 elision flags set — the binary-feature CTR
    regime the packed wire format's vals elision targets."""
    from fast_tffm_tpu.data.binary import (
        _HEADER,
        FLAG_FIELDS_ALL_ZERO,
        FLAG_VALS_ALL_ONES,
        FMB_MAGIC,
        FMB_VERSION,
        _section_offsets,
        open_fmb,
    )

    tag = "ones" if all_ones else "zipf"
    path = f"/tmp/fmb_scale_cache/{tag}_v{vocab}_n{NNZ}_r{rows}_s{seed}.fmb"
    if os.path.exists(path):
        try:
            f = open_fmb(path)
            if f.n_rows == rows and f.vocabulary_size == vocab and (
                not all_ones or f.flags & FLAG_VALS_ALL_ONES
            ):
                return path
        except ValueError:
            pass
    os.makedirs(os.path.dirname(path), exist_ok=True)
    rng = np.random.default_rng(seed)
    o_lab, o_nnz, o_ids, o_val, o_fld, total = _section_offsets(rows, NNZ, 4)
    tmp = path + f".{os.getpid()}.tmp"
    with open(tmp, "wb") as fh:
        fh.truncate(total)
    mm = np.memmap(tmp, np.uint8, mode="r+")
    flags = FLAG_FIELDS_ALL_ZERO | (FLAG_VALS_ALL_ONES if all_ones else 0)
    mm[: _HEADER.size] = np.frombuffer(
        _HEADER.pack(FMB_MAGIC, FMB_VERSION, rows, NNZ, vocab, 1, 4, flags, 0, 0, NNZ),
        np.uint8,
    )

    def view(off, count, dtype, shape):
        return mm[off : off + count * np.dtype(dtype).itemsize].view(dtype).reshape(shape)

    view(o_lab, rows, np.float32, (rows,))[:] = rng.integers(
        0, 2, size=rows
    ).astype(np.float32)
    view(o_nnz, rows, np.int32, (rows,))[:] = NNZ
    view(o_ids, rows * NNZ, np.int32, (rows, NNZ))[:] = zipf_ids(
        rng, (rows, NNZ), vocab
    )
    if all_ones:
        view(o_val, rows * NNZ, np.float32, (rows, NNZ))[:] = 1.0
    else:
        view(o_val, rows * NNZ, np.float32, (rows, NNZ))[:] = np.abs(
            rng.normal(size=(rows, NNZ)).astype(np.float32)
        ) + 0.1
    view(o_fld, rows * NNZ, np.int32, (rows, NNZ))[:] = 0
    mm.flush()
    del mm
    os.replace(tmp, path)
    return path


def bench_fmb_streamed(step, state, path, vocab, wire_format="packed"):
    """(final state, examples/sec, info) through the REAL input path:
    memmap stream → producer-thread H2D staging (training's binary-input
    placement, ``wire_format`` selecting packed-wire vs classic arrays)
    → jitted step.  ``info`` carries the wire accounting the BENCH JSON
    commits: bytes/step on the wire and the per-batch staging-time median.
    """
    from fast_tffm_tpu.data.binary import fmb_batch_stream, fmb_wire_flags, open_fmb
    from fast_tffm_tpu.data.wire import WireConverter, arrays_nbytes, make_spec
    from fast_tffm_tpu.utils.prefetch import prefetch

    n_rows = open_fmb(path).n_rows
    count = n_rows // BATCH
    if wire_format == "packed":
        all_ones, _ = fmb_wire_flags([path])
        conv = WireConverter(
            make_spec(vocab, NNZ, with_vals=not all_ones, with_fields=False)
        )
        wire_bytes = conv.spec.batch_nbytes(BATCH)
    else:
        conv = lambda p, w: Batch.from_parsed(p, w, with_fields=False)
        wire_bytes = arrays_nbytes(BATCH, NNZ, with_fields=False)
    stage_ms = []

    def stream(timed=False):
        raw = fmb_batch_stream(
            [path], batch_size=BATCH, vocabulary_size=vocab,
            hash_feature_id=True, max_nnz=NNZ, epochs=1, drop_remainder=True,
        )

        def gen():
            for p, w in raw:
                t0 = time.perf_counter()
                b = conv(p, w)
                if timed:
                    stage_ms.append(1e3 * (time.perf_counter() - t0))
                yield b, p, w

        return prefetch(gen(), depth=8)

    loss = None
    for b, _p, _w in stream():  # warm epoch (page cache, executable reuse)
        state, loss = step(state, b)
    forced_sync(state)
    t0 = time.perf_counter()
    for b, _p, _w in stream(timed=True):
        state, loss = step(state, b)
    forced_sync(state)
    dt = time.perf_counter() - t0
    import statistics

    info = {
        "wire_format": wire_format,
        "wire_bytes_per_step": wire_bytes,
        "h2d_stage_ms_median": (
            round(statistics.median(stage_ms), 3) if stage_ms else None
        ),
    }
    return state, count * BATCH / dt, info


def main():
    if jax.default_backend() != "tpu":
        # A number taken off the chip is never written under
        # examples/sec/chip: no TPU, no result line.
        _watchdog.cancel()
        raise SystemExit(
            f"bench.py measures the chip: jax's backend is "
            f"{jax.default_backend()!r} (devices {jax.devices()}), not a TPU "
            "— no result written"
        )
    rng = np.random.default_rng(0)
    results = {}
    # One telemetry identity per bench invocation (artifact join key —
    # stamped on the result line with schema_version below).
    from fast_tffm_tpu.telemetry import new_run_id

    _BENCH_RUN_ID = new_run_id()

    # --- the memory-filling rung: local jitted step, Zipf ids.  The FUSED
    #     tile-row layout + capped compact tail (PROBE_COMPACT /
    #     UPDATE_OPS_r05: the measured scale-regime layout).  Not caught:
    #     without this state no later section has anything to run on. ---
    vocab = SCALE_VOCAB
    model = FMModel(vocabulary_size=vocab, factor_num=SCALE_K, order=2)
    step = make_packed_train_step(
        model, learning_rate=0.01, update="auto", compact_cap=SCALE_CAP
    )
    batches = [make_batch(zipf_ids(rng, (BATCH, NNZ), vocab), i) for i in range(16)]
    state = fused_scale_state(vocab, SCALE_K)
    state, scale_rate = measure(step, state, batches, iters=20)
    results["scale_b16384_value"] = round(scale_rate / jax.device_count(), 1)
    results["scale_vocab_rows"] = vocab
    results["scale_table_gib"] = round(vocab * (1 + SCALE_K) * 4 / 2**30, 2)
    results["scale_layout"] = f"fused tile-row + compact cap {SCALE_CAP}"

    # The rung's best operating point: B=65536 amortizes the per-step
    # fixed costs (bitmap + dispatch) over 4× the examples — measured
    # ~295k vs ~170k at B=16384 (PROBE_COMPACT_r05).  Falls back to the
    # B=16384 number if the bigger shape doesn't fit this session.
    scale_batch = BATCH
    try:
        big = [
            make_batch(zipf_ids(rng, (SCALE_BATCH_BIG, NNZ), vocab), 50 + i)
            for i in range(6)
        ]
        state, big_rate = measure(
            step, state, big, iters=10, batch_size=SCALE_BATCH_BIG
        )
        results["scale_value"] = round(big_rate / jax.device_count(), 1)
        results["scale_batch"] = SCALE_BATCH_BIG
        scale_rate, scale_batch = big_rate, SCALE_BATCH_BIG
        del big
    except Exception as e:
        results["scale_value"] = results["scale_b16384_value"]
        results["scale_batch"] = BATCH
        results["scale_b65536_error"] = str(e)[:120]

    # --- bytes-moved roofline: make the headline physically auditable ---
    step_us = scale_batch / scale_rate * 1e6
    parts, total_bytes, uniq = modeled_fused_step_bytes(
        [b.ids for b in batches], 1 + SCALE_K, vocab, SCALE_CAP,
        batch_scale=scale_batch // BATCH,
    )
    kind = jax.devices()[0].device_kind
    nominal = nominal_hbm_gbps(kind)
    implied = total_bytes / (step_us * 1e-6) / 1e9
    results["scale_step_time_us"] = round(step_us, 2)
    results["scale_modeled_hbm_bytes_per_step"] = total_bytes
    results["scale_modeled_hbm_bytes_parts"] = parts
    results["mean_unique_ids_per_batch"] = round(uniq, 1)
    results["scale_implied_hbm_gbps_floor"] = round(implied, 1)
    results["platform"] = jax.devices()[0].platform
    results["device_kind"] = kind
    results["device_count"] = jax.device_count()
    results["nominal_hbm_gbps"] = nominal
    # >1.0 means the measured rate needs more bandwidth than the device
    # nominally has — a flag to audit, not hide.
    results["scale_implied_over_nominal"] = round(implied / nominal, 2)

    # --- sparse-tail A/B: XLA program chain vs one-pass Pallas kernel ---
    # BENCH_TAIL_MODES (default "xla,pallas") selects which tails run at
    # the rung's B=16384 operating point.  Each mode records ex/s plus
    # bytes/example BOTH ways — measured (Lowered.cost_analysis via
    # profiling.program_cost, no second backend compile) and modeled
    # (the per-tail lower-bound formula) — so tools/report.py can render
    # the two tails side by side against the HBM roof.  A leg the
    # compiler refuses records its error and fails the exit code (the
    # fused layout's Pallas tail does not compile on a TPU v5 lite today —
    # ops/pallas_tail.py; ROADMAP D2 decides the kernel).
    from fast_tffm_tpu.profiling import program_cost

    tail_modes = [
        m.strip()
        for m in os.environ.get("BENCH_TAIL_MODES", "xla,pallas").split(",")
        if m.strip()
    ]
    ab = {"batch": BATCH, "modes": {}}
    ids_16k = [b.ids for b in batches]
    px_parts, px_total, _ = modeled_fused_step_bytes(
        ids_16k, 1 + SCALE_K, vocab, SCALE_CAP
    )
    pp_parts, pp_total, _ = modeled_pallas_tail_step_bytes(
        ids_16k, 1 + SCALE_K, vocab, SCALE_CAP
    )

    def _measured_bpe(fn):
        cost = program_cost(fn, (state, batches[0]))
        if cost and cost.get("bytes_accessed"):
            return round(cost["bytes_accessed"] / BATCH, 1)
        return None

    for mode in tail_modes:
        if mode == "xla":
            ab["modes"]["xla"] = {
                "value": results["scale_b16384_value"],
                "modeled_bytes_per_example": round(px_total / BATCH, 1),
                "modeled_parts": px_parts,
                "measured_bytes_per_example": _measured_bpe(step),
            }
        elif mode == "pallas":
            entry = {
                "modeled_bytes_per_example": round(pp_total / BATCH, 1),
                "modeled_parts": pp_parts,
            }
            try:
                pstep = make_packed_train_step(
                    model, learning_rate=0.01, compact_cap=SCALE_CAP,
                    tail="pallas",
                )
                state, p_rate = measure(pstep, state, batches, iters=20)
                entry["value"] = round(p_rate / jax.device_count(), 1)
                entry["measured_bytes_per_example"] = _measured_bpe(pstep)
                big = [
                    make_batch(
                        zipf_ids(rng, (SCALE_BATCH_BIG, NNZ), vocab), 200 + i
                    )
                    for i in range(4)
                ]
                state, pb_rate = measure(
                    pstep, state, big, iters=8, batch_size=SCALE_BATCH_BIG
                )
                entry["b65536_value"] = round(pb_rate / jax.device_count(), 1)
                del big
            except Exception as e:
                entry["error"] = results["tail_ab_pallas_error"] = str(e)[:300]
            ab["modes"]["pallas"] = entry
    results["tail_ab"] = ab

    # Uniform ids over the same giant table: the true cold-gather worst
    # case (Zipf's hot head concentrates most gathers on a few cached
    # rows; uniform makes every row gather + update RMW touch cold HBM).
    # Same executable — only the id values change — and timed INTERLEAVED
    # with the Zipf batches in one window set, so the Zipf/uniform
    # ordering claim comes from a same-session A/B, not two adjacent
    # sections that drift apart on a shared chip (VERDICT r3 weak #3).
    try:
        uni = [
            make_batch(
                rng.integers(0, vocab, size=(BATCH, NNZ)).astype(np.int32), 100 + i
            )
            for i in range(16)
        ]
        (z_rate, u_rate), state = interleaved_measure(
            step, state, batches, uni, iters=10
        )
        n = jax.device_count()
        results["zipf_interleaved_value"] = round(z_rate / n, 1)
        results["uniform_ids_value"] = round(u_rate / n, 1)
        results["uniform_over_zipf"] = round(u_rate / z_rate, 3)
        del uni
    except Exception as e:
        results["uniform_ids_value"] = None
        results["uniform_ids_error"] = str(e)[:120]

    # --- end-to-end through the FMB input path (same live state), on the
    #     default packed wire.  Then the wire_format A/B on the all-ones
    #     workload (the vals-elision regime): same stream, same step, the
    #     two formats timed back to back so the trajectory captures the
    #     wire win (or a regression) automatically. ---
    try:
        state, fmb_rate, fmb_info = bench_fmb_streamed(
            step, state, ensure_scale_fmb(vocab), vocab
        )
        results["fmb_streamed_value"] = round(fmb_rate, 1)
        results["streamed_wire_bytes_per_step"] = fmb_info["wire_bytes_per_step"]
        results["streamed_h2d_ms_median"] = fmb_info["h2d_stage_ms_median"]
    except Exception as e:  # input-path trouble must not kill the headline
        results["fmb_streamed_value"] = None
        results["fmb_streamed_error"] = str(e)[:120]
    try:
        ones_path = ensure_scale_fmb(vocab, all_ones=True)
        ab = {}
        for wf in ("packed", "arrays"):
            state, r, info = bench_fmb_streamed(
                step, state, ones_path, vocab, wire_format=wf
            )
            ab[wf] = {
                "value": round(r, 1),
                "wire_bytes_per_step": info["wire_bytes_per_step"],
                "h2d_stage_ms_median": info["h2d_stage_ms_median"],
            }
        ab["wire_cut_x"] = round(
            ab["arrays"]["wire_bytes_per_step"] / ab["packed"]["wire_bytes_per_step"],
            3,
        )
        results["wire_format_ab_allones"] = ab
    except Exception as e:
        results["wire_format_ab_error"] = str(e)[:120]

    # --- same shapes through the sharded SPMD step (dist_train's program).
    #     The rung state is FUSED (local-only layout), so this section
    #     frees it and builds the rows-layout state the sharded step
    #     takes — r4's sharded_value semantics, now with the mesh=1
    #     short-circuits in the collectives (VERDICT r4 #3). ---
    del state
    state = None
    try:
        from fast_tffm_tpu.parallel import make_mesh, make_sharded_train_step

        n = jax.device_count()
        mesh = make_mesh(1, n)
        sh_step = make_sharded_train_step(model, 0.01, mesh)
        sh_state = scale_state(vocab, SCALE_K)
        sh_state, sh_rate = measure(sh_step, sh_state, batches, iters=20)
        results["sharded_value"] = round(sh_rate / n, 1)
        del sh_state
    except Exception as e:
        results["sharded_value"] = None
        results["sharded_error"] = str(e)[:120]
    del batches

    # --- device-resident dataset (device_cache = true): the epoch lives in
    #     HBM beside the table and every step slices its batch on-chip —
    #     zero per-step H2D.  Expected within ~2× of the synthetic-batch
    #     headline (same program + a fused dynamic-slice), vs the ~300×
    #     gap of the host-streamed path.  A FRESH single-device state:
    #     the sharded section's mesh-committed buffers can't feed this
    #     single-device step, and this is a one-chip number (no /n). ---
    try:
        from fast_tffm_tpu.data.device_cache import (
            load_device_dataset,
            make_cached_train_step,
        )

        data = load_device_dataset(
            [ensure_scale_fmb(vocab)],
            batch_size=BATCH,
            vocabulary_size=vocab,
            hash_feature_id=True,
            max_nnz=NNZ,
            with_fields=False,
        )
        cached_step, _ = make_cached_train_step(model, 0.01, data)
        idx = [jax.device_put(np.int32(i)) for i in range(data.batches)]

        class _IdxBatches:
            def __getitem__(self, i):
                return idx[i % len(idx)]

            def __len__(self):
                return len(idx)

        dc_state = scale_state(vocab, SCALE_K)
        dc_state, dc_rate = measure(cached_step, dc_state, _IdxBatches(), iters=20)
        results["device_cached_value"] = round(dc_rate, 1)
        results["device_cached_mib"] = round(data.nbytes / 2**20, 1)
        # --- steps_per_call lever: K fused steps per dispatch (lax.scan
        #     over K resident batch slices — the tentpole of the dispatch-
        #     overhead fix).  K=1 is the per-dispatch number just measured;
        #     each K>1 rung re-measures the SAME step body scanned, so the
        #     ratio isolates pure dispatch/latency amortization.  Honest
        #     timing: only full-K index chunks (the remainder executable is
        #     excluded from the window), same value-synced measure(). ---
        try:
            from fast_tffm_tpu.data.device_cache import (
                epoch_index_chunks,
                make_cached_scan_train_step,
            )

            ks = [
                k
                for k in (
                    int(x)
                    for x in os.environ.get("BENCH_STEPS_PER_CALL", "8").split(",")
                    if x.strip()
                )
                if k > 1
            ]
            spc = {"1": round(dc_rate, 1)}
            stepk, _ = make_cached_scan_train_step(model, 0.01, data)
            for kk in ks:
                chunks = [
                    c for c in epoch_index_chunks(data.batches, kk) if len(c) == kk
                ]
                dc_state, k_rate = measure(
                    stepk, dc_state, chunks, iters=max(4, 24 // kk),
                    batch_size=BATCH * kk,
                )
                spc[str(kk)] = round(k_rate, 1)
            results["steps_per_call_values"] = spc
            if "8" in spc:
                results["steps_per_call_k8_over_k1"] = round(spc["8"] / spc["1"], 3)
        except Exception as e:
            results["steps_per_call_error"] = str(e)[:120]
        finally:
            # stepk's closure captures the resident dataset arrays; left
            # alive it would carry the whole device cache into the next
            # (packed 2^24) rung and shrink its memory headroom.
            stepk = chunks = None
        del data, cached_step, idx, dc_state
    except Exception as e:
        results["device_cached_value"] = None
        results["device_cached_error"] = str(e)[:120]

    # --- lane-packed layout + dense-G update (table_layout = packed,
    #     packed_update = auto -> dense at this vocab): the FLAGSHIP
    #     operating point and the round-4 HEADLINE — vocab 2^24 (16.8M
    #     rows, Criteo-hash scale), Zipf ids, element accumulator, batch
    #     at the measured knee (65536, where the per-step dense sweep
    #     amortizes — tools/probe_knee.py).  The 134M-row rung above
    #     stays on the line as scale_value (its sorted-path number). ---
    try:
        from fast_tffm_tpu.ops.packed_table import (
            LANES,
            packed_rows,
            resolve_packed_update,
            rows_per_tile,
        )
        from fast_tffm_tpu.trainer import init_packed_state

        pv = 1 << 24
        pmodel = FMModel(vocabulary_size=pv, factor_num=SCALE_K, order=2)
        pstep = make_packed_train_step(pmodel, 0.01)
        pstate = init_packed_state(pmodel, jax.random.key(0))
        n = jax.device_count()
        vp_rows = packed_rows(pv, 1 + SCALE_K)
        results["packed_vocab_rows"] = pv
        results["packed_update_mode"] = resolve_packed_update(
            "auto", vp_rows, LANES
        )
        pbatches = [
            make_batch(zipf_ids(rng, (BATCH, NNZ), pv), 300 + i) for i in range(8)
        ]
        pstate, p_rate = measure(pstep, pstate, pbatches, iters=20)
        results["packed_value"] = round(p_rate / n, 1)
        del pbatches
        # Knee batch: the dense sweep's per-step cost is independent of
        # B, so larger batches amortize it (probe_knee.py located the
        # knee at ~65536).  This is the headline number.  Its OWN
        # try/except: a knee-shape compile/OOM failure must demote the
        # headline to the just-measured default-batch packed number, not
        # clobber it (the fallback ladder below depends on that).
        try:
            kb = 65536
            kbatches = [
                make_batch(zipf_ids(rng, (kb, NNZ), pv), 400 + i) for i in range(4)
            ]
            pstate, _ = pstep(pstate, kbatches[0])  # compile the new shape
            forced_sync(pstate)
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for i in range(10):
                    pstate, _ = pstep(pstate, kbatches[i % len(kbatches)])
                forced_sync(pstate)
                best = min(best, time.perf_counter() - t0)
            pk_rate = kb * 10 / best
            results["packed_b65536_value"] = round(pk_rate / n, 1)
            results["headline_batch"] = kb
            # Bytes model for THIS config (the headline's roofline): one
            # wide [M,128] gather, one wide scatter-add into G, and the
            # dense Adagrad sweep over table+accum+G (reads) and
            # table+accum (writes) — all independent of id locality
            # except the gather.
            m_ids = kb * NNZ
            lane_b = LANES * 4
            parts = {
                "ids_read": m_ids * 4,
                "wide_gather_read": m_ids * lane_b,
                "grad_scatter_write": m_ids * lane_b,
                "dense_sweep_read_3x": 3 * vp_rows * lane_b,
                "dense_sweep_write_2x": 2 * vp_rows * lane_b,
            }
            total = sum(parts.values())
            step_s = kb / pk_rate
            results["packed_modeled_hbm_bytes_per_step"] = total
            results["packed_modeled_hbm_bytes_parts"] = parts
            results["packed_implied_hbm_gbps_floor"] = round(total / step_s / 1e9, 1)
        except Exception as e:
            results["packed_b65536_value"] = None
            results["packed_b65536_error"] = str(e)[:120]
        del pstate
    except Exception as e:
        results["packed_value"] = None
        results["packed_error"] = str(e)[:120]



    # --- checkpoint A/B lever (ckpt_mode sync|async|delta): train-loop
    #     stall per save and bytes per save on a 1M-row state.  `sync` is
    #     the classic blocking save (convert + D2H + write inline);
    #     `async` is the boundary cost of the snapshot+handoff (the writer
    #     thread finishes off-loop); `delta` is the touched-window path
    #     (bitmap D2H + row gather dispatch).  BENCH_CKPT_MODES selects a
    #     subset.  ckpt_stall_ms_per_save is the trajectory key the report
    #     gate watches (ckpt stall share). ---
    try:
        import statistics as _stats
        import tempfile

        from fast_tffm_tpu.checkpoint_async import AsyncCheckpointer

        modes = [
            m.strip()
            for m in os.environ.get("BENCH_CKPT_MODES", "sync,async,delta").split(",")
            if m.strip()
        ]
        cv = 1 << 20
        cmodel = FMModel(vocabulary_size=cv, factor_num=SCALE_K, order=2)
        cstate = init_state(cmodel, jax.random.key(1))
        cbatch = make_batch(zipf_ids(rng, (BATCH, NNZ), cv), 900)
        cdir = tempfile.mkdtemp(prefix="bench_ckpt_")
        ident = lambda s: s
        stall_ms: dict = {}
        bytes_per: dict = {}
        if "sync" in modes:
            ck = AsyncCheckpointer(os.path.join(cdir, "sync.ckpt"), "npz")
            ts = []
            for i in range(3):
                t0 = time.perf_counter()
                ck.save_boundary(cstate, ident, i, sync=True, emit=False)
                ts.append((time.perf_counter() - t0) * 1e3)
            stall_ms["sync"] = round(_stats.median(ts), 2)
            bytes_per["full"] = os.path.getsize(os.path.join(cdir, "sync.ckpt"))
        if "async" in modes:
            ck = AsyncCheckpointer(
                os.path.join(cdir, "async.ckpt"), "npz", async_save=True
            )
            ts = []
            for i in range(3):
                t0 = time.perf_counter()
                ck.save_boundary(cstate, ident, i)
                ts.append((time.perf_counter() - t0) * 1e3)
                ck.finalize()  # writer time excluded: it overlaps training
            stall_ms["async"] = round(_stats.median(ts), 2)
        if "delta" in modes:
            ck = AsyncCheckpointer(
                os.path.join(cdir, "delta.ckpt"), "npz",
                delta_every_steps=1, vocab=cv, row_dim=1 + SCALE_K,
            )
            ck.save_boundary(cstate, ident, 0, sync=True, emit=False)  # base
            ts = []
            for i in range(3):
                ck.note_batch(cbatch)
                t0 = time.perf_counter()
                ck.delta_boundary(cstate, ident, i + 1)
                ts.append((time.perf_counter() - t0) * 1e3)
                ck.finalize()
            stall_ms["delta"] = round(_stats.median(ts), 2)
            dps = sorted(
                p for p in os.listdir(cdir) if ".delta-" in p and p.endswith(".npz")
            )
            if dps:
                bytes_per["delta"] = os.path.getsize(os.path.join(cdir, dps[-1]))
        results["ckpt_stall_ms_per_save"] = stall_ms
        results["ckpt_bytes_per_save"] = bytes_per
        if "sync" in stall_ms and "async" in stall_ms and stall_ms["sync"]:
            results["ckpt_async_over_sync_stall"] = round(
                stall_ms["async"] / stall_ms["sync"], 4
            )
        del cstate, cbatch
        import shutil

        shutil.rmtree(cdir, ignore_errors=True)
    except Exception as e:
        results["ckpt_ab_error"] = str(e)[:120]

    # --- r1 continuity: the 1M-row uniform-id microbench ---
    try:
        toy_model = FMModel(vocabulary_size=1 << 20, factor_num=8, order=2)
        toy_step = make_train_step(toy_model, learning_rate=0.01)
        toy_batches = [
            make_batch(
                rng.integers(0, 1 << 20, size=(BATCH, NNZ)).astype(np.int32), 200 + i
            )
            for i in range(8)
        ]
        toy_state = init_state(toy_model, jax.random.key(0))
        _, toy_rate = measure(toy_step, toy_state, toy_batches, iters=30)
        results["toy_vocab1m_value"] = round(toy_rate / jax.device_count(), 1)
    except Exception as e:
        results["toy_vocab1m_value"] = None
        results["toy_error"] = str(e)[:120]

    # Headline: the flagship packed-dense operating point (vocab 2^24,
    # knee batch).  Falls back to the packed default batch, then to the
    # scale rung, so a degraded session still emits an honest number —
    # the metric string always names which config the value came from.
    if results.get("packed_b65536_value") is not None:
        value = results["packed_b65536_value"]
        metric = (
            f"train examples/sec/chip (2nd-order FM, k=8, nnz=39, "
            f"vocab={results['packed_vocab_rows']} rows, lane-packed table "
            f"+ dense-G Adagrad, batch 65536, Zipf(1.1) ids; "
            f"scale rung vocab={vocab} fused+capped-compact at batch "
            f"{results.get('scale_batch', BATCH)} on the line as scale_value)"
        )
    elif results.get("packed_value") is not None:
        value = results["packed_value"]
        metric = (
            f"train examples/sec/chip (2nd-order FM, k=8, nnz=39, "
            f"vocab={results['packed_vocab_rows']} rows, lane-packed table "
            f"+ dense-G Adagrad, batch {BATCH}, Zipf(1.1) ids)"
        )
    else:
        value = results["scale_value"]
        metric = (
            f"train examples/sec/chip (2nd-order FM, k=8, nnz=39, "
            f"vocab={vocab} rows ~{results['scale_table_gib']}GiB "
            "table, Zipf(1.1) ids, fused tile-row layout, capped compact tail)"
        )
    _watchdog.cancel()
    from fast_tffm_tpu.telemetry import artifact_stamp

    result = {
        "metric": metric,
        "value": value,
        "unit": "examples/sec/chip",
        "vs_baseline": round(value / BASELINE_EXAMPLES_PER_SEC_PER_CHIP, 4),
        # Envelope join keys: one identity per bench invocation (the main
        # rungs run raw jitted loops with no monitor — the stamp names the
        # invocation; bench --dist threads its run_id into the workers'
        # [Telemetry] so THAT artifact joins its streams for real).
        **artifact_stamp(_BENCH_RUN_ID),
        **results,
    }
    print(json.dumps(result))
    # Round-over-round delta table: REPORT_rNN.md next to the committed
    # BENCH_r*.json artifacts (tools/report.py) — the bench's own compare
    # gate output, written best-effort AFTER the result line so a report
    # failure can never cost the number.
    import sys

    try:
        from tools.report import write_bench_report

        rp = write_bench_report(result, os.path.dirname(os.path.abspath(__file__)))
        if rp:
            print(f"bench report -> {rp}", file=sys.stderr)
    except Exception as e:
        print(f"bench report skipped: {e!r}", file=sys.stderr)
    # Every caught section left its *_error key on the line: the line is
    # printed, the exit code says it is incomplete.
    errors = sorted(k for k in results if k.endswith("_error"))
    if errors:
        print(f"bench.py: sections failed: {errors}", file=sys.stderr)
        raise SystemExit(1)


_DIST_WORKER = '''
import sys
pid, nproc, port, tmp, files = (
    int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4], sys.argv[5]
)
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(f"127.0.0.1:{{port}}", num_processes=nproc, process_id=pid)

from fast_tffm_tpu.config import Config
from fast_tffm_tpu.training import dist_train

cfg = Config(
    model="fm", factor_num=8, vocabulary_size={vocab},
    model_file=f"{{tmp}}/m.ckpt",
    train_files=tuple(files.split(",")),
    epoch_num=1, batch_size={batch}, max_nnz={nnz}, learning_rate=0.01,
    log_every=4, metrics_path=f"{{tmp}}/run.jsonl",
    telemetry_run_id={run_id!r},
    input_assignment="files",
    barrier_timeout_s=120,
    hash_feature_id=True,  # the synthetic FMB files are written hashed
)
cfg.validate()
dist_train(cfg, log=lambda m: print(f"[{{pid}}] {{m}}", flush=True))
print(f"[{{pid}}] BENCH DONE", flush=True)
'''


def bench_dist(
    processes: int = 2, out_path: str | None = None, run_id: str = ""
) -> dict:
    """The ``processes`` lever (ROADMAP item 1): a REAL multi-process CPU
    pod — N OS processes, gloo collectives, shard-disjoint FMB file
    assignment, host-local packed wire — measured through the production
    ``dist_train`` driver.  Reports the aggregate global examples/sec
    (every host trains the same global batch, so the lead's meter IS the
    pod rate), per-host medians, and the steady-recompile pin.  Writes
    ``BENCH_DIST_rNN.json`` when ``out_path`` is given."""
    import socket
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    vocab, rows, batch = 1 << 16, 1 << 15, 2048
    files = [
        ensure_scale_fmb(vocab, rows=rows, seed=7 + p) for p in range(processes)
    ]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    from fast_tffm_tpu.telemetry import artifact_stamp

    # The workers adopt this run_id via [Telemetry] (the worker template's
    # telemetry_run_id), so the stamp genuinely joins artifact to streams.
    stamp = artifact_stamp(run_id)
    run_id = stamp["run_id"]
    result: dict = {
        "metric": (
            f"dist_train global examples/sec ({processes}-process CPU pod, "
            f"gloo, shard-disjoint FMB files, packed wire, batch {batch}, "
            f"vocab {vocab}, nnz {NNZ})"
        ),
        **stamp,
        "processes": processes,
        "rows_per_host": rows,
    }
    with tempfile.TemporaryDirectory(prefix="bench-dist-") as tmp:
        script = os.path.join(tmp, "worker.py")
        with open(script, "w") as f:
            f.write(
                _DIST_WORKER.format(
                    repo=repo, vocab=vocab, batch=batch, nnz=NNZ, run_id=run_id
                )
            )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        procs = [
            subprocess.Popen(
                [sys.executable, script, str(p), str(processes), str(port), tmp,
                 ",".join(files)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
            )
            for p in range(processes)
        ]
        outs = [p.communicate(timeout=900)[0] for p in procs]
        failed = [
            (p, out)
            for p, (proc, out) in enumerate(zip(procs, outs))
            if proc.returncode != 0
        ]
        if failed:
            result["dist_error"] = failed[0][1][-800:]
            result["value"] = None
            if out_path:
                write_json_artifact(out_path, result)
            print(json.dumps(result))
            return result
        import json as _json

        def _metrics(path):
            recs = []
            try:
                with open(path) as f:
                    recs = [_json.loads(line) for line in f]
            except OSError:
                pass
            return recs

        per_host = {}
        for p in range(processes):
            path = os.path.join(tmp, "run.jsonl" if p == 0 else f"run.p{p}.jsonl")
            recs = _metrics(path)
            rates = [
                r["examples_per_sec"] for r in recs if r.get("kind") == "train"
            ]
            steady = sum(
                r.get("compiles", 0)
                for r in recs
                if r.get("kind") == "compile" and not r.get("warmup")
            )
            wire = [
                r["wire_bytes_per_step"]
                for r in recs
                if r.get("kind") == "input"
                and isinstance(r.get("wire_bytes_per_step"), (int, float))
            ]
            per_host[str(p)] = {
                "examples_per_sec_median": (
                    round(float(np.median(rates)), 1) if rates else None
                ),
                "steady_recompiles": steady,
                "wire_bytes_per_step": int(np.median(wire)) if wire else None,
            }
        lead = per_host.get("0", {})
        result["value"] = lead.get("examples_per_sec_median")
        result["unit"] = "examples/sec (global)"
        result["per_host"] = per_host
        result["steady_recompiles_total"] = sum(
            h["steady_recompiles"] for h in per_host.values()
        )
    if out_path:
        write_json_artifact(out_path, result)
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    import sys as _sys

    if len(_sys.argv) >= 2 and _sys.argv[1] == "--tier":
        # Beyond-HBM paramstore rung (`python bench.py --tier [args]`):
        # delegates to tools/probe_tier.py — one source of truth for the
        # Zipf(1.1) workload, the coverage-curve comparison, and the
        # committed PROBE_TIER artifact.
        import subprocess as _sp

        _script = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools", "probe_tier.py"
        )
        _sys.exit(_sp.call([_sys.executable, _script, *_sys.argv[2:]]))
    if len(_sys.argv) >= 2 and _sys.argv[1] == "--dist":
        # The processes lever runs standalone (it spawns its own pod and
        # never touches this process's jax backend): `python bench.py
        # --dist [N] [OUT.json]`.
        _n = int(_sys.argv[2]) if len(_sys.argv) > 2 else int(
            os.environ.get("BENCH_PROCESSES", "2")
        )
        _out = _sys.argv[3] if len(_sys.argv) > 3 else os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_DIST_r07.json"
        )
        _watchdog = arm_hang_exit(1200.0, what="bench --dist")
        bench_dist(_n, _out)
        _watchdog.cancel()
        _sys.exit(0)
    main()
