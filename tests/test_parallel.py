"""Sharded trainer == single-device trainer, on a virtual 8-device CPU mesh.

This is the determinism guarantee replacing the reference's Hogwild races
(SURVEY.md §5): the mesh-sharded step must reproduce the single-shard step
bit-for-bit (up to float reassociation).
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.models import Batch, DeepFMModel, FMModel
from fast_tffm_tpu.parallel import (
    init_sharded_state,
    make_mesh,
    make_sharded_predict_step,
    make_sharded_train_step,
)
from fast_tffm_tpu.trainer import init_state, make_predict_step, make_train_step

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices (see conftest.py)"
)

V = 96  # divisible by row shards (4) after padding


def _batches(rng, n=5, B=32, N=6, F=4):
    out = []
    for _ in range(n):
        out.append(
            Batch(
                labels=jnp.asarray(rng.integers(0, 2, size=(B,)).astype(np.float32)),
                ids=jnp.asarray(rng.integers(0, V, size=(B, N)).astype(np.int32)),
                vals=jnp.asarray(rng.normal(size=(B, N)).astype(np.float32)),
                fields=jnp.asarray((rng.integers(0, F, size=(B, N))).astype(np.int32)),
                weights=jnp.ones((B,), jnp.float32),
            )
        )
    return out


@pytest.mark.parametrize(
    "mesh_shape", [(8, 1), (1, 8), (4, 2), (2, 4)], ids=lambda s: f"data{s[0]}xrow{s[1]}"
)
def test_sharded_fm_matches_single_device(mesh_shape):
    model = FMModel(vocabulary_size=V, factor_num=4, order=2, factor_lambda=1e-4, bias_lambda=1e-4)
    mesh = make_mesh(*mesh_shape)
    rng = np.random.default_rng(0)
    batches = _batches(rng)

    ref_state = init_state(model, jax.random.key(7))
    ref_step = make_train_step(model, learning_rate=0.1)
    sh_state = init_sharded_state(model, mesh, jax.random.key(7))
    sh_step = make_sharded_train_step(model, 0.1, mesh)

    for b in batches:
        ref_state, ref_loss = ref_step(ref_state, b)
        sh_state, sh_loss = sh_step(sh_state, b)
        np.testing.assert_allclose(float(sh_loss), float(ref_loss), rtol=1e-5)

    V_pad = sh_state.table.shape[0]
    np.testing.assert_allclose(
        np.asarray(sh_state.table)[:V], np.asarray(ref_state.table), rtol=1e-4, atol=1e-6
    )
    # Vocab-padding rows (if any) stay at init.
    if V_pad > V:
        assert not np.any(np.asarray(sh_state.table)[V:, 0])

    ref_pred = make_predict_step(model)
    sh_pred = make_sharded_predict_step(model, mesh)
    b = batches[0]
    np.testing.assert_allclose(
        np.asarray(sh_pred(sh_state, b)), np.asarray(ref_pred(ref_state, b)), rtol=1e-4
    )


def test_sharded_deepfm_matches_single_device():
    model = DeepFMModel(vocabulary_size=V, num_fields=6, factor_num=4, hidden_dims=(8, 8, 8))
    mesh = make_mesh(2, 4)
    rng = np.random.default_rng(1)
    batches = _batches(rng, n=3)

    ref_state = init_state(model, jax.random.key(3))
    ref_step = make_train_step(model, learning_rate=0.05)
    sh_state = init_sharded_state(model, mesh, jax.random.key(3))
    sh_step = make_sharded_train_step(model, 0.05, mesh)

    for b in batches:
        ref_state, ref_loss = ref_step(ref_state, b)
        sh_state, sh_loss = sh_step(sh_state, b)
        np.testing.assert_allclose(float(sh_loss), float(ref_loss), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(sh_state.table)[:V], np.asarray(ref_state.table), rtol=1e-4, atol=1e-6
    )
    for k in ref_state.dense:
        np.testing.assert_allclose(
            np.asarray(sh_state.dense[k]), np.asarray(ref_state.dense[k]), rtol=1e-4, atol=1e-6
        )



def test_sharded_ffm_matches_single_device():
    from fast_tffm_tpu.models import FFMModel

    model = FFMModel(vocabulary_size=V, num_fields=4, factor_num=3)
    mesh = make_mesh(4, 2)
    rng = np.random.default_rng(2)
    batches = _batches(rng, n=3)

    ref_state = init_state(model, jax.random.key(5))
    ref_step = make_train_step(model, learning_rate=0.05)
    sh_state = init_sharded_state(model, mesh, jax.random.key(5))
    sh_step = make_sharded_train_step(model, 0.05, mesh)

    for b in batches:
        ref_state, ref_loss = ref_step(ref_state, b)
        sh_state, sh_loss = sh_step(sh_state, b)
        np.testing.assert_allclose(float(sh_loss), float(ref_loss), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(sh_state.table)[:V], np.asarray(ref_state.table), rtol=1e-4, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(make_sharded_predict_step(model, mesh)(sh_state, batches[0])),
        np.asarray(make_predict_step(model)(ref_state, batches[0])),
        rtol=1e-4,
    )


def test_table_actually_sharded():
    model = FMModel(vocabulary_size=V, factor_num=4)
    mesh = make_mesh(2, 4)
    state = init_sharded_state(model, mesh, jax.random.key(0))
    shard_shapes = {s.data.shape for s in state.table.addressable_shards}
    assert shard_shapes == {(V // 4, 5)}


def test_dist_batch_size_must_divide_mesh(tmp_path):
    # batch_size that doesn't split over every chip must fail with the
    # config-level message, not a shard_map axis error inside step one.
    from fast_tffm_tpu.config import Config
    from fast_tffm_tpu.training import dist_train
    from fast_tffm_tpu.prediction import dist_predict

    f = tmp_path / "d.libsvm"
    f.write_text("1 0:1.0\n0 1:1.0\n" * 8)
    n = jax.device_count()
    cfg = Config(
        model="fm", factor_num=2, vocabulary_size=16,
        model_file=str(tmp_path / "m.ckpt"),
        train_files=(str(f),), predict_files=(str(f),),
        score_path=str(tmp_path / "s.txt"),
        epoch_num=1, batch_size=n + 1,  # never divisible by n > 1 devices
    ).validate()
    for fn in (dist_train, dist_predict):
        with pytest.raises(ValueError, match=f"not divisible by the {n}-device mesh"):
            fn(cfg, log=lambda *_: None)


@pytest.mark.parametrize(
    "mesh_shape", [(1, 8), (2, 4), (4, 2)], ids=lambda s: f"data{s[0]}xrow{s[1]}"
)
def test_alltoall_lookup_matches_allgather(mesh_shape):
    """The routed (all_to_all) lookup must produce the SAME training
    trajectory as the all-gather lookup — same collectives semantics,
    fewer bytes.  Uniform ids keep every destination within capacity."""
    model = FMModel(vocabulary_size=V, factor_num=4, order=2)
    mesh = make_mesh(*mesh_shape)
    rng = np.random.default_rng(4)
    batches = _batches(rng, n=3)

    ag_state = init_sharded_state(model, mesh, jax.random.key(9))
    ag_step = make_sharded_train_step(model, 0.1, mesh)
    aa_state = init_sharded_state(model, mesh, jax.random.key(9))
    aa_step = make_sharded_train_step(model, 0.1, mesh, lookup="alltoall")

    for b in batches:
        ag_state, ag_loss = ag_step(ag_state, b)
        aa_state, aa_loss = aa_step(aa_state, b)
        np.testing.assert_allclose(float(aa_loss), float(ag_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(aa_state.table), np.asarray(ag_state.table), rtol=1e-5, atol=1e-7
    )

    ag_pred = make_sharded_predict_step(model, mesh)
    aa_pred = make_sharded_predict_step(model, mesh, lookup="alltoall")
    np.testing.assert_allclose(
        np.asarray(aa_pred(aa_state, batches[0])),
        np.asarray(ag_pred(ag_state, batches[0])),
        rtol=1e-5,
    )


def test_alltoall_overflow_poisons_not_corrupts():
    """Skewed ids that exceed a destination's capacity must surface as NaN
    (visible failure), never as silently wrong rows."""
    model = FMModel(vocabulary_size=V, factor_num=4)
    mesh = make_mesh(1, 8)
    step = make_sharded_train_step(model, 0.1, mesh, lookup="alltoall", capacity_factor=1.0)
    rng = np.random.default_rng(0)
    # Large batch so capacity (factor·M/R + tail slack) sits well below M,
    # then slam every id onto shard 0's row range.
    b = _batches(rng, n=1, B=256)[0]
    skewed = Batch(
        labels=b.labels,
        ids=jnp.zeros_like(b.ids),
        vals=b.vals,
        fields=b.fields,
        weights=b.weights,
    )
    _, loss = step(init_sharded_state(model, mesh, jax.random.key(0)), skewed)
    assert np.isnan(float(loss))
    # The same batch through the default lookup is finite (fresh state —
    # the train step donates its input state).
    _, ok_loss = make_sharded_train_step(model, 0.1, mesh)(
        init_sharded_state(model, mesh, jax.random.key(0)), skewed
    )
    assert np.isfinite(float(ok_loss))


def test_alltoall_overflow_aborts_training_before_checkpoint(tmp_path):
    """End-to-end: with lookup_overflow = abort, a capacity overflow must
    abort the RUN (RuntimeError naming the remedy), not keep training on
    NaN state or overwrite the checkpoint with it."""
    from fast_tffm_tpu.config import Config
    from fast_tffm_tpu.training import dist_train

    f = tmp_path / "skew.libsvm"
    # Every row: 8 occurrences of id 0 — all routed to shard 0.
    f.write_text("".join("1 " + " ".join("0:1.0" for _ in range(8)) + "\n" for _ in range(64)))
    cfg = Config(
        model="fm", factor_num=2, vocabulary_size=64,
        model_file=str(tmp_path / "m.ckpt"),
        train_files=(str(f),),
        epoch_num=1, batch_size=64, learning_rate=0.1, log_every=1,
        row_parallel=8, lookup="alltoall", lookup_capacity_factor=0.5,
        lookup_overflow="abort",
    ).validate()
    with pytest.raises(RuntimeError, match="lookup_capacity_factor"):
        dist_train(cfg, log=lambda *_: None)
    assert not (tmp_path / "m.ckpt").exists()  # no poisoned checkpoint


def test_alltoall_overflow_fallback_matches_allgather():
    """lookup_overflow = fallback: an overflowing step must produce EXACTLY
    the allgather step's result (same state, finite loss), flag the event,
    and a non-overflowing step must stay on the routed path (flag 0,
    result identical to the abort-mode alltoall step)."""
    model = FMModel(vocabulary_size=V, factor_num=4)
    mesh = make_mesh(1, 8)
    rng = np.random.default_rng(6)
    uniform = _batches(rng, n=1, B=256)[0]
    skewed = Batch(
        labels=uniform.labels,
        ids=jnp.zeros_like(uniform.ids),  # all ids -> shard 0: overflow
        vals=uniform.vals,
        fields=uniform.fields,
        weights=uniform.weights,
    )
    mk = lambda **kw: make_sharded_train_step(
        model, 0.1, mesh, lookup="alltoall", capacity_factor=1.0, **kw
    )
    fb_step = mk(overflow_mode="fallback")
    ag_step = make_sharded_train_step(model, 0.1, mesh)

    # Overflowing batch: fallback == allgather, bit for bit, and flagged.
    fb_state, fb_loss, over = fb_step(
        init_sharded_state(model, mesh, jax.random.key(1)), skewed
    )
    ag_state, ag_loss = ag_step(
        init_sharded_state(model, mesh, jax.random.key(1)), skewed
    )
    assert int(over) == 1
    assert np.isfinite(float(fb_loss))
    np.testing.assert_array_equal(np.asarray(fb_loss), np.asarray(ag_loss))
    np.testing.assert_array_equal(np.asarray(fb_state.table), np.asarray(ag_state.table))
    np.testing.assert_array_equal(
        np.asarray(fb_state.table_opt.accum), np.asarray(ag_state.table_opt.accum)
    )

    # Uniform batch: no flag, and the routed path's result (== the
    # abort-mode step's) is what lands.
    fb_state, fb_loss, over = fb_step(
        init_sharded_state(model, mesh, jax.random.key(2)), uniform
    )
    aa_state, aa_loss = mk(overflow_mode="abort")(
        init_sharded_state(model, mesh, jax.random.key(2)), uniform
    )
    assert int(over) == 0
    np.testing.assert_array_equal(np.asarray(fb_loss), np.asarray(aa_loss))
    np.testing.assert_array_equal(np.asarray(fb_state.table), np.asarray(aa_state.table))


def test_alltoall_predict_fallback_finite_and_matches():
    """Predict with fallback: an overflowing batch's scores must equal the
    allgather predict's scores instead of NaN-poisoning."""
    model = FMModel(vocabulary_size=V, factor_num=4)
    mesh = make_mesh(1, 8)
    rng = np.random.default_rng(8)
    b = _batches(rng, n=1, B=256)[0]
    skewed = Batch(
        labels=b.labels, ids=jnp.zeros_like(b.ids), vals=b.vals,
        fields=b.fields, weights=b.weights,
    )
    state = init_sharded_state(model, mesh, jax.random.key(3))
    fb = make_sharded_predict_step(
        model, mesh, lookup="alltoall", capacity_factor=1.0,
        overflow_mode="fallback",
    )(state, skewed)
    ag = make_sharded_predict_step(model, mesh)(state, skewed)
    assert np.isfinite(np.asarray(fb)).all()
    np.testing.assert_array_equal(np.asarray(fb), np.asarray(ag))


def test_alltoall_overflow_fallback_trains_through(tmp_path):
    """End-to-end: the default lookup_overflow = fallback trains THROUGH a
    deliberately-undersized capacity — finite losses, checkpoint written,
    overflow steps counted in the JSONL metrics."""
    import json

    from fast_tffm_tpu.config import Config
    from fast_tffm_tpu.training import dist_train

    f = tmp_path / "skew.libsvm"
    f.write_text("".join("1 " + " ".join("0:1.0" for _ in range(8)) + "\n" for _ in range(64)))
    cfg = Config(
        model="fm", factor_num=2, vocabulary_size=64,
        model_file=str(tmp_path / "m.ckpt"),
        train_files=(str(f),),
        epoch_num=1, batch_size=64, learning_rate=0.1, log_every=1,
        row_parallel=8, lookup="alltoall", lookup_capacity_factor=0.5,
        metrics_path=str(tmp_path / "metrics.jsonl"),
    ).validate()
    assert cfg.lookup_overflow == "fallback"  # the default
    state = dist_train(cfg, log=lambda *_: None)
    assert (tmp_path / "m.ckpt").exists()
    assert np.isfinite(np.asarray(state.table)).all()
    records = [
        json.loads(line) for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    assert sum(r.get("lookup_overflow_steps", 0) for r in records) >= 1


def test_lookup_choice_changes_emitted_collectives():
    """The compiled HLO must actually contain the intended collectives:
    all-gather + reduce-scatter for the default lookup; all-to-all (and no
    row reduce-scatter) for the routed one."""
    model = FMModel(vocabulary_size=V, factor_num=4)
    mesh = make_mesh(2, 4)
    state = init_sharded_state(model, mesh, jax.random.key(0))
    rng = np.random.default_rng(0)
    b = _batches(rng, n=1)[0]

    def hlo_for(lookup):
        step = make_sharded_train_step(model, 0.1, mesh, lookup=lookup)
        return jax.jit(lambda s, bb: step(s, bb)).lower(state, b).compile().as_text()

    ag = hlo_for("allgather")
    assert "all-to-all" not in ag and "reduce-scatter" in ag
    aa = hlo_for("alltoall")
    assert "all-to-all" in aa and "reduce-scatter" not in aa


# --- ICI byte accounting from compiled HLO -------------------------------
#
# The alltoall docstring claims ~R× fewer ICI bytes than the allgather
# path (parallel/alltoall.py).  No multi-chip hardware exists here, but the
# byte counts are a static property of the compiled program: parse every
# cross-device collective out of the HLO, model per-device wire bytes with
# the standard ring costs, and pin the ratio.

_HLO_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "pred": 1, "s8": 1, "u8": 1,
}
_HLO_SHAPE_RE = re.compile(
    r"(f64|s64|u64|f32|s32|u32|bf16|f16|s16|u16|pred|s8|u8)\[([\d,]*)\]"
)
_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?\S+ = (.*?) "
    r"(all-to-all|all-gather|reduce-scatter|collective-permute|all-reduce)"
    r"\(.*?replica_groups=(\{\{[\d,{} ]*\}\}|\[\d+,\d+\]<=)",
    re.M,
)


def hlo_ici_bytes(hlo: str) -> dict:
    """Per-device wire bytes by collective op, from compiled HLO text.

    Ring-algorithm costs: all-gather (g-1)/g × result, reduce-scatter
    (g-1) × result (its input is g× the result), all-to-all (g-1)/g ×
    buffer, all-reduce 2(g-1)/g × buffer.  Group size g comes from
    ``replica_groups`` (explicit or iota [n,g]<= form); g=1 collectives
    (e.g. a data-axis gather on a 1-wide axis) cost zero, as on hardware.
    """
    totals = {}
    for m in _HLO_OP_RE.finditer(hlo):
        shapes, op, groups = m.groups()
        if groups.startswith("{{"):
            g = groups[2:].split("}")[0].count(",") + 1
        else:  # iota form: replica_groups=[num_groups,group_size]<=
            g = int(groups[1:-3].split(",")[1])
        result = sum(
            math.prod(
                int(x) for x in sm.group(2).split(",") if x
            ) * _HLO_DTYPE_BYTES[sm.group(1)]
            for sm in _HLO_SHAPE_RE.finditer(shapes)
        )
        wire = {
            "all-gather": result * (g - 1) / g,
            "all-to-all": result * (g - 1) / g,
            "reduce-scatter": result * (g - 1),
            "all-reduce": 2 * result * (g - 1) / g,
            "collective-permute": float(result),
        }[op]
        totals[op] = totals.get(op, 0.0) + wire
    return totals


def test_alltoall_moves_fewer_ici_bytes():
    """Pin the ICI byte claim (alltoall.py:17): at R=8 with capacity giving
    ~1.4× slack, the routed path's per-step wire bytes are a small fraction
    of the allgather path's — measured statically from the compiled HLO."""
    V8 = 4096
    model = FMModel(vocabulary_size=V8, factor_num=8, order=2)
    mesh = make_mesh(1, 8)
    state = init_sharded_state(model, mesh, jax.random.key(0))
    rng = np.random.default_rng(0)
    B, N = 512, 16
    b = Batch(
        labels=jnp.asarray(rng.integers(0, 2, size=(B,)).astype(np.float32)),
        ids=jnp.asarray(rng.integers(0, V8, size=(B, N)).astype(np.int32)),
        vals=jnp.asarray(rng.normal(size=(B, N)).astype(np.float32)),
        fields=jnp.zeros((B, N), jnp.int32),
        weights=jnp.ones((B,), jnp.float32),
    )

    def wire_bytes(lookup):
        step = make_sharded_train_step(
            model, 0.1, mesh, lookup=lookup, capacity_factor=1.0
        )
        hlo = jax.jit(lambda s, bb: step(s, bb)).lower(state, b).compile().as_text()
        return hlo_ici_bytes(hlo)

    ag = wire_bytes("allgather")
    aa = wire_bytes("alltoall")
    # Strategy shape sanity: the bytes live where the design says they do.
    assert ag.get("all-to-all", 0) == 0 and ag["reduce-scatter"] > 0
    assert aa.get("reduce-scatter", 0) == 0 and aa["all-to-all"] > 0
    ag_total = sum(ag.values())
    aa_total = sum(aa.values())
    # Measured at these shapes (M=1024 ids/chip, cap=184, slack≈1.44):
    # allgather ≈ 573 KiB/step/device vs alltoall ≈ 103 KiB — a 5.6×
    # reduction.  Pin a conservative 3× so benign compiler-version shape
    # jitter can't flake the suite, plus the exact all-to-all buffer size
    # (2 directions × (ids + rows) over the [R, C(, D)] buffers).
    assert ag_total > 3 * aa_total, (ag, aa)
    C = 184  # capacity_for(1024, 8, 1.0), pinned
    R, D = 8, 9
    expected_a2a = 2 * (R * C * 4 + R * C * D * 4) * (R - 1) / R
    assert aa["all-to-all"] == expected_a2a


def test_impossible_overflow_skips_cond():
    """When capacity_for caps at M (overflow statically impossible), the
    fallback step must emit the routed branch ALONE: no lax.cond dual
    compile, no routing_overflow bincount — pinned by the absence of any
    conditional and of the allgather branch's reduce-scatter in the HLO."""
    model = FMModel(vocabulary_size=V, factor_num=4)
    mesh = make_mesh(2, 4)
    state = init_sharded_state(model, mesh, jax.random.key(0))
    rng = np.random.default_rng(0)
    b = _batches(rng, n=1)[0]  # B=32 → M=24 ids/chip: cap caps at M

    def hlo_for(capacity_factor, B=32):
        bb = b
        if B != 32:
            bb = _batches(rng, n=1, B=B)[0]
        step = make_sharded_train_step(
            model, 0.1, mesh, lookup="alltoall",
            capacity_factor=capacity_factor, overflow_mode="fallback",
        )
        return jax.jit(lambda s, bb_: step(s, bb_)).lower(state, bb).compile().as_text()

    from fast_tffm_tpu.parallel.alltoall import capacity_for

    assert capacity_for(24, 4, 2.0) == 24  # the premise: cap == M
    short = hlo_for(2.0)
    assert "conditional" not in short and "reduce-scatter" not in short
    assert "all-to-all" in short

    # Contrast: a capacity below M must still compile both branches.
    full = hlo_for(0.25, B=256)
    assert "conditional" in full and "all-to-all" in full


def test_impossible_overflow_still_counts_zero():
    """The short-circuited fallback step keeps the 3-tuple API and reports
    a constant 0 overflow flag."""
    model = FMModel(vocabulary_size=V, factor_num=4)
    mesh = make_mesh(2, 4)
    state = init_sharded_state(model, mesh, jax.random.key(0))
    rng = np.random.default_rng(0)
    b = _batches(rng, n=1)[0]
    step = make_sharded_train_step(
        model, 0.1, mesh, lookup="alltoall", overflow_mode="fallback"
    )
    state, loss, overflowed = step(state, b)
    assert int(overflowed) == 0 and np.isfinite(float(loss))


# --- PR 35: the state drawn shard by shard, the exchange named and counted --

V_PAD = 1001  # needs padding on every mesh below, under both layouts


@pytest.mark.parametrize("layout", ["rows", "packed"])
@pytest.mark.parametrize("accumulator", ["element", "row"])
@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2), (4, 1)], ids=lambda s: f"data{s[0]}xrow{s[1]}")
def test_init_sharded_state_is_the_one_device_draw(mesh_shape, accumulator, layout):
    """Table, accumulator and dense leaves of init_sharded_state are
    trainer.init_state's on the padded model BIT FOR BIT (the packed layout:
    its pack_state), padded rows included, with the table row-sharded."""
    from fast_tffm_tpu.parallel.train_step import _pad_model_vocab, packed_shard_meta
    from fast_tffm_tpu.trainer import pack_state

    model = DeepFMModel(vocabulary_size=V_PAD, num_fields=3, factor_num=4, hidden_dims=(8,))
    mesh = make_mesh(*mesh_shape)
    got = init_sharded_state(model, mesh, jax.random.key(7), 0.25, accumulator, table_layout=layout)
    if layout == "packed":
        padded, _, _ = packed_shard_meta(model, mesh)
        want = pack_state(init_state(padded, jax.random.key(7), 0.25, accumulator), 0.25)
    else:
        padded = _pad_model_vocab(model, mesh)
        want = init_state(padded, jax.random.key(7), 0.25, accumulator)
    assert padded.vocabulary_size >= V_PAD and (mesh_shape[1] == 1 or padded.vocabulary_size > V_PAD)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path))
    assert got.table.sharding.spec == jax.sharding.PartitionSpec("row", None)
    assert {s.data.shape[0] for s in got.table.addressable_shards} == {got.table.shape[0] // mesh_shape[1]}
    assert got.dense["w0"].sharding.is_fully_replicated


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)], ids=lambda s: f"data{s[0]}xrow{s[1]}")
def test_init_sharded_state_asks_no_device_for_a_whole_table(mesh_shape):
    """Compiled, the construction behind init_sharded_state hands each device
    its shard of table and accumulator and nothing table-shaped besides: its
    outputs are the shard's bytes, and arguments + outputs + temporaries are
    the row-th part of what the one-device draw asks (5% of margin), at
    V = 2^16 rows of 17."""
    from fast_tffm_tpu.parallel.train_step import _sharded_table_init
    from fast_tffm_tpu.trainer import init_table_state

    vocab, rows = 1 << 16, mesh_shape[1]
    model = FMModel(vocabulary_size=vocab, factor_num=16)
    key = jax.random.split(jax.random.key(0))[0]

    def asked(jitted):
        m = jitted.lower(key).compile().memory_analysis()
        return m.output_size_in_bytes, m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes

    one_out, one_all = asked(jax.jit(lambda k: init_table_state(model, k, 0.1, "element")))
    out, everything = asked(_sharded_table_init(model, make_mesh(*mesh_shape), 0.1, "element"))
    whole = vocab * 17 * 4
    assert one_out >= 2 * whole  # the premise: one device, both arrays whole
    assert 2 * whole // rows <= out <= 2 * whole // rows + 64
    assert everything <= 1.05 * one_all / rows


_COLLECTIVES = {"all_gather", "reduce_scatter", "all_to_all", "psum", "psum_invariant", "ppermute"}
_HLO_COLLECTIVE = re.compile(
    r"= \S+ (?:all-gather|all-reduce|all-to-all|reduce-scatter|collective-permute)(?:-start)?\("
)


def _leaf_eqns(jaxpr):
    """Every equation that holds no jaxpr of its own, at any depth."""
    from fast_tffm_tpu.parallel.exchange import _sub_jaxprs

    for eqn in jaxpr.eqns:
        inner = list(_sub_jaxprs(eqn))
        if not inner:
            yield eqn
        for j in inner:
            yield from _leaf_eqns(j)


@pytest.mark.parametrize(
    "lookup,overflow_mode,layout,accumulator",
    [
        ("allgather", "abort", "rows", "element"),
        ("alltoall", "abort", "rows", "element"),
        ("alltoall", "fallback", "rows", "element"),
        ("allgather", "abort", "packed", "element"),
        ("alltoall", "fallback", "packed", "row"),
        ("allgather", "abort", "packed", "fused"),
    ],
)
def test_every_collective_of_the_sharded_step_is_named_fm_exchange(lookup, overflow_mode, layout, accumulator):
    """As traced: an equation runs under ``fm.exchange`` exactly when it is a
    collective (the scope names the exchange and nothing of the local work),
    nested in fm.gather, fm.tail or fm.loss.  As compiled: every collective
    instruction carries the scope in its op_name."""
    model = DeepFMModel(vocabulary_size=V, num_fields=6, factor_num=4, hidden_dims=(8,))
    mesh = make_mesh(2, 4)
    state = init_sharded_state(model, mesh, jax.random.key(0), 0.1, accumulator, table_layout=layout)
    b = _batches(np.random.default_rng(0), n=1, B=256)[0]
    step = make_sharded_train_step(
        model, 0.1, mesh, lookup=lookup, overflow_mode=overflow_mode, capacity_factor=0.5,
        table_layout=layout, accumulator=accumulator,
    )
    eqns = list(_leaf_eqns(jax.make_jaxpr(step)(state, b).jaxpr))
    named = [e for e in eqns if "fm.exchange" in str(e.source_info.name_stack)]
    collectives = [e for e in eqns if e.primitive.name in _COLLECTIVES]
    assert collectives and {id(e) for e in named} == {id(e) for e in collectives}
    for e in collectives:
        assert re.search(r"fm\.(gather|tail|loss)\)*/fm\.exchange$", str(e.source_info.name_stack)), e.source_info.name_stack
    if lookup == "alltoall":
        assert any(e.primitive.name == "all_to_all" for e in collectives)
    hlo = step.lower(state, b).compile().as_text()
    lines = [ln for ln in hlo.splitlines() if _HLO_COLLECTIVE.search(ln)]
    assert lines and all("fm.exchange" in ln for ln in lines)


@pytest.mark.parametrize(
    "mesh_shape,lookup,want",
    [
        # ids i32[2,3] all-gathered over row=4 (24 B x 2 x 3), rows f32[8,3,5]
        # reduce-scattered (480 B x 2 x 3/4), the update's unique ids i32[6] and
        # sums f32[6,5] all-gathered over 4 chips (24 and 120 B x 2 x 3), two
        # scalar all-reduces (the batch's weight, the loss: 4 B x 4 x 3/4).
        ((1, 4), "allgather", 144 + 720 + 144 + 720 + 12 + 12),
        # row=1: the lookup crosses no chip; the update's all-gathers do.
        ((4, 1), "allgather", 144 + 720 + 12 + 12),
        # row=2: ids 24 B x 2 x 1, rows f32[4,3,5] 240 B x 2 x 1/2; update over 4 chips.
        ((2, 2), "allgather", 48 + 240 + 144 + 720 + 12 + 12),
        # routed, capacity 8 a destination (capacity_for's floor): ids i32[4,8]
        # and rows f32[4,8,5] all-to-all (128 and 640 B x 2 x 3/4) for the lookup
        # and as much again for the update, the overflow flag's all-reduce (12)
        # beside the two scalars.
        ((1, 4), "alltoall", 2 * (192 + 960) + 12 + 12 + 12),
    ],
)
def test_exchange_bytes_are_the_collectives_operands_by_hand(mesh_shape, lookup, want):
    from fast_tffm_tpu.parallel.exchange import exchange_bytes

    model = FMModel(vocabulary_size=V, factor_num=4)
    mesh = make_mesh(*mesh_shape)
    state = init_sharded_state(model, mesh, jax.random.key(0))
    B, N = 8, 3
    sds = jax.ShapeDtypeStruct
    batch = Batch(
        labels=sds((B,), jnp.float32), ids=sds((B, N), jnp.int32), vals=sds((B, N), jnp.float32),
        fields=sds((B, 0), jnp.int32), weights=sds((B,), jnp.float32),
    )
    step = make_sharded_train_step(model, 0.1, mesh, lookup=lookup)
    assert exchange_bytes(step, state, batch) == want
    # A fused call of K steps moves K times as much; the fallback's cond
    # counts the routed branch, not both.
    if lookup == "allgather":
        k_step = make_sharded_train_step(model, 0.1, mesh, steps_per_call=3)
        k_batch = jax.tree.map(lambda x: sds((3,) + x.shape, x.dtype), batch)
        assert exchange_bytes(k_step, state, k_batch) == 3 * want


@pytest.mark.parametrize("steps_per_call", [1, 2])
def test_dist_train_records_what_its_exchange_moves(tmp_path, steps_per_call):
    """dist_train says once at start-up, on its kind=profile record and on
    every kind=train record how many bytes a chip sends and receives in one
    step's collectives, worked out here by hand for a 1x4 mesh: 16 rows a
    chip x 8 ids, rows of 5 float32."""
    import json

    from fast_tffm_tpu.config import Config
    from fast_tffm_tpu.training import dist_train

    f = tmp_path / "d.libsvm"
    f.write_text("".join(f"{i % 2} " + " ".join(f"{(i * 7 + j * 13) % 96}:1.0" for j in range(8)) + "\n" for i in range(256)))
    cfg = Config(
        model="fm", factor_num=4, vocabulary_size=96, model_file=str(tmp_path / "m.ckpt"),
        train_files=(str(f),), epoch_num=1, batch_size=64, learning_rate=0.1, log_every=2,
        data_parallel=1, row_parallel=4, steps_per_call=steps_per_call,
        metrics_path=str(tmp_path / "metrics.jsonl"),
    ).validate()
    said = []
    dist_train(cfg, log=said.append, mesh=make_mesh(1, 4))
    ids, rows = 16 * 8 * 4, 16 * 8 * 5 * 4  # a chip's ids and its rows, in bytes
    # ... the two scalars of the loss, and (ISSUE 38) the int32 flag beside them:
    # "this shard's tail took the whole list" (384 of the 512 slots bound it here).
    by_hand = ids * 6 + 4 * rows * 3 // 2 + ids * 6 + rows * 6 + 12 + 12 + 12
    records = [json.loads(line) for line in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    trains = [r for r in records if r["kind"] == "train"]
    assert trains and all(r["exchange_bytes_per_step"] == by_hand for r in trains)
    assert all(r["shard_tail_full_steps"] == 0 for r in trains)  # 96 distinct ids: nobody owns over 384
    profile = next(r for r in records if r["kind"] == "profile" and r["program"] == "train_step")
    assert (profile["mesh"], profile["shard_rows"], profile["lookup"], profile["exchange_bytes_per_step"]) == (
        {"data": 1, "row": 4}, 24, "allgather", by_hand)
    assert "tail_form" in profile and "row_dim" in profile  # beside the tail's fields
    assert any(str(s).startswith("exchange: allgather lookup, 24 rows a shard") and f"{by_hand} bytes" in str(s) for s in said)
    # The lookup's form off a TPU: the masked row gather of the 4 x 16 x 8
    # slots the row peers hand a shard, and no chip counted as reading them all.
    assert (profile["lookup_form"], profile["lookup_slots"]) == ("rows", 512)
    assert "shard lookup: xla masked row gather (512 slots of 5 a step)" in said
    assert all(r["shard_lookup_full_steps"] == 0 for r in trains)


# --- PR 36: the shard's tail is the single-device step's, and says which form it took --


@pytest.mark.parametrize("lookup", ["allgather", "alltoall"])
@pytest.mark.parametrize("form", ["rows", "sweep"])
def test_dist_train_says_the_form_its_shard_tail_took(tmp_path, monkeypatch, form, lookup):
    """dist_train's start-up line and its kind=profile record carry the tail's
    trace-time choices at the SHARD's shapes and the gathered ids, and they are
    the truth: ``optim.rows_tail_form`` is asked at those very shapes by the
    step as it is traced, and the traced step holds the Pallas call exactly
    when the record says ``sweep`` (patched in, as a TPU's rule would say; on
    the CPU mesh the rule itself says ``rows``)."""
    import json

    from fast_tffm_tpu import optim
    from fast_tffm_tpu.config import Config
    import fast_tffm_tpu.parallel as par
    from fast_tffm_tpu.parallel.alltoall import capacity_for
    from fast_tffm_tpu.training import dist_train

    rule, asked = optim.rows_tail_form, []
    monkeypatch.setattr(
        optim, "rows_tail_form",
        lambda *a, **k: asked.append(a) or ("sweep" if form == "sweep" else rule(*a, **k)),
    )
    steps, make = [], par.make_sharded_train_step
    monkeypatch.setattr(par, "make_sharded_train_step", lambda *a, **k: steps.append(make(*a, **k)) or steps[-1])
    f = tmp_path / "d.libsvm"
    f.write_text("".join(f"{i % 2} " + " ".join(f"{(i * 7 + j * 13) % 96}:1.0" for j in range(8)) + "\n" for i in range(128)))
    cfg = Config(
        model="fm", factor_num=16, vocabulary_size=96, model_file=str(tmp_path / "m.ckpt"),
        train_files=(str(f),), epoch_num=1, batch_size=64, learning_rate=0.1, log_every=2,
        data_parallel=1, row_parallel=4, lookup=lookup, metrics_path=str(tmp_path / "metrics.jsonl"),
    ).validate()
    said = []
    state = dist_train(cfg, log=said.append, mesh=make_mesh(1, 4))
    assert np.isfinite(np.asarray(state.table)).all()
    # A shard of 24 rows of 17; from each of four chips the capacity one row peer
    # sends another: the routed update's slots, and (ISSUE 38) what the tail keeps
    # of the all-gather update's 4 x 16 x 8.
    slots = 4 * capacity_for(16 * 8, 4, cfg.lookup_capacity_factor)
    handed = slots if lookup == "alltoall" else 4 * 16 * 8
    assert slots == 384
    # dist_train's question and the traced step's are the same one (the tail's
    # whole-list branch, and under ``lookup_overflow = fallback`` the cond's other
    # branch, ask at the all-gather's slots).
    assert asked.count((24, slots, 17, 17)) >= 2
    assert set(asked) <= {(24, slots, 17, 17), (24, 4 * 16 * 8, 17, 17)}
    records = [json.loads(line) for line in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    profile = next(r for r in records if r["kind"] == "profile" and r["program"] == "train_step")
    want = optim.rows_tail_profile(24, slots, 17, form, handed)
    assert want["tail_form"] == form and profile["row_dim"] == 17
    assert {k: profile[k] for k in want} == want and profile["tail_slots"] == slots
    trains = [r for r in records if r["kind"] == "train"]
    assert trains and all(r["shard_tail_full_steps"] == 0 for r in trains)
    assert sum(f"; the shard's first {slots} of {handed} exchanged slots" in str(s) for s in said) == 1
    if form == "sweep":
        assert (profile["tail_duplicates"], profile["tail_permutation"], profile["segment_sum_lanes"]) == ("kernel", "row gather", None)
        line = "sparse tail: pallas rows sweep (block 128 lanes, 1 blocks; duplicates summed in the kernel"
    else:
        assert (profile["tail_duplicates"], profile["tail_block_lanes"]) == ("segment_sum", None)
        line = "sparse tail: xla rows (segment sum on 128-lane rows (row width 17)"
    assert sum(str(s).startswith("sparse tail: ") for s in said) == 1  # said once
    assert any(str(s).startswith(line) for s in said), said
    # The step as dist_train built it, traced again at the run's shapes.
    sds = jax.ShapeDtypeStruct
    batch = Batch(
        labels=sds((64,), jnp.float32), ids=sds((64, 8), jnp.int32), vals=sds((64, 8), jnp.float32),
        fields=sds((64, 0), jnp.int32), weights=sds((64,), jnp.float32),
    )
    abstract = jax.tree.map(lambda x: sds(x.shape, x.dtype), state)
    from fast_tffm_tpu.parallel.exchange import _sub_jaxprs

    def primitives(jaxpr):  # of every equation at any depth, those that hold a jaxpr too
        for e in jaxpr.eqns:
            yield e.primitive.name
            for inner in _sub_jaxprs(e):
                yield from primitives(inner)

    prims = set(primitives(jax.make_jaxpr(steps[0])(abstract, batch).jaxpr))
    assert ("pallas_call" in prims) == (form == "sweep")
