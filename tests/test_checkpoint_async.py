"""Async + incremental checkpointing (ISSUE 5 tentpole).

Pins, per the acceptance criteria:
  * async and delta restores are BIT-IDENTICAL to a sync-save restore of
    the same step — on the streamed, device-cached, and sharded driver
    paths, rows and packed (and fused) layouts;
  * the train-loop stall of an async save is < 25% of a sync save's on
    the same workload (not-slow);
  * kill-during-save leaves the previous checkpoint loadable;
  * torn/partial files (truncated npz, half-written delta, broken chain)
    fail the TRAIN restore path with an error NAMING the file — never
    garbage.
"""

import json
import os
import signal
import time

import jax
import numpy as np
import pytest

from fast_tffm_tpu.checkpoint import (
    checkpoint_save_id,
    checkpoint_signature,
    delta_paths,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
    save_delta,
)
from fast_tffm_tpu.checkpoint_async import AsyncCheckpointer
from fast_tffm_tpu.config import Config, build_model, load_config
from fast_tffm_tpu.models import FMModel
from fast_tffm_tpu.trainer import init_state
from fast_tffm_tpu.training import train
from tests.test_e2e import _write_cfg, _write_dataset


class _Abort(Exception):
    """Deterministic mid-run abort: skips the final sync save, so the
    on-disk checkpoint is whatever the boundary under test published."""


def _abort_at(n):
    def hook(step):
        if step >= n:
            raise _Abort()

    return hook


def _sigterm_at(n):
    fired = []

    def hook(step):
        if step >= n and not fired:
            fired.append(step)
            os.kill(os.getpid(), signal.SIGTERM)

    return hook


def _workspace(tmp_path, name, extra=""):
    d = tmp_path / name
    d.mkdir()
    rng = np.random.default_rng(0)
    _write_dataset(d / "train.libsvm", rng, n=300)
    _write_dataset(d / "valid.libsvm", rng, n=50)
    _write_cfg(d / "run.cfg", d, extra=extra)
    cfg = load_config(str(d / "run.cfg"))
    cfg.validation_files = ()  # keep the runs step-deterministic and fast
    return cfg


_LAYOUTS = {
    "rows": ("", "element"),
    "packed": ("table_layout = packed\n", "element"),
    "fused": (
        "table_layout = packed\n",
        "fused",
    ),
}


def _mk_cfg(tmp_path, name, layout, ckpt_extra=""):
    cfg = _workspace(tmp_path, name, extra=ckpt_extra)
    if layout in ("packed", "fused"):
        cfg.table_layout = "packed"
    if layout == "fused":
        cfg.adagrad_accumulator = "fused"
    cfg.validate()
    return cfg


def _restore_like(cfg, key=99):
    """A fresh template matching the checkpoint's LOGICAL layout (fused
    checkpoints store a [V, 1] row accumulator)."""
    model = build_model(cfg)
    accum = "row" if cfg.adagrad_accumulator == "fused" else cfg.adagrad_accumulator
    return restore_checkpoint(
        cfg.model_file, init_state(model, jax.random.key(key), accumulator=accum)
    )


def _assert_states_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- bit-identity: streamed driver ---------------------------------------


@pytest.mark.parametrize("layout", ["rows", "packed", "fused"])
def test_delta_restore_bit_identical_streamed(tmp_path, layout):
    """Base + delta chain replays to EXACTLY the state a sync save at the
    same step produced (training is deterministic, so two runs on the
    same data reach identical step-6 states; only the save paths differ).
    The delta run aborts (no final save), leaving base@3 + delta@6; the
    sync run SIGTERMs at 6, leaving a classic full save@6."""
    cfg_d = _mk_cfg(tmp_path, "delta", layout, "[Checkpoint]\ndelta_every_steps = 3\n")
    with pytest.raises(_Abort):
        train(cfg_d, log=lambda *_: None, step_hook=_abort_at(8))
    assert [os.path.basename(p) for p in delta_paths(cfg_d.model_file)] == [
        "model.ckpt.delta-0001.npz"
    ]
    assert latest_step(cfg_d.model_file) == 6

    cfg_s = _mk_cfg(tmp_path, "sync", layout)
    train(cfg_s, log=lambda *_: None, step_hook=_sigterm_at(6))
    assert latest_step(cfg_s.model_file) == 6

    _assert_states_equal(_restore_like(cfg_d), _restore_like(cfg_s))


@pytest.mark.parametrize("layout", ["rows", "packed"])
def test_async_restore_bit_identical_streamed(tmp_path, layout):
    """An async epoch save restores bitwise-equal to a sync epoch save of
    the same step (both runs abort after the epoch-0 boundary so the
    final sync save never overwrites the save under test)."""
    states = {}
    for name, extra in (("async", "[Checkpoint]\nasync_save = true\n"), ("syncref", "")):
        cfg = _mk_cfg(tmp_path, name, layout, extra)
        cfg.metrics_path = str(tmp_path / f"{name}.jsonl")
        with pytest.raises(_Abort):
            # 300 rows / batch 32 -> 10 steps/epoch: abort in epoch 1,
            # after the epoch-0 save boundary published step 10.
            train(cfg, log=lambda *_: None, step_hook=_abort_at(12))
        assert latest_step(cfg.model_file) == 10
        states[name] = _restore_like(cfg)
    _assert_states_equal(states["async"], states["syncref"])
    # Telemetry: the async save emitted a kind=ckpt record, mode=full.
    recs = [json.loads(l) for l in open(str(tmp_path / "async.jsonl"))]
    modes = [r["mode"] for r in recs if r["kind"] == "ckpt"]
    assert "full" in modes


def test_async_delta_combined_full_run(tmp_path):
    """async_save + delta_every_steps through a full run: epoch saves go
    async, deltas land between them, the final save is synchronous and
    resets the chain — the end state on disk equals a plain run's."""
    cfg = _mk_cfg(
        tmp_path, "combo", "packed",
        "[Checkpoint]\nasync_save = true\ndelta_every_steps = 4\n",
    )
    cfg.metrics_path = str(tmp_path / "combo.jsonl")
    state = train(cfg, log=lambda *_: None)
    # Final sync save reset the chain: no delta files survive a run end.
    assert delta_paths(cfg.model_file) == []
    assert latest_step(cfg.model_file) == int(state.step)

    cfg_p = _mk_cfg(tmp_path, "plain", "packed")
    state_p = train(cfg_p, log=lambda *_: None)
    _assert_states_equal(_restore_like(cfg), _restore_like(cfg_p))
    assert int(state.step) == int(state_p.step)
    recs = [json.loads(l) for l in open(cfg.metrics_path)]
    ck = [r for r in recs if r["kind"] == "ckpt"]
    assert {r["mode"] for r in ck} >= {"full", "delta"}
    # Schema: every ckpt record carries its required keys.
    from fast_tffm_tpu.telemetry import SCHEMAS

    for r in ck:
        assert all(k in r for k in SCHEMAS["ckpt"])


# -- bit-identity: device-cached driver ----------------------------------


@pytest.mark.parametrize("layout", ["rows", "packed"])
def test_delta_restore_bit_identical_device_cached(tmp_path, layout):
    """The device-cache driver marks touched rows from the RESIDENT id
    arrays (no per-step host ids exist); the chain must still replay to
    the sync state bitwise."""
    extra = "binary_cache = true\ndevice_cache = true\n"
    cfg_d = _workspace(tmp_path, "dc_delta", extra=extra)
    cfg_d.table_layout = layout
    cfg_d.delta_every_steps = 3
    cfg_d.validate()
    with pytest.raises(_Abort):
        train(cfg_d, log=lambda *_: None, step_hook=_abort_at(8))
    assert latest_step(cfg_d.model_file) == 6

    cfg_s = _workspace(tmp_path, "dc_sync", extra=extra)
    cfg_s.table_layout = layout
    cfg_s.validate()
    train(cfg_s, log=lambda *_: None, step_hook=_sigterm_at(6))
    assert latest_step(cfg_s.model_file) == 6
    _assert_states_equal(_restore_like(cfg_d), _restore_like(cfg_s))


# -- bit-identity: sharded driver ----------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")
def test_async_and_delta_bit_identical_sharded(tmp_path):
    from fast_tffm_tpu.parallel import make_mesh
    from fast_tffm_tpu.training import dist_train

    mesh = make_mesh(2, 4)
    runs = {}
    for name, patch in (
        ("delta", dict(delta_every_steps=3)),
        ("async", dict(async_save=True)),
        ("sync", {}),
    ):
        cfg = _workspace(tmp_path, f"sh_{name}")
        cfg.table_layout = "packed"
        for k, v in patch.items():
            setattr(cfg, k, v)
        cfg.validate()
        hook = _abort_at(8) if name == "delta" else _sigterm_at(6)
        if name == "delta":
            with pytest.raises(_Abort):
                dist_train(cfg, log=lambda *_: None, mesh=mesh, step_hook=hook)
        else:
            dist_train(cfg, log=lambda *_: None, mesh=mesh, step_hook=hook)
        assert latest_step(cfg.model_file) == 6
        runs[name] = _restore_like(cfg)
    _assert_states_equal(runs["delta"], runs["sync"])
    _assert_states_equal(runs["async"], runs["sync"])


# -- stall pin ------------------------------------------------------------


def test_async_stall_under_quarter_of_sync(tmp_path):
    """The loop-side cost of an async boundary (raw snapshot + handoff)
    must be well under the sync save's inline convert+D2H+write on the
    same workload — the pin is < 25%.  Measured on the PACKED layout with
    its real unpack ``saveable``: the issue's motivating shape, where the
    sync path pays the O(table) packed→logical conversion inline and the
    async boundary pays only the raw-state copy (the conversion runs in
    the writer thread).  On CPU (synchronous execution) the copy is a
    real memcpy, so this is a conservative measurement — on an
    accelerator the boundary is dispatch-only."""
    from fast_tffm_tpu.ops.packed_table import unpack_accum_any, unpack_table
    from fast_tffm_tpu.trainer import init_packed_state

    model = FMModel(vocabulary_size=1 << 20, factor_num=8)
    state = init_packed_state(model, jax.random.key(0))
    v, d = model.vocabulary_size, model.row_dim

    def saveable(st):
        return st._replace(
            table=unpack_table(st.table, v, d),
            table_opt=st.table_opt._replace(
                accum=unpack_accum_any(st.table_opt.accum, v, d)
            ),
        )

    sync_ck = AsyncCheckpointer(str(tmp_path / "s.ckpt"), "npz")
    sync_times = []
    for i in range(3):
        t0 = time.perf_counter()
        sync_ck.save_boundary(state, saveable, i, sync=True, emit=False)
        sync_times.append(time.perf_counter() - t0)

    async_ck = AsyncCheckpointer(str(tmp_path / "a.ckpt"), "npz", async_save=True)
    async_times = []
    for i in range(3):
        t0 = time.perf_counter()
        async_ck.save_boundary(state, saveable, i)
        async_times.append(time.perf_counter() - t0)
        async_ck.finalize()  # writer time is OFF the measured loop side

    med = lambda xs: sorted(xs)[len(xs) // 2]
    assert med(async_times) < 0.25 * med(sync_times), (
        f"async boundary {med(async_times) * 1e3:.1f} ms vs "
        f"sync save {med(sync_times) * 1e3:.1f} ms"
    )
    # And the async file is a real, loadable LOGICAL checkpoint.
    r = restore_checkpoint(
        str(tmp_path / "a.ckpt"), init_state(model, jax.random.key(1))
    )
    np.testing.assert_array_equal(
        np.asarray(r.table), np.asarray(saveable(state).table)
    )


# -- crash consistency ----------------------------------------------------


def _small_state(v=128, k=4, key=0, bump=0.0):
    model = FMModel(vocabulary_size=v, factor_num=k)
    st = init_state(model, jax.random.key(key))
    return model, st._replace(table=st.table + bump)


def test_kill_during_save_previous_checkpoint_loadable(tmp_path, monkeypatch):
    """A write that dies mid-save (simulated at the two worst points:
    before the tmp finishes, and as a stale .tmp litter file) leaves the
    PREVIOUS checkpoint fully loadable."""
    model, st_a = _small_state(bump=1.0)
    path = str(tmp_path / "m.ckpt")
    save_checkpoint(path, st_a._replace(step=st_a.step + 1), "npz")

    # (1) async writer dies mid-write: failure counted, base intact.
    import fast_tffm_tpu.checkpoint as ckpt_mod

    def boom(*a, **kw):
        raise OSError("disk gone")

    _, st_b = _small_state(bump=2.0)
    ck = AsyncCheckpointer(path, "npz", async_save=True, log=lambda *_: None)
    monkeypatch.setattr(ckpt_mod, "_write_npz_streaming", boom)
    ck.save_boundary(st_b._replace(step=st_b.step + 2), lambda s: s, 2)
    ck.finalize()
    monkeypatch.undo()
    assert ck.write_failures == 1
    r = restore_checkpoint(path, init_state(model, jax.random.key(7)))
    assert int(r.step) == 1
    np.testing.assert_array_equal(np.asarray(r.table), np.asarray(st_a.table))

    # (2) a SIGKILL between tmp-write and publish = stale .tmp litter:
    # restore ignores it, and the next save replaces it cleanly.
    with open(path + ".tmp", "wb") as f:
        f.write(b"half a checkpoint")
    r = restore_checkpoint(path, init_state(model, jax.random.key(8)))
    assert int(r.step) == 1
    save_checkpoint(path, st_b._replace(step=st_b.step + 3), "npz")
    assert latest_step(path) == 3


def test_failed_write_forces_full_promotion(tmp_path, monkeypatch):
    """A failed delta (or async full) write DROPPED its window's touched
    rows — the boundary already reset the bitmap past them.  Later deltas
    alone could then never reconstruct the state, so the next delta
    boundary must promote itself to a FULL save; the eventual restore is
    complete, not stale."""
    import fast_tffm_tpu.checkpoint as ckpt_mod

    model, st = _small_state(bump=1.0)
    path = str(tmp_path / "m.ckpt")
    ck = AsyncCheckpointer(
        path, "npz", delta_every_steps=1, delta_chain_max=16,
        vocab=128, row_dim=5, log=lambda *_: None,
    )
    ck.save_boundary(st, lambda s: s, 0, sync=True, emit=False)  # signed base

    # Window 1 touches row 3 — and its delta write FAILS.
    real_save_delta = ckpt_mod.save_delta

    def boom(*a, **kw):
        raise OSError("disk gone")

    st1 = st._replace(table=st.table.at[3].add(5.0), step=st.step + 1)
    ck.note_batch(np.array([[3]]))
    monkeypatch.setattr("fast_tffm_tpu.checkpoint_async.save_delta", boom)
    ck.delta_boundary(st1, lambda s: s, 1)
    ck.finalize()
    monkeypatch.setattr("fast_tffm_tpu.checkpoint_async.save_delta", real_save_delta)
    assert ck.write_failures == 1
    # The on-disk base+chain is exactly as before the failure.
    r = restore_checkpoint(path, init_state(model, jax.random.key(7)))
    np.testing.assert_array_equal(np.asarray(r.table), np.asarray(st.table))

    # Window 2 touches only row 9; the boundary must promote to FULL
    # (a chain-valid delta here would silently lose row 3's update).
    st2 = st1._replace(table=st1.table.at[9].add(2.0), step=st1.step + 1)
    ck.note_batch(np.array([[9]]))
    ck.delta_boundary(st2, lambda s: s, 2)
    ck.finalize()
    assert ck.full_saves + ck.sync_saves == 2 and ck.delta_saves == 0
    assert delta_paths(path) == []
    r = restore_checkpoint(path, init_state(model, jax.random.key(8)))
    _assert_states_equal(r, st2)


def test_delta_paths_glob_metacharacters(tmp_path):
    """A model_file whose path contains glob metacharacters ('run[1]/')
    must still find its own delta files — an unescaped glob silently
    returned [] and restored the stale base."""
    d = tmp_path / "run[1]"
    d.mkdir()
    model, st = _small_state(bump=0.5)
    path = str(d / "m.ckpt")
    save_checkpoint(path, st, "npz")
    save_delta(
        path, 1,
        idx=np.array([2]), table_rows=np.full((1, 5), 7.0, np.float32),
        accum_rows=np.full((1, 5), 7.0, np.float32),
        dense_leaves=[], dense_accum_leaves=[],
        step=np.int32(5), parent_sig=checkpoint_save_id(path),
    )
    assert len(delta_paths(path)) == 1
    r = restore_checkpoint(path, init_state(model, jax.random.key(1)))
    assert int(r.step) == 5
    np.testing.assert_array_equal(np.asarray(r.table)[2], np.full((5,), 7.0))


def test_truncated_npz_restore_fails_naming_file(tmp_path):
    model, st = _small_state()
    path = str(tmp_path / "m.ckpt")
    save_checkpoint(path, st, "npz")
    raw = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(raw[: len(raw) // 2])
    with pytest.raises(ValueError, match="m.ckpt"):
        restore_checkpoint(path, init_state(model, jax.random.key(1)))


def test_half_written_delta_fails_naming_file(tmp_path):
    model, st = _small_state()
    path = str(tmp_path / "m.ckpt")
    save_checkpoint(path, st, "npz")
    with open(path + ".delta-0001.npz", "wb") as f:
        f.write(b"not an npz at all")
    with pytest.raises(ValueError, match="delta-0001"):
        restore_checkpoint(path, init_state(model, jax.random.key(1)))
    # latest_step degrades to None-safe behavior, never garbage.
    assert latest_step(path) is None or isinstance(latest_step(path), int)


def test_broken_chain_fails_loudly(tmp_path):
    model, st = _small_state()
    path = str(tmp_path / "m.ckpt")
    save_checkpoint(path, st, "npz")
    save_delta(
        path, 1,
        idx=np.array([1]), table_rows=np.ones((1, 5), np.float32),
        accum_rows=np.ones((1, 5), np.float32),
        dense_leaves=[], dense_accum_leaves=[],
        step=np.int32(9), parent_sig="deadbeef" * 4,
    )
    with pytest.raises(ValueError, match="does not chain"):
        restore_checkpoint(path, init_state(model, jax.random.key(1)))


def test_full_save_resets_stale_chain(tmp_path):
    """A full save unlinks the previous chain BEFORE publishing — deltas
    from an older base can never be replayed onto a newer one."""
    model, st = _small_state()
    path = str(tmp_path / "m.ckpt")
    save_checkpoint(path, st, "npz")
    save_delta(
        path, 1,
        idx=np.array([2]), table_rows=np.full((1, 5), 7.0, np.float32),
        accum_rows=np.full((1, 5), 7.0, np.float32),
        dense_leaves=[], dense_accum_leaves=[],
        step=np.int32(5), parent_sig=checkpoint_save_id(path),
    )
    assert len(delta_paths(path)) == 1
    sig_before = checkpoint_signature(path)
    save_checkpoint(path, st._replace(step=st.step + 9), "npz")
    assert delta_paths(path) == []
    assert latest_step(path) == 9
    assert checkpoint_signature(path) != sig_before


def test_chunked_restore_matches_whole_file(tmp_path):
    """Bounded-slice device placement (the restore satellite) lands the
    exact bytes np.load would."""
    model, st = _small_state(v=333, k=7, bump=0.25)
    path = str(tmp_path / "m.ckpt")
    save_checkpoint(path, st, "npz", chunk_bytes=512)
    r = restore_checkpoint(
        path, init_state(model, jax.random.key(3)), chunk_bytes=512
    )
    with np.load(path) as z:
        np.testing.assert_array_equal(np.asarray(r.table), z["table"])
        np.testing.assert_array_equal(np.asarray(r.table_opt.accum), z["table_accum"])


def test_delta_config_validation():
    with pytest.raises(ValueError, match="checkpoint_format = npz"):
        Config(delta_every_steps=4, checkpoint_format="orbax").validate()
    with pytest.raises(ValueError, match="delta_chain_max"):
        Config(delta_chain_max=0).validate()
    with pytest.raises(ValueError, match="checkpoint_chunk_mb"):
        Config(checkpoint_chunk_mb=0).validate()


def test_compilation_cache_enable_and_compile_record_cache_hits(tmp_path, monkeypatch):
    """[Telemetry] compilation_cache_dir: with no JAX_COMPILATION_CACHE_DIR
    in the environment the key points jax's persistent cache at the dir
    (the full precedence is pinned in tests/test_chip_smoke.py), and
    kind=compile records carry the cache_hits count distinctly (0 on a
    cold compile)."""
    from fast_tffm_tpu import telemetry

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")  # conftest sets it
    cc = str(tmp_path / "cc")
    try:
        assert telemetry.enable_compilation_cache(cc) == cc
        assert jax.config.jax_compilation_cache_dir == cc
        mon = telemetry.RunMonitor(str(tmp_path / "m.jsonl"))
        import jax.numpy as jnp

        jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(13))
        mon.on_dispatch(1, warmup=True)
        mon.close()
        recs = [json.loads(l) for l in open(str(tmp_path / "m.jsonl"))]
        comp = [r for r in recs if r["kind"] == "compile"]
        assert comp, "expected the fresh program to fire the compile sentinel"
        assert all("cache_hits" in r for r in comp)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_report_renders_ckpt_and_gates_stall_share(tmp_path):
    """tools/report.py: kind=ckpt records render a Checkpointing section
    with the stall share next to input-vs-compute, and --compare --strict
    flags a run whose ckpt stall share regressed."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "report_tool", os.path.join(repo, "tools", "report.py")
    )
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    def synth(path, stall_ms):
        recs = []
        for i in range(4):
            recs.append(
                dict(
                    run_id="r", schema_version=1, kind="train", step=i * 10,
                    t=float(i), ts=0.0, epoch=0, loss=0.5,
                    examples_per_sec=1000.0, examples_per_sec_per_chip=1000.0,
                )
            )
        recs.append(
            dict(
                run_id="r", schema_version=1, kind="ckpt", step=40, t=4.0,
                ts=0.0, mode="sync", snapshot_ms=0.0, convert_ms=1.0,
                d2h_ms=1.0, write_ms=1.0, bytes=1 << 20, rows_written=100,
                train_stall_ms=stall_ms,
            )
        )
        with open(path, "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        return path

    base = synth(str(tmp_path / "base.jsonl"), stall_ms=10.0)
    run = synth(str(tmp_path / "run.jsonl"), stall_ms=2500.0)
    s_run = report.summarize(report.load_run(run))
    assert s_run["ckpt_saves"] == 1
    assert s_run["ckpt_stall_share"] is not None and s_run["ckpt_stall_share"] > 0.1
    text = report.render(s_run)
    assert "## Checkpointing" in text
    # Strict compare: the stalled run regresses vs the quiet base...
    _, regressions = report.compare(
        s_run, report.summarize(report.load_run(base)), threshold=0.15, strict=True
    )
    assert any("ckpt stall share" in r for r in regressions)
    # ...but not under the default (non-strict) gate.
    _, regressions = report.compare(
        s_run, report.summarize(report.load_run(base)), threshold=0.15, strict=False
    )
    assert not any("ckpt" in r for r in regressions)


def test_delta_chain_max_promotes_to_full(tmp_path):
    """The boundary after chain_max deltas writes a FULL save and resets
    the chain (bounds restore replay length)."""
    model, st = _small_state()
    path = str(tmp_path / "m.ckpt")
    ck = AsyncCheckpointer(
        path, "npz", delta_every_steps=1, delta_chain_max=2,
        vocab=128, row_dim=5, log=lambda *_: None,
    )
    ident = lambda s: s
    ck.save_boundary(st, ident, 0, sync=True, emit=False)
    import jax.numpy as jnp

    ids = jnp.asarray(np.array([[1, 2, 3]], np.int32))

    class B:
        pass

    b = B()
    b.ids = ids
    for step in (1, 2, 3):
        ck.note_batch(b)
        ck.delta_boundary(st._replace(step=st.step + step), ident, step)
        ck.finalize()
    # Boundaries 1 and 2 wrote deltas; boundary 3 hit the cap -> full
    # save, chain reset.
    assert delta_paths(path) == []
    assert ck.delta_saves == 2
    assert latest_step(path) == 3
