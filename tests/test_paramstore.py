"""Tiered host/device parameter store (ISSUE 12 tentpole).

Pins, per the acceptance criteria:
  * tiered-vs-resident BIT-IDENTITY at overlapping vocab — logged loss
    sequences, validation AUC, and the full reconstructed logical state
    (store + hot tier) against the resident checkpoint, on the streamed
    (K=1 and fused K>1) path and against the device-cached path;
  * exact-position resume mid-run (prefix/suffix of the uninterrupted
    run's loss sequence) with residency restored from the checkpoint;
  * kill-during-eviction-writeback leaves the chain loadable with no
    lost or stale rows (the new FaultPlan kind, appended LAST so seeded
    schedules stay byte-identical);
  * a vocab past the 2^28 device wall (2^30) trains on one chip
    (sparse-file lazy store);
  * device-side dedup-before-gather (dedup_gather_rows) losses
    bit-identical, with a LOUD error when a batch exceeds the cap;
  * kind=tiering telemetry + report section + --compare --strict gates.
"""

import json
import os
import shutil
import signal
import subprocess
import sys

import numpy as np
import pytest

from fast_tffm_tpu.checkpoint import restore_checkpoint
from fast_tffm_tpu.config import Config, build_model
from fast_tffm_tpu.paramstore import ColdStore, hashed_uniform_rows
from fast_tffm_tpu.paramstore.residency import ResidencyMap, choose_hot_ids
from fast_tffm_tpu.resilience import FAULT_KINDS, FaultPlan
from fast_tffm_tpu.trainer import init_state
from fast_tffm_tpu.training import train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 300


def _write_dataset(path, n=300, vocab=VOCAB, nnz=6, seed=0, hot_bias=True):
    """Synthetic libsvm rows with a skewed id head (so a small hot tier
    actually absorbs traffic) and reproducible labels."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n):
            if hot_bias:
                head = rng.integers(0, 20, size=nnz // 2)
                tail = rng.integers(0, vocab, size=nnz - nnz // 2)
                ids = np.unique(np.concatenate([head, tail]))[:nnz]
                while ids.size < nnz:
                    ids = np.unique(
                        np.concatenate([ids, rng.integers(0, vocab, size=nnz)])
                    )[:nnz]
            else:
                ids = rng.choice(vocab, size=nnz, replace=False)
            vals = np.round(np.abs(rng.normal(size=nnz)) + 0.1, 4)
            y = int(rng.random() < 0.5)
            f.write(f"{y} " + " ".join(f"{i}:{v}" for i, v in zip(ids, vals)) + "\n")


@pytest.fixture
def ds(tmp_path):
    p = tmp_path / "train.libsvm"
    _write_dataset(str(p))
    v = tmp_path / "valid.libsvm"
    _write_dataset(str(v), n=100, seed=9)
    return tmp_path


def _cfg(tmp_path, name, **kw):
    c = Config()
    c.model = "fm"
    c.factor_num = 4
    c.vocabulary_size = VOCAB
    c.train_files = (str(tmp_path / "train.libsvm"),)
    c.epoch_num = 2
    c.batch_size = 32
    c.learning_rate = 0.1
    c.log_every = 1
    c.save_every_epochs = 1
    c.model_file = str(tmp_path / f"{name}.ckpt")
    for k, v in kw.items():
        setattr(c, k, v)
    return c.validate()


def _losses(logs):
    return [float(l.split("loss ")[1].split()[0]) for l in logs if "loss " in l]


def _aucs(logs):
    return [l for l in logs if "validation auc" in l]


def _run(cfg, **kw):
    logs = []
    state = train(cfg, log=lambda *a: logs.append(" ".join(map(str, a))), **kw)
    return state, logs


def _tiered_logical(cfg):
    """Reconstruct the FULL logical (table, accum) of a finished tiered
    run: cold store (final sync save applied pending) + the npz's hot
    tier + its pending members (idempotent overlay)."""
    z = np.load(cfg.model_file)
    store = ColdStore.open(cfg.paramstore_dir or cfg.model_file + ".store")
    t, a = store.read_rows(np.arange(cfg.vocabulary_size))
    ci = np.asarray(z["tier_cold_idx"], np.int64)
    if ci.size:
        t[ci] = z["tier_cold_rows"]
        a[ci] = z["tier_cold_accum"]
    hi = np.asarray(z["tier_hot_ids"], np.int64)
    t[hi] = z["table"]
    a[hi] = z["table_accum"]
    return t, a


# -- cold store -----------------------------------------------------------


def test_store_lazy_init_deterministic_and_persistent(tmp_path):
    p = str(tmp_path / "store")
    s = ColdStore.create(
        p, vocab=1000, row_dim=5, accum_width=5, seed=3, init_range=0.02,
        init_accum=0.1,
    )
    ids = np.array([0, 7, 999])
    t1, a1 = s.read_rows(ids)
    assert np.all(t1[:, 0] == 0.0)  # bias column
    assert np.all(np.abs(t1[:, 1:]) < 0.02) and np.any(t1[:, 1:] != 0.0)
    assert np.all(a1 == np.float32(0.1))
    # Lazy reads are pure: same rows again, and across a reopen.
    t2, _ = s.read_rows(ids)
    assert np.array_equal(t1, t2)
    s.write_rows(np.array([7]), np.full((1, 5), 2.0), np.full((1, 5), 3.0))
    s.flush()
    s2 = ColdStore.open(p)
    assert s2.fingerprint == s.fingerprint
    t3, a3 = s2.read_rows(ids)
    assert np.all(t3[1] == 2.0) and np.all(a3[1] == 3.0)
    assert np.array_equal(t3[0], t1[0])  # unwritten rows still lazy-init
    with pytest.raises(ValueError, match="out of range"):
        s2.read_rows(np.array([1000]))


def test_hashed_uniform_rows_shape_and_determinism():
    a = hashed_uniform_rows(np.array([5, 6]), 4, seed=1, init_range=0.5)
    b = hashed_uniform_rows(np.array([5, 6]), 4, seed=1, init_range=0.5)
    c = hashed_uniform_rows(np.array([5, 6]), 4, seed=2, init_range=0.5)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(a[:, 0] == 0.0) and np.all(np.abs(a) < 0.5)


# -- residency ------------------------------------------------------------


def test_residency_resolve_remaps_and_dedups():
    m = ResidencyMap(np.array([10, 3, 50]))  # slots by SORTED rank: 3,10,50
    ids = [np.array([[3, 99, 10], [99, 7, 3]])]
    res = m.resolve(ids, miss_capacity=8)
    assert list(res.miss_ids) == [7, 99]
    h = m.hot_rows
    expect = np.array([[0, h + 1, 1], [h + 1, h + 0, 0]])
    assert np.array_equal(res.remapped[0], expect)
    assert res.hit_slots == 3 and res.total_slots == 6 and res.miss_ids.size == 2
    with pytest.raises(ValueError, match="miss_rows"):
        m.resolve(ids, miss_capacity=1)


def test_choose_hot_ids_policies(tmp_path):
    assert list(choose_hot_ids("first", 3, 100)) == [0, 1, 2]
    # sample: exact top-K by (count desc, id asc) — deterministic ties.
    batches = [np.array([5, 5, 9, 9, 2, 7])]
    top = choose_hot_ids("sample", 2, 100, sample_batches=iter(batches))
    assert sorted(top) == [5, 9]
    f = tmp_path / "hot.txt"
    f.write_text("42\n42\n17\n3\n")
    assert list(choose_hot_ids(f"file:{f}", 2, 100)) == [42, 17]
    with pytest.raises(ValueError, match="residency"):
        choose_hot_ids("nope", 2, 100)


# -- bit-identity ---------------------------------------------------------


def test_tiered_bit_identical_to_resident_streamed(ds):
    res_cfg = _cfg(ds, "resident", validation_files=(str(ds / "valid.libsvm"),))
    res_state, res_logs = _run(res_cfg)
    tier_cfg = _cfg(
        ds, "tiered", validation_files=(str(ds / "valid.libsvm"),),
        paramstore=True, paramstore_hot_rows=48, delta_every_steps=3,
    )
    _state, tier_logs = _run(tier_cfg)
    assert _losses(res_logs) == _losses(tier_logs)
    assert _aucs(res_logs) == _aucs(tier_logs)
    # The reconstructed logical state matches the resident checkpoint
    # BIT FOR BIT — every row's latest value is in exactly one tier.
    ref = restore_checkpoint(
        res_cfg.model_file,
        init_state(build_model(res_cfg), __import__("jax").random.key(4)),
    )
    t, a = _tiered_logical(tier_cfg)
    assert np.array_equal(t, np.asarray(ref.table))
    assert np.array_equal(a, np.asarray(ref.table_opt.accum))


def test_tiered_bit_identical_fused_and_device_cache(ds):
    # steps_per_call=2 exercises the superbatch wire + scan; the
    # device-cache run pins the third driver path to the same sequence.
    kw = dict(steps_per_call=2, binary_cache=True)
    _s, res_logs = _run(_cfg(ds, "res_k2", **kw))
    _s, cache_logs = _run(_cfg(ds, "cache_k2", device_cache=True, **kw))
    _s, tier_logs = _run(
        _cfg(ds, "tier_k2", paramstore=True, paramstore_hot_rows=48,
             delta_every_steps=4, **kw)
    )
    assert _losses(res_logs) == _losses(tier_logs)
    assert _losses(cache_logs) == _losses(tier_logs)


def test_tiered_row_accumulator(ds):
    kw = dict(adagrad_accumulator="row")
    _s, res_logs = _run(_cfg(ds, "res_row", **kw))
    _s, tier_logs = _run(
        _cfg(ds, "tier_row", paramstore=True, paramstore_hot_rows=32, **kw)
    )
    assert _losses(res_logs) == _losses(tier_logs)


def test_a_long_tiered_run_holds_no_more_once_every_miss_is_pending(ds):
    """Once every missed row is pending, a step leaves nothing behind: the
    live device arrays, their bytes, the threads and the overlay's pool
    stay where they were epochs before."""
    import threading

    import jax

    epochs = 12
    seen = []

    def hook(step):
        srv = sys._getframe(1).f_locals["paramstore"]
        live = jax.live_arrays()
        seen.append((
            len(live), sum(a.nbytes for a in live), threading.active_count(),
            srv.pending_rows, srv._overlay._pool.shape[0],
        ))

    cfg = _cfg(
        ds, "t_long", paramstore=True, paramstore_hot_rows=48,
        epoch_num=epochs, save_every_epochs=0, queue_size=2,
    )
    _run(cfg, step_hook=hook)
    n = len(seen) // epochs
    early, late = seen[3 * n : 4 * n], seen[(epochs - 1) * n :]
    assert {s[3:] for s in early} == {s[3:] for s in late} and len({s[3] for s in late}) == 1
    # The prefetch queue holds up to its depth of batches, whenever a hook
    # looks: the late epoch may hold as much as the early one, no more.
    for k in range(3):
        assert max(s[k] for s in late) <= max(s[k] for s in early), (k, early, late)


def test_tiered_coherency_restage_stays_exact(ds, tmp_path):
    # A hot set that misses EVERYTHING (file policy naming never-seen
    # ids) forces every repeated id through the staging path — with the
    # prefetch queue running ahead, consecutive-batch repeats go stale
    # and must restage.  Losses must still match the resident run.
    hot = tmp_path / "hot_ids.txt"
    hot.write_text("\n".join(str(i) for i in range(290, 299)))
    tier_cfg = _cfg(
        ds, "tier_cold", paramstore=True, paramstore_hot_rows=8,
        paramstore_residency=f"file:{hot}", metrics_path=str(ds / "m.jsonl"),
    )
    _s, tier_logs = _run(tier_cfg)
    _s, res_logs = _run(_cfg(ds, "res_cold"))
    assert _losses(res_logs) == _losses(tier_logs)
    recs = [json.loads(l) for l in open(ds / "m.jsonl") if l.strip()]
    tier = [r for r in recs if r["kind"] == "tiering"]
    assert tier, "no kind=tiering records"
    assert sum(r["restages"] for r in tier) > 0, (
        "cold residency + queue-ahead resolution should have forced "
        "coherency restages"
    )
    for r in tier:
        assert r["hit_rate"] <= 0.05  # the hot set really is cold


# -- resume / crash-consistency -------------------------------------------


def test_tiered_resume_exact(ds):
    cfg = _cfg(
        ds, "t_resume", paramstore=True, paramstore_hot_rows=48,
        delta_every_steps=3,
    )
    def hook(step):
        if step >= 10:
            os.kill(os.getpid(), signal.SIGTERM)

    _s, part1 = _run(cfg, step_hook=hook)
    _s, part2 = _run(cfg, resume=True)
    _s, ref = _run(
        _cfg(ds, "t_ref", paramstore=True, paramstore_hot_rows=48,
             delta_every_steps=3)
    )
    l1, l2, lr = _losses(part1), _losses(part2), _losses(ref)
    # The SIGTERM step's own window is saved but never logged; everything
    # around it must match the uninterrupted run exactly.
    assert l1 == lr[: len(l1)]
    assert l2 == lr[len(l1) + 1 :]
    assert any("resumed tiered run" in l for l in part2)


def test_tiered_store_replaced_refused(ds):
    cfg = _cfg(ds, "t_swap", paramstore=True, paramstore_hot_rows=32)
    _run(cfg)
    shutil.rmtree(cfg.model_file + ".store")
    ColdStore.create(
        cfg.model_file + ".store", vocab=VOCAB, row_dim=5, accum_width=5,
        seed=0, init_range=0.01, init_accum=0.1,
    )
    with pytest.raises(ValueError, match="store was replaced"):
        train(cfg, resume=True, log=lambda *a: None)


def test_resident_restore_refuses_tiered_checkpoint(ds):
    cfg = _cfg(ds, "t_guard", paramstore=True, paramstore_hot_rows=32)
    _run(cfg)
    import jax

    with pytest.raises(ValueError, match="TIERED"):
        restore_checkpoint(
            cfg.model_file, init_state(build_model(cfg), jax.random.key(0))
        )


_KILL_CHILD = r"""
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
from fast_tffm_tpu.config import Config
from fast_tffm_tpu.resilience import FaultPlan, install_faults
from fast_tffm_tpu.training import train
import json
cfg = Config(**json.loads({cfg_json!r}))
cfg.train_files = tuple(cfg.train_files)
cfg.validate()
install_faults(FaultPlan.parse({plan!r}))
train(cfg, log=print)
"""


# Apply ordinals under this test config (9 batches/epoch, delta_every=3,
# save_every_epochs=1): #2 = a mid-epoch DELTA boundary's apply; #4 = the
# apply right after the first epoch-end FULL publish — the window where
# the store's applied_sig names a link of the chain that publish just
# unlinked (recoverable via the base's tier_prev_sigs lineage).
@pytest.mark.parametrize("plan", ["kill_writeback@2", "kill_writeback@4"])
def test_kill_during_writeback_apply_chain_loadable(ds, plan):
    """The satellite pin: SIGKILL mid-apply (cold-store pages dirty, the
    boundary unstamped) must leave base+chain loadable; the resumed run
    finishes with the exact state of an uninterrupted one — no lost, no
    stale rows."""
    cfg = _cfg(
        ds, "t_kill", paramstore=True, paramstore_hot_rows=48,
        delta_every_steps=3,
    )
    cfg_json = json.dumps(
        {
            k: (list(v) if isinstance(v, tuple) else v)
            for k, v in cfg.__dict__.items()
        }
    )
    r = subprocess.run(
        [
            sys.executable, "-c",
            _KILL_CHILD.format(repo=REPO, cfg_json=cfg_json, plan=plan),
        ],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stdout, r.stderr)
    # Chain loadable + resume-to-completion exact vs uninterrupted.
    _s, part2 = _run(cfg, resume=True)
    _s, ref = _run(
        _cfg(ds, "t_kill_ref", paramstore=True, paramstore_hot_rows=48,
             delta_every_steps=3)
    )
    lr = _losses(ref)
    l2 = _losses(part2)
    assert l2 == lr[len(lr) - len(l2):]
    t, a = _tiered_logical(cfg)
    t_ref, a_ref = _tiered_logical(
        _cfg(ds, "t_kill_ref", paramstore=True, paramstore_hot_rows=48,
             delta_every_steps=3)
    )
    assert np.array_equal(t, t_ref)
    assert np.array_equal(a, a_ref)


def test_faultplan_kill_writeback_appended_last():
    assert FAULT_KINDS[-1] == "kill_writeback"
    plan = FaultPlan.parse("kill_writeback@2,kill@5")
    assert {e["kind"] for e in plan.events} == {"kill", "kill_writeback"}
    # Seeded schedules that never name the new kind are byte-identical
    # to what the pre-ISSUE-12 grammar drew (appended LAST).
    old = FaultPlan.parse("random:kill=2,io_error=1,torn_delta=1", seed=5)
    assert "kill_writeback" not in old.to_json()
    again = FaultPlan.parse("random:kill=2,io_error=1,torn_delta=1", seed=5)
    assert old.to_json() == again.to_json()


# -- beyond-HBM -----------------------------------------------------------


def test_beyond_hbm_vocab_trains(tmp_path):
    """2^30 logical rows — 4x past the measured 2^28 single-chip wall —
    trains on one chip: the cold store is a sparse lazy file, the device
    holds only hot + staging rows."""
    big = tmp_path / "big.libsvm"
    rng = np.random.default_rng(1)
    with open(big, "w") as f:
        for _ in range(64):
            ids = rng.integers(0, 1 << 30, size=4)
            f.write("1 " + " ".join(f"{i}:1.0" for i in ids) + "\n")
    c = Config()
    c.model = "fm"
    c.factor_num = 4
    c.vocabulary_size = 1 << 30
    c.train_files = (str(big),)
    c.epoch_num = 1
    c.batch_size = 16
    c.log_every = 1
    c.learning_rate = 0.1
    c.model_file = str(tmp_path / "big.ckpt")
    c.paramstore = True
    c.paramstore_hot_rows = 32
    c.paramstore_materialize = "auto"  # 2^30 >> bound -> lazy
    c.delta_every_steps = 2
    c.adagrad_accumulator = "row"
    c.validate()
    _s, logs = _run(c)
    losses = _losses(logs)
    assert len(losses) == 4 and all(np.isfinite(losses))
    # The store files are SPARSE: apparent size is the full table, disk
    # blocks are only the touched pages.
    table = os.path.join(c.model_file + ".store", "table.dat")
    st = os.stat(table)
    assert st.st_size == (1 << 30) * 5 * 4
    assert st.st_blocks * 512 < 64 << 20, "store file is not sparse"


# -- dedup-before-gather ---------------------------------------------------


def test_dedup_gather_bit_identical(ds):
    _s, ref = _run(_cfg(ds, "dd_ref"))
    _s, ded = _run(_cfg(ds, "dd_on", dedup_gather_rows=256))
    assert _losses(ref) == _losses(ded)
    _s, ded2 = _run(
        _cfg(ds, "dd_k2", dedup_gather_rows=256, steps_per_call=2,
             binary_cache=True)
    )
    _s, ref2 = _run(_cfg(ds, "dd_ref2", steps_per_call=2, binary_cache=True))
    assert _losses(ref2) == _losses(ded2)


def test_dedup_gather_overflow_is_loud(ds):
    from fast_tffm_tpu.utils.prefetch import PrefetchError

    with pytest.raises((ValueError, PrefetchError), match="dedup_gather_rows"):
        train(_cfg(ds, "dd_tiny", dedup_gather_rows=3), log=lambda *a: None)


# -- telemetry / report / config ------------------------------------------


def test_tiering_telemetry_and_report_section(ds):
    import importlib.util

    cfg = _cfg(
        ds, "t_tel", paramstore=True, paramstore_hot_rows=48,
        delta_every_steps=3, metrics_path=str(ds / "tel.jsonl"),
    )
    _run(cfg)
    recs = [json.loads(l) for l in open(ds / "tel.jsonl") if l.strip()]
    tier = [r for r in recs if r["kind"] == "tiering"]
    assert tier
    from fast_tffm_tpu.telemetry import SCHEMAS

    for r in tier:
        missing = [k for k in SCHEMAS["tiering"] if k not in r]
        assert not missing, missing
        assert 0.0 <= r["hit_rate"] <= 1.0
    # Steady-state recompiles stay pinned at zero on the tiered path.
    steady = [
        r for r in recs if r["kind"] == "compile" and not r.get("warmup")
    ]
    assert not steady, steady
    spec = importlib.util.spec_from_file_location(
        "report_tool", os.path.join(REPO, "tools", "report.py")
    )
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    s = rep.summarize(recs)
    assert s["tiering_windows"] == len(tier)
    assert 0.0 < s["tier_hit_rate_mean"] <= 1.0
    text = rep.render(s)
    assert "Parameter store (tiered)" in text
    # --compare --strict gates: a degraded hit rate (and fatter miss
    # bytes) past the threshold regress.
    worse = dict(s, tier_hit_rate_mean=s["tier_hit_rate_mean"] * 0.5,
                 tier_miss_bytes_per_step=(s["tier_miss_bytes_per_step"] or 1) * 3)
    _md, regressions = rep.compare(worse, s, threshold=0.15, strict=True)
    joined = "\n".join(regressions)
    assert "hit rate regressed" in joined
    assert "miss bytes/step regressed" in joined
    _md, ok = rep.compare(s, s, threshold=0.15, strict=True)
    assert not [r for r in ok if "paramstore" in r]


def test_paramstore_config_rejections():
    def mk(**kw):
        c = Config()
        c.train_files = ("x.libsvm",)
        for k, v in kw.items():
            setattr(c, k, v)
        return c

    with pytest.raises(ValueError, match="table_layout = rows"):
        mk(paramstore=True, table_layout="packed").validate()
    with pytest.raises(ValueError, match="device_cache"):
        mk(paramstore=True, device_cache=True).validate()
    with pytest.raises(ValueError, match="async_save"):
        mk(paramstore=True, async_save=True).validate()
    with pytest.raises(ValueError, match="npz"):
        mk(paramstore=True, checkpoint_format="orbax").validate()
    with pytest.raises(ValueError, match="rollback"):
        mk(paramstore=True, on_nan="rollback").validate()
    with pytest.raises(ValueError, match="redundant"):
        mk(paramstore=True, dedup_gather_rows=8).validate()
    with pytest.raises(ValueError, match="local-train only"):
        from fast_tffm_tpu.training import dist_train

        dist_train(mk(paramstore=True).validate(), log=lambda *a: None)
    with pytest.raises(ValueError, match="rows"):
        mk(dedup_gather_rows=8, table_layout="packed").validate()
    mk(paramstore=True).validate()  # the plain enablement is legal


# -- the host path's arrays (resolve, overlay, shipped rows) --------------


class _RowModel:
    """What the server asks of a model: its row width, whether it reads
    fields."""

    uses_fields = False

    def __init__(self, row_dim):
        self.row_dim = row_dim


def _server(tmp_path, vocab=256, d=5, hot=np.arange(0, 256, 7), miss_rows=13):
    from fast_tffm_tpu.paramstore import TieredParamServer

    store = ColdStore.create(
        str(tmp_path / "store"), vocab=vocab, row_dim=d, accum_width=d,
        seed=1, init_range=0.5, init_accum=0.1,
    )
    return TieredParamServer(store, hot, miss_rows, _RowModel(d), init_accum=0.1)


def _stage_and_fetch(srv, ids, rows):
    """What a step does with its misses: ``rows`` [n, D + A] in the staging
    slots of a state, fetched for the writeback after the next dispatch."""
    import jax.numpy as jnp

    from fast_tffm_tpu.optim import AdagradState
    from fast_tffm_tpu.trainer import TrainState

    d, h = srv.row_dim, srv.hot_rows
    table = np.zeros((srv.capacity, d), np.float32)
    accum = np.full((srv.capacity, srv.accum_width), 0.1, np.float32)
    table[h : h + ids.size], accum[h : h + ids.size] = rows[:, :d], rows[:, d:]
    state = TrainState(
        table=jnp.asarray(table), table_opt=AdagradState(jnp.asarray(accum)),
        dense={}, dense_opt=AdagradState({}), step=jnp.asarray(np.int32(0)),
    )
    srv._build_jits()
    srv._fetch_staged(state, ids)


def _searchsorted_lookup(hot, ids):
    """The lookup the dense map replaced: a binary search of the sorted hot
    ids."""
    pos = np.searchsorted(hot, ids)
    pos_c = np.minimum(pos, max(0, hot.size - 1))
    hit = (pos < hot.size) & (hot[pos_c] == ids) if hot.size else np.zeros(ids.shape, bool)
    return hit, pos_c


def _searchsorted_resolve(hot, ids_seq):
    flat = np.concatenate([a.reshape(-1) for a in ids_seq])
    hit_all, _ = _searchsorted_lookup(hot, flat)
    miss = np.unique(flat[~hit_all])
    out = []
    for a in ids_seq:
        hit, slot = _searchsorted_lookup(hot, a.reshape(-1))
        rank = np.minimum(np.searchsorted(miss, a.reshape(-1)), max(0, miss.size - 1))
        out.append(np.where(hit, slot, hot.size + rank).reshape(a.shape))
    return out, miss, int(hit_all.sum())


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_id_map_resolves_as_the_search_of_the_hot_set_did(seed):
    rng = np.random.default_rng(seed)
    vocab = int(rng.integers(50, 5000))
    hot = rng.choice(vocab, size=int(rng.integers(0, vocab // 2)), replace=False)
    m = ResidencyMap(hot, vocab)
    ids = rng.integers(0, vocab, size=(int(rng.integers(1, 4)), 17, 5))  # repeats, hits and misses
    hit, slot = m.lookup(ids.reshape(-1))
    want_hit, want_slot = _searchsorted_lookup(np.sort(hot), ids.reshape(-1))
    assert np.array_equal(hit, want_hit) and np.array_equal(slot[hit], want_slot[hit])
    assert ((0 <= slot) & (slot < max(1, hot.size))).all()
    res = m.resolve(list(ids), miss_capacity=ids.size)
    remapped, miss, hits = _searchsorted_resolve(np.sort(hot), list(ids))
    assert np.array_equal(res.miss_ids, miss) and res.hit_slots == hits
    assert all(np.array_equal(a, b) and a.dtype == np.int32 for a, b in zip(res.remapped, remapped))
    # A map sized by its hot ids alone: an id past its end misses.
    small = ResidencyMap(hot)
    assert np.array_equal(small.lookup(np.array([vocab + 5]))[0], [False])


def test_the_sample_policy_counts_densely_as_it_sorted():
    """Top-K by (count desc, id asc), whichever way the sample is counted:
    a dense count (a vocabulary within four times the sample) and a sort."""
    rng = np.random.default_rng(7)
    flat = rng.zipf(1.3, size=4000) % 900
    for vocab in (900, 100_000):
        for k in (1, 17, 250, 899):  # 899: more than the sample's distinct ids, the smallest unseen fill
            uniq, cnt = np.unique(flat, return_counts=True)
            want = uniq[np.lexsort((uniq, -cnt))][:k]
            fill = np.setdiff1d(np.arange(min(vocab, 2 * k)), want)[: k - want.size]
            got = choose_hot_ids("sample", k, vocab, sample_batches=iter([flat]))
            assert np.array_equal(got, np.sort(np.concatenate([want, fill])))


def test_the_shipped_rows_round_trip_bit_for_bit(tmp_path):
    """The converter's staged rows reach the device as the overlay holds
    them, bit for bit: negative zero, subnormals, an odd staging capacity,
    a single batch and a superbatch of two (``k > 0``)."""
    import jax

    from fast_tffm_tpu.data.libsvm import ParsedBatch
    from fast_tffm_tpu.data.wire import make_spec
    from fast_tffm_tpu.paramstore import TieredConverter

    srv = _server(tmp_path)
    d = srv.row_dim
    special = np.array([-0.0, 1e-45, -1.4e-40, 3.4e38, 1.17e-38], np.float32)
    odd = np.array([3, 5, 200, 201, 255], np.int64)  # none of them hot
    rows = np.concatenate([np.tile(special, (5, 2))[:, :d], np.tile(special[::-1], (5, 2))[:, :d]], axis=1)
    _stage_and_fetch(srv, odd, rows)
    srv.flush_writeback(None)
    spec = make_spec(srv.capacity, 4, with_vals=True, with_fields=False, with_weights=True)
    conv = TieredConverter(srv, spec)

    def parsed(ids):
        ids = np.asarray(ids, np.int32).reshape(2, 4)
        return ParsedBatch(
            labels=np.array([0, 1], np.float32), ids=ids, vals=np.ones(ids.shape, np.float32),
            fields=np.zeros(ids.shape, np.int32), nnz=np.full(2, 4, np.int32),
        )

    a, b = parsed([3, 7, 5, 200, 14, 201, 255, 3]), parsed([255, 5, 0, 1, 2, 9, 3, 10])
    for given, weights in ((a, np.ones(2, np.float32)), ([a, b], [np.ones(2, np.float32)] * 2)):
        tb = conv(given, weights)
        n = tb.miss_ids.size
        s = srv.staged_rows(n)
        staged = np.asarray(tb.staged.result()[0]).reshape(2 * d, -1).T
        mt, ma = staged[:, :d], staged[:, d:]
        assert staged.shape == (s, 2 * d) and s <= srv.miss_rows == 13  # an odd staging capacity
        t, acc, _ = srv.read_latest(tb.miss_ids)
        assert np.array_equal(mt[:n].view(np.uint32), t.view(np.uint32))
        assert np.array_equal(ma[:n].view(np.uint32), acc.view(np.uint32))
        assert (mt[n:] == 0).all() and (ma[n:] == np.float32(0.1)).all()
        at = np.searchsorted(tb.miss_ids, odd)
        assert np.array_equal(mt[at].view(np.uint32), rows[:, :d].view(np.uint32))
        seq = given if isinstance(given, list) else [given]
        res = srv.residency.resolve([p.ids for p in seq], srv.miss_rows)
        got_ids = np.asarray(jax.device_get(tb.batch.ids))
        assert np.array_equal(got_ids, np.stack(res.remapped) if isinstance(given, list) else res.remapped[0])
    # Payloads of one staged size made in a row keep each its own rows on
    # the device.
    cold = np.setdiff1d(np.arange(256), srv.residency.hot_ids)
    made = [conv(parsed(np.resize(cold[6 * i : 6 * i + 6], 8)), np.ones(2, np.float32)) for i in range(6)]
    for tb in made:
        t, acc, _ = srv.read_latest(tb.miss_ids)
        assert tb.miss_ids.size == 6
        assert np.array_equal(np.asarray(tb.staged.result()[0]).reshape(2 * d, -1).T[:6], np.concatenate([t, acc], axis=1))
    conv.close()


class _DictOverlay:
    """The pending overlay as the dict it replaced, with the store beneath
    it: what ``read_latest``, ``_stale`` and ``apply_pending`` must agree
    with."""

    def __init__(self, d, store_seed=1, init_range=0.5):
        self.d, self.seed, self.r = d, store_seed, init_range
        self.pending, self.store, self.log, self.version = {}, {}, [], 0

    def write(self, ids, rows):
        self.version += 1
        self.log.append((self.version, ids))
        for i, row in zip(ids.tolist(), rows):
            self.pending[i] = row.copy()

    def read(self, ids):
        lazy = np.concatenate(
            [hashed_uniform_rows(ids, self.d, self.seed, self.r), np.full((ids.size, self.d), 0.1, np.float32)], axis=1
        )
        return np.stack([self.pending.get(i, self.store.get(i, lazy[j])) for j, i in enumerate(ids.tolist())])

    def stale(self, miss, version, in_flight):
        return any(np.intersect1d(miss, ids).size for v, ids in self.log if v > version) or bool(
            in_flight is not None and np.intersect1d(miss, in_flight).size
        )

    def apply(self):
        self.store.update(self.pending)
        self.pending.clear()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_array_overlay_agrees_with_a_dict(tmp_path, monkeypatch, seed):
    """Seeded sequences of writebacks, reads with repeats, staleness checks
    and applies, one of them killed between two chunks by the chaos hook:
    the overlay reads what a dict over the store reads, and a kill
    mid-apply loses no row and leaves none stale."""
    from fast_tffm_tpu import resilience
    from fast_tffm_tpu.paramstore import tiered

    rng = np.random.default_rng(seed)
    srv = _server(tmp_path, miss_rows=40)
    d, vocab = srv.row_dim, srv.store.vocab
    model = _DictOverlay(d)
    monkeypatch.setattr(tiered, "APPLY_CHUNK_BYTES", 3 * d * 4)  # three rows a chunk
    kill_at = int(rng.integers(1, 4))

    def fault(ordinal):
        if ordinal == kill_at:
            raise RuntimeError("killed between chunks")

    monkeypatch.setattr(resilience, "maybe_writeback_fault", fault)
    versions = [0]
    for _ in range(50):
        op = rng.choice(["write", "read", "stale", "in_flight", "apply"], p=[0.35, 0.3, 0.15, 0.1, 0.1])
        if op in ("write", "in_flight"):
            ids = np.sort(rng.choice(vocab, size=int(rng.integers(1, 30)), replace=False))
            rows = rng.standard_normal((ids.size, 2 * d)).astype(np.float32)
            rows[rng.random(rows.shape) < 0.1] = -0.0
            _stage_and_fetch(srv, ids, rows)
            if op == "in_flight":
                miss = np.unique(rng.choice(vocab, size=20))
                assert srv._stale(miss, srv._version) == model.stale(miss, srv._version, ids)
            srv.flush_writeback(None)
            model.write(ids, rows)
            versions.append(srv._version)
            assert srv._version == model.version
        elif op == "read":
            ids = rng.integers(0, vocab, size=int(rng.integers(1, 60)))
            t, a, v = srv.read_latest(ids)
            assert v == model.version
            assert np.array_equal(np.concatenate([t, a], axis=1).view(np.uint32), model.read(ids).view(np.uint32))
        elif op == "stale":
            miss = np.unique(rng.choice(vocab, size=int(rng.integers(1, 30))))
            v = int(rng.choice(versions))
            assert srv._stale(miss, v) == model.stale(miss, v, None)
        else:
            try:
                srv.apply_pending(f"save-{rng.integers(1 << 30)}")
            except RuntimeError:
                # Killed mid-apply: every pending row is still pending, and
                # what reached the store is the same rows (a redo).
                ids = np.array(sorted(model.pending), np.int64)
                assert srv.pending_rows == ids.size
                if ids.size:
                    t, a = srv.store.read_rows(ids[:3])
                    assert np.array_equal(np.concatenate([t, a], axis=1), model.read(ids[:3]))
                    t, a, _ = srv.read_latest(ids)
                    assert np.array_equal(np.concatenate([t, a], axis=1), model.read(ids))
                continue
            model.apply()
            assert srv.pending_rows == 0
        ids, t, a = srv.pending_snapshot()
        assert list(ids) == sorted(model.pending)
        if ids.size:
            assert np.array_equal(np.concatenate([t, a], axis=1), np.stack([model.pending[i] for i in ids.tolist()]))
    every = np.arange(vocab)
    t, a, _ = srv.read_latest(every)
    assert np.array_equal(np.concatenate([t, a], axis=1), model.read(every))


def test_a_large_read_of_pending_rows_is_split_over_threads_and_reads_the_same():
    from fast_tffm_tpu.paramstore import tiered

    rng = np.random.default_rng(5)
    ov = tiered._Overlay(1 << 20, 6, capacity=1024)
    ids = np.unique(rng.integers(0, 1 << 20, size=400_000))
    rows = rng.standard_normal((ids.size, 6)).astype(np.float32)
    ov.write(ids, rows)
    # Several parts of pending ids, with ids not pending among them.
    absent = np.setdiff1d(np.arange(1 << 20), ids)[:5000]
    ask = rng.permutation(np.concatenate([ids[: 3 * tiered._PART_ROWS + 17], absent]))
    out = np.full((ask.size, 6), np.nan, np.float32)
    cold, version = ov.read_into(ask, out)
    assert version == 1
    assert np.array_equal(np.sort(ask[cold]), absent)
    found = np.setdiff1d(np.arange(ask.size), cold)
    assert np.array_equal(out[found], rows[np.searchsorted(ids, ask[found])])
    assert np.isnan(out[cold]).all()


def test_overlay_readers_beside_its_writer_see_the_latest_rows_or_a_stale_version():
    """The prefetch thread reads the overlay while the loop thread writes
    it.  Under a short switch interval, with more readers than cores: every
    row a reader gets is the latest of its id at the version it was handed,
    unless a LATER writeback holds that id (torn or newer: what ``_stale``
    restages)."""
    import threading

    from fast_tffm_tpu.paramstore.tiered import _Overlay

    vocab, width, writes = 4096, 8, 150
    ov = _Overlay(vocab, width, capacity=16)  # small: the pool grows under the readers
    log = {}  # version -> (ids, value)
    seen, stop = [], threading.Event()

    def reader(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            ids = rng.integers(0, vocab, size=64)
            rows = np.empty((ids.size, width), np.float32)
            cold, version = ov.read_into(ids, rows)
            found = np.setdiff1d(np.arange(ids.size), cold)
            seen.append((ids[found], rows[found], version))

    def writer():
        rng = np.random.default_rng(99)
        for k in range(1, writes + 1):
            ids = np.unique(rng.integers(0, vocab, size=int(rng.integers(1, 200))))
            log[k] = ids
            assert ov.write(ids, np.full((ids.size, width), float(k), np.float32)) == k
        stop.set()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=reader, args=(i,)) for i in range((os.cpu_count() or 1) + 1)]
        threads.append(threading.Thread(target=writer))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert ov.version == writes and len(seen) > 0
    last = {}  # id -> [(version, value)] in order
    for k in range(1, writes + 1):
        for i in log[k].tolist():
            last.setdefault(i, []).append(k)
    checked = 0
    for ids, rows, version in seen:
        for i, row in zip(ids.tolist(), rows):
            wrote = last[i]
            latest = max((k for k in wrote if k <= version), default=None)
            if any(k > version for k in wrote) and not (row == latest).all():
                continue  # a later writeback holds it: the payload restages
            assert latest is not None and (row == latest).all(), (i, version, row)
            checked += 1
    assert checked > 0


@pytest.fixture
def harness():
    """The benchmark's harness (a script's package, not an installed one)."""
    bench = os.path.join(REPO, "benchmark")
    sys.path.insert(0, bench)
    try:
        from harness import cells, gen, train

        yield cells, gen, train
    finally:
        sys.path.remove(bench)


def test_a_lazy_tiered_run_follows_the_float32_reference_and_fails_it_without_its_writeback(tmp_path, monkeypatch, harness):
    """The tiered cell's own check at a toy size past the materialize bound
    (the lazy store, ``fm2_hashed``'s initial rows): about a fifth of a
    step's slots miss the hot tier, and ids that miss in one step miss
    again in the next ones, so that payloads resolved ahead go stale and
    restage.  The run matches the float32 reference after three steps
    within ``train_fmb``'s limits; dropping the writeback fails them."""
    import time

    from fast_tffm_tpu.paramstore import TieredParamServer, tiered

    cells, gen, train = harness
    seed, vocab, batch, n_batches = 11, 1 << 22, 512, 8
    cell = cells.load_cell("fm16_criteo_tiered.train_fmb_tiered")
    cell["ini"]["General"]["vocabulary_size"] = vocab
    cell["ini"]["Train"].update(batch_size=batch, thread_num=2)
    cell["traffic"].update(file_batches=n_batches, warm_steps=8)
    cell["model"] = cells.harness_model(cell["config"], cell["ini"])
    _, ids, _ = gen.rows_from_seed(seed, n_batches * batch, 39, vocab)
    # The hot set: every id seen twice or more but every 16th of them, then
    # singletons up to four fifths of the slots.
    uniq, cnt = np.unique(ids, return_counts=True)
    repeated = uniq[cnt > 1]
    hot = np.concatenate([np.setdiff1d(repeated, repeated[3::16]), uniq[cnt == 1][:107_000]])
    (tmp_path / "hot.txt").write_text("\n".join(map(str, hot)))
    cell["ini"]["ParamStore"].update(hot_rows=hot.size, miss_rows=8192, residency=f"file:{tmp_path / 'hot.txt'}")
    seen = {"hit": 0, "slots": 0, "restages": 0}
    resolve, restage = tiered._TierStats.note_resolve, tiered._TierStats.note_restage

    def note_resolve(self, res, *a):
        seen["hit"] += res.hit_slots
        seen["slots"] += res.total_slots
        return resolve(self, res, *a)

    def note_restage(self, *a):
        seen["restages"] += 1
        return restage(self, *a)

    monkeypatch.setattr(tiered._TierStats, "note_resolve", note_resolve)
    monkeypatch.setattr(tiered._TierStats, "note_restage", note_restage)
    run = lambda: train.run(cell, seed, 0.2, False, time.time(), require_chip=False, workroot=str(tmp_path / "w"))
    r = run()
    assert r["correct"] is True and r["failed"] == 0, r["compared"]
    assert 0.75 < seen["hit"] / seen["slots"] < 0.85 and seen["restages"] > 0, seen

    def dropped(self, state):
        self._last_staged = self._fetched = None

    monkeypatch.setattr(TieredParamServer, "flush_writeback", dropped)
    r = run()
    assert r["correct"] is False and min(v["value"] / v["limit"] for k, v in r["compared"].items() if k != "loss_gap") > 1


def test_the_tiered_cell_stops_at_once_a_program_whose_store_searches_its_hot_set(monkeypatch, harness):
    """The tiered cell's harness model loads over this program's store (its
    residency map is built over the vocabulary) and stops a program whose
    map searches the sorted hot set, with a message and before the runtime
    starts: such a program's set-up alone outlasts a run."""
    from fast_tffm_tpu.paramstore import residency

    cells, _, _ = harness
    cell = cells.load_cell("fm16_criteo_tiered.train_fmb_tiered")
    assert cell["model"].row_dim == 17 and cell["config"]["harness_model"] == "fm2_tiered"

    class SearchedMap:
        def __init__(self, hot_ids):
            self.hot_ids = np.sort(hot_ids)

    monkeypatch.setattr(residency, "ResidencyMap", SearchedMap)
    with pytest.raises(SystemExit, match="searching the sorted hot set"):
        cells.load_cell("fm16_criteo_tiered.train_fmb_tiered")


@pytest.mark.parametrize("shape", [(3, 5), (5, 3), (20_011, 34), (34, 70_001)])
def test_the_blocked_transpose_is_the_transpose(shape, monkeypatch):
    """The staged rows' way to the chip and back: a block of the long axis
    at a time, on threads, across blocks and parts of odd sizes."""
    from fast_tffm_tpu.paramstore import tiered

    monkeypatch.setattr(tiered, "_T_BLOCK", 1000)
    monkeypatch.setattr(tiered, "_PART_ROWS", 4096)
    a = np.random.default_rng(5).standard_normal(shape).astype(np.float32)
    got = tiered._transposed(a)
    assert got.flags.c_contiguous and np.array_equal(got, a.T)
