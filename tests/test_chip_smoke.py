"""chip_smoke.py rehearsed on the CPU test mesh.

The script's own ``__main__`` always expects a TPU; here its phase
functions run at toy size with the expected platform passed in as ``cpu``
— in a CHILD interpreter, because this pytest process has jax loaded and
the smoke's parent must not — and its checks are fed doctored artifacts to
show that each failure the contract names really fails the script.
"""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# BASELINE #1 / #2 with only the widths cut (the full-size run replaces
# nothing but paths, epoch_num and model_file).
_TOY = textwrap.dedent(
    """
    import sys
    import chip_smoke
    toy = chip_smoke.Sizes(
        train_batches=3, epoch_num=2, valid_rows=100, serve_rows=64,
        overrides=(
            (("General", "vocabulary_size"), "4096"),
            (("Train", "batch_size"), "64"),
            (("Train", "log_every"), "2"),
        ),
        anova=(256, 11, 8),
        dist_overrides=(
            (("General", "vocabulary_size"), "4096"),
            (("Train", "batch_size"), "64"),
        ),
    )
    device = chip_smoke.run(sys.argv[1], "cpu", toy)
    assert "jax" not in sys.modules
    print("PARENT_JAX_FREE " + str(device["count"]))
    """
)


def test_toy_rehearsal_runs_every_phase_with_a_jax_free_parent(tmp_path):
    r = subprocess.run(
        [sys.executable, "-c", _TOY, str(tmp_path / "out")],
        cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = r.stdout
    n_dev = jax.device_count()
    assert f"PARENT_JAX_FREE {n_dev}" in out
    phases = ["device", "data", "train", "predict", "serve", "kernels"]
    if n_dev >= 4:  # the forced 8-device host mesh: the four-chip phases run
        phases += ["dist_train", "dist_predict", "dist_check"]
    else:
        assert "chip_smoke: dist skipped" in out
    for phase in phases:
        (line,) = [l for l in out.splitlines() if l.startswith(f"chip_smoke: {phase} ok")]
        if phase != "data":
            assert "platform=cpu" in line, line
    (train,) = [l for l in out.splitlines() if l.startswith("chip_smoke: train ok")]
    for field in ("device_kind=", "devices=", "wall=", "compiles=", "cache_hits=", "parser="):
        assert field in train, train
    assert "chip_smoke: kernels rows_tail: interpreted+matched" in out


def test_main_refuses_the_cpu_and_prints_no_result():
    """`python chip_smoke.py` on a machine without a TPU: non-zero exit,
    the phase and the reason on the last line, no result object."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("chip_smoke: FAILED device:") and "'cpu'" in last
    assert '"ok"' not in r.stdout


def _smoke(tmp_path, expect="cpu"):
    return chip_smoke.Smoke(str(tmp_path), expect, echo=lambda *_: None)


def test_child_exiting_nonzero_fails_the_phase(tmp_path):
    s = _smoke(tmp_path)
    with pytest.raises(chip_smoke.SmokeFailure, match="device: child exited 3: boom"):
        s.run_child(
            "device", [sys.executable, "-c", "import sys; print('boom'); sys.exit(3)"]
        )
    s.close()


def test_child_timing_out_fails_the_phase_and_is_killed(tmp_path):
    s = chip_smoke.Smoke(str(tmp_path), "cpu", deadline_s=1.0, echo=lambda *_: None)
    with pytest.raises(chip_smoke.SmokeFailure, match="timed out"):
        s.run_child("device", [sys.executable, "-c", "import time; time.sleep(60)"])
    assert s._live == []


def _records(platform="cpu", loss=0.69):
    return [
        {"kind": "train", "step": 6, "loss": loss},
        {"kind": "validation", "step": 6, "validation_auc": 0.7},
        {"kind": "compile", "step": 1, "compiles": 2, "cache_hits": 1},
        {
            "kind": "summary", "step": 6, "total_compiles": 2,
            "platform": platform, "device_kind": platform, "device_count": 1,
        },
    ]


_LOG = 'train: device platform={p} device_kind="x" device_count=1 pallas={k} parser=native\n'


def test_train_checks(tmp_path):
    ckpt = tmp_path / "m.ckpt"
    ckpt.write_text("x")
    s = _smoke(tmp_path)
    log = _LOG.format(p="cpu", k="interpreted")
    info = s.check_train("train", _records(), log, 5, str(ckpt))
    assert info["compiles"] == 2 and info["cache_hits"] == 1 and info["parser"] == "native"
    with pytest.raises(chip_smoke.SmokeFailure, match="not finite"):
        s.check_train("train", _records(loss=float("nan")), log, 5, str(ckpt))
    with pytest.raises(chip_smoke.SmokeFailure, match="only 6 optimizer steps"):
        s.check_train("train", _records(), log, 7, str(ckpt))
    with pytest.raises(chip_smoke.SmokeFailure, match="no checkpoint"):
        s.check_train("train", _records(), log, 5, str(tmp_path / "absent"))
    with pytest.raises(chip_smoke.SmokeFailure, match="did not close"):
        s.check_train("train", _records()[:-1], log, 5, str(ckpt))


def test_a_child_on_another_platform_fails(tmp_path):
    ckpt = tmp_path / "m.ckpt"
    ckpt.write_text("x")
    s = _smoke(tmp_path, expect="tpu")
    # the summary record names the wrong platform
    with pytest.raises(chip_smoke.SmokeFailure, match="platform 'cpu', expected 'tpu'"):
        s.check_train("train", _records("cpu"), _LOG.format(p="tpu", k="compiled"),
                      5, str(ckpt))
    # right platform, but the path's Pallas kernels ran interpreted
    with pytest.raises(chip_smoke.SmokeFailure, match="interpreted"):
        s.check_train("train", _records("tpu"), _LOG.format(p="tpu", k="interpreted"),
                      5, str(ckpt))
    s.check_train("train", _records("tpu"), _LOG.format(p="tpu", k="compiled"),
                  5, str(ckpt))


def test_a_short_score_file_fails(tmp_path):
    s = _smoke(tmp_path)
    data = tmp_path / "in.libsvm"
    data.write_text("1 3:1\n0 4:1\n\n1 5:1\n")
    scores = tmp_path / "scores.txt"
    scores.write_text("0.5\n0.25\n0.75\n")
    assert s.check_scores("predict", str(scores), str(data)) == [0.5, 0.25, 0.75]
    scores.write_text("0.5\n0.25\n")
    with pytest.raises(chip_smoke.SmokeFailure, match="2 score lines for 3 input lines"):
        s.check_scores("predict", str(scores), str(data))
    scores.write_text("0.5\n1.000000\n0.25\n")
    with pytest.raises(chip_smoke.SmokeFailure, match=r"outside \(0, 1\)"):
        s.check_scores("predict", str(scores), str(data))


def test_derived_config_replaces_only_what_it_names(tmp_path):
    src = os.path.join(REPO, "configs", "baseline1_fm_criteo_sample.cfg")
    dst = tmp_path / "d.cfg"
    chip_smoke.derive_config(
        src, str(dst),
        {("General", "model_file"): "/x/m.ckpt", ("Train", "epoch_num"): "4",
         ("Serving", "replicas"): "2"},
    )
    a, b = chip_smoke.read_cfg(src), chip_smoke.read_cfg(str(dst))
    assert b["General"]["model_file"] == "/x/m.ckpt"
    assert b["Train"]["epoch_num"] == "4" and b["Serving"]["replicas"] == "2"
    changed = {
        (sec, k)
        for sec in a.sections() for k in a[sec]
        if a[sec][k] != b[sec][k]
    }
    assert changed == {("General", "model_file"), ("Train", "epoch_num")}


def test_compilation_cache_precedence(tmp_path, monkeypatch):
    """env -> config key -> the one fixed path in the checkout; with the
    env variable set our code leaves jax's directory setting alone."""
    from fast_tffm_tpu import telemetry

    # the real fixed path: inside the checkout, no pid/time/temp name in it
    assert telemetry.CHECKOUT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    fixed = tmp_path / "checkout" / ".jax_cache"
    monkeypatch.setattr(telemetry, "CHECKOUT_CACHE_DIR", str(fixed))
    try:
        # 1. the environment wins, and nothing of ours touches jax's setting
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
        assert telemetry.enable_compilation_cache(str(tmp_path / "cfg")) == str(
            tmp_path / "env"
        )
        assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"
        assert not (tmp_path / "cfg").exists() and not fixed.exists()
        # 2. no env: the config key
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert telemetry.enable_compilation_cache(str(tmp_path / "cfg")) == str(
            tmp_path / "cfg"
        )
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cfg")
        assert not fixed.exists()
        # 3. neither: the fixed path inside the checkout
        assert telemetry.enable_compilation_cache("") == str(fixed)
        assert jax.config.jax_compilation_cache_dir == str(fixed) and fixed.is_dir()
        # every program caches, small ones included
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_router_refuses_more_replicas_than_chips(tmp_path, monkeypatch):
    """serve_replicas > chips on a TPU host is a start-up error naming both
    numbers — raised before anything is spawned."""
    from fast_tffm_tpu.config import Config
    from fast_tffm_tpu.serving import router

    real_tpu_chips = router.tpu_chips
    monkeypatch.setattr(router, "tpu_chips", lambda: ["0"])
    cfg = Config(serve_replicas=2)
    with pytest.raises(ValueError, match=r"serve_replicas = 2 .* 1 TPU chip"):
        router.Router(cfg, config_path=str(tmp_path / "none.cfg"))
    # what the workers are pinned with, and when nothing is pinned at all
    assert router._one_chip_env("3")["TPU_VISIBLE_CHIPS"] == "3"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert real_tpu_chips() == []
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert real_tpu_chips() == ["2", "3"]
