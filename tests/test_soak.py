"""Soak-harness tests (ISSUE 11): the ~30 s miniature soak runs inside
tier-1 — trainer tail-following a live writer, continuous delta publish,
a loaded replica fleet applying the chain, one trainer kill + one stream
stall, every sentinel enforced.  The full multi-minute soak is
slow-marked."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_soak(tmp_path, extra_args, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = str(tmp_path / "probe.json")
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "tools", "soak.py"),
            "--out", out, *extra_args,
        ],
        capture_output=True,
        text=True,
        env=env,
        cwd=REPO,
        timeout=timeout,
    )
    assert os.path.isfile(out), (
        f"soak wrote no probe JSON\nstdout:\n{proc.stdout[-4000:]}"
        f"\nstderr:\n{proc.stderr[-4000:]}"
    )
    with open(out) as f:
        result = json.load(f)
    return proc, result


def _assert_gates(proc, result):
    gates = result["gates"]
    failed = [k for k, v in gates.items() if not v]
    assert proc.returncode == 0 and result["gate"] == "OK", (
        f"soak gate {result['gate']} rc {proc.returncode}, failed {failed}\n"
        f"stdout tail:\n{proc.stdout[-4000:]}\nstderr tail:\n{proc.stderr[-3000:]}"
    )
    # Every answered-or-nothing request got its response line.
    assert result["unanswered"] == 0
    assert result["requests_sent"] > 0
    assert result["requests_answered"] == result["requests_sent"]
    # The chaos actually happened: the trainer was SIGKILLed and came
    # back (supervised restart + mid-stream resume), and the writer went
    # silent once (the follow reader idled and resumed).
    assert result["trainer_restarts"] >= 1
    assert result["stream_stalls_executed"] >= 1
    assert result["trainer_rc"] == 0
    # Zero steady-state recompiles on the trainer; the per-replica pin is
    # a sentinel check (replicas_no_steady_recompiles) inside the gate.
    assert result["trainer_steady_compiles"] == 0
    # The delta chain stayed bounded the whole run.
    assert 0 <= result["max_chain_len"] <= 16


def test_soak_smoke(tmp_path):
    """The tier-1 miniature: ~20 s of concurrent trainer + publisher +
    1-replica fleet under load with a live trainer kill + stream stall."""
    proc, result = _run_soak(
        tmp_path, ["--smoke", "--minutes", "0.3"], timeout=360
    )
    _assert_gates(proc, result)
    assert result["mode"] == "smoke"
    # The sentinel loop ran (kind=soak ticks) and all passed.
    assert result["sentinel_ticks"] >= 2
    assert result["sentinel_failures"] == 0


@pytest.mark.slow
def test_soak_full_two_replicas(tmp_path):
    """The committed-probe shape at reduced length: 2 replicas, replica
    kill + torn delta + stream faults, several minutes of sustained
    concurrency."""
    proc, result = _run_soak(
        tmp_path,
        [
            "--minutes", "3", "--replicas", "2", "--qps", "150",
            "--fault-plan",
            "kill@300,torn_delta@2,replica_kill@1,stream_stall@3,append_torn@4",
        ],
        timeout=900,
    )
    _assert_gates(proc, result)
    assert result["replicas"] == 2
    assert result["torn_appends_executed"] >= 1
