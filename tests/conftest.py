"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Multi-chip sharding code paths (SURVEY.md §5: "multi-device tests via XLA
host-device emulation") run on `--xla_force_host_platform_device_count=8`;
real-TPU behavior is exercised by chip_smoke.py on the chip instead.
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Tier-1 neither reads nor writes the checkout's persistent compile cache:
# tests count compiles, and a count that depends on what an earlier run
# left on disk is not a test.  The cache is OFF (in this process and in
# every CLI/replica child, which inherit the environment), and the
# directory variable points outside the checkout so
# telemetry.enable_compilation_cache sets no directory of its own.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    tempfile.gettempdir(), "fast_tffm_tpu-tier1-jax-cache"
)

import jax  # noqa: E402
import pytest  # noqa: E402

# Multi-device tests gate themselves on len(jax.devices()) (test_parallel's
# skipif), so no device-count assert here — an ambient XLA_FLAGS with a
# smaller forced count must degrade to skips, not a collection error.
assert jax.default_backend() == "cpu", "tests must run on the virtual CPU mesh"

# Tier-1 wall-clock budget (ROADMAP's verify timeout is 870 s; leave slack
# for collection + interpreter startup).  Exceeding it doesn't fail the
# run — the driver's timeout already does that, brutally — but the summary
# warning names the problem while it is one new test old, not twenty.
TIER1_BUDGET_S = 700


# Hardcoded-TCP-port guard (ISSUE 8 satellite): a test that binds (or
# serves on) a LITERAL nonzero port races every parallel CI shard and
# every leftover process for that number — tier-1 must never flake on a
# port collision.  The only collision-proof pattern is the ephemeral
# helper: bind port 0, then introspect the real port (getsockname()[1] /
# Frontend.port / the child's READY line).  Scanned statically at
# collection so the guard itself can't flake.
import re as _re

_PORT_LITERAL_RE = _re.compile(
    r"(?:\.bind\(|create_server\(|TCPServer\(|UDPServer\()"
    r"\s*\(\s*[^,()]+,\s*([1-9]\d*)\s*\)"
)


def pytest_collection_modifyitems(config, items):
    """Collection-time tier-1 guards.

    1. Tests that spawn multi-process worker jobs (their module uses the
       ``_run_workers`` subprocess harness) MUST carry
       ``@pytest.mark.slow``, or the 'not slow' verify gate silently
       inherits minutes-long subprocess runs and blows the ROADMAP
       timeout.  Unknown markers are caught by --strict-markers
       (pytest.ini addopts).
    2. No test module may bind a TCP/UDP socket to a literal nonzero
       port (see _PORT_LITERAL_RE above) — use port 0 + introspection.
    """
    import pytest

    offenders = [
        item.nodeid
        for item in items
        if getattr(item.module, "_run_workers", None) is not None
        and "slow" not in {m.name for m in item.iter_markers()}
    ]
    if offenders:
        raise pytest.UsageError(
            "tier-1 guard: these tests use the subprocess worker harness "
            "(_run_workers) but are not @pytest.mark.slow — they would run "
            "inside the 'not slow' verify gate and exceed its timeout:\n  "
            + "\n  ".join(offenders)
        )
    port_offenders = []
    for path in sorted({str(item.path) for item in items}):
        try:
            with open(path) as f:
                src = f.read()
        except OSError:
            continue
        for m in _PORT_LITERAL_RE.finditer(src):
            line = src.count("\n", 0, m.start()) + 1
            port_offenders.append(f"{path}:{line} (literal port {m.group(1)})")
    if port_offenders:
        raise pytest.UsageError(
            "tier-1 guard: tests must bind ephemeral ports (port=0, then "
            "introspect via getsockname()/Frontend.port/READY line) — a "
            "literal port number flakes on collisions:\n  "
            + "\n  ".join(port_offenders)
        )


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    start = getattr(terminalreporter, "_sessionstarttime", None)
    if start is None or "not slow" not in (config.getoption("-m") or ""):
        return  # only the tier-1 selection carries the budget
    import time as _time

    elapsed = _time.time() - start
    if elapsed > TIER1_BUDGET_S:
        terminalreporter.write_line(
            f"WARNING: 'not slow' suite took {elapsed:.0f}s > tier-1 budget "
            f"{TIER1_BUDGET_S}s — the verify gate (870s hard timeout) is "
            "at risk; mark long tests slow or trim them",
            yellow=True,
        )


@pytest.fixture
def sweep_form(monkeypatch):
    """``optim.rows_tail_form`` says the sweep whatever the shapes and the
    backend: every step TRACED while this holds takes the kernel (here
    interpreted), as a TPU's does where the rule says so."""
    asked = []
    monkeypatch.setattr(
        "fast_tffm_tpu.optim.rows_tail_form", lambda *a, **k: asked.append(a) or "sweep"
    )
    return asked  # the shapes of every step traced so
