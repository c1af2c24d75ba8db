"""The benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

They drive the harness with the look for a chip skipped, at toy sizes."""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")  # the four-chip cell's mesh
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tempfile.gettempdir(), "fast_tffm_tpu-bench-tests-jax-cache")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def toy_bench(tmp_path):
    """A benchmark directory of its own: the shipped configurations and mixes
    at toy sizes, the shipped metrics as they are.  Found by name alone."""
    root = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "metrics"), root / "metrics")
    (root / "configs").mkdir()
    (root / "traffic").mkdir()
    for fn in os.listdir(os.path.join(BENCH, "configs")):
        c = json.load(open(os.path.join(BENCH, "configs", fn)))
        c["ini"]["General"]["vocabulary_size"] = 1 << 14
        c["ini"]["Train"]["batch_size"] = 512
        c["ini"]["Train"]["thread_num"] = 2
        json.dump(c, open(root / "configs" / fn, "w"))
    for fn in os.listdir(os.path.join(BENCH, "traffic")):
        t = json.load(open(os.path.join(BENCH, "traffic", fn)))
        if "file_batches" in t:
            t["file_batches"] = 8
        for k, v in t.get("toy", {}).items():
            t[k] = v
        json.dump(t, open(root / "traffic" / fn, "w"))
    return str(root)
