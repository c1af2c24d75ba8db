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


# The one model with parameters outside the table, named by no shipped
# configuration: a toy cell of it, made of new files alone.  The mix is
# ``train_fmb`` key for key with limits for the two numbers over dense leaves;
# every limit is set from readings at THIS size on the CPU (test_dense_seam.py
# says which), not from a chip.
DEEPFM_TOY = {
    "name": "deepfm_toy", "harness_model": "deepfm", "chips": 1, "reduced": [],
    "source": "a toy for the harness's own tests: DeepFM at 39 fields, k = 10, a perceptron of 16-16-16",
    "ini": {
        "General": {"model": "deepfm", "factor_num": 10, "num_fields": 39, "hidden_dims": "16 16 16", "compute_dtype": "float32",
                    "vocabulary_size": 1 << 14, "hash_feature_id": "false"},
        "Train": {"batch_size": 512, "max_nnz": 39, "learning_rate": 0.05, "factor_lambda": 1e-7, "bias_lambda": 1e-7,
                  "init_accumulator_value": 0.1, "thread_num": 2, "queue_size": 8},
    },
}
DENSE_LIMITS = {"dense_grad1_norm_gap": 1e-4, "dense_delta3_norm_gap": 1e-4}


@pytest.fixture
def dense_bench(toy_bench):
    """``toy_bench`` with the toy DeepFM configuration and the mix
    ``train_fmb_dense_toy`` beside the shipped ones (a name of its own: the
    shipped ``train_fmb_dense`` keeps its limits)."""
    json.dump(DEEPFM_TOY, open(os.path.join(toy_bench, "configs", "deepfm_toy.json"), "w"))
    mix = json.load(open(os.path.join(toy_bench, "traffic", "train_fmb.json")))
    mix["limits"] = dict(mix["limits"], **DENSE_LIMITS)
    json.dump(mix, open(os.path.join(toy_bench, "traffic", "train_fmb_dense_toy.json"), "w"))
    return toy_bench
