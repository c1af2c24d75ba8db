"""The benchmark's copy of the work model equals the program's, today."""

import numpy as np
import pytest

from harness import models, peaks


@pytest.mark.parametrize("seed,row_dim,accum_cols", [(0, 9, 9), (1, 17, 17), (2, 9, 1), (3, 157, 157)])
def test_step_bytes_equal_the_programs(seed, row_dim, accum_cols):
    from fast_tffm_tpu.profiling import modeled_step_bytes

    ids = np.random.default_rng(seed).integers(0, 5000, size=(256, 39))
    assert models.sparse_step_bytes(ids, row_dim, accum_cols) == modeled_step_bytes(ids, row_dim, accum_cols)


def test_least_time_names_its_bound_and_unknown_chips_are_errors():
    t, bound = peaks.least_seconds(1e9, 819e9, "TPU v5 lite")
    assert bound == "hbm" and t == pytest.approx(1.0)
    assert peaks.least_seconds(197e12, 1.0, "TPU v5 lite")[1] == "flops"
    with pytest.raises(SystemExit):
        peaks.peaks_for("cpu")
