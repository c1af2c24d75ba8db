"""Every configuration the benchmark ships names a harness model
(``benchmark/harness/models/<name>.py``) that loads and that agrees with the
program's own model on what a table row is: its width and whether the score
reads field ids.  A program PR that changes a row's layout meets the
benchmark's plain reference here, in the tests the driver runs, and not first
on the chip."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIGS = sorted(fn[: -len(".json")] for fn in os.listdir(os.path.join(BENCH, "configs")) if fn.endswith(".json"))


@pytest.fixture
def cells():
    sys.path.insert(0, BENCH)  # the harness is a script's package, not an installed one
    try:
        from harness import cells as module

        yield module
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("config", CONFIGS)
def test_the_named_harness_model_has_the_programs_row(config, cells, tmp_path):
    from fast_tffm_tpu.config import build_model, load_config

    cell = cells.load_cell(f"{config}.train_fmb")
    assert cell["config"]["name"] == config and cell["config"]["harness_model"]
    program = build_model(load_config(cells.write_ini(str(tmp_path / "cell.cfg"), cell["ini"])))
    assert cell["model"].row_dim == program.row_dim
    assert cell["model"].reads_fields == bool(getattr(program, "uses_fields", False))

