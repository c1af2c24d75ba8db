"""``rehearse_state.py``: the initial state of the four-chip cell as the
program builds it, compiled at the cell's real shapes for a described
``v5e:2x2``: a device is asked for its shard of table and accumulator (2 x
2^25 rows of 17 float32, lane-major at 24 sublanes: 2 x 3.0 GiB) and a
quarter GiB of the draw's temporaries, not for the 24 + 1 GiB of a one-device
draw."""

import re
import sys

import pytest

from harness import cells


def test_no_device_is_asked_for_more_than_its_shard_and_the_draws_temporaries(capsys):
    from jax.experimental import topologies

    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    sys.path.insert(0, cells.BENCH_DIR)
    try:
        import rehearse_state
    finally:
        sys.path.remove(cells.BENCH_DIR)
    assert rehearse_state.main(["--workload", "fm16_criteo_row4.dist_train_fmb"]) == 0
    out = capsys.readouterr().out
    m = re.search(r"outputs ([\d.]+) GiB aliased [\d.]+ GiB temporaries ([\d.]+) GiB -> live at peak about ([\d.]+) GiB per device", out)
    assert m and "{'data': 1, 'row': 4}" in out and "(134217728, 17)" in out
    outputs, temporaries, peak = map(float, m.groups())
    assert outputs == pytest.approx(6.0, abs=0.01) and temporaries <= 0.5 and peak <= 6.5
