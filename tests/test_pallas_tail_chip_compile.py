"""The rows sweep compiled for a described TPU v5e at the train cell's real
shapes (no chip: the TPU's compiler is installed here and compiles for a
topology that is described, not attached).  What interpret mode cannot
show: that Mosaic takes the kernel (it refused the per-row DMA kernels this
one replaced), that it fits VMEM at every row width, and that XLA hands it
table and accumulator in place — the four transposes are bitcasts.

All of it in this one file and behind a fixture: one process may hold the
TPU's library, so only the worker that runs this file loads it.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fast_tffm_tpu.ops.pallas_tail import sweep_adagrad_update


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, v, d, a, m):
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    sweep = jax.jit(
        lambda t, acc, u, g: sweep_adagrad_update(t, acc, u, g, 0.05, interpret=False),
        donate_argnums=(0, 1),
    )
    args = (sd((v, d), jnp.float32), sd((v, a), jnp.float32), sd((m,), jnp.int32), sd((m, d), jnp.float32))
    return sweep.lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "v, d, a, m",
    [
        (2**26, 9, 9, 65536 * 39),  # fm8_criteo.train_fmb
        (2**26, 9, 1, 65536 * 39),  # fm8_criteo_rowacc's state under the same batch
        (2**20, 89, 89, 32768 * 22),  # an Avazu-shaped FFM row: 12 sublane groups a block
    ],
    ids=["fm8_element", "fm8_row", "ffm_d89"],
)
def test_the_sweep_compiles_for_the_chip_in_place(one_chip, v, d, a, m):
    text = _compiled_text(one_chip, v, d, a, m)
    assert "tpu_custom_call" in text
    # Whatever touches a whole [V, D] (or [V, A]) buffer outside the kernel
    # is a bitcast of it, a parameter or the result tuple: no copy, no
    # transpose, no fusion reads or writes the table.
    whole = re.compile(rf"f32\[({v},({d}|{a})|({d}|{a}),{v})\]")
    ops = set()
    for line in text.splitlines():
        m_ = re.match(r"\s*(ROOT )?%?[\w.\-]+ = (\([^=]*\)|\S+) ([\w\-]+)\(", line)
        if m_ and whole.search(line):
            ops.add(m_.group(3))
    assert ops <= {"parameter", "bitcast", "custom-call", "get-tuple-element", "tuple"}, ops
    assert "bitcast" in ops and "custom-call" in ops
    assert f"f32[{v},{d}]{{0,1:T(8,128)}}" in text  # the lane-major layout the view rests on
