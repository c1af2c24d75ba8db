"""The row shard's lookup compiled for a described ``v5e:2x2`` at
``fm16_criteo_row4.dist_train_fmb``'s shapes (no chip: the TPU's compiler
compiles for a topology that is described, not attached), with the lookup's
rule asked as a TPU's (``parallel.embedding.shard_lookup_form``): what the
interpreted kernel on the CPU mesh cannot show, that Mosaic takes the gather
kernel inside the sharded step, which ops stand in each branch of the
lookup's conditional and under which scope, and that the shard reaches the
kernel as a bitcast.  Apart from ``test_pallas_tail_chip_compile.py``, whose
helper compiles the step, so that the two compiles run on two workers.
"""

import re

import pytest

from test_pallas_tail_chip_compile import _sharded_step


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_the_sharded_steps_lookup_sweeps_the_slots_the_shard_owns(four_chips):
    """The same step with the lookup's rule asked as a TPU's: the masked row
    gather of all four chips' 2,555,904 slots stands in the whole list's
    branch of a second conditional alone.  Its other branch, under
    ``fm.gather``, holds ONE sort of two ``s32[2555904]`` operands (local id,
    slot), the gather kernel on the shard's transposed view (a bitcast: no
    copy of the shard, which the tail's kernel updates in place after it),
    and the way back as ``f32[2555904,128]`` rows scattered into zeros; no
    gather there makes ``f32[2555904,17]`` rows.  The step still fits a
    chip's 16 GB; its temporaries are printed (the masked lookup's step
    prints its own in ``test_pallas_tail_chip_compile.py``)."""
    text, memory, asked = _sharded_step(four_chips, "sweep")
    print(f"temporaries a chip with the swept lookup: {memory.temp_size_in_bytes} bytes")
    held = memory.argument_size_in_bytes + memory.output_size_in_bytes - memory.alias_size_in_bytes
    assert held + memory.temp_size_in_bytes < 16 * 10**9
    bounded = "fm.gather/cond/branch_0_fun/"
    shard = re.compile(r"f32\[(33554432,17|17,33554432)\]")
    on_shard, sorts, kernels, made_wide, narrow_in_bounded, narrow_in_whole = set(), [], [], set(), [], []
    for line in text.splitlines():
        op = re.match(r"\s*(ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not op:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        path = name.group(1) if name else ""
        result, opcode = op.group(2), op.group(3)
        assert not ("fm.gather" in path and re.search(r"/fm\.(dedup|tail)", path)), line[:200]
        if shard.search(result):
            on_shard.add(opcode)
        if "tpu_custom_call" in line and "fm.gather" in path:
            kernels.append(path)
        if opcode == "sort" and bounded in path and "2555904]" in result:
            sorts.append(result)
        if bounded in path and "f32[2555904,128]" in result:
            made_wide.add((opcode, path.rsplit("/", 1)[-1]))
        if opcode in ("gather", "fusion") and path.endswith("/gather") and "f32[2555904,17]" in result:
            (narrow_in_bounded if bounded in path else narrow_in_whole).append(path)
    assert text.count(" conditional(") == 2  # the tail's, and now the lookup's
    assert [re.findall(r"s32\[2555904\]", r) for r in sorts] == [["s32[2555904]"] * 2], sorts
    assert kernels and all(k.endswith(bounded + "pallas_call") for k in kernels), kernels
    assert ("fusion", "scatter") in made_wide or ("scatter", "scatter") in made_wide, made_wide
    assert not narrow_in_bounded and narrow_in_whole, (narrow_in_bounded, narrow_in_whole)
    assert on_shard <= {"parameter", "bitcast", "custom-call", "get-tuple-element", "tuple", "conditional"}, on_shard
    assert sorted({a for a, _ in asked}) == [(2**25, 1284384, 17, 17), (2**25, 2555904, 17, 17)]
