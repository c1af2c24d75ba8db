"""The rows sweep compiled for a described TPU v5e at the train cell's real
shapes (no chip: the TPU's compiler is installed here and compiles for a
topology that is described, not attached).  What interpret mode cannot
show: that Mosaic takes the kernel (it refused the per-row DMA kernels this
one replaced), that it fits VMEM at every row width, and that XLA hands it
table and accumulator in place — the four transposes are bitcasts.  And
of the whole step at the two train cells' shapes: which ops stand under
``fm.dedup`` and ``fm.tail`` in each form (ISSUE 32: the sweep's step sums
no segments and sorts once; the rows' step is what it was), and of the
SHARDED step at the four-chip cell's shapes (ISSUE 36: the shard's tail is
the same sweep, under the same scopes).  Since ISSUE 40 the forward gather's
kernel too (ops/pallas_gather.py), alone at both one-chip FM cells' shapes
and inside ``fm8_criteo``'s step, under ``fm.gather``.  And the tiered
cell's inner step, whose gradients of 17 reach id order as tile-wide rows.

All of it in this one file and behind a fixture: one process may hold the
TPU's library, so only the worker that runs this file loads it.
"""

import collections
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fast_tffm_tpu.ops.pallas_tail import sweep_adagrad_update


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


def _compiled_text(one_chip, v, d, a, m):
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    sweep = jax.jit(
        lambda t, acc, u, gt: sweep_adagrad_update(t, acc, u, gt, 0.05, interpret=False),
        donate_argnums=(0, 1),
    )
    args = (sd((v, d), jnp.float32), sd((v, a), jnp.float32), sd((m,), jnp.int32), sd((d, m), jnp.float32))
    return sweep.lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "v, d, a, m",
    [
        (2**26, 9, 9, 65536 * 39),  # fm8_criteo.train_fmb
        (2**26, 9, 1, 65536 * 39),  # fm8_criteo_rowacc's state under the same batch
        (2**20, 89, 89, 32768 * 22),  # an Avazu-shaped FFM row: 12 sublane groups a block
        (2**25, 17, 17, 65536 * 39),  # fm16_criteo_row4's shard under all four chips' ids
    ],
    ids=["fm8_element", "fm8_row", "ffm_d89", "fm16_shard"],
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


def _step_ops(one_chip, monkeypatch, model, b, n):
    """(compiled text, {scope: Counter of HLO opcodes}, forms asked) of the
    train step as ``make_train_step`` builds it, compiled for the described
    chip; ``rows_tail_form`` and ``gather_form`` are told the backend is a
    TPU (they ask ``jax.default_backend()``, which is the CPU here) and the
    kernels are compiled, not interpreted."""
    from fast_tffm_tpu import optim, trainer
    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.ops import pallas_gather, pallas_tail
    from fast_tffm_tpu.trainer import init_state, make_train_step

    rule, asked = optim.rows_tail_form, []
    monkeypatch.setattr(optim, "rows_tail_form", lambda *a, backend=None: asked.append(rule(*a, backend="tpu")) or asked[-1])
    monkeypatch.setattr(pallas_tail, "resolve_interpret", lambda interpret: False)
    gather_rule = trainer.gather_form  # the forward gather's form is asked the same way
    monkeypatch.setattr(trainer, "gather_form", lambda *a, backend=None: gather_rule(*a, backend="tpu"))
    monkeypatch.setattr(pallas_gather, "resolve_interpret", lambda interpret: False)
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = jax.eval_shape(lambda: init_state(model, jax.random.key(0), 0.1, "element"))
    state = jax.tree.map(lambda x: sd(x.shape, x.dtype), state)
    batch = Batch(
        labels=sd((b,), jnp.float32), ids=sd((b, n), jnp.int32), vals=sd((b, n), jnp.float32),
        fields=sd((b, n if model.uses_fields else 0), jnp.int32), weights=sd((b,), jnp.float32),
    )
    text = make_train_step(model, 0.05).lower(state, batch).compile().as_text()
    ops = collections.defaultdict(collections.Counter)
    for line in text.splitlines():
        op = re.match(r"\s*(ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)  # a long tuple type holds /*index=5*/
        scope = re.search(r'op_name="jit\(step\)/(fm\.(?:gather|dedup|tail))/', line)
        if op and scope:
            ops[scope.group(1)][op.group(3)] += 1
    return text, ops, asked


def test_the_sweeps_step_sums_no_segments_and_sorts_once(one_chip, monkeypatch):
    """``fm8_criteo.train_fmb``'s step (2^26 rows of 9, 65,536 x 39 ids): the
    form is the sweep, and between the backward pass and the kernel stands
    ONE sort, which carries the nine gradient columns as operands: no
    segment sum (a ``scatter`` under ``fm.dedup``), none of its two
    ``f32[2555904,128]`` temporaries, no second sort, and no gather of
    ``f32[2555904,9]`` rows by the sort's order."""
    from fast_tffm_tpu.models import FMModel

    text, ops, asked = _step_ops(one_chip, monkeypatch, FMModel(vocabulary_size=2**26, factor_num=8, order=2), 65536, 39)
    assert asked == ["sweep"] and "tpu_custom_call" in text
    assert ops["fm.dedup"]["sort"] == 1 and ops["fm.dedup"]["scatter"] == 0
    assert "2555904,128]" not in text and "scatter-add" not in text
    assert ops["fm.dedup"]["gather"] == 0  # the permutation rides the sort
    assert ops["fm.tail"]["sort"] == 0 and ops["fm.tail"]["scatter"] == 0
    # ISSUE 40: the forward gather is a sweep too.  Under ``fm.gather`` stand
    # the sort of the ids with their positions, the kernel and the sort that
    # brings the nine columns back to batch order; no row is gathered from
    # the table (no ``gather`` producing ``f32[2555904,9]``), the table
    # reaches both kernels as a bitcast, and no instruction stands under two
    # of the three scopes (``harness/scopes.py`` would count it twice).
    assert ops["fm.gather"]["sort"] == 2
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*?op_name="jit\(step\)/(fm\.\w+)/pallas_call"', text)
    assert sorted(kernels) == ["fm.gather", "fm.tail"]
    for line in text.splitlines():
        assert not (" gather(" in line and "f32[2555904,9]" in line.split(" gather(")[0]), line
        assert not re.search(r" copy\(.*f32\[67108864,9\]", line), line
        assert sum(f"/{scope}/" in line for scope in ("fm.gather", "fm.dedup", "fm.tail")) <= 1, line


def test_the_tiered_step_gathers_its_17_wide_gradients_as_tile_wide_rows(one_chip, monkeypatch):
    """``fm16_criteo_tiered.train_fmb_tiered``'s inner step (the compact table
    of 2^25 + 2^20 rows of 17, 65,536 x 39 ids): the tail is the sweep, and
    under ``fm.dedup`` stand ONE sort (the ids with their positions) and the
    gather of the gradients padded to ``f32[2555904,128]`` rows, held
    row-major, in its order; no gather there makes ``f32[2555904,17]`` rows
    (the narrow gather reads 17 single lanes a row)."""
    from fast_tffm_tpu.models import FMModel

    model = FMModel(vocabulary_size=2**25 + 2**20, factor_num=16, order=2)
    text, ops, asked = _step_ops(one_chip, monkeypatch, model, 65536, 39)
    assert asked == ["sweep"] and "tpu_custom_call" in text
    assert ops["fm.dedup"]["sort"] == 1
    gathers = [l for l in text.splitlines() if re.search(r"= \S+ gather\(", l)]
    made = lambda l: l.split(" gather(")[0]
    dedup = [l for l in gathers if "/fm.dedup/" in l]
    assert dedup and all("f32[2555904,128]{1,0" in made(l) for l in dedup), dedup


@pytest.mark.parametrize(
    "v, d, m",
    [(2**26, 9, 65536 * 39), (2**25, 31, 65536 * 11)],  # fm8_criteo.train_fmb; fm3_k30_kdd12.train_fmb_order3
    ids=["fm8", "fm3"],
)
def test_the_gather_kernel_compiles_for_the_chip_on_the_tables_own_buffer(one_chip, v, d, m):
    """``ops.pallas_gather.sweep_gather`` at the two one-chip FM cells'
    shapes: Mosaic takes it, its blocks and scratch fit VMEM, and the
    transposed view of the lane-major table is a bitcast: nothing copies or
    transposes a ``[V, D]`` buffer on the way in."""
    from fast_tffm_tpu.ops.pallas_gather import sweep_gather

    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    gather = jax.jit(lambda t, u: sweep_gather(t, u, interpret=False))
    compiled = gather.lower(sd((v, d), jnp.float32), sd((m,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    whole = re.compile(rf"f32\[({v},{d}|{d},{v})\]")
    ops = set()
    for line in text.splitlines():
        m_ = re.match(r"\s*(ROOT )?%?[\w.\-]+ = (\([^=]*\)|\S+) ([\w\-]+)\(", line)
        if m_ and whole.search(line):
            ops.add(m_.group(3))
    assert ops == {"parameter", "bitcast", "custom-call"}, ops
    assert f"f32[{v},{d}]{{0,1:T(8,128)}}" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20  # the work list and the result's padding, no table


def test_the_rows_step_keeps_its_dedup_and_its_row_operations(one_chip, monkeypatch):
    """``ffm4_criteo.train_fmb_fields``' step (2^20 rows of 157, 32,768 x 39
    ids): rows past one tile keep the rows form, whose ``fm.dedup`` is two
    sorts, the permutation gather and the segment sum on 256-lane rows, and
    whose ``fm.tail`` is one gather and two scatters, as before ISSUE 32
    (on the chip the whole compiled step is the parent's as text: PERF.md
    §6)."""
    from fast_tffm_tpu.models import FFMModel

    model = FFMModel(vocabulary_size=2**20, num_fields=39, factor_num=4)
    text, ops, asked = _step_ops(one_chip, monkeypatch, model, 32768, 39)
    assert asked == ["rows"] and "tpu_custom_call" not in text
    dedup = {k: ops["fm.dedup"][k] for k in ("sort", "gather", "scatter")}
    assert dedup == {"sort": 2, "gather": 1, "scatter": 1}
    assert "f32[1277952,256]" in text  # the segment sum's wide rows
    tail = {k: ops["fm.tail"][k] for k in ("sort", "gather", "scatter")}
    assert tail == {"sort": 0, "gather": 1, "scatter": 2}


_SHARDED = {}  # lookup form -> the compiled four-chip step: one compile a form a process


def _sharded_step(four_chips, lookup_form):
    """(compiled text, its ``memory_analysis()``, the tail rule's questions
    and answers) of ``fm16_criteo_row4.dist_train_fmb``'s step (2^27 rows of 17
    over ``{data: 1, row: 4}``, 65,536 x 39 ids, allgather lookup) compiled
    for the described ``v5e:2x2``, its tail's form asked as a TPU's rule
    answers and the kernels compiled, not interpreted.  ``lookup_form``
    ``rows``: the lookup's rule left to the CPU it runs on, which says the
    masked row gather (the program before the lookup had another form);
    ``sweep``: the rule asked as a TPU's."""
    if lookup_form in _SHARDED:
        return _SHARDED[lookup_form]
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from fast_tffm_tpu import optim
    from fast_tffm_tpu.models import FMModel
    from fast_tffm_tpu.models.base import Batch
    from fast_tffm_tpu.ops import pallas_gather, pallas_tail
    from fast_tffm_tpu.parallel import make_sharded_train_step
    from fast_tffm_tpu.parallel import train_step as ts
    from fast_tffm_tpu.trainer import init_state

    mesh = Mesh(np.array(four_chips).reshape(1, 4), ("data", "row"))
    rule, asked = optim.rows_tail_form, []
    lookup_rule = ts.shard_lookup_form
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(optim, "rows_tail_form", lambda *a, backend=None: asked.append((a, rule(*a, backend="tpu"))) or asked[-1][1])
        mp.setattr(pallas_tail, "resolve_interpret", lambda interpret: False)
        if lookup_form == "sweep":
            mp.setattr(ts, "shard_lookup_form", lambda *a, backend=None: lookup_rule(*a, backend="tpu"))
            mp.setattr(pallas_gather, "resolve_interpret", lambda interpret: False)
        model, b, n = FMModel(vocabulary_size=2**27, factor_num=16, order=2), 65536, 39
        ns = lambda spec: NamedSharding(mesh, spec)
        sd = lambda x, spec: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=ns(spec))
        state = jax.eval_shape(lambda: init_state(model, jax.random.key(0), 0.1, "element"))
        state = state._replace(
            table=sd(state.table, P("row", None)), table_opt=type(state.table_opt)(sd(state.table_opt.accum, P("row", None))),
            step=sd(state.step, P()),
        )
        batch = Batch(
            labels=jax.ShapeDtypeStruct((b,), jnp.float32), ids=jax.ShapeDtypeStruct((b, n), jnp.int32),
            vals=jax.ShapeDtypeStruct((b, n), jnp.float32), fields=jax.ShapeDtypeStruct((b, 0), jnp.int32),
            weights=jax.ShapeDtypeStruct((b,), jnp.float32),
        )
        batch = jax.tree.map(sd, batch, ts._batch_specs())
        compiled = make_sharded_train_step(model, 0.05, mesh).lower(state, batch).compile()
    _SHARDED[lookup_form] = compiled.as_text(), compiled.memory_analysis(), asked
    return _SHARDED[lookup_form]


def test_the_sharded_step_takes_the_sweep_on_the_ids_the_shard_owns(four_chips):
    """``fm16_criteo_row4.dist_train_fmb``'s step (2^27 rows of 17 over
    ``{data: 1, row: 4}``, 65,536 x 39 ids, allgather lookup) compiled for the
    described ``v5e:2x2``: the shard's tail is the Pallas sweep under
    ``fm.tail``, asked at the shard's shapes and (ISSUE 38) at the 1,284,384
    slots the tail keeps of all four chips' 2,555,904, and at those in the
    whole list's branch; each branch of the ONE conditional holds one kernel,
    in place: nothing but the kernels, and no copy, is on a
    ``f32[33554432,17]`` shard; both branches' permutation gathers read the
    gradients padded to ``f32[2555904,128]`` rows under ``fm.dedup``, the
    bounded one ``f32[1284384,128]`` of them; the global dedup is gone (one
    segment sum, the local one, and its ``[638976,128]`` rows); and no
    instruction stands under both ``fm.tail`` and ``fm.dedup``
    (``harness/scopes.py`` would count it twice)."""
    import numpy as np
    from jax.sharding import Mesh

    from fast_tffm_tpu.parallel.train_step import shard_tail_ids

    text, memory, asked = _sharded_step(four_chips, "rows")
    print(f"temporaries a chip with the masked row gather: {memory.temp_size_in_bytes} bytes")
    mesh = Mesh(np.array(four_chips).reshape(1, 4), ("data", "row"))
    b, n = 65536, 39
    bound = shard_tail_ids(mesh, b // 4 * n, 2.0)
    assert bound == 1284384
    assert sorted(asked) == [((2**25, bound, 17, 17), "sweep"), ((2**25, 2555904, 17, 17), "sweep")]
    shard = re.compile(r"f32\[(33554432,17|17,33554432)\]")
    on_shard, both, tail_calls, segment_sums, bounded_gathers = set(), [], collections.Counter(), [], 0
    branch_gathers, wide = [], []
    computation = None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(", line)
        if head:
            computation = head.group(2)
        op = re.match(r"\s*(ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not op:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        path = name.group(1).split("/") if name else []
        scopes = {c for c in path if c.startswith(("fm.tail", "fm.dedup"))}
        if len(scopes) > 1:
            both.append(line.strip()[:160])
        if op.group(3) == "custom-call" and "tpu_custom_call" in line:
            assert "fm.tail" in path and "fm.dedup" not in path and "cond" in path, path
            tail_calls[computation] += 1
        if shard.search(op.group(2)):  # the instruction's own result is shard-shaped
            on_shard.add(op.group(3))
        if op.group(3) == "scatter":
            segment_sums.append(op.group(2))
        bounded_gathers += op.group(2).startswith(f"f32[{bound},128]") and "fm.dedup" in path and path[-1] == "gather"
        if op.group(3) == "gather" and "fm.dedup" in path and "cond" in path:
            branch_gathers.append(op.group(2).split("{")[0])
        if "2555904,128]" in op.group(2) and op.group(3) != "parameter":
            wide.append((op.group(3), "/".join(path)))
    assert text.count(" conditional(") == 1 and sorted(tail_calls.values()) == [1, 1]  # one kernel a branch
    assert on_shard <= {"parameter", "bitcast", "custom-call", "get-tuple-element", "tuple", "conditional"}, on_shard
    assert bounded_gathers >= 1  # the fusion and the gather inside it
    # Both branches gather the seventeen-wide gradients padded to tile-wide
    # rows: the bounded one the rows it keeps, the whole list's all of them.
    assert sorted(set(branch_gathers)) == [f"f32[{bound},128]", "f32[2555904,128]"], branch_gathers
    assert not both, both
    # One segment sum, the local one on [638976,128] rows.  Rows of
    # [2555904,128] are made only in the branches, under fm.dedup, by the
    # pad and the whole list's gather of the tile-wide rows (a fusion, and
    # the gather, transpose and reshape inside it): no global dedup.
    assert len(segment_sums) == 1 and "638976,128]" in segment_sums[0], segment_sums
    made = {(opcode, where.split("/")[-1]) for opcode, where in wide}
    assert wide and all(re.search(r"/cond/branch_[01]_fun/fm\.dedup/", where) for _, where in wide), wide
    assert made <= {("pad", "pad"), ("fusion", "gather"), ("gather", "gather"), ("transpose", "gather"),
                    ("reshape", "gather")}, made


def test_the_anova_kernel_compiles_for_the_chip_at_the_order_3_cells_shape(one_chip):
    """``fm3_k30_kdd12.train_fmb_order3``'s interaction (ISSUE 37: 65,536 rows
    x 11 ids x k = 30, order 3), forward and backward, compiled for the
    described chip in seconds: Mosaic takes both kernels at a grid of 512 x 30
    programs on blocks of [1, 11, 128], and the layout transposes around them
    are not copies: XLA lays ``z[B, N, k]`` out batch-minor, so ``[k, N, B]``
    is a bitcast of it, in and out.  (Here for the one library load this
    file's fixture makes; the whole step at these shapes takes the compiler
    half a minute and is rehearsed by the builder, PERF.md §6.)"""
    import time

    from fast_tffm_tpu.ops.pallas_anova import anova_inter, grid_programs

    b, n, k = 65536, 11, 30
    z = jax.ShapeDtypeStruct((b, n, k), jnp.float32, sharding=one_chip)
    t = time.time()
    text = jax.jit(jax.value_and_grad(lambda z: jnp.sum(anova_inter(z, 3, False)))).lower(z).compile().as_text()
    assert time.time() - t < 30
    assert text.count("tpu_custom_call") == 2 and grid_programs(b, k) == 15360
    assert f"f32[{k},{n},{b}]" in text and not re.search(rf"f32\[{k},{n},{b}\]\S* (copy|transpose)\(", text)
