"""The swept forward gather (ISSUE 40) against ``table[ids]``.

Runs the kernel in the Pallas interpreter on the CPU mesh (resolve
auto-detects the backend); its compile for the chip at the cells' shapes is
tests/test_pallas_tail_chip_compile.py's and its chip readings are
PERF.md's.

The contract is equality BIT FOR BIT: every output column of the kernel's
contraction holds exactly one non-zero product, of 1.0 with one of three
bfloat16 parts that sum back to the float32 value, so nothing is rounded;
and ``trainer.gather_rows`` returns the same ``[B, N, D]`` array in either
form, so a train step's state and losses are the same bits whichever it
takes.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu import trainer as tr
from fast_tffm_tpu.models import Batch, FMModel
from fast_tffm_tpu.ops.pallas_gather import sweep_gather, sweep_gather_items
from fast_tffm_tpu.optim import sort_ids


def _table(rng, v, d):
    """Values of mixed magnitude, all three bfloat16 parts non-zero: 24
    significant bits over sixteen decades."""
    x = rng.standard_normal((v, d)) * 10.0 ** rng.integers(-8, 8, (v, d))
    return jnp.asarray(x.astype(np.float32))


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _assert_same_bits(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("d", [5, 8, 9])
def test_the_sweep_and_the_way_back_are_the_row_gather_bit_for_bit(d):
    """``gather_rows`` in its sweep form against ``table[ids]`` over several
    blocks (the last one cut short by the table's end): repeated ids, the
    first and the last row, ids on both sides of every block's and tile's
    edge, and ``m`` no multiple of the chunk.  At the widths ``gather_form``
    can choose the sweep for; the kernel alone is held to rows of 17 and 31
    below."""
    rng = np.random.default_rng(d)
    v, b, n = 20000, 67, 13  # 871 ids: three chunks and a part
    table = _table(rng, v, d)
    ids = rng.integers(0, v, (b, n)).astype(np.int32)
    edges = [0, v - 1, 0, v - 1, 127, 128, 1023, 1024, 4095, 4096, 5119, 5120, 8191, 8192, 16383, 16384]
    ids.reshape(-1)[: len(edges)] = edges
    ids[-1, :] = ids[0, :]  # a row of repeats
    got = jax.jit(functools.partial(tr.gather_rows, form="sweep"))(table, jnp.asarray(ids))
    _assert_same_bits(got, table[jnp.asarray(ids)])
    assert got.shape == (b, n, d)


@pytest.mark.parametrize(
    "name, v, d, m, block_lanes",
    [
        ("shorter_than_a_block", 100, 9, 300, None),  # one block of 128 lanes, 28 of them past the table
        ("one_id", 5000, 9, 1, 1024),
        ("every_row_twice", 640, 17, 1280, 256),
        ("blocks_without_ids", 60000, 9, 40, 1024),  # most blocks are never read
        ("a_chunk_over_many_blocks", 40000, 31, 256, 1024),
        ("a_block_under_many_chunks", 512, 9, 4000, 512),
    ],
    ids=lambda x: x if isinstance(x, str) else None,
)
def test_sweep_gather_on_block_and_chunk_edges(name, v, d, m, block_lanes):
    rng = np.random.default_rng(len(name))
    table = _table(rng, v, d)
    if name == "every_row_twice":
        ids = np.repeat(np.arange(v, dtype=np.int32), 2)
    else:
        ids = np.sort(rng.integers(0, v, m).astype(np.int32))
    got = jax.jit(lambda t, s: sweep_gather(t, s, block_lanes=block_lanes))(table, jnp.asarray(ids))
    _assert_same_bits(got, table[jnp.asarray(ids)].T)


def test_ids_outside_the_table_read_what_the_row_gather_reads():
    """``table[ids]`` counts a negative id from the end and brings what is
    still outside to the nearest row; the sweep form brings its ids there
    before it sorts them."""
    v, d = 300, 9
    table = _table(np.random.default_rng(3), v, d)
    big = np.iinfo(np.int32)
    ids = jnp.asarray([[0, -1, -v, -v - 1, -v - 5, v - 1, v, v + 100, big.max, big.min, 17, -17]], jnp.int32)
    _assert_same_bits(tr.gather_rows(table, ids, form="sweep"), table[ids])
    _assert_same_bits(tr.gather_rows(table, ids, form="rows"), table[ids])


def test_a_value_is_copied_whatever_its_parts():
    """Powers of two (one part), 16-bit significands (two), the largest
    floats, small ones (down to 1e-30: under about 1e-33 the last part is
    subnormal and flushed, the kernel's stated limit) and zero."""
    v, d = 256, 9
    col = np.array([1.0, -2.0**-20, 1.0 + 2.0**-15, 3.0e38, -1.2e-30, 0.0, 1.0 + 2.0**-23, -(2.0**100) * (1 + 2.0**-9), 123456.789], np.float32)
    table = jnp.asarray(np.tile(col, (v, 1)) * (1 + np.arange(v, dtype=np.float32)[:, None] * 2.0**-12))
    ids = jnp.asarray(np.arange(v, dtype=np.int32)[::-1])
    sid, _ = sort_ids(ids, v)
    _assert_same_bits(sweep_gather(table, sid), table[sid].T)


def test_a_non_finite_value_reaches_its_groups_rows_in_the_same_chunk_and_no_further():
    """The kernel's stated limit, pinned so that a later change sees it: the
    contraction multiplies every value of a 128-row group by the one-hot's 0
    or 1, so an ``inf`` makes NaN of the SAME column of the group's other
    rows that the chunk reads (and of itself: its parts are inf, NaN, NaN);
    other columns, other groups and -0.0's sign aside, every value is
    ``table[ids]``'s bits.  XLA's row gather keeps an ``inf`` to its row."""
    v, d = 512, 9
    table = np.asarray(_table(np.random.default_rng(7), v, d)).copy()
    table[130, 3] = np.inf
    table[300, 0] = -0.0
    ids = jnp.asarray([5, 128, 130, 131, 255, 256, 300], jnp.int32)
    got = np.asarray(sweep_gather(jnp.asarray(table), ids)).T
    want = table[np.asarray(ids)]
    hit = np.zeros_like(want, bool)
    hit[1:5, 3] = True  # rows 128, 130, 131, 255: group 1, column 3
    assert np.isnan(got[hit]).all()
    assert _bits(got[6, 0]) == 0 and _bits(want[6, 0]) == 0x80000000  # -0.0 comes back as 0.0
    hit[6, 0] = True
    np.testing.assert_array_equal(_bits(got[~hit]), _bits(want[~hit]))


def _cell_shapes(config):
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", config + ".json")
    with open(path) as f:
        c = json.load(f)
    train = c["ini"]["Train"]
    return c["vocabulary_size"], train["batch_size"] * train["max_nnz"], c.get("row_dim", 1 + c["factor_num"])


@pytest.mark.parametrize(
    "shapes, backend, form",
    [
        ((2**26, 65536 * 39, 9), "tpu", "sweep"),  # fm8_criteo.train_fmb
        ((2**26, 65536 * 39, 9), "cpu", "rows"),  # no kernel interpreted inside a step
        ((2**26, 512 * 39, 9), "tpu", "rows"),  # the serving cell's largest flush: 7.7 ms of table against 0.5 of rows
        ((2**26, 64 * 39, 9), "tpu", "rows"),
        ((2**20, 32768 * 39, 128), "tpu", "rows"),  # a whole tile a row: row-major, a row is one descriptor
        ((2**20, 32768 * 39, 157), "tpu", "rows"),
        ((2**26, 20_000_000, 9), "tpu", "rows"),  # the work list would not fit the scalar memory
        (("fm8_criteo",), "tpu", "sweep"),
        (("ffm4_criteo",), "tpu", "rows"),
        (("fm3_k30_kdd12",), "tpu", "rows"),  # 2^25 rows of 31 under 720,896 ids: the chip read 30.3 ms against 28.1
        ((2**25, 65536 * 39, 17), "tpu", "rows"),  # a k = 16 table or row shard: the chip read 114 ms against 78.7
        ((2**26, 65536 * 39, 16), "tpu", "rows"),  # a 17-operand sort back: no reading covers it
        ((2**26, 65536 * 39, 5), "tpu", "rows"),  # one sublane tile: the row reads are cheaper than the ids' way round
    ],
    ids=[
        "fm8_on_tpu", "cpu", "serve_flush_512", "serve_flush_64", "d128", "d157", "work_list_too_long",
        "cell_fm8_criteo", "cell_ffm4_criteo", "cell_fm3_k30_kdd12", "d17_under_2555904", "d16", "d5",
    ],
)
def test_the_form_is_chosen_from_shapes_and_backend(shapes, backend, form):
    if isinstance(shapes[0], str):
        shapes = _cell_shapes(*shapes)
    assert tr.gather_form(*shapes, backend=backend) == form
    if backend == "cpu":  # what this suite's steps get when nobody says
        assert tr.gather_form(*shapes) == "rows"


def test_the_profile_and_the_start_up_line_say_the_form_and_the_grid():
    v, m, d = 2**26, 65536 * 39, 9
    assert tr.gather_profile(v, m, d, "rows") == {"gather_form": "rows", "gather_items": None}
    items = 2**26 // 8192 + m // 256  # every block once and every chunk once
    assert sweep_gather_items(v, d, m) == items
    assert tr.gather_profile(v, m, d, "sweep") == {"gather_form": "sweep", "gather_items": items}
    assert tr.describe_gather(v, m, d, "sweep").startswith(f"pallas sweep of table.T ({items} grid items a step; ")
    assert "as sort operands, row width 9" in tr.describe_gather(v, m, d, "sweep")
    assert tr.describe_gather(v, m, d, "rows") == f"xla row gather ({m} rows of 9 a step)"


@pytest.mark.parametrize("k", [8, 4], ids=["d9", "d5"])
def test_three_train_steps_are_the_same_bits_in_either_form(k):
    """``train_step_body`` with the sweep forced against the default step:
    the interaction, the loss, the dedup and the tail see the same
    ``[B, N, D]`` array, so state and losses are equal bit for bit."""
    v, b, n = 3000, 32, 7
    model = FMModel(vocabulary_size=v, factor_num=k, order=2)
    rng = np.random.default_rng(k)
    swept = functools.partial(tr.gather_rows, form="sweep")
    steps = {
        "rows": jax.jit(lambda st, bt: tr.train_step_body(model, 0.05, st, bt)),
        "sweep": jax.jit(lambda st, bt: tr.train_step_body(model, 0.05, st, bt, gather=swept)),
    }
    states = {f: tr.init_state(model, jax.random.key(1), 0.1, "element") for f in steps}
    for _ in range(3):
        batch = Batch(
            labels=jnp.asarray(rng.integers(0, 2, b), jnp.float32),
            ids=jnp.asarray(rng.integers(0, v, (b, n)), jnp.int32),
            vals=jnp.asarray(rng.uniform(0.5, 1.5, (b, n)), jnp.float32),
            fields=jnp.zeros((b, 0), jnp.int32),
            weights=jnp.ones((b,), jnp.float32),
        )
        losses = {}
        for f, step in steps.items():
            states[f], losses[f] = step(states[f], batch)
        _assert_same_bits(losses["sweep"], losses["rows"])
    _assert_same_bits(states["sweep"].table, states["rows"].table)
    _assert_same_bits(states["sweep"].table_opt.accum, states["rows"].table_opt.accum)
