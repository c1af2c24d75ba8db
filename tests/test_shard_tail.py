"""The row shard's Adagrad tail IS the single-device step's (ISSUE 36).

``parallel.embedding.apply_shard_adagrad`` holds no Adagrad expression of its
own: it maps the gathered ids to the shard (``owned_local_ids``) and calls
``optim.sparse_adagrad_update``, whose form ``optim.rows_tail_form`` chooses
from the SHARD's shapes.  Both forms run here on the CPU mesh (the sweep
interpreted, chosen by patching the rule as ``tests/test_pallas_tail.py``
does), under both lookups, on two meshes, at the two row widths the measured
cells have (9: ``fm8_criteo``; 17: ``fm16_criteo_row4``), with either
accumulator, decayed or not:

  * directly, under ``shard_map``, against a NumPy oracle: ids BELOW and ABOVE
    the shard's range and dedup sentinels (``>= num_rows_global``, here with
    gradients that are not zero, so that a sentinel applied would show) are
    dropped; a row that several chips touched (several slots, one id) takes
    ONE Adagrad step from its summed gradient; every row nobody touched, on
    this shard or any other, comes out bit for bit;
  * through ``make_sharded_train_step`` against ``trainer.make_train_step`` on
    the same global batch: touched rows within float32 summation error (the
    chips' partial sums are added in another order than the one device's
    segment sum, and the CPU contracts the update into an FMA in one program
    and not the other), untouched rows and the vocabulary's padding bit for
    bit.

ISSUE 38: under the all-gather update a shard's tail keeps the first
``train_step.shard_tail_ids`` entries of its sorted list (the capacity the
routed update would have handed it) and takes the whole list only where it
owns more than that, counted (``count_full_tails``).  The tests at the end
run steps large enough for the bound to lie under the slot count (those above
are too small: ``capacity_for`` caps at a chip's ids there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from fast_tffm_tpu.models import Batch, FMModel
from fast_tffm_tpu.parallel import init_sharded_state, make_mesh, make_sharded_train_step
from fast_tffm_tpu.parallel.embedding import apply_shard_adagrad
from fast_tffm_tpu.trainer import init_state, make_decayed_body, make_train_step

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices (see conftest.py)"
)

_EPS = float(np.finfo(np.float32).eps)
_FORMS = pytest.mark.parametrize("form", ["rows", "sweep"])
# Row width, lazy decay, accumulator: each value of each with each of another.
_KINDS = pytest.mark.parametrize(
    "d, decay, accumulator",
    [(9, 1.0, "element"), (17, 0.9, "element"), (9, 0.9, "row"), (17, 1.0, "row")],
    ids=["d9-classic-element", "d17-decayed-element", "d9-decayed-row", "d17-classic-row"],
)


def _take_form(request, form):
    """From here on every tail traced takes ``form``; returns the shapes the
    rule was asked at, or None where the rule itself answers (on the CPU: the
    rows)."""
    return request.getfixturevalue("sweep_form") if form == "sweep" else None


# --- the shard's tail alone ----------------------------------------------

V, ROWS = 64, 4  # 16 rows a shard


def _slots(d, seed=0):
    """What four chips might hand every shard: ids of all four shards (so
    each shard sees ids below AND above its range), ids repeated as several
    chips' unique ids are (rows 3, 17 and 40 three or four times), and drop
    ids from ``V`` up whose gradients are NOT zero."""
    rng = np.random.default_rng(seed)
    ids = np.concatenate([
        rng.choice(V, size=24, replace=False), [3, 3, 3, 17, 17, 40, 40, 40],
        [V, V + 1, V + 7, V + 7, 2**31 - 1],  # sentinels, one of them the largest id there is
        rng.choice(V, size=11, replace=False),
    ]).astype(np.int32)
    return ids, rng.standard_normal((ids.shape[0], d)).astype(np.float32)


def _oracle(table, accum, ids, grads, lr, decay):
    """Adagrad, once a touched row, from its float64-summed gradient."""
    table, accum = table.astype(np.float64), accum.astype(np.float64)
    touched = np.unique(ids[ids < V])
    for r in touched:
        g = grads[ids == r].astype(np.float64).sum(axis=0)
        gg = g * g if accum.shape[1] > 1 else np.sum(g * g, keepdims=True)
        accum[r] = decay * accum[r] + gg
        table[r] = table[r] - lr * g / np.sqrt(accum[r])
    return table, accum, touched


@_FORMS
@_KINDS
def test_the_shard_tail_steps_the_rows_it_owns_once_and_drops_the_rest(request, form, d, decay, accumulator):
    rng = np.random.default_rng(1)
    table = rng.standard_normal((V, d)).astype(np.float32)
    accum = rng.uniform(0.05, 2.0, (V, d if accumulator == "element" else 1)).astype(np.float32)
    ids, grads = _slots(d)
    lr = 0.07
    asked = _take_form(request, form)
    mesh = make_mesh(1, ROWS)
    shard = P("row", None)
    tail = jax.jit(shard_map(
        lambda t, a, i, g: apply_shard_adagrad(t, a, i, g, lr, decay=decay)[:2],
        mesh=mesh, in_specs=(shard, shard, P(), P()), out_specs=(shard, shard), check_vma=False,
    ))
    got_t, got_a = (np.asarray(x) for x in tail(table, accum, ids, grads))
    if asked is not None:  # the rule was asked at the SHARD's shapes, every slot of every chip
        assert asked == [(V // ROWS, ids.shape[0], d, accum.shape[1])]
    want_t, want_a, touched = _oracle(table, accum, ids, grads, lr, decay)
    assert 3 in touched and 17 in touched and 40 in touched and len(touched) < V
    rest = np.setdiff1d(np.arange(V), touched)
    np.testing.assert_array_equal(got_t[rest], table[rest])  # nobody's rows, on every shard
    np.testing.assert_array_equal(got_a[rest], accum[rest])
    # Touched rows: |w| < 5, |lr g / sqrt(acc)| < 2; sums of up to four
    # float32 gradients, rounded once more in the update.
    np.testing.assert_allclose(got_t[touched], want_t[touched], rtol=0, atol=16 * _EPS * 5)
    np.testing.assert_allclose(got_a[touched], want_a[touched], rtol=8 * _EPS * d, atol=0)
    # A row touched by several chips took ONE step from the summed gradient:
    # stepping it once a slot would move it by another amount altogether.
    once_a_slot = table[3].astype(np.float64) - sum(
        lr * g / np.sqrt(decay * accum[3] + (g * g if accum.shape[1] > 1 else np.sum(g * g)))
        for g in grads[ids == 3].astype(np.float64)
    )
    assert np.max(np.abs(got_t[3] - once_a_slot)) > 1e-3


# --- the sharded step against the single-device step -----------------------

V_PAD = 201  # padded to 204 rows over four shards, 202 over two
B, N = 32, 6


def _batches(seed, n=2):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(0, V_PAD, size=(B, N)).astype(np.int32)
        ids[:, 0] = 5  # every chip's micro-batch touches row 5
        ids[::3, 1] = 200  # and the last shard's last row, from most chips
        out.append(Batch(
            labels=jnp.asarray(rng.integers(0, 2, size=(B,)).astype(np.float32)), ids=jnp.asarray(ids),
            vals=jnp.asarray(rng.normal(size=(B, N)).astype(np.float32)),
            fields=jnp.zeros((B, 0), jnp.int32), weights=jnp.ones((B,), jnp.float32),
        ))
    return out


@_FORMS
@_KINDS
@pytest.mark.parametrize("lookup", ["allgather", "alltoall"])
@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)], ids=lambda s: f"data{s[0]}xrow{s[1]}")
def test_the_sharded_step_is_the_single_device_step(request, mesh_shape, lookup, form, d, decay, accumulator):
    model = FMModel(vocabulary_size=V_PAD, factor_num=d - 1, order=2, factor_lambda=1e-4, bias_lambda=1e-4)
    batches = _batches(seed=d)
    lr, key = 0.1, jax.random.key(11)

    ref = init_state(model, key, 0.1, accumulator)
    ref_step = make_train_step(model, lr, decay=decay, body=make_decayed_body(decay) if decay != 1.0 else None)
    ref_losses = []
    for b in batches:  # the one device's rows form, traced before the rule is patched
        ref, loss = ref_step(ref, b)
        ref_losses.append(float(loss))

    asked = _take_form(request, form)
    mesh = make_mesh(*mesh_shape)
    got = init_sharded_state(model, mesh, key, 0.1, accumulator)
    first_t, first_a = np.asarray(got.table), np.asarray(got.table_opt.accum)  # the state is donated
    step = make_sharded_train_step(model, lr, mesh, lookup=lookup, accumulator=accumulator, adagrad_decay=decay)
    for b, want in zip(batches, ref_losses):
        got, loss = step(got, b)
        np.testing.assert_allclose(float(loss), want, rtol=1e-6)
    if asked is not None:
        assert asked and {a[0] for a in asked} == {got.table.shape[0] // mesh_shape[1]}  # the shard's rows

    touched = np.unique(np.concatenate([np.asarray(b.ids).ravel() for b in batches]))
    rest = np.setdiff1d(np.arange(got.table.shape[0]), touched)  # the padding's rows among them
    assert 5 in touched and 200 in touched and len(rest) > 20 and got.table.shape[0] > V_PAD
    t, a = np.asarray(got.table), np.asarray(got.table_opt.accum)
    np.testing.assert_array_equal(t[rest], first_t[rest])
    np.testing.assert_array_equal(a[rest], first_a[rest])
    # |w| <= 0.01 at the start and a step moves it by less than lr = 0.1.
    np.testing.assert_allclose(t[touched], np.asarray(ref.table)[touched], rtol=0, atol=32 * _EPS * 0.1)
    np.testing.assert_allclose(a[touched], np.asarray(ref.table_opt.accum)[touched], rtol=1e-5, atol=0)


# --- ISSUE 38: the tail takes its own share of the exchanged slots ---------

V_BIG = 4 * 4096  # 4,096 rows a shard over four, 8,192 over two
B_BIG, N_BIG = 128, 8  # 256 ids a chip on four chips
# Mesh, factor: the bound is 4 x capacity_for(256, 4, 2.0) = 672 of 1,024 slots;
# a row axis of 2 bounds nothing at factor 2, so it runs at 1: 4 x 184 = 736.
_BOUNDED = pytest.mark.parametrize(
    "mesh_shape, factor, bound", [((1, 4), 2.0, 672), ((2, 2), 1.0, 736)], ids=["data1xrow4", "data2xrow2"]
)
_UNBOUNDED = 4.0  # capacity_for caps at a chip's ids: the parent's program


def _big_batches(seed, n=2, high=V_BIG):
    rng = np.random.default_rng(seed)
    return [Batch(
        labels=jnp.asarray(rng.integers(0, 2, size=(B_BIG,)).astype(np.float32)),
        ids=jnp.asarray(rng.integers(0, high, size=(B_BIG, N_BIG)).astype(np.int32)),
        vals=jnp.asarray(rng.normal(size=(B_BIG, N_BIG)).astype(np.float32)),
        fields=jnp.zeros((B_BIG, 0), jnp.int32), weights=jnp.ones((B_BIG,), jnp.float32),
    ) for _ in range(n)]


def _run(model, mesh, batches, **kw):
    state = init_sharded_state(model, mesh, jax.random.key(11), 0.1, "element")
    step = make_sharded_train_step(model, 0.1, mesh, count_full_tails=True, **kw)
    full = []
    for b in batches:
        state, _, whole = step(state, b)
        full.append(int(whole))
    return np.asarray(state.table), np.asarray(state.table_opt.accum), full


def _primitives(jaxpr, out=None):
    """(primitive name, equation) of every equation, sub-jaxprs included."""
    from fast_tffm_tpu.parallel.exchange import _sub_jaxprs

    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, eqn))
        for sub in _sub_jaxprs(eqn):
            _primitives(sub, out)
    return out


@_FORMS
@_BOUNDED
def test_the_bounded_tail_is_bitwise_the_unbounded_tail(request, mesh_shape, factor, bound, form):
    """(a) Uniform ids: every shard owns about a quarter (a half) of the 1,024
    slots, under its bound; what the bound cuts off is drop ids alone, so table
    and accumulator are the whole list's bit for bit, and no shard counts."""
    from fast_tffm_tpu.parallel.train_step import shard_tail_ids

    model = FMModel(vocabulary_size=V_BIG, factor_num=8, order=2, factor_lambda=1e-4, bias_lambda=1e-4)
    mesh, batches = make_mesh(*mesh_shape), _big_batches(3)
    assert shard_tail_ids(mesh, B_BIG * N_BIG // 4, factor) == bound < B_BIG * N_BIG
    assert shard_tail_ids(mesh, B_BIG * N_BIG // 4, _UNBOUNDED) == B_BIG * N_BIG
    asked = _take_form(request, form)
    want_t, want_a, none = _run(model, mesh, batches, capacity_factor=_UNBOUNDED)
    if asked is not None:
        assert {a[1] for a in asked} == {B_BIG * N_BIG}
        del asked[:]
    got_t, got_a, full = _run(model, mesh, batches, capacity_factor=factor)
    if asked is not None:  # the bounded branch and the whole list's, each asked at its own count
        assert {a[1] for a in asked} == {bound, B_BIG * N_BIG}
    assert full == none == [0, 0]
    assert np.any(got_t != np.asarray(init_sharded_state(model, mesh, jax.random.key(11), 0.1, "element").table))
    np.testing.assert_array_equal(got_t, want_t)
    np.testing.assert_array_equal(got_a, want_a)


@_FORMS
@_BOUNDED
def test_a_shard_that_owns_more_than_its_bound_takes_the_whole_list_and_is_counted(request, mesh_shape, factor, bound, form):
    """(b) Every id in shard 0's range: it owns all four chips' slots (about
    990 distinct of 1,024, over the bound), takes the whole list and is the
    one shard counted, a step; the others own nothing and take the bounded
    branch.  The state is still the single-device step's."""
    model = FMModel(vocabulary_size=V_BIG, factor_num=8, order=2, factor_lambda=1e-4, bias_lambda=1e-4)
    batches = _big_batches(5, high=4096)
    ref = init_state(model, jax.random.key(11), 0.1, "element")
    ref_step = make_train_step(model, 0.1)
    for b in batches:  # the one device's rows form, traced before the rule is patched
        ref, _ = ref_step(ref, b)
    owned = [sum(len(np.unique(c)) for c in np.split(np.asarray(b.ids).ravel(), 4)) for b in batches]
    assert min(owned) > bound
    _take_form(request, form)
    mesh = make_mesh(*mesh_shape)
    got_t, got_a, full = _run(model, mesh, batches, capacity_factor=factor)
    assert full == [1, 1]  # that shard alone, whatever the data axis replicates
    touched = np.unique(np.concatenate([np.asarray(b.ids).ravel() for b in batches]))
    rest = np.setdiff1d(np.arange(V_BIG), touched)
    first = init_state(model, jax.random.key(11), 0.1, "element")
    np.testing.assert_array_equal(got_t[rest], np.asarray(first.table)[rest])
    np.testing.assert_allclose(got_t[touched], np.asarray(ref.table)[touched], rtol=0, atol=32 * _EPS * 0.1)
    np.testing.assert_allclose(got_a[touched], np.asarray(ref.table_opt.accum)[touched], rtol=1e-5, atol=0)


@pytest.mark.parametrize(
    "mesh_shape, kw",
    [((2, 2), dict(capacity_factor=2.0)), ((1, 4), dict(lookup="alltoall")), ((4, 1), {}), ((1, 4), dict(capacity_factor=_UNBOUNDED))],
    ids=["row2-factor2", "alltoall", "one-row-shard", "factor4"],
)
def test_where_the_bound_is_not_under_the_slots_there_is_no_conditional_and_no_count(mesh_shape, kw):
    """(c) A trace-time branch, as ``test_impossible_overflow_skips_cond`` pins
    for the lookup: the traced step holds no ``cond``, counts nothing (no
    reduction to an int32 scalar) and its psums carry the two scalars of the
    loss alone, asked for the counter or not; the counter is a constant 0."""
    model = FMModel(vocabulary_size=V_BIG, factor_num=8, order=2)
    mesh, (b,) = make_mesh(*mesh_shape), _big_batches(7, n=1)
    state = init_sharded_state(model, mesh, jax.random.key(0), 0.1, "element")
    step = make_sharded_train_step(model, 0.1, mesh, count_full_tails=True, **kw)
    eqns = _primitives(jax.make_jaxpr(step)(state, b).jaxpr)
    names = [n for n, _ in eqns]
    assert "cond" not in names and "shard_map" in names
    counts = [e for n, e in eqns if n == "reduce_sum" and e.outvars[0].aval.shape == () and e.outvars[0].aval.dtype == jnp.int32]
    assert not counts
    scalars = lambda eqns: sorted(str(v.aval.dtype) for n, e in eqns if n.startswith("psum") for v in e.invars if v.aval.shape == ())
    # The weights' sum and the loss (and the routed update's own overflow flag).
    assert scalars(eqns) == ["float32", "float32"] + ["int32"] * ("lookup" in kw)
    _, _, whole = step(state, b)
    assert int(whole) == 0
    # ... and the step that is bounded holds exactly those: one cond, and the flag beside the loss.
    if "lookup" not in kw and mesh_shape == (1, 4):
        eqns = _primitives(jax.make_jaxpr(make_sharded_train_step(model, 0.1, mesh, count_full_tails=True))(state, b).jaxpr)
        assert [n for n, _ in eqns].count("cond") == 1
        assert scalars(eqns) == ["float32", "float32", "int32"]


@pytest.mark.parametrize("mesh_shape, factor", [((1, 4), 2.0), ((2, 2), 1.0), ((2, 2), 2.0), ((1, 4), 0.5)])
def test_the_tail_is_asked_at_the_same_slots_under_both_lookups(monkeypatch, mesh_shape, factor):
    """(d) ``shard_tail_ids`` does not ask which exchange fed the tail: data x
    row x the routed capacity, never more than the slots handed in; and it is
    the ``m`` ``rows_tail_form`` is asked at as either step is traced (the
    all-gather's whole-list branch asks at every chip's slots besides)."""
    from fast_tffm_tpu import optim
    from fast_tffm_tpu.parallel.alltoall import capacity_for
    from fast_tffm_tpu.parallel.train_step import shard_tail_ids

    rule, asked = optim.rows_tail_form, []
    monkeypatch.setattr(optim, "rows_tail_form", lambda *a, **k: asked.append(a[1]) or rule(*a, **k))
    model = FMModel(vocabulary_size=V_BIG, factor_num=8, order=2)
    mesh, (b,) = make_mesh(*mesh_shape), _big_batches(7, n=1)
    per_chip, slots = B_BIG * N_BIG // 4, B_BIG * N_BIG
    bound = shard_tail_ids(mesh, per_chip, factor)
    assert bound == 4 * capacity_for(per_chip, mesh_shape[1], factor) <= slots
    state = init_sharded_state(model, mesh, jax.random.key(0), 0.1, "element")
    for lookup, want in (("allgather", {bound, slots}), ("alltoall", {bound})):
        del asked[:]
        jax.make_jaxpr(make_sharded_train_step(model, 0.1, mesh, lookup=lookup, capacity_factor=factor))(state, b)
        assert set(asked) == want, (lookup, asked)


@_BOUNDED
def test_the_bounded_step_exchanges_the_unbounded_steps_bytes_and_one_flag(mesh_shape, factor, bound):
    """(e) No collective moves a row more or less: the bounded step's
    ``exchange_bytes`` are the whole list's plus the flag's int32 on the loss's
    psum (4 (n - 1) / n of its 4 bytes), and nothing where nobody counts."""
    from fast_tffm_tpu.parallel.exchange import exchange_bytes

    model = FMModel(vocabulary_size=V_BIG, factor_num=8, order=2)
    mesh, (b,) = make_mesh(*mesh_shape), _big_batches(7, n=1)
    state = init_sharded_state(model, mesh, jax.random.key(0), 0.1, "element")
    parent = exchange_bytes(make_sharded_train_step(model, 0.1, mesh, capacity_factor=_UNBOUNDED), state, b)
    assert exchange_bytes(make_sharded_train_step(model, 0.1, mesh, capacity_factor=factor), state, b) == parent
    counted = make_sharded_train_step(model, 0.1, mesh, capacity_factor=factor, count_full_tails=True)
    assert exchange_bytes(counted, state, b) == parent + 4 * 3 * 4 // 4


@pytest.mark.parametrize("d, what", [(9, "sort operands"), (17, "row gather"), (17, "dedup_rows")])
def test_keep_cuts_the_sorted_list_and_nothing_else(d, what):
    """``keep`` is honoured right after the ONE sort, in each of the tail's
    three fronts: what comes back is the first ``keep`` entries of what comes
    back without it (for ``dedup_rows``: while the kept prefix holds every id
    under ``num_rows``), and ``None`` is the call without it."""
    from fast_tffm_tpu import optim

    rng = np.random.default_rng(d)
    m, keep, rows = 96, 40, 50
    ids = rng.integers(0, rows, size=m).astype(np.int32)
    ids[rng.permutation(m)[: m - 30]] = rows + 5  # 30 ids of the table, 66 drop ids
    g = rng.standard_normal((m, d)).astype(np.float32)
    sid, order = optim.sort_ids(ids, rows)
    np.testing.assert_array_equal(np.asarray(sid)[30:], rows)  # the drop ids sort last, clamped
    for got, want in zip(optim.sort_ids(ids, rows, keep), (sid, order)):
        np.testing.assert_array_equal(got, want[:keep])
    if what == "dedup_rows":
        (u, s), (uk, sk) = optim.dedup_rows(ids, g, rows), optim.dedup_rows(ids, g, rows, keep)
        n = len(np.unique(ids[ids < rows]))
        assert uk.shape == (keep,) and sk.shape == (keep, d)
        np.testing.assert_array_equal(uk[:n], u[:n])
        np.testing.assert_array_equal(sk[:n], s[:n])
        assert np.all(np.asarray(uk[n:]) >= rows) and np.all(np.diff(np.asarray(uk)) > 0)
        return
    assert optim.occurrences_permutation(d, m) == what
    (s0, g0), (sk, gk) = optim.occurrences_by_id(ids, g, rows), optim.occurrences_by_id(ids, g, rows, keep)
    np.testing.assert_array_equal(sk, s0[:keep])
    np.testing.assert_array_equal(gk, g0[:, :keep])


# --- the lookup reads the shard's own share of the slots ------------------
#
# ``embedding.sharded_gather`` in its ``sweep`` form sorts the slots its row
# peers hand it once, sweeps the kept prefix from the shard's ``table.T`` (the
# kernel interpreted here) and brings the rows back through a zero row; where
# a shard owns more than its bound it reads the whole list as the masked row
# gather does.  ``shard_lookup_form`` says ``rows`` on the CPU, so the tests
# name the form.

V_LOOK, B_LOOK, N_LOOK = 4 * 3000, 64, 10  # 3,000 rows a shard: a block cut short by the end


def _lookup(mesh, table, ids, form, bound):
    """(rows every chip gets back, [row] the flag each shard returned)."""
    from fast_tffm_tpu.parallel.embedding import sharded_gather

    def body(t, i):
        rows, whole = sharded_gather(t, i, bound=bound, form=form)
        return rows, jnp.reshape(jnp.zeros((), jnp.int32) if whole is None else whole, (1,))

    both = P(("data", "row"))
    fn = shard_map(body, mesh=mesh, in_specs=(P("row", None), both), out_specs=(both, P(("data", "row"))), check_vma=False)
    rows, flags = jax.jit(fn)(table, ids)
    return np.asarray(rows), np.asarray(flags)


def _look_ids(rng, high=V_LOOK):
    ids = rng.integers(0, high, size=(B_LOOK, N_LOOK)).astype(np.int32)
    # Ids outside [0, V) read zeros in every form, and the first and last
    # row of every shard.
    ids.reshape(-1)[:12] = [-1, -(2**31), V_LOOK, 2**31 - 1, 0, 2999, 3000, 5999, 6000, 8999, 9000, V_LOOK - 1]
    return ids


@pytest.mark.parametrize("d", [9, 17])
def test_the_swept_lookup_is_the_masked_gather_bit_for_bit(d):
    """A 1 x 4 row mesh, uniform ids, a bound of half the 640 slots a shard is
    handed: every shard takes the bounded branch, and the rows every chip
    gets back are the masked row gather's bit for bit (values of sixteen
    decades, every bfloat16 part of them non-zero)."""
    rng = np.random.default_rng(d)
    x = rng.standard_normal((V_LOOK, d)) * 10.0 ** rng.integers(-8, 8, (V_LOOK, d))
    table = jnp.asarray(x.astype(np.float32))
    ids = jnp.asarray(_look_ids(rng))
    mesh = make_mesh(1, 4)
    want, no_flags = _lookup(mesh, table, ids, "rows", None)
    got, flags = _lookup(mesh, table, ids, "sweep", 320)
    assert want.shape == (B_LOOK, N_LOOK, d)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    np.testing.assert_array_equal(want.reshape(-1, d)[:4], 0.0)  # the ids outside [0, V)
    np.testing.assert_array_equal(want.reshape(-1, d)[4:], np.asarray(table)[np.asarray(ids).reshape(-1)[4:]])
    assert flags.tolist() == [0, 0, 0, 0] and no_flags.tolist() == [0, 0, 0, 0]


def test_a_shard_that_owns_more_than_its_lookup_bound_reads_the_whole_list():
    """Every id in shard 0's range: shard 0 owns all 640 slots, over its bound
    of 320, and reads the whole list (counted: its flag alone is 1); the
    others own none and take the bounded branch.  The rows are the same."""
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.standard_normal((V_LOOK, 17)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 3000, size=(B_LOOK, N_LOOK)).astype(np.int32))
    mesh = make_mesh(1, 4)
    want, _ = _lookup(mesh, table, ids, "rows", None)
    got, flags = _lookup(mesh, table, ids, "sweep", 320)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    assert flags.tolist() == [1, 0, 0, 0]


@pytest.mark.parametrize("skewed", [False, True], ids=["uniform", "skewed"])
def test_the_step_counts_the_chips_whose_lookup_read_the_whole_list(monkeypatch, skewed):
    """Through ``make_sharded_train_step`` with the rule saying ``sweep`` (as
    a TPU's does at ``fm16_criteo_row4``'s shapes): the step's state and loss
    are the masked lookup's bit for bit, and ``count_full_lookups`` returns
    after the tail's count the chips whose lookup took the whole list: 0
    under uniform ids, 1 a step (shard 0) where every id is shard 0's."""
    import fast_tffm_tpu.parallel.train_step as ts

    model = FMModel(vocabulary_size=V_BIG, factor_num=16, order=2, factor_lambda=1e-4, bias_lambda=1e-4)
    mesh, batches = make_mesh(1, 4), _big_batches(9, high=4096 if skewed else V_BIG)

    def run():
        state = init_sharded_state(model, mesh, jax.random.key(11), 0.1, "element")
        step = make_sharded_train_step(model, 0.1, mesh, count_full_tails=True, count_full_lookups=True)
        out = []
        for b in batches:
            state, loss, tail, lookup = step(state, b)
            out.append((float(loss), int(tail), int(lookup)))
        return np.asarray(state.table), np.asarray(state.table_opt.accum), out

    want_t, want_a, want = run()
    asked = []
    monkeypatch.setattr(ts, "shard_lookup_form", lambda *a, **k: asked.append(a) or "sweep")
    got_t, got_a, got = run()
    assert asked == [(V_BIG // 4, B_BIG * N_BIG, 17, 672)]  # the shard's rows, its row peers' slots, the tail's bound
    assert [(l, t) for l, t, _ in got] == [(l, t) for l, t, _ in want]
    assert [n for *_, n in want] == [0, 0] and [n for *_, n in got] == ([1, 1] if skewed else [0, 0])
    np.testing.assert_array_equal(got_t, want_t)
    np.testing.assert_array_equal(got_a, want_a)


@pytest.mark.parametrize(
    "mesh_shape, kw",
    [((4, 1), {}), ((1, 1), {}), ((1, 4), dict(table_layout="packed")),
     ((1, 4), dict(table_layout="packed", accumulator="fused"))],
    ids=["one-row-shard", "one-chip-mesh", "packed", "fused"],
)
def test_one_row_shard_and_the_packed_layouts_keep_the_row_gather(monkeypatch, mesh_shape, kw):
    """Where the rule would say ``sweep`` for every shape it is asked at, a
    mesh of one row shard and the packed layouts never ask it: their steps
    hold no kernel and no ``cond`` from the lookup, and no flag beside the
    loss."""
    import fast_tffm_tpu.parallel.train_step as ts

    asked = []
    monkeypatch.setattr(ts, "shard_lookup_form", lambda *a, **k: asked.append(a) or "sweep")
    model = FMModel(vocabulary_size=V_BIG, factor_num=16, order=2)
    mesh, (b,) = make_mesh(*mesh_shape), _big_batches(7, n=1)
    state = init_sharded_state(
        model, mesh, jax.random.key(0), 0.1, kw.get("accumulator", "element"), kw.get("table_layout", "rows")
    )
    step = make_sharded_train_step(model, 0.1, mesh, count_full_lookups=True, **kw)
    names = [n for n, _ in _primitives(jax.make_jaxpr(step)(state, b).jaxpr)]
    assert asked == [] and "pallas_call" not in names
    assert int(step(state, b)[2]) == 0


def test_the_lookup_takes_the_sweep_on_a_tpu_where_the_bound_cuts_the_list():
    """``shard_lookup_form`` at ``fm16_criteo_row4``'s shard (2^25 rows of 17,
    2,555,904 slots, bound 1,284,384) and around it."""
    from fast_tffm_tpu.parallel.embedding import shard_lookup_form

    shard, m, bound = 2**25, 65536 * 39, 1284384
    assert shard_lookup_form(shard, m, 17, bound, backend="tpu") == "sweep"
    assert shard_lookup_form(shard, m, 17, bound, backend="cpu") == "rows"
    assert shard_lookup_form(shard, m, 17, m, backend="tpu") == "rows"  # the bound cuts nothing
    assert shard_lookup_form(shard, m, 17, None, backend="tpu") == "rows"
    assert shard_lookup_form(shard, m, 128, bound, backend="tpu") == "rows"  # a row-major table
    assert shard_lookup_form(shard, 40 * m, 17, 20 * m, backend="tpu") == "rows"  # past the scalar memory
