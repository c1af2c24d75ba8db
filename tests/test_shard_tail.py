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
        lambda t, a, i, g: apply_shard_adagrad(t, a, i, g, lr, decay=decay),
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
