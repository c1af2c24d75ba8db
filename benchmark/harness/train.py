"""The train window, for ``train`` and ``dist_train`` mixes alike: the
program's own entry point (``training.train`` / ``training.dist_train``, what
``fast_tffm.py`` calls) runs in this process on FMB input made from the seed.

The program fetches the losses every ``log_every`` steps, a host wait on the
device, and then calls ``log``.  Those calls are the sync boundaries: the
window opens at the first one at or after ``warm_steps`` and closes at the
first one ``--seconds`` later, so it holds whole steps only.  The same compiled
step and state serve steps 1-3, which the reference follows, and the window.

Where the cell's model has dense leaves (``models/__init__.py``) the check
reads ``state.dense`` back too and compares it apart from the table's two
leaves: a perceptron through the MXU and a gathered row through the VPU do not
agree with float32 to the same digits, and one worst-leaf number would force
the table's limit up to the perceptron's.

The check reads each of steps 1-3 through a ``StepView``: the state, the loss
and the LOGICAL rows of logical ids, read as the run lays out and tiers its
table (``row_reader``): a resident or row-sharded ``[V, D]`` table, the packed
``[V/P, 128]`` tiles, or the tiered store's device hot tier over its host cold
store.
"""

from __future__ import annotations

import gc
import os
import sys
import time
import traceback
from functools import partial
from typing import Callable, NamedTuple

import numpy as np

from . import cells, common, gen, readers, reference
from .models import dense_leaves

CHECK_STEPS = 3
DENSE_NUMBERS = ("dense_grad1_norm_gap", "dense_delta3_norm_gap")


class _WindowClosed(Exception):
    """Raised from ``step_hook`` to end the run without its closing save."""


class SyncWindow:
    """The window between two sync boundaries.  ``boundary(now, step, loss)``
    is called at each of the program's loss fetches; the window opens at the
    first one at or after ``warm_steps`` and closes at the first one
    ``seconds`` or more later.  Steps between boundaries are never counted."""

    def __init__(self, seconds: float, warm_steps: int, log_every: int):
        self.seconds, self.warm_steps, self.log_every = seconds, warm_steps, log_every
        self.open = self.close = None  # (time, step)
        self.bad_steps = 0

    def boundary(self, now: float, step: int, loss: float) -> str | None:
        """'open' / 'close' when this boundary is that edge, else None."""
        if self.open is None:
            if step >= self.warm_steps:
                self.open = (now, step)
                return "open"
        elif self.close is None:
            if not np.isfinite(loss):
                self.bad_steps += self.log_every
            if now - self.open[0] >= self.seconds:
                self.close = (now, step)
                return "close"
        return None

    @property
    def steps(self) -> int:
        return self.close[1] - self.open[1]

    def rate(self, examples_per_step: int, chips: int) -> float:
        return self.steps * examples_per_step / (self.close[0] - self.open[0]) / chips


def run(cell, seed, seconds, do_trace, t_start, require_chip=True, workroot=cells.CHECKOUT, keep_events=None):
    import jax

    from fast_tffm_tpu import training

    device = common.device_info(cell["chips"], require_chip)
    phase = common.phases(t_start)
    tr, ini = cell["traffic"], cell["ini"]
    batch, nnz = int(ini["Train"]["batch_size"]), int(ini["Train"]["max_nnz"])
    hyper, model = _hyper(ini), cell["model"]
    has_dense = _dense_limits_stated(cell)
    vocab = hyper["vocab"]
    log_every = int(ini["Train"]["log_every"])
    n_batches = int(tr["file_batches"])
    runtime_start_s = phase("device found")
    labels, ids, vals = gen.rows_from_seed(seed, n_batches * batch, nnz, vocab, tr.get("zipf_alpha", 2.5))
    phase("rows drawn")
    work, cfg = common.configured(cell, cell["name"], workroot, train_file="train.fmb")
    fields = gen.column_fields(ids)
    gen.write_fmb(cfg.train_files[0], labels, ids, vals, vocab, fields if model.reads_fields else None)
    phase("input made")

    # The rows the first three steps touch: what the check reads back.
    first = ids[: CHECK_STEPS * batch].reshape(CHECK_STEPS, batch, nnz)
    u = np.unique(first)
    u1 = np.unique(first[0])
    read = row_reader(cfg.table_layout, model.row_dim)
    # The next step donates its state: a dense leaf is kept as a copy.
    kept = lambda tree: {k: jax.numpy.copy(v) for k, v in tree.items()}
    u_dev, u1_dev = _pad(u, first.size), _pad(u1, first[0].size)
    phase("check rows found")

    cap = {"losses": []}
    win = SyncWindow(seconds, int(tr["warm_steps"]), log_every)
    wall_open = [None]
    trace_dir = os.path.join(work, "trace")

    def check(view: StepView):
        cap["losses"].append(view.loss)
        dense = getattr(view.state, "dense", None) if has_dense else None
        if view.step == 1:
            cap["t1"], cap["a1"] = view.rows(u1_dev)
            if dense:
                cap["d1"], cap["da1"] = kept(dense), kept(view.state.dense_opt.accum)
        if view.step == CHECK_STEPS:
            cap["t3"], _ = view.rows(u_dev, accum=False)
            if dense:
                cap["d3"] = kept(dense)

    def hook(step_num):
        if win.close is not None:
            raise _WindowClosed
        if step_num <= CHECK_STEPS:
            check(_loop_view(sys._getframe(1).f_locals, step_num, read))

    def log(msg):
        msg = str(msg)
        if not msg.startswith("step "):
            print(msg, file=sys.stderr)
            return
        parts = msg.split()
        step, loss = int(parts[1]), float(parts[5])
        if win.open is None and step >= win.warm_steps and do_trace:
            common.start_trace(trace_dir)  # before the clock is read
        edge = win.boundary(time.perf_counter(), step, loss)
        if edge == "open":
            wall_open[0] = time.time()
        elif edge == "close" and do_trace:
            jax.profiler.stop_trace()

    entry = training.dist_train if cell["kind"] == "dist_train" else training.train
    try:
        entry(cfg, log=log, step_hook=hook)
        raise SystemExit("the input ended before the window closed: raise epoch_num")
    except _WindowClosed as e:
        traceback.clear_frames(e.__traceback__)
    peak = common.memory_peak_bytes()
    phase("window")
    steps, s_open, s_close = win.steps, win.open[1], win.close[1]
    rate = win.rate(batch, cell["chips"])

    got = {
        "losses": [float(x) for x in cap["losses"][:CHECK_STEPS]],
        "t1": np.asarray(cap["t1"])[: u1.size],
        "a1": np.asarray(cap["a1"])[: u1.size],
        "t3": np.asarray(cap["t3"])[: u.size],
        **{k: {n: np.asarray(a) for n, a in cap[k].items()} for k in ("d1", "da1", "d3") if k in cap},
    }
    cap.clear()
    gc.collect()
    phase("state read back")
    n3 = CHECK_STEPS * batch
    ref = followed(hyper, model, first, vals[:n3].reshape(first.shape), fields[:n3].reshape(first.shape), labels[:n3].reshape(CHECK_STEPS, batch), u, u1)
    numbers = compare(got, ref, hyper["lr"])
    correct, compared = common.decide(numbers, tr["limits"])
    phase("reference followed and compared")

    result = {
        "correct": correct,
        "attempted": steps,
        "failed": win.bad_steps,
        "metrics": {},
        "device": dict(device, memory_peak_bytes=peak),
    }
    if do_trace:
        red = common.reduce_trace(result, trace_dir, keep_events)
        m = min(4, n_batches)
        work_bytes = np.mean([model.step_bytes(ids[i * batch : (i + 1) * batch]) for i in range(m)], axis=0)
        ctx = {
            "records": readers.read_jsonl(cfg.metrics_path),
            "steps": (s_open, s_close),
            "trace": red,
            "trace_dir": trace_dir,
            "n_steps": steps,
            "device_kind": device["kind"],
            "chips": cell["chips"],
            "model": model,
            "values": {"runtime_start_s": runtime_start_s},
            "step_bytes": float(work_bytes[0]),
            "step_flops": model.step_flops(batch, nnz, int(work_bytes[1])),
        }
        result["metrics"] = readers.read_all(cells.load_metrics(cell["kind"], cell["bench_dir"], cell["name"]), ctx)
    else:
        result["metrics"] = {
            "train_examples_per_s_per_chip": common.metric(rate, "examples/s/chip"),
            "setup_s": common.metric(wall_open[0] - t_start, "s"),
        }
    result["compared"] = compared
    common.remove_tree(work)
    return result


def _pad(rows, n):
    """``rows`` padded to the most rows the batches can touch, so that every
    seed compiles the same shapes; the padding repeats a row and is never read."""
    return np.concatenate([rows, np.full(n - rows.size, rows[0], rows.dtype)])


class StepView(NamedTuple):
    """The step ``step_hook`` follows, as the check reads it: its number, the
    state after it, its loss (a device value) and ``rows(ids, accum=True)``,
    the LOGICAL table rows of logical ``ids`` with their accumulator rows
    (None without ``accum``).  Only ``rows`` reads the device."""

    step: int
    state: object
    loss: object
    rows: Callable


def _loop_view(frame: dict, step: int, read) -> StepView:
    """The view from the locals of the loop that calls ``step_hook``
    (``training._run_training``), which hands its hook the step number alone:
    ``state``, ``loss`` and ``paramstore``, the tiered store's server or None."""
    state = frame["state"]
    return StepView(step, state, frame["loss"], partial(read, state, server=frame.get("paramstore")))


def row_reader(layout: str, row_dim: int):
    """``read(state, ids, accum=True, server=None) -> (table rows, accumulator
    rows or None)`` for logical ``ids`` (a host int array):

    - ``rows`` layout, on one chip or row-sharded (``dist_train``'s global
      array): ``state.table[ids]`` and ``state.table_opt.accum[ids]``, one
      jitted gather;
    - ``packed``: the program's own gathers of logical rows from the
      ``[V/P, 128]`` tiles, the accumulator packed alike or fused into the
      table's tiles (an empty ``table_opt.accum`` marks it), as its
      checkpoint's delta writer reads them (``checkpoint_async.make_row_gather``);
    - the tiered store (``server``): the state is the compact ``[C, D]`` table
      of hot rows and staging slots, indexed by slot; hot ids are read from
      their slots, the others from the pending overlay over the cold store once
      the last step's staged rows are fetched into it (``flush_writeback``,
      which the next step calls first: fetching them earlier stages the same
      rows)."""
    import jax

    take = jax.jit(lambda t, i: t[i])
    if layout == "packed":
        from fast_tffm_tpu.ops import packed_table as pt

        jit = lambda gather: jax.jit(partial(gather, d=row_dim))
        packed = jit(pt.packed_gather), jit(pt.packed_accum_gather_any)
        fused = jit(pt.fused_gather), jit(pt.fused_accum_gather)

        def leaves(state):
            if state.table_opt.accum.size == 0:  # the accumulator fused into the table's tiles
                return fused, (state.table, state.table)
            return packed, (state.table, state.table_opt.accum)

    else:

        def leaves(state):
            return (take, take), (state.table, state.table_opt.accum)

    def read(state, ids, accum=True, server=None):
        if server is not None:
            return _tiered_rows(server, state, ids, accum, take)
        (gt, ga), (t, a) = leaves(state)
        return gt(t, ids), (ga(a, ids) if accum else None)

    return read


def _tiered_rows(server, state, ids, accum, take):
    ids = np.asarray(ids, np.int64)
    server.flush_writeback(state)
    hit, slot = server.residency.lookup(ids)
    table, acc, _ = server.read_latest(ids[~hit])

    def rows(leaf, cold):
        out = np.array(take(leaf, slot))
        out[~hit] = cold
        return out

    return rows(state.table, table), (rows(state.table_opt.accum, acc) if accum else None)


def followed(h, model, first, vals, fields, labels, u, u1, dtype=None, shards=0, dense_frozen=False):
    """The reference over the first three batches (``first`` ids [3, B, N],
    ``vals`` and ``fields`` [3, B, N], ``labels`` [3, B]), on the compact table
    of the rows ``u`` they touch and on the model's dense leaves, where it has
    any.  Returns what ``compare`` reads, as numpy."""
    import jax.numpy as jnp

    t0 = model.init_rows(_pad(u, CHECK_STEPS * h["rows_per_step"]))
    d0 = dense_leaves(model)
    idx = np.searchsorted(u, first).astype(np.int32)
    # Padding rows of the compact table are never read; shard 0 may own them.
    owner = _pad(u // (h["vocab"] // shards), t0.shape[0]).astype(np.int32) if shards else None
    outs = reference.train_steps(
        model.score, t0, list(zip(idx, vals, fields, labels)), h["lr"], h["accum0"], h["bias_lambda"], h["factor_lambda"],
        dtype=dtype or jnp.float32, owner=owner, dense0=d0, dense_frozen=dense_frozen,
    )
    at1 = np.searchsorted(u, u1)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))[: u.size]
    out = {
        "losses": [float(o[0]) for o in outs],
        "t0": f32(t0), "at1": at1,
        "t1": f32(outs[0][1])[at1], "a1": f32(outs[0][2])[at1],
        "t3": f32(outs[-1][1]),
    }
    if d0:
        leaves = lambda tree: {k: np.asarray(v.astype(jnp.float32)) for k, v in tree.items()}
        out.update(d0=leaves(d0), d1=leaves(outs[0][3]), da1=leaves(outs[0][4]), d3=leaves(outs[-1][3]))
    return out


def planted(cell, seed, what):
    """The control or a fault, planted in the reference put in the program's
    place, at the cell's own size: ``control`` follows the three steps in
    bfloat16; ``half_batch`` leaves half of each batch out and takes the mean
    over the rest; ``no_exchange`` (cells on several chips) leaves out the
    exchange of gradients between the row shards; ``dense_frozen`` (models
    with dense leaves) never updates them, what a step that drops their
    gradient computes.  Returns the numbers ``compare`` gives against the
    sound float32 reference."""
    import jax.numpy as jnp

    ini = cell["ini"]
    batch, nnz = int(ini["Train"]["batch_size"]), int(ini["Train"]["max_nnz"])
    h, model = _hyper(ini), cell["model"]
    labels, ids, vals = gen.rows_from_seed(seed, CHECK_STEPS * batch, nnz, h["vocab"], cell["traffic"].get("zipf_alpha", 2.5))
    first, vals, labels = ids.reshape(CHECK_STEPS, batch, nnz), vals.reshape(CHECK_STEPS, batch, nnz), labels.reshape(CHECK_STEPS, batch)
    fields = gen.column_fields(first)
    u, u1 = np.unique(first), np.unique(first[0])
    ref = followed(h, model, first, vals, fields, labels, u, u1)
    if what == "control":
        bad = followed(h, model, first, vals, fields, labels, u, u1, dtype=jnp.bfloat16)
    elif what == "half_batch":
        half = batch // 2
        bad = followed(h, model, first[:, :half], vals[:, :half], fields[:, :half], labels[:, :half], u, u1)
    elif what == "no_exchange":
        bad = followed(h, model, first, vals, fields, labels, u, u1, shards=cell["chips"])
    elif what == "dense_frozen":
        if "d0" not in ref:
            raise SystemExit(f"{cell['name']}: the configuration's model has no dense leaves to freeze")
        bad = followed(h, model, first, vals, fields, labels, u, u1, dense_frozen=True)
    else:
        raise ValueError(what)
    return compare(bad, ref, h["lr"])


def _hyper(ini):
    return {
        "vocab": int(ini["General"]["vocabulary_size"]),
        "rows_per_step": int(ini["Train"]["batch_size"]) * int(ini["Train"]["max_nnz"]),
        "init_range": float(ini["Train"].get("init_value_range", 0.01)),
        "lr": float(ini["Train"]["learning_rate"]),
        "accum0": float(ini["Train"].get("init_accumulator_value", 0.1)),
        "bias_lambda": float(ini["Train"].get("bias_lambda", 0.0)),
        "factor_lambda": float(ini["Train"].get("factor_lambda", 0.0)),
    }


def _dense_limits_stated(cell) -> bool:
    """Whether the cell's model has dense leaves; one that has, under a mix
    that states no limit for a number over them, exits: ``common.decide``
    skips a number without a limit, and the cell would read ``correct`` with
    its dense leaves held to nothing."""
    if not hasattr(cell["model"], "init_dense"):
        return False
    lacking = [n for n in DENSE_NUMBERS if cell["traffic"].get("limits", {}).get(n) is None]
    if lacking:
        raise SystemExit(
            f"{cell['name']}: the configuration's model has dense leaves and the mix states no limit for "
            f"{' or '.join(lacking)}: it would read correct with those leaves held to nothing"
        )
    return True


def _leaf_norms(rows):
    """The two leaves of a row table: biases (column 0), factors (the rest)."""
    rows = rows.astype(np.float64)
    return np.array([np.sqrt((rows[:, 0] ** 2).sum()), np.sqrt((rows[:, 1:] ** 2).sum())])


def compare(got, ref, lr):
    """Gap of each step's loss; gap between the program's and the reference's
    norm of the first gradient, worked out on both sides from the state after
    one step (g = (p0 - p1) * sqrt(accum1) / lr); gap of the norm of the
    parameters' change after three steps.  Norms by leaf, the worst leaf
    counts, each against the reference's norm of that leaf.  Those three are
    over the table's two leaves alone; where the reference has dense leaves
    the two norms are taken over them too, by the same formulas, as two
    numbers of their own (``_dense_gaps``)."""
    t0_1 = ref["t0"][ref["at1"]]
    grad = lambda s: _leaf_norms((t0_1 - s["t1"]) * np.sqrt(s["a1"]) / lr)
    delta = lambda s: _leaf_norms(s["t3"] - ref["t0"])
    worst = lambda p, r: float(np.max(np.abs(p - r) / r))
    lp, lr_ = np.array(got["losses"]), np.array(ref["losses"])
    numbers = {
        "loss_gap": float(np.max(np.abs(lp - lr_) / np.abs(lr_))) if lp.shape == lr_.shape else float("inf"),
        "grad1_norm_gap": worst(grad(got), grad(ref)),
        "delta3_norm_gap": worst(delta(got), delta(ref)),
    }
    if "d0" in ref:
        numbers.update(zip(DENSE_NUMBERS, _dense_gaps(got, ref, lr)))
    return numbers


def _dense_gaps(got, ref, lr):
    """(gap of the first gradient's norm, gap of the three steps' change's
    norm) over the dense leaves, the worst leaf counting, each leaf against
    the reference's norm of it or of the median dense leaf, whichever is
    larger (a small leaf beside large ones rounds to a large share of
    itself).  A leaf the program lacks or shapes otherwise reads infinity; a
    leaf whose reference norm is 0 cannot be compared and is an error."""
    norm = lambda a: float(np.sqrt((np.asarray(a, np.float64) ** 2).sum()))

    def norms(s, name, p0):
        if not all(name in s.get(k, {}) and s[k][name].shape == p0.shape for k in ("d1", "da1", "d3")):
            return None
        return norm((p0 - s["d1"][name]) * np.sqrt(s["da1"][name]) / lr), norm(s["d3"][name] - p0)

    theirs = {name: norms(ref, name, p0) for name, p0 in ref["d0"].items()}
    median = [float(m) for m in np.median(list(theirs.values()), axis=0)]
    gaps = [0.0, 0.0]
    for name, r in theirs.items():
        p = norms(got, name, ref["d0"][name])
        for i, what in enumerate(("first gradient", "change after three steps")):
            if not r[i]:
                raise SystemExit(f"the reference's {what} of the dense leaf {name!r} has norm 0: the leaf cannot be compared")
            gaps[i] = max(gaps[i], abs(p[i] - r[i]) / max(r[i], median[i]) if p else float("inf"))
    return gaps
