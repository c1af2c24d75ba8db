"""The perceptron of a model with dense leaves (DeepFM): its necessary work,
and its share of the roofline from a traced window.

The program runs the perceptron's matmuls under ``jax.named_scope("deepfm.mlp")``
(the backward as ``transpose(jvp(deepfm.mlp))``) and the leaves' Adagrad under
``deepfm.dense_update`` (PR 43); XLA fuses a weight's gradient into its update,
so the two are timed together.  A program that has neither scope, as every one
before PR 43, gives None, and so does a cell whose model has no dense leaves:
the metric is left out.  What the perceptron has to do is the model's to say
(``models/deepfm.Model.weights``, ``dense_elements``, ``dims``), not the
program's."""

from __future__ import annotations

from . import peaks, scopes, trace


def perceptron_work(rows: int, d_in: int, weights: int, dense_elements: int) -> tuple[int, int]:
    """(FLOPs, HBM bytes) a train step's perceptron cannot avoid: a
    multiply-add a weight a row forward and twice that backward (by the input
    and by the weight); the ``[rows, d_in]`` input read once and its gradient
    written once, every leaf and its accumulator read once and written once."""
    return 6 * weights * rows, 2 * rows * d_in * 4 + 4 * dense_elements * 4


def seconds_under(ops: dict, prefixes) -> float | None:
    """Seconds in which an op under ANY of the scopes ran (the union of their
    intervals), the mean over device planes; None where no op is under one."""
    unders = [scopes._under(p) for p in prefixes]
    per_plane = [
        trace._length(trace._union((s, s + d) for _, sc, s, d in ev if any(u(sc) for u in unders)))
        for ev in ops.values()
    ]
    if not per_plane or not any(per_plane):
        return None
    return sum(per_plane) / len(per_plane)


def _rows_per_step(ctx) -> int | None:
    """The examples of one train step, as the program's ``kind=profile``
    record of the step says them."""
    for r in ctx.get("records", []):
        if r.get("kind") == "profile" and r.get("program") == "train_step" and r.get("examples"):
            return int(r["examples"])
    return None


def roofline(m, ctx):
    """The least time the chip needs for ``perceptron_work`` at the cell's
    shapes (the bfloat16 matrix peak bounds it) over the device time under the
    scopes the metric file names (``scopes``), in percent."""
    model, rows = ctx.get("model"), _rows_per_step(ctx)
    dims, weights, elements = (getattr(model, a, None) for a in ("dims", "weights", "dense_elements"))
    if not ctx.get("n_steps") or not rows or None in (dims, weights, elements):
        return None
    s = seconds_under(scopes._ops_of(ctx), m["scopes"])
    if s is None:
        return None
    least, _ = peaks.least_seconds(*perceptron_work(rows, dims[0], weights, elements), ctx["device_kind"])
    return 100.0 * least * ctx["n_steps"] / s
