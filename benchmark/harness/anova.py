"""The ANOVA interaction of a higher-order factorization machine: its
necessary work, and its share of the roofline from a traced window.

The program runs it under ``jax.named_scope("fm.anova")`` (inside
``fm.interaction``), forward and backward, in either of its forms (the Pallas
kernel with its layout transposes, the ``lax.scan``).  A program that has no
such scope, as every one before PR 37, gives None: the metric is left out."""

from __future__ import annotations

from . import peaks, scopes


def anova_work(rows: int, nnz: int, k: int, order: int) -> tuple[int, int]:
    """(FLOPs, HBM bytes) a train step's ANOVA interaction cannot avoid,
    fixed by the mathematics and not by the form computed: per occurrence and
    factor the dynamic program makes ``order`` multiply-adds forward (a_m +=
    z a_{m-1}, m = order..1; 2 FLOPs each) and twice that backward; the
    gathered factors are read once and their gradient is written once."""
    return rows * nnz * k * order * 2 * 3, 2 * rows * nnz * k * 4


def roofline(m, ctx):
    """The least time the chip needs for ``anova_work`` at the cell's shapes
    over the device time under the scope the metric file names, in percent."""
    model = ctx.get("model")
    shape = [getattr(model, a, None) for a in ("batch", "nnz", "k", "order")]
    if not ctx.get("n_steps") or None in shape:
        return None
    s = scopes.scope_seconds(scopes._ops_of(ctx), m["scope"])
    if s is None:
        return None
    least, _ = peaks.least_seconds(*anova_work(*shape), ctx["device_kind"])
    return 100.0 * least * ctx["n_steps"] / s
