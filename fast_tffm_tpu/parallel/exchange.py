"""The exchange between chips, named and counted.

Every collective of the sharded step runs under ``fm.exchange``
(``exchange_scope``), so that a device trace can tell what crossing chips
costs from the local work around it; ``exchange_bytes`` says, from the traced
program alone, how many bytes a chip sends and receives in them.  Neither
changes any arithmetic.
"""

from __future__ import annotations

import math

import jax

__all__ = ["exchange_scope", "exchange_bytes"]


def exchange_scope(serves: str | None = None):
    """``fm.exchange`` nested in the scope the collective serves
    (``fm.gather/fm.exchange``, ``fm.tail/fm.exchange``, ``fm.loss/...``):
    inside that scope call it bare, outside it name the scope."""
    return jax.named_scope(f"{serves}/fm.exchange" if serves else "fm.exchange")


# Bytes a chip sends plus bytes it receives, as multiples of its operand's
# bytes, in a group of n chips (a ring's count, which every schedule that moves
# each byte once shares): an all-gather passes its operand to n-1 peers and
# takes theirs; a reduce-scatter and an all-to-all keep 1/n of the operand and
# pass the rest on, and take as much; an all-reduce is a reduce-scatter and an
# all-gather of the n-th part.
_SENT_AND_RECEIVED = {
    "all_gather": lambda n: 2 * (n - 1),
    "reduce_scatter": lambda n: 2 * (n - 1) / n,
    "all_to_all": lambda n: 2 * (n - 1) / n,
    "psum": lambda n: 4 * (n - 1) / n,
    "psum_invariant": lambda n: 4 * (n - 1) / n,
}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            inner = getattr(x, "jaxpr", x)  # ClosedJaxpr | Jaxpr
            if hasattr(inner, "eqns"):
                yield inner


def _count(jaxpr, mesh_shape) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "shard_map":
            total += _count(eqn.params["jaxpr"], dict(eqn.params["mesh"].shape))
        elif name in _SENT_AND_RECEIVED:
            axes = eqn.params.get("axis_name", eqn.params.get("axes"))
            axes = axes if isinstance(axes, (tuple, list)) else (axes,)
            n = math.prod(mesh_shape[a] for a in axes)
            operand = sum(v.aval.size * v.aval.dtype.itemsize for v in eqn.invars)
            total += _SENT_AND_RECEIVED[name](n) * operand
        elif name == "cond":
            # Alternatives: the least one counts, the step as it runs when no
            # destination overflows (the fallback's steps are counted apart,
            # ``lookup_overflow_steps``).
            total += min(_count(b.jaxpr, mesh_shape) for b in eqn.params["branches"])
        elif name == "scan":
            total += eqn.params["length"] * _count(eqn.params["jaxpr"].jaxpr, mesh_shape)
        else:
            total += sum(_count(j, mesh_shape) for j in _sub_jaxprs(eqn))
    return total


def exchange_bytes(fn, *args) -> int:
    """The payload bytes ONE chip sends and receives in the collectives of
    ``fn(*args)`` as traced (``args`` may be ``jax.ShapeDtypeStruct``s): a
    trace-time number from each collective's per-chip operand and the size of
    the group it runs over; nothing runs on a device."""
    return int(round(_count(jax.make_jaxpr(fn)(*args).jaxpr, {})))
