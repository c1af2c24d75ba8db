"""Shared Pallas kernel plumbing: interpret-mode resolution.

Every Pallas kernel in this package takes an ``interpret`` flag so the
CPU tier-1 suite can run it in the Pallas interpreter.  The detection
lives here once so (a) production modules never spell ``interpret=True``
(the static-analysis suite flags the literal outside this module — a
compiled path silently running interpreted is a throughput bug, not an
error), and (b) tests need no per-test plumbing: on the test mesh the
kernels interpret themselves automatically.
"""

from __future__ import annotations

import jax

__all__ = ["default_interpret", "resolve_interpret"]


def default_interpret() -> bool:
    """True only where the CPU was ASKED for (``JAX_PLATFORMS=cpu`` — the
    tier-1 test mesh): there Pallas kernels run in the interpreter.

    A CPU backend nobody asked for (libtpu failed to initialise and jax
    dropped to the CPU with a warning) is NOT a reason to interpret: the
    kernel is then lowered for the backend it finds and the lowering's own
    error surfaces, instead of a whole run proceeding interpreted."""
    requested = (jax.config.jax_platforms or "").split(",")[0]
    return requested == "cpu" and jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` (the wrapper default) → auto-detect; a bool is explicit.

    Tests pass ``interpret=True`` explicitly; production call sites pass
    ``None`` and inherit the detection — the one CPU branch the analysis
    suite sanctions.
    """
    return default_interpret() if interpret is None else bool(interpret)
