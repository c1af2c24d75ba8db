"""``fm2_hashed``'s model for a configuration whose tiered store holds a hot
tier of millions of rows (``fm16_criteo_tiered``), with one check made when
the cell is loaded, before the runtime starts: the program's store must
resolve a batch's ids by one read of an id-to-slot map
(``paramstore.residency.ResidencyMap(hot_ids, vocab)``).

A store without that map resolves by searching the sorted hot set, three
searches of every id of a batch, and keeps its pending rows in a dict
written and read id by id.  At 2^25 hot rows and 2,555,904 ids a step that
is seconds of host time a batch: such a program's set-up and 32 warm steps
alone outlast a run (353.9 s of set-up on a TPU v5e host, then 8 steps in a
window of nearly a minute).  It cannot run the cell, so it is stopped at
once with a message and exit code 1, not left to a run's time limit.

This is the one module of ``models/`` that asks the program anything; the
reference it gives is ``fm2_hashed``'s."""

from __future__ import annotations

import inspect

from . import fm2_hashed


def program_resolves_by_map() -> bool:
    """Whether the program's residency map is built over the vocabulary (one
    gather a lookup) rather than a search of the sorted hot set."""
    from fast_tffm_tpu.paramstore.residency import ResidencyMap

    return "vocab" in inspect.signature(ResidencyMap).parameters


class Model(fm2_hashed.Model):
    def __init__(self, ini):
        if not program_resolves_by_map():
            raise SystemExit(
                "this program's tiered store resolves ids by searching the sorted hot set "
                "(paramstore.residency.ResidencyMap takes no vocab): at this configuration's "
                "hot tier its set-up and warm-up alone outlast a run; the cell needs a store "
                "that resolves by one read of an id-to-slot map"
            )
        super().__init__(ini)
