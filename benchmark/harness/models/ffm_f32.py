"""The field-aware model of ``ffm.py`` for a cell at full size on the chip:
the same pair sum, row and work model, and three things that cell needs.

**The pair sum runs in blocks of rows.**  ``ffm.Model.score`` holds
``v_{i, f_j}`` for every pair of a batch at once, ``[B, 39, 39, 4]``; on the
TPU an array whose last dimension is 4 is laid out 128 lanes wide, so at
B = 32,768 that one array is 25.5 GB (the TPU compiler refuses the reference's
step: PERF.md section 6, PR 29).  Here the same function scores 1,024 rows at
a time (``lax.map``), recomputed on the way back (``jax.checkpoint``) so that
autodiff keeps a block's rows and not its pair tensor: the same sum for every
row, 0.8 GB at a time.

**The compact table is capped at the vocabulary.**  ``train.followed`` pads
the rows the three check batches touch to 3 x B x N, so that every seed
compiles one shape; at B = 32,768 x 39 ids and 2^20 rows that is 3.83M rows of
628 bytes, 3.7 times the whole table, and the reference keeps a table and an
accumulator for each of its three steps: 19 GB on a 16 GB chip.  No batch
touches more distinct rows than the table has, and the padding trails the
touched rows and is never read, so ``init_rows`` draws at most ``vocab`` rows:
one shape for every seed all the same, 658 MB an array.

**The program is probed for the precision the configuration states**, and
this is the one place where a harness model imports the program: a guard at
load, not part of the reference.  On a TPU a float32 contraction at the default
precision is one bfloat16 pass.  ``correct`` would say so too (the bfloat16
control fails all three limits), but only after the window, and a checkout
from before PR 29 does not get that far in a run's time: its wire unpack of
this cell's 3-byte ids takes the TPU compiler 16 minutes on a cold cache
(PERF.md section 6, PR 29).  The driver runs a new cell on the parent commit
with these benchmark files laid over it, and a parent that hangs there, or
is killed, refuses the PR; one that exits non-zero, soon, does not.  So the
cell says at load, on the device it is about to run on, in under a second,
that such a program cannot run a configuration that states float32: a
handful of rows through the program's ``FFMModel.score`` against the pair
sum here.
"""

from __future__ import annotations

import numpy as np

from . import ffm

_BLOCK_ROWS = 1024
_PROBE_ROWS = 8
# Two float32 orders of the sum read 3.5e-6 on the CPU; one bfloat16 pass on
# the chip's MXU reads 6.0e-3 (the parent of PR 29; PERF.md section 6).
_PROBE_LIMIT = 3e-5


class Model(ffm.Model):
    def __init__(self, ini: dict):
        super().__init__(ini)
        self.batch = int(ini["Train"]["batch_size"])
        self.nnz = int(ini["Train"]["max_nnz"])
        gap = self.program_score_gap(ini)
        if gap > _PROBE_LIMIT:
            raise SystemExit(
                f"the program's field-aware score is not float32 on this device: {_PROBE_ROWS} rows differ from the "
                f"plain pair sum by {gap:.3g} of its size (limit {_PROBE_LIMIT}); this configuration states float32"
            )

    def init_rows(self, rows):
        return super().init_rows(np.asarray(rows)[: self.vocab])

    def score(self, rows, vals, fields):
        import jax
        from jax import lax

        b = vals.shape[0]
        if b <= _BLOCK_ROWS or b % _BLOCK_ROWS:
            return super().score(rows, vals, fields)
        blocks = tuple(a.reshape(b // _BLOCK_ROWS, _BLOCK_ROWS, *a.shape[1:]) for a in (rows, vals, fields))
        return lax.map(lambda block: jax.checkpoint(super(Model, self).score)(*block), blocks).reshape(b)

    def program_score_gap(self, ini: dict) -> float:
        """Widest gap between the program's score and the pair sum over a few
        random rows, over the widest score: what one bfloat16 pass moves."""
        import jax
        import jax.numpy as jnp

        from fast_tffm_tpu.models.base import Batch
        from fast_tffm_tpu.models.ffm import FFMModel

        rng = np.random.default_rng(0)
        b, n = _PROBE_ROWS, self.nnz
        rows = jnp.asarray(rng.uniform(-0.3, 0.3, (b, n, self.row_dim)), jnp.float32)
        vals = jnp.asarray(np.round(rng.uniform(0.05, 1.5, (b, n)), 4), jnp.float32)
        fields = jnp.asarray(rng.integers(0, self.fields, (b, n)), jnp.int32)
        program = FFMModel(
            vocabulary_size=self.vocab, num_fields=self.fields, factor_num=self.k,
            compute_dtype=ini["General"].get("compute_dtype", "float32"),
        )
        batch = Batch(labels=jnp.zeros(b), ids=jnp.zeros((b, n), jnp.int32), vals=vals, fields=fields, weights=jnp.ones(b))
        got = np.asarray(jax.jit(program.score)(rows, {}, batch), np.float64)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(self.score)(rows, vals, fields), np.float64)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
