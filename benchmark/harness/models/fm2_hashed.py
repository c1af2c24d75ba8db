"""``fm2``'s model over the tiered store's LAZY cold store: a vocabulary past
the store's materialize bound (2^21 rows), or ``[ParamStore] materialize =
never``.  A row's initial factors are then drawn from a hash of (seed 0, id,
column), as the store's documented lazy init draws them, not from one uniform
draw of the whole table; the bias column starts at 0 as before.  Everything
else is ``fm2``'s."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import fm2

STORE_SEED = 0  # the seed the program creates every cold store with


def hashed_rows(ids, row_dim: int, seed: int, init_range: float) -> np.ndarray:
    """Copy of ``paramstore.store.hashed_uniform_rows``: splitmix64 over a
    (seed, id, column) counter, its top 24 bits a uniform in [0, 1), scaled to
    [-init_range, init_range); column 0 is 0."""
    ids = np.asarray(ids, np.uint64).reshape(-1, 1)
    cols = np.arange(row_dim, dtype=np.uint64).reshape(1, -1)
    seed_mix = np.uint64((int(seed) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF)
    x = ids * np.uint64(0x9E3779B97F4A7C15) + cols * np.uint64(0xBF58476D1CE4E5B9) + seed_mix
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    u = (x >> np.uint64(40)).astype(np.float32) / np.float32(1 << 24)
    rows = ((u * 2.0 - 1.0) * np.float32(init_range)).astype(np.float32)
    rows[:, 0] = 0.0
    return rows


class Model(fm2.Model):
    def init_rows(self, rows):
        return jnp.asarray(hashed_rows(rows, self.row_dim, STORE_SEED, self.init_range))
