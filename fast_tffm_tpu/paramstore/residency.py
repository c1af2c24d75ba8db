"""Hot-tier residency: which logical rows live on device, and the
host-side id resolution every batch goes through.

The hot set is chosen ONCE per run (deterministically — ``--resume``
restores the exact set from the checkpoint, so a resumed run's
residency, and therefore its remapped-id programs and its loss
sequence, are identical to the uninterrupted run's):

  * ``sample`` (default) — exact frequency count over the first N
    batches of the train stream, top-K by (count desc, id asc).  This is
    the PR-9 heavy-hitter telemetry's exact twin: the committed coverage
    curve (top-4096 rows absorb 59% of gathers at the Zipf(1.1) scale
    shape) is precisely what this policy caches.
  * ``first`` — ids [0, K): the degenerate deterministic policy (useful
    when the id space is already frequency-ranked, e.g. hashed ranks).
  * ``file:PATH`` — an id array (.npy, or one id per line) exported from
    telemetry; the first K ids win.

Resolution (``ResidencyMap.resolve``) is one gather per id from a dense
id-to-slot map (``int32[V + 1]``, slot + 1 per hot id, 0 for the rest: 4 B a
logical row, built once; the zeros are never-touched pages until a hot id
lands on them): hot id -> its rank (= its device slot), miss id -> a
per-superbatch staging slot.  Slots are ranks in SORTED order, so the
mapping is a pure function of the hot set — no insertion-order state to
drift."""

from __future__ import annotations

import os
from typing import NamedTuple

import numpy as np

__all__ = ["ResidencyMap", "choose_hot_ids", "Resolved"]


class Resolved(NamedTuple):
    """One (super)batch's residency resolution (host side)."""

    remapped: list  # per-micro-batch [B, N] int32 LOCAL ids (slots)
    miss_ids: np.ndarray  # unique missed LOGICAL ids (sorted), [m]
    hit_slots: int  # gather slots that hit the hot tier
    total_slots: int  # all gather slots (B*N per micro batch)


class ResidencyMap:
    """The hot set and its id-to-slot map.  ``vocab`` sizes the map (the
    logical rows); without it the map covers the largest hot id.  An id at
    or past the map's end reads its last entry, which no hot id holds, so
    it misses."""

    def __init__(self, hot_ids: np.ndarray, vocab: int | None = None):
        hot = np.unique(np.asarray(hot_ids, np.int64))
        if hot.size != np.asarray(hot_ids).size:
            raise ValueError("hot_ids must be unique")
        self.hot_ids = hot  # sorted; slot of hot_ids[i] is i
        self.hot_rows = int(hot.size)
        top = int(hot[-1]) + 1 if hot.size else 0
        self._slot1 = np.zeros(max(int(vocab or 0), top) + 1, np.int32)
        self._slot1[hot] = np.arange(1, hot.size + 1, dtype=np.int32)

    def _slots1(self, ids) -> np.ndarray:
        """slot + 1 per id, 0 for a miss: one gather."""
        return self._slot1.take(ids, mode="clip")

    def lookup(self, ids: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(hit mask, slot per id) for flat logical ``ids``: a hit's hot
        slot, slot 0 for a miss, so that every slot is in range."""
        s = self._slots1(np.asarray(ids))
        return s > 0, np.maximum(s - 1, 0)

    def resolve(self, ids_seq: list[np.ndarray], miss_capacity: int) -> Resolved:
        """Remap a superbatch's logical ids to device slots.

        Hot ids map to their rank slot; every unique missed id gets a
        staging slot ``hot_rows + rank`` (rank within the sorted unique
        miss set of THIS superbatch).  Dedup-before-gather falls out for
        free: a miss row is staged (and its bytes cross the wire) once
        per superbatch no matter how many slots repeat it.  One map read
        per id; the only sort is over the missed ids."""
        shapes = [np.shape(a) for a in ids_seq]
        flats = [np.asarray(a).reshape(-1) for a in ids_seq]
        all_flat = np.concatenate(flats) if len(flats) > 1 else flats[0]
        local = self._slots1(all_flat)
        miss_at = np.flatnonzero(local == 0)
        miss_ids, rank = np.unique(all_flat[miss_at], return_inverse=True)
        if miss_ids.size > miss_capacity:
            raise ValueError(
                f"paramstore: a superbatch touches {miss_ids.size} unique "
                f"non-resident rows, over the staging capacity "
                f"{miss_capacity} — raise [ParamStore] miss_rows (or "
                "hot_rows), or lower batch_size/steps_per_call"
            )
        local -= 1
        local[miss_at] = self.hot_rows + rank.reshape(-1)
        bounds = np.cumsum([f.size for f in flats])[:-1]
        return Resolved(
            remapped=[p.reshape(sh) for p, sh in zip(np.split(local, bounds), shapes)],
            miss_ids=miss_ids,
            hit_slots=int(all_flat.size - miss_at.size),
            total_slots=int(all_flat.size),
        )


def choose_hot_ids(
    policy: str,
    hot_rows: int,
    vocab: int,
    *,
    sample_batches=None,
) -> np.ndarray:
    """The run-start residency decision (see module docstring).
    ``sample_batches`` is an iterator of host id arrays for the
    ``sample`` policy (the driver hands it the first N parsed batches of
    the train stream)."""
    k = min(int(hot_rows), int(vocab))
    if policy == "first":
        return np.arange(k, dtype=np.int64)
    if policy.startswith("file:"):
        path = policy[len("file:"):]
        if not os.path.exists(path):
            raise ValueError(f"[ParamStore] residency file not found: {path!r}")
        if path.endswith(".npy"):
            ids = np.load(path).astype(np.int64).reshape(-1)
        else:
            with open(path) as f:
                ids = np.array(
                    [int(x) for x in f.read().split() if x.strip()], np.int64
                )
        ids = ids[(ids >= 0) & (ids < vocab)]
        uniq = np.unique(ids)
        if uniq.size < k:
            raise ValueError(
                f"[ParamStore] residency file {path!r} holds {uniq.size} "
                f"distinct in-range ids, fewer than hot_rows = {k}"
            )
        # Preserve the file's ranking: first K distinct ids in file order.
        _, first = np.unique(ids, return_index=True)
        return ids[np.sort(first)[:k]]
    if policy != "sample":
        raise ValueError(
            f"unknown [ParamStore] residency policy {policy!r} "
            "(sample | first | file:PATH)"
        )
    ids_all = [np.asarray(a, np.int64).reshape(-1) for a in sample_batches or ()]
    if not ids_all:
        # No sample available (empty stream): fall back to the first-K
        # deterministic set rather than failing a run that would work.
        return np.arange(k, dtype=np.int64)
    flat = np.concatenate(ids_all)
    if int(vocab) <= 4 * flat.size:
        # A dense count: one pass, no sort of the sample.
        counts = np.bincount(flat, minlength=int(vocab))
        uniq = np.flatnonzero(counts)
        cnt = counts[uniq]
        del counts
    else:
        uniq, cnt = np.unique(flat, return_counts=True)
    # Top-K by (count desc, id asc) — a full deterministic order, so ties
    # cannot reshuffle residency between runs.  The cut is the largest
    # count c with at least K ids seen c times or more: every id above it
    # is in, and its ties are taken in id order (``uniq`` is sorted).
    of_count = np.bincount(cnt)
    at_least = np.cumsum(of_count[::-1])[::-1]
    cut = max(1, int(np.flatnonzero(at_least >= k)[-1])) if at_least[0] >= k else 1
    top = uniq[cnt > cut]
    top = np.concatenate([top, uniq[cnt == cut][: k - top.size]])
    if top.size < k:
        # Fewer distinct ids than hot_rows in the sample: fill with the
        # smallest unseen ids (deterministic).
        fill = np.setdiff1d(np.arange(min(vocab, k * 2), dtype=np.int64), top)
        top = np.concatenate([top, fill[: k - top.size]])
    return np.sort(top)
