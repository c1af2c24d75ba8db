"""The exchange between the chips of a row-sharded train step, against the
least the interconnect could do.

The program names every collective of the sharded step ``fm.exchange`` and
says in its telemetry what they move (``kind=train``
``exchange_bytes_per_step``; ``kind=profile`` ``mesh``, ``shard_rows``).  A
program that has neither (one before PR 35) gives None everywhere here.

NECESSARY bytes are fixed by the batch and by who owns which row, not by the
collectives that happen to implement the exchange: a chip scores its
B / chips rows of the batch; of the distinct ids in that share, those another
row shard owns must come to it once, one table row forward, and their summed
gradient must go back once, one row backward.  Counted from the ids of the
FMB file the window trained on, the mean over chips and over the file's
batches.
"""

from __future__ import annotations

import os

import numpy as np

from . import gen, readers, scopes

# Google Cloud documentation, "TPU v5e" system architecture: interchip
# interconnect bandwidth 1,600 Gbit/s a chip (all of its links together).
ICI_BYTES_PER_S = {"TPU v5 lite": 200e9, "TPU v5e": 200e9}


def _step_profile(ctx) -> dict | None:
    """The program's ``kind=profile`` record of the sharded train step."""
    for r in ctx.get("records", []):
        if r.get("kind") == "profile" and r.get("program") == "train_step" and r.get("mesh") and r.get("shard_rows"):
            return r
    return None


def fmb_ids(path: str) -> np.ndarray:
    """ids i32[n_rows, width] of an FMB file as ``gen.write_fmb`` lays it out:
    header, then labels, row lengths and ids, each section on a 64-byte edge."""
    with open(path, "rb") as f:
        _, _, n, width, *_ = gen._HEADER.unpack(f.read(gen._HEADER.size))
    edge = lambda at: -(-at // gen._ALIGN) * gen._ALIGN
    at = edge(edge(edge(gen._HEADER.size) + 4 * n) + 4 * n)  # past labels f32[n] and lengths i32[n]
    return np.memmap(path, "<i4", "r", offset=at, shape=(n, width))


def necessary_bytes(ids: np.ndarray, batch: int, mesh: dict, shard_rows: int, row_dim: int) -> float:
    """Bytes a chip must receive and send a step: for every distinct id of its
    share that another row shard owns, one float32 row in and one out; the mean
    over the chips and over the whole batches ``ids`` holds."""
    rows, chips = int(mesh["row"]), int(mesh["row"]) * int(mesh["data"])
    share = batch // chips
    remote = []
    for b in range(ids.shape[0] // batch):
        for c in range(chips):
            u = np.unique(ids[b * batch + c * share : b * batch + (c + 1) * share])
            remote.append(np.count_nonzero(u // shard_rows != c % rows))
    return float(np.mean(remote)) * row_dim * 4 * 2 if remote else 0.0


def roofline(m, ctx):
    """The least time the interconnect needs for the step's necessary bytes
    over the device time under ``fm.exchange``, in percent."""
    del m
    prof, ici = _step_profile(ctx), ICI_BYTES_PER_S.get(ctx.get("device_kind"))
    path = os.path.join(os.path.dirname(ctx.get("trace_dir") or ""), "train.fmb")
    if not prof or not ici or not ctx.get("n_steps") or not ctx.get("trace") or not os.path.isfile(path):
        return None
    seconds = scopes.scope_seconds(scopes._ops_of(ctx), "fm.exchange")
    if not seconds:
        return None
    need = necessary_bytes(fmb_ids(path), int(prof["examples"]), prof["mesh"], int(prof["shard_rows"]), int(prof["row_dim"]))
    return 100.0 * (need / ici) * ctx["n_steps"] / seconds


def mb_per_step(m, ctx):
    """``exchange_bytes_per_step`` of the window's ``kind=train`` records, in MB
    (10^6 bytes): what the step's collectives, as traced, make a chip send and
    receive."""
    v = readers.telemetry_field(dict(m, kind="train", field="exchange_bytes_per_step", phase="window", reduce="mean"), ctx)
    return None if v is None else v / 1e6
