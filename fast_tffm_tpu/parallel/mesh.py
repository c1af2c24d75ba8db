"""Device mesh construction for data×row parallelism.

The reference scales two ways (SURVEY.md §3): data-parallel asynchronous
workers (Hogwild on a parameter server) and a `vocabulary_block_num`-way
row partition of the parameter table across ps tasks.  The TPU-native
equivalents are the two axes of one `jax.sharding.Mesh`:

  * ``data``  — batch sharding, synchronous gradient combination over ICI
                (replacing Hogwild with deterministic sync updates);
  * ``row``   — contiguous row sharding of the embedding/parameter table
                (replacing the modulo block partition over ps hosts).

On a multi-host pod the same mesh spans all chips: JAX lays ICI within a
slice and DCN across slices automatically from the device order.
"""

from __future__ import annotations

import numpy as np
import jax
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "make_mesh",
    "table_sharding",
    "replicated",
    "pad_vocab",
    "axis_size",
]

DATA_AXIS = "data"
ROW_AXIS = "row"


def make_mesh(
    data_parallel: int | None = None,
    row_parallel: int = 1,
    devices=None,
) -> Mesh:
    """Mesh of shape [data_parallel, row_parallel] over ``devices``.

    ``data_parallel=None`` uses all remaining devices after row_parallel.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    if data_parallel is None:
        if n % row_parallel:
            raise ValueError(f"{n} devices not divisible by row_parallel={row_parallel}")
        data_parallel = n // row_parallel
    need = data_parallel * row_parallel
    if need > n:
        raise ValueError(f"need {need} devices, have {n}")
    grid = np.asarray(devices[:need]).reshape(data_parallel, row_parallel)
    return Mesh(grid, (DATA_AXIS, ROW_AXIS))


def table_sharding(mesh: Mesh) -> NamedSharding:
    """[V, D] tables: rows split over ROW_AXIS, replicated over DATA_AXIS."""
    return NamedSharding(mesh, P(ROW_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_vocab(vocabulary_size: int, row_parallel: int) -> int:
    """Round the table row count up so every row shard is equal-sized."""
    r = row_parallel
    return ((vocabulary_size + r - 1) // r) * r


def check_batch_divides(batch_size: int, mesh: Mesh) -> None:
    """Fail fast when the global batch cannot split over every chip.

    The train/predict steps shard the batch over BOTH mesh axes; catching
    the mismatch here gives a config-level message instead of a shard_map
    axis-divisibility error from inside the first step."""
    if batch_size % mesh.devices.size:
        raise ValueError(
            f"batch_size {batch_size} not divisible by the "
            f"{mesh.devices.size}-device mesh"
        )
