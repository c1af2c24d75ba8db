"""Checkpoint save/restore for train state.

Capability parity with the reference's `tf.train.Saver` → `model_file`
(`renyi533/fast_tffm` :: local/dist trainer save + predictor restore), in
two formats:

  * **npz** — a single atomic .npz holding the sparse table, Adagrad
    accumulators, flattened dense params, and the step counter.  Simple,
    single-file, but gathers everything to one host — right for vocabs
    that fit host RAM.
  * **orbax** — a sharded Orbax checkpoint directory: every host writes
    only its own table shards in parallel (OCDBT).  The only format that
    works at the 10B-parameter-table scale (BASELINE north star), where no
    single host can materialize the table.

Both restores are mesh-shape-agnostic (SURVEY.md §5: "restore-compatible
across mesh shapes"): arrays are re-placed with whatever shardings the
caller's ``like`` state supplies; a vocab-padding mismatch (different
row-shard counts pad the table differently) is reconciled by re-padding
with the ``like`` state's init rows.  Format is auto-detected on restore
(directory = orbax, file = npz).
"""

from __future__ import annotations

import glob as _glob_mod
import json
import os
import re
import time
import uuid
import zipfile

import jax
import numpy as np

from fast_tffm_tpu.optim import AdagradState
from fast_tffm_tpu.trainer import TrainState

__all__ = [
    "save_checkpoint",
    "restore_checkpoint",
    "latest_step",
    "checkpoint_signature",
    "checkpoint_save_id",
    "read_publish_time",
    "save_delta",
    "read_delta_chain",
    "load_delta",
    "delta_paths",
    "read_input_cursor",
    "DEFAULT_CHUNK_BYTES",
]

# Host-staging bound for chunked D2H / disk streaming: a multi-GB table is
# fetched and written (or read and placed) this many bytes at a time, so
# saving/restoring never holds 2x the table on the host.
DEFAULT_CHUNK_BYTES = 64 << 20


def _maybe_publish_fault(path: str) -> None:
    """Chaos hook (resilience.FaultPlan ``kill_publish@K``): SIGKILL the
    writer between finishing the tmp file and the atomic rename — the
    torn-publish-under-kill window the pod failover tests exercise."""
    from fast_tffm_tpu.resilience import maybe_publish_fault

    maybe_publish_fault(path)


def _torn_error(path: str, what: str, exc: Exception) -> ValueError:
    """Torn/truncated checkpoint files must fail LOUDLY with the file
    named — a partial npz that half-parses could otherwise restore
    garbage weights into a training run (serving already counts+retries
    torn reads; training never had the pin)."""
    return ValueError(
        f"checkpoint file {path!r} is unreadable ({what}: {exc}) — "
        "truncated or torn write?  Saves are atomic (tmp + os.replace), so "
        "a complete save never looks like this; delete or replace the file"
    )


def _open_npz(path: str):
    """np.load with torn-file errors that NAME the file (np.load's bare
    BadZipFile/ValueError does not)."""
    try:
        return np.load(path, allow_pickle=False)
    except (zipfile.BadZipFile, ValueError, OSError, EOFError) as e:
        if isinstance(e, OSError) and not os.path.exists(path):
            raise
        raise _torn_error(path, type(e).__name__, e) from e


# ---------------------------------------------------------------------------
# npz format — chunked streaming writer/reader
# ---------------------------------------------------------------------------
#
# np.savez materializes every array on the host before writing; at the
# multi-GB-table scale that is 1x table of host staging ON TOP of the D2H
# fetch.  The writer below streams each array into the zip member in
# bounded row chunks (np.load reads the result exactly like a savez file),
# and the reader streams members back out in bounded chunks so restore can
# place slices on device without ever materializing the logical table on
# host.


def _npy_header_bytes(shape, dtype) -> bytes:
    import io

    from numpy.lib import format as npf

    buf = io.BytesIO()
    npf.write_array_header_1_0(
        buf,
        {"descr": npf.dtype_to_descr(np.dtype(dtype)), "fortran_order": False,
         "shape": tuple(int(s) for s in shape)},
    )
    return buf.getvalue()


def _array_row_chunks(arr, chunk_bytes: int):
    """Yield C-contiguous host chunks of ``arr`` (device or host), never
    staging more than ~chunk_bytes on the host at once.  The per-chunk
    ``np.asarray`` is where the (chunked) D2H transfer happens for device
    arrays."""
    a_shape = tuple(getattr(arr, "shape", ()))
    if not a_shape:
        yield np.ascontiguousarray(np.asarray(arr))
        return
    row_bytes = int(np.dtype(arr.dtype).itemsize) * int(
        np.prod(a_shape[1:], dtype=np.int64) or 1
    )
    rows = max(1, chunk_bytes // max(1, row_bytes))
    for lo in range(0, a_shape[0], rows):
        yield np.ascontiguousarray(np.asarray(arr[lo : lo + rows]))


def _write_npz_streaming(
    fileobj, entries: dict, chunk_bytes: int, timings: dict | None = None
) -> int:
    """Write a np.load-compatible npz (ZIP_STORED) from ``entries``
    (name -> array-like, possibly device-resident), streaming each array
    in bounded chunks.  Returns total payload bytes.  ``timings`` (if
    given) accumulates ``d2h_ms`` (chunk fetch) and ``write_ms`` (disk)."""
    total = 0
    with zipfile.ZipFile(fileobj, "w", zipfile.ZIP_STORED) as zf:
        for name, arr in entries.items():
            shape = tuple(getattr(arr, "shape", ()))
            dtype = np.asarray(arr).dtype if not hasattr(arr, "dtype") else arr.dtype
            with zf.open(name + ".npy", "w", force_zip64=True) as member:
                member.write(_npy_header_bytes(shape, dtype))
                # The D2H fetch happens in the generator ADVANCE (the
                # per-chunk np.asarray), so time the advance itself —
                # else d2h_ms reads ~0 and the transfer cost (the dominant
                # term at multi-GB scale) lands in neither bucket.
                it = _array_row_chunks(arr, chunk_bytes)
                while True:
                    t0 = time.perf_counter()
                    chunk = next(it, None)
                    t1 = time.perf_counter()
                    if chunk is None:
                        break
                    member.write(chunk)
                    t2 = time.perf_counter()
                    total += chunk.nbytes
                    if timings is not None:
                        timings["write_ms"] = timings.get("write_ms", 0.0) + (t2 - t1) * 1e3
                        timings["d2h_ms"] = timings.get("d2h_ms", 0.0) + (t1 - t0) * 1e3
    return total


def _npz_member_chunks(path: str, name: str, chunk_bytes: int):
    """Stream one npz member's rows in bounded host chunks:
    yields (shape, dtype) first, then row-chunk arrays.  Raises ValueError
    (naming the file) on truncation — a member shorter than its own header
    promises is a torn write, never silently-zero rows."""
    from numpy.lib import format as npf

    try:
        zf = zipfile.ZipFile(path)
    except (zipfile.BadZipFile, OSError, EOFError) as e:
        if isinstance(e, OSError) and not os.path.exists(path):
            raise
        raise _torn_error(path, type(e).__name__, e) from e
    with zf, zf.open(name + ".npy") as f:
        version = npf.read_magic(f)
        shape, fortran, dtype = npf._read_array_header(f, version)
        if fortran:
            raise ValueError(f"{path!r}: {name} is fortran-ordered (unsupported)")
        yield shape, dtype
        if not shape:
            raw = f.read(dtype.itemsize)
            if len(raw) < dtype.itemsize:
                raise _torn_error(path, "member truncated", ValueError(name))
            yield np.frombuffer(raw, dtype).reshape(())
            return
        row_bytes = int(dtype.itemsize) * int(np.prod(shape[1:], dtype=np.int64) or 1)
        rows_per = max(1, chunk_bytes // max(1, row_bytes))
        lo = 0
        while lo < shape[0]:
            n = min(rows_per, shape[0] - lo)
            raw = f.read(n * row_bytes)
            if len(raw) < n * row_bytes:
                raise _torn_error(
                    path,
                    f"member {name} truncated at row {lo}",
                    ValueError(f"expected {n * row_bytes} bytes, got {len(raw)}"),
                )
            yield np.frombuffer(raw, dtype).reshape((n,) + shape[1:])
            lo += n


def _chunked_device_place(path: str, name: str, target, chunk_bytes: int):
    """Stream npz member ``name`` straight onto ``target``'s device
    placement in bounded slices — the whole logical array never
    materializes on host (satellite: restore host-memory bound matches
    the writer's).  Only called when the saved shape equals the target's;
    returns the placed jax array."""
    from functools import partial as _p

    import jax.numpy as jnp

    gen = _npz_member_chunks(path, name, chunk_bytes)
    shape, dtype = next(gen)
    if not shape:
        return jax.device_put(next(gen), target.sharding)
    buf = jax.device_put(jnp.zeros(shape, dtype), target.sharding)

    @_p(jax.jit, donate_argnums=(0,), out_shardings=target.sharding)
    def _upd(b, chunk, start):
        return jax.lax.dynamic_update_slice_in_dim(b, chunk, start, axis=0)

    lo = 0
    for chunk in gen:
        buf = _upd(buf, chunk, np.int32(lo))
        lo += chunk.shape[0]
    return buf


def _cursor_entry(cursor: dict) -> np.ndarray:
    """The input-position cursor as an npz member: canonical JSON bytes
    (sort_keys so identical cursors are byte-identical members)."""
    return np.frombuffer(json.dumps(cursor, sort_keys=True).encode(), np.uint8)


def _save_npz(
    path: str,
    state: TrainState,
    *,
    chunk_bytes: int = DEFAULT_CHUNK_BYTES,
    save_id: str | None = None,
    timings: dict | None = None,
    cursor: dict | None = None,
) -> int:
    """Atomic full npz save.  Arrays stream to disk in bounded chunks
    (device arrays fetch chunk-by-chunk — never 2x table bytes on host).
    Embeds ``save_id`` (content identity for the delta chain), the
    optional ``cursor`` (the exact input position this state corresponds
    to — epoch, batch offset, shuffle identity; see training.py), and
    resets the chain: any sibling delta files are unlinked BEFORE the
    publish, so a crash between the two leaves the OLD base + OLD chain
    (or the old base alone) — always a complete, loadable checkpoint.
    Returns bytes written."""
    entries = {
        "table": state.table,
        "table_accum": state.table_opt.accum,
        "step": state.step,
        "save_id": np.frombuffer(
            (save_id or uuid.uuid4().hex).encode(), np.uint8
        ),
        # Publish event time (wall clock): the anchor every downstream
        # freshness SLO (publish→applied, publish→first-scored) measures
        # from.  Stamped at write start — the rename lands moments later,
        # so the serving-side latency INCLUDES the final write tail.
        "published_at": np.float64(time.time()),
    }
    if cursor is not None:
        entries["input_cursor"] = _cursor_entry(cursor)
    dense_leaves, _dense_def = jax.tree.flatten(state.dense)
    acc_leaves, _ = jax.tree.flatten(state.dense_opt.accum)
    for i, (p, a) in enumerate(zip(dense_leaves, acc_leaves)):
        entries[f"dense_{i}"] = p
        entries[f"dense_accum_{i}"] = a
    tmp = path + ".tmp"
    dirpart = os.path.dirname(path)
    if dirpart:
        os.makedirs(dirpart, exist_ok=True)
    with open(tmp, "wb") as f:
        nbytes = _write_npz_streaming(f, entries, chunk_bytes, timings)
    # Chain reset BEFORE the publish (see docstring for the crash window).
    for dp in delta_paths(path):
        try:
            os.remove(dp)
        except OSError:
            pass
    # Chaos injection point: a planned kill_publish fault SIGKILLs the
    # writer HERE — tmp fully written, rename not yet issued — the exact
    # window a real crash-during-publish leaves behind.  The atomic
    # os.replace below is why that window is safe: the old head (and the
    # old chain, already unlinked above for fulls) stays loadable.
    _maybe_publish_fault(path)
    os.replace(tmp, path)
    return nbytes


def _npz_string(z, key) -> str | None:
    if key not in getattr(z, "files", ()):
        return None
    return bytes(np.asarray(z[key]).tobytes()).decode()


def _load_npz(path: str, like: TrainState):
    with _open_npz(path) as z:
        if "tier_hot_ids" in getattr(z, "files", ()):
            # A tiered (paramstore) checkpoint's ``table`` member is only
            # the HOT tier — loading it as a full table would silently
            # score/train on a sliver of the model.
            raise ValueError(
                f"{path!r} is a TIERED parameter-store checkpoint (its "
                "'table' member holds only the device-resident hot rows; "
                "the cold tier lives in the run's .store directory) — "
                "resume it with [ParamStore] enabled; predict/serve need "
                "a resident export"
            )
        dense_leaves, _ = jax.tree.flatten(like.dense)
        try:
            return (
                z["table"],
                z["table_accum"],
                [z[f"dense_{i}"] for i in range(len(dense_leaves))],
                [z[f"dense_accum_{i}"] for i in range(len(dense_leaves))],
                z["step"],
            )
        except (KeyError, zipfile.BadZipFile, ValueError, EOFError) as e:
            raise _torn_error(path, "missing or unreadable member", e) from e


# ---------------------------------------------------------------------------
# delta chain (incremental checkpoints)
# ---------------------------------------------------------------------------
#
# Between full saves, `delta-NNNN` files carry only the rows a training
# window actually touched (plus the dense leaves, which every step
# updates) — Check-N-Run-style differential checkpointing.  Chain
# integrity is CONTENT-based, not name/mtime-based: every full save
# embeds a fresh `save_id`, every delta records its own `save_id` plus
# the `parent_sig` it extends (the base's save_id for delta 1, the
# previous delta's for the rest).  Restore replays base + chain in order
# and refuses a link whose parent_sig does not match — a stale or torn
# delta can never be silently applied.  Full saves unlink the chain
# before publishing, so the on-disk invariant is: the chain, when
# present, always roots at the current base.

_DELTA_RE = re.compile(r"\.delta-(\d{4})\.npz$")


def _delta_path(path: str, seq: int) -> str:
    return f"{path}.delta-{seq:04d}.npz"


def delta_paths(path: str) -> list[str]:
    """Existing delta files for ``path``, in chain (seq) order."""
    out = []
    # glob.escape: a model_file with glob metacharacters ('run[1]/m.ckpt')
    # must still find its own deltas — an unescaped glob would silently
    # return [] and restore the stale base.
    for p in _glob_mod.glob(_glob_mod.escape(path) + ".delta-*.npz"):
        m = _DELTA_RE.search(p)
        if m:
            out.append((int(m.group(1)), p))
    return [p for _, p in sorted(out)]


def save_delta(
    path: str,
    seq: int,
    *,
    idx: np.ndarray,
    table_rows,
    accum_rows,
    dense_leaves,
    dense_accum_leaves,
    step,
    parent_sig: str,
    save_id: str | None = None,
    chunk_bytes: int = DEFAULT_CHUNK_BYTES,
    timings: dict | None = None,
    cursor: dict | None = None,
) -> tuple[str, str, int]:
    """Atomically write delta file ``seq`` for base ``path``.  Carries
    the optional input ``cursor`` so the CHAIN HEAD always names the
    exact input position of the state it restores to.  Returns
    (delta_path, save_id, bytes_written)."""
    sid = save_id or uuid.uuid4().hex
    entries = {
        "delta_idx": np.asarray(idx, np.int64),
        "table_rows": table_rows,
        "accum_rows": accum_rows,
        "step": step,
        "parent_sig": np.frombuffer(parent_sig.encode(), np.uint8),
        "save_id": np.frombuffer(sid.encode(), np.uint8),
        # Same freshness anchor full saves carry (see _save_npz).
        "published_at": np.float64(time.time()),
    }
    if cursor is not None:
        entries["input_cursor"] = _cursor_entry(cursor)
    for i, (p, a) in enumerate(zip(dense_leaves, dense_accum_leaves)):
        entries[f"dense_{i}"] = p
        entries[f"dense_accum_{i}"] = a
    out = _delta_path(path, seq)
    tmp = out + ".tmp"
    with open(tmp, "wb") as f:
        nbytes = _write_npz_streaming(f, entries, chunk_bytes, timings)
    # Same crash window as the full save's: kill-before-rename leaves a
    # tmp file and an unchanged chain head (see _save_npz).
    _maybe_publish_fault(out)
    os.replace(tmp, out)
    return out, sid, nbytes


def load_delta(dp: str, n_dense: int) -> dict:
    """One delta file's full payload (host arrays).  Torn/truncated files
    raise a ValueError naming the file."""
    with _open_npz(dp) as z:
        try:
            return {
                "idx": np.asarray(z["delta_idx"]),
                "table_rows": np.asarray(z["table_rows"]),
                "accum_rows": np.asarray(z["accum_rows"]),
                "dense": [np.asarray(z[f"dense_{i}"]) for i in range(n_dense)],
                "dense_accum": [
                    np.asarray(z[f"dense_accum_{i}"]) for i in range(n_dense)
                ],
                "step": np.asarray(z["step"]),
                "parent_sig": _npz_string(z, "parent_sig"),
                "save_id": _npz_string(z, "save_id"),
            }
        except (KeyError, zipfile.BadZipFile, ValueError, EOFError) as e:
            raise _torn_error(dp, "missing or unreadable member", e) from e


def read_delta_chain(path: str) -> tuple[str | None, list[dict]]:
    """(base save_id, chain metadata) for ``path``'s delta files —
    metadata only (idx/step/sigs), no row payloads.  A delta whose
    parent_sig breaks the chain raises ValueError naming the file (full
    saves unlink the chain before publishing, so a mismatched link on
    disk is corruption, not staleness)."""
    base_sig = checkpoint_save_id(path)
    chain: list[dict] = []
    expect = base_sig
    for dp in delta_paths(path):
        with _open_npz(dp) as z:
            try:
                meta = {
                    "path": dp,
                    "parent_sig": _npz_string(z, "parent_sig"),
                    "save_id": _npz_string(z, "save_id"),
                    "step": int(z["step"]),
                    "rows": int(z["delta_idx"].shape[0]),
                }
            except (KeyError, zipfile.BadZipFile, ValueError, EOFError) as e:
                raise _torn_error(dp, "missing or unreadable member", e) from e
        if expect is None or meta["parent_sig"] != expect:
            raise ValueError(
                f"delta checkpoint {dp!r} does not chain from "
                f"{'the base ' + path if not chain else chain[-1]['path']!r} "
                f"(parent_sig {meta['parent_sig']!r} != expected {expect!r}) — "
                "stale or corrupt delta; delete the delta files or re-save a "
                "full checkpoint"
            )
        chain.append(meta)
        expect = meta["save_id"]
    return base_sig, chain


def read_publish_time(path: str) -> float | None:
    """Publish event time (wall clock, seconds) of ``path``'s CHAIN HEAD
    — the newest delta when incremental files extend the base, else the
    base itself.  None for orbax dirs, pre-PR-9 files (no ``published_at``
    member), or anything unreadable: freshness measurement degrades to
    absent, never to an error on an old checkpoint."""
    path = path.rstrip("/")
    if not os.path.isfile(path):
        return None
    deltas = delta_paths(path)
    head = deltas[-1] if deltas else path
    try:
        with _open_npz(head) as z:
            if "published_at" not in getattr(z, "files", ()):
                return None
            return float(z["published_at"])
    except (ValueError, OSError):
        return None


def checkpoint_save_id(path: str) -> str | None:
    """Content identity of a full npz checkpoint (None for orbax dirs,
    pre-save_id files, or missing files)."""
    path = path.rstrip("/")
    if not os.path.isfile(path):
        return None
    try:
        with _open_npz(path) as z:
            return _npz_string(z, "save_id")
    except ValueError:
        return None


# ---------------------------------------------------------------------------
# orbax format
# ---------------------------------------------------------------------------


_STEP_SIDECAR = "TRAIN_STEP"


def _save_orbax(path: str, state: TrainState) -> None:
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, state, force=True)
    ckptr.wait_until_finished()
    if jax.process_index() == 0:
        # Tiny sidecar (next to the dir — orbax owns the dir's contents) so
        # latest_step never has to restore the possibly larger-than-host-RAM
        # table just to read one scalar.
        with open(path + "." + _STEP_SIDECAR, "w") as f:
            f.write(str(int(state.step)))


def _orbax_metadata_item(path: str):
    """Checkpoint metadata tree (no data reads), fetched once per restore."""
    import orbax.checkpoint as ocp

    meta = ocp.StandardCheckpointer().metadata(os.path.abspath(path))
    return getattr(meta, "item_metadata", meta)


def _meta_field(item, name):
    return getattr(item, name) if hasattr(item, name) else item[name]


def _orbax_table_shape(path: str, item=None):
    """Saved table's global shape from checkpoint metadata."""
    if item is None:
        item = _orbax_metadata_item(path)
    return tuple(_meta_field(item, "table").shape)


def _orbax_accum_width(item):
    """Saved table accumulator's trailing dim from the metadata tree;
    None when the tree doesn't expose it (older orbax versions)."""
    try:
        return int(tuple(_meta_field(_meta_field(item, "table_opt"), "accum").shape)[-1])
    except Exception:
        return None


def _accum_mode_error(path: str, saved_width: int, want_width: int) -> ValueError:
    """Accumulator granularity is part of the optimizer's identity: a
    [V, D] element accumulator cannot serve a row-mode state (or vice
    versa) — silently proceeding would either ignore the configured mode
    or numpy-broadcast a fabricated accumulator in the re-pad path."""
    if saved_width > 1 and want_width > 1:
        # Both element-mode: the widths differ because the ROW width does
        # (factor_num / model change) — adagrad_accumulator is the wrong
        # knob for that.
        return ValueError(
            f"checkpoint {path!r} has accumulator rows of width {saved_width} "
            f"but this config expects width {want_width} — the model's row "
            "width changed (factor_num / model type); restore with the "
            "configuration the checkpoint was trained under"
        )
    mode = lambda d: "row" if d == 1 else "element"
    return ValueError(
        f"checkpoint {path!r} was trained with adagrad_accumulator = "
        f"{mode(saved_width)} (accum width {saved_width}) "
        f"but this config expects {mode(want_width)} "
        f"(width {want_width}); set adagrad_accumulator "
        "to match the checkpoint"
    )


def _restore_orbax_inplace(path: str, like: TrainState, meta_item=None):
    """Sharded restore straight onto ``like``'s placement (no host gather).

    Real restore failures (corrupt checkpoint, version mismatch) propagate;
    only a table-shape mismatch (vocab re-padding across mesh shapes) makes
    the caller take the host-side re-pad path, decided via metadata before
    any data is read.
    """
    import orbax.checkpoint as ocp

    if _orbax_table_shape(path, meta_item) != tuple(like.table.shape):
        return None
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), like
    )
    return ocp.StandardCheckpointer().restore(os.path.abspath(path), abstract)


def _load_orbax_host(path: str, like: TrainState):
    import orbax.checkpoint as ocp
    from jax.sharding import SingleDeviceSharding

    # Restore with an EXPLICIT target built from the checkpoint's own
    # metadata, every array placed whole on one local device: a bare
    # restore() replays the SAVED device topology and fails outright when
    # the checkpoint came from a different mesh/process count — exactly
    # the cross-topology case this host-side path exists for.  Land on
    # the CPU backend: this path only needs host RAM, and placing a
    # near-HBM-sized table whole on one accelerator device would OOM
    # device memory for no reason.  jax keeps a CPU backend beside the
    # accelerator unless JAX_PLATFORMS names the accelerator ALONE (the
    # TPU v5e hosts this was checked on export ``tpu,cpu``) — then there
    # is nowhere safe to land, and saying so beats silently spending HBM.
    ckptr = ocp.StandardCheckpointer()
    try:
        host = jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            f"restoring the orbax checkpoint {path!r} on the host needs "
            "jax's CPU backend, which JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r} excludes — add cpu to "
            "it (e.g. JAX_PLATFORMS=tpu,cpu)"
        ) from e
    dev = SingleDeviceSharding(host)
    abstract = jax.tree.map(
        lambda m: jax.ShapeDtypeStruct(tuple(m.shape), m.dtype, sharding=dev),
        _orbax_metadata_item(path),
    )
    raw = ckptr.restore(os.path.abspath(path), abstract)
    table = np.asarray(raw.table if hasattr(raw, "table") else raw["table"])
    if hasattr(raw, "table_opt"):
        accum = np.asarray(raw.table_opt.accum)
        dense = raw.dense
        dense_acc = raw.dense_opt.accum
        step = np.asarray(raw.step)
    else:
        accum = np.asarray(raw["table_opt"]["accum"])
        dense = raw["dense"]
        dense_acc = raw["dense_opt"]["accum"]
        step = np.asarray(raw["step"])
    dense_leaves = [np.asarray(x) for x in jax.tree.leaves(dense)]
    acc_leaves = [np.asarray(x) for x in jax.tree.leaves(dense_acc)]
    return table, accum, dense_leaves, acc_leaves, step


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def save_checkpoint(
    path: str,
    state: TrainState,
    format: str = "auto",
    *,
    chunk_bytes: int = DEFAULT_CHUNK_BYTES,
    save_id: str | None = None,
    timings: dict | None = None,
    cursor: dict | None = None,
) -> int | None:
    """Write ``state`` to ``path``; returns payload bytes for npz saves.

    format: 'npz' | 'orbax' | 'auto' (auto = orbax when the path looks like
    a directory target — trailing slash or '.orbax' suffix — else npz).
    npz saves stream arrays to disk in ``chunk_bytes`` host slices, embed
    ``save_id`` (the delta chain's content anchor) and the optional input
    ``cursor`` (exact-position resume — training.py), and reset any
    existing delta chain.  Orbax saves carry the cursor in a tiny JSON
    sidecar next to the directory (orbax owns the directory's contents).
    """
    if format == "auto":
        format = "orbax" if path.endswith((".orbax", "/")) or os.path.isdir(path) else "npz"
    if format == "orbax":
        _save_orbax(path.rstrip("/"), state)
        _write_cursor_sidecar(path.rstrip("/"), cursor)
        return None
    elif format == "npz":
        return _save_npz(
            path, state, chunk_bytes=chunk_bytes, save_id=save_id,
            timings=timings, cursor=cursor,
        )
    else:
        raise ValueError(f"unknown checkpoint format {format!r}")


_CURSOR_SIDECAR = "INPUT_CURSOR"


def _write_cursor_sidecar(path: str, cursor: dict | None) -> None:
    """Cursor sidecar for orbax directories (process 0 only — the same
    single-writer rule as the step sidecar).  A save WITHOUT a cursor
    removes any stale sidecar: a cursor must never outlive the state it
    described."""
    if jax.process_index() != 0:
        return
    sidecar = path + "." + _CURSOR_SIDECAR
    if cursor is None:
        try:
            os.remove(sidecar)
        except OSError:
            pass
        return
    tmp = sidecar + ".tmp"
    with open(tmp, "w") as f:
        json.dump(cursor, f, sort_keys=True)
    os.replace(tmp, sidecar)


def read_input_cursor(path: str) -> dict | None:
    """The input-position cursor of ``path``'s CHAIN HEAD (the newest
    delta when incremental files extend the base, else the base itself;
    the sidecar for orbax directories).  None when absent or unreadable —
    pre-cursor checkpoints restore with the legacy start-of-data
    behavior, never an error (forward compatibility)."""
    path = path.rstrip("/")
    if os.path.isdir(path):
        try:
            with open(path + "." + _CURSOR_SIDECAR) as f:
                out = json.load(f)
            return out if isinstance(out, dict) else None
        except (OSError, ValueError):
            return None
    if not os.path.isfile(path):
        return None
    deltas = delta_paths(path)
    head = deltas[-1] if deltas else path
    try:
        with _open_npz(head) as z:
            raw = _npz_string(z, "input_cursor")
        out = json.loads(raw) if raw else None
        return out if isinstance(out, dict) else None
    except (ValueError, OSError, json.JSONDecodeError):
        return None


def _npz_member_meta(path: str, name: str):
    """(shape, dtype) of one npz member from its header alone (no data)."""
    gen = _npz_member_chunks(path, name, 1)
    try:
        return next(gen)
    finally:
        gen.close()


def _apply_delta_to_arrays(table, accum, delta):
    """Scatter one delta's rows into (table, accum) — device arrays take a
    donated jitted scatter (no 2x-table transient), host arrays a numpy
    fancy-index write.  Returns the updated pair."""
    idx = delta["idx"]
    if idx.size == 0:
        return table, accum
    if isinstance(table, np.ndarray):
        keep = idx < table.shape[0]
        table[idx[keep]] = delta["table_rows"][keep]
        accum[idx[keep]] = delta["accum_rows"][keep]
        return table, accum
    from functools import partial as _p

    @_p(jax.jit, donate_argnums=(0,))
    def _scat(buf, i, rows):
        return buf.at[i].set(rows, mode="drop")

    i32 = idx.astype(np.int32)
    return _scat(table, i32, delta["table_rows"]), _scat(
        accum, i32, delta["accum_rows"]
    )


def _repad_to_like(table, accum, like: TrainState):
    """Mesh-shape change ⇒ different vocab padding: copy the overlapping
    rows into writable host copies of ``like``'s init arrays (the rare
    cross-mesh case keeps the simple full-materialize semantics)."""
    v = min(table.shape[0], like.table.shape[0])
    host_table = np.array(like.table)  # writable host copies
    host_accum = np.array(like.table_opt.accum)
    host_table[:v] = table[:v]
    host_accum[:v] = accum[:v]
    return host_table, host_accum


def _restore_npz(path: str, like: TrainState, chunk_bytes: int):
    """npz restore: chunked straight-to-device placement when the saved
    shapes match ``like``'s (bounded host staging — the satellite twin of
    the chunked writer), host re-pad otherwise; then the delta chain
    replays in order (content-signature checked)."""
    t_shape, _ = _npz_member_meta(path, "table")
    a_shape, _ = _npz_member_meta(path, "table_accum")
    if a_shape[-1] != like.table_opt.accum.shape[-1]:
        raise _accum_mode_error(path, a_shape[-1], like.table_opt.accum.shape[-1])
    dense_leaves, dense_def = jax.tree.flatten(like.dense)
    base_sig, chain = read_delta_chain(path)

    if t_shape == tuple(like.table.shape) and a_shape == tuple(
        like.table_opt.accum.shape
    ):
        table = _chunked_device_place(path, "table", like.table, chunk_bytes)
        accum = _chunked_device_place(
            path, "table_accum", like.table_opt.accum, chunk_bytes
        )
        with _open_npz(path) as z:
            try:
                new_dense = [np.asarray(z[f"dense_{i}"]) for i in range(len(dense_leaves))]
                new_accum = [
                    np.asarray(z[f"dense_accum_{i}"]) for i in range(len(dense_leaves))
                ]
                step = np.asarray(z["step"])
            except (KeyError, zipfile.BadZipFile, ValueError, EOFError) as e:
                raise _torn_error(path, "missing or unreadable member", e) from e
    else:
        table, accum, new_dense, new_accum, step = _load_npz(path, like)
        if table.shape[0] != like.table.shape[0]:
            table, accum = _repad_to_like(table, accum, like)
        else:
            table = np.array(table)
            accum = np.array(accum)

    for meta in chain:
        delta = load_delta(meta["path"], len(dense_leaves))
        if delta["accum_rows"].size and delta["accum_rows"].shape[-1] != a_shape[-1]:
            # Width check BEFORE the scatter — a mismatched delta must be
            # the actionable mode error, not a raw broadcast failure.
            raise _accum_mode_error(
                meta["path"], delta["accum_rows"].shape[-1], a_shape[-1]
            )
        table, accum = _apply_delta_to_arrays(table, accum, delta)
        new_dense = delta["dense"]
        new_accum = delta["dense_accum"]
        step = delta["step"]
    return table, accum, new_dense, new_accum, step


def restore_checkpoint(
    path: str, like: TrainState, *, chunk_bytes: int = DEFAULT_CHUNK_BYTES
) -> TrainState:
    """Load ``path`` into the structure (and shardings) of ``like``.

    ``like`` supplies the dense pytree structure and the target placement:
    each loaded array lands with the corresponding array's sharding, so a
    checkpoint written on one mesh restores onto another (or onto a single
    device).  Orbax checkpoints with matching shapes restore shard-parallel
    with no host gather.  npz restores stream the big arrays to device in
    ``chunk_bytes`` slices and then replay any delta chain
    (base + ``delta-NNNN`` files, content-signature checked) so the
    returned state is the chain head's.
    """
    path = path.rstrip("/")
    if os.path.isdir(path):
        # Mode mismatch first, from metadata alone: the inplace restore
        # would otherwise surface it as an opaque orbax shape error (or,
        # multi-host with a vocab-padding difference too, as the misleading
        # table-shape RuntimeError below).
        meta_item = _orbax_metadata_item(path)
        saved_width = _orbax_accum_width(meta_item)
        want_width = like.table_opt.accum.shape[-1]
        if saved_width is not None and saved_width != want_width:
            raise _accum_mode_error(path, saved_width, want_width)
        restored = _restore_orbax_inplace(path, like, meta_item)
        if restored is not None:
            return restored
        if jax.process_count() > 1:
            # The re-pad fallback materializes the table on every host and
            # writes through a host copy of `like` — both impossible once
            # shards live on non-addressable devices.  Fail with the remedy
            # rather than OOM-ing or crashing mid-gather.
            raise RuntimeError(
                f"checkpoint {path!r} has table shape {_orbax_table_shape(path)} "
                f"but this mesh expects {tuple(like.table.shape)} — multi-host "
                "restore needs a matching padded vocab (same row-shard count), "
                "or a single-host re-pad pass first"
            )
        table, table_accum, new_dense, new_accum, step = _load_orbax_host(path, like)
        if table_accum.shape[-1] != like.table_opt.accum.shape[-1]:
            raise _accum_mode_error(
                path, table_accum.shape[-1], like.table_opt.accum.shape[-1]
            )
        if table.shape[0] != like.table.shape[0]:
            table, table_accum = _repad_to_like(table, table_accum, like)
    else:
        table, table_accum, new_dense, new_accum, step = _restore_npz(
            path, like, chunk_bytes
        )

    def put(arr, target):
        if isinstance(arr, jax.Array):
            # Already placed by the chunked streaming path (or a delta
            # scatter on it) — re-fetching it to host just to put it back
            # would defeat the bounded-staging restore.
            if arr.sharding.is_equivalent_to(target.sharding, ndim=arr.ndim):
                return arr
            return jax.device_put(arr, target.sharding)
        return jax.device_put(np.asarray(arr), target.sharding)

    dense_leaves, dense_def = jax.tree.flatten(like.dense)
    return TrainState(
        table=put(table, like.table),
        table_opt=AdagradState(put(table_accum, like.table_opt.accum)),
        dense=jax.tree.unflatten(
            dense_def, [put(a, t) for a, t in zip(new_dense, dense_leaves)]
        ),
        dense_opt=AdagradState(
            jax.tree.unflatten(
                dense_def,
                [put(a, t) for a, t in zip(new_accum, jax.tree.leaves(like.dense_opt.accum))],
            )
        ),
        step=put(step, like.step),
    )


def checkpoint_signature(path: str) -> tuple | None:
    """Cheap change detector for the serving hot-reload watcher:
    (step, mtime_ns, size) of the checkpoint, or None when absent or
    unreadable.  Step alone would miss a same-step overwrite (a trainer
    re-saving after a rollback); mtime alone would miss nothing but says
    nothing — together with the size they identify a write without
    reading any array data.  npz saves are atomic (tmp + os.replace), so
    a changed signature on npz always names a COMPLETE file; orbax
    directories can be observed mid-write, which is why the watcher
    treats a failed restore as retry-next-tick, not an error."""
    path = path.rstrip("/")
    step = latest_step(path)
    if step is None:
        return None
    try:
        st = os.stat(path)
    except OSError:
        return None
    sig = [step, st.st_mtime_ns, st.st_size]
    # The delta chain is part of the checkpoint's identity: a new delta
    # landing (or the chain resetting under a full save) must change the
    # signature, or the serving watcher would never see incremental
    # progress.  Per-file (name, mtime, size) keeps this stat-only cheap.
    for dp in delta_paths(path):
        try:
            dst = os.stat(dp)
        except OSError:
            continue
        sig.append((os.path.basename(dp), dst.st_mtime_ns, dst.st_size))
    return tuple(sig)


def latest_step(path: str) -> int | None:
    """Step stored in a checkpoint — the DELTA CHAIN HEAD's step when
    incremental files extend the base — or None if absent/unreadable."""
    path = path.rstrip("/")
    if not os.path.exists(path):
        return None
    try:
        if os.path.isdir(path):
            with open(path + "." + _STEP_SIDECAR) as f:
                return int(f.read().strip())
        deltas = delta_paths(path)
        head = deltas[-1] if deltas else path
        with np.load(head) as z:
            return int(z["step"])
    except Exception:
        return None
