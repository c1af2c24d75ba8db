"""ctypes bindings for the native C++ libsvm parser.

Loads ``_libsvm_parser.so`` (built by csrc/Makefile) and exposes the same
``parse_lines`` contract as the pure-Python reference implementation in
data/libsvm.py.  Mirrors the reference's py/fm_ops.py, which
``tf.load_op_library``'d the compiled fm_ops.so — here the binding is plain
ctypes because the op consumes host NumPy buffers, not graph tensors.

If the shared library is absent (not built), ``load_native_parser`` returns
None and callers fall back to the Python parser.
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import sys

import numpy as np

from fast_tffm_tpu.data.libsvm import ParsedBatch

_SO_PATH = os.path.join(os.path.dirname(__file__), "_libsvm_parser.so")


def _find_csrc_dir() -> str | None:
    """csrc/ from a repo checkout / sdist build tree, or the copy setup.py
    places inside the package for wheel installs."""
    here = os.path.dirname(__file__)
    for cand in (
        os.path.join(here, os.pardir, os.pardir, "csrc"),
        os.path.join(here, os.pardir, "csrc"),
    ):
        if os.path.isfile(os.path.join(cand, "Makefile")):
            return cand
    return None


_CSRC_DIR = _find_csrc_dir()
_BUILD_ATTEMPTED = False


def _try_build() -> None:
    """Build the .so from csrc/ once per process if a toolchain is present.

    The reference shipped its kernels as a compile-it-yourself Makefile; here
    the build is a sub-second g++ invocation, so running it lazily on first
    use keeps the fast path on by default without a packaging step.  A
    failure (no make/g++, read-only tree, compile error) leaves the
    pure-Python parser in place and says so ONCE on stderr — a run that
    silently parses 10x slower looks like an input-bound device.
    """
    global _BUILD_ATTEMPTED
    if _BUILD_ATTEMPTED:
        return
    _BUILD_ATTEMPTED = True
    if _CSRC_DIR is None or not shutil.which("make"):
        print(
            "native libsvm parser not built (no csrc/Makefile or no `make` "
            "on PATH) — using the pure-Python parser",
            file=sys.stderr,
        )
        return
    # Build to a process-unique name, then atomically rename into place:
    # concurrent processes (multi-host pods share the filesystem) must never
    # dlopen a half-written ELF.
    tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["make", "-C", _CSRC_DIR, f"OUT={tmp}"],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, _SO_PATH)
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", b"") or b""
        print(
            f"native libsvm parser build failed ({e!r}) — using the "
            "pure-Python parser\n" + detail.decode(errors="replace")[-2000:],
            file=sys.stderr,
        )
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass

_ERRORS = {
    1: "empty line",
    2: "bad label",
    3: "bad token",
    4: "feature id out of range",
    5: "row wider than max_nnz",
    6: "read error (I/O failure mid-file, not clean EOF)",
}


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.fm_fnv1a64.restype = ctypes.c_uint64
    lib.fm_fnv1a64.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.fm_parse_shape.restype = None
    lib.fm_parse_shape.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.fm_parse_mt.restype = ctypes.c_int32
    lib.fm_parse_mt.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,  # n
        ctypes.c_int64,  # width
        ctypes.c_int64,  # vocabulary_size
        ctypes.c_int32,  # hash_feature_id
        ctypes.c_int32,  # threads
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),  # labels
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),  # ids
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),  # vals
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # fields
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # nnz
        ctypes.POINTER(ctypes.c_int64),  # error_line
    ]
    lib.fm_reader_open.restype = ctypes.c_void_p
    lib.fm_reader_open.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,  # shard_index
        ctypes.c_int64,  # shard_count
        ctypes.c_int64,  # counter_start
    ]
    lib.fm_reader_open2.restype = ctypes.c_void_p
    lib.fm_reader_open2.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,  # shard_index
        ctypes.c_int64,  # shard_count
        ctypes.c_int64,  # shard_block
        ctypes.c_int64,  # counter_start
    ]
    lib.fm_count_lines.restype = ctypes.c_int64
    lib.fm_count_lines.argtypes = [ctypes.c_char_p]
    lib.fm_scan_file.restype = ctypes.c_int32
    lib.fm_scan_file.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),  # n_lines
        ctypes.POINTER(ctypes.c_int64),  # widest
    ]
    lib.fm_reader_counter.restype = ctypes.c_int64
    lib.fm_reader_counter.argtypes = [ctypes.c_void_p]
    lib.fm_reader_close.restype = None
    lib.fm_reader_close.argtypes = [ctypes.c_void_p]
    lib.fm_reader_next.restype = ctypes.c_int64
    lib.fm_reader_next.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,  # want
        ctypes.c_int64,  # width
        ctypes.c_int64,  # vocabulary_size
        ctypes.c_int32,  # hash_feature_id
        ctypes.c_int32,  # threads
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),  # labels
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),  # ids
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),  # vals
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # fields
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # nnz
        ctypes.POINTER(ctypes.c_int32),  # error_code
        ctypes.POINTER(ctypes.c_int64),  # error_line
    ]
    lib.fm_reader_next32.restype = ctypes.c_int64
    lib.fm_reader_next32.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,  # want
        ctypes.c_int64,  # width
        ctypes.c_int64,  # vocabulary_size
        ctypes.c_int32,  # hash_feature_id
        ctypes.c_int32,  # threads
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),  # labels
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # ids (int32!)
        np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),  # vals
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # fields
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # nnz
        ctypes.POINTER(ctypes.c_int32),  # error_code
        ctypes.POINTER(ctypes.c_int64),  # error_line
    ]
    try:
        # Wire-v2 constant detection: absent from .so's built before the
        # symbol existed — optional, so a prebuilt library on a box with
        # no toolchain keeps parsing (callers fall back to numpy).
        lib.fm_vals_all_ones.restype = ctypes.c_int32
        lib.fm_vals_all_ones.argtypes = [
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),  # vals
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),  # nnz
            ctypes.c_int64,  # n
            ctypes.c_int64,  # width
        ]
    except AttributeError:
        pass
    return lib


def usable_cores() -> int:
    """Cores THIS process may run on — cgroup/affinity-aware where the OS
    exposes it (a containerized pod worker pinned to 8 of 64 cores must
    size its pool at 8, not 64)."""
    try:
        return len(os.sched_getaffinity(0)) or 1
    except AttributeError:  # non-Linux
        return os.cpu_count() or 1


class NativeParser:
    """Callable with the signature of ``libsvm.parse_lines``.

    ``threads`` spreads the parse over an in-kernel std::thread pool — the
    analog of the reference trainer's cfg-driven parse-thread count, but
    inside one GIL-released ctypes call instead of TF queue-runner threads.
    ``threads=0`` (the default) uses every USABLE core: a pod host feeding
    4-8 chips needs the full parse bandwidth, and the pool only spins up
    when a batch is large enough to pay for it (parse_spans_mt in
    csrc/libsvm_parser.cpp).
    """

    def __init__(self, lib: ctypes.CDLL, threads: int = 0):
        self._lib = lib
        if threads < 0:
            # Mirror config.validate: a negative count is a bug upstream,
            # not a request for every core.
            raise ValueError(f"threads must be >= 0 (0 = all cores), got {threads}")
        self.threads = int(threads) if threads > 0 else usable_cores()

    def fnv1a64(self, token: bytes) -> int:
        return int(self._lib.fm_fnv1a64(token, len(token)))

    def vals_all_ones(self, vals, nnz) -> bool:
        """In-kernel twin of data/wire.py's ``vals_all_ones`` (the wire-v2
        convert-time constant detection); numpy fallback when the loaded
        .so predates the symbol."""
        vals = np.ascontiguousarray(vals, np.float32)
        nnz = np.ascontiguousarray(nnz, np.int32)
        if not hasattr(self._lib, "fm_vals_all_ones"):
            from fast_tffm_tpu.data.wire import vals_all_ones

            return vals_all_ones(vals, nnz)
        n, width = vals.shape
        return bool(self._lib.fm_vals_all_ones(vals, nnz, n, width))

    def __call__(
        self,
        lines: list[str],
        *,
        vocabulary_size: int,
        hash_feature_id_flag: bool = False,
        max_nnz: int | None = None,
    ) -> ParsedBatch:
        buf = ("\n".join(lines)).encode("utf-8")
        n = len(lines)
        if max_nnz is not None:
            width = max_nnz
        else:
            n_lines = ctypes.c_int64()
            widest = ctypes.c_int64()
            self._lib.fm_parse_shape(buf, ctypes.byref(n_lines), ctypes.byref(widest))
            width = max(int(widest.value), 1)
        labels = np.zeros((n,), np.float32)
        ids = np.zeros((n, width), np.int64)
        vals = np.zeros((n, width), np.float32)
        fields = np.zeros((n, width), np.int32)
        nnz = np.zeros((n,), np.int32)
        err_line = ctypes.c_int64(-1)
        code = self._lib.fm_parse_mt(
            buf,
            n,
            width,
            vocabulary_size,
            1 if hash_feature_id_flag else 0,
            self.threads,
            labels,
            ids,
            vals,
            fields,
            nnz,
            ctypes.byref(err_line),
        )
        if code != 0:
            raise ValueError(
                f"{_ERRORS.get(code, f'error {code}')} at line {err_line.value}"
            )
        return ParsedBatch(labels=labels, ids=ids, vals=vals, fields=fields, nnz=nnz)


def native_batch_stream(
    parser: "NativeParser",
    files,
    *,
    batch_size: int,
    vocabulary_size: int,
    hash_feature_id: bool = False,
    max_nnz: int,
    epochs: int = 1,
    shard_index: int = 0,
    shard_count: int = 1,
    shard_block: int = 1,
    weights=None,
    drop_remainder: bool = False,
    pad_to_batches: int | None = None,
):
    """Stream (ParsedBatch, example_weights) batches entirely through C++.

    Same contract as ``pipeline.batch_stream`` (epoch repeats, per-file
    example weights, block-cyclic line sharding by global non-blank line
    index, zero-padded short final batch with weight-0 rows, optional
    pad_to_batches for fixed multi-host step counts), but the file reading,
    line splitting, sharding, and parsing all happen inside
    ``fm_reader_next`` — the Python side only schedules files and yields
    filled NumPy buffers.  Batches freely span file and epoch boundaries,
    exactly like the Python generator chain.
    """
    if weights is not None and len(weights) != len(files):
        raise ValueError(f"weights has {len(weights)} entries for {len(files)} files")
    if shard_block > 1 and epochs != 1:
        raise ValueError(
            "shard_block > 1 requires epochs == 1 (batch-aligned sharding "
            "does not survive epoch boundaries); create one stream per epoch"
        )
    lib = parser._lib
    width = int(max_nnz)
    # int32 ids whenever the vocabulary fits (always, for the device batch:
    # TPU gathers index with int32) — halves the largest buffer/transfer
    # and skips the astype copy in Batch.from_parsed.
    ids_dtype = np.int32 if vocabulary_size <= np.iinfo(np.int32).max else np.int64
    reader_next = lib.fm_reader_next32 if ids_dtype is np.int32 else lib.fm_reader_next

    def alloc():
        return (
            np.zeros((batch_size,), np.float32),
            np.zeros((batch_size, width), ids_dtype),
            np.zeros((batch_size, width), np.float32),
            np.zeros((batch_size, width), np.int32),
            np.zeros((batch_size,), np.int32),
            np.zeros((batch_size,), np.float32),
        )

    labels, ids, vals, fields, nnz, w = alloc()
    filled = 0
    emitted = 0
    counter = 0  # global non-blank line index, threaded through every file
    for _ in range(max(0, epochs)):
        for fi, path in enumerate(files):
            fw = 1.0 if weights is None else float(weights[fi])
            handle = lib.fm_reader_open2(
                os.fspath(path).encode(),
                shard_index,
                shard_count,
                max(1, shard_block),
                counter,
            )
            if not handle:
                raise FileNotFoundError(path)
            try:
                while True:
                    want = batch_size - filled
                    ec = ctypes.c_int32(0)
                    el = ctypes.c_int64(-1)
                    got = reader_next(
                        handle,
                        want,
                        width,
                        vocabulary_size,
                        1 if hash_feature_id else 0,
                        parser.threads,
                        labels[filled:],
                        ids[filled:],
                        vals[filled:],
                        fields[filled:],
                        nnz[filled:],
                        ctypes.byref(ec),
                        ctypes.byref(el),
                    )
                    if got < 0:
                        # el is relative to THIS fm_reader_next call, which
                        # writes at offset `filled`; report the batch row.
                        where = (
                            f" (batch row {filled + el.value})" if el.value >= 0 else ""
                        )
                        raise ValueError(
                            f"{_ERRORS.get(ec.value, f'error {ec.value}')} in {path}{where}"
                        )
                    w[filled : filled + got] = fw
                    filled += int(got)
                    if filled == batch_size:
                        yield ParsedBatch(labels, ids, vals, fields, nnz), w
                        emitted += 1
                        labels, ids, vals, fields, nnz, w = alloc()
                        filled = 0
                        if pad_to_batches is not None and emitted >= pad_to_batches:
                            return
                        continue
                    break  # got < want: file exhausted
            finally:
                counter = int(lib.fm_reader_counter(handle))
                lib.fm_reader_close(handle)
    from fast_tffm_tpu.data.pipeline import emit_assembled_tail

    yield from emit_assembled_tail(
        alloc, (labels, ids, vals, fields, nnz, w), filled, emitted,
        drop_remainder, pad_to_batches,
    )


# (path, mtime_ns, size) -> (n_lines, widest).  Startup calls scan_files /
# count_lines on overlapping file sets (static width scan, then multi-host
# steps-per-epoch on train and again on validation files); caching per file
# keeps that one streaming pass each.  Entries invalidate when the file
# changes; the table stays tiny (one tuple per data file).
_scan_cache: dict[tuple[str, int, int], tuple[int, int]] = {}


def _scan_one(path) -> tuple[int, int]:
    path = os.fspath(path)
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    hit = _scan_cache.get(key)
    if hit is not None:
        return hit
    from fast_tffm_tpu.data.binary import _read_header, is_fmb

    if is_fmb(path):
        # Header-only read (64 bytes) — no reason to memmap the data
        # sections here.  Prefer the recorded widest ACTUAL row over the
        # stored width (the converter's possibly-generous --max-nnz
        # padding choice), so an auto-derived training max_nnz doesn't
        # inherit padding; 0 means a pre-field file, where only the
        # stored width is trustworthy.
        n_rows, width, _v, _h, _i, _s, _m, widest, _f, _ver = _read_header(path)
        out = (n_rows, widest if widest > 0 else width)
        _scan_cache[key] = out
        return out
    native = load_native_parser()
    if native is not None:
        n = ctypes.c_int64()
        w = ctypes.c_int64()
        if native._lib.fm_scan_file(path.encode(), ctypes.byref(n), ctypes.byref(w)):
            raise OSError(f"cannot read {path}")
        out = (n.value, w.value)
    else:
        total, widest = 0, 0
        with open(path, "r") as f:
            for line in f:
                toks = len(line.split())
                if toks > 0:
                    total += 1
                    widest = max(widest, toks - 1)
        out = (total, widest)
    _scan_cache[key] = out
    return out


def scan_files(files) -> tuple[int, int]:
    """(total non-blank lines, widest row nnz) across ``files`` in ONE
    streaming pass per file (C++ when the native library is built, buffered
    Python otherwise; per-file results cached by (path, mtime, size)).
    Serves both the multi-host steps-per-epoch count and the static batch
    width (``max_nnz = 0`` config scan)."""
    total, widest = 0, 0
    for path in files:
        n, w = _scan_one(path)
        total += n
        widest = max(widest, w)
    return total, widest


def count_lines(files) -> int:
    """Total non-blank lines across ``files``.

    Uses cached scan_files results when present; a cold count-only call
    takes the cheaper fm_count_lines path (per-line is_blank check instead
    of tokenizing every byte)."""
    native = load_native_parser()
    total = 0
    for path in files:
        path = os.fspath(path)
        st = os.stat(path)
        hit = _scan_cache.get((path, st.st_mtime_ns, st.st_size))
        if hit is not None:
            total += hit[0]
            continue
        from fast_tffm_tpu.data.binary import is_fmb

        if is_fmb(path):
            total += _scan_one(path)[0]
        elif native is not None:
            n = int(native._lib.fm_count_lines(path.encode()))
            if n < 0:
                raise OSError(f"cannot read {path}")
            total += n
        else:
            with open(path, "r") as f:
                total += sum(1 for line in f if line.strip())
    return total


def _stale() -> bool:
    """True when the .so is missing or older than any csrc/ source file."""
    if not os.path.exists(_SO_PATH):
        return True
    if _CSRC_DIR is None:
        return False
    so_mtime = os.path.getmtime(_SO_PATH)
    try:
        entries = os.listdir(_CSRC_DIR)
    except OSError:
        return False
    return any(
        e.endswith((".cpp", ".h")) and os.path.getmtime(os.path.join(_CSRC_DIR, e)) > so_mtime
        for e in entries
    )


def load_native_parser(threads: int = 0) -> NativeParser | None:
    """Load the C++ parser, (re)building it on first use; None → Python fallback."""
    if _stale():
        _try_build()
    if not os.path.exists(_SO_PATH):
        return None
    try:
        lib = _bind(ctypes.CDLL(_SO_PATH))
    except (OSError, AttributeError):
        # AttributeError: a stale pre-fm_parse_mt .so — rebuild next process.
        return None
    return NativeParser(lib, threads)


def parser_name() -> str:
    """``native`` when the C++ parser loads (building it if needed),
    ``python`` otherwise — named on every run's device line."""
    return "python" if load_native_parser() is None else "native"


def best_parser(threads: int = 0):
    """The fastest available parser honoring the parse_lines contract."""
    native = load_native_parser(threads)
    if native is not None:
        return native
    from fast_tffm_tpu.data.libsvm import parse_lines

    return parse_lines
