"""Packed wire format (`wire_format = packed`): bit-exactness pins.

The load-bearing property: the packed wire may only change HOW a batch
crosses the host→device link (one coalesced byte buffer, elided
tensors), never a single bit of WHAT arrives — every reconstructed
Batch leaf equals the classic array staging bitwise, and therefore
train losses, final states, and predict scores match bitwise against
``wire_format = arrays`` on every consumer (streamed superbatch,
device-cached, sharded/SPMD) and every steps_per_call.
"""

import json

import numpy as np
import pytest

import jax

from fast_tffm_tpu.config import Config
from fast_tffm_tpu.data.binary import (
    FLAG_FIELDS_ALL_ZERO,
    FLAG_VALS_ALL_ONES,
    fmb_stats,
    fmb_wire_flags,
    open_fmb,
    write_fmb,
)
from fast_tffm_tpu.data.libsvm import parse_lines
from fast_tffm_tpu.data.wire import (
    WireConverter,
    arrays_nbytes,
    bytes_for,
    make_spec,
    pack_batch,
    vals_all_ones,
)
from fast_tffm_tpu.models.base import Batch
from fast_tffm_tpu.training import train

VOCAB = 1000


def _random_parsed(rng, rows=9, width=8, ones=False, with_fields=False, vocab=VOCAB):
    lines = []
    for _ in range(rows):
        nnz = int(rng.integers(1, width - 1))
        toks = []
        for _ in range(nnz):
            val = 1 if ones else round(float(rng.normal()), 4)
            fid = rng.integers(vocab // 2, vocab)  # the id's top byte is in use
            toks.append(f"{rng.integers(0, 4)}:{fid}:{val}" if with_fields else f"{fid}:{val}")
        lines.append(f"{rng.integers(0, 2)} {' '.join(toks)}")
    return parse_lines(lines, vocabulary_size=vocab, max_nnz=width)


def _assert_batches_equal(got: Batch, ref: Batch):
    for name in ("labels", "ids", "vals", "fields", "weights"):
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(ref, name))
        assert a.dtype == b.dtype, (name, a.dtype, b.dtype)
        np.testing.assert_array_equal(a, b, err_msg=name)


# --- pack/unpack bit-parity ----------------------------------------------


@pytest.mark.parametrize("with_fields", [False, True])
@pytest.mark.parametrize("with_weights", [False, True])
@pytest.mark.parametrize("vocab", [VOCAB, 1 << 20, 1 << 26], ids=["ids2", "ids3", "ids4"])
def test_roundtrip_explicit_vals(with_fields, with_weights, vocab):
    """Every id width: 3 bytes (a table of up to 2^24 rows) is read on the
    device like the others, byte planes by strided slices (wire.make_unpacker)."""
    rng = np.random.default_rng(0)
    p = _random_parsed(rng, with_fields=with_fields, vocab=vocab)
    w = np.ones((p.batch_size,), np.float32)
    if with_weights:
        w[:] = 0.25  # non-uniform per-file weight
    spec = make_spec(
        vocab, p.max_nnz, with_vals=True, with_fields=with_fields,
        with_weights=with_weights,
    )
    assert spec.id_bytes == {VOCAB: 2, 1 << 20: 3, 1 << 26: 4}[vocab]
    got = WireConverter(spec)(p, w)
    ref = Batch.from_parsed(p, w, with_fields=with_fields)
    _assert_batches_equal(got, ref)


def test_roundtrip_elided_vals_and_padding_rows():
    rng = np.random.default_rng(1)
    p = _random_parsed(rng, ones=True)
    w = np.ones((p.batch_size,), np.float32)
    # Short-tail padding: zero rows with weight 0 at the suffix, exactly
    # what pad_batch / the assembled streams emit.
    w[-3:] = 0.0
    p.labels[-3:] = 0
    p.ids[-3:] = 0
    p.vals[-3:] = 0
    p.fields[-3:] = 0
    p.nnz[-3:] = 0
    spec = make_spec(VOCAB, p.max_nnz, with_vals=False, with_fields=False)
    got = WireConverter(spec)(p, w)
    _assert_batches_equal(got, Batch.from_parsed(p, w, with_fields=False))


def test_roundtrip_superbatch_and_tail_group():
    rng = np.random.default_rng(2)
    ps = [_random_parsed(rng) for _ in range(3)]
    ws = [np.ones((p.batch_size,), np.float32) for p in ps]
    spec = make_spec(VOCAB, ps[0].max_nnz, with_vals=True, with_fields=False)
    conv = WireConverter(spec)
    got = conv(ps, ws)
    ref = Batch.stack_parsed(ps, ws, with_fields=False)
    _assert_batches_equal(got, ref)
    # The epoch-tail group is shorter in K — same spec, same unpacker.
    _assert_batches_equal(
        conv(ps[:1], ws[:1]), Batch.stack_parsed(ps[:1], ws[:1], with_fields=False)
    )


def test_roundtrip_float_bit_patterns():
    """Raw-byte f32 shipping must preserve every bit pattern (inf, huge,
    denormal, negative zero) — bitcast, not value round-trip."""
    p = parse_lines(["1 3:2.5 4:1"], vocabulary_size=VOCAB, max_nnz=4)
    special = np.array([np.inf, -0.0, 1e-41, 3.4e38], np.float32)
    p.vals[0, :] = special
    spec = make_spec(VOCAB, 4, with_vals=True, with_fields=False)
    got = WireConverter(spec)(p, np.ones((1,), np.float32))
    np.testing.assert_array_equal(
        np.asarray(got.vals).view(np.uint32)[0], special.view(np.uint32)
    )


def test_wire_width_and_savings():
    assert [bytes_for(x) for x in (1, 255, 256, 65535, 65536, (1 << 24) - 1, 1 << 24)] == [
        1, 1, 2, 2, 3, 3, 4,
    ]
    # The acceptance regime: Criteo-hash vocab 2^24, nnz 39, all-ones FM.
    spec = make_spec(1 << 24, 39, with_vals=False, with_fields=False)
    assert spec.id_bytes == 3 and spec.nnz_bytes == 1
    cut = arrays_nbytes(1, 39, False) / spec.row_bytes
    assert cut >= 2.5, f"wire cut {cut:.2f}x < 2.5x on the all-ones workload"


def test_pack_rejects_broken_elision_assumptions():
    rng = np.random.default_rng(3)
    p = _random_parsed(rng)  # random vals, NOT all ones
    w = np.ones((p.batch_size,), np.float32)
    with pytest.raises(ValueError, match="all-ones"):
        pack_batch(make_spec(VOCAB, p.max_nnz, with_vals=False, with_fields=False), p, w)
    w2 = w.copy()
    w2[0] = 0.0  # weight hole — not the prefix pattern
    with pytest.raises(ValueError, match="prefix"):
        pack_batch(make_spec(VOCAB, p.max_nnz, with_vals=True, with_fields=False), p, w2)
    p.labels[0] = 0.5
    with pytest.raises(ValueError, match="labels"):
        pack_batch(make_spec(VOCAB, p.max_nnz, with_vals=True, with_fields=False), p, w)


def test_pack_rejects_ids_wider_than_spec():
    """Narrowing must raise on out-of-range ids, never alias them onto a
    different valid row (a spec built for the wrong vocabulary)."""
    p = parse_lines(["1 900:1"], vocabulary_size=VOCAB, max_nnz=2)
    small = make_spec(256, 2, with_vals=True, with_fields=False)  # id_bytes=1
    assert small.id_bytes == 1
    with pytest.raises(ValueError, match="id_bytes"):
        pack_batch(small, p, np.ones((1,), np.float32))


def test_vals_all_ones_detector():
    p = parse_lines(["1 3:1 4:1", "0 5:1"], vocabulary_size=VOCAB, max_nnz=4)
    assert vals_all_ones(p.vals, p.nnz)
    p.vals[0, 0] = 2.0
    assert not vals_all_ones(p.vals, p.nnz)
    # A 1.0 in a padding slot is NOT the pattern (nnz says empty).
    p2 = parse_lines(["1 3:1"], vocabulary_size=VOCAB, max_nnz=4)
    p2.vals[0, 3] = 1.0
    assert not vals_all_ones(p2.vals, p2.nnz)


def test_native_parser_all_ones_matches_numpy():
    from fast_tffm_tpu.data.native import load_native_parser

    native = load_native_parser()
    if native is None:
        pytest.skip("native parser not built")
    rng = np.random.default_rng(4)
    for ones in (True, False):
        p = _random_parsed(rng, ones=ones)
        assert native.vals_all_ones(p.vals, p.nnz) == vals_all_ones(p.vals, p.nnz)


# --- FMB v2 flags ---------------------------------------------------------


def _write_text(path, rows, rng, ones=False):
    with open(path, "w") as f:
        for _ in range(rows):
            nnz = rng.integers(1, 8)
            toks = [
                f"{rng.integers(0, VOCAB)}:{1 if ones else round(float(rng.normal()), 4)}"
                for _ in range(nnz)
            ]
            f.write(f"{rng.integers(0, 2)} {' '.join(toks)}\n")
    return str(path)


def test_write_fmb_sets_v2_flags(tmp_path):
    rng = np.random.default_rng(5)
    ones_src = _write_text(tmp_path / "ones.libsvm", 40, rng, ones=True)
    mix_src = _write_text(tmp_path / "mix.libsvm", 40, rng, ones=False)
    f1 = open_fmb(write_fmb(ones_src, ones_src + ".fmb", vocabulary_size=VOCAB))
    f2 = open_fmb(write_fmb(mix_src, mix_src + ".fmb", vocabulary_size=VOCAB))
    assert f1.flags & FLAG_VALS_ALL_ONES
    assert f1.flags & FLAG_FIELDS_ALL_ZERO
    assert not (f2.flags & FLAG_VALS_ALL_ONES)
    # Stream-level AND: one explicit-vals file disables elision for all.
    assert fmb_wire_flags([f1.path]) == (True, True)
    assert fmb_wire_flags([f1.path, f2.path]) == (False, True)
    assert fmb_wire_flags([f1.path, "/nonexistent"]) == (False, False)


def test_fmb_stats_fractions(tmp_path):
    rng = np.random.default_rng(6)
    src = _write_text(tmp_path / "ones.libsvm", 30, rng, ones=True)
    st = fmb_stats(write_fmb(src, src + ".fmb", vocabulary_size=VOCAB))
    assert st["vals_all_ones_fraction"] == 1.0
    assert st["fields_zero_fraction"] == 1.0
    assert st["projected_wire_cut_x"] > 2.0
    mix = _write_text(tmp_path / "mix.libsvm", 30, rng, ones=False)
    st2 = fmb_stats(write_fmb(mix, mix + ".fmb", vocabulary_size=VOCAB))
    assert st2["vals_all_ones_fraction"] < 1.0
    assert st2["projected_wire_cut_x"] > 1.0  # coalescing + narrow ids still win


# --- driver-level parity: packed vs arrays, every consumer ----------------


@pytest.fixture()
def ones_fmb(tmp_path):
    """All-ones FMB train set (the vals-elision regime) + a small
    explicit-vals validation file."""
    rng = np.random.default_rng(42)
    out = []
    for name, rows in (("a", 83), ("b", 41)):  # 124 rows / B=32, tail batch
        src = _write_text(tmp_path / f"{name}.libsvm", rows, rng, ones=True)
        out.append(write_fmb(src, src + ".fmb", vocabulary_size=VOCAB))
    return out


def _cfg(tmp_path, files, tag, **kw):
    base = dict(
        model="fm",
        factor_num=4,
        vocabulary_size=VOCAB,
        model_file=str(tmp_path / f"model_{tag}.ckpt"),
        train_files=tuple(files),
        epoch_num=2,
        batch_size=32,
        learning_rate=0.05,
        log_every=2,
        metrics_path=str(tmp_path / f"m_{tag}.jsonl"),
    )
    base.update(kw)
    return Config(**base).validate()


def _records(path):
    return [json.loads(line) for line in open(path).read().splitlines()]


def _losses(path):
    return [r["loss"] for r in _records(path) if "loss" in r]


def _assert_state_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.table), np.asarray(b.table))
    if a.table_opt.accum.size:
        np.testing.assert_array_equal(
            np.asarray(a.table_opt.accum), np.asarray(b.table_opt.accum)
        )
    assert int(a.step) == int(b.step)


def test_train_wire_parity_streamed(tmp_path, ones_fmb):
    silent = lambda *a: None
    s_arr = train(_cfg(tmp_path, ones_fmb, "warr", wire_format="arrays"), log=silent)
    s_pkd = train(_cfg(tmp_path, ones_fmb, "wpkd", wire_format="packed"), log=silent)
    _assert_state_equal(s_arr, s_pkd)
    assert _losses(tmp_path / "m_warr.jsonl") == _losses(tmp_path / "m_wpkd.jsonl")


def test_train_wire_parity_steps_per_call(tmp_path, ones_fmb):
    """K=8 fused superbatches ride the same wire: packed vs arrays stays
    bitwise at K>1, and K=8-packed equals K=1-arrays (fusion x wire)."""
    silent = lambda *a: None
    s_k1 = train(_cfg(tmp_path, ones_fmb, "wk1", wire_format="arrays"), log=silent)
    s_k8a = train(
        _cfg(tmp_path, ones_fmb, "wk8a", wire_format="arrays", steps_per_call=8),
        log=silent,
    )
    s_k8p = train(
        _cfg(tmp_path, ones_fmb, "wk8p", wire_format="packed", steps_per_call=8),
        log=silent,
    )
    _assert_state_equal(s_k1, s_k8a)
    _assert_state_equal(s_k8a, s_k8p)
    assert _losses(tmp_path / "m_wk8a.jsonl") == _losses(tmp_path / "m_wk8p.jsonl")


def test_train_wire_parity_device_cache(tmp_path, ones_fmb):
    """The device-cached consumer (no per-step wire at all) lands on the
    same bits as the packed-wire streamed path."""
    silent = lambda *a: None
    s_dc = train(_cfg(tmp_path, ones_fmb, "wdc", device_cache=True), log=silent)
    s_pkd = train(_cfg(tmp_path, ones_fmb, "wstr", wire_format="packed"), log=silent)
    _assert_state_equal(s_dc, s_pkd)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")
def test_dist_train_wire_parity(tmp_path, ones_fmb):
    from fast_tffm_tpu.parallel import make_mesh
    from fast_tffm_tpu.training import dist_train

    silent = lambda *a: None
    s_arr = dist_train(
        _cfg(tmp_path, ones_fmb, "darr", wire_format="arrays"),
        log=silent, mesh=make_mesh(2, 4),
    )
    s_pkd = dist_train(
        _cfg(tmp_path, ones_fmb, "dpkd", wire_format="packed"),
        log=silent, mesh=make_mesh(2, 4),
    )
    _assert_state_equal(s_arr, s_pkd)
    assert _losses(tmp_path / "m_darr.jsonl") == _losses(tmp_path / "m_dpkd.jsonl")


def test_predict_wire_parity(tmp_path, ones_fmb):
    from fast_tffm_tpu.prediction import predict

    silent = lambda *a: None
    train(_cfg(tmp_path, ones_fmb, "wpre"), log=silent)
    base = _cfg(tmp_path, ones_fmb, "wpre")
    import dataclasses

    scores = {}
    for wf in ("arrays", "packed"):
        cfg = dataclasses.replace(
            base,
            wire_format=wf,
            predict_files=tuple(ones_fmb),
            score_path=str(tmp_path / f"scores_{wf}.txt"),
        ).validate()
        predict(cfg, log=silent)
        scores[wf] = open(cfg.score_path).read()
    assert scores["packed"] == scores["arrays"]
    assert scores["packed"].strip()  # not vacuous


def test_weight_files_keep_explicit_weights(tmp_path, ones_fmb):
    """Non-uniform per-file weights disable the weight elision (spec
    with_weights=True) and stay bit-identical to arrays."""
    silent = lambda *a: None
    kw = dict(weight_files=(2.0, 0.5))
    s_arr = train(_cfg(tmp_path, ones_fmb, "fwarr", wire_format="arrays", **kw), log=silent)
    s_pkd = train(_cfg(tmp_path, ones_fmb, "fwpkd", wire_format="packed", **kw), log=silent)
    _assert_state_equal(s_arr, s_pkd)


def test_ffm_fields_ship_on_the_wire(tmp_path):
    """FFM (uses_fields) keeps fields on the wire — packed vs arrays
    bitwise on a libffm stream."""
    rng = np.random.default_rng(7)
    path = tmp_path / "ffm.libsvm"
    with open(path, "w") as f:
        for _ in range(64):
            nnz = rng.integers(1, 6)
            toks = [
                f"{rng.integers(0, 3)}:{rng.integers(0, VOCAB)}:{round(float(rng.normal()), 4)}"
                for _ in range(nnz)
            ]
            f.write(f"{rng.integers(0, 2)} {' '.join(toks)}\n")
    fmb = write_fmb(str(path), str(path) + ".fmb", vocabulary_size=VOCAB)
    silent = lambda *a: None
    kw = dict(model="ffm", num_fields=3)
    s_arr = train(_cfg(tmp_path, [fmb], "ffma", wire_format="arrays", **kw), log=silent)
    s_pkd = train(_cfg(tmp_path, [fmb], "ffmp", wire_format="packed", **kw), log=silent)
    _assert_state_equal(s_arr, s_pkd)


# --- observability --------------------------------------------------------


def test_input_metrics_records(tmp_path, ones_fmb):
    """kind=input JSONL records flow through MetricsLogger: wire bytes,
    parse/h2d timings, prefetch queue depth — and the packed wire ships
    measurably fewer bytes than arrays on the all-ones stream."""
    silent = lambda *a: None
    cfgs = {
        wf: _cfg(tmp_path, ones_fmb, f"obs_{wf}", wire_format=wf)
        for wf in ("packed", "arrays")
    }
    for cfg in cfgs.values():
        train(cfg, log=silent)
    recs = {
        wf: [r for r in _records(cfg.metrics_path) if r.get("kind") == "input"]
        for wf, cfg in cfgs.items()
    }
    for wf, rs in recs.items():
        assert rs, f"no kind=input records for {wf}"
        r = rs[0]
        for key in ("parse_ms", "h2d_ms", "wire_bytes_per_step", "input_steps"):
            assert key in r, (wf, key)
    packed_b = recs["packed"][0]["wire_bytes_per_step"]
    arrays_b = recs["arrays"][0]["wire_bytes_per_step"]
    assert packed_b * 2 < arrays_b, (packed_b, arrays_b)


# --- serving --------------------------------------------------------------


def test_bucket_ladder_wire_batches_match_arrays():
    from fast_tffm_tpu.serving.buckets import BucketLadder

    class _Score:
        max_nnz = 6
        uses_fields = False

    rng = np.random.default_rng(8)
    rows = []
    for _ in range(5):
        ids = np.zeros((6,), np.int32)
        vals = np.zeros((6,), np.float32)
        n = int(rng.integers(1, 6))
        ids[:n] = rng.integers(0, VOCAB, n)
        vals[:n] = rng.normal(size=n).astype(np.float32)
        rows.append((ids, vals, np.zeros((6,), np.int32)))
    arr = BucketLadder(_Score(), (8,))
    pkd = BucketLadder(_Score(), (8,), wire_format="packed", vocabulary_size=VOCAB)
    b_arr, k_arr = arr.assemble(rows)
    b_pkd, k_pkd = pkd.assemble(rows)
    assert k_arr == k_pkd == 8
    _assert_batches_equal(b_pkd, b_arr)


def test_config_wire_format_parse_and_validate(tmp_path):
    from fast_tffm_tpu.config import load_config

    p = tmp_path / "c.cfg"
    p.write_text("[Train]\ntrain_files = x\nwire_format = arrays\n")
    assert load_config(str(p)).wire_format == "arrays"
    assert Config().wire_format == "packed"  # the default
    with pytest.raises(ValueError, match="wire_format"):
        Config(wire_format="gzip").validate()


# ---------------------------------------------------------------------------
# serving DATA frames (serving/protocol.py): the binary score plane
# ---------------------------------------------------------------------------


def test_serving_frame_request_roundtrip():
    import io

    from fast_tffm_tpu.serving import protocol as sp

    rng = np.random.default_rng(7)
    n, w = 5, 6
    req = np.arange(100, 100 + n, dtype=np.uint32)
    ids = rng.integers(0, 4096, (n, w)).astype(np.int32)
    vals = rng.normal(size=(n, w)).astype(np.float32)
    fields = rng.integers(0, 8, (n, w)).astype(np.int32)
    dl = np.array([0, 50, 0, 12.5, 100], np.float32)
    classes = ["gold", "std", "std", "", "gold"]
    data = sp.pack_request_frame(
        req, ids, vals, fields=fields, deadlines_ms=dl, classes=classes
    )
    kind, flags, count, width, payload = sp.read_frame(io.BytesIO(data))
    assert (kind, count, width) == (sp.FRAME_KIND_REQUEST, n, w)
    assert flags & sp.FRAME_FLAG_HAS_FIELDS
    d = sp.unpack_request_frame(flags, count, width, payload)
    np.testing.assert_array_equal(d["req_ids"], req)
    np.testing.assert_array_equal(d["ids"], ids)
    # Bit-exact floats: the frame is a memcpy, not a repr round-trip.
    assert d["vals"].tobytes() == vals.tobytes()
    assert d["deadlines_ms"].tobytes() == dl.tobytes()
    np.testing.assert_array_equal(d["fields"], fields)
    assert d["classes"] == classes
    # The no-fields / no-classes path: flag off, fields None, default class.
    data2 = sp.pack_request_frame(req, ids, vals)
    kind2, flags2, c2, w2, payload2 = sp.read_frame(io.BytesIO(data2))
    assert not (flags2 & sp.FRAME_FLAG_HAS_FIELDS)
    d2 = sp.unpack_request_frame(flags2, c2, w2, payload2)
    assert d2["fields"] is None
    assert d2["classes"] == [""] * n


def test_serving_frame_scores_and_error_roundtrip():
    import io

    from fast_tffm_tpu.serving import protocol as sp

    req = np.array([3, 1, 2], np.uint32)
    st = np.array([0, 2, 3], np.uint8)  # ok, deadline, bad_request
    sc = np.array([0.25, 0.0, 0.0], np.float32)
    kind, _, count, _, payload = sp.read_frame(
        io.BytesIO(sp.pack_scores_frame(req, st, sc))
    )
    assert kind == sp.FRAME_KIND_SCORES
    r, s, v = sp.unpack_scores_frame(count, payload)
    np.testing.assert_array_equal(r, req)
    np.testing.assert_array_equal(s, st)
    assert v.tobytes() == sc.tobytes()
    kind, _, _, _, payload = sp.read_frame(
        io.BytesIO(sp.pack_error_frame("bad_request", "torn header"))
    )
    assert kind == sp.FRAME_KIND_ERROR
    assert sp.unpack_error_frame(payload) == ("bad_request", "torn header")
    # An unknown code index decodes as unavailable, never an IndexError.
    assert sp.unpack_error_frame(bytes([250]) + b"\x00\x00")[0] == "unavailable"


def test_serving_frame_torn_input_typed_never_hung():
    """Every way a frame stream can tear maps to BadRequest (or clean
    None at EOF) — the reader never blocks past the announced payload
    and never raises an untyped exception."""
    import io

    from fast_tffm_tpu.serving import protocol as sp

    good = sp.pack_request_frame(
        np.array([1], np.uint32),
        np.zeros((1, 2), np.int32),
        np.ones((1, 2), np.float32),
    )
    assert sp.read_frame(io.BytesIO(b"")) is None  # clean EOF at boundary
    for torn in (
        good[:7],  # truncated header
        b"XXXX" + good[4:],  # bad magic
        good[:4] + b"\xff" + good[5:],  # unsupported version
        good[: sp.FRAME_HEADER.size + 3],  # EOF mid-payload
        sp.FRAME_HEADER.pack(
            sp.FRAME_MAGIC, sp.FRAME_VERSION, sp.FRAME_KIND_REQUEST,
            0, 1, 2, sp.FRAME_MAX_PAYLOAD + 1,
        ),  # absurd payload length: must refuse, not await 16 MiB
    ):
        with pytest.raises(sp.BadRequest):
            sp.read_frame(io.BytesIO(torn))
    # A payload inconsistent with its header counts is typed too.
    kind, flags, count, width, payload = sp.read_frame(io.BytesIO(good))
    with pytest.raises(sp.BadRequest):
        sp.unpack_request_frame(flags, count + 7, width, payload)
    with pytest.raises(sp.BadRequest):
        sp.unpack_scores_frame(3, b"\x00" * 5)


def test_serving_frame_layout_pinned_in_lockfile():
    """The committed formats.lock.json pins the frame constants: layout
    drift (reordered status codes, resized header, new magic) fails HERE
    before any cross-version peer sees a torn stream."""
    import os

    from fast_tffm_tpu.serving import protocol as sp

    lock_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "analysis", "formats.lock.json",
    )
    with open(lock_path) as f:
        lock = json.load(f)
    wp = lock["sections"]["wire_protocol"]
    assert wp["FRAME_STATUS_CODES"] == list(sp.FRAME_STATUS_CODES)
    assert sp.FRAME_STATUS_CODES[1:] == sp.WIRE_CODES  # u8 0 is "ok"
    frame = wp["frame"]
    assert frame["FRAME_MAGIC"] == sp.FRAME_MAGIC.decode()
    assert frame["FRAME_VERSION"] == sp.FRAME_VERSION
    assert frame["FRAME_HEADER_FORMAT"] == sp.FRAME_HEADER_FORMAT
    assert frame["FRAME_KIND_REQUEST"] == sp.FRAME_KIND_REQUEST
    assert frame["FRAME_KIND_SCORES"] == sp.FRAME_KIND_SCORES
    assert frame["FRAME_KIND_ERROR"] == sp.FRAME_KIND_ERROR
    assert frame["FRAME_FLAG_HAS_FIELDS"] == sp.FRAME_FLAG_HAS_FIELDS
    assert frame["FRAME_MAX_PAYLOAD"] == sp.FRAME_MAX_PAYLOAD
    assert sp.FRAME_HEADER.size == 16  # u32-aligned; peers hardcode this
