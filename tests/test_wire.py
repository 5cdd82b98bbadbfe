"""Byte-exact wire format round-trips + property tests + frame layer."""
import doctest
import pathlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
from repro.core import compressors as C, wire

ROOT = pathlib.Path(__file__).resolve().parents[1]


@given(st.integers(2, 2048), st.integers(1, 16), st.integers(0, 1000))
@settings(max_examples=30, deadline=None)
def test_sparse_roundtrip(d, k, seed):
    k = min(k, d)
    rng = np.random.RandomState(seed)
    vals = rng.randn(k).astype(np.float32)
    idx = rng.choice(d, size=k, replace=False)
    buf = wire.encode_sparse(vals, idx, d)
    v2, i2 = wire.decode_sparse(buf, k, d)
    np.testing.assert_array_equal(v2, vals)
    np.testing.assert_array_equal(i2, idx)
    # byte count matches Table 2 within rounding
    expect_bits = k * 32 + k * wire.index_bits(d)
    assert len(buf) == 4 * k + (k * wire.index_bits(d) + 7) // 8
    assert abs(len(buf) * 8 - expect_bits) < 8


@given(st.integers(1, 64), st.integers(1, 300), st.integers(0, 1000))
@settings(max_examples=40, deadline=None)
def test_pack_unpack_bits_roundtrip_any_width(width, n, seed):
    """Every width the wire can carry [1, 64]: pack -> unpack is the
    identity, the byte count is exactly ceil(n*width/8), and appending a
    value extends the stream without disturbing the existing bytes'
    values (the stream is truly positional, no per-value alignment)."""
    rng = np.random.RandomState(seed)
    hi = min(2 ** width, 2 ** 63)
    vals = rng.randint(0, hi, size=n).astype(np.uint64)
    buf = wire._pack_bits(vals, width)
    assert len(buf) == (n * width + 7) // 8
    np.testing.assert_array_equal(wire._unpack_bits(buf, width, n), vals)
    longer = wire._pack_bits(np.concatenate([vals, vals[:1]]), width)
    np.testing.assert_array_equal(
        wire._unpack_bits(longer, width, n + 1)[:n], vals)
    # a shorter read off the same buffer is a strict prefix
    np.testing.assert_array_equal(
        wire._unpack_bits(buf, width, n // 2), vals[:n // 2])


@given(st.integers(1, 200), st.integers(1, 5), st.integers(0, 500))
@settings(max_examples=30, deadline=None)
def test_mask_words_bytes_roundtrip(d, n, seed):
    """Packed support bitmask serialization: words -> per-row byte-aligned
    wire bytes -> words is the identity at every d (including d not a
    multiple of 8 or 32), and the byte count is n * ceil(d/8)."""
    rng = np.random.RandomState(seed)
    mask = rng.rand(n, d) < 0.3
    words = np.zeros((n, wire.mask_words(d)), np.uint32)
    for j in range(d):
        words[:, j // 32] |= mask[:, j].astype(np.uint32) << (j % 32)
    buf = wire.mask_words_to_bytes(words, d)
    assert len(buf) == n * wire.mask_row_nbytes(d)
    np.testing.assert_array_equal(wire.mask_bytes_to_words(buf, n, d),
                                  words)


def test_sparse_to_dense():
    vals = np.array([[1.0, -2.0]])
    idx = np.array([[3, 0]])
    dense = wire.sparse_to_dense(vals, idx, 5)
    np.testing.assert_array_equal(dense, [[-2.0, 0, 0, 1.0, 0]])


@given(st.integers(2, 64), st.integers(1, 8), st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_quant_roundtrip(d, bits, seed):
    rng = np.random.RandomState(seed)
    n = 3
    x = rng.randn(n, d).astype(np.float32)
    lo = x.min(-1)
    step = (x.max(-1) - lo) / 2**bits
    step[step <= 0] = 1.0
    codes = np.clip(np.floor((x - lo[:, None]) / step[:, None]), 0,
                    2**bits - 1)
    buf = wire.encode_quant(codes, lo, step, bits)
    deq = wire.decode_quant(buf, n, d, bits)
    assert np.abs(deq - x).max() <= step.max() * 0.51


def test_bytes_per_step():
    b_train = wire.bytes_per_step("topk", 128, 10, k=4, training=True)
    b_inf = wire.bytes_per_step("topk", 128, 10, k=4, training=False)
    assert b_train > b_inf > 0
    ident = wire.bytes_per_step("identity", 128, 10, training=False)
    assert ident == 128 * 4 * 10


# ---------------------------------------------------------------------------
# Frame layer (docs/wire-format.md is the normative spec)
# ---------------------------------------------------------------------------

ALL_COMPRESSORS = [("identity", {}), ("size_reduction", dict(k=5)),
                   ("topk", dict(k=5)), ("randtopk", dict(k=5, alpha=0.2)),
                   ("quant", dict(bits=4)),
                   ("randtopk_quant", dict(k=5, bits=8)), ("l1", {}),
                   ("randtopk_mask", dict(k=5, alpha=0.2))]


@pytest.mark.parametrize("name,kw", ALL_COMPRESSORS)
def test_payload_frame_roundtrip_all_kinds(name, kw):
    """header + payload bytes -> decode -> exact array equality, per kind."""
    d = 48
    comp = C.make_compressor(name, **kw)
    x = jax.numpy.asarray(
        np.random.RandomState(7).randn(2, 3, d).astype(np.float32))
    p = jax.tree.map(np.asarray,
                     comp.encode(x, key=jax.random.key(0), training=True))
    buf = wire.encode_payload_frame(session=11, seq=4, p=p)
    frame, consumed = wire.decode_frame(buf)
    assert consumed == len(buf) == frame.nbytes
    assert (frame.kind, frame.session, frame.seq) == (wire.FRAME_PAYLOAD,
                                                      11, 4)
    assert frame.payload.meta == p.meta
    assert frame.payload_nbytes == wire.payload_nbytes(p)
    for (name_a, a), (name_b, b) in zip(p.wire_leaves(),
                                        frame.payload.wire_leaves()):
        assert name_a == name_b
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name,kw", ALL_COMPRESSORS)
def test_grad_frame_roundtrip_all_kinds(name, kw):
    """Backward wire: the grad payload a forward kind dictates frames,
    decodes, and routes back onto the forward support exactly."""
    from repro.split import protocol

    d = 48
    comp = C.make_compressor(name, **kw)
    rng = np.random.RandomState(11)
    x = jax.numpy.asarray(rng.randn(2, d).astype(np.float32))
    p = jax.tree.map(np.asarray,
                     comp.encode(x, key=jax.random.key(0), training=True))
    g = rng.randn(2, d).astype(np.float32)
    gp = protocol.server_grad_encode(p, g)
    buf = wire.encode_grad_frame(session=5, seq=7, p=gp, loss=1.5)
    frame, consumed = wire.decode_frame(buf)
    assert consumed == len(buf) == frame.nbytes
    assert (frame.kind, frame.session, frame.seq) == (wire.FRAME_GRAD, 5, 7)
    assert frame.loss == 1.5
    assert frame.payload.meta == gp.meta
    assert frame.payload_nbytes == wire.payload_nbytes(gp)
    assert frame.header_nbytes == wire.grad_frame_header_nbytes(gp)
    g_cut = np.asarray(protocol.client_grad_decode(
        frame.payload, fwd_kind=p.meta.kind, indices=p.indices, d=d))
    assert g_cut.shape == g.shape
    if p.meta.kind in ("sparse", "sparse_quant"):
        mask = np.zeros_like(g, dtype=bool)
        np.put_along_axis(mask, p.indices.astype(np.int64), True, axis=-1)
        np.testing.assert_array_equal(g_cut, g * mask)
    elif p.meta.kind == "mask":
        from repro.core import selection
        mask = np.asarray(selection.unpack_mask_words(
            jax.numpy.asarray(p.indices), d)).astype(bool)
        np.testing.assert_array_equal(g_cut, g * mask)
    elif p.meta.kind == "slice":
        k = p.meta.k
        np.testing.assert_array_equal(g_cut[..., :k], g[..., :k])
        assert not g_cut[..., k:].any()
    else:
        np.testing.assert_array_equal(g_cut, g)


def test_grad_frame_bwd_bytes_match_table2():
    """Grad payload bytes ARE the Table-2 bwd column, measured: k floats
    for sparse kinds, d floats for dense/quant."""
    from repro.core.payload import Payload, PayloadMeta
    from repro.split import protocol

    d, k, n = 64, 5, 3
    g = np.zeros((n, d), np.float32)
    sparse_fwd = Payload(meta=PayloadMeta("sparse", d=d, k=k),
                         values=np.zeros((n, k), np.float32),
                         indices=np.arange(k, dtype=np.uint16)[None].repeat(
                             n, 0))
    assert wire.payload_nbytes(
        protocol.server_grad_encode(sparse_fwd, g)) == 4 * k * n
    dense_fwd = Payload(meta=PayloadMeta("dense", d=d),
                        values=np.zeros((n, d), np.float32))
    assert wire.payload_nbytes(
        protocol.server_grad_encode(dense_fwd, g)) == 4 * d * n


def test_token_and_close_frames():
    buf = wire.encode_token_frame(3, 9, [42, 7]) + wire.encode_close_frame(3)
    f1, off = wire.decode_frame(buf)
    f2, off2 = wire.decode_frame(buf, off)
    assert off2 == len(buf)
    assert f1.kind == wire.FRAME_TOKENS and f1.tokens.tolist() == [42, 7]
    assert f1.payload_nbytes == 8 and f1.nbytes + f2.nbytes == len(buf)
    assert f2.kind == wire.FRAME_CLOSE and f2.session == 3


def test_frame_reader_arbitrary_chunks():
    """Reassembly must not depend on chunk boundaries (1-byte feeds)."""
    p = C.make_compressor("topk", k=2).encode(
        jax.numpy.asarray(np.random.RandomState(0).randn(1, 8).astype(
            np.float32)))
    stream = (wire.encode_payload_frame(0, 0, jax.tree.map(np.asarray, p))
              + wire.encode_token_frame(0, 1, [5])
              + wire.encode_close_frame(0))
    reader = wire.FrameReader()
    got = []
    for i in range(len(stream)):
        reader.feed(stream[i:i + 1])
        got.extend(reader.frames())
    assert [f.kind for f in got] == [wire.FRAME_PAYLOAD, wire.FRAME_TOKENS,
                                     wire.FRAME_CLOSE]


def test_frame_reader_abandoned_iterator_does_not_replay():
    """Consuming one frame and dropping the iterator must not re-yield it."""
    reader = wire.FrameReader()
    reader.feed(wire.encode_token_frame(0, 0, [1])
                + wire.encode_token_frame(0, 1, [2]))
    first = next(reader.frames())        # iterator abandoned mid-stream
    assert first.seq == 0
    assert [f.seq for f in reader.frames()] == [1]


# ---------------------------------------------------------------------------
# Typed error taxonomy: every malformed-but-CRC-valid frame must raise the
# *specific* WireError naming the bad field, and raw corruption must raise
# ChecksumError — never decode silently, never raise something untyped.
# `_forge` builds frames with arbitrary (inconsistent) contents but a valid
# CRC, so each validator is reached past the checksum gate.
# ---------------------------------------------------------------------------

def _forge(kind, body, session=0, seq=0, version=None):
    buf = bytearray(wire._frame(kind, session, seq, body))
    if version is not None:
        buf[4] = version
        buf[-4:] = wire._CRC.pack(
            __import__("zlib").crc32(bytes(buf[4:-4])))
    return bytes(buf)


def _payload_body(kind_idx=2, d=16, k=2, bits=0, bshape=(1,),
                  payload=b"\x00" * 9):
    sub = wire._PAYLOAD_HEAD.pack(kind_idx, d, k, bits, len(bshape))
    import struct as _s
    return (sub + (_s.pack(f"<{len(bshape)}I", *bshape) if bshape else b"")
            + payload)


def test_corrupt_count_raises_typed_badcount():
    """A token frame whose count field disagrees with the body length must
    raise the typed BadCount (it used to be a generic ValueError)."""
    body = wire._TOKENS_HEAD.pack(200) + np.asarray(
        [1, 2], "<i4").tobytes()
    with pytest.raises(wire.BadCount, match="count"):
        wire.decode_frame(_forge(wire.FRAME_TOKENS, body))


def test_bad_payload_kind_index_raises_unknown_kind():
    with pytest.raises(wire.UnknownKind, match="kind index"):
        wire.decode_frame(_forge(wire.FRAME_PAYLOAD,
                                 _payload_body(kind_idx=250)))


def test_bad_payload_d_raises_badcount():
    for d in (0, 1 << 20):
        with pytest.raises(wire.BadCount, match="d="):
            wire.decode_frame(_forge(wire.FRAME_PAYLOAD,
                                     _payload_body(d=d)))


def test_bad_payload_k_raises_badcount():
    for k in (0, 17):                    # k must be in [1, d] for sparse
        with pytest.raises(wire.BadCount, match="k="):
            wire.decode_frame(_forge(wire.FRAME_PAYLOAD,
                                     _payload_body(d=16, k=k)))


def test_bad_payload_bits_raises_badcount():
    for bits in (0, 9):                  # quant code width is 1..8
        with pytest.raises(wire.BadCount, match="bits="):
            wire.decode_frame(_forge(wire.FRAME_PAYLOAD,
                                     _payload_body(kind_idx=3, bits=bits)))


def test_bad_payload_batch_shape_raises_badcount():
    with pytest.raises(wire.BadCount, match="zero dim"):
        wire.decode_frame(_forge(wire.FRAME_PAYLOAD,
                                 _payload_body(bshape=(0,))))
    with pytest.raises(wire.BadCount, match="rank"):
        wire.decode_frame(_forge(wire.FRAME_PAYLOAD,
                                 _payload_body(bshape=(1,) * 9)))


def test_payload_body_length_mismatch_raises_badcount():
    """Declared (meta, batch shape) must account for the body bytes exactly
    — one byte short or long is BadCount, not a misdecode."""
    for payload in (b"\x00" * 8, b"\x00" * 10):     # sparse d=16,k=2 -> 9 B
        with pytest.raises(wire.BadCount, match="needs 9 B"):
            wire.decode_frame(_forge(wire.FRAME_PAYLOAD,
                                     _payload_body(payload=payload)))


def test_truncated_subheader_raises_truncated_frame():
    with pytest.raises(wire.TruncatedFrame):
        wire.decode_frame(_forge(wire.FRAME_PAYLOAD, b"\x02"))
    with pytest.raises(wire.TruncatedFrame, match="batch shape"):
        wire.decode_frame(_forge(
            wire.FRAME_PAYLOAD,
            wire._PAYLOAD_HEAD.pack(2, 16, 2, 0, 4) + b"\x01"))


def test_grad_frame_missing_loss_raises_truncated_frame():
    body = wire._PAYLOAD_HEAD.pack(1, 16, 2, 0, 0)   # slice, no loss field
    with pytest.raises(wire.TruncatedFrame, match="loss"):
        wire.decode_frame(_forge(wire.FRAME_GRAD, body))


def test_close_frame_with_body_raises_badcount():
    with pytest.raises(wire.BadCount, match="close frame"):
        wire.decode_frame(_forge(wire.FRAME_CLOSE, b"\x00\x01"))


def test_unknown_frame_kind_raises_unknown_kind():
    with pytest.raises(wire.UnknownKind, match="frame kind"):
        wire.decode_frame(_forge(77, b""))


def test_absurd_length_prefix_raises_truncated_frame():
    """A corrupt length prefix must fail fast, not stall the reader
    waiting for bytes that will never come."""
    import struct as _s
    with pytest.raises(wire.TruncatedFrame, match="MAX_FRAME_BODY"):
        wire.decode_frame(_s.pack("<I", wire.MAX_FRAME_BODY + 1) + b"\x00")
    with pytest.raises(wire.TruncatedFrame, match="minimum"):
        wire.decode_frame(_s.pack("<I", 3) + b"\x00" * 3)


def test_flipped_byte_raises_checksum_error():
    buf = bytearray(wire.encode_token_frame(0, 0, [1, 2]))
    buf[wire.FRAME_HEAD_NBYTES] ^= 0x40      # corrupt the count field
    with pytest.raises(wire.ChecksumError):
        wire.decode_frame(bytes(buf))


def test_error_frame_roundtrip():
    buf = wire.encode_error_frame(9, 3, wire.ERR_BAD_COUNT, "k=99 > d=16")
    frame, consumed = wire.decode_frame(buf)
    assert consumed == len(buf) == frame.nbytes == frame.header_nbytes
    assert frame.kind == wire.FRAME_ERROR and frame.session == 9
    assert frame.error_code == wire.ERR_BAD_COUNT
    assert frame.error_msg == "k=99 > d=16"
    assert frame.payload_nbytes == 0
    # code mapping covers the whole taxonomy
    assert wire.error_code(wire.ChecksumError("x")) == wire.ERR_CHECKSUM
    assert wire.error_code(wire.TruncatedFrame("x")) == wire.ERR_TRUNCATED
    assert wire.error_code(wire.UnknownKind("x")) == wire.ERR_UNKNOWN_KIND
    assert wire.error_code(wire.BadCount("x")) == wire.ERR_BAD_COUNT
    assert wire.error_code(wire.VersionMismatch("x")) == wire.ERR_VERSION
    assert wire.error_code(RuntimeError("x")) == wire.ERR_PROTOCOL


def test_wire_errors_are_value_errors():
    """Back-compat: pre-taxonomy callers caught ValueError."""
    for cls in (wire.ChecksumError, wire.TruncatedFrame, wire.UnknownKind,
                wire.BadCount, wire.VersionMismatch):
        assert issubclass(cls, wire.WireError)
        assert issubclass(cls, ValueError)


def test_decode_frame_incomplete_returns_none():
    buf = wire.encode_token_frame(0, 0, [1])
    for cut in (0, 3, len(buf) - 1):
        assert wire.decode_frame(buf[:cut]) is None


def test_frame_rejects_unknown_version():
    with pytest.raises(wire.VersionMismatch, match="version"):
        wire.decode_frame(_forge(wire.FRAME_CLOSE, b"", version=99))


def test_wire_format_doc_examples():
    """docs/wire-format.md's examples are executable and must stay true."""
    failures, n = doctest.testfile(str(ROOT / "docs" / "wire-format.md"),
                                   module_relative=False,
                                   optionflags=doctest.NORMALIZE_WHITESPACE)
    assert n > 0 and failures == 0
