"""Property-based fuzz of the frame layer: chunk boundaries, garbage
prefixes, interleaved sessions, and single-byte corruption.

Built on `hypothesis`. The core contract under fuzz: a `FrameReader`
either yields exactly the frames that were sent, or raises a typed
`wire.WireError` — it never yields a frame that was not sent, and never
hangs on a complete buffer.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
from repro.core import compressors as C, wire


def _sample_stream(seed: int, n_sessions: int = 2, steps: int = 3):
    """A deterministic multi-session byte stream + its expected frames."""
    rng = np.random.RandomState(seed)
    comp = C.make_compressor("randtopk", k=3)
    chunks, expect = [], []
    for step in range(steps):
        for sid in range(n_sessions):
            p = jax.tree.map(np.asarray, comp.encode(
                jax.numpy.asarray(rng.randn(1, 16).astype(np.float32)),
                key=jax.random.key(seed + sid), training=True))
            chunks.append(wire.encode_payload_frame(sid, step, p))
            expect.append((wire.FRAME_PAYLOAD, sid, step))
            chunks.append(wire.encode_token_frame(sid, step, [step]))
            expect.append((wire.FRAME_TOKENS, sid, step))
    for sid in range(n_sessions):
        chunks.append(wire.encode_close_frame(sid))
        expect.append((wire.FRAME_CLOSE, sid, 0))
    return b"".join(chunks), expect


def _drain(reader):
    return [(f.kind, f.session, f.seq) for f in reader.frames()]


@given(st.integers(0, 500), st.integers(1, 64))
@settings(max_examples=25, deadline=None)
def test_reader_invariant_under_chunk_boundaries(seed, chunk_size):
    """Frames recovered must be identical no matter how the stream is cut —
    including interleaved sessions back-to-back in one buffer."""
    stream, expect = _sample_stream(seed % 5)
    reader = wire.FrameReader()
    got = []
    for off in range(0, len(stream), chunk_size):
        reader.feed(stream[off: off + chunk_size])
        got.extend(_drain(reader))
    assert got == expect


@given(st.integers(0, 10_000))
@settings(max_examples=50, deadline=None)
def test_garbage_prefix_never_yields_a_frame(seed):
    """Random garbage must never decode to a frame: either a typed
    WireError (bad length/CRC) or an incomplete-buffer wait, never a
    silent bogus frame."""
    rng = np.random.RandomState(seed)
    garbage = rng.randint(0, 256, size=rng.randint(4, 200),
                          dtype=np.uint8).tobytes()
    reader = wire.FrameReader()
    reader.feed(garbage)
    try:
        assert _drain(reader) == []
    except wire.WireError:
        # poisoned reader must keep refusing (connection-teardown contract)
        with pytest.raises(wire.WireError):
            _drain(reader)


@given(st.integers(0, 1000), st.integers(1, 255))
@settings(max_examples=40, deadline=None)
def test_single_byte_flip_never_decodes_silently(seed, xor):
    """THE integrity contract: flip any one byte of a valid framed stream
    and no decoder path may return a different frame as if it were good.
    Every outcome is either a typed WireError or a shortened/incomplete
    stream — zero silent decodes."""
    rng = np.random.RandomState(seed)
    comp = C.make_compressor("randtopk_quant", k=3, bits=8)
    p = jax.tree.map(np.asarray, comp.encode(
        jax.numpy.asarray(rng.randn(2, 16).astype(np.float32)),
        key=jax.random.key(seed), training=True))
    clean = wire.encode_payload_frame(1, 5, p)
    pos = rng.randint(len(clean))
    corrupt = bytearray(clean)
    corrupt[pos] ^= xor
    try:
        got = wire.decode_frame(bytes(corrupt))
    except wire.WireError:
        return                          # typed rejection: contract held
    # a flipped length prefix may leave the buffer "incomplete" (reader
    # would wait for more bytes) — that is not a silent decode
    assert got is None, (
        f"silent decode after flipping byte {pos} with {xor:#x}")


@given(st.integers(0, 300), st.sampled_from(
    ["identity", "topk:k=4", "randtopk:k=4", "quant:bits=4",
     "randtopk_quant:k=4,bits=8", "randtopk_mask:k=4"]))
@settings(max_examples=25, deadline=None)
def test_truncated_tail_then_valid_frame_is_detected(seed, spec):
    """A truncated frame glued to a later valid frame desyncs the stream;
    the reader must raise, not resynchronize onto garbage."""
    rng = np.random.RandomState(seed)
    comp = C.make_compressor(spec)
    p = jax.tree.map(np.asarray, comp.encode(
        jax.numpy.asarray(rng.randn(1, 32).astype(np.float32)),
        key=jax.random.key(seed), training=True))
    f1 = wire.encode_payload_frame(0, 0, p)
    f2 = wire.encode_token_frame(0, 1, [7])
    cut = rng.randint(5, len(f1))       # keep the length prefix intact
    reader = wire.FrameReader()
    reader.feed(f1[:cut] + f2)
    with pytest.raises(wire.WireError):
        while _drain(reader):
            pass
        # stream still incomplete per the (valid) length prefix: append
        # more bytes until the checksum gate must fire
        reader.feed(f2 * 8)
        _drain(reader)
