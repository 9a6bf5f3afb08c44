"""Property-based fuzzing of the protocol stack (hypothesis).

The adversarial suites (tests/test_nasty.py) cover structured attacks;
these throw unstructured randomness at the decoders and assert the
failure contract: arbitrary junk may only ever produce packets or a
ZKProtocolError — never an uncontrolled exception — and the native and
Python frame scanners stay byte-for-byte equivalent under any input
and chunking."""

import struct

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from zkstream_tpu.protocol.errors import (
    ZKFrameTooLargeError,
    ZKProtocolError,
)
from zkstream_tpu.protocol.framing import FrameDecoder, PacketCodec
from zkstream_tpu.protocol.jute import JuteReader, JuteWriter
from zkstream_tpu.utils import native


def _first_framing_fault(junk: bytes, cap: int):
    """Walk ``junk`` as the framer does: the code the first invalid
    length prefix must raise (None when every prefix is valid or
    incomplete) and how many whole frames stand before it — a body
    among those may fail to decode first."""
    off = frames = 0
    while len(junk) - off >= 4:
        (ln,) = struct.unpack_from('>i', junk, off)
        if ln < 0:
            return 'BAD_LENGTH', frames
        if ln > cap:
            return 'FRAME_TOO_LARGE', frames
        if len(junk) - off < 4 + ln:
            break
        off += 4 + ln
        frames += 1
    return None, frames


def _assert_junk_contract(codec, junk):
    """Arbitrary bytes into a codec: packets out or a typed
    ZKProtocolError — ``FRAME_TOO_LARGE`` exactly when the first
    invalid prefix declares more than the codec's cap (and carries the
    length and the cap), ``BAD_LENGTH`` when it is negative,
    ``BAD_DECODE`` only for a whole frame's body — nothing else."""
    fault, frames = _first_framing_fault(junk, codec._max_frame)
    try:
        pkts = codec.decode(junk)
    except ZKProtocolError as e:
        assert isinstance(getattr(e, 'packets', []), list)
        if e.code == 'BAD_DECODE':
            assert frames > 0
        else:
            assert e.code == fault
            if fault == 'FRAME_TOO_LARGE':
                assert isinstance(e, ZKFrameTooLargeError)
                assert e.length > e.cap == codec._max_frame
    else:
        assert isinstance(pkts, list)
        # an invalid prefix with no frame before it cannot pass
        assert fault is None or frames > 0


@settings(max_examples=120, deadline=None)
@given(st.binary(max_size=400),
       st.lists(st.integers(1, 64), max_size=8))
def test_codec_decode_junk_contract(junk, xids):
    codec = PacketCodec()
    codec.handshaking = False
    for x in xids:
        codec.xid_map[x] = 'GET_DATA'
    _assert_junk_contract(codec, junk)


@settings(max_examples=120, deadline=None)
@given(st.binary(max_size=400))
def test_handshake_decode_junk_contract(junk):
    _assert_junk_contract(PacketCodec(), junk)


@settings(max_examples=100, deadline=None)
@given(st.binary(max_size=600), st.data())
def test_native_and_python_scanners_agree(blob, data):
    """Same bytes, arbitrary chunk boundaries: identical frames,
    identical error behavior, identical residual buffering."""
    if native.ensure_lib() is None:  # pragma: no cover - no compiler
        pytest.skip('native codec unavailable')
    py = FrameDecoder(use_native=False)
    nat = FrameDecoder(use_native=True)
    pos = 0
    while pos < len(blob):
        take = data.draw(st.integers(1, len(blob) - pos))
        chunk = blob[pos:pos + take]
        pos += take
        py_frames = py_err = None
        try:
            py_frames = py.feed(chunk)
        except ZKProtocolError as e:
            py_err = e.code
        try:
            nat_frames = nat.feed(chunk)
            nat_err = None
        except ZKProtocolError as e:
            nat_frames, nat_err = None, e.code
        assert py_frames == nat_frames
        assert py_err == nat_err
        assert py.pending() == nat.pending()
        if py_err is not None:
            return


@settings(max_examples=120, deadline=None)
@given(st.binary(max_size=500),
       st.lists(st.integers(1, 64), unique=True, max_size=8),
       st.data())
def test_ext_and_python_codecs_agree(junk, xids, data):
    """Arbitrary bytes, arbitrary chunking: the C-extension decode path
    and the pure-Python codec produce identical packets, identical
    errors (code + attached packets), identical residue, and identical
    xid-map consumption."""
    if native.ensure_ext() is None:  # pragma: no cover - no compiler
        pytest.skip('native extension unavailable')
    py = PacketCodec(use_native=False)
    ext = PacketCodec(use_native=True)
    for c in (py, ext):
        c.handshaking = False
        c.xid_map = {x: 'GET_DATA' for x in xids}
    pos = 0
    while pos < len(junk):
        take = data.draw(st.integers(1, len(junk) - pos))
        chunk = junk[pos:pos + take]
        pos += take
        outcomes = []
        for c in (py, ext):
            try:
                outcomes.append(('ok', c.decode(chunk), None))
            except ZKProtocolError as e:
                outcomes.append(
                    ('err', getattr(e, 'packets', []), e.code))
        assert outcomes[0] == outcomes[1]
        assert py._decoder.pending() == ext._decoder.pending()
        assert py.xid_map == ext.xid_map
        if outcomes[0][0] == 'err':
            return


@settings(max_examples=120, deadline=None)
@given(st.binary(max_size=500), st.data())
def test_server_ext_and_python_codecs_agree(junk, data):
    """Server direction (request decode): same A/B contract as the
    client direction, over arbitrary junk and chunking."""
    if native.ensure_ext() is None:  # pragma: no cover - no compiler
        pytest.skip('native extension unavailable')
    py = PacketCodec(server=True, use_native=False)
    ext = PacketCodec(server=True, use_native=True)
    for c in (py, ext):
        c.handshaking = False
    pos = 0
    while pos < len(junk):
        take = data.draw(st.integers(1, len(junk) - pos))
        chunk = junk[pos:pos + take]
        pos += take
        outcomes = []
        for c in (py, ext):
            try:
                outcomes.append(('ok', c.decode(chunk), None))
            except ZKProtocolError as e:
                outcomes.append(
                    ('err', getattr(e, 'packets', []), e.code))
        assert outcomes[0] == outcomes[1]
        assert py._decoder.pending() == ext._decoder.pending()
        if outcomes[0][0] == 'err':
            return


@settings(max_examples=200, deadline=None)
@given(st.integers(-2**31, 2**31 - 1), st.integers(-2**63, 2**63 - 1),
       st.binary(max_size=64), st.text(max_size=32),
       st.booleans(), st.integers(-128, 127))
def test_jute_roundtrip_property(i32, i64, buf, text, flag, byte):
    w = JuteWriter()
    w.write_int(i32)
    w.write_long(i64)
    w.write_buffer(buf)
    w.write_ustring(text)
    w.write_bool(flag)
    w.write_byte(byte)
    r = JuteReader(w.to_bytes())
    assert r.read_int() == i32
    assert r.read_long() == i64
    assert r.read_buffer() == buf
    assert r.read_ustring() == text
    assert r.read_bool() == flag
    assert r.read_byte() == byte
    assert r.at_end()


@settings(max_examples=40, deadline=None)
@given(st.lists(st.binary(max_size=120), min_size=1, max_size=6))
def test_tensor_scan_agrees_with_scalar_on_junk(rows):
    """Random per-stream junk: the batched cursor scan and the scalar
    decoder agree on frame counts, bad flags, and residuals."""
    jnp = pytest.importorskip('jax.numpy')
    from zkstream_tpu.ops import frame_cursor_scan

    L = max(len(r) for r in rows)
    L = max(L, 4)
    buf = np.zeros((len(rows), L), np.uint8)
    lens = np.zeros((len(rows),), np.int32)
    for i, r in enumerate(rows):
        buf[i, :len(r)] = np.frombuffer(r, np.uint8)
        lens[i] = len(r)
    starts, sizes, counts, bad, resid = frame_cursor_scan(
        jnp.asarray(buf), jnp.asarray(lens), max_frames=32)
    for i, r in enumerate(rows):
        dec = FrameDecoder(use_native=False)
        try:
            frames = dec.feed(r)
            assert not bool(bad[i])
            assert int(counts[i]) == len(frames)
            assert int(resid[i]) == len(r) - dec.pending()
        except ZKProtocolError:
            assert bool(bad[i])


def test_jute_byte_accepts_unsigned_reads_signed():
    """Jute bytes are signed (Java convention, like the reference's
    Buffer readInt8); the writer also tolerates the unsigned spelling
    and normalizes the bit pattern."""
    w = JuteWriter()
    w.write_byte(200)
    assert JuteReader(w.to_bytes()).read_byte() == 200 - 256


def test_fuzz_seed_corpus_regression():
    """Known tricky shapes stay fixed: empty, lone prefix, prefix
    crossing chunk boundary, max-length frame, zero-length frames."""
    d = FrameDecoder(use_native=False)
    assert d.feed(b'') == []
    assert d.feed(b'\x00\x00\x00') == []
    assert d.feed(b'\x05') == []  # len=5 now complete across chunks
    assert d.feed(b'abcde') == [b'abcde']
    assert d.feed(struct.pack('>i', 0) * 3) == [b'', b'', b'']
    assert d.pending() == 0
