"""Tensor wire-codec ops vs the scalar protocol codec.

The scalar ``FrameDecoder``/``records`` stack (itself validated against
the reference's golden capture) is the oracle: every op must agree with
it on randomized frame streams, including the adversarial cases the
reference guards (negative / oversized length prefixes,
lib/zk-streams.js:47-53; truncated tails).
"""

import random
import struct

import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402

from zkstream_tpu.ops import (  # noqa: E402
    be_i32_at,
    be_i64pair_at,
    frame_cursor_scan,
    frame_starts_pointer_doubling,
    parse_reply_headers,
    stream_stats,
    u64pair_lt,
    u64pair_max,
    wire_pipeline_step,
)
from zkstream_tpu.ops.bytesops import u64pair_to_int  # noqa: E402
from zkstream_tpu.protocol.framing import FrameDecoder  # noqa: E402
from zkstream_tpu.protocol.errors import ZKProtocolError  # noqa: E402


def _reply_frame(xid, zxid, err, body=b''):
    """A raw reply frame: 16-byte header + body, length-prefixed."""
    hdr = struct.pack('>iqi', xid, zxid, err)
    return struct.pack('>i', len(hdr) + len(body)) + hdr + body


def _random_stream(rng, nframes, max_body=64):
    frames = []
    metas = []
    for _ in range(nframes):
        xid = rng.choice([-1, -2, rng.randrange(1, 1 << 20)])
        zxid = rng.randrange(0, 1 << 62) if xid >= 0 else -1
        err = rng.choice([0, 0, 0, -101, -110])
        body = bytes(rng.randrange(256)
                     for _ in range(rng.randrange(max_body)))
        frames.append(_reply_frame(xid, zxid, err, body))
        metas.append((xid, zxid, err))
    return b''.join(frames), metas


def _pad_batch(streams, L):
    B = len(streams)
    buf = np.zeros((B, L), np.uint8)
    lens = np.zeros((B,), np.int32)
    for i, s in enumerate(streams):
        buf[i, :len(s)] = np.frombuffer(s, np.uint8)
        lens[i] = len(s)
    return jnp.asarray(buf), jnp.asarray(lens)


# ---------------------------------------------------------------- bytes


def test_be_i32_matches_struct():
    rng = random.Random(1)
    raw = bytes(rng.randrange(256) for _ in range(64))
    buf = jnp.asarray(np.frombuffer(raw, np.uint8))[None, :]
    for off in range(0, 60, 3):
        expect = struct.unpack_from('>i', raw, off)[0]
        got = int(be_i32_at(buf, jnp.asarray([off]))[0])
        assert got == expect


def test_be_i64pair_roundtrip():
    rng = random.Random(2)
    for _ in range(20):
        v = rng.randrange(0, 1 << 63)
        raw = struct.pack('>q', v)
        buf = jnp.asarray(np.frombuffer(raw, np.uint8))[None, :]
        h, l = be_i64pair_at(buf, jnp.asarray([0]))
        assert u64pair_to_int(h[0], l[0]) == v


def test_u64pair_compare_and_max():
    rng = random.Random(3)
    vals = [rng.randrange(0, 1 << 64) for _ in range(50)] + [0, 1, 1 << 63]

    def pair(v):
        def i32(x):
            return jnp.asarray(
                np.array(x & 0xFFFFFFFF, np.uint32).astype(np.int32))
        return i32(v >> 32), i32(v)

    for a in vals[:12]:
        for b in vals[:12]:
            ah, al = pair(a)
            bh, bl = pair(b)
            assert bool(u64pair_lt(ah, al, bh, bl)) == (a < b)
            mh, ml = u64pair_max(ah, al, bh, bl)
            assert u64pair_to_int(mh, ml) == max(a, b)


# ----------------------------------------------------------- frame scan


def test_cursor_scan_matches_frame_decoder():
    rng = random.Random(4)
    streams = []
    expected = []
    for _ in range(16):
        s, _ = _random_stream(rng, rng.randrange(0, 12))
        # half the rows get a truncated partial tail frame
        if rng.random() < 0.5:
            s += struct.pack('>i', 100) + b'\x01' * rng.randrange(0, 99)
        streams.append(s)
        dec = FrameDecoder()
        expected.append(dec.feed(s))
    L = max(len(s) for s in streams) + 8
    buf, lens = _pad_batch(streams, L)
    starts, sizes, counts, bad, resid = frame_cursor_scan(buf, lens, 16)
    for i, exp in enumerate(expected):
        assert int(counts[i]) == len(exp)
        assert not bool(bad[i])
        for f, body in enumerate(exp):
            st, sz = int(starts[i, f]), int(sizes[i, f])
            assert streams[i][st:st + sz] == body
        # residual cursor leaves exactly the partial tail
        consumed = int(resid[i])
        assert consumed == sum(4 + len(b) for b in exp)


def test_cursor_scan_flags_bad_length():
    evil = struct.pack('>i', -5) + b'\x00' * 16
    ok = _reply_frame(1, 7, 0)
    buf, lens = _pad_batch([evil, ok + evil, ok], 64)
    starts, sizes, counts, bad, resid = frame_cursor_scan(buf, lens, 8)
    assert bool(bad[0]) and int(counts[0]) == 0
    assert bool(bad[1]) and int(counts[1]) == 1  # good frame still decoded
    assert not bool(bad[2]) and int(counts[2]) == 1
    # the scalar decoder agrees these are BAD_LENGTH streams
    with pytest.raises(ZKProtocolError):
        FrameDecoder().feed(evil)


def test_pointer_doubling_matches_cursor_scan():
    rng = random.Random(5)
    for trial in range(6):
        s, _ = _random_stream(rng, rng.randrange(1, 20), max_body=32)
        if trial % 2:
            s += b'\x00\x00'  # truncated tail
        L = len(s) + (16 - len(s) % 16) % 16 + 16
        pad = np.zeros(L, np.uint8)
        pad[:len(s)] = np.frombuffer(s, np.uint8)
        is_start, bad = frame_starts_pointer_doubling(
            jnp.asarray(pad), jnp.int32(len(s)))
        got = np.nonzero(np.asarray(is_start))[0].tolist()
        dec = FrameDecoder()
        bodies = dec.feed(s)
        exp = []
        off = 0
        for b in bodies:
            exp.append(off)
            off += 4 + len(b)
        assert got == exp
        assert not bool(bad)


def test_pointer_doubling_bad_prefix_reachable():
    s = _reply_frame(1, 1, 0) + struct.pack('>i', -1) + b'\x00' * 8
    pad = np.zeros(64, np.uint8)
    pad[:len(s)] = np.frombuffer(s, np.uint8)
    is_start, bad = frame_starts_pointer_doubling(
        jnp.asarray(pad), jnp.int32(len(s)))
    assert bool(bad)
    assert np.nonzero(np.asarray(is_start))[0].tolist() == [0]


# -------------------------------------------------------------- headers


def test_headers_and_stats():
    rng = random.Random(6)
    streams, metas = [], []
    for _ in range(8):
        s, m = _random_stream(rng, rng.randrange(0, 10))
        streams.append(s)
        metas.append(m)
    L = max((len(s) for s in streams), default=0) + 8
    buf, lens = _pad_batch(streams, L)
    starts, sizes, counts, bad, resid = frame_cursor_scan(buf, lens, 16)
    hdrs = parse_reply_headers(buf, starts)
    stats = stream_stats(hdrs)
    for i, m in enumerate(metas):
        assert int(counts[i]) == len(m)
        for f, (xid, zxid, err) in enumerate(m):
            assert int(hdrs['xid'][i, f]) == xid
            assert int(hdrs['err'][i, f]) == err
            if xid >= 0:
                assert u64pair_to_int(hdrs['zxid_hi'][i, f],
                                      hdrs['zxid_lo'][i, f]) == zxid
        replies = [t for t in m if t[0] >= 0]
        assert int(stats['n_replies'][i]) == len(replies)
        assert int(stats['n_notifications'][i]) == sum(
            1 for t in m if t[0] == -1)
        assert int(stats['n_pings'][i]) == sum(1 for t in m if t[0] == -2)
        assert int(stats['n_errors'][i]) == sum(
            1 for t in replies if t[2] != 0)
        max_z = max((t[1] for t in replies), default=0)
        assert u64pair_to_int(stats['max_zxid_hi'][i],
                              stats['max_zxid_lo'][i]) == max_z


def test_short_frame_flagged_not_misparsed():
    # a zero-length frame followed by a real reply: the header parser
    # must not read the next frame's bytes as a header (regression:
    # corrupted max-zxid checkpoint), and the stream is flagged bad
    s = struct.pack('>i', 0) + _reply_frame(5, 9, 0)
    buf, lens = _pad_batch([s], 64)
    out = wire_pipeline_step(buf, lens, max_frames=8)
    assert int(out.n_frames[0]) == 2  # both frames sliced
    assert bool(out.bad[0])
    assert int(out.n_replies[0]) == 1  # only the real reply counted
    assert u64pair_to_int(out.max_zxid_hi[0], out.max_zxid_lo[0]) == 9


# ------------------------------------------------------------- pipeline


def test_wire_pipeline_step_end_to_end_jit():
    rng = random.Random(7)
    streams = [_random_stream(rng, 5)[0] for _ in range(4)]
    L = max(len(s) for s in streams) + 4
    buf, lens = _pad_batch(streams, L)
    step = jax.jit(wire_pipeline_step, static_argnames='max_frames')
    out = step(buf, lens, max_frames=8)
    assert out.n_frames.shape == (4,)
    assert int(jnp.sum(out.n_frames)) == 20
    # decoding is deterministic
    out2 = step(buf, lens, max_frames=8)
    assert np.array_equal(np.asarray(out.starts), np.asarray(out2.starts))
