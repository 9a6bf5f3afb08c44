"""The fleet ingest's size classes against the per-socket scalar drain.

A tick's rows are dispatched by width (io/ingest.py, "Size classes"):
one dispatch a power-of-two class present, a slot whose first frame is
not whole yet waits, one dispatch holds at most ``DISPATCH_BYTES``.
The reference is, as in tests/test_ingest_route.py, the same real
``ZKConnection`` + ``ZKSession`` without an ingest: one seeded corpus
of heavy-tailed reply sizes, cut into seeded random chunks, goes
through both, and what every connection observed must be identical.
Beside parity: what the tick copied, padded and deferred (its
always-on counters), and what ``prewarm`` compiles.

A slot that holds exactly ONE whole frame wider than ``min_len`` gives
a header row (the frame's first ``min_len`` bytes under its true
length) and stands in the narrowest class whatever its size: the cases
that drive the wide classes, the dispatch bound and the full tick give
their slots a frame AND a tail (``tailed``), which is what still
reaches them.
"""

import random

import numpy as np
import pytest

from test_ingest_route import Peer, _Time, settle
from zkstream_tpu.io import ingest as ingest_mod
from zkstream_tpu.io import session as session_mod
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.protocol.records import Stat
from zkstream_tpu.utils import native

MIN_LEN = 256
#: a GET_DATA reply frame over its data: length prefix, reply header,
#: the data's own length, the Stat
OVERHEAD = 4 + 16 + 4 + 68


def _ingest(**kw) -> FleetIngest:
    kw.setdefault('max_frames', 4)
    return FleetIngest(bypass_bytes=0, warm='block', placement='host',
                       min_len=MIN_LEN, **kw)


def reply_sized(p: Peer, xid: int, size: int) -> int:
    """A GET_DATA reply of ``size`` data bytes onto the peer's wire;
    returns the frame's bytes (prefix included)."""
    before = len(p.wire)
    p.wire += p.srv.encode({
        'xid': xid, 'zxid': p._next_zxid(), 'err': 'OK',
        'opcode': 'GET_DATA', 'data': p.rng.randbytes(size),
        'stat': Stat(*(p.rng.randrange(1 << 20) for _ in range(11)))})
    return len(p.wire) - before


#: the small reply ``tailed`` puts behind a frame: its data, its bytes
TAIL = 8
TAIL_FRAME = OVERHEAD + TAIL


def tailed(p: Peer, size: int) -> int:
    """A GET_DATA reply of ``size`` data bytes and a small one behind
    it: a slot that holds more than its first frame, so its row stands
    in the class of its bytes.  Returns the two frames' bytes."""
    return (reply_sized(p, p.get(), size)
            + reply_sized(p, p.get(), TAIL))


def heavy_tailed(rng, top: int = 1 << 18) -> int:
    """A size whose class is uniform over 1 KiB .. ``top``'s and below
    (log-uniform from 16 B): many small, a few near ``top``."""
    return min(top - OVERHEAD, int(16 * 2 ** (rng.random() * 14.5)))


async def feed_chunked(peers, rng) -> None:
    """Every peer's wire, cut into seeded random chunks, handed to its
    connection round-robin, the loop turning between rounds."""
    wires = [bytes(p.wire) for p in peers]
    for p in peers:
        p.wire = bytearray()
    offs = [0] * len(peers)
    while any(o < len(w) for o, w in zip(offs, wires)):
        for i, p in enumerate(peers):
            if offs[i] < len(wires[i]) and rng.random() < 0.8:
                n = rng.choice((1, 3, 200, 4096, 65536, 1 << 20))
                n = rng.randrange(1, n + 1)
                p.conn.emit('sockData', wires[i][offs[i]:offs[i] + n])
                offs[i] += n
        await settle()
    await settle()


async def run_corpus(through_ingest: bool, use_native: bool, seed: int,
                     watch=None):
    ingest = _ingest() if through_ingest else None
    if watch is not None and ingest is not None:
        watch(ingest)
    peers = [Peer(i, ingest, use_native, random.Random(seed * 977 + i))
             for i in range(10)]
    rng = random.Random(seed)
    try:
        for p in peers:
            for _ in range(rng.randrange(3, 9)):
                reply_sized(p, p.get(), heavy_tailed(p.rng))
        await feed_chunked(peers, rng)
        snaps = [p.snapshot(ingest) for p in peers]
    finally:
        for p in peers:
            p.session.close()
            p.conn.destroy()
        await settle()
        if ingest is not None:
            ingest.close()
    return snaps, ingest


def _patch_clock(monkeypatch) -> None:
    monkeypatch.setattr(session_mod, 'time', _Time)
    monkeypatch.setattr(ingest_mod, 'time', _Time)


def _codec(use_native: bool, monkeypatch) -> None:
    if use_native:
        if native.ensure_ext() is None:
            pytest.skip('no C extension here (no compiler)')
    else:
        monkeypatch.setenv('ZKSTREAM_NO_NATIVE', '1')


#: the async runner's default budget (tests/conftest.py: 30 s of wall
#: clock a test) is for tests that wait on sockets; these compile
#: dozens of tick programs (every class x row count the corpus
#: reaches), which alone takes ~10-25 s and several times that beside
#: five other workers: the budget of a test that compiles is set by
#: what it compiles, not by the machine's load
COMPILES = pytest.mark.timeout(300)


@COMPILES
@pytest.mark.parametrize('use_native', [True, False],
                         ids=['ext', 'no_native'])
@pytest.mark.parametrize('seed', [3, 30, 300])
async def test_heavy_tailed_streams_equal_the_scalar_drain(
        seed, use_native, monkeypatch):
    """Classes 1 KiB .. 256 KiB at ``min_len`` 256, any chunking: per
    stream and in order exactly what the scalar drain delivers; every
    dispatch wider than ``min_len`` pads to under four times its
    payload (or is the 8 x ``min_len`` floor); nothing was drained off
    the device."""
    _codec(use_native, monkeypatch)
    _patch_clock(monkeypatch)
    seen: list = []

    def watch(ingest):
        # the ticks' batch memory is used again and never zeroed: what
        # an earlier tick left in the padding must not matter
        ingest._arena = np.full((ingest.TICK_BYTES,), 0xFF, np.uint8)
        inner = ingest._dispatch

        def noted(plans, before, t0):
            seen.extend((key, nbytes, len(streams))
                        for _ex, key, streams, _b, _l, nbytes, *_h in plans)
            return inner(plans, before, t0)
        ingest._dispatch = noted

    want, _none = await run_corpus(False, use_native, seed)
    got, ingest = await run_corpus(True, use_native, seed, watch)
    for i, (w, g) in enumerate(zip(want, got)):
        assert g == w, 'connection %d differs' % i
    assert sum(len(w['log']) for w in want) > 40
    assert ingest.ticks and not (ingest.ticks_scalar
                                 or ingest.ticks_warming)
    widths = {key[2] for key, _n, _r in seen}
    assert len(widths) >= 6 and max(widths) >= 1 << 17
    for (_dev, bp, width), nbytes, rows in seen:
        assert rows <= bp and nbytes <= bp * width
        if width > MIN_LEN:
            assert bp * width < 4 * nbytes or bp * width <= 8 * MIN_LEN
    assert ingest.dispatches == len(seen) > ingest.ticks
    assert ingest.bytes_batched == sum(n for _k, n, _r in seen)
    assert ingest.bytes_dispatched == sum(k[1] * k[2] for k, _n, _r in seen)
    # a reply that came in pieces waited for its last one
    assert ingest.slots_deferred > 0


async def _one_peer(ingest, seed: int = 5) -> Peer:
    return Peer(0, ingest, native.ensure_ext() is not None,
                random.Random(seed))


def _futs(p: Peer) -> list:
    return [(e[1], len(e[2]['data'])) for e in p.log if e[0] == 'fut']


@pytest.mark.parametrize('tail', [True, False], ids=['tailed', 'alone'])
async def test_large_frame_in_pieces_is_batched_once(tail):
    """A 200 KiB reply over 26 reads: the slot sits the ticks out (the
    host reads the first frame's length prefix, no more) and gives the
    tick its bytes once, when the frame is whole — with the small reply
    that came behind it a 256 KiB row, alone a header row."""
    ingest = _ingest()
    p = await _one_peer(ingest)
    large = reply_sized(p, p.get(), 200 * 1024)
    total = large + (reply_sized(p, p.get(), TAIL) if tail else 0)
    wire, p.wire = bytes(p.wire), bytearray()
    for lo in range(0, total, 8192):
        p.conn.emit('sockData', wire[lo:lo + 8192])
        await settle()
    assert _futs(p) == [(1, 200 * 1024)] + [(2, TAIL)] * tail
    assert ingest.bytes_recopied == 0
    assert ingest.dispatches == ingest.ticks == 1
    assert ingest.slots_deferred == (large - 1) // 8192
    if tail:
        assert ingest.bytes_batched == total
        assert ingest.bytes_dispatched == 1 << 18       # [1, 256 KiB]
        assert ingest.rows_headed == ingest.bytes_kept_home == 0
    else:
        assert ingest.bytes_batched == MIN_LEN
        assert ingest.bytes_dispatched == 8 * MIN_LEN   # [8, min_len]
        assert ingest.rows_headed == 1
        assert ingest.bytes_kept_home == large - MIN_LEN
    p.conn.destroy()
    ingest.close()


async def test_small_frame_then_partial_large_one_in_one_slot():
    """The small reply is delivered at once; of the partial large one
    behind it the tick copies no more than the frame bound could have
    consumed at the small one's size, and then it waits like any
    partial first frame: given to a tick once more, whole."""
    ingest = _ingest()
    p = await _one_peer(ingest)
    small = reply_sized(p, p.get(), 8)
    large = reply_sized(p, p.get(), 150 * 1024)
    wire, p.wire = bytes(p.wire), bytearray()
    p.conn.emit('sockData', wire[:small + 100 * 1024])
    await settle()
    assert _futs(p) == [(1, 8)]
    cut = 512               # the power of two over max_frames x small
    assert small * ingest.max_frames <= cut < 2 * small * ingest.max_frames
    assert ingest.bytes_batched == cut
    assert ingest.bytes_recopied == cut - small
    p.conn.emit('sockData', wire[small + 100 * 1024:])
    await settle()
    assert _futs(p) == [(1, 8), (2, 150 * 1024)]
    # ...and whole it is all the slot holds: a header row
    assert ingest.bytes_batched == cut + MIN_LEN
    assert ingest.rows_headed == 1
    assert ingest.bytes_kept_home == large - MIN_LEN
    assert ingest.bytes_recopied == cut - small
    assert ingest.slots_deferred >= 1
    p.conn.destroy()
    ingest.close()


async def test_wide_row_meets_the_frame_bound():
    """Six 3 KiB replies in one slot at ``max_frames`` 4: the row is
    the 16 KiB the bound can consume, four frames route, the follow-up
    tick takes the rest — in order."""
    ingest = _ingest()
    p = await _one_peer(ingest)
    for _ in range(6):
        reply_sized(p, p.get(), 3 * 1024)
    p.flush()
    await settle()
    assert _futs(p) == [(x, 3 * 1024) for x in range(1, 7)]
    assert ingest.ticks == 2 and ingest.frames_routed == 6
    assert sorted(k[2] for k in ingest.buckets) == [1 << 13, 1 << 14]
    p.conn.destroy()
    ingest.close()


async def test_frame_wider_than_a_dispatch_still_routes_on_the_device():
    """``DISPATCH_BYTES`` bounds the rows of a class, not a frame: one
    wider than it is a dispatch of its own one row."""
    ingest = _ingest()
    ingest.DISPATCH_BYTES = 1 << 16
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(3)]
    for p, size in zip(peers, (200 * 1024, 30 * 1024, 30 * 1024)):
        tailed(p, size)
        p.flush()
    await settle()
    assert [_futs(p) for p in peers] == [
        [(1, size), (2, TAIL)]
        for size in (200 * 1024, 30 * 1024, 30 * 1024)]
    assert ingest.ticks == 1 and not ingest.ticks_scalar
    assert ingest.rows_headed == 0
    # the two 32 KiB rows fill a dispatch; the 256 KiB row is alone
    assert sorted(ingest.buckets) == [(False, 1, 1 << 18),
                                      (False, 2, 1 << 15)]
    for p in peers:
        p.conn.destroy()
    ingest.close()


async def test_a_class_beyond_the_dispatch_bound_splits():
    """Five 30 KiB rows (a frame and a tail each) at a 64 KiB bound:
    2 + 2 + 1, one tick, each stream in one dispatch."""
    ingest = _ingest()
    ingest.DISPATCH_BYTES = 1 << 16
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(5)]
    for p in peers:
        tailed(p, 30 * 1024)
        p.flush()
    await settle()
    assert all(_futs(p) == [(1, 30 * 1024), (2, TAIL)] for p in peers)
    assert ingest.ticks == 1 and ingest.dispatches == 3
    assert ingest.bytes_dispatched == (2 + 2 + 1) << 15
    assert ingest.rows_headed == 0
    for p in peers:
        p.conn.destroy()
    ingest.close()


async def test_a_full_tick_leaves_the_rest_to_the_follow_up_tick():
    """``TICK_BYTES`` bounds what one tick dispatches (its batches lie
    side by side in memory every tick uses again): five 30 KiB rows (a
    frame and a tail each), one a dispatch, two dispatches a tick —
    three ticks, every reply delivered."""
    ingest = _ingest()
    ingest.DISPATCH_BYTES = 1 << 15
    ingest.TICK_BYTES = 1 << 16
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(5)]
    for p in peers:
        tailed(p, 30 * 1024)
        p.flush()
    await settle()
    await settle()
    assert all(_futs(p) == [(1, 30 * 1024), (2, TAIL)] for p in peers)
    assert ingest.ticks == 3 and ingest.dispatches == 5
    assert ingest.rows_headed == 0
    assert len(ingest._arena) == 1 << 16
    for p in peers:
        p.conn.destroy()
    ingest.close()


async def _run_peers(ingest, use_native: bool, script, n: int,
                     seed: int = 70):
    """``n`` fresh peers on ``ingest`` (None: the per-socket scalar
    drain), ``script(peers)`` (a coroutine function) writing and
    handing over their wires; returns every connection's snapshot."""
    if ingest is not None:
        # batch memory full of an earlier tick's bytes: a header row
        # leaves most of its row as it found it
        ingest._arena = np.full((ingest.TICK_BYTES,), 0xFF, np.uint8)
    peers = [Peer(i, ingest, use_native, random.Random(seed + i))
             for i in range(n)]
    await script(peers)
    await settle()
    snaps = [p.snapshot(ingest) for p in peers]
    for p in peers:
        p.session.close()
        p.conn.destroy()
    await settle()
    return snaps


#: single frames of 8 KiB .. 1 MiB (one exactly a power of two wide),
#: and one under ``min_len`` beside them
ALONE = (8 << 10, 20_000, (1 << 16) - OVERHEAD, 100_000, 300_000,
         700_000, (1 << 20) - OVERHEAD, 10)


@pytest.mark.parametrize('use_native', [True, False],
                         ids=['ext', 'no_native'])
async def test_single_frame_slots_are_one_narrow_dispatch(
        use_native, monkeypatch):
    """(a) Slots that hold exactly one whole frame each, 8 KiB .. 1 MiB:
    ONE dispatch in the ``min_len`` bucket however wide the frames, the
    small reply's row beside them; delivered byte for byte and in order
    what the scalar codec delivers."""
    _codec(use_native, monkeypatch)
    _patch_clock(monkeypatch)
    frames: list = []

    async def script(peers):
        frames[:] = [reply_sized(p, p.get(), size)
                     for p, size in zip(peers, ALONE)]
        for p in peers:
            p.flush()

    want = await _run_peers(None, use_native, script, len(ALONE))
    ingest = _ingest()
    got = await _run_peers(ingest, use_native, script, len(ALONE))
    for i, (w, g) in enumerate(zip(want, got)):
        assert g == w, 'connection %d differs' % i
    assert ingest.ticks == ingest.dispatches == 1
    assert sorted(ingest.buckets) == [(False, 8, MIN_LEN)]
    assert ingest.bytes_dispatched == 8 * MIN_LEN
    assert ingest.rows_headed == 7
    assert ingest.bytes_batched == 7 * MIN_LEN + frames[7]
    assert ingest.bytes_kept_home == sum(frames[:7]) - 7 * MIN_LEN
    assert ingest.bytes_recopied == 0 and not ingest.slots_cut
    assert not (ingest.ticks_scalar or ingest.ticks_warming)
    ingest.close()


async def test_a_frame_and_a_tail_and_a_deep_row_dispatch_as_before():
    """(b) What lies behind a slot's first frame is unknown until the
    device has scanned it: a wide frame with a small one behind it and
    a row of 8 frames of 1.1 KB (a pipelined session's) stand in the
    classes of their bytes, and neither is a header row."""
    ingest = _ingest(max_frames=8)
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(2)]
    wide = tailed(peers[0], 40_000)
    deep = sum(reply_sized(peers[1], peers[1].get(), 1024)
               for _ in range(8))
    for p in peers:
        p.flush()
    await settle()
    assert _futs(peers[0]) == [(1, 40_000), (2, TAIL)]
    assert _futs(peers[1]) == [(x, 1024) for x in range(1, 9)]
    assert ingest.rows_headed == ingest.bytes_kept_home == 0
    assert ingest.ticks == 1 and ingest.dispatches == 2
    assert sorted(ingest.buckets) == [(False, 1, 1 << 14),
                                      (False, 1, 1 << 16)]
    assert ingest.bytes_dispatched == (1 << 14) + (1 << 16)
    assert ingest.bytes_batched == wide + deep
    assert ingest.slots_bound == 1
    for p in peers:
        p.conn.destroy()
    ingest.close()


async def test_a_notification_in_front_of_a_wide_reply():
    """The tick takes of the slot what the frame bound could consume at
    the notification's size, as it always did; the reply is then all
    the slot holds, and the follow-up tick's header row."""
    ingest = _ingest()
    p = await _one_peer(ingest)
    p.notification()
    front = len(p.wire)
    large = reply_sized(p, p.get(), 5000)
    p.flush()
    await settle()
    assert [e[0] for e in p.log[-2:]] == ['notify', 'fut']
    assert _futs(p) == [(1, 5000)]
    assert ingest.ticks == 2 and ingest.slots_cut == 1
    cut = ingest._width(front * ingest.max_frames)
    assert ingest.bytes_batched == cut + MIN_LEN
    assert ingest.bytes_recopied == cut - front
    assert ingest.rows_headed == 1
    assert ingest.bytes_kept_home == large - MIN_LEN
    p.conn.destroy()
    ingest.close()


@pytest.mark.parametrize('heads,buckets', [
    # beside a [1, 8 KiB] dispatch: one more row of it
    ((30_000,), [(False, 2, 1 << 13)]),
    # three: four rows of 8 KiB are still under a dispatch's cost
    ((30_000, 9000, 500_000), [(False, 4, 1 << 13)]),
], ids=['one', 'three'])
async def test_header_rows_beside_wider_groups_add_no_dispatch(
        heads, buckets):
    """(c) A tick whose other rows all stand in wider classes (a frame
    and a tail each): its header rows ride the narrowest dispatch
    present — a header row fits any width."""
    ingest = _ingest()
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(len(heads) + 1)]
    tailed(peers[0], 5000)
    for p, size in zip(peers[1:], heads):
        reply_sized(p, p.get(), size)
    for p in peers:
        p.flush()
    await settle()
    assert _futs(peers[0]) == [(1, 5000), (2, TAIL)]
    assert [_futs(p) for p in peers[1:]] == [[(1, n)] for n in heads]
    assert ingest.ticks == ingest.dispatches == 1
    assert sorted(ingest.buckets) == buckets
    assert ingest.rows_headed == len(heads)
    for p in peers:
        p.conn.destroy()
    ingest.close()


async def test_header_rows_do_not_widen_a_large_dispatch():
    """...but not at any price: rows that would add more than
    ``RIDE_BYTES`` of padding to the dispatch they ride are a
    ``min_len`` dispatch of their own — two dispatches, where their
    frames' own classes had made three."""
    ingest = _ingest()
    ingest.RIDE_BYTES = 1 << 16
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(3)]
    tailed(peers[0], 40_000)                    # [1, 64 KiB]
    for p, size in zip(peers[1:], (9000, 70_000)):
        reply_sized(p, p.get(), size)
    for p in peers:
        p.flush()
    await settle()
    assert [_futs(p) for p in peers] == [
        [(1, 40_000), (2, TAIL)], [(1, 9000)], [(1, 70_000)]]
    assert ingest.ticks == 1 and ingest.dispatches == 2
    assert sorted(ingest.buckets) == [(False, 1, 1 << 16),
                                      (False, 8, MIN_LEN)]
    assert ingest.rows_headed == 2
    for p in peers:
        p.conn.destroy()
    ingest.close()


def _frame(body: bytes) -> bytes:
    return len(body).to_bytes(4, 'big') + body


#: streams that end their connection, each all its slot holds: a body
#: under the 16-byte reply header; a wide frame whose reply header is
#: sound and whose body is not (its data's length runs past the frame);
#: a prefix over the cap, and a negative one
BROKEN = {
    'short_body': (lambda xid: _frame(b'\x00' * 8), 'BAD_DECODE'),
    'wide_bad_body': (lambda xid: _frame(
        xid.to_bytes(4, 'big') + (77).to_bytes(8, 'big') + bytes(4)
        + (1 << 20).to_bytes(4, 'big') + b'x' * 3000), 'BAD_DECODE'),
    'prefix_over_cap': (lambda xid: b'\x7f\xff\xff\xf0' + b'x' * 5000,
                        'FRAME_TOO_LARGE'),
    'prefix_negative': (lambda xid: b'\xff\xff\xff\xf0' + b'x' * 5000,
                        'BAD_LENGTH'),
}


@pytest.mark.parametrize('use_native', [True, False],
                         ids=['ext', 'no_native'])
@pytest.mark.parametrize('what', sorted(BROKEN))
async def test_a_broken_single_frame_is_the_scalar_error(
        what, use_native, monkeypatch):
    """(d) The errors are today's: the same code, the same message, the
    same state of the connection as the per-socket scalar drain — the
    wide frame with the broken body as a header row (the host decodes
    it from the slot, as it decodes every body)."""
    _codec(use_native, monkeypatch)
    _patch_clock(monkeypatch)
    make, code = BROKEN[what]

    async def script(peers):
        good, bad = peers
        reply_sized(good, good.get(), 9000)
        bad.raw(make(bad.get()))
        for p in peers:
            p.flush()

    want = await _run_peers(None, use_native, script, 2)
    ingest = _ingest()
    got = await _run_peers(ingest, use_native, script, 2)
    if what == 'wide_bad_body' and not use_native:
        # without the extension the tick's body reader counts its
        # offsets from the body and the codec's from the frame (the
        # text of the error, wide frame or not, header row or not)
        for snaps in (want, got):
            snaps[1]['last_error'] = snaps[1]['last_error'][:2]
    assert got == want
    assert want[1]['last_error'][1] == code
    assert ingest.ticks == ingest.dispatches == 1
    assert ingest.rows_headed == 1 + (what == 'wide_bad_body')
    assert not ingest.ticks_scalar
    ingest.close()


async def test_a_wide_frame_is_a_header_row_only_once_it_is_whole():
    """(d) A first frame that is not whole yet waits, as it always did
    (``slots_deferred``), and is a header row the tick it is whole —
    unless more came behind it meanwhile."""
    ingest = _ingest()
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(2)]
    large = [reply_sized(p, p.get(), 50_000) for p in peers]
    reply_sized(peers[1], peers[1].get(), TAIL)
    wires = [p.take() for p in peers]
    for p, wire in zip(peers, wires):
        p.conn.emit('sockData', wire[:30_000])
    await settle()
    assert ingest.slots_deferred == 2 and not ingest.ticks
    assert not any(_futs(p) for p in peers)
    for p, wire in zip(peers, wires):
        p.conn.emit('sockData', wire[30_000:])
    await settle()
    assert _futs(peers[0]) == [(1, 50_000)]
    assert _futs(peers[1]) == [(1, 50_000), (2, TAIL)]
    # ...whose row rides the other's dispatch
    assert ingest.ticks == ingest.dispatches == 1
    assert ingest.rows_headed == 1
    assert ingest.bytes_kept_home == large[0] - MIN_LEN
    assert sorted(ingest.buckets) == [(False, 2, 1 << 16)]
    for p in peers:
        p.conn.destroy()
    ingest.close()


async def test_header_rows_are_counted_on_the_tick_span_and_the_gauges(
        monkeypatch):
    """``headed`` / ``kept`` on the ``ingest.tick`` host span, the
    dispatch's ``nbytes`` what was copied; the two always-on counters as
    gauges beside ``zkstream_ingest_dispatched_bytes``."""
    from zkstream_tpu.utils import trace
    from zkstream_tpu.utils.metrics import Collector

    ingest = _ingest()
    col = Collector()
    ingest.bind_metrics(col)
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(2)]
    frames = [reply_sized(p, p.get(), size)
              for p, size in zip(peers, (10, 30_000))]
    await ingest.prewarm(2)
    trace.host_ring.reset()
    monkeypatch.setattr(trace, '_is_enabled', lambda: True)
    monkeypatch.setattr(trace, '_recording', True)
    for p in peers:
        p.flush()
    await settle()
    monkeypatch.setattr(trace, '_is_enabled', lambda: False)
    spans = {s.op: s for s in trace.host_ring.spans()
             if s.op.startswith('ingest.')}
    tick, sent = spans['ingest.tick'], spans['ingest.dispatch']
    assert (tick.headed, tick.kept) == (1, frames[1] - MIN_LEN)
    assert tick.nbytes == sent.nbytes == frames[0] + MIN_LEN
    assert (sent.rows, sent.width) == (2, MIN_LEN)
    assert tick.to_dict()['headed'] == 1
    text = col.expose()
    assert 'zkstream_ingest_headed_rows 1' in text
    assert 'zkstream_ingest_kept_home_bytes %d' % (frames[1] - MIN_LEN,) \
        in text
    trace.host_ring.reset()
    for p in peers:
        p.conn.destroy()
    ingest.close()


async def test_bad_prefix_behind_the_classes_is_the_scalar_error():
    """A length prefix no frame can have is the device's to flag, wide
    slot or not: the stream dies with the scalar drain's error."""
    ingest = _ingest()
    p = await _one_peer(ingest)
    p.raw(b'\xff\xff\xff\xf0' + b'x' * 5000)
    p.flush()
    await settle()
    assert p.conn.last_error is not None
    assert getattr(p.conn.last_error, 'code', None) == 'BAD_LENGTH'
    assert ingest.bytes_batched == MIN_LEN      # not the 5 KB behind it
    p.session.close()
    await settle()
    ingest.close()


@pytest.mark.parametrize('n,nbytes,bound,key', [
    (1, None, 16 << 20, (False, 8, 256)),          # as before classes
    (24, None, 16 << 20, (False, 32, 256)),
    (1024, None, 16 << 20, (False, 1024, 256)),
    (1, 300, 16 << 20, (False, 4, 512)),           # 8 x min_len floor
    (3, 1024, 16 << 20, (False, 4, 1024)),
    (1, 4096, 16 << 20, (False, 1, 4096)),
    (100, 1 << 16, 1 << 18, (False, 4, 1 << 16)),  # the dispatch bound
    (100, 1 << 16, 1 << 14, (False, 1, 1 << 16)),
])
async def test_prewarm_compiles_the_bucket_asked_for(n, nbytes, bound,
                                                     key):
    ingest = _ingest()
    ingest.DISPATCH_BYTES = bound
    await (ingest.prewarm(n) if nbytes is None
           else ingest.prewarm(n, nbytes))
    assert list(ingest.buckets) == [key]
    assert ingest.buckets[key]['error'] is None
    ingest.close()


@COMPILES
async def test_no_bucket_after_warmup_in_a_run_of_mixed_sizes(
        monkeypatch):
    """Warmed as a deployment warms (every class its replies reach x
    every row count its fleet can give a dispatch), a run of mixed
    sizes compiles nothing."""
    _patch_clock(monkeypatch)

    def watch(ingest):
        async def warm():
            # a slot may give max_frames replies of the largest size
            width = MIN_LEN
            while width <= ingest.max_frames << 18:
                rows = 1
                while rows <= 16:
                    await ingest.prewarm(rows, width)
                    rows *= 2
                width *= 2
        # run_corpus is already inside the loop: warm synchronously
        # (warm='block' never awaits)
        coro = warm()
        with pytest.raises(StopIteration):
            coro.send(None)
        ingest.warmed = set(ingest.buckets)

    _snaps, ingest = await run_corpus(True, native.ensure_ext() is not None,
                                      31, watch)
    assert ingest.ticks > 5
    assert set(ingest.buckets) == ingest.warmed
    assert not any(b['error'] for b in ingest.buckets.values())


async def test_mesh_ingest_adds_up_a_ticks_dispatches():
    """The mesh proxy's fleet stats are a tick's, not its last
    dispatch's: a tick of two classes is two collective launches."""
    from zkstream_tpu.parallel.fleet import MeshFleetIngest

    ingest = MeshFleetIngest(min_len=MIN_LEN, max_frames=4, warm='block')
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(4)]
    for p, size in zip(peers, (10, 10, 5000, 40000)):
        reply_sized(p, p.get(), size)
        reply_sized(p, p.get(), size)
        p.flush()
    await settle()
    assert [_futs(p) for p in peers] == [
        [(1, s), (2, s)] for s in (10, 10, 5000, 40000)]
    assert ingest.ticks == 1 and ingest.dispatches == 3
    assert ingest.global_stats['total_frames'] == 8
    assert ingest.global_stats['total_replies'] == 8
    assert ingest.global_stats['max_zxid'] == max(p.zxid for p in peers)
    assert ingest.fleet_max_zxid == ingest.global_stats['max_zxid']
    for p in peers:
        p.conn.destroy()
    ingest.close()


async def test_spans_of_a_tick_of_two_classes(monkeypatch):
    """In a profiler session: one ``ingest.tick``, under it one
    ``ingest.batch``, an ``ingest.dispatch`` (with the dispatch's
    ``rows``, ``width`` and payload ``nbytes``) and an
    ``ingest.readback`` a size class, one ``ingest.route`` — all
    carrying the tick's number."""
    from zkstream_tpu.utils import trace

    ingest = _ingest()
    peers = [Peer(i, ingest, native.ensure_ext() is not None,
                  random.Random(i)) for i in range(3)]
    frames = [reply_sized(p, p.get(), size)
              for p, size in zip(peers, (10, 20))]
    frames.append(tailed(peers[2], 3000))     # a wide row: not alone
    await ingest.prewarm(2)
    await ingest.prewarm(1, 3000)
    trace.host_ring.reset()
    monkeypatch.setattr(trace, '_is_enabled', lambda: True)
    monkeypatch.setattr(trace, '_recording', True)
    for p in peers:
        p.flush()
    await settle()
    monkeypatch.setattr(trace, '_is_enabled', lambda: False)
    spans = [s for s in trace.host_ring.spans()
             if s.op.startswith('ingest.')]
    assert [s.op for s in spans] == [
        'ingest.batch', 'ingest.dispatch', 'ingest.dispatch',
        'ingest.readback', 'ingest.readback', 'ingest.route',
        'ingest.tick']
    tick = spans[-1]
    assert {s.tick for s in spans} == {1} and tick.parent is None
    assert all(s.parent == 'ingest.tick' for s in spans[:-1])
    assert tick.detail == 'device 2 dispatches streams=3'
    assert tick.batch == 4 and tick.nbytes == sum(frames)
    assert tick.headed == tick.kept == 0
    narrow, wide = spans[1], spans[2]
    assert (narrow.rows, narrow.width, narrow.nbytes) == (
        2, MIN_LEN, frames[0] + frames[1])
    assert (wide.rows, wide.width, wide.nbytes) == (1, 4096, frames[2])
    assert spans[5].lane == 4 and spans[5].emitted == 0
    assert 'rows' in wide.to_dict() and 'rows' not in tick.to_dict()
    trace.host_ring.reset()
    for p in peers:
        p.conn.destroy()
    ingest.close()


@pytest.mark.parametrize('use_native', [True, False],
                         ids=['ext', 'no_native'])
async def test_host_decode_reads_nothing_of_a_stale_batch(
        use_native, monkeypatch):
    """Replies of two classes over batch memory full of an earlier
    tick's bytes equal the scalar drain's: the scan and the body
    readers look at nothing beyond a row's own length."""
    _codec(use_native, monkeypatch)
    _patch_clock(monkeypatch)

    async def run(ingest):
        if ingest is not None:
            ingest._arena = np.full((ingest.TICK_BYTES,), 0xA5, np.uint8)
        peers = [Peer(i, ingest, use_native, random.Random(40 + i))
                 for i in range(4)]
        for p, size in zip(peers, (0, 100, 200, 700)):
            reply_sized(p, p.get(), size)
            reply_sized(p, p.get(), size // 2)
            p.flush()
        await settle()
        snaps = [p.snapshot(ingest) for p in peers]
        for p in peers:
            p.session.close()
            p.conn.destroy()
        await settle()
        return snaps

    want = await run(None)
    ingest = _ingest()
    got = await run(ingest)
    assert len(ingest.buckets) == 3
    for i, (w, g) in enumerate(zip(want, got)):
        assert g == w, 'connection %d differs in %s' % (
            i, [k for k in w if w[k] != g[k]])
    assert ingest.ticks == 1 and ingest.dispatches == 3
    ingest.close()
