"""The fleet ingest's early dispatch (io/ingest.py, "The early
dispatch"): a tick's batches go out at the end of the receive reap
that fed the slots, the scheduled tick reads them back and routes.

tests/test_ingest_route.py holds the parity — every case of its corpus
through a reap gives what the per-socket drain gives.  Here: what is
in flight and when, bytes that arrive behind a flight, the slot that
waits, the injector's tick-time faults at the new moment, a connection
torn down between the two halves, the regimes that never dispatch
ahead, and the counter.  The peers are test_ingest_route's (real
``ZKConnection`` + ``ZKSession``, no sockets), the reap is the tier's
real ``_rx_reap`` over a stand-in receiver (``ReapRig``) — every test
of a reap once with each delivery handed to the connection
(``through_sock_data``: the sinks withdrawn) and once with the
connections' sinks standing (``sunk``: the reap's one call appends to
the ingest's slots, io/transport.py ``rx_sink``).  Last: everything
that withdraws a sink, each against the same traffic with no sink.
"""

import asyncio
import random

import numpy as np
import pytest

from test_ingest_route import Peer, ReapRig, _Time, settle
from zkstream_tpu.io import ingest as ingest_mod
from zkstream_tpu.io import session as session_mod
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.utils import native
from zkstream_tpu.utils.metrics import Collector


def _ingest(**kw) -> FleetIngest:
    kw.setdefault('bypass_bytes', 0)
    kw.setdefault('warm', 'block')
    # one size class: a reply here is under 300 B
    return FleetIngest(placement='host', max_frames=4, min_len=512, **kw)


def _peers(ingest, n: int, seed: int = 7) -> list:
    ext = native.ensure_ext() is not None
    return [Peer(i, ingest, ext, random.Random(seed * 131 + i))
            for i in range(n)]


def _settled(p: Peer) -> list:
    """The xids whose futures settled at ``p``, in order."""
    return [e[1] for e in p.log if e[0] == 'fut']


async def _stop(ingest, peers) -> None:
    for p in peers:
        p.session.close()
        p.conn.destroy()
    await settle()
    ingest.close()


async def turn() -> None:
    """One loop iteration: what was scheduled before runs."""
    await asyncio.sleep(0)


@pytest.fixture(params=[False, True], ids=['through_sock_data', 'sunk'])
def rig(request) -> ReapRig:
    return ReapRig(sink=request.param)


def _fed(rig: ReapRig, n: int) -> bool:
    """``n`` deliveries so far went into sinks — where they stand."""
    return rig.fed == (n if rig.sink else 0)


async def test_a_reap_dispatches_and_the_next_tick_routes(rig):
    """The reap's end builds and dispatches the batch (``ticks``,
    ``dispatches`` and ``ticks_early`` move, nothing is routed); the
    scheduled tick routes it without dispatching again."""
    ingest = _ingest()
    peers = _peers(ingest, 3)
    try:
        xids = [p.get() for p in peers]
        for p, x in zip(peers, xids):
            p.reply(x)
        rig.flush(peers)
        assert (ingest.ticks, ingest.ticks_early, ingest.dispatches) \
            == (1, 1, 1)
        assert ingest._flight is not None and ingest.frames_routed == 0
        assert _fed(rig, 3) and rig.tier.received_reads == 3
        assert all(not _settled(p) for p in peers)
        await turn()
        assert [_settled(p) for p in peers] == [[x] for x in xids]
        assert ingest._flight is None and ingest.frames_routed == 3
        await settle()
        assert (ingest.ticks, ingest.ticks_early, ingest.dispatches) \
            == (1, 1, 1)
        assert ingest.phase_hist.count({'phase': 'batch'}) == 1
        assert ingest.phase_hist.count({'phase': 'route'}) == 1
        assert ingest.tick_hist.count() == 1
    finally:
        await _stop(ingest, peers)


async def test_a_dispatch_asks_for_its_results_copy_home_at_once(rig):
    """Every dispatch's result is asked to the host when it is made
    (``copy_to_host_async``), not when the readback wants it: the
    readback of a tick that stood behind other work finds it there."""
    ingest = _ingest()
    peers = _peers(ingest, 2)
    asked: list = []

    class Out:
        def __init__(self, arr):
            self.arr = arr

        def copy_to_host_async(self):
            asked.append(ingest._flight)    # None: still being dispatched

        def __array__(self, dtype=None, copy=None):
            return np.asarray(self.arr)

    try:
        await ingest.prewarm(2)
        for key, ex in list(ingest._exec.items()):
            ingest._exec[key] = lambda b, n, ex=ex: Out(ex(b, n))
        xids = [p.get() for p in peers]
        for p, x in zip(peers, xids):
            p.reply(x)
        rig.flush(peers)
        assert asked == [None] and ingest._flight is not None
        await settle()
        assert [_settled(p) for p in peers] == [[x] for x in xids]
        assert len(asked) == ingest.dispatches == 1
    finally:
        await _stop(ingest, peers)


async def test_bytes_behind_a_flight_wait_for_the_next_tick(rig):
    """One batch is in flight at most: a second reap before the tick
    feeds the slots and dispatches nothing; the tick routes the first
    batch alone, then dispatches what waited — the batch memory is not
    written under the flight — and the tick after routes that.  Every
    reply settles once, a connection's in the order sent."""
    ingest = _ingest()
    peers = _peers(ingest, 4)
    try:
        first = [p.get() for p in peers[:3]]
        for p, x in zip(peers, first):
            p.reply(x)
        rig.flush(peers[:3])
        arena = bytes(ingest._arena[:8 * 512])
        # behind the flight: a second reply on peer 1, a first on peer 3
        second = [peers[1].get(), peers[3].get()]
        peers[1].reply(second[0])
        peers[3].reply(second[1])
        rig.flush([peers[1], peers[3]])
        assert (ingest.ticks, ingest.dispatches) == (1, 1)
        assert bytes(ingest._arena[:8 * 512]) == arena
        assert peers[1].pending(ingest) and peers[3].pending(ingest)
        await turn()
        # the first batch routed; what waited went out at the tick's end
        assert [_settled(p) for p in peers] == [
            [first[0]], [first[1]], [first[2]], []]
        assert (ingest.ticks, ingest.ticks_early, ingest.dispatches) \
            == (2, 2, 2)
        await turn()
        assert [_settled(p) for p in peers] == [
            [first[0]], [first[1], second[0]], [first[2]], [second[1]]]
        await settle()
        assert ingest.ticks == 2 and ingest.frames_routed == 5
        assert not any(p.pending(ingest) for p in peers)
        assert _fed(rig, 5)
        assert not any(p.conn.reqs for p in peers)
    finally:
        await _stop(ingest, peers)


async def test_a_partial_first_frame_waits_at_the_reap(rig):
    """A slot whose first frame is not whole gives the early dispatch
    nothing: no tick, no flight, the scheduled tick does not scan
    again; the rest of the frame, a reap later, is dispatched."""
    ingest = _ingest()
    peers = _peers(ingest, 2)
    try:
        p = peers[0]
        x = p.get()
        p.reply(x)
        wire = p.take()
        rig.reap([(p.conn, wire[:-9])])
        assert (ingest.ticks, ingest.slots_deferred) == (0, 1)
        assert ingest._flight is None
        await settle()
        assert (ingest.ticks, ingest.slots_deferred) == (0, 1)
        assert _settled(p) == [] and p.pending(ingest) == wire[:-9]
        rig.reap([(p.conn, wire[-9:])])
        assert (ingest.ticks, ingest.ticks_early) == (1, 1)
        await settle()
        assert _settled(p) == [x] and not p.pending(ingest)
        assert _fed(rig, 2)
    finally:
        await _stop(ingest, peers)


class Injector:
    """The ingest's tick-time faults, decided by the test: reset the
    connections in ``resets`` once; withhold ``cut`` bytes of a slot's
    suffix once a connection in ``cuts``."""

    def __init__(self, resets=(), cuts=None):
        self.resets = set(resets)
        self.cuts = dict(cuts or {})
        self.asked = 0

    def ingest_reset(self, conn) -> bool:
        self.asked += 1
        if conn in self.resets:
            self.resets.discard(conn)
            return True
        return False

    def ingest_cut(self, conn, nbytes: int) -> int:
        return min(self.cuts.pop(conn, 0), nbytes - 1)


async def _injected(fed: str, sink: bool = False):
    """Three peers with two replies each; the injector resets peer 0 at
    its tick and withholds 11 bytes of peer 1's suffix; a third reply
    reaches peer 1 while its suffix is withheld (reap mode: while the
    batch is in flight).  Returns every peer's log and state."""
    ingest = _ingest()
    peers = _peers(ingest, 3)
    rig = ReapRig(sink)
    inj = ingest.faults = Injector(resets=[peers[0].conn],
                                   cuts={peers[1].conn: 11})

    def hand(ps):
        if fed == 'reap':
            rig.flush(ps)
        else:
            for p in ps:
                p.flush()

    try:
        xids = [[p.get(), p.get()] for p in peers]
        for p, (a, b) in zip(peers, xids):
            p.reply(a)
            p.reply(b)
        late = peers[1].get()
        hand(peers)
        if fed == 'reap':
            # the injector ran at the reap's end, with the dispatch
            assert inj.asked == 3 and ingest._flight is not None
            assert not peers[0].conn.is_in_state('connected')
            assert len(ingest._held[id(peers[1].conn)]) == 11
            peers[1].reply(late)
            n = len(peers[1].wire)
            hand([peers[1]])
            # behind the withheld suffix, not in front of it
            assert len(ingest._held[id(peers[1].conn)]) == 11 + n
        else:
            assert inj.asked == 0
            await turn()
            assert inj.asked >= 3
            peers[1].reply(late)
            hand([peers[1]])
        await settle()
        await settle()
        assert not ingest._held and ingest._flight is None
        assert rig.fed == 0         # an injector's ingest takes no sink
        return [(_settled(p), p.conn.get_state(), sorted(p.conn.reqs),
                 p.pending(ingest)) for p in peers], xids, late
    finally:
        await _stop(ingest, peers)


@pytest.mark.parametrize('sink', [False, True],
                         ids=['through_sock_data', 'sunk'])
async def test_injector_faults_at_the_early_dispatch(monkeypatch, sink):
    """The injector's tick reset and withheld suffix are applied where
    the batch is built — at the reap's end — and end as they do when
    the scheduled tick applies them: the reset connection's requests
    fail once, the cut connection's replies all settle, in order, the
    bytes fed behind the withheld suffix behind it."""
    monkeypatch.setattr(session_mod, 'time', _Time)
    monkeypatch.setattr(ingest_mod, 'time', _Time)
    want, xids, late = await _injected('push')
    got, _x, _l = await _injected('reap', sink)
    assert got == want
    assert got[1][0] == xids[1] + [late]      # in order, the late one last
    assert got[2][0] == xids[2]
    assert got[0][2] == []                    # the reset one's: all failed


async def test_teardown_between_dispatch_and_route_settles_once(rig):
    """A connection that leaves ``connected`` while its rows are in
    flight: its pending requests are failed by the teardown, once; the
    route drops its rows (and hands the xids the decode took back to
    its codec); the others' replies settle."""
    ingest = _ingest()
    peers = _peers(ingest, 3)
    try:
        xids = [[p.get(), p.get()] for p in peers]
        for p, (a, _b) in zip(peers, xids):
            p.reply(a)
        rig.flush(peers)
        assert ingest._flight is not None
        victim = peers[1]
        victim.conn.destroy()
        assert id(victim.conn) not in ingest._slots
        await settle()
        failed = [e for e in victim.log if e[0] == 'fut']
        assert [e[1] for e in failed] == xids[1]
        assert all(len(e) == 4 for e in failed)     # errors, not replies
        assert _settled(peers[0]) == [xids[0][0]]
        assert _settled(peers[2]) == [xids[2][0]]
        assert ingest.frames_routed == 2
        # the bytes went back to the codec at the teardown, and the
        # xids the tick's decode consumed for them are its again
        assert xids[1][0] in victim.conn.codec.xid_map
    finally:
        await _stop(ingest, peers)


async def test_the_direct_regime_never_dispatches_early(rig):
    """Pass-through: a reap's bytes are decoded and delivered in the
    delivery itself; nothing is batched, dispatched or in flight."""
    ingest = _ingest(bypass_bytes=16384)
    peers = _peers(ingest, 3)
    try:
        assert ingest.direct
        xids = [p.get() for p in peers]
        for p, x in zip(peers, xids):
            p.reply(x)
        rig.flush(peers)
        assert [_settled(p) for p in peers] == [[x] for x in xids]
        assert ingest._flight is None and not ingest._asked
        assert rig.fed == 0 and not rig.tier._sinks     # pass-through
        await settle()
        assert (ingest.ticks, ingest.ticks_early) == (0, 0)
        assert ingest.ticks_scalar == 1
    finally:
        await _stop(ingest, peers)


async def test_a_bucket_still_compiling_never_dispatches_early(rig):
    """``warm='background'``: the reap's end finds the bucket cold,
    starts its compile and drains the streams through the scalar codec
    there — no dispatch, no flight; once the bucket is warm the next
    reap dispatches ahead of its tick."""
    ingest = _ingest(warm='background', bypass_bytes=16384,
                     frag_guard=False)
    ingest._direct = False          # the batch regime, buckets cold
    ingest.bypass_bytes = 0
    peers = _peers(ingest, 3)
    try:
        xids = [p.get() for p in peers]
        for p, x in zip(peers, xids):
            p.reply(x)
        rig.flush(peers)
        assert [_settled(p) for p in peers] == [[x] for x in xids]
        assert (ingest.ticks, ingest.ticks_early, ingest.ticks_warming) \
            == (0, 0, 1)
        assert ingest._flight is None
        (ev,) = ingest._warm_events.values()
        await asyncio.wait_for(ev.wait(), 60)
        await settle()
        xids = [p.get() for p in peers]
        for p, x in zip(peers, xids):
            p.reply(x)
        rig.flush(peers)
        assert (ingest.ticks, ingest.ticks_early) == (1, 1)
        await settle()
        assert [_settled(p)[1:] for p in peers] == [[x] for x in xids]
    finally:
        await _stop(ingest, peers)


@pytest.mark.parametrize('fed', ['push', 'reap', 'sink'])
async def test_the_early_counter_counts_what_happened(fed):
    """``ticks_early`` beside ``ticks``: every tick a reap fed, and
    under asyncio's push only the follow-up ticks (here: the frame
    bound of 4 hit with 6 replies buffered); exported as
    ``zkstream_ingest_early_ticks``."""
    ingest = _ingest()
    col = Collector()
    ingest.bind_metrics(col)
    peers = _peers(ingest, 2)
    rig = ReapRig(sink=fed == 'sink')
    try:
        for n in (1, 6):
            xids = [[p.get() for _ in range(n)] for p in peers]
            for p, xs in zip(peers, xids):
                for x in xs:
                    p.reply(x)
            if fed == 'push':
                for p in peers:
                    p.flush()
            else:
                rig.flush(peers)
            await settle()
            assert [_settled(p)[-n:] for p in peers] == xids
        # 1 reply: one tick; 6 replies at 4 frames a tick: two
        assert ingest.ticks == 3
        assert ingest.ticks_early == (1 if fed == 'push' else 3)
        assert rig.fed == (4 if fed == 'sink' else 0)
        text = col.expose()
        assert 'zkstream_ingest_early_ticks %d' % ingest.ticks_early \
            in text
        assert 'zkstream_ingest_ticks 3' in text
    finally:
        await _stop(ingest, peers)


# -- what withdraws a sink (io/connection.py ``resink``) ---------------

class _PassInjector:
    """An injector that changes nothing: its presence is what is asked
    (on the connection: ``tx`` / ``rx``; on the ingest: the tick-time
    questions)."""

    def tx(self, conn, data):
        return data

    def rx(self, conn, data) -> None:
        conn.emit('sockData', data)

    def ingest_reset(self, conn) -> bool:
        return False

    def ingest_cut(self, conn, nbytes: int) -> int:
        return 0


def _waiting(p: Peer, ingest) -> bytes:
    """Bytes received and not yet decoded: the codec's (a flip to the
    pass-through regime hands them there), then the slot's."""
    pend = p.conn.codec.take_pending()
    p.conn.codec.restore_pending(pend)
    slot = ingest._slots.get(id(p.conn))
    return bytes(pend) + (b'' if slot is None else bytes(slot[1]))


async def _withdrawn(how: str, sink: bool):
    """Two peers, three replies each.  One reap brings each its first
    reply and all but 9 bytes of its second, so bytes wait in the
    slots behind a routed tick; then ``how`` happens (to peer 0, or to
    the ingest); then the rest of the second reply and the third.
    Returns what every peer observed, and what the rig fed before and
    after."""
    ingest = _ingest()
    peers = _peers(ingest, 2)
    rig = ReapRig(sink)
    victim = peers[0]
    heard: list = []
    try:
        xids = [[p.get() for _ in range(3)] for p in peers]
        wires = []
        for p, xs in zip(peers, xids):
            for x in xs[:2]:
                p.reply(x)
            wires.append(p.take())
            p.reply(xs[2])
        rig.reap([(p.conn, w[:-9]) for p, w in zip(peers, wires)])
        await settle()
        assert [_settled(p) for p in peers] == [xs[:1] for xs in xids]
        held = [_waiting(p, ingest) for p in peers]
        assert all(held) and rig.fed == (2 if sink else 0)
        token = rig.token(victim.conn)
        assert (token in rig.tier._sinks) == sink

        if how == 'conn_injector':
            victim.conn.faults = _PassInjector()
        elif how == 'ingest_injector':
            ingest.faults = _PassInjector()
        elif how == 'second_listener':
            victim.conn.on('sockData', lambda d: heard.append(len(d)))
        elif how == 'flip_direct':
            ingest._flip_direct(list(ingest._slots.values()))
            assert ingest.direct
        elif how == 'state_exit':
            victim.conn.close()
            assert victim.conn.is_in_state('closing')
        assert token not in rig.tier._sinks
        if how in ('ingest_injector', 'flip_direct'):
            assert not rig.tier._sinks
        elif sink:
            assert len(rig.tier._sinks) == 1    # the other keeps its own
        fed = rig.fed
        # nothing was lost on the way out of the slot
        assert [_waiting(p, ingest) for p in peers] == held

        rest = [w[-9:] + p.take() for p, w in zip(peers, wires)]
        rig.reap([(p.conn, r) for p, r in zip(peers, rest)])
        await settle()
        await settle()
        if how == 'second_listener':
            assert heard == [len(rest[0])]      # a delivery, as ever
        out = [(_settled(p), sorted(p.conn.reqs), _waiting(p, ingest))
               for p in peers]
        return out, xids, fed, rig.fed
    finally:
        await _stop(ingest, peers)


@pytest.mark.parametrize('how', ['conn_injector', 'ingest_injector',
                                 'second_listener', 'flip_direct',
                                 'state_exit'])
async def test_a_withdrawn_sink_loses_no_byte_and_keeps_the_order(how):
    """Each thing that withdraws a connection's sink — an injector
    installed on it or on its ingest mid-stream, a second ``sockData``
    listener, the flip to the pass-through regime, the state's exit —
    with half a reply in the slot: no byte is lost, the connection's
    replies settle in the order sent, and everything observed is what
    the same traffic gives with no sink at all; from the withdrawal on
    the connection's bytes come through ``sockData`` (nothing more is
    fed for it), while its neighbour keeps its own sink."""
    want, xids, _f, never = await _withdrawn(how, sink=False)
    got, _x, before, after = await _withdrawn(how, sink=True)
    assert never == 0 and before == 2
    assert got == want
    for (settled, reqs, pending), xs in zip(got, xids):
        assert settled == xs and not reqs and not pending
    # the neighbour's second delivery was fed unless the ingest itself
    # stopped taking sinks; the flip back to batch hands them out again
    assert after - before == (0 if how == 'ingest_injector' else
                              0 if how == 'flip_direct' else 1)
