"""The collector's young generation follows a fleet's registered slots
— and, where its clients pipeline, the requests it has alive
(utils/alloc.py ``fit_collector``, called by ``FleetIngest``): the rule,
who it leaves alone, what the last ``close()`` puts back — and the
fact it rests on: a read through a fleet ingest leaves no cyclic
garbage, so a higher threshold defers nothing that reference counting
does not free at once.
"""

import asyncio
import gc

import pytest

from zkstream_tpu import Client
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.server import ZKServer
from zkstream_tpu.utils import alloc

DEFAULT = (700, 10, 10)
K = alloc.YOUNG_PER_SLOT


@pytest.fixture(autouse=True)
def interpreter_defaults():
    """Every test starts as a fresh process does: the interpreter's
    thresholds, no fleet known to the policy (ingests other tests left
    unclosed in this process are forgotten)."""
    was = gc.get_threshold()
    alloc._fleets.clear()
    alloc._set = alloc._found = None
    gc.set_threshold(*DEFAULT)
    yield
    alloc._fleets.clear()
    alloc._set = alloc._found = None
    gc.set_threshold(*was)


class _Conn:
    """All ``register`` asks of a connection in the batch regime."""
    codec = None


def _ingest() -> FleetIngest:
    return FleetIngest(bypass_bytes=0, warm='block', placement='host')


def _grow(ingest, conns: list, n: int) -> None:
    while len(conns) < n:
        conns.append(_Conn())
        ingest.register(conns[-1])


def _shrink(ingest, conns: list, n: int) -> None:
    while len(conns) > n:
        ingest.unregister(conns.pop())


@pytest.mark.parametrize('slots,young', [
    (0, 700), (8, 700), (21, 700),      # under ~22 sessions: the default
    (32, K * 32), (64, K * 64), (1024, K * 1024),
    (1000, K * 512)])                   # derived when 512 doubled 256
def test_the_young_threshold_follows_the_slots(slots, young):
    """``max(interpreter default, YOUNG_PER_SLOT x slots)``, the slots
    as they stood when they last doubled."""
    ing = _ingest()
    conns: list = []
    _grow(ing, conns, slots)
    assert gc.get_threshold() == (young, 10, 10)
    ing.close()
    assert gc.get_threshold() == DEFAULT


def test_the_threshold_is_derived_again_at_double_and_half():
    """Between a doubling and a halving of the slot count nothing is
    set; the older generations' thresholds are never touched."""
    ing = _ingest()
    conns: list = []
    _grow(ing, conns, 1024)
    assert gc.get_threshold() == (K * 1024, 10, 10)
    _grow(ing, conns, 2047)
    assert gc.get_threshold() == (K * 1024, 10, 10)
    _grow(ing, conns, 2048)
    assert gc.get_threshold() == (K * 2048, 10, 10)
    _shrink(ing, conns, 1025)
    assert gc.get_threshold() == (K * 2048, 10, 10)
    _shrink(ing, conns, 1024)
    assert gc.get_threshold() == (K * 1024, 10, 10)
    _shrink(ing, conns, 0)
    assert gc.get_threshold() == DEFAULT
    ing.close()
    assert gc.get_threshold() == DEFAULT


@pytest.mark.parametrize('when', ['before', 'after'])
def test_an_owners_thresholds_are_left_alone(when):
    """A process owner who set their own — before the ingest came, or
    after it raised the threshold — keeps them: nothing is set over
    theirs, and ``close()`` puts nothing back over them."""
    own = (5000, 20, 20)
    if when == 'before':
        gc.set_threshold(*own)
    ing = _ingest()
    conns: list = []
    _grow(ing, conns, 256)
    if when == 'after':
        assert gc.get_threshold() == (K * 256, 10, 10)
        gc.set_threshold(*own)
    _grow(ing, conns, 1024)
    assert gc.get_threshold() == own
    _shrink(ing, conns, 0)
    ing.close()
    assert gc.get_threshold() == own


def test_the_last_ingest_to_close_puts_the_thresholds_back():
    """The process's fleets add up; a closed ingest leaves the sum; the
    last one restores what was found; a dropped one counts no more."""
    a, b = _ingest(), _ingest()
    ca: list = []
    cb: list = []
    _grow(a, ca, 512)
    _grow(b, cb, 256)
    assert gc.get_threshold() == (K * 768, 10, 10)
    a.close()
    assert gc.get_threshold() == (K * 256, 10, 10)
    a.register(_Conn())                 # closed: follows nothing now
    assert gc.get_threshold() == (K * 256, 10, 10)
    c = _ingest()
    assert gc.get_threshold() == (K * 256, 10, 10)
    b.close()
    assert gc.get_threshold() == DEFAULT        # c holds no slot
    cc: list = []
    _grow(c, cc, 64)
    assert gc.get_threshold() == (K * 64, 10, 10)
    del c, cc
    gc.collect()
    d = _ingest()                       # c was dropped, never closed
    d.close()
    assert gc.get_threshold() == DEFAULT


@pytest.mark.parametrize('depth,answered,alive,later', [
    (1, 1, 64, 64),     # one request a session: the slots, as PR 43 set
    (2, 2, 128, 128),   # 128 frames from 64 slots: 128 alive
    (8, 8, 512, 512),   # the whole window of 8 in one tick
    (8, 2, 512, 512),   # 128 routed, 384 still pending: 512 alive
    (8, 1, 64, 448),    # 64 frames of 64 slots: nobody looks, until
    (3, 1, 64, 128)])   # the rest comes in one tick
async def test_the_young_threshold_follows_the_requests_alive(
        depth, answered, alive, later):
    """Where the clients pipeline the collector is sized to the
    requests the fleet has alive — what a tick routed and what is still
    pending on its slots' connections — found when a tick routes more
    frames than any look has seen, set once that is twice what stands;
    at one request outstanding a tick never routes more frames than
    there are slots, and the threshold is the slots' byte for byte."""
    import random

    from test_ingest_route import Peer, settle

    ing = FleetIngest(bypass_bytes=0, warm='block', placement='host',
                      max_frames=8, min_len=256)
    peers = [Peer(i, ing, True, random.Random(i)) for i in range(64)]
    try:
        assert gc.get_threshold() == (K * 64, 10, 10)
        for p in peers:
            xids = [p.get() for _ in range(depth)]
            for xid in xids[:answered]:
                p.reply(xid)
            p.flush()
        await settle()
        assert ing.frames_routed == 64 * answered
        assert gc.get_threshold() == (K * alive, 10, 10)
        # the rest of the window comes: a look only where that tick
        # routes more frames than the last look found alive
        for p in peers:
            for xid in sorted(p.conn.reqs):
                p.reply(xid)
            p.flush()
        await settle()
        assert ing.frames_routed == 64 * depth
        assert gc.get_threshold() == (K * later, 10, 10)
        # the fleet halves: the slots again, whatever was alive before
        for p in peers[32:]:
            p.session.close()
            p.conn.destroy()
        await settle()
        assert gc.get_threshold() == (K * 32, 10, 10)
    finally:
        for p in peers:
            p.session.close()
            p.conn.destroy()
        await settle()
        ing.close()
    assert gc.get_threshold() == DEFAULT


@pytest.mark.timeout(120)
async def test_reads_through_the_ingest_leave_no_cyclic_garbage():
    """A few thousand reads from 16 sessions through one
    ``FleetIngest`` with the collector off and ``DEBUG_SAVEALL`` on:
    the collection afterwards finds nothing — a read's packets, Stats,
    requests, spans, futures and coroutine frames all die by reference
    count — so a young generation that collects later frees nothing
    later."""
    srv = await ZKServer().start()
    ingest = FleetIngest(placement='host', max_frames=8,
                         min_len=256, bypass_bytes=0, warm='block')
    for bp in (8, 16):
        await ingest.prewarm(bp)
    clients = [Client(address='127.0.0.1', port=srv.port, ingest=ingest,
                      session_timeout=30000, max_spares=0)
               for _ in range(16)]
    for c in clients:
        c.start()
    try:
        await asyncio.gather(*[c.wait_connected(timeout=10)
                               for c in clients])
        await clients[0].create('/k', b'x' * 100)

        async def reads(c, n):
            for _ in range(n):
                data, stat = await c.get('/k')
                assert data == b'x' * 100 and stat.version == 0

        await asyncio.gather(*[reads(c, 10) for c in clients])  # warm
        ticks = ingest.ticks
        gc.collect()
        gc.disable()
        gc.set_debug(gc.DEBUG_SAVEALL)
        try:
            await asyncio.gather(*[reads(c, 200) for c in clients])
            found = gc.collect()
            garbage = [type(o).__name__ for o in gc.garbage]
        finally:
            gc.set_debug(0)
            gc.garbage.clear()
            gc.enable()
        assert ingest.ticks - ticks >= 200 and ingest.ticks_scalar == 0
        assert (found, garbage) == (0, [])
    finally:
        for c in clients:
            await c.close()
        ingest.close()
        await srv.stop()
