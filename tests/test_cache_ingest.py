"""The client cache plane through the fleet ingest (ISSUE 26).

``tests/test_cache.py`` holds the plane to its contract on the
per-connection path.  A fleet's sessions drain through one shared
``FleetIngest`` instead: a tick hands every stream's frames of that
tick to its connection, stream after stream, and the coroutines that
await the replies resume only after the whole tick.  These tests hold
the same contract there — ``Client(cache=, ingest=)`` together:

- a change invalidates every cached subscriber exactly once;
- on one stream a reply never overtakes an earlier notification,
  inside one tick or across a tick boundary;
- the serve gate and the fill gate hold (no read older than what its
  session has seen; a reply from before an invalidation does not
  resurrect the dropped entry);

and the two things the benchmark reads about this path: the host span
``client.notify`` (count and total, profiler sessions only) and the
members' cumulative ``zk_persistent_notifications``.
"""

from __future__ import annotations

import asyncio

import pytest

from helpers import mntr_rows, wait_until
from zkstream_tpu import Client
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.server import ZKEnsemble
from zkstream_tpu.utils import trace

ROOT = '/conf'
KEYS = [ROOT + '/g%d/k%02d' % (g, k) for g in range(2) for k in range(3)]
N = 9           # cached sessions, three a member


@pytest.fixture(autouse=True)
def clean_host_ring():
    trace.host_ring.reset()
    trace._recording = False
    yield
    trace.host_ring.reset()
    trace._recording = False


def _ingest() -> FleetIngest:
    return FleetIngest(placement='host', max_frames=8,
                       min_len=256, max_data=256, bypass_bytes=0,
                       warm='block')


class Fleet:
    """An in-process 3-voter ensemble, the tree under ``/conf``, a
    plain writer and N cached sessions on ONE ingest, round-robin over
    the members, every subtree armed and loaded (``prime``)."""

    async def start(self, n: int = N):
        self.ens = await ZKEnsemble(3).start()
        self.ports = [s.port for s in self.ens.servers]
        self.ingest = _ingest()
        for bp in (8, 16):
            await self.ingest.prewarm(bp)
        self.writer = Client(address='127.0.0.1', port=self.ports[0],
                             session_timeout=30000)
        self.writer.start()
        await self.writer.wait_connected(timeout=5)
        await self.writer.create(ROOT, b'')
        for g in sorted({k.rsplit('/', 1)[0] for k in KEYS}):
            await self.writer.create(g, b'')
        for k in KEYS:
            await self.writer.create(k, b'v0')
        self.clients = [
            Client(servers=[('127.0.0.1', self.ports[i % 3])],
                   shuffle_backends=False, ingest=self.ingest,
                   session_timeout=30000, cache=[ROOT], max_spares=0)
            for i in range(n)]
        for c in self.clients:
            c.start()
        await asyncio.gather(*[c.wait_connected(timeout=10)
                               for c in self.clients])
        await wait_until(lambda: all(
            c.cache.stats()['armed'] == 1 for c in self.clients))
        await asyncio.gather(*[c.sync(ROOT) for c in self.clients])
        seen = await asyncio.gather(*[c.cache.prime(ROOT)
                                      for c in self.clients])
        assert set(seen) == {1 + 2 + len(KEYS)}
        return self

    async def stop(self):
        for c in self.clients + [self.writer]:
            await c.close()
        self.ingest.close()
        await self.ens.stop()


@pytest.fixture
def fleet(event_loop):
    f = event_loop.run_until_complete(Fleet().start())
    yield f
    event_loop.run_until_complete(f.stop())


async def test_every_cached_session_is_invalidated_exactly_once(fleet):
    """One ``setData``: every session's persistent recursive watcher
    fires once, its plane drops what it held of the znode (after
    ``prime``: the data entry and the leaf's empty children list; after
    a refresh: the data entry alone), and the refreshed read — a miss,
    through the ingest — shows the change."""
    key = KEYS[4]
    fired = [0] * N
    for i, c in enumerate(fleet.clients):
        w = await c.add_watch(ROOT, recursive=True)
        w.on('dataChanged',
             lambda p, z, i=i: fired.__setitem__(i, fired[i] + 1))
    ticks0 = fleet.ingest.ticks
    for version, dropped in ((1, 2), (2, 1)):
        before = [c.cache.stats() for c in fleet.clients]
        for c in fleet.clients:                 # a hit each
            assert (await c.get(key))[1].version == version - 1
        assert [c.cache.hits for c in fleet.clients] == [
            b['hits'] + 1 for b in before]
        await fleet.writer.set(key, b'v%d' % version, version=-1)
        await wait_until(lambda: min(fired) == version)
        assert fired == [version] * N
        assert [c.cache.invalidations for c in fleet.clients] == [
            b['invalidations'] + dropped for b in before]
        got = await asyncio.gather(*[c.get(key) for c in fleet.clients])
        assert {(d, s.version) for d, s in got} == {
            (b'v%d' % version, version)}
        assert [c.cache.misses for c in fleet.clients] == [
            b['misses'] + 1 for b in before]
    # nothing else fired, and the frames came through the tick program
    await asyncio.sleep(0.05)
    assert fired == [2] * N
    assert fleet.ingest.ticks > ticks0
    assert fleet.ingest.ticks_scalar == fleet.ingest.ticks_warming == 0


async def test_a_reply_never_overtakes_a_notification_of_its_stream(fleet):
    """Publishers write while every session keeps reads in flight, so
    ticks carry replies and xid -1 frames of one stream together and
    split them over tick boundaries (8 frames a stream a tick).  Per
    stream, in delivery order: no reply stamped at or above a
    notification's zxid comes before it; and a read that completes
    after a notification of its key shows that change or a later one
    (the serve gate), whether it was a hit or a miss."""
    logs = [[] for _ in range(N)]       # (tick, zxid, is_notification)
    told = [dict() for _ in range(N)]   # key -> newest version told of
    versions: dict = {}                 # zxid -> (key, version)
    stale: list = []
    stop = [False]
    for i, c in enumerate(fleet.clients):
        c.current_connection().on(
            'packet', lambda pkt, i=i: logs[i].append(
                (fleet.ingest.ticks, pkt['zxid'],
                 pkt['opcode'] == 'NOTIFICATION'))
            if pkt['opcode'] in ('NOTIFICATION', 'GET_DATA') else None)
        w = await c.add_watch(ROOT, recursive=True)
        w.on('dataChanged', lambda p, z, i=i: told[i].__setitem__(
            p, max(told[i].get(p, 0), z)))

    async def publisher(p):
        mine = KEYS[p::3]
        v = 0
        while not stop[0]:
            v += 1
            for k in mine:
                st = await fleet.clients[p].set(k, b'v%d' % v, version=-1)
                versions[st.mzxid] = (k, st.version)

    async def reader(i, lane):
        c = fleet.clients[i]
        n = 0
        while not stop[0]:
            k = KEYS[(i + lane + n) % len(KEYS)]
            n += 1
            seen = told[i].get(k, 0)
            data, st = await c.get(k)
            assert data == b'v%d' % st.version
            if st.mzxid < seen:
                stale.append((i, k, st.mzxid, seen))
            if n % 7 == 0:
                await asyncio.sleep(0)

    def mixed_ticks():
        """Stream-ticks that delivered both kinds of frame."""
        out = 0
        for log in logs:
            kinds: dict = {}
            for tick, _z, notif in log:
                kinds.setdefault(tick, set()).add(notif)
            out += sum(len(k) == 2 for k in kinds.values())
        return out

    tasks = [asyncio.ensure_future(publisher(p)) for p in range(3)]
    tasks += [asyncio.ensure_future(reader(i, lane))
              for i in range(N) for lane in range(3)]
    try:
        await wait_until(lambda: mixed_ticks() >= 20 and all(
            sum(n for _t, _z, n in log) >= 30 for log in logs),
            timeout=20)
    finally:
        stop[0] = True
        done = await asyncio.gather(*tasks, return_exceptions=True)
    assert [d for d in done if d is not None] == []
    assert stale == []
    split = 0
    for log in logs:
        newest_reply, reply_tick = 0, None
        for tick, zxid, notif in log:
            if notif:
                assert zxid > newest_reply, (
                    'a reply at zxid %#x was delivered before the '
                    'notification at %#x' % (newest_reply, zxid))
                split += reply_tick is not None and reply_tick != tick
            elif zxid > newest_reply:
                newest_reply, reply_tick = zxid, tick
    assert split > 0        # the order held across tick boundaries too
    assert fleet.ingest.ticks_scalar == 0
    # every session was told of every acknowledged change, once
    for i in range(N):
        zs = [z for _t, z, n in logs[i] if n]
        assert sorted(zs) == sorted(versions), i


async def test_the_fill_gate_holds_with_ingest_delivery(fleet):
    """A read whose reply was decoded in the same tick as a later
    invalidation of its znode resumes after the whole tick: its value
    must not be deposited (it would be served until the next change).
    Forced here: the reply's future is held back until the
    notification has been applied."""
    c, key = fleet.clients[1], KEYS[0]
    await fleet.writer.set(key, b'v1', version=-1)
    await wait_until(lambda: c.cache.lookup('GET_DATA', key) is None)
    primary = c._primary_request
    gate = asyncio.Event()

    async def slow_primary(pkt, opcode, *rest):
        out = await primary(pkt, opcode, *rest)
        if opcode == 'GET_DATA':
            await gate.wait()
        return out
    c._primary_request = slow_primary
    read = asyncio.ensure_future(c.get(key))
    await asyncio.sleep(0.1)                    # the reply is in hand
    inv0 = c.cache.invalidations
    pos0 = c.cache._pos
    await fleet.writer.set(key, b'v2', version=-1)
    await wait_until(lambda: c.cache._pos > pos0)
    assert c.cache.invalidations == inv0        # nothing was cached
    gate.set()
    data, stat = await read
    assert (data, stat.version) == (b'v1', 1)   # what the server said
    c._primary_request = primary
    assert c.cache.lookup('GET_DATA', key) is None      # and not kept
    data, stat = await c.get(key)
    assert (data, stat.version) == (b'v2', 2)
    assert c.cache.lookup('GET_DATA', key)['data'] == b'v2'


async def test_refreshes_of_overlapping_changes_are_kept(fleet):
    """Three publishers change their keys side by side and every
    session re-reads a key when its event arrives (what a Curator
    cache does).  A tick hands a session replies and LATER keys'
    notifications together, and the reads resume after the tick: the
    fill gate is per path, so each refresh is kept — the plane ends
    holding every key at its newest version, and serves it."""
    done = [0]

    async def refresh(c, path):
        await c.get(path)
        done[0] += 1
    for c in fleet.clients:
        w = await c.add_watch(ROOT, recursive=True)
        w.on('dataChanged', lambda p, z, c=c:
             asyncio.ensure_future(refresh(c, p)))

    async def publisher(p):
        for v in (1, 2, 3):
            for k in KEYS[p::3]:
                await fleet.clients[p].set(k, b'v%d' % v, version=-1)
    await asyncio.gather(*[publisher(p) for p in range(3)])
    await wait_until(lambda: done[0] == 3 * len(KEYS) * N)
    misses = [c.cache.misses for c in fleet.clients]
    for c in fleet.clients:
        for k in KEYS:
            held = c.cache.lookup('GET_DATA', k)
            assert held is not None and held['data'] == b'v3', k
            assert (await c.get(k))[1].version == 3
    assert [c.cache.misses for c in fleet.clients] == misses


async def test_client_notify_totals_only_inside_a_profiler_session(
        fleet, monkeypatch):
    key = KEYS[2]
    await fleet.writer.set(key, b'v1', version=-1)
    await wait_until(lambda: all(
        c.cache.lookup('GET_DATA', key) is None for c in fleet.clients))
    assert 'client.notify' not in trace.host_ring.totals
    monkeypatch.setattr(trace, '_is_enabled', lambda: True)
    inv0 = sum(c.cache.invalidations for c in fleet.clients)
    for v in (2, 3):
        await asyncio.gather(*[c.get(key) for c in fleet.clients])
        await fleet.writer.set(key, b'v%d' % v, version=-1)
        await wait_until(lambda: sum(
            c.cache.invalidations for c in fleet.clients) == inv0
            + N * (v - 1))
    count, total_ns = trace.host_ring.totals['client.notify']
    assert count == 2 * N and total_ns > 0
    assert not [s for s in trace.host_ring.spans()
                if s.op == 'client.notify']     # count and total only


async def test_mntr_counts_the_persistent_frames_sent(fleet):
    """``zk_persistent_notifications``: what ``_fan_persistent`` handed
    to the send plane, per member, cumulatively — three subscribers a
    member here, so three a change; a subscriber that has gone is not
    counted."""
    async def rows():
        return [int((await mntr_rows(p))['zk_persistent_notifications'])
                for p in fleet.ports]
    assert await rows() == [0, 0, 0]
    await fleet.writer.set(KEYS[0], b'v1', version=-1)
    await fleet.writer.set(KEYS[5], b'v1', version=-1)
    await wait_until(lambda: all(
        c.cache.lookup('GET_DATA', KEYS[5]) is None
        for c in fleet.clients))
    assert await rows() == [6, 6, 6]
    # the group znode is not a subscriber's business... but its
    # children's creation is: a recursive watch matches descendants
    await fleet.writer.create(ROOT + '/g0/new', b'')
    await fleet.clients[0].sync(ROOT)
    gone = fleet.clients.pop(1)         # on member 1
    await gone.close()
    await wait_until(lambda: fleet.ens.servers[1].recursive_watch_count()
                     == 2)
    await fleet.writer.set(KEYS[0], b'v2', version=-1)
    await wait_until(lambda: all(
        c.cache._pos >= fleet.writer.session.last_zxid
        for c in fleet.clients))
    got = await rows()
    assert got[1] == got[0] - 1 == got[2] - 1
    assert got[0] == 6 + 3 + 3
