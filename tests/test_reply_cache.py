"""The members' ``getData`` reply cache (server/server.py
``ReplyCache`` with ``data_parts``, ``ZKServer.data_cache``): a
GET_DATA reply's whole body — the data behind its length and the
68-byte Stat — is encoded once a path and handed to every asker behind
the 16-byte header of its own xid / zxid, while the node's Stat equals
the one the body was encoded with (ZooKeeper's ``readResponseCache``).
A member keeps the body of a record of ``REPLY_SHARE_BYTES`` or more;
a smaller one it encodes for its asker (cheaper than a miss).

Held here: a cached reply's bytes are the uncached encoder's at 0 B,
1.1 KB and 960 KiB; a member serves the first two through the encoder
and the last from the cache; a repeat is a hit;
whatever changes the reply is a miss (nothing invalidates an entry:
the Stat moved); a follower that trails serves its own store's body
and misses once the commit is applied; 400 paths, least recently used
out; a watch armed by a cached read fires; the ``mntr`` rows and the
tick phase ``data_encode``.
"""

from __future__ import annotations

import asyncio

import pytest

from helpers import mntr_rows, wait_until
from zkstream_tpu import Client, CreateFlag
from zkstream_tpu.protocol import fastencode
from zkstream_tpu.protocol.framing import PacketCodec
from zkstream_tpu.protocol.records import OPEN_ACL_UNSAFE
from zkstream_tpu.server import ZKEnsemble, ZKServer
from zkstream_tpu.server import server as server_mod
from zkstream_tpu.server.server import ReplyCache, data_parts
from zkstream_tpu.server.store import ZKDatabase
from zkstream_tpu.utils.metrics import TickLedger

SIZES = [0, 1121, 960 * 1024]
BIG = server_mod.REPLY_SHARE_BYTES      # the smallest record a member keeps


def big(tag: bytes) -> bytes:
    return tag + b'.' * (BIG - len(tag))


def DataCache() -> ReplyCache:
    return ReplyCache(data_parts, 'data_encode')


def _db(size: int = 8) -> ZKDatabase:
    db = ZKDatabase()
    db.create('/k', bytes(range(256)) * (size // 256) + b'x' * (size % 256),
              OPEN_ACL_UNSAFE, CreateFlag(0))
    return db


def _uncached(xid: int, zxid: int, db, path='/k') -> bytes:
    """The reply as the codec's encoders make it from the packet."""
    data, stat = db.get_data(path)
    codec = PacketCodec(server=True)
    codec.handshaking = False
    return codec.encode({'xid': xid, 'zxid': zxid, 'err': 'OK',
                         'opcode': 'GET_DATA', 'data': data, 'stat': stat})


@pytest.mark.parametrize('size', SIZES)
def test_cached_reply_is_byte_equal_to_the_uncached_encoder(size):
    db = _db(size)
    cache, led = DataCache(), TickLedger()
    for xid, zxid in [(1, 0), (7, 1 << 40), (2 ** 31 - 1, 2 ** 63 - 1),
                      (12345, 0x100000002)]:
        (body,) = cache.body('/k', db.nodes['/k'], led)
        want = _uncached(xid, zxid, db)
        assert fastencode.reply_frame(xid, zxid, body) == want
    # every asker was handed the SAME object: nothing was copied
    assert cache.body('/k', db.nodes['/k'], led)[0] is body
    assert (cache.hits, cache.misses) == (4, 1)
    assert cache.bytes == len(body) == 4 + size + 68
    # the Python tier and the spec encoder agree with it too
    data, stat = db.get_data('/k')
    pkt = {'xid': 9, 'zxid': 11, 'err': 'OK', 'opcode': 'GET_DATA',
           'data': data, 'stat': stat}
    assert fastencode.FastEncoder().encode_response(pkt) \
        == fastencode.reply_frame(9, 11, body)


async def test_a_repeat_hits_and_every_change_of_the_reply_misses(
        event_loop):
    db = _db()
    cache, led = DataCache(), TickLedger()

    def ask(path='/k'):
        before = cache.misses
        (body,) = cache.body(path, db.nodes[path], led)
        assert fastencode.reply_frame(3, db.zxid, body) \
            == _uncached(3, db.zxid, db, path)
        return cache.misses - before

    assert ask() == 1 and ask() == 0 and ask() == 0
    db.set_data('/k', b'second', -1)
    assert ask() == 1 and ask() == 0                # a setData
    db.set_data('/k', b'second', -1)
    assert ask() == 1 and ask() == 0                # the same bytes again
    db.create('/k/c', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    assert ask() == 1 and ask() == 0                # a child created
    db.set_data('/k/c', b'x', -1)                   # a CHILD's is no change
    assert ask() == 0
    db.delete('/k/c', -1)
    assert ask() == 1 and ask() == 0                # a child deleted
    db.multi([{'op': 'set_data', 'path': '/k', 'data': b'third',
               'version': -1}], None)
    assert ask() == 1 and ask() == 0                # a MULTI's setData
    stat = db.nodes['/k'].stat()
    db.delete('/k', -1)
    db.create('/k', b'third', OPEN_ACL_UNSAFE, CreateFlag(0))
    again = db.nodes['/k'].stat()
    assert again.czxid != stat.czxid and again.version == 0
    assert ask() == 1 and ask() == 0                # delete and create
    assert len(cache) == 1 and cache.bytes == 4 + 5 + 68


def test_the_401st_path_evicts_the_least_recently_used():
    db = ZKDatabase()
    paths = ['/p%03d' % (i,) for i in range(401)]
    for p in paths:
        db.create(p, b'0123456789', OPEN_ACL_UNSAFE, CreateFlag(0))
    cache, led = DataCache(), TickLedger()
    assert cache.CAPACITY == 400
    for p in paths[:400]:
        cache.body(p, db.nodes[p], led)
    cache.body(paths[0], db.nodes[paths[0]], led)     # 0 is recent again
    assert (cache.hits, cache.misses, len(cache)) == (1, 400, 400)
    assert cache.bytes == 400 * 82
    cache.body(paths[400], db.nodes[paths[400]], led)
    assert len(cache) == 400 and cache.bytes == 400 * 82
    cache.body(paths[0], db.nodes[paths[0]], led)     # still held
    assert cache.hits == 2
    cache.body(paths[1], db.nodes[paths[1]], led)     # 1 went
    assert cache.misses == 402
    # an entry whose Stat moved is replaced where it stands in the
    # count, and is the newest again
    db.set_data(paths[2], b'longer than it was', -1)
    cache.body(paths[2], db.nodes[paths[2]], led)
    assert len(cache) == 400 and cache.misses == 403
    assert cache.bytes == 399 * 82 + 4 + 18 + 68


@pytest.mark.parametrize('size,transport', [
    (SIZES[0], None), (SIZES[1], None), (SIZES[2], None),
    (SIZES[2], 'mmsg'), (SIZES[2], 'asyncio')])
async def test_server_replies_rows_and_phase(event_loop, size, transport):
    # the large reply is served from the cache, on every tier (the
    # chip machine's members resolve to mmsg); the small ones go
    # through the encoder
    kept = size >= BIG
    assert SIZES[1] < BIG <= SIZES[2]
    srv = ZKServer(transport=transport)
    await srv.start()
    c = Client(address='127.0.0.1', port=srv.port, session_timeout=30000)
    c.start()
    try:
        await c.wait_connected(timeout=5)
        want = bytes(range(256)) * (size // 256) + b'x' * (size % 256)
        await c.create('/k', want)
        for _ in range(3):
            data, stat = await c.get('/k')
            assert data == want and stat.dataLength == size
        dc = srv.data_cache
        assert (dc.hits, dc.misses) == ((2, 1) if kept else (0, 0))
        # pipelined askers of one turn share the body and keep order
        # (3 of the large one: the overload plane's hard watermark,
        # 4 MiB of unsent bytes a connection, stands as it did)
        burst = 8 if size < 1 << 16 else 3
        got = await asyncio.gather(*[c.get('/k') for _ in range(burst)])
        assert all(d == want and s == stat for d, s in got)
        assert (dc.hits, dc.misses) == ((2 + burst, 1) if kept else (0, 0))
        new = want[::-1] if kept else b'new'
        await c.set('/k', new)
        data, stat = await c.get('/k')
        assert data == new and stat.version == 1
        with pytest.raises(Exception) as e:
            await c.get('/nope')
        assert e.value.code == 'NO_NODE'
        rows = await mntr_rows(srv.port)
        assert rows['zk_data_cache_hits'] == str((2 + burst) * kept)
        assert rows['zk_data_cache_misses'] == str(2 * kept)
        assert int(rows['zk_data_cache_bytes']) == dc.bytes \
            == (4 + size + 68) * kept
        assert rows['zk_children_cache_hits'] == '0'
        # (a phase that never ran has no row)
        assert rows.get('zk_tick_phase_ms_count{phase="data_encode"}',
                        '0') == str(2 * kept)
        assert TickLedger.PHASES.index('list_encode') \
            < TickLedger.PHASES.index('data_encode') \
            < TickLedger.PHASES.index('control')
    finally:
        await c.close()
        await srv.stop()


async def test_a_watch_armed_by_a_cached_read_still_fires(event_loop):
    srv = ZKServer()
    await srv.start()
    a = Client(address='127.0.0.1', port=srv.port, session_timeout=30000)
    b = Client(address='127.0.0.1', port=srv.port, session_timeout=30000)
    for c in (a, b):
        c.start()
    try:
        for c in (a, b):
            await c.wait_connected(timeout=5)
        one, two, three = big(b'one'), big(b'two'), big(b'three')
        await a.create('/k', one)
        assert (await a.get('/k'))[0] == one        # the body is held
        seen: list = []
        # the watcher's own read arms the watch and is served from
        # the cache (a hit): the watch must stand all the same
        b.watcher('/k').on('dataChanged',
                           lambda data, stat: seen.append(
                               (data, stat.version)))
        await wait_until(lambda: seen == [(one, 0)], timeout=5)
        assert srv.data_cache.hits >= 1 and srv.data_cache.misses == 1
        await a.set('/k', two)
        await wait_until(lambda: seen[-1] == (two, 1), timeout=5)
        await a.set('/k', three)
        await wait_until(lambda: seen[-1] == (three, 2), timeout=5)
    finally:
        await a.close()
        await b.close()
        await srv.stop()


async def test_a_follower_never_serves_a_body_older_than_its_store(
        event_loop):
    ens = await ZKEnsemble(3).start()
    ports = [s.port for s in ens.servers]
    leader = Client(address='127.0.0.1', port=ports[0],
                    session_timeout=30000)
    follower = Client(address='127.0.0.1', port=ports[1],
                      session_timeout=30000)
    for c in (leader, follower):
        c.start()
    try:
        for c in (leader, follower):
            await c.wait_connected(timeout=5)
        v0, v1, v2 = big(b'v0'), big(b'v1'), big(b'v2')
        await leader.create('/k', v0)
        await follower.sync('/k')
        assert (await follower.get('/k'))[0] == v0
        assert (await follower.get('/k'))[0] == v0
        dc = ens.servers[1].data_cache
        assert (dc.hits, dc.misses) == (1, 1)
        # the follower trails: its own view, and its cached reply, stand
        ens.set_lag(1, None)
        await leader.set('/k', v1)
        data, stat = await follower.get('/k')
        assert (data, stat.version) == (v0, 0)
        assert ens.servers[1].store.nodes['/k'].data == v0
        assert (dc.hits, dc.misses) == (2, 1)
        # once the commit is applied the held body no longer matches
        ens.set_lag(1, 0)
        await follower.sync('/k')
        data, stat = await follower.get('/k')
        assert (data, stat.version) == (v1, 1)
        assert (dc.hits, dc.misses) == (2, 2)
        # a write THROUGH the follower is read back from it at once
        st = await follower.set('/k', v2)
        data, stat = await follower.get('/k')
        assert (data, stat.version) == (v2, 2) and stat == st
        assert (dc.hits, dc.misses) == (2, 3)
        # a record under REPLY_SHARE_BYTES goes through the encoder
        await follower.set('/k', v2[:BIG - 1])
        data, stat = await follower.get('/k')
        assert (data, stat.version) == (v2[:BIG - 1], 3)
        assert (dc.hits, dc.misses) == (2, 3)
        # every member has a cache of its own
        assert ens.servers[0].data_cache is not dc
    finally:
        await leader.close()
        await follower.close()
        await ens.stop()
