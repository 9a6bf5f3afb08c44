"""The members' children-reply cache (server/server.py ``ReplyCache``
with ``children_parts``; its ``getData`` half is
tests/test_reply_cache.py): the serialized body of a GET_CHILDREN /
GET_CHILDREN2 reply — the count, the names, and for GET_CHILDREN2 the
Stat — is encoded once a path and handed to every asker behind the
16-byte header of its own xid / zxid, while the node's Stat equals the
one the body was encoded with.

Held here: a cached reply's bytes are the uncached encoder's; a repeat
is a hit; every kind of change of the list — and a ``setData`` on the
parent — is a miss (nothing invalidates an entry: the Stat moved); a
follower's cache misses once the commit is applied; 400 paths, least
recently used out; the ``mntr`` rows and the tick phase
``list_encode``.
"""

from __future__ import annotations

import pytest

from helpers import mntr_rows
from zkstream_tpu import Client, CreateFlag
from zkstream_tpu.protocol import fastencode
from zkstream_tpu.protocol.framing import PacketCodec
from zkstream_tpu.server import ZKEnsemble, ZKServer
from zkstream_tpu.server.server import ReplyCache, children_parts
from zkstream_tpu.server.store import ZKDatabase
from zkstream_tpu.protocol.records import OPEN_ACL_UNSAFE
from zkstream_tpu.utils.metrics import TickLedger


def ChildrenReplyCache() -> ReplyCache:
    return ReplyCache(children_parts, 'list_encode')


def _tree(names) -> ZKDatabase:
    db = ZKDatabase()
    db.create('/d', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    for n in names:
        db.create('/d/' + n, b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    return db


def _uncached(opcode: str, xid: int, zxid: int, db, path='/d') -> bytes:
    """The reply as the codec's encoders make it from the packet."""
    node = db.nodes[path]
    children, stat = sorted(node.children), node.stat()
    codec = PacketCodec(server=True)
    codec.handshaking = False
    pkt = {'xid': xid, 'zxid': zxid, 'err': 'OK', 'opcode': opcode,
           'children': children}
    if opcode == 'GET_CHILDREN2':
        pkt['stat'] = stat
    return codec.encode(pkt)


@pytest.mark.parametrize('count', [0, 1, 1024])
@pytest.mark.parametrize('opcode', ['GET_CHILDREN', 'GET_CHILDREN2'])
def test_cached_reply_is_byte_equal_to_the_uncached_encoder(opcode, count):
    names = ['10.%d.%d.7:8983_solr' % (i // 256, i % 256)
             for i in range(count)]
    db = _tree(names)
    cache, led = ChildrenReplyCache(), TickLedger()
    for xid, zxid in [(1, 0), (7, 1 << 40), (2 ** 31 - 1, 2 ** 63 - 1),
                      (12345, 0x100000002)]:
        body, stat = cache.body('/d', db.nodes['/d'], led)
        if opcode == 'GET_CHILDREN2':
            body += stat
        assert fastencode.reply_frame(xid, zxid, body) \
            == _uncached(opcode, xid, zxid, db)
    # the Python tier and the spec encoder agree with it too
    pkt = {'xid': 9, 'zxid': 11, 'err': 'OK', 'opcode': 'GET_CHILDREN2',
           'children': sorted(names), 'stat': db.nodes['/d'].stat()}
    body, stat = cache.body('/d', db.nodes['/d'], led)
    assert fastencode.FastEncoder().encode_response(pkt) \
        == fastencode.reply_frame(9, 11, body + stat)
    assert (cache.hits, cache.misses) == (4, 1)
    assert cache.bytes == len(body) + 68


async def test_a_repeat_hits_and_every_change_of_the_list_misses(
        event_loop):
    db = _tree(['a', 'b'])      # (a session's expiry timer wants a loop)
    sess = db.create_session(30000)
    cache, led = ChildrenReplyCache(), TickLedger()
    node = db.nodes['/d']

    def ask():
        before = cache.misses
        body, stat = cache.body('/d', node, led)
        assert fastencode.reply_frame(3, db.zxid, body + stat) \
            == _uncached('GET_CHILDREN2', 3, db.zxid, db)
        return cache.misses - before

    assert ask() == 1 and ask() == 0 and ask() == 0
    db.create('/d/c', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    assert ask() == 1 and ask() == 0                # a child created
    db.delete('/d/a', -1)
    assert ask() == 1 and ask() == 0                # a child deleted
    db.multi([{'op': 'create', 'path': '/d/m', 'data': b'',
               'acl': OPEN_ACL_UNSAFE, 'flags': 0}], None)
    assert ask() == 1 and ask() == 0                # a MULTI's create
    db.multi([{'op': 'delete', 'path': '/d/m', 'version': -1}], None)
    assert ask() == 1 and ask() == 0                # a MULTI's delete
    db.create('/d/e', b'', OPEN_ACL_UNSAFE, CreateFlag.EPHEMERAL, sess)
    assert ask() == 1 and ask() == 0
    db.close_session(sess.id)                       # the owner's close
    assert '/d/e' not in db.nodes
    assert ask() == 1 and ask() == 0
    db.set_data('/d', b'x', -1)                     # the parent's setData
    assert ask() == 1 and ask() == 0
    db.set_data('/d/b', b'x', -1)                   # a CHILD's is no change
    assert ask() == 0
    assert len(cache) == 1


def test_the_401st_path_evicts_the_least_recently_used():
    db = ZKDatabase()
    paths = ['/p%03d' % (i,) for i in range(401)]
    for p in paths:
        db.create(p, b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    cache, led = ChildrenReplyCache(), TickLedger()
    assert cache.CAPACITY == 400
    for p in paths[:400]:
        cache.body(p, db.nodes[p], led)
    cache.body(paths[0], db.nodes[paths[0]], led)     # 0 is recent again
    assert (cache.hits, cache.misses, len(cache)) == (1, 400, 400)
    per_entry = cache.bytes // 400
    cache.body(paths[400], db.nodes[paths[400]], led)
    assert len(cache) == 400 and cache.bytes == 400 * per_entry
    cache.body(paths[0], db.nodes[paths[0]], led)     # still held
    assert cache.hits == 2
    cache.body(paths[1], db.nodes[paths[1]], led)     # 1 went
    assert cache.misses == 402


async def test_server_replies_rows_and_phase(event_loop):
    srv = ZKServer()
    await srv.start()
    c = Client(address='127.0.0.1', port=srv.port, session_timeout=30000)
    c.start()
    try:
        await c.wait_connected(timeout=5)
        await c.create('/d', b'')
        for n in ('b', 'a', 'c'):
            await c.create('/d/' + n, b'')
        for _ in range(3):
            names, stat = await c.list('/d')
            assert names == ['a', 'b', 'c'] and stat.numChildren == 3
        cc = srv.children_cache
        assert (cc.hits, cc.misses) == (2, 1)
        await c.delete('/d/b', -1)
        names, stat = await c.list('/d')
        assert names == ['a', 'c'] and stat.cversion == 4
        with pytest.raises(Exception) as e:
            await c.list('/nope')
        assert e.value.code == 'NO_NODE'
        rows = await mntr_rows(srv.port)
        assert rows['zk_children_cache_hits'] == '2'
        assert rows['zk_children_cache_misses'] == '2'
        assert int(rows['zk_children_cache_bytes']) == cc.bytes > 68
        assert rows['zk_tick_phase_ms_count{phase="list_encode"}'] == '2'
        assert TickLedger.PHASES.index('list_encode') \
            < TickLedger.PHASES.index('control')
    finally:
        await c.close()
        await srv.stop()


async def test_a_followers_cache_misses_once_the_commit_is_applied(
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
        await leader.create('/d', b'')
        await leader.create('/d/a', b'')
        await follower.sync('/d')
        assert (await follower.list('/d'))[0] == ['a']
        assert (await follower.list('/d'))[0] == ['a']
        cc = ens.servers[1].children_cache
        assert (cc.hits, cc.misses) == (1, 1)
        # the follower trails: its own view, and its cached reply, stand
        ens.set_lag(1, None)
        await leader.create('/d/b', b'')
        assert (await follower.list('/d'))[0] == ['a']
        assert (cc.hits, cc.misses) == (2, 1)
        ens.set_lag(1, 0)
        await follower.sync('/d')
        names, stat = await follower.list('/d')
        assert names == ['a', 'b'] and stat.cversion == 2
        assert (cc.hits, cc.misses) == (2, 2)
        # every member has a cache of its own
        assert ens.servers[0].children_cache is not cc
    finally:
        await leader.close()
        await follower.close()
        await ens.stop()
