"""Watcher-engine integration tests: the rebuild's equivalent of the
reference's watcher sequences in test/basic.test.js:644-981."""

import asyncio

import pytest

from helpers import wait_until
from zkstream_tpu import Client
from zkstream_tpu.io.connection import ZKConnection


@pytest.fixture
def two_clients(event_loop, server):
    async def setup():
        cs = []
        for _ in range(2):
            c = Client(address='127.0.0.1', port=server.port,
                       session_timeout=5000)
            c.start()
            await c.wait_connected(timeout=5)
            cs.append(c)
        return cs
    cs = event_loop.run_until_complete(setup())
    yield cs
    for c in cs:
        event_loop.run_until_complete(c.close())


async def test_data_watcher_cross_client(two_clients):
    c1, c2 = two_clients
    await c1.create('/foo', b'hi there')
    seen = []
    c1.watcher('/foo').on('dataChanged',
                          lambda data, stat: seen.append(bytes(data)))
    await wait_until(lambda: seen == [b'hi there'])
    await c2.set('/foo', b'hi')
    await wait_until(lambda: seen == [b'hi there', b'hi'])


async def test_delete_while_watching(two_clients):
    c1, c2 = two_clients
    await c1.create('/dw', b'x')
    deleted = []
    c1.watcher('/dw').on('deleted', lambda *a: deleted.append(True))
    stat = await c1.stat('/dw')
    await c1.delete('/dw', stat.version)
    await wait_until(lambda: deleted == [True])


async def test_delete_while_watching_data(two_clients):
    # dataChanged fires exactly once (the initial arm), then deleted
    # (reference: basic.test.js:728-771).
    c1, _ = two_clients
    await c1.create('/foobar', b'hi')
    dw_fired = []
    done = []
    w = c1.watcher('/foobar')
    w.on('dataChanged', lambda data, stat: dw_fired.append(1))
    w.on('deleted', lambda *a: done.append(len(dw_fired)))
    await wait_until(lambda: len(dw_fired) == 1)
    stat = await c1.stat('/foobar')
    await c1.delete('/foobar', stat.version)
    await wait_until(lambda: bool(done))
    assert done[0] == 1


async def test_children_watcher_sequence(two_clients):
    # Children changes arrive with monotonically increasing cversion
    # (reference: basic.test.js:764-810).
    c1, c2 = two_clients
    await c1.create('/kids', b'')
    snaps = []
    c1.watcher('/kids').on(
        'childrenChanged',
        lambda kids, stat: snaps.append((sorted(kids), stat.cversion)))
    await wait_until(lambda: len(snaps) == 1)
    await c2.create('/kids/a', b'')
    await wait_until(lambda: len(snaps) >= 2)
    await c2.create('/kids/b', b'')
    await wait_until(lambda: len(snaps) >= 3)
    await c2.delete('/kids/a', -1)
    await wait_until(lambda: any(s[0] == ['b'] for s in snaps))
    cversions = [s[1] for s in snaps]
    assert cversions == sorted(cversions)
    assert snaps[0][0] == []


async def test_children_watcher_no_node_parks(two_clients):
    # A children watch on a missing node parks in wait_node until the
    # node is created (reference: basic.test.js:812-870).
    c1, c2 = two_clients
    snaps = []
    w = c1.watcher('/parent')
    w.on('childrenChanged', lambda kids, stat: snaps.append(sorted(kids)))
    # Also watch existence so wait_node has a 'created' to chain from.
    w.on('created', lambda *a: None)
    await asyncio.sleep(0.1)
    assert snaps == []
    await c2.create('/parent', b'')
    await wait_until(lambda: snaps == [[]])
    await c2.create('/parent/kid', b'')
    await wait_until(lambda: ['kid'] in snaps)


async def test_existence_watcher_lifecycle(two_clients):
    c1, c2 = two_clients
    events = []
    w = c1.watcher('/ghost')
    w.on('created', lambda *a: events.append('created'))
    w.on('deleted', lambda *a: events.append('deleted'))
    # Arming on a missing node reports deleted
    # (reference: lib/zk-session.js:869-875).
    await wait_until(lambda: events == ['deleted'])
    await c2.create('/ghost', b'')
    await wait_until(lambda: events == ['deleted', 'created'])
    await c2.delete('/ghost', -1)
    await wait_until(lambda: events == ['deleted', 'created', 'deleted'])


async def test_watcher_cached_per_path(two_clients):
    c1, _ = two_clients
    assert c1.watcher('/x') is c1.watcher('/x')
    assert c1.watcher('/x') is not c1.watcher('/y')


async def test_watcher_once_forbidden(two_clients):
    c1, _ = two_clients
    with pytest.raises(NotImplementedError):
        c1.watcher('/x').once('dataChanged', lambda *a: None)


async def test_watcher_zxid_dedup_suppresses_duplicate_emits(two_clients):
    # A created notification also re-arms the dataChanged watch (server
    # watch-kind overlap); the zxid dedup keeps user emits unique
    # (reference: lib/zk-session.js:496-526, 849-856).
    c1, c2 = two_clients
    await c1.create('/dd', b'v')
    seen = []
    c1.watcher('/dd').on('dataChanged',
                         lambda data, stat: seen.append(bytes(data)))
    await wait_until(lambda: seen == [b'v'])
    # Reads that do not change mzxid must not re-emit.
    await c1.get('/dd')
    await asyncio.sleep(0.2)
    assert seen == [b'v']


async def test_stale_rearm_on_lagging_follower_does_not_reemit():
    """A churn-forced re-arm can land on a lagging follower whose tree
    is BEHIND what this watcher already delivered; the stale read's
    older mzxid must not re-emit (watch at-most-once per change —
    io/invariants.py check_watch_once).  Deterministic: the follower
    is parked (lag=None) before the change, the serving member is
    killed after the fire, and the session resumes on the stale
    follower."""
    from zkstream_tpu.io.backoff import BackoffPolicy
    from zkstream_tpu.server import ZKEnsemble

    ens = await ZKEnsemble(2, lag=0.0).start()
    c = Client(servers=ens.addresses(), shuffle_backends=False,
               session_timeout=8000, op_timeout=2000,
               connect_policy=BackoffPolicy(timeout=400, retries=3,
                                            delay=30, cap=200))
    c.start()
    try:
        await c.wait_connected(timeout=10)
        assert c.current_connection().backend.port == \
            ens.servers[0].port
        await c.create('/w', b'v0')
        fires = []
        c.watcher('/w').on(
            'dataChanged',
            lambda data, stat: fires.append((bytes(data),
                                             stat.mzxid)))
        await wait_until(lambda: len(fires) == 1)   # the arming emit
        ens.set_lag(1, None)           # park the follower HERE
        await c.set('/w', b'v1', version=-1)
        await wait_until(lambda: len(fires) == 2)   # the change fires
        created_zxid, changed_zxid = fires[0][1], fires[1][1]
        assert changed_zxid > created_zxid

        dying = c.current_connection()
        await ens.kill(0)
        await wait_until(
            lambda: not dying.is_in_state('connected'), timeout=10)
        # session resumes on the parked follower; its re-arm read
        # serves the PRE-change tree (mzxid == created_zxid) — the
        # stale state must not re-emit
        await c.wait_connected(timeout=10, fail_fast=False)
        await asyncio.sleep(0.5)       # window for a wrong emit
        assert fires[2:] == [], fires
        # un-park: the follower applies the change it lagged on; the
        # re-armed watch must not double-fire it either (the watcher
        # already delivered changed_zxid)
        ens.set_lag(1, 0.0)
        await asyncio.sleep(0.5)
        assert [z for _d, z in fires].count(changed_zxid) == 1, fires
        # a genuinely new change still fires exactly once
        await c.set('/w', b'v2', version=-1)
        await wait_until(lambda: any(d == b'v2' for d, _z in fires))
        assert len(fires) == 3, fires
    finally:
        await c.close()
        await ens.stop()


# -- the re-arm is one pass each way (ISSUE 51) --

def _transitions(client, old, new):
    return client.collector.get_collector(
        'zkstream_fsm_transitions').value(
            {'fsm': 'ZKWatchEvent', 'from': old, 'to': new})


class _Sent:
    """The requests the connections send while it stands, and the wire
    bytes of each (the xid fixed, so that two re-arms compare)."""

    def __init__(self):
        self.pkts = []
        self._codec = None
        request = self._request = ZKConnection.request

        def tap(conn, pkt, span=None):
            self.pkts.append(dict(pkt))
            self._codec = conn.codec
            return request(conn, pkt, span)
        ZKConnection.request = tap

    def wire(self, i=-1):
        return bytes(self._codec.encode(dict(self.pkts[i], xid=1)))

    def close(self):
        ZKConnection.request = self._request


async def _armed_event(client, path, data=b'v0'):
    await client.create(path, data)
    seen = []
    client.watcher(path).on(
        'dataChanged', lambda d, stat: seen.append(bytes(d)))
    we = client.watcher(path).watch_events['dataChanged']
    await wait_until(lambda: we.is_in_state('armed'))
    assert seen == [data]
    return we, seen


async def test_notification_to_an_armed_event_rearms_in_one_pass(
        two_clients):
    """Attached, connected, no retry owed: ``armed`` -> ``arming`` in
    ONE transition, and the read leaves inside the ``notify`` call."""
    c1, _ = two_clients
    we, seen = await _armed_event(c1, '/one')
    states = []
    we.on('stateChanged', states.append)
    sent = _Sent()
    try:
        we.emitter.notify('dataChanged')
        # inside the call: one request, the machine already waits on it
        assert [p['opcode'] for p in sent.pkts] == ['GET_DATA']
        assert sent.pkts[0]['path'] == '/one' and sent.pkts[0]['watch']
        assert states == ['arming']
        await wait_until(lambda: we.is_in_state('armed'))
    finally:
        sent.close()
    assert states == ['arming', 'armed']
    assert len(sent.pkts) == 1
    assert _transitions(c1, 'armed', 'arming') == 1
    assert _transitions(c1, 'armed', 'wait_session') == 0
    assert _transitions(c1, 'wait_connected', 'arming') == 1  # the arm
    assert seen == [b'v0']          # same zxid: nothing to emit


async def _hold_detached(c, we, server, monkeypatch):
    """The session detached and its event on the resume list: a
    (catch-up) notification there finds no session to re-arm on."""
    await server.stop()
    await wait_until(lambda: we.is_in_state('resuming'), interval=0)
    assert c.current_connection() is None
    assert not c.session.is_in_state('attached')

    async def recover():
        await server.restart()
    return 'wait_session', recover


async def _hold_unconnected(c, we, server, monkeypatch):
    """The session still attached to a connection that is no longer
    ``connected`` (it has not heard yet)."""
    conn = c.current_connection()
    monkeypatch.setattr(
        conn, 'is_in_state',
        lambda name: name != 'connected' and
        type(conn).is_in_state(conn, name))

    async def recover():
        monkeypatch.undo()
    # wait_connected bounces back one loop turn later
    return 'wait_connected', recover


async def _hold_retry(c, we, server, monkeypatch):
    """The last arming attempt failed: its backoff is owed."""
    we._arm_retry = True
    delays = []
    next_delay = we._arm_backoff.next_delay

    def noted():
        delays.append(next_delay() + 60)
        return delays[-1]
    monkeypatch.setattr(we._arm_backoff, 'next_delay', noted)

    async def recover():
        assert len(delays) == 1
        return delays[0]
    return 'wait_connected', recover


@pytest.mark.parametrize('hold', [_hold_detached, _hold_unconnected,
                                  _hold_retry])
async def test_rearm_takes_the_stepped_way_when_it_must(
        event_loop, server, monkeypatch, hold):
    """Session detached, connection not ``connected``, or a retry
    latched: ``wait_session`` -> ``wait_connected`` as ever — no
    request inside the call, the backoff honoured, re-armed after
    recovery, with the bytes the direct way sends."""
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000)
    c.start()
    await c.wait_connected(timeout=5)
    try:
        we, seen = await _armed_event(c, '/step')
        # the direct way's request, to compare with
        sent = _Sent()
        try:
            we.notify()
            direct = sent.wire()
        finally:
            sent.close()
        await wait_until(lambda: we.is_in_state('armed'))
        assert _transitions(c, 'armed', 'arming') == 1

        parked, recover = await hold(c, we, server, monkeypatch)
        origin = we.get_state()
        sent = _Sent()
        try:
            t0 = event_loop.time()
            we.notify()
            assert we.get_state() == parked
            assert sent.pkts == []
            assert _transitions(c, origin, 'wait_session') == 1
            assert _transitions(c, origin, 'arming') == \
                (origin == 'armed')
            owed = await recover()
            await wait_until(lambda: sent.pkts, interval=0)
            if owed is not None:
                assert (event_loop.time() - t0) * 1000.0 >= owed
            assert sent.wire() == direct
            await wait_until(lambda: we.is_in_state('armed'))
            assert not we._arm_retry
            assert len(sent.pkts) == 1
        finally:
            sent.close()
        # and it is a working watch
        await c.set('/step', b'v1')
        await wait_until(lambda: seen == [b'v0', b'v1'])
    finally:
        await c.close()


async def test_rearms_make_and_cancel_no_timer(event_loop, two_clients):
    """The double-check is ONE lazy timer an event: 100 re-arms move
    its deadline and hand the loop nothing."""
    c1, _ = two_clients
    we, _seen = await _armed_event(c1, '/lazy')
    timer = we._probe_handle
    assert timer is not None and not timer.cancelled()
    made = []
    mine = ('zkstream_tpu.io.watcher', 'zkstream_tpu.utils.fsm')

    def tap(name):
        orig = getattr(event_loop, name)

        def tapped(when, cb, *args, **kw):
            if getattr(cb, '__module__', None) in mine:
                made.append((name, cb))
            return orig(when, cb, *args, **kw)
        setattr(event_loop, name, tapped)
    tap('call_later')
    tap('call_at')
    try:
        for _ in range(100):
            at = we._probe_at
            we.notify()
            assert we.is_in_state('arming')
            await wait_until(lambda: we.is_in_state('armed'), interval=0)
            assert we._probe_at > at
    finally:
        del event_loop.call_later, event_loop.call_at
    assert made == []
    assert we._probe_handle is timer and not timer.cancelled()
    assert _transitions(c1, 'armed', 'arming') == 100


@pytest.mark.parametrize('left', ['notified', 'moved', 'torn_down'])
async def test_reply_after_the_event_left_arming_changes_nothing(
        server, two_clients, left):
    c1, c2 = two_clients
    we, seen = await _armed_event(c1, '/late')
    _data, stat = await c1.get('/late')
    await c2.set('/late', b'v1')
    await wait_until(lambda: seen == [b'v0', b'v1'] and
                     we.is_in_state('armed'))
    conn = c1.current_connection()
    server.drop_replies = True
    try:
        we.notify()
        assert we.is_in_state('arming')
        req = we._arm_req
        assert conn.reqs[req.packet['xid']] is req
        # what a lagging member might answer: the OLD node, newer zxid
        late = {'xid': req.packet['xid'], 'opcode': 'GET_DATA',
                'err': 'OK', 'zxid': c1.session.last_zxid,
                'data': b'stale', 'stat': stat._replace(
                    mzxid=we.prev_zxid + 1000)}
        if left == 'notified':
            # mid-arm a notification is nobody's: same request waited on
            we.notify()
            assert we._arm_req is req and len(conn.reqs) == 1
            conn.process_reply(late)
            assert we.is_in_state('armed')
            assert seen == [b'v0', b'v1', b'stale']
            return
        if left == 'moved':
            we._transition('wait_session')      # and straight on
            assert we.is_in_state('arming') and we._arm_req is not req
        else:
            conn.transport.abort()
            await wait_until(lambda: not we.is_in_state('arming'),
                             interval=0)
            assert we._arm_retry
        state, held, prev = we.get_state(), we._arm_req, we.prev_zxid
        assert req.settle(late) is True         # heard, by a deaf ear
        assert we.get_state() == state and we._arm_req is held
        assert we.prev_zxid == prev and seen == [b'v0', b'v1']
    finally:
        server.drop_replies = False


async def test_session_calls_the_notify_the_watcher_instance_holds(
        two_clients):
    """A ``notify`` replaced on the ``ZKWatcher`` instance is what the
    session calls (the benchmark stamps a node's notification there)."""
    c1, c2 = two_clients
    _we, seen = await _armed_event(c1, '/hook')
    w = c1.watcher('/hook')
    notify, told = w.notify, []

    def stamp(evt):
        told.append(evt)
        notify(evt)
    w.notify = stamp
    await c2.set('/hook', b'v1')
    await wait_until(lambda: seen == [b'v0', b'v1'])
    assert told == ['dataChanged']
    assert _transitions(c1, 'armed', 'arming') == 1
