"""In-process unit tests for the cross-process replication layer
(tests/test_process_ensemble.py proves the tier end-to-end across real
OS processes; these drive the same code in ONE process so the error
paths and bookkeeping are observable: RPC error propagation, mirror
ingest/ack flow, truncation interplay, late-joiner snapshot
bootstrap, detach on follower death).

The control channel is a blocking socket by design (follower request
handlers call it inline); here the blocking calls run on an executor
thread while the service runs on the test's loop — the same
cross-process topology, folded into one process."""

from __future__ import annotations

import asyncio

import pytest

from zkstream_tpu.protocol.consts import CreateFlag
from zkstream_tpu.protocol.records import OPEN_ACL_UNSAFE
from zkstream_tpu.server.replication import (
    RemoteLeader,
    RemoteReplicaStore,
    ReplicationService,
)
from zkstream_tpu.server.store import ZKDatabase, ZKOpError


@pytest.fixture
def repl(event_loop):
    db = ZKDatabase()
    svc = event_loop.run_until_complete(ReplicationService(db).start())
    remotes: list[RemoteLeader] = []

    async def connect():
        r = await RemoteLeader('127.0.0.1', svc.port).connect()
        remotes.append(r)
        return r

    yield db, svc, connect
    for r in remotes:
        r.close()
    event_loop.run_until_complete(svc.stop())


async def _rpc(fn, *args):
    """Run a blocking RemoteLeader call off-loop, as a second process
    would effectively do from the service's point of view."""
    return await asyncio.get_running_loop().run_in_executor(
        None, lambda: fn(*args))


async def test_rpc_write_ops_and_error_propagation(repl):
    db, svc, connect = repl
    remote = await connect()
    store = RemoteReplicaStore(remote, lag=0.0)

    path = await _rpc(remote.create, '/a', b'x', OPEN_ACL_UNSAFE,
                      CreateFlag(0), None)
    assert path == '/a'
    # the RPC piggyback delivered the commit: local catch_up suffices
    store.catch_up()
    assert store.nodes['/a'].data == b'x'

    stat = await _rpc(remote.set_data, '/a', b'y', 0)
    assert stat.version == 1
    with pytest.raises(ZKOpError) as ei:
        await _rpc(remote.set_data, '/missing', b'', -1)
    assert ei.value.code == 'NO_NODE'
    with pytest.raises(ZKOpError):
        await _rpc(remote.delete, '/a', 99)      # BAD_VERSION
    await _rpc(remote.delete, '/a', 1)
    store.catch_up()
    assert '/a' not in store.nodes


async def test_session_lifecycle_over_control_channel(repl):
    db, svc, connect = repl
    remote = await connect()

    sess = await _rpc(remote.create_session, 9000)
    assert db.sessions[sess.id].timeout == 9000
    # resume with the right and wrong password
    again = await _rpc(remote.resume_session, sess.id, sess.passwd)
    assert again is sess                 # same local mirror object
    bad = await _rpc(remote.resume_session, sess.id, b'\x00' * 16)
    assert bad is None
    # touch is fire-and-forget; close reaps leader-side
    remote.touch_session(sess)
    await _rpc(remote.close_session, sess.id)
    assert db.sessions[sess.id].closed
    assert remote.sessions[sess.id].closed


async def test_events_channel_pushes_commits_and_acks(repl):
    db, svc, connect = repl
    remote = await connect()
    applied = []
    remote.on('committed', lambda: applied.append(remote.log_end()))

    # a write NOT through this follower (the leader's own member):
    # reaches the mirror via the events push
    db.create('/pushed', b'p', OPEN_ACL_UNSAFE, CreateFlag(0))
    for _ in range(50):
        if remote.log_end() == db.log_end():
            break
        await asyncio.sleep(0.02)
    assert remote.log_end() == db.log_end() == 1
    assert applied, 'committed never emitted from the events push'
    # ...and the follower's ack advanced the leader-side floor
    (handle,) = svc._handles.values()
    for _ in range(50):
        if handle.applied == 1:
            break
        await asyncio.sleep(0.02)
    assert handle.applied == 1


async def test_expiry_broadcast_reaches_follower(repl):
    db, svc, connect = repl
    remote = await connect()
    sess = await _rpc(remote.create_session, 1000)
    seen = []
    remote.on('sessionExpired', seen.append)
    db.expire_session(sess.id)
    for _ in range(50):
        if seen:
            break
        await asyncio.sleep(0.02)
    assert seen == [sess.id]
    assert remote.sessions[sess.id].expired


async def test_late_joiner_bootstraps_from_snapshot(repl):
    """A follower joining after history began installs the leader's
    snapshot and replays only the tail — real ZK's follower resync.
    History before ANY replica attached was never logged; the image
    carries its effects anyway."""
    db, svc, connect = repl
    # pre-replication history: zxid advances, nothing is logged
    db.create('/pre', b'old', OPEN_ACL_UNSAFE, CreateFlag(0))
    assert db.zxid == 1 and db.log_end() == 0

    late = await connect()
    store = RemoteReplicaStore(late, lag=0.0)
    assert store.nodes['/pre'].data == b'old'
    assert store.zxid == 1 and store.applied == 0

    # post-join traffic replicates normally, via both channels
    await _rpc(late.create, '/post', b'new', OPEN_ACL_UNSAFE,
               CreateFlag(0), None)
    store.catch_up()
    assert store.nodes['/post'].data == b'new'
    db.create('/pushed', b'p', OPEN_ACL_UNSAFE, CreateFlag(0))
    for _ in range(50):
        if late.log_end() == db.log_end():
            break
        await asyncio.sleep(0.02)
    store.catch_up()
    assert store.nodes['/pushed'].data == b'p'
    assert store.zxid == db.zxid == 3


async def test_snapshot_join_past_truncated_log(repl):
    """A joiner arriving after the log was truncated (its prefix
    applied everywhere and dropped) still bootstraps correctly: the
    snapshot position sits past the truncation floor by
    construction."""
    db, svc, connect = repl
    first = await connect()
    RemoteReplicaStore(first, lag=0.0)
    n = ZKDatabase.LOG_TRUNC_CHUNK + 20
    for i in range(n):
        await _rpc(first.create, '/n%d' % i, b'', OPEN_ACL_UNSAFE,
                   CreateFlag(0), None)
    (h1,) = svc._handles.values()
    for _ in range(100):
        if h1.applied == db.log_end():
            break
        await asyncio.sleep(0.02)
    await _rpc(first.create, '/trunc-trigger', b'', OPEN_ACL_UNSAFE,
               CreateFlag(0), None)
    assert db.log_base > 0, 'truncation never ran'

    late = await connect()
    store = RemoteReplicaStore(late, lag=0.0)
    await _rpc(store.sync_flush)
    assert store.nodes.keys() == db.nodes.keys()
    assert store.zxid == db.zxid


async def test_follower_death_detaches_handle(repl):
    db, svc, connect = repl
    remote = await connect()
    await _rpc(remote.create, '/x', b'', OPEN_ACL_UNSAFE,
               CreateFlag(0), None)
    assert len(svc._handles) == 1 and len(db._replicas) == 1
    remote.close()                       # both channels die
    for _ in range(50):
        if not svc._handles:
            break
        await asyncio.sleep(0.02)
    assert not svc._handles and not db._replicas
    # with no replicas attached the next write is not even logged
    # (nothing left that could replay it)
    db.create('/after', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    assert db.log_end() == db.log_base + len(db.log)


async def test_sync_barrier_fetches_unpushed_history(repl):
    """sync_flush must round-trip: a commit the events channel has NOT
    delivered is still visible after the barrier.  The hold-back is
    deterministic — the leader-side push writer is detached while the
    commit lands, so the events channel genuinely never carries it and
    only the barrier's control-channel piggyback can (a regression of
    sync_flush to plain catch_up fails this test every run)."""
    db, svc, connect = repl
    remote = await connect()
    store = RemoteReplicaStore(remote, lag=0.0)
    (handle,) = svc._handles.values()
    writer, handle.writer = handle.writer, None    # pause pushes
    try:
        db.create('/s', b'v0', OPEN_ACL_UNSAFE, CreateFlag(0))
        await asyncio.sleep(0.05)
        assert remote.log_end() == 0, 'push leaked past the hold-back'
        await _rpc(store.sync_flush)
        assert store.nodes['/s'].data == b'v0'
        assert remote.log_end() == db.log_end()
    finally:
        handle.writer = writer


async def test_truncation_waits_for_follower_acks(repl):
    """The leader must never truncate past the lowest follower ACK:
    a slow-to-ack follower pins the log tail its next control RPC may
    piggyback from."""
    db, svc, connect = repl
    remote = await connect()
    RemoteReplicaStore(remote, lag=0.0)
    n = ZKDatabase.LOG_TRUNC_CHUNK + 40
    for i in range(n):
        await _rpc(remote.create, '/t%d' % i, b'', OPEN_ACL_UNSAFE,
                   CreateFlag(0), None)
    (handle,) = svc._handles.values()
    # acks flow on the events channel; wait for them to drain
    for _ in range(100):
        if handle.applied == db.log_end():
            break
        await asyncio.sleep(0.02)
    assert handle.applied == db.log_end()
    # the next commit runs the truncation sweep past the chunk floor
    await _rpc(remote.create, '/t-last', b'', OPEN_ACL_UNSAFE,
               CreateFlag(0), None)
    assert db.log_base >= ZKDatabase.LOG_TRUNC_CHUNK
    assert db.log_base <= handle.applied


async def test_stop_with_live_followers_does_not_hang(repl):
    """Since Python 3.12.1, Server.wait_closed() also waits for client
    handlers; stop() must sever live follower channels first (the
    ZKServer.stop() hazard, server.py) — bounded here so a regression
    fails fast instead of deadlocking the suite."""
    db, svc, connect = repl
    remote = await connect()
    await _rpc(remote.create, '/live', b'', OPEN_ACL_UNSAFE,
               CreateFlag(0), None)
    await asyncio.wait_for(svc.stop(), timeout=10)
    assert not svc._handles


async def test_unknown_rpc_method_is_a_loud_error(repl):
    """A protocol-version skew (follower asking for an RPC this leader
    does not speak) surfaces as a RuntimeError naming the method, not
    a hang or a silent None."""
    db, svc, connect = repl
    remote = await connect()
    with pytest.raises(RuntimeError, match='nonsense'):
        await _rpc(remote._rpc, 'nonsense')
    # the channel survives the error: normal RPCs keep working
    await _rpc(remote.create, '/after-err', b'', OPEN_ACL_UNSAFE,
               CreateFlag(0), None)


async def test_unknown_hello_kind_is_dropped(repl):
    """A connection speaking neither channel role is closed, and the
    service keeps serving real followers."""
    import struct as _struct

    db, svc, connect = repl
    reader, writer = await asyncio.open_connection('127.0.0.1',
                                                   svc.port)
    import pickle
    payload = pickle.dumps(('bogus', 'tok'))
    writer.write(_struct.pack('>I', len(payload)) + payload)
    await writer.drain()
    data = await asyncio.wait_for(reader.read(), 5)
    assert data == b''                   # server closed it
    writer.close()
    remote = await connect()             # real followers still join
    await _rpc(remote.create, '/ok', b'', OPEN_ACL_UNSAFE,
               CreateFlag(0), None)


# -- the batch message: a turn's writes in ONE control-channel RPC -----

def _set(path, data, version=-1):
    return ('set_data', (path, data, version))


async def test_batch_answers_each_write_on_its_own_in_order(repl):
    """One ``batch`` RPC: the leader applies the elements in list
    order and answers each with its own result — a BAD_VERSION between
    two good sets fails alone, a multi stays one all-or-nothing
    element (its rejection reports per-op errors under 'ok' and
    applies nothing) — and ONE response piggybacks every entry."""
    db, svc, connect = repl
    remote = await connect()
    store = RemoteReplicaStore(remote, lag=0.0)
    db.create('/a', b'0', OPEN_ACL_UNSAFE, CreateFlag(0))
    db.create('/b', b'0', OPEN_ACL_UNSAFE, CreateFlag(0))
    rpcs = remote.forward_rpcs
    results = await _rpc(remote.forward, [
        _set('/a', b'1', 0),
        _set('/a', b'bad', 7),                    # BAD_VERSION: alone
        _set('/a', b'2', 1),
        ('multi', ([{'op': 'create', 'path': '/m', 'data': b'm'},
                    {'op': 'set_data', 'path': '/b', 'data': b'1'}],
                   None)),
        ('multi', ([{'op': 'set_data', 'path': '/b', 'data': b'x'},
                    {'op': 'delete', 'path': '/nope'}], None)),
        ('create', ('/c', b'c', OPEN_ACL_UNSAFE, CreateFlag(0), None)),
        ('delete', ('/c', 0)),
    ])
    assert remote.forward_rpcs == rpcs + 1
    assert remote.forward_writes >= 7
    statuses = [s for s, _ in results]
    assert statuses == ['ok', 'err', 'ok', 'ok', 'ok', 'ok', 'ok']
    assert results[0][1].version == 1
    assert results[1][1] == 'BAD_VERSION'
    assert results[2][1].version == 2
    assert results[3][1][0]['path'] == '/m'
    # the rejected multi: per-op error results, nothing applied
    assert any(r.get('err', 'OK') != 'OK' for r in results[4][1])
    assert results[5][1] == '/c' and results[6][1] is None
    assert db.nodes['/a'].data == b'2' and db.nodes['/b'].data == b'1'
    assert '/c' not in db.nodes and '/m' in db.nodes
    # the one response carried every entry: local catch_up suffices
    assert remote.log_end() == db.log_end()
    store.catch_up()
    assert store.nodes['/a'].data == b'2'
    assert store.nodes['/b'].data == b'1'


async def test_batch_is_made_durable_and_quorum_held_once(
        event_loop, tmp_path):
    """The leader answers a batch behind ONE ``sync_for_flush`` that
    covers every record in it and ONE quorum wait at the batch's LAST
    zxid — in that order, after every element is applied."""
    from zkstream_tpu.server.persist import open_wal_database

    db = open_wal_database(str(tmp_path / 'w'), sync='tick')
    svc = await ReplicationService(db, total=3, quorum=True).start()
    remote = await RemoteLeader('127.0.0.1', svc.port).connect()
    calls = []
    sync_for_flush = db.wal.sync_for_flush
    wait = svc.quorum.wait

    def spy_sync():
        calls.append(('sync', db.zxid))
        sync_for_flush()
        assert db.wal.durable_zxid == db.zxid

    async def spy_wait(target, timeout_s=None, grant=None):
        calls.append(('quorum', target, grant))
        return await wait(target, timeout_s, grant=grant)

    db.wal.sync_for_flush = spy_sync
    svc.quorum.wait = spy_wait
    try:
        db.create('/q', b'0', OPEN_ACL_UNSAFE, CreateFlag(0))
        calls.clear()
        results = await _rpc(remote.forward, [
            _set('/q', b'%d' % i) for i in range(16)])
        assert [s for s, _ in results] == ['ok'] * 16
        last = db.zxid
        # 3 voters: leader + the caller's virtual grant are a majority
        assert calls == [('sync', last), ('quorum', last, remote.token)]
        # a batch that commits nothing waits for no quorum
        calls.clear()
        results = await _rpc(remote.forward, [_set('/none', b'')])
        assert results == [('err', 'NO_NODE')]
        assert calls == [('sync', last)]
    finally:
        remote.close()
        await svc.stop()
        db.wal.close()


async def test_a_write_behind_a_touch_does_not_wait_for_its_ack(repl):
    """A ``touch`` has no response, so the RPC written behind it (a new
    session's first write through a follower) used to sit in the
    kernel until the leader's delayed ACK of the touch, ~40 ms later:
    the control channel runs without Nagle."""
    import socket
    import time

    db, svc, connect = repl
    remote = await connect()
    assert remote._sock.getsockopt(socket.IPPROTO_TCP,
                                   socket.TCP_NODELAY) != 0
    db.create('/t', b'0', OPEN_ACL_UNSAFE, CreateFlag(0))
    took = []
    for i in range(5):
        sess = db.create_session(30000)
        sess.last_touch_fwd = 0.0

        def touch_then_write(sess=sess, i=i):
            t = time.perf_counter()
            remote.touch_session(sess)
            remote.forward([_set('/t', b'%d' % i)])
            return time.perf_counter() - t
        took.append(await _rpc(touch_then_write))
    assert min(took) < 0.030, took


async def test_fenced_batch_fails_every_element_and_applies_nothing(
        repl):
    db, svc, connect = repl
    remote = await connect()
    db.create('/f', b'0', OPEN_ACL_UNSAFE, CreateFlag(0))
    svc.depose()
    zxid = db.zxid
    results = await _rpc(remote.forward, [
        _set('/f', b'1'), _set('/f', b'2'),
        ('create', ('/g', b'', OPEN_ACL_UNSAFE, CreateFlag(0), None))])
    assert results == [('err', 'EPOCH_FENCED')] * 3
    assert db.zxid == zxid and db.nodes['/f'].data == b'0'
    assert '/g' not in db.nodes
    # the db-shaped surface (a batch of one) raises it typed
    with pytest.raises(ZKOpError) as ei:
        await _rpc(remote.set_data, '/f', b'3', -1)
    assert ei.value.code == 'EPOCH_FENCED'


async def test_observer_batch_waits_for_real_voter_acks(event_loop):
    """An observer's mirror is outside the voter set: its batch gets no
    virtual grant, so the response waits for a REAL voter's ack of the
    batch's last zxid (here: degrades after the bounded wait, since
    no voter is attached)."""
    db = ZKDatabase()
    svc = await ReplicationService(db, total=3, quorum=True).start()
    svc.quorum.wait_ms = 60.0
    obs = await RemoteLeader('127.0.0.1', svc.port,
                             observer=True).connect()
    voter = await RemoteLeader('127.0.0.1', svc.port).connect()
    try:
        db.create('/o', b'0', OPEN_ACL_UNSAFE, CreateFlag(0))
        await asyncio.sleep(0.05)         # both mirrors acked the create
        # the voter's mirror keeps acking pushes: the observer's batch
        # is released by that REAL ack, well inside the degrade window
        results = await _rpc(obs.forward,
                             [_set('/o', b'1'), _set('/o', b'2')])
        assert [s for s, _ in results] == ['ok', 'ok']
        assert svc.quorum.degraded_releases == 0
        assert svc.quorum.quorum_zxid_floor == db.zxid
        # the voter gone: only the leader holds the next batch, the
        # observer's own mirror never counts, and the wait degrades
        voter.close()
        for _ in range(50):
            if len(svc._handles) == 1:
                break
            await asyncio.sleep(0.02)
        results = await _rpc(obs.forward, [_set('/o', b'3')])
        assert [s for s, _ in results] == ['ok']
        assert svc.quorum.degraded_releases == 1
    finally:
        obs.close()
        voter.close()
        await svc.stop()


async def test_control_phase_is_not_open_across_the_quorum_wait(
        event_loop):
    """The leader's service of a control-channel message is ledger
    phase ``control`` (server/replication.py ``_serve_control``) — up
    to the quorum wait and again after it, never across it: a batch
    parked behind a slow follower books no ``control`` time, and the
    ledger's stack is empty whenever the loop is given back.  The
    writes' ``repl_push`` nests under it; a follower's ack is phase
    ``repl_ack``."""
    import time

    from zkstream_tpu.utils.metrics import TickLedger

    db = ZKDatabase()
    led = db.ledger = TickLedger()
    svc = await ReplicationService(db, total=3, quorum=True).start()
    svc.quorum.wait_ms = 120.0
    obs = await RemoteLeader('127.0.0.1', svc.port,
                             observer=True).connect()
    voter = await RemoteLeader('127.0.0.1', svc.port).connect()
    open_at_wait = []
    wait = svc.quorum.wait

    async def spy_wait(target, timeout_s=None, grant=None):
        open_at_wait.append(list(led._stack))
        return await wait(target, timeout_s, grant=grant)
    svc.quorum.wait = spy_wait

    def phase_ms(phase):
        return dict(led.phase_hist.rows()).get(
            'zk_tick_phase_ms_sum{phase="%s"}' % (phase,), 0.0)

    try:
        db.create('/c', b'0', OPEN_ACL_UNSAFE, CreateFlag(0))
        await asyncio.sleep(0.05)       # both mirrors acked the create
        assert phase_ms('repl_ack') > 0         # the voter's ack
        # no voter left: the observer's batch parks for the whole
        # bounded wait (its own mirror never counts) and degrades
        voter.close()
        for _ in range(50):
            if len(svc._handles) == 1:
                break
            await asyncio.sleep(0.02)
        before = phase_ms('control')
        t0 = time.perf_counter()
        results = await _rpc(obs.forward,
                             [_set('/c', b'1'), _set('/c', b'2')])
        parked_ms = (time.perf_counter() - t0) * 1e3
        await asyncio.sleep(0.02)       # the tick closes
        assert [s for s, _ in results] == ['ok', 'ok']
        assert svc.quorum.degraded_releases == 1
        assert open_at_wait == [[]]     # nothing open when it parked
        assert parked_ms >= 100
        booked = phase_ms('control') - before
        assert 0 < booked < 0.25 * parked_ms
        assert phase_ms('repl_push') > 0 and not led._stack
        # a read-side RPC (no wait at all) is control time too
        before = phase_ms('control')
        await _rpc(obs.sync_barrier)
        await asyncio.sleep(0.02)
        assert phase_ms('control') > before
        assert set(dict(led.phase_hist.rows())) >= {
            'zk_tick_phase_ms_count{phase="control"}',
            'zk_tick_phase_ms_count{phase="repl_ack"}'}
    finally:
        obs.close()
        voter.close()
        await svc.stop()


async def test_leader_lost_with_a_batch_in_flight_loses_every_element(
        event_loop):
    """The control channel dies while the leader holds the batch (here:
    inside its quorum wait): EVERY element comes back outcome-unknown
    — typed CONNECTION_LOSS through the db-shaped surface — and the
    follower retries nothing (the elements may well have applied)."""
    from zkstream_tpu.server.replication import ZKLeaderLostError

    db = ZKDatabase()
    svc = await ReplicationService(db, total=3, quorum=True).start()
    svc.quorum.wait_ms = 5000.0
    obs = await RemoteLeader('127.0.0.1', svc.port,
                             observer=True).connect()
    lost = []
    obs.on_leader_lost = lambda: lost.append(True)
    try:
        db.create('/k', b'0', OPEN_ACL_UNSAFE, CreateFlag(0))
        fut = event_loop.run_in_executor(
            None, obs.forward, [_set('/k', b'%d' % i) for i in range(5)])
        for _ in range(100):
            if db.nodes['/k'].data == b'4':
                break
            await asyncio.sleep(0.01)
        assert db.nodes['/k'].data == b'4'     # applied, unanswered
        await svc.stop()                       # the leader "dies"
        results = await asyncio.wait_for(fut, 5)
        assert [s for s, _ in results] == ['lost'] * 5
        assert obs.forward_rpcs == 1 and lost
        with pytest.raises(ZKLeaderLostError) as ei:
            await _rpc(obs.set_data, '/k', b'x', -1)
        assert ei.value.code == 'CONNECTION_LOSS'
    finally:
        obs.close()
        await svc.stop()


async def test_batch_is_bounded_in_bytes(repl, monkeypatch):
    """What one turn collected beyond ``FORWARD_BATCH_BYTES`` goes in
    the next RPC of the same flush, in order; one oversized element
    still goes alone."""
    from zkstream_tpu.server import replication

    db, svc, connect = repl
    remote = await connect()
    db.create('/z', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    monkeypatch.setattr(replication, 'FORWARD_BATCH_BYTES', 2500)
    results = await _rpc(remote.forward, [
        _set('/z', b'a' * 1000), _set('/z', b'b' * 1000),
        _set('/z', b'c' * 1000), _set('/z', b'd' * 5000),
        _set('/z', b'e' * 10)])
    assert [p.version for _, p in results] == [1, 2, 3, 4, 5]
    # [a b] [c] [d: over the bound, alone] [e]
    assert remote.forward_rpcs == 4 and remote.forward_writes == 5
    assert db.nodes['/z'].data == b'e' * 10


async def test_a_write_outside_a_batch_is_refused_loudly(repl):
    db, svc, connect = repl
    remote = await connect()
    with pytest.raises(RuntimeError, match='set_data'):
        await _rpc(remote._rpc, 'set_data', '/x', b'', -1)
    # ...and a batch carries writes, nothing else
    results = await _rpc(remote._rpc, 'batch', [('sync_barrier', ())])
    assert results[0][0] == 'exc'


# -- the group ship: the commit log leaves once a GROUP of commits -----

class Wire:
    """Every message read whole off a replication stream, in arrival
    order; a mirror's events channel is the reader its ``'attached'``
    came on."""

    def __init__(self):
        self.seen: list = []        # (reader, msg)
        self.mirrors: list = []     # events readers, in attach order

    def note(self, reader, msg) -> None:
        if msg[0] == 'attached':
            self.mirrors.append(reader)
        self.seen.append((reader, msg))

    def clear(self) -> None:
        self.seen.clear()

    def pushed(self, nth: int, kinds=('commit',)) -> list:
        """What the ``nth`` mirror to attach was pushed, of ``kinds``."""
        return [m for r, m in self.seen
                if r is self.mirrors[nth] and m[0] in kinds]


@pytest.fixture
def wire(monkeypatch):
    from zkstream_tpu.server import replication

    w = Wire()
    read = replication._read_msg

    async def spy(reader):
        msg = await read(reader)
        w.note(reader, msg)
        return msg
    monkeypatch.setattr(replication, '_read_msg', spy)
    return w


def spy_acks(svc) -> list:
    """Every ack the leader takes, as ``(token, msg)``."""
    acks: list = []
    note = svc._note_ack

    def spy(h, msg):
        acks.append((h.token, msg))
        note(h, msg)
    svc._note_ack = spy
    return acks


async def settle(cond, what='the mirrors'):
    for _ in range(250):
        if cond():
            return
        await asyncio.sleep(0.01)
    raise AssertionError('%s never settled' % (what,))


async def test_a_forwarded_batch_is_one_push_and_one_ack_a_mirror(
        repl, wire):
    """The n commits of one forwarded ``batch`` reach EVERY mirror —
    the forwarder's too — as ONE ``'commit'`` message of n entries,
    shipped from ``_apply_batch``; each mirror acks once.  The ship
    the batch's first commit scheduled for the turn's end finds
    nothing left."""
    db, svc, connect = repl
    a, b = await connect(), await connect()
    db.create('/g', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    (ha, hb) = (svc._handles[a.token], svc._handles[b.token])
    await settle(lambda: ha.applied == hb.applied == db.log_end())
    acks = spy_acks(svc)
    wire.clear()
    before = (db.repl_pushes, db.repl_pushed_commits)
    base = db.log_end()

    results = await _rpc(a.forward,
                         [_set('/g', b'%d' % i) for i in range(5)])
    assert [s for s, _ in results] == ['ok'] * 5
    await settle(lambda: ha.applied == hb.applied == db.log_end())
    await asyncio.sleep(0.05)           # a second message would land
    for nth in (0, 1):
        (msg,) = wire.pushed(nth)
        assert msg[1] == base and len(msg[2]) == 5
        assert msg[2] == db.log[base - db.log_base:]
    assert sorted(t for t, _ in acks) == sorted([a.token, b.token])
    assert {m[1] for _, m in acks} == {db.log_end()}
    assert a.log == b.log == db.log
    # the leader counted two messages of five entries
    assert (db.repl_pushes - before[0],
            db.repl_pushed_commits - before[1]) == (2, 10)
    assert not svc._ship_due


async def test_a_turns_own_commits_leave_as_one_push(repl, wire):
    """Commits made on the leader's loop outside a batch (its own
    connections' writes): the k of ONE turn leave as one message
    behind the turn, those of two turns as two."""
    db, svc, connect = repl
    remote = await connect()
    db.create('/t', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    await settle(lambda: remote.log_end() == db.log_end())
    wire.clear()

    for i in range(4):                  # one turn: nothing awaited
        db.set_data('/t', b'%d' % i, -1)
    assert svc._ship_due and remote.log_end() == 1
    await settle(lambda: remote.log_end() == db.log_end())
    (msg,) = wire.pushed(0)
    assert msg[1] == 1 and len(msg[2]) == 4

    db.set_data('/t', b'x', -1)
    await asyncio.sleep(0)              # the turn ends: its ship runs
    assert not svc._ship_due
    db.set_data('/t', b'y', -1)
    await settle(lambda: remote.log_end() == db.log_end())
    assert [(m[1], len(m[2])) for m in wire.pushed(0)] == [
        (1, 4), (5, 1), (6, 1)]
    assert remote.log == db.log


async def test_a_commit_with_no_mirror_attached_schedules_nothing(
        repl):
    db, svc, connect = repl
    db.create('/alone', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    assert not svc._ship_due and db.repl_pushes == 0


async def test_an_expiry_push_does_not_overtake_the_turns_commits(
        repl, wire):
    """The events channel keeps its order: a ``session_expired``
    pushed in the turn that committed a write and the expiry's own
    records first ships them, so the mirror holds every entry that
    preceded the broadcast when it arrives."""
    db, svc, connect = repl
    remote = await connect()
    sess = db.create_session(30000)
    db.create('/e', b'', OPEN_ACL_UNSAFE, CreateFlag.EPHEMERAL, sess)
    db.create('/x', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    await settle(lambda: remote.log_end() == db.log_end())
    wire.clear()
    held = []
    remote.on('sessionExpired', lambda sid: held.append(
        (sid, remote.log_end())))

    db.set_data('/x', b'1', -1)
    db.expire_session(sess.id)          # same turn: close + reap
    end = db.log_end()
    await settle(lambda: held, 'the expiry broadcast')
    assert held == [(sess.id, end)]
    msgs = wire.pushed(0, ('commit', 'session_expired'))
    assert [m[0] for m in msgs] == ['commit', 'session_expired']
    # the set, the session's close record, the ephemeral's delete
    assert len(msgs[0][2]) == 3


class DropNext:
    """The injector's ``drop_push``, deterministic: the next ``n``
    push messages are lost."""

    def __init__(self, n: int = 1):
        self.left = n

    def drop_push(self, token: str) -> bool:
        if self.left:
            self.left -= 1
            return True
        return False


async def test_a_dropped_group_push_is_recovered_by_the_piggyback(
        repl, wire):
    """``faults.drop_push`` loses a push MESSAGE, now a whole group:
    the push cursor has moved on, the next push gaps and is refused
    by the mirror, and the next control-channel response serves from
    the mirror's end — no entry lost, none doubled."""
    db, svc, connect = repl
    remote = await connect()
    store = RemoteReplicaStore(remote, lag=0.0)
    db.create('/d', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    await settle(lambda: remote.log_end() == db.log_end())
    (handle,) = svc._handles.values()
    acks = spy_acks(svc)

    svc.faults = DropNext(1)
    for i in range(3):                  # one group, lost whole
        db.set_data('/d', b'%d' % i, -1)
    await asyncio.sleep(0.05)
    assert svc.faults.left == 0
    assert remote.log_end() == 1 and handle.shipped == db.log_end() == 4
    db.set_data('/d', b'late', -1)      # pushed, but past a gap
    await asyncio.sleep(0.05)
    assert remote.log_end() == 1 and not acks
    assert [(m[1], len(m[2])) for m in wire.pushed(0)][-1] == (4, 1)
    await _rpc(remote.sync_barrier)
    assert remote.log == db.log and len(remote.log) == 5
    store.catch_up()
    assert store.nodes['/d'].data == b'late'
    assert store.zxid == db.zxid
    await settle(lambda: handle.applied == db.log_end(), 'the ack')


async def test_quorum_ack_ms_is_stamped_a_commit_at_commit_time(
        event_loop):
    """``zk_quorum_ack_ms`` keeps its subject through the group ship:
    one sample a COMMIT, from the commit's own time — a group of n
    observes n samples, and none is shorter than the commit's wait
    for the group's push."""
    import time

    db = ZKDatabase()
    svc = await ReplicationService(db, total=2, quorum=True).start()
    voter = await RemoteLeader('127.0.0.1', svc.port).connect()
    try:
        db.create('/q', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
        await settle(lambda: svc.quorum.quorum_zxid_floor == db.zxid)
        samples: list = []
        svc.quorum.ack_hist = type('Hist', (), {
            'observe': staticmethod(samples.append)})
        committed: list = []
        db.on('committed', lambda: committed.append(time.monotonic()))
        shipped_at: list = []
        ship = svc._ship

        def spy_ship():
            shipped_at.append(time.monotonic())
            ship()
        svc._ship = spy_ship

        for i in range(3):              # one turn, 10 ms a commit
            db.set_data('/q', b'%d' % i, -1)
            time.sleep(0.01)
        assert len(svc.quorum._commit_t) == 3 and not shipped_at
        await settle(lambda: svc.quorum.quorum_zxid_floor == db.zxid)
        assert len(shipped_at) == 1 and len(samples) == 3
        waits = [(shipped_at[0] - t) * 1e3 for t in committed]
        assert waits[0] >= 29 and waits[2] >= 9
        # _advance observes in zxid order: sample i is commit i's
        assert all(s >= w for s, w in zip(samples, waits)), (
            samples, waits)
    finally:
        voter.close()
        await svc.stop()


async def test_a_batch_whose_every_element_fails_ships_nothing(repl):
    from zkstream_tpu.utils.metrics import TickLedger

    db, svc, connect = repl
    led = db.ledger = TickLedger()
    remote = await connect()
    db.create('/f', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    await settle(lambda: remote.log_end() == db.log_end())
    await asyncio.sleep(0.02)           # the tick closes
    sent = []
    push = svc._push
    svc._push = lambda h, msg, data=None: (sent.append(msg[0]),
                                           push(h, msg, data))
    before = (db.repl_pushes, db.repl_pushed_commits,
              db.repl_pushed_bytes)
    count = 'zk_tick_phase_ms_count{phase="repl_push"}'
    pushes = dict(led.phase_hist.rows()).get(count, 0)
    results = await _rpc(remote.forward, [
        _set('/none', b''), _set('/f', b'bad', 7),
        ('delete', ('/none', -1)),
        ('create', ('/f', b'', OPEN_ACL_UNSAFE, CreateFlag(0), None))])
    assert results == [('err', 'NO_NODE'), ('err', 'BAD_VERSION'),
                       ('err', 'NO_NODE'), ('err', 'NODE_EXISTS')]
    await asyncio.sleep(0.05)
    assert not sent and not svc._ship_due
    assert (db.repl_pushes, db.repl_pushed_commits,
            db.repl_pushed_bytes) == before
    assert dict(led.phase_hist.rows()).get(count, 0) == pushes


async def test_a_push_group_carries_one_stamp_and_the_mirror_trails_by_it(
        repl, wire):
    """``zk_apply_lag_ms``'s source: a ``'commit'`` push carries ONE
    stamp, the leader's ``time.monotonic()`` when the group's FIRST
    entry was committed (a float a group, not a commit), and so does a
    control-channel response for the entries it piggybacks; the
    mirror's replica observes, for each entry it applies, the time
    since that stamp — here the 30 ms the first commit of the turn
    waited for the turn to end, at least."""
    import time

    db, svc, connect = repl
    remote = await connect()
    store = RemoteReplicaStore(remote)
    db.create('/s', b'', OPEN_ACL_UNSAFE, CreateFlag(0))
    await settle(lambda: store.applied == db.log_end())
    n0 = store.apply_lag.count()
    wire.clear()

    t0 = time.monotonic()
    for i in range(3):                  # one turn, 10 ms a commit
        db.set_data('/s', b'%d' % i, -1)
        time.sleep(0.01)
    t1 = time.monotonic()
    await settle(lambda: store.applied == db.log_end())
    (msg,) = wire.pushed(0)
    assert len(msg) == 5 and len(msg[2]) == 3
    assert t0 <= msg[4] <= t0 + 0.005 < t1      # the FIRST commit's
    assert msg[4] == db.stamps.at(msg[1]) == remote.stamps.at(msg[1])
    # the group's later entries read the group's stamp on the mirror
    assert remote.stamps.at(msg[1] + 2) == msg[4]
    assert db.stamps.at(msg[1] + 2) >= msg[4] + 0.019
    assert store.apply_lag.count() == n0 + 3
    assert store.apply_lag.sum() >= 3 * 29.0
    # a response's piggyback: entries the mirror got no push of
    svc.partitioned.add(remote.token)
    db.set_data('/s', b'late', -1)
    t2 = time.monotonic()
    await asyncio.sleep(0.03)
    assert store.applied == db.log_end() - 1
    svc.partitioned.discard(remote.token)
    await _rpc(remote.sync_barrier)
    store.catch_up()
    assert store.applied == db.log_end()
    assert t2 - 0.005 <= remote.stamps.at(db.log_end() - 1) <= t2
    assert store.apply_lag.count() == n0 + 4
    assert store.apply_lag.sum() >= 3 * 29.0 + 29.0
    # history from before the marks has no stamp, and costs nothing
    assert remote.stamps.at(-1) is None
