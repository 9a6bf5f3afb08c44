"""Process-isolated ensemble tier (VERDICT r4 next #4): each member is
its own OS process, and the member holding the session dies by SIGKILL
— the OS severs the client's TCP connection, not a cooperative close —
while the session, its ephemeral, and its watches survive on the rest
of the ensemble.  The rebuild's version of the reference experiment at
test/multi-node.test.js:233-350 (three real server processes; kills in
test/zkserver.js:236-264)."""

from __future__ import annotations

import asyncio
import os
import signal
import subprocess
import sys
import types

import pytest

from helpers import mntr_rows
from test_server_edges import RawClient

WORKER = os.path.join(os.path.dirname(__file__),
                      'process_member_worker.py')


class Member:
    def __init__(self, proc: subprocess.Popen, ports: list[int]):
        self.proc = proc
        self.ports = ports


def _spawn(*args: str) -> Member:
    proc = subprocess.Popen(
        [sys.executable, WORKER, *args],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
    line = proc.stdout.readline().strip()
    assert line.startswith('READY '), (args, line)
    return Member(proc, [int(x) for x in line.split()[1:]])


@pytest.fixture
def process_ensemble():
    """A leader process + two follower processes; yields
    (leader, [follower1, follower2]).  SIGKILLs everything left at
    teardown."""
    members: list[Member] = []
    leader = _spawn('leader')
    members.append(leader)
    try:
        for _ in range(2):
            members.append(_spawn('follower', '127.0.0.1',
                                  str(leader.ports[1])))
        yield leader, members[1:]
    finally:
        for m in members:
            if m.proc.poll() is None:
                m.proc.kill()
            m.proc.wait()
            m.proc.stdout.close()


def _client(addrs, **kw):
    from zkstream_tpu import Client

    kw.setdefault('session_timeout', 12000)
    c = Client(servers=addrs, shuffle_backends=False, **kw)
    c.start()
    return c


async def _retrying(coro_fn, attempts=20, delay=0.25):
    last = None
    for _ in range(attempts):
        try:
            return await coro_fn()
        except Exception as e:        # reconnect churn mid-failover
            last = e
            await asyncio.sleep(delay)
    raise last


async def test_sigkill_member_session_and_watches_survive(
        process_ensemble):
    """The reference experiment: kill -9 the member serving the
    session; the client reconnects to another member, resumes the SAME
    session (no 'expire', no fresh 'session'), its ephemeral is intact,
    and a re-armed watch still fires (multi-node.test.js:233-350)."""
    from zkstream_tpu.protocol.consts import CreateFlag

    leader, (f1, f2) = process_ensemble
    others = [('127.0.0.1', f2.ports[0]), ('127.0.0.1', leader.ports[0])]
    c1 = _client([('127.0.0.1', f1.ports[0])] + others)
    c2 = _client(list(reversed(others)))
    events: list[str] = []
    for ev in ('session', 'connect', 'disconnect', 'expire', 'failed'):
        c1.on(ev, lambda *a, ev=ev: events.append(ev))
    try:
        await c1.wait_connected(timeout=10)
        await c2.wait_connected(timeout=10)
        sid = c1.session.get_session_id()
        await c1.create('/eph', b'mine', flags=CreateFlag.EPHEMERAL)
        await c1.create('/watched', b'v0')

        fired: asyncio.Future = asyncio.get_running_loop().create_future()

        def on_change(*a):
            if not fired.done():
                fired.set_result(a)

        c1.watcher('/watched').on('dataChanged', on_change)
        await asyncio.sleep(0.3)       # arm (and swallow arm-time emit)
        events.clear()

        # the OS, not a cooperative close, severs the connection
        os.kill(f1.proc.pid, signal.SIGKILL)
        f1.proc.wait()

        # the session resumes on a surviving member within the timeout
        st = await _retrying(lambda: c1.stat('/eph'))
        assert st is not None
        assert c1.session.get_session_id() == sid, \
            'session did not survive the SIGKILL'
        assert 'disconnect' in events and 'connect' in events, events
        assert 'expire' not in events and 'session' not in events, events

        # the ephemeral survives — its session never expired
        data, _ = await c2.get('/eph')
        assert data == b'mine'

        # the re-armed watch still fires, through the new member
        await c2.set('/watched', b'v1')
        got = await asyncio.wait_for(fired, 10)
        assert got, 'watch lost across the SIGKILL failover'
        data, _ = await c1.get('/watched')
        assert data == b'v1'
    finally:
        await c1.close()
        await c2.close()


async def test_process_members_replicate_and_sync(process_ensemble):
    """Plumbing check for the tier itself: a write through one OS
    process is readable through another after sync, and sequential
    numbering stays leader-global across processes."""
    from zkstream_tpu.protocol.consts import CreateFlag

    leader, (f1, f2) = process_ensemble
    c1 = _client([('127.0.0.1', f1.ports[0])])
    c2 = _client([('127.0.0.1', f2.ports[0])])
    try:
        await c1.wait_connected(timeout=10)
        await c2.wait_connected(timeout=10)
        await c1.create('/x', b'hello')
        await c2.sync('/x')
        data, stat = await c2.get('/x')
        assert data == b'hello' and stat.version == 0
        p1 = await c1.create('/s-', b'', flags=CreateFlag.SEQUENTIAL)
        p2 = await c2.create('/s-', b'', flags=CreateFlag.SEQUENTIAL)
        assert p1 == '/s-0000000000' and p2 == '/s-0000000001'
        await c1.set('/x', b'world')
        await c2.sync('/x')
        data, stat = await c2.get('/x')
        assert data == b'world' and stat.version == 1

        # push past LOG_TRUNC_CHUNK commits so the leader's truncation
        # sweep runs UNDER the control-channel piggyback: acks (not
        # shipments) gate the floor, so forwarded writes must keep
        # working throughout
        for i in range(300):
            await c1.set('/x', b'w%d' % i)
        await c2.sync('/x')
        data, stat = await c2.get('/x')
        assert data == b'w299' and stat.version == 301
    finally:
        await c1.close()
        await c2.close()


async def test_killed_follower_replaced_by_fresh_process(
        process_ensemble):
    """The restart half of the reference experiment
    (multi-node.test.js restarts a killed server): after SIGKILLing a
    follower, a replacement follower process joins the live ensemble
    late — bootstrapped from the leader's snapshot — and serves the
    full tree to clients."""
    leader, (f1, f2) = process_ensemble
    c = _client([('127.0.0.1', f1.ports[0])])
    try:
        await c.wait_connected(timeout=10)
        for i in range(5):
            await c.create('/pre%d' % i, b'v%d' % i)
    finally:
        await c.close()

    os.kill(f1.proc.pid, signal.SIGKILL)
    f1.proc.wait()

    # a replacement member, joining AFTER history began
    f3 = _spawn('follower', '127.0.0.1', str(leader.ports[1]))
    try:
        c3 = _client([('127.0.0.1', f3.ports[0])])
        try:
            await c3.wait_connected(timeout=10)
            await c3.sync('/pre0')
            for i in range(5):
                data, _ = await c3.get('/pre%d' % i)
                assert data == b'v%d' % i
            # and it serves writes + watches like any member
            await c3.create('/via3', b'x')
            data, _ = await c3.get('/via3')
            assert data == b'x'
        finally:
            await c3.close()
    finally:
        f3.proc.kill()
        f3.proc.wait()
        f3.proc.stdout.close()


@pytest.mark.timeout(120)
async def test_leader_sigkill_restart_from_disk(tmp_path):
    """The durability plane's headline at the OS-process tier: the
    LEADER process — the quorum itself, whose death previously lost
    every acked write — is SIGKILLed and respawned over its WAL dir
    (server/persist.py), and every acked write is back.  Two
    generations deep, so recovery-of-a-recovered-log is covered."""
    wal_dir = str(tmp_path / 'leader-wal')
    leader = _spawn('leader', wal_dir)
    c = _client([('127.0.0.1', leader.ports[0])])
    try:
        await c.wait_connected(timeout=10)
        for i in range(10):
            await c.create('/d%d' % i, b'gen0-%d' % i)
        await c.set('/d0', b'gen0-final')
    finally:
        await c.close()

    # the OS severs everything; RAM is gone
    os.kill(leader.proc.pid, signal.SIGKILL)
    leader.proc.wait()
    leader.proc.stdout.close()

    leader2 = _spawn('leader', wal_dir)
    c2 = _client([('127.0.0.1', leader2.ports[0])])
    try:
        await c2.wait_connected(timeout=10)
        data, stat = await c2.get('/d0')
        assert bytes(data) == b'gen0-final' and stat.version == 1
        for i in range(1, 10):
            data, _ = await c2.get('/d%d' % i)
            assert bytes(data) == b'gen0-%d' % i
        await c2.create('/gen1', b'after-recovery')
    finally:
        await c2.close()

    os.kill(leader2.proc.pid, signal.SIGKILL)
    leader2.proc.wait()
    leader2.proc.stdout.close()

    leader3 = _spawn('leader', wal_dir)
    c3 = _client([('127.0.0.1', leader3.ports[0])])
    try:
        await c3.wait_connected(timeout=10)
        data, _ = await c3.get('/gen1')
        assert bytes(data) == b'after-recovery'
        data, _ = await c3.get('/d0')
        assert bytes(data) == b'gen0-final'
    finally:
        await c3.close()
        leader3.proc.kill()
        leader3.proc.wait()
        leader3.proc.stdout.close()


@pytest.mark.timeout(120)
async def test_follower_sigkill_rejoins_from_recovered_zxid(
        process_ensemble, tmp_path):
    """A follower with its own mirror WAL is SIGKILLed and respawned
    over the same dir: it recovers its tree from disk and rejoins
    with the recovered zxid as the replication catch-up base (tail
    resync) — then serves the full tree, pre- and post-outage writes
    included."""
    leader, (f1, f2) = process_ensemble
    wal_dir = str(tmp_path / 'follower-wal')
    fw = _spawn('follower', '127.0.0.1', str(leader.ports[1]),
                wal_dir)
    try:
        c = _client([('127.0.0.1', fw.ports[0])])
        try:
            await c.wait_connected(timeout=10)
            for i in range(6):
                await c.create('/pre%d' % i, b'p%d' % i)
            await c.sync('/pre0')
        finally:
            await c.close()

        os.kill(fw.proc.pid, signal.SIGKILL)
        fw.proc.wait()
        fw.proc.stdout.close()

        # the follower's WAL captured the mirrored history
        from zkstream_tpu.server.persist import recover_state
        rec = recover_state(wal_dir)
        assert rec.zxid >= 6, rec.zxid

        # writes land while it is down (via another member)
        c2 = _client([('127.0.0.1', f2.ports[0])])
        try:
            await c2.wait_connected(timeout=10)
            for i in range(3):
                await c2.create('/during%d' % i, b'd%d' % i)
        finally:
            await c2.close()

        fw = _spawn('follower', '127.0.0.1', str(leader.ports[1]),
                    wal_dir)
        c3 = _client([('127.0.0.1', fw.ports[0])])
        try:
            await c3.wait_connected(timeout=10)
            await c3.sync('/pre0')
            for i in range(6):
                data, _ = await c3.get('/pre%d' % i)
                assert bytes(data) == b'p%d' % i
            for i in range(3):
                data, _ = await c3.get('/during%d' % i)
                assert bytes(data) == b'd%d' % i
        finally:
            await c3.close()
    finally:
        if fw.proc.poll() is None:
            fw.proc.kill()
        fw.proc.wait()
        if not fw.proc.stdout.closed:
            fw.proc.stdout.close()


@pytest.mark.timeout(120)
async def test_rolling_sigkill_chaos_soak(process_ensemble):
    """Tier-4 chaos on the process tier: SIGKILL the member serving
    the session, twice in a row (the client's preference order makes
    the serving member deterministic: f1, then f2, then the leader
    member), with replacement followers joining the live ensemble
    mid-churn via snapshot bootstrap — one client session and its
    ephemeral live through every generation.  The reference's
    kill/restart cycling, compressed (multi-node.test.js:309-338)."""
    from zkstream_tpu.protocol.consts import CreateFlag

    leader, (f1, f2) = process_ensemble
    spawned: list = []
    c = _client([('127.0.0.1', f1.ports[0]),
                 ('127.0.0.1', f2.ports[0]),
                 ('127.0.0.1', leader.ports[0])],
                session_timeout=15000)
    try:
        await c.wait_connected(timeout=10)
        sid = c.session.get_session_id()
        await c.create('/soak-eph', b'alive',
                       flags=CreateFlag.EPHEMERAL)
        for gen, victim in enumerate((f1, f2)):
            # kill the member the session is being served through
            os.kill(victim.proc.pid, signal.SIGKILL)
            victim.proc.wait()
            # ...while a replacement joins the live ensemble
            nxt = _spawn('follower', '127.0.0.1', str(leader.ports[1]))
            spawned.append(nxt)
            st = await _retrying(lambda: c.stat('/soak-eph'),
                                 attempts=40)
            assert st is not None
            assert c.session.get_session_id() == sid, \
                'session lost at generation %d' % gen
            await c.set('/soak-eph', b'gen%d' % gen)
        # the replacements serve the whole churned tree to new clients
        c2 = _client([('127.0.0.1', spawned[-1].ports[0])])
        try:
            await c2.wait_connected(timeout=10)
            await c2.sync('/soak-eph')
            data, _ = await c2.get('/soak-eph')
            assert data == b'gen1'
        finally:
            await c2.close()
    finally:
        await c.close()
        for m in spawned:
            if m.proc.poll() is None:
                m.proc.kill()
            m.proc.wait()
            m.proc.stdout.close()


async def test_follower_counts_the_time_parked_in_forwarded_rpcs(
        process_ensemble):
    """A follower forwards a turn's writes through ONE blocking
    control-channel RPC on its loop's thread, from the flush its first
    queued write scheduled (``ZKServer._flush_forwards``): its tick
    ledger books the round trip as ``forward_rpc`` — subtracted from
    the flush's own ``decode_apply`` section (the mirror's catch-up
    and the replies), no longer from the decode that received the
    write — and its ``mntr`` exports the phase cumulatively: the
    window's parked time is after minus before.  The RPCs and the
    writes they carried are counted beside it.  The leader, which
    forwards nothing, has none of these series."""
    leader, (f1, _f2) = process_ensemble
    c = _client([('127.0.0.1', f1.ports[0])])
    try:
        await c.wait_connected(timeout=10)
        await c.create('/fw', b'0')
        before = await mntr_rows(f1.ports[0])
        for i in range(25):
            await c.set('/fw', b'v%d' % i)
        after = await mntr_rows(f1.ports[0])
        lead = await mntr_rows(leader.ports[0])
    finally:
        await c.close()
    count = 'zk_tick_phase_ms_count{phase="forward_rpc"}'
    total = 'zk_tick_phase_ms_sum{phase="forward_rpc"}'
    inf = 'zk_tick_phase_ms_bucket{phase="forward_rpc",le="+Inf"}'
    # the ledger books per busy tick, and a tick may hold several
    # flushes: at least one tick, at most one per write (plus pings)
    ticks = int(after[count]) - int(before[count])
    assert 1 <= ticks <= 30
    assert int(after[inf]) - int(before[inf]) == ticks
    parked_ms = float(after[total]) - float(before[total])
    assert parked_ms > 0
    # one session with one write outstanding: every batch is a batch
    # of one, the same code
    assert int(after['zk_forward_writes']) \
        - int(before['zk_forward_writes']) == 25
    assert int(after['zk_forward_rpcs']) \
        - int(before['zk_forward_rpcs']) == 25
    # the parked time is not inside decode_apply: the follower's
    # own decode, catch-up and replies of 25 small writes are far
    # under the round trips it waited for
    own = 'zk_tick_phase_ms_sum{phase="decode_apply"}'
    assert float(after[own]) - float(before[own]) < parked_ms * 5
    assert float(after[own]) > 0
    assert count not in lead and 'zk_tick_count' in lead
    assert 'zk_forward_rpcs' not in lead
    assert 'zk_forward_writes' not in lead


async def test_pipelined_connection_through_a_follower_answers_in_order(
        process_ensemble):
    """``set, get, set, sync, get, closeSession`` in ONE chunk through
    a follower: the first set joins the turn's batch and everything
    behind it waits on the connection, in arrival order, until its
    reply is written — so the replies come back in xid order, each
    ``get`` sees the ``set`` before it, and nothing is left
    outstanding."""
    _leader, (f1, _f2) = process_ensemble
    c = _client([('127.0.0.1', f1.ports[0])])
    try:
        await c.wait_connected(timeout=10)
        await c.create('/pl', b'zero')
    finally:
        await c.close()
    raw = RawClient()
    await raw.connect(types.SimpleNamespace(port=f1.ports[0]),
                      timeout=12000)
    reqs = [
        {'xid': 1, 'opcode': 'SET_DATA', 'path': '/pl', 'data': b'one',
         'version': -1},
        {'xid': 2, 'opcode': 'GET_DATA', 'path': '/pl', 'watch': False},
        {'xid': 3, 'opcode': 'SET_DATA', 'path': '/pl', 'data': b'two',
         'version': 1},
        {'xid': 4, 'opcode': 'SYNC', 'path': '/pl'},
        {'xid': 5, 'opcode': 'GET_DATA', 'path': '/pl', 'watch': False},
        {'xid': 6, 'opcode': 'SET_DATA', 'path': '/pl', 'data': b'no',
         'version': 7},
        {'xid': 7, 'opcode': 'CLOSE_SESSION'},
    ]
    try:
        raw.writer.write(b''.join(raw.codec.encode(r) for r in reqs))
        got = await raw.recv(len(reqs), timeout=10)
    finally:
        raw.close()
    assert [p['xid'] for p in got] == [1, 2, 3, 4, 5, 6, 7]
    assert [p['err'] for p in got] == [
        'OK', 'OK', 'OK', 'OK', 'OK', 'BAD_VERSION', 'OK']
    assert got[0]['stat'].version == 1 and got[2]['stat'].version == 2
    assert got[1]['data'] == b'one' and got[1]['stat'].version == 1
    assert got[4]['data'] == b'two' and got[4]['stat'].version == 2
    # reply zxids never go back on the connection
    zxids = [p['zxid'] for p in got]
    assert zxids == sorted(zxids)
    rows = await mntr_rows(f1.ports[0])
    assert int(rows['zk_outstanding_requests']) == 0


async def test_concurrent_writers_through_one_follower_share_rpcs(
        process_ensemble):
    """16 closed-loop writers on one follower: the writes one turn of
    its loop receives leave in ONE control-channel RPC, so the
    follower makes far fewer round trips (and ``forward_rpc`` ticks)
    than writes, every write is answered with its own result, and the
    other members hold them all."""
    leader, (f1, f2) = process_ensemble
    n, rounds = 16, 20
    clients = [_client([('127.0.0.1', f1.ports[0])]) for _ in range(n)]
    try:
        await asyncio.gather(*(c.wait_connected(timeout=10)
                               for c in clients))
        await asyncio.gather(*(c.create('/w%d' % i, b'')
                               for i, c in enumerate(clients)))
        before = await mntr_rows(f1.ports[0])

        async def writer(i, c):
            for r in range(rounds):
                stat = await c.set('/w%d' % i, b'%d.%d' % (i, r))
                assert stat.version == r + 1

        await asyncio.gather(*(writer(i, c)
                               for i, c in enumerate(clients)))
        after = await mntr_rows(f1.ports[0])
    finally:
        await asyncio.gather(*(c.close() for c in clients))

    def delta(key):
        return float(after[key]) - float(before.get(key, 0))

    writes = delta('zk_forward_writes')
    rpcs = delta('zk_forward_rpcs')
    assert writes == n * rounds
    assert 1 <= rpcs <= writes / 2, (writes, rpcs)
    assert delta('zk_tick_phase_ms_count{phase="forward_rpc"}') <= rpcs
    r = _client([('127.0.0.1', f2.ports[0])])
    try:
        await r.wait_connected(timeout=10)
        await r.sync('/')
        for i in range(n):
            data, stat = await r.get('/w%d' % i)
            assert data == b'%d.%d' % (i, rounds - 1)
            assert stat.version == rounds
    finally:
        await r.close()


async def test_leader_books_forwarded_writes_as_control_and_repl_ack(
        process_ensemble):
    """The leader of an OS-process ensemble serves forwarded writes on
    its control channel and takes its followers' acks on their event
    streams: both are ledger phases in its ``mntr`` (``control``,
    ``repl_ack``) with the commits' ``repl_push`` nested — a member
    that forwards has neither — and every member exports its process's
    cumulative CPU (``zk_process_cpu_ms``), so phases over CPU can be
    read from inside."""
    leader, (f1, _f2) = process_ensemble
    c = _client([('127.0.0.1', f1.ports[0])])
    try:
        await c.wait_connected(timeout=10)
        await c.create('/cr', b'0')
        before = await mntr_rows(leader.ports[0])
        for i in range(40):
            await c.set('/cr', b'v%d' % i)
        after = await mntr_rows(leader.ports[0])
        follower = await mntr_rows(f1.ports[0])
    finally:
        await c.close()

    def delta(key):
        return float(after[key]) - float(before.get(key, 0))

    for phase in ('control', 'repl_ack', 'repl_push'):
        assert delta('zk_tick_phase_ms_sum{phase="%s"}' % phase) > 0
        assert delta('zk_tick_phase_ms_count{phase="%s"}' % phase) >= 1
    # 40 serial round trips, each parked ~a quorum wait: the phase is
    # the leader's own work, a small part of the elapsed time
    assert delta('zk_tick_phase_ms_sum{phase="control"}') \
        < 0.5 * delta('zk_uptime_ms')
    assert delta('zk_process_cpu_ms') > 0
    assert float(follower['zk_process_cpu_ms']) > 0
    assert 'zk_tick_phase_ms_count{phase="control"}' not in follower
    assert 'zk_tick_phase_ms_count{phase="repl_ack"}' not in follower
    # the leader's phases over its CPU: what the ledger names
    phases = sum(delta(k) for k in after
                 if k.startswith('zk_tick_phase_ms_sum{'))
    assert 0 < phases <= 1.5 * delta('zk_process_cpu_ms') + 5


async def test_leader_ships_a_forwarded_batch_as_one_push_a_mirror(
        process_ensemble):
    """16 closed-loop writers on one follower arrive at the leader in
    batches, and each batch's commits leave for the mirrors as ONE
    push a mirror (server/replication.py ``_ship``): the leader's
    ``mntr`` counts the push messages and the entries in them,
    cumulatively, beside their bytes — entries over messages is the
    group a push carried, well over one here and exactly what the
    followers' ``zk_forward_writes`` over ``zk_forward_rpcs`` promise
    — and a member that forwards has neither row."""
    leader, (f1, f2) = process_ensemble
    n, rounds = 16, 20
    clients = [_client([('127.0.0.1', f1.ports[0])]) for _ in range(n)]
    try:
        await asyncio.gather(*(c.wait_connected(timeout=10)
                               for c in clients))
        await asyncio.gather(*(c.create('/gp%d' % i, b'')
                               for i, c in enumerate(clients)))
        before = await mntr_rows(leader.ports[0])
        fwd_before = await mntr_rows(f1.ports[0])

        async def writer(i, c):
            for r in range(rounds):
                await c.set('/gp%d' % i, b'%d.%d' % (i, r))

        await asyncio.gather(*(writer(i, c)
                               for i, c in enumerate(clients)))
        after = await mntr_rows(leader.ports[0])
        fwd_after = await mntr_rows(f1.ports[0])
    finally:
        await asyncio.gather(*(c.close() for c in clients))

    def delta(key, rows=None):
        a, b = rows or (after, before)
        return float(a[key]) - float(b[key])

    pushes = delta('zk_repl_pushes')
    commits = delta('zk_repl_pushed_commits')
    # every write went to both mirrors (a ping or a scrape's session
    # commits nothing in between)
    assert commits == 2 * n * rounds
    assert delta('zk_repl_pushed_bytes') > commits * 20
    rpcs = delta('zk_forward_rpcs', (fwd_after, fwd_before))
    assert 1 <= rpcs <= n * rounds / 2
    # one push a mirror a batch: no more messages than two an RPC
    assert 2 <= pushes <= 2 * rpcs, (pushes, rpcs)
    assert commits / pushes >= 2
    # ... and the acks fell with them: one a message that grew a mirror
    assert delta('zk_tick_phase_ms_count{phase="repl_push"}') <= rpcs
    for key in ('zk_repl_pushes', 'zk_repl_pushed_commits',
                'zk_repl_pushed_bytes'):
        assert delta(key) > 0
        assert key not in fwd_after
    f2_rows = await mntr_rows(f2.ports[0])
    assert 'zk_repl_pushes' not in f2_rows
    assert 'zk_repl_pushed_commits' not in f2_rows


async def test_leader_sigkill_with_batches_in_flight_loses_no_ack(
        process_ensemble):
    """SIGKILL the leader under 16 closed-loop writers on one
    follower: every write in flight settles — acked, or the typed
    outcome-unknown CONNECTION_LOSS, never a hang and never a torn
    connection — and no acked write is lost: the follower's mirror
    held each one before its ack left."""
    from zkstream_tpu.protocol.errors import ZKError

    leader, (f1, _f2) = process_ensemble
    n = 16
    clients = [_client([('127.0.0.1', f1.ports[0])]) for _ in range(n)]
    acked = [0] * n
    errors: list = []
    try:
        await asyncio.gather(*(c.wait_connected(timeout=10)
                               for c in clients))
        await asyncio.gather(*(c.create('/k%d' % i, b'')
                               for i, c in enumerate(clients)))

        async def writer(i, c):
            try:
                while True:
                    stat = await c.set('/k%d' % i, b'x', deadline=8000)
                    acked[i] = stat.version
            except ZKError as e:
                errors.append(e.code)

        tasks = [asyncio.ensure_future(writer(i, c))
                 for i, c in enumerate(clients)]
        await asyncio.sleep(0.3)
        os.kill(leader.proc.pid, signal.SIGKILL)
        await asyncio.wait_for(asyncio.gather(*tasks), 15)
        assert min(acked) > 0
        assert errors == ['CONNECTION_LOSS'] * n
        # the follower keeps serving its mirror: every acked version
        # is there (a later, unacked one may be too)
        for i, c in enumerate(clients):
            _data, stat = await c.get('/k%d' % i)
            assert stat.version >= acked[i]
    finally:
        await asyncio.gather(*(c.close() for c in clients),
                             return_exceptions=True)


async def _scrape_trce(port: int) -> dict:
    import json

    reader, writer = await asyncio.open_connection('127.0.0.1', port)
    try:
        writer.write(b'trce')
        await writer.drain()
        return json.loads(await asyncio.wait_for(reader.read(), 5))
    finally:
        writer.close()


async def test_trce_scrape_merges_cross_process_timeline(
        process_ensemble):
    """Acceptance (OS-process tier): a watched write through a
    follower process leaves a zxid-keyed span chain spanning real
    processes — client submit, leader commit + replication push,
    follower apply, fan-out delivery — reassembled by scraping every
    member's `trce` admin word over raw TCP and merging by zxid."""
    from zkstream_tpu.utils.trace import (
        format_timeline,
        merge_timelines,
    )

    leader, (f1, f2) = process_ensemble
    c = _client([('127.0.0.1', f1.ports[0])])
    try:
        await c.wait_connected(timeout=10)
        await c.create('/xproc', b'v0')

        fires: list = []
        fired = asyncio.get_running_loop().create_future()

        def on_change(*a):
            fires.append(a)
            if len(fires) >= 2 and not fired.done():
                fired.set_result(None)
        c.watcher('/xproc').on('dataChanged', on_change)
        await asyncio.sleep(0.3)      # armed; arm-time emit delivered
        stat = await c.set('/xproc', b'v1')
        zxid = stat.mzxid
        await asyncio.wait_for(fired, 10)
        await c.sync('/xproc')

        rings = {'client': c.trace.dump()}
        for port in (leader.ports[0], f1.ports[0], f2.ports[0]):
            dump = await _scrape_trce(port)
            assert dump['trace_schema'] == 3
            rings['member:%s' % (dump['member'],)] = dump['spans']
        merged = merge_timelines(rings)
        sel = [(e['source'], e['op']) for e in merged
               if e['zxid'] == zxid]
        assert ('client', 'SET_DATA') in sel, sel
        assert ('member:leader', 'COMMIT') in sel, sel
        assert any(src == 'member:leader' and op == 'REPL_PUSH'
                   for src, op in sel), sel
        appliers = {src for src, op in sel
                    if op == 'APPLY'
                    and src.startswith('member:follower-')}
        assert len(appliers) == 2, sel   # both follower processes
        assert any(op == 'FANOUT'
                   and src.startswith('member:follower-')
                   for src, op in sel), sel
        # causal order within the zxid group: submit before commit
        # before push before any apply
        ops = [op for _src, op in sel]
        assert ops.index('SET_DATA') < ops.index('COMMIT') \
            < ops.index('REPL_PUSH') < ops.index('APPLY')
        assert format_timeline(merged)
    finally:
        await c.close()


@pytest.mark.timeout(240)
async def test_election_kill_loop_and_full_sigkill_generations():
    """The election plane's OS-process acceptance, via the exact
    seeded driver `zkstream_tpu chaos --tier process --seed N` runs
    (server/election.py run_process_schedule): three symmetric peer
    members; the elected leader is SIGKILLed twice and each survivor
    set elects a successor at a strictly higher epoch with no
    operator; then the WHOLE ensemble is SIGKILLed twice and each
    generation elects from recovered WALs alone — every acked write
    intact, invariant 7 (one leader per epoch, epochs monotone)
    checked over the recorded history."""
    from zkstream_tpu.server.election import run_process_schedule

    r = await run_process_schedule(seed=5, ops=3, elections=2,
                                   generations=2)
    assert r.ok, r.violations
    # initial + 2 forced + 2 full-ensemble generations
    assert r.elections >= 5, r.history
    epochs = [rec['epoch'] for rec in r.history
              if rec['kind'] == 'election']
    assert epochs == sorted(epochs) and len(set(epochs)) == len(epochs)
    assert epochs[-1] >= 5
    assert r.acked > 0


@pytest.mark.timeout(240)
async def test_process_tier_cached_clients_survive_leader_kills():
    """The cache plane's OS-process slice (`chaos --tier process
    --cached`): the seeded election schedule's clients run with the
    watch-backed client cache on (cache='/') — leader SIGKILLs force
    the cache through connection loss, SET_WATCHES2 replay and
    resync, and every acked write must still read back correctly
    through the (possibly cached) read path; invariant 7 and the
    final read-back hold as in the uncached schedule."""
    from zkstream_tpu.server.election import run_process_schedule

    r = await run_process_schedule(seed=5, ops=3, elections=1,
                                   generations=1, cached=True)
    assert r.ok, r.violations
    assert r.elections >= 2, r.history
    assert r.acked > 0


@pytest.mark.timeout(120)
async def test_member_worker_role_via_test_worker():
    """The tests/ worker's `member` role delegates to the package
    worker: one single-member 'ensemble' elects itself leader from an
    empty WAL and serves clients."""
    import tempfile

    from zkstream_tpu.server.election import allocate_ports

    with tempfile.TemporaryDirectory() as wal_dir:
        cport, eport = allocate_ports(2)
        m = _spawn('member', '0', wal_dir, str(cport), str(eport))
        try:
            c = _client([('127.0.0.1', m.ports[0])])
            try:
                await c.wait_connected(timeout=15)
                await c.create('/solo', b'x')
                data, _ = await c.get('/solo')
                assert data == b'x'
            finally:
                await c.close()
        finally:
            m.proc.kill()
            m.proc.wait()
            m.proc.stdout.close()
