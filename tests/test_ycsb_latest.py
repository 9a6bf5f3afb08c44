"""YCSB core workload D ("read latest") through YCSB's ZooKeeper
binding (the deployment ``ycsb3_latest`` at toy size): an in-process
3-voter ensemble, 24 sessions through ONE ``FleetIngest``, 512 records
of 1,121 B under ``/benchmark`` and room for the inserts of a run, every
session sending 95% reads of the NEWEST records and 5% creates in one
loop, driven by the benchmark's own engine
(``benchmark/engines/ycsb_latest.py``) on a stand-in for the harness's
fleet.

Held against the benchmark's plain reference of this deployment
(``benchmark/reference_ycsb_latest.py``, which imports nothing of the
program): every ``getData`` reply, every ``NO_NODE``, every
acknowledgement, the final tree from another member.  The key chooser is
held to YCSB's own (``SkewedLatestGenerator`` over an
``AcknowledgedCounterGenerator``), the reference to catching what it
exists to catch, and the members' two new rows (``zk_apply_lag_ms``,
``zk_read_no_node``) to moving when a follower trails."""

from __future__ import annotations

import importlib.util
import os
import random
import sys

import pytest
from test_ycsb import Cell as _Cell

from zkstream_tpu import Client
from zkstream_tpu.server import ZKEnsemble

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_ycsb_latest  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    'bench_engines_ycsb_latest', os.path.join(BENCH, 'engines',
                                              'ycsb_latest.py'))
ycsb_latest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ycsb_latest)

N = 24
RECORDS = 512
ROOM = 4096
CONFIG = {'sessions': N,
          'tree': {'parent': '/benchmark', 'recordcount': RECORDS,
                   'insert_room': ROOM, 'fieldcount': 10,
                   'fieldlength': 100}}
MIX = {'readproportion': 0.95, 'insertproportion': 0.05,
       'requestdistribution': 'latest', 'zipfian_constant': 0.99}


class Cell(_Cell):
    """tests/test_ycsb.py's cell — an in-process ``ZKEnsemble(3)``, one
    host-placed ``FleetIngest``, a stand-in for the harness's fleet —
    around workload D's engine, deployment and mix."""

    Engine, config, mix = ycsb_latest.Engine, CONFIG, MIX


def mntr(srv) -> dict:
    return {k: v for k, v in srv.monitor_stats()}


@pytest.mark.parametrize('seed', [5, 2 ** 31 + 40])
async def test_workload_d_against_the_plain_reference(event_loop, seed):
    cell = await Cell().start(seed)
    try:
        buckets = set(cell.ingest.buckets)
        res = await cell.run(1.5)
        assert not res['violations'], res['violations']
        assert res['failed'] == 0 and res['counters']['errors'] == {}
        assert res['attempted'] > 200
        assert abs(res['acked'] - res['attempted']) <= N
        for line in res['compared'][:len(reference_ycsb_latest.KINDS)]:
            assert line.endswith(' 0 limit 0'), line
        c = res['counters']
        # reads and inserts in the mix's proportion; every getData a
        # read sample, every create an insert sample
        assert 0.02 < c['inserts_acked'] / res['acked'] < 0.09
        assert c['changes_acked'] == c['inserts_acked']
        assert len(res['samples']['read']) == c['reads']
        assert abs(len(res['samples']['insert']) - c['inserts_acked']) <= N
        assert c['reads'] + len(res['samples']['insert']) \
            == res['attempted']
        # the frontier moved with the acknowledgements, and the reads
        # with it: most land on records this window created
        chk, eng = cell.engine.checker, cell.engine
        assert c['frontier_advance'] >= c['inserts_acked'] - N > 10
        assert eng.counter.last == eng.counter.counter - 1  # all acked
        assert c['records_inserted'] == eng.counter.counter - RECORDS
        assert c['reads_on_window_records_share'] > 30.0
        assert abs(c['newest_share'] - c['newest_closed_form']) < 3.0
        # every record a create was sent for exists and was read back,
        # with the loaded ones and the names past the counter
        inserted = range(RECORDS, eng.counter.counter)
        assert all(chk.state[k] == reference_ycsb_latest.ACKED
                   for k in inserted)
        assert all(chk.czxid[k] > 0 for k in inserted)
        assert chk.checked >= res['attempted'] + eng.counter.counter \
            + ycsb_latest.READBACK_BEYOND
        # an in-process follower applies at the commit: nothing was
        # read before it was visible
        assert c['reads_not_yet_visible'] == chk.not_yet_visible == 0
        # every tick went through the one program that was warmed
        assert set(cell.ingest.buckets) == buckets
        assert cell.ingest.ticks_scalar == 0
        # the parent's children grew by the inserts, on every member
        for s in cell.ens.servers:
            assert len(s.store.nodes['/benchmark'].children) \
                == eng.counter.counter
    finally:
        await cell.stop()


async def test_a_target_paces_reads_and_inserts_from_one_schedule(
        event_loop):
    """YCSB's ``-target`` on workload D, as the cell runs it: 24
    sessions at 480 operations a second are one operation every 50 ms
    each, a read or an insert alike.  Held on the engine's own record,
    not on how many this machine completed: an operation is never sent
    before it was due, every one — a ``create`` too — is timed from
    when it was DUE, a read's key is drawn when it is SENT (among the
    records the frontier counts then), and the paced run is as sound
    against the plain reference as the closed loop's."""
    cell = await Cell().start(13, dict(MIX, target_ops_per_s=480))
    try:
        eng = cell.engine
        assert eng.interval == pytest.approx(0.05)
        res = await cell.run(1.5)
        assert not res['violations'], res['violations']
        assert res['failed'] == 0 and res['counters']['errors'] == {}
        assert abs(res['acked'] - res['attempted']) <= N
        for line in res['compared'][:len(reference_ycsb_latest.KINDS)]:
            assert line.endswith(' 0 limit 0'), line
        late = sorted(eng.late_ms)
        c = res['counters']
        # one lateness an operation sent in the window, none negative
        assert len(late) == res['attempted'] > 5 * N
        assert late[0] >= 0.0
        # no session ran ahead of its schedule: at most one operation
        # an interval a session, and the one the window cut
        assert res['attempted'] <= N * (1.5 / eng.interval + 2)
        # timed from when it was due: its lateness is IN its latency
        timed = sorted(res['samples']['read'] + res['samples']['insert'])
        assert abs(len(timed) - len(late)) <= N
        assert all(t > l for t, l in zip(timed, late))
        assert c['gen_late_ms_p50'] == pytest.approx(
            late[int(0.5 * (len(late) - 1))], abs=1.0)
        assert c['gen_late_ms_p95'] == pytest.approx(
            late[int(0.95 * (len(late) - 1))], abs=1.0)
        # the mix and the frontier are the closed loop's
        assert c['inserts_acked'] > 0
        assert c['frontier_advance'] >= c['inserts_acked'] - N
        assert eng.counter.last == eng.counter.counter - 1
    finally:
        await cell.stop()


async def test_a_trailing_follower_answers_no_node_and_both_rows_move(
        event_loop):
    """A follower whose apply is held 60 ms behind the commit: readers
    attached to it are told ``NO_NODE`` for the newest records — an
    answer ZooKeeper allows, counted, no violation — the member counts
    them (``zk_read_no_node``) and its apply-lag histogram
    (``zk_apply_lag_ms``) holds the commits that waited at 60 ms or
    more, while the member that applies at the commit has none."""
    cell = await Cell().start(21)
    try:
        before = [mntr(s) for s in cell.ens.servers]
        assert all(r['zk_read_no_node'] == 0 for r in before)
        assert 'zk_apply_lag_ms_count' not in before[0]     # the leader
        cell.ens.set_lag(2, 0.06)
        res = await cell.run(1.2)
        cell.ens.set_lag(2, 0)
        assert not res['violations'], res['violations']
        assert res['failed'] == 0
        c = res['counters']
        assert c['reads_not_yet_visible'] > 0
        assert cell.engine.checker.not_yet_visible \
            >= c['reads_not_yet_visible']
        after = [mntr(s) for s in cell.ens.servers]
        missed = [int(a['zk_read_no_node']) for a in after]
        # only the held member's readers were ever told absent (the
        # read-back asks member 0 and 1 for names nobody created)
        assert missed[2] >= c['reads_not_yet_visible'] > 0
        assert missed[2] > missed[1]
        held, prompt, was = after[2], after[1], before[2]
        n_held = int(held['zk_apply_lag_ms_count'])
        assert n_held - int(was['zk_apply_lag_ms_count']) \
            >= c['inserts_acked'] > 0

        def over_50(rows):
            return int(rows['zk_apply_lag_ms_count']) \
                - int(rows['zk_apply_lag_ms_bucket{le="50"}'])
        # what came through the OTHER members trailed on the held one
        # until its 60 ms were over or a create through the held member
        # itself caught its store up (with 8 of the 24 sessions there,
        # every few ms) ...
        waited = over_50(held) - over_50(was)
        trailed = float(held['zk_apply_lag_ms_sum']) \
            - float(was['zk_apply_lag_ms_sum'])
        assert waited >= 1 and trailed >= 60.0 * waited
        # ... and nothing did on the member that applies at the commit
        # (one, for a stall of this process between a stamp and its apply)
        assert over_50(prompt) <= 1
        assert int(prompt['zk_apply_lag_ms_count']) >= n_held
        assert trailed > 5.0 * (float(prompt['zk_apply_lag_ms_sum'])
                                - float(before[1]['zk_apply_lag_ms_sum']))
    finally:
        await cell.stop()


async def test_a_create_is_in_the_series_and_spans_a_get_is(event_loop):
    """``create`` goes through the same ``_start_op`` / ``_await_op``
    as ``get``: the per-op latency series labelled with its opcode,
    and inside a profiler session the ``client.submit`` /
    ``client.resume`` totals and the four stage waits."""
    from zkstream_tpu.utils import trace

    ens = await ZKEnsemble(3).start()
    c = Client(servers=[('127.0.0.1', ens.servers[1].port)],
               shuffle_backends=False, max_spares=0)
    c.start()
    armed = trace._is_enabled
    try:
        await c.wait_connected(timeout=10)
        trace._is_enabled = lambda: True
        trace.host_ring.reset()
        await c.create('/made', b'x' * 1121)
        data, _stat = await c.get('/made')
        assert data == b'x' * 1121
        totals = trace.host_ring.totals
        for name in ('client.submit', 'client.resume', 'client.wire_wait',
                     'client.cork_wait', 'client.tick_wait',
                     'client.wake_wait'):
            assert totals[name][0] == 2, (name, totals.get(name))
        assert totals['client.prepare'][0] == 1     # the read's alone
        hist = c._op_latency
        assert hist.count({'op': 'CREATE'}) == 1
        assert hist.count({'op': 'GET_DATA'}) == 1
    finally:
        trace._is_enabled = armed
        trace.host_ring.reset()
        await c.close()
        await ens.stop()


def test_the_mix_the_engine_does_not_send_is_refused():
    class F:
        config, seed, deadline_ms = CONFIG, 1, 1000
        params = dict(MIX, requestdistribution='zipfian')
    with pytest.raises(ValueError):
        ycsb_latest.Engine(F())

    class G(F):
        params = dict(MIX, insertproportion=0.10)
    with pytest.raises(ValueError):
        ycsb_latest.Engine(G())


# -- the key chooser -----------------------------------------------------

def _zeta(n: int, theta: float = 0.99) -> float:
    return sum(i ** -theta for i in range(1, n + 1))


def test_key_chooser_shares_against_the_closed_form():
    """``frontier - Zipfian(0.99)`` over 65,535 items, unscrambled:
    rank 0 — the newest acknowledged record — takes 1 / zeta = 8.13%
    of the draws and rank 1 0.5 ** 0.99 / zeta = 4.09%, exactly; Gray's
    closed form puts the newest 10 1.2 points over the exact zeta's
    24.0%, the newest 400 0.9 over its 54.9%, the newest 5,000 0.5 over
    its 76.9%.  Tolerance 0.4 points on 200,000 draws."""
    n = 65535
    z = ycsb_latest.LatestZipfian(n, 0.99)
    zeta = _zeta(n)
    assert abs(zeta - 12.3052) < 1e-3 and abs(z.zetan - zeta) < 1e-9
    rng = random.Random(50)
    draws = [z.rank(rng.random(), n) for _ in range(200_000)]
    assert min(draws) == 0 and max(draws) <= n
    for top, want in ((1, 1 / zeta), (2, (1 + 0.5 ** 0.99) / zeta),
                      (10, 0.2522), (400, 0.5581), (5000, 0.7742)):
        got = sum(1 for r in draws if r < top) / len(draws)
        assert abs(got - want) < 0.004, (top, got, want)
    for top, exact in ((10, 0.2402), (400, 0.5487), (5000, 0.7694)):
        assert abs(_zeta(top) / zeta - exact) < 1e-3
    assert z.rank(0.0, n) == 0 and z.rank(1.0 - 2 ** -53, n) == n
    # the engine's key: back from the frontier, never past it, never
    # hashed — the hottest key IS the frontier
    keys = [70000 - z.rank(rng.random(), 70000) for _ in range(50_000)]
    assert max(keys) == 70000 and min(keys) >= 0
    assert abs(keys.count(70000) / len(keys) - 1 / _zeta(70000)) < 0.004


def test_zeta_extended_with_the_frontier_equals_zeta_computed_whole():
    """``ZipfianGenerator.nextLong(itemcount)`` with a larger count
    adds the new items' terms to ``zetan``: in steps as the frontier
    advances, or at once, it is the zeta of that many items."""
    z = ycsb_latest.LatestZipfian(511, 0.99)
    assert abs(z.zetan - _zeta(511)) < 1e-12 and z.countforzeta == 511
    rng = random.Random(3)
    count = 511
    for _ in range(200):
        count += rng.randrange(0, 9)
        r = z.rank(rng.random(), count)
        assert 0 <= r <= count and z.countforzeta == count
    assert abs(z.zetan - _zeta(count)) < 1e-9
    whole = ycsb_latest.LatestZipfian(count, 0.99)
    assert abs(whole.zetan - z.zetan) < 1e-9
    # a smaller count (no draw goes back: the frontier only advances)
    # leaves it alone, as YCSB without allowitemcountdecrease
    z.rank(0.5, 600)
    assert z.countforzeta == count
    # eta keeps the ORIGINAL item count (YCSB's slip): the two differ
    assert whole.eta != z.eta


def test_the_frontier_holds_behind_an_unacknowledged_insert():
    """``AcknowledgedCounterGenerator``: ``lastValue()`` is the highest
    keynum with every lower one acknowledged — it holds behind the one
    insert still out, and jumps over all that were waiting when it
    lands."""
    c = ycsb_latest.AcknowledgedCounter(512)
    assert c.last == 511            # the newest LOADED record
    keys = [c.next() for _ in range(6)]
    assert keys == [512, 513, 514, 515, 516, 517] and c.counter == 518
    for k in (513, 514, 516):
        c.acknowledge(k)
        assert c.last == 511        # 512 is still out
    c.acknowledge(512)
    assert c.last == 514            # ... and jumps to the next gap
    c.acknowledge(517)
    assert c.last == 514
    c.acknowledge(515)
    assert c.last == 517 and not c._acked
    # a failed insert is never acknowledged: the frontier stays behind
    # it whatever lands after
    lost, nxt = c.next(), c.next()
    c.acknowledge(nxt)
    assert (lost, c.last) == (518, 517)


# -- the reference catches what it is there to catch --------------------

def _made(key=RECORDS + 3, session=1, member=1, czxid=900, read=True):
    """A checker in which ``session`` (attached to ``member``) created
    ``key`` and was acknowledged, and (``read``) read it back once."""
    chk = reference_ycsb_latest.LatestChecker(7, RECORDS, 64)
    data = chk.create_sent(session, key)
    assert data == chk.initial(key) and len(data) == 1121
    chk.create_acked(session, member, key)
    if read:
        chk.read(session, member, key, data, 0, len(data), czxid, czxid,
                 1.0)
    assert not chk.bad.first and chk.exists(key)
    return chk, key, data


def test_the_reference_catches_a_flipped_byte_in_a_created_record():
    chk, key, data = _made()
    size = len(data)
    for at in (0, 11, size // 2, size - 1):
        bad = data[:at] + bytes([data[at] ^ 1]) + data[at + 1:]
        chk.read(2, 0, key, bad, 0, size, 900, 900, 2.0)
    assert chk.bad.by_kind == {'payload': 4}
    chk.read(2, 0, key, data[:-1], 0, size - 1, 900, 900, 2.0)  # short
    chk.read(2, 0, key, data, 0, size + 1, 900, 900, 2.0)   # wrong Stat
    assert chk.bad.by_kind == {'payload': 6}
    # the right bytes under a Stat that is not a created record's
    chk.read(2, 0, key, data, 1, size, 900, 901, 2.0)       # rewritten?
    chk.read(2, 0, key, data, 0, size, 905, 905, 2.0)       # two czxids
    assert chk.bad.by_kind == {'payload': 6, 'stat': 2}
    # a loaded record is held to its bytes too
    chk.read(2, 0, 5, chk.initial(6), 0, size, 40, 40, 2.0)
    assert chk.bad.by_kind['payload'] == 7


def test_the_reference_catches_no_node_for_a_record_the_session_read():
    chk, key, data = _made()
    chk.read(2, 0, key, data, 0, len(data), 900, 900, 2.0)
    chk.miss(2, 0, key, 3.0, 3.1)       # it has read it: never absent
    assert chk.bad.by_kind == {'stale-miss': 1}
    assert 'read before' in chk.bad.first[0]
    chk.miss(1, 1, key, 3.0, 3.1)       # ... nor for its creator
    assert chk.bad.by_kind == {'stale-miss': 2}
    chk.miss(3, 2, 17, 3.0, 3.1)        # ... nor ever a loaded record
    assert chk.bad.by_kind == {'stale-miss': 3}
    assert chk.not_yet_visible == 0


def test_the_reference_catches_no_node_on_a_member_that_showed_it():
    """Session 5 on member 2 READ the record at t = 2.0: a read another
    session SENT to member 2 after that finds it, or the member's store
    went back.  A read sent before that reply was received is no
    proof."""
    chk, key, data = _made(read=False)
    chk.read(5, 2, key, data, 0, len(data), 900, 900, 2.0)
    chk.miss(8, 2, key, 1.9, 2.3)       # sent before: allowed
    assert not chk.bad.first and chk.not_yet_visible == 1
    chk.miss(8, 2, key, 2.1, 2.3)       # sent after: the store went back
    assert chk.bad.by_kind == {'stale-miss': 1}
    assert 'member 2 had shown another session' in chk.bad.first[0]
    # the creator's acknowledgement through member 1 proves nothing for
    # member 1's other sessions (a follower acknowledges before its
    # replica applies): absent on member 0 or for another session of
    # member 1 is an answer
    chk.miss(9, 0, key, 2.5, 2.6)
    chk.miss(4, 1, key, 2.5, 2.6)
    assert chk.bad.count == 1 and chk.not_yet_visible == 3


def test_the_reference_passes_no_node_from_a_member_before_its_apply():
    """Session 2 (member 0) is told a record acknowledged through
    member 1 is absent, then finds it: allowed, counted.  But a session
    that had ALREADY been shown a zxid at or above the record's czxid
    was on a member that had applied it: judged when the czxid is
    learnt."""
    chk = reference_ycsb_latest.LatestChecker(7, RECORDS, 64)
    key, other = RECORDS, RECORDS + 1
    data = chk.create_sent(1, key)
    chk.miss(2, 0, key, 0.5, 0.6)       # the create is still out
    chk.create_acked(1, 1, key)
    chk.miss(2, 0, key, 1.0, 1.1)       # acknowledged, not applied there
    chk.read(2, 0, key, data, 0, len(data), 700, 700, 1.5)
    assert not chk.bad.first and chk.not_yet_visible == 2
    # session 3 saw zxid 800 (another record's Stat), then is told
    # ``other`` is absent; ``other`` turns out to have czxid 750
    made = chk.create_sent(4, other)
    chk.create_acked(4, 2, other)
    chk.read(3, 0, 9, chk.initial(9), 0, len(data), 800, 800, 2.0)
    chk.miss(3, 0, other, 2.1, 2.2)
    assert not chk.bad.first and chk.not_yet_visible == 3   # kept
    chk.miss(6, 0, other, 2.1, 2.2)     # a session that saw nothing
    chk.read(7, 1, other, made, 0, len(made), 750, 750, 2.5)
    assert chk.bad.by_kind == {'stale-miss': 1}
    assert 'czxid 750' in chk.bad.first[0]
    assert chk.not_yet_visible == 3     # session 6's stands
    # ... and with the czxid known, at once
    chk.miss(3, 0, other, 3.0, 3.1)
    assert chk.bad.by_kind == {'stale-miss': 2}
    chk.settle()
    assert not chk.pending


def test_the_reference_catches_a_lost_create_at_the_read_back():
    chk, key, data = _made()
    chk.final(key, None, 0, 0, 0, 'member 2')
    assert chk.bad.by_kind == {'lost-create': 1}
    chk.final(key, data[:-1] + b'?', 0, len(data), 900, 'member 2')
    chk.final(3, None, 0, 0, 0, 'member 0')         # a loaded record
    assert chk.bad.by_kind == {'lost-create': 1, 'final-tree': 2}
    chk.final(key, data, 0, len(data), 900, 'member 2')
    chk.final(3, chk.initial(3), 0, len(data), 44, 'member 0')
    assert chk.bad.count == 3
    # a create cut by the drain may be either
    cut = RECORDS + 9
    sent = chk.create_sent(2, cut)
    chk.create_unknown(cut)
    chk.final(cut, None, 0, 0, 0, 'member 1')
    chk.final(cut, sent, 0, len(sent), 950, 'member 1')
    assert chk.bad.count == 3
    # where it is read back: another member than took the create
    assert chk.readback_member(key, 3) == 2
    assert chk.readback_member(7, 3) == 1


def test_the_reference_catches_a_record_nobody_created():
    chk, key, data = _made()
    ghost = RECORDS + 20
    chk.final(ghost, None, 0, 0, 0, 'member 1')     # absent: right
    chk.miss(2, 0, ghost, 1.0, 1.1)                 # ... to a reader too
    assert not chk.bad.first and chk.not_yet_visible == 0
    chk.final(ghost, chk.initial(ghost), 0, 1121, 990, 'member 1')
    chk.read(2, 0, ghost + 1, chk.initial(ghost + 1), 0, 1121, 991, 991,
             2.0)
    assert chk.bad.by_kind == {'phantom': 2}
    # a second create of a key, or of a loaded one, is the engine's bug
    chk.create_sent(3, key)
    chk.create_sent(3, 5)
    assert chk.bad.by_kind == {'phantom': 4}
    chk.create_refused(3, RECORDS + 30, 'NODE_EXISTS')
    chk.gap(4, 'disconnect')
    assert chk.bad.by_kind == {'phantom': 4, 'refused': 1, 'evicted': 1}
