"""YCSB core workload B through YCSB's ZooKeeper binding (the
deployment ``ycsb3`` at toy size): an in-process 3-voter ensemble, 24
sessions through ONE ``FleetIngest``, 512 records of 1,121 B under
``/benchmark``, every session drawing Zipfian keys and sending reads
and the binding's read-modify-write updates in one loop — so the hot
records have many concurrent writers — driven by the benchmark's own
engine (``benchmark/engines/ycsb_core.py``) on a stand-in for the
harness's fleet.

Held against the benchmark's plain reference of this deployment
(``benchmark/reference_ycsb.py``, which imports nothing of the
program): every ``getData`` reply, every acknowledgement, the final
tree from another member.  The key chooser is held to YCSB's own
(``ScrambledZipfianGenerator``: Gray's closed form over a zeta of
10 billion items, ``fnvhash64`` onto the keys), and the reference to
catching what it exists to catch."""

from __future__ import annotations

import asyncio
import gc
import importlib.util
import os
import random
import sys
import time

import pytest

from zkstream_tpu import Client
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.server import ZKEnsemble

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_ycsb  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    'bench_engines_ycsb_core', os.path.join(BENCH, 'engines',
                                            'ycsb_core.py'))
ycsb_core = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ycsb_core)

N = 24
RECORDS = 512
CONFIG = {'sessions': N,
          'tree': {'parent': '/benchmark', 'recordcount': RECORDS,
                   'fieldcount': 10, 'fieldlength': 100}}
MIX = {'readproportion': 0.95, 'updateproportion': 0.05,
       'requestdistribution': 'zipfian', 'zipfian_constant': 0.99,
       'writeallfields': False}


class Fleet:
    """What ``benchmark/harness.Fleet`` gives an engine."""

    def __init__(self, seed: int, ports, ingest, mix=MIX, config=CONFIG):
        self.config, self.params, self.seed = config, mix, seed
        self.addrs = [('127.0.0.1', p) for p in ports]
        self.ingest = ingest
        self.deadline_ms = 15000
        self.clients: list = []

    def new_client(self, member: int, through_ingest: bool = True):
        c = Client(servers=[self.addrs[member % len(self.addrs)]],
                   shuffle_backends=False, session_timeout=30000,
                   ingest=self.ingest if through_ingest else None,
                   max_spares=0)
        c.start()
        self.clients.append(c)
        return c


class Cell:
    #: the engine under test and the deployment and mix it is given
    #: (tests/test_ycsb_latest.py runs workload D's through this class)
    Engine, config, mix = ycsb_core.Engine, CONFIG, MIX

    async def start(self, seed: int, mix=None):
        gc.collect()    # the cell before this one is not this one's pause
        self.ens = await ZKEnsemble(3).start()
        self.ingest = FleetIngest(
            placement='host', max_frames=8,
            min_len=2048, max_data=2048, bypass_bytes=0, warm='block')
        for bp in (8, 16, 32):
            await self.ingest.prewarm(bp)
        self.fleet = Fleet(seed, [s.port for s in self.ens.servers],
                           self.ingest, mix or self.mix, self.config)
        self.engine = self.Engine(self.fleet)
        await self.engine.load()
        await self.engine.connect()
        return self

    async def run(self, seconds: float) -> dict:
        eng = self.engine
        eng.start()
        await asyncio.sleep(0.2)
        eng.open_window(time.perf_counter())
        await asyncio.sleep(seconds)
        eng.close_window(time.perf_counter())
        assert await eng.drain(10.0) == 0
        await eng.validate()
        return eng.result()

    async def stop(self) -> None:
        await self.engine.stop()
        await asyncio.gather(*[c.close() for c in self.fleet.clients],
                             return_exceptions=True)
        self.ingest.close()
        await self.ens.stop()


@pytest.mark.parametrize('seed', [5, 2 ** 31 + 40])
async def test_workload_b_against_the_plain_reference(event_loop, seed):
    cell = await Cell().start(seed)
    try:
        chk = cell.engine.checker
        writers: dict = {}
        acked = chk.write_acked

        def noted(session, member, key, *rest):
            writers.setdefault(key, set()).add(session)
            acked(session, member, key, *rest)
        chk.write_acked = noted
        buckets = set(cell.ingest.buckets)
        res = await cell.run(1.5)
        assert not res['violations'], res['violations']
        assert res['failed'] == 0 and res['counters']['errors'] == {}
        # (sent in the window / acknowledged in it: an operation in
        # flight at either edge is one and not the other)
        assert res['attempted'] > 200
        assert abs(res['acked'] - res['attempted']) <= N
        for line in res['compared'][:len(reference_ycsb.KINDS)]:
            assert line.endswith(' 0 limit 0'), line
        # reads and updates in the mix's proportion, every getData of
        # either kind a read sample
        updates = res['counters']['changes_acked']
        assert 0.02 < updates / res['acked'] < 0.09
        assert len(res['samples']['read']) == res['attempted']
        assert abs(len(res['samples']['rmw']) - updates) <= N
        # MANY writers a znode: the most rewritten record (rank 0's
        # key or, 1 draw in 26 against 1 in 52, rank 1's) was rewritten
        # by several sessions, and its versions count all their acks
        top = max(range(RECORDS), key=chk.acked.__getitem__)
        assert top in {ycsb_core.fnvhash64(r) % RECORDS for r in range(4)}
        assert len(writers[top]) >= 2
        assert res['counters']['most_versions'] == chk.acked[top] \
            == chk.newest[top] >= 3
        assert res['counters']['writes_unknown'] == 0
        # whatever rewrote a record, the checker read ALL of them back
        assert chk.checked >= res['attempted'] + updates + RECORDS
        # every tick went through the one program that was warmed
        assert set(cell.ingest.buckets) == buckets
        assert cell.ingest.ticks_scalar == 0
        # a 1,121 B record is under REPLY_SHARE_BYTES: every reply
        # went through the encoder, no member kept one
        assert all(s.data_cache.hits == s.data_cache.misses == 0
                   for s in cell.ens.servers)
    finally:
        await cell.stop()


async def test_a_target_paces_every_session_from_its_own_schedule(
        event_loop):
    """YCSB's ``-target``: 24 sessions at 960 operations a second are
    one operation every 25 ms each.  What the engine promises, held on
    its own record (``late_ms``: due -> sent) and on when each
    session's requests were sent and answered — not on how many this
    machine completed: a session's k-th operation is due at its first
    due time + k intervals and never sent before; one that is late is
    late by what held it — the operation before it still out, or this
    process's loop waking late, which the test measures itself — and
    sent at once, so the schedule never drifts; every read is timed
    from when it was due."""
    cell = await Cell().start(13, dict(MIX, target_ops_per_s=960))
    try:
        eng = cell.engine
        interval = eng.interval
        assert interval == pytest.approx(0.025)
        clock = time.perf_counter
        sent = [[] for _ in range(N)]   # a session's operations: sent
        done = [[] for _ in range(N)]   # ... and answered (its setData)

        def watched(s, c):
            get, put = c.get, c.set

            async def get_(path, **kw):
                sent[s].append(clock())
                done[s].append(0.0)
                try:
                    return await get(path, **kw)
                finally:
                    done[s][-1] = clock()

            async def set_(path, data, **kw):
                try:
                    return await put(path, data, **kw)
                finally:
                    done[s][-1] = clock()
            c.get, c.set = get_, set_
        for s, c in enumerate(eng.clients):
            watched(s, c)
        # how late THIS loop wakes a sleeper, all through the run: a
        # stall of the process or a long callback delays this probe as
        # it delays a session
        lag = [0.0]

        async def probe():
            while True:
                t = clock()
                await asyncio.sleep(0.002)
                lag.append(clock() - t - 0.002)
        prober = asyncio.ensure_future(probe())
        t_start = clock()
        res = await cell.run(1.5)
        prober.cancel()
        assert not res['violations'], res['violations']
        assert res['failed'] == 0
        assert abs(res['acked'] - res['attempted']) <= N
        # the engine's record: never sent before it was due, and every
        # read timed from its due time — its lateness is IN its latency
        late = eng.late_ms
        assert len(late) == res['attempted'] > 10 * N
        assert min(late) >= 0.0
        reads = sorted(res['samples']['read'])
        assert len(reads) == len(late) and reads[0] > 0.0
        assert all(r > l for r, l in zip(reads, sorted(late)))
        assert res['counters']['gen_late_ms_p95'] == pytest.approx(
            sorted(late)[int(0.95 * (len(late) - 1))], abs=1.0)
        assert res['counters']['changes_acked'] > 0
        # each session against its own schedule.  Its first due time is
        # not recorded; the most punctual of its operations bounds it
        # from above (d = sent - k * interval >= first due, with
        # equality for an operation sent when due), which only makes
        # an operation look LESS late than it was
        slack = max(lag) + 0.003
        for s in range(N):
            t, end = sent[s], done[s]
            assert len(t) > 10
            d = [t[k] - k * interval for k in range(len(t))]
            first_due = min(d)
            # no more than the schedule asks: k intervals lie between
            # the first due time (after the start) and the k-th send
            assert len(t) - 1 <= (t[-1] - t_start) / interval
            for k in range(1, len(t)):
                due = first_due + k * interval
                # held by the operation before it, or by the loop; sent
                # at once then, so the lateness never adds up
                held = max(0.0, end[k - 1] - due)
                assert t[k] - due <= held + slack, (
                    s, k, (t[k] - due) * 1e3, held * 1e3, slack * 1e3)
    finally:
        await cell.stop()


async def test_a_load_past_its_deadline_fails_the_run(event_loop,
                                                      monkeypatch):
    """A load phase that has not finished in ``LOAD_DEADLINE_S`` raises
    (the harness then exits non-zero, soon) instead of running into the
    run's own time limit."""
    monkeypatch.setattr(ycsb_core, 'LOAD_DEADLINE_S', 0.001)
    cell = Cell()
    cell.ens = await ZKEnsemble(3).start()
    cell.ingest = None
    cell.fleet = Fleet(3, [s.port for s in cell.ens.servers], None)
    cell.engine = ycsb_core.Engine(cell.fleet)
    try:
        with pytest.raises(RuntimeError, match='load phase'):
            await cell.engine.load()
    finally:
        await asyncio.gather(*[c.close() for c in cell.fleet.clients],
                             return_exceptions=True)
        await cell.ens.stop()


def test_the_mix_the_engine_does_not_send_is_refused():
    class F:
        config, seed, deadline_ms = CONFIG, 1, 1000
        params = dict(MIX, requestdistribution='uniform')
    with pytest.raises(ValueError):
        ycsb_core.Engine(F())


def test_fnvhash64_is_ycsbs():
    """``Utils.fnvhash64`` in a signed ``long``: FNV-1 (multiply after
    the XOR) over 8 octets, low first, then ``Math.abs``."""
    def java(val):
        h = 0xCBF29CE484222325 - (1 << 64)          # the basis, signed
        for _ in range(8):
            h ^= val & 0xff
            val >>= 8
            h = (h * 1099511628211 + (1 << 63)) % (1 << 64) - (1 << 63)
        return abs(h)
    rng = random.Random(64)
    for val in [0, 1, 255, 256, 65535, 10 ** 10] + [
            rng.randrange(10 ** 10) for _ in range(2000)]:
        assert ycsb_core.fnvhash64(val) == java(val) < 1 << 63


def test_key_chooser_shares_against_ycsbs_closed_form():
    """``ScrambledZipfianGenerator`` at any record count: the two
    hottest ranks are exact (1 / ZETAN = 3.778%, 0.5 ** 0.99 / ZETAN =
    1.902%), Gray's closed form puts the ten hottest 0.6 points over
    the exact zeta's 11.17% and the 400 hottest 0.7 over its 25.51%.
    Tolerance 0.4 points on 200,000 draws (a share of 3.8% has a
    standard error of 0.04 there) around what YCSB's sampler gives."""
    z = ycsb_core.ScrambledZipfian(65536, 0.99)
    assert abs(z.share(1) - 0.037780) < 1e-6
    assert abs(z.share(10) - 0.111682) < 1e-6
    assert abs(z.share(400) - 0.255106) < 1e-6
    # zeta(10**10, 0.99) itself, by Euler-Maclaurin from 10**6 on
    n, m, th = 10 ** 10 + 1, 10 ** 6, 0.99
    zeta = sum(i ** -th for i in range(1, m)) \
        + (n ** (1 - th) - m ** (1 - th)) / (1 - th) \
        + (m ** -th + n ** -th) / 2
    assert abs(zeta - z.ZETAN) < 1e-6
    rng = random.Random(40)
    draws = [z.rank(rng.random()) for _ in range(200_000)]
    assert min(draws) == 0 and max(draws) <= z.items
    for top, want in ((1, z.share(1)), (2, z.share(2)), (10, 0.1177),
                      (400, 0.2620)):
        got = sum(1 for r in draws if r < top) / len(draws)
        assert abs(got - want) < 0.004, (top, got)
    assert z.rank(0.0) == 0 and z.rank(1.0 - 2 ** -53) == z.items
    # a key is the rank's hash onto the records: the hottest key is
    # rank 0's, and a small table gets the same head
    assert z.key(0.0) == ycsb_core.fnvhash64(0) % 65536
    small = ycsb_core.ScrambledZipfian(512, 0.99)
    keys = [small.key(rng.random()) for _ in range(50_000)]
    assert 0 <= min(keys) and max(keys) < 512
    hottest = max(set(keys), key=keys.count)
    assert hottest == ycsb_core.fnvhash64(0) % 512
    assert abs(keys.count(hottest) / len(keys) - 0.0378 - 1 / 512) < 0.006
    with pytest.raises(ValueError):
        ycsb_core.ScrambledZipfian(512, 0.9)


# -- the reference catches what it is there to catch --------------------

def _seen(n=3):
    """A checker in which session 1 rewrote key 5 ``n`` times, each
    acknowledged, and session 2 read every version."""
    chk = reference_ycsb.YcsbChecker(7, RECORDS)
    data = chk.initial(5)
    size = len(data)
    chk.read(2, 5, data, 0, 50, size)
    versions = [data]
    for v in range(1, n + 1):
        chk.read(1, 5, data, v - 1, 50 + v - 1, size)
        data = chk.rewrite(5, data, v % 10)
        chk.write_acked(1, 0, 5, v, 50 + v, data)
        chk.read(2, 5, data, v, 50 + v, size)
        versions.append(data)
    assert not chk.bad.first and len(set(versions)) == n + 1
    return chk, versions, size


def test_the_reference_passes_the_bindings_lost_update():
    """Two sessions read version 0, both write: version 2 lacks version
    1's field — the binding's race, and no violation."""
    chk = reference_ycsb.YcsbChecker(9, RECORDS)
    base = chk.initial(3)
    chk.read(1, 3, base, 0, 10, len(base))
    chk.read(2, 3, base, 0, 10, len(base))
    one = chk.rewrite(3, base, 0)
    two = chk.rewrite(3, base, 1)
    # the second writer's ack arrives first, and a third session reads
    # version 1 before ITS ack has
    chk.read(3, 3, one, 1, 11, len(one))
    chk.write_acked(2, 1, 3, 2, 12, two)
    chk.write_acked(1, 0, 3, 1, 11, one)
    chk.read(3, 3, two, 2, 12, len(two))
    chk.settle()
    chk.final(3, two, 2, len(two), 'member 2')
    assert chk.records.fields(two)[0] == chk.records.fields(base)[0]
    assert not chk.bad.first, chk.bad.first
    assert (chk.newest[3], chk.newest_member[3]) == (2, 1)


def test_the_reference_catches_an_old_read():
    chk, versions, size = _seen()
    chk.read(2, 5, versions[2], 2, 52, size)    # its own bytes, but old
    assert chk.bad.by_kind == {'stale-read': 1}
    # ... and a read below the session's own acknowledged write
    chk.read(1, 5, versions[1], 1, 51, size)
    assert chk.bad.by_kind == {'stale-read': 2}


def test_the_reference_catches_a_lost_write():
    chk, versions, size = _seen()
    chk.settle()
    chk.final(5, versions[2], 2, size, 'member 1')
    assert chk.bad.by_kind == {'lost-write': 1}
    chk.final(5, versions[2], 3, size, 'member 1')  # right count, old bytes
    assert chk.bad.by_kind == {'lost-write': 1, 'final-tree': 1}
    chk.final(6, None, 0, 0, 'member 1')
    assert chk.bad.by_kind['final-tree'] == 2
    chk.final(7, chk.initial(7), 0, size, 'member 1')
    assert chk.bad.count == 3


def test_the_reference_catches_a_flipped_byte():
    chk, versions, size = _seen()
    data = versions[3]
    for at in (0, 11, size // 2, size - 1):
        bad = data[:at] + bytes([data[at] ^ 1]) + data[at + 1:]
        chk.read(2, 5, bad, 3, 53, size)
    assert chk.bad.by_kind == {'payload': 4}
    chk.read(2, 5, data[:-1], 3, 53, size - 1)      # short, honest Stat
    chk.read(2, 5, data, 3, 53, size + 1)           # whole, wrong Stat
    assert chk.bad.by_kind == {'payload': 6}


def test_the_reference_catches_two_bodies_for_one_version():
    chk, versions, size = _seen()
    # version 2's bytes handed out as version 3's: every field is one
    # somebody wrote, only the version is not theirs
    chk.read(2, 5, versions[2], 3, 53, size)
    assert chk.bad.by_kind == {'version-bytes': 1}
    # ahead of the acknowledgement: two readers, two bodies
    sent = chk.rewrite(5, versions[3], 0)
    chk.read(3, 5, sent, 4, 54, size)
    chk.read(4, 5, versions[3], 4, 54, size)
    assert chk.bad.by_kind == {'version-bytes': 2}
    # ... and what was read ahead of it is held to what the writer sent
    other = chk.rewrite(5, versions[3], 0)
    chk.read(3, 5, other, 5, 55, size)
    chk.write_acked(1, 0, 5, 5, 55, chk.rewrite(5, versions[3], 1))
    assert chk.bad.by_kind == {'version-bytes': 3}
    # two acknowledgements at one version
    chk.write_acked(2, 1, 5, 5, 55, other)
    assert chk.bad.by_kind == {'version-bytes': 3, 'write-version': 1}
    # a version that was read and that nobody is acknowledged for
    chk.settle()
    assert chk.bad.by_kind['version-bytes'] == 4


def test_the_reference_catches_a_future_version():
    chk, versions, size = _seen()
    chk.read(3, 5, versions[3], 4, 54, size)        # 3 writes were sent
    assert chk.bad.by_kind == {'future-read': 1}
    chk.write_acked(3, 2, 5, 9, 59, versions[3])
    assert chk.bad.by_kind == {'future-read': 1, 'write-version': 1}
    chk.gap(4, 'disconnect')
    assert chk.bad.by_kind['evicted'] == 1
