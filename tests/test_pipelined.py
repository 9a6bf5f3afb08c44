"""Clients that pipeline (ISSUE 47): ``max_frames`` requests
outstanding a session, as the deployment ``hunt3_1k`` runs them in its
cell ``hunt3_1k.read_deep`` — at toy size, on the CPU.

Three things are held here.  (a) A fleet of 24 sessions x 8 reads
outstanding through ONE ``FleetIngest`` against a 3-voter ensemble,
driven by the benchmark's own engine (``benchmark/engines/
kv_closed.py``) on a stand-in for the harness's fleet: every reply's
bytes are what the plain reference says (``benchmark/reference.py``,
which imports nothing of the program), every session's replies
complete in the order it sent the requests, and the scalar codec
(``protocol/framing.py``) over the very bytes each connection received
decodes the same replies in the same order.  (b) The same without
sockets (tests/test_ingest_route.py's ``Peer``), with the bytes handed
over as asyncio's push and as a receive reap hand them: rows of eight
replies behind one another, against the per-socket scalar drain.
(c) The device scan on rows of 1 .. 8 and 9 or more back-to-back
frames of mixed sizes: what does not fit the frame bound, or the bytes
a slot gives a tick, comes with the follow-up tick — nothing lost,
nothing doubled — and the always-on counters say which it was.
"""

from __future__ import annotations

import asyncio
import importlib.util
import os
import random
import sys
import time

import pytest

from test_ingest_classes import _codec, _patch_clock, reply_sized
from test_ingest_route import Peer, ReapRig, settle
from zkstream_tpu import Client
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.protocol.framing import PacketCodec
from zkstream_tpu.protocol.records import Stat
from zkstream_tpu.server import ZKEnsemble

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    'bench_engines_kv_closed', os.path.join(BENCH, 'engines',
                                            'kv_closed.py'))
kv_closed = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kv_closed)

N = 24
DEPTH = 8
CONFIG = {'sessions': N,
          'tree': {'root': '/kv', 'parents': 2, 'children': 96,
                   'bytes': 256}}
MIX = {'engine': 'kv_closed', 'ops': {'get': 100}, 'keys': 'uniform',
       'outstanding': DEPTH}
MIN_LEN = 1024


def _ingest(**kw) -> FleetIngest:
    kw.setdefault('max_frames', DEPTH)
    kw.setdefault('min_len', MIN_LEN)
    return FleetIngest(bypass_bytes=0, warm='block', placement='host',
                       **kw)


# ---------------------------------------------------------------------
# (a) the engine, a live ensemble, the reference, the scalar codec
# ---------------------------------------------------------------------

class Fleet:
    """What ``benchmark/harness.Fleet`` gives an engine."""

    def __init__(self, seed: int, ports, ingest):
        self.config, self.params, self.seed = CONFIG, MIX, seed
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


class _AllReads(dict):
    """The scalar codec's ``xid_map`` for a stream of ``getData``
    replies and nothing else."""

    def pop(self, xid, default=None):
        return 'GET_DATA'


class Tap:
    """One connection's received bytes from now on, and what its
    session's reads returned, in the order they completed."""

    def __init__(self, client: Client):
        self.wire = bytearray()
        self.done: list = []
        self.sent = self.completed = 0
        conn = client.current_connection()
        conn.on('sockData', self.wire.extend)
        get = client.get

        async def noted_get(path, **kw):
            nth = self.sent
            self.sent += 1
            data, stat = await get(path, **kw)
            # a session's replies complete in the order of its requests
            assert nth == self.completed, (nth, self.completed)
            self.completed += 1
            self.done.append((data, stat.version, stat.mzxid))
            return data, stat
        client.get = noted_get

    def scalar(self) -> list:
        """The same bytes through the scalar framing."""
        codec = PacketCodec(use_native=False)
        codec.handshaking = False
        codec.xid_map = _AllReads()
        return [(p['data'], p['stat'].version, p['stat'].mzxid)
                for p in codec.decode(bytes(self.wire))
                if p['xid'] > 0]


@pytest.mark.parametrize('seed', [47, 2 ** 31 + 47])
async def test_a_pipelined_fleet_against_the_plain_reference(event_loop,
                                                            seed):
    ens = await ZKEnsemble(3).start()
    ingest = _ingest()
    # every row count a fleet of 24 can give, in every class up to the
    # one that holds a session's whole window
    for rows in (1, 2, 4, 8, 16, 32):
        for width in (MIN_LEN, 2 * MIN_LEN, 4 * MIN_LEN):
            await ingest.prewarm(rows, width)
    fleet = Fleet(seed, [s.port for s in ens.servers], ingest)
    engine = kv_closed.Engine(fleet)
    try:
        await engine.load()
        await engine.connect()
        warmed = set(ingest.buckets)
        taps = [Tap(c) for c in engine.clients]
        engine.start()
        await asyncio.sleep(0.2)
        engine.open_window(time.perf_counter())
        await asyncio.sleep(1.0)
        engine.close_window(time.perf_counter())
        assert await engine.drain(10.0) == 0
        await engine.validate()
        res = engine.result()
        assert not res['violations'], res['violations']
        assert res['failed'] == 0 and res['counters']['errors'] == {}
        assert res['attempted'] > 20 * N * DEPTH
        for line in res['compared'][:-1]:
            assert line.endswith(' 0 limit 0'), line
        # every reply of the run was held to the model, and the whole
        # tree after it
        total = sum(t.completed for t in taps)
        assert engine.checker.checked == total + len(engine.paths)
        # the pipeline was full: eight requests a session in flight,
        # so a routed stream gave a tick several frames, up to the bound
        assert ingest.frames_routed >= total
        assert ingest.slots_bound > 0
        assert ingest.ticks_scalar == 0
        assert set(ingest.buckets) == warmed    # nothing compiled late
        # the scalar framing over the same bytes: the same replies, in
        # the same order, connection by connection
        for t in taps:
            assert t.sent == t.completed > DEPTH
            assert t.scalar() == t.done
    finally:
        await engine.stop()
        await asyncio.gather(*[c.close() for c in fleet.clients],
                             return_exceptions=True)
        ingest.close()
        await ens.stop()


# ---------------------------------------------------------------------
# (b) without sockets: rows of eight replies, push and reap
# ---------------------------------------------------------------------

async def _drive_windows(through_ingest: bool, use_native: bool,
                         how: str, seed: int):
    """24 sessions keep 8 reads outstanding over 6 rounds; a round's
    replies (seeded payloads of the reference) reach the connections in
    one to three hand-overs, so a tick's rows hold 1 .. 8 frames."""
    ingest = _ingest() if through_ingest else None
    rig = ReapRig() if how == 'reap' else None
    rng = random.Random(seed)
    peers = [Peer(i, ingest, use_native, random.Random(seed * 31 + i))
             for i in range(N)]
    payloads = reference.Payloads(seed, 256)
    model = [[] for _ in peers]     # a session: (xid, znode, version)
    try:
        window = {p.idx: [p.get('/kv/%d' % k) for k in range(DEPTH)]
                  for p in peers}
        for left in reversed(range(6)):
            cuts = {}
            for p in peers:
                for xid in window[p.idx]:
                    idx, version = rng.randrange(192), rng.randrange(4)
                    model[p.idx].append((xid, idx, version))
                    before = len(p.wire)
                    p.wire += p.srv.encode({
                        'xid': xid, 'zxid': p._next_zxid(), 'err': 'OK',
                        'opcode': 'GET_DATA',
                        'data': payloads.get(idx, version),
                        'stat': _stat(version, p.zxid)})
                    assert len(p.wire) - before == 256 + 92
                whole = bytes(p.wire)
                p.wire = bytearray()
                pts = sorted(rng.sample(range(1, len(whole)),
                                        rng.randrange(0, 3)))
                cuts[p.idx] = [whole[a:b] for a, b in zip(
                    [0] + pts, pts + [len(whole)])]
            for part in range(3):
                pairs = [(p.conn, cuts[p.idx][part]) for p in peers
                         if part < len(cuts[p.idx])]
                if rig is not None and ingest is not None:
                    rig.reap(pairs)
                else:
                    for conn, data in pairs:
                        conn.emit('sockData', data)
                await settle()
            # the window slides: every reply that came is asked again
            window = {p.idx: [p.get('/kv/%d' % k) for k in range(DEPTH)]
                      for p in peers} if left else {}
        snaps = [p.snapshot(ingest) for p in peers]
    finally:
        for p in peers:
            p.session.close()
            p.conn.destroy()
        await settle()
        if ingest is not None:
            ingest.close()
    return snaps, model, payloads, ingest


def _stat(version: int, zxid: int):
    return Stat(zxid, zxid, 0, 0, version, 0, 0, 0, 256, 0, zxid)


@pytest.mark.parametrize('how', ['push', 'reap'])
@pytest.mark.parametrize('use_native', [True, False],
                         ids=['ext', 'no_native'])
async def test_rows_of_eight_replies_equal_the_scalar_drain(
        event_loop, monkeypatch, use_native, how):
    _codec(use_native, monkeypatch)
    _patch_clock(monkeypatch)
    want, model, payloads, _none = await _drive_windows(
        False, use_native, how, 4747)
    got, _model, _p, ingest = await _drive_windows(
        True, use_native, how, 4747)
    assert got == want
    # ...and both are what the plain reference says: every future, in
    # the order the requests were sent, with the model's bytes
    for snap, sent in zip(want, model):
        futs = [e for e in snap['log'] if e[0] == 'fut']
        assert [e[1] for e in futs] == [xid for xid, _i, _v in sent]
        for entry, (_xid, idx, version) in zip(futs, sent):
            assert entry[2]['data'] == payloads.get(idx, version)
            assert entry[2]['stat'].version == version
    assert ingest.frames_routed == 6 * N * DEPTH == sum(map(len, model))
    assert ingest.ticks_scalar == 0
    # rows held several replies: fewer stream-ticks than frames, and
    # whole windows at the bound
    assert ingest.slots_bound > 0
    assert (ingest.ticks_early > 0) == (how == 'reap')


# ---------------------------------------------------------------------
# (c) the frame bound, the cut and the follow-up tick
# ---------------------------------------------------------------------

async def _row_of(k: int, sizes, through_ingest: bool, use_native: bool):
    ingest = _ingest(min_len=256) if through_ingest else None
    p = Peer(0, ingest, use_native, random.Random(1000 + k))
    try:
        for i in range(k):
            reply_sized(p, p.get(), sizes[i % len(sizes)])
        p.flush()               # all k frames in one receive call
        for _ in range(k + 2):
            await settle()
        snap = p.snapshot(ingest)
    finally:
        p.session.close()
        p.conn.destroy()
        await settle()
        if ingest is not None:
            ingest.close()
    return snap, ingest


#: reply sizes a row mixes: small ones, one near the class edge, one
#: that alone is wider than eight of the first
MIXED = (40, 1024, 7, 300, 2500, 0, 180, 64, 900)


@pytest.mark.parametrize('k', [1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 17, 24])
async def test_a_row_of_k_frames_comes_whole_bound_or_not(
        event_loop, monkeypatch, k):
    _codec(True, monkeypatch)
    _patch_clock(monkeypatch)
    want, _none = await _row_of(k, MIXED, False, True)
    got, ingest = await _row_of(k, MIXED, True, True)
    assert got == want
    assert len([e for e in got['log'] if e[0] == 'fut']) == k
    assert got['reqs'] == [] and got['residue'] == b''
    assert ingest.frames_routed == k        # none lost, none doubled
    # a tick takes at most eight frames of a row, and of a slot no
    # more than the power of two over eight of its first frame: what
    # is left comes with the follow-up ticks, each counted
    assert ingest.ticks >= -(-k // DEPTH)
    assert ingest.reticks == ingest.ticks - 1
    assert ingest.slots_bound + ingest.slots_cut >= ingest.reticks
    if k <= DEPTH and ingest.slots_cut == 0:
        assert ingest.ticks == 1 and ingest.reticks == 0
    assert ingest.slots_bound <= k // DEPTH


@pytest.mark.parametrize('k', [8, 9, 16, 20])
async def test_equal_frames_meet_the_bound_exactly(event_loop,
                                                   monkeypatch, k):
    """Frames of one size: a tick takes exactly eight of a row, and
    only a row with more behind the bound re-ticks (a slot that holds
    more than the power of two over eight frames is cut as well)."""
    _codec(True, monkeypatch)
    _patch_clock(monkeypatch)
    want, _none = await _row_of(k, (100,), False, True)
    got, ingest = await _row_of(k, (100,), True, True)
    assert got == want
    assert ingest.frames_routed == k
    assert ingest.slots_cut <= ingest.reticks
    assert ingest.ticks == -(-k // DEPTH)
    assert ingest.slots_bound == k // DEPTH
    assert ingest.reticks == ingest.ticks - 1
