"""The fleet ingest's batch route against the per-stream reference.

A tick routes its replies as one batch: one pass over the head planes,
one C decode for every stream (``decode_streams``), and each stream
handed to its connection's direct settle lane
(io/connection.py ``state_connected``) — which settles a run of plain
replies itself and leaves everything else to ``deliver``.  The
reference is the per-socket scalar drain, the same connection without
an ingest: every case here drives ONE seeded corpus of wire bytes
through real ``ZKConnection`` + ``ZKSession`` pairs (no sockets: a
stub client, a fake transport, a server-side codec that writes the
bytes) twice, and what can be observed at every connection must be
identical — the order in which futures settle and with what, the
notifications, reserved-xid callbacks and state changes between them,
``session.last_zxid``, the expiry deadline under one clock, ``reqs``,
``xid_map`` and the bytes left over.  Each case runs with the C
extension and under ``ZKSTREAM_NO_NATIVE``, and with the bytes handed
over as asyncio's protocol push does (one call a connection: the
scheduled tick dispatches and waits), as a receive reap does (the
tier's ``_rx_reap`` over all of them: the batch is dispatched at the
reap's end and the scheduled tick finds it — io/ingest.py, "The early
dispatch"), and as a reap does while the connections' sinks stand (its
one call appends the bytes to the ingest's slots and tells the ingest
once: io/transport.py ``rx_sink``).
"""

import asyncio
import random
import struct
import time

import pytest

from zkstream_tpu.io import ingest as ingest_mod
from zkstream_tpu.io import session as session_mod
from zkstream_tpu.io.connection import Backend, ZKConnection
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.io.session import ZKSession
from zkstream_tpu.io.transport import TransportTier, _Entry
from zkstream_tpu.protocol.framing import PacketCodec, frame
from zkstream_tpu.protocol.records import Stat
from zkstream_tpu.utils import native

CLOCK = 1000.0


class _Time:
    """One clock for both runs: ``time.monotonic`` as the session and
    the ingest read it (the span and the loop keep the real one)."""

    perf_counter = staticmethod(time.perf_counter)
    time = staticmethod(time.time)

    @staticmethod
    def monotonic() -> float:
        return CLOCK


class FakeTransport:
    def __init__(self):
        self.out: list[bytes] = []

    def write(self, data) -> None:
        self.out.append(bytes(data))

    def abort(self) -> None:
        pass

    def can_write_eof(self) -> bool:
        return False


class StubClient:
    """What a ZKConnection asks of its client."""

    def __init__(self, ingest, use_native: bool):
        self.ingest = ingest
        self.use_native_codec = use_native
        self.session = ZKSession(30000)

    def get_session(self):
        return self.session


class LoggedFuture:
    """Stands where a request's awaiter future stands and logs the
    settle the moment it happens (a real future's callbacks run a
    loop turn later, after whatever else the routing call logged)."""

    def __init__(self, log: list, xid: int):
        self._log, self._xid, self._done = log, xid, False

    def done(self) -> bool:
        return self._done

    def set_result(self, pkt) -> None:
        assert not self._done
        self._done = True
        self._log.append(('fut', self._xid, pkt))

    def set_exception(self, err) -> None:
        assert not self._done
        self._done = True
        self._log.append(('fut', self._xid, type(err).__name__,
                          getattr(err, 'code', None)))


class Peer:
    """One connected session, the server's half of its wire, and the
    log of everything that was observed at it."""

    def __init__(self, idx: int, ingest, use_native: bool, rng):
        self.idx = idx
        self.rng = rng
        self.log: list = []
        self.client = StubClient(ingest, use_native)
        self.session = self.client.session
        self.conn = conn = ZKConnection(self.client,
                                        Backend('127.0.0.1', 1 + idx))
        conn.codec = PacketCodec(use_native=use_native)
        conn.transport = FakeTransport()
        self.srv = PacketCodec(server=True, use_native=False)
        conn.on('stateChanged', lambda st: self.log.append(('state', st)))
        conn._transition('handshaking')
        conn.emit('sockData', self.srv.encode({
            'protocolVersion': 0, 'timeOut': 30000,
            'sessionId': 0x1000 + idx, 'passwd': b'\x01' * 16}))
        self.srv.handshaking = False
        assert conn.is_in_state('connected')
        assert self.session.is_in_state('attached')
        self.session.process_notification = \
            lambda pkt: self.log.append(('notify', pkt['type'],
                                         pkt['path'], pkt['zxid']))
        self.zxid = 100 * (idx + 1)
        self.wire = bytearray()

    # -- the client side: requests whose settling is logged --

    def get(self, path='/k') -> int:
        req = self.conn.request({'opcode': 'GET_DATA', 'path': path,
                                 'watch': False})
        xid = req.packet['xid']
        req.fut = LoggedFuture(self.log, xid)
        return xid

    def ls(self, path='/live_nodes', stat=True) -> int:
        req = self.conn.request({
            'opcode': 'GET_CHILDREN2' if stat else 'GET_CHILDREN',
            'path': path, 'watch': True})
        xid = req.packet['xid']
        req.fut = LoggedFuture(self.log, xid)
        return xid

    def ping(self) -> None:
        self.conn.ping(lambda err, _lat: self.log.append(('ping', err)))

    def set_watches(self) -> None:
        self.conn.set_watches(
            {'dataChanged': ['/w'], 'createdOrDestroyed': [],
             'childrenChanged': []}, 5,
            lambda err: self.log.append(('set_watches', err)))

    # -- the server side: reply bytes onto this peer's wire --

    def _next_zxid(self) -> int:
        self.zxid += self.rng.randrange(1, 9)
        return self.zxid

    def reply(self, xid: int, err: str = 'OK') -> None:
        pkt = {'xid': xid, 'zxid': self._next_zxid(), 'err': err,
               'opcode': 'GET_DATA'}
        if err == 'OK':
            pkt['data'] = self.rng.randbytes(self.rng.randrange(0, 200))
            pkt['stat'] = Stat(*(self.rng.randrange(1 << 20)
                                 for _ in range(11)))
        self.wire += self.srv.encode(pkt)

    def reply_list(self, xid: int, names, stat=None) -> None:
        """A GET_CHILDREN2 reply, or (no ``stat``) a GET_CHILDREN one."""
        pkt = {'xid': xid, 'zxid': self._next_zxid(), 'err': 'OK',
               'opcode': 'GET_CHILDREN' if stat is None
               else 'GET_CHILDREN2', 'children': names}
        if stat is not None:
            pkt['stat'] = stat
        self.wire += self.srv.encode(pkt)

    def notification(self, path='/w') -> None:
        self.wire += self.srv.encode({
            'xid': -1, 'zxid': self._next_zxid(), 'err': 'OK',
            'opcode': 'NOTIFICATION', 'type': 'DATA_CHANGED',
            'state': 'SYNC_CONNECTED', 'path': path})

    def reserved(self, xid: int, opcode: str) -> None:
        self.wire += self.srv.encode({
            'xid': xid, 'zxid': self._next_zxid(), 'err': 'OK',
            'opcode': opcode})

    def raw(self, data: bytes) -> None:
        self.wire += data

    def take(self) -> bytes:
        """What the server wrote since the last hand-over."""
        data, self.wire = bytes(self.wire), bytearray()
        return data

    def flush(self) -> None:
        """Hand what the server wrote to the connection."""
        data = self.take()
        if data:
            self.conn.emit('sockData', data)

    # -- what the comparison reads --

    def snapshot(self, ingest) -> dict:
        err = self.conn.last_error
        return {
            'log': self.log,
            'state': self.conn.get_state(),
            'session': self.session.get_state(),
            'last_zxid': self.session.last_zxid,
            'expiry_deadline': self.session._expiry_deadline,
            'last_pkt': self.session.last_pkt,
            'reqs': sorted(self.conn.reqs),
            'xid_map': dict(self.conn.codec.xid_map),
            'last_error': (None if err is None
                           else (type(err).__name__,
                                 getattr(err, 'code', None), str(err))),
            'residue': self.pending(ingest),
        }

    def pending(self, ingest) -> bytes:
        """Bytes received and not yet decoded, wherever they wait:
        the ingest's slot, else the codec's accumulator."""
        slot = None if ingest is None else ingest._slots.get(id(self.conn))
        if slot is not None:
            return bytes(slot[1])
        pend = self.conn.codec.take_pending()
        self.conn.codec.restore_pending(pend)
        return bytes(pend)


class _RigTx:
    """A connection's send plane with the rig's tier behind
    ``sink_rx`` — the one call of it that needs a tier's entry, which
    these connections (no socket, no tier) do not have."""

    def __init__(self, tx, tier, entry):
        self._tx, self._tier, self._entry = tx, tier, entry

    def __getattr__(self, name):
        return getattr(self._tx, name)

    def sink_rx(self, sink) -> None:
        self._tier.rx_sink(self._entry, sink)


class ReapRig:
    """A client tier whose native receiver is the test: ``reap`` runs
    the tier's real ``_rx_reap`` over the bytes given, a delivery a
    connection, as the receiver thread's wake-up does.  With ``sink``
    a connection's sink (io/transport.py ``rx_sink``) reaches the
    rig's tier, and the stand-in reap does with the table what the C
    call does: a sunk token's bytes are appended to its bytearray and
    make no item (tests/test_native_ext.py holds the C call to that)."""

    def __init__(self, sink: bool = False):
        self.sink = sink
        self.tier = TransportTier('mmsg', plane='client')
        self.tier._receiver, self.tier._ext = object(), self
        self._items: list = []
        self._tokens: dict = {}

    def receiver_reap(self, _receiver, sinks=None, want=False):
        items, self._items = self._items, []
        left, each = [], []
        for token, data in items:
            buf = (sinks or {}).get(token)
            if buf is not None and data.__class__ is bytes and data:
                buf += data
                each.append((token, len(data)))
            else:
                left.append((token, data))
        fed = each and (len(each), sum(n for _t, n in each), 0,
                        each if want else None)
        return left, 0, 0, fed or None

    def token(self, conn) -> int:
        token = self._tokens.get(conn)
        if token is None:
            e = _Entry(None, None)
            # as ``Peer.flush`` hands bytes over: below ``_sock_data``,
            # whose injector gate the cases' stand-in does not have
            e.on_bytes = lambda data: conn.emit('sockData', data)
            token = self._tokens[conn] = len(self._tokens) + 1
            e.rx_token = token
            self.tier._rx[token] = e
            if self.sink:
                conn._tx = _RigTx(conn._tx, self.tier, e)
                if conn._resink is not None:
                    conn._resink()      # its state asks again, and is heard
        return token

    @property
    def fed(self) -> int:
        """Deliveries the reaps' stand-in C call made into sinks."""
        return self.tier.received_fed

    def reap(self, pairs) -> None:
        """``pairs``: (connection, bytes | -errno) in arrival order."""
        self._items = [(self.token(conn), data)
                       for conn, data in pairs if data]
        self.tier._rx_reap()

    def flush(self, peers) -> None:
        self.reap([(p.conn, p.take()) for p in peers])


async def settle() -> None:
    """Let the call_soon-scheduled tick and the futures' callbacks
    run."""
    for _ in range(4):
        await asyncio.sleep(0)


# ---------------------------------------------------------------------
# the cases: each writes its part of the corpus and says how many
# frames the lanes must have settled (None: not pinned)
# ---------------------------------------------------------------------

async def case_plain(peers, flush):
    for p in peers:
        p.reply(p.get())
    await flush()
    return len(peers)


async def case_two_frames(peers, flush):
    for p in peers:
        a, b = p.get(), p.get()
        p.reply(a)
        p.reply(b)
    await flush()
    return 2 * len(peers)


async def case_notification_before_reply(peers, flush):
    for p in peers:
        x = p.get()
        p.notification()
        p.reply(x)
    await flush()
    return 0     # the stream leaves the lane at its first packet


async def case_notification_after_reply(peers, flush):
    for p in peers:
        a, b = p.get(), p.get()
        p.reply(a)
        p.notification()
        p.reply(b)
    await flush()
    return len(peers)


async def case_ping_reply(peers, flush):
    for p in peers:
        a, b = p.get(), p.get()
        p.ping()
        p.reply(a)
        p.reserved(-2, 'PING')
        p.reply(b)
    await flush()
    return len(peers)


async def case_set_watches_reply(peers, flush):
    for p in peers:
        p.set_watches()
        x = p.get()
        p.reserved(-8, 'SET_WATCHES')
        p.reply(x)
    await flush()
    return 0


async def case_error_reply(peers, flush):
    for p in peers:
        a, b = p.get(), p.get()
        p.reply(a, 'NO_NODE')
        p.reply(b)
    await flush()
    return 2 * len(peers)


async def case_throttled(peers, flush):
    for p in peers:
        p.reply(p.get(), 'THROTTLED')
    await flush()
    return len(peers)


async def case_bad_stream(peers, flush):
    """A length prefix below zero after a whole frame: the device marks
    the stream bad and the connection's own codec decides (BAD_LENGTH
    drops the frames of the chunk before it, like the scalar drain)."""
    for i, p in enumerate(peers):
        p.reply(p.get())
        if i % 2 == 0:
            p.raw(struct.pack('>i', -5) + b'\x00' * 8)
    await flush()
    return None


async def case_truncated_tail(peers, flush):
    for p in peers:
        a, b = p.get(), p.get()
        p.reply(a)
        p.reply(b)
        whole = bytes(p.wire)
        cut = len(whole) - p.rng.randrange(1, 12)
        p.wire = bytearray(whole[:cut])
        p.rest = whole[cut:]
    await flush()
    for p in peers:
        p.raw(p.rest)
    await flush()
    return 2 * len(peers)


async def case_decode_error_mid_stream(peers, flush):
    """A reply whose xid matches no request, after a good one: the
    packets before it are delivered, then the connection errors."""
    for i, p in enumerate(peers):
        a, b = p.get(), p.get()
        p.reply(a)
        if i % 2 == 0:
            p.raw(frame(struct.pack('>iqi', 31337, p.zxid + 1, 0)))
        p.reply(b)
    await flush()
    return None


async def case_reply_after_deadline(peers, flush):
    """The awaiter gave up (its deadline fired): the late reply is
    dropped exactly once and the xid leaves ``reqs``."""
    from zkstream_tpu.protocol.errors import ZKDeadlineError
    for p in peers:
        a, b = p.get(), p.get()
        p.conn.reqs[a].fut.set_exception(
            ZKDeadlineError('GET_DATA', '/k', 50))
        p.reply(a)
        p.reply(b)
    await flush()
    return 2 * len(peers)


async def case_callback_closes_later_connection(peers, flush):
    """A callback inside the routing of peer 0's stream (a request's
    'reply' listener, as a watcher's arm is) closes peer 2, whose bytes
    are in the same tick: peer 2 is skipped, its bytes go back to its
    codec with the xids the batch decode took for them, and its
    ``closing`` state decodes them with the next segment."""
    first, victim = peers[0], peers[2]
    x = first.get()
    first.conn.reqs[x].on('reply', lambda _pkt: victim.conn.close())
    first.reply(x)
    for p in peers[1:]:
        a, b = p.get(), p.get()
        p.reply(a)
        if p is not victim:
            p.reply(b)
    vb = sorted(victim.conn.reqs)[-1]
    await flush()
    victim.reply(vb)
    await flush()
    return None


async def case_second_packet_listener(peers, flush):
    for p in peers:
        p.conn.on('packet', lambda pkt, p=p: p.log.append(
            ('packet', pkt['xid'], pkt['zxid'])))
        a, b = p.get(), p.get()
        p.reply(a)
        p.notification()
        p.reply(b)
    await flush()
    return 0


async def case_fault_injector_installed(peers, flush):
    class Injector:
        """Passes every frame: its presence alone is what is asked."""

        def tx(self, conn, data):
            return data

    for p in peers:
        p.conn.faults = Injector()
        p.reply(p.get())
    await flush()
    return 0


async def case_session_moving_away(peers, flush):
    """A session that is reattaching elsewhere no longer listens to
    this connection's packets: replies still settle, its zxid and
    expiry stay."""
    for p in peers:
        x = p.get()
        p.other = ZKConnection(p.client, Backend('127.0.0.1', 99))
        p.other.codec = PacketCodec(use_native=False)
        p.other.transport = FakeTransport()
        p.session.attach_and_send_cr(p.other)
        assert p.session.is_in_state('reattaching')
        p.reply(x)
    await flush()
    return 0


HERD = ['node-%04d:8983_solr' % i for i in range(48)]


async def case_equal_relists(peers, flush):
    """A herd: every session re-lists ONE path in ONE state, so the
    tick holds equal bodies — both layouts, behind a notification, one
    session a state ahead, one list too short to be worth sharing.
    Every asker sees what its own reply carried."""
    stat = Stat(*range(1, 12))
    for i, p in enumerate(peers):
        a, b, c = p.ls(), p.ls(stat=False), p.ls('/few')
        p.notification('/live_nodes')
        p.reply_list(a, HERD[:-1] if i == 3 else HERD, stat)
        p.reply_list(b, HERD)
        p.reply_list(c, HERD[:2], stat)
    await flush()
    return None


async def case_seeded_mix(peers, flush):
    """Several ticks of everything that keeps a connection alive, drawn
    from the seed."""
    for _round in range(6):
        for p in peers:
            rng = p.rng
            xs = [p.get() for _ in range(rng.randrange(0, 5))]
            if rng.random() < 0.3:
                p.ping()
                xs.append(-2)
            rng.shuffle(xs)
            for x in xs:
                if x == -2:
                    p.reserved(-2, 'PING')
                    continue
                if rng.random() < 0.25:
                    p.notification('/n%d' % rng.randrange(4))
                p.reply(x, rng.choice(['OK'] * 6 + ['NO_NODE',
                                                    'THROTTLED']))
        await flush()
    return None


CASES = {
    fn.__name__[len('case_'):]: fn for fn in (
        case_plain, case_two_frames, case_notification_before_reply,
        case_notification_after_reply, case_ping_reply,
        case_set_watches_reply, case_error_reply, case_throttled,
        case_bad_stream, case_truncated_tail,
        case_decode_error_mid_stream, case_reply_after_deadline,
        case_callback_closes_later_connection,
        case_second_packet_listener, case_fault_injector_installed,
        case_session_moving_away, case_equal_relists,
        case_seeded_mix)}


async def run_case(case, through_ingest: bool, use_native: bool,
                   seed: int, fed: str = 'push'):
    ingest = None
    rig = None if fed == 'push' else ReapRig(sink=fed == 'sink')
    if through_ingest:
        # one size class for the case whose callback closes a LATER
        # stream of the same tick: streams route in slot order within a
        # class's dispatch, classes narrowest first
        ingest = FleetIngest(
            bypass_bytes=0, warm='block', placement='host', max_frames=4,
            min_len=1024 if case == 'callback_closes_later_connection'
            else 256)
    lanes: list = []
    if ingest is not None:
        route = ingest._route_batch

        def counted(*args):
            out = route(*args)
            lanes.append(out)
            return out
        ingest._route_batch = counted
    peers = [Peer(i, ingest, use_native, random.Random(seed * 131 + i))
             for i in range(5)]

    async def flush():
        if rig is not None:
            rig.flush(peers)
        else:
            for p in peers:
                p.flush()
        await settle()

    try:
        expect = await CASES[case](peers, flush)
        await settle()
        snaps = [p.snapshot(ingest) for p in peers]
    finally:
        for p in peers:
            p.session.close()
            p.conn.destroy()
            if getattr(p, 'other', None) is not None:
                p.other.destroy()
        await settle()
        if ingest is not None:
            ingest.close()
    return snaps, expect, sum(a for a, _b in lanes), \
        sum(b for _a, b in lanes), \
        ingest and (ingest.lists_routed, ingest.lists_shared,
                    ingest.ticks, ingest.ticks_early,
                    rig and (rig.fed, rig.tier.received_reads))


#: the case whose connection leaves ``connected`` with replies still
#: to come: those come through ``sockData``, to its closing state
PARTLY_SUNK = ('callback_closes_later_connection',)


@pytest.mark.parametrize('fed', ['push', 'reap', 'sink'])
@pytest.mark.parametrize('use_native', [True, False],
                         ids=['ext', 'no_native'])
@pytest.mark.parametrize('case', list(CASES))
async def test_batch_route_equals_per_stream_reference(
        case, use_native, fed, monkeypatch):
    if use_native:
        if native.ensure_ext() is None:
            pytest.skip('no C extension here (no compiler)')
    else:
        monkeypatch.setenv('ZKSTREAM_NO_NATIVE', '1')
    monkeypatch.setattr(session_mod, 'time', _Time)
    monkeypatch.setattr(ingest_mod, 'time', _Time)
    want, _n, _l, _e, _s = await run_case(case, False, use_native,
                                          seed=29)
    got, lane_frames, laned, emitted, lists = await run_case(
        case, True, use_native, seed=29, fed=fed)
    *lists, ticks, early, reaped = lists
    lists = tuple(lists)
    # every device tick a reap fed was dispatched at the reap's end, or
    # (a follow-up) at the end of the tick before; asyncio's push
    # leaves only the follow-ups to go ahead of their tick
    assert ticks > 0
    assert early == ticks if fed != 'push' else early < ticks
    # with the sinks standing the reap's one call put the bytes in the
    # slots (every delivery, where nothing in the case withdraws one);
    # without them every delivery came through ``sockData``
    if fed == 'sink' and case == 'fault_injector_installed':
        assert reaped[0] == 0 < reaped[1]   # it came before any byte
    elif fed == 'sink':
        assert 0 < reaped[0] <= reaped[1]
        assert (case in PARTLY_SUNK) == (reaped[0] != reaped[1]), reaped
    elif fed == 'reap':
        assert reaped[0] == 0 < reaped[1]
    for i, (w, g) in enumerate(zip(want, got)):
        assert g == w, 'connection %d differs' % i
    assert any(w['log'] for w in want)       # the case observed something
    if lane_frames is not None:
        assert laned == lane_frames
    if case in ('plain', 'two_frames', 'error_reply', 'throttled'):
        assert emitted == 0                  # nothing but the lane
    if case == 'equal_relists':
        # 15 lists; all but the first HERD, the one HERD[:-1] and the
        # five short ones came from the tick's memo — with the C decode
        assert lists == (15, 8 if use_native else 0)
        views = [[e[2]['children'] for e in g['log'] if e[0] == 'fut']
                 for g in got]
        assert len({id(v) for vs in views for v in vs}) == 15
        views[0][0].reverse()       # one listener edits its own view
        assert views[0][1] == views[1][0] == HERD
    else:
        assert lists == (sum('children' in e[2] for g in got
                             for e in g['log'] if e[0] == 'fut'
                             and isinstance(e[2], dict)), 0)


async def test_lane_is_what_state_connected_registers():
    """The slot holds the connection's lane until the state's exit."""
    ingest = FleetIngest(bypass_bytes=0, warm='block', placement='host',
                         max_frames=4, min_len=256)
    p = Peer(0, ingest, False, random.Random(1))
    conn, _buf, lane = ingest._slots[id(p.conn)]
    assert conn is p.conn and callable(lane)
    p.conn.destroy()
    assert id(p.conn) not in ingest._slots
    p.session.close()
    await settle()
    ingest.close()


@pytest.mark.parametrize('whole', [True, False], ids=['whole', 'partial'])
async def test_a_close_drains_the_reply_its_slot_held(whole):
    """A connection that closes with a request out and that request's
    reply in its ingest slot — whole and waiting for the next tick, or
    cut short by the segment — gets the reply: the slot's bytes come
    back to the codec (``unregister``), the closing state drains what
    is whole at once and the rest as it arrives, and CLOSE_SESSION
    follows.  (No later byte completes a WHOLE reply: it waited for
    the session's timeout.)"""
    ingest = FleetIngest(bypass_bytes=0, warm='block', placement='host',
                         max_frames=4, min_len=256)
    p = Peer(0, ingest, False, random.Random(2))
    try:
        xid = p.get()
        p.reply(xid)
        wire = bytes(p.wire)
        p.wire.clear()
        cut = len(wire) if whole else len(wire) - 7
        p.conn.emit('sockData', wire[:cut])     # into the slot; no tick yet
        assert p.pending(ingest) == wire[:cut] and xid in p.conn.reqs
        def closes():
            # CLOSE_SESSION frames written so far (a bare header, op -11)
            return [b for b in p.conn.transport.out if len(b) == 12
                    and struct.unpack('>i', b[8:12])[0] == -11]

        p.conn.close()
        assert p.conn.is_in_state('closing')
        assert id(p.conn) not in ingest._slots
        await settle()      # the drain runs a turn after the close
        if not whole:
            assert xid in p.conn.reqs
            assert not closes()         # the reply is not whole yet
            p.conn.emit('sockData', wire[cut:])
        assert xid not in p.conn.reqs
        assert ('fut', xid) in [e[:2] for e in p.log]
        assert len(closes()) == 1               # CLOSE_SESSION, once
        close_xid = struct.unpack('>i', closes()[0][4:8])[0]
        p.conn.emit('sockData', p.srv.encode({
            'xid': close_xid, 'zxid': p.zxid, 'err': 'OK',
            'opcode': 'CLOSE_SESSION'}))
        assert p.conn.is_in_state('closed')
    finally:
        p.conn.destroy()
        p.session.close()
        await settle()
        ingest.close()
