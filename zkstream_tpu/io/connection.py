"""The per-TCP-connection state machine.

One ``ZKConnection`` owns one socket to one ZooKeeper backend and drives
it through ``init -> connecting -> handshaking -> connected ->
closing/error -> closed`` (reference: lib/connection-fsm.js:78-351).
Responsibilities mirror the reference exactly: xid allocation, the
pending-request table, reply routing, automatic ping keepalive with
piggybacking, SET_WATCHES queueing, and failing every outstanding
request exactly once on each teardown path.

Where the reference wires Node streams and sockets together, this uses
an asyncio ``Protocol`` feeding the symmetric ``PacketCodec``; a request
is a ``ZKRequest``, which carries the client facade's future and is an
emitter ('reply'/'error') for what must run inside the reply routing.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Callable

from ..protocol import consts
from ..protocol.errors import ZKError, ZKPingTimeoutError, \
    ZKProtocolError, ZKThrottledError
from ..protocol.framing import PacketCodec
from ..utils.aio import set_nodelay
from ..utils.events import EventEmitter
from ..utils.fsm import FSM
from ..utils.logging import Logger
from ..utils.trace import NO_SPAN, host_span, stamp_reply
from .sendplane import SendPlane

METRIC_ZK_CONNECT_LATENCY = 'zookeeper_connect_latency_ms'


@dataclasses.dataclass(frozen=True)
class Backend:
    """One ZooKeeper server address (reference: cueball backend objects)."""

    address: str
    port: int

    @property
    def key(self) -> str:
        return '%s:%d' % (self.address, self.port)


def _finish_span(req, zxid: int | None = None, status: str = 'ok',
                 error: str | None = None) -> None:
    """Close a request's trace span, when the client attached one
    (utils/trace.py — the xid-correlated span is stamped with the
    reply zxid here, where the reply routes back by xid).  Safe on
    every settle path: a span closes once, first outcome wins."""
    span = req.span
    if span is not None:
        span.finish(zxid=zxid, status=status, error=error)


#: What a request's ``_listeners`` is until its first ``on`` / ``once``:
#: ONE shared mapping that stays empty — the emitter's readers find
#: nobody listening in it, and nothing writes to it.
_NO_LISTENERS: dict = {}


class ZKRequest(EventEmitter):
    """One in-flight request, settled exactly once by the connection:
    :meth:`settle` (its reply packet) or :meth:`fail` (a typed error
    on a teardown path) (reference: lib/connection-fsm.js:378-382).
    Whoever
    awaits it takes :meth:`as_future` — the connection settles that
    future itself, with no listener in between; the 'reply' /
    'error' events serve what must run inside the routing call (the
    reserved xids' piggy-backing), and so does ``on_settled``, the
    ONE callback a request may carry instead: ``on_settled(req, None,
    pkt)`` on its reply, ``on_settled(req, err, None)`` on a failure
    (a watcher's arm, a thousand a change: no table, no disposers).
    The client
    facade may attach a trace ``span``; the connection's reply/error
    routing closes it.

    One is made an op on the fleet's one loop, so it has no
    ``__dict__`` and makes no listener table: almost no request is
    listened to (the reserved xids' are), and the table is made by
    the first ``on`` / ``once``.  ``packet`` stays with the request
    until it settles: it is the request's xid and opcode for whoever
    looks at ``conn.reqs`` (the tests do, a debugger would)."""

    __slots__ = ('packet', 'span', 'fut', 'on_settled')

    def __init__(self, packet: dict):
        self._listeners = _NO_LISTENERS
        self._ver = 0
        self.packet = packet
        #: Optional ``cb(req, err, pkt)``, run inside the routing call.
        self.on_settled: Callable | None = None
        #: Optional utils/trace.Span, attached by Client._start_op.
        self.span = None
        #: The awaiter's future, once as_future() was asked for it.
        self.fut: asyncio.Future | None = None

    def on(self, event: str, cb: Callable) -> 'ZKRequest':
        if self._listeners is _NO_LISTENERS:
            self._listeners = {}
        return super().on(event, cb)

    def once(self, event: str, cb: Callable) -> 'ZKRequest':
        if self._listeners is _NO_LISTENERS:
            self._listeners = {}
        return super().once(event, cb)

    def as_future(self) -> asyncio.Future:
        """The awaitable that resolves to the reply packet (one per
        request: a second call returns the same future)."""
        if self.fut is None:
            self.fut = asyncio.get_running_loop().create_future()
        return self.fut

    def settle(self, pkt: dict, rx: tuple | None = None) -> bool:
        """The reply has arrived: close the span, stamped with the
        reply zxid, then resolve the request with the packet or fail
        it with the typed error it carries — the one settle of
        :meth:`ZKConnection.process_reply` and of the direct lane
        (``state_connected``).  ``rx`` (profiler sessions only, else
        None): :meth:`ZKConnection.rx_mark`, for the stage stamps of
        an op that was submitted inside the session.  True when
        listeners heard it: their callbacks ran inside this call and
        may have moved any state machine."""
        code = pkt['err']
        span = self.span
        if (rx is not None and span is not None
                and span.stages is not None):
            stamp_reply(span, rx)
        if code != 'OK':
            if span is not None:
                span.finish(zxid=pkt.get('zxid'), status='error',
                            error=code)
            heard = bool(self._listeners) or self.on_settled is not None
            # the overloaded-member bounce gets its typed class so
            # the client's write path can key its backoff+retry on
            # isinstance instead of string-matching the code
            self.fail(ZKThrottledError() if code == 'THROTTLED'
                      else ZKError(code), pkt)
            return heard
        if span is not None:
            span.finish(zxid=pkt.get('zxid'))
        fut = self.fut
        # done() already: the awaiter gave up (deadline, cancelled)
        # and the late reply is dropped
        if fut is not None and not fut.done():
            fut.set_result(pkt)
        heard = False
        cb = self.on_settled
        if cb is not None:
            cb(self, None, pkt)
            heard = True
        if self._listeners:
            self.emit('reply', pkt)
            return True
        return heard

    def fail(self, err: Exception, *args) -> None:
        fut = self.fut
        if fut is not None and not fut.done():
            fut.set_exception(err)
        cb = self.on_settled
        if cb is not None:
            cb(self, err, None)
        if self._listeners:
            self.emit('error', err, *args)


class _SocketProtocol(asyncio.Protocol):
    """Socket callbacks -> connection events, on one of two receive
    paths, decided when the connection is made (the tier entry's
    ``rx_transport`` says which):

    - the loop's shared client tier owns the receive
      (io/transport.py, "Who receives": ``mmsg``, the extension built):
      the transport's reading is paused for good, a native receiver
      thread ``recv``s the socket and the tier's reap calls
      :meth:`ZKConnection._sock_data` with the bytes, :meth:`_rx_eof`
      at EOF and :meth:`_rx_error` with a hard errno.
      ``data_received`` then runs only for bytes that landed in the
      one-callback window before the tier claimed the fd;
    - asyncio pushes (every other tier, and none): ``data_received`` /
      ``eof_received`` as ever.

    Both feed the same ``_sock_data`` -> ``sockData``, so the fault
    injector's boundary, the ``client.rx`` span and every state's
    handlers do not know which one brought the bytes.  One thing the
    tier's reap does without ``_sock_data``: while state ``connected``
    has a sink standing (``resink`` there: all that ``sockData`` would
    do is append the bytes to the fleet ingest's slot), the reap's one
    C call makes that append itself."""

    def __init__(self, conn: 'ZKConnection'):
        self._conn = conn
        self._transport = None
        #: the errno that ended an adopted receive: ``connection_lost``
        #: reports it, as it reports a failed ``recv`` of asyncio's
        self._rx_exc: OSError | None = None

    def connection_made(self, transport) -> None:
        set_nodelay(transport)
        self._transport = transport
        self._conn.transport = transport
        self._conn._proto = self
        self.adopt()
        self._conn.emit('sockConnect')

    def adopt(self) -> bool:
        """Hand the socket's receive to the tier, if it takes it."""
        return self._conn._tx.adopt_rx(
            self._transport, self._conn._sock_data, self._rx_eof,
            self._rx_error)

    def release(self) -> None:
        """Out of the tier's receiver, if it reads this socket (what
        it had is delivered first); the transport's reading stays
        paused."""
        self._conn._tx.forget_rx(self._transport)

    def data_received(self, data: bytes) -> None:
        self._conn._sock_data(data)

    def eof_received(self) -> bool:
        self._conn.emit('sockEnd')
        return True  # keep half-open, like the reference's allowHalfOpen

    _rx_eof = eof_received

    def _rx_error(self, exc: OSError) -> None:
        """A hard errno on the adopted socket: what asyncio does with
        a failed ``recv`` — the transport is torn down and
        ``connection_lost`` carries the error."""
        self._rx_exc = exc
        self._transport.abort()

    def connection_lost(self, exc) -> None:
        # asyncio closes the socket as soon as this returns: no send
        # and no receive of it may still be in flight on the tier's
        # threads then; what the receiver had is delivered before the
        # close is seen
        self._conn._tx.quiesce()
        self.release()
        if exc is None:
            exc = self._rx_exc
        if exc is not None:
            self._conn.emit('sockError', exc)
        else:
            self._conn.emit('sockClose')


class ZKConnection(FSM):
    def __init__(self, client, backend: Backend, spare: bool = False):
        #: A spare parks after the TCP connect instead of handshaking:
        #: the ZK handshake binds the session to one connection, so a
        #: warm standby must stop just short of it.  ``promote()``
        #: resumes the normal lifecycle (pool failover skips the TCP
        #: dial; cueball's target 1 / max 3 warm set,
        #: reference: lib/client.js:108-109).
        self.spare = spare
        #: The owning client; consulted for the session during handshake
        #: (reference: lib/connection-fsm.js:174).
        self.client = client
        self.backend = backend
        #: What this connection's ops stamp their trace spans with
        #: (``Client._start_op``): where it leads, and — from
        #: ``connected`` on, None before — whose session it carries,
        #: each rendered once a connection and not once an op.
        self.span_backend = backend.key
        self.span_session_id: str | None = None
        # Child logger carrying this connection's address context
        # (reference: lib/connection-fsm.js:93-96); sessionId accretes
        # once connected (reference: lib/connection-fsm.js:209-211).
        self.log = getattr(client, 'log', Logger()).child(
            component='ZKConnectionFSM', zkAddress=backend.address,
            zkPort=backend.port)
        self.codec: PacketCodec | None = None
        self.transport = None
        #: ``transport``'s protocol (which knows the receive path)
        self._proto: _SocketProtocol | None = None
        self.session = None
        #: Optional FleetIngest: when the owning client carries one,
        #: connected-state bytes drain through the batched device
        #: pipeline instead of the per-socket scalar codec.
        self.ingest = getattr(client, 'ingest', None)
        #: Optional FaultInjector (io/faults.py): when the owning
        #: client carries one, dials, received bytes and outbound
        #: frames route through its seeded fault schedule.
        self._faults = getattr(client, 'faults', None)
        #: State ``connected`` under a fleet ingest, else None: looks
        #: again at where the receive may put this connection's bytes
        #: (``resink`` there) — called when something it depends on
        #: changes: the ingest's word, the ``sockData`` listeners, the
        #: injector.
        self._resink = None
        self.last_error: Exception | None = None
        self._xid = 0
        #: xid -> ZKRequest for everything awaiting a reply
        #: (reference: zcf_reqs).
        self.reqs: dict[int, ZKRequest] = {}
        self._dial_task: asyncio.Task | None = None
        #: Dial/handshake latency instrumentation: t0 set on entering
        #: 'connecting' (or on promote for a parked spare), observed
        #: into the histogram on reaching 'connected'.
        self._connect_t0: float | None = None
        #: Profiler sessions only, else 0: the start of the newest
        #: ``_sock_data`` call on ``time.perf_counter_ns``
        #: (:meth:`rx_mark`).
        self._rx_t0 = 0
        #: Outbound cork (io/sendplane.py): every encoded frame goes
        #: through it; frames of one event-loop tick leave as a single
        #: transport.write — or, when the client carries a batched
        #: transport tier (io/transport.py), as part of the tick's one
        #: batched submission.  ``client.cork`` forces the cork on/off
        #: (None = process default, see sendplane.cork_default);
        #: ``client.flush_cap`` resizes the early-flush cap.
        collector = getattr(client, 'collector', None)
        self._tx = SendPlane(self._tx_write,
                             enabled=getattr(client, 'cork', None),
                             max_bytes=getattr(client, 'flush_cap',
                                               None),
                             collector=collector, plane='client',
                             tier=getattr(client, 'transport_tier',
                                          None),
                             transport_fn=lambda: self.transport)
        self._connect_latency = None
        if collector is not None:
            self._connect_latency = collector.histogram(
                METRIC_ZK_CONNECT_LATENCY,
                'TCP connect + ZK handshake latency, milliseconds, '
                'by backend')
            self.bind_fsm_metrics(collector, 'ZKConnection')
        super().__init__('init')

    @property
    def faults(self):
        return self._faults

    @faults.setter
    def faults(self, injector) -> None:
        self._faults = injector
        if self._resink is not None:
            self._resink()

    def on(self, event: str, cb) -> 'ZKConnection':
        super().on(event, cb)
        if event == 'sockData' and self._resink is not None:
            self._resink()
        return self

    def remove_listener(self, event: str, cb) -> None:
        super().remove_listener(event, cb)
        if event == 'sockData' and self._resink is not None:
            self._resink()

    # -- public controls (reference: lib/connection-fsm.js:51-76) --

    def connect(self) -> None:
        assert self.is_in_state('closed') or self.is_in_state('init')
        self.emit('connectAsserted')

    def close(self) -> None:
        if self.is_in_state('closed'):
            return
        self.emit('closeAsserted')

    def destroy(self) -> None:
        if self.is_in_state('closed'):
            return
        self.emit('destroyAsserted')

    def promote(self) -> None:
        """Turn a parked spare into a live connection: run the ZK
        handshake on the already-open socket."""
        assert self.is_in_state('parked'), self.get_state()
        self.spare = False
        # a promoted spare's latency sample measures the handshake
        # only — the TCP dial was paid when it parked
        self._connect_t0 = time.monotonic()
        self.emit('promoteAsserted')

    def pause_reading(self) -> None:
        """Stop reading the socket — a stalled consumer (tests, the
        chaos schedules' ``stall``).  The transport's own
        ``pause_reading`` alone would not do where the tier's receiver
        thread reads the socket: this stops whichever reads it."""
        t = self.transport
        if t is not None:
            # what the thread had is delivered here, and may close us
            self._proto.release()
            t.pause_reading()

    def resume_reading(self) -> None:
        """Read again, on the path the connection was made on."""
        if self.transport is not None and not self._proto.adopt():
            self.transport.resume_reading()

    def next_xid(self) -> int:
        self._xid += 1
        return self._xid

    # -- states --

    def state_init(self, S) -> None:
        S.on(self, 'connectAsserted', lambda: S.goto_state('connecting'))

    def state_connecting(self, S) -> None:
        self.codec = PacketCodec(
            use_native=getattr(self.client, 'use_native_codec', None),
            max_frame=getattr(self.client, 'max_frame', None))
        self.log.debug('attempting new connection')
        self._connect_t0 = time.monotonic()

        async def dial():
            loop = asyncio.get_running_loop()
            try:
                if self.faults is not None:
                    # injected reconnect latency and/or refusal
                    await self.faults.before_connect(self.backend.key)
                await loop.create_connection(
                    lambda: _SocketProtocol(self),
                    self.backend.address, self.backend.port)
            except asyncio.CancelledError:
                raise
            except Exception as e:
                self.emit('sockError', e)

        self._dial_task = asyncio.get_running_loop().create_task(dial())

        S.on(self, 'sockConnect', lambda: S.goto_state(
            'parked' if self.spare else 'handshaking'))

        def on_error(err):
            self.last_error = err
            S.goto_state('error')
        S.on(self, 'sockError', on_error)
        S.on(self, 'sockClose', lambda: S.goto_state('closed'))
        S.on(self, 'closeAsserted', lambda: S.goto_state('closed'))
        S.on(self, 'destroyAsserted', lambda: S.goto_state('closed'))

    def state_parked(self, S) -> None:
        """Warm spare: TCP is open, no ZK bytes exchanged.  Wakes into
        ``handshaking`` on promote; any socket activity or death tears
        it down (a ZK server must not speak first, so inbound data here
        is a protocol violation)."""
        S.on(self, 'promoteAsserted',
             lambda: S.goto_state('handshaking'))

        def on_data(_data):
            self.last_error = ZKProtocolError('UNEXPECTED_PACKET',
                'Server sent data before the handshake')
            S.goto_state('error')
        S.on(self, 'sockData', on_data)

        def on_error(err):
            self.last_error = err
            S.goto_state('error')
        S.on(self, 'sockError', on_error)

        def on_end():
            self.last_error = ZKProtocolError('CONNECTION_LOSS',
                'Connection closed unexpectedly.')
            S.goto_state('error')
        S.on(self, 'sockEnd', on_end)
        S.on(self, 'sockClose', on_end)
        S.on(self, 'closeAsserted', lambda: S.goto_state('closed'))
        S.on(self, 'destroyAsserted', lambda: S.goto_state('closed'))

    def state_handshaking(self, S) -> None:
        def on_data(data):
            try:
                pkts = self.codec.decode(data)
            except ZKProtocolError as e:
                self.last_error = e
                S.goto_state('error')
                return
            if not pkts:
                return
            # Exactly one packet may arrive during the connect phase
            # (reference: lib/connection-fsm.js:130-140).
            if len(pkts) > 1:
                self.last_error = ZKProtocolError('UNEXPECTED_PACKET',
                    'Received unexpected additional packet during '
                    'connect phase')
                S.goto_state('error')
                return
            pkt = pkts[0]
            if pkt['protocolVersion'] != consts.PROTOCOL_VERSION:
                self.last_error = ZKProtocolError('VERSION_INCOMPAT',
                    'Server version is not compatible')
                S.goto_state('error')
                return
            self.emit('packet', pkt)
        S.on(self, 'sockData', on_data)

        def on_error(err):
            self.last_error = err
            S.goto_state('error')
        S.on(self, 'sockError', on_error)

        def on_end():
            self.last_error = ZKProtocolError('CONNECTION_LOSS',
                'Connection closed unexpectedly.')
            S.goto_state('error')
        S.on(self, 'sockEnd', on_end)
        S.on(self, 'sockClose', on_end)
        S.on(self, 'closeAsserted', lambda: S.goto_state('closed'))
        S.on(self, 'destroyAsserted', lambda: S.goto_state('closed'))

        self.session = self.client.get_session()
        if self.session is None:
            S.goto_state('closed')
            return

        # Guard against a session already attaching to another connection
        # (reference: lib/connection-fsm.js:180-187, the nasty.test.js
        # monitor-mode race).
        if self.session.is_attaching():
            self.log.debug('session in state %s while handshaking',
                           self.session.get_state())
            self.last_error = ZKProtocolError('ATTACH_RACE',
                'ZKSession attaching to another connection')
            S.goto_state('error')
            return

        def on_session_state(st):
            if st == 'attached':
                S.goto_state('connected')
        S.on(self.session, 'stateChanged', on_session_state)

        self.session.attach_and_send_cr(self)

    def state_connected(self, S) -> None:
        # Handshake is over: steady-state request/reply framing from here
        # (the reference flips this per-frame via isInState checks).
        self.codec.handshaking = False
        self.span_session_id = self.session.get_session_id()
        self.log = self.log.child(sessionId=self.span_session_id)

        if self._connect_latency is not None and \
                self._connect_t0 is not None:
            self._connect_latency.observe(
                (time.monotonic() - self._connect_t0) * 1000.0,
                {'backend': self.backend.key})
            self._connect_t0 = None

        ping_interval = max(self.session.get_timeout() / 4, 2000)
        S.interval(ping_interval, self.ping)

        def deliver(pkts, err, times=None):
            # ``times``: the lane's, for the packets it hands on
            for k, pkt in enumerate(pkts):
                self.emit('packet', pkt)
                # Notifications are the session's business
                # (reference: lib/connection-fsm.js:223-224).
                if pkt['opcode'] != 'NOTIFICATION':
                    self.process_reply(pkt, times[k] if times else 0)
            if err is not None:
                self.last_error = err
                S.goto_state('error')

        if self.ingest is not None:
            # Fleet drain: in the ingest's BATCH regime bytes go to the
            # batched device pipeline, which routes the decoded packets
            # back through the same deliver path, so semantics cannot
            # diverge from the scalar drain below.  In its pass-through
            # (direct) regime the connection runs the per-socket drain
            # itself — the ingest only gets the byte/frame counts its
            # dispatch policy needs — so the regime where batching does
            # not pay costs one flag check over the no-ingest path.
            session = self.session

            def lane_open():
                # what the lane restates is only right while the
                # session's attached-state handler is the one 'packet'
                # listener, and no injector stands in the stream
                held = self._listeners.get('packet')
                return (held is not None and len(held) == 1
                        and held[0] is session.packet_listener
                        and self._faults is None)

            def lane(pkts, err, now, times=None):
                """The direct settle lane: what one routed stream of
                the ingest's tick costs when it is a run of plain
                replies.  For the leading packets with ``xid > 0`` it
                does, in this one function, what ``deliver`` ->
                ``emit('packet')`` -> the session's ``on_packet`` ->
                ``process_reply`` do per packet: the session's expiry
                pushed out from the tick's clock ``now`` (once),
                ``last_zxid`` raised, the request popped and settled
                (:meth:`ZKRequest.settle`, as ``process_reply`` does).
                From the first packet that is anything else — a
                notification, a reserved xid — and for the stream's
                decode error, ``deliver`` takes over, in stream order.
                ``times`` (profiler sessions only, else None): for
                each packet the start of the receive call that brought
                its last byte, the ``t_rx`` of its request's stage
                stamps — a pipelined connection's replies of one tick
                may have come in several calls.
                Returns the packets settled here."""
                if not pkts or pkts[0]['xid'] <= 0 or not lane_open():
                    deliver(pkts, err, times)
                    return 0
                session.reset_expiry_timer(now)
                log_trace = (self.log.trace
                             if self.log.enabled_for_trace() else None)
                rx = self.rx_mark() if self._rx_t0 else None
                if rx is None:
                    times = None
                n = 0
                for pkt in pkts:
                    xid = pkt['xid']
                    if xid <= 0:
                        break
                    if times is not None:
                        rx = (times[n], rx[1])
                    n += 1
                    if pkt['zxid'] > session.last_zxid:
                        session.last_zxid = pkt['zxid']
                    # self.reqs anew each time: a teardown swaps it
                    req = self.reqs.pop(xid, None)
                    if log_trace is not None:
                        log_trace('server replied to xid %d err %s',
                                  xid, pkt['err'])
                    if (req is not None and req.settle(pkt, rx)
                            and not lane_open()):
                        break
                if n < len(pkts) or err is not None:
                    deliver(pkts[n:], err, times and times[n:])
                return n

            def on_sock(data):
                ing = self.ingest
                if not ing.direct:
                    ing.feed(self, data, self._rx_t0)
                    return
                # Deliberately restates FleetIngest._deliver_direct
                # minus its emit hop: calling deliver() directly here
                # skips one event dispatch per segment, which is the
                # point of the pass-through.  Slot residue cannot
                # exist in this regime (register/flip keep it in the
                # codec), so no splice is needed.
                err = None
                try:
                    pkts = self.codec.decode(data)
                except ZKProtocolError as e:
                    pkts = getattr(e, 'packets', [])
                    err = e
                ing.note_direct(len(data), len(pkts))
                deliver(pkts, err)
            sock_listener = S.on(self, 'sockData', on_sock)
            S.on(self, 'ingestDeliver', deliver)

            #: the ingest's word (``sink``): its slot's accumulator
            #: while bytes may be appended to it from outside, else None
            opened = None

            def resink():
                """Where the receive puts this connection's bytes.
                Straight into the ingest's slot (the tier's reap
                appends them in its one C call and tells the ingest
                once a reap: io/transport.py ``rx_sink``) while that
                is ALL a delivery would do: the ingest takes them
                (batch regime, no injector: ``opened``), ``on_sock``
                is the one ``sockData`` listener, and no injector
                stands in this stream — the lane's rule, for bytes.
                Else through ``_sock_data``, as ever.  Nothing here
                sets it: it follows what is so."""
                buf = opened
                if buf is not None:
                    held = self._listeners.get('sockData')
                    if (self._faults is not None or held is None
                            or len(held) != 1
                            or held[0] is not sock_listener):
                        buf = None
                self._tx.sink_rx(None if buf is None
                                 else (buf, self.ingest, self))

            def sink(buf):
                nonlocal opened
                opened = buf
                resink()

            def leave():
                # the slot's bytes go back to the codec with no sink
                # left to put more behind them
                self.ingest.unregister(self)
                self._resink = None

            self._resink = resink
            self.ingest.register(self, lane, sink)
            S.defer(leave)
        else:
            def on_data(data):
                err = None
                try:
                    pkts = self.codec.decode(data)
                except ZKProtocolError as e:
                    # Deliver packets decoded before the bad frame first.
                    pkts = getattr(e, 'packets', [])
                    err = e
                deliver(pkts, err)
            S.on(self, 'sockData', on_data)

        def on_error(err):
            self.last_error = err
            S.goto_state('error')
        S.on(self, 'sockError', on_error)

        def on_end():
            self.last_error = ZKProtocolError('CONNECTION_LOSS',
                'Connection closed unexpectedly.')
            S.goto_state('error')
        S.on(self, 'sockEnd', on_end)
        S.on(self, 'sockClose', on_end)

        S.on(self, 'closeAsserted', lambda: S.goto_state('closing'))
        S.on(self, 'destroyAsserted', lambda: S.goto_state('closed'))

        def on_ping_timeout():
            self.last_error = ZKPingTimeoutError()
            S.goto_state('error')
        S.on(self, 'pingTimeout', on_ping_timeout)

        S.immediate(lambda: self.emit('connect'))

    def state_closing(self, S) -> None:
        """Drain outstanding requests, then send CLOSE_SESSION and wait
        for its reply (reference: lib/connection-fsm.js:263-307)."""
        close_xid: list[int | None] = [None]

        def send_close_session():
            if close_xid[0] is not None:
                return
            close_xid[0] = self.next_xid()
            self.log.info('sent CLOSE_SESSION request (xid %d)',
                          close_xid[0])
            self._write({'opcode': 'CLOSE_SESSION', 'xid': close_xid[0]})
            # the EOF must not cut ahead of the corked CLOSE_SESSION —
            # hard: a batched transport tier defers flush_now to the
            # tick submission, which would land after the write_eof
            self._tx.flush_hard()
            try:
                if self.transport and self.transport.can_write_eof():
                    self.transport.write_eof()
            except (OSError, RuntimeError):
                pass

        def on_data(data):
            try:
                pkts = self.codec.decode(data)
            except ZKProtocolError as e:
                self.last_error = e
                S.goto_state('closed')
                return
            for pkt in pkts:
                if pkt['xid'] == close_xid[0]:
                    S.goto_state('closed')
                    return
                self.process_reply(pkt)
                if not self.reqs:
                    send_close_session()
        S.on(self, 'sockData', on_data)

        def on_error(err):
            self.last_error = err
            S.goto_state('closed')
        S.on(self, 'sockError', on_error)
        S.on(self, 'sockEnd', lambda: S.goto_state('closed'))
        S.on(self, 'sockClose', lambda: S.goto_state('closed'))
        S.on(self, 'destroyAsserted', lambda: S.goto_state('closed'))

        if not self.reqs:
            send_close_session()
        elif self.ingest is not None:
            # the fleet ingest's slot came back to the codec when
            # ``connected`` was left (``unregister``).  A reply that
            # sat there WHOLE, waiting for the next tick, is completed
            # by no later byte: drain it, or the close waits for the
            # session to time out.  One turn later: a close asserted
            # from inside a tick's route (a callback of an earlier
            # stream) comes before the route has handed back the xids
            # its batch decode took for these bytes
            S.immediate(lambda: on_data(b''))

    def state_error(self, S) -> None:
        self.log.warning('error communicating with ZK: %s',
                         self.last_error)
        reqs, self.reqs = self.reqs, {}
        # Pending ops surface the typed ZK errors, never a raw OS
        # exception: a socket-level error becomes CONNECTION_LOSS with
        # the original chained as __cause__ (the clean-close straggler
        # path already spoke ZKProtocolError only).
        req_err = self.last_error
        if not isinstance(req_err, (ZKProtocolError, ZKError)):
            wrapped = ZKProtocolError(
                'CONNECTION_LOSS', 'Connection lost: %s' % (req_err,))
            wrapped.__cause__ = req_err
            req_err = wrapped
        for req in reqs.values():
            _finish_span(req, status='error',
                         error=getattr(req_err, 'code', None)
                         or type(req_err).__name__)
            req.fail(req_err)

        # Deliberately not scope-bound: the 'error' event must fire even
        # though we leave this state immediately
        # (reference: lib/connection-fsm.js:317-323).
        err = self.last_error
        asyncio.get_running_loop().call_soon(lambda: self.emit('error', err))

        S.goto_state('closed')

    def state_closed(self, S) -> None:
        if self._dial_task is not None and not self._dial_task.done():
            self._dial_task.cancel()
        self._dial_task = None
        gate = getattr(self, '_fault_rx_gate', None)
        if gate is not None:
            gate.close()
        if self.transport is not None:
            try:
                self.transport.abort()
            except (OSError, RuntimeError):
                pass
        self.transport = None
        # corked frames have nowhere to go once the socket is dead
        self._tx.reset()

        S.on(self, 'connectAsserted', lambda: S.goto_state('connecting'))

        def fail_stragglers():
            self.emit('close')
            # Fail any remaining outstanding requests or they would hang
            # forever (reference: lib/connection-fsm.js:338-350).
            # Their spans settle as 'abandoned': the op was evicted
            # from the pending table without a reply ever routing —
            # distinct from a request that saw a typed error — so the
            # ring can never hold an open span after teardown (the
            # chaos campaigns assert exactly that).
            err = ZKProtocolError('CONNECTION_LOSS', 'Connection closed.')
            reqs, self.reqs = self.reqs, {}
            for req in reqs.values():
                _finish_span(req, status='abandoned', error=err.code)
                req.fail(err)
        S.immediate(fail_stragglers)

    # -- request plumbing --

    def _sock_data(self, data: bytes) -> None:
        """Socket bytes -> 'sockData', via the fault schedule when an
        injector is installed (splits/delays/dups/mid-frame resets).

        Host span ``client.rx`` (profiler sessions only; count and
        total, no object per call): the bytes' way from here through
        the state's ``sockData`` handler — on a fleet connection in
        the ingest's batch regime that ends with them in its slot
        (``FleetIngest.feed``); in the pass-through regime, and with
        no ingest, the decode and delivery are inside it too."""
        sp = host_span('client.rx', accumulate=True)
        if sp is not NO_SPAN:
            # a request's stage stamp ``t_rx`` (utils/trace.py): the
            # start of the call that brought the newest bytes
            self._rx_t0 = time.perf_counter_ns()
        elif self._rx_t0:
            self._rx_t0 = 0
        with sp:
            if self._faults is None:
                self.emit('sockData', data)
            else:
                self._faults.rx(self, data)

    def rx_mark(self, t_rx: int = 0) -> tuple:
        """What a reply settled now knows of its way in (profiler
        sessions only; ``ZKRequest.settle``'s ``rx``): the start of
        the ``_sock_data`` call that brought its last byte — ``t_rx``
        where the ingest's route says which call that was (a
        pipelined connection's replies of one tick may have come in
        several), else this connection's newest call, which is that
        call wherever a reply is decoded inside the call that
        completed it (the scalar drain, the pass-through regime) —
        and the number of the ``ingest.tick`` whose route is
        delivering it, None off the device."""
        ing = self.ingest
        return (t_rx or self._rx_t0,
                None if ing is None else ing.routing)

    def _tx_write(self, data: bytes) -> None:
        """The send plane's sink: one coalesced buffer per flush."""
        if self.transport is not None:
            self.transport.write(data)

    def _write(self, pkt: dict) -> None:
        data = self.codec.encode(pkt)
        if self._faults is not None:
            # Per-frame fault boundary, BEFORE the cork: may truncate
            # the frame and schedule an injected reset.
            out = self._faults.tx(self, data)
            if out is None:
                return
            if out is not data:
                # A fault fired on this frame.  Its scheduled reset
                # lands next tick — deliver everything already corked
                # plus the truncated frame NOW, in stream order, so
                # the reset still targets exactly this frame (hard:
                # the batched transport tier must drain synchronously
                # or the direct write below would overtake it).
                self._tx.flush_hard()
                self._tx_write(out)
                return
        if self.transport is None:
            return
        self._tx.send(data)

    def process_reply(self, pkt: dict, t_rx: int = 0) -> None:
        """Route a reply to its pending request
        (reference: lib/connection-fsm.js:353-376).  ``t_rx``: where
        the ingest's route knows which receive call brought this
        reply's last byte (profiler sessions only), that call's start;
        else 0 — the connection's newest call (:meth:`rx_mark`)."""
        xid = pkt['xid']
        if xid > 0:
            # One reply settles a normal request; dropping it here
            # (rather than via per-request cleanup listeners) keeps the
            # map tight.  Reserved xids (PING/SET_WATCHES) stay: their
            # handlers manage piggybacking and pop themselves.
            req = self.reqs.pop(xid, None)
        else:
            req = self.reqs.get(xid)
        self.log.trace('server replied to xid %d err %s',
                       xid, pkt['err'])
        if req is not None:
            req.settle(pkt, self.rx_mark(t_rx) if self._rx_t0 else None)

    def request(self, pkt: dict, span=None) -> ZKRequest:
        """Send a normal (positive-xid) request
        (reference: lib/connection-fsm.js:384-408).  ``span``: the
        op's trace span (``Client._start_op``), which the reply/error
        routing closes; one that carries ``stages`` (a profiler
        session) is stamped by the flush that takes these bytes."""
        if not self.is_in_state('connected'):
            raise ZKProtocolError('CONNECTION_LOSS',
                'Client must be connected to send requests')
        req = ZKRequest(pkt)
        req.span = span
        pkt['xid'] = self.next_xid()
        self.reqs[pkt['xid']] = req
        if self.log.enabled_for_trace():
            self.log.trace('sent request xid %d opcode %s',
                           pkt['xid'], pkt['opcode'])
        if span is not None and span.stages is not None:
            # before the write: a disabled cork flushes inside it
            self._tx.stamps.append(span.stages)
        self._write(pkt)
        return req

    def send(self, pkt: dict) -> None:
        """Raw send, used by the session for ConnectRequests
        (reference: lib/connection-fsm.js:410-413)."""
        self._write(pkt)

    def ping(self, cb: Callable | None = None) -> None:
        """Keep-alive ping on the reserved xid; concurrent pings
        piggyback on the in-flight one
        (reference: lib/connection-fsm.js:415-463)."""
        if not self.is_in_state('connected'):
            raise ZKProtocolError('CONNECTION_LOSS',
                'Client must be connected to send packets')
        pkt = {'xid': consts.XID_PING, 'opcode': 'PING'}
        existing = self.reqs.get(consts.XID_PING)
        if existing is not None:
            if cb:
                existing.once('reply', lambda _pkt: cb(None, None))
                existing.once('error', lambda err, *a: cb(err, None))
            return
        req = ZKRequest(pkt)
        self.reqs[consts.XID_PING] = req
        timeout_ms = max(self.session.get_timeout() / 8, 2000)
        loop = asyncio.get_running_loop()
        t1 = time.monotonic()

        def on_reply(rpkt):
            self.reqs.pop(consts.XID_PING, None)
            timer.cancel()
            latency = (time.monotonic() - t1) * 1000.0
            self.log.debug('ping ok in %d ms', latency)
            if cb:
                cb(None, latency)

        def on_error(err, *args):
            self.reqs.pop(consts.XID_PING, None)
            timer.cancel()
            if cb:
                cb(err, None)

        def on_timeout():
            req.remove_listener('reply', on_reply)
            self.emit('pingTimeout')

        req.once('reply', on_reply)
        req.once('error', on_error)
        timer = loop.call_later(timeout_ms / 1000.0, on_timeout)
        self._write(pkt)

    def set_watches(self, events: dict, rel_zxid: int,
                    cb: Callable,
                    opcode: str = 'SET_WATCHES') -> None:
        """Send SET_WATCHES on its reserved xid; a second call while one
        is in flight queues behind it
        (reference: lib/connection-fsm.js:465-499).  ``opcode`` selects
        the five-list SET_WATCHES2 variant when the session also
        replays persistent (ADD_WATCH) registrations."""
        if not self.is_in_state('connected'):
            raise ZKProtocolError('CONNECTION_LOSS',
                'Client must be connected to send packets (is in state %s)'
                % (self.get_state(),))
        pkt = {'xid': consts.XID_SET_WATCHES, 'opcode': opcode,
               'relZxid': rel_zxid, 'events': events}
        existing = self.reqs.get(consts.XID_SET_WATCHES)
        if existing is not None:
            existing.once('reply',
                lambda _pkt: self.set_watches(events, rel_zxid, cb,
                                              opcode))
            existing.once('error', lambda err, *a: cb(err))
            return
        req = ZKRequest(pkt)
        self.reqs[consts.XID_SET_WATCHES] = req

        def on_reply(rpkt):
            self.reqs.pop(consts.XID_SET_WATCHES, None)
            cb(None)

        def on_error(err, *args):
            self.reqs.pop(consts.XID_SET_WATCHES, None)
            cb(err)

        req.once('reply', on_reply)
        req.once('error', on_error)
        self._write(pkt)
