"""Batched-syscall transport backends beneath the send plane — and,
for the loop's shared client tier, the receive side of the same
connections ("Who receives", below).

The tick cork (io/sendplane.py) already joins every frame a connection
sends within one event-loop iteration into one ``transport.write`` —
but a WIDE tick still costs one write(2) per dirty connection, so a
busy server at 1k–10k connections spends its ``cork_flush`` /
``fanout_flush`` tick phases (the PR 7 ledger's numbers) on pure
syscall dispatch.  This module swaps the syscall layer underneath the
unchanged SendPlane API — the PAPERS.md thread (RPCAcc, ACCL+,
transparent InfiniBand under netty) applied here: the RPC surface
stays put, the batching decision lives in exactly one place.

Three tiers, capability-probed and env-forced exactly like the codec
tiers (``ZKSTREAM_TRANSPORT=uring|mmsg|asyncio``):

- ``uring``   — a shared io_uring submission queue: ONE
  ``io_uring_enter`` per corked tick covers every dirty connection
  (one ``IORING_OP_SENDMSG`` SQE per connection, iovec-joined, so no
  intermediate Python ``bytes`` is materialized per connection
  either).  Requires Linux >= 5.1 and the native extension
  (native/zkwire_ext.c ``uring_*``).
- ``mmsg``    — per-connection vectored writes: one ``writev(2)`` per
  dirty connection per tick, submitted for the whole batch in ONE C
  call (``zkwire_ext.submit_writev``) when the extension is built, an
  ``os.writev`` loop otherwise.  TCP has no cross-fd ``sendmmsg``;
  the vectored submit is its stream-socket equivalent — the syscall
  count stays O(dirty conns) but the join and the per-write asyncio
  transport walk disappear.
- ``asyncio`` — the existing per-plane ``transport.write`` path,
  untouched: the env-gated validator (and the only tier off Linux).

The default is the best available tier; forcing an unavailable tier
falls DOWN the order (never up), so an exported ``uring`` on an old
kernel degrades to ``mmsg`` instead of failing — ``probe()`` records
why, and the ``zk_transport_backend`` mntr row shows what a member
actually runs.

Correctness contract (the parity suite in tests/test_transport.py
holds all tiers to byte-identical per-connection streams):

- **Per-connection ordering is submission order.**  An entry's chunks
  append in plane-flush order; raw submission happens at the tick
  boundary; a partial or refused (``EAGAIN``) raw write routes the
  REMAINDER through the asyncio transport, and every subsequent tick
  defers to the transport until its buffer drains (`` raw writes only
  when get_write_buffer_size() == 0``) — so the kernel sees every
  byte exactly once, in order, whichever path carried it.
- **Hard flushes stay synchronous.**  ``SendPlane.flush_hard`` (fault
  injection delivering mid-tick, CLOSE_SESSION ahead of EOF,
  connection close) drains that entry's pending bytes with an
  immediate single-entry submission before returning — the fault
  injector's per-frame boundary rule (io/faults.py) is unchanged.
- **The durability barrier is upstream.**  The plane gates corked
  acks on the WAL's group fsync BEFORE handing bytes to the tier
  (SendPlane.flush_now), so no ack byte reaches a submission queue
  before its txn is on disk — backend-independent.

Who shares a tier.  A server builds one for all its connections
(:func:`make_tier`).  A client has ONE connection, so a tier of its own
would cover one connection per submission; instead the clients running
on one event loop share that loop's tier per resolved backend
(:class:`TierLease`: a registry keyed by loop and backend, counted by
the clients that joined) — a fleet of sessions flushes one loop
iteration's requests in one ``_tick`` and one submission.  A lone
client gets the same thing at depth 1.

Who sends.  On ``mmsg`` a submission is one ``send(2)`` a connection
inside one C call that holds the GIL: at a few hundred connections
that is milliseconds of the event loop's thread.  The loop's shared
client tier (and no other: a member's submission stays inside its tick
ledger, behind the WAL barrier) therefore hands a batch of
:data:`OFFLOAD_MIN_SENDS` connections or more to ONE native thread
(``zkwire_ext.sender_*``: no Python object, no GIL), keeps the batch in
``_inflight`` and applies its results when the thread's ``eventfd``
wakes the loop (``_reap``) — the same result handling as the inline
submission, which shallower batches, the ``uring`` backend (already one
``io_uring_enter`` a batch) and a process without the extension keep.
What holds while a batch is in flight: a connection in it is not
submitted again, raw or through its asyncio sink, until the batch is
reaped (its new chunks wait in its entry: order on a connection);
``drain`` / ``discard`` / ``quiesce`` first wait for the entry's batch
(bytes on the wire before a hard flush returns; an fd is never closed,
and so never reused, under a send in flight); the entry's in-flight
bytes stay in ``SendPlane.buffered_bytes()``.

Who receives.  A reply costs the loop one ``recv(2)`` a connection
behind asyncio's selector transport (``_read_ready`` ->
``data_received``).  The tier that ``attach_sender`` arms — the loop's
shared client tier on ``mmsg``, and no other — also owns its
connections' RECEIVE: ``rx_adopt`` (the connection's protocol, at
``connection_made``) pauses the asyncio transport's reading and, one
callback later (``_rx_claim``: a 3.10-3.11 selector transport re-takes
the fd once behind ``connection_made``), registers the fd with ONE
native receiver thread (``zkwire_ext.receiver_*``: its own ``epoll``,
one ``recv`` a ready connection, no Python object, no GIL).  The
thread's ``eventfd`` wakes the loop and ``_rx_reap`` hands each
connection its bytes in the order they were received
(``_Entry.on_bytes``; EOF and a hard errno likewise, once).  So a
connection is on exactly one of two paths, decided when it is made and
visible as ``entry.rx_transport``: (a) the receiver thread — client
tier, ``mmsg``, a selector loop, the extension built and its thread
started; (b) asyncio's protocol push, exactly as it was — everything
else (the ``asyncio`` backend has no tier; ``uring``; a member's tier;
no extension yet; ``receiver_create`` failing; a loop without
``_remove_reader``).  What holds on (a): bytes are keyed by the
receiver's token, never by fd number; ``rx_forget``
(``connection_lost``, before asyncio closes the socket) returns only
when no ``recv`` of the fd is in flight, and delivers what was received
and not yet reaped first; a connection's unreaped bytes are bounded in
the thread (``RECEIVER_LIMIT``), behind which the kernel's socket
buffer pushes back on the peer; ``close`` gives live connections back
to their transports.

Where a reap's bytes go.  A fleet connection's ``on_bytes`` is a chain
of Python calls (``_sock_data`` -> ``sockData`` -> the state's handler
-> ``FleetIngest.feed``) that ends by appending the bytes to the
ingest's slot for the connection, a bytearray.  While that append is
ALL the chain would do, the connection says so (:meth:`TransportTier.
rx_sink`: state ``connected`` under an ingest in its batch regime, no
injector on either, no second ``sockData`` listener —
io/connection.py ``resink``; it follows what is so, nothing sets it)
and the tier keeps ``{token: bytearray}`` (``_sinks``) for the
connections its thread reads.  A reap's ONE C call
(``receiver_reap(receiver, sinks, want)``) then appends such a
connection's chunks straight to its bytearray — no ``bytes``, no
tuple, no Python call a connection — and returns every other
connection's bytes, and every EOF and errno (a sunk connection's
too), as items, which take ``on_bytes`` / ``on_eof`` / ``on_error`` as
ever, after the feeds.  The ingest hears of a reap's feeds once
(``owner.fed``: its byte window, its tick, its early dispatch).  A
connection's bytes travel one way at a time — what the thread held
when a sink came or went takes the way that stands at the next reap —
so a connection's order holds by construction; ``_rx_release``
(pause, close, give-back) takes the table's entry with the token, and
``_rx_claim`` puts it back when the connection is read here again.

A reap is also the one moment that knows a loop
iteration's bytes are ALL with their connections: a callee of one of
its deliveries, or the owner of a sink it fed, may leave a callable to
be run when the last delivery is made (:func:`after_reap` — the fleet
ingest does, and builds and dispatches that iteration's device batch
there instead of a loop iteration later: io/ingest.py, "The early
dispatch").

Observability: ``zookeeper_flush_syscalls_total{plane,backend}``
counts actual write submissions (the A/B number: O(dirty conns) per
tick on mmsg/asyncio, O(1) on uring) and ``zookeeper_submit_depth``
histograms connections covered per batched submission.  Both are the
tier's own series (registered with the ``collector`` it was built
with; a shared client tier has none and every joined client's
collector adopts them).  Under a profiler session each tick is a host span
``<plane>.flush`` (utils/trace.host_span; count and total only):
``client.submit``'s count over ``client.flush``'s is requests per
flush; a hand-over is ``client.handoff``, a reap ``client.reap``, and
``<plane>.send`` totals the connections every raw batch covered with
the nanoseconds inside their send loop (the sender's own clock, or the
loop's around an inline submission).  Always on:
``zookeeper_flush_offloaded_total{plane}``, ``tier.offloaded_flushes``,
``tier.offloaded_batches``; for the receive
``zookeeper_recv_offloaded_total{plane}``, ``tier.received_reads``
(deliveries through a reap), ``tier.received_batches`` (reaps that
brought any), and of the deliveries those a reap's C call made into a
sink: ``zookeeper_recv_fed_total{plane}``, ``tier.received_fed``.
Under a profiler session a reap is ``client.rx_reap``
(the connections' ``client.rx`` spans nest inside it),
``client.rx_reaped`` counts the deliveries it made and ``client.recv``
totals the thread's ``recv(2)`` calls with the nanoseconds inside them,
on the thread's own clock.  A fed connection has no ``_sock_data``
call to be a span: it counts once under ``client.rx``, with the
nanoseconds the C call's pass over its connections took (booked like
``client.recv``), once under ``client.rx_reaped`` and once under
``client.rx_fed`` — so ``client.rx`` still counts every delivery and
still nests in ``client.rx_reap`` — and its stage stamp ``t_rx`` is
the one clock read the reap made before the call (``owner.fed_mark``).
"""

from __future__ import annotations

import dataclasses
import errno
import logging
import os
import sys
import threading
import weakref
from time import perf_counter_ns

from ..utils.aio import ambient_loop
from ..utils.metrics import Collector
from ..utils.trace import NO_SPAN, host_add, host_span, stamp_flush

log = logging.getLogger('zkstream_tpu.transport')

#: ``pending``: while one of this thread's tiers hands a reap's bytes
#: to their connections, the callables those deliveries left behind
#: (:func:`after_reap`), in the order left; None otherwise.
_reaping = threading.local()


def after_reap(fn) -> bool:
    """Called from inside a delivery of :meth:`TransportTier._rx_reap`
    (a connection's ``on_bytes`` and whatever it calls): run ``fn()``
    once, when the reap has handed every connection its bytes — still
    inside the reap's callback, after its own accounting.  False, and
    ``fn`` is dropped, when no reap is delivering on this thread
    (asyncio's protocol push, a hand-back outside a reap)."""
    pending = getattr(_reaping, 'pending', None)
    if pending is None:
        return False
    pending.append(fn)
    return True

TRANSPORT_ENV = 'ZKSTREAM_TRANSPORT'

#: Fallback order: forcing an unavailable tier falls DOWN this list.
BACKENDS = ('uring', 'mmsg', 'asyncio')

METRIC_FLUSH_SYSCALLS = 'zookeeper_flush_syscalls_total'
METRIC_SUBMIT_DEPTH = 'zookeeper_submit_depth'
METRIC_FLUSH_PARTIAL = 'zookeeper_flush_partial_total'
METRIC_FLUSH_REQUEUED = 'zookeeper_flush_partial_requeued_bytes'
METRIC_FLUSH_OFFLOADED = 'zookeeper_flush_offloaded_total'
METRIC_RECV_OFFLOADED = 'zookeeper_recv_offloaded_total'
METRIC_RECV_FED = 'zookeeper_recv_fed_total'

#: Connections in one raw batch from which the loop's shared client
#: tier hands the batch to its native sender thread instead of sending
#: inline.  A hand-over costs the loop a condvar signal, the thread's
#: ``eventfd`` write, a selector wake-up and the reap callback — ~25 us
#: and 0.9 us a connection where a ``send(2)`` costs 43 (TPU host,
#: kernel 4.4; PERF.md section 6, PR 33) — so loop time alone would put
#: this near 2.  It stands where a hand-over was measured to pay end to
#: end: flushes of 110 connections and more do; a closed loop of 48
#: writers (9 a flush, at times all 48) read the same either way, and
#: its sends then also wait for a thread to wake.
OFFLOAD_MIN_SENDS = 64

#: Connections per batched submission (the depth distribution: 1 =
#: batching bought nothing that tick, the interesting mass is 2+).
DEPTH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096)

#: Per-entry chunk-count guard: above this the entry's chunks are
#: coalesced in place before submission so one connection's frame
#: count can never overflow an iovec array (IOV_MAX is 1024).
IOV_GUARD = 512

#: io_uring submission-queue depth (entries per ring; batches wider
#: than this submit in waves — still one enter syscall per wave).
URING_DEPTH = 1024

#: Raw-write errnos meaning the connection itself is gone (drop the
#: bytes, exactly as an aborted transport would) — anything else
#: (EAGAIN backpressure, ring-level transients like EBUSY/ENOMEM/
#: ENOBUFS) re-routes through the asyncio transport, which either
#: delivers or runs its own teardown.  EIO doubles as the native
#: uring layer's "submission state unknown" sentinel: a resend there
#: could duplicate bytes, so those drop.
_DEAD_ERRNOS = frozenset({errno.EPIPE, errno.ECONNRESET,
                          errno.EBADF, errno.ENOTCONN,
                          errno.ESHUTDOWN, errno.ECONNABORTED,
                          errno.EIO})


@dataclasses.dataclass(frozen=True)
class Probe:
    """What the capability probe found (``zk_transport_backend`` and
    the pytest skip markers read this)."""

    platform: str
    uring: bool
    uring_reason: str
    mmsg: bool
    mmsg_reason: str
    forced: str | None
    chosen: str

    def available(self, backend: str) -> bool:
        if backend == 'uring':
            return self.uring
        if backend == 'mmsg':
            return self.mmsg
        return True


#: Cached CAPABILITY results only — the env force is re-read on every
#: probe() call (like cork_default), so tests and the chaos CLI can
#: flip ZKSTREAM_TRANSPORT mid-process.
_caps_cache: tuple[tuple[bool, str], tuple[bool, str]] | None = None


def _probe_uring() -> tuple[bool, str]:
    """Can this process create an io_uring?  Needs Linux, the native
    extension (the ring lives in native/zkwire_ext.c), and a kernel
    that answers io_uring_setup (>= 5.1)."""
    if not sys.platform.startswith('linux'):
        return False, 'not linux'
    from ..utils.native import get_ext
    ext = get_ext()
    if ext is None:
        return False, 'native ext unavailable (build pending or off)'
    if not hasattr(ext, 'uring_create'):
        return False, 'native ext predates uring support'
    try:
        ring = ext.uring_create(8)
    except OSError as e:
        return False, 'io_uring_setup: %s' % (e.strerror or e,)
    ext.uring_close(ring)
    return True, 'ok'


def _probe_mmsg() -> tuple[bool, str]:
    if not hasattr(os, 'writev'):
        return False, 'os.writev unavailable'
    if sys.platform.startswith('win'):
        return False, 'not posix'
    return True, 'ok'


def probe(refresh: bool = False) -> Probe:
    """Resolve the process's transport tier: capability probe
    (cached; ``refresh=True`` re-probes — tests that build the native
    extension mid-process use it, and a tier created before the
    background ext build lands simply runs one tier lower) plus the
    env force, re-read every call."""
    global _caps_cache
    if _caps_cache is None or refresh:
        _caps_cache = (_probe_uring(), _probe_mmsg())
    (uring_ok, uring_why), (mmsg_ok, mmsg_why) = _caps_cache
    forced = os.environ.get(TRANSPORT_ENV) or None
    if forced is not None and forced not in BACKENDS:
        forced = None
    order = BACKENDS[BACKENDS.index(forced):] if forced else BACKENDS
    chosen = 'asyncio'
    for b in order:
        if (b == 'uring' and uring_ok) or (b == 'mmsg' and mmsg_ok) \
                or b == 'asyncio':
            chosen = b
            break
    return Probe(platform=sys.platform, uring=uring_ok,
                 uring_reason=uring_why, mmsg=mmsg_ok,
                 mmsg_reason=mmsg_why, forced=forced, chosen=chosen)


def backend_default() -> str:
    """The process-wide backend (env force resolved against the
    probe) — what a knobless ZKServer/Client runs."""
    return probe().chosen


def resolve_backend(arg: str | None) -> str:
    """Resolve an explicit constructor knob ('uring'|'mmsg'|'asyncio',
    None = process default) against availability, falling down the
    tier order like the env force does."""
    if arg is None:
        return backend_default()
    if arg not in BACKENDS:
        raise ValueError('unknown transport backend %r (choose from '
                         '%s)' % (arg, '|'.join(BACKENDS)))
    p = probe()
    for b in BACKENDS[BACKENDS.index(arg):]:
        if p.available(b):
            return b
    return 'asyncio'


class _Entry:
    """One connection's slot in the tier: the transport accessor (the
    live asyncio transport, or None once the socket is gone), the
    legacy sink for fallback writes, and the chunks deferred to the
    next tick submission.  The resolved fd is cached keyed on the
    transport's identity — safe against fd reuse because it is only
    consulted while ``transport_fn()`` returns that same, still-open
    transport object.  Its receive half, on a tier that owns the
    receive (``TransportTier.rx_adopt``): the transport whose reading
    the tier took over, the receiver's token for it, and where the
    connection wants its bytes, its EOF and its error."""

    __slots__ = ('transport_fn', 'write', 'chunks', 'nbytes',
                 'batch', 'flying', 'stamps', '_t', '_fd',
                 'rx_transport', 'rx_token', 'on_bytes', 'on_eof',
                 'on_error', 'sink')

    def __init__(self, write, transport_fn):
        self.write = write              # the plane's asyncio sink
        self.transport_fn = transport_fn
        self.chunks: list[bytes] = []
        self.nbytes = 0
        #: The sender's batch that holds this connection's last raw
        #: flush (0 = none in flight) and that flush's bytes: until the
        #: batch is reaped nothing more of the connection is submitted.
        self.batch = 0
        self.flying = 0
        #: Profiler sessions only (else empty): the stage stamps of
        #: the requests whose bytes are in ``chunks`` (SendPlane.stamps)
        self.stamps: list = []
        self._t = None
        self._fd = -1
        #: None = asyncio's protocol push; else the adopted transport,
        #: and its token once ``_rx_claim`` registered the fd (0 until)
        self.rx_transport = None
        self.rx_token = 0
        self.on_bytes = self.on_eof = self.on_error = None
        #: ``(accumulator, owner, conn)`` while the connection's bytes
        #: may go straight where ``on_bytes`` would end by putting them
        #: (``TransportTier.rx_sink``), else None
        self.sink = None

    def resolve_fd(self, t) -> int:
        if t is self._t:
            return self._fd
        fd = -1
        sock = t.get_extra_info('socket')
        if sock is not None:
            try:
                fd = sock.fileno()
            except (OSError, ValueError):
                fd = -1
        self._t = t
        self._fd = fd
        return fd

    def take(self) -> list[bytes]:
        chunks = self.chunks
        self.chunks = []
        self.nbytes = 0
        self.stamps = []
        return chunks


class TransportTier:
    """One event loop's batched submission queue: SendPlanes enqueue
    their flushed chunk lists here instead of writing, and ONE
    deferred callback per busy tick submits every dirty connection's
    buffer in a single batched syscall chain."""

    def __init__(self, backend: str, collector=None,
                 plane: str = 'server', ledger=None):
        assert backend in ('uring', 'mmsg'), backend
        self.backend = backend
        self.plane = plane
        #: Optional utils/metrics.TickLedger: submission time is the
        #: tick's ``cork_flush`` phase (the same phase the per-plane
        #: asyncio writes account under, so ledger shares stay
        #: comparable across backends).
        self.ledger = ledger
        self._dirty: list[_Entry] = []
        #: Planes that corked frames this tick and delegated their
        #: tick flush here: ONE loop callback flushes them all and
        #: submits the resulting batch — the per-connection
        #: ``call_soon`` the legacy path pays per tick (PR 6 measured
        #: it at ~45% of a wide fan-out; the reply path paid it
        #: until now) collapses into this single callback.
        self._tick_work: list = []
        #: The loop holding the pending tick callback (None = none).
        #: Loop identity, not a bool: a callback stranded on a dead
        #: loop (a client reused across asyncio.run calls) must not
        #: block scheduling on the next loop forever.
        self._scheduled_on = None
        self._uring = None
        self._uring_dead = False
        #: The native sender (``attach_sender``: the loop's shared
        #: client tier alone): whether deep batches are handed over,
        #: the thread's capsule (and the extension that made it) once
        #: the first one was, the loop its ``eventfd`` is a reader of,
        #: and batch id -> the batch's ``(entry, chunks, nbytes)`` —
        #: which is also what keeps the thread's buffers referenced.
        self._handoff = False
        self._sender = None
        self._ext = None
        self._reader_loop = None
        self._inflight: dict[int, list] = {}
        #: The native receiver (``attach_sender`` too): whether new
        #: connections' receive is taken over, the thread's capsule
        #: once the first one was, and token -> entry for every
        #: connection it reads.
        self._rx_on = False
        self._receiver = None
        self._rx: dict[int, _Entry] = {}
        #: token -> the bytearray a reap's C call appends that
        #: connection's bytes to (:meth:`rx_sink`: the entries of
        #: ``_rx`` that have a sink), and owner -> how many of them are
        #: its (one owner, and a reap tells it once what it fed)
        self._sinks: dict[int, bytearray] = {}
        self._sink_owners: dict = {}
        #: the last reap was inside a profiler session: it stamped the
        #: connections it fed, and the first reap outside one unstamps
        self._rx_stamped = False
        #: Profiler sessions only, inside :meth:`_tick` (else 0): when
        #: this tick's flush began — the ``t_flush`` of the requests
        #: whose bytes it submits or hands over (utils/trace.py).
        self._flush_t0 = 0
        self.syscalls = 0        # lifetime submissions (tests/mntr)
        self.submissions = 0     # batched submit rounds
        #: connection flushes submitted raw, those of them the kernel
        #: took only part of (its socket buffer filled inside a large
        #: request or reply), and the bytes of their remainders, which
        #: went through the asyncio transport instead
        self.flushes = 0
        self.partial_flushes = 0
        self.requeued_bytes = 0
        #: of ``flushes`` / ``submissions``, those the sender took
        self.offloaded_flushes = 0
        self.offloaded_batches = 0
        #: ``on_bytes`` deliveries the receiver's reaps made, and the
        #: reaps that brought any
        self.received_reads = 0
        self.received_batches = 0
        #: of ``received_reads``, the deliveries a reap's C call made
        #: itself, into the connection's sink (:meth:`rx_sink`)
        self.received_fed = 0
        #: Clients holding a :class:`TierLease` on this tier (a
        #: server's tier is its own and stays at 0).
        self.refs = 0
        #: The tick's host span (utils/trace.host_span): the client
        #: plane's ``client.flush`` is the engagement counter beside
        #: ``client.submit``.
        self._span = plane + '.flush'
        self._send_span = plane + '.send'
        self._handoff_span = plane + '.handoff'
        self._reap_span = plane + '.reap'
        self._rx_reap_span = plane + '.rx_reap'
        self._rx_reaped_span = plane + '.rx_reaped'
        self._rx_span = plane + '.rx'
        self._rx_fed_span = plane + '.rx_fed'
        self._recv_span = plane + '.recv'
        #: The tier's own series: registered with the collector it
        #: was given, standalone without one (a loop's shared client
        #: tier belongs to no client's collector; each joined client
        #: adopts them).
        source = collector if collector is not None else Collector()
        self.syscall_ctr = source.counter(
            METRIC_FLUSH_SYSCALLS,
            'Write submissions issued by the outbound plane, by '
            'plane and backend')
        self.depth_hist = source.histogram(
            METRIC_SUBMIT_DEPTH,
            'Connections covered per batched transport '
            'submission, by plane and backend',
            buckets=DEPTH_BUCKETS)
        self.partial_ctr = source.counter(
            METRIC_FLUSH_PARTIAL,
            'Raw connection flushes the kernel took only part of, '
            'by plane')
        self.requeued_ctr = source.counter(
            METRIC_FLUSH_REQUEUED,
            'Bytes of partial raw flushes re-queued through the '
            'asyncio transport, by plane')
        self.offloaded_ctr = source.counter(
            METRIC_FLUSH_OFFLOADED,
            'Raw connection flushes sent by the native sender thread '
            'instead of the event loop, by plane')
        self.received_ctr = source.counter(
            METRIC_RECV_OFFLOADED,
            'Connection reads received by the native receiver thread '
            'instead of the event loop, by plane')
        self.fed_ctr = source.counter(
            METRIC_RECV_FED,
            'Of those, the reads a reap appended to the fleet '
            "ingest's slot in its one native call, by plane")

    @property
    def series(self) -> tuple:
        """The tier's own series (what a joined client's collector
        adopts)."""
        return (self.syscall_ctr, self.depth_hist, self.partial_ctr,
                self.requeued_ctr, self.offloaded_ctr,
                self.received_ctr, self.fed_ctr)

    def attach_sender(self) -> None:
        """Hand deep ``mmsg`` batches to a native sender thread from
        now on (made when the first such batch comes, so a lone
        client's tier never starts one), and the receive of every
        connection made from now on to a native receiver thread (made
        when the first one is).  ``uring`` has nothing to hand over:
        its batch is one ``io_uring_enter``, and its receive stays
        asyncio's."""
        self._handoff = self._rx_on = self.backend == 'mmsg'

    # -- SendPlane-facing API --

    def channel(self, write, transport_fn) -> _Entry:
        """One per SendPlane: created at plane construction, reused
        for the connection's lifetime."""
        return _Entry(write, transport_fn)

    def enqueue(self, entry: _Entry, chunks: list[bytes],
                nbytes: int) -> None:
        """Defer one plane flush to the tick submission.  The entry's
        transport is resolved at submit time — an entry whose
        transport is already gone falls back to its plane sink there
        (where the write is a no-op on a dead connection anyway)."""
        if not entry.chunks:
            self._dirty.append(entry)
            entry.chunks = chunks       # adopt: the plane released it
        else:
            entry.chunks.extend(chunks)
        entry.nbytes += nbytes
        if len(entry.chunks) > IOV_GUARD:
            # bound the iovec array a pathological tick could build
            entry.chunks = [b''.join(entry.chunks)]
        self._schedule()

    def _schedule(self) -> None:
        """Ensure the tick callback is pending on the CURRENT loop.
        ``is_closed`` on the stored loop (cheap, ~75 ns) — not a loop
        compare via ``get_running_loop`` (which pays a getpid syscall
        per call on this image) — detects a callback stranded on a
        dead loop, so a tier reused across asyncio.run calls can
        never wedge."""
        sched = self._scheduled_on
        if sched is not None and not sched.is_closed():
            return
        loop = ambient_loop()
        self._scheduled_on = loop
        loop.call_soon(self._tick)

    def schedule_flush(self, plane) -> None:
        """Register one plane for the tick's shared flush callback
        (SendPlane.send calls this instead of scheduling its own
        ``call_soon`` when a tier is attached).  The plane guards
        against double registration with its own ``_scheduled``
        flag."""
        self._tick_work.append(plane._tick_flush)
        self._schedule()

    def schedule_call(self, fn) -> None:
        """Run ``fn`` inside the tick callback, BEFORE the batched
        submission — for flush work that feeds the tier (the watch
        table's per-shard fan-out flushes): scheduling it as its own
        ``call_soon`` would land its bytes one loop hop after the
        submission that should have carried them."""
        self._tick_work.append(fn)
        self._schedule()

    def drain(self, entry: _Entry) -> None:
        """Hard flush: submit THIS entry's pending bytes now (the
        flush_hard contract — bytes on the wire before return).  The
        entry may stay in the dirty list; the tick submission skips
        entries whose chunks are already gone."""
        self.quiesce(entry)
        if entry.chunks:
            self._submit([entry])

    def discard(self, entry: _Entry) -> None:
        """Connection aborted: its pending bytes have nowhere to go
        (SendPlane.reset)."""
        self.quiesce(entry)
        entry.take()

    def quiesce(self, entry: _Entry) -> None:
        """Return only once no send of this connection is in flight on
        the sender thread, its result applied: before a hard flush
        writes behind it, and before the connection's socket is closed
        (asyncio closes it right after ``connection_lost``) — a send
        into an fd number that another connection has since been given
        would put one session's request on another's wire."""
        if entry.batch:
            self._ext.sender_wait(self._sender, entry.batch)
            self._reap()

    # -- the tick submission --

    def _tick(self) -> None:
        """The tick boundary: run every registered flush (plane tick
        flushes and shard fan-out flushes — their enqueues land while
        the schedule slot is still held, so they cannot re-schedule),
        then submit the whole dirty set as one batch — flush and
        submission share the one callback, so batched bytes reach the
        kernel in the same loop iteration the legacy per-plane
        flushes would have used.

        One raising flush must not take the rest of the tick with it:
        the legacy path isolated a callback failure to its one
        connection (each flush was its own ``call_soon``), and the
        shared callback must be no weaker — errors are logged per
        flush, and the submission + schedule-slot release always
        run."""
        sp = host_span(self._span, accumulate=True)
        with sp:
            if sp is not NO_SPAN:
                self._flush_t0 = sp.t0_ns
            if self._inflight:
                # whatever the sender finished meanwhile: its entries
                # may be in this tick's dirty set
                self._reap()
            work, self._tick_work = self._tick_work, []
            try:
                for fn in work:
                    try:
                        fn()
                    except Exception:
                        log.exception('transport tick flush failed')
            finally:
                self._scheduled_on = None
                dirty, self._dirty = self._dirty, []
                self._submit(dirty)
                self._flush_t0 = 0

    def _count(self, n: int, backend: str) -> None:
        self.syscalls += n
        if n:
            self.syscall_ctr.increment(
                {'plane': self.plane, 'backend': backend}, by=n)

    def _submit(self, entries: list[_Entry]) -> None:
        """Resolve each entry's fd and submit the whole batch through
        the backend; anything raw-ineligible (no socket, transport
        already buffering, closing) routes through its asyncio sink —
        the FIFO transport buffer keeps ordering either way."""
        batch_fds: list[int] = []
        batch_chunks: list[list[bytes]] = []
        raw_entries: list[tuple[_Entry, list[bytes], int]] = []
        for e in entries:
            chunks = e.chunks
            if not chunks:
                continue        # drained hard mid-tick, or reset
            if e.batch:
                # order on a connection: nothing more of it leaves,
                # raw or through its sink, before its batch in flight
                # is reaped; it stays dirty and _reap schedules the
                # tick that takes these chunks
                self._dirty.append(e)
                continue
            # take the chunks NOW: a hard-drained entry re-dirtied in
            # the same tick appears in `entries` twice, and only an
            # emptied entry makes the second visit a no-op
            nbytes = e.nbytes
            e.chunks = []
            e.nbytes = 0
            if e.stamps:
                # a tick's flush stamps its start; a hard drain, now
                stamp_flush(e.stamps, self._flush_t0)
            fd = -1
            t = e.transport_fn()
            if t is not None:
                # fast paths over the selector transport's private
                # state: is_closing() is an attribute read behind a
                # method call, and get_write_buffer_size() allocates
                # (sum(map(len, deque))) — at 10k dirty connections
                # per tick both matter.  Transports without the
                # attributes (uvloop, proactor) take the public API.
                closing = getattr(t, '_closing', None)
                if closing is None:
                    closing = t.is_closing()
                if not closing:
                    wbuf = getattr(t, '_buffer', None)
                    if (not wbuf if wbuf is not None
                            else t.get_write_buffer_size() == 0):
                        fd = e.resolve_fd(t)
            if fd < 0:
                self._count(1, 'asyncio')
                e.write(chunks[0] if len(chunks) == 1
                        else b''.join(chunks))
                continue
            batch_fds.append(fd)
            batch_chunks.append(chunks)
            raw_entries.append((e, chunks, nbytes))
        if not batch_fds:
            return
        if (self._handoff and len(batch_fds) >= OFFLOAD_MIN_SENDS
                and self._hand_over(batch_fds, batch_chunks,
                                    raw_entries)):
            return
        led = self.ledger
        if led is not None:
            led.enter('cork_flush')
        t0 = perf_counter_ns()
        try:
            results, nsys = self._submit_raw(batch_fds, batch_chunks)
        finally:
            if led is not None:
                led.exit()
        host_add(self._send_span, len(batch_fds), perf_counter_ns() - t0)
        self._submitted(len(batch_fds), nsys)
        self._apply(raw_entries, results)

    def _submitted(self, depth: int, nsys: int) -> None:
        self.submissions += 1
        self.flushes += depth
        self._count(nsys, self.backend)
        self.depth_hist.observe(
            depth, {'plane': self.plane, 'backend': self.backend})

    def _apply(self, raw_entries, results) -> None:
        """A raw batch's results, inline or reaped."""
        for (e, chunks, nbytes), res in zip(raw_entries, results):
            if res != nbytes:       # the hot path writes everything
                self._settle(e, chunks, nbytes, res)

    # -- the native sender (the loop's shared client tier) --

    def _hand_over(self, fds, chunklists, raw_entries) -> bool:
        """Queue one raw batch for the sender thread; False when there
        is no sender to be had (the extension is not built, or not yet:
        the batch then goes inline)."""
        loop = ambient_loop()
        if self._sender is None:
            ext = _sender_ext()
            if ext is None:
                return False
            try:
                self._sender = ext.sender_create()
            except OSError:
                self._handoff = False   # no thread to be had here
                return False
            self._ext = ext
            self._move_reader(loop)     # its eventfd is no reader yet
        elif loop is not self._reader_loop:
            self._move_reader(loop)
        with host_span(self._handoff_span, accumulate=True):
            batch = self._ext.sender_submit(self._sender, fds,
                                            chunklists)
        self._inflight[batch] = raw_entries
        for e, _chunks, nbytes in raw_entries:
            e.batch = batch
            e.flying = nbytes
        depth = len(fds)
        self.offloaded_batches += 1
        self.offloaded_flushes += depth
        self.offloaded_ctr.increment({'plane': self.plane}, by=depth)
        self._submitted(depth, depth)
        return True

    def _move_reader(self, loop) -> None:
        """The sender's and the receiver's ``eventfd`` are readers of
        the loop the tier works on (a tier reused across
        ``asyncio.run`` calls moves them; what an ended loop left in
        flight, or unreaped, is reaped on the next)."""
        ext = self._ext
        old, self._reader_loop = self._reader_loop, loop
        for fd, reap in (
                (-1 if self._sender is None
                 else ext.sender_fileno(self._sender), self._reap),
                (-1 if self._receiver is None
                 else ext.receiver_fileno(self._receiver),
                 self._rx_reap)):
            if fd < 0:
                continue
            if old is not None and not old.is_closed():
                old.remove_reader(fd)
            if loop is not None:
                loop.add_reader(fd, reap)

    def _reap(self) -> None:
        """Apply what the sender finished, oldest batch first, exactly
        as the inline submission's tail does, and schedule the tick for
        entries that were held behind their batch."""
        if self._sender is None:
            return      # a wake-up that outlived close()
        with host_span(self._reap_span, accumulate=True):
            held = False
            for batch, results, busy_ns in \
                    self._ext.sender_reap(self._sender):
                raw_entries = self._inflight.pop(batch)
                host_add(self._send_span, len(results), busy_ns)
                for e, _chunks, _nbytes in raw_entries:
                    e.batch = e.flying = 0
                    if e.chunks:
                        held = True
                self._apply(raw_entries, results)
            if held:
                self._schedule()

    # -- the native receiver (the loop's shared client tier) --

    def rx_adopt(self, entry: _Entry, transport, on_bytes, on_eof,
                 on_error) -> bool:
        """Take over the receive of one connection that was just made
        (its protocol's ``connection_made``): pause the transport's
        reading now, register the fd with the receiver thread one
        callback later (:meth:`_rx_claim`).  False — and nothing
        touched — where asyncio's protocol push stays: a tier that was
        not armed, a loop that is no selector loop, no fd, no extension
        (yet), no thread to be had."""
        if not self._rx_on:
            return False
        loop = ambient_loop()
        if (not hasattr(loop, '_remove_reader')
                or entry.resolve_fd(transport) < 0):
            return False
        if self._receiver is None:
            ext = _receiver_ext()
            if ext is None:
                return False
            try:
                self._receiver = ext.receiver_create()
            except OSError:
                self._rx_on = False     # no thread to be had here
                return False
            self._ext = ext
            self._move_reader(loop)     # its eventfd is no reader yet
        elif loop is not self._reader_loop:
            self._move_reader(loop)
        if entry.rx_transport is not None:
            # the entry's earlier socket never saw connection_lost
            self.rx_forget(entry, entry.rx_transport, deliver=False)
        transport.pause_reading()
        entry.rx_transport = transport
        entry.on_bytes, entry.on_eof, entry.on_error = (
            on_bytes, on_eof, on_error)
        loop.call_soon(self._rx_claim, entry, transport)
        return True

    def _rx_claim(self, entry: _Entry, transport) -> None:
        """One callback behind ``connection_made``: a 3.10-3.11
        selector transport queued its own reader registration there
        and checks only ``_closing``, not ``_paused``, so it has the fd
        again by now (io/ingress.py ``_adopted`` has the same window);
        bytes that landed in it came through ``data_received`` and the
        same ``on_bytes``.  Take the fd from the loop's selector, then
        hand it to the thread — never both at once."""
        if entry.rx_transport is not transport or entry.rx_token:
            return          # forgotten, or given back, meanwhile
        fd = entry.resolve_fd(transport)
        try:
            if transport.is_closing() or self._receiver is None:
                raise OSError(errno.EBADF, 'connection is closing')
            ambient_loop()._remove_reader(fd)
            token = self._ext.receiver_add(self._receiver, fd)
        except (OSError, ValueError, RuntimeError):
            self._rx_give_back(entry)
            return
        entry.rx_token = token
        self._rx[token] = entry
        if entry.sink is not None:
            self._sink_on(token, entry.sink)

    def _rx_give_back(self, entry: _Entry) -> None:
        """The connection's receive returns to its asyncio transport:
        the thread does not read it (or no longer: what it had is
        delivered first)."""
        transport = entry.rx_transport
        self._rx_release(entry)
        entry.rx_transport = None
        if transport is not None and not transport.is_closing():
            try:
                transport.resume_reading()
            except (OSError, RuntimeError):
                pass        # its loop is closed: nobody reads it again

    def _rx_release(self, entry: _Entry, deliver: bool = True) -> None:
        """Out of the receiver thread: returns only once no ``recv`` of
        the fd is in flight there, and first delivers, in order, what
        was received and not yet reaped."""
        token, entry.rx_token = entry.rx_token, 0
        if token and self._rx.pop(token, None) is not None:
            if entry.sink is not None:
                self._sink_off(token, entry.sink)
            left = self._ext.receiver_forget(self._receiver, token)
            if deliver:
                for data in left:
                    self._rx_deliver(entry, data)

    def rx_forget(self, entry: _Entry, transport,
                  deliver: bool = True) -> None:
        """The connection's socket is about to be closed
        (``connection_lost``: asyncio closes it right after), or its
        reading is to stop: take it from the receiver thread
        (:meth:`_rx_release`) — an fd is never closed, and so never
        reused, under a receive in flight.  Not from inside one of the
        tier's own deliveries."""
        if entry.rx_transport is not transport:
            return
        self._rx_release(entry, deliver)
        entry.rx_transport = None
        entry.on_bytes = entry.on_eof = entry.on_error = None

    def rx_sink(self, entry: _Entry, sink) -> None:
        """``sink`` = ``(accumulator, owner, conn)``: from now on a
        reap appends what the receiver thread has for this connection
        straight to ``accumulator`` (a bytearray) inside its one C
        call, instead of handing ``on_bytes`` a ``bytes`` — the caller
        saying that ``on_bytes`` would do exactly that append and
        nothing else.  ``owner`` (the fleet ingest) is then told once a
        reap, not once a connection: ``owner.fed(nbytes, t_rx)``, and
        inside a profiler session first ``owner.fed_mark(conn, end,
        t_rx)`` a connection fed (``end``: the accumulator's length
        with its bytes in it; ``t_rx``: the start of the reap's C
        call, 0 outside a session).  None withdraws it: the
        connection's bytes come through ``on_bytes`` again, behind
        what was appended.  The sink follows the entry, not the
        socket: it stands only while the receiver thread reads the
        connection (``rx_token``), so a connection that is not
        adopted, paused (``rx_forget``) or given back has none until
        it is read here again."""
        was, entry.sink = entry.sink, sink
        token = entry.rx_token
        if token:
            if was is not None:
                self._sink_off(token, was)
            if sink is not None:
                self._sink_on(token, sink)

    def _sink_on(self, token: int, sink: tuple) -> None:
        self._sinks[token] = sink[0]
        owners = self._sink_owners
        owners[sink[1]] = owners.get(sink[1], 0) + 1

    def _sink_off(self, token: int, sink: tuple) -> None:
        del self._sinks[token]
        owners = self._sink_owners
        left = owners[sink[1]] - 1
        if left:
            owners[sink[1]] = left
        else:
            del owners[sink[1]]

    def _rx_fed(self, fed: tuple, t_rx: int) -> int:
        """What a reap's C call appended to sinks: the owners told,
        the counters fed.  Returns the connections fed."""
        n, nbytes, ns, each = fed
        owners = self._sink_owners
        if each is not None and len(owners) > 1:
            # whose bytes: every owner hears of its own
            rx = self._rx
            owed: dict = {}
            for token, took in each:
                owner = rx[token].sink[1]
                owed[owner] = owed.get(owner, 0) + took
        else:
            owed = dict.fromkeys(owners, nbytes)
        try:
            if t_rx:
                rx = self._rx
                for token, _took in each:
                    buf, owner, conn = rx[token].sink
                    owner.fed_mark(conn, len(buf), t_rx)
            for owner, took in owed.items():
                owner.fed(took, t_rx)
        except Exception:
            # the bytes are in their slots; the deliveries go on
            log.exception('transport receive sink owner failed')
        if t_rx:
            # a fed connection is one ``client.rx`` — its way into the
            # slot, here inside the C call — and one of them fed
            host_add(self._rx_span, n, ns)
            host_add(self._rx_fed_span, n, ns)
        self.received_fed += n
        self.fed_ctr.increment({'plane': self.plane}, by=n)
        return n

    def _rx_deliver(self, entry: _Entry, data) -> bool:
        """One ``bytes | -errno`` of a reap or a hand-back to its
        connection; True for bytes.  A raising connection keeps its
        error to itself (``_tick``'s rule)."""
        try:
            if data.__class__ is not bytes:
                entry.on_error(OSError(-data, os.strerror(-data)))
            elif data:
                entry.on_bytes(data)
                return True
            else:
                entry.on_eof()
        except Exception:
            log.exception('transport receive callback failed')
            return bool(data)
        return False

    def _rx_reap(self) -> None:
        """The receiver's ``eventfd`` is readable: hand every
        connection what the thread received for it, in the thread's
        order.  A token no connection holds any more (forgotten since)
        is dropped: a closed connection's bytes reach nobody else."""
        if self._receiver is None:
            return      # a wake-up that outlived close()
        after = _reaping.pending = []
        try:
            with host_span(self._rx_reap_span, accumulate=True) as sp:
                # a profiler session stamps every fed connection with
                # ONE clock read: the receive call that brought its
                # bytes to the loop is this one
                t_rx = 0 if sp is NO_SPAN else perf_counter_ns()
                if self._rx_stamped and not t_rx:
                    self._rx_unstamp()
                items, calls, ns, fed = self._ext.receiver_reap(
                    self._receiver, self._sinks,
                    t_rx != 0 or len(self._sink_owners) > 1)
                if calls:
                    host_add(self._recv_span, calls, ns)
                n = 0
                if fed is not None:
                    self._rx_stamped = t_rx != 0
                    n = self._rx_fed(fed, t_rx)
                # what has no sink, and every end, after the feeds
                rx = self._rx
                for token, data in items:
                    e = rx.get(token)
                    if e is not None and self._rx_deliver(e, data):
                        n += 1
        finally:
            _reaping.pending = None
        if n:
            host_add(self._rx_reaped_span, n, 0)
            self.received_batches += 1
            self.received_reads += n
            self.received_ctr.increment({'plane': self.plane}, by=n)
        # every connection has its bytes: what the deliveries left to
        # be done with all of them (``after_reap``; an error goes to
        # the loop's exception handler, as one of a tick's does)
        for fn in after:
            fn()

    def _rx_unstamp(self) -> None:
        """The profiler session is over: the connections its reaps
        stamped (``fed_mark``) carry no receive time into a later one
        (``ZKConnection._sock_data`` does the same for its own)."""
        self._rx_stamped = False
        for e in self._rx.values():
            if e.sink is not None:
                e.sink[2]._rx_t0 = 0

    def _settle(self, entry: _Entry, chunks: list[bytes],
                nbytes: int, res: int) -> None:
        """Apply one incomplete raw-write result: a short or refused
        write hands the remainder to the asyncio transport (which
        queues FIFO and re-enables raw writes only once drained); a
        dead-socket errno drops the bytes exactly as a closed
        transport would.  Transient errnos (backpressure, a failed
        ring submission that provably sent nothing) resend through
        the transport — never a silent drop on a live connection."""
        if res < 0:
            if -res not in _DEAD_ERRNOS:
                self._count(1, 'asyncio')
                entry.write(b''.join(chunks))
            return
        if res >= nbytes:
            return
        # partial write: the kernel buffer filled mid-entry — the
        # remainder must queue in the transport so later ticks (which
        # see a nonzero write buffer) stay behind it
        self.partial_flushes += 1
        self.requeued_bytes += nbytes - res
        labels = {'plane': self.plane}
        self.partial_ctr.increment(labels)
        self.requeued_ctr.increment(labels, by=nbytes - res)
        self._count(1, 'asyncio')
        # the remainder alone, copied once: whole chunks the kernel
        # took are skipped, not joined and sliced away
        for i, c in enumerate(chunks):
            if res < len(c):
                break
            res -= len(c)
        head = memoryview(chunks[i])[res:]
        entry.write(bytes(head) if i + 1 == len(chunks)
                    else b''.join([head] + chunks[i + 1:]))

    # -- backends --

    def _submit_raw(self, fds, chunklists) -> tuple[list[int], int]:
        if self.backend == 'uring':
            out = self._submit_uring(fds, chunklists)
            if out is not None:
                return out
            # ring creation failed after probe said OK (fd limits,
            # seccomp): latch down to the mmsg path for this tier
        return self._submit_mmsg(fds, chunklists)

    def _submit_uring(self, fds, chunklists
                      ) -> tuple[list[int], int] | None:
        if self._uring_dead:
            return None
        from ..utils.native import get_ext
        ext = get_ext()
        if ext is None or not hasattr(ext, 'uring_submit'):
            return None
        if self._uring is None:
            try:
                self._uring = ext.uring_create(URING_DEPTH)
            except OSError:
                self._uring_dead = True
                return None
        try:
            results, enters = ext.uring_submit(self._uring, fds,
                                               chunklists)
        except OSError:
            self._uring_dead = True
            return None
        return results, enters

    def _submit_mmsg(self, fds, chunklists) -> tuple[list[int], int]:
        from ..utils.native import get_ext
        ext = get_ext()
        if ext is not None and hasattr(ext, 'submit_writev'):
            # ONE C call for the whole batch: per-entry writev loops
            # (the join-and-write boundary) without a Python-level
            # join or per-connection Python syscall dispatch
            return ext.submit_writev(fds, chunklists), len(fds)
        results = []
        for fd, chunks in zip(fds, chunklists):
            try:
                results.append(os.writev(fd, chunks))
            except BlockingIOError:
                results.append(-errno.EAGAIN)
            except OSError as e:
                results.append(-(e.errno or 1))
        return results, len(fds)

    def close(self) -> None:
        """Release the ring fd + mmaps now (ZKServer.stop /
        Client.close call this — the plane/entry closures hold the
        tier in reference cycles, so refcount-time release never
        happens; the capsule destructor remains the GC backstop).
        The next submission lazily re-creates the ring, so a
        restarted server/client keeps working."""
        if self._uring is not None:
            from ..utils.native import get_ext
            ext = get_ext()
            if ext is not None:
                try:
                    ext.uring_close(self._uring)
                except (OSError, ValueError):
                    pass
            self._uring = None
        if self._sender is not None:
            # what is in flight goes out and is settled
            if self._inflight:
                self._ext.sender_wait(self._sender, max(self._inflight))
            self._reap()
        if self._receiver is not None:
            # what was received is delivered; connections that live on
            # read through their own transports again
            self._rx_reap()
        if self._reader_loop is not None:
            self._move_reader(None)
        if self._sender is not None:
            # the thread is joined; the next deep batch starts another
            self._ext.sender_close(self._sender)
            self._sender = None
        if self._receiver is not None:
            # joined likewise (no recv in flight from here on), and
            # the next connection made starts another
            for e in list(self._rx.values()):
                self._rx_give_back(e)
            self._ext.receiver_close(self._receiver)
            self._receiver = None


def _ext_with(name: str):
    """The extension, if it is built and has ``name``."""
    from ..utils.native import get_ext
    ext = get_ext()
    return ext if hasattr(ext, name) else None


def _sender_ext():
    return _ext_with('sender_submit')


def _receiver_ext():
    return _ext_with('receiver_reap')


def make_tier(arg: str | None, collector=None, plane: str = 'server',
              ledger=None) -> TransportTier | None:
    """Build the tier for one server/client, or None when the
    resolved backend is ``asyncio`` (planes then keep their legacy
    write path untouched)."""
    backend = resolve_backend(arg)
    if backend == 'asyncio':
        return None
    return TransportTier(backend, collector=collector, plane=plane,
                         ledger=ledger)


#: The client plane's shared tiers: loop -> {backend: tier}.  Weak on
#: the loop; a tier leaves when its last lease is released, and a
#: closed loop's tiers are swept by the next join (a tick stranded on
#: a dead loop holds that loop, so the weak key alone would not).
_loop_tiers: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()
#: Loops in different threads join and leave through one registry.
_loop_tiers_lock = threading.Lock()


class TierLease:
    """One client's reference on its event loop's shared client-plane
    tier.  ``Client.transport_tier`` reads :meth:`tier`: the first
    read on a loop joins that loop's tier for the client's backend
    (building it if this client is the first there), and a client
    reused on a later loop (one ``asyncio.run`` after another) moves
    with it.  :meth:`release` is the client's close: the ring fd goes
    with the tier's last lease, and nothing another client has
    pending is touched (entries are per connection)."""

    __slots__ = ('backend', '_collector', '_tier', '_loop')

    def __init__(self, arg: str | None, collector):
        #: resolved once, here, as a tier of one's own was
        self.backend = resolve_backend(arg)
        #: the client's: adopts each joined tier's series
        self._collector = collector
        self._tier: TransportTier | None = None
        self._loop = None

    def tier(self) -> TransportTier | None:
        """The running loop's shared tier (None on ``asyncio``: the
        planes keep their own writes)."""
        if self.backend == 'asyncio':
            return None
        loop = ambient_loop()
        if loop is self._loop:
            return self._tier
        self.release()
        with _loop_tiers_lock:
            for dead in [lp for lp in _loop_tiers if lp.is_closed()]:
                for t in _loop_tiers.pop(dead).values():
                    t.close()
            tiers = _loop_tiers.setdefault(loop, {})
            tier = tiers.get(self.backend)
            if tier is None:
                tier = tiers[self.backend] = TransportTier(
                    self.backend, plane='client')
                tier.attach_sender()
            tier.refs += 1
        self._tier, self._loop = tier, loop
        for series in tier.series:
            self._collector.adopt(series)
        return tier

    def release(self) -> None:
        tier, loop = self._tier, self._loop
        self._tier = self._loop = None
        if tier is None:
            return
        with _loop_tiers_lock:
            tier.refs -= 1
            if tier.refs:
                return
            tiers = _loop_tiers.get(loop)
            if tiers is not None and tiers.get(tier.backend) is tier:
                del tiers[tier.backend]
                if not tiers:
                    del _loop_tiers[loop]
        tier.close()
