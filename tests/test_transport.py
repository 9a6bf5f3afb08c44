"""The batched-syscall transport tier (io/transport.py).

Covers the capability probe and its fallback order (env force falls
DOWN, never up — a forced ``uring`` on a pre-5.1 kernel runs mmsg,
and this suite stays green there via skip markers), the byte-stream
parity invariant the whole tier hangs on — every backend produces the
identical per-connection stream over the full opcode corpus, through
plane flushes, hard flushes and partial kernel writes — the
O(1)-submissions-per-tick contract with its syscall accounting
(``zookeeper_flush_syscalls_total`` / ``zookeeper_submit_depth``),
the flush_hard synchronous-delivery contract fault injection depends
on, backpressure fallback through the asyncio transport, the e2e
request/reply + notification parity across backends over real
sockets, and the ``zk_transport_backend`` mntr row."""

from __future__ import annotations

import asyncio
import errno
import os
import socket
import threading

import pytest

from zkstream_tpu.io.sendplane import SendPlane
from zkstream_tpu.io.transport import (
    BACKENDS,
    METRIC_FLUSH_OFFLOADED,
    METRIC_FLUSH_SYSCALLS,
    METRIC_SUBMIT_DEPTH,
    OFFLOAD_MIN_SENDS,
    TransportTier,
    backend_default,
    make_tier,
    probe,
    resolve_backend,
)
from zkstream_tpu import Client
from zkstream_tpu.protocol.framing import PacketCodec
from zkstream_tpu.server import ZKServer
from zkstream_tpu.utils.metrics import Collector

from test_fastencode import REPLIES, REQUESTS
from test_server_edges import RawClient

#: The batched backends this box can actually run (probe-resolved):
#: the parametrized suites cover each, and skip cleanly on platforms
#: with neither (the asyncio validator is always covered).
BATCHED = [b for b in ('uring', 'mmsg') if probe().available(b)]

needs_batched = pytest.mark.skipif(
    not BATCHED, reason='no batched transport backend on this '
    'platform (uring: %s; mmsg: %s)' % (probe().uring_reason,
                                        probe().mmsg_reason))
needs_uring = pytest.mark.skipif(
    not probe().uring,
    reason='io_uring unavailable: %s' % (probe().uring_reason,))


def _has_sender() -> bool:
    from zkstream_tpu.utils.native import ensure_ext
    return probe().mmsg and hasattr(ensure_ext(), 'sender_submit')


needs_sender = pytest.mark.skipif(
    not _has_sender(), reason='no native sender: the mmsg backend or '
    'the extension is missing')

#: How a raw batch leaves: sent inline on the loop's thread (any
#: batched backend), or handed to the tier's native sender thread
#: (mmsg, the loop's shared client tier, OFFLOAD_MIN_SENDS deep).
SUBMISSIONS = ['inline'] + (['handed_over'] if _has_sender() else [])


# -- a real transport over a socketpair --------------------------------

async def _pipe():
    """A live asyncio transport writing into a readable peer socket —
    the smallest thing the tier can resolve a raw fd from."""
    left, right = socket.socketpair()
    left.setblocking(False)
    right.setblocking(False)
    loop = asyncio.get_running_loop()
    transport, _ = await loop.create_connection(asyncio.Protocol,
                                                sock=left)
    return transport, right


async def _read_exact(sock, n, timeout=5.0) -> bytes:
    data = b''
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while len(data) < n:
        try:
            chunk = sock.recv(1 << 16)
            if not chunk:
                break
            data += chunk
        except BlockingIOError:
            pass
        assert loop.time() < deadline, \
            'timed out: %d/%d bytes' % (len(data), n)
        await asyncio.sleep(0)
    return data


# -- probe + resolution -------------------------------------------------

def test_probe_shape_and_default():
    p = probe()
    assert p.chosen in BACKENDS
    assert p.available(p.chosen)
    assert backend_default() == p.chosen
    # the chosen tier is the best available one
    for b in BACKENDS:
        if b == p.chosen:
            break
        assert not p.available(b)


def test_env_force_falls_down_never_up(monkeypatch):
    monkeypatch.setenv('ZKSTREAM_TRANSPORT', 'asyncio')
    assert backend_default() == 'asyncio'
    monkeypatch.setenv('ZKSTREAM_TRANSPORT', 'mmsg')
    assert backend_default() == ('mmsg' if probe().mmsg else 'asyncio')
    monkeypatch.setenv('ZKSTREAM_TRANSPORT', 'uring')
    d = backend_default()
    if not probe().uring:
        assert d != 'uring'        # degraded down the order
    monkeypatch.setenv('ZKSTREAM_TRANSPORT', 'bogus')
    assert backend_default() == probe().chosen   # ignored


def test_resolve_backend_rejects_unknown():
    with pytest.raises(ValueError):
        resolve_backend('sendfile')
    assert resolve_backend('asyncio') == 'asyncio'
    assert resolve_backend(None) == backend_default()


def test_make_tier_none_for_asyncio():
    assert make_tier('asyncio') is None


# -- byte-stream parity (the satellite): every backend, full corpus ----

async def _stream_through(backend: str | None,
                          frames: list[bytes]) -> bytes:
    """Push the corpus through one plane configuration — corked sends,
    a mid-stream flush_now, a hard flush, then a tail rides the tick
    flush — and return what the peer read."""
    transport, peer = await _pipe()
    try:
        tier = TransportTier(backend) if backend else None
        plane = SendPlane(transport.write, enabled=True, tier=tier,
                          transport_fn=lambda: transport)
        half = len(frames) // 2
        for f in frames[:half]:
            plane.send(f)
        plane.flush_now()            # deferred tier submission
        for f in frames[half:]:
            plane.send(f)
        plane.flush_hard()           # synchronous mid-tick drain
        for f in frames[:3]:
            plane.send(f)            # tail: tick-boundary flush
        for _ in range(4):
            await asyncio.sleep(0)
        expect = len(b''.join(frames)) + len(b''.join(frames[:3]))
        return await _read_exact(peer, expect)
    finally:
        transport.close()
        peer.close()


@needs_batched
async def test_byte_stream_parity_all_opcodes():
    """The invariant the tier hangs on: batched and asyncio backends
    produce IDENTICAL per-connection byte streams — for every opcode,
    both directions, across deferred, hard and tick flushes (the
    test_sendplane coalescing harness, run per backend)."""
    for server, corpus in ((True, REPLIES), (False, REQUESTS)):
        enc = PacketCodec(server=server, use_native=False)
        enc.handshaking = False
        frames = [enc.encode(dict(p)) for p in corpus]
        expect = b''.join(frames) + b''.join(frames[:3])
        baseline = await _stream_through(None, frames)
        assert baseline == expect
        for backend in BATCHED:
            got = await _stream_through(backend, frames)
            assert got == expect, \
                'backend %s diverged from the asyncio stream' % backend


@needs_batched
async def test_one_submission_covers_every_dirty_connection():
    """The tentpole's number: a tick that dirties N connections costs
    ONE batched submission (tier.submissions), with the syscall
    counter O(1) on uring and O(N) on mmsg — never O(frames)."""
    backend = BATCHED[0]
    col = Collector()
    tier = TransportTier(backend, collector=col, plane='server')
    pipes = [await _pipe() for _ in range(8)]
    try:
        planes = [SendPlane(t.write, enabled=True, tier=tier,
                            transport_fn=lambda t=t: t)
                  for t, _ in pipes]
        for i, p in enumerate(planes):
            p.send(b'a%d' % i)
            p.send(b'b%d' % i)       # two frames, one plane flush
        for _ in range(3):
            await asyncio.sleep(0)
        assert tier.submissions == 1
        expected_syscalls = 1 if backend == 'uring' else 8
        assert tier.syscalls == expected_syscalls
        ctr = col.get_collector(METRIC_FLUSH_SYSCALLS)
        assert ctr.value({'plane': 'server',
                          'backend': backend}) == expected_syscalls
        dep = col.get_collector(METRIC_SUBMIT_DEPTH)
        assert dep.count({'plane': 'server', 'backend': backend}) == 1
        assert dep.sum({'plane': 'server', 'backend': backend}) == 8
        for i, (_, peer) in enumerate(pipes):
            assert await _read_exact(peer, 4) == b'a%db%d' % (i, i)
    finally:
        for t, peer in pipes:
            t.close()
            peer.close()


@needs_batched
async def test_flush_hard_is_synchronous_on_batched_backends():
    """The fault injector's boundary rule: after flush_hard returns,
    the bytes are already in the kernel — a direct transport write
    issued immediately after can never overtake them."""
    backend = BATCHED[0]
    transport, peer = await _pipe()
    try:
        tier = TransportTier(backend)
        plane = SendPlane(transport.write, enabled=True, tier=tier,
                          transport_fn=lambda: transport)
        plane.send(b'corked-')
        plane.flush_hard()
        transport.write(b'injected')     # the gate's delivery path
        assert await _read_exact(peer, 15) == b'corked-injected'
    finally:
        transport.close()
        peer.close()


@needs_batched
async def test_flush_hard_drains_tier_held_bytes():
    """A cap-hit flush parks bytes in the tier entry with the PLANE
    buffer empty; a later flush_hard must still put them on the wire
    before returning — the fault gate writes directly right after,
    and nothing may overtake (the review-found ordering hole)."""
    backend = BATCHED[0]
    transport, peer = await _pipe()
    try:
        tier = TransportTier(backend)
        plane = SendPlane(transport.write, enabled=True, max_bytes=4,
                          tier=tier, transport_fn=lambda: transport)
        plane.send(b'early')        # over the cap: parked in the tier
        assert plane.pending == 0
        plane.flush_hard()          # plane empty, tier entry is NOT
        transport.write(b'late')
        assert await _read_exact(peer, 9) == b'earlylate'
    finally:
        transport.close()
        peer.close()


async def test_stranded_tick_callback_recovers_on_next_loop():
    """A tier whose tick callback was stranded on a dead loop (a
    client reused across asyncio.run calls) must reschedule on the
    next loop instead of wedging."""
    if not BATCHED:
        pytest.skip('no batched backend')
    from zkstream_tpu.io.transport import TransportTier
    tier = TransportTier(BATCHED[0])

    class _DeadLoop:
        def is_closed(self):
            return True
    tier._scheduled_on = _DeadLoop()    # the stranded state
    transport, peer = await _pipe()
    try:
        plane = SendPlane(transport.write, enabled=True, tier=tier,
                          transport_fn=lambda: transport)
        plane.send(b'revived')
        for _ in range(3):
            await asyncio.sleep(0)
        assert await _read_exact(peer, 7) == b'revived'
    finally:
        transport.close()
        peer.close()


@needs_batched
@pytest.mark.parametrize('submission', SUBMISSIONS)
async def test_partial_write_falls_back_in_order(submission):
    """A raw write that fills the kernel buffer hands the REMAINDER to
    the asyncio transport, and later ticks queue behind it — the
    stream survives backpressure byte-identical, whether the loop's
    thread or the sender's made the write."""
    left, right = socket.socketpair()
    left.setblocking(False)
    right.setblocking(False)
    left.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)
    loop = asyncio.get_running_loop()
    transport, _ = await loop.create_connection(asyncio.Protocol,
                                                sock=left)
    rig = await _Rig(submission).start()
    try:
        tier = rig.tier
        plane = SendPlane(transport.write, enabled=True, tier=tier,
                          transport_fn=lambda: transport)
        payload = os.urandom(400000)     # >> SO_SNDBUF and the cap
        plane.send(payload)              # cap hit: immediate flush
        rig.fill()
        await asyncio.sleep(0)
        plane.send(b'TAIL')              # must queue BEHIND the spill
        reader = asyncio.ensure_future(
            _read_exact(right, len(payload) + 4, timeout=10))
        got = await reader
        assert got == payload + b'TAIL'
        assert tier.partial_flushes == 1
        assert tier.requeued_bytes > 0
        rig.check(batches=1)
    finally:
        transport.close()
        right.close()
        await rig.stop()


@needs_batched
@pytest.mark.parametrize('submission', SUBMISSIONS)
async def test_dead_socket_inside_a_batch_drops_only_its_bytes(
        submission):
    """``EPIPE`` on one connection of a batch (its peer is gone): that
    entry's bytes are dropped as an aborted transport's would be — no
    resend through the sink — and every other connection of the batch
    gets its bytes, whichever thread sent."""
    rig = await _Rig(submission, width=max(OFFLOAD_MIN_SENDS, 4)).start()
    try:
        dead_t, dead_peer = rig.pipes[0]
        dead_peer.close()
        sunk = []
        rig.planes[0]._entry.write = sunk.append
        for i, plane in enumerate(rig.planes):
            plane.send(b'frame-%03d' % i)
        await rig.settle()
        assert sunk == []
        assert rig.tier.partial_flushes == 0
        for i, (_t, peer) in enumerate(rig.pipes[1:], 1):
            assert await _read_exact(peer, 9) == b'frame-%03d' % i
        rig.check(batches=1)
    finally:
        await rig.stop()

@needs_batched
async def test_iov_guard_coalesces_pathological_chunk_counts():
    """A tick holding more chunks than an iovec can carry coalesces in
    place instead of overflowing the submission (IOV_MAX guard)."""
    from zkstream_tpu.io.transport import IOV_GUARD
    backend = BATCHED[0]
    transport, peer = await _pipe()
    try:
        tier = TransportTier(backend)
        plane = SendPlane(transport.write, enabled=True, tier=tier,
                          transport_fn=lambda: transport)
        n = IOV_GUARD + 64
        for i in range(n):
            plane.send(b'%04d' % i)
        plane.flush_now()
        entry = plane._entry
        assert len(entry.chunks) <= IOV_GUARD + 1
        for _ in range(3):
            await asyncio.sleep(0)
        expect = b''.join(b'%04d' % i for i in range(n))
        assert await _read_exact(peer, len(expect)) == expect
    finally:
        transport.close()
        peer.close()


@needs_uring
async def test_uring_ring_roundtrip():
    """Where io_uring exists: one enter syscall delivers a whole batch
    across distinct sockets (the native ring in zkwire_ext.c)."""
    from zkstream_tpu.utils.native import ensure_ext
    ext = ensure_ext()
    assert ext is not None
    pairs = [socket.socketpair() for _ in range(4)]
    try:
        ring = ext.uring_create(64)
        fds = [a.fileno() for a, _b in pairs]
        chunks = [[b'frame-', b'%d' % i] for i in range(len(pairs))]
        results, enters = ext.uring_submit(ring, fds, chunks)
        assert enters == 1
        assert results == [7] * 4
        for i, (a, b) in enumerate(pairs):
            assert b.recv(16) == b'frame-%d' % i
        ext.uring_close(ring)
    finally:
        for a, b in pairs:
            a.close()
            b.close()


# -- the native sender: hand over, reap, and every rule ----------------

class _Latch:
    """Holds a tier's sender thread inside one blocking ``send(2)`` (a
    batch of its own, queued ahead of the tier's) until released:
    everything the tier hands over meanwhile is in flight, untouched."""

    def __init__(self, tier: TransportTier):
        from zkstream_tpu.utils.native import ensure_ext
        self.ext = ensure_ext()
        if tier._sender is None:
            tier._sender, tier._ext = self.ext.sender_create(), self.ext
        self.sender = tier._sender
        self.a, self.b = socket.socketpair()    # blocking
        self.a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        self.batch = self.ext.sender_submit(
            self.sender, [self.a.fileno()], [[bytes(8 << 20)]])
        tier._inflight[self.batch] = []     # the tier reaps it: no entry

    def release(self) -> None:
        self.b.close()          # the blocked send ends in EPIPE

    def release_in(self, seconds: float) -> threading.Timer:
        """From another thread: for a loop thread that is about to
        block on the batch behind the latch."""
        timer = threading.Timer(seconds, self.release)
        timer.start()
        return timer

    def close(self) -> None:
        self.release()
        self.ext.sender_wait(self.sender, self.batch)
        self.a.close()


class _Rig:
    """``width`` socketpair connections on one tier that sends as
    ``submission`` says: ``inline`` is any batched backend's tier as a
    server builds it; ``handed_over`` is the loop's shared client tier
    (mmsg, ``attach_sender``), whose batches of OFFLOAD_MIN_SENDS
    connections go to the sender thread.  ``fill`` dirties the
    connections that are only there to make the batch deep."""

    def __init__(self, submission: str, width: int = OFFLOAD_MIN_SENDS):
        self.handed = submission == 'handed_over'
        self.tier = TransportTier('mmsg' if self.handed else BATCHED[0],
                                  plane='client')
        if self.handed:
            self.tier.attach_sender()
        self.width = width
        self.pipes: list = []
        self.planes: list[SendPlane] = []

    async def start(self) -> '_Rig':
        self.pipes = [await _pipe() for _ in range(self.width)]
        self.planes = [SendPlane(t.write, enabled=True, tier=self.tier,
                                 transport_fn=lambda t=t: t)
                       for t, _peer in self.pipes]
        return self

    def fill(self, skip: int = 0) -> None:
        for plane in self.planes[skip:]:
            plane.send(b'fill')

    async def settle(self) -> None:
        """Until nothing is in flight and the tick has run."""
        for _ in range(500):
            await asyncio.sleep(0)
            if not self.tier._inflight and not self.tier._dirty:
                return
            await asyncio.sleep(0.002)
        raise AssertionError('a batch was never reaped')

    def check(self, batches: int) -> None:
        assert self.tier.offloaded_batches == (batches if self.handed
                                               else 0)

    async def stop(self) -> None:
        self.tier.close()
        for t, peer in self.pipes:
            t.close()
            peer.close()
        await asyncio.sleep(0)      # the transports' own teardown


@pytest.mark.parametrize('backend', BATCHED)
async def test_deep_fleet_burst_is_one_handed_over_batch(backend):
    """One request from each of N >= OFFLOAD_MIN_SENDS clients in one
    loop iteration: ONE submission of depth N — on mmsg handed to the
    sender thread as ONE batch and reaped, on uring one enter as ever —
    every connection's bytes intact and in order, the depth and the
    offloaded flushes in every client's collector."""
    n = OFFLOAD_MIN_SENDS + 3
    srv = await ZKServer().start()
    tap = await _Tap(srv.port).start()
    clients = await _fleet(tap.port, n, backend)
    try:
        tier = clients[0].transport_tier
        handed = backend == 'mmsg' and _has_sender()
        subs0, sys0 = tier.submissions, tier.syscalls
        depth0 = _depth(clients[-1], backend)
        futs = [_send(c, '/a%d' % i) for i, c in enumerate(clients)]
        futs.append(_send(clients[2], '/second'))   # 2 frames, 1 entry
        replies = await asyncio.gather(*futs, return_exceptions=True)
        assert all(getattr(r, 'code', None) == 'NO_NODE'
                   for r in replies), replies
        assert tier.submissions == subs0 + 1
        assert tier.syscalls == sys0 + (1 if backend == 'uring' else n)
        assert tier.offloaded_batches == (1 if handed else 0)
        assert tier.offloaded_flushes == (n if handed else 0)
        assert (tier._sender is not None) == handed
        assert not tier._inflight
        for c in clients:
            count, total = _depth(c, backend)
            assert (count, total) == (depth0[0] + 1, depth0[1] + n)
            ctr = c.collector.get_collector(METRIC_FLUSH_OFFLOADED)
            assert ctr.value({'plane': 'client'}) == tier.offloaded_flushes
        for i, stream in enumerate(tap.streams):
            _assert_intact(stream, ['/a%d' % i] + ['/second'] * (i == 2))
    finally:
        for c in clients:
            await c.close()
        await tap.stop()
        await srv.stop()
    assert tier._sender is None and tier.refs == 0


@needs_sender
async def test_shallow_burst_never_touches_the_sender():
    """Below OFFLOAD_MIN_SENDS the batch is sent inline: no thread is
    ever started for it."""
    rig = await _Rig('handed_over', width=OFFLOAD_MIN_SENDS - 1).start()
    try:
        for _round in range(3):
            for i, plane in enumerate(rig.planes):
                plane.send(b'f%03d' % i)
            await rig.settle()
        assert rig.tier.submissions == 3
        assert rig.tier.offloaded_batches == 0
        assert rig.tier._sender is None
        for i, (_t, peer) in enumerate(rig.pipes):
            assert await _read_exact(peer, 12) == b'f%03d' % i * 3
    finally:
        await rig.stop()


@needs_sender
async def test_a_server_tier_never_hands_over():
    """``make_tier`` (a member's tier) has no sender whatever the
    depth: its submission stays inside its tick ledger."""
    tier = make_tier('mmsg', plane='server')
    pipes = [await _pipe() for _ in range(OFFLOAD_MIN_SENDS + 1)]
    try:
        planes = [SendPlane(t.write, enabled=True, tier=tier,
                            transport_fn=lambda t=t: t)
                  for t, _ in pipes]
        for plane in planes:
            plane.send(b'reply')
        for _ in range(3):
            await asyncio.sleep(0)
        assert tier.submissions == 1 and tier.offloaded_batches == 0
        assert tier._sender is None
        for _t, peer in pipes:
            assert await _read_exact(peer, 5) == b'reply'
    finally:
        for t, peer in pipes:
            t.close()
            peer.close()


@needs_sender
@pytest.mark.parametrize('second', ['raw', 'sink'])
async def test_second_flush_waits_for_the_batch_in_flight(second):
    """Order on a connection: while its batch is in flight (the sender
    held) a connection's next flush is not submitted — neither raw nor
    through its asyncio sink, which would write at once and overtake —
    and leaves, behind the first, once the batch is reaped."""
    rig = await _Rig('handed_over').start()
    latch = _Latch(rig.tier)
    try:
        plane, (transport, peer) = rig.planes[0], rig.pipes[0]
        plane.send(b'first-')
        rig.fill(skip=1)
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert rig.tier.offloaded_batches == 1 and rig.tier._inflight
        if second == 'sink':
            # raw-ineligible from now on: the tier must take the sink
            plane._entry.transport_fn = lambda: None
        plane.send(b'second')
        for _ in range(5):
            await asyncio.sleep(0.002)
        # held: still with its entry, nothing written anywhere
        assert plane._entry.nbytes == 6 and plane._entry.batch
        assert transport.get_write_buffer_size() == 0
        with pytest.raises(BlockingIOError):
            peer.recv(64)
        latch.release()
        await rig.settle()
        assert await _read_exact(peer, 12) == b'first-second'
        assert rig.tier.offloaded_batches == 1
        assert rig.tier.submissions == (2 if second == 'raw' else 1)
    finally:
        latch.close()
        await rig.stop()


@needs_sender
async def test_flush_hard_with_a_batch_in_flight():
    """Bytes on the wire before ``flush_hard`` returns: it waits for
    the connection's batch in flight, then submits what is pending
    inline — a direct write issued right after cannot overtake."""
    rig = await _Rig('handed_over').start()
    latch = _Latch(rig.tier)
    try:
        plane, (transport, peer) = rig.planes[0], rig.pipes[0]
        plane.send(b'first-')
        rig.fill(skip=1)
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert plane._entry.batch
        plane.send(b'corked-')
        timer = latch.release_in(0.05)
        plane.flush_hard()              # blocks until the batch is out
        assert not plane._entry.batch and not rig.tier._inflight
        transport.write(b'injected')
        assert await _read_exact(peer, 21) == b'first-corked-injected'
        timer.join()
        # every other connection of the batch was settled by that reap
        for _t, other in rig.pipes[1:]:
            assert await _read_exact(other, 4) == b'fill'
    finally:
        latch.close()
        await rig.stop()


@needs_sender
async def test_buffered_bytes_holds_what_is_in_flight():
    """The overload plane's tx account: bytes handed to the sender
    stay in ``buffered_bytes()`` until the batch is reaped."""
    rig = await _Rig('handed_over').start()
    latch = _Latch(rig.tier)
    try:
        plane = rig.planes[0]
        plane.send(b'x' * 700)
        rig.fill(skip=1)
        assert plane.buffered_bytes() == 700        # corked
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert plane.pending == 0 and plane._entry.nbytes == 0
        assert plane.buffered_bytes() == 700        # in flight
        plane.send(b'y' * 50)
        await asyncio.sleep(0.005)
        assert plane.buffered_bytes() == 750        # + held behind it
        latch.release()
        await rig.settle()
        assert plane.buffered_bytes() == 0
        assert len(await _read_exact(rig.pipes[0][1], 750)) == 750
    finally:
        latch.close()
        await rig.stop()


@needs_sender
async def test_reset_in_flight_never_reaches_the_fds_next_owner():
    """The hazard the hand-over is built around: a connection is reset
    while its request waits in a batch in flight; asyncio closes the
    socket right after, and the next connection dialed is given the
    SAME fd number.  The reset waits for the batch, so the request
    went out on its own connection: not one stale byte on the new
    one."""
    n = OFFLOAD_MIN_SENDS
    srv = await ZKServer().start()
    tap = await _Tap(srv.port).start()
    clients = await _fleet(tap.port, n, 'mmsg')
    tier = clients[0].transport_tier
    latch = _Latch(tier)
    try:
        futs = [_send(c, '/stale%d' % i) for i, c in enumerate(clients)]
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert tier.offloaded_batches == 1 and tier._inflight
        conn = clients[0]._conn_or_raise()
        old_fd = conn.transport.get_extra_info('socket').fileno()
        timer = latch.release_in(0.1)
        conn.destroy()                  # blocks until the batch is out
        assert not tier._inflight
        timer.join()
        # the client re-dials on its own; the new socket takes the
        # lowest free number: the one just closed
        for _ in range(500):
            await asyncio.sleep(0.01)
            if len(tap.streams) > n and clients[0].is_connected():
                break
        new = clients[0]._conn_or_raise()
        assert new is not conn
        assert new.transport.get_extra_info('socket').fileno() == old_fd
        await asyncio.gather(*futs, return_exceptions=True)
        after = await asyncio.gather(_send(clients[0], '/after'),
                                     return_exceptions=True)
        assert getattr(after[0], 'code', None) == 'NO_NODE'
        # the old connection carried its request, whole, exactly once;
        # the new one starts with its ConnectRequest and holds
        # nothing of the old session's
        _assert_intact(tap.streams[0], ['/stale0'])
        reqs = _assert_intact(tap.streams[n], ['/after'])
        assert '/stale0' not in [p.get('path') for p in reqs]
    finally:
        latch.close()
        for c in clients:
            await c.close()
        await tap.stop()
        await srv.stop()


@needs_sender
async def test_close_with_a_batch_in_flight_reaps_and_joins():
    """``tier.close()`` (the last lease released): what is in flight
    goes out and is settled, the thread is joined, and a later deep
    batch starts a sender anew."""
    rig = await _Rig('handed_over').start()
    latch = _Latch(rig.tier)
    try:
        rig.fill()
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert rig.tier._inflight
        timer = latch.release_in(0.05)
        first = rig.tier._sender
        rig.tier.close()
        timer.join()
        assert rig.tier._sender is None and not rig.tier._inflight
        assert all(p._entry.batch == 0 for p in rig.planes)
        with pytest.raises(ValueError):
            latch.ext.sender_reap(first)        # closed, not leaked
        rig.fill()
        await rig.settle()
        assert rig.tier.offloaded_batches == 2
        assert rig.tier._sender is not None
        for _t, peer in rig.pipes:
            assert await _read_exact(peer, 8) == b'fillfill'
    finally:
        latch.a.close()
        await rig.stop()


@needs_sender
def test_sender_tier_across_two_runs_moves_its_reader():
    """One ``asyncio.run`` after another on one tier: the sender's
    ``eventfd`` is a reader of the loop that hands batches over, and a
    batch the first loop left unreaped is settled on the second."""
    tier = TransportTier('mmsg', plane='client')
    tier.attach_sender()
    seen = {}

    async def one_run(tag: bytes, reap: bool):
        pipes = [await _pipe() for _ in range(OFFLOAD_MIN_SENDS)]
        planes = [SendPlane(t.write, enabled=True, tier=tier,
                            transport_fn=lambda t=t: t)
                  for t, _ in pipes]
        for plane in planes:
            plane.send(tag)
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        for _t, peer in pipes:
            assert await _read_exact(peer, len(tag)) == tag
        if reap:
            for _ in range(200):
                if not tier._inflight:
                    break
                await asyncio.sleep(0.005)
        seen[tag] = (asyncio.get_running_loop(), tier._reader_loop,
                     dict(tier._inflight))
        for t, peer in pipes:
            t.close()
            peer.close()
        await asyncio.sleep(0)
    try:
        # the reader callback is the only reap here: stop the loop
        # before it can run, with the batch done but not settled
        tier._reap, real = (lambda: None), tier._reap
        asyncio.run(one_run(b'run-1', reap=False))
        tier._reap = real
        loop1, reader1, left = seen[b'run-1']
        assert reader1 is loop1 and len(left) == 1
        asyncio.run(one_run(b'run-2', reap=True))
        loop2, reader2, left = seen[b'run-2']
        assert loop2 is not loop1 and reader2 is loop2 and not left
        assert tier.offloaded_batches == 2
    finally:
        tier.close()


@needs_sender
async def test_without_the_extension_a_deep_batch_goes_inline(
        monkeypatch):
    """No extension (or one that predates the sender): the inline
    submission, as before."""
    from zkstream_tpu.io import transport as tmod
    monkeypatch.setattr(tmod, '_sender_ext', lambda: None)
    rig = await _Rig('handed_over').start()
    try:
        for i, plane in enumerate(rig.planes):
            plane.send(b'f%03d' % i)
        await rig.settle()
        assert rig.tier.submissions == 1
        assert rig.tier.offloaded_batches == 0
        assert rig.tier._sender is None
        for i, (_t, peer) in enumerate(rig.pipes):
            assert await _read_exact(peer, 4) == b'f%03d' % i
    finally:
        await rig.stop()


# -- the native receiver: the client tier's receive half ---------------

def _has_receiver() -> bool:
    from zkstream_tpu.utils.native import ensure_ext
    return probe().mmsg and hasattr(ensure_ext(), 'receiver_reap')


needs_receiver = pytest.mark.skipif(
    not _has_receiver(), reason='no native receiver: the mmsg backend '
    'or the extension is missing')

#: How a client connection's bytes come in: asyncio's protocol push
#: (no tier, or one that does not own the receive); the loop's shared
#: client tier's native receiver thread, a delivery a connection
#: through ``_sock_data``; or that thread with the connections under a
#: fleet ingest and their sinks standing — the reap's one C call
#: appends the bytes to the ingest's slots (``rx_sink``).
RX_PATHS = ['asyncio_push'] + (['receiver_thread', 'receiver_sink']
                               if _has_receiver() else [])


def _rx_tier(path: str) -> TransportTier | None:
    if path == 'asyncio_push':
        return None
    tier = TransportTier('mmsg', plane='client')
    tier.attach_sender()
    return tier


def _counting_ingest():
    """A force-device fleet ingest that counts the bytes that reach
    it, whichever way: a ``feed`` a delivery, or a ``fed`` a reap."""
    from zkstream_tpu.io.ingest import FleetIngest

    class Counting(FleetIngest):
        arrived = 0

        def feed(self, conn, data, t_rx=0):
            self.arrived += len(data)
            super().feed(conn, data, t_rx)

        def fed(self, nbytes, t_rx=0):
            self.arrived += nbytes
            super().fed(nbytes, t_rx)

    return Counting(bypass_bytes=0, warm='block', placement='host',
                    max_frames=8, min_len=512)


class _RxGroup:
    """The peers of one receive path, and how many bytes reached their
    connections so far (the waits of a test that cuts a stream)."""

    def __init__(self, path: str):
        self.path = path
        self.tier = _rx_tier(path)
        self.ingest = (_counting_ingest() if path == 'receiver_sink'
                       else None)
        self.peers: list = []

    async def peer(self, idx: int, **kw) -> '_RxPeer':
        p = _RxPeer(idx, self.tier, ingest=self.ingest, **kw)
        self.peers.append(await p.start())
        return p

    def arrived(self) -> int:
        if self.ingest is not None:
            return self.ingest.arrived
        return sum(sum(p.chunks) for p in self.peers)

    def sunk(self, p: '_RxPeer') -> bool:
        """Does ``p``'s sink stand?"""
        return (self.tier is not None
                and p.entry.rx_token in self.tier._sinks)

    async def close(self) -> None:
        for p in self.peers:
            await p.stop()
        if self.tier is not None:
            assert not self.tier._rx and not self.tier._sinks
            assert not self.tier._sink_owners
            self.tier.close()
        if self.ingest is not None:
            self.ingest.close()


class _RxStub:
    """What a ZKConnection asks of its client."""

    def __init__(self, tier, faults=None, ingest=None):
        from zkstream_tpu.io.session import ZKSession
        self.transport_tier = tier
        self.faults = faults
        if ingest is not None:
            self.ingest = ingest
        self.use_native_codec = False
        self.session = ZKSession(30000)

    def get_session(self):
        return self.session


class _RxPeer:
    """A real ``ZKConnection`` made through the real ``_SocketProtocol``
    over a socket pair, handshaken by the test, which plays the member
    on ``self.peer``; everything the connection observes is logged."""

    def __init__(self, idx: int, tier, faults=None, tcp: bool = False,
                 ingest=None):
        self.idx, self.tier, self.tcp = idx, tier, tcp
        self.client = _RxStub(tier, faults, ingest)
        self.log: list = []
        #: the length of every ``sockData`` segment — listened for only
        #: WITHOUT an ingest: a second listener withdraws the sink
        self.chunks: list[int] = []

    async def start(self) -> '_RxPeer':
        from zkstream_tpu.io.connection import (
            Backend, ZKConnection, _SocketProtocol)
        if self.tcp:
            lsock = socket.socket()
            lsock.bind(('127.0.0.1', 0))
            lsock.listen(1)
            left = socket.create_connection(lsock.getsockname())
            self.peer, _addr = lsock.accept()
            lsock.close()
        else:
            left, self.peer = socket.socketpair()
        left.setblocking(False)
        self.peer.setblocking(False)
        self.conn = conn = ZKConnection(
            self.client, Backend('127.0.0.1', 1 + self.idx))
        conn.codec = PacketCodec(use_native=False)
        if getattr(self.client, 'ingest', None) is None:
            conn.on('sockData', lambda d: self.chunks.append(len(d)))
        conn.on('packet', lambda p: self.log.append(('packet', p)))
        for ev in ('sockEnd', 'sockClose'):
            conn.on(ev, lambda ev=ev: self.log.append((ev,)))
        conn.on('sockError', lambda e: self.log.append(
            ('sockError', type(e).__name__, e.errno)))
        self.client.session.process_notification = lambda pkt: None
        loop = asyncio.get_running_loop()
        await loop.create_connection(lambda: _SocketProtocol(conn),
                                     sock=left)
        conn._transition('handshaking')
        srv = PacketCodec(server=True, use_native=False)
        await _read_exact(self.peer, 44)        # the ConnectRequest
        self.peer.send(srv.encode({
            'protocolVersion': 0, 'timeOut': 30000,
            'sessionId': 0x2000 + self.idx, 'passwd': b'\x01' * 16}))
        await _until(lambda: conn.is_in_state('connected'))
        del self.log[:], self.chunks[:]
        return self

    @property
    def entry(self):
        return self.conn._tx._entry

    def expect(self, replies) -> None:
        """Teach the connection's codec the xids about to be answered
        (a reply decodes by the opcode its xid was sent with)."""
        for p in replies:
            if p['xid'] > 0:
                self.conn.codec.xid_map[p['xid']] = p['opcode']

    def packets(self) -> list:
        return [e[1] for e in self.log if e[0] == 'packet']

    async def stop(self) -> None:
        self.conn.destroy()
        self.peer.close()
        await asyncio.sleep(0)
        await asyncio.sleep(0)


async def _until(cond, timeout: float = 10.0) -> None:
    loop = asyncio.get_running_loop()
    end = loop.time() + timeout
    while not cond():
        assert loop.time() < end, 'never came true'
        await asyncio.sleep(0.0005)


def _reply_wire() -> tuple[bytes, list]:
    """The reply corpus as one member would write it, and what a
    client decodes from it."""
    enc = PacketCodec(server=True, use_native=False)
    enc.handshaking = False
    wire = b''.join(enc.encode(dict(p)) for p in REPLIES)
    dec = PacketCodec(use_native=False)
    dec.handshaking = False
    for p in REPLIES:
        if p['xid'] > 0:
            dec.xid_map[p['xid']] = p['opcode']
    return wire, dec.decode(wire)


@pytest.mark.parametrize('path', RX_PATHS)
async def test_client_receive_parity_at_every_byte_offset(path):
    """The invariant the receive half hangs on: whichever path brings
    the bytes, every connection decodes the IDENTICAL frame stream —
    the whole reply corpus, cut in two at EVERY byte offset, three
    connections of one tier at once, each cut elsewhere."""
    group = _RxGroup(path)
    tier = group.tier
    wire, want = _reply_wire()
    peers = [await group.peer(i) for i in range(3)]
    try:
        for p in peers:
            on_thread = path != 'asyncio_push'
            assert (p.entry is not None and p.entry.rx_token != 0) \
                == on_thread
            assert group.sunk(p) == (path == 'receiver_sink')
        sent = group.arrived()
        for base in range(1, len(wire), 3):
            cuts = [min(base + i, len(wire) - 1) for i in range(3)]
            for p, cut in zip(peers, cuts):
                p.expect(REPLIES)
                del p.log[:]
                p.peer.send(wire[:cut])
            sent += sum(cuts)
            await _until(lambda: group.arrived() == sent)
            for p, cut in zip(peers, cuts):
                p.peer.send(wire[cut:])
            sent += 3 * len(wire) - sum(cuts)
            await _until(lambda: group.arrived() == sent)
            for p in peers:
                await _until(lambda: len(p.packets()) >= len(want))
                assert p.packets() == want, (path, p.idx, cuts)
        if tier is not None:
            assert tier.received_reads >= 2 * len(peers)
            assert tier.received_batches > 0
            assert tier.received_ctr.value({'plane': 'client'}) \
                == tier.received_reads
            # with the sinks standing the reaps' C call made every
            # delivery but the handshakes' itself; without them none
            assert tier.received_fed == (
                tier.received_reads - len(peers)
                if path == 'receiver_sink' else 0)
            assert tier.fed_ctr.value({'plane': 'client'}) \
                == tier.received_fed
    finally:
        await group.close()


class _RxTap:
    """Stands where the fault injector stands on the receive side and
    logs its boundary: one call a ``_sock_data``, one connection's
    bytes a call."""

    def __init__(self):
        self.calls: list = []

    def rx(self, conn, data: bytes) -> None:
        self.calls.append((conn, bytes(data)))
        conn.emit('sockData', data)

    def tx(self, conn, data: bytes) -> bytes:
        return data


@pytest.mark.parametrize('path', RX_PATHS)
async def test_fault_injector_rx_boundary_stays_per_connection(path):
    """An installed injector still sees every received segment, and a
    segment is one connection's: a reap of many connections' bytes is
    as many ``faults.rx`` calls, each with the connection whose socket
    received them — never one joined buffer, never a neighbour's."""
    group = _RxGroup(path)
    tier = group.tier
    tap = _RxTap()
    wire, want = _reply_wire()
    peers = [await group.peer(i, faults=tap) for i in range(8)]
    try:
        del tap.calls[:]
        for p in peers:
            p.expect(REPLIES)
            p.peer.send(b'%c' % (65 + p.idx) * 3)   # its own mark...
        # ...not a frame: never mind, the boundary is what is watched
        for p in peers:
            await _until(lambda: sum(
                len(d) for c, d in tap.calls if c is p.conn) == 3)
        by_conn = {p.conn: p for p in peers}
        for conn, data in tap.calls:
            assert data == b'%c' % (65 + by_conn[conn].idx) * len(data)
        # a connection with an injector has no sink, ingest or none
        assert not any(group.sunk(p) for p in peers)
        assert tier is None or tier.received_fed == 0
    finally:
        await group.close()


@pytest.mark.parametrize('path', RX_PATHS)
async def test_client_receive_end_and_reset_read_the_same(path):
    """EOF is ``sockEnd`` and a reset is ``sockError`` with the
    ``OSError`` on both paths, each once and behind the connection's
    last bytes; the transport is torn down as asyncio tears it down."""
    group = _RxGroup(path)
    wire, want = _reply_wire()
    ended = await group.peer(0, tcp=True)
    reset = await group.peer(1, tcp=True)
    try:
        ended.expect(REPLIES)
        ended.peer.send(wire)
        if group.ingest is not None:
            # under an ingest replies are routed a tick later, and an
            # end that overtakes the tick takes the slot's bytes with
            # the connection: let them settle first
            await _until(lambda: ended.packets() == want)
            assert group.sunk(ended) and group.tier.received_fed >= 1
        ended.peer.shutdown(socket.SHUT_WR)
        await _until(lambda: ('sockClose',) in ended.log)
        assert ended.packets() == want
        # the end once, behind the bytes; state `error` then aborts
        assert ended.log[len(want):] == [('sockEnd',), ('sockClose',)]
        # RST: the member closes with our request unread, linger 0
        import struct
        reset.conn._tx_write(b'unread by the member')
        await asyncio.sleep(0.01)
        reset.peer.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                              struct.pack('ii', 1, 0))
        reset.peer.close()
        await _until(lambda: any(e[0] == 'sockError'
                                 for e in reset.log))
        await asyncio.sleep(0.01)
        assert reset.log == [
            ('sockError', 'ConnectionResetError', errno.ECONNRESET)]
        assert reset.conn.transport is None     # error -> closed
    finally:
        await group.close()


@pytest.mark.parametrize('path', RX_PATHS)
async def test_connection_pause_and_resume_reading(path):
    """``ZKConnection.pause_reading`` stops whichever path reads the
    socket (the transport's own pause cannot stop a thread it does not
    know), what had already arrived is delivered first, and
    ``resume_reading`` puts the connection back on the path it was
    made on with nothing lost."""
    group = _RxGroup(path)
    tier = group.tier
    sink = path == 'receiver_sink'
    wire, want = _reply_wire()
    p = await group.peer(0)
    try:
        p.expect(REPLIES)
        p.peer.send(wire[:100])
        await _until(lambda: group.arrived() == 100)
        assert group.sunk(p) == sink
        p.conn.pause_reading()
        p.peer.send(wire[100:])
        await asyncio.sleep(0.05)
        assert group.arrived() == 100
        if tier is not None:
            # paused: no thread reads it, and so nothing feeds a sink
            assert p.entry.rx_transport is None and not tier._rx
            assert not tier._sinks and not tier._sink_owners
        p.conn.resume_reading()
        await _until(lambda: group.arrived() == len(wire))
        await _until(lambda: len(p.packets()) >= len(want))
        assert p.packets() == want
        if tier is not None:
            assert p.entry.rx_token != 0
            # read again on the path it was made on: the sink with it
            assert group.sunk(p) == sink
            assert (tier.received_fed >= 2) == sink
    finally:
        await group.close()


@needs_receiver
async def test_connection_lost_with_bytes_the_thread_holds():
    """``connection_lost`` — behind which asyncio closes the socket —
    takes the connection from the receiver first: what the thread had
    received and the loop had not reaped is delivered BEFORE the close
    is seen, in order; what a reap already holds for a connection that
    was forgotten since goes to nobody."""
    import time
    tier = _rx_tier('receiver_thread')
    wire, want = _reply_wire()
    lost = await _RxPeer(0, tier).start()
    kept = await _RxPeer(1, tier).start()
    try:
        lost.expect(REPLIES)
        kept.expect(REPLIES)
        lost.peer.send(wire)
        kept.peer.send(wire)
        time.sleep(0.05)        # received by the thread; the loop stood
        assert not lost.chunks
        token = lost.entry.rx_token
        lost.conn.transport.abort()     # -> connection_lost, next turn
        await _until(lambda: ('sockClose',) in lost.log)
        assert lost.packets() == want
        assert lost.log[-1] == ('sockClose',)
        assert token not in tier._rx and lost.entry.rx_token == 0
        await _until(lambda: sum(kept.chunks) == len(wire))
        assert kept.packets() == want
        # a reap that still names the forgotten token reaches nobody
        items = [(token, b'late'), (kept.entry.rx_token, b'')]
        real = tier._ext.receiver_reap
        tier._ext = _ReapOnce(tier._ext, items)
        del lost.chunks[:], kept.chunks[:]
        tier._rx_reap()
        assert not lost.chunks and not kept.chunks
        assert ('sockEnd',) in kept.log
        tier._ext = tier._ext.ext
        assert tier._ext.receiver_reap is real
    finally:
        await lost.stop()
        await kept.stop()
        tier.close()


class _ReapOnce:
    """The extension with ONE reap's result put in its place."""

    def __init__(self, ext, items):
        self.ext, self.items = ext, items

    def __getattr__(self, name):
        return getattr(self.ext, name)

    def receiver_reap(self, cap, _sinks=None, _want=False):
        return self.items, 0, 0, None


@needs_receiver
async def test_one_raising_connection_keeps_its_error_to_itself():
    """A connection whose handler raises inside a reap does not take
    the batch with it (``_tick``'s rule): its neighbours get their
    bytes."""
    import time
    tier = _rx_tier('receiver_thread')
    wire, want = _reply_wire()
    peers = [await _RxPeer(i, tier).start() for i in range(3)]
    try:
        def boom(_data):
            raise RuntimeError('a handler broke')
        peers[0].conn.on('sockData', boom)
        for p in peers:
            p.expect(REPLIES)
            p.peer.send(wire)
        time.sleep(0.05)            # one reap carries all three
        for p in peers[1:]:
            await _until(lambda: sum(p.chunks) == len(wire))
            assert p.packets() == want
    finally:
        for p in peers:
            await p.stop()
        tier.close()


@needs_receiver
def test_receiver_tier_across_two_runs_moves_its_reader():
    """One ``asyncio.run`` after another on one tier: the receiver's
    ``eventfd`` is a reader of the loop whose connections it reads, a
    connection of the second run is read like one of the first, and
    the thread is the same."""
    tier = _rx_tier('receiver_thread')
    wire, want = _reply_wire()
    seen = {}

    async def one_run(tag: str):
        p = await _RxPeer(0, tier).start()
        p.expect(REPLIES)
        p.peer.send(wire)
        await _until(lambda: sum(p.chunks) == len(wire))
        assert p.packets() == want and p.entry.rx_token
        seen[tag] = (asyncio.get_running_loop(), tier._reader_loop,
                     tier._receiver)
        await p.stop()
    try:
        asyncio.run(one_run('run-1'))
        asyncio.run(one_run('run-2'))
        (loop1, reader1, thread1), (loop2, reader2, thread2) = (
            seen['run-1'], seen['run-2'])
        assert loop2 is not loop1
        assert reader1 is loop1 and reader2 is loop2
        assert thread1 is thread2
    finally:
        tier.close()
        assert tier._receiver is None


@needs_receiver
async def test_where_no_receiver_is_to_be_had_asyncio_pushes(
        monkeypatch):
    """No extension (or one that predates the receiver), a
    ``receiver_create`` that fails, a tier nobody armed (a member's):
    the connection is read by its asyncio transport exactly as before,
    and nothing of the receive half is touched."""
    from zkstream_tpu.io import transport as tmod
    wire, want = _reply_wire()

    async def reads_by_push(tier):
        p = await _RxPeer(0, tier).start()
        try:
            p.expect(REPLIES)
            p.peer.send(wire)
            await _until(lambda: sum(p.chunks) == len(wire))
            assert p.packets() == want
            assert p.entry.rx_transport is None and not p.entry.rx_token
            assert tier._receiver is None and not tier._rx
            assert tier.received_reads == 0
            assert p.conn.transport.is_reading()
        finally:
            await p.stop()
            tier.close()
    unarmed = TransportTier('mmsg', plane='server')
    await reads_by_push(unarmed)
    monkeypatch.setattr(tmod, '_receiver_ext', lambda: None)
    await reads_by_push(_rx_tier('receiver_thread'))

    class _NoThread:
        def receiver_create(self):
            raise OSError(errno.EMFILE, 'no thread, no epoll')
    monkeypatch.setattr(tmod, '_receiver_ext', _NoThread)
    tier = _rx_tier('receiver_thread')
    await reads_by_push(tier)
    assert tier._rx_on is False         # asked once, not again


@needs_receiver
async def test_closing_the_tier_gives_live_connections_back():
    """``tier.close()`` with connections alive (the last client of a
    loop closed while others' sockets still are): what the thread had
    is delivered, the thread is joined, and the connections read
    through their own transports from then on."""
    import time
    tier = _rx_tier('receiver_thread')
    wire, want = _reply_wire()
    p = await _RxPeer(0, tier).start()
    try:
        p.expect(REPLIES)
        p.peer.send(wire[:200])
        time.sleep(0.05)            # with the thread, unreaped
        tier.close()
        assert tier._receiver is None and not tier._rx
        assert sum(p.chunks) == 200
        assert p.conn.transport.is_reading()
        p.peer.send(wire[200:])
        await _until(lambda: sum(p.chunks) == len(wire))
        assert p.packets() == want
    finally:
        await p.stop()


@needs_receiver
async def test_after_reap_runs_what_the_deliveries_left():
    """``after_reap``: a callee of a reap's delivery leaves a callable;
    it runs once, inside the reap's callback, when every connection of
    that reap has its bytes — never between two deliveries; outside a
    reap the ask is refused."""
    from zkstream_tpu.io.transport import after_reap
    tier = _rx_tier('receiver_thread')
    wire, _want = _reply_wire()
    peers = [await _RxPeer(i, tier).start() for i in range(3)]
    order: list = []
    asked: list = []
    try:
        assert after_reap(lambda: order.append('refused')) is False
        for p in peers:
            p.expect(REPLIES)

            def on_data(_d, p=p):
                order.append(('data', p.idx))
                asked.append(after_reap(
                    lambda: order.append(('after', p.idx))))
            p.conn.on('sockData', on_data)
            p.peer.send(wire)
        await _until(lambda: all(sum(p.chunks) == len(wire)
                                 for p in peers))
        assert asked == [True] * len(asked) and len(asked) >= 3
        # within a reap: its deliveries, then what they left, in order
        reaps, cur = [], []
        for e in order:
            if e[0] == 'data' and cur and cur[-1][0] == 'after':
                reaps.append(cur)
                cur = []
            cur.append(e)
        reaps.append(cur)
        for r in reaps:
            datas = [e[1] for e in r if e[0] == 'data']
            assert [e[1] for e in r if e[0] == 'after'] == datas
            assert r[:len(datas)] == [('data', i) for i in datas]
        assert 'refused' not in order
        assert after_reap(lambda: None) is False
    finally:
        for p in peers:
            await p.stop()
        tier.close()


@needs_receiver
@pytest.mark.parametrize('sink', [False, True],
                         ids=['through_sock_data', 'sunk'])
async def test_a_reap_hands_the_fleet_ingest_its_early_dispatch(sink):
    """Through the real receiver thread: the connections of a fleet
    ingest get their bytes in a reap — a delivery a connection through
    ``_sock_data`` (a second ``sockData`` listener stands: no sink), or
    appended to their slots by the reap's one C call (``sunk``) — the
    ingest dispatches its batch at the reap's end (``ticks_early`` =
    ``ticks``) and the scheduled tick delivers every connection the
    stream the scalar drain decodes."""
    from zkstream_tpu.io.ingest import FleetIngest
    tier = _rx_tier('receiver_thread')
    ingest = FleetIngest(bypass_bytes=0, warm='block', placement='host',
                         max_frames=8, min_len=512)
    wire, want = _reply_wire()
    peers = []
    try:
        for i in range(3):
            p = _RxPeer(i, tier, ingest=ingest)
            peers.append(await p.start())
            if not sink:
                p.conn.on('sockData', lambda d, p=p: p.chunks.append(
                    len(d)))
        for p in peers:
            assert p.entry.rx_token and id(p.conn) in ingest._slots
            assert (p.entry.rx_token in tier._sinks) == sink
            p.expect(REPLIES)
            p.peer.send(wire)
        await _until(lambda: all(len(p.packets()) == len(want)
                                 for p in peers))
        assert all(p.packets() == want for p in peers)
        assert ingest.ticks >= 1 and ingest.ticks_scalar == 0
        assert ingest.ticks_early == ingest.ticks
        assert ingest._flight is None
        if sink:
            assert tier.received_fed == tier.received_reads - 3 > 0
            assert not any(p.chunks for p in peers)
        else:
            assert tier.received_fed == 0
            assert all(sum(p.chunks) == len(wire) for p in peers)
        assert tier.fed_ctr.value({'plane': 'client'}) \
            == tier.received_fed
    finally:
        for p in peers:
            await p.stop()
        assert not tier._sinks and not tier._sink_owners
        tier.close()
        ingest.close()


def _cuts(rng, n: int, pieces: int) -> list[int]:
    """``pieces - 1`` distinct cut points inside ``n`` bytes, sorted,
    with both ends."""
    return [0] + sorted(rng.sample(range(1, n), pieces - 1)) + [n]


@needs_receiver
@pytest.mark.parametrize('seed', [3, 11, 29, 47])
async def test_a_stream_cut_anywhere_settles_the_same_through_the_sink(
        seed):
    """The property the sink hangs on: one reply stream a connection,
    cut at random points (four connections, two to nine pieces each,
    a piece sent when the one before has arrived — every cut is a
    delivery of its own), gives every connection the packets the
    scalar decoder reads from the whole stream, in order — fed into
    the ingest's slots by the reaps' C call, and handed over a
    delivery a connection through ``_sock_data`` (the same fleet with
    a second ``sockData`` listener, which withdraws every sink)."""
    import random
    wire, want = _reply_wire()
    seen = {}
    for path in ('receiver_sink', 'withdrawn'):
        rng = random.Random(seed)
        group = _RxGroup('receiver_sink')
        peers = [await group.peer(i) for i in range(4)]
        try:
            if path == 'withdrawn':
                for p in peers:
                    p.conn.on('sockData', lambda d, p=p:
                              p.chunks.append(len(d)))
            assert [group.sunk(p) for p in peers] \
                == [path == 'receiver_sink'] * 4
            plans = [_cuts(rng, len(wire), rng.randrange(2, 10))
                     for _ in peers]
            for p in peers:
                p.expect(REPLIES)
            sent = group.arrived()
            for step in range(max(map(len, plans)) - 1):
                for p, cuts in zip(peers, plans):
                    if step + 1 < len(cuts):
                        p.peer.send(wire[cuts[step]:cuts[step + 1]])
                        sent += cuts[step + 1] - cuts[step]
                await _until(lambda: group.arrived() == sent)
            for p in peers:
                await _until(lambda: len(p.packets()) >= len(want))
            seen[path] = [p.packets() for p in peers]
            fed = group.tier.received_fed
            if path == 'withdrawn':
                assert fed == 0
                assert [len(p.chunks) for p in peers] == [
                    len(cuts) - 1 for cuts in plans]
            else:
                assert fed == sum(len(cuts) - 1 for cuts in plans)
        finally:
            await group.close()
    assert seen['receiver_sink'] == seen['withdrawn'] == [want] * 4


# -- e2e over real sockets: parity + accounting + mntr -----------------

async def _scripted_ops(backend: str) -> list[tuple]:
    """One deterministic request/watch workload against a forced-
    backend server; returns the decoded reply/notification stream."""
    srv = await ZKServer(transport=backend).start()
    want = ('asyncio' if srv.transport_tier is None
            else srv.transport_tier.backend)
    assert want == backend
    c = RawClient()
    out: list[tuple] = []
    try:
        await c.connect(srv)
        c.send({'opcode': 'CREATE', 'path': '/t', 'data': b'v0',
                'acl': [], 'flags': 0})
        c.send({'opcode': 'GET_DATA', 'path': '/t', 'watch': True})
        # pipelined burst: multi-frame coalescing through the tier
        for i in range(8):
            c.send({'opcode': 'GET_DATA', 'path': '/t',
                    'watch': False})
        c.send({'opcode': 'SET_DATA', 'path': '/t', 'data': b'v1',
                'version': -1})
        c.send({'opcode': 'GET_DATA', 'path': '/t', 'watch': False})
        # replies: create + watch-get + 8 gets + set + get, plus the
        # DATA_CHANGED notification (which must precede the post-set
        # read result — the ordering contract)
        pkts = await c.recv(13)
        for p in pkts:
            out.append((p['opcode'], p['err'],
                        p.get('path'), bytes(p.get('data') or b'')))
        notif_at = [i for i, p in enumerate(pkts)
                    if p['opcode'] == 'NOTIFICATION']
        read_v1 = [i for i, p in enumerate(pkts)
                   if p['opcode'] == 'GET_DATA'
                   and bytes(p.get('data') or b'') == b'v1']
        assert notif_at and read_v1 and notif_at[0] < read_v1[0], \
            'notification overtaken by the read of the new state'
    finally:
        c.close()
        await srv.stop()
    return out


async def test_e2e_stream_parity_across_backends():
    backends = ['asyncio'] + BATCHED
    streams = {b: await _scripted_ops(b) for b in backends}
    for b in backends[1:]:
        assert streams[b] == streams['asyncio'], b


@needs_batched
async def test_e2e_batched_backend_counts_syscalls():
    backend = BATCHED[0]
    col = Collector()
    srv = await ZKServer(transport=backend, collector=col).start()
    c = RawClient()
    try:
        await c.connect(srv)
        for i in range(6):
            c.send({'opcode': 'EXISTS', 'path': '/none%d' % i,
                    'watch': False})
        await c.recv(6)
    finally:
        c.close()
        await srv.stop()
    ctr = col.get_collector(METRIC_FLUSH_SYSCALLS)
    assert ctr.value({'plane': 'server', 'backend': backend}) > 0


def test_mntr_reports_transport_backend():
    srv = ZKServer(transport='asyncio')
    rows = dict(srv.monitor_stats())
    assert rows['zk_transport_backend'] == 'asyncio'
    if BATCHED:
        srv2 = ZKServer(transport=BATCHED[0])
        rows2 = dict(srv2.monitor_stats())
        assert rows2['zk_transport_backend'] == BATCHED[0]


# -- the client plane: one tier for the clients of one loop ------------

class _Tap:
    """A TCP proxy in front of a server that keeps, per accepted
    connection, every byte the client side sent — the wire as the
    member saw it."""

    def __init__(self, upstream_port: int):
        self.upstream = upstream_port
        self.streams: list[bytearray] = []
        self._server = None
        self._writers: list = []

    async def start(self) -> '_Tap':
        self._server = await asyncio.start_server(
            self._accept, '127.0.0.1', 0)
        self.port = self._server.sockets[0].getsockname()[1]
        return self

    async def _accept(self, reader, writer):
        rec = bytearray()
        self.streams.append(rec)
        up_r, up_w = await asyncio.open_connection('127.0.0.1',
                                                   self.upstream)
        self._writers += [writer, up_w]

        async def pump(src, dst, keep):
            try:
                while True:
                    data = await src.read(1 << 16)
                    if not data:
                        break
                    if keep is not None:
                        keep.extend(data)
                    dst.write(data)
            except (ConnectionError, OSError):
                pass
            finally:
                dst.close()
        await asyncio.gather(pump(reader, up_w, rec),
                             pump(up_r, writer, None))

    async def stop(self) -> None:
        self._server.close()
        for w in self._writers:
            w.close()


def _requests_in(stream: bytes) -> tuple[list[dict], bytes, bytes]:
    """A recorded client stream after its ConnectRequest, decoded as a
    member decodes it: the request of every whole frame, the bytes of
    a trailing partial frame, and the recorded bytes decoded from."""
    head = 4 + int.from_bytes(stream[:4], 'big')
    codec = PacketCodec(server=True, use_native=False)
    assert len(codec.decode(bytes(stream[:head]))) == 1
    codec.handshaking = False
    body = bytes(stream[head:])
    return codec.decode(body), codec.take_pending(), body


def _assert_intact(stream: bytes, last_paths: list[str]) -> list[dict]:
    """The stream is whole frames only, byte-exact (a client's encoder
    gives the recorded bytes back from what the member decoded), xids
    ascending, and its last requests are for ``last_paths``, in that
    order."""
    reqs, partial, body = _requests_in(stream)
    assert partial == b''
    enc = PacketCodec(use_native=False)
    enc.handshaking = False
    assert b''.join(enc.encode(dict(p)) for p in reqs) == body
    xids = [p['xid'] for p in reqs]
    assert xids == sorted(xids)
    assert [p.get('path') for p in reqs[-len(last_paths):]] == last_paths
    return reqs


async def _fleet(port: int, n: int, backend: str, **kw) -> list[Client]:
    """``n`` connected clients, dialed one after another so that the
    i-th accepted connection is the i-th client's."""
    clients = []
    for _ in range(n):
        c = Client(address='127.0.0.1', port=port, transport=backend,
                   session_timeout=30000, max_spares=0, **kw)
        c.start()
        await c.wait_connected(timeout=10)
        clients.append(c)
    await asyncio.sleep(0.05)       # the handshakes' own flushes
    return clients


def _send(c: Client, path: str):
    """One ``getData`` handed to the client's send plane NOW (no loop
    hop): the future of its reply."""
    fut, _span = c._start_op(c._conn_or_raise(), {
        'opcode': 'GET_DATA', 'path': path, 'watch': False})
    return fut


def _depth(c: Client, backend: str) -> tuple[int, float]:
    h = c.collector.get_collector(METRIC_SUBMIT_DEPTH)
    labels = {'plane': 'client', 'backend': backend}
    return h.count(labels), h.sum(labels)


@pytest.mark.parametrize('backend', BATCHED)
async def test_clients_of_one_loop_hold_one_tier(backend):
    from zkstream_tpu.io import transport as tmod
    srv = await ZKServer().start()
    clients = await _fleet(srv.port, 5, backend)
    legacy = Client(address='127.0.0.1', port=srv.port,
                    transport='asyncio', session_timeout=30000)
    try:
        tier = clients[0].transport_tier
        assert isinstance(tier, TransportTier)
        assert tier.plane == 'client' and tier.backend == backend
        assert all(c.transport_tier is tier for c in clients)
        assert tier.refs == 5
        loop = asyncio.get_running_loop()
        assert tmod._loop_tiers[loop][backend] is tier
        # every connection's plane sends through it
        assert all(c._conn_or_raise()._tx._tier is tier
                   for c in clients)
        assert legacy.transport_tier is None
        other = [b for b in BATCHED if b != backend]
        if other:
            odd = (await _fleet(srv.port, 1, other[0]))[0]
            clients.append(odd)
            assert odd.transport_tier is not tier
            assert odd.transport_tier.backend == other[0]
        # the members' side is untouched: a tier of the server's own
        assert srv.transport_tier is not tier
    finally:
        for c in clients:
            await c.close()
        await srv.stop()


@pytest.mark.parametrize('backend', BATCHED)
async def test_fleet_burst_is_one_submission(backend):
    """One request from each of N clients in one loop iteration: ONE
    tick, ONE submission of depth N (in every client's collector: the
    series is the tier's), every connection's bytes intact and in
    order — on the parent N ticks and N submissions of depth 1."""
    n = 8
    srv = await ZKServer().start()
    tap = await _Tap(srv.port).start()
    clients = await _fleet(tap.port, n, backend)
    try:
        tier = clients[0].transport_tier
        subs0, sys0 = tier.submissions, tier.syscalls
        depth0 = _depth(clients[-1], backend)
        sent0 = [len(s) for s in tap.streams]
        futs = []
        for i, c in enumerate(clients):
            futs.append(_send(c, '/a%d' % i))
        futs.append(_send(clients[2], '/second'))   # 2 frames, 1 entry
        replies = await asyncio.gather(*futs, return_exceptions=True)
        assert all(getattr(r, 'code', None) == 'NO_NODE'
                   for r in replies), replies
        assert tier.submissions == subs0 + 1
        assert tier.syscalls == sys0 + (1 if backend == 'uring' else n)
        for c in clients:
            count, total = _depth(c, backend)
            assert (count, total) == (depth0[0] + 1, depth0[1] + n)
        ctr = clients[0].collector.get_collector(METRIC_FLUSH_SYSCALLS)
        assert ctr.value({'plane': 'client',
                          'backend': backend}) == tier.syscalls
        for i, stream in enumerate(tap.streams):
            assert len(stream) > sent0[i]
            _assert_intact(stream, ['/a%d' % i] + ['/second'] * (i == 2))
    finally:
        for c in clients:
            await c.close()
        await tap.stop()
        await srv.stop()


@pytest.mark.parametrize('backend', BATCHED)
async def test_lone_client_still_reads_depth_one(backend):
    srv = await ZKServer().start()
    (c,) = await _fleet(srv.port, 1, backend)
    try:
        await c.create('/lone', b'v')
        for _ in range(5):
            assert (await c.get('/lone'))[0] == b'v'
        count, total = _depth(c, backend)
        assert count >= 6 and total == count
        assert c.transport_tier.refs == 1
    finally:
        await c.close()
        await srv.stop()


@pytest.mark.parametrize('backend', BATCHED)
async def test_last_close_releases_the_tier(backend):
    """Closing one client leaves the others sending on an open tier;
    the ring fd and the registry entry go with the last one."""
    from zkstream_tpu.io import transport as tmod
    srv = await ZKServer().start()
    a, b, c = await _fleet(srv.port, 3, backend)
    loop = asyncio.get_running_loop()
    try:
        tier = a.transport_tier
        await b.create('/k', b'v')
        await a.close()
        assert tier.refs == 2 and tmod._loop_tiers[loop][backend] is tier
        if backend == 'uring':
            assert tier._uring is not None
        subs = tier.submissions
        got = await asyncio.gather(b.get('/k'), c.get('/k'))
        assert [d for d, _st in got] == [b'v', b'v']
        assert tier.submissions > subs and b.transport_tier is tier
        await b.close()
        await c.close()
        assert tier.refs == 0 and tier._uring is None
        assert loop not in tmod._loop_tiers
        # a later client builds the loop's tier anew
        (d,) = await _fleet(srv.port, 1, backend)
        try:
            assert d.transport_tier is not tier
            assert (await d.get('/k'))[0] == b'v'
        finally:
            await d.close()
    finally:
        for x in (a, b, c):
            await x.close()
        await srv.stop()


@pytest.mark.parametrize('backend', BATCHED)
def test_client_reused_across_two_runs_sends_on_both(backend):
    """One ``asyncio.run`` after another with the same client: its
    connection's plane keeps sending through the first loop's tier on
    the second loop (the dead-loop guard), and the client's lease
    moves to the second loop's tier, the first released."""
    import threading

    from zkstream_tpu.io import transport as tmod
    srv_loop = asyncio.new_event_loop()
    srv = srv_loop.run_until_complete(ZKServer().start())
    thread = threading.Thread(target=srv_loop.run_forever, daemon=True)
    thread.start()
    box: dict = {}

    async def first():
        c = box['c'] = Client(address='127.0.0.1', port=srv.port,
                              transport=backend, session_timeout=30000)
        c.start()
        await c.wait_connected(timeout=10)
        await c.create('/run1', b'1')
        box['tier'] = c.transport_tier
        box['loop'] = asyncio.get_running_loop()

    async def second():
        c = box['c']
        # the reply is never read (the socket's reader died with the
        # first loop): only the send is this test's business
        op = asyncio.ensure_future(c.create('/run2', b'2'))
        for _ in range(200):
            if '/run2' in box['seen']():
                break
            await asyncio.sleep(0.01)
        op.cancel()
        tier = c.transport_tier
        assert tier is not box['tier'] and tier.refs == 1
        assert tmod._loop_tiers[asyncio.get_running_loop()][backend] \
            is tier
        assert box['tier'].refs == 0
        assert box['loop'] not in tmod._loop_tiers
        c._tier_lease.release()

    def seen():
        fut = asyncio.run_coroutine_threadsafe(_paths(srv), srv_loop)
        return fut.result(5)
    box['seen'] = seen
    try:
        asyncio.run(first())
        assert '/run1' in seen()
        asyncio.run(second())
        assert '/run2' in seen()
    finally:
        asyncio.run_coroutine_threadsafe(srv.stop(), srv_loop).result(10)
        srv_loop.call_soon_threadsafe(srv_loop.stop)
        thread.join(10)
        assert not thread.is_alive()
        srv_loop.close()


async def _paths(srv) -> set:
    return {p for p in ('/run1', '/run2') if p in srv.db.nodes}


@pytest.mark.parametrize('backend', BATCHED)
async def test_flush_hard_drains_only_its_own_entry(backend):
    """Two clients with bytes parked in the shared tier: a hard flush
    on one is a submission of depth 1 that puts ITS bytes on the wire
    before returning, ahead of its later frames; the neighbour's stay
    parked until the tick and arrive whole."""
    srv = await ZKServer().start()
    tap = await _Tap(srv.port).start()
    a, b = await _fleet(tap.port, 2, backend)
    try:
        tier = a.transport_tier
        pa, pb = a._conn_or_raise()._tx, b._conn_or_raise()._tx
        futs = [_send(b, '/b1'), _send(b, '/b2')]
        pb.flush_now()                  # parked in the tier's entry
        futs.append(_send(a, '/a1'))
        subs, depth = tier.submissions, _depth(a, backend)
        sent_a = len(tap.streams[0])
        pa.flush_hard()
        assert tier.submissions == subs + 1
        assert _depth(a, backend) == (depth[0] + 1, depth[1] + 1)
        assert pa._entry.nbytes == 0 and pb._entry.nbytes > 0
        futs += [_send(a, '/a2'), _send(b, '/b3')]
        await asyncio.gather(*futs, return_exceptions=True)
        assert len(tap.streams[0]) > sent_a
        _assert_intact(tap.streams[0], ['/a1', '/a2'])
        _assert_intact(tap.streams[1], ['/b1', '/b2', '/b3'])
    finally:
        await a.close()
        await b.close()
        await tap.stop()
        await srv.stop()


@pytest.mark.parametrize('backend', BATCHED)
async def test_tx_fault_on_one_client_spares_its_neighbour(backend):
    """An injected truncated frame + reset on one client
    (``faults.tx``: before the cork, delivered by a hard flush of that
    client's entry) while a neighbour has frames corked and parked in
    the same tier: the neighbour's stream is byte-exact."""
    from zkstream_tpu.io.faults import FaultConfig, FaultInjector
    inj = FaultInjector(7, FaultConfig(p_tx_reset=1.0, max_faults=1))
    inj.active = False
    srv = await ZKServer().start()
    tap = await _Tap(srv.port).start()
    (a,) = await _fleet(tap.port, 1, backend, faults=inj)
    (b,) = await _fleet(tap.port, 1, backend)
    try:
        assert a.transport_tier is b.transport_tier
        futs = [_send(b, '/b1'), _send(b, '/b2')]
        b._conn_or_raise()._tx.flush_now()
        futs += [_send(b, '/b3'), _send(a, '/a1')]
        inj.active = True
        futs.append(_send(a, '/cut-%s' % ('x' * 64)))
        inj.active = False
        assert inj.fired == [('tx', 'tx mid-frame reset')]
        futs.append(_send(b, '/b4'))
        got = await asyncio.gather(*futs, return_exceptions=True)
        # the neighbour: four replies, four whole frames in order
        assert [getattr(r, 'code', None) for r in got[:3] + got[5:]] \
            == ['NO_NODE'] * 4
        reqs = _assert_intact(tap.streams[1], ['/b1', '/b2', '/b3', '/b4'])
        assert len(reqs) == 4
        # the faulted client: its earlier frame whole, then a cut one
        reqs, partial, _body = _requests_in(tap.streams[0])
        assert [p.get('path') for p in reqs] == ['/a1']
        assert 0 < len(partial) < 64
        assert isinstance(got[4], Exception)
    finally:
        inj.close()
        await a.close()
        await b.close()
        await tap.stop()
        await srv.stop()


@pytest.mark.parametrize('backend', BATCHED)
async def test_collector_shared_with_a_server_keeps_both_planes(backend):
    """The shared tier's series are its own and a joined client's
    collector ADOPTS them (reads its own rows plus theirs): a
    collector a server registered the same names in first keeps the
    server's rows."""
    col = Collector()
    srv = await ZKServer(transport=backend, collector=col).start()
    clients = await _fleet(srv.port, 2, backend, collector=col)
    try:
        await asyncio.gather(*[c.list('/') for c in clients])
        ctr = col.get_collector(METRIC_FLUSH_SYSCALLS)
        assert ctr.value({'plane': 'server', 'backend': backend}) > 0
        assert ctr.value({'plane': 'client', 'backend': backend}) \
            == clients[0].transport_tier.syscalls
        dep = col.get_collector(METRIC_SUBMIT_DEPTH)
        for plane in ('server', 'client'):
            assert dep.count({'plane': plane, 'backend': backend}) > 0
        text = col.expose()
        assert text.count('# TYPE %s counter' % METRIC_FLUSH_SYSCALLS) == 1
        assert ('%s_count{backend="%s",plane="client"}'
                % (METRIC_SUBMIT_DEPTH, backend)) in text
    finally:
        for c in clients:
            await c.close()
        await srv.stop()


# -- chaos slices: the batched tier under seeded faults ----------------

@needs_batched
async def test_chaos_slice_transport_batched(monkeypatch):
    """Transport-tier chaos with the batched backend force-enabled:
    byte faults, resets and delays against planes that defer to the
    submission queue — invariants and the no-open-spans check hold
    (`zkstream_tpu chaos --transport <be>` reruns any seed)."""
    from zkstream_tpu.io.faults import run_schedule
    monkeypatch.setenv('ZKSTREAM_TRANSPORT', BATCHED[0])
    for seed in range(3100, 3106):
        res = await run_schedule(seed)
        assert res.ok, (seed, res.violations)


@needs_sender
async def test_chaos_slice_every_batch_handed_over(monkeypatch):
    """The same seeds with EVERY raw batch of the clients' tier handed
    to the sender thread (the hand-over depth patched to 1): byte
    faults, injected resets and hard flushes meet batches in flight at
    every step, and the invariants hold."""
    from zkstream_tpu.io import transport as tmod
    from zkstream_tpu.io.faults import run_schedule
    monkeypatch.setenv('ZKSTREAM_TRANSPORT', 'mmsg')
    monkeypatch.setattr(tmod, 'OFFLOAD_MIN_SENDS', 1)
    handed = []
    real = tmod.TransportTier._hand_over

    def counted(self, *args):
        handed.append(real(self, *args))
        return handed[-1]
    monkeypatch.setattr(tmod.TransportTier, '_hand_over', counted)
    for seed in range(3100, 3106):
        res = await run_schedule(seed)
        assert res.ok, (seed, res.violations)
    assert len(handed) > 30 and all(handed)


async def test_chaos_slice_transport_asyncio_validator(monkeypatch):
    """The same seeds on the forced asyncio validator: a failure that
    appears in only one slice bisects to the tier."""
    from zkstream_tpu.io.faults import run_schedule
    monkeypatch.setenv('ZKSTREAM_TRANSPORT', 'asyncio')
    for seed in range(3100, 3106):
        res = await run_schedule(seed)
        assert res.ok, (seed, res.violations)


@needs_batched
@pytest.mark.timeout(120)
async def test_ensemble_chaos_slice_transport_batched(monkeypatch):
    """Ensemble tier with the batched backend force-enabled: member
    kills/restarts, partitions, migration, the crash-recovery image —
    invariants 1–7 and the no-open-spans check unchanged."""
    from zkstream_tpu.io.faults import run_ensemble_schedule
    monkeypatch.setenv('ZKSTREAM_TRANSPORT', BATCHED[0])
    for seed in range(3200, 3203):
        res = await run_ensemble_schedule(seed)
        assert res.ok, (seed, res.violations)
