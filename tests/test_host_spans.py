"""Time by phase from inside the program (ISSUE 24): host spans armed
by the profiler session (utils/trace.host_span), the fleet ingest's
tick phases as spans and as an always-on histogram, the members'
tick-ledger phases and latency histograms as cumulative ``mntr`` rows
whose before/after difference is a window's exact histogram."""

from __future__ import annotations

import asyncio
import sys

import pytest

from helpers import mntr_rows, wait_until
from zkstream_tpu import Client
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.utils import trace
from zkstream_tpu.utils.metrics import TICK_BUCKETS, Histogram, TickLedger

PHASES = ['ingest.batch', 'ingest.dispatch', 'ingest.readback',
          'ingest.route']


@pytest.fixture(autouse=True)
def clean_host_ring():
    # bound before any test patches the switch: a patch over the
    # unbound None would be restored to None beside a bound annotation
    trace._bind()
    trace.host_ring.reset()
    trace._recording = False
    _no_loops_thread()
    yield
    trace.host_ring.reset()
    trace._recording = False


@pytest.fixture
def armed(monkeypatch):
    """The profiler's switch patched on: spans are recorded as inside
    a session (the annotation itself is then an un-armed no-op)."""
    monkeypatch.setattr(trace, '_is_enabled', lambda: True)


def _no_loops_thread() -> None:
    """This thread is no loop's until a test's loop turns under a
    session (an earlier test's did)."""
    trace._turn()[:] = [None, None, 0, None, None, None]


def _totals() -> dict:
    """The ring's totals without the collector's pauses: a collection
    may fall inside any armed stretch (``gc.pause[@span]``)."""
    return {k: v for k, v in trace.host_ring.totals.items()
            if not k.startswith('gc.pause')}


def _ingest() -> FleetIngest:
    return FleetIngest(max_frames=8, min_len=256,
                       bypass_bytes=0, warm='block')


async def _fleet_client(server, ingest) -> Client:
    await ingest.prewarm(1)
    c = Client(address='127.0.0.1', port=server.port, ingest=ingest,
               session_timeout=5000)
    c.start()
    await c.wait_connected(timeout=5)
    return c


def _check_device_ticks(spans) -> list:
    """Every device tick in ``spans``: ``ingest.tick`` with its four
    phases in order, all numbered alike, inside it, summing to no more
    than it.  Returns the tick spans."""
    ticks = [s for s in spans
             if s.op == 'ingest.tick' and s.tick is not None]
    assert len({t.tick for t in ticks}) == len(ticks)
    for t in ticks:
        assert t.kind == 'host' and t.parent is None
        assert t.detail.startswith('device ') and t.nbytes > 0
        kids = [s for s in spans
                if s.parent == 'ingest.tick' and s.tick == t.tick]
        assert [k.op for k in kids] == PHASES
        for k in kids:
            assert t.t0_ns <= k.t0_ns <= k.t1_ns <= t.t1_ns
            assert k.duration_ms == pytest.approx(
                (k.t1_ns - k.t0_ns) / 1e6)
        assert [k.t0_ns for k in kids] == sorted(k.t0_ns for k in kids)
        assert sum(k.t1_ns - k.t0_ns for k in kids) <= t.t1_ns - t.t0_ns
    return ticks


def test_unarmed_host_span_is_the_shared_noop():
    """No profiler session: one shared object, nothing recorded,
    nothing allocated."""
    assert not trace.host_ring and not trace.host_ring.totals
    for accumulate in (False, True):
        assert trace.host_span('x', accumulate) is trace.NO_SPAN
    with trace.host_span('ingest.tick', tick=3) as sp:
        assert sp is trace.NO_SPAN
        sp.set(detail='d', batch=1)
        sp.cancel()

    def many():
        for _ in range(10_000):
            with trace.host_span('client.rx', accumulate=True):
                pass
            with trace.host_span('ingest.batch', tick=7):
                pass
    many()                                  # warm every cache
    before = sys.getallocatedblocks()
    many()
    assert sys.getallocatedblocks() - before < 1000      # not 20,000
    assert len(trace.host_ring) == 0 and not trace.host_ring.totals
    assert trace.host_ring.dropped == 0


def test_armed_spans_nest_settle_and_accumulate(armed):
    with trace.host_span('ingest.tick', tick=5) as sp:
        with trace.host_span('ingest.batch', tick=5):
            pass
        with trace.host_span('client.rx', accumulate=True):
            pass
        sp.set(detail='device 8x256 streams=1', batch=2, nbytes=40)
    with trace.host_span('client.rx', accumulate=True):
        pass
    with trace.host_span('ingest.tick', tick=6) as sp:
        sp.cancel()                         # routed nothing
    batch, tick = trace.host_ring.spans()
    assert (batch.op, batch.parent, batch.tick) == (
        'ingest.batch', 'ingest.tick', 5)
    assert (tick.op, tick.parent, tick.tick, tick.batch, tick.nbytes) == (
        'ingest.tick', None, 5, 2, 40)
    assert tick.status == 'ok' and tick.kind == 'host'
    assert tick.t0_ns <= batch.t0_ns <= batch.t1_ns <= tick.t1_ns
    count, total_ns = trace.host_ring.totals['client.rx']
    assert count == 2 and total_ns > 0
    # schema 3: the host fields follow the schema-2 keys, in one order
    keys = list(tick.to_dict())
    assert keys[-4:] == ['tick', 't0_ns', 't1_ns', 'duration_ms']
    assert keys.index('detail') < keys.index('tick')
    assert list(batch.to_dict())[-5:] == [
        'parent', 'tick', 't0_ns', 't1_ns', 'duration_ms']


def test_a_new_session_resets_the_ring(monkeypatch):
    """The ring holds exactly one profiler session."""
    on = [True]
    monkeypatch.setattr(trace, '_is_enabled', lambda: on[0])
    for _ in range(3):
        with trace.host_span('a'):
            pass
        with trace.host_span('b', accumulate=True):
            pass
    assert len(trace.host_ring) == 3
    on[0] = False
    assert trace.host_span('a') is trace.NO_SPAN
    assert len(trace.host_ring) == 3        # kept for the reader
    on[0] = True
    with trace.host_span('a'):
        pass
    assert len(trace.host_ring) == 1
    assert _totals() == {}


def test_host_ring_counts_what_it_drops(armed, monkeypatch):
    small = trace.TraceRing(4)
    monkeypatch.setattr(trace, 'host_ring', small)
    for _ in range(6):
        with trace.host_span('a'):
            pass
    assert len(small) == 4 and small.dropped == 2
    small.reset()
    assert len(small) == 0 and small.dropped == 0


async def test_device_tick_leaves_tick_and_four_phases(server, armed):
    ingest = _ingest()
    c = await _fleet_client(server, ingest)
    try:
        await c.create('/h', b'v' * 100)
        trace.host_ring.reset()
        t0, ops0 = ingest.ticks, 6
        for _ in range(ops0):
            data, _stat = await c.get('/h')
            assert data == b'v' * 100
        spans = trace.host_ring.spans()
        ticks = _check_device_ticks(spans)
        assert [t.tick for t in ticks] == list(
            range(t0 + 1, ingest.ticks + 1))
        assert sum(t.batch for t in ticks) >= ops0      # frames routed
        # the per-op boundaries: counted, no object each
        assert trace.host_ring.totals['client.submit'][0] == ops0
        assert trace.host_ring.totals['client.rx'][0] >= len(ticks)
        assert {s.op for s in spans} == {'ingest.tick', *PHASES}
        assert trace.host_ring.dropped == 0
    finally:
        await c.close()
    # always on, armed or not: one observation per phase per device tick
    for phase in ('batch', 'dispatch', 'readback', 'route'):
        assert ingest.phase_hist.count({'phase': phase}) == ingest.ticks
    assert ingest.phase_hist.buckets == TICK_BUCKETS


async def test_a_fleets_burst_is_one_client_flush(server, armed):
    """The send side's engagement counter: ``client.flush`` is the
    shared tier's tick, so ``client.submit``'s count over its count is
    requests per flush — N for a burst from N sessions of one loop
    (1.0 by construction while every client had a tier of its own)."""
    from zkstream_tpu.io.transport import probe
    if probe().chosen == 'asyncio':
        pytest.skip('no batched transport backend: no tier, no flush')
    clients = []
    try:
        for _ in range(6):
            c = Client(address='127.0.0.1', port=server.port,
                       session_timeout=30000, max_spares=0)
            c.start()
            await c.wait_connected(timeout=5)
            clients.append(c)
        await asyncio.sleep(0.05)
        trace.host_ring.reset()
        got = await asyncio.gather(*[c.list('/') for c in clients])
        assert len(got) == 6
        totals = trace.host_ring.totals
        assert totals['client.submit'][0] == 6
        assert totals['client.flush'][0] == 1
        assert 0 < totals['client.flush'][1]
        assert len(trace.host_ring) == 0        # count and total only
    finally:
        for c in clients:
            await c.close()


async def test_a_deep_burst_is_one_handoff_one_reap_and_its_sends(
        server, armed):
    """The hand-over's books: a burst from N >= OFFLOAD_MIN_SENDS
    sessions of one loop on ``mmsg`` is ONE ``client.handoff`` and (at
    least) one ``client.reap``, and ``client.send`` totals N
    connections with the sender thread's own busy nanoseconds; a
    shallow burst is sent inline and totals its connections under
    ``client.send`` on the loop's clock, with no hand-over."""
    from zkstream_tpu.io.transport import OFFLOAD_MIN_SENDS, probe
    from zkstream_tpu.utils.native import ensure_ext
    if not probe().mmsg or not hasattr(ensure_ext(), 'sender_submit'):
        pytest.skip('no native sender here')
    n = OFFLOAD_MIN_SENDS + 2
    clients = []
    try:
        for _ in range(n):
            c = Client(address='127.0.0.1', port=server.port,
                       transport='mmsg', session_timeout=30000,
                       max_spares=0)
            c.start()
            await c.wait_connected(timeout=5)
            clients.append(c)
        await asyncio.sleep(0.05)
        tier = clients[0].transport_tier
        trace.host_ring.reset()
        assert len(await asyncio.gather(
            *[c.list('/') for c in clients])) == n
        totals = trace.host_ring.totals
        assert totals['client.flush'][0] == 1
        assert totals['client.handoff'][0] == 1 == tier.offloaded_batches
        assert totals['client.reap'][0] >= 1
        assert totals['client.send'][0] == n
        assert 0 < totals['client.send'][1]
        assert 0 < totals['client.handoff'][1] < totals['client.flush'][1]
        assert len(trace.host_ring) == 0        # counts and totals only
        trace.host_ring.reset()
        few = clients[:3]
        assert len(await asyncio.gather(*[c.list('/') for c in few])) == 3
        totals = trace.host_ring.totals
        assert totals['client.send'][0] == 3 and totals['client.send'][1] > 0
        assert 'client.handoff' not in totals
        assert tier.offloaded_batches == 1
    finally:
        for c in clients:
            await c.close()


async def test_a_reap_books_its_deliveries_and_the_threads_recvs(
        server, armed):
    """The receive half's books: replies to N sessions of one loop on
    ``mmsg`` come through ``client.rx_reap`` — one span a reap, the
    connections' ``client.rx`` spans nested inside it —
    ``client.rx_reaped`` counts the deliveries those reaps made
    (every ``client.rx`` of the window: the offload share is 100%)
    and ``client.recv`` totals the receiver thread's ``recv(2)`` calls
    with the nanoseconds inside them, on its own clock.  On a tier
    that does not own the receive none of the three exists."""
    from zkstream_tpu.io.transport import probe
    from zkstream_tpu.utils.native import ensure_ext
    if not probe().mmsg or not hasattr(ensure_ext(), 'receiver_reap'):
        pytest.skip('no native receiver here')
    n = 12
    clients = []
    try:
        for _ in range(n):
            c = Client(address='127.0.0.1', port=server.port,
                       transport='mmsg', session_timeout=30000,
                       max_spares=0)
            c.start()
            await c.wait_connected(timeout=5)
            clients.append(c)
        await asyncio.sleep(0.05)
        tier = clients[0].transport_tier
        reads = tier.received_reads
        trace.host_ring.reset()
        assert len(await asyncio.gather(
            *[c.list('/') for c in clients])) == n
        totals = trace.host_ring.totals
        assert totals['client.rx'][0] == n
        assert totals['client.rx_reaped'] == [n, 0]
        assert tier.received_reads - reads == n
        assert 1 <= totals['client.rx_reap'][0] <= n
        # the reaps hold the deliveries' own spans, and more
        assert totals['client.rx_reap'][1] > totals['client.rx'][1] > 0
        assert totals['client.recv'][0] >= n
        assert 0 < totals['client.recv'][1]
        assert len(trace.host_ring) == 0        # counts and totals only
    finally:
        for c in clients:
            await c.close()
    c = Client(address='127.0.0.1', port=server.port,
               transport='asyncio', session_timeout=30000, max_spares=0)
    try:
        c.start()
        await c.wait_connected(timeout=5)
        trace.host_ring.reset()
        await c.list('/')
        totals = trace.host_ring.totals
        assert totals['client.rx'][0] == 1
        assert not {'client.rx_reap', 'client.rx_reaped',
                    'client.recv'} & set(totals)
    finally:
        await c.close()


async def test_a_reap_that_feeds_books_each_connection_once(
        server, armed):
    """The same books with the connections under a fleet ingest and
    their sinks standing (io/transport.py ``rx_sink``): a connection
    the reap's C call fed is one ``client.rx`` — its total the
    nanoseconds inside that call's feeds — one ``client.rx_reaped``
    and one ``client.rx_fed``; a connection without a sink (a second
    ``sockData`` listener) is a ``client.rx`` span of its own beside
    them, so count(``client.rx``) = count(``client.rx_reaped``) = the
    deliveries, and the fed share is fed / reaped.  Every op's four
    stage waits still sum to its span to the nanosecond: the fed
    connections' ``t_rx`` is ONE clock read a reap."""
    from zkstream_tpu.io.transport import probe
    from zkstream_tpu.utils.native import ensure_ext
    if not probe().mmsg or not hasattr(ensure_ext(), 'receiver_reap'):
        pytest.skip('no native receiver here')
    n = 12
    ingest = _ingest()
    await ingest.prewarm(n)
    clients = []
    try:
        for _ in range(n):
            c = Client(address='127.0.0.1', port=server.port,
                       transport='mmsg', session_timeout=30000,
                       max_spares=0, ingest=ingest)
            c.start()
            await c.wait_connected(timeout=5)
            clients.append(c)
        await clients[0].create('/fed', b'v' * 100)
        await asyncio.sleep(0.05)
        tier = clients[0].transport_tier
        assert len(tier._sinks) == n and list(tier._sink_owners) == [ingest]
        # one of them is listened to: it has no sink
        heard: list = []
        plain = clients[-1]
        plain.get_session().conn.on('sockData', heard.append)
        assert len(tier._sinks) == n - 1
        reads, fed = tier.received_reads, tier.received_fed
        trace.host_ring.reset()
        for c in clients:
            c.trace.clear()
        rounds = 3
        for _ in range(rounds):
            assert len(await asyncio.gather(
                *[c.get('/fed') for c in clients])) == n
        totals = trace.host_ring.totals
        assert totals['client.rx'][0] == rounds * n
        assert totals['client.rx_reaped'] == [rounds * n, 0]
        assert totals['client.rx_fed'][0] == rounds * (n - 1)
        assert len(heard) == rounds
        assert tier.received_reads - reads == rounds * n
        assert tier.received_fed - fed == rounds * (n - 1)
        assert tier.fed_ctr.value({'plane': 'client'}) \
            == tier.received_fed
        # the feeds' nanoseconds are inside ``client.rx``, which is
        # inside the reaps
        assert 0 < totals['client.rx_fed'][1] <= totals['client.rx'][1] \
            < totals['client.rx_reap'][1]
        spans = [s for c in clients for s in c.trace.spans()]
        _check_stages(spans, rounds * n)
        assert all(ns > 0 for _c, ns in _stage_totals())
        # a reap's fed connections share its one stamp
        ticks = {s.tick for s in spans}
        assert ticks <= {s.tick for s in trace.host_ring.spans()
                         if s.op == 'ingest.tick'}
        assert not ingest._rx_marks      # consumed with the bytes
    finally:
        for c in clients:
            await c.close()
        ingest.close()


def test_host_add_is_armed_by_the_session_alone(monkeypatch):
    """``host_add`` books work counted and timed elsewhere (a native
    thread's batch) under a name's totals inside a profiler session,
    and nothing outside one."""
    trace.host_add('client.send', 5, 1000)
    assert trace.host_ring.totals == {}
    monkeypatch.setattr(trace, '_is_enabled', lambda: True)
    trace.host_add('client.send', 5, 1000)
    trace.host_add('client.send', 2, 500)
    assert _totals() == {'client.send': [7, 1500]}
    assert len(trace.host_ring) == 0


async def test_a_sessions_birth_and_death_are_booked(server, monkeypatch):
    """``client.connect`` (``start()`` -> the first ``'connect'``) and
    ``client.close`` (``close()`` from the call to its return) are
    totals on the wall clock, one count a session, inside a profiler
    session and not outside one."""
    quiet = Client(address='127.0.0.1', port=server.port,
                   session_timeout=5000)
    quiet.start()
    await quiet.wait_connected(timeout=5)
    assert 'client.connect' not in trace.host_ring.totals
    monkeypatch.setattr(trace, '_is_enabled', lambda: True)
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000)
    c.start()
    await c.wait_connected(timeout=5)
    await c.create('/born', b'')
    born = trace.host_ring.totals['client.connect']
    assert born[0] == 1 and born[1] > 0
    assert 'client.close' not in trace.host_ring.totals
    await c.close()
    await quiet.close()     # connected before the session: its close counts
    assert trace.host_ring.totals['client.connect'] == born
    gone = trace.host_ring.totals['client.close']
    assert gone[0] == 2 and gone[1] > 0


async def test_the_route_counts_the_names_it_delivered(server, armed):
    """``names_routed`` (always on) and the ``ingest.route`` span's
    ``names``: the names in the children lists a device tick routed."""
    ingest = _ingest()
    c = await _fleet_client(server, ingest)
    try:
        await c.create('/dir', b'')
        for i in range(5):
            await c.create('/dir/n%d' % (i,), b'')
        assert ingest.names_routed == 0
        trace.host_ring.reset()
        for _ in range(3):
            names, _stat = await c.list('/dir')
            assert len(names) == 5
        await c.get('/dir')
        assert ingest.names_routed == 15
        routes = [s for s in trace.host_ring.spans()
                  if s.op == 'ingest.route']
        assert sum(s.names for s in routes) == 15
        assert all(s.to_dict()['names'] == s.names for s in routes
                   if s.names)
    finally:
        await c.close()


async def test_the_route_counts_the_lists_it_shared(server, armed):
    """``lists_routed`` / ``lists_shared`` (always on) and the
    ``ingest.route`` span's ``lists`` / ``shared``: the children lists
    a device tick routed, and those its one C decode served from an
    equal body it had parsed already.  Every decode parses a body at
    least once, so a span shares fewer lists than it routed."""
    ingest = _ingest()
    c = await _fleet_client(server, ingest)
    try:
        await c.create('/herd', b'')
        wide = sorted('node-%04d:8983_solr' % i for i in range(24))
        for name in wide:
            await c.create('/herd/' + name, b'')
        assert (ingest.lists_routed, ingest.lists_shared) == (0, 0)
        trace.host_ring.reset()
        for _ in range(4):
            views = await asyncio.gather(*[c.list('/herd')
                                           for _ in range(6)])
            assert [sorted(v) for v, _stat in views] == [wide] * 6
            assert len({id(v) for v, _stat in views}) == 6
        await c.get('/herd')
        assert ingest.lists_routed == 24
        routes = [s for s in trace.host_ring.spans()
                  if s.op == 'ingest.route']
        assert sum(s.lists for s in routes) == 24
        assert sum(s.shared for s in routes) == ingest.lists_shared
        assert all(s.shared < s.lists for s in routes if s.lists)
        if c.current_connection().codec.ext is None:
            assert ingest.lists_shared == 0
        else:   # the replies to a pipelined burst stand in few ticks
            assert ingest.lists_shared >= 4
    finally:
        await c.close()


async def test_a_loops_requests_share_its_deadline_timer(server, armed):
    """The deadline queue's engagement counter: ``client.deadline`` is
    an arming or a firing of the loop's ONE timer, so ``client.submit``'s
    count over its count is requests per loop timer (1.0 by
    construction while every op armed an ``asyncio.wait_for``)."""
    from zkstream_tpu.utils.aio import deadline_queue
    clients = []
    try:
        for _ in range(4):
            c = Client(address='127.0.0.1', port=server.port,
                       session_timeout=30000, max_spares=0)
            c.start()
            await c.wait_connected(timeout=5)
            clients.append(c)
        queue = deadline_queue(asyncio.get_running_loop())
        trace.host_ring.reset()
        for _ in range(10):
            await asyncio.gather(*[c.list('/', deadline=30000)
                                   for c in clients for _ in range(25)])
        totals = trace.host_ring.totals
        assert totals['client.submit'][0] == 1000
        # the first arming: equal timeouts come due in the order
        # they were added, so no later one moves the timer
        assert totals['client.deadline'][0] == 1 and len(queue) == 0
        server.drop_replies = True
        before = totals['client.deadline'][0]
        with pytest.raises(Exception) as ei:
            await clients[0].list('/', deadline=20)
        assert getattr(ei.value, 'code', None) == 'DEADLINE_EXCEEDED'
        # the earlier deadline moved the timer, and it fired
        assert totals['client.deadline'][0] >= before + 2
        assert len(trace.host_ring) == 0        # count and total only
        # the reply that never came, or close() would wait for it
        conn = clients[0].current_connection()
        (xid,) = [x for x in conn.reqs if x > 0]
        conn.process_reply({'xid': xid, 'zxid': 1, 'err': 'OK',
                            'opcode': 'GET_CHILDREN2', 'children': [],
                            'stat': None})
    finally:
        server.drop_replies = False
        for c in clients:
            await c.close()


async def test_phase_histogram_needs_no_session_and_binds(server):
    from zkstream_tpu import Collector

    col = Collector()
    ingest = _ingest()
    ingest.bind_metrics(col)
    c = await _fleet_client(server, ingest)
    try:
        await c.create('/p', b'x')
        await c.get('/p')
    finally:
        await c.close()
    assert ingest.ticks > 0 and len(trace.host_ring) == 0
    hist = col.get_collector('zkstream_ingest_phase_ms')
    assert hist is ingest.phase_hist
    text = col.expose()
    for phase in ('batch', 'dispatch', 'readback', 'route'):
        assert hist.count({'phase': phase}) == ingest.ticks
        assert ('zkstream_ingest_phase_ms_count{phase="%s"} %d'
                % (phase, ingest.ticks)) in text
    assert ingest.tick_hist.count() >= ingest.ticks


async def test_a_tick_off_the_device_says_which(server, armed):
    """A pass-through (direct) tick is one ``ingest.tick`` span with no
    tick number and no phases."""
    ingest = FleetIngest(max_frames=8, min_len=256,
                         warm='block')       # bypass_bytes at default
    c = Client(address='127.0.0.1', port=server.port, ingest=ingest,
               session_timeout=5000)
    c.start()
    try:
        await c.wait_connected(timeout=5)
        await c.create('/d', b'x')
        await c.get('/d')
        await asyncio.sleep(0)               # the bookkeeping tick
    finally:
        await c.close()
    spans = trace.host_ring.spans()
    assert ingest.ticks == 0 and ingest.ticks_scalar > 0
    assert spans and {s.op for s in spans} == {'ingest.tick'}
    assert all(s.tick is None and s.detail == 'direct' for s in spans)
    assert not ingest.phase_hist.count({'phase': 'batch'})


async def test_spans_lie_in_the_profilers_trace(server, tmp_path):
    """Under a real profiler session (CPU backend): the same device
    tick is in the host ring AND, as ``TraceAnnotation`` events with
    the tick's number, on one thread's line of ``/host:CPU`` in the
    ``.xplane.pb`` — nested, phases inside the tick."""
    import glob

    import jax
    from jax.profiler import ProfileData

    ingest = _ingest()
    c = await _fleet_client(server, ingest)
    try:
        await c.create('/t', b'v' * 64)
        assert trace.host_span('x') is trace.NO_SPAN
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(4):
                await c.get('/t')
        finally:
            jax.profiler.stop_trace()
        assert trace.host_span('x') is trace.NO_SPAN
    finally:
        await c.close()
    ticks = _check_device_ticks(trace.host_ring.spans())
    assert ticks and trace.host_ring.totals['client.submit'][0] == 4
    (path,) = glob.glob(str(tmp_path / '**' / '*.xplane.pb'),
                        recursive=True)
    names = ['ingest.tick', *PHASES, 'client.rx', 'client.submit']
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != '/host:CPU':
            continue
        for line in plane.lines:
            found = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats).get('tick'))
                     for e in line.events if e.name in names]
            if found:
                lines.append(found)
    assert len(lines) == 1                   # the loop's thread
    events = lines[0]
    assert {n for n, *_ in events} == set(names)
    for t in ticks:
        (outer,) = [e for e in events
                    if e[0] == 'ingest.tick' and e[3] == t.tick]
        inner = [e for e in events if e[0] in PHASES and e[3] == t.tick]
        assert [e[0] for e in sorted(inner, key=lambda e: e[1])] == PHASES
        assert all(outer[1] <= e[1] <= e[2] <= outer[2] for e in inner)


# -- a request's latency by stage, and the collector's pauses ------------

def _stage_totals() -> list:
    return [trace.host_ring.totals.get(name, [0, 0])
            for name in trace.STAGE_WAITS]


def _check_stages(spans, ops: int) -> None:
    """``ops`` staged ops were booked, once each, and their four waits
    sum to what their spans say they took on the host clock."""
    assert [c for c, _ns in _stage_totals()] == [ops] * 4
    assert all(s.stages is None for s in spans)     # booked: released
    staged = [s for s in spans if s.t0_ns is not None]
    assert len(staged) == ops
    assert sum(ns for _c, ns in _stage_totals()) == sum(
        s.t1_ns - s.t0_ns for s in staged)


async def test_an_ops_four_waits_sum_to_its_span(server, armed):
    """Inside a session every op through the ingest is stamped at
    submit, flush, rx, settle and resume: the four differences are
    counted once an op under ``client.*_wait`` and sum to the op
    span's ``t1_ns - t0_ns`` exactly; the span's ``tick`` is the
    ``ingest.tick`` whose route settled it; the two synchronous
    stretches beside them are ``client.prepare`` / ``client.resume``."""
    ingest = _ingest()
    c = await _fleet_client(server, ingest)
    try:
        await c.create('/s', b'v' * 100)
        trace.host_ring.reset()
        c.trace.clear()
        ops = 5
        for _ in range(ops):
            await c.get('/s')
        with pytest.raises(Exception) as ei:         # a failed op too
            await c.get('/missing')
        assert getattr(ei.value, 'code', None) == 'NO_NODE'
        ops += 1
        spans = c.trace.spans()
        _check_stages(spans, ops)
        totals = trace.host_ring.totals
        assert totals['client.prepare'][0] == ops
        assert totals['client.resume'][0] == ops
        assert totals['client.submit'][0] == ops
        routes = {s.tick: s for s in trace.host_ring.spans()
                  if s.op == 'ingest.route'}
        ticks = {s.tick for s in trace.host_ring.spans()
                 if s.op == 'ingest.tick' and s.tick is not None}
        for s in spans:
            # settled inside that tick's route: it began after the
            # submit and before the awaiter ran again
            assert s.tick in ticks
            assert s.t0_ns < routes[s.tick].t0_ns < s.t1_ns
            # the op's own clock agrees: submit -> settle on it
            assert s.duration_ms <= (s.t1_ns - s.t0_ns) / 1e6 + 0.05
        # every wait is a real stretch here: bytes crossed a socket
        assert all(ns > 0 for _c, ns in _stage_totals())
        # the schema-3 keys carry it: no new key
        keys = list(spans[0].to_dict())
        assert keys[-4:] == ['tick', 't0_ns', 't1_ns', 'duration_ms']
        assert len(trace.host_ring) == 5 * len(ticks)   # no object an op
    finally:
        await c.close()


async def test_an_expired_and_a_late_reply_are_booked_once(server, armed):
    """An op whose deadline fires is booked when its awaiter resumes —
    its ``wire_wait`` runs to there, the stages it never reached take
    nothing — and the reply that comes late, dropped, books nothing
    more.  Off the device an op's ``tick`` stays None."""
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=30000, max_spares=0)
    c.start()
    await c.wait_connected(timeout=5)
    try:
        await asyncio.sleep(0.05)
        trace.host_ring.reset()
        c.trace.clear()
        await c.list('/')
        server.drop_replies = True
        with pytest.raises(Exception) as ei:
            await c.list('/', deadline=20)
        assert getattr(ei.value, 'code', None) == 'DEADLINE_EXCEEDED'
        ok, expired = c.trace.spans()
        _check_stages([ok, expired], 2)
        assert ok.tick is None and expired.tick is None
        assert (expired.t1_ns - expired.t0_ns) / 1e6 >= 20
        cork, wire, tick, wake = _stage_totals()
        # nearly all of the 20 ms stood between the flush and a reply
        assert wire[1] >= 0.9 * (expired.t1_ns - expired.t0_ns)
        before = [list(t) for t in _stage_totals()]
        conn = c.current_connection()
        (xid,) = [x for x in conn.reqs if x > 0]
        conn._sock_data(b'')                # arms this call's rx mark
        conn.process_reply({'xid': xid, 'zxid': 1, 'err': 'OK',
                            'opcode': 'GET_CHILDREN2', 'children': [],
                            'stat': None})
        assert xid not in conn.reqs
        assert _stage_totals() == before
        assert expired.stages is None and expired.status == 'deadline'
    finally:
        server.drop_replies = False
        await c.close()


async def test_two_requests_in_flight_are_each_stamped_by_their_own_flush(
        armed):
    """A pipelined connection: the flush that takes a request's bytes
    stamps THAT request (two corked in one tick share a flush, the one
    sent a turn later has a later one); both replies in one segment
    share the ``_sock_data`` call that brought them and the tick that
    routed them, and settle in order."""
    import random

    from test_ingest_route import Peer, settle

    ingest = FleetIngest(bypass_bytes=0, warm='block', placement='host',
                         max_frames=4, min_len=256)
    p = Peer(0, ingest, True, random.Random(7))
    ring = trace.TraceRing(8)

    def submit():
        return _staged_submit(p, ring)

    try:
        (a, ra), (b, rb) = submit(), submit()
        assert a.stages[trace.T_FLUSH] == 0 == b.stages[trace.T_FLUSH]
        await settle()                      # the cork's tick flush
        c_, rc = submit()
        await settle()
        fa, fb, fc = (s.stages[trace.T_FLUSH] for s in (a, b, c_))
        assert a.stages[trace.T_SUBMIT] < b.stages[trace.T_SUBMIT] < fa
        assert fa == fb < c_.stages[trace.T_SUBMIT] < fc
        assert len(p.conn.transport.out) >= 2       # two writes went out
        for req in (ra, rb, rc):
            p.reply(req.packet['xid'])
        wire, p.wire = bytes(p.wire), bytearray()
        t_before = ingest.ticks
        p.conn._sock_data(wire)             # one segment, three replies
        await settle()
        assert ingest.ticks == t_before + 1
        rx = [s.stages[trace.T_RX] for s in (a, b, c_)]
        assert rx[0] == rx[1] == rx[2] == p.conn._rx_t0 > fc
        st = [s.stages[trace.T_SETTLE] for s in (a, b, c_)]
        assert rx[0] < st[0] < st[1] < st[2]
        assert {s.tick for s in (a, b, c_)} == {ingest.ticks}
        assert ingest.routing is None       # only while a route runs
        for span, req in ((a, ra), (b, rb), (c_, rc)):
            assert req.fut.done() and span.status == 'ok'
            trace.op_resumed(span).__exit__(None, None, None)
        _check_stages([a, b, c_], 3)
        assert trace.host_ring.totals['client.resume'][0] == 3
    finally:
        p.session.close()
        p.conn.destroy()
        await settle()
        ingest.close()


def _staged_submit(p, ring):
    sub = trace.host_span('client.submit', accumulate=True)
    with sub:
        span = ring.start('GET_DATA', '/k')
        span.stages = [sub.t0_ns, 0, 0, 0]
        req = p.conn.request({'opcode': 'GET_DATA', 'path': '/k',
                              'watch': False}, span)
    req.as_future()
    return span, req


@pytest.mark.parametrize('via', ['sock_data', 'sink'])
@pytest.mark.parametrize('lead', ['reply', 'notification'])
async def test_a_pipelined_reply_is_stamped_by_the_call_that_completed_it(
        armed, lead, via):
    """Three replies of one connection come in two receive calls
    before one tick — the first whole and half of the second, then the
    rest: each request's ``t_rx`` is the start of the call that brought
    ITS reply's last byte, not the connection's newest, through the
    direct lane and (a notification leads the stream) through
    ``deliver``; the marks are gone with the bytes.  A call is a
    ``_sock_data``, or (``sink``) a reap whose one C call fed the
    slot: its stamp is that reap's one clock read."""
    import random

    from test_ingest_route import Peer, ReapRig, settle

    ingest = FleetIngest(bypass_bytes=0, warm='block', placement='host',
                         max_frames=8, min_len=256)
    p = Peer(0, ingest, True, random.Random(11))
    ring = trace.TraceRing(8)
    calls: list = []
    rig = ReapRig(sink=True)
    if via == 'sock_data':
        p.conn.on('sockData', lambda _d: calls.append(p.conn._rx_t0))

        def receive(data):
            p.conn._sock_data(data)
    else:
        def receive(data):
            rig.reap([(p.conn, data)])
            calls.append(p.conn._rx_t0)
    try:
        (a, ra), (b, rb), (c_, rc) = (_staged_submit(p, ring)
                                      for _ in range(3))
        await settle()
        if lead == 'notification':
            p.notification()
        p.reply(ra.packet['xid'])
        first = len(p.wire)
        p.reply(rb.packet['xid'])
        cut = (first + len(p.wire)) // 2        # inside the second reply
        p.reply(rc.packet['xid'])
        wire, p.wire = bytes(p.wire), bytearray()
        ticks = ingest.ticks
        receive(wire[:cut])
        receive(wire[cut:])
        assert len(calls) == 2 and 0 < calls[0] < calls[1]
        assert rig.fed == (2 if via == 'sink' else 0)
        assert [m[0] for m in ingest._rx_marks[id(p.conn)]] == [
            cut, len(wire)]
        await settle()
        # one tick routed all three; behind a notification (a first
        # frame of ~50 B: the slot gives 256) the cut slot finishes on
        # follow-up ticks, and the marks follow what it consumed; a
        # reap dispatches at its end, so the first reap's whole reply
        # is in flight when the second reap comes: two ticks
        assert ingest.ticks - ticks == (2 if via == 'sink' else 1) \
            or lead == 'notification'
        rx = [s.stages[trace.T_RX] for s in (a, b, c_)]
        assert rx == [calls[0], calls[1], calls[1]]
        st = [s.stages[trace.T_SETTLE] for s in (a, b, c_)]
        assert calls[1] < st[0] < st[1] < st[2]
        assert {s.tick for s in (a, b, c_)} <= set(
            range(ticks + 1, ingest.ticks + 1))
        assert not ingest._rx_marks
        for span, req in ((a, ra), (b, rb), (c_, rc)):
            assert req.fut.done() and span.status == 'ok'
            trace.op_resumed(span).__exit__(None, None, None)
        _check_stages([a, b, c_], 3)
        # the first reply waited in its slot while the second came
        wire_w, tick_w = (trace.host_ring.totals[n][1] for n in (
            'client.wire_wait', 'client.tick_wait'))
        assert tick_w >= 3 * (st[0] - calls[1]) + (calls[1] - calls[0])
    finally:
        p.session.close()
        p.conn.destroy()
        await settle()
        ingest.close()


@pytest.mark.parametrize('via', ['sock_data', 'sink'])
async def test_the_marks_end_with_the_session(monkeypatch, via):
    """Outside a profiler session a receive call leaves no mark, and
    the first one after a session drops what the session left — the
    slots' marks, and (``sink``: the call is a reap that fed the
    slots) the stamp of every connection the session's reaps fed."""
    import random

    from test_ingest_route import Peer, ReapRig, settle

    ingest = FleetIngest(bypass_bytes=0, warm='block', placement='host',
                         max_frames=8, min_len=256)
    p, q = (Peer(i, ingest, True, random.Random(i)) for i in range(2))
    rig = ReapRig(sink=True)

    def receive(x, data):
        if via == 'sink':
            rig.reap([(x.conn, data)])
        else:
            x.conn._sock_data(data)
    try:
        xid = p.get()
        p.reply(xid)
        wire, p.wire = bytes(p.wire), bytearray()
        receive(p, wire[:5])
        assert not ingest._rx_marks and p.conn._rx_t0 == 0
        monkeypatch.setattr(trace, '_is_enabled', lambda: True)
        receive(p, wire[5:9])
        assert [m[0] for m in ingest._rx_marks[id(p.conn)]] == [9]
        assert p.conn._rx_t0 > 0
        monkeypatch.setattr(trace, '_is_enabled', lambda: False)
        q.reply(q.get())
        receive(q, q.take())
        assert not ingest._rx_marks
        # a fed connection makes no call of its own that would drop
        # its stamp: the first reap outside the session does
        assert via == 'sock_data' or p.conn._rx_t0 == 0
        receive(p, wire[9:])
        assert p.conn._rx_t0 == 0 and rig.fed == (
            4 if via == 'sink' else 0)
        await settle()
        assert not p.conn.reqs and not q.conn.reqs
        assert ingest.frames_routed == 2
    finally:
        for x in (p, q):
            x.session.close()
            x.conn.destroy()
        await settle()
        ingest.close()


@pytest.mark.parametrize('k,ticks,bound,reticks', [
    (3, 1, 0, 0), (8, 1, 1, 0), (9, 2, 1, 1), (20, 3, 2, 2)])
async def test_a_tick_says_what_it_met_of_the_frame_bound(
        armed, k, ticks, bound, reticks):
    """``k`` equal replies behind one another in one slot,
    ``max_frames`` 8: every device tick's ``ingest.tick`` span carries
    ``bound`` (rows that gave the whole frame bound), ``cut`` (slots
    that held more than they gave) and ``retick`` (it left a follow-up
    for either) — the spans' share of the always-on ``slots_bound`` /
    ``slots_cut`` / ``reticks``, which a collector exports."""
    import random

    from test_ingest_classes import reply_sized
    from test_ingest_route import Peer, settle
    from zkstream_tpu.utils.metrics import Collector

    ingest = FleetIngest(bypass_bytes=0, warm='block', placement='host',
                         max_frames=8, min_len=256)
    col = Collector()
    ingest.bind_metrics(col)
    p = Peer(0, ingest, True, random.Random(k))
    try:
        for _ in range(k):
            reply_sized(p, p.get(), 100)
        p.conn._sock_data(p.take())
        for _ in range(ticks + 2):
            await settle()
        assert not p.conn.reqs
        spans = [s for s in trace.host_ring.spans()
                 if s.op == 'ingest.tick' and s.tick is not None]
        assert len(spans) == ticks == ingest.ticks
        assert sum(s.batch for s in spans) == k
        assert [s.retick for s in spans] == [1] * reticks + [0] * (
            ticks - reticks)
        assert sum(s.bound for s in spans) == bound == ingest.slots_bound
        assert sum(s.cut for s in spans) == ingest.slots_cut <= reticks
        assert ingest.reticks == reticks
        assert all(s.to_dict()['retick'] == s.retick for s in spans)
        text = col.expose()
        for name, val in (('zkstream_ingest_bound_slots', bound),
                          ('zkstream_ingest_cut_slots', ingest.slots_cut),
                          ('zkstream_ingest_reticks', reticks)):
            assert '%s %d' % (name, val) in text
    finally:
        p.session.close()
        p.conn.destroy()
        await settle()
        ingest.close()


async def test_with_no_session_an_op_leaves_nothing(server):
    """No profiler session: an op carries no stamps, the ring and its
    totals stay empty, no collector hook records anything."""
    import gc

    ingest = _ingest()
    c = await _fleet_client(server, ingest)
    try:
        await c.create('/n', b'x')
        for _ in range(3):
            await c.get('/n')
        gc.collect()
        assert all(s.stages is None and s.tick is None
                   and s.t0_ns is None for s in c.trace.spans())
        conn = c.current_connection()
        assert conn._rx_t0 == 0 and conn._tx.stamps == []
        assert len(trace.host_ring) == 0 and not trace.host_ring.totals
        assert trace._gc_open is None
    finally:
        await c.close()


def test_a_collection_is_booked_under_the_span_that_held_it(armed):
    """Inside a session the collector's pauses are ``gc.pause`` totals,
    and ``gc.pause@<name>`` for the innermost host span (accumulated
    ones count) open on the thread when the collection began; the one
    hook is the module's, installed once."""
    import gc

    gc.collect()
    with trace.host_span('ingest.route', tick=1):
        gc.collect()
        with trace.host_span('client.notify', accumulate=True):
            gc.collect()
    gc.collect()                            # under no span
    assert gc.callbacks.count(trace._gc_pause) == 1
    totals = trace.host_ring.totals
    n_route, ns_route = totals['gc.pause@ingest.route']
    n_notify, ns_notify = totals['gc.pause@client.notify']
    assert n_route >= 1 and n_notify >= 1 and ns_route > 0 < ns_notify
    assert totals['gc.pause'][0] >= n_route + n_notify + 1
    assert totals['gc.pause'][1] >= ns_route + ns_notify
    (route,) = trace.host_ring.spans()
    # the span held its pauses: subtracting them leaves its own work
    assert ns_route + ns_notify < route.t1_ns - route.t0_ns
    assert not [k for k in totals if k.startswith('gc.pause@')
                and k.split('@')[1] not in ('ingest.route',
                                            'client.notify')]


def test_the_collector_hook_is_inert_outside_a_session(monkeypatch):
    import gc

    on = [True]
    monkeypatch.setattr(trace, '_is_enabled', lambda: on[0])
    with trace.host_span('a'):
        pass                                # installs the hook
    assert trace._gc_pause in gc.callbacks
    # a session's pause total is there before its first collection
    assert 'gc.pause' in trace.host_ring.totals
    on[0] = False
    before = dict(trace.host_ring.totals)
    gc.collect()
    assert trace.host_ring.totals == before and trace._gc_open is None
    # a collection that began inside a session is closed whatever the
    # session does meanwhile
    on[0] = True
    trace._gc_pause('start', {})
    on[0] = False
    trace._gc_pause('stop', {})
    assert trace._gc_open is None
    assert trace.host_ring.totals['gc.pause'][0] >= 1


async def test_stages_and_pauses_under_a_real_session(server, tmp_path):
    """Under a real profiler session (CPU backend): the stage waits are
    booked by the session alone, and ``client.prepare``,
    ``client.resume`` and ``gc.pause`` are annotations on the loop
    thread's line of ``/host:CPU``, a pause inside the span that held
    it."""
    import gc
    import glob

    import jax
    from jax.profiler import ProfileData

    ingest = _ingest()
    c = await _fleet_client(server, ingest)
    try:
        await c.create('/r', b'v' * 64)
        await c.get('/r')                       # before: nothing
        assert not trace.host_ring.totals
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            c.trace.clear()
            for _ in range(3):
                await c.get('/r')
            with trace.host_span('client.notify', accumulate=True):
                gc.collect()
        finally:
            jax.profiler.stop_trace()
        await c.get('/r')                       # after: nothing more
    finally:
        await c.close()
    _check_stages(c.trace.spans()[:3], 3)
    assert c.trace.spans()[3].t0_ns is None
    totals = trace.host_ring.totals
    assert totals['client.prepare'][0] == totals['client.resume'][0] == 3
    assert totals['gc.pause@client.notify'][0] >= 1
    (path,) = glob.glob(str(tmp_path / '**' / '*.xplane.pb'),
                        recursive=True)
    names = ('client.prepare', 'client.resume', 'gc.pause',
             'client.notify')
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name == '/host:CPU'
              for line in plane.lines for e in line.events
              if e.name in names]
    assert {n for n, *_ in events} == set(names)
    assert sum(n == 'client.prepare' for n, *_ in events) == 3
    assert sum(n == 'client.resume' for n, *_ in events) == 3
    (held,) = [e for e in events if e[0] == 'client.notify']
    assert any(held[1] <= a <= b <= held[2]
               for n, a, b in events if n == 'gc.pause')


# -- the loop's whole turn: named spans, named gaps, idle ----------------

APP_GAP = 'loop.gap@client.resume>client.prepare'


def _a_loops_thread() -> None:
    """What the select hook does in a session: this thread is a
    loop's from its first ``loop.idle`` on."""
    with trace.loop_idle():
        pass


def _gaps() -> dict:
    return {k: v for k, v in trace.host_ring.totals.items()
            if k.startswith('loop.gap@')}


def test_a_gap_is_booked_under_the_pair_that_bounds_it(armed):
    """Top-level span to top-level span on a loop's thread: one count
    under ``loop.gap@<previous>><next>``, from the two spans' own
    stamps; a nested span opens and closes none."""
    _a_loops_thread()
    with trace.host_span('ingest.tick', tick=1):
        with trace.host_span('ingest.batch', tick=1):
            pass
        with trace.host_span('client.rx', accumulate=True):
            pass
    with trace.host_span('client.flush', accumulate=True):
        pass
    with trace.host_span('ingest.tick', tick=2) as sp:
        sp.cancel()             # no span in the ring, a boundary still
    with trace.host_span('client.flush', accumulate=True):
        pass
    gaps = _gaps()
    assert {k: v[0] for k, v in gaps.items()} == {
        'loop.gap@loop.idle>ingest.tick': 1,
        'loop.gap@ingest.tick>client.flush': 2,
        'loop.gap@client.flush>ingest.tick': 1}
    assert all(ns > 0 for _n, ns in gaps.values())
    (batch, tick) = trace.host_ring.spans()
    assert trace.host_ring.totals['loop.named'][0] == 5
    assert trace.host_ring.totals['loop.named'][1] >= tick.t1_ns - tick.t0_ns


def test_named_gaps_and_pauses_sum_to_the_threads_time(armed):
    """``loop.named`` + every gap + ``gc.pause@loop.gap`` is the time
    from the first top-level span's start to the last one's end, to
    the nanosecond."""
    import gc

    with trace.host_span('first'):
        pass                    # not a loop's thread yet: not booked
    _a_loops_thread()
    with trace.host_span('a'):
        with trace.host_span('client.rx', accumulate=True):
            gc.collect()        # inside a span: that span's
    gc.collect()                # in a gap
    for tick in range(3):
        with trace.host_span('ingest.tick', tick=tick) as sp:
            if tick == 1:
                sp.cancel()
        with trace.host_span('client.submit', accumulate=True):
            pass
    with trace.host_span('z'):
        pass
    totals = trace.host_ring.totals
    idle_ns = totals['loop.idle'][1]
    a, *_rest, z = [s for s in trace.host_ring.spans() if s.parent is None
                    and s.op != 'first']
    assert (a.op, z.op) == ('a', 'z')
    booked = (totals['loop.named'][1] + totals['gc.pause@loop.gap'][1]
              + sum(ns for _n, ns in _gaps().values()))
    # less what lies before ``a``: the idle span and the gap after it
    assert booked - idle_ns - totals['loop.gap@loop.idle>a'][1] == (
        z.t1_ns - a.t0_ns)
    assert totals['loop.named'][0] == 9


def test_a_thread_without_the_hook_books_no_gap(armed):
    """A span opened on a thread whose loop has no idle hook (an
    executor's ``prewarm``) books neither a gap nor ``loop.named`` —
    here, or on a loop's thread meanwhile."""
    import threading

    def work():
        for _ in range(3):
            with trace.host_span('ingest.tick', tick=0):
                pass
    work()
    assert not _gaps() and 'loop.named' not in trace.host_ring.totals
    _a_loops_thread()
    with trace.host_span('a', accumulate=True):
        pass
    t = threading.Thread(target=work)
    t.start()
    t.join()
    with trace.host_span('b', accumulate=True):
        pass
    assert {k: v[0] for k, v in _gaps().items()} == {
        'loop.gap@loop.idle>a': 1, 'loop.gap@a>b': 1}
    assert trace.host_ring.totals['loop.named'][0] == 3


def test_two_empty_spans_back_to_back_book_the_instruments_floor(armed):
    """The calibration the chip run repeats: the gap between two empty
    top-level spans opened back to back holds nothing but the closing
    annotation's exit, the next ``host_span()`` call and the opening
    annotation's enter."""
    _a_loops_thread()
    n = 2000
    for _ in range(n):
        with trace.host_span('floor.a', accumulate=True):
            pass
        with trace.host_span('floor.b', accumulate=True):
            pass
    count, total_ns = trace.host_ring.totals['loop.gap@floor.a>floor.b']
    assert count == n
    assert trace.host_ring.totals['loop.gap@floor.b>floor.a'][0] == n - 1
    # a floor: positive, and far under what a gap with work in it
    # holds (un-armed annotations here: a fraction of the chip's
    # figure, PERF.md section 5)
    assert 0 < total_ns / count < 20_000


def test_a_collection_in_a_gap_is_taken_out_of_it(armed):
    """A collection that begins under no span on a loop's thread is
    ``gc.pause@loop.gap``, and the gap it fell in is booked without
    it."""
    import gc
    import time

    junk = [[i] for i in range(200_000)]        # a pause worth timing
    gc.collect()
    gc.disable()                # none but the one asked for
    try:
        _a_loops_thread()
        with trace.host_span('a'):
            pass
        t0 = time.perf_counter_ns()
        gc.collect()
        t1 = time.perf_counter_ns()
        with trace.host_span('b'):
            pass
    finally:
        gc.enable()
    del junk
    a, b = trace.host_ring.spans()
    n, pause_ns = trace.host_ring.totals['gc.pause@loop.gap']
    gap = trace.host_ring.totals['loop.gap@a>b']
    assert n == 1 and gap[0] == 1
    assert 0 < pause_ns <= t1 - t0
    assert gap[1] + pause_ns == b.t0_ns - a.t1_ns
    assert trace.host_ring.totals['gc.pause'][1] >= pause_ns
    # off a loop's thread a pause under no span is ``gc.pause`` alone
    _no_loops_thread()
    gc.collect()
    assert trace.host_ring.totals['gc.pause@loop.gap'][0] == 1


async def test_outside_a_session_the_loop_keeps_two_integers(server):
    """No profiler session: the hook counts the loop's turns and its
    time in ``select`` (the client's ``zkstream_loop_*`` series) and
    books nothing — the ring's totals stay empty."""
    from zkstream_tpu.utils.aio import deadline_queue

    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000)
    c.start()
    queue = deadline_queue(asyncio.get_running_loop())
    assert c._deadlines is queue
    turns, idle_ns = queue.turns, queue.idle_ns
    try:
        await c.wait_connected(timeout=5)
        await c.create('/n', b'x')
        await asyncio.sleep(0.05)
        for _ in range(3):
            await c.get('/n')
        assert queue.turns >= turns + 4
        assert queue.idle_ns >= idle_ns + 40_000_000    # the sleep
        assert not trace.host_ring.totals and len(trace.host_ring) == 0
        assert trace._turn()[trace.L_SESSION] is None
        rows = dict(line.rsplit(' ', 1) for line in
                    c.collector.expose().splitlines()
                    if line.startswith('zkstream_loop_'))
        assert float(rows['zkstream_loop_idle_ms_total']) >= 40.0
        assert int(rows['zkstream_loop_turns_total']) >= turns + 4
    finally:
        await c.close()


def test_a_new_session_forgets_the_last_ones_mark(monkeypatch):
    """``_begin_session()``: the first top-level span of a session has
    no predecessor, whatever the thread closed in the session
    before."""
    on = [True]
    monkeypatch.setattr(trace, '_is_enabled', lambda: on[0])
    _a_loops_thread()
    with trace.host_span('a', accumulate=True):
        pass
    assert 'loop.gap@loop.idle>a' in trace.host_ring.totals
    on[0] = False
    assert trace.host_span('a') is trace.NO_SPAN
    on[0] = True
    with trace.host_span('b', accumulate=True):
        pass                                    # the new session's first
    assert not _gaps()
    assert trace.host_ring.totals['loop.named'][0] == 1
    with trace.host_span('c', accumulate=True):
        pass
    assert {k: v[0] for k, v in _gaps().items()} == {'loop.gap@b>c': 1}


async def test_the_idle_hook_nests_under_a_wrapper_put_on_later(armed):
    """The harness's pattern (``_time_select``): a second wrapper put
    around ``loop._selector.select`` after the program's and taken off
    first finds the program's underneath, and leaves it in place;
    inside a session each turn is one ``loop.idle``, a top-level span
    like any other."""
    from zkstream_tpu.utils.aio import deadline_queue

    loop = asyncio.get_running_loop()
    queue = deadline_queue(loop)
    selector = loop._selector
    hooked = selector.select
    assert deadline_queue(loop) is queue and selector.select is hooked
    outer = []

    def timed(timeout=None):
        outer.append(timeout)
        return hooked(timeout)
    selector.select = timed
    try:
        turns = queue.turns
        with trace.host_span('client.flush', accumulate=True):
            pass
        await asyncio.sleep(0.02)
        assert len(outer) >= 1 and queue.turns == turns + len(outer)
    finally:
        selector.select = hooked
    await asyncio.sleep(0.01)
    assert queue.turns > turns + len(outer)     # still on
    totals = trace.host_ring.totals
    assert totals['loop.idle'][0] >= 2
    assert totals['loop.idle'][1] >= 25_000_000
    assert any(k.startswith('loop.gap@loop.idle>') for k in totals)
    assert any(k.endswith('>loop.idle') for k in _gaps())


def test_a_loop_without_a_selector_gets_no_hook(armed):
    """No ``_selector`` (a proactor loop, uvloop): no wrapper, both
    integers stay 0, and the loop's thread books no gap."""
    from zkstream_tpu.utils.aio import DeadlineQueue

    class Loop:
        pass

    loop = Loop()
    queue = DeadlineQueue(loop)
    assert (queue.turns, queue.idle_ns) == (0, 0)
    assert not hasattr(loop, '_selector')
    for _ in range(2):
        with trace.host_span('a', accumulate=True):
            pass
    assert not _gaps() and 'loop.named' not in trace.host_ring.totals


async def test_a_closed_loop_caller_books_one_app_gap_an_op(server, armed):
    """``client.resume`` -> ``client.prepare``: a caller that sends
    its next request when the last one's reply wakes it leaves exactly
    one ``loop.gap@client.resume>client.prepare`` an op after the
    first — its own code between the two."""
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000)
    c.start()
    try:
        await c.wait_connected(timeout=5)
        await c.create('/r', b'v' * 64)
        await asyncio.sleep(0.01)
        trace.host_ring.reset()
        trace._recording = False                # a session of its own
        for _ in range(6):
            await c.get('/r')
        totals = dict(trace.host_ring.totals)
    finally:
        await c.close()
    assert totals['client.prepare'][0] == totals['client.resume'][0] == 6
    count, total_ns = totals[APP_GAP]
    assert count == 5 and total_ns > 0
    # every other gap an op opens is the loop's own: its request left
    # (``client.submit``) and nothing of the caller's ran until the
    # reply woke it
    assert totals['loop.gap@client.prepare>client.submit'][0] >= 5
    assert not [k for k in totals
                if k.startswith('loop.gap@client.submit>client.prepare')]


async def test_mntr_has_the_loop_threads_cpu(server):
    """``zk_loop_cpu_ms``: cumulative, the scraping thread's, so no
    more than the process's (``zk_process_cpu_ms``) beside it."""
    before = await mntr_rows(server.port)
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000)
    c.start()
    try:
        await c.wait_connected(timeout=5)
        for i in range(200):
            await c.create('/n%d' % i, b'x' * 128)
    finally:
        await c.close()
    after = await mntr_rows(server.port)
    keys = list(after)
    assert keys.index('zk_loop_cpu_ms') == keys.index(
        'zk_process_cpu_ms') + 1
    loop_ms = [float(r['zk_loop_cpu_ms']) for r in (before, after)]
    cpu_ms = [float(r['zk_process_cpu_ms']) for r in (before, after)]
    assert 0 < loop_ms[0] < loop_ms[1]
    assert all(lp <= cpu for lp, cpu in zip(loop_ms, cpu_ms))
    assert loop_ms[1] - loop_ms[0] <= cpu_ms[1] - cpu_ms[0] + 1.0


# -- the members: ledger phases, always-on histograms, mntr rows ---------

def test_tick_ledger_subtracts_a_nested_forward_rpc(monkeypatch):
    """A follower parked in the forwarded RPC is ``forward_rpc`` time,
    not ``decode_apply`` time."""
    import types

    from zkstream_tpu.utils import metrics

    assert 'forward_rpc' in TickLedger.PHASES
    assert TICK_BUCKETS[-3:] == (100.0, 250.0, 1000.0)
    # the ledger's clock, scripted: decode_apply opens at 0 ms, the
    # RPC parks the loop from 1 to 5 ms, decode_apply closes at 5.25
    clock = iter([0.0, 0.001, 0.005, 0.00525])
    monkeypatch.setattr(metrics, 'time', types.SimpleNamespace(
        perf_counter=lambda: next(clock)))
    led = TickLedger()
    led.enter('decode_apply')
    led.enter('forward_rpc')
    led.exit()
    led.exit()
    led.close_tick()
    phases = led.last_tick['phases']
    assert phases['forward_rpc'] == pytest.approx(4.0)
    assert phases['decode_apply'] == pytest.approx(1.25)
    assert led.last_tick['total_ms'] == pytest.approx(5.25)
    rows = dict(led.phase_hist.rows())
    assert rows['zk_tick_phase_ms_count{phase="forward_rpc"}'] == 1
    assert rows['zk_tick_phase_ms_sum{phase="forward_rpc"}'] == \
        phases['forward_rpc']
    assert rows['zk_tick_phase_ms_bucket{phase="forward_rpc",le="2.5"}'] == 0
    assert rows['zk_tick_phase_ms_bucket{phase="forward_rpc",le="+Inf"}'] == 1


def test_histogram_rows_are_what_expose_prints():
    h = Histogram('zk_x_ms', 'help', buckets=(1.0, 10.0))
    for v, labels in ((0.5, None), (5.0, None), (50.0, None),
                      (2.0, {'phase': 'a'})):
        h.observe(v, labels)
    rows = h.rows()
    assert rows[:5] == [('zk_x_ms_bucket{le="1"}', 1),
                        ('zk_x_ms_bucket{le="10"}', 2),
                        ('zk_x_ms_bucket{le="+Inf"}', 3),
                        ('zk_x_ms_sum', 55.5), ('zk_x_ms_count', 3)]
    assert ('zk_x_ms_bucket{phase="a",le="10"}', 1) in rows
    assert h.expose().splitlines()[2:] == ['%s %s' % kv for kv in rows]


def _window(before: dict, after: dict, name: str, labels: str = '') -> list:
    """``name``'s rows over a window: after minus before (a series the
    member had not yet opened reads 0 before)."""
    lead = labels[:-1] + ',' if labels else '{'
    keys = [k for k in after
            if k in (name + '_sum' + labels, name + '_count' + labels)
            or k.startswith(name + '_bucket' + lead)]
    return [(k, float(after[k]) - float(before.get(k, 0))) for k in keys]


async def test_mntr_histograms_with_no_collector_and_window_delta():
    """A quorum-enabled member built with NO collector exports, for
    every ledger phase, the busy tick, the quorum ack and the fan-out
    flush, cumulative ``_bucket`` / ``_sum`` / ``_count`` rows; after
    minus before is exactly the histogram of the window's own
    observations (so a percentile over the window is
    ``Histogram.percentile`` on them)."""
    from zkstream_tpu.server import ZKEnsemble
    from zkstream_tpu.server.replication import QUORUM_ACK_BUCKETS

    ens = await ZKEnsemble(3).start()
    gate = ens.quorum
    assert gate.enabled and ens.servers[0].collector is None
    port = ens.addresses()[0][1]
    c = Client(address='127.0.0.1', port=port, session_timeout=5000)
    w = Client(address='127.0.0.1', port=port, session_timeout=5000)
    c.start()
    w.start()
    try:
        await c.wait_connected(timeout=5)
        await w.wait_connected(timeout=5)
        await c.create('/q', b'0')
        seen = []
        # another session's watch: its notifications leave through the
        # fan-out shards (the mutator's own ride its reply)
        w.watcher('/q').on('dataChanged', lambda d, s: seen.append(d))
        await wait_until(lambda: seen)
        for i in range(5):
            await c.set('/q', b'a%d' % i)   # before the window
        await wait_until(lambda: len(seen) >= 2)
        before = await mntr_rows(port)

        window = Histogram('zk_quorum_ack_ms', buckets=QUORUM_ACK_BUCKETS)
        observe = gate.ack_hist.observe

        def both(value, labels=None):
            observe(value, labels)
            window.observe(value, labels)
        gate.ack_hist.observe = both
        try:
            for i in range(20):
                await c.set('/q', b'b%d' % i)
        finally:
            del gate.ack_hist.observe
        after = await mntr_rows(port)
    finally:
        await c.close()
        await w.close()
        await ens.stop()

    assert window.count() == 20
    got = _window(before, after, 'zk_quorum_ack_ms')
    want = window.rows()
    assert [k for k, _ in got] == [k for k, _ in want]
    assert [v for _, v in got] == pytest.approx([v for _, v in want])
    assert len(got) == len(QUORUM_ACK_BUCKETS) + 3
    assert float(before['zk_quorum_ack_ms_count']) >= 5
    # the same shape for the ledger's phases, the tick and the fan-out
    for name, labels in (
            ('zk_tick_phase_ms', '{phase="decode_apply"}'),
            ('zk_tick_phase_ms', '{phase="cork_flush"}'),
            ('zk_tick_ms', ''),
            ('zk_fanout_tick_ms', '{plane="fanout"}')):
        rows = dict(_window(before, after, name, labels))
        count = rows[name + '_count' + labels]
        assert count > 0 and rows[name + '_sum' + labels] > 0, name
        inf = name + '_bucket' + (labels[:-1] + ',le="+Inf"}'
                                  if labels else '{le="+Inf"}')
        assert rows[inf] == count
        cum = [v for k, v in rows.items() if '_bucket' in k]
        assert cum == sorted(cum)
    # the since-start row the older readers name is still there
    assert 'zk_tick_phase_ms_p99{phase="decode_apply"}' in after
    # and the flight recorder's frames keep the counters only
    assert not [k for k, _ in ens.servers[0].monitor_stats(histograms=False)
                if '_bucket' in k]
