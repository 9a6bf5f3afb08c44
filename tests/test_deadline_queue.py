"""The per-loop deadline queue (utils/aio.DeadlineQueue) and the
request future the connection settles itself (io/connection.ZKRequest):
one loop timer for every pending op, never early, nothing held after a
wait ends; late replies, cancellation, teardown and THROTTLED keep
their contracts."""

import asyncio
import gc
import time
import weakref

import pytest

from zkstream_tpu import Client
from zkstream_tpu.io.backoff import BackoffPolicy
from zkstream_tpu.io.overload import OverloadPlane
from zkstream_tpu.protocol.errors import (
    ZKDeadlineError,
    ZKProtocolError,
    ZKThrottledError,
)
from zkstream_tpu.server import ZKServer
from zkstream_tpu.utils.aio import (
    DeadlineExpired,
    DeadlineQueue,
    deadline_queue,
)

#: What a deadline may be late by (README "Deadlines"): the loop
#: iteration its timer fires in.  Generous for a loaded test machine.
SLACK_S = 0.25


class CountingLoop:
    """Counts the timers the queue arms on the test's loop."""

    def __init__(self, loop):
        self.loop, self.armed = loop, 0

    def __getattr__(self, name):
        return getattr(self.loop, name)

    def call_at(self, when, cb, *args):
        self.armed += 1
        return self.loop.call_at(when, cb, *args)


async def connected(server, **kw) -> Client:
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000, max_spares=0, **kw)
    c.start()
    await c.wait_connected(timeout=5)
    return c


def last_span(c: Client, op: str) -> dict:
    return [s for s in c.trace.dump() if s['op'] == op][-1]


# -- the queue alone ---------------------------------------------------

def _held(queue: DeadlineQueue) -> list:
    """Every future the queue still refers to."""
    return [f for lane in queue._lanes.values() for f in lane]


async def test_thousand_waits_arm_one_timer_and_hold_nothing():
    loop = CountingLoop(asyncio.get_running_loop())
    queue = DeadlineQueue(loop)
    futs = [loop.create_future() for _ in range(1000)]
    entries = [queue.add(f, 30.0) for f in futs]
    assert loop.armed == 1 and len(queue) == 1000
    refs = [weakref.ref(f) for f in futs]
    for f, e in zip(futs, entries):
        f.set_result({'data': b'x' * 1024})
        queue.discard(e, f)
        queue.discard(e, f)                 # idempotent
    del futs, f
    gc.collect()
    # no future (nor the reply it holds) outlives its wait
    assert [r for r in refs if r() is not None] == []
    assert len(queue) == 0 and _held(queue) == []
    # nothing moved the timer: it stands for a time no later than any
    # live deadline, fires once for nothing and is not armed again
    assert loop.armed == 1
    entries.clear()


async def test_steady_traffic_keeps_the_queue_at_what_is_live():
    loop = CountingLoop(asyncio.get_running_loop())
    queue = DeadlineQueue(loop)
    live = []
    for _ in range(20_000):                 # 64 in flight, 30 s each
        f = loop.create_future()
        live.append((f, queue.add(f, 30.0)))
        if len(live) > 64:
            f0, e0 = live.pop(0)
            f0.set_result(None)
            queue.discard(e0, f0)
        assert len(queue) == len(live) == len(_held(queue))
    # one timer, not one a request nor one a compaction
    assert loop.armed == 1
    for f, e in live:
        f.cancel()
        queue.discard(e, f)


@pytest.mark.parametrize('order', [(50, 5, 120), (5, 50, 120),
                                   (120, 50, 5, 50, 5, 120, 80)])
async def test_mixed_deadlines_fire_in_order_and_never_early(order):
    """A FIFO a distinct timeout, and the timer at the least of their
    heads: whatever the order the waits were added in, they come due
    in deadline order — within one timeout, in the order added."""
    loop = asyncio.get_running_loop()
    queue = DeadlineQueue(loop)
    fired = []
    futs = []
    for n, ms in enumerate(order):
        f = loop.create_future()
        t0 = loop.time()
        f.add_done_callback(
            lambda f, n=n, ms=ms, t0=t0: fired.append(
                (ms, n, loop.time() - t0)))
        queue.add(f, ms / 1000.0)
        futs.append(f)
    unbounded = loop.create_future()        # deadline=None: never added
    await asyncio.sleep(0.3)
    assert [(ms, n) for ms, n, _ in fired] == sorted(
        (ms, n) for n, ms in enumerate(order))
    for ms, _n, at in fired:
        assert ms / 1000.0 <= at < ms / 1000.0 + SLACK_S
    assert all(isinstance(f.exception(), DeadlineExpired) for f in futs)
    assert not unbounded.done()
    # every timeout left the table with its last wait
    assert len(queue) == 0 and queue._timer is None
    assert queue._lanes == {}


async def test_earlier_deadline_moves_the_timer_and_settled_is_skipped():
    loop = asyncio.get_running_loop()
    queue = DeadlineQueue(loop)
    slow, fast = loop.create_future(), loop.create_future()
    queue.add(slow, 30.0)
    e_fast = queue.add(fast, 0.02)          # re-arms: now the head
    assert queue._timer.when() == e_fast[fast] == queue._when
    fast.set_result('in time')              # settled, not yet discarded
    await asyncio.sleep(0.06)
    assert fast.result() == 'in time' and not slow.done()
    assert queue._timer is not None         # moved on to ``slow``
    assert _held(queue) == [slow]
    slow.cancel()


async def test_a_discarded_entry_releases_its_future_at_once():
    """Discarded from the middle of a FIFO whose other waits go on:
    the queue's reference is gone with the call, not at some later
    sweep."""
    loop = asyncio.get_running_loop()
    queue = DeadlineQueue(loop)
    futs = [loop.create_future() for _ in range(5)]
    entries = [queue.add(f, 30.0) for f in futs]
    mid = futs[2]
    ref = weakref.ref(mid)
    mid.set_result({'data': b'x' * 1024})
    queue.discard(entries[2], mid)
    del mid, futs[2]
    gc.collect()
    assert ref() is None
    assert _held(queue) == futs and len(queue) == 4
    for f, e in zip(futs, [entries[i] for i in (0, 1, 3, 4)]):
        f.cancel()
        queue.discard(e, f)


async def test_a_discarded_head_costs_one_early_firing_and_no_more():
    """The wait the timer was armed for is discarded: the timer stays
    where it is (a time no later than any live deadline), fires once
    for nothing, moves to the oldest wait still live — which then
    fires on time — and with nothing live is not armed again."""
    loop = CountingLoop(asyncio.get_running_loop())
    queue = DeadlineQueue(loop)
    head, live = loop.create_future(), loop.create_future()
    e_head = queue.add(head, 0.03)
    t0 = loop.time()
    queue.add(live, 0.09)
    assert loop.armed == 1
    head.set_result(None)
    queue.discard(e_head, head)
    assert loop.armed == 1 and queue._when == pytest.approx(t0 + 0.03,
                                                            abs=0.01)
    await asyncio.sleep(0.05)               # the stale firing
    assert loop.armed == 2 and not live.done()
    assert queue._timer.when() == queue._when >= t0 + 0.09
    with pytest.raises(DeadlineExpired):
        await live
    assert 0.09 <= loop.time() - t0 < 0.09 + SLACK_S
    await asyncio.sleep(0)
    assert loop.armed == 2 and queue._timer is None
    assert queue._when == float('inf') and queue._lanes == {}


async def test_timeouts_nothing_waits_under_are_swept():
    """A caller that hands every op the rest of its own budget makes
    a timeout an op: the table of FIFOs stays near the waits alive."""
    loop = CountingLoop(asyncio.get_running_loop())
    queue = DeadlineQueue(loop)
    for n in range(5000):
        f = loop.create_future()
        e = queue.add(f, 30.0 + n / 1000.0)
        f.set_result(None)
        queue.discard(e, f)
        assert len(queue._lanes) <= 2 * queue.SWEEP_MIN
    assert len(queue) == 0 and loop.armed == 1
    # an entry handed out before its FIFO was swept still discards
    f = loop.create_future()
    e = queue.add(f, 1.0)
    for n in range(3 * queue.SWEEP_MIN):
        queue._new_lane(100.0 + n)
    queue.discard(e, f)
    assert len(queue) == 0


async def test_equal_timeouts_arm_no_more_timers_than_the_heap_did():
    """1,024 waits under one timeout, added and discarded in rounds as
    a fleet's closed loop does: the heap this queue had re-armed at
    every compaction (one a ``COMPACT_MIN`` = 64 discards: 1 + 16
    armings here); the FIFO arms once."""
    loop = CountingLoop(asyncio.get_running_loop())
    queue = DeadlineQueue(loop)
    for _ in range(4):
        futs = [loop.create_future() for _ in range(256)]
        entries = [queue.add(f, 30.0) for f in futs]
        for f, e in zip(futs, entries):
            f.set_result(None)
            queue.discard(e, f)
    assert loop.armed == 1 <= 1 + 1024 // 64
    assert len(queue) == 0


def test_each_loop_has_its_own_queue():
    seen = []

    async def main():
        loop = asyncio.get_running_loop()
        q = deadline_queue(loop)
        assert deadline_queue(loop) is q and q.loop is loop
        seen.append(q)
        f = loop.create_future()
        q.add(f, 0.01)
        with pytest.raises(DeadlineExpired):
            await f
    asyncio.run(main())
    asyncio.run(main())                     # sweeps the closed loop's
    assert seen[0] is not seen[1]


# -- through the client -------------------------------------------------

async def test_deadline_on_a_stalled_server_is_never_early(server):
    c = await connected(server)
    try:
        await c.create('/d', b'x')
        server.drop_replies = True
        t0 = time.monotonic()
        with pytest.raises(ZKDeadlineError) as ei:
            await c.get('/d', deadline=50)
        took = time.monotonic() - t0
        assert 0.050 <= took < 0.050 + SLACK_S
        assert (ei.value.opcode, ei.value.path,
                ei.value.deadline_ms) == ('GET_DATA', '/d', 50)
        span = last_span(c, 'GET_DATA')
        assert (span['status'], span['error']) == \
            ('deadline', 'DEADLINE_EXCEEDED')
        # the wait left the queue
        assert len(deadline_queue(asyncio.get_running_loop())) == 0
        conn = c.current_connection()
        _late_reply(conn, max(conn.reqs))   # or close() waits for it
    finally:
        server.drop_replies = False
        await c.close()


async def test_unbounded_op_arms_nothing(server):
    loop = asyncio.get_running_loop()
    queue = deadline_queue(loop)
    counting = queue.loop = CountingLoop(loop)
    c = await connected(server, op_timeout=None)
    try:
        await c.create('/u', b'x')
        assert (await c.get('/u'))[0] == b'x'
        assert (await c.get('/u', deadline=None))[0] == b'x'
        assert counting.armed == 0 and len(queue) == 0
        assert (await c.get('/u', deadline=1000))[0] == b'x'
        assert counting.armed == 1
    finally:
        queue.loop = loop
        await c.close()


async def test_thousand_ops_on_one_loop_share_the_loop_timer(server):
    loop = asyncio.get_running_loop()
    queue = deadline_queue(loop)
    clients = [await connected(server) for _ in range(4)]
    counting = queue.loop = CountingLoop(loop)
    try:
        await clients[0].create('/k', b'v' * 1024)
        for _ in range(5):
            got = await asyncio.gather(*[
                c.get('/k', deadline=30000)
                for c in clients for _ in range(50)])
            assert all(data == b'v' * 1024 for data, _stat in got)
        # 1,000 requests with deadlines: one timer
        assert counting.armed == 1
        assert len(queue) == 0
        gc.collect()
        assert _held(queue) == []
        assert all(not c.current_connection().reqs for c in clients)
    finally:
        queue.loop = loop
        await asyncio.gather(*[c.close() for c in clients])


async def _pending_get(c: Client, path: str, deadline):
    """Start one ``getData`` whose reply the server withholds: the
    awaiting task, its connection and its xid."""
    conn = c.current_connection()
    before = set(conn.reqs)
    task = asyncio.ensure_future(c.get(path, deadline=deadline))
    await asyncio.sleep(0.02)
    (xid,) = set(conn.reqs) - before
    return task, conn, xid


def _late_reply(conn, xid: int) -> None:
    conn.process_reply({'xid': xid, 'zxid': 1, 'err': 'OK',
                        'opcode': 'GET_DATA', 'data': b'late',
                        'stat': None})


async def test_reply_after_the_deadline_is_dropped(server):
    c = await connected(server)
    try:
        await c.create('/l', b'x')
        server.drop_replies = True
        task, conn, xid = await _pending_get(c, '/l', 40)
        with pytest.raises(ZKDeadlineError):
            await task
        # the request outlives its caller until a reply or teardown
        assert xid in conn.reqs
        _late_reply(conn, xid)              # no InvalidStateError
        assert not conn.reqs
        assert last_span(c, 'GET_DATA')['status'] == 'deadline'
    finally:
        server.drop_replies = False
        await c.close()


async def test_reply_after_the_caller_was_cancelled_is_dropped(server):
    c = await connected(server)
    try:
        await c.create('/c', b'x')
        server.drop_replies = True
        task, conn, xid = await _pending_get(c, '/c', 30000)
        fut = conn.reqs[xid].fut
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        # the cancellation took the op's own future with it and left
        # the queue; the connection still holds the request
        assert fut.cancelled()
        assert len(deadline_queue(asyncio.get_running_loop())) == 0
        assert xid in conn.reqs
        _late_reply(conn, xid)
        assert not conn.reqs
        server.drop_replies = False
        assert (await c.get('/c'))[0] == b'x'
    finally:
        server.drop_replies = False
        await c.close()


@pytest.mark.parametrize('how,status', [('abort', 'error'),
                                        ('destroy', 'abandoned')])
async def test_connection_loss_settles_every_pending_future(
        server, how, status):
    c = await connected(server)
    try:
        await c.create('/p', b'x')
        server.drop_replies = True
        conn = c.current_connection()
        tasks = [asyncio.ensure_future(c.get('/p', deadline=30000))
                 for _ in range(8)]
        await asyncio.sleep(0.02)
        assert len(conn.reqs) == 8
        if how == 'abort':
            conn.transport.abort()          # sockClose -> state_error
        else:
            conn.destroy()                  # state_closed's stragglers
        done = await asyncio.gather(*tasks, return_exceptions=True)
        assert [type(e) for e in done] == [ZKProtocolError] * 8
        assert {e.code for e in done} == {'CONNECTION_LOSS'}
        assert not conn.reqs
        assert len(deadline_queue(asyncio.get_running_loop())) == 0
        spans = [s for s in c.trace.dump() if s['op'] == 'GET_DATA'][-8:]
        assert {(s['status'], s['error']) for s in spans} == \
            {(status, 'CONNECTION_LOSS')}
    finally:
        server.drop_replies = False
        await c.close()


async def test_throttled_reaches_the_write_retry_typed():
    srv = await ZKServer().start()
    c = await connected(srv, default_policy=BackoffPolicy(
        timeout=500, retries=3, delay=20, cap=120))
    orig = OverloadPlane.write_throttled
    bounces = []

    def throttled(self):
        bounces.append(1)
        return len(bounces) <= 2            # two bounces, then lands
    try:
        await c.create('/t', b'v')
        OverloadPlane.write_throttled = throttled
        await c.set('/t', b'after', deadline=5000)
        assert len(bounces) == 3
        OverloadPlane.write_throttled = lambda self: True
        with pytest.raises(ZKThrottledError):
            await c._primary_request(
                {'opcode': 'SET_DATA', 'path': '/t', 'data': b'no',
                 'version': -1}, 'SET_DATA', '/t', 5000)
        assert last_span(c, 'SET_DATA')['error'] == 'THROTTLED'
        assert (await c.get('/t'))[0] == b'after'
    finally:
        OverloadPlane.write_throttled = orig
        await c.close()
        await srv.stop()


def test_client_across_two_loops_gets_deadlines_on_both():
    """One ``asyncio.run`` after another with the same client: on the
    second loop its socket's reader is gone, so only a deadline ends
    the op — and a deadline left with the first loop's queue (whose
    timer died with that loop) would never fire."""
    import threading
    srv_loop = asyncio.new_event_loop()
    srv = srv_loop.run_until_complete(ZKServer().start())
    thread = threading.Thread(target=srv_loop.run_forever, daemon=True)
    thread.start()
    box: dict = {}

    async def overdue(c, ms):
        t0 = time.monotonic()
        with pytest.raises(ZKDeadlineError):
            await c.get('/x', deadline=ms)
        assert ms / 1000.0 <= time.monotonic() - t0 < ms / 1000.0 + SLACK_S

    async def first():
        c = box['c'] = Client(address='127.0.0.1', port=srv.port,
                              session_timeout=30000, max_spares=0)
        c.start()
        await c.wait_connected(timeout=10)
        await c.create('/x', b'1', deadline=5000)
        srv.drop_replies = True
        await overdue(c, 40)
        srv.drop_replies = False
        box['queue'] = deadline_queue(asyncio.get_running_loop())
        # an op still waiting when the loop ends: its timer dies here
        box['left'] = asyncio.ensure_future(c.get('/x', deadline=60000))
        await asyncio.sleep(0)

    async def second():
        c = box['c']
        assert box['queue'].loop.is_closed()
        await overdue(c, 40)
        assert deadline_queue(asyncio.get_running_loop()) \
            is not box['queue']
        c._tier_lease.release()

    try:
        asyncio.run(first())
        asyncio.run(second())
    finally:
        asyncio.run_coroutine_threadsafe(srv.stop(), srv_loop).result(10)
        srv_loop.call_soon_threadsafe(srv_loop.stop)
        thread.join(10)
        assert not thread.is_alive()
        srv_loop.close()
