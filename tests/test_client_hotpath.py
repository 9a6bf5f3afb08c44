"""One client request's own bookkeeping (ISSUE 48): the pass from an
API call to the awaited future, the slotted span started with one clock
read, the histogram's bound series, the request object that makes no
listener table.  Each of them is the same work for less Python, so
what they leave behind must be what the code before them left: the
ring's contents on every completion path, ``on_op``'s calls, the
histogram's rows and text, a request's settle and fail."""

from __future__ import annotations

import ast
import asyncio
import pathlib
import time

import pytest

from helpers import wait_until
from zkstream_tpu import Client
from zkstream_tpu.io import connection as connection_mod
from zkstream_tpu.io.connection import ZKRequest
from zkstream_tpu.protocol.errors import (
    ZKDeadlineError,
    ZKError,
    ZKProtocolError,
    ZKThrottledError,
)
from zkstream_tpu.utils import trace
from zkstream_tpu.utils.aio import deadline_queue
from zkstream_tpu.utils.metrics import (
    DEFAULT_BUCKETS,
    BoundSeries,
    Collector,
    Histogram,
)
from zkstream_tpu.utils.trace import Span, TraceRing

PKG = pathlib.Path(trace.__file__).resolve().parents[1]


async def connected(server, **kw) -> Client:
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000, max_spares=0, **kw)
    c.start()
    await c.wait_connected(timeout=5)
    return c


def tap(c: Client) -> list:
    """``on_op``'s calls, in order."""
    seen: list = []
    c.on_op = seen.append
    return seen


def _late_reply(conn, xid: int) -> None:
    conn.process_reply({'xid': xid, 'zxid': 1, 'err': 'OK',
                        'opcode': 'GET_DATA', 'data': b'late',
                        'stat': None})


# -- the ring and on_op, path by path -----------------------------------

async def _ok(c, server):
    t_wall = time.time()
    data, _stat = await c.get('/k')
    assert data == b'v'
    return t_wall, {'status': 'ok', 'wire': True, 'zxid': True}


async def _error_reply(c, server):
    t_wall = time.time()
    with pytest.raises(ZKError) as ei:
        await c.get('/missing')
    assert ei.value.code == 'NO_NODE'
    return t_wall, {'status': 'error', 'error': 'NO_NODE', 'wire': True,
                    'zxid': True, 'path': '/missing'}


async def _deadline(c, server):
    server.drop_replies = True
    t_wall = time.time()
    with pytest.raises(ZKDeadlineError):
        await c.get('/k', deadline=30)
    server.drop_replies = False
    conn = c.current_connection()
    _late_reply(conn, max(conn.reqs))       # or close() waits for it
    return t_wall, {'status': 'deadline', 'error': 'DEADLINE_EXCEEDED',
                    'wire': True, 'zxid': False}


async def _abandoned(c, server):
    # the connection dies between the liveness check and the send: the
    # request never reaches the pending table
    conn = c.current_connection()

    def request(pkt, span=None):
        raise ZKProtocolError('CONNECTION_LOSS', 'gone')
    conn.request = request
    t_wall = time.time()
    try:
        with pytest.raises(ZKProtocolError):
            await c.get('/k')
    finally:
        del conn.request
    # never pending, so never awaited: no latency sample, no on_op —
    # as before the one pass
    return t_wall, {'status': 'abandoned', 'error': 'CONNECTION_LOSS',
                    'wire': False, 'zxid': False, 'on_op': 0}


async def _abandoned_untyped(c, server):
    conn = c.current_connection()

    def request(pkt, span=None):
        raise RuntimeError('no code on this one')
    conn.request = request
    t_wall = time.time()
    try:
        with pytest.raises(RuntimeError):
            await c.get('/k')
    finally:
        del conn.request
    return t_wall, {'status': 'abandoned', 'error': 'RuntimeError',
                    'wire': False, 'zxid': False, 'on_op': 0}


async def _cached(c, server):
    await wait_until(lambda: c.cache.stats()['armed'] == 1)
    assert (await c.get('/k'))[0] == b'v'   # fills
    c.trace.clear()
    c.on_op.__self__.clear()
    t_wall = time.time()
    assert (await c.get('/k'))[0] == b'v'   # served locally
    return t_wall, {'status': 'ok', 'wire': False, 'zxid': True,
                    'detail': 'cached'}


PATHS = {'ok': (_ok, {}), 'error-reply': (_error_reply, {}),
         'deadline': (_deadline, {}), 'abandoned': (_abandoned, {}),
         'abandoned-untyped': (_abandoned_untyped, {}),
         'cached': (_cached, {'cache': '/'})}


@pytest.mark.parametrize('how', list(PATHS))
async def test_an_op_leaves_the_ring_what_it_always_did(server, how):
    drive, kw = PATHS[how]
    c = await connected(server, **kw)
    try:
        await c.create('/k', b'v')
        conn = c.current_connection()
        c.trace.clear()
        seen = tap(c)
        xid_before = conn._xid
        t_wall, want = await drive(c, server)
        t_done = time.time()
        (span,) = [s for s in c.trace.spans() if s.kind == 'op']
        d = span.to_dict()
        assert (d['op'], d['path']) == ('GET_DATA',
                                        want.get('path', '/k'))
        assert d['status'] == want['status']
        assert d.get('error') == want.get('error')
        assert d.get('detail') == want.get('detail')
        if want['wire']:
            assert d['xid'] == xid_before + 1 == conn._xid
            assert d['backend'] == '127.0.0.1:%d' % (server.port,)
            assert d['session_id'] == '%016x' % (c.session.session_id,)
        else:
            assert 'xid' not in d and 'backend' not in d \
                and 'session_id' not in d
        assert ('zxid' in d) == want['zxid']
        if want['zxid']:
            assert d['zxid'] > 0
        # started on ONE clock, and still on the wall's to the ms
        assert t_wall - 0.001 <= d['t_wall'] <= t_done + 0.001
        assert d['t_wall'] == round(span.t_wall, 6)
        assert 0.0 <= d['duration_ms'] <= (t_done - t_wall) * 1000 + 1
        # the keys, in the order a dump has always had them
        order = ['span', 'kind', 'op', 'status', 't_wall', 'path', 'xid',
                 'zxid', 'backend', 'session_id', 'detail', 'error',
                 'duration_ms']
        assert list(d) == [k for k in order if k in d]
        # once an op, with the op's own (settled) span
        assert seen == [span] * want.get('on_op', 1)
        assert c.trace.open_spans() == []
    finally:
        server.drop_replies = False
        await c.close()
    assert c.trace.open_spans() == []


@pytest.mark.parametrize('how,status', [('abort', 'error'),
                                        ('destroy', 'abandoned')])
async def test_teardown_leaves_no_span_open_and_tells_on_op_once(
        server, how, status):
    c = await connected(server)
    try:
        await c.create('/p', b'x')
        server.drop_replies = True
        conn = c.current_connection()
        c.trace.clear()
        seen = tap(c)
        tasks = [asyncio.ensure_future(c.get('/p')) for _ in range(8)]
        await asyncio.sleep(0.02)
        assert len(c.trace.open_spans()) == len(conn.reqs) == 8
        if how == 'abort':
            conn.transport.abort()
        else:
            conn.destroy()
        done = await asyncio.gather(*tasks, return_exceptions=True)
        assert {getattr(e, 'code', e) for e in done} == {'CONNECTION_LOSS'}
        assert c.trace.open_spans() == []
        ops = [s for s in c.trace.spans() if s.op == 'GET_DATA']
        assert [(s.status, s.error) for s in ops] == \
            [(status, 'CONNECTION_LOSS')] * 8
        assert sorted(s.span_id for s in seen) == \
            [s.span_id for s in ops]
        assert len(deadline_queue(asyncio.get_running_loop())) == 0
    finally:
        server.drop_replies = False
        await c.close()


# -- the pass itself ----------------------------------------------------

def _await_chain(task) -> list:
    """The coroutine frames between a task and the future it waits
    on, outermost first."""
    names, coro = [], task.get_coro()
    while hasattr(coro, 'cr_code'):
        names.append(coro.cr_code.co_name)
        coro = coro.cr_await
    assert type(coro).__name__ == 'FutureIter'
    return names


CALLS = {
    'get': (lambda c: c.get('/k'), ['get', '_await_op']),
    'list': (lambda c: c.list('/k'), ['list', '_await_op']),
    'stat': (lambda c: c.stat('/k'), ['stat', '_await_op']),
    'get_acl': (lambda c: c.get_acl('/k'), ['get_acl', '_await_op']),
    'sync': (lambda c: c.sync('/k'), ['sync', '_await_op']),
    # a write keeps its THROTTLED retry loop around the same frame
    'set': (lambda c: c.set('/k', b'w'), ['set', '_write_op',
                                          '_await_op']),
    'delete': (lambda c: c.delete('/k', -1), ['delete', '_write_op',
                                              '_await_op']),
}


@pytest.mark.parametrize('call', list(CALLS))
async def test_a_plain_request_is_one_frame_from_its_future(server, call):
    make, want = CALLS[call]
    c = await connected(server)
    try:
        await c.create('/k', b'v')
        server.drop_replies = True
        conn = c.current_connection()
        task = asyncio.ensure_future(make(c))
        await asyncio.sleep(0.02)
        assert _await_chain(task) == want
        # the future the frame awaits is the request's own
        (req,) = [r for x, r in conn.reqs.items() if x > 0]
        assert task._fut_waiter is req.fut
        server.drop_replies = False
        conn.transport.abort()
        with pytest.raises(ZKProtocolError):
            await task
        assert req.fut.done()
    finally:
        server.drop_replies = False
        await c.close()


async def test_a_cache_plane_keeps_its_own_route(server):
    c = await connected(server, cache='/')
    try:
        await wait_until(lambda: c.cache.stats()['armed'] == 1)
        await c.create('/k', b'v')
        server.drop_replies = True
        task = asyncio.ensure_future(c.get('/k'))       # a miss
        await asyncio.sleep(0.02)
        assert _await_chain(task) == ['get', '_routed_read', '_await_op']
        server.drop_replies = False
        c.current_connection().transport.abort()
        with pytest.raises(ZKProtocolError):
            await task
    finally:
        server.drop_replies = False
        await c.close()


async def test_an_op_asks_for_a_profiler_session_once(server, monkeypatch):
    """Outside a session an op's whole cost of the host spans is ONE
    ``is_enabled()``: the look ``client.prepare`` makes answers for
    ``client.submit``, the stage stamps and ``client.resume``."""
    c = await connected(server)
    try:
        await c.create('/k', b'v')
        await c.get('/k', deadline=60000)   # the loop's timer is armed
        trace._bind()
        asked = []
        monkeypatch.setattr(trace, '_is_enabled',
                            lambda: asked.append(1) and False)
        # the synchronous pass: look-up, span, request, future
        waiting = c._primary_request(
            {'opcode': 'GET_DATA', 'path': '/k', 'watch': False},
            'GET_DATA', '/k', 30000)
        assert len(asked) == 1
        assert (await waiting)['data'] == b'v'
        span = c.trace.spans()[-1]
        assert span.stages is None and span.t0_ns is None
    finally:
        await c.close()


async def test_a_client_keeps_its_loops_deadline_queue(server):
    c = await connected(server, op_timeout=None)
    try:
        await c.create('/k', b'v')
        # the loop's, from ``start()`` on (it carries the idle clock);
        # an unbounded op stands in it never
        queue = deadline_queue(asyncio.get_running_loop())
        assert c._deadlines is queue and len(queue) == 0
        await c.get('/k', deadline=5000)
        assert c._deadlines is queue and len(queue) == 0
        await c.get('/k', deadline=5000)
        assert c._deadlines is queue
    finally:
        await c.close()


async def test_a_connection_stamps_what_changes_once_a_connection(server):
    c = await connected(server)
    try:
        conn = c.current_connection()
        assert conn.span_backend == conn.backend.key
        assert conn.span_session_id == c.session.get_session_id()
        await c.create('/k', b'v')
        assert await c.ping() >= 0
        ping = [s for s in c.trace.spans() if s.op == 'PING'][-1]
        assert ping.backend == conn.backend.key
    finally:
        await c.close()


# -- the span -----------------------------------------------------------

def test_a_span_has_no_dict_and_refuses_a_name_outside_its_slots():
    ring = TraceRing(8)
    span = ring.start('GET_DATA', '/k')
    assert not hasattr(span, '__dict__')
    with pytest.raises(AttributeError):
        span.colour = 'red'
    with pytest.raises(AttributeError):
        ring.note('COMMIT', zxid=1, colour='red')
    with pytest.raises(AttributeError):
        span.colour
    assert getattr(span, 'colour', 7) == 7 and not hasattr(span, 'colour')
    # every optional field reads None until stamped, however made
    for s in (span, ring.note('COMMIT', zxid=1), Span(3, 'X')):
        unset = [f for f in trace._OPTIONAL_FIELDS
                 if f not in ('path', 'zxid', 'member')]
        assert [getattr(s, f) for f in unset] == [None] * len(unset)
        assert s.stages is None and s._on_slow is None
    assert span.duration_ms is None
    assert set(trace._OPTIONAL_FIELDS) < set(Span.__slots__)


def _span_field_names() -> dict:
    """Every keyword the package hands a span by name: ``note(...)``,
    ``host_span(...)`` and a host span's ``.set(...)`` — name ->
    where."""
    found: dict = {}
    for path in PKG.rglob('*.py'):
        tree = ast.parse(path.read_text())
        host = {item.optional_vars.id
                for node in ast.walk(tree) if isinstance(node, ast.With)
                for item in node.items
                if isinstance(item.context_expr, ast.Call)
                and getattr(item.context_expr.func, 'id', None)
                == 'host_span'
                and isinstance(item.optional_vars, ast.Name)}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = getattr(f, 'attr', getattr(f, 'id', None))
            recv = getattr(getattr(f, 'value', None), 'id', None)
            if name == 'note' and isinstance(f, ast.Attribute):
                skip = {'kind'}
            elif name == 'host_span':
                skip = {'accumulate'}
            elif name == 'set' and recv in host:
                skip = set()
            else:
                continue
            for kw in node.keywords:
                if kw.arg is not None and kw.arg not in skip:
                    found.setdefault(kw.arg, '%s:%d' % (
                        path.relative_to(PKG), node.lineno))
    return found


def test_note_accepts_every_name_the_tree_passes_it():
    names = _span_field_names()
    # the scan sees the calls: a member's txn stage, a host span's ids
    # and what one sets under way
    assert {'zxid', 'detail', 'nbytes', 'tick', 'lane', 'lists',
            'shared', 'duration_ms'} <= set(names)
    stray = {n: at for n, at in names.items()
             if n not in Span.__slots__}
    assert stray == {}
    ring = TraceRing(4)
    span = ring.note('X', **{n: 1 for n in names
                             if n not in ('path', 'zxid')})
    assert all(getattr(span, n) == 1 for n in names
               if n not in ('path', 'zxid'))


@pytest.mark.parametrize('make', ['start', 'note'])
def test_a_span_is_on_the_wall_clock_to_the_millisecond(make):
    ring = TraceRing(4, member='m1')
    before = time.time()
    span = (ring.start('GET_DATA', '/k') if make == 'start'
            else ring.note('COMMIT', '/k', zxid=9, nbytes=3))
    after = time.time()
    assert before - 0.001 <= span.t_wall <= after + 0.001
    d = span.to_dict()
    assert d['t_wall'] == round(span.t_wall, 6) and d['member'] == 'm1'
    assert list(d)[:5] == ['span', 'kind', 'op', 'status', 't_wall']
    # one anchor a ring: the order of two spans' wall times is the
    # order of their starts, whatever the wall clock did between
    later = ring.start('GET_DATA', '/k')
    assert later.t_wall >= span.t_wall and later._anchor == span._anchor


def test_a_ring_dumps_its_newest_spans_without_the_rest():
    ring = TraceRing(16)
    for n in range(40):
        ring.note('X', zxid=n)
    assert ring.dump(last=5) == ring.dump()[-5:]
    assert ring.dump(last=16) == ring.dump(last=99) == ring.dump()
    assert [s['zxid'] for s in ring.dump(last=2)] == [38, 39]


def test_a_slow_op_hook_still_fires_once():
    ring = TraceRing(4)
    slow: list = []
    ring.slow_ms, ring.on_slow = 0.0, slow.append
    span = ring.start('SET_DATA', '/k')
    span.finish(zxid=5)
    span.finish(zxid=6, status='error')     # first outcome wins
    assert slow == [span] and span.zxid == 5 and span.status == 'ok'
    noted = ring.note('GROUP_FSYNC', duration_ms=3.0)
    assert slow == [span, noted]


# -- the histogram's bound series ---------------------------------------

def _reference_rows(bounds, samples) -> list:
    """The cumulative rows of one series, by the linear scan the
    histogram did before it bisected: a value ON a bound counts under
    that bound."""
    counts = [0] * (len(bounds) + 1)
    for v in samples:
        for i, bound in enumerate(bounds):
            if v <= bound:
                counts[i] += 1
                break
        else:
            counts[-1] += 1
    out, cum = [], 0
    for n in counts:
        cum += n
        out.append(cum)
    return out


SAMPLES = [0.0, 0.25, 0.5, 0.500001, 1.0, 2.4, 2.5, 10.0, 24.9, 25.0,
           9999.0, 10000.0, 10000.5, 1e9, -1.0]


@pytest.mark.parametrize('how', ['bound', 'labelled', 'mixed'])
def test_a_bound_series_and_a_labelled_observe_land_in_one_row(how):
    labels = {'op': 'GET_DATA'}
    want = Histogram('zookeeper_op_latency_ms', 'help')
    for v in SAMPLES:
        want.observe(v, labels)
    got = Histogram('zookeeper_op_latency_ms', 'help')
    series = got.labels(labels)
    assert isinstance(series, BoundSeries)
    for n, v in enumerate(SAMPLES):
        if how == 'bound' or (how == 'mixed' and n % 2):
            series.observe(v)
        else:
            got.observe(v, dict(labels))
    # byte for byte, bucket edges included
    assert got.expose() == want.expose()
    assert got.label_keys() == [(('op', 'GET_DATA'),)]
    rows = dict(got.rows())
    ref = _reference_rows(DEFAULT_BUCKETS, SAMPLES)
    for bound, cum in zip(DEFAULT_BUCKETS, ref):
        assert rows['zookeeper_op_latency_ms_bucket{op="GET_DATA",'
                    'le="%g"}' % (bound,)] == cum
        assert got.bucket_value(bound, labels) == cum
    assert rows['zookeeper_op_latency_ms_bucket{op="GET_DATA",'
                'le="+Inf"}'] == ref[-1] == len(SAMPLES)
    assert got.count(labels) == len(SAMPLES)
    assert got.sum(labels) == pytest.approx(sum(SAMPLES))
    # a second handle of the same labels is the same row
    got.labels({'op': 'GET_DATA'}).observe(0.5)
    assert got.count(labels) == len(SAMPLES) + 1


def test_a_bound_series_is_read_through_a_link_and_unlabelled():
    tier = Histogram('zookeeper_submit_depth', buckets=(1, 2, 4))
    tier.labels({'plane': 'client', 'backend': 'mmsg'}).observe(2)
    tier.labels().observe(3)
    coll = Collector()
    coll.adopt(tier)
    own = coll.get_collector('zookeeper_submit_depth')
    own.observe(2, {'backend': 'mmsg', 'plane': 'client'})
    labels = {'plane': 'client', 'backend': 'mmsg'}
    assert own.count(labels) == 2 and own.bucket_value(2, labels) == 2
    assert own.count() == 1 and own.bucket_value(2) == 0
    assert 'zookeeper_submit_depth_bucket{backend="mmsg",' \
        'plane="client",le="2"} 2' in coll.expose()


async def test_the_clients_latency_rows_are_the_labelled_ones(server):
    coll = Collector()
    c = await connected(server, collector=coll)
    try:
        await c.create('/k', b'v')
        for _ in range(3):
            await c.get('/k')
        with pytest.raises(ZKError):
            await c.get('/missing')
        h = coll.get_collector('zookeeper_op_latency_ms')
        assert h.count({'op': 'GET_DATA'}) == 4
        assert h.count({'op': 'CREATE'}) == 1
        assert sorted(c._op_series) == ['CREATE', 'GET_DATA']
        h.observe(1.0, {'op': 'GET_DATA'})  # a caller that keeps labels
        assert 'zookeeper_op_latency_ms_count{op="GET_DATA"} 5' \
            in coll.expose()
        # the send plane's two, bound once a connection
        flush = coll.get_collector('zookeeper_flush_batch_frames')
        assert flush.count({'plane': 'client'}) >= 5
        assert flush.label_keys() == [(('plane', 'client'),)]
    finally:
        await c.close()


# -- the request object -------------------------------------------------

OK_PKT = {'xid': 5, 'zxid': 9, 'err': 'OK', 'opcode': 'GET_DATA',
          'data': b'v', 'stat': None}


def _request(listen: bool):
    req = ZKRequest({'opcode': 'GET_DATA', 'path': '/k', 'xid': 5})
    heard: list = []
    if listen:
        req.on('reply', lambda pkt: heard.append(('reply', pkt)))
        req.once('error', lambda err, *a: heard.append(('error', err, a)))
    return req, heard


@pytest.mark.parametrize('listen', [False, True])
@pytest.mark.parametrize('how', ['ok', 'error', 'throttled', 'fail',
                                 'late'])
async def test_a_request_settles_and_fails_as_before(listen, how):
    req, heard = _request(listen)
    assert not hasattr(req, '__dict__')
    ring = TraceRing(4)
    req.span = ring.start('GET_DATA', '/k')
    fut = req.as_future()
    assert req.as_future() is fut
    if how == 'ok':
        assert req.settle(dict(OK_PKT)) is listen
        assert fut.result()['data'] == b'v'
        assert heard == ([('reply', OK_PKT)] if listen else [])
        assert (req.span.status, req.span.zxid) == ('ok', 9)
    elif how in ('error', 'throttled'):
        code = 'NO_NODE' if how == 'error' else 'THROTTLED'
        pkt = dict(OK_PKT, err=code)
        assert req.settle(pkt) is listen
        err = fut.exception()
        assert type(err) is (ZKError if how == 'error'
                             else ZKThrottledError) and err.code == code
        assert heard == ([('error', err, (pkt,))] if listen else [])
        assert (req.span.status, req.span.error) == ('error', code)
        if listen:                      # a ``once`` is gone once heard
            assert req.listener_count('error') == 0
            assert req.listener_count('reply') == 1
    elif how == 'fail':
        err = ZKProtocolError('CONNECTION_LOSS', 'x')
        req.fail(err)
        assert fut.exception() is err
        assert heard == ([('error', err, ())] if listen else [])
        assert req.span.status == 'open'    # the teardown path's to close
        req.span.finish(status='abandoned')
    else:
        fut.cancel()                        # the awaiter gave up
        assert req.settle(dict(OK_PKT)) is listen   # dropped, no raise
        assert fut.cancelled() and req.span.status == 'ok'
        req.fail(ZKProtocolError('CONNECTION_LOSS', 'x'))
    # nobody's listeners ended up in the table every request shares
    assert connection_mod._NO_LISTENERS == {}
    assert (req._listeners is connection_mod._NO_LISTENERS) is not listen


def test_a_request_nobody_listens_to_answers_the_emitters_questions():
    req, _ = _request(False)
    assert req.emit('reply', OK_PKT) is False
    assert req.listeners('reply') == [] and req.listener_count('x') == 0
    req.remove_listener('reply', print)
    req.remove_all_listeners('reply')
    req.remove_all_listeners()
    assert connection_mod._NO_LISTENERS == {}
    cb = []
    req.once('reply', cb.append)
    assert req._listeners is not connection_mod._NO_LISTENERS
    assert req.emit('reply', 1) is True and cb == [1]
    assert req.emit('reply', 2) is False and cb == [1]
