"""Doublecheck ("missed wakeup" self-probe) tests.

After a long idle window an armed watch probes EXISTS (no watch) and
compares zxids: a moved zxid with no notification means the watch
machinery lost an event, and the process deliberately crashes
(reference: lib/zk-session.js:901-970, window constants :35-36).  These
tests shrink the window to milliseconds and drive both the clean pass
and the crash path.
"""

import asyncio

import pytest

from helpers import wait_until
from zkstream_tpu import Client
from zkstream_tpu.io import watcher as watcher_mod
from zkstream_tpu.io.watcher import LostWakeupError


@pytest.fixture
def fast_doublecheck(monkeypatch):
    """Shrink the 4-12 h idle window to ~80 ms, deterministically."""
    monkeypatch.setattr(watcher_mod, 'DOUBLECHECK_TIMEOUT', 80)
    monkeypatch.setattr(watcher_mod, 'DOUBLECHECK_RAND', 0)


@pytest.fixture
def client(event_loop, server):
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000)
    c.start()
    event_loop.run_until_complete(c.wait_connected(timeout=5))
    yield c
    event_loop.run_until_complete(c.close())


async def test_doublecheck_probe_clean(fast_doublecheck, client):
    """Idle watch probes, finds the zxid unmoved, and returns to armed;
    the watch keeps working afterwards."""
    await client.create('/dc', b'v0')
    seen = []
    client.watcher('/dc').on('dataChanged',
                             lambda data, stat: seen.append(bytes(data)))
    await wait_until(lambda: seen == [b'v0'])

    we = client.watcher('/dc').watch_events['dataChanged']
    states = []
    we.on('stateChanged', lambda st: states.append(st))
    await wait_until(lambda: 'armed.doublecheck' in states)
    # The probe reply found prev_zxid unchanged: back to armed.
    await wait_until(lambda: states[-1] == 'armed' and
                     we.is_in_state('armed'))

    await client.set('/dc', b'v1')
    await wait_until(lambda: seen == [b'v0', b'v1'])


async def test_doublecheck_detects_missed_wakeup(
        event_loop, fast_doublecheck, client):
    """If the zxid moved behind the watch's back, the probe escalates
    fatally BY DEFAULT — no custom handler installed: the client emits
    'failed' with the LostWakeupError, the session tears down through
    'expire', and the loop's exception handler is invoked (crash-on-bug,
    reference: lib/zk-session.js:916-919)."""
    await client.create('/dc2', b'v0')
    seen = []
    client.watcher('/dc2').on('dataChanged',
                              lambda data, stat: seen.append(bytes(data)))
    await wait_until(lambda: seen == [b'v0'])

    failures, expires = [], []
    client.on('failed', lambda *a: failures.append(a))
    client.on('expire', lambda *a: expires.append(True))
    sess = client.session

    we = client.watcher('/dc2').watch_events['dataChanged']
    # Simulate a lost wakeup: the node's mzxid no longer matches what
    # the armed watch believes it last emitted for.
    we.prev_zxid -= 1

    # Process-visible failure, with NO handler installed anywhere.
    await wait_until(lambda: failures and expires, timeout=10)
    assert isinstance(failures[0][0], LostWakeupError)
    assert sess.is_in_state('expired')


async def test_missed_wakeup_custom_fatal_handler(
        fast_doublecheck, server):
    """on_fatal= overrides the loud default; teardown still happens."""
    caught = []
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000, on_fatal=caught.append)
    c.start()
    await c.wait_connected(timeout=5)
    try:
        await c.create('/dc4', b'v0')
        seen = []
        c.watcher('/dc4').on('dataChanged',
                             lambda data, stat: seen.append(bytes(data)))
        await wait_until(lambda: seen == [b'v0'])
        sess = c.session
        c.watcher('/dc4').watch_events['dataChanged'].prev_zxid -= 1
        await wait_until(lambda: bool(caught), timeout=10)
        assert isinstance(caught[0], LostWakeupError)
        assert sess.is_in_state('expired')
    finally:
        await c.close()


async def test_doublecheck_defers_when_disconnected(monkeypatch, server):
    """An armed watch whose session detached must not probe: it goes to
    resuming, and the doublecheck timer only re-arms on reconnect.

    Uses a 500 ms window (not the 80 ms fast fixture): the window must
    be comfortably wider than the abort -> connection_lost gap, or the
    timer could legitimately fire before the FSM hears about the dead
    transport and the no-probe assertion would race."""
    monkeypatch.setattr(watcher_mod, 'DOUBLECHECK_TIMEOUT', 500)
    monkeypatch.setattr(watcher_mod, 'DOUBLECHECK_RAND', 0)
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000)
    c.start()
    await c.wait_connected(timeout=5)
    try:
        await c.create('/dc3', b'v0')
        seen = []
        c.watcher('/dc3').on('dataChanged',
                             lambda data, stat: seen.append(bytes(data)))
        await wait_until(lambda: seen == [b'v0'])
        we = c.watcher('/dc3').watch_events['dataChanged']
        states = []
        we.on('stateChanged', lambda st: states.append(st))

        # Kill the transport: session detaches, watch goes to resuming
        # (or re-arms from scratch), never straight into a probe.
        c.current_connection().transport.abort()
        await wait_until(
            lambda: any(st in ('resuming', 'wait_session')
                        for st in states), timeout=5)
        # No probe may have fired in the detached window.
        assert 'armed.doublecheck' not in states
        # Reconnection re-arms it; doublecheck still fires cleanly after.
        await wait_until(lambda: we.is_in_state('armed'), timeout=10)
        del states[:]
        await wait_until(lambda: 'armed.doublecheck' in states and
                         we.is_in_state('armed'), timeout=5)
        await c.set('/dc3', b'v1')
        await wait_until(lambda: seen == [b'v0', b'v1'])
    finally:
        await c.close()


async def test_notify_unmatched_escalates_fatally(client):
    """A notification that matches no armed event FSM means our model of
    ZK watch semantics is wrong: crash-on-bug escalation — client emits
    'failed' and the session tears down, with no handler installed
    (reference throws: lib/zk-session.js:584-592)."""
    await client.create('/nm', b'')
    w = client.watcher('/nm')
    w.on('childrenChanged', lambda *a: None)
    await asyncio.sleep(0.1)
    failures = []
    client.on('failed', lambda *a: failures.append(a))
    sess = client.session
    # 'created' fans out to createdOrDeleted/dataChanged only — neither
    # is armed here.
    w.notify('created')
    await wait_until(lambda: bool(failures), timeout=5)
    assert isinstance(failures[0][0], LostWakeupError)
    assert sess.is_in_state('expired')


async def test_doublecheck_probe_through_ingest(
        fast_doublecheck, event_loop, server):
    """The probe's EXISTS reply routes back through the fleet ingest's
    batched delivery (bypass disabled so the device path carries it) —
    the lost-wakeup self-check composes with the TPU data plane."""
    from zkstream_tpu.io.ingest import FleetIngest

    ingest = FleetIngest(max_frames=8, bypass_bytes=0,
                         warm='block')
    c = Client(address='127.0.0.1', port=server.port,
               session_timeout=5000, ingest=ingest)
    c.start()
    try:
        await c.wait_connected(timeout=5)
        await c.create('/dci', b'v0')
        seen = []
        c.watcher('/dci').on('dataChanged',
                             lambda data, stat: seen.append(bytes(data)))
        await wait_until(lambda: seen == [b'v0'])
        we = c.watcher('/dci').watch_events['dataChanged']
        states = []
        we.on('stateChanged', lambda st: states.append(st))
        await wait_until(lambda: 'armed.doublecheck' in states)
        await wait_until(lambda: states[-1] == 'armed'
                         and we.is_in_state('armed'))
        await c.set('/dci', b'v1')
        await wait_until(lambda: seen == [b'v0', b'v1'])
        assert ingest.ticks > 0
    finally:
        await c.close()
