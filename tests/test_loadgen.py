"""The C load generator (tools/loadgen.c; README "Load generation").

Four pillars:

1. **Parity** — the C arm and the Python client arm agree against a
   live server: the loadgen's op counts reconcile exactly with the
   server's zxid advance bracketed by Python-client writes, its
   acked-write max zxid sits inside the bracket, and its fan-out SET
   fires a watch armed by the Python client (cross-arm watch
   delivery).
2. **zxid floor check** — a fake server replaying a stale zxid (a
   reply older than what the connection already saw) is detected:
   distinct exit code 4, violation counted in the summary JSON.
3. **Malformed/torn replies** — a fake server closing mid-frame gets
   the distinct exit code 3, not a crash and not a silent zero.
4. **Scale smoke** — 1k sessions against one in-process server,
   inside the tier-1 budget.

Every test skips cleanly when the host has no C compiler.
"""

import asyncio
import json
import socket
import struct
import threading

import pytest

from zkstream_tpu.utils import loadgen, native

BIN = native.build_loadgen()

pytestmark = pytest.mark.skipif(
    BIN is None, reason='no C compiler: zkloadgen unavailable')


async def _run_loadgen(cmd, timeout=120):
    """Run one loadgen invocation to completion while the caller's
    event loop (and therefore any in-process server) keeps serving.
    Returns (rc, summary dict)."""
    proc = await asyncio.create_subprocess_exec(
        *cmd, stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.DEVNULL)
    out, _ = await asyncio.wait_for(proc.communicate(), timeout)
    summary = json.loads(out.decode().strip().splitlines()[-1])
    return proc.returncode, summary


# -- pillar 1: loadgen-vs-Python parity ---------------------------------


async def test_parity_op_counts_zxids_and_watch_fires(event_loop):
    """Bracket a count-mode loadgen run between two Python-client
    writes: every successful loadgen write must account for exactly
    one zxid step, its acked-write max zxid must fall inside the
    bracket, and its fan-out SET must fire a watch the PYTHON client
    armed (the two arms observe each other's effects)."""
    from zkstream_tpu import Client
    from zkstream_tpu.server import ZKServer

    srv = await ZKServer().start()
    c = Client(servers=[('127.0.0.1', srv.port)],
               shuffle_backends=False, session_timeout=30000)
    c.start()
    try:
        await c.wait_connected(timeout=10)
        await c.create('/parity', b'seed')
        fired = asyncio.Event()
        w = c.watcher('/parity')
        w.on('dataChanged', lambda *a: fired.set())
        await asyncio.sleep(0.05)     # let the watch arm land
        before = (await c.set('/parity', b'a')).mzxid

        sessions, count = 10, 30
        cmd = loadgen.argv(
            [('127.0.0.1', srv.port)], sessions, count=count,
            mix='get=50,set=50', path='/parity', ensure_path=False,
            arm_watch=True, fanout_sets=2, pipeline=4,
            close_sessions=True)
        rc, s = await _run_loadgen(cmd)
        assert rc == 0, s
        after = (await c.set('/parity', b'b')).mzxid

        # op-count parity: the steady window issued exactly
        # sessions x count mix ops (the fan-out rounds' SETs ride the
        # SET_DATA class too), all of them acked
        ops = s['ops']
        mix_ops = (ops['GET_DATA']['count']
                   + ops['SET_DATA']['count'])
        assert mix_ops == sessions * count + s['fanout']['rounds']
        assert s['errors'] == {'connect': 0, 'io': 0, 'proto': 0}
        assert s['zxid']['floor_violations'] == 0

        # zxid parity: every write the server acked to the loadgen
        # (steady SETs + the 2 fan-out SETs) is one zxid step in the
        # Python client's bracket, and nothing else wrote
        writes = (ops['SET_DATA']['count']
                  - ops['SET_DATA']['errors'])
        assert after - before == writes + 1
        assert before < s['zxid']['acked_write_max_zxid'] < after
        assert s['zxid']['max_zxid'] <= after

        # cross-arm watch delivery: the loadgen's fan-out SET fired
        # the watch the Python client armed...
        await asyncio.wait_for(fired.wait(), 5)
        # ...and the loadgen's own armed watchers all fired too
        # (steady-window SETs also fire armed watches, so total
        # notifications exceed the dedicated fan-out rounds')
        assert s['fanout']['rounds'] == 2
        assert s['fanout']['delivered'] == s['fanout']['expected']
        assert s['notifications'] >= s['fanout']['delivered']
    finally:
        try:
            await asyncio.wait_for(c.close(), 5)
        except Exception:
            c.pool.stop()
        await srv.stop()


# -- fake servers for the failure pillars -------------------------------

_CONNECT_RESP = struct.pack('>iiq', 0, 30000, 0x1234) + \
    struct.pack('>i', 16) + b'\0' * 16


def _frame(body: bytes) -> bytes:
    return struct.pack('>i', len(body)) + body


def _recv_frame(conn: socket.socket) -> bytes | None:
    hdr = b''
    while len(hdr) < 4:
        chunk = conn.recv(4 - len(hdr))
        if not chunk:
            return None
        hdr += chunk
    need = struct.unpack('>i', hdr)[0]
    body = b''
    while len(body) < need:
        chunk = conn.recv(need - len(body))
        if not chunk:
            return None
        body += chunk
    return body


def _fake_server(per_request):
    """One-connection fake ZK server: answers the handshake, then
    calls ``per_request(conn, n, xid)`` for each request frame.
    Returns (port, thread, stop)."""
    lsock = socket.socket()
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(('127.0.0.1', 0))
    lsock.listen(8)
    port = lsock.getsockname()[1]

    def serve():
        try:
            conn, _ = lsock.accept()
            with conn:
                if _recv_frame(conn) is None:   # ConnectRequest
                    return
                conn.sendall(_frame(_CONNECT_RESP))
                n = 0
                while True:
                    body = _recv_frame(conn)
                    if body is None:
                        return
                    xid = struct.unpack('>i', body[:4])[0]
                    if not per_request(conn, n, xid):
                        return
                    n += 1
        except OSError:
            pass
        finally:
            lsock.close()

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    return port, t


# -- pillar 2: stale-reply zxid-floor detection -------------------------


async def test_zxid_floor_violation_exits_4(event_loop):
    """A reply whose header zxid is OLDER than what the connection
    already observed (a member serving stale state) trips the
    per-connection floor check: counted in the summary, distinct
    exit code 4."""
    def stale(conn, n, xid):
        # the first reply (a HOLD-phase keepalive ping gets one too)
        # raises the floor to 100; later replies replay zxid 50 —
        # stale reads the loadgen must catch on EVERY reply
        zxid = 100 if n == 0 else 50
        conn.sendall(_frame(struct.pack('>iqi', xid, zxid, 0)))
        return True

    port, _t = _fake_server(stale)
    cmd = loadgen.argv([('127.0.0.1', port)], 1, count=2,
                       mix='get=100', ensure_path=False, pipeline=1)
    rc, s = await _run_loadgen(cmd)
    assert rc == 4, s
    assert s['zxid']['floor_violations'] >= 1
    assert s['client_capped'] is False


async def test_monotone_zxids_exit_0(event_loop):
    """The control arm: the same fake server with monotone zxids is
    clean — exit 0, no violations (the floor check has no false
    positives on legal streams)."""
    def monotone(conn, n, xid):
        conn.sendall(_frame(struct.pack('>iqi', xid, 100 + n, 0)))
        return True

    port, _t = _fake_server(monotone)
    cmd = loadgen.argv([('127.0.0.1', port)], 1, count=2,
                       mix='get=100', ensure_path=False, pipeline=1)
    rc, s = await _run_loadgen(cmd)
    assert rc == 0, s
    assert s['zxid']['floor_violations'] == 0


# -- pillar 3: malformed / torn replies ---------------------------------


async def test_torn_reply_exits_3(event_loop):
    """A reply torn mid-frame (length prefix promises 16 bytes, the
    peer sends 8 and closes) is a protocol error: counted, distinct
    exit code 3 — never conflated with the floor-violation exit."""
    def torn(conn, n, xid):
        conn.sendall(struct.pack('>i', 16)
                     + struct.pack('>iI', xid, 0))
        return False    # close mid-frame

    port, _t = _fake_server(torn)
    cmd = loadgen.argv([('127.0.0.1', port)], 1, count=2,
                       mix='get=100', ensure_path=False, pipeline=1)
    rc, s = await _run_loadgen(cmd)
    assert rc == 3, s
    assert s['errors']['proto'] == 1
    assert s['zxid']['floor_violations'] == 0


async def test_unmatched_xid_exits_3(event_loop):
    """A reply whose xid matches no outstanding request (a corrupt
    or misrouted frame) is malformed, same distinct exit code."""
    def misrouted(conn, n, xid):
        conn.sendall(_frame(struct.pack('>iqi', xid + 7, 1, 0)))
        return True

    port, _t = _fake_server(misrouted)
    cmd = loadgen.argv([('127.0.0.1', port)], 1, count=2,
                       mix='get=100', ensure_path=False, pipeline=1)
    rc, s = await _run_loadgen(cmd)
    assert rc == 3, s
    assert s['errors']['proto'] >= 1


async def test_uneven_connect_phase_does_not_hang(event_loop):
    """Two epoll threads whose connect phases end far apart (one
    member answers its handshake 300 ms late — a follower taking the
    ensure-path CREATE does the same): the thread that finished first
    keeps its phase stamp while it idles, so the run proceeds.  It
    used to clear the stamp after one 10 ms pump, and main then waited
    forever for both stamps at once."""
    import time

    lsock = socket.socket()
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(('127.0.0.1', 0))
    lsock.listen(8)
    port = lsock.getsockname()[1]

    def serve(conn, late):
        try:
            with conn:
                if _recv_frame(conn) is None:   # ConnectRequest
                    return
                if late:
                    time.sleep(0.3)
                conn.sendall(_frame(_CONNECT_RESP))
                n = 0
                while True:
                    body = _recv_frame(conn)
                    if body is None:
                        return
                    xid = struct.unpack('>i', body[:4])[0]
                    conn.sendall(_frame(
                        struct.pack('>iqi', xid, 100 + n, 0)))
                    n += 1
        except OSError:
            pass

    def accept():
        try:
            for i in range(2):
                conn, _ = lsock.accept()
                threading.Thread(target=serve, args=(conn, i == 0),
                                 daemon=True).start()
        except OSError:
            pass
        finally:
            lsock.close()

    threading.Thread(target=accept, daemon=True).start()
    cmd = loadgen.argv([('127.0.0.1', port)], 2, threads=2, count=2,
                       mix='get=100', ensure_path=False, pipeline=1)
    rc, s = await _run_loadgen(cmd, timeout=20)
    assert rc == 0, s
    assert s['connected'] == 2
    assert s['ops']['GET_DATA']['count'] == 4


# -- pillar 4: 1k-session tier-1 smoke ----------------------------------


async def test_thousand_session_smoke(event_loop):
    """1000 raw-socket sessions against one in-process server: every
    session connects, the count-mode window drains exactly, zero
    floor violations / protocol errors, and the summary carries the
    fd-cap accounting the million-session campaign relies on."""
    from zkstream_tpu.server import ZKServer

    srv = await ZKServer().start()
    try:
        sessions, count = 1000, 5
        cmd = loadgen.argv([('127.0.0.1', srv.port)], sessions,
                           count=count, mix='get=100',
                           path='/smoke', pipeline=2,
                           close_sessions=True)
        rc, s = await _run_loadgen(cmd, timeout=180)
        assert rc == 0, s
        assert s['connected'] == sessions
        assert s['ops']['GET_DATA']['count'] == sessions * count
        assert s['errors'] == {'connect': 0, 'io': 0, 'proto': 0}
        assert s['zxid']['floor_violations'] == 0
        assert s['handshake']['failures'] == 0
        caps = s['caps']
        assert caps['nofile_soft'] >= sessions
        assert caps['sessions_clamped'] is False
    finally:
        await srv.stop()


# -- pillar 5: cached arm (ADD_WATCH + local hit simulation) ------------


async def test_cached_arm_add_watch_and_local_hits(event_loop):
    """--cached arms one persistent-recursive ADD_WATCH per session,
    serves steady reads from the local entry (no wire traffic), and
    every writer-churn notification invalidates exactly one refill
    read.  Wire reads therefore track invalidations, not the read
    rate, and the floor check still holds on every wire reply."""
    from zkstream_tpu.server import ZKServer

    srv = await ZKServer().start()
    try:
        sessions = 8
        cmd = loadgen.argv([('127.0.0.1', srv.port)], sessions,
                           duration=2, pipeline=4, path='/cbench',
                           cached=True, cached_write_ms=100)
        rc, s = await _run_loadgen(cmd, timeout=120)
        assert rc == 0, s
        assert s['connected'] == sessions
        assert s['ops']['ADD_WATCH']['count'] == sessions
        assert s['ops']['ADD_WATCH']['errors'] == 0
        cache = s['cache']
        assert cache['hits'] > 0
        assert cache['invalidations'] > 0
        # one wire refill per invalidation, like the client cache
        assert cache['wire_reads_win'] <= cache['invalidations'] + sessions
        assert cache['hit_ratio'] > 0.5
        # local hits never cross the wire: single-digit microseconds
        assert cache['hit_p50_us'] < 10.0
        assert s['notifications'] >= cache['invalidations']
        assert s['zxid']['floor_violations'] == 0
        assert s['errors'] == {'connect': 0, 'io': 0, 'proto': 0}
    finally:
        await srv.stop()
