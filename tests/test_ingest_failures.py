"""FleetIngest failure-path coverage (VERDICT r3 Next #8): the code
that only runs when things go wrong — compile-failure latch (and its
force-device refusal), placement probe, loop-closed-mid-compile,
torn-down-mid-tick
connections, unmatched xids, unsupported reply opcodes, and the C-slice
error wrap.  Driven through lightweight fake connections so each path
is hit deterministically, with asserts on observable behavior (what got
delivered / counted), not line touches.
"""

from __future__ import annotations

import asyncio
import struct
import threading
import types

import pytest

from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.protocol.errors import ZKProtocolError
from zkstream_tpu.protocol.framing import PacketCodec, frame
from zkstream_tpu.protocol.jute import JuteWriter
from zkstream_tpu.protocol.records import write_response


class FakeConn:
    """The slice of ZKConnection the ingest touches: a codec, a state
    probe, and the 'ingestDeliver' emitter."""

    def __init__(self, use_native=False):
        self.codec = PacketCodec(use_native=use_native)
        self.codec.handshaking = False
        self.delivered: list = []
        self.on_deliver = None
        self.state = 'connected'

    def is_in_state(self, s):
        return self.state == s

    def emit(self, name, *args):
        assert name == 'ingestDeliver'
        self.delivered.append(args)
        if self.on_deliver is not None:
            self.on_deliver(self)


def reply_frame(xid, opcode='PING', zxid=7, **body) -> bytes:
    w = JuteWriter()
    write_response(w, {'xid': xid, 'zxid': zxid, 'err': 'OK',
                       'opcode': opcode, **body})
    return frame(w.to_bytes())


def mk_ingest(**kw):
    kw.setdefault('bypass_bytes', 0)
    kw.setdefault('warm', 'block')
    kw.setdefault('min_len', 256)
    kw.setdefault('max_frames', 4)
    return FleetIngest(**kw)


async def drain():
    """Run the call_soon-scheduled tick."""
    await asyncio.sleep(0)
    await asyncio.sleep(0)


def _broken_compile(key):
    raise RuntimeError('injected compile failure')


async def _enter_batch_regime(ing, conn):
    """With a production byte threshold the ingest starts as a
    pass-through; one window of traffic above it flips to batching."""
    ing.feed(conn, reply_frame(-2))
    await drain()
    assert not ing._direct


async def test_compile_failure_latches_bucket_to_scalar():
    """With a production byte threshold (``bypass_bytes`` > 0: the
    scalar drain is a legitimate path) a failed XLA compile latches
    that bucket onto the scalar drain — never retry-compile, never
    lose traffic — and the failure is readable, not only logged."""
    ing = mk_ingest(bypass_bytes=1)
    ing._compile = _broken_compile
    conn = FakeConn()
    ing.register(conn)
    await _enter_batch_regime(ing, conn)
    ing.feed(conn, reply_frame(-2))
    await drain()
    # delivered through the codec anyway, and the bucket is poisoned
    assert conn.delivered[-1][0][0]['opcode'] == 'PING'
    assert list(ing._exec.values()) == [None]
    (info,) = ing.buckets.values()
    assert info['impl'] is None
    assert 'injected compile failure' in info['error']
    before = ing.ticks_scalar
    ing.feed(conn, reply_frame(-2))
    await drain()
    assert ing.ticks_scalar == before + 1   # stays scalar, no retry
    assert ing.ticks == 0


async def test_force_device_compile_failure_raises():
    """``bypass_bytes=0`` promises every tick on the device program:
    a bucket that fails to compile raises — from prewarm, and from
    the tick that needs it — instead of quietly becoming a scalar
    bucket.  Nothing is delivered, nothing is counted as scalar."""
    ing = mk_ingest()                      # bypass_bytes=0, block
    ing._compile = _broken_compile
    with pytest.raises(RuntimeError, match='force-device'):
        await ing.prewarm(4)
    conn = FakeConn()
    ing.register(conn)
    ing.feed(conn, reply_frame(-2))
    with pytest.raises(RuntimeError, match='injected compile failure'):
        ing._tick()
    assert conn.delivered == []
    assert ing.ticks == 0 and ing.ticks_scalar == 0
    assert [b['error'] is not None for b in ing.buckets.values()] \
        == [True]


async def test_background_compile_failure_unblocks_prewarm():
    """warm='background': a failing compile still sets the warm event
    (None latched), so prewarm callers do not hang — with a production
    threshold traffic then flows scalar; in force-device mode the
    prewarm raises."""
    ing = mk_ingest(warm='background', bypass_bytes=1)
    ing._compile = _broken_compile
    await asyncio.wait_for(ing.prewarm(4), timeout=10)
    assert list(ing._exec.values()) == [None]
    # traffic flows scalar through the latched bucket
    conn = FakeConn()
    ing.register(conn)
    await _enter_batch_regime(ing, conn)
    before = ing.ticks_scalar
    ing.feed(conn, reply_frame(-2))
    await drain()
    assert conn.delivered[-1][0][0]['opcode'] == 'PING'
    assert ing.ticks == 0 and ing.ticks_scalar == before + 1

    forced = mk_ingest(warm='background')
    forced._compile = _broken_compile
    with pytest.raises(RuntimeError, match='force-device'):
        await asyncio.wait_for(forced.prewarm(4), timeout=10)


def test_loop_closed_mid_compile_is_contained():
    """The background warm thread surviving its event loop: the
    call_soon_threadsafe on a closed loop raises RuntimeError, which
    must be swallowed (the process is shutting down; nothing to do).
    Sync test: it owns its own short-lived loop."""
    ing = mk_ingest(warm='background')
    release = threading.Event()
    done = threading.Event()

    def slow_compile(key):
        release.wait(10)
        done.set()
        return None

    ing._compile = slow_compile

    async def kick():
        ing._start_warm((False, 8, 256))

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(kick())
    finally:
        loop.close()          # close BEFORE the compile finishes
    release.set()
    assert done.wait(10)
    ing._warm_queue.join()    # daemon worker finished the task cleanly
    # the result could not be delivered: the bucket is still unwarmed
    assert ing._exec == {}


async def test_warming_tick_defers_scalar_then_flips_to_device():
    """warm='background' handoff: ticks before the compile lands drain
    scalar (counted as warming), and the ready callback re-schedules so
    queued bytes flow through the device program."""
    ing = mk_ingest(warm='background')
    conn = FakeConn()
    ing.register(conn)
    ing.feed(conn, reply_frame(-2))
    await drain()
    assert ing.ticks_warming == 1
    assert conn.delivered[0][0][0]['opcode'] == 'PING'
    # wait for the single bucket to finish compiling
    ev = next(iter(ing._warm_events.values()))
    await asyncio.wait_for(ev.wait(), timeout=30)
    ing.feed(conn, reply_frame(-2))
    await drain()
    assert ing.ticks == 1                  # device path engaged
    assert conn.delivered[1][0][0]['opcode'] == 'PING'


async def test_register_migrates_codec_residue():
    """BATCH regime: a partial steady-state frame that rode the same
    TCP segment as the handshake must migrate from the scalar decoder
    into the slot (the tick scan owns the stream), and complete once
    the rest arrives."""
    ing = mk_ingest()                      # bypass 0 -> batch regime
    conn = FakeConn()
    wire = reply_frame(-2)
    conn.codec.restore_pending(wire[:5])   # partial frame in the codec
    ing.register(conn)
    assert bytes(ing._slots[id(conn)][1]) == wire[:5]
    ing.feed(conn, wire[5:])
    await drain()
    assert conn.delivered[0][0][0]['opcode'] == 'PING'


async def test_register_direct_regime_leaves_residue_in_codec():
    """DIRECT regime (the shipped default at startup): the codec keeps
    draining the stream itself, so handshake-coincident residue must
    STAY in the codec — migrating it into a slot nothing drains would
    strand it and misframe every later byte (r4 regression: the
    connection died with BAD_LENGTH on the next read)."""
    ing = mk_ingest(bypass_bytes=16384)
    assert ing._direct
    conn = FakeConn()
    wire = reply_frame(-2) + reply_frame(-2)
    conn.codec.restore_pending(wire[:5])
    ing.register(conn)
    assert bytes(ing._slots[id(conn)][1]) == b''   # slot stays empty
    # the connection-side direct drain continues the partial frame
    # exactly where the codec left off
    pkts = conn.codec.decode(wire[5:])
    ing.note_direct(len(wire) - 5, len(pkts))
    assert [p['opcode'] for p in pkts] == ['PING', 'PING']
    await drain()
    assert ing.frames_routed == 2


async def test_feed_after_unregister_is_dropped():
    ing = mk_ingest()
    conn = FakeConn()
    ing.register(conn)
    ing.unregister(conn)
    ing.feed(conn, reply_frame(-2))        # raced a teardown: no slot
    await drain()
    assert conn.delivered == []


async def test_unregister_restores_pending_bytes_to_codec():
    ing = mk_ingest()
    conn = FakeConn()
    ing.register(conn)
    wire = reply_frame(-2)
    ing.feed(conn, wire[:5])
    ing.unregister(conn)
    # the closing state keeps draining through the codec
    pkts = conn.codec.decode(wire[5:])
    assert pkts[0]['opcode'] == 'PING'


#: stands in for an accelerator the CPU-only test host does not have
_FAKE_CHIP = types.SimpleNamespace(platform='tpu',
                                   device_kind='TPU v5 lite')


async def test_placement_host_pins_cpu_and_is_recorded():
    ing = mk_ingest(placement='host')
    assert ing.placed is None              # resolved lazily
    ing._resolve_placement()
    assert ing._device.platform == 'cpu'
    assert ing.placed == {'platform': 'cpu',
                          'device_kind': ing._device.device_kind,
                          'rtt_ms': None}


async def test_placement_accelerator_raises_on_cpu_only_backend():
    """'accelerator' means a chip: where JAX's default backend is the
    host CPU it raises — from the placement, and so from the first
    compile — instead of running 'accelerator' ticks on the CPU."""
    # a production byte threshold: the scalar drain is a legitimate
    # path there, but not for a placement that cannot be honoured
    ing = mk_ingest(placement='accelerator', bypass_bytes=1)
    with pytest.raises(RuntimeError, match='no accelerator'):
        ing._resolve_placement()
    assert ing.placed is None              # nothing latched
    with pytest.raises(RuntimeError, match='no accelerator'):
        await ing.prewarm(4)
    conn = FakeConn()
    ing.register(conn)
    await _enter_batch_regime(ing, conn)
    ing.feed(conn, reply_frame(-2))
    with pytest.raises(RuntimeError, match='no accelerator'):
        ing._tick()
    assert ing.ticks == 0


async def test_placement_accelerator_stays_on_the_chip():
    """'accelerator' records the measured round trip and never moves
    the ticks, whatever the budget."""
    ing = mk_ingest(placement='accelerator', latency_budget_ms=-1.0)
    ing._default_device = lambda: _FAKE_CHIP
    ing._probe_rtt_ms = lambda dev: 7.5
    ing._resolve_placement()
    assert ing._device is _FAKE_CHIP
    assert ing.placed == {'platform': 'tpu',
                          'device_kind': 'TPU v5 lite', 'rtt_ms': 7.5}


async def test_placement_auto_probes_and_moves_to_host():
    """placement='auto' on a non-CPU default backend measures the
    dispatch+readback RTT and pins ticks to the host CPU backend when
    it exceeds the budget — visibly: the resolved platform says cpu
    and the measured round trip stays on record."""
    ing = mk_ingest(placement='auto', latency_budget_ms=5.0)
    ing._default_device = lambda: _FAKE_CHIP
    ing._probe_rtt_ms = lambda dev: 69.8
    ing._resolve_placement()
    assert ing._device.platform == 'cpu'
    assert ing.placed['platform'] == 'cpu'
    assert ing.placed['rtt_ms'] == 69.8
    ing._probe_rtt_ms = lambda dev: 1 / 0  # resolved once, not again
    ing._resolve_placement()

    inside = mk_ingest(placement='auto', latency_budget_ms=5.0)
    inside._default_device = lambda: _FAKE_CHIP
    inside._probe_rtt_ms = lambda dev: 0.2
    inside._resolve_placement()
    assert inside._device is _FAKE_CHIP    # inside the budget: stays


async def test_placement_and_buckets_ride_bind_metrics():
    """The resolved placement and what each bucket compiled to are
    scrapeable series, not log lines."""
    from zkstream_tpu.utils.metrics import Collector

    ing = mk_ingest()
    col = Collector()
    ing.bind_metrics(col)
    await ing.prewarm(4)
    (info,) = ing.buckets.values()
    assert info['impl'] == 'jnp' and info['platform'] == 'cpu'
    assert info['error'] is None and info['compile_s'] > 0
    text = col.get_collector('zkstream_ingest_buckets').expose()
    assert 'zkstream_ingest_buckets{impl="jnp",platform="cpu"} 1' \
        in text
    text = col.get_collector(
        'zkstream_ingest_placement_rtt_ms').expose()
    assert 'platform="cpu"' in text and 'device_kind=' in text


async def test_unmatched_reply_xid_is_bad_decode():
    """A reply xid matching no request surfaces the same BAD_DECODE
    the scalar codec raises (framing.py parity)."""
    ing = mk_ingest()
    conn = FakeConn()
    ing.register(conn)
    ing.feed(conn, reply_frame(31337))     # nothing in xid_map
    await drain()
    pkts, err = conn.delivered[0]
    assert pkts == []
    assert isinstance(err, ZKProtocolError) and err.code == 'BAD_DECODE'
    assert 'matches no request' in str(err)


async def test_unsupported_reply_opcode_is_bad_decode():
    ing = mk_ingest()
    conn = FakeConn()
    conn.codec.xid_map[9] = 'SET_ACL'      # decodable header, no reader
    ing.register(conn)
    w = JuteWriter()
    w.write_struct(struct.Struct('>iqi'), 9, 7, 0)
    w.write_ustring('/x')
    ing.feed(conn, frame(w.to_bytes()))
    await drain()
    pkts, err = conn.delivered[0]
    assert pkts == []
    assert isinstance(err, ZKProtocolError) and err.code == 'BAD_DECODE'


async def test_ext_slice_failure_wraps_as_bad_decode():
    """The C fast path: a stream whose decode raised
    inside the extension (``decode_streams`` hands the exception back
    as that stream's error) becomes connection-level BAD_DECODE, not
    a raw crash."""
    ing = mk_ingest()
    conn = FakeConn()

    class BrokenExt:
        def decode_streams(self, bufs, lens, xid_maps, max_packet):
            n = len(bufs)
            return ([], [0] * n, [0] * n,
                    {i: MemoryError('injected') for i in range(n)},
                    (0, 0))

    conn.codec._ext = BrokenExt()
    ing.register(conn)
    ing.feed(conn, reply_frame(-2))
    await drain()
    pkts, err = conn.delivered[0]
    assert pkts == []
    assert isinstance(err, ZKProtocolError) and err.code == 'BAD_DECODE'
    assert 'MemoryError' in str(err)


async def test_bypass_scalar_error_delivers_prior_packets():
    """The small-tick bypass drains through the codec: a frame with an
    undecodable BODY mid-chunk delivers the packets before it plus the
    error (the scalar drain's contract, test_native_ext's
    bad-body case), and a bad LENGTH prefix surfaces BAD_LENGTH."""
    ing = mk_ingest(bypass_bytes=1 << 20)   # force the bypass path
    conn = FakeConn()
    ing.register(conn)
    # valid framing, body truncated mid-stat
    bad_body = struct.pack('>iqi', 2, 5, 0) + b'\x00' * 4
    conn.codec.xid_map[2] = 'EXISTS'
    ing.feed(conn, reply_frame(-2) + frame(bad_body))
    await drain()
    pkts, err = conn.delivered[0]
    assert [p['opcode'] for p in pkts] == ['PING']
    assert isinstance(err, ZKProtocolError) and err.code == 'BAD_DECODE'

    conn2 = FakeConn()
    ing.register(conn2)
    ing.feed(conn2, struct.pack('>i', -5))  # negative length prefix
    await drain()
    pkts, err = conn2.delivered[0]
    assert pkts == []
    assert isinstance(err, ZKProtocolError) and err.code == 'BAD_LENGTH'


async def test_teardown_mid_tick_skips_dead_connection():
    """A delivery callback tearing down ANOTHER connection mid-tick:
    the torn-down conn is skipped on every drain loop (bypass, warming,
    device) and its bytes returned to its codec."""
    for setup in ('bypass', 'warming', 'device'):
        ing = mk_ingest(
            bypass_bytes=(1 << 20) if setup == 'bypass' else 0,
            warm='background' if setup == 'warming' else 'block')
        if setup == 'device':
            await ing.prewarm(8)
        a, b = FakeConn(), FakeConn()
        ing.register(a)
        ing.register(b)

        def kill_b(_conn):
            ing.unregister(b)
        a.on_deliver = kill_b
        ing.feed(conn=a, data=reply_frame(-2))
        ing.feed(conn=b, data=reply_frame(-2))
        await drain()
        assert a.delivered and a.delivered[0][0][0]['opcode'] == 'PING'
        # b was skipped; its bytes went back to its codec intact
        assert b.delivered == [], setup
        assert id(b) not in ing._slots


@pytest.mark.parametrize('mesh', [False, True], ids=['fleet', 'mesh'])
def test_a_body_mode_other_than_host_is_refused(mesh):
    """The one mode left: bodies are parsed on the host.  The keyword
    stays for the benchmark's config files (ROADMAP D19); any other
    value says so at construction."""
    if mesh:
        from zkstream_tpu.parallel import MeshFleetIngest as cls
    else:
        cls = FleetIngest
    with pytest.raises(ValueError, match="body_mode='device'"):
        cls(body_mode='device')


async def test_fragmentation_guard_enters_and_exits():
    """The upper dispatch guard: a large fleet whose ticks are sparse
    routes to the scalar drain; when ticks become batches again the
    device path resumes — with hysteresis in between."""
    # the guard must be requested explicitly here: bypass_bytes=0
    # auto-disables it (force-device means force-device)
    ing = mk_ingest(frag_guard=True)   # bypass_bytes=0, warm='block'
    ing.FRAG_MIN_FLEET = 8        # scale the guard to a test fleet
    await ing.prewarm(8)
    conns = [FakeConn() for _ in range(8)]
    for c in conns:
        ing.register(c)

    # synchronized bursts: every conn delivers every tick -> device
    for _ in range(4):
        for c in conns:
            ing.feed(c, reply_frame(-2))
        await drain()
    assert ing.ticks >= 4 and ing.ticks_frag == 0

    # fragmented: one frame per tick over an 8-conn fleet -> the EMA
    # decays below FRAG_ENTER * 8 = 2 and the guard engages
    for i in range(16):
        ing.feed(conns[i % 8], reply_frame(-2))
        await drain()
    assert ing.ticks_frag > 0
    assert ing._frag_scalar
    frag_at = ing.ticks_frag
    # every frame still delivered, through whichever path
    assert ing.frames_routed == 4 * 8 + 16

    # batches return: EMA recovers past FRAG_EXIT * 8 and device
    # ticks resume (a couple of guarded ticks while the EMA climbs is
    # the hysteresis working)
    device_before = ing.ticks
    for _ in range(8):
        for c in conns:
            ing.feed(c, reply_frame(-2))
        await drain()
    assert not ing._frag_scalar
    assert ing.ticks_frag <= frag_at + 3
    assert ing.ticks > device_before     # device path resumed


async def test_direct_and_batch_regimes_deliver_identically():
    """Property: the SAME randomized feed pattern through a forced
    pass-through ingest and a forced batch ingest delivers identical
    packet sequences per connection — the regime machine is an
    execution-layout choice, never a semantics change."""
    import random

    rng = random.Random(2024)

    def traffic():
        out = []
        for i in range(40):
            kind = rng.random()
            if kind < 0.6:
                out.append(('frame', reply_frame(-2)))
            elif kind < 0.8:
                w = reply_frame(-1, 'NOTIFICATION', zxid=100 + i,
                                type='DATA_CHANGED',
                                state='SYNC_CONNECTED', path='/p%d' % i)
                out.append(('frame', w))
            else:
                out.append(('split', reply_frame(-2)))
        return out

    plan = traffic()

    async def run(ing):
        conns = [FakeConn() for _ in range(3)]
        for c in conns:
            ing.register(c)
        for j, (kind, wire) in enumerate(plan):
            c = conns[j % 3]
            if kind == 'split':      # byte-at-a-time partial feeds
                for off in range(0, len(wire), 5):
                    ing.feed(c, wire[off:off + 5])
                    await asyncio.sleep(0)
            else:
                ing.feed(c, wire)
            if j % 4 == 0:
                await drain()
        for _ in range(6):
            await drain()
        for c in conns:          # no regime may surface an error
            assert all(e is None for _pkts, e in c.delivered)
        return [[(p['opcode'], p.get('path'), p['zxid'])
                 for pkts, _e in c.delivered for p in pkts]
                for c in conns]

    direct = await run(mk_ingest(bypass_bytes=1 << 30))  # always direct
    batch = await run(mk_ingest(bypass_bytes=0))         # always batch
    assert direct == batch
    assert sum(len(x) for x in direct) == len(plan)


async def test_force_device_auto_disables_frag_guard():
    """bypass_bytes=0 promises every tick on the device pipeline
    (tests, benchmarks); under frag_guard auto (the default) that
    promise extends to the fragmentation guard: no caller has to pass
    frag_guard=False by hand."""
    assert mk_ingest().frag_guard is False          # bypass_bytes=0
    assert mk_ingest(frag_guard=True).frag_guard is True   # pinned
    assert FleetIngest().frag_guard is True         # production default
    assert FleetIngest(frag_guard=False).frag_guard is False


async def test_background_warm_thread_is_daemon():
    """The warm worker must be a daemon thread: a compile wedged on an
    unreachable accelerator backend (documented prewarm hazard) must
    not hang interpreter exit — which a ThreadPoolExecutor's
    non-daemon worker, joined by concurrent.futures atexit, would
    (r4 advisor finding)."""
    import threading

    ing = mk_ingest(warm='background')
    ev = ing._start_warm(ing._bucket(2, ing.min_len))
    warm = [t for t in threading.enumerate()
            if t.name == 'ingest-warm']
    assert warm and all(t.daemon for t in warm)
    await asyncio.wait_for(ev.wait(), 60)


async def test_close_releases_warm_worker_and_is_idempotent():
    """close() drains queued compiles FIFO, then the daemon worker
    exits; a second close is a no-op; an ingest that never warmed has
    nothing to release."""
    import threading

    mk_ingest().close()                  # never warmed: no-op

    # other suites' ingests may have parked warm workers of their own;
    # only the thread THIS ingest starts must exit on close
    before = {t for t in threading.enumerate()
              if t.name == 'ingest-warm'}
    ing = mk_ingest(warm='background')
    ev = ing._start_warm(ing._bucket(2, ing.min_len))
    await asyncio.wait_for(ev.wait(), 60)    # queued compile lands
    (mine,) = [t for t in threading.enumerate()
               if t.name == 'ingest-warm' and t not in before]
    ing.close()
    ing.close()                          # idempotent
    for _ in range(100):
        if not mine.is_alive():
            break
        await asyncio.sleep(0.05)
    else:
        raise AssertionError('warm worker survived close()')
