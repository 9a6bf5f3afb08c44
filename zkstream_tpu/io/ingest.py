"""Fleet ingest: the runtime consumer of the TPU wire-decode plane.

The reference drains every connection with its own scalar loop — bytes
-> frames -> header dispatch, once per socket
(lib/zk-streams.js:39-99, lib/connection-fsm.js:213-229).  This module
replaces that per-socket drain at fleet scale: N live connections
append their received bytes to per-connection accumulators, and a
per-event-loop-tick batcher pads those whose first frame is whole into
[B, L] tensors — one a **size class** present (below) — runs
:func:`zkstream_tpu.ops.pipeline.wire_pipeline_step`
in one device dispatch each, and routes the results back on host —
reply packets to each connection's pending-request futures via the
normal ``packet``/``process_reply`` path, notifications to the session
watcher engine.  Observable semantics are identical to the scalar
drain; the integration tests (tests/test_ingest.py) assert this over
hundreds of live connections.

Division of labor per tick:

- **device**: frame boundary scan, reply-header parse (xid/zxid/err),
  per-stream routing counts, bad-frame flags — the O(bytes) work;
- **host**: per-frame packet-dict assembly, and the route: a tick's
  streams are delivered as one batch (:meth:`FleetIngest._route_batch`),
  each to its connection's direct settle lane.  A reply's BODY is
  parsed here and nowhere else: the packets come from the C-extension
  decoder when it is loaded (ONE call a tick over every stream's
  device-delimited complete-frame slice — byte-identical to the scalar
  drain because it *is* the scalar decoder), else from the scalar
  readers positioned at the device-located body offsets.

Streams flagged ``bad`` by the device scan re-run through the
connection's own ``PacketCodec`` so the error surfaced (BAD_LENGTH /
BAD_DECODE, with pre-error packets attached) matches the scalar path
exactly.

The tick is synchronous inside the event loop: all ``data_received``
callbacks of one select cycle run before the ``call_soon``-scheduled
tick, so one tick coalesces everything the loop just read.

How bytes reach a slot: :meth:`FleetIngest.feed`, a call a delivery —
or, where the loop's shared client tier's receiver thread reads the
connection and a delivery would do nothing but that append
(:attr:`FleetIngest.sinkable`, and the connection's own conditions:
io/connection.py ``resink``), the tier's reap appends to the slot's
bytearray itself, in its one C call, and tells the ingest once a reap
(:meth:`FleetIngest.fed`): the window's bytes, the tick, the early
dispatch.  The slots do not know which it was.

**The early dispatch.**  A tick has two halves — build the batches and
dispatch them (phases ``batch`` and ``dispatch``), then read the
results back and route them (``readback`` and ``route``) — and between
them the device computes while the loop has nothing of the tick's to
do.  So the first half runs as soon as a loop iteration's bytes are
all in their slots and no batch is in flight: at the end of the
receive reap that fed them (io/transport.py, ``after_reap``: the
loop's shared client tier on ``mmsg``), or, for a follow-up tick (a
slot that held more than it gave, a full tick, the frame bound, a
withheld suffix released), at the end of the tick before.  The second
half is the ``call_soon``-scheduled tick, which stands in the loop's
queue behind what was ready when the bytes came — the tier's flush,
the awaiters the last route woke and their next submits — and so
finds the results on the host; where nothing else was ready it runs
at once and waits, as a tick always did.  Where asyncio's protocol
push delivers (no reap marks the end of an iteration's bytes) the
scheduled tick runs both halves together.  Every dispatch asks for
its results' copy to the host at once (``copy_to_host_async``), so a
readback that comes later than the device's answer finds them there
and pays no round trip.  At most one batch is in
flight: bytes that arrive behind it stay in their slots for the next
one, and the batch memory is not touched before the route has read
the results.  Every decision a tick makes (the regime and its flip,
the size classes, the slot that waits, the injector's faults, a
bucket still compiling) is made by the same code, at the dispatch;
what is routed, and in what order, does not depend on which moment
that was.  ``ticks_early`` counts the device ticks dispatched ahead of
their tick, beside ``ticks``.

**Size classes.**  A tick's rows are dispatched by width: a row's
class is the power of two that holds the bytes it gives the tick, from
``min_len`` up to the one that holds a frame at the 16 MiB cap, and
each class present is a dispatch of its own (``[Bp, L]`` through
:meth:`FleetIngest._bucket`), so the bytes a tick pads, sends and
scans follow the bytes it routes — under four times them in every
class wider than ``min_len`` — and not ``rows x longest row``.  One
dispatch holds at most :attr:`FleetIngest.DISPATCH_BYTES` padded
bytes; a class's further rows go to the next dispatch of the same
tick; a tick's dispatches are in flight together and hold at most
:attr:`FleetIngest.TICK_BYTES` (what does not fit waits for the
follow-up tick).  A slot
whose buffered bytes do not yet hold its first frame whole waits (the
host reads that frame's 4-byte length prefix and nothing else: the
frame scan stays on the device), so a large reply that arrives over
many reads is given to a tick once.  A stream is in one dispatch a
tick.  With one class present — every tick of a fleet of small
replies — the tick is one dispatch, as it was before there were
classes.

**What a slot gives a tick.**  The tick program reads, of every frame,
its length prefix and the 16 header bytes behind it (ops/frame_scan.py,
ops/headers.py), and compares cursors with the row's ``lens``; the
bodies are decoded on the host, from the slot.  So a slot whose bytes
are EXACTLY one whole frame wider than ``min_len`` — what the prefix
the host reads anyway says, and what every slot of a fleet with one
request outstanding holds — gives a **header row**: the frame's first
``min_len`` bytes, under a ``lens`` entry that is the frame's TRUE
length.  The scan's first step finds the frame whole (``4 + ln <=
lens``), emits its start and size and moves the cursor to its end,
where no further step finds a prefix; the header parse gathers bytes
4..19; ``resid`` is the frame's length: plane for plane what the
frame's full-width row gives (tests/test_pallas.py holds both
implementations to it), and the row stands in the ``min_len`` class
whatever the frame's size — a tick of large replies is ONE narrow
dispatch.  What stops is copying and sending bytes that no device op
reads; the device still delimits every frame and parses every header of
every tick.  Any other slot gives its bytes to their class as above: a
slot that holds MORE than its first frame (a pipelined session's run of
replies, a notification in front of a reply) — what lies behind the
first frame is unknown until the device has scanned it — a frame of at
most ``min_len``, a prefix no frame can have.  The code decides by what
it finds in the slot; nothing is set.  A tick's header rows join its
``min_len`` dispatch; a tick whose other rows all stand in wider classes
lets them ride the narrowest dispatch it has (a header row fits any
width) unless that would pad it by more than
:attr:`FleetIngest.RIDE_BYTES`, where a ``min_len`` dispatch of their
own is cheaper.  ``rows_headed`` counts the header rows,
``bytes_kept_home`` the bytes of their frames that stayed in the slots;
``bytes_batched`` is, as before, what was copied into rows, and
``bytes_dispatched`` the ``Bp x L`` that crossed the link.

**No tick ever blocks on XLA.**  Compiling the tick program for a new
(batch, length) bucket costs ~1 s on the host CPU backend — 3 orders
of magnitude over a steady tick — and the placement probe costs
several dispatch round trips.  Both therefore
run off-loop: under the default ``warm='background'`` a tick whose
shape bucket has no compiled executable yet is delivered through the
scalar codec (identical semantics) while a daemon thread AOT-compiles
the bucket (``jit(...).lower(...).compile()``); once it lands,
subsequent ticks run the device program.  ``warm='block'`` compiles
inline on first use — deterministic, for tests and one-shot tools —
and :meth:`prewarm` lets benchmarks/servers pay the compile up front.
This is what bounds the ingest latency tail: the worst tick costs
max(scalar drain, steady device tick), never a compile.

**Every diversion is visible.**  A tick that did not run the device
program is counted by the path it took (``ticks_scalar`` /
``ticks_warming`` / ``ticks_frag``); where the ticks run
(:attr:`FleetIngest.placed`) and what each shape bucket compiled to
(:attr:`FleetIngest.buckets`) are attributes that ride
:meth:`FleetIngest.bind_metrics`.  In force-device mode
(``bypass_bytes=0``) nothing diverts: a bucket that fails to compile
raises.
"""

from __future__ import annotations

import asyncio
import queue
import struct
import threading
import time
from typing import TYPE_CHECKING

import numpy as np

from ..protocol.consts import MAX_PACKET, REPLY_HDR, SPECIAL_XIDS, err_name
from ..protocol.errors import ZKProtocolError
from ..protocol.jute import JuteReader
from ..protocol.records import (
    _EMPTY_RESPONSES,
    _RESP_READERS,
)
from ..utils import alloc
from ..utils.logging import Logger
from ..utils.metrics import TICK_BUCKETS, Histogram
from ..utils.trace import NO_SPAN, host_span
from .transport import after_reap

if TYPE_CHECKING:  # pragma: no cover - typing only
    from .connection import ZKConnection  # noqa: quoted annotations

def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


#: sentinel distinguishing "never compiled" from "compile failed" in
#: the executable cache
_MISSING = object()

#: a frame's 4-byte length prefix, and the most bytes a frame has with it
_PREFIX = struct.Struct('>I').unpack_from
_FRAME_TOP = MAX_PACKET + 4
#: the bytes of a frame the tick program reads: its length prefix and
#: the reply header behind it
_HEAD = 4 + REPLY_HDR

METRIC_INGEST_PHASE = 'zkstream_ingest_phase_ms'
_PHASE_HELP = ('Device tick time by phase, milliseconds (batch: find the '
               'slots that hold bytes, build [Bp, L] | dispatch: the '
               'executable call, H2D + enqueue | readback: device wait + '
               'D2H | route: unpack, assemble, deliver)')
#: the label sets of ``zkstream_ingest_phase_ms``, built once
_PHASE_LABELS = tuple({'phase': p} for p in
                      ('batch', 'dispatch', 'readback', 'route'))


class _Flight:
    """A device tick between its two halves: dispatched, not yet read
    back.  ``tick`` its number, ``plans`` its dispatches
    (:meth:`FleetIngest._prepare_batch`) and ``outs`` their results on
    the device, ``before`` the ingest's ``frames_routed`` when the
    tick began, ``times`` when phases ``batch`` and ``dispatch``
    opened and when the last dispatch returned; ``early_ms`` the loop
    time the first half took and ``fields`` what it noted for the
    ``ingest.tick`` span, where it ran ahead of its tick (else 0.0 and
    None)."""

    __slots__ = ('tick', 'plans', 'outs', 'before', 'times',
                 'early_ms', 'fields')

    def __init__(self, tick, plans, outs, before, times):
        self.tick = tick
        self.plans = plans
        self.outs = outs
        self.before = before
        self.times = times
        self.early_ms = 0.0
        self.fields = None


def _executable_platform(ex) -> str | None:
    """The platform(s) a compiled tick program's inputs live on, as
    the executable itself reports them ('tpu', 'cpu', ...)."""
    shardings = getattr(ex, 'input_shardings', None)
    if shardings is None:
        return None
    return ','.join(sorted({d.platform for sh in shardings[0]
                            for d in sh.device_set}))


def _guard_warm_exit(thread: threading.Thread, q: queue.Queue) -> None:
    """Interpreter-exit guard for one warm worker.  A compile's lazy
    ``import jax`` racing jax's own atexit cache teardown in the main
    thread leaves jax half-imported while ``clear_caches`` walks it —
    observed as a segfault/abort at process exit the first time a
    server spun up LATE in a run (e.g. a member added by a runtime
    reconfiguration) queues its first bucket compile just before the
    CLI returns.  ``threading._register_atexit`` callbacks run at
    threading shutdown, BEFORE the atexit module's handlers — so
    before jax's — where a BOUNDED join lets an in-flight compile
    finish while a wedged one still cannot hang exit (the worker
    stays a daemon).  Plain atexit is the (weaker) fallback when the
    private hook is missing."""
    def _drain_and_join() -> None:
        q.put(None)
        thread.join(timeout=30.0)
    reg = getattr(threading, '_register_atexit', None)
    if reg is not None:
        try:
            reg(_drain_and_join)
            return
        except RuntimeError:    # already shutting down: nothing to do
            return
    import atexit
    atexit.register(_drain_and_join)


class FleetIngest:
    """Batches the byte streams of many live connections through the
    device wire pipeline, one dispatch per event-loop tick.

    Args:
      max_frames: static per-stream frame bound per tick; streams with
        more complete frames buffered are finished on follow-up ticks.
      body_mode / max_data: inert; the ``ingest`` blocks of
        ``benchmark/configs/*.json`` pass them (ROADMAP D19).  Any
        ``body_mode`` but ``'host'`` raises ``ValueError``.
      min_len: smallest padded stream length, to bound jit cache churn.
      warm: ``'background'`` (default) — a tick whose shape bucket is
        not compiled yet delivers through the scalar codec while the
        XLA program compiles on a daemon thread, so the event loop
        never blocks on a compile; ``'block'`` — compile inline on
        first use (deterministic; tests/tools).
      frag_guard: route fragmented mega-fleet ticks back to the scalar
        drain (see the attribute comment below).  Default ``None`` =
        auto: enabled for production thresholds, disabled when
        ``bypass_bytes=0`` (force-device: tests, benchmarks — "every
        tick on the device pipeline" must mean exactly that).  Pass
        ``True``/``False`` to pin it either way; the mesh proxy
        disables it.
      log: parent logger.
    """

    #: Fragmentation-guard calibration (fitted on a host-CPU backend
    #: at 1,024 connections, never on the chip: ROADMAP D2): engage
    #: only for fleets at least this large...
    FRAG_MIN_FLEET = 600
    #: ...entering scalar routing when the frames-per-tick EMA drops
    #: below ENTER x fleet size (ticks stopped being batches), leaving
    #: it again above EXIT x fleet size (hysteresis so the router
    #: cannot flap on tick-to-tick noise).
    FRAG_ENTER = 0.25
    FRAG_EXIT = 0.40

    #: The padded bytes (``Bp x L``) one dispatch may hold; the rows of
    #: a size class beyond it go to the next dispatch of the same
    #: tick.  Only a single row wider than this (a frame near the
    #: 16 MiB cap) makes a larger dispatch.
    DISPATCH_BYTES = 16 << 20
    #: The padded bytes ONE TICK may dispatch: its batches are laid
    #: side by side in one buffer of this size that every tick uses
    #: again (no allocation and no zeroing a tick: the scan uses
    #: nothing beyond the bytes a row was given, so what an earlier
    #: tick left in the padding is never looked at), all of them are in flight
    #: together, so the device holds this much + the packed results,
    #: and what does not fit waits in its slots for the follow-up tick.
    TICK_BYTES = 4 * DISPATCH_BYTES
    #: The padded bytes a tick's header rows may ADD to a wider
    #: dispatch they ride (a tick with no ``min_len`` dispatch of its
    #: own): a dispatch costs the loop ~0.7 ms whatever it carries and
    #: the link moves ~2.5 GB/s (PERF.md), so beyond ~1 MiB of padding
    #: a ``min_len`` dispatch of their own is the cheaper one.
    RIDE_BYTES = 1 << 20

    #: always 0: ``benchmark/harness.py`` reads it (``INGEST_COUNTERS``)
    body_fallbacks = 0

    def __init__(self, max_frames: int = 32, body_mode: str = 'host',
                 max_data: int = 256,
                 min_len: int = 256, placement: str = 'auto',
                 latency_budget_ms: float = 5.0,
                 bypass_bytes: int = 16384,
                 warm: str = 'background',
                 frag_guard: bool | None = None,
                 log: Logger | None = None):
        # ``body_mode`` and ``max_data``: said by the ``ingest`` blocks of
        # benchmark/configs/*.json; nothing reads them
        if body_mode != 'host':
            raise ValueError(
                'FleetIngest(body_mode=%r): reply bodies are parsed on '
                "the host; 'host' is the only mode" % (body_mode,))
        assert placement in ('auto', 'accelerator', 'host'), placement
        assert warm in ('background', 'block'), warm
        self.max_frames = max_frames
        self.min_len = min_len
        self.warm = warm
        #: Small-tick crossover: while the fleet's bytes-per-tick EMA
        #: sits under this threshold, the ingest runs as a PASS-THROUGH
        #: — ``feed`` delivers straight through each connection's own
        #: scalar codec (C-accelerated when built), no accumulator, no
        #: deferred tick — identical observable semantics, the scalar
        #: path being the spec, and none of the batching overhead the
        #: r4 re-sweep measured costing 10-24% when the old design
        #: still accumulated + tick-drained in this regime.  0 forces
        #: every tick onto the device pipeline (tests, benchmarks) —
        #: including disabling the fragmentation guard, which would
        #: otherwise still divert >=600-connection fragmented fleets
        #: to the scalar drain.  Default 16 KiB (~128 connections x
        #: ~135 B frames) is where the two paths met on a host-CPU
        #: backend; the chip has not re-fitted it (ROADMAP D2).
        self.bypass_bytes = bypass_bytes
        #: Where the tick's XLA program runs.  A tick is latency-bound
        #: (one dispatch + one readback inside the event loop), so
        #: 'auto' probes the default accelerator's dispatch->readback
        #: round trip once and moves the ticks to the host CPU backend
        #: when it exceeds ``latency_budget_ms``.  'accelerator' never
        #: moves them and raises where the default backend is itself
        #: the host CPU; 'host' always runs them on the CPU backend.
        self.placement = placement
        self.latency_budget_ms = latency_budget_ms
        self._device = None        # resolved lazily at first warm
        #: The resolved placement, None until the first warm-up:
        #: ``{'platform', 'device_kind', 'rtt_ms'}`` — the device the
        #: tick programs compile for and the measured dispatch+readback
        #: round trip of the default accelerator (None where the
        #: default backend is the CPU: nothing to measure against).
        self.placed: dict | None = None
        self._place_lock = threading.Lock()
        self.log = (log or Logger()).child(component='FleetIngest')
        #: id(conn) -> (conn, accumulator, lane): ``lane`` is the
        #: connection's direct settle lane (:meth:`register`), None
        #: for one that takes its packets as 'ingestDeliver' events
        self._slots: dict[int, tuple] = {}
        #: id(conn) -> the ``sink`` its connection registered with
        #: (:meth:`register`): called with the slot's accumulator while
        #: received bytes may be appended to it from outside
        #: (:attr:`sinkable`), with None when that ends
        self._sinks: dict[int, object] = {}
        # the process that holds a fleet's sessions makes and frees a
        # ``bytes`` a reply body, ~1 MB each in a herd of large
        # re-reads: keep them for the next tick (utils/alloc.py)
        alloc.keep_freed_memory()
        # ...and a device tick allocates a reply dict and a Stat a
        # frame in one burst: the collector's young generation follows
        # the requests the fleet has alive (utils/alloc.py) — its
        # registered slots, and where its clients pipeline what is
        # pending on them (:meth:`_fit_collector`) — from here to
        # close().  ``_gc_slots``: the slots when they last doubled or
        # halved; ``_gc_fit``: what the collector is sized to now;
        # ``_gc_look``: a tick that routes more frames than this looks
        # at the requests pending again.
        self._gc_slots = self._gc_fit = self._gc_look = 0
        self._closed = False
        alloc.fit_collector(self, 0)
        #: Profiler sessions only (empty outside one): id(conn) ->
        #: ``[[end, t_ns], ...]``, one mark a receive call that fed the
        #: slot — the slot's length once the call's bytes were in it,
        #: and the call's start on ``time.perf_counter_ns`` — so the
        #: route can hand every reply the call that brought ITS last
        #: byte (:meth:`_reply_times`), however many replies of one
        #: connection came in how many calls before one tick.
        self._rx_marks: dict[int, list] = {}
        #: a tick is queued on the loop / something came up that the
        #: next tick's first half must look at (bytes fed, a follow-up)
        self._scheduled = False
        self._due = False
        #: the device tick that is dispatched and not yet routed
        self._flight: _Flight | None = None
        #: this ingest's early dispatch stands in the running reap's
        #: ``after_reap`` list
        self._asked = False
        #: diagnostics for tests/benchmarks (``ticks`` counts device
        #: ticks; small ticks under ``bypass_bytes`` and ticks deferred
        #: to the scalar drain while a shape bucket compiles count
        #: separately)
        self.ticks = 0
        #: of ``ticks``, those whose batches were dispatched ahead of
        #: their tick (at the end of the reap that fed them, or of the
        #: tick before): the rest dispatched and waited in one call
        self.ticks_early = 0
        self.ticks_scalar = 0
        self.ticks_warming = 0
        #: While a device tick's route runs, its number (the ``tick``
        #: of its ``ingest.tick`` host span and that span's four
        #: phases); None otherwise.  A profiler session stamps it on
        #: the span of every op the route settles
        #: (``ZKConnection.rx_mark``): what joins an op to the tick
        #: that delivered it.
        self.routing: int | None = None
        #: Batched-drain latency distribution: wall time of each tick
        #: that routed work (device dispatch or scalar drain), ms.
        #: Standalone until bind_metrics() swaps in a collector-
        #: registered histogram at setup time.
        self.tick_hist = Histogram(
            'zkstream_ingest_tick_ms',
            'Ingest tick (batched drain) duration, milliseconds')
        #: Where a DEVICE tick's wall time went, one observation per
        #: phase per device tick (so each series counts ``ticks``):
        #: the operator's always-on view of the boundaries the host
        #: spans ``ingest.batch|dispatch|readback|route`` mark in a
        #: profiler session.  Swapped by bind_metrics like tick_hist.
        self.phase_hist = Histogram(METRIC_INGEST_PHASE, _PHASE_HELP,
                                    buckets=TICK_BUCKETS)
        #: ticks routed to the scalar drain by the fragmentation guard
        self.ticks_frag = 0
        self.frames_routed = 0
        #: What the device ticks moved: dispatches made (one a size
        #: class present a tick, more where a class outgrew
        #: ``DISPATCH_BYTES``), the stream bytes copied into their
        #: batches, the padded bytes those batches held (``Bp x L``
        #: summed), the bytes copied that the tick did not consume (a
        #: partial frame behind whole ones: they are copied again), and
        #: the slot-ticks a slot sat out because its first frame was
        #: not whole yet.
        self.dispatches = 0
        self.bytes_batched = 0
        self.bytes_dispatched = 0
        self.bytes_recopied = 0
        self.slots_deferred = 0
        #: The header rows the device ticks were given (a slot that
        #: held exactly one whole frame wider than ``min_len``: the
        #: row is the frame's first ``min_len`` bytes under its true
        #: length), and the bytes of those frames that were not copied
        #: and did not cross the link: no device op reads them.
        self.rows_headed = 0
        self.bytes_kept_home = 0
        #: What a fleet whose clients pipeline meets, and one request
        #: a session never does: streams that gave a tick the whole
        #: frame bound (``max_frames`` frames from one row: the scan's
        #: every cursor step found one), slots that held more than the
        #: tick took of them (the power of two over ``max_frames`` x
        #: their first frame), and the device ticks that scheduled a
        #: follow-up because of either — a stream at the bound with
        #: bytes left behind it, or a cut slot.
        self.slots_bound = 0
        self.slots_cut = 0
        self.reticks = 0
        #: the device tick being built or in flight has a follow-up
        #: scheduled for a cut or the bound (``reticks`` counts it when
        #: it has routed)
        self._retick = False
        #: device ticks that left whole frames in their slots for the
        #: follow-up tick because the tick's batch memory
        #: (``TICK_BYTES``) was full
        self.ticks_full = 0
        #: names the routed children lists held (GET_CHILDREN /
        #: GET_CHILDREN2 replies of the device ticks): what a fleet
        #: that watches wide directories pays the list parse and its
        #: listeners for, a ``str`` each
        self.names_routed = 0
        #: those lists, and how many of them the tick's one C decode
        #: did not parse again: a herd's re-lists of ONE path in ONE
        #: state are byte-equal, and ``decode_streams`` hands every
        #: asker after the first its own list of the SAME ``str``
        #: objects (0 without the extension)
        self.lists_routed = 0
        self.lists_shared = 0
        #: Upper dispatch guard: when a large fleet's connections
        #: desynchronize, the tick batches fragment (a small share of
        #: the slots hold a frame) and the per-socket drain is the
        #: cheaper path — a regime the byte threshold cannot see,
        #: because fragmented mega-fleets still clear 16 KiB/tick.
        #: An EMA of frames routed per tick, compared against the
        #: registered fleet size with hysteresis, routes those ticks
        #: back to the scalar drain.  Auto (None): enabled only with a
        #: production byte threshold — ``bypass_bytes=0`` (force-device:
        #: tests, benchmarks) must mean every tick on the device
        #: pipeline, so auto disables the guard there.
        self.frag_guard = (bypass_bytes > 0 if frag_guard is None
                           else frag_guard)
        self._ema_frames: float | None = None
        self._frag_scalar = False
        #: Regime flag: in DIRECT mode ``feed`` delivers through the
        #: connection's own codec immediately — the per-socket scalar
        #: drain itself, zero accumulate/copy/defer overhead — because
        #: the dispatch policy says batching does not pay (bytes/tick
        #: under ``bypass_bytes``, or the fragmentation guard).  In
        #: BATCH mode bytes accumulate per slot and the tick
        #: dispatches the device program.  The r4 re-sweep measured
        #: the old design (accumulate + per-tick scalar drain even
        #: when bypassing) costing 10-24% vs the native drain — a
        #: replacement may never regress the drain it replaces, so the
        #: bypass is now a true pass-through.
        self._direct = bypass_bytes > 0
        self._window_bytes = 0
        self._ema_bytes: float | None = None
        self._frames_mark = 0
        self._fn = None
        #: (False, Bp, L) -> AOT executable (None = compile
        #: failed; that bucket stays on the scalar drain, or raises in
        #: force-device mode)
        self._exec: dict = {}
        #: (False, Bp, L) -> what that bucket compiled to:
        #: ``{'impl', 'platform', 'compile_s', 'error'}`` — ``impl`` is
        #: the header-scan implementation the trace chose ('pallas' |
        #: 'jnp'), ``platform`` where the executable lives as the
        #: executable itself reports it, ``error`` the compile failure
        #: (None when it compiled).
        self.buckets: dict = {}
        self._traced_impl: str | None = None
        #: the ticks' batch memory (``TICK_BYTES``), made by the first
        #: device tick
        self._arena = None
        #: one compile at a time (``_traced_impl`` is the trace's note
        #: to the bucket being compiled): a deployment's wide classes
        #: may be prewarmed from one thread while the fleet's narrow
        #: buckets are from another
        self._compile_lock = threading.Lock()
        self._warm_events: dict = {}
        #: background compiles drain FIFO through a one-thread
        #: executor (created lazily): a load pattern hopping several
        #: (Bp, L) buckets at once must not stack ~1 s XLA compiles
        #: concurrently on the host that is also serving scalar ticks
        self._warm_queue: queue.Queue | None = None
        #: Optional seeded FaultInjector (io/faults.py): tick-time
        #: faults in the BATCH regime — a slot's buffered suffix held
        #: back across a tick boundary (the device scan must handle a
        #: partial frame at an arbitrary cut and finish it next tick)
        #: or a connection reset at tick time (teardown mid-batch:
        #: unregister/restore_pending while other streams route).  In
        #: the pass-through regime the per-connection rx gate already
        #: owns byte-level faults — the drain there IS the scalar
        #: codec — so these hooks fire only on the batched tick.
        self._faults = None
        #: id(conn) -> bytes withheld from the current tick by the
        #: injector; re-appended after the tick routes (FIFO: the
        #: suffix of a slot goes back to the same position).
        self._held: dict[int, bytes] = {}
        #: slots whose withheld suffix was just released: exempt from
        #: a fresh hold for one tick, so the follow-up tick finishes
        #: the partial frame instead of re-cutting the same bytes in
        #: a busy loop until new data arrives
        self._no_hold: set[int] = set()

    @property
    def faults(self):
        return self._faults

    @faults.setter
    def faults(self, injector) -> None:
        # an injector cuts and resets at tick time what ``feed`` put
        # in the slots and what it holds back (``_held``): every byte
        # comes through ``feed`` while one stands
        self._faults = injector
        self._sink_all(self.sinkable)

    @property
    def sinkable(self) -> bool:
        """May a connection's received bytes be appended to its slot
        from outside (:meth:`fed`)?  Where :meth:`feed` itself would do
        that append and nothing else: the batch regime, no injector."""
        return not self._direct and self._faults is None

    def _sink_all(self, on: bool) -> None:
        """Tell every connection that brought a ``sink`` whether its
        slot takes bytes from outside from now on."""
        for cid, sink in list(self._sinks.items()):
            slot = self._slots.get(cid)
            if slot is not None:
                sink(slot[1] if on else None)

    # -- connection registry --

    def register(self, conn: 'ZKConnection', lane=None,
                 sink=None) -> None:
        """Give ``conn`` a slot until :meth:`unregister`.  ``lane`` is
        the one callable its state ``connected`` hands over
        (io/connection.py): ``lane(pkts, err, now, times) -> int``
        takes a routed stream's packets, its decode error if any, the
        tick's ``time.monotonic()`` and — inside a profiler session,
        else None — for each packet the start of the receive call that
        brought its last byte (:meth:`_reply_times`), delivers them in
        stream order and returns how many it settled itself.  The slot
        holds it and
        ``unregister`` (the state's exit) drops it, so it is never
        called outside that state.  Without one the stream goes out
        as the connection's ``'ingestDeliver'`` event, as the scalar,
        fallback and pass-through deliveries always do.

        ``sink`` is the connection's other callable, ``sink(buf)``:
        called with the slot's accumulator (whose identity never
        changes: it is extended, cleared and cut in place) whenever
        what receives for the connection may append to it directly and
        tell the ingest once a batch (:meth:`fed`) instead of calling
        :meth:`feed` a delivery — :attr:`sinkable`: here, and when the
        regime returns to batch or an injector leaves — and with None
        when it may not: the pass-through flip, an injector, and
        :meth:`unregister`."""
        slot = self._slots.setdefault(id(conn),
                                      (conn, bytearray(), lane))
        if sink is not None:
            self._sinks[id(conn)] = sink
            if self.sinkable:
                sink(slot[1])
        # A partial steady-state frame may have ridden the same TCP
        # segment as the ConnectResponse.  In the BATCH regime it must
        # migrate out of the scalar decoder into the slot (the tick
        # scan owns the stream).  In the DIRECT regime the codec keeps
        # draining the stream itself, so the residue must STAY there —
        # moving it into a slot nothing drains would strand it and
        # misframe every later byte.
        if not self._direct and conn.codec is not None:
            resid = conn.codec.take_pending()
            if resid:
                slot[1].extend(resid)
                self._schedule()
        self._fit_collector()

    def _fit_collector(self, routed: int = 0) -> None:
        """Size the collector's young generation to the requests this
        fleet has alive, when that may have changed by a factor of
        two.  The registered slots doubled or halved since it was
        sized: to the slots — a frame a slot is the most a tick's
        burst holds while every session keeps one request outstanding.
        A tick routed ``routed`` frames, more than any look has found
        alive: the clients pipeline, and the requests alive are what
        is pending on the slots' connections and what that tick just
        settled; sized to them once they are twice what stands.  It
        grows with the traffic and comes down with the slots."""
        n, was = len(self._slots), self._gc_slots
        if self._closed:
            return
        if (n >= 2 * was or 2 * n <= was) and n != was:
            self._gc_slots = self._gc_fit = self._gc_look = n
            alloc.fit_collector(self, n)
        elif routed > self._gc_look:
            alive = self._gc_look = routed + sum(
                len(getattr(slot[0], 'reqs', ()))
                for slot in self._slots.values())
            if alive >= 2 * self._gc_fit:
                self._gc_fit = alive
                alloc.fit_collector(self, alive)

    def unregister(self, conn: 'ZKConnection') -> None:
        sink = self._sinks.pop(id(conn), None)
        if sink is not None:
            sink(None)      # nothing lands in the slot from here on
        slot = self._slots.pop(id(conn), None)
        self._fit_collector()
        self._no_hold.discard(id(conn))
        self._rx_marks.pop(id(conn), None)
        held = self._held.pop(id(conn), None)
        if held is not None and slot is not None:
            slot[1].extend(held)     # withheld suffix rejoins in order
        # Return unprocessed bytes to the scalar decoder: the closing
        # state keeps draining replies through the codec.
        if slot is not None and slot[1] and conn.codec is not None:
            conn.codec.restore_pending(bytes(slot[1]))

    def feed(self, conn: 'ZKConnection', data: bytes,
             t_rx: int = 0) -> None:
        """``conn`` received ``data``.  ``t_rx``: inside a profiler
        session, the start of the receive call that brought it (the
        connection's stage stamp, ``time.perf_counter_ns``); else 0."""
        slot = self._slots.get(id(conn))
        if slot is None:  # raced a teardown; the bytes die with the conn
            return
        self._window_bytes += len(data)
        if self._direct:
            self._schedule()          # bookkeeping tick at cycle end
            if slot[1]:               # leftover from a regime flip
                slot[1].extend(data)
                data = bytes(slot[1])
                slot[1].clear()
            self._deliver_direct(conn, data)
            return
        if self._held and id(conn) in self._held:
            # behind the suffix an early dispatch's injector withheld
            # (it rejoins the slot when that tick has routed)
            self._held[id(conn)] += data
        else:
            slot[1].extend(data)
            if t_rx:
                self._rx_marks.setdefault(id(conn), []).append(
                    [len(slot[1]), t_rx])
            elif self._rx_marks:
                self._rx_marks.clear()      # the session is over
        self._schedule()
        if not self._asked:
            # fed by a reap: the batch is dispatched when the reap has
            # fed the last of its connections
            self._asked = after_reap(self._reaped)

    def fed(self, nbytes: int, t_rx: int = 0) -> None:
        """A reap of the transport tier appended ``nbytes`` to the
        slots of connections whose sink stands (:meth:`register`;
        io/transport.py ``rx_sink``): :meth:`feed`'s plain branch for
        all of them, once a reap and not once a connection.  ``t_rx``:
        inside a profiler session the start of that reap's receive
        call, after :meth:`fed_mark` stamped each of them; else 0."""
        self._window_bytes += nbytes
        if not t_rx and self._rx_marks:
            self._rx_marks.clear()          # the session is over
        self._schedule()
        if not self._asked:
            self._asked = after_reap(self._reaped)

    def fed_mark(self, conn: 'ZKConnection', end: int,
                 t_rx: int) -> None:
        """Profiler sessions only: the receive call that began at
        ``t_rx`` fed ``conn``'s slot through its sink, which holds
        ``end`` bytes with them — what ``ZKConnection._sock_data`` and
        :meth:`feed` stamp a delivery: the connection's ``_rx_t0``,
        the slot's mark."""
        conn._rx_t0 = t_rx
        marks = self._rx_marks.get(id(conn))
        if marks is None:
            self._rx_marks[id(conn)] = [[end, t_rx]]
        else:
            marks.append([end, t_rx])

    @property
    def direct(self) -> bool:
        """True while the ingest is in its pass-through regime: the
        connection should run the per-socket drain itself and report
        the counts via :meth:`note_direct` (io/connection.py wires
        this)."""
        return self._direct

    def note_direct(self, nbytes: int, nframes: int) -> None:
        """Bookkeeping for a connection-side direct delivery: feeds
        the dispatch policy's byte/frame EMAs and schedules the
        regime-decision tick."""
        self._window_bytes += nbytes
        self.frames_routed += nframes
        self._schedule()

    def _deliver_direct(self, conn: 'ZKConnection',
                        data: bytes) -> None:
        """The pass-through drain: decode straight through the
        connection's codec (which keeps its own partial-frame state
        across feeds, exactly like the per-socket scalar drain) and
        emit.  No accumulator, no copy, no deferred tick."""
        err = None
        try:
            pkts = conn.codec.decode(data)
        except ZKProtocolError as e:
            pkts = getattr(e, 'packets', [])
            err = e
        self.frames_routed += len(pkts)
        if pkts or err is not None:
            conn.emit('ingestDeliver', pkts, err)

    def _schedule(self) -> None:
        """A tick is due: bytes reached a slot, or a tick left work for
        a follow-up."""
        self._due = True
        if not self._scheduled:
            self._scheduled = True
            asyncio.get_running_loop().call_soon(self._tick)

    # -- the per-tick batch --

    # int32 plane order in the packed tick output; the head columns
    # (n_frames, resid, bad) come first, then these [B, F] planes.
    _HDR_PLANES = ('starts', 'sizes', 'xids', 'errs',
                   'zxid_hi', 'zxid_lo')

    def _trace_step(self, buf, lens):
        """The traced tick computation: decode ``buf``/``lens`` and
        pack the results into one int32 array.  Pure array code —
        jitted directly here, re-wrapped in ``shard_map`` by the
        mesh-aware subclass (parallel/fleet.py)."""
        import jax
        import jax.numpy as jnp

        from ..ops.pipeline import WIRE_STEP_IMPLS, auto_impl

        # auto-dispatch picks the measured winner for this shape and
        # target platform (jnp on the host CPU backend; the Pallas
        # kernel only in its recorded TPU win pocket, and only where
        # it fits the device's scoped VMEM); the choice is recorded
        # for the bucket being compiled
        impl = self._traced_impl = auto_impl(
            buf.shape[0], buf.shape[1], self.max_frames)
        st = WIRE_STEP_IMPLS[impl](buf, lens,
                                   max_frames=self.max_frames)

        # the stages carry named scopes (frame_scan / header_gather
        # inside the step, pack here): metadata only, so a kept
        # profiler trace names the program's ops by stage
        with jax.named_scope('pack'):
            head = jnp.stack(
                [st.n_frames, st.resid,
                 st.bad.astype(jnp.int32)], axis=1)     # [B, 3]
            flat = jnp.stack([getattr(st, f) for f in self._HDR_PLANES],
                             axis=1)                    # [B, K, F]
            B = flat.shape[0]
            return st, jnp.concatenate([head, flat.reshape(B, -1)],
                                       axis=1)

    def _step_fn(self, _bodies=False):
        """Build (and cache) the jittable one-dispatch decode for this
        configuration — the lowering source for the per-shape AOT
        executables (:meth:`_compile`).

        Everything the host needs comes back as ONE packed int32
        array: every readback is a host<->device round trip inside the
        event loop, so the per-tick readback count is held at one."""
        # ``_bodies`` is ignored: benchmark/rehearse.py passes False
        fn = self._fn
        if fn is None:
            import jax

            def step(buf, lens):
                return self._trace_step(buf, lens)[1]
            fn = self._fn = jax.jit(step)
        return fn

    # -- shape-bucket warm-up (AOT compile off the event loop) --

    def _bucket(self, n_streams: int, nbytes: int) -> tuple:
        """The shape bucket of one dispatch: ``n_streams`` rows of up
        to ``nbytes`` each.  The width is the size class; the rows pad
        to a power of two from the count that makes the smallest
        dispatch ``8 x min_len`` bytes (8 rows in the ``min_len``
        class, 1 in the classes eight times as wide and wider)."""
        L = self._width(nbytes)
        # the leading False: benchmark/reduce_trace.py unpacks 3-tuples
        return (False, self._padded_rows(n_streams, L), L)

    def _padded_rows(self, n_streams: int, width: int) -> int:
        """The rows of a dispatch of ``n_streams`` rows of ``width``."""
        return _next_pow2(max(n_streams, 8 * self.min_len // width, 1))

    def _width(self, nbytes: int) -> int:
        """The size class of a row of ``nbytes``: its width."""
        return _next_pow2(max(self.min_len, nbytes))

    def _class_rows(self, nbytes: int) -> int:
        """How many rows of the size class of ``nbytes`` one dispatch
        holds."""
        return max(1, self.DISPATCH_BYTES // self._width(nbytes))

    def _head_class(self, classes: dict, heads: int) -> int:
        """The size class (its width's bit length; 0: ``min_len``)
        whose dispatch a tick's ``heads`` header rows stand in, given
        the tick's other rows by class: the narrowest — ``min_len``'s
        where the tick has such rows or no others; else they ride the
        narrowest class present, so that they add no dispatch, where
        its one dispatch has the rows and grows by no more than
        ``RIDE_BYTES`` for them."""
        if not classes or 0 in classes:
            return 0
        c = min(classes)
        width, rows = 1 << c, len(classes[c][0])
        grown = (self._padded_rows(rows + heads, width)
                 - self._padded_rows(rows, width)) * width
        if (rows + heads <= self._class_rows(width)
                and grown <= self.RIDE_BYTES):
            return c
        return 0

    def _compile(self, key: tuple):
        """Lower + AOT-compile the tick program for one shape bucket.
        Runs on the warm thread (or inline under warm='block')."""
        import contextlib

        import jax

        from ..utils.platform import enable_compile_cache

        _bodies, Bp, L = key
        enable_compile_cache()
        fn = self._step_fn()
        batch = np.zeros((Bp, L), np.uint8)
        lens = np.zeros((Bp,), np.int32)
        ctx = (jax.default_device(self._device) if self._device is not
               None else contextlib.nullcontext())
        with ctx:
            return fn.lower(batch, lens).compile()

    def _try_compile(self, key: tuple):
        """Compile ``key``'s bucket and record in :attr:`buckets` what
        it compiled to; a failure is recorded, logged and returns None
        (one policy for the inline and background warm paths)."""
        info = self.buckets[key] = {'impl': None, 'platform': None,
                                    'compile_s': None, 'error': None}
        with self._compile_lock:
            self._traced_impl = None
            t0 = time.perf_counter()
            try:
                self._resolve_placement()
                ex = self._compile(key)
            except Exception as e:
                info['error'] = '%s: %s' % (type(e).__name__, e)
                self.log.warning('tick program compile failed for '
                                 'bucket %r: %s', key, e)
                ex = None
            else:
                info['impl'] = self._traced_impl
                info['platform'] = _executable_platform(ex)
            info['compile_s'] = time.perf_counter() - t0
        return ex

    def _compile_or_latch(self, key: tuple):
        """Inline warm: compile and store, latching a failure as None
        so the bucket permanently drains scalar."""
        ex = self._exec[key] = self._try_compile(key)
        return ex

    def _require_compiled(self, key: tuple) -> None:
        """The force-device contract (``bypass_bytes=0``): every tick
        runs the device program, so a bucket whose program failed to
        compile is an error here, not a scalar bucket.  So is, in any
        mode, a placement that could not be honoured (``placed`` still
        unset: 'accelerator' was asked for and there is none)."""
        if not self.bypass_bytes or self.placed is None:
            raise RuntimeError(
                'tick program for bucket %r failed to compile (%s); '
                'force-device mode (bypass_bytes=0) and an unmet '
                'placement have no scalar fallback'
                % (key, self.buckets[key]['error']))

    def _start_warm(self, key: tuple) -> asyncio.Event:
        """Queue (or join) the background compile for ``key``;
        returns the event set when the bucket is ready (or failed).
        Compiles drain FIFO through one DAEMON worker thread, so at
        most one XLA compile runs at any moment, a failure is contained
        to its task (never to the serialization mechanism), and — the
        reason it must be a daemon, not an executor worker — a compile
        wedged on an unreachable accelerator backend can never hang
        interpreter exit (concurrent.futures joins its non-daemon
        workers at shutdown; a daemon thread just dies)."""
        ev = self._warm_events.get(key)
        if ev is not None:
            return ev
        ev = asyncio.Event()
        self._warm_events[key] = ev
        loop = asyncio.get_running_loop()
        if self._warm_queue is None:
            q = self._warm_queue = queue.Queue()

            # the drain closure must reference only the QUEUE, never
            # self: a thread parked in q.get() would otherwise pin the
            # whole ingest (compiled executables included) for the
            # process lifetime; None is the close() shutdown sentinel
            def drain():
                while True:
                    task = q.get()
                    try:
                        if task is None:
                            return
                        task()
                    except Exception:   # containment; _try_compile
                        pass            # already latches failures
                    finally:
                        q.task_done()

            t = threading.Thread(target=drain, daemon=True,
                                 name='ingest-warm')
            t.start()
            _guard_warm_exit(t, q)

        def work():
            ex = self._try_compile(key)

            def done():
                self._exec[key] = ex
                ev.set()
                # bytes may be waiting that deferred to scalar
                self._schedule()
            try:
                # the _exec write happens on the loop thread (done)
                loop.call_soon_threadsafe(done)
            except RuntimeError:     # loop closed mid-compile
                pass

        self._warm_queue.put(work)
        return ev

    def close(self) -> None:
        """Release the background warm worker (idempotent).  Queued
        compiles still drain first (FIFO), then the daemon thread
        exits; without this the parked worker lives until process
        exit — harmless (it holds only the queue, never the ingest)
        but untidy in thread dumps.  The ingest itself needs no other
        teardown: connections unregister themselves.  The collector's
        young generation stops following this ingest's slots; the
        process's last ingest to close puts the thresholds back
        (utils/alloc.py)."""
        self._closed = True
        alloc.release_collector(self)
        if self._warm_queue is not None:
            self._warm_queue.put(None)
            self._warm_queue = None

    def bind_metrics(self, collector, prefix: str = '') -> None:
        """Expose this ingest's tick/frame counters as pull-model
        gauges on ``collector`` (utils/metrics.Collector) — the
        observability twin of the reference's artedi counters
        (lib/client.js:29,58-61) for the batched plane.  When several
        ingests share one collector, give each a distinct ``prefix``
        (name collisions raise rather than silently dropping a
        registrant's series)."""
        for name, attr, help_text in (
                ('zkstream_ingest_ticks', 'ticks',
                 'device ticks dispatched'),
                ('zkstream_ingest_early_ticks', 'ticks_early',
                 'device ticks whose batches were dispatched ahead of '
                 'their tick, at the end of the receive reap that fed '
                 'them or of the tick before (the device computes '
                 'while the loop works)'),
                ('zkstream_ingest_scalar_ticks', 'ticks_scalar',
                 'ticks drained through the scalar codec (bypass or '
                 'failed bucket)'),
                ('zkstream_ingest_warming_ticks', 'ticks_warming',
                 'ticks deferred to scalar while a shape bucket '
                 'compiled'),
                ('zkstream_ingest_frag_ticks', 'ticks_frag',
                 'ticks routed to the scalar drain by the '
                 'fragmentation guard (fleet large, ticks sparse)'),
                ('zkstream_ingest_frames_routed', 'frames_routed',
                 'frames delivered through the ingest'),
                ('zkstream_ingest_dispatches', 'dispatches',
                 'device dispatches made (one a size class present a '
                 'tick)'),
                ('zkstream_ingest_batched_bytes', 'bytes_batched',
                 'stream bytes copied into the ticks\' batches'),
                ('zkstream_ingest_dispatched_bytes', 'bytes_dispatched',
                 'padded bytes the dispatches held (Bp x L summed)'),
                ('zkstream_ingest_headed_rows', 'rows_headed',
                 'header rows given to the device ticks: a slot that '
                 'held exactly one whole frame wider than min_len gave '
                 'the frame\'s first min_len bytes under its true '
                 'length'),
                ('zkstream_ingest_kept_home_bytes', 'bytes_kept_home',
                 'bytes of the header rows\' frames that stayed in '
                 'their slots: not copied into a batch, not sent over '
                 'the link (no device op reads them)'),
                ('zkstream_ingest_recopied_bytes', 'bytes_recopied',
                 'bytes batched that their tick did not consume (a '
                 'partial frame behind whole ones), so batched again'),
                ('zkstream_ingest_deferred_slots', 'slots_deferred',
                 'slot-ticks sat out because the slot\'s first frame '
                 'was not whole yet'),
                ('zkstream_ingest_bound_slots', 'slots_bound',
                 'streams that gave a device tick max_frames frames, '
                 'the per-tick bound of one row'),
                ('zkstream_ingest_cut_slots', 'slots_cut',
                 'slots that held more bytes than the tick took of '
                 'them (they finish on the follow-up tick)'),
                ('zkstream_ingest_reticks', 'reticks',
                 'device ticks that scheduled a follow-up tick for a '
                 'stream at the frame bound with more buffered, or '
                 'for a cut slot'),
                ('zkstream_ingest_full_ticks', 'ticks_full',
                 'device ticks that left whole frames in their slots '
                 'because the tick\'s batch memory was full'),
                ('zkstream_ingest_routed_names', 'names_routed',
                 'names in the children lists the device ticks '
                 'routed'),
                ('zkstream_ingest_routed_lists', 'lists_routed',
                 'children lists the device ticks routed'),
                ('zkstream_ingest_shared_lists', 'lists_shared',
                 'children lists a tick\'s decode served from an '
                 'equal body it had parsed already (the names are '
                 'shared, the list is the asker\'s own)')):
            collector.gauge(prefix + name,
                            (lambda a=attr: getattr(self, a)),
                            help_text)
        collector.multi_gauge(
            prefix + 'zkstream_ingest_placement_rtt_ms',
            self._placement_series,
            'dispatch+readback round trip of the default accelerator '
            'measured at placement, labelled with the platform / '
            'device_kind the ticks resolved to (NaN: not probed, the '
            'default backend is the CPU)')
        collector.multi_gauge(
            prefix + 'zkstream_ingest_buckets',
            self._bucket_series,
            'compiled tick-program shape buckets by header-scan '
            'implementation (pallas | jnp; failed = did not compile) '
            'and the platform the executable lives on')
        # swap the standalone tick-duration histogram for a collector-
        # registered one; samples observed before binding stay with the
        # discarded instance (bind at setup time)
        self.tick_hist = collector.histogram(
            prefix + 'zkstream_ingest_tick_ms',
            'Ingest tick (batched drain) duration, milliseconds')
        self.phase_hist = collector.histogram(
            prefix + METRIC_INGEST_PHASE, _PHASE_HELP,
            buckets=TICK_BUCKETS)

    def _placement_series(self) -> dict:
        if self.placed is None:
            return {}
        rtt = self.placed['rtt_ms']
        return {(('platform', self.placed['platform']),
                 ('device_kind', self.placed['device_kind'])):
                float('nan') if rtt is None else rtt}

    def _bucket_series(self) -> dict:
        out: dict = {}
        for info in list(self.buckets.values()):
            if info['compile_s'] is None:
                continue               # still compiling
            key = (('impl', 'failed' if info['error'] else info['impl']),
                   ('platform', info['platform'] or ''))
            out[key] = out.get(key, 0) + 1
        return out

    async def prewarm(self, n_streams: int,
                      nbytes: int | None = None) -> None:
        """Compile the tick program for an expected fleet shape up
        front (servers at startup, benchmarks before timing): the
        bucket of one dispatch of ``n_streams`` rows (as many of them
        as one dispatch holds) in the size class of ``nbytes`` (default:
        ``min_len``, the narrowest).  A deployment whose replies reach
        wider classes warms those it expects, row count by row count
        (powers of two).  Concurrent
        prewarms for several buckets drain through the single warm
        worker one at a time (total ~= sum of compiles, not max) — the
        same serialization that keeps background warms from
        oversubscribing a host mid-service.  In force-device mode a
        bucket that fails to compile raises here."""
        nbytes = nbytes or self.min_len
        key = self._bucket(min(n_streams, self._class_rows(nbytes)), nbytes)
        if self._exec.get(key, _MISSING) is _MISSING:
            if self.warm == 'block':
                self._compile_or_latch(key)
            else:
                await self._start_warm(key).wait()
        if self._exec.get(key) is None:
            self._require_compiled(key)

    @staticmethod
    def _default_device():
        import jax
        return jax.devices()[0]

    @staticmethod
    def _probe_rtt_ms(device) -> float:
        """Dispatch->readback round trip of a trivial program on
        ``device``, ms — the floor every tick pays."""
        import jax
        import jax.numpy as jnp

        with jax.default_device(device):
            probe = jax.jit(lambda x: x + 1)
            x = jnp.zeros((8,), jnp.int32)
            np.asarray(probe(x))   # compile + first readback
            t0 = time.perf_counter()
            for _ in range(3):
                np.asarray(probe(x))
        return (time.perf_counter() - t0) / 3 * 1e3

    def _resolve_placement(self) -> None:
        """Pick the tick's execution device (once, at first warm-up —
        never on the event loop under warm='background': the probe
        costs several accelerator round trips) and record it in
        :attr:`placed`."""
        with self._place_lock:
            if self.placed is not None:
                return
            import jax

            dev = self._default_device()
            if self.placement == 'accelerator' and dev.platform == 'cpu':
                raise RuntimeError(
                    "FleetIngest(placement='accelerator'): the default "
                    'JAX backend is the host CPU (%s), there is no '
                    'accelerator to place ticks on' % (dev.device_kind,))
            rtt_ms = None
            if self.placement == 'host':
                dev = jax.devices('cpu')[0]
            elif dev.platform != 'cpu':
                # the dispatch+readback floor every accelerator tick
                # pays; under 'auto' it also decides the placement
                rtt_ms = self._probe_rtt_ms(dev)
                if (self.placement == 'auto'
                        and rtt_ms > self.latency_budget_ms):
                    dev = jax.devices('cpu')[0]
            self._device = dev
            self.placed = {'platform': dev.platform,
                           'device_kind': dev.device_kind,
                           'rtt_ms': rtt_ms}

    def _unpack(self, ints):
        """The host-side views of one dispatch's packed array (numpy
        views, no copies): the head columns and the ``_HDR_PLANES``."""
        import types

        B = ints.shape[0]
        head = ints[:, :3]
        flat = ints[:, 3:].reshape(B, -1, self.max_frames)
        st = types.SimpleNamespace(n_frames=head[:, 0],
                                   resid=head[:, 1], bad=head[:, 2])
        for k, name in enumerate(self._HDR_PLANES):
            setattr(st, name, flat[:, k])
        return st

    def _note_frames(self, n: int) -> None:
        """Feed the fragmentation EMA with one tick's routed frames
        (every path: device, bypass, warming, guard)."""
        self._ema_frames = (float(n) if self._ema_frames is None
                            else 0.2 * n + 0.8 * self._ema_frames)

    def _frag_guarded(self) -> bool:
        """The upper dispatch guard: True routes this tick to the
        scalar drain because the fleet is large but its ticks are
        fragmented (frames/tick ≪ fleet size).  Hysteresis keeps the
        router from flapping on tick noise."""
        if not self.frag_guard:
            return False
        n = len(self._slots)
        if n < self.FRAG_MIN_FLEET or self._ema_frames is None:
            self._frag_scalar = False
            return False
        if self._frag_scalar:
            if self._ema_frames >= self.FRAG_EXIT * n:
                self._frag_scalar = False
        elif self._ema_frames < self.FRAG_ENTER * n:
            self._frag_scalar = True
        return self._frag_scalar

    def _want_direct(self) -> bool:
        """The dispatch policy: should the ingest run as a
        pass-through drain?  True when the byte volume per tick sits
        under ``bypass_bytes`` (the measured low-end crossover) or the
        fragmentation guard says a mega-fleet's ticks stopped being
        batches (the measured high-end losing regime)."""
        frag = self._frag_guarded()
        if frag:
            return True
        if not self.bypass_bytes or self._ema_bytes is None:
            return False
        if self._direct:
            # hysteresis: leave the pass-through only once the volume
            # clearly justifies batching
            return self._ema_bytes < 1.25 * self.bypass_bytes
        return self._ema_bytes < self.bypass_bytes

    def _flip_direct(self, active) -> None:
        """Batch -> pass-through: drain what the slots hold, hand each
        codec its partial-frame residue, switch.  Fault-withheld
        suffixes rejoin their slots FIRST — the direct regime never
        drains slot buffers, so a tail left in ``_held`` across the
        flip would strand, then reorder behind fresh rx bytes."""
        self._release_held()
        self._rx_marks.clear()
        for conn, buf, _lane in active:
            if id(conn) not in self._slots:
                continue
            self._deliver_scalar(conn, buf)
        for conn, buf, _lane in list(self._slots.values()):
            if buf and conn.codec is not None:
                conn.codec.restore_pending(bytes(buf))
                buf.clear()
        self._direct = True
        self._sink_all(False)   # from here every byte through ``feed``

    def _flip_batch(self) -> None:
        """Pass-through -> batch: reclaim each codec's partial-frame
        residue into its slot so the next tick's scan continues it."""
        self._direct = False
        for conn, buf, _lane in list(self._slots.values()):
            if conn.codec is not None:
                resid = conn.codec.take_pending()
                if resid:
                    buf[:0] = resid
        self._sink_all(self.sinkable)

    def _tick(self) -> None:
        """The scheduled tick: the second half of the device tick in
        flight (its batches went out ahead, "The early dispatch"), or
        both halves of one that begins here."""
        self._scheduled = False
        flight, self._flight = self._flight, None
        if flight is None and not self._due:
            return      # an early first half found nothing to dispatch
        t0 = time.perf_counter()
        # host span ``ingest.tick`` (utils/trace.host_span: recorded
        # only inside a profiler session): the tick as the duration
        # histogram times it, from here — its phases ``batch`` and
        # ``dispatch`` stand before it where they ran ahead.  ``tick``
        # is the number this tick takes if it runs the device program
        # — its phases carry the same one; a tick that did not says so
        # in ``detail``.
        early_ms = 0.0
        with host_span('ingest.tick',
                       tick=(self.ticks + 1 if flight is None
                             else flight.tick)) as sp:
            if flight is None:
                routed = flight = self._begin(sp)
            else:
                routed = True
                early_ms = flight.early_ms
                if flight.fields:
                    sp.set(**flight.fields)
            if flight.__class__ is _Flight:
                self._finish(flight, sp)
            elif not routed:
                sp.cancel()
        if routed:
            self.tick_hist.observe(
                (time.perf_counter() - t0) * 1000.0 + early_ms)
        if self._due:
            # a follow-up (the route hit the frame bound, a slot held
            # more than it gave, bytes came behind the flight): its
            # first half now, so the device works while the woken run
            self._schedule()
            self._early()

    def _reaped(self) -> None:
        """The reap that fed the slots has fed the last of them
        (io/transport.py ``after_reap``)."""
        self._asked = False
        self._early()

    def _early(self) -> None:
        """The first half of the tick that is scheduled, ahead of it:
        what it dispatches is in flight until that tick routes it.
        Nothing where no tick is scheduled or nothing is due, in the
        pass-through regime, or behind a batch still in flight (the
        bytes wait in their slots for the next one)."""
        if (self._flight is not None or self._direct
                or not self._scheduled or not self._due):
            return
        t0 = time.perf_counter()
        with host_span('ingest.tick', tick=self.ticks + 1) as sp:
            flight = self._begin(sp)
            if flight is not True:
                # nothing drained, or dispatched: the scheduled tick's
                # span is the one the ring keeps
                sp.cancel()
        took = (time.perf_counter() - t0) * 1000.0
        if flight is True:      # drained here another way
            self.tick_hist.observe(took)
        elif flight:
            flight.early_ms = took
            flight.fields = getattr(sp, 'fields', None)
            self._flight = flight
            self.ticks_early += 1

    def _begin(self, sp=NO_SPAN):
        """A tick's first half: the regime's bookkeeping, the
        injector's tick-time faults, the batches, their dispatch.
        Returns the :class:`_Flight` to read back and route; True when
        the tick routed its work here another way (pass-through
        bookkeeping or flip, buckets still compiling); False when it
        found nothing to drain.  Ticks that return True or a flight
        feed the duration histogram — empty bookkeeping wakeups would
        only blur the distribution's low end."""
        self._due = False
        win = self._window_bytes
        self._window_bytes = 0
        if win:
            self._ema_bytes = (float(win) if self._ema_bytes is None
                               else 0.2 * win + 0.8 * self._ema_bytes)
        if self._direct:
            if not win:
                return False
            # deliveries already happened inline (connection-side
            # drain or feed()); this tick is bookkeeping + the regime
            # decision.  Policy FIRST, then count: ticks_frag must
            # reflect the updated guard state, not last tick's.
            self._note_frames(self.frames_routed - self._frames_mark)
            self._frames_mark = self.frames_routed
            self.ticks_scalar += 1
            still_direct = self._want_direct()
            if self._frag_scalar:
                self.ticks_frag += 1
            if not still_direct:
                self._flip_batch()
            sp.set(tick=None, detail='direct', nbytes=win)
            return True
        if self._faults is not None:
            self._inject_tick_faults()
        # Phase ``batch`` (host span ``ingest.batch``) opens with the
        # scan for the slots that hold bytes — the first step of
        # building the batch, and at fleet width not a small one —
        # and closes when every dispatch's ``[Bp, L]`` is built.  A
        # tick that is drained another way (nothing buffered,
        # pass-through flip, bucket still compiling) leaves no batch
        # span behind.
        plans = ()
        flight = None
        before = self.frames_routed
        try:
            t0 = time.perf_counter()
            with host_span('ingest.batch', tick=self.ticks + 1) as bsp:
                active = [slot for slot in self._slots.values()
                          if slot[1] and slot[0].is_in_state('connected')]
                plans = self._prepare_batch(active, sp) if active else ()
                if not plans:
                    bsp.cancel()
            if plans:
                flight = self._dispatch(plans, before, t0)
                return flight
        finally:
            if flight is None:
                # ``()``: no slot held a whole frame — nothing drained
                self._end_tick(plans != (), before, sp)
        return plans != ()

    def _end_tick(self, drained: bool, before: int, sp) -> None:
        """What every tick of the batch regime ends with, however it
        drained: the fragmentation EMA fed, the withheld suffixes back
        in their slots."""
        if drained:
            frames = self.frames_routed - before
            self._note_frames(frames)
            self._frames_mark = self.frames_routed
            sp.set(batch=frames)
            if frames > self._gc_look:
                self._fit_collector(frames)
        if self._release_held():
            self._schedule()     # finish the withheld suffixes

    def _inject_tick_faults(self) -> None:
        """Apply the injector's tick-time decisions to the batch-regime
        slots: a connection reset at the tick boundary, or a suffix of
        a slot's buffered bytes withheld from this tick (a partial
        frame at an arbitrary cut for the device scan to finish on the
        follow-up tick)."""
        fi = self.faults
        for cid, (conn, buf, _lane) in list(self._slots.items()):
            if not buf or not conn.is_in_state('connected'):
                continue
            if fi.ingest_reset(conn):
                conn.emit('sockError', ConnectionResetError(
                    'injected ingest tick reset'))
                continue
            if cid in self._no_hold:
                self._no_hold.discard(cid)
                continue
            cut = fi.ingest_cut(conn, len(buf))
            if cut:
                self._held[cid] = \
                    self._held.get(cid, b'') + bytes(buf[-cut:])
                del buf[-cut:]

    def _release_held(self) -> bool:
        """Re-append every withheld suffix to its slot (in order);
        True when any slot got bytes back (a follow-up tick is due)."""
        if not self._held:
            return False
        released = False
        held, self._held = self._held, {}
        for cid, tail in held.items():
            slot = self._slots.get(cid)
            if slot is None:
                continue             # conn died; its bytes die with it
            slot[1].extend(tail)
            self._no_hold.add(cid)
            released = True
        return released

    def _prepare_batch(self, active, sp=NO_SPAN):
        """Decide how this tick drains and, for a device tick, build
        its batches: returns the tick's dispatches, each ``(ex, key,
        streams, batch, lens, nbytes, headed, kept)`` — ``lens`` what
        the device gets, ``nbytes`` the bytes copied into the rows,
        ``headed`` / ``kept`` its header rows and the bytes of their
        frames that were not copied; None when the tick was
        drained here another way (the pass-through flip, buckets still
        compiling or that failed to compile); ``()`` when no slot
        holds a whole frame yet: nothing was drained."""
        if self._want_direct():
            self.ticks_scalar += 1
            if self._frag_scalar:
                self.ticks_frag += 1
            sp.set(tick=None,
                   detail='frag' if self._frag_scalar else 'scalar')
            self._flip_direct(active)
            return None

        # What each slot gives this tick.  A slot whose first frame is
        # not whole yet waits — the one thing the host reads of a
        # stream is that frame's length prefix — so a reply that
        # arrives over many reads is copied once, when it is whole.
        # A slot that holds exactly one whole frame wider than
        # ``min_len`` gives a header row ("What a slot gives a tick"):
        # its first ``min_len`` bytes under the frame's true length (a
        # ``min_len`` under what the program reads of a frame has
        # none).  Behind a
        # whole first frame any other slot gives what the frame
        # bound could consume if the frames behind are of its size
        # (the power of two over ``max_frames`` of it): a partial large
        # frame behind a small one is not copied along.  A prefix no
        # frame can have goes to the device, which flags the stream.
        min_len, frames = self.min_len, self.max_frames
        streams, sizes = [], []
        heads, trues = [], []
        head_top = _FRAME_TOP if min_len >= _HEAD else 0
        cut = 0
        for slot in active:
            buf = slot[1]
            have = len(buf)
            # the first frame's bytes, prefix included (unsigned: a
            # negative length reads as one over the cap)
            n = _PREFIX(buf)[0] + 4 if have >= 4 else _FRAME_TOP
            if have < n <= _FRAME_TOP:
                self.slots_deferred += 1
                continue
            if have > min_len:
                if have == n <= head_top:
                    heads.append(slot)
                    trues.append(n)
                    continue
                have = min(have, min_len if n > _FRAME_TOP
                           else self._width(n * frames))
                cut += have < len(buf)
            streams.append(slot)
            sizes.append(have)
        if cut:
            self.slots_cut += cut
            self._schedule()    # a slot holds more than it gave
        if not streams and not heads:
            return ()

        # one dispatch a size class present, a class's rows beyond
        # ``DISPATCH_BYTES`` in further ones; with every row in the
        # narrowest class (a fleet of small replies) the tick is one
        # dispatch of everything, and this is all it costs.  A group
        # is its slots, the bytes each gives, and — where header rows
        # stand in it — the lengths the device gets (else None: the
        # bytes given).
        if not heads and max(sizes) <= min_len:
            groups = [(streams, sizes, None)]
        else:
            classes: dict = {}
            for slot, n in zip(streams, sizes):
                g = classes.setdefault(
                    (n - 1).bit_length() if n > min_len else 0, ([], []))
                g[0].append(slot)
                g[1].append(n)
            host = None
            if heads:
                host = self._head_class(classes, len(heads))
                classes.setdefault(host, ([], []))
            groups = []
            for c, (g_streams, g_sizes) in sorted(classes.items()):
                g_lens = None
                if c == host:
                    g_lens = g_sizes + trues
                    g_streams = g_streams + heads
                    g_sizes = g_sizes + [min_len] * len(heads)
                rows = self._class_rows(max(g_sizes))
                for lo in range(0, len(g_streams), rows):
                    groups.append((g_streams[lo:lo + rows],
                                   g_sizes[lo:lo + rows],
                                   g_lens and g_lens[lo:lo + rows]))

        plans, scalar = [], []
        arena, used = self._arena, 0
        if arena is None:
            arena = self._arena = np.empty((self.TICK_BYTES,), np.uint8)
        for g_streams, g_sizes, g_lens in groups:
            key = self._bucket(len(g_streams), max(g_sizes))
            ex = self._exec.get(key, _MISSING)
            if ex is _MISSING:
                if self.warm == 'block':
                    ex = self._compile_or_latch(key)
                else:
                    # never block the loop on a compile: drain these
                    # streams through the scalar codec while the
                    # bucket warms
                    self._start_warm(key)
                    scalar.append((g_streams, 'warming'))
                    continue
            if ex is None:  # compile failed: this bucket stays scalar
                self._require_compiled(key)
                scalar.append((g_streams, 'scalar'))
                continue
            _bodies, Bp, L = key
            if used + Bp * L <= len(arena):
                batch = arena[used:used + Bp * L].reshape(Bp, L)
                used += Bp * L
            elif used:
                # the tick is full: the rest waits for the next one
                self.ticks_full += 1
                self._schedule()
                break
            else:   # one row wider than a tick: a frame near the cap
                batch = np.zeros((Bp, L), np.uint8)
                used = len(arena)
            # one flat byte view of the batch: a slice assignment
            # copies a stream into its row before anything can mutate
            # it, without a numpy call a stream (it was a third of
            # phase ``batch``)
            rows = memoryview(batch.reshape(-1))
            for i, (slot, n) in enumerate(zip(g_streams, g_sizes)):
                rows[i * L:i * L + n] = (
                    slot[1] if n == len(slot[1])
                    else memoryview(slot[1])[:n])
            lens = np.zeros((Bp,), np.int32)
            lens[:len(g_sizes)] = g_lens or g_sizes
            nbytes = sum(g_sizes)
            # of a group with header rows: how many, and the bytes of
            # their frames that stayed in the slots
            headed = sum(map(int.__ne__, g_lens, g_sizes)) if g_lens else 0
            kept = sum(g_lens) - nbytes if g_lens else 0
            plans.append((ex, key, g_streams, batch, lens, nbytes,
                          headed, kept))
        hows = {how for _s, how in scalar}
        self.ticks_warming += 'warming' in hows
        self.ticks_scalar += 'scalar' in hows
        for g_streams, _how in scalar:
            for conn, buf, _lane in g_streams:
                if id(conn) in self._slots:
                    self._deliver_scalar(conn, buf)
        if not plans:
            sp.set(tick=None, detail=scalar[0][1])
            return None
        self.ticks += 1
        self._retick = cut > 0
        if sp is not NO_SPAN:
            _bodies, Bp, L = plans[0][1]
            rows = sum(len(p[2]) for p in plans)
            sp.set(detail=('device %dx%d streams=%d' % (Bp, L, rows)
                           if len(plans) == 1 else
                           'device %d dispatches streams=%d'
                           % (len(plans), rows)),
                   nbytes=sum(p[5] for p in plans), cut=cut,
                   headed=sum(p[6] for p in plans),
                   kept=sum(p[7] for p in plans))
        return plans

    def _dispatch(self, plans, before: int, t0: float) -> _Flight:
        """Phase ``dispatch``, once a tick's batches stand (``t0``:
        when phase ``batch`` opened): every dispatch sent — all in
        flight together, in the memory ``TICK_BYTES`` bounds, which
        nothing writes again until :meth:`_finish` has read the
        results — and each result's copy to the host asked for at
        once, so the readback finds it there where the loop had other
        work meanwhile.  Host span ``ingest.dispatch`` once a
        dispatch, with the tick's number (profiler sessions only)."""
        n = self.ticks
        t1 = time.perf_counter()
        outs = []
        for ex, key, streams, batch, lens, nbytes, headed, kept in plans:
            with host_span('ingest.dispatch', tick=n, rows=len(streams),
                           width=key[2], nbytes=nbytes):
                out = ex(batch, lens)
                # the results come to the host as soon as they stand,
                # not when the readback asks (a D2H round trip is
                # ~0.6 ms of the loop on the chip, PERF.md, PR 43)
                out.copy_to_host_async()
                outs.append(out)
            self.dispatches += 1
            self.bytes_batched += nbytes
            self.bytes_dispatched += batch.size
            self.rows_headed += headed
            self.bytes_kept_home += kept
        return _Flight(n, plans, outs, before,
                       (t0, t1, time.perf_counter()))

    def _finish(self, flight: _Flight, sp) -> None:
        """A device tick's second half: every result read back — found
        on the host where the dispatch ran ahead of the tick, waited
        for where it did not — then one route over all of them.  Each
        phase is a host span carrying the tick's number (profiler
        sessions only; ``ingest.readback`` once a dispatch) and, with
        the first half's two, one observation of
        ``zkstream_ingest_phase_ms{phase=}`` a tick (always)."""
        n, plans = flight.tick, flight.plans
        results = []
        try:
            t2 = time.perf_counter()
            for out in flight.outs:
                with host_span('ingest.readback', tick=n):
                    results.append(np.asarray(out))
            t3 = time.perf_counter()
            with host_span('ingest.route', tick=n) as rsp:
                laned = emitted = 0
                names = self.names_routed
                lists, shared = self.lists_routed, self.lists_shared
                bound = self.slots_bound
                self.routing = n
                try:
                    for plan, ints in zip(plans, results):
                        streams, lens = plan[2], plan[4]
                        st = self._unpack(ints)
                        B = len(streams)
                        self.bytes_recopied += int(np.where(
                            st.bad[:B], 0, lens[:B] - st.resid[:B]).sum())
                        a, b = self._route_batch(streams, None, st)
                        laned += a
                        emitted += b
                finally:
                    self.routing = None
                rsp.set(lane=laned, emitted=emitted,
                        names=self.names_routed - names,
                        lists=self.lists_routed - lists,
                        shared=self.lists_shared - shared)
            # what a pipelined tick did (profiler sessions: on the
            # ``ingest.tick`` span, beside the first half's ``cut``):
            # rows at the frame bound, a follow-up left for either
            self.reticks += self._retick
            sp.set(bound=self.slots_bound - bound,
                   retick=int(self._retick))
            t4 = time.perf_counter()
        finally:
            self._end_tick(True, flight.before, sp)
        t0, t1, t_sent = flight.times
        observe = self.phase_hist.observe
        for labels, a, b in zip(_PHASE_LABELS, (t0, t1, t2, t3),
                                (t1, t_sent, t3, t4)):
            observe((b - a) * 1000.0, labels)

    def _route_batch(self, streams, rows, st) -> tuple[int, int]:
        """Deliver a tick's decoded results as one batch (shared by
        the event-driven tick and the multihost cadence tick):
        ``streams`` are the slots that were in it, ``rows`` their rows
        in the planes (None: the first ``len(streams)``).  One pass
        over the head planes as Python lists, one clock read, and with
        the extension loaded ONE C call that decodes every stream's
        complete-frame slice (:meth:`_decode_batch`); then each stream
        in turn goes to its connection — through its direct lane when
        it brought one.
        Schedules the follow-up tick when a stream hit the per-stream
        frame bound with more buffered.  Returns the frames the lanes
        settled and the frames that went the emitter path."""
        if rows is None:
            B = len(streams)
            rows = range(B)
            n_frames = st.n_frames[:B].tolist()
            resids = st.resid[:B].tolist()
            bads = st.bad[:B].tolist()
        else:
            n_frames = st.n_frames[rows].tolist()
            resids = st.resid[rows].tolist()
            bads = st.bad[rows].tolist()
        now = time.monotonic()
        lens = None      # no stream was decoded in a batch (yet)
        decoded = self._decode_batch(streams, n_frames, resids, bads)
        if decoded is not None:
            lens, maps, flat, counts, errors = decoded
        slots = self._slots
        rx_marks = self._rx_marks
        max_frames = self.max_frames
        laned = routed = pos = 0
        retick = False
        for i, slot in enumerate(streams):
            conn, buf, lane = slot
            pkts = None
            if lens is not None and lens[i]:
                # this stream's share of the batch decode
                pkts = flat[pos:pos + counts[i]]
                pos += counts[i]
            # A user callback from an earlier stream's delivery may
            # have torn this connection down mid-tick, or the
            # connection left ``connected`` between an early dispatch
            # and this route (unregister already restored its bytes to
            # the codec): skip it — and hand back the xids the batch
            # decode consumed for it, for the codec will decode those
            # bytes again.
            if slots.get(id(conn)) is not slot:
                if pkts:
                    maps[i].update((pkt['xid'], pkt['opcode'])
                                   for pkt in pkts
                                   if pkt['xid'] not in SPECIAL_XIDS)
                continue
            if bads[i]:
                # Exact scalar-error parity: re-run this stream through
                # the connection's own codec, which raises BAD_LENGTH/
                # BAD_DECODE with the pre-error packets attached.
                self._deliver_fallback(conn, buf)
                continue
            n = n_frames[i]
            if pkts is None:
                pkts, err = self._assemble_stream(conn, buf, st,
                                                  rows[i], n)
            else:
                err = self._decode_error(errors[i]) if i in errors \
                    else None
            times = (self._reply_times(id(conn), st, rows[i], n, resids[i])
                     if rx_marks else None)
            if resids[i]:
                del buf[:resids[i]]
            self.frames_routed += n
            routed += n
            for pkt in pkts:
                kids = pkt.get('children')
                if kids is not None:
                    self.lists_routed += 1
                    self.names_routed += len(kids)
            if pkts or err is not None:
                if lane is None:
                    conn.emit('ingestDeliver', pkts, err)
                else:
                    laned += lane(pkts, err, now, times)
            if n == max_frames:
                self.slots_bound += 1
                if err is None and len(buf) >= 4:
                    retick = True   # at the frame bound: more may wait
        if retick:
            self._retick = True
            self._schedule()
        return laned, routed - laned

    def _reply_times(self, cid: int, st, row: int, n: int,
                     resid: int) -> list | None:
        """Profiler sessions only: for each of the ``n`` frames the
        tick found in row ``row`` of ``st``, the start of the receive
        call that brought the frame's LAST byte — the first of the
        slot's marks (``_rx_marks``) that reaches the frame's end, the
        newest where bytes came unmarked — in frame order; the marks
        then follow the slot, which the route cuts by ``resid``.  None
        for a slot no armed call has fed."""
        marks = self._rx_marks.get(cid)
        if not marks:
            return None
        times = []
        j, last = 0, len(marks) - 1
        for end in (st.starts[row, :n] + st.sizes[row, :n]).tolist():
            while j < last and marks[j][0] < end:
                j += 1
            times.append(marks[j][1])
        left = [[end - resid, t] for end, t in marks if end > resid]
        if left:
            self._rx_marks[cid] = left
        else:
            del self._rx_marks[cid]
        return times

    def _decode_batch(self, streams, n_frames, resids, bads):
        """The C fast path: every stream's device-delimited
        complete-frame slice decoded in ONE call of
        the C-extension decoder — the same code the scalar drain runs,
        so parity is by construction, at C speed.  The device scan
        already proved each slice frame-complete and length-valid
        (``bad`` streams take :meth:`_deliver_fallback`).  Returns
        None when no stream's codec has the extension, else
        ``(lens, maps, pkts, counts, errors)``: ``lens[i]`` the bytes
        of stream ``i`` that were decoded — 0 for one left to
        :meth:`_deliver_fallback` or :meth:`_assemble_stream` (bad, no
        complete frame, a codec without the extension) — against
        ``maps[i]``; the packets of all of them in ONE flat list,
        ``counts[i]`` each; ``errors[i]`` where a stream's decode
        failed.  No buffer is consumed here: the route does that,
        stream by stream.

        Children lists whose names are byte-equal within the call (a
        herd's re-lists) share their ``str`` objects: each packet has
        its own ``list``, so a listener that sorts or edits its view
        changes nobody else's; only ``is`` between two sessions' names
        can tell.  ``lists_shared`` counts them."""
        ext = None
        bufs, lens, maps = [], [], []
        for (conn, buf, _lane), n, resid, bad in zip(
                streams, n_frames, resids, bads):
            codec = conn.codec
            if bad or not n or codec.ext is None:
                resid = 0
            else:
                ext = codec.ext     # the process has one
            bufs.append(buf)
            lens.append(resid)
            maps.append(codec.xid_map)
        if ext is None:
            return None
        pkts, counts, _consumed, errors, (_lists, shared) = \
            ext.decode_streams(bufs, lens, maps, MAX_PACKET)
        self.lists_shared += shared
        return lens, maps, pkts, counts, errors

    @staticmethod
    def _decode_error(what) -> ZKProtocolError:
        """One stream's entry in ``decode_streams``' errors, typed as
        the scalar drain types it."""
        if isinstance(what, BaseException):
            err = ZKProtocolError('BAD_DECODE',
                'Failed to decode Response: %s: %s'
                % (type(what).__name__, what))
            err.__cause__ = what
            return err
        return ZKProtocolError(*what)

    def _deliver_scalar(self, conn: 'ZKConnection', buf: bytearray,
                        keep_stream: bool = True) -> None:
        """Drain one stream through the connection's own codec and emit
        the result — the scalar-parity delivery shared by the small-tick
        bypass (``keep_stream=True``: partial-frame residue returns to
        this slot's accumulator, traffic is counted) and the bad-frame
        fallback (``keep_stream=False``: the error the codec raises is
        the point; the stream is about to die)."""
        data, err, pkts = bytes(buf), None, []
        buf.clear()
        self._rx_marks.pop(id(conn), None)
        try:
            pkts = conn.codec.decode(data)
        except ZKProtocolError as e:
            pkts = getattr(e, 'packets', [])
            err = e
        else:
            if keep_stream:
                resid = conn.codec.take_pending()
                if resid:
                    buf.extend(resid)
        if keep_stream:
            self.frames_routed += len(pkts)
            if not pkts and err is None:
                return
        conn.emit('ingestDeliver', pkts, err)

    def _deliver_fallback(self, conn: 'ZKConnection',
                          buf: bytearray) -> None:
        self._deliver_scalar(conn, buf, keep_stream=False)

    # -- host packet assembly --

    def _assemble_stream(self, conn, buf, st, i: int, n: int):
        """Build the packet dicts for stream ``i``'s ``n`` frames from
        the tick's planes (the streams :meth:`_decode_batch` took never
        come here).  Returns (packets, err); a decode failure
        mid-stream keeps the packets decoded before it, like
        PacketCodec.decode."""
        if not n:
            return [], None
        pkts: list[dict] = []
        xid_map = conn.codec.xid_map
        # bulk-convert the header planes for this stream to Python ints
        # once: per-element numpy scalar indexing and (hi, lo) numpy
        # arithmetic cost ~10x the whole packet-dict build
        xids = st.xids[i, :n].tolist()
        zhis = st.zxid_hi[i, :n].tolist()
        zlos = st.zxid_lo[i, :n].tolist()
        errs = st.errs[i, :n].tolist()
        for f in range(n):
            xid = xids[f]
            opcode = SPECIAL_XIDS.get(xid)
            if opcode is None:
                opcode = xid_map.pop(xid, None)
            if opcode is None:
                return pkts, ZKProtocolError('BAD_DECODE',
                    'Failed to decode Response: ValueError: reply xid '
                    '%d matches no request' % (xid,))
            zxid = ((zhis[f] & 0xFFFFFFFF) << 32) | (zlos[f] & 0xFFFFFFFF)
            if zxid >= 1 << 63:
                zxid -= 1 << 64
            pkt = {
                'xid': xid,
                'zxid': zxid,
                'err': err_name(errs[f]),
                'opcode': opcode,
            }
            if pkt['err'] == 'OK' and opcode not in _EMPTY_RESPONSES:
                try:
                    self._read_body(pkt, buf, st, i, f)
                except ZKProtocolError as e:
                    return pkts, e
                except Exception as e:
                    err = ZKProtocolError('BAD_DECODE',
                        'Failed to decode Response: %s: %s'
                        % (type(e).__name__, e))
                    err.__cause__ = e
                    return pkts, err
            pkts.append(pkt)
        return pkts, None

    def _read_body(self, pkt, buf, st, i: int, f: int) -> None:
        """Fill ``pkt`` with its opcode-specific body: the scalar
        reader positioned at the device-located body offset."""
        opcode = pkt['opcode']
        start = int(st.starts[i, f])
        size = int(st.sizes[i, f])
        r = JuteReader(bytes(buf[start + REPLY_HDR:start + size]))
        reader = _RESP_READERS.get(opcode)
        if reader is None:
            raise ValueError('unsupported reply opcode %r' % (opcode,))
        reader(r, pkt)
