"""Lightweight causal tracing: client op spans + member span chains.

The metrics layer answers "how much / how slow in aggregate"; this
module answers "what happened to THAT request".  A :class:`Span` is
created per client op (client.py), threaded by xid through the
connection's pending-request table (io/connection.py) and stamped with
the reply's zxid when the reply routes back; the session layer records
notification deliveries into the same ring (io/session.py), so one
dump interleaves requests, replies, errors, and watch notifications in
arrival order.

Since the server grew its own trace plane, every ensemble member also
carries a ring (server/server.py ``ZKServer.trace``): a write txn
leaves a **zxid-keyed span chain** across the ensemble — the batch
decode (``SRV_DECODE``), the store apply (``COMMIT``), the WAL append
(``WAL_APPEND``), the group fsync its ack rode (``GROUP_FSYNC``, one
span shared by every txn in the barrier, stamped with the batch size),
the replication push per follower (``REPL_PUSH``), each follower's
apply (``APPLY``), and the watch fan-out delivery (``FANOUT``, watch
count + flushed bytes).  :func:`merge_timelines` joins the client ring
and any number of member rings **by zxid** into one causal timeline;
:func:`format_timeline` renders it.  ``python -m zkstream_tpu
timeline`` demos the merge end to end, and both chaos tiers dump the
member rings next to the client ring on failure.

Spans live in a bounded in-memory ring buffer (:class:`TraceRing`) —
fixed memory, no I/O, safe to leave on in production; overwrites are
counted in :attr:`TraceRing.dropped` (the ``zk_trace_ring_dropped``
mntr row).  The chaos campaign (io/faults.py, tests/test_chaos.py,
``chaos`` CLI) dumps the rings alongside the failing seed, so a
schedule failure arrives with the exact cross-member path of the
lost or duplicated write instead of a log-grepping session.

``TRACE_SCHEMA`` versions every JSON emission of spans
(``chaos --trace-out``, the ``trce`` admin word, ``timeline --json``);
:meth:`Span.to_dict` emits its keys in one fixed order so dumps are
byte-stable for a given span.

**Host spans** (:func:`host_span`) are the third kind: where a process
spends its own loop time (the fleet ingest's tick and its phases, the
client's receive and submit paths).  They are armed by the JAX
profiler session and by nothing else — no option, no environment
variable: while ``jax.profiler.start_trace`` is active a host span is
a ``jax.profiler.TraceAnnotation`` (an event on the calling thread's
line of ``/host:CPU`` in the ``.xplane.pb``, on the clock of the
device's ``XLA Ops`` line) AND a settled :class:`Span` in the
process-wide :data:`host_ring`; with no session it is one
``is_enabled()`` call and a shared no-op.

What a session leaves in ``host_ring.totals`` (``[count, total_ns]``
a name, no object an op) beside the spans, and what an operator reads
from each:

- ``client.prepare`` / ``client.submit`` / ``client.resume`` — the
  loop time of one API call before its request is built (cache and
  read-plane routing, the connection lookup), while it is encoded
  and handed to the send plane, and after its reply woke the caller
  (the deadline entry's discard, the latency observation, ``on_op``);
  ``client.rx``, ``client.flush``, ``client.handoff``, ``client.reap``,
  ``client.send``, ``client.notify``, ``client.deadline`` as before.
- ``client.connect`` / ``client.close`` — a session's birth and death
  on the wall clock (not loop time: the loop serves the rest of the
  fleet meanwhile): ``Client.start()`` until its first ``'connect'``,
  and ``Client.close()`` from the call to its return.
- ``client.cork_wait`` / ``client.wire_wait`` / ``client.tick_wait`` /
  ``client.wake_wait`` — a request's latency by stage, one count an
  op, stamped on ``time.perf_counter_ns`` (:func:`op_resumed`):
  submitted -> the tier flush that took its bytes (corked, or held
  behind the connection's batch in flight); that flush -> the
  ``_sock_data`` call that brought its reply (the send, the member,
  and the kernel's socket buffer while the loop was busy); that call
  -> ``ZKRequest.settle`` (in the ingest's slot, the tick, the route
  up to this frame); settle -> the awaiting coroutine running again
  (the rest of the route and the loop's ready queue).  The four sum
  to the op's ``t1_ns - t0_ns``, which its :class:`Span` carries
  with the ``tick`` that routed it.
- ``gc.pause`` and ``gc.pause@<span>`` — the garbage collector's
  pauses inside the session (one ``gc.callbacks`` hook, installed by a
  process's first armed span, inert outside a session; each pause is
  a ``gc.pause`` annotation in the trace too), and the part of them
  that began while ``<span>`` was the innermost host span open on
  that thread: subtract it from that span's own total.
- ``loop.idle``, ``loop.named``, ``loop.gap@<previous>><next>`` and
  ``gc.pause@loop.gap`` — a client loop's whole turn (see "The loop's
  whole turn", below): blocked in ``select``; under any top-level
  span (``loop.idle`` among them); between two of them, named by
  both; and the collections that began there.  How much of my loop is
  the library (the named spans less ``loop.idle``), how much my own
  code (``client.resume>client.prepare``), how much the loop itself
  (every other gap).
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import sys
import threading
import time

#: Version stamp for every JSON emission of span dumps.  Bump when
#: span fields or their meaning change; consumers key on it.
#: Schema 2: member rings (``member``/``batch``/``nbytes``/``detail``
#: fields, server-side ops), stable-ordered ``Span.to_dict``.
#: Schema 3: host spans (``parent``/``tick``/``t0_ns``/``t1_ns``
#: fields, emitted after the schema-2 keys; spans that do not carry
#: them serialize exactly as under schema 2).
TRACE_SCHEMA = 3

#: A span's optional fields, each None until something stamps it.
#: The ONE list of them: they are slots of the class, so neither
#: ``Span.__init__`` nor ``TraceRing.note`` names a field it does not
#: set (``Span.__getattr__`` answers None for the rest), and it is
#: ``to_dict``'s emission order (after the always-present keys) —
#: fixed, so a span serializes byte-identically regardless of which
#: setattr path populated it.
_OPTIONAL_FIELDS = ('path', 'xid', 'zxid', 'backend', 'session_id',
                    'member', 'batch', 'nbytes', 'detail', 'error',
                    'parent', 'tick', 't0_ns', 't1_ns', 'lane', 'emitted',
                    'rows', 'width', 'names', 'bound', 'cut', 'retick',
                    'lists', 'shared', 'headed', 'kept')

#: The slots that read None while nothing has stamped them.
_READS_NONE = frozenset(_OPTIONAL_FIELDS) | {'duration_ms', '_on_slow',
                                            'stages'}


class Span:
    """One traced operation: request-side fields stamped at creation,
    reply-side fields stamped on completion.

    A span has no ``__dict__``: one is made an op on the fleet's one
    loop, and a name outside its slots is refused (``AttributeError``),
    not kept.  Optional fields (``_OPTIONAL_FIELDS``) read None until
    stamped — an unstamped slot costs its reader an exception inside
    the interpreter, so the fields a client op's path reads
    (``stages``, ``_on_slow``, ``duration_ms``) are stamped at
    creation:
    ``member`` — which ensemble member recorded the span (None =
    client); ``batch`` — the batch size where the span covers several
    frames/txns (decode batch, group-fsync barrier, fan-out watch
    count); ``nbytes`` — bytes the span moved (WAL record, flushed
    fan-out bytes); ``detail`` — free-form qualifier (log-entry op,
    follower token); ``parent`` / ``tick`` / ``t0_ns`` / ``t1_ns`` —
    host spans (:func:`host_span`): the enclosing host span's name,
    the identifier everything under one ingest tick shares, start/end
    on ``time.perf_counter_ns`` — and, inside a profiler session, a
    client op's span too: the ``ingest.tick`` whose route settled it
    (None: settled off the device) and submit / resume on that clock
    (:func:`op_resumed`); ``lane`` / ``emitted`` — ``ingest.route``
    only: the tick's frames settled through the connections' direct
    lanes, and those handed to the ``'ingestDeliver'`` emitter path,
    ``names``, the names in the children lists it routed, and
    ``lists`` / ``shared``, those lists and the ones among them that
    the tick's one decode did not parse again;
    ``rows`` / ``width`` — ``ingest.dispatch`` only: the streams in
    the dispatch and the width of its size class (``nbytes``: their
    payload); ``bound`` / ``cut`` / ``retick`` — a device tick's
    ``ingest.tick`` only: the rows that gave the tick the whole frame
    bound (``max_frames`` frames), the slots that held more than they
    gave, and 1 where the tick left a follow-up tick for either;
    ``headed`` / ``kept`` — the header rows it was given (a slot that
    held exactly one whole frame wider than ``min_len``) and the bytes
    of their frames that stayed in the slots.

    Beside them: ``duration_ms``; ``_on_slow`` — armed by a ring with a
    slow-op threshold: called once with the span when finish()
    measures a duration at/over it; ``stages`` — a client op inside a
    profiler session: its stage stamps ``[t_submit, t_flush, t_rx,
    t_settle]`` on ``time.perf_counter_ns`` (0 = not reached), from
    ``_start_op`` until :func:`op_resumed` books them.  None is the
    op's answer to "was a session active when it was submitted": every
    later stamp is a branch on it.

    The span's start is ONE clock read (``time.monotonic``); its wall
    time :attr:`t_wall` is that plus ``_anchor``, the wall clock minus
    the monotonic one as the span's ring read them once when it was
    made — a step of the system's clock after that moves no span."""

    __slots__ = ('span_id', 'kind', 'op', 'status', '_t0', '_anchor',
                 'duration_ms', '_on_slow', 'stages') + _OPTIONAL_FIELDS

    def __init__(self, span_id: int, op: str, path: str | None = None,
                 kind: str = 'op'):
        self.span_id = span_id
        self.kind = kind  # 'op'|'notification'|'event'|'server'|...
        self.op = op
        self.path = path
        self.status: str = 'open'
        self._t0 = t0 = time.monotonic()
        self._anchor = time.time() - t0     # a ring stamps its own
        self.duration_ms: float | None = None
        self._on_slow = None
        self.stages: list | None = None

    def __getattr__(self, name: str):
        # reached only for a slot nothing has stamped yet
        if name in _READS_NONE:
            return None
        raise AttributeError('%r object has no attribute %r'
                             % (type(self).__name__, name))

    @property
    def t_wall(self) -> float:
        """The span's start on the wall clock."""
        return self._anchor + self._t0

    def finish(self, zxid: int | None = None, status: str = 'ok',
               error: str | None = None) -> None:
        """Close the span exactly once; a double-settle (teardown races
        in the connection) keeps the first outcome."""
        if self.status != 'open':
            return
        self.duration_ms = (time.monotonic() - self._t0) * 1000.0
        if zxid is not None:
            self.zxid = zxid
        self.status = status
        self.error = error
        hook = self._on_slow
        if hook is not None:
            self._on_slow = None
            hook(self)

    def to_dict(self) -> dict:
        """JSON-ready dict, keys in one fixed order (insertion order
        survives ``json.dumps``), so a span's serialization is stable
        across processes and runs."""
        d = {'span': self.span_id, 'kind': self.kind, 'op': self.op,
             'status': self.status,
             't_wall': round(self._anchor + self._t0, 6)}
        for field in _OPTIONAL_FIELDS:
            val = getattr(self, field)
            if val is not None:
                d[field] = val
        if self.duration_ms is not None:
            d['duration_ms'] = round(self.duration_ms, 3)
        return d

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return '<Span %s>' % (self.to_dict(),)


class TraceRing:
    """A bounded ring of recent spans: appends evict the oldest entry
    once ``capacity`` is reached — memory is fixed regardless of op
    volume — and :attr:`dropped` counts the evictions so a scrape can
    tell a quiet ring from one that wrapped.  ``member`` stamps every
    span recorded here with the owning ensemble member's id (None for
    the client ring)."""

    def __init__(self, capacity: int = 256,
                 member: str | None = None):
        assert capacity > 0, capacity
        self.capacity = capacity
        self.member = member
        #: ring overwrites since construction (the mntr
        #: ``zk_trace_ring_dropped`` row)
        self.dropped = 0
        #: Slow-op digest threshold in ms, or None (off).  When set,
        #: every span settled on this ring whose duration meets it is
        #: handed to :attr:`on_slow` — the black-box plane's hook
        #: (utils/blackbox.py persists the span's causal chain).
        self.slow_ms: float | None = None
        self.on_slow = None
        #: name -> [count, total_ns]: boundaries crossed once per op,
        #: where a Span object each would be the cost being measured
        #: (:func:`host_span` with ``accumulate=True``)
        self.totals: dict[str, list] = {}
        self._ring: collections.deque[Span] = collections.deque(
            maxlen=capacity)
        self._ids = itertools.count(1)
        #: wall clock minus monotonic clock, read once: every span of
        #: this ring starts with ONE clock read and derives its
        #: ``t_wall`` from this (:class:`Span`)
        self._anchor = time.time() - time.monotonic()

    def __len__(self) -> int:
        return len(self._ring)

    def _slow_settled(self, span: Span) -> None:
        """Span.finish() callback: apply the threshold (the hook fires
        on every settle; sub-threshold spans stop here)."""
        if (self.slow_ms is not None and self.on_slow is not None
                and span.duration_ms is not None
                and span.duration_ms >= self.slow_ms):
            self.on_slow(span)

    def start(self, op: str, path: str | None = None,
              kind: str = 'op') -> Span:
        # built inline, as ``note`` is: one is made an op
        span = Span.__new__(Span)
        span.span_id = next(self._ids)
        span.kind = kind
        span.op = op
        span.path = path
        span.status = 'open'
        span._t0 = time.monotonic()
        span._anchor = self._anchor
        span.duration_ms = None
        span.stages = None
        span._on_slow = (None if self.slow_ms is None
                         else self._slow_settled)
        if self.member is not None:
            span.member = self.member
        ring = self._ring
        if len(ring) >= self.capacity:
            self.dropped += 1       # the append below evicts one
        ring.append(span)
        return span

    def note(self, op: str, path: str | None = None,
             zxid: int | None = None, kind: str = 'event',
             **fields) -> Span:
        """Record an instantaneous event (notification delivery, state
        edge, a member-side txn stage) as an already-settled span.
        ``fields`` land last, so an explicit ``duration_ms=`` (a
        pre-measured stage, e.g. WAL_RECOVER or GROUP_FSYNC)
        overrides the 0 the instant close stamps.

        Built inline rather than via start()+finish(): this is the
        server hot path (a COMMIT + WAL_APPEND note per write txn),
        and skipping the open-span bookkeeping roughly halves the
        cost."""
        span = Span.__new__(Span)
        span.span_id = next(self._ids)
        span.kind = kind
        span.op = op
        span.path = path
        span.zxid = zxid
        span.member = self.member
        span.status = 'ok'
        span._t0 = time.monotonic()
        span._anchor = self._anchor
        span.duration_ms = 0.0      # already settled; checked below
        for name, val in fields.items():
            setattr(span, name, val)
        if len(self._ring) >= self.capacity:
            self.dropped += 1       # the append below evicts one
        self._ring.append(span)
        if (self.slow_ms is not None
                and span.duration_ms >= self.slow_ms):
            self._slow_settled(span)
        return span

    def spans(self) -> list[Span]:
        return list(self._ring)

    def open_spans(self) -> list[Span]:
        """Spans still unsettled — after teardown there must be none
        (the chaos campaigns assert it; an op evicted from the pending
        table without a settle is a span-leak bug)."""
        return [s for s in self._ring if s.status == 'open']

    def dump(self, last: int | None = None) -> list[dict]:
        """The ring's contents, oldest first, as JSON-ready dicts —
        all of them, or the newest ``last``."""
        ring = self._ring
        if last is not None and last < len(ring):
            ring = itertools.islice(ring, len(ring) - last, None)
        return [s.to_dict() for s in ring]

    def dump_json(self, indent: int | None = None) -> str:
        return json.dumps(self.dump(), indent=indent)

    def clear(self) -> None:
        self._ring.clear()

    def reset(self) -> None:
        """Empty the ring AND its books (``dropped``, ``totals``): the
        start of a new recording window."""
        self._ring.clear()
        self.totals.clear()
        self.dropped = 0


def format_spans(spans: list[dict], limit: int | None = None) -> str:
    """Render dumped spans as aligned text lines for failure reports
    (newest-last; ``limit`` keeps assertion messages bounded)."""
    if limit is not None and len(spans) > limit:
        spans = spans[-limit:]
    lines = []
    for s in spans:
        dur = ('%8.2fms' % s['duration_ms']
               if s.get('duration_ms') is not None else '      open')
        lines.append(
            '  #%-4d %-12s xid=%-6s zxid=%-6s %-7s %s %s%s'
            % (s['span'], s['op'], s.get('xid', '-'),
               s.get('zxid', '-'), s['status'], dur,
               s.get('path') or '',
               (' [%s]' % s['error']) if s.get('error') else ''))
    return '\n'.join(lines)


# ---------------------------------------------------------------------
# Cross-ring merge: the zxid-keyed causal timeline.
# ---------------------------------------------------------------------

#: Causal stage rank within one zxid: in-process hops settle within
#: the same millisecond, so wall time alone cannot order the chain —
#: the pipeline's actual order does.  Client op spans (submit) lead,
#: the client-side notification delivery trails.
_STAGE_RANK = {
    'COMMIT': 2,
    'WAL_APPEND': 3,
    'GROUP_FSYNC': 4,
    'REPL_PUSH': 5,
    'APPLY': 6,
    'FANOUT': 7,
    'NOTIFICATION': 8,
}
_STAGE_DEFAULT = 9


def _stage(span: dict) -> int:
    rank = _STAGE_RANK.get(span.get('op', ''))
    if rank is not None:
        return rank
    if span.get('kind') == 'op':
        return 1                    # client submit leads its zxid
    return _STAGE_DEFAULT


def merge_timelines(rings: dict[str, list[dict]]) -> list[dict]:
    """Merge span dumps from several rings into one causal timeline.

    ``rings`` maps a source name ('client', 'member:1', ...) to that
    ring's :meth:`TraceRing.dump`.  Every span carrying a zxid joins
    the timeline, stamped with its source (a span's own ``member``
    field wins over the ring name), ordered by
    ``(zxid, causal stage, wall time)`` — so a lagging follower's
    apply span, recorded long after later transactions, still merges
    back into its own zxid's group in causal position."""
    out: list[dict] = []
    for source, spans in rings.items():
        # a member-qualified ring name wins over the span's own member
        # field: a caller merging two same-id members keys them apart
        # ('member:0@hostB:2181', timeline --live) and that distinction
        # must survive into the rendered source
        qualified = source.startswith('member:')
        for s in spans:
            if s.get('zxid') is None:
                continue
            e = dict(s)
            member = s.get('member')
            e['source'] = ('member:%s' % (member,)
                           if member is not None and not qualified
                           else source)
            out.append(e)
    out.sort(key=lambda e: (e['zxid'], _stage(e),
                            e.get('t_wall', 0.0)))
    return out


def format_timeline(entries: list[dict],
                    limit: int | None = None) -> str:
    """Render a merged timeline as aligned text, one causal step per
    line, zxid-grouped (oldest first)."""
    if limit is not None and len(entries) > limit:
        entries = entries[-limit:]
    lines = []
    last_zxid = None
    for e in entries:
        zxid = e['zxid']
        zcol = ('zxid %-6d' % zxid) if zxid != last_zxid \
            else '     %-6s' % ''
        last_zxid = zxid
        extra = []
        if e.get('batch') is not None:
            extra.append('batch=%d' % e['batch'])
        if e.get('nbytes') is not None:
            extra.append('%dB' % e['nbytes'])
        if e.get('detail'):
            extra.append(str(e['detail']))
        if e.get('xid') is not None:
            extra.append('xid=%d' % e['xid'])
        if e.get('duration_ms'):
            extra.append('%.2fms' % e['duration_ms'])
        if e.get('error'):
            extra.append('[%s]' % e['error'])
        lines.append(('%s %-10s %-12s %-7s %s %s'
                      % (zcol, e.get('source', '?'), e['op'],
                         e.get('status', ''), e.get('path') or '-',
                         ' '.join(extra))).rstrip())
    return '\n'.join(lines)


# ---------------------------------------------------------------------
# Host spans: where this process's own loop time goes, on the
# profiler's clock.
# ---------------------------------------------------------------------

#: Sized for one 4 s profiler window of the busiest recorder: the
#: ingest leaves 5 spans a device tick (``ingest.tick`` and its four
#: phases) and ticks at most ~200 times a second (a 2 ms tick floor on
#: the chip: ~800 ticks, 4,000 spans in 4 s); 16,384 is four times
#: that.  A longer session wraps, and ``host_ring.dropped`` says so.
HOST_RING_CAPACITY = 16384

#: The process-wide ring of host spans.  It holds exactly one profiler
#: session: the first host span of a new session resets it.
host_ring = TraceRing(HOST_RING_CAPACITY)


class _NoSpan:
    """What :func:`host_span` returns with no profiler session: one
    shared object, nothing recorded, nothing allocated."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **fields) -> None:
        pass

    def cancel(self) -> None:
        pass


NO_SPAN = _NoSpan()

#: ``jax.profiler.TraceAnnotation`` and its ``is_enabled``, bound the
#: first time a host span is asked for AFTER something else imported
#: jax: this module never imports it (an ensemble member never does,
#: server/member_worker.py), and without jax no session can be active.
_annotation = None
_is_enabled = None
#: a session was active at the last look (its first span reset the ring)
_recording = False


#: ``turn``, a thread's own list (``L_*``; made by :func:`_turn`): the
#: innermost host span open on the thread and — on a thread whose
#: event loop carries the idle hook (:func:`loop_idle`), else None —
#: the profiler session its mark belongs to (a mark of an earlier one
#: opens no gap), the last top-level span's end and name, that name's
#: row of ``_gaps``, and the session's ``loop.named`` total: what the
#: last top-level span that closed there left for the next, the gap's
#: start.
_open = threading.local()
L_SPAN, L_SESSION, L_END, L_NAME, L_GAPS, L_NAMED = range(6)
#: the number of the profiler session the ring holds
_session = 0
#: previous span's name -> {next span's name -> the ``[count,
#: total_ns]`` under ``loop.gap@<previous>><next>`` in ``host_ring.
#: totals``}: a pair's key is built once a session (some forty occur)
_gaps: dict = {}


def _turn() -> list:
    """This thread's ``turn``, made at its first host span."""
    try:
        return _open.turn
    except AttributeError:
        turn = _open.turn = [None, None, 0, None, None, None]
        return turn


def _bind() -> bool:
    """Bind the profiler's annotation type and its switch; False while
    jax is not (fully) imported in this process."""
    global _annotation, _is_enabled
    prof = getattr(sys.modules.get('jax'), 'profiler', None)
    ann = getattr(prof, 'TraceAnnotation', None)
    if ann is None:
        return False
    _annotation = ann
    if _is_enabled is None:
        _is_enabled = ann.is_enabled
    return True


def _begin_session() -> None:
    """The first look inside a new profiler session: the ring holds
    exactly one session, and the collector's pauses are recorded
    (their total is there from the start: a session without a
    collection reads zero pauses, not an unrecorded figure)."""
    global _recording, _session
    host_ring.reset()
    host_ring.totals['gc.pause'] = [0, 0]
    _gaps.clear()
    _session += 1
    if _gc_pause not in gc.callbacks:
        gc.callbacks.append(_gc_pause)
    _recording = True


def armed() -> bool:
    """Is a profiler session active?  (``host_span`` asks the same
    question inline: it is the one call an op pays outside a
    session.)"""
    global _recording
    if (_annotation is None and not _bind()) or not _is_enabled():
        _recording = False
        return False
    if not _recording:
        _begin_session()
    return True


def _add(name: str, count: int, total_ns: int) -> None:
    tot = host_ring.totals.get(name)
    if tot is None:
        tot = host_ring.totals[name] = [0, 0]
    tot[0] += count
    tot[1] += total_ns


def host_span(name: str, accumulate: bool = False, **ids):
    """Mark a span of this thread's time: ``with host_span('ingest.
    batch', tick=n): ...``.

    With no profiler session active this is one ``is_enabled()`` call
    and returns :data:`NO_SPAN`.  Inside one, the span is (1) a
    ``jax.profiler.TraceAnnotation(name, **ids)`` and (2) on exit a
    settled :class:`Span` in :data:`host_ring` — ``kind='host'``,
    ``op`` the name, ``parent`` the enclosing host span's name,
    ``ids`` (``tick``; a dispatch's ``rows`` / ``width`` / ``nbytes``)
    as fields, ``t0_ns``/``t1_ns`` from
    ``time.perf_counter_ns`` — plus whatever :meth:`set` added
    (``batch``, ``nbytes``, ``detail``).  ``accumulate=True`` is for a
    boundary crossed once per op: the annotation is opened, but the
    ring gets ``(count, total_ns)`` under the name
    (``host_ring.totals``) and no object."""
    global _recording
    if (_annotation is None and not _bind()) or not _is_enabled():
        _recording = False
        return NO_SPAN
    if not _recording:
        _begin_session()
    return _HostSpan(name, accumulate, ids)


def host_add(name: str, count: int, total_ns: int) -> None:
    """Add to ``host_ring.totals[name]`` work that was counted and
    timed elsewhere — a batch of ``count`` units that took
    ``total_ns`` on the caller's clock or on a native thread's own
    (the send plane's ``client.send``: connections sent to, and the
    nanoseconds inside their ``send(2)`` loop).  Armed like
    :func:`host_span`: nothing outside a profiler session."""
    if armed():
        _add(name, count, total_ns)


class _HostSpan:
    __slots__ = ('name', 'ids', 'fields', '_accumulate', '_ann',
                 '_parent', '_turn', 't0_ns', '_cancelled')

    def __init__(self, name: str, accumulate: bool, ids: dict):
        self.name = name
        self.ids = ids
        self.fields: dict | None = None
        self._accumulate = accumulate
        self._cancelled = False

    def set(self, **fields) -> None:
        """Span fields known only once the work is under way."""
        if self.fields is None:
            self.fields = fields
        else:
            self.fields.update(fields)

    def cancel(self) -> None:
        """Leave nothing in the ring (the tick routed no work)."""
        self._cancelled = True

    def __enter__(self):
        try:
            turn = self._turn = _open.turn
        except AttributeError:
            turn = self._turn = _turn()
        parent = self._parent = turn[L_SPAN]
        turn[L_SPAN] = self
        self._ann = _annotation(self.name, **self.ids)
        self._ann.__enter__()
        t0 = self.t0_ns = time.perf_counter_ns()
        if parent is None and turn[L_SESSION] == _session:
            # a top-level span closes the gap its predecessor on this
            # loop's thread opened ("The loop's whole turn", below)
            tot = turn[L_GAPS].get(self.name)
            if tot is None:
                tot = turn[L_GAPS][self.name] = host_ring.totals[
                    'loop.gap@%s>%s' % (turn[L_NAME], self.name)
                ] = [0, 0]
            tot[0] += 1
            tot[1] += t0 - turn[L_END]
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        turn = self._turn
        parent = turn[L_SPAN] = self._parent
        if parent is None and turn[L_SESSION] is not None:
            # ... and opens the next (a cancelled span too: the
            # thread's time was under it all the same)
            if turn[L_SESSION] != _session:
                turn[L_SESSION] = _session
                turn[L_NAMED] = host_ring.totals.setdefault(
                    LOOP_NAMED, [0, 0])
            name = turn[L_NAME] = self.name
            turn[L_END] = t1
            gaps = _gaps.get(name)
            if gaps is None:
                gaps = _gaps[name] = {}
            turn[L_GAPS] = gaps
            named = turn[L_NAMED]
            named[0] += 1
            named[1] += t1 - self.t0_ns
        if self._cancelled:
            return False
        if self._accumulate:
            _add(self.name, 1, t1 - self.t0_ns)
            return False
        # what the caller knew at the start (``ids``: the annotation's
        # own stats in the trace) and what it set under way
        fields = dict(self.ids, **(self.fields or {}))
        fields.setdefault('tick', None)
        host_ring.note(
            self.name, kind='host',
            parent=None if parent is None else parent.name,
            t0_ns=self.t0_ns, t1_ns=t1,
            duration_ms=(t1 - self.t0_ns) / 1e6, **fields)
        return False


# ---------------------------------------------------------------------
# The loop's whole turn: inside a profiler session every nanosecond of
# a client loop's thread is under a top-level host span, in a named gap
# between two of them, or idle (which is a top-level span too).
# ---------------------------------------------------------------------

#: ``host_ring.totals`` names.  ``loop.gap@<previous>><next>``: the
#: time between the end of one top-level span and the start of the
#: next on a loop's thread, one count a gap — WHAT it was follows from
#: its neighbours (``client.resume>client.prepare`` is the caller's own
#: code between one reply and its next request; ``client.submit>
#: client.resume``, ``ingest.tick>client.resume``, ``X>loop.idle`` are
#: asyncio's switch and nothing else).  Both stamps are the spans' own
#: (``t0_ns`` is read after the annotation's ``__enter__``, ``t1``
#: before its ``__exit__``), so a gap holds the closing annotation's
#: exit, the next ``host_span()`` call and the opening annotation's
#: enter: the instrument's floor a gap, which two empty top-level spans
#: opened back to back book alone.  ``loop.named``: every top-level
#: span's own ``t1 - t0_ns`` (``loop.idle`` among them).
#: ``gc.pause@loop.gap``: the collections that began under no span,
#: taken out of the gap they fell in — so ``loop.named`` + every gap +
#: ``gc.pause@loop.gap`` is the thread's time from its first top-level
#: span's start to its last one's end.
LOOP_NAMED = 'loop.named'
LOOP_GAP = 'loop.gap'


def loop_idle():
    """Host span ``loop.idle`` (count and total), for the one hook an
    event loop's ``select`` carries (utils/aio.DeadlineQueue): the loop
    blocked waiting for sockets and timers.  Asking for it inside a
    session is what makes the calling thread a loop's thread: from its
    first ``loop.idle`` on, the thread's top-level spans book their
    gaps and ``loop.named``.  A span on any other thread (a
    ``prewarm`` on an executor) books neither."""
    sp = host_span('loop.idle', accumulate=True)
    if sp is not NO_SPAN:
        turn = _turn()
        if turn[L_SESSION] is None:
            turn[L_SESSION] = 0     # no session's: no predecessor
    return sp


# ---------------------------------------------------------------------
# A request's latency by stage: the waits no span of the loop's own
# time can name, because the loop is doing something else meanwhile.
# ---------------------------------------------------------------------

#: Indices into ``Span.stages``.
T_SUBMIT, T_FLUSH, T_RX, T_SETTLE = range(4)

#: The totals the four differences are booked under, in stage order:
#: submit -> flush -> rx -> settle -> resume.
STAGE_WAITS = ('client.cork_wait', 'client.wire_wait',
               'client.tick_wait', 'client.wake_wait')


def stamp_flush(stamps: list, t_ns: int = 0) -> None:
    """The requests corked since the last flush (``stamps``: their
    ``Span.stages``, emptied here) have their bytes taken by the flush
    that began at ``t_ns`` (now, by default)."""
    t_ns = t_ns or time.perf_counter_ns()
    for st in stamps:
        st[T_FLUSH] = t_ns
    stamps.clear()


def stamp_reply(span: Span, rx: tuple) -> None:
    """``ZKRequest.settle`` of a staged op: ``rx`` is what the
    connection knows of the reply's way in — the start of the
    ``_sock_data`` call that brought the connection's newest bytes,
    and the number of the ``ingest.tick`` whose route is delivering it
    (None off the device)."""
    st = span.stages
    st[T_RX], span.tick = rx
    st[T_SETTLE] = time.perf_counter_ns()


def op_submitted() -> '_HostSpan':
    """An op is submitted inside a profiler session (the caller has
    asked: :func:`armed`, or a ``host_span`` that was not
    :data:`NO_SPAN`): open host span ``client.submit``, which the
    caller closes (``__exit__``) once the request is with the send
    plane.  Its ``t0_ns`` is the op's ``t_submit``."""
    return _HostSpan('client.submit', True, {}).__enter__()


def op_resumed(span: Span):
    """The awaiter of a staged op runs again (``Client._await_op``,
    whatever the outcome): book the op's four waits — one count each,
    here and nowhere else, so an op that expired, failed or whose
    reply came late is counted once too — and open host span
    ``client.resume``, which the caller closes (``__exit__``).  A
    stage the op never reached takes no time: its stamp is the next
    one's (an expired op's ``wire_wait`` runs to its resume).  The
    span keeps ``t0_ns`` / ``t1_ns`` = submit / resume, so the four
    sum to ``t1_ns - t0_ns``.  None, and nothing booked, when the
    session has ended meanwhile."""
    t_resume = time.perf_counter_ns()
    (t_submit, t_flush, t_rx, t_settle), span.stages = span.stages, None
    if not armed():
        return None
    span.t0_ns, span.t1_ns = t_submit, t_resume
    # backwards from the resume: a stamp that is missing, or (a reply
    # routed after the awaiter gave up) past its successor, takes the
    # successor's time
    if not 0 < t_settle <= t_resume:
        t_settle = t_resume
    if not 0 < t_rx <= t_settle:
        t_rx = t_settle
    if not t_submit <= t_flush <= t_rx:
        t_flush = t_rx
    cork, wire, tick, wake = STAGE_WAITS
    _add(cork, 1, t_flush - t_submit)
    _add(wire, 1, t_rx - t_flush)
    _add(tick, 1, t_settle - t_rx)
    _add(wake, 1, t_resume - t_settle)
    return _HostSpan('client.resume', True, {}).__enter__()


# ---------------------------------------------------------------------
# The collector's pauses: they land inside whatever span is open.
# ---------------------------------------------------------------------

#: the pause under way: (annotation, innermost open host span's name,
#: start on ``time.perf_counter_ns``)
_gc_open = None


def _gc_pause(phase: str, info: dict) -> None:
    """The one ``gc.callbacks`` hook (installed by :func:`armed`):
    inside a profiler session a collection is a ``gc.pause``
    annotation from its ``start`` to its ``stop`` and one count in
    ``host_ring.totals['gc.pause']`` — and in ``['gc.pause@<name>']``
    for the innermost host span open on this thread when it began, so
    a reader can take the pauses out of the span that held them; one
    that began under no span on a loop's thread is booked under
    ``gc.pause@loop.gap`` and taken out of that gap here.
    Outside a session it is one ``is_enabled()`` a collection."""
    global _gc_open
    if phase == 'start':
        if not armed():
            return
        ann = _annotation('gc.pause')
        ann.__enter__()
        turn = _turn()
        holder = turn[L_SPAN]
        if holder is not None:
            holder = holder.name
        elif turn[L_SESSION] == _session:
            holder = LOOP_GAP
        _gc_open = (ann, holder, time.perf_counter_ns())
    elif _gc_open is not None:
        t1 = time.perf_counter_ns()
        ann, holder, t0 = _gc_open
        _gc_open = None
        ann.__exit__(None, None, None)
        _add('gc.pause', 1, t1 - t0)
        if holder is not None:
            _add('gc.pause@' + holder, 1, t1 - t0)
            if holder == LOOP_GAP:
                # the gap the pause fell in starts that much later
                _open.turn[L_END] += t1 - t0
