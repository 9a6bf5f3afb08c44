"""Prometheus-style metrics: counters, gauges, and histograms.

The reference records client events through the artedi collector
(reference: lib/client.js:46-61, lib/zk-session.js:61-65).  This is a
dependency-free equivalent: labelled counters, pull-model gauges, and
cumulative-bucket histograms with text exposition in the Prometheus
format.  A caller may supply their own ``Collector`` to ``Client`` or
let one be created internally, as in the reference.

Label values are escaped per the Prometheus exposition spec
(backslash, double quote, and newline), so a path or error string can
ride in a label without producing unparseable scrape output.
"""

from __future__ import annotations

import time
from bisect import bisect_left

from .aio import ambient_loop


def escape_label_value(value) -> str:
    """Escape a label value per the Prometheus text exposition format:
    ``\\`` -> ``\\\\``, ``"`` -> ``\\"``, newline -> ``\\n``."""
    return (str(value)
            .replace('\\', '\\\\')
            .replace('"', '\\"')
            .replace('\n', '\\n'))


def _render_labels(key: tuple[tuple[str, str], ...],
                   extra: tuple[tuple[str, str], ...] = ()) -> str:
    pairs = tuple(key) + tuple(extra)
    if not pairs:
        return ''
    return '{%s}' % ','.join(
        '%s="%s"' % (k, escape_label_value(v)) for k, v in pairs)


def _label_key(labels) -> tuple[tuple[str, str], ...]:
    """Normalize a label set (dict, or an iterable of (k, v) pairs —
    MultiGauge callbacks need hashable keys) to a sorted tuple."""
    if not labels:
        return ()
    items = labels.items() if isinstance(labels, dict) else labels
    if len(labels) == 1:
        return tuple(items)         # one pair is sorted
    return tuple(sorted(items))


#: what a series is keyed by, for a caller that resolves its label set
#: once (``Counter.add``)
label_key = _label_key


class Counter:
    def __init__(self, name: str, help_text: str = ''):
        self.name = name
        self.help = help_text
        self._values: dict[tuple[tuple[str, str], ...], float] = {}
        #: Counters other objects own and feed, read as part of this
        #: one (:meth:`Collector.adopt`).
        self._linked: list[Counter] = []

    def increment(self, labels: dict[str, str] | None = None,
                  by: float = 1.0) -> None:
        key = _label_key(labels)
        self._values[key] = self._values.get(key, 0.0) + by

    def add(self, key: tuple[tuple[str, str], ...],
            by: float = 1.0) -> None:
        """:meth:`increment` for a caller that kept its label set's
        :func:`label_key` (utils/fsm.py: a transition counts into one
        of a few series, over and over)."""
        self._values[key] = self._values.get(key, 0.0) + by

    def link(self, other: Counter) -> None:
        if other is not self and other not in self._linked:
            self._linked.append(other)

    def _merged(self) -> dict:
        """Own values plus the linked counters', per label set."""
        if not self._linked:
            return self._values
        out = dict(self._values)
        for other in self._linked:
            for key, val in other._values.items():
                out[key] = out.get(key, 0.0) + val
        return out

    def value(self, labels: dict[str, str] | None = None) -> float:
        return self._merged().get(_label_key(labels), 0.0)

    def label_keys(self) -> list[tuple[tuple[str, str], ...]]:
        """Every label set with a recorded value (scrape helpers walk
        this to enumerate series, like Histogram.label_keys)."""
        return list(self._merged())

    def expose(self) -> str:
        lines = []
        if self.help:
            lines.append('# HELP %s %s' % (self.name, self.help))
        lines.append('# TYPE %s counter' % (self.name,))
        for key, val in sorted(self._merged().items()):
            lines.append('%s%s %s' % (self.name, _render_labels(key),
                                      val))
        return '\n'.join(lines)


class Gauge:
    """A pull-model gauge: the value is read from a callback at
    exposition time — zero hot-path cost for instrumented components
    (the fleet ingest binds its tick/frame counters this way)."""

    def __init__(self, name: str, fn, help_text: str = ''):
        self.name = name
        self.help = help_text
        self._fn = fn

    def expose(self) -> str:
        lines = []
        if self.help:
            lines.append('# HELP %s %s' % (self.name, self.help))
        lines.append('# TYPE %s gauge' % (self.name,))
        try:
            val = self._fn()
        except Exception:  # a dead callback must not sink exposition
            val = float('nan')
        lines.append('%s %s' % (self.name, val))
        return '\n'.join(lines)


class MultiGauge:
    """A pull-model gauge with one series per label set: the callback
    returns ``{labels_dict: value}`` at exposition time.  Used for the
    FSM current-state gauge, where the series population (which
    machines exist, which states they sit in) changes at runtime."""

    def __init__(self, name: str, fn, help_text: str = ''):
        self.name = name
        self.help = help_text
        self._fn = fn

    def expose(self) -> str:
        lines = []
        if self.help:
            lines.append('# HELP %s %s' % (self.name, self.help))
        lines.append('# TYPE %s gauge' % (self.name,))
        try:
            values = {_label_key(labels): val
                      for labels, val in self._fn().items()}
        except Exception:  # a dead callback must not sink exposition
            lines.append('%s %s' % (self.name, float('nan')))
            return '\n'.join(lines)
        for key, val in sorted(values.items()):
            lines.append('%s%s %s' % (self.name, _render_labels(key),
                                      val))
        return '\n'.join(lines)


#: Default latency buckets, milliseconds: sub-ms client-loop hops up
#: through multi-second retry storms.
DEFAULT_BUCKETS = (0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
                   250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0)


class BoundSeries:
    """One series of a :class:`Histogram`, its label set resolved
    once (:meth:`Histogram.labels`): ``observe(value)`` touches the
    series' row directly — a bisect over the bounds and two adds —
    where ``Histogram.observe(value, labels)`` first normalizes and
    hashes the label set, every call.  Both land in the same row."""

    __slots__ = ('_row', '_bounds')

    def __init__(self, row: list, bounds: tuple):
        self._row = row
        self._bounds = bounds

    def observe(self, value: float) -> None:
        row = self._row
        # the first bound with value <= bound; past the last: +Inf
        row[bisect_left(self._bounds, value)] += 1
        row[-1] += value


class Histogram:
    """A labelled Prometheus histogram: cumulative ``_bucket`` series
    (``le`` upper bounds plus ``+Inf``), ``_sum``, and ``_count``.

    ``observe`` is the hot-path call: the label set's key, one bisect
    over a small tuple of bounds plus two adds — cheap enough for
    per-op recording; a caller that observes one series over and over
    binds it once (:meth:`labels`) and skips the key."""

    def __init__(self, name: str, help_text: str = '',
                 buckets=DEFAULT_BUCKETS):
        self.name = name
        self.help = help_text
        bounds = tuple(sorted(float(b) for b in buckets))
        assert bounds, 'histogram needs at least one bucket bound'
        self.buckets = bounds
        #: label key -> [per-bucket counts..., +Inf count, sum]
        self._series: dict[tuple[tuple[str, str], ...], list] = {}
        #: label key -> the series' rendered row keys (:meth:`rows`)
        self._row_names: dict[tuple[tuple[str, str], ...], list] = {}
        #: Histograms other objects own and feed, read as part of
        #: this one (:meth:`Collector.adopt`).
        self._linked: list[Histogram] = []

    def link(self, other: Histogram) -> None:
        """``other`` has this histogram's buckets (the collector's
        registration checks)."""
        if other is not self and other not in self._linked:
            self._linked.append(other)

    def _merged(self) -> dict:
        """Own series plus the linked histograms', per label set."""
        if not self._linked:
            return self._series
        out = dict(self._series)
        for other in self._linked:
            for key, row in other._series.items():
                mine = out.get(key)
                out[key] = (list(row) if mine is None
                            else [a + b for a, b in zip(mine, row)])
        return out

    def _row(self, labels: dict[str, str] | None) -> list:
        key = _label_key(labels)
        row = self._series.get(key)
        if row is None:
            row = self._series[key] = [0] * (len(self.buckets) + 1) \
                + [0.0]
        return row

    def labels(self, labels: dict[str, str] | None = None) \
            -> BoundSeries:
        """The series of one label set as a handle to observe into
        (made with its row on first ask)."""
        return BoundSeries(self._row(labels), self.buckets)

    def observe(self, value: float,
                labels: dict[str, str] | None = None) -> None:
        row = self._row(labels)
        row[bisect_left(self.buckets, value)] += 1
        row[-1] += value

    def count(self, labels: dict[str, str] | None = None) -> int:
        row = self._merged().get(_label_key(labels))
        return sum(row[:-1]) if row is not None else 0

    def sum(self, labels: dict[str, str] | None = None) -> float:
        row = self._merged().get(_label_key(labels))
        return row[-1] if row is not None else 0.0

    def label_keys(self) -> list[tuple[tuple[str, str], ...]]:
        """Every label set this histogram holds series for (sorted
        key tuples, as ``_label_key`` produces)."""
        return list(self._merged())

    def percentile(self, q: float,
                   labels: dict[str, str] | None = None) -> float:
        """Estimate the ``q``-th percentile (0..100) the way
        ``histogram_quantile`` does: find the bucket the rank falls
        in, interpolate linearly inside it.  The +Inf bucket clamps
        to the largest finite bound (no upper edge to interpolate
        toward); an empty series returns NaN."""
        row = self._merged().get(_label_key(labels))
        if row is None:
            return float('nan')
        total = sum(row[:-1])
        if total == 0:
            return float('nan')
        rank = q / 100.0 * total
        cum = 0.0
        lo = 0.0
        for i, bound in enumerate(self.buckets):
            prev = cum
            cum += row[i]
            if cum >= rank:
                frac = (rank - prev) / row[i] if row[i] else 0.0
                return lo + (bound - lo) * frac
            lo = bound
        return self.buckets[-1]

    def bucket_value(self, le: float,
                     labels: dict[str, str] | None = None) -> int:
        """Cumulative count for the bucket with upper bound ``le``
        (``float('inf')`` for the +Inf bucket)."""
        row = self._merged().get(_label_key(labels))
        if row is None:
            return 0
        if le == float('inf'):
            return sum(row[:-1])
        idx = self.buckets.index(float(le))
        return sum(row[:idx + 1])

    @staticmethod
    def _fmt_bound(bound: float) -> str:
        return '%g' % (bound,)

    def rows(self) -> list[tuple[str, object]]:
        """Every series as cumulative ``(key, value)`` rows in
        Prometheus form — ``<name>_bucket{...,le="..."}`` per bound
        and ``+Inf``, then ``<name>_sum{...}`` and ``<name>_count{...}``
        — what :meth:`expose` prints and what a member's ``mntr``
        carries (server/server.py), so that after-minus-before over
        any window gives that window's exact bucket counts, its sum
        and its count.  The keys of a series are rendered once."""
        out: list[tuple[str, object]] = []
        for key, row in sorted(self._merged().items()):
            names = self._row_names.get(key)
            if names is None:
                les = [self._fmt_bound(b) for b in self.buckets] + ['+Inf']
                names = self._row_names[key] = [
                    self.name + '_bucket' + _render_labels(
                        key, (('le', le),)) for le in les] + [
                    self.name + '_sum' + _render_labels(key),
                    self.name + '_count' + _render_labels(key)]
            cum = 0
            for i in range(len(self.buckets) + 1):
                cum += row[i]
                out.append((names[i], cum))
            out.append((names[-2], row[-1]))
            out.append((names[-1], cum))
        return out

    def expose(self) -> str:
        lines = []
        if self.help:
            lines.append('# HELP %s %s' % (self.name, self.help))
        lines.append('# TYPE %s histogram' % (self.name,))
        lines += ['%s %s' % kv for kv in self.rows()]
        return '\n'.join(lines)


METRIC_TICK = 'zk_tick_ms'
METRIC_TICK_PHASE = 'zk_tick_phase_ms'

#: Tick/phase duration buckets, ms: a busy tick on this stack spans
#: tens of microseconds (one pipelined reply) up to tens of
#: milliseconds (a wide fan-out flush or a slow-device fsync); under a
#: write herd ``decode_apply`` and a parked ``forward_rpc`` run to
#: hundreds (PERF.md section 6), so the edges go on to 1 s.
TICK_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                10.0, 25.0, 50.0, 100.0, 250.0, 1000.0)


class TickLedger:
    """Per-busy-tick phase accounting for one server member.

    The busy loop tick is the unit every plane already coalesces on —
    one cork flush, one group fsync, one fan-out flush per tick — but
    nothing said where the tick's wall time went.  The ledger splits
    it: call sites bracket their work with :meth:`enter`/:meth:`exit`
    (nested sections subtract cleanly, so a cork flush inside a shard
    flush is counted once), and when the burst goes quiet the tick
    closes — per-phase durations land in ``zk_tick_phase_ms{phase=}``
    and the burst's wall span in ``zk_tick_ms``.

    Phases (server/server.py wires them):

    - ``rx_drain`` — the ingress plane's batched receive: kernel-to-
      user time for one shard's dirty set (io/ingress.py; ~0 on the
      single-loop validator, whose reads are awaited, not drained);
    - ``decode_apply`` — request decode + handler dispatch (store
      apply and WAL append included, minus nested phases);
    - ``fsync_gate`` — loop-blocking durability-barrier time (the
      inline fast-device fsync, ``sync='always'`` appends, the
      synchronous barrier on close paths);
    - ``cork_flush`` — send-plane buffer join + transport write;
    - ``fanout_flush`` — the watch table's per-shard flush loop
      (minus the nested cork writes it triggers);
    - ``forward_rpc`` — a follower parked in the blocking control-
      channel RPC (server/replication.py ``RemoteLeader._rpc``): the
      whole loop stands still for it.  Writes cost one once a turn of
      the loop — the turn's writes leave as ONE ``batch`` from the
      flush of the server's forward queue (server/server.py
      ``ZKServer._flush_forwards``) — a session open/close or a
      ``sync`` one each.  Nested under ``decode_apply`` like the
      rest, so a follower's ``decode_apply`` is its own decode,
      catch-up and replies, not the leader's round trip;
    - ``wal_append`` — one record's build, CRC32C and write
      (server/persist.py ``WriteAheadLog.append``, without the fsync
      gate), ``wal_roll`` — what a segment roll holds the loop for
      (its blocking sync + the whole-tree snapshot's capture), and
      ``repl_push`` — a group of commits' one push a mirror, frame +
      send (server/replication.py ``_ship``: a forwarded batch's
      commits, or what a turn of the loop committed).  All three nest
      under whatever phase holds that time (``decode_apply`` for a
      client's write, ``control`` for what the control channel
      applied; a turn's own ship runs behind the turn, under no
      other phase), so the parents keep their subject and the three
      say what a large record costs;
    - ``list_encode`` — the sort and encode of a children list whose
      serialized reply the member did not hold (server/server.py
      ``ReplyCache``: once a change of the parent, whoever asks),
      and ``data_encode`` — the encode of a ``GET_DATA`` body of
      ``REPLY_SHARE_BYTES`` or more that it did not hold (once a
      change of the node, and again when 400 other paths were asked
      for since), both nested under ``decode_apply`` the same way;
    - ``control`` — the leader's service of a follower's control
      channel (server/replication.py ``_serve_control``): a message
      from its bytes in hand — unpickling, a forwarded batch's
      applies and its one barrier — up to the quorum wait, and from
      the wait's return through the response's piggybacked entries,
      pickle and write; never the wait itself;
    - ``repl_ack`` — a follower-stream ack at the leader, from its
      bytes in hand through the quorum floor's advance and the
      releases it makes (the flushes it releases nest under it).

    A "tick" here is the whole burst: asyncio runs ``call_soon``
    callbacks scheduled during a callback in the *next* loop
    iteration, so the cork/fan-out flushes of one logical tick land
    one iteration after the decode that corked them — the close
    callback re-arms while activity continues and finalizes on the
    first quiet iteration.  Phase sums are <= the tick wall span by
    construction; the gap is un-instrumented loop work.

    Works without a collector (mntr-only servers keep their own
    histograms); with one, the same histograms are registered for
    scraping.
    """

    PHASES = ('rx_drain', 'decode_apply', 'fsync_gate', 'cork_flush',
              'fanout_flush', 'forward_rpc', 'wal_append', 'wal_roll',
              'repl_push', 'list_encode', 'data_encode', 'control',
              'repl_ack')

    #: Close a still-active burst after this many loop iterations
    #: anyway: under saturating back-to-back load every iteration has
    #: new phase activity and a pure quiet-pass rule would never
    #: close — the ledger then reports bounded burst slices (shares
    #: stay exact; only the per-tick bucketing coarsens).
    MAX_DEFERS = 8

    __slots__ = ('ticks', 'phase_hist', 'tick_hist', 'last_tick',
                 '_acc', '_stack', '_first', '_last', '_scheduled',
                 '_gen', '_sched_gen', '_defers')

    def __init__(self, collector=None):
        self.ticks = 0
        self.last_tick: dict | None = None
        self._acc: dict[str, float] = {}
        self._stack: list = []      # [phase, t0, child_seconds]
        self._first = 0.0
        self._last = 0.0
        self._scheduled = False
        self._gen = 0
        self._sched_gen = -1
        self._defers = 0
        source = collector if collector is not None else Collector()
        self.phase_hist = source.histogram(
            METRIC_TICK_PHASE,
            'Busy-tick time by phase, ms (rx_drain | decode_apply | '
            'fsync_gate | cork_flush | fanout_flush | forward_rpc | '
            'wal_append | wal_roll | repl_push | list_encode | '
            'data_encode | control | '
            'repl_ack)',
            buckets=TICK_BUCKETS)
        self.tick_hist = source.histogram(
            METRIC_TICK, 'Busy-tick wall span, ms',
            buckets=TICK_BUCKETS)

    def enter(self, phase: str) -> None:
        """Open one phase section (re-entrant across phases: a nested
        section's time is subtracted from its parent)."""
        now = time.perf_counter()
        if not self._stack and not self._acc:
            self._first = now
        self._gen += 1
        self._stack.append([phase, now, 0.0])
        if not self._scheduled:
            # -1 forces the close callback to re-arm at least once:
            # it is queued BEFORE the tick's own spill-over callbacks
            # (cork/fan-out flushes land behind it in the same
            # iteration), so closing on the first run would split one
            # logical tick in two
            self._sched_gen = -1
            try:
                ambient_loop().call_soon(self._tick_close)
            except RuntimeError:
                return          # no loop (unit test): close manually
            self._scheduled = True

    def exit(self) -> None:
        """Close the innermost open section."""
        now = time.perf_counter()
        phase, t0, child = self._stack.pop()
        dur = now - t0
        self._acc[phase] = self._acc.get(phase, 0.0) + dur - child
        if self._stack:
            self._stack[-1][2] += dur
        self._last = now

    def _tick_close(self) -> None:
        self._scheduled = False
        self._defers += 1
        if self._stack or (self._gen != self._sched_gen
                           and self._defers < self.MAX_DEFERS):
            # activity since the last look (the burst spilled into
            # this iteration — cork/fan-out callbacks of the same
            # logical tick): look again next iteration; close after
            # one fully quiet pass, or at MAX_DEFERS under
            # saturating load
            self._sched_gen = self._gen
            try:
                ambient_loop().call_soon(self._tick_close)
            except RuntimeError:
                return
            self._scheduled = True
            return
        self.close_tick()

    def close_tick(self) -> None:
        """Finalize the current tick: observe every accumulated phase
        and the tick wall span.  Loop-driven normally; callable
        directly where no loop runs (unit tests)."""
        if not self._acc or self._stack:
            return
        self._defers = 0
        total_ms = (self._last - self._first) * 1000.0
        phases = {p: round(s * 1000.0, 6)
                  for p, s in self._acc.items()}
        self._acc = {}
        self.ticks += 1
        for phase, ms in phases.items():
            self.phase_hist.observe(ms, {'phase': phase})
        self.tick_hist.observe(total_ms)
        self.last_tick = {'total_ms': round(total_ms, 6),
                          'phases': phases}

    def phase_p99(self, phase: str) -> float | None:
        """p99 of one phase's per-tick duration, ms (None when the
        phase never ran) — the mntr ``zk_tick_phase_ms_p99`` rows."""
        labels = {'phase': phase}
        if not self.phase_hist.count(labels):
            return None
        return self.phase_hist.percentile(99, labels)


class Collector:
    def __init__(self) -> None:
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge | MultiGauge] = {}
        self._histograms: dict[str, Histogram] = {}

    def _check_collision(self, name: str, kind: str) -> None:
        for other_kind, table in (('counter', self._counters),
                                  ('gauge', self._gauges),
                                  ('histogram', self._histograms)):
            if kind != other_kind and name in table:
                raise ValueError(
                    'metric %r already registered as a %s'
                    % (name, other_kind))

    def counter(self, name: str, help_text: str = '') -> Counter:
        """Create (or fetch) a counter by name — idempotent, like
        artedi's collector.counter()."""
        self._check_collision(name, 'counter')
        if name not in self._counters:
            self._counters[name] = Counter(name, help_text)
        return self._counters[name]

    def histogram(self, name: str, help_text: str = '',
                  buckets=DEFAULT_BUCKETS) -> Histogram:
        """Create (or fetch) a histogram by name — idempotent like
        :meth:`counter`, so shared collectors (many clients, one
        scrape) register per-op latency once.  Re-registering with
        DIFFERENT bucket bounds raises: silently handing back the
        first registrant's buckets would mis-bucket the second
        registrant's observations with no warning."""
        self._check_collision(name, 'histogram')
        existing = self._histograms.get(name)
        if existing is not None:
            want = tuple(sorted(float(b) for b in buckets))
            if want != existing.buckets:
                raise ValueError(
                    'histogram %r already registered with buckets %r '
                    '(requested %r); use a distinct name/prefix'
                    % (name, existing.buckets, want))
            return existing
        self._histograms[name] = Histogram(name, help_text, buckets)
        return self._histograms[name]

    def adopt(self, series: Counter | Histogram) -> None:
        """Expose, under its own name, a series that another object
        owns and feeds (an event loop's shared transport tier,
        io/transport.py).  This collector's series of that name —
        made if need be — then reads as its own rows plus the adopted
        ones; adopting the same series again changes nothing."""
        if isinstance(series, Counter):
            own = self.counter(series.name, series.help)
        else:
            own = self.histogram(series.name, series.help,
                                 series.buckets)
        own.link(series)

    def _check_gauge_free(self, name: str) -> None:
        """Gauges are never idempotent — a same-name registration (of
        any kind) raises: silently replacing would drop the first
        registrant's series (bind two instrumented components under
        distinct prefixes instead)."""
        self._check_collision(name, 'gauge')
        if name in self._gauges:
            raise ValueError(
                'metric %r already registered; use a distinct '
                'name/prefix' % (name,))

    def gauge(self, name: str, fn, help_text: str = '') -> Gauge:
        """Register a callback-backed gauge (see
        :meth:`_check_gauge_free` for the collision policy)."""
        self._check_gauge_free(name)
        self._gauges[name] = Gauge(name, fn, help_text)
        return self._gauges[name]

    def multi_gauge(self, name: str, fn,
                    help_text: str = '') -> MultiGauge:
        """Register a labelled pull gauge (callback returns
        ``{labels: value}``); same collision policy as :meth:`gauge`."""
        self._check_gauge_free(name)
        self._gauges[name] = MultiGauge(name, fn, help_text)
        return self._gauges[name]

    def histograms(self) -> list[Histogram]:
        return list(self._histograms.values())

    def get_collector(self, name: str):
        if name in self._counters:
            return self._counters[name]
        if name in self._histograms:
            return self._histograms[name]
        if name in self._gauges:
            return self._gauges[name]
        registered = sorted(list(self._counters) + list(self._gauges)
                            + list(self._histograms))
        raise ValueError(
            'no metric %r registered; registered names: %s'
            % (name, ', '.join(registered) or '(none)'))

    def expose(self) -> str:
        parts = [c.expose() for c in self._counters.values()]
        parts += [h.expose() for h in self._histograms.values()]
        parts += [g.expose() for g in self._gauges.values()]
        return '\n'.join(parts)
