"""One run of one cell: members, data, fleet, warm-up, the measured
window, the bounded drain, validation, the metrics.

Everything that belongs to one configuration, one traffic mix, one
engine or one metric lives in a file of its own that this module finds
by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``      the deployment (``file`` of the config)
- ``traffic/<traffic>.json``     the mix: ``engine`` and its parameters
- ``engines/<engine>.py``        the general generator the mix drives
- ``end_to_end/<metric>.py``     ``value(run) -> float``
- ``layer_metrics/<metric>.py``  ``read(run) -> float | None``
  (one entry a reader and end-to-end metric it moves, its cells on the
  entry's ``workloads`` list; the last dotted part says which:
  ``x.read``, ``x.write`` and ``x.converge`` share ``x.py``)
- ``controls/<name>.py``         ``wrap_client(client)``: a timed path
                                 broken on purpose (never in a run the
                                 driver makes)

A slow, failed or mismatching operation is a count (``failed``,
``correct: false``), never an exit code; a non-zero exit is for what
makes a measurement impossible (no chip, a member that did not start,
the harness's own exception).  Members, ports, WAL and trace
directories are gone on every exit path.
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import members  # noqa: E402
import reduce_trace  # noqa: E402
import stats  # noqa: E402

#: the host annotations a traced run's idle gaps are attributed to,
#: innermost first (``reduce_trace.attribute_gaps``: an earlier name
#: takes what it covers): the program's own spans, written into the
#: profiler's trace by ``zkstream_tpu.utils.trace.host_span`` on the
#: device plane's clock — a collection (it lies inside whatever span
#: was open when it began), a watch delivery, the ingest tick's four
#: phases and then what is left of the tick, the send tier's hand-over
#: and reaping inside its flush, a connection's receive and then what
#: is left of the receive reap that delivered it (``client.rx`` nests
#: in ``client.rx_reap``), the deadline timer, an API call's own work
#: before its submission, the submission, the awaiter's resumption —
#: then the harness's two: the loop blocked in ``select`` (the program
#: has no span around it) and the post-window checks
HOST_SPANS = ('gc.pause', 'client.notify',
              'ingest.batch', 'ingest.dispatch', 'ingest.readback',
              'ingest.route', 'ingest.tick',
              'client.handoff', 'client.reap', 'client.flush',
              'client.rx', 'client.rx_reap', 'client.deadline',
              'client.prepare', 'client.submit', 'client.resume',
              'await_replies', 'validate')
#: device idle time under none of those: what the loop's thread did
#: that no span of the program names (the engine's callbacks, asyncio's
#: own turn, a tick that found nothing to drain)
LOOP_REST = 'loop_callbacks'


class HarnessError(Exception):
    """A measurement is impossible; the run exits non-zero and prints
    no result."""


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------

def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def reader_path(kind: str, name: str) -> str | None:
    """``<kind>/<name>.py``, or — the one other rule — the file of the
    name less its LAST dotted part: a metric split because its cells
    report different end-to-end metrics or need bounds of their own
    carries the family it moves as a suffix (``x.read`` / ``x.write`` /
    ``x.converge`` share ``x.py``; a cell's own label where the entry
    is that cell's alone) unless it has a file of its own."""
    names = [name]
    if '.' in name:
        names.append(name.rsplit('.', 1)[0])
    return next((p for p in (os.path.join(HERE, kind, n + '.py')
                             for n in names) if os.path.isfile(p)), None)


def _load_module(kind: str, name: str):
    path = reader_path(kind, name)
    if path is None:
        raise HarnessError('no %s/%s.py' % (kind, name))
    spec = importlib.util.spec_from_file_location(
        'bench_%s_%s' % (kind, name.replace('.', '_').replace('-', '_')),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, workload: str, toy: bool = False,
                 bench_path: str | None = None):
        self.bench = _load_json(bench_path
                                or os.path.join(ROOT, 'BENCHMARK.json'))
        cells = {w['name']: w for w in self.bench['workloads']}
        if workload not in cells:
            raise HarnessError('no workload %r in BENCHMARK.json (have '
                               '%s)' % (workload, sorted(cells)))
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry['chips'])
        cfg = next(c for c in self.bench['configs']
                   if c['name'] == self.entry['config'])
        self.config = _load_json(os.path.join(ROOT, cfg['file']))
        self.traffic = _load_json(os.path.join(
            HERE, 'traffic', self.entry['traffic'] + '.json'))
        self.toy = toy
        if toy:
            self.config = _merge(self.config, self.config.get('toy', {}))
            self.traffic = _merge(self.traffic,
                                  self.traffic.get('toy', {}))
        self.engine = _load_module('engines', self.traffic['engine'])
        self.end_to_end = [m for m in self.bench['end_to_end']
                           if workload in m.get('workloads', [workload])]
        e2e = {m['name'] for m in self.end_to_end}
        self.per_layer = [
            m for m in self.bench['per_layer']
            if (workload in m['workloads'] if 'workloads' in m
                else m['moves'] in e2e)]


# ---------------------------------------------------------------------
# what the engines and the metric readers are handed
# ---------------------------------------------------------------------

class Fleet:
    """The engines' side of the harness: where the members are, how to
    make a session, how to mark a span."""

    def __init__(self, cell: Cell, seed: int, addrs, ingest,
                 traced: bool, wrap_client=None):
        self.cell = cell
        self.config = cell.config
        self.params = cell.traffic
        self.seed = seed
        self.addrs = addrs
        self.ingest = ingest
        self.traced = traced
        self.wrap_client = wrap_client
        self.clients: list = []
        self.session_timeout_ms = int(
            self.config.get('session_timeout_ms', 120_000))
        self.deadline_ms = int(self.params.get('op_deadline_ms', 15_000))

    def new_client(self, member: int, through_ingest: bool = True):
        """One session attached to ``member`` (no shuffling, no
        fail-over: a cell says where each session is)."""
        from zkstream_tpu import Client

        c = Client(servers=[self.addrs[member % len(self.addrs)]],
                   shuffle_backends=False,
                   ingest=self.ingest if through_ingest else None,
                   session_timeout=self.session_timeout_ms,
                   op_timeout=self.deadline_ms)
        if self.wrap_client is not None and through_ingest:
            c = self.wrap_client(c) or c
        c.start()
        self.clients.append(c)
        return c

    def span(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    async def close(self, timeout: float = 30.0) -> None:
        clients, self.clients = self.clients, []
        if not clients:
            return
        with contextlib.suppress(asyncio.TimeoutError, TimeoutError):
            await asyncio.wait_for(asyncio.gather(
                *[c.close() for c in clients], return_exceptions=True),
                timeout)


class Run:
    """What one run measured — what ``end_to_end/*.py`` and
    ``layer_metrics/*.py`` read."""

    def __init__(self):
        self.cell: Cell | None = None
        self.window_s = 0.0         # as run
        self.setup_s = 0.0
        self.result: dict = {}      # the engine's
        self.ingest_before: dict = {}
        self.ingest_after: dict = {}
        self.ingest_params: dict = {}
        self.tick_ms: list | None = None        # traced runs
        self.tick_buckets: list | None = None   # traced window's ticks
        self.select_s: float | None = None      # traced runs
        self.mntr_before: list = []
        self.mntr_after: list = []
        self.leader = 0
        self.trace: dict | None = None
        self.peaks: dict = {}

    def ingest_delta(self, name: str) -> int:
        return self.ingest_after.get(name, 0) - self.ingest_before.get(
            name, 0)

    def mntr_delta(self, member: int, key: str) -> float | None:
        try:
            return (float(self.mntr_after[member][key])
                    - float(self.mntr_before[member][key]))
        except (KeyError, IndexError, ValueError):
            return None


INGEST_COUNTERS = ('ticks', 'ticks_early', 'ticks_scalar', 'ticks_warming',
                   'ticks_frag', 'frames_routed')


def ingest_counters(ingest) -> dict:
    return {k: int(getattr(ingest, k)) for k in INGEST_COUNTERS}


class _TickSink:
    """Stands where the ingest's tick histogram stands and keeps every
    duration (traced runs: an exact median, no bucket edges)."""

    def __init__(self):
        self.values: list[float] = []

    def observe(self, value, labels=None) -> None:
        self.values.append(value)


# ---------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------

def device_block(mode: str, chips: int) -> dict:
    """The device as JAX reports it; raises when it is not what the
    cell asks for.  ``mode`` 'chip' wants a TPU; 'rehearse' takes what
    is there and stamps it."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if mode == 'chip' and (dev.platform != 'tpu' or len(devs) < chips):
        raise HarnessError(
            'no accelerator: JAX reports %d x %s (%s); the cell needs %d '
            'TPU chip(s)' % (len(devs), dev.device_kind, dev.platform,
                             chips))
    return {'platform': str(dev.platform), 'kind': str(dev.device_kind),
            'count': len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get('peak_bytes_in_use', 0)))
    return peak


def load_peaks(kind: str, mode: str) -> dict:
    peaks = _load_json(os.path.join(HERE, 'peaks.json'))
    if kind not in peaks:
        if mode == 'chip':
            raise HarnessError('device kind %r is not in peaks.json'
                               % (kind,))
        return {}
    return peaks[kind]


def held_guarantees(cfg: dict, leader: int, start: list, before: list,
                    final: list, hold_ms: float | None
                    ) -> tuple[list, dict, list]:
    """The configuration's first guarantee — a write is acknowledged
    only after the WAL barrier and a quorum — as far as a run without a
    crash can show it, from the members' own ``mntr`` rows and one
    probe.  Exact: the leader gates acks on as many voters as the
    configuration has; every member's WAL syncs as the configuration
    says, reports no sync error, and made an fsync if it appended a
    record.  By limits in the configuration's ``held``: the acks
    released WITHOUT a confirmed quorum (the program lets one go after
    its 250 ms wait and counts it, ``zk_quorum_degraded``) from the
    window's start to the end of the drain number at most
    ``quorum_degraded_max``; an ack that no quorum can confirm
    (``quorum_hold_ms``) is held ``quorum_hold_min_ms`` or longer.
    ``start`` / ``before`` / ``final`` are every member's rows at
    start-up, at the window's start and after the drain.  Returns the
    ``# compared`` lines, the violations by kind and a line for each."""
    held = cfg.get('held', {})
    voters = int(cfg['voters'])
    want_sync = cfg.get('member_env', {}).get('ZKSTREAM_MEMBER_SYNC', 'tick')
    kinds: dict = {}
    first: list = []

    def bad(kind, msg):
        kinds[kind] = kinds.get(kind, 0) + 1
        first.append('%s: %s' % (kind, msg))

    got = start[leader].get('zk_quorum_members')
    if got != str(voters):
        bad('quorum-members', 'the leader (member %d) gates acks on %s '
            'voters, the configuration has %d' % (leader, got, voters))
    syncs = [r.get('zk_wal_sync') for r in start]
    for m, v in enumerate(syncs):
        if v != want_sync:
            bad('wal-sync', "member %d's WAL syncs %r, the configuration "
                'says %r' % (m, v, want_sync))
    errs = degraded = 0
    unread, unsynced = [], []
    for m, (b, f) in enumerate(zip(before, final)):
        try:
            errs += int(f['zk_wal_sync_errors']) - int(
                b['zk_wal_sync_errors'])
            if (int(f['zk_wal_last_index']) > int(b['zk_wal_last_index'])
                    and int(f['zk_wal_fsyncs']) <= int(b['zk_wal_fsyncs'])):
                unsynced.append(m)
            if 'zk_quorum_degraded' in f or m == leader:
                degraded += int(f['zk_quorum_degraded']) - int(
                    b.get('zk_quorum_degraded', 0))
        except (KeyError, ValueError):
            unread.append(m)
    if unread:
        bad('guarantee-unread', 'members %s gave no WAL / quorum rows'
            % (unread,))
    if errs:
        bad('wal-sync-errors', '%d WAL sync errors in the run' % (errs,))
    if unsynced:
        bad('wal-unsynced', 'members %s appended records and made no '
            'fsync' % (unsynced,))
    limit = int(held.get('quorum_degraded_max', 0))
    if degraded > limit:
        bad('quorum-degraded', '%d acks left without a confirmed quorum '
            '(limit %d)' % (degraded, limit))
    hold_min = float(held.get('quorum_hold_min_ms', 0))
    if hold_ms is None:
        bad('guarantee-unread', 'the quorum-hold probe gave no reading')
    elif hold_ms < hold_min:
        bad('quorum-hold', 'an ack no quorum could confirm left after '
            '%.1f ms (limit >= %g ms)' % (hold_ms, hold_min))
    compared = [
        'quorum-hold-ms %s limit >= %g' % (
            'unread' if hold_ms is None else '%.1f' % (hold_ms,), hold_min),
        'quorum-members %s limit = %d' % (got, voters),
        'wal-sync %s limit = %s' % (json.dumps(syncs), want_sync),
        'wal-sync-errors %d limit 0' % (errs,),
        'wal-unsynced %d limit 0' % (len(unsynced),),
        'quorum-degraded %d limit %d' % (degraded, limit),
        'guarantee-unread %d limit 0' % (len(unread) + (hold_ms is None),)]
    return compared, kinds, first


HOLD_PATH = '/zkbench-hold'


async def quorum_hold_ms(ens, fleet: Fleet, leader: int) -> float | None:
    """How long the leader holds an ack that NO quorum can confirm:
    with every other member stopped (SIGSTOP; the harness owns them),
    one write through a plain session on the leader.  The program
    lets such an ack go only after its quorum wait (250 ms); one that
    leaves sooner was never waiting for a quorum, or not that long.
    The last thing a run does with the ensemble; the others are
    resumed before it returns.  None when the write failed."""
    c = fleet.new_client(leader, through_ingest=False)
    try:
        await c.wait_connected(timeout=30)
        await c.create(HOLD_PATH, b'')
        ens.signal_others(leader, signal.SIGSTOP)
        try:
            t = time.perf_counter()
            await c.set(HOLD_PATH, b'held')
            return (time.perf_counter() - t) * 1e3
        finally:
            ens.signal_others(leader, signal.SIGCONT)
    except asyncio.CancelledError:
        raise
    except Exception as e:
        say('# quorum-hold probe failed: %r' % (e,))
        return None
    finally:
        fleet.clients.remove(c)
        await _quiet(c.close())


# ---------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------

def buckets(sessions: int):
    """The batch buckets (streams, padded to a power of two from 8) a
    fleet of ``sessions`` can produce."""
    bp = 8
    while True:
        yield bp
        if bp >= sessions:
            return
        bp *= 2


def warm_shapes(sessions: int, min_len: int, max_len: int = 0):
    """The ``(rows, width)`` of every tick program a cell warms: the
    fleet's batch buckets in the narrowest size class (width None) and
    — where the mix states ``warm_max_len``, the most bytes one slot
    hands a tick — every wider class from ``2 * min_len`` up to the one
    that holds ``max_len``, at every row count a fleet of this size can
    give a dispatch (``FleetIngest.prewarm`` pads rows and width to the
    bucket; one asked for twice is compiled once)."""
    for bp in buckets(sessions):
        yield bp, None
    width = 2 * min_len
    while width < 2 * max_len:
        rows = 1
        while rows < 2 * sessions:
            yield rows, width
            rows *= 2
        width *= 2


async def _prewarm(ingest, sessions: int, max_len: int) -> float:
    """Compile (or fetch from the cache) the tick program of every
    bucket ``warm_shapes`` names, off the loop."""
    t0 = time.perf_counter()

    def work():
        for rows, width in warm_shapes(sessions, ingest.min_len, max_len):
            asyncio.run(ingest.prewarm(rows, width))
    await asyncio.get_running_loop().run_in_executor(None, work)
    return time.perf_counter() - t0


def _time_select(loop, span):
    """Time the loop's blocking ``select``; returns (seconds-cell,
    restore)."""
    sel = loop._selector
    orig = sel.select
    acc = [0.0]

    def timed(timeout=None):
        t = time.perf_counter()
        try:
            with span('await_replies'):
                return orig(timeout)
        finally:
            acc[0] += time.perf_counter() - t
    sel.select = timed

    def restore():
        sel.select = orig
    return acc, restore


async def run_cell(workload: str, seed: int, seconds: float, trace: bool,
                   mode: str = 'chip', t_process: float | None = None,
                   member_env: dict | None = None,
                   control: str | None = None,
                   keep_trace: str | None = None,
                   bench_path: str | None = None) -> dict:
    """Run one cell once.  Returns the result line's dictionary (the
    caller prints it, or in a rehearsal does not)."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = Cell(workload, toy=(mode != 'chip'), bench_path=bench_path)
    cfg = cell.config
    wrap_client = (_load_module('controls', control).wrap_client
                   if control else None)
    killed = members.kill_leftovers(ROOT)
    if killed:
        say('# ended %d member process(es) an earlier run of this '
            'checkout left under %s: %s'
            % (len(killed), tempfile.gettempdir(), killed))
    run_dir = tempfile.mkdtemp(prefix=members.RUN_PREFIX)
    ens = members.Ensemble(
        ROOT, run_dir, int(cfg['voters']),
        env=dict(cfg.get('member_env', {}), **(member_env or {})))
    fleet = None
    ingest = None
    engine = None
    restore_select = None
    loop = asyncio.get_running_loop()
    loop_errors: list = []
    prev_handler = loop.get_exception_handler()
    # a tick that raises (force-device refusal, a routing bug) surfaces
    # here, not in an awaiting op: count it, the ops it lost time out
    loop.set_exception_handler(lambda _l, ctx: loop_errors.append(
        repr(ctx.get('exception') or ctx.get('message'))))
    main = asyncio.current_task()
    with contextlib.suppress(NotImplementedError, RuntimeError):
        loop.add_signal_handler(signal.SIGTERM, main.cancel)
    try:
        if 'jax' in sys.modules and mode == 'chip':
            raise HarnessError('jax was imported before the members '
                               'were spawned')
        t_spawn = time.perf_counter()
        ens.spawn()
        # the members elect while this process brings JAX up
        import jax

        device = device_block(mode, cell.chips)
        t_jax = time.perf_counter()
        peaks = load_peaks(device['kind'], mode)
        from zkstream_tpu.io.ingest import FleetIngest
        from zkstream_tpu.utils.platform import enable_compile_cache

        cache_dir = enable_compile_cache()
        await ens.wait_ready()
        leader = await ens.find_leader()
        rows = mntr_start = await ens.mntr_all()
        say('# device %s cache %s' % (json.dumps(device), cache_dir))
        say('# ensemble leader=%d %s' % (leader, json.dumps([
            {k: r.get(k) for k in ('zk_member_role', 'zk_transport_backend',
                                   'zk_ingress_backend', 'zk_wal_sync',
                                   'zk_quorum_members')} for r in rows])))

        ingest = FleetIngest(
            placement='accelerator' if mode == 'chip' else 'host',
            **cfg['ingest'])
        fleet = Fleet(cell, seed, ens.addrs, ingest, trace, wrap_client)
        engine = cell.engine.Engine(fleet)
        t0 = time.perf_counter()
        # the data loads through a plain session while the tick
        # programs compile on a thread: neither waits for the other
        prewarm_s, _ = await asyncio.gather(
            _prewarm(ingest, int(cfg['sessions']),
                     int(cell.traffic.get('warm_max_len', 0))),
            engine.load())
        t1 = time.perf_counter()
        warmed = set(ingest.buckets)
        bad = {str(k): b['error'] for k, b in ingest.buckets.items()
               if b['error']}
        if bad:
            raise HarnessError('tick programs failed to compile: %r'
                               % (bad,))
        await engine.connect()
        t2 = time.perf_counter()
        tier = fleet.clients[0].transport_tier if fleet.clients else None
        say('# fleet transport=%s (the client tier every session shares)'
            % (tier.backend if tier is not None else 'asyncio',))
        say('# setup start %.2fs jax+device %.2fs members+leader %.2fs '
            'load+prewarm %.2fs (prewarm %.2fs, compile %.2fs, '
            '%d buckets %s on %s) connect %.2fs'
            % (t_spawn - t_process, t_jax - t_spawn, t0 - t_jax,
               t1 - t0, prewarm_s,
               sum(b['compile_s'] for b in ingest.buckets.values()),
               len(warmed),
               sorted({b['impl'] for b in ingest.buckets.values()}),
               sorted({b['platform'] for b in ingest.buckets.values()}),
               t2 - t1))

        run = Run()
        run.cell, run.peaks, run.leader = cell, peaks, leader
        run.ingest_params = dict(cfg['ingest'])
        if trace:
            sink = ingest.tick_hist = _TickSink()
            acc, restore_select = _time_select(loop, fleet.span)
            bucket = ingest._bucket
            ticks_seen: list = []

            def noted_bucket(n, nbytes):
                key = bucket(n, nbytes)
                ticks_seen.append((time.perf_counter(), key))
                return key
            ingest._bucket = noted_bucket

        # -- warm-up: the cell's own traffic, unrecorded --------------
        # what set-up built (the tree's model, 1,024 sessions) is not
        # garbage: keep the collector from walking it in the window
        gc.collect()
        gc.freeze()
        stalls = _Stalls()
        stalls.start()
        engine.start()
        await asyncio.sleep(float(cell.traffic.get('warm_seconds', 2.0)))

        # -- the window ------------------------------------------------
        run.mntr_before = await ens.mntr_all()
        stalls.reset()
        run.ingest_before = ingest_counters(ingest)
        if trace:
            sink.values.clear()
            acc[0] = 0.0
        t_open = time.perf_counter()
        run.setup_s = t_open - t_process
        engine.open_window(t_open)
        trace_dir = os.path.join(run_dir, 'trace')
        t_trace = None
        if trace:
            # the traced span ends with the window: stopping the
            # profiler blocks the loop for seconds, which must not
            # fall inside what the counters cover
            span_s = max(0.2, min(float(cell.traffic.get(
                'trace_seconds', 4.0)), seconds / 2.0))
            await asyncio.sleep(seconds - span_s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t_trace = [time.perf_counter(), None]
            await asyncio.sleep(span_s)
        else:
            await asyncio.sleep(seconds)
        t_close = time.perf_counter()
        engine.close_window(t_close)
        run.window_s = t_close - t_open
        run.ingest_after = ingest_counters(ingest)
        hist = ingest.tick_hist
        ticks_ms = (' tick_ms sum=%.0f over_%gms=%d' % (
            hist.sum(), hist.buckets[-1],
            hist.count() - hist.bucket_value(hist.buckets[-1]))
            if hasattr(hist, 'bucket_value') else
            ' tick_ms sum=%.0f max=%.1f' % (sum(hist.values),
                                            max(hist.values or [0.0])))
        stalled = stalls.stop() + ticks_ms
        run.mntr_after = await ens.mntr_all()
        if trace:
            t_trace[1] = t_close
            run.select_s = acc[0]
            run.tick_ms = list(sink.values)
            jax.profiler.stop_trace()
        compiled_in_window = sorted(
            str(k) for k in set(ingest.buckets) - warmed)

        # -- bounded drain, then the checks ----------------------------
        undrained = await engine.drain(fleet.deadline_ms / 1000.0 + 2.0)
        t_drained = time.perf_counter()
        mntr_final = await ens.mntr_all()
        say('# stalls ' + stalled)
        say('# mntr leader delta %s' % (json.dumps(_mntr_delta(
            run.mntr_before[leader], run.mntr_after[leader])),))
        with fleet.span('validate'):
            await engine.validate()
            held = held_guarantees(
                cfg, leader, mntr_start, run.mntr_before, mntr_final,
                await quorum_hold_ms(ens, fleet, leader))
        t_checked = time.perf_counter()
        run.result = result = engine.result()
        result['compared'] += held[0]
        for kind, n in held[1].items():
            result['violation_kinds'][kind] = n
        result['violations'] += held[2]
        say('# window %.3fs drain %.2fs (%d still out) checks %.2fs '
            'members_alive=%s' % (run.window_s, t_drained - t_close,
                                  undrained, t_checked - t_drained,
                                  ens.all_alive()))
        say('# ingest %s compiled_in_window=%s loop_errors=%d%s'
            % (json.dumps({k: run.ingest_delta(k)
                           for k in INGEST_COUNTERS}),
               compiled_in_window, len(loop_errors),
               ' first: ' + loop_errors[0][:300] if loop_errors else ''))
        for cls, vals in sorted(result['samples'].items()):
            say('# latency %s_ms %s' % (cls, json.dumps(
                stats.summary(vals))))
        for m, vals in sorted(result.get('samples_by_member', {}).items()):
            say('# latency member=%d%s %s' % (
                m, ' (leader)' if m == leader else '',
                json.dumps(stats.summary(vals))))
        say('# ops attempted=%d failed=%d acked_in_window=%d %s'
            % (result['attempted'], result['failed'], result['acked'],
               json.dumps(result.get('counters', {}))))
        for line in result['compared']:
            say('# compared ' + line)
        if result['violations']:
            say('# NOT CORRECT: %d violation(s) %s' % (
                sum(result['violation_kinds'].values()),
                json.dumps(result['violation_kinds'])))
            for v in result['violations']:
                say('#   ' + v)
        correct = (not result['violations'] and ens.all_alive()
                   and result['checked'] > 0)

        # -- the metrics -----------------------------------------------
        metrics: dict = {}
        out: dict = {'correct': bool(correct),
                     'attempted': int(result['attempted']),
                     'failed': int(result['failed']), 'metrics': metrics}
        dev = dict(device, memory_peak_bytes=memory_peak_bytes())
        if trace:
            xplane = reduce_trace.find_xplane(trace_dir)
            if xplane is None:
                raise HarnessError('the profiler wrote no trace')
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(xplane, os.path.join(
                    keep_trace, '%s.%d.xplane.pb' % (workload, seed)))
            t_load = time.perf_counter()
            tr = reduce_trace.load_xplane(xplane, keep_host=HOST_SPANS)
            t_load = time.perf_counter() - t_load
            if keep_trace:
                with open(os.path.join(keep_trace, '%s.%d.txt' % (
                        workload, seed)), 'w') as f:
                    f.write(reduce_trace.summarize(
                        reduce_trace.load_xplane(xplane)))
            t_reduce = time.perf_counter()
            run.trace = red = reduce_trace.reduce(
                tr, window_ns=(t_trace[1] - t_trace[0]) * 1e9,
                host_spans=HOST_SPANS, rest=LOOP_REST)
            t_reduce = time.perf_counter() - t_reduce
            run.tick_buckets = [k for t, k in ticks_seen
                                if t_trace[0] <= t <= t_trace[1]]
            say('# trace %.3fs busy %.6fs programs %s ticks_in_trace=%d '
                'reduced in %.2fs (load) + %.2fs (attribute)'
                % (red['window_s'], red['busy_s'],
                   json.dumps(red['programs']), len(run.tick_buckets),
                   t_load, t_reduce))
            # every name's share (the result line's breakdown holds ten)
            say('# idle gaps %s' % (json.dumps(red['idle_gaps']),))
            if mode == 'chip' and red['busy_s'] <= 0:
                raise HarnessError('no operation ran on the device in '
                                   'the traced window')
            dev['busy_s'] = red['busy_s']
            dev['window_s'] = red['window_s']
            out['breakdown'] = {'device_ops': red['ops'][:10],
                                'idle_gaps': red['idle_gaps'][:10]}
            for m in cell.per_layer:
                val = _load_module('layer_metrics', m['name']).read(run)
                if val is not None:
                    metrics[m['name']] = {'value': float(val),
                                          'unit': m['unit']}
        else:
            for m in cell.end_to_end:
                val = _load_module('end_to_end', m['name']).value(run)
                metrics[m['name']] = {'value': float(val),
                                      'unit': m['unit']}
        out['device'] = dev
        # sessions close (quietly) before the members are killed
        await engine.stop()
        await fleet.close()
        return out
    finally:
        loop.set_exception_handler(prev_handler)
        with contextlib.suppress(NotImplementedError, RuntimeError,
                                 ValueError):
            loop.remove_signal_handler(signal.SIGTERM)
        if restore_select is not None:
            restore_select()
        # the members go first: whatever state the fleet is in, no
        # process of this run outlives it
        ens.kill()
        try:
            if engine is not None:
                await asyncio.shield(_quiet(engine.stop()))
            if fleet is not None:
                await asyncio.shield(_quiet(fleet.close(5.0)))
        finally:
            if ingest is not None:
                ingest.close()
            shutil.rmtree(run_dir, ignore_errors=True)


class _Stalls:
    """Where a stall came from, for the earlier lines: how late a
    50 ms sleep on the fleet's loop woke (a long callback or a
    collection in THIS process), and what the collector cost."""

    def __init__(self):
        self.lag_ms: list[float] = []
        self.gc_ms: list[float] = []
        #: the same 50 ms sleep on a thread of its own: late there too
        #: means the whole process (or machine) stood still; on time
        #: there means the loop's thread alone was held
        self.thread_lag_ms: list[float] = []
        self._t = 0.0
        self.task = None
        self._halt = threading.Event()

    def _thread(self) -> None:
        while not self._halt.is_set():
            t = time.perf_counter()
            time.sleep(0.05)
            self.thread_lag_ms.append(
                (time.perf_counter() - t - 0.05) * 1e3)

    def _gc(self, phase, info) -> None:
        if phase == 'start':
            self._t = time.perf_counter()
        else:
            self.gc_ms.append((time.perf_counter() - self._t) * 1e3)

    async def _probe(self) -> None:
        while True:
            t = time.perf_counter()
            await asyncio.sleep(0.05)
            self.lag_ms.append((time.perf_counter() - t - 0.05) * 1e3)

    def start(self) -> None:
        gc.callbacks.append(self._gc)
        self.task = asyncio.ensure_future(self._probe())
        threading.Thread(target=self._thread, daemon=True,
                         name='stall-probe').start()

    def reset(self) -> None:
        self.lag_ms.clear()
        self.gc_ms.clear()
        self.thread_lag_ms.clear()

    def stop(self) -> str:
        if self._gc in gc.callbacks:
            gc.callbacks.remove(self._gc)
        if self.task is not None:
            self.task.cancel()
        self._halt.set()
        lag = self.lag_ms or [0.0]
        return ('loop_lag_ms p50=%.1f p99=%.1f max=%.1f thread_lag_ms '
                'max=%.1f gc n=%d total_ms=%.1f max_ms=%.1f' % (
                    stats.percentile(lag, 50), stats.percentile(lag, 99),
                    max(lag), max(self.thread_lag_ms or [0.0]),
                    len(self.gc_ms), sum(self.gc_ms),
                    max(self.gc_ms or [0.0])))


def _mntr_delta(before: dict, after: dict) -> dict:
    """The leader's counters that moved in the window (and its phase
    p99s as they stand), for the earlier lines."""
    out = {}
    for k, v in after.items():
        try:
            d = float(v) - float(before.get(k, 0))
        except ValueError:
            continue
        if 'phase_ms' in k:
            out[k] = float(v)
        elif d and not k.startswith(('zk_uptime', 'zk_ingress_shard')):
            out[k] = d
    return out


async def _quiet(coro) -> None:
    with contextlib.suppress(Exception):
        await coro
