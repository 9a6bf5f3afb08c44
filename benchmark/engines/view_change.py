"""``view_change``: cluster-state documents REWRITTEN whole by
controllers and re-read whole by watching brokers — an Apache Pinot
cluster on Apache Helix adding segments to its tables.

The deployment (``configs/<config>.json``): ``tree`` = ``root``,
``segments`` (a table), ``bytes_per_segment``, ``controllers``,
``brokers``, ``ephemeral_bytes``, ``large_bytes``.  Table t has two
documents, ``<root>/IDEALSTATES/t<tt>`` and ``<root>/EXTERNALVIEW/t<tt>``;
version v of either holds ``(segments + v) x bytes_per_segment`` bytes.
Sessions, in order: the controllers (table t is written only by
controller ``t % controllers``), the brokers (each arms the reference
client's own ``client.watcher(path).on('dataChanged', ...)`` on every
table's EXTERNALVIEW document: the watcher's re-arm after a
notification IS the ``getData`` with watch that returns the whole
document), the servers (each holds the ephemeral
``<root>/LIVEINSTANCES/Server_<i>`` and is otherwise idle).  Session s
is attached to member ``(leader + s) % members``: round-robin over the
members BEGINNING AT THE LEADER (the engine asks every member's ``mntr``
for its role), so that controller 0 is leader-attached and controllers 1
and 2 forward in every run, whichever member the election chose — a
large write through the leader is ~5 ms quicker than a forwarded one,
and with the members numbered from 0 the table whose group holds the
median pair was leader-attached in one run in three.

Parameters (``traffic/<mix>.json``): ``changes_per_s`` changes a second
fleet-wide on a fixed open-loop schedule (change j is due at start +
(j + 1/2) / rate); its table comes from a smooth weighted round-robin
over the tables with the segment counts as weights, from a fixed start:
the same sequence in every run and for every seed (the seed makes the
bytes, not the schedule).  A change is, by the table's controller,
``setData(IDEALSTATES/t, next version)`` and, when that is
acknowledged, ``setData(EXTERNALVIEW/t, ...)`` of the same size; a
table's changes are serial, and one that falls due while the table's
last is unacknowledged waits for it and is still timed from when it
was DUE.  A (change, broker) pair is timed from when the change was due
until that broker's ``'dataChanged'`` listener holds that version or a
later one.

Set-up: the tree through MULTIs bounded in bytes, the servers'
ephemerals, the brokers' watches a table at a time, then ONE change of
every table in turn that every broker must show before the window
opens.  ``load()`` also warms the size classes of the fleet ingest that
the brokers' slots can reach and the harness (which warms the
narrowest) does not.
"""

from __future__ import annotations

import asyncio
import os
import time

import reference_docs

LOAD_BYTES = 2 << 20    # payload bytes per MULTI while loading the tree
LOAD_OPS = 64
LOAD_LANES = 4
SETTLE_S = 10.0         # set-up waits this long for one herd
#: a reply frame over its znode's data: length prefix, reply header,
#: the data's own length, the Stat
REPLY_OVERHEAD = 4 + 16 + 4 + 68
#: ZooKeeper's ``jute.maxbuffer`` default: no reply frame may reach it
JUTE_MAXBUFFER = 0xfffff
#: the fleet ingest's counters of what its device ticks moved, kept by
#: the engine over the window (the harness's own list is older)
INGEST_MOVED = ('dispatches', 'bytes_batched', 'bytes_dispatched',
                'bytes_recopied', 'slots_deferred', 'ticks', 'ticks_full')
#: the client transport tier's counters (one tier a loop)
TIER_MOVED = ('flushes', 'partial_flushes', 'requeued_bytes')


def schedule(weights):
    """Smooth weighted round-robin: every table has a counter at 0; at
    each step every counter grows by its table's weight, the table with
    the largest counter is chosen (ties: the lowest index) and its
    counter falls by the sum of the weights."""
    total = sum(weights)
    counters = [0] * len(weights)
    while True:
        best = 0
        for i, w in enumerate(weights):
            counters[i] += w
            if counters[i] > counters[best]:
                best = i
        counters[best] -= total
        yield best


class Engine:
    def __init__(self, fleet):
        self.fleet = fleet
        cfg, p = fleet.config, fleet.params
        tree = cfg['tree']
        self.sessions = int(cfg['sessions'])
        self.root = tree['root']
        self.segments = [int(n) for n in tree['segments']]
        self.grow = int(tree['bytes_per_segment'])
        self.n_ctrl = int(tree['controllers'])
        self.n_brokers = int(tree['brokers'])
        self.n_servers = self.sessions - self.n_ctrl - self.n_brokers
        self.eph_bytes = int(tree['ephemeral_bytes'])
        self.large = int(tree['large_bytes'])
        self.tables = len(self.segments)
        names = ['t%02d' % (t,) for t in range(self.tables)]
        #: document 2t is table t's IDEALSTATES, 2t + 1 its EXTERNALVIEW
        self.paths = [p_ for name in names for p_ in (
            '%s/IDEALSTATES/%s' % (self.root, name),
            '%s/EXTERNALVIEW/%s' % (self.root, name))]
        self.live_dir = self.root + '/LIVEINSTANCES'
        self.rate = float(p['changes_per_s'])
        self.deadline_ms = fleet.deadline_ms
        self.members = len(fleet.addrs)
        self.first = 0          # the member session 0 is attached to
        base = [n * self.grow for n in self.segments for _ in (0, 1)]
        self.checker = reference_docs.DocsChecker(
            fleet.seed, base, self.grow, self.n_brokers,
            [2 * t + 1 for t in range(self.tables)])
        self.order = schedule(self.segments)
        self.clients: list = []
        self.controllers: list = []
        self.brokers: list = []
        self.servers: list = []
        self.sent = [0] * len(self.paths)       # writes sent a document
        self.locks = [asyncio.Lock() for _ in range(self.tables)]
        self.broken: set[int] = set()   # tables with an unknown write
        #: every change sent: dict(table, version, size, due, acked,
        #: recorded, told)
        self.changes: list[dict] = []
        #: table -> its newest change whose EXTERNALVIEW write is out
        self.herd_open: list = [None] * self.tables
        self.recording = False
        self.stopping = False
        self.live = False       # set-up is over: a gap is a violation
        self.t_go = 0.0
        self.gen = None
        self.tasks: set = set()
        self.errors: dict[str, int] = {}
        self.failed_writes = 0
        self.acked = 0          # changes acknowledged in the window
        self.views = 0          # views handed to listeners in the window
        self.writes_acked = 0   # setData acknowledged in the window
        self.bytes_written = 0
        self.samples = {'write': [], 'write_large': [], 'converge': [],
                        'herd': []}
        #: large writes by the member their controller is attached to
        #: (the harness marks the leader's): attached against forwarded
        self.by_member: dict[int, list] = {}
        self.late_ms: list[float] = []
        self.never = 0
        self.expected = 0
        self.readback_failures = 0
        self.moved_open: dict = {}
        self.moved: dict = {}

    # -- set-up ---------------------------------------------------------

    def _size(self, table: int, version: int) -> int:
        return (self.segments[table] + version) * self.grow

    async def _warm_classes(self) -> None:
        """The size classes wider than the narrowest (which the harness
        warms) up to the one that holds every watched document at once
        (a broker's slot can hold the replies of overlapping changes),
        at every row count the brokers can give a dispatch of that
        width: what is compiled here is not compiled in the window."""
        ingest = self.fleet.ingest
        widest = sum(self._size(t, 64) + REPLY_OVERHEAD
                     for t in range(self.tables))
        cap = int(getattr(ingest, 'DISPATCH_BYTES', 16 << 20))
        t0 = time.perf_counter()

        def work():
            width = 2 * ingest.min_len
            while width < 2 * widest:
                rows = 1
                while rows < 2 * min(self.n_brokers,
                                     max(1, cap // width)):
                    asyncio.run(ingest.prewarm(rows, width))
                    rows *= 2
                width *= 2
        await asyncio.get_running_loop().run_in_executor(None, work)
        print('# view_change warmed the classes up to %d B: %d buckets '
              'in %.2fs' % (widest, len(ingest.buckets),
                            time.perf_counter() - t0), flush=True)

    async def load(self) -> None:
        """The tree, through one plain session on member 0, in MULTI
        batches bounded in bytes; the ingest's wide classes compile
        meanwhile."""
        warm = asyncio.ensure_future(self._warm_classes())
        c = self.fleet.new_client(0, through_ingest=False)
        await c.wait_connected(timeout=60)
        t0 = time.perf_counter()
        await c.create(self.root, b'')
        tx = c.transaction()
        for d in ('IDEALSTATES', 'EXTERNALVIEW', 'LIVEINSTANCES'):
            tx.create('%s/%s' % (self.root, d), b'')
        await tx.commit()
        batches, batch, held = [], [], 0
        for doc, n in enumerate(self.checker.base):
            if batch and (held + n > LOAD_BYTES or len(batch) >= LOAD_OPS):
                batches.append(batch)
                batch, held = [], 0
            batch.append(doc)
            held += n
        batches.append(batch)
        lanes = asyncio.Semaphore(LOAD_LANES)

        async def one(batch):
            async with lanes:
                tx = c.transaction()
                for doc in batch:
                    tx.create(self.paths[doc], self.checker.initial(doc))
                await tx.commit()
        await asyncio.gather(*[one(b) for b in batches])
        self.fleet.clients.remove(c)
        await c.close()
        print('# view_change tree: %d documents, %d bytes in %d batches, '
              '%.2fs' % (len(self.paths), sum(self.checker.base),
                         len(batches), time.perf_counter() - t0),
              flush=True)
        await warm

    def _sid(self, c) -> int:
        sid = c.session.session_id
        return int(sid, 16) if isinstance(sid, str) else int(sid)

    def _eph(self, i: int) -> tuple[str, bytes]:
        return ('%s/Server_%04d' % (self.live_dir, i),
                self.checker.payloads.get(1000 + i, 0, self.eph_bytes))

    async def _herd(self, doc: int, version: int) -> bool:
        """Wait, at most ``SETTLE_S``, until every broker's listener
        holds ``doc`` at ``version`` or later."""
        deadline = time.monotonic() + SETTLE_S
        seen = self.checker.seen_at
        while not all(seen(b, doc, version) is not None
                      for b in range(self.n_brokers)):
            if time.monotonic() > deadline:
                return False
            await asyncio.sleep(0.005)
        return True

    async def _leader(self) -> int:
        """The member whose ``mntr`` says it leads (0 when none
        answers so: the placement is then the plain round-robin)."""
        for m, (host, port) in enumerate(self.fleet.addrs):
            try:
                reader, writer = await asyncio.wait_for(
                    asyncio.open_connection(host, port), 5)
                writer.write(b'mntr')
                rows = (await asyncio.wait_for(reader.read(-1), 5)).decode(
                    'utf-8', 'replace')
                writer.close()
            except (OSError, asyncio.TimeoutError, TimeoutError):
                continue
            if 'zk_member_role\tleader' in rows:
                return m
        return 0

    async def connect(self) -> None:
        from zkstream_tpu import CreateFlag

        n = self.members
        self.first = first = await self._leader()
        self.clients = [self.fleet.new_client((first + s) % n)
                        for s in range(self.sessions)]
        await asyncio.gather(*[c.wait_connected(timeout=120)
                               for c in self.clients])
        a, b = self.n_ctrl, self.n_ctrl + self.n_brokers
        self.controllers = self.clients[:a]
        self.brokers = self.clients[a:b]
        self.servers = self.clients[b:]
        for s, c in enumerate(self.clients):
            c.on('disconnect', lambda s=s: self._gap(s, 'a disconnect'))
            c.on('expire', lambda s=s: self._gap(s, 'an expiry'))
        # a session attached to a follower must not start against half
        # a tree: every member serves the last document loaded
        for m in range(n):
            await self.clients[m].sync(self.root)
            await self.clients[m].stat(self.paths[-1])
        t0 = time.perf_counter()
        await asyncio.gather(*[
            c.create(*self._eph(i), flags=CreateFlag.EPHEMERAL)
            for i, c in enumerate(self.servers)])
        t1 = time.perf_counter()
        # the brokers' watches, a table at a time: each arming is a
        # herd of first reads of one document, as a change's is
        waiting = True
        for t in range(self.tables):
            doc = 2 * t + 1
            for bi, c in enumerate(self.brokers):
                self._arm(bi, c, t)
            if waiting:
                waiting = await self._herd(doc, 0)
                if not waiting:
                    print('# view_change: not every broker was shown '
                          'table %d in %g s; not waiting for the rest'
                          % (t, SETTLE_S), flush=True)
        t2 = time.perf_counter()
        # the first change of every table in turn: proves every
        # subscription and takes every size class through the tick.  A
        # broker that is not shown it is the check's to report
        # (``missed-change``), not set-up's to wait for
        for t in range(self.tables):
            await self._change(t, time.perf_counter(), False)
            if self.broken:
                raise RuntimeError('view_change: the first change of '
                                   'table %d failed' % (t,))
            if waiting:
                waiting = await self._herd(2 * t + 1, 1)
        print('# view_change session 0 on member %d (the leader); '
              'ephemerals %.2fs (%d) watches %.2fs (%d first '
              'reads) first changes %.2fs (%d)' % (
                  self.first, t1 - t0, len(self.servers), t2 - t1,
                  self.tables * self.n_brokers,
                  time.perf_counter() - t2, self.tables), flush=True)
        self.live = True

    def _arm(self, bi: int, c, t: int) -> None:
        """Broker ``bi`` watches table ``t``'s EXTERNALVIEW document
        through the client's own watcher.  The benchmark's one hook:
        the watcher's ``notify`` (where the session hands it the
        watch's notification) is stamped on its way through — when a
        change first reached any broker, and that this broker was told
        — and nothing of it is changed."""
        doc = 2 * t + 1
        w = c.watcher(self.paths[doc])
        notify = w.notify

        def told(evt):
            ch = self.herd_open[t]
            if ch is not None and ch['told'] is None:
                ch['told'] = time.perf_counter()
            self.checker.notified(bi, doc)
            notify(evt)
        w.notify = told
        self.checker.armed(bi, doc)

        def shown(data, stat):
            self.views += self.recording
            self.checker.emitted(bi, doc, time.perf_counter(), data,
                                 stat.dataLength, stat.version,
                                 self.sent[doc])
        w.on('dataChanged', shown)

    def _gap(self, s: int, what: str) -> None:
        if self.live:
            self.checker.gap(s, what)

    def _error(self, exc: BaseException) -> None:
        name = getattr(exc, 'code', None) or type(exc).__name__
        self.errors[name] = self.errors.get(name, 0) + 1

    # -- traffic --------------------------------------------------------

    def _moved(self) -> dict:
        ingest = self.fleet.ingest
        out = {k: int(getattr(ingest, k)) for k in INGEST_MOVED
               if hasattr(ingest, k)}
        hist = getattr(ingest, 'phase_hist', None)
        if hist is not None:
            for phase in ('batch', 'dispatch', 'readback', 'route'):
                out[phase + '_ms'] = round(hist.sum({'phase': phase}))
        tier = getattr(self.clients[0], 'transport_tier', None)
        for k in TIER_MOVED:
            if hasattr(tier, k):
                out['tier_' + k] = int(getattr(tier, k))
        t = os.times()
        out.update(loop_cpu_ms=round(time.thread_time() * 1e3),
                   user_ms=round(t.user * 1e3), sys_ms=round(t.system * 1e3))
        return out

    def start(self) -> None:
        self.t_go = time.perf_counter()
        self.gen = asyncio.ensure_future(self._generate())

    def open_window(self, t: float) -> None:
        self.recording = True
        self.moved_open = self._moved()

    def close_window(self, t: float) -> None:
        self.recording = False
        self.stopping = True
        self.moved = {k: v - self.moved_open.get(k, 0)
                      for k, v in self._moved().items()}

    async def _generate(self) -> None:
        j = 0
        while not self.stopping:
            due = self.t_go + (j + 0.5) / self.rate
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
                if self.stopping:
                    return
            t = asyncio.ensure_future(self._change(
                next(self.order), due, self.recording))
            self.tasks.add(t)
            t.add_done_callback(self.tasks.discard)
            j += 1

    def _change_lost(self, table: int, due: float, rec: bool) -> None:
        """A change that reached no one: a write of it failed, or it
        was not sent because the table's last write has an unknown
        outcome.  It was due all the same: it counts, and weighs as the
        deadline."""
        self.changes.append({'table': table, 'version': None, 'size': 0,
                             'due': due, 'acked': None, 'recorded': rec,
                             'told': None})
        if rec:
            self.failed_writes += 1
            self.samples['write'].append(float(self.deadline_ms))

    async def _change(self, table: int, due: float, rec: bool) -> None:
        """Table ``table``'s controller adds a segment: IDEALSTATES,
        then EXTERNALVIEW; serial per table."""
        ctrl = self.controllers[table % self.n_ctrl]
        async with self.locks[table]:
            begun = time.perf_counter()
            if rec:
                self.late_ms.append((begun - due) * 1e3)
            if table in self.broken:
                self._change_lost(table, due, rec)
                return
            ch = {'table': table, 'version': None, 'size': 0, 'due': due,
                  'acked': None, 'recorded': rec, 'told': None}
            for doc in (2 * table, 2 * table + 1):
                data = self.checker.next_write(doc)
                if len(data) + REPLY_OVERHEAD > JUTE_MAXBUFFER:
                    raise RuntimeError(
                        'view_change: document %d would grow to %d B, a '
                        'reply frame over jute.maxbuffer' % (doc, len(data)))
                self.sent[doc] += 1
                if doc & 1:
                    ch['size'] = len(data)
                    self.herd_open[table] = ch
                sent = time.perf_counter()
                try:
                    stat = await ctrl.set(self.paths[doc], data, version=-1,
                                          deadline=self.deadline_ms)
                except asyncio.CancelledError:
                    self.checker.write_unknown(doc)
                    raise
                except Exception as e:
                    self._error(e)
                    self.checker.write_unknown(doc)
                    self.broken.add(table)
                    self._change_lost(table, due, rec)
                    return
                acked = time.perf_counter()
                self.checker.write_acked(doc, stat.version, stat.mzxid)
                if rec:
                    ms = (acked - sent) * 1e3
                    self.samples['write'].append(ms)
                    if len(data) >= self.large:
                        self.by_member.setdefault(
                            (self.first + table % self.n_ctrl)
                            % self.members, []).append(ms)
                        if doc & 1:
                            self.samples['write_large'].append(ms)
                if self.recording:
                    self.writes_acked += 1
                    self.bytes_written += len(data)
            ch['version'] = stat.version
            ch['acked'] = acked
            self.changes.append(ch)
            if self.recording:
                self.acked += 1

    def _settled(self) -> bool:
        seen = self.checker.seen_at
        return all(ch['acked'] is None or all(
            seen(b, 2 * ch['table'] + 1, ch['version']) is not None
            for b in range(self.n_brokers))
            for ch in self.changes[-self.tables:])

    async def drain(self, timeout: float) -> int:
        """Wait, bounded, for the changes in flight and for every
        broker to have been shown the last ones."""
        deadline = time.monotonic() + timeout
        if self.gen is not None:
            await asyncio.gather(self.gen, return_exceptions=True)
        waiting = set(self.tasks)
        if waiting:
            await asyncio.wait(waiting, timeout=timeout)
        out = sum(not t.done() for t in waiting)
        for t in waiting:
            t.cancel()
        while time.monotonic() < deadline and not self._settled():
            await asyncio.sleep(0.05)
        return out

    # -- the checks after the window ------------------------------------

    async def validate(self) -> None:
        chk = self.checker
        self.live = False
        chk.finish()
        # a change is converged at a broker by the first view its
        # listener was handed that shows it (or a later change); one
        # that failed, or was not sent, reached no one: every pair of
        # it counts, as failed and as the deadline
        for ch in self.changes:
            if not ch['recorded']:
                continue
            doc = 2 * ch['table'] + 1
            times = [None if ch['acked'] is None
                     else chk.seen_at(b, doc, ch['version'])
                     for b in range(self.n_brokers)]
            for t in times:
                self.expected += 1
                self.never += t is None
                self.samples['converge'].append(
                    float(self.deadline_ms) if t is None
                    else (t - ch['due']) * 1e3)
            if (ch['size'] >= self.large and ch['told'] is not None
                    and None not in times):
                self.samples['herd'].append(
                    (max(times) - ch['told']) * 1e3)
        # the tree after the window, after sync: every document from
        # another member than its controller's, every ephemeral from
        # another member than its owner's
        n = self.members
        readers = [self.fleet.new_client(m, through_ingest=False)
                   for m in range(n)]
        await asyncio.gather(*[r.wait_connected(timeout=60)
                               for r in readers])
        await asyncio.gather(*[r.sync(self.root) for r in readers])
        lanes = asyncio.Semaphore(16)

        async def read(m, path):
            async with lanes:
                try:
                    return await readers[m].get(path)
                except Exception as e:
                    if getattr(e, 'code', None) == 'NO_NODE':
                        return None, None
                    self.readback_failures += 1
                    return None

        async def one_doc(doc):
            m = (self.first + (doc // 2) % self.n_ctrl + 1) % n
            got = await read(m, self.paths[doc])
            if got is None:
                return
            data, stat = got
            chk.final(doc, data, stat.dataLength if stat else 0,
                      stat.version if stat else 0, 'member %d' % (m,))

        async def one_eph(i):
            s = self.n_ctrl + self.n_brokers + i
            m = (self.first + s + 1) % n
            path, want = self._eph(i)
            got = await read(m, path)
            if got is None:
                return
            data, stat = got
            chk.final_ephemeral(
                i, data, stat.ephemeralOwner if stat else 0, want,
                self._sid(self.servers[i]), 'member %d' % (m,))
        await asyncio.gather(
            *[one_doc(d) for d in range(len(self.paths))],
            *[one_eph(i) for i in range(len(self.servers))])

    def result(self) -> dict:
        bad = self.checker.bad
        kinds = dict(bad.by_kind)
        first = list(bad.first)
        if self.readback_failures:
            kinds['readback-failed'] = self.readback_failures
            first.append('readback-failed: %d znodes could not be read '
                         'back after the window'
                         % (self.readback_failures,))
        compared = ['%s %d limit 0' % (k, kinds.get(k, 0))
                    for k in reference_docs.KINDS + ('readback-failed',)]
        compared.append('observations_checked %d' % (self.checker.checked,))
        recorded = [c for c in self.changes if c['recorded']]
        return {
            'attempted': 2 * len(recorded) + self.expected,
            'failed': self.failed_writes + self.never,
            'acked': self.views, 'samples': self.samples,
            'deadline_ms': self.deadline_ms,
            'late_ms': self.late_ms,
            'samples_by_member': self.by_member,
            'counters': {'errors': self.errors,
                         'writes_acked': self.writes_acked,
                         'bytes_written': self.bytes_written,
                         'changes_acked': self.acked,
                         'changes_recorded': len(recorded),
                         'changes_failed': self.failed_writes,
                         'changes_large': len(self.samples['herd']),
                         'pairs_never_converged': self.never,
                         'tables_broken': len(self.broken),
                         'ingest': self.moved},
            'compared': compared, 'violations': first,
            'violation_kinds': kinds, 'checked': self.checker.checked,
        }

    async def stop(self) -> None:
        self.stopping = True
        tasks = list(self.tasks) + ([self.gen] if self.gen else [])
        for t in tasks:
            t.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
