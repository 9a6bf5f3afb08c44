"""``config_push``: configuration distribution to a fleet of
subscribers that each hold the configuration subtree in a watch-backed
cache (Hunt et al. sec 2.4 "Configuration Management", in the shape of
Apache Curator's ``CuratorCache``), with changes pushed on a paced
open-loop schedule.

The deployment (``configs/<config>.json``): ``tree`` = ``root``,
``groups``, ``keys`` (a group), ``bytes``: ``groups x keys``
configuration znodes two levels below the root.  Each of ``sessions``
sessions is a ``Client(cache=[root])`` on the fleet's shared ingest —
built as ``Fleet.new_client`` builds one, plus the cache — so its cache
plane subscribes the root ONCE with a persistent recursive watch; at
set-up it loads the subtree through the program's own
``CachePlane.prime(root)`` (as ``CuratorCache.start()`` does) and, from
the same registration (``client.add_watch(root, recursive=True)``
returns the session's one emitter), does on ``'dataChanged'(path,
zxid)`` what CuratorCache does: ``client.get(path)``, which misses the
entry the notification just dropped, goes to its member and fills the
cache again.

Sessions 0 .. members-1 (one a member) are the publishers; key k is
written only by publisher ``k % members``, so each key's writes are
serial and its version counts them.  Set-up ends with the first
publication of every key (version 1), which every subscriber must show
before the window opens: it proves each subscription end to end, and it
leaves each plane holding exactly what a change drops from then on —
the key's data entry (``prime`` also deposits each leaf's empty
children list, which the first change drops with it and nothing
re-reads).

Parameters (``traffic/<mix>.json``): ``changes_per_s`` configuration
changes a second fleet-wide on a fixed schedule (change j is due at
start + (j + 1/2) / rate: half a period off the harness's window
edges, which fall on whole periods), the keys taking turns in a seeded
order (and with them their publishers); ``reads_per_s``: every session
reads one seeded-random key through ``client.get`` that often — the
traffic the cache exists for; ``op_deadline_ms``.  A change is timed
from when it was DUE; a (change, subscriber) pair from then until that
subscriber's refreshed read shows the change's version or a later one.
"""

from __future__ import annotations

import asyncio
import random
import time

import reference_conf

SETTLE_S = 10.0         # set-up waits this long for one publication


class Engine:
    def __init__(self, fleet):
        self.fleet = fleet
        cfg, p = fleet.config, fleet.params
        tree = cfg['tree']
        self.sessions = int(cfg['sessions'])
        self.root = tree['root']
        self.groups = ['%s/g%d' % (self.root, g)
                       for g in range(int(tree['groups']))]
        self.paths = ['%s/k%02d' % (g, k) for g in self.groups
                      for k in range(int(tree['keys']))]
        self.index = {p: i for i, p in enumerate(self.paths)}
        self.rate = float(p['changes_per_s'])
        self.read_rate = float(p.get('reads_per_s', 0))
        self.deadline_ms = fleet.deadline_ms
        self.members = len(fleet.addrs)
        self.checker = reference_conf.ConfChecker(
            fleet.seed, len(self.paths), int(tree['bytes']), self.sessions)
        self.order = list(range(len(self.paths)))
        random.Random('%d/push' % (fleet.seed,)).shuffle(self.order)
        self.clients: list = []
        self.sent = [0] * len(self.paths)       # writes sent per key
        self.locks = [asyncio.Lock() for _ in self.paths]
        self.broken: set[int] = set()   # keys with an unknown write
        #: every change sent: dict(key, version, due, acked, recorded)
        self.changes: list[dict] = []
        self.recording = False
        self.stopping = False
        self.live = False       # set-up is over: a gap is a violation
        self.t_go = 0.0
        self.gen = None
        self.tasks: set = set()         # changes in flight
        self.readers: list = []
        self.refresh_tasks: set = set()     # refresh reads in flight
        self.errors: dict[str, int] = {}
        self.failed_writes = 0
        self.failed_reads = 0
        self.reads = 0                  # recorded reads
        self.acked = 0                  # changes acked in the window
        self.samples = {'write': [], 'converge': [], 'read': []}
        self.late_ms: list[float] = []
        self.never = 0
        self.expected = 0
        self.readback_failures = 0
        self.cache_open: dict = {}
        self.cache_window: dict = {}

    # -- set-up ---------------------------------------------------------

    async def load(self) -> None:
        """The tree, through one plain session on member 0 (no ingest:
        the tick programs are compiling meanwhile)."""
        c = self.fleet.new_client(0, through_ingest=False)
        await c.wait_connected(timeout=60)
        await c.create(self.root, b'')
        tx = c.transaction()
        for g in self.groups:
            tx.create(g, b'')
        await tx.commit()
        tx = c.transaction()
        for idx, path in enumerate(self.paths):
            tx.create(path, self.checker.initial(idx))
        await tx.commit()
        self.fleet.clients.remove(c)
        await c.close()

    def _new_client(self, member: int):
        """``Fleet.new_client`` (which takes no ``cache``), plus the
        cache: the same servers, ingest, time-outs and control wrap,
        closed by the harness with the rest of the fleet."""
        from zkstream_tpu import Client

        f = self.fleet
        c = Client(servers=[f.addrs[member % len(f.addrs)]],
                   shuffle_backends=False, ingest=f.ingest,
                   session_timeout=f.session_timeout_ms,
                   op_timeout=f.deadline_ms, cache=[self.root])
        if f.wrap_client is not None:
            c = f.wrap_client(c) or c
        c.start()
        f.clients.append(c)
        return c

    async def _until(self, cond, what: str, timeout: float = 120.0):
        deadline = time.monotonic() + timeout
        while not cond():
            if time.monotonic() > deadline:
                raise RuntimeError('config_push: %s after %g s'
                                   % (what, timeout))
            await asyncio.sleep(0.02)

    async def _settle(self, told: int) -> bool:
        """Wait, at most ``SETTLE_S``, until ``told`` (change,
        subscriber) pairs have been notified and no refresh is out."""
        deadline = time.monotonic() + SETTLE_S
        while len(self.checker.told) < told or self.refresh_tasks:
            if time.monotonic() > deadline:
                return False
            await asyncio.sleep(0.005)
        return True

    async def connect(self) -> None:
        n = self.members
        self.clients = [self._new_client(s % n)
                        for s in range(self.sessions)]
        await asyncio.gather(*[c.wait_connected(timeout=120)
                               for c in self.clients])
        # the cache serves and fills only once its recursive watch is
        # armed; a session on a follower must see the whole tree
        await self._until(lambda: all(
            c.cache.stats()['armed'] == 1 for c in self.clients),
            'not every cache plane armed')
        await asyncio.gather(*[c.sync(self.root) for c in self.clients])
        t0 = time.perf_counter()
        seen = await asyncio.gather(*[c.cache.prime(self.root)
                                      for c in self.clients])
        want = 1 + len(self.groups) + len(self.paths)
        if set(seen) != {want}:
            raise RuntimeError('config_push: prime visited %s nodes, the '
                               'subtree has %d' % (sorted(set(seen)), want))
        t1 = time.perf_counter()

        async def subscribe(s, c):
            # the plane's own registration: one emitter a session,
            # the plane's invalidation ahead of this listener
            w = await c.add_watch(self.root, recursive=True)
            w.on('dataChanged', lambda path, zxid:
                 self._on_changed(s, path, zxid))
            w.on('resumed', lambda: self._gap(s, "'resumed'"))
            w.on('lost', lambda: self._gap(s, "'lost'"))
            c.on('disconnect', lambda: self._gap(s, 'a disconnect'))
        await asyncio.gather(*[subscribe(s, c)
                               for s, c in enumerate(self.clients)])
        # the first publication, one change at a time: a program
        # whose fill gate is not per path (PR 26's parent) keeps few
        # of the refreshes of overlapping changes, and the window has
        # to open on a full cache.  A subscriber that is not told is
        # the check's to report (``missed-change``), not set-up's to
        # wait for: after the first such key the rest are not awaited
        waiting = True
        for k, idx in enumerate(self.order):
            await self._change(idx, time.perf_counter(), False)
            if self.broken:
                raise RuntimeError('config_push: the first publication '
                                   'of key %d failed' % (idx,))
            if waiting:
                waiting = await self._settle((k + 1) * self.sessions)
                if not waiting:
                    print('# config_push: the first publication of key '
                          '%d reached %d of %d (key, subscriber) pairs in '
                          '%g s; not waiting for the rest' % (
                              idx, len(self.checker.told),
                              (k + 1) * self.sessions, SETTLE_S),
                          flush=True)
        if not waiting:
            await self._settle(0)
        print('# config_push prime %.2fs (%d reads and lists) first '
              'publication %.2fs (%d changes)' % (
                  t1 - t0, 2 * want * self.sessions,
                  time.perf_counter() - t1, len(self.paths)), flush=True)
        self.live = True

    def _gap(self, s: int, what: str) -> None:
        if self.live:
            self.checker.gap(s, what)

    # -- the subscribers --------------------------------------------------

    def _on_changed(self, s: int, path: str, zxid: int) -> None:
        idx = self.index.get(path)
        if idx is None:
            return
        self.checker.notified(s, idx, zxid)
        self.refresh_tasks.add(
            asyncio.ensure_future(self._refresh(s, idx, zxid)))

    async def _refresh(self, s: int, idx: int, zxid: int) -> None:
        try:
            data, stat = await self.clients[s].get(
                self.paths[idx], deadline=self.deadline_ms)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            self._error(e)
        else:
            self.checker.refreshed(s, idx, zxid, time.perf_counter(), data,
                                   stat.version, self.sent[idx])
        finally:
            self.refresh_tasks.discard(asyncio.current_task())

    async def _reader(self, s: int) -> None:
        """The subscriber's application: one read of a random key
        every 1 / ``reads_per_s`` s, on a schedule of its own."""
        c = self.clients[s]
        rng = random.Random('%d/read/%d' % (self.fleet.seed, s))
        period = 1.0 / self.read_rate
        first = self.t_go + period * s / self.sessions
        chk, n = self.checker, len(self.paths)
        k = 0
        while not self.stopping:
            delay = first + k * period - time.perf_counter()
            k += 1
            if delay > 0:
                await asyncio.sleep(delay)
                if self.stopping:
                    return
            idx = rng.randrange(n)
            rec = self.recording
            t0 = time.perf_counter()
            try:
                data, stat = await c.get(self.paths[idx],
                                         deadline=self.deadline_ms)
            except asyncio.CancelledError:
                raise
            except Exception as e:
                self._error(e)
                if rec:
                    self.reads += 1
                    self.failed_reads += 1
                    self.samples['read'].append(float(self.deadline_ms))
                continue
            if rec:
                self.reads += 1
                self.samples['read'].append(
                    (time.perf_counter() - t0) * 1e3)
            chk.read(s, idx, data, stat.version, self.sent[idx])

    def _error(self, exc: BaseException) -> None:
        name = getattr(exc, 'code', None) or type(exc).__name__
        self.errors[name] = self.errors.get(name, 0) + 1

    # -- traffic --------------------------------------------------------

    def _cache_totals(self) -> dict:
        planes = [c.cache for c in self.clients]
        return {k: sum(getattr(p, k) for p in planes)
                for k in ('hits', 'misses', 'invalidations')}

    def start(self) -> None:
        self.t_go = time.perf_counter()
        self.gen = asyncio.ensure_future(self._generate())
        if self.read_rate > 0:
            self.readers = [asyncio.ensure_future(self._reader(s))
                            for s in range(self.sessions)]

    def open_window(self, t: float) -> None:
        self.recording = True
        self.cache_open = self._cache_totals()

    def close_window(self, t: float) -> None:
        self.recording = False
        self.stopping = True
        after = self._cache_totals()
        self.cache_window = {k: after[k] - self.cache_open.get(k, 0)
                             for k in after}

    async def _generate(self) -> None:
        j = 0
        while not self.stopping:
            due = self.t_go + (j + 0.5) / self.rate
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
                if self.stopping:
                    return
            idx = self.order[j % len(self.order)]
            t = asyncio.ensure_future(self._change(idx, due,
                                                   self.recording))
            self.tasks.add(t)
            t.add_done_callback(self.tasks.discard)
            j += 1

    def _change_lost(self, idx: int, due: float, rec: bool) -> None:
        """A change that reached no one: failed, or not sent because
        the key's last write has an unknown outcome.  It was due all
        the same: it counts, and weighs as the deadline."""
        self.changes.append({'key': idx, 'version': None, 'due': due,
                             'acked': None, 'recorded': rec})
        if rec:
            self.failed_writes += 1
            self.samples['write'].append(float(self.deadline_ms))

    async def _change(self, idx: int, due: float, rec: bool) -> None:
        """Key ``idx``'s publisher writes its next version; serial per
        key."""
        async with self.locks[idx]:
            sent = time.perf_counter()
            if rec:
                self.late_ms.append((sent - due) * 1e3)
            if idx in self.broken:
                self._change_lost(idx, due, rec)
                return
            data = self.checker.next_write(idx)
            self.sent[idx] += 1
            try:
                stat = await self.clients[idx % self.members].set(
                    self.paths[idx], data, version=-1,
                    deadline=self.deadline_ms)
            except asyncio.CancelledError:
                self.checker.write_unknown(idx)
                raise
            except Exception as e:
                self._error(e)
                self.checker.write_unknown(idx)
                self.broken.add(idx)
                self._change_lost(idx, due, rec)
                return
            acked = time.perf_counter()
            self.checker.write_acked(idx, stat.version, stat.mzxid)
            self.changes.append({'key': idx, 'version': stat.version,
                                 'due': due, 'acked': acked,
                                 'recorded': rec})
            if rec:
                self.samples['write'].append((acked - sent) * 1e3)
            if self.recording:
                self.acked += 1

    def _settled(self) -> bool:
        acked = sum(ch['acked'] is not None for ch in self.changes)
        return (not self.refresh_tasks
                and len(self.checker.told) >= acked * self.sessions)

    async def drain(self, timeout: float) -> int:
        """Wait, bounded, for the changes and reads in flight and for
        every subscriber to have refreshed to the last change."""
        deadline = time.monotonic() + timeout
        if self.gen is not None:
            await asyncio.gather(self.gen, return_exceptions=True)
        waiting = set(self.tasks) | set(self.readers)
        if waiting:
            await asyncio.wait(waiting, timeout=timeout)
        out = sum(not t.done() for t in waiting)
        for t in waiting:
            t.cancel()
        while time.monotonic() < deadline and not self._settled():
            await asyncio.sleep(0.05)
        return out + len(self.refresh_tasks)

    # -- the checks after the window ------------------------------------

    async def validate(self) -> None:
        chk = self.checker
        self.live = False
        chk.finish()
        # a change is converged at a subscriber by its first refreshed
        # read that shows it (or a later change); one that failed, or
        # was not sent, reached no one: every pair of it counts, as
        # failed and as the deadline
        for ch in self.changes:
            if not ch['recorded']:
                continue
            for s in range(self.sessions):
                self.expected += 1
                t = (None if ch['acked'] is None
                     else chk.seen_at(s, ch['key'], ch['version']))
                self.never += t is None
                self.samples['converge'].append(
                    float(self.deadline_ms) if t is None
                    else (t - ch['due']) * 1e3)
        # the tree after the window, after sync, each key from another
        # member than its publisher's
        n = self.members
        readers = [self.fleet.new_client(m, through_ingest=False)
                   for m in range(n)]
        await asyncio.gather(*[r.wait_connected(timeout=60)
                               for r in readers])
        await asyncio.gather(*[r.sync(self.root) for r in readers])

        async def one(idx):
            m = (idx % n + 1) % n
            try:
                data, stat = await readers[m].get(self.paths[idx])
            except Exception as e:
                if getattr(e, 'code', None) == 'NO_NODE':
                    chk.final(idx, None, 0, 'member %d' % (m,))
                else:
                    self.readback_failures += 1
                return
            chk.final(idx, data, stat.version, 'member %d' % (m,))
        await asyncio.gather(*[one(i) for i in range(len(self.paths))])

    def result(self) -> dict:
        bad = self.checker.bad
        kinds = dict(bad.by_kind)
        first = list(bad.first)
        if self.readback_failures:
            kinds['readback-failed'] = self.readback_failures
            first.append('readback-failed: %d keys could not be read '
                         'back after the window'
                         % (self.readback_failures,))
        compared = ['%s %d limit 0' % (k, kinds.get(k, 0))
                    for k in reference_conf.KINDS + ('readback-failed',)]
        compared.append('observations_checked %d' % (self.checker.checked,))
        recorded = [c for c in self.changes if c['recorded']]
        return {
            'attempted': len(recorded) + self.expected + self.reads,
            'failed': self.failed_writes + self.never + self.failed_reads,
            'acked': self.acked, 'samples': self.samples,
            'deadline_ms': self.deadline_ms,
            'late_ms': self.late_ms,
            'counters': {'errors': self.errors,
                         'writes_acked': self.acked,
                         'changes_recorded': len(recorded),
                         'changes_failed': self.failed_writes,
                         'pairs_never_converged': self.never,
                         'keys_broken': len(self.broken),
                         'reads_recorded': self.reads,
                         'cache': self.cache_window},
            'compared': compared, 'violations': first,
            'violation_kinds': kinds, 'checked': self.checker.checked,
        }

    async def stop(self) -> None:
        self.stopping = True
        tasks = (list(self.tasks) + list(self.readers)
                 + list(self.refresh_tasks)
                 + ([self.gen] if self.gen else []))
        for t in tasks:
            t.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
