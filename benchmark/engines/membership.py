"""``membership``: group membership with watchers, churned on a paced
open-loop schedule (the ZooKeeper "Group Membership" recipe).

The deployment (``configs/<config>.json``): ``services`` group znodes
under ``root``; each of ``sessions`` sessions registers ONE
ephemeral-sequential instance (``instance_bytes`` of payload) under
service ``s % services`` and subscribes, the way a user of the library
does — ``client.watcher(service).on('childrenChanged', ...)``, whose
re-arm engine re-lists with a watch on every notification — to
``watch_per_session`` other services drawn from the seed so that every
service has the same number of watchers.

Parameters (``traffic/<mix>.json``): ``changes_per_s`` membership
changes a second fleet-wide on a fixed schedule, the churners (one per
service: session g churns service g) taking turns in a seeded order;
a change is the churner deleting its instance, or creating it again.
A churner's changes are serial.  Each change is timed from when it was
DUE, so a stall shows in the changes behind it.

Registration and the first lists are set-up.
"""

from __future__ import annotations

import asyncio
import random
import time

import reference

PREFIX = 'i-'


def assign_watches(seed: int, sessions: int, services: int,
                   per_session: int) -> list[list[int]]:
    """``per_session`` distinct services for every session, none its
    own, every service watched by the same number of sessions: deal the
    shuffled slots round and repair the few collisions by swapping."""
    rng = random.Random('%d/watches' % (seed,))
    slots = [g for g in range(services)
             for _ in range(sessions * per_session // services)]
    rng.shuffle(slots)

    def clash(i):
        s = i // per_session
        mine = slots[s * per_session:(s + 1) * per_session]
        return slots[i] == s % services or mine.count(slots[i]) > 1
    for _round in range(64):
        bad = [i for i in range(len(slots)) if clash(i)]
        if not bad:
            break
        for i in bad:
            j = rng.randrange(len(slots))
            slots[i], slots[j] = slots[j], slots[i]
            if clash(i) or clash(j):
                slots[i], slots[j] = slots[j], slots[i]
    else:
        raise ValueError('membership: no watch assignment found')
    return [slots[s * per_session:(s + 1) * per_session]
            for s in range(sessions)]


class Engine:
    def __init__(self, fleet):
        self.fleet = fleet
        cfg, p = fleet.config, fleet.params
        self.sessions = int(cfg['sessions'])
        self.services = int(cfg['services'])
        self.root = cfg['root']
        self.paths = ['%s/s%02d' % (self.root, g)
                      for g in range(self.services)]
        self.rate = float(p['changes_per_s'])
        self.deadline_ms = fleet.deadline_ms
        self.payloads = reference.Payloads(fleet.seed,
                                           int(cfg['instance_bytes']))
        self.watches = assign_watches(
            fleet.seed, self.sessions, self.services,
            int(cfg['watch_per_session']))
        self.watchers_of = [[] for _ in range(self.services)]
        for s, gs in enumerate(self.watches):
            for g in gs:
                self.watchers_of[g].append(s)
        self.checker = reference.MembershipChecker(self.paths, PREFIX)
        self.order = list(range(self.services))
        random.Random('%d/churn' % (fleet.seed,)).shuffle(self.order)
        self.clients: list = []
        self.sids: list[int] = []
        self.instance: list[str | None] = [None] * self.sessions
        #: (t, watcher, service, names, cversion) as the callbacks ran
        self.events: list = []
        #: every change sent: dict(g, k, due, sent, acked, recorded)
        self.changes: list[dict] = []
        self.sent_changes = 0
        self.locks = [asyncio.Lock() for _ in range(self.services)]
        self.broken: set[int] = set()   # services with an unknown change
        self.recording = False
        self.stopping = False
        self.t_go = 0.0
        self.gen = None
        self.tasks: set = set()
        self.errors: dict[str, int] = {}
        self.failed_writes = 0
        self.acked = 0
        self.samples = {'write': [], 'converge': []}
        self.late_ms: list[float] = []
        self.missed = 0
        self.never = 0      # (change, watcher) pairs that never converged
        self.expected = 0
        self.readback_failures = 0

    # -- set-up ---------------------------------------------------------

    async def load(self) -> None:
        c = self.fleet.new_client(0, through_ingest=False)
        await c.wait_connected(timeout=60)
        await c.create(self.root, b'')
        tx = c.transaction()
        for p in self.paths:
            tx.create(p, b'')
        await tx.commit()
        self.fleet.clients.remove(c)
        await c.close()

    def _sid(self, c) -> int:
        sid = c.session.session_id
        return int(sid, 16) if isinstance(sid, str) else int(sid)

    async def connect(self) -> None:
        from zkstream_tpu import CreateFlag

        self.flags = CreateFlag.EPHEMERAL | CreateFlag.SEQUENTIAL
        n = len(self.fleet.addrs)
        self.clients = [self.fleet.new_client(s % n)
                        for s in range(self.sessions)]
        await asyncio.gather(*[c.wait_connected(timeout=120)
                               for c in self.clients])
        self.sids = [self._sid(c) for c in self.clients]
        for m in range(n):
            await self.clients[m].sync(self.root)

        async def register(s):
            g = s % self.services
            data = self.payloads.get(s, 0)
            path = await self.clients[s].create(
                self.paths[g] + '/' + PREFIX, data, flags=self.flags)
            name = path.rsplit('/', 1)[1]
            self.instance[s] = name
            self.checker.register(g, name, self.sids[s], data)
        for lo in range(0, self.sessions, 256):
            await asyncio.gather(*[register(s) for s in range(
                lo, min(self.sessions, lo + 256))])
        # a watcher on a follower must see every registration in its
        # first list: sync, then subscribe
        await asyncio.gather(*[c.sync(self.root) for c in self.clients])
        self.checker.open_window()
        first = [0]
        want = sum(len(w) for w in self.watches)

        def subscribe(s, g):
            def on_children(children, stat, *_a):
                self.events.append((time.perf_counter(), s, g,
                                    tuple(children), stat.cversion))
                first[0] += 1
            self.clients[s].watcher(self.paths[g]).on(
                'childrenChanged', on_children)
        for s, gs in enumerate(self.watches):
            for g in gs:
                subscribe(s, g)
        deadline = time.monotonic() + 120
        while first[0] < want:
            if time.monotonic() > deadline:
                raise RuntimeError('membership: %d of %d watchers armed '
                                   'after 120 s' % (first[0], want))
            await asyncio.sleep(0.02)

    # -- traffic --------------------------------------------------------

    def start(self) -> None:
        self.t_go = time.perf_counter()
        self.gen = asyncio.ensure_future(self._generate())

    def open_window(self, t: float) -> None:
        self.recording = True

    def close_window(self, t: float) -> None:
        self.recording = False
        self.stopping = True

    async def _generate(self) -> None:
        j = 0
        while not self.stopping:
            due = self.t_go + j / self.rate
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
                if self.stopping:
                    return
            g = self.order[j % self.services]
            t = asyncio.ensure_future(self._change(g, due, self.recording))
            self.tasks.add(t)
            t.add_done_callback(self.tasks.discard)
            j += 1

    async def _change(self, g: int, due: float, rec: bool) -> None:
        """Churner g (session g) deletes its instance or creates it
        again; serial per churner."""
        async with self.locks[g]:
            if g in self.broken:
                # the churner's last change has an unknown outcome, so
                # this one cannot be sent; it was due all the same: a
                # change a user did not get, counted as failed
                if rec:
                    self.late_ms.append(
                        (time.perf_counter() - due) * 1e3)
                    self.changes.append({'g': g, 'k': None, 'due': due,
                                         'sent': None, 'acked': None,
                                         'recorded': True})
                    self.failed_writes += 1
                    self.samples['write'].append(float(self.deadline_ms))
                return
            c = self.clients[g]
            name = self.instance[g]
            kind = 'delete' if name is not None else 'create'
            data = self.payloads.get(g, self.sent_changes + 1)
            self.sent_changes += 1
            sent = time.perf_counter()
            if rec:
                self.late_ms.append((sent - due) * 1e3)
            try:
                if kind == 'delete':
                    await c.delete(self.paths[g] + '/' + name, -1,
                                   deadline=self.deadline_ms)
                    new = None
                else:
                    path = await c.create(self.paths[g] + '/' + PREFIX,
                                          data, flags=self.flags,
                                          deadline=self.deadline_ms)
                    new = path.rsplit('/', 1)[1]
            except asyncio.CancelledError:
                raise
            except Exception as e:
                code = getattr(e, 'code', None) or type(e).__name__
                self.errors[code] = self.errors.get(code, 0) + 1
                self.broken.add(g)
                k = self.checker.change(g, kind, None)
                self.changes.append({'g': g, 'k': k, 'due': due,
                                     'sent': sent, 'acked': None,
                                     'recorded': rec})
                if rec:
                    self.failed_writes += 1
                    self.samples['write'].append(float(self.deadline_ms))
                return
            acked = time.perf_counter()
            self.instance[g] = new
            k = self.checker.change(g, kind, new if kind == 'create'
                                    else name, self.sids[g], data)
            self.changes.append({'g': g, 'k': k, 'due': due, 'sent': sent,
                                 'acked': acked, 'recorded': rec})
            if rec:
                self.samples['write'].append((acked - sent) * 1e3)
            if self.recording:
                self.acked += 1

    def _converged(self) -> bool:
        last = [len(st) - 1 for st in self.checker.states]
        seen: dict = {}
        for _t, w, g, _names, cv in self.events:
            k = cv - self.checker.base[g]
            if k > seen.get((w, g), -1):
                seen[(w, g)] = k
        return all(seen.get((w, g), -1) >= last[g]
                   for g in range(self.services) if last[g] > 0
                   and g not in self.broken
                   for w in self.watchers_of[g])

    async def drain(self, timeout: float) -> int:
        """Wait, bounded, for the changes in flight and for every
        watcher to have listed the last change of its services."""
        deadline = time.monotonic() + timeout
        if self.gen is not None:
            await asyncio.gather(self.gen, return_exceptions=True)
        while self.tasks and time.monotonic() < deadline:
            await asyncio.wait(set(self.tasks),
                               timeout=deadline - time.monotonic())
        out = len(self.tasks)
        for t in list(self.tasks):
            t.cancel()
        while time.monotonic() < deadline and not self._converged():
            await asyncio.sleep(0.05)
        return out

    # -- the checks after the window ------------------------------------

    async def validate(self) -> None:
        chk = self.checker
        # every list a watcher was handed, in the order it arrived
        seen_at: dict = {}
        for t, w, g, names, cv in self.events:
            k = chk.listing(w, g, names, cv)
            if k >= 0:
                seen_at.setdefault((w, g), []).append((t, k))
        self.missed = chk.finish(
            [[] if g in self.broken else ws
             for g, ws in enumerate(self.watchers_of)])
        # a change is converged at a watcher by the first list that
        # shows it (or a later change); one that failed, or was not
        # sent because the churner's last one had, reached no watcher:
        # every pair of it counts, as failed and as the deadline
        for ch in self.changes:
            if not ch['recorded']:
                continue
            for w in self.watchers_of[ch['g']]:
                self.expected += 1
                t = None if ch['acked'] is None else next(
                    (t for t, k in seen_at.get((w, ch['g']), ())
                     if k >= ch['k']), None)
                self.never += t is None
                self.samples['converge'].append(
                    float(self.deadline_ms) if t is None
                    else (t - ch['due']) * 1e3)
        # the tree after the window, from another member than the
        # churner's, after sync
        n = len(self.fleet.addrs)
        readers = [self.fleet.new_client(m, through_ingest=False)
                   for m in range(n)]
        await asyncio.gather(*[r.wait_connected(timeout=60)
                               for r in readers])
        await asyncio.gather(*[r.sync(self.root) for r in readers])

        async def one(g):
            r = readers[(g % n + 1) % n]
            try:
                names, _stat = await r.list(self.paths[g])
                stats = await asyncio.gather(*[
                    r.stat(self.paths[g] + '/' + nm) for nm in names])
            except Exception:
                self.readback_failures += 1
                return
            chk.final(g, names, {nm: st.ephemeralOwner
                                 for nm, st in zip(names, stats)},
                      'member %d' % ((g % n + 1) % n,))
        await asyncio.gather(*[one(g) for g in range(self.services)])

    def result(self) -> dict:
        bad = self.checker.bad
        kinds = dict(bad.by_kind)
        first = list(bad.first)
        if self.readback_failures:
            kinds['readback-failed'] = self.readback_failures
            first.append('readback-failed: %d services could not be '
                         'listed after the window'
                         % (self.readback_failures,))
        compared = ['%s %d limit 0' % (k, kinds.get(k, 0)) for k in (
            'children', 'stale-list', 'future-list', 'not-notified',
            'sequential-name', 'sequential-names', 'duplicate-name',
            'delete', 'final-children', 'ephemeral-owner',
            'readback-failed')]
        compared.append('observations_checked %d' % (self.checker.checked,))
        recorded = [c for c in self.changes if c['recorded']]
        return {
            'attempted': len(recorded) + self.expected,
            'failed': self.failed_writes + self.never,
            'acked': self.acked, 'samples': self.samples,
            'deadline_ms': self.deadline_ms,
            'late_ms': self.late_ms,
            'counters': {'errors': self.errors,
                         'writes_acked': self.acked,
                         'changes_recorded': len(recorded),
                         'lists_received': len(self.events),
                         'changes_failed': self.failed_writes,
                         'pairs_never_converged': self.never,
                         'services_broken': len(self.broken)},
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
