"""``rolling_restart``: every node of a cluster holds one ephemeral under
ONE directory and watches that directory; the nodes leave and return
one at a time — SolrCloud's ``/live_nodes`` through a rolling restart.

The deployment (``configs/<config>.json``): ``tree`` = ``parent``.
Session s is node s, attached to member ``s % members``: it holds the
ephemeral ``<parent>/<name_s>`` (no data; the names are drawn from
``--seed``) and arms the reference client's own
``client.watcher(parent).on('childrenChanged', ...)``: the watcher's
re-arm after a notification IS the ``getChildren`` with watch that
returns the whole directory.

Parameters (``traffic/<mix>.json``): ``changes_per_s`` changes a second
on a fixed open-loop schedule (change j is due at start + (j + 1/2) /
rate).  The nodes take turns in an order drawn from the seed; change
2k: node n_k LEAVES — ``await client.close()``, and the close removes
its ephemeral; change 2k + 1: node n_k RETURNS — a new session through
``fleet.new_client(<the same member>)``, connected, the ephemeral
created again, its watcher armed.  The changes are serial: one is sent
when it is due and the one before it is acknowledged (a return: its
watcher has been handed its first list), and is timed from when it was
DUE all the same.  A (change, other node) pair is timed from when the
change was due until that node's listener holds a list that shows it
or a later one.

Set-up: the parent, the registrations, the first lists, then ONE
leave-and-return that every session must show before the window opens.
``load()`` also warms the size classes of the fleet ingest that the
lists reach and the harness (which warms the narrowest) does not.
"""

from __future__ import annotations

import asyncio
import os
import random
import time

import reference_live

SETTLE_S = 20.0         # set-up waits this long for one herd
ARM_LANES = 256         # first lists asked for together in set-up
#: a reply frame over its names: length prefix, reply header, the
#: count, the Stat; a name: its length and the longest name there is
REPLY_OVERHEAD = 4 + 16 + 4 + 68
NAME_BYTES = 4 + 24
#: the fleet ingest's counters of what its device ticks moved, kept by
#: the engine over the window (the harness's own list is older)
INGEST_MOVED = ('dispatches', 'bytes_batched', 'bytes_dispatched',
                'bytes_recopied', 'slots_deferred', 'ticks', 'ticks_full',
                'frames_routed', 'names_routed')


class Engine:
    def __init__(self, fleet):
        self.fleet = fleet
        cfg, p = fleet.config, fleet.params
        self.sessions = int(cfg['sessions'])
        self.parent = cfg['tree']['parent']
        self.rate = float(p['changes_per_s'])
        self.deadline_ms = fleet.deadline_ms
        self.members = len(fleet.addrs)
        self.checker = reference_live.LiveChecker(
            fleet.seed, self.sessions, self.parent)
        self.order = list(range(self.sessions))
        random.Random('%d/restart' % (fleet.seed,)).shuffle(self.order)
        self.clients: list = [None] * self.sessions
        self.leaving: set[int] = set()  # nodes whose close is their own
        self.lock = asyncio.Lock()
        self.sent = 0           # changes sent
        self.broken = False     # a change's outcome is unknown
        #: every change sent: dict(k, node, kind, due, acked, recorded,
        #: told)
        self.changes: list[dict] = []
        self.herd_open: dict | None = None
        self.first_views = 0
        self.recording = False
        self.stopping = False
        self.live = False       # set-up is over: a gap is a violation
        self.t_go = 0.0
        self.gen = None
        self.tasks: set = set()
        self.errors: dict[str, int] = {}
        self.failed_changes = 0
        self.acked = 0          # changes acknowledged in the window
        self.views = 0          # views handed to listeners in the window
        self.samples = {'change': [], 'connect': [], 'converge': [],
                        'herd': []}
        #: a change's own time by the member its node is attached to
        #: (the harness marks the leader's): attached against forwarded
        self.by_member: dict[int, list] = {}
        self.late_ms: list[float] = []
        self.never = 0
        self.expected = 0
        self.readback_failures = 0
        self.moved_open: dict = {}
        self.moved: dict = {}

    # -- set-up ---------------------------------------------------------

    async def _warm_classes(self) -> None:
        """The size classes wider than the narrowest (which the harness
        warms) up to the one that holds two whole lists (a slot can
        hold the replies of two changes), at every row count the fleet
        can give a dispatch of that width: what is compiled here is not
        compiled in the window."""
        ingest = self.fleet.ingest
        widest = 2 * (REPLY_OVERHEAD + NAME_BYTES * self.sessions)
        cap = int(getattr(ingest, 'DISPATCH_BYTES', 16 << 20))
        t0 = time.perf_counter()

        def work():
            width = 2 * ingest.min_len
            while width < 2 * widest:
                rows = 1
                while rows < 2 * min(self.sessions, max(1, cap // width)):
                    asyncio.run(ingest.prewarm(rows, width))
                    rows *= 2
                width *= 2
        await asyncio.get_running_loop().run_in_executor(None, work)
        print('# rolling_restart warmed the classes up to %d B: %d '
              'buckets in %.2fs' % (widest, len(ingest.buckets),
                                    time.perf_counter() - t0), flush=True)

    async def load(self) -> None:
        """The one persistent parent, through a plain session on member
        0; the ingest's wide classes compile meanwhile."""
        warm = asyncio.ensure_future(self._warm_classes())
        c = self.fleet.new_client(0, through_ingest=False)
        await c.wait_connected(timeout=60)
        await c.create(self.parent, b'')
        self.fleet.clients.remove(c)
        await c.close()
        await warm

    def _sid(self, c) -> int:
        sid = c.session.session_id
        return int(sid, 16) if isinstance(sid, str) else int(sid)

    def _member(self, node: int) -> int:
        return node % self.members

    def _attach(self, node: int):
        """A new session for ``node`` on its member, a gap of it
        reported unless it is the node's own leave."""
        c = self.fleet.new_client(self._member(node))
        self.clients[node] = c
        for evt, what in (('disconnect', 'a disconnect'),
                          ('expire', 'an expiry')):
            c.on(evt, lambda what=what: self._gap(node, what))
        return c

    def _gap(self, node: int, what: str) -> None:
        if self.live and node not in self.leaving:
            self.checker.gap(node, what)

    async def _herd(self, k: int, but: int) -> bool:
        """Wait, at most ``SETTLE_S``, until every node but ``but``
        holds a list that shows change ``k`` or a later one."""
        deadline = time.monotonic() + SETTLE_S
        newest = self.checker.newest
        while not all(newest[n] >= k
                      for n in range(self.sessions) if n != but):
            if time.monotonic() > deadline:
                return False
            await asyncio.sleep(0.005)
        return True

    async def connect(self) -> None:
        from zkstream_tpu import CreateFlag

        self.flag = CreateFlag.EPHEMERAL
        t0 = time.perf_counter()
        for s in range(self.sessions):
            self._attach(s)
        await asyncio.gather(*[c.wait_connected(timeout=120)
                               for c in self.clients])
        # a session attached to a follower must not register under a
        # parent its member has not applied yet
        for m in range(self.members):
            await self.clients[m].sync(self.parent)
        t1 = time.perf_counter()

        async def register(s):
            path = await self.clients[s].create(
                self.checker.path(s), b'', flags=self.flag)
            self.checker.registered(s, path, self._sid(self.clients[s]))
        for lo in range(0, self.sessions, ARM_LANES):
            await asyncio.gather(*[register(s) for s in range(
                lo, min(self.sessions, lo + ARM_LANES))])
        # a watcher on a follower must see every registration in its
        # first list: sync, then subscribe
        await asyncio.gather(*[c.sync(self.parent) for c in self.clients])
        t2 = time.perf_counter()
        for lo in range(0, self.sessions, ARM_LANES):
            want = min(self.sessions, lo + ARM_LANES)
            for s in range(lo, want):
                self._arm(s, self.clients[s])
            deadline = time.monotonic() + 120
            while self.first_views < want:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        'rolling_restart: %d of %d watchers armed after '
                        '120 s' % (self.first_views, want))
                await asyncio.sleep(0.005)
        t3 = time.perf_counter()
        # one leave-and-return: proves every subscription and takes a
        # full herd through the tick twice.  A node that is not shown it
        # is the check's to report (``missed-change``), not set-up's to
        # wait for
        node = self.order[-1]
        for kind in ('leave', 'return'):
            await self._change(node, kind, time.perf_counter(), False)
            if self.broken:
                raise RuntimeError('rolling_restart: the first %s '
                                   'failed: %r' % (kind, self.errors))
            if not await self._herd(self.checker.changes, node):
                print('# rolling_restart: not every node was shown the '
                      'first %s in %g s; not waiting for the rest'
                      % (kind, SETTLE_S), flush=True)
        print('# rolling_restart connects %.2fs registrations %.2fs '
              '(%d) first lists %.2fs first leave-and-return %.2fs'
              % (t1 - t0, t2 - t1, self.sessions, t3 - t2,
                 time.perf_counter() - t3), flush=True)
        self.live = True

    def _arm(self, node: int, c) -> None:
        """Node ``node`` watches the directory through the client's own
        watcher.  The benchmark's one hook: the watcher's ``notify``
        (where the session hands it the watch's notification) is
        stamped on its way through — when a change first reached any
        node, and that this node was told — and nothing of it is
        changed."""
        w = c.watcher(self.parent)
        notify = w.notify
        first = [True]

        def told(evt):
            ch = self.herd_open
            if ch is not None and ch['told'] is None:
                ch['told'] = time.perf_counter()
            self.checker.notified(node)
            notify(evt)
        w.notify = told
        self.checker.armed(node)

        def shown(children, stat, *_a):
            self.views += self.recording
            if first[0]:
                first[0] = False
                self.first_views += 1
            self.checker.emitted(node, time.perf_counter(), children,
                                 stat.cversion, self.sent)
        w.on('childrenChanged', shown)

    def _error(self, kind: str, exc: BaseException) -> None:
        name = '%s:%s' % (kind, getattr(exc, 'code', None)
                          or type(exc).__name__)
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
            node = self.order[(j // 2) % self.sessions]
            t = asyncio.ensure_future(self._change(
                node, 'return' if j & 1 else 'leave', due, self.recording))
            self.tasks.add(t)
            t.add_done_callback(self.tasks.discard)
            j += 1

    def _change_lost(self, node: int, kind: str, due: float,
                     rec: bool) -> None:
        """A change that reached no one: it failed, or it was not sent
        because an earlier one has an unknown outcome.  It was due all
        the same: it counts, and weighs as the deadline."""
        self.changes.append({'k': None, 'node': node, 'kind': kind,
                             'due': due, 'acked': None, 'recorded': rec,
                             'told': None})
        if rec:
            self.failed_changes += 1
            self.samples['change'].append(float(self.deadline_ms))

    async def _leave(self, node: int) -> None:
        c = self.clients[node]
        self.leaving.add(node)
        await asyncio.wait_for(c.close(), self.deadline_ms / 1e3)
        if c in self.fleet.clients:
            self.fleet.clients.remove(c)
        self.clients[node] = None

    async def _return(self, node: int, rec: bool) -> str:
        t0 = time.perf_counter()
        c = self._attach(node)
        self.leaving.discard(node)
        await c.wait_connected(timeout=self.deadline_ms / 1e3)
        if rec:
            self.samples['connect'].append(
                (time.perf_counter() - t0) * 1e3)
        return await c.create(self.checker.path(node), b'',
                              flags=self.flag, deadline=self.deadline_ms)

    async def _change(self, node: int, kind: str, due: float,
                      rec: bool) -> None:
        """Node ``node`` leaves, or returns; the directory's changes
        are serial."""
        async with self.lock:
            begun = time.perf_counter()
            if rec:
                self.late_ms.append((begun - due) * 1e3)
            if self.broken:
                self._change_lost(node, kind, due, rec)
                return
            ch = {'k': None, 'node': node, 'kind': kind, 'due': due,
                  'acked': None, 'recorded': rec, 'told': None}
            self.sent += 1
            self.herd_open = ch
            try:
                if kind == 'leave':
                    await self._leave(node)
                    k = self.checker.left(node)
                else:
                    path = await self._return(node, rec)
                    k = self.checker.returned(
                        node, path, self._sid(self.clients[node]))
            except asyncio.CancelledError:
                self.checker.unknown(node)
                raise
            except Exception as e:
                self._error(kind, e)
                self.checker.unknown(node)
                self.broken = True
                self._change_lost(node, kind, due, rec)
                return
            acked = time.perf_counter()
            self.checker.settle()
            ch['k'], ch['acked'] = k, acked
            self.changes.append(ch)
            if rec:
                ms = (acked - begun) * 1e3
                self.samples['change'].append(ms)
                self.by_member.setdefault(self._member(node),
                                          []).append(ms)
            if self.recording:
                self.acked += 1
            if kind == 'return':
                # the node watches before the next change is sent
                views = len(self.checker.views[node])
                self._arm(node, self.clients[node])
                deadline = time.monotonic() + SETTLE_S
                while (len(self.checker.views[node]) == views
                       and time.monotonic() < deadline
                       and not self.stopping):
                    await asyncio.sleep(0.002)

    def _settled(self) -> bool:
        if not self.changes or self.changes[-1]['acked'] is None:
            return True
        ch = self.changes[-1]
        newest = self.checker.newest
        return all(newest[n] >= ch['k']
                   for n in range(self.sessions) if n != ch['node'])

    async def drain(self, timeout: float) -> int:
        """Wait, bounded, for the changes in flight and for every node
        to have been shown the last one."""
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
        # a change is converged at a node by the first list its
        # listener was handed that shows it (or a later change); one
        # that failed, or was not sent, reached no one: every pair of
        # it counts, as failed and as the deadline
        for ch in self.changes:
            if not ch['recorded']:
                continue
            times = [None if ch['acked'] is None
                     else chk.seen_at(n, ch['k'])
                     for n in range(self.sessions) if n != ch['node']]
            for t in times:
                self.expected += 1
                self.never += t is None
                self.samples['converge'].append(
                    float(self.deadline_ms) if t is None
                    else (t - ch['due']) * 1e3)
            if ch['told'] is not None and None not in times:
                self.samples['herd'].append(
                    (max(times) - ch['told']) * 1e3)
        # the tree after the window, after sync: the directory from
        # every member, every ephemeral's owner from another member
        # than took its create
        n = self.members
        readers = [self.fleet.new_client(m, through_ingest=False)
                   for m in range(n)]
        await asyncio.gather(*[r.wait_connected(timeout=60)
                               for r in readers])
        await asyncio.gather(*[r.sync(self.parent) for r in readers])
        for m, r in enumerate(readers):
            try:
                names, _stat = await r.list(self.parent)
            except Exception:
                self.readback_failures += 1
                continue
            chk.final(names, 'member %d' % (m,))
        lanes = asyncio.Semaphore(64)

        async def one(node):
            m = (self._member(node) + 1) % n
            async with lanes:
                try:
                    st = await readers[m].stat(chk.path(node))
                    owner = st.ephemeralOwner
                except Exception as e:
                    if getattr(e, 'code', None) != 'NO_NODE':
                        self.readback_failures += 1
                        return
                    owner = None
            chk.final_owner(node, owner, 'member %d' % (m,))
        await asyncio.gather(*[one(s) for s in range(self.sessions)])

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
                    for k in reference_live.KINDS + ('readback-failed',)]
        compared.append('observations_checked %d' % (self.checker.checked,))
        recorded = [c for c in self.changes if c['recorded']]
        return {
            'attempted': len(recorded) + self.expected,
            'failed': self.failed_changes + self.never,
            'acked': self.views, 'samples': self.samples,
            'deadline_ms': self.deadline_ms,
            'late_ms': self.late_ms,
            'samples_by_member': self.by_member,
            'counters': {'errors': self.errors,
                         'changes_acked': self.acked,
                         'changes_recorded': len(recorded),
                         'changes_failed': self.failed_changes,
                         'pairs_never_converged': self.never,
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
