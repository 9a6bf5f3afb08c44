"""``configset_load``: a closed loop of whole-file reads over a tree of
znodes of very different sizes — the cores of a SolrCloud cluster
loading their collection's configset out of ZooKeeper.

The deployment (``configs/<config>.json``): ``tree`` = ``root``,
``configsets`` and ``files`` (``[name, bytes]`` a file, the same list
in every configset; ``size_divisor`` scales every size down for the
rehearsal).  Session s belongs to configset ``s % configsets``; when it
connects it lists its configset's directories once (the names are
checked), then it reads the configset's files one after the other in
an order of its own, and begins again: a core load after a core load.

Parameters (``traffic/<mix>.json``): ``outstanding`` (reads a session
keeps in flight; each lane walks the whole configset), ``op_deadline_ms``.

The tree is written once, by the set-up, through a plain session, in
MULTI batches bounded in bytes; the cell writes nothing in its window,
so the reference keeps each znode's expected bytes once and the timed
loop's check is a comparison.  ``load()`` also warms the size classes
of the fleet ingest that the deployment's replies reach and the
harness (which warms the narrowest) does not.
"""

from __future__ import annotations

import asyncio
import os
import random
import time

import reference_sized

LOAD_BYTES = 2 << 20    # payload bytes per MULTI while loading the tree
LOAD_OPS = 64
LOAD_LANES = 4
#: a reply frame over its znode's data: length prefix, reply header,
#: the data's own length, the Stat
REPLY_OVERHEAD = 4 + 16 + 4 + 68
#: the fleet ingest's counters of what its device ticks moved, kept by
#: the engine over the window (the harness's own list is older)
INGEST_MOVED = ('dispatches', 'bytes_batched', 'bytes_dispatched',
                'bytes_recopied', 'slots_deferred', 'ticks')


class Engine:
    def __init__(self, fleet):
        self.fleet = fleet
        cfg, p = fleet.config, fleet.params
        tree = cfg['tree']
        self.sessions = int(cfg['sessions'])
        self.root = tree['root']
        self.configsets = int(tree['configsets'])
        div = int(tree.get('size_divisor', 1))
        names = [name for name, _n in tree['files']]
        sizes = [max(1, int(n) // div) for _name, n in tree['files']]
        self.per_set = len(names)
        self.dirs = ['%s/cs%d' % (self.root, k)
                     for k in range(self.configsets)]
        #: directories under a configset that hold files ('' = itself)
        self.subdirs = sorted({name.rsplit('/', 1)[0] if '/' in name
                               else '' for name in names})
        self.paths = ['%s/%s' % (d, name) for d in self.dirs
                      for name in names]
        self.sizes = sizes * self.configsets
        self.lanes = int(p.get('outstanding', 1))
        self.deadline_ms = fleet.deadline_ms
        self.checker = reference_sized.SizedChecker(
            fleet.seed, self.paths, self.sizes)
        self.clients: list = []
        self.tasks: list = []
        self.live = False
        self.recording = False
        self.stopping = False
        self.attempted = 0
        self.failed = 0
        self.acked = 0
        self.bytes_read = 0         # inside the window
        self.samples: dict = {'read': []}
        self.by_member: dict[int, list] = {}
        self.errors: dict[str, int] = {}
        self.in_flight = 0
        self.readback_failures = 0
        self.moved_open: dict = {}
        self.moved: dict = {}

    # -- set-up ---------------------------------------------------------

    async def _warm_classes(self) -> None:
        """The size classes wider than the narrowest (which the
        harness warms) up to the one that holds the largest reply, at
        every row count a fleet of this size can give a dispatch: what
        is compiled here is not compiled in the window."""
        ingest = self.fleet.ingest
        # a slot holds a reply a lane (and now and then a ping's)
        widest = (max(self.sizes) + REPLY_OVERHEAD) * self.lanes + 64
        t0 = time.perf_counter()

        def work():
            width = 2 * ingest.min_len
            while width < 2 * widest:
                rows = 1
                while rows < 2 * self.sessions:
                    asyncio.run(ingest.prewarm(rows, width))
                    rows *= 2
                width *= 2
        await asyncio.get_running_loop().run_in_executor(None, work)
        print('# configset_load warmed the classes up to %d B: %d buckets '
              'in %.2fs' % (widest, len(ingest.buckets),
                            time.perf_counter() - t0), flush=True)

    async def load(self) -> None:
        """The tree, through one plain session on member 0, in MULTI
        batches bounded in bytes (64 creates of 960 KiB are over the
        frame cap); the ingest's wide classes compile meanwhile."""
        warm = asyncio.ensure_future(self._warm_classes())
        c = self.fleet.new_client(0, through_ingest=False)
        await c.wait_connected(timeout=60)
        t0 = time.perf_counter()
        await c.create(self.root, b'')
        tx = c.transaction()
        for d in self.dirs:
            tx.create(d, b'')
        for d in self.dirs:
            for sub in self.subdirs:
                if sub:
                    tx.create('%s/%s' % (d, sub), b'')
        await tx.commit()
        batches, batch, held = [], [], 0
        for idx, n in enumerate(self.sizes):
            if batch and (held + n > LOAD_BYTES or len(batch) >= LOAD_OPS):
                batches.append(batch)
                batch, held = [], 0
            batch.append(idx)
            held += n
        batches.append(batch)
        lanes = asyncio.Semaphore(LOAD_LANES)

        async def one(batch):
            async with lanes:
                tx = c.transaction()
                for idx in batch:
                    tx.create(self.paths[idx], self.checker.expected[idx])
                await tx.commit()
        await asyncio.gather(*[one(b) for b in batches])
        self.fleet.clients.remove(c)
        await c.close()
        print('# configset_load tree: %d znodes, %d bytes in %d batches, '
              '%.2fs' % (len(self.paths), sum(self.sizes), len(batches),
                         time.perf_counter() - t0), flush=True)
        await warm

    async def connect(self) -> None:
        n = len(self.fleet.addrs)
        self.clients = [self.fleet.new_client(s % n)
                        for s in range(self.sessions)]
        await asyncio.gather(*[c.wait_connected(timeout=120)
                               for c in self.clients])
        # a session attached to a follower must not start against half
        # a tree: every member serves the last znode loaded
        for m in range(n):
            await self.clients[m].sync(self.root)
            await self.clients[m].stat(self.paths[-1])

        async def look(s, c):
            """What a core does first: list its configset."""
            c.on('disconnect', lambda: self._gap(s))
            d = self.dirs[s % self.configsets]
            for sub in self.subdirs:
                path = '%s/%s' % (d, sub) if sub else d
                names, _stat = await c.list(path)
                self.checker.listing(s, path, names)
        await asyncio.gather(*[look(s, c)
                               for s, c in enumerate(self.clients)])
        self.live = True

    def _gap(self, s: int) -> None:
        if self.live:
            self.checker.gap(s, 'a disconnect')

    # -- traffic --------------------------------------------------------

    def _moved(self) -> dict:
        ingest = self.fleet.ingest
        out = {k: int(getattr(ingest, k)) for k in INGEST_MOVED
               if hasattr(ingest, k)}
        # where the ticks' time went (the ingest's always-on phase
        # sums, ms) and what the process and its loop thread had of the
        # CPU: a run that was slow says which of them was
        hist = getattr(ingest, 'phase_hist', None)
        if hist is not None:
            for phase in ('batch', 'dispatch', 'readback', 'route'):
                out[phase + '_ms'] = round(hist.sum({'phase': phase}))
        t = os.times()
        out.update(loop_cpu_ms=round(time.thread_time() * 1e3),
                   user_ms=round(t.user * 1e3), sys_ms=round(t.system * 1e3))
        return out

    def start(self) -> None:
        self.tasks = [asyncio.ensure_future(self._lane(s, lane))
                      for s in range(self.sessions)
                      for lane in range(self.lanes)]

    def open_window(self, t: float) -> None:
        self.recording = True
        self.moved_open = self._moved()

    def close_window(self, t: float) -> None:
        self.recording = False
        self.stopping = True
        self.moved = {k: v - self.moved_open.get(k, 0)
                      for k, v in self._moved().items()}

    def _fail(self, rec: bool, exc: BaseException) -> None:
        name = getattr(exc, 'code', None) or type(exc).__name__
        self.errors[name] = self.errors.get(name, 0) + 1
        if rec:
            self.failed += 1
            self.samples['read'].append(float(self.deadline_ms))

    async def _lane(self, s: int, lane: int) -> None:
        c = self.clients[s]
        rng = random.Random('%d/load/%d/%d' % (self.fleet.seed, s, lane))
        mine_ms = self.by_member.setdefault(s % len(self.fleet.addrs), [])
        base = (s % self.configsets) * self.per_set
        order = list(range(base, base + self.per_set))
        paths, chk, deadline = self.paths, self.checker, self.deadline_ms
        while not self.stopping:
            rng.shuffle(order)          # one core load
            for idx in order:
                if self.stopping:
                    return
                rec = self.recording
                if rec:
                    self.attempted += 1
                    self.in_flight += 1
                t0 = time.perf_counter()
                try:
                    got, stat = await c.get(paths[idx], deadline=deadline)
                except asyncio.CancelledError:
                    raise
                except Exception as e:
                    if rec:
                        self.in_flight -= 1
                    self._fail(rec, e)
                    await asyncio.sleep(0.05)
                    continue
                t1 = time.perf_counter()
                if rec:
                    self.in_flight -= 1
                    self.samples['read'].append((t1 - t0) * 1e3)
                    mine_ms.append((t1 - t0) * 1e3)
                if self.recording:
                    self.acked += 1
                    self.bytes_read += len(got)
                chk.read(s, idx, got, stat.dataLength, stat.version,
                         stat.mzxid)

    async def drain(self, timeout: float) -> int:
        """Wait, bounded, for the requests in flight when the window
        closed; what is still out then is ``failed``."""
        if self.tasks:
            _done, pending = await asyncio.wait(self.tasks,
                                                timeout=timeout)
            for t in pending:
                t.cancel()
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
        for t in self.tasks:
            if t.done() and not t.cancelled() and t.exception():
                self._fail(False, t.exception())
        self.tasks = []
        self.live = False
        out = self.in_flight
        self.failed += out
        self.in_flight = 0
        return out

    # -- the checks after the window ------------------------------------

    async def validate(self) -> None:
        """``sync``, then every znode read back through plain sessions
        on the members that did NOT take its create (member 0 took
        them all), and held to the model."""
        n = len(self.fleet.addrs)
        others = list(range(1, n)) or [0]
        readers = {m: self.fleet.new_client(m, through_ingest=False)
                   for m in others}
        await asyncio.gather(*[r.wait_connected(timeout=60)
                               for r in readers.values()])
        await asyncio.gather(*[r.sync(self.root)
                               for r in readers.values()])
        lanes = asyncio.Semaphore(16)

        async def one(idx):
            m = others[idx % len(others)]
            async with lanes:
                try:
                    data, stat = await readers[m].get(self.paths[idx])
                except Exception as e:
                    if getattr(e, 'code', None) == 'NO_NODE':
                        self.checker.final(idx, None, 0, 0,
                                           'member %d' % (m,))
                    else:
                        self.readback_failures += 1
                    return
            self.checker.final(idx, data, stat.dataLength, stat.version,
                               'member %d' % (m,))
        await asyncio.gather(*[one(i) for i in range(len(self.paths))])

    def result(self) -> dict:
        bad = self.checker.bad
        kinds = dict(bad.by_kind)
        if self.readback_failures:
            kinds['readback-failed'] = self.readback_failures
        first = list(bad.first)
        if self.readback_failures:
            first.append('readback-failed: %d znodes could not be read '
                         'back' % (self.readback_failures,))
        compared = ['%s %d limit 0' % (k, kinds.get(k, 0)) for k in (
            'payload', 'data-length', 'version', 'stale-read', 'listing',
            'lost-znode', 'evicted', 'readback-failed')]
        compared.append('observations_checked %d' % (self.checker.checked,))
        return {
            'attempted': self.attempted, 'failed': self.failed,
            'acked': self.acked, 'samples': self.samples,
            'deadline_ms': self.deadline_ms,
            'samples_by_member': self.by_member,
            'counters': {'errors': self.errors,
                         'bytes_read': self.bytes_read,
                         'configset_loads': self.acked / self.per_set,
                         'ingest': self.moved},
            'compared': compared, 'violations': first,
            'violation_kinds': kinds, 'checked': self.checker.checked,
        }

    async def stop(self) -> None:
        self.stopping = True
        for t in self.tasks:
            t.cancel()
        if self.tasks:
            await asyncio.gather(*self.tasks, return_exceptions=True)
        self.tasks = []
