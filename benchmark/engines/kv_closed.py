"""``kv_closed``: a closed-loop key-value mix over a tree of fixed-size
znodes.

Parameters (``traffic/<mix>.json``):

- ``ops``: weights of ``get`` and ``set``;
- ``keys``: ``uniform`` (reads draw from every znode);
- ``outstanding``: requests each session keeps in flight (lanes);
- ``active_sessions``: how many of the fleet's sessions send (sessions
  0 .. n-1, so spread evenly over the members); the rest stay
  connected and idle, as most of a real fleet is at any moment;
- ``write_own``: a session writes only its own ``write_own`` znodes
  (session s owns znodes s*write_own ..), so each znode has one writer
  and its version counts that writer's acknowledged writes;
- ``op_deadline_ms``: an op that has no reply by then is ``failed``.

The deployment (``configs/<config>.json``): ``sessions`` sessions
spread round-robin over the members, ``tree`` = ``root``, ``parents``,
``children``, ``bytes``.  Every seed draws from the same distributions,
so every seed does the same work in another order.
"""

from __future__ import annotations

import asyncio
import random
import time

import reference

LOAD_BATCH = 64         # creates per MULTI while loading the tree
LOAD_LANES = 8


class Engine:
    def __init__(self, fleet):
        self.fleet = fleet
        cfg, p = fleet.config, fleet.params
        tree = cfg['tree']
        self.sessions = int(cfg['sessions'])
        self.size = int(tree['bytes'])
        self.paths = ['%s/p%02d/c%03d' % (tree['root'], i, j)
                      for i in range(int(tree['parents']))
                      for j in range(int(tree['children']))]
        self.parents = ['%s/p%02d' % (tree['root'], i)
                        for i in range(int(tree['parents']))]
        self.root = tree['root']
        self.weights = {k: float(v) for k, v in p['ops'].items() if v}
        unknown = set(self.weights) - {'get', 'set'}
        if unknown or p.get('keys', 'uniform') != 'uniform':
            raise ValueError('kv_closed: unknown ops %s or key '
                             'distribution %r' % (sorted(unknown),
                                                  p.get('keys')))
        self.lanes = int(p.get('outstanding', 1))
        self.active = min(self.sessions,
                          int(p.get('active_sessions', self.sessions)))
        self.own = int(p.get('write_own', 0))
        if 'set' in self.weights and (
                self.own < self.lanes
                or self.own * self.sessions > len(self.paths)):
            raise ValueError('kv_closed: write_own=%d does not give '
                             'every lane of %d sessions a znode of its '
                             'own among %d' % (self.own, self.sessions,
                                               len(self.paths)))
        self.deadline_ms = fleet.deadline_ms
        self.checker = reference.KvChecker(fleet.seed, self.paths,
                                           self.size)
        self.sent = [0] * len(self.paths)      # writes sent per znode
        self.dropped = set()    # znodes with a write of unknown outcome
        self.clients: list = []
        self.tasks: list = []
        self.recording = False
        self.stopping = False
        self.attempted = 0
        self.failed = 0
        self.acked = 0
        self.writes_acked = 0   # inside the window
        self.samples = {cls: [] for op, cls in (('get', 'read'),
                                                ('set', 'write'))
                        if op in self.weights}
        #: the same latencies by the member the session is attached to
        #: (an earlier line prints them: who waits, the leader's own
        #: sessions or the followers')
        self.by_member: dict[int, list] = {}
        self.errors: dict[str, int] = {}
        self.in_flight = 0      # recorded ops without an outcome yet
        self.readback_failures = 0

    # -- set-up ---------------------------------------------------------

    async def load(self) -> None:
        """The tree, through one plain session on member 0 (no ingest:
        the tick programs are compiling meanwhile)."""
        c = self.fleet.new_client(0, through_ingest=False)
        await c.wait_connected(timeout=60)
        await c.create(self.root, b'')
        tx = c.transaction()
        for p in self.parents:
            tx.create(p, b'')
        await tx.commit()
        lanes = asyncio.Semaphore(LOAD_LANES)

        async def batch(lo):
            async with lanes:
                tx = c.transaction()
                for idx in range(lo, min(len(self.paths), lo + LOAD_BATCH)):
                    tx.create(self.paths[idx], self.checker.initial(idx))
                await tx.commit()
        await asyncio.gather(*[batch(lo) for lo in
                               range(0, len(self.paths), LOAD_BATCH)])
        self.fleet.clients.remove(c)
        await c.close()

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

    # -- traffic --------------------------------------------------------

    def start(self) -> None:
        self.tasks = [asyncio.ensure_future(self._lane(s, lane))
                      for s in range(self.active)
                      for lane in range(self.lanes)]

    def open_window(self, t: float) -> None:
        self.recording = True

    def close_window(self, t: float) -> None:
        self.recording = False
        self.stopping = True

    def _fail(self, rec: bool, kind: str, exc: BaseException) -> None:
        name = getattr(exc, 'code', None) or type(exc).__name__
        self.errors[name] = self.errors.get(name, 0) + 1
        if rec:
            self.failed += 1
            self.samples[kind].append(float(self.deadline_ms))

    async def _lane(self, s: int, lane: int) -> None:
        c = self.clients[s]
        rng = random.Random('%d/kv/%d/%d' % (self.fleet.seed, s, lane))
        mine_ms = self.by_member.setdefault(s % len(self.fleet.addrs), [])
        kinds = sorted(self.weights)
        weights = [self.weights[k] for k in kinds]
        mine = [s * self.own + j for j in range(lane, self.own, self.lanes)]
        n = len(self.paths)
        chk = self.checker
        deadline = self.deadline_ms
        while not self.stopping:
            kind = (kinds[0] if len(kinds) == 1
                    else rng.choices(kinds, weights)[0])
            rec = self.recording
            if kind == 'set':
                live = [i for i in mine if i not in self.dropped]
                if not live:
                    return
                idx = rng.choice(live)
                data = chk.next_write(idx)
            else:
                idx = rng.randrange(n)
            if rec:
                self.attempted += 1
                self.in_flight += 1
            t0 = time.perf_counter()
            try:
                if kind == 'set':
                    self.sent[idx] += 1
                    stat = await c.set(self.paths[idx], data,
                                       deadline=deadline)
                else:
                    got, stat = await c.get(self.paths[idx],
                                            deadline=deadline)
            except asyncio.CancelledError:
                if kind == 'set':       # cut by the drain: unknown too
                    chk.write_unknown(idx)
                raise
            except Exception as e:
                if rec:
                    self.in_flight -= 1
                if kind == 'set':
                    # the outcome is unknown: the znode leaves the mix,
                    # the final read-back allows either version
                    chk.write_unknown(idx)
                    self.dropped.add(idx)
                self._fail(rec, 'write' if kind == 'set' else 'read', e)
                await asyncio.sleep(0.05)
                continue
            t1 = time.perf_counter()
            if rec:
                self.in_flight -= 1
                self.samples['write' if kind == 'set' else 'read'].append(
                    (t1 - t0) * 1e3)
                mine_ms.append((t1 - t0) * 1e3)
            if self.recording:
                self.acked += 1
                self.writes_acked += kind == 'set'
            if kind == 'set':
                chk.write_acked(s, idx, stat.version, stat.mzxid)
            else:
                chk.read(s, idx, got, stat.version, stat.mzxid,
                         sent_writes=self.sent[idx])

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
                self._fail(False, 'read', t.exception())
        self.tasks = []
        out = self.in_flight
        self.failed += out
        self.in_flight = 0
        return out

    # -- the checks after the window ------------------------------------

    async def validate(self) -> None:
        """``sync``, then read the whole tree back through plain
        sessions — a written znode from ANOTHER member than the one
        that took the write — and hold it to the model."""
        n = len(self.fleet.addrs)
        readers = [self.fleet.new_client(m, through_ingest=False)
                   for m in range(n)]
        await asyncio.gather(*[r.wait_connected(timeout=60)
                               for r in readers])
        await asyncio.gather(*[r.sync(self.root) for r in readers])

        async def one(idx):
            owner = idx // self.own if self.own else None
            if owner is not None and owner < self.sessions \
                    and self.sent[idx]:
                m = (owner % n + 1) % n
            else:
                m = idx % n
            try:
                data, stat = await readers[m].get(self.paths[idx])
            except Exception as e:
                if getattr(e, 'code', None) == 'NO_NODE':
                    self.checker.final(idx, None, 0, 'member %d' % (m,))
                else:
                    self.readback_failures += 1
                return
            self.checker.final(idx, data, stat.version, 'member %d' % (m,))
        for lo in range(0, len(self.paths), 1024):
            await asyncio.gather(*[one(i) for i in range(
                lo, min(len(self.paths), lo + 1024))])

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
            'payload', 'stale-read', 'future-read', 'write-version',
            'lost-write', 'lost-znode', 'readback-failed')]
        compared.append('observations_checked %d' % (self.checker.checked,))
        return {
            'attempted': self.attempted, 'failed': self.failed,
            'acked': self.acked, 'samples': self.samples,
            'deadline_ms': self.deadline_ms,
            'samples_by_member': self.by_member,
            'counters': {'errors': self.errors,
                         'writes_acked': self.writes_acked,
                         'znodes_dropped': len(self.dropped)},
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
