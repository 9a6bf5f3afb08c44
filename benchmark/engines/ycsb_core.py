"""``ycsb_core``: a YCSB core workload through YCSB's ZooKeeper binding
(``site.ycsb.db.zookeeper.ZKClient``), closed loop or paced by
YCSB's ``-target``.

The binding's operations, as this engine sends them:

- ``read``   = ``getData(path)``, all fields;
- ``update`` = the binding's read-modify-write: ``getData(path)``,
  replace the written field in the record it returned,
  ``setData(path, whole record, version=-1)``;
- ``insert`` = ``create`` (the load phase; here MULTIs of creates — it
  is set-up, not the cell, and plain creates load 770 a second through
  a session's one write at a time — held to ``LOAD_DEADLINE_S``);
  ``scan`` the binding does not implement.

Parameters (``traffic/<mix>.json``, YCSB's own property names):

- ``readproportion`` / ``updateproportion``: an operation's kind;
- ``requestdistribution``: ``zipfian`` — every operation of every
  session draws its key as ``CoreWorkload`` does, from YCSB's
  ``ScrambledZipfianGenerator`` (:class:`ScrambledZipfian`), so a hot
  record has many concurrent readers AND writers;
- ``writeallfields`` false: an update rewrites ONE field;
- ``target_ops_per_s`` (YCSB's ``-target``; absent: closed loop): the
  fleet's operations a second, each session pacing its own at
  ``target / sessions`` — operation k of a session is DUE at its first
  due time + k intervals, the first a seeded fraction of an interval
  after the start so the fleet's arrivals are even; a session sleeps
  until its next one is due and, behind its schedule (a stall), sends
  at once until it has caught up, as a YCSB thread does.  A paced
  operation is timed from when it was DUE, which counts the wait a
  stall imposes on the operations behind it, and how late the
  generator sent is kept beside it (``gen_late_ms_p95``);
- ``op_deadline_ms``: a request that has no reply by then is
  ``failed``.

The deployment (``configs/<config>.json``): ``sessions`` sessions (one
YCSB thread each), round-robin
over the members; ``tree`` = ``parent`` (the binding's chroot, written
out), ``recordcount``, ``fieldcount``, ``fieldlength``.  One operation
in flight a session in either loop.

``result['acked']`` counts YCSB operations acknowledged in the window
(a read 1; an update 1, when its ``setData`` is acknowledged);
``result['samples']['read']`` holds every ``getData`` sent in the
window, an update's too; ``['rmw']`` an update's ``getData`` sent ->
``setData`` acknowledged.  Every seed draws from the same
distributions, so every seed does the same work in another order.
"""

from __future__ import annotations

import asyncio
import random
import time

import reference_ycsb
import stats

LOAD_BATCH = 128        # creates per MULTI while loading the records
LOAD_LANES = 8
#: What the load phase may take of a run.  The harness has no limit on
#: set-up of its own (a ``benchmark`` PR's to add: PERF.md section 7),
#: and a run is given ``run_seconds`` + 60 s: 20 s of them go to JAX,
#: the members and the connects, so a load not done after 40 s has left
#: its window no time, and the engine exits the run non-zero there
#: instead of being killed at the run's limit.  This program loads
#: 65,536 records in 11-12 s on the chip machine.
LOAD_DEADLINE_S = 40.0
HOT_RANKS = 400         # upstream's reply cache holds this many paths


def fnvhash64(val: int) -> int:
    """YCSB's ``Utils.fnvhash64``: FNV-1 over the value's 8 octets, low
    one first, in a signed 64-bit ``long``, then ``Math.abs``."""
    h = 0xCBF29CE484222325
    for octet in val.to_bytes(8, 'little'):
        h = ((h ^ octet) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    return h if h < 1 << 63 else (1 << 64) - h


class ScrambledZipfian:
    """YCSB's ``ScrambledZipfianGenerator``, which ``CoreWorkload``
    builds for ``requestdistribution=zipfian``: a rank is drawn from
    ``ZipfianGenerator(0, ITEM_COUNT, theta, ZETAN)`` — a zeta over
    10 billion items whatever the record count, sampled by Gray et
    al.'s closed form with the precomputed ``zetan`` — and lands on
    key ``fnvhash64(rank) % n``.  So the hottest key takes
    1 / ZETAN = 3.8% of the draws at any ``n``, and the ranks past
    the first few thousand spread evenly over the keys."""

    ITEM_COUNT = 10_000_000_000
    ZETAN = 26.46902820178302       # zeta(ITEM_COUNT, 0.99)
    USED_ZIPFIAN_CONSTANT = 0.99

    def __init__(self, n: int, theta: float):
        if theta != self.USED_ZIPFIAN_CONSTANT:
            raise ValueError('ZETAN is zeta(10**10, 0.99); YCSB sums '
                             'that of another constant for minutes')
        self.n = n
        self.theta = theta
        self.items = self.ITEM_COUNT + 1    # max - min + 1, as YCSB
        self.alpha = 1.0 / (1.0 - theta)
        self.half_pow = 0.5 ** theta
        zeta2 = 1.0 + self.half_pow
        self.eta = ((1.0 - (2.0 / self.items) ** (1.0 - theta))
                    / (1.0 - zeta2 / self.ZETAN))

    def rank(self, u: float) -> int:
        """The rank a uniform draw ``u`` in [0, 1) falls on
        (``ZipfianGenerator.nextLong``)."""
        uz = u * self.ZETAN
        if uz < 1.0:
            return 0
        if uz < 1.0 + self.half_pow:
            return 1
        return int(self.items * (self.eta * u - self.eta + 1.0)
                   ** self.alpha)

    def key(self, u: float) -> int:
        """The key index that draw lands on."""
        return fnvhash64(self.rank(u)) % self.n

    def share(self, top: int) -> float:
        """The exact zeta's share of draws on the ``top`` hottest
        ranks."""
        return sum((i + 1) ** -self.theta for i in range(top)) / self.ZETAN


class Engine:
    def __init__(self, fleet):
        self.fleet = fleet
        cfg, p = fleet.config, fleet.params
        tree = cfg['tree']
        self.sessions = int(cfg['sessions'])
        self.parent = tree['parent']
        self.n = int(tree['recordcount'])
        self.fieldcount = int(tree['fieldcount'])
        self.read_p = float(p['readproportion'])
        if p.get('requestdistribution') != 'zipfian' or abs(
                self.read_p + float(p['updateproportion']) - 1.0) > 1e-9 \
                or p.get('writeallfields', False):
            raise ValueError('ycsb_core: reads and one-field updates '
                             'over zipfian keys are what it sends, not '
                             '%r' % ({k: v for k, v in p.items()
                                      if k != 'toy'},))
        self.zipf = ScrambledZipfian(self.n, float(p['zipfian_constant']))
        #: seconds between one session's operations (0: closed loop)
        target = float(p.get('target_ops_per_s') or 0)
        self.interval = self.sessions / target if target else 0.0
        #: the keys the HOT_RANKS hottest ranks land on
        self.hot = frozenset(fnvhash64(r) % self.n
                             for r in range(HOT_RANKS))
        self.deadline_ms = fleet.deadline_ms
        self.checker = reference_ycsb.YcsbChecker(
            fleet.seed, self.n, self.fieldcount, int(tree['fieldlength']),
            self.parent)
        self.paths = self.checker.paths
        self.clients: list = []
        self.tasks: list = []
        self.live = False
        self.recording = False
        self.stopping = False
        self.attempted = 0
        self.failed = 0
        self.acked = 0
        self.updates_acked = 0  # inside the window
        self.hot_ops = 0        # operations sent in the window on the
        #                         HOT_RANKS hottest ranks
        self.samples: dict[str, list] = {'read': [], 'rmw': []}
        self.late_ms: list = []     # paced: due -> sent, in the window
        #: a read's latency by the member the session is attached to
        self.by_member: dict[int, list] = {}
        self.errors: dict[str, int] = {}
        self.in_flight = 0      # recorded ops without an outcome yet
        self.readback_failures = 0
        self.keys_touched = 0

    # -- set-up ---------------------------------------------------------

    async def load(self) -> None:
        """YCSB's load phase: every record, through one plain session
        on member 0 (no ingest: the tick programs are compiling
        meanwhile)."""
        c = self.fleet.new_client(0, through_ingest=False)
        await c.wait_connected(timeout=60)
        t0 = time.perf_counter()
        await c.create(self.parent, b'')
        lanes = asyncio.Semaphore(LOAD_LANES)
        initial = self.checker.initial

        async def batch(lo):
            async with lanes:
                tx = c.transaction()
                for key in range(lo, min(self.n, lo + LOAD_BATCH)):
                    tx.create(self.paths[key], initial(key))
                await tx.commit()
        try:
            await asyncio.wait_for(
                asyncio.gather(*[batch(lo) for lo in
                                 range(0, self.n, LOAD_BATCH)]),
                LOAD_DEADLINE_S)
        except asyncio.TimeoutError:
            raise RuntimeError(
                'ycsb_core: the load phase has not finished in %.0f s '
                '(%d records under %s): no time is left for the window'
                % (LOAD_DEADLINE_S, self.n, self.parent)) from None
        self.fleet.clients.remove(c)
        await c.close()
        print('# ycsb_core loaded %d records of %d B (%.1f MiB) in %.2fs'
              % (self.n, self.checker.records.record_bytes,
                 self.n * self.checker.records.record_bytes / 2.0 ** 20,
                 time.perf_counter() - t0), flush=True)

    async def connect(self) -> None:
        n = len(self.fleet.addrs)
        self.clients = [self.fleet.new_client(s % n)
                        for s in range(self.sessions)]
        await asyncio.gather(*[c.wait_connected(timeout=120)
                               for c in self.clients])
        for s, c in enumerate(self.clients):
            for evt in ('disconnect', 'expire'):
                c.on(evt, lambda s=s, evt=evt: self._gap(s, evt))
        # a session attached to a follower must not start against half
        # a tree: every member serves the last record loaded
        for m in range(n):
            await self.clients[m].sync(self.parent)
            await self.clients[m].stat(self.paths[-1])
        self.live = True

    def _gap(self, s: int, what: str) -> None:
        if self.live:
            self.checker.gap(s, what)

    # -- traffic --------------------------------------------------------

    def start(self) -> None:
        self.tasks = [asyncio.ensure_future(self._thread(s))
                      for s in range(self.sessions)]

    def open_window(self, t: float) -> None:
        self.recording = True

    def close_window(self, t: float) -> None:
        self.recording = False
        self.stopping = True

    def _fail(self, rec: bool, exc: BaseException) -> None:
        name = getattr(exc, 'code', None) or type(exc).__name__
        self.errors[name] = self.errors.get(name, 0) + 1
        if rec:
            self.failed += 1
            self.in_flight -= 1

    async def _thread(self, s: int) -> None:
        """One YCSB client thread: one operation after another."""
        c = self.clients[s]
        member = s % len(self.fleet.addrs)
        rng = random.Random('%d/ycsb/%d' % (self.fleet.seed, s))
        uniform = rng.random
        mine_ms = self.by_member.setdefault(member, [])
        reads, rmws = self.samples['read'], self.samples['rmw']
        chk, paths, hot = self.checker, self.paths, self.hot
        draw = self.zipf.key
        read_p, fields = self.read_p, self.fieldcount
        deadline = self.deadline_ms
        clock = time.perf_counter
        interval = self.interval
        due = clock() + uniform() * interval
        while not self.stopping:
            update = uniform() >= read_p
            key = draw(uniform())
            path = paths[key]
            if interval:
                wait = due - clock()
                if wait > 0:
                    await asyncio.sleep(wait)
                    if self.stopping:
                        return
            rec = self.recording
            if rec:
                self.attempted += 1
                self.in_flight += 1
                if key in hot:
                    self.hot_ops += 1
            t0 = clock()
            if interval:
                if rec:
                    self.late_ms.append((t0 - due) * 1e3)
                t0 = due
                due += interval
            try:
                data, stat = await c.get(path, deadline=deadline)
            except Exception as e:
                if rec:
                    reads.append(float(deadline))
                self._fail(rec, e)
                await asyncio.sleep(0.05)
                continue
            t1 = clock()
            if rec:
                reads.append((t1 - t0) * 1e3)
                mine_ms.append((t1 - t0) * 1e3)
            chk.read(s, key, data, stat.version, stat.mzxid,
                     stat.dataLength)
            if update:
                new = chk.rewrite(key, data, int(uniform() * fields))
                try:
                    stat = await c.set(path, new, deadline=deadline)
                except asyncio.CancelledError:
                    chk.write_unknown(key)  # cut by the drain
                    raise
                except Exception as e:
                    chk.write_unknown(key)
                    self._fail(rec, e)
                    await asyncio.sleep(0.05)
                    continue
                if rec:
                    rmws.append((clock() - t0) * 1e3)
                chk.write_acked(s, member, key, stat.version, stat.mzxid,
                                new)
            if rec:
                self.in_flight -= 1
            if self.recording:
                self.acked += 1
                self.updates_acked += update

    async def drain(self, timeout: float) -> int:
        """Wait, bounded, for the operations in flight when the window
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
        out = self.in_flight
        self.failed += out
        self.in_flight = 0
        return out

    # -- the checks after the window ------------------------------------

    async def validate(self) -> None:
        """Every write has its outcome now: settle what was read ahead
        of an acknowledgement, ``sync``, then read ALL the records back
        through plain sessions — a written record from ANOTHER member
        than the one that took its newest write — and hold them to the
        model."""
        self.live = False
        chk = self.checker
        self.keys_touched = chk.touched     # the read-back touches all
        chk.settle()
        n = len(self.fleet.addrs)
        readers = [self.fleet.new_client(m, through_ingest=False)
                   for m in range(n)]
        await asyncio.gather(*[r.wait_connected(timeout=60)
                               for r in readers])
        await asyncio.gather(*[r.sync(self.parent) for r in readers])

        async def one(key):
            m = ((chk.newest_member[key] + 1) % n if chk.newest[key]
                 else key % n)
            where = 'member %d' % (m,)
            try:
                data, stat = await readers[m].get(self.paths[key])
            except Exception as e:
                if getattr(e, 'code', None) == 'NO_NODE':
                    chk.final(key, None, 0, 0, where)
                else:
                    self.readback_failures += 1
                return
            chk.final(key, data, stat.version, stat.dataLength, where)
        for lo in range(0, self.n, 2048):
            await asyncio.gather(*[one(k) for k in range(
                lo, min(self.n, lo + 2048))])

    def result(self) -> dict:
        bad = self.checker.bad
        kinds = dict(bad.by_kind)
        first = list(bad.first)
        if self.readback_failures:
            kinds['readback-failed'] = self.readback_failures
            first.append('readback-failed: %d records could not be read '
                         'back' % (self.readback_failures,))
        compared = ['%s %d limit 0' % (k, kinds.get(k, 0))
                    for k in reference_ycsb.KINDS + ('readback-failed',)]
        compared.append('observations_checked %d' % (self.checker.checked,))
        chk = self.checker
        return {
            'attempted': self.attempted, 'failed': self.failed,
            'acked': self.acked, 'samples': self.samples,
            'deadline_ms': self.deadline_ms,
            'samples_by_member': self.by_member,
            'counters': {
                'errors': self.errors,
                # what wal.roll_ms_per_change divides by
                'changes_acked': self.updates_acked,
                'hot_ops_share': round(
                    100.0 * self.hot_ops / max(1, self.attempted), 3),
                # those ranks' share of the zeta, and the even share
                # of the ranks behind them that land on the same keys
                'hot_ops_closed_form': round(100.0 * (
                    self.zipf.share(HOT_RANKS) + (
                        1.0 - self.zipf.share(HOT_RANKS))
                    * len(self.hot) / self.n), 3),
                'gen_late_ms_p95': (round(stats.percentile(
                    self.late_ms, 95), 3) if self.late_ms else None),
                'keys_touched': self.keys_touched,
                'keys_written': sum(1 for v in chk.acked if v),
                'writes_sent': sum(chk.sent),
                'writes_unknown': sum(chk.unknown),
                'most_versions': max(chk.acked)},
            'compared': compared, 'violations': first,
            'violation_kinds': kinds, 'checked': self.checker.checked,
        }

    async def stop(self) -> None:
        self.stopping = True
        self.live = False
        for t in self.tasks:
            t.cancel()
        if self.tasks:
            await asyncio.gather(*self.tasks, return_exceptions=True)
        self.tasks = []
