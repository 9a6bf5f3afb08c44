"""``ycsb_latest``: YCSB core workload D ("read latest") through YCSB's
ZooKeeper binding (``site.ycsb.db.zookeeper.ZKClient``), closed loop or
paced by YCSB's ``-target`` — ``ycsb_core``'s loader, session loop,
pacing and result shape, with D's operations and D's key chooser.

What ``CoreWorkload`` sends for D, as this engine sends it:

- an operation is a ``read`` with probability ``readproportion``, else
  an ``insert``;
- ``insert``: ``keynum`` = the next value of ONE counter all sessions
  share, starting at ``recordcount``
  (``AcknowledgedCounterGenerator``: :class:`AcknowledgedCounter`);
  ``create(paths[keynum], the whole record, OPEN_ACL_UNSAFE,
  PERSISTENT)``; on the acknowledgement ``acknowledge(keynum)``: the
  FRONTIER (``lastValue()``) is the highest keynum with every lower one
  acknowledged.  An insert that fails is ``failed``, is not retried
  (``core_workload_insertion_retry_limit=0``) and holds the frontier
  where it is, as in YCSB;
- ``read``: ``keynum = frontier - Z``, ``Z`` from ``ZipfianGenerator``
  (constant 0.99) over as many items as the frontier counts, its zeta
  extended as the frontier advances (``SkewedLatestGenerator``:
  :class:`LatestZipfian`), drawn again while ``keynum > frontier``; NOT
  hashed: rank 0 is the newest acknowledged record.
  ``getData(paths[keynum])``, all fields.  ``NO_NODE`` is an ANSWER
  where ZooKeeper allows it (the session's member has not applied the
  create yet; YCSB books a read that found nothing): timed and counted
  like any read, tallied in ``counters.reads_not_yet_visible``, and
  held to ``reference_ycsb_latest``'s rules.

Parameters (``traffic/<mix>.json``, YCSB's own property names):
``readproportion`` / ``insertproportion``, ``requestdistribution``
``latest``, ``zipfian_constant``, ``target_ops_per_s`` and
``op_deadline_ms`` as ``ycsb_core``.  The deployment
(``configs/<config>.json``): ``sessions``; ``tree`` = ``parent``,
``recordcount`` (loaded), ``insert_room`` (names made from ``--seed``
for the inserts a run can make), ``fieldcount``, ``fieldlength``.

``result['acked']`` counts reads answered plus inserts acknowledged in
the window; ``samples['read']`` every ``getData`` sent in it,
``samples['insert']`` every ``create`` (sent -> acknowledged).
"""

from __future__ import annotations

import asyncio
import importlib.util
import os
import random
import time

import reference_ycsb_latest
import stats

_spec = importlib.util.spec_from_file_location(
    'bench_engines_ycsb_core',
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 'ycsb_core.py'))
ycsb_core = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ycsb_core)

HOT_RANKS = ycsb_core.HOT_RANKS
READBACK_BEYOND = 256   # names past the counter the read-back asks for
#: a create's definite refusals (anything else — a deadline, a lost
#: connection — leaves its outcome unknown)
REFUSALS = frozenset(('NODE_EXISTS', 'THROTTLED', 'NO_NODE', 'NO_AUTH',
                      'INVALID_ACL', 'BAD_ARGUMENTS', 'EPOCH_FENCED',
                      'NO_CHILDREN_FOR_EPHEMERALS'))


def zeta_terms(lo: int, hi: int, theta: float) -> float:
    """Items ``lo + 1 .. hi`` of the zeta sum: what YCSB's
    ``zeta(st, n, theta, initialsum)`` adds to ``initialsum``."""
    return sum((i + 1) ** -theta for i in range(lo, hi))


class AcknowledgedCounter:
    """YCSB's ``AcknowledgedCounterGenerator``: ``next`` hands out
    consecutive keynums from ``start``; ``last`` (``lastValue()``) is
    the highest one such that it and every lower one is acknowledged —
    ``start - 1``, the newest loaded record, until then."""

    def __init__(self, start: int):
        self.counter = start
        self.last = start - 1
        self._acked: set[int] = set()

    def next(self) -> int:
        self.counter += 1
        return self.counter - 1

    def acknowledge(self, value: int) -> None:
        acked = self._acked
        acked.add(value)
        last = self.last
        while last + 1 in acked:
            last += 1
            acked.remove(last)
        self.last = last


class LatestZipfian:
    """YCSB's ``ZipfianGenerator`` as ``SkewedLatestGenerator`` drives
    it: built over ``items`` items, asked for a rank among ``itemcount``
    of them — the frontier's value at that draw — with ``zetan``
    extended by the new items' terms when ``itemcount`` has grown
    (``zeta(countforzeta, itemcount, theta, zetan)``) and ``eta``
    re-derived as YCSB does (from the ORIGINAL ``items``: YCSB's own
    slip, kept).  Gray et al.'s closed form: ranks 0 and 1 exact, the
    rest within a point of the exact zeta's shares."""

    def __init__(self, items: int, theta: float):
        self.items = items
        self.theta = theta
        self.alpha = 1.0 / (1.0 - theta)
        self.half_pow = 0.5 ** theta
        self.zeta2 = 1.0 + self.half_pow
        self.countforzeta = 0
        self.zetan = 0.0
        self.eta = 0.0
        self._extend(items)

    def _extend(self, itemcount: int) -> None:
        self.zetan += zeta_terms(self.countforzeta, itemcount, self.theta)
        self.countforzeta = itemcount
        self.eta = ((1.0 - (2.0 / self.items) ** (1.0 - self.theta))
                    / (1.0 - self.zeta2 / self.zetan))

    def rank(self, u: float, itemcount: int) -> int:
        """The rank a uniform draw ``u`` in [0, 1) falls on among
        ``itemcount`` items (``nextLong(itemcount)``)."""
        if itemcount > self.countforzeta:
            self._extend(itemcount)
        uz = u * self.zetan
        if uz < 1.0:
            return 0
        if uz < self.zeta2:
            return 1
        return int(itemcount * (self.eta * u - self.eta + 1.0)
                   ** self.alpha)


class Engine(ycsb_core.Engine):
    def __init__(self, fleet):     # not ycsb_core's: another mix
        self.fleet = fleet
        cfg, p = fleet.config, fleet.params
        tree = cfg['tree']
        self.sessions = int(cfg['sessions'])
        self.parent = tree['parent']
        self.n = int(tree['recordcount'])
        self.room = int(tree['insert_room'])
        self.fieldcount = int(tree['fieldcount'])
        self.read_p = float(p['readproportion'])
        if p.get('requestdistribution') != 'latest' or abs(
                self.read_p + float(p['insertproportion']) - 1.0) > 1e-9:
            raise ValueError('ycsb_latest: reads and inserts over the '
                             'latest keys are what it sends, not %r'
                             % ({k: v for k, v in p.items()
                                 if k != 'toy'},))
        self.theta = float(p['zipfian_constant'])
        target = float(p.get('target_ops_per_s') or 0)
        self.interval = self.sessions / target if target else 0.0
        self.deadline_ms = fleet.deadline_ms
        self.checker = reference_ycsb_latest.LatestChecker(
            fleet.seed, self.n, self.room, self.fieldcount,
            int(tree['fieldlength']), self.parent)
        self.all_paths = self.checker.paths
        #: what ``ycsb_core``'s load and connect walk: the loaded ones
        self.paths = self.all_paths[:self.n]
        self.counter = AcknowledgedCounter(self.n)
        # as SkewedLatestGenerator: over lastValue() items at the start
        self.zipf = LatestZipfian(self.counter.last, self.theta)
        #: the first keynum handed out inside the window: a record from
        #: there on did not exist when the window opened
        self.base = self.n
        #: zeta over the records the frontier counts from ``base`` on
        #: (the window share's closed form), extended with the frontier
        #: like ``zetan``; and over the HOT_RANKS newest
        self._zeta_new = 0.0
        self._zeta_new_n = 0
        self.clients: list = []
        self.tasks: list = []
        self.live = False
        self.recording = False
        self.stopping = False
        self.attempted = 0
        self.failed = 0
        self.acked = 0
        self.inserts_acked = 0      # inside the window
        self.frontier_open = self.counter.last
        self.frontier_close = self.counter.last
        self.reads = 0              # getData sent in the window
        self.reads_newest = 0       # ... on rank 0
        self.reads_hot = 0          # ... on the HOT_RANKS newest
        self.reads_new = 0          # ... on a record created in it
        self.expect_new = 0.0       # the zeta's share of those, summed
        self.expect_newest = 0.0    # 1 / zetan, summed: rank 0's share
        self.misses = 0             # NO_NODE answers in the window
        self.samples: dict[str, list] = {'read': [], 'insert': []}
        self.late_ms: list = []
        self.by_member: dict[int, list] = {}
        self.errors: dict[str, int] = {}
        self.in_flight = 0
        self.readback_failures = 0

    # -- traffic --------------------------------------------------------

    def open_window(self, t: float) -> None:
        self.frontier_open = self.counter.last
        self.base = self.counter.counter
        super().open_window(t)

    def close_window(self, t: float) -> None:
        self.frontier_close = self.counter.last
        super().close_window(t)

    def _expected(self, frontier: int) -> None:
        """One recorded read's closed forms, from the exact zeta over
        the ``frontier`` items it was drawn among."""
        new = frontier - self.base + 1
        if new > self._zeta_new_n:
            self._zeta_new += zeta_terms(self._zeta_new_n, new, self.theta)
            self._zeta_new_n = new
        zetan = self.zipf.zetan
        self.expect_new += self._zeta_new / zetan
        self.expect_newest += 1.0 / zetan

    async def _thread(self, s: int) -> None:
        """One YCSB client thread: one operation after another."""
        c = self.clients[s]
        member = s % len(self.fleet.addrs)
        rng = random.Random('%d/ycsb-latest/%d' % (self.fleet.seed, s))
        uniform = rng.random
        mine_ms = self.by_member.setdefault(member, [])
        reads, inserts = self.samples['read'], self.samples['insert']
        chk, paths, counter = self.checker, self.all_paths, self.counter
        rank = self.zipf.rank
        read_p, top = self.read_p, self.n + self.room
        deadline = self.deadline_ms
        clock = time.perf_counter
        interval = self.interval
        due = clock() + uniform() * interval
        while not self.stopping:
            insert = uniform() >= read_p
            if insert and counter.counter >= top:
                # the room a run was given is spent: nothing to create
                self.errors['insert_room'] = self.errors.get(
                    'insert_room', 0) + 1
                insert = False
            if not insert:
                u = uniform()
            if interval:
                wait = due - clock()
                if wait > 0:
                    await asyncio.sleep(wait)
                    if self.stopping:
                        return
            rec = self.recording
            if insert:
                key = counter.next()
                data = chk.create_sent(s, key)
            else:
                # drawn when it is sent: among the records the
                # frontier counts NOW
                frontier = counter.last
                while True:
                    z = rank(u, frontier)
                    key = frontier - z
                    if 0 <= key <= counter.last:
                        break
                    u = uniform()
            if rec:
                self.attempted += 1
                self.in_flight += 1
                if not insert:
                    self.reads += 1
                    self.reads_newest += not z
                    self.reads_hot += z < HOT_RANKS
                    self.reads_new += key >= self.base
                    self._expected(frontier)
            t0 = clock()
            if interval:
                if rec:
                    self.late_ms.append((t0 - due) * 1e3)
                t_sent, t0 = t0, due
                due += interval
            else:
                t_sent = t0
            path = paths[key]
            if insert:
                try:
                    await c.create(path, data, deadline=deadline)
                except asyncio.CancelledError:
                    chk.create_unknown(key)     # cut by the drain
                    raise
                except Exception as e:
                    code = getattr(e, 'code', None)
                    if code in REFUSALS:
                        chk.create_refused(s, key, code)
                    else:
                        chk.create_unknown(key)
                    if rec:
                        inserts.append(float(deadline))
                    self._fail(rec, e)
                    await asyncio.sleep(0.05)
                    continue
                if rec:
                    inserts.append((clock() - t0) * 1e3)
                chk.create_acked(s, member, key)
                counter.acknowledge(key)
            else:
                try:
                    data, stat = await c.get(path, deadline=deadline)
                except Exception as e:
                    if getattr(e, 'code', None) != 'NO_NODE':
                        if rec:
                            reads.append(float(deadline))
                        self._fail(rec, e)
                        await asyncio.sleep(0.05)
                        continue
                    data = None
                t1 = clock()
                if rec:
                    reads.append((t1 - t0) * 1e3)
                    mine_ms.append((t1 - t0) * 1e3)
                if data is None:
                    if rec:
                        self.misses += 1
                    chk.miss(s, member, key, t_sent, t1)
                else:
                    chk.read(s, member, key, data, stat.version,
                             stat.dataLength, stat.czxid, stat.mzxid, t1)
            if rec:
                self.in_flight -= 1
            if self.recording:
                self.acked += 1
                self.inserts_acked += insert

    # -- the checks after the window ------------------------------------

    async def validate(self) -> None:
        """Every create has its outcome now: ``sync``, then read back
        through plain sessions every loaded record, every record a
        create was sent for — an acknowledged one from ANOTHER member
        than the one that took it — and the names just past the
        counter, which nobody created."""
        self.live = False
        chk = self.checker
        n = len(self.fleet.addrs)
        readers = [self.fleet.new_client(m, through_ingest=False)
                   for m in range(n)]
        await asyncio.gather(*[r.wait_connected(timeout=60)
                               for r in readers])
        await asyncio.gather(*[r.sync(self.parent) for r in readers])

        async def one(key):
            m = chk.readback_member(key, n)
            where = 'member %d' % (m,)
            try:
                data, stat = await readers[m].get(self.all_paths[key])
            except Exception as e:
                if getattr(e, 'code', None) == 'NO_NODE':
                    chk.final(key, None, 0, 0, 0, where)
                else:
                    self.readback_failures += 1
                return
            chk.final(key, data, stat.version, stat.dataLength,
                      stat.czxid, where)
        end = min(self.n + self.room,
                  self.counter.counter + READBACK_BEYOND)
        for lo in range(0, end, 2048):
            await asyncio.gather(*[one(k) for k in range(
                lo, min(end, lo + 2048))])
        chk.settle()

    def result(self) -> dict:
        chk = self.checker
        bad = chk.bad
        kinds = dict(bad.by_kind)
        first = list(bad.first)
        if self.readback_failures:
            kinds['readback-failed'] = self.readback_failures
            first.append('readback-failed: %d records could not be read '
                         'back' % (self.readback_failures,))
        compared = ['%s %d limit 0' % (k, kinds.get(k, 0))
                    for k in reference_ycsb_latest.KINDS
                    + ('readback-failed',)]
        compared.append('observations_checked %d' % (chk.checked,))
        compared.append('reads_not_yet_visible %d (no limit: the '
                        "deployment's staleness)" % (chk.not_yet_visible,))
        reads = max(1, self.reads)

        def share(count):
            return round(100.0 * count / reads, 3)
        return {
            'attempted': self.attempted, 'failed': self.failed,
            'acked': self.acked, 'samples': self.samples,
            'deadline_ms': self.deadline_ms,
            'samples_by_member': self.by_member,
            'counters': {
                'errors': self.errors,
                'inserts_acked': self.inserts_acked,
                # what wal.roll_ms_per_change divides by
                'changes_acked': self.inserts_acked,
                'frontier_advance': (self.frontier_close
                                     - self.frontier_open),
                'records_inserted': self.counter.counter - self.n,
                'reads': self.reads,
                # NO_NODE answers to reads sent in the window; the
                # compared line counts the run's, warm-up and drain too
                'reads_not_yet_visible': self.misses,
                'newest_share': share(self.reads_newest),
                'newest_closed_form': share(self.expect_newest),
                'hot_ops_share': share(self.reads_hot),
                # the HOT_RANKS newest: their terms over each zetan
                'hot_ops_closed_form': share(
                    zeta_terms(0, HOT_RANKS, self.theta)
                    * self.expect_newest),
                'reads_on_window_records_share': share(self.reads_new),
                'reads_on_window_records_closed_form': share(
                    self.expect_new),
                # paced: due -> sent; the median says whether the
                # generator was on its schedule most of the window
                'gen_late_ms_p50': (round(stats.percentile(
                    self.late_ms, 50), 3) if self.late_ms else None),
                'gen_late_ms_p95': (round(stats.percentile(
                    self.late_ms, 95), 3) if self.late_ms else None)},
            'compared': compared, 'violations': first,
            'violation_kinds': kinds, 'checked': chk.checked,
        }
