"""The plain reference of the YCSB "read latest" cells (core workload
D through YCSB's ZooKeeper binding): records under ONE parent, loaded
before the window or CREATED inside it, each by one ``create`` of one
session, never rewritten and never deleted, and read from every
session on every member — the newest most of all.

Nothing here imports the program (``zkstream_tpu``) or takes anything
the program made: every name and every byte is made HERE from
``--seed`` (``reference_ycsb.Records`` over ``recordcount`` +
``insert_room`` keys: key index = YCSB's ``keynum``), and what exists
comes from the acknowledgements the harness recorded on its own clock.

The model is a dictionary: key -> absent | the bytes its one creator
sent.  All comparisons are exact (limit 0):

- a ``getData`` that finds a record finds exactly those bytes,
  ``version`` 0, ``dataLength`` their length, and the same ``czxid`` =
  ``mzxid`` whoever reads it, wherever;
- every acknowledged create is read back after ``sync`` from another
  member, whole; a record nobody created is absent; a create of
  unknown outcome may be either;
- ``NO_NODE`` is allowed only as ZooKeeper allows it.  A session on a
  member that has not applied a create yet is promised nothing, so
  absent is an ANSWER there (counted, ``not_yet_visible``: the
  deployment's staleness, no fault).  It is a ``stale-miss``:

  1. for a loaded record;
  2. for a record this session created and was acknowledged for;
  3. for a record this session has read before;
  4. when the session has already seen — in the ``Stat`` of any
     earlier reply — a ``czxid`` / ``mzxid`` at or above the ``czxid``
     the record turns out to have (a session never goes back; where the
     record's ``czxid`` is not known yet the miss is kept and judged
     when its first successful read, or the read-back, tells it);
  5. on ONE member, once any session attached to it has READ the
     record there, for a read SENT to that member after that reply was
     received (a member's store never goes back).  An acknowledged
     create proves this only for its own session — a follower may
     acknowledge before its replica applies — so the member-wide rule
     hangs on reads alone.
"""

from __future__ import annotations

from reference import Violations
from reference_ycsb import Records

KINDS = ('payload', 'stat', 'stale-miss', 'phantom', 'lost-create',
         'final-tree', 'refused', 'evicted')

ABSENT, SENT, ACKED, UNKNOWN = 0, 1, 2, 3


class LatestChecker:
    """The harness reports, in the order its own clock saw them:
    ``create_sent`` (it returns the bytes to send) and then
    ``create_acked`` / ``create_refused`` / ``create_unknown`` for every
    insert; ``read`` for every ``getData`` that found a record and
    ``miss`` for every one answered ``NO_NODE``, each with the session,
    the member the session is attached to and the times the request was
    sent and its reply received; ``gap`` for a disconnect, an expiry or
    a resume; after the drain ``final`` for every record as read back
    after ``sync``, then ``settle``."""

    def __init__(self, seed: int, recordcount: int, insert_room: int,
                 fieldcount: int = 10, fieldlength: int = 100,
                 parent: str = '/benchmark'):
        self.loaded = recordcount
        self.total = recordcount + insert_room
        self.records = Records(seed, self.total, fieldcount, fieldlength,
                               parent)
        self.paths = self.records.paths
        #: key -> the bytes its creator sent (filled at the first
        #: observation of a loaded record, at ``create_sent`` of an
        #: inserted one)
        self.model: dict[int, bytes] = {}
        # flat tables of ints, which the collector does not track
        self.state = bytearray(self.total)          # inserted keys only
        self.creator = [-1] * self.total
        self.creator_member = [-1] * self.total
        self.czxid = [0] * self.total               # 0: not known yet
        #: session -> the highest czxid / mzxid any Stat showed it
        self.seen: dict[int, int] = {}
        #: session << 32 | key: that session has read that record
        self.read_by: set[int] = set()
        #: member << 32 | key -> when the first reply that FOUND the
        #: record on that member was received
        self.first_on: dict[int, float] = {}
        #: key -> [(session, member, the session's ``seen`` at the
        #: miss)]: misses of records whose czxid nothing has told yet
        self.pending: dict[int, list] = {}
        self.not_yet_visible = 0        # misses ZooKeeper allows
        self.bad = Violations()
        self.checked = 0

    # -- what exists ----------------------------------------------------

    def initial(self, key: int) -> bytes:
        return self.records.initial(key)

    def _bytes(self, key: int) -> bytes:
        want = self.model.get(key)
        if want is None:
            want = self.model[key] = self.records.initial(key)
        return want

    def exists(self, key: int) -> bool:
        """The record was loaded or its create acknowledged."""
        return key < self.loaded or self.state[key] == ACKED

    # -- inserts --------------------------------------------------------

    def create_sent(self, session: int, key: int) -> bytes:
        if key < self.loaded or self.state[key] != ABSENT:
            self.bad.add('phantom', 'session %d is sent to create key '
                         '%d, which exists or was sent before'
                         % (session, key))
        self.state[key] = SENT
        self.creator[key] = session
        return self._bytes(key)

    def create_acked(self, session: int, member: int, key: int) -> None:
        self.checked += 1
        self.state[key] = ACKED
        self.creator_member[key] = member

    def create_refused(self, session: int, key: int, code: str) -> None:
        """A definite refusal (``NODE_EXISTS``, ``THROTTLED`` ...): no
        create is refused in a run.  What the tree holds is then
        whatever the refusal means; the read-back takes either."""
        self.state[key] = UNKNOWN
        self.bad.add('refused', "session %d's create of key %d was "
                     'refused: %s' % (session, key, code))

    def create_unknown(self, key: int) -> None:
        """Cut by the drain, a deadline, a lost connection."""
        self.state[key] = UNKNOWN

    # -- reads ----------------------------------------------------------

    def _learn(self, key: int, czxid: int) -> None:
        """The record's czxid is known now: judge the misses that
        waited for it (rule 4)."""
        self.czxid[key] = czxid
        for session, member, seen in self.pending.pop(key, ()):
            if seen >= czxid:
                self.not_yet_visible -= 1
                self.bad.add('stale-miss', 'session %d (member %d) was '
                             'told key %d is absent after a Stat had '
                             'shown it zxid %d; the record has czxid %d'
                             % (session, member, key, seen, czxid))

    def read(self, session: int, member: int, key: int, data: bytes,
             version: int, length: int, czxid: int, mzxid: int,
             t_received: float) -> None:
        """One ``getData`` reply that found the record."""
        self.checked += 1
        if key >= self.loaded and self.state[key] == ABSENT:
            self.bad.add('phantom', 'session %d read key %d on member '
                         '%d: a record nobody created'
                         % (session, key, member))
            return
        if data != self._bytes(key) or length != len(data):
            self.bad.add('payload', 'session %d read key %d on member '
                         '%d: %d bytes (dataLength %d) that are not '
                         'what its creator sent'
                         % (session, key, member, len(data), length))
        known = self.czxid[key]
        if version != 0 or mzxid != czxid or czxid <= 0 or (
                known and known != czxid):
            self.bad.add('stat', 'session %d read key %d on member %d '
                         'at version %d czxid %d mzxid %d (czxid %d '
                         'seen before)' % (session, key, member, version,
                                           czxid, mzxid, known))
        elif not known:
            self._learn(key, czxid)
        if czxid > self.seen.get(session, 0):
            self.seen[session] = czxid
        self.read_by.add(session << 32 | key)
        self.first_on.setdefault(member << 32 | key, t_received)

    def miss(self, session: int, member: int, key: int, t_sent: float,
             t_received: float) -> None:
        """One ``getData`` answered ``NO_NODE``."""
        self.checked += 1
        why = None
        if key < self.loaded:
            why = 'a loaded record'
        elif self.state[key] == ABSENT:
            return                  # nobody created it: absent is right
        elif self.creator[key] == session and self.state[key] == ACKED:
            why = 'its own acknowledged create'
        elif session << 32 | key in self.read_by:
            why = 'a record it has read before'
        else:
            first = self.first_on.get(member << 32 | key)
            seen = self.seen.get(session, 0)
            known = self.czxid[key]
            if first is not None and t_sent > first:
                why = ('a record member %d had shown another session '
                       '%.1f ms before this read was sent'
                       % (member, (t_sent - first) * 1e3))
            elif known and seen >= known:
                why = ('a record of czxid %d after a Stat had shown it '
                       'zxid %d' % (known, seen))
            elif not known:
                self.pending.setdefault(key, []).append(
                    (session, member, seen))
        if why is None:
            self.not_yet_visible += 1
            return
        self.bad.add('stale-miss', 'session %d (member %d) was told key '
                     '%d is absent: %s' % (session, member, key, why))

    def gap(self, session: int, what: str) -> None:
        """No session loses its connection in a run."""
        self.bad.add('evicted', 'session %d saw %s' % (session, what))

    # -- after the drain ------------------------------------------------

    def readback_member(self, key: int, members: int) -> int:
        """Where a record is read back: another member than the one
        that took its create."""
        took = self.creator_member[key]
        return (took + 1) % members if took >= 0 else key % members

    def final(self, key: int, data: bytes | None, version: int,
              length: int, czxid: int, where: str) -> None:
        """The record as read back after ``sync`` (None: absent)."""
        self.checked += 1
        state = ACKED if key < self.loaded else self.state[key]
        if data is None:
            if key < self.loaded:
                self.bad.add('final-tree', 'loaded key %d missing from '
                             '%s' % (key, where))
            elif state == ACKED:
                self.bad.add('lost-create', 'key %d, created by session '
                             '%d and acknowledged, is missing from %s'
                             % (key, self.creator[key], where))
            return
        if state == ABSENT:
            self.bad.add('phantom', 'key %d read back from %s: a record '
                         'nobody created' % (key, where))
            return
        if data != self._bytes(key) or length != len(data) or version:
            self.bad.add('final-tree', 'key %d read back from %s at '
                         'version %d with %d bytes that are not what '
                         'its creator sent' % (key, where, version,
                                               len(data)))
        known = self.czxid[key]
        if known and known != czxid:
            self.bad.add('stat', 'key %d read back from %s with czxid '
                         '%d, read with %d before'
                         % (key, where, czxid, known))
        elif not known:
            self._learn(key, czxid)

    def settle(self) -> None:
        """After the read-back: a miss that still waits is of a record
        that is not in the tree (its create failed or was cut), and
        absent was right."""
        self.pending.clear()
