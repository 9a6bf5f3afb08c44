"""The plain reference of the YCSB cells: records of ``fieldcount``
fields under ONE parent, read and rewritten by read-modify-write from
EVERY session — many writers a znode — and the validator that holds
every ``getData`` reply, every ``setData`` acknowledgement and the
final tree to what those writers sent.

Nothing here imports the program (``zkstream_tpu``) or takes anything
the program made: the keys, the field values and every record a writer
sends are made HERE from ``--seed`` (``Records``; ``rewrite`` builds an
update's bytes from the bytes its ``getData`` returned), and what a
version holds comes from the acknowledgements the harness recorded on
its own clock.

No znode has one writer, so a version is not a count of anyone's acks.
What makes the checks interleaving-independent instead:

- a ``setData`` at version -1 always applies, and its acknowledgement
  carries the version IT made: the ack's ``stat.version`` names the
  bytes that writer sent.  Per key the model is a map version -> bytes,
  version 0 from the load, and whoever observes (key, version) — any
  session, any member, the final read-back — must see those bytes over
  their whole length.  A read of a version whose ack has not arrived
  yet is kept and judged when it has;
- no two acks of one key share a version, none is above the writes
  sent to that key, and after the drain the versions acknowledged are
  exactly 1 .. the count of acks (plus at most one per write whose
  outcome is unknown);
- within a session a key's version and ``mzxid`` never go back, and a
  session's own acknowledged write is a floor under its later reads;
- a record is judged field by field only where no version's bytes can
  decide (a version of unknown outcome): every field must be a value
  the load or some write of THAT key and field produced.

The binding's lost-update race (two sessions read version v, both
write: the later ack's record lacks the earlier one's field) is part of
the traffic and no violation: each version still holds exactly what
its writer sent.

All comparisons are exact (limit 0).
"""

from __future__ import annotations

import random

from reference import Violations

KINDS = ('payload', 'version-bytes', 'stale-read', 'future-read',
         'write-version', 'evicted', 'final-tree', 'lost-write')

POOL_BYTES = 1 << 20
_ZXID_MASK = (1 << 64) - 1
_ALNUM = (b'0123456789abcdefghijklmnopqrstuvwxyz'
          b'ABCDEFGHIJKLMNOPQRSTUVWXYZ')
_TO_ALNUM = bytes(_ALNUM[i % len(_ALNUM)] for i in range(256))


class Records:
    """``recordcount`` records of ``fieldcount`` fields x ``fieldlength``
    bytes, in the shape YCSB's ZooKeeper binding stores them: ONE znode
    a record, ``<parent>/user<19-20 digits>``, holding the JSON map
    ``{"field0":"...",...,"field9":"..."}`` with no space.  Keys and
    values are drawn from ``seed``; a value is ``fieldlength`` bytes cut
    from a seeded pool of letters and digits (nothing JSON escapes, so
    a record's length is fixed) at an offset that depends on (key,
    field, write number), so a field's successive values differ."""

    def __init__(self, seed: int, recordcount: int, fieldcount: int = 10,
                 fieldlength: int = 100, parent: str = '/benchmark'):
        self.fieldcount = fieldcount
        self.fieldlength = fieldlength
        rng = random.Random('ycsb-keys/%d' % (seed,))
        seen: set[int] = set()
        while len(seen) < recordcount:
            seen.add(rng.randrange(10 ** 18, 1 << 64))
        nums = list(seen)
        nums.sort()
        rng.shuffle(nums)
        #: key index -> name; index order is load order, and the
        #: engine's rank -> key permutation is drawn apart from it
        self.names = ['user%d' % (v,) for v in nums]
        self.paths = ['%s/%s' % (parent, name) for name in self.names]
        self.pool = random.Random('ycsb-pool/%d' % (seed,)).randbytes(
            POOL_BYTES + fieldlength).translate(_TO_ALNUM)
        self._prefix = [b'"field%d":"' % (f,) for f in range(fieldcount)]
        #: where field f's value starts in a record
        self._at: list[int] = []
        at = 1
        for f in range(fieldcount):
            at += len(self._prefix[f])
            self._at.append(at)
            at += fieldlength + 2       # the value, '"' and ',' (or '}')
        self.record_bytes = at

    def value(self, key: int, field: int, write: int) -> bytes:
        """Field ``field`` of key ``key`` as its ``write``-th writer
        sends it (0: the load)."""
        off = (key * 2654435761 + field * 7919 + write * 40503
               + 12345) % POOL_BYTES
        return self.pool[off:off + self.fieldlength]

    def join(self, values) -> bytes:
        parts = [b'{']
        for f, v in enumerate(values):
            parts += (self._prefix[f], v, b'",')
        parts[-1] = b'"}'
        return b''.join(parts)

    def initial(self, key: int) -> bytes:
        """The record the load phase creates."""
        return self.join([self.value(key, f, 0)
                          for f in range(self.fieldcount)])

    def replace(self, data: bytes, field: int, value: bytes) -> bytes:
        """``data`` with field ``field`` replaced (what the binding's
        ``update`` does with the map it parsed)."""
        at = self._at[field]
        return data[:at] + value + data[at + self.fieldlength:]

    def fields(self, data: bytes) -> list[bytes] | None:
        """The values of a well-formed record; None for anything
        else (another length, a byte of the skeleton changed)."""
        if len(data) != self.record_bytes:
            return None
        n = self.fieldlength
        vals = [data[at:at + n] for at in self._at]
        return vals if self.join(vals) == data else None


class YcsbChecker:
    """The harness reports, in the order its own clock saw them:
    ``read`` for every ``getData`` reply (a plain read's and an
    update's alike); ``rewrite`` when an update is about to send its
    ``setData`` (it returns the bytes to send); ``write_acked`` /
    ``write_unknown`` for that ``setData``'s outcome; ``gap`` for a
    disconnect, an expiry or a resume; after the drain ``settle``, then
    ``final`` for every record as read back after ``sync``."""

    def __init__(self, seed: int, recordcount: int, fieldcount: int = 10,
                 fieldlength: int = 100, parent: str = '/benchmark'):
        self.records = rec = Records(seed, recordcount, fieldcount,
                                     fieldlength, parent)
        self.paths = rec.paths
        # Flat tables of ints and bytes, which the collector does not
        # track: a window of 10^5 reads does not grow its work.
        #: key << 32 | version -> the bytes that version holds (a
        #: key's version 0 is made at its first observation)
        self.known: dict[int, bytes] = {}
        #: key << 32 | version -> the bytes first observed at a
        #: version whose acknowledgement has not arrived
        self.pending: dict[int, bytes] = {}
        self.sent = [0] * recordcount       # setData sent, per key
        self.acked = [0] * recordcount      # ... acknowledged
        self.unknown = [0] * recordcount    # ... of unknown outcome
        #: a key's newest acknowledged version, and the member that
        #: took that write
        self.newest = [0] * recordcount
        self.newest_member = [0] * recordcount
        #: (key * fieldcount + field, 4 bytes) + value: what writes
        #: gave a field
        self.written: set[bytes] = set()
        #: session << 32 | key -> version << 64 | mzxid last observed
        self.floor: dict[int, int] = {}
        self.touched = 0        # keys observed
        self.bad = Violations()
        self.checked = 0

    def initial(self, key: int) -> bytes:
        return self.records.initial(key)

    def _holds(self, key: int, version: int) -> bytes | None:
        """The bytes ``version`` of ``key`` holds, if anything says."""
        want = self.known.get(key << 32 | version)
        if want is None and not version:
            want = self.known[key << 32] = self.records.initial(key)
            self.touched += 1
        return want

    def _tag(self, key: int, field: int, value: bytes) -> bytes:
        return (key * self.records.fieldcount + field).to_bytes(
            4, 'big') + value

    def _produced(self, key: int, data: bytes) -> bool:
        """Every field of ``data`` is a value the load or some write
        of that key and field produced."""
        vals = self.records.fields(data)
        if vals is None:
            return False
        return all(v == self.records.value(key, f, 0)
                   or self._tag(key, f, v) in self.written
                   for f, v in enumerate(vals))

    def _observe(self, session: int, key: int, version: int,
                 mzxid: int) -> None:
        at = session << 32 | key
        seen = self.floor.get(at)
        if seen is not None and (version < seen >> 64
                                 or mzxid < seen & _ZXID_MASK):
            self.bad.add('stale-read', 'session %d saw key %d at '
                         'version %d mzxid %d after version %d mzxid %d'
                         % (session, key, version, mzxid, seen >> 64,
                            seen & _ZXID_MASK))
            return
        self.floor[at] = version << 64 | mzxid

    def read(self, session: int, key: int, data: bytes, version: int,
             mzxid: int, length: int) -> None:
        """One ``getData`` reply: ``data`` and the ``stat``'s
        ``version``, ``mzxid`` and ``dataLength``."""
        self.checked += 1
        want = self._holds(key, version)
        if want is None:
            if version > self.sent[key] or version < 0:
                self.bad.add('future-read', 'key %d read at version %d '
                             'with %d writes sent'
                             % (key, version, self.sent[key]))
            else:
                self._unacked(key, version, data,
                              'by session %d' % (session,))
        elif data != want:
            kind = ('version-bytes' if self._produced(key, data)
                    else 'payload')
            self.bad.add(kind, 'session %d read key %d at version %d: '
                         '%d bytes that are not what that version holds'
                         % (session, key, version, len(data)))
        if length != len(data):
            self.bad.add('payload', 'key %d: %d bytes under a '
                         'dataLength of %d' % (key, len(data), length))
        self._observe(session, key, version, mzxid)

    def _unacked(self, key: int, version: int, data: bytes,
                 where: str) -> None:
        """An observation of a version no acknowledgement has named
        (yet): every field something produced, and equal to whatever
        else was seen at that version."""
        if not self._produced(key, data):
            self.bad.add('payload', 'key %d at version %d %s: a field '
                         'nothing wrote, or no record'
                         % (key, version, where))
        first = self.pending.setdefault(key << 32 | version, data)
        if first != data:
            self.bad.add('version-bytes', 'key %d at version %d %s '
                         'differs from an earlier read of that version'
                         % (key, version, where))

    def rewrite(self, key: int, data: bytes, field: int) -> bytes:
        """The record an update sends: what its ``getData`` returned
        with ``field`` replaced by a value nobody has sent before."""
        self.sent[key] += 1
        value = self.records.value(key, field, self.sent[key])
        self.written.add(self._tag(key, field, value))
        return self.records.replace(data, field, value)

    def write_acked(self, session: int, member: int, key: int,
                    version: int, mzxid: int, data: bytes) -> None:
        """``setData`` of ``data`` acknowledged at ``version``."""
        self.checked += 1
        self.acked[key] += 1
        if self._holds(key, version) is not None:
            self.bad.add('write-version', 'key %d: two acknowledgements '
                         'at version %d' % (key, version))
        elif not 1 <= version <= self.sent[key]:
            self.bad.add('write-version', 'key %d acked at version %d '
                         'with %d writes sent'
                         % (key, version, self.sent[key]))
        else:
            self.known[key << 32 | version] = data
            if version > self.newest[key]:
                self.newest[key] = version
                self.newest_member[key] = member
            first = self.pending.pop(key << 32 | version, None)
            if first is not None and first != data:
                self.bad.add('version-bytes', 'key %d was read at '
                             'version %d with other bytes than the '
                             'writer acknowledged at it sent'
                             % (key, version))
        self._observe(session, key, version, mzxid)

    def write_unknown(self, key: int) -> None:
        self.unknown[key] += 1

    def gap(self, session: int, what: str) -> None:
        """No session loses its connection in a run."""
        self.bad.add('evicted', 'session %d saw %s' % (session, what))

    def settle(self) -> None:
        """After the drain: every write has its outcome, so a version
        that was read and that no acknowledgement named is one nobody
        wrote — unless a write of that key has an unknown outcome."""
        for kv in sorted(self.pending):
            key, version = kv >> 32, kv & 0xFFFFFFFF
            if not self.unknown[key]:
                self.bad.add('version-bytes', 'key %d was read at '
                             'version %d, which no acknowledged write '
                             'made' % (key, version))
        # distinct, and none below 1: as many as the newest says
        for key, top in enumerate(self.newest):
            if top != self.acked[key] and not self.unknown[key]:
                self.bad.add('write-version', 'key %d: %d '
                             'acknowledgements reach version %d'
                             % (key, self.acked[key], top))

    def final(self, key: int, data: bytes | None, version: int,
              length: int, where: str) -> None:
        """The record as read back after ``sync``: its version is the
        count of acknowledged writes (and at most the unknown ones
        beyond), its bytes what that version holds."""
        self.checked += 1
        lo = self.acked[key]
        hi = lo + self.unknown[key]
        if data is None:
            self.bad.add('final-tree', 'key %d missing from %s'
                         % (key, where))
        elif not lo <= version <= hi:
            self.bad.add('lost-write', 'key %d reads version %d from %s '
                         'after %d acknowledged writes'
                         % (key, version, where, lo))
        else:
            want = self._holds(key, version)
            if length != len(data) or (
                    data != want if want is not None
                    else not self._produced(key, data)):
                self.bad.add('final-tree', 'key %d version %d read back '
                             'from %s with other bytes'
                             % (key, version, where))
