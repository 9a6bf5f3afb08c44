"""The plain reference of the configuration-distribution cells: a
dictionary model, key -> the versions its one publisher had
acknowledged, in order, and the validators that hold a run to the
configuration's guarantees WHATEVER the interleaving of the publishers
and the subscribers was.

Nothing here imports the program (``zkstream_tpu``) or takes anything
the program made: payloads are rebuilt from ``--seed`` (``reference.
Payloads``), expected versions from the acknowledgements the harness
recorded on its own clock.

What makes the checks interleaving-independent:

- a key's payload is a pure function of (seed, key index, version), so
  a read is judged by its own ``stat.version`` alone;
- every key has ONE publisher whose writes are serial, so a key's
  version is the count of that publisher's acknowledged writes (plus at
  most one per write whose outcome is unknown), and the change a
  notification announces is found by the zxid both carry;
- a subscriber's reads of one key are judged in the order they
  COMPLETED at the caller: a version never goes back, so no read —
  served from the cache or by a member — is older than one its session
  has been handed, and none is older than the session's last refresh.

All comparisons are exact (limit 0).
"""

from __future__ import annotations

from reference import Payloads, Violations

KINDS = ('payload', 'write-version', 'refresh-version', 'stale-hit',
         'future-read', 'missed-change', 'unknown-notification',
         'evicted', 'lost-write', 'lost-key')


class ConfChecker:
    """``keys`` configuration znodes of ``size`` bytes, ``subscribers``
    sessions that each hold the whole subtree in a watch-backed cache.

    The harness reports, as its own clock saw them: ``write_acked`` /
    ``write_unknown`` for every ``setData``; ``notified`` when a
    subscriber's watcher delivered a change event; ``refreshed`` for
    the read that event caused; ``read`` for every other read of the
    subscriber (the traffic the cache serves); ``gap`` for a
    ``'resumed'`` / ``'lost'`` edge or a reconnect; after the window
    ``final`` for each key as read back after ``sync``, and ``finish``,
    which holds every (acknowledged change, subscriber) pair to having
    been seen and returns when."""

    def __init__(self, seed: int, keys: int, size: int, subscribers: int):
        self.payloads = Payloads(seed, size)
        self.keys = keys
        self.subscribers = subscribers
        #: key -> versions acknowledged, in order: acked[k][i] is
        #: (version, mzxid); the model's current version is the last
        self.acked: list[list[tuple[int, int]]] = [[] for _ in range(keys)]
        self.version = [0] * keys           # the model
        self.unknown = [0] * keys
        #: change zxid -> (key, version), from the acknowledgements
        self.by_zxid: dict[int, tuple[int, int]] = {}
        #: subscriber * keys + key -> newest version a completed read
        #: showed.  Ints in a dict of ints: the collector does not
        #: track them
        self.floor: dict[int, int] = {}
        #: subscriber * keys + key -> [(t, version)] of its refreshes,
        #: in completion order
        self.refreshes: dict[int, list] = {}
        #: (subscriber, change zxid) told of, and the refreshes whose
        #: cause is judged once every acknowledgement is in
        self.told: set = set()
        self._pending: list = []
        self.bad = Violations()
        self.checked = 0

    def initial(self, key: int) -> bytes:
        return self.payloads.get(key, 0)

    def next_write(self, key: int) -> bytes:
        """The bytes the key's one publisher sends next."""
        return self.payloads.get(key, self.version[key] + 1)

    # -- the publishers ---------------------------------------------

    def write_acked(self, key: int, version: int, mzxid: int) -> int:
        """An acknowledged ``setData``; returns the model's version."""
        self.checked += 1
        self.version[key] += 1
        want = self.version[key]
        if version != want and not self.unknown[key]:
            self.bad.add('write-version', 'key %d acked at version %d, '
                         'the model says %d' % (key, version, want))
        self.acked[key].append((version, mzxid))
        self.by_zxid[mzxid] = (key, version)
        return want

    def write_unknown(self, key: int) -> None:
        self.unknown[key] += 1

    # -- the subscribers --------------------------------------------

    def notified(self, sub: int, key: int, zxid: int) -> None:
        self.told.add((sub, zxid))

    def _observe(self, sub: int, key: int, data: bytes, version: int,
                 sent: int, what: str) -> None:
        self.checked += 1
        if data != self.payloads.get(key, version):
            self.bad.add('payload', 'subscriber %d: %s of key %d at '
                         'version %d returned %d bytes that are not '
                         'payload(seed, %d, %d)' % (sub, what, key, version,
                                                    len(data), key, version))
        if version > sent:
            self.bad.add('future-read', 'key %d read at version %d with '
                         '%d writes sent' % (key, version, sent))
        slot = sub * self.keys + key
        seen = self.floor.get(slot, 0)
        if version < seen:
            self.bad.add('stale-hit', 'subscriber %d: %s of key %d shows '
                         'version %d after a completed read showed %d'
                         % (sub, what, key, version, seen))
        else:
            self.floor[slot] = version

    def read(self, sub: int, key: int, data: bytes, version: int,
             sent: int) -> None:
        """A read the subscriber's application made (most are served
        from its cache).  ``sent``: writes of the key SENT so far."""
        self._observe(sub, key, data, version, sent, 'a read')

    def refreshed(self, sub: int, key: int, zxid: int, t: float,
                  data: bytes, version: int, sent: int) -> None:
        """The read a change event (``zxid``) caused, completed at
        ``t``."""
        self._observe(sub, key, data, version, sent, 'the refresh')
        self.refreshes.setdefault(sub * self.keys + key, []).append(
            (t, version))
        self._pending.append((sub, key, zxid, version))

    def gap(self, sub: int, what: str) -> None:
        self.bad.add('evicted', 'subscriber %d: %s inside the run'
                     % (sub, what))

    # -- after the window -------------------------------------------

    def seen_at(self, sub: int, key: int, version: int) -> float | None:
        """When ``sub``'s refreshed view of ``key`` first showed
        ``version`` or a later one (None: never)."""
        return next((t for t, v in self.refreshes.get(
            sub * self.keys + key, ()) if v >= version), None)

    def finish(self) -> int:
        """Judge every refresh by the change that caused it, and every
        (acknowledged change, subscriber) pair: the subscriber was told
        of it and a refresh of its shows it or a later one.  Returns
        the pairs that were not."""
        for sub, key, zxid, version in self._pending:
            self.checked += 1
            cause = self.by_zxid.get(zxid)
            if cause is None:
                if not self.unknown[key]:
                    self.bad.add('unknown-notification', 'subscriber %d '
                                 'was told of a change of key %d at zxid '
                                 '%#x that no publisher had acknowledged'
                                 % (sub, key, zxid))
            elif cause[0] != key or version < cause[1]:
                self.bad.add('refresh-version', 'subscriber %d: the '
                             'refresh after the change of key %d to '
                             'version %d shows key %d at version %d'
                             % (sub, cause[0], cause[1], key, version))
        self._pending = []
        missed = 0
        for key, versions in enumerate(self.acked):
            for version, mzxid in versions:
                for sub in range(self.subscribers):
                    self.checked += 1
                    if ((sub, mzxid) in self.told
                            and self.seen_at(sub, key, version) is not None):
                        continue
                    missed += 1
                    self.bad.add(
                        'missed-change', 'subscriber %d %s the change of '
                        'key %d to version %d' % (
                            sub, 'was never told of'
                            if (sub, mzxid) not in self.told
                            else 'never refreshed to', key, version))
        return missed

    def final(self, key: int, data: bytes | None, version: int,
              where: str) -> None:
        """The key as read back after ``sync`` equals the model's:
        every acknowledged write is there (and at most the unknown
        ones beyond)."""
        self.checked += 1
        lo = self.version[key]
        hi = lo + self.unknown[key]
        if data is None:
            self.bad.add('lost-key', 'key %d missing from %s' % (key, where))
        elif not lo <= version <= hi:
            self.bad.add('lost-write', 'key %d reads version %d from %s '
                         'after %d acknowledged writes'
                         % (key, version, where, lo))
        elif data != self.payloads.get(key, version):
            self.bad.add('payload', 'key %d version %d read back from %s '
                         'with other bytes' % (key, version, where))
