"""The plain reference of the cluster-state cells: a dictionary
document -> (size, version) over documents that are REWRITTEN whole and
grow with every version, each broker's last seen version a document,
and the validator that holds every write, every watcher emission and
the final tree to it.

Nothing here imports the program (``zkstream_tpu``) or takes anything
the program made: every payload is cut from a pool seeded with
``--seed`` at the document's OWN size for that version
(``reference_sized.SizedPayloads``), expected versions come from the
acknowledgements the harness recorded on its own clock, and a view is
compared over its whole length, never a sample of it.

What makes the checks interleaving-independent:

- a document's size is ``base + version x grow`` and its bytes a pure
  function of (seed, document, version), so a view is judged by its own
  ``stat.version`` alone;
- every document has ONE writer whose writes are serial, so its version
  is the count of that writer's acknowledged writes (plus at most one
  per write whose outcome is unknown);
- a broker's views of a document are judged in the order its listener
  was handed them: a version never goes back;
- a one-shot watch owes its listener exactly one view per arming and
  one per notification (the change that fired it happened after the
  read that armed it, so the re-read shows something newer): a broker
  that was told and whose listener was not handed a view is a missed
  change, whatever a LATER change shows it.

All comparisons are exact (limit 0).
"""

from __future__ import annotations

import os.path

from reference import Violations
from reference_sized import SizedPayloads

KINDS = ('payload', 'data-length', 'write-version', 'stale-view',
         'future-read', 'missed-change', 'evicted', 'lost-write',
         'lost-doc', 'ephemeral')


class DocsChecker:
    """``len(base)`` documents; document d at version v holds
    ``base[d] + v * grow`` bytes.  ``watched`` are the documents the
    ``brokers`` watch.

    The harness reports, as its own clock saw them: ``write_acked`` /
    ``write_unknown`` for every ``setData``; ``armed`` when a broker's
    watcher is armed on a document and ``notified`` when the watch's
    notification reached that watcher (each owes the listener a view);
    ``emitted`` for every view the listener was handed; ``gap`` for a
    disconnect or an expiry; after the window ``final`` for every
    document and ``final_ephemeral`` for every ephemeral as read back
    after ``sync``, and ``finish``, which holds every (acknowledged
    change of a watched document, broker) pair to having been shown."""

    def __init__(self, seed: int, base: list[int], grow: int,
                 brokers: int, watched: list[int], max_versions: int = 4096):
        self.base = list(base)
        self.grow = grow
        self.brokers = brokers
        self.watched = list(watched)
        self.payloads = SizedPayloads(
            seed, max(self.base) + max_versions * grow)
        self.max_versions = max_versions
        n = len(self.base)
        self.version = [0] * n          # the model
        self.unknown = [0] * n
        #: document -> [(version, mzxid)] acknowledged, in order
        self.acked: list[list[tuple[int, int]]] = [[] for _ in range(n)]
        #: broker * n + document -> [(t, version)] views, in the order
        #: the listener was handed them
        self.views: dict[int, list] = {}
        #: broker * n + document -> views the watch still owes
        self.owed: dict[int, int] = {}
        self._expected: dict[int, tuple[int, bytes]] = {}
        self.bad = Violations()
        self.checked = 0

    # -- the model ---------------------------------------------------

    def size(self, doc: int, version: int) -> int:
        return self.base[doc] + version * self.grow

    def expected(self, doc: int, version: int) -> bytes:
        """payload(seed, doc, version) at the document's size for that
        version (the newest one asked for is kept: a herd of brokers
        compares against the same bytes)."""
        have = self._expected.get(doc)
        if have is not None and have[0] == version:
            return have[1]
        data = self.payloads.get(doc, version, self.size(doc, version))
        self._expected[doc] = (version, data)
        return data

    def initial(self, doc: int) -> bytes:
        return self.expected(doc, 0)

    def next_write(self, doc: int) -> bytes:
        """The bytes the document's one writer sends next."""
        v = self.version[doc] + 1
        if v > self.max_versions:
            raise ValueError('document %d past %d versions'
                             % (doc, self.max_versions))
        return self.payloads.get(doc, v, self.size(doc, v))

    # -- the writers ---------------------------------------------------

    def write_acked(self, doc: int, version: int, mzxid: int) -> int:
        """An acknowledged ``setData``; returns the model's version."""
        self.checked += 1
        self.version[doc] += 1
        want = self.version[doc]
        if version != want and not self.unknown[doc]:
            self.bad.add('write-version', 'document %d acked at version '
                         '%d, the model says %d' % (doc, version, want))
        self.acked[doc].append((version, mzxid))
        return want

    def write_unknown(self, doc: int) -> None:
        self.unknown[doc] += 1

    # -- the brokers -----------------------------------------------------

    def armed(self, broker: int, doc: int) -> None:
        slot = broker * len(self.base) + doc
        self.owed[slot] = self.owed.get(slot, 0) + 1

    def notified(self, broker: int, doc: int) -> None:
        self.armed(broker, doc)

    def _bytes(self, doc: int, data: bytes, length: int, version: int,
               where: str) -> None:
        if not 0 <= version <= self.max_versions:
            self.bad.add('future-read', 'document %d %s at version %d'
                         % (doc, where, version))
            return
        size = self.size(doc, version)
        if length != size or len(data) != size:
            self.bad.add('data-length', 'document %d version %d: %d bytes '
                         'and dataLength %d %s, the model says %d'
                         % (doc, version, len(data), length, where, size))
            return
        want = self.expected(doc, version)
        if data != want:
            at = len(os.path.commonprefix([data, want]))
            self.bad.add('payload', 'document %d version %d (%d bytes) %s '
                         'differs from payload(seed, %d, %d) from byte %d '
                         'on' % (doc, version, size, where, doc, version,
                                 at))

    def emitted(self, broker: int, doc: int, t: float, data: bytes,
                length: int, version: int, sent: int) -> None:
        """A view the broker's ``'dataChanged'`` listener was handed at
        ``t``: ``data`` and the ``stat``'s ``dataLength`` and
        ``version``.  ``sent``: writes of the document SENT so far."""
        self.checked += 1
        self._bytes(doc, data, length, version,
                    'shown to broker %d' % (broker,))
        if version > sent:
            self.bad.add('future-read', 'document %d shown at version %d '
                         'with %d writes sent' % (doc, version, sent))
        slot = broker * len(self.base) + doc
        mine = self.views.setdefault(slot, [])
        if mine and version < mine[-1][1]:
            self.bad.add('stale-view', 'broker %d was shown document %d at '
                         'version %d after version %d'
                         % (broker, doc, version, mine[-1][1]))
        mine.append((t, version))
        if self.owed.get(slot, 0) > 0:
            self.owed[slot] -= 1

    def gap(self, session: int, what: str) -> None:
        self.bad.add('evicted', 'session %d: %s inside the run'
                     % (session, what))

    # -- after the window ------------------------------------------------

    def seen_at(self, broker: int, doc: int, version: int) -> float | None:
        """When ``broker``'s view of ``doc`` first showed ``version`` or
        a later one (None: never)."""
        return next((t for t, v in self.views.get(
            broker * len(self.base) + doc, ()) if v >= version), None)

    def finish(self) -> int:
        """Every (acknowledged change of a watched document, broker)
        pair: the broker was shown that version or a later one; and no
        watch owes its listener a view (it was armed, or told of a
        change, and handed nothing).  Returns the pairs and the owed
        views that were not."""
        missed = 0
        for doc in self.watched:
            for version, _mzxid in self.acked[doc]:
                for b in range(self.brokers):
                    self.checked += 1
                    if self.seen_at(b, doc, version) is None:
                        missed += 1
                        self.bad.add('missed-change', 'broker %d was never '
                                     'shown the change of document %d to '
                                     'version %d' % (b, doc, version))
        n = len(self.base)
        for slot, owed in sorted(self.owed.items()):
            self.checked += 1
            for _ in range(max(0, owed)):
                missed += 1
                self.bad.add('missed-change', 'broker %d was armed on or '
                             'told of a change of document %d and its '
                             'listener was handed no view for it'
                             % (slot // n, slot % n))
        return missed

    def final(self, doc: int, data: bytes | None, length: int,
              version: int, where: str) -> None:
        """The document as read back after ``sync`` equals the model's:
        every acknowledged write is there (and at most the unknown ones
        beyond)."""
        self.checked += 1
        lo = self.version[doc]
        hi = lo + self.unknown[doc]
        if data is None:
            self.bad.add('lost-doc', 'document %d missing from %s'
                         % (doc, where))
        elif not lo <= version <= hi:
            self.bad.add('lost-write', 'document %d reads version %d from '
                         '%s after %d acknowledged writes'
                         % (doc, version, where, lo))
        else:
            self._bytes(doc, data, length, version, 'from ' + where)

    def final_ephemeral(self, index: int, data: bytes | None, owner: int,
                        want: bytes, want_owner: int, where: str) -> None:
        """Ephemeral ``index`` as read back: there, with its bytes, owned
        by the session that created it."""
        self.checked += 1
        if data is None:
            self.bad.add('ephemeral', 'ephemeral %d missing from %s'
                         % (index, where))
        elif data != want or owner != want_owner:
            self.bad.add('ephemeral', 'ephemeral %d from %s: %d bytes owned '
                         'by %#x, the model says %d bytes owned by %#x'
                         % (index, where, len(data), owner, len(want),
                            want_owner))
