"""The plain reference of the configset cells: a dictionary
path -> (size, version) over znodes of very different sizes, and the
validator that holds every reply to it byte for byte.

Nothing here imports the program (``zkstream_tpu``) or takes anything
the program made: every payload is cut from a pool seeded with
``--seed`` at the znode's OWN size, and a reply is compared over its
whole length, never a sample of it — a plane that delivered a large
body's head and lost or exchanged something behind it is what this
reference exists to catch.

The cell writes nothing inside its window, so a znode's expected bytes
are made once (``SizedChecker.expected``) and the timed loop pays a
comparison, not a generator.
"""

from __future__ import annotations

import os.path
import random

from reference import Violations

POOL_BYTES = 1 << 20
_ZXID_MASK = (1 << 64) - 1


class SizedPayloads:
    """payload(index, version, size): ``size`` bytes cut from a seeded
    pool at an offset that depends on the znode and the version."""

    def __init__(self, seed: int, largest: int):
        self.pool = random.Random('sized/%d' % (seed,)).randbytes(
            POOL_BYTES + largest)

    def get(self, index: int, version: int, size: int) -> bytes:
        off = (index * 2654435761 + version * 40503 + 977) % POOL_BYTES
        return self.pool[off:off + size]


class SizedChecker:
    """Znodes ``paths[i]`` of ``sizes[i]`` bytes, written once (version
    0) by the set-up and read by sessions.

    The harness reports ``listing`` for the directory a session lists
    when it connects, ``read`` for every ``getData`` reply in the order
    its own clock saw them, and ``final`` for every znode as read back
    after the window.  Numbers compared, all exact (limit 0): the bytes
    of every reply over their whole length, ``stat.dataLength``, the
    version, version and mzxid floors within a session, the directory
    listings, the final tree."""

    def __init__(self, seed: int, paths: list[str], sizes: list[int]):
        self.paths = paths
        self.model = {p: (n, 0) for p, n in zip(paths, sizes)}
        payloads = SizedPayloads(seed, max(sizes))
        self.expected = [payloads.get(i, 0, n)
                         for i, n in enumerate(sizes)]
        #: session -> {znode: version << 64 | mzxid} last observed
        self.floor: dict[int, dict[int, int]] = {}
        self.bad = Violations()
        self.checked = 0

    def children(self, directory: str) -> list[str]:
        """The names the model holds directly under ``directory``: its
        files and the directories that hold files further down."""
        head = directory.rstrip('/') + '/'
        return sorted({p[len(head):].split('/', 1)[0] for p in self.model
                       if p.startswith(head)})

    def listing(self, session: int, directory: str, names) -> None:
        self.checked += 1
        want = self.children(directory)
        if sorted(names) != want:
            got = set(names)
            self.bad.add('listing', 'session %d listed %s: missing %s, '
                         'unexpected %s' % (
                             session, directory,
                             sorted(set(want) - got)[:3],
                             sorted(got - set(want))[:3]))

    def _bytes(self, idx: int, data: bytes, length: int, version: int,
               where: str) -> None:
        size, want_version = self.model[self.paths[idx]]
        if version != want_version:
            self.bad.add('version', 'znode %d read at version %d %s, the '
                         'model says %d' % (idx, version, where,
                                            want_version))
        if length != size or len(data) != size:
            self.bad.add('data-length', 'znode %d: %d bytes and '
                         'dataLength %d %s, the model says %d'
                         % (idx, len(data), length, where, size))
        elif data != self.expected[idx]:
            at = len(os.path.commonprefix([data, self.expected[idx]]))
            self.bad.add('payload', 'znode %d (%d bytes) %s differs from '
                         'payload(seed, %d, %d) from byte %d on'
                         % (idx, size, where, idx, version, at))

    def read(self, session: int, idx: int, data: bytes, length: int,
             version: int, mzxid: int) -> None:
        """One ``getData`` reply: ``data`` and the ``stat``'s
        ``dataLength``, ``version`` and ``mzxid``."""
        self.checked += 1
        self._bytes(idx, data, length, version,
                    'by session %d' % (session,))
        mine = self.floor.get(session)
        if mine is None:
            mine = self.floor[session] = {}
        seen = mine.get(idx)
        if seen is not None and (version < seen >> 64
                                 or mzxid < seen & _ZXID_MASK):
            self.bad.add('stale-read', 'session %d saw znode %d at '
                         'version %d mzxid %d after version %d mzxid %d'
                         % (session, idx, version, mzxid, seen >> 64,
                            seen & _ZXID_MASK))
            return
        mine[idx] = version << 64 | mzxid

    def gap(self, session: int, what: str) -> None:
        """No session loses its connection in a run."""
        self.bad.add('evicted', 'session %d saw %s' % (session, what))

    def final(self, idx: int, data: bytes | None, length: int,
              version: int, where: str) -> None:
        """The znode as read back after ``sync`` equals the model's."""
        self.checked += 1
        if data is None:
            self.bad.add('lost-znode', 'znode %d missing from %s'
                         % (idx, where))
        else:
            self._bytes(idx, data, length, version, 'from ' + where)
