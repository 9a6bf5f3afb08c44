"""The plain reference: a single-threaded dictionary model of the
ZooKeeper tree and the validators that hold a run's observations to
what the configuration's guarantees allow, WHATEVER the interleaving
of the concurrent sessions was.

Nothing here imports the program (``zkstream_tpu``) or takes anything
the program made: the payloads, the tree and every expected answer are
rebuilt from ``--seed`` and from the acknowledgements the harness
recorded on its own clock.

What makes the checks interleaving-independent:

- a znode's payload is a pure function of (seed, znode index, version),
  so a ``getData`` reply is judged by its own ``stat.version`` alone;
- every znode of the key-value cells has ONE writer, so its version is
  the count of that writer's acknowledged writes (plus at most one per
  write whose outcome is unknown);
- every service of the membership cells has ONE churner whose changes
  are serial, so a children list is judged by its own ``stat.cversion``
  alone: it must equal the membership after exactly
  ``cversion - base`` changes.
"""

from __future__ import annotations

import random

POOL_BYTES = 1 << 20
_ZXID_MASK = (1 << 64) - 1


class Payloads:
    """payload(index, version): ``size`` bytes cut from a seeded pool at
    an offset that depends on both, so two versions of one znode (or two
    znodes) differ, and making one costs a slice."""

    def __init__(self, seed: int, size: int):
        self.size = size
        self.pool = random.Random('payload/%d' % (seed,)).randbytes(
            POOL_BYTES + size)

    def get(self, index: int, version: int) -> bytes:
        off = (index * 2654435761 + version * 40503 + 12345) % POOL_BYTES
        return self.pool[off:off + self.size]


# ---------------------------------------------------------------------
# the tree model
# ---------------------------------------------------------------------

class ModelError(Exception):
    """The operation is refused by ZooKeeper's semantics (the code is
    the protocol's error name)."""

    def __init__(self, code: str):
        super().__init__(code)
        self.code = code


class Node:
    __slots__ = ('data', 'version', 'cversion', 'owner', 'children',
                 'seq')

    def __init__(self, data: bytes, owner: int = 0):
        self.data = data
        self.version = 0
        self.cversion = 0
        self.owner = owner          # ephemeral owner's session id, or 0
        self.children: set[str] = set()
        self.seq = 0                # sequential creates under this node


def _parent(path: str) -> str:
    head = path.rsplit('/', 1)[0]
    return head or '/'


class TreeModel:
    """data, ``version``, ``cversion``, ephemeral owner and sequential
    suffix, as a dictionary of paths.  Single-threaded: the caller
    applies operations in the order they took effect."""

    def __init__(self):
        self.nodes: dict[str, Node] = {'/': Node(b'')}

    def create(self, path: str, data: bytes, owner: int = 0,
               sequential: bool = False) -> str:
        parent = self.nodes.get(_parent(path))
        if parent is None:
            raise ModelError('NO_NODE')
        if parent.owner:
            raise ModelError('NO_CHILDREN_FOR_EPHEMERALS')
        if sequential:
            path = '%s%010d' % (path, parent.seq)
            parent.seq += 1
        if path in self.nodes:
            raise ModelError('NODE_EXISTS')
        self.nodes[path] = Node(data, owner)
        parent.children.add(path.rsplit('/', 1)[1])
        parent.cversion += 1
        return path

    def set(self, path: str, data: bytes, version: int = -1) -> int:
        node = self.nodes.get(path)
        if node is None:
            raise ModelError('NO_NODE')
        if version not in (-1, node.version):
            raise ModelError('BAD_VERSION')
        node.data = data
        node.version += 1
        return node.version

    def delete(self, path: str, version: int = -1) -> None:
        node = self.nodes.get(path)
        if node is None:
            raise ModelError('NO_NODE')
        if node.children:
            raise ModelError('NOT_EMPTY')
        if version not in (-1, node.version):
            raise ModelError('BAD_VERSION')
        del self.nodes[path]
        parent = self.nodes[_parent(path)]
        parent.children.discard(path.rsplit('/', 1)[1])
        parent.cversion += 1

    def get(self, path: str) -> tuple[bytes, int]:
        node = self.nodes.get(path)
        if node is None:
            raise ModelError('NO_NODE')
        return node.data, node.version

    def children(self, path: str) -> tuple[list[str], int]:
        node = self.nodes.get(path)
        if node is None:
            raise ModelError('NO_NODE')
        return sorted(node.children), node.cversion


# ---------------------------------------------------------------------
# validators
# ---------------------------------------------------------------------

class Violations:
    """What did not hold: a count per kind and the first few in words."""

    KEEP = 8

    def __init__(self):
        self.count = 0
        self.by_kind: dict[str, int] = {}
        self.first: list[str] = []

    def add(self, kind: str, what: str) -> None:
        self.count += 1
        self.by_kind[kind] = self.by_kind.get(kind, 0) + 1
        if len(self.first) < self.KEEP:
            self.first.append('%s: %s' % (kind, what))


class KvChecker:
    """Key-value cells: znodes of ``size`` bytes at ``paths``, each with
    at most one writing session.

    The harness reports, in the order its own clock saw them,
    ``write_acked`` / ``write_unknown`` for every ``setData`` and
    ``read`` for every ``getData`` reply; ``final`` for the value read
    back after the window, which must equal the model's.  Numbers
    compared, all exact (limit 0): payload bytes, version floors,
    version ceilings."""

    def __init__(self, seed: int, paths: list[str], size: int):
        self.payloads = Payloads(seed, size)
        self.paths = paths
        self.model = TreeModel()
        for path in paths:
            parts = path.split('/')[1:]
            for d in range(1, len(parts)):
                anc = '/' + '/'.join(parts[:d])
                if anc not in self.model.nodes:
                    self.model.create(anc, b'')
        for idx, path in enumerate(paths):
            self.model.create(path, self.payloads.get(idx, 0))
        #: writes sent whose outcome is unknown (deadline, lost reply)
        self.unknown = [0] * len(paths)
        #: session -> {znode: version << 64 | mzxid} last observed.  Ints
        #: in dicts of ints: the collector does not track them, so a
        #: window of 10^5 reads does not grow its work
        self.floor: dict[int, dict[int, int]] = {}
        self.bad = Violations()
        self.checked = 0

    def initial(self, idx: int) -> bytes:
        return self.payloads.get(idx, 0)

    def next_write(self, idx: int) -> bytes:
        """The bytes the znode's one writer sends next."""
        return self.payloads.get(
            idx, self.model.nodes[self.paths[idx]].version + 1)

    def write_acked(self, session: int, idx: int, version: int,
                    mzxid: int) -> None:
        self.checked += 1
        want = self.model.set(self.paths[idx], self.next_write(idx))
        if version != want and not self.unknown[idx]:
            self.bad.add('write-version', 'znode %d acked at version %d, '
                         'the model says %d' % (idx, version, want))
        self._observe(session, idx, version, mzxid)

    def write_unknown(self, idx: int) -> None:
        self.unknown[idx] += 1

    def _observe(self, session: int, idx: int, version: int,
                 mzxid: int) -> None:
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

    def read(self, session: int, idx: int, data: bytes, version: int,
             mzxid: int, sent_writes: int | None = None) -> None:
        """One ``getData`` reply.  ``sent_writes``: how many writes the
        znode's writer had SENT when the reply arrived (None: the
        acknowledged count plus the unknown ones)."""
        self.checked += 1
        if data != self.payloads.get(idx, version):
            self.bad.add('payload', 'znode %d at version %d returned %d '
                         'bytes that are not payload(seed, %d, %d)'
                         % (idx, version, len(data), idx, version))
        ceiling = (self.model.nodes[self.paths[idx]].version
                   + self.unknown[idx]
                   if sent_writes is None else sent_writes)
        if version > ceiling:
            self.bad.add('future-read', 'znode %d read at version %d '
                         'with %d writes sent' % (idx, version, ceiling))
        self._observe(session, idx, version, mzxid)

    def final(self, idx: int, data: bytes | None, version: int,
              where: str) -> None:
        """The znode as read back after ``sync`` equals the model's:
        every acknowledged write is there (and at most the unknown
        ones beyond)."""
        self.checked += 1
        want, lo = self.model.get(self.paths[idx])
        hi = lo + self.unknown[idx]
        if data is None:
            self.bad.add('lost-znode', 'znode %d missing from %s'
                         % (idx, where))
        elif not lo <= version <= hi:
            self.bad.add('lost-write', 'znode %d reads version %d from '
                         '%s after %d acknowledged writes'
                         % (idx, version, where, lo))
        elif data != (want if version == lo
                      else self.payloads.get(idx, version)):
            self.bad.add('payload', 'znode %d version %d read back from '
                         '%s with other bytes' % (idx, version, where))


class MembershipChecker:
    """Membership cells: group znodes at ``service_paths``, one serial
    churner each; an instance is an ephemeral-sequential child
    ``<service>/<prefix>NNNNNNNNNN``.

    ``register`` is one set-up registration; ``open_window`` fixes each
    service's ``cversion`` base; ``change`` appends one acknowledged (or
    unknown) change and holds a sequential create to the name the model
    gives it; ``listing`` judges one children list a watcher was handed
    by its own ``cversion``; ``finish`` holds every watcher to having
    seen the last change; ``final`` compares the tree after the window
    with the model."""

    def __init__(self, service_paths: list[str], prefix: str = 'i-'):
        self.paths = service_paths
        self.prefix = prefix
        self.model = TreeModel()
        root = _parent(service_paths[0])
        if root != '/':
            self.model.create(root, b'')
        for p in service_paths:
            self.model.create(p, b'')
        self.base: list[int] = [0] * len(service_paths)
        #: states[g][k]: the member names after k changes (None from
        #: the first change whose outcome is unknown onwards)
        self.states: list[list[frozenset | None]] = [
            [] for _ in service_paths]
        #: (watcher, service) -> newest change count seen
        self.seen: dict[tuple[int, int], int] = {}
        self._registered: list[set] = [set() for _ in service_paths]
        self._owners: dict = {}
        self.bad = Violations()
        self.checked = 0

    def register(self, g: int, name: str, owner: int, data: bytes) -> None:
        """A set-up registration: 32 sessions race for the suffixes, so
        only the SET of names is predictable (checked when the window
        opens)."""
        self.model.create(self.paths[g] + '/' + self.prefix, data,
                          owner=owner, sequential=True)
        self._registered[g].add(name)
        self._owners[(g, name)] = (owner, data)

    def open_window(self) -> None:
        for g, path in enumerate(self.paths):
            names, cversion = self.model.children(path)
            if set(names) != self._registered[g]:
                self.bad.add('sequential-names', 'service %d: '
                             'registrations returned %s, the model %s'
                             % (g, sorted(self._registered[g])[:3],
                                names[:3]))
            self.base[g] = cversion
            self.states[g] = [frozenset(names)]
        # which session got which suffix is the race's outcome: take it
        # from the acknowledgements
        for (g, name), (owner, data) in self._owners.items():
            node = self.model.nodes.get(self.paths[g] + '/' + name)
            if node is not None:
                node.owner, node.data = owner, data

    def change(self, g: int, kind: str, name: str | None,
               owner: int = 0, data: bytes = b'') -> int:
        """Append the service's next change; returns its index k (the
        membership after it is ``states[g][k]``).  ``name`` None: the
        outcome is unknown, nothing after it can be predicted."""
        if name is None or self.states[g][-1] is None:
            self.states[g].append(None)
            return len(self.states[g]) - 1
        path = self.paths[g]
        if kind == 'create':
            want = self.model.create(path + '/' + self.prefix, data,
                                     owner=owner, sequential=True)
            if want != path + '/' + name:
                self.bad.add('sequential-name', 'service %d: create '
                             'returned %s, the model %s'
                             % (g, name, want.rsplit('/', 1)[1]))
        else:
            try:
                self.model.delete(path + '/' + name)
            except ModelError as e:
                self.bad.add('delete', 'service %d: delete of %s '
                             'acknowledged, the model says %s'
                             % (g, name, e.code))
        self.states[g].append(frozenset(self.model.children(path)[0]))
        return len(self.states[g]) - 1

    def listing(self, watcher: int, g: int, names, cversion: int) -> int:
        """One children list as a watcher received it; returns the
        change count k it reflects (-1 when it cannot be placed)."""
        self.checked += 1
        k = cversion - self.base[g]
        if not 0 <= k < len(self.states[g]):
            self.bad.add('future-list', 'service %d listed at cversion '
                         '%d with %d changes sent'
                         % (g, cversion, len(self.states[g]) - 1))
            return -1
        want = self.states[g][k]
        got = frozenset(names)
        if want is not None and got != want:
            self.bad.add('children', 'service %d after %d changes: '
                         'missing %s, unexpected %s'
                         % (g, k, sorted(want - got)[:3],
                            sorted(got - want)[:3]))
        prev = self.seen.get((watcher, g), -1)
        if k < prev:
            self.bad.add('stale-list', 'watcher %d saw service %d go '
                         'back from change %d to %d'
                         % (watcher, g, prev, k))
        else:
            self.seen[(watcher, g)] = k
        return k

    def finish(self, watchers_of) -> int:
        """Every armed watcher of a changed service was notified of its
        last change; returns how many (watcher, service) pairs were
        not."""
        missed = 0
        for g, states in enumerate(self.states):
            last = len(states) - 1
            if last <= 0:
                continue
            for w in watchers_of[g]:
                if self.seen.get((w, g), -1) < last:
                    missed += 1
                    self.bad.add('not-notified', 'watcher %d never saw '
                                 'change %d of service %d'
                                 % (w, last, g))
        return missed

    def final(self, g: int, names, owners: dict, where: str) -> None:
        """The service as listed after ``sync`` equals the model's, and
        every instance is still owned by the session that made it."""
        self.checked += 1
        if self.states[g][-1] is None:
            return
        want, _cv = self.model.children(self.paths[g])
        got = frozenset(names)
        if got != frozenset(want):
            self.bad.add('final-children', 'service %d from %s: missing '
                         '%s, unexpected %s'
                         % (g, where, sorted(frozenset(want) - got)[:3],
                            sorted(got - frozenset(want))[:3]))
        for name, owner in owners.items():
            node = self.model.nodes.get(self.paths[g] + '/' + name)
            if node is not None and node.owner != owner:
                self.bad.add('ephemeral-owner', 'service %d: %s is owned '
                             'by %#x, the model says %#x'
                             % (g, name, owner, node.owner))
