"""The plain reference of the live-nodes cells: a dictionary model of
ONE directory in which every node of a cluster holds one ephemeral and
which every node watches — membership as a list, change index -> set of
names — and the validator that holds every view a watcher hands its
listener, every (change, other node) pair and the final tree to it.

Nothing here imports the program (``zkstream_tpu``) or takes anything
the program made: the names are drawn from ``--seed``, and a view's
expected membership comes from the acknowledgements the harness
recorded on its own clock.

What makes the checks interleaving-independent:

- the directory's changes are SERIAL (one node is down at a time and a
  change is sent when the one before it is acknowledged), a session
  close removes exactly one child and a create adds one, so the
  directory's ``cversion`` is ``base`` + the count of changes and a
  view is judged by its own ``stat.cversion`` alone: it must equal, as
  a set and with no duplicate, the membership after exactly
  ``cversion - base`` changes;
- a node's views are judged in the order its listener was handed them:
  within one session the ``cversion`` never goes back, nor stands
  still (a one-shot watcher hands its listener a list only when the
  directory has moved since the one before);
- a one-shot watch owes its listener exactly one view per arming and
  one per notification (the change that fired it happened after the
  read that armed it, so the re-list shows something newer): a node
  that was told and whose listener was handed nothing is a missed
  change, whatever a LATER change shows it.  A node's own leave closes
  its session and with it whatever its watch still owed.

All comparisons are exact (limit 0).
"""

from __future__ import annotations

import random

from reference import Violations

KINDS = ('children', 'stale-view', 'future-read', 'missed-change',
         'create-path', 'evicted', 'final-children', 'ephemeral-owner')

PORT = ':8983_solr'


def node_names(seed: int, count: int) -> list[str]:
    """``count`` distinct Solr node names ``10.<a>.<b>.<c>:8983_solr``
    drawn from ``seed`` (18 to 24 bytes each)."""
    rng = random.Random('live-nodes/%d' % (seed,))
    seen: set[str] = set()
    out: list[str] = []
    while len(out) < count:
        name = '10.%d.%d.%d%s' % (rng.randrange(256), rng.randrange(256),
                                  rng.randrange(1, 255), PORT)
        if name not in seen:
            seen.add(name)
            out.append(name)
    return out


class LiveChecker:
    """``nodes`` nodes, node n named ``names[n]``, each holding the
    ephemeral ``<parent>/<names[n]>`` and watching ``parent``.

    The harness reports, as its own clock saw them: ``registered`` for
    every set-up registration; ``left`` / ``returned`` for every
    acknowledged change and ``unknown`` for one whose outcome it does
    not know; ``armed`` when a node's watcher is armed and ``notified``
    when the watch's notification reached it (each owes the listener a
    view); ``emitted`` for every view the listener was handed; ``gap``
    for a disconnect, an expiry or a close that was not the node's own
    leave; after the window ``final`` for the directory and
    ``final_owner`` for every ephemeral as read back after ``sync``,
    and ``finish``, which holds every (acknowledged change, other node)
    pair to having been shown."""

    def __init__(self, seed: int, nodes: int, parent: str = '/live_nodes'):
        self.parent = parent
        self.names = node_names(seed, nodes)
        self.nodes = nodes
        #: the directory's ``cversion`` after the registrations: every
        #: node's create moved it once
        self.base = nodes
        #: states[k]: the names after k changes (None from the first
        #: change whose outcome is unknown onwards)
        self.states: list[frozenset | None] = [frozenset(self.names)]
        #: change k -> the node that made it (None: before any change)
        self.by: list[int | None] = [None]
        self.owner: dict[str, int] = {}     # name -> session id
        #: node -> [(t, k)] views in the order its listener got them,
        #: through every session the node has had
        self.views: list[list] = [[] for _ in range(nodes)]
        #: node -> the newest change its CURRENT session was shown
        self.floor: list[int] = [-1] * nodes
        #: node -> the newest change any of its sessions was shown
        self.newest: list[int] = [-1] * nodes
        #: node -> views its watch still owes its listener
        self.owed: list[int] = [0] * nodes
        #: views of a change whose acknowledgement had not arrived yet
        self._pending: list = []
        self.bad = Violations()
        self.checked = 0

    # -- the model -----------------------------------------------------

    def path(self, node: int) -> str:
        return '%s/%s' % (self.parent, self.names[node])

    def registered(self, node: int, path: str, sid: int) -> None:
        """A create's acknowledgement (a set-up registration, or a
        returning node's): it names the model's path, and the
        ephemeral is session ``sid``'s."""
        self.checked += 1
        if path != self.path(node):
            self.bad.add('create-path', "node %d's create was "
                         'acknowledged as %r, the model says %r'
                         % (node, path, self.path(node)))
        self.owner[self.names[node]] = sid

    def _append(self, node: int | None, names) -> int:
        self.states.append(names)
        self.by.append(node)
        return len(self.states) - 1

    def left(self, node: int) -> int:
        """Node ``node``'s session close was acknowledged: its
        ephemeral is gone.  Returns the change's index k."""
        self.owed[node] = 0
        self.floor[node] = -1
        last = self.states[-1]
        if last is None:
            return self._append(node, None)
        self.owner.pop(self.names[node], None)
        return self._append(node, last - {self.names[node]})

    def returned(self, node: int, path: str, sid: int) -> int:
        """Node ``node``'s new session created its ephemeral again."""
        self.registered(node, path, sid)
        last = self.states[-1]
        if last is None:
            return self._append(node, None)
        return self._append(node, last | {self.names[node]})

    def unknown(self, node: int) -> int:
        """A change whose outcome is not known: nothing after it can
        be predicted."""
        self.owed[node] = 0
        return self._append(node, None)

    @property
    def changes(self) -> int:
        return len(self.states) - 1

    # -- the watchers ----------------------------------------------------

    def armed(self, node: int) -> None:
        self.owed[node] += 1

    def notified(self, node: int) -> None:
        self.owed[node] += 1

    def emitted(self, node: int, t: float, names, cversion: int,
                sent: int) -> int:
        """A view node ``node``'s ``'childrenChanged'`` listener was
        handed at ``t``.  ``sent``: changes SENT so far.  Returns the
        change count k it shows (-1 when it cannot be placed)."""
        self.checked += 1
        if self.owed[node] > 0:
            self.owed[node] -= 1
        k = cversion - self.base
        if not 0 <= k <= sent:
            self.bad.add('future-read', 'node %d was shown cversion %d '
                         '(%d changes) with %d changes sent'
                         % (node, cversion, k, sent))
            return -1
        names = list(names)
        got = frozenset(names)
        if k < len(self.states):
            self._compare(node, k, got, len(names), self.states[k])
        else:
            # the change is sent and not acknowledged yet (a watcher
            # can be told of a close before the closing session's own
            # reply is read): judged once it is (``settle``)
            self._pending.append((node, k, got, len(names)))
        if k <= self.floor[node]:
            self.bad.add('stale-view', 'node %d was shown change %d '
                         'after change %d' % (node, k, self.floor[node]))
        else:
            self.floor[node] = k
        self.views[node].append((t, k))
        if k > self.newest[node]:
            self.newest[node] = k
        return k

    def _compare(self, node, k, got, count, want) -> None:
        if count != len(got):
            self.bad.add('children', 'node %d after %d changes: %d '
                         'names, %d of them distinct'
                         % (node, k, count, len(got)))
        elif want is not None and got != want:
            self.bad.add('children', 'node %d after %d changes: missing '
                         '%s, unexpected %s'
                         % (node, k, sorted(want - got)[:3],
                            sorted(got - want)[:3]))

    def settle(self) -> None:
        """Judge the views that arrived before their change's
        acknowledgement did."""
        pending, self._pending = self._pending, []
        for node, k, got, count in pending:
            if k < len(self.states):
                self._compare(node, k, got, count, self.states[k])
            else:
                self.bad.add('future-read', 'node %d was shown %d '
                             'changes, %d were acknowledged'
                             % (node, k, len(self.states) - 1))

    def gap(self, node: int, what: str) -> None:
        self.bad.add('evicted', 'node %d: %s inside the run'
                     % (node, what))

    # -- after the window ------------------------------------------------

    def seen_at(self, node: int, k: int) -> float | None:
        """When ``node``'s listener was first handed a view that shows
        change ``k`` or a later one (None: never)."""
        return next((t for t, v in self.views[node] if v >= k), None)

    def finish(self) -> int:
        """Every (acknowledged change, OTHER node) pair: the node was
        shown that change or a later one; and no watch owes its
        listener a view.  Returns the pairs and the owed views that
        were not."""
        self.settle()
        missed = 0
        for k in range(1, len(self.states)):
            if self.states[k] is None:
                break
            for node in range(self.nodes):
                if node == self.by[k]:
                    continue
                self.checked += 1
                if self.seen_at(node, k) is None:
                    missed += 1
                    self.bad.add('missed-change', 'node %d was never '
                                 'shown change %d' % (node, k))
        for node, owed in enumerate(self.owed):
            self.checked += 1
            for _ in range(owed):
                missed += 1
                self.bad.add('missed-change', 'node %d was armed or told '
                             'of a change and its listener was handed '
                             'no view for it' % (node,))
        return missed

    def final(self, names, where: str) -> None:
        """The directory as listed after ``sync`` equals the model's."""
        self.checked += 1
        want = self.states[-1]
        if want is None:
            return
        names = list(names)
        got = frozenset(names)
        if got != want or len(names) != len(got):
            self.bad.add('final-children', '%s from %s: %d names, missing '
                         '%s, unexpected %s'
                         % (self.parent, where, len(names),
                            sorted(want - got)[:3], sorted(got - want)[:3]))

    def final_owner(self, node: int, owner: int | None,
                    where: str) -> None:
        """Node ``node``'s ephemeral as read back: there, and owned by
        the session that created it last (None: not there)."""
        self.checked += 1
        if self.states[-1] is None:
            return
        name = self.names[node]
        want = self.owner.get(name)
        if name not in self.states[-1]:
            if owner is not None:
                self.bad.add('ephemeral-owner', '%s is there from %s, its '
                             'node is down' % (name, where))
        elif owner != want:
            self.bad.add('ephemeral-owner', '%s from %s is owned by %s, '
                         'the model says %#x'
                         % (name, where, 'nobody (it is missing)'
                            if owner is None else '%#x' % (owner,),
                            want or 0))
