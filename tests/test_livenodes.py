"""One wide directory that every session registers in AND watches,
through a rolling restart with real session closes and new sessions
(the deployment ``livenodes3`` at toy size): an in-process 3-voter
ensemble, 24 sessions through ONE ``FleetIngest``, each holding one
ephemeral under ``/live_nodes`` and arming
``client.watcher('/live_nodes').on('childrenChanged')``; ten nodes
leave (``client.close()``: the close removes the ephemeral) and return
(a new ``Client`` on the same member) in turn.

Held against the benchmark's plain reference of this deployment
(``benchmark/reference_live.py``, which imports nothing of the
program): every view a listener was handed, every (change, other
node) pair, the final tree from another member — and what the cell's
readers read: the members' children-reply cache and the ingest's
``names_routed``."""

from __future__ import annotations

import asyncio
import os
import random
import sys
import time

import pytest

from helpers import wait_until
from zkstream_tpu import Client, CreateFlag
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.server import ZKEnsemble

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmark')
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference_live  # noqa: E402

PARENT = '/live_nodes'
N = 24


class Cluster:
    """The toy deployment; ``restart(k)`` is node k's leave and
    return."""

    async def start(self, seed: int):
        self.ens = await ZKEnsemble(3).start()
        self.ports = [s.port for s in self.ens.servers]
        self.ingest = FleetIngest(
            placement='host', max_frames=8,
            min_len=1024, max_data=256, bypass_bytes=0, warm='block')
        for bp in (8, 16, 32):
            await self.ingest.prewarm(bp)
        self.chk = reference_live.LiveChecker(seed, N, PARENT)
        self.sent = 0
        self.gaps: list = []
        self.clients: list = [None] * N
        boot = self._client(0, ingest=False)
        await boot.wait_connected(timeout=5)
        await boot.create(PARENT, b'')
        await boot.close()
        for n in range(N):
            self.clients[n] = self._client(n)
        await asyncio.gather(*[c.wait_connected(timeout=10)
                               for c in self.clients])
        for n, c in enumerate(self.clients):
            path = await c.create(self.chk.path(n), b'',
                                  flags=CreateFlag.EPHEMERAL)
            self.chk.registered(n, path, self._sid(c))
        await asyncio.gather(*[c.sync(PARENT) for c in self.clients])
        for n, c in enumerate(self.clients):
            self._arm(n, c)
        await self._shown(0, None)
        return self

    def _client(self, node: int, ingest: bool = True) -> Client:
        c = Client(servers=[('127.0.0.1', self.ports[node % 3])],
                   shuffle_backends=False, session_timeout=30000,
                   ingest=self.ingest if ingest else None, max_spares=0)
        c.on('expire', lambda: self.gaps.append((node, 'expire')))
        c.start()
        return c

    @staticmethod
    def _sid(c) -> int:
        sid = c.session.session_id
        return int(sid, 16) if isinstance(sid, str) else int(sid)

    def _arm(self, node: int, c) -> None:
        w = c.watcher(PARENT)
        notify = w.notify

        def told(evt):
            self.chk.notified(node)
            notify(evt)
        w.notify = told
        self.chk.armed(node)
        w.on('childrenChanged', lambda children, stat: self.chk.emitted(
            node, time.perf_counter(), children, stat.cversion,
            self.sent))

    async def _shown(self, k: int, but) -> None:
        await wait_until(lambda: all(
            self.chk.newest[n] >= k for n in range(N) if n != but),
            timeout=10)

    async def restart(self, node: int) -> None:
        self.sent += 1
        await self.clients[node].close()
        k = self.chk.left(node)
        self.chk.settle()
        await self._shown(k, node)
        self.sent += 1
        c = self.clients[node] = self._client(node)
        await c.wait_connected(timeout=10)
        path = await c.create(self.chk.path(node), b'',
                              flags=CreateFlag.EPHEMERAL)
        k = self.chk.returned(node, path, self._sid(c))
        self.chk.settle()
        self._arm(node, c)
        await self._shown(k, None)

    async def finish(self) -> None:
        """The checks after the run: every pair, then the tree from a
        member that did not take the create."""
        self.chk.finish()
        readers = [self._client(m, ingest=False) for m in range(3)]
        for m, r in enumerate(readers):
            await r.wait_connected(timeout=5)
            await r.sync(PARENT)
            names, _stat = await r.list(PARENT)
            self.chk.final(names, 'member %d' % (m,))
        for n in range(N):
            m = (n + 1) % 3
            st = await readers[m].stat(self.chk.path(n))
            self.chk.final_owner(n, st.ephemeralOwner, 'member %d' % (m,))
        for r in readers:
            await r.close()

    async def stop(self) -> None:
        await asyncio.gather(*[c.close() for c in self.clients
                               if c is not None],
                             return_exceptions=True)
        self.ingest.close()
        await self.ens.stop()


@pytest.mark.parametrize('seed', [5, 2 ** 31 + 38])
async def test_rolling_restart_against_the_plain_reference(
        event_loop, seed):
    cl = await Cluster().start(seed)
    try:
        order = list(range(N))
        random.Random(seed).shuffle(order)
        hits0 = sum(s.children_cache.hits for s in cl.ens.servers)
        names0 = cl.ingest.names_routed
        for node in order[:10]:
            await cl.restart(node)
        await cl.finish()
        chk = cl.chk
        assert not chk.bad.first, chk.bad.first
        assert not cl.gaps
        assert chk.changes == 20 and chk.base == N
        # every other node was handed a view of every change: 20
        # changes x 23 nodes, the first lists, the returning nodes'
        views = sum(len(v) for v in chk.views)
        assert views == N + 20 * (N - 1) + 10
        # the names went through the ingest's list parse ...
        assert cl.ingest.names_routed - names0 >= 20 * (N - 1) * (N - 1)
        # ... and the members encoded a change's list once, not once
        # an asker: 3 members, 20 changes
        hits = sum(s.children_cache.hits for s in cl.ens.servers) - hits0
        misses = sum(s.children_cache.misses for s in cl.ens.servers)
        assert hits >= 20 * (N - 1) - 3 * 20 - 10
        assert misses <= 3 * (1 + 20) + N
    finally:
        await cl.stop()


async def test_a_closed_nodes_ephemeral_is_gone_at_every_member(
        event_loop):
    cl = await Cluster().start(9)
    try:
        name = cl.chk.names[4]
        sid = cl._sid(cl.clients[4])
        cl.sent += 1
        await cl.clients[4].close()
        k = cl.chk.left(4)
        await cl._shown(k, 4)
        cl.clients[4] = None
        for srv in cl.ens.servers:
            assert PARENT + '/' + name not in srv.store.nodes
            assert name not in srv.store.nodes[PARENT].children
        assert sid not in {s.id for s in cl.ens.db.sessions.values()
                           if not s.closed}
        # the closed connection's slot left the ingest with it
        assert len(cl.ingest._slots) == N - 1
        cl.chk.finish()
        assert not cl.chk.bad.first, cl.chk.bad.first
    finally:
        await cl.stop()


async def test_the_reference_catches_a_ghost_and_a_stale_view(
        event_loop):
    """The same live system, its views tampered with on their way to
    the reference: a closed node's name put back, and a list handed
    out a second time."""
    cl = await Cluster().start(11)
    try:
        await cl.restart(3)
        chk = cl.chk
        assert not chk.bad.first
        gone = sorted(chk.states[1])          # node 3 is down here
        chk.emitted(0, time.perf_counter(), gone + [chk.names[3]],
                    chk.base + 1, cl.sent)
        assert chk.bad.by_kind == {'children': 1, 'stale-view': 1}
        chk.emitted(1, time.perf_counter(), sorted(chk.states[2]),
                    chk.base + 2, cl.sent)
        assert chk.bad.by_kind['stale-view'] == 2
        chk.emitted(2, time.perf_counter(), sorted(chk.states[2]),
                    chk.base + 3, cl.sent)
        assert chk.bad.by_kind['future-read'] == 1
    finally:
        await cl.stop()
