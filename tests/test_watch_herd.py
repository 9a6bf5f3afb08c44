"""A herd of one-shot watchers on ONE large znode through ONE fleet
ingest whose tick memory is smaller than the burst.

Every change of the znode notifies N ``ZKWatcher``s at once; each
re-arms with a ``getData`` that returns the whole document, so the N
replies land in the ingest together — each all its slot holds, so each
a header row of ``min_len`` bytes (io/ingest.py, "Size classes"): the
90 KiB behind it stay in the slot.  With ``TICK_BYTES`` below N such
rows a tick dispatches what fits and leaves the rest — whole frames —
in their slots for the follow-up tick (``ticks_full`` counts those
ticks).  Held against a plain
dictionary model (version -> bytes) and against the same run on the
per-socket scalar drain: every watcher emits every version once, in
order, with the model's bytes over their whole length.
"""

from __future__ import annotations

import asyncio
import random

import pytest

from helpers import wait_until
from zkstream_tpu import Client
from zkstream_tpu.io.ingest import FleetIngest
from zkstream_tpu.server import ZKEnsemble

PATH = '/view'
N = 12              # watchers, four a member
VERSIONS = 5
BASE = 90 * 1024    # version v holds BASE + 160 v bytes


def payload(seed: int, version: int) -> bytes:
    return random.Random('%d/%d' % (seed, version)).randbytes(
        BASE + 160 * version)


async def herd(through_ingest: bool, seed: int):
    """Run the herd; returns every watcher's emissions and the ingest."""
    model = {v: payload(seed, v) for v in range(VERSIONS + 1)}
    ens = await ZKEnsemble(3).start()
    ports = [s.port for s in ens.servers]
    ingest = None
    if through_ingest:
        ingest = FleetIngest(placement='host',
                             max_frames=4, min_len=1024, max_data=256,
                             bypass_bytes=0, warm='block')
        # four rows a dispatch (its bucket is the [8, min_len]
        # floor), one dispatch a tick
        ingest.DISPATCH_BYTES = 4 << 10
        ingest.TICK_BYTES = 8 << 10
    writer = Client(address='127.0.0.1', port=ports[0],
                    session_timeout=30000)
    clients = [Client(servers=[('127.0.0.1', ports[i % 3])],
                      shuffle_backends=False, ingest=ingest,
                      session_timeout=30000, max_spares=0)
               for i in range(N)]
    seen: list[list] = [[] for _ in range(N)]
    try:
        writer.start()
        await writer.wait_connected(timeout=5)
        await writer.create(PATH, model[0])
        for c in clients:
            c.start()
        await asyncio.gather(*[c.wait_connected(timeout=10)
                               for c in clients])
        await asyncio.gather(*[c.sync(PATH) for c in clients])
        for i, c in enumerate(clients):
            c.watcher(PATH).on(
                'dataChanged', lambda data, stat, i=i: seen[i].append(
                    (stat.version, stat.dataLength, data)))
        await wait_until(lambda: all(len(s) == 1 for s in seen),
                         timeout=20)
        for v in range(1, VERSIONS + 1):
            stat = await writer.set(PATH, model[v])
            assert stat.version == v
            await wait_until(lambda: all(len(s) == v + 1 for s in seen),
                             timeout=20)
    finally:
        await asyncio.gather(*[c.close() for c in clients + [writer]],
                             return_exceptions=True)
        await ens.stop()
        if ingest is not None:
            ingest.close()
    return seen, model, ingest


@pytest.mark.timeout(240)
@pytest.mark.parametrize('seed', [7, 2 ** 31 + 11])
async def test_every_watcher_emits_every_version_once_in_order(seed):
    got, model, ingest = await herd(True, seed)
    want, _model, _none = await herd(False, seed)
    for i, views in enumerate(got):
        assert [v for v, _n, _d in views] == list(range(VERSIONS + 1)), i
        for version, length, data in views:
            assert length == len(data) == len(model[version]), (i, version)
            assert data == model[version], (i, version)
    assert got == want          # the scalar drain saw the same
    # the bursts did not fit a tick: whole frames waited in their slots
    # and every one of them was delivered by a follow-up tick, all on
    # the tick program
    assert ingest.ticks_full > 0
    assert ingest.ticks and not (ingest.ticks_scalar
                                 or ingest.ticks_warming
                                 or ingest.ticks_frag)
    assert ingest.bytes_recopied == 0
    assert len(ingest._arena) == 8 << 10
    # the re-reads were header rows (all of them, but for one that a
    # slow machine lets a ping's reply share a slot with): a document's
    # bytes stayed home
    assert ingest.rows_headed >= N * VERSIONS
    assert ingest.bytes_kept_home >= ingest.rows_headed * (BASE - 1024)


async def test_full_ticks_are_exported(monkeypatch):
    """``ticks_full`` is a series of the ingest's collector, beside
    PR 30's five."""
    from zkstream_tpu.utils.metrics import Collector

    ingest = FleetIngest(placement='host', bypass_bytes=0, warm='block',
                         min_len=256)
    col = Collector()
    ingest.bind_metrics(col)
    ingest.ticks_full = 3
    assert 'zkstream_ingest_full_ticks 3' in col.expose()
    ingest.close()
