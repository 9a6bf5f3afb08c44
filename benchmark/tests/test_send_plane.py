"""The readers of the send side's engagement span (``client.flush``,
the tick of the transport tier that one loop's client sessions share):
each on a toy ring gives the value a hand count gives; a ring that
dropped spans, an untraced run, a program without the span (the parent
of the PR that brought it) and a program without a ring give None; and
the toy cells, traced, print them with a fleet's worth of requests to
a flush in the read cell."""

import json
import os
import tempfile

import harness
import pytest
from conftest import ROOT
from test_inside import (entries_read_by, read, ring,  # noqa: F401
                         toy_run)
from test_runs import members_alive, rehearse

from zkstream_tpu.utils import trace

with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    BENCH = json.load(f)

#: PR 25's three entries, by name (wherever they stand, whatever cells
#: later PRs put on their lists)
NAMES = ['client.flush_share.read', 'client.sends_per_flush.read',
         'client.sends_per_flush.write']
READERS = ('client.flush_share', 'client.sends_per_flush')


def test_entries_and_their_readers():
    by_name = {m['name']: m for m in BENCH['per_layer']}
    cells = {w['name'] for w in BENCH['workloads']}
    e2e = {m['name']: m for m in BENCH['end_to_end']}
    assert set(NAMES) <= set(entries_read_by(*READERS))
    for name in entries_read_by(*READERS):
        m = by_name[name]
        assert m['layer'] == 'client session'
        assert m['source'] == 'program_span'
        assert set(m['workloads']) <= cells
        assert set(m['workloads']) <= set(e2e[m['moves']]['workloads'])
    assert 'hunt3_1k.read' in by_name[NAMES[0]]['workloads']
    assert 'hunt3_1k.read' in by_name[NAMES[1]]['workloads']
    assert by_name[NAMES[2]]['workloads'] == ['hunt3_1k.write']


def test_flush_readers_on_a_toy_ring(ring):  # noqa: F811
    ring.totals['client.submit'] = [6000, 600_000_000]
    ring.totals['client.flush'] = [12, 100_000_000]     # 0.1 s of 4 s
    run = toy_run()
    assert read('client.flush_share.read', run) == pytest.approx(2.5)
    assert read('client.sends_per_flush.read', run) == 500.0
    assert read('client.sends_per_flush.write', run) == 500.0
    # a ring that wrapped is not the window's
    ring.dropped = 1
    assert [read(n, run) for n in NAMES] == [None] * 3
    ring.dropped = 0
    # an untraced run
    run.trace = None
    assert [read(n, run) for n in NAMES] == [None] * 3
    # the parent: requests counted, no ``client.flush`` at all
    del ring.totals['client.flush']
    assert [read(n, toy_run()) for n in NAMES] == [None] * 3
    # a flush span that never closed a tick divides nothing
    ring.totals['client.flush'] = [0, 0]
    assert read('client.sends_per_flush.read', toy_run()) is None


def test_flush_readers_on_a_program_without_a_ring(monkeypatch):
    monkeypatch.delattr(trace, 'host_ring')
    assert [read(n, toy_run()) for n in NAMES] == [None] * 3


@pytest.mark.parametrize('cell,metrics', [
    ('hunt3_1k.read', ['client.flush_share.read',
                       'client.sends_per_flush.read']),
    ('hunt3_1k.write', ['client.sends_per_flush.write'])])
def test_toy_cell_traced_prints_the_flush_metrics(cell, metrics):
    with tempfile.TemporaryDirectory(prefix='benchtest-') as tmp:
        r, out = rehearse(tmp, '--one', cell, '--seed', str(2 ** 31 + 25),
                          '--seconds', '3', '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    got = {k: v['value'] for k, v in out['metrics'].items()}
    assert set(metrics) <= set(got), sorted(set(metrics) - set(got))
    label = cell.rsplit('.', 1)[1]
    assert got['client.sends_per_flush.' + label] >= 1
    if label == 'read':
        # the toy fleet's lanes wake together: many requests a flush
        assert got['client.sends_per_flush.read'] > 2
        assert 0 < got['client.flush_share.read'] \
            < got['client.loop_busy_share.read'] <= 100
    assert not members_alive()
