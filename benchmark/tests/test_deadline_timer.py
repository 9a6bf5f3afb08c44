"""The reader of the deadline queue's engagement span
(``client.deadline``: an arming or a firing of the one loop timer that
stands for every pending request's deadline): on a toy ring it gives
the value a hand count gives; a ring that dropped spans, an untraced
run, a program without the span (the parent of the PR that brought it:
one ``asyncio.wait_for`` a request) and a program without a ring give
None; and the toy read cell, traced, prints it with many requests to a
timer.  Everything here finds the metric by the file that reads it and
the read cell: entries that later PRs append to BENCHMARK.json, and
cells a merge puts on its list, move nothing."""

import json
import os
import tempfile

import harness
from conftest import ROOT, entry
from test_inside import read, ring, toy_run  # noqa: F401  (fixture)
from test_runs import members_alive, rehearse

from zkstream_tpu.utils import trace

with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    BENCH = json.load(f)

NAME = entry('client.ops_per_deadline_timer', 'hunt3_1k.read')


def test_entry_and_its_reader():
    (m,) = [m for m in BENCH['per_layer'] if m['name'] == NAME]
    (moved,) = [e for e in BENCH['end_to_end'] if e['name'] == m['moves']]
    (like,) = [x for x in BENCH['per_layer'] if x['name'] == entry(
        'client.sends_per_flush', 'hunt3_1k.read')]
    # the timer's entry was the read cell's alone when PR 27 appended
    # it; the flush's lists every cell of its end-to-end family
    assert 'hunt3_1k.read' in m['workloads']
    assert set(m['workloads']) <= set(like['workloads'])
    assert m == dict(like, name=NAME, workloads=m['workloads'])
    assert set(m['workloads']) <= set(moved['workloads'])
    path = harness.reader_path('layer_metrics', NAME)
    assert path and path.endswith('client.ops_per_deadline_timer.py')


def test_reader_on_a_toy_ring(ring):  # noqa: F811
    ring.totals['client.submit'] = [27_000, 200_000_000]
    ring.totals['client.deadline'] = [27, 90_000]
    run = toy_run()
    assert read(NAME, run) == 1000.0
    # a ring that wrapped is not the window's
    ring.dropped = 1
    assert read(NAME, run) is None
    ring.dropped = 0
    # an untraced run
    run.trace = None
    assert read(NAME, run) is None
    # the parent: requests counted, no ``client.deadline`` at all
    del ring.totals['client.deadline']
    assert read(NAME, toy_run()) is None
    # a window in which the timer was never touched divides nothing
    ring.totals['client.deadline'] = [0, 0]
    assert read(NAME, toy_run()) is None
    # no request counted: nothing to divide
    ring.totals['client.deadline'] = [3, 9_000]
    del ring.totals['client.submit']
    assert read(NAME, toy_run()) is None


def test_reader_on_a_program_without_a_ring(monkeypatch):
    monkeypatch.delattr(trace, 'host_ring')
    assert read(NAME, toy_run()) is None


def test_toy_read_cell_traced_prints_requests_per_timer():
    with tempfile.TemporaryDirectory(prefix='benchtest-') as tmp:
        r, out = rehearse(tmp, '--one', 'hunt3_1k.read',
                          '--seed', str(2 ** 31 + 27),
                          '--seconds', '3', '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    got = {k: v['value'] for k, v in out['metrics'].items()}
    assert NAME in got, sorted(got)
    # every deadline is 30 s away: the timer moves once a compaction
    # of the queue's heap, not once a request
    assert got[NAME] >= 10
    assert not members_alive()
