"""PR 52's four entries — the client loop's whole turn split from
inside the program (the caller's gap, asyncio's gaps, the coverage of
the window by named spans + gaps + pauses) and the leader's
loop-thread share of its process's CPU: each reader on a toy ring or
toy ``mntr`` rows gives the value a hand count gives; a ring without
the totals, a ring that dropped spans, an untraced run, rows without
``zk_loop_cpu_ms`` (the parent of the PR that brought them) give None
and never raise; and the toy ``hunt3_1k.read`` and ``hunt3_1k.write``
cells, traced, print a number for each.  An entry is found by the file
that reads it and the cell that lists it — never by name, count or
position."""

import json
import os
import tempfile

import pytest
from conftest import ROOT, entries, entry
from test_inside import read, ring, toy_run  # noqa: F401  (ring: a fixture)
from test_runs import members_alive, rehearse

from zkstream_tpu.utils import trace

READ = 'hunt3_1k.read'
WRITE = 'hunt3_1k.write'
FOUR = [READ, 'hunt3_1k.read_deep', 'solrconf3.load', 'ycsb3.workloadb']
RING_READERS = ('client.loop_app_share', 'client.loop_switch_share',
                'client.loop_coverage')

#: (reader file, layer, source, moves, better, the cells the entry
#: listed when PR 52 appended it)
ENTRIES = [
    ('client.loop_app_share', 'client session', 'program_span',
     'ops_per_s.read', 'lower', FOUR),
    ('client.loop_switch_share', 'client session', 'program_span',
     'ops_per_s.read', 'lower', FOUR),
    ('client.loop_coverage', 'client session', 'program_span',
     'ops_per_s.read', 'higher', FOUR + ['ycsb3_latest.workloadd']),
    ('server.loop_cpu_share', 'server tick', 'program_counter',
     'write_p95_ms', 'higher', [WRITE]),
]


@pytest.mark.parametrize('reader,layer,source,moves,better,cells',
                         ENTRIES, ids=[e[0] for e in ENTRIES])
def test_the_entry_is_in_the_benchmark(reader, layer, source, moves,
                                       better, cells):
    (m,) = entries(reader)
    assert (m['layer'], m['source'], m['moves'], m['better'], m['unit']) \
        == (layer, source, moves, better, '%')
    assert set(cells) <= set(m['workloads'])
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    reports = next(e for e in bench['end_to_end']
                   if e['name'] == moves)['workloads']
    assert set(m['workloads']) <= set(reports)


def _book(r) -> None:
    """A 4 s window as the program books it: 1.0 s idle and 1.6 s of
    other spans (named 2.6 s), the caller's gap 0.4 s, asyncio's gaps
    0.5 + 0.3 + 0.1 s, 0.06 s of collections in gaps = 3.96 s."""
    r.totals['loop.idle'] = [900, 1_000_000_000]
    r.totals['loop.named'] = [9000, 2_600_000_000]
    r.totals['loop.gap@client.resume>client.prepare'] = [
        4000, 400_000_000]
    r.totals['loop.gap@client.submit>client.resume'] = [
        3000, 500_000_000]
    r.totals['loop.gap@ingest.tick>client.resume'] = [900, 300_000_000]
    r.totals['loop.gap@client.flush>loop.idle'] = [900, 100_000_000]
    r.totals['gc.pause'] = [12, 90_000_000]
    r.totals['gc.pause@loop.gap'] = [8, 60_000_000]
    r.totals['gc.pause@ingest.route'] = [4, 30_000_000]


def test_ring_readers_on_a_toy_ring(ring):
    _book(ring)
    run = toy_run()             # a traced window of 4 s
    assert read(entry('client.loop_app_share', READ),
                run) == pytest.approx(10.0)
    assert read(entry('client.loop_switch_share', READ),
                run) == pytest.approx(22.5)
    assert read(entry('client.loop_coverage', READ),
                run) == pytest.approx(99.0)
    # a window without a collection in a gap: the total is not there
    del ring.totals['gc.pause@loop.gap']
    assert read(entry('client.loop_coverage', READ),
                run) == pytest.approx(97.5)
    # a paced caller: its time is in other pairs, the split says None
    # for the one it has not, the rest reads on
    del ring.totals['loop.gap@client.resume>client.prepare']
    assert read(entry('client.loop_app_share', READ), run) is None
    assert read(entry('client.loop_switch_share', READ),
                run) == pytest.approx(22.5)
    assert read(entry('client.loop_coverage', READ),
                run) == pytest.approx(87.5)


def test_ring_readers_find_nothing_without_the_totals(ring, monkeypatch):
    names = [entry(r, READ) for r in RING_READERS]
    nothing = [None] * len(names)
    run = toy_run()
    # the parent's ring: every total it had, none of the loop's
    ring.totals['client.resume'] = [1000, 600_000_000]
    ring.totals['client.prepare'] = [1000, 200_000_000]
    ring.totals['gc.pause'] = [3, 9_000_000]
    assert [read(n, run) for n in names] == nothing
    # a loop without the hook: no gap was booked
    _book(ring)
    for k in [k for k in ring.totals if k.startswith('loop.gap@')]:
        del ring.totals[k]
    assert [read(n, run) for n in names] == nothing
    # a ring that dropped spans, an untraced run, no ring at all
    _book(ring)
    assert None not in [read(n, run) for n in names]
    ring.dropped = 1
    assert [read(n, run) for n in names] == nothing
    ring.dropped = 0
    run.trace = None
    assert [read(n, run) for n in names] == nothing
    monkeypatch.delattr(trace, 'host_ring')
    assert [read(n, toy_run()) for n in names] == nothing


def test_the_leaders_loop_share_on_toy_mntr_rows():
    name = entry('server.loop_cpu_share', WRITE)
    run = toy_run()             # leader = member 1
    before = [{'zk_process_cpu_ms': '3000.0', 'zk_loop_cpu_ms': '2500.0'}
              for _ in range(3)]
    after = [{'zk_process_cpu_ms': '9000.0', 'zk_loop_cpu_ms': '4000.0'},
             {'zk_process_cpu_ms': '20500.0', 'zk_loop_cpu_ms': '16500.0'},
             {'zk_process_cpu_ms': '4000.0', 'zk_loop_cpu_ms': '3400.0'}]
    run.mntr_before, run.mntr_after = before, after
    assert read(name, run) == pytest.approx(100.0 * 14_000 / 17_500)
    # the parent's rows: the process's CPU, not the thread's
    for rows in before + after:
        del rows['zk_loop_cpu_ms']
    assert read(name, run) is None
    # a process that burned nothing, a member that did not answer, none
    run.mntr_before = run.mntr_after = after
    assert read(name, run) is None
    run.mntr_before, run.mntr_after = [{}, {}, {}], [{}, {}, {}]
    assert read(name, run) is None
    run.mntr_before, run.mntr_after = [], []
    assert read(name, run) is None


@pytest.mark.parametrize('cell', [READ, WRITE])
def test_toy_cell_traced_prints_its_entries(cell):
    e = {r: entry(r, cell) for r, *_rest, cells in ENTRIES if cell in cells}
    assert e
    with tempfile.TemporaryDirectory(prefix='benchtest-') as tmp:
        r, out = rehearse(tmp, '--one', cell, '--seed', str(2 ** 31 + 52),
                          '--seconds', '3', '--trace', '1', timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    got = {k: v['value'] for k, v in out['metrics'].items()}
    assert set(e.values()) <= set(got), set(e.values()) - set(got)
    if cell == READ:
        app = got[e['client.loop_app_share']]
        switch = got[e['client.loop_switch_share']]
        assert 90.0 <= got[e['client.loop_coverage']] <= 102.0
        assert 0.0 < app < 60.0 and 0.0 < switch < 60.0
        # a closed loop is busy: the gaps are no small part of it
        busy = got[entry('client.loop_busy_share', cell)]
        assert app + switch < busy
    else:
        assert 0.0 < got[e['server.loop_cpu_share']] <= 100.0
    assert not members_alive()
