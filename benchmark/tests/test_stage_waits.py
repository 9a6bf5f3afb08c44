"""PR 35's thirteen entries — a request's latency by stage, the two
synchronous stretches around its await, the collector's pauses (whole,
and the part that fell into ``ingest.route``), and the leader's
``control`` / ``repl_ack`` phases with the coverage of its CPU: each
reader on a toy ring or toy ``mntr`` rows gives the value a hand count
gives; a ring that dropped spans, an untraced run, a program without
the totals, phases or row (the parent of the PR that brought them) give
None and never raise; and every cell on an entry's ``workloads`` list,
run toy and traced, prints a number for it."""

import json
import os
import tempfile

import pytest
from conftest import ROOT, entries, entry
from test_inside import (entries_read_by, member_rows, read,  # noqa: F401
                         ring, toy_run)
from test_runs import members_alive, rehearse

from zkstream_tpu.utils import trace

with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    BENCH = json.load(f)

READ = ['hunt3_1k.read', 'solrconf3.load']
WRITE = ['hunt3_1k.write']
CONVERGE = ['discovery3.relist', 'confcache3.push',
            'helixview3.viewchange']

#: (reader file, layer, source, moves, better, the cells the entry
#: listed when PR 35 appended it: a later merge may add cells, and names
#: the entry as it likes)
ENTRIES = [
    ('client.cork_wait_us', 'client session', 'program_span',
     'read_p95_ms', 'lower', READ),
    ('client.wire_wait_us', 'client session', 'program_span',
     'read_p95_ms', 'lower', READ),
    ('client.tick_wait_us', 'client session', 'program_span',
     'read_p95_ms', 'lower', READ),
    ('client.wake_wait_us', 'client session', 'program_span',
     'read_p95_ms', 'lower', READ),
    ('client.await_share', 'client session', 'program_span',
     'ops_per_s.read', 'lower', READ),
    ('gc.pause_share', 'client session', 'program_span',
     'ops_per_s.read', 'lower', READ),
    ('gc.pause_share', 'client session', 'program_span',
     'write_p95_ms', 'lower', WRITE),
    ('gc.pause_share', 'client session', 'program_span',
     'converge_p50_ms', 'lower', CONVERGE),
    ('ingest.route_gc_us_per_frame', 'fleet ingest', 'program_span',
     'ops_per_s.read', 'lower', READ),
    ('server.control_share', 'server tick', 'program_counter',
     'write_p95_ms', 'lower', WRITE),
    ('server.control_share', 'server tick', 'program_counter',
     'converge_p50_ms', 'lower', CONVERGE),
    ('server.repl_ack_share', 'replication', 'program_counter',
     'write_p95_ms', 'lower', WRITE),
    ('server.phase_coverage', 'server tick', 'program_counter',
     'write_p95_ms', 'higher', WRITE),
]
#: the thirteen by the name they stand under today, found by reader and
#: cell (never by where they stand in the file, nor by a suffix)
NAMES = [entry(e[0], e[5][0]) for e in ENTRIES]
RING_ENTRIES = [n for n, e in zip(NAMES, ENTRIES) if e[2] == 'program_span']
MNTR_ENTRIES = [n for n, e in zip(NAMES, ENTRIES)
                if e[2] == 'program_counter']


def test_the_thirteen_entries_and_their_readers():
    by_name = {m['name']: m for m in BENCH['per_layer']}
    e2e = {m['name']: m for m in BENCH['end_to_end']}
    readers = {e[0] for e in ENTRIES}
    layers = {m['layer'] for m in BENCH['per_layer']
              if m['name'] not in NAMES}
    assert len(BENCH['per_layer']) <= 128
    assert len(set(NAMES)) == len(ENTRIES) == 13
    for name, (reader, layer, source, moves, better, cells) in \
            zip(NAMES, ENTRIES):
        m = by_name[name]
        assert name in entries_read_by(reader)
        assert (m['layer'], m['source'], m['moves'], m['better']) == (
            layer, source, moves, better)
        # every cell it was given reads it through this entry still
        assert {entry(reader, c) for c in cells} == {name}
        assert layer in layers          # a name the file had already
        assert set(m['workloads']) <= set(e2e[moves]['workloads'])
        assert m['unit'] == ('us' if '_us' in name else '%')
    # one entry a reader and family: no suffixed copy a cell
    for reader in readers:
        got = entries(reader)
        assert len({m['moves'] for m in got}) == len(got)


def fill(ring):  # noqa: F811
    """A toy traced window of 4 s: 1,000 ops, three ticks."""
    for name, ns in (('client.cork_wait', 2_000_000_000),
                     ('client.wire_wait', 80_000_000_000),
                     ('client.tick_wait', 9_000_000_000),
                     ('client.wake_wait', 1_500_000_000)):
        ring.totals[name] = [1000, ns]
    ring.totals['client.prepare'] = [1000, 120_000_000]     # 0.12 s
    ring.totals['client.resume'] = [1000, 80_000_000]       # 0.08 s
    ring.totals['gc.pause'] = [40, 300_000_000]             # 0.3 s
    ring.totals['gc.pause@ingest.route'] = [9, 1_800_000]
    for n, frames in enumerate((100, 200, 300), 1):
        ring.note('ingest.route', kind='host', parent='ingest.tick',
                  tick=n, t0_ns=0, t1_ns=1_000_000, duration_ms=1.0)
        ring.note('ingest.tick', kind='host', tick=n, batch=frames,
                  duration_ms=3.0)


R, W, C = 'hunt3_1k.read', 'hunt3_1k.write', 'discovery3.relist'
WANT = {entry('client.cork_wait_us', R): 2000.0,
        entry('client.wire_wait_us', R): 80000.0,
        entry('client.tick_wait_us', R): 9000.0,
        entry('client.wake_wait_us', R): 1500.0,
        entry('client.await_share', R): 5.0,
        entry('gc.pause_share', R): 7.5,
        entry('gc.pause_share', W): 7.5,
        entry('gc.pause_share', C): 7.5,
        entry('ingest.route_gc_us_per_frame', R): 3.0}


def test_ring_readers_on_a_toy_ring(ring):  # noqa: F811
    assert sorted(WANT) == sorted(RING_ENTRIES)
    fill(ring)
    run = toy_run()
    for name, want in WANT.items():
        assert read(name, run) == pytest.approx(want), name
    nothing = [None] * len(WANT)
    # a ring that wrapped is not the window's
    ring.dropped = 1
    assert [read(n, run) for n in WANT] == nothing
    ring.dropped = 0
    # an untraced run
    run.trace = None
    assert [read(n, run) for n in WANT] == nothing
    # no collection fell into a route: 0, not nothing
    del ring.totals['gc.pause@ingest.route']
    assert read(entry('ingest.route_gc_us_per_frame', R), toy_run()) == 0.0
    # half of a pair is not the pair
    del ring.totals['client.resume']
    assert read(entry('client.await_share', R), toy_run()) is None
    # an op count of zero divides nothing
    ring.totals['client.cork_wait'] = [0, 0]
    assert read(entry('client.cork_wait_us', R), toy_run()) is None


def test_ring_readers_on_the_parents_ring(ring):  # noqa: F811
    """The parent's program: a ring with its spans and its totals, and
    none of this PR's."""
    ring.totals['client.submit'] = [1000, 600_000_000]
    ring.totals['client.rx'] = [1000, 200_000_000]
    ring.note('ingest.route', kind='host', parent='ingest.tick', tick=1,
              t0_ns=0, t1_ns=1_000_000, duration_ms=1.0)
    ring.note('ingest.tick', kind='host', tick=1, batch=100,
              duration_ms=3.0)
    assert [read(n, toy_run()) for n in WANT] == [None] * len(WANT)


def test_ring_readers_on_a_program_without_a_ring(monkeypatch):
    monkeypatch.delattr(trace, 'host_ring')
    assert [read(n, toy_run()) for n in WANT] == [None] * len(WANT)


def test_leader_readers_on_toy_mntr_rows():
    run = toy_run()             # leader = member 1, window 20 s
    start = {'decode_apply': [40.0] * 10, 'control': [30.0] * 5}
    before = [dict(member_rows(start, uptime_ms=5_000),
                   zk_process_cpu_ms='3000.0') for _ in range(3)]
    # the leader's window: control 8 s, repl_ack 1 s, repl_push 2 s,
    # decode_apply 3 s = 14 s of phases in 20 s, on 17.5 s of CPU
    m1 = {'decode_apply': start['decode_apply'] + [0.3] * 10000,
          'control': start['control'] + [0.8] * 10000,
          'repl_ack': [0.05] * 20000, 'repl_push': [0.2] * 10000}
    # a follower busier than the leader: not what these read
    m0 = dict(start)
    m0['forward_rpc'] = [4.0] * 4500
    after = [dict(member_rows(m0, uptime_ms=25_000),
                  zk_process_cpu_ms='9000.0'),
             dict(member_rows(m1, uptime_ms=25_000),
                  zk_process_cpu_ms='20500.0'),
             dict(member_rows(start, uptime_ms=25_000),
                  zk_process_cpu_ms='4000.0')]
    run.mntr_before, run.mntr_after = before, after
    assert read(entry('server.control_share', W),
                run) == pytest.approx(40.0)
    assert read(entry('server.control_share', C),
                run) == pytest.approx(40.0)
    assert read(entry('server.repl_ack_share', W),
                run) == pytest.approx(5.0)
    assert read(entry('server.phase_coverage', W),
                run) == pytest.approx(100.0 * 14_000 / 17_500)
    # the parent's program: the phases and the row are not there
    for rows in before + after:
        del rows['zk_process_cpu_ms']
        for key in [k for k in rows if 'phase="control"' in k
                    or 'phase="repl_ack"' in k]:
            del rows[key]
    assert [read(n, run) for n in MNTR_ENTRIES] == [None] * 4
    # a member that did not answer, or no member at all
    run.mntr_before, run.mntr_after = [{}, {}, {}], [{}, {}, {}]
    assert [read(n, run) for n in MNTR_ENTRIES] == [None] * 4
    run.mntr_before, run.mntr_after = [], []
    assert [read(n, run) for n in MNTR_ENTRIES] == [None] * 4


@pytest.mark.parametrize('cell', READ + WRITE + CONVERGE)
def test_toy_cell_traced_prints_its_entries(cell):
    e = {r: entry(r, cell) for r, *_rest, cells in ENTRIES if cell in cells}
    want = set(e.values())
    with tempfile.TemporaryDirectory(prefix='benchtest-') as tmp:
        r, out = rehearse(tmp, '--one', cell, '--seed', str(2 ** 31 + 35),
                          '--seconds', '3', '--trace', '1', timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    got = {k: v['value'] for k, v in out['metrics'].items()}
    assert want <= set(got), sorted(want - set(got))
    assert all(got[n] >= 0 for n in want)
    assert 0 <= got[e['gc.pause_share']] < 50
    if cell in READ:
        waits = [got[e['client.%s_wait_us' % s]]
                 for s in ('cork', 'wire', 'tick', 'wake')]
        assert all(w > 0 for w in waits)
        assert 0 < got[e['client.await_share']] < 50
    if cell in WRITE:
        assert 0 < got[e['server.control_share']] < 100
        assert 0 < got[e['server.repl_ack_share']] < 100
        assert 0 < got[e['server.phase_coverage']] <= 150
    assert not members_alive()
