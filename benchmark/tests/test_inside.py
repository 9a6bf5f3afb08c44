"""The readers of what the program records about itself
(``inside.py`` and the per-layer metrics over it): each new reader on a
toy ``Run`` — ring contents, ``mntr`` before and after — gives the
value a hand count gives; a ring that dropped spans, a program without
a ring and members without the rows give None; the windowed percentile
agrees with the program's own estimate on the same observations; and
the toy cells still run correct with ``--trace 1`` and print the new
metrics."""

import json
import os
import tempfile

import harness
import inside
import pytest
from conftest import ROOT, entries, entry
from test_runs import members_alive, rehearse

from zkstream_tpu.utils import trace
from zkstream_tpu.utils.metrics import Histogram

with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
    BENCH = json.load(f)


def read(name, run):
    return harness._load_module('layer_metrics', name).read(run)


def entries_read_by(*readers) -> list[str]:
    """The names of every entry one of the reader files reads."""
    return [m['name'] for r in readers for m in entries(r)]


def rows(*hists) -> dict:
    """Histograms as a member's ``mntr`` gives them: strings."""
    return {k: str(v) for h in hists for k, v in h.rows()}


@pytest.fixture
def ring(monkeypatch):
    r = trace.TraceRing(64)
    monkeypatch.setattr(trace, 'host_ring', r)
    return r


def toy_run() -> harness.Run:
    run = harness.Run()
    run.trace = {'window_s': 4.0}
    run.window_s = 20.0
    run.leader = 1
    return run


def test_ring_readers_on_a_toy_ring(ring):
    for n, (b, d, r, o) in enumerate([(0.5, 0.2, 1.0, 0.1),
                                      (0.7, 0.4, 3.0, 0.3),
                                      (0.6, 0.3, 2.0, 0.2)], 1):
        for op, ms in (('ingest.batch', b), ('ingest.dispatch', d),
                       ('ingest.readback', r), ('ingest.route', o)):
            ring.note(op, kind='host', parent='ingest.tick', tick=n,
                      duration_ms=ms)
        ring.note('ingest.tick', kind='host', tick=n,
                  duration_ms=b + d + r + o + 0.05)
    ring.totals['client.rx'] = [1000, 200_000_000]      # 0.2 s
    ring.totals['client.submit'] = [1000, 600_000_000]  # 0.6 s
    run = toy_run()
    want = {'ingest.batch_ms_p50': 0.6, 'ingest.dispatch_ms_p50': 0.3,
            'ingest.readback_ms_p50': 2.0, 'ingest.route_ms_p50': 0.2,
            'client.rx_share': 5.0, 'client.submit_share': 15.0}
    # entry -> its reader: every reader has its entries, one through
    # which the read cell reads it and another for the write cell
    ring_metrics = {m['name']: r for r in want for m in entries(r)}
    for r in want:
        assert entry(r, 'hunt3_1k.read') != entry(r, 'hunt3_1k.write')
    for name, r in ring_metrics.items():
        assert read(name, run) == pytest.approx(want[r])
    nothing = [None] * len(ring_metrics)
    # a ring that wrapped is not the window's: nothing to read
    ring.dropped = 1
    assert [read(n, run) for n in ring_metrics] == nothing
    ring.dropped = 0
    # an untraced run, and an empty ring
    run.trace = None
    assert [read(n, run) for n in ring_metrics] == nothing
    ring.reset()
    assert [read(n, toy_run()) for n in ring_metrics] == nothing


def test_ring_readers_on_a_program_without_a_ring(monkeypatch):
    """The parent of the PR that brought the ring: no attribute."""
    monkeypatch.delattr(trace, 'host_ring')
    run = toy_run()
    assert read('ingest.batch_ms_p50.read', run) is None
    assert read('client.rx_share.write', run) is None


def member_rows(phase_ms: dict, ticks=(), acks=(), fanout=(),
                uptime_ms=0) -> dict:
    from zkstream_tpu.server.replication import QUORUM_ACK_BUCKETS
    from zkstream_tpu.server.watchtable import TICK_BUCKETS as FAN
    from zkstream_tpu.utils.metrics import TICK_BUCKETS

    ph = Histogram('zk_tick_phase_ms', buckets=TICK_BUCKETS)
    for phase, vals in phase_ms.items():
        for v in vals:
            ph.observe(v, {'phase': phase})
    th = Histogram('zk_tick_ms', buckets=TICK_BUCKETS)
    for v in ticks:
        th.observe(v)
    qh = Histogram('zk_quorum_ack_ms', buckets=QUORUM_ACK_BUCKETS)
    for v in acks:
        qh.observe(v)
    fh = Histogram('zk_fanout_tick_ms', buckets=FAN)
    for v in fanout:
        fh.observe(v, {'plane': 'fanout'})
    return dict(rows(ph, th, qh, fh), zk_uptime_ms=str(uptime_ms))


def test_member_readers_on_toy_mntr_rows():
    run = toy_run()
    # set-up left observations behind: the window must not see them
    start = {'decode_apply': [40.0] * 10, 'fsync_gate': [30.0] * 5}
    before = [member_rows(start, acks=[200.0] * 4, fanout=[80.0] * 3,
                          uptime_ms=5_000) for _ in range(3)]
    # member 0, a follower: 2 s in decode_apply, 12 s parked
    m0 = dict(start)
    m0['decode_apply'] = start['decode_apply'] + [2.0] * 1000
    m0['forward_rpc'] = [4.0] * 3000
    # member 1, the leader: decode 3 s, fsync 1 s, cork 0.5 s
    m1 = {'decode_apply': start['decode_apply'] + [0.3] * 9900 + [8.0] * 100,
          'fsync_gate': start['fsync_gate'] + [0.4] * 2475 + [2.0] * 25,
          'cork_flush': [0.05] * 10000}
    # member 2, a follower: the series opens inside the window
    m2 = dict(start)
    m2['forward_rpc'] = [2.0] * 1000
    after = [
        member_rows(m0, acks=[200.0] * 4, fanout=[80.0] * 3 + [0.2] * 50,
                    uptime_ms=25_000),
        member_rows(m1, acks=[200.0] * 4 + [0.8] * 900 + [4.0] * 100,
                    fanout=[80.0] * 3 + [0.04] * 90 + [0.7] * 10,
                    uptime_ms=25_000),
        member_rows(m2, acks=[200.0] * 4, fanout=[80.0] * 3,
                    uptime_ms=25_000)]
    run.mntr_before, run.mntr_after = before, after

    # busiest member: member 0, (2 + 12) s of 20 s
    busy = entries_read_by('server.busy_share')
    # one entry a family: the read, the write and the converge cells
    assert sorted(busy) == sorted(
        entry('server.busy_share', c) for c in (
            'hunt3_1k.read', 'hunt3_1k.write', 'discovery3.relist'))
    for name in busy:
        assert read(name, run) == pytest.approx(70.0)
    # most parked follower: member 0, 12 s of 20 s (member 2: 2 s)
    assert read('forward.rpc_parked_share', run) == pytest.approx(60.0)
    assert inside.phase_share(run, 2, ('forward_rpc',)) == pytest.approx(
        10.0)
    assert inside.phase_share(run, 1) == pytest.approx(
        100.0 * (9900 * 0.3 + 800 + 2475 * 0.4 + 50 + 500) / 20_000)
    # p99 over the window, not since start: rank 9,900 of 10,000 is the
    # top of the (0.25, 0.5] bucket on the leader; member 0's window is
    # all in (1, 2.5], its p99 2.485
    assert read('server.decode_apply_win_ms_p99', run) == pytest.approx(
        1.0 + 1.5 * 0.99)
    assert inside.percentile(inside.member_hist(
        run, 1, 'zk_tick_phase_ms', {'phase': 'decode_apply'}),
        99) == pytest.approx(0.5)
    gates = entries_read_by('wal.fsync_gate_win_ms_p99')
    acks = entries_read_by('quorum.ack_ms_p95')
    for reader, names in (('wal.fsync_gate_win_ms_p99', gates),
                          ('quorum.ack_ms_p95', acks)):
        # write's, and converge's
        assert sorted(names) == sorted(entry(reader, c) for c in (
            'hunt3_1k.write', 'discovery3.relist'))
    for name in gates:
        # rank 2,475 of 2,500: the top of (0.25, 0.5]
        assert read(name, run) == pytest.approx(0.5)
    for name in acks:
        # rank 950 of 1,000: half way through the 100 in (2.5, 5]
        assert read(name, run) == pytest.approx(3.75)
    # member 1: rank 95 of 100, half way through the 10 in (0.5, 1];
    # member 0: all 50 in (0.1, 0.25]
    assert read('fanout.tick_ms_p95', run) == pytest.approx(0.75)
    # no uptime row: the run's window stands in
    for r in before + after:
        del r['zk_uptime_ms']
    assert [read(n, run) for n in busy] == [pytest.approx(70.0)] * len(busy)


def test_member_readers_find_nothing_on_the_parents_rows():
    run = toy_run()
    old = {'zk_tick_phase_ms_p99{phase="decode_apply"}': '13.2',
           'zk_uptime_ms': '5000', 'zk_quorum_degraded': '0'}
    run.mntr_before, run.mntr_after = [dict(old)] * 3, [dict(old)] * 3
    readers = ('server.busy_share', 'server.decode_apply_win_ms_p99',
               'wal.fsync_gate_win_ms_p99', 'quorum.ack_ms_p95',
               'fanout.tick_ms_p95', 'forward.rpc_parked_share')
    names = entries_read_by(*readers)
    assert {harness.reader_path('layer_metrics', n) for n in names} \
        == {harness.reader_path('layer_metrics', r) for r in readers}
    nothing = [None] * len(names)
    assert [read(n, run) for n in names] == nothing
    # a member that did not answer gave an empty dict, or none at all
    run.mntr_before, run.mntr_after = [{}, {}, {}], [{}, {}, {}]
    assert [read(n, run) for n in names] == nothing
    run.mntr_before, run.mntr_after = [], []
    assert [read(n, run) for n in names] == nothing


@pytest.mark.parametrize('q', [50, 90, 95, 99, 100])
def test_windowed_percentile_is_the_programs_estimate(q):
    """After-minus-before of the cumulative rows, de-cumulated and
    interpolated here, equals ``Histogram.percentile`` of a histogram
    that saw only the window's observations."""
    import random

    from zkstream_tpu.utils.metrics import TICK_BUCKETS

    rng = random.Random(q)
    whole = Histogram('zk_tick_phase_ms', buckets=TICK_BUCKETS)
    labels = {'phase': 'fsync_gate'}
    for _ in range(500):
        whole.observe(rng.lognormvariate(2.0, 2.0), labels)
    before = rows(whole)
    window = Histogram('zk_tick_phase_ms', buckets=TICK_BUCKETS)
    for _ in range(2000):
        v = rng.lognormvariate(-1.0, 1.5)
        whole.observe(v, labels)
        window.observe(v, labels)
    hist = inside.window_hist(before, rows(whole), 'zk_tick_phase_ms',
                              labels)
    assert hist['count'] == 2000
    assert hist['sum'] == pytest.approx(window.sum(labels))
    assert [n for _le, n in hist['buckets']] == [
        window.bucket_value(le, labels) - (window.bucket_value(
            hist['buckets'][i - 1][0], labels) if i else 0)
        for i, (le, _n) in enumerate(hist['buckets'])]
    assert inside.percentile(hist, q) == pytest.approx(
        window.percentile(q, labels))
    # another series of the same histogram is not this one's
    assert inside.window_hist(before, rows(whole), 'zk_tick_phase_ms',
                              {'phase': 'cork_flush'}) is None
    assert inside.window_hist(before, rows(whole), 'zk_tick_phase_ms') \
        is None
    assert inside.percentile(None, q) is None
    assert inside.percentile(inside.window_hist(
        rows(whole), rows(whole), 'zk_tick_phase_ms', labels), q) is None


def test_a_rank_past_the_last_edge_reads_the_last_edge():
    h = Histogram('zk_x_ms', buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(500.0)
    hist = inside.window_hist({}, rows(h), 'zk_x_ms')
    assert hist['buckets'] == [(1.0, 1.0), (10.0, 0.0),
                               (float('inf'), 1.0)]
    assert inside.percentile(hist, 99) == 10.0 == h.percentile(99)


#: reader files whose entry each toy cell, traced, must print
CELL_READERS = {
    'hunt3_1k.read': {'server.decode_apply_win_ms_p99'},
    'hunt3_1k.write': {'wal.fsync_gate_win_ms_p99', 'quorum.ack_ms_p95',
                       'forward.rpc_parked_share'},
    'discovery3.relist': {'wal.fsync_gate_win_ms_p99', 'quorum.ack_ms_p95',
                          'fanout.tick_ms_p95'}}
RING_READERS = ('ingest.batch_ms_p50', 'ingest.dispatch_ms_p50',
                'ingest.readback_ms_p50', 'ingest.route_ms_p50',
                'client.rx_share', 'client.submit_share')


@pytest.mark.parametrize('cell', sorted(CELL_READERS))
def test_toy_cell_traced_is_correct_and_prints_the_new_metrics(cell):
    ring_cell = not cell.endswith('.relist')
    readers = CELL_READERS[cell] | {'server.busy_share'}
    if ring_cell:
        readers |= set(RING_READERS) | {'ingest.tick_ms_p50'}
    e = {r: entry(r, cell) for r in readers}
    want = set(e.values())
    with tempfile.TemporaryDirectory(prefix='benchtest-') as tmp:
        r, out = rehearse(tmp, '--one', cell, '--seed', str(2 ** 31 + 24),
                          '--seconds', '3', '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    got = {k: v['value'] for k, v in out['metrics'].items()}
    assert want <= set(got), sorted(want - set(got))
    assert all(v >= 0 for k, v in got.items() if k in want)
    assert 0 < got[e['server.busy_share']] <= 100
    if ring_cell:
        inner = sum(got[e['ingest.%s_ms_p50' % (p,)]] for p in (
            'batch', 'dispatch', 'readback', 'route'))
        # medians of parts against the median of the whole: close, and
        # the parts cannot make up much more than the whole
        assert inner <= 1.5 * got[e['ingest.tick_ms_p50']]
        assert got[e['client.rx_share']] \
            + got[e['client.submit_share']] <= 100
    if cell.endswith('.write'):
        assert got[e['forward.rpc_parked_share']] > 0
    assert not members_alive()
