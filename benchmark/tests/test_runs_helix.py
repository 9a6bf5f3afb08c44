"""Whole runs of the cell PR 32 added, at toy size on the CPU backend
(``test_runs.py``'s manner, its lists left as they are): the cell is
sound on three seeds; a body spliced before it is written, a view
spliced or swallowed between the watcher and its listener is NOT; the
traced run reports what the large-write path and the herd's ticks
moved."""

import pytest
from test_runs import members_alive, rehearse, run_dirs, tmp  # noqa: F401

import reference_docs

CELL = 'helixview3.viewchange'


@pytest.mark.parametrize('seed', [2 ** 31 + 32, 5, 987654321])
def test_sound_run_is_correct_and_leaves_nothing(tmp, seed):  # noqa: F811
    r, out = rehearse(tmp, '--one', CELL, '--seed', str(seed),
                      '--seconds', '3')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    assert set(out['metrics']) == {'converge_p50_ms', 'setup_s'}
    for kind in reference_docs.KINDS:
        assert '# compared %s 0 limit 0' % (kind,) in r.stdout
    assert 'compiled_in_window=[]' in r.stdout
    assert not members_alive() and not run_dirs(tmp)


@pytest.mark.parametrize('control,kind', [
    ('splice_write', 'payload'), ('splice_emit', 'payload'),
    ('drop_emit', 'missed-change')])
def test_broken_path_reads_not_correct(tmp, control, kind):  # noqa: F811
    r, out = rehearse(tmp, '--one', CELL, '--seed', '5', '--seconds', '3',
                      '--control', control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is False
    bad = next(ln for ln in r.stdout.splitlines()
               if ln.startswith('# NOT CORRECT'))
    assert '"%s"' % (kind,) in bad
    assert not members_alive() and not run_dirs(tmp)


def test_traced_run_reports_the_large_write_path(tmp):  # noqa: F811
    """Toy: 24 sessions (3 + 6 + 15), 4 tables, sizes a sixteenth,
    ``min_len`` 1 KiB."""
    r, out = rehearse(tmp, '--one', CELL, '--seed', '9', '--seconds', '3',
                      '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    m = {k: v['value'] for k, v in out['metrics'].items()}
    v = '.viewchange'
    assert m['ingest.offdevice_share' + v] == 0.0
    assert m['ingest.recopied_share' + v] == 0.0
    assert 25.0 < m['ingest.batch_fill_share' + v] <= 100.0
    assert 0.0 <= m['ingest.full_tick_share' + v] <= 100.0
    assert 0.0 <= m['client.partial_flush_share' + v] <= 100.0
    assert m['write.large_ms_p50' + v] > 0
    assert m['refresh.herd_ms_p50' + v] > 0
    assert m['wal.append_ms_per_mib' + v] > 0
    assert m['repl.push_ms_per_mib' + v] > 0
    assert m['wal.snapshots_per_change' + v] >= 0
    assert {'ingest.dispatches_per_tick' + v, 'ingest.h2d_bytes_per_read' + v,
            'ingest.frames_per_tick' + v, 'ingest.batch_ms_p50' + v,
            'ingest.dispatch_ms_p50' + v, 'ingest.readback_ms_p50' + v,
            'ingest.route_ms_p50' + v, 'client.loop_busy_share' + v,
            'client.rx_share' + v, 'client.flush_share' + v,
            'server.busy_share' + v, 'quorum.ack_ms_p95' + v,
            'wal.fsync_gate_win_ms_p99' + v, 'wal.fsyncs_per_write' + v,
            'forward.writes_per_rpc' + v, 'fanout.tick_ms_p95' + v,
            'converge.p95_ms' + v, 'gen.late_ms_p95' + v} <= set(m)
    assert 'compiled_in_window=[]' in r.stdout
    # no device, no device metric: the readers found nothing to read
    assert 'decode.viewchange.jit_step_roofline' not in m
    assert 'decode.kernel_ms_per_tick' + v not in m
