"""Whole runs of the cell PR 32 added, at toy size on the CPU backend
(``test_runs.py``'s manner, its lists left as they are): the cell is
sound on three seeds; a body spliced before it is written, a view
spliced or swallowed between the watcher and its listener is NOT; the
traced run reports what the large-write path and the herd's ticks
moved."""

import pytest
from conftest import entry
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
    e = lambda reader: entry(reader, CELL)      # noqa: E731
    assert m[e('ingest.offdevice_share')] == 0.0
    assert m[e('ingest.recopied_share')] == 0.0
    assert 25.0 < m[e('ingest.batch_fill_share')] <= 100.0
    assert 0.0 <= m[e('ingest.full_tick_share')] <= 100.0
    assert 0.0 <= m[e('client.partial_flush_share')] <= 100.0
    assert m[e('write.large_ms_p50')] > 0
    assert m[e('refresh.herd_ms_p50')] > 0
    assert m[e('wal.append_ms_per_mib')] > 0
    assert m[e('repl.push_ms_per_mib')] > 0
    assert m[e('wal.snapshots_per_change')] >= 0
    assert {e('ingest.dispatches_per_tick'), e('ingest.h2d_bytes_per_read'),
            e('ingest.frames_per_tick'), e('ingest.batch_ms_p50'),
            e('ingest.dispatch_ms_p50'), e('ingest.readback_ms_p50'),
            e('ingest.route_ms_p50'), e('client.loop_busy_share'),
            e('client.rx_share'), e('client.flush_share'),
            e('server.busy_share'), e('quorum.ack_ms_p95'),
            e('wal.fsync_gate_win_ms_p99'), e('wal.fsyncs_per_write'),
            e('forward.writes_per_rpc'), e('fanout.tick_ms_p95'),
            e('converge.p95_ms'), e('gen.late_ms_p95')} <= set(m)
    assert 'compiled_in_window=[]' in r.stdout
    # no device, no device metric: the readers found nothing to read
    assert e('decode.converge.jit_step_roofline') not in m
    assert e('decode.kernel_ms_per_tick') not in m
