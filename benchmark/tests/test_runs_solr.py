"""Whole runs of the cell PR 30 added, at toy size on the CPU backend
(``test_runs.py``'s manner, its lists left as they are): the cell is
sound; a reply altered in its first byte or spliced behind its head is
NOT; the traced run reports what the size-classed ticks moved."""

import pytest
from conftest import entry
from test_runs import members_alive, rehearse, run_dirs, tmp  # noqa: F401

KINDS = ('payload', 'data-length', 'version', 'stale-read', 'listing',
         'lost-znode', 'evicted')


def test_sound_run_is_correct_and_leaves_nothing(tmp):  # noqa: F811
    r, out = rehearse(tmp, '--one', 'solrconf3.load', '--seed',
                      str(2 ** 31 + 30), '--seconds', '3')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    assert set(out['metrics']) == {'ops_per_s.read', 'read_p95_ms',
                                   'setup_s'}
    for kind in KINDS:
        assert '# compared %s 0 limit 0' % (kind,) in r.stdout
    assert 'compiled_in_window=[]' in r.stdout
    assert not members_alive() and not run_dirs(tmp)


@pytest.mark.parametrize('control', ['flip_byte', 'splice_large'])
def test_altered_reply_reads_not_correct(tmp, control):  # noqa: F811
    r, out = rehearse(tmp, '--one', 'solrconf3.load', '--seed', '5',
                      '--seconds', '3', '--control', control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is False
    bad = next(ln for ln in r.stdout.splitlines()
               if ln.startswith('# NOT CORRECT'))
    assert '"payload"' in bad
    assert not members_alive() and not run_dirs(tmp)


def test_traced_load_reports_what_the_ticks_moved(tmp):  # noqa: F811
    """Toy: 24 sessions, sizes a sixteenth (8 B .. 60 KiB), ``min_len``
    1 KiB.  Several classes a tick, nothing copied twice, no tick off
    the (host-placed) tick program, every reply through the lane."""
    r, out = rehearse(tmp, '--one', 'solrconf3.load', '--seed', '9',
                      '--seconds', '3', '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    m = {k: v['value'] for k, v in out['metrics'].items()}
    e = lambda reader: entry(reader, 'solrconf3.load')      # noqa: E731
    assert m[e('ingest.offdevice_share')] == 0.0
    assert m[e('ingest.recopied_share')] == 0.0
    assert 25.0 < m[e('ingest.batch_fill_share')] <= 100.0
    assert 1.0 < m[e('ingest.dispatches_per_tick')] < 8.0
    assert m[e('ingest.h2d_bytes_per_read')] > 1024.0
    assert m[e('ingest.settle_lane_share')] > 99.0
    assert {e('ingest.batch_ms_p50'), e('ingest.dispatch_ms_p50'),
            e('ingest.readback_ms_p50'), e('ingest.route_ms_p50'),
            e('ingest.route_us_per_frame'), e('ingest.frames_per_tick'),
            e('client.rx_share'), e('client.flush_share'),
            e('client.sends_per_flush'), e('server.busy_share'),
            e('server.decode_apply_win_ms_p99')} <= set(m)
    assert 'compiled_in_window=[]' in r.stdout
    # no device, no device metric: the readers found nothing to read
    assert e('decode.read.jit_step_roofline') not in m
