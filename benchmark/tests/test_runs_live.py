"""Whole runs of the cell PR 38 added, at toy size on the CPU backend
(``test_runs.py``'s manner, its lists left as they are): the cell is
sound on three seeds; a list handed out a second time, a closed node's
name put back, a list without its first name are NOT; the traced run
reports what the herd and the session churn moved."""

import pytest
from conftest import entry
from test_runs import members_alive, rehearse, run_dirs, tmp  # noqa: F401

import reference_live

CELL = 'livenodes3.rolling'


@pytest.mark.parametrize('seed', [2 ** 31 + 38, 5, 987654321])
def test_sound_run_is_correct_and_leaves_nothing(tmp, seed):  # noqa: F811
    r, out = rehearse(tmp, '--one', CELL, '--seed', str(seed),
                      '--seconds', '3')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    assert set(out['metrics']) == {'converge_p50_ms', 'setup_s'}
    for kind in reference_live.KINDS:
        assert '# compared %s 0 limit 0' % (kind,) in r.stdout
    assert 'compiled_in_window=[]' in r.stdout
    assert '"errors": {}' in r.stdout
    assert not members_alive() and not run_dirs(tmp)


@pytest.mark.parametrize('control,kind', [
    ('stale_children', 'stale-view'), ('ghost_node', 'children'),
    ('short_list', 'children')])
def test_broken_path_reads_not_correct(tmp, control, kind):  # noqa: F811
    r, out = rehearse(tmp, '--one', CELL, '--seed', '5', '--seconds', '4',
                      '--control', control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is False
    bad = next(ln for ln in r.stdout.splitlines()
               if ln.startswith('# NOT CORRECT'))
    assert '"%s"' % (kind,) in bad
    assert not members_alive() and not run_dirs(tmp)


def test_traced_run_reports_the_herd_and_the_churn(tmp):  # noqa: F811
    """Toy: 24 sessions, ``min_len`` 1 KiB, 4 changes a second."""
    r, out = rehearse(tmp, '--one', CELL, '--seed', '9', '--seconds', '3',
                      '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    m = {k: v['value'] for k, v in out['metrics'].items()}
    e = lambda reader: entry(reader, CELL)      # noqa: E731
    # a change costs a member ONE encode: 8 askers a member, 7 hits
    assert 60.0 < m[e('server.children_cache_hit_share')] < 100.0
    assert m[e('server.list_encode_ms_per_change')] > 0
    assert m[e('ingest.route_us_per_name')] > 0
    assert m[e('refresh.herd_ms_p50')] > 0
    assert m[e('client.connect_ms_p50')] > 0
    assert m[e('ingest.full_tick_share')] == 0.0
    assert 0.0 < m[e('ingest.batch_fill_share')] <= 100.0
    assert {e('server.busy_share'), e('fanout.tick_ms_p95'),
            e('client.loop_busy_share'), e('client.rx_share'),
            e('ingest.dispatches_per_tick'), e('gen.late_ms_p95'),
            e('converge.p95_ms')} <= set(m)
    assert 'compiled_in_window=[]' in r.stdout
    # no device, no device metric: the readers found nothing to read
    assert e('decode.converge.jit_step_roofline') not in m
    assert e('decode.kernel_ms_per_tick') not in m
