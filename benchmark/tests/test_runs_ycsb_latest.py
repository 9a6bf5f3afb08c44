"""Whole runs of the cell PR 50 added (paced: ``target_ops_per_s`` in
``traffic/workloadd.json``), at toy size on the CPU backend
(``test_runs.py``'s manner, its lists left as they are): the cell is
sound on three seeds; a ``NO_NODE`` for a record the session has read
is NOT; the traced run reports the inserts, the share of reads that met
a record not yet visible, and the followers' apply lag."""

import pytest
from conftest import entry
from test_runs import members_alive, rehearse, run_dirs, tmp  # noqa: F401

import reference_ycsb_latest

CELL = 'ycsb3_latest.workloadd'


@pytest.mark.parametrize('seed', [2 ** 31 + 40, 5, 987654321])
def test_sound_run_is_correct_and_leaves_nothing(tmp, seed):  # noqa: F811
    r, out = rehearse(tmp, '--one', CELL, '--seed', str(seed),
                      '--seconds', '3')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    # paced (rule C's second leg): the cell is off ``read_p95_ms``
    assert set(out['metrics']) == {'ops_per_s.read', 'setup_s'}
    assert '"gen_late_ms_p50": ' in r.stdout
    for kind in reference_ycsb_latest.KINDS:
        assert '# compared %s 0 limit 0' % (kind,) in r.stdout
    assert '# compared reads_not_yet_visible ' in r.stdout
    assert 'compiled_in_window=[]' in r.stdout
    assert '"ticks_scalar": 0, "ticks_warming": 0, "ticks_frag": 0' \
        in r.stdout
    assert '"errors": {}' in r.stdout
    assert not members_alive() and not run_dirs(tmp)


def test_a_hidden_node_reads_not_correct(tmp):  # noqa: F811
    r, out = rehearse(tmp, '--one', CELL, '--seed', '5', '--seconds', '4',
                      '--control', 'hide_node')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is False
    bad = next(ln for ln in r.stdout.splitlines()
               if ln.startswith('# NOT CORRECT'))
    assert '"stale-miss"' in bad
    assert 'a record it has read before' in r.stdout
    assert not members_alive() and not run_dirs(tmp)


def test_traced_run_reports_the_inserts_and_the_followers(tmp):  # noqa: F811
    """Toy: 24 sessions, 512 records, room for 8,192 inserts,
    ``min_len`` 2 KiB."""
    r, out = rehearse(tmp, '--one', CELL, '--seed', '9', '--seconds', '3',
                      '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    m = {k: v['value'] for k, v in out['metrics'].items()}
    e = lambda reader: entry(reader, CELL)      # noqa: E731
    assert m[e('insert.ms_p95')] > 0
    assert 0.0 <= m[e('read.not_yet_visible_share')] < 50.0
    assert m[e('repl.apply_lag_ms_p95')] > 0
    assert m[e('quorum.degraded_releases')] >= 0
    assert 0.0 < m[e('client.loop_busy_share')] <= 100.0
    assert 'compiled_in_window=[]' in r.stdout
    assert '"inserts_acked": ' in r.stdout
    # (a toy WAL does not roll in 3 s: no wal.roll_ms_per_change)
    # the cell makes no update: that reader is not its
    assert not [x for x in out['metrics'] if x.startswith('update.')]
    # no device, no device metric: the readers found nothing to read
    assert e('decode.read.jit_step_roofline') not in m
    assert e('decode.kernel_ms_per_tick') not in m
