"""Whole runs of the cell PR 40 added, at toy size on the CPU backend
(``test_runs.py``'s manner, its lists left as they are): the cell is
sound on three seeds; a flipped byte, an acknowledged write that was
never sent and a read answered from an older version are NOT; the
traced run reports what the mixed loop moved."""

import pytest
from conftest import entry
from test_runs import members_alive, rehearse, run_dirs, tmp  # noqa: F401

import reference_ycsb

CELL = 'ycsb3.workloadb'


@pytest.mark.parametrize('seed', [2 ** 31 + 40, 5, 987654321])
def test_sound_run_is_correct_and_leaves_nothing(tmp, seed):  # noqa: F811
    r, out = rehearse(tmp, '--one', CELL, '--seed', str(seed),
                      '--seconds', '3')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    assert set(out['metrics']) == {'ops_per_s.read', 'read_p95_ms',
                                   'setup_s'}
    for kind in reference_ycsb.KINDS:
        assert '# compared %s 0 limit 0' % (kind,) in r.stdout
    assert 'compiled_in_window=[]' in r.stdout
    assert '"ticks_scalar": 0, "ticks_warming": 0, "ticks_frag": 0' \
        in r.stdout
    assert '"errors": {}' in r.stdout
    assert not members_alive() and not run_dirs(tmp)


@pytest.mark.parametrize('control,kinds', [
    ('flip_byte', ('payload',)),
    ('lose_write', ('lost-write', 'write-version', 'version-bytes')),
    ('old_read', ('stale-read',))])
def test_broken_path_reads_not_correct(tmp, control, kinds):  # noqa: F811
    r, out = rehearse(tmp, '--one', CELL, '--seed', '5', '--seconds', '4',
                      '--control', control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is False
    bad = next(ln for ln in r.stdout.splitlines()
               if ln.startswith('# NOT CORRECT'))
    assert any('"%s"' % (kind,) in bad for kind in kinds)
    assert not members_alive() and not run_dirs(tmp)


def test_traced_run_reports_the_updates_and_the_members(tmp):  # noqa: F811
    """Toy: 24 sessions, 512 records, ``min_len`` 2 KiB."""
    r, out = rehearse(tmp, '--one', CELL, '--seed', '9', '--seconds', '3',
                      '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    m = {k: v['value'] for k, v in out['metrics'].items()}
    e = lambda reader: entry(reader, CELL)      # noqa: E731
    assert m[e('update.rmw_ms_p95')] > 0
    assert 0.0 < m[e('server.busy_share')] <= 100.0
    assert 0.0 < m[e('client.loop_busy_share')] <= 100.0
    assert 'compiled_in_window=[]' in r.stdout
    assert '"changes_acked": ' in r.stdout
    # no device, no device metric: the readers found nothing to read
    assert e('decode.read.jit_step_roofline') not in m
    assert e('decode.kernel_ms_per_tick') not in m
