"""Whole runs of the cell PR 26 added, at toy size on the CPU backend
(``test_runs.py``'s manner, its lists left as they are): the cell is
sound; the cache's serve gate and the invalidation stream broken
underneath are NOT; the traced run reports the cell's counters."""

import pytest
from conftest import entry
from test_runs import members_alive, rehearse, run_dirs, tmp  # noqa: F401

def test_sound_run_is_correct_and_leaves_nothing(tmp):  # noqa: F811
    r, out = rehearse(tmp, '--one', 'confcache3.push', '--seed',
                      str(2 ** 31 + 78), '--seconds', '3')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    assert set(out['metrics']) == {'converge_p50_ms', 'setup_s'}
    for kind in ('missed-change', 'stale-hit', 'evicted', 'payload'):
        assert '# compared %s 0 limit 0' % (kind,) in r.stdout
    assert not members_alive() and not run_dirs(tmp)


@pytest.mark.parametrize('control,kind', [
    ('stale_hit', 'stale-hit'), ('drop_notify', 'missed-change')])
def test_broken_cache_plane_reads_not_correct(tmp, control, kind):  # noqa: F811
    r, out = rehearse(tmp, '--one', 'confcache3.push', '--seed', '5',
                      '--seconds', '3', '--control', control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is False
    bad = next(ln for ln in r.stdout.splitlines()
               if ln.startswith('# NOT CORRECT'))
    assert '"%s"' % (kind,) in bad
    assert not members_alive() and not run_dirs(tmp)


def test_traced_push_reports_its_counters(tmp):  # noqa: F811
    """Toy: 24 sessions.  Every change invalidates one entry at every
    subscriber and leaves the members as one frame a subscriber; no one
    is evicted; no tick ran off the (host-placed) tick program."""
    r, out = rehearse(tmp, '--one', 'confcache3.push', '--seed', '9',
                      '--seconds', '3', '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    m = {k: v['value'] for k, v in out['metrics'].items()}
    e = lambda reader: entry(reader, 'confcache3.push')     # noqa: E731
    assert m[e('cache.invalidations_per_change')] == 24.0
    assert m[e('fanout.persistent_per_change')] == 24.0
    assert m[e('overload.persistent_evictions')] == 0.0
    assert m[e('ingest.offdevice_share')] == 0.0
    assert 0.0 < m[e('cache.hit_share')] < 100.0
    assert m[e('client.notify_share')] > 0.0
    assert {e(r) for r in (
        'converge.p95_ms', 'gen.late_ms_p95', 'fanout.tick_ms_p95',
        'server.busy_share', 'client.sends_per_flush',
        'ingest.route_ms_p50')} <= set(m)
    # no device, no device metric: the readers found nothing to read
    assert e('decode.converge.jit_step_roofline') not in m
