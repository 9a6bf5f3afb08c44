"""Whole runs at toy size on the CPU backend (the harness's look for a
chip skipped, everything else as ``run.py`` drives it): a sound run is
correct; a timed path broken underneath is NOT; an op that raises is a
count, not an exit code; SIGTERM mid-window and every other exit leave
no member process, no listening port and no run directory."""

import glob
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import pytest
from conftest import BENCH

REHEARSE = os.path.join(BENCH, 'rehearse.py')


def members_alive():
    out = subprocess.run(['pgrep', '-f', 'member_worker.py'],
                         capture_output=True, text=True).stdout.split()
    return [int(p) for p in out]


def run_dirs(tmp):
    return glob.glob(os.path.join(tmp, 'zkbench-*'))


def rehearse(tmp, *args, timeout=300):
    env = dict(os.environ, TMPDIR=tmp)
    r = subprocess.run([sys.executable, REHEARSE, *args], env=env,
                       capture_output=True, text=True, timeout=timeout)
    last = (r.stdout.strip().splitlines() or [''])[-1]
    result = None
    if last.startswith('# rehearsal '):
        result = json.loads(last[last.index('{'):])
    return r, result


def listening(ports):
    with open('/proc/net/tcp') as f:
        rows = [ln.split() for ln in f.readlines()[1:]]
    held = {int(r[1].rsplit(':', 1)[1], 16) for r in rows if r[3] == '0A'}
    return sorted(held & set(ports))


@pytest.fixture
def tmp():
    with tempfile.TemporaryDirectory(prefix='benchtest-') as d:
        yield d
    assert not members_alive()


@pytest.mark.parametrize('cell', ['hunt3_1k.read', 'hunt3_1k.write',
                                  'discovery3.relist'])
def test_sound_run_is_correct_and_leaves_nothing(tmp, cell):
    r, out = rehearse(tmp, '--one', cell, '--seed', str(2 ** 31 + 77),
                      '--seconds', '2')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    assert 'setup_s' in out['metrics'] and len(out['metrics']) >= 2
    # the contract's final line is never printed from a rehearsal
    assert not r.stdout.strip().splitlines()[-1].startswith('{')
    assert not members_alive() and not run_dirs(tmp)


@pytest.mark.parametrize('cell,control,kind', [
    ('hunt3_1k.read', 'flip_byte', 'payload'),
    ('hunt3_1k.write', 'lose_write', 'lost-write'),
    ('discovery3.relist', 'short_list', 'children')])
def test_broken_timed_path_reads_not_correct(tmp, cell, control, kind):
    r, out = rehearse(tmp, '--one', cell, '--seed', '5', '--seconds', '3',
                      '--control', control)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is False
    assert '# NOT CORRECT' in r.stdout and kind in r.stdout
    assert not members_alive() and not run_dirs(tmp)


def test_a_later_pr_adds_a_cell_with_new_files_only(tmp):
    """The acceptance test of the data-driven harness: a throw-away
    cell — a new mix over ``kv_closed``, a new configuration, a new
    per-layer reader, three entries in (a copy of) ``BENCHMARK.json`` —
    runs without an edit to any file that is there, and is removed."""
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, 'configs', 'hunt3_1k.json')) as f:
        cfg = json.load(f)
    cfg['name'] = 'throwaway3'
    cfg['toy']['sessions'] = 12
    cfg['toy']['tree']['children'] = 48
    added = {
        os.path.join(BENCH, 'configs', 'throwaway3.json'): json.dumps(cfg),
        os.path.join(BENCH, 'traffic', 'throwaway_mixed.json'): json.dumps({
            'engine': 'kv_closed', 'ops': {'get': 90, 'set': 10},
            'keys': 'uniform', 'outstanding': 2, 'write_own': 8,
            'op_deadline_ms': 30000, 'warm_seconds': 0.3}),
        os.path.join(BENCH, 'layer_metrics', 'throwaway.read_share.py'):
            'def read(run):\n'
            '    s = run.result["samples"]\n'
            '    return 100.0 * len(s["read"]) / '
            '(len(s["read"]) + len(s["write"]))\n',
    }
    bench['configs'].append({'name': 'throwaway3', 'source': 'a test',
                             'file': 'benchmark/configs/throwaway3.json',
                             'reduced': [], 'why': 'a test'})
    bench['workloads'].append({'name': 'throwaway3.mixed',
                               'config': 'throwaway3',
                               'traffic': 'throwaway_mixed', 'chips': 1,
                               'why': 'a test'})
    bench['per_layer'].append({
        'name': 'throwaway.read_share', 'unit': '%', 'better': 'higher',
        'source': 'program_counter', 'layer': 'load generator',
        'moves': 'ops_per_s.read', 'workloads': ['throwaway3.mixed']})
    for m in bench['end_to_end']:
        if m['name'] in ('ops_per_s.read', 'read_p95_ms', 'write_p95_ms'):
            m['workloads'].append('throwaway3.mixed')
    alt = os.path.join(tmp, 'BENCHMARK.throwaway.json')
    before = subprocess.run(['git', 'status', '--short'], cwd=root,
                            capture_output=True, text=True).stdout
    try:
        for path, text in added.items():
            assert not os.path.exists(path)
            with open(path, 'w') as f:
                f.write(text)
        with open(alt, 'w') as f:
            json.dump(bench, f)
        r, out = rehearse(tmp, '--one', 'throwaway3.mixed', '--seed', '4',
                          '--seconds', '2', '--bench', alt)
        assert r.returncode == 0, r.stderr[-2000:]
        assert out['correct'] is True and out['failed'] == 0
        assert {'ops_per_s.read', 'read_p95_ms', 'write_p95_ms',
                'setup_s'} == set(out['metrics'])
        r, out = rehearse(tmp, '--one', 'throwaway3.mixed', '--seed', '4',
                          '--seconds', '2', '--bench', alt, '--trace', '1')
        assert r.returncode == 0, r.stderr[-2000:]
        assert 80 < out['metrics']['throwaway.read_share']['value'] < 98
    finally:
        for path in added:
            if os.path.exists(path):
                os.remove(path)
    after = subprocess.run(['git', 'status', '--short'], cwd=root,
                           capture_output=True, text=True).stdout
    assert before == after


def compiled_in_window(stdout: str) -> str:
    (row,) = [ln for ln in stdout.splitlines()
              if ln.startswith('# ingest ')]
    return row.split('compiled_in_window=', 1)[1].split(' loop_errors')[0]


def test_warm_shapes_are_the_buckets_and_the_classes_a_mix_states():
    import harness
    # no ``warm_max_len``: the fleet's batch buckets, narrowest class
    assert list(harness.warm_shapes(24, 1024)) == [
        (8, None), (16, None), (32, None)]
    got = list(harness.warm_shapes(24, 1024, 4096))
    assert got[:3] == [(8, None), (16, None), (32, None)]
    # 2 x min_len up to the class that holds 4,096 B, rows 1 .. 32
    assert got[3:] == [(r, w) for w in (2048, 4096)
                       for r in (1, 2, 4, 8, 16, 32)]
    # a length between two classes is held by the wider one; one that
    # the narrowest class holds adds nothing
    assert {w for _r, w in harness.warm_shapes(24, 1024, 4097)} == {
        None, 2048, 4096, 8192}
    assert list(harness.warm_shapes(24, 1024, 1024)) == got[:3]
    assert max(r for r, _w in harness.warm_shapes(1024, 4096, 16384)) \
        == 1024


@pytest.mark.parametrize('stated', [True, False])
def test_the_widths_a_mix_states_are_warm_before_the_window(tmp, stated):
    """``hunt3_1k.read`` under a scratch mix that keeps 8 requests a
    session outstanding (``tests/data/read_deep.json``; as a throw-away
    file under ``traffic/`` for the run): a slot then hands a tick up to
    8 replies, which no bucket of the narrowest class holds.  With the
    mix's ``warm_max_len`` nothing compiles in the window; without it
    (the control) the wide buckets compile on the loop, inside it."""
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    with open(os.path.join(BENCH, 'tests', 'data', 'read_deep.json')) as f:
        mix = json.load(f)
    assert mix['outstanding'] == 8 and mix['warm_max_len'] == 16384
    if not stated:
        del mix['warm_max_len'], mix['toy']['warm_max_len']
    cell = next(w for w in bench['workloads']
                if w['name'] == 'hunt3_1k.read')
    cell['traffic'] = 'throwaway_read_deep'
    path = os.path.join(BENCH, 'traffic', 'throwaway_read_deep.json')
    alt = os.path.join(tmp, 'BENCHMARK.throwaway.json')
    assert not os.path.exists(path)
    try:
        with open(path, 'w') as f:
            json.dump(mix, f)
        with open(alt, 'w') as f:
            json.dump(bench, f)
        r, out = rehearse(tmp, '--one', 'hunt3_1k.read', '--seed', '11',
                          '--seconds', '2', '--bench', alt)
    finally:
        os.remove(path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True and out['failed'] == 0
    assert (compiled_in_window(r.stdout) == '[]') is stated
    assert not members_alive() and not run_dirs(tmp)


def test_traced_run_reports_layer_metrics(tmp):
    r, out = rehearse(tmp, '--one', 'hunt3_1k.read', '--seed', '9',
                      '--seconds', '3', '--trace', '1')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is True
    assert {'client.loop_busy_share.read', 'ingest.tick_ms_p50.read',
            'ingest.frames_per_tick.read', 'ingest.offdevice_share'} <= set(
                out['metrics'])
    assert out['metrics']['ingest.offdevice_share']['value'] == 0.0
    # no device, no device metric: the readers found nothing to read
    assert 'decode.kernel_ms_per_tick.read' not in out['metrics']


def test_an_op_that_raises_is_a_count_not_an_exit_code(tmp):
    r, out = rehearse(tmp, '--one', 'hunt3_1k.read', '--seed', '6',
                      '--seconds', '2', '--control', 'raise_op')
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['failed'] > 0 and out['attempted'] > out['failed']
    assert not members_alive() and not run_dirs(tmp)


def test_failed_membership_changes_count_and_cost(tmp):
    """A churner's change that fails, and every later change of that
    churner that cannot be sent, are attempted and failed, with each of
    their (change, watcher) pairs, and weigh as the deadline."""
    r, out = rehearse(tmp, '--one', 'discovery3.relist', '--seed', '6',
                      '--seconds', '4', '--control', 'fail_delete')
    assert r.returncode == 0, r.stderr[-2000:]
    counters = json.loads(next(
        ln for ln in r.stdout.splitlines()
        if ln.startswith('# ops '))[len('# ops '):].split(' ', 3)[3])
    lost = counters['changes_failed']
    watchers = 24 * 2 // 4          # toy: sessions x watches / services
    assert lost > 1 and counters['services_broken'] >= 1
    assert counters['pairs_never_converged'] == lost * watchers
    assert out['failed'] == lost * (1 + watchers)
    assert out['attempted'] == (counters['changes_recorded']
                                * (1 + watchers))
    # 30 s for every lost pair: the median shows it once they are many
    conv = json.loads(next(ln for ln in r.stdout.splitlines()
                           if ln.startswith('# latency converge_ms ')
                           )[len('# latency converge_ms '):])
    assert conv['max'] == 30000.0
    assert not members_alive() and not run_dirs(tmp)


@pytest.mark.parametrize('cell,env,kind', [
    ('hunt3_1k.write', 'ZKSTREAM_NO_QUORUM=1', 'quorum-members'),
    ('hunt3_1k.write', 'ZKSTREAM_QUORUM_WAIT_MS=0.01', 'quorum-degraded'),
    ('hunt3_1k.write', 'ZKSTREAM_QUORUM_WAIT_MS=100', 'quorum-hold'),
    ('hunt3_1k.write', 'ZKSTREAM_MEMBER_SYNC=never', 'wal-sync'),
    ('discovery3.relist', 'ZKSTREAM_NO_QUORUM=1', 'quorum-members'),
    ('discovery3.relist', 'ZKSTREAM_MEMBER_SYNC=never', 'wal-unsynced')])
def test_a_member_that_skips_quorum_or_wal_reads_not_correct(
        tmp, cell, env, kind):
    """The program's own switches on the members, for that run only:
    an ack without a quorum, a quorum wait cut so short that acks leave
    unconfirmed in the window, one cut to 100 ms (only the probe with
    the followers stopped sees that), a WAL that does not sync.  No
    crash is needed to see them."""
    r, out = rehearse(tmp, '--one', cell, '--seed', '7', '--seconds', '3',
                      '--member-env', env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert out['correct'] is False and out['failed'] == 0
    assert '# NOT CORRECT' in r.stdout and kind in r.stdout.split(
        '# NOT CORRECT', 1)[1]


def test_sigterm_mid_window_leaves_nothing(tmp):
    env = dict(os.environ, TMPDIR=tmp)
    p = subprocess.Popen([sys.executable, REHEARSE, '--one',
                          'hunt3_1k.write', '--seed', '8', '--seconds',
                          '60'], env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, text=True)
    ports = []
    try:
        deadline = time.time() + 120
        for line in p.stdout:            # '# setup ...' = fleet is up
            if line.startswith('# setup '):
                break
            assert time.time() < deadline
        time.sleep(2.0)                  # inside the window
        pids = members_alive()
        assert len(pids) == 3 and len(run_dirs(tmp)) == 1
        for pid in pids:
            with open('/proc/%d/cmdline' % pid, 'rb') as f:
                argv = f.read().split(b'\0')
            ports += [int(argv[4]), int(argv[5])]
        assert len(listening(ports)) >= 3
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=60) != 0
    finally:
        if p.poll() is None:
            p.kill()
        p.stdout.close()
    assert not members_alive()
    assert not run_dirs(tmp)
    assert not listening(ports)


def spawn_stale(root, run_dir):
    import members
    os.makedirs(run_dir)
    ens = members.Ensemble(root, run_dir, 1)
    ens.spawn()
    time.sleep(0.5)
    assert ens.all_alive()
    return ens


def test_a_member_left_by_an_earlier_run_is_ended(tmp, monkeypatch):
    import members
    monkeypatch.setattr(tempfile, 'tempdir', tmp)
    root = os.path.dirname(BENCH)
    ens = spawn_stale(root, os.path.join(tmp, 'zkbench-stale'))
    try:
        assert members.leftovers(root) == [ens.procs[0].pid]
        r, out = rehearse(tmp, '--one', 'hunt3_1k.read', '--seed', '3',
                          '--seconds', '1')
        assert r.returncode == 0 and out['correct'] is True
        assert 'an earlier run of this checkout left' in r.stdout
        assert not members_alive()
    finally:
        ens.kill()


def test_a_member_of_another_side_is_never_signalled(tmp):
    """The driver runs parent and change on one machine, each with a
    TMPDIR and a checkout of its own: a member under another TMPDIR, or
    started from another checkout, is not this run's to end."""
    root = os.path.dirname(BENCH)
    with tempfile.TemporaryDirectory(prefix='otherside-') as other:
        link = os.path.join(other, 'checkout')
        os.symlink(root, link)
        theirs = [spawn_stale(root, os.path.join(other, 'zkbench-theirs')),
                  spawn_stale(link, os.path.join(tmp, 'zkbench-linked'))]
        try:
            r, out = rehearse(tmp, '--one', 'hunt3_1k.read', '--seed',
                              '3', '--seconds', '1')
            assert r.returncode == 0 and out['correct'] is True
            assert 'an earlier run' not in r.stdout
            assert all(e.all_alive() for e in theirs)
        finally:
            for e in theirs:
                e.kill()


def test_no_program_no_result(tmp):
    """A directory that holds only BENCHMARK.json and the benchmark's
    files: non-zero exit, nothing on stdout."""
    import shutil
    shutil.copy(os.path.join(os.path.dirname(BENCH), 'BENCHMARK.json'), tmp)
    shutil.copytree(BENCH, os.path.join(tmp, 'benchmark'),
                    ignore=shutil.ignore_patterns('__pycache__'))
    r = subprocess.run([sys.executable, os.path.join(
        tmp, 'benchmark', 'run.py'), '--workload', 'hunt3_1k.read',
        '--seed', '1', '--seconds', '1', '--trace', '0'],
        capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ''


def test_no_chip_no_result(tmp):
    env = dict(os.environ, TMPDIR=tmp, JAX_PLATFORMS='cpu')
    r = subprocess.run([sys.executable, os.path.join(BENCH, 'run.py'),
                        '--workload', 'hunt3_1k.read', '--seed', '1',
                        '--seconds', '1', '--trace', '0'], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert not any(ln.startswith('{') for ln in r.stdout.splitlines())
    assert 'no accelerator' in r.stderr
    assert not members_alive() and not run_dirs(tmp)
