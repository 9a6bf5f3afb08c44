"""chip_smoke.py's contract, as far as a host without a chip can hold
it: the explicit ``--cpu-dry-run`` drives every phase end to end at toy
size and says so in its stamp; without the flag a machine whose JAX
finds no accelerator gets a non-zero exit and no result; and the
script alone, without the program, fails too."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from zkstream_tpu.utils import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, 'chip_smoke.py')

pytestmark = pytest.mark.skipif(
    native.build_loadgen() is None or native.ensure_ext() is None,
    reason='no C compiler: the smoke builds its natives from source')


def _run(argv, cwd=REPO, timeout=600, **env):
    e = dict(os.environ)
    for k in ('JAX_COMPILATION_CACHE_DIR', 'PYTHONPATH'):
        e.pop(k, None)
    e.update(env)
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=e,
                          capture_output=True, text=True,
                          timeout=timeout)


def _results(stdout: str) -> list:
    return [ln for ln in stdout.splitlines() if ln.startswith('{')]


@pytest.mark.timeout(600)
def test_cpu_dry_run_end_to_end():
    out = _run([SMOKE, '--cpu-dry-run', '--seed', '5'])
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    report, last = out.stdout.strip().splitlines()[-2:]
    # the last line is the verdict and nothing else: exactly these keys
    verdict = json.loads(last)
    assert verdict == {'ok': True, 'device': {
        'platform': 'cpu', 'kind': verdict['device']['kind'],
        'count': 1}}
    assert isinstance(verdict['device']['kind'], str)
    r = json.loads(report)
    assert r['device'] == verdict['device']
    assert r['ok'] is True and r['claim'] is None and r['seed'] == 5
    # never under a device's name: the stamp says CPU, no chip, and
    # lists every size that was cut
    assert r['device']['platform'] == 'cpu' and r['chip'] is False
    assert {'sessions', 'payload', 'children'} <= set(r['reduced'])
    assert r['kernels']['interpret'] is True
    members = r['ensemble']['members']
    assert len(members) == 3 and all(m['jax_free'] for m in members)
    assert [m['role'] for m in members].count('leader') == 1
    n = r['deployment']['sessions']
    arm = r['ingest']
    assert arm['ticks'] > 0 and arm['frames'] >= arm['ops']
    assert (arm['ticks_scalar'], arm['ticks_warming'],
            arm['ticks_frag'], arm['failed_buckets']) == (0, 0, 0, 0)
    assert arm['equal_to_reference'] is True
    assert arm['notifications'] == n
    assert arm['placed']['platform'] == 'cpu'
    for k in ('scan_pocket', 'scan_single', 'tick_pocket'):
        assert r['kernels'][k]['matches_jnp'] is True
    lg = r['loadgen']
    assert lg['errors'] == {'connect': 0, 'io': 0, 'proto': 0}
    assert lg['fanout']['delivered'] == lg['fanout']['expected'] > 0


@pytest.mark.timeout(180)
def test_no_accelerator_exits_nonzero_without_a_result():
    """Held to the CPU (as this sandbox holds JAX), the script does not
    choose the dry run for itself: non-zero exit, no result line, the
    members it spawned stopped."""
    out = _run([SMOKE], timeout=170, JAX_PLATFORMS='cpu')
    assert out.returncode != 0
    assert _results(out.stdout) == []
    assert 'no accelerator' in out.stderr
    left = subprocess.run(['pgrep', '-f', 'zk-chip-smoke-'],
                          capture_output=True, text=True).stdout
    assert left.strip() == ''


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to drive."""
    shutil.copy(SMOKE, tmp_path / 'chip_smoke.py')
    out = _run([str(tmp_path / 'chip_smoke.py'), '--cpu-dry-run'],
               cwd=str(tmp_path), timeout=60)
    assert out.returncode != 0
    assert _results(out.stdout) == []
