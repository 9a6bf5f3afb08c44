"""The device contract of utils/platform.py and its callers: where JAX
work lands (``force_cpu`` / ``target_device``), that a measurement
path without a chip fails before it prints (``require_accelerator``),
and that the compile cache can be placed from outside
(``enable_compile_cache``)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**extra) -> dict:
    """The ambient env minus the test session's CPU pinning and any
    cache placement, so a child sees what a user's shell would."""
    env = dict(os.environ)
    for k in ('JAX_PLATFORMS', 'XLA_FLAGS', 'JAX_COMPILATION_CACHE_DIR'):
        env.pop(k, None)
    env.update(extra)
    return env


def _run(code: str, env: dict, timeout: float = 120):
    return subprocess.run([sys.executable, '-c', code], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


# -- force_cpu env manipulation --

def test_force_cpu_appends_device_count_flag(monkeypatch):
    monkeypatch.setenv('XLA_FLAGS', '--xla_dump_to=/tmp/x')
    from zkstream_tpu.utils.platform import force_cpu

    force_cpu(n_devices=8)
    flags = os.environ['XLA_FLAGS'].split()
    assert '--xla_dump_to=/tmp/x' in flags
    assert '--xla_force_host_platform_device_count=8' in flags
    assert os.environ['JAX_PLATFORMS'] == 'cpu'


def test_force_cpu_replaces_existing_device_count(monkeypatch):
    monkeypatch.setenv(
        'XLA_FLAGS',
        '--xla_force_host_platform_device_count=2 --xla_dump_to=/tmp/x')
    from zkstream_tpu.utils.platform import force_cpu

    force_cpu(n_devices=8)
    flags = os.environ['XLA_FLAGS'].split()
    assert '--xla_force_host_platform_device_count=8' in flags
    assert '--xla_force_host_platform_device_count=2' not in flags
    assert flags.count('--xla_dump_to=/tmp/x') == 1


def test_force_cpu_pins_the_default_backend():
    import jax

    from zkstream_tpu.utils.platform import force_cpu

    force_cpu()
    assert jax.default_backend() == 'cpu'


def test_force_cpu_after_jax_import_subprocess():
    """In a fresh process WITHOUT the test env's CPU pinning,
    force_cpu called after ``import jax`` (but before first backend
    use) pins the process to N virtual CPU devices."""
    code = (
        'import jax\n'
        'from zkstream_tpu.utils.platform import force_cpu\n'
        'force_cpu(n_devices=6)\n'
        'ds = jax.devices()\n'
        'assert len(ds) == 6, ds\n'
        "assert ds[0].platform == 'cpu', ds\n"
        "print('FORCED-CPU-OK')\n")
    out = _run(code, _clean_env())
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert 'FORCED-CPU-OK' in out.stdout


# -- where a traced computation lands --

def test_target_device_follows_default_device_override():
    """``target_device`` is the default backend's first device unless
    a ``jax.default_device`` override is active — as a Device or as a
    platform string, both of which JAX accepts."""
    import jax

    from zkstream_tpu.utils.platform import target_device

    assert target_device() == jax.devices()[0]
    other = jax.devices('cpu')[3]
    with jax.default_device(other):
        assert target_device() == other
    with jax.default_device('cpu'):
        assert target_device().platform == 'cpu'


# -- no chip, no number --

def test_device_stamp_is_what_jax_reports():
    import jax

    from zkstream_tpu.utils.platform import device_stamp

    assert device_stamp() == {
        'platform': 'cpu',
        'device_kind': jax.devices()[0].device_kind,
        'device_count': len(jax.devices())}


def test_require_accelerator_raises_on_cpu_backend():
    from zkstream_tpu.utils.platform import require_accelerator

    with pytest.raises(RuntimeError, match='no accelerator'):
        require_accelerator()


def test_require_accelerator_returns_the_stamp(monkeypatch):
    from zkstream_tpu.utils import platform

    stamp = {'platform': 'tpu', 'device_kind': 'TPU v5 lite',
             'device_count': 1}
    monkeypatch.setattr(platform, 'device_stamp', lambda: dict(stamp))
    assert platform.require_accelerator() == stamp


# -- the compile cache --

_CACHE_CODE = (
    'import os, jax\n'
    'from zkstream_tpu.utils.platform import enable_compile_cache\n'
    'a = enable_compile_cache()\n'
    'b = enable_compile_cache()\n'
    'assert a == b\n'
    'f = jax.jit(lambda x: x * 3 + 1)\n'
    'import numpy as np\n'
    'f.lower(np.zeros((8, 128), np.int32)).compile()\n'
    "print('DIR', a)\n"
    "print('CFG', jax.config.jax_compilation_cache_dir)\n"
    "print('MIN', jax.config.jax_persistent_cache_min_compile_time_secs)\n"
    "print('N', len(os.listdir(a)))\n")


def _fields(stdout: str) -> dict:
    return dict(line.split(' ', 1) for line in stdout.splitlines()
                if line.split(' ', 1)[0] in ('DIR', 'CFG', 'MIN', 'N'))


def test_compile_cache_honours_env_and_sets_no_path(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no path in
    code (JAX reads the variable itself), an AOT
    ``lower().compile()`` of a sub-second program lands there, and a
    second process finds it again."""
    env = _clean_env(JAX_PLATFORMS='cpu',
                     JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    code = _CACHE_CODE.replace(
        'a = enable_compile_cache()',
        'orig = jax.config.update\n'
        'seen = []\n'
        'jax.config.update = lambda k, v: (seen.append(k), '
        'orig(k, v))[1]\n'
        'a = enable_compile_cache()')
    code += "print('SEEN', ','.join(seen))\n"
    first = _run(code, env)
    assert first.returncode == 0, first.stderr
    got = _fields(first.stdout)
    assert got['DIR'] == got['CFG'] == str(tmp_path)
    assert float(got['MIN']) == 0
    assert int(got['N']) > 0
    assert 'jax_compilation_cache_dir' not in first.stdout.split(
        'SEEN ')[1]
    second = _run(code, env)
    assert second.returncode == 0, second.stderr
    assert _fields(second.stdout)['N'] == got['N']   # hit, no new entry


def test_compile_cache_defaults_to_fixed_path_in_checkout():
    """Unset, the cache is <checkout>/.jax_cache — fixed, so a later
    process finds what an earlier one compiled — and git ignores it."""
    out = _run(_CACHE_CODE, _clean_env(JAX_PLATFORMS='cpu'))
    assert out.returncode == 0, out.stderr
    got = _fields(out.stdout)
    assert got['DIR'] == got['CFG'] == os.path.join(REPO, '.jax_cache')
    assert int(got['N']) > 0
    ignored = open(os.path.join(REPO, '.gitignore')).read().split()
    assert '.jax_cache/' in ignored


# -- regression tripwires --

def test_entry_keeps_example_args_on_host():
    """entry() only describes the computation: it must never place its
    example batch on a device itself (the caller decides which device
    compiles it).  Host numpy operands are placed by jit at trace
    time."""
    sys.path.insert(0, REPO)
    import __graft_entry__

    fn, args = __graft_entry__.entry()
    for a in args:
        assert type(a).__module__ == 'numpy', \
            ('example arg eagerly placed on a device', type(a))
