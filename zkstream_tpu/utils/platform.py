"""Which device JAX work lands on, and where its compile cache lives.

Three jobs, all of which must happen before the process's first
compile (first ``jax.devices()`` / first traced op):

- ``force_cpu`` pins the process to N in-process CPU devices — unit
  tests, host-path benches and the virtual multi-chip dry run;
- ``require_accelerator`` is the opposite contract for anything that
  reports a device number: no chip, no run;
- ``enable_compile_cache`` points JAX's persistent compilation cache
  at a place that survives the process.
"""

from __future__ import annotations

import os

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed inside the checkout (the path is part of what a later run must
#: find again, so never a temp name, a pid or a time)
_CACHE_DIRNAME = '.jax_cache'


def force_cpu(n_devices: int | None = None) -> None:
    """Pin this process's JAX to the host CPU platform.

    With ``n_devices``, also request that many virtual CPU devices
    (``--xla_force_host_platform_device_count``) — only effective if
    the CPU backend has not been initialized yet.
    """
    if n_devices is not None:
        flags = os.environ.get('XLA_FLAGS', '')
        flag = f'--xla_force_host_platform_device_count={n_devices}'
        if '--xla_force_host_platform_device_count' in flags:
            flags = ' '.join(
                flag if f.startswith('--xla_force_host_platform_device_count')
                else f for f in flags.split())
        else:
            flags = (flags + ' ' + flag).strip()
        os.environ['XLA_FLAGS'] = flags
    os.environ['JAX_PLATFORMS'] = 'cpu'

    import jax

    jax.config.update('jax_platforms', 'cpu')


def target_device():
    """The device a computation traced right now lowers for: an active
    ``jax.default_device`` override (how the fleet ingest pins its
    tick programs) before the default backend's first device."""
    import jax

    dev = jax.config.jax_default_device
    if dev is None:
        return jax.devices()[0]
    # jax.default_device accepts a Device or a platform string
    return jax.devices(dev)[0] if isinstance(dev, str) else dev


def device_stamp() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    the default backend — the stamp every printed result carries."""
    import jax

    devs = jax.devices()
    return {'platform': devs[0].platform,
            'device_kind': devs[0].device_kind,
            'device_count': len(devs)}


def require_accelerator() -> dict:
    """The :func:`device_stamp` of the default backend, or
    ``RuntimeError`` when that backend is the host CPU: a measurement
    path that finds no chip fails, it does not fall back."""
    stamp = device_stamp()
    if stamp['platform'] == 'cpu':
        raise RuntimeError(
            'no accelerator: the default JAX backend is the host CPU '
            '(%(device_count)d x %(device_kind)s)' % stamp)
    return stamp


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
    reads it and no path is set in code; otherwise the cache is
    ``<checkout>/.jax_cache``.  The caching threshold drops to zero
    either way: a host-body tick program compiles in about a second,
    right at JAX's default threshold, and would otherwise never be
    kept.  Idempotent; call before the first compile."""
    import jax

    path = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), _CACHE_DIRNAME)
        jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    return path
