"""Compile for the v5e without one.

libtpu offers a compile-only topology: ``get_topology_desc('v5e:2x2')``
gives ``TPU v5 lite`` devices, and lowering under
``jax.default_device(<one of them>)`` runs the real XLA:TPU + Mosaic
compiler.  That is enough to hold, on a CPU-only host, the two things
a chip run would otherwise be the first to find out:

- no shape the kernels' VMEM guard admits is refused by the compiler
  (the guard used to admit shapes this Mosaic answers
  ``RESOURCE_EXHAUSTED ... scoped vmem`` to — and the fleet ingest then
  latched that bucket onto the scalar codec for good);
- the ingest's tick programs compile for the chip, and say which
  header-scan implementation they were built from.

Skips (with the reason) only where the topology cannot be created —
no libtpu, or another process holds its lock.
"""

from __future__ import annotations

import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402

from zkstream_tpu.ops import pallas_scan as ps  # noqa: E402

V5E = 'TPU v5 lite'


@pytest.fixture(scope='module')
def v5e():
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(topology_name='v5e:2x2',
                                            platform='tpu')
    except Exception as e:   # no libtpu / lock held / plugin refused
        pytest.skip('no compile-only TPU topology here: %s: %s'
                    % (type(e).__name__, str(e).splitlines()[0][:200]))
    dev = topo.devices[0]
    assert dev.device_kind == V5E
    return dev


def _compile(dev, fn, B, L):
    with jax.default_device(dev):
        return jax.jit(fn).lower(
            jax.ShapeDtypeStruct((B, L), jnp.uint8),
            jax.ShapeDtypeStruct((B,), jnp.int32)).compile()


def _largest_admitted(fits, B, F, block_rows) -> int:
    """The longest row (stepping whole 128-byte lane tiles) the guard
    admits for this blocking."""
    lo, hi = 108, 1 << 20
    assert fits(B, lo, F, block_rows, device_kind=V5E)
    while hi - lo > 128:
        mid = (lo + hi) // 2 // 128 * 128 + 108
        if fits(B, mid, F, block_rows, device_kind=V5E):
            lo = mid
        else:
            hi = mid
    return lo


@pytest.mark.parametrize('B,F,regime', [
    (8192, 64, 'multi-block'),       # R=128 programs over a grid
    (64, 32, 'single-block'),        # one program spans the batch
])
def test_header_kernel_compiles_at_the_guards_edge(v5e, B, F, regime):
    L = _largest_admitted(ps.fits_vmem, B, F, 64)
    R, Bp, _Lp = ps._block_shape(B, L, 64)
    assert (Bp > R) == (regime == 'multi-block')
    _compile(v5e, lambda b, l: ps.pallas_wire_scan(
        b, l, max_frames=F, block_rows=64), B, L)


@pytest.mark.parametrize('B,Lp,F', [
    (8192, 7296, 64),     # Mosaic needs 16.57 MiB; old estimate 12.59
    (8192, 8320, 64),     # 18.82 vs 14.19
    (64, 16512, 32),      # single block: 16.2 vs 13.95
    (40, 30080, 32),      # 18.39 vs 15.72
])
def test_shapes_mosaic_refuses_are_refused_by_the_guard(v5e, B, Lp, F):
    """The regression cases: the guard answers (a readable ValueError
    at trace time), so the compiler is never asked."""
    assert not ps.fits_vmem(B, Lp - 20, F, 64, V5E)
    with pytest.raises(ValueError, match='scoped VMEM'):
        _compile(v5e, lambda b, l: ps.pallas_wire_scan(
            b, l, max_frames=F, block_rows=64), B, Lp - 20)


def _ingest_for(dev, **kw):
    """A FleetIngest placed on the compile-only device by hand (there
    is nothing to probe a round trip against)."""
    from zkstream_tpu.io.ingest import FleetIngest

    ing = FleetIngest(bypass_bytes=0, warm='block',
                      placement='accelerator', **kw)
    ing._device = dev
    ing.placed = {'platform': dev.platform,
                  'device_kind': dev.device_kind, 'rtt_ms': None}
    return ing


@pytest.mark.parametrize('Bp,L,frames,impl', [
    # inside the auto-dispatch pocket and inside VMEM: the kernel
    (4096, 4096, 32, 'pallas'),
    # inside the pocket, but one program would need 18.8 MiB: this
    # bucket used to fail to compile and drain scalar for the life of
    # the process; the dispatcher now builds it from jnp, and says so
    (8192, 8192, 32, 'jnp'),
    # the size classes a tick of large replies dispatches: a full
    # dispatch of the 1 MiB class, and the one row of the widest class
    # (a frame at the 16 MiB cap)
    (16, 1 << 20, 32, 'jnp'),
    (1, 1 << 25, 32, 'jnp'),
    # the benchmark cells' own ``max_frames``: the read cell's widest
    # dispatch and a wide class of the load cell
    (1024, 4096, 8, 'jnp'),
    (4, 131072, 8, 'jnp'),
    # a pipelined fleet's full dispatches (hunt3_1k.read_deep: 1,024
    # sessions x 8 replies of 1,116 B behind one another in a row): the
    # 16 KiB class at DISPATCH_BYTES exactly, and the 8 KiB class
    (1024, 16384, 8, 'jnp'),
    (1024, 8192, 8, 'jnp'),
])
def test_host_body_tick_bucket_compiles_for_v5e(v5e, Bp, L, frames, impl):
    ing = _ingest_for(v5e, max_frames=frames)
    key = (False, Bp, L)
    assert ing._try_compile(key) is not None, ing.buckets[key]
    assert ing.buckets[key]['error'] is None
    assert ing.buckets[key]['impl'] == impl
    assert ing.buckets[key]['platform'] == 'tpu'
