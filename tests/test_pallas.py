"""Pallas fused wire-scan kernel vs the reference jnp pipeline.

The kernel (ops/pallas_scan.py) must agree field-for-field with
``wire_pipeline_step`` (itself property-tested against the scalar
codec in test_ops.py), across random fleets, adversarial length
prefixes, padding/blocking edge cases, and partial trailing frames.
Runs in the Pallas interpreter on CPU; the same code path compiles to
Mosaic on a real TPU.
"""

import random
import struct

import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402

from zkstream_tpu.ops.pipeline import (  # noqa: E402
    wire_pipeline_step,
    wire_pipeline_step_pallas,
)
from zkstream_tpu.protocol.consts import MAX_PACKET  # noqa: E402


def _reply_frame(xid, zxid, err, body=b''):
    hdr = struct.pack('>iqi', xid, zxid, err)
    return struct.pack('>i', len(hdr) + len(body)) + hdr + body


def _fleet(rng, B, L, partial_tail=False, bad_rows=()):
    buf = np.zeros((B, L), np.uint8)
    lens = np.zeros((B,), np.int32)
    for i in range(B):
        s = b''
        for _ in range(rng.randrange(0, 7)):
            xid = rng.choice([-2, -1, rng.randrange(1, 1000)])
            zxid = rng.randrange(0, 1 << 48) if xid >= 0 else -1
            err = rng.choice([0, 0, 0, -101])
            body = bytes(rng.randrange(0, 256)
                         for _ in range(rng.randrange(0, 24)))
            s += _reply_frame(xid, zxid, err, body)
        if i in bad_rows:
            s += struct.pack('>i', MAX_PACKET + 1) + b'\0' * 8
        elif partial_tail and rng.random() < 0.5:
            s += struct.pack('>i', 40) + b'\xab' * rng.randrange(0, 20)
        s = s[:L]
        buf[i, :len(s)] = np.frombuffer(s, np.uint8)
        lens[i] = len(s)
    return jnp.asarray(buf), jnp.asarray(lens)


def _assert_same(a, b):
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f'field {f}')


@pytest.mark.parametrize('seed', [0, 1, 2])
def test_pallas_matches_jnp_pipeline(seed):
    rng = random.Random(seed)
    buf, lens = _fleet(rng, B=24, L=512, partial_tail=True)
    want = wire_pipeline_step(buf, lens, max_frames=16)
    got = wire_pipeline_step_pallas(buf, lens, max_frames=16,
                                    block_rows=8, interpret=True)
    _assert_same(want, got)


def test_pallas_bad_length_prefixes():
    rng = random.Random(7)
    buf, lens = _fleet(rng, B=16, L=256, bad_rows=(0, 3, 9))
    want = wire_pipeline_step(buf, lens, max_frames=8)
    got = wire_pipeline_step_pallas(buf, lens, max_frames=8,
                                    block_rows=8, interpret=True)
    _assert_same(want, got)
    assert bool(got.bad[0]) and bool(got.bad[3]) and bool(got.bad[9])


def test_pallas_row_padding_and_odd_batch():
    """B not a multiple of block_rows: padded rows must not leak."""
    rng = random.Random(11)
    buf, lens = _fleet(rng, B=5, L=200, partial_tail=True)
    want = wire_pipeline_step(buf, lens, max_frames=8)
    got = wire_pipeline_step_pallas(buf, lens, max_frames=8,
                                    block_rows=8, interpret=True)
    _assert_same(want, got)


def test_pallas_empty_and_full_rows():
    B, L = 8, 192
    buf = np.zeros((B, L), np.uint8)
    lens = np.zeros((B,), np.int32)
    # row 0: empty; row 1: exactly one frame filling the row
    body = b'\x01' * (L - 4 - 16)
    f = _reply_frame(5, 9, 0, body)
    assert len(f) == L
    buf[1] = np.frombuffer(f, np.uint8)
    lens[1] = L
    # row 2: short frame (body < 16 bytes) -> short/bad path
    g = struct.pack('>i', 8) + b'\x02' * 8
    buf[2, :len(g)] = np.frombuffer(g, np.uint8)
    lens[2] = len(g)
    buf, lens = jnp.asarray(buf), jnp.asarray(lens)
    want = wire_pipeline_step(buf, lens, max_frames=4)
    got = wire_pipeline_step_pallas(buf, lens, max_frames=4,
                                    block_rows=8, interpret=True)
    _assert_same(want, got)
    assert int(got.n_frames[1]) == 1 and bool(got.bad[2])


def _step(impl, buf, lens, max_frames):
    if impl == 'jnp':
        return wire_pipeline_step(buf, lens, max_frames=max_frames)
    return wire_pipeline_step_pallas(buf, lens, max_frames=max_frames,
                                     block_rows=8, interpret=True)


def _one_frame_rows(rng, B, L, widest):
    """``B`` rows of width ``widest``: the even ones hold exactly ONE
    frame wider than ``L`` (a reply of random header and body), the odd
    ones an ordinary run of frames that fits ``L``.  Returns the
    full-width batch, its ``lens``, and the rows' first ``L`` bytes."""
    small, small_lens = _fleet(rng, B, L, partial_tail=True)
    buf = np.zeros((B, widest), np.uint8)
    buf[:, :L] = np.asarray(small)
    lens = np.array(small_lens)
    for i in range(0, B, 2):
        n = rng.choice([L + 1, L + 17, rng.randrange(L + 1, widest),
                        widest])
        f = _reply_frame(rng.randrange(1, 1 << 30),
                         rng.randrange(0, 1 << 62),
                         rng.choice([0, 0, -101]),
                         rng.randbytes(n - 20))
        buf[i, :n] = np.frombuffer(f, np.uint8)
        buf[i, n:] = 0xEE
        lens[i] = n
    return jnp.asarray(buf), jnp.asarray(lens), jnp.asarray(buf[:, :L])


@pytest.mark.parametrize('impl', ['jnp', 'pallas'])
@pytest.mark.parametrize('seed', [0, 1, 2])
def test_header_rows_scan_as_their_full_width_rows(seed, impl):
    """What the fleet ingest gives a tick for a slot that holds exactly
    one whole frame (io/ingest.py, "Size classes"): the frame's first
    ``L`` bytes under its TRUE length.  Both implementations — the jnp
    pipeline and the Pallas kernel (interpret mode) — give every plane
    of ``WireStats`` as they give it for the full-width row: one frame
    at 4, its size the prefix, ``resid == lens``, the header parsed,
    nothing flagged; the ordinary rows beside them are not disturbed.
    So ``auto_impl`` needs no exception for a bucket that may hold
    header rows: the kernel agrees."""
    rng = random.Random(seed)
    B, L, widest = 16, 128, 4096
    full, lens, heads = _one_frame_rows(rng, B, L, widest)
    want = wire_pipeline_step(full, lens, max_frames=8)
    got = _step(impl, heads, lens, 8)
    _assert_same(want, got)
    lens = np.asarray(lens)
    for i in range(0, B, 2):
        assert int(got.n_frames[i]) == 1 and not bool(got.bad[i])
        assert int(got.resid[i]) == lens[i] > L
        assert int(got.starts[i, 0]) == 4
        assert int(got.sizes[i, 0]) == lens[i] - 4


@pytest.mark.parametrize('impl', ['jnp', 'pallas'])
def test_a_header_row_of_a_frame_at_the_cap(impl):
    """``lens`` far beyond the row: a frame of the 16 MiB cap from its
    first 64 bytes (nothing wider is built to compare it with)."""
    f = _reply_frame(77, (5 << 32) | 9, -101, b'\x05' * 44)
    row = bytearray(f)
    row[:4] = struct.pack('>i', MAX_PACKET)
    buf = jnp.asarray(np.frombuffer(bytes(row), np.uint8)[None, :])
    lens = jnp.asarray(np.array([MAX_PACKET + 4], np.int32))
    st = _step(impl, buf, lens, 4)
    assert int(st.n_frames[0]) == 1 and not bool(st.bad[0])
    assert int(st.resid[0]) == MAX_PACKET + 4
    assert (int(st.starts[0, 0]), int(st.sizes[0, 0])) == (4, MAX_PACKET)
    assert (int(st.xids[0, 0]), int(st.errs[0, 0])) == (77, -101)
    assert (int(st.zxid_hi[0, 0]), int(st.zxid_lo[0, 0])) == (5, 9)
    assert np.asarray(st.starts[0, 1:]).tolist() == [-1, -1, -1]


V5E = 'TPU v5 lite'


def test_vmem_ceiling_is_keyed_by_device_kind():
    """The guard's ceiling comes from a table keyed by the device_kind
    JAX reports; a device that is not in it is an error, not a
    default — and this suite's CPU devices are not in it."""
    from zkstream_tpu.ops.pallas_scan import fits_vmem, scoped_vmem_limit

    assert scoped_vmem_limit(V5E) == 16 * 1024 * 1024
    with pytest.raises(ValueError, match='no scoped-VMEM ceiling'):
        scoped_vmem_limit('TPU v9000')
    with pytest.raises(ValueError, match='no scoped-VMEM ceiling'):
        scoped_vmem_limit()              # the target device: a CPU
    with pytest.raises(ValueError, match='no scoped-VMEM ceiling'):
        fits_vmem(64, 512)


def test_vmem_guard_refuses_what_mosaic_refuses():
    """The shapes the old estimate admitted and the installed Mosaic
    refuses (RESOURCE_EXHAUSTED, scoped vmem) are refused by the guard
    now; the pocket shapes that compile are still admitted.
    (tests/test_compile_v5e.py compiles the boundary for real.)"""
    from zkstream_tpu.ops.pallas_scan import fits_vmem

    # multi-block R=128: Lp=7296 needs 16.57 MiB, Lp=8320 18.82
    assert not fits_vmem(8192, 7296 - 20, 64, 64, V5E)
    assert not fits_vmem(8192, 8320 - 20, 64, 64, V5E)
    # single block: R=64 x Lp=16512 needs 16.2, R=40 x Lp=30080 18.39
    assert not fits_vmem(64, 16512 - 20, 32, 64, V5E)
    assert not fits_vmem(40, 30080 - 20, 32, 64, V5E)
    # the pocket and its ingest buckets
    assert fits_vmem(8192, 6144, 64, 64, V5E)
    assert fits_vmem(4096, 4096, 32, 64, V5E)
    assert not fits_vmem(8192, 8192, 32, 64, V5E)
    # a 16,384-connection corpus row does not fit one program
    assert not fits_vmem(16384, 15792, 64, 64, V5E)
    # rows/program double with block_rows 256: half the length fits
    assert fits_vmem(256, 2048, 48, 128, V5E)
    assert not fits_vmem(512, 5000, 48, 256, V5E)


def test_pallas_names_never_substitute_jnp(monkeypatch):
    """A function named ``*_pallas`` runs the kernel or raises: a
    shape past the VMEM ceiling is a ValueError, never a quiet jnp
    result (only ``auto_impl`` may choose jnp, and it says so)."""
    from zkstream_tpu.ops import pallas_scan, pipeline

    monkeypatch.setattr(pallas_scan, 'scoped_vmem_limit',
                        lambda device_kind=None: 16 * 1024 * 1024)

    def no_jnp(*a, **kw):
        raise AssertionError('jnp pipeline substituted for the kernel')
    monkeypatch.setattr(pipeline, 'wire_pipeline_step', no_jnp)

    buf = jnp.zeros((1024, 13440), jnp.uint8)
    lens = jnp.zeros((1024,), jnp.int32)
    with pytest.raises(ValueError, match='scoped VMEM'):
        wire_pipeline_step_pallas(buf, lens, max_frames=128,
                                  block_rows=128)
    buf = jnp.zeros((8, 200_000), jnp.uint8)
    lens = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match='scoped VMEM'):
        wire_pipeline_step_pallas(buf, lens, max_frames=6, block_rows=8)


def test_pallas_on_a_device_without_a_ceiling_raises():
    """Non-interpret on this suite's CPU backend: the guard has no
    ceiling for the device and says so (Mosaic could not lower there
    either) — it does not fall back."""
    buf = jnp.zeros((8, 256), jnp.uint8)
    lens = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match='no scoped-VMEM ceiling'):
        wire_pipeline_step_pallas(buf, lens, max_frames=4)


def test_auto_dispatch_routes_by_platform_and_shape(monkeypatch):
    """auto_impl names the measured winner: jnp on non-TPU platforms
    (this suite runs on the CPU backend), the kernel inside the
    recorded pocket on TPU — but only where it also fits the device's
    scoped VMEM, and the name says which."""
    import types

    from zkstream_tpu.ops.pipeline import (
        _pallas_pocket,
        auto_impl,
        wire_pipeline_step_auto,
    )
    from zkstream_tpu.utils import platform

    # the recorded win pocket
    assert _pallas_pocket(8192, 64)
    assert not _pallas_pocket(8192, 8)       # frame-sparse: jnp
    assert not _pallas_pocket(2048, 64)      # small fleet: jnp
    assert not _pallas_pocket(32768, 64)     # tie band: jnp default

    assert auto_impl(8192, 2048, 64) == 'jnp'    # CPU: forced by conftest
    buf = np.zeros((8192, 256), np.uint8)
    lens = np.zeros((8192,), np.int32)
    auto = wire_pipeline_step_auto(buf, lens, max_frames=64)
    ref = wire_pipeline_step(buf, lens, max_frames=64)
    # on CPU the auto path IS the jnp path (pallas cannot lower here)
    assert int(jnp.sum(auto.n_frames)) == int(jnp.sum(ref.n_frames))

    chip = types.SimpleNamespace(platform='tpu', device_kind=V5E)
    monkeypatch.setattr(platform, 'target_device', lambda: chip)
    assert auto_impl(8192, 2048, 64) == 'pallas'
    assert auto_impl(4096, 4096, 32) == 'pallas'
    assert auto_impl(8192, 8192, 32) == 'jnp'    # pocket, but no fit
    assert auto_impl(2048, 2048, 64) == 'jnp'    # outside the pocket
    # a TPU the kernels were never sized for is an error, not a guess
    chip.device_kind = 'TPU v9000'
    with pytest.raises(ValueError, match='no scoped-VMEM ceiling'):
        auto_impl(8192, 2048, 64)


def test_auto_dispatch_honors_default_device_override():
    """An active jax.default_device(cpu) override (how the fleet
    ingest pins ticks to the host backend) routes auto-dispatch to
    jnp even when the pocket matches."""
    import jax

    from zkstream_tpu.ops.pipeline import auto_impl

    with jax.default_device(jax.devices('cpu')[0]):
        assert auto_impl(8192, 2048, 64) == 'jnp'
