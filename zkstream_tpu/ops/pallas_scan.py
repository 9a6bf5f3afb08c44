"""Fused Pallas TPU kernel for the wire-decode hot path.

One kernel invocation = frame scan + reply-header parse for a block of
connection streams, entirely in VMEM.  This fuses what
:mod:`zkstream_tpu.ops.frame_scan` and :mod:`zkstream_tpu.ops.headers`
express as separate XLA ops (a ``lax.scan`` whose every step re-gathers
from the HBM-resident buffer, then a second gather pass for headers)
into a single pass: the byte block is staged into VMEM once, and the
per-frame cursor walk plus all five header-field reads run on-chip as
weighted lane-reduces — each 4-byte window gets big-endian place
values (1 << 8*(3-d)) and a row-sum assembles the word.  That is the
VPU-shaped formulation of a per-row dynamic gather, which Mosaic has
no native vector instruction for.

Semantics match ``frame_cursor_scan`` + ``parse_reply_headers`` exactly
(property-tested against them in tests/test_pallas.py); both re-state
the reference's sequential decode loop, lib/zk-streams.js:39-99, and
drain-loop routing, lib/connection-fsm.js:213-229, as array code.

Grid: one program per row-block, ``dimension_semantics=("parallel",)``
so Megacore splits blocks across TensorCores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..protocol.consts import MAX_PACKET

# Header field offsets relative to the frame's length prefix: the body
# begins at +4 with xid:int32, zxid:int64 (as hi/lo words), err:int32
# (reference: lib/zk-buffer.js:275-331).
_LEN_OFF = 0
_XID_OFF = 4
_ZHI_OFF = 8
_ZLO_OFF = 12
_ERR_OFF = 16
# widest read starts at cur + 16 and spans 4 bytes -> need 20 bytes of
# zero padding past the last valid position so speculative reads of
# masked-off lanes stay in bounds
_PAD = 20


def _word_plane(buf_ref):
    """Precompute, once per block, the big-endian int32 word STARTING
    at every byte position: w32[r, l] = b[l]<<24 | b[l+1]<<16 |
    b[l+2]<<8 | b[l+3] (the vectorized restatement of
    lib/jute-buffer.js:102-106).  Static lane rotates are native
    Mosaic ops; the wrap-around at the row tail only touches positions
    >= n - 3, which every reader masks off.  Non-overlapping bit
    planes, so wrapping int32 adds reproduce the signed bit pattern
    exactly."""
    _R, Lp = buf_ref.shape
    b = buf_ref[:].astype(jnp.int32)
    return ((b << 24) + (pltpu.roll(b, Lp - 1, 1) << 16)
            + (pltpu.roll(b, Lp - 2, 1) << 8)
            + pltpu.roll(b, Lp - 3, 1))


def _scan_frame(lane, w32, n, cur, bad):
    """One frame step of the cursor scan.  One subtract per step; each
    field read is a single-lane equality select + row-sum over the
    precomputed words — no per-field variable shifts or int multiplies
    in the loop.

    Returns (start, size, (xid, zhi, zlo, err), new_cur, new_bad)."""
    d = lane - cur

    def gather(off):
        return jnp.sum(jnp.where(d == off, w32, 0),
                       axis=1, keepdims=True)

    has_prefix = cur + 4 <= n
    ln = jnp.where(has_prefix, gather(_LEN_OFF), 0)
    is_bad = has_prefix & ((ln < 0) | (ln > MAX_PACKET))
    complete = (has_prefix & ~is_bad & (bad == 0)
                & (cur + 4 + ln <= n))
    start = jnp.where(complete, cur + 4, -1)
    size = jnp.where(complete, ln, 0)
    # header fields only exist when the body holds the full 16-byte
    # reply header; shorter complete frames are protocol violations
    # surfaced via size (pipeline flags them as short)
    hdr_ok = complete & (ln >= 16)
    fields = tuple(jnp.where(hdr_ok, gather(off), 0)
                   for off in (_XID_OFF, _ZHI_OFF, _ZLO_OFF, _ERR_OFF))
    return (start, size, fields,
            jnp.where(complete, cur + 4 + ln, cur),
            bad | is_bad.astype(jnp.int32))


def _kernel(buf_ref, len_ref, starts_ref, sizes_ref, xid_ref,
            zhi_ref, zlo_ref, err_ref, resid_ref, bad_ref,
            *, max_frames: int):
    """Scan one [R, Lp] uint8 block; emit [F, R] frame/header planes."""
    R, Lp = buf_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, Lp), 1)
    n = len_ref[:]  # [R, 1]
    w32 = _word_plane(buf_ref)

    def step(j, carry):
        cur, bad = carry  # bad is int32 0/1 (Mosaic-friendly carry)
        start, size, (xid, zhi, zlo, err), cur, bad = _scan_frame(
            lane, w32, n, cur, bad)
        row = pl.ds(j, 1)
        starts_ref[row, :] = start.reshape(1, R)
        sizes_ref[row, :] = size.reshape(1, R)
        xid_ref[row, :] = xid.reshape(1, R)
        zhi_ref[row, :] = zhi.reshape(1, R)
        zlo_ref[row, :] = zlo.reshape(1, R)
        err_ref[row, :] = err.reshape(1, R)
        return (cur, bad)

    cur0 = jnp.zeros((R, 1), jnp.int32)
    bad0 = jnp.zeros((R, 1), jnp.int32)
    cur, bad = jax.lax.fori_loop(0, max_frames, step, (cur0, bad0))
    resid_ref[0, :] = cur.reshape(R)
    bad_ref[0, :] = bad.reshape(R)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


#: Mosaic's scoped-VMEM ceiling per kernel program, by the
#: ``device_kind`` JAX reports.  One entry per device the kernels have
#: been compiled for; the compiler states the figure itself when a
#: program exceeds it ("limit 16.00M", libtpu 0.0.34).  A device that
#: is not in the table is an error, not a default.
_SCOPED_VMEM_BYTES = {
    'TPU v5 lite': 16 * 1024 * 1024,
}


def scoped_vmem_limit(device_kind: str | None = None) -> int:
    """The scoped-VMEM ceiling the guard holds a kernel program to on
    ``device_kind`` (default: the device the computation is being
    traced for, utils/platform.target_device)."""
    if device_kind is None:
        from ..utils.platform import target_device
        device_kind = target_device().device_kind
    try:
        return _SCOPED_VMEM_BYTES[device_kind]
    except KeyError:
        raise ValueError(
            'no scoped-VMEM ceiling on record for device kind %r: the '
            'Pallas kernels have only been sized for %s'
            % (device_kind, sorted(_SCOPED_VMEM_BYTES))) from None


#: (output words/frame, live [R, 1] columns) of the header kernel, for
#: the VMEM guard: the 6 tick planes out; under 3 columns measured
_SCAN_SIZES = (6, 4)


def _vmem_estimate(R: int, Bp: int, Lp: int, max_frames: int,
                   words_per_frame: int, columns: int) -> int:
    """Upper bound on the scoped-VMEM bytes of one kernel program,
    fitted to what the installed Mosaic (libtpu 0.0.34) allocates for
    ``TPU v5 lite`` — found by bisecting ``vmem_limit_bytes`` per shape
    on the compile-only topology (tests/test_compile_v5e.py holds the
    guard to it):

    - 4 int32 planes of [R, Lp] live at once (byte plane, rolled word
      plane, lane iota, select temporaries): 4.0x the plane in every
      shape measured;
    - ``columns`` [R, 1] int32 values live across the frame loop, each
      padded to a full 128-lane tile (R x 512 B);
    - on a multi-block grid, the double-buffered u8 input block and
      the double-buffered per-frame output blocks.  (Mosaic only
      charges the input block from Lp >= 7296 at R=128 — 16.38 MiB
      there against 14.36 at Lp=7168 — so counting it always refuses
      Lp 7040-7168, which would compile; a single block is charged
      neither);
    - 256 KiB of slack on top.

    The previous fit (3.2x the plane + outputs + 1 MiB, calibrated on
    an older Mosaic) admitted shapes this compiler refuses: R=128,
    Lp=7296, F=64 estimated 12.59 MiB, needs 16.57."""
    est = 4 * R * Lp * 4 + columns * R * 512 + (256 << 10)
    if Bp > R:
        est += 2 * R * Lp + 2 * words_per_frame * max_frames * R * 4
    return est


def _block_shape(B: int, L: int, block_rows: int,
                 interpret: bool = False) -> tuple[int, int, int]:
    """(R, Bp, Lp) blocking for one kernel program.  Mosaic tiling: the
    [F, R] output blocks put rows on the lane axis, so a multi-block
    grid needs R % 128 == 0; a single block spanning the whole (padded)
    batch is exempt.  Shared by the compile path and fits_vmem so the
    guard can never drift from the actual blocking."""
    if interpret:
        R = min(block_rows, _round_up(B, 8))
        Bp = _round_up(B, R)
    elif B <= block_rows:
        R = Bp = _round_up(B, 8)
    else:
        R = _round_up(block_rows, 128)
        Bp = _round_up(B, R)
    return R, Bp, _round_up(L + _PAD, 128)


def _check_vmem(kernel: str, R: int, Bp: int, Lp: int, max_frames: int,
                words: int, columns: int) -> None:
    """The kernel's compile guard: a readable error where Mosaic would
    answer RESOURCE_EXHAUSTED."""
    need = _vmem_estimate(R, Bp, Lp, max_frames, words, columns)
    limit = scoped_vmem_limit()
    if need > limit:
        raise ValueError(
            '%s: one program of R=%d rows x Lp=%d bytes x %d frames '
            '(%d words/frame) needs ~%.1f MiB of scoped VMEM (> %d MiB '
            'on this device); shrink block_rows or L, or use '
            'the jnp pipeline, which has no such bound'
            % (kernel, R, Lp, max_frames, words, need / 2**20,
               limit >> 20))


def fits_vmem(B: int, L: int, max_frames: int = 32,
              block_rows: int = 64,
              device_kind: str | None = None) -> bool:
    """Whether :func:`pallas_wire_scan` compiles for this shape inside
    ``device_kind``'s scoped-VMEM ceiling (default: the device being
    traced for)."""
    R, Bp, Lp = _block_shape(B, L, block_rows)
    return (_vmem_estimate(R, Bp, Lp, max_frames, *_SCAN_SIZES)
            <= scoped_vmem_limit(device_kind))


@functools.partial(
    jax.jit, static_argnames=('max_frames', 'block_rows', 'interpret'))
def pallas_wire_scan(buf, lens, max_frames: int = 32,
                     block_rows: int = 64, interpret: bool = False):
    """Fused frame scan + header parse on TPU via Pallas.

    Args:
      buf: uint8 [B, L] accumulated bytes per connection.
      lens: int32 [B] valid byte counts (beyond L for a header row, as
        in ``frame_cursor_scan``: no lane matches a cursor past the row).
      max_frames: static per-stream frame bound.
      block_rows: streams per kernel program (grid = B / block_rows).
      interpret: run in the Pallas interpreter (for CPU-based tests).

    Returns:
      dict with int32 [B, F] planes ``starts``, ``sizes``, ``xid``,
      ``zxid_hi``, ``zxid_lo``, ``err``; int32 [B] ``counts`` and
      ``resid``; bool [B] ``bad`` — field-for-field the outputs of
      ``frame_cursor_scan`` + ``parse_reply_headers``.
    """
    B, L = buf.shape
    R, Bp, Lp = _block_shape(B, L, block_rows, interpret)
    if not interpret:
        _check_vmem('pallas_wire_scan', R, Bp, Lp, max_frames,
                    *_SCAN_SIZES)

    buf = jnp.zeros((Bp, Lp), jnp.uint8).at[:B, :L].set(buf)
    lens = jnp.zeros((Bp, 1), jnp.int32).at[:B, 0].set(
        lens.astype(jnp.int32))

    kern = functools.partial(_kernel, max_frames=max_frames)
    plane = jax.ShapeDtypeStruct((max_frames, Bp), jnp.int32)
    rowvec = jax.ShapeDtypeStruct((1, Bp), jnp.int32)
    grid = (Bp // R,)
    in_specs = [
        pl.BlockSpec((R, Lp), lambda i: (i, 0)),
        pl.BlockSpec((R, 1), lambda i: (i, 0)),
    ]
    plane_spec = pl.BlockSpec((max_frames, R), lambda i: (0, i))
    row_spec = pl.BlockSpec((1, R), lambda i: (0, i))

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=(plane_spec,) * 6 + (row_spec, row_spec),
        out_shape=(plane,) * 6 + (rowvec, rowvec),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',)),
        interpret=interpret,
    )(buf, lens)
    starts, sizes, xid, zhi, zlo, err, resid, bad = out

    def unpad(p):
        return jnp.moveaxis(p, 0, 1)[:B]

    starts = unpad(starts)
    return {
        'starts': starts,
        'sizes': unpad(sizes),
        'xid': unpad(xid),
        'zxid_hi': unpad(zhi),
        'zxid_lo': unpad(zlo),
        'err': unpad(err),
        'counts': jnp.sum((starts >= 0).astype(jnp.int32), axis=1),
        'resid': resid[0, :B],
        'bad': bad[0, :B].astype(jnp.bool_),
    }
