"""The flagship jittable step: batched wire decode for a stream fleet.

One call = one "network tick" for B connections: slice every complete
frame out of every stream, parse every reply header, route by xid, and
reduce the per-stream session checkpoints — the vectorized equivalent
of running the reference's decode loop (lib/zk-streams.js:39-99) and
connected-state drain (lib/connection-fsm.js:213-229) once per
connection, but as a single fused XLA computation with static shapes.

This is the unit the driver compile-checks (see __graft_entry__.py) and
the benchmark times as ``jit_step``.  Two equivalent implementations:
``wire_pipeline_step`` (pure jnp/lax — runs anywhere; the XLA scan
gathers only the ~20 header bytes per frame, so it is the fast path on
TPU v5e) and ``wire_pipeline_step_pallas`` (the scan + header parse
fused into one Mosaic kernel, ops/pallas_scan.py — a single
custom-call, worth it when per-op dispatch overhead dominates); both
share :func:`_assemble` so the routing/stats semantics cannot diverge.
:func:`auto_impl` is the one place that chooses between them; a
function named ``*_pallas`` runs the kernel or raises.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .frame_scan import frame_cursor_scan
from .headers import parse_reply_headers, stream_stats


class WireStats(NamedTuple):
    """Per-stream results of one pipeline step (all shaped [B] unless
    noted)."""

    starts: jnp.ndarray        # int32 [B, F] frame body offsets, -1 pad
    sizes: jnp.ndarray         # int32 [B, F] frame body lengths
    xids: jnp.ndarray          # int32 [B, F] reply xids (0 where pad)
    errs: jnp.ndarray          # int32 [B, F] reply error codes
    zxid_hi: jnp.ndarray       # int32 [B, F] per-reply zxid, high word
    zxid_lo: jnp.ndarray       # int32 [B, F] per-reply zxid, low word
    n_frames: jnp.ndarray      # int32 [B]
    n_replies: jnp.ndarray     # int32 [B]
    n_notifications: jnp.ndarray  # int32 [B]
    n_pings: jnp.ndarray       # int32 [B]
    n_errors: jnp.ndarray      # int32 [B]
    max_zxid_hi: jnp.ndarray   # int32 [B] session checkpoint, high word
    max_zxid_lo: jnp.ndarray   # int32 [B] session checkpoint, low word
    bad: jnp.ndarray           # bool [B] BAD_LENGTH or short-frame seen
    resid: jnp.ndarray         # int32 [B] partial-frame cursor


def _assemble(headers, starts, sizes, counts, bad, resid) -> WireStats:
    """Shared tail of both pipeline variants: routing reductions over
    parsed headers + WireStats assembly.  A frame too short to hold the
    16-byte reply header is a protocol violation (scalar codec:
    BAD_DECODE) — flagged via ``bad``, never misparsed."""
    stats = stream_stats(headers)
    return WireStats(
        starts=starts,
        sizes=sizes,
        xids=headers['xid'],
        errs=headers['err'],
        zxid_hi=headers['zxid_hi'],
        zxid_lo=headers['zxid_lo'],
        n_frames=counts,
        n_replies=stats['n_replies'],
        n_notifications=stats['n_notifications'],
        n_pings=stats['n_pings'],
        n_errors=stats['n_errors'],
        max_zxid_hi=stats['max_zxid_hi'],
        max_zxid_lo=stats['max_zxid_lo'],
        bad=bad | jnp.any(headers['short'], axis=1),
        resid=resid,
    )


def _stats_from_scan(r) -> WireStats:
    """WireStats from a Pallas scan-result dict."""
    valid = r['starts'] >= 0
    short = valid & (r['sizes'] < 16)
    headers = {
        'valid': valid & ~short,
        'short': short,
        'xid': r['xid'],
        'zxid_hi': r['zxid_hi'],
        'zxid_lo': r['zxid_lo'],
        'err': r['err'],
    }
    return _assemble(headers, r['starts'], r['sizes'], r['counts'],
                     r['bad'], r['resid'])


def wire_pipeline_step_pallas(buf, lens, max_frames: int = 32,
                              block_rows: int = 64,
                              interpret: bool = False) -> WireStats:
    """Same step as :func:`wire_pipeline_step`, with the scan + header
    parse fused into one Pallas kernel (ops/pallas_scan.py); only the
    cheap [B, F] -> [B] routing reductions remain as XLA ops.

    A shape whose kernel would exceed the device's scoped-VMEM ceiling
    raises (``pallas_wire_scan``'s guard): callers that want the jnp
    pipeline there ask :func:`auto_impl`."""
    from .pallas_scan import pallas_wire_scan

    r = pallas_wire_scan(buf, lens, max_frames=max_frames,
                         block_rows=block_rows, interpret=interpret)
    return _stats_from_scan(r)


def wire_pipeline_step(buf, lens, max_frames: int = 32) -> WireStats:
    """Decode one tick of B streams.

    Args:
      buf: uint8 [B, L] accumulated bytes per connection.
      lens: int32 [B] valid byte counts.
      max_frames: static per-stream frame bound for this tick.
    """
    # named scopes are metadata only: a kept profiler trace names the
    # program's ops by stage, the compiled code is the same
    with jax.named_scope('frame_scan'):
        starts, sizes, counts, bad, resid = frame_cursor_scan(
            buf, lens, max_frames)
    with jax.named_scope('header_gather'):
        headers = parse_reply_headers(buf, starts, sizes)
        return _assemble(headers, starts, sizes, counts, bad, resid)


def _pallas_pocket(B: int, max_frames: int) -> bool:
    """The shape region where the fused kernel (block_rows=64) was
    once recorded ahead of the jnp pipeline on a v5e, on older code
    and another installation; level or behind everywhere else, so jnp
    is the default.  The table has not been re-measured on the
    installed compiler and no benchmark cell reaches it; ROADMAP S10(b)
    does that and keeps or deletes it.

    Caveat: under ``shard_map`` (parallel/fleet.py) ``B`` here is the
    per-shard LOCAL batch (global B / dp), while the pocket was
    measured on single-device global shapes — so a mesh ingest enters
    the pocket when each device's shard is itself pocket-sized, which
    is the per-device work the measurement actually bounds (the kernel
    runs per shard).  Perf-only either way: both paths are
    property-tested equivalent."""
    return max_frames >= 32 and 4096 <= B <= 16384


#: rows per kernel program the auto-dispatch runs the kernel at (the
#: pocket's measured configuration)
_AUTO_BLOCK_ROWS = 64

#: header-scan implementations by the name :func:`auto_impl` returns
WIRE_STEP_IMPLS = {
    'jnp': wire_pipeline_step,
    'pallas': functools.partial(wire_pipeline_step_pallas,
                                block_rows=_AUTO_BLOCK_ROWS),
}


def auto_impl(B: int, L: int, max_frames: int) -> str:
    """Name the *measured* winner for this shape on the device the
    computation is being traced for (utils/platform.target_device):
    ``'pallas'`` inside the kernel's recorded win pocket on TPU where
    the kernel also fits the device's scoped VMEM, ``'jnp'`` everywhere
    else — and on every non-TPU platform, where Mosaic cannot lower.
    Trace-time (shapes are static under jit); both are property-tested
    equivalent.  Returning the name, not the result, is what lets a
    caller record which implementation its program was built from."""
    from ..utils.platform import target_device

    dev = target_device()
    if dev.platform == 'tpu' and _pallas_pocket(B, max_frames):
        from .pallas_scan import fits_vmem

        if fits_vmem(B, L, max_frames, _AUTO_BLOCK_ROWS,
                     device_kind=dev.device_kind):
            return 'pallas'
    return 'jnp'


def wire_pipeline_step_auto(buf, lens, max_frames: int = 32) -> WireStats:
    """:func:`auto_impl`'s choice for this shape, run."""
    impl = auto_impl(buf.shape[0], buf.shape[1], max_frames)
    return WIRE_STEP_IMPLS[impl](buf, lens, max_frames=max_frames)
