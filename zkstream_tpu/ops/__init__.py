"""Tensor wire-codec ops — the TPU data plane.

The reference client's hot path is a scalar byte loop: slice 4-byte
big-endian length prefixes out of a TCP stream (lib/zk-streams.js:39-64)
and dispatch each frame on its reply header (lib/connection-fsm.js:213-229).
This package re-states that work as array programs so a fleet of
connection streams can be decoded in one fused XLA computation:

- :mod:`bytesops` — gather-based big-endian field extraction, with
  64-bit protocol fields (zxid, sessionId) carried as (hi, lo) int32
  pairs: the same move the reference makes with jsbn BigInteger for
  pre-BigInt Node (lib/jute-buffer.js:63-77), chosen here because TPU
  vector lanes are 32-bit native.
- :mod:`frame_scan` — frame-boundary discovery: a lockstep cursor scan
  vectorized across a batch of streams, and a pointer-doubling
  reachability scan that finds every frame of a single long stream in
  O(log L) parallel steps.
- :mod:`headers` — batched reply-header parse (xid / zxid / err) and
  the per-stream reductions the session layer needs (max zxid seen,
  notification counts) (lib/zk-session.js:229-235).
- :mod:`pipeline` — the flagship jittable step combining all of the
  above for a [batch, stream_len] tensor of raw connection bytes.
- :mod:`encode` — the inverse direction: batched field planes ->
  length-prefixed reply streams (the tensor restatement of the scalar
  codec's isServer encode mode, lib/zk-streams.js:121-148).
"""

from .encode import build_reply_streams
from .bytesops import (
    be_i32_at,
    be_i64pair_at,
    u64pair_max,
    u64pair_lt,
    u64pair_reduce_max,
)
from .frame_scan import (
    MAX_PACKET,
    frame_cursor_scan,
    frame_starts_pointer_doubling,
)
from .headers import parse_reply_headers, stream_stats
from .pipeline import (
    WireStats,
    wire_pipeline_step,
    wire_pipeline_step_auto,
)

__all__ = [
    'MAX_PACKET',
    'build_reply_streams',
    'be_i32_at',
    'be_i64pair_at',
    'u64pair_max',
    'u64pair_lt',
    'u64pair_reduce_max',
    'frame_cursor_scan',
    'frame_starts_pointer_doubling',
    'parse_reply_headers',
    'stream_stats',
    'WireStats',
    'wire_pipeline_step',
    'wire_pipeline_step_auto',
]
