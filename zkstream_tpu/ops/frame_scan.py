"""Frame-boundary discovery as array programs.

The reference finds frame boundaries with a sequential accumulator loop
— read 4-byte length, slice, repeat (lib/zk-streams.js:39-64), guarding
length < 0 or > 16 MiB (lib/zk-streams.js:23,47-53).  Two TPU-shaped
reformulations live here:

``frame_cursor_scan``
    Decodes a *batch* of independent streams in lockstep: one
    ``lax.scan`` step advances every stream's cursor by its current
    frame length, so the scan length is max-frames-per-stream while the
    work per step is vectorized across the whole batch.  This is the
    server-fleet shape: thousands of connections, each with a handful
    of frames per network tick.

``frame_starts_pointer_doubling``
    Finds every frame of a *single* long stream in O(log L) parallel
    steps.  Every byte position i speculatively computes its successor
    "if a frame started here, the next would start at i + 4 + len(i)";
    frame starts are then exactly the positions reachable from 0 in the
    successor graph, computed by pointer doubling (scatter-or of a
    reachability mask while squaring the successor map).  The
    sequential chain the reference walks one frame at a time becomes a
    log-depth gather/scatter cascade.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

from .bytesops import be_i32_at

# single source of truth shared with the scalar FrameDecoder
# (reference: lib/zk-streams.js:23); protocol.consts imports no JAX
from ..protocol.consts import MAX_PACKET


def frame_cursor_scan(buf, lens, max_frames: int):
    """Lockstep frame scan over a batch of streams.

    Args:
      buf: uint8 [B, L] — each row is one connection's accumulated bytes.
      lens: int32 [B] — valid byte count per row.  It may exceed L
        for a row that holds the first bytes of exactly ONE frame (the
        fleet ingest's header row, io/ingest.py): the first step finds
        the frame whole from its prefix and moves the cursor to its
        end, where no step finds another; the gathers clip to the row.
      max_frames: static bound on frames per stream (scan length).

    Returns:
      starts: int32 [B, max_frames] — body start offset per frame, -1
        where no frame.
      sizes: int32 [B, max_frames] — body length per frame, 0 where none.
      counts: int32 [B] — complete frames found per stream.
      bad: bool [B] — a negative/oversized length prefix was seen
        (the BAD_LENGTH protocol error, lib/zk-streams.js:47-53).
      resid: int32 [B] — cursor after the last complete frame (bytes
        from here to ``lens`` are a partial frame to keep buffered).
    """
    B, L = buf.shape
    lens = lens.astype(jnp.int32)

    def step(carry, _):
        cur, bad = carry
        has_prefix = cur + 4 <= lens
        ln = be_i32_at(buf, cur)
        ln = jnp.where(has_prefix, ln, 0)
        is_bad = has_prefix & ((ln < 0) | (ln > MAX_PACKET))
        complete = has_prefix & ~is_bad & ~bad & (cur + 4 + ln <= lens)
        start = jnp.where(complete, cur + 4, -1)
        size = jnp.where(complete, ln, 0)
        nxt = jnp.where(complete, cur + 4 + ln, cur)
        return (nxt, bad | is_bad), (start, size)

    # init carry derived from `lens` (not fresh constants) so that under
    # shard_map the carry is varying over the mesh axis from the start,
    # matching the loop body's output types
    init = (lens * 0, lens < 0)
    (resid, bad), (starts, sizes) = lax.scan(
        step, init, None, length=max_frames)
    starts = jnp.moveaxis(starts, 0, 1)
    sizes = jnp.moveaxis(sizes, 0, 1)
    counts = jnp.sum((starts >= 0).astype(jnp.int32), axis=1)
    return starts, sizes, counts, bad, resid


def frame_starts_pointer_doubling(buf, n):
    """All frame starts of one stream in O(log L) parallel steps.

    Args:
      buf: uint8 [L] — a single stream's bytes.
      n: int32 scalar — valid byte count.

    Returns:
      is_start: bool [L] — True at each offset where a complete frame's
        4-byte length prefix begins.
      bad: bool — a reachable position had an invalid length prefix.

    The successor map saturates at sentinel L for incomplete/invalid
    positions, so reachability never escapes the buffer.  Positions
    past a bad prefix are unreachable, matching the sequential
    decoder's stop-at-error behavior.
    """
    L = buf.shape[0]
    idx = jnp.arange(L, dtype=jnp.int32)
    ln = be_i32_at(buf[None, :], idx[None, :])[0]
    has_prefix = idx + 4 <= n
    ln = jnp.where(has_prefix, ln, 0)
    invalid = has_prefix & ((ln < 0) | (ln > MAX_PACKET))
    complete = has_prefix & ~invalid & (idx + 4 + ln <= n)
    succ = jnp.where(complete, idx + 4 + ln, L).astype(jnp.int32)

    # Reachability from position 0 by pointer doubling: after k rounds
    # every position within 2^k frame-hops of 0 is marked.
    f = jnp.concatenate([succ, jnp.array([L], jnp.int32)])  # f[L] = L
    reach = jnp.zeros((L + 1,), jnp.bool_).at[0].set(True)
    rounds = max(1, math.ceil(math.log2(max(2, L))))

    def body(_, carry):
        f, reach = carry
        # scatter-or: mark f[i] reachable wherever i is, then square f
        reach = reach.at[f[:-1]].max(reach[:-1])
        f = f[f]
        return f, reach

    f, reach = lax.fori_loop(0, rounds, body, (f, reach))
    is_start = reach[:L] & complete
    bad = jnp.any(reach[:L] & invalid)
    return is_start, bad
