"""Gather-based big-endian field extraction over uint8 tensors.

The Jute wire format is big-endian throughout (reference:
lib/jute-buffer.js:102-125).  These helpers read int32 / int64 fields at
arbitrary (batched) byte offsets out of uint8 buffers using four/eight
one-byte gathers plus shift-or assembly — fully vectorized, no byte
loops.

64-bit fields (zxid, sessionId, timestamps) are represented as
``(hi, lo)`` int32 pairs.  The reference faces the same problem — Node
pre-BigInt has no int64 — and solves it with jsbn BigInteger
(lib/jute-buffer.js:63-77); on TPU the natural carrier is a pair of
32-bit lanes, with unsigned comparison built from the sign-flip trick.
All offset gathers are clamped so speculative lanes (masked-off frames)
stay in bounds.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# 0x80000000 as an int32 bit pattern.  A numpy scalar, NOT jnp: a
# module-level jnp scalar is a device array that jit captures as a
# buffer constant (an extra operand on every dispatch, and a backend
# touch at import); a np scalar inlines into the HLO as a literal.
_SIGN = np.int32(-0x80000000)


def _byte_at(buf, off):
    """Gather one byte per offset -> int32.

    ``buf`` is uint8 [..., L]; ``off`` either matches buf's rank (K
    offsets per row, result [..., K]) or has one fewer dim (one offset
    per row, result [...]).
    """
    off = jnp.clip(off.astype(jnp.int32), 0, buf.shape[-1] - 1)
    squeeze = off.ndim == buf.ndim - 1
    if squeeze:
        off = off[..., None]
    out = jnp.take_along_axis(buf, off, axis=-1).astype(jnp.int32)
    return out[..., 0] if squeeze else out


def be_i32_at(buf, off):
    """Read a big-endian int32 at byte offset ``off``.

    ``buf`` is uint8 [..., L]; ``off`` is int32 broadcastable to
    buf.shape[:-1] + (k,) trailing offsets.  Two's-complement wraparound
    of the high-byte shift yields the signed value directly.
    """
    b0 = _byte_at(buf, off)
    b1 = _byte_at(buf, off + 1)
    b2 = _byte_at(buf, off + 2)
    b3 = _byte_at(buf, off + 3)
    return (b0 << 24) | (b1 << 16) | (b2 << 8) | b3


def be_i64pair_at(buf, off):
    """Read a big-endian int64 at ``off`` as an ``(hi, lo)`` int32 pair."""
    return be_i32_at(buf, off), be_i32_at(buf, off + 4)


def _as_unsigned_key(x):
    """Map int32 -> int32 so that signed compare == unsigned compare."""
    return x ^ _SIGN


def u64pair_lt(ah, al, bh, bl):
    """Unsigned 64-bit ``a < b`` on (hi, lo) pairs."""
    ah_u, bh_u = _as_unsigned_key(ah), _as_unsigned_key(bh)
    al_u, bl_u = _as_unsigned_key(al), _as_unsigned_key(bl)
    return (ah_u < bh_u) | ((ah == bh) & (al_u < bl_u))


def u64pair_max(ah, al, bh, bl):
    """Elementwise unsigned 64-bit max on (hi, lo) pairs."""
    a_lt_b = u64pair_lt(ah, al, bh, bl)
    return jnp.where(a_lt_b, bh, ah), jnp.where(a_lt_b, bl, al)


def u64pair_reduce_max(h, l, axis=None):
    """Unsigned 64-bit max-reduce of (hi, lo) int32 pairs along
    ``axis`` (None = all), without a scan: unsigned max of hi, then
    unsigned max of lo among the elements achieving it."""
    uh = h ^ _SIGN
    mh_u = jnp.max(uh, axis=axis, keepdims=True)
    lo_key = jnp.where(uh == mh_u, l ^ _SIGN, _SIGN)
    ml_u = jnp.max(lo_key, axis=axis)
    if axis is None:
        mh_u = mh_u.reshape(())
    else:
        mh_u = jnp.squeeze(mh_u, axis=axis)
    return mh_u ^ _SIGN, ml_u ^ _SIGN


def u64pair_to_int(h, l) -> int:
    """Host-side: collapse a (hi, lo) pair (or arrays thereof) to Python
    int / numpy int64 for interop with the scalar codec."""
    h = (np.asarray(h).astype(np.int64) & 0xFFFFFFFF).astype(np.uint64)
    l = (np.asarray(l).astype(np.int64) & 0xFFFFFFFF).astype(np.uint64)
    out = (h << np.uint64(32)) | l
    return int(out) if out.ndim == 0 else out


def i64pair_to_int(h, l) -> int:
    """Host-side: collapse a (hi, lo) pair to the SIGNED int64 the wire
    carries — the scalar codec's ``read_long`` is ``>q``
    (reference long fields are signed, lib/jute-buffer.js:63-77)."""
    out = np.asarray(u64pair_to_int(h, l), dtype=np.uint64)
    signed = out.view(np.int64)
    return int(signed) if signed.ndim == 0 else signed
