"""Pallas-vs-jnp crossover sweep for the wire pipeline step.

Times ``wire_pipeline_step_pallas`` (the fused Mosaic kernel) against
``wire_pipeline_step`` (pure jnp/lax) across fleet shapes on the
accelerator (no accelerator, no run), and prints one JSON line per
cell, stamped with the device — the measured basis for the
shape-based auto-dispatch in ops/pipeline.py.  A cell whose shape the
kernel's VMEM guard refuses records the refusal instead of a number.

Every cell is timed before the correctness gates read anything back.

Usage: python tools/sweep_pallas.py [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

FRAME = 104           # 4-byte prefix + 16-byte header + 84-byte body
REPEATS = 20


def fleet(B: int, frames: int, seed: int = 7):
    rng = np.random.RandomState(seed)
    L = frames * FRAME
    v = np.zeros((B, frames, FRAME), np.uint8)

    def be(field, width, out):
        shifts = np.arange(8 * (width - 1), -1, -8, dtype=np.int64)
        out[...] = ((field[..., None] >> shifts) & 0xFF).astype(np.uint8)

    be(np.full((B, frames), FRAME - 4, np.int64), 4, v[:, :, 0:4])
    be(rng.randint(1, 1 << 20, (B, frames)).astype(np.int64), 4,
       v[:, :, 4:8])
    be(rng.randint(1, 1 << 40, (B, frames)).astype(np.int64), 8,
       v[:, :, 8:16])
    v[:, :, 20:] = rng.randint(0, 256, (B, frames, FRAME - 20),
                               dtype=np.uint8)
    return v.reshape(B, L), np.full((B,), L, np.int32)


def _time_candidate(row, name, fn, jb, jl, total, leaf):
    """Shared timing protocol for every candidate (both sweeps):
    jit + warm (exceptions recorded, e.g. the VMEM guard refusing the
    shape), then min-of-3 rounds of REPEATS dispatches holding only a
    tiny leaf per repeat — no full readback until the correctness
    gates at the end.  Returns the warm output or None."""
    import jax

    try:
        step = jax.jit(fn)
        out = step(jb, jl)
        jax.block_until_ready(out)
    except Exception as e:
        row[name] = None
        row[name + '_err'] = repr(e)[:80]
        return None
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        leaves = [leaf(step(jb, jl)) for _ in range(REPEATS)]
        jax.block_until_ready(leaves)
        dts.append((time.perf_counter() - t0) / REPEATS)
    row[name] = round(total / min(dts) / 2**20, 0)
    return out


def run_full(args) -> None:
    """Full-decode confirmation rows (VERDICT r3 next #3): the fused
    Mosaic scan+header+GET_DATA-body kernel vs (a) the equivalent jnp
    GET_DATA-only decode and (b) the full speculative
    parse_reply_bodies — at the header kernel's win pocket and its
    neighbors.  The number decides whether the kernel line lives."""
    import jax.numpy as jnp

    from zkstream_tpu.ops import replies as R
    from zkstream_tpu.ops.pipeline import (
        getdata_bodies_jnp,
        wire_full_decode_pallas,
        wire_pipeline_step,
    )

    MD = 16

    def jnp_getdata(b, l, F):
        # the same work as the fused kernel, expressed as XLA ops
        st = wire_pipeline_step(b, l, max_frames=F)
        return st, getdata_bodies_jnp(b, st, MD)

    def jnp_full(b, l, F):
        st = wire_pipeline_step(b, l, max_frames=F)
        bd = R.parse_reply_bodies(b, st.starts, st.sizes,
                                  max_data=MD, max_path=8)
        return st, bd

    shapes = [(2048, 64), (8192, 64), (32768, 64)]
    if args.quick:
        shapes = [(8192, 64)]
    gates = []
    for B, F in shapes:
        buf, lens = fleet(B, F)
        jb, jl = jnp.asarray(buf), jnp.asarray(lens)
        total = int(lens.sum())
        row = {'B': B, 'frames': F, 'mib': round(total / 2**20, 1),
               **args.stamp, 'what': 'full'}
        outs = {}
        for name, fn in (
                ('pallas-full',
                 lambda b, l, F=F: wire_full_decode_pallas(
                     b, l, max_frames=F, max_data=MD,
                     block_rows=args.block_rows)),
                ('jnp-getdata',
                 lambda b, l, F=F: jnp_getdata(b, l, F)),
                ('jnp-fullspec',
                 lambda b, l, F=F: jnp_full(b, l, F))):
            out = _time_candidate(row, name, fn, jb, jl, total,
                                  lambda o: o[0].n_frames)
            if out is not None:
                outs[name] = out
        if row.get('pallas-full') and row.get('jnp-getdata'):
            row['ratio_vs_getdata'] = round(
                row['pallas-full'] / row['jnp-getdata'], 2)
        if row.get('pallas-full') and row.get('jnp-fullspec'):
            row['ratio_vs_fullspec'] = round(
                row['pallas-full'] / row['jnp-fullspec'], 2)
        print(json.dumps(row), flush=True)
        gates.append((row, outs, B * F))
    # correctness gates after all timing
    for row, outs, want in gates:
        if 'pallas-full' in outs:
            stp, bdp = outs['pallas-full']
            assert int(np.asarray(stp.n_frames).sum()) == want, row
            if 'jnp-getdata' in outs:
                _stj, bdj = outs['jnp-getdata']
                np.testing.assert_array_equal(
                    np.asarray(bdp.data_len), np.asarray(bdj.data_len))
                np.testing.assert_array_equal(
                    np.asarray(bdp.data), np.asarray(bdj.data))
                np.testing.assert_array_equal(
                    np.asarray(bdp.stat_after_data.mzxid_lo),
                    np.asarray(bdj.stat_after_data.mzxid_lo))
    print('# all full-decode gates passed', file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--quick', action='store_true')
    ap.add_argument('--full', action='store_true',
                    help='run the fused full-decode confirmation rows')
    ap.add_argument('--block-rows', type=int, default=128)
    args = ap.parse_args()

    from zkstream_tpu.utils.platform import (
        enable_compile_cache,
        require_accelerator,
    )
    try:
        args.stamp = require_accelerator()
    except RuntimeError as e:
        sys.exit('sweep_pallas.py: %s' % (e,))
    enable_compile_cache()

    if args.full:
        run_full(args)
        return

    import jax.numpy as jnp

    from zkstream_tpu.ops.pipeline import (
        wire_pipeline_step,
        wire_pipeline_step_pallas,
    )

    shapes = [(256, 8), (256, 64), (2048, 8), (2048, 64),
              (8192, 64), (32768, 8), (32768, 64)]
    if args.quick:
        shapes = [(2048, 64), (32768, 64)]

    cells = []
    for B, F in shapes:
        buf, lens = fleet(B, F)
        jb, jl = jnp.asarray(buf), jnp.asarray(lens)
        total = int(lens.sum())
        row = {'B': B, 'frames': F, 'mib': round(total / 2**20, 1),
               **args.stamp}
        for name, fn in (
                ('pallas', lambda b, l, F=F: wire_pipeline_step_pallas(
                    b, l, max_frames=F, block_rows=args.block_rows)),
                ('jnp', lambda b, l, F=F: wire_pipeline_step(
                    b, l, max_frames=F))):
            out = _time_candidate(row, name, fn, jb, jl, total,
                                  lambda o: o.n_frames)
            if out is not None:
                cells.append((row, name, out, B * F))
        if row.get('pallas') and row.get('jnp'):
            row['winner'] = ('pallas' if row['pallas'] > row['jnp']
                             else 'jnp')
            row['ratio'] = round(row['pallas'] / row['jnp'], 2)
        print(json.dumps(row), flush=True)
    # correctness gates last
    for row, name, out, want in cells:
        got = int(np.asarray(out.n_frames).sum())
        assert got == want, (row, name, got, want)
    print('# all decode gates passed', file=sys.stderr)


if __name__ == '__main__':
    main()
