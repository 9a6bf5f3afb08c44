"""Send-direction crossover study (VERDICT r3 next #4): could the
batched device encoder (ops/encode.py) beat the host encoders in any
runtime shape this framework actually has?

The two judge-named candidate consumers are measured against their
host-side incumbents:

1. **Server notification fan-out** (server/server.py ``notify``): one
   database change -> N subscribed connections.  Incumbent: encode the
   packet ONCE, share the bytes (one ``encode`` + N buffer appends —
   the appends are the floor ANY implementation pays to hand N sockets
   their bytes).  Device candidate: ``build_reply_streams`` emitting N
   identical notification frames, one dispatch + one readback.

2. **Proxy outbound sweep** (MeshFleetIngest sending its fleet's
   pings / watch re-arms in one tick): N distinct small frames
   (per-connection xids).  Incumbents: the C-extension
   ``encode_request`` and the Python ``JuteWriter`` per frame.  Device
   candidate: the same ``build_reply_streams`` dispatch (header-only
   frames — exactly a ping).

Prints one JSON line per measurement; paste into CROSSOVER.md.  Run
with the default JAX device (TPU under the driver) AND
JAX_PLATFORMS=cpu for the host-backend column.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_host_fanout(n: int, reps: int) -> dict:
    """Encode-once fan-out: the server's actual notify shape."""
    from zkstream_tpu.protocol.framing import PacketCodec

    codec = PacketCodec(server=True)
    codec.handshaking = False
    pkt = {'xid': -1, 'zxid': 12345, 'err': 'OK',
           'opcode': 'NOTIFICATION', 'type': 'DATA_CHANGED',
           'state': 'SYNC_CONNECTED', 'path': '/some/watched/node'}
    sinks = [bytearray() for _ in range(n)]
    t0 = time.perf_counter()
    for _ in range(reps):
        data = codec.encode(dict(pkt))      # encode ONCE
        for s in sinks:                     # the floor: N byte hands
            s += data
    dt = (time.perf_counter() - t0) / reps
    for s in sinks:
        s.clear()
    return {'what': 'host_fanout_encode_once', 'n': n,
            'us_per_event': round(dt * 1e6, 2),
            'ns_per_conn': round(dt / n * 1e9, 1)}


def bench_host_replies(n: int, reps: int, use_ext: bool) -> dict | None:
    """N DISTINCT small frames (per-connection xids) — the proxy
    outbound sweep shape — through the scalar encoders."""
    from zkstream_tpu.protocol.framing import PacketCodec

    kw = {'use_native': True} if use_ext else {'use_native': False}
    try:
        codec = PacketCodec(server=True, **kw)
    except RuntimeError:
        return None
    codec.handshaking = False
    pkts = [{'xid': i + 1, 'zxid': 1000 + i, 'err': 'OK',
             'opcode': 'PING'} for i in range(n)]
    total = 0
    t0 = time.perf_counter()
    for _ in range(reps):
        for p in pkts:
            total += len(codec.encode(p))
    dt = (time.perf_counter() - t0) / reps
    return {'what': 'host_replies_%s' % ('c' if use_ext else 'py'),
            'n': n, 'us_per_tick': round(dt * 1e6, 2),
            'ns_per_frame': round(dt / n * 1e9, 1),
            'mib_s': round(total / reps / dt / (1 << 20), 1)}


def bench_device_batch(n: int, frames: int, reps: int,
                       device=None) -> dict:
    """The batched device encode for the same sweep: field planes in,
    framed streams out, ONE dispatch + ONE readback per tick (the
    readback is the point — the bytes must reach host sockets)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from zkstream_tpu.ops.encode import build_reply_streams

    out_len = frames * 24
    fn = jax.jit(lambda x, zh, zl, e, s: build_reply_streams(
        x, zh, zl, e, s, out_len=out_len))
    xid = np.arange(1, n * frames + 1, dtype=np.int32
                    ).reshape(n, frames)
    zh = np.zeros((n, frames), np.int32)
    zl = np.full((n, frames), 1234, np.int32)
    err = np.zeros((n, frames), np.int32)
    sizes = np.full((n, frames), 16, np.int32)

    import contextlib
    ctx = (jax.default_device(device) if device is not None
           else contextlib.nullcontext())
    with ctx:
        args = [jnp.asarray(a) for a in (xid, zh, zl, err, sizes)]
        buf, lens = fn(*args)
        np.asarray(buf), np.asarray(lens)     # warm + first readback
        t0 = time.perf_counter()
        for _ in range(reps):
            buf, lens = fn(*args)
            np.asarray(buf)                   # bytes must reach host
            np.asarray(lens)
        dt = (time.perf_counter() - t0) / reps
    # e2e variant: the produced bytes must reach N sockets — add the
    # per-row slice handoff every consumer pays after the readback
    sinks = [bytearray() for _ in range(n)]
    with ctx:
        t0 = time.perf_counter()
        for _ in range(reps):
            buf, lens_o = fn(*args)
            host = np.asarray(buf)
            ln = np.asarray(lens_o).tolist()
            mv = memoryview(host).cast('B', (n * out_len,))
            for i in range(n):
                sinks[i] += mv[i * out_len:i * out_len + ln[i]]
        dt_e2e = (time.perf_counter() - t0) / reps
    for s in sinks:
        s.clear()
    plat = (device.platform if device is not None
            else jax.default_backend())
    return {'what': 'device_batch_encode', 'platform': plat,
            'n': n, 'frames': frames,
            'us_per_tick': round(dt * 1e6, 2),
            'us_per_tick_e2e': round(dt_e2e * 1e6, 2),
            'ns_per_frame': round(dt / (n * frames) * 1e9, 1),
            'ns_per_frame_e2e': round(
                dt_e2e / (n * frames) * 1e9, 1)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--conns', default='128,1024')
    ap.add_argument('--frames', type=int, default=1)
    ap.add_argument('--reps', type=int, default=50)
    args = ap.parse_args()

    import jax

    from zkstream_tpu.utils.platform import enable_compile_cache
    enable_compile_cache()

    for n in [int(x) for x in args.conns.split(',')]:
        print(json.dumps(bench_host_fanout(n, args.reps)), flush=True)
        for use_ext in (True, False):
            r = bench_host_replies(n, args.reps, use_ext)
            if r is not None:
                print(json.dumps(r), flush=True)
        print(json.dumps(bench_device_batch(
            n, args.frames, args.reps)), flush=True)
        # the host CPU XLA backend column (what a tick uses once
        # placement resolves to the host)
        try:
            cpu = jax.devices('cpu')[0]
        except Exception:
            cpu = None
        if cpu is not None and jax.default_backend() != 'cpu':
            print(json.dumps(bench_device_batch(
                n, args.frames, args.reps, device=cpu)), flush=True)


if __name__ == '__main__':
    main()
