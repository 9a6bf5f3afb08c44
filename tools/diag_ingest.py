"""Diagnose the FleetIngest per-tick latency tail (VERDICT r2 item 2).

Runs the bench's create workload in ingest mode with every tick phase
timed (pad, dispatch+readback, unpack, assemble), then prints the tick
distribution and the worst ticks with their batch shapes — enough to
tell jit shape-bucket churn from dispatch-floor pacing from host
assembly cost.

Usage: python tools/diag_ingest.py [clients] [ops_per_client]
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The tick latency being diagnosed is the host CPU backend's (the
# placement every CROSSOVER.md cell ran): a host-path tool, pinned to
# the CPU before jax initializes so it never takes the chip.
from zkstream_tpu.utils.platform import force_cpu  # noqa: E402

force_cpu(n_devices=1)

TICKS: list[dict] = []


def instrument(FleetIngest):
    def wrap_execs(self):
        for key, ex in list(self._exec.items()):
            if ex is None or getattr(ex, '_diag', False):
                continue

            def timed(*a, _inner=ex, _key=key):
                t0 = time.perf_counter()
                out = _inner(*a)
                TICKS.append({'kind': 'exec_call',
                              'dt': time.perf_counter() - t0,
                              'shape': _key[1:]})
                return out
            timed._diag = True
            self._exec[key] = timed

    orig_tick = FleetIngest._tick

    def _tick(self):
        wrap_execs(self)
        n_bufs = sum(1 for _c, b in self._slots.values() if b)
        nbytes = sum(len(b) for _c, b in self._slots.values())
        t0 = time.perf_counter()
        orig_tick(self)
        TICKS.append({'kind': 'tick', 'dt': time.perf_counter() - t0,
                      'n_bufs': n_bufs, 'nbytes': nbytes,
                      'ticks': self.ticks,
                      'scalar': self.ticks_scalar})
    FleetIngest._tick = _tick


async def run(n_clients: int, n_ops: int) -> None:
    from zkstream_tpu import Client
    from zkstream_tpu.io.ingest import FleetIngest
    from zkstream_tpu.server import ZKServer

    instrument(FleetIngest)
    # placement='host': the tick latency being diagnosed is the host
    # CPU backend's
    ingest = FleetIngest(body_mode='host', max_frames=16,
                         bypass_bytes=0, placement='host')
    srv = await ZKServer().start()
    clients = [Client(address='127.0.0.1', port=srv.port,
                      session_timeout=30000, ingest=ingest)
               for _ in range(n_clients)]
    for c in clients:
        c.start()
    await asyncio.gather(*[c.wait_connected(timeout=30)
                           for c in clients])
    await clients[0].create('/b', b'x' * 64)
    for bp in (8, 16, n_clients):
        await ingest.prewarm(bp)
    for _ in range(5):
        await asyncio.gather(*[c.get('/b') for c in clients])
    TICKS.clear()

    loop = asyncio.get_running_loop()
    lat: list[float] = []

    async def one(c, i):
        for s in range(n_ops):
            t0 = loop.time()
            await c.create('/c%d-%d' % (i, s), b'')
            lat.append((loop.time() - t0) * 1000.0)
    t0 = loop.time()
    await asyncio.gather(*[one(c, i) for i, c in enumerate(clients)])
    dt = loop.time() - t0
    lat.sort()
    print(f'create: {len(lat)/dt:.1f} ops/s  '
          f'p50={lat[len(lat)//2]*1:.3f} ms  '
          f'p99={lat[int(len(lat)*0.99)]:.3f} ms  '
          f'max={lat[-1]:.3f} ms')
    await asyncio.gather(*[c.close() for c in clients])
    await srv.stop()

    ticks = [t for t in TICKS if t['kind'] == 'tick']
    jits = [t for t in TICKS if t['kind'] == 'exec_call']
    ticks.sort(key=lambda t: -t['dt'])
    print(f'{len(ticks)} ticks, {len(jits)} exec calls')
    shapes: dict = {}
    for j in jits:
        shapes.setdefault(j['shape'], []).append(j['dt'] * 1e3)
    for sh, dts in sorted(shapes.items()):
        dts.sort()
        print(f'  exec shape {sh}: n={len(dts)} first={dts[-1]:.1f}ms '
              f'p50={dts[len(dts)//2]*1:.2f}ms')
    print('worst 10 ticks:')
    for t in ticks[:10]:
        print(f'  dt={t["dt"]*1e3:8.2f} ms  n_bufs={t["n_bufs"]:4d} '
              f'bytes={t["nbytes"]:6d}')


if __name__ == '__main__':
    n_clients = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    n_ops = int(sys.argv[2]) if len(sys.argv) > 2 else 15
    asyncio.run(run(n_clients, n_ops))
