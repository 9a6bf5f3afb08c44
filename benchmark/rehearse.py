#!/usr/bin/env python3
"""Rehearse every cell at toy size on the CPU backend, before any chip
time is spent and after every change to an engine.

    python3 benchmark/rehearse.py                 # every cell, 8 seeds,
                                                  # plain and on 2 cores
    python3 benchmark/rehearse.py --cell hunt3_1k.read --seeds 2
    python3 benchmark/rehearse.py --one hunt3_1k.read --seed 5 [--trace 1]
                                                  # one run, in-process
    python3 benchmark/rehearse.py --lower         # compile-only v5e
                                                  # lowering of the buckets

Each run is a process of its own (``--one``), the same harness code
path as ``run.py`` with the configuration's and the mix's ``toy``
overrides merged in and the ingest placed on the host.  The result is
printed as a ``# rehearsal`` line stamped with the platform (``cpu``);
the final-line contract is never printed from here, and nothing here
is a device number.  ``--control NAME`` breaks the timed path
underneath with ``controls/NAME.py`` (an answer altered where it is
produced); the check must then read ``correct: false``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def one(args) -> int:
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import harness

    env = dict(kv.split('=', 1) for kv in args.member_env)
    out = asyncio.run(harness.run_cell(
        args.one, args.seed, args.seconds, bool(args.trace),
        mode='rehearse', t_process=T_PROCESS, member_env=env,
        control=args.control, bench_path=args.bench))
    # NOT the contract's line: a rehearsal on the CPU, at toy size
    print('# rehearsal %s seed=%d platform=%s %s' % (
        args.one, args.seed, out['device']['platform'],
        json.dumps({k: out[k] for k in
                    ('correct', 'attempted', 'failed', 'metrics')})),
        flush=True)
    return 0


def sweep(args) -> int:
    bench = json.load(open(args.bench or os.path.join(
        ROOT, 'BENCHMARK.json')))
    cells = ([args.cell] if args.cell
             else [w['name'] for w in bench['workloads']])
    pins = [None]
    if shutil.which('taskset') and not args.no_pin:
        pins.append('0,1')
    bad = 0
    for cell in cells:
        for pin in pins:
            for seed in range(args.seeds):
                seed_n = (2 ** 31 - 1) * (seed % 2) + 1000 + seed
                cmd = [sys.executable, os.path.abspath(__file__),
                       '--one', cell, '--seed', str(seed_n),
                       '--seconds', str(args.seconds),
                       '--trace', str(seed % 2)]
                if args.bench:
                    cmd += ['--bench', args.bench]
                if pin:
                    cmd = ['taskset', '-c', pin] + cmd
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=600)
                last = (r.stdout.strip().splitlines() or [''])[-1]
                ok = (r.returncode == 0 and '"correct": true' in last
                      and '"failed": 0' in last)
                left = subprocess.run(
                    ['pgrep', '-f', 'member_worker.py'],
                    capture_output=True, text=True).stdout.split()
                print('%s %-22s pin=%-4s seed=%-11d %5.1fs rc=%d '
                      'leftover=%d %s' % (
                          'ok  ' if ok and not left else 'FAIL', cell,
                          pin or '-', seed_n, time.time() - t0,
                          r.returncode, len(left), last[:160]),
                      flush=True)
                if not ok or left:
                    bad += 1
                    sys.stdout.write(r.stdout[-3000:])
                    sys.stdout.write(r.stderr[-3000:])
    print('rehearsal: %d failed' % (bad,))
    return 1 if bad else 0


def lower(args) -> int:
    """Compile the cells' tick buckets for a described (not attached)
    v5e: what the chip's compiler refuses, it refuses here."""
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    from zkstream_tpu.io.ingest import FleetIngest

    bench = json.load(open(os.path.join(ROOT, 'BENCHMARK.json')))
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    chip = SingleDeviceSharding(topo.devices[0])
    seen = set()
    for w in bench['workloads']:
        cfg = harness.Cell(w['name']).config
        key = json.dumps(cfg['ingest'], sort_keys=True)
        if key in seen:
            continue
        seen.add(key)
        ingest = FleetIngest(placement='host', **cfg['ingest'])
        fn = ingest._step_fn()
        for bp in harness.buckets(int(cfg['sessions'])):
            t0 = time.time()
            length = int(cfg['ingest']['min_len'])
            fn.lower(jax.ShapeDtypeStruct((bp, length), jnp.uint8,
                                          sharding=chip),
                     jax.ShapeDtypeStruct((bp,), jnp.int32,
                                          sharding=chip)).compile()
            print('lowered %s [%d, %d] for %s in %.1fs' % (
                w['config'], bp, length, topo.devices[0].device_kind,
                time.time() - t0), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--one', metavar='CELL')
    ap.add_argument('--cell')
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--seeds', type=int, default=8)
    ap.add_argument('--seconds', type=float, default=3.0)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--control', metavar='NAME',
                    help='break the timed path underneath with '
                         'controls/NAME.py')
    ap.add_argument('--member-env', action='append', default=[])
    ap.add_argument('--bench', help='another BENCHMARK.json (the '
                    'throw-away cell of the acceptance test)')
    ap.add_argument('--no-pin', action='store_true')
    ap.add_argument('--lower', action='store_true')
    args = ap.parse_args()
    if args.lower:
        return lower(args)
    if args.one:
        return one(args)
    return sweep(args)


if __name__ == '__main__':
    sys.exit(main())
