#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of ``BENCHMARK.json``
on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

A new process that owns the chip: it spawns the ensemble (OS processes
that never import JAX) before its first JAX call, fails — non-zero
exit, no result line — when JAX finds no TPU or fewer chips than the
cell asks for (it neither sets nor trusts ``JAX_PLATFORMS``), loads the
data from ``--seed``, warms up the cell's tick programs through the
compile cache, measures for ``--seconds``, drains for a bounded time,
checks every answer against the plain reference and prints, as the
last line of its standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``).  With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.
Everything else it has to say goes on earlier lines, which start with
``#``.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--member-env', action='append', default=[],
                    metavar='KEY=VALUE',
                    help="added to the members' environment for this "
                         'run only (the degraded controls)')
    ap.add_argument('--control', metavar='NAME',
                    help='break the timed path underneath with '
                         'controls/NAME.py: the check must read '
                         'correct false (never given by the driver)')
    ap.add_argument('--keep-trace', metavar='DIR',
                    help='copy the raw trace and a summary there')
    args = ap.parse_args()
    # the program lives beside the benchmark; without it (a directory
    # that holds only BENCHMARK.json and the benchmark's files) there
    # is nothing to measure
    if not os.path.isfile(os.path.join(ROOT, 'zkstream_tpu',
                                       '__init__.py')):
        print('benchmark/run.py: the program (zkstream_tpu/) is not in '
              '%s' % (ROOT,), file=sys.stderr)
        return 2
    import harness

    env = dict(kv.split('=', 1) for kv in args.member_env)
    try:
        out = asyncio.run(harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            mode='chip', t_process=T_PROCESS, member_env=env,
            control=args.control, keep_trace=args.keep_trace))
    except harness.HarnessError as e:
        print('benchmark/run.py: %s' % (e,), file=sys.stderr)
        return 2
    except asyncio.CancelledError:
        print('benchmark/run.py: terminated', file=sys.stderr)
        return 143
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
