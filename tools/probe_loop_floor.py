"""The instrument's floor a gap, on whatever host this runs on.

Inside a profiler session every top-level host span on a client loop's
thread books the time since its predecessor ended under
``loop.gap@<previous>><next>`` (utils/trace.py, "The loop's whole
turn").  A span's stamps lie inside its annotation, so each gap holds
the closing annotation's exit, the next ``host_span()`` call and the
opening annotation's enter.  This probe measures that floor ALONE: it
opens a real session (the options ``benchmark/harness.py`` uses), makes
the calling thread a loop's, opens pairs of empty top-level spans back
to back and prints what a gap between them booked — beside what a
top-level span costs on a loop's thread and on a plain one (the
difference is the booking), and what the ``select`` wrapper
(utils/aio.DeadlineQueue) costs a loop turn outside a session.

Run it where the traced cells run (through the chip tool, from the
root of a checkout) and state the floor beside every split of
``client.loop_app_share`` / ``client.loop_switch_share`` (PERF.md,
section 5).  It prints host-clock numbers of THIS machine's CPU; never
a device metric and never a cell's number.  Usage::

    python tools/probe_loop_floor.py [--pairs 20000] [--rounds 4]
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # invoked as `python tools/probe_loop_floor.py`
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

from zkstream_tpu.utils import aio, trace  # noqa: E402

AB = 'loop.gap@floor.a>floor.b'
BA = 'loop.gap@floor.b>floor.a'


def pairs(n: int) -> float:
    """``n`` pairs of empty top-level spans; nanoseconds a span."""
    span = trace.host_span
    t = time.perf_counter_ns()
    for _ in range(n):
        with span('floor.a', accumulate=True):
            pass
        with span('floor.b', accumulate=True):
            pass
    return (time.perf_counter_ns() - t) / (2 * n)


def session_round(n: int) -> str:
    plain = []
    th = threading.Thread(target=lambda: (pairs(500),
                                          plain.append(pairs(n))))
    th.start()
    th.join()
    with trace.loop_idle():     # this thread is a loop's from here
        pass
    pairs(500)
    totals = trace.host_ring.totals
    before = {k: list(totals[k]) for k in (AB, BA)}
    booked = pairs(n)
    (n_ab, ns_ab), (n_ba, ns_ba) = (
        [totals[k][i] - before[k][i] for i in (0, 1)] for k in (AB, BA))
    return ('plain thread %.0f ns a span, loop thread %.0f (booking '
            '%+.0f); floor a gap: a>b %.0f ns (n=%d), b>a %.0f ns (n=%d)'
            % (plain[0], booked, booked - plain[0], ns_ab / n_ab, n_ab,
               ns_ba / n_ba, n_ba))


def select_wrapper_ns(turns: int = 200_000) -> float:
    """What the always-on wrapper adds to one ``select``."""
    class Selector:
        def select(self, timeout=None):
            return []

    class Loop:
        _selector = Selector()

    loop = Loop()
    raw = loop._selector.select
    aio.DeadlineQueue(loop)
    wrapped = loop._selector.select
    took = []
    for select in (raw, wrapped):
        t = time.perf_counter_ns()
        for _ in range(turns):
            select(0)
        took.append(time.perf_counter_ns() - t)
    return (took[1] - took[0]) / turns


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--pairs', type=int, default=20000)
    ap.add_argument('--rounds', type=int, default=4)
    args = ap.parse_args()
    dev = jax.devices()[0]
    print('# device %s %s' % (dev.platform, dev.device_kind), flush=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    for rnd in range(args.rounds):
        out = tempfile.mkdtemp(prefix='loop-floor-')
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            print('# session %d: %s' % (rnd, session_round(args.pairs)),
                  flush=True)
        finally:
            jax.profiler.stop_trace()
            shutil.rmtree(out, ignore_errors=True)
    for rnd in range(3):
        print('# no session %d: the select wrapper %.0f ns a turn'
              % (rnd, select_wrapper_ns()), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
