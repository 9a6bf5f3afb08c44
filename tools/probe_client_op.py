"""What ONE client request's own bookkeeping costs, without a chip.

The client session layer (``Client.get`` down to the awaited future:
the span, the request object, the deadline entry, the latency
observation, ``on_op``) runs on the fleet's one event loop, so in a
closed loop that is busy all the time its microseconds a request are
throughput (PERF.md section 5).  This probe sizes that layer ALONE on
whatever CPU it runs on: N ``Client``s that were never started, each
over a stub session and a stub connection whose ``request()`` makes
the real :class:`~zkstream_tpu.io.connection.ZKRequest` and assigns
the xid but encodes and sends nothing; every pending request is
settled (``ZKRequest.settle``, as the reply routing does) once a loop
turn; the collector's young generation sized as the fleet ingest sizes
it (``utils/alloc``: 32 x sessions).  No socket, no codec, no send
plane, no ingest: their costs are other layers'.

It prints microseconds a ``get`` on THIS machine — a CPU number, good
for ranking what the layer spends (run it before and after a change,
or under ``--profile`` for ``cProfile``'s own-time table), never a
device metric and never a cell's number: no cell runs this file and it
runs no cell.  Usage::

    python tools/probe_client_op.py [--sessions 1024] [--outstanding 1]
                                    [--seconds 3] [--profile]
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:  # invoked as `python tools/probe_client_op.py`
    sys.path.insert(0, ROOT)

# a fleet's process has imported jax, so outside a profiler session a
# host span costs it ``TraceAnnotation.is_enabled()`` (utils/trace.py);
# without jax the probe would time the look for the module instead
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
import jax  # noqa: E402,F401

from zkstream_tpu import Client  # noqa: E402
from zkstream_tpu.io.connection import Backend, ZKRequest  # noqa: E402

#: What the fleet ingest gives the young generation a slot
#: (``utils/alloc.fit_collector``).
GC_PER_SESSION = 32

DATA = b'v' * 1024


class StubSession:
    """What ``Client`` asks of a live, attached session."""

    last_zxid = 0
    gate_floor = 0
    _state = 'attached'

    def __init__(self, conn):
        self.conn = conn

    def is_in_state(self, name: str) -> bool:
        return name == 'attached'

    def get_connection(self):
        return self.conn

    def get_session_id(self) -> str:
        return '%016x' % (0x1000 + self.conn.idx,)


class StubConnection:
    """A connected connection that sends nothing: ``request`` is
    ``ZKConnection.request`` without the trace log, the stage stamps
    and the write."""

    _state = 'connected'

    def __init__(self, idx: int, pending: list):
        self.idx = idx
        self.session = StubSession(self)
        # what a connection stamps its ops' spans with
        self.span_backend = Backend('127.0.0.1', 2181).key
        self.span_session_id = self.session.get_session_id()
        self.reqs: dict[int, ZKRequest] = {}
        self._xid = 0
        self._pending = pending

    def is_in_state(self, name: str) -> bool:
        return name == 'connected'

    def request(self, pkt: dict, span=None) -> ZKRequest:
        req = ZKRequest(pkt)
        req.span = span
        self._xid += 1
        pkt['xid'] = self._xid
        self.reqs[self._xid] = req
        self._pending.append(self)
        return req


def settle_all(pending: list) -> None:
    """One loop turn's replies: every pending request of every
    connection, settled as ``process_reply`` settles it."""
    conns = pending[:]
    pending.clear()
    for conn in conns:
        reqs, conn.reqs = conn.reqs, {}
        for xid, req in reqs.items():
            req.settle({'xid': xid, 'zxid': 7, 'err': 'OK',
                        'opcode': 'GET_DATA', 'data': DATA,
                        'stat': None})


async def probe(sessions: int, outstanding: int, seconds: float,
                bare: bool = False) -> dict:
    loop = asyncio.get_running_loop()
    pending: list = []
    clients = []
    for idx in range(sessions):
        c = Client(address='127.0.0.1', port=2181, max_spares=0)
        c.session = StubConnection(idx, pending).session
        clients.append(c)
    done = 0
    stop = False

    async def lane(c: Client) -> None:
        nonlocal done
        while not stop:
            await c.get('/k')
            done += 1

    async def bare_lane(c: Client) -> None:
        # the floor: a task that awaits a future settled next turn,
        # with none of the layer between them
        nonlocal done
        while not stop:
            fut = loop.create_future()
            bare_pending.append(fut)
            await fut
            done += 1

    bare_pending: list = []

    def turn() -> None:
        if pending:
            settle_all(pending)
        if bare_pending:
            futs = bare_pending[:]
            bare_pending.clear()
            for fut in futs:
                fut.set_result(None)
        if not stop:
            loop.call_soon(turn)

    old = gc.get_threshold()
    gc.set_threshold(max(old[0], GC_PER_SESSION * sessions), *old[1:])
    try:
        loop.call_soon(turn)
        tasks = [asyncio.ensure_future((bare_lane if bare else lane)(c))
                 for c in clients for _ in range(outstanding)]
        await asyncio.sleep(min(1.0, seconds / 3))      # warm
        n0, t0, cpu0 = done, time.perf_counter(), time.process_time()
        await asyncio.sleep(seconds)
        n1, t1, cpu1 = done, time.perf_counter(), time.process_time()
        stop = True
        await asyncio.gather(*tasks)
    finally:
        gc.set_threshold(*old)
    ops = n1 - n0
    return {'sessions': sessions, 'outstanding': outstanding,
            'gets': ops, 'seconds': round(t1 - t0, 3),
            'us_per_get': round((t1 - t0) * 1e6 / max(ops, 1), 3),
            'cpu_us_per_get': round((cpu1 - cpu0) * 1e6 / max(ops, 1), 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--sessions', type=int, default=1024)
    ap.add_argument('--outstanding', type=int, default=1)
    ap.add_argument('--seconds', type=float, default=3.0)
    ap.add_argument('--profile', action='store_true',
                    help="print cProfile's own-time table too")
    args = ap.parse_args(argv)
    if args.profile:
        import cProfile
        import pstats
        prof = cProfile.Profile()
        prof.enable()
    out = asyncio.run(probe(args.sessions, args.outstanding, args.seconds))
    if args.profile:
        prof.disable()
        pstats.Stats(prof).sort_stats('tottime').print_stats(18)
    floor = asyncio.run(probe(args.sessions, args.outstanding,
                              args.seconds / 2, bare=True))
    print('# on this CPU (no device, no cell): %s' % (out,))
    print('# the floor, a task awaiting a bare future: %s' % (floor,))
    print('%.3f us a get, %.3f of them the layer (floor %.3f)'
          % (out['us_per_get'],
             out['us_per_get'] - floor['us_per_get'],
             floor['us_per_get']))
    return 0


if __name__ == '__main__':
    sys.exit(main())
