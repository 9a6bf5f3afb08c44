"""Requests per loop timer over the traced window: the count of
``client.submit`` (one per request handed to a send plane) over the
count of ``client.deadline`` (one per arming or firing of the ONE timer
that the deadline queue of the fleet's event loop keeps for all its
pending requests, ``zkstream_tpu/utils/aio.py``), both from the host
ring's totals.  1.0 by construction while every request armed a timer
of its own (``asyncio.wait_for``), which left no span: None against
such a program, and when the ring dropped spans."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    sends = ring.totals.get('client.submit')
    timers = ring.totals.get('client.deadline')
    if not sends or not timers or not timers[0]:
        return None
    return sends[0] / timers[0]
