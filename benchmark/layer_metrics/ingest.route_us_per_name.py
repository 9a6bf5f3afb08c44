"""Microseconds of the program's host span ``ingest.route`` per NAME
the routed children lists held, over the device ticks of the traced
window: the sum of the ``ingest.route`` spans that carry the ``names``
field over the sum of that field (the span's share of the ingest's
always-on ``names_routed``).  Route is unpacking a tick's planes, the
list parse — a ``str`` a name — and the delivery up to the watcher's
listener; a herd's re-lists are nearly all of it.  None against a
program whose route spans carry no such field."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    route_ns = names = 0
    for s in ring.spans():
        if s.op == 'ingest.route' and getattr(s, 'names', None):
            route_ns += s.t1_ns - s.t0_ns
            names += s.names
    if not names:
        return None
    return route_ns / 1e3 / names
