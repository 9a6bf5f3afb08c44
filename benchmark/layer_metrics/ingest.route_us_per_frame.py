"""Microseconds of the program's host span ``ingest.route`` per frame
routed, over the device ticks of the traced window: the sum of the
``ingest.route`` durations of the ticks whose ``ingest.tick`` span is in
the ring too, over the sum of those spans' ``batch`` (the frames the
tick routed), joined on the ``tick`` number both carry.  Route is
unpacking a tick's planes, assembling each stream's packets and
delivering them, reply settle included; a tick's median
(``ingest.route_ms_p50``) moves with how many frames the lock-step
groups put into a tick, this does not."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    spans = ring.spans()
    frames = {s.tick: s.batch for s in spans
              if s.op == 'ingest.tick' and s.tick is not None and s.batch}
    route_ns = routed = 0
    for s in spans:
        if s.op == 'ingest.route' and s.tick in frames:
            route_ns += s.t1_ns - s.t0_ns
            routed += frames[s.tick]
    if not routed:
        return None
    return route_ns / 1e3 / routed
