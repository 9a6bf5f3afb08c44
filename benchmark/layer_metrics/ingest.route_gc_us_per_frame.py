"""Microseconds of garbage collection per frame routed that began
inside the program's host span ``ingest.route`` (its
``gc.pause@ingest.route`` total), over the frames
``ingest.route_us_per_frame`` divides by: what to take off that figure
to have the route's own work.  0.0 when the ticks routed frames and no
collection fell into a route; None against a program that does not
record the pauses."""

import inside
import inside_totals


def read(run):
    ring = inside.host_ring(run)
    if ring is None or 'gc.pause' not in ring.totals:
        return None
    spans = ring.spans()
    frames = {s.tick: s.batch for s in spans
              if s.op == 'ingest.tick' and s.tick is not None and s.batch}
    routed = sum(frames[s.tick] for s in spans
                 if s.op == 'ingest.route' and s.tick in frames)
    if not routed:
        return None
    held = inside_totals.total(run, 'gc.pause@ingest.route')
    return (held[1] if held else 0) / 1e3 / routed
