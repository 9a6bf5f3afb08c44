"""Frames a routed stream gave a device tick in the traced window: the
sum of the ``batch`` field of the program's ``ingest.tick`` host spans
(the frames the tick routed) over the sum of the ``rows`` field of its
``ingest.dispatch`` spans (the streams its dispatches held), device
ticks only.  1.0 where every session keeps one request outstanding —
each row of a dispatch holds one frame and ``frame_cursor_scan`` finds
nothing on seven of its eight steps; up to the configuration's
``max_frames`` where the clients pipeline.  None against a program
whose spans carry neither field, in an untraced run, or in a window
without a device tick."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    frames = rows = 0
    for s in ring.spans():
        if s.tick is None:
            continue
        if s.op == 'ingest.tick':
            frames += getattr(s, 'batch', None) or 0
        elif s.op == 'ingest.dispatch':
            rows += getattr(s, 'rows', None) or 0
    if not rows or not frames:
        return None
    return frames / rows
