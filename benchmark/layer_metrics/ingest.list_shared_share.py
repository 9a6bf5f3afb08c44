"""Share (%) of the children lists the device ticks of the traced
window routed that the tick's one C decode did NOT parse again: the sum
of the ``shared`` field over the sum of the ``lists`` field on the
program's ``ingest.route`` host spans that carry them (the spans' share
of the ingest's always-on ``lists_shared`` / ``lists_routed``).  A
herd's re-lists of one path in one state are byte-equal, and
``decode_streams`` hands every asker after the first its own list of
the same ``str`` objects; the misses are a tick's first list of each
distinct body.  None against a program whose route spans carry no such
fields, or in a window that routed no list."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    lists = shared = 0
    for s in ring.spans():
        if s.op == 'ingest.route' and getattr(s, 'lists', None):
            lists += s.lists
            shared += getattr(s, 'shared', 0)
    if not lists:
        return None
    return 100.0 * shared / lists
