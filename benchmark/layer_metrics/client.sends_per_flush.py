"""Requests per flush of the client plane's transport tier over the
traced window: the count of ``client.submit`` (one per request handed
to a send plane) over the count of ``client.flush`` (one per tick of
the tier), both from the host ring's totals.  1.0 by construction while
every session had a tier of its own; None when the ring dropped spans
or the program has no ``client.flush``."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    sends = ring.totals.get('client.submit')
    flushes = ring.totals.get('client.flush')
    if not sends or not flushes or not flushes[0]:
        return None
    return sends[0] / flushes[0]
