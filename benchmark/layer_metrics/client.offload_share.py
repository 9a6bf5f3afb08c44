"""Share (%) of the client tier's flushes in the traced window whose
raw batch was handed to the native sender thread: the count of
``client.handoff`` (one per batch handed over) over the count of
``client.flush`` (one per tick of the tier), both from the host ring's
totals.  0 where every batch stayed under the tier's hand-over depth;
None when the ring dropped spans or against a program without a
sender (no ``client.send`` totals)."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None or 'client.send' not in ring.totals:
        return None
    flushes = ring.totals.get('client.flush')
    if not flushes or not flushes[0]:
        return None
    handed = ring.totals.get('client.handoff')
    return 100.0 * (handed[0] if handed else 0) / flushes[0]
