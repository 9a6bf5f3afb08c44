"""Microseconds a watch notification cost at the client over the traced
window: the host ring's ``client.notify`` totals, nanoseconds over
count — an xid -1 frame at the session until every watcher it matches
has emitted (``io/session.py``): the session's own bookkeeping, the
watcher's fan-out to its event machines, the one-shot engine's re-arm
(the machine's transitions and the re-read it sends inside the call)
and whatever the watcher's ``notify`` is wrapped in.  None when the
ring dropped spans, no notification came or the program keeps no such
totals."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    notified = ring.totals.get('client.notify')
    if not notified or not notified[0]:
        return None
    return notified[1] / 1e3 / notified[0]
