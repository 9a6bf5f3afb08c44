"""Microseconds a connection send of the client plane's transport tier
cost over the traced window: the host ring's ``client.send`` totals,
which hold the connections every raw batch of the tier covered and the
nanoseconds inside their ``send(2)`` loop — on the native sender's own
clock for a batch that was handed over, on the loop's clock around the
inline submission otherwise (``io/transport.py``).  None when the ring
dropped spans or the program keeps no such totals."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    sends = ring.totals.get('client.send')
    if not sends or not sends[0]:
        return None
    return sends[1] / 1e3 / sends[0]
