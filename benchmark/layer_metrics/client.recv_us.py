"""Microseconds a ``recv(2)`` of the client tier's native receiver
thread cost over the traced window: the host ring's ``client.recv``
totals, which hold the thread's calls and the nanoseconds inside them
on the thread's OWN clock, booked at each reap (``io/transport.py``,
"Who receives").  The loop's clock books nothing here: where asyncio's
transport makes the call (the parent, ``uring``, no extension) there
are no such totals and this reads None, as it does when the ring
dropped spans."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    recvs = ring.totals.get('client.recv')
    if not recvs or not recvs[0]:
        return None
    return recvs[1] / 1e3 / recvs[0]
