"""Share (%) of the traced window's ``client.rx`` calls — a
connection's received bytes handed to ``_sock_data`` — that came
through a reap of the client tier's native receiver thread instead of
asyncio's ``data_received``: the count of ``client.rx_reaped`` (the
deliveries the tier's reaps made) over the count of ``client.rx``,
both from the host ring's totals (``io/transport.py``).  None when the
ring dropped spans, nothing was received, or against a program without
a receiver (no ``client.recv`` totals: the parent)."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None or 'client.recv' not in ring.totals:
        return None
    rx = ring.totals.get('client.rx')
    if not rx or not rx[0]:
        return None
    reaped = ring.totals.get('client.rx_reaped')
    return 100.0 * (reaped[0] if reaped else 0) / rx[0]
