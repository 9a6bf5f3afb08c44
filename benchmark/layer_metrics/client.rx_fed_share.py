"""Share (%) of the deliveries the client tier's receive reaps made —
the count of ``client.rx_reaped`` — that the reap's one native call
appended straight to the fleet ingest's slot, with no ``bytes``, no
``_sock_data`` and no ``feed`` a connection: the count of
``client.rx_fed`` (``io/transport.py``, ``rx_sink``), both from the
host ring's totals.  None when the ring dropped spans, no reap
delivered anything, or against a program that has no such total (the
parent: every delivery goes through ``_sock_data`` there)."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None or 'client.rx_fed' not in ring.totals:
        return None
    reaped = ring.totals.get('client.rx_reaped')
    if not reaped or not reaped[0]:
        return None
    return 100.0 * ring.totals['client.rx_fed'][0] / reaped[0]
