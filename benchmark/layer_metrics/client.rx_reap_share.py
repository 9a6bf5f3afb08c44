"""Share (%) of the traced window the event loop spent in the client
tier's ``client.rx_reap`` — its callback on the native receiver's
``eventfd`` — NOT counting the connections' own ``client.rx`` spans
nested inside it: the reap's C call, one ``bytes`` a connection, the
walk and the dispatch (``io/transport.py``).  The nested part is the
``client.rx`` total scaled by the share of its calls the reaps made
(``client.rx_reaped`` / ``client.rx`` counts: all of them, where every
connection is on the receiver).  None against a program without the
span (the parent), or when the ring dropped spans."""

import inside


def read(run):
    ring = inside.host_ring(run)
    window_s = (run.trace or {}).get('window_s')
    if ring is None or not window_s:
        return None
    reap = ring.totals.get('client.rx_reap')
    if not reap:
        return None
    rx = ring.totals.get('client.rx')
    reaped = ring.totals.get('client.rx_reaped')
    nested = (rx[1] * min(1.0, reaped[0] / rx[0])
              if rx and rx[0] and reaped else 0)
    return 100.0 * max(0.0, reap[1] - nested) / 1e9 / window_s
