"""Share (%) of the frames the device ticks of the traced window routed
that were settled through the connections' direct lanes — a run of
plain replies handed straight to the requests' futures
(``zkstream_tpu/io/connection.py``, state ``connected``) — and not
through the ``'ingestDeliver'`` -> ``'packet'`` emitter path, which
notifications, reserved xids and decode errors take: the ``lane`` and
``emitted`` fields of the ``ingest.route`` host spans.  None against a
program whose route spans carry neither (it has no lane)."""

import inside


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    lane = emitted = 0
    for s in ring.spans():
        if s.op == 'ingest.route':
            lane += getattr(s, 'lane', None) or 0
            emitted += getattr(s, 'emitted', None) or 0
    if not lane + emitted:
        return None
    return 100.0 * lane / (lane + emitted)
