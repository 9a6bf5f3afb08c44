"""Microseconds a request spent in stage ``client.tick_wait`` over the
traced window, mean over the ops that resumed in it (the program's
stage stamps, one clock: ``zkstream_tpu/utils/trace.py``):
that ``_sock_data`` call -> ``ZKRequest.settle``: in the ingest's
slot until the tick, the tick's batch, dispatch and readback, and the
route up to this frame.
The four stages sum to a request's latency."""

import inside_totals


def read(run):
    return inside_totals.mean_us(run, 'client.tick_wait')
