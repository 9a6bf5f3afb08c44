"""Microseconds a request spent in stage ``client.wire_wait`` over the
traced window, mean over the ops that resumed in it (the program's
stage stamps, one clock: ``zkstream_tpu/utils/trace.py``):
that flush -> the start of the ``_sock_data`` call that brought its
reply: the flush's own work and the send, the member's service, and the
reply waiting in the kernel's socket buffer while the loop is busy.
The four stages sum to a request's latency."""

import inside_totals


def read(run):
    return inside_totals.mean_us(run, 'client.wire_wait')
