"""Microseconds a request spent in stage ``client.cork_wait`` over the
traced window, mean over the ops that resumed in it (the program's
stage stamps, one clock: ``zkstream_tpu/utils/trace.py``):
submitted (``Client._start_op``) -> the start of the shared tier's
flush that took its bytes: corked until the loop's tick boundary, and
held behind the connection's batch while one is in flight on the
sender thread.
The four stages sum to a request's latency."""

import inside_totals


def read(run):
    return inside_totals.mean_us(run, 'client.cork_wait')
