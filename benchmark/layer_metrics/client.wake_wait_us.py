"""Microseconds a request spent in stage ``client.wake_wait`` over the
traced window, mean over the ops that resumed in it (the program's
stage stamps, one clock: ``zkstream_tpu/utils/trace.py``):
``ZKRequest.settle`` -> the awaiting coroutine runs again
(``Client._await_op``): the rest of the route and the loop's ready
queue.
The four stages sum to a request's latency."""

import inside_totals


def read(run):
    return inside_totals.mean_us(run, 'client.wake_wait')
