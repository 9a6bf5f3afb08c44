"""Share of the traced window inside the two synchronous stretches of
an API call that stand around its await: ``client.prepare`` (the cache
and read-plane routing and the connection lookup, up to
``Client._start_op``) and ``client.resume`` (``Client._await_op`` from
the moment its future woke it: the deadline entry's discard, the
latency observation, ``on_op``)."""

import inside_totals


def read(run):
    return inside_totals.share(run, 'client.prepare', 'client.resume')
