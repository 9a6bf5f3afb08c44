"""Share of the traced window inside the program's per-op boundary
``client.submit``: ``Client._start_op`` until the encoded request is
with the connection's send plane."""

import inside


def read(run):
    return inside.span_total_share(run, 'client.submit')
