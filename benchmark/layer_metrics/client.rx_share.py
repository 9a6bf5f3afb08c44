"""Share of the traced window inside the program's per-op boundary
``client.rx``: socket bytes arriving until they sit in the ingest's
slot."""

import inside


def read(run):
    return inside.span_total_share(run, 'client.rx')
