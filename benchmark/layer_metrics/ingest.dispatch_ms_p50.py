"""Median of the program's host span ``ingest.dispatch`` in the traced
window — the call of the tick executable: H2D copy and enqueue."""

import inside


def read(run):
    return inside.span_median_ms(run, 'ingest.dispatch')
