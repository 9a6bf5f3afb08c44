"""Median of the program's host span ``ingest.route`` in the traced
window — unpacking, per-stream assembly and delivery of a tick's frames (reply settle included)."""

import inside


def read(run):
    return inside.span_median_ms(run, 'ingest.route')
