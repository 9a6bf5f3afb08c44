"""Median of the program's host span ``ingest.batch`` in the traced
window — finding the slots that hold bytes and building the ``[Bp, L]`` u8 batch
and the lengths from them."""

import inside


def read(run):
    return inside.span_median_ms(run, 'ingest.batch')
