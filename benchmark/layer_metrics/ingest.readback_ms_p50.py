"""Median of the program's host span ``ingest.readback`` in the traced
window — ``np.asarray`` of the packed result: the wait for the device and the D2H copy."""

import inside


def read(run):
    return inside.span_median_ms(run, 'ingest.readback')
