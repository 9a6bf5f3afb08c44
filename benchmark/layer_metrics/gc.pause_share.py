"""Share of the traced window the fleet's process stood in the garbage
collector: the program's ``gc.pause`` total (one ``gc.callbacks`` hook,
armed by the profiler session; each pause is an annotation in the trace
too)."""

import inside_totals


def read(run):
    return inside_totals.share(run, 'gc.pause')
