"""``jit_step``'s share of its bytes roofline in the traced window
(``reduce_trace.tick_roofline_share``); named for the kernel, so the
label — here the one cell the entry lists, ``ycsb3.workloadb`` — stands
in the middle and the reader has this file."""

import reduce_trace


def read(run):
    return reduce_trace.tick_roofline_share(run, 'jit_step')
