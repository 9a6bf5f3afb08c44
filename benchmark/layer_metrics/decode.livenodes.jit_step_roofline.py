"""``jit_step``'s share of its bytes roofline in the traced window
(``reduce_trace.tick_roofline_share``), every size class's dispatches
together; named for the kernel, so the label — here the one cell the
entry lists, ``livenodes3.rolling`` — stands in the middle and the
reader has this file."""

import reduce_trace


def read(run):
    return reduce_trace.tick_roofline_share(run, 'jit_step')
