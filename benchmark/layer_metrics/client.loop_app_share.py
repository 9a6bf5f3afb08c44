"""Share of the traced window the fleet's loop spent in its CALLER's
own code between one reply and the next request: the program's total
``loop.gap@client.resume>client.prepare`` — the gap that opens where
``Client._await_op`` returns to its caller and closes where the
caller's next API call begins (here: the engine's check and draw).
The figure holds the instrument's floor a gap (the closing
annotation's exit, the next ``host_span()`` call, the opening
annotation's enter: what two empty top-level spans opened back to
back book; PERF.md section 5 has it as measured on the chip).  None
against a program that books no gaps."""

import inside_totals

APP = 'loop.gap@client.resume>client.prepare'


def read(run):
    return inside_totals.share(run, APP)
