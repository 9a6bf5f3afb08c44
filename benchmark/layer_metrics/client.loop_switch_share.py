"""Share of the traced window the fleet's loop spent between two of
the program's top-level spans OTHER than in its caller's code: every
``loop.gap@<previous>><next>`` total but ``client.resume>
client.prepare`` — asyncio's own turn (a ``Task.__step`` out, a
handle, a ``Task.__step`` in, the coroutine chain down to
``_await_op``).  The figure holds the instrument's floor a gap (the
closing annotation's exit, the next ``host_span()`` call, the opening
annotation's enter: what two empty top-level spans opened back to
back book; PERF.md section 5 has it as measured on the chip).  None
against a program that books no gaps."""

import inside
import inside_totals

GAP = 'loop.gap@'
APP = GAP + 'client.resume>client.prepare'


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    gaps = [name for name in ring.totals
            if name.startswith(GAP) and name != APP]
    return inside_totals.share(run, *gaps) if gaps else None
