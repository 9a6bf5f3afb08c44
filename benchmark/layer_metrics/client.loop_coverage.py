"""How much of the traced window the program's own account of its
loop's thread covers: ``loop.named`` (every top-level span's own time,
``loop.idle`` among them) + every ``loop.gap@<previous>><next>`` +
``gc.pause@loop.gap`` (the collections that began in a gap, taken out
of it), over the window.  Near 100 the split of the gaps can be read
as shares of the window; well under it the session, the ring or the
loop's hook lost part of it; over it the session outlasted the window
(the harness stops the profiler after its post-window scrape, which a
saturated loop serves late: 101-104 on the chip, and the two shares
are high by the same factor).  None against a program that books no
gaps."""

import inside
import inside_totals

GAP = 'loop.gap@'
PAUSES = 'gc.pause@loop.gap'


def read(run):
    ring = inside.host_ring(run)
    if ring is None:
        return None
    gaps = [name for name in ring.totals if name.startswith(GAP)]
    if not gaps:
        return None
    if PAUSES in ring.totals:
        gaps.append(PAUSES)
    return inside_totals.share(run, 'loop.named', *gaps)
