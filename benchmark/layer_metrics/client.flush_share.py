"""Share of the traced window inside the program's ``client.flush``:
the tick of the transport tier that the client sessions of one event
loop share — every corked plane's flush and the one batched submission
that carries them (``io/transport.py``).  None against a program
without the span."""

import inside


def read(run):
    return inside.span_total_share(run, 'client.flush')
