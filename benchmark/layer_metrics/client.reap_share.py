"""Share of the traced window inside the program's ``client.reap``:
the loop's callback on the native sender's ``eventfd``, which applies
the results of the batches the sender finished (``io/transport.py``).
None against a program without the span, or when no batch was handed
over in the window."""

import inside


def read(run):
    return inside.span_total_share(run, 'client.reap')
