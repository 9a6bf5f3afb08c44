"""Share (%) of the padded bytes the window's dispatches held
(``Bp x L`` summed) that were stream bytes: how closely what the
ticks pad, send and scan follows what they route.  From the ingest's
always-on counters ``bytes_batched`` / ``bytes_dispatched`` as the
engine kept them over the window; None against a program that has
neither (it pads every tick to ``rows x longest row``)."""


def read(run):
    moved = run.result.get('counters', {}).get('ingest') or {}
    if not moved.get('bytes_dispatched'):
        return None
    return 100.0 * moved['bytes_batched'] / moved['bytes_dispatched']
