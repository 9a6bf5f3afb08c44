"""Padded bytes sent to the device per acknowledged read in the window
(``bytes_dispatched`` over the engine's ``acked``): what a read costs
the host's memory and the link, padding included.  None against a
program without the counter."""


def read(run):
    moved = run.result.get('counters', {}).get('ingest') or {}
    acked = run.result.get('acked')
    if not acked or not moved.get('bytes_dispatched'):
        return None
    return moved['bytes_dispatched'] / acked
