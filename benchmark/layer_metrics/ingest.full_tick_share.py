"""Share (%) of the window's device ticks that left whole frames in
their slots for the follow-up tick because the tick's batch memory
(``TICK_BYTES``) was full: the ingest's ``ticks_full`` / ``ticks`` as
the engine kept them.  None against a program without the counter."""


def read(run):
    moved = run.result.get('counters', {}).get('ingest') or {}
    if not moved.get('ticks') or 'ticks_full' not in moved:
        return None
    return 100.0 * moved['ticks_full'] / moved['ticks']
