"""Device dispatches per device tick in the window (``dispatches`` /
``ticks``, the ingest's counters as the engine kept them): one a size
class present in the tick, more where a class outgrew one dispatch.
None against a program without the counter (it makes one a tick)."""


def read(run):
    moved = run.result.get('counters', {}).get('ingest') or {}
    if not moved.get('ticks') or 'dispatches' not in moved:
        return None
    return moved['dispatches'] / moved['ticks']
