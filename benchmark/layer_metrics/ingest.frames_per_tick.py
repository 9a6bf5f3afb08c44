"""Frames routed per device tick in the window (``frames_routed`` /
``ticks``): how wide the batches are."""

def read(run):
    ticks = run.ingest_delta('ticks')
    if not ticks:
        return None
    return run.ingest_delta('frames_routed') / ticks
