"""Share (%) of the window's device ticks whose batch was built and
dispatched from the receive reap, ahead of the tick that routes it
(``ticks_early`` / ``ticks``; PR 43's early dispatch).  ~100 where the
receiver thread feeds the slots; None where no device tick ran."""

def read(run):
    ticks = run.ingest_delta('ticks')
    if not ticks:
        return None
    return 100.0 * run.ingest_delta('ticks_early') / ticks
