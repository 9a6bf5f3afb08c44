"""Share of the window's ticks that did NOT run the device program
(scalar, warming, fragmentation guard).  Must read 0."""

def read(run):
    off = sum(run.ingest_delta(k) for k in
              ('ticks_scalar', 'ticks_warming', 'ticks_frag'))
    total = off + run.ingest_delta('ticks')
    if not total:
        return None
    return 100.0 * off / total
