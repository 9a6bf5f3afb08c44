"""Median wall time of an ingest tick that routed work (the program's
``zkstream_ingest_tick_ms`` observations, kept exactly)."""

import stats


def read(run):
    if not run.tick_ms:
        return None
    return stats.percentile(run.tick_ms, 50)
