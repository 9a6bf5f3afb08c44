"""Share (%) of the client tier's raw connection flushes in the window
that ended in a partial write (the kernel's socket buffer filled inside
a request, the remainder re-queued through the asyncio transport):
the tier's ``partial_flushes`` / ``flushes`` as the engine kept them.
None against a program without the counters."""


def read(run):
    moved = run.result.get('counters', {}).get('ingest') or {}
    if not moved.get('tier_flushes') or 'tier_partial_flushes' not in moved:
        return None
    return 100.0 * moved['tier_partial_flushes'] / moved['tier_flushes']
