"""Share (%) of the stream bytes copied into the window's batches that
their tick did not consume — a partial frame behind whole ones — and
that were therefore copied again (``bytes_recopied`` /
``bytes_batched``).  A slot whose first frame is not whole waits, so a
reply that arrives over many reads is copied once: reads 0 where a
slot holds one reply.  None against a program without the counters
(it copies a slot into every tick's batch while its reply arrives)."""


def read(run):
    moved = run.result.get('counters', {}).get('ingest') or {}
    if not moved.get('bytes_batched') or 'bytes_recopied' not in moved:
        return None
    return 100.0 * moved['bytes_recopied'] / moved['bytes_batched']
