"""Share (%) of the ``getData`` sent in the window that were answered
``NO_NODE`` where ZooKeeper allows it: the record's create was
acknowledged to ANOTHER session (the key chooser draws at or under the
acknowledged frontier) and the reader's member had not applied it yet.
The deployment's staleness as its readers meet it — the engine's
``reads_not_yet_visible`` over its ``reads`` — not a fault: a miss
ZooKeeper does not allow is a violation (``stale-miss``) and never
counted here.  None when the engine counts neither."""


def read(run):
    counters = run.result.get('counters', {})
    reads = counters.get('reads')
    missed = counters.get('reads_not_yet_visible')
    if not reads or missed is None:
        return None
    return 100.0 * missed / reads
