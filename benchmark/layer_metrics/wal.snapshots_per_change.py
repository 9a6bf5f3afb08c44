"""Snapshots the leader's WAL took in the window (``zk_wal_snapshots``
delta: one a segment roll) per change acknowledged in it: how often a
stream of ~1 MB records rolls a segment."""

import inside_wal


def read(run):
    snaps = run.mntr_delta(run.leader, 'zk_wal_snapshots')
    n = inside_wal.changes(run)
    return snaps / n if snaps is not None and n else None
