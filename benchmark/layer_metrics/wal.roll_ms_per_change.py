"""Milliseconds a change acknowledged in the window cost the leader's
loop in WAL segment rolls: ledger phase ``wal_roll`` (the roll's
blocking ``sync_now`` + the whole-tree snapshot's capture) over the
engine's ``changes_acked``.  None against a program without the
phase."""

import inside_wal


def read(run):
    ms = inside_wal.phase_ms(run, run.leader, 'wal_roll')
    n = inside_wal.changes(run)
    return ms / n if ms is not None and n else None
