"""Milliseconds of the leader's loop per MiB appended to its WAL in the
window: ledger phase ``wal_append`` (record build + CRC32C + write,
without the fsync gate) over the ``zk_wal_appended_bytes`` delta.  None
against a program without them."""

import inside_wal


def read(run):
    return inside_wal.ms_per_mib(run, run.leader, 'wal_append',
                                 'zk_wal_appended_bytes')
