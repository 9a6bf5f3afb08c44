"""Milliseconds of the leader's loop per MiB pushed to its followers in
the window: ledger phase ``repl_push`` (a commit's pushes: frame +
send) over the ``zk_repl_pushed_bytes`` delta.  None against a program
without them."""

import inside_wal


def read(run):
    return inside_wal.ms_per_mib(run, run.leader, 'repl_push',
                                 'zk_repl_pushed_bytes')
