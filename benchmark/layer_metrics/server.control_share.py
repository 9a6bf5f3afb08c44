"""Share of the window the LEADER's loop spent serving its followers'
control channels: tick-ledger phase ``control`` (a forwarded batch from
its bytes in hand — unpickling, the applies, the barrier — up to the
quorum wait, and from the wait's return through the response's pickle
and write; ``wal_append`` / ``repl_push`` / ``fsync_gate`` under it are
subtracted).  None against a program without the phase."""

import inside_leader


def read(run):
    return inside_leader.phase_share(run, ('control',))
