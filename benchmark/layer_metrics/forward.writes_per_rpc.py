"""Writes a follower forwarded in the window per control-channel RPC it
made for them (``zk_forward_writes`` / ``zk_forward_rpcs``, cumulative
``mntr`` rows of a member that forwards), all followers together: what
one turn of a follower's loop collects into one batch (1 = a blocking
round trip a write)."""

import inside


def read(run):
    rpcs = writes = 0.0
    for m in inside.members(run):
        if m == run.leader:
            continue
        d_rpcs = run.mntr_delta(m, 'zk_forward_rpcs')
        d_writes = run.mntr_delta(m, 'zk_forward_writes')
        if d_rpcs is None or d_writes is None:
            continue        # a program without the rows: nothing to read
        rpcs += d_rpcs
        writes += d_writes
    return writes / rpcs if rpcs else None
