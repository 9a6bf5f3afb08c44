"""Share of the window a follower's loop stood parked in the blocking
control-channel RPC that forwards a write to the leader (tick phase
``forward_rpc``), the most parked follower."""

import inside


def read(run):
    return inside.largest(
        inside.phase_share(run, m, phases=('forward_rpc',))
        for m in inside.members(run) if m != run.leader)
