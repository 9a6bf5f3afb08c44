"""Share of the window a member's loop spent in its tick ledger's
phases (``zk_tick_phase_ms_sum`` deltas over the member's own window),
the busiest member."""

import inside


def read(run):
    return inside.largest(inside.phase_share(run, m)
                          for m in inside.members(run))
