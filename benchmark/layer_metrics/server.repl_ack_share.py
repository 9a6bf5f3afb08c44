"""Share of the window the LEADER's loop spent on its followers' acks:
tick-ledger phase ``repl_ack`` (an ack from its bytes in hand through
the quorum floor's advance and the releases it makes; the flushes it
releases are subtracted).  None against a program without the phase."""

import inside_leader


def read(run):
    return inside_leader.phase_share(run, ('repl_ack',))
