"""p99 of the leader's ``fsync_gate`` tick phase over the WINDOW
(bucket deltas of ``zk_tick_phase_ms``)."""

import inside

LABELS = {'phase': 'fsync_gate'}


def read(run):
    return inside.percentile(inside.member_hist(
        run, run.leader, 'zk_tick_phase_ms', LABELS), 99)
