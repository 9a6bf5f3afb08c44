"""p99 of the members' ``decode_apply`` tick phase over the WINDOW
(bucket deltas of ``zk_tick_phase_ms``), the largest over the
members."""

import inside

LABELS = {'phase': 'decode_apply'}


def read(run):
    return inside.largest(
        inside.percentile(inside.member_hist(
            run, m, 'zk_tick_phase_ms', LABELS), 99)
        for m in inside.members(run))
