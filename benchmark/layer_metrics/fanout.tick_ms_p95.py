"""p95 of a fan-out shard flush over the window (bucket deltas of
``zk_fanout_tick_ms{plane="fanout"}``), the largest over the
members."""

import inside

LABELS = {'plane': 'fanout'}


def read(run):
    return inside.largest(
        inside.percentile(inside.member_hist(
            run, m, 'zk_fanout_tick_ms', LABELS), 95)
        for m in inside.members(run))
