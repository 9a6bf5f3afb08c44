"""Median latency of the writes of sessions attached to a FOLLOWER
(forwarded to the leader), against a leader that its own sessions keep
saturated: how long a forwarded write starves."""

import stats


def read(run):
    by_member = run.result.get('samples_by_member') or {}
    vals = [v for m, ms in by_member.items() if m != run.leader
            for v in ms]
    if not vals:
        return None
    return stats.percentile(vals, 50)
