"""p95 of commit -> majority ack on the leader over the window
(bucket deltas of ``zk_quorum_ack_ms``)."""

import inside


def read(run):
    return inside.percentile(inside.member_hist(
        run, run.leader, 'zk_quorum_ack_ms'), 95)
