"""How far a follower's tree trails the leader's commits, 95th
percentile over the window and the largest of the followers': bucket
deltas of ``zk_apply_lag_ms`` — for each commit a follower applies, the
time since the leader stamped its group (both ``time.monotonic()`` on
one host).  What a reader on a follower waits before a record created
through another member exists for it.  None against a program without
the histogram (the parent of the PR that brought it)."""

import inside


def read(run):
    return inside.largest(
        inside.percentile(inside.member_hist(run, m, 'zk_apply_lag_ms'),
                          95)
        for m in inside.members(run) if m != run.leader)
