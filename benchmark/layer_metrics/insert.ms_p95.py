"""An insert as YCSB's ZooKeeper binding makes it — ``create`` of the
whole record sent -> acknowledged (committed: WAL barrier + quorum;
through a follower, its forward to the leader too) — 95th percentile of
the creates sent in the window, host clock, as the engine kept them
(``result['samples']['insert']``).  Recorded, not judged: the cell's
end-to-end metrics are the fleet's operations a second and a read's
tail.  None when the engine kept none."""

import stats


def read(run):
    vals = run.result.get('samples', {}).get('insert')
    return stats.percentile(vals, 95) if vals else None
