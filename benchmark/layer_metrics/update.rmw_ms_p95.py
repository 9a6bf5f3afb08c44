"""An update as YCSB's ZooKeeper binding makes it — ``getData`` sent ->
the ``setData`` of the rewritten record acknowledged (committed: WAL
barrier + quorum) — 95th percentile of the updates whose ``getData``
was sent in the window, host clock, as the engine kept them
(``result['samples']['rmw']``).  Recorded, not judged: the cell's
end-to-end metrics are the fleet's operations a second and a read's
tail.  None when the engine kept none."""

import stats


def read(run):
    vals = run.result.get('samples', {}).get('rmw')
    return stats.percentile(vals, 95) if vals else None
