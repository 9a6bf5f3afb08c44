"""95th percentile of the convergence times whose median is the
end-to-end metric: the tail, which on this host swings with the
machine's own stalls (38-67 ms between runs of one tree)."""

import stats


def read(run):
    vals = run.result.get('samples', {}).get('converge')
    if not vals:
        return None
    return stats.percentile(vals, 95)
