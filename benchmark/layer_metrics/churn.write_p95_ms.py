"""Request sent -> committed reply of the churners' ephemeral creates
and deletes, 95th percentile: the first leg of every convergence.  Some
hundreds of samples a run, so it stands here and not among the bounded
end-to-end metrics."""

import stats


def read(run):
    vals = run.result.get('samples', {}).get('write')
    if not vals:
        return None
    return stats.percentile(vals, 95)
