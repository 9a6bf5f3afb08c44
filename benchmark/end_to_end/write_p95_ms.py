"""Request sent -> committed reply, writes, 95th percentile of every
write sent in the window (a failed write counts as its deadline)."""

import stats


def value(run) -> float:
    return stats.percentile(run.result['samples']['write'], 95)
