"""Request sent -> reply delivered to the caller, reads, 95th percentile
of every read sent in the window (a failed read counts as its
deadline)."""

import stats


def value(run) -> float:
    return stats.percentile(run.result['samples']['read'], 95)
