"""A membership change DUE -> the first refreshed children list of each
armed watcher that shows it, median over every (change, watcher) pair
of the window.  The median, because on the chip's shared host the
process stands still for ~100 ms several times in some runs and not in
others, and the 95th percentile sits exactly where that tail begins
(``converge.p95_ms`` stands beside it as a per-layer metric)."""

import stats


def value(run) -> float:
    return stats.percentile(run.result['samples']['converge'], 50)
