"""A change's first notification at any broker -> the LAST broker's
refreshed view, median over the window's changes of ``large_bytes``
(256,000 B) or more (host clock, the engine's stamp on the watcher's
``notify``): the herd half of a change's convergence."""

import stats


def read(run):
    vals = run.result.get('samples', {}).get('herd')
    return stats.percentile(vals, 50) if vals else None
