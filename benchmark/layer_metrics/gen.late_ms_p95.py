"""How late the load generator sent each scheduled change (due ->
sent), 95th percentile: a starved generator is not a fast server."""

import stats


def read(run):
    late = run.result.get('late_ms')
    if not late:
        return None
    return stats.percentile(late, 95)
