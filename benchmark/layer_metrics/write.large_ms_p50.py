"""Median of an EXTERNALVIEW ``setData`` of ``large_bytes`` (256,000 B)
or more, sent -> committed reply at its controller (host clock, the
engine's): the write half of a change's convergence."""

import stats


def read(run):
    vals = run.result.get('samples', {}).get('write_large')
    return stats.percentile(vals, 50) if vals else None
