"""A returning node's new session from ``fleet.new_client`` to
connected (TCP connect, the ``createSession`` through the quorum and
the WAL, the handshake's reply through the shared tier beside the
fleet's busy connections), median over the window's returns (host
clock, the engine's)."""

import stats


def read(run):
    vals = run.result.get('samples', {}).get('connect')
    return stats.percentile(vals, 50) if vals else None
