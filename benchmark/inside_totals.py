"""Readers of ``host_ring.totals`` — ``name -> [count, total_ns]``, what
the program keeps for a boundary crossed once an op (no span each) —
beyond the share ``inside.span_total_share`` gives.  Against a program
that keeps no such total (the parent of the PR that brought it), a
ring that wrapped or an untraced run, every function returns None."""

from __future__ import annotations

import inside


def total(run, name: str):
    """``[count, total_ns]`` under ``name``, or None."""
    ring = inside.host_ring(run)
    if ring is None:
        return None
    return ring.totals.get(name)


def mean_us(run, name: str) -> float | None:
    """Microseconds a count under ``name``."""
    tot = total(run, name)
    if not tot or not tot[0]:
        return None
    return tot[1] / 1e3 / tot[0]


def share(run, *names: str) -> float | None:
    """Share (%) of the traced window under ``names`` together; None
    unless the ring holds every one of them."""
    window_s = (run.trace or {}).get('window_s')
    tots = [total(run, n) for n in names]
    if not window_s or any(t is None for t in tots):
        return None
    return 100.0 * sum(t[1] for t in tots) / 1e9 / window_s
