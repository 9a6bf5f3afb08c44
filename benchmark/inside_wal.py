"""Readers of what the large-write path recorded about itself on a
member: the tick ledger's nested phases ``wal_append`` / ``wal_roll`` /
``repl_push`` (``zk_tick_phase_ms_sum{phase=}``) and the cumulative
``mntr`` rows beside them, over the window (``run.mntr_before/after``).
Against a program that has neither (the parent of the PR that brought
them) every function here finds nothing and returns None."""

from __future__ import annotations

MIB = float(1 << 20)


def phase_ms(run, member: int, phase: str) -> float | None:
    """Milliseconds the member's loop spent in ledger phase ``phase``
    inside the window; None when the member exports no such series."""
    key = 'zk_tick_phase_ms_sum{phase="%s"}' % (phase,)
    try:
        after = run.mntr_after[member]
    except IndexError:
        return None
    if key not in after:
        return None
    d = run.mntr_delta(member, key)
    if d is None:           # the series opened inside the window
        try:
            d = float(after[key])
        except ValueError:
            return None
    return d


def ms_per_mib(run, member: int, phase: str, row: str) -> float | None:
    """Phase milliseconds per MiB of the cumulative byte row ``row``."""
    ms = phase_ms(run, member, phase)
    moved = run.mntr_delta(member, row)
    if ms is None or not moved:
        return None
    return ms / (moved / MIB)


def changes(run) -> int:
    return int(run.result.get('counters', {}).get('changes_acked') or 0)
