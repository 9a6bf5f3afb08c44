"""Readers of the LEADER's tick ledger over the window (``run.leader``:
the member the harness found leading; its cumulative ``mntr`` rows
before and after).  Against a run without that member's rows, or a
program without the phase or row asked for, every function returns
None."""

from __future__ import annotations

import inside


def phase_share(run, phases=None) -> float | None:
    """``inside.phase_share`` of the leader: share (%) of its window
    in ``phases`` (every phase when None)."""
    if run.leader not in inside.members(run):
        return None
    return inside.phase_share(run, run.leader, phases)


def phase_coverage(run) -> float | None:
    """Every phase's time over the leader PROCESS's CPU in the window
    (%): ``zk_tick_phase_ms_sum`` deltas over the ``zk_process_cpu_ms``
    delta."""
    busy = phase_share(run)
    cpu_ms = run.mntr_delta(run.leader, 'zk_process_cpu_ms')
    window_ms = inside.member_window_ms(run, run.leader)
    if busy is None or not cpu_ms or cpu_ms <= 0 or not window_ms:
        return None
    return busy * window_ms / cpu_ms
