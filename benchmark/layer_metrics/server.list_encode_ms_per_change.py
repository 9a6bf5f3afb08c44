"""Milliseconds a change acknowledged in the window cost a member's
loop in sorting and encoding children lists it did not hold: ledger
phase ``list_encode`` (``zk_tick_phase_ms_sum{phase="list_encode"}``,
after less before) over the engine's ``changes_acked``, the busiest
member.  None against a program without the phase."""

import inside
import inside_wal


def read(run):
    ms = inside.largest(inside_wal.phase_ms(run, m, 'list_encode')
                        for m in inside.members(run))
    n = inside_wal.changes(run)
    return ms / n if ms is not None and n else None
