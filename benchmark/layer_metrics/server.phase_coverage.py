"""How much of the LEADER process's CPU its tick ledger names: every
phase's ``zk_tick_phase_ms_sum`` delta over the ``zk_process_cpu_ms``
delta (cumulative ``mntr`` rows; the CPU is the whole process's, every
thread).  Phases are wall time on the loop's thread, so a phase that
blocks (an inline fsync) counts time the CPU row does not.  None
against a program without the CPU row."""

import inside_leader


def read(run):
    return inside_leader.phase_coverage(run)
