"""p99 of the leader's ``fsync_gate`` tick phase since it started
(``mntr``)."""

KEY = 'zk_tick_phase_ms_p99{phase="fsync_gate"}'


def read(run):
    return run.mntr_leader(KEY)
