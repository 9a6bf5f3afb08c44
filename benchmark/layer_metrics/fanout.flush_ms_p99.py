"""p99 of the members' ``fanout_flush`` tick phase since they started
(``mntr``), the largest over the members."""

KEY = 'zk_tick_phase_ms_p99{phase="fanout_flush"}'


def read(run):
    return run.mntr_max(KEY)
