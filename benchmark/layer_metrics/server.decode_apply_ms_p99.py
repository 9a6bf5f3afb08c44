"""p99 of the members' ``decode_apply`` tick phase since they started
(``mntr``), the largest over the members."""

KEY = 'zk_tick_phase_ms_p99{phase="decode_apply"}'


def read(run):
    return run.mntr_max(KEY)
