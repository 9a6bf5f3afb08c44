"""Device time of the tick program (``jit_step`` in the trace) per
execution in the traced window."""

PROGRAM = 'jit_step'


def read(run):
    prog = (run.trace or {}).get('programs', {}).get(PROGRAM)
    if not prog or not prog['count']:
        return None
    return prog['seconds'] * 1e3 / prog['count']
