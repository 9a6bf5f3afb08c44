"""Share of the window the fleet's event loop was NOT blocked in
``select``: 1 - time waiting for sockets / window."""

def read(run):
    if run.select_s is None or not run.window_s:
        return None
    return 100.0 * (1.0 - run.select_s / run.window_s)
