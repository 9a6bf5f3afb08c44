"""Share of the traced window inside the program's per-frame boundary
``client.notify``: an xid -1 frame at the session until every watcher
it matches has emitted — the cache plane's invalidation and the
subscriber's own listener (which only schedules its refresh) included.
None against a program without the span."""

import inside


def read(run):
    return inside.span_total_share(run, 'client.notify')
