"""Control for the watch herd's read path: one ``'childrenChanged'``
view in ``EVERY`` reaches the watcher's listener as the list and the
stat of the change BEFORE — what a member that answered a re-list from
a serialized reply it should have dropped would hand out.  The list is
a membership the directory really had: the check must read
``stale-view`` > 0 (the node had been shown that change already)."""

EVERY = 199
_N = [0]
_SEEN: dict = {}        # cversion -> (children, stat), fleet-wide


def wrap_client(c):
    n, seen = _N, _SEEN
    watcher = c.watcher

    def bad_watcher(path):
        w = watcher(path)
        on = w.on

        def bad_on(evt, cb):
            if evt != 'childrenChanged':
                return on(evt, cb)

            def stale(children, stat, *rest):
                seen.setdefault(stat.cversion, (children, stat))
                seen.pop(stat.cversion - 8, None)
                older = seen.get(stat.cversion - 1)
                if older is not None:
                    n[0] += 1
                    if n[0] % EVERY == 3:
                        children, stat = older
                return cb(children, stat, *rest)
            return on(evt, stale)
        w.on = bad_on
        return w
    c.watcher = bad_watcher
    return c
