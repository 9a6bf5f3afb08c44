"""Control for the watch path: one children list in ``EVERY`` reaches
the watcher's callback without its first name — a stale or torn list.
The check must read ``children`` > 0."""

EVERY = 199
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    watcher = c.watcher

    def bad_watcher(path):
        w = watcher(path)
        on = w.on

        def bad_on(evt, cb):
            def short(children, *rest):
                n[0] += 1
                if n[0] % EVERY == 3 and children:
                    children = list(children)[1:]
                return cb(children, *rest)
            return on(evt, short if evt == 'childrenChanged' else cb)
        w.on = bad_on
        return w
    c.watcher = bad_watcher
    return c
