"""Control for the invalidation stream: one change event in ``EVERY`` is
swallowed where the session hands it to its persistent watcher, so
neither the cache plane nor the subscriber's listener hears of it — the
silent gap the overload plane's eviction exists to prevent.  The check
must read ``missed-change`` > 0."""

EVERY = 499
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    add_watch = c.add_watch

    async def bad_add_watch(path, **kw):
        w = await add_watch(path, **kw)
        if getattr(w, '_dropping', False):
            return w
        w._dropping = True
        notify = w._notify

        def lossy(evt, p, zxid):
            n[0] += 1
            if n[0] % EVERY == 3:
                return
            notify(evt, p, zxid)
        w._notify = lossy
        return w
    c.add_watch = bad_add_watch
    return c
