"""Control for the session churn: in one ``'childrenChanged'`` view in
``EVERY`` of those that lack a name some earlier view held, that name
— a node whose session was closed — is put back: an ephemeral that
outlived its owner, as a member that missed a close's delete would
list it.  The stat is as it was: the check must read ``children``
> 0."""

EVERY = 199
_N = [0]
_EVER: set = set()      # every name any view held, fleet-wide


def wrap_client(c):
    n, ever = _N, _EVER
    watcher = c.watcher

    def bad_watcher(path):
        w = watcher(path)
        on = w.on

        def bad_on(evt, cb):
            if evt != 'childrenChanged':
                return on(evt, cb)

            def ghost(children, *rest):
                gone = ever.difference(children)
                ever.update(children)
                if gone:
                    n[0] += 1
                    if n[0] % EVERY == 3:
                        children = list(children) + [min(gone)]
                return cb(children, *rest)
            return on(evt, ghost)
        w.on = bad_on
        return w
    c.watcher = bad_watcher
    return c
