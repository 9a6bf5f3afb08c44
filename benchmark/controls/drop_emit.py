"""Control for the watch herd's delivery: one ``'dataChanged'`` emission
in ``EVERY`` is swallowed between a ``client.watcher(path)`` and its
listeners — a watcher that was told of a change (or armed) and handed
its listener nothing, as one that missed a wake-up across a follow-up
tick would.  A LATER change of the same document shows the broker a
newer version and would hide the gap from a check of "that version or
a later one": the check must read ``missed-change`` > 0 all the
same."""

EVERY = 499
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    watcher = c.watcher

    def bad_watcher(path):
        w = watcher(path)
        if getattr(w, '_dropping', False):
            return w
        w._dropping = True
        on = w.on

        def bad_on(evt, cb):
            if evt != 'dataChanged':
                return on(evt, cb)

            def lossy(data, stat):
                n[0] += 1
                if n[0] % EVERY == 3:
                    return
                cb(data, stat)
            return on(evt, lossy)
        w.on = bad_on
        return w
    c.watcher = bad_watcher
    return c
