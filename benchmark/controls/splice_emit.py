"""Control for the watch herd's read path: in one view in ``EVERY`` of
those over ``HEAD`` bytes that a ``client.watcher(path)`` hands its
``'dataChanged'`` listeners, two ``BLOCK``-byte blocks BEYOND the first
``HEAD`` bytes change places — what a tick that laid a wide row down
wrongly, or split a burst over two ticks wrongly, would produce.  The
length, ``stat.dataLength`` and the head are as they were: the check
must read ``payload`` > 0.  (The controls that wrap ``client.get``
prove nothing here: a broker's bytes come out of the watcher's own
re-arm request.)

The rehearsal (``rehearse.py`` holds JAX to the CPU) runs the
deployment with every size a sixteenth: there ``HEAD`` and ``BLOCK``
are a sixteenth too."""

import os

EVERY = 7
HEAD = 64 * 1024
BLOCK = 4 * 1024
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    watcher = c.watcher
    scale = 16 if os.environ.get('JAX_PLATFORMS') == 'cpu' else 1
    head, block = HEAD // scale, BLOCK // scale

    def bad_watcher(path):
        w = watcher(path)
        if getattr(w, '_splicing', False):
            return w
        w._splicing = True
        on = w.on

        def bad_on(evt, cb):
            if evt != 'dataChanged':
                return on(evt, cb)

            def spliced(data, stat):
                if len(data) >= head + 2 * block:
                    n[0] += 1
                    if n[0] % EVERY == 3:
                        a, b = head, len(data) - block
                        data = (data[:a] + data[b:] + data[a + block:b]
                                + data[a:a + block])
                cb(data, stat)
            return on(evt, spliced)
        w.on = bad_on
        return w
    c.watcher = bad_watcher
    return c
