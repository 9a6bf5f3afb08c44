"""Control for the large-write path: in one ``set`` body in ``EVERY`` of
those over ``HEAD`` bytes, two ``BLOCK``-byte blocks BEYOND the first
``HEAD`` bytes change places before the request is sent — what a send
plane that re-queued the remainder of a partial write wrongly, a WAL or
a replication frame that laid a large record down wrongly would leave
in the tree.  The length and the head are as they were: the brokers'
views and the final tree must read ``payload`` > 0.

The rehearsal (``rehearse.py`` holds JAX to the CPU) runs the
deployment with every size a sixteenth: there ``HEAD`` and ``BLOCK``
are a sixteenth too."""

import os

EVERY = 7
HEAD = 64 * 1024
BLOCK = 4 * 1024
_N = [0]


def splice(data, head, block):
    a, b = head, len(data) - block
    return data[:a] + data[b:] + data[a + block:b] + data[a:a + block]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    set_ = c.set
    scale = 16 if os.environ.get('JAX_PLATFORMS') == 'cpu' else 1
    head, block = HEAD // scale, BLOCK // scale

    async def bad_set(path, data, **kw):
        if len(data) >= head + 2 * block:
            n[0] += 1
            if n[0] % EVERY == 3:
                data = splice(data, head, block)
        return await set_(path, data, **kw)
    c.set = bad_set
    return c
