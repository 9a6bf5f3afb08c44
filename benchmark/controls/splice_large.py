"""Control for the wide rows of the read path: in one reply in ``EVERY``
of those over ``HEAD`` bytes, two ``BLOCK``-byte blocks BEYOND the first
``HEAD`` bytes change places where the client hands the data to the
caller — what a batch plane that laid a wide row down wrongly would
produce.  The length, ``stat.dataLength`` and the head are as they
were, so a check that compares a large body's length or samples its
head passes it: the check must read ``payload`` > 0.

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
    get = c.get
    scale = 16 if os.environ.get('JAX_PLATFORMS') == 'cpu' else 1
    head, block = HEAD // scale, BLOCK // scale

    async def bad_get(path, **kw):
        data, stat = await get(path, **kw)
        if len(data) >= head + 2 * block:
            n[0] += 1
            if n[0] % EVERY == 3:
                a, b = head, len(data) - block
                data = (data[:a] + data[b:] + data[a + block:b]
                        + data[a:a + block])
        return data, stat
    c.get = bad_get
    return c
