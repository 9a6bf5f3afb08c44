"""Control for the read path: one reply in ``EVERY`` has one bit of its
payload flipped where the client hands it to the caller — what a lossy
decode plane would produce.  The check must read ``payload`` > 0."""

EVERY = 997
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    get = c.get

    async def bad_get(path, **kw):
        data, stat = await get(path, **kw)
        n[0] += 1
        if n[0] % EVERY == 3 and data:
            data = bytes([data[0] ^ 1]) + data[1:]
        return data, stat
    c.get = bad_get
    return c
