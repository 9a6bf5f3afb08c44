"""Control for the cache's serve gate: one read in ``EVERY`` of those
that show a version the session has shown before (a cache hit, but for
the races) is answered with the value the session held BEFORE that one
— an entry a notification dropped, served once more.  The bytes match
their version, so only the order gives it away: the check must read
``stale-hit`` > 0."""

EVERY = 199
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    get = c.get
    newest: dict = {}       # path -> the last (data, stat) handed out
    dropped: dict = {}      # path -> the one before it

    async def bad_get(path, **kw):
        data, stat = await get(path, **kw)
        last = newest.get(path)
        newest[path] = (data, stat)
        if last is not None and last[1].version != stat.version:
            dropped[path] = last        # a refresh: left as it is
        elif path in dropped:
            n[0] += 1
            if n[0] % EVERY == 3:
                return dropped[path]
        return data, stat
    c.get = bad_get
    return c
