"""Control for the members' ``getData`` reply cache: one read in
``EVERY`` of a key the session has been shown at two versions or more
is answered with the bytes and the Stat of a version BELOW the newest
it was shown — what a member that handed out a serialized reply it
should have dropped would send.  The bytes match their version, so only
the order gives it away: the check must read ``stale-read`` > 0."""

EVERY = 199
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    get = c.get
    newest: dict = {}       # path -> the last (data, stat) handed out
    older: dict = {}        # path -> the version shown before that one

    async def bad_get(path, **kw):
        data, stat = await get(path, **kw)
        stale = older.get(path)
        last = newest.get(path)
        if last is not None and last[1].version < stat.version:
            older[path] = last
        newest[path] = (data, stat)
        if stale is not None:
            n[0] += 1
            if n[0] % EVERY == 3:
                return stale
        return data, stat
    c.get = bad_get
    return c
