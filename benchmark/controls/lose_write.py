"""Control for the write path: one write in ``EVERY`` is acknowledged
to the caller without ever being sent — the acknowledged write a leader
that skipped the WAL barrier or the quorum would lose in a crash.  The
check must read ``lost-write`` (or ``write-version``) > 0."""

EVERY = 499
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    set_ = c.set

    async def bad_set(path, data, **kw):
        n[0] += 1
        if n[0] % EVERY == 3:
            stat = await c.stat(path)
            return stat._replace(version=stat.version + 1)
        return await set_(path, data, **kw)
    c.set = bad_set
    return c
