"""Control for a member's store going back: one ``getData`` in
``EVERY`` of a record the session has ALREADY read is answered
``NO_NODE`` — what a member that lost an applied create (or served a
session from an older tree than it had shown it) would send.  ZooKeeper
allows ``NO_NODE`` for a record the session's member has not applied
yet, never for one the session has seen: the check must read
``stale-miss`` > 0."""

EVERY = 29
_N = [0]


class NoNode(Exception):
    """What the client raises for a ``NO_NODE`` reply, as far as a
    caller that reads ``.code`` can tell."""

    code = 'NO_NODE'


def wrap_client(c):
    n = _N      # one count over the whole fleet
    get = c.get
    seen: set = set()

    async def bad_get(path, **kw):
        out = await get(path, **kw)
        if path in seen:
            n[0] += 1
            if n[0] % EVERY == 3:
                raise NoNode('injected by controls/hide_node.py')
        seen.add(path)
        return out
    c.get = bad_get
    return c
