"""Control for a pipelined connection: of two ``getData`` replies in
flight on ONE session, one in ``EVERY`` changes places with the next
one to arrive — each caller is handed the other's data and Stat, what
a route that settled a reply into another request's future would
produce.  Where every session keeps one request outstanding there is
never a second reply to swap with and nothing is changed.  The check
must read ``payload`` > 0 (the bytes are another znode's)."""

import asyncio

EVERY = 997
_N = [0]


def wrap_client(c):
    n = _N              # one count over the whole fleet
    out = [0]           # this session's reads in flight
    held: list = []     # at most one reply waiting for its partner
    get = c.get

    async def bad_get(path, **kw):
        out[0] += 1
        try:
            mine = await get(path, **kw)
        finally:
            out[0] -= 1
        n[0] += 1
        if held:
            fut, theirs = held.pop()
            if not fut.done():
                fut.set_result(mine)
                return theirs
        if n[0] % EVERY == 3 and out[0] > 0:
            fut = asyncio.get_running_loop().create_future()
            held.append((fut, mine))
            try:
                # the partner may fail instead of arriving: then this
                # reply is handed over as it came
                return await asyncio.wait_for(fut, 5.0)
            except asyncio.TimeoutError:
                if held and held[0][0] is fut:
                    held.pop()
                return mine
        return mine
    c.get = bad_get
    return c
