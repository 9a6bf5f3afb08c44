"""Not a control of the check but of the harness: one op in ``EVERY``
raises in the caller.  The run must still exit 0 with the op counted
in ``failed``, and leave nothing behind."""

EVERY = 5
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet

    def raising(fn):
        async def op(*a, **kw):
            n[0] += 1
            if n[0] % EVERY == 0:
                raise RuntimeError('injected by controls/raise_op.py')
            return await fn(*a, **kw)
        return op
    c.get, c.set = raising(c.get), raising(c.set)
    c.delete, c.create = raising(c.delete), raising(c.create)
    return c
