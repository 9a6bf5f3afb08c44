"""Not a control of the check but of the accounting: one churner's
``delete`` in ``EVERY`` raises in the caller (registration, which only
creates, is untouched).  The run must still exit 0; the failed change,
every change of that churner that could then not be sent, and each
(change, watcher) pair of those count in ``attempted`` and ``failed``
and as the deadline in the latencies."""

EVERY = 3
_N = [0]


def wrap_client(c):
    n = _N      # one count over the whole fleet
    delete = c.delete

    async def bad_delete(*a, **kw):
        n[0] += 1
        if n[0] % EVERY == 0:
            raise RuntimeError('injected by controls/fail_delete.py')
        return await delete(*a, **kw)
    c.delete = bad_delete
    return c
