"""Event-loop plumbing shared by the runtime components."""

from __future__ import annotations

import asyncio
import math
import socket
import threading
import time

from .trace import host_span, loop_idle


def ambient_loop() -> asyncio.AbstractEventLoop:
    """The running loop, or — outside a running loop — the thread's set
    loop.

    ``Client.start()`` — and the client/pool FSM transitions it drives
    synchronously — may legitimately run before the loop starts
    spinning, queuing work the loop will process once entered;
    ``asyncio.get_running_loop`` alone would forbid that pattern, while
    bare ``get_event_loop`` is deprecated when no loop is set.  This
    helper keeps both cases working and never creates an implicit loop
    inside callbacks.  (Connections themselves are constructed only
    inside pool tasks, so ``io/connection.py`` uses the stricter
    ``get_running_loop`` throughout.)
    """
    try:
        return asyncio.get_running_loop()
    except RuntimeError:
        return asyncio.get_event_loop()


class DeadlineExpired(Exception):
    """What :class:`DeadlineQueue` sets on a future whose deadline
    passed while it was still pending."""


class DeadlineQueue:
    """The deadlines of one event loop's pending futures, behind ONE
    loop timer (not one ``asyncio.wait_for`` — a coroutine, a timeout
    object and a ``TimerHandle`` — per request).

    :meth:`add` registers ``(deadline, future)`` and hands back the
    entry; the awaiter awaits the bare future and calls :meth:`discard`
    on the entry when its wait ends, however it ends.  The timer is armed
    for the earliest deadline; when it fires, every overdue future
    that is still pending fails with :class:`DeadlineExpired` — never
    before its deadline by the loop's clock, and late by no more than
    the loop iteration the timer fires in — and the timer moves to the
    next.  A discarded entry drops its future at once, so the queue
    never holds a settled future or its reply.

    Nearly every wait of a loop carries one of a few timeouts (a
    client's ``op_timeout``), and waits of ONE timeout come due in the
    order they were added.  So the queue keeps a FIFO a distinct
    timeout — ``{future: when}``, a dict in insertion order — and no
    heap: an entry is one append (the future itself is its key, no
    object is made for it), a discard one removal, and the earliest
    deadline of all is the least of the FIFOs' heads.  The timer
    stands for a time no later than that: the head it was armed for
    may have been discarded since, and then it fires early for
    nothing, once, and moves to the oldest head still live; with
    nothing live it is not armed again.  A timeout nothing waits under
    any more leaves the table when that is swept (at a firing, and
    when a new timeout finds more than :attr:`SWEEP_MIN` others).

    Host span ``client.deadline`` (profiler sessions only; count and
    total): each arming and each firing of the timer.  Beside
    ``client.submit``'s count it gives requests per loop timer.

    Being the one object a loop's clients share from their start, it
    also carries the loop's idle clock: :attr:`idle_ns` and
    :attr:`turns`, fed by one wrapper around the loop's
    ``_selector.select`` that is put on here and left in place
    (a loop without that attribute gets none, and both stay 0).
    1 - idle / elapsed is the loop's utilisation; inside a profiler
    session the same call is host span ``loop.idle``
    (utils/trace.loop_idle)."""

    __slots__ = ('loop', '_lanes', '_timer', '_when', '_sweep_at',
                 'idle_ns', 'turns')

    #: Timeouts in the table before a new one looks for empty ones.
    SWEEP_MIN = 64

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self.loop = loop
        #: timeout in seconds -> ``{future: when}``, oldest first
        self._lanes: dict[float, dict] = {}
        self._timer: asyncio.TimerHandle | None = None
        #: what the timer is armed for; inf while it is not
        self._when = math.inf
        self._sweep_at = self.SWEEP_MIN
        #: nanoseconds the loop stood in ``select``, and how often
        self.idle_ns = 0
        self.turns = 0
        self._time_select()

    def _time_select(self) -> None:
        selector = getattr(self.loop, '_selector', None)
        if selector is None:
            return
        select = selector.select
        clock = time.perf_counter_ns

        def timed(timeout=None):
            t0 = clock()
            with loop_idle():
                events = select(timeout)
            self.idle_ns += clock() - t0
            self.turns += 1
            return events
        selector.select = timed

    def __len__(self) -> int:
        """Entries still waited on."""
        return sum(map(len, self._lanes.values()))

    def add(self, fut: asyncio.Future, seconds: float) -> dict:
        """Bound ``fut`` (which stands in the queue once) by
        ``seconds`` from now.  The entry handed back is for
        :meth:`discard`, with the future."""
        when = self.loop.time() + seconds
        lane = self._lanes.get(seconds)
        if lane is None:
            lane = self._new_lane(seconds)
        lane[fut] = when
        if when < self._when:
            with host_span('client.deadline', accumulate=True):
                self._set_timer(when)
        return lane

    def discard(self, entry: dict, fut: asyncio.Future) -> None:
        """``fut``'s wait has ended: idempotent, and nothing if its
        deadline fired."""
        entry.pop(fut, None)

    def _new_lane(self, seconds: float) -> dict:
        lanes = self._lanes
        if len(lanes) >= self._sweep_at:
            for s in [s for s, lane in lanes.items() if not lane]:
                del lanes[s]
            self._sweep_at = max(self.SWEEP_MIN, 2 * len(lanes))
        lane = lanes[seconds] = {}
        return lane

    def _set_timer(self, when: float) -> None:
        if self._timer is not None:
            self._timer.cancel()
        self._when = when
        self._timer = self.loop.call_at(when, self._fire)

    def _fire(self) -> None:
        with host_span('client.deadline', accumulate=True):
            self._timer, self._when = None, math.inf
            now, nxt = self.loop.time(), math.inf
            for seconds, lane in list(self._lanes.items()):
                overdue = []
                for fut, when in lane.items():
                    if when > now:
                        nxt = min(nxt, when)
                        break
                    overdue.append(fut)
                for fut in overdue:
                    del lane[fut]
                    if not fut.done():
                        fut.set_exception(DeadlineExpired())
                if not lane:
                    # nothing in it to orphan (an awaiter that still
                    # holds it as its entry only discards)
                    del self._lanes[seconds]
            if nxt != math.inf:
                self._set_timer(nxt)


#: loop -> its DeadlineQueue.  A closed loop's queue is swept by the
#: next loop's first registration (as io/transport's shared tiers are).
_loop_deadlines: dict[asyncio.AbstractEventLoop, DeadlineQueue] = {}
#: Loops in different threads register through one table.
_loop_deadlines_lock = threading.Lock()


def deadline_queue(loop: asyncio.AbstractEventLoop) -> DeadlineQueue:
    """The one :class:`DeadlineQueue` of ``loop`` (made on first ask;
    every later ask is one dict lookup, no lock)."""
    queue = _loop_deadlines.get(loop)
    if queue is None:
        with _loop_deadlines_lock:
            for dead in [lp for lp in _loop_deadlines if lp.is_closed()]:
                del _loop_deadlines[dead]
            queue = _loop_deadlines.setdefault(loop, DeadlineQueue(loop))
    return queue


def set_nodelay(endpoint) -> None:
    """Set ``TCP_NODELAY`` on an asyncio transport or StreamWriter.

    ZooKeeper traffic is small request/reply frames; with Nagle on, the
    kernel delays a short frame behind an unacked one, adding an RTT-ish
    stall per op under write-heavy load.  Any write batching should be
    the send plane's explicit per-tick cork (io/sendplane.py), not the
    kernel's implicit one.  Best-effort: non-TCP endpoints (unix
    sockets, test doubles without a real socket) are left alone."""
    try:
        sock = endpoint.get_extra_info('socket')
        if sock is not None:
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except (OSError, ValueError, AttributeError):
        pass
