"""Event-loop plumbing shared by the runtime components."""

from __future__ import annotations

import asyncio
import heapq
import socket
import threading

from .trace import host_span


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
    next.  A discarded entry drops its future at once and leaves the
    heap at the next compaction (more discarded than live), so the
    queue never holds a settled future or its reply; a compaction
    that dropped the entry the timer stood for moves the timer too,
    and one that leaves nothing cancels it.

    Host span ``client.deadline`` (profiler sessions only; count and
    total): each arming and each firing of the timer.  Beside
    ``client.submit``'s count it gives requests per loop timer."""

    __slots__ = ('loop', '_heap', '_seq', '_dead', '_timer')

    #: Discarded entries tolerated before a compaction is considered.
    COMPACT_MIN = 64

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self.loop = loop
        #: ``[when, seq, future]``; the future is None once discarded
        self._heap: list[list] = []
        self._seq = 0
        self._dead = 0
        self._timer: asyncio.TimerHandle | None = None

    def __len__(self) -> int:
        """Entries still waited on."""
        return len(self._heap) - self._dead

    def add(self, fut: asyncio.Future, seconds: float) -> list:
        self._seq += 1
        entry = [self.loop.time() + seconds, self._seq, fut]
        heapq.heappush(self._heap, entry)
        if self._timer is None or entry[0] < self._timer.when():
            with host_span('client.deadline', accumulate=True):
                self._set_timer(entry[0])
        return entry

    def discard(self, entry: list) -> None:
        if entry[2] is None:
            return              # fired, or discarded before
        entry[2] = None
        self._dead += 1
        if self._dead > self.COMPACT_MIN and \
                self._dead * 2 > len(self._heap):
            self._heap = [e for e in self._heap if e[2] is not None]
            heapq.heapify(self._heap)
            self._dead = 0
            when = self._heap[0][0] if self._heap else None
            if when != self._timer.when():
                with host_span('client.deadline', accumulate=True):
                    self._set_timer(when)

    def _set_timer(self, when: float | None) -> None:
        if self._timer is not None:
            self._timer.cancel()
        self._timer = None if when is None \
            else self.loop.call_at(when, self._fire)

    def _fire(self) -> None:
        with host_span('client.deadline', accumulate=True):
            self._timer = None
            heap, now = self._heap, self.loop.time()
            while heap and (heap[0][2] is None or heap[0][0] <= now):
                entry = heapq.heappop(heap)
                fut, entry[2] = entry[2], None
                if fut is None:
                    self._dead -= 1
                elif not fut.done():
                    fut.set_exception(DeadlineExpired())
            if heap:
                self._set_timer(heap[0][0])


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
