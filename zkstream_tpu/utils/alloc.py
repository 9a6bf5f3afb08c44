"""Process-wide memory policy of a process that holds a fleet: the C
allocator's handling of LARGE blocks, pinned once a process, and the
garbage collector's young generation, sized to the fleet.

A reply or request body of ~0.1-1 MB becomes a ``bytes`` of its own
(the C decode's, a pickle's, a record's ``join``), lives for a tick and
is freed.  glibc's defaults decide per process, from the order its
first large blocks happened to be freed in, whether such a block is
``mmap``-ed and ``munmap``-ed every time or cut from the heap, and
whether the heap's top is handed back to the kernel after every burst
and faulted in again for the next — and on a machine whose ``mmap`` /
``brk`` are slow (the chip machine's sandboxed kernel) that is a
factor of four in the decode of a herd of large replies, different
from run to run of one tree (PERF.md, PR 32).  A process that holds a
fleet of sessions (``io/ingest.py``) or serves as an ensemble member
(``server/member_worker.py``) keeps what it has freed for the next
burst instead: blocks up to 32 MiB come from the heap, the heap is
not trimmed, and it grows in 64 MiB steps.  The cost is that the
process's resident size stays at its peak.

**The collector.**  One device tick of a fleet's ingest allocates its
replies in a burst — a packet ``dict`` and a ``Stat`` a frame, up to a
frame a request alive, nothing freed in between — and 700 net container
allocations (the interpreter's young-generation threshold) start a
collection: at 1,024 sessions that was ONE collection a tick, ~2.3 ms
each, 8.6-8.8% of a window (PERF.md, PR 43), and every one of them
walked the ~1,024 in-flight operations' packets, requests, spans,
futures and coroutine frames, found nothing — an operation's objects
die by reference count when it completes (tests/test_ingest_gc.py
pins that a read leaves no cyclic garbage) — and promoted them.  A
process that holds a ``FleetIngest`` therefore sizes the young
generation to its fleet (:func:`fit_collector`):
``max(interpreter default, YOUNG_PER_SLOT x requests alive)`` — the
registered slots while every session keeps one request outstanding,
derived again when the slot count doubles or halves; where the
clients pipeline, the requests the ingest finds pending on its slots'
connections when a tick routes more frames than it was sized to
(``FleetIngest._fit_collector``: 8,192 at 1,024 sessions x 8
outstanding; nothing to configure) — the older
generations' thresholds as they were, put back when the process's
last ingest closes.  It is applied only while ``gc.get_threshold()``
reads an interpreter default or what this module set: an owner who
calls ``gc.set_threshold`` — before or after — keeps theirs, and so
does one who disables the collector.  What it defers is cyclic
garbage alone, by at most that many net container allocations (a few
MB).
"""

from __future__ import annotations

import ctypes
import gc
import weakref

#: glibc ``mallopt`` parameters (malloc.h)
_M_TRIM_THRESHOLD = -1
_M_TOP_PAD = -2
_M_MMAP_THRESHOLD = -3

MMAP_THRESHOLD = 32 << 20       # the largest glibc accepts
TRIM_THRESHOLD = 1 << 30
TOP_PAD = 64 << 20

_done: bool | None = None


def keep_freed_memory() -> bool:
    """Pin the thresholds (idempotent; the first call's answer is
    kept).  False where the C library has no ``mallopt`` or refuses:
    the process then runs on the allocator's defaults, as before."""
    global _done
    if _done is None:
        try:
            mallopt = ctypes.CDLL(None).mallopt
            mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
            mallopt.restype = ctypes.c_int
            _done = all([mallopt(_M_MMAP_THRESHOLD, MMAP_THRESHOLD) == 1,
                         mallopt(_M_TRIM_THRESHOLD, TRIM_THRESHOLD) == 1,
                         mallopt(_M_TOP_PAD, TOP_PAD) == 1])
        except (OSError, AttributeError):
            _done = False
    return _done


#: Net container allocations the young generation allows a request
#: alive before a collection: a tick's burst is about two a frame and
#: at most a frame a request alive — a frame a registered slot where
#: every session keeps one request outstanding, which is what the name
#: says and what it was fitted at; ``max_frames`` a slot where the
#: clients pipeline — so the burst and what the woken operations
#: allocate before the next tick's stay under it.  Fitted on the chip
#: at 1,024 sessions x 1 against its two neighbours (PERF.md section
#: 5, PR 43), held at 1,024 x 8 (section 6, PR 47).
YOUNG_PER_SLOT = 32

#: What CPython starts with (3.8-3.12; 3.13; 3.14): thresholds an
#: owner has not touched.
INTERPRETER_DEFAULTS = ((700, 10, 10), (2000, 10, 10), (2000, 10, 0))

#: holder (a ``FleetIngest``) -> the requests alive it reported last;
#: weak, so an ingest dropped without ``close()`` leaves the sum
_fleets: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()
#: the default found before the first raise, and what stands set now
#: (None: nothing of this module's stands)
_found: tuple | None = None
_set: tuple | None = None


def fit_collector(holder, alive: int) -> None:
    """``holder`` (a fleet ingest) can have ``alive`` requests alive
    now: its registered slots, or what it found pending on them where
    its clients pipeline — it calls at construction and whenever that
    doubled or halved: size the young generation to the process's
    fleets."""
    _fleets[holder] = alive
    _derive()


def release_collector(holder) -> None:
    """``holder`` closed: the young generation follows the fleets that
    remain; the last one puts back what was found."""
    if _fleets.pop(holder, None) is not None:
        _derive()


def _derive() -> None:
    global _found, _set
    now = gc.get_threshold()
    if _set is None:
        if now not in INTERPRETER_DEFAULTS:
            return              # the owner's own: left alone
        _found = now
    elif now != _set:
        _set = None             # set by the owner since: theirs stands
        return
    if not _fleets:
        gc.set_threshold(*_found)
        _set = None
        return
    young = max(_found[0], YOUNG_PER_SLOT * sum(_fleets.values()))
    _set = (young,) + _found[1:]
    gc.set_threshold(*_set)
