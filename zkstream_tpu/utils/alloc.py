"""The C allocator's handling of LARGE blocks, pinned once a process.

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
"""

from __future__ import annotations

import ctypes

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
