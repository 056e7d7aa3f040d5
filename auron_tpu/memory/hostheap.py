"""The host allocator's freed pages, handed back when the program chooses.

glibc returns freed heap memory to the system lazily: a ``free()`` that
happens to leave the top of an arena's heap empty unmaps every empty heap
below it too, in the thread that called it — under the interpreter's lock
when it was a Python object that died, so every thread of the process
stands still while the kernel takes the pages back. After a table hand-over
(a staging copy of every split) or a compile burst (the compiler's working
memory) that is gigabytes: on a four-chip host the 23rd query after set-up
gave back 1.1 GB in 0.35 s between two of its stages, and 4-5 s in a
process that had compiled its 525 programs itself (PERF.md section 6,
PR 36).

``release_freed_heap`` pays that bill at the two moments the program knows
it has just freed a lot, and that are slow anyway: when a server has taken
its tables, and after a query that missed the plan cache (it traced,
lowered and compiled or loaded its programs).
"""

from __future__ import annotations

import ctypes

_libc = None


def _resident_bytes() -> int:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * 4096
    except (OSError, ValueError, IndexError):
        return 0


def release_freed_heap() -> int:
    """``malloc_trim(0)``: every arena's freed pages go back to the system
    now (the call releases the interpreter's lock; other threads run on).
    Returns the resident bytes it gave back (0 where the allocator is not
    glibc's, or there was nothing to give)."""
    global _libc
    if _libc is None:
        try:
            _libc = ctypes.CDLL(None)
            _libc.malloc_trim.argtypes = [ctypes.c_size_t]
            _libc.malloc_trim.restype = ctypes.c_int
        except (OSError, AttributeError):
            _libc = False
    if not _libc:
        return 0
    before = _resident_bytes()
    _libc.malloc_trim(0)
    return max(before - _resident_bytes(), 0)
