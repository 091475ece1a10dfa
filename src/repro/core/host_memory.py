"""Host heap policy of a long-lived query-serving process.

Each fragment allocates and frees column arrays of 10-200 MB: decoded
objects, their concatenation, sort orders, gathers and padded value
stacks. glibc serves a request above its mmap threshold with a fresh
mapping, whose pages fault in one at a time on first touch, and hands
freed memory at the top of the heap back to the kernel once it exceeds
its trim threshold. By default both thresholds follow the largest
mapped block the process has freed so far, so the same query pays a
different number of page faults from one process to the next, and a
server's throughput depends on its allocation history.

``steady_heap`` fixes both thresholds: requests up to 32 MiB come from
the heap, and the heap keeps up to 1 GiB of freed memory for reuse.
"""
from __future__ import annotations

import ctypes

# glibc's upper limit for the mmap threshold on 64-bit hosts.
MMAP_THRESHOLD = 32 << 20
TRIM_THRESHOLD = 1 << 30

_M_TRIM_THRESHOLD = -1
_M_MMAP_THRESHOLD = -3


def steady_heap() -> bool:
    """Fix the C library's mmap and trim thresholds for this process.
    Returns False where the C library has no ``mallopt`` or refuses."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    return bool(mallopt(_M_MMAP_THRESHOLD, MMAP_THRESHOLD)) \
        and bool(mallopt(_M_TRIM_THRESHOLD, TRIM_THRESHOLD))
