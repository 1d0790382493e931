"""Host memory under the result path's large buffers.

A partitioned pattern flush over many keys pulls a result of hundreds of
megabytes (a 147,456 x 64 lane grid: 302 MB), which the runtime lands in a
buffer it allocates for that pull, and packs grids of tens of megabytes
beside it: about a gigabyte of buffers born and freed every flush.  glibc
serves a chunk over its mmap threshold by a fresh `mmap` and gives it back by
`munmap`, and the threshold never passes 32 MB, so none of those buffers is
ever recycled: every flush takes every page of them from the kernel again.
What a page costs is the machine's to say, and it says different things at
different times: on the chip machines (gVisor) `pattern200k.sat` ran a flush
in 272-278 ms or in 355-375, drawn per run and moving inside a run, on
programs that were the same to the byte (PERF.md 7.13).

`keep_large_chunks` tells glibc to serve large chunks from its heap and to
keep what is given back (`M_MMAP_MAX` 0, `M_TRIM_THRESHOLD` at its ceiling):
after the first flushes every buffer is memory the process already holds.
The policy is the PROCESS's, so it is not set at import or for every app: a
device plan asks for it when it first pulls a result glibc could never
recycle (`LARGE`), and a process that never does keeps glibc's defaults.
The cost is the heap's high-water mark held until the process ends.
"""
import ctypes
import sys

# glibc's DEFAULT_MMAP_THRESHOLD_MAX on 64-bit: its dynamic threshold stops
# here, so a chunk this large is mapped and unmapped every time by default
LARGE = 32 << 20

_M_TRIM_THRESHOLD = -1
_M_MMAP_MAX = -4
_INT_MAX = 2 ** 31 - 1      # mallopt takes an int

_kept = None                # None: not asked yet


def keep_large_chunks() -> bool:
    """Ask the C library, once a process, to recycle large chunks; says
    whether it agreed (False off Linux and where libc has no `mallopt`)."""
    global _kept
    if _kept is None:
        _kept = False
        if sys.platform.startswith("linux"):
            try:
                mallopt = ctypes.CDLL(None).mallopt
                mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
                mallopt.restype = ctypes.c_int
                _kept = bool(mallopt(_M_MMAP_MAX, 0)
                             and mallopt(_M_TRIM_THRESHOLD, _INT_MAX))
            except (OSError, AttributeError):
                pass
    return _kept


def kept():
    """None until a plan has asked, then what `keep_large_chunks` said."""
    return _kept
