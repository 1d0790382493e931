"""The allocator policy of the result path's large buffers (core/hostmem.py):
glibc told, once a process, to recycle large chunks; asked for by a device
pattern plan when it first pulls a result the C library could never recycle,
and by nothing else.  The policy is the process's, so every case that turns
it on runs in a process of its own: this suite's workers keep glibc's
defaults.
"""
import subprocess
import sys
import warnings

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import hostmem
from siddhi_tpu.core.pattern_plan import DevicePatternPlan

GLIBC = sys.platform.startswith("linux")


def _child(code: str) -> str:
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True)
    return out.stdout.strip()


def test_the_threshold_is_glibcs_ceiling():
    assert hostmem.LARGE == 32 * 1024 * 1024


def test_nothing_is_asked_at_import():
    assert _child("from siddhi_tpu.core import hostmem; import siddhi_tpu; "
                  "print(hostmem.kept())") == "None"


@pytest.mark.skipif(not GLIBC, reason="mallopt is glibc's")
def test_it_is_asked_once_and_says_so():
    assert _child(
        "from siddhi_tpu.core import hostmem as h; "
        "print(h.kept(), h.keep_large_chunks(), h.kept(), "
        "h.keep_large_chunks())") == "None True True True"


@pytest.mark.skipif(not GLIBC, reason="mallopt is glibc's")
@pytest.mark.parametrize("mb", [48, 160])
def test_a_large_chunk_then_comes_from_the_heap_and_comes_back(mb):
    """Before: a chunk over the threshold is a mapping of its own, far from
    the heap.  After: it is cut from the heap, stays with the process when
    freed, and the next request of its size gets the same memory."""
    code = f"""
import numpy as np
from siddhi_tpu.core import hostmem
n = {mb} << 20
small = np.empty(64, np.uint8).ctypes.data          # the heap is here
a = np.empty(n, np.uint8); mapped = a.ctypes.data; del a
assert hostmem.keep_large_chunks()
b = np.empty(n, np.uint8); b[::4096] = 1; first = b.ctypes.data; del b
c = np.empty(n, np.uint8); again = c.ctypes.data
far = abs(mapped - small) > (1 << 40)
near = abs(first - small) < (1 << 36)
print(far, near, first == again)
"""
    assert _child(code) == "True True True"


def test_off_linux_it_declines(monkeypatch):
    monkeypatch.setattr(hostmem, "_kept", None)
    monkeypatch.setattr(hostmem.sys, "platform", "darwin")
    assert hostmem.keep_large_chunks() is False
    assert hostmem.kept() is False


def test_a_libc_without_mallopt_declines(monkeypatch):
    class _NoMallopt:
        def __getattr__(self, name):
            raise AttributeError(name)
    monkeypatch.setattr(hostmem, "_kept", None)
    monkeypatch.setattr(hostmem.sys, "platform", "linux")
    monkeypatch.setattr(hostmem.ctypes, "CDLL", lambda name: _NoMallopt())
    assert hostmem.keep_large_chunks() is False


APP = """@app:partitionCapacity(8)
define stream S (sym string, price double);
partition with (sym of S)
begin
  @info(name='q')
  from every e1=S[price > 100] -> e2=S[price > e1.price] within 1 sec
  select e1.price as a, e2.price as b insert into Out;
end;
"""


@pytest.mark.parametrize("large,asked", [(hostmem.LARGE, 0), (1, 1)])
def test_a_pattern_plan_asks_at_its_first_large_pull_only(monkeypatch, large,
                                                          asked):
    """A small test's pulls are kilobytes: the plan asks nothing.  With the
    threshold lowered under them every pull asks (the helper answers from
    its flag after the first)."""
    calls = []
    monkeypatch.setattr(hostmem, "LARGE", large)
    monkeypatch.setattr(hostmem, "keep_large_chunks",
                        lambda: calls.append(1) or True)
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(APP)
    rt.start()
    assert any(isinstance(p, DevicePatternPlan) for p in rt._plans)
    rows = []
    rt.add_callback("Out", lambda evs: rows.extend(e.data for e in evs))
    sym = np.array([rt.strings.encode(f"K{k}") for k in range(4)], np.int32)
    h = rt.input_handler("S")
    for i in range(3):
        h.send_batch({"sym": sym, "price": np.full(4, 101.0 + i)},
                     np.full(4, 1_700_000_000_000 + 400 * i, np.int64))
        rt.flush()
    mgr.shutdown()
    assert len(rows) == 8               # two flushes complete four rows each
    assert (len(calls) > 0) == bool(asked)
    assert hostmem.kept() is None       # this process was never asked
