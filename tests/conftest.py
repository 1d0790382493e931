"""Test config: the CPU lane.  Forces the CPU backend with 8 virtual devices
so the sharding tests exercise a multi-chip mesh without TPU hardware, and
turns the persistent compile cache off: the package places it inside the
checkout (siddhi_tpu/__init__.py), and CPU executables left there would be
copied to — and offered to — the chip machine.

TPU lane, a debugging aid on the chip machine only: `SIDDHI_TEST_TPU=1
python -m pytest tests/<one kernel-family file> -q` keeps the real chip
and runs that file against device numerics (f64 emulation, scatter
mode="drop").  One process per chip: never the whole suite at once, never
beside another chip-holding process.  Mesh tests that need 8 devices skip
themselves on a smaller host."""
import os

TPU_LANE = bool(os.environ.get("SIDDHI_TEST_TPU"))

if not TPU_LANE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    # through the environment, so the subprocesses tests spawn inherit it
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

    import jax
else:
    import jax

import pytest

# Cases whose pinned expectation a later PR made stale and which that PR may
# not edit (`tests/benchmark/` is the benchmark's, BENCHMARK.json `paths`: a
# `benchmark` PR's to change).  Each still runs, strictly expected to fail:
# the day its literal is brought up to date it passes, fails the run as an
# unexpected pass, and its line here goes.  What else such a case held is
# held meanwhile by the test named beside it.
STALE_PINS = {
    # line 384, `fill["grids"] == {"1024x64x64": flushes}`: the rehearsal's
    # ~800 active lanes rode the power of two; since PR 46 the lane axis
    # pads to a sticky sixteenth of it, 832 rows (PERF.md section 7).  Kept
    # whole, with the grid as it is now, by tests/test_lane_axis.py::
    # test_the_pattern200k_rehearsal_is_steady_on_one_grid
    "benchmark/test_pattern200k_cell.py::"
    "test_the_rehearsal_is_steady_and_finds_the_cells_metrics":
        "pins the rehearsal's lane grid at the power of two (1024x64x64); "
        "832x64x64 since PR 46",
    # lines 100 and 116-118, `len(data["configs"]) == 7 and
    # len(data["workloads"]) == 8` and window1k.sat as the LAST cell: true
    # until PR 49 appended roomtemp10m.sat, as BENCHMARK.json's contract has
    # every later cell.  What else it holds (the cell's lists, 44 per-layer
    # entries, nothing before the cell moved) is held, by `index` and so for
    # every later cell too, by benchmark/test_roomtemp10m_cell.py::
    # test_the_manifest_gains_the_cell_and_nothing_before_it_moves
    "benchmark/test_window1k_cell.py::"
    "test_the_manifest_gains_the_cell_and_nothing_moves":
        "pins 7 configurations, 8 cells and window1k.sat as the last cell; "
        "roomtemp10m.sat is the ninth since PR 49",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        case = item.nodeid.split("[")[0]
        for pinned, why in STALE_PINS.items():
            if case.endswith(pinned):
                item.add_marker(pytest.mark.xfail(
                    reason=why, raises=AssertionError, strict=True))
    if not TPU_LANE or len(jax.devices()) >= 8:
        return
    skip = pytest.mark.skip(reason="TPU lane: needs an 8-device mesh")
    for item in items:
        if "test_mesh_async" in str(item.fspath):
            item.add_marker(skip)


def pytest_configure(config):
    # tier-1 CI runs `-m 'not slow'` (ROADMAP.md): long fuzz/paced-load
    # tests ride the full suite only, keeping tier-1 under its time box
    config.addinivalue_line(
        "markers", "slow: long-running (fuzz tapes, paced load); "
        "excluded from tier-1 via -m 'not slow'")


@pytest.fixture(scope="session", autouse=True)
def _siddhi_thread_leak_gate():
    """Thread-leak gate (docs/ANALYSIS.md "Concurrency self-analysis"):
    every engine thread is named `siddhi-<role>` (the SL06 lint holds
    that), so a NON-daemon siddhi-* thread still alive after the whole
    session tore its runtimes/services down is a leak — some shutdown
    path stopped joining it.  Daemon threads are exempt (process exit
    reaps them by design).  A failure here fails tier-1."""
    yield
    import threading
    import time
    deadline = time.time() + 2.0        # teardown joins may still settle

    def _leaky(t):
        if not t.name.startswith("siddhi-") or not t.is_alive():
            return False
        # the trace exporter (core/tracing.py) is daemonized BUT must
        # never outlive the session: tracer.close() joins it on
        # shutdown, and an unclosed tracer's exporter self-terminates
        # after ~0.5 s idle — either way it must be gone by now.  The
        # phase profiler (core/profiler.py) spawns no threads by
        # design; the gate pins that contract too
        if t.name in ("siddhi-trace-export", "siddhi-profile"):
            return True
        return not t.daemon

    while True:
        leaked = [t for t in threading.enumerate() if _leaky(t)]
        if not leaked or time.time() >= deadline:
            break
        time.sleep(0.1)
    assert not leaked, (
        "siddhi-* threads outlived the session (a shutdown "
        f"path stopped joining them): {sorted(t.name for t in leaked)}")


import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
