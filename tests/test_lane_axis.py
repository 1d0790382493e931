"""The lane axis of the partitioned lane grid (lane_grid.py
`_sticky_sixteenth`, `LaneGrid.L`): the rows of a flush (its active lanes, or
the cut rows of its hot ones) pad to a granule of a sixteenth of their
power of two, never under 8, sticky like the grid's F, then to the mesh's
device count.  Held here: (a) the padded count, by arithmetic and through a
plan with and without a mesh; (b) the stickiness, by `lane_fill.grids` and
by jax's count of backend compilations, and its rollback with `LaneGrid.F`
when a dispatch fails; (c) that padding is only padding: a cut flush and a
many-short-lanes run with held tails deliver, row for row and in order,
what the same run delivers with the lane axis forced to the power of two,
and what the host interpreter delivers.
"""
import warnings

import numpy as np
import pytest

import test_lane_cut as lc
import test_many_short_lanes as msl
from test_lane_pack import _NoDevice
from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import lane_grid
from siddhi_tpu.core.nfa_device import pow2_at_least
from siddhi_tpu.core.lane_grid import _sticky_sixteenth
from siddhi_tpu.core.pattern_plan import DevicePatternPlan

T0 = 1_700_000_000_000

# lanes a flush -> rows of its grid (the first flush: nothing held)
PADDED = {1: 8, 3: 8, 8: 8, 9: 16, 250: 256, 801: 832, 1000: 1024,
          1024: 1024, 1025: 1152, 1088: 1152, 146_080: 147_456,
          200_000: 212_992}


# -- (a) the padded count ------------------------------------------------------

@pytest.mark.parametrize("lanes", list(PADDED))
def test_the_padded_count(lanes):
    assert _sticky_sixteenth(lanes, 0, lo=8) == PADDED[lanes]


def test_the_padding_is_under_an_eighth_and_whole_sublanes():
    for n in list(range(1, 5000)) + [2 ** k + d for k in range(13, 19)
                                     for d in (-1, 0, 1, 777)]:
        rows = _sticky_sixteenth(n, 0, lo=8)
        assert rows >= n and rows % 8 == 0, n
        assert rows <= pow2_at_least(n, lo=8), n
        if n > 128:
            assert 8 * rows <= 9 * n, (n, rows)


@pytest.mark.parametrize("n", [1, 5, 8, 9, 17, 71, 72, 73, 1000])
def test_with_no_floor_it_is_the_cut_fused_rows_rule(n):
    """`_fused_cut` pads its ROWS by the same helper with no floor: the
    arithmetic it has carried since PR 37 (rules1k.sat's 71-72 rows -> 72)."""
    g = max(1, pow2_at_least(n) // 16)
    assert _sticky_sixteenth(n, 0) == -(-n // g) * g


@pytest.mark.parametrize("held,n,want", [
    (0, 1088, 1152),        # the first flush
    (1152, 1086, 1152),     # drift inside the granule
    (1152, 1152, 1152),
    (1152, 1153, 1280),     # past it: one granule more
    (1280, 1088, 1280),     # and it stays
    (1280, 320, 1280),      # 4 x 320 = 1280: not over four times
    (1280, 289, 1280),
    (1280, 288, 288),       # 4 x 288 < 1280: dropped for what 288 needs
    (1024, 1000, 1024), (1024, 960, 1024), (0, 960, 960),
])
def test_what_is_held_stays_while_it_serves(held, n, want):
    assert _sticky_sixteenth(n, held, lo=8) == want


APP = """@app:partitionCapacity(8)
define stream S (sym string, price double);
partition with (sym of S)
begin
  @info(name='q')
  from every e1=S[price > 100] -> e2=S[price > e1.price] within 1 sec
  select e1.price as a, e2.price as b insert into Out;
end;
"""


class Lanes:
    """One runtime and its partitioned scan plan; `flush(keys)` sends one
    event for each key, 400 ms after the flush before and at a higher
    price (a lane's tail is its last three events, so F stays 16, and a
    key's event completes a row with each of them), and returns the rows
    the lane axis holds after it.  `device=False` cuts the dispatch off
    and records the packed shapes, for flushes no block should compile
    for."""

    def __init__(self, device=True, mesh_devices=None):
        self.mgr = SiddhiManager()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            self.rt = self.mgr.create_app_runtime(APP)
        self.rt.start()
        self.plan = next(p for p in self.rt._plans
                         if isinstance(p, DevicePatternPlan))
        assert self.plan.family == "scan" and self.plan._partitioned
        self.rows_out = []
        self.rt.add_callback("Out", lambda evs: self.rows_out.extend(
            (e.timestamp, *e.data) for e in evs))
        self.t, self.price = T0, 101.0
        self.sym = {}
        self.packed = None
        if mesh_devices:
            # the pack reads the mesh's size alone; the dispatch is cut off
            assert not device
            self.plan.grid.n_devices = mesh_devices
        if not device:
            self.plan._pipe = _NoDevice(self.plan._pipe)
            self.plan._dispatch_par = self._record

    def _record(self, ev, F, M, ts_base, seq_base, lanes=None):
        self.packed = {k: np.shape(v) for k, v in ev.items()}
        return {"L": lanes}

    def flush(self, keys):
        keys = list(keys)
        for k in keys:
            if k not in self.sym:
                self.sym[k] = self.rt.strings.encode(f"K{k}")
        n = len(keys)
        self.rt.input_handler("S").send_batch(
            {"sym": np.array([self.sym[k] for k in keys], np.int32),
             "price": np.full(n, self.price)},
            self.t + np.arange(n, dtype=np.int64) // 64)
        self.t += 400
        self.price += 0.25
        self.rt.flush()
        return self.plan.grid.L

    def grids(self):
        return self.rt.explain()["queries"]["q"]["lane_fill"]["grids"]

    def close(self):
        self.mgr.shutdown()


@pytest.mark.parametrize("lanes", [1, 3, 8, 9, 250, 801, 1000, 1024, 1025,
                                   1088])
def test_a_plans_first_flush_packs_the_padded_count(lanes):
    rig = Lanes(device=False)
    try:
        assert rig.flush(range(lanes)) == PADDED[lanes]
        assert rig.packed["__nev__"] == (PADDED[lanes],)
        assert rig.packed["__flat.__ts__"] == (PADDED[lanes], 16)
    finally:
        rig.close()


@pytest.mark.parametrize("devices", [3, 4, 6, 8])
@pytest.mark.parametrize("lanes", [1, 9, 250, 801, 1025])
def test_a_mesh_rounds_the_padded_count_to_its_devices(lanes, devices):
    rig = Lanes(device=False, mesh_devices=devices)
    try:
        assert rig.flush(range(lanes)) == PADDED[lanes]   # what is held
        rows = rig.packed["__nev__"][0]
        assert rows % devices == 0
        assert PADDED[lanes] <= rows < PADDED[lanes] + devices
    finally:
        rig.close()


# -- (b) stickiness --------------------------------------------------------------

def test_drift_inside_a_granule_keeps_the_grid_and_a_step_adds_one():
    """41-48 lanes ride 48 rows whatever the count does; the 50th lane
    takes 56: one more `lane_fill.grids` entry, one more compilation; a
    flush under a quarter of that drops back to what it needs."""
    import jax.monitoring
    seen = []

    def on(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(on)
    rig = Lanes()
    try:
        assert rig.flush(range(43)) == 48
        settled = len(seen)
        assert settled > 0 and rig.grids() == {"48x16x16": 1}
        for n in (45, 41, 44, 46, 42, 48):
            assert rig.flush(range(n)) == 48
        assert len(seen) == settled, "a drifting lane count compiled"
        assert rig.grids() == {"48x16x16": 7}
        assert rig.flush(range(50)) == 56
        assert len(seen) == settled + 1
        assert rig.flush(range(47)) == 56 and rig.flush(range(14)) == 56
        assert rig.grids() == {"48x16x16": 7, "56x16x16": 3}
        assert len(seen) == settled + 1
        assert rig.flush(range(5)) == 8         # 56 > 4 x 8: dropped
        assert rig.grids() == {"48x16x16": 7, "56x16x16": 3, "8x16x16": 1}
        assert len(seen) == settled + 2
        # every event found the lane's earlier ones, replayed or held
        assert len(rig.rows_out) > 300
    finally:
        rig.close()


def test_a_failed_dispatch_rolls_the_lane_axis_back_with_F():
    rig = Lanes(device=False)
    try:
        assert rig.flush(range(43)) == 48
        plan = rig.plan
        before = (plan.grid.F, plan.grid.L, plan._last_seq)
        assert before[:2] == (16, 48)
        tail = plan.grid.tail
        record = plan._dispatch_par

        def fails(*a, **k):
            # the pack has sized the flush by now: 130 lanes, 80 events on
            # the busiest
            assert (plan.grid.F, plan.grid.L) == (192, 144)
            raise RuntimeError("no device")
        plan._dispatch_par = fails
        n = 130 + 79
        keys = np.r_[np.arange(130), np.zeros(79, np.int64)]
        for k in keys.tolist():
            rig.sym.setdefault(k, rig.rt.strings.encode(f"K{k}"))
        from siddhi_tpu.core.batch import EventBatch
        plan.process("S", EventBatch(
            rig.rt.schemas["S"], rig.t + np.arange(n, dtype=np.int64),
            {"sym": np.array([rig.sym[k] for k in keys.tolist()], np.int32),
             "price": np.full(n, 101.0)}, n))
        with pytest.raises(RuntimeError, match="no device"):
            plan.finalize()
        assert (plan.grid.F, plan.grid.L, plan._last_seq) == before
        assert plan.grid.tail is tail and len(plan._buffered) == 1
        # the re-run of the same flush sizes it as the first try did
        plan._dispatch_par = record
        plan.finalize()
        assert (plan.grid.F, plan.grid.L) == (192, 144)
        assert rig.packed["__flat.__ts__"] == (144, 192)
    finally:
        rig.close()


# -- (c) padding is only padding ---------------------------------------------------

def _force_pow2(monkeypatch):
    """The lane axis as it stood: the next power of two, whatever is held.
    (`_fused_cut`'s rows, which pass no floor, keep the rule.)"""
    rule = lane_grid._sticky_sixteenth
    monkeypatch.setattr(
        lane_grid, "_sticky_sixteenth",
        lambda n, held, lo=1: pow2_at_least(n, lo=8) if lo == 8
        else rule(n, held, lo))


def _in_order(head, q, tape):
    """Every delivered row in delivery order, as (ts, key, values), and the
    plan's EXPLAIN entry."""
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(
            head + lc.STREAM + "partition with (sym of S)\nbegin\n  "
            "@info(name='q') " + q + " insert into Out;\nend;\n")
    rows = []
    rt.add_callback("Out", lambda evs: rows.extend(
        (e.timestamp, *e.data) for e in evs))
    rt.start()
    nk = 1 + max(int(b["key"].max()) for b in tape)
    sym = np.array([rt.strings.encode(f"K{k}") for k in range(nk)], np.int32)
    for b in tape:
        rt.input_handler("S").send_batch(
            {"sym": sym[b["key"]], "price": b["price"],
             "volume": b["volume"]}, b["ts"])
        rt.flush()
    ent = rt.explain()["queries"].get("q")
    mgr.shutdown()
    return rows, ent


CUT_Q = ("from every e1=S[price > 100] -> e2=S[price > e1.price] "
         "-> e3=S[price > e2.price] within 1 sec select e1.sym as sym, "
         "e1.price as a, e2.price as b, e3.price as c")


@pytest.fixture(scope="module")
def cut_run():
    """Hot lanes past the cut (lowered to 128 events a row) among 40: a
    few rows over 40 a flush, 48 rows of 128 under the sixteenth rule
    where the power of two has 64."""
    tape = lc.zipf_tape(21, keys=40, n=1500, flushes=4)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(lane_grid, "LANE_CUT", 128)
        dev, ent = _in_order("@app:partitionCapacity(64)\n", CUT_Q, tape)
        host, _e = _in_order(lc.HOST, CUT_Q, tape)
        _force_pow2(mp)
        pow2, ent2 = _in_order("@app:partitionCapacity(64)\n", CUT_Q, tape)
    return dev, ent, host, pow2, ent2


def _by_key(rows):
    out = {}
    for r in rows:
        out.setdefault(r[1], []).append(r)
    return out


@pytest.mark.parametrize("against", ["the_power_of_two", "the_interpreter"])
def test_a_cut_flush_delivers_the_same_rows_in_order(cut_run, against):
    dev, ent, host, pow2, ent2 = cut_run
    assert len(dev) > 1500
    assert ent["lane_cut"] == ent2["lane_cut"]
    assert ent["lane_cut"]["flushes_cut"] == 4
    fill, fill2 = ent["lane_fill"], ent2["lane_fill"]
    rows = [int(g.split("x")[0]) for g in fill["grids"]]
    assert all(r & (r - 1) for r in rows), fill["grids"]    # no power of two
    assert {g.split("x", 1)[1] for g in fill["grids"]} == {"128x128"}
    assert list(fill2["grids"]) == ["64x128x128"]
    assert fill["total"]["lanes_active"] == fill2["total"]["lanes_active"]
    assert fill["total"]["cells_total"] < fill2["total"]["cells_total"]
    if against == "the_power_of_two":
        assert dev == pow2          # the whole delivery, row for row
    else:
        dev, host = _by_key(dev), _by_key(host)
        assert sorted(dev) == sorted(host)
        for k in host:
            assert dev[k] == host[k], k


@pytest.fixture(scope="module")
def short_run():
    tape = msl.make_tape(11)
    dev = msl.run(msl.DEVICE, tape)
    with pytest.MonkeyPatch.context() as mp:
        _force_pow2(mp)
        pow2 = msl.run(msl.DEVICE, tape)
    return tape, dev, pow2


@pytest.mark.parametrize("against", ["the_power_of_two", "the_interpreter"])
def test_many_short_lanes_with_held_tails_deliver_the_same_rows(short_run,
                                                                against):
    tape, (dev, entries, *_), (pow2, entries2, *_) = short_run
    fill, fill2 = entries[-1]["lane_fill"], entries2[-1]["lane_fill"]
    assert fill["last"]["lanes_held"] > 1000
    assert list(fill2["grids"]) == ["2048x64x64"]
    assert any(not g.startswith("2048x") for g in fill["grids"])
    other = pow2 if against == "the_power_of_two" \
        else msl.run(msl.HOST, tape)[0]
    assert sum(map(len, dev.values())) > 5000
    assert list(dev) == list(other) if against == "the_power_of_two" \
        else sorted(dev) == sorted(other)
    for k in other:
        assert dev[k] == other[k], k


# -- the pattern200k rehearsal ---------------------------------------------------

def _rehearse(cell, seed, trace):
    """(standard output, result line) of the cell's CPU rehearsal, run as
    tests/benchmark/test_rehearsal.py runs it."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "benchmark"))
    from test_rehearsal import last_line, run_cell
    r = run_cell(["--workload", cell, "--seed", str(seed), "--seconds",
                  "1.5", "--trace", str(trace), "--rehearse-cpu"])
    return r.stdout, last_line(r)


@pytest.mark.parametrize("trace,seed", [(0, 2 ** 31 + 49), (1, 2 ** 31 + 307)])
def test_the_pattern200k_rehearsal_is_steady_on_one_grid(trace, seed):
    """tests/benchmark/test_pattern200k_cell.py's rehearsal case, whole,
    with the lane grid as it is since PR 46 (that file pins 1024x64x64 and
    is the benchmark's to edit: tests/conftest.py STALE_PINS): 2,000 keys
    in 1024-event batches are ~800 active lanes a flush (s.d. 10.5), 832
    rows.  On these two seeds no flush of the first 2,500 holds over 832
    lanes nor the first under 769 (reckoned from the tape), so the grid is
    one however many flushes the machine fits into the window."""
    from benchmark import manifest
    stdout, out = _rehearse("pattern200k.sat", seed, trace)
    assert out["correct"] is True, out["compared"]
    assert all(v == {"value": 0, "limit": 0}
               for v in out["compared"].values())
    assert "compiles_in_window 0 " in stdout
    assert "('device', 'pattern', 'scan')" in stdout
    counts = out["counts"]
    assert counts["keys_compared"] == 2000
    assert counts["rows_delivered"] == counts["rows_owed"] \
        == counts["rows_delivered_all_keys"] > 0
    assert counts["events_sharing_key_and_ms"] > 0          # ties occurred
    fill = counts["lane_fill"]
    flushes = out["attempted"] + 4
    assert fill["flushes"] == flushes
    assert fill["grids"] == {"832x64x64": flushes}          # one geometry
    assert fill["last"]["lanes_padded"] == 832 \
        >= fill["last"]["lanes_active"] > 768
    assert counts["first_hit"]["lanes"] == 832
    assert fill["total"]["lanes_held"] > 0 < fill["total"]["events_replayed"]
    assert fill["total"]["events_new"] == flushes * 1024
    assert fill["total"]["cells_filled"] == fill["total"]["events_new"] \
        + fill["total"]["events_replayed"]
    assert fill["total"]["cells_total"] == flushes * 832 * 64
    assert fill["total"]["rows_delivered"] == counts["rows_delivered"]
    if trace:       # every listed metric but the two device shares
        assert out["metrics_found"] == sorted(
            m["name"] for m in manifest.Manifest().metrics_of(
                "pattern200k.sat", "per_layer") if "share" not in m["name"])
    else:
        assert out["metrics_found"] == ["events_per_s", "setup_s"]
