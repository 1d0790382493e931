"""The cut of hot lanes (lane_grid.py `LANE_CUT`, `_cut_rows`): a flush
whose longest lane holds more than the cut length lays that lane out as
several rows of the (Lpad, F) grid, each `[the lane's last `within` of
events | new events]` with the sequence before its new events as the row's
dedup bound: a flush boundary the key never saw.  Held here against the host
interpreter (`@app:devicePatterns('never')`, siddhi_tpu/interp) and, for the
benchmark's chain, against the plain reference
`benchmark/reference/pattern_chain.matches`.

Which test forces the cut how:
  * `test_cut_by_shape_*` leave `LANE_CUT` as it is and send a lane longer
    than it (as tests/test_first_hit.py forces DENSE_MAX_F): the shapes the
    chip compiles, F = LANE_CUT, on one device and on a mesh of four;
  * every other test lowers the module constant by `monkeypatch` (the cut
    and F read it at each flush), so that the many-case sweeps stay short
    lanes on the CPU.
A flush within the cut takes the lines that were there before, byte for byte:
tests/test_lane_pack.py holds that, unedited; here only its counter.
"""
import os
import sys
import warnings

import numpy as np
import pytest

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference.pattern_chain import matches     # noqa: E402
from siddhi_tpu import SiddhiManager                      # noqa: E402
from siddhi_tpu.core import lane_grid, nfa_parallel       # noqa: E402
from siddhi_tpu.core.lane_grid import _cut_rows          # noqa: E402
from siddhi_tpu.core.pattern_plan import DevicePatternPlan  # noqa: E402

T0 = 1_700_000_000_000
STREAM = "define stream S (sym string, price double, volume int);\n"
HOST = "@app:devicePatterns('never')\n"
DEVICE = "@app:partitionCapacity(8)\n"

# every pattern shape the partitioned lane path takes (an `every` head and a
# `within` on each position; a non-`every` head is refused for partitioned
# lanes, `classify_parallel`)
SHAPES = {
    "chain3": "from every e1=S[price > 100] -> e2=S[price > e1.price] "
              "-> e3=S[price > e2.price] within 1 sec "
              "select e1.price as a, e2.price as b, e3.price as c",
    "count": "from every e1=S[price > 118] -> e2=S[price > 112]<2:4> "
             "-> e3=S[price < 96] within 1 sec select e1.price as a, "
             "e2[0].price as b, e2[last].price as c, e3.price as d",
    "logical": "from every e1=S[price > 120] -> e2=S[price < 100] and "
               "e3=S[price > 125] within 1 sec "
               "select e1.price as a, e2.price as b, e3.price as c",
    "sequence": "from every e1=S[price > 115], e2=S[price > e1.price] "
                "within 1 sec select e1.price as a, e2.price as b",
}


def _app(q):
    return (STREAM + "partition with (sym of S)\nbegin\n  @info(name='q') "
            + q + " insert into Out;\nend;\n")


def zipf_tape(seed, keys, n, flushes, dt=7, regress=False):
    """`flushes` batches of `n` events over `keys` symbols of zipfian 0.99
    popularity, `dt` ms apart; `regress`: a third of the timestamps fall
    back by up to 90 ms, inside their lane too."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, keys + 1) ** 0.99
    out = []
    for f in range(flushes):
        ts = T0 + (f * n + np.arange(n, dtype=np.int64)) * dt
        if regress:
            ts = ts - rng.integers(1, 90, n) * (rng.random(n) < 1 / 3)
        out.append({"key": rng.choice(keys, n, p=p / p.sum()),
                    "price": 90 + 0.25 * rng.integers(0, 161, n).astype(
                        np.float64),
                    "volume": rng.integers(1, 1000, n).astype(np.int32),
                    "ts": ts})
    return out


def run(head, q, tape, stats=False):
    """(sorted rows, the plan's EXPLAIN entry, the plan) after one
    send_batch + flush a batch of `tape`; `stats`: the engine's stage
    statistics in the plan's place."""
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(head + _app(q))
    rows = []
    rt.add_callback("Out", lambda evs: rows.extend(
        (e.timestamp, tuple(None if x is None else round(float(x), 3)
                            for x in e.data)) for e in evs))
    if stats:
        rt.enable_stats()
    rt.start()
    nk = 1 + max(int(b["key"].max()) for b in tape)
    sym = np.array([rt.strings.encode(f"K{k}") for k in range(nk)], np.int32)
    for b in tape:
        rt.input_handler("S").send_batch(
            {"sym": sym[b["key"]], "price": b["price"],
             "volume": b["volume"]}, b["ts"])
        rt.flush()
    plan = next((p for p in rt._plans if isinstance(p, DevicePatternPlan)),
                None)
    ent = rt.explain()["queries"].get("q")
    if stats:
        plan = rt.statistics()["stages"]
    mgr.shutdown()
    return sorted(rows), ent, plan


def reference_rows(tape, within_ms):
    """What `pattern_chain.matches` owes for the whole tape (chain3)."""
    want = matches(np.concatenate([b["key"] for b in tape]),
                   np.concatenate([b["price"] for b in tape]),
                   np.concatenate([b["ts"] for b in tape]),
                   {"threshold": 100.0, "within_ms": within_ms})
    return sorted((int(t), (round(a, 3), round(b, 3), round(c, 3)))
                  for t, a, b, c in zip(want["ts"], want["p1"], want["p2"],
                                        want["p3"]))


@pytest.fixture
def cut128(monkeypatch):
    """The module constant lowered: rows of 128 events."""
    monkeypatch.setattr(lane_grid, "LANE_CUT", 128)
    return 128


# -- forced by SHAPE: the constant as it stands ---------------------------------

def _assert_cut(ent, flushes, F):
    cut, hit = ent["lane_cut"], ent["first_hit"]
    assert cut["cut_length"] == F and cut["flushes_cut"] == flushes, cut
    assert cut["flushes_uncuttable"] == 0 and cut["rows_added"] >= flushes
    assert cut["lanes_cut"] >= flushes and cut["events_replayed"] > 0
    assert hit["F"] == F and hit["tree"] == 0 and hit["dense"] > 0, hit


def test_cut_by_shape_equals_interpreter_and_reference():
    assert lane_grid.LANE_CUT <= nfa_parallel.DENSE_MAX_F
    # 4 keys, 48% of 6000 events a flush on the first: past LANE_CUT
    tape = zipf_tape(7, keys=4, n=6000, flushes=3)
    assert max(np.bincount(b["key"]).max() for b in tape) \
        > lane_grid.LANE_CUT
    host, _e, _p = run(HOST, SHAPES["chain3"], tape)
    dev, ent, plan = run(DEVICE, SHAPES["chain3"], tape)
    assert plan.family == "scan" and plan._partitioned
    assert dev == host and len(dev) > 5000
    assert dev == reference_rows(tape, 1000)
    _assert_cut(ent, 3, lane_grid.LANE_CUT)
    assert plan.device_metrics()["lane_cut"] == ent["lane_cut"]
    assert plan.grid.F == lane_grid.LANE_CUT


def test_cut_by_shape_on_four_virtual_devices(monkeypatch):
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
    tape = zipf_tape(8, keys=5, n=6000, flushes=2)
    host, _e, _p = run(HOST, SHAPES["chain3"], tape)
    dev, ent, plan = run("@app:deviceMesh('always')\n" + DEVICE,
                         SHAPES["chain3"], tape)
    assert plan.mesh is not None and plan.mesh.devices.size == 4
    assert dev == host == reference_rows(tape, 1000)
    _assert_cut(ent, 2, lane_grid.LANE_CUT)
    assert ent["first_hit"]["lanes"] % 4 == 0


# -- the constant lowered: the sweeps ----------------------------------------------

@pytest.mark.parametrize("regress", [False, True],
                         ids=["in_order", "a_third_regressed"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_every_lane_shape_cut_equals_interpreter(cut128, shape, regress):
    tape = zipf_tape(21, keys=6, n=600, flushes=4, regress=regress)
    host, _e, _p = run(HOST, SHAPES[shape], tape)
    dev, ent, plan = run(DEVICE, SHAPES[shape], tape)
    assert plan.family in ("scan", "dfa") and plan._partitioned
    assert dev == host and len(dev) > 20, (len(dev), len(host))
    if shape == "chain3" and not regress:
        assert dev == reference_rows(tape, 1000)
    cut = ent["lane_cut"]
    assert cut["flushes_cut"] > 0 and cut["rows_added"] > 0, cut
    assert cut["flushes_cut"] + cut["flushes_uncuttable"] <= 4
    assert ent["first_hit"]["F"] == 128


@pytest.mark.parametrize("lead", range(7))
def test_a_cut_on_every_position_of_a_pending_chain(monkeypatch, lead):
    """One lane cycles through e1, filler, e2, filler, e3, filler, filler
    (101, 105, 110 open and extend rising chains; 90 does neither); `lead`
    fillers in front move the row boundaries over every phase of the cycle,
    so some boundary falls before, on and after each position of a chain
    that is pending across it."""
    monkeypatch.setattr(lane_grid, "LANE_CUT", 32)
    cycle = np.array([101.0, 90.0, 105.0, 90.0, 110.0, 90.0, 90.0])
    n = 150
    hot = np.r_[np.full(lead, 90.0), np.tile(cycle, n // 7 + 1)][:n]
    q = SHAPES["chain3"].replace("within 1 sec", "within 60 milliseconds")
    tape = []
    for f in range(2):
        key = np.zeros(n + 6, np.int64)
        key[n:] = [1, 2, 1, 2, 1, 2]        # two quiet lanes beside it
        tape.append({"key": key, "price": np.r_[hot, [101, 101, 105, 105,
                                                     110, 110.0]],
                     "volume": np.ones(n + 6, np.int32),
                     "ts": T0 + (f * (n + 6) + np.arange(n + 6)) * 7})
    host, _e, _p = run(HOST, q, tape)
    dev, ent, plan = run(DEVICE, q, tape)
    assert dev == host == reference_rows(tape, 60) and len(dev) > 30
    cut = ent["lane_cut"]
    assert cut["flushes_cut"] == 2 and cut["lanes_cut"] == 2, cut
    assert cut["rows_added"] >= 8 and cut["flushes_uncuttable"] == 0
    assert plan.grid.F == 32    # the first row ends at phase (32 - lead) % 7


def test_a_key_whose_window_overfills_a_row_is_not_cut(monkeypatch):
    """`within 1 sec` at 7 ms an event is 143 events, 57 of them the top
    key's: a 64-event row would leave 7 cells for new events, under a
    quarter of it.  The flush keeps one row a lane (F from the longest),
    is counted, and is exact."""
    monkeypatch.setattr(lane_grid, "LANE_CUT", 64)
    tape = zipf_tape(3, keys=6, n=600, flushes=3)
    host, _e, _p = run(HOST, SHAPES["chain3"], tape)
    dev, ent, plan = run(DEVICE, SHAPES["chain3"], tape)
    assert dev == host == reference_rows(tape, 1000)
    cut = ent["lane_cut"]
    assert cut["flushes_uncuttable"] >= 2 and cut["cut_length"] == 64, cut
    assert cut["flushes_cut"] + cut["flushes_uncuttable"] == 3
    assert plan.grid.F > 64 or cut["flushes_cut"]


def test_cut_rows_geometry(monkeypatch):
    """`_cut_rows` alone: rows of one lane consecutive, in order, each at
    most the cut; each later row starts at the first event within W of the
    event before its new ones; rows tile the new events exactly once."""
    monkeypatch.setattr(lane_grid, "LANE_CUT", 100)
    counts = np.array([30, 260, 100, 101])
    run_start = np.cumsum(counts) - counts
    tail_n = np.array([5, 20, 0, 0])
    rng = np.random.default_rng(0)
    tsmono = np.concatenate([np.cumsum(rng.integers(1, 9, c))
                             for c in counts]).astype(np.int64)
    W = 60
    row_run, row_at, row_n, row_new = _cut_rows(counts, run_start, tail_n,
                                                tsmono, W)
    assert row_run.tolist() == sorted(row_run.tolist())
    assert (row_n <= 100).all() and (row_new >= row_at).all()
    for r in range(4):
        mine = np.flatnonzero(row_run == r)
        a, c = run_start[r], counts[r]
        assert (len(mine) == 1) == (c <= 100)
        assert row_at[mine[0]] == a and row_new[mine[0]] == a + tail_n[r]
        ends = row_at[mine] + row_n[mine]
        assert ends[-1] == a + c
        assert np.array_equal(row_new[mine][1:], ends[:-1])  # new events tile
        for j in mine[1:]:
            s = row_new[j]
            first = a + np.flatnonzero(
                tsmono[a:a + c] >= tsmono[s - 1] - W)[0]
            assert row_at[j] == first
    # a window that leaves under a quarter of a row: not cut
    dense = np.arange(counts.sum(), dtype=np.int64)
    assert _cut_rows(counts, run_start, tail_n, dense, 80) is None
    assert _cut_rows(counts, run_start, tail_n, dense, 70) is not None


def test_a_uniform_flush_is_not_cut_and_counts_nothing():
    tape = zipf_tape(5, keys=6, n=300, flushes=3)
    dev, ent, plan = run(DEVICE, SHAPES["chain3"], tape)
    assert ent["lane_cut"] == dict(
        flushes_cut=0, lanes_cut=0, rows_added=0, events_replayed=0,
        flushes_uncuttable=0, cut_length=lane_grid.LANE_CUT)
    assert list(ent)[:8] == ["path", "plan", "kind", "family",
                             "expiry_queries", "first_hit",
                             "lane_pack_order", "lane_cut"], list(ent)
    assert dev == reference_rows(tape, 1000)


def test_span_lane_cut_opens_only_on_a_cut_flush(cut128):
    def stages(n):
        return run(DEVICE, SHAPES["chain3"],
                   zipf_tape(21, keys=6, n=n, flushes=3), stats=True)[2]
    cut = stages(600)
    assert cut["lane_cut"]["batches"] == 3
    assert cut["lane_cut"]["seconds"] <= cut["host_build"]["seconds"]
    assert "lane_cut" not in stages(120)


def test_arm_done_rows_are_query_lanes_never_cut_rows():
    """`_materialize_par` ORs the block's per-row arm flags into
    `_arm_done[:nl]` by grid ROW.  That is right as it stands, also when
    only some partition lanes are active, because the two never meet:
    `_arm_done` exists for a non-`every` head only, partitioned lanes
    refuse a non-`every` head (per-key single-arm state), so the rows it
    indexes are the fused multi-query lanes, where row i IS query lane i in
    every flush and no row is ever a cut segment."""
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(DEVICE + _app(
            "from e1=S[price > 125] -> e2=S[price > e1.price] within 1 sec "
            "select e1.price as a, e2.price as b"))
    plans = [p for p in rt._plans if isinstance(p, DevicePatternPlan)]
    for p in plans:
        assert p.family == "seq" and p._arm_done is None
        assert "single-arm" in p.families["scan"]
        assert p.lane_cut is None
    mgr.shutdown()
