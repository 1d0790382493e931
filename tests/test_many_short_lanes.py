"""Many short lanes (the `pattern200k` regime at a CPU size): thousands of
partition keys, each with a handful of events in its `within`, so that a
flush of the partitioned lane grid is mostly REPLAYED TAIL (several events
replayed for every new one), a third of the lanes are quiet in any flush and
their tails are held apart until they come back, timestamps tie, and the
STRING partition key is delivered with every match.  The device path is held
to the host interpreter (`@app:devicePatterns('never')`) and to the plain
reference `benchmark/reference/pattern_chain.matches`, row for row in
per-key order; `lane_fill` (EXPLAIN / device_metrics) to a flush worked by
hand; span `lane_tail` to its place inside `host_build`.
"""
import os
import sys
import warnings
from collections import Counter

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference.pattern_chain import matches     # noqa: E402
from siddhi_tpu import SiddhiManager                      # noqa: E402
from siddhi_tpu.core import lane_grid, telemetry          # noqa: E402
from siddhi_tpu.core.pattern_plan import DevicePatternPlan  # noqa: E402

T0 = 1_700_000_000_000
WITHIN_MS = 2000
HOST = "@app:devicePatterns('never')\n"
DEVICE = "@app:partitionCapacity(4096)\n@app:deviceSlots(32)\n"
APP = """
define stream S (sym string, price double, volume int);
define stream Out (sym string, a double, b double, c double);
partition with (sym of S)
begin
  @info(name='q')
  from every e1=S[price > 100] -> e2=S[price > e1.price]
    -> e3=S[price > e2.price] within 2 sec
  select e1.sym as sym, e1.price as a, e2.price as b, e3.price as c
  insert into Out;
end;
"""

KEYS, N, FLUSHES, PER_MS = 3000, 4096, 9, 8     # a flush: 512 ms of events
SETTLE_KEY, SETTLE_EVENTS = 0, 40
# keys by the flushes they may appear in (100 keys a group; every other key
# in any flush): quiet for one flush, for three, for longer than `within`
# (five flushes, 2.56 s), and first seen mid-run
GROUPS = {"held_one": (range(100, 200), {0, 2, 4, 6, 8}),
          "held_three": (range(200, 300), {0, 4, 8}),
          "held_past_within": (range(300, 400), {0, 1, 7, 8}),
          "first_seen_mid_run": (range(400, 500), {3, 4, 5, 6, 7, 8})}


def make_tape(seed):
    """FLUSHES batches of N events over KEYS keys, PER_MS events sharing
    each millisecond; the grouped keys are drawn five times as often as
    the others in the flushes they appear in, key SETTLE_KEY holds
    SETTLE_EVENTS events of flush 0."""
    rng = np.random.default_rng(seed)
    out = []
    for f in range(FLUSHES):
        w = np.ones(KEYS)
        for keys, flushes in GROUPS.values():
            w[list(keys)] = 5.0 if f in flushes else 0.0
        key = rng.choice(KEYS, N, p=w / w.sum()).astype(np.int32)
        if f == 0:
            key[rng.choice(N, SETTLE_EVENTS, replace=False)] = SETTLE_KEY
        j = f * N + np.arange(N, dtype=np.int64)
        out.append({"key": key, "ts": T0 + j // PER_MS,
                    "price": 90 + 0.25 * rng.integers(0, 161, N).astype(
                        np.float64),
                    "volume": rng.integers(1, 1000, N).astype(np.int32)})
    return out


def run(head, tape, app=APP, stats=False):
    """Rows by key in delivery order {key name: [(ts, a, b, c), ...]}, the
    plan's EXPLAIN entry after every flush, the plan, the runtime's
    statistics and traces."""
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(head + app)
    rows = {}

    def on_batch(b):
        for t, s, *p in zip(b.timestamps.tolist(), b.columns["sym"].tolist(),
                            *(b.columns[c].tolist() for c in "abc")):
            rows.setdefault(rt.strings.decode(s), []).append((t, *p))
    rt.add_batch_callback("Out", on_batch)
    if stats:
        rt.enable_stats()
    rt.start()
    nk = 1 + max(int(b["key"].max()) for b in tape)
    sym = np.array([rt.strings.encode(f"K{k}") for k in range(nk)], np.int32)
    entries = []
    for b in tape:
        rt.input_handler("S").send_batch(
            {"sym": sym[b["key"]], "price": b["price"],
             "volume": b["volume"]}, b["ts"])
        rt.flush()
        entries.append(rt.explain()["queries"].get("q"))
    plan = next((p for p in rt._plans if isinstance(p, DevicePatternPlan)),
                None)
    st = rt.statistics() if stats else None
    trees = list(rt.tracing.traces().values()) if rt.tracing else []
    metrics = plan.device_metrics() if plan is not None else None
    mgr.shutdown()
    return rows, entries, plan, st, trees, metrics


def owed(tape):
    """{key name: rows} that `pattern_chain.matches` owes, in e3 order."""
    key = np.concatenate([b["key"] for b in tape])
    want = matches(key, np.concatenate([b["price"] for b in tape]),
                   np.concatenate([b["ts"] for b in tape]),
                   {"threshold": 100.0, "within_ms": WITHIN_MS})
    out = {}
    for i in np.argsort(want["e3"], kind="stable").tolist():
        out.setdefault(f"K{key[want['e3'][i]]}", []).append(
            (int(want["ts"][i]), float(want["p1"][i]), float(want["p2"][i]),
             float(want["p3"][i])))
    return out


@pytest.fixture(scope="module")
def ran():
    tape = make_tape(42)
    return tape, run(DEVICE, tape), owed(tape)


def _by_ms(rows):
    """Rows of one key with those completed in one millisecond as a sorted
    group: across milliseconds the delivery order is owed, inside one the
    reference orders by e3 and two e3 events may share it."""
    out = []
    for r in rows:
        if out and out[-1][0][0] == r[0]:
            out[-1].append(r)
        else:
            out.append([r])
    return [sorted(g) for g in out]


def test_the_tape_is_the_regime(ran):
    tape, _dev, want = ran
    key, ts = tape[1]["key"], tape[1]["ts"]
    assert np.all(np.diff(ts) >= 0) and np.count_nonzero(np.diff(ts) == 0) \
        == N - N // PER_MS
    assert len(np.unique(key)) > 1000         # many lanes, ~1.5 events each
    assert np.count_nonzero(tape[0]["key"] == SETTLE_KEY) >= SETTLE_EVENTS
    for name, (keys, flushes) in GROUPS.items():
        for f, b in enumerate(tape):
            seen = np.isin(b["key"], list(keys)).any()
            assert seen == (f in flushes), (name, f)
    # matches complete on events that come back after a held tail
    assert sum(map(len, want.values())) > 5000


def test_device_rows_equal_the_interpreters_in_per_key_order(ran):
    tape, (dev, _e, plan, *_), _want = ran
    host = run(HOST, tape)[0]
    assert plan.family == "scan" and plan._partitioned
    assert sorted(dev) == sorted(host)
    for k in host:
        assert dev[k] == host[k], k


def test_device_rows_equal_the_plain_reference_and_carry_their_key(ran):
    _tape, (dev, *_), want = ran
    assert sorted(dev) == sorted(want)
    for k in want:
        # the delivered STRING is the key of the row's three events: a row
        # under another key's name would be missing here and extra there
        assert _by_ms(dev[k]) == _by_ms(want[k]), k


@pytest.mark.parametrize("group", sorted(GROUPS))
def test_a_held_tail_comes_back(ran, group):
    """A row completed on the FIRST or SECOND event a key sends after its
    quiet spell has its e1 before the spell: it exists only if the tail was
    held and replayed.  A key quiet for longer than `within`, or never seen
    before, owes no such row and gets none."""
    tape, (dev, *_), want = ran
    keys, _flushes = GROUPS[group]
    back = {"held_one": 2, "held_three": 4, "held_past_within": 7,
            "first_seen_mid_run": 3}[group]
    b = tape[back]
    across = later = 0
    for k in keys:
        rows = dev.get(f"K{k}", [])
        assert _by_ms(rows) == _by_ms(want.get(f"K{k}", [])), k
        mine = np.sort(b["ts"][b["key"] == k])
        for r in rows:
            if mine.size and mine[0] <= r[0] <= b["ts"][-1]:
                arrived = np.searchsorted(mine, r[0], side="right")
                across += arrived <= 2
                later += arrived > 2
    assert later > 0
    if group in ("held_one", "held_three"):
        assert across > 10, across
    else:
        assert across == 0, across


def _settled(grids):
    """The flush from which on `grids` (one a flush) no longer change."""
    return next(k for k in range(len(grids)) if len(set(grids[k:])) == 1)


def test_the_grid_settles_by_flush_1_and_stays(ran):
    """Key 0's 40 events put F in its 64 bucket in the first flush.  The
    lane axis steps in sixteenths of its power of two (128 rows here):
    flush 0's ~1,900 active lanes settle it unless a later flush holds
    over a step more, and the tape's fullest flushes (~2,000-2,030 lanes)
    come early.  From the settling flush on every flush has the same
    (rows, F, M): nothing compiles."""
    _tape, (_dev, entries, plan, *_), _w = ran
    fills = [e["lane_fill"] for e in entries]
    assert [f["flushes"] for f in fills] == list(range(1, FLUSHES + 1))
    grids = [(f["last"]["lanes_padded"], f["last"]["F"], f["last"]["M"])
             for f in fills]
    k = _settled(grids)
    assert {g[1:] for g in grids} == {(64, 64)} and k <= 1, grids
    # the lane axis only grew, by whole steps, to under 9/8 of the fullest
    rows = [g[0] for g in grids]
    assert rows == sorted(rows) and all(r % 128 == 0 for r in rows), rows
    fullest = max(f["last"]["lanes_active"] for f in fills)
    assert fullest <= rows[-1] < fullest + 128
    want = Counter("%dx%dx%d" % g for g in grids)
    assert fills[-1]["grids"] == want and len(want) <= 2, want
    assert want["%dx%dx%d" % grids[-1]] == FLUSHES - k
    kern = plan._parallel_kernel()
    # beside the two-lane block every plan compiles when it is built
    assert [k for k in kern._block_cache if k[0][0] > 2] == [
        ((r, 64), 64) for r in dict.fromkeys(rows)]


def test_compiles_stay_flat_after_the_settling_flush():
    import jax.monitoring
    seen = []

    def on(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)
    jax.monitoring.register_event_duration_secs_listener(on)
    tape = make_tape(7)
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(DEVICE + APP)
    rt.start()
    sym = np.array([rt.strings.encode(f"K{k}") for k in range(KEYS)],
                   np.int32)
    counts, grids = [], []
    for b in tape[:5]:
        rt.input_handler("S").send_batch(
            {"sym": sym[b["key"]], "price": b["price"],
             "volume": b["volume"]}, b["ts"])
        rt.flush()
        counts.append(len(seen))
        last = rt.explain()["queries"]["q"]["lane_fill"]["last"]
        grids.append((last["lanes_padded"], last["F"], last["M"]))
    mgr.shutdown()
    k = _settled(grids)
    assert k <= 1, grids
    # at most one compilation for the one step the lane axis may take,
    # none from the settling flush on
    assert counts[0] > 0 and counts[k] - counts[0] <= k, counts
    assert len(set(counts[k:])) == 1, counts


def test_lane_fill_counts_what_the_flushes_held(ran):
    tape, (dev, entries, plan, _st, _tr, metrics), _w = ran
    fill = entries[-1]["lane_fill"]
    assert list(fill) == ["flushes", "total", "last", "grids"]
    assert list(fill["total"]) == list(lane_grid.LANE_FILL)
    assert list(fill["last"]) == list(lane_grid.LANE_FILL) + ["F", "M"]
    assert metrics["lane_fill"] == fill == plan.lane_fill
    total = fill["total"]
    assert total["events_new"] == FLUSHES * N
    assert total["rows_delivered"] == sum(map(len, dev.values()))
    assert total["lanes_active"] == sum(
        len(np.unique(b["key"])) for b in tape)
    assert total["cells_filled"] == total["events_new"] \
        + total["events_replayed"]
    assert total["cells_total"] == total["lanes_padded"] * 64
    # the regime: more replayed than new, a result far wider than its rows
    assert total["events_replayed"] > 2 * total["events_new"]
    assert total["result_cells"] == total["lanes_padded"] * 8 * 64
    assert total["result_cells"] > 100 * total["rows_delivered"]
    # quiet lanes whose tails were held: keys seen before and absent now
    seen, held = set(), 0
    for f, b in enumerate(tape):
        now = set(b["key"].tolist())
        if f == FLUSHES - 1:
            held = len(seen - now)
        seen |= now
    assert fill["last"]["lanes_held"] == held > 1000
    # every flush that held rows was decoded through the one index
    with_rows = sum(e["lane_fill"]["total"]["rows_delivered"] > (
        p["lane_fill"]["total"]["rows_delivered"] if p else 0)
        for p, e in zip([None] + entries, entries))
    assert 0 < with_rows <= FLUSHES
    assert entries[-1]["result_decode"] == metrics["result_decode"] \
        == plan.result_decode == {"indexed": with_rows}


def _small(prices_by_flush):
    """Flushes of (key name, price) events 10 ms apart, as a tape."""
    names = sorted({k for f in prices_by_flush for k, _p in f})
    out, j = [], 0
    for f in prices_by_flush:
        n = len(f)
        out.append({"key": np.array([names.index(k) for k, _p in f], np.int32),
                    "price": np.array([p for _k, p in f], np.float64),
                    "volume": np.ones(n, np.int32),
                    "ts": T0 + 10 * (j + np.arange(n, dtype=np.int64))})
        j += n
    return out


def test_lane_fill_on_a_flush_worked_by_hand():
    """Flush 0: a, a, b, c.  Flush 1: a, d.  In flush 1 lanes a and d are
    active (2, padded to the grid's floor of 8 rows), b and c are quiet and
    their one-event tails are held (2), a's two events are replayed in
    front of its new one, d has no tail: 2 new + 2 replayed = 4 cells of
    8 x 16, a result of 8 rows x 8 words (header, ts, seq, head seq, sym,
    a, b, c) x 16, and a's chain 101 -> 102 -> 103 completes: 1 row."""
    tape = _small([[("a", 101.0), ("a", 102.0), ("b", 99.0), ("c", 99.0)],
                   [("a", 103.0), ("d", 99.0)]])
    dev, entries, plan, *_ = run("@app:partitionCapacity(8)\n", tape)
    assert dev == {"K0": [(T0 + 40, 101.0, 102.0, 103.0)]}
    first, fill = entries[0]["lane_fill"], entries[1]["lane_fill"]
    assert first["last"] == dict(
        lanes_active=3, lanes_padded=8, lanes_held=0, events_new=4,
        events_replayed=0, cells_filled=4, cells_total=128,
        result_cells=1024, rows_delivered=0, F=16, M=16)
    assert first["total"] == {k: first["last"][k]
                              for k in lane_grid.LANE_FILL}
    assert fill["last"] == dict(
        lanes_active=2, lanes_padded=8, lanes_held=2, events_new=2,
        events_replayed=2, cells_filled=4, cells_total=128,
        result_cells=1024, rows_delivered=1, F=16, M=16)
    assert fill["total"] == {k: first["last"][k] + fill["last"][k]
                             for k in lane_grid.LANE_FILL}
    assert fill["flushes"] == 2 and fill["grids"] == {"8x16x16": 2}
    assert list(entries[1])[:10] == [
        "path", "plan", "kind", "family", "expiry_queries", "first_hit",
        "lane_pack_order", "lane_cut", "lane_fill", "result_decode"]
    # flush 0 held no row: nothing decoded, no record yet
    assert "result_decode" not in entries[0]
    assert entries[1]["result_decode"] == {"indexed": 1}


def test_lane_fill_is_a_partitioned_scan_plans_alone():
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:devicePatterns('always')\n"
        "define stream S (sym string, price double, volume int);\n"
        "@info(name='q') from every e1=S[price > 100] -> "
        "e2=S[price > e1.price] within 1 sec "
        "select e1.price as a, e2.price as b insert into Out;")
    rt.start()
    rt.input_handler("S").send_batch(
        {"sym": np.array(["x", "x"]), "price": np.array([101.0, 102.0]),
         "volume": np.ones(2, np.int32)}, T0 + np.arange(2, dtype=np.int64))
    rt.flush()
    ent = rt.explain()["queries"]["q"]
    plan = next(p for p in rt._plans if isinstance(p, DevicePatternPlan))
    mgr.shutdown()
    assert "lane_fill" not in ent and plan.lane_fill is None
    assert "lane_fill" not in plan.device_metrics()
    # its one flat block held a row: decoded as the one-lane case
    assert ent["result_decode"] == {"indexed": 1}


# -- span lane_tail -------------------------------------------------------------

def test_span_lane_tail_is_in_the_taxonomy_and_times_the_replay():
    assert "lane_tail" in telemetry.SPANS
    tape = make_tape(3)[:4]
    _d, _e, _p, st, _t, _m = run(DEVICE, tape, stats=True)
    stages = st["stages"]
    # the first flush has no tail to split, only one to keep; every later
    # flush opens the span twice
    assert stages["lane_tail"]["batches"] == 1 + 2 * (len(tape) - 1)
    assert 0 < stages["lane_tail"]["seconds"] <= stages["host_build"][
        "seconds"]
    # statistics off: the span is the shared no-op and nothing is recorded
    _d, _e, plan, *_ = run(DEVICE, tape[:2])
    assert plan.rt.span("lane_tail", plan="q") is telemetry.NOOP_SPAN


def test_span_lane_tail_nests_in_host_build():
    tape = make_tape(5)[:3]
    *_, trees, _m = run("@app:trace('all')\n" + DEVICE, tape)
    found = 0
    for spans in trees:
        by_id = {s["span"]: s for s in spans}
        for sp in spans:
            if sp["name"] != "lane_tail":
                continue
            found += 1
            up = sp
            while up["name"] != "host_build":     # a parent edge, or the
                up = by_id[up["parent"]]          # sibling closed before it
            assert sp["t0_s"] >= up["t0_s"] - 2e-6
            assert sp["t0_s"] + sp["dur_s"] <= up["t0_s"] + up["dur_s"] + 2e-6
    assert found == 1 + 2 * (len(tape) - 1)
