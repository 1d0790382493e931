"""Device window-aggregation plans: differential equality against the
sequential host interpreter on randomized streams (the device kernel's
claim is exact reference semantics — SURVEY §4 differential strategy)."""
import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import window_device as wd
from siddhi_tpu.core.window_device import DeviceWindowAggPlan


def cents(rng, n):
    """0.01-step prices: sums that ROUND, so the order of the arithmetic
    shows in the bits."""
    return np.round(rng.uniform(90.0, 130.0, n) * 100) / 100


# the file's lightest cases come first (the tier-1 run hands the files with
# the most cases out first, beside tests/test_spans.py's timing test)

@pytest.mark.parametrize("fill", [0, 3, 1024])
@pytest.mark.parametrize("k", [0, 5, 64])
@pytest.mark.parametrize("L", [1, 37, 1000, 1024])
def test_length_left_and_trailing_sum_against_search_and_gather(fill, k, L):
    """The three identities on the primitives, over a contiguous valid run
    [C - fill, C + k) of a 1024 + 64 sequence: at every position of the run
    and before it the arithmetic left IS the searched one, and on the run the
    shifted base and the integer count give the gathered forms' bits."""
    C, T = 1024, 64
    g = np.arange(C + T)
    valid = (g >= C - fill) & (g < C + k)
    v = np.where(valid, cents(np.random.default_rng([fill, k, L]), C + T),
                 0.0).astype(np.float32)
    vcnt = jnp.cumsum(jnp.asarray(valid).astype(jnp.int64))
    searched = np.asarray(jnp.searchsorted(
        vcnt, jnp.maximum(vcnt - L, 0), side="right"))
    left = np.asarray(wd._length_left(jnp.asarray(g), C - fill, L))
    if fill + k:        # no entry valid: no run, no row, and nothing kept
        assert np.array_equal(left[:C + k], searched[:C + k])
    pfx = wd._prefix_pairs(jnp.asarray(v))
    gathered = np.asarray(wd._range_sum(pfx, jnp.asarray(searched) - 1))
    shifted = np.asarray(wd._trailing_sum(pfx, L))
    assert np.array_equal(shifted[valid].view(np.uint32),
                          gathered[valid].view(np.uint32))
    counted = np.asarray(wd._range_sum(
        wd._prefix_pairs(jnp.asarray(valid).astype(jnp.float32)),
        jnp.asarray(searched) - 1))
    assert np.array_equal(np.clip(g - (C - fill) + 1, 0, L)[valid],
                          counted[valid])



# -- a group-by's ranks read off its own sort (PR 50) --------------------------

def _searched_ranks(cols, valid, left, C):
    """The forms PR 50 replaced, kept here as the plain reference: PR 49's
    `group_seg` (dense segment ids in the order of the keys' words, brought
    to arrival order by a scatter, invalid -> n) and `_seg_ranges` (a stable
    sort by segment, then `searchsorted` over seg * n + pos keys: each
    entry's own rank and the rank of its segment's first member at or after
    `left`), with `_seg_running_sum`'s way back to arrival order."""
    n = len(valid)
    words = []
    for c in cols:
        if c.dtype.kind == "f":
            c = c.astype(np.float64)
            c = np.where(c == 0.0, 0.0, c).view(np.int64)
        words += [np.asarray(w) for w in wd._words(jnp.asarray(c))]
    order = np.lexsort(words[::-1])
    diff = np.zeros(n, bool)
    for w in words:
        diff |= np.r_[True, w[order][1:] != w[order][:-1]]
    seg = np.zeros(n, np.int64)
    seg[order] = np.cumsum(diff) - 1
    seg = np.where(valid, seg, n)
    gpos = np.arange(n, dtype=np.int64)
    by = np.argsort(seg, kind="stable")
    ks = (seg * n + gpos)[by]
    own = np.searchsorted(ks, seg * n + gpos)
    first = np.searchsorted(ks, seg[C:] * n + left)
    return seg, by, own, first


def _ranked(name):
    """(key columns, valid, left of the batch's rows, C) of a case: a carry
    of C entries packed right, a batch compacted left."""
    rng = np.random.default_rng(sum(map(ord, name)))
    C, T, fill, k, groups = 1024, 256, 700, 200, 40
    if name == "one_group":
        groups = 1
    elif name == "every_entry_its_own_group":
        groups = 0
    elif name == "two_thousand_groups":
        C, T, fill, k, groups = 8192, 2048, 6000, 2048, 2000
    elif name == "no_invalid_entry":
        fill, k = C, T
    elif name == "an_empty_carry":
        fill = 0
    elif name == "an_empty_batch":
        k = 0
    elif name == "under_two_scan_rows":
        C, T, fill, k = 1024, 1000, 1000, 990          # N = 2024 < 2048
    elif name == "over_two_scan_rows_and_ragged":
        C, T, fill, k = 4096, 1001, 3000, 900          # N = 5097 = 4 x 1024 + 1001
    n = C + T
    g = np.arange(n)
    valid = (g >= C - fill) & (g < C + k)
    key = g.astype(np.int32) if groups == 0 \
        else rng.integers(0, groups, n).astype(np.int32)
    cols = [key]
    if name == "float_keys":            # -0.0 groups with 0.0, halves apart
        cols = [np.where(key % 5 == 0, -0.0, key % 5 * 0.5).astype(
            np.float32), (key // 5).astype(np.float64) - 3.5]
    elif name == "wide_keys":           # both words of an i64 tell groups apart
        cols = [(key % 4).astype(np.int64) * (2 ** 33 + 7) - 2 ** 40,
                (key // 4).astype(np.int32) - 3]
    # a sliding window's edge: some way back, never past the row itself
    left = np.maximum(g[C:] - rng.integers(0, fill + 2, T), 0)
    if name == "left_past_the_segments_last_member":
        left = np.minimum(g[C:] + rng.integers(0, 3, T)     # an edge is a
                          * rng.integers(1, T, T), n)       # position, or n
    return cols, valid, left, C


RANKED = ["one_group", "every_entry_its_own_group", "two_thousand_groups",
          "invalid_in_carry_and_pads", "no_invalid_entry", "an_empty_carry",
          "an_empty_batch", "left_past_the_segments_last_member",
          "under_two_scan_rows", "over_two_scan_rows_and_ragged",
          "float_keys", "wide_keys"]


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", RANKED)
def test_ranks_off_the_group_bys_order_are_the_searched_ranks(case, jitted):
    """`_group_order`, `_to_arrival` and `_seg_ranges` against the sort,
    scatter and two 64-bit `searchsorted`s they replaced, at every valid
    batch row: the order over the valid entries, a row's own rank and its
    window's first rank; and the two ways back to arrival order (segment
    ids, a tumbling kind's running values) at every entry."""
    cols, valid, left, C = _ranked(case)
    n = len(valid)
    seg, by, own, first = _searched_ranks(cols, valid, left, C)

    def ranks(cols, valid, left):
        order, seg_sorted = wd._group_order(cols, valid)
        _, first, own = wd._seg_ranges(order, seg_sorted, left, valid[C:])
        run = jnp.arange(n, dtype=jnp.float32) * 0.5      # any payload
        return (order, wd._to_arrival(order, seg_sorted), first, own,
                wd._to_arrival(order, run))
    got = (jax.jit(ranks) if jitted else ranks)(
        [jnp.asarray(c) for c in cols], jnp.asarray(valid), jnp.asarray(left))
    order, seg_back, first_got, own_got, run_back = map(np.asarray, got)
    held = int(valid.sum())
    live = valid[C:]
    assert np.array_equal(order[:held], by[:held])
    assert sorted(order[held:]) == sorted(by[held:])     # the invalid, last
    # the same groups in the same order, numbered over the valid entries
    # alone (PR 49's ids skipped a key that only invalid entries held)
    assert np.array_equal(seg_back[valid],
                          np.unique(seg[valid], return_inverse=True)[1])
    assert (seg_back[~valid] == n).all()
    assert np.array_equal(own_got[live], own[C:][live])
    assert np.array_equal(first_got[live], first[live])
    # a tumbling kind's way back, `run[searchsorted(ks, seg * n + arange)]`
    # over ks = (seg * n + arange)[order]: the rank of every entry
    ks = (seg * n + np.arange(n))[by]
    rank = np.searchsorted(ks, seg * n + np.arange(n))
    assert np.array_equal(run_back[valid], (rank * 0.5)[valid])
    assert sorted(run_back) == list(np.arange(n) * 0.5)


def run_app(app, rows, batch_sizes=None, rng=None):
    m = SiddhiManager()
    rt = m.create_app_runtime(app)
    out = []
    rt.add_callback("O", lambda evs: out.extend((e.timestamp, e.data)
                                                for e in evs))
    h = rt.input_handler("S")
    i = 0
    while i < len(rows):
        n = (batch_sizes and batch_sizes.pop(0)) or \
            (rng.randint(1, 7) if rng else 1)
        for ts, row in rows[i:i + n]:
            h.send(row, timestamp=ts)
        rt.flush()
        i += n
    rt.flush()
    m.shutdown()
    return out


def differential(query, rows, seed=0):
    head = "@app:playback define stream S (sym string, p double, v long);\n"
    dev_app = "@app:deviceWindows('always')\n" + head + query
    host_app = "@app:deviceWindows('never')\n" + head + query
    rng1, rng2 = random.Random(seed), random.Random(seed)
    dev = run_app(dev_app, rows, rng=rng1)
    host = run_app(host_app, rows, rng=rng2)
    assert len(dev) == len(host), (len(dev), len(host))
    for d, h in zip(dev, host):
        assert d[0] == h[0], (d, h)
        for a, b in zip(d[1], h[1]):
            if isinstance(a, float):
                assert b == pytest.approx(a, rel=2e-5, abs=2e-4), (d, h)
            else:
                assert a == b, (d, h)


def gen_rows(n, n_syms=3, seed=1):
    r = random.Random(seed)
    ts = 1000
    rows = []
    for _ in range(n):
        ts += r.randint(0, 400)
        rows.append((ts, (f"s{r.randint(0, n_syms - 1)}",
                          round(r.uniform(-50, 150), 2), r.randint(1, 9))))
    return rows


QUERIES = [
    "from S#window.length(5) select sym, sum(p) as s, count() as c "
    "insert into O;",
    "from S#window.length(1) select sum(p) as s insert into O;",
    "from S#window.length(7) select sym, sum(p) as s group by sym "
    "insert into O;",
    "from S#window.length(4) select min(p) as lo, max(p) as hi, avg(p) as m "
    "insert into O;",
    "from S#window.time(1 sec) select sum(p) as s, count() as c "
    "insert into O;",
    "from S#window.time(700 milliseconds) select sym, avg(p) as m "
    "group by sym insert into O;",
    "from S#window.lengthBatch(4) select sym, sum(p) as s group by sym "
    "insert into O;",
    "from S#window.lengthBatch(3) select min(p) as lo, max(p) as hi "
    "insert into O;",
    "from S[p > 0]#window.length(5) select sym, sum(p) as s insert into O;",
    "from S#window.length(6) select sym, sum(p) as s group by sym "
    "having s > 100.0 insert into O;",
    "from S#window.time(2 sec) select sum(v) as sv, avg(p) as ap "
    "group by sym insert into O;",
]


@pytest.mark.parametrize("qi", [
    pytest.param(i, marks=pytest.mark.slow) if i == 2 else i
    for i in range(len(QUERIES))])
def test_differential(qi):
    differential(QUERIES[qi], gen_rows(120, seed=qi + 10), seed=qi)


def test_differential_large_batches():
    # batch boundaries crossing window size + carry growth
    rows = gen_rows(400, n_syms=5, seed=99)
    differential("from S#window.time(300 milliseconds) select sym, "
                 "sum(p) as s group by sym insert into O;", rows, seed=7)


def test_device_snapshot_restore():
    app = ("@app:deviceWindows('always') @app:playback\n"
           "define stream S (sym string, p double, v long);\n"
           "from S#window.length(4) select sum(p) as s insert into O;")
    m = SiddhiManager()
    rt = m.create_app_runtime(app)
    out = []
    rt.add_callback("O", lambda evs: out.extend(e.data for e in evs))
    h = rt.input_handler("S")
    for i, (ts, row) in enumerate(gen_rows(10, seed=3)):
        h.send(row, timestamp=ts)
    rt.flush()
    snap = rt.snapshot()

    m2 = SiddhiManager()
    rt2 = m2.create_app_runtime(app)
    out2 = []
    rt2.add_callback("O", lambda evs: out2.extend(e.data for e in evs))
    rt2.restore(snap)
    extra = gen_rows(6, seed=4)
    for ts, row in extra:
        rt2.input_handler("S").send(row, timestamp=ts)
    rt2.flush()
    # continuity: same as uninterrupted run
    m3 = SiddhiManager()
    rt3 = m3.create_app_runtime(app)
    out3 = []
    rt3.add_callback("O", lambda evs: out3.extend(e.data for e in evs))
    for ts, row in gen_rows(10, seed=3) + extra:
        rt3.input_handler("S").send(row, timestamp=ts)
    rt3.flush()
    a = [v for row in out + out2 for v in row]
    b = [v for row in out3 for v in row]
    assert a == pytest.approx(b, rel=2e-5, abs=2e-4)
    m.shutdown(); m2.shutdown(); m3.shutdown()


def test_carry_overflow_grows():
    # tiny initial carry forces growth for a long time window
    app = ("@app:deviceWindows('always') @app:playback\n"
           "define stream S (sym string, p double, v long);\n"
           "from S#window.time(1 hour) select count() as c insert into O;")
    m = SiddhiManager()
    rt = m.create_app_runtime(app)
    plan = rt._plans[0]
    assert isinstance(plan, DeviceWindowAggPlan)
    plan.C = 8
    plan.state = plan._init_state()
    out = []
    rt.add_callback("O", lambda evs: out.extend(e.data for e in evs))
    h = rt.input_handler("S")
    ts = 1000
    for i in range(50):
        ts += 10
        h.send(("x", 1.0, 1), timestamp=ts)
    rt.flush()
    assert plan.C > 8
    assert out[-1] == (50,)
    m.shutdown()


def test_window_record_says_the_plans_form_and_counts_its_reruns():
    """`rt.explain()["queries"][q]["window"]` and `device_metrics()`: the
    static form, and the two counters of a carry that overflowed (each a
    recompile); the pull notes its D2H bytes with the profiler."""
    app = ("@app:deviceWindows('always') @app:playback\n"
           "define stream S (sym string, p double, v long);\n"
           "@info(name='q') from S#window.time(1 hour) select sym, "
           "sum(p) as s, count() as c group by sym insert into O;")
    m = SiddhiManager()
    rt = m.create_app_runtime(app)
    plan = rt._plan_by_name["q"]
    want = {"kind": "time", "duration_ms": 3_600_000, "grouped": True,
            "sites": ["sum", "count"], "T": None, "carry_capacity": 1024,
            "sum_form": "pair_prefix", "block": None,
            "carry_overflow_reruns": 0, "carry_grows": 0}
    assert rt.explain()["queries"]["q"]["window"] == want
    assert rt.explain()["queries"]["q"]["window_carry"] == {
        "capacity": 1024, "held": 0, "held_max": 0, "grows": 0, "reruns": 0}
    plan.C = 8
    plan.state = plan._init_state()
    out = []
    rt.add_callback("O", lambda evs: out.extend(e.data for e in evs))
    h = rt.input_handler("S")
    for i in range(50):
        h.send(("x", 1.0, 1), timestamp=1000 + 10 * i)
    rt.flush()
    assert out[-1] == ("x", 50.0, 50)
    rec = rt.explain()["queries"]["q"]["window"]
    # 8 -> 64 at once: the step's word said 50, ONE grow and one re-run
    # (by doubling, until PR 49: three of each)
    assert rec == {**want, "T": 64, "carry_capacity": 64,
                   "carry_overflow_reruns": 1, "carry_grows": 1}
    assert rt.statistics()["device"]["q"]["window"] == rec
    # beside it, how full the carry is: the count the step's word carries
    carry = rt.explain()["queries"]["q"]["window_carry"]
    assert carry == {"capacity": 64, "held": 50, "held_max": 50,
                     "grows": 1, "reruns": 1}
    assert rt.statistics()["device"]["q"]["window_carry"] == carry
    assert rt.statistics()["profile"]["plans"]["q"]["bytes"]["d2h"] > 0
    m.shutdown()
    # a length window says its length
    m = SiddhiManager()
    rt = m.create_app_runtime(
        "define stream S (sym string, p double, v long);\n"
        "@info(name='q') from S#window.lengthBatch(4) select max(p) as hi "
        "insert into O;")
    rec = rt.explain()["queries"]["q"]["window"]
    assert (rec["kind"], rec["length"], rec["sites"]) == (
        "lengthbatch", 4, ["max"])
    assert "duration_ms" not in rec
    m.shutdown()


def test_f64_all_double_outputs():
    """Slim pack with every output column DOUBLE in f64 mode: the i-pack
    is empty and must be omitted, not stacked (r4 review finding)."""
    rows = gen_rows(60, seed=42)
    head = ("@app:devicePrecision('f64')\n@app:playback "
            "define stream S (sym string, p double, v long);\n")
    q = "from S#window.length(5) select avg(p) as m, sum(p) as s insert into O;"
    import random as _r
    dev = run_app("@app:deviceWindows('always')\n" + head + q, rows,
                  rng=_r.Random(1))
    host = run_app("@app:deviceWindows('never')\n" + head + q, rows,
                   rng=_r.Random(1))
    assert len(dev) == len(host)
    for d, h in zip(dev, host):
        assert d[0] == h[0]
        for a, b in zip(d[1], h[1]):
            assert b == pytest.approx(a, rel=1e-9)


# -- r5 widening: grouped sliding min/max, externalTime, order-by/limit ---

@pytest.mark.parametrize("q", [
    "from S#window.length(9) select sym, min(p) as lo, max(p) as hi "
    "group by sym insert into O;",
    "from S#window.length(4) select sym, max(p) as hi, sum(v) as sv "
    "group by sym having hi > 50.0 insert into O;",
    "from S#window.time(800) select sym, min(p) as lo group by sym "
    "insert into O;",
])
def test_grouped_sliding_minmax(q):
    differential(q, gen_rows(160, seed=31), seed=31)


def test_grouped_sliding_minmax_device_engaged():
    m = SiddhiManager()
    rt = m.create_app_runtime(
        "@app:deviceWindows('always')\n"
        "define stream S (sym string, p double, v long);\n"
        "from S#window.length(5) select sym, min(p) as lo group by sym "
        "insert into O;")
    assert any(isinstance(p, DeviceWindowAggPlan) for p in rt._plans)
    m.shutdown()


def test_external_time_differential():
    """externalTime(et, D): window clock from an event attribute."""
    head = ("@app:playback define stream S (sym string, p double, "
            "v long, et long);\n")
    q = ("from S#window.externalTime(et, 700) select sym, avg(p) as ap, "
         "count() as c group by sym insert into O;")
    r = random.Random(41)
    ts, et = 1000, 50_000
    rows = []
    for _ in range(150):
        ts += r.randint(1, 50)
        et += r.randint(0, 300)
        rows.append((ts, (f"s{r.randint(0, 2)}",
                          round(r.uniform(0, 90), 2), r.randint(1, 9), et)))
    dev_app = "@app:deviceWindows('always')\n" + head + q
    host_app = "@app:deviceWindows('never')\n" + head + q
    dev = run_app(dev_app, rows, rng=random.Random(5))
    host = run_app(host_app, rows, rng=random.Random(5))
    assert len(dev) == len(host), (len(dev), len(host))
    for d, h in zip(dev, host):
        assert d[0] == h[0], (d, h)
        for a, b in zip(d[1], h[1]):
            if isinstance(a, float):
                assert b == pytest.approx(a, rel=2e-5, abs=2e-4), (d, h)
            else:
                assert a == b, (d, h)


def test_external_time_device_engaged():
    m = SiddhiManager()
    rt = m.create_app_runtime(
        "@app:deviceWindows('always')\n"
        "define stream S (sym string, p double, et long);\n"
        "from S#window.externalTime(et, 500) select sum(p) as s "
        "insert into O;")
    assert any(isinstance(p, DeviceWindowAggPlan) for p in rt._plans)
    m.shutdown()


@pytest.mark.parametrize("q", [
    "from S#window.length(6) select sym, sum(p) as s group by sym "
    "order by s insert into O;",
    "from S#window.length(6) select sym, sum(p) as s group by sym "
    "order by s desc limit 2 insert into O;",
    "from S#window.lengthBatch(8) select sym, count() as c group by sym "
    "order by sym limit 2 offset 1 insert into O;",
])
def test_order_by_limit_on_device_outputs(q):
    differential(q, gen_rows(120, seed=51), seed=51)


def test_order_by_device_engaged():
    m = SiddhiManager()
    rt = m.create_app_runtime(
        "@app:deviceWindows('always')\n"
        "define stream S (sym string, p double, v long);\n"
        "from S#window.length(5) select sym, sum(p) as s group by sym "
        "order by s desc limit 3 insert into O;")
    assert any(isinstance(p, DeviceWindowAggPlan) for p in rt._plans)
    m.shutdown()


def _differential_et(q, rows, seed):
    head = ("@app:playback define stream S (sym string, p double, "
            "v long, et long);\n")
    dev = run_app("@app:deviceWindows('always')\n" + head + q, rows,
                  rng=random.Random(seed))
    host = run_app("@app:deviceWindows('never')\n" + head + q, rows,
                   rng=random.Random(seed))
    assert len(dev) == len(host), (len(dev), len(host), dev[:3], host[:3])
    for d, h in zip(dev, host):
        assert d[0] == h[0], (d, h)
        for a, b in zip(d[1], h[1]):
            if isinstance(a, float):
                assert b == pytest.approx(a, rel=2e-5, abs=2e-4), (d, h)
            else:
                assert a == b, (d, h)


def _et_rows(n, seed, gap=300):
    r = random.Random(seed)
    ts, et = 1000, 50_000
    rows = []
    for _ in range(n):
        ts += r.randint(1, 50)
        et += r.randint(0, gap)
        rows.append((ts, (f"s{r.randint(0, 2)}",
                          round(r.uniform(0, 90), 2), r.randint(1, 9), et)))
    return rows


@pytest.mark.parametrize("seed", [3, 4])
def test_a_clock_that_leaps_by_days_is_searched_wide_and_narrow(seed):
    """An event clock that moves up to ten days an event, a three-day
    window: a flush of a few events lies within 2^31 ms (24.8 days) and is
    searched on 32-bit offsets, a longer one is not (`_clock_left`); both
    owe what the interpreter delivers."""
    day = 86_400_000
    _differential_et(
        f"from S#window.externalTime(et, {3 * day}) select sym, "
        "sum(p) as s, count() as c group by sym insert into O;",
        _et_rows(150, seed, gap=10 * day), seed)


@pytest.mark.parametrize("q", [
    "from S#window.externalTimeBatch(et, 700) select sum(p) as s, "
    "count() as c insert into O;",
    "from S#window.externalTimeBatch(et, 900) select sym, max(p) as hi, "
    "avg(v) as av group by sym insert into O;",
])
def test_external_time_batch_differential(q):
    _differential_et(q, _et_rows(150, 61), 61)


def test_external_time_batch_sparse_buckets():
    """Empty buckets between events emit nothing (the reference advances
    start through them silently)."""
    _differential_et(
        "from S#window.externalTimeBatch(et, 200) select count() as c "
        "insert into O;", _et_rows(80, 62, gap=1500), 62)


def test_external_time_batch_filtered_first_batch_anchor():
    """A fully-filtered first micro-batch must NOT latch the bucket
    anchor: the device kernel's argmax over an all-False valid mask
    points at carry slot 0, and latching that garbage event-time would
    permanently shift every bucket boundary vs the host path."""
    q = ("from S[p > 0]#window.externalTimeBatch(et, 700) "
         "select sum(p) as s, count() as c insert into O;")
    r = random.Random(7)
    ts, et, rows = 1000, 50_000, []
    for i in range(60):
        ts += r.randint(1, 50)
        et += r.randint(0, 300)
        # the first 6 rows (batch 1, see batch_sizes below) all fail the
        # filter; later rows mix pass/fail
        p = round(r.uniform(-90.0, -1.0), 2) if i < 6 \
            else round(r.uniform(-50.0, 90.0), 2)
        rows.append((ts, ("s0", p, 1, et)))
    head = ("@app:playback define stream S (sym string, p double, "
            "v long, et long);\n")
    dev = run_app("@app:deviceWindows('always')\n" + head + q, rows,
                  batch_sizes=[6] + [5] * 100)
    host = run_app("@app:deviceWindows('never')\n" + head + q, rows,
                   batch_sizes=[6] + [5] * 100)
    assert len(dev) == len(host) and dev, (len(dev), len(host))
    for d, h in zip(dev, host):
        assert d[0] == h[0], (d, h)
        for a, b in zip(d[1], h[1]):
            if isinstance(a, float):
                assert b == pytest.approx(a, rel=2e-5, abs=2e-4), (d, h)
            else:
                assert a == b, (d, h)


def test_external_time_batch_device_engaged():
    m = SiddhiManager()
    rt = m.create_app_runtime(
        "@app:deviceWindows('always')\n"
        "define stream S (sym string, p double, et long);\n"
        "from S#window.externalTimeBatch(et, 500) select sum(p) as s "
        "insert into O;")
    assert any(isinstance(p, DeviceWindowAggPlan) for p in rt._plans)
    m.shutdown()


# -- the step's closed forms (PR 45) -----------------------------------------
# A length window's left edge by arithmetic, its base prefix read as a static
# shift, its count an integer, and no compaction scatter where no filter is:
# each against the searched / gathered form it replaced, bit for bit on every
# delivered row.  What they rest on: the valid entries of [carry | batch] are
# ONE contiguous run (the carry packed right, the batch compacted left).  (The
# identities on the primitives alone open the file.)

W_HEAD = ("@app:playback @app:deviceWindows('always')\n"
          "define stream S (symbol string, price double, volume int);\n"
          "@info(name='q') from S")


@functools.partial(jax.jit, static_argnums=(4,))
def _searched(cvalid, cprice, price, k, L):
    valid = jnp.concatenate([cvalid, jnp.arange(len(price)) < k])
    v = jnp.where(valid, jnp.concatenate([cprice, price]), 0.0)
    vcnt = jnp.cumsum(valid.astype(jnp.int64))
    left = jnp.searchsorted(vcnt, jnp.maximum(vcnt - L, 0), side="right")
    s = wd._range_sum(wd._prefix_pairs(v), left - 1)
    c = wd._range_sum(wd._prefix_pairs(valid.astype(v.dtype)), left - 1)
    return s, s / jnp.maximum(c, 1.0), c


def searched_step(state, price, k, L):
    """The general forms written out, as the step traced them before the
    closed forms: running valid counts, the left edge by `searchsorted`,
    each prefix pair GATHERED at `left - 1`, the count a second pair scan.
    -> (sum, avg, count) of the batch's k rows."""
    C = len(state["valid"])
    sac = _searched(state["valid"], state["c.price"], price, k, L)
    return [np.asarray(x)[C:C + k].astype(np.float64) for x in sac]


def is_suffix(valid) -> bool:
    v = np.asarray(valid).astype(np.int8)
    return bool((np.diff(v) >= 0).all())


class Driven:
    """One window query fed batch by batch, its plan's state at hand."""

    def __init__(self, query, head=W_HEAD):
        self.mgr = SiddhiManager()
        self.rt = self.mgr.create_app_runtime(head + query)
        self.plan = self.rt._plan_by_name["q"]
        assert isinstance(self.plan, DeviceWindowAggPlan)
        self.got = []
        self.rt.add_batch_callback("O", lambda b: self.got.append(
            {k: np.array(v) for k, v in b.columns.items()}))
        self.rt.start()
        self.sent = 0

    def send(self, price, volume=None, symbol=None):
        """-> the delivered columns of this batch (None: no row)."""
        n = len(price)
        cols = {"symbol": np.zeros(n, np.int32) if symbol is None else symbol,
                "price": np.asarray(price, np.float64),
                "volume": np.ones(n, np.int32) if volume is None else volume}
        self.got.clear()
        self.rt.input_handler("S").send_batch(
            cols, 1000 + self.sent + np.arange(n, dtype=np.int64))
        self.rt.flush()
        self.sent += n
        assert len(self.got) <= 1
        return self.got[0] if self.got else None

    def state(self):
        return {k: np.asarray(v) for k, v in self.plan.state.items()}

    def close(self):
        self.mgr.shutdown()


def padded32(x, T):
    out = np.zeros(T, np.float32)
    out[:len(x)] = x
    return out


SAC = " select sum(price) as s, avg(price) as a, count() as c insert into O;"
# (window length, batch sizes): the carry empty, part-filled and full, batches
# under their padded T, a batch longer and shorter than the window
CLOSED_FORM_CASES = {
    "length1": (1, [1, 3, 9, 2]),
    "part_filled_carry": (5, [3, 1, 7, 8, 2, 16]),
    "L_equals_C": (8, [5, 8, 3, 20, 1]),
    "L_not_a_power_of_two": (1000, [300, 600, 1500, 7, 2048, 999]),
    "L_equals_C_1024": (1024, [1000, 100, 3000, 24]),
}


@pytest.mark.parametrize("case", sorted(CLOSED_FORM_CASES))
@pytest.mark.parametrize("filtered", [False, True], ids=["all", "thinned"])
def test_closed_forms_deliver_the_searched_forms_bits(case, filtered):
    L, sizes = CLOSED_FORM_CASES[case]
    rng = np.random.default_rng([L, filtered])
    d = Driven(("[volume >= 400]" if filtered else "")
               + f"#window.length({L})" + SAC)
    try:
        assert d.plan.window_step == {
            "left_edge": "arithmetic", "prefix_read": "shift",
            "compaction": "scatter" if filtered else "identity"}
        for n in sizes:
            price = cents(rng, n)
            volume = rng.integers(1, 1000, n).astype(np.int32)
            keep = volume >= 400 if filtered else np.ones(n, bool)
            before = d.state()
            T = wd.pow2_at_least(n)
            want = searched_step(before, padded32(price[keep], T),
                                 int(keep.sum()), L)
            out = d.send(price, volume)
            if not keep.any():
                assert out is None
                continue
            for name, w in zip("sac", want):
                assert np.array_equal(out[name].astype(np.float64), w), \
                    (case, n, name)
            after = d.state()
            assert is_suffix(after["valid"])
            assert after["valid"].sum() == min(
                L, before["valid"].sum() + keep.sum())
    finally:
        d.close()


def test_closed_forms_a_filter_that_passes_nothing():
    """k = 0: no row, the carry as it was; then traffic again."""
    d = Driven("[volume >= 400]#window.length(5)" + SAC)
    try:
        rng = np.random.default_rng(5)
        d.send(cents(rng, 3), np.full(3, 500, np.int32))
        before = d.state()
        assert d.send(cents(rng, 6), np.zeros(6, np.int32)) is None
        after = d.state()
        for k in ("valid", "c.price"):
            assert np.array_equal(before[k], after[k]), k
        price = cents(rng, 4)
        want = searched_step(after, padded32(price, 8), 4, 5)
        out = d.send(price, np.full(4, 999, np.int32))
        for name, w in zip("sac", want):
            assert np.array_equal(out[name].astype(np.float64), w), name
    finally:
        d.close()


def test_closed_forms_after_a_grow():
    """`_grow` pads the carry on the LEFT: the valid run stays one run."""
    L = 5
    d = Driven(f"#window.length({L})" + SAC)
    try:
        rng = np.random.default_rng(11)
        d.send(cents(rng, 3))
        d.plan._grow(4 * d.plan.C)
        assert d.plan.C == 32 and is_suffix(d.state()["valid"])
        for n in (1, 9, 4):
            price = cents(rng, n)
            want = searched_step(d.state(), padded32(price, wd.pow2_at_least(n)),
                                 n, L)
            out = d.send(price)
            for name, w in zip("sac", want):
                assert np.array_equal(out[name].astype(np.float64), w), name
            assert is_suffix(d.state()["valid"])
    finally:
        d.close()


# `state_dict()` of `#window.length(5)` + SAC after ONE 3-event batch, as the
# step of PR 44 wrote it (its tree, run here): a length window's unread
# timestamp column holds 0 where that step had carried an entry
PARENT_STATE = {"C": 8, "state": {
    "c.price": np.array([0, 0, 0, 0, 0, 101.25, 99.5, 120.75], np.float32),
    "seen": np.int64(3),
    "ts": np.array([-(2 ** 62)] * 5 + [0] * 3, np.int64),
    "valid": np.array([False] * 5 + [True] * 3)}}


def test_closed_forms_restore_a_state_the_parents_step_wrote():
    """The state's layout is unchanged: an old snapshot loads, and the
    window goes on from it."""
    d = Driven("#window.length(5)" + SAC)
    try:
        fresh = d.plan.state_dict()
        assert fresh["C"] == PARENT_STATE["C"]
        assert {k: (v.shape, v.dtype) for k, v in fresh["state"].items()} == {
            k: (np.shape(v), np.asarray(v).dtype)
            for k, v in PARENT_STATE["state"].items()}
        d.plan.load_state_dict(PARENT_STATE)
        out = d.send([100.5, 90.25, 110.0])
        assert out["s"].tolist() == [422.0, 512.25, 521.0]
        assert out["c"].tolist() == [4, 5, 5]
        # ONE f32 division: correctly rounded on the CPU, within 2.26 ulps
        # on a TPU v5e (PERF.md section 6, PR 44)
        mean = np.array([422.0 / 4, 512.25 / 5, 521.0 / 5])
        assert (np.abs(out["a"] - mean)
                <= 3 * np.spacing(mean.astype(np.float32))).all()
        snap = d.plan.state_dict()
    finally:
        d.close()
    d = Driven("#window.length(5)" + SAC)      # and through its own snapshot
    try:
        d.plan.load_state_dict(snap)
        assert d.send([95.0])["s"].tolist() == [516.5]
    finally:
        d.close()


def test_closed_forms_grouped_length_takes_the_arithmetic_left():
    """Grouped `length`: the sums stay segmented and only receive the
    arithmetic left edge.  Quarter-step prices, so every sum is exact and
    float64 arithmetic over each window's members owes the same bits."""
    L = 7
    d = Driven(f"#window.length({L}) select symbol, sum(price) as s, "
               "count() as c, max(price) as hi group by symbol "
               "insert into O;")
    try:
        assert d.plan.window_step == {
            "left_edge": "arithmetic", "prefix_read": "segmented",
            "compaction": "identity"}
        rng = np.random.default_rng(3)
        codes = np.array([d.rt.strings.encode(f"K{i}") for i in range(3)],
                         np.int32)
        hist_p, hist_g = [], []
        for n in (4, 1, 9, 16, 2):
            price = np.round(rng.uniform(90, 130, n) * 4) / 4
            group = rng.integers(0, 3, n)
            out = d.send(price, symbol=codes[group])
            for j in range(n):
                hist_p.append(price[j]); hist_g.append(group[j])
                win = [(p, g) for p, g in zip(hist_p[-L:], hist_g[-L:])
                       if g == group[j]]
                assert out["s"][j] == sum(p for p, _g in win)
                assert out["c"][j] == len(win)
                assert out["hi"][j] == max(p for p, _g in win)
            assert is_suffix(d.state()["valid"])
    finally:
        d.close()


INVARIANT_QUERIES = {
    "length": "#window.length(6)" + SAC,
    "length_filtered": "[volume >= 400]#window.length(6)" + SAC,
    "length_grouped": "#window.length(6) select symbol, sum(price) as s "
                      "group by symbol insert into O;",
    "time": "#window.time(5 milliseconds)" + SAC,
    "lengthBatch": "#window.lengthBatch(6)" + SAC,
}


@pytest.mark.parametrize("kind", sorted(INVARIANT_QUERIES))
def test_the_carrys_valid_is_a_suffix_after_every_step(kind):
    """The invariant itself (`carry()` in `_build_step_fn`): the carry is
    packed right, whatever the kind, the batch and the filter."""
    d = Driven(INVARIANT_QUERIES[kind])
    try:
        rng = np.random.default_rng(len(kind))
        for n in (1, 4, 9, 2, 30, 3, 1):
            d.send(cents(rng, n), rng.integers(1, 1000, n).astype(np.int32),
                   rng.integers(0, 3, n).astype(np.int32))
            st = d.state()
            assert is_suffix(st["valid"]), (kind, n, st["valid"])
            assert st["valid"].shape == (d.plan.C,)
    finally:
        d.close()


WINDOW_STEP_RECORDS = {
    "length": ("#window.length(9) select avg(price) as a insert into O;",
               ("arithmetic", "shift", "identity")),
    "length_filter": ("[volume > 3]#window.length(9) select avg(price) as a "
                      "insert into O;", ("arithmetic", "shift", "scatter")),
    "length_grouped": ("#window.length(9) select symbol, avg(price) as a "
                       "group by symbol insert into O;",
                       ("arithmetic", "segmented", "identity")),
    "time": ("#window.time(1 sec) select avg(price) as a insert into O;",
             ("search", "gather", "identity")),
    "lengthBatch": ("#window.lengthBatch(9) select avg(price) as a "
                    "insert into O;", ("arithmetic", "gather", "identity")),
}


WINDOW_RANKS_RECORDS = {
    "length": ("#window.length(9) select avg(price) as a insert into O;",
               (None, None)),
    "time": ("#window.time(1 sec) select avg(price) as a insert into O;",
             (None, None)),
    "length_grouped": ("#window.length(9) select symbol, avg(price) as a "
                       "group by symbol insert into O;",
                       ("order", "bounded_search")),
    "time_grouped": ("#window.time(1 sec) select symbol, max(price) as hi, "
                     "count() as c group by symbol insert into O;",
                     ("order", "bounded_search")),
    "lengthBatch_grouped": ("#window.lengthBatch(9) select symbol, "
                            "sum(price) as s group by symbol insert into O;",
                            ("order", None)),
}


@pytest.mark.parametrize("kind", sorted(WINDOW_RANKS_RECORDS))
def test_window_ranks_record_says_how_a_group_bys_ranks_are_taken(kind):
    """`window_ranks`, one more SIBLING of `window` (not a key of
    `window_step`, whose three keys a benchmark cell's test holds as they
    are): `segment_rank` "order" wherever the step groups ("search" no
    longer occurs), `window_first` "bounded_search" where a grouped window
    slides; None where nothing is grouped."""
    query, (rank, first) = WINDOW_RANKS_RECORDS[kind]
    d = Driven(query)
    try:
        want = {"segment_rank": rank, "window_first": first}
        ent = d.rt.explain()["queries"]["q"]
        assert ent["window_ranks"] == want
        assert "segment_rank" not in ent["window_step"]
        d.send([100.0, 101.0], np.array([5, 5], np.int32))
        assert d.plan.device_metrics()["window_ranks"] == want
    finally:
        d.close()


@pytest.mark.parametrize("kind", sorted(WINDOW_STEP_RECORDS))
def test_window_step_record_says_the_form_of_each_indexed_pass(kind):
    """`window_step`, a SIBLING of `window` in `rt.explain()` and
    `device_metrics()`: what the step traced, static for the plan's life."""
    query, (left, read, compaction) = WINDOW_STEP_RECORDS[kind]
    d = Driven(query)
    try:
        want = {"left_edge": left, "prefix_read": read,
                "compaction": compaction}
        ent = d.rt.explain()["queries"]["q"]
        assert ent["window_step"] == want
        assert "left_edge" not in ent["window"]
        d.send([100.0, 101.0], np.array([5, 5], np.int32))
        assert d.plan.device_metrics()["window_step"] == want
        assert d.rt.explain()["queries"]["q"]["window_step"] == want
    finally:
        d.close()


# -- the carry grows to what overflowed, at once (PR 49) -----------------------
# The step's word (the one that was the overflow flag) carries HOW MANY
# entries the step had to keep; `_materialize` grows to the power of two that
# holds them (never less than double).  Against growth by doubling, the
# policy it replaced: the same bits on every row of every carried kind, and
# one grow where there were four.

G_HEAD = ("@app:playback @app:deviceWindows('always')\n"
          "define stream S (symbol string, price double, volume int, "
          "et long);\n@info(name='q') from S")
GROWN = {
    "time": "#window.time(1 hour) select sum(price) as s, avg(price) as a, "
            "count() as c insert into O;",
    "grouped_time": "#window.time(1 hour) select symbol, avg(price) as a, "
                    "max(price) as hi group by symbol insert into O;",
    "external_time": "#window.externalTime(et, 1 hour) select symbol, "
                     "sum(price) as s group by symbol insert into O;",
    "length_batch": "#window.lengthBatch(128) select symbol, sum(price) as s, "
                    "min(price) as lo group by symbol insert into O;",
    "external_time_batch": "#window.externalTimeBatch(et, 150 milliseconds) "
                           "select "
                           "sum(price) as s, count() as c insert into O;",
}


def _grown_run(query, doubling, batches=(120, 7, 40)):
    """The query on a carry forced down to 8 entries, fed `batches` events
    (1 ms apart, so an hour's window, a second's bucket and a batch of 128
    keep all of the first 120: 16x the carry); -> (rows by batch, grows
    after each batch, window, window_carry)."""
    d = Driven(query, head=G_HEAD)
    if doubling:
        grow = d.plan._grow
        d.plan._grow = lambda new_c: grow(2 * d.plan.C)
    d.plan.C = 8
    d.plan.state = d.plan._init_state()
    rng = np.random.default_rng(48)
    rows, grows, sent = [], [], 0
    for n in batches:
        cols = {"symbol": rng.integers(0, 3, n).astype(np.int32),
                "price": cents(rng, n),
                "volume": np.ones(n, np.int32),
                "et": 5000 + sent + np.arange(n, dtype=np.int64)}
        d.got.clear()
        d.rt.input_handler("S").send_batch(
            cols, 1000 + sent + np.arange(n, dtype=np.int64))
        d.rt.flush()
        sent += n
        rows.append({k: np.concatenate([g[k] for g in d.got])
                     for k in d.got[0]} if d.got else None)
        grows.append((d.plan.counters["carry_grows"], d.plan.C))
    entry = d.rt.explain()["queries"]["q"]
    d.close()
    return rows, grows, entry["window"], entry["window_carry"]


@pytest.mark.parametrize("kind", sorted(GROWN))
def test_growth_by_count_delivers_growth_by_doublings_bits(kind):
    by_count, grows, win, carry = _grown_run(GROWN[kind], doubling=False)
    doubled, grows2, win2, carry2 = _grown_run(GROWN[kind], doubling=True)
    assert len(by_count) == len(doubled) == 3
    delivered = 0
    for a, b in zip(by_count, doubled):
        assert (a is None) == (b is None)
        if a is None:
            continue
        assert sorted(a) == sorted(b)
        for k in a:
            assert a[k].dtype == b[k].dtype and a[k].tobytes() == \
                b[k].tobytes(), (kind, k)
            delivered += len(a[k])
    assert delivered > 0
    # the first batch needs 16x the 8 entries: ONE grow and one re-run once
    # the step has said 120, where doubling took four of each (16, 32, 64,
    # 128), each a recompile
    sliding = "Batch" not in GROWN[kind]
    assert grows[0] == (1, 128) and grows2[0] == (4, 128)
    assert grows[1] == grows[0] and grows2[1] == grows2[0]      # 7 more fit
    # 40 more: 167 in an hour's window overflow the 128 once more, either
    # way (never less than double); a tumbling kind has emitted its bucket
    assert grows[2] == ((2, 256) if sliding else (1, 128))
    assert grows2[2] == ((5, 256) if sliding else (4, 128))
    for w, g in ((win, grows), (win2, grows2)):
        assert w["carry_grows"] == w["carry_overflow_reruns"] == g[2][0]
    assert {k: carry[k] for k in ("held", "held_max")} == \
        {k: carry2[k] for k in ("held", "held_max")}
    assert 0 < carry["held"] <= carry["held_max"] <= 167
    if sliding:         # it keeps everything: the word carried every count
        assert carry["held"] == carry["held_max"] == 167


def test_the_steps_word_is_a_count_and_an_overflow_is_a_count_over_capacity():
    """120 events into 8 entries: the step says 120 and the carry grows to
    128 and holds them; a step that fits still says what it keeps; a step
    that needs 4x goes there at once."""
    d = Driven("[price > 0.0]#window.time(1 hour) select count() as c "
               "insert into O;")
    d.plan.C = 8
    d.plan.state = d.plan._init_state()
    assert d.plan.window_carry == {"capacity": 8, "held": 0, "held_max": 0,
                                   "grows": 0, "reruns": 0}
    out = d.send(np.full(120, 1.0))
    assert out["c"].tolist() == list(range(1, 121))
    assert d.plan.window_carry == {"capacity": 128, "held": 120,
                                   "held_max": 120, "grows": 1, "reruns": 1}
    assert int(np.asarray(d.plan.state["valid"]).sum()) == 120
    out = d.send(np.full(7, 1.0))       # fits: the word still counts
    assert out["c"].tolist() == list(range(121, 128))
    assert d.plan.window_carry == {"capacity": 128, "held": 127,
                                   "held_max": 127, "grows": 1, "reruns": 1}
    assert d.plan.device_metrics()["window_carry"] == d.plan.window_carry
    out = d.send(np.full(400, 1.0))     # 527: 1,024 at once, not 256, 512
    assert out["c"].tolist() == list(range(128, 528))
    assert d.plan.window_carry == {"capacity": 1024, "held": 527,
                                   "held_max": 527, "grows": 2, "reruns": 2}
    d.close()


def test_a_time_window_grows_to_each_count_as_it_fills():
    """`roomtemp10m` at a small size: a 600 ms window at an event a ms, fed
    in 256-event batches.  The steps of batches 0, 1 and 2 say 256, 512 and
    600: the carry goes 8 -> 256 -> 512 -> 1,024, three grows and three
    re-runs (by doubling: seven of each), then holds 600 for good."""
    d = Driven("#window.time(600 milliseconds) select symbol, avg(price) as a "
               "group by symbol insert into O;")
    d.plan.C = 8
    d.plan.state = d.plan._init_state()
    for i in range(5):
        out = d.send(np.full(256, 2.0), symbol=np.arange(256, dtype=np.int32)
                     % 3)
        # a mean of equal prices: exact on the CPU, one f32 division (within
        # 3 ulps) on the chip
        assert np.allclose(out["a"], 2.0, rtol=4e-7, atol=0)
        held = min(256 * (i + 1), 600)
        assert d.plan.window_carry == {
            "capacity": (256, 512, 1024)[min(i, 2)], "held": held,
            "held_max": held, "grows": min(i + 1, 3),
            "reruns": min(i + 1, 3)}
    # (the (8, 1024) entry is the constructor's shape check at C_START)
    assert sorted(d.plan._step_cache) == [
        (8, 1024), (256, 8), (256, 256), (256, 512), (256, 1024)]
    d.close()


@pytest.mark.parametrize("depth", [2, 4])
def test_a_pipelined_overflow_replays_the_chain_to_depth_0s_rows(depth):
    """Batches in flight behind a step whose count is over the capacity ran
    on the carry it could not hold: they are taken back and run again at
    the grown capacity, every one at the capacity the plan now has.  The
    rows are depth 0's; the re-runs count the whole chain."""
    def run(head):
        d = Driven("#window.time(1 hour) select symbol, sum(price) as s, "
                   "count() as c group by symbol insert into O;",
                   head=head + W_HEAD)
        d.plan.C = 8
        d.plan.state = d.plan._init_state()
        rng = np.random.default_rng(3)
        sent = 0
        for n in (60, 60, 60, 300, 60):             # no flush between them
            d.rt.input_handler("S").send_batch(
                {"symbol": rng.integers(0, 3, n).astype(np.int32),
                 "price": cents(rng, n), "volume": np.ones(n, np.int32)},
                1000 + sent + np.arange(n, dtype=np.int64))
            sent += n
        d.rt.flush()
        rows = {k: np.concatenate([g[k] for g in d.got]) for k in d.got[0]}
        carry = d.plan.window_carry
        d.close()
        return rows, carry
    base, carry0 = run("")
    piped, carry = run(f"@app:devicePipeline({depth})\n")
    assert sorted(piped) == sorted(base) and len(base["c"]) == 540
    assert all(piped[k].tobytes() == base[k].tobytes() for k in base)
    assert carry0 == {"capacity": 1024, "held": 540, "held_max": 540,
                      "grows": 5, "reruns": 5}      # 64, 128, 256, 512, 1024
    assert {**carry, "reruns": 5} == carry0 and carry["reruns"] > 5


def test_a_length_windows_word_counts_what_it_holds():
    """`window1k`'s shape at a small size: the carry never grows, and
    `held` reads min(n, L) of the capacity the length gives."""
    d = Driven("#window.length(1000) select avg(price) as ap insert into O;")
    d.send(np.full(600, 2.0))
    assert d.plan.window_carry == {"capacity": 1024, "held": 600,
                                   "held_max": 600, "grows": 0, "reruns": 0}
    d.send(np.full(600, 2.0))
    assert d.plan.window_carry == {"capacity": 1024, "held": 1000,
                                   "held_max": 1000, "grows": 0, "reruns": 0}
    d.close()


# -- forms chosen for the TPU compiler's sake (PR 49) ---------------------------

@pytest.mark.parametrize("n", [5, 2047, 2048, 2049, 5000, 1024 * 9])
def test_scan_in_rows_is_the_associative_scan(n):
    rng = np.random.default_rng(n)
    plain = lambda op: jax.jit(lambda x: jax.lax.associative_scan(op, x))
    rows = lambda op: jax.jit(lambda x: wd._scan(op, x))
    ints = jnp.asarray(rng.integers(-2 ** 40, 2 ** 40, n))
    assert np.array_equal(rows(jnp.maximum)(ints), plain(jnp.maximum)(ints))
    # a non-commutative operator, flags and values (the segmented scans')
    flags = jnp.asarray(rng.random(n) < 0.01)
    vals = jnp.asarray(rng.integers(0, 1000, n))

    def comb(a, b):
        return a[0] | b[0], jnp.where(b[0], b[1], a[1] + b[1])
    got, want = rows(comb)((flags, vals)), plain(comb)((flags, vals))
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    # the pair prefix on a grid: exact, so the same whatever the order
    v = jnp.asarray((np.round(rng.uniform(15, 35, n) * 4) / 4).astype(
        np.float32))
    hi, lo = jax.jit(wd._prefix_pairs)(v)
    assert np.array_equal(np.asarray(hi, np.float64) + np.asarray(lo),
                          np.cumsum(np.asarray(v, np.float64)))


@pytest.mark.parametrize("query", [
    "#window.time(3 sec) select symbol, avg(price) as a, max(price) as hi "
    "group by symbol insert into O;",
    "#window.length(1000) select sum(price) as s, count() as c insert into O;",
    "#window.lengthBatch(700) select symbol, sum(price) as s, min(price) as lo "
    "group by symbol insert into O;",
], ids=["grouped_time", "length", "grouped_length_batch"])
def test_a_step_scanned_in_rows_delivers_the_same_rows(query, monkeypatch):
    """The whole step with its scans laid out as rows (as it is from 2,048
    entries on) against the step with every scan a 1-D `associative_scan`
    (as it was until PR 49): [carry | batch] of 3,072 to 6,144 entries,
    quarter-step prices (every sum exact, so the order of the additions
    cannot show), the same bits on every row."""
    def run():
        d = Driven(query)
        rng = np.random.default_rng(7)
        rows = []
        for n in (2048, 2048, 1500):
            price = np.round(rng.uniform(90, 130, n) * 4) / 4
            rows.append(d.send(price, symbol=rng.integers(0, 5, n).astype(
                np.int32)))
        d.close()
        return rows
    in_rows = run()
    monkeypatch.setattr(wd, "_scan", jax.lax.associative_scan)
    plain = run()
    assert any(r is not None for r in plain)
    for a, b in zip(plain, in_rows):
        assert (a is None) == (b is None)
        if a is not None:
            assert sorted(a) == sorted(b)
            assert all(a[k].tobytes() == b[k].tobytes() for k in a)


def test_order_by_words_is_the_stable_lexsort():
    rng = np.random.default_rng(3)
    n = 4000
    a = rng.integers(-3, 3, n).astype(np.int32)
    b = rng.integers(-2 ** 40, 2 ** 40, n) * rng.integers(0, 2, n)
    c = rng.integers(0, 4, n).astype(np.int64) - 2
    words = wd._words(jnp.asarray(a)) + wd._words(jnp.asarray(b)) \
        + wd._words(jnp.asarray(c))
    assert [w.dtype for w in words] == [jnp.uint32] * 5
    order = np.asarray(wd._order_by_words(words))
    # grouped as the keys group, arrival order kept inside a group
    key = np.stack([a, b, c], 1)[order]
    change = np.flatnonzero((key[1:] != key[:-1]).any(1)) + 1
    groups = np.split(order, change)
    assert len(groups) == len(np.unique(np.stack([a, b, c], 1), axis=0))
    assert all((np.diff(g) > 0).all() for g in groups)
    # and as words compare (unsigned), it IS the lexsort
    as_u = [np.asarray(w) for w in words]
    assert np.array_equal(order, np.lexsort(as_u[::-1]))


# -- the clock's left edge on 32-bit offsets ---------------------------------------

def _clock(carry, batch, pads, empty=0):
    """[empty carry slots | carry | batch | pads] as a step's `all_ts`
    (monotone), with the positions of the batch's first and last valid
    entries."""
    ts = np.concatenate([np.full(empty, -2 ** 62), carry, batch,
                         np.full(pads, 2 ** 62)]).astype(np.int64)
    first = empty + len(carry)
    return ts, first, max(first + len(batch) - 1, 0)


T0 = 1_700_000_000_000
DAY = 86_400_000
_CLOCKS = {
    # a minute of stream a ms apart: every edge within the range
    "dense": (_clock(T0 + np.arange(300), T0 + 300 + np.arange(200), 12, 8),
              100, "narrow"),
    # carry entries a month old, long expired, clamp to the range's floor
    "old_carry": (_clock(T0 - 40 * DAY + np.arange(50),
                         T0 + np.arange(0, 5000, 50), 4, 2),
                  600_000, "narrow"),
    # the oldest batch entry and its edge EXACTLY 2^31 - 1 ms behind
    "at_the_limit": (_clock(T0 - 2 ** 31 - 4000 + np.arange(5) * 1000,
                            [T0 - (2 ** 31 - 1) + 1000, T0 - 7, T0], 0),
                     1000, "narrow"),
    # one ms further: the batch spans too far for 32 bits
    "past_the_limit": (_clock(T0 - 2 ** 31 - 4000 + np.arange(5) * 1000,
                              [T0 - (2 ** 31 - 1) + 999, T0 - 7, T0], 0),
                       1000, "wide"),
    "batch_of_a_month": (_clock(T0 - 40 * DAY + np.arange(9) * DAY,
                                T0 - 30 * DAY + np.arange(31) * DAY, 3),
                         2 * DAY, "wide"),
    # equal timestamps: an edge lies after ALL of a run of equals
    "ties": (_clock([T0, T0, T0 + 5, T0 + 5],
                    [T0 + 5, T0 + 10, T0 + 10, T0 + 15], 2, 1),
             5, "narrow"),
    # no valid batch entry: nothing is read, nothing must fail
    "empty_batch": (_clock(T0 + np.arange(10), [], 6, 2), 100, "narrow"),
    # a window longer than 32 bits of ms hold: never narrowed
    "a_long_window": (_clock(T0 + np.arange(100), T0 + 100 + np.arange(28),
                             0), 30 * DAY, "wide"),
}


@pytest.mark.parametrize("case", list(_CLOCKS))
def test_the_clocks_left_edge_on_32_bits_is_the_i64_search(case, monkeypatch):
    """`_clock_left`, asked for the batch's rows alone (positions `first`..,
    the step's static C), is at every valid one of them
    `searchsorted(all_ts, all_ts - D, "right")`, whichever form the batch's
    span lets it take; and it takes the narrow one exactly while the oldest
    valid batch entry's edge lies within 2^31 - 1 ms of the newest."""
    (ts, first, last), D, form = _CLOCKS[case]
    assert (np.diff(ts) >= 0).all()
    took = []
    cond = jax.lax.cond

    def spy(pred, narrow, wide):
        took.append("narrow" if bool(pred) else "wide")
        return cond(pred, narrow, wide)
    monkeypatch.setattr(jax.lax, "cond", spy)
    left = np.asarray(wd._clock_left(jnp.asarray(ts), D, first, last))
    want = np.searchsorted(ts, ts - D, side="right")[first:]
    live = max(last + 1 - first, 0)
    assert left.shape == want.shape         # the batch's rows, no more
    assert np.array_equal(left[:live], want[:live])
    assert took == ([] if D > 2 ** 31 - 1 else [form])
    monkeypatch.undo()
    # under jit, as the step traces it
    jitted = jax.jit(lambda a, l: wd._clock_left(a, D, first, l))
    left = np.asarray(jitted(jnp.asarray(ts), last))
    assert np.array_equal(left[:live], want[:live])
