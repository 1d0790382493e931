"""Device window-aggregation plans: differential equality against the
sequential host interpreter on randomized streams (the device kernel's
claim is exact reference semantics — SURVEY §4 differential strategy)."""
import random

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.window_device import DeviceWindowAggPlan


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
    # 8 -> 16 -> 32 -> 64: three grows, the one flush re-run each time
    assert rec == {**want, "T": 64, "carry_capacity": 64,
                   "carry_overflow_reruns": 3, "carry_grows": 3}
    assert rt.statistics()["device"]["q"]["window"] == rec
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
