"""The cut of a fused multi-query flush (pattern_plan.py `_fused_cut`,
`FUSED_ROW_WINDOWS`, `FUSED_ROW_MIN`; its decode: lane_grid.py
`ResultDecoder.cut`): every fused lane sees the ONE shared stream, so a flush longer than a row is laid out once as rows `[the last
within-window | new events]` by `_cut_rows`' rule, each row a flush boundary
the rules never saw, and the block runs over rows x lanes with event grids
that vary by row only and lifted constants that vary by lane only.  Held
here against the host interpreter (`@app:devicePatterns('never')`) and, for
the benchmark's shapes, against the plain reference
`benchmark/reference/rules_mixed.matches`; with the routing of
`multi_query.finalize`, the seq family's exit for spent single-arm groups,
and the spans and EXPLAIN record that say what ran.

Which test forces the cut how:
  * `test_cut_by_shape_*` leave the constants as they are and send flushes
    longer than a row of `FUSED_ROW_MIN` (1024) events: the shapes the chip
    compiles, on one device and on a mesh of four;
  * the sweeps lower the module constants by `monkeypatch` (the cut reads
    them at each flush), so that short flushes are many rows.
"""
import os
import sys
import warnings

import numpy as np
import pytest

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import rules_mixed               # noqa: E402
from siddhi_tpu import SiddhiManager                      # noqa: E402
from siddhi_tpu.core import lane_grid, pattern_plan       # noqa: E402
from siddhi_tpu.core.multi_query import MultiQueryDevicePatternPlan  # noqa: E402
from tests.test_lane_decode import masked_rows            # noqa: E402

T0 = 1_700_000_000_000
STREAM = "define stream S (symbol string, price double, volume int);\n"
HOST = "@app:devicePatterns('never')\n"
Q = {"rules": 12, "streams": 4, "lo_base": 123, "lo_mod": 6,
     "within_ms": {"0": 1000, "3": 2000}, "for_ms": 500,
     "absent_head_above_lo": 1, "absent_below_lo": 30}

# a rule of each kind the fused scan path takes, `{lo}` its lifted constant
RULES = {
    0: "from every e1=S[price > {lo}] -> e2=S[price > e1.price] "
       "within 1 sec select e1.price as p1, e2.price as p2",
    3: "from every e1=S[price > {lo}] -> e2=S[price > e1.price] -> "
       "e3=S[price > e2.price] within 2 sec "
       "select e1.price as p1, e3.price as p3",
    "count": "from every e1=S[price > {lo}] -> e2=S[price > 112]<2:4> "
             "-> e3=S[price < 96] within 1 sec "
             "select e1.price as p1, e2[last].price as p2",
    "logical": "from every e1=S[price > {lo}] -> e2=S[price < 100] and "
               "e3=S[price > 125] within 1 sec select e1.price as p1",
}
# the single-arm rules of the deployment: the seq family
SINGLE = {
    1: "from e1=S[price > {lo}], e2=S[price > e1.price] "
       "select e1.price as p1, e2.price as p2",
    2: "from e1=S[price > {lo1}] -> not S[price < {lo30}] "
       "for 500 milliseconds select e1.price as p1",
}


def app_of(kind, n=12, streams=4):
    """`n` rules of one kind, rule i with lo = 123 + i % 6 into Out<i % streams>
    (rules_mixed.rule_of's constants)."""
    text = {**RULES, **SINGLE}[kind]
    parts = ["@app:playback\n" + STREAM]
    for i in range(n):
        lo = Q["lo_base"] + i % Q["lo_mod"]
        parts.append(f"@info(name='q{i}') "
                     + text.format(lo=lo, lo1=lo + 1, lo30=lo - 30)
                     + f" insert into Out{i % streams};")
    return "\n".join(parts) + "\n"


def tape(seed, n, flushes, dt=50, regress=False):
    """`flushes` batches of `n` events, quarter-step prices in [90, 130],
    `dt` ms apart; `regress`: a third of the timestamps fall back by up to
    four events' time."""
    rng = np.random.default_rng(seed)
    out = []
    for f in range(flushes):
        ts = T0 + (f * n + np.arange(n, dtype=np.int64)) * dt
        if regress:
            ts = ts - rng.integers(1, 4 * dt, n) * (rng.random(n) < 1 / 3)
        out.append({"price": 90 + 0.25 * rng.integers(0, 161, n).astype(
            np.float64), "ts": ts})
    return out


def run(head, app, batches, streams=4, stats=False):
    """(per stream the delivered batches as lists of rows, the runtime's
    EXPLAIN, the fused plans, stage statistics) after one send_batch +
    flush a batch."""
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(head + app)
    got = [[] for _ in range(streams)]
    for j in range(streams):
        rt.add_batch_callback(f"Out{j}", lambda b, g=got[j]: g.append(
            [(int(t),) + tuple(round(float(b.columns[c][i]), 3)
                               for c in sorted(b.columns))
             for i, t in enumerate(b.timestamps)]))
    if stats:
        rt.enable_stats()
    rt.start()
    code = rt.strings.encode("K0")
    for b in batches:
        n = len(b["ts"])
        rt.input_handler("S").send_batch(
            {"symbol": np.full(n, code, np.int32), "price": b["price"],
             "volume": np.ones(n, np.int32)}, b["ts"])
        rt.flush()
    plans = [p for p in rt._plans
             if isinstance(p, MultiQueryDevicePatternPlan)]
    ex = rt.explain()
    st = rt.statistics() if stats else None
    mgr.shutdown()
    return got, ex, plans, st


def flat(got):
    return [sorted(r for batch in g for r in batch) for g in got]


def owed(kind, batches):
    """What `rules_mixed.matches` owes each of the 4 streams for 12 rules
    of one of the deployment's shapes."""
    price = np.concatenate([b["price"] for b in batches])
    ts = np.concatenate([b["ts"] for b in batches])
    out = [[] for _ in range(4)]
    for i in range(12):
        r = rules_mixed.rule_of(i, Q)
        m = rules_mixed.matches(price, ts, {"shape": kind, "lo": r["lo"]}, Q)
        cols = [m["p1"]] if kind == 2 else [m["p1"], m["p2"]]
        out[i % 4] += [(int(t),) + tuple(round(float(c[k]), 3) for c in cols)
                       for k, t in enumerate(m["ts"])]
    return [sorted(o) for o in out]


@pytest.fixture
def rows64(monkeypatch):
    """The module constants lowered: rows of two replay windows, at least
    16 events (64 events for `within 1 sec` at 50 ms an event)."""
    monkeypatch.setattr(pattern_plan, "FUSED_ROW_WINDOWS", 2)
    monkeypatch.setattr(pattern_plan, "FUSED_ROW_MIN", 16)


def _assert_cut(fused, flushes, C):
    cut = fused["lane_cut"]
    assert cut["cut_length"] == C and cut["flushes_cut"] == flushes, cut
    assert cut["flushes_uncuttable"] == 0 and cut["rows"] > flushes
    assert cut["events_replayed"] > 0
    assert fused["first_hit"]["F"] == C and fused["first_hit"]["tree"] == 0
    assert fused["indexed_read"]["gather"] == 0
    assert fused["first_hit"]["dense"] > 0
    assert fused["compaction"]["dense"] > 0
    assert fused["compaction"]["scatter"] == 0 and fused["compaction"]["F"] == C


# -- forced by SHAPE: the constants as they stand -------------------------------

@pytest.mark.parametrize("kind", [0, 3])
def test_cut_by_shape_equals_interpreter_and_reference(kind):
    C = pattern_plan.FUSED_ROW_MIN
    assert 8 * 41 <= C <= lane_grid.LANE_CUT     # the floor sets the row
    batches = tape(7, 2600, 3)
    host, _e, _p, _s = run(HOST, app_of(kind), batches)
    dev, ex, plans, _s = run("@app:deviceMesh('never')\n", app_of(kind),
                             batches)         # one device, as the cell
    assert len(plans) == 1 and plans[0].inner.family == "scan"
    assert plans[0].inner.mesh is None and plans[0].inner.P == 12
    assert flat(dev) == flat(host) == owed(kind, batches)
    assert sum(map(len, flat(dev))) > 1000
    fused = ex["queries"][plans[0].name]["fused"]
    assert fused["queries"] == 12 and fused["family"] == "scan"
    _assert_cut(fused, 3, C)
    assert plans[0].device_metrics()["fused"] == fused
    # the block's lane axis is rows x lanes
    lane_rows = plans[0].inner._fused_R * plans[0].inner.P
    assert fused["first_hit"]["lanes"] == lane_rows >= 3 * 12
    assert plans[0].device_metrics()["lanes_last_dispatch"] == lane_rows


def test_cut_by_shape_on_four_virtual_devices(monkeypatch):
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
    batches = tape(8, 2200, 2)
    host, _e, _p, _s = run(HOST, app_of(0), batches)
    dev, ex, plans, _s = run("@app:deviceMesh('always')\n", app_of(0),
                             batches)
    inner = plans[0].inner
    assert inner.mesh is not None and inner.mesh.devices.size == 4
    assert flat(dev) == flat(host) == owed(0, batches)
    _assert_cut(ex["queries"][plans[0].name]["fused"], 2,
                pattern_plan.FUSED_ROW_MIN)


# -- the constants lowered: the sweeps ----------------------------------------------

@pytest.mark.parametrize("regress", [False, True],
                         ids=["in_order", "a_third_regressed"])
@pytest.mark.parametrize("kind", list(RULES))
def test_every_fused_shape_cut_equals_interpreter(rows64, kind, regress):
    batches = tape(21, 400, 4, regress=regress)
    host, _e, _p, _s = run(HOST, app_of(kind), batches)
    dev, ex, plans, _s = run("", app_of(kind), batches)
    assert len(plans) == 1 and plans[0].inner.family in ("scan", "dfa")
    assert flat(dev) == flat(host)
    assert sum(map(len, flat(dev))) > 20
    if kind in (0, 3) and not regress:
        assert flat(dev) == owed(kind, batches)
    cut = ex["queries"][plans[0].name]["fused"]["lane_cut"]
    assert cut["flushes_cut"] == 4 and cut["rows"] >= 12, cut
    assert cut["cut_length"] <= 128


@pytest.mark.parametrize("lead", range(7))
def test_a_cut_on_every_phase_of_a_pending_chain(monkeypatch, lead):
    """The stream cycles through e1, filler, e2, filler, e3, filler, filler
    (124, 126, 128 open and extend rising chains; 90 does neither); `lead`
    fillers in front move the row boundaries over every phase of the cycle,
    so some boundary falls before, on and after each position of a chain
    that is pending across it."""
    monkeypatch.setattr(pattern_plan, "FUSED_ROW_WINDOWS", 2)
    monkeypatch.setattr(pattern_plan, "FUSED_ROW_MIN", 16)
    cycle = np.array([124.0, 90.0, 126.0, 90.0, 128.0, 90.0, 90.0])
    n = 150
    price = np.r_[np.full(lead, 90.0), np.tile(cycle, n // 7 + 1)][:n]
    app = app_of(3).replace("within 2 sec", "within 400 milliseconds")
    batches = [{"price": price, "ts": T0 + (f * n + np.arange(n)) * 50}
               for f in range(2)]
    host, _e, _p, _s = run(HOST, app, batches)
    dev, ex, plans, _s = run("", app, batches)
    assert flat(dev) == flat(host) and sum(map(len, flat(dev))) > 30
    cut = ex["queries"][plans[0].name]["fused"]["lane_cut"]
    assert cut["flushes_cut"] == 2 and cut["cut_length"] == 32, cut
    assert cut["rows"] >= 10 and cut["flushes_uncuttable"] == 0


def test_a_flush_whose_window_overfills_a_row_is_not_cut(monkeypatch):
    """A row of ONE replay window: `within 1 sec` at 34 ms an event is 30
    events, the row 32, which leaves 2 cells for new events, under a
    quarter of it.  The flush keeps the flat form (one lane of the whole
    flush), is counted, and is exact."""
    monkeypatch.setattr(pattern_plan, "FUSED_ROW_WINDOWS", 1)
    monkeypatch.setattr(pattern_plan, "FUSED_ROW_MIN", 16)
    batches = tape(3, 300, 3, dt=34)
    host, _e, _p, _s = run(HOST, app_of(0), batches)
    dev, ex, plans, _s = run("", app_of(0), batches)
    assert flat(dev) == flat(host) == owed(0, batches)
    fused = ex["queries"][plans[0].name]["fused"]
    cut = fused["lane_cut"]
    assert cut["flushes_uncuttable"] >= 2 and cut["flushes_cut"] <= 1, cut
    assert fused["first_hit"]["lanes"] == plans[0].inner.P   # not rows x lanes


def test_a_flush_within_a_row_is_not_cut_and_counts_nothing():
    batches = tape(5, 200, 3)
    dev, ex, plans, _s = run("", app_of(0), batches)
    fused = ex["queries"][plans[0].name]["fused"]
    assert fused["lane_cut"] == dict(flushes_cut=0, rows=0,
                                     flushes_uncuttable=0,
                                     events_replayed=0, cut_length=0)
    assert flat(dev) == owed(0, batches)


# -- what goes up and what comes back ----------------------------------------------

def test_the_event_grid_goes_up_once_not_once_a_lane():
    """H2D of a cut flush: three 4-byte grids of (rows, C) cells, two i32 a
    row, one constant and one qid a lane: the grids are the same whatever
    the number of rules; replicated they would be a grid a lane."""
    from siddhi_tpu.core.telemetry import env_nbytes
    seen = []
    orig = pattern_plan.DevicePatternPlan._dispatch_par

    def spy(self, ev, F, M, *a, **kw):
        seen.append((env_nbytes(ev), kw.get("rows"), F, self.P))
        return orig(self, ev, F, M, *a, **kw)
    pattern_plan.DevicePatternPlan._dispatch_par = spy
    try:
        batches = tape(9, 2500, 1)
        for n in (12, 48):
            run("", app_of(0, n=n), batches)
    finally:
        pattern_plan.DevicePatternPlan._dispatch_par = orig
    (few, rows, C, p_few), (many, rows_many, _c, p_many) = seen
    assert rows == rows_many and rows and p_many > p_few
    assert C == pattern_plan.FUSED_ROW_MIN
    grids = 3 * 4 * rows * C
    assert grids <= few <= grids + 8 * rows + 8 * p_few + 64
    assert many - few == 8 * (p_many - p_few)   # a constant and a qid a lane


def test_capacity_follows_the_counts_and_an_overflow_is_exact():
    """The first cut flush runs at M = F; the next at twice the fullest
    lane-row's count in 128s; a later flush that overflows it is run again,
    exactly, at a capacity that holds it."""
    quiet = tape(11, 2400, 2)
    for b in quiet:
        b["price"] = np.where(b["price"] > 123, 100.0, b["price"])
        b["price"][::50] = 125.0
        b["price"][1::50] = 126.0
    busy = {"ts": quiet[-1]["ts"] + 2400 * 50,
            "price": np.tile([124.0, 126.0, 128.0, 130.0], 600)}
    batches = quiet + [busy]
    host, _e, _p, _s = run(HOST, app_of(0), batches)
    dev, _ex, plans, _s = run("", app_of(0), batches)
    assert flat(dev) == flat(host) == owed(0, batches)
    inner = plans[0].inner
    assert inner._fused_C == 1024 and 128 < inner._fused_M <= 1024 + 512
    assert inner._family_dispatches["scan"] == 4    # three flushes, a re-run


# -- routing -------------------------------------------------------------------

def _mask_form(plan, outs):
    """`finalize`'s routing as it was before this change: one lexsort by
    (completion, head), then a full-length mask a rule."""
    tss, seqs, hseqs, data, qids = outs
    order = np.lexsort((hseqs, seqs))
    tss, seqs, qids = tss[order], seqs[order], qids[order]
    data = {k: v[order] for k, v in data.items()}
    res = []
    for qi in np.unique(qids):
        m = qids == qi
        res.append((plan.targets[int(qi)], plan.query_names[int(qi)],
                    tss[m], seqs[m],
                    {nm: data[src][m] for nm, src in zip(
                        plan.per_q_names[int(qi)], plan.inner._names)}))
    return res


def test_routing_equals_the_mask_form_row_for_row():
    rng = np.random.default_rng(4)
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(app_of(0, n=250, streams=16))
    plan = next(p for p in rt._plans
                if isinstance(p, MultiQueryDevicePatternPlan))
    n = 20000
    seqs = rng.integers(1000, 1400, n).astype(np.int64)
    outs = (T0 + seqs * 50, seqs,
            (seqs - rng.integers(1, 20, n)).astype(np.int32),
            {nm: rng.random(n) for nm in plan.inner._names},
            rng.integers(0, 250, n).astype(np.int32))
    plan.inner.finalize_multi = lambda: outs
    got = plan.finalize()
    del plan.inner.finalize_multi
    want = _mask_form(plan, outs)
    assert len(got) == len(want) == 250
    for ob, (target, name, tss, sq, cols) in zip(got, want):
        assert (ob.target, ob.callback_name) == (target, name)
        assert ob.batch.n == len(tss)
        assert np.array_equal(ob.batch.timestamps, tss)
        assert np.array_equal(ob.batch.seqs, sq)
        assert list(ob.batch.columns) == list(cols)
        for nm in cols:
            assert np.array_equal(ob.batch.columns[nm], cols[nm])
    mgr.shutdown()


def test_rows_of_one_rule_arrive_in_the_order_of_their_last_event(rows64):
    dev, _ex, _p, _s = run("", app_of(0), tape(13, 600, 2))
    batches = [b for g in dev for b in g]
    assert len(batches) >= 20        # a batch is one rule's rows of a flush
    for b in batches:
        assert [r[0] for r in b] == sorted(r[0] for r in b)


# -- the decode of a cut flush's result ---------------------------------------------

TYPED = ("@app:playback\n@app:devicePrecision('f64')\n"
         "define stream T (price double, vol long, qty int);\n")


def typed_app(n=10, head=""):
    """Rules whose outputs take every form the pack has: f64 in the `f`
    pack, an i64 hi / lo pair, an i32 word, a BOOL."""
    return head + TYPED + "".join(
        f"@info(name='q{i}') from every e1=T[price > {123 + i % 6}] -> "
        f"e2=T[price > e1.price] within 1 sec select e1.price as p1, "
        f"e2.vol as v, e2.qty as q, e2.price > e1.price + 1.0 as hot, "
        f"e2.price as p2 insert into Out{i % 4};\n" for i in range(n))


def fused_plan(app):
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(app)
    plan, = [p for p in rt._plans
             if isinstance(p, MultiQueryDevicePatternPlan)]
    return mgr, plan


def packed(plan, rng, counts, M, row_events=1000, seqs_of=None):
    """A cut fused flush's result as the block packs it, (rows, lanes,
    words, M) i32 (+ f64 `f` pack): `counts[r, l]` matches in lane-row
    (r, l), heads rising inside it, completions in row r's own range, a
    few events after the head (so many rows tie on one completion); every
    other word, and every cell past a count, random."""
    inner = plan.inner
    R, L = counts.shape
    words = inner.decoder.words
    n_i = 1 + sum(2 if dt == np.int64 else 1
                  for pack, _w, dt in words.values() if pack == "i")
    n_f = sum(pack == "f" for pack, _w, _dt in words.values())
    ipack = rng.integers(-2 ** 20, 2 ** 20, (R, L, n_i, M)).astype(np.int32)
    fpack = rng.random((R, L, n_f, M)) if n_f else None
    ipack[:, :, 0, :] = 0
    ipack[:, :, 0, 0] = counts
    at = {nm: words[nm][1] for nm in
          ("__timestamp__", "__seq__", "__head_seq__", "__qid__")}
    for r in range(R):
        for ln in range(L):
            c = min(int(counts[r, ln]), M)
            head = np.sort(rng.integers(r * row_events,
                                        (r + 1) * row_events, c))
            seq = np.minimum(head + rng.integers(0, 4, c) * 5,
                             (r + 1) * row_events - 1)
            if seqs_of is not None:
                head, seq = seqs_of(r, ln, head, seq)
            ipack[r, ln, at["__seq__"], :c] = seq
            ipack[r, ln, at["__timestamp__"], :c] = seq * 50
            ipack[r, ln, at["__head_seq__"], :c] = head
            ipack[r, ln, at["__qid__"], :] = ln
    return ipack, fpack


BASES = {"ts_base": T0, "seq_base": 7_000_000_000}


def new_form(plan, results, tick=()):
    """The production path from the pulled results on: `_materialize_par`
    (given numpy arrays where the device's would be), `_multi_table`,
    `_route`."""
    inner = plan.inner
    chunks = list(tick)
    for ipack, fpack in results:
        out = {"i": ipack} if fpack is None else {"i": ipack, "f": fpack}
        chunks.append(inner._materialize_par(
            {"out": out, "M": ipack.shape[-1], "L": ipack.shape[1],
             "R": ipack.shape[0], **BASES}))
    return plan._route(inner._multi_table(chunks))


def old_form(plan, results, tick=()):
    """The decode and the routing as they were before PR 41, kept as the
    reference: every word read under a capacity-sized mask (`masked_rows`,
    the partitioned plans' own decode until PR 43, kept in
    tests/test_lane_decode.py), one three-key lexsort, a slice a rule."""
    inner = plan.inner
    chunks = list(tick)
    for ipack, fpack in results:
        ipack = ipack[:, :inner._lanes_real]
        filled = np.arange(ipack.shape[-1]) < ipack[:, :, 0, 0][..., None]
        ip2 = [None] + [ipack[:, :, r, :][filled]
                        for r in range(1, ipack.shape[2])]
        fp2 = None if fpack is None else [
            fpack[:, :inner._lanes_real, r, :][filled]
            for r in range(fpack.shape[2])]
        chunks.append(masked_rows(inner, ip2, fp2,
                                  np.ones(len(ip2[1]), bool), **BASES))
    chunks = [c for c in chunks if c is not None]
    tss, seqs, hseqs, qids = (np.concatenate([c[k] for c in chunks])
                              for k in (0, 1, 2, 5))
    data = {nm: np.concatenate([c[3][nm] for c in chunks])
            for nm in inner._names}
    order = np.lexsort((hseqs, seqs, qids))
    tss, seqs, qids = tss[order], seqs[order], qids[order]
    data = {k: v[order] for k, v in data.items()}
    starts = np.flatnonzero(np.r_[True, qids[1:] != qids[:-1]])
    res = []
    for a, b in zip(starts.tolist(), np.r_[starts[1:], len(qids)].tolist()):
        qi = int(qids[a])
        res.append((plan.targets[qi], plan.query_names[qi], tss[a:b],
                    seqs[a:b], {nm: data[src][a:b] for nm, src in zip(
                        plan.per_q_names[qi], inner._names)}))
    return res


def assert_same_batches(got, want):
    assert len(got) == len(want)
    for ob, (target, name, tss, sq, cols) in zip(got, want):
        assert (ob.target, ob.callback_name) == (target, name)
        assert ob.batch.n == len(tss)
        for mine, theirs in ((ob.batch.timestamps, tss), (ob.batch.seqs, sq)):
            assert mine.dtype == theirs.dtype
            assert np.array_equal(mine, theirs)
        assert list(ob.batch.columns) == list(cols)
        for nm in cols:
            assert ob.batch.columns[nm].dtype == cols[nm].dtype, nm
            assert np.array_equal(      # random words are NaNs as well
                ob.batch.columns[nm], cols[nm],
                equal_nan=cols[nm].dtype.kind == "f"), nm


def _restart(r, ln, head, seq):
    """Unstamped batches: every row's seqs start again, so two rows of a
    lane hold the same completions and the later row's heads come first."""
    return head % 1000, seq % 1000


def _wide(r, ln, head, seq):
    """Completions across 2^28 seqs: 12 lanes x the span is past 31 bits."""
    return head, seq + r * (1 << 26)


def _heads_fall(r, ln, head, seq):
    """A final-count burst: a head's rows come out of head order."""
    return head[::-1], np.full_like(seq, seq.max(initial=0))


# name -> (app, counts(rng, R, L, M), seqs_of, the order it must take)
DECODES = {
    "uniform": (None, lambda rng, R, L, M: rng.integers(0, M // 2, (R, L)),
                None, "keyed"),
    "mostly_empty_lane_rows": (
        None, lambda rng, R, L, M: rng.integers(0, M // 2, (R, L))
        * (rng.random((R, L)) < 0.2), None, "keyed"),
    "a_lane_row_at_capacity": (
        None, lambda rng, R, L, M: np.where(
            rng.random((R, L)) < 0.3, M, rng.integers(0, 3, (R, L))),
        None, "keyed"),
    "one_rule_holds_every_row": (
        None, lambda rng, R, L, M: rng.integers(1, M, (R, L))
        * (np.arange(L) == 7), None, "keyed"),
    "one_row_in_all": (
        None, lambda rng, R, L, M: (np.arange(R * L).reshape(R, L) == 17)
        .astype(np.int64), None, "keyed"),
    "f64_i64_i32_bool_outputs": (
        typed_app(), lambda rng, R, L, M: rng.integers(0, M, (R, L)),
        None, "keyed"),
    "lanes_padded_by_a_mesh": (
        typed_app(10, "@app:deviceMesh('always')\n"),
        lambda rng, R, L, M: rng.integers(0, M, (R, L)), None, "keyed"),
    "seqs_that_restart": (
        None, lambda rng, R, L, M: rng.integers(M // 2, M, (R, L)),
        _restart, "lexsort"),
    "a_span_past_the_key": (
        None, lambda rng, R, L, M: rng.integers(0, M // 2, (R, L)),
        _wide, "lexsort"),
    "heads_out_of_order_in_a_cell": (
        None, lambda rng, R, L, M: rng.integers(2, M // 2, (R, L)),
        _heads_fall, "lexsort"),
}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("case", list(DECODES))
def test_the_decode_equals_the_mask_form_row_for_row(case, seed):
    app, counts_of, seqs_of, order = DECODES[case]
    mgr, plan = fused_plan(app or app_of(0))
    inner = plan.inner
    rng = np.random.default_rng(100 * seed + len(case))
    R, M = 6, 32
    counts = np.zeros((R, inner.P), np.int64)
    counts[:, :inner._lanes_real] = counts_of(rng, R, inner._lanes_real, M)
    if "mesh" in case:
        if inner._lanes_real == inner.P:
            pytest.skip("needs a mesh that pads the lane axis")
        counts[:, inner._lanes_real:] = M + 5   # nobody's, and no overflow
    ipack, fpack = packed(plan, rng, counts, M, seqs_of=seqs_of)
    got = new_form(plan, [(ipack, fpack)])
    assert_same_batches(got, old_form(plan, [(ipack, fpack)]))
    assert sum(ob.batch.n for ob in got) \
        == counts[:, :inner._lanes_real].sum() > 0
    fused = plan.fused
    assert fused["result_decode"] == {"indexed": 1}
    assert fused["route_order"] == {
        "keyed": int(order == "keyed"), "lexsort": int(order == "lexsort")}
    mgr.shutdown()


def test_the_decode_reads_a_result_in_whatever_axis_order_it_was_laid():
    """The pulled array's strides are the device's to choose (on the chip
    the partitioned result comes words-major): the index follows them."""
    mgr, plan = fused_plan(typed_app())
    rng = np.random.default_rng(5)
    counts = rng.integers(0, 24, (4, plan.inner.P))
    ipack, fpack = packed(plan, rng, counts, 24)
    want = old_form(plan, [(ipack, fpack)])
    for axes in ((2, 0, 1, 3), (1, 0, 3, 2), (3, 2, 1, 0)):
        back = np.argsort(axes)
        laid = [np.ascontiguousarray(a.transpose(axes)).transpose(back)
                for a in (ipack, fpack)]
        assert laid[0].shape == ipack.shape
        assert not laid[0].flags.c_contiguous
        assert_same_batches(new_form(plan, [laid]), want)
    mgr.shutdown()


def test_an_index_past_the_result_raises_and_reads_no_neighbour(monkeypatch):
    """The decode's takes clip (they write into scratch), so what
    `mode="raise"` would have checked is checked once a result: strides
    that put a cell outside the pack stop the flush."""
    mgr, plan = fused_plan(app_of(0))
    rng = np.random.default_rng(3)
    result = packed(plan, rng, rng.integers(1, 16, (4, plan.inner.P)), 16)
    flat_words = lane_grid._flat_words

    def doubled(a):
        flat, strides = flat_words(a)
        return flat, tuple(2 * s for s in strides)
    monkeypatch.setattr(lane_grid, "_flat_words", doubled)
    with pytest.raises(IndexError, match="decode index past the result"):
        new_form(plan, [result])
    mgr.shutdown()


@pytest.mark.parametrize("tick_first", [True, False])
def test_a_tick_chunk_beside_the_cut_result_is_one_batch_a_rule(tick_first):
    """A flush of several chunks: the rows a timer tick produced (a flat
    table, decoded when it was pulled) and the cut result's join into one
    table, ordered as one: a rule still gets ONE batch."""
    mgr, plan = fused_plan(app_of(0))
    inner = plan.inner
    rng = np.random.default_rng(8)
    counts = rng.integers(0, 16, (5, inner.P))
    result = packed(plan, rng, counts, 32)
    n = 40
    seqs = np.sort(rng.integers(0, 900, n)) + BASES["seq_base"]
    tick = (T0 + seqs, seqs, (seqs - BASES["seq_base"] - 3).astype(np.int32),
            {nm: rng.random(n) for nm in inner._names}, {},
            rng.integers(0, plan.n_queries, n).astype(np.int32))
    ticks = [tick] if tick_first else []
    got = new_form(plan, [result], tick=ticks)
    assert_same_batches(got, old_form(plan, [result], tick=ticks))
    assert len({ob.callback_name for ob in got}) == len(got)
    fused = plan.fused
    # (the tick's table is handed in ready made: no decode of it here)
    assert fused["result_decode"] == {"indexed": 1}
    assert fused["route_order"] == {"keyed": 1, "lexsort": 0}
    # two cut results in one collect (a drained pipeline) join the same way
    got = new_form(plan, [result, result])
    assert_same_batches(got, old_form(plan, [result, result]))
    mgr.shutdown()


def test_delivered_memory_is_the_batch_s_own():
    """Flush n's arrays are kept (a callback may); flushes n+1 (larger)
    and n+2 (smaller) decode through the same scratch: the kept arrays
    stay as they were, and nothing delivered is the plan's scratch or the
    pulled result."""
    mgr, plan = fused_plan(typed_app())
    inner = plan.inner
    rng = np.random.default_rng(12)
    kept = []
    for hi in (12, 32, 4):
        ipack, fpack = packed(
            plan, rng, rng.integers(0, hi, (6, inner.P)), 32)
        obs = new_form(plan, [(ipack, fpack)])
        arrays = [a for ob in obs for a in (
            ob.batch.timestamps, ob.batch.seqs, *ob.batch.columns.values())]
        scratch = [*inner.decoder.scratch._bufs.values()]
        assert len(scratch) > 8
        for a in arrays:
            assert not any(np.shares_memory(a, b)
                           for b in (*scratch, ipack, fpack))
        kept.append((arrays, [a.copy() for a in arrays]))
        # the flat form's batches too (the order it gathers through is
        # scratch)
        n = 50
        seqs = np.sort(rng.integers(0, 900, n)) + BASES["seq_base"]
        flat = plan._route((T0 + seqs, seqs, (seqs % 7).astype(np.int32),
                            {nm: rng.random(n) for nm in inner._names},
                            rng.integers(0, plan.n_queries, n)
                            .astype(np.int32)))
        for ob in flat:
            for a in (ob.batch.timestamps, ob.batch.seqs,
                      *ob.batch.columns.values()):
                assert not any(np.shares_memory(a, b) for b in scratch)
    for arrays, copies in kept:
        for a, c in zip(arrays, copies):
            assert np.array_equal(a, c)
    mgr.shutdown()


# -- the seq family: single arms that are spent ----------------------------------

@pytest.mark.parametrize("kind", [1, 2])
def test_a_spent_seq_group_is_skipped_and_exact(kind):
    """Rules without `every` fire or die once.  When every lane's arm is
    spent the group stops dispatching; later batches change nothing, as on
    the interpreter and in the reference."""
    batches = tape(17, 300, 4)
    host, _e, _p, _s = run(HOST, app_of(kind), batches)
    dev, ex, plans, _s = run("", app_of(kind), batches)
    assert plans[0].inner.family == "seq"
    assert flat(dev) == flat(host) == owed(kind, batches)
    fused = ex["queries"][plans[0].name]["fused"]
    assert fused["family"] == "seq" and fused["first_hit"] is None
    assert fused["arms_resolved"] == 12
    assert fused["dispatches_skipped"] == 3
    assert plans[0].inner._family_dispatches["seq"] == 1


def test_an_arm_with_a_deadline_outstanding_is_not_spent():
    """Shape 2's head sits in the last events of the first batch: its
    deadline is outstanding at the flush, so the group keeps dispatching
    until the wait has fired or died, and the row is delivered."""
    first = {"price": np.r_[np.full(295, 100.0), 129.5, np.full(4, 100.0)],
             "ts": T0 + np.arange(300) * 50}
    rest = [{"price": np.full(300, 100.0),
             "ts": T0 + (f * 300 + np.arange(300)) * 50} for f in (1, 2)]
    batches = [first] + rest
    host, _e, _p, _s = run(HOST, app_of(2), batches)
    dev, ex, plans, _s = run("", app_of(2), batches)
    assert flat(dev) == flat(host) == owed(2, batches)
    assert sum(map(len, flat(dev))) == 12
    fused = ex["queries"][plans[0].name]["fused"]
    assert fused["arms_resolved"] == 12 and fused["dispatches_skipped"] == 1


def test_a_violator_stamped_at_the_deadline_comes_too_late():
    """`not S[price < x] for 500 milliseconds` as the LAST position: the
    deadline fires before an event stamped exactly at it is looked at
    (the host's timers run first), so that event cannot kill the wait.
    The seq kernel used to let it."""
    price = np.r_[100.0, 129.5, np.full(9, 100.0), 91.0, np.full(8, 100.0)]
    batches = [{"price": price, "ts": T0 + np.arange(len(price)) * 50}]
    host, _e, _p, _s = run(HOST, app_of(2), batches)
    dev, _ex, _pl, _s = run("", app_of(2), batches)
    assert flat(dev) == flat(host) == owed(2, batches)
    assert sum(map(len, flat(dev))) == 12


# -- spans and EXPLAIN -------------------------------------------------------

def test_spans_route_and_lane_cut_and_the_fused_record():
    batches = tape(19, 2500, 2)
    _d, ex, plans, st = run("", app_of(0), batches, stats=True)
    stages = st["stages"]
    # a cut flush opens `route` twice: the order, then a slice a rule
    # around `scatter`, the fetch of the payload; `unpack` for the counts
    # and for the index over the filled cells and the key words
    assert stages["route"]["batches"] == 4
    assert stages["unpack"]["batches"] == 4
    delivered = sum(len(g) for g in _d)
    assert stages["scatter"]["batches"] == 2 + delivered
    assert stages["lane_cut"]["batches"] == 2
    assert stages["lane_cut"]["seconds"] <= stages["host_build"]["seconds"]
    ent = ex["queries"][plans[0].name]
    assert ent["kind"] == "multi_query" and "family" not in ent
    assert list(ent["fused"]) == [
        "queries", "padded_lanes", "family", "first_hit", "indexed_read",
        "compaction", "lane_cut", "arms_resolved", "dispatches_skipped",
        "result_decode", "route_order"]
    assert sorted(ent["fused"]["lane_cut"]) == [
        "cut_length", "events_replayed", "flushes_cut",
        "flushes_uncuttable", "rows"]
    assert ent["fused"]["result_decode"] == {"indexed": 2}
    assert ent["fused"]["route_order"] == {"keyed": 2, "lexsort": 0}
    assert plans[0].device_metrics()["fused"] == ent["fused"]
    # a flush within a row: the flat form, a lane result of its own, decoded
    # through the same index (no mask since PR 43), the same keyed order
    _d, ex, plans, st = run("", app_of(0), tape(19, 200, 2), stats=True)
    assert "lane_cut" not in st["stages"]
    assert st["stages"]["route"]["batches"] == 2
    fused = ex["queries"][plans[0].name]["fused"]
    assert fused["result_decode"] == {"indexed": 2}
    assert fused["route_order"] == {"keyed": 2, "lexsort": 0}
