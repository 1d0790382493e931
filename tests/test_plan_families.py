"""Plan-family differentials: the parallel-in-time NFA families (scan =
associative-scan SFA, dfa = bit-packed multi-stride hybrid) must be
byte-identical to the sequential device kernel AND the host interpreter
across the pattern matrix — and ineligible patterns must provably fall
back (the plan reports the family it actually engaged plus the
ineligibility reason for every rejected family).

The matrix reuses the chunked-halo corpus (tests/test_nfa_chunked.py
QUERIES: counts, logicals, sequences — all ineligible shapes that must
force-fall-back) plus eligible chains covering static, threshold, and
hybrid hops, multi-stream chains, having, and cross-flush tail replay
(many small flushes)."""
import warnings

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.pattern_plan import DevicePatternPlan

HEAD = "define stream S (sym string, price double, volume int);\n" \
       "@info(name='q') "

# forced-family matrix: "seq" is exercised by every other pattern suite
# (it is the default device kernel there) and by the ineligible-fallback
# tests below; "chunk" has its own differential corpus
# (test_nfa_chunked.py) and rides three representative shapes here —
# keeping both out of the full matrix saves ~17 kernel compiles of
# tier-1 budget without losing coverage
FAMILIES = ("scan", "dfa")
# chunk × {threshold2, static-chain} shapes are test_nfa_chunked.py's own
# corpus; one hybrid (static + threshold hops) run here suffices
CHUNK_SUBSET = ("hybrid",)

# eligible chains: family -> expected engagement under force
ELIGIBLE = {
    "threshold2": (
        "from every e1=S[price > 100] -> e2=S[price > e1.price] "
        "within 1 sec select e1.price as p1, e2.price as p2 "
        "insert into Out;",
        {"seq", "chunk", "scan"}),
    "threshold3": (
        "from every e1=S[price > 100] -> e2=S[price > e1.price] -> "
        "e3=S[price > e2.price] within 2 sec "
        "select e1.price as p1, e2.price as p2, e3.price as p3 "
        "insert into Out;",
        {"seq", "chunk", "scan"}),
    "static2": (
        "from every e1=S[price > 120] -> e2=S[price < 95] within 1 sec "
        "select e1.price as a, e2.price as b insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "static3": (
        "from every e1=S[price > 118] -> e2=S[price < 96] -> "
        "e3=S[price > 124] within 2 sec "
        "select e1.price as a, e2.price as b, e3.price as c "
        "insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "hybrid": (
        "from every e1=S[price > 110] -> e2=S[price < 100] -> "
        "e3=S[price > e1.price] within 2 sec "
        "select e1.price as a, e2.price as b, e3.price as c "
        "insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "cross_threshold": (
        "from every e1=S[price > 105] -> e2=S[volume > 500] -> "
        "e3=S[price < e1.price] within 2 sec "
        "select e1.price as a, e2.volume as b, e3.price as c "
        "insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "le_threshold": (
        "from every e1=S[price > 115] -> e2=S[price <= e1.price] "
        "within 1 sec select e1.price as a, e2.price as b "
        "insert into Out;",
        {"seq", "chunk", "scan"}),
    "having": (
        "from every e1=S[price > 110] -> e2=S[price < 100] within 1 sec "
        "select e1.price as a, e2.price as b "
        "having a - b > 15.0 insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "computed_sel": (
        "from every e1=S[price > 112] -> e2=S[price < 98] within 1 sec "
        "select e1.price * 2.0 as d, e2.volume as v insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "string_sel": (
        "from every e1=S[price > 112] -> e2=S[price < 98] within 1 sec "
        "select e1.sym as s1, e2.sym as s2, e2.price as p "
        "insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    # ---- the expanded algebra (ISSUE 13): counts, logical AND/OR,
    # strict sequences, and non-`every` single arms all lower onto the
    # rank/select + prev-scan state chase now
    "count_head": (
        "from every e1=S[price > 110]<1:3> -> e2=S[price < 95] "
        "within 1 sec select e1[0].price as a, e1[last].price as b, "
        "e2.price as c insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "count_mid": (
        "from every e1=S[price > 118] -> e2=S[price > 112]<2:4> -> "
        "e3=S[price < 96] within 2 sec select e1.price as a, "
        "e2[0].price as b, e2[last].price as c, e3.price as d "
        "insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "count_final": (
        "from every e1=S[price > 118] -> e2=S[price < 97]<2:3> "
        "within 1 sec select e1.price as a, e2[last].price as b "
        "insert into Out;",
        {"seq", "chunk", "scan"}),
    "logical_and": (
        "from every e1=S[price > 120] -> e2=S[price < 100] and "
        "e3=S[price > 125] within 1 sec "
        "select e1.price as a, e2.price as b, e3.price as c "
        "insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "logical_or": (
        "from every e1=S[price > 122] -> e2=S[price < 95] or "
        "e3=S[price > 126] within 1 sec "
        "select e1.price as a, e2.price as b, e3.price as c "
        "insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
    "sequence": (
        "from every e1=S[price > 115], e2=S[price > e1.price] "
        "within 1 sec select e1.price as a, e2.price as b "
        "insert into Out;",
        {"seq", "chunk", "scan"}),
    "sequence_conj": (
        "from every e1=S[price > 110], "
        "e2=S[price > e1.price and volume > e1.volume] within 1 sec "
        "select e1.price as a, e2.price as b insert into Out;",
        {"seq", "chunk", "scan"}),
    "nonevery": (
        "from e1=S[price > 125] -> e2=S[price > e1.price] "
        "within 1 sec select e1.price as a, e2.price as b "
        "insert into Out;",
        {"seq", "scan"}),
    "count_null_idx": (
        "from every e1=S[price > 115]<1:3> -> e2=S[price < 95] "
        "within 1 sec select e1[1].price as b, e2.price as c "
        "insert into Out;",
        {"seq", "chunk", "scan", "dfa"}),
}

# ineligible shapes: every parallel family must REJECT them — forced
# requests fall back, outputs stay identical to the interpreter
INELIGIBLE = {
    "every_mid": (
        "from every e1=S[price > 127] -> every e2=S[price < 91] "
        "within 200 milliseconds select e1.price as a, e2.price as b "
        "insert into Out;",
        "every"),
    "optional_count": (
        "from every e1=S[price > 110] -> e2=S[price < 100]<0:3> -> "
        "e3=S[price > 124] within 1 sec select e1.price as a, "
        "e2[last].price as b, e3.price as c insert into Out;",
        "count quantifier"),
    "adjacent_counts": (
        "from every e1=S[price > 118]<1:2> -> e2=S[price < 97]<1:2> -> "
        "e3=S[price > 124] within 1 sec select e1[last].price as a, "
        "e2[last].price as b, e3.price as c insert into Out;",
        "adjacent"),
    "no_within": (
        "from every e1=S[price > 120] -> e2=S[price < 95] "
        "select e1.price as a, e2.price as b insert into Out;",
        "within"),
    "conjunction_step": (
        "from every e1=S[price > 110] -> "
        "e2=S[price > e1.price and volume > e1.volume] within 1 sec "
        "select e1.price as a, e2.price as b insert into Out;",
        "conjunct"),
}


def _run(head, q, n=900, batches=3, seed=11, dt=7, keys=4, plan_out=None):
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(head + HEAD + q)
    rows = []
    rt.add_callback("Out", lambda evs: rows.extend(
        (e.timestamp,
         tuple(None if x is None else round(float(x), 3)
               if isinstance(x, float) else x for x in e.data))
        for e in evs))
    rt.start()
    plan = next((p for p in rt._plans
                 if isinstance(p, DevicePatternPlan)), None)
    fam = plan.family if plan is not None else None
    families = dict(plan.families) if plan is not None else {}
    rng = np.random.default_rng(seed)
    ih = rt.input_handler("S")
    ts0 = 1_700_000_000_000
    for b in range(batches):
        for j in range(n // batches):
            i = b * (n // batches) + j
            ih.send((f"K{rng.integers(0, keys)}",
                     float(np.round(rng.uniform(90, 130) * 4) / 4),
                     int(rng.integers(1, 1000))),
                    timestamp=ts0 + i * dt)
        rt.flush()
    if plan_out is not None:
        plan_out["explain"] = rt.explain()
    mgr.shutdown()
    return fam, families, rows


def _cached_rows(head):
    cache = {}

    def get(q):
        if q not in cache:
            _f, _e, rows = _run(head, q)
            cache[q] = rows
        return cache[q]
    return get


@pytest.fixture(scope="module")
def host_rows():
    return _cached_rows("@app:devicePatterns('never')\n")


@pytest.fixture(scope="module")
def seq_rows():
    return _cached_rows("@app:patternFamily('seq')\n"
                        "@app:devicePatterns('always')\n")


# dfa provably rejects these (sequence/nonevery/final-count shapes) and
# falls back to scan — running them under a forced dfa would just re-run
# the scan differential, so they ride the slow lane only.  count_null_idx
# joins them: its dfa count machinery is count_head/count_mid's coverage
_DFA_FALLBACK = {"count_final", "sequence", "sequence_conj", "nonevery",
                 "count_null_idx"}


@pytest.mark.parametrize("name,fam", [
    pytest.param(n, f, marks=pytest.mark.slow)
    if f == "dfa" and n in _DFA_FALLBACK else (n, f)
    for n in ELIGIBLE for f in FAMILIES])
def test_eligible_differential(name, fam, host_rows):
    q, ok_fams = ELIGIBLE[name]
    used, families, dev = _run(
        f"@app:patternFamily('{fam}')\n@app:devicePatterns('always')\n", q)
    host = host_rows(q)
    if fam in ok_fams:
        assert used == fam, (name, fam, used, families)
    else:
        # provable fallback: the family rejected with a reason, and the
        # plan engaged a sound family instead
        assert families.get(fam) is not True, (name, fam)
        assert used != fam and used in ok_fams, (name, fam, used)
    assert len(dev) > 0, f"{name}: no matches — tape too easy?"
    assert dev == host, (name, fam, used, len(dev), len(host),
                         dev[:3], host[:3])


@pytest.mark.parametrize("name", CHUNK_SUBSET)
def test_chunk_family_differential(name, host_rows):
    q, ok_fams = ELIGIBLE[name]
    assert "chunk" in ok_fams
    used, _families, dev = _run(
        "@app:patternFamily('chunk')\n@app:devicePatterns('always')\n", q)
    assert used == "chunk"
    assert dev == host_rows(q), (name, len(dev))


@pytest.mark.parametrize("name", list(INELIGIBLE))
def test_ineligible_fallback(name, host_rows):
    # a forced scan and a forced dfa fall back to the SAME auto family
    # for these shapes, so one device run proves both rejections.
    # deviceChunkLanes(0) pins the fallback onto the sequential kernel —
    # chunk-vs-host for these exact shapes is test_nfa_chunked.py's job,
    # and the chunk compile would double this test's tier-1 cost
    q, reason_frag = INELIGIBLE[name]
    used, families, dev = _run(
        "@app:patternFamily('scan')\n@app:deviceChunkLanes(0)\n"
        "@app:devicePatterns('always')\n", q)
    host = host_rows(q)
    assert used == "seq", (name, used)
    for fam in ("scan", "dfa"):
        reason = families.get(fam)
        assert isinstance(reason, str) and reason, (name, fam, families)
        assert reason_frag.lower() in reason.lower(), (name, fam, reason)
    assert dev == host, (name, used, len(dev), len(host))


def test_unknown_family_name_is_a_build_error():
    from siddhi_tpu.core.planner import PlanError
    with pytest.raises(PlanError):
        SiddhiManager().create_app_runtime(
            "@app:patternFamily('warp')\n" + HEAD
            + ELIGIBLE["static2"][0])


def test_default_selection_prefers_parallel_families():
    q3, _ = ELIGIBLE["threshold2"]
    fam, families, _rows = _run(
        "@app:devicePatterns('always')\n", q3, n=300, batches=1)
    assert fam == "scan" and families["scan"] is True \
        and families["dfa"] is not True
    qs, _ = ELIGIBLE["static2"]
    fam, families, _rows = _run(
        "@app:devicePatterns('always')\n", qs, n=300, batches=1)
    assert fam == "scan" and families["dfa"] is True


@pytest.mark.slow
def test_cross_flush_tail_replay_many_small_flushes(host_rows):
    # many tiny flushes hammer the replay/dedup path: within 1 sec, dt=9
    # -> the tail spans several flushes of 60 events
    # fam -> a query the family genuinely engages for (dfa on threshold2
    # would just fall back to scan and re-test the same path)
    for fam, qname in (("scan", "threshold2"), ("dfa", "hybrid")):
        q, _ = ELIGIBLE[qname]
        _hf, _he, host = _run("@app:devicePatterns('never')\n",
                              q, n=900, batches=15, dt=9)
        used, _f, dev = _run(
            f"@app:patternFamily('{fam}')\n@app:devicePatterns('always')\n",
            q, n=900, batches=15, dt=9)
        assert used == fam
        assert dev == host, (fam, used, len(dev), len(host))


def test_family_gauges_in_statistics():
    q, _ = ELIGIBLE["static2"]
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime(
        "@app:patternFamily('dfa')\n@app:devicePatterns('always')\n"
        + HEAD + q)
    rt.enable_stats(True)
    rt.start()
    ih = rt.input_handler("S")
    rng = np.random.default_rng(0)
    ts0 = 1_700_000_000_000
    for i in range(256):
        ih.send((f"K{i % 4}",
                 float(np.round(rng.uniform(90, 130) * 4) / 4), 10),
                timestamp=ts0 + i * 7)
    rt.flush()
    dev = rt.statistics().get("device", {}).get("q", {})
    mgr.shutdown()
    assert dev.get("plan_family") == "dfa"
    assert dev.get("dispatches_dfa", 0) >= 1
    assert "family_ineligible" not in dev or \
        isinstance(dev["family_ineligible"], dict)


@pytest.mark.slow
def test_out_of_order_expiry_matches_sequential():
    """The sequential kernel expires a waiting instance on ANY arriving
    event past the `within` horizon — even a non-matching one — so a
    later event with a REGRESSED timestamp must not complete it.  The
    pointer chase reproduces this via the killer-event query (review
    finding, confirmed divergent pre-fix: host/seq emitted [] while
    scan emitted the resurrected match)."""
    q = ("from every e1=S[price > 100] -> e2=S[price > e1.price] "
         "within 1 sec select e1.price as p1, e2.price as p2 "
         "insert into Out;")
    sends = [(0, 101.0), (2000, 50.0), (500, 150.0),   # killed instance
             (2100, 102.0), (2200, 103.0)]             # live pair

    def run(head):
        mgr = SiddhiManager()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            rt = mgr.create_app_runtime(head + HEAD + q)
        rows = []
        rt.add_callback("Out", lambda evs: rows.extend(
            tuple(e.data) for e in evs))
        rt.start()
        ih = rt.input_handler("S")
        ts0 = 1_700_000_000_000
        for dt, p in sends:
            ih.send(("K", p, 1), timestamp=ts0 + dt)
        rt.flush()
        mgr.shutdown()
        return rows

    host = run("@app:devicePatterns('never')\n")
    assert host == [(102.0, 103.0)]
    for fam in ("seq", "chunk", "scan", "dfa"):
        dev = run(f"@app:patternFamily('{fam}')\n"
                  "@app:devicePatterns('always')\n")
        assert dev == host, (fam, dev, host)


@pytest.mark.slow
def test_threshold_hop_nan_column_matches_sequential():
    """A NaN in the threshold column must behave like the sequential
    kernel's per-event compare (NaN compares False): it neither
    satisfies a hop nor poisons its segment-tree block (jnp.maximum
    would propagate NaN to every ancestor — review finding, confirmed
    divergent pre-fix)."""
    q = ("from every e1=S[price > 100] -> e2=S[price > e1.price] "
         "within 1 sec select e1.price as p1, e2.price as p2 "
         "insert into Out;")
    prices = [101.0, 90.0, 91.0, 92.0, float("nan"), 150.0,
              93.0, 94.0, 95.0, 160.0, 96.0, 97.0]

    def run(head):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(head + HEAD + q)
        rows = []
        rt.add_callback("Out", lambda evs: rows.extend(
            tuple(e.data) for e in evs))
        rt.start()
        ih = rt.input_handler("S")
        ts0 = 1_700_000_000_000
        for i, p in enumerate(prices):
            ih.send(("K", p, 1), timestamp=ts0 + i * 10)
        rt.flush()
        mgr.shutdown()
        return rows

    host = run("@app:devicePatterns('never')\n")
    assert host == [(101.0, 150.0), (150.0, 160.0)]
    for fam in ("scan", "dfa"):
        dev = run(f"@app:patternFamily('{fam}')\n"
                  "@app:devicePatterns('always')\n")
        assert dev == host, (fam, dev, host)


def test_classifier_agreement_build_vs_analysis():
    """Satellite: classify_shape (analysis time, AST only) and
    classify_parallel (build time, lowered kernel) must agree — same
    eligibility verdict AND same reason string — across the full
    eligible matrix and every ineligible shape, so SA08 can never
    disagree with the family the build actually selects."""
    from siddhi_tpu.core.nfa_parallel import classify_shape
    from siddhi_tpu.core.schema import StringTable
    from siddhi_tpu.query.parser import parse

    from siddhi_tpu.core.schema import StreamSchema
    for name, q in [(n, e[0]) for n, e in ELIGIBLE.items()] \
            + [(n, e[0]) for n, e in INELIGIBLE.items()]:
        app = parse(HEAD + q)
        query = app.execution_elements[0]
        schemas = {"S": StreamSchema.of(app.stream_definitions["S"])}
        shape = classify_shape(query.input, schemas, StringTable())
        mgr = SiddhiManager()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            rt = mgr.create_app_runtime(
                "@app:devicePatterns('always')\n" + HEAD + q)
        plan = next(p for p in rt._plans
                    if isinstance(p, DevicePatternPlan))
        for fam in ("chunk", "scan", "dfa"):
            assert plan.families[fam] == shape[fam], \
                (name, fam, plan.families[fam], shape[fam])
        mgr.shutdown()


PART_HEAD = "define stream S (sym string, price double, volume int);\n"
PART_Q = """partition with (sym of S)
begin
  @info(name='q')
  from every e1=S[price > 100] -> e2=S[price > e1.price]
    -> e3=S[price > e2.price] within 10 sec
  select e1.price as p1, e2.price as p2, e3.price as p3 insert into Out;
end;
"""


def _run_part(head, n=1200, batches=4, seed=3, dt=7, keys=37,
              plan_out=None, q=PART_Q):
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(head + PART_HEAD + q)
    rows = []
    rt.add_callback("Out", lambda evs: rows.extend(
        (e.timestamp, tuple(round(float(x), 3) for x in e.data))
        for e in evs))
    rt.start()
    plan = next((p for p in rt._plans
                 if isinstance(p, DevicePatternPlan)), None)
    rng = np.random.default_rng(seed)
    ih = rt.input_handler("S")
    ts0 = 1_700_000_000_000
    for b in range(batches):
        for j in range(n // batches):
            i = b * (n // batches) + j
            ih.send((f"K{rng.integers(0, keys)}",
                     float(np.round(rng.uniform(90, 130) * 4) / 4),
                     int(rng.integers(1, 1000))), timestamp=ts0 + i * dt)
        rt.flush()
    fam = plan.family if plan is not None else None
    if plan_out is not None:
        plan_out["metrics"] = plan.device_metrics() if plan else {}
        plan_out["explain"] = rt.explain()
    mgr.shutdown()
    # host clones deliver per instance: order differs from the device's
    # global completion order — compare as multisets with timestamps
    return fam, sorted(rows)


def test_partitioned_lanes_run_parallel_family_by_default():
    """The ISSUE 13 headline: a partitioned pattern (config 4's shape)
    runs a lane-vmapped parallel family BY DEFAULT, byte-identical to
    the per-key host clones, with zero D-FAMILY demotions."""
    _f, host = _run_part("@app:devicePatterns('never')\n")
    info: dict = {}
    fam, dev = _run_part("@app:partitionCapacity(64)\n", plan_out=info)
    assert fam == "scan", fam
    assert dev == host, (len(dev), len(host), dev[:3], host[:3])
    m = info["metrics"]
    assert m.get("dispatches_lane_vmapped", 0) >= 1
    assert m.get("lanes_last_dispatch", 0) >= 37
    ent = info["explain"]["queries"]["q"]
    assert ent["path"] == "device" and ent["family"] == "scan", ent
    assert not [d for d in ent.get("demotions", ())
                if d["rule_id"] in ("D-FAMILY", "D-PARTITION")], ent


@pytest.mark.slow
def test_partitioned_lanes_forced_dfa_differential():
    """The bit-packed family under the lane vmap: a static partitioned
    chain forced onto dfa matches the host clones byte-for-byte."""
    q_static = PART_Q.replace("e2=S[price > e1.price]",
                              "e2=S[price < 96]") \
                     .replace("e3=S[price > e2.price]",
                              "e3=S[price > 124]")

    def run(head):
        mgr = SiddhiManager()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            rt = mgr.create_app_runtime(head + PART_HEAD + q_static)
        rows = []
        rt.add_callback("Out", lambda evs: rows.extend(
            (e.timestamp, tuple(round(float(x), 3) for x in e.data))
            for e in evs))
        rt.start()
        plan = next((p for p in rt._plans
                     if isinstance(p, DevicePatternPlan)), None)
        rng = np.random.default_rng(3)
        ih = rt.input_handler("S")
        ts0 = 1_700_000_000_000
        for b in range(3):
            for j in range(300):
                i = b * 300 + j
                ih.send((f"K{rng.integers(0, 16)}",
                         float(np.round(rng.uniform(90, 130) * 4) / 4),
                         1), timestamp=ts0 + i * 7)
            rt.flush()
        fam = plan.family if plan is not None else None
        mgr.shutdown()
        return fam, sorted(rows)

    _f, host = run("@app:devicePatterns('never')\n")
    fam, dev = run("@app:patternFamily('dfa')\n"
                   "@app:partitionCapacity(32)\n")
    assert fam == "dfa", fam
    assert len(dev) > 0 and dev == host, (len(dev), len(host))


def test_partition_hot_add_reuses_lane_plan():
    """Satellite: a partitioned app that sees a NEW key mid-stream must
    reuse the vmapped lane plan — no per-key recompile (the (L, F) lane
    bucket absorbs it), no D-PARTITION demotion, and the placement
    plane keeps reporting one device query."""
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(
            "@app:partitionCapacity(16)\n" + PART_HEAD + PART_Q)
    rows = []
    rt.add_callback("Out", lambda evs: rows.extend(evs))
    rt.start()
    plan = next(p for p in rt._plans if isinstance(p, DevicePatternPlan))
    assert plan.family == "scan"
    rng = np.random.default_rng(9)
    ih = rt.input_handler("S")
    ts0 = 1_700_000_000_000
    for i in range(300):                       # 5 keys, warm compile
        ih.send((f"K{rng.integers(0, 5)}",
                 float(np.round(rng.uniform(90, 130) * 4) / 4), 1),
                timestamp=ts0 + i * 7)
    rt.flush()
    kern = plan._parallel_kernel()
    compiled_before = set(kern._block_cache)
    lanes_before = plan._lane_dispatches
    for i in range(300, 600):                  # 3 NEW keys hot-added
        ih.send((f"K{rng.integers(0, 8)}",
                 float(np.round(rng.uniform(90, 130) * 4) / 4), 1),
                timestamp=ts0 + i * 7)
    rt.flush()
    assert plan._lane_dispatches > lanes_before
    # 8 keys still fit the pow2 lane bucket of 8: the SAME compiled
    # (L, F) block served the new keys — zero recompiles
    assert set(kern._block_cache) == compiled_before, \
        (compiled_before, set(kern._block_cache))
    ent = rt.explain()["queries"]["q"]
    assert ent["path"] == "device" and ent["family"] == "scan"
    assert not [d for d in ent.get("demotions", ())
                if d["rule_id"] == "D-PARTITION"], ent
    assert len(plan._key_to_part) == 8
    mgr.shutdown()


def test_partitioned_quiet_lane_tail_held_aside():
    """Review regression: a lane with no new events this flush must NOT
    replay its tail (it cannot produce a new completion, and its old
    events would pin the shared i32 offset bases forever).  The held
    tail still resumes correctly when the key speaks again."""
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(
            "@app:partitionCapacity(8)\n" + PART_HEAD + PART_Q.replace(
                "within 10 sec", "within 1 hour"))
    rows = []
    rt.add_callback("Out", lambda evs: rows.extend(
        tuple(e.data) for e in evs))
    rt.start()
    plan = next(p for p in rt._plans if isinstance(p, DevicePatternPlan))
    assert plan.family == "scan"
    ih = rt.input_handler("S")
    ts0 = 1_700_000_000_000
    ih.send(("A", 110.0, 1), timestamp=ts0)       # A arms a pending head
    ih.send(("B", 101.0, 1), timestamp=ts0 + 1)
    rt.flush()
    # flush 2: only B speaks — A's tail must be held aside, not gridded
    ih.send(("B", 102.0, 1), timestamp=ts0 + 2)
    rt.flush()
    tail_parts = set(plan.grid.tail["part"].tolist())
    assert len(tail_parts) == 2, tail_parts        # A held + B kept
    # flush 3: A resumes and completes its 3-chain from the held tail
    ih.send(("A", 120.0, 1), timestamp=ts0 + 3)
    ih.send(("A", 130.0, 1), timestamp=ts0 + 4)
    rt.flush()
    assert (110.0, 120.0, 130.0) in rows, rows
    mgr.shutdown()


def test_fused_lanes_run_parallel_family():
    """Fused multi-query groups (config 5's substrate) ride the SAME
    lane vmap: per-lane `__qparam` thresholds, events broadcast —
    byte-identical to per-query host matchers."""
    def app():
        parts = [PART_HEAD]
        for i in range(10):
            lo = 110 + (i % 5)
            parts.append(
                f"@info(name='q{i}') from every e1=S[price > {lo}] -> "
                f"e2=S[price > e1.price] within 1 sec "
                f"select e1.price as p1, e2.price as p2 "
                f"insert into Out{i % 3};")
        return "\n".join(parts) + "\n"

    def run(head):
        from siddhi_tpu.core.multi_query import MultiQueryDevicePatternPlan
        mgr = SiddhiManager()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            rt = mgr.create_app_runtime(head + app())
        rows = []
        for o in range(3):
            rt.add_callback(f"Out{o}", lambda evs, o=o: rows.extend(
                (o, e.timestamp, tuple(round(float(x), 3)
                                       for x in e.data)) for e in evs))
        rt.start()
        mq = next((p for p in rt._plans
                   if isinstance(p, MultiQueryDevicePatternPlan)), None)
        fam = mq.inner.family if mq is not None else None
        rng = np.random.default_rng(5)
        ih = rt.input_handler("S")
        ts0 = 1_700_000_000_000
        for b in range(3):
            for j in range(200):
                i = b * 200 + j
                ih.send((f"K{rng.integers(0, 4)}",
                         float(np.round(rng.uniform(90, 130) * 4) / 4),
                         int(rng.integers(1, 1000))),
                        timestamp=ts0 + i * 7)
            rt.flush()
        mgr.shutdown()
        return fam, sorted(rows)

    _f, host = run("@app:devicePatterns('never')\n")
    fam, dev = run("")
    assert fam == "scan", fam
    assert len(dev) > 0 and dev == host, (fam, len(dev), len(host))


def test_nonevery_single_arm_resolves_across_flushes():
    """A non-`every` chain arms ONCE, globally: a pending arm spans the
    flush boundary through the replay tail, and once resolved the host
    stops dispatching (the meta-row flag)."""
    q = ("from e1=S[price > 100] -> e2=S[price > e1.price] "
         "within 1 sec select e1.price as a, e2.price as b "
         "insert into Out;")
    sends = [(0, 90.0), (10, 101.0),            # flush 1: arm pending
             (20, 95.0), (30, 107.0),           # flush 2: completes
             (40, 120.0), (50, 130.0)]          # flush 3: must NOT match

    def run(head):
        mgr = SiddhiManager()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            rt = mgr.create_app_runtime(head + HEAD + q)
        rows = []
        rt.add_callback("Out", lambda evs: rows.extend(
            tuple(e.data) for e in evs))
        rt.start()
        ih = rt.input_handler("S")
        ts0 = 1_700_000_000_000
        plan = next((p for p in rt._plans
                     if isinstance(p, DevicePatternPlan)), None)
        for i, (dt, p) in enumerate(sends):
            ih.send(("K", p, 1), timestamp=ts0 + dt)
            if i % 2 == 1:
                rt.flush()
        rt.flush()
        done = (None if plan is None or plan._arm_done is None
                else bool(plan._arm_done.all()))
        mgr.shutdown()
        return rows, done

    host, _d = run("@app:devicePatterns('never')\n")
    assert host == [(101.0, 107.0)]
    dev, done = run("@app:patternFamily('scan')\n"
                    "@app:devicePatterns('always')\n")
    assert dev == host, (dev, host)
    assert done is True


# ---------------------------------------------------------------------------
# one `within` query per horizon, shared down the chain (ISSUE 27): the
# lane block asks "when does this instance expire?" once per distinct
# horizon; every shape below must stay byte-identical to the interpreter
# whether its hops share the head's query or must make their own
# ---------------------------------------------------------------------------

# name -> (query, expiry_queries the plan must report, dfa eligible)
EXPIRY = {
    "hops3_one_within": (
        "from every e1=S[price > 118] -> e2=S[price < 100] -> "
        "e3=S[price > e1.price] -> e4=S[price < e2.price] within 2 sec "
        "select e1.price as a, e2.price as b, e3.price as c, e4.price as d "
        "insert into Out;",
        {"built": 1, "shared": 2}, True),
    "hops4_one_within": (
        "from every e1=S[price > 120] -> e2=S[price < 100] -> "
        "e3=S[price > 118] -> e4=S[price < 98] -> e5=S[price > e1.price] "
        "within 3 sec select e1.price as a, e2.price as b, e3.price as c, "
        "e4.price as d, e5.price as e insert into Out;",
        {"built": 1, "shared": 3}, True),
    # per-element horizons that differ from their predecessor's: every hop
    # must make its own query
    "within_short_then_long": (
        "from every e1=S[price > 110] -> (e2=S[price < 100]) "
        "within 300 milliseconds -> e3=S[price > e1.price] within 2 sec "
        "select e1.price as a, e2.price as b, e3.price as c "
        "insert into Out;",
        {"built": 2, "shared": 0}, True),
    "within_long_then_short": (
        "from every e1=S[price > 110] -> e2=S[price < 100] -> "
        "(e3=S[price > e1.price]) within 300 milliseconds within 2 sec "
        "select e1.price as a, e2.price as b, e3.price as c "
        "insert into Out;",
        {"built": 2, "shared": 0}, True),
    "within_long_short_long": (
        "from every e1=S[price > 112] -> e2=S[price < 100] -> "
        "(e3=S[price > e1.price]) within 400 milliseconds -> "
        "e4=S[price < 102] within 2 sec select e1.price as a, "
        "e2.price as b, e3.price as c, e4.price as d insert into Out;",
        {"built": 3, "shared": 0}, True),
    "count_head_successor": (
        "from every e1=S[price > 112]<1:3> -> e2=S[price < 96] -> "
        "e3=S[price > 120] within 1 sec select e1[0].price as a, "
        "e1[last].price as b, e2.price as c, e3.price as d "
        "insert into Out;",
        {"built": 1, "shared": 2}, True),
    "logical_pair_chain": (
        "from every e1=S[price > 120] -> e2=S[price < 100] and "
        "e3=S[price > 125] -> e4=S[price < 97] within 2 sec "
        "select e1.price as a, e2.price as b, e3.price as c, "
        "e4.price as d insert into Out;",
        {"built": 1, "shared": 1}, True),
    "final_count_chain": (
        "from every e1=S[price > 118] -> e2=S[price > 122] -> "
        "e3=S[price < 97]<2:3> within 1 sec select e1.price as a, "
        "e2.price as b, e3[last].price as c insert into Out;",
        {"built": 1, "shared": 1}, False),
}


def _lane_app(q):
    return ("partition with (sym of S)\nbegin\n  @info(name='q') "
            + q + "\nend;\n")


@pytest.mark.parametrize("name,fam,layout", [
    (n, f, lay) for n, (_q, _e, dfa_ok) in EXPIRY.items()
    for f in FAMILIES if f == "scan" or dfa_ok
    for lay in ("flat", "lanes")])
def test_shared_expiry_differential(name, fam, layout, host_rows, seq_rows):
    q, expiry, _dfa_ok = EXPIRY[name]
    info: dict = {}
    if layout == "flat":
        used, _families, dev = _run(
            f"@app:patternFamily('{fam}')\n@app:devicePatterns('always')\n",
            q, plan_out=info)
        # in emission order against the sequential device kernel; the
        # interpreter orders completions of ONE event differently on
        # chains of four positions and more (every device family agrees),
        # so it is held as a multiset
        assert dev == seq_rows(q), (name, fam, len(dev))
        dev, host = sorted(dev), sorted(host_rows(q))
    else:
        # 5 keys: a key sees an event every 35 ms, so chains of four and
        # five positions still complete inside their horizons
        used, dev = _run_part(
            f"@app:patternFamily('{fam}')\n@app:partitionCapacity(8)\n",
            keys=5, plan_out=info, q=_lane_app(q))
        _f, host = _run_part("@app:devicePatterns('never')\n", keys=5,
                             q=_lane_app(q))
        assert info["metrics"].get("dispatches_lane_vmapped", 0) >= 1
    assert used == fam, (name, fam, used)
    assert info["explain"]["queries"]["q"]["expiry_queries"] == expiry
    assert len(dev) > 0, f"{name}: no matches — tape too easy?"
    assert dev == host, (name, fam, layout, len(dev), len(host),
                         dev[:3], host[:3])


C4_Q = ("from every e1=S[price > 100] -> e2=S[price > e1.price] -> "
        "e3=S[price > e2.price] within 1 sec "
        "select e1.price as p1, e2.price as p2, e3.price as p3 "
        "insert into Out;")
# (ms offset, price): timestamps regress after each killer event, so some
# heads are expired while waiting at hop 1 and some at hop 2, by an event
# that sits BEFORE the one that would have completed them
OOO_SENDS = [
    (0, 101.0),      # head A
    (100, 102.0),    # A's e2; head B
    (2000, 50.0),    # past both horizons: A dies at hop 2, B at hop 1
    (300, 150.0),    # regressed: would be A's e3 and B's e2; head D
    (400, 151.0),    # D's e2; head E
    (500, 152.0),    # D's e3 -> (150, 151, 152); E's e2; head F
    (2500, 60.0),    # E dies at hop 2, F at hop 1
    (600, 160.0),    # regressed: would be E's e3 and F's e2; head G
    (700, 170.0),    # G's e2
    (800, 180.0),    # G's e3 -> (160, 170, 180)
]
OOO_WANT = [(150.0, 151.0, 152.0), (160.0, 170.0, 180.0)]


def _run_sends(head, app, sends, plan_out=None):
    """Feed (sym, ms offset, price) rows in ONE flush; rows as sorted
    (timestamp, data) pairs."""
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(head + app)
    rows = []
    rt.add_callback("Out", lambda evs: rows.extend(
        (e.timestamp, tuple(e.data)) for e in evs))
    rt.start()
    plan = next((p for p in rt._plans
                 if isinstance(p, DevicePatternPlan)), None)
    if plan_out is not None:
        plan_out["plan"] = plan
    ih = rt.input_handler("S")
    for sym, dt, p in sends:
        ih.send((sym, p, 1), timestamp=1_700_000_000_000 + dt)
    rt.flush()
    fam = plan.family if plan is not None else None
    mgr.shutdown()
    return fam, sorted(rows)


@pytest.mark.parametrize("fam,layout", [
    (f, lay) for f in ("seq",) + FAMILIES for lay in ("flat", "lanes")])
def test_regressed_timestamps_die_at_hop1_and_hop2(fam, layout):
    # dfa rejects C4 (no static hop to bit-pack): it takes the chain with
    # a static last hop, which this tape completes with the same rows
    q = C4_Q.replace("e3=S[price > e2.price]", "e3=S[price > 151.5]") \
        if fam == "dfa" else C4_Q
    if layout == "flat":
        app = HEAD + q
        sends = [("K", dt, p) for dt, p in OOO_SENDS]
        force = (f"@app:patternFamily('{fam}')\n"
                 "@app:devicePatterns('always')\n")
        want = OOO_WANT
    else:
        # the same tape on two keys, interleaved, beside an in-order key
        app = PART_HEAD + _lane_app(q)
        sends = [(k, dt, p) for dt, p in OOO_SENDS for k in ("A", "B")] \
            + [("C", 3000 + 10 * i, 150.0 + i) for i in range(3)]
        force = f"@app:patternFamily('{fam}')\n@app:partitionCapacity(8)\n"
        want = OOO_WANT * 2 + [(150.0, 151.0, 152.0)]
    _f, host = _run_sends("@app:devicePatterns('never')\n", app, sends)
    assert sorted(d for _t, d in host) == sorted(want), host
    used, dev = _run_sends(force, app, sends)
    assert used == fam
    assert dev == host, (fam, layout, dev, host)


def _record_lane_blocks(kern, out):
    """Wrap a kernel's block_fn so every dispatch's (T, M, ev) lands in
    `out` as numpy."""
    import jax
    orig = kern.block_fn

    def block_fn(T, M):
        fn = orig(T, M)

        def call(state, ev):
            out.append((T, M, jax.tree_util.tree_map(np.asarray, ev)))
            return fn(state, ev)
        return call
    kern.block_fn = block_fn


@pytest.mark.parametrize("name", ["c4", "count_head_successor",
                                  "logical_pair_chain",
                                  "final_count_chain"])
def test_lane_block_bytes_equal_unshared_queries(name):
    """The packed output of the lane block is byte-identical to the block
    that makes EVERY expiry query afresh (the block as it was before the
    sharing) on the (lanes, F) grids of a seeded partitioned run whose
    timestamps regress on about a third of the events."""
    import dataclasses
    from siddhi_tpu.core.nfa_parallel import ParallelChainKernel
    q = C4_Q.replace("1 sec", "400 milliseconds") if name == "c4" \
        else EXPIRY[name][0]
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(
            "@app:partitionCapacity(16)\n" + PART_HEAD + _lane_app(q))
    rt.start()
    plan = next(p for p in rt._plans if isinstance(p, DevicePatternPlan))
    kern = plan._parallel_kernel()
    assert kern.expiry_queries["shared"] >= 1
    calls: list = []
    _record_lane_blocks(kern, calls)
    rng = np.random.default_rng(3)
    ih = rt.input_handler("S")
    for b in range(3):
        for j in range(400):
            i = b * 400 + j
            late = int(rng.integers(-300, 300)) if rng.random() < 0.3 else 0
            ih.send((f"K{rng.integers(0, 9)}",
                     float(np.round(rng.uniform(90, 130) * 4) / 4), 1),
                    timestamp=1_700_000_000_000 + i * 7 + late)
        rt.flush()
    mgr.shutdown()
    assert calls and all(isinstance(T, tuple) for T, _M, _ev in calls)
    shared = ParallelChainKernel(kern.prog, kern.nfak, kern.family)
    fresh = ParallelChainKernel(kern.prog, kern.nfak, kern.family)
    fresh.expiry_plan = {pi: dataclasses.replace(a, fresh=True)
                         for pi, a in fresh.expiry_plan.items()}
    matches = 0
    for T, M, ev in calls:
        got = shared.block_fn(T, M)({}, ev)[1]
        want = fresh.block_fn(T, M)({}, ev)[1]
        assert sorted(got) == sorted(want)
        for k in want:
            assert np.asarray(got[k]).tobytes() \
                == np.asarray(want[k]).tobytes(), (name, T, M, k)
        matches += int(np.asarray(want["i"])[:, 0, 0].sum())
    assert matches > 0, f"{name}: no match in any lane"


def _c4_kernel():
    from siddhi_tpu.core.nfa_parallel import ParallelChainKernel
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(
            "@app:partitionCapacity(16)\n" + PART_HEAD + PART_Q)
    plan = next(p for p in rt._plans if isinstance(p, DevicePatternPlan))
    kern = plan._parallel_kernel()
    mgr.shutdown()
    return ParallelChainKernel(kern.prog, kern.nfak, kern.family)


def test_c4_makes_one_expiry_query_and_shares_it():
    """The north star's chain (`every e1 -> e2 -> e3 within 10 sec`) asks
    once, at hop 1, and hop 2 reads the answer: the plan says so, EXPLAIN's
    counter says so, and the lowered lane block carries ONE `within_kill`
    scope (two when every hop asks)."""
    import dataclasses
    import re
    from siddhi_tpu.core.nfa_parallel import ExpiryAsk, _expiry_plan
    kern = _c4_kernel()
    assert _expiry_plan(kern.prog) == {1: ExpiryAsk(10_000, fresh=True),
                                       2: ExpiryAsk(10_000, fresh=False)}
    assert kern.expiry_queries == {"built": 1, "shared": 1}
    lanes, F = 8, 64
    ev = {"__flat.__ts__": np.zeros((lanes, F), np.int32),
          "__flat.__seq__": np.zeros((lanes, F), np.int32),
          "__flat.0.price": np.zeros((lanes, F), np.float32),
          "__nev__": np.zeros((lanes,), np.int32),
          "__prev_seq__": np.zeros((lanes,), np.int32),
          "__base_ts__": np.int64(0), "__base_seq__": np.int64(0)}

    def scopes(k):
        txt = k.block_fn((lanes, F), F).lower({}, ev).as_text(
            debug_info=True)
        return sorted(set(re.findall(r"hop(\d+)\)?/within_kill", txt)))
    assert scopes(kern) == ["1"]
    kern.expiry_plan = {pi: dataclasses.replace(a, fresh=True)
                        for pi, a in kern.expiry_plan.items()}
    kern._block_cache.clear()
    assert scopes(kern) == ["1", "2"]


@pytest.mark.parametrize("F,gathers", [(64, False), (None, True)],
                         ids=["short_lane", "past_DENSE_MAX_F"])
def test_c4_lane_block_reads_a_short_lane_without_a_gather(F, gathers):
    """The structure the one-hot read's gain rests on, checked without a
    chip: the lowered C4 lane block of a short lane carries no `gather`
    under the scopes that read a column by index (one hop, the dedup, the
    capture indices, the selected values), and past DENSE_MAX_F it carries
    them all."""
    import re
    from siddhi_tpu.core.nfa_parallel import DENSE_MAX_F
    kern = _c4_kernel()
    lanes, F = 8, F or DENSE_MAX_F + 1
    ev = {"__flat.__ts__": np.zeros((lanes, F), np.int32),
          "__flat.__seq__": np.zeros((lanes, F), np.int32),
          "__flat.0.price": np.zeros((lanes, F), np.float32),
          "__nev__": np.zeros((lanes,), np.int32),
          "__prev_seq__": np.zeros((lanes,), np.int32),
          "__base_ts__": np.int64(0), "__base_seq__": np.int64(0)}
    txt = kern.block_fn((lanes, F), F).lower({}, ev).as_text(debug_info=True)
    # loc("jit(lane_block)/vmap(hop2)/threshold_next/gather"): the scope
    # path up to the op; a tree's own gathers sit under `heap` and the
    # first-hit walk, which `[^"]*` would take for a read's
    found = set(re.findall(
        r'loc\("[^"]*?vmap\((select|capture|emit_candidates|hop\d+)\)'
        r'(?:/threshold_next)?/gather"', txt))
    assert found == ({"select", "capture", "emit_candidates", "hop2"}
                     if gathers else set()), found
    assert kern.indexed_read["gather"] == (10 if gathers else 0)


def _prog(*positions, sequence=False):
    """Hand-built ParallelProgram: positions as (kind, within_ms)."""
    from siddhi_tpu.core.nfa_parallel import HopNode, PPos, ParallelProgram
    pp = [PPos(kind, [HopNode(f"e{i}", 0)] * (2 if kind == "logical" else 1),
               within_ms=w) for i, (kind, w) in enumerate(positions)]
    return ParallelProgram(pp, ["S"], {}, {}, sequence=sequence)


@pytest.mark.parametrize("name,prog,want", [
    ("one_within_3_hops",
     _prog(("single", 5), ("single", 5), ("single", 5), ("single", 5)),
     {1: (5, True), 2: (5, False), 3: (5, False)}),
    ("shorter_then_longer",
     _prog(("single", 9), ("single", 3), ("single", 9)),
     {1: (3, True), 2: (9, True)}),
    ("longer_then_shorter",
     _prog(("single", 9), ("single", 9), ("single", 3)),
     {1: (9, True), 2: (3, True)}),
    ("back_to_the_first_horizon_is_fresh",
     _prog(("single", 9), ("single", 9), ("single", 3), ("single", 9)),
     {1: (9, True), 2: (3, True), 3: (9, True)}),
    # a count's successor advances under the COUNT's within, whatever its
    # own: it shares the head's query, and so does what follows under it
    ("count_head_successor_takes_the_counts_within",
     _prog(("count", 7), ("single", 2), ("single", 7)),
     {0: (7, True), 1: (7, False), 2: (7, False)}),
    ("count_mid_then_successor",
     _prog(("single", 7), ("count", 7), ("single", 7)),
     {1: (7, True), 2: (7, False)}),
    ("count_mid_of_another_within",
     _prog(("single", 7), ("count", 4), ("single", 7)),
     {1: (4, True), 2: (4, False)}),
    ("logical_pair",
     _prog(("single", 6), ("logical", 6), ("single", 6)),
     {1: (6, True), 2: (6, False)}),
    ("final_count_shares",
     _prog(("single", 6), ("single", 6), ("count", 6)),
     {1: (6, True), 2: (6, False)}),
    ("final_count_of_another_within",
     _prog(("single", 6), ("single", 6), ("count", 2)),
     {1: (6, True), 2: (2, True)}),
    # strict succession tests ts[j + 1] directly: no query, none held
    ("sequence_asks_nothing",
     _prog(("single", 5), ("single", 5), ("single", 5), sequence=True),
     {}),
])
def test_expiry_plan_function(name, prog, want):
    from siddhi_tpu.core.nfa_parallel import ExpiryAsk, _expiry_plan
    assert _expiry_plan(prog) == {
        pi: ExpiryAsk(w, fresh=f) for pi, (w, f) in want.items()}, name


def test_expiry_plan_does_not_share_past_a_hop_that_does_not_enforce(
        monkeypatch):
    """Reuse leans on `alive implies j < kl` after every hop since the
    query: a hop kind that stops enforcing it makes its successor ask
    afresh, under the same `within` too."""
    from siddhi_tpu.core import nfa_parallel as npar
    prog = _prog(("single", 5), ("logical", 5), ("single", 5), ("single", 5))
    assert [a.fresh for a in npar._expiry_plan(prog).values()] \
        == [True, False, False]
    monkeypatch.setitem(npar._ENFORCES_EXPIRY, "logical", False)
    assert [a.fresh for a in npar._expiry_plan(prog).values()] \
        == [True, True, False]
