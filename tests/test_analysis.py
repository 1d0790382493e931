"""Static query analyzer + EXPLAIN plane (docs/ANALYSIS.md).

Four surfaces under test:
  * rule engine (analysis/rules.py): a seeded-violation fixture corpus —
    one app per rule, expected rule ids + severities — and a clean
    corpus that must produce ZERO findings;
  * placement accounting (core/placement.py): every interpreter
    fallback in the build path carries a machine-readable Demotion
    visible through rt.explain(), statistics()["placement"], and the
    Prometheus series (the PR-5 silent-demotion regression class);
  * the CLI (python -m siddhi_tpu.analysis) and the service EXPLAIN
    endpoint (byte-for-byte equal to rt.explain());
  * the self-lint (analysis/selflint.py): SL01 silent-demotion swallow
    and SL02 unguarded shared-counter gates, including the
    strip-one-reason test the acceptance criteria pin.
"""
import json
import os
import warnings

import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.analysis import (RULES, StrictAnalysisError, analyze_source,
                                 strict_check)
from siddhi_tpu.analysis.__main__ import extract_apps, main as cli_main
from siddhi_tpu.analysis.selflint import (LOWERING_FILES, lint_package,
                                          lint_source)
from siddhi_tpu.core.placement import DEMOTION_RULES, PlacementLog


def _build(app):
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rt = mgr.create_app_runtime(app)
    return mgr, rt


# ---------------------------------------------------------------------------
# rule engine: seeded-violation corpus (one app per rule) + clean corpus
# ---------------------------------------------------------------------------

FIXTURES = {
    "SA01": """
        define stream S (v double);
        define stream Out (a double, b double);
        @info(name='q') from every e1=S[v > 1] -> e2=S[v < 0]
        select e1.v as a, e2.v as b insert into Out;
    """,
    "SA02": """
        define stream S (v double);
        define stream Out (m double);
        @info(name='q') from S select avg(v) as m insert into Out;
    """,
    "SA03": """
        define stream S (k string, v double);
        define stream Out (a double);
        partition with (k of S) begin
          @info(name='q') from S#window.length(5)
          select sum(v) as a insert into Out;
        end;
    """,
    "SA04": """
        define stream S (v double);
        define stream Out (a double, b double);
        @info(name='q') from S[v > 0] select v as a insert into Out;
    """,
    "SA05": """
        define stream S (v double);
        define stream Dead (x int);
        define stream Out (v double);
        @info(name='q') from S[v > 0] select v insert into Out;
    """,
    "SA06": """
        define stream Out (v double);
        @info(name='q') from Nope select v insert into Out;
    """,
    "SA07": """
        define stream S (v double);
        @info(name='q') from S[v > 0] select v insert into Out;
    """,
    "SA08": """
        @app:patternFamily('scan')
        define stream S (v double);
        define stream Out (a double, b double, c double);
        @info(name='q') from every e1=S[v > 1] -> e2=S[v < 0]<0:3>
        -> e3=S[v > 2] within 1 sec
        select e1.v as a, e2[last].v as b, e3.v as c insert into Out;
    """,
    "SA09": """
        @source(type='tcp', rate.limit='0')
        define stream S (v double);
        define stream Out (v double);
        @info(name='q') from S[v > 0] select v insert into Out;
    """,
    "SA10": """
        @app:patternFamily('scan')
        @app:deviceChunkLanes(8)
        define stream S (v double);
        define stream Out (a double, b double);
        @info(name='q') from every e1=S[v > 1] -> e2=S[v > e1.v]
        within 1 sec select e1.v as a, e2.v as b insert into Out;
    """,
    "SA11": """
        define stream L (k string, v double);
        define stream R (k string, w double);
        define stream Out (v double, w double);
        @info(name='q') from L#window.length(5) join R#window.length(5)
        select v, w insert into Out;
    """,
    "SA12": """
        @app:devicePatterns('prefer')
        define stream S (v double);
        define stream Out (a double, b double);
        @info(name='q') from every e1=S[v > 1] -> e2=S[v > e1.v]
        within 1 sec select e1.v as a, e2.v as b insert into Out;
    """,
    "SA13": """
        @app:durability('fsync')
        @source(type='tcp', port='0')
        define stream S (v double);
        define stream Out (v double);
        @info(name='q') from S[v > 0] select v insert into Out;
    """,
    "SA14": """
        @app:durability('batch', dir='/tmp/wal')
        @app:replication('semi-sync', peer='127.0.0.1:7071')
        @source(type='tcp', port='0')
        define stream S (v double);
        define stream Out (v double);
        @info(name='q') from S[v > 0] select v insert into Out;
    """,
    "SA15": """
        define stream Trades (sym string, price double, ts long);
        define aggregation TradeAgg
        from Trades
        select sym, sum(price) as total
        group by sym
        aggregate by ts every sec, min;
    """,
}

CLEAN = [
    """
    define stream S (v double);
    define stream Out (v double);
    @info(name='q') from S[v > 1.0] select v insert into Out;
    """,
    """
    define stream S (k string, v double);
    define stream Mid (v double);
    define stream Out (v double);
    @info(name='q1') from S[v > 0] select v insert into Mid;
    @info(name='q2') from Mid[v > 1] select v insert into Out;
    """,
    """
    @app:partitionCapacity(64)
    define stream Txn (card string, amt int);
    define stream Alerts (a int, b int);
    partition with (card of Txn) begin
      @info(name='p') from every e1=Txn[amt > 100] -> e2=Txn[amt > e1.amt]
      within 1 min select e1.amt as a, e2.amt as b insert into Alerts;
    end;
    """,
]


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_seeded_violation_caught(rule):
    findings = analyze_source(FIXTURES[rule])
    hits = [f for f in findings if f.rule_id == rule]
    assert hits, (rule, [str(f) for f in findings])
    assert all(f.severity == RULES[rule][0] for f in hits)
    # a fixture must not trip UNRELATED error-severity rules (the
    # violation is seeded, everything else in the app is legal)
    assert all(f.rule_id == rule
               for f in findings if f.severity == "error"), \
        [str(f) for f in findings]


@pytest.mark.parametrize("i", range(len(CLEAN)))
def test_clean_app_zero_findings(i):
    assert analyze_source(CLEAN[i]) == []


def test_sa04_lossy_type_mismatch():
    findings = analyze_source("""
        define stream S (v double);
        define stream Out (a int);
        @info(name='q') from S[v > 0] select v as a insert into Out;
    """)
    sa04 = [f for f in findings if f.rule_id == "SA04"]
    assert sa04 and "lossy" in sa04[0].message


@pytest.mark.parametrize("purge", ["@purge(retention='1 hour')",
                                   "@purge(enable='false')"])
def test_sa15_silent_on_purge_decision(purge):
    """Any @purge on the aggregation — a retention span OR an explicit
    opt-out — is a decision; SA15 only fires on the silent default."""
    findings = analyze_source(f"""
        define stream Trades (sym string, price double, ts long);
        {purge}
        define aggregation TradeAgg
        from Trades
        select sym, sum(price) as total
        group by sym
        aggregate by ts every sec, min;
    """)
    assert not [f for f in findings if f.rule_id == "SA15"], \
        [str(f) for f in findings]


def test_sa15_silent_without_group_by():
    # no group key: one row per bucket, bounded by elapsed time alone —
    # not the cardinality blow-up the rule is about
    findings = analyze_source("""
        define stream Trades (sym string, price double, ts long);
        define aggregation TotalAgg
        from Trades
        select sum(price) as total
        aggregate by ts every sec, min;
    """)
    assert not [f for f in findings if f.rule_id == "SA15"], \
        [str(f) for f in findings]


def test_sa08_reuses_classify_reason_strings():
    # the analysis-time verdict is literally a classify_parallel reason
    findings = analyze_source(FIXTURES["SA08"])
    msg = next(f.message for f in findings if f.rule_id == "SA08")
    assert "count quantifier" in msg


# ---------------------------------------------------------------------------
# placement accounting: demotions visible through explain()
# ---------------------------------------------------------------------------

def test_placement_log_basics():
    log = PlacementLog()
    with pytest.raises(ValueError):
        log.demote("q", "D-NOPE", "bogus rule id")
    d1 = log.demote("q", "D-SHAPE", "first reason")
    d2 = log.demote("q", "D-SHAPE", "repeat ignored")
    assert d1 is d2 and len(log) == 1          # idempotent per key
    assert d1.reason == "first reason"
    log.demote("q", "D-FAMILY", "rejected family", alternative="scan")
    log.demote("q2", "D-FUSED", "group too small",
               alternative="fused-lanes")
    # D-FAMILY / D-FUSED do not count as interpreter exits
    assert len(log) == 3 and log.interp_demotions() == 1
    cause = log.demote("q3", "D-FILTER", "lowering failed",
                       cause=RuntimeError("boom"))
    assert cause.to_dict()["cause"] == "RuntimeError: boom"
    assert set(DEMOTION_RULES) >= {d.rule_id for d in log.records()}


def test_windowless_agg_demoted_with_shape_reason():
    mgr, rt = _build("""
        define stream S (v double);
        @info(name='q') from S select avg(v) as m insert into Agg;
    """)
    ent = rt.explain()["queries"]["q"]
    assert ent["path"] == "interpreter"
    dems = ent["demotions"]
    assert dems[0]["rule_id"] == "D-SHAPE"
    assert "aggregation without a window" in dems[0]["reason"]
    mgr.shutdown()


def test_window_plan_demotion_cause_visible():
    """The build.py bare-except regression (satellite 1): a device
    window rejection must surface its cause in explain(), not vanish
    into a silent interpreter fallback."""
    mgr, rt = _build("""
        define stream S (v double);
        define stream Out (m double);
        @info(name='q') from S#window.sort(5, v)
        select max(v) as m insert into Out;
    """)
    ent = rt.explain()["queries"]["q"]
    assert ent["path"] == "interpreter"
    d = next(d for d in ent["demotions"] if d["rule_id"] == "D-WINDOW")
    assert d["reason"] == "window sort"
    assert d["cause"] == "DeviceWindowUnsupported: window sort"
    assert d["alternative"] == "device-window"
    mgr.shutdown()


def test_filter_lowering_failure_reason_visible(monkeypatch):
    """The literal PR-5 bug shape: FilterProjectPlan raising used to be
    swallowed by `except Exception: pass` — now the cause must reach
    explain()."""
    import siddhi_tpu.core.build as build

    def boom(*a, **k):
        raise RuntimeError("synthetic lowering failure")

    monkeypatch.setattr(build, "FilterProjectPlan", boom)
    mgr, rt = _build("""
        define stream S (v double);
        define stream Out (v double);
        @info(name='q') from S[v > 1.0] select v insert into Out;
    """)
    ent = rt.explain()["queries"]["q"]
    assert ent["path"] == "interpreter"
    d = next(d for d in ent["demotions"] if d["rule_id"] == "D-FILTER")
    assert d["cause"] == "RuntimeError: synthetic lowering failure"
    mgr.shutdown()


def test_policy_optout_recorded():
    mgr, rt = _build("""
        @app:deviceFilters('never')
        define stream S (v double);
        define stream Out (v double);
        @info(name='q') from S[v > 1.0] select v insert into Out;
    """)
    d = rt.explain()["queries"]["q"]["demotions"][0]
    assert d["rule_id"] == "D-POLICY"
    assert "deviceFilters" in d["reason"]
    mgr.shutdown()


def test_geometry_provenance_annotation_vs_default():
    mgr, rt = _build("""
        @app:devicePipeline(2)
        define stream S (v double);
        define stream Out (v double);
        @info(name='q') from S[v > 1.0] select v insert into Out;
    """)
    geo = rt.explain()["queries"]["q"]["geometry"]
    assert geo["pipeline_depth"] == {"value": 2, "source": "annotation"}
    mgr.shutdown()
    mgr, rt = _build("""
        define stream S (v double);
        define stream Out (v double);
        @info(name='q') from S[v > 1.0] select v insert into Out;
    """)
    geo = rt.explain()["queries"]["q"]["geometry"]
    assert geo["pipeline_depth"]["source"] == "default"
    mgr.shutdown()


_GEO_FILTER = """
    define stream S (v double);
    @info(name='q') from S[v > 1.0] select v insert into Out;
"""
_GEO_PATTERN = """
    @app:devicePatterns('always')
    define stream S (v double);
    @info(name='q') from every e1=S[v > 1.0] -> e2=S[v > e1.v] within 1 sec
    select e1.v as a, e2.v as b insert into Out;
"""
_GEO_FUSED = "define stream S (v double);\n" + "\n".join(
    f"@info(name='q{i}') from every e1=S[v > {i}.5] -> e2=S[v > e1.v] "
    f"within 1 sec select e1.v as a, e2.v as b insert into Out{i};"
    for i in range(16))
_GEO_AGG = """
    define stream S (k string, v double, ts long);
    define aggregation A from S select k, sum(v) as s group by k
    aggregate by ts every sec;
"""


def _fused(rt):
    return sorted(p.n_queries for p in rt._plans
                  if type(p).__name__ == "MultiQueryDevicePatternPlan")


# knob -> (annotation, app, where EXPLAIN reports it, what the built
#          plan holds, (annotated text, value, held), (default, held))
_GEO_KNOBS = {
    "pipeline_depth": (
        "devicePipeline", _GEO_FILTER,
        lambda ex: ex["queries"]["q"]["geometry"],
        lambda rt: rt._plans[0].pipeline_depth, ("2", 2, 2), (0, 0)),
    "chunk_lanes": (
        "deviceChunkLanes", _GEO_PATTERN,
        lambda ex: ex["queries"]["q"]["geometry"],
        lambda rt: rt._plans[0]._stateless_lanes, ("8", 8, 8), (64, 64)),
    "plan_family": (
        "patternFamily", _GEO_PATTERN,
        lambda ex: ex["queries"]["q"]["geometry"],
        lambda rt: rt._plans[0].family,
        ("'seq'", "seq", "seq"), ("scan", "scan")),
    "lane_pack": (
        "fusedLanes", _GEO_FUSED,
        lambda ex: min(ex["queries"].items())[1]["geometry"],
        _fused, ("8", 8, [8, 8]), (0, [16])),
    "agg_capacity": (
        "aggCapacity", _GEO_AGG,
        lambda ex: ex["aggregations"]["A"]["geometry"],
        lambda rt: sorted({r.capacity for r in rt.aggregations["A"]
                           .device_plan.rings.values()}),
        ("4", 8, [8]), (1024, [1024])),     # the floor of 8 holds
}


@pytest.mark.parametrize("annotated", [True, False],
                         ids=["annotation", "default"])
@pytest.mark.parametrize("knob", sorted(_GEO_KNOBS))
def test_geometry_knob_has_two_sources(knob, annotated):
    """Each of the five geometry annotations, set and absent: the built
    plan holds the value and EXPLAIN names where it came from."""
    ann, app, where, held_by, set_, default = _GEO_KNOBS[knob]
    if annotated:
        text, value, held = set_
        app = f"@app:{ann}({text})\n" + app
    else:
        value, held = default
    mgr, rt = _build(app)
    source = "annotation" if annotated else "default"
    assert where(rt.explain())[knob] == {"value": value, "source": source}
    assert held_by(rt) == held
    mgr.shutdown()


@pytest.mark.parametrize("where", ["env", "home"])
def test_stale_tuning_file_changes_nothing(where, tmp_path, monkeypatch):
    """Geometry has no source outside the app text: a file where the
    deleted tuning cache used to live, keyed for this very app on this
    very device and JAX version, must not move family, depth or batch
    capacity.  (At the parent of PR 31 it moved all three.)"""
    import hashlib

    import jax

    def key(family, payload):
        sig = hashlib.sha1(f"{family}|{payload!r}".encode()).hexdigest()[:20]
        return (f"{family}:{sig}|{jax.devices()[0].device_kind}"
                f"|jax{jax.__version__}")

    mgr, rt = _build(_GEO_PATTERN)
    stale = {"batch": 64, "pipeline_depth": 3, "plan_family": "seq"}
    entries = {
        key("pattern", rt._plans[0]._q_ast): {
            "geometry": stale, "family": "pattern"},
        key("app", (tuple(sorted((sid, repr(sd)) for sid, sd in
                                 rt.app.stream_definitions.items())),
                    tuple(repr(e) for e in rt.app.execution_elements))): {
            "geometry": {"batch": 64}, "family": "app"}}
    mgr.shutdown()
    if where == "env":
        path = tmp_path / "tuning.json"
        monkeypatch.setenv("SIDDHI_TUNE_CACHE", str(path))
    else:
        path = tmp_path / ".cache" / "siddhi_tpu" / "tuning.json"
        path.parent.mkdir(parents=True)
        monkeypatch.delenv("SIDDHI_TUNE_CACHE", raising=False)
        monkeypatch.setenv("HOME", str(tmp_path))
    path.write_text(json.dumps({"version": 1, "entries": entries}))
    mgr, rt = _build(_GEO_PATTERN)
    plan = rt._plans[0]
    assert (plan.family, plan.pipeline_depth, rt.batch_capacity) \
        == ("scan", 0, 2048)
    mgr.shutdown()


def test_unknown_pattern_family_is_a_plan_error():
    from siddhi_tpu.core.planner import PlanError
    with pytest.raises(PlanError, match="unknown family"):
        _build("@app:patternFamily('nope')\n" + _GEO_FILTER)


def test_ineligible_family_reasons_reach_explain():
    """Satellite: every classify_parallel reason string for the 5
    ineligible shapes is reachable through rt.explain() — both in the
    per-family rejection map and as a D-FAMILY demotion."""
    from test_plan_families import HEAD, INELIGIBLE
    force = ("@app:patternFamily('scan')\n@app:deviceChunkLanes(0)\n"
             "@app:devicePatterns('always')\n")
    for name, (q, frag) in INELIGIBLE.items():
        mgr, rt = _build(force + HEAD + q)
        ent = rt.explain()["queries"]["q"]
        assert ent["path"] == "device" and ent["family"] == "seq", \
            (name, ent)
        for fam in ("scan", "dfa"):
            assert frag.lower() in str(ent["rejected"][fam]).lower(), \
                (name, fam, ent["rejected"])
        dem = [d for d in ent["demotions"] if d["rule_id"] == "D-FAMILY"]
        assert dem and frag.lower() in dem[0]["reason"].lower(), \
            (name, dem)
        assert dem[0]["alternative"] == "scan"
        mgr.shutdown()


@pytest.mark.parametrize("force,want", [
    ("", {"built": 1, "shared": 1}),   # the default family here is scan
    ("@app:patternFamily('seq')\n", None),            # no expiry heap there
])
def test_explain_shows_expiry_queries_of_parallel_families(force, want):
    """`expiry_queries` sits beside `family` for a device pattern query of
    a parallel family: how many `within` descents the block makes per head
    (built) and how many positions read one already made (shared).  The
    sequential kernel has no such query and no such key."""
    mgr, rt = _build(force + "@app:devicePatterns('always')\n" + """
        define stream S (sym string, price double);
        @info(name='q')
        from every e1=S[price > 100] -> e2=S[price > e1.price]
            -> e3=S[price > e2.price] within 10 sec
        select e1.price as p1, e2.price as p2, e3.price as p3
        insert into Out;
    """)
    ent = rt.explain()["queries"]["q"]
    assert ent["path"] == "device" and ent["kind"] == "pattern"
    assert ent["family"] == ("scan" if want else "seq")
    assert ent.get("expiry_queries") == want, ent
    assert list(ent)[:5] == ["path", "plan", "kind", "family"] \
        + (["expiry_queries"] if want else ["rejected"]), list(ent)
    mgr.shutdown()


@pytest.mark.parametrize("part,events,side", [
    (True, 40, "dense"),      # a lane grid of 64-slot lanes
    (False, 40, "dense"),     # the flat block's capacity starts AT the bound
    (False, 2100, "tree"),    # ... and its next bucket, 6144, is past it
    (False, 0, "dense"),      # before any flush: the (2, 8) build validation
])
def test_explain_shows_first_hit_form_of_the_block_last_dispatched(
        part, events, side):
    """`first_hit` sits beside `expiry_queries`: the block's first-hit
    queries by the form that answers them, chosen from the block's F alone
    (nfa_parallel.DENSE_MAX_F), and the pairs the dense form reduces."""
    from siddhi_tpu.core.nfa_parallel import DENSE_MAX_F
    q = """@info(name='q')
        from every e1=S[price > 100] -> e2=S[price > e1.price]
            -> e3=S[price > e2.price] within 10 sec
        select e1.price as p1, e2.price as p2, e3.price as p3
        insert into Out;"""
    head = "define stream S (sym string, price double);\n"
    if part:
        app = "@app:partitionCapacity(8)\n" + head \
            + "partition with (sym of S)\nbegin\n" + q + "\nend;\n"
    else:
        app = "@app:devicePatterns('always')\n" + head + q
    mgr, rt = _build(app)
    rt.start()
    ih = rt.input_handler("S")
    for i in range(events):
        ih.send((f"K{i % 3}", 101.0 + i % 7), timestamp=1_700_000_000_000 + i)
    rt.flush()
    ent = rt.explain()["queries"]["q"]
    assert list(ent)[:6] == ["path", "plan", "kind", "family",
                             "expiry_queries", "first_hit"], list(ent)
    fh = ent["first_hit"]
    assert sorted(fh) == ["F", "dense", "lanes", "pairs_per_call", "tree"]
    assert (fh["F"] <= DENSE_MAX_F) == (side == "dense"), fh
    if not part and events:
        assert fh["F"] == (DENSE_MAX_F if side == "dense" else 6144), fh
    # one expiry query (shared down the chain) and two threshold hops
    assert (fh["dense"], fh["tree"]) == ((3, 0) if side == "dense"
                                         else (0, 3)), fh
    assert fh["pairs_per_call"] == (3 * fh["lanes"] * fh["F"] ** 2
                                    if side == "dense" else 0), fh
    mgr.shutdown()


def test_placement_statistics_and_prometheus():
    from siddhi_tpu.core.telemetry import render_prometheus
    mgr, rt = _build("""
        @app:name('P')
        define stream S (v double);
        @info(name='dev') from S[v > 1.0] select v insert into Out;
        @info(name='host') from S select avg(v) as m insert into Agg;
    """)
    pl = rt.statistics()["placement"]
    assert pl["device"] == 1 and pl["interpreter"] == 1
    assert pl["interp_demotions"] == 1
    assert pl["queries"]["dev"]["path"] == "device"
    assert pl["queries"]["host"] == {"path": "interpreter",
                                     "kind": "single", "demotions": 1}
    text = render_prometheus({"P": rt.stats.report()})
    assert 'siddhi_tpu_interp_demotions{app="P"} 1' in text
    assert ('siddhi_tpu_placement_queries{app="P",path="device"} 1'
            in text)
    assert ('siddhi_tpu_placement_queries{app="P",path="interpreter"} 1'
            in text)
    assert ('siddhi_tpu_query_placement{app="P",query="dev",'
            'path="device"} 1' in text)
    mgr.shutdown()


def test_strict_analysis_blocks_warn_findings():
    app = """
        @app:name('Strict') @app:strictAnalysis
        define stream S (v double);
        @info(name='q') from S select avg(v) as m insert into Out;
    """
    with pytest.raises(StrictAnalysisError) as ei:
        SiddhiManager().create_app_runtime(app)
    assert any(f.rule_id == "SA02" for f in ei.value.findings)
    # the same app without the annotation deploys (with findings)
    mgr, rt = _build(app.replace("@app:strictAnalysis", ""))
    assert strict_check.__module__  # imported surface stays stable
    mgr.shutdown()


def test_strict_analysis_passes_clean_app():
    mgr, rt = _build("@app:name('C') @app:strictAnalysis\n" + CLEAN[0])
    assert rt.explain()["placement"]["interp_demotions"] == 0
    mgr.shutdown()


# ---------------------------------------------------------------------------
# CLI: python -m siddhi_tpu.analysis
# ---------------------------------------------------------------------------

def test_cli_json_and_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.siddhi"
    bad.write_text(FIXTURES["SA06"])
    assert cli_main(["--json", str(bad)]) == 1        # error severity
    out = json.loads(capsys.readouterr().out)
    assert out["severities"]["error"] == 1
    assert out["apps"][0]["findings"][0]["rule_id"] == "SA06"

    clean = tmp_path / "clean.siddhi"
    clean.write_text(CLEAN[0])
    assert cli_main(["--json", str(clean)]) == 0
    capsys.readouterr()

    warn = tmp_path / "warn.siddhi"
    warn.write_text(FIXTURES["SA02"])
    assert cli_main([str(warn)]) == 0                 # warn passes...
    capsys.readouterr()
    assert cli_main(["--strict", str(warn)]) == 1     # ...unless strict
    capsys.readouterr()


def test_cli_expect_pinning(tmp_path, capsys):
    p = tmp_path / "warn.siddhi"
    p.write_text(FIXTURES["SA02"])
    assert cli_main(["--expect", "SA02", str(p)]) == 0
    capsys.readouterr()
    assert cli_main(["--expect", "SA02,SA05", str(p)]) == 1
    capsys.readouterr()


def test_cli_extracts_apps_from_python_samples(tmp_path):
    py = tmp_path / "sample.py"
    py.write_text(
        'X = 1\nAPP = """\ndefine stream S (v double);\n'
        '@info(name=\'q\') from S[v > 0] select v insert into Out;\n"""\n'
        'OTHER = "not an app"\n')
    apps = extract_apps(str(py))
    assert len(apps) == 1 and apps[0][0].endswith("sample.py:APP")
    assert "define stream S" in apps[0][1]


def test_cli_explain_matches_runtime_explain(tmp_path, capsys):
    """The CLI's --explain JSON is the same EXPLAIN plane rt.explain()
    serves — including every ineligible-shape reason (satellite: CLI
    half of the classify_parallel reason matrix)."""
    from test_plan_families import HEAD, INELIGIBLE
    force = ("@app:patternFamily('scan')\n@app:deviceChunkLanes(0)\n"
             "@app:devicePatterns('always')\n")
    paths = []
    for name, (q, _frag) in sorted(INELIGIBLE.items()):
        p = tmp_path / f"{name}.siddhi"
        p.write_text(force + HEAD + q)
        paths.append(str(p))
    rc = cli_main(["--json", "--explain"] + paths)
    out = json.loads(capsys.readouterr().out)
    assert rc == 0                  # warns (SA08/SA10) don't fail plain
    by_src = {os.path.basename(e["source"]): e for e in out["apps"]}
    for name, (q, frag) in INELIGIBLE.items():
        entry = by_src[f"{name}.siddhi"]
        ex = entry["explain"]
        qd = ex["queries"]["q"]
        assert frag.lower() in str(qd["rejected"]["scan"]).lower(), \
            (name, qd)
        # the forced-but-ineligible annotation ALSO fires SA08 at
        # analysis time, before any build happens
        assert any(f["rule_id"] == "SA08" for f in entry["findings"]), \
            (name, entry["findings"])
        mgr, rt = _build(force + HEAD + q)
        assert ex == rt.explain(), name       # CLI == runtime, verbatim
        mgr.shutdown()


def test_cli_self_lint_gate_is_green(capsys):
    assert cli_main(["--self"]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# self-lint: SL01 / SL02
# ---------------------------------------------------------------------------

SWALLOW = """
def plan(rt, name):
    try:
        lower()
    except Exception:
        pass
"""

SWALLOW_DEMOTED = """
def plan(rt, name):
    try:
        lower()
    except Exception as e:
        rt.placement.demote(name, "D-FILTER", "lowering failed", cause=e)
"""

SWALLOW_RERAISED = """
def plan(rt, name):
    try:
        lower()
    except Exception:
        raise
"""

SWALLOW_PRAGMA = """
def plan(rt, name):
    try:
        lower()
    except Exception:   # lint: allow-swallow (best-effort probe)
        pass
"""


def test_sl01_swallow_variants():
    assert [f.rule_id for f in lint_source(SWALLOW, "core/build.py")] \
        == ["SL01"]
    assert lint_source(SWALLOW_DEMOTED, "core/build.py") == []
    assert lint_source(SWALLOW_RERAISED, "core/build.py") == []
    assert lint_source(SWALLOW_PRAGMA, "core/build.py") == []
    # outside the lowering-path file set the swallow is out of scope
    assert lint_source(SWALLOW, "net/frame.py") == []


COUNTER_RACE = """
import threading
class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.frames_total = 0
    def bump(self):
        self.frames_total += 1
"""


def test_sl02_counter_variants():
    assert [f.rule_id for f in lint_source(COUNTER_RACE, "net/x.py")] \
        == ["SL02"]
    guarded = COUNTER_RACE.replace(
        "        self.frames_total += 1",
        "        with self._lock:\n            self.frames_total += 1")
    assert lint_source(guarded, "net/x.py") == []
    locked_name = COUNTER_RACE.replace("def bump", "def bump_locked")
    assert lint_source(locked_name, "net/x.py") == []
    pragma = COUNTER_RACE.replace(
        "self.frames_total += 1",
        "self.frames_total += 1   # lint: unlocked-ok (single writer)")
    assert lint_source(pragma, "net/x.py") == []
    # a class that owns no lock makes no locking promise
    no_lock = COUNTER_RACE.replace(
        "        self._lock = threading.Lock()\n", "")
    assert lint_source(no_lock, "net/x.py") == []


def test_self_lint_package_is_clean():
    assert [str(f) for f in lint_package()] == []


def test_self_lint_catches_stripped_reason():
    """Acceptance criterion: strip ONE recorded Demotion out of a real
    lowering file and the lint must catch the now-silent swallow."""
    import ast as pyast
    from siddhi_tpu.core import build
    path = build.__file__
    src = open(path, encoding="utf-8").read()
    assert "core/build.py" in LOWERING_FILES
    assert not lint_source(src, "core/build.py"), "gate not green?"
    tree = pyast.parse(src)
    victim = None
    for node in pyast.walk(tree):
        if not isinstance(node, pyast.ExceptHandler):
            continue
        body_src = "\n".join(pyast.unparse(s) for s in node.body)
        if "demote" in body_src and not any(
                isinstance(n, pyast.Raise) for stmt in node.body
                for n in pyast.walk(stmt)):
            victim = node
            break
    assert victim is not None, "build.py has no demoting handler?"
    lines = src.splitlines(True)
    for i in range(victim.lineno - 1, victim.end_lineno):
        lines[i] = lines[i].replace("demote", "demoted_no_more")
    stripped = "".join(lines)
    findings = lint_source(stripped, "core/build.py")
    assert [f.rule_id for f in findings] == ["SL01"], \
        [str(f) for f in findings]
    assert f"core/build.py:{victim.lineno}" == findings[0].subject


def test_quarantine_records_demotion_in_explain():
    """The runtime half of the taxonomy: a degradation-ladder
    quarantine (docs/RELIABILITY.md) must surface as a D-QUARANTINE
    demotion — the query reads `interpreter` in explain() with the
    device failure as its cause."""
    from siddhi_tpu.core.faults import FaultInjector
    mgr = SiddhiManager()
    rt = mgr.create_app_runtime("""
        @OnError(action='store')
        define stream S (sym string, p double);
        @info(name='q') from S#window.length(4)
        select sum(p) as sp insert into Out;
    """)
    assert rt.explain()["queries"]["q"]["path"] == "device"
    rt.fault_injector = FaultInjector(seed=3,
                                      counts={"dispatch": 100_000})
    h = rt.input_handler("S")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for k in range(4):
            h.send([(f"K{j % 3}", float(j + k)) for j in range(8)])
            rt.flush()
    ent = rt.explain()["queries"]["q"]
    assert ent["path"] == "interpreter"
    d = next(d for d in ent["demotions"]
             if d["rule_id"] == "D-QUARANTINE")
    assert "consecutive device dispatch failures" in d["reason"]
    assert "RESOURCE_EXHAUSTED" in d["cause"]
    pl = rt.statistics()["placement"]
    assert pl["interpreter"] == 1 and pl["interp_demotions"] == 1
    mgr.shutdown()


def test_partition_clones_aggregate_per_query():
    """Per-key host-clone plans (`<base>#<inst>`) must collapse onto
    their base query in placement/explain — counts are per QUERY, and
    the per-query Prometheus label set must not scale with partition
    key cardinality."""
    from siddhi_tpu.core.telemetry import render_prometheus
    mgr, rt = _build("""
        @app:name('PK')
        define stream S (k string, v double);
        define stream Out (a double);
        partition with (k of S) begin
          @info(name='q') from S#window.length(4)
          select sum(v) as a insert into Out;
        end;
    """)
    h = rt.input_handler("S")
    h.send([(f"K{i}", float(i)) for i in range(4)])   # 4 key instances
    rt.flush()
    pl = rt.statistics()["placement"]
    assert pl["interpreter"] + pl["device"] == 2      # group + q, not 5
    assert set(pl["queries"]) == {"#partition_0", "q"}
    assert pl["queries"]["q"]["instances"] == 4
    ex = rt.explain()
    assert set(ex["queries"]) == {"#partition_0", "q"}
    assert ex["queries"]["q"]["instances"] == 4
    text = render_prometheus({"PK": rt.stats.report()})
    assert text.count('siddhi_tpu_query_placement{app="PK",query="q"') == 1
    mgr.shutdown()
