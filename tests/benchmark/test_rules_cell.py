"""The cell `rules1k.sat`: its app file (bench.py's `c5_app(1000)` byte for
byte), its plain reference on hand-written streams for each of the four rule
shapes (each case read off the host interpreter when it was written), its
judge (a dropped, an altered and a doubled row in each kind of output
stream), its control, the turn-away of an engine that does not cut a fused
flush, and that the traced CPU rehearsal finds every per-layer metric the
manifest lists for the cell.  The cell joins test_rehearsal.py,
test_span_metrics.py and test_manifest.py by being in the manifest."""
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import numpy as np
import pytest

from benchmark import compare, control, engine, manifest
from benchmark.reference import rules_mixed
from test_rehearsal import ROOT, last_line, run_cell

CELL = "rules1k.sat"
T0 = 1_700_000_000_000


def _cell(rehearse=True):
    cell = manifest.Manifest().cell(CELL)
    if rehearse:
        cell["config"] = manifest.rehearsed(cell["config"])
        cell["traffic"] = manifest.rehearsed(cell["traffic"])
    return cell


Q = _cell()["config"]["query"]


# -- the configuration ----------------------------------------------------------

def test_the_app_file_is_c5_app_1000_byte_for_byte():
    sys.path.insert(0, ROOT)
    import bench                        # while bench.py stands
    with open(os.path.join(ROOT, "benchmark", "apps", "rules1k.siddhi")) as f:
        assert f.read() == bench.c5_app(1000)


def test_the_configuration_states_the_deployment():
    cell = _cell(rehearse=False)
    cfg, tr = cell["config"], cell["traffic"]
    assert cfg["annotations"] == ["@app:deviceMesh('never')"]
    assert cfg["reduced"] == ["stream_events"] and cfg["stateful"] is True
    assert cfg["tape"] == "stock" and cfg["tape_params"] == {
        "keys": 8, "dt_ms": 50, "price_lo": 90.0, "price_hi": 130.0,
        "price_step": 0.25}
    assert cfg["fused_groups"] == {"plans": 4, "rules_per_plan": 250,
                                   "families": ["scan", "seq", "seq", "scan"]}
    assert cfg["expect"] == {"path": "device", "kind": "multi_query",
                             "family": None, "sharded_over": 0}
    assert cfg["kernel"] == "fused_lane_block"
    assert set(cfg["guarantees"]) == {"match_set", "order", "state",
                                      "delivery", "device_precision"}
    assert cfg["compare_events_budget"] >= 2 ** 20
    assert tr["driver"] == "inproc_sat" and tr["batch"] == 65536
    assert tr["warm_batches"] == 4 and "skew" not in tr
    # the query stanza is the generator's formula: rule i of the app file
    with open(os.path.join(ROOT, "benchmark", "apps", "rules1k.siddhi")) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("@info")]
    assert len(lines) == Q["rules"] == 1000
    for i in (0, 1, 2, 3, 5, 10, 998, 999):
        r = rules_mixed.rule_of(i, Q)
        assert f"name='q{i}'" in lines[i] and f"Out{r['stream']};" in lines[i]
        head = {2: r["lo"] + Q["absent_head_above_lo"]}.get(r["shape"],
                                                            r["lo"])
        assert f"e1=StockStream[price > {head}]" in lines[i]
        assert ("every" in lines[i]) == (r["shape"] in (0, 3))
        if r["shape"] == 2:
            assert f"price < {r['lo'] - Q['absent_below_lo']}] for 500 " \
                in lines[i]
    pairs = {(rules_mixed.rule_of(i, Q)["shape"],
              rules_mixed.rule_of(i, Q)["lo"]) for i in range(1000)}
    assert len(pairs) == 12
    # one shape an output stream: a stream's rows have one set of columns
    assert all(rules_mixed.rule_of(i, Q)["stream"] % 4 == i % 4
               for i in range(1000))


# -- the reference on hand-written streams --------------------------------------

def _rows(shape, prices, lo=123, dt=50):
    price = np.asarray(prices, np.float64)
    ts = T0 + np.arange(len(price), dtype=np.int64) * dt
    m = rules_mixed.matches(price, ts, {"shape": shape, "lo": lo}, Q)
    return [(int(t - T0), a, b, int(k))
            for t, a, b, k in zip(m["ts"], m["p1"], m["p2"], m["at"])]


@pytest.mark.parametrize("prices,want", [
    # e2 exactly `within` (1 s = 20 events) after e1 counts; one more does not
    ([124] + [100] * 19 + [125], [(1000, 124, 125, 20)]),
    ([124] + [100] * 20 + [125], []),
    # every event above lo opens a chain; each takes its FIRST greater event
    ([124, 125, 126], [(50, 124, 125, 1), (100, 125, 126, 2)]),
    ([124, 124.5, 124.25, 125], [(50, 124, 124.5, 1), (150, 124.5, 125, 3),
                                 (150, 124.25, 125, 3)]),
    ([123, 122, 130], []),              # at the threshold is not above it
])
def test_shape_0_every_e1_then_greater_within(prices, want):
    assert _rows(0, prices) == want


@pytest.mark.parametrize("prices,want", [
    ([124, 125] + [100] * 38 + [126], [(2000, 124, 126, 40)]),
    ([124, 125] + [100] * 39 + [126], []),
    ([124, 125, 124.5, 126, 127], [(150, 124, 126, 3), (200, 125, 127, 4),
                                   (200, 124.5, 127, 4)]),
])
def test_shape_3_rising_chain_of_three_within(prices, want):
    assert _rows(3, prices) == want


@pytest.mark.parametrize("prices,want", [
    ([100, 124, 125, 126], [(100, 124, 125, 2)]),   # before e1: ignored
    ([124, 125, 126], [(50, 124, 125, 1)]),         # one arm, one row, ever
    ([124, 100, 125, 126, 127], []),                # a failed step ends it
    ([100, 100, 124, 123, 125, 126], []),
    ([124, 124, 125], []),                          # and it never re-arms
    ([100, 100, 124], []),
])
def test_shape_1_a_strict_sequence_started_once(prices, want):
    assert _rows(1, prices) == want


@pytest.mark.parametrize("prices,want", [
    # lo 123: head above 124, forbidden below 93, the row at e1 + 500 ms
    ([100, 125] + [100] * 12, [(550, 125, 0, 11)]),
    ([100, 125, 126] + [100] * 12, [(550, 125, 0, 11)]),
    ([125, 100, 92] + [100] * 12 + [126] + [100] * 12, []),     # killed, for good
    ([125] + [100] * 9 + [92] + [100] * 3, [(500, 125, 0, 10)]),  # at the deadline
    ([125] + [100] * 8 + [92] + [100] * 4, []),     # one event inside it
    ([125, 100, 100], []),              # no event at or past the deadline yet
    ([124, 100] + [100] * 12, []),
])
def test_shape_2_one_wait_without_a_forbidden_arrival(prices, want):
    assert _rows(2, prices) == want


def test_the_reference_agrees_with_the_interpreter_on_a_seeded_tape():
    """All four shapes, 16 rules, through the host interpreter
    (`@app:devicePatterns('never')`): the repo's statement of Siddhi's
    meaning, which decided every case above."""
    import warnings
    from siddhi_tpu import SiddhiManager
    sys.path.insert(0, ROOT)
    import bench
    q = {**Q, "rules": 16}
    tape = engine.tape_of(_cell(), 2 ** 31 + 5)
    b = tape.batch(0)
    price, ts = b["price"][:1500], b["ts"][:1500]
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime("@app:devicePatterns('never')\n"
                                    + bench.c5_app(16))
    got = [[] for _ in range(16)]
    for j in range(16):
        rt.add_batch_callback(f"Out{j}", lambda bb, g=got[j]: g.extend(
            (int(t),) + tuple(float(bb.columns[c][i])
                              for c in sorted(bb.columns))
            for i, t in enumerate(bb.timestamps)))
    rt.start()
    rt.input_handler("StockStream").send_batch(
        {"symbol": np.full(1500, rt.strings.encode("K0"), np.int32),
         "price": price, "volume": np.ones(1500, np.int32)}, ts)
    rt.flush()
    mgr.shutdown()
    total = 0
    for j, mine in enumerate(rules_mixed.owed(price, ts, q)):
        want = sorted(
            (int(t), a) if j % 4 == 2 else (int(t), a, c)
            for r, n in mine for _ in range(n)
            for t, a, c in zip(r["ts"], r["p1"], r["p2"]))
        assert sorted(got[j]) == want, j
        total += len(want)
    assert total > 300


# -- the judge ---------------------------------------------------------------------

def _judged(seed, n_batches, tamper=None):
    """The checks, and the judge, had the program delivered rule by rule
    what the plain reference computes, `tamper`ed with on the way:
    `tamper(stream, k, rows) -> rows` for the k-th batch of a stream."""
    cell = _cell()
    tape = engine.tape_of(cell, seed)
    judge = rules_mixed.Judge(cell["config"], tape, seed)
    made = [tape.batch(i) for i in range(n_batches)]
    price = np.concatenate([b["price"] for b in made])
    ts = np.concatenate([b["ts"] for b in made])
    for j, mine in enumerate(rules_mixed.owed(price, ts, Q)):
        k = 0
        for rows, n in mine:
            for _ in range(n):
                r = rows if tamper is None else tamper(j, k, rows)
                judge.add_rows(j, r["ts"], r["p1"], r["p2"])
                k += 1
    return judge.judge(n_batches), judge


def test_what_the_reference_owes_is_correct_and_counts_every_stream():
    checks, judge = _judged(5, 3)
    assert compare.verdict(checks), checks
    d = judge.detail
    assert d["events_compared"] == 3 * 4096
    assert d["rows_owed"] == d["rows_compared"] \
        == d["rows_delivered_all_streams"] > 100_000
    assert [c["name"] for c in checks] == [
        "rows_missing", "rows_extra", "values_off_grid",
        "rows_out_of_rule_order", "nothing_to_compare"]
    assert all(c["limit"] == 0 for c in checks)


def _first_batch_with_rows(stream):
    def pick(tamper_rows):
        hit = []

        def tamper(j, k, rows):
            if j == stream and not hit and len(rows["ts"]) > 0:
                hit.append(k)
                return tamper_rows(rows)
            return rows
        return tamper
    return pick


# streams 0, 2 and 3 carry rules of shapes 0, 2 and 3 (shape 1's rules, on
# streams 1, 5, ..., rarely owe a row: its one arm mostly dies at once)
@pytest.mark.parametrize("stream", [0, 2, 3])
def test_a_dropped_row_is_seen_in_each_kind_of_stream(stream):
    drop = _first_batch_with_rows(stream)(
        lambda r: {c: v[1:] for c, v in r.items()})
    checks, _j = _judged(6, 3, drop)
    by = {c["name"]: c["value"] for c in checks}
    assert not compare.verdict(checks)
    assert by["rows_missing"] == 1 and by["rows_extra"] == 0


@pytest.mark.parametrize("stream", [0, 2, 3])
def test_an_altered_row_is_seen_in_each_kind_of_stream(stream):
    def alter(r):
        r = {c: v.copy() for c, v in r.items()}
        r["p1"][0] += 0.25
        return r
    checks, _j = _judged(6, 3, _first_batch_with_rows(stream)(alter))
    by = {c["name"]: c["value"] for c in checks}
    assert not compare.verdict(checks)
    assert by["rows_missing"] == 1 and by["rows_extra"] == 1


@pytest.mark.parametrize("stream", [0, 2, 3])
def test_a_doubled_row_is_seen_in_each_kind_of_stream(stream):
    double = _first_batch_with_rows(stream)(
        lambda r: {c: np.insert(v, 0, v[0]) for c, v in r.items()})
    checks, _j = _judged(6, 3, double)
    by = {c["name"]: c["value"] for c in checks}
    assert by["rows_extra"] == 1 and by["rows_missing"] == 0


def test_a_rule_whose_rows_arrive_out_of_order_is_seen():
    swap = _first_batch_with_rows(0)(
        lambda r: {c: np.r_[v[-1:], v[:-1]] for c, v in r.items()})
    checks, _j = _judged(6, 3, swap)
    by = {c["name"]: c["value"] for c in checks}
    assert by["rows_out_of_rule_order"] == 1
    assert by["rows_missing"] == by["rows_extra"] == 0


def test_an_off_grid_value_and_an_empty_run_are_seen():
    def nudge(r):
        r = {c: v.copy() for c, v in r.items()}
        r["p1"][0] += 0.01
        return r
    by = {c["name"]: c["value"] for c in
          _judged(6, 3, _first_batch_with_rows(3)(nudge))[0]}
    assert by["values_off_grid"] == 1
    cell = _cell()
    judge = rules_mixed.Judge(cell["config"], engine.tape_of(cell, 1), 1)
    by = {c["name"]: c["value"] for c in judge.judge(0)}
    assert by["nothing_to_compare"] == 1


def test_rows_past_the_budget_are_counted_and_not_compared():
    cell = _cell()
    cell["config"]["compare_events_budget"] = 4096 + 100
    tape = engine.tape_of(cell, 7)
    judge = rules_mixed.Judge(cell["config"], tape, 7)
    rules_mixed.stand_in(judge, [tape.batch(i) for i in range(3)], np.asarray)
    checks = judge.judge(3)
    assert compare.verdict(checks), checks
    d = judge.detail
    assert d["events_compared"] == 4196
    assert d["rows_compared"] == d["rows_owed"] \
        < d["rows_delivered_all_streams"]


# -- the control -------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_lower_precision_in_the_programs_place_fails(seed):
    cell = _cell()
    sound = control.stand_in(cell, seed, 3, lower=False)
    assert compare.verdict(sound), sound
    lowered = control.stand_in(cell, seed, 3, lower=True)
    assert not compare.verdict(lowered)
    by = {c["name"]: c["value"] for c in lowered}
    assert by["rows_missing"] > 1000 and by["rows_extra"] > 1000


# -- the turn-away and the rehearsal ---------------------------------------------

def _explains(fused):
    return SimpleNamespace(
        explain=lambda: {"queries": {f"q{i}__x250": {
            "path": "device", "kind": "multi_query", **f}
            for i, f in enumerate(fused)}},
        add_batch_callback=lambda sid, fn: None)


def test_an_engine_that_does_not_cut_is_turned_away_before_the_first_event():
    """The parent of the PR that brought the cell runs a scan group's flush
    as one lane of the whole batch, past the dense bound, minutes a batch:
    the judge, bound before warm-up, ends such a run at once (exit code 1,
    nothing run)."""
    cell = _cell()

    def judge():
        return rules_mixed.Judge(cell["config"], engine.tape_of(cell, 1), 1)
    rec = {"fused": {"queries": 250, "family": "scan",
                     "lane_cut": {"flushes_cut": 0}}}
    judge().bind(_explains([rec] * 4))
    for bad in ([{}] * 4,                                   # the parent
                [rec] * 3,                                  # three plans
                [rec] * 3 + [{"fused": {"queries": 250}}],  # no cut record
                [rec] * 3 + [{"fused": {**rec["fused"], "queries": 12}}]):
        with pytest.raises(SystemExit) as e:
            judge().bind(_explains(bad))
        assert "Nothing was run" in str(e.value)


def test_bind_subscribes_the_fifteen_other_streams():
    cell = _cell()
    judge = rules_mixed.Judge(cell["config"], engine.tape_of(cell, 1), 1)
    rt = _explains([{"fused": {"queries": 250, "lane_cut": {}}}] * 4)
    seen = []
    rt.add_batch_callback = lambda sid, fn: seen.append(sid)
    judge.bind(rt)
    assert seen == [f"Out{j}" for j in range(1, 16)]
    assert cell["config"]["out_stream"] == "Out0"


def test_the_traced_rehearsal_finds_every_metric_the_cell_lists():
    mf = manifest.Manifest()
    # (a metric read from the trace's spans needs a traced interval, which
    # starts between two batches: this window holds one; test_trace_names
    # rehearses a longer one)
    want = sorted(m["name"] for m in mf.metrics_of(CELL, "per_layer")
                  if "share" not in m["name"] and "roofline" not in m["name"]
                  and mf.metric_spec(m["name"])["reader"] != "span_self")
    assert len(want) >= 12
    out = last_line(run_cell(["--workload", CELL, "--seed", str(2**31 + 37),
                              "--seconds", "1.0", "--trace", "1",
                              "--rehearse-cpu"]))
    assert out["correct"] is True, out["compared"]
    assert out["metrics_found"] == want
    fused = out["counts"]["fused"]
    assert [f["family"] for f in fused.values()] == [
        "scan", "seq", "seq", "scan"]
    flushes = out["attempted"] + 2          # the rehearsal's warm-up batches
    for f in fused.values():
        assert f["queries"] == 250 and f["padded_lanes"] == 0
        if f["family"] == "scan":
            cut = f["lane_cut"]
            assert cut["flushes_cut"] == flushes
            assert cut["flushes_uncuttable"] == 0
            assert f["first_hit"]["tree"] == 0
            assert f["indexed_read"]["gather"] == 0
            assert f["first_hit"]["F"] == cut["cut_length"] == 1024
        else:
            assert f["arms_resolved"] == 250
            assert f["dispatches_skipped"] == flushes - 1
    assert out["counts"]["rows_delivered"] == out["counts"]["rows_owed"] > 0
