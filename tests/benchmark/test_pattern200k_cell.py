"""The cell `pattern200k.sat`: its tape (the stock stream at an event-time
rate, so timestamps tie), its judge (a delivered row's key is read from the
row's `symbol`; where a key has two events in one millisecond both sides
name the first), its control, its files beside `pattern1k`'s, and that the
CPU rehearsal at the sizes its files give is steady and finds the cell's
metrics.  The cell joins test_rehearsal.py, test_span_metrics.py and
test_manifest.py by being in the manifest."""
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import numpy as np
import pytest

from benchmark import compare, control, engine, manifest
from benchmark.reference import pattern_chain, pattern_chain_keyed
from benchmark.tapes import stock, stock_ties
from siddhi_tpu.core.schema import StringTable
from test_rehearsal import last_line, run_cell

CELL = "pattern200k.sat"
PER_LAYER = ["ingest_ms_per_batch", "freeze_ms_per_batch",
             "host_pack_ms_per_batch", "h2d_bytes_per_event",
             "kernel_dispatch_ms_per_batch", "kernel_busy_share",
             "device_wait_ms_per_batch", "d2h_bytes_per_event",
             "materialise_ms_per_batch", "device_idle_share",
             "compiles_in_window"]


def _cell(rehearse=True, **tape_params):
    cell = manifest.Manifest().cell(CELL)
    if rehearse:
        cell["config"] = manifest.rehearsed(cell["config"])
        cell["traffic"] = manifest.rehearsed(cell["traffic"])
    cell["config"]["tape_params"].update(tape_params)
    return cell


# -- the files ----------------------------------------------------------------------

def test_the_configuration_is_pattern1k_at_200000_keys_with_the_key_out():
    mf = manifest.Manifest()
    big, plain = mf.cell(CELL)["config"], mf.cell("pattern1k.sat")["config"]
    same = ("stream", "out_stream", "stream_cols", "stateful", "query",
            "expect", "reduced", "stream_events")
    assert {k: big[k] for k in same} == {k: plain[k] for k in same}
    assert big["annotations"] == [
        "@app:partitionCapacity(200000)", "@app:deviceSlots(32)",
        "@app:deviceMesh('never')"]
    assert big["tape_params"] == {
        "keys": 200000, "events_per_ms": 200, "price_lo": 90.0,
        "price_hi": 130.0, "price_step": 0.25}
    assert big["reduced"] == ["stream_events"]      # the keys are not cut
    assert big["out_cols"] == [["symbol", "string"]] + plain["out_cols"]
    assert big["guarantees"] == {
        **plain["guarantees"],
        "key": "the delivered symbol is the key of the match's three events"}
    assert big["kernel"] != "lane_block"    # lane_block_roofline: 3 in, 7 out
    assert big["tape"] == "stock_ties" and \
        big["reference"] == "pattern_chain_keyed"
    assert len(big["source"]) <= 200 and "PartitionPerformance" in big[
        "source"]
    # every hand-set number is under `assumed`
    told = " ".join(big["assumed"])
    for said in ("200 events a millisecond", "200,000 keys",
                 "deviceSlots(32)", "compare_events_budget 16,000,000",
                 "prebuild_events_per_s 400,000", "PartitionPerformance"):
        assert said in told, said
    assert big["compare_events_budget"] >= 10_000_000


def test_the_app_is_pattern1ks_with_the_key_selected():
    mf = manifest.Manifest()
    new, old = mf.cell(CELL)["app_text"], mf.cell("pattern1k.sat")["app_text"]

    def code(text):
        return " ".join(ln.strip() for ln in text.splitlines()
                        if ln.strip() and not ln.startswith("--"))
    assert code(new) == code(old).replace(
        "define stream Out (p1 double,",
        "define stream Out (symbol string, p1 double,").replace(
        "select e1.price as p1,", "select e1.symbol as symbol, e1.price as p1,")
    assert "{source}define" in new and "{sink}define" in new


def test_the_traffic_is_the_closed_loop_with_ten_warm_up_batches():
    mf = manifest.Manifest()
    new, old = mf.cell(CELL)["traffic"], mf.cell("pattern1k.sat")["traffic"]
    assert (new["driver"], new["batch"]) == (old["driver"], old["batch"]) \
        == ("inproc_sat", 262144)
    assert new["warm_batches"] == 10
    assert new["skew"] == {"only_if_keys_at_least": 100, "batch": 0,
                           "key": 0, "events": 40}
    assert new["trace"] == {"start_after_s": 3.0, "length_s": 8.0}
    # one `within` of event time is over before the window opens
    per_ms = mf.cell(CELL)["config"]["tape_params"]["events_per_ms"]
    assert new["warm_batches"] * new["batch"] / per_ms > \
        mf.cell(CELL)["config"]["query"]["within_ms"]


def test_the_manifest_lists_the_cell_on_twelve_lists_and_adds_no_metric():
    data = manifest.Manifest().data
    assert len(data["per_layer"]) == 44
    assert [m["name"] for m in data["per_layer"] if CELL in m["workloads"]] \
        == sorted(PER_LAYER, key=[m["name"] for m in data["per_layer"]].index)
    assert [m["name"] for m in data["end_to_end"]
            if CELL in m.get("workloads", ())] == ["events_per_s"]
    for m in data["per_layer"] + data["end_to_end"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL       # appended, nothing moved
    assert data["workloads"][-1]["name"] == CELL
    # one chip computes; the cell holds a whole four-chip host for steadiness
    # alone (PERF.md section 6: 8.2% over six seeds on a one-chip machine)
    assert data["workloads"][-1]["chips"] == 4
    assert data["configs"][-1]["name"] == "pattern200k"


# -- the tape ---------------------------------------------------------------------

@pytest.mark.parametrize("index", [0, 3, 7])
def test_a_batch_is_the_same_whatever_was_asked_before(index):
    cell = _cell(rehearse=False)
    tape = engine.tape_of(cell, 2 ** 31 + 11)
    assert isinstance(tape, stock_ties.Tape) and tape.ring == 0
    first = tape.batch(index)
    for i in (0, 5, 1):
        tape.batch(i)
    alone = engine.tape_of(cell, 2 ** 31 + 11).batch(index)
    for k in ("sym_idx", "price", "volume", "ts"):
        assert np.array_equal(first[k], alone[k])
        assert np.array_equal(first[k], tape.batch(index)[k])
    other = engine.tape_of(cell, 2 ** 31 + 12).batch(index)
    assert not np.array_equal(first["sym_idx"], other["sym_idx"])
    n = int(cell["traffic"]["batch"])
    assert first["n"] == n == len(first["ts"])
    assert first["sym_idx"].dtype == np.int32 and first["ts"].dtype == np.int64
    assert first["sym_idx"].min() >= 0 and first["sym_idx"].max() < 200000
    assert np.array_equal(first["price"], stock.on_grid(first["price"], 0.25))
    assert first["price"].min() >= 90 and first["price"].max() <= 130


@pytest.mark.parametrize("per_ms", [1, 2, 200])
def test_timestamps_never_fall_and_tie_by_the_rate(per_ms):
    cell = _cell(rehearse=False, events_per_ms=per_ms)
    tape = engine.tape_of(cell, 5)
    n = int(cell["traffic"]["batch"])
    ts = np.concatenate([tape.batch(i)["ts"] for i in (0, 1, 2)])
    assert np.array_equal(ts, stock_ties.TS0 + np.arange(3 * n) // per_ms)
    assert (np.diff(ts) >= 0).all()
    assert np.count_nonzero(np.diff(ts) == 0) == 3 * n - -(-3 * n // per_ms)
    # the draw is stock.py's own: only the timestamps are this tape's
    plain = stock.Tape({**tape.params, "dt_ms": 1}, 5).batch(1)
    mine = tape.batch(1)
    for k in ("sym_idx", "price", "volume"):
        assert np.array_equal(mine[k], plain[k])


def test_a_key_sees_an_event_a_second_and_one_in_a_thousand_ties():
    cell = _cell(rehearse=False)
    b = engine.tape_of(cell, 8).batch(4)
    n, keys = b["n"], 200000
    span_s = (b["ts"][-1] - b["ts"][0] + 1) / 1000.0
    assert abs(n / keys / span_s - 1.0) < 0.01       # events a key a second
    first = pattern_chain_keyed.canonical_e3(b["sym_idx"], b["ts"]).first
    tied = np.count_nonzero(first != np.arange(n))
    assert 0.0002 * n < tied < 0.002 * n, tied
    quiet = keys - len(np.unique(b["sym_idx"]))
    assert abs(quiet / keys - np.exp(-n / keys)) < 0.01     # ~27% of lanes


def test_the_skew_stanza_is_honoured_with_stocks_meaning():
    cell = _cell(rehearse=False)
    tape = engine.tape_of(cell, 4)
    assert tape.params["skew"] == cell["traffic"]["skew"]
    b0, b1 = tape.batch(0), tape.batch(1)
    assert np.count_nonzero(b0["sym_idx"] == 0) == 40
    assert np.count_nonzero(b1["sym_idx"] == 0) < 12
    assert np.array_equal(
        b0["sym_idx"],
        stock.Tape({**tape.params, "dt_ms": 1}, 4).batch(0)["sym_idx"])
    # the rehearsal keeps the stanza: it settles its own grid the same way
    small = engine.tape_of(_cell(), 4)
    assert np.count_nonzero(small.batch(0)["sym_idx"] == 0) == 40
    with pytest.raises(ValueError):
        stock_ties.Tape({**tape.params, "ring": 4}, 4)


# -- the judge ---------------------------------------------------------------------

QUERY = {"threshold": 100.0, "within_ms": 10000}


def _hand_judge(sym, price, ts, budget=10 ** 9, rt=None):
    """A judge over one hand-written batch."""
    batch = {"sym_idx": np.array(sym, np.int32),
             "price": np.array(price, np.float64),
             "ts": stock_ties.TS0 + np.array(ts, np.int64), "n": len(sym)}
    tape = SimpleNamespace(
        params={"keys": 3, "batch": len(sym), "price_lo": 90.0,
                "price_step": 0.25}, batch=lambda i: batch)
    cfg = {"query": QUERY, "compare_events_budget": budget,
           "out_stream": "Out", "tape": "stock_ties"}
    judge = pattern_chain_keyed.Judge(cfg, tape, 1)
    if rt is not None:
        judge.bind(rt)
    return judge, batch


def _by(checks):
    return {c["name"]: c["value"] for c in checks}


# a dozen events, three keys interleaved.  Key 0 has two events in
# millisecond 5 (positions 4 and 5) and each completes a chain: 101 -> 102
# -> 103 at position 4, 102 -> 103 -> 104 at position 5.  Key 1 completes
# 110 -> 111 -> 112 at position 6 and 111 -> 112 -> 113 at position 8.
# Key 2 never gets a third step.
HAND = dict(sym=[0, 1, 0, 1, 0, 0, 1, 2, 1, 0, 2, 2],
            price=[101, 110, 102, 111, 103, 104, 112, 95, 113, 90, 120, 121],
            ts=[0, 1, 2, 3, 5, 5, 6, 7, 8, 9, 10, 11])


def test_the_judge_by_hand_with_a_tie_inside_one_key():
    judge, batch = _hand_judge(**HAND)
    first = pattern_chain_keyed.canonical_e3(batch["sym_idx"],
                                             batch["ts"]).first
    assert first.tolist() == [0, 1, 2, 3, 4, 4, 6, 7, 8, 9, 10, 11]
    owed = pattern_chain.matches(batch["sym_idx"], batch["price"],
                                 batch["ts"], QUERY)
    rows = sorted(zip(owed["e3"].tolist(), owed["p1"], owed["p2"], owed["p3"]))
    assert rows == [(4, 101, 102, 103), (5, 102, 103, 104),
                    (6, 110, 111, 112), (8, 111, 112, 113)]
    # delivered as an engine may: the two keys' rows interleaved, key 0's
    # two rows of millisecond 5 in either order
    for order in ([0, 2, 1, 3], [1, 2, 0, 3], [2, 0, 3, 1]):
        judge, _b = _hand_judge(**HAND)
        r = [rows[i] for i in order]
        judge.add_rows([batch["ts"][e] for e, *_p in r],
                       [batch["sym_idx"][e] for e, *_p in r],
                       *zip(*[p for _e, *p in r]))
        checks = judge.judge(1)
        assert compare.verdict(checks), (order, checks)
        assert judge.detail["rows_owed"] == 4
        assert judge.detail["events_sharing_key_and_ms"] == 1
        assert judge.detail["keys_compared"] == 3


def _delivered(tamper=None, **kw):
    """The hand stream's checks had the program delivered what it owes,
    `tamper`ed with on the way: columns ts, symbol, p1, p2, p3."""
    judge, batch = _hand_judge(**HAND, **kw)
    owed = pattern_chain.matches(batch["sym_idx"], batch["price"],
                                 batch["ts"], QUERY)
    cols = [owed["ts"], batch["sym_idx"][owed["e3"]].astype(np.int64),
            owed["p1"], owed["p2"], owed["p3"]]
    if kw.get("rt") is not None:        # the engine delivers codes
        cols[1] = np.array([kw["rt"].strings.encode(f"K{k}")
                            for k in cols[1]], np.int64)
    if tamper is not None:
        cols = tamper([np.array(c) for c in cols])
    judge.add_rows(*cols)
    return judge.judge(1), judge


def _under_another_key(cols):
    cols[1][0] = 1 if cols[1][0] != 1 else 0
    return cols


@pytest.mark.parametrize("tamper,want", [
    (None, {}),
    (_under_another_key, {"rows_missing": 1, "rows_extra": 1}),
    (lambda c: [np.delete(v, 1) for v in c], {"rows_missing": 1}),
    (lambda c: [np.insert(v, 1, v[1]) for v in c], {"rows_extra": 1}),
    (lambda c: [np.r_[c[0][:1] + 1, c[0][1:]]] + c[1:],
     {"rows_missing": 1, "rows_extra": 1}),         # a millisecond late
    (lambda c: [v[::-1] for v in c], {"rows_out_of_key_order": 1}),
    (lambda c: c[:2] + [c[2] + 0.1] + c[3:], {"values_off_grid": 4}),
], ids=["sound", "another_keys_symbol", "dropped", "doubled", "late",
        "out_of_order", "off_grid"])
def test_what_turns_correct_false(tamper, want):
    checks, _j = _delivered(tamper)
    # (a price 0.1 off its grid point still rounds onto its code: the rows
    # pair up as a multiset and `values_off_grid` alone sees them)
    assert {k: v for k, v in _by(checks).items() if v} == want
    assert compare.verdict(checks) == (not want)


def _fake_rt(out_attrs=("symbol", "p1", "p2", "p3"), explain=None):
    strings = StringTable()
    strings.encode("unrelated")         # codes do not start at the keys'
    for k in (2, 0, 1):                 # nor follow the key order
        strings.encode(f"K{k}")
    return SimpleNamespace(
        strings=strings,
        schemas={"Out": SimpleNamespace(attributes=[
            SimpleNamespace(name=a) for a in out_attrs])},
        explain=lambda: explain or {"queries": {"q": {"path": "device"}}})


def test_bound_to_a_runtime_the_symbol_is_a_dictionary_code():
    rt = _fake_rt(explain={"queries": {"q": {
        "lane_fill": {"flushes": 1}, "lane_cut": {"flushes_cut": 0}}}})
    checks, judge = _delivered(rt=rt)
    assert compare.verdict(checks), checks
    assert judge.detail["lane_fill"] == {"flushes": 1}      # printed only
    assert judge.detail["lane_cut"] == {"flushes_cut": 0}
    assert len(rt.strings) == 5             # the judge added no string
    # a code that is no key's is a false row of no key
    checks, _j = _delivered(lambda c: [c[0], np.r_[1, c[1][1:]]] + c[2:],
                            rt=rt)
    assert _by(checks)["rows_extra"] == 1 and _by(checks)["rows_missing"] == 1
    # an engine that keeps no `lane_fill` (the parent of the PR that
    # brought the cell) is judged all the same; the detail leaves it out
    checks, judge = _delivered(rt=_fake_rt())
    assert compare.verdict(checks) and "lane_fill" not in judge.detail


def test_an_engine_whose_out_has_no_symbol_is_turned_away_at_once():
    judge, _b = _hand_judge(**HAND)
    with pytest.raises(SystemExit) as e:
        judge.bind(_fake_rt(out_attrs=("p1", "p2", "p3")))
    assert "no 'symbol'" in str(e.value) and "Nothing was run" in str(e.value)
    assert judge.rows == 0 and judge._rt is None


@pytest.mark.parametrize("budget,keys", [(10 ** 9, 2000), (8192, 1000),
                                         (1, 1)])
def test_all_keys_inside_the_budget_then_a_seeded_sample(budget, keys):
    cell = _cell()
    cell["config"]["compare_events_budget"] = budget
    tape = engine.tape_of(cell, 6)
    judge = pattern_chain_keyed.Judge(cell["config"], tape, 6)
    made = [tape.batch(i) for i in range(16)]
    pattern_chain_keyed.stand_in(judge, made, np.asarray)
    checks = judge.judge(16)
    assert compare.verdict(checks), checks
    d = judge.detail
    assert d["keys_compared"] == keys
    assert d["rows_delivered_all_keys"] >= d["rows_owed"] > 0
    if keys == 2000:
        assert d["events_compared"] == 16 * 1024
        assert d["rows_delivered_all_keys"] == d["rows_owed"]
    again = pattern_chain_keyed.Judge(cell["config"], tape, 6)
    assert np.array_equal(again.chosen_keys(2000, 16 * 1024),
                          judge.chosen_keys(2000, 16 * 1024))


# -- the control -------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_lower_precision_in_the_programs_place_fails(seed):
    cell = _cell()
    sound = control.stand_in(cell, seed, 24, lower=False)
    assert compare.verdict(sound), sound
    lowered = control.stand_in(cell, seed, 24, lower=True)
    assert not compare.verdict(lowered)
    assert _by(lowered)["rows_missing"] > 100


# -- the rehearsal -----------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_the_rehearsal_is_steady_and_finds_the_cells_metrics(trace):
    r = run_cell(["--workload", CELL, "--seed", str(2 ** 31 + 42 + trace),
                  "--seconds", "1.5", "--trace", str(trace),
                  "--rehearse-cpu"])
    out = last_line(r)
    assert out["correct"] is True, out["compared"]
    assert all(v == {"value": 0, "limit": 0} for v in out["compared"].values())
    assert "compiles_in_window 0 " in r.stdout
    assert "('device', 'pattern', 'scan')" in r.stdout
    counts = out["counts"]
    assert counts["keys_compared"] == 2000
    assert counts["rows_delivered"] == counts["rows_owed"] + 0 \
        == counts["rows_delivered_all_keys"] > 0
    assert counts["events_sharing_key_and_ms"] > 0          # ties occurred
    fill = counts["lane_fill"]
    flushes = out["attempted"] + 4
    assert fill["flushes"] == flushes
    assert fill["grids"] == {"1024x64x64": flushes}         # one geometry
    # (how MANY are held and replayed grows over the first `within`, twenty
    # of these batches, and a loaded machine's window holds fewer; the
    # steady ratios are tests/test_many_short_lanes.py's)
    assert fill["total"]["lanes_held"] > 0 < fill["total"]["events_replayed"]
    assert fill["total"]["events_new"] == flushes * 1024
    assert fill["total"]["cells_filled"] == fill["total"]["events_new"] \
        + fill["total"]["events_replayed"]
    assert fill["total"]["rows_delivered"] == counts["rows_delivered"]
    if trace:       # every listed metric but the two device shares
        assert out["metrics_found"] == sorted(
            n for n in PER_LAYER if "share" not in n)
    else:
        assert out["metrics_found"] == ["events_per_s", "setup_s"]
