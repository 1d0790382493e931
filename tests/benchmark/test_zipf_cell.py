"""The cell `pattern1k-zipf.sat`: its tape (YCSB's scrambled zipfian over the
keys), its judge (the popular keys are always among those compared, the rest
of the budget is reckoned in events), its control, and that the CPU
rehearsal, at the sizes its files give, takes the engine's cut of hot lanes.
The cell joins test_rehearsal.py and test_manifest.py by being in the
manifest."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import numpy as np
import pytest

from benchmark import compare, control, engine, harness, manifest
from benchmark.reference import pattern_chain, pattern_chain_hot
from benchmark.tapes import stock, stock_zipf

CELL = "pattern1k-zipf.sat"


def _cell(rehearse=True, **tape_params):
    cell = manifest.Manifest().cell(CELL)
    if rehearse:
        cell["config"] = manifest.rehearsed(cell["config"])
        cell["traffic"] = manifest.rehearsed(cell["traffic"])
    cell["config"]["tape_params"].update(tape_params)
    return cell


# -- the tape ---------------------------------------------------------------------

def test_the_configuration_is_pattern1k_but_for_its_keys():
    mf = manifest.Manifest()
    zipf, plain = mf.cell(CELL)["config"], mf.cell("pattern1k.sat")["config"]
    same = ("app", "annotations", "stream", "out_stream", "stream_cols",
            "out_cols", "stateful", "query", "guarantees", "expect", "kernel",
            "schema", "reduced")
    assert {k: zipf[k] for k in same} == {k: plain[k] for k in same}
    assert len(zipf["guarantees"]) == 5 and "device_precision" in zipf[
        "guarantees"]
    assert zipf["tape"] == "stock_zipf" and zipf["reference"] == \
        "pattern_chain_hot"
    assert zipf["tape_params"] == {**plain["tape_params"],
                                   "zipf_exponent": 0.99}
    assert mf.cell(CELL)["traffic"] == mf.cell("pattern1k.sat")["traffic"]


def test_rank_shares_are_ycsbs_zipfian():
    share = stock_zipf.rank_shares(1000, 0.99)
    assert abs(share.sum() - 1) < 1e-12 and (np.diff(share) < 0).all()
    assert abs(share[0] - 0.1294) < 5e-4            # the top key: 12.94%
    assert abs(share[9] / share[0] - 0.1) < 3e-3    # rank 10: a tenth of it
    assert abs(share[:32].sum() - 0.53) < 0.01      # the judge's hot keys
    assert round(share[0] * 2 ** 18) == 33917
    assert round(share[0] * 10_000) == 1294         # of any 10 s `within`


def test_the_scramble_is_a_permutation_of_all_keys_from_the_seed():
    a = stock_zipf.key_of_rank(1000, 7)
    assert sorted(a.tolist()) == list(range(1000))
    assert not np.array_equal(a, np.arange(1000))
    assert np.array_equal(a, stock_zipf.key_of_rank(1000, 7))
    assert not np.array_equal(a, stock_zipf.key_of_rank(1000, 8))
    big = stock_zipf.key_of_rank(1000, 2 ** 31 + 5)     # the driver's seeds
    assert sorted(big.tolist()) == list(range(1000))


def test_a_batch_is_the_same_whatever_was_asked_before():
    cell = _cell(rehearse=False)
    tape = engine.tape_of(cell, 2 ** 31 + 11)
    assert isinstance(tape, stock_zipf.Tape)
    b3 = tape.batch(3)
    for i in (0, 5, 1):
        tape.batch(i)
    again = engine.tape_of(cell, 2 ** 31 + 11).batch(3)
    for k in ("sym_idx", "price", "volume", "ts"):
        assert np.array_equal(b3[k], again[k]) and \
            np.array_equal(b3[k], tape.batch(3)[k])
    n = int(cell["traffic"]["batch"])
    assert b3["n"] == n == len(b3["sym_idx"])
    # a timestamp names its event; prices on the quarter grid; as stock.py
    assert np.array_equal(tape.event_index(b3["ts"]),
                          3 * n + np.arange(n))
    assert np.array_equal(b3["price"], stock.on_grid(b3["price"], 0.25))
    assert b3["price"].min() >= 90 and b3["price"].max() <= 130
    assert b3["sym_idx"].dtype == np.int32
    other = engine.tape_of(cell, 2 ** 31 + 12).batch(3)
    assert not np.array_equal(b3["sym_idx"], other["sym_idx"])


def test_a_batch_holds_the_shares_on_the_scrambled_keys():
    cell = _cell(rehearse=False)
    tape = engine.tape_of(cell, 99)
    events = np.bincount(tape.batch(2)["sym_idx"], minlength=1000)
    hot = tape.hot_keys(32)
    assert np.array_equal(hot, stock_zipf.key_of_rank(1000, 99)[:32])
    n = 2 ** 18
    assert abs(events[hot[0]] / n - 0.1294) < 0.004
    assert abs(events[hot[1]] / n - 0.0652) < 0.003
    assert abs(events[hot].sum() / n - 0.53) < 0.01
    assert events.min() > 0 and events.argmax() == hot[0]


def test_the_skew_stanza_is_honoured_and_harmless():
    cell = _cell(rehearse=False)
    assert cell["traffic"]["skew"]["events"] == 352
    tape = engine.tape_of(cell, 4)
    assert tape.params["skew"] == cell["traffic"]["skew"]
    b0, b1 = tape.batch(0), tape.batch(1)
    k = int(cell["traffic"]["skew"]["key"])
    rank = int(np.flatnonzero(stock_zipf.key_of_rank(1000, 4) == k)[0])
    want = 2 ** 18 * stock_zipf.rank_shares(1000, 0.99)[rank]
    assert np.count_nonzero(b0["sym_idx"] == k) >= max(352, int(want * 0.8))
    if want < 300:      # raised in batch 0 alone
        assert np.count_nonzero(b1["sym_idx"] == k) < 352


# -- the judge ---------------------------------------------------------------------

def _judged(cell, seed, n_batches, tamper=None):
    """The checks, and the judge, had the program delivered what the plain
    reference computes, `tamper`ed with on the way."""
    cfg = cell["config"]
    tape = engine.tape_of(cell, seed)
    judge = pattern_chain_hot.Judge(cfg, tape, seed)
    made = [tape.batch(i) for i in range(n_batches)]
    sym = np.concatenate([b["sym_idx"] for b in made])
    rows = pattern_chain.matches(
        sym, np.concatenate([b["price"] for b in made]),
        np.concatenate([b["ts"] for b in made]), cfg["query"])
    order = np.argsort(rows["e3"], kind="stable")
    rows = {c: rows[c][order] for c in rows}
    key = sym[rows["e3"]]
    if tamper is not None:
        rows = tamper(rows, key, tape)
    judge.add_rows(*(rows[c] for c in ("ts", "p1", "p2", "p3")))
    return judge.judge(n_batches), judge


@pytest.mark.parametrize("budget", [1, 12_500, 14_000, 10 ** 9])
def test_hot_keys_are_always_compared_and_the_rest_fits_the_budget(budget):
    cell = _cell(keys=200)
    cell["config"]["compare_events_budget"] = budget
    checks, judge = _judged(cell, 5, 4)
    assert compare.verdict(checks), checks
    d = judge.detail
    events = 4 * int(cell["traffic"]["batch"])
    assert d["hot_keys_compared"] == pattern_chain_hot.HOT_KEYS == 32
    assert 0.6 * events < d["events_of_hot_keys"] < 0.8 * events
    assert d["events_compared"] <= max(budget, d["events_of_hot_keys"])
    if budget == 1:             # the hot keys alone pass it: they stay
        assert d["keys_compared"] == 32
        assert d["events_compared"] == d["events_of_hot_keys"]
    elif budget == 10 ** 9:
        assert d["keys_compared"] == 200 and d["events_compared"] == events
    else:
        assert 32 < d["keys_compared"] < 200
        assert d["events_compared"] > d["events_of_hot_keys"]
    assert d["rows_owed"] > 0 and d["rows_delivered_all_keys"] >= d[
        "rows_owed"]


def test_a_uniform_sample_may_miss_what_the_hot_judge_holds():
    """Why the cell brings a judge: pattern_chain's sizes its uniform sample
    as if keys were uniform."""
    cell = _cell(keys=200)
    cell["config"]["compare_events_budget"] = 2_000
    tape = engine.tape_of(cell, 5)
    plain = pattern_chain.Judge(cell["config"], tape, 5)
    pattern_chain.stand_in(plain, [tape.batch(i) for i in range(4)],
                           np.asarray)
    plain.judge(4)
    assert plain.detail["keys_compared"] == 200 * 2_000 // (4 * 4096) == 24
    _checks, hot = _judged(cell, 5, 4)
    assert hot.detail["keys_compared"] >= 32


def _of_top_key(key, tape):
    return np.flatnonzero(key == tape.hot_keys(1)[0])


def test_a_dropped_hot_key_row_is_seen():
    def drop(rows, key, tape):
        gone = _of_top_key(key, tape)[7]
        return {c: np.delete(v, gone) for c, v in rows.items()}
    checks, _j = _judged(_cell(keys=200), 6, 3, drop)
    assert not compare.verdict(checks)
    assert {c["name"]: c["value"] for c in checks}["rows_missing"] == 1


def test_an_altered_hot_key_row_is_seen():
    def alter(rows, key, tape):
        rows["p2"] = rows["p2"].copy()
        rows["p2"][_of_top_key(key, tape)[3]] += 0.25
        return rows
    checks, _j = _judged(_cell(keys=200), 6, 3, alter)
    by = {c["name"]: c["value"] for c in checks}
    assert not compare.verdict(checks)
    assert by["rows_missing"] == 1 and by["rows_extra"] == 1


def test_a_doubled_hot_key_row_is_seen():
    def double(rows, key, tape):
        i = _of_top_key(key, tape)[11]
        return {c: np.insert(v, i, v[i]) for c, v in rows.items()}
    checks, _j = _judged(_cell(keys=200), 6, 3, double)
    assert {c["name"]: c["value"] for c in checks}["rows_extra"] == 1


# -- the control -------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_lower_precision_in_the_programs_place_fails(seed):
    cell = _cell(keys=200)
    sound = control.stand_in(cell, seed, 4, lower=False)
    assert compare.verdict(sound), sound
    lowered = control.stand_in(cell, seed, 4, lower=True)
    assert not compare.verdict(lowered)
    assert sum(c["value"] > c["limit"] for c in lowered) >= 1


# -- the rehearsal takes the cut -------------------------------------------------

def test_the_rehearsal_sizes_cut_every_flush_on_the_cpu():
    """Three keys at the rehearsal's 4096-event batch put 54% of a batch,
    2,200 events, on one lane: past the engine's cut length, so the CPU lane
    (test_rehearsal.py) drives the cut and not only the uniform lines."""
    import jax
    cell = _cell()
    assert cell["config"]["tape_params"]["keys"] == 3
    run = harness.Run(cell=cell, seed=2 ** 31 + 21, seconds=0.5,
                      trace_on=False, devices=jax.devices()[:1])
    out = manifest.module("drivers", cell["traffic"]["driver"]).run(run)
    assert out["correct"] is True, out["checks"]
    counts = out["counts"]
    cut, hit = counts["lane_cut"], counts["first_hit"]
    flushes = out["attempted"] + int(cell["traffic"]["warm_batches"])
    assert cut["flushes_cut"] == flushes and cut["flushes_uncuttable"] == 0
    assert cut["rows_added"] >= flushes and cut["events_replayed"] > 0
    assert hit["F"] == cut["cut_length"] and hit["tree"] == 0
    assert counts["hot_keys_compared"] == counts["keys_compared"] == 3
    assert counts["rows_delivered"] == counts["rows_owed"] > 0


def test_an_engine_that_does_not_cut_is_turned_away_before_the_first_event():
    """The parent of the PR that brought the cell pads every lane to the
    hottest key and takes minutes a batch: the judge, bound before warm-up,
    ends such a run at once (exit code 1, nothing run)."""
    from types import SimpleNamespace
    cell = _cell()
    judge = pattern_chain_hot.Judge(cell["config"],
                                    engine.tape_of(cell, 1), 1)
    cuts = SimpleNamespace(explain=lambda: {"queries": {"q": {
        "path": "device", "lane_cut": {"flushes_cut": 0}}}})
    judge.bind(cuts)
    pads = SimpleNamespace(explain=lambda: {"queries": {"q": {
        "path": "device", "first_hit": None}}})
    with pytest.raises(SystemExit) as e:
        judge.bind(pads)
    assert e.value.code not in (0, None) and "Nothing was run" in str(
        e.value.code)
