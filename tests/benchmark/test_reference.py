"""The plain references equal the engine's host interpreter on both query
shapes, over several seeds and at the `within` boundary; and the by-value
comparison sees a lost, a doubled and an altered row."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))        # the repo root holds `benchmark`

import numpy as np
import pytest

from benchmark import compare, manifest
from benchmark.reference import filter as filter_ref
from benchmark.reference import pattern_chain
from benchmark.tapes import cse, stock

HOST = ("@app:devicePatterns('never')\n@app:deviceFilters('never')\n"
        "@app:deviceWindows('never')\n")


def _app(name):
    with open(os.path.join(manifest.ROOT, "benchmark", "apps",
                           name + ".siddhi")) as f:
        return HOST + f.read().replace("{source}", "").replace("{sink}", "")


def _interpret(app, batches, keys, tape=stock, stream="StockStream",
               out="Out"):
    from siddhi_tpu import SiddhiManager
    mgr = SiddhiManager()
    got = []
    try:
        rt = mgr.create_app_runtime(app)
        rt.add_callback(out, lambda evs: got.extend(
            (e.timestamp, *e.data) for e in evs))
        rt.start()
        h = rt.input_handler(stream)
        names = tape.symbol_names(keys)
        for b in batches:
            h.send_batch(*tape.feed_columns(b, names))
        rt.flush()
    finally:
        mgr.shutdown()
    return sorted(got)


# dt 625 ms x 16 keys and 1000 ms x 8 keys put event pairs exactly 10 s apart
@pytest.mark.parametrize("seed,keys,dt_ms", [
    (1, 16, 625), (2, 16, 625), (3_000_000_011, 8, 1000), (4, 64, 64),
    (5, 64, 64), (6, 8, 1000)])
def test_pattern_chain_equals_the_host_interpreter(seed, keys, dt_ms):
    params = {"batch": 1024, "keys": keys, "dt_ms": dt_ms,
              "price_lo": 90.0, "price_hi": 130.0, "price_step": 0.25}
    batches = [stock.make_batch(params, seed, i) for i in range(3)]
    want = _interpret(_app("pattern1k"), batches, keys)
    r = pattern_chain.matches(
        np.concatenate([b["sym_idx"] for b in batches]),
        np.concatenate([b["price"] for b in batches]),
        np.concatenate([b["ts"] for b in batches]),
        {"threshold": 100.0, "within_ms": 10000})
    got = sorted(zip(r["ts"].tolist(), r["p1"].tolist(), r["p2"].tolist(),
                     r["p3"].tolist()))
    assert len(got) > 50 and got == want


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_011])
def test_filter_equals_the_host_interpreter(seed):
    params = {"batch": 2048, "keys": 2, "dt_ms": 1, "price_lo": 50.0,
              "price_split": 70.0, "price_hi": 90.0}
    batches = [cse.make_batch(params, seed, i) for i in range(2)]
    want = _interpret(_app("filter1q"), batches, 2, tape=cse,
                      stream="cseEventStream", out="outputStream")
    names = cse.symbol_names(2)
    got = []
    for b in batches:
        keep = filter_ref.passing(b["price"], {"threshold": 70.0})
        rows = cse.rows(b, keep, names)
        got += list(zip(b["ts"][keep].tolist(), *(
            rows[c].tolist() for c in ("symbol", "price", "volume",
                                       "timestamp"))))
    assert len(got) == 2048 and sorted(got) == want     # exactly half


def test_filter_compares_in_the_columns_own_type():
    price = np.array([69.99999, 70.0, 70.00001], np.float32)
    assert filter_ref.passing(price, {"threshold": 70.0}).tolist() == [0]
    from ml_dtypes import bfloat16      # the control's type: 69.99999 -> 70
    assert filter_ref.passing(price.astype(bfloat16),
                              {"threshold": 70.0}).tolist() == []


def test_cse_tape_is_upstreams_stream_made_from_a_seed():
    p = {"batch": 512, "keys": 2, "dt_ms": 1, "price_lo": 50.0,
         "price_split": 70.0, "price_hi": 90.0}
    a = cse.Tape(p, 2**31 + 5).batch(3)
    b = cse.Tape(p, 2**31 + 5).batch(3)
    assert all(np.array_equal(a[k], b[k]) for k in a if k != "n")
    assert a["price"].dtype == np.float32 and a["volume"].dtype == np.int64
    assert a["sym_idx"].tolist()[:4] == [0, 1, 0, 1]        # WSO2, IBM, ...
    # every WSO2 event passes `70 > price`, no IBM event does: exactly half
    assert np.array_equal(a["price"] < np.float32(70), a["sym_idx"] == 0)
    assert a["price"].min() >= 50 and a["price"].max() < 90
    near = cse.make_batch({**p, "batch": 1 << 18, "price_lo": 69.9999},
                          1, 0)     # draws that round up to 70 are held under
    assert np.array_equal(near["price"] < np.float32(70), near["sym_idx"] == 0)
    cols, ts = cse.feed_columns(a, cse.symbol_names(2))
    assert list(cols) == ["symbol", "price", "volume", "timestamp"]
    assert np.array_equal(cols["timestamp"], ts)
    assert not np.shares_memory(cols["timestamp"], ts)
    ring = cse.Tape({**p, "ring": 2}, 5)
    assert np.array_equal(ring.batch(5)["price"], ring.batch(1)["price"])
    assert ring.batch(5)["ts"][0] == stock.TS0 + 5 * 512


def test_tape_is_deterministic_extendable_and_takes_large_seeds():
    p = {"batch": 512, "keys": 100, "dt_ms": 1, "price_lo": 90.0,
         "price_hi": 130.0, "price_step": 0.25,
         "skew": {"batch": 0, "key": 0, "events": 40}}
    a, b = stock.Tape(p, 2**31 + 12345), stock.Tape(p, 2**31 + 12345)
    assert all(np.array_equal(a.batch(3)[k], b.batch(3)[k])
               for k in ("sym_idx", "price", "volume", "ts"))
    assert not np.array_equal(a.batch(3)["price"],
                              stock.Tape(p, 7).batch(3)["price"])
    counts = np.bincount(a.batch(0)["sym_idx"], minlength=100)
    assert counts[0] == 40 and counts[1:].max() < 20          # the skew
    assert a.batch(2)["ts"][0] == stock.TS0 + 2 * 512
    assert np.all(a.batch(1)["price"] * 4 == np.rint(a.batch(1)["price"] * 4))
    ring = stock.Tape({**p, "ring": 2}, 5)
    assert np.array_equal(ring.batch(5)["price"], ring.batch(1)["price"])
    assert ring.batch(5)["ts"][0] == stock.TS0 + 5 * 512


def _rows(n=200, seed=0):
    rng = np.random.default_rng(seed)
    e3 = np.sort(rng.integers(0, 5000, n))
    p = stock.on_grid(rng.uniform(100.25, 130, (3, n)), 0.25)
    key = e3 % 7
    return {"ts": stock.TS0 + e3, "p1": p[0], "p2": p[1], "p3": p[2],
            "e3": e3}, key


def _verdict(got, key, want):
    checks = compare.pattern_rows(got, want, key, 90.0, 0.25)
    return compare.verdict(checks), {c["name"]: c["value"] for c in checks}


def test_comparison_passes_equal_rows_in_any_cross_key_order():
    want, key = _rows()
    shuffle = np.argsort(key, kind="stable")     # keys regrouped, order kept
    got = {k: v[shuffle] for k, v in want.items()}
    ok, vals = _verdict(got, key[shuffle], want)
    assert ok and not any(vals.values())


@pytest.mark.parametrize("fault,name", [
    ("lost", "rows_missing"), ("doubled", "rows_extra"),
    ("altered", "rows_missing"), ("off_grid", "values_off_grid"),
    ("reordered", "rows_out_of_key_order")])
def test_comparison_sees_each_fault(fault, name):
    want, key = _rows()
    got = {k: v.copy() for k, v in want.items()}
    if fault == "lost":
        got = {k: np.delete(v, 17) for k, v in got.items()}
        key = np.delete(key, 17)
    elif fault == "doubled":
        got = {k: np.append(v, v[17]) for k, v in got.items()}
        key = np.append(key, key[17])
    elif fault == "altered":
        got["p2"][17] += 0.25
    elif fault == "off_grid":
        got["p2"][17] += 0.001
    else:
        same = np.flatnonzero(key == key[0])[:2]
        for v in got.values():
            v[same] = v[same[::-1]]
    ok, vals = _verdict(got, key, want)
    assert not ok and vals[name] > 0


def test_comparison_holds_on_a_finer_grid_and_sees_f32_rounded_prices():
    """The off-grid experiment of PERF.md: on a 0.01 grid a price that came
    back rounded to f32 is off the grid, the row still pairs with its own."""
    rng = np.random.default_rng(3)
    e3 = np.sort(rng.integers(0, 5000, 100))
    p = stock.on_grid(rng.uniform(100.01, 130, (3, 100)), 0.01)
    want = {"ts": stock.TS0 + e3, "p1": p[0], "p2": p[1], "p3": p[2],
            "e3": e3}
    same = compare.pattern_rows(want, want, e3 % 7, 90.0, 0.01)
    assert compare.verdict(same)
    got = {**want, "p2": p[1].astype(np.float32).astype(np.float64)}
    vals = {c["name"]: c["value"]
            for c in compare.pattern_rows(got, want, e3 % 7, 90.0, 0.01)}
    assert vals["values_off_grid"] > 90 and vals["rows_missing"] == 0
