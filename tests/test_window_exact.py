"""Device window SUMS are exact to the rounding of the window's own contents
(core/window_device.py `_range_sum`): every kind x aggregator x grouping x
filter x batch size against float64 numpy on seeded quarter-step tapes,
where a sum under 2^24 quarter steps is representable in f32 and has to
come out EXACT, whatever the size of the batch around it; off the grid the
stated k-ulp-of-the-range bound; and the formula this replaced (a
difference of two f32 prefixes over the whole [carry | batch] sequence,
written out in numpy) fails the same comparison at 2^18 events."""
import functools

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager

TS0 = 1_700_000_000_000
L, D_MS, BUCKET_MS = 1000, 300, 64
WINDOWS = {
    "length": f"#window.length({L})",
    "time": f"#window.time({D_MS} milliseconds)",
    "externalTime": f"#window.externalTime(et, {D_MS} milliseconds)",
    "lengthBatch": f"#window.lengthBatch({L})",
    "externalTimeBatch": f"#window.externalTimeBatch(et, {BUCKET_MS})",
}
K_ULPS = 16         # window_device.py: the bound's k, with its derivation
# an `avg` is its sum through ONE f32 division, which the CPU rounds
# correctly and a TPU v5e to within 2.26 ulps of the exact quotient (PERF.md
# section 6, PR 44): 3, so that the file holds on the chip too
# (`SIDDHI_TEST_TPU=1`); a sum that is off shows in the `sum` cases, at 0
DIV_ULPS = 3


def tape(seed: int, n: int, step: float) -> dict:
    rng = np.random.default_rng([seed, n])
    inv = round(1.0 / step)
    return {"symbol": rng.integers(0, 8, n).astype(np.int32),
            "price": np.round(rng.uniform(90.0, 130.0, n) * inv) / inv,
            "volume": rng.integers(1, 1000, n).astype(np.int32),
            "ts": TS0 + np.arange(n, dtype=np.int64),
            "et": 5000 + 2 * np.arange(n, dtype=np.int64)}


def expected(kind: str, t: dict, grouped: bool, filtered: bool) -> dict:
    """What the query owes, in float64 on the prices as the device holds
    them (f32): per emitted event its timestamp, the sum of |price| and
    the count of its range.  A prefix sum a group, exact on the grid."""
    keep = np.flatnonzero(t["volume"] >= 500) if filtered \
        else np.arange(len(t["ts"]))
    price = t["price"][keep].astype(np.float32).astype(np.float64)
    group = t["symbol"][keep] if grouped else np.zeros(len(keep), np.int32)
    n = len(keep)
    pos = np.arange(n)
    emit = np.ones(n, bool)
    if kind == "length":
        first = np.maximum(pos - L + 1, 0)
    elif kind == "time":
        clock = t["ts"][keep]
        first = np.searchsorted(clock, clock - D_MS, side="right")
    elif kind == "externalTime":
        clock = t["et"][keep]
        first = np.searchsorted(clock, clock - D_MS, side="right")
    elif kind == "lengthBatch":
        first = (pos // L) * L
        emit = pos < (n // L) * L
    else:
        bucket = (t["et"][keep] - t["et"][keep][0]) // BUCKET_MS
        first = np.searchsorted(bucket, bucket, side="left")
        emit = bucket < bucket[-1]
    mine = group[None, :] == np.arange(group.max() + 1)[:, None]
    zero = np.zeros((len(mine), 1))
    psum = np.concatenate([zero, np.cumsum(mine * price, axis=1)], axis=1)
    pcnt = np.concatenate([zero, np.cumsum(mine, axis=1)], axis=1)
    return {"ts": t["ts"][keep][emit],
            "sum": (psum[group, pos + 1] - psum[group, first])[emit],
            "count": (pcnt[group, pos + 1] - pcnt[group, first])[emit]}


@functools.lru_cache(maxsize=2)
def delivered(kind: str, grouped: bool, filtered: bool, batch: int,
              batches: int, step: float = 0.25):
    """One run of the device plan serves the three aggregators' cases."""
    app = ("@app:playback @app:deviceWindows('always')\n"
           "define stream S (symbol string, price double, volume int, "
           "et long);\n@info(name='q') from S"
           + ("[volume >= 500]" if filtered else "") + WINDOWS[kind]
           + " select sum(price) as s, avg(price) as a, count() as c"
           + (" group by symbol" if grouped else "") + " insert into O;")
    t = tape(len(kind) + 2 * grouped + filtered, batch * batches, step)
    mgr = SiddhiManager()
    try:
        rt = mgr.create_app_runtime(app)
        ent = rt.explain()["queries"]["q"]
        assert (ent["path"], ent["kind"]) == ("device", "window"), ent
        got = []
        rt.add_batch_callback("O", lambda b: got.append(
            (np.array(b.timestamps), {k: np.array(v)
                                      for k, v in b.columns.items()})))
        rt.start()
        codes = np.array([rt.strings.encode(f"K{i}") for i in range(8)],
                         np.int32)
        h = rt.input_handler("S")
        for i in range(batches):
            cut = slice(i * batch, (i + 1) * batch)
            h.send_batch({"symbol": codes[t["symbol"][cut]],
                          "price": t["price"][cut],
                          "volume": t["volume"][cut], "et": t["et"][cut]},
                         t["ts"][cut])
        rt.flush()
        record = rt.explain()["queries"]["q"]["window"]
    finally:
        mgr.shutdown()
    out = {"ts": np.concatenate([ts for ts, _c in got]),
           **{k: np.concatenate([c[k] for _ts, c in got]) for k in "sac"}}
    return out, expected(kind, t, grouped, filtered), record


def ulps(value) -> np.ndarray:
    return np.spacing(np.abs(value).astype(np.float32)).astype(np.float64)


def held(agg: str, got: dict, want: dict, exact_sums: bool) -> None:
    assert np.array_equal(got["ts"], want["ts"])    # a row an owed event
    assert len(want["ts"]) > 0
    if agg == "count":
        assert got["c"].dtype == np.int64
        assert np.array_equal(got["c"], want["count"])
    elif agg == "sum" and exact_sums:
        assert np.array_equal(got["s"], want["sum"])
    elif agg == "sum":
        # prices are positive: the range's sum of |v| is its sum
        err = np.abs(got["s"] - want["sum"])
        assert (err <= K_ULPS * ulps(want["sum"])).all(), err.max()
    else:
        mean = want["sum"] / want["count"]
        # the sum (exact on the grid, K_ULPS off it) and ONE f32 division
        room = (DIV_ULPS if exact_sums else K_ULPS + DIV_ULPS) * ulps(mean)
        assert (np.abs(got["a"] - mean) <= room).all()


# the small batches first: pytest-xdist hands a run's largest files out
# first, and the 2^16 programs' threads should not meet the timing tests
# of tests/test_spans.py, which open the run beside this file
CASES = [(kind, grouped, filtered, batch, 3 if batch == 1 << 10 else 2)
         for batch in (1 << 10, 1 << 16) for kind in WINDOWS
         for grouped in (False, True) for filtered in (False, True)] \
    + [("length", False, False, 1 << 18, 3)]    # the cell's own shape


@pytest.mark.parametrize("agg", ["sum", "avg", "count"])
@pytest.mark.parametrize(
    "kind,grouped,filtered,batch,batches", CASES,
    ids=[f"{k}-{'grouped' if g else 'ungrouped'}-"
         f"{'filtered' if f else 'unfiltered'}-{b}x{n}"
         for k, g, f, b, n in CASES])
def test_every_delivered_value_is_exact_on_the_grid(kind, grouped, filtered,
                                                    batch, batches, agg):
    got, want, record = delivered(kind, grouped, filtered, batch, batches)
    held(agg, got, want, exact_sums=True)
    assert record["kind"] == kind.lower()
    assert record["grouped"] is grouped
    assert record["sites"] == ["sum", "avg", "count"]
    assert record["sum_form"] == "pair_prefix"
    assert record["T"] == batch
    assert record["carry_overflow_reruns"] == record["carry_grows"] == 0


@pytest.mark.parametrize("agg", ["sum", "avg"])
def test_off_the_grid_a_sum_errs_by_its_own_ranges_rounding(agg):
    got, want, _r = delivered("length", False, False, 1 << 16, 2, step=0.01)
    held(agg, got, want, exact_sums=False)
    # and by far less than a prefix's rounding (an ulp of 7e6 is 0.5)
    assert np.abs(got["s"] - want["sum"]).max() < 0.05


def test_the_formula_this_replaced_fails_the_same_comparison():
    """`c = cumsum(v); c - c[left - 1]` in f32 over the whole
    [carry | batch] sequence: at 2^18 events of ~110 the prefixes reach
    2.9e7, where an f32 ulp is 2."""
    _got, want, _r = delivered("length", False, False, 1 << 18, 3)
    price = tape(len("length"), 3 << 18, 0.25)["price"].astype(np.float32)
    carry = np.zeros(1024, np.float32)
    sums = []
    for i in range(3):
        seq = np.concatenate([carry, price[i << 18:(i + 1) << 18]])
        prefix = np.cumsum(seq, dtype=np.float32)
        g = np.arange(len(seq))
        left = np.maximum(g - L + 1, 0)
        before = np.where(left > 0, prefix[np.maximum(left - 1, 0)],
                          np.float32(0))
        sums.append((prefix - before)[1024:])
        carry = seq[-1024:]
    old = {"ts": want["ts"], "s": np.concatenate(sums).astype(np.float64)}
    off = np.abs(old["s"] - want["sum"])
    assert off.max() >= 2.0 and (off > 0).mean() > 0.5
    with pytest.raises(AssertionError):
        held("sum", old, want, exact_sums=True)
    # the first rows of the stream, short prefixes, it had right
    assert np.array_equal(old["s"][:L], want["sum"][:L])
