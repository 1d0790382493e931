"""The decode of a lane block's packed result (lane_grid.py `_Filled`,
`ResultDecoder.lanes`; pattern_plan.py `_unpack_block`): ONE index over the filled cells, built
from the count header alone and read off the pulled array's strides, every
word fetched once through it.  Held here, column for column and in order,
to the form it replaced, kept below as the plain numpy reference
(`masked_lanes`, `masked_rows`): the `(lanes, words, M)` result transposed
to `(words, lanes * M)`, a validity mask as wide as the capacity, every
word read under it.  The packs are made by hand (random words in every
cell, past the counts too), so every geometry, word form and memory layout
is reached without a device; the device path end to end is held to the host
interpreter in tests/test_many_short_lanes.py and tests/test_lane_cut.py.
"""
import os
import sys
import warnings

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from siddhi_tpu import SiddhiManager                      # noqa: E402
from siddhi_tpu.core import lane_grid                     # noqa: E402
from siddhi_tpu.core.multi_query import MultiQueryDevicePatternPlan  # noqa: E402
from siddhi_tpu.core.nfa_device import join64_np          # noqa: E402
from siddhi_tpu.core.pattern_plan import DevicePatternPlan  # noqa: E402
from siddhi_tpu.core.schema import dtype_of               # noqa: E402
from siddhi_tpu.query import ast                          # noqa: E402

BASES = {"ts_base": 1_700_000_000_000, "seq_base": 7_000_000_000}
STREAM = "define stream S (sym string, price double, vol long, qty int);\n"
PARTITIONED = ("@app:devicePatterns('always')\n{head}" + STREAM +
               "partition with (sym of S) begin @info(name='q') {query} "
               "insert into Out; end;\n")
APPS = {
    # every form a word takes: f64 in the `f` pack, an i64 hi / lo pair,
    # an i32 word, a BOOL, the STRING key's code
    "typed": PARTITIONED.format(
        head="@app:devicePrecision('f64')\n",
        query="from every e1=S[price > 100] -> e2=S[price > e1.price] "
              "within 2 sec select e1.sym as sym, e1.price as a, e2.vol as v,"
              " e2.qty as n, e2.price > e1.price + 1.0 as hot, e2.price as b"),
    # f32 words, and the flag word of a `having` before them
    "having": PARTITIONED.format(
        head="",
        query="from every e1=S[price > 100] -> e2=S[price > e1.price] "
              "within 2 sec select e1.sym as sym, e1.price as a, "
              "e2.price as b, e2.vol as v having b > a + 5.0"),
    # `__present__` words: the side of an `or` that did not match is null
    "or": PARTITIONED.format(
        head="",
        query="from every e1=S[price > 100] -> e2=S[price > 120] or "
              "e3=S[price < 95] within 2 sec "
              "select e1.sym as sym, e2.price as b, e3.qty as c"),
    # the seq family's one flat block, an absent state beside a present one
    "absent": "@app:devicePatterns('always')\n" + STREAM +
              "@info(name='q') from every e1=S[price > 100] -> "
              "not S[price < 95] for 500 milliseconds or e2=S[price > 120] "
              "select e1.price as a, e2.price as b insert into Out;\n",
    # a fused group's flat flush: lanes are rules, `__qid__` says which
    "fused": "@app:playback\n" + STREAM + "".join(
        f"@info(name='q{i}') from every e1=S[price > {120 + i}] -> "
        "e2=S[price > e1.price] within 1 sec select e1.price as a, "
        f"e2.vol as v insert into Out{i % 2};\n" for i in range(8)),
}


@pytest.fixture(scope="module")
def plans():
    """{app: its one device pattern plan (a fused group's inner plan)}."""
    mgr, got = SiddhiManager(), {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        for name, app in APPS.items():
            plan, = [p for p in mgr.create_app_runtime(app)._plans
                     if isinstance(p, (DevicePatternPlan,
                                       MultiQueryDevicePatternPlan))]
            got[name] = getattr(plan, "inner", plan)
    yield got
    mgr.shutdown()


# -- the reference: the decode as it was before PR 43 ------------------------

def masked_rows(plan, ipack, fpack, base_valid, ts_base, seq_base):
    """`(words, cells)` packs to the match table, every word read under
    the mask `base_valid` over ALL cells (pattern_plan `_unpack_rows` as it
    stood)."""
    k = plan.kernel
    valid, ii = base_valid, 1
    if k.having is not None:
        valid, ii = base_valid & (ipack[1] != 0), 2
    if not valid.any():
        return None
    row, fi = {}, 0
    for nm in k.out_names:
        dt = np.dtype(k.out_dtypes[nm])
        if dt == np.float64:
            row[nm] = fpack[fi]; fi += 1
        elif dt == np.float32:
            row[nm] = ipack[ii].view(np.float32); ii += 1
        elif dt == np.int64:
            row[nm] = join64_np(ipack[ii], ipack[ii + 1]); ii += 2
        else:
            row[nm] = ipack[ii]; ii += 1
    tss = row["__timestamp__"][valid].astype(np.int64) + ts_base
    seqs = row["__seq__"][valid].astype(np.int64) + seq_base
    hseqs = row["__head_seq__"][valid]
    qids = row["__qid__"][valid] if k.emit_qid else None
    data = {}
    for nm, t in zip(plan._names, plan._types):
        col = row[nm][valid]
        if t == ast.AttrType.BOOL:
            col = col != 0
        data[nm] = col.astype(dtype_of(t))
    nulls = {}
    for nm, ref in k.null_outputs.items():
        pres = row.get(f"__present__.{ref}")
        if pres is not None:
            mask = pres[valid] == 0
            if mask.any():
                nulls[nm] = mask
    return (tss, seqs, hseqs, data, nulls, qids)


def masked_lanes(plan, ipack, fpack, ts_base, seq_base):
    """A `(lanes, words, M)` result: transposed to `(words, lanes * M)`, a
    COPY of the capacity, and one mask of it from the per-lane counts
    (`_unpack_lanes` as it stood)."""
    Ln, rows, Mm = ipack.shape
    ip2 = np.swapaxes(ipack, 0, 1).reshape(rows, Ln * Mm)
    fp2 = (np.swapaxes(fpack, 0, 1).reshape(fpack.shape[1], Ln * Mm)
           if fpack is not None else None)
    base = (np.arange(Mm)[None, :] < ipack[:, 0, 0][:, None]).reshape(-1)
    return masked_rows(plan, ip2, fp2, base, ts_base, seq_base)


def masked_block(plan, ipack, fpack, ts_base, seq_base):
    """One flat block's `(words, M)` result (`_unpack_block` as it stood)."""
    base = np.arange(ipack.shape[1]) < int(ipack[0, 0])
    return masked_rows(plan, ipack, fpack, base, ts_base, seq_base)


# -- hand-made packs ---------------------------------------------------------

def packed(plan, rng, counts, M):
    """A lane block's result as the device packs it, `(lanes, words, M)`
    i32 (+ the f64 `f` pack): `counts[l]` matches in lane l; every word of
    every cell random, past the counts too, flags (`having`, presence) 0
    or 1."""
    words = plan.decoder.words
    n_i = 1 + sum(2 if dt == np.int64 else 1
                  for pack, _w, dt in words.values() if pack == "i")
    n_f = sum(pack == "f" for pack, _w, _dt in words.values())
    L = len(counts)
    ipack = rng.integers(-2 ** 20, 2 ** 20, (L, n_i, M)).astype(np.int32)
    for nm, (_p, w, _dt) in words.items():
        if nm == "__having__" or nm.startswith("__present__."):
            ipack[:, w, :] = rng.integers(0, 2, (L, M))
    ipack[:, 0, :] = rng.integers(0, 9, (L, M))     # the header's other cells
    ipack[:, 0, 0] = counts
    return ipack, (rng.random((L, n_f, M)) if n_f else None)


def fill_counts(fill, rng, L, M):
    return {"empty": lambda: np.zeros(L, np.int64),
            "one-row": lambda: (np.arange(L) == L // 3).astype(np.int64),
            "full": lambda: np.full(L, M),
            # most lanes quiet, a few rows in some, one lane at exactly M
            "ragged": lambda: np.where(rng.random(L) < 0.6, 0,
                                       rng.integers(1, M + 1, L))
            * (np.arange(L) != 1) + M * (np.arange(L) == 1)}[fill]()


def decode(plan, ipack, fpack):
    """The production path from the pulled result on: `_materialize_par`
    given numpy arrays where the device's would be (the header read, the
    overflow check, `ResultDecoder.lanes`)."""
    out = {"i": ipack} if fpack is None else {"i": ipack, "f": fpack}
    return plan._materialize_par({"out": out, "M": ipack.shape[-1],
                                  "L": ipack.shape[0], "R": None, **BASES})


def assert_same_table(got, want):
    """Column for column, row for row, dtype for dtype (bytes: random
    words make NaNs of some f32 views)."""
    if want is None:
        assert got is None
        return
    assert got is not None

    def same(a, b, what):
        assert a.dtype == b.dtype and a.shape == b.shape, (what, a.dtype,
                                                           b.dtype)
        assert a.tobytes() == b.tobytes(), what
    for k, what in enumerate(("tss", "seqs", "hseqs")):
        same(got[k], want[k], what)
    for d, what in ((3, "data"), (4, "nulls")):
        assert list(got[d]) == list(want[d]), what
        for nm in want[d]:
            same(got[d][nm], want[d][nm], (what, nm))
    assert (got[5] is None) == (want[5] is None)
    if want[5] is not None:
        same(got[5], want[5], "qids")


LANE_APPS = ["typed", "having", "or", "fused"]


@pytest.mark.parametrize("fill", ["empty", "one-row", "full", "ragged"])
@pytest.mark.parametrize("app", LANE_APPS)
def test_the_indexed_decode_equals_the_masked_form(plans, app, fill):
    plan = plans[app]
    rng = np.random.default_rng(7)
    L, M = 37, 16
    counts = fill_counts(fill, rng, L, M)
    ipack, fpack = packed(plan, rng, counts, M)
    if app == "having" and fill == "one-row":       # ... which is kept
        ipack[:, plan.decoder.words["__having__"][1], :] = 1
    want = masked_lanes(plan, ipack, fpack, **BASES)
    before = dict(plan.decoder.result_decode)
    got = decode(plan, ipack, fpack)
    assert_same_table(got, want)
    if fill == "empty":
        assert want is None
    else:
        assert len(want[0]) > 0
        if app != "having":
            assert len(want[0]) == counts.sum()
        assert (want[5] is not None) == (app == "fused")
        assert bool(want[4]) == (app == "or")
    assert plan.decoder.result_decode == {
        "indexed": before["indexed"] + (want is not None)}


def test_a_having_that_keeps_no_row_is_no_table(plans):
    plan = plans["having"]
    rng = np.random.default_rng(2)
    ipack, fpack = packed(plan, rng, fill_counts("ragged", rng, 9, 8), 8)
    ipack[:, plan.decoder.words["__having__"][1], :] = 0
    assert masked_lanes(plan, ipack, fpack, **BASES) is None
    assert decode(plan, ipack, fpack) is None


# the axis orders a pulled `(lanes, words, M)` result has been seen in or
# may come in: as made, word-major (zipf's: the transposed reshape a view),
# match-major, and lanes innermost
LAYOUTS = [(0, 1, 2), (1, 0, 2), (2, 0, 1), (1, 2, 0)]


@pytest.mark.parametrize("axes", LAYOUTS, ids=lambda a: "".join(map(str, a)))
@pytest.mark.parametrize("app", ["typed", "or"])
def test_positions_are_read_off_the_strides(plans, app, axes):
    plan = plans[app]
    rng = np.random.default_rng(11)
    ipack, fpack = packed(plan, rng, fill_counts("ragged", rng, 21, 8), 8)
    want = masked_lanes(plan, ipack, fpack, **BASES)
    back = np.argsort(axes)
    laid = [None if a is None else
            np.ascontiguousarray(a.transpose(axes)).transpose(back)
            for a in (ipack, fpack)]
    assert laid[0].shape == ipack.shape
    assert laid[0].flags.c_contiguous == (axes == (0, 1, 2))
    assert_same_table(decode(plan, *laid), want)


@pytest.mark.parametrize("cut", ["lanes", "matches", "words"])
def test_a_result_that_is_no_permutation_of_one_block_is_decoded(plans, cut):
    """A view with holes in it (every other lane, match or word of a
    larger buffer): `_flat_words` copies it, the rows are the same."""
    plan = plans["typed"]
    rng = np.random.default_rng(13)
    ipack, fpack = packed(plan, rng, fill_counts("ragged", rng, 12, 8), 8)
    want = masked_lanes(plan, ipack, fpack, **BASES)

    def holed(a):
        axis = ("lanes", "words", "matches").index(cut)
        big = np.repeat(a, 2, axis=axis)
        view = big[tuple(slice(None, None, 2) if k == axis else slice(None)
                         for k in range(3))]
        assert not view.flags.c_contiguous and np.array_equal(view, a)
        return view
    assert_same_table(decode(plan, holed(ipack), holed(fpack)), want)


def test_an_index_past_the_result_raises_and_reads_no_neighbour(
        plans, monkeypatch):
    """The takes clip (they write into scratch), so what `mode="raise"`
    would have checked is checked once a result: strides that put a cell
    outside the pack stop the flush."""
    plan = plans["typed"]
    rng = np.random.default_rng(3)
    ipack, fpack = packed(plan, rng, np.full(6, 8), 8)
    flat_words = lane_grid._flat_words

    def doubled(a):
        flat, strides = flat_words(a)
        return flat, tuple(2 * s for s in strides)
    monkeypatch.setattr(lane_grid, "_flat_words", doubled)
    with pytest.raises(IndexError, match="decode index past the result"):
        decode(plan, ipack, fpack)


def test_a_count_past_the_capacity_is_no_silent_prefix(plans):
    """`_unpack_block` is handed its count: one past M (the callers re-run
    the block before they decode, so none arrives) stops the flush."""
    plan = plans["absent"]
    rng = np.random.default_rng(4)
    ipack, fpack = packed(plan, rng, np.array([9]), 8)
    plan._ts_base, plan._seq_base = BASES["ts_base"], BASES["seq_base"]
    with pytest.raises(IndexError, match="decode index past the result"):
        plan._unpack_block(ipack[0], None, 9)


@pytest.mark.parametrize("n", [0, 1, 5, 8])
@pytest.mark.parametrize("app", ["absent", "having", "typed"])
def test_a_flat_block_is_the_one_lane_case(plans, app, n):
    """`(words, M)`, the seq and chunk families' and an unpartitioned
    flush's result: the filled cells are the prefix `[:n]`."""
    plan = plans[app]
    rng = np.random.default_rng(5)
    ipack, fpack = packed(plan, rng, np.array([n]), 8)
    ipack, fpack = ipack[0], None if fpack is None else fpack[0]
    plan._ts_base, plan._seq_base = BASES["ts_base"], BASES["seq_base"]
    want = masked_block(plan, ipack, fpack, **BASES)
    assert (want is None) == (n == 0)
    assert_same_table(plan._unpack_block(ipack, fpack, n), want)
    if app == "absent" and n >= 5:      # some row's `e2` is absent
        assert list(want[4]) == ["b"]


def test_the_table_is_the_flush_s_own_memory(plans):
    """Flush n's table is kept; flushes n+1 (larger) and n+2 (smaller)
    decode through the same scratch: the kept arrays stay as they were, and
    none is the plan's scratch or the pulled result."""
    plan = plans["typed"]
    rng = np.random.default_rng(17)
    kept = []
    for L in (9, 40, 5):
        ipack, fpack = packed(plan, rng, fill_counts("ragged", rng, L, 8), 8)
        got = decode(plan, ipack, fpack)
        arrays = [*got[:3], *got[3].values(), *got[4].values()]
        for a in arrays:
            assert not any(np.shares_memory(a, b) for b in
                           (ipack, fpack, *plan.decoder.scratch._bufs.values()))
        kept.append((arrays, [a.copy() for a in arrays]))
    for arrays, copies in kept:
        for a, c in zip(arrays, copies):
            assert a.tobytes() == c.tobytes()
