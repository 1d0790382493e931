"""The lane block's one question, "the first index i >= s[h] with keep[i]
and vals[i] OP v[h]; L if none", in its two forms: the dense all-pairs
masked min (short lanes) and the segment tree (long ones), each against a
plain numpy loop; its other question, "the column's element at idx[m]", as
one fused one-hot sum against numpy's own indexing, bit for bit; its
third, "the live candidates' columns as the M match rows", as one fused
one-hot sum against the scatter and a numpy loop; then the
whole block on both sides of the rule that
picks the form (nfa_parallel.DENSE_MAX_F), forced by SHAPE: the same
recorded block input padded past the bound must give the same bytes, and
flushes longer than the bound must still equal the sequential kernel."""
import warnings
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import nfa_parallel as npar, pattern_plan
from siddhi_tpu.core.nfa_device import LOCAL_SPAN, pow2_at_least
from siddhi_tpu.core.pattern_plan import DevicePatternPlan

import test_fused_cut as fc
import test_plan_families as pf

OPS = {"gt": np.greater, "ge": np.greater_equal,
       "lt": np.less, "le": np.less_equal}
I32_MIN, I32_MAX = -2 ** 31, 2 ** 31 - 1
F = 37                                   # not a power of two: L = 64
L = pow2_at_least(F, lo=2)


def _ftz(a):
    """A floating compare on the backend reads a denormal as zero."""
    if a.dtype.kind != "f":
        return a
    return np.where(np.abs(a) < np.finfo(a.dtype).tiny, a.dtype.type(0), a)


def _loop(vals, keep, s, v, op):
    """The question, one query and one event at a time."""
    out = np.full(len(s), L, np.int32)
    vals, v = _ftz(vals), _ftz(v)
    for h in range(len(s)):
        for i in range(max(int(s[h]), 0), len(vals)):
            if keep[i] and vals[i] == vals[i] and OPS[op](vals[i], v[h]):
                out[h] = i
                break
    return out


def _dense(vals, keep, s, v, op):
    return np.asarray(npar._first_hit_dense(
        jnp.asarray(vals), jnp.asarray(keep), L, jnp.asarray(s),
        jnp.asarray(v), op))


def _tree(vals, keep, s, v, op):
    dt = npar._tree_dtype(vals.dtype, v.dtype)
    heap = npar._build_heap(jnp.asarray(vals), jnp.asarray(keep), L,
                            "max" if op in ("gt", "ge") else "min", dt)
    return np.asarray(npar._first_hit(heap, L, jnp.asarray(s),
                                      jnp.asarray(v), op))


def _columns(kind, rng):
    """(vals, v per query): the column's special values as data AND as rhs."""
    if kind == "f32":
        pool = np.array([0.0, -0.0, 1.5, -2.0, np.nan, np.inf, -np.inf,
                         3.25, 1e-40, -1e-40], np.float32)
        return rng.choice(pool, F), rng.choice(pool, F)
    if kind == "i32":
        pool = np.array([0, 1, -1, I32_MAX, I32_MIN, 7, I32_MAX - 1,
                         I32_MIN + 1], np.int32)
        return rng.choice(pool, F), rng.choice(pool, F)
    # the expiry query: int32 ts offsets out to +-LOCAL_SPAN against an
    # int64 horizon `head ts + within_ms`, `within_ms` above 2^31 too
    pool = np.array([0, LOCAL_SPAN, -LOCAL_SPAN, 5, 1000, LOCAL_SPAN - 1],
                    np.int32)
    ts = rng.choice(pool, F)
    if kind == "ts":
        ts = np.sort(ts)
    within = rng.choice(np.array([0, 10, 2 ** 30, 2 ** 31 + 5, 2 ** 33],
                                 np.int64), F)
    return ts, ts.astype(np.int64) + within      # "ts_regressed": unsorted


def _starts(rng):
    """Every kind of s in one array: below 0, inside, = F, in (F, L], above L."""
    return np.concatenate([
        [-5, -1, 0, F - 1, F, F + 1, L, L + 3],
        rng.integers(0, F, F - 8)]).astype(np.int32)


@pytest.mark.parametrize("mask", ["none", "all", "random"])
@pytest.mark.parametrize("kind", ["f32", "i32", "ts", "ts_regressed"])
@pytest.mark.parametrize("op", list(OPS))
def test_dense_tree_and_loop_agree(op, kind, mask):
    rng = np.random.default_rng(zlib.crc32(f"{op}-{kind}-{mask}".encode()))
    vals, v = _columns(kind, rng)
    keep = {"none": np.zeros(F, bool), "all": np.ones(F, bool),
            "random": rng.random(F) < 0.6}[mask]
    s = _starts(rng)
    want = _loop(vals, keep, s, v, op)
    if mask != "none":
        assert (want < L).any() and (want == L).any(), "a one-sided case"
    assert _dense(vals, keep, s, v, op).tolist() == want.tolist()
    got = _tree(vals, keep, s, v, op)
    # the tree's accepted corner (its docstring): an infinite rhs, which
    # masked-out leaves' sentinels would otherwise answer
    sure = np.isfinite(v) if kind == "f32" else np.ones(F, bool)
    assert got[sure].tolist() == want[sure].tolist()


@pytest.mark.parametrize("s0,want", [(-7, 2), (3, 5), (F, L), (L + 9, L)])
@pytest.mark.parametrize("form", ["dense", "tree"])
def test_start_is_clipped_not_wrapped(form, s0, want):
    """s below 0 reads from 0; s at or past F finds nothing (L)."""
    vals = np.zeros(F, np.float32)
    vals[[2, 5]] = 9.0
    s = np.full(4, s0, np.int32)
    v = np.full(4, 1.0, np.float32)
    got = (_dense if form == "dense" else _tree)(
        vals, np.ones(F, bool), s, v, "gt")
    assert got.tolist() == [want] * 4


@pytest.mark.parametrize("op,rhs,hit", [
    ("ge", 0.0, 0.0), ("le", 0.0, 0.0), ("ge", -0.0, 0.0),
    # the smallest normal's neighbour is the largest denormal
    ("ge", float(np.finfo(np.float32).tiny), float(np.finfo(np.float32).tiny)),
    ("le", -float(np.finfo(np.float32).tiny), -float(np.finfo(np.float32).tiny)),
])
@pytest.mark.parametrize("form", ["dense", "tree"])
def test_ge_le_at_a_rhs_whose_neighbour_is_denormal(form, op, rhs, hit):
    """`price >= 0.0` keeps its `price == 0.0` rows: the tree used to
    rewrite `>= v` to `> nextafter(v, -inf)`, a denormal for v = 0.0 that
    the compare flushes to zero, so it behaved as `> 0.0`."""
    miss = -3.0 if op == "ge" else 3.0
    vals = np.full(F, miss, np.float32)
    vals[11] = hit
    s = np.array([0, 11, 12], np.int32)
    v = np.full(3, rhs, np.float32)
    got = (_dense if form == "dense" else _tree)(
        vals, np.ones(F, bool), s, v, op)
    assert got.tolist() == [11, 11, L]


@pytest.mark.parametrize("within,want", [
    # every horizon past INT32_MAX: nothing expires anyone (a compare that
    # wrapped int32 would expire every head at once)
    (2 ** 31 + 5, [8, 8, 8, 8, 8]),
    # the earliest head's horizon is 2^30 - 8: the event at 2^30 - 3 ends it
    (2 ** 31 - 8, [3, 8, 8, 8, 8]),
    (2 ** 33, [8, 8, 8, 8, 8]),
])
@pytest.mark.parametrize("form", ["dense", "tree"])
def test_expiry_horizon_past_int32_does_not_wrap(form, within, want):
    """ts offsets at +-2^30 (LOCAL_SPAN) against `head ts + within_ms`."""
    ts = np.array([-LOCAL_SPAN, -LOCAL_SPAN + 10, 0, LOCAL_SPAN - 3,
                   LOCAL_SPAN], np.int32)
    v = ts.astype(np.int64) + within
    s = np.arange(1, 6, dtype=np.int32)
    keep = np.ones(5, bool)
    if form == "dense":
        got = npar._first_hit_dense(jnp.asarray(ts), jnp.asarray(keep), 8,
                                    s, jnp.asarray(v), "gt")
    else:
        heap = npar._build_heap(jnp.asarray(ts), jnp.asarray(keep), 8,
                                "max", jnp.dtype(jnp.int64))
        got = npar._first_hit(heap, 8, jnp.asarray(s), jnp.asarray(v), "gt")
    assert np.asarray(got).tolist() == want


@pytest.mark.parametrize("op,want", [("gt", [1, 2, 3, 4, 8]),
                                     ("ge", [1, 2, 3, 4, 8]),
                                     ("lt", [8] * 5), ("le", [8] * 5)])
def test_int64_rhs_below_every_int32_saturates_exactly(op, want):
    """The dense form narrows an int64 rhs to int32 by saturation: a rhs
    under INT32_MIN is beaten by every value, INT32_MIN itself included."""
    vals = np.array([5, I32_MIN, 0, I32_MIN, 7], np.int32)
    v = np.full(5, -2 ** 40, np.int64)
    got = npar._first_hit_dense(jnp.asarray(vals), jnp.ones(5, bool), 8,
                                np.arange(1, 6, dtype=np.int32),
                                jnp.asarray(v), op)
    assert np.asarray(got).tolist() == want


# ---------------------------------------------------------------------------
# the indexed read: a one-hot sum over the lane, not a gather
# ---------------------------------------------------------------------------

def _read_column(kind, rng, shape):
    """A column whose every special value is there to be read."""
    if kind == "f32":
        pool = np.array([0x7FC00000, 0xFFC12345,      # NaN of two payloads
                         0x80000000, 0x00000000,      # -0.0, 0.0
                         0x7F800000, 0xFF800000,      # +inf, -inf
                         0x00000001, 0x80012345,      # denormals
                         0x3FC00000, 0xC2F70000], np.uint32).view(np.float32)
    elif kind == "i32":
        pool = np.array([I32_MIN, I32_MAX, 0, -1, 1, I32_MIN + 1, 12345],
                        np.int32)
    else:
        pool = np.array([True, False])
    return pool[rng.integers(0, len(pool), shape)]


def _read_index(kind, rng, shape):
    n = shape[-1]
    return {"in_range": lambda: rng.integers(0, F, shape),
            "repeated": lambda: np.full(shape, F - 1) * (np.arange(n) % 3 > 0),
            "negative": lambda: rng.integers(-9, 3, shape),
            "past_F": lambda: rng.integers(F - 3, F + 40, shape),
            }[kind]().astype(np.int32)


@pytest.mark.parametrize("lanes", [0, 5], ids=["flat", "lane_vmap"])
@pytest.mark.parametrize("index", ["in_range", "repeated", "negative",
                                   "past_F", "identity"])
@pytest.mark.parametrize("kind", ["f32", "i32", "bool"])
def test_dense_read_equals_the_gather_bit_for_bit(kind, index, lanes):
    """`_Read` on a short lane: col[clip(idx, 0, F - 1)] as numpy reads it,
    byte for byte (no float is compared or added: NaN payloads, -0.0 and
    denormals pass through), for M askers that are not F; the block's own
    arange is answered by the column itself and asks nothing."""
    rng = np.random.default_rng(zlib.crc32(f"{kind}-{index}-{lanes}".encode()))
    lead = (lanes,) if lanes else ()
    M = F if index == "identity" else 53
    col = _read_column(kind, rng, lead + (F,))
    idx = None if index == "identity" else _read_index(index, rng, lead + (M,))
    asked = []

    def lane(col, idx):
        j0 = jnp.arange(F, dtype=jnp.int32)
        read = npar._Read(F, j0)
        out = read(col, j0 if idx is None else idx)
        if idx is None:
            assert out is col
        asked.append(read.asked(max(lanes, 1)))
        return out

    got = np.asarray((jax.vmap(lane, in_axes=(0, None if idx is None else 0))
                      if lanes else lane)(jnp.asarray(col), idx))
    want = col if idx is None else np.take_along_axis(
        col, np.clip(idx, 0, F - 1), axis=-1)
    assert got.dtype == col.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    dense = 0 if idx is None else 1
    assert asked == [{"dense": dense, "gather": 0, "identity": 1 - dense,
                      "pairs_per_call": dense * max(lanes, 1) * F * M,
                      "lanes": max(lanes, 1), "F": F}]


@pytest.mark.parametrize("lanes", [0, 3], ids=["flat", "lane_vmap"])
def test_columns_read_at_one_index_share_one_reduction(lanes):
    """`_Read.all`: columns of every narrow dtype at ONE index array come
    back as each would alone (one variadic sum: the lowered text carries
    one `reduce` and no gather); a wide one among them takes the gather."""
    rng = np.random.default_rng(23 + lanes)
    lead = (lanes,) if lanes else ()
    kinds = ["f32", "i32", "bool", "f32"]
    cols = [_read_column(k, rng, lead + (F,)) for k in kinds]
    wide = rng.integers(-2 ** 40, 2 ** 40, lead + (F,))
    idx = _read_index("past_F", rng, lead + (41,))
    asked = []

    def lane(idx, wide, *cols):
        read = npar._Read(F, jnp.arange(F, dtype=jnp.int32))
        out = read.all([*cols, wide], idx)
        asked.append(read.asked(1))
        return out

    fn = jax.jit(jax.vmap(lane) if lanes else lane)
    got = fn(idx, wide, *cols)
    for g, c in zip(got, [*cols, wide]):
        want = np.take_along_axis(c, np.clip(idx, 0, F - 1), axis=-1)
        assert np.asarray(g).dtype == c.dtype
        assert np.asarray(g).tobytes() == want.tobytes()
    assert asked == [{"dense": 4, "gather": 1, "identity": 0,
                      "pairs_per_call": 4 * F * 41, "lanes": 1, "F": F}]
    txt = fn.lower(idx, wide, *cols).as_text()
    assert txt.count("stablehlo.reduce") == 1, txt.count("stablehlo.reduce")
    assert txt.count('"stablehlo.gather"(') == 1


@pytest.mark.parametrize("F_,dtype", [(F, np.int64), (F, np.float64),
                                      (npar.DENSE_MAX_F + 1, np.float32)])
def test_wide_columns_and_long_lanes_keep_the_gather(F_, dtype):
    """A column wider than four bytes has no int32 image, and past
    DENSE_MAX_F the pairs cost more than the gather: both read as before."""
    rng = np.random.default_rng(11)
    col = rng.integers(-2 ** 40, 2 ** 40, F_).astype(dtype)
    idx = rng.integers(-5, F_ + 5, 29).astype(np.int32)
    read = npar._Read(F_, jnp.arange(F_, dtype=jnp.int32))
    got = np.asarray(read(jnp.asarray(col), jnp.asarray(idx)))
    assert got.tobytes() == col[np.clip(idx, 0, F_ - 1)].tobytes()
    assert read.asked(1) == {"dense": 0, "gather": 1, "identity": 0,
                             "pairs_per_call": 0, "lanes": 1, "F": F_}


# ---------------------------------------------------------------------------
# the compaction: a one-hot sum over the lane, not a scatter
# ---------------------------------------------------------------------------

def _compact_loop(cols, live, M):
    """The rows, one candidate at a time: the first M live ones in order."""
    out = [np.zeros(M, c.dtype) for c in cols]
    n = 0
    for i in np.flatnonzero(live):
        if n < M:
            for o, c in zip(out, cols):
                o[n] = c[i]
        n += 1
    return out, n


_COMPACT = npar._Compact      # the class itself: a test below patches the name


def _scatter_form(F_, M):
    """`_Compact` as a lane past DENSE_MAX_F takes it."""
    return _COMPACT(npar.DENSE_MAX_F + 1, M)


LIVE = {"empty": lambda rng, n: np.zeros(n, bool),
        "all": lambda rng, n: np.ones(n, bool),
        "random": lambda rng, n: rng.random(n) < 0.4,
        "one_at_the_end": lambda rng, n: np.arange(n) == n - 1}


@pytest.mark.parametrize("nest", [(), (5,), (3, 4)],
                         ids=["flat", "lane_vmap", "row_lane_vmap"])
@pytest.mark.parametrize("C", [1, 3], ids=["C1", "final_count_C3"])
@pytest.mark.parametrize("M", [5, F, 128], ids=["M_lt_F", "M_eq_F", "M_gt_CF"])
@pytest.mark.parametrize("live", list(LIVE))
def test_dense_compaction_equals_the_scatter_bit_for_bit(live, M, C, nest):
    """`_Compact` on a short lane against the scatter it replaces and a
    numpy loop, byte for byte: the block's columns (head, completion, and
    the slot when the final position counts) of C * F candidates, F = 37
    (no multiple of 128); more live candidates than M keep the first M in
    order and `n` counts them all; rows past the live count are 0; flat,
    under one vmap and under `_make_lane_block`'s two (rows x lanes)."""
    rng = np.random.default_rng(zlib.crc32(f"{live}-{M}-{C}-{nest}".encode()))
    n_c = C * F
    mask = np.stack([LIVE[live](rng, n_c) for _ in range(int(np.prod(nest)))]
                    ).reshape(nest + (n_c,)) if nest else LIVE[live](rng, n_c)
    comp = _read_column("i32", rng, nest + (n_c,))
    asked = []

    def lane(form, comp, mask):
        compact = form(F, M)
        cols = [jnp.tile(jnp.arange(F, dtype=jnp.int32), C), comp]
        if C > 1:
            cols.append(jnp.repeat(jnp.arange(C, dtype=jnp.int32), F))
        out, n = compact(cols, mask)
        asked.append(compact.asked(int(np.prod(nest))))
        return tuple(out), n

    got = {}
    for name, form in (("dense", npar._Compact), ("scatter", _scatter_form)):
        fn = lambda comp, mask, form=form: lane(form, comp, mask)  # noqa: E731
        for _ in nest:
            fn = jax.vmap(fn)
        got[name] = jax.tree_util.tree_map(np.asarray, jax.jit(fn)(comp, mask))
    cols_np = [np.tile(np.arange(F, dtype=np.int32), C), None] \
        + ([np.repeat(np.arange(C, dtype=np.int32), F)] if C > 1 else [])
    for at in np.ndindex(*nest):
        cols_np[1] = comp[at]
        want, n = _compact_loop(cols_np, mask[at], M)
        for name, (out, n_got) in got.items():
            assert int(n_got[at]) == n, (name, at)
            for o, w in zip(out, want):
                assert o[at].dtype == w.dtype
                assert o[at].tobytes() == w.tobytes(), (name, at)
    if live == "all":
        assert n > min(M, n_c) or M >= n_c       # the overflow case is there
    ncols, lanes = 2 + (C > 1), int(np.prod(nest))
    assert asked == [
        {"dense": ncols, "scatter": 0, "lanes": lanes, "F": F, "M": M,
         "pairs_per_call": lanes * ncols * n_c * M},
        {"dense": 0, "scatter": ncols, "lanes": lanes, "M": M,
         "F": npar.DENSE_MAX_F + 1, "pairs_per_call": 0}]


@pytest.mark.parametrize("F_,scatters", [(F, 0), (200, 0),
                                         (npar.DENSE_MAX_F, 0),
                                         (npar.DENSE_MAX_F + 1, 3)])
def test_columns_compacted_at_one_prefix_share_one_reduction(F_, scatters):
    """The form is read off F alone; the dense form's lowered text carries
    ONE variadic `reduce` for all the columns and no scatter, the other a
    scatter a column (only traced: a lane at the bound holds F * M pairs
    on a backend that does not fuse them)."""
    def lane(comp, mask):
        compact = npar._Compact(F_, 64)
        j0 = jnp.arange(F_, dtype=jnp.int32)
        return compact([j0, comp, j0 // 2], mask)

    txt = jax.jit(lane).lower(
        jax.ShapeDtypeStruct((F_,), jnp.int32),
        jax.ShapeDtypeStruct((F_,), jnp.bool_)).as_text()
    assert txt.count('"stablehlo.scatter"(') == scatters
    assert txt.count("stablehlo.reduce(") == (0 if scatters else 1)


# ---------------------------------------------------------------------------
# the rule, and the block on both sides of it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes,F_,dense", [
    (1024, 448, True),            # pattern1k.sat; a quarter of it per mesh chip
    (256, 448, True),
    (1024, 64, True),             # pattern1k.wire-paced
    (2048, 2048, True),           # pattern1k-zipf.sat: hot lanes cut at 2048
    (1, npar.DENSE_MAX_F, True),  # the boundary itself
    (1, npar.DENSE_MAX_F + 1, False),
    (1, 2 ** 18, False),          # the flat P = 1 block of a 2^18-event batch
    (1000, 2 ** 16, False),       # fused multi-query lanes see the whole stream
])
def test_rule_reads_the_blocks_static_shape(lanes, F_, dense):
    """The form is a function of F alone, for the first-hit queries, the
    indexed reads and the compaction alike, and the counters that say which engaged
    are what tracing the block at that shape records (no run: the block is
    only traced here, as `jax.eval_shape` does)."""
    kern = pf._c4_kernel()
    T = (lanes, F_) if lanes > 1 else F_
    shape = (lanes, F_) if lanes > 1 else (F_,)
    lead = (lanes,) if lanes > 1 else ()
    ev = {"__flat.__ts__": jax.ShapeDtypeStruct(shape, jnp.int32),
          "__flat.__seq__": jax.ShapeDtypeStruct(shape, jnp.int32),
          "__flat.0.price": jax.ShapeDtypeStruct(shape, jnp.float32),
          "__nev__": jax.ShapeDtypeStruct(lead, jnp.int32),
          "__prev_seq__": jax.ShapeDtypeStruct(lead, jnp.int32),
          "__base_ts__": jax.ShapeDtypeStruct((), jnp.int64),
          "__base_seq__": jax.ShapeDtypeStruct((), jnp.int64)}
    assert kern.first_hit is None and kern.indexed_read is None \
        and kern.compaction is None
    jax.eval_shape(kern.block_fn(T, F_), {}, ev)
    # C4: one expiry query (shared down the chain) + two threshold hops
    want = {"dense": 3, "tree": 0, "pairs_per_call": 3 * lanes * F_ * F_} \
        if dense else {"dense": 0, "tree": 3, "pairs_per_call": 0}
    assert kern.first_hit == {**want, "lanes": lanes, "F": F_}
    # C4: e2's price at hop 2, the dedup's seq, two capture indices, three
    # selected prices and the three stamps of a match row; e1's price at
    # hop 1 is read at the block's own arange
    want = {"dense": 10, "gather": 0, "pairs_per_call": 10 * lanes * F_ * F_} \
        if dense else {"dense": 0, "gather": 10, "pairs_per_call": 0}
    assert kern.indexed_read == {**want, "identity": 1, "lanes": lanes,
                                 "F": F_}
    # C4: a match row's head and its completion, at one prefix count
    want = {"dense": 2, "scatter": 0, "pairs_per_call": 2 * lanes * F_ * F_} \
        if dense else {"dense": 0, "scatter": 2, "pairs_per_call": 0}
    assert kern.compaction == {**want, "lanes": lanes, "F": F_, "M": F_}


def _resize_block(ev, T, F2, lanes=2):
    """The same block input with every event array cut or padded to F2
    slots (`__nev__` says how many are events: the rest are never valid),
    and a lane grid cut to its first `lanes` lanes."""
    lane = isinstance(T, tuple)
    F1 = T[1] if lane else T
    assert int(np.max(ev["__nev__"])) <= F2
    out = {}
    for k, v in ev.items():
        if lane and v.ndim and v.shape[0] == T[0]:
            v = v[:lanes]
        if k.startswith("__flat.") and v.shape[-1:] == (F1,):
            v = v[..., :F2]
            v = np.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, F2 - v.shape[-1])])
        out[k] = v
    return out, ((min(lanes, T[0]), F2) if lane else F2)


# name -> (query, partitioned, families that engage); per-element `within`s
# that differ, a count head, a final count and a logical position among
# them (PR 27's corpus)
BLOCKS = {
    "c4_lanes": (pf.C4_Q.replace("1 sec", "400 milliseconds"), True,
                 ("scan",)),
    "within_differs_lanes": (pf.EXPIRY["within_long_then_short"][0], True,
                             ("scan", "dfa")),
    "count_head_flat": (pf.EXPIRY["count_head_successor"][0], False,
                        ("scan", "dfa")),
    "logical_flat": (pf.EXPIRY["logical_pair_chain"][0], False,
                     ("scan", "dfa")),
    "final_count_lanes": (pf.EXPIRY["final_count_chain"][0], True,
                          ("scan",)),
    "le_threshold_flat": (pf.ELIGIBLE["le_threshold"][0], False, ("scan",)),
}


@pytest.mark.parametrize("name,fam", [(n, f) for n, b in BLOCKS.items()
                                      for f in b[2]])
def test_block_bytes_equal_across_the_rule(name, fam):
    """The last block of a seeded run, cut to the 64-slot bucket its
    events fill (dense), gives the same packed bytes when the SAME input
    is padded to DENSE_MAX_F (still dense: the boundary) and to
    DENSE_MAX_F + 1 (tree).  A third of the timestamps regress."""
    q, part, _fams = BLOCKS[name]
    force = f"@app:patternFamily('{fam}')\n"
    if part:
        app = force + "@app:partitionCapacity(8)\n" + pf.PART_HEAD \
            + pf._lane_app(q)
    else:
        app = force + "@app:devicePatterns('always')\n" + pf.HEAD + q
    mgr = SiddhiManager()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        rt = mgr.create_app_runtime(app)
    rt.start()
    plan = next(p for p in rt._plans if isinstance(p, DevicePatternPlan))
    assert plan.family == fam, plan.families
    kern = plan._parallel_kernel()
    calls: list = []
    pf._record_lane_blocks(kern, calls)
    rng = np.random.default_rng(5)
    ih = rt.input_handler("S")
    for b in range(2):
        for j in range(300):
            i = b * 300 + j
            late = int(rng.integers(-300, 300)) if rng.random() < 0.3 else 0
            ih.send((f"K{rng.integers(0, 4)}",
                     float(np.round(rng.uniform(90, 130) * 4) / 4),
                     int(rng.integers(1, 1000))),
                    timestamp=1_700_000_000_000 + i * 7 + late)
        rt.flush()
    mgr.shutdown()
    assert calls
    fresh = npar.ParallelChainKernel(kern.prog, kern.nfak, kern.family)
    T, M, ev = calls[-1]
    F1 = 64 * -(-int(np.max(ev["__nev__"])) // 64)
    assert F1 < npar.DENSE_MAX_F
    ev1, T1 = _resize_block(ev, T, F1)
    want = fresh.block_fn(T1, M)({}, ev1)[1]
    assert fresh.first_hit["tree"] == 0 and fresh.first_hit["dense"] > 0
    ncols = 3 if name.startswith("final_count") else 2
    assert fresh.compaction["dense"] == ncols
    for F2, tree in ((npar.DENSE_MAX_F, False), (npar.DENSE_MAX_F + 1, True)):
        ev2, T2 = _resize_block(ev, T, F2)
        got = fresh.block_fn(T2, M)({}, ev2)[1]
        assert (fresh.first_hit["dense"] == 0) == tree, fresh.first_hit
        assert (fresh.first_hit["tree"] > 0) == tree, fresh.first_hit
        assert fresh.compaction["scatter"] == (ncols if tree else 0)
        assert fresh.compaction["dense"] == (0 if tree else ncols)
        assert sorted(got) == sorted(want)
        for k in want:
            assert np.asarray(got[k]).tobytes() \
                == np.asarray(want[k]).tobytes(), (name, fam, F2, k)
    assert int(np.asarray(want["i"])[..., 0, 0].sum()) > 0, \
        f"{name}: no match in the block"


# flushes the engine itself ships past the bound: a flat block holds
# (N // 2048 + 2) * 2048 slots, so 2300 events a flush make F = 6144
LONG_N = 2 * 2300
LONG = {
    "threshold3": ("scan",),
    "hybrid": ("scan", "dfa"),
    "le_threshold": ("scan",),
    "count_head": ("scan", "dfa"),
    "count_final": ("scan",),
    "logical_and": ("scan", "dfa"),
}


@pytest.fixture(scope="module")
def long_seq_rows():
    cache = {}

    def get(q):
        if q not in cache:
            cache[q] = pf._run("@app:patternFamily('seq')\n"
                               "@app:devicePatterns('always')\n", q,
                               n=LONG_N, batches=2)[2]
        return cache[q]
    return get


@pytest.mark.parametrize("name,fam", [(n, f) for n, fs in LONG.items()
                                      for f in fs])
def test_long_flush_takes_the_tree_and_equals_the_sequential_kernel(
        name, fam, long_seq_rows):
    q = pf.ELIGIBLE[name][0]
    info: dict = {}
    used, _fams, dev = pf._run(
        f"@app:patternFamily('{fam}')\n@app:devicePatterns('always')\n", q,
        n=LONG_N, batches=2, plan_out=info)
    assert used == fam
    fh = info["explain"]["queries"]["q"]["first_hit"]
    assert fh["dense"] == 0 and fh["tree"] > 0 and fh["pairs_per_call"] == 0 \
        and fh["F"] > npar.DENSE_MAX_F, fh
    assert len(dev) > 0
    assert dev == long_seq_rows(q), (name, fam, len(dev))


@pytest.mark.parametrize("op", ["ge", "le"])
def test_zero_rhs_rows_survive_on_the_tree_side(op):
    """`e2.price >= e1.price` with both 0.0, in a flush past the bound: the
    tree form kept `>` semantics there (the denormal neighbour of 0.0) and
    lost the rows the sequential kernel and the interpreter deliver."""
    q = (f"from every e1=S[volume == 1] -> e2=S[price {'>=' if op == 'ge' else '<='} e1.price] "
         "within 1 sec select e1.price as a, e2.price as b insert into Out;")
    n = LONG_N // 2
    rng = np.random.default_rng(9)
    prices = rng.choice([0.0, 0.0, 2.5, -2.5], n)
    rows = {}
    for head in ("@app:devicePatterns('never')\n",
                 "@app:patternFamily('seq')\n@app:devicePatterns('always')\n",
                 "@app:patternFamily('scan')\n@app:devicePatterns('always')\n"):
        mgr = SiddhiManager()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            rt = mgr.create_app_runtime(head + pf.HEAD + q)
        out = []
        rt.add_callback("Out", lambda evs, out=out: out.extend(
            (e.timestamp, tuple(e.data)) for e in evs))
        rt.start()
        ih = rt.input_handler("S")
        for i in range(n):
            ih.send(("K", float(prices[i]), 1 if i % 5 == 0 else 0),
                    timestamp=1_700_000_000_000 + i * 7)
        rt.flush()
        plan = next((p for p in rt._plans
                     if isinstance(p, DevicePatternPlan)), None)
        if "scan" in head:
            assert plan.family == "scan" and plan.first_hit["tree"] > 0
        mgr.shutdown()
        rows[head] = out
    host, seq, scan = rows.values()
    assert any(a == 0.0 and b == 0.0 for _t, (a, b) in host)
    assert seq == host
    assert scan == host


# ---------------------------------------------------------------------------
# whole plans, the scatter forced and the dense form
# ---------------------------------------------------------------------------

def _partitioned_rows():
    """(the interpreter's rows, the lane grid's, its EXPLAIN entry): 37
    keys through the partitioned scan plan, four flushes."""
    _f, host = pf._run_part("@app:devicePatterns('never')\n")
    info: dict = {}
    fam, dev = pf._run_part("@app:partitionCapacity(64)\n", plan_out=info)
    assert fam == "scan"
    return host, dev, info["explain"]["queries"]["q"]


def _cut_fused_rows():
    """The same of a fused group of 12 rules whose every flush is cut into
    rows (the row constants lowered by the caller: 64-event rows)."""
    batches = fc.tape(21, 400, 4)
    host, _e, _p, _s = fc.run(fc.HOST, fc.app_of(3), batches)
    dev, ex, plans, _s = fc.run("", fc.app_of(3), batches)
    fused = ex["queries"][plans[0].name]["fused"]
    assert fused["lane_cut"]["flushes_cut"] == 4
    inner = plans[0].inner
    assert fused["compaction"]["lanes"] == inner._fused_R * inner.P
    return fc.flat(host), fc.flat(dev), fused


@pytest.mark.parametrize("form", ["dense", "scatter"])
@pytest.mark.parametrize("rows_of", [_partitioned_rows, _cut_fused_rows],
                         ids=["partitioned", "cut_fused_group"])
def test_plans_rows_equal_the_interpreters_in_either_form(
        rows_of, form, monkeypatch):
    """Lanes of a few hundred events compact dense by themselves; the
    scatter is forced by handing the block the `_Compact` of a lane past
    the bound.  Both deliver the interpreter's rows."""
    monkeypatch.setattr(pattern_plan, "FUSED_ROW_WINDOWS", 2)
    monkeypatch.setattr(pattern_plan, "FUSED_ROW_MIN", 16)
    if form == "scatter":
        monkeypatch.setattr(npar, "_Compact", _scatter_form)
    host, dev, ent = rows_of()
    assert dev == host and sum(map(len, dev)) > 100
    assert ent["first_hit"]["tree"] == 0 and ent["indexed_read"]["gather"] == 0
    other = "scatter" if form == "dense" else "dense"
    assert ent["compaction"][form] == 2 and ent["compaction"][other] == 0
