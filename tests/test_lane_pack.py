"""The host pack of the partitioned lane grid (lane_grid.py): the lane
order of a flush comes from one stable radix pass over the lane id, on
rows already in arrival order, and the lane id from a dense key table.

Held here against the plain form it replaced, kept below as the reference
(`RefPack`: np.unique for key -> lane, lexsort((seq,)) for the union,
lexsort((seq, part)) for the lanes): the same input must give the same
permutation, the same lane ids and, flush after flush, byte for byte the
same (Lpad, F) grids, tails, prev seqs and sticky F and L, so the jitted
block and everything it emits are the plain form's.  `lane_pack_order`
(EXPLAIN, device_metrics) says which form each flush took."""
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.batch import EventBatch
from siddhi_tpu.core.nfa_device import LOCAL_SPAN, pow2_at_least
from siddhi_tpu.core.lane_grid import _stable_lane_order
from siddhi_tpu.core.pattern_plan import DevicePatternPlan

_I32 = np.int32


# -- (a) the permutation -----------------------------------------------------

def _tail_and_new(rng, lanes, n_new, n_tail, hot=None, held=False):
    """[tail | new] as LaneGrid.pack hands it over: the tail lane
    by lane with each lane's rows in seq order, every tail seq under every
    new one, the new rows in seq order.  `held`: lanes that sat out the
    flush before ride BEHIND the lanes that did not, as the tail keeps
    them, so the tail as a whole is not in lane order."""
    ids = np.asarray(lanes)
    p = None
    if hot is not None:
        p = np.full(len(ids), (1 - hot) / (len(ids) - 1))
        p[0] = hot
    new_part = rng.choice(ids, n_new, p=p)
    new_seq = 10_000 + np.cumsum(rng.integers(1, 4, n_new))
    tail_part = rng.choice(ids, n_tail)
    tail_seq = rng.integers(0, 9_000, n_tail)
    o = np.lexsort((tail_seq, tail_part))
    if held:
        quiet = np.isin(tail_part[o], ids[::3])
        o = np.r_[o[~quiet], o[quiet]]
    return (np.concatenate([tail_part[o], new_part]).astype(_I32),
            np.concatenate([tail_seq[o], new_seq]).astype(np.int64))


ORDER_CASES = {
    "uniform_keys": dict(lanes=range(1000), n_new=20_000, n_tail=900),
    "one_key_13_percent": dict(lanes=range(1000), n_new=20_000, n_tail=900,
                               hot=0.13),
    "single_lane": dict(lanes=[0], n_new=5_000, n_tail=40),
    "lane_ids_above_65535": dict(
        lanes=np.r_[np.arange(40), 65_530 + np.arange(40),
                    70_000 + np.arange(40) * 5_003],
        n_new=20_000, n_tail=600),
    "empty_tail": dict(lanes=range(300), n_new=8_000, n_tail=0),
    "held_lanes_appended": dict(lanes=range(200), n_new=8_000, n_tail=700,
                                held=True),
}


@pytest.mark.parametrize("case", list(ORDER_CASES))
def test_stable_lane_order_is_the_lexsort_permutation(case):
    part, seq = _tail_and_new(np.random.default_rng(11), **ORDER_CASES[case])
    want = np.lexsort((seq, part))
    got = _stable_lane_order(part)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    if case == "lane_ids_above_65535":
        assert int(part.max()) >= 1 << 16       # the two-pass form ran


def test_stable_lane_order_of_nothing():
    assert _stable_lane_order(np.zeros(0, _I32)).shape == (0,)


# -- the plain reference: the host pack as it stood before ---------------------

class RefPack:
    """`part_of`, `_finalize_chunks` steps 1-2 and `LaneGrid.pack`
    in the plain form: two comparison sorts and an np.unique a flush.  It
    reads the plan's static shape only (stream codes, gridded attributes,
    `within`) and keeps its own key map, tails, prev seqs and the sticky
    grid, F and L."""

    def __init__(self, plan):
        self.plan = plan
        self.k2p: dict = {}
        self.tail = None
        self.prev = np.zeros(0, dtype=np.int64)
        self.F = self.L = 0

    def part_of(self, sid, b):
        keys = self.plan.part_key_fns[sid](b)
        uniq, inv = np.unique(keys, return_inverse=True)
        parts_u = np.empty(len(uniq), dtype=_I32)
        for j, k in enumerate(uniq.tolist()):
            p = self.k2p.get(k)
            if p is None:
                p = self.k2p[k] = len(self.k2p)
            parts_u[j] = p
        return parts_u[inv]

    def flush(self, bufs):
        pl = self.plan
        N = sum(b.n for _s, b in bufs)
        ts = np.empty(N, dtype=np.int64)
        seq = np.empty(N, dtype=np.int64)
        scode = np.empty(N, dtype=_I32)
        part = np.empty(N, dtype=_I32)
        cols = {f"{si}.{attr}": np.zeros(N, dtype=pl._np_dtype(t))
                for si, attr, t in pl._grid_attrs}
        o = 0
        for sid, b in bufs:
            si = pl._scode[sid]
            sl = slice(o, o + b.n)
            ts[sl] = b.timestamps
            seq[sl] = b.seqs if b.seqs is not None \
                else np.arange(o, o + b.n)
            scode[sl] = si
            part[sl] = self.part_of(sid, b)
            for sj, attr, _t in pl._grid_attrs:
                if sj == si:
                    cols[f"{si}.{attr}"][sl] = b.columns[attr]
            o += b.n
        order = np.lexsort((seq,))
        ts, seq, scode, part = ts[order], seq[order], scode[order], part[order]
        cols = {k: v[order] for k, v in cols.items()}
        return self._lanes(ts, seq, scode, cols, part)

    def _lanes(self, ts, seq, scode, cols, part):
        pl = self.plan
        W0 = int(pl._chunk_cfg["W"])
        tl = self.tail
        held = None

        def rows(t, m):
            return {"ts": t["ts"][m], "seq": t["seq"][m],
                    "scode": t["scode"][m], "part": t["part"][m],
                    "cols": {k: v[m] for k, v in t["cols"].items()}}

        if tl is not None:
            active = np.isin(tl["part"], np.unique(part))
            if not active.all():
                held = rows(tl, ~active)
                tl = rows(tl, active)
            ts = np.concatenate([tl["ts"], ts])
            seq = np.concatenate([tl["seq"], seq])
            scode = np.concatenate([tl["scode"], scode])
            part = np.concatenate([tl["part"], part])
            cols = {k: np.concatenate([tl["cols"][k], v])
                    for k, v in cols.items()}
        N = len(ts)
        order = np.lexsort((seq, part))
        ts, seq, scode, part = ts[order], seq[order], scode[order], part[order]
        cols = {k: v[order] for k, v in cols.items()}
        change = np.r_[True, part[1:] != part[:-1]]
        run_id = np.cumsum(change) - 1
        run_start = np.flatnonzero(change)
        lane_ids = part[run_start].astype(np.int64)
        counts = np.diff(np.r_[run_start, N])
        idx_within = np.arange(N) - run_start[run_id]
        Lr = len(lane_ids)
        run_end = run_start + counts - 1
        span = int(ts.max()) - int(ts.min()) + 1
        sh = ts.astype(np.int64) + run_id.astype(np.int64) * span
        tsmono = np.maximum.accumulate(sh) - run_id.astype(np.int64) * span
        W = W0 + int(np.max(tsmono - ts))
        fm = int(counts.max())
        f_min = pow2_at_least(fm, lo=16) if fm <= 64 else (fm // 64 + 2) * 64
        F = max(self.F, f_min)
        if F > 4 * f_min:
            F = f_min
        self.F = F
        # the lane axis: up to a sixteenth of the count's power of two, a
        # granule of at least 8; what is in use stays while it serves and
        # is dropped once it is over four times what the flush needs
        p = 8
        while p < Lr:
            p *= 2
        g = max(8, p // 16)
        l_min = (Lr + g - 1) // g * g
        Lpad = max(self.L, l_min)
        if Lpad > 4 * l_min:
            Lpad = l_min
        self.L = Lpad
        budget = LOCAL_SPAN - (1 << 16)
        ts_base = max(int(ts.min()), int(ts.max()) - budget)
        seq_base = max(int(seq.min()), int(seq.max()) - budget)
        if len(self.prev) < len(self.k2p):
            grown = np.full(len(self.k2p), -(2 ** 62), dtype=np.int64)
            grown[:len(self.prev)] = self.prev
            self.prev = grown

        def grid(a):
            g = np.zeros((Lpad, F), dtype=a.dtype)
            g[run_id, idx_within] = a
            return g

        nev = np.zeros(Lpad, _I32)
        nev[:Lr] = counts
        prev = np.full(Lpad, -LOCAL_SPAN, _I32)
        prev[:Lr] = np.clip(self.prev[lane_ids] - seq_base,
                            -LOCAL_SPAN, LOCAL_SPAN).astype(_I32)
        ev = {"__flat.__ts__": grid(np.clip(
                  ts - ts_base, -LOCAL_SPAN, LOCAL_SPAN).astype(_I32)),
              "__flat.__seq__": grid(np.clip(
                  seq - seq_base, -LOCAL_SPAN, LOCAL_SPAN).astype(_I32)),
              "__nev__": nev, "__prev_seq__": prev,
              "__base_ts__": np.int64(ts_base),
              "__base_seq__": np.int64(seq_base)}
        if len(pl.spec.stream_ids) > 1:
            ev["__flat.__scode__"] = grid(scode)
        for k, v in cols.items():
            ev[f"__flat.{k}"] = grid(v)
        keep = tsmono >= (tsmono[run_end][run_id] - W)
        self.tail = rows({"ts": ts, "seq": seq, "scode": scode, "part": part,
                          "cols": cols}, keep)
        if held is not None:
            self.tail = {
                k: (np.concatenate([self.tail[k], held[k]]) if k != "cols"
                    else {c: np.concatenate([self.tail["cols"][c],
                                             held["cols"][c]])
                          for c in held["cols"]})
                for k in self.tail}
        self.prev[lane_ids] = seq[run_end]
        return ev, F, ts_base, seq_base, Lpad


# -- driving one plan beside the reference ---------------------------------------

ONE = """@app:partitionCapacity(8)
define stream S (sym string, price double, acct long, score double);
partition with ({key} of S)
begin
  @info(name='q')
  from every e1=S[price > 100] -> e2=S[price > e1.price]
      -> e3=S[price > e2.price] within 1 sec
  select e1.price as p1, e2.price as p2, e3.price as p3 insert into Out;
end;
"""
TWO = """@app:partitionCapacity(8)
define stream A (sym string, price double);
define stream B (sym string, price double);
partition with (sym of A, sym of B)
begin
  @info(name='q')
  from every e1=A[price > 100] -> e2=B[price > e1.price] within 1 sec
  select e1.price as p1, e2.price as p2 insert into Out;
end;
"""
T0 = 1_700_000_000_000


class _NoDevice:
    """Stands where the plan's dispatch pipeline stands and drops what is
    pushed: these tests hold the host pack, and a block a flush shape
    would cost a compile each."""

    def __init__(self, pipe):
        self._pipe = pipe

    def push(self, entry):
        return []

    def __len__(self):
        return 0

    def __getattr__(self, name):
        return getattr(self._pipe, name)


class Rig:
    """One runtime, its device pattern plan with the dispatch cut off and
    recorded, and the reference fed the same buffered batches."""

    def __init__(self, app, ref=None):
        self.app = app
        self.mgr = SiddhiManager()
        self.rt = self.mgr.create_app_runtime(app)
        self.rt.start()
        self.plan = next(p for p in self.rt._plans
                         if isinstance(p, DevicePatternPlan))
        assert self.plan.family == "scan" and self.plan._partitioned
        self.ref = ref if ref is not None else RefPack(self.plan)
        self.ref.plan = self.plan
        self.flushes = 0
        self.plan._pipe = _NoDevice(self.plan._pipe)
        self.plan._dispatch_par = self._record
        inner = self.plan._finalize_chunks

        def finalize_beside_the_reference():
            bufs = list(self.plan._buffered)
            self.got = None
            out = inner()
            if bufs:
                self._compare(self.ref.flush(bufs))
            return out
        self.plan._finalize_chunks = finalize_beside_the_reference

    def _record(self, ev, F, M, ts_base, seq_base, lanes=None):
        self.got = (ev, F, ts_base, seq_base, lanes)
        assert M == F
        return {}       # the entry: the pack notes its `lane_fill` on it

    def _compare(self, want):
        assert self.got is not None, "the flush never reached _dispatch_par"
        ev, F, ts_base, seq_base, lanes = self.got
        wev, wF, wtb, wsb, wl = want
        assert (F, ts_base, seq_base, lanes) == (wF, wtb, wsb, wl)
        assert list(ev) == list(wev)
        for k in wev:
            a, b = np.asarray(ev[k]), np.asarray(wev[k])
            assert a.dtype == b.dtype and a.shape == b.shape, k
            assert np.array_equal(a, b), k
        pl, ref = self.plan, self.ref
        assert pl._key_to_part == ref.k2p
        assert list(pl._key_to_part) == list(ref.k2p)
        assert (pl.grid.F, pl.grid.L) == (ref.F, ref.L)
        assert pl.grid.prev.dtype == ref.prev.dtype
        assert np.array_equal(pl.grid.prev, ref.prev)
        for k in ("ts", "seq", "scode", "part"):
            assert pl.grid.tail[k].dtype == ref.tail[k].dtype, k
            assert np.array_equal(pl.grid.tail[k], ref.tail[k]), k
        assert list(pl.grid.tail["cols"]) == list(ref.tail["cols"])
        for k, v in ref.tail["cols"].items():
            assert pl.grid.tail["cols"][k].dtype == v.dtype
            assert np.array_equal(pl.grid.tail["cols"][k], v), k
        self.flushes += 1

    def restored(self):
        """A new runtime restored from this one's snapshot; the reference
        carries on as it is, but for the sticky grid, which no snapshot
        holds: a restored plan sizes its first flush anew."""
        snap = self.rt.snapshot()
        self.mgr.shutdown()
        self.ref.F = self.ref.L = 0
        return Rig(self.app, ref=self.ref)._restore(snap)

    def _restore(self, snap):
        self.rt.restore(snap)
        return self

    def counted(self):
        return dict(self.plan._lane_pack_order)

    def close(self):
        self.mgr.shutdown()


def _send_one(rig, rng, keys, n, t, ooo=False):
    """One send_batch = one flush of one stamped batch."""
    sym = np.array([rig.rt.strings.encode(f"K{k}") for k in keys], _I32)
    pick = rng.integers(0, len(keys), n)
    ts = t + np.arange(n, dtype=np.int64) * 7
    if ooo:                 # timestamps that regress inside a lane
        ts = ts - rng.integers(0, 90, n) * (rng.random(n) < 0.3)
    rig.rt.input_handler("S").send_batch(
        {"sym": sym[pick],
         "price": 90 + 0.25 * rng.integers(0, 161, n).astype(np.float64),
         "acct": (10 ** 12 + np.asarray(keys)[pick] * 10 ** 7
                  ).astype(np.int64),
         "score": np.asarray(keys)[pick] * 0.5}, ts)
    return t + n * 7


def _flows(name):
    """(rig, counted) after the flow `name`; every flush inside it has been
    held against the reference by the rig."""
    rng = np.random.default_rng(5)
    if name == "two_streams_interleaved_seq":
        rig = Rig(TWO)
        ha, hb = rig.rt.input_handler("A"), rig.rt.input_handler("B")
        t = T0
        for _flush in range(4):
            for _i in range(60):    # alternate: each stream's batch holds
                h = ha if rng.random() < 0.5 else hb    # every other seq
                h.send((f"K{rng.integers(0, 6)}",
                        90 + 0.25 * float(rng.integers(0, 161))),
                       timestamp=t)
                t += 5
            rig.rt.flush()
        return rig
    rig = Rig(ONE.format(key="sym"))
    t = T0
    if name == "keys_hot_added_across_the_lane_bucket":
        for keys in (range(5), range(7), range(3, 12), range(20), range(9)):
            t = _send_one(rig, rng, list(keys), 240, t)
    elif name == "quiet_lanes_held":
        for keys in (range(8), [0, 1], [2, 3], range(8), [7]):
            t = _send_one(rig, rng, list(keys), 160, t)
    elif name == "timestamps_out_of_order":
        for _ in range(4):
            t = _send_one(rig, rng, list(range(6)), 200, t, ooo=True)
    elif name == "snapshot_restore_between_flushes":
        for _ in range(2):
            t = _send_one(rig, rng, list(range(9)), 200, t)
        before = rig.flushes
        rig = rig.restored()
        assert rig.plan._key_table is None and rig.plan._key_to_part
        rig.flushes = before
        for keys in (range(4, 9), range(12)):
            t = _send_one(rig, rng, list(keys), 200, t)
    elif name == "unstamped_batches_restart_their_seq":
        # straight into the plan, as a caller without the runtime's
        # stamps would: seqs restart at 0 every flush, so a replayed tail
        # is NOT below the new rows and the radix order would be wrong
        schema = rig.rt.schemas["S"]
        for _ in range(3):
            n = 120
            keys = rng.integers(0, 5, n)
            rig.plan.process("S", EventBatch(
                schema, t + np.arange(n, dtype=np.int64) * 7,
                {"sym": (keys + 1).astype(_I32),
                 "price": 90 + 0.25 * rng.integers(0, 161, n).astype(float),
                 "acct": keys.astype(np.int64), "score": keys * 0.5}, n))
            rig.plan.finalize()
            t += n * 7
    else:
        raise KeyError(name)
    return rig


# name -> (flushes, lane_pack_order after them)
FLOWS = {
    "keys_hot_added_across_the_lane_bucket":
        (5, dict(radix=5, lexsort=0, key_table=5, key_unique=0,
                 seq_sort_skipped=5)),
    "quiet_lanes_held":
        (5, dict(radix=5, lexsort=0, key_table=5, key_unique=0,
                 seq_sort_skipped=5)),
    "timestamps_out_of_order":
        (4, dict(radix=4, lexsort=0, key_table=4, key_unique=0,
                 seq_sort_skipped=4)),
    # both streams' batches hold every other seq: the union is sorted
    "two_streams_interleaved_seq":
        (4, dict(radix=4, lexsort=0, key_table=4, key_unique=0,
                 seq_sort_skipped=0)),
    # the new runtime counts its own two flushes
    "snapshot_restore_between_flushes":
        (4, dict(radix=2, lexsort=0, key_table=2, key_unique=0,
                 seq_sort_skipped=2)),
    # flush 1 has no tail to collide with; 2 and 3 observe the collision
    "unstamped_batches_restart_their_seq":
        (3, dict(radix=1, lexsort=2, key_table=3, key_unique=0,
                 seq_sort_skipped=3)),
}


@pytest.mark.parametrize("flow", list(FLOWS))
def test_every_flush_packs_what_the_plain_reference_packs(flow):
    """(c): ev, the grid's tail, prev, F and L and the lane ids,
    flush after flush, are the plain lexsort + unique implementation's."""
    rig = _flows(flow)
    try:
        assert rig.flushes == FLOWS[flow][0]
    finally:
        rig.close()


@pytest.mark.parametrize("flow", list(FLOWS))
def test_lane_pack_order_counts_the_path_each_flush_took(flow):
    """(d): the counters name the form every flush took, and the form
    follows from the flush's own columns."""
    rig = _flows(flow)
    try:
        assert rig.counted() == FLOWS[flow][1]
        assert rig.plan.lane_pack_order == FLOWS[flow][1]
        assert rig.plan.device_metrics()["lane_pack_order"] == FLOWS[flow][1]
        ent = rig.rt.explain()["queries"]["q"]
        assert ent["lane_pack_order"] == FLOWS[flow][1]
    finally:
        rig.close()


# what `state_dict()` of a partitioned stateless plan has held since its
# lane state was the plan's own fields (before lane_grid.py), in order
SNAPSHOT_KEYS = ["state", "key_to_part", "ts_base", "seq_base",
                 "next_deadline", "last_seq", "start_anchor", "chunk_tail",
                 "chunk_prev_last_seq", "chunk_of_dropped", "lane_tail",
                 "lane_prev", "arm_done"]


def test_a_snapshot_keeps_the_keys_and_shapes_written_before_the_grid():
    """The `snapshot_restore_between_flushes` scenario, on the snapshot
    itself: the grid's share (`LaneGrid.state`) goes under the keys and in
    the shapes `state_dict` always wrote, so a snapshot from before the
    grid was a module restores; and a plan restored from exactly those
    packs what the reference packs."""
    rng = np.random.default_rng(5)
    rig = Rig(ONE.format(key="sym"))
    try:
        t = T0
        for _ in range(2):
            t = _send_one(rig, rng, list(range(9)), 200, t)
        d = rig.plan.state_dict()
        assert list(d) == SNAPSHOT_KEYS
        tail = d["lane_tail"]
        assert list(tail) == ["ts", "seq", "scode", "part", "cols"]
        assert [tail[k].dtype for k in ("ts", "seq", "scode", "part")] \
            == [np.int64, np.int64, _I32, _I32]
        n = len(tail["ts"])
        assert n > 0 and list(tail["cols"]) == ["0.price"]
        assert all(v.shape == (n,) for k, v in tail.items() if k != "cols")
        assert tail["cols"]["0.price"].shape == (n,)
        assert d["lane_prev"].dtype == np.int64 \
            and d["lane_prev"].shape == (len(d["key_to_part"]),) == (9,)
        assert d["chunk_tail"] is None and d["arm_done"] is None
        assert d["chunk_prev_last_seq"] == -1
        before = rig.flushes
        rig = rig.restored()
        assert (rig.plan.grid.F, rig.plan.grid.L) == (0, 0)
        assert np.array_equal(rig.plan.grid.tail["seq"], tail["seq"])
        assert np.array_equal(rig.plan.grid.prev, d["lane_prev"])
        for keys in (range(4, 9), range(12)):
            t = _send_one(rig, rng, list(keys), 200, t)
        assert rig.flushes == 2 and before == 2
    finally:
        rig.close()


# -- (b) key -> lane ---------------------------------------------------------------

def _sorted_walk(k2p, keys):
    """Lane ids as the np.unique walk assigns them: new keys numbered in
    sorted order, flush by flush."""
    for k in sorted(set(keys.tolist())):
        k2p.setdefault(k, len(k2p))
    return np.array([k2p[k] for k in keys.tolist()], _I32)


# key attribute -> (column of a flush from its lane draw, table or unique)
KEY_COLUMNS = {
    "sym": (lambda d: (d + 1).astype(_I32), True),      # dictionary codes
    "acct_small_range": (lambda d: (d * 3 - 40).astype(np.int64), True),
    "acct_far_from_zero": (lambda d: (10 ** 12 + d).astype(np.int64), True),
    "acct_wide_int": (lambda d: (d * 10 ** 9).astype(np.int64), False),
    "score_float": (lambda d: d * 0.5, False),
}


@pytest.mark.parametrize("col", list(KEY_COLUMNS))
def test_key_table_assigns_the_lanes_the_sorted_unique_walk_assigns(col):
    attr = col.split("_")[0]
    make, by_table = KEY_COLUMNS[col]
    rig = Rig(ONE.format(key=attr))
    try:
        plan, schema = rig.plan, rig.rt.schemas["S"]
        rng = np.random.default_rng(9)
        want_k2p: dict = {}
        # distinct keys 5 -> 40, across the 8-lane bucket and two more;
        # the range grows at both ends, so a table off zero moves its base
        for lo, hi in ((20, 25), (12, 29), (0, 17), (0, 40), (5, 12)):
            d = rng.integers(lo, hi, 300)
            c = {"sym": np.ones(300, _I32), "price": np.zeros(300),
                 "acct": np.zeros(300, np.int64), "score": np.zeros(300)}
            c[attr] = make(d)
            got, tabled = plan.part_of(
                "S", EventBatch(schema, np.zeros(300, np.int64), c, 300))
            assert tabled == by_table
            assert got.dtype == _I32
            assert np.array_equal(got, _sorted_walk(want_k2p, c[attr]))
        assert plan._key_to_part == want_k2p
        assert list(plan._key_to_part) == list(want_k2p)
        assert (plan._key_table is not None) == by_table
        if by_table:
            # the table is a cache of the dict, entry for entry
            base, tab = plan._key_base, plan._key_table
            hit = np.flatnonzero(tab >= 0)
            assert {int(i) + base: int(tab[i]) for i in hit} == want_k2p
            assert len(tab) <= plan.KEY_TABLE_MAX
    finally:
        rig.close()


def test_a_flush_with_one_batch_off_the_table_counts_as_key_unique():
    """A flush is `key_table` only if every buffered batch took the table."""
    rig = Rig(ONE.format(key="acct"))
    try:
        schema, n = rig.rt.schemas["S"], 50
        for k, acct in enumerate((np.arange(n), np.arange(n) * 10 ** 10)):
            rig.plan.process("S", EventBatch(
                schema, T0 + k * n + np.arange(n, dtype=np.int64),
                {"sym": np.ones(n, _I32), "price": np.full(n, 101.0),
                 "acct": acct.astype(np.int64), "score": np.zeros(n)}, n,
                seqs=k * n + np.arange(n, dtype=np.int64)))
        rig.plan.finalize()
        assert rig.flushes == 1
        assert rig.counted() == dict(radix=1, lexsort=0, key_table=0,
                                     key_unique=1, seq_sort_skipped=1)
    finally:
        rig.close()


# -- end to end: the real block behind the new pack ----------------------------------

def test_matches_and_explain_through_the_real_block():
    """The whole path, device block included, against the host clones; and
    `lane_pack_order` beside `first_hit` in rt.explain()."""
    def run(head):
        mgr = SiddhiManager()
        rt = mgr.create_app_runtime(head + ONE.format(key="sym"))
        rows = []
        rt.add_callback("Out", lambda evs: rows.extend(
            (e.timestamp, tuple(e.data)) for e in evs))
        rt.start()
        rng = np.random.default_rng(3)
        sym = np.array([rt.strings.encode(f"K{k}") for k in range(6)], _I32)
        t = T0
        for _ in range(3):
            n = 150
            pick = rng.integers(0, 6, n)
            rt.input_handler("S").send_batch(
                {"sym": sym[pick],
                 "price": 90 + 0.25 * rng.integers(0, 161, n).astype(float),
                 "acct": pick.astype(np.int64), "score": pick * 0.5},
                t + np.arange(n, dtype=np.int64) * 7)
            t += n * 7
        rt.flush()
        ent = rt.explain()["queries"].get("q")
        mgr.shutdown()
        return sorted(rows), ent
    host, _ent = run("@app:devicePatterns('never')\n")
    dev, ent = run("")
    assert dev == host and len(dev) > 20
    assert list(ent)[:7] == ["path", "plan", "kind", "family",
                             "expiry_queries", "first_hit",
                             "lane_pack_order"], list(ent)
    assert ent["lane_pack_order"] == dict(
        radix=3, lexsort=0, key_table=3, key_unique=0, seq_sort_skipped=3)
