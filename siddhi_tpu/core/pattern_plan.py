"""Device pattern/sequence query plan — host wrapper around NFAKernel.

Buffers per-stream micro-batches, merges them by global arrival seq,
buckets events into dense (T, P) blocks (one event per partition per scan
step), runs the jitted batched-NFA block, and compacts emitted matches
back into an output EventBatch.

The partition axis is 1 for plain pattern queries; partitioned queries
(`partition with (key of Stream) begin ... end`) set a key extractor and
a partition capacity so thousands of per-key NFA instances run as one
kernel (reference clones the whole query graph per key instead:
core:partition/PartitionRuntime.java:257-306).

Timestamps and seqs are shipped to the device as i32 offsets from
per-plan bases (TPU x64 is emulated; see nfa_device.py); the plan
rebases the persistent slot state host-side before offsets can overflow.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import numpy as np

from ..query import ast
from .batch import EventBatch
from .expr import ExprError, MultiStreamContext, compile_expression
from .nfa_device import (ChainSpec, DeviceNFAUnsupported, LOCAL_SPAN,
                         NFAKernel, join64_np, lower_chain, pow2_at_least)
from .nfa_parallel import DENSE_MAX_F
from .planner import (AGGREGATOR_NAMES, OutputBatch, PlanError, QueryPlan,
                      selector_has_aggregators)
from .schema import StreamSchema, TIMESTAMP_DTYPE, dtype_of
from .telemetry import call_kernel, env_nbytes

_I32 = np.int32


def _m_bucket(n: int) -> int:
    """Match-buffer capacity bucket: pow2 up to 16K, then 16K multiples —
    every pulled byte costs, so over-allocating 2x at large n (pow2)
    wastes real time; finer buckets cost a rare recompile.  (Granules
    chosen on a remote-attached chip; not re-measured on a local one —
    ROADMAP "found at bring-up".)"""
    if n <= 16384:
        return pow2_at_least(n, lo=16)
    return -(-n // 16384) * 16384


def _m_bucket_chunk(n: int) -> int:
    """Chunked-flat blocks are costly to compile: coarse
    64K buckets keep M stable flush-to-flush (a 16K-granular bucket
    recompiled whenever the match count drifted past the last bucket)."""
    if n <= 16384:
        return pow2_at_least(n, lo=16)
    return -(-n // 65536) * 65536


def _stable_lane_order(part: np.ndarray) -> np.ndarray:
    """The permutation `np.lexsort((seq, part))` returns, for rows that are
    ALREADY in seq order inside every lane (the caller's invariant): a
    stable sort by the lane id alone keeps each lane's rows as they stand.
    A lane id is a small non-negative integer, so the sort is numpy's
    radix pass over 16-bit keys (what `kind="stable"` picks for them):
    one pass while every id is under 2^16, the low half then the high half
    (LSD, each pass stable) above that."""
    if part.size == 0 or int(part.max()) < 1 << 16:
        return np.argsort(part.astype(np.uint16), kind="stable")
    by_low = np.argsort((part & 0xFFFF).astype(np.uint16), kind="stable")
    high = (part >> 16).astype(np.uint16)
    return by_low[np.argsort(high[by_low], kind="stable")]


def _rises_in_lanes(a_l: np.ndarray, run_start: np.ndarray) -> bool:
    """Whether lane-ordered `a_l` is non-decreasing inside every lane
    (`run_start`: the row each lane's run begins at)."""
    rises = a_l[1:] >= a_l[:-1]
    rises[run_start[1:] - 1] = True         # a lane's first row
    return bool(rises.all())


def _tail_rows(t: dict, rows) -> dict:
    """The rows `rows` (a mask or an index) of a lane tail, or of the
    flush's columns laid out like one."""
    return {"ts": t["ts"][rows], "seq": t["seq"][rows],
            "scode": t["scode"][rows], "part": t["part"][rows],
            "cols": {k: v[rows] for k, v in t["cols"].items()}}


def _offsets32(a: np.ndarray, base: int, lo: int) -> np.ndarray:
    """`a - base` as the i32 offsets the device reads, saturating at
    +-LOCAL_SPAN.  `lo` is a's minimum; a base is never more than
    LOCAL_SPAN under a's maximum, so only the low side can need the clip."""
    off = a - base
    if lo - base < -LOCAL_SPAN:
        np.clip(off, -LOCAL_SPAN, LOCAL_SPAN, out=off)
    return off.astype(_I32)


# The most events one row of the partitioned lane grid holds when a flush
# is cut: a lane longer than this is laid out as several rows, each a
# flush boundary its key never saw (_cut_rows).  Every lane pads to the
# longest row and the block's first-hit queries stay dense only up to
# DENSE_MAX_F events a row, so the cut is at most that.  Half of it, by two
# readings of pattern1k-zipf.sat on the chip (one seed, 30 s; PERF.md
# section 6, PR 35): at DENSE_MAX_F // 2 = 2048 a batch is 1,088 rows, a
# 2048 x 2048 grid, 412,970 events/s; at DENSE_MAX_F = 4096 it is 1,025
# rows, astride the lane axis' power of two, a 2048 x 4096 grid, 178,713
# events/s.  A shorter row serves fewer new events behind its replayed
# window (the top key's 1,294 of 2048), but the grid is rows x cut cells
# and the dense queries cost cut^2 a row.
LANE_CUT = DENSE_MAX_F // 2


def _cut_rows(counts, run_start, tail_n, tsmono, W: int) -> Optional[tuple]:
    """The grid rows of a flush in which some lane holds more than LANE_CUT
    events, as (lane run of each row, its first lane-ordered event, its
    length, its first NEW event), rows of one lane consecutive and in
    order; None when some lane's replay window leaves under a quarter of
    a row for new events: that lane is hotter than the cut can serve (the
    rows it needs grow as LANE_CUT over the room left).

    A lane run is [replayed tail | new events] in arrival order
    (`tail_n` of them replayed).  Its first row starts where the run
    starts; every next row starts with the events whose running-max
    timestamp is within W of the last event BEFORE its first new one:
    what the lane's tail would hold had a flush ended there, so the row
    is what that lane's next flush would have been."""
    cut = np.flatnonzero(counts > LANE_CUT)
    room = LANE_CUT // 4
    per_lane = []
    for r in cut.tolist():
        a, c = int(run_start[r]), int(counts[r])
        mono = tsmono[a:a + c]
        first, p, s = [], 0, int(tail_n[r])
        while True:
            if LANE_CUT - (s - p) < room:
                return None
            e = min(c, p + LANE_CUT)
            first.append((a + p, e - p, a + s))
            if e == c:
                break
            s = e
            p = int(np.searchsorted(mono, mono[s - 1] - W, side="left"))
        per_lane.append(first)
    n_rows = np.ones(len(counts), dtype=np.int64)
    n_rows[cut] = [len(f) for f in per_lane]
    row_run = np.repeat(np.arange(len(counts)), n_rows)
    row_at, row_n = run_start[row_run], counts[row_run]
    row_new = row_at + tail_n[row_run]
    at = np.cumsum(n_rows) - n_rows
    for r, first in zip(cut.tolist(), per_lane):
        sl = slice(int(at[r]), int(at[r]) + len(first))
        row_at[sl], row_n[sl], row_new[sl] = np.array(first).T
    return row_run, row_at, row_n, row_new


class DevicePatternPlan(QueryPlan):
    """from [every] e1=A[...] -> e2=B[...] within T — batched device NFA."""

    A_CAP = 512      # default adaptive slot-growth ceiling (@app:deviceSlotCap)

    def __init__(self, name: str, rt, q: ast.Query, state_input,
                 target: Optional[str], partitions: int = 1,
                 part_key_fns: Optional[dict] = None, slots: int = 16,
                 param_extra: Optional[dict] = None,
                 broadcast_events: bool = False,
                 params: Optional[dict] = None):
        from ..interp.engine import _collect_filters
        self.param_extra = param_extra
        self.broadcast_events = broadcast_events

        self.name = name
        self.rt = rt
        cap = ast.find_annotation(rt.app.annotations, "app:deviceSlotCap")
        if cap is not None:
            self.A_CAP = int(cap.element())
        prec = ast.find_annotation(rt.app.annotations, "app:devicePrecision")
        self.f64 = prec is not None and str(prec.element()).lower() == "f64"
        self.output_target = target
        self.events_for = getattr(q.output, "events_for",
                                  ast.OutputEventsFor.CURRENT)
        if q.rate is not None:
            raise DeviceNFAUnsupported("output rate limiting")
        if q.selector.group_by or q.selector.order_by \
                or selector_has_aggregators(q.selector):
            raise DeviceNFAUnsupported("group-by/order-by/aggregating selector")
        self.limit, self.offset = q.selector.limit, q.selector.offset

        self.spec: ChainSpec = lower_chain(
            state_input, rt.schemas, rt.strings,
            _collect_filters(state_input.state), param_extra=param_extra)
        self.input_streams = tuple(self.spec.stream_ids)

        # partitioning: key fn per input stream (row cols -> np int codes)
        self.P = partitions
        self.part_key_fns = part_key_fns        # stream_id -> fn(batch)->codes
        self._key_to_part: dict = {}            # key value -> partition index
        # dense cache of _key_to_part for integer key columns of narrow
        # range: _key_table[key - _key_base] is the lane, -1 = not cached
        self._key_table: Optional[np.ndarray] = None
        self._key_base = 0
        # flushes by the way their lane order was found (EXPLAIN /
        # device_metrics `lane_pack_order`)
        self._lane_pack_order = {"radix": 0, "lexsort": 0, "key_table": 0,
                                 "key_unique": 0, "seq_sort_skipped": 0}
        # what cutting long lanes into grid rows did so far (EXPLAIN /
        # device_metrics `lane_cut`)
        self._lane_cut = {"flushes_cut": 0, "lanes_cut": 0, "rows_added": 0,
                          "events_replayed": 0, "flushes_uncuttable": 0}

        # multi-chip mesh: shard the partition axis (last axis of every
        # state leaf / event grid) over jax.devices() — the production
        # analog of the reference's per-key clone fan-out scaled across
        # chips (SURVEY §2.3 item 2: our DP ≅ their partitions)
        self.mesh = None
        mode = getattr(rt, "device_mesh", "auto")
        ndev = len(jax.devices())
        if mode == "always" or (mode == "auto" and ndev > 1
                                and partitions >= ndev):
            from jax.sharding import Mesh
            self.mesh = Mesh(np.array(jax.devices()), ("part",))
            self.P = -(-self.P // ndev) * ndev     # even shards

        # selector over capture refs
        sel = q.selector
        sctx = MultiStreamContext(self.spec.schemas, rt.strings,
                                  extra=dict(param_extra or {}))
        names, types, fns = [], [], []
        if sel.select_all:
            seen = set()
            for nd in self.spec.all_nodes:
                for a in self.spec.schemas[nd.ref].attributes:
                    nm = a.name if a.name not in seen else f"{nd.ref}_{a.name}"
                    seen.add(nm)
                    ce = compile_expression(
                        ast.Variable(a.name, stream_ref=nd.ref), sctx)
                    names.append(nm)
                    types.append(ce.type)
                    fns.append(ce)
        else:
            for oa in sel.attributes:
                try:
                    ce = compile_expression(oa.expr, sctx)
                except ExprError as e:
                    raise DeviceNFAUnsupported(f"selector: {e}")
                names.append(oa.name)
                types.append(ce.type)
                fns.append(ce)
        self._names, self._types = names, types
        having = None
        if sel.having is not None:
            import copy
            hctx = copy.copy(sctx)
            hctx.extra = {n: (n, t) for n, t in zip(names, types)}
            try:
                having = compile_expression(sel.having, hctx)
            except ExprError as e:
                raise DeviceNFAUnsupported(f"having: {e}")
        self.out_schema = StreamSchema(target or f"#{name}", tuple(
            ast.Attribute(n, t) for n, t in zip(names, types)))

        if params:
            # pad per-lane parameter vectors to the (possibly mesh-rounded)
            # lane count; padding lanes never match (they get zero params,
            # and the host routes by qid < n_queries anyway)
            params = {k: (np.concatenate([v, np.zeros(self.P - len(v),
                                                      v.dtype)])
                          if len(v) < self.P else v)
                      for k, v in params.items()}
        # unpartitioned chains also arm their pre-registered START slot
        # on a timer tick (the host matcher starts at plan start);
        # partitioned lanes arm on their key's first event only
        self._init_on_tick = part_key_fns is None
        self.kernel = NFAKernel(self.spec, dict(zip(names, fns)), having,
                                self.P, slots, f64=self.f64,
                                playback=rt._playback, params=params,
                                emit_qid=broadcast_events,
                                init_on_tick=self._init_on_tick)
        self.state = self._shard(self.kernel.init_state())
        self._start_anchor: Optional[int] = None   # init-slot arm time
        self._ts_base: Optional[int] = None
        self._seq_base: Optional[int] = None
        self._m_hint = 16           # last match-buffer capacity that sufficed
        self._of_slots_seen = 0     # accepted (at-cap) overflow totals
        self._next_deadline: Optional[int] = None   # absent-state wakeup
        self._last_seq = 0
        self._buffered: list = []   # (stream_id, EventBatch)
        self._scode = {sid: i for i, sid in enumerate(self.spec.stream_ids)}

        # ---- plan-family selection (docs/PERFORMANCE.md "Plan families").
        # A within-bounded every-head pattern with no partition key can run
        # STATELESS: every pending instance dies within W of its head, so
        # blocks replay the last W of events at the next flush and drop
        # completions at or before the previous flush's last seq.  Three
        # stateless execution families share that harness:
        #   chunk — split each flush into K own-chunks scanned by K
        #           parallel lanes with halo reads (sequential-in-T per
        #           lane; `__can_start__` keeps matches exactly-once);
        #   scan  — associative-scan SFA lowering (nfa_parallel.py):
        #           whole-flush next-pointer composition, O(log T) depth;
        #   dfa   — bit-packed multi-stride hybrid lowering: u32 symbol
        #           words + stride-4 precomposed block tables.
        # Eligibility analysis picks the first sound family of
        # FAMILY_ORDER; the sequential kernel ("seq") is the universal
        # fallback, and @app:patternFamily asks for one by name.  The
        # family is entered here, at build, and never changes after.
        self._chunk_cfg = None
        self._tail: Optional[dict] = None       # replayed raw events
        self._prev_last_seq = -1
        self._chunk_A = slots
        self._chunk_E: Optional[int] = None
        self._kern_by_p: dict = {}
        self._par_kerns: dict = {}              # family -> kernel
        self._of_dropped = 0
        self._family_dispatches: dict = {}
        self._lane_dispatches = 0               # lane-vmapped block count
        self._lanes_last = 0                    # lane width of the last one
        # partitioned/fused lane bookkeeping (scan/dfa lane-vmap path):
        # per-key replay tails + per-key last-emitted completion seq, and
        # the per-lane single-arm resolution flags for non-`every` heads
        self._lane_tail: Optional[dict] = None
        self._lane_prev = np.zeros(0, dtype=np.int64)
        self._lane_F = 0
        self._arm_done: Optional[np.ndarray] = None
        self.family = "seq"
        self._partitioned = part_key_fns is not None or \
            (partitions != 1 and not broadcast_events)
        # hard gates: no stateless family can run these shapes — blocks
        # would need device state or a deterministic flush order
        hard = None
        if getattr(rt, "_async_workers", 1) != 1:
            hard = "async ingest workers (flush order not deterministic)"
        elif self.kernel.has_absent or self.spec.needs_init_slot:
            hard = "absent state (timer-driven deadlines need device state)"
        elif not all(p.within_ms is not None for p in self.spec.positions):
            hard = "position without a `within` bound"
        self.families: dict = {"seq": True}
        self._stateless_lanes = rt.geometry["chunk_lanes"][0]
        if hard is not None:
            self.families.update({"chunk": hard, "scan": hard, "dfa": hard})
        else:
            from .nfa_parallel import classify_parallel
            par = classify_parallel(self.spec, self.kernel, rt.strings,
                                    param_extra)
            if self._partitioned:
                # per-key lanes ride ONE vmap of the flat scan/dfa block
                # ((L, F) grids, per-lane tails/dedup); chunk's lane axis
                # is already spent on own-chunks, and a non-`every` arm
                # would need per-key persistent state
                if not self.spec.every_head:
                    par = {f: ("non-`every` head with partitioned lanes "
                               "(per-key single-arm state)")
                           if v is True else v for f, v in par.items()}
                self.families["chunk"] = ("partitioned (the lane axis "
                                          "holds partition keys)")
            elif broadcast_events:
                # fused multi-query lanes vmap the same way: per-lane
                # `__qparam` constants, events broadcast
                self.families["chunk"] = "fused multi-query lane kernel"
            elif not self.spec.every_head:
                self.families["chunk"] = ("non-`every` head (single "
                                          "stateful arm)")
            else:
                self.families["chunk"] = True if self._stateless_lanes > 1 \
                    else "chunk lanes <= 1 (@app:deviceChunkLanes)"
            if self.mesh is not None and not self._partitioned \
                    and not broadcast_events:
                # partitioned/fused lane grids shard their LANE axis over
                # the mesh (_dispatch_par); only the flat P=1 block has
                # no axis to shard
                for f in ("scan", "dfa"):
                    if par.get(f) is True:
                        par[f] = ("multi-device mesh (flat block has no "
                                  "lane axis to shard)")
            self.families.update(par)
        fam = self._choose_family(rt.geometry["plan_family"][0])
        if fam != "seq":
            # fused groups route matches through finalize_multi, which
            # drains synchronously — no deferred-pull pipeline there
            self.pipeline_depth = 0 if broadcast_events \
                else rt.geometry["pipeline_depth"][0]
            self._enter_stateless(fam)
        # device grids shipped per block: only attrs some predicate or
        # capture row reads, per scode
        self._grid_attrs: list = sorted(self._needed_grid_attrs())

        # build-time validation: trace a tiny block so unsupported env keys
        # fail here (-> sequential fallback) instead of at first flush
        dummy = self._dense_dummy(T=2)
        jax.eval_shape(self.kernel.block_fn(2, 8), self.state, dummy)
        lane_mode = self._partitioned or self.broadcast_events
        while self.family in ("scan", "dfa"):
            # same guarantee for the parallel-in-time families: a lowering
            # surprise demotes to the NEXT sound family at build (each
            # candidate validated in turn), never at first flush
            try:
                jax.eval_shape(
                    self._parallel_kernel().block_fn(
                        (2, 8) if lane_mode else 8, 16),
                    {}, self._flat_dummy(8, L=2 if lane_mode else None))
                break
            except Exception as e:   # pragma: no cover - safety net
                import warnings
                self.families[self.family] = \
                    f"build validation failed: {e}"
                self._par_kerns.pop(self.family, None)
                pl = getattr(rt, "placement", None)
                if pl is not None:
                    pl.demote(name, "D-FAMILY",
                              f"plan family {self.family!r} failed build "
                              f"validation", cause=e,
                              alternative=self.family)
                fam = self._choose_family(None)
                warnings.warn(
                    f"pattern {name!r}: plan family {self.family!r} failed "
                    f"build validation ({e}); demoting to {fam!r}",
                    RuntimeWarning, stacklevel=2)
                if fam == "seq":
                    self.family = "seq"
                    self._chunk_cfg = None
                    self._pipe = None
                    self.retryable_finalize = False
                else:
                    self.family = fam

    # -- helpers -------------------------------------------------------------

    def _needed_grid_attrs(self) -> set:
        """(scode, attr, AttrType) triples whose (T, P) grids the kernel
        reads (predicate inputs + capture writes)."""
        from .nfa_device import _base_ref
        keys: set = set()
        for nd in self.spec.all_nodes:
            for ce in nd.pre_conjs + nd.step_conjs:
                keys.update(k for k in ce.reads if "." in k)
        keys.update(k for k in self.kernel._row_of if not k.startswith("__"))
        ref_scode = {nd.ref: nd.scode for nd in self.spec.all_nodes}
        ref_schema = self.spec.schemas
        out = set()
        for k in keys:
            refpart, attr = k.split(".", 1)
            ref, _idx = _base_ref(refpart)
            if ref in ref_scode and attr in ref_schema[ref].types:
                out.add((ref_scode[ref], attr, ref_schema[ref].type_of(attr)))
        return out

    def _part_sharding(self, ndim: int):
        from jax.sharding import NamedSharding, PartitionSpec
        if ndim == 0:
            return NamedSharding(self.mesh, PartitionSpec())
        return NamedSharding(self.mesh,
                             PartitionSpec(*((None,) * (ndim - 1) + ("part",))))

    def _lane_sharding(self, ndim: int):
        """Lane-MAJOR sharding for the vmapped scan/dfa grids: axis 0 is
        the lane axis (partition keys / fused queries), everything else
        replicates."""
        from jax.sharding import NamedSharding, PartitionSpec
        if ndim == 0:
            return NamedSharding(self.mesh, PartitionSpec())
        return NamedSharding(self.mesh,
                             PartitionSpec(*(("part",) + (None,) * (ndim - 1))))

    def _shard(self, tree):
        """Place every leaf with its partition-axis sharding (no-op when
        no mesh is configured).  Leaves whose last dim is not the lane
        axis — e.g. (T, 1) broadcast event grids — replicate."""
        if self.mesh is None:
            return tree

        def put(a):
            nd = np.ndim(a)
            if nd and np.shape(a)[-1] == self.P:
                return jax.device_put(a, self._part_sharding(nd))
            return jax.device_put(a, self._part_sharding(0))
        return jax.tree_util.tree_map(put, tree)

    def _np_dtype(self, t: ast.AttrType):
        if not self.f64 and t == ast.AttrType.DOUBLE:
            return np.float32
        return dtype_of(t)

    def _flat_dummy(self, F: int, L: Optional[int] = None) -> dict:
        """Tiny flat-block ev (the scan/dfa families' input layout) for
        build-time shape validation.  L adds the lane axis: partitioned
        grids carry per-lane event arrays; fused (broadcast) lanes share
        the event arrays and vary only params/qids/arm flags."""
        import jax.numpy as jnp
        per_lane_ev = L is not None and not self.broadcast_events
        fs = (L, F) if per_lane_ev else (F,)
        ss = (L,) if per_lane_ev else ()
        ls = (L,) if L is not None else ()
        ev = {"__flat.__ts__": jnp.zeros(fs, jnp.int32),
              "__flat.__seq__": jnp.zeros(fs, jnp.int32),
              "__nev__": jnp.zeros(ss, jnp.int32),
              "__prev_seq__": jnp.zeros(ss, jnp.int32),
              "__base_ts__": jnp.zeros((), jnp.int64),
              "__base_seq__": jnp.zeros((), jnp.int64)}
        if len(self.spec.stream_ids) > 1:
            ev["__flat.__scode__"] = jnp.zeros(fs, jnp.int32)
        for si, attr, t in self._grid_attrs:
            ev[f"__flat.{si}.{attr}"] = jnp.zeros(fs, self._np_dtype(t))
        for k, v in (self.kernel.params or {}).items():
            ev[f"__param.{k}"] = jnp.zeros(ls, np.asarray(v).dtype)
        if self.kernel.emit_qid:
            ev["__lane_qid__"] = jnp.zeros(ls, jnp.int32)
        if not self.spec.every_head:
            ev["__arm_done__"] = jnp.zeros(ls, jnp.int32)
        return ev

    def _dense_dummy(self, T: int) -> dict:
        import jax.numpy as jnp
        P = 1 if self.broadcast_events else self.P
        ev = {"__ts__": jnp.zeros((T, P), dtype=jnp.int32),
              "__seq__": jnp.zeros((T, P), dtype=jnp.int32),
              "__valid__": jnp.zeros((T, P), dtype=bool),
              "__base_ts__": jnp.zeros((), dtype=jnp.int64),
              "__base_seq__": jnp.zeros((), dtype=jnp.int64)}
        if len(self.spec.stream_ids) > 1:
            ev["__scode__"] = jnp.zeros((T, P), dtype=jnp.int32)
        for si, attr, t in self._grid_attrs:
            ev[f"{si}.{attr}"] = jnp.zeros((T, P), dtype=self._np_dtype(t))
        return ev

    @property
    def dropped(self) -> int:
        """Partial matches / emissions lost to capacity exhaustion — only
        possible once adaptive growth hits the A_CAP ceiling.  Carried in
        device state (host-side counter in chunked mode), so snapshot-safe."""
        if self._chunk_cfg is not None:
            return self._of_dropped
        return int(np.asarray(self.state["of_slots"]).sum())

    # An integer key column whose values span at most this many entries
    # is mapped through a dense key -> lane table: 2^22 int32 entries are
    # 16 MiB of host memory a plan, which holds a string attribute's
    # dictionary codes (rt.strings hands out consecutive ints from 1) up
    # to four million symbols, and any id range that narrow.  A wider
    # span would trade the sort for a table mostly of holes.
    KEY_TABLE_MAX = 1 << 22

    def part_of(self, stream_id: str, batch: EventBatch) -> tuple:
        """(partition index per event, whether the key table gave it);
        grows the key map (host side).
        The form is read off the key column: an integer dtype over a
        narrow range goes through the table; float, wide-int and computed
        object keys go through np.unique.  Either way the python dict is
        consulted once per DISTINCT key it has to resolve, and new keys
        are numbered in sorted order."""
        if self.part_key_fns is None:
            return np.zeros(batch.n, dtype=_I32), False
        keys = np.asarray(self.part_key_fns[stream_id](batch))
        parts = self._lanes_by_table(keys)
        if parts is not None:
            return parts, True
        uniq, inv = np.unique(keys, return_inverse=True)
        parts_u = np.fromiter((self._lane_of_key(k) for k in uniq.tolist()),
                              dtype=_I32, count=len(uniq))
        return parts_u[inv], False

    def _lane_of_key(self, k) -> int:
        k2p = self._key_to_part
        p = k2p.get(k)
        if p is None:
            # stateless lane families size their (L, F) grid per
            # flush: a hot-added key is just a new lane id — no
            # device-state growth, no recompile below the next
            # pow2 lane bucket
            if self._chunk_cfg is None and len(k2p) >= self.P:
                self._grow(2 * self.P)
            p = k2p[k] = len(k2p)
        return p

    def _lanes_by_table(self, keys: np.ndarray) -> Optional[np.ndarray]:
        """One np.take on the dense key -> lane table, or None for a
        column the table cannot hold.  The table is a cache of
        _key_to_part: its misses (keys new to the plan, or to a table
        rebuilt after a restore) go through the dict in sorted order, so
        lane ids are assigned exactly as the np.unique walk assigns them."""
        if not keys.size or keys.dtype.kind not in "iu" \
                or keys.dtype == np.uint64:
            return None
        lo, hi = int(keys.min()), int(keys.max())
        tab, base = self._key_table, self._key_base
        if tab is None or lo < base or hi >= base + len(tab):
            if tab is not None:
                lo, hi = min(lo, base), max(hi, base + len(tab) - 1)
            if 0 <= lo and hi < self.KEY_TABLE_MAX:
                lo = 0          # codes and small ids index the table as is
            if hi - lo >= self.KEY_TABLE_MAX:
                return None
            grown = np.full(pow2_at_least(hi - lo + 1, lo=1024), -1, _I32)
            if tab is not None:
                grown[base - lo:base - lo + len(tab)] = tab
            self._key_table, self._key_base = tab, base = grown, lo
        idx = keys.astype(np.intp, copy=False)
        if base:
            idx = idx - base
        parts = tab.take(idx)
        miss = parts < 0
        if miss.any():
            for k in np.unique(keys[miss]).tolist():
                tab[k - base] = self._lane_of_key(k)
            parts = tab.take(idx)
        return parts

    def _grow(self, new_p: int) -> None:
        """Double the partition axis (last axis of every state leaf): pad,
        rebuild the kernel (the next block jit-compiles at the new P)."""
        import jax.numpy as jnp
        if self.mesh is not None:
            nd = len(self.mesh.devices)
            new_p = -(-new_p // nd) * nd
        old = jax.tree_util.tree_map(np.asarray, self.state)
        kern = NFAKernel(self.spec, self.kernel.sel_fns, self.kernel.having,
                         new_p, self.kernel.A, self.kernel.E, f64=self.f64,
                         playback=self.rt._playback, params=self.kernel.params,
                         emit_qid=self.kernel.emit_qid,
                         init_on_tick=self._init_on_tick)
        fresh = kern.init_state()
        self.state = self._shard(jax.tree_util.tree_map(
            lambda f, o: np.concatenate(
                [o, np.asarray(f)[..., o.shape[-1]:]], axis=-1),
            fresh, old))
        self.kernel = kern
        self.P = new_p

    def _grow_slots(self, new_a: int) -> None:
        """Pad the slot axis of per-slot state leaves and rebuild."""
        import jax.numpy as jnp
        old = jax.tree_util.tree_map(np.asarray, self.state)
        kern = NFAKernel(self.spec, self.kernel.sel_fns, self.kernel.having,
                         self.P, new_a, self.kernel.E, f64=self.f64,
                         playback=self.rt._playback, params=self.kernel.params,
                         emit_qid=self.kernel.emit_qid,
                         init_on_tick=self._init_on_tick)
        fresh = kern.init_state()

        def pad(f, o):
            ax = {2: 0, 3: 1}.get(o.ndim)
            if ax is None or f.shape == o.shape:
                return jnp.asarray(o)
            filler = np.asarray(f)[(slice(None),) * ax + (slice(o.shape[ax], None),)]
            return np.concatenate([o, filler], axis=ax)
        self.state = self._shard(jax.tree_util.tree_map(pad, fresh, old))
        self.kernel = kern

    def _rebuild_kernel(self, E: int) -> None:
        import jax.numpy as jnp
        self.kernel = NFAKernel(self.spec, self.kernel.sel_fns,
                                self.kernel.having, self.P, self.kernel.A,
                                E, f64=self.f64, playback=self.rt._playback,
                                params=self.kernel.params,
                                emit_qid=self.kernel.emit_qid,
                                init_on_tick=self._init_on_tick)

    # -- plan families ---------------------------------------------------

    # auto-selection preference: the first eligible family wins, "seq"
    # is the universal fallback.  The order is unmeasured on the chip:
    # every benchmark cell runs scan, and the one reading of all four
    # (PR 21's smoke, flat P = 1 block) ranked chunk < dfa < scan < seq
    # before PRs 27 and 30 changed scan and dfa.  ROADMAP A9 (cells that
    # run the others) and C2 (delete what loses) decide it.
    FAMILY_ORDER = ("scan", "dfa", "chunk")

    def _choose_family(self, want: Optional[str]) -> str:
        if want is not None:
            if want == "seq" or self.families.get(want) is True:
                return want
            import warnings
            pl = getattr(getattr(self, "rt", None), "placement", None)
            if pl is not None:
                pl.demote(self.name, "D-FAMILY",
                          f"requested plan family {want!r} is not "
                          f"eligible: {self.families.get(want)}",
                          alternative=want)
            warnings.warn(
                f"pattern {self.name!r}: requested plan family {want!r} is "
                f"not eligible ({self.families.get(want)}); falling back to "
                f"automatic selection", RuntimeWarning, stacklevel=2)
        for f in self.FAMILY_ORDER:
            if self.families.get(f) is True:
                return f
        return "seq"

    def _enter_stateless(self, fam: str) -> None:
        """Engage a stateless family (chunk/scan/dfa): blocks carry no
        device state, cross-flush continuity = tail replay + seq dedup,
        and finalize rolls its bookkeeping back on failure so the
        degradation ladder may halve and retry the flush."""
        self.family = fam
        if self._chunk_cfg is None:
            self._chunk_cfg = {
                "W": max(p.within_ms for p in self.spec.positions),
                "lanes": max(2, self._stateless_lanes)}
        if self._pipe is None:
            from .pipeline import DispatchPipeline
            self._pipe = DispatchPipeline(
                self.name, lambda e: [self._materialize_chunk(e)],
                depth=self.pipeline_depth)
        if not self.spec.every_head and self._arm_done is None:
            # non-`every`: ONE instance per lane ever; the device reports
            # resolution through the meta flag and the host stops
            # dispatching once every lane's arm is resolved
            nl = self.P if self.broadcast_events else 1
            self._arm_done = np.zeros(nl, dtype=bool)
        self.retryable_finalize = True

    def _parallel_kernel(self):
        """Build (and cache) the parallel-in-time kernel for the current
        scan/dfa family — shares the NFAKernel's selector/having/output
        metadata so packed blocks unpack identically."""
        kern = self._par_kerns.get(self.family)
        if kern is None:
            from .nfa_parallel import ParallelChainKernel, lower_parallel
            prog = lower_parallel(self.spec, self.rt.strings,
                                  self.param_extra)
            kern = ParallelChainKernel(prog, self.kernel,
                                       family=self.family)
            self._par_kerns[self.family] = kern
        return kern

    @property
    def expiry_queries(self) -> Optional[dict]:
        """{'built': b, 'shared': s}: `within` expiry queries the parallel
        block makes per head, and positions that reuse one (EXPLAIN)."""
        if self.family not in ("scan", "dfa"):
            return None
        return dict(self._parallel_kernel().expiry_queries)

    @property
    def first_hit(self) -> Optional[dict]:
        """The first-hit queries of the parallel block last dispatched, by
        the form that answers them (ParallelChainKernel.first_hit;
        EXPLAIN)."""
        if self.family not in ("scan", "dfa"):
            return None
        asked = self._parallel_kernel().first_hit
        return dict(asked) if asked else None

    @property
    def indexed_read(self) -> Optional[dict]:
        """The indexed reads of a lane's column in the parallel block last
        dispatched, by form (ParallelChainKernel.indexed_read; EXPLAIN)."""
        if self.family not in ("scan", "dfa"):
            return None
        asked = self._parallel_kernel().indexed_read
        return dict(asked) if asked else None

    @property
    def lane_pack_order(self) -> Optional[dict]:
        """Flushes by the way the host pack ordered them (EXPLAIN): lane
        order by one `radix` pass or by the two-key `lexsort`; key -> lane
        by the `key_table` or by `key_unique`; and `seq_sort_skipped`, the
        flushes whose union was in arrival order as it stood.  Each form is
        picked from the flush's own columns (dtype, range, order)."""
        counted = self._lane_pack_order
        return dict(counted) if any(counted.values()) else None

    @property
    def lane_cut(self) -> Optional[dict]:
        """What the host pack's cut of long lanes did (EXPLAIN), for a
        plan that packs partitioned lane grids: `flushes_cut`, and over
        them the `lanes_cut`, the grid `rows_added` to one a lane and the
        `events_replayed` at the head of those rows; `flushes_uncuttable`,
        flushes with a lane past `cut_length` (LANE_CUT) that kept one row
        a lane because some lane's replay window overfills a row."""
        if not self._partitioned or self.family not in ("scan", "dfa"):
            return None
        return {**self._lane_cut, "cut_length": LANE_CUT}

    def _rebase(self, min_ts: int, min_seq: int) -> None:
        """Shift the plan's ts/seq bases forward and adjust persistent slot
        offsets so i32 locals never overflow.  Ancient slots clamp to
        -LOCAL_SPAN (their age saturates; `within` then expires them)."""
        import jax.numpy as jnp
        st = {k: np.asarray(v) for k, v in self.state.items()}
        if self._ts_base is not None and min_ts > self._ts_base:
            d = min_ts - self._ts_base
            no_first = st["first_ts"] == np.int32(LOCAL_SPAN)  # NO_FIRST
            st["first_ts"] = np.where(no_first, st["first_ts"], np.maximum(
                st["first_ts"].astype(np.int64) - d, -LOCAL_SPAN)).astype(_I32)
            if st["dl"].size:
                no_dl = st["dl"] == np.int32(2**31 - 1)
                st["dl"] = np.where(
                    no_dl, st["dl"],
                    np.maximum(st["dl"].astype(np.int64) - d,
                               -LOCAL_SPAN).astype(_I32))
            self._ts_base = min_ts
        if self._seq_base is not None and min_seq > self._seq_base:
            d = min_seq - self._seq_base
            st["head_seq"] = np.maximum(
                st["head_seq"].astype(np.int64) - d, -LOCAL_SPAN).astype(_I32)
            self._seq_base = min_seq
        self.state = self._shard(st)

    # -- telemetry ---------------------------------------------------------

    def _call_block(self, kern: NFAKernel, T: int, M: int, st, ev):
        """Invoke one jitted NFA block recording compile/kernel stage,
        block-cache hit/miss, and the H2D payload size."""
        self.rt.inject("dispatch", self.name)   # fault-injection boundary
        stats = self.rt.stats
        prof = self.rt.profiler
        if not stats.enabled and prof is None:
            return kern.block_fn(T, M)(st, ev)
        hit = (T, M) in kern._block_cache
        fn = kern.block_fn(T, M)
        return call_kernel(stats, self.name, fn, (st, ev),
                           cache_hit=hit, nbytes=env_nbytes(ev),
                           prof=prof)

    def _pull(self, out: dict) -> tuple:
        """The blocking pull of one block's packed outputs: the wait for
        the device, then ONE D2H transfer per pack; notes the bytes."""
        with self.rt.span("transfer", plan=self.name):
            ipack = np.asarray(out["i"])
            fpack = np.asarray(out["f"]) if "f" in out else None
        prof = self.rt.profiler
        if prof is not None:
            prof.note_bytes(self.name, "d2h", ipack.nbytes
                            + (0 if fpack is None else fpack.nbytes))
        return ipack, fpack

    def device_metrics(self) -> dict:
        """Sampled device gauges: lane occupancy + state-frontier width
        (one D2H pull of `occ`), partition-key fill, capacity drops."""
        d = {"lanes_total": int(self.P)}
        if self._chunk_cfg is None:
            d.update(self.kernel.occupancy(self.state))
        if self.part_key_fns is not None:
            # distinct from lanes_active (lanes holding LIVE partial
            # matches): keys ever assigned to a lane
            d["keys_assigned"] = len(self._key_to_part)
        d["dropped_partials"] = int(self.dropped)
        # plan-family gauges: the selected execution family (string —
        # statistics() only; Prometheus skips non-numerics), per-family
        # dispatch counts, and eligibility reasons for rejected families
        d["plan_family"] = self.family
        for f, n in self._family_dispatches.items():
            d[f"dispatches_{f}"] = int(n)
        if self._lane_dispatches:
            # lane-vmapped scan/dfa blocks (partitioned keys / fused
            # queries ride ONE vmap of the flat block over the lanes)
            d["dispatches_lane_vmapped"] = int(self._lane_dispatches)
            d["lanes_last_dispatch"] = int(self._lanes_last)
        inel = {f: r for f, r in self.families.items() if r is not True}
        if inel:
            d["family_ineligible"] = inel
        counted = self.lane_pack_order
        if counted:
            d["lane_pack_order"] = counted
        cut = self.lane_cut
        if cut:
            d["lane_cut"] = cut
        return d

    # -- QueryPlan interface -------------------------------------------------

    def process(self, stream_id: str, batch: EventBatch) -> list:
        if batch.n:
            self._buffered.append((stream_id, batch))
        return []

    def finalize(self) -> list:
        if self._chunk_cfg is None or not self._buffered:
            return self._rows_to_batches(self._finalize_chunks())
        # chunked mode is retryable (degradation ladder): blocks carry no
        # device state, and _run_chunked_flat rolls back its host-side
        # tail/seq bookkeeping on a dispatch failure — so restoring the
        # input buffer makes a failed flush fully re-runnable (possibly
        # split in half by the runtime)
        snapshot = list(self._buffered)
        try:
            return self._rows_to_batches(self._finalize_chunks())
        except Exception:
            self._buffered = snapshot
            raise

    def _finalize_chunks(self) -> list:
        if not self._buffered:
            return []
        if self.spec.needs_init_slot and self._init_on_tick:
            # pin the START anchor while _buffered still holds the tape
            # (pre-clock playback anchors at the earliest buffered event;
            # after the pop the fallback would be the wall clock — review r5)
            self._anchor_ms()
        bufs, self._buffered = self._buffered, []

        with self.rt.span("host_build", plan=self.name):
            # 1. union columns over all buffered batches
            N = sum(b.n for _s, b in bufs)
            ts = np.empty(N, dtype=np.int64)
            seq = np.empty(N, dtype=np.int64)
            scode = np.empty(N, dtype=_I32)
            part = np.empty(N, dtype=_I32)
            cols: dict = {}
            for si, attr, t in self._grid_attrs:
                cols[f"{si}.{attr}"] = np.zeros(N, dtype=self._np_dtype(t))
            o = 0
            tabled = True
            for sid, b in bufs:
                si = self._scode[sid]
                sl = slice(o, o + b.n)
                ts[sl] = b.timestamps
                seq[sl] = b.seqs if b.seqs is not None \
                    else np.arange(o, o + b.n)
                scode[sl] = si
                part[sl], by_table = self.part_of(sid, b)
                tabled &= by_table
                for sj, attr, _t in self._grid_attrs:
                    if sj == si:
                        cols[f"{si}.{attr}"][sl] = b.columns[attr]
                o += b.n
            if self.part_key_fns is not None:
                self._lane_pack_order[
                    "key_table" if tabled else "key_unique"] += 1

            # 2. order by arrival.  One send_batch's stamps, and batches
            # buffered in the order they were stamped, are in order as
            # they stand; streams flushed together interleave and sort.
            if (seq[1:] >= seq[:-1]).all():
                self._lane_pack_order["seq_sort_skipped"] += 1
            else:
                order = np.argsort(seq, kind="stable")
                ts, seq, scode, part = (ts[order], seq[order], scode[order],
                                        part[order])
                for k in cols:
                    cols[k] = cols[k][order]
        if self._chunk_cfg is not None:
            return self._run_chunked_flat(ts, seq, scode, cols, part)
        with self.rt.span("host_build", plan=self.name):
            if self.broadcast_events:
                # every lane sees every event, so the grid is (T, 1)
                idx_within = np.arange(N, dtype=np.int64)
                part = np.zeros(N, dtype=_I32)
            else:
                # index-within-partition: step 2 left the rows in seq
                # order, which is _stable_lane_order's invariant
                by_part = _stable_lane_order(part)
                self._lane_pack_order["radix"] += 1
                idx_within = np.empty(N, dtype=np.int64)
                sp = part[by_part]
                run_start = np.flatnonzero(np.r_[True, sp[1:] != sp[:-1]])
                run_id = np.cumsum(np.r_[True, sp[1:] != sp[:-1]]) - 1
                idx_within[by_part] = np.arange(N) - run_start[run_id]

            # 3. i32 offset bases (+ rebase persistent state before overflow).
            # The base is chosen from the flush MAX so headroom is always
            # restored even when a stale event pins the minimum; events older
            # than base - LOCAL_SPAN clamp low (their age saturates and
            # `within` expires them — never a silent wrap).
            budget = LOCAL_SPAN - (1 << 16)
            if self._ts_base is None:
                lo = int(ts.min())
                if self.spec.needs_init_slot and self._init_on_tick:
                    lo = min(lo, self._anchor_ms())
                self._ts_base = max(lo, int(ts.max()) - budget)
                self._seq_base = max(int(seq.min()), int(seq.max()) - budget)
            if int(ts.max()) - self._ts_base >= budget \
                    or int(seq.max()) - self._seq_base >= budget:
                self._rebase(max(int(ts.min()), int(ts.max()) - budget),
                             max(int(seq.min()), int(seq.max()) - budget))
            ts32 = np.clip(ts - self._ts_base, -LOCAL_SPAN, LOCAL_SPAN).astype(_I32)
            seq32 = np.clip(seq - self._seq_base, -LOCAL_SPAN, LOCAL_SPAN).astype(_I32)
            self._last_seq = max(self._last_seq, int(seq.max()))

            # 4. run dense (T, P) blocks (chunked if one partition hogs the
            # batch); T_CAP widens for small P so single-partition patterns
            # amortize per-block overhead over longer scans
            T_CAP = min(8192, max(512, (1 << 19) // max(self.P, 1)))
            if self.broadcast_events:
                T_CAP = 4096
            GW = 1 if self.broadcast_events else self.P    # grid width
            multi = len(self.spec.stream_ids) > 1
            chunk_evs: list = []
            n_chunks = int(idx_within.max()) // T_CAP + 1
            for c in range(n_chunks):
                m = (idx_within >= c * T_CAP) & (idx_within < (c + 1) * T_CAP)
                if not m.any():
                    continue
                t_local = (idx_within[m] - c * T_CAP).astype(np.int64)
                T = pow2_at_least(int(t_local.max()) + 1)
                ev = {"__ts__": np.zeros((T, GW), _I32),
                      "__seq__": np.zeros((T, GW), _I32),
                      "__valid__": np.zeros((T, GW), bool)}
                if multi:
                    ev["__scode__"] = np.full((T, GW), -1, _I32)
                for k, v in cols.items():
                    ev[k] = np.zeros((T, GW), v.dtype)
                pm = part[m]
                ev["__ts__"][t_local, pm] = ts32[m]
                ev["__seq__"][t_local, pm] = seq32[m]
                if multi:
                    ev["__scode__"][t_local, pm] = scode[m]
                ev["__valid__"][t_local, pm] = True
                for k, v in cols.items():
                    ev[k][t_local, pm] = v[m]
                ev["__base_ts__"] = np.int64(self._ts_base)
                ev["__base_seq__"] = np.int64(self._seq_base)
                if self.spec.needs_init_slot and self._init_on_tick:
                    ev["__anchor__"] = np.int32(np.clip(
                        self._anchor_ms() - self._ts_base,
                        -LOCAL_SPAN, LOCAL_SPAN))
                chunk_evs.append((ev, T))

        return self._run_chunks(chunk_evs)

    def _run_chunks(self, chunk_evs: list) -> list:
        """Dispatch ALL blocks first (device state threads functionally),
        then pull outputs — async D2H copies overlap each pull's fixed
        latency.

        Retries are exact because state is functional: a match-buffer
        overflow re-runs only that block from its saved pre-state (state
        evolution is M-independent); pending-slot exhaustion grows A and
        restarts the chain from the exhausted block (dropped heads change
        downstream state)."""
        results: list = [None] * len(chunk_evs)
        i = 0
        while i < len(chunk_evs):
            dispatched = []
            st = self.state
            for j in range(i, len(chunk_evs)):
                ev, T = chunk_evs[j]
                ev = self._shard(ev)
                if self.broadcast_events:
                    # multi-query lanes are matchy and this kernel costs
                    # ~17s to compile: size M generously in pow2 so the
                    # steady state reuses ONE compiled block
                    M = max(self._m_hint, pow2_at_least(32 * T))
                else:
                    M = max(self._m_hint, _m_bucket(2 * T))
                pre = st
                st, out = self._call_block(self.kernel, T, M, pre, ev)
                self._family_dispatches["seq"] = \
                    self._family_dispatches.get("seq", 0) + 1
                from .pipeline import start_d2h
                start_d2h(out, keys=("i",))   # pull overlaps the compute
                dispatched.append((j, pre, ev, T, M, out))
            restart = None
            for j, pre, ev, T, M, out in dispatched:
                ipack, fpack = self._pull(out)
                n, ofs, ofl = (int(ipack[0, 0]), int(ipack[0, 1]),
                               int(ipack[0, 2]))
                while n > M:                   # exact re-run, bigger buffer
                    M = pow2_at_least(n) if self.broadcast_events \
                        else _m_bucket(n)
                    _st2, out = self._call_block(self.kernel, T, M, pre, ev)
                    ipack, fpack = self._pull(out)
                    n, ofs, ofl = (int(ipack[0, 0]), int(ipack[0, 1]),
                                   int(ipack[0, 2]))
                self._m_hint = max(self._m_hint, M)
                if ofs > self._of_slots_seen and self.kernel.A < self.A_CAP:
                    self.state = pre
                    self._grow_slots(min(2 * self.kernel.A, self.A_CAP))
                    restart = j
                    break
                if ofl > 0:
                    # a count-survivor emission burst outran the E lanes:
                    # widen E (recompile) and re-run from this block
                    self.state = pre
                    self._rebuild_kernel(E=self.kernel.E * 2)
                    restart = j
                    break
                if ofs > self._of_slots_seen:
                    import warnings
                    warnings.warn(
                        f"pattern {self.name!r}: pending-match slots hit the "
                        f"deviceSlotCap ceiling ({self.A_CAP}); {ofs} partial "
                        f"matches dropped so far (raise @app:deviceSlotCap)",
                        RuntimeWarning, stacklevel=2)
                    self._of_slots_seen = ofs
                dlm = int(ipack[0, 3])
                self._next_deadline = (None if dlm >= 2**31 - 1
                                       else self._ts_base + dlm)
                results[j] = self._unpack_block(ipack, fpack, n)
            if restart is None:
                self.state = st
                break
            i = restart
        return results

    # -- chunked-halo execution (stateless, within-bounded patterns) -----

    def _chunk_kernel(self, K: int) -> NFAKernel:
        kern = self._kern_by_p.get(K)
        if kern is None or kern.A != self._chunk_A \
                or (self._chunk_E is not None and kern.E != self._chunk_E):
            kern = NFAKernel(self.spec, self.kernel.sel_fns,
                             self.kernel.having, K, self._chunk_A,
                             self._chunk_E, f64=self.f64,
                             playback=self.rt._playback)
            self._kern_by_p[K] = kern
        return kern

    def _run_chunked_flat(self, ts, seq, scode, cols, part=None) -> list:
        """One stateless flat block per flush: [replayed tail | new events]
        split into K own-chunks, gathered into lanes on device.  Blocks
        carry no device state, so flushes pipeline independently
        (@app:devicePipeline) and retries are self-contained.  A dispatch
        failure rolls the host-side tail/seq bookkeeping back so the
        runtime's degradation ladder can re-run the flush.

        Partitioned patterns on a scan/dfa family route through the
        lane-grid variant instead: each key's events form an independent
        sub-stream, laid out as one (L, F) grid and executed by ONE vmap
        of the flat block over the lane axis."""
        if self._partitioned and self.family in ("scan", "dfa"):
            return self._run_lanes_flat(ts, seq, scode, cols, part)
        saved = (self._tail, self._prev_last_seq, self._last_seq,
                 getattr(self, "_chunk_F", 0))
        try:
            return self._run_chunked_flat_inner(ts, seq, scode, cols)
        except Exception:
            (self._tail, self._prev_last_seq, self._last_seq,
             self._chunk_F) = saved
            raise

    def _run_chunked_flat_inner(self, ts, seq, scode, cols) -> list:
        fam = self.family
        with self.rt.span("host_build", plan=self.name):
            cfg = self._chunk_cfg
            W = int(cfg["W"])
            if self._tail is not None:
                ts = np.concatenate([self._tail["ts"], ts])
                seq = np.concatenate([self._tail["seq"], seq])
                scode = np.concatenate([self._tail["scode"], scode])
                cols = {k: np.concatenate([self._tail["cols"][k], v])
                        for k, v in cols.items()}
            N = len(ts)
            ts_mono = np.maximum.accumulate(ts)
            # `within` compares RAW event timestamps, but halo/tail bounds
            # search the running max — a regressed (out-of-order) timestamp
            # could place a still-completable event past the searched bound.
            # Widening the window by the worst regression keeps every such
            # event inside the halo/tail (over-covering is harmless).
            W = W + int(np.max(ts_mono - ts)) if N else W

            K = CS = H = T = None
            if fam == "chunk":
                # lane geometry: halo-dominated data (few events per W)
                # gets fewer, longer chunks; K buckets to pow2 so kernels
                # are reused
                def _halo(K: int):
                    CS = -(-N // K)
                    ends = np.unique(np.minimum(np.arange(1, K + 1) * CS, N))
                    ends = ends[ends > 0]
                    to = np.searchsorted(ts_mono, ts_mono[ends - 1] + W,
                                         side="right")
                    return CS, int(np.max(to - ends))
                # K rides pow2 buckets: latency-capped ingest produces
                # VARIABLE small flushes, and every distinct K is a fresh
                # kernel compile; empty lanes
                # are free
                K = min(int(cfg["lanes"]), pow2_at_least(max(1, N), lo=8))
                CS, H = _halo(K)
                if CS < H:
                    # halo-dominated: fewer, longer chunks (lo=8 keeps the
                    # K bucket set tiny — empty lanes are free, fresh
                    # compiles are not)
                    K = min(int(cfg["lanes"]),
                            pow2_at_least(max(1, N // max(H, 1)), lo=8))
                    CS, H = _halo(K)
                if self.mesh is not None:
                    # lane axis shards over the mesh: K must divide evenly
                    # over the device count (K = min(lanes, N) is arbitrary)
                    nd = self.mesh.devices.size
                    if K % nd:
                        K = -(-K // nd) * nd
                        CS, H = _halo(K)
                T = pow2_at_least(CS + H, lo=64)

            # fresh i32 bases every flush (no persistent device state)
            ts_base = int(ts_mono[0])
            seq_base = int(seq[0])
            ts32 = np.clip(ts - ts_base, -LOCAL_SPAN, LOCAL_SPAN).astype(_I32)
            self._last_seq = max(self._last_seq, int(seq[-1]))
            # completions at or before the previous flush's last seq are
            # replays — suppressed ON DEVICE so they are never pulled
            prev_off = np.int32(np.clip(self._prev_last_seq - seq_base,
                                        -LOCAL_SPAN, LOCAL_SPAN))

            # flat-buffer capacity: fine-granular bucket + one granule of
            # headroom, STICKY per plan — the replay tail appearing after
            # flush 1 (or drifting in size) must not change F, because every
            # distinct F is a recompile.  Shrinks only
            # when the flush size drops 4x (batch regime change).
            f_min = (N // 2048 + 2) * 2048
            F = max(getattr(self, "_chunk_F", 0), f_min)
            if F > 4 * f_min:
                F = f_min
            self._chunk_F = F

            def pad(a):
                out = np.zeros(F, dtype=a.dtype)
                out[:N] = a
                return out
            ev = {"__flat.__ts__": pad(ts32),
                  "__nev__": np.int32(N),
                  "__prev_seq__": prev_off,
                  "__base_ts__": np.int64(ts_base),
                  "__base_seq__": np.int64(seq_base)}
            if fam == "chunk":
                ev["__cs__"] = np.int32(CS)
            if fam == "chunk" and seq[-1] - seq[0] == N - 1:
                # consecutive seqs derive on device from one scalar.
                # Chunk-family only: output events consume seqs, so flush
                # 2+ always lands on the explicit-seq variant anyway —
                # the scan/dfa families ship it from flush 1 and save a
                # whole structural recompile
                # for 4 bytes/event of upload
                ev["__seq0__"] = np.int32(0)
            else:
                ev["__flat.__seq__"] = pad(
                    np.clip(seq - seq_base, -LOCAL_SPAN, LOCAL_SPAN).astype(_I32))
            if len(self.spec.stream_ids) > 1:
                ev["__flat.__scode__"] = pad(scode)
            for k, v in cols.items():
                ev[f"__flat.{k}"] = pad(v)

            last_ts = int(ts_mono[-1])
            keep = ts_mono >= last_ts - W
            self._tail = {"ts": ts[keep], "seq": seq[keep],
                          "scode": scode[keep],
                          "cols": {k: v[keep] for k, v in cols.items()}}
            self._prev_last_seq = int(seq[-1])

        # M sizing: the first flush guesses from N (could retry once);
        # after that the hint PINS it — an N-based floor would drift
        # across 64K buckets as the replay tail varies, and every drift
        # is a recompile
        if fam != "chunk":
            # scan/dfa: one candidate completion per head (times the
            # final count's emission lanes), so M = F rarely overflows
            # and riding the sticky F bucket means M never recompiles on
            # its own; a final-count burst retries with a bigger M
            lanes = None
            if self.broadcast_events:
                if self._arm_done is not None and self._arm_done.all():
                    return []      # every lane's single arm is resolved
                lanes = self.P
                for k, v in (self.kernel.params or {}).items():
                    ev[f"__param.{k}"] = np.asarray(v)
                ev["__lane_qid__"] = np.arange(self.P, dtype=_I32)
                if self._arm_done is not None:
                    ev["__arm_done__"] = self._arm_done.astype(_I32)
            elif self._arm_done is not None:
                if self._arm_done.all():
                    return []      # the one non-`every` arm is resolved
                ev["__arm_done__"] = np.int32(0)
            return self._pipe.push(self._dispatch_par(
                ev, F, F, ts_base, seq_base, lanes=lanes))
        M = (self._m_hint if self._m_hint >= 16384
             else max(self._m_hint, _m_bucket_chunk(N)))
        return self._pipe.push(self._dispatch_chunk(
            ev, K, T, M, ts_base, seq_base))

    def _run_lanes_flat(self, ts, seq, scode, cols, part) -> list:
        """Partitioned scan/dfa: each key's events are an independent
        sub-stream — ONE (L, F) lane grid, ONE vmapped flat block, with
        per-lane replay tails and per-lane completion-seq dedup.  A
        dispatch failure rolls the per-lane bookkeeping back so the
        degradation ladder can re-run the flush."""
        saved = (self._lane_tail, self._lane_prev.copy(), self._last_seq,
                 self._lane_F)
        try:
            return self._run_lanes_flat_inner(ts, seq, scode, cols, part)
        except Exception:
            (self._lane_tail, self._lane_prev, self._last_seq,
             self._lane_F) = saved
            raise

    def _lane_order(self, part, seq, run_start) -> tuple:
        """(order, seq[order]): the rows by (lane, seq).  `[tail | new]`
        is in seq order inside every lane when (a) the tail keeps each
        lane's rows in seq order (it is a mask over rows this function
        ordered; `held` lanes are disjoint from the active ones), (b)
        every tail seq is below every new seq of its lane (arrival stamps
        only grow) and (c) the new rows are in seq order
        (_finalize_chunks step 2): then one stable radix pass over the
        lane id is the whole sort.  Whether it held is read off the
        result, lane by lane (`run_start`: where each lane's run begins);
        input that breaks it (unstamped batches, whose seqs restart at
        every flush; a restored tail from elsewhere) takes the two-key
        comparison sort."""
        order = _stable_lane_order(part)
        seq_l = seq[order]
        if _rises_in_lanes(seq_l, run_start):
            self._lane_pack_order["radix"] += 1
        else:
            self._lane_pack_order["lexsort"] += 1
            order = np.lexsort((seq, part))
            seq_l = seq[order]
        return order, seq_l

    def _cut_lanes(self, counts, run_start, tail_n, tsmono, W: int,
                   order, seq_l, ts_l, lane_prev) -> Optional[tuple]:
        """The grid rows of a flush whose longest lane is past LANE_CUT
        (_cut_rows), as what the grid is filled from: per cell, in row
        order, the flush row, seq and ts it takes (`order`, `seq_l`, `ts_l`
        gathered: a row's replayed head repeats events of the row before);
        per row, its events, where its cells start, and its dedup bound: a
        lane's first row at the lane's prev seq (`lane_prev`, per lane
        run), a later one at the event before its first new one.  Counted.
        None, and counted, when a lane is hotter than the cut serves: the
        flush then keeps one row a lane, whatever its length."""
        rows = _cut_rows(counts, run_start, tail_n, tsmono, W)
        did = self._lane_cut
        if rows is None:
            did["flushes_uncuttable"] += 1
            return None
        row_run, row_at, row_n, row_new = rows
        g_start = np.cumsum(row_n) - row_n
        src = np.repeat(row_at - g_start, row_n)
        src += np.arange(len(src))
        first = np.r_[True, row_run[1:] != row_run[:-1]]
        did["flushes_cut"] += 1
        did["lanes_cut"] += int(np.count_nonzero(counts > LANE_CUT))
        did["rows_added"] += len(row_n) - len(counts)
        did["events_replayed"] += int((row_new - row_at)[~first].sum())
        g_prev = np.where(first, lane_prev[row_run], seq_l[row_new - 1])
        return order[src], seq_l[src], ts_l[src], row_n, g_start, g_prev

    def _run_lanes_flat_inner(self, ts, seq, scode, cols, part) -> list:
        with self.rt.span("host_build", plan=self.name):
            W0 = int(self._chunk_cfg["W"])
            # rows a lane, new then replayed; lane ids are dense in
            # [0, len(_key_to_part)), so a count is a bincount
            lane_n = np.bincount(part, minlength=len(self._key_to_part))
            tl = self._lane_tail
            held = tail_n = None
            if tl is not None:
                # only lanes with NEW events this flush replay their
                # tail; a quiet lane cannot produce a new completion
                # (everything it could emit is at or before its prev
                # seq), and letting its old events into the flush would
                # pin the shared i32 ts/seq bases forever (review
                # finding: a long-quiet lane saturated every live
                # lane's offsets at the 2^30 clip)
                active = lane_n[tl["part"]] > 0
                if not active.all():
                    held = _tail_rows(tl, ~active)
                    tl = _tail_rows(tl, active)
                tail_n = np.bincount(tl["part"], minlength=len(lane_n))
                lane_n = lane_n + tail_n
                ts = np.concatenate([tl["ts"], ts])
                seq = np.concatenate([tl["seq"], seq])
                scode = np.concatenate([tl["scode"], scode])
                part = np.concatenate([tl["part"], part])
                cols = {k: np.concatenate([tl["cols"][k], v])
                        for k, v in cols.items()}
            N = len(ts)
            # the flush's lanes in ascending id, each one run of the
            # lane-ordered rows
            lane_ids = np.flatnonzero(lane_n)
            counts = lane_n[lane_ids]
            Lr = len(lane_ids)
            run_start = np.cumsum(counts) - counts
            run_end = run_start + counts - 1
            order, seq_l = self._lane_order(part, seq, run_start)
            ts_l = ts[order]

            # per-lane running-max ts: feeds the tail-retention bound and
            # the out-of-order `within` widening, exactly like the flat
            # path's global cummax.  Timestamps that rise inside every
            # lane are their own running max; else ONE cummax over all
            # lanes, each lifted clear of the one before (offset trick)
            ts_lo, ts_hi = int(ts.min()), int(ts.max())
            if _rises_in_lanes(ts_l, run_start):
                tsmono, W = ts_l, W0
            else:
                lift = np.repeat(np.arange(Lr, dtype=np.int64)
                                 * (ts_hi - ts_lo + 1), counts)
                tsmono = np.maximum.accumulate(ts_l + lift) - lift
                W = W0 + int(np.max(tsmono - ts_l))

            # lane-grid geometry: the lane axis pads to pow2 (hot-adding
            # a key keeps the compiled (L, F) shape until the count
            # crosses the next pow2 — no per-key recompile), and F rides
            # a sticky 64-granule bucket so tail drift never recompiles:
            # finer than pow2 because every padded cell multiplies by
            # the lane count (pow2 wasted up to 2x the whole grid)
            fm = int(counts.max())
            if len(self._lane_prev) < len(self._key_to_part):
                grown = np.full(len(self._key_to_part), -(2 ** 62),
                                dtype=np.int64)
                grown[:len(self._lane_prev)] = self._lane_prev
                self._lane_prev = grown
            # what each grid row holds, in lane order: a lane's run as it
            # stands, unless the flush is cut (rows of g_counts events
            # from g_start on; g_prev: the seq before a row's new events)
            g_order, g_seq, g_ts = order, seq_l, ts_l
            g_counts, g_start = counts, run_start
            g_prev = self._lane_prev[lane_ids]
            cut = None
            if fm > LANE_CUT:
                with self.rt.span("lane_cut", plan=self.name):
                    cut = self._cut_lanes(
                        counts, run_start,
                        np.zeros(Lr, dtype=np.int64) if tail_n is None
                        else tail_n[lane_ids], tsmono, W,
                        order, seq_l, ts_l, g_prev)
            if cut is None:
                f_min = pow2_at_least(fm, lo=16) if fm <= 64 \
                    else (fm // 64 + 2) * 64
                F = max(self._lane_F, f_min)
                if F > 4 * f_min:
                    F = f_min
            else:
                g_order, g_seq, g_ts, g_counts, g_start, g_prev = cut
                Lr, N = len(g_counts), len(g_order)
                F = LANE_CUT        # a cut lane's first row fills it
            self._lane_F = F
            Lpad = pow2_at_least(max(Lr, 1), lo=8)
            if self.mesh is not None:
                nd = self.mesh.devices.size
                Lpad = -(-Lpad // nd) * nd      # even lane shards

            # bases anchor at the flush MAX with i32 headroom (like the
            # dense path): a lane resuming after a >2^30 ms / seq gap
            # saturates ITS stale offsets low — which reads as "ancient,
            # expired, already-deduped" on device, the conservative and
            # host-identical outcome — instead of saturating every live
            # lane's offsets high
            budget = LOCAL_SPAN - (1 << 16)
            seq_lo, seq_hi = int(seq.min()), int(seq.max())
            ts_base = max(ts_lo, ts_hi - budget)
            seq_base = max(seq_lo, seq_hi - budget)
            self._last_seq = max(self._last_seq, seq_hi)

            # cell (grid row r, index-within-row i) of the (Lpad, F)
            # grid is flat cell r * F + i: ascending in the row-ordered
            # events, so each column is one in-order scatter
            cell = np.repeat(np.arange(Lr) * F - g_start, g_counts)
            cell += np.arange(N)

            def grid(a):
                g = np.zeros(Lpad * F, dtype=a.dtype)
                g[cell] = a
                return g.reshape(Lpad, F)

            nev = np.zeros(Lpad, _I32)
            nev[:Lr] = g_counts
            prev = np.full(Lpad, -LOCAL_SPAN, _I32)
            prev[:Lr] = np.clip(g_prev - seq_base,
                                -LOCAL_SPAN, LOCAL_SPAN).astype(_I32)
            ev = {"__flat.__ts__": grid(_offsets32(g_ts, ts_base, ts_lo)),
                  "__flat.__seq__": grid(_offsets32(g_seq, seq_base,
                                                    seq_lo)),
                  "__nev__": nev, "__prev_seq__": prev,
                  "__base_ts__": np.int64(ts_base),
                  "__base_seq__": np.int64(seq_base)}
            if len(self.spec.stream_ids) > 1:
                ev["__flat.__scode__"] = grid(scode[g_order])
            for k, v in cols.items():
                ev[f"__flat.{k}"] = grid(v[g_order])

            # per-lane tail: the last `within` window of each lane's
            # events replays at that lane's next flush (lanes quiet this
            # flush keep their stored tail untouched).  Only the kept
            # rows are gathered, in lane order.  By LANE (run_end,
            # counts, lane_ids), never by grid row: a cut lane's tail is
            # its last window whatever rows it was laid out as.
            keep = order[tsmono >= np.repeat(tsmono[run_end] - W, counts)]
            self._lane_tail = _tail_rows(
                {"ts": ts, "seq": seq, "scode": scode, "part": part,
                 "cols": cols}, keep)
            if held is not None:
                # quiet lanes' tails ride along untouched: their lanes
                # are none of the kept ones, so every lane's rows stay
                # contiguous and in seq order (_lane_order's invariant)
                self._lane_tail = {
                    k: (np.concatenate([self._lane_tail[k], held[k]])
                        if k != "cols" else
                        {c: np.concatenate([self._lane_tail["cols"][c],
                                            held["cols"][c]])
                         for c in held["cols"]})
                    for k in self._lane_tail}
            self._lane_prev[lane_ids] = seq_l[run_end]

        return self._pipe.push(self._dispatch_par(
            ev, F, F, ts_base, seq_base, lanes=Lpad))

    def _dispatch_par(self, ev, F, M, ts_base, seq_base,
                      lanes=None) -> dict:
        """One stateless scan/dfa-family block over the whole flat flush
        (no chunk-lane geometry — the kernel is log-depth in T).  With
        `lanes`, the SAME block runs once per lane under jax.vmap
        (partitioned (L, F) grids / fused broadcast lanes)."""
        with self.rt.span("host_build", plan=self.name):
            kern = self._parallel_kernel()
            if self.mesh is not None and lanes:
                # lane axis shards over the mesh; shared scalars and
                # fused broadcast event arrays replicate
                ev = {k: jax.device_put(
                          v, self._lane_sharding(np.ndim(v))
                          if np.ndim(v) and np.shape(v)[0] == lanes
                          else self._lane_sharding(0))
                      for k, v in ev.items()}
        T = (lanes, F) if lanes else F
        _st, out = self._call_block(kern, T, M, {}, ev)
        from .pipeline import start_d2h
        start_d2h(out)      # start the D2H pull while the device computes
        self._family_dispatches[self.family] = \
            self._family_dispatches.get(self.family, 0) + 1
        if lanes:
            self._lane_dispatches += 1
            self._lanes_last = int(lanes)
        return {"ev": ev, "F": F, "M": M, "L": lanes, "out": out,
                "ts_base": ts_base, "seq_base": seq_base}

    def _materialize_par(self, e: dict):
        lanes = e.get("L")
        while True:
            ipack, fpack = self._pull(e["out"])
            n = int(ipack[..., 0, 0].max()) if lanes else int(ipack[0, 0])
            if n > e["M"]:      # final-count emission burst: exact retry
                e = self._dispatch_par(e["ev"], e["F"], _m_bucket_chunk(n),
                                       e["ts_base"], e["seq_base"],
                                       lanes=lanes)
                continue
            break
        if self._arm_done is not None:
            from .nfa_parallel import ARM_RESOLVED
            kern = self._parallel_kernel()
            if kern.prog.single_arm:
                # indexed by grid ROW, which here is a lane id: only
                # fused multi-query lanes reach this with `lanes` (row i
                # is query lane i in every flush); partitioned lanes,
                # whose rows are a flush's ACTIVE lanes or cut segments of
                # them, refuse a non-`every` head (classify_parallel)
                flags = np.asarray(ipack[:, 0, 4] if lanes
                                   else ipack[0, 4:5])
                done = flags == ARM_RESOLVED
                nl = min(len(self._arm_done), len(done))
                self._arm_done[:nl] |= done[:nl]
        # NOTE: _m_hint deliberately not updated — it sizes the chunk/seq
        # match buffers, and par blocks ride M = F instead
        # bases are per-flush: _unpack_block must see THIS entry's
        self._ts_base, self._seq_base = e["ts_base"], e["seq_base"]
        if lanes:
            return self._unpack_lanes(ipack, fpack)
        return self._unpack_block(ipack, fpack, n)

    def _dispatch_chunk(self, ev, K, T, M, ts_base, seq_base) -> dict:
        with self.rt.span("host_build", plan=self.name):
            kern = self._chunk_kernel(K)
            st0 = kern.init_state()
            if self.mesh is not None:
                # lane-axis sharding: state (.., K) shards over the mesh, the
                # flat event buffers replicate (each device gathers its own
                # lanes' chunk+halo windows on device)
                st0 = jax.tree_util.tree_map(
                    lambda a: jax.device_put(
                        a, self._part_sharding(np.ndim(a))
                        if np.ndim(a) and np.shape(a)[-1] == K
                        else self._part_sharding(0)), st0)
                ev = {k: jax.device_put(v, self._part_sharding(0))
                      for k, v in ev.items()}
        _st, out = self._call_block(kern, T, M, st0, ev)
        from .pipeline import start_d2h
        start_d2h(out)      # start the D2H pull while the device computes
        self._family_dispatches["chunk"] = \
            self._family_dispatches.get("chunk", 0) + 1
        return {"ev": ev, "K": K, "T": T, "M": M, "out": out,
                "ts_base": ts_base, "seq_base": seq_base}

    def _materialize_chunk(self, e: dict):
        if "F" in e:                  # scan/dfa-family entry
            return self._materialize_par(e)
        while True:
            ipack, fpack = self._pull(e["out"])
            n, ofs, ofl = (int(ipack[0, 0]), int(ipack[0, 1]),
                           int(ipack[0, 2]))
            if n > e["M"]:
                e = self._dispatch_chunk(e["ev"], e["K"], e["T"],
                                         _m_bucket_chunk(n),
                                         e["ts_base"], e["seq_base"])
                continue
            if ofs > 0 and self._chunk_A < self.A_CAP:
                self._chunk_A = min(2 * self._chunk_A, self.A_CAP)
                e = self._dispatch_chunk(e["ev"], e["K"], e["T"], e["M"],
                                         e["ts_base"], e["seq_base"])
                continue
            if ofl > 0:
                self._chunk_E = 2 * self._kern_by_p[e["K"]].E
                e = self._dispatch_chunk(e["ev"], e["K"], e["T"], e["M"],
                                         e["ts_base"], e["seq_base"])
                continue
            if ofs > 0:
                import warnings
                self._of_dropped += ofs
                warnings.warn(
                    f"pattern {self.name!r}: pending-match slots hit the "
                    f"deviceSlotCap ceiling ({self.A_CAP}); {ofs} partial "
                    f"matches dropped this flush (raise @app:deviceSlotCap)",
                    RuntimeWarning, stacklevel=2)
            break
        self._m_hint = max(self._m_hint, e["M"])
        # bases are per-flush: _unpack_block must see THIS entry's
        self._ts_base, self._seq_base = e["ts_base"], e["seq_base"]
        return self._unpack_block(ipack, fpack, n)

    def flush_pending(self) -> list:
        # chunk results are raw columnar match tables, not OutputBatches:
        # wrap the base pipeline drain/collect in _rows_to_batches
        if self._pipe is None or not len(self._pipe):
            return []
        return self._rows_to_batches(self._pipe.drain())

    def collect_ready(self) -> list:
        if self._pipe is None:
            return []
        chunks = self._pipe.collect()
        return self._rows_to_batches(chunks) if chunks else []

    def _unpack_lanes(self, ipack, fpack):
        """Columnar match table from one lane-vmapped block's packed
        output: (L, rows, M) transposes to (rows, L*M) and the per-lane
        match counts become one validity mask — the row decode is then
        identical to the flat path (no per-lane python)."""
        Ln, rows, Mm = ipack.shape
        n_l = ipack[:, 0, 0]
        ip2 = np.swapaxes(ipack, 0, 1).reshape(rows, Ln * Mm)
        fp2 = (np.swapaxes(fpack, 0, 1).reshape(fpack.shape[1], Ln * Mm)
               if fpack is not None else None)
        base = (np.arange(Mm)[None, :] < n_l[:, None]).reshape(-1)
        return self._unpack_rows(ip2, fp2, base)

    def _unpack_block(self, ipack, fpack, n: int):
        """Columnar match table from one flat block's packed output."""
        return self._unpack_rows(ipack, fpack,
                                 np.arange(ipack.shape[1]) < n)

    def _unpack_rows(self, ipack, fpack, base_valid):
        with self.rt.span("scatter", plan=self.name):
            if self.kernel.having is not None:
                valid = base_valid & (ipack[1] != 0)
                ii = 2
            else:
                valid = base_valid
                ii = 1
            if not valid.any():
                return None
            # unpack columns in out_names order (columnar, no per-row python):
            # f32 rows are bitcast into the i32 pack, f64 rows (f64 mode) come
            # from the float pack, i64 as hi/lo row pairs
            row = {}
            fi = 0
            for nm in self.kernel.out_names:
                dt = np.dtype(self.kernel.out_dtypes[nm])
                if dt == np.float64:
                    row[nm] = fpack[fi]; fi += 1
                elif dt == np.float32:
                    row[nm] = ipack[ii].view(np.float32); ii += 1
                elif dt == np.int64:
                    row[nm] = join64_np(ipack[ii], ipack[ii + 1]); ii += 2
                else:
                    row[nm] = ipack[ii]; ii += 1
            tss = row["__timestamp__"][valid].astype(np.int64) + self._ts_base
            seqs = row["__seq__"][valid].astype(np.int64) + self._seq_base
            hseqs = row["__head_seq__"][valid]
            self._last_qids = (row["__qid__"][valid]
                               if self.kernel.emit_qid else None)
            data = {}
            for nm, t in zip(self._names, self._types):
                col = row[nm][valid]
                if t == ast.AttrType.BOOL:
                    col = col != 0
                data[nm] = col.astype(dtype_of(t))
            nulls = {}
            for nm, ref in self.kernel.null_outputs.items():
                pres = row.get(f"__present__.{ref}")
                if pres is not None:
                    mask = pres[valid] == 0
                    if mask.any():
                        nulls[nm] = mask
            return (tss, seqs, hseqs, data, nulls, self._last_qids)

    def _rows_to_batches(self, chunks: list) -> list:
        """chunks: list of (tss, seqs, hseqs, data) columnar match tables."""
        with self.rt.span("scatter", plan=self.name):
            chunks = [c for c in chunks if c is not None]
            if not chunks or self.events_for == ast.OutputEventsFor.EXPIRED:
                return []
            if self.broadcast_events:
                raise RuntimeError("multi-query plans use finalize_multi()")
            tss = np.concatenate([c[0] for c in chunks])
            seqs = np.concatenate([c[1] for c in chunks])
            hseqs = np.concatenate([c[2] for c in chunks])
            data = {nm: np.concatenate([c[3][nm] for c in chunks])
                    for nm in self._names}
            nulls_all = {}
            if any(c[4] for c in chunks):
                for nm in self._names:
                    parts = [c[4].get(nm, np.zeros(len(c[0]), bool))
                             for c in chunks]
                    m = np.concatenate(parts)
                    if m.any():
                        nulls_all[nm] = m
            # emit in completion order; same-event ties by head arrival
            # (reference emits pending-list == arrival order)
            o = np.lexsort((hseqs, seqs))
            if self.offset:
                o = o[self.offset:]
            if self.limit is not None:
                o = o[:self.limit]
            if not len(o):
                return []
            cols = {nm: data[nm][o] for nm in self._names}
            nulls = {nm: m[o] for nm, m in nulls_all.items()} or None
            batch = EventBatch(self.out_schema, tss[o].astype(TIMESTAMP_DTYPE),
                               cols, len(o), seqs[o], nulls)
            return [OutputBatch(self.output_target, batch)]

    def finalize_multi(self):
        """Multi-query mode: drain buffered events and return the raw
        columnar match table (tss, seqs, hseqs, data, qids) — the outer
        MultiQueryDevicePatternPlan routes rows per lane."""
        chunks = list(getattr(self, "_tick_chunks", ()) or ())
        self._tick_chunks = []
        chunks += [c for c in self._finalize_chunks() if c is not None]
        chunks = [c for c in chunks if c is not None]
        if not chunks:
            return None
        tss = np.concatenate([c[0] for c in chunks])
        seqs = np.concatenate([c[1] for c in chunks])
        hseqs = np.concatenate([c[2] for c in chunks])
        data = {nm: np.concatenate([c[3][nm] for c in chunks])
                for nm in self._names}
        qids = np.concatenate([c[5] for c in chunks])
        return (tss, seqs, hseqs, data, qids)

    # -- timers (absent-state deadlines) ---------------------------------

    def _anchor_ms(self) -> int:
        """START-state arm time for init-slot chains (host parity:
        matcher.start at first finalize/next_wakeup with rt.now_ms(), or
        the earliest buffered event time in pre-clock playback)."""
        if self._start_anchor is None:
            now = self.rt.now_ms()
            if self.rt._playback and self.rt._clock_ms is None \
                    and self._buffered:
                now = min(int(b.timestamps.min())
                          for _s, b in self._buffered)
            self._start_anchor = int(now)
        return self._start_anchor

    def next_wakeup(self) -> Optional[int]:
        if (self.spec.needs_init_slot and self._init_on_tick
                and self._ts_base is None):
            # pre-registered absent head, no block run yet: the first
            # deadline is anchor + waiting (host: matcher.start then
            # next_wakeup)
            ws = [n.waiting_ms for n in self.spec.positions[0].nodes
                  if n.kind == "absent" and n.waiting_ms is not None]
            if ws:
                return self._anchor_ms() + min(ws)
        return self._next_deadline

    def on_timer(self, now_ms: int) -> list:
        """Fire pending absent-state deadlines <= now via a 1-step tick
        block (valid=False cells with the timer's timestamp)."""
        if not self.kernel.has_absent:
            return []
        if self._ts_base is None:
            if not (self.spec.needs_init_slot and self._init_on_tick):
                return []
            w = self.next_wakeup()
            if w is None or now_ms < w:
                return []
            # first activity is a timer: anchor the offset bases so the
            # tick block can arm the init slots and fire their deadlines
            self._ts_base = self._anchor_ms()
            self._seq_base = 0
        elif self._next_deadline is None or now_ms < self._next_deadline:
            return []
        import jax.numpy as jnp
        T = 1
        GW = 1 if self.broadcast_events else self.P
        ev = {"__ts__": np.full((T, GW),
                                np.clip(now_ms - self._ts_base, -LOCAL_SPAN,
                                        LOCAL_SPAN), _I32),
              "__seq__": np.full((T, GW),
                                 np.clip(self._last_seq - self._seq_base,
                                         -LOCAL_SPAN, LOCAL_SPAN), _I32),
              "__valid__": np.zeros((T, GW), bool),
              "__tick__": np.ones((T, GW), bool)}
        if self.spec.needs_init_slot and self._init_on_tick:
            ev["__anchor__"] = np.int32(np.clip(
                self._anchor_ms() - self._ts_base, -LOCAL_SPAN, LOCAL_SPAN))
        if len(self.spec.stream_ids) > 1:
            ev["__scode__"] = np.full((T, GW), -1, _I32)
        for si, attr, t in self._grid_attrs:
            ev[f"{si}.{attr}"] = np.zeros((T, GW), self._np_dtype(t))
        ev["__base_ts__"] = np.int64(self._ts_base)
        ev["__base_seq__"] = np.int64(self._seq_base)
        chunks = self._run_chunks([(ev, T)])
        if self.broadcast_events:
            self._tick_chunks = [c for c in chunks if c is not None]
            return []
        return self._rows_to_batches(chunks)

    # -- snapshot ------------------------------------------------------------

    def state_dict(self) -> dict:
        st = jax.tree_util.tree_map(np.asarray, self.state)
        d = {"state": st, "key_to_part": dict(self._key_to_part),
             "ts_base": self._ts_base, "seq_base": self._seq_base,
             "next_deadline": self._next_deadline,
             "last_seq": self._last_seq,
             "start_anchor": self._start_anchor}
        if self._chunk_cfg is not None:
            # chunked mode keeps no device state: continuity lives in the
            # replayed tail + the last-emitted completion seq (per lane
            # for partitioned grids, plus single-arm resolution flags)
            d["chunk_tail"] = self._tail
            d["chunk_prev_last_seq"] = self._prev_last_seq
            d["chunk_of_dropped"] = self._of_dropped
            d["lane_tail"] = self._lane_tail
            d["lane_prev"] = np.asarray(self._lane_prev)
            d["arm_done"] = (np.asarray(self._arm_done)
                             if self._arm_done is not None else None)
        return d

    def load_state_dict(self, d: dict) -> None:
        import jax.numpy as jnp
        if self._pipe is not None:
            self._pipe.take_all()   # in-flight results predate the restore
        st = d["state"]
        a, p = st["occ"].shape
        if self.mesh is not None:
            nd = len(self.mesh.devices)
            p_r = -(-p // nd) * nd
            if p_r != p:       # snapshot from a differently-sized mesh/host
                kern = NFAKernel(self.spec, self.kernel.sel_fns,
                                 self.kernel.having, p_r, a, self.kernel.E,
                                 f64=self.f64, playback=self.rt._playback,
                                 params=self.kernel.params,
                                 emit_qid=self.kernel.emit_qid,
                                 init_on_tick=self._init_on_tick)
                fresh = jax.tree_util.tree_map(np.asarray, kern.init_state())
                st = jax.tree_util.tree_map(
                    lambda o, f: np.concatenate(
                        [o, f[..., o.shape[-1]:]], axis=-1)
                    if np.ndim(o) else o, dict(st), fresh)
                p = p_r
        if p != self.P or a != self.kernel.A:  # snapshot taken after growth
            self.kernel = NFAKernel(self.spec, self.kernel.sel_fns,
                                    self.kernel.having, p, a, self.kernel.E,
                                    f64=self.f64, playback=self.rt._playback,
                                    params=self.kernel.params,
                                    emit_qid=self.kernel.emit_qid,
                                    init_on_tick=self._init_on_tick)
            self.P = p
        self.state = self._shard(st)
        self._key_to_part = dict(d["key_to_part"])
        self._key_table = None      # a cache of the dict: refilled by misses
        self._ts_base = d.get("ts_base")
        self._seq_base = d.get("seq_base")
        self._start_anchor = d.get("start_anchor")
        # legacy snapshots (no last_seq) fall back to the seq base — a
        # deadline fired before the next batch must not emit seq 0-based
        self._last_seq = int(d["last_seq"] if d.get("last_seq") is not None
                             else (d.get("seq_base") or 0))
        self._of_slots_seen = int(np.asarray(st["of_slots"]).sum())
        # pending absent-state deadlines must survive the restore, or the
        # scheduler never wakes to fire them; older snapshots (no key)
        # recompute the earliest armed deadline from the restored dl rows
        if "next_deadline" in d:
            self._next_deadline = d["next_deadline"]
        elif self.kernel.has_absent and st["dl"].size \
                and self._ts_base is not None:
            live = (st["occ"] > 0) & (st["occ"] <= self.spec.S)
            dls = np.where(live[None], st["dl"], np.int32(2**31 - 1))
            dlm = int(dls.min()) if dls.size else 2**31 - 1
            self._next_deadline = (None if dlm >= 2**31 - 1
                                   else self._ts_base + dlm)
        else:
            self._next_deadline = None
        if self._chunk_cfg is not None and "chunk_prev_last_seq" in d:
            self._tail = d.get("chunk_tail")
            self._prev_last_seq = int(d["chunk_prev_last_seq"])
            self._of_dropped = int(d.get("chunk_of_dropped", 0))
            self._lane_tail = d.get("lane_tail")
            if d.get("lane_prev") is not None:
                self._lane_prev = np.asarray(d["lane_prev"],
                                             dtype=np.int64)
            if d.get("arm_done") is not None:
                self._arm_done = np.asarray(d["arm_done"], dtype=bool)
