"""Device (TPU) window-join plan — batched probe of the opposite window.

Reference semantics (core:query/input/stream/join/JoinProcessor.java:62-126):
each arriving event, after its side's filters, probes the OPPOSITE side's
current window content with the `on` condition and emits one joined event
per match, in arrival order; outer joins emit null-filled rows for probes
with no match; `unidirectional` restricts which side triggers.

TPU-first reformulation: the per-event probe loop becomes ONE dense
(T_probe, N_other) boolean grid per micro-batch —

  * window membership "as of the probing event" is rank arithmetic:
    an opposite event with in-window position p is visible to probe a iff
    nlt(a) - M <= p < nlt(a), where nlt(a) counts opposite arrivals before
    a (mirror prefix + passed in-batch arrivals with smaller seq) and M is
    the opposite window length — the in-batch evolution of both windows is
    captured exactly, with no sequential loop;
  * the `on` condition (equality keys AND residuals alike) evaluates over
    the broadcast (T, N) grid in one fused pass — at micro-batch scale the
    dense grid saturates the VPU and needs no index structure;
  * matched pairs compact to (a_idx, b_idx) index pairs via the standard
    count-then-compact idiom (capacity-doubling retry; the kernel is
    STATELESS, so a retry is a plain re-dispatch);
  * only pair indices, miss bitmasks, filter bitmasks, and device-computed
    selector columns travel back — pass-through outputs gather host-side
    from the window mirror + batch columns (fewer bytes pulled).

The window contents are mirrored host-side (bounded by the window length):
the mirror is both the device upload for the next block and the source for
pass-through output materialization, so the kernel carries NO persistent
device state (snapshot = the mirrors).

Supported: stream-stream joins where both sides are windowless or carry
#window.length(N), any device-compilable `on`/filters/projection,
inner/left/right/full outer, unidirectional.  Everything else (time
windows — their expiry rides the host scheduler —, tables, aggregations,
named windows, group-by/order-by/limit/rate/having) raises
DeviceJoinUnsupported -> the host interp plan takes over.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..query import ast
from .batch import EventBatch
from .expr import (ExprError, MultiStreamContext, SingleStreamContext,
                   compile_expression, compute_dtypes, F32_MODE, jnp_dtype)
from .planner import (OutputBatch, PlanError, QueryPlan,
                      selector_has_aggregators)
from .nfa_device import _hi32, _lo32, join64_np, pow2_at_least as pow2
from .telemetry import call_kernel, env_nbytes
from .schema import StreamSchema, TIMESTAMP_DTYPE, dtype_of

_I32 = jnp.int32


class DeviceJoinUnsupported(Exception):
    """Join shape needs the host interp plan."""


class _Side:
    """One join side: schema, length window, compiled filters, mirror."""

    def __init__(self, inp: ast.SingleInputStream, rt):
        if inp.stream_id in rt.tables or inp.stream_id in rt.aggregations \
                or inp.stream_id in getattr(rt, "named_windows", {}):
            raise DeviceJoinUnsupported("table/aggregation/named-window side")
        if inp.stream_id not in rt.schemas:
            raise PlanError(f"join: unknown stream {inp.stream_id!r}")
        self.ref = inp.alias
        self.stream_id = inp.stream_id
        self.schema = rt.schemas[inp.stream_id]
        for h in inp.handlers:
            if isinstance(h, ast.StreamFunction):
                raise DeviceJoinUnsupported("stream function on join side")
        self.win_len = 0                   # 0 = windowless (retains nothing)
        if inp.window is not None:
            w = inp.window
            if w.namespace is not None or w.name.lower() != "length":
                raise DeviceJoinUnsupported(f"window {w.name!r} on join side")
            if len(w.args) != 1 or not isinstance(w.args[0], ast.Constant):
                raise DeviceJoinUnsupported("non-constant window length")
            self.win_len = int(w.args[0].value)
            if self.win_len <= 0 or self.win_len > (1 << 16):
                raise DeviceJoinUnsupported("window length out of range")
        ctx = SingleStreamContext(self.schema, rt.strings, alias=self.ref)
        try:
            self.filters = [compile_expression(f.expr, ctx)
                            for f in inp.filters]
        except ExprError as e:
            raise DeviceJoinUnsupported(f"filter: {e}")
        for ce in self.filters:
            if ce.type != ast.AttrType.BOOL:
                raise DeviceJoinUnsupported("non-boolean side filter")
        # host mirror of the window content, right-packed, columnar
        self.mirror_cols = {a.name: np.empty(0, dtype=dtype_of(a.type))
                            for a in self.schema.attributes}
        self.mirror_ts = np.empty(0, dtype=np.int64)
        self.mirror_seq = np.empty(0, dtype=np.int64)

    @property
    def mirror_n(self) -> int:
        return len(self.mirror_ts)

    def update_mirror(self, batch_cols, batch_ts, batch_seq, passed) -> None:
        if self.win_len == 0:
            return
        for k in self.mirror_cols:
            self.mirror_cols[k] = np.concatenate(
                [self.mirror_cols[k], batch_cols[k][passed]])[-self.win_len:]
        self.mirror_ts = np.concatenate(
            [self.mirror_ts, batch_ts[passed]])[-self.win_len:]
        self.mirror_seq = np.concatenate(
            [self.mirror_seq, batch_seq[passed]])[-self.win_len:]

    def state(self) -> dict:
        return {"cols": {k: v.copy() for k, v in self.mirror_cols.items()},
                "ts": self.mirror_ts.copy(), "seq": self.mirror_seq.copy()}

    def restore(self, st: dict) -> None:
        self.mirror_cols = {k: np.asarray(v) for k, v in st["cols"].items()}
        self.mirror_ts = np.asarray(st["ts"], dtype=np.int64)
        self.mirror_seq = np.asarray(st["seq"], dtype=np.int64)


class DeviceJoinPlan(QueryPlan):
    """`from A#window.length(N) as a join B#window.length(M) as b
    on <cond> select ... insert into O` as one dense device probe grid."""

    def __init__(self, name: str, rt, q: ast.Query,
                 inp: ast.JoinInputStream, target: Optional[str]):
        self.name = name
        self.rt = rt
        self.output_target = target
        self.events_for = getattr(q.output, "events_for",
                                  ast.OutputEventsFor.CURRENT)
        if q.rate is not None:
            raise DeviceJoinUnsupported("output rate limiting")
        sel = q.selector
        if sel.group_by or sel.order_by or sel.having is not None \
                or selector_has_aggregators(sel):
            raise DeviceJoinUnsupported("group-by/order-by/having selector")
        if inp.per is not None or inp.within is not None:
            raise DeviceJoinUnsupported("within/per (aggregation join)")
        self.limit, self.offset = sel.limit, sel.offset
        if self.limit is not None or self.offset:
            raise DeviceJoinUnsupported("limit/offset")

        self.left = _Side(inp.left, rt)
        self.right = _Side(inp.right, rt)
        if self.left.ref == self.right.ref:
            raise PlanError(f"join {name!r}: both sides named "
                            f"{self.left.ref!r}; alias one with `as`")
        self.join_type = inp.join_type
        self.trigger = inp.trigger          # "all" | "left" | "right"

        schemas = {self.left.ref: self.left.schema,
                   self.right.ref: self.right.schema}
        ctx = MultiStreamContext(schemas, rt.strings)
        self.on = None
        if inp.on is not None:
            try:
                self.on = compile_expression(inp.on, ctx)
            except ExprError as e:
                raise DeviceJoinUnsupported(f"on: {e}")
            if self.on.type != ast.AttrType.BOOL:
                raise DeviceJoinUnsupported("non-boolean on condition")

        # selector: pass-through outputs gather host-side; computed ones
        # evaluate on device over the matched pairs
        from ..interp.joins import _join_selector
        sel = _join_selector(sel, self)
        names, types, fns, passthrough = [], [], [], []
        for oa in sel.attributes:
            try:
                ce = compile_expression(oa.expr, ctx)
            except ExprError as e:
                raise DeviceJoinUnsupported(f"selector: {e}")
            names.append(oa.name)
            types.append(ce.type)
            fns.append(ce)
            if ce.is_var:
                passthrough.append(next(iter(ce.reads)))
            else:
                passthrough.append(None)
        self._names, self._types, self._fns = names, types, fns
        self._passthrough = passthrough
        self.out_schema = StreamSchema(target or f"#{name}", tuple(
            ast.Attribute(n, t) for n, t in zip(names, types)))
        # miss rows (outer joins): evaluated via host closures (null side)
        self._py_sel = None
        if any(pt is None for pt in passthrough) and self._any_outer():
            from ..interp.expr import PyExprContext, compile_py
            pctx = PyExprContext(schemas, tables=rt.tables)
            try:
                self._py_sel = [compile_py(oa.expr, pctx)[0]
                                for oa in sel.attributes]
            except Exception:
                raise DeviceJoinUnsupported(
                    "outer-join selector not host-evaluable for miss rows")

        self.input_streams = tuple({self.left.stream_id,
                                    self.right.stream_id})
        from .pipeline import DispatchPipeline
        self._mode = F32_MODE       # device DOUBLE policy (f32 compute)
        self._buffered: list = []
        self._fn_cache: dict = {}
        self._m_hint = 16
        # side filters force a sync per flush (the mirror update needs the
        # device-evaluated pass masks); filter-less joins pipeline
        self._can_pipeline = not (self.left.filters or self.right.filters)
        self.pipeline_depth = rt.geometry["pipeline_depth"][0] \
            if self._can_pipeline else 0
        self._pipe = DispatchPipeline(name, self._materialize,
                                      depth=self.pipeline_depth)
        # build-time trace so unsupported expressions fail at plan time
        # (eval_shape: no compile, no device)
        self._shape_check()

    def _shape_check(self) -> None:
        TL = TR = 8
        NL, NR = max(self.left.win_len, 1), max(self.right.win_len, 1)

        def dummy(side, T, N):
            ev = {"valid": np.zeros(T, bool), "ts64": np.zeros(T, np.int64),
                  "seq": np.zeros(T, np.int64), "bT": np.int32(T),
                  "mirror_n": np.int32(0)}
            for a in side.schema.attributes:
                dt = self._np_dtype(a.type)
                ev[a.name] = np.zeros(T, dtype=dt)
                ev[f"m.{a.name}"] = np.zeros(N, dtype=dt)
            return ev
        fn = self._block_fn(TL, TR, NL, NR, 16)
        jax.eval_shape(fn, dummy(self.left, TL, NL),
                       dummy(self.right, TR, NR))

    def _any_outer(self) -> bool:
        return self.join_type in (ast.JoinType.LEFT_OUTER,
                                  ast.JoinType.RIGHT_OUTER,
                                  ast.JoinType.FULL_OUTER)

    def _outer_for(self, side_name: str) -> bool:
        return (self.join_type == ast.JoinType.FULL_OUTER
                or (self.join_type == ast.JoinType.LEFT_OUTER
                    and side_name == "left")
                or (self.join_type == ast.JoinType.RIGHT_OUTER
                    and side_name == "right"))

    # -- kernel ----------------------------------------------------------

    def _np_dtype(self, t):
        if t == ast.AttrType.DOUBLE:
            return np.float32
        return dtype_of(t)

    def _block_fn(self, TL, TR, NL, NR, M):
        key = (TL, TR, NL, NR, M)
        fn = self._fn_cache.get(key)
        if fn is not None:
            return fn
        left, right = self.left, self.right
        on, mode = self.on, self._mode
        fns, passthrough = self._fns, self._passthrough
        types = self._types
        trig, jt = self.trigger, self.join_type
        outer_l, outer_r = self._outer_for("left"), self._outer_for("right")

        def bits32(m):
            n_ = m.shape[0]
            padded = -(-n_ // 32) * 32
            if padded != n_:
                m = jnp.concatenate([m, jnp.zeros(padded - n_, bool)])
            r = m.reshape(-1, 32).astype(jnp.uint32)
            w = (r << jnp.arange(32, dtype=jnp.uint32)[None, :]) \
                .sum(axis=1).astype(jnp.uint32)
            return jax.lax.bitcast_convert_type(w, jnp.int32)

        def side_pass(side, ev, T):
            m = ev["valid"]
            for ce in side.filters:
                env = {}
                for a in side.schema.attributes:     # unqualified + ref.
                    env[a.name] = ev[a.name]
                    env[f"{side.ref}.{a.name}"] = ev[a.name]
                env["__timestamp__"] = ev["ts64"]
                m = m & jnp.broadcast_to(ce.fn(env), (T,))
            return m

        def probes(probe, other, p_ev, o_ev, p_pass, o_pass, NO, Mw):
            """pairs (T, NO + T_other) grid: probe side vs other's window."""
            Lo = o_ev["mirror_n"]                      # i32 scalar
            # opposite union: [mirror slots (NO cap) | other batch]
            def ucol(name):
                return jnp.concatenate([o_ev[f"m.{name}"], o_ev[name]])
            # position of each union entry in the other side's arrival
            # order (mirror first, then passed batch events by rank)
            rankb = jnp.cumsum(o_pass.astype(_I32)) - o_pass
            b_pos = jnp.concatenate(
                [jnp.arange(NO, dtype=_I32), Lo + rankb])
            b_valid = jnp.concatenate(
                [jnp.arange(NO, dtype=_I32) < Lo, o_pass])
            # arrivals of `other` strictly before each probe event
            nlt = Lo + jnp.sum(
                (o_pass[None, :] & (o_ev["seq"][None, :]
                                    < p_ev["seq"][:, None])).astype(_I32),
                axis=1)                                 # (T,)
            member = b_valid[None, :] & (b_pos[None, :] < nlt[:, None])
            if Mw > 0:
                member = member & (b_pos[None, :]
                                   >= nlt[:, None] - jnp.int32(Mw))
            else:
                member = jnp.zeros_like(member)         # windowless: empty
            grid = member
            if on is not None:
                env = {}
                for a in probe.schema.attributes:
                    env[f"{probe.ref}.{a.name}"] = p_ev[a.name][:, None]
                for a in other.schema.attributes:
                    env[f"{other.ref}.{a.name}"] = ucol(a.name)[None, :]
                env["__timestamp__"] = p_ev["ts64"][:, None]
                grid = grid & jnp.broadcast_to(on.fn(env), member.shape)
            return grid & p_pass[:, None]

        def compact_pairs(grid, cap):
            flat = grid.reshape(-1)
            n = jnp.sum(flat, dtype=_I32)
            pos = jnp.cumsum(flat.astype(_I32)) - flat
            wpos = jnp.where(flat, jnp.minimum(pos, cap - 1), cap)
            idx = jnp.full((cap,), -1, _I32).at[wpos].set(
                jnp.arange(flat.shape[0], dtype=_I32), mode="drop")
            return n, idx                       # flat grid index per pair

        def computed_cols(probe, other, p_ev, o_ev, NO, flat_idx, width):
            """Device-computed selector columns for compacted pairs."""
            a_idx = flat_idx // width
            b_idx = flat_idx % width
            safe_a = jnp.maximum(a_idx, 0)
            safe_b = jnp.maximum(b_idx, 0)
            env = {}
            for a in probe.schema.attributes:
                env[f"{probe.ref}.{a.name}"] = p_ev[a.name][safe_a]
            for a in other.schema.attributes:
                u = jnp.concatenate([o_ev[f"m.{a.name}"], o_ev[a.name]])
                env[f"{other.ref}.{a.name}"] = u[safe_b]
            env["__timestamp__"] = p_ev["ts64"][safe_a]
            cols = {}
            for nm, ce, pt, t in zip(self._names, fns, passthrough, types):
                if pt is not None:
                    continue
                v = ce.fn(env)
                cols[nm] = jnp.broadcast_to(v, (flat_idx.shape[0],))
            return a_idx, b_idx, cols

        def block(lev, rev):
            with compute_dtypes(mode):
                pl = side_pass(left, lev, TL)
                pr = side_pass(right, rev, TR)
                out = {"pl": bits32(pl), "pr": bits32(pr)}  # packed below
                widthL = NR + TR        # left probes right's union
                widthR = NL + TL
                gl = probes(left, right, lev, rev, pl, pr, NR,
                            right.win_len) if trig in ("all", "left") \
                    else jnp.zeros((TL, NR + TR), bool)
                gr = probes(right, left, rev, lev, pr, pl, NL,
                            left.win_len) if trig in ("all", "right") \
                    else jnp.zeros((TR, NL + TL), bool)
                nL, idxL = compact_pairs(gl, M)
                nR, idxR = compact_pairs(gr, M)
                aL, bL, colsL = computed_cols(left, right, lev, rev, NR,
                                              idxL, widthL)
                aR, bR, colsR = computed_cols(right, left, rev, lev, NL,
                                              idxR, widthR)
                # EVERYTHING packs into ONE i32 vector: every pull has a
                # fixed cost, so one result = one pull
                irows = [jnp.stack([nL, nR, jnp.int32(M), jnp.int32(0)]),
                         out["pl"], out["pr"]]
                if trig in ("all", "left") and outer_l:
                    irows.append(bits32(pl & ~gl.any(axis=1)))
                if trig in ("all", "right") and outer_r:
                    irows.append(bits32(pr & ~gr.any(axis=1)))
                irows += [aL, bL, aR, bR]
                frows = []
                for nm, t in zip(self._names, types):
                    for cols in (colsL, colsR):
                        if nm not in cols:
                            continue
                        v = cols[nm]
                        if v.dtype in (jnp.float32,):
                            irows.append(jax.lax.bitcast_convert_type(
                                v, jnp.int32))
                        elif v.dtype == jnp.float64:
                            frows.append(v)
                        elif v.dtype == jnp.int64:
                            irows.append(_hi32(v))
                            irows.append(_lo32(v))
                        else:
                            irows.append(v.astype(_I32))
                res = {"i": jnp.concatenate([r.reshape(-1)
                                             for r in irows])}
                if frows:
                    res["f"] = jnp.stack(frows)
                return res

        fn = jax.jit(block)
        self._fn_cache[key] = fn
        return fn

    # -- QueryPlan interface ---------------------------------------------

    def process(self, stream_id: str, batch: EventBatch) -> list:
        if batch.n:
            self._buffered.append((stream_id, batch))
        return []

    def _side_arrays(self, side: _Side, bufs):
        """Concatenate this side's buffered batches into (T,) arrays."""
        mine = [b for sid, b in bufs if sid == side.stream_id]
        n = sum(b.n for b in mine)
        cols = {}
        for a in side.schema.attributes:
            # ORIGINAL dtype: pass-through outputs gather from these
            # host-side at full precision; the device upload (ev_of)
            # downcasts its own padded copies (f32 DOUBLE policy)
            dt = dtype_of(a.type)
            col = np.empty(n, dtype=dt)
            o = 0
            for b in mine:
                col[o:o + b.n] = b.columns[a.name]
                o += b.n
            cols[a.name] = col
        ts = np.concatenate([b.timestamps for b in mine]) if mine \
            else np.empty(0, np.int64)
        seq = np.concatenate(
            [b.seqs if b.seqs is not None else np.arange(b.n)
             for b in mine]) if mine else np.empty(0, np.int64)
        order = np.argsort(seq, kind="stable")
        return ({k: v[order] for k, v in cols.items()}, ts[order],
                seq[order], n)

    # degradation-ladder contract: finalize restores its input buffer on
    # a dispatch failure (so the runtime may retry with a halved flush);
    # once mirrors advance the flush passed its point of no return and
    # _finalize_retry_ok drops, forcing propagation instead of a retry
    # that would double-advance window mirrors
    retryable_finalize = True

    def finalize(self) -> list:
        if not self._buffered:
            return []
        snapshot = list(self._buffered)
        self._finalize_retry_ok = True
        try:
            return self._finalize_impl()
        except Exception:
            if self._finalize_retry_ok:
                self._buffered = snapshot
            raise

    def _finalize_impl(self) -> list:
        bufs, self._buffered = self._buffered, []
        with self.rt.span("host_build", plan=self.name):
            lc, lts, lseq, ln = self._side_arrays(self.left, bufs)
            rc, rts, rseq, rn = self._side_arrays(self.right, bufs)
        if ln == 0 and rn == 0:
            return []
        TL, TR = pow2(max(ln, 1)), pow2(max(rn, 1))
        NL = max(self.left.win_len, 1)
        NR = max(self.right.win_len, 1)

        def ev_of(side, cols, ts, seq, n, T, N):
            ev = {"valid": np.zeros(T, bool),
                  "ts64": np.zeros(T, np.int64),
                  "seq": np.zeros(T, np.int64),
                  "bT": np.int32(T), "mirror_n": np.int32(side.mirror_n)}
            ev["valid"][:n] = True
            ev["ts64"][:n] = ts
            ev["seq"][:n] = seq
            ev["seq"][n:] = np.int64(2**62)    # padding: after everything
            for a in side.schema.attributes:
                dt = self._np_dtype(a.type)
                col = np.zeros(T, dtype=dt)
                col[:n] = cols[a.name]
                ev[a.name] = col
                mc = np.zeros(N, dtype=dt)
                mc[:side.mirror_n] = side.mirror_cols[a.name].astype(dt)
                ev[f"m.{a.name}"] = mc
            return ev

        lev = ev_of(self.left, lc, lts, lseq, ln, TL, NL)
        rev = ev_of(self.right, rc, rts, rseq, rn, TR, NR)
        entry = self._dispatch(lev, rev, TL, TR, NL, NR,
                               dict(lc=lc, rc=rc, lts=lts, rts=rts,
                                    lseq=lseq, rseq=rseq, ln=ln, rn=rn))
        if self._can_pipeline:
            # no side filters: every valid event passes — mirrors advance
            # host-side immediately, so the next flush needs NO sync.
            # The pipeline then defers the blocking pull: depth-D across
            # flushes, and within one dispatch round the runtime collects
            # AFTER every other device plan has dispatched (overlap)
            self._finalize_retry_ok = False     # mirrors advance now
            self.left.update_mirror(lc, lts, lseq, np.ones(ln, bool))
            self.right.update_mirror(rc, rts, rseq, np.ones(rn, bool))
            return self._pipe.push(entry)
        rows = self._materialize(entry, update_mirrors=True)
        return rows

    def _dispatch(self, lev, rev, TL, TR, NL, NR, meta, M=None,
                  mirror_snap=None) -> dict:
        # dispatch-boundary fault injection: raising here (before any
        # mirror advance) keeps the flush retryable
        self.rt.inject("dispatch", self.name)
        M = M if M is not None else max(self._m_hint, 16)
        prof = self.rt.profiler
        if not self.rt.stats.enabled and prof is None:
            res = self._block_fn(TL, TR, NL, NR, M)(lev, rev)
        else:
            hit = (TL, TR, NL, NR, M) in self._fn_cache
            fn = self._block_fn(TL, TR, NL, NR, M)
            res = call_kernel(
                self.rt.stats, self.name, fn, (lev, rev), cache_hit=hit,
                nbytes=env_nbytes(lev) + env_nbytes(rev), prof=prof)
        from .pipeline import start_d2h
        start_d2h(res)      # start the D2H pull while the device computes
        # snapshot the mirrors the probe actually saw: with pipelining
        # (and overflow retries) they advance before the entry
        # materializes, so a fresh snapshot would gather wrong values
        if mirror_snap is None:
            mirror_snap = {}
            for key, side in (("L", self.left), ("R", self.right)):
                mirror_snap[key] = (
                    {k: v.copy() for k, v in side.mirror_cols.items()},
                    side.mirror_n)
        return {"res": res, "lev": lev, "rev": rev, "TL": TL, "TR": TR,
                "NL": NL, "NR": NR, "M": M, "meta": meta,
                "mirror_snap": mirror_snap}

    def _materialize(self, entry: dict, update_mirrors: bool = False) -> list:
        while True:
            with self.rt.span("transfer", plan=self.name):
                ipack = np.asarray(entry["res"]["i"])      # ONE pull
            nL, nR = int(ipack[0]), int(ipack[1])
            M = entry["M"]
            if max(nL, nR) <= M:
                break
            entry = self._dispatch(entry["lev"], entry["rev"], entry["TL"],
                                   entry["TR"], entry["NL"], entry["NR"],
                                   entry["meta"],
                                   M=pow2(max(nL, nR), lo=32),
                                   mirror_snap=entry["mirror_snap"])
        self._m_hint = max(self._m_hint, entry["M"])
        fpack = np.asarray(entry["res"]["f"]) if "f" in entry["res"]             else None
        me = entry["meta"]
        TL, TR, M = entry["TL"], entry["TR"], entry["M"]
        ln, rn = me["ln"], me["rn"]
        off = [4]

        def take(n):
            v = ipack[off[0]:off[0] + n]
            off[0] += n
            return v
        pl = _unbits(take(-(-TL // 32)), TL)[:ln]
        pr = _unbits(take(-(-TR // 32)), TR)[:rn]
        missL = missR = None
        if self.trigger in ("all", "left") and self._outer_for("left"):
            missL = _unbits(take(-(-TL // 32)), TL)[:ln]
        if self.trigger in ("all", "right") and self._outer_for("right"):
            missR = _unbits(take(-(-TR // 32)), TR)[:rn]
        aL, bL, aR, bR = take(M), take(M), take(M), take(M)
        comp_cols = {"L": {}, "R": {}}
        fi = 0
        for nm, t, pt in zip(self._names, self._types, self._passthrough):
            if pt is not None:
                continue
            for sk in ("L", "R"):
                dt = np.float32 if t == ast.AttrType.DOUBLE \
                    else np.dtype(jnp_dtype(t))
                if dt == np.float64:
                    comp_cols[sk][nm] = np.asarray(fpack[fi]); fi += 1
                elif dt == np.float32:
                    comp_cols[sk][nm] = take(M).view(np.float32)
                elif dt == np.int64:
                    comp_cols[sk][nm] = join64_np(take(M), take(M))
                else:
                    comp_cols[sk][nm] = take(M)
        if update_mirrors:
            # entry mirrors were pre-advance: the probe saw the old ones
            self._finalize_retry_ok = False
            self.left.update_mirror(me["lc"], me["lts"], me["lseq"], pl)
            self.right.update_mirror(me["rc"], me["rts"], me["rseq"], pr)
        return self._assemble(entry, nL, nR, aL, bL, aR, bR, comp_cols,
                              missL, missR)

    def _assemble(self, entry, nL, nR, aL, bL, aR, bR, comp_cols,
                  missL, missR) -> list:
        """Merge pair and miss rows in the reference's arrival order
        (probe seq, left-probe-first, opposite position)."""
        if self.events_for == ast.OutputEventsFor.EXPIRED:
            return []
        names, types, passthrough = self._names, self._types, self._passthrough
        me = entry["meta"]
        lc, rc = me["lc"], me["rc"]
        lts, rts, lseq, rseq = me["lts"], me["rts"], me["lseq"], me["rseq"]
        ln, rn = me["ln"], me["rn"]
        TL, TR = entry["TL"], entry["TR"]

        def union_col(side, key, cols, name, n, T):
            dt = dtype_of(side.schema.type_of(name))     # full precision
            w = max(side.win_len, 1)
            u = np.zeros(w + T, dtype=dt)
            mc, mn = entry["mirror_snap"][key]
            u[:mn] = mc[name][:mn]
            u[w:w + n] = cols[name]
            return u

        segs = []       # (sort_seq, side_rank, pos, ts, row_cols, nulls)

        def pair_rows(side_probe, side_other, okey, a_idx, b_idx, npairs,
                      p_cols, p_ts, p_seq, o_cols, o_n, o_T, side_rank,
                      comp):
            if npairs == 0:
                return
            a = a_idx[:npairs]
            b = b_idx[:npairs]
            cols_out = {}
            for nm, t, pt in zip(names, types, passthrough):
                if pt is None:
                    cols_out[nm] = comp[nm][:npairs]
                    continue
                ref, attr = pt.split(".", 1)
                if ref == side_probe.ref:
                    cols_out[nm] = p_cols[attr][a]
                else:
                    u = union_col(side_other, okey, o_cols, attr, o_n, o_T)
                    cols_out[nm] = u[b]
            segs.append((p_seq[a], np.full(npairs, side_rank, np.int8),
                         b.astype(np.int64), p_ts[a], cols_out, None))

        pair_rows(self.left, self.right, "R", aL, bL, nL, lc, lts, lseq,
                  rc, rn, TR, 0, comp_cols["L"])
        pair_rows(self.right, self.left, "L", aR, bR, nR, rc, rts, rseq,
                  lc, ln, TL, 1, comp_cols["R"])

        def miss_rows(side_probe, side_other, miss, p_cols, p_ts, p_seq,
                      side_rank):
            if miss is None:
                return
            idx = np.flatnonzero(miss)
            if idx.size == 0:
                return
            cols_out = {}
            nulls = {}
            if all(pt is not None for pt in passthrough):
                for nm, t, pt in zip(names, types, passthrough):
                    ref, attr = pt.split(".", 1)
                    if ref == side_probe.ref:
                        cols_out[nm] = p_cols[attr][idx]
                    else:
                        cols_out[nm] = np.zeros(idx.size, dtype=dtype_of(t))
                        nulls[nm] = np.ones(idx.size, bool)
            else:
                # computed outputs over a null side: host closures
                rows = []
                pnames = side_probe.schema.names
                dec = self.rt.strings._to_str
                for i in idx:
                    env = {}
                    for nm2 in pnames:
                        v = p_cols[nm2][i]
                        if side_probe.schema.type_of(nm2) \
                                == ast.AttrType.STRING:
                            c = int(v)
                            v = dec[c] if 0 <= c < len(dec) else None
                        elif isinstance(v, np.generic):
                            v = v.item()
                        env[f"{side_probe.ref}.{nm2}"] = v
                        env[nm2] = v
                    env["__timestamp__"] = int(p_ts[i])
                    for nm2 in side_other.schema.names:
                        env[f"{side_other.ref}.{nm2}"] = None
                    rows.append([f(env) for f in self._py_sel])
                for j, (nm, t) in enumerate(zip(names, types)):
                    vals = [r[j] for r in rows]
                    isnull = np.array([v is None for v in vals])
                    filled = [0 if v is None else v for v in vals]
                    if t == ast.AttrType.STRING:
                        enc = self.rt.strings.encode
                        filled = [v if isinstance(v, (int, np.integer))
                                  else enc(v) for v in filled]
                    cols_out[nm] = np.asarray(filled, dtype=dtype_of(t))
                    if isnull.any():
                        nulls[nm] = isnull
            segs.append((p_seq[idx], np.full(idx.size, side_rank, np.int8),
                         np.full(idx.size, 1 << 60, np.int64),
                         p_ts[idx], cols_out, nulls or None))

        miss_rows(self.left, self.right, missL, lc, lts, lseq, 0)
        miss_rows(self.right, self.left, missR, rc, rts, rseq, 1)

        if not segs:
            return []
        tot = sum(len(s[0]) for s in segs)
        seq_all = np.concatenate([s[0] for s in segs])
        rank_all = np.concatenate([s[1] for s in segs])
        pos_all = np.concatenate([np.asarray(s[2], np.int64) for s in segs])
        ts_all = np.concatenate([s[3] for s in segs])
        order = np.lexsort((pos_all, rank_all, seq_all))
        cols = {}
        nulls_out = {}
        for nm, t in zip(names, types):
            dt = dtype_of(t)
            parts, nparts = [], []
            for s in segs:
                v = s[4][nm]
                parts.append(np.asarray(v))
                nl = (s[5] or {}).get(nm)
                nparts.append(nl if nl is not None
                              else np.zeros(len(s[0]), bool))
            cols[nm] = np.concatenate(parts).astype(dt)[order]
            nl = np.concatenate(nparts)[order]
            if nl.any():
                nulls_out[nm] = nl
        out = EventBatch(self.out_schema,
                         ts_all[order].astype(TIMESTAMP_DTYPE), cols, tot,
                         nulls=nulls_out or None)
        return [OutputBatch(self.output_target, out)]

    # -- snapshot ---------------------------------------------------------

    def state_dict(self) -> dict:
        return {"left": self.left.state(), "right": self.right.state()}

    def load_state_dict(self, d: dict) -> None:
        self._pipe.take_all()       # in-flight results predate the restore
        self.left.restore(d["left"])
        self.right.restore(d["right"])


def _unbits(words: np.ndarray, n: int) -> np.ndarray:
    b = ((words.view(np.uint32)[:, None]
          >> np.arange(32, dtype=np.uint32)) & 1).astype(bool)
    return b.reshape(-1)[:n]
