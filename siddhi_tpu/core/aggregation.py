"""Incremental (multi-granularity) aggregation:
`define aggregation A from S select sum(price) as total group by sym
 aggregate by ts every sec ... year`.

Reference: core:aggregation/IncrementalExecutor.java:45-133 (per-duration
tumbling-bucket executor chain: seconds feed minutes feed hours ...),
AggregationRuntime.java:65-105 (duration->executor + duration->table maps),
AggregationParser.java:87, IncrementalAggregateCompileCondition.java:277
(within/per join selection), Incremental*AttributeAggregator (avg ->
(sum,count) decomposition).

TPU-first reformulation (SURVEY §5 "maps to parallel-prefix"): the chain
is replaced by **independent per-duration segmented reductions** — every
micro-batch computes (bucket, group) segment ids and reduces all base
fields with vectorized scatter-reductions (bincount / ufunc.at), then
merges the few unique segments into per-duration bucket stores.  Because
sum/count/min/max bases are associative, reducing raw events per duration
equals the reference's bucket-of-buckets cascade, with no sequential
dependency between levels — each duration is one data-parallel reduction.

Buckets are never "finalized": within/per queries read running and past
buckets uniformly (the reference merges in-memory + table state the same
way: IncrementalDataAggregator).
"""
from __future__ import annotations

import datetime as _dt
from typing import Callable, Optional

import numpy as np

from ..query import ast
from ..query.ast import AttrType, Duration
from .batch import EventBatch
from .planner import OutputBatch, PlanError, QueryPlan
from .schema import StreamSchema, StringTable, dtype_of

AGG_TIMESTAMP = "AGG_TIMESTAMP"

# base-field decomposition (reference: aggregator/incremental/
# Incremental{Sum,Count,Avg,Min,Max}AttributeAggregator)
_BASES = {
    "sum": ("sum",),
    "count": ("count",),
    "avg": ("sum", "count"),
    "min": ("min",),
    "max": ("max",),
}

_DUR_NAMES = {
    "sec": Duration.SECONDS, "seconds": Duration.SECONDS,
    "min": Duration.MINUTES, "minutes": Duration.MINUTES,
    "hour": Duration.HOURS, "hours": Duration.HOURS,
    "day": Duration.DAYS, "days": Duration.DAYS,
    "week": Duration.WEEKS, "weeks": Duration.WEEKS,
    "month": Duration.MONTHS, "months": Duration.MONTHS,
    "year": Duration.YEARS, "years": Duration.YEARS,
}


def duration_of(name: str) -> Duration:
    d = _DUR_NAMES.get(name.strip().lower())
    if d is None:
        raise PlanError(f"unknown aggregation duration {name!r}")
    return d


def parse_span_ms(text) -> int:
    """'1 hour' / '90 sec' / bare ms integer -> milliseconds."""
    s = str(text).strip()
    parts = s.split()
    if len(parts) == 2:
        return int(float(parts[0]) * duration_of(parts[1]).approx_millis)
    try:
        return int(s)
    except ValueError:
        raise PlanError(f"cannot parse retention span {text!r} "
                        f"(want e.g. '1 hour' or ms)") from None


def _parse_retention(ad: ast.AggregationDefinition) -> dict:
    """@purge on a `define aggregation` -> {Duration: retention_ms}.

    Forms (reference: @purge/@retentionPeriod on aggregations):
      @purge(retention='1 hour')            uniform retention
      @purge('1 hour')                      same, positional
      @purge(retention='1 hour', sec='2 min')   per-duration override
      @purge(enable='false', ...)           disabled
    Returns {} when absent/disabled — keep every bucket forever."""
    ann = ast.find_annotation(ad.annotations, "purge")
    if ann is None:
        return {}
    if str(ann.element("enable", "true")).lower() in ("false", "off"):
        return {}
    out: dict = {}
    default = ann.element("retention")
    if default is not None:
        for d in ad.durations:
            out[d] = parse_span_ms(default)
    seen = set()
    for name, dur in _DUR_NAMES.items():
        if dur in seen or dur not in ad.durations:
            continue
        v = ann.element(name) if len(ann.elements) > 1 or default is None \
            else None
        if v is not None and v != default:
            out[dur] = parse_span_ms(v)
            seen.add(dur)
    if not out:
        raise PlanError(
            f"aggregation {ad.id!r}: @purge needs a retention span "
            f"(e.g. @purge(retention='1 hour'))")
    return out


def bucket_starts(ts: np.ndarray, dur: Duration) -> np.ndarray:
    """Vectorized bucket start (ms) per timestamp; months/years use
    calendar boundaries via numpy datetime64 truncation (the reference
    uses Calendar arithmetic: IncrementalTimeConverterUtil)."""
    if dur == Duration.MONTHS:
        d = ts.astype("datetime64[ms]").astype("datetime64[M]")
        return d.astype("datetime64[ms]").astype(np.int64)
    if dur == Duration.YEARS:
        d = ts.astype("datetime64[ms]").astype("datetime64[Y]")
        return d.astype("datetime64[ms]").astype(np.int64)
    w = dur.approx_millis
    return (ts // w) * w


class _Site:
    """One aggregator call site in the aggregation's selector."""
    __slots__ = ("name", "key", "arg", "arg_fn", "in_type", "out_type")

    def __init__(self, name, key, arg, arg_fn, in_type, out_type):
        self.name = name          # sum/count/avg/min/max
        self.key = key            # env placeholder "__agg<i>"
        self.arg = arg            # column name if plain Variable, else None
        self.arg_fn = arg_fn      # per-row fallback evaluator
        self.in_type = in_type
        self.out_type = out_type


class AggregationRuntime(QueryPlan):
    """Ingest plan + queryable per-duration bucket store."""

    def __init__(self, rt, ad: ast.AggregationDefinition):
        from ..interp.engine import extract_aggregators
        from ..interp.expr import PyExprContext, compile_py

        self.rt = rt
        self.ad = ad
        self.name = f"#aggregation_{ad.id}"
        inp = ad.input
        if inp.stream_id not in rt.schemas:
            raise PlanError(f"aggregation {ad.id!r}: unknown input stream "
                            f"{inp.stream_id!r}")
        if inp.window is not None:
            raise PlanError(f"aggregation {ad.id!r}: windows not allowed")
        self.in_schema = rt.schemas[inp.stream_id]
        self.input_streams = (inp.stream_id,)
        self.output_target = None
        self.durations = tuple(ad.durations)
        if not self.durations:
            raise PlanError(f"aggregation {ad.id!r}: no durations")

        ctx = PyExprContext({inp.alias: self.in_schema,
                             inp.stream_id: self.in_schema},
                            default_ref=inp.alias, tables=rt.tables)
        self.filters = [compile_py(f.expr, ctx)[0] for f in inp.filters]

        # event-time source (reference: `aggregate by <attr>`)
        self.by_attr = None
        if ad.by_attribute is not None:
            self.by_attr = ad.by_attribute.attribute
            t = self.in_schema.type_of(self.by_attr)
            if t != AttrType.LONG:
                raise PlanError(f"aggregation {ad.id!r}: aggregate-by "
                                f"attribute must be long (epoch ms)")

        # group-by columns (plain variables, reference restriction)
        self.group_attrs: list[str] = []
        for g in ad.selector.group_by:
            if g.stream_ref not in (None, inp.alias, inp.stream_id):
                raise PlanError(f"aggregation {ad.id!r}: bad group-by ref")
            self.group_attrs.append(g.attribute)

        # selector: rewrite aggregator calls into placeholder sites
        if ad.selector.select_all:
            raise PlanError(f"aggregation {ad.id!r}: select * not allowed; "
                            f"name the aggregates")
        raw_sites: list = []
        rewritten: list[tuple[str, ast.Expression]] = []
        for oa in ad.selector.attributes:
            rewritten.append((oa.name,
                              extract_aggregators(oa.expr, raw_sites, ctx)))
        self.sites: list[_Site] = []
        for i, s in enumerate(raw_sites):
            if s.name not in _BASES:
                raise PlanError(
                    f"aggregation {ad.id!r}: {s.name}() has no incremental "
                    f"decomposition (reference supports sum/count/avg/min/max)")
            self.sites.append(_Site(s.name, s.key, None,
                                    s.arg_fns[0] if s.arg_fns else None,
                                    s.in_type, s.out_type))
        # plain-variable fast path for site args
        site_i = 0
        def scan_args(e):
            nonlocal site_i
            if isinstance(e, ast.FunctionCall) and e.namespace is None \
                    and e.name.lower() in _BASES:
                if len(e.args) == 1 and isinstance(e.args[0], ast.Variable) \
                        and e.args[0].attribute in self.in_schema.types:
                    self.sites[site_i].arg = e.args[0].attribute
                site_i += 1
                return
            for sub in getattr(e, "args", ()) or ():
                scan_args(sub)
            for nm in ("left", "right", "expr"):
                sub = getattr(e, nm, None)
                if isinstance(sub, ast.Expression):
                    scan_args(sub)
        for oa in ad.selector.attributes:
            scan_args(oa.expr)

        # output row evaluators over {group attrs, AGG_TIMESTAMP, __agg*}
        extra = {a: (a, self.in_schema.type_of(a)) for a in self.group_attrs}
        extra[AGG_TIMESTAMP] = (AGG_TIMESTAMP, AttrType.LONG)
        extra.update({s.key: (s.key, s.out_type) for s in self.sites})
        octx = PyExprContext({}, extra=extra, tables=rt.tables)
        self.out_fns: list = []
        names, types = [], []
        for nm, expr in rewritten:
            f, t = compile_py(expr, octx)
            self.out_fns.append(f)
            names.append(nm)
            types.append(t)
        self.out_schema = StreamSchema(ad.id, tuple(
            ast.Attribute(n, t) for n, t in zip(names, types)))

        # per-duration bucket stores:
        # (bucket_start_ms, group_key_tuple) -> [base floats ...]
        self.n_bases = sum(len(_BASES[s.name]) for s in self.sites)
        self.store: dict = {d: {} for d in self.durations}

        # @purge retention (reference: @purge/@retentionPeriod on the
        # aggregation definition): buckets whose start falls behind the
        # newest seen start minus the duration's retention are evicted
        # on ingest.  None = keep forever (and analyzer rule SA15 warns
        # when that meets an unbounded group-by).
        self.retention_ms: dict = _parse_retention(ad)
        self.evicted: dict = {d: 0 for d in self.durations}
        self._newest: dict = {d: None for d in self.durations}

        # Placement (docs/AGGREGATION.md "Device lowering"):
        #   default   device-RESIDENT plan (core/agg_device.py) — bucket
        #             state lives on device, host touch on query only;
        #   'always'  the legacy per-batch device reduce (kernel per
        #             batch, store on host) — kept for mesh sharding;
        #   'off'     host numpy path (also the forced-path differential
        #             lever).  Ineligible shapes (calendar durations,
        #             failed jax import) demote to host with a D-AGG
        #             record visible in rt.explain().
        da = ast.find_annotation(rt.app.annotations, "app:deviceAggregations")
        mode = str(da.element()).lower() if da is not None else "auto"
        calendar = (Duration.MONTHS in self.durations
                    or Duration.YEARS in self.durations)
        self.device = mode in ("always", "true") and not calendar
        self._dev_cache: dict = {}      # padded n -> jitted kernel
        # multi-chip: events shard over devices, each computes its
        # shard's per-(bucket, group) partials, and the commutative base
        # merge (sum/count/min/max) combines them host-side — the same
        # merge that already combines batches into the store
        from .planner import mesh_for
        self._mesh = mesh_for(rt, "shard") if self.device else None
        self.device_plan = None
        if not self.device:
            self._plan_device(rt, ad, mode, calendar)

    def _plan_device(self, rt, ad, mode: str, calendar: bool) -> None:
        """Build the device-resident plan, or record WHY not (D-AGG)."""
        import os
        env = os.environ.get("SIDDHI_AGG_DEVICE", "").lower()
        if mode in ("off", "never", "false", "host"):
            rt.placement.demote(
                ad.id, "D-AGG",
                f"@app:deviceAggregations({mode!r}) chose the host path",
                alternative="device-agg")
            return
        if env in ("0", "off", "host"):
            rt.placement.demote(
                ad.id, "D-AGG",
                "SIDDHI_AGG_DEVICE env opt-out chose the host path",
                alternative="device-agg")
            return
        if calendar:
            rt.placement.demote(
                ad.id, "D-AGG",
                "month/year durations need calendar (datetime64) bucket "
                "truncation — host path",
                alternative="device-agg")
            return
        try:
            from .agg_device import DeviceAggregationPlan
            self.device_plan = DeviceAggregationPlan(
                self, rt.geometry["agg_capacity"][0])
        except Exception as e:          # jax missing / backend init failed
            rt.placement.demote(
                ad.id, "D-AGG", "device aggregation plan unavailable",
                cause=e, alternative="device-agg")

    # -- ingest (vectorized segmented reduction) -----------------------------

    def process(self, stream_id: str, batch: EventBatch) -> list:
        n = batch.n
        if n == 0:
            return []
        ts = (batch.columns[self.by_attr].astype(np.int64)
              if self.by_attr else batch.timestamps)
        keep = None
        if self.filters:
            rows = batch.rows(self.rt.strings)
            names = self.in_schema.names
            keep = np.fromiter(
                (all(f(dict(zip(names, r), __timestamp__=int(t)))
                     for f in self.filters)
                 for t, r in zip(batch.timestamps, rows)),
                dtype=bool, count=n)
            if not keep.any():
                return []

        # rows whose group key or aggregate argument is NULL would otherwise
        # be bucketed/summed as their fill values (advisor r2): mask them out
        if batch.nulls:
            null_mask = np.zeros(n, dtype=bool)
            for a in self.group_attrs:
                if a in batch.nulls:
                    null_mask |= batch.nulls[a]
            for s in self.sites:
                if s.arg is not None and s.arg in batch.nulls:
                    null_mask |= batch.nulls[s.arg]
            if null_mask.any():
                keep = ~null_mask if keep is None else (keep & ~null_mask)
                if not keep.any():
                    return []

        gcols = [batch.columns[a] for a in self.group_attrs]
        vals = self._site_values(batch)
        if keep is not None:
            ts = ts[keep]
            gcols = [c[keep] for c in gcols]
            vals = [v[keep] for v in vals]

        # integer views of group columns for exact vectorized unique
        gints = [self._int_view(c) for c in gcols]
        if self.device_plan is not None:
            self._ingest_device_resident(ts, gints, gcols, vals)
            self._enforce_retention()
            return []
        if self.device:
            per_dur = self._reduce_device(ts, gints, vals)
        else:
            per_dur = self._reduce_host(ts, gints, vals)
        for dur, (buckets_of, rows_any, reduced) in zip(self.durations,
                                                        per_dur):
            st = self.store[dur]
            for j in range(len(rows_any)):
                r = int(rows_any[j])
                gkey = tuple(self._decode_gval(c[r], a)
                             for c, a in zip(gcols, self.group_attrs))
                key = (int(buckets_of[j]), gkey)
                new = [float(red[j]) for red in reduced]
                old = st.get(key)
                if old is None:
                    st[key] = new
                else:
                    st[key] = self._merge(old, new)
            if len(buckets_of):
                top = int(buckets_of.max())
                if self._newest[dur] is None or top > self._newest[dur]:
                    self._newest[dur] = top
        self._enforce_retention()
        return []

    def _ingest_device_resident(self, ts, gints, gcols, vals) -> None:
        """Per duration: host computes the batch's unique (bucket,
        group) segments (the same np.unique the host reduce uses, so
        keys match bit-for-bit), the device plan segment-reduces the
        bases and scatter-merges them into the resident bucket store —
        no per-event host work, no D2H until somebody queries."""
        vals64 = [np.ascontiguousarray(v, dtype=np.float64) for v in vals]
        for dur in self.durations:
            buckets = bucket_starts(ts, dur)
            segs = np.stack([buckets, *gints], axis=1) if gints \
                else buckets[:, None]
            uniq, inv = np.unique(segs, axis=0, return_inverse=True)
            m = len(uniq)
            first_rows = np.empty(m, dtype=np.int64)
            first_rows[inv[::-1]] = np.arange(len(inv))[::-1]
            gkeys = [tuple(self._decode_gval(c[int(r)], a)
                           for c, a in zip(gcols, self.group_attrs))
                     for r in first_rows]
            self.device_plan.ingest(dur, uniq[:, 0], gkeys,
                                    inv.astype(np.int32), vals64)
            top = int(uniq[:, 0].max())
            if self._newest[dur] is None or top > self._newest[dur]:
                self._newest[dur] = top

    def _enforce_retention(self) -> None:
        """@purge: drop buckets older than newest-start minus retention.
        Device-resident stores evict host-side only (slot frees; the
        stale device row is overwritten on reuse)."""
        if not self.retention_ms:
            return
        for dur in self.durations:
            r = self.retention_ms.get(dur)
            newest = self._newest[dur]
            if r is None or newest is None:
                continue
            cutoff = newest - r
            if self.device_plan is not None:
                self.evicted[dur] += self.device_plan.evict_before(
                    dur, cutoff)
                continue
            st = self.store[dur]
            doomed = [k for k in st if k[0] < cutoff]
            for k in doomed:
                del st[k]
            self.evicted[dur] += len(doomed)

    def _reduce_host(self, ts, gints, vals):
        """numpy segmented reduction; returns per duration
        (bucket_start_per_segment, any_row_of_segment, reduced[nb][m])."""
        out = []
        for dur in self.durations:
            buckets = bucket_starts(ts, dur)
            segs = np.stack([buckets, *gints], axis=1) if gints \
                else buckets[:, None]
            uniq, inv = np.unique(segs, axis=0, return_inverse=True)
            m = len(uniq)
            reduced: list[np.ndarray] = []
            for s, v in zip(self.sites, vals):
                for base in _BASES[s.name]:
                    if base == "sum":
                        reduced.append(np.bincount(inv, weights=v, minlength=m))
                    elif base == "count":
                        reduced.append(np.bincount(inv, minlength=m).astype(float))
                    elif base == "min":
                        acc = np.full(m, np.inf)
                        np.minimum.at(acc, inv, v)
                        reduced.append(acc)
                    elif base == "max":
                        acc = np.full(m, -np.inf)
                        np.maximum.at(acc, inv, v)
                        reduced.append(acc)
            first_rows = np.empty(m, dtype=np.int64)
            first_rows[inv[::-1]] = np.arange(len(inv))[::-1]
            out.append((uniq[:, 0], first_rows, reduced))
        return out

    # -- device segmented reduction (sort + segmented scans; no scatters —
    #    TPU scatters serialize).  One packed i32 pull for ALL durations.
    def _reduce_device(self, ts, gints, vals):
        import jax
        import jax.numpy as jnp

        n = len(ts)
        D = len(self._mesh.devices.ravel()) if self._mesh is not None else 1
        npad = 8 * D
        while npad < n:
            npad *= 2
        L = npad // D
        spans = [d.approx_millis for d in self.durations]
        nb = self.n_bases
        base_ops = [b for s in self.sites for b in _BASES[s.name]]
        val_of_base = []
        for i, s in enumerate(self.sites):
            for _b in _BASES[s.name]:
                val_of_base.append(i)

        fn = self._dev_cache.get(npad)
        if fn is None:
            def kernel(ts64, g64, v32):
                outs_i, outs_f = [], []
                pos = jnp.arange(L, dtype=jnp.int64)
                for w in spans:
                    bucket = (ts64 // w) * w
                    keys = [pos] + [g64[gi] for gi in
                                    range(g64.shape[0])][::-1] + [bucket]
                    order = jnp.lexsort(keys)
                    sb = bucket[order]
                    starts = jnp.concatenate(
                        [jnp.array([True]), sb[1:] != sb[:-1]])
                    for gi in range(g64.shape[0]):
                        sg = g64[gi][order]
                        starts = starts | jnp.concatenate(
                            [jnp.array([True]), sg[1:] != sg[:-1]])
                    rows = []
                    for bi, b in enumerate(base_ops):
                        if b == "count":
                            v = jnp.ones(L, jnp.float32)
                        else:
                            v = v32[val_of_base[bi]][order]
                        if b in ("sum", "count"):
                            # segmented associative scan in f64: a global
                            # f32 prefix difference cancels catastrophically
                            # for large values (advisor finding)
                            def comb_add(a, c):
                                af, av = a
                                cf, cv = c
                                return (af | cf,
                                        jnp.where(cf, cv, av + cv))
                            _f, run = jax.lax.associative_scan(
                                comb_add, (starts, v.astype(jnp.float64)))
                            rows.append(run)
                        else:
                            is_max = b == "max"
                            op = jnp.maximum if is_max else jnp.minimum

                            def comb(a, c):
                                af, av = a
                                cf, cv = c
                                return (af | cf,
                                        jnp.where(cf, cv, op(av, cv)))
                            _f, run = jax.lax.associative_scan(
                                comb, (starts, v.astype(jnp.float64)))
                            rows.append(run)
                    outs_i.append(jnp.stack(
                        [order.astype(jnp.int32), starts.astype(jnp.int32)]))
                    outs_f.append(jnp.stack(rows))
                return {"i": jnp.concatenate(outs_i, axis=0),
                        "f": jnp.concatenate(outs_f, axis=0)}
            if D == 1:
                fn = jax.jit(kernel)
            else:
                # shard axis 0 over the mesh: every device reduces its
                # own event shard in parallel; partials merge host-side
                from jax.sharding import NamedSharding, PartitionSpec
                sh = NamedSharding(self._mesh, PartitionSpec("shard"))
                fn = jax.jit(jax.vmap(kernel),
                             in_shardings=(sh, sh, sh), out_shardings=sh)
            self._dev_cache[npad] = fn

        ts_p = np.full(npad, np.int64(2**62))
        ts_p[:n] = ts
        g_p = np.zeros((len(gints), npad), np.int64)
        for i, g in enumerate(gints):
            g_p[i, :n] = g
        v_p = np.zeros((len(vals), npad), np.float32)
        for i, v in enumerate(vals):
            v_p[i, :n] = v
        if D == 1:
            res = fn(ts_p, g_p, v_p)
        else:
            res = fn(ts_p.reshape(D, L),
                     g_p.reshape(len(gints), D, L).swapaxes(0, 1),
                     v_p.reshape(len(vals), D, L).swapaxes(0, 1))
        from .pipeline import start_d2h
        start_d2h(res, keys=("i",))
        ipack = np.asarray(res["i"])
        fpack = np.asarray(res["f"])
        out = []
        for di, dur in enumerate(self.durations):
            parts = ([], [], [[] for _ in range(nb)])
            for s in range(D):
                ip = ipack if D == 1 else ipack[s]
                fp = fpack if D == 1 else fpack[s]
                n_s = min(max(n - s * L, 0), L)
                if n_s == 0:
                    continue
                order = ip[2 * di]
                starts = ip[2 * di + 1] != 0
                runs = fp[di * nb:(di + 1) * nb]
                sidx = np.flatnonzero(starts)
                sidx = sidx[sidx < n_s]         # drop padding segments
                ends = np.concatenate([sidx[1:], [n_s]]) - 1
                rows_any = order[sidx] + s * L
                parts[0].append(bucket_starts(ts[rows_any], dur))
                parts[1].append(rows_any)
                for bi in range(nb):
                    parts[2][bi].append(runs[bi][ends])
            out.append((np.concatenate(parts[0]),
                        np.concatenate(parts[1]),
                        [np.concatenate(p) for p in parts[2]]))
        return out

    def _merge(self, a: list, b: list) -> list:
        out = []
        i = 0
        for s in self.sites:
            for base in _BASES[s.name]:
                if base in ("sum", "count"):
                    out.append(a[i] + b[i])
                elif base == "min":
                    out.append(min(a[i], b[i]))
                else:
                    out.append(max(a[i], b[i]))
                i += 1
        return out

    def _site_values(self, batch: EventBatch) -> list:
        vals = []
        rows = None
        for s in self.sites:
            if s.name == "count" or s.arg_fn is None:
                vals.append(np.ones(batch.n))
            elif s.arg is not None:
                vals.append(batch.columns[s.arg].astype(np.float64))
            else:
                if rows is None:
                    rows = batch.rows(self.rt.strings)
                names = self.in_schema.names
                vals.append(np.fromiter(
                    (float(s.arg_fn(dict(zip(names, r)))) for r in rows),
                    dtype=np.float64, count=batch.n))
        return vals

    @staticmethod
    def _int_view(col: np.ndarray) -> np.ndarray:
        if col.dtype.kind in "iub":
            return col.astype(np.int64)
        if col.dtype.kind == "f":
            v = col.astype(np.float64)
            v = np.where(v == 0.0, 0.0, v)     # -0.0 keys with +0.0
            return v.view(np.int64)            # exact bit key otherwise
        raise PlanError("unsupported group-by column type")

    @staticmethod
    def _decode_gval(v, attr: str):
        # unwrap numpy scalars for stable dict keys; string codes decode
        # lazily in rows_between
        return v.item() if isinstance(v, np.generic) else v

    # -- query side (within/per selection) -----------------------------------

    def _materialize(self) -> None:
        """Pull device-resident bucket state into the host dict stores
        (no-op on the host path, and per-duration dirty-gated on the
        device path) — every read surface (store queries, snapshots)
        calls this first so both paths share one store format."""
        if self.device_plan is not None:
            self.device_plan.sync_into(self.store)

    def rows_between(self, per: Duration, t0: Optional[int],
                     t1: Optional[int]) -> list:
        """Output rows [(bucket_start, env)] for buckets of `per` whose
        start lies in [t0, t1)."""
        if per not in self.store:
            raise PlanError(
                f"aggregation {self.ad.id!r}: per-duration {per.value!r} not "
                f"in defined range {[d.value for d in self.durations]}")
        self._materialize()
        out = []
        for (start, gkey), bases in sorted(self.store[per].items()):
            if t0 is not None and start < t0:
                continue
            if t1 is not None and start >= t1:
                continue
            env = {AGG_TIMESTAMP: start, "__timestamp__": start}
            for a, v in zip(self.group_attrs, gkey):
                if self.in_schema.type_of(a) == AttrType.STRING:
                    v = self.rt.strings.decode(int(v))
                env[a] = v
            i = 0
            for s in self.sites:
                b = _BASES[s.name]
                if s.name == "avg":
                    sm, ct = bases[i], bases[i + 1]
                    env[s.key] = (sm / ct) if ct else None
                elif s.name == "count":
                    env[s.key] = int(bases[i])
                elif s.name in ("min", "max"):
                    env[s.key] = self._cast(bases[i], s.in_type)
                else:
                    env[s.key] = self._cast(bases[i], s.out_type)
                i += len(b)
            row_env = dict(env)
            row = [f(env) for f in self.out_fns]
            for nm, v in zip(self.out_schema.names, row):
                row_env[nm] = v
            out.append((start, row_env, row))
        return out

    @staticmethod
    def _cast(v: float, t: Optional[AttrType]):
        if t in (AttrType.INT, AttrType.LONG):
            return int(v)
        return float(v)

    # -- store-query support (reference: StoreQueryParser aggregation path) --

    def compile_store_query(self, sq: ast.StoreQuery):
        return _AggStoreExec(self, sq)

    # -- snapshot ------------------------------------------------------------

    def state_dict(self) -> dict:
        self._materialize()
        return {"store": {d.value: {k: list(v) for k, v in s.items()}
                          for d, s in self.store.items()}}

    def load_state_dict(self, d: dict) -> None:
        by_val = {x.value: x for x in Duration}
        self.store = {by_val[dv]: {k: list(v) for k, v in s.items()}
                      for dv, s in d["store"].items()}
        for dur in self.durations:           # tolerate missing durations
            self.store.setdefault(dur, {})
        for dur, st in self.store.items():
            self._newest[dur] = (max(k[0] for k in st) if st else None)
        if self.device_plan is not None:
            self.device_plan.load_from(self.store)

    # -- telemetry (statistics()["aggregation"] / siddhi_tpu_agg_*) ----------

    def group_count(self) -> int:
        """Distinct live group keys, measured on the finest duration
        (group cardinality is duration-invariant until retention evicts
        a key's last bucket)."""
        fine = self.durations[0]
        if self.device_plan is not None:
            keys = self.device_plan.rings[fine].key_to_slot
        else:
            keys = self.store[fine]
        return len({g for (_b, g) in keys})

    def metrics(self) -> dict:
        durs = {}
        for d in self.durations:
            live = (self.device_plan.live_buckets(d)
                    if self.device_plan is not None
                    else len(self.store[d]))
            ent = {"buckets": live, "evicted": self.evicted[d]}
            if self.device_plan is not None:
                ent["capacity"] = self.device_plan.capacity(d)
            r = self.retention_ms.get(d) if self.retention_ms else None
            if r is not None:
                ent["retention_ms"] = r
            durs[d.name] = ent
        return {"device": bool(self.device or self.device_plan is not None),
                "resident": self.device_plan is not None,
                "groups": self.group_count(),
                "durations": durs}


# ---------------------------------------------------------------------------
# within / per evaluation (shared by store queries and joins)
# ---------------------------------------------------------------------------

def parse_time_point(v) -> int:
    """'2017-06-01 04:05:50' / epoch-ms long -> epoch ms (UTC)."""
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, str):
        s = v.strip()
        for fmt in ("%Y-%m-%d %H:%M:%S", "%Y-%m-%d %H:%M", "%Y-%m-%d"):
            try:
                t = _dt.datetime.strptime(s, fmt).replace(
                    tzinfo=_dt.timezone.utc)
                return int(t.timestamp() * 1000)
            except ValueError:
                continue
    raise PlanError(f"cannot interpret time point {v!r}")


def within_range_of(expr, value_fn_compiler, now_fn) -> Callable:
    """Compile a `within` clause to env -> (t0, t1).

    Forms: `within start, end` (two points), `within '2017-06-** ...'`
    (wildcard pattern -> covered range), `within 1 day` (trailing window
    ending now)."""
    if expr is None:
        return lambda env: (None, None)
    if isinstance(expr, ast.FunctionCall) and expr.name == "withinRange":
        f0 = value_fn_compiler(expr.args[0])
        f1 = value_fn_compiler(expr.args[1])
        return lambda env: (parse_time_point(f0(env)),
                            parse_time_point(f1(env)))
    if isinstance(expr, ast.TimeConstant):
        ms = expr.millis
        return lambda env: (now_fn() - ms, None)
    f = value_fn_compiler(expr)

    def rng(env):
        v = f(env)
        if isinstance(v, str) and "*" in v:
            return _wildcard_range(v)
        t0 = parse_time_point(v)
        return (t0, None)
    return rng


def _wildcard_range(pat: str) -> tuple[int, int]:
    """'2017-06-** **:**:**' -> (start, end) of the covered span, derived
    component-wise: wildcards floor to their minimum for the start, and
    the finest fully-specified component is incremented for the end."""
    pat = pat.strip()
    if len(pat) == 10:                  # date only
        pat = pat + " **:**:**"
    comps = _split_dt(pat)
    lo_v = []
    hi_v = []
    mins = [1, 1, 1, 0, 0, 0]
    for i, (c, mn) in enumerate(zip(comps, mins)):
        if "*" in c:
            lo_v.append(mn)
            hi_v.append(None)
        else:
            lo_v.append(int(c))
            hi_v.append(int(c))
    start = _dt.datetime(lo_v[0], lo_v[1], lo_v[2], lo_v[3], lo_v[4],
                         lo_v[5], tzinfo=_dt.timezone.utc)
    # end: increment the finest fully-specified component
    last_fixed = max(i for i, h in enumerate(hi_v) if h is not None)
    end = start
    if last_fixed == 0:
        end = start.replace(year=start.year + 1)
    elif last_fixed == 1:
        end = (start.replace(day=1) + _dt.timedelta(days=32)).replace(day=1)
    elif last_fixed == 2:
        end = start + _dt.timedelta(days=1)
    elif last_fixed == 3:
        end = start + _dt.timedelta(hours=1)
    elif last_fixed == 4:
        end = start + _dt.timedelta(minutes=1)
    else:
        end = start + _dt.timedelta(seconds=1)
    return int(start.timestamp() * 1000), int(end.timestamp() * 1000)


def _split_dt(pat: str) -> list:
    """'YYYY-MM-DD HH:MM:SS' -> 6 components."""
    date, _, time = pat.partition(" ")
    d = (date.split("-") + ["**", "**"])[:3]
    t = (time.split(":") + ["**", "**", "**"])[:3] if time else ["**"] * 3
    return d + t


def per_duration_of(expr, ctx=None) -> Duration:
    if isinstance(expr, ast.Constant):
        return duration_of(str(expr.value))
    if isinstance(expr, ast.Variable) and expr.stream_ref is None:
        return duration_of(expr.attribute)
    raise PlanError("per must be a constant duration like 'seconds'")


class _AggStoreExec:
    """`from A [on cond] within ... per ... select ...`"""

    def __init__(self, agg: AggregationRuntime, sq: ast.StoreQuery):
        from ..interp.expr import PyExprContext, compile_py
        self.agg = agg
        if sq.per is None:
            raise PlanError("aggregation store query needs `per`")
        self.per = per_duration_of(sq.per)
        empty = PyExprContext({}, tables=agg.rt.tables)
        self.within_fn = within_range_of(
            sq.within, lambda e: compile_py(e, empty)[0],
            lambda: agg.rt.now_ms())
        octx = PyExprContext({agg.ad.id: agg.out_schema},
                             default_ref=agg.ad.id, tables=agg.rt.tables)
        on = None
        for f in sq.input.filters:
            on = f.expr if on is None else ast.And(on, f.expr)
        self.cond = compile_py(on, octx)[0] if on is not None else None
        sel = sq.selector
        if sel.select_all:
            self.sel_fns = None
            self.out_schema = agg.out_schema
        else:
            extra = {a.name: (a.name, a.type)
                     for a in agg.out_schema.attributes}
            extra[AGG_TIMESTAMP] = (AGG_TIMESTAMP, AttrType.LONG)
            sctx = PyExprContext({}, extra=extra, tables=agg.rt.tables)
            self.sel_fns = []
            names, types = [], []
            for oa in sel.attributes:
                f, t = compile_py(oa.expr, sctx)
                self.sel_fns.append(f)
                names.append(oa.name)
                types.append(t)
            self.out_schema = StreamSchema(f"#store_{agg.ad.id}", tuple(
                ast.Attribute(n, t) for n, t in zip(names, types)))

    def execute(self) -> list:
        t0, t1 = self.within_fn({})
        out = []
        for start, row_env, row in self.agg.rows_between(self.per, t0, t1):
            if self.cond is not None and not self.cond(row_env):
                continue
            if self.sel_fns is None:
                out.append((start, tuple(row)))
            else:
                out.append((start, tuple(f(row_env) for f in self.sel_fns)))
        return out
