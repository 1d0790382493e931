"""Multi-query device batching: structurally identical pattern queries
become LANES of one batched NFA kernel.

The reference's "1k concurrent queries over a shared InputHandler"
scenario (BASELINE config 5; reference analog: 1k QueryRuntimes each
walking its own processor chain per event —
core:query/QueryRuntime.java:47) maps naturally onto the TPU kernel's
partition axis: queries that share an AST SHAPE and differ only in
constants (thresholds, within windows, ...) compile once, with every
lifted constant becoming a per-lane (P,) parameter vector.  Every event
broadcasts to all lanes: the events ship ONCE, never once a lane.  A seq-
family group ships (T, 1) grids that broadcast on device; a scan / dfa
group ships the flat flush, and a flush longer than a row of a few
`within` windows ships it as (rows, C) grids `[the last window | new
events]`, the block then running over rows x lanes (pattern_plan
`_fused_cut`).  A lane is a rule: the host puts the flush's matches in
delivery order (rule, completion, head) by one stable sort on one composite
key (lane_grid `ResultDecoder.rule_order`), and hands each rule one slice
(span `route`).  Every result is decoded through one index over its filled
cells (`_Filled`); a cut flush's alone with that order composed into the
index (`ResultDecoder.cut`), so its columns are written once, in delivery
order.

Grouping is automatic: >= MIN_GROUP StateInputStream queries with equal
shape signatures (and no rate/having/limit) fuse; everything else plans
individually.  `@app:devicePatterns('never')` disables it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from ..query import ast

MIN_GROUP = 8


# ---------------------------------------------------------------------------
# shape signature + constant lifting
# ---------------------------------------------------------------------------

def _sig(node, consts: Optional[list] = None):
    """Canonical shape token tree: constants -> type tokens (collected in
    order into `consts` when given)."""
    if isinstance(node, ast.Constant):
        if consts is not None:
            consts.append(node)
        return ("const", node.type.name)
    if isinstance(node, ast.TimeConstant):
        return ("timeconst", node.millis)   # within/for stay literal
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        out = [type(node).__name__]
        for f in dataclasses.fields(node):
            out.append((f.name, _sig(getattr(node, f.name), consts)))
        return tuple(out)
    if isinstance(node, (tuple, list)):
        return tuple(_sig(x, consts) for x in node)
    if isinstance(node, (str, int, float, bool)) or node is None:
        return node
    if isinstance(node, ast.AttrType) or hasattr(node, "name"):
        return getattr(node, "name", str(node))
    return str(node)


def _has_string_const(node) -> bool:
    if isinstance(node, ast.Constant):
        return node.type == ast.AttrType.STRING
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        return any(_has_string_const(getattr(node, f.name))
                   for f in dataclasses.fields(node))
    if isinstance(node, (tuple, list)):
        return any(_has_string_const(x) for x in node)
    return False


def query_signature(q: ast.Query):
    """Hashable shape signature of a pattern query (constants abstracted);
    None when the query can't participate in fusion."""
    if not isinstance(q.input, ast.StateInputStream):
        return None
    if q.rate is not None or q.selector.having is not None \
            or q.selector.group_by or q.selector.order_by \
            or q.selector.limit is not None or q.selector.offset \
            or q.selector.select_all:
        return None
    if not isinstance(q.output, ast.InsertInto):
        return None
    if getattr(q.output, "events_for",
               ast.OutputEventsFor.CURRENT) != ast.OutputEventsFor.CURRENT:
        return None
    if _has_string_const(q.input) or any(_has_string_const(oa.expr)
                                         for oa in q.selector.attributes):
        return None        # string params need interning: not lifted yet
    # output NAMES may differ per query; the target stream SCHEMA shape
    # must match (routing is per-lane)
    return ("pattern", _sig(q.input), _sig(tuple(
        ("attr", _sig(oa.expr)) for oa in q.selector.attributes)))


class _Lifter:
    """Rewrites constants into __qparam<i> variables (resolved through
    ctx.extra) and records each instance's constant values."""

    def __init__(self):
        self.types: list = []       # AttrType per param slot

    def lift(self, node, counter: list):
        if isinstance(node, ast.Constant):
            i = counter[0]
            counter[0] += 1
            if i == len(self.types):
                self.types.append(node.type)
            return ast.Variable(f"__qparam{i}")
        if isinstance(node, ast.TimeConstant):
            # time constants stay literal: `within 1 sec` feeds the
            # kernel's per-position within, parameterized separately
            return node
        if dataclasses.is_dataclass(node) and not isinstance(node, type):
            changes = {}
            for f in dataclasses.fields(node):
                v = getattr(node, f.name)
                nv = self.lift(v, counter)
                if nv is not v:
                    changes[f.name] = nv
            return dataclasses.replace(node, **changes) if changes else node
        if isinstance(node, tuple):
            out = tuple(self.lift(x, counter) for x in node)
            return out if any(a is not b for a, b in zip(out, node)) else node
        return node

    @staticmethod
    def const_values(node, acc: list):
        if isinstance(node, ast.Constant):
            acc.append(node.value)
            return
        if isinstance(node, ast.TimeConstant):
            return
        if dataclasses.is_dataclass(node) and not isinstance(node, type):
            for f in dataclasses.fields(node):
                _Lifter.const_values(getattr(node, f.name), acc)
        elif isinstance(node, (tuple, list)):
            for x in node:
                _Lifter.const_values(x, acc)


def plan_query_group(rt, queries: list, names: list):
    """Build one MultiQueryDevicePatternPlan for a same-shape group.
    queries: [(ast.Query)] — returns the plan or raises
    DeviceNFAUnsupported to fall back to per-query planning."""
    from .nfa_device import DeviceNFAUnsupported
    from .pattern_plan import DevicePatternPlan

    proto = queries[0]
    lifter2 = _Lifter()
    counter = [0]
    lifted = _lift_query(proto, lifter2, counter)
    n_params = counter[0]

    # per-instance parameter matrix (P queries x n_params)
    values = []
    for q in queries:
        acc: list = []
        _Lifter.const_values(q.input, acc)
        for oa in q.selector.attributes:
            _Lifter.const_values(oa.expr, acc)
        if len(acc) != n_params:
            raise DeviceNFAUnsupported("constant-count mismatch in group")
        values.append(acc)

    for q in queries:
        if _target_of(q) in rt.tables:
            raise DeviceNFAUnsupported("fused query targets a table")
    plan = MultiQueryDevicePatternPlan(
        names[0] + f"__x{len(queries)}", rt, lifted, lifted.input,
        param_types=lifter2.types, param_values=values,
        targets=[_target_of(q) for q in queries],
        out_names=[[oa.name for oa in q.selector.attributes]
                   for q in queries],
        query_names=names)
    return plan


def _lift_query(q: ast.Query, lifter: _Lifter, counter: list) -> ast.Query:
    new_input = lifter.lift(q.input, counter)
    new_attrs = tuple(dataclasses.replace(oa, expr=lifter.lift(oa.expr, counter))
                      for oa in q.selector.attributes)
    return dataclasses.replace(
        q, input=new_input,
        selector=dataclasses.replace(q.selector, attributes=new_attrs))


def _target_of(q: ast.Query) -> str:
    return q.output.target


# ---------------------------------------------------------------------------
# the fused plan
# ---------------------------------------------------------------------------

class MultiQueryDevicePatternPlan:
    """One device NFA whose lanes are query INSTANCES (not partition
    keys): events broadcast to every lane; emitted matches route to their
    lane's output stream."""

    def __init__(self, name, rt, q, state_input, param_types, param_values,
                 targets, out_names, query_names):
        from .expr import jnp_dtype
        from .pattern_plan import DevicePatternPlan

        self.name = name
        self.rt = rt
        self.query_names = query_names
        rt._known_query_names.update(query_names)
        self.targets = targets
        self.per_q_names = out_names
        P = len(param_values)

        extra = {f"__qparam{i}": (f"__qparam{i}", t)
                 for i, t in enumerate(param_types)}
        from .nfa_device import F32_MODE
        from .expr import compute_dtypes as _cd
        prec = ast.find_annotation(rt.app.annotations, "app:devicePrecision")
        f64 = prec is not None and str(prec.element()).lower() == "f64"
        with _cd(None if f64 else F32_MODE):
            params = {}
            for i, t in enumerate(param_types):
                col = np.asarray([v[i] for v in param_values])
                params[f"__qparam{i}"] = col.astype(np.dtype(jnp_dtype(t)))
        self.inner = DevicePatternPlan(
            name, rt, q, state_input, target=targets[0], partitions=P,
            part_key_fns=None, slots=rt.device_slots, param_extra=extra,
            broadcast_events=True, params=params)
        if self.inner.kernel.null_outputs:
            from .nfa_device import DeviceNFAUnsupported
            raise DeviceNFAUnsupported(
                "fused selector over maybe-absent refs (null routing)")
        self.n_queries = P
        self._schemas: dict = {}
        # mesh rounding may pad the lane axis: padding lanes carry zero
        # params (match-everything thresholds) — permanently disarm them
        if self.inner.P > P:
            import jax.numpy as jnp
            st = dict(self.inner.state)
            st["armed0"] = st["armed0"] & (jnp.arange(self.inner.P) < P)
            self.inner.state = self.inner._shard(
                {k: np.asarray(v) for k, v in st.items()})
        # register inferred schemas for every routed target stream
        from .schema import StreamSchema
        for qi, tgt in enumerate(targets):
            if tgt not in rt.schemas and tgt not in rt.tables:
                rt.schemas[tgt] = StreamSchema(tgt, tuple(
                    ast.Attribute(nm, t) for nm, t in
                    zip(out_names[qi], self.inner._types)))
        self.input_streams = self.inner.input_streams
        self.output_target = None          # routed per lane
        self.out_schema = None
        self.table_writer = None

    # -- QueryPlan surface -------------------------------------------------

    def device_metrics(self) -> dict:
        """Sampled gauges of the fused kernel (lane = query instance, so
        occupancy here reads as per-query pending-match population)."""
        m = self.inner.device_metrics()
        m["fused_queries"] = self.n_queries
        m["padded_lanes"] = self.inner.P - self.n_queries
        m["fused"] = self.fused
        return m

    @property
    def fused(self) -> dict:
        """EXPLAIN's `fused` record of this group (DevicePatternPlan.fused
        with the rule count; padding lanes are disarmed at build and are
        not counted as resolved arms)."""
        rec = self.inner.fused
        padded = self.inner.P - self.n_queries
        if self.inner._arm_done is None:
            rec["arms_resolved"] = max(0, rec["arms_resolved"] - padded)
        return {"queries": self.n_queries, "padded_lanes": padded, **rec}

    def flush_pending(self):
        return []

    def begin_dispatch_round(self):
        # a scan / dfa group joins the runtime's dispatch round: its block
        # launches in finalize() and is pulled in collect_ready(), after
        # every other plan's block has launched, so one group's compute
        # runs under another's pull, unpack and routing (a seq group keeps
        # device state and drains in finalize)
        pipe = self.inner._pipe
        if pipe is not None:
            pipe.hold()

    def collect_ready(self):
        pipe = self.inner._pipe
        if pipe is None or not len(pipe):
            return []
        return self._route(self.inner.collect_multi())

    def process(self, stream_id, batch):
        return self.inner.process(stream_id, batch)

    def finalize(self):
        return self._route(self.inner.finalize_multi())

    def _route(self, outs):
        """The flush's matches to one OutputBatch a rule: a batch is a
        slice of the columns in delivery order (lane_grid RuleRuns),
        which a cut flush's decode has made already and a flat flush's
        table is put into here."""
        from .batch import EventBatch
        from .lane_grid import RuleRuns
        from .planner import OutputBatch

        if not outs:
            return []
        res = []
        with self.rt.span("route", plan=self.name):
            runs = outs if isinstance(outs, RuleRuns) \
                else self.inner.decoder.rule_runs(outs)
            starts = runs.starts.tolist()
            for qi, a, b in zip(runs.lanes.tolist(), starts,
                                starts[1:] + [len(runs.tss)]):
                if qi >= self.n_queries:      # defensive: padding lanes
                    continue
                names = self.per_q_names[qi]
                cols = {nm: runs.data[src][a:b] for nm, src
                        in zip(names, self.inner._names)}
                ob = OutputBatch(self.targets[qi], EventBatch(
                    self._schema_of(qi), runs.tss[a:b], cols, b - a,
                    runs.seqs[a:b]))
                ob.callback_name = self.query_names[qi]
                res.append(ob)
        return res

    def _schema_of(self, qi: int):
        """The routed batch's schema for rule `qi` (its own output names
        over the group's shared types), built once."""
        sch = self._schemas.get(qi)
        if sch is None:
            from .schema import StreamSchema
            sch = self._schemas[qi] = StreamSchema(self.targets[qi], tuple(
                ast.Attribute(nm, t) for nm, t
                in zip(self.per_q_names[qi], self.inner._types)))
        return sch

    def on_timer(self, now_ms):
        self.inner.on_timer(now_ms)      # deadline ticks; matches surface
        return self.finalize()           # via the buffered path

    def next_wakeup(self):
        return self.inner.next_wakeup()

    def state_dict(self):
        return self.inner.state_dict()

    def load_state_dict(self, d):
        self.inner.load_state_dict(d)
