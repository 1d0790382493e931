"""Query planner: AST Query -> executable plan over columnar batches.

The TPU analog of the reference's parser layer (reference:
core:util/parser/QueryParser.java:81, SingleInputStreamParser.java:94,
SelectorParser.java, OutputParser.java) — but instead of assembling a
linked chain of per-event Processor objects, each query lowers to ONE
jitted array program `step(state, env) -> (state, mask, out_cols)` plus a
thin host wrapper that routes compacted outputs.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..query import ast
from ..query.ast import AttrType
from .batch import EventBatch
from .expr import (CompiledExpr, ExprError, MultiStreamContext,
                   SingleStreamContext, compile_expression, jnp_dtype)
from .schema import TIMESTAMP_DTYPE, StreamSchema, StringTable, dtype_of
from .telemetry import call_kernel, device_wait, env_nbytes

# aggregator function names recognized in selectors (reference:
# core:query/selector/attribute/aggregator/*)
AGGREGATOR_NAMES = {
    "sum", "avg", "count", "min", "max", "minforever", "maxforever",
    "stddev", "distinctcount", "and", "or", "unionset",
}


def mesh_for(rt, axis: str):
    """Opt-in execution mesh for the batch-sharded kernels (window-agg,
    incremental agg): @app:deviceMesh('always') with a power-of-two
    device count; returns None otherwise.  (Pattern plans have their own
    auto policy keyed on partition count.)"""
    if str(getattr(rt, "device_mesh", "auto")).lower() != "always":
        return None
    ndev = len(jax.devices())
    if ndev <= 1 or ndev & (ndev - 1):
        return None
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()), (axis,))


class PlanError(Exception):
    pass


def selector_has_aggregators(selector: ast.Selector) -> bool:
    def walk(e) -> bool:
        if isinstance(e, ast.FunctionCall):
            if e.namespace is None and e.name.lower() in AGGREGATOR_NAMES:
                return True
            return any(walk(a) for a in e.args)
        if isinstance(e, (ast.Math, ast.Compare, ast.And, ast.Or)):
            return walk(e.left) or walk(e.right)
        if isinstance(e, ast.Not):
            return walk(e.expr)
        return False
    return any(walk(a.expr) for a in selector.attributes)


@dataclass
class CompiledSelector:
    """Projection part of a selector (no aggregators)."""
    names: list
    types: list
    fns: list                      # each: env -> column
    having: Optional[CompiledExpr]
    # env key when the output is a plain variable — read host column directly,
    # skipping the device round-trip (zero-copy passthrough)
    passthrough: list = None
    # per-output read-sets, parallel to fns (the bare fns carry no
    # metadata; reading .reads off them silently demoted every computed
    # column to the interpreter path)
    reads: list = None

    def out_schema(self, stream_id: str) -> StreamSchema:
        return StreamSchema(stream_id, tuple(
            ast.Attribute(n, t) for n, t in zip(self.names, self.types)))


def compile_selector(selector: ast.Selector, ctx, in_schema: Optional[StreamSchema],
                     extra_names: Optional[dict] = None) -> CompiledSelector:
    """Compile projection expressions. select * requires in_schema."""
    names, types, fns, passthrough, reads = [], [], [], [], []
    if selector.select_all:
        if in_schema is None:
            raise PlanError("select * not supported for this input type")
        out_attrs = [(a.name, ast.Variable(a.name)) for a in in_schema.attributes]
    else:
        out_attrs = [(oa.name, oa.expr) for oa in selector.attributes]
    for nm, expr in out_attrs:
        ce = compile_expression(expr, ctx)
        names.append(nm)
        types.append(ce.type)
        fns.append(ce.fn)
        reads.append(frozenset(ce.reads))
        if isinstance(expr, ast.Variable):
            key, _ = ctx.resolve(expr)
            passthrough.append(key)
        else:
            passthrough.append(None)
    having = None
    if selector.having is not None:
        # having may reference output attribute names
        extra = {n: (n, t) for n, t in zip(names, types)}
        hctx = _with_extra(ctx, extra)
        having = compile_expression(selector.having, hctx)
        if having.type != AttrType.BOOL:
            raise PlanError("having must be boolean")
    return CompiledSelector(names, types, fns, having, passthrough, reads)


def _with_extra(ctx, extra: dict):
    import copy
    c = copy.copy(ctx)
    c.extra = {**getattr(ctx, "extra", {}), **extra}
    return c


# ---------------------------------------------------------------------------
# Output routing descriptor
# ---------------------------------------------------------------------------

@dataclass
class OutputBatch:
    """A produced batch plus where it should go."""
    target: Optional[str]          # stream id, or None for `return`
    batch: EventBatch
    is_expired: bool = False       # expired-events output (timestamp = expiry)
    is_signal: bool = False        # zero-event control signal (window reset):
                                   # must be dispatched despite n == 0


class QueryPlan:
    """Base: stateful executable for one query."""

    name: str
    input_streams: tuple          # stream ids this plan subscribes to
    output_target: Optional[str]
    out_schema: Optional[StreamSchema]
    table_writer = None           # set when output_target is a table
    _pipe = None                  # DispatchPipeline when the plan defers
                                  # D2H pulls (pipeline.py)
    rt = None                     # owning runtime (set by _register_plan
                                  # when the plan doesn't hold it already)
    _q_ast = None                 # normalized source Query AST (set by
                                  # build.attach_table_writer; enables the
                                  # interpreter-quarantine twin)
    # graceful-degradation contract (core/faults.py ladder):
    # retryable_process: process() leaves plan state untouched when the
    # device dispatch raises, so the runtime may retry with a split batch.
    # retryable_finalize: finalize() restores its input buffer
    # (self._buffered) when the dispatch raises, so the runtime may retry
    # with a halved flush; _finalize_retry_ok goes False once a flush
    # passed its point of no return (e.g. join mirrors advanced).
    retryable_process = False
    retryable_finalize = False
    _finalize_retry_ok = True
    pipeline_depth = 0

    def process(self, stream_id: str, batch: EventBatch) -> list:
        raise NotImplementedError

    def on_timer(self, now_ms: int) -> list:
        """Called by the scheduler tick (time windows, absent patterns...)."""
        return []

    def next_wakeup(self):
        """Next timestamp (ms) this plan needs a timer callback, or None."""
        return None

    def flush_pending(self) -> list:
        """Deliver any device results still in flight (pipelined plans
        defer materialization by up to @app:devicePipeline batches); the
        runtime calls this at its flush barrier."""
        if self._pipe is not None:
            return self._pipe.drain()
        return []

    # -- dispatch-round overlap (runtime._drain) -------------------------
    #
    # The runtime opens a dispatch round over every plan touched by a
    # batch (or finalize pass), calls process/finalize on each — which
    # dispatch device work but defer the blocking D2H pull — then
    # collects.  N device plans therefore overlap on device instead of
    # running build -> compute -> readback serially per plan.

    def begin_dispatch_round(self) -> None:
        if self._pipe is not None:
            self._pipe.hold()

    def collect_ready(self) -> list:
        if self._pipe is not None:
            return self._pipe.collect()
        return []

    def finalize(self) -> list:
        """Called when a drain round settles; multi-input plans flush their
        seq-merged buffers here. Returns OutputBatches."""
        return []

    # checkpoint hooks (reference: core:util/snapshot/Snapshotable.java)
    def state_dict(self) -> dict:
        return {}

    def load_state_dict(self, d: dict) -> None:
        pass


# ---------------------------------------------------------------------------
# Filter/project plan — the minimum end-to-end slice
# ---------------------------------------------------------------------------

class FilterProjectPlan(QueryPlan):
    """`from S[p>100] select a, b+1 as c insert into O` — stateless.

    Reference equivalents: FilterProcessor.java:55 loop + QuerySelector
    projection; here: one fused jit over whole columns.
    """

    retryable_process = True        # stateless: safe to re-dispatch splits

    def __init__(self, name: str, in_schema: StreamSchema, alias: str,
                 filters: list, selector: ast.Selector,
                 strings: StringTable, output_target: Optional[str],
                 limit: Optional[int] = None, offset: Optional[int] = None,
                 events_for: ast.OutputEventsFor = ast.OutputEventsFor.CURRENT,
                 pipeline_depth: int = 0):
        from .pipeline import DispatchPipeline
        self.name = name
        self.pipeline_depth = pipeline_depth
        self._pipe = DispatchPipeline(
            name, lambda e: self._materialize(*e), depth=pipeline_depth)
        # a stateless query never expires events; `insert expired events into`
        # therefore emits nothing (matches reference semantics)
        self.emits_nothing = events_for == ast.OutputEventsFor.EXPIRED
        self.in_schema = in_schema
        self.input_streams = (in_schema.id,)
        self.output_target = output_target
        ctx = SingleStreamContext(in_schema, strings, alias)
        self._filter = None
        if filters:
            f = filters[0]
            for g in filters[1:]:
                f = ast.And(f, g)
            self._filter = compile_expression(f, ctx)
            if self._filter.type != AttrType.BOOL:
                raise PlanError(f"filter must be boolean in query {name!r}")
        self._sel = compile_selector(selector, ctx, in_schema)
        self.out_schema = self._sel.out_schema(output_target or f"#{name}")
        self.limit, self.offset = limit, offset
        # upload ONLY the columns the device program reads (every byte
        # crosses the host<->device link): filter reads + computed-output reads +
        # having reads (incl. pass-through sources having renames)
        need: set = set()
        if self._filter is not None:
            need |= set(self._filter.reads)
        for rd, pt in zip(self._sel.reads, self._sel.passthrough):
            if pt is None:
                need |= set(rd)
        if self._sel.having is not None:
            h_reads = set(self._sel.having.reads)
            need |= h_reads - set(self._sel.names)
            for nm, pt in zip(self._sel.names, self._sel.passthrough):
                if pt is not None and nm in h_reads:
                    need.add(pt)
        if not need:
            # constant filter / constant computed column: no data reads,
            # but the step still needs one column for the batch length
            need = {"__timestamp__"}
        self._need = need
        self._step = jax.jit(self._make_step())
        # first real dispatch pays trace+XLA compile: the device-time
        # profiler must not fold that into its kernel_compute estimate
        self._warm = False

    def _make_step(self):
        filt, sel = self._filter, self._sel

        # named scopes: the device operations keep an `op_name` path
        # (jit(step)/compare, ...) that survives a recompile
        def step(env):
            n = next(iter(env.values())).shape[0]
            with jax.named_scope("compare"):
                mask = (jnp.broadcast_to(filt.fn(env), (n,))  # 0-d if const
                        if filt is not None else jnp.ones(n, dtype=bool))
            with jax.named_scope("project"):
                outs = [None if pt is not None else fn(env)
                        for fn, pt in zip(sel.fns, sel.passthrough)]
            if sel.having is not None:
                with jax.named_scope("having"):
                    henv = dict(env)
                    h_reads = set(sel.having.reads)
                    for nm, col, pt in zip(sel.names, outs,
                                           sel.passthrough):
                        if nm not in h_reads:
                            continue    # env is pruned: only map names read
                        henv[nm] = env[pt] if pt is not None else col
                    mask = mask & sel.having.fn(henv)
            # the mask travels bit-packed: the bool row is 8x the packed
            # words on the device->host pull
            with jax.named_scope("mask_pack"):
                pad = -(-n // 32) * 32
                if pad != n:
                    mask = jnp.concatenate([mask, jnp.zeros(pad - n, bool)])
                words = (mask.reshape(-1, 32).astype(jnp.uint32)
                         << jnp.arange(32, dtype=jnp.uint32)[None, :]) \
                    .sum(axis=1).astype(jnp.uint32)  # sum may promote to u64
                return jax.lax.bitcast_convert_type(words, jnp.int32), \
                    [o for o in outs if o is not None]
        return step

    def process(self, stream_id: str, batch: EventBatch) -> list:
        if batch.n == 0 or self.emits_nothing:
            return []
        rt = self.rt
        with rt.span("host_build", plan=self.name):
            host_env = {a.name: batch.columns[a.name]
                        for a in self.in_schema.attributes}
            host_env["__timestamp__"] = batch.timestamps
            passthrough = self._filter is None \
                and self._sel.having is None \
                and all(pt is not None for pt in self._sel.passthrough)
            if not passthrough:
                env = {k: host_env[k] for k in sorted(self._need)
                       if k in host_env
                       and host_env[k].dtype != np.dtype(object)}
        if passthrough:
            # pure pass-through (no filter/having/computed column): nothing
            # for the device to do — emit the batch directly (NOTE: keyed
            # on plan shape, not on the read-set — constant filters and
            # constant columns have empty reads but still must evaluate)
            mask = np.ones(batch.n, dtype=bool)
            return self._pipe.push((None, [], host_env, batch, mask))
        rt.inject("dispatch", self.name)
        mask_w, outs = call_kernel(
            rt.stats, self.name, self._step, (env,), cache_hit=self._warm,
            nbytes=env_nbytes(env), prof=rt.profiler)
        self._warm = True
        from .pipeline import start_d2h
        start_d2h([mask_w] + list(outs))    # pulls overlap device compute
        return self._pipe.push((mask_w, outs, host_env, batch, None))

    def _materialize(self, mask_w, outs, host_env, batch, mask) -> list:
        span = self.rt.span
        if mask is None:
            # the pulls: the wait for the device (a span of its own
            # while a sink is on) and the D2H copies
            with span("transfer", plan=self.name):
                device_wait(span, self.name, (mask_w, outs))
                with span("transfer.copy", plan=self.name):
                    words = np.asarray(mask_w)
                    outs = [np.asarray(o) for o in outs]
        with span("unpack", plan=self.name, events=batch.n):
            if mask is None:
                mask = ((words.view(np.uint32)[:, None]
                         >> np.arange(32, dtype=np.uint32)) & 1
                        ).astype(bool).reshape(-1)[:batch.n]
            if not mask.any():
                return []
            ts = batch.timestamps[mask]
            cols = {}
            outs = iter(outs)
            for nm, t, pt in zip(self._sel.names, self._sel.types,
                                 self._sel.passthrough):
                if pt is not None:
                    cols[nm] = host_env[pt][mask]
                else:
                    arr = next(outs)
                    if arr.ndim == 0:   # constant column: 0-d on device
                        arr = np.broadcast_to(arr, (batch.n,))
                    cols[nm] = arr[mask].astype(dtype_of(t))
            if self.offset:
                ts = ts[self.offset:]
                cols = {k: v[self.offset:] for k, v in cols.items()}
            if self.limit is not None:
                ts = ts[:self.limit]
                cols = {k: v[:self.limit] for k, v in cols.items()}
            out = EventBatch(self.out_schema, ts, cols, len(ts))
            return [OutputBatch(self.output_target, out)]


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def output_target_of(q: ast.Query) -> Optional[str]:
    if isinstance(q.output, ast.InsertInto):
        if q.output.is_fault:
            return "!" + q.output.target
        return q.output.target
    if isinstance(q.output, ast.ReturnAction):
        return None
    if isinstance(q.output, (ast.UpdateTable, ast.DeleteFrom, ast.UpdateOrInsertTable)):
        return q.output.target
    raise PlanError(f"unsupported output action {type(q.output).__name__}")
