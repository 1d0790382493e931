"""App builder: walks the AST's execution elements and instantiates plans.

Analog of the reference's SiddhiAppParser.parse loop (reference:
core:util/parser/SiddhiAppParser.java:225-254) + QueryParser dispatch +
DefinitionParserHelper table/trigger instantiation
(core:util/parser/helper/DefinitionParserHelper.java:160).
Kept separate from runtime.py so the runtime facade stays small.
"""
from __future__ import annotations

from ..query import ast
from .planner import (FilterProjectPlan, PlanError, output_target_of,
                      selector_has_aggregators)


def build_app(rt) -> None:
    """Populate rt (SiddhiAppRuntime) with tables and plans from rt.app."""
    from ..interp.expr import (ExprError, compile_script_function, udf_scope)

    # script UDFs compile first: queries below may call them (reference:
    # SiddhiAppParser defines scripts before queries, Script.java:27).
    # Unsupported languages fail HERE, loudly — not at first use.
    rt.udfs = {}
    mgr = getattr(rt, "manager", None)
    if (rt.app.function_definitions
            and mgr is not None and not getattr(mgr, "allow_scripts", True)):
        raise PlanError(
            "script functions are disabled on this SiddhiManager "
            "(allow_scripts=False): app text is untrusted input here and "
            "[python] script bodies execute with full interpreter privileges")
    for fid, fd in rt.app.function_definitions.items():
        try:
            rt.udfs[fid.lower()] = (compile_script_function(fd),
                                    fd.return_type)
        except ExprError as e:
            raise PlanError(str(e)) from None
    with udf_scope(rt.udfs):
        _build_app_scoped(rt)


def _build_app_scoped(rt) -> None:
    from .table import InMemoryTable, TableError

    app = rt.app
    for tid, td in app.table_definitions.items():
        if tid in rt.schemas:
            raise PlanError(f"{tid!r} defined as both stream and table")
        try:
            from .record_table import build_record_table
            bridge = build_record_table(td, rt.strings)
            rt.tables[tid] = bridge if bridge is not None \
                else InMemoryTable(td, rt.strings)
        except TableError as e:
            raise PlanError(str(e)) from None
        except PlanError:
            raise
        except Exception as e:      # store connect failures etc.
            raise PlanError(f"table {tid!r}: {e}") from e

    from ..interp.named_window import NamedWindowRuntime
    from .schema import StreamSchema
    for wid, wd in app.window_definitions.items():
        if wid in rt.schemas or wid in rt.tables:
            raise PlanError(f"{wid!r} defined as both window and stream/table")
        nw = NamedWindowRuntime(rt, wd)
        rt.named_windows[wid] = nw
        rt.schemas[wid] = nw.schema
        rt._register_plan(nw)

    from .trigger import TriggerRuntime
    for tid, td in app.trigger_definitions.items():
        rt._register_plan(TriggerRuntime(rt, td))

    from .aggregation import AggregationRuntime
    for aid, ad in app.aggregation_definitions.items():
        if aid in rt.schemas or aid in rt.tables:
            raise PlanError(f"{aid!r} defined as both aggregation and "
                            f"stream/table/window")
        agg = AggregationRuntime(rt, ad)
        rt.aggregations[aid] = agg
        rt._register_plan(agg)

    # multi-query device batching pre-pass: >= MIN_GROUP structurally
    # identical pattern queries fuse into ONE batched kernel whose lanes
    # are the query instances (BASELINE config 5's "1k concurrent queries")
    fused: dict = {}
    if getattr(rt, "device_patterns", "auto") != "never":
        from .multi_query import MIN_GROUP, query_signature
        groups: dict = {}
        for i, elem in enumerate(app.execution_elements):
            if isinstance(elem, ast.Query):
                sig = query_signature(elem)
                if sig is not None:
                    groups.setdefault(sig, []).append(i)
        from .multi_query import plan_query_group
        from .nfa_device import DeviceNFAUnsupported
        for sig, idxs in groups.items():
            if len(idxs) < MIN_GROUP:
                # a LONE query was never a fusion candidate — recording
                # "group of 1 too small" for every pattern app is noise
                if len(idxs) > 1:
                    for i in idxs:
                        q = app.execution_elements[i]
                        rt.placement.demote(
                            q.name(f"query_{i}"), "D-FUSED",
                            f"structurally-identical group too small to "
                            f"fuse ({len(idxs)} < {MIN_GROUP}); planned "
                            f"individually",
                            alternative="fused-lanes")
                continue
            # fused-lane packing (@app:fusedLanes): cap the lane count
            # per fused kernel — a group larger than the pack splits
            # into several kernels (0 = unbounded, one kernel)
            pack = rt.geometry["lane_pack"][0]
            if pack and pack >= MIN_GROUP:
                slices = [idxs[j:j + pack]
                          for j in range(0, len(idxs), pack)]
                if len(slices) > 1 and len(slices[-1]) < MIN_GROUP:
                    slices[-2].extend(slices.pop())   # tail too small to
            else:                                     # fuse on its own
                slices = [idxs]
            for sub in slices:
                qs = [app.execution_elements[i] for i in sub]
                names = [q.name(f"query_{i}") for q, i in zip(qs, sub)]
                try:
                    plan = plan_query_group(rt, qs, names)
                except DeviceNFAUnsupported as e:
                    for nm in names:
                        rt.placement.demote(
                            nm, "D-FUSED",
                            "fused multi-query lane kernel unavailable "
                            "for this group; queries planned individually",
                            cause=e, alternative="fused-lanes")
                    break
                rt._register_plan(plan)
                for i in sub:
                    fused[i] = plan

    for i, elem in enumerate(app.execution_elements):
        if i in fused:
            continue
        if isinstance(elem, ast.Query):
            plan = plan_query(rt, elem, default_name=f"query_{i}")
            rt._register_plan(plan)
        elif isinstance(elem, ast.Partition):
            plan_partition(rt, elem, index=i)
        else:
            raise PlanError(f"unknown execution element {type(elem).__name__}")


def attach_table_writer(rt, plan, q: ast.Query, name: str):
    """If the query's target is a table, build the matching write-side
    callback (reference: OutputParser.java:117-220 chooses the
    Insert/Update/Delete/UpdateOrInsert table callback)."""
    from .table import TableError, make_table_writer

    target = plan.output_target
    if isinstance(q.output, (ast.UpdateTable, ast.DeleteFrom,
                             ast.UpdateOrInsertTable)):
        if target not in rt.tables:
            raise PlanError(
                f"query {name!r}: {type(q.output).__name__} target "
                f"{target!r} is not a defined table")
    if target is not None and target in rt.tables:
        try:
            plan.table_writer = make_table_writer(
                q.output, rt.tables[target], plan.out_schema)
        except TableError as e:
            raise PlanError(f"query {name!r}: {e}") from None
    # keep the (normalized) source AST: the fault layer rebuilds the plan
    # on the interpreter path from it when a device plan is quarantined
    # (runtime._build_twin)
    plan._q_ast = q
    return plan


def _normalize_fault_inputs(node, rt, name: str):
    """Rewrite every `!S` input reference (single streams, join sides,
    pattern state elements) to the registered "!S" fault schema."""
    import dataclasses
    if isinstance(node, ast.SingleInputStream):
        if not node.is_fault:
            return node
        fid = "!" + node.stream_id
        if fid not in rt.schemas:
            raise PlanError(f"query {name!r}: stream {node.stream_id!r} has "
                            f"no fault stream; annotate it with "
                            f"@OnError(action='stream')")
        return dataclasses.replace(node, stream_id=fid, is_fault=False)
    if isinstance(node, ast.JoinInputStream):
        return dataclasses.replace(
            node, left=_normalize_fault_inputs(node.left, rt, name),
            right=_normalize_fault_inputs(node.right, rt, name))
    if isinstance(node, ast.StateInputStream):
        return dataclasses.replace(
            node, state=_normalize_fault_inputs(node.state, rt, name))
    if isinstance(node, (ast.StreamStateElement, ast.AbsentStreamStateElement)):
        return dataclasses.replace(
            node, stream=_normalize_fault_inputs(node.stream, rt, name))
    if isinstance(node, ast.CountStateElement):
        return dataclasses.replace(
            node, stream=_normalize_fault_inputs(node.stream, rt, name))
    if isinstance(node, ast.LogicalStateElement):
        return dataclasses.replace(
            node, left=_normalize_fault_inputs(node.left, rt, name),
            right=_normalize_fault_inputs(node.right, rt, name))
    if isinstance(node, ast.NextStateElement):
        return dataclasses.replace(
            node, state=_normalize_fault_inputs(node.state, rt, name),
            next=_normalize_fault_inputs(node.next, rt, name))
    if isinstance(node, ast.EveryStateElement):
        return dataclasses.replace(
            node, state=_normalize_fault_inputs(node.state, rt, name))
    return node


def plan_query(rt, q: ast.Query, default_name: str):
    """Compile one query into a plan.  Re-enters udf_scope: partition
    groups call this lazily (first event per key), long after build_app's
    scope has exited — script functions must still resolve."""
    from ..interp.expr import udf_scope
    with udf_scope(getattr(rt, "udfs", None)):
        return _plan_query_scoped(rt, q, default_name)


def _plan_query_scoped(rt, q: ast.Query, default_name: str):
    import dataclasses
    name = q.name(default_name)
    target = output_target_of(q)
    inp = _normalize_fault_inputs(q.input, rt, name)
    if inp is not q.input:
        q = dataclasses.replace(q, input=inp)

    if isinstance(inp, ast.SingleInputStream):
        if inp.stream_id in rt.tables:
            raise PlanError(
                f"query {name!r}: cannot stream from table "
                f"{inp.stream_id!r}; use a join or an on-demand (store) query")
        if inp.stream_id not in rt.schemas:
            raise PlanError(f"query {name!r}: unknown input stream {inp.stream_id!r}")
        schema = rt.schemas[inp.stream_id]
        has_window = inp.window is not None
        has_agg = selector_has_aggregators(q.selector) or q.selector.group_by
        # reading from a named window with expired/all output needs the
        # host path's expired-stream subscription
        nw_needs_host = (inp.stream_id in rt.named_windows
                         and q.output.events_for != ast.OutputEventsFor.CURRENT)
        # TPU windowed-aggregation path (length/time/lengthBatch windows
        # with sum/count/avg/min/max): one fused device step per batch
        dw_mode = rt.device_windows
        if has_window and has_agg and dw_mode != "never":
            from .window_device import DeviceWindowAggPlan, DeviceWindowUnsupported
            try:
                return attach_table_writer(rt, DeviceWindowAggPlan(
                    name, rt, q, inp, target), q, name)
            except DeviceWindowUnsupported as e:
                if dw_mode == "always":
                    raise PlanError(f"query {name!r}: deviceWindows=always "
                                    f"but unsupported: {e}")
                rt.placement.demote(name, "D-WINDOW", str(e), cause=e,
                                    alternative="device-window")
        # TPU fast path: stateless filter/project with device-typed columns
        if (not has_window and not has_agg and q.rate is None and not nw_needs_host
                and rt.device_filters != "never"
                and isinstance(q.output, (ast.InsertInto, ast.ReturnAction))
                and not any(isinstance(h, ast.StreamFunction) for h in inp.handlers)):
            try:
                filters = [f.expr for f in inp.filters]
                return attach_table_writer(rt, FilterProjectPlan(
                    name, schema, inp.alias, filters, q.selector, rt.strings,
                    target, q.selector.limit, q.selector.offset,
                    events_for=q.output.events_for,
                    pipeline_depth=rt.geometry["pipeline_depth"][0]),
                    q, name)
            except PlanError:
                raise
            except Exception as e:
                # host-only functions etc. -> sequential backend.  NOT
                # silent: PR 5 found a whole query class demoted through
                # this exact handler — the cause must reach explain()
                rt.placement.demote(
                    name, "D-FILTER",
                    "device filter/projection lowering failed; host "
                    "interpreter handles this query",
                    cause=e, alternative="device-filter")
        elif not rt.placement.for_query(name):
            # the stateless fast path never applied: account for WHY the
            # query lands on the host (the window branch above recorded
            # its own reason when it was attempted and rejected)
            rule, why = _interp_shape_reasons(rt, q, inp, has_window,
                                              has_agg, nw_needs_host,
                                              dw_mode)
            rt.placement.demote(name, rule, why, alternative="device")
        from ..interp.engine import InterpSingleQueryPlan
        return attach_table_writer(
            rt, InterpSingleQueryPlan(name, rt, q, inp, target), q, name)

    if isinstance(inp, ast.JoinInputStream):
        mode = getattr(rt, "device_joins", "auto")
        if mode != "never":
            from .join_device import DeviceJoinPlan, DeviceJoinUnsupported
            try:
                return attach_table_writer(
                    rt, DeviceJoinPlan(name, rt, q, inp, target), q, name)
            except DeviceJoinUnsupported as e:
                if mode == "always":
                    raise PlanError(
                        f"query {name!r}: @app:deviceJoins('always') but "
                        f"the shape is host-only: {e}")
                rt.placement.demote(name, "D-JOIN", str(e), cause=e,
                                    alternative="device-join")
        else:
            rt.placement.demote(name, "D-POLICY",
                                "@app:deviceJoins('never')",
                                alternative="device-join")
        from ..interp.joins import InterpJoinQueryPlan
        return attach_table_writer(
            rt, InterpJoinQueryPlan(name, rt, q, inp, target), q, name)

    if isinstance(inp, ast.StateInputStream):
        mode = getattr(rt, "device_patterns", "auto")
        if mode == "always":
            from .pattern_plan import DevicePatternPlan
            return attach_table_writer(rt, DevicePatternPlan(
                name, rt, q, inp, target, slots=rt.device_slots), q, name)
        if mode == "prefer":
            from .nfa_device import DeviceNFAUnsupported
            from .pattern_plan import DevicePatternPlan
            try:
                return attach_table_writer(rt, DevicePatternPlan(
                    name, rt, q, inp, target, slots=rt.device_slots), q, name)
            except DeviceNFAUnsupported as e:
                rt.placement.demote(name, "D-PATTERN", str(e), cause=e,
                                    alternative="device-pattern")
        if mode == "auto":
            # policy, untested on a locally attached chip (ROADMAP "found
            # at bring-up"): a P=1 kernel has no lane axis to fill, so
            # unpartitioned patterns default to the host matcher; the
            # partition planner routes partitioned patterns to the device
            rt.placement.demote(
                name, "D-POLICY",
                "devicePatterns='auto': unpartitioned patterns run the "
                "host matcher (a P=1 kernel has no lane axis to fill; the "
                "default is not yet measured on a locally attached chip); "
                "partition the query to take the device "
                "lane axis, or force @app:devicePatterns('prefer')",
                alternative="device-pattern")
        elif mode == "never":
            rt.placement.demote(name, "D-POLICY",
                                "@app:devicePatterns('never')",
                                alternative="device-pattern")
        from ..interp.engine import InterpPatternQueryPlan
        return attach_table_writer(
            rt, InterpPatternQueryPlan(name, rt, q, inp, target), q, name)

    raise PlanError(f"query {name!r}: input type {type(inp).__name__} not yet supported")


def _interp_shape_reasons(rt, q: ast.Query, inp, has_window: bool,
                          has_agg: bool, nw_needs_host: bool,
                          dw_mode: str) -> tuple:
    """(rule_id, reason) for a single-stream query that reached the host
    interpreter without any device-plan attempt — the placement plane's
    answer to "why is this query not on the device?".  Policy opt-outs
    (annotations/env) report as D-POLICY; everything else is a shape
    gate (D-SHAPE)."""
    reasons, policy = [], []
    if has_window and has_agg and dw_mode == "never":
        policy.append("@app:deviceWindows('never')")
    if has_window and not has_agg:
        reasons.append("window without device-supported aggregation "
                       "(host window operators)")
    if has_agg and not has_window:
        reasons.append("aggregation without a window "
                       "(host running aggregators)")
    if nw_needs_host:
        reasons.append("named-window expired/all output needs the host "
                       "expired-stream subscription")
    if q.rate is not None:
        reasons.append("output rate limiting is host-only")
    if any(isinstance(h, ast.StreamFunction) for h in inp.handlers):
        reasons.append("stream functions are host-only")
    if not isinstance(q.output, (ast.InsertInto, ast.ReturnAction)):
        reasons.append(f"{type(q.output).__name__} table output runs on "
                       f"the host path")
    if (not reasons and not policy and rt.device_filters == "never"):
        policy.append("@app:deviceFilters('never')")
    if reasons:
        return "D-SHAPE", "; ".join(reasons)
    if policy:
        return "D-POLICY", "; ".join(policy)
    return "D-SHAPE", "query shape has no device plan family"


def plan_partition(rt, p: ast.Partition, index: int) -> None:
    from .partition import plan_partition as _pp
    _pp(rt, p, index)
