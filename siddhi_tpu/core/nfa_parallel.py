"""Parallel-in-time NFA plan families: associative-scan (SFA) + DFA/hybrid.

The sequential device kernel (nfa_device.NFAKernel) walks one event per
`lax.scan` step per lane: throughput is bounded by the T-long dependency
chain, not by math.  *Simultaneous Finite Automata* (arXiv
1405.0562) breaks that chain: simulate the automaton from EVERY state,
compose per-event transition functions associatively, and the whole
block collapses to log-depth scans.  First-match semantics make the
composed transition function DETERMINISTIC given a head event, so the
SFA composition factorizes into per-state primitives, none of which
steps through events:

  * next-match pointers for statically-maskable transitions — a reverse
    `jax.lax.associative_scan` (min semiring) per chase node;
  * ONE first-hit question, "the first index >= s whose masked value
    beats v", batched over every pending instance at once (_FirstHit),
    for *threshold* transitions — capture-dependent filters of the
    monotone comparison form `attr > f(earlier captures)` (the BENCH
    config-3/4 shape `e2.price > e1.price`) — for the `within` expiry
    ("the first event past the head's horizon") and for rank/select.
    It has two forms, picked from the block's static F (DENSE_MAX_F):
    a lane of a few hundred events reduces all (event, query) pairs in
    one fused masked min, no gather and no loop; a long lane (the flat
    P = 1 block, fused multi-query lanes) builds a perfect segment tree
    and walks it in up to 2*log2(L)+1 rounds of dependent gathers;
  * ONE indexed read, "this column's element at idx[m]" (_Read), for
    whatever a resolved index then fetches: a hop's captured value a
    head, the capture indices and the selected values of the M match
    rows.  The same rule and constant pick its form: a short lane is
    read by one fused one-hot sum over its (event, asker) pairs, bit
    for bit and with no gather; a long one by the gather;
  * ONE compaction, "the candidates that matched, in order, as the M
    match rows" (_Compact): a prefix count gives every live candidate
    its row, and the same rule and constant pick how the rows are
    filled: a short lane by one fused one-hot sum over its (candidate,
    row) pairs, a long one by a scatter;
  * rank/select over occurrence-count prefix sums for `<m:n>` count
    quantifiers — "the min-th occurrence after entry" is one first-hit
    query on the monotone cumulative-count array (the bit-packed
    state-SET lowering of arXiv 2210.10077 collapsed onto the counter
    lattice: the u32 frontier word's reachable set is an interval, so
    its boundary IS the rank);
  * forward prev-match scans (max semiring) for logical AND/OR partner
    pairs — "done" is the min (or) / max (and) of the two sides' first
    matches, captures re-resolve to the LAST side match at or before
    the done event, exactly like the sequential kernel's re-capturing
    station.

Two plan families are built on these primitives:

  * family "scan" — the SFA lowering above: O(S log T) depth on the
    tree form, O(S) fused reductions on the dense one.
  * family "dfa"  — NFA->DFA/hybrid lowering (arXiv 2210.10077) with
    state-set compaction and bit-packed transitions: the per-event
    chase-node masks pack into one u32 *symbol word* (bit k = event
    matches chase node k), blocks of STRIDE=4 events precompose into
    dense per-block transition tables (first-hit offsets for all
    chase nodes bit-packed into one u32 per block), and the block-level
    next pointers ride ONE associative scan over T/4 elements — a
    multi-stride dense table walk instead of per-event stepping
    (cf. 2209.05686, CAMA 2112.00267).  Threshold and count hops share
    the first-hit machinery (the "hybrid" part).

Eligibility (classify_parallel) is strict and *sound*: anything outside
the supported algebra reports a reason string and the planner keeps the
sequential kernel (or the chunked-halo mode) — the families never guess.
The accepted algebra (byte-identical to the sequential kernel, asserted
by tests/test_plan_families.py):

  * linear chains of stream positions, within-bounded, `every` or
    single-arm (non-`every`) heads;
  * (1,1) positions with event-only filters plus at most one monotone
    threshold conjunct below the head;
  * `<m:n>` count quantifiers (min >= 1; unbounded max allowed except
    in the final position), event-only filters, incl. count heads and
    indexed capture reads (e1[0] / e1[last] / e1[last-1]);
  * logical AND/OR partner pairs of two stream nodes below the head,
    event-only filters (OR's unmatched side null-reconstructs through
    the presence rows, like the sequential kernel);
  * strict sequences (`,` succession): each hop reads the immediately
    next event, so capture-dependent filters are evaluated directly —
    arbitrary conjunctions allowed;
  * fused multi-query lanes (per-lane `__qparam` constants) and
    partitioned per-key lanes, both via ONE vmap of the flat block
    over the lane axis (pattern_plan ships (L, F) grids).

Cross-flush continuity reuses the chunked-halo harness in
pattern_plan.py: blocks are stateless, the last `within` window of
events replays at the next flush, and completions at or before the
previous flush's last seq are suppressed on device (per lane, for
partitioned grids).  Non-`every` chains additionally report a per-lane
resolution flag in the meta row so the host stops dispatching once the
single arm has definitively completed or died.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..query import ast
from .expr import (ExprError, compile_expression, compute_dtypes)
from .nfa_device import (ChainSpec, NFAKernel, _base_ref, _hi32, _lo32,
                         _I32, pow2_at_least)

STRIDE = 4                # dfa family: events per precomposed transition
_OFF_BITS = 3             # bits per packed first-hit offset (0..STRIDE)
NUMERIC = (ast.AttrType.INT, ast.AttrType.LONG,
           ast.AttrType.FLOAT, ast.AttrType.DOUBLE)
UNBOUNDED = 10 ** 9       # NFACompiler's normalization of <m:> counts
# single-arm (non-`every`) resolution flag, meta row slot 4
ARM_NONE, ARM_PENDING, ARM_RESOLVED = 0, 1, 2


class ParallelUnsupported(Exception):
    """Chain shape outside the parallel families' sound subset."""


@dataclass
class HopThreshold:
    """One monotone capture-dependent conjunct: own_col OP rhs(captures)."""
    own_key: str                  # "e2.price" — the arriving event's column
    op: str                       # "gt" | "ge" | "lt" | "le"
    rhs: object                   # CompiledExpr over earlier-ref captures
    own_type: ast.AttrType = ast.AttrType.DOUBLE


@dataclass
class HopNode:
    """One lowered stream node inside a chase position."""
    ref: str
    scode: int
    pre_conjs: list = field(default_factory=list)   # CompiledExpr, event-only
    threshold: Optional[HopThreshold] = None
    step_conjs: list = field(default_factory=list)  # sequence-mode direct eval

    @property
    def is_static(self) -> bool:
        return self.threshold is None and not self.step_conjs


@dataclass
class PPos:
    """One chain position lowered for the state chase."""
    kind: str                     # "single" | "count" | "logical"
    nodes: list                   # [HopNode]; 2 for logical
    within_ms: int = 0
    op: Optional[str] = None      # "and" | "or" (logical)
    min_count: int = 1
    max_count: int = 1


@dataclass
class ParallelProgram:
    positions: list               # [PPos], index = chain position
    stream_ids: list
    schemas: dict                 # ref -> StreamSchema
    ref_of: dict                  # ref -> (position index, node index)
    sequence: bool = False        # strict `,` succession
    single_arm: bool = False      # non-`every` head (one instance ever)

    @property
    def S(self) -> int:
        return len(self.positions)

    @property
    def count_refs(self) -> set:
        return {p.nodes[0].ref for p in self.positions if p.kind == "count"}


_FLIP = {"gt": "lt", "ge": "le", "lt": "gt", "le": "ge"}
_OPN = {ast.CompareOp.GT: "gt", ast.CompareOp.GE: "ge",
        ast.CompareOp.LT: "lt", ast.CompareOp.LE: "le"}


def _own_var(e, node, schemas) -> Optional[str]:
    """Attr name when `e` is a plain Variable over the node's OWN event
    (qualified with its ref, or unqualified resolving to its schema —
    PatternFilterContext resolution order), else None."""
    if not isinstance(e, ast.Variable) or e.index is not None:
        return None
    if e.stream_ref == node.ref:
        return e.attribute
    if e.stream_ref is None and e.attribute in schemas[node.ref].types:
        return e.attribute
    return None


def lower_parallel(spec: ChainSpec, strings,
                   param_extra: Optional[dict] = None) -> ParallelProgram:
    """Lower a ChainSpec into a state-chase program, or raise
    ParallelUnsupported with the (human-readable) ineligibility reason.
    See the module docstring for the accepted algebra."""
    if spec.S < 2:
        raise ParallelUnsupported("single-position chain (no scan depth)")
    sequence = bool(spec.is_sequence)
    single_arm = not spec.every_head
    positions: list = []
    ref_of: dict = {}
    count_refs: set = set()
    or_refs: set = set()
    S = spec.S
    for pi, pos in enumerate(spec.positions):
        for n in pos.nodes:
            if n.kind != "stream":
                raise ParallelUnsupported("absent (`not ... for`) position")
        if pos.sticky and pi > 0:
            raise ParallelUnsupported("`every` below the head")
        if pos.within_ms is None:
            raise ParallelUnsupported(
                "position without a `within` bound (stateless tail replay "
                "needs a finite horizon)")
        if pos.op is not None:
            if pi == 0:
                raise ParallelUnsupported("logical and/or head")
            if sequence:
                raise ParallelUnsupported(
                    "logical and/or position in a strict sequence")
            if pi > 0 and spec.positions[pi - 1].is_count:
                raise ParallelUnsupported("logical position after a count "
                                          "(no station to consume the arm)")
            nodes = []
            for n in pos.nodes:
                if n.step_conjs:
                    raise ParallelUnsupported(
                        "capture-dependent filter on a logical position")
                nodes.append(HopNode(n.ref, n.scode, list(n.pre_conjs)))
            pp = PPos("logical", nodes, pos.within_ms, op=pos.op)
            if pos.op == "or":
                or_refs.update(n.ref for n in pos.nodes)
        elif pos.is_count:
            if sequence:
                raise ParallelUnsupported(
                    "count quantifier in a strict sequence")
            if pos.min_count < 1:
                raise ParallelUnsupported(
                    "optional count quantifier (min 0 arms on entry)")
            if pi > 0 and spec.positions[pi - 1].is_count:
                raise ParallelUnsupported("adjacent count positions")
            if pi == S - 1 and (pos.max_count >= UNBOUNDED
                                or pos.max_count - pos.min_count + 1 > 8):
                raise ParallelUnsupported(
                    "unbounded or wide count in the final position "
                    "(one emission lane per allowed occurrence)")
            n = pos.nodes[0]
            if n.step_conjs:
                raise ParallelUnsupported(
                    "capture-dependent filter on a count position")
            pp = PPos("count", [HopNode(n.ref, n.scode, list(n.pre_conjs))],
                      pos.within_ms, min_count=pos.min_count,
                      max_count=pos.max_count)
            count_refs.add(n.ref)
        else:
            n = pos.nodes[0]
            hop = HopNode(n.ref, n.scode, list(n.pre_conjs))
            if n.step_conjs:
                if pi == 0:
                    raise ParallelUnsupported("head filter reads captures")
                if sequence:
                    # the strict next event is KNOWN (j+1): evaluate the
                    # conjunction directly, no monotonicity needed
                    hop.step_conjs = list(n.step_conjs)
                    _check_step_reads(n.step_conjs, n.ref, ref_of,
                                      count_refs, param_extra)
                else:
                    if len(n.step_conjs) > 1:
                        raise ParallelUnsupported(
                            "multiple capture-dependent conjuncts on one "
                            "position (first-match of a conjunction is not "
                            "decomposable)")
                    hop.threshold = _lower_threshold(
                        n, n.step_asts[0], spec, strings, param_extra,
                        ref_of, count_refs, or_refs)
            pp = PPos("single", [hop], pos.within_ms)
        positions.append(pp)
        for ni, hn in enumerate(pp.nodes):
            ref_of[hn.ref] = (pi, ni)
    return ParallelProgram(positions, list(spec.stream_ids),
                           dict(spec.schemas), ref_of, sequence=sequence,
                           single_arm=single_arm)


def _check_step_reads(step_conjs, own_ref, ref_of, count_refs, param_extra):
    """Sequence-mode step conjuncts: reads must be the own event's
    columns, earlier FROZEN captures, params, or __timestamp__."""
    for ce in step_conjs:
        for k in ce.reads:
            if k == "__timestamp__" or (param_extra and k in param_extra):
                continue
            if "." not in k:
                raise ParallelUnsupported(
                    f"step filter reads non-capture key {k!r}")
            base = _base_ref(k.split(".", 1)[0])[0]
            if base == own_ref:
                continue
            if base in count_refs:
                raise ParallelUnsupported(
                    "step filter reads a still-collecting count capture")
            if base not in ref_of:
                raise ParallelUnsupported(
                    f"step filter reads unresolved key {k!r}")


def _lower_threshold(node, cond, spec, strings, param_extra,
                     ref_of, count_refs, or_refs=()) -> HopThreshold:
    """`own.attr OP expr(earlier captures)` -> HopThreshold, else raise."""
    from .nfa_device import PatternFilterContext
    if not isinstance(cond, ast.Compare) or cond.op not in _OPN:
        raise ParallelUnsupported(
            "capture-dependent filter is not a <,<=,>,>= comparison")
    own_l = _own_var(cond.left, node, spec.schemas)
    own_r = _own_var(cond.right, node, spec.schemas)
    if (own_l is None) == (own_r is None):
        raise ParallelUnsupported(
            "comparison must have the arriving event's attribute on "
            "exactly one side")
    attr = own_l if own_l is not None else own_r
    op = _OPN[cond.op] if own_l is not None else _FLIP[_OPN[cond.op]]
    own_t = spec.schemas[node.ref].type_of(attr)
    if own_t not in NUMERIC:
        raise ParallelUnsupported(
            f"threshold attribute {attr!r} is not numeric")
    rhs_ast = cond.right if own_l is not None else cond.left
    ctx = PatternFilterContext(spec.schemas, strings, node.ref)
    if param_extra:
        ctx.extra = dict(param_extra)
    try:
        rhs = compile_expression(rhs_ast, ctx)
    except ExprError as e:
        raise ParallelUnsupported(f"threshold rhs not compilable: {e}")
    if rhs.type not in NUMERIC:
        raise ParallelUnsupported("threshold rhs is not numeric")
    ok_reads = set()
    for r in ref_of:
        for a in spec.schemas[r].attributes:
            ok_reads.add(f"{r}.{a.name}")
    if param_extra:
        ok_reads.update(param_extra)
    bad = set(rhs.reads) - ok_reads
    if bad:
        raise ParallelUnsupported(
            f"threshold rhs reads non-capture keys {sorted(bad)!r} "
            f"(own event / timestamp / later positions)")
    for k in rhs.reads:
        if "." not in k:
            continue
        base = _base_ref(k.split(".", 1)[0])[0]
        if base in count_refs:
            raise ParallelUnsupported(
                "threshold rhs reads a still-collecting count capture")
        if base in or_refs:
            raise ParallelUnsupported(
                "threshold rhs reads a maybe-absent `or` capture")
    return HopThreshold(f"{node.ref}.{attr}", op, rhs, own_t)


def classify_parallel(spec: ChainSpec, kernel: NFAKernel, strings,
                      param_extra: Optional[dict] = None) -> dict:
    """{'scan': True|reason, 'dfa': True|reason} for one lowered chain.
    A True value means the family is sound for this ChainSpec; a string
    is the ineligibility reason (surfaced in statistics() and asserted
    by the forced-fallback tests)."""
    try:
        prog = lower_parallel(spec, strings, param_extra)
        count_refs = prog.count_refs
        logical_refs = {n.ref for p in prog.positions
                        if p.kind == "logical" for n in p.nodes}
        for ce in (list(kernel.sel_fns.values())
                   + ([kernel.having] if kernel.having else [])):
            is_having = kernel.having is not None and ce is kernel.having
            for k in ce.reads:
                if "." not in k or k.startswith("__"):
                    continue
                refpart = k.split(".", 1)[0]
                base, cidx = _base_ref(refpart)
                if cidx is not None:
                    if base in count_refs and (
                            cidx in ("last", "last-1") or cidx.isdigit()):
                        pass            # rank/select-resolvable
                    elif cidx == "last" and base in prog.ref_of:
                        pass            # [last] over a (1,1) ref == plain
                    else:
                        raise ParallelUnsupported(
                            f"indexed capture read {k!r} outside a count "
                            f"position")
                if is_having and base in logical_refs:
                    raise ParallelUnsupported(
                        "having reads a capture of a logical (maybe-"
                        "absent) position")
    except ParallelUnsupported as e:   # lint: allow-swallow (the reason
        # string IS the demotion record — the planner surfaces it via
        # plan.families / rt.explain())
        return {"scan": str(e), "dfa": str(e)}
    return _classify_prog(prog)


def _chase_lanes(prog: ParallelProgram) -> list:
    """Static chase nodes (pi, ni) that resolve via next-match pointers —
    the dfa family's bit-packable symbol lanes.  Count positions resolve
    via rank/select and threshold hops via a first-hit query; neither
    consumes a symbol bit."""
    lanes = []
    for pi, pos in enumerate(prog.positions):
        if pi == 0:
            continue
        if pos.kind == "single" and pos.nodes[0].is_static:
            lanes.append((pi, 0))
        elif pos.kind == "logical":
            lanes.extend((pi, ni) for ni in range(len(pos.nodes)))
    return lanes


@dataclass(frozen=True)
class ExpiryAsk:
    """One place the lane block needs a head's expiry index: "the first
    event at or after s past the horizon `head ts + within_ms`"."""
    within_ms: int
    fresh: bool               # build the query; False = reuse the held index


# hop kind -> does the hop apply the expiry index it asks for to every
# surviving instance (step_fail / the final count's `ok & (jc < kl)`), so
# that alive implies j < kl afterwards?  Sharing leans on exactly this.
_ENFORCES_EXPIRY = {"count": True, "single": True, "logical": True,
                    "sequence": False}   # strict succession tests ts[j+1]


def _expiry_plan(prog: ParallelProgram) -> dict:
    """{position index: ExpiryAsk} for every position at which the lane
    block needs a head's expiry index (a count head at 0, every
    non-sequence hop, a final count at S-1 — asked in emit_candidates).

    kl(s) = min{i >= s : ts[i] > horizon}, L when none.  A hop that asked
    from s and enforced its answer leaves every surviving instance at an
    index j with s <= j < kl(s); the next hop asks from j + 1, which lies
    in (s, kl(s)], so under the SAME horizon kl(j + 1) == kl(s): the
    smaller range cannot hold an earlier hit, and still holds kl(s).
    Nothing there orders ts, so regressed timestamps share alike; dead
    instances never observe kl.  A query is therefore fresh exactly when
    no index is held, the horizon (anchor, within_ms) differs from the
    held one's, or a hop since did not enforce it."""
    asks: dict = {}
    held = None                  # horizon every surviving instance is inside
    pend = None                  # armed count position awaiting its advance
    S = prog.S
    for pi, pos in enumerate(prog.positions):
        kind = "sequence" if prog.sequence and pos.kind == "single" \
            else pos.kind
        if pi == 0 and kind != "count":
            continue             # a (1,1) head waits for nothing
        if kind == "sequence":
            held = None
            continue
        # a count's successor consumes the armed count: the station never
        # waits AT the successor, so the COUNT's within bounds that advance
        # and the successor's own never applies (host parity: at_pos is
        # never true for a count's successor)
        src = pend if kind == "single" and pend is not None else pos
        # (anchor, span): every horizon counts from the head's ts today
        horizon = ("head", src.within_ms)
        asks[pi] = ExpiryAsk(src.within_ms, fresh=horizon != held)
        held = horizon if _ENFORCES_EXPIRY[kind] else None
        if kind == "single":
            pend = None
        elif kind == "count" and pi < S - 1:
            pend = pos
    return asks


def _classify_prog(prog: ParallelProgram) -> dict:
    """Family verdicts for a successfully-lowered chase program (shared
    between the built-kernel classifier above and the analysis-time
    classify_shape below)."""
    out = {"scan": True}
    lanes = _chase_lanes(prog)
    if prog.sequence:
        out["dfa"] = ("strict sequence (consecutive-event steps leave "
                      "nothing to bit-pack)")
    elif len(lanes) > 8:
        out["dfa"] = ("more than 8 positions (symbol words bit-pack one "
                      "position per u32 lane bit)")
    elif not lanes:
        out["dfa"] = ("no static transition to bit-pack (every hop is "
                      "threshold- or count-dependent)")
    else:
        out["dfa"] = True
    return out


def classify_shape(state_input, schemas, strings,
                   partitioned: bool = False) -> dict:
    """Analysis-time family eligibility for a raw AST pattern input:
    {'chunk'|'scan'|'dfa': True | reason} with the SAME reason strings
    classify_parallel reports for a built kernel — computable without
    constructing a device plan.  Used by the static analyzer's
    annotation-conflict rule (SA08, docs/ANALYSIS.md) so a forced
    `@app:patternFamily` on a provably ineligible shape is flagged at
    analysis time, before a deploy quietly falls back.

    `schemas` maps stream id -> StreamSchema for every stream the
    pattern consumes; a shape the device chain lowering itself rejects
    reports that reason for every family.  `partitioned` applies the
    per-key lane-vmap gates pattern_plan applies for patterns inside a
    `partition with (...)` block."""
    from ..interp.engine import _collect_filters
    from .nfa_device import lower_chain
    try:
        spec = lower_chain(state_input, schemas, strings,
                           _collect_filters(state_input.state))
    except Exception as e:   # lint: allow-swallow (reason IS the record)
        r = f"device chain lowering unavailable: {e}"
        return {"chunk": r, "scan": r, "dfa": r}
    # the stateless-harness gates DevicePatternPlan applies before any
    # family runs (pattern_plan.py "plan-family selection")
    base = True
    if any(n.kind != "stream" for n in spec.all_nodes) \
            or spec.needs_init_slot:
        base = "absent state (timer-driven deadlines need device state)"
    elif not all(p.within_ms is not None for p in spec.positions):
        base = "position without a `within` bound"
    if base is not True:
        return {"chunk": base, "scan": base, "dfa": base}
    if partitioned:
        out = {"chunk": "partitioned (the lane axis holds partition keys)"}
    elif not spec.every_head:
        out = {"chunk": "non-`every` head (single stateful arm)"}
    else:
        out = {"chunk": True}
    try:
        prog = lower_parallel(spec, strings)
        out.update(_classify_prog(prog))
        if partitioned and prog.single_arm:
            r = ("non-`every` head with partitioned lanes (per-key "
                 "single-arm state)")
            out.update({"scan": r, "dfa": r})
    except ParallelUnsupported as e:   # lint: allow-swallow (reason IS
        # the analysis-time record)
        out.update({"scan": str(e), "dfa": str(e)})
    return out


# ---------------------------------------------------------------------------
# vectorized "first index >= s with masked value OP v" primitives
# ---------------------------------------------------------------------------

def _sentinel(dt, agg: str):
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.array(-jnp.inf if agg == "max" else jnp.inf, dt)
    info = jnp.iinfo(dt)
    return jnp.array(info.min if agg == "max" else info.max, dt)


def _tree_dtype(own_dt, rhs_dt):
    """Dtype the threshold tree aggregates (and compares) in: the
    promotion of both comparison sides, with int32 widened to int64 so
    the sentinel sits strictly OUTSIDE the value range — `>=`/`<=` hit
    checks must never be satisfiable by a masked-out leaf (an int32
    column whose rhs equals INT32_MIN would otherwise match them).
    Mixed int/float comparisons promote to the float side, whose ±inf
    sentinels are strictly outside every value, and whose rounding then
    matches the sequential kernel's own promoted per-event compare."""
    dt = jnp.promote_types(own_dt, rhs_dt)
    if dt == jnp.int32:
        return jnp.dtype(jnp.int64)
    return dt


def _build_heap(vals, mask, L: int, agg: str, dt):
    """Perfect binary segment tree in heap layout (1-based; leaves at
    [L, 2L)).  Built with log2(L) vectorized reductions — the SFA
    transition-composition tree for threshold hops.  Masked-out and NaN
    leaves are replaced by the sentinel BEFORE aggregation: the
    sequential kernel evaluates the predicate per event (NaN compares
    False there), while jnp.maximum/minimum would propagate a NaN to
    every ancestor and poison whole subtrees."""
    sent = _sentinel(dt, agg)
    keep = mask
    if jnp.issubdtype(vals.dtype, jnp.floating):
        keep = keep & ~jnp.isnan(vals)
    vals = jnp.where(keep, vals.astype(dt), sent)
    red = jnp.maximum if agg == "max" else jnp.minimum
    lvl = jnp.full((L,), sent, dt).at[:vals.shape[0]].set(vals)
    levels = [lvl]
    while lvl.shape[0] > 1:
        lvl = red(lvl[0::2], lvl[1::2])
        levels.append(lvl)
    # heap[1]=root ... heap[L:2L)=leaves; heap[0] unused (sentinel)
    return jnp.concatenate([jnp.full((1,), sent, dt)]
                           + [lv for lv in reversed(levels)])


_CMP = {"gt": lambda a, b: a > b, "ge": lambda a, b: a >= b,
        "lt": lambda a, b: a < b, "le": lambda a, b: a <= b}


def _first_hit(heap, L: int, s, v, op: str):
    """The tree form: first leaf index >= s whose value satisfies OP v; L
    when none.  Vectorized over query arrays s, v; 2*log2(L) rounds of
    data-dependent gathers (up-walk decomposing [s, L) into aligned blocks
    visited left to right, then a descent into the first qualifying
    subtree), each round a gather of one element a query.

    Hit checks are sentinel-safe.  Integer `>=`/`<=` rewrite to strict
    compares against the adjacent value (exact: int32 trees are widened;
    an int64 rhs of exactly INT64_MIN/MAX is the accepted corner).
    Floating `>=`/`<=` compare directly and refuse the sentinel itself, so
    a masked-out leaf never answers an infinite rhs (real infinite data
    meeting an infinite rhs of its own sign is the accepted corner).  NOT
    through nextafter: the neighbour of 0.0 is a denormal, which the
    backend's compare flushes to zero, and `price >= 0.0` lost its
    `price == 0.0` rows."""
    v = jnp.asarray(v, heap.dtype)
    cmp = _CMP[op]
    if jnp.issubdtype(heap.dtype, jnp.floating):
        if op == "ge":
            cmp = lambda a, b: (a >= b) & (a > -jnp.inf)    # noqa: E731
        elif op == "le":
            cmp = lambda a, b: (a <= b) & (a < jnp.inf)     # noqa: E731
    elif op == "ge":
        v, cmp = v - 1, _CMP["gt"]
    elif op == "le":
        v, cmp = v + 1, _CMP["lt"]
    P = max(L.bit_length() - 1, 0)

    # fori_loop (not an unrolled python loop): the round count is static
    # but the body is identical each round, and unrolling 2*log2(L)
    # gather rounds made the XLA program ~4x slower to COMPILE — which
    # dominates small deployments (every pattern test runtime pays it)
    def up(i, st):
        l, found, fnode = st
        r = jnp.int32(2 * L) >> i
        odd = (l & 1) == 1
        nv = heap[jnp.clip(l, 0, 2 * L - 1)]
        take = odd & (l < r) & cmp(nv, v) & ~found
        fnode = jnp.where(take, l, fnode)
        found = found | take
        return ((l + odd.astype(_I32)) >> 1, found, fnode)

    l0 = (jnp.clip(s, 0, L) + L).astype(_I32)
    _l, found, fnode = lax.fori_loop(
        0, P + 1, up, (l0, jnp.zeros(l0.shape, bool),
                       jnp.zeros(l0.shape, _I32)))

    def down(_i, fnode):
        internal = found & (fnode < L)
        left = 2 * fnode
        lv = heap[jnp.clip(left, 0, 2 * L - 1)]
        goleft = cmp(lv, v)
        return jnp.where(internal,
                         jnp.where(goleft, left, left + 1), fnode)

    fnode = lax.fori_loop(0, P, down, fnode)
    return jnp.where(found, fnode - L, L).astype(_I32)


def _first_hit_dense(vals, keep, L: int, s, v, op: str):
    """The dense form of the same question: a masked min-reduction over
    every (event, query) pair of the block,

        out[h] = min over i of (i  if  i >= s[h] and keep[i]
                                       and vals[i] OP v[h]   else  L)

    No tree, no gather, no loop and no sentinel, so the compare runs in
    the plain promotion of the two sides and `>=`/`<=` are themselves.
    NaN values never hit (the sequential kernel's per-event compare is
    False on them); a NaN rhs hits nothing.  Events lie along axis 0 and
    queries along axis 1: the reduction then runs down the sublanes and
    leaves the answers a query a lane, the layout every caller holds its
    per-head arrays in.

    An int32 column against an int64 rhs (the expiry query: ts offsets
    reach +-2^30 and `ts + within` passes 2^31) compares in int32 against
    the rhs SATURATED to int32, which is exact: a rhs above INT32_MAX is
    beaten by nothing (`gt`/`ge`) or by everything (`lt`/`le`), one below
    INT32_MIN the other way round, and both are settled a query."""
    F = vals.shape[0]
    s, v = jnp.broadcast_arrays(jnp.asarray(s, _I32), jnp.asarray(v))
    if jnp.issubdtype(vals.dtype, jnp.floating):
        keep = keep & ~jnp.isnan(vals)
    every = None
    if vals.dtype == jnp.int32 and v.dtype == jnp.int64:
        info = jnp.iinfo(jnp.int32)
        below, above = v < info.min, v > info.max
        every, none = (below, above) if op in ("gt", "ge") \
            else (above, below)
        s = jnp.where(none, jnp.int32(L), s)
        v = jnp.clip(v, info.min, info.max).astype(jnp.int32)
    else:
        dt = jnp.promote_types(vals.dtype, v.dtype)
        vals, v = vals.astype(dt), v.astype(dt)
    i = jnp.arange(F, dtype=_I32)[:, None]
    beats = _CMP[op](vals[:, None], v[None, :])
    if every is not None:
        beats = beats | every[None, :]
    hit = keep[:, None] & (i >= s[None, :]) & beats
    return jnp.min(jnp.where(hit, i, jnp.int32(L)), axis=0)


# Which form answers a block's first-hit queries.  Both forms cost the
# same for every lane and every query, so the choice is a bound on F, the
# events one lane of the block holds: its static shape, and nothing else
# is consulted.  A query costs the tree log2(L) to 2*log2(L)+1 rounds of
# one dependent gather an element, and costs the dense form F pairs.  Two
# TPU v5e readings decide (PERF.md section 5, PR 30): a round takes
# 10.25 ns an element at every shape from 1024 x 64 to 16 x 8192 (twice to
# three times that on the emulated-int64 expiry tree); the fused
# compare-select-min takes 2.0-2.6 ps a pair from 2e8 pairs a call up, at
# F = 448 as at F = 8192, and never holds the (lanes, F, F) intermediate.
# At F = 448 that is 1 ns against 92-195 ns a query; the forms cross where
# F * 2.2 ps = rounds * 10.25 ns, between F = 80,000 (rounds = log2 L) and
# 170,000 (2*log2(L)+1).  The bound sits a factor of twenty under that:
# nothing was measured past F = 8192; pairs grow as F squared, so a lane
# near the crossover costs seconds a call either way (1024 lanes x 16384^2
# pairs are 0.6 s a query); and a backend that does not fuse the reduction
# (the CPU test lane) holds F*F*4 bytes a lane, 64 MB at the bound.  4096
# is also the smallest flat P = 1 block the plan ships (pattern_plan's
# f_min): a small unpartitioned flush is dense, and one of 2^18 events, or
# a fused multi-query lane that sees the whole stream, walks the tree.
#
# The same bound picks the form of an indexed read (_Read), and there it
# sits AT the crossing, not a factor under it.  A gather of lanes x F
# elements costs 10.25 ns an element at 1024 x 448, 256 x 448, 1024 x 64
# and 2048 x 2048 alike, f32 or i32 (TPU v5e, PERF.md section 5, PR 36).
# The fused one-hot sum costs F pairs an element: 0.84 ps a pair at
# F = 64, 0.60 at F = 448 (0.27 ns an element, 38 times under the
# gather), and at F = 2048 0.67 ps over 1088 lanes but 2.22 ps over 2048
# (4.5 ns an element: the slowest reading, whose cause is the grid's size
# and not F alone, PERF.md section 7.7); reads that share an index array
# share the compare (two 1.13, five 0.87 ps a pair at 2048 x 2048).
# 10.25 ns / 2.22 ps is F = 4,600.  Nothing was measured between 2048 and
# the bound: a flat F = 4096 block reads 9.1 ns an element at the slowest
# rate, even with the gather.
#
# And it picks the form of the compaction (_Compact), again at the
# crossing.  A scatter of lanes x F candidates into lanes x M rows costs
# 4.4-4.7 ns a candidate a column (TPU v5e, PERF.md section 5, PR 38:
# 2.12 ms at 1024 x 448, 84.97 at 72 x 250 x 1024 into M = 192), and once
# its rows number 2^22 or more the compiler sorts the (row, value) pairs
# first: 7.3 ns at 72 x 250 x 1024 into M = 256 or 384 (two scatters 89.8 ms
# each and two `sort`s 44.7), 5.8 at 2048 x 2048 (20.45 and 3.79).  The
# fused one-hot sum costs M pairs a candidate, both columns in one pass:
# 0.92 ps a pair at 1024 x 448 x 448 (0.19 ms against 4.23), 1.5-2.9 at
# 72 x 250 x 1024 x {192, 256, 384} (5.5 / 13.5 / 18.2 ms against 170 / 269 /
# 269) and 2.2 at 2048 x 2048 x 2048 (19.0 against 48.5: the closest
# reading).  Two columns by scatter are 9-15 ns a candidate, so the forms
# cross at M of 4,000-6,500 at the slowest rate, and no cell sits between
# F = 2048 and the bound.  The form is read off F alone though the cost is
# C*F*M pairs: a first dispatch has M = F, or less from counts, but the
# overflow's exact re-run (_materialize_par) raises M to the count's
# bucket, up to C*F where a final position counts (C > 1) and 2*F for a
# cut fused row.  At F near the bound that re-run is the accepted
# exception: at F = 4096, C = 3, M = 12,288 the dense pass is ~0.33 ms a
# lane against ~0.18 by scatter (computed from the rates above, not
# measured), once, on a flush that already pays a second dispatch and a
# compile; the rows are bit-equal either way.
DENSE_MAX_F = 4096


class _FirstHit:
    """One block's entry for "the first index i >= s[h] with keep[i] and
    vals[i] OP v[h]; L if none", asked by every query h at once: the
    expiry query, a threshold hop and rank/select are this one question.
    A lane of at most DENSE_MAX_F events reduces all (event, query) pairs
    (_first_hit_dense); a longer one builds a segment tree over the
    column, once per (column, mask, direction), and walks it
    (_build_heap, _first_hit).  Counts what it was asked while the block
    is traced: rt.explain()'s `first_hit`."""

    def __init__(self, F: int, L: int):
        self.F, self.L = F, L
        self.dense = F <= DENSE_MAX_F
        self.queries = 0
        self.pairs = 0            # (event, query) pairs a lane, dense form
        self._heaps: list = []    # (vals, keep, agg, dtype, heap)

    def __call__(self, vals, keep, s, v, op: str):
        self.queries += 1
        if self.dense:
            out = _first_hit_dense(vals, keep, self.L, s, v, op)
            self.pairs += self.F * out.shape[0]
            return out
        agg = "max" if op in ("gt", "ge") else "min"
        dt = _tree_dtype(vals.dtype, jnp.asarray(v).dtype)
        for a, k, g, d, heap in self._heaps:
            if a is vals and k is keep and g == agg and d == dt:
                break
        else:
            with jax.named_scope("heap"):
                heap = _build_heap(vals, keep, self.L, agg, dt)
            self._heaps.append((vals, keep, agg, dt, heap))
        return _first_hit(heap, self.L, s, v, op)

    def asked(self, lanes: int) -> dict:
        """What one call of the traced block asks, over all its lanes."""
        return {"dense": self.queries if self.dense else 0,
                "tree": 0 if self.dense else self.queries,
                "pairs_per_call": lanes * self.pairs,
                "lanes": lanes, "F": self.F}


def _one_hot_sum(cols: list, hit) -> list:
    """[sum over i of (bits[i]  if  hit[i, m]  else  0) for each column],
    where at most ONE i hits a given m and `bits` is the column as int32:
    float32 and uint32 bitcast, bool and the narrower ints widened.  At
    most one term of each sum is non-zero, so the answer is that element
    bit for bit (NaN payloads, -0.0, infinities and denormals pass through
    as integers: nothing is compared or added as a float), and 0 where no
    i hits.  The summed axis is 0 and the answers lie along axis 1,
    _first_hit_dense's layout; the compiler fuses compare, select and sum
    and never holds the pairs.  The columns are summed by one variadic
    reduce: `hit` is made once for all of them."""
    cast = [c.dtype in (jnp.float32, jnp.uint32) for c in cols]
    bits = [lax.bitcast_convert_type(c, _I32) if b else c.astype(_I32)
            for c, b in zip(cols, cast)]
    sums = lax.reduce(
        [jnp.where(hit, b[:, None], jnp.int32(0)) for b in bits],
        [jnp.int32(0)] * len(bits),
        lambda x, y: tuple(p + q for p, q in zip(x, y)), (0,))
    return [lax.bitcast_convert_type(o, c.dtype) if b else o.astype(c.dtype)
            for o, c, b in zip(sums, cols, cast)]


def _read_dense(cols: list, idx) -> list:
    """[col[clip(idx, 0, len(col) - 1)] for col in cols] without a gather:
    a one-hot sum (_one_hot_sum) over every (event, asker) pair of the
    lane, hit[i, m] = i == clip(idx[m]): exactly one event a sum."""
    n = cols[0].shape[0]
    i = jnp.arange(n, dtype=_I32)[:, None]
    hit = i == jnp.clip(idx.reshape(-1), 0, n - 1).astype(_I32)[None, :]
    return [o.reshape(idx.shape) for o in _one_hot_sum(cols, hit)]


def _compact_dense(cols: list, wpos, M: int) -> list:
    """[zeros(M).at[wpos].set(col, mode="drop") for col in cols] without a
    scatter, for a `wpos` that sends no two candidates to one row: a
    one-hot sum (_one_hot_sum) over every (candidate, row) pair of the
    lane, hit[i, m] = wpos[i] == m.  A row nobody writes sums nothing and
    stays 0, as the scatter's zeros do; a candidate sent to M or past it
    hits no row, as `mode="drop"` drops it."""
    hit = wpos[:, None] == jnp.arange(M, dtype=_I32)[None, :]
    return _one_hot_sum(cols, hit)


class _Read:
    """One block's entry for "the column's element at idx[m]", asked by
    every m at once (idx clipped into the column, as every caller's
    downstream mask expects): the chase's per-head reads, the capture
    indices and the selected values of the M match rows.  The form follows
    the block's F by _FirstHit's rule and constant: a lane of at most
    DENSE_MAX_F events is read by one fused one-hot sum (_read_dense), a
    longer one, or a column wider than four bytes (f64 mode), by a
    gather.  A read at the block's own arange is the column itself.
    Counts what it was asked while the block is traced: rt.explain()'s
    `indexed_read`."""

    def __init__(self, F: int, j0):
        self.F, self.j0 = F, j0
        self.dense = self.gather = self.identity = 0
        self.pairs = 0            # (event, asker) pairs a lane, dense form

    def __call__(self, col, idx):
        return self.all([col], idx)[0]

    def all(self, cols: list, idx) -> list:
        """The columns (one length) at one index array: the dense form
        then compares once for all of them."""
        if idx is self.j0 and cols[0].shape[0] == self.F:
            self.identity += len(cols)
            return list(cols)
        short = [self.F <= DENSE_MAX_F and c.dtype.itemsize <= 4
                 for c in cols]
        dense = iter(_read_dense([c for c, d in zip(cols, short) if d], idx)
                     if any(short) else ())
        self.dense += sum(short)
        self.gather += len(cols) - sum(short)
        self.pairs += sum(short) * cols[0].shape[0] * idx.size
        at = jnp.clip(idx, 0, cols[0].shape[0] - 1)
        return [next(dense) if d else c[at] for c, d in zip(cols, short)]

    def asked(self, lanes: int) -> dict:
        """What one call of the traced block reads, over all its lanes."""
        return {"dense": self.dense, "gather": self.gather,
                "identity": self.identity,
                "pairs_per_call": lanes * self.pairs,
                "lanes": lanes, "F": self.F}


class _Compact:
    """One block's entry for "these columns of the live candidates, in
    candidate order, as the M match rows": `live` over the C*F (slot,
    head) candidates, a prefix count for the row each live one takes, and
    the columns written there.  Rows past the live count are 0, live
    candidates past M are dropped, and `n` counts every live one, so the
    caller sees an overflow whole.  The form follows the block's F by
    _FirstHit's rule and constant: a lane of at most DENSE_MAX_F events
    fills the rows by one fused one-hot sum (_compact_dense), a longer one
    by a scatter a column.  Counts what it was asked while the block is
    traced: rt.explain()'s `compaction`."""

    def __init__(self, F: int, M: int):
        self.F, self.M = F, M
        self.dense = self.scatter = 0
        self.pairs = 0        # (candidate, row) pairs a lane, dense form

    def __call__(self, cols: list, live):
        """(the columns compacted, n).  Columns and `live` are (C*F,)."""
        M = self.M
        pos = jnp.cumsum(live.astype(_I32), dtype=_I32) - live
        n = pos[-1] + live[-1]
        wpos = jnp.where(live & (pos < M), pos, M)
        if self.F <= DENSE_MAX_F:
            self.dense += len(cols)
            self.pairs += len(cols) * live.shape[0] * M
            return _compact_dense(cols, wpos, M), n
        self.scatter += len(cols)
        return [jnp.zeros((M,), c.dtype).at[wpos].set(c, mode="drop")
                for c in cols], n

    def asked(self, lanes: int) -> dict:
        """What one call of the traced block compacts, over all its lanes."""
        return {"dense": self.dense, "scatter": self.scatter,
                "pairs_per_call": lanes * self.pairs,
                "lanes": lanes, "F": self.F, "M": self.M}


def _next_static_scan(mask, L: int):
    """next[t] = first index >= t with mask set (L = none): ONE reverse
    associative scan in the min semiring — the SFA composition of
    per-event transition functions restricted to a static position."""
    F = mask.shape[0]
    idx = jnp.where(mask, jnp.arange(F, dtype=_I32), jnp.int32(L))
    return lax.associative_scan(jnp.minimum, idx, reverse=True)


def _prev_static_scan(mask):
    """prev[t] = LAST index <= t with mask set (-1 = none): one forward
    associative scan in the max semiring — resolves the sequential
    kernel's re-capturing logical stations (capture = last side match
    at or before the pair's done event)."""
    F = mask.shape[0]
    idx = jnp.where(mask, jnp.arange(F, dtype=_I32), jnp.int32(-1))
    return lax.associative_scan(jnp.maximum, idx)


# ---------------------------------------------------------------------------
# the block kernel
# ---------------------------------------------------------------------------

def varies_by_lane(key: str) -> bool:
    """Whether a leaf of a fused block's `ev` is one value a LANE (a lifted
    constant, the lane's qid, its arm flag); every other leaf of a cut
    fused flush is shared or varies by row."""
    return key.startswith("__param.") or key in ("__lane_qid__",
                                                 "__arm_done__")


class ParallelChainKernel:
    """Stateless flat-block kernel for one lowered chain, in either the
    "scan" (pure SFA) or "dfa" (bit-packed multi-stride hybrid) family.

    Mirrors NFAKernel's packed-output contract exactly (meta row, valid
    row under `having`, out_names/out_dtypes from the plan's NFAKernel)
    so DevicePatternPlan's unpack consumes both interchangeably.
    Blocks carry no device state: ev is the chunked-halo flat layout
    (`__flat.*` arrays + `__nev__`/`__prev_seq__`/bases) minus the lane
    geometry — the whole flush is ONE log-depth program.  block_fn
    accepts T as an int (flat block) or an (L, F) tuple (ONE jax.vmap
    of the flat block over the lane axis: partitioned per-key grids and
    fused multi-query lanes — per-lane leaves map on axis 0, shared
    scalars broadcast)."""

    def __init__(self, prog: ParallelProgram, nfak: NFAKernel,
                 family: str = "scan"):
        assert family in ("scan", "dfa")
        self.prog = prog
        self.nfak = nfak              # selector/having/output metadata
        self.family = family
        self.f64 = nfak.f64
        self._mode = nfak._mode
        self._block_cache: dict = {}
        self.expiry_plan = _expiry_plan(prog)
        fresh = sum(a.fresh for a in self.expiry_plan.values())
        # how often the one-query-per-horizon sharing engages: static per
        # compiled plan, shown by rt.explain()
        self.expiry_queries = {"built": fresh,
                               "shared": len(self.expiry_plan) - fresh}
        # what each traced block asked of _FirstHit, _Read and _Compact,
        # by block key, and the key last asked for: rt.explain()'s
        # `first_hit`, `indexed_read` and `compaction`
        self._asked_of: dict = {}
        self._last_key = None

    def _asked(self, entry: str) -> Optional[dict]:
        return self._asked_of.get(self._last_key, {}).get(entry)

    @property
    def first_hit(self) -> Optional[dict]:
        """{'dense': n, 'tree': m, 'pairs_per_call': p, 'lanes': l,
        'F': f}: the first-hit queries of the block last dispatched, by
        the form that answers them (one form a block, by its F:
        DENSE_MAX_F), and the (event, query) pairs the dense form reduces
        in one call over all lanes.  None until a block has been traced."""
        return self._asked("first_hit")

    @property
    def indexed_read(self) -> Optional[dict]:
        """{'dense': n, 'gather': m, 'identity': k, 'pairs_per_call': p,
        'lanes': l, 'F': f}: the indexed reads of a lane's column in the
        block last dispatched, by the form that answers them (_Read, the
        same F and DENSE_MAX_F as `first_hit`), and the (event, asker)
        pairs the dense form sums in one call over all lanes.  None until
        a block has been traced."""
        return self._asked("indexed_read")

    @property
    def compaction(self) -> Optional[dict]:
        """{'dense': n, 'scatter': m, 'pairs_per_call': p, 'lanes': l,
        'F': f, 'M': cap}: the columns the block last dispatched compacts
        into its M match rows, by the form that fills the rows (_Compact,
        the same F and DENSE_MAX_F as `first_hit`), and the (candidate,
        row) pairs the dense form sums in one call over all lanes.  None
        until a block has been traced."""
        return self._asked("compaction")

    # NFAKernel-compatible surface consumed by _call_block / bench
    def block_fn(self, T, M: int):
        key = self._last_key = (T, M)
        fn = self._block_cache.get(key)
        if fn is None:
            if isinstance(T, tuple):
                fn = jax.jit(self._make_lane_block(T, M))
            else:
                fn = jax.jit(self._make_block(T, M))
            self._block_cache[key] = fn
        return fn

    def _make_block(self, T, M: int):
        def block(state, ev):
            with compute_dtypes(self._mode):
                return state, self._block_impl(ev, M, T)
        return block

    def _make_lane_block(self, T, M: int):
        """vmap the flat block over the lane axis: per-lane leaves (lane-
        major grids, per-lane scalars, params, qids) map on axis 0;
        shared leaves (bases, broadcast event arrays in fused mode)
        replicate."""
        def one(e):
            with compute_dtypes(self._mode):
                return self._block_impl(e, M, T)

        def lane_block(state, ev):
            shared_nd = {"__base_ts__": 0, "__base_seq__": 0}
            axes = {}
            for k, v in ev.items():
                if k in shared_nd:
                    axes[k] = None
                elif k.startswith("__flat."):
                    axes[k] = 0 if v.ndim == 2 else None
                else:               # __nev__/__prev_seq__/__param.*/...
                    axes[k] = 0 if v.ndim >= 1 else None
            return state, jax.vmap(one, in_axes=(axes,))(ev)

        def row_lane_block(state, ev):
            # a cut fused flush, T = (rows, lanes, F): the event grids,
            # counts and dedup bounds vary by ROW only, the lifted
            # constants, qids and arm flags by LANE only; the output is
            # (rows, lanes, words, M)
            by_lane = {k: 0 if varies_by_lane(k) else None for k in ev}
            by_row = {k: 0 if k.startswith("__flat.") or k in (
                "__nev__", "__prev_seq__") else None for k in ev}
            over_lanes = jax.vmap(one, in_axes=(by_lane,))
            return state, jax.vmap(over_lanes, in_axes=(by_row,))(ev)
        return row_lane_block if len(T) == 3 else lane_block

    # -- mask/env helpers -----------------------------------------------

    def _param_env(self, ev) -> dict:
        """Per-lane lifted constants (fused multi-query mode): scalars
        under the lane vmap, named exactly like NFAKernel.params."""
        return {k[len("__param."):]: v for k, v in ev.items()
                if k.startswith("__param.")}

    def _flat_env(self, ev, node: HopNode, ts, base_ts) -> dict:
        env = self._param_env(ev)
        for a in self.prog.schemas[node.ref].attributes:
            key = f"__flat.{node.scode}.{a.name}"
            if key in ev:
                env[f"{node.ref}.{a.name}"] = ev[key]
        env["__timestamp__"] = base_ts + ts.astype(jnp.int64)
        return env

    def _node_mask(self, ev, node: HopNode, ts, valid, base_ts):
        m = valid
        if len(self.prog.stream_ids) > 1:
            m = m & (ev["__flat.__scode__"] == node.scode)
        if node.pre_conjs:
            env = self._flat_env(ev, node, ts, base_ts)
            for ce in node.pre_conjs:
                m = m & jnp.broadcast_to(ce.fn(env), m.shape)
        return m

    def _gather_env(self, ev, idx_of: dict, keys, read: "_Read", base_ts,
                    comp_j=None) -> dict:
        """Capture env read at resolved indices: key "r.attr" (or
        "r[i].attr") -> flat column at idx_of[refpart] (clipped by `read`,
        the block's _Read; callers mask validity downstream).  `keys`
        bounds the reads to what's used.  idx_of maps refpart -> index
        array (per-head or per-match, caller's choice)."""
        env = self._param_env(ev)
        at: dict = {}       # id(idx) -> (idx, keys, columns): one shared read
        # sorted: `keys` is a set of strings, whose iteration order moves
        # with the process's hash seed — and with it the traced op order,
        # the HLO text and the persistent compile cache's key
        for k in sorted(keys):
            if k == "__timestamp__":
                if comp_j is not None:
                    env[k] = base_ts + read(ev["__flat.__ts__"], comp_j) \
                        .astype(jnp.int64)
                continue
            if "." not in k or k.startswith("__"):
                continue
            refpart, attr = k.split(".", 1)
            base = _base_ref(refpart)[0]
            idx = idx_of.get(refpart, idx_of.get(base))
            if idx is None:
                continue
            pn = self.prog.ref_of.get(base)
            if pn is None:
                continue
            scode = self.prog.positions[pn[0]].nodes[pn[1]].scode
            col = ev.get(f"__flat.{scode}.{attr}")
            if col is None:
                continue
            _idx, ks, cols = at.setdefault(id(idx), (idx, [], []))
            ks.append(k)
            cols.append(col)
        for idx, ks, cols in at.values():
            env.update(zip(ks, read.all(cols, idx)))
        return env

    # -- dfa family: bit-packed multi-stride static tables ----------------

    def _dfa_tables(self, lane_masks: list, F: int, L: int):
        """Precompose per-event symbol words into stride-4 block tables.
        lane_masks: one (F,) mask per chase node (symbol bit).  Returns
        (suffix_flat per lane, packed first-offset words, block-level
        next pointers per lane, NB)."""
        B = STRIDE
        NB = -(-F // B)
        Fp = NB * B
        lanes = range(len(lane_masks))
        # ONE u32 symbol word per event: bit k = matches chase node k
        sym = jnp.zeros((Fp,), jnp.uint32)
        for k in lanes:
            mk = jnp.zeros((Fp,), bool).at[:F].set(lane_masks[k])
            sym = sym | (mk.astype(jnp.uint32) << np.uint32(k))
        o = jnp.arange(B, dtype=_I32)[None, :]
        suffix = {}
        first = {}
        for k in lanes:
            bits = ((sym.reshape(NB, B) >> np.uint32(k)) & 1) != 0
            offs = jnp.where(bits, o, jnp.int32(B))
            # in-block suffix-first offsets (stride-4: 3 dense mins)
            acc = offs[:, B - 1]
            cols = [acc]
            for c in range(B - 2, -1, -1):
                acc = jnp.minimum(offs[:, c], acc)
                cols.append(acc)
            suf = jnp.stack(list(reversed(cols)), axis=1)   # (NB, B)
            suffix[k] = suf.reshape(-1)
            first[k] = suf[:, 0]
        # per-block transition table: first-hit offsets for ALL chase
        # nodes bit-packed into one u32 word per block
        packed = jnp.zeros((NB,), jnp.uint32)
        for k in lanes:
            packed = packed | (first[k].astype(jnp.uint32)
                               << np.uint32(_OFF_BITS * k))
        # block-level next pointers: one associative scan over F/4
        # elements per chase node (stacked -> a single scan call)
        if lane_masks:
            blk = jnp.stack(
                [jnp.where(first[k] < B,
                           jnp.arange(NB, dtype=_I32), jnp.int32(NB))
                 for k in lanes], axis=1)
            nblk = lax.associative_scan(jnp.minimum, blk, reverse=True,
                                        axis=0)
            nblk = {k: nblk[:, i] for i, k in enumerate(lanes)}
        else:
            nblk = {}
        return suffix, packed, nblk, NB

    def _dfa_next(self, k: int, s, suffix, packed, nblk, NB: int, L: int,
                  read: "_Read"):
        """Multi-stride lookup: in-block suffix table, then the packed
        block-transition word of the next block containing a hit."""
        B = STRIDE
        Fp = NB * B
        sc = jnp.clip(s, 0, Fp - 1)
        inb = read(suffix[k], sc)                # first o >= s%B in block
        b = sc >> 2
        j_in = (b << 2) + inb
        b2 = read(nblk[k], b + 1)
        ok2 = (b + 1 < NB) & (b2 < NB)
        f2 = ((read(packed, b2)
               >> (jnp.uint32(_OFF_BITS * k))) & jnp.uint32(7)).astype(_I32)
        j_blk = (b2 << 2) + f2
        j = jnp.where(inb < B, j_in, jnp.where(ok2, j_blk, jnp.int32(L)))
        return jnp.where(s < Fp, j, jnp.int32(L)).astype(_I32)

    # -- the block --------------------------------------------------------

    def _block_impl(self, ev, M: int, T):
        # every phase runs under a jax.named_scope, so each device
        # operation's `op_name` says which phase it belongs to and the
        # names survive a recompile (XLA's own `while.73` do not)
        scope = jax.named_scope
        prog, nfak = self.prog, self.nfak
        S = prog.S
        F = ev["__flat.__ts__"].shape[0]
        L = pow2_at_least(F, lo=2)
        nev = ev["__nev__"].astype(_I32)
        prev_seq = ev["__prev_seq__"]
        base_ts = ev["__base_ts__"]
        ts = ev["__flat.__ts__"]
        # scan/dfa flushes always ship the explicit seq array (output
        # events consume global seqs, so derived-consecutive seqs would
        # force a second structural compile at flush 2)
        seq = ev["__flat.__seq__"]
        valid = jnp.arange(F, dtype=_I32) < nev
        with scope("node_masks"):
            nmask = {(pi, ni): self._node_mask(ev, n, ts, valid, base_ts)
                     for pi, pos in enumerate(prog.positions)
                     for ni, n in enumerate(pos.nodes)}

        chase = _chase_lanes(prog) if self.family == "dfa" else []
        if chase:
            lane_of = {pn: k for k, pn in enumerate(chase)}
            with scope("dfa_tables"):
                suffix, packed, nblk, NB = self._dfa_tables(
                    [nmask[pn] for pn in chase], F, L)

        scan_next: dict = {}

        def nxt(pi, ni, s):
            """First index >= s matching chase node (pi, ni); L if none."""
            with scope("next_hit"):
                if chase and (pi, ni) in lane_of:
                    return self._dfa_next(lane_of[(pi, ni)], s, suffix,
                                          packed, nblk, NB, L, read)
                key = (pi, ni)
                if key not in scan_next:
                    scan_next[key] = _next_static_scan(nmask[key], L)
                nx = scan_next[key]
                return jnp.where(s < F, read(nx, s), jnp.int32(L))

        # every "first event at or after s that ..." below is one entry,
        # dense or tree by the block's F (DENSE_MAX_F); every "the column's
        # element at idx" is another, dense or gather by the same rule, and
        # "the live candidates as the M match rows" the third
        first_hit = _FirstHit(F, L)
        j0 = jnp.arange(F, dtype=_I32)
        read = _Read(F, j0)
        compact = _Compact(F, M)

        # occurrence ranks per count position: the inclusive cumulative
        # match count — "the r-th occurrence after entry" is ONE monotone
        # first-hit query on it (rank/select), so count minima and capture
        # indices never iterate
        ranks: dict = {}
        for pi, pos in enumerate(prog.positions):
            if pos.kind != "count":
                continue
            with scope("ranks"):
                ranks[pi] = jnp.cumsum(nmask[(pi, 0)].astype(_I32),
                                       dtype=_I32)

        def select(pi, s, r):
            """First index >= s whose inclusive occurrence rank >= r."""
            with scope("rank_select"):
                return first_hit(ranks[pi], valid, s, r, "ge")

        # expiry: the sequential kernel expires a waiting instance on the
        # FIRST arriving event whose age exceeds the position's `within`
        # horizon — matching or not (nfa_device._step computes `expired`
        # before the match mask, over timey=valid).  With out-of-order
        # timestamps a later event can carry a REGRESSED ts, so checking
        # the matched event alone would resurrect instances the
        # sequential kernel killed.  The horizon is int64: ts offsets
        # reach ±2^30 and ts+W must not wrap i32.
        ts64 = ts.astype(jnp.int64)

        asks = self.expiry_plan
        held_kl = None

        def killer(pi, s):
            """First event at or after s past the head's `within` horizon
            (per-head v = head ts + W; queries indexed by head).  One
            query per distinct horizon: a position whose ask is not
            fresh reads the index the chain already holds
            (_expiry_plan)."""
            nonlocal held_kl
            if asks[pi].fresh:
                with scope("within_kill"):
                    held_kl = first_hit(
                        ts, valid, s,
                        ts64 + jnp.int64(asks[pi].within_ms), "gt")
            return held_kl

        def threshold_next(hop: HopNode, s, idx_of):
            with scope("threshold_next"):
                th = hop.threshold
                own = ev[f"__flat.{hop.scode}."
                         f"{th.own_key.split('.', 1)[1]}"]
                env = self._gather_env(ev, idx_of, th.rhs.reads, read,
                                       base_ts)
                v = jnp.broadcast_to(th.rhs.fn(env), (F,))
                return first_hit(own, nmask[self.prog.ref_of[hop.ref]],
                                 s, v, th.op)

        # ---- the state chase: every event index is a candidate head ----
        head = prog.positions[0]
        ok = nmask[(0, 0)]
        dead = jnp.zeros((F,), bool)    # definitive failure (single-arm)
        idx_of = {}                     # refpart -> per-head value index
        pres_of = {}                    # refpart -> per-head presence bool
        count_ctx = {}                  # pi -> (s_occ, ra) occurrence base
        j = j0

        def step_fail(alive, kl, jn):
            """Advance-step outcome: (still_ok, definitively_dead).
            Dead = the killer event exists in-block and the match did not
            land before it; not-found with no killer stays pending."""
            good = jn < kl
            return alive & good, alive & ~good & (kl < F)

        if head.kind == "count":
            # the arming event IS occurrence 1 (host _alloc_head): the
            # rank base excludes it, the select starts AT the head
            with scope("hop0"):
                ra = read(ranks[0], j0) - 1
                count_ctx[0] = (j0, ra)
                jmin = select(0, j0, ra + jnp.int32(head.min_count))
                kl = killer(0, j0 + 1)
                if S > 1:
                    ok, d = step_fail(ok, kl, jmin)
                    dead = dead | d
                    j = jnp.clip(jmin, 0, F - 1)
        else:
            idx_of[head.nodes[0].ref] = j0

        final_count = prog.positions[S - 1].kind == "count"

        for pi in range(1, S):
            with scope(f"hop{pi}"):
                pos = prog.positions[pi]
                if pos.kind == "single":
                    hop = pos.nodes[0]
                    s = j + 1
                    if not prog.sequence:
                        kl = killer(pi, s)
                    if prog.sequence:
                        # strict succession: the hop consumes EXACTLY the
                        # next valid event — mask/filter/expiry all resolve
                        # by a direct read at s (the int32 ts, widened
                        # after: an int64 column would take the gather)
                        own = {f"{hop.ref}.{a.name}":
                               ev[f"__flat.{hop.scode}.{a.name}"]
                               for a in prog.schemas[hop.ref].attributes
                               if hop.step_conjs
                               and f"__flat.{hop.scode}.{a.name}" in ev}
                        m, ts_s, *at_s = read.all(
                            [nmask[(pi, 0)], ts, *own.values()], s)
                        ts_s = ts_s.astype(jnp.int64)
                        if hop.step_conjs:
                            senv = self._gather_env(ev, idx_of, set().union(
                                *[ce.reads for ce in hop.step_conjs]), read,
                                base_ts)
                            senv.update(zip(own, at_s))
                            senv["__timestamp__"] = base_ts + ts_s
                            for ce in hop.step_conjs:
                                m = m & jnp.broadcast_to(ce.fn(senv), m.shape)
                        expired = ts_s > ts64 + jnp.int64(pos.within_ms)
                        have = s < nev
                        jn = jnp.where(have & m & ~expired, s, jnp.int32(L))
                        dead = dead | (ok & have & (expired | ~m))
                        ok = ok & (jn < F)
                    elif hop.threshold is not None:
                        jn = threshold_next(hop, s, idx_of)
                        ok, d = step_fail(ok, kl, jn)
                        dead = dead | d
                    else:
                        jn = nxt(pi, 0, s)
                        ok, d = step_fail(ok, kl, jn)
                        dead = dead | d
                    j = jnp.clip(jn, 0, F - 1)
                    idx_of[hop.ref] = j
                elif pos.kind == "logical":
                    s = j + 1
                    jl = nxt(pi, 0, s)
                    jr = nxt(pi, 1, s)
                    if pos.op == "or":
                        jd = jnp.minimum(jl, jr)
                    else:
                        jd = jnp.where((jl < F) & (jr < F),
                                       jnp.maximum(jl, jr), jnp.int32(L))
                    kl = killer(pi, s)
                    ok, d = step_fail(ok, kl, jd)
                    dead = dead | d
                    jdc = jnp.clip(jd, 0, F - 1)
                    for ni, n in enumerate(pos.nodes):
                        jside = jl if ni == 0 else jr
                        if pos.op == "or":
                            # winner captures its own first match; loser is
                            # absent (presence row nulls it host-side)
                            idx_of[n.ref] = jnp.clip(jside, 0, F - 1)
                            pres_of[n.ref] = jside == jd
                        else:
                            # AND stations re-capture while waiting: the
                            # emitted value is the LAST side match at or
                            # before the done event
                            pv = _prev_static_scan(nmask[(pi, ni)])
                            idx_of[n.ref] = jnp.clip(read(pv, jdc), 0, F - 1)
                            pres_of[n.ref] = jnp.ones((F,), bool)
                    j = jdc
                else:                       # count (non-head entry)
                    entry = j
                    # the entry event is NOT an occurrence
                    ra = read(ranks[pi], entry)
                    count_ctx[pi] = (entry + 1, ra)
                    if pi < S - 1:
                        jmin = select(pi, entry + 1,
                                      ra + jnp.int32(pos.min_count))
                        kl = killer(pi, entry + 1)
                        ok, d = step_fail(ok, kl, jmin)
                        dead = dead | d
                        j = jnp.clip(jmin, 0, F - 1)

        with scope("emit_candidates"):
            # ---- emission candidates --------------------------------------
            if final_count:
                fpos = prog.positions[S - 1]
                s_occ, ra = count_ctx[S - 1]
                kl = killer(S - 1, s_occ)
                C = fpos.max_count - fpos.min_count + 1
                lvs, comps = [], []
                for c in range(fpos.min_count, fpos.max_count + 1):
                    jc = select(S - 1, s_occ, ra + jnp.int32(c))
                    lvs.append(ok & (jc < kl))
                    comps.append(jnp.clip(jc, 0, F - 1))
                lv_all = jnp.stack(lvs)                 # (C, F)
                comp_all = jnp.stack(comps)
                # single-arm resolution: parked at max, or dead
                resolved = dead | lvs[-1]
            else:
                C = 1
                lv_all = ok[None, :]
                comp_all = j[None, :]
                resolved = dead | ok

            # dedup: completions at or before the previous flush's last seq
            # are tail replays — suppressed on device, per lane
            lv_all = lv_all & (read(seq, comp_all) > prev_seq.astype(_I32))

            arm_flag = jnp.int32(0)
            if prog.single_arm:
                # ONE instance ever: the first head match arms it; everything
                # else never existed.  The meta flag tells the host whether
                # the arm is still pending (keep dispatching) or resolved.
                hm = nmask[(0, 0)]
                h0 = jnp.min(jnp.where(hm, j0, jnp.int32(F)))
                lv_all = lv_all & (j0[None, :] == h0)
                arm_off = ev.get("__arm_done__")
                if arm_off is not None:
                    lv_all = lv_all & (arm_off.astype(_I32) == 0)
                has_head = h0 < F
                r0 = resolved[jnp.clip(h0, 0, F - 1)]
                arm_flag = jnp.where(
                    has_head,
                    jnp.where(r0, jnp.int32(ARM_RESOLVED),
                              jnp.int32(ARM_PENDING)),
                    jnp.int32(ARM_NONE))
                if arm_off is not None:
                    arm_flag = jnp.where(arm_off.astype(_I32) != 0,
                                         jnp.int32(ARM_RESOLVED), arm_flag)

        with scope("compact"):
            # ---- compaction: (slot, head) candidates -> M match rows ------
            # a candidate's head, its completion and (where the final
            # position counts: nothing else reads it) its slot
            cols = [jnp.tile(j0, C), comp_all.reshape(C * F)]
            if final_count:
                cols.append(jnp.repeat(jnp.arange(C, dtype=_I32), F))
            rows, n = compact(cols, lv_all.reshape(C * F))
            hm_, comp_m = rows[:2]
            cm_ = rows[2] if final_count else None

        with scope("capture"):
            # per-match capture indices: single/logical refs read their
            # per-head chase results; count refs rank/select at the match's
            # completion index (collection is station-independent in the
            # sequential kernel — occurrences keep absorbing until max or
            # the park freeze at completion)
            # everything a match row takes at its head index is one read
            heads = {("idx", rp): arr for rp, arr in idx_of.items()
                     if arr is not j0}
            heads.update({("pres", rp): arr for rp, arr in pres_of.items()})
            heads["seq"] = seq
            at_head = dict(zip(heads, read.all(list(heads.values()), hm_)))
            midx = {rp: at_head.get(("idx", rp), hm_) for rp in idx_of}
            mpres = {rp: at_head[("pres", rp)] for rp in pres_of}

            need = set()
            for ce in list(nfak.sel_fns.values()) \
                    + ([nfak.having] if nfak.having else []):
                need.update(ce.reads)
            need_bases: dict = {}
            for k in need:
                if "." in k and not k.startswith("__"):
                    need_bases.setdefault(_base_ref(k.split(".", 1)[0])[0],
                                          set()).add(k.split(".", 1)[0])
            for k in nfak.out_names:
                if k.startswith("__present__."):
                    rp = k[len("__present__."):]
                    need_bases.setdefault(_base_ref(rp)[0], set()).add(rp)

            for pi, pos in enumerate(prog.positions):
                if pos.kind != "count":
                    continue
                ref = pos.nodes[0].ref
                rps = need_bases.get(ref)
                if not rps:
                    continue
                s_occ, ra = count_ctx[pi]
                if s_occ is j0:
                    s_m, ra_m = hm_, read(ra, hm_)
                else:
                    s_m, ra_m = read.all([s_occ, ra], hm_)
                if pi == S - 1:
                    q_m = jnp.int32(pos.min_count) + cm_
                else:
                    avail = read(ranks[pi], comp_m) - ra_m
                    q_m = jnp.minimum(avail, jnp.int32(pos.max_count)) \
                        if pos.max_count < UNBOUNDED else avail

                def sel_q(r):
                    return jnp.clip(first_hit(ranks[pi], valid, s_m,
                                              ra_m + r, "ge"), 0, F - 1)
                for rp in sorted(rps):        # set of str: see _gather_env
                    _b, cidx = _base_ref(rp)
                    if cidx is None or cidx == "last":
                        if pi == S - 1:
                            midx[rp] = comp_m   # the emitting occurrence
                        else:
                            midx[rp] = sel_q(q_m)
                        mpres[rp] = q_m >= 1
                    elif cidx == "last-1":
                        midx[rp] = sel_q(q_m - 1)
                        mpres[rp] = q_m >= 2
                    else:
                        want = jnp.int32(int(cidx) + 1)
                        midx[rp] = sel_q(want)
                        mpres[rp] = q_m >= want

        with scope("select"):
            env = self._gather_env(ev, midx, need, read, base_ts,
                                   comp_j=comp_m)
            sel = {name: jnp.broadcast_to(ce.fn(env), (M,))
                   for name, ce in nfak.sel_fns.items()}
            mvalid = jnp.arange(1, M + 1, dtype=_I32) <= n
            if nfak.having is not None:
                henv = dict(env)
                henv.update(sel)
                mvalid = mvalid & jnp.broadcast_to(nfak.having.fn(henv), (M,))
            sel["__timestamp__"], sel["__seq__"] = read.all([ts, seq], comp_m)
            sel["__head_seq__"] = at_head["seq"]
            if nfak.emit_qid:
                qid = ev.get("__lane_qid__", jnp.int32(0))
                sel["__qid__"] = jnp.broadcast_to(qid.astype(_I32), (M,))
            for name in nfak.out_names:
                if not name.startswith("__present__."):
                    continue
                rp = name[len("__present__."):]
                pr = mpres.get(rp)
                if pr is None:
                    pr = jnp.ones((M,), bool)
                sel[name] = pr.astype(_I32)

        with scope("pack"):
            NO_DL = jnp.int32(2 ** 31 - 1)
            meta = (jnp.zeros((M,), _I32)
                    .at[0].set(n).at[3].set(NO_DL).at[4].set(arm_flag))
            irows = [meta]
            if nfak.having is not None:
                irows.append(mvalid.astype(_I32))
            frows = []
            for name in nfak.out_names:
                col = sel[name]
                if col.dtype == jnp.float64:
                    frows.append(col)
                elif col.dtype == jnp.float32:
                    irows.append(lax.bitcast_convert_type(col, _I32))
                elif col.dtype == jnp.int64:
                    irows.append(_hi32(col))
                    irows.append(_lo32(col))
                else:
                    irows.append(col.astype(_I32))
            out = {"i": jnp.stack(irows, axis=0)}
            if frows:
                out["f"] = jnp.stack(frows, axis=0)
        lanes = int(np.prod(T[:-1])) if isinstance(T, tuple) else 1
        self._asked_of[(T, M)] = {"first_hit": first_hit.asked(lanes),
                                  "indexed_read": read.asked(lanes),
                                  "compaction": compact.asked(lanes)}
        return out
