"""Batched device NFA — the TPU pattern/sequence matching kernel.

The north-star component (SURVEY §3.3): the reference walks per-event
pending-StateEvent lists through Pre/PostStateProcessor chains
(reference: core:query/input/stream/state/StreamPreStateProcessor.java:292,
StreamPostStateProcessor.java:53, LogicalPreStateProcessor.java:330-337,
CountPreStateProcessor.java:370-393, AbsentStreamPreStateProcessor.java:60-115).
Here the whole matcher is ONE fused array program:

  * the partition axis P (reference: core:partition/PartitionRuntime.java
    clones the query graph per key) becomes the minor (lane) axis —
    thousands of independent NFA instances evaluated in lockstep and
    shardable over a `jax.sharding.Mesh`;
  * pending partial matches become A fixed "slots" per partition laid out
    (A, P): `occ` (0 = free, p = stationed at position p-1, S+1 = parked
    completion) plus capture rows `ref.attr -> (A, P)`;
  * a micro-batch becomes a dense (T, P) block — one event per partition
    per `lax.scan` step, so in-partition order (the sequential semantics)
    is preserved while all partitions and slots advance in parallel;
  * `every` heads are an always-armed flag; `within` expiry, sequence
    strictness, logical fills, count collection, absent deadlines, and
    match emission are masked vector ops.

Pattern algebra on device (mirrors the host oracle interp/nfa.py):
  * count quantifiers `<m:n>` / `+`: a per-slot counter row per count
    position; collection is decoupled from the slot's station (`cnt_active`)
    so a partial match keeps absorbing occurrences while waiting further
    down the chain, exactly like the reference's pending count lists;
    indexed captures (e1[0], e1[i], e1[last], e1[last-1]) are capture rows;
    completions whose count is still collecting emit WITHOUT freeing the
    slot (more occurrences -> more matches).
  * logical `and`/`or`: a position holds a partner pair with a fill
    bitmask; `or` completions leave the other ref NULL (emitted present
    bits -> host-side null columns); an absent partner (`not X and e2=Y`)
    kills the slot when X arrives.
  * absent (`not X for T`): a deadline row per absent position; the
    forbidden stream's arrival kills the slot; deadline passage emits (at
    the deadline timestamp) or advances.  Deadlines fire on timer "tick"
    cells injected by the host scheduler (and, in playback mode, lazily
    against event timestamps, matching the host's pre-fire loop); the
    block reports the earliest pending deadline so the host scheduler
    knows when to tick.

TPU-economics of this kernel (what round-2 got wrong; measured on v5e):
  * NO f64/i64 inside the scan.  x64 arrays are emulated as f32/u32
    pairs, which (a) doubles every carry/output buffer and (b) made XLA
    choose mismatched layouts for the big scan-output accumulators,
    copying ~30 GB of HBM per block (~2 ms/step).  Timestamps and seqs
    travel as i32 offsets from per-plan bases, rebased host-side before
    they can overflow; DOUBLE computes in f32 by default
    (`@app:devicePrecision('f64')` opts out, documented slower).
  * capture storage holds ONLY the columns some predicate / selector /
    having actually reads (CompiledExpr.reads), grouped per-dtype into
    stacked (K, A, P) arrays so writes/emissions are one masked select
    per group instead of one per column.
  * predicates that read only the arriving event (no captures) are
    evaluated for the WHOLE block outside the scan as fused (T, P)
    vector ops; only capture-dependent conjuncts run per-step.
  * completing slots park their snapshot in slot storage (sentinel
    station) and drain through E narrow i32/f32 lanes per step (masked
    one-hot reductions — TPU scatters serialize); after the scan,
    ceil(A/E) drain rounds empty any backlog, then ONE cumsum + one
    scatter per lane-grid row compacts matches into a flat (M,) buffer
    (capacity doubled-and-retried on overflow — state is functional, so
    a retry is exact).

Still host-only (DeviceNFAUnsupported -> sequential fallback):
absent states in the head position, `every` wrapping logical/count/
absent states below the head, min-count 0 in the head position,
sequences containing absent states, and non-Variable selector outputs
over maybe-absent refs.  Everything else — `every` below the head
(slot forking), optional states (min-count 0 epsilon cascade),
adjacent/multiple count positions, sequences with logical states —
runs on device.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..query import ast
from .expr import (CompiledExpr, ExprError, MultiStreamContext, compute_dtypes,
                   F32_MODE, compile_expression, jnp_dtype)
from .schema import StreamSchema, StringTable

# local-offset budget: rebase when offsets approach this (i32 headroom)
LOCAL_SPAN = 1 << 30
NO_DEADLINE = np.int32(2**31 - 1)
NO_FIRST = np.int32(LOCAL_SPAN)   # first_ts sentinel: no capture yet


class DeviceNFAUnsupported(Exception):
    """Raised when a pattern shape needs the sequential fallback."""


class PatternFilterContext(MultiStreamContext):
    """Filter compile context for one chain state: unqualified attributes
    resolve to the state's own (arriving) event first — mirroring the
    reference, where a condition's unqualified variables read the current
    event (reference: core:util/parser/ExpressionParser variable binding
    for state elements)."""

    def __init__(self, schemas: dict, strings, own_ref: str):
        super().__init__(schemas, strings)
        self.own_ref = own_ref

    def resolve(self, var: ast.Variable):
        if var.stream_ref is None and var.index is None \
                and var.attribute in self.schemas[self.own_ref].types:
            return (f"{self.own_ref}.{var.attribute}",
                    self.schemas[self.own_ref].type_of(var.attribute))
        return super().resolve(var)


@dataclass
class PNode:
    """One condition inside a position (a reference Pre/PostStateProcessor)."""
    ref: str
    stream_id: str
    scode: int
    kind: str                       # "stream" | "absent"
    waiting_ms: Optional[int]       # absent `for T`
    pre_conjs: list = field(default_factory=list)   # event-only -> (T,P)
    step_conjs: list = field(default_factory=list)  # capture-referencing
    step_asts: list = field(default_factory=list)   # raw AST per step conj
    #   (parallel to step_conjs; nfa_parallel lowers monotone comparisons
    #   over earlier captures into segment-tree threshold hops)
    pre_key: Optional[str] = None   # xs key of the precomputed mask


@dataclass
class Position:
    """One chain position: a single state or a logical partner pair."""
    nodes: list                     # [PNode] (2 for logical)
    op: Optional[str] = None        # None | "and" | "or"
    min_count: int = 1
    max_count: int = 1
    within_ms: Optional[int] = None
    sticky: bool = False            # `every` head arm
    # state-row assignments (set by the kernel):
    cnt_row: Optional[int] = None   # counter row (count positions)
    log_row: Optional[int] = None   # fill-bit row (logical positions)
    dl_rows: Optional[dict] = None  # node idx -> deadline row (absent+for)

    @property
    def is_count(self) -> bool:
        return (self.min_count, self.max_count) != (1, 1)

    @property
    def refs(self) -> list:
        return [n.ref for n in self.nodes]


@dataclass
class ChainSpec:
    positions: list                  # [Position]
    stream_ids: list                 # distinct stream ids, scode order
    schemas: dict                    # ref -> StreamSchema
    is_sequence: bool
    every_head: bool

    @property
    def S(self) -> int:
        return len(self.positions)

    @property
    def all_nodes(self) -> list:
        return [n for p in self.positions for n in p.nodes]

    def maybe_absent_refs(self) -> set:
        """Refs that can be NULL in an emitted match (or-sides, absent
        nodes, and-pair sides advanced by a partner deadline, min-0
        counts that may emit with zero occurrences)."""
        out = set()
        for p in self.positions:
            if p.op is not None:
                out.update(p.refs)
            if p.is_count and p.min_count == 0:
                out.update(p.refs)
            for n in p.nodes:
                if n.kind == "absent":
                    out.add(n.ref)
        return out

    @property
    def needs_init_slot(self) -> bool:
        """Chains whose START state pre-registers a partial match before
        any event (host: PatternMatcher.start + _commit_epsilons): an
        absent head (`not A for T -> ...`) or a min-0 count head
        (`e1=A<0:2> -> ...`).  Each lane lazily arms one slot on its
        first activity."""
        head = self.positions[0]
        return (any(n.kind == "absent" for n in head.nodes)
                or (head.is_count and head.min_count == 0))


def _conjuncts(e: ast.Expression) -> list:
    if isinstance(e, ast.And):
        return _conjuncts(e.left) + _conjuncts(e.right)
    return [e]


def lower_chain(state_input, schemas_by_stream: dict, strings: StringTable,
                filters_by_node: list,
                param_extra: Optional[dict] = None) -> ChainSpec:
    """Validate + lower a StateInputStream into a device position chain.

    Reuses the host NFACompiler lowering so device and host agree on
    structure; anything outside the supported algebra raises
    DeviceNFAUnsupported (-> sequential fallback).
    """
    from ..interp.nfa import NFACompiler
    from ..query.ast import StateType

    comp = NFACompiler()
    entries, _exits = comp.lower(state_input.state)
    nodes = comp.nodes
    is_sequence = state_input.type == StateType.SEQUENCE
    qw = state_input.within.millis if state_input.within else None

    # walk entry -> FINAL, grouping logical partners into one position
    if len(entries) == 1:
        head_ids = [entries[0].id]
    elif len(entries) == 2 and entries[0].partner_id == entries[1].id:
        head_ids = [entries[0].id, entries[1].id]
    else:
        raise DeviceNFAUnsupported("unsupported entry structure")

    stream_ids, scode_of = [], {}

    def scode(sid: str) -> int:
        if sid not in schemas_by_stream:
            raise DeviceNFAUnsupported(f"unknown stream {sid!r}")
        if sid not in scode_of:
            scode_of[sid] = len(stream_ids)
            stream_ids.append(sid)
        return scode_of[sid]

    def mk_pnode(n) -> PNode:
        return PNode(n.ref, n.stream_id, scode(n.stream_id), n.kind,
                     n.waiting_ms)

    positions: list = []
    seen: set = set()
    cur = head_ids
    while cur:
        n0 = nodes[cur[0]]
        group = [n0] + ([nodes[n0.partner_id]] if n0.partner_id is not None
                        else [])
        for g in group:
            if g.id in seen:
                raise DeviceNFAUnsupported("cyclic state graph")
            seen.add(g.id)
        pos = Position([mk_pnode(g) for g in group])
        if n0.partner_id is not None:
            pos.op = n0.partner_op
        pos.min_count, pos.max_count = n0.min_count, n0.max_count
        w = n0.within_ms if n0.within_ms is not None else qw
        if w is not None and w >= LOCAL_SPAN:
            raise DeviceNFAUnsupported("within > ~12 days (i32 ms offsets)")
        pos.within_ms = w
        pos.sticky = bool(n0.sticky)
        positions.append(pos)
        nxt = n0.next_id
        cur = [nxt] if nxt is not None else []
    if len(seen) != len(nodes):
        raise DeviceNFAUnsupported("non-linear state graph")

    # ---- support matrix ---------------------------------------------------
    # (absent-in-head, sequences with absents, min-0 heads, and
    # `every`-wrapped absents below the head all lower now — r5)
    S = len(positions)
    for i, pos in enumerate(positions):
        if pos.sticky and i != 0 and (pos.op is not None or pos.is_count):
            # `every` wrapping a logical pair or count BELOW the head needs
            # per-slot standing-arm forking at a shared station — host-only
            # (head every-logical/count re-arm via armed0; head every-absent
            # via the init-slot fork)
            raise DeviceNFAUnsupported(
                "`every`-wrapped logical/count state below the head")
        if pos.sticky and i == 0 and (
                (pos.op is not None
                 and any(n.kind == "absent" for n in pos.nodes))
                or (pos.is_count and pos.min_count == 0)):
            # every-wrapped absent-logical / optional-count heads would
            # need a forking standing INIT slot — host-only
            raise DeviceNFAUnsupported(
                "`every`-wrapped absent-logical or optional-count head")
        if pos.min_count == 0 and i > 0 and positions[i - 1].is_count \
                and positions[i - 1].min_count >= 1:
            # an optional-count run after a counting state keeps the
            # station at the counting state with a chained arm; the chain
            # must land on a plain (1,1) stream position
            k = i
            while k < S and positions[k].is_count \
                    and positions[k].min_count == 0:
                k += 1
            if (k >= S or positions[k].is_count
                    or positions[k].op is not None
                    or positions[k].nodes[0].kind == "absent"
                    or positions[k].sticky):
                raise DeviceNFAUnsupported(
                    "optional count run after a counting state landing on "
                    "a non-stream state")
        # (count on logical/absent states and min-0 non-count states are
        # structurally unbuildable from the AST: CountStateElement wraps a
        # StreamStateElement only — no check needed)

    schemas = {n.ref: schemas_by_stream[n.stream_id]
               for p in positions for n in p.nodes}
    spec = ChainSpec(positions, stream_ids, schemas, is_sequence,
                     positions[0].sticky)

    # ---- compile filters (filters_by_node follows NFACompiler node order) -
    flat_pnodes: dict = {}
    for p in positions:
        for n in p.nodes:
            flat_pnodes[n.ref] = n
    for host_n, elem_filters in zip(nodes, filters_by_node):
        pn = flat_pnodes.get(host_n.ref)
        if pn is None:
            continue
        conjs: list = []
        for f in elem_filters:
            conjs.extend(_conjuncts(f.expr))
        ctx = PatternFilterContext(spec.schemas, strings, pn.ref)
        if param_extra:
            ctx.extra = dict(param_extra)
        is_head = host_n.id in head_ids
        for c in conjs:
            try:
                ce = compile_expression(c, ctx)
            except ExprError as e:
                raise DeviceNFAUnsupported(f"filter not device-compilable: {e}")
            if ce.type != ast.AttrType.BOOL:
                raise DeviceNFAUnsupported("non-boolean filter")
            own = {f"{pn.ref}.{a.name}" for a in spec.schemas[pn.ref].attributes}
            own.add("__timestamp__")
            if param_extra:
                own.update(param_extra)
            if set(ce.reads) <= own:
                pn.pre_conjs.append(ce)
            else:
                if is_head:
                    raise DeviceNFAUnsupported(
                        "head filter references later captures")
                pn.step_conjs.append(ce)
                pn.step_asts.append(c)
    return spec


# ---------------------------------------------------------------------------
# kernel builder
# ---------------------------------------------------------------------------

_I32 = jnp.int32


def _base_ref(refpart: str):
    """'e1' -> ('e1', None); 'e1[0]' -> ('e1', 0); 'e1[last]' etc."""
    if "[" in refpart and refpart.endswith("]"):
        base, idx = refpart[:-1].split("[", 1)
        return base, idx
    return refpart, None


class NFAKernel:
    """Builds the jitted block function for one ChainSpec.

    state pytree (persistent across blocks; all (A, P) with P minor):
      occ      (A, P) i32      0 = free, p = stationed at position p-1,
                               S+1 = parked completion awaiting a drain lane
      first_ts (A, P) i32      head-capture ts offset (within anchor)
      head_seq (A, P) i32      head-capture seq offset (emission tie order)
      cnt      (Kc, A, P) i32  occurrence counters (count positions)
      cnt_on   (Kc, A, P) bool still-collecting flags
      narm     (Kc, A, P) bool successor armed (set when cnt hits min,
                               consumed by the successor's match — the
                               reference re-registers the next state only
                               at the exact min crossing)
      fl       (Kl, A, P) i32  logical fill bits (1 = left, 2 = right)
      dl       (Ka, A, P) i32  absent deadlines (NO_DEADLINE = disarmed)
      caps_f   (Kf, A, P) f32  float capture rows (self.rows_f)
      caps_i   (Ki, A, P) i32  int/string/bool/present capture rows +
                               parked completion ts/seq (self.rows_i)
      caps_l   (Kl', A, P) i64 LONG capture rows (hi/lo i32 lane pairs)
      armed0   (P,)  bool      entry arm (always True for `every`)
      of_slots (P,)  i32       head drops from slot exhaustion
      of_lanes (P,)  i32       direct-emit drops (count-survivor bursts
                               wider than E; host doubles E and retries)

    block(state, ev) -> (state', out): ev holds (T, P) i32/f32 grids plus
    0-d base scalars; out is ONE packed i32 matrix (+ f64 matrix only in
    f64 mode).  out row 0 = [n, of_slots, of_lanes, min_deadline, ...].
    """

    def __init__(self, spec: ChainSpec, sel_fns: dict, having: Optional[CompiledExpr],
                 P: int, A: int, E: Optional[int] = None, f64: bool = False,
                 playback: bool = False, params: Optional[dict] = None,
                 emit_qid: bool = False, init_on_tick: bool = False):
        self.spec = spec
        self.sel_fns = sel_fns          # out name -> CompiledExpr (ref.attr env)
        self.having = having
        self.P, self.A = P, A
        self.f64 = f64
        self.playback = playback
        # chains with a pre-registered START state (absent / min-0 count
        # head): each lane lazily arms one slot on first activity.
        # init_on_tick: unpartitioned plans also arm on a timer tick (the
        # host matcher starts at plan start, not first event); partitioned
        # lanes arm only on their first OWN event (host clones are created
        # lazily per key).
        self.needs_init = spec.needs_init_slot
        self.init_on_tick = init_on_tick
        # multi-query lanes: per-lane (P,) parameter vectors for lifted
        # constants, baked into the trace; emit_qid adds a lane-id row so
        # the host can route each match to its query's output stream
        self.params = params or {}
        self.emit_qid = emit_qid
        self._mode = None if f64 else F32_MODE
        self.E = E if E is not None else (1 if spec.S == 1 else min(A, 2))

        # ---- state-row assignment ----------------------------------------
        kc = kl = ka = 0
        for pos in spec.positions:
            if pos.is_count:
                pos.cnt_row = kc
                kc += 1
            if pos.op is not None:
                pos.log_row = kl
                kl += 1
            pos.dl_rows = {}
            for ni, n in enumerate(pos.nodes):
                if n.kind == "absent" and n.waiting_ms is not None:
                    pos.dl_rows[ni] = ka
                    ka += 1
        self.Kc, self.Kl, self.Ka = kc, kl, ka
        self.has_absent = any(n.kind == "absent" for n in spec.all_nodes)

        # ---- capture rows: only columns something downstream reads -------
        cap_keys: set = set()
        for pos in spec.positions:
            for n in pos.nodes:
                for ce in n.step_conjs:
                    for k in ce.reads:
                        if k == "__timestamp__":
                            continue
                        ref = k.split(".", 1)[0]
                        if ref != n.ref:
                            cap_keys.add(k)
        for ce in list(sel_fns.values()) + ([having] if having else []):
            for k in ce.reads:
                if k.startswith("__present__."):
                    cap_keys.add(k)
                elif "." in k and not k.startswith("__"):
                    cap_keys.add(k)
        # present bits for maybe-absent refs are always emitted (host null
        # reconstruction needs them even when the selector doesn't is-null)
        self._maybe_absent = spec.maybe_absent_refs()
        sel_refs = set()
        sel_rparts = set()
        for ce in sel_fns.values():
            for k in ce.reads:
                if "." in k and not k.startswith("__"):
                    sel_rparts.add(k.split(".", 1)[0])
                    sel_refs.add(_base_ref(k.split(".", 1)[0])[0])
        for r in self._maybe_absent & sel_refs:
            cap_keys.add(f"__present__.{r}")

        # indexed captures over count positions that may be UNFILLED at
        # emission (fewer than i+1 occurrences collected): the host emits
        # NULL for them (interp/nfa.py env_of_captures leaves the key out
        # of the env).  Selector reads get a per-index presence bit so the
        # host can null-reconstruct; predicate/having reads can't express
        # null semantics on device and fall back.
        minc_of = {p.nodes[0].ref: p.min_count
                   for p in spec.positions if p.is_count}
        self._maybe_unfilled = set()
        for k in list(cap_keys):
            if k.startswith("__present__."):
                continue
            refpart = k.split(".", 1)[0]
            base, cidx = _base_ref(refpart)
            if cidx is None or base not in minc_of:
                continue
            if cidx not in ("last", "last-1") and not cidx.isdigit():
                continue        # the _key_type loop below rejects it
            want = (1 if cidx == "last" else
                    2 if cidx == "last-1" else int(cidx) + 1)
            if want > minc_of[base]:
                self._maybe_unfilled.add(refpart)
        if self._maybe_unfilled:
            conjs = [c for n_ in spec.all_nodes for c in n_.step_conjs]
            if having is not None:
                conjs.append(having)
            for ce in conjs:
                for k in ce.reads:
                    if "." in k and k.split(".", 1)[0] in self._maybe_unfilled:
                        raise DeviceNFAUnsupported(
                            f"predicate reads maybe-unfilled indexed "
                            f"capture {k!r}")
        self._unfilled_sel = sorted(self._maybe_unfilled & sel_rparts)
        for rp in self._unfilled_sel:
            cap_keys.add(f"__present__.{rp}")

        self._key_type: dict = {}
        for k in sorted(cap_keys):
            if k.startswith("__present__."):
                self._key_type[k] = ast.AttrType.BOOL
                continue
            refpart, attr = k.split(".", 1)
            base, cidx = _base_ref(refpart)
            if base not in spec.schemas:
                raise DeviceNFAUnsupported(f"unresolvable capture key {k!r}")
            if cidx is not None and cidx not in ("last", "last-1") \
                    and not cidx.isdigit():
                raise DeviceNFAUnsupported(f"indexed capture {k!r}")
            self._key_type[k] = spec.schemas[base].type_of(attr)
        with compute_dtypes(self._mode):
            grp = {}
            for k, t in self._key_type.items():
                if k.startswith("__present__."):
                    grp[k] = "i"
                else:
                    grp[k] = self._group_of(jnp_dtype(t))
        self.rows_f = [k for k in sorted(cap_keys) if grp[k] == "f"]
        self.rows_l = [k for k in sorted(cap_keys) if grp[k] == "l"]
        self.rows_i = [k for k in sorted(cap_keys) if grp[k] == "i"]
        if spec.S > 1 or self.has_absent or spec.positions[0].op is not None \
                or spec.positions[0].is_count:
            self.rows_i += ["__comp_ts__", "__comp_seq__"]
        self._parked_emission = "__comp_ts__" in self.rows_i
        self._row_of = {k: ("f", i) for i, k in enumerate(self.rows_f)}
        self._row_of.update({k: ("i", i) for i, k in enumerate(self.rows_i)})
        self._row_of.update({k: ("l", i) for i, k in enumerate(self.rows_l)})

        # or-sides whose selected outputs must come back as NULL: selector
        # outputs that are plain variables over maybe-absent refs (anything
        # fancier can't be null-reconstructed host-side)
        self.null_outputs: dict = {}      # out name -> ref (or indexed refpart)
        for name, ce in sel_fns.items():
            reads = [k for k in ce.reads if "." in k and not k.startswith("__")]
            rparts = {k.split(".", 1)[0] for k in reads}
            # indexed reads (e2[last].p over a count) null-reconstruct via
            # the per-index presence machinery; bare reads via the ref's
            # presence bit — don't double-count one read as both
            hit = set()
            for rp in rparts:
                base, cidx = _base_ref(rp)
                if cidx is not None:
                    if rp in self._maybe_unfilled:
                        hit.add(rp)
                elif base in self._maybe_absent:
                    hit.add(base)
            if not hit:
                continue
            if ce.is_var and len(hit) == 1:
                self.null_outputs[name] = next(iter(hit))
            else:
                # a derived expression (e.g. `x is null`) must EVALUATE
                # the null, which the device can't represent — fall back
                raise DeviceNFAUnsupported(
                    f"selector output {name!r} derives from a maybe-absent "
                    f"ref (only bare variables null-reconstruct)")

        # ---- output rows (post-selector) ----------------------------------
        self.out_names = list(sel_fns) + ["__timestamp__", "__seq__",
                                          "__head_seq__"]
        if emit_qid:
            self.out_names.append("__qid__")
        for r in sorted(self._maybe_absent & sel_refs):
            self.out_names.append(f"__present__.{r}")
        for rp in self._unfilled_sel:
            self.out_names.append(f"__present__.{rp}")
        with compute_dtypes(self._mode):
            self.out_dtypes = {n: jnp_dtype(ce.type)
                               for n, ce in sel_fns.items()}
        self.out_dtypes["__timestamp__"] = _I32   # local offsets
        self.out_dtypes["__seq__"] = _I32
        self.out_dtypes["__head_seq__"] = _I32
        if emit_qid:
            self.out_dtypes["__qid__"] = _I32
        for r in self._maybe_absent & sel_refs:
            self.out_dtypes[f"__present__.{r}"] = _I32
        for rp in self._unfilled_sel:
            self.out_dtypes[f"__present__.{rp}"] = _I32
        self._block_cache: dict = {}    # (T, M) -> jitted fn

    @staticmethod
    def _group_of(dt) -> str:
        if dt in (jnp.float32, jnp.float64):
            return "f"
        if dt == jnp.int64:
            return "l"
        return "i"

    @property
    def fdt(self):
        return jnp.float64 if self.f64 else jnp.float32

    # -- state ---------------------------------------------------------------

    def init_state(self) -> dict:
        P, A = self.P, self.A
        st = {} if not self.needs_init else \
            {"init": jnp.zeros((P,), dtype=bool)}
        first0 = NO_FIRST if self.needs_init else 0
        st.update({
            "occ": jnp.zeros((A, P), dtype=_I32),
            "first_ts": jnp.full((A, P), int(first0), dtype=_I32),
            "head_seq": jnp.zeros((A, P), dtype=_I32),
            "cnt": jnp.zeros((self.Kc, A, P), dtype=_I32),
            "cnt_on": jnp.zeros((self.Kc, A, P), dtype=bool),
            "narm": jnp.zeros((self.Kc, A, P), dtype=bool),
            "fl": jnp.zeros((self.Kl, A, P), dtype=_I32),
            "dl": jnp.full((self.Ka, A, P), int(NO_DEADLINE), dtype=_I32),
            "caps_f": jnp.zeros((len(self.rows_f), A, P), dtype=self.fdt),
            "caps_i": jnp.zeros((len(self.rows_i), A, P), dtype=_I32),
            "caps_l": jnp.zeros((len(self.rows_l), A, P), dtype=jnp.int64),
            "armed0": jnp.ones((P,), dtype=bool),
            "of_slots": jnp.zeros((P,), dtype=_I32),
            "of_lanes": jnp.zeros((P,), dtype=_I32),
        })
        return st

    def occupancy(self, state) -> dict:
        """Sampled lane/slot occupancy + state-frontier width — the
        quantities that govern throughput on this kernel (state-set
        width / lane utilization; cf. Simultaneous Finite Automata,
        arxiv 1405.0562).  One D2H pull of `occ` (A, P) i32; call from
        a metrics scrape, not the hot path."""
        occ = np.asarray(state["occ"])
        S = self.spec.S
        live = (occ > 0) & (occ <= S)          # stationed partial matches
        per_lane = live.sum(axis=0)
        active = per_lane > 0
        d = {"slots_total": int(occ.size),
             "slots_live": int(per_lane.sum()),
             "slots_parked": int((occ == S + 1).sum()),
             "lanes_total": int(occ.shape[1]),
             "lanes_active": int(active.sum()),
             "frontier_width_max": int(per_lane.max()) if occ.size else 0}
        if d["lanes_active"]:
            d["frontier_width_mean"] = round(
                float(per_lane[active].mean()), 3)
        return d

    # -- env helpers -----------------------------------------------------

    def _caps_env(self, caps: dict) -> dict:
        """Capture rows as named (A, P) views (bool rows decoded)."""
        env = {}
        for k, (g, i) in self._row_of.items():
            col = caps[f"caps_{g}"][i]
            t = self._key_type.get(k)
            if t == ast.AttrType.BOOL:
                col = col != 0
            env[k] = col
        for k, v in self.params.items():
            env[k] = jnp.asarray(v)         # (P,) broadcasts vs (A, P)
        return env

    def _event_env(self, x: dict, n: PNode, base_ts) -> dict:
        """Arriving event's own columns as (P,) arrays (broadcast vs (A,P))."""
        env = {}
        sch = self.spec.schemas[n.ref]
        for a in sch.attributes:
            key = f"{n.scode}.{a.name}"
            if key in x:
                env[f"{n.ref}.{a.name}"] = x[key]
        env["__timestamp__"] = base_ts + x["__ts__"].astype(jnp.int64)
        return env

    def _node_match(self, x: dict, n: PNode, caps_env: dict, base_ts,
                    valid) -> jnp.ndarray:
        """(A, P) mask: does the arriving event satisfy node n's condition
        (stream + filters)?  Independent of slot station."""
        P = self.P
        m = valid
        if len(self.spec.stream_ids) > 1:
            m = m & (x["__scode__"] == n.scode)
        if n.pre_key is not None:
            m = m & x[n.pre_key]
        m = jnp.broadcast_to(m, (self.A, P)) if m.ndim == 1 else m
        for ce in n.step_conjs:
            env = dict(caps_env)
            env.update(self._event_env(x, n, base_ts))
            m = m & jnp.broadcast_to(ce.fn(env), (self.A, P))
        return m

    def _write_caps(self, caps: dict, mask, values: dict) -> dict:
        """Masked write of named values into capture rows; `mask` (A,P);
        values maps cap key -> (P,) / (A,P) array (missing keys skipped)."""
        caps = dict(caps)
        for g, rows in (("f", self.rows_f), ("i", self.rows_i),
                        ("l", self.rows_l)):
            idx, vals = [], []
            arr = caps[f"caps_{g}"]
            for i, k in enumerate(rows):
                if k in values:
                    idx.append(i)
                    v = values[k]
                    if getattr(v, "ndim", 0) < 2:
                        v = jnp.broadcast_to(v, (self.P,))[None, :]
                    vals.append(v.astype(arr.dtype))
            if not idx:
                continue
            if len(idx) == arr.shape[0]:
                new = jnp.stack([jnp.broadcast_to(v, (self.A, self.P))
                                 for v in vals], axis=0)
                caps[f"caps_{g}"] = jnp.where(mask[None], new, arr)
            else:
                for i, v in zip(idx, vals):
                    caps[f"caps_{g}"] = caps[f"caps_{g}"].at[i].set(
                        jnp.where(mask, v, caps[f"caps_{g}"][i]))
        return caps

    # -- the per-event step ----------------------------------------------

    def _step(self, carry: dict, x: dict):
        spec, P, A, E = self.spec, self.P, self.A, self.E
        S = spec.S
        PARK = S + 1
        occ0 = carry["occ"]           # pre-event stations (two-phase commit)
        occ = occ0
        first_ts, head_seq = carry["first_ts"], carry["head_seq"]
        cnt, cnt_on, fl, dl = (carry["cnt"], carry["cnt_on"], carry["fl"],
                               carry["dl"])
        narm = carry["narm"]
        caps = {k: carry[k] for k in ("caps_f", "caps_i", "caps_l")}
        armed0 = carry["armed0"]
        of_slots, of_lanes = carry["of_slots"], carry["of_lanes"]
        base_ts = x["__base_ts__"]

        ts, seq, valid = x["__ts__"], x["__seq__"], x["__valid__"]
        tick = x.get("__tick__")
        timey = valid if tick is None else (valid | tick)
        if self.playback:
            dl_fire = timey
        elif tick is not None:
            dl_fire = tick
        else:
            dl_fire = jnp.zeros((P,), dtype=bool)

        init_flag = carry.get("init")
        if self.needs_init:
            # lazy initial slot (host: PatternMatcher.start registers the
            # entry PM; partition clones start on their key's first event).
            # Slot 0 of a virgin lane is free by construction.
            trigger = (valid | tick) if (self.init_on_tick
                                         and tick is not None) else valid
            act = ~init_flag & trigger                      # (P,)
            init_flag = init_flag | act
            hot0 = (jnp.arange(A, dtype=_I32)[:, None] == 0) & act[None, :]
            # deadline base: unpartitioned plans ship the START anchor
            # (host matcher.start time); partitioned lanes use their
            # first event's timestamp (host clones start per key)
            anchor = x.get("__anchor__")
            arm_ts = ts if anchor is None \
                else jnp.broadcast_to(anchor, ts.shape)
            head = spec.positions[0]
            if head.nodes[0].kind == "absent" or head.op is not None:
                # absent head (or logical head containing an absent):
                # station at the head, arm its deadlines at activation time
                occ0 = jnp.where(hot0, 1, occ0)
                cnt, cnt_on, narm, fl, dl = self._enter_position(
                    0, hot0, cnt, cnt_on, narm, fl, dl, arm_ts)
            else:
                # min-0 count head: collection arms on the head (and any
                # following optional counts); the station lands on the
                # first non-optional position (host: _commit_epsilons)
                land, mids = self._landing_from(-1)
                occ0 = jnp.where(hot0, land + 1, occ0)
                for t in (*mids, land):
                    cnt, cnt_on, narm, fl, dl = self._enter_position(
                        t, hot0, cnt, cnt_on, narm, fl, dl, arm_ts)
            head_seq = jnp.where(hot0, seq[None, :], head_seq)
            occ = occ0

        caps_env = self._caps_env(caps)
        age = ts[None, :] - first_ts
        narm0 = narm      # successor arms as of step START: a min crossing
        #                   and its consumption may not share one event
        #                   (host stages registrations until post-event)
        transitioned = jnp.zeros((A, P), dtype=bool)
        complete = jnp.zeros((A, P), dtype=bool)
        kill = jnp.zeros((A, P), dtype=bool)
        enters: list = []             # (target position index, mask)
        cap_writes: list = []         # (mask, values dict)

        # node-match masks (station-independent; shared below)
        nm: dict = {}
        for pi, pos in enumerate(spec.positions):
            for ni, n in enumerate(pos.nodes):
                nm[(pi, ni)] = self._node_match(x, n, caps_env, base_ts, valid)

        # absent-deadline pre-pass: deadlines at or before this event's
        # timestamp fire BEFORE the event is processed (the host's playback
        # pre-fire loop / scheduler ordering), so the freed slot can consume
        # this very event at its next position.  `every`-wrapped absents
        # fork: the CLONE advances, the standing arm re-arms its deadline
        # one waiting period later (host: on_timer sticky branch).
        for pi, pos in enumerate(spec.positions):
            if pos.op is not None or not pos.dl_rows:
                continue
            n0 = pos.nodes[0]
            if n0.kind != "absent":
                continue
            r = pos.dl_rows[0]
            due = (occ0 == pi + 1) & (dl[r] <= ts[None, :]) & dl_fire[None, :]
            if pos.sticky:
                (occ0, first_ts, head_seq, cnt, cnt_on, narm, fl, dl,
                 caps, adv, lost) = self._fork_slots(
                    due, occ0, first_ts, head_seq, cnt, cnt_on, narm, fl,
                    dl, caps)
                of_slots = of_slots + lost
                # clones inherited the fired deadline value; read it
                # BEFORE re-arming the standing arms one period later
                dl_at = dl[r]
                rearm = jnp.int32(max(n0.waiting_ms or 1, 1))
                dl = dl.at[r].set(jnp.where(due, dl[r] + rearm, dl[r]))
            else:
                adv = due
                dl_at = dl[r]             # fired deadline (emission ts)
            # host: work.first_ts = dl when still unset (timer advance)
            first_ts = jnp.where(adv & (first_ts == NO_FIRST), dl_at,
                                 first_ts)
            if pi == S - 1:
                complete = complete | adv
                cap_writes.append((adv, {
                    "__comp_ts__": dl_at, "__comp_seq__": seq,
                    f"__present__.{n0.ref}": jnp.zeros((P,), _I32)}))
            else:
                land, mids = self._landing_from(pi)
                occ0 = jnp.where(adv, land + 1, occ0)
                for t in (*mids, land):
                    cnt, cnt_on, narm, fl, dl2 = self._enter_position(
                        t, adv, cnt, cnt_on, narm, fl, dl, dl_at)
                    dl = dl2
                zero_e = self._present_zero(
                    {n.ref for t in (*mids, land)
                     for n in spec.positions[t].nodes})
                zero_e[f"__present__.{n0.ref}"] = jnp.zeros((P,), _I32)
                caps = self._write_caps(caps, adv, zero_e)
            # disarm the fired row: the advancing slot (clone, for sticky)
            # left this position — a live slot carrying the stale value
            # would pin the reported min-deadline and wedge the scheduler
            clear = adv if pos.sticky else due
            dl = dl.at[r].set(jnp.where(clear, NO_DEADLINE, dl[r]))
        occ = occ0

        # within expiry per station (lazy, on event/tick time — reference
        # StreamPreStateProcessor.java:102-113)
        expired = jnp.zeros((A, P), dtype=bool)
        at_pos: list = []
        for pi, pos in enumerate(spec.positions):
            at = occ0 == pi + 1
            if pos.within_ms is not None:
                exp = at & timey[None, :] & (age > jnp.int32(pos.within_ms))
                expired = expired | exp
                at = at & ~exp
            at_pos.append(at)

        def advance(pi_from: int, mask):
            nonlocal occ, complete
            if pi_from == S - 1:
                complete = complete | mask
                return
            # epsilon cascade: mid-chain optional counts (min 0) arm
            # collection but the station lands on the first non-optional
            # position (host: _commit_epsilons registers successors at
            # entry; FINAL is never epsilon-reached, so an all-optional
            # suffix stations on the last count without emitting)
            t, mids = self._landing_from(pi_from)
            for mid in mids:
                enters.append((mid, mask))
            occ = jnp.where(mask, t + 1, occ)
            enters.append((t, mask))

        # --- count collection (station-independent: a partial match keeps
        #     absorbing occurrences while waiting further down the chain,
        #     reference CountPreStateProcessor pending lists) -------------
        for pi, pos in enumerate(spec.positions):
            if not pos.is_count:
                continue
            c = pos.cnt_row
            collect = cnt_on[c] & nm[(pi, 0)]
            newc = cnt[c] + collect.astype(_I32)
            vals = self._count_capture_values(x, pos.nodes[0], newc, caps)
            if pi == S - 1:
                vals["__comp_ts__"] = ts
                vals["__comp_seq__"] = seq
            cap_writes.append((collect, vals))
            cnt = cnt.at[c].set(newc)
            cnt_on = cnt_on.at[c].set(
                cnt_on[c] & (newc < jnp.int32(pos.max_count)))
            if pi < S - 1:
                cross = collect & (newc == jnp.int32(pos.min_count))
                narm = narm.at[c].set(narm[c] | cross)
                # epsilon cascade while the station STAYS here: optional
                # counts after this one arm their collection (staged to
                # post-event, like the host's deferred registrations)
                _land, mids_x = self._landing_from(pi)
                for midp in mids_x:
                    enters.append((midp, cross))
            transitioned = transitioned | collect
            if pi == S - 1:
                # count in the final position: every collection at or past
                # min emits (reference _emit_or_stage for count-final)
                complete = complete | (collect
                                       & (newc >= jnp.int32(pos.min_count)))

            # adjacent count positions: the previous count's armed
            # successor IS this count — entry consumes the arm and counts
            # the entering event as occurrence #1
            prevp = spec.positions[pi - 1] if pi else None
            if prevp is not None and prevp.is_count:
                ent = at_pos[pi - 1] & narm0[prevp.cnt_row] & nm[(pi, 0)]
                narm = narm.at[prevp.cnt_row].set(
                    narm[prevp.cnt_row] & ~ent)
                occ = jnp.where(ent, pi + 1, occ)
                transitioned = transitioned | ent
                one = jnp.where(ent, 1, cnt[c])
                cnt = cnt.at[c].set(one)
                cnt_on = cnt_on.at[c].set(
                    jnp.where(ent, pos.max_count > 1, cnt_on[c]))
                caps = self._write_caps(
                    caps, ent, self._present_zero({pos.nodes[0].ref}))
                evals = self._count_capture_values(
                    x, pos.nodes[0], jnp.where(ent, 1, 0), caps)
                if pi == S - 1:
                    evals["__comp_ts__"] = ts
                    evals["__comp_seq__"] = seq
                    complete = complete | (ent
                                           & (pos.min_count <= 1))
                else:
                    narm = narm.at[c].set(
                        narm[c] | (ent & (pos.min_count <= 1)))
                cap_writes.append((ent, evals))

        # --- per-position station logic -----------------------------------
        for pi, pos in enumerate(spec.positions):
            at = at_pos[pi]
            if pos.is_count:
                continue              # handled above
            if pi == 0 and pos.op is None \
                    and pos.nodes[0].kind != "absent":
                continue              # plain stream head: alloc below
                                      # (absent heads hold an init slot
                                      # that forbidden arrivals must kill)

            if pos.op is not None:
                fl, dl, k2, t2 = self._logical_step(
                    pi, pos, at, nm, x, ts, seq, dl, fl, caps,
                    cap_writes, advance, dl_fire)
                kill = kill | k2
                transitioned = transitioned | t2
                continue

            n0 = pos.nodes[0]
            if n0.kind == "absent":
                # forbidden arrival kills (deadline passage is handled by
                # the pre-pass above, reference
                # AbsentStreamPreStateProcessor.java:60-115); an `every`
                # arm re-arms its wait after the offender instead (host:
                # _absent_stream_arrived sticky branch)
                arr = at & nm[(pi, 0)]
                if pos.sticky:
                    r = pos.dl_rows.get(0)
                    if r is not None:
                        dl = dl.at[r].set(jnp.where(
                            arr, ts[None, :] + jnp.int32(n0.waiting_ms or 0),
                            dl[r]))
                else:
                    kill = kill | arr
                continue

            # (1,1) stream position: eligible when stationed here, or via
            # an armed predecessor count (set at its exact min crossing,
            # consumed here) — walking back across a run of OPTIONAL
            # counts, whose arms chain (host: _commit_epsilons keeps the
            # pm pending at every node of the run)
            elig = at
            chain = []               # armed predecessor count positions
            j = pi - 1
            while j >= 0 and spec.positions[j].is_count:
                chain.append(j)
                elig = elig | (at_pos[j]
                               & narm0[spec.positions[j].cnt_row])
                if spec.positions[j].min_count != 0:
                    break
                j -= 1
            m = elig & nm[(pi, 0)]
            for j in chain:
                cr = spec.positions[j].cnt_row
                narm = narm.at[cr].set(narm[cr] & ~m)
            transitioned = transitioned | m
            if pos.sticky:
                # `every` below the head: the slot is a standing arm — a
                # CLONE advances carrying this capture, the original stays
                # armed (host oracle: PM.sticky_at clone in _transition;
                # reference: EveryInnerStateRuntime re-registration)
                (occ, first_ts, head_seq, cnt, cnt_on, narm, fl, dl, caps,
                 m, lost) = self._fork_slots(
                    m, occ, first_ts, head_seq, cnt, cnt_on, narm, fl, dl,
                    caps)
                of_slots = of_slots + lost
                transitioned = transitioned | m
            vals = self._capture_values(x, n0)
            vals["__comp_ts__"] = ts
            vals["__comp_seq__"] = seq
            cap_writes.append((m, vals))
            advance(pi, m)

        dead = expired | kill
        occ = jnp.where(dead, 0, occ)
        if self.Kc:
            cnt_on = cnt_on & ~dead[None]
            narm = narm & ~dead[None]
        if self.Ka:
            dl = jnp.where(dead[None], NO_DEADLINE, dl)
        complete = complete & ~dead

        # --- apply capture writes (post-match) ----------------------------
        for mask, vals in cap_writes:
            caps = self._write_caps(caps, mask & ~dead, vals)

        # --- completion: park (slot freed at drain) or, for completions
        #     whose count is still collecting, direct-emit keeping the slot
        survivor = jnp.zeros((A, P), dtype=bool)
        if self.Kc and spec.positions[S - 1].is_count:
            survivor = cnt_on[spec.positions[S - 1].cnt_row]
        park = complete & ~survivor
        emit_now = complete & survivor
        occ = jnp.where(park, PARK, occ)
        if self.Kc:
            # a parked snapshot must freeze: station-independent collection
            # would otherwise overwrite captures before the drain lane emits
            # (the host's surviving count-pm keeps collecting, but it can
            # never re-emit, so freezing is unobservable)
            cnt_on = cnt_on & ~park[None]
            narm = narm & ~park[None]

        # --- entry writes on advance --------------------------------------
        for tpi, mask in enters:
            mask = mask & ~dead
            tpos = spec.positions[tpi]
            cnt, cnt_on, narm, fl, dl = self._enter_position(
                tpi, mask, cnt, cnt_on, narm, fl, dl, ts)
            # clear stale capture/present rows of the entered position's
            # refs (slots are reused; a previous life's captures must not
            # leak into this match's emission)
            caps = self._write_caps(
                caps, mask, self._present_zero({n.ref for n in tpos.nodes}))

        if self.needs_init:
            # first capture stamps the within-anchor (host: first_ts set on
            # first captures append; init slots start with NO_FIRST)
            stamp = transitioned & (first_ts == NO_FIRST)
            first_ts = jnp.where(stamp, ts[None, :], first_ts)

        # --- sequence strictness ------------------------------------------
        if spec.is_sequence:
            started = (occ > 0) & (occ < PARK) & (first_ts != NO_FIRST)
            kills = started & ~transitioned & valid[None, :]
            occ = jnp.where(kills, 0, occ)
            if self.Kc:
                cnt_on = cnt_on & ~kills[None]
                narm = narm & ~kills[None]

        # --- emission lanes ------------------------------------------------
        if self._parked_emission:
            occ, y, lost = self._drain_done(occ, head_seq, caps, emit_now)
            of_lanes = of_lanes + lost.sum(axis=0, dtype=_I32)

        # --- head: slot alloc (or direct single-position emission) --------
        head = spec.positions[0]
        if self.needs_init:
            ok0 = jnp.zeros((P,), dtype=bool)   # entry = the init slot
        else:
            ok0 = armed0 & self._head_match(x, head, valid)
        if not spec.every_head:
            armed0 = armed0 & ~ok0
        if not self._parked_emission:
            y = self._emit_single(x, head.nodes[0], ts, seq, ok0)
        else:
            free = occ == 0
            has_free = free.any(axis=0)
            do = ok0 & has_free
            of_slots = of_slots + (ok0 & ~has_free).astype(_I32)
            hot = free & (jnp.cumsum(free.astype(_I32), axis=0,
                                     dtype=_I32) == 1) & do[None, :]
            first_ts = jnp.where(hot, ts[None, :], first_ts)
            head_seq = jnp.where(hot, seq[None, :], head_seq)
            occ, cnt, cnt_on, narm, fl, dl, caps = self._alloc_head(
                x, head, hot, occ, cnt, cnt_on, narm, fl, dl, caps, ts, seq,
                PARK)

        carry = {"occ": occ, "first_ts": first_ts, "head_seq": head_seq,
                 "cnt": cnt, "cnt_on": cnt_on, "narm": narm, "fl": fl,
                 "dl": dl,
                 "caps_f": caps["caps_f"], "caps_i": caps["caps_i"],
                 "caps_l": caps["caps_l"], "armed0": armed0,
                 "of_slots": of_slots, "of_lanes": of_lanes}
        if init_flag is not None:
            carry["init"] = init_flag
        return carry, y

    # -- helpers for pieces of the step ----------------------------------

    def _fork_slots(self, src, occ, first_ts, head_seq, cnt, cnt_on, narm,
                    fl, dl, caps):
        """Clone every `src` slot into a free slot (rank-matched); returns
        updated state + the clone mask (the clones are the ones that then
        advance).  Clones that find no free slot count into the overflow
        counter — the host grows A and retries the block exactly."""
        A = self.A
        srci = src.astype(_I32)
        nfork = jnp.cumsum(srci, axis=0)
        src_rank = nfork - srci
        total = nfork[-1]                               # (P,)
        free = occ == 0
        freei = free.astype(_I32)
        dst_rank = jnp.cumsum(freei, axis=0) - freei
        dst = free & (dst_rank < total[None, :])
        lost = jnp.maximum(total - jnp.sum(freei, axis=0), 0).astype(_I32)
        key = jnp.where(src, src_rank, A + 1)
        by_rank = jnp.argsort(key, axis=0)              # (A, P)
        src_of = jnp.take_along_axis(by_rank,
                                     jnp.minimum(dst_rank, A - 1), axis=0)

        def cp(row):
            g = jnp.take_along_axis(row, src_of, axis=0)
            return jnp.where(dst, g, row)

        def cp3(t):
            if t.shape[0] == 0:
                return t
            g = jnp.take_along_axis(
                t, jnp.broadcast_to(src_of[None], t.shape), axis=1)
            return jnp.where(dst[None], g, t)
        occ = cp(occ)
        first_ts = cp(first_ts)
        head_seq = cp(head_seq)
        cnt, cnt_on, narm, fl, dl = (cp3(cnt), cp3(cnt_on), cp3(narm),
                                     cp3(fl), cp3(dl))
        caps = {k: cp3(v) for k, v in caps.items()}
        return (occ, first_ts, head_seq, cnt, cnt_on, narm, fl, dl, caps,
                dst, lost)

    def _landing_from(self, pi_from: int):
        """Station landing after pi_from, skipping mid-chain optional
        counts (min 0): returns (landing_pi, [skipped positions])."""
        t = pi_from + 1
        mids = []
        S = self.spec.S
        while (t < S - 1 and self.spec.positions[t].is_count
               and self.spec.positions[t].min_count == 0):
            mids.append(t)
            t += 1
        return t, mids

    def _present_zero(self, refs: Optional[set] = None) -> dict:
        """Zero-writes for presence rows (base + per-index) — applied when
        a slot is reused or advances into a position, so a previous life's
        captures can't leak.  refs=None clears every presence row."""
        out = {}
        for k in self.rows_i:
            if not k.startswith("__present__."):
                continue
            if refs is None or _base_ref(k[len("__present__."):])[0] in refs:
                out[k] = jnp.zeros((self.P,), _I32)
        return out

    def _enter_position(self, tpi, mask, cnt, cnt_on, narm, fl, dl, ts):
        """State-row resets/arms when slots advance into position tpi."""
        tpos = self.spec.positions[tpi]
        if tpos.is_count:
            cnt = cnt.at[tpos.cnt_row].set(jnp.where(mask, 0, cnt[tpos.cnt_row]))
            cnt_on = cnt_on.at[tpos.cnt_row].set(
                jnp.where(mask, True, cnt_on[tpos.cnt_row]))
            # min-0 counts arm their successor from entry (epsilon)
            eps = tpos.min_count == 0 and tpi < len(self.spec.positions) - 1
            narm = narm.at[tpos.cnt_row].set(
                jnp.where(mask, eps, narm[tpos.cnt_row]))
        if tpos.log_row is not None:
            fl = fl.at[tpos.log_row].set(jnp.where(mask, 0, fl[tpos.log_row]))
        for ni, r in (tpos.dl_rows or {}).items():
            w = tpos.nodes[ni].waiting_ms
            base = ts[None, :] if getattr(ts, "ndim", 1) == 1 else ts
            dl = dl.at[r].set(jnp.where(mask, base + jnp.int32(w), dl[r]))
        return cnt, cnt_on, narm, fl, dl

    def _capture_values(self, x, n: PNode) -> dict:
        """Values written when node n's event is captured into a slot."""
        vals: dict = {}
        for a in self.spec.schemas[n.ref].attributes:
            key = f"{n.scode}.{a.name}"
            if key not in x:
                continue
            vals[f"{n.ref}.{a.name}"] = x[key]
            vals[f"{n.ref}[last].{a.name}"] = x[key]
        vals[f"__present__.{n.ref}"] = jnp.ones((self.P,), _I32)
        return vals

    def _count_capture_values(self, x, n, newc, caps) -> dict:
        """Capture writes for a count collection: plain/[last]/[last-1]/[i]."""
        vals: dict = {}
        for a in self.spec.schemas[n.ref].attributes:
            key = f"{n.scode}.{a.name}"
            if key not in x:
                continue
            v = x[key]
            lk = f"{n.ref}[last].{a.name}"
            pk = f"{n.ref}[last-1].{a.name}"
            if pk in self._row_of and lk in self._row_of:
                g, i = self._row_of[lk]
                vals[pk] = caps[f"caps_{g}"][i]
            vals[f"{n.ref}.{a.name}"] = v
            vals[lk] = v
        vals[f"__present__.{n.ref}"] = jnp.ones((self.P,), _I32)
        # indexed rows e1[i].attr: written when this collection is the i-th
        for k in self._row_of:
            if k.startswith("__"):
                continue
            refpart, attr = k.split(".", 1)
            base, cidx = _base_ref(refpart)
            if base != n.ref or cidx is None or not cidx.isdigit():
                continue
            want = int(cidx) + 1
            keyx = f"{n.scode}.{attr}"
            if keyx in x:
                g, i = self._row_of[k]
                cur = caps[f"caps_{g}"][i]
                vals[k] = jnp.where(newc == jnp.int32(want),
                                    jnp.broadcast_to(x[keyx], cur.shape
                                                     ).astype(cur.dtype), cur)
        # per-index presence bits (host nulls unfilled indexed captures)
        for rp in self._unfilled_sel:
            pkey = f"__present__.{rp}"
            if pkey not in self._row_of:
                continue
            base, cidx = _base_ref(rp)
            if base != n.ref:
                continue
            want = (1 if cidx == "last"
                    else 2 if cidx == "last-1" else int(cidx) + 1)
            g, i = self._row_of[pkey]
            cur = caps[f"caps_{g}"][i]
            vals[pkey] = jnp.where(newc >= jnp.int32(want), jnp.int32(1), cur)
        return vals

    def _logical_step(self, pi, pos, at, nm, x, ts, seq, dl, fl, caps,
                      cap_writes, advance, dl_fire):
        """and/or partner pair at position pi (station mask `at`).
        Returns (fl', dl', kill, transitioned)."""
        A, P = self.A, self.P
        r = pos.log_row
        kill = jnp.zeros((A, P), dtype=bool)
        trans = jnp.zeros((A, P), dtype=bool)
        newbits = fl[r]
        side_due = jnp.zeros((A, P), dtype=bool)
        for ni, n in enumerate(pos.nodes):
            m = at & nm[(pi, ni)]
            if n.kind == "absent":
                dr = pos.dl_rows.get(ni)
                if pos.op == "or":
                    # arrival disarms this side (can no longer complete it)
                    if dr is not None:
                        dl = dl.at[dr].set(jnp.where(m, NO_DEADLINE, dl[dr]))
                else:
                    kill = kill | m
                if dr is not None:
                    due = at & (dl[dr] <= ts[None, :]) & dl_fire[None, :]
                    side_due = side_due | due
                    dl = dl.at[dr].set(jnp.where(due, NO_DEADLINE, dl[dr]))
                continue
            newbits = jnp.where(m, newbits | (1 << ni), newbits)
            trans = trans | m
            vals = self._capture_values(x, n)
            vals["__comp_ts__"] = ts
            vals["__comp_seq__"] = seq
            cap_writes.append((m & ~kill, vals))
        if pos.op == "or":
            done = at & ((newbits != 0) | side_due) & ~kill
        else:
            need = 0
            for ni, n in enumerate(pos.nodes):
                if n.kind != "absent":
                    need |= (1 << ni)
            # an absent partner is satisfied by not-having-arrived; a
            # deadline passage also advances the pair (host semantics)
            done = at & (((newbits & need) == need) | side_due) & ~kill
        advance(pi, done)
        trans = trans | done
        for dr in (pos.dl_rows or {}).values():
            dl = dl.at[dr].set(jnp.where(done | kill, NO_DEADLINE, dl[dr]))
        fl = fl.at[r].set(jnp.where(done, 0, newbits))
        return fl, dl, kill, trans

    def _head_match(self, x, head: Position, valid):
        """(P,) mask: does this event arm a new partial match?  Head
        filters are always pre-evaluated (lower_chain enforces it)."""
        P = self.P
        ok = jnp.zeros((P,), dtype=bool)
        for n in head.nodes:
            if n.kind == "absent":
                continue
            m = valid
            if len(self.spec.stream_ids) > 1:
                m = m & (x["__scode__"] == n.scode)
            if n.pre_key is not None:
                m = m & x[n.pre_key]
            ok = ok | m
        cs = x.get("__can_start__")
        if cs is not None:
            # chunked-halo mode: halo events extend pending matches but
            # never arm new heads (the lane that OWNS the event arms it)
            ok = ok & cs
        return ok

    def _alloc_head(self, x, head: Position, hot, occ, cnt, cnt_on, narm,
                    fl, dl, caps, ts, seq, PARK):
        """Entry writes for a freshly allocated slot (mask `hot`)."""
        # clear stale capture/present/deadline rows from the slot's
        # previous life (a stale armed deadline on a live slot would wedge
        # the timer scheduler in a fire-nothing loop)
        caps = self._write_caps(caps, hot, self._present_zero())
        if self.Ka:
            dl = jnp.where(hot[None], NO_DEADLINE, dl)

        if head.op is not None:
            r = head.log_row
            bits = jnp.zeros((self.A, self.P), dtype=_I32)
            for ni, n in enumerate(head.nodes):
                if n.kind == "absent":
                    continue
                m0 = x["__valid__"]
                if len(self.spec.stream_ids) > 1:
                    m0 = m0 & (x["__scode__"] == n.scode)
                if n.pre_key is not None:
                    m0 = m0 & x[n.pre_key]
                mm = hot & m0[None, :]
                bits = jnp.where(mm, bits | (1 << ni), bits)
                vals = self._capture_values(x, n)
                vals["__comp_ts__"] = ts
                vals["__comp_seq__"] = seq
                caps = self._write_caps(caps, mm, vals)
            occ = jnp.where(hot, 1, occ)
            fl = fl.at[r].set(jnp.where(hot, bits, fl[r]))
            if head.op == "or":
                # one side suffices: complete (S==1) or advance immediately
                done = hot & (bits != 0)
                land, mids = self._landing_from(0)
                occ = jnp.where(done,
                                PARK if self.spec.S == 1 else land + 1, occ)
                if self.spec.S > 1:
                    for t in (*mids, land):
                        cnt, cnt_on, narm, fl, dl = self._enter_position(
                            t, done, cnt, cnt_on, narm, fl, dl, ts)
        elif head.is_count:
            c = head.cnt_row
            occ = jnp.where(hot, 1, occ)
            one = jnp.where(hot, 1, cnt[c])
            cnt = cnt.at[c].set(one)
            cnt_on = cnt_on.at[c].set(
                jnp.where(hot, head.max_count > 1, cnt_on[c]))
            if self.spec.S > 1:
                narm = narm.at[c].set(
                    jnp.where(hot, head.min_count <= 1, narm[c]))
            vals = self._count_capture_values(x, head.nodes[0], one, caps)
            if self.spec.S == 1:
                vals["__comp_ts__"] = ts
                vals["__comp_seq__"] = seq
            caps = self._write_caps(caps, hot, vals)
            if self.spec.S == 1 and head.min_count <= 1:
                occ = jnp.where(hot, PARK, occ)   # immediate first emission
        else:
            land, mids = self._landing_from(0)
            occ = jnp.where(hot, land + 1, occ)
            vals = self._capture_values(x, head.nodes[0])
            caps = self._write_caps(caps, hot, vals)
            if self.spec.S > 1:
                for t in (*mids, land):
                    cnt, cnt_on, narm, fl, dl = self._enter_position(
                        t, hot, cnt, cnt_on, narm, fl, dl, ts)
        return occ, cnt, cnt_on, narm, fl, dl, caps

    def _emit_single(self, x, n: PNode, ts, seq, ok0):
        """Single-(1,1)-stream-position chain: direct lane emission."""
        P = self.P
        ev_env = {}
        for a in self.spec.schemas[n.ref].attributes:
            key = f"{n.scode}.{a.name}"
            if key in x:
                ev_env[f"{n.ref}.{a.name}"] = x[key]
                ev_env[f"{n.ref}[last].{a.name}"] = x[key]
        ev_env[f"__present__.{n.ref}"] = jnp.ones((P,), _I32)
        irows = [ok0.astype(_I32)[None, :]]
        frows = []
        for k in self.rows_f:
            v = ev_env.get(k, jnp.zeros((P,), self.fdt))
            frows.append(jnp.broadcast_to(v, (P,)).astype(self.fdt)[None, :])
        for k in self.rows_i:
            v = ev_env.get(k, jnp.zeros((P,), _I32))
            irows.append(jnp.broadcast_to(v, (P,)).astype(_I32)[None, :])
        irows.append(seq[None, :])      # __head_seq__
        if self.emit_qid:
            irows.append(jnp.arange(P, dtype=_I32)[None, :])
        for k in self.rows_l:
            v = jnp.broadcast_to(ev_env.get(k, jnp.zeros((P,), jnp.int64)),
                                 (P,)).astype(jnp.int64)
            irows.append(_hi32(v)[None, :])
            irows.append(_lo32(v)[None, :])
        irows.append(ts[None, :])       # __comp_ts__ (tail rows)
        irows.append(seq[None, :])      # __comp_seq__
        y = {"i": jnp.stack(irows, axis=0)}           # (Ci, 1=E, P)
        if frows:
            y["f"] = jnp.stack(frows, axis=0)
        return y

    def _drain_done(self, occ, head_seq, caps, emit_now=None):
        """Emit up to E parked completions (freed) + direct emissions
        (count survivors, not freed) per partition from slot storage.
        Returns (occ', y, lost): lost marks direct emissions that found
        no lane (host doubles E and retries the block)."""
        spec, P, A, E = self.spec, self.P, self.A, self.E
        PARK = spec.S + 1
        parked = occ == PARK
        done = parked if emit_now is None else (parked | emit_now)
        rank = jnp.cumsum(done.astype(_I32), axis=0, dtype=_I32) - done
        sels = [done & (rank == e) for e in range(E)]       # one-hot over A
        lv = jnp.stack([s.any(axis=0) for s in sels], axis=0)   # (E, P)
        igrid = [caps["caps_i"], head_seq[None]]
        if self.emit_qid:
            igrid.append(jnp.broadcast_to(
                jnp.arange(P, dtype=_I32)[None, :], (A, P))[None])
        if self.rows_l:
            cl = caps["caps_l"]
            igrid.append(_hi32(cl))
            igrid.append(_lo32(cl))
        igrid = jnp.concatenate(igrid, axis=0)              # (Ki', A, P)
        ilanes = jnp.stack(
            [jnp.where(s[None], igrid, 0).sum(axis=1, dtype=_I32)
             for s in sels], axis=1)                        # (Ki', E, P)
        y = {"i": jnp.concatenate([lv.astype(_I32)[None], ilanes], axis=0)}
        if self.rows_f:
            fgrid = caps["caps_f"]
            y["f"] = jnp.stack(
                [jnp.where(s[None], fgrid, 0).sum(axis=1, dtype=fgrid.dtype)
                 for s in sels], axis=1)                    # (Kf, E, P)
        emitted = done & (rank < E)
        freed = parked & emitted
        lost = (jnp.zeros((A, P), bool) if emit_now is None
                else (emit_now & ~parked & ~emitted))
        return jnp.where(freed, 0, occ), y, lost

    # lane-grid row order for y["i"] (after the lv row)
    def _ilane_names(self) -> list:
        names = list(self.rows_i) + ["__head_seq__"]
        if self.emit_qid:
            names.append("__qid__")
        for k in self.rows_l:
            names += [f"{k}.hi", f"{k}.lo"]
        if not self._parked_emission:
            names += ["__comp_ts__", "__comp_seq__"]
        return names

    # -- block ---------------------------------------------------------------

    def raw_block_fn(self, M: int) -> Callable:
        """Unjitted block(state, ev) — the framework's 'forward step' for
        compile checks and mesh-sharded execution."""
        return self._make_block(M)

    def block_fn(self, T: int, M: int) -> Callable:
        key = (T, M)
        fn = self._block_cache.get(key)
        if fn is None:
            fn = self._block_cache[key] = jax.jit(self._make_block(M, T))
        return fn

    def _pre_masks(self, ev: dict) -> dict:
        """Evaluate event-only filter conjuncts over the whole (T, P) block
        in one fused pass (outside the scan)."""
        out = {}
        for gi, n in enumerate(self.spec.all_nodes):
            if not n.pre_conjs:
                n.pre_key = None
                continue
            env = {}
            for a in self.spec.schemas[n.ref].attributes:
                key = f"{n.scode}.{a.name}"
                if key in ev:
                    env[f"{n.ref}.{a.name}"] = ev[key]
            env["__timestamp__"] = ev["__base_ts__"] \
                + ev["__ts__"].astype(jnp.int64)
            for k, v in self.params.items():
                env[k] = jnp.asarray(v)     # (P,) broadcasts vs (T, P)
            m = None
            for ce in n.pre_conjs:
                p = ce.fn(env)
                m = p if m is None else (m & p)
            n.pre_key = f"__pre{gi}__"
            # per-lane params make pre-masks (T, P) even when event grids
            # are broadcast (T, 1)
            out[n.pre_key] = jnp.broadcast_to(
                m, (ev["__ts__"].shape[0], self.P))
        return out

    def _make_block(self, M: int, T: Optional[int] = None) -> Callable:
        def block(state, ev):
            with compute_dtypes(self._mode):
                return self._block_impl(state, ev, M, T)
        return block

    def _chunk_dedup_row(self) -> int:
        """Row index (within the packed lane grid, after the lv row) of
        __comp_seq__ — used to suppress replayed-tail completions on
        device so they are never pulled to the host."""
        return 1 + self._ilane_names().index("__comp_seq__")

    def _expand_flat(self, ev: dict, T: int) -> dict:
        """Chunked-halo mode: the host ships events once as flat (F,)
        arrays; lane grids are gathered ON DEVICE (lane l reads events
        [l*CS, l*CS + T)), so the upload never carries the halo-duplicated
        (T, P) grids.  `__can_start__` marks each lane's OWN range (the
        first CS steps); trailing reads past the event count are invalid
        cells.  Events past a lane's halo are harmless: `within` expires
        every owned instance before they could matter (pattern_plan sizes
        T to cover the worst-case halo)."""
        P = self.P
        cs = ev["__cs__"].astype(_I32)          # own-chunk length
        nev = ev["__nev__"].astype(_I32)        # flat event count
        lane = jnp.arange(P, dtype=_I32)[None, :]
        t = jnp.arange(T, dtype=_I32)[:, None]
        idx = lane * cs + t                     # (T, P) global positions
        F = ev["__flat.__ts__"].shape[0]
        safe = jnp.clip(idx, 0, F - 1)
        out = {}
        for k, v in ev.items():
            if k.startswith("__flat."):
                out[k[len("__flat."):]] = v[safe]
        if "__seq__" not in out:
            # single-stream flushes have consecutive seqs: derive instead
            # of uploading another (F,) array
            out["__seq__"] = ev["__seq0__"].astype(_I32) + idx
        out["__valid__"] = idx < nev
        out["__can_start__"] = jnp.broadcast_to(t < cs, (T, P))
        out["__base_ts__"] = ev["__base_ts__"]
        out["__base_seq__"] = ev["__base_seq__"]
        return out

    def _block_impl(self, state, ev, M: int, T_static: Optional[int] = None):
        spec = self.spec
        ev = dict(ev)
        prev_seq = ev.pop("__prev_seq__", None)
        if "__cs__" in ev:
            ev = self._expand_flat(ev, T_static)
        ev.update(self._pre_masks(ev))
        base_ts = ev["__base_ts__"]
        anchor = ev.get("__anchor__")
        xs = {k: v for k, v in ev.items()
              if k not in ("__base_ts__", "__base_seq__", "__anchor__")}
        T = xs["__ts__"].shape[0]

        def step(carry, x):
            x = dict(x)
            x["__base_ts__"] = base_ts
            if anchor is not None:
                x["__anchor__"] = anchor
            return self._step(carry, x)

        carry, ys = lax.scan(step, dict(state), xs)
        if self._parked_emission:
            def drain_step(c, _):
                occ2, y2, _lost = self._drain_done(
                    c["occ"], c["head_seq"],
                    {k: c[k] for k in ("caps_f", "caps_i", "caps_l")})
                c2 = dict(c)
                c2["occ"] = occ2
                return c2, y2
            rounds = -(-self.A // self.E)
            carry, ys2 = lax.scan(drain_step, carry, None, length=rounds)
            ys = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=0), ys, ys2)

        # compact the (T', C, E, P) lane grids into flat (M,) buffers: one
        # i32 cumsum for positions + ONE scatter per row (searchsorted+
        # gather lowers to an O(M)-serialized loop on TPU: 460 ms at M=131k
        # vs 0.1 ms for the scatter form)
        ys_i = ys["i"]                        # (T', Ci, E, P) i32
        ys_f = ys.get("f")                    # (T', Cf, E, P) f32
        lv = ys_i[:, 0].reshape(-1) != 0      # (T'*E*P,)
        if prev_seq is not None:
            # chunked-halo replay: completions at or before the previous
            # flush's last seq already emitted — drop them BEFORE the
            # compaction so they never occupy the M buffer or the pull
            lv = lv & (ys_i[:, self._chunk_dedup_row()].reshape(-1)
                       > prev_seq.astype(_I32))
        pos = jnp.cumsum(lv.astype(_I32), dtype=_I32) - lv
        n = pos[-1] + lv[-1]
        wpos = jnp.where(lv & (pos < M), pos, M)
        cols = {}
        for r, name in enumerate(self._ilane_names()):
            cols[name] = jnp.zeros((M,), _I32).at[wpos].set(
                ys_i[:, r + 1].reshape(-1), mode="drop")
        if ys_f is not None:
            for r, name in enumerate(self.rows_f):
                cols[name] = jnp.zeros((M,), ys_f.dtype).at[wpos].set(
                    ys_f[:, r].reshape(-1), mode="drop")

        # rebuild typed env for selector/having
        env = {}
        for k, t in self._key_type.items():
            g, _i = self._row_of[k]
            if g == "l":
                env[k] = _join64(cols[f"{k}.hi"], cols[f"{k}.lo"])
            elif t == ast.AttrType.BOOL:
                env[k] = cols[k] != 0
            else:
                env[k] = cols[k].astype(jnp_dtype(t))
        env["__timestamp__"] = base_ts + cols["__comp_ts__"].astype(jnp.int64)
        if self.params:
            qid = jnp.clip(cols["__qid__"], 0, self.P - 1)
            for k, v in self.params.items():
                env[k] = jnp.asarray(v)[qid]
        sel = {name: jnp.broadcast_to(ce.fn(env), (M,))
               for name, ce in self.sel_fns.items()}
        valid = jnp.arange(1, M + 1, dtype=_I32) <= n
        if self.having is not None:
            henv = dict(env)
            henv.update(sel)
            valid = valid & jnp.broadcast_to(self.having.fn(henv), (M,))
        sel["__timestamp__"] = cols["__comp_ts__"]
        sel["__seq__"] = cols["__comp_seq__"]
        sel["__head_seq__"] = cols["__head_seq__"]
        if self.emit_qid:
            sel["__qid__"] = cols["__qid__"]
        for name in self.out_names:
            if name.startswith("__present__."):
                sel[name] = cols.get(name, jnp.ones((M,), _I32))

        # earliest pending deadline (for the host scheduler's next_wakeup)
        if self.Ka:
            live = (carry["occ"] > 0) & (carry["occ"] <= spec.S)
            min_dl = jnp.where(live[None], carry["dl"],
                               NO_DEADLINE).min().astype(_I32)
        else:
            min_dl = jnp.int32(NO_DEADLINE)

        # pack ALL outputs into ONE i32 matrix: every device->host transfer
        # pays a fixed latency (not yet measured on a locally attached chip —
        # ROADMAP "found at bring-up"), so one pull per block, not one per
        # column.  f32 rows travel bitcast to
        # i32; LONG as hi/lo pairs.  (f64 mode keeps a separate float pack —
        # correct but slower, documented.)
        meta = (jnp.zeros((M,), _I32)
                .at[0].set(n)
                .at[1].set(carry["of_slots"].sum(dtype=_I32))
                .at[2].set(carry["of_lanes"].sum(dtype=_I32))
                .at[3].set(min_dl))
        irows = [meta]
        if self.having is not None:     # else the host derives valid from n
            irows.append(valid.astype(_I32))
        frows = []
        for name in self.out_names:
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
        return carry, out

def pow2_at_least(n: int, lo: int = 8) -> int:
    return max(lo, 1 << max(0, math.ceil(math.log2(max(1, n)))))


def _hi32(v):
    return lax.shift_right_arithmetic(v, jnp.int64(32)).astype(_I32)


def _lo32(v):
    return lax.bitcast_convert_type(
        v.astype(jnp.uint64).astype(jnp.uint32), _I32)


def _join64(hi, lo):
    return (hi.astype(jnp.int64) << jnp.int64(32)) | \
        lax.bitcast_convert_type(lo, jnp.uint32).astype(jnp.int64)


def join64_np(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (hi.astype(np.int64) << 32) | lo.view(np.uint32).astype(np.int64)
