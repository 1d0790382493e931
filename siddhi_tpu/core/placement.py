"""Placement accounting: no silent demotions.

The engine's single worst historical bug class is *silent placement*: a
query that should run on the device path quietly landing on the host
interpreter because some lowering step swallowed an exception (PR 5
found a whole query class demoted that way).  This module makes every
placement decision a first-class record:

  * every interpreter fallback (and every rejected plan family) in the
    build path calls ``rt.placement.demote(...)`` with a machine-readable
    ``Demotion(query, rule_id, reason, cause)`` — the self-lint
    (``python -m siddhi_tpu.analysis --self``) fails CI on any swallow
    site in a plan-lowering file that records nothing;
  * ``rt.explain()`` (also ``GET /siddhi/artifact/explain`` and the
    ``python -m siddhi_tpu.analysis`` CLI) reports, per query: the chosen
    execution path (device family vs interpreter), the chosen pattern
    plan family, where each geometry knob came from
    (annotation / default), and the full reason chain for every rejected
    alternative;
  * ``statistics()["placement"]`` + the ``siddhi_tpu_interp_demotions``
    Prometheus series keep the counts scrapeable, so a future silent
    demotion shows up in the bench trajectory (bench.py summary carries
    a ``placement`` field per config).

Demotion rule ids (docs/ANALYSIS.md "Demotion records"):

  D-FILTER      device filter/projection lowering raised; interpreter path
  D-WINDOW      device window-aggregation shape unsupported
  D-JOIN        device join shape unsupported
  D-PATTERN     device pattern kernel unsupported (prefer mode)
  D-SHAPE       no device plan family covers this query shape
  D-POLICY      an annotation/env opt-out chose the host path
  D-FUSED       fused multi-query lane kernel unavailable for a group
  D-PARTITION   partitioned pattern fell back to per-key host clones
  D-FAMILY      a pattern plan family was rejected (forced-but-ineligible
                or failed build validation) in favor of another family
  D-QUARANTINE  the runtime degradation ladder swapped a device plan for
                its interpreter twin after consecutive dispatch failures
  D-AGG         an incremental aggregation stayed on the host reduce path
                instead of the device-resident bucket store (calendar
                durations, explicit opt-out, or jax unavailable)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..utils.locks import new_lock

DEMOTION_RULES = {
    "D-FILTER": "device filter/projection lowering failed",
    "D-WINDOW": "device window-aggregation shape unsupported",
    "D-JOIN": "device join shape unsupported",
    "D-PATTERN": "device pattern kernel unsupported",
    "D-SHAPE": "no device plan family covers this query shape",
    "D-POLICY": "annotation/env opt-out chose the host path",
    "D-FUSED": "fused multi-query lane kernel unavailable",
    "D-PARTITION": "partitioned pattern fell back to host clones",
    "D-FAMILY": "pattern plan family rejected",
    "D-QUARANTINE": "runtime ladder quarantined the plan",
    "D-AGG": "aggregation stayed on the host reduce path",
}

# rule ids whose records mean the query itself left (or never reached)
# the device path — D-FAMILY keeps the query on device under another
# family, D-FUSED only rejects the fused-lane packing (the query may
# still plan onto the device individually), and D-AGG concerns the
# aggregation state plane, not a query's execution path — so none of
# the three counts toward `interp_demotions`
_INTERP_RULES = frozenset(DEMOTION_RULES) - {"D-FAMILY", "D-FUSED", "D-AGG"}


@dataclass
class Demotion:
    """One recorded placement downgrade.  `cause` carries the swallowed
    exception (as ``TypeName: message``) when the demotion was
    exception-driven; `alternative` names the execution path that was
    rejected or lost (``device-filter``, ``scan``, ...)."""
    query: str
    rule_id: str
    reason: str
    cause: Optional[str] = None
    alternative: str = "device"

    def to_dict(self) -> dict:
        d = {"query": self.query, "rule_id": self.rule_id,
             "reason": self.reason, "alternative": self.alternative}
        if self.cause is not None:
            d["cause"] = self.cause
        return d


class PlacementLog:
    """Per-runtime collector of Demotion records.  Build-time demotions
    arrive on the constructing thread; runtime quarantines arrive on the
    dispatch thread — appends are lock-guarded, reads snapshot."""

    def __init__(self):
        self._lock = new_lock("PlacementLog._lock")
        self._demotions: list = []

    def demote(self, query: str, rule_id: str, reason: str,
               cause: Optional[BaseException] = None,
               alternative: str = "device") -> Demotion:
        if rule_id not in DEMOTION_RULES:
            raise ValueError(f"unknown demotion rule id {rule_id!r} "
                             f"(have {sorted(DEMOTION_RULES)})")
        d = Demotion(query, rule_id, str(reason),
                     f"{type(cause).__name__}: {cause}"
                     if cause is not None else None,
                     alternative)
        with self._lock:
            # idempotent per (query, rule, alternative): partition groups
            # re-plan the same query lazily per key — the first record
            # carries the reason; repeats must not grow without bound
            for prev in self._demotions:
                if (prev.query, prev.rule_id, prev.alternative) == \
                        (d.query, d.rule_id, d.alternative):
                    return prev
            self._demotions.append(d)
        return d

    def records(self) -> list:
        with self._lock:
            return list(self._demotions)

    def for_query(self, name: str) -> list:
        with self._lock:
            return [d for d in self._demotions if d.query == name]

    def interp_demotions(self) -> int:
        with self._lock:
            return sum(1 for d in self._demotions
                       if d.rule_id in _INTERP_RULES)

    def __len__(self) -> int:
        with self._lock:
            return len(self._demotions)


# ---------------------------------------------------------------------------
# EXPLAIN: per-query placement + geometry provenance + rejection chains
# ---------------------------------------------------------------------------

_QUERY_PLAN_KINDS = {
    "FilterProjectPlan": ("device", "filter"),
    "DeviceWindowAggPlan": ("device", "window"),
    "DeviceJoinPlan": ("device", "join"),
    "DevicePatternPlan": ("device", "pattern"),
    "MultiQueryDevicePatternPlan": ("device", "multi_query"),
    "InterpSingleQueryPlan": ("interpreter", "single"),
    "InterpJoinQueryPlan": ("interpreter", "join"),
    "InterpPatternQueryPlan": ("interpreter", "pattern"),
    "PartitionGroup": ("interpreter", "partition-group"),
}


def _knob(value, source: str) -> dict:
    return {"value": value, "source": source}


def _geometry_entry(rt, plan, kind: str) -> dict:
    """Each geometry knob the plan was built with: the value the PLAN
    holds (a correctness pin — join side filters, fused groups — can
    override an annotated depth) and the source rt.geometry resolved."""
    def knob(name, value):
        return _knob(value, rt.geometry[name][1])

    geo: dict = {}
    if hasattr(plan, "pipeline_depth"):
        geo["pipeline_depth"] = knob(
            "pipeline_depth", int(getattr(plan, "pipeline_depth", 0) or 0))
    if kind == "pattern":
        geo["chunk_lanes"] = knob(
            "chunk_lanes", int(getattr(plan, "_stateless_lanes", 0) or 0))
        geo["plan_family"] = knob(
            "plan_family", getattr(plan, "family", None))
    if kind == "multi_query":
        geo["lane_pack"] = _knob(*rt.geometry["lane_pack"])
    return geo


def _agg_name(plan) -> str:
    """Aggregation key for a plan: per-key partition clone instances
    (`<base>#<inst>`, partition.py) collapse onto their base query name
    — placement is per QUERY, never per partition key, or the counts
    (and the per-query Prometheus label set) would scale with key
    cardinality."""
    name = plan.name
    if "#" in name and not name.startswith("#"):
        return name.split("#", 1)[0]
    return name


def _query_entry(rt, plan) -> Optional[dict]:
    cls = type(plan).__name__
    if cls not in _QUERY_PLAN_KINDS:
        return None          # named windows, triggers, aggregations...
    path, kind = _QUERY_PLAN_KINDS[cls]
    lad = getattr(rt, "_ladders", {}).get(plan.name)
    quarantined = bool(lad is not None and getattr(lad, "quarantined", False))
    ent: dict = {"path": "interpreter" if quarantined else path,
                 "plan": cls, "kind": kind}
    fam = getattr(plan, "family", None)
    if kind == "pattern" and fam is not None:
        ent["family"] = fam
        ent.update(plan.explain_records())
        families = getattr(plan, "families", None)
        if families:
            rejected = {f: r for f, r in sorted(families.items())
                        if r is not True}
            if rejected:
                ent["rejected"] = rejected
    if kind == "multi_query":
        # what the fused group ran, nested: a top-level `family` would
        # read as a pattern plan's
        ent["fused"] = plan.fused
    if kind == "window":
        ent["window"] = plan.window
        ent["window_step"] = dict(plan.window_step)
        ent["window_ranks"] = dict(plan.window_ranks)
        ent["window_carry"] = plan.window_carry
    if kind == "partition-group":
        ent["queries"] = sorted(
            q.name(f"query_p{plan.index}_{qi}")
            for qi, q in enumerate(getattr(plan, "clone_queries", ())))
    if path == "device":     # interpreter plans hold no device geometry
        geo = _geometry_entry(rt, plan, kind)
        if geo:
            ent["geometry"] = geo
    dems = [d.to_dict() for d in rt.placement.for_query(_agg_name(plan))]
    if dems:
        ent["demotions"] = dems
    return ent


def explain(rt) -> dict:
    """The EXPLAIN plane: placement + reason chains for every query of a
    built runtime.  Deterministically ordered and JSON-safe — the
    service endpoint serves exactly this dict, and the test suite holds
    `GET /siddhi/artifact/explain` byte-for-byte equal to it."""
    queries: dict = {}
    for plan in list(getattr(rt, "_plans", ())):
        ent = _query_entry(rt, plan)
        if ent is None:
            continue
        base = _agg_name(plan)
        prev = queries.get(base)
        if prev is None:
            queries[base] = ent
        else:                # another per-key clone of the same query
            prev["instances"] = prev.get("instances", 1) + 1
    # the queryable-state plane: per-aggregation placement (device-
    # resident vs host), retention/eviction accounting, and the D-AGG
    # reason chain for anything that stayed on the host reduce path
    aggs: dict = {}
    for an, a in sorted(getattr(rt, "aggregations", {}).items()):
        ent = {"path": ("device-resident"
                        if getattr(a, "device_plan", None) is not None
                        else "device-batch" if getattr(a, "device", False)
                        else "host"),
               "durations": [d.name for d in a.durations]}
        ret = getattr(a, "retention_ms", None)
        if ret:
            ent["retention_ms"] = {d.name: v for d, v in sorted(
                ret.items(), key=lambda kv: kv[0].approx_millis)}
        if ent["path"] == "device-resident":
            ent["geometry"] = {"agg_capacity": _knob(
                *rt.geometry["agg_capacity"])}
        ev = getattr(a, "evicted", None)
        if ev and any(ev.values()):
            ent["evicted"] = {d.name: n for d, n in ev.items() if n}
        dems = [d.to_dict() for d in rt.placement.for_query(an)]
        if dems:
            ent["demotions"] = dems
        aggs[an] = ent
    # demotions whose query never produced a plan entry (fused-group
    # probes keyed by candidate names, partition clones not yet
    # instantiated) still surface at the top level
    return {
        "app": rt.app.name,
        "queries": {k: queries[k] for k in sorted(queries)},
        **({"aggregations": aggs} if aggs else {}),
        "demotions": [d.to_dict() for d in rt.placement.records()],
        "placement": summary(rt),
        # the durability plane's EXPLAIN entry: the SAME block
        # statistics() serves (rt.durability_report — one builder, so
        # the two observability surfaces can never disagree)
        "durability": rt.durability_report()
        if hasattr(rt, "durability_report")
        else {"policy": getattr(rt, "durability", "off")},
    }


def summary(rt) -> dict:
    """Compact placement accounting for statistics()/Prometheus/bench:
    device vs interpreter query counts + the demotion tally."""
    device = interp = 0
    queries: dict = {}
    for plan in list(getattr(rt, "_plans", ())):
        cls = type(plan).__name__
        if cls not in _QUERY_PLAN_KINDS:
            continue
        path, kind = _QUERY_PLAN_KINDS[cls]
        lad = getattr(rt, "_ladders", {}).get(plan.name)
        if lad is not None and getattr(lad, "quarantined", False):
            path = "interpreter"
        base = _agg_name(plan)
        prev = queries.get(base)
        if prev is not None:     # per-key clone: count the QUERY once
            prev["instances"] = prev.get("instances", 1) + 1
            continue
        if path == "device":
            device += 1
        else:
            interp += 1
        qent = {"path": path, "kind": kind}
        fam = getattr(plan, "family", None)
        if kind == "pattern" and fam is not None:
            qent["family"] = fam
        nd = len(rt.placement.for_query(base))
        if nd:
            qent["demotions"] = nd
        queries[base] = qent
    return {"device": device, "interpreter": interp,
            "interp_demotions": rt.placement.interp_demotions(),
            "demotions": len(rt.placement),
            "queries": {k: queries[k] for k in sorted(queries)}}
