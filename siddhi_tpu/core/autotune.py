"""Adaptive execution geometry: persistent autotuner + SLO batching control.

Every device plan family exposes geometry knobs — micro-batch/flush size,
`@app:devicePipeline` depth, NFA chunk-lane count, fused multi-query lane
packing — and they dominate performance the way kernel tile sizes do in an
inference stack: the chunking chosen for the hardware IS the performance
model (Simultaneous Finite Automata, arxiv 1405.0562; In-Memory Regular
Pattern Matching codesign, arxiv 2209.05686).  This module makes the
engine pick and adapt that geometry itself, in three cooperating parts:

  * `TuningCache` + `Autotuner` — offline/warmup sweep of a bounded
    candidate grid per app, scored with the telemetry latency histograms
    (`telemetry.Histogram` p99 + measured events/sec) over a synthetic or
    recorded sample tape.  Winners persist in an on-disk JSON cache keyed
    by (plan signature, device kind, JAX version), so later deploys of
    the same query shapes skip the sweep entirely.  The cache is surfaced
    via `GET /siddhi/artifact/tuning` and hit/miss gauges in
    `statistics()` / Prometheus; `python -m siddhi_tpu.core.autotune
    --lint` schema-checks a persisted cache (wired into
    scripts/smoke.sh so a malformed cache can never brick deploy — a
    corrupt file is also quarantined and ignored at load, never trusted).
  * `SLOController` — `@app:latencySLO('25ms')` adapts the runtime's
    micro-batch/flush cadence AIMD-style from the observed p99 of a
    rolling window (additive increase of the batch target while p99 sits
    below the hysteresis band, multiplicative decrease when the target is
    violated), with a telemetry-visible decision log.
    `@app:maxBatchLatency` rides the same controller in cadence-only
    (non-adaptive) mode, preserving its one-shot semantics exactly.
  * planner/runtime integration — plan constructors consult
    `pipeline_depth_for` / `chunk_lanes_for` / `fused_lane_pack_for`
    (annotation wins, then the tuning cache, then the built-in default);
    plans advertise a `regeometry(batch_hint, depth, ...)` hook; the
    runtime applies controller decisions at flush boundaries only and
    splits oversized batches with the PR-4 halving machinery
    (`faults.split_batch`), which already proves geometry splits are
    output-invariant — so outputs stay byte-identical to fixed geometry.
"""
from __future__ import annotations

import hashlib
import json
import math
import os
import time
import warnings
from collections import deque
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from ..query import ast
from ..utils.locks import new_lock

CACHE_VERSION = 1
GEOMETRY_KEYS = ("batch", "pipeline_depth", "chunk_lanes", "lane_pack",
                 "plan_family", "agg_capacity")
PLAN_FAMILIES = ("filter", "window", "join", "pattern", "multi_query", "app")
# pattern-kernel execution families (docs/PERFORMANCE.md "Plan families"):
# seq = persistent sequential-in-T NFA scan, chunk = stateless chunked-halo
# lanes, scan = associative-scan SFA, dfa = bit-packed multi-stride hybrid
PATTERN_FAMILIES = ("seq", "chunk", "scan", "dfa")


class AutotuneError(Exception):
    pass


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------

@dataclass
class Geometry:
    """One point in the execution-geometry space.  None = knob not set
    (the consumer keeps its annotation/default)."""
    batch: Optional[int] = None             # micro-batch / flush size
    pipeline_depth: Optional[int] = None    # @app:devicePipeline depth
    chunk_lanes: Optional[int] = None       # chunked-NFA lane count K
    lane_pack: Optional[int] = None         # fused multi-query lanes/kernel
    plan_family: Optional[str] = None       # pattern family (PATTERN_FAMILIES)
    agg_capacity: Optional[int] = None      # device agg bucket-ring slots

    def to_dict(self) -> dict:
        return {k: getattr(self, k) for k in GEOMETRY_KEYS
                if getattr(self, k) is not None}

    @classmethod
    def from_dict(cls, d: dict) -> "Geometry":
        out = {}
        for k in GEOMETRY_KEYS:
            if k not in d or d.get(k) is None:
                continue
            out[k] = str(d[k]) if k == "plan_family" else int(d[k])
        return cls(**out)

    def label(self) -> str:
        return ",".join(f"{k}={v}" for k, v in self.to_dict().items())


def device_kind() -> str:
    """The device the tuned numbers were measured on, as JAX names it
    (`jax.devices()[0].device_kind`, e.g. "TPU v5 lite" or "cpu") — a
    tuning measured on one device kind must never apply to another.
    Raises when JAX finds no backend: a tuning keyed "unknown" would
    apply anywhere."""
    import jax
    return str(jax.devices()[0].device_kind)


def jax_version() -> str:
    import jax
    return str(jax.__version__)


# ---------------------------------------------------------------------------
# plan signatures (cache keys)
# ---------------------------------------------------------------------------

def signature_of(family: str, payload) -> str:
    """Stable signature for one tuned shape: sha1 over the family plus a
    canonical text form of the query (its normalized AST repr — the
    dataclass reprs are deterministic).  The full cache key adds device
    kind + JAX version (see `cache_key`): a tuning measured on one
    backend/version never silently applies to another."""
    text = f"{family}|{payload!r}"
    return f"{family}:" + hashlib.sha1(text.encode()).hexdigest()[:20]


def family_of(plan) -> Optional[str]:
    cls = type(plan).__name__
    return {"FilterProjectPlan": "filter",
            "DeviceWindowAggPlan": "window",
            "DeviceJoinPlan": "join",
            "DevicePatternPlan": "pattern",
            "MultiQueryDevicePatternPlan": "multi_query"}.get(cls)


def plan_signature(plan) -> Optional[str]:
    """Signature of a BUILT plan (keyed off the normalized query AST the
    planner kept for the interpreter twin; fused multi-query plans key
    off their group shape signature — the same payload
    `fused_lane_pack_for` looks up at build time)."""
    fam = family_of(plan)
    if fam == "multi_query":
        gs = getattr(plan, "_group_sig", None)
        return signature_of(fam, gs) if gs is not None else None
    q = getattr(plan, "_q_ast", None)
    if fam is None or q is None:
        return None
    return signature_of(fam, q)


def app_signature(app) -> str:
    """App-level signature (batch-capacity entry): streams + queries."""
    payload = (tuple(sorted((sid, repr(sd)) for sid, sd in
                            app.stream_definitions.items())),
               tuple(repr(e) for e in app.execution_elements))
    return signature_of("app", payload)


def cache_key(sig: str, dev: Optional[str] = None,
              jaxv: Optional[str] = None) -> str:
    return f"{sig}|{dev or device_kind()}|jax{jaxv or jax_version()}"


# ---------------------------------------------------------------------------
# the on-disk tuning cache
# ---------------------------------------------------------------------------

def default_cache_path() -> str:
    env = os.environ.get("SIDDHI_TUNE_CACHE", "")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "siddhi_tpu",
                        "tuning.json")


def validate_cache_data(data) -> list:
    """Schema lint: list of problems (empty = valid).  The schema the
    smoke-test lint step enforces — a malformed persisted cache must be
    detected before it can brick a deploy."""
    probs: list = []
    if not isinstance(data, dict):
        return [f"top level must be an object, got {type(data).__name__}"]
    if data.get("version") != CACHE_VERSION:
        probs.append(f"version must be {CACHE_VERSION}, "
                     f"got {data.get('version')!r}")
    entries = data.get("entries")
    if not isinstance(entries, dict):
        return probs + ["'entries' must be an object"]
    for key, ent in entries.items():
        where = f"entry {key!r}"
        if not isinstance(key, str) or "|" not in key:
            probs.append(f"{where}: key must be 'sig|device|jaxver'")
        if not isinstance(ent, dict):
            probs.append(f"{where}: value must be an object")
            continue
        geo = ent.get("geometry")
        if not isinstance(geo, dict) or not geo:
            probs.append(f"{where}: 'geometry' must be a non-empty object")
        else:
            for k, v in geo.items():
                if k not in GEOMETRY_KEYS:
                    probs.append(f"{where}: unknown geometry knob {k!r}")
                elif k == "plan_family":
                    if v not in PATTERN_FAMILIES:
                        probs.append(f"{where}: plan_family must be one of "
                                     f"{PATTERN_FAMILIES}, got {v!r}")
                elif not isinstance(v, int) or isinstance(v, bool) \
                        or v < 0:
                    probs.append(f"{where}: knob {k!r} must be a "
                                 f"non-negative int, got {v!r}")
        fam = ent.get("family")
        if fam is not None and fam not in PLAN_FAMILIES:
            probs.append(f"{where}: unknown family {fam!r}")
        score = ent.get("score")
        if score is not None:
            if not isinstance(score, dict):
                probs.append(f"{where}: 'score' must be an object")
            else:
                for k, v in score.items():
                    if v is not None and not isinstance(v, (int, float)):
                        probs.append(f"{where}: score {k!r} not numeric")
    return probs


class TuningCache:
    """On-disk geometry winners, keyed `sig|device_kind|jaxVERSION`.

    Load is defensive by design: a corrupt/truncated file is quarantined
    (renamed `<path>.corrupt`, best-effort) and the cache starts empty —
    a bad persisted artifact degrades to a cold cache, never a failed
    deploy.  Writes are atomic (tmp + rename)."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self.hits = 0
        self.misses = 0
        self.corrupt = False
        self._lock = new_lock("TuningCache._lock")
        self._data: Optional[dict] = None

    # -- persistence -----------------------------------------------------

    def _load_locked(self) -> dict:
        if self._data is not None:
            return self._data
        data = {"version": CACHE_VERSION, "entries": {}}
        if os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    loaded = json.load(f)
                probs = validate_cache_data(loaded)
                if probs:
                    raise ValueError("; ".join(probs[:3]))
                data = loaded
            except (OSError, ValueError) as e:
                self.corrupt = True
                warnings.warn(
                    f"tuning cache {self.path!r} is corrupt and was "
                    f"ignored ({type(e).__name__}: {e}); starting cold",
                    RuntimeWarning)
                try:                         # keep for postmortem, get it
                    os.replace(self.path, self.path + ".corrupt")
                except OSError:              # out of the load path
                    pass
        self._data = data
        return data

    def _save_locked(self) -> None:
        try:
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self._data, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError as e:                 # read-only FS: stay in-memory
            warnings.warn(f"tuning cache {self.path!r} not persisted: {e}",
                          RuntimeWarning)

    # -- access ----------------------------------------------------------

    def entries(self) -> dict:
        with self._lock:
            return dict(self._load_locked()["entries"])

    def get(self, sig: str) -> Optional[dict]:
        """Entry for a plan signature under the CURRENT device/JAX key;
        counts the hit/miss gauges surfaced in statistics()."""
        with self._lock:
            ent = self._load_locked()["entries"].get(cache_key(sig))
            if ent is None:
                self.misses += 1
            else:
                self.hits += 1
            return ent

    def peek(self, sig: str) -> Optional[dict]:
        """get() without touching the hit/miss gauges."""
        with self._lock:
            return self._load_locked()["entries"].get(cache_key(sig))

    def put(self, sig: str, geometry: dict, family: Optional[str] = None,
            score: Optional[dict] = None) -> str:
        geometry = {k: (str(v) if k == "plan_family" else int(v))
                    for k, v in geometry.items()
                    if k in GEOMETRY_KEYS and v is not None}
        if not geometry:
            raise AutotuneError(f"empty geometry for {sig!r}")
        ent = {"geometry": geometry, "tuned_at_ms": int(time.time() * 1000)}
        if family:
            ent["family"] = family
        if score:
            ent["score"] = {k: v for k, v in score.items()
                            if isinstance(v, (int, float)) or v is None}
        with self._lock:
            data = self._load_locked()
            key = cache_key(sig)
            data["entries"][key] = ent
            self._save_locked()
        return key

    def metrics(self) -> dict:
        with self._lock:
            n = len(self._data["entries"]) if self._data is not None else None
        m = {"tuning_cache_hits": self.hits,
             "tuning_cache_misses": self.misses,
             "tuning_cache_path": self.path,
             "tuning_cache_corrupt": self.corrupt}
        if n is not None:
            m["tuning_cache_entries"] = n
        return m


_SHARED: dict = {}
_SHARED_LOCK = new_lock("autotune._SHARED_LOCK")


def shared_cache(path: Optional[str] = None) -> TuningCache:
    """Process-wide TuningCache per path (runtimes share the counters a
    /siddhi/artifact/tuning scrape reads)."""
    p = path or default_cache_path()
    with _SHARED_LOCK:
        c = _SHARED.get(p)
        if c is None:
            c = _SHARED[p] = TuningCache(p)
        return c


# ---------------------------------------------------------------------------
# runtime facade + planner consult helpers
# ---------------------------------------------------------------------------

class TunerRuntime:
    """Per-runtime view of the tuning cache, consulted by plan
    constructors at build time.  `@app:autotune('off')` disables the
    consult (annotations/defaults only); anything else — or no
    annotation — reads the shared on-disk cache."""

    def __init__(self, rt):
        self.rt = rt
        an = ast.find_annotation(rt.app.annotations, "app:autotune")
        self.mode = (an.element() or "cache").lower() if an is not None \
            else "cache"
        self.enabled = self.mode != "off"
        self.cache = shared_cache() if self.enabled else None
        self.hits = 0
        self.misses = 0
        self.resolved: dict = {}       # sig -> geometry dict (this build)

    def lookup(self, family: str, payload) -> Optional[Geometry]:
        if not self.enabled:
            return None
        sig = signature_of(family, payload)
        ent = self.cache.get(sig)
        if ent is None:
            self.misses += 1
            return None
        self.hits += 1
        g = Geometry.from_dict(ent.get("geometry", {}))
        self.resolved[sig] = g.to_dict()
        return g

    def batch_hint(self) -> Optional[int]:
        """App-level tuned micro-batch capacity (the `app` family)."""
        g = self.lookup("app", _app_payload(self.rt.app))
        return g.batch if g is not None else None

    def metrics(self) -> dict:
        m = {"cache_hits": self.hits, "cache_misses": self.misses,
             "mode": self.mode}
        if self.cache is not None:
            m.update(self.cache.metrics())
        if self.resolved:
            m["resolved"] = dict(self.resolved)
        return m


def _app_payload(app):
    return (tuple(sorted((sid, repr(sd)) for sid, sd in
                         app.stream_definitions.items())),
            tuple(repr(e) for e in app.execution_elements))


def pipeline_depth_for(rt, family: str, q=None) -> int:
    """Initial `@app:devicePipeline` depth for one plan: the annotation
    wins, then the tuning cache's persisted winner, then 0."""
    pl = ast.find_annotation(rt.app.annotations, "app:devicePipeline")
    if pl is not None:
        return int(pl.element())
    tn = getattr(rt, "tuner", None)
    if tn is not None and q is not None:
        g = tn.lookup(family, q)
        if g is not None and g.pipeline_depth is not None:
            return g.pipeline_depth
    return 0


def chunk_lanes_for(rt, q=None, default: int = 64) -> int:
    """Chunked-NFA lane count K: @app:deviceChunkLanes wins, then the
    tuning cache, then the built-in default."""
    an = ast.find_annotation(rt.app.annotations, "app:deviceChunkLanes")
    if an is not None:
        return int(an.element())
    tn = getattr(rt, "tuner", None)
    if tn is not None and q is not None:
        g = tn.lookup("pattern", q)
        if g is not None and g.chunk_lanes is not None:
            return g.chunk_lanes
    return default


def pattern_family_for(rt, q=None) -> Optional[str]:
    """Requested pattern execution family (seq|chunk|scan|dfa), or None
    for automatic selection: `@app:patternFamily` wins, then the tuning
    cache's persisted winner.  The plan only honors a family its
    eligibility analysis proved sound (DevicePatternPlan.families) —
    an ineligible request falls back with a warning, never silently
    changes semantics."""
    an = ast.find_annotation(rt.app.annotations, "app:patternFamily")
    if an is not None:
        fam = str(an.element()).lower()
        if fam in ("auto", ""):
            return None
        if fam not in PATTERN_FAMILIES:
            raise AutotuneError(
                f"@app:patternFamily({fam!r}): unknown family "
                f"(have {PATTERN_FAMILIES} or 'auto')")
        return fam
    tn = getattr(rt, "tuner", None)
    if tn is not None and q is not None:
        g = tn.lookup("pattern", q)
        if g is not None and g.plan_family is not None:
            return g.plan_family
    return None


def fused_lane_pack_for(rt, group_sig) -> int:
    """Fused multi-query lane packing: max query instances per fused
    kernel (0 = unbounded, the historical behavior).  @app:fusedLanes
    wins, then the tuning cache keyed on the group signature."""
    an = ast.find_annotation(rt.app.annotations, "app:fusedLanes")
    if an is not None:
        return max(0, int(an.element()))
    tn = getattr(rt, "tuner", None)
    if tn is not None:
        g = tn.lookup("multi_query", group_sig)
        if g is not None and g.lane_pack is not None:
            return g.lane_pack
    return 0


def agg_capacity_for(rt, payload=None, default: int = 1024) -> int:
    """Initial slot count of the device-resident aggregation bucket
    store, per duration (core/agg_device.py; the ring doubles on
    overflow so this is a starting geometry, not a bound).
    @app:aggCapacity wins, then the tuning cache, then the default —
    the same precedence every other geometry knob applies."""
    an = ast.find_annotation(rt.app.annotations, "app:aggCapacity")
    if an is not None:
        return max(8, int(an.element()))
    tn = getattr(rt, "tuner", None)
    if tn is not None and payload is not None:
        g = tn.lookup("app", payload)
        if g is not None and g.agg_capacity is not None:
            return max(8, g.agg_capacity)
    return default


# ---------------------------------------------------------------------------
# the online SLO controller
# ---------------------------------------------------------------------------

class SLOController:
    """AIMD micro-batch/flush-cadence controller behind
    `@app:latencySLO('25ms')`.

    The runtime feeds `observe()` one end-to-end latency sample per
    dispatched micro-batch (first-buffered-event -> batch processed) and
    calls `maybe_decide()` at flush boundaries.  Each decision window
    (>= `decide_every_s` elapsed AND >= `min_samples` observed) the
    controller reads the window's p99 from a telemetry Histogram and
    moves the batch target:

      p99 > target                      -> multiplicative decrease (x backoff)
      p99 < target * (1 - hysteresis)   -> additive increase (+ add_step)
      otherwise                         -> hold (the hysteresis band)

    Decisions are returned to the runtime, which applies them ONLY at a
    flush boundary (`_apply_batch_target`): batch boundaries move, but
    every event still flows through the same plans in the same order, so
    outputs are byte-identical to a fixed-geometry run (the PR-4 halving
    machinery proves batch splits are output-invariant; the differential
    suite asserts it per plan family).

    `@app:maxBatchLatency` constructs this same controller with
    `adaptive=False`: only the flush cadence (`flush_after_s`) is used,
    reproducing the original one-shot heuristic with no semantic change.

    A virtual clock (`maybe_decide(now_s)`) keeps the controller fully
    deterministic under test."""

    def __init__(self, target_s: Optional[float] = None, *,
                 initial_batch: int = 2048, min_batch: int = 32,
                 max_batch: int = 1 << 17, adaptive: bool = True,
                 flush_after_s: Optional[float] = None,
                 decide_every_s: float = 0.25, hysteresis: float = 0.3,
                 min_samples: int = 8, backoff: float = 0.5,
                 add_step: Optional[int] = None, log_capacity: int = 128):
        from .telemetry import Histogram
        if target_s is None and flush_after_s is None:
            raise AutotuneError("SLOController needs target_s or "
                                "flush_after_s")
        self.target_s = target_s
        self.adaptive = bool(adaptive) and target_s is not None
        # builders age out at half the target by default: the other half
        # is headroom for dispatch + device + materialization
        self.flush_after_s = flush_after_s if flush_after_s is not None \
            else target_s / 2.0
        self.min_batch = int(min_batch)
        self.max_batch = int(max_batch)
        self.batch_target = max(self.min_batch,
                                min(self.max_batch, int(initial_batch)))
        self.decide_every_s = float(decide_every_s)
        self.hysteresis = float(hysteresis)
        self.min_samples = int(min_samples)
        self.backoff = float(backoff)
        self.add_step = int(add_step) if add_step is not None \
            else max(32, self.min_batch)
        self._win = Histogram()
        # cumulative (never window-reset): the demo/report p99 over a
        # whole measured run, not just the last decision window
        self.total = Histogram()
        self._last_decide: Optional[float] = None
        self.last_p99_s: Optional[float] = None
        self.decisions: deque = deque(maxlen=log_capacity)
        self.counts = {"increase": 0, "decrease": 0, "hold": 0}
        # serving-plane admission throttle (net/admission.py token
        # buckets scale their refill rate by this): multiplicative
        # decrease with the batch target when p99 overshoots, additive
        # recovery back to 1.0 under the target — overload lowers
        # ADMISSION before engine latency collapses (ROADMAP item 3)
        self.admission_factor = 1.0
        self.admission_floor = 0.1
        # SLO-breach trace trigger (core/tracing.py): called with the
        # decision record whenever a window's p99 overshoots the target.
        # The runtime wires it to FrameTracer.trigger — nonblocking
        # enqueue, safe even though maybe_decide runs under the runtime
        # lock (the dump builds on the siddhi-trace-export thread)
        self.on_breach: Optional[Callable[[dict], None]] = None

    def observe(self, seconds: float) -> None:
        """One per-batch latency sample (first buffered event ->
        processed)."""
        self._win.record(seconds)
        self.total.record(seconds)

    def maybe_decide(self, now_s: Optional[float] = None) -> Optional[dict]:
        """Close the decision window if due; returns the decision record
        (also appended to the telemetry-visible log) or None."""
        if not self.adaptive:
            return None
        if now_s is None:
            now_s = time.perf_counter()
        if self._last_decide is None:
            self._last_decide = now_s
            return None
        if now_s - self._last_decide < self.decide_every_s \
                or self._win.count < self.min_samples:
            return None
        p99 = self._win.percentile(99)
        self.last_p99_s = p99
        old = self.batch_target
        if p99 > self.target_s:
            action = "decrease"
            new = max(self.min_batch, int(old * self.backoff))
            self.admission_factor = max(self.admission_floor,
                                        self.admission_factor * self.backoff)
        elif p99 < self.target_s * (1.0 - self.hysteresis):
            action = "increase"
            new = min(self.max_batch, old + self.add_step)
            self.admission_factor = min(1.0, self.admission_factor + 0.1)
        else:
            action = "hold"
            new = old
        self.batch_target = new
        self.counts[action] += 1
        dec = {"t_s": round(now_s, 4), "action": action,
               "p99_ms": round(p99 * 1e3, 3),
               "target_ms": round(self.target_s * 1e3, 3),
               "samples": self._win.count,
               "batch_from": old, "batch": new,
               "admission_factor": round(self.admission_factor, 4)}
        self.decisions.append(dec)
        if action == "decrease" and self.on_breach is not None:
            # a p99 breach IS the trigger the tracing plane retains a
            # dump for — the handler only enqueues, so firing under the
            # runtime lock (the _drain call site) is safe
            try:
                self.on_breach(dec)
            except Exception:
                pass
        self._win.reset()
        self._last_decide = now_s
        return dec

    def metrics(self) -> dict:
        m = {"adaptive": self.adaptive,
             "flush_after_ms": round(self.flush_after_s * 1e3, 3),
             "batch_target": self.batch_target,
             "admission_factor": round(self.admission_factor, 4),
             "decisions": dict(self.counts),
             "decision_log": list(self.decisions)[-16:]}
        if self.target_s is not None:
            m["target_ms"] = round(self.target_s * 1e3, 3)
        if self.last_p99_s is not None:
            m["window_p99_ms"] = round(self.last_p99_s * 1e3, 3)
        if self.total.count:
            m["observed_batches"] = self.total.count
            for p in (50, 99):
                v = self.total.percentile(p)
                if v is not None:
                    m[f"p{p}_ms"] = round(v * 1e3, 3)
        return m


# ---------------------------------------------------------------------------
# synthetic sample tapes
# ---------------------------------------------------------------------------

def synthetic_tape(schema, n_events: int, seed: int = 0, keys: int = 8,
                   dt_ms: int = 1, ts0: int = 1_700_000_000_000) -> tuple:
    """(cols, ts) columnar sample for one stream schema — the warmup
    tape the Autotuner sweeps when the caller records none.  Strings
    draw from `keys` symbols, numerics from quarter-rounded uniforms
    (exactly representable in f32, so device/host scoring tapes agree)."""
    rng = np.random.default_rng(seed)
    cols: dict = {}
    for a in schema.attributes:
        t = a.type
        if t == ast.AttrType.STRING:
            cols[a.name] = np.asarray(
                [f"K{i}" for i in rng.integers(0, keys, n_events)])
        elif t in (ast.AttrType.FLOAT, ast.AttrType.DOUBLE):
            cols[a.name] = np.round(
                rng.uniform(90.0, 130.0, n_events) * 4) / 4
        elif t == ast.AttrType.BOOL:
            cols[a.name] = rng.integers(0, 2, n_events).astype(bool)
        elif t == ast.AttrType.LONG:
            cols[a.name] = (ts0 + np.arange(n_events, dtype=np.int64)
                            * dt_ms)
        else:
            cols[a.name] = rng.integers(1, 1000, n_events).astype(np.int32)
    ts = ts0 + np.arange(n_events, dtype=np.int64) * dt_ms
    return cols, ts


def _slice_cols(cols: dict, ts, lo: int, hi: int) -> tuple:
    return {k: v[lo:hi] for k, v in cols.items()}, ts[lo:hi]


# ---------------------------------------------------------------------------
# the offline / warmup autotuner
# ---------------------------------------------------------------------------

class Autotuner:
    """Bounded-grid geometry sweep for one app.

    Each candidate builds a fresh runtime from the SAME app text, applies
    the geometry programmatically (batch capacity + `regeometry` on every
    plan — no annotation rewriting, so plan signatures stay stable),
    replays the sample tape, and scores with the telemetry latency
    histograms: events/sec over the timed window plus the per-stream
    dispatch-latency p99.  The winner maximizes eps (subject to `slo_ms`
    when given, with infeasible candidates falling back to lowest p99)
    and persists per-plan + app-level entries in the TuningCache.

    Every candidate must deliver the IDENTICAL output row sequence — the
    sweep double-checks the geometry-invariance contract (count + order-
    sensitive checksum) and raises AutotuneError on divergence rather
    than persist a geometry that changes results."""

    DEFAULT_BATCHES = (2048, 8192, 32768)
    DEFAULT_DEPTHS = (0, 2)

    def __init__(self, cache: Optional[TuningCache] = None):
        self.cache = cache or shared_cache()

    # -- grid ------------------------------------------------------------

    def default_grid(self, n_events: int, chunk_lanes=None,
                     plan_families=None) -> list:
        batches = [b for b in self.DEFAULT_BATCHES if b <= max(256,
                                                               n_events)]
        batches = batches or [min(2048, n_events)]
        lanes = list(chunk_lanes) if chunk_lanes else [None]
        fams = list(plan_families) if plan_families else [None]
        return [Geometry(batch=b, pipeline_depth=d, chunk_lanes=k,
                         plan_family=f)
                for b in batches for d in self.DEFAULT_DEPTHS
                for k in lanes for f in fams]

    # -- sweep -----------------------------------------------------------

    def tune(self, app_text: str, tapes: Optional[dict] = None,
             n_events: int = 1 << 14, grid: Optional[list] = None,
             slo_ms: Optional[float] = None, warm_events: int = 2048,
             persist: bool = True, force: bool = False,
             out_streams: Optional[tuple] = None,
             plan_families: Optional[tuple] = None,
             log: Optional[Callable] = None) -> dict:
        """Sweep `grid` (or the bounded default) over `app_text`.

        tapes: {stream_id: (cols, ts)} recorded sample; synthesized from
        the stream schemas when omitted.  Returns {"winner": geometry,
        "candidates": [scored...], "from_cache": bool, "keys": [...]}.
        With `force=False` a warm cache (an app-level entry for this app
        under the current device/JAX key) skips the sweep entirely."""
        from . import runtime as _rtmod
        app = _rtmod.parse(app_text)
        app_sig = signature_of("app", _app_payload(app))
        if not force:
            ent = self.cache.peek(app_sig)
            if ent is not None:
                return {"winner": dict(ent["geometry"]),
                        "from_cache": True, "candidates": [],
                        "keys": [cache_key(app_sig)],
                        "score": ent.get("score")}

        grid = list(grid) if grid is not None else \
            self.default_grid(n_events, plan_families=plan_families)
        if not grid:
            raise AutotuneError("empty candidate grid")
        results = []
        baseline_out = None
        for g in grid:
            if log is not None:
                log(f"autotune: measuring {g.label()}")
            res = self._measure(app_text, g, tapes, n_events, warm_events,
                                out_streams)
            if baseline_out is None:
                baseline_out = (res["matches"], res["out_crc"])
            elif (res["matches"], res["out_crc"]) != baseline_out:
                raise AutotuneError(
                    f"geometry {g.label()} changed outputs "
                    f"(matches {res['matches']} vs {baseline_out[0]}, "
                    f"crc {res['out_crc']:#x} vs {baseline_out[1]:#x}) — "
                    f"geometry must be output-invariant")
            results.append({"geometry": g.to_dict(), "eps": res["eps"],
                            "p99_ms": res["p99_ms"],
                            "matches": res["matches"]})
        winner_i = self._pick(results, slo_ms)
        winner = results[winner_i]
        keys = []
        if persist:
            keys = self._persist(app_text, grid[winner_i], winner)
        return {"winner": dict(winner["geometry"]), "from_cache": False,
                "candidates": results, "keys": keys,
                "score": {"eps": winner["eps"],
                          "p99_ms": winner["p99_ms"]}}

    @staticmethod
    def _pick(results: list, slo_ms: Optional[float]) -> int:
        idx = range(len(results))
        if slo_ms is not None:
            ok = [i for i in idx
                  if results[i]["p99_ms"] is not None
                  and results[i]["p99_ms"] <= slo_ms]
            if ok:
                return max(ok, key=lambda i: results[i]["eps"])
            # nothing meets the SLO: least-bad latency wins
            return min(idx, key=lambda i: (results[i]["p99_ms"]
                                           if results[i]["p99_ms"]
                                           is not None else math.inf))
        return max(idx, key=lambda i: results[i]["eps"])

    def _persist(self, app_text: str, g: Geometry, winner: dict) -> list:
        """Write the winner: one entry per device plan signature (with
        the family-relevant knobs) + the app-level batch entry."""
        from . import runtime as _rtmod
        score = {"eps": winner["eps"], "p99_ms": winner["p99_ms"]}
        mgr = _rtmod.SiddhiManager()
        keys = []
        try:
            rt = mgr.create_app_runtime(app_text)
            app_sig = signature_of("app", _app_payload(rt.app))
            keys.append(self.cache.put(app_sig, {"batch": g.batch},
                                       family="app", score=score))
            for plan in rt._plans:
                fam = family_of(plan)
                sig = plan_signature(plan)
                if fam is None or sig is None:
                    continue
                geo = {"batch": g.batch, "pipeline_depth": g.pipeline_depth}
                if fam == "pattern" and g.chunk_lanes is not None:
                    geo["chunk_lanes"] = g.chunk_lanes
                if fam == "pattern" and g.plan_family is not None:
                    geo["plan_family"] = g.plan_family
                if fam == "multi_query" and g.lane_pack is not None:
                    geo["lane_pack"] = g.lane_pack
                keys.append(self.cache.put(sig, geo, family=fam,
                                           score=score))
        finally:
            mgr.shutdown()
        return keys

    # -- one candidate ---------------------------------------------------

    def _measure(self, app_text: str, g: Geometry, tapes: Optional[dict],
                 n_events: int, warm_events: int,
                 out_streams: Optional[tuple]) -> dict:
        import zlib
        from . import runtime as _rtmod
        mgr = _rtmod.SiddhiManager()
        try:
            rt = mgr.create_app_runtime(app_text)
            if g.batch:
                rt.batch_capacity = int(g.batch)
            for plan in rt._plans:
                rg = getattr(plan, "regeometry", None)
                if rg is not None:
                    rg(batch_hint=g.batch, depth=g.pipeline_depth,
                       chunk_lanes=g.chunk_lanes,
                       plan_family=g.plan_family)
            rt.enable_stats(True)
            if out_streams is None:
                # every insert-into stream target — from the AST, not the
                # plans (partition groups and fused multi-query plans
                # route per inner query and report no output_target)
                tgts: set = set()
                for elem in rt.app.execution_elements:
                    qs = elem.queries if isinstance(elem, ast.Partition) \
                        else (elem,)
                    for q in qs:
                        t = getattr(q.output, "target", None)
                        if t is not None and t not in rt.tables \
                                and t not in rt.named_windows:
                            tgts.add(t)
                out_streams = tuple(sorted(tgts))
            crc = [0]
            count = [0]

            def on_batch(b, _crc=crc, _n=count):
                _n[0] += b.n
                for row in b.rows(rt.strings):
                    _crc[0] = zlib.crc32(repr(row).encode(), _crc[0])
            for s in out_streams:
                rt.add_batch_callback(s, on_batch)
            rt.start()
            feeds = self._feeds(rt, tapes, n_events)
            bsz = int(g.batch or rt.batch_capacity)
            total = min(len(ts) for _h, _c, ts in feeds)
            warm = min(max(warm_events, bsz), max(total - bsz, 0))
            if warm < bsz:
                # the tape is too short to warm one full batch of this
                # geometry: its compiles land inside the timed window
                # and the score under-reads steady state.  Size tapes
                # >= 2x the largest candidate batch (bench --autotune
                # does) to keep the sweep compile-free.
                warnings.warn(
                    f"autotune: candidate {g.label()} cannot warm a "
                    f"full batch ({warm} warm events < batch {bsz}); "
                    f"its timed window includes compile time",
                    RuntimeWarning)
            for h, cols, ts in feeds:           # warm: compiles + growth
                for lo in range(0, warm, bsz):
                    c, t = _slice_cols(cols, ts, lo, min(lo + bsz, warm))
                    h.send_batch(c, t)
            rt.flush()
            rt.stats.reset()
            n_timed = 0
            t0 = time.perf_counter()
            for lo in range(warm, total, bsz):
                hi = min(lo + bsz, total)
                for h, cols, ts in feeds:
                    c, t = _slice_cols(cols, ts, lo, hi)
                    h.send_batch(c, t)
                    n_timed += hi - lo
            rt.flush()
            dt = time.perf_counter() - t0
            # score with the PR-1 telemetry histograms: per-stream
            # dispatch-latency p99 over the timed (compile-free) window
            p99s = [trk.hist.percentile(99)
                    for trk in rt.stats.stream_in.values()
                    if trk.hist.count]
            p99_ms = round(max(p99s) * 1e3, 3) if p99s else None
            return {"eps": round(n_timed / dt) if dt > 0 else 0,
                    "p99_ms": p99_ms, "matches": count[0],
                    "out_crc": crc[0] & 0xFFFFFFFF}
        finally:
            mgr.shutdown()

    @staticmethod
    def _feeds(rt, tapes: Optional[dict], n_events: int) -> list:
        """[(handler, cols, ts)] for every feedable input stream."""
        feeds = []
        input_ids = sorted({sid for sid, subs in rt._subscribers.items()
                            for _p in subs
                            if sid in rt.schemas
                            and not sid.startswith("!")
                            and sid not in rt.named_windows
                            and sid not in rt.tables})
        if tapes:
            input_ids = [s for s in input_ids if s in tapes]
        for i, sid in enumerate(input_ids):
            if tapes and sid in tapes:
                cols, ts = tapes[sid]
            else:
                cols, ts = synthetic_tape(rt.schemas[sid], n_events,
                                          seed=i)
            feeds.append((rt.input_handler(sid), cols, ts))
        if not feeds:
            raise AutotuneError("app has no feedable input stream")
        return feeds


# ---------------------------------------------------------------------------
# CLI: cache lint / show (wired into scripts/smoke.sh)
# ---------------------------------------------------------------------------

def lint_path(path: Optional[str] = None) -> tuple:
    """(ok, problems) for a persisted cache file; a missing file is OK
    (cold cache)."""
    p = path or default_cache_path()
    if not os.path.exists(p):
        return True, [f"{p}: no cache file (cold cache) — OK"]
    try:
        with open(p) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        return False, [f"{p}: unreadable ({type(e).__name__}: {e})"]
    probs = validate_cache_data(data)
    if probs:
        return False, [f"{p}: {m}" for m in probs]
    n = len(data.get("entries", {}))
    return True, [f"{p}: valid (version {data.get('version')}, "
                  f"{n} entries)"]


def _main(argv) -> int:
    import sys
    path = None
    rest = [a for a in argv if not a.startswith("--")]
    if rest:
        path = rest[0]
    if "--show" in argv:
        p = path or default_cache_path()
        c = TuningCache(p)
        print(json.dumps({"path": p, "entries": c.entries()}, indent=1))
        return 0
    # default action: lint
    ok, msgs = lint_path(path)
    for m in msgs:
        print(("OK: " if ok else "LINT: ") + m,
              file=sys.stdout if ok else sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    import sys
    sys.exit(_main(sys.argv[1:]))
