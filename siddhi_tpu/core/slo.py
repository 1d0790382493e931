"""The online SLO batching controller.

`@app:latencySLO('25ms')` adapts the runtime's micro-batch/flush cadence
AIMD-style from the observed p99 of a rolling window (additive increase
of the batch target while p99 sits below the hysteresis band,
multiplicative decrease when the target is violated), with a
telemetry-visible decision log.  `@app:maxBatchLatency` rides the same
controller in cadence-only (non-adaptive) mode, preserving its one-shot
semantics exactly.  The runtime applies decisions at flush boundaries
only (`runtime._apply_batch_target`) and splits oversized batches with
`faults.split_batch`, so outputs stay byte-identical to a fixed batch
size; the serving plane's token buckets scale their refill rate by
`admission_factor` (net/admission.py `set_rate_factor`).
docs/SLO.md has the walkthrough.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Optional


class AutotuneError(Exception):
    pass


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
