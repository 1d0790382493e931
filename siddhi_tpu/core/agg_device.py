"""Device-resident incremental aggregation: the queryable state plane's
kernel layer (docs/AGGREGATION.md "Device lowering").

The host path (core/aggregation.py) reduces every micro-batch with numpy
scatter-reductions and merges the few unique (bucket, group) segments
into per-duration Python dict stores.  This module keeps the ROLLING
BUCKET STATE ITSELF on device: one f64 base matrix per duration
(`[capacity + 1, n_bases]`, the +1 row is scatter scratch for padding),
updated in place by a jitted segment-reduce + scatter-merge step, and
pulled to host ONLY on query / snapshot / restore — ROADMAP item 2's
device-resident steady state applied to aggregation state.

Per ingest batch and duration the division of labor is:

  host   (bucket, group) segment ids via one np.unique over int64 views
         (exact — float group keys compare by bit pattern), slot
         assignment against the per-duration ring (dict lookups on the
         FEW unique segments, never per event);
  device segment_sum / segment_min / segment_max of every base column
         over the batch's inverse segment ids, then one gather +
         elementwise combine + scatter that merges the partials into
         the resident base matrix at the host-assigned slots.

Base arithmetic is float64 end-to-end and the per-segment accumulation
order equals the host path's (both fold events in batch order, and both
merge batch partials into standing state as `old op new`), so the two
paths produce BYTE-IDENTICAL stores — `bench.py --matrix` and the
forced-path differential tests assert exactly that.

Slot lifecycle: the ring starts at `rt.geometry["agg_capacity"]` slots
(@app:aggCapacity, else 1024) and doubles when full; @purge
retention frees slots host-side only (the stale device row is simply
overwritten on reuse), so eviction costs zero device traffic.
"""
from __future__ import annotations

import numpy as np

from ..query.ast import Duration

__all__ = ["DeviceAggregationPlan"]


def _pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


class _DurationRing:
    """Host-side slot directory of one duration's device base matrix."""

    __slots__ = ("key_to_slot", "slot_keys", "free", "bases", "dirty")

    def __init__(self, capacity: int, n_bases: int, jnp):
        self.key_to_slot: dict = {}
        self.slot_keys: list = [None] * capacity
        self.free: list = list(range(capacity - 1, -1, -1))
        # +1 scratch row: padded segments scatter there, never read back
        self.bases = jnp.zeros((capacity + 1, n_bases), dtype=jnp.float64)
        self.dirty = False

    @property
    def capacity(self) -> int:
        return len(self.slot_keys)

    def live(self) -> int:
        return len(self.key_to_slot)


class DeviceAggregationPlan:
    """Device-resident per-duration bucket stores for one
    AggregationRuntime.  The owning runtime keeps parsing, filtering,
    retention policy, and the query/snapshot surfaces; this plan owns
    the base matrices and the segment-reduce merge step."""

    def __init__(self, agg, capacity: int):
        import jax
        import jax.numpy as jnp
        self._jax = jax
        self._jnp = jnp
        from .aggregation import _BASES
        self.agg = agg
        self.base_ops = [b for s in agg.sites for b in _BASES[s.name]]
        self.val_of_base = [i for i, s in enumerate(agg.sites)
                            for _b in _BASES[s.name]]
        self.n_bases = agg.n_bases
        self.rings = {d: _DurationRing(capacity, self.n_bases, jnp)
                      for d in agg.durations}
        # one jitted step reused across (capacity, npad, mpad) shapes —
        # jit's shape cache handles retraces; pow2 padding bounds them.
        # Donation hands the old base matrix's buffer to the output
        # (in-place on TPU); CPU ignores donation, so gate the flag to
        # keep tier-1 logs warning-free.
        kwargs = ({} if jax.default_backend() == "cpu"
                  else {"donate_argnums": (0,)})
        self._step = jax.jit(self._make_step(), **kwargs)

    # -- kernel ---------------------------------------------------------------

    def _make_step(self):
        jnp = self._jnp
        base_ops = list(self.base_ops)
        val_of_base = list(self.val_of_base)

        def step(bases, inv, vals, slots, fresh):
            """bases [cap+1, nb] f64; inv [npad] i32 (padding -> dummy
            segment); vals [n_sites, npad] f64; slots [mpad] i32
            (padding -> scratch row cap); fresh [mpad] bool."""
            from jax import ops as jops
            mpad = slots.shape[0]
            cols = []
            for bi, op in enumerate(base_ops):
                if op == "count":
                    v = jnp.ones(inv.shape[0], dtype=jnp.float64)
                else:
                    v = vals[val_of_base[bi]]
                if op in ("sum", "count"):
                    cols.append(jops.segment_sum(v, inv,
                                                 num_segments=mpad))
                elif op == "min":
                    cols.append(jops.segment_min(v, inv,
                                                 num_segments=mpad))
                else:
                    cols.append(jops.segment_max(v, inv,
                                                 num_segments=mpad))
            partial = jnp.stack(cols, axis=1)            # [mpad, nb]
            cur = bases[slots]                           # gather
            merged_cols = []
            for bi, op in enumerate(base_ops):
                if op in ("sum", "count"):
                    merged_cols.append(cur[:, bi] + partial[:, bi])
                elif op == "min":
                    merged_cols.append(jnp.minimum(cur[:, bi],
                                                   partial[:, bi]))
                else:
                    merged_cols.append(jnp.maximum(cur[:, bi],
                                                   partial[:, bi]))
            merged = jnp.stack(merged_cols, axis=1)
            new = jnp.where(fresh[:, None], partial, merged)
            return bases.at[slots].set(new)
        return step

    # -- ingest ---------------------------------------------------------------

    def ingest(self, dur: Duration, buckets: np.ndarray, gkeys: list,
               inv: np.ndarray, vals: list) -> None:
        """Merge one batch's segments into `dur`'s resident store.
        `buckets`/`gkeys` describe the m unique segments (host-decoded
        keys, exactly the host path's dict keys); `inv` maps each of the
        n events onto its segment; `vals` are the per-site f64 value
        columns."""
        jnp = self._jnp
        ring = self.rings[dur]
        m = len(gkeys)
        n = len(inv)
        # slot assignment (the ONLY per-segment host work)
        slot_of = np.empty(m, dtype=np.int32)
        fresh_of = np.zeros(m, dtype=bool)
        for j in range(m):
            key = (int(buckets[j]), gkeys[j])
            slot = ring.key_to_slot.get(key)
            if slot is None:
                if not ring.free:
                    self._grow(ring)
                slot = ring.free.pop()
                ring.key_to_slot[key] = slot
                ring.slot_keys[slot] = key
                fresh_of[j] = True
            slot_of[j] = slot

        npad = _pow2(n)
        mpad = _pow2(m + 1)          # >= 1 dummy segment for event padding
        inv_p = np.full(npad, mpad - 1, dtype=np.int32)
        inv_p[:n] = inv
        vals_p = np.zeros((max(len(vals), 1), npad), dtype=np.float64)
        for i, v in enumerate(vals):
            vals_p[i, :n] = v
        slots_p = np.full(mpad, ring.capacity, dtype=np.int32)  # scratch
        slots_p[:m] = slot_of
        fresh_p = np.ones(mpad, dtype=bool)   # scratch rows: plain set
        fresh_p[:m] = fresh_of
        ring.bases = self._step(ring.bases, jnp.asarray(inv_p),
                                jnp.asarray(vals_p), jnp.asarray(slots_p),
                                jnp.asarray(fresh_p))
        ring.dirty = True

    def _grow(self, ring: _DurationRing) -> None:
        jnp = self._jnp
        old_cap = ring.capacity
        new_cap = old_cap * 2
        host = np.asarray(ring.bases)
        grown = np.zeros((new_cap + 1, self.n_bases), dtype=np.float64)
        grown[:old_cap] = host[:old_cap]
        ring.bases = jnp.asarray(grown)
        ring.slot_keys.extend([None] * (new_cap - old_cap))
        ring.free.extend(range(new_cap - 1, old_cap - 1, -1))

    # -- eviction (host-side slot frees; zero device traffic) -----------------

    def evict_before(self, dur: Duration, cutoff_ms: int) -> int:
        ring = self.rings[dur]
        doomed = [k for k in ring.key_to_slot if k[0] < cutoff_ms]
        for key in doomed:
            slot = ring.key_to_slot.pop(key)
            ring.slot_keys[slot] = None
            ring.free.append(slot)
        if doomed:
            ring.dirty = True    # the materialized dict view is stale now
        return len(doomed)

    # -- host materialization (query / snapshot / restore) --------------------

    def sync_into(self, store: dict) -> None:
        """Rebuild the owning runtime's per-duration dict stores from
        the device matrices — one D2H pull per DIRTY duration, so a
        steady ingest stream pays nothing until somebody asks."""
        for dur, ring in self.rings.items():
            if not ring.dirty:
                continue
            host = np.asarray(ring.bases)
            store[dur] = {key: [float(x) for x in host[slot]]
                          for key, slot in ring.key_to_slot.items()}
            ring.dirty = False

    def load_from(self, store: dict) -> None:
        """Reset the rings from restored host dict stores (snapshot /
        WAL recovery) — the inverse of sync_into, one H2D per
        duration."""
        jnp = self._jnp
        for dur, ring in self.rings.items():
            entries = store.get(dur, {})
            cap = ring.capacity
            while cap < len(entries):
                cap *= 2
            ring.key_to_slot = {}
            ring.slot_keys = [None] * cap
            ring.free = list(range(cap - 1, -1, -1))
            host = np.zeros((cap + 1, self.n_bases), dtype=np.float64)
            for key, bases in sorted(entries.items()):
                slot = ring.free.pop()
                ring.key_to_slot[key] = slot
                ring.slot_keys[slot] = key
                host[slot] = bases
            ring.bases = jnp.asarray(host)
            # restored state lives on device now; the dict store the
            # caller holds is already current
            ring.dirty = False

    # -- telemetry ------------------------------------------------------------

    def live_buckets(self, dur: Duration) -> int:
        return self.rings[dur].live()

    def capacity(self, dur: Duration) -> int:
        return self.rings[dur].capacity
