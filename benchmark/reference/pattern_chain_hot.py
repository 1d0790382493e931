"""The plain reference of `pattern_chain` (its `matches`, and its `stand_in`
for the control) with a judge for a stream whose keys are skewed.

`pattern_chain.Judge` samples keys uniformly and sizes the sample as if
every key held the same share of the stream.  Under a zipfian tape that
sample may miss every popular key, which are the keys whose lanes the engine
treats differently, or hold the most popular one and compare far more events
than `compare_events_budget`.  This judge ALWAYS compares the `HOT_KEYS` most
popular keys of the tape (`tape.hot_keys`), even where they alone pass the
budget, and fills what the budget leaves with a seeded sample of the other
keys, reckoned in EVENTS from the stream's own counts.  Same checks, every
limit 0.  Nothing of the engine is imported; where the driver binds the
runtime, its public EXPLAIN of the query's lane grid is copied into the
run's printed detail and decides nothing.
"""
import numpy as np

from benchmark.reference import pattern_chain
from benchmark.reference.pattern_chain import matches, stand_in

__all__ = ["HOT_KEYS", "Judge", "matches", "stand_in"]

HOT_KEYS = 32


class Judge(pattern_chain.Judge):

    _rt = None

    def bind(self, rt) -> None:
        """The driver hands over the runtime before the first event.  An
        engine whose EXPLAIN has no `lane_cut` for the query pads every
        lane of the grid to the busiest key's 35,000 events of a batch and
        spends minutes on each (PERF.md section 6, PR 35): this deployment
        is not one it serves, and the run ends here, at once and with
        nothing run, rather than far past any limit on a run's time."""
        self._rt = rt
        if not any("lane_cut" in entry
                   for entry in rt.explain()["queries"].values()):
            raise SystemExit(
                "pattern_chain_hot: the engine's EXPLAIN shows no lane_cut "
                "for the partitioned query: it does not cut hot lanes, and "
                "this deployment's hottest key would set every lane's "
                "length. Nothing was run.")

    def chosen_keys(self, sym: np.ndarray) -> np.ndarray:
        """The keys compared, as a mask over all keys: the hot ones, then
        other keys in a seeded order while their events fit the budget."""
        keys = int(self.tape.params["keys"])
        events = np.bincount(sym, minlength=keys)
        hot = self.tape.hot_keys(min(HOT_KEYS, keys))
        chosen = np.zeros(keys, bool)
        chosen[hot] = True
        left = int(self.config["compare_events_budget"]) \
            - int(events[hot].sum())
        others = np.random.default_rng([self.seed, 0xC0FFEE]).permutation(
            np.flatnonzero(~chosen))
        chosen[others[np.cumsum(events[others]) <= left]] = True
        self.detail = {"keys_compared": int(chosen.sum()),
                       "hot_keys_compared": len(hot),
                       "events_compared": int(events[chosen].sum()),
                       "events_of_hot_keys": int(events[hot].sum())}
        return chosen

    def judge(self, n_batches: int) -> list:
        from benchmark import compare
        tp = self.tape.params
        keys = int(tp["keys"])
        made = [self.tape.batch(i) for i in range(n_batches)]
        sym, price, ts = (np.concatenate([b[c] for b in made])
                          for c in ("sym_idx", "price", "ts"))
        chosen = self.chosen_keys(sym)
        pos = np.flatnonzero(chosen[sym])
        want = matches(sym[pos], price[pos], ts[pos], self.config["query"])
        want["e3"] = pos[want["e3"]]
        if self._got:
            cols = [np.concatenate(c) for c in zip(*self._got)]
        else:
            cols = [np.zeros(0, np.int64)] + [np.zeros(0)] * 3
        e3 = self.tape.event_index(cols[0])
        # a delivered timestamp outside the stream is a false row of no key
        inside = (e3 >= 0) & (e3 < len(sym))
        key = np.where(inside, sym[np.clip(e3, 0, len(sym) - 1)], -1)
        mine = (key < 0) | chosen[np.clip(key, 0, keys - 1)]
        got = {"ts": cols[0][mine], "p1": cols[1][mine], "p2": cols[2][mine],
               "p3": cols[3][mine], "e3": e3[mine]}
        checks = compare.pattern_rows(got, want, key[mine],
                                      float(tp["price_lo"]),
                                      float(tp["price_step"]))
        self.detail.update(rows_owed=len(want["ts"]),
                           rows_delivered_all_keys=self.rows)
        if self._rt is not None:
            for entry in self._rt.explain()["queries"].values():
                for k in ("first_hit", "lane_cut"):
                    if k in entry:
                        self.detail[k] = entry[k]
        return checks
