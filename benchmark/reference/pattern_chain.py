"""Plain reference for the partitioned rising-chain pattern

    partition with (symbol of S)
      from every e1=S[price > T] -> e2=S[price > e1.price]
        -> e3=S[price > e2.price] within W
      select e1.price, e2.price, e3.price

written from the query's meaning, with nothing of the engine in it.  Within
one key, in arrival order: every event above T opens a chain; a chain takes
the FIRST later event of its key with a higher price as e2, then the first
later one above e2 as e3; it yields a row stamped with e3's timestamp if e3
arrives within W ms of e1.  So e2 and e3 are "next greater element" links,
looked for only W ms ahead.  `prices` may be handed in any float type: the
control (benchmark/control.py) runs this same code on bfloat16 prices.
"""
import numpy as np


def _next_greater_within(key, price, ts, within_ms):
    """For each position of the key-grouped arrays, the first later
    position of the same key with a greater price and a timestamp at most
    `within_ms` later, else -1."""
    n = len(key)
    link = np.full(n, -1, dtype=np.int64)
    pending = np.arange(n, dtype=np.int64)
    step = 0
    while len(pending):
        step += 1
        cand = pending + step
        ok = cand < n
        pending, cand = pending[ok], cand[ok]
        ok = (key[cand] == key[pending]) \
            & (ts[cand] - ts[pending] <= within_ms)
        pending, cand = pending[ok], cand[ok]
        hit = price[cand] > price[pending]
        link[pending[hit]] = cand[hit]
        pending = pending[~hit]
    return link


def matches(key, price, ts, params: dict) -> dict:
    """Every row the query owes for the stream (key, price, ts), as columns
    `ts` (of e3), `p1`, `p2`, `p3` (as float64) and `e3` (stream position
    of e3).  `params`: {"threshold": T, "within_ms": W}."""
    key = np.asarray(key)
    ts = np.asarray(ts, np.int64)
    price = np.asarray(price)
    order = np.argsort(key, kind="stable")
    k, p, t = key[order], price[order], ts[order]
    link = _next_greater_within(k, p, t, int(params["within_ms"]))
    e1 = np.flatnonzero(p > params["threshold"])
    e2 = link[e1]
    e1, e2 = e1[e2 >= 0], e2[e2 >= 0]
    e3 = link[e2]
    e1, e2, e3 = e1[e3 >= 0], e2[e3 >= 0], e3[e3 >= 0]
    ok = t[e3] - t[e1] <= int(params["within_ms"])
    e1, e2, e3 = e1[ok], e2[ok], e3[ok]
    return {"ts": t[e3], "p1": p[e1].astype(np.float64),
            "p2": p[e2].astype(np.float64), "p3": p[e3].astype(np.float64),
            "e3": order[e3]}


def stand_in(judge: "Judge", batches: list, cast) -> None:
    """Put this reference in the program's place: hand `judge` what the
    query owes for `batches`, computed on `cast(price)` against
    `cast(threshold)`."""
    q = {**judge.config["query"],
         "threshold": cast(judge.config["query"]["threshold"])}
    rows = matches(np.concatenate([b["sym_idx"] for b in batches]),
                   cast(np.concatenate([b["price"] for b in batches])),
                   np.concatenate([b["ts"] for b in batches]), q)
    order = np.argsort(rows["e3"], kind="stable")
    judge.add_rows(*(rows[c][order] for c in ("ts",) + Judge.columns))


class Judge:
    """Collects what the timed path delivers on the out stream and, once
    the window has closed, holds it to `matches` over the same events.  The
    rows of a seeded sample of keys are compared (all keys while the stream
    is within `compare_events_budget`), so that the reference stays shorter
    than the window; partitions are independent, so a key's rows depend on
    that key's events alone."""

    columns = ("p1", "p2", "p3")

    def __init__(self, config: dict, tape, seed: int):
        self.config, self.tape, self.seed = config, tape, int(seed)
        self._got = []
        self.rows = 0

    def on_batch(self, b) -> None:
        """Batch callback of the engine (in the timed window)."""
        if b.n:
            self.add_rows(b.timestamps, *(b.columns[c] for c in self.columns))

    def add_rows(self, ts, p1, p2, p3) -> None:
        self.rows += len(ts)
        self._got.append((np.array(ts, np.int64), np.array(p1, np.float64),
                          np.array(p2, np.float64), np.array(p3, np.float64)))

    def judge(self, n_batches: int) -> list:
        from benchmark import compare
        q = self.config["query"]
        tp = self.tape.params
        keys, n_events = int(tp["keys"]), n_batches * int(tp["batch"])
        budget = int(self.config["compare_events_budget"])
        n_keys = max(1, min(keys, keys * budget // max(n_events, 1)))
        chosen = np.zeros(keys, bool)
        chosen[np.random.default_rng([self.seed, 0xC0FFEE]).choice(
            keys, size=n_keys, replace=False)] = True
        sym, price, ts = [], [], []
        for i in range(n_batches):
            b = self.tape.batch(i)
            sym.append(b["sym_idx"])
            price.append(b["price"])
            ts.append(b["ts"])
        sym, price, ts = (np.concatenate(sym), np.concatenate(price),
                          np.concatenate(ts))
        pos = np.flatnonzero(chosen[sym])
        want = matches(sym[pos], price[pos], ts[pos], q)
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
        self.detail = {"keys_compared": n_keys, "rows_owed": len(want["ts"]),
                       "rows_delivered_all_keys": self.rows}
        return checks
