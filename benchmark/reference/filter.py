"""Plain reference for `from S[T > price] select * insert into Out`: the
events below T, whole and in arrival order.  `price` may be handed in any float type: the control
(benchmark/control.py) runs this same code on bfloat16 prices."""
from types import SimpleNamespace

import numpy as np


def passing(price, params: dict) -> np.ndarray:
    """Positions of the events the query lets through.  `params`:
    {"threshold": T}; the comparison is made in the price column's own
    type."""
    price = np.asarray(price)
    return np.flatnonzero(price < price.dtype.type(params["threshold"]))


def stand_in(judge: "Judge", batches: list, cast) -> None:
    """Put this reference in the program's place: hand `judge` what the
    query owes for `batches`, computed on `cast(price)`."""
    from benchmark import manifest
    tape_mod = manifest.module("tapes", judge.config["tape"])
    names = tape_mod.symbol_names(int(judge.tape.params["keys"]))
    codes = np.arange(1, len(names) + 1, dtype=np.int32)
    judge.bind(SimpleNamespace(strings=SimpleNamespace(
        decode=lambda c: str(names[c - 1]))))
    for b in batches:
        price = cast(b["price"])
        keep = passing(price, judge.config["query"])
        cols = tape_mod.rows(b, keep, codes)
        cols["price"] = price[keep].astype(b["price"].dtype)
        judge.on_batch(SimpleNamespace(n=len(keep), timestamps=b["ts"][keep],
                                       columns=cols))


class Judge:
    """Counts the rows delivered for EVERY batch of the window, keeps a
    seeded sample of the delivered batches whole, and once the window has
    closed holds the counts and the sampled rows to `passing` over the same
    events.  (A window carries about 10^9 events: every row cannot be kept,
    every batch can be counted.)"""

    def __init__(self, config: dict, tape, seed: int):
        self.config, self.tape, self.seed = config, tape, int(seed)
        tp = tape.params
        self._span_ms = int(tp["batch"]) * int(tp["dt_ms"])
        self._every = max(1, int(config["compare_one_batch_in"]))
        self._phase = int(np.random.default_rng(
            [self.seed, 0xC0FFEE]).integers(0, self._every))
        self._counts = {}
        self._kept = {}
        self._strings = None
        self.rows = 0

    def bind(self, rt) -> None:
        """Delivered symbols are the engine's dictionary codes; they are
        turned back into strings through its public decode."""
        self._strings = rt.strings

    def on_batch(self, b) -> None:
        """Batch callback of the engine (in the timed window)."""
        if not b.n:
            return
        from benchmark.tapes.stock import TS0
        self.rows += b.n
        first = (int(b.timestamps[0]) - TS0) // self._span_ms
        last = (int(b.timestamps[-1]) - TS0) // self._span_ms
        if first != last:           # an output batch astride two inputs
            self._counts[-1] = self._counts.get(-1, 0) + b.n
            return
        self._counts[first] = self._counts.get(first, 0) + b.n
        if first % self._every == self._phase:
            part = {"ts": np.array(b.timestamps, np.int64),
                    **{c: np.array(v) for c, v in b.columns.items()}}
            self._kept.setdefault(first, []).append(part)

    def judge(self, n_batches: int) -> list:
        from benchmark import compare, manifest
        tape_mod = manifest.module("tapes", self.config["tape"])
        q = self.config["query"]
        names = tape_mod.symbol_names(int(self.tape.params["keys"]))
        owed, sampled = {}, []
        ring = self.tape.ring or n_batches
        per_entry = {}
        for i in range(n_batches):
            e = i % ring
            if e not in per_entry:
                per_entry[e] = len(passing(self.tape.batch(e)["price"], q))
            if per_entry[e]:
                owed[i] = per_entry[e]
            if i % self._every == self._phase:
                b = self.tape.batch(i)
                keep = passing(b["price"], q)
                want = {"ts": b["ts"][keep], **tape_mod.rows(b, keep, names)}
                parts = self._kept.get(i, [])
                got = {c: np.concatenate([p[c] for p in parts])
                       for c in want} if parts else \
                    {c: np.zeros(0) for c in want}
                if parts:
                    codes, inverse = np.unique(got["symbol"],
                                               return_inverse=True)
                    got["symbol"] = np.array([self._strings.decode(int(c))
                                              for c in codes])[inverse]
                sampled.append((got, want))
        self.detail = {"batches_counted": n_batches,
                       "batches_compared_by_value": len(sampled),
                       "rows_delivered": self.rows}
        return compare.filter_rows(self._counts, owed, sampled)
