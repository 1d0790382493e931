"""The plain reference of `pattern_chain` (its `matches`: arrival order
inside a key, `within` on timestamps) with a judge that reads a delivered
row's key FROM THE ROW, for a deployment that delivers the partition key

    select e1.symbol as symbol, e1.price as p1, e2.price as p2, e3.price as p3

over a tape whose timestamps tie (tapes/stock_ties.py: hundreds of events a
millisecond).  `pattern_chain.Judge` finds a row's key from its timestamp
("a timestamp names its event"); here a timestamp names a millisecond of the
stream, and the row names its key: its `symbol` column, a dictionary code of
the engine's string table, is mapped back to the tape's key index through
the runtime the driver binds (`bind(rt)`, `rt.strings`: the public string
table, nothing else of the engine decides anything).  A row delivered under
another key's symbol is then a row that key does not owe AND a row its own
key misses: the guarantee "the delivered symbol is the key of the match's
three events" is held by the same two counts as the match set.

`(key, timestamp)` still does not name the e3 EVENT where a key has two
events in one millisecond (about one event in a thousand at one event a key
a second).  Both sides therefore map e3 to the FIRST event of that key at
that timestamp (`canonical_e3`) before `compare.pattern_rows`: rows are
compared as a multiset of (that event, p1, p2, p3), and per-key order is
held between rows of different milliseconds; two rows of one key completed
inside one millisecond are not ordered against each other.

All keys are compared while the stream is within `compare_events_budget`,
then a seeded sample of keys (partitions are independent).  Every limit 0.
"""
from typing import NamedTuple

import numpy as np

from benchmark import manifest
from benchmark.reference import pattern_chain
from benchmark.reference.pattern_chain import matches

__all__ = ["Judge", "canonical_e3", "matches", "stand_in"]

KEY_COLUMN = "symbol"


class ByKeyAndMs(NamedTuple):
    """A stream of at least one event, indexed by (key, timestamp):
    `first[j]` is the stream position of the first event with event `j`'s
    key and timestamp; `order` sorts the stream by (key, arrival) and
    `words` is the composite `key * span + (ts - ts0)` in that order,
    ascending because timestamps never fall (the tape's contract)."""
    first: np.ndarray
    order: np.ndarray
    words: np.ndarray
    ts0: int
    span: int

    def lookup(self, key, ts) -> np.ndarray:
        """Stream position of the first event of `key` stamped `ts`, -1
        where the stream holds none (a false row of no event)."""
        inside = (key >= 0) & (ts >= self.ts0) & (ts < self.ts0 + self.span)
        want = np.where(inside, key * self.span + (ts - self.ts0), -1)
        at = np.minimum(np.searchsorted(self.words, want, side="left"),
                        len(self.words) - 1)
        return np.where(inside & (self.words[at] == want), self.order[at], -1)


def canonical_e3(sym: np.ndarray, ts: np.ndarray) -> ByKeyAndMs:
    """The stream (key index and timestamp of each event, in arrival
    order) indexed so that (key, millisecond) names one event on both
    sides of the comparison: the first of that key in that millisecond."""
    order = np.argsort(sym, kind="stable")
    ts0, span = int(ts[0]), int(ts[-1] - ts[0]) + 1
    words = (sym.astype(np.int64) * span + (ts - ts0))[order]
    new = np.r_[True, words[1:] != words[:-1]]
    first = np.empty(len(sym), np.int64)
    first[order] = order[np.flatnonzero(new)[np.cumsum(new) - 1]]
    return ByKeyAndMs(first, order, words, ts0, span)


def stand_in(judge: "Judge", batches: list, cast) -> None:
    """Put this reference in the program's place: hand `judge` what the
    query owes for `batches`, computed on `cast(price)` against
    `cast(threshold)`, the key of each row as the key INDEX (no runtime is
    bound, so no dictionary stands between)."""
    q = {**judge.config["query"],
         "threshold": cast(judge.config["query"]["threshold"])}
    sym = np.concatenate([b["sym_idx"] for b in batches])
    rows = matches(sym, cast(np.concatenate([b["price"] for b in batches])),
                   np.concatenate([b["ts"] for b in batches]), q)
    order = np.argsort(rows["e3"], kind="stable")
    judge.add_rows(rows["ts"][order], sym[rows["e3"]][order],
                   *(rows[c][order] for c in ("p1", "p2", "p3")))


class Judge(pattern_chain.Judge):
    """`pattern_chain.Judge` (it collects what the timed path delivers on
    the out stream, through the same batch callback) with the key column
    collected too, and a comparison that reads the key from it."""

    columns = (KEY_COLUMN, "p1", "p2", "p3")
    _rt = None

    def bind(self, rt) -> None:
        """The driver hands over the runtime before the first event.  An
        engine whose out stream carries no `symbol` delivers rows that do
        not say which key they are of: nothing this judge could compare.
        The run ends here, at once and with nothing run."""
        schema = rt.schemas.get(self.config["out_stream"])
        names = [a.name for a in schema.attributes] if schema else []
        if KEY_COLUMN not in names:
            raise SystemExit(
                f"pattern_chain_keyed: the engine's {self.config['out_stream']}"
                f" stream has the attributes {names}, no {KEY_COLUMN!r}: a "
                "match does not say which key it is of. Nothing was run.")
        self._rt = rt

    def add_rows(self, ts, symbol, p1, p2, p3) -> None:
        self.rows += len(ts)
        self._got.append((np.array(ts, np.int64), np.array(symbol, np.int64),
                          np.array(p1, np.float64), np.array(p2, np.float64),
                          np.array(p3, np.float64)))

    def key_of_code(self, codes: np.ndarray, keys: int) -> np.ndarray:
        """The tape's key index of each delivered `symbol` code, -1 for a
        code that is no key's.  Bound to a runtime, a code is the string
        table's for the key's name (an `encode` of a string the driver has
        already encoded hands back its code and adds nothing); unbound
        (`stand_in`), a code IS the key index."""
        if self._rt is None:
            return np.where((codes >= 0) & (codes < keys), codes, -1)
        names = manifest.module("tapes", self.config["tape"]).symbol_names(keys)
        strings = self._rt.strings
        code_of_key = np.fromiter((strings.encode(str(s)) for s in names),
                                  np.int64, count=keys)
        key_of = np.full(len(strings) + 1, -1, np.int64)
        key_of[code_of_key] = np.arange(keys)
        inside = (codes >= 0) & (codes < len(key_of))
        return np.where(inside, key_of[np.clip(codes, 0, len(key_of) - 1)], -1)

    def chosen_keys(self, keys: int, n_events: int) -> np.ndarray:
        """The keys compared, as a mask: every key while the stream is
        within the budget, else a seeded sample in proportion."""
        budget = int(self.config["compare_events_budget"])
        n_keys = max(1, min(keys, keys * budget // max(n_events, 1)))
        chosen = np.zeros(keys, bool)
        chosen[np.random.default_rng([self.seed, 0xC0FFEE]).choice(
            keys, size=n_keys, replace=False)] = True
        return chosen

    def judge(self, n_batches: int) -> list:
        from benchmark import compare
        tp = self.tape.params
        keys = int(tp["keys"])
        made = [self.tape.batch(i) for i in range(n_batches)]
        sym, price, ts = (np.concatenate([b[c] for b in made])
                          for c in ("sym_idx", "price", "ts"))
        chosen = self.chosen_keys(keys, len(sym))
        index = canonical_e3(sym, ts)
        pos = np.flatnonzero(chosen[sym])
        want = matches(sym[pos], price[pos], ts[pos], self.config["query"])
        want["e3"] = index.first[pos[want["e3"]]]
        if self._got:
            cols = [np.concatenate(c) for c in zip(*self._got)]
        else:
            cols = [np.zeros(0, np.int64)] * 2 + [np.zeros(0)] * 3
        key = self.key_of_code(cols[1], keys)
        # a symbol that is no key's is a false row of no key: compared
        mine = (key < 0) | chosen[np.clip(key, 0, keys - 1)]
        key = key[mine]
        got = {"ts": cols[0][mine], "p1": cols[2][mine], "p2": cols[3][mine],
               "p3": cols[4][mine]}
        # a (key, timestamp) the stream does not hold is a false row too
        got["e3"] = index.lookup(key, got["ts"])
        checks = compare.pattern_rows(got, want, key, float(tp["price_lo"]),
                                      float(tp["price_step"]))
        self.detail = {
            "keys_compared": int(chosen.sum()),
            "events_compared": len(pos), "rows_owed": len(want["ts"]),
            "rows_delivered_all_keys": self.rows,
            "events_sharing_key_and_ms": int(np.count_nonzero(
                index.first != np.arange(len(sym))))}
        if self._rt is not None:
            # the engine's own account of its lane grid: printed, decides
            # nothing (absent from an engine that keeps none)
            for entry in self._rt.explain()["queries"].values():
                for k in ("lane_fill", "lane_cut", "lane_pack_order",
                          "first_hit"):
                    if k in entry:
                        self.detail[k] = entry[k]
        return checks
