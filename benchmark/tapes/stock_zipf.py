"""The synthetic stock tape with skewed keys: `stock.py`'s stream (same
attributes, prices, volumes and timestamps, same contract: batch `i` is drawn
from `default_rng([seed, i])`, so a stream is the same whatever its length
and any batch can be made alone; a timestamp names its event), with the
symbol of each event drawn as YCSB's core workloads draw a record (Cooper et
al., SoCC 2010, `ScrambledZipfianGenerator`): popularity rank `r` (from 1)
with probability proportional to `1 / r**zipf_exponent`, and the ranks
scattered over the key space so that the popular keys are not neighbours.
YCSB scatters by hashing into a larger item space; here exactly `keys`
symbols stay, so the scatter is a permutation of them drawn from the run's
seed: `key_of_rank(keys, seed)[r - 1]` is the key of popularity rank `r`.

The traffic file's `skew` stanza ({"batch": i, "key": k, "events": n}) is
honoured as `stock.py` honours it: key `k` is raised to at least `n` events
in batch `i`, taken from the other keys.
"""
import functools

import numpy as np

from benchmark.tapes import stock
from benchmark.tapes.stock import (EVENT_TIME_COLUMNS, TS0, event_index,
                                   feed_columns, on_grid, rows, symbol_names)

__all__ = ["EVENT_TIME_COLUMNS", "TS0", "Tape", "event_index", "feed_columns",
           "key_of_rank", "make_batch", "rank_shares", "rows", "symbol_names"]


@functools.lru_cache(maxsize=8)
def rank_shares(keys: int, exponent: float) -> np.ndarray:
    """Share of the stream that popularity rank 1, 2, .. `keys` holds."""
    w = 1.0 / np.arange(1, keys + 1, dtype=np.float64) ** exponent
    return w / w.sum()


@functools.lru_cache(maxsize=8)
def key_of_rank(keys: int, seed: int) -> np.ndarray:
    """The scramble: a permutation of all keys, the same for every batch of
    a run; entry `r - 1` is the key of popularity rank `r`."""
    return np.random.default_rng([int(seed), 0x5C4A3B]).permutation(
        keys).astype(np.int32)


def make_batch(params: dict, seed: int, index: int) -> dict:
    """Batch `index` (0-based) of the stream that `params` and `seed`
    define.  `params`: keys, zipf_exponent, batch, dt_ms, price_lo,
    price_hi, price_step and an optional `skew`."""
    n, keys = int(params["batch"]), int(params["keys"])
    rng = np.random.default_rng([int(seed), int(index)])
    cdf = np.cumsum(rank_shares(keys, float(params["zipf_exponent"])))
    rank = np.minimum(np.searchsorted(cdf, rng.random(n), side="right"),
                      keys - 1)
    sym = key_of_rank(keys, int(seed))[rank]
    price = on_grid(rng.uniform(params["price_lo"], params["price_hi"],
                                size=n), params["price_step"])
    volume = rng.integers(1, 1000, size=n).astype(np.int32)
    skew = params.get("skew")
    if skew and int(skew["batch"]) == index:
        k, want = int(skew["key"]), int(skew["events"])
        others = np.flatnonzero(sym != k)
        more = max(0, min(want - (n - len(others)), len(others)))
        sym[rng.choice(others, size=more, replace=False)] = k
    start = index * n
    return {"sym_idx": sym, "price": price, "volume": volume,
            "ts": TS0 + np.arange(start, start + n, dtype=np.int64)
            * int(params["dt_ms"]),
            "n": n}


class Tape(stock.Tape):
    """`stock.Tape` over the skewed batches; `hot_keys(n)` names the `n`
    most popular keys of this run, most popular first."""

    make = staticmethod(make_batch)

    def hot_keys(self, n: int) -> np.ndarray:
        return key_of_rank(int(self.params["keys"]), self.seed)[:n].copy()
