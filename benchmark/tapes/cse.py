"""The tape of upstream's SimpleFilterSingleQueryPerformance, made from a
seed: `cseEventStream(symbol string, price float, volume long, timestamp
long)`, the two symbols WSO2 and IBM in strict alternation as the sample
sends them.  The sample's two constant events (55.6f and 75.6f, volume 100)
would make every delivered row one of two values; here a WSO2 price is a
float32 drawn uniformly from [lo, split) and an IBM price from [split, hi),
`split` being the query's 70: as upstream, every WSO2 event passes and no
IBM event does, so every batch of every seed delivers exactly half its rows
(a count that varied around 2^17 made the rate follow the seed: PERF.md
section 6), rows differ by value, and prices fall close to 70 on both sides;
volumes are seeded longs; `timestamp` is the event's time (upstream: the
wall clock at send).  Same interface as tapes/stock.py.
"""
import numpy as np

from benchmark.tapes import stock
from benchmark.tapes.stock import TS0

EVENT_TIME_COLUMNS = ("timestamp",)     # advance with a ring's laps
SYMBOLS = ("WSO2", "IBM")


def make_batch(params: dict, seed: int, index: int) -> dict:
    n = int(params["batch"])
    rng = np.random.default_rng([int(seed), int(index)])
    pos = np.arange(index * n, (index + 1) * n, dtype=np.int64)
    sym = (pos % len(SYMBOLS)).astype(np.int32)
    lo, split, hi = (np.float32(params[k]) for k in
                     ("price_lo", "price_split", "price_hi"))
    u = rng.uniform(size=n)
    price = np.where(sym == 0, lo + u * (split - lo),
                     split + u * (hi - split)).astype(np.float32)
    # a draw just under `split` may round up to it in float32
    price[sym == 0] = np.minimum(price[sym == 0], np.nextafter(split, lo))
    return {"sym_idx": sym, "price": price,
            "volume": rng.integers(1, 1000, size=n, dtype=np.int64),
            "ts": TS0 + pos * int(params["dt_ms"]), "n": n}


def symbol_names(keys: int) -> np.ndarray:
    if keys != len(SYMBOLS):
        raise ValueError(f"the cse tape has {len(SYMBOLS)} symbols")
    return np.array(SYMBOLS)


def rows(batch: dict, keep, symbols: np.ndarray) -> dict:
    return {**stock.rows(batch, keep, symbols),
            "timestamp": batch["ts"][keep]}


def feed_columns(batch: dict, symbols: np.ndarray) -> tuple:
    cols = rows(batch, slice(None), symbols)
    cols["timestamp"] = cols["timestamp"].copy()    # not a view of `ts`
    return cols, batch["ts"]


class Tape(stock.Tape):
    make = staticmethod(make_batch)
