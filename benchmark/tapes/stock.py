"""Synthetic stock tape: a seeded, deterministic stream of
`StockStream(symbol string, price double, volume int)` events.

A copy of `bench.make_tape` (which later PRs may delete) that takes the
run's seed and is as long as the caller asks: batch `i` of a stream is drawn
from `default_rng([seed, i])`, so a stream is the same whatever its length
and any batch can be made alone.  Symbols are key INDICES (the drivers turn
them into strings or dictionary codes); prices sit on a grid of `price_step`
in [lo, hi] (the configurations' quarter steps are exact in f32, in which
the device evaluates DOUBLE); event `j` of the stream carries the timestamp
`TS0 + j * dt_ms`, so a timestamp names its event.

What a tape module gives (a new tape is a new file with the same names):
`Tape(params, seed)`, `symbol_names(keys)`, `feed_columns(batch, symbols)`,
`rows(batch, keep, symbols)` and `EVENT_TIME_COLUMNS`.
"""
import numpy as np

TS0 = 1_700_000_000_000
EVENT_TIME_COLUMNS = ()     # no attribute of the stream carries event time


def on_grid(x, step: float):
    """`x` rounded to multiples of `step` (the nearest double to k/inv)."""
    inv = round(1.0 / step)
    return np.round(np.asarray(x) * inv) / inv


def make_batch(params: dict, seed: int, index: int) -> dict:
    """Batch `index` (0-based) of the stream that `params` and `seed`
    define.  `params`: keys, batch, dt_ms, price_lo, price_hi, and an
    optional `skew` {"batch": i, "key": k, "events": n} that raises key `k`
    to `n` events in batch `i`, taken evenly from the other keys (see traffic/sat-2p18-inproc.json)."""
    n, keys = int(params["batch"]), int(params["keys"])
    rng = np.random.default_rng([int(seed), int(index)])
    sym = rng.integers(0, keys, size=n).astype(np.int32)
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


def symbol_names(keys: int) -> np.ndarray:
    return np.array([f"K{i}" for i in range(keys)])


def rows(batch: dict, keep, symbols: np.ndarray) -> dict:
    """The events at positions `keep` as the stream's attributes; `symbols`
    maps a key index to what stands for it (a string, a dictionary code)."""
    return {"symbol": symbols[batch["sym_idx"][keep]],
            "price": batch["price"][keep], "volume": batch["volume"][keep]}


def feed_columns(batch: dict, symbols: np.ndarray) -> tuple:
    """A batch as send_batch's arguments: (columns, timestamps)."""
    return rows(batch, slice(None), symbols), batch["ts"]


def event_index(ts, params: dict):
    """Stream position of the event that carries timestamp `ts`."""
    return (np.asarray(ts, np.int64) - TS0) // int(params["dt_ms"])


class Tape:
    """The stream of one run: `batch(i)` is batch `i` of it, whatever was
    asked for before.  With `params["ring"] = R` the stream repeats its first
    R batches with timestamps that keep advancing (sound only for a
    stateless query: see traffic/sat-2p18-inproc.json)."""

    make = staticmethod(make_batch)

    def __init__(self, params: dict, seed: int):
        self.params, self.seed = dict(params), int(seed)
        self.ring = int(params.get("ring", 0))
        self.lap_ms = self.ring * int(params["batch"]) * int(params["dt_ms"])

    def batch(self, i: int) -> dict:
        if not self.ring:
            return self.make(self.params, self.seed, i)
        b = self.make(self.params, self.seed, i % self.ring)
        b["ts"] = b["ts"] + (i // self.ring) * self.lap_ms
        return b

    def event_index(self, ts):
        return event_index(ts, self.params)
