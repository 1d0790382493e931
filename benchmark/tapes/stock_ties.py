"""The synthetic stock tape at an event-time RATE: `stock.py`'s stream (same
attributes, prices and volumes, same contract: batch `i` is drawn from
`default_rng([seed, i])` exactly as `stock.py` draws it, so a stream is the
same whatever its length and any batch can be made alone), with event `j` of
the stream stamped `TS0 + j // events_per_ms`: `events_per_ms` events share
each millisecond of event time, so timestamps are non-decreasing and TIE.

`stock.py` stamps one event a millisecond ("a timestamp names its event").
Over 200,000 keys that is an event a key every 200 s, and a `within 10 sec`
never matches; at the rate such a deployment sees (a few hundred thousand
events a second over its keys) a millisecond holds hundreds of events.  So a
timestamp no longer names its event, this tape has no `event_index`, and a
judge of it reads a row's key from the row (reference/pattern_chain_keyed.py).

The traffic file's `skew` stanza ({"batch": i, "key": k, "events": n}) has
`stock.py`'s meaning and `stock.py`'s draw: key `k` is raised to at least `n`
events in batch `i`, taken from the other keys.
"""
import numpy as np

from benchmark.tapes import stock
from benchmark.tapes.stock import (EVENT_TIME_COLUMNS, TS0, feed_columns,
                                   on_grid, rows, symbol_names)

__all__ = ["EVENT_TIME_COLUMNS", "TS0", "Tape", "feed_columns", "make_batch",
           "on_grid", "rows", "symbol_names"]


def make_batch(params: dict, seed: int, index: int) -> dict:
    """Batch `index` (0-based) of the stream that `params` and `seed`
    define.  `params`: keys, batch, events_per_ms, price_lo, price_hi,
    price_step and an optional `skew`.  Symbols, prices, volumes and the
    skew are `stock.make_batch`'s own (one event a millisecond there);
    only the timestamps are this tape's."""
    b = stock.make_batch({**params, "dt_ms": 1}, seed, index)
    n = b["n"]
    j = np.arange(index * n, (index + 1) * n, dtype=np.int64)
    b["ts"] = TS0 + j // int(params["events_per_ms"])
    return b


class Tape:
    """The stream of one run: `batch(i)` is batch `i` of it, whatever was
    asked for before.  For a stateful query only: no ring."""

    ring = 0

    def __init__(self, params: dict, seed: int):
        if params.get("ring"):
            raise ValueError("stock_ties: a ring of batches would repeat "
                             "keys' histories; the tape is for stateful "
                             "configurations")
        self.params, self.seed = dict(params), int(seed)

    def batch(self, i: int) -> dict:
        return make_batch(self.params, self.seed, i)
