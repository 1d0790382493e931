"""Synthetic sensor tape: a seeded, deterministic stream of the Siddhi Query
Guide's `TempStream(deviceID long, roomNo int, temp double)` events, a tape
whose keys are NUMBERS (the next numeric-keyed deployment copies this file,
not `stock.py`, whose key is a string).

A fleet of `keys` sensors sits four to a room in `rooms` rooms: sensor `d`
(0-based) reports as `deviceID = DEVICE_ID0 + d` from `roomNo = d //
(keys // rooms)`, so the (roomNo, deviceID) groups of the guide's query are
the sensors.  The reporting sensor of every event is drawn uniformly, the
temperature uniformly on a grid of `temp_step` in [temp_lo, temp_hi] (the
configuration's quarter degrees are exact in f32, in which the device
evaluates DOUBLE); event `j` of the stream carries the timestamp `TS0 + j *
dt_ms`, so a timestamp names its event.  Batch `i` is drawn from
`default_rng([seed, i])`: a stream is the same whatever its length, and any
batch can be made alone.

What a tape module gives (a new tape is a new file with the same names):

    Tape(params, seed)      `.params`, `.seed`, `.ring`, `.lap_ms`,
                            `.batch(i)` -> {"n", "ts", <the columns>},
                            `.event_index(ts)`
    symbol_names(keys)      what the drivers hand to `rt.strings.encode`,
                            one name a key; the codes that come back are
                            passed to the two functions below as `symbols`
    feed_columns(batch, symbols) -> (columns, timestamps), send_batch's
                            arguments
    rows(batch, keep, symbols)   the events at positions `keep` as the
                            stream's attributes
    EVENT_TIME_COLUMNS      the attributes that carry event time (they
                            advance with a ring's laps); none here

Without a string key there is nothing to encode: `symbol_names` gives the
sensor indices (the drivers still encode their decimal spellings into the
runtime's dictionary, 2,000 entries that no column refers to) and
`feed_columns` / `rows` take `symbols` and do not read it.  `params["skew"]`
(traffic/sat-2p18-inproc.json hands it to every tape of 100 keys and more,
to settle a lane grid's capacity in set-up) is accepted and NOT applied:
this deployment has no lane grid, and a sensor raised to 352 events would
be a different fleet.  `params["ring"]` is refused: a sliding window is
stateful, and a tape that repeats is sound only for a stateless query.
"""
import numpy as np

from benchmark.tapes.stock import TS0, event_index, on_grid

DEVICE_ID0 = 100_000
EVENT_TIME_COLUMNS = ()     # no attribute of the stream carries event time


def device_id(dev_idx):
    return DEVICE_ID0 + np.asarray(dev_idx, np.int64)


def room_no(dev_idx, params: dict):
    """The room of sensor `dev_idx`: `keys // rooms` sensors a room."""
    per_room = int(params["keys"]) // int(params["rooms"])
    return (np.asarray(dev_idx) // per_room).astype(np.int32)


def make_batch(params: dict, seed: int, index: int) -> dict:
    """Batch `index` (0-based) of the stream that `params` and `seed`
    define.  `params`: keys, rooms, batch, dt_ms, temp_lo, temp_hi,
    temp_step."""
    n, keys = int(params["batch"]), int(params["keys"])
    rng = np.random.default_rng([int(seed), int(index)])
    dev = rng.integers(0, keys, size=n).astype(np.int32)
    temp = on_grid(rng.uniform(params["temp_lo"], params["temp_hi"], size=n),
                   params["temp_step"])
    start = index * n
    return {"dev_idx": dev, "deviceID": device_id(dev),
            "roomNo": room_no(dev, params), "temp": temp,
            "ts": TS0 + np.arange(start, start + n, dtype=np.int64)
            * int(params["dt_ms"]),
            "n": n}


def symbol_names(keys: int) -> np.ndarray:
    return np.arange(keys)


def rows(batch: dict, keep, symbols=None) -> dict:
    return {"deviceID": batch["deviceID"][keep],
            "roomNo": batch["roomNo"][keep], "temp": batch["temp"][keep]}


def feed_columns(batch: dict, symbols=None) -> tuple:
    return rows(batch, slice(None)), batch["ts"]


class Tape:
    """The stream of one run: `batch(i)` is batch `i` of it, whatever was
    asked for before."""

    make = staticmethod(make_batch)
    ring, lap_ms = 0, 0

    def __init__(self, params: dict, seed: int):
        if params.get("ring"):
            raise ValueError("the temp tape feeds a stateful query: no ring")
        if int(params["keys"]) % int(params["rooms"]):
            raise ValueError("keys must be a multiple of rooms")
        self.params, self.seed = dict(params), int(seed)

    def batch(self, i: int) -> dict:
        return self.make(self.params, self.seed, i)

    def event_index(self, ts):
        return event_index(ts, self.params)
