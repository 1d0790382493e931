"""The comparison that decides `correct`: what the timed path delivered
against the plain reference, by value.  Every number compared is a count
with the limit 0 (the configurations state an exact match set); `checks` is
a list of {"name", "value", "limit"} and a run is correct when every value
is within its limit."""
import numpy as np


def check(name: str, value, limit=0) -> dict:
    return {"name": name, "value": value, "limit": limit}


def verdict(checks: list) -> bool:
    return bool(checks) and all(c["value"] <= c["limit"] for c in checks)


GRID_CODES = 4096       # 12 bits a price: three prices and e3 in an int64


def _grid_code(p, lo, step):
    inv = round(1.0 / step)
    return np.clip(np.rint((np.asarray(p, np.float64) - lo) * inv),
                   0, GRID_CODES - 1).astype(np.int64)


def _row_key(e3, p1, p2, p3, lo, step):
    """One int64 per (e3 position, p1, p2, p3) row: injective while prices
    sit on the tape's grid within 4095 steps of `lo`, which `off_grid`
    checks apart."""
    return (np.asarray(e3, np.int64) << 36) | (_grid_code(p1, lo, step) << 24) \
        | (_grid_code(p2, lo, step) << 12) | _grid_code(p3, lo, step)


def multiset_gap(got_keys, want_keys):
    """(rows of `want` missing from `got`, rows of `got` not in `want`),
    both as multisets."""
    got_keys, want_keys = np.sort(got_keys), np.sort(want_keys)
    if len(got_keys) == len(want_keys) \
            and np.array_equal(got_keys, want_keys):
        return 0, 0
    _u, inv = np.unique(np.concatenate([got_keys, want_keys]),
                        return_inverse=True)
    n = int(inv.max()) + 1 if len(inv) else 0
    have = np.bincount(inv[:len(got_keys)], minlength=n)
    owed = np.bincount(inv[len(got_keys):], minlength=n)
    return (int(np.maximum(owed - have, 0).sum()),
            int(np.maximum(have - owed, 0).sum()))


def pattern_rows(got: dict, want: dict, got_key, price_lo: float,
                 price_step: float) -> list:
    """`got`: delivered columns ts, p1, p2, p3, e3 (stream position of the
    row's timestamp) in delivery order; `got_key`: the partition key of each
    delivered row; `want`: the reference's columns for the same events."""
    prices = np.concatenate([got["p1"], got["p2"], got["p3"]]) \
        if len(got["ts"]) else np.zeros(0)
    steps = (prices - price_lo) * round(1.0 / price_step)
    off_grid = int(np.count_nonzero(
        (np.abs(steps - np.rint(steps)) > 1e-7)     # a double sits within
        | (steps < 0) | (steps > GRID_CODES - 1)))  # 1e-10 of its grid point
    missing, extra = multiset_gap(
        _row_key(got["e3"], got["p1"], got["p2"], got["p3"], price_lo,
                 price_step),
        _row_key(want["e3"], want["p1"], want["p2"], want["p3"], price_lo,
                 price_step))
    # per-key order: within one key, rows arrive in e3 order
    by_key = np.argsort(got_key, kind="stable")
    k, e3 = np.asarray(got_key)[by_key], np.asarray(got["e3"])[by_key]
    out_of_order = int(np.count_nonzero(
        (k[1:] == k[:-1]) & (e3[1:] < e3[:-1])))
    return [check("rows_missing", missing), check("rows_extra", extra),
            check("values_off_grid", off_grid),
            check("rows_out_of_key_order", out_of_order),
            check("nothing_to_compare", int(len(want["ts"]) == 0))]


def filter_rows(counts_got: dict, counts_want: dict, sampled: list) -> list:
    """`counts_*`: rows per input batch, delivered and owed, for EVERY batch
    of the window; `sampled`: (delivered columns, owed columns) for the
    batches compared by value."""
    wrong_counts = sum(1 for b in set(counts_got) | set(counts_want)
                       if counts_got.get(b, 0) != counts_want.get(b, 0))
    differing, compared = 0, 0
    for got, want in sampled:
        n = len(want["ts"])
        compared += n
        if len(got["ts"]) != n:
            differing += max(len(got["ts"]), n)
            continue
        bad = np.zeros(n, bool)
        for col in want:
            bad |= np.asarray(got[col]) != np.asarray(want[col])
        differing += int(bad.sum())
    return [check("batches_with_wrong_row_count", wrong_counts),
            check("sampled_rows_differing", differing),
            check("nothing_to_compare", int(compared == 0))]
