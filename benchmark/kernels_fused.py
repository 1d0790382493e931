"""Bytes the fused multi-query block must move, from the WORK it does and
not from how it is laid out: a gain that shrinks a grid, a capacity or an
upload leaves this count where it was.  Kept with the benchmark so that no
PR that claims a gain can move it."""


def fused_block_bytes(events_sent: float, rows_delivered: float,
                      in_cols: int = 3, out_words: int = 7) -> float:
    """HBM bytes the fused blocks must move for `events_sent` events of the
    one shared stream and the `rows_delivered` match rows they produce:
    each event is read once as `in_cols` 4-byte columns (timestamp and
    sequence offsets as i32, the price as f32: a broadcast stream, however
    many groups, rows or lanes it is laid out over), and each delivered row
    is written once as `out_words` 4-byte words (the lane block's packed
    result: header, last timestamp, sequence, head sequence and one word a
    selected value).  State, intermediates and padding are left out: this
    is the least the work can move.  The counts may be fractional (rows an
    event times events)."""
    if events_sent <= 0 or rows_delivered < 0 or min(in_cols, out_words) <= 0:
        raise ValueError("events and shapes must be positive")
    return 4.0 * (in_cols * events_sent + out_words * rows_delivered)
