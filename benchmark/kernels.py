"""Bytes and operations that a kernel needs for one call, from its shapes.
Kept with the benchmark so that no PR that claims a gain can move them."""


def lane_block_bytes(lanes: int, capacity: int, in_cols: int = 3,
                     out_rows: int = 7) -> int:
    """HBM bytes one call of the lane-vmapped pattern block (scan family)
    must move: it reads `in_cols` 4-byte grids of (lanes, capacity) cells
    (timestamp and sequence offsets as i32, each captured attribute as f32)
    with two i32 per lane (event count, previous sequence), and writes the
    packed i32 result of (lanes, out_rows, capacity) cells (header, e3
    timestamp, sequence, head sequence and one row per selected f32 value;
    the match capacity of a lane block equals its event capacity).  State
    and intermediates are left out: this is the least the call can move."""
    if min(lanes, capacity, in_cols, out_rows) <= 0:
        raise ValueError("shapes must be positive")
    cells = lanes * capacity
    return 4 * (in_cols * cells + 2 * lanes + out_rows * cells)
