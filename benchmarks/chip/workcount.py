"""Bytes the algorithms need, computed from what a call has to process.

These count the work a call has to do, whatever implements it, so that a
change to a kernel cannot make them stale: they never count a padded row
or segment, or a copy the current program happens to make.
"""
from __future__ import annotations

import numpy as np


def segment_reduce_bytes(columns: int, rows: int, segments: int) -> int:
    """A segmented reduction reads its float32 values and int32 segment
    ids and writes one float32 per column and segment."""
    return 4 * (columns * rows + rows + columns * segments)


def live_rows_and_segments(seg_ids) -> tuple[int, int]:
    """The rows and segments a segmented reduction has to reduce: rows
    with a segment id of 0 or more (a negative id marks a padding row,
    reduced into nothing) and the segments those rows name."""
    ids = np.asarray(seg_ids)
    live = ids[ids >= 0]
    return int(live.size), int(live.max()) + 1 if live.size else 0
