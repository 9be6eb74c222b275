"""Peaks by device kind, and the least bytes a window's work must move.

Peaks: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A device kind that is not
in the table is an error, never a default.

Bytes follow from the semantics of the operations, not from how the
program implements them, so that copying the index state or building a
capacity-sized table counts for nothing and no implementation can read
over 100%.  Index geometry: ``N`` slots, fanout ``F``; a point lookup in
an ordered index of fanout ``F`` reads one ``F``-key entry per level from
the top down to the record (``levels(N, F) + 1`` entries of ``F`` keys);
a record is its key, its value and its delete flag.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
                    "int8_op_per_s": 393e12},
}

KEY_B = 4        # int32 key
VAL_B = 4        # int32 value (a record reference)
FLAG_B = 1       # delete flag
RECORD_B = KEY_B + VAL_B + FLAG_B
OP_IN_B = 4 + KEY_B + VAL_B      # op code, key, value
POINT_OUT_B = 1 + VAL_B          # found, value
SCAN_IN_B = 4 + 2 * KEY_B        # op code, lo, hi
SCAN_OUT_B = 4 + 4               # count, sum


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       f"them to bench/roofline.py with their source")


def levels(n_slots: int, fanout: int) -> int:
    """Index levels above the records: until one entry holds the top."""
    h, size = 0, n_slots
    while size > fanout:
        size = -(-size // fanout)
        h += 1
    return h


def descent_bytes(n_slots: int, fanout: int) -> int:
    """One root-to-record path: an ``F``-key entry per level and one at
    the record layer."""
    return (levels(n_slots, fanout) + 1) * fanout * KEY_B


def point_bytes(n_point: int, n_written: int, n_inserted: int,
                n_slots: int, fanout: int) -> int:
    """Least bytes of a window's point work.

    ``n_point`` point operations each descend and read their record;
    ``n_written`` distinct keys take a new value and flag; ``n_inserted``
    new records are written whole.  Rebuilds are not counted (a lower
    bound), nor is any copy of state the operations did not touch.
    """
    return (n_point * (descent_bytes(n_slots, fanout) + RECORD_B
                       + OP_IN_B + POINT_OUT_B)
            + n_written * (VAL_B + FLAG_B) + n_inserted * RECORD_B)


def scan_bytes(n_scans: int, n_records: int, n_slots: int,
               fanout: int) -> int:
    """Least bytes of a window's scans: a descent to each start, then
    every record in the ranges, read once."""
    return (n_scans * (descent_bytes(n_slots, fanout) + SCAN_IN_B
                       + SCAN_OUT_B) + n_records * RECORD_B)
