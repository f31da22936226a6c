"""Least device time of the scan programs, from their shapes.

A query group's program reads each of its input columns at least once from
HBM: codes are int32 and validity masks one byte a row. The dictionary,
bounds and overlay corrections are a few KiB and are left out, so the bytes
are a lower bound and a share of the roofline computed from them cannot
pass 100%. The scans compare and add a handful of integers per row: far
below the chip's compute peak, so HBM bandwidth bounds them.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

# program name -> bytes read per table row: (int32 code columns, bool masks)
SCAN_PROGRAMS = {
    # filter and aggregate codes, filter validity
    "scan_filter_agg_exact_kernel": (2, 1),
    "scan_group_kernel": (2, 1),
    # filter, aggregate and join codes, filter and join validity
    "join_scan_pallas": (3, 2),
    "join_group_kernel": (3, 2),
}


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; have {sorted(table)}")
    return table[device_kind]


def scan_bytes(program: str, n_rows: int) -> int | None:
    """HBM bytes one run of a scan program must read, or None for a
    program that is not a scan."""
    if program not in SCAN_PROGRAMS:
        return None
    codes, masks = SCAN_PROGRAMS[program]
    return n_rows * (4 * codes + masks)
