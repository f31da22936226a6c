"""Public wrapper for the stage-3 re-encode of a device-resident column.

The host side pads the batch's small arrays to pow2 widths
(``common.width_bucket``), so the traced shapes are the column length and
those widths; the dictionary's size never enters one.
"""

from __future__ import annotations

import numpy as np

from repro.kernels.common import kernel_mode, width_bucket
from repro.kernels.reencode.reencode import (reencode_rows_kernel,
                                             reencode_rows_lowered)

_I32_MAX = np.iinfo(np.int32).max


def _padded(values, width: int, fill: int) -> np.ndarray:
    out = np.full(width, fill, dtype=np.int32)
    out[:len(values)] = values
    return out


def reencode_rows(codes, valid, thresholds, write_rows, write_codes,
                  del_rows):
    """One column's stage 3 on the device: its new ``(codes, valid)``
    device arrays.

    ``codes``/``valid``: the column's device arrays; ``thresholds``: the
    sorted ``searchsorted(old_dict, new values)`` of the batch's genuinely
    new values; ``write_rows``/``write_codes``: one entry per written row,
    its last write's code in the new dictionary; ``del_rows``: the deleted
    rows. The thresholds and the writes are padded to one bucket
    (``common.width_bucket``) of the larger of them, the deletes to their
    own.
    """
    n = int(codes.shape[0])
    width = width_bucket(max(len(thresholds), len(write_rows)))
    args = (codes, valid,
            _padded(thresholds, width, _I32_MAX),
            np.array([len(thresholds)], dtype=np.int32),
            _padded(write_rows, width, n),
            _padded(write_codes, width, 0),
            _padded(del_rows, width_bucket(len(del_rows)), n))
    mode = kernel_mode()
    if mode == "lowered":
        return reencode_rows_lowered(*args)
    return reencode_rows_kernel(*args, interpret=(mode == "interpret"))
