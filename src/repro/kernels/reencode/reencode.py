"""Stage-3 re-encode kernel (§5.2) on a device-resident column.

Both dictionaries of an apply are sorted and every old value survives the
merge, so the old->new code map is monotone: an old code ``c`` moves up by
the number of genuinely new values that sort below its value,

    new_code = c + #{t in T : t <= c},   T = searchsorted(old_dict, new values)

The kernel streams the codes as ``(rows, 128)`` int32 tiles and adds one
compare per threshold; the thresholds and their count ride in SMEM (scalar
prefetch), and the loop runs over the real thresholds only, so the padded
width costs nothing. There is no gather, and no shape depends on the
dictionary's size. The batch's row ops then run in the same jitted
program: the update codes are scattered at their rows (one entry per row,
the last write in commit order), ``valid`` is set at the written rows and
cleared at the deleted ones — `application._apply_row_ops`'s order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, instrumented_jit

BLOCK_ROWS = 512   # one grid step: a (512, 128) int32 tile, 256 KiB
SUB_ROWS = 64      # rows held in vector registers across the threshold loop


def _reencode_kernel(t_ref, nt_ref, c_ref, o_ref):
    n_t = nt_ref[0]
    rows = c_ref.shape[0]
    sub = min(SUB_ROWS, rows)
    for s in range(0, rows, sub):
        c = c_ref[s:s + sub, :]

        def step(j, acc, c=c):
            return acc + (t_ref[j] <= c).astype(jnp.int32)

        acc = jax.lax.fori_loop(0, n_t, step, jnp.zeros_like(c))
        o_ref[s:s + sub, :] = c + acc


def _shift_codes(codes, thresholds, n_thr, interpret: bool):
    """Traced: ``codes + #{t in thresholds[:n_thr] : t <= codes}`` through
    the Pallas kernel; rows are padded in-trace to whole tiles."""
    (n,) = codes.shape
    rows = -(-n // LANES)
    block = min(BLOCK_ROWS, -(-rows // 8) * 8)
    rows_pad = -(-rows // block) * block
    pad = rows_pad * LANES - n
    c = jnp.pad(codes, (0, pad)) if pad else codes
    tile = pl.BlockSpec((block, LANES), lambda i, t, nt: (i, 0))
    out = pl.pallas_call(
        _reencode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows_pad // block,),
            in_specs=[tile], out_specs=tile),
        out_shape=jax.ShapeDtypeStruct((rows_pad, LANES), jnp.int32),
        interpret=interpret,
    )(thresholds, n_thr, c.reshape(rows_pad, LANES)).reshape(-1)
    return out[:n] if pad else out


def _row_ops(codes, valid, w_rows, w_codes, d_rows):
    """Traced: the batch's row ops on the shifted ``codes``. Padded row
    slots hold an out-of-range row id, which the scatters drop."""
    codes = codes.at[w_rows].set(w_codes, mode="drop")
    valid = valid.at[w_rows].set(True, mode="drop")
    return codes, valid.at[d_rows].set(False, mode="drop")


@functools.partial(instrumented_jit, static_argnames=("interpret",))
def reencode_rows_kernel(codes, valid, thresholds, n_thr, w_rows, w_codes,
                         d_rows, interpret: bool = True):
    """Stage 3 on the device: the Pallas compare-and-add over the codes,
    then the row ops. ``thresholds`` is sorted, padded with int32.max;
    ``n_thr`` is a (1,) int32 count of its real entries. Returns the new
    codes and validity."""
    new = _shift_codes(codes, thresholds, n_thr, interpret)
    return _row_ops(new, valid, w_rows, w_codes, d_rows)


@instrumented_jit
def reencode_rows_lowered(codes, valid, thresholds, n_thr, w_rows, w_codes,
                          d_rows):
    """The same stage 3 in jax-numpy (the CPU path): the padded
    thresholds are int32.max, above every code, so a right-sided search
    counts exactly the real ones."""
    del n_thr
    shift = jnp.searchsorted(thresholds, codes, side="right")
    return _row_ops(codes + shift.astype(jnp.int32), valid, w_rows, w_codes,
                    d_rows)
