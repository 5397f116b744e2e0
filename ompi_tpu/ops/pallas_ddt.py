"""Pallas kernel of the datatype engine: the transpose of a matrix of
small blocks.

``transpose_blocks(x, rows, cols, b)`` takes a matrix of rows x cols
blocks of ``b`` elements each (``b`` 2, 4 or 8 of a 4-byte type: an FFT's
complex element is two float32), as a (rows, b x cols) array or flat, and
returns it transposed with every block kept whole, flat: ``out[(j x rows +
i) x b + p] = x[i, b x j + p]``.  It is what ``resized(vector(rows, b, b x
cols, T), 0, 4 x b)`` packs, count cols, and its own inverse with rows and
cols exchanged.

Why a kernel: XLA writes this as ``reshape(rows, cols, b).transpose(1, 0,
2)``, and on a TPU an array whose minor dimension is ``b`` is tiled ``T(b,
128)`` with every b padded to 128 lanes.  Compiled for a v5e the 4096 x
4096 case holds 8.1 GiB of temporaries for 128 MiB of data and 8192 x 8192
does not fit the chip; lane-strided slices and interior pads, the other
ways to say it in XLA, do not finish compiling at 1024 x 1024 (PERF.md
section 6, PR 27).  Here no array has a minor dimension under 128: a
sub-tile of 128 x 128 blocks is transposed whole on the XLU, its rows
de-interleaved by strided sublane loads, transposed back, interleaved by
strided sublane stores and transposed once more.  A flat side is read or
written as (n, k, 128), which is the flat order under ``T(8, 128)``
tiling, so neither side pays XLA's relayout copy between a matrix and a
1-D stream.  Every step moves bits; nothing is computed.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.base.jaxenv import pallas_interpret

LANES = 128
TILE = 512      # blocks a side of a grid step: 512 x 512 x b x 4 bytes


def supported(rows: int, cols: int, b: int, dtype) -> bool:
    """Whole tiles only; anything else keeps XLA's program."""
    return (b in (2, 4, 8) and np.dtype(dtype).itemsize == 4
            and rows % TILE == 0 and cols % TILE == 0)


def _kernel(b, flat_in, x_ref, o_ref, t_ref, z_ref):
    # Mosaic's strided loads and stores want a scratch whose last
    # dimension is exactly 128 lanes, so a tile goes through in
    # sub-tiles of 128 x 128 blocks
    w = b * LANES
    for k in range(TILE // LANES):
        rows = slice(k * LANES, (k + 1) * LANES)
        for m in range(TILE // LANES):
            if flat_in:         # (128, b, 128) -> (128, b x 128)
                sub = x_ref[rows, m * b:(m + 1) * b, :].reshape(LANES, w)
            else:
                sub = x_ref[rows, m * w:(m + 1) * w]
            t_ref[...] = sub.T                               # (b*128, 128)
            for p in range(b):
                plane = t_ref[pl.ds(p, LANES, stride=b), :]  # x[:, p::b].T
                z_ref[pl.ds(p, LANES, stride=b), :] = plane.T
            o_ref[m * LANES:(m + 1) * LANES, k * b:(k + 1) * b, :] = \
                z_ref[...].T.reshape(LANES, b, LANES)


@functools.partial(jax.jit, static_argnums=(1, 2, 3),
                   static_argnames=("interpret",))
def transpose_blocks(x, rows: int, cols: int, b: int, *, interpret=None):
    if interpret is None:
        interpret = pallas_interpret()
    flat_in = x.ndim == 1
    if flat_in:
        x = x.reshape(rows, b * cols // LANES, LANES)
        in_spec = pl.BlockSpec((TILE, b * TILE // LANES, LANES),
                               lambda i, j: (i, j, 0))
    else:
        in_spec = pl.BlockSpec((TILE, b * TILE), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_kernel, b, flat_in),
        out_shape=jax.ShapeDtypeStruct((cols, b * rows // LANES, LANES),
                                       x.dtype),
        grid=(rows // TILE, cols // TILE),
        in_specs=[in_spec],
        out_specs=pl.BlockSpec((TILE, b * TILE // LANES, LANES),
                               lambda i, j: (j, i, 0)),
        scratch_shapes=[pltpu.VMEM((b * LANES, LANES), x.dtype),
                        pltpu.VMEM((b * LANES, LANES), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="otpu_ddt_transpose_blocks",
    )(x)
    return out.reshape(-1)
