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

``compact(x, windows, bases, table, ...)`` packs a sorted index list whose
packed stream walks the source forwards: 128 packed elements (one output
row) come from a short run of consecutive 128-lane source rows.  XLA's
gather costs 19-21 ns an index whatever it is told, while the rows a
dense list touches are nearly all of its span (``datatype/plan.py`` has
the numbers).  So the source stays in HBM, a grid
step DMAs the window of rows its ``tile`` output rows span into VMEM
(double-buffered; the start comes from the prefetched scalars), and an
output row is built with the two in-register gathers Mosaic has: each
(8, 128) slab of the row's run is gathered along lanes by the row's lane
indices, the slabs are merged by which slab an element's source row is
in, and one gather along sublanes picks the row.  A sublane gather takes
one vreg, which is why the lane gathers come first.  ``plan.IndexPlan``
builds the tables and decides from the index array which lists stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.base.jaxenv import pallas_interpret

LANES = 128
TILE = 512      # blocks a side of a grid step: 512 x 512 x b x 4 bytes
# compact: slabs gathered side by side in one pass of the loop.  A row is
# a chain of dependent loads and gathers, so rows one at a time leave the
# vector slots idle: 9.3 ms for the 98,304 rows (2 slabs each) of
# rank1-ddt's list on a v5e, 1.76 ms at 8 rows a pass, 1.30 at 16, 1.10
# at 32, 1.00 at 64.  The pass is unrolled, and at 64 rows the program's
# first call takes 1.2 s longer (set-up), at 32 no longer than the
# gather's did (PERF.md section 6, PR 30)
PASS_SLABS = 64


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


def _compact_kernel(tile, height, slabs, w_ref, b_ref, x_hbm, t_ref, o_ref,
                    win, sem):
    n, i = pl.program_id(0), pl.program_id(1)
    slot = i % 2

    def window(step, slot):
        start = pl.multiple_of(w_ref[step], 8)
        return pltpu.make_async_copy(x_hbm.at[n, pl.ds(start, height), :],
                                     win.at[slot], sem.at[slot])

    @pl.when(i == 0)
    def _():
        window(0, 0).start()

    @pl.when(i + 1 < pl.num_programs(1))
    def _():
        window(i + 1, 1 - slot).start()

    window(i, slot).wait()
    w = win.at[slot]
    sublane = lax.broadcasted_iota(jnp.int32, (8, LANES), 0)

    def row(j):
        """Output row j of the step, in every sublane of a vreg."""
        t = jnp.broadcast_to(t_ref[pl.ds(j, 1), :], (8, LANES))
        lane, delta = t & (LANES - 1), t >> 7
        base = b_ref[i * tile + j]
        picked = None
        for k in range(slabs):
            g = jnp.take_along_axis(w[pl.ds(base + 8 * k, 8), :], lane,
                                    axis=1)
            picked = g if k == 0 else jnp.where(delta >> 3 == k, g, picked)
        return jnp.take_along_axis(picked, delta & 7, axis=0)

    rows = 8        # a pass: whole (8, 128) output tiles, a power of two
    while 2 * rows <= min(tile, PASS_SLABS // slabs):
        rows *= 2

    def step(p, carry):
        for u in range(0, rows, 8):
            j = pl.multiple_of(p * rows + u, 8)
            out = row(j)
            for r in range(1, 8):
                out = jnp.where(sublane == r, row(j + r), out)
            o_ref[pl.ds(j, 8), :] = out
        return carry

    lax.fori_loop(0, tile // rows, step, 0)


@functools.lru_cache(maxsize=None)
def _compact(tile: int, height: int, slabs: int, interpret: bool):
    @jax.custom_batching.custom_vmap
    def fn(x, windows, bases, table):
        return pl.pallas_call(
            functools.partial(_compact_kernel, tile, height, slabs),
            out_shape=jax.ShapeDtypeStruct(x.shape[:1] + table.shape,
                                           x.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(x.shape[0], table.shape[0] // tile),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                          pl.BlockSpec((tile, LANES),
                                       lambda n, i, *_: (i, 0))],
                out_specs=pl.BlockSpec((None, tile, LANES),
                                       lambda n, i, *_: (n, i, 0)),
                scratch_shapes=[pltpu.VMEM((2, height, LANES), x.dtype),
                                pltpu.SemaphoreType.DMA((2,))]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="otpu_ddt_compact",
        )(windows, bases, x, table)

    @fn.def_vmap
    def batched(size, in_batched, x, *tables):
        # a typed slot vmaps a pack over its ranks and blocks: the buffers
        # become the kernel's leading grid axis, the tables stay one
        if tuple(in_batched) != (True, False, False, False):
            raise NotImplementedError("compact: one list, many buffers")
        out = fn(x.reshape((-1,) + x.shape[2:]), *tables)
        return out.reshape((size, -1) + out.shape[1:]), True

    return fn


def compact(x, windows, bases, table, *, tile: int, height: int, slabs: int,
            interpret=None):
    """``x`` (n, rows, 128) of a 4-byte type, at least ``height`` rows:
    each of the n buffers packed by one list, (n, len(table), 128).
    ``table[j, c]`` names the source of output row j's lane c as ``(row -
    first row of j's slabs) << 7 | lane``; ``bases[j]`` is that first row
    counted from the step's window, ``windows[i]`` step i's first row (a
    multiple of 8).  A step is ``tile`` output rows, a window ``height``
    source rows, a row's run ``slabs`` slabs of 8 rows."""
    if interpret is None:
        interpret = pallas_interpret()
    return _compact(tile, height, slabs, bool(interpret))(
        x, windows, bases, table)
