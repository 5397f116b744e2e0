"""Pallas row scatter-add: ``acc[token[i]] += scale[i] * y[i]`` for the
live rows of one chunk, the sums updated where they lie.

What the held experts' loop of a public model's train step
(``parallel/experts.local_expert_ffn``) does twice a trip: a chunk's rows
added into a carried ``(T, d)`` float32 array by token.  XLA's
scatter-add of 2,048 such rows runs at an eighth of the HBM's rate on a
v5e (0.17-0.34 us a row of 1,024 to 2,560 floats where the row's three
transfers take 0.015-0.0375: ``PERF.md`` section 6, PR 57 and 66), so
where Mosaic compiles the rows move by DMA: ``acc`` stays in HBM and is
the call's result (``input_output_aliases``: no ``(T, d)`` temporary and
no copy of the carry a trip), a row is read from ``acc[token[r]]`` into
a VMEM buffer beside the chunk's ``y`` block, ``buffer += scale * y`` on
the VPU, and the row is written back.  Two buffers, a block of rows
each: a block's writes fly while the next block's rows are read.  A DMA
places whole tiles, so the sums are held as tiles a row (``as_tiles``:
``(T, 8, d / 8)`` where a row's lane tiles fill 8 sublanes) while a loop
adds to them; ``y`` comes as it is and a row of it is turned in the
kernel.

**Which rows may be in flight together.**  The rows come sorted by group
(``experts.local_dispatch``: a stable ``argsort`` of the flattened
``(t, k)`` slots by held expert), and a token's k experts are distinct
(``lax.top_k``), so inside one group the tokens strictly ascend: no two
rows of a group name the same row of ``acc``, and their reads and writes
overlap freely.  Two rows of different groups may name the same token,
so before a group's first row is read every write started so far is
waited for, and again at the call's end.  Sums of one ``acc`` row are
therefore made in the order of the rows, as ``.at[].add`` makes them.

Rows at and past ``offsets[-1]`` (the chunk's live count) are not
walked: their ``y`` is never read (it may hold anything, NaN too) and
their ``token`` names no row.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.ops.grouped_matmul import LANES, VMEM_LIMIT

#: rows a grid step takes: the two buffers and ``y``'s two blocks are 20
#: MiB at rows of 2,560 floats.  On the v5e 512 and 1,024 were 2-4% faster
#: than 256 at 2,048 rows of 1,024 to 2,560 floats, and 128 3% slower (my
#: chip runs, PR 66)
BLOCK_ROWS = 512
#: rows a trip of the kernel's loops starts or adds: 4, 8 and 16 were
#: within 2% of each other and 10-20% faster than one by one (the same
#: runs); the fewest of them, because a step's trace and lowering pay for
#: every copy of the body (at 8 and a wait a row two kernels cost a step
#: cell 2.2 s of ``setup_s`` on the chip's host: ``PERF.md`` section 6)
UNROLL = 4
#: rows one wait takes
WAIT_ROWS = 8


def block_rows(rows: int) -> int:
    """Rows a grid step takes of a chunk of ``rows``."""
    return min(rows, BLOCK_ROWS)


def refusal(rows: int, d: int, dtype=jnp.float32) -> str:
    """Why ``row_scatter_add`` has no kernel for a chunk of ``rows`` rows
    of ``d`` entries of ``dtype`` ("": it has one): the clause of
    ``supported`` that fails first."""
    if jnp.dtype(dtype) != jnp.float32:
        return f"the sums are {jnp.dtype(dtype).name}, not float32"
    if d % LANES:
        return f"a row of {d} entries is no whole lane tiles of {LANES}"
    if rows % block_rows(rows):
        return (f"a chunk of {rows} rows is no whole blocks of "
                f"{block_rows(rows)}")
    return ""


def supported(rows: int, d: int, dtype=jnp.float32) -> bool:
    """Whether ``row_scatter_add`` has a kernel for a chunk of ``rows``
    rows of ``d`` entries of ``dtype``, by shape alone: float32 sums,
    rows of whole lane tiles, whole blocks."""
    return not refusal(rows, d, dtype)


def tile_shape(d: int) -> tuple:
    """(sublanes, lanes) a row of ``d`` floats is held as: whole lane
    tiles, over as many of a float32 tile's 8 sublanes as divide them,
    so that no sublane is padding: 2,048 floats as (8, 256), 2,560 (20
    lane tiles) as (4, 640), which the TPU tiles by (4, 128); as
    (20, 128) a row would lie in 24 sublanes, 33.5 MB more for 16,384
    rows."""
    sublanes = math.gcd(d // LANES, 8)
    return sublanes, d // sublanes


def as_tiles(x):
    """``x`` (n, d) as (n, ``*tile_shape(d)``): a row as tiles of its
    own, which is what a DMA can place (Mosaic slices a tiled dimension
    by whole tiles only, so one row of an (n, d) buffer is no slice;
    seen offline, PR 66).  On a TPU this is another layout in memory, so
    a loop's sums stay in this form from their zeros to the loop's
    end."""
    return x.reshape(x.shape[0], *tile_shape(x.shape[1]))


def as_rows(x):
    """``as_tiles``'s inverse."""
    n, sublanes, lanes = x.shape
    return x.reshape(n, sublanes * lanes)


def _kernel(block, token, offsets, scale, y, _, acc, buf, rsem, wsem,
            pending):
    i, groups = pl.program_id(0), offsets.shape[0] - 1
    slot, first_row = i % 2, i * block

    @pl.when(i == 0)
    def _():
        pending[0] = 0
        pending[1] = 0

    def read(r):
        return pltpu.make_async_copy(
            acc.at[token[r]], buf.at[slot, r - first_row], rsem)

    def write(r):
        return pltpu.make_async_copy(
            buf.at[slot, r - first_row], acc.at[token[r]], wsem.at[slot])

    def each(lo, hi, do):
        """``do(r)`` for r from ``lo`` to ``hi``, ``UNROLL`` rows a trip
        and the rest one by one."""
        def some(n):
            def step(i, c):
                for j in range(n):
                    do(lo + i * n + j)
                return c
            return step
        whole = (hi - lo) // UNROLL
        jax.lax.fori_loop(0, whole, some(UNROLL), 0)
        jax.lax.fori_loop(whole * UNROLL, hi - lo, some(1), 0)

    def landed(sem, n):
        """Wait until ``n`` of the row copies that signal ``sem`` have
        landed.  A DMA semaphore counts bytes and every copy is a row's,
        so one wait takes ``WAIT_ROWS`` rows' at once (its descriptor
        only says how many bytes: no copy is made), and the rest one by
        one.  Copies land in any order: ``n`` of them is all that is
        known, so ``n`` is always every copy in flight on ``sem``."""
        def rows(k):
            part = buf.at[0, pl.ds(0, k)]
            return lambda i, c: pltpu.make_async_copy(
                part, part, sem).wait() or c
        jax.lax.fori_loop(0, n // WAIT_ROWS, rows(WAIT_ROWS), 0)
        jax.lax.fori_loop(0, n % WAIT_ROWS, rows(1), 0)

    def drain(first_buffer, buffers):
        """Wait for every write started from ``buffers`` of the two
        buffers, ``first_buffer`` and up."""
        def one(s, c):
            landed(wsem.at[s], pending[s])
            pending[s] = 0
            return c
        jax.lax.fori_loop(first_buffer, first_buffer + buffers, one, 0)

    def group(g):
        first, end = offsets[g], offsets[g + 1]
        lo = jnp.maximum(first, first_row)
        hi = jnp.minimum(end, first_row + block)

        @pl.when(hi > lo)
        def _():
            # a group's first row: another group may have written its
            # token, so both buffers' writes.  A group going on from the
            # last block: this buffer's own, of two blocks ago
            new = lo == first
            drain(jnp.where(new, 0, slot), jnp.where(new, 2, 1))
            each(lo, hi, lambda r: read(r).start())
            landed(rsem, hi - lo)

            def add(r):
                k = r - first_row
                term = y[pl.ds(k, 1), :].reshape(buf.shape[2:]) * scale[r]
                buf[slot, k] = buf[slot, k] + term
                write(r).start()
            each(lo, hi, add)
            pending[slot] = pending[slot] + hi - lo

    jax.lax.fori_loop(0, groups, lambda g, c: group(g) or c, 0)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        drain(0, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def row_scatter_add(acc, token, offsets, y, scale, *,
                    interpret: bool = False):
    """``acc`` (T, sublanes, lanes) float32 (``as_tiles``) with ``scale[i]
    * y[i]`` added to row ``token[i]`` for every i under
    ``offsets[-1]``, written over ``acc``'s own buffer.  ``token`` (rows,) int32; ``y`` (rows, d)
    float32 as it is; ``scale`` (rows,) float32; ``offsets`` (g + 1,)
    int32 from 0 up: group e's rows are ``offsets[e]`` to ``offsets[e +
    1]``, and inside a group no token comes twice."""
    (rows, d), block = y.shape, block_rows(y.shape[0])
    def live_block(i, token, offsets, scale):
        # a block past the last live row is the last live block again:
        # the pipeline fetches nothing for it
        last = offsets[offsets.shape[0] - 1]
        return jnp.minimum(i, jnp.maximum(last - 1, 0) // block), 0

    vma = frozenset().union(*(jax.typeof(a).vma for a in
                              (acc, token, offsets, scale, y)))
    return pl.pallas_call(
        functools.partial(_kernel, block),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((block, d), live_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            grid=(rows // block,),
            scratch_shapes=[pltpu.VMEM((2, block, *acc.shape[1:]), acc.dtype),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((2,), jnp.int32)]),
        # the three prefetched tables count: ``acc`` comes after ``y``
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * y.size, transcendentals=0,
            bytes_accessed=3 * 4 * y.size),
        interpret=interpret,
        name="otpu_row_scatter_add",
    )(token, offsets, scale, y, acc)
