"""Pallas grouped matmuls: rows sorted by group times each group's own
matrix, and the two transposed products its gradient needs.

The experts' matmuls of a public model's train step
(``parallel/experts._grouped_matmul``) run here where Mosaic compiles (a
TPU) and the inputs are bfloat16; everywhere else they stay
``lax.ragged_dot``.  The algorithm is megablox's, as JAX ships it
(``jax.experimental.pallas.ops.tpu.megablox``) and as XLA's own
``ragged-dot`` runs it on a TPU at fixed tiles of 512 x 512 x 256: a
grid step is one row tile under one group, the tiles a group touches
come from a table made outside the kernel from the group sizes, and a
tile that straddles two groups is visited once by each.  Kept here, and
not called there, for three things that module does not offer: a kernel
name a trace can be read by (``otpu_gmm*``), tiles and a VMEM limit
chosen from the shape, and the whole contraction in one grid step, so
no accumulator stands between the MXU and the result.

- ``gmm``: ``(m, k) x (g, k, n) -> (m, n)``, or with ``transpose_rhs``
  ``(m, k) x (g, n, k) -> (m, n)`` (the cotangent of the rows);
- ``tgmm``: ``(m, k), (m, n) -> (g, k, n)``, a group's weight gradient,
  zeros for an empty group; or, given a running sum ``acc`` (g, k, n),
  that sum with the gradient added in place: a group with no row is not
  visited and keeps what it held (megablox's ``existing_out``).

bfloat16 operands, float32 accumulation on the MXU, float32 results.
Rows past the last group belong to no tile: ``gmm`` leaves them as they
were in memory, as ``ragged-dot`` does, and ``tgmm`` does not read them.
Both are jitted, as the flash kernels are: a step's like products (gate
and up, one layer's and the next's) are traced and lowered once, and a
product's op in a trace is named by its kernel (``otpu_gmm.N``) whatever
transformation the caller stood under.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: what a call's double-buffered tiles may take of the v5e's 128 MiB of
#: VMEM (Mosaic's default is 16), and the limit handed to Mosaic
VMEM_TILES = 40 << 20
VMEM_LIMIT = 64 << 20
LANES = 128


#: rows a grid step takes.  On the v5e 256 was the fastest, or within 2% of
#: it, in all three forms at every shape the four model cells send, groups
#: of 200 rows (JoyAI's) as of 2,100 (LFM2's): a tile that straddles two
#: groups is computed once for each, so 512 loses 3-9% and 1,024 10-100%,
#: and 128 gains nothing (my chip run, PR 47: ``PERF.md`` section 6)
ROW_TILE = 256


def _widths(n: int) -> list:
    """The multiples of 128 that divide ``n``, widest first."""
    return [w for w in range(n, 0, -LANES) if n % w == 0 and w % LANES == 0]


def gmm_tiles(m: int, k: int, n: int):
    """(row tile, column tile) of ``gmm``, or None where this module has
    no kernel for the shape (``supported``): the contraction whole, and
    the widest column tile whose double-buffered blocks fit
    ``VMEM_TILES``: a narrower one reads the rows once more a tile, and
    at every shape measured the widest was the fastest."""
    tm = min(m, ROW_TILE)
    if m % tm or tm % 16 or k % LANES:
        return None
    for tn in _widths(n):
        if 2 * (tm * k * 2 + k * tn * 2 + tm * tn * 4) <= VMEM_TILES:
            return tm, tn
    return None


def tgmm_tiles(m: int, k: int, n: int, acc: bool = False):
    """(row tile, ``k`` tile, ``n`` tile) of ``tgmm``, or None: the rows
    are the contraction here, the result's (k, n) tile stays in VMEM
    while a group's row tiles add to it, and the tiles that fit and read
    the rows again least often are taken (whole matrices at the cells'
    shapes: 2,048 x 1,792 float32 twice is 29 MiB).  With ``acc`` the
    running sum's tile comes in beside the result's."""
    tm = min(m, ROW_TILE)
    if m % tm or tm % 16:
        return None
    best = None
    for tk in _widths(k):
        for tn in _widths(n):
            if 2 * (tm * tk * 2 + tm * tn * 2
                    + (1 + acc) * tk * tn * 4) > VMEM_TILES:
                continue
            reread = k * (n // tn) + n * (k // tk)
            if best is None or reread < best[0]:
                best = (reread, tm, tk, tn)
    return best and best[1:]


def refusal(m: int, k: int, n: int) -> str:
    """Why a grouped matmul ``(m, k) x (g, k, n)`` or one of its
    transposed products has no tiles ("": all three have): the clause of
    ``supported`` that fails first."""
    tm = min(m, ROW_TILE)
    if m % tm or tm % 16:
        return (f"{m} rows are no whole row tiles of {tm}" if m % tm
                else f"a row tile of {tm} rows is no whole sublane tiles "
                "of 16")
    for width in (k, n):
        if width % LANES:
            return f"a width of {width} is not a multiple of {LANES}"
    if not (gmm_tiles(m, k, n) and gmm_tiles(m, n, k)
            and tgmm_tiles(m, k, n)):
        return (f"no tiles of ({m}, {k}) x ({k}, {n}) fit "
                f"{VMEM_TILES >> 20} MiB of VMEM")
    return ""


def supported(m: int, k: int, n: int) -> bool:
    """Whether a grouped matmul ``(m, k) x (g, k, n)`` and both its
    transposed products have tiles."""
    return not refusal(m, k, n)


def group_tiles(sizes, m: int, tm: int, visit_empty: bool):
    """The table a kernel walks: (``offsets`` (g + 1,): the row a group
    starts at; ``group_ids`` and ``tile_ids`` (m / tm + g - 1,): grid
    step i is row tile ``tile_ids[i]`` under group ``group_ids[i]``), and
    the steps to run.  A group visits the tiles from the one its first
    row lies in to the one its last row lies in; an empty group none, or
    with ``visit_empty`` one (``tgmm`` writes its zeros there).  Tiles
    past the last group's are in no step."""
    g = sizes.shape[0]
    tiles_m, steps = m // tm, m // tm + g - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    empty = sizes == 0
    visits = jnp.where(empty, int(visit_empty),
                       (ends + tm - 1) // tm - starts // tm)
    # a tile is visited once by the group its first row belongs to, and
    # once more by every group that starts further down in it
    again = jnp.where(empty, visit_empty, starts % tm != 0)
    per_tile = 1 + jnp.sum(again & (starts // tm == jnp.arange(tiles_m)[:, None]),
                           axis=1)

    def repeated(counts):
        """0 ``counts[0]`` times, then 1 ``counts[1]`` times, ..."""
        step = jnp.arange(steps, dtype=jnp.int32)[:, None]
        return jnp.minimum(jnp.sum(jnp.cumsum(counts) <= step, axis=1),
                           counts.shape[0] - 1).astype(jnp.int32)

    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return ((offsets.astype(jnp.int32), repeated(visits), repeated(per_tile)),
            jnp.sum(visits))


def _rows_of_group(offsets, group, tile, tm):
    """(whether row tile ``tile`` lies whole inside ``group``, a function
    of a shape (tm, width): the mask of the tile's rows that do)."""
    first, end = offsets[group], offsets[group + 1]

    def mine(shape):
        rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        return (rows >= first) & (rows < end)

    return (first <= tile * tm) & ((tile + 1) * tm <= end), mine


def _gmm_kernel(tm, dims, offsets, group_ids, tile_ids, lhs, rhs, out):
    i = pl.program_id(1)
    whole, mine = _rows_of_group(offsets, group_ids[i], tile_ids[i], tm)
    prod = jax.lax.dot_general(lhs[...], rhs[...], dims,
                               preferred_element_type=jnp.float32)

    @pl.when(whole)
    def _():
        out[...] = prod

    @pl.when(jnp.logical_not(whole))
    def _():
        out[...] = jnp.where(mine(out.shape), prod, out[...])


@functools.partial(jax.jit,
                   static_argnames=("transpose_rhs", "tiles", "interpret"))
def gmm(lhs, rhs, sizes, *, transpose_rhs: bool = False, tiles=None,
        interpret: bool = False):
    """Group e's rows of ``lhs`` (m, k), the ``sizes[e]`` after those of
    the groups before it, times ``rhs[e]`` (k, n), or its transpose where
    ``rhs`` is (g, n, k) and ``transpose_rhs``: (m, n) float32."""
    m, k = lhs.shape
    g, n = rhs.shape[0], rhs.shape[1 if transpose_rhs else 2]
    tm, tn = tiles or gmm_tiles(m, k, n)
    table, steps = group_tiles(sizes, m, tm, visit_empty=False)
    if transpose_rhs:
        dims = (((1,), (1,)), ((), ()))
        rhs_spec = pl.BlockSpec(
            (None, tn, k), lambda j, i, off, gid, tid: (gid[i], j, 0))
    else:
        dims = (((1,), (0,)), ((), ()))
        rhs_spec = pl.BlockSpec(
            (None, k, tn), lambda j, i, off, gid, tid: (gid[i], 0, j))
    vma = jax.typeof(lhs).vma | jax.typeof(rhs).vma | jax.typeof(sizes).vma
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm, dims),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec(
                (tm, k), lambda j, i, off, gid, tid: (tid[i], 0)), rhs_spec],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, i, off, gid, tid: (tid[i], j)),
            grid=(n // tn, steps)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=2 * m * k * (n // tn) + 2 * g * k * n + 4 * m * n),
        interpret=interpret,
        name="otpu_gmm_nt" if transpose_rhs else "otpu_gmm",
    )(*table, lhs, rhs)


def _tgmm_kernel(tm, offsets, group_ids, tile_ids, lhs, rhs, *acc_out):
    *acc, out = acc_out
    i = pl.program_id(2)
    group = group_ids[i]
    whole, mine = _rows_of_group(offsets, group, tile_ids[i], tm)
    dims = (((0,), (0,)), ((), ()))
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=jnp.float32)

    @pl.when((i == 0) | (group_ids[jnp.maximum(i - 1, 0)] != group))
    def _():
        out[...] = acc[0][...] if acc else jnp.zeros_like(out)

    @pl.when(whole)
    def _():
        out[...] += dot(lhs[...], rhs[...])

    # a tile the group shares: the other groups' rows, and what lies past
    # the last group (anything, NaN too), are cut off on both sides
    @pl.when(jnp.logical_not(whole) & (offsets[group + 1] > offsets[group]))
    def _():
        cut = lambda x: jnp.where(mine(x.shape), x[...].astype(jnp.float32),
                                  0.0).astype(x.dtype)
        out[...] += dot(cut(lhs), cut(rhs))


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def tgmm(lhs, rhs, sizes, acc=None, *, tiles=None, interpret: bool = False):
    """A group's ``lhs`` rows (m, k) transposed times its ``rhs`` rows
    (m, n): (g, k, n) float32, zeros where a group has no row.  With
    ``acc`` (g, k, n) float32 the result is ``acc`` plus that, written
    over ``acc``'s own buffer: a group's first visit starts from what is
    there, and a group with no row is not visited at all, so a call costs
    by the groups that have rows and not by ``g``."""
    (m, k), n, g = lhs.shape, rhs.shape[1], sizes.shape[0]
    tm, tk, tn = tiles or tgmm_tiles(m, k, n, acc is not None)
    table, steps = group_tiles(sizes, m, tm, visit_empty=acc is None)
    vma = jax.typeof(lhs).vma | jax.typeof(rhs).vma | jax.typeof(sizes).vma
    out_spec = pl.BlockSpec(
        (None, tk, tn), lambda j, c, i, off, gid, tid: (gid[i], c, j))
    sums = () if acc is None else (acc,)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm),
        out_shape=jax.ShapeDtypeStruct((g, k, n), jnp.float32, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, c, i, off, gid, tid: (tid[i], c)),
                pl.BlockSpec((tm, tn),
                             lambda j, c, i, off, gid, tid: (tid[i], j)),
                *[out_spec] * len(sums)],
            out_specs=out_spec,
            grid=(n // tn, k // tk, steps)),
        # the three table operands count: ``acc`` is the sixth
        input_output_aliases={5: 0} if sums else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(2 * m * k * (n // tn) + 2 * m * n * (k // tk)
                            + 4 * g * k * n)),
        interpret=interpret,
        name="otpu_gmm_t",
    )(*table, lhs, rhs, *sums)
