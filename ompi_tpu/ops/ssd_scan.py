"""Pallas kernels for Mamba-2's chunked state-space scan: a pass's
per-chunk work and its chunk-to-chunk recurrence in one call, forward and
backward, with a packed row's document resets and the skip term inside.

A Mamba-2 mixer's scan (``parallel/mamba._kernel_scan``, which
``mamba_mixer`` calls) runs here where Mosaic compiles (a TPU) and the
shape has tiles (``supported``); everywhere else it stays
``mamba.ssd_chunked``'s XLA form, which is these kernels' oracle and whose
docstring holds the equations and the four rules of a document's start.
Both kernels walk the chunks in their grid with the float32 state of
every held head in VMEM scratch, transposed ((n, heads x p): a head's
scalars then lie along lanes), as ``ops/gated_delta`` keeps the delta
rule's: no (chunk, chunk) array a head (the decay, ``c b^T`` times it)
goes to HBM, no (.., chunk, heads, p) copy of x is made, the running sum
of ``dt a`` inside a chunk is a product with a triangle of ones, and no
step of the recurrence is a launch.

- ``scan_forward``: grid (batch, chunk, tile of heads), the tile walked
  innermost so that what a chunk's heads share is made once and kept in
  scratch: the running sums by position and by head, a B/C group's ``c
  b^T``, the documents as a column.  A step takes the chunk's rows of x
  for the tile's heads, of B and C for their group (lane blocks of the
  arrays as the convolution left them, picked by the index maps) and of
  dt, and makes for each head the decay, ``(c b^T * decay) xd``, and for
  the tile at once the read of the entering state, the chunk's ``left``,
  the state's update and the skip term ``D x``; writes y and, for a
  backward pass, the state that entered each chunk.
- ``scan_backward``: the same grid from the last chunk to the first with
  the state's cotangent resident.  A step makes the chunk's matrices
  again, transposed ((j, i): every product is then plain or against a
  transposed right operand), from x, B, C, dt and the saved state, and
  writes dx, dB and dC (summed over a group's heads), d(dt)'s direct part,
  d(``dt a``) and the rows of D's gradient; ``a``'s gradient, the rest of
  dt's and the sums over positions are XLA's over (b, s, heads) arrays.

A chunk's (chunk, chunk) matrices are made a block of 128 x 128 at a
time and only on and under the diagonal (position i reads no j > i: three
blocks of four at a chunk of 256).  Two heads of 64 share a 128-lane
block: a head's product is made over the whole block and its half
selected, which costs the MXU what the half alone would (a 64-wide
result half-fills it either way) and slices no lane.

Every product is float32 at ``Precision.HIGHEST``; running sums,
exponentials and states are float32.  Every mask is a ``where`` over a
finite difference before the exponential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the tile's lanes, the kernels' VMEM limit, float32 products at the
#: highest precision and the small index helpers are the delta rule's
from ompi_tpu.ops.gated_delta import (LANES, VMEM_LIMIT, _NT, _TN, _at,
                                      _column, _dot)

#: positions times heads a grid step takes at most: a step's products
#: amortise its overhead, and what is unrolled stays a program Mosaic
#: compiles in seconds
STEP_HEAD_ROWS = 2048


def heads_a_step(chunk: int, p: int, r: int):
    """The heads of one B/C group a grid step takes: whole lane blocks of
    heads ``p`` wide, a divisor of the group's ``r`` heads, as many as
    keep ``chunk`` x heads within ``STEP_HEAD_ROWS``; None where a lane
    block's heads do not divide the group."""
    if p <= 0 or LANES % p or r % (LANES // p):
        return None
    per = LANES // p
    fits = [t for t in range(per, r + 1, per)
            if r % t == 0 and t * chunk <= max(STEP_HEAD_ROWS, per * chunk)]
    return max(fits)


def supported(chunk: int, p: int, n: int, heads: int, groups: int,
              s: int) -> bool:
    """Whether the kernels have tiles for a scan in chunks of ``chunk``
    positions over ``heads`` heads ``p`` wide in ``groups`` B/C groups
    with a state of ``n`` a channel, ``s`` positions long: a state as wide
    as a tile's lanes (a group's B is a lane block of the convolution's
    array), heads that fill lane blocks and divide into the groups, and a
    chunk of whole lane blocks, since a chunk's matrices are (chunk,
    chunk), of at most 512 (four such matrices a head in VMEM).  Any
    length: it is padded to whole chunks."""
    return not refusal(chunk, p, n, heads, groups, s)


def refusal(chunk: int, p: int, n: int, heads: int, groups: int,
            s: int) -> str:
    """Why the kernels have no tiles for such a scan ("": they have): the
    clause of ``supported`` that fails first."""
    if n != LANES:
        return f"a state of {n} a channel is not a tile's {LANES} lanes"
    if groups < 1 or heads % groups:
        return f"{heads} heads do not divide into {groups} B/C groups"
    if chunk % LANES or chunk > 4 * LANES:
        return (f"a chunk of {chunk} positions is not 1 to 4 lane blocks "
                f"of {LANES}")
    if s < 1:
        return "no position"
    if heads_a_step(chunk, p, heads // groups) is None:
        return (f"heads {p} wide do not fill lane blocks that divide a "
                f"group's {heads // groups} heads")
    return ""


def _spread(by_head, h0, tile, p):
    """(rows, tile x p) from a (rows, heads) block: head ``h0 + j``'s
    column along the ``p`` lanes of head j of the tile."""
    rows, per = by_head.shape[0], LANES // p
    lane = _at((rows, LANES), 1)
    blocks = []
    for first in range(0, tile, per):
        block = jnp.broadcast_to(_column(by_head, h0 + first), (rows, LANES))
        for m in range(1, per):
            block = jnp.where(lane >= m * p,
                              _column(by_head, h0 + first + m), block)
        blocks.append(block)
    return jnp.concatenate(blocks, axis=1)


def _gather(wide, h0, tile, p, heads):
    """(rows, heads) from a (rows, tile x p) block: the sum over head j's
    ``p`` lanes in column ``h0 + j``, zero in the other tiles' columns."""
    rows, per = wide.shape[0], LANES // p
    lane, col = _at((rows, LANES), 1), _at((rows, heads), 1)
    out = jnp.zeros((rows, heads), jnp.float32)
    for first in range(0, tile, per):
        block = wide[:, first * p:first * p + LANES]
        for m in range(per):
            mine = (lane >= m * p) & (lane < (m + 1) * p)
            out = jnp.where(col == h0 + first + m, jnp.sum(
                jnp.where(mine, block, 0.0), axis=1, keepdims=True), out)
    return out


def _chunk_sums(dt_ref, a_ref, cc_ref, cr_ref):
    """The running sum of ``dt a`` inside the chunk, by position (chunk,
    heads), a product with a triangle of ones, and the same numbers by
    head (block x heads, a block's positions: a row is a head's sums over
    one block of lanes), a product with the identity and so exact: a
    decay is ``exp`` of a difference of two of these, which has to be 0
    where i is j and near it where i is near j, however large the sums."""
    da = dt_ref[...] * a_ref[0:1, :]
    n, heads = da.shape
    row, col = _at((n, n), 0), _at((n, n), 1)
    cc_ref[...] = _dot((row >= col).astype(jnp.float32), da)
    by_head = _dot(cc_ref[...], (row == col).astype(jnp.float32), _TN)
    width, count = _blocks(n)
    for j in range(count):
        cr_ref[j * heads:(j + 1) * heads, :] = \
            by_head[:, j * width:(j + 1) * width]


def _doc_column(doc_ref, dcol_ref):
    """The chunk's documents down a column (every lane the same), from
    the row they come in: a product with the identity, exact for a
    document's number."""
    n = doc_ref.shape[1]
    eye = (_at((n, n), 0) == _at((n, n), 1)).astype(jnp.float32)
    dcol_ref[...] = _dot(eye, jnp.broadcast_to(
        doc_ref[0:1, :].astype(jnp.float32), (LANES, n)), _NT)


def _chunk_parts(cc_ref, doc_ref, dcol_ref):
    """Of the chunk, by position and head: (``exp`` of the running sum
    where the position reads the entering state, ``exp(last - sum)`` where
    it reaches the chunk's state, (1, heads) ``exp(last)`` where the
    entering state passes on)."""
    cum = cc_ref[...]
    n = cum.shape[0]
    last = cum[n - 1:n, :]
    reads, to_end, through = jnp.exp(cum), jnp.exp(last - cum), jnp.exp(last)
    if doc_ref is None:
        return reads, to_end, through
    drow = doc_ref[0:1, :].astype(jnp.float32)
    dcol = dcol_ref[:, 0:1]
    entering = _column(doc_ref[1:2, :].astype(jnp.float32), 0)
    first, end = _column(drow, 0), _column(drow, n - 1)
    reads = jnp.where(dcol == entering, reads, 0.0)
    to_end = jnp.where(dcol == end, to_end, 0.0)
    through = jnp.where((first == end) & (first == entering), through, 0.0)
    return reads, to_end, through


def _blocks(n):
    """(the width of a chunk's square blocks, their count): a chunk's
    (n, n) matrices are made a block of a tile's lanes at a time and only
    on and under the diagonal, since position i reads no j > i."""
    width = LANES if n % LANES == 0 else n
    return width, n // width


def _seen(n, transposed, doc_ref, dcol_ref):
    """Whether position i reads position j <= i, a block of a chunk's (n,
    n) matrix at a time, {(row block, column block): a mask, or None
    where every entry reads (off the diagonal, without documents)}: rows i
    and columns j, ``transposed`` rows j and columns i."""
    width, count = _blocks(n)
    row, col = _at((width, width), 0), _at((width, width), 1)
    seen = {}
    for r in range(count):
        for c in range(r + 1) if not transposed else range(r, count):
            mask = None
            if r == c:
                mask = row <= col if transposed else row >= col
            if doc_ref is not None:
                same = dcol_ref[r * width:(r + 1) * width, 0:1] == doc_ref[
                    0:1, c * width:(c + 1) * width].astype(jnp.float32)
                mask = same if mask is None else mask & same
            seen[r, c] = mask
    return seen


def _decay(seen, difference):
    """``exp`` of a block of differences of running sums where position i
    reads j."""
    if seen is None:
        return jnp.exp(difference)
    return jnp.exp(jnp.where(seen, difference, -jnp.inf))


def _fwd_kernel(tile, p, tiles_a_group, save, documents, skip, x_ref, b_ref,
                c_ref, dt_ref, a_ref, *rest):
    """One tile of heads of one chunk: the module's text."""
    doc_ref = rest[0] if documents else None
    y_ref = rest[documents]
    kept_ref = rest[documents + 1] if save else None
    s_ref, cc_ref, cr_ref, g_ref = rest[documents + 1 + save:][:4]
    dcol_ref = rest[-1] if documents else None
    t = pl.program_id(2)
    h0 = t * tile
    n, per = x_ref.shape[0], LANES // p
    width, count = _blocks(n)
    heads = dt_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[t] = jnp.zeros(s_ref.shape[1:], jnp.float32)

    @pl.when(t == 0)
    def _():
        _chunk_sums(dt_ref, a_ref, cc_ref, cr_ref)
        if documents:
            _doc_column(doc_ref, dcol_ref)

    @pl.when(t % tiles_a_group == 0)
    def _():
        g_ref[...] = _dot(c_ref[...], b_ref[...], _NT)      # (i, j)

    reads, to_end, through = _chunk_parts(cc_ref, doc_ref, dcol_ref)
    seen = _seen(n, False, doc_ref, dcol_ref)                # (i, j)
    x = x_ref[...]
    xd = x * _spread(dt_ref[...], h0, tile, p)
    state = s_ref[t]
    if save:
        kept_ref[...] = state
    lane = _at((n, LANES), 1)
    blocks = []
    for first in range(0, tile, per):
        pair = xd[:, first * p:first * p + LANES]
        block = None
        for m in range(per):
            h = h0 + first + m
            mine = []
            for i in range(count):
                at_i = slice(i * width, (i + 1) * width)
                c_col = _column(cc_ref[at_i, :], h)
                for j in range(i + 1):
                    at_j = slice(j * width, (j + 1) * width)
                    part = _dot(g_ref[at_i, at_j] * _decay(
                        seen[i, j], c_col - cr_ref[pl.ds(j * heads + h, 1), :]),
                        pair[at_j])
                    reads_i = part if j == 0 else reads_i + part
                mine.append(reads_i)
            mine = jnp.concatenate(mine, axis=0)
            block = mine if m == 0 else jnp.where(lane >= m * p, mine, block)
        blocks.append(block)
    y = jnp.concatenate(blocks, axis=1) \
        + _dot(c_ref[...], state) * _spread(reads, h0, tile, p)
    if skip:
        y += x * _spread(a_ref[1:2, :], h0, tile, p)
    y_ref[...] = y
    s_ref[t] = state * _spread(through, h0, tile, p) + _dot(
        b_ref[...], xd * _spread(to_end, h0, tile, p), _TN)


def _bwd_kernel(tile, p, tiles_a_group, tiles, documents, skip, x_ref, b_ref,
                c_ref, dt_ref, a_ref, *rest):
    """One tile of heads of one chunk, the last chunk first: the chunk's
    parts made again from the saved state, then the gradient of every
    product the forward kernel makes, the state's cotangent going from a
    chunk to the one before it in ``ds_ref``."""
    doc_ref = rest[0] if documents else None
    kept_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dda_ref = \
        rest[documents:documents + 7]
    dskip_ref = rest[documents + 7] if skip else None
    ds_ref, cc_ref, cr_ref, g_ref, dg_ref, rows_ref = \
        rest[documents + 7 + skip:][:6]
    dcol_ref = rest[-1] if documents else None
    t = pl.program_id(2)
    h0 = t * tile
    n, per = x_ref.shape[0], LANES // p
    width, count = _blocks(n)
    heads = dt_ref.shape[1]
    first_of_group = t % tiles_a_group == 0

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[t] = jnp.zeros(ds_ref.shape[1:], jnp.float32)

    @pl.when(t == 0)
    def _():
        _chunk_sums(dt_ref, a_ref, cc_ref, cr_ref)
        if documents:
            _doc_column(doc_ref, dcol_ref)
        for ref in (ddt_ref, dda_ref, rows_ref) + (dskip_ref,) * skip:
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    @pl.when(first_of_group)
    def _():
        g_ref[...] = _dot(b_ref[...], c_ref[...], _NT)      # (j, i)
        for ref in (dg_ref, db_ref, dc_ref):
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    reads, to_end, through = _chunk_parts(cc_ref, doc_ref, dcol_ref)
    seen = _seen(n, True, doc_ref, dcol_ref)                 # (j, i)
    x, dy, b, c = x_ref[...], dy_ref[...], b_ref[...], c_ref[...]
    state, d_state = kept_ref[...], ds_ref[t]
    dt_wide = _spread(dt_ref[...], h0, tile, p)
    to_end_wide = _spread(to_end, h0, tile, p)
    through_wide = _spread(through, h0, tile, p)
    xd = x * dt_wide
    dy_reads = dy * _spread(reads, h0, tile, p)
    xd_to_end = xd * to_end_wide
    from_state = _dot(c, state)                 # c S^T, (i, tile x p)
    to_state = _dot(b, d_state)                 # b dS^T, (j, tile x p)
    dc_ref[...] += _dot(dy_reads, state, _NT)
    db_ref[...] += _dot(xd_to_end, d_state, _NT)
    ds_ref[t] = through_wide * d_state + _dot(c, dy_reads, _TN)

    lane, head_row = _at((n, LANES), 1), _at((heads, n), 0)
    block_lane = _at((width, LANES), 1)
    d_g = {}
    rows = jnp.zeros((heads, n), jnp.float32)
    blocks = []
    for first in range(0, tile, per):
        cols = slice(first * p, first * p + LANES)
        block = None
        for m in range(per):
            h = h0 + first + m
            own = (block_lane >= m * p) & (block_lane < (m + 1) * p)
            mine, pulls = [], [0.0] * count
            for j in range(count):
                at_j = slice(j * width, (j + 1) * width)
                c_col = _column(cc_ref[at_j, :], h)
                for i in range(j, count):
                    at_i = slice(i * width, (i + 1) * width)
                    decay = _decay(seen[j, i],
                                   cr_ref[pl.ds(i * heads + h, 1), :] - c_col)
                    scores = g_ref[at_j, at_i] * decay       # M^T, (j, i)
                    part = _dot(scores, dy[at_i, cols])      # M^T dy
                    reads_j = part if i == j else reads_j + part
                    d_scores = _dot(jnp.where(own, xd[at_j, cols], 0.0),
                                    dy[at_i, cols], _NT)     # xd_j . dy_i
                    d_g[j, i] = d_g.get((j, i), 0.0) + d_scores * decay
                    pulls[i] += jnp.sum(d_scores * scores, axis=0,
                                        keepdims=True)
                mine.append(reads_j)
            # position i's pull on its running sum, a row a head
            rows = jnp.where(head_row == h, jnp.concatenate(pulls, axis=1),
                             rows)
            mine = jnp.concatenate(mine, axis=0)
            block = mine if m == 0 else jnp.where(lane >= m * p, mine, block)
        blocks.append(block)
    within = jnp.concatenate(blocks, axis=1)
    for (j, i), block in d_g.items():
        dg_ref[j * width:(j + 1) * width, i * width:(i + 1) * width] += block
    rows_ref[...] += rows
    d_xd = within + to_end_wide * to_state
    dx = d_xd * dt_wide
    if skip:
        dx += dy * _spread(a_ref[1:2, :], h0, tile, p)
        dskip_ref[...] += _gather(dy * x, h0, tile, p, heads)
    dx_ref[...] = dx
    ddt_ref[...] += _gather(d_xd * x, h0, tile, p, heads)
    # the running sum's gradient: what reads it as i less what reads it
    # as j (the column sums of d_scores * scores are xd . (M^T dy))
    d_cum = _gather(dy_reads * from_state - xd_to_end * to_state
                    - xd * within, h0, tile, p, heads)
    # the chunk's last position's: exp(last - sum) of every j, and the
    # chunk's decay of the entering state
    at_last = _gather(
        jnp.sum(xd_to_end * to_state, axis=0, keepdims=True)
        + jnp.sum(state * d_state, axis=0, keepdims=True) * through_wide,
        h0, tile, p, heads)
    dda_ref[...] += d_cum + jnp.where(_at((n, heads), 0) == n - 1, at_last,
                                      0.0)

    @pl.when(t % tiles_a_group == tiles_a_group - 1)
    def _():
        dc_ref[...] += _dot(dg_ref[...], b, _TN)
        db_ref[...] += _dot(dg_ref[...], c)

    @pl.when(t == tiles - 1)
    def _():
        row, col = _at((n, n), 0), _at((n, n), 1)
        eye = (row == col).astype(jnp.float32)
        # d(dt a) from its running sum's: the sum from a position to the
        # chunk's end
        dda_ref[...] = _dot((row <= col).astype(jnp.float32),
                            dda_ref[...] + _dot(eye, rows_ref[...], _NT))


def _laid_out(x, b, c, dt, doc, dy, chunk):
    """The kernels' operands from the scan's: the length padded to whole
    chunks (dt = 0 leaves the state as it is; the padding lies in the
    last document), and the documents a chunk, (bt, chunks, 2, chunk):
    each position's, and the one that enters the chunk, the chunk
    before's last position's (the first chunk's own first)."""
    bt, s, _ = dt.shape
    pad = -s % chunk
    rows = lambda t: t if t is None or not pad else jnp.pad(
        t, ((0, 0), (0, pad), (0, 0)))
    x, b, c, dt, dy = (rows(t) for t in (x, b, c, dt, dy))
    if doc is not None:
        dc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge").reshape(
            bt, -1, chunk)
        entering = jnp.concatenate([dc[:, :1, 0], dc[:, :-1, -1]], axis=1)
        doc = jnp.stack([dc, jnp.broadcast_to(entering[..., None], dc.shape)],
                        axis=2)
    return x, b, c, dt, doc, dy


def _specs(heads, groups, p, n, chunk, tile, order, at):
    """Block specs of the operands the two kernels share (x's tile of
    heads, its group's B and C from the lane blocks ``at``, dt, a, the
    documents), and those of (a tile's columns of a (b, s, heads x p)
    array, a group's of a (b, s, groups x n) one, a (b, s, heads) array,
    the saved states); ``order`` maps the grid's second index to the
    chunk it takes."""
    tiles_a_group = heads // groups // tile
    wide = lambda first: pl.BlockSpec(
        (None, chunk, tile * p), lambda z, c, t: (z, order(c), first + t))
    group = lambda first: pl.BlockSpec(
        (None, chunk, n),
        lambda z, c, t: (z, order(c), first + t // tiles_a_group))
    by_head = pl.BlockSpec((None, chunk, heads),
                           lambda z, c, t: (z, order(c), 0))
    a = pl.BlockSpec((2, heads), lambda z, c, t: (0, 0))
    docs = pl.BlockSpec((None, None, 2, chunk),
                        lambda z, c, t: (z, order(c), 0, 0))
    kept = pl.BlockSpec((None, None, n, tile * p),
                        lambda z, c, t: (z, order(c), 0, t))
    return [wide(at[0]), group(at[1]), group(at[2]), by_head, a], docs, (
        wide(0), group(0), by_head, kept)


def _by_head(a, skip):
    """(2, heads): ``a`` and the skip term's factors (zeros without)."""
    return jnp.stack([a, jnp.zeros_like(a) if skip is None else skip])


def _call(kernel, name, operands, grid, in_specs, out_specs, out_shapes,
          scratch, interpret):
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    return pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)
                        for shape in out_shapes),
        grid=grid, in_specs=in_specs, out_specs=tuple(out_specs),
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name,
    )(*operands)


def _scratch(heads, p, n, chunk, tile, documents, backward):
    """The state (or its cotangent) a tile, the running sums by position
    and by head, ``c b^T``; backward also its gradient and the heads'
    rows; under documents their column."""
    width, count = _blocks(chunk)
    shapes = [(heads // tile, n, tile * p), (chunk, heads),
              (count * heads, width), (chunk, chunk)]
    if backward:
        shapes += [(chunk, chunk), (heads, chunk)]
    return shapes + [(chunk, LANES)] * documents


@functools.partial(jax.jit, static_argnames=(
    "chunk", "p", "groups", "at", "tile", "states", "interpret"))
def scan_forward(x, b, c, dt, a, doc=None, skip=None, *, chunk: int, p: int,
                 groups: int = 1, at=(0, 0, 0), tile=None,
                 states: bool = False, interpret: bool = False):
    """The scan's output y (bt, s, heads x p) float32, with ``skip``
    (heads,) plus the skip term ``skip x``, for the ``heads`` heads of ``dt`` (bt, s, heads) and ``a``
    (heads,), ``p`` wide, in ``groups`` B/C groups, in chunks of ``chunk``
    positions, ``tile`` heads a grid step (``heads_a_step``).  x, b and c
    are arrays of (bt, s, .) with a head's ``p`` columns, a group's 128,
    side by side: x's heads start at lane block ``at[0]`` (of ``tile x
    p`` lanes) of ``x``, group g's B is block ``at[1] + g`` (of 128) of
    ``b``, its C block ``at[2] + g`` of ``c``, so one array [x | B | C]
    given three times is read where it lies.  ``doc`` (bt, s) int32 or
    None: ``mamba.ssd_chunked``'s.  With ``states`` also what
    ``scan_backward`` reads: the state that entered each chunk,
    transposed, (bt, chunks, 128, heads x p) (the chunks of the padded
    length)."""
    bt, s, heads = dt.shape
    n = LANES
    tile = tile or heads_a_step(chunk, p, heads // groups)
    x, b, c, dt, docs, _ = _laid_out(x, b, c, dt, doc, None, chunk)
    sp = dt.shape[1]
    in_specs, doc_spec, (wide, _, _, kept) = _specs(
        heads, groups, p, n, chunk, tile, lambda c: c, at)
    operands = (x, b, c, dt, _by_head(a, skip))
    if doc is not None:
        operands, in_specs = operands + (docs,), in_specs + [doc_spec]
    shapes = [(bt, sp, heads * p)]
    if states:
        shapes.append((bt, sp // chunk, n, heads * p))
    out = _call(
        functools.partial(_fwd_kernel, tile, p, heads // groups // tile,
                          states, doc is not None, skip is not None),
        "otpu_ssd_scan_fwd", operands, (bt, sp // chunk, heads // tile),
        in_specs, [wide, kept][:len(shapes)], shapes,
        _scratch(heads, p, n, chunk, tile, doc is not None, False),
        interpret)
    return (out[0][:, :s], out[1]) if states else out[0][:, :s]


@functools.partial(jax.jit, static_argnames=(
    "chunk", "p", "groups", "at", "tile", "interpret"))
def scan_backward(x, b, c, dt, a, doc, skip, kept, dy, *, chunk: int,
                  p: int, groups: int = 1, at=(0, 0, 0), tile=None,
                  interpret: bool = False):
    """(dx (bt, s, heads x p), db, dc (bt, s, groups x 128), d(dt) (bt, s,
    heads), da (heads,), dskip (heads,) or None) of ``scan_forward``'s y
    for its cotangent ``dy`` (bt, s, heads x p), from the scan's operands
    as ``scan_forward`` took them and the states it ``kept``
    (``scan_forward(..., states=True)``)."""
    bt, s, heads = dt.shape
    n = LANES
    tile = tile or heads_a_step(chunk, p, heads // groups)
    tiles = heads // tile
    x, b, c, dt_p, docs, dy = _laid_out(x, b, c, dt, doc,
                                        dy.astype(jnp.float32), chunk)
    sp = dt_p.shape[1]
    nc = sp // chunk
    in_specs, doc_spec, (wide, group, by_head, state) = _specs(
        heads, groups, p, n, chunk, tile, lambda c: nc - 1 - c, at)
    operands = (x, b, c, dt_p, _by_head(a, skip))
    if doc is not None:
        operands, in_specs = operands + (docs,), in_specs + [doc_spec]
    by_heads = 2 + (skip is not None)
    dx, db, dc, ddt, dda, *dskip = _call(
        functools.partial(_bwd_kernel, tile, p, heads // groups // tile,
                          tiles, doc is not None, skip is not None),
        "otpu_ssd_scan_bwd", operands + (kept, dy), (bt, nc, tiles),
        in_specs + [state, wide],
        [wide, group, group] + [by_head] * by_heads,
        [(bt, sp, heads * p)] + [(bt, sp, groups * n)] * 2
        + [(bt, sp, heads)] * by_heads,
        _scratch(heads, p, n, chunk, tile, doc is not None, True), interpret)
    dda = dda[:, :s]
    return (dx[:, :s], db[:, :s], dc[:, :s], ddt[:, :s] + dda * a,
            jnp.sum(dda * dt, axis=(0, 1)),
            jnp.sum(dskip[0], axis=(0, 1)) if dskip else None)
