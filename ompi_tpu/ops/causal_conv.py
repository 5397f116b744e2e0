"""Pallas kernels for a causal depthwise convolution and its silu: one
pass over the array forward and one backward.

A Gated DeltaNet layer's convolution (``parallel/gdn._kernel_conv``,
which ``gated_delta_net`` calls under ``otpu_gdn_conv``) runs here where
Mosaic compiles (a TPU) and the shape has tiles (``supported``);
everywhere else it stays the lines in ``gated_delta_net``, which are
these kernels' oracle.  It is elementwise but for a shift of ``taps - 1``
rows, ``y_t = silu(sum_j w_j x_{t - (taps - 1) + j})`` a channel, zeros
before the sequence's start, never reset inside a row.  XLA reads a
padded copy of x once a tap and again for silu's derivative, and makes
the taps' gradient in a pass of its own (64 ms of a 692 ms step on the
v5e for 14 ms of traffic, PR 52's table); a kernel that holds a tile of
rows in VMEM reads and writes each array once.

- ``conv_forward``: grid (batch, channel block, row tile), the rows
  innermost and in order.  A step takes ``rows`` positions by ``lanes``
  channels of x, carries the tile's last sublane tile of rows to the next
  step in VMEM scratch (zeros at a row's first tile: no padded copy of x
  exists), shifts along the sublanes in VMEM and writes the tile of y.
- ``conv_backward``: grid (channel block, batch, row tile), the tiles
  walked from the last to the first: dx at t needs the pre-activation's
  gradient at t + 1 .. t + taps - 1, so the carry goes the other way.  A
  step makes the pre-activation again from x (the rows before the tile
  come through a second, one-sublane-tile view of x), ``dpre = dy
  silu'(pre)``, writes dx, and adds its part of ``dw_j = sum_t dpre_t
  x_{t - (taps - 1) + j}`` to the channel block's sums in VMEM scratch,
  which go out once, at the block's last step.  Nothing but (x, w) is
  kept from the forward pass.

Inside a step the tile is walked in pieces of ``SUB_ROWS`` rows by one
tile's 128 lanes, whose parts all fit the vector registers: a tile
taken whole would put every intermediate array through VMEM.  So
walked, both kernels run at the HBM's rate on the v5e whatever the tile
(1.6 and 2.4 ms at (1, 16384, 8192), a plain pass over the array 1.7;
PR 54).

All arithmetic is float32; silu is ``x * sigmoid(x)``, as
``jax.nn.silu`` writes it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: a float32 tile's sublanes: the rows a step carries to the next, so
#: ``taps - 1`` may not pass it
HALO = 8
#: positions and channels a grid step takes (2 MiB of float32: a step's
#: overhead is a twentieth of its traffic's time), where the shape has them
ROWS, BLOCK_LANES = 512, 1024
#: rows of the pieces a step is walked in
SUB_ROWS = 64
VMEM_LIMIT = 64 << 20


def row_tile(s: int) -> int:
    """The positions a grid step takes of ``s``: ``ROWS``, or all of a
    shorter length in whole sublane tiles."""
    return min(ROWS, -(-s // HALO) * HALO)


def lane_block(c: int) -> int:
    """The channels a grid step takes of ``c``: the widest multiple of a
    tile's lanes up to ``BLOCK_LANES`` that divides them."""
    return max(n for n in range(LANES, BLOCK_LANES + 1, LANES) if c % n == 0)


def supported(taps: int, c: int, s: int) -> bool:
    """Whether the kernels have tiles for ``taps`` taps over ``c``
    channels and ``s`` positions: channels in whole tiles of 128 lanes,
    the ``taps - 1`` rows a step carries no more than one sublane tile.
    Any length: it is padded to whole row tiles (``row_tile``)."""
    return not refusal(taps, c, s)


def refusal(taps: int, c: int, s: int) -> str:
    """Why the kernels have no tiles for such a convolution ("": they
    have): the clause of ``supported`` that fails first."""
    if c % LANES:
        return f"{c} channels are no whole lane tiles of {LANES}"
    if not 1 <= taps <= HALO + 1:
        return (f"{taps} taps: a step carries at most one sublane tile's "
                f"{HALO} rows")
    if s < 1:
        return "no position"
    return ""


def _sub_rows(rows: int) -> int:
    return max(n for n in range(HALO, SUB_ROWS + 1, HALO) if rows % n == 0)


def _shifted(window, taps):
    """``[x_{t - d} for d in range(taps)]`` of a window whose first
    ``HALO`` rows lie before the piece's."""
    return [(pltpu.roll(window, d, 0) if d else window)[HALO:]
            for d in range(taps)]


def _pre(shifted, w, taps):
    """The pre-activation: tap j reads ``taps - 1 - j`` rows back."""
    return sum(shifted[taps - 1 - j] * w[j:j + 1] for j in range(taps))


def _lane_blocks(width, body):
    """``body(cols)`` a tile's 128 lanes at a time."""
    def one(n, carry):
        body(pl.ds(pl.multiple_of(n * LANES, LANES), LANES))
        return carry

    jax.lax.fori_loop(0, width // LANES, one, None)


def _fwd_kernel(taps, sub, x_ref, w_ref, y_ref, carry_ref):
    """One tile of rows of one channel block: the module's text."""
    rows, width = x_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros(carry_ref.shape, jnp.float32)

    def piece(cols):
        w = w_ref[:, cols]
        for r0 in range(0, rows, sub):
            if r0:
                window = x_ref[r0 - HALO:r0 + sub, cols]
            else:
                window = jnp.concatenate(
                    [carry_ref[:, cols], x_ref[:sub, cols]], axis=0)
            pre = _pre(_shifted(window, taps), w, taps)
            y_ref[r0:r0 + sub, cols] = pre * jax.nn.sigmoid(pre)
        carry_ref[:, cols] = x_ref[rows - HALO:, cols]

    _lane_blocks(width, piece)


def _bwd_kernel(taps, sub, x_ref, before_ref, w_ref, dy_ref, dx_ref, dw_ref,
                carry_ref, sums_ref):
    """One tile of rows of one channel block, the last piece first: the
    pre-activation's gradient goes from a piece to the one before it as a
    value, from a tile to the one before it in ``carry_ref``."""
    rows, width = x_ref.shape
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        carry_ref[...] = jnp.zeros(carry_ref.shape, jnp.float32)

    @pl.when((step == 0) & (pl.program_id(1) == 0))
    def _():
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.float32)

    def piece(cols):
        w = w_ref[:, cols]
        after = carry_ref[:, cols]
        sums = [jnp.zeros((HALO, LANES), jnp.float32)] * taps
        for r0 in reversed(range(0, rows, sub)):
            if r0:
                window = x_ref[r0 - HALO:r0 + sub, cols]
            else:
                # nothing lies before the sequence's first tile, the
                # walk's last
                before = jnp.where(step == steps - 1, 0.0,
                                   before_ref[:, cols])
                window = jnp.concatenate([before, x_ref[:sub, cols]], axis=0)
            shifted = _shifted(window, taps)
            pre = _pre(shifted, w, taps)
            sig = jax.nn.sigmoid(pre)
            dpre = dy_ref[r0:r0 + sub, cols] * (sig * (1 + pre * (1 - sig)))
            ahead = jnp.concatenate([dpre, after], axis=0)
            # tap j's reader of x_t is the position taps - 1 - j ahead
            dx_ref[r0:r0 + sub, cols] = sum(
                (pltpu.roll(ahead, sub + HALO - d, 0) if d else ahead)[:sub]
                * w[taps - 1 - d:taps - d] for d in reversed(range(taps)))
            for j in range(taps):
                both = dpre * shifted[taps - 1 - j]
                sums[j] = sums[j] + sum(both[n:n + HALO]
                                        for n in range(0, sub, HALO))
            after = dpre[:HALO]
        carry_ref[:, cols] = after
        for j in range(taps):
            sums_ref[j * HALO:(j + 1) * HALO, cols] += sums[j]

    _lane_blocks(width, piece)

    @pl.when((step == steps - 1) & (pl.program_id(1) == pl.num_programs(1) - 1))
    def _():
        for j in range(taps):
            dw_ref[j:j + 1, :] = jnp.sum(
                sums_ref[j * HALO:(j + 1) * HALO, :], axis=0, keepdims=True)


def _laid_out(arrays, rows):
    """The (b, s, c) ``arrays`` with the length padded with zeros to whole
    row tiles (rows that read nothing and whose gradient is nothing)."""
    pad = -arrays[0].shape[1] % rows
    if pad:
        arrays = [jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in arrays]
    return arrays


def _call(kernel, name, operands, grid, in_specs, out_specs, out_shapes,
          scratch_shapes, order, interpret):
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    return pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)
                        for shape in out_shapes),
        grid=grid, in_specs=in_specs, out_specs=tuple(out_specs),
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                        for shape in scratch_shapes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=order, vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("rows", "lanes", "interpret"))
def conv_forward(x, w, *, rows=None, lanes=None, interpret: bool = False):
    """``silu(sum_j w[j] x[t - (taps - 1) + j])`` (b, s, c) float32 of x
    (b, s, c) and the taps w (taps, c), both float32, the last tap on the
    position itself and zeros before the sequence's start; ``rows``
    positions by ``lanes`` channels a grid step (``row_tile``,
    ``lane_block``)."""
    b, s, c = x.shape
    taps = w.shape[0]
    rows, lanes = rows or row_tile(s), lanes or lane_block(c)
    (x,) = _laid_out([x], rows)
    sp = x.shape[1]
    tile = pl.BlockSpec((None, rows, lanes), lambda z, n, i: (z, i, n))
    (y,) = _call(
        functools.partial(_fwd_kernel, taps, _sub_rows(rows)),
        "otpu_gdn_conv_fwd", (x, w), (b, c // lanes, sp // rows),
        [tile, pl.BlockSpec((taps, lanes), lambda z, n, i: (0, n))], [tile],
        [(b, sp, c)], [(HALO, lanes)],
        ("parallel", "parallel", "arbitrary"), interpret)
    return y[:, :s]


@functools.partial(jax.jit, static_argnames=("rows", "lanes", "interpret"))
def conv_backward(x, w, dy, *, rows=None, lanes=None,
                  interpret: bool = False):
    """(dx (b, s, c), dw (taps, c)) of ``conv_forward``'s result for its
    cotangent ``dy`` (b, s, c), from x and w as ``conv_forward`` took
    them; dw is summed over the batch and the positions."""
    b, s, c = x.shape
    taps = w.shape[0]
    rows, lanes = rows or row_tile(s), lanes or lane_block(c)
    x, dy = _laid_out([x, dy.astype(jnp.float32)], rows)
    sp = x.shape[1]
    steps, halos = sp // rows, rows // HALO
    tile = pl.BlockSpec((None, rows, lanes),
                        lambda n, z, i: (z, steps - 1 - i, n))
    before = pl.BlockSpec(
        (None, HALO, lanes),
        lambda n, z, i: (z, jnp.maximum((steps - 1 - i) * halos - 1, 0), n))
    taps_block = pl.BlockSpec((taps, lanes), lambda n, z, i: (0, n))
    dx, dw = _call(
        functools.partial(_bwd_kernel, taps, _sub_rows(rows)),
        "otpu_gdn_conv_bwd", (x, x, w, dy), (c // lanes, b, steps),
        [tile, before, taps_block, tile], [tile, taps_block],
        [(b, sp, c), (taps, c)], [(HALO, lanes), (taps * HALO, lanes)],
        ("parallel", "arbitrary", "arbitrary"), interpret)
    return dx[:, :s], dw
