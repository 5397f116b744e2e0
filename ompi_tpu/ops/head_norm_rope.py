"""Pallas kernels for a head's way from its projection's product to the
flash kernels' operand: per-head RMSNorm with a gain where the head has
one, RoPE in the half-split form, the head split and the cast, one pass
over the array forward and one backward.

Grouped-query attention's q and k (``parallel/attention.normed_qk``,
which ``gqa_attention``'s ``layer_types`` branch and ``dsa.dsa_attention``
call under ``otpu_attn_proj``) run here where Mosaic compiles (a TPU),
RoPE turns the layer and a head is whole tiles of 128 lanes, turned
whole, with no gate behind it (``supported``): the Qwen3-MoE form with
its per-head QK-norm (SDAR, Keye) and the form that is turned and not
normed (``gain`` None: Ouro, SmallThinker's window layers).  Everywhere
else they stay ``attention.normed_turned_heads``, the ``jnp`` lines that
are these kernels' oracle.  XLA makes of the normed lines five fusions
with HBM between them (the transposed float32 heads, the normed heads,
RoPE's ``[-x2, x1]``, the turned heads, the cast: 43 ms of SDAR's 420 ms
step on the v5e, every one a pass over a (32, 16384, 128) array; PR 64's
scope table); a kernel that holds a tile of one head's rows in VMEM
reads the product once and writes the operand once.

- ``heads_forward``: grid (batch, row tile, head), the heads innermost so
  that a row tile's cos / sin block is fetched once for all of them.  A step
  reads ``rows`` positions of one head's ``hd`` lanes of the product
  **where it lies**, (b, s, n x hd) float32: the head split is the
  block's address, and no transposed copy exists.  Per row the mean
  square (a float32 sum over the lanes on the VPU / XLU: no MXU product,
  so no question of its precision), the scale by ``rsqrt(. + eps)`` and
  the gain, then ``y cos + partner(y) sin`` with the partner ``[-y2,
  y1]`` a lane rotation by ``hd / 2`` inside the tile against a sine
  table whose first half carries the sign (a negation is exact); writes
  (b, n, s, hd) in the operand's dtype.
- ``heads_backward``: the same grid, every axis in order, because the
  gain's gradient is one (8, hd) block that every step adds to.  A step reads
  the cotangent (b, n, s, hd) and the product, un-turns (the rotation is
  its own transpose: ``dy = do cos + partner'(do sin)``), makes the norm
  again from the product, applies its transpose, writes the product's
  cotangent (b, s, n x hd) where the matmuls' transposes read it, and
  adds ``sum_rows dy x rsqrt(.)`` to the gain's sums.  Nothing but (the
  product, the gain, the tables) is kept from the forward pass.
- A head without a gain (``gain`` None) is the same algorithm with the
  norm's step absent, on the same grid, tiles and pieces
  (``otpu_head_rope_fwd`` / ``_bwd``): forward ``x cos + partner(x) sin``;
  backward ``dx = do cos + partner'(do sin)`` from the cotangent and the
  tables **alone** (the turn is linear: nothing of the product is kept or
  read), and with no gain's sums there is no shared block, so every axis
  is ``parallel``.  XLA makes of these lines four fusions (the transposed
  float32 heads, ``[-x2, x1]``, the turned heads, the cast): 3.5 ms a
  layer and pass over SmallThinker's 67 M entries of q and k on the v5e
  (``PERF.md`` section 5, PR 56's scope table).

Inside a step the tile is walked in pieces of ``SUB_ROWS`` rows, written
out one after the other.  On the v5e, at (1, 16384, 32 x 128) with the
tables made in the same program, forward takes 0.75 ms and backward 0.92
where the lines take 4.19 and 5.49 and the HBM's rate allows 0.49 and
0.65 (host clock over 20 calls, launch floor 0.19 ms; PR 65's probe).

All arithmetic is float32, in the order of ``layers.rmsnorm_gain`` and
``layers.rope``: ``((x * rsqrt(mean(x x) + eps)) * gain) * cos +
partner * sin``, and ``x * cos + partner * sin`` without a gain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: a bfloat16 tile's sublanes: the rows a tile is a multiple of
SUBLANES = 16
#: positions a grid step takes of one head (1 MiB of float32 at a head of
#: 128: a step's overhead is a tenth of its traffic's time), where the
#: length has them
ROWS = 2048
#: rows of the pieces a step is walked in, one after the other and
#: unrolled: a loop of pieces of 64 rows took 1.66 ms forward at (1, 16384,
#: 4096) on the v5e, of 128 rows 0.92, four pieces of 512 written out 0.75
#: (0.49 is the HBM's rate; PR 65's probe)
SUB_ROWS = 512
VMEM_LIMIT = 64 << 20


def refusal(hd: int, rotary_width: int | None, gated: bool) -> str:
    """Why the kernels have no tiles for heads of ``hd`` ("": they have):
    the clause of ``supported`` that fails first."""
    if hd % LANES:
        return f"head width {hd} is not a multiple of {LANES}"
    if rotary_width not in (None, hd):
        return (f"RoPE turns {rotary_width} of a head's {hd} entries: the "
                "partner is no rotation of the tile")
    if gated:
        return "a gate stands behind every query head in the product"
    return ""


def supported(hd: int, rotary_width: int | None, gated: bool) -> bool:
    """Whether the kernels have tiles for heads of ``hd``: whole tiles of
    128 lanes, turned whole (``rotary_width`` None or ``hd``: the partner
    is one rotation of the tile), no gate behind the head in the
    product."""
    return not refusal(hd, rotary_width, gated)


def row_tile(s: int) -> int:
    """The positions a grid step takes of ``s``: ``ROWS``, or all of a
    shorter length in whole sublane tiles."""
    return min(ROWS, -(-s // SUBLANES) * SUBLANES)


def signed_sin(sin):
    """``sin`` (s, hd) with its first half negated: ``partner(y) * sin``
    with ``partner(y) = [-y2, y1]`` is ``roll(y, hd / 2) * signed_sin``."""
    half = sin.shape[-1] // 2
    return jnp.concatenate([-sin[:, :half], sin[:, half:]], axis=-1)


def _sub_rows(rows: int) -> int:
    """The rows of a piece of a tile of ``rows``: the most whole sublane
    tiles up to ``SUB_ROWS`` that divide it."""
    return max(n for n in range(SUBLANES, SUB_ROWS + 1, SUBLANES)
               if rows % n == 0)


def _fwd_kernel(eps, sub, x_ref, g_ref, cos_ref, sin_ref, o_ref):
    """One tile of rows of one head: the module's text."""
    rows, hd = x_ref.shape
    g = g_ref[...]
    for r0 in range(0, rows, sub):
        at = slice(r0, r0 + sub)
        x = x_ref[at, :]
        y = x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) / hd + eps) * g
        o_ref[at, :] = (y * cos_ref[at, :] + pltpu.roll(y, hd // 2, 1)
                        * sin_ref[at, :]).astype(o_ref.dtype)


def _bwd_kernel(eps, sub, x_ref, g_ref, cos_ref, sin_ref, do_ref, dx_ref,
                dg_ref):
    """One tile of rows of one head; the gain's gradient goes to the one
    block every step shares, eight partial sums a lane."""
    rows, hd = x_ref.shape
    g = g_ref[...]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
             & (pl.program_id(2) == 0))
    def _():
        dg_ref[...] = jnp.zeros(dg_ref.shape, jnp.float32)

    sums = jnp.zeros((8, hd), jnp.float32)
    for r0 in range(0, rows, sub):
        at = slice(r0, r0 + sub)
        x = x_ref[at, :]
        do = do_ref[at, :].astype(jnp.float32)
        dy = do * cos_ref[at, :] + pltpu.roll(do * sin_ref[at, :],
                                              hd // 2, 1)
        r = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) / hd + eps)
        xn = x * r
        dn = dy * g
        dx_ref[at, :] = (r * (dn - xn * (jnp.sum(
            dn * xn, axis=-1, keepdims=True) / hd))).astype(dx_ref.dtype)
        both = dy * xn
        sums = sums + sum(both[n:n + 8] for n in range(0, sub, 8))
    dg_ref[...] += sums


def _turn_fwd_kernel(sub, x_ref, cos_ref, sin_ref, o_ref):
    """One tile of rows of one head that has no gain: ``_fwd_kernel``
    without the norm."""
    rows, hd = x_ref.shape
    for r0 in range(0, rows, sub):
        at = slice(r0, r0 + sub)
        x = x_ref[at, :]
        o_ref[at, :] = (x * cos_ref[at, :] + pltpu.roll(x, hd // 2, 1)
                        * sin_ref[at, :]).astype(o_ref.dtype)


def _turn_bwd_kernel(sub, cos_ref, sin_ref, do_ref, dx_ref):
    """One tile of rows of one head that has no gain: the un-turn of
    ``_bwd_kernel`` alone, from the cotangent and the tables."""
    rows, hd = do_ref.shape
    for r0 in range(0, rows, sub):
        at = slice(r0, r0 + sub)
        do = do_ref[at, :].astype(jnp.float32)
        dx_ref[at, :] = (do * cos_ref[at, :] + pltpu.roll(
            do * sin_ref[at, :], hd // 2, 1)).astype(dx_ref.dtype)


def _padded(a, axis, rows):
    """``a`` with ``axis`` padded with zeros to whole row tiles (rows whose
    norm is of nothing and whose gradient is nothing)."""
    pad = -a.shape[axis] % rows
    if not pad:
        return a
    return jnp.pad(a, [(0, pad if n == axis else 0) for n in range(a.ndim)])


def _specs(rows, hd):
    """(the product's, the operand's, the gain's, a table's) blocks over
    the grid (batch, row tile, head)."""
    return (pl.BlockSpec((None, rows, hd), lambda z, i, h: (z, i, h)),
            pl.BlockSpec((None, None, rows, hd),
                         lambda z, i, h: (z, h, i, 0)),
            pl.BlockSpec((1, hd), lambda z, i, h: (0, 0)),
            pl.BlockSpec((rows, hd), lambda z, i, h: (i, 0)))


def _call(kernel, name, operands, grid, in_specs, out_specs, out_shapes,
          order, interpret):
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    return pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(shape, dtype, vma=vma)
                        for shape, dtype in out_shapes),
        grid=grid, in_specs=in_specs, out_specs=tuple(out_specs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=order, vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name,
    )(*operands)


def heads_forward(x, gain, cos, sin, *, heads: int, eps: float, dtype,
                  interpret: bool = False):
    """``rope(rmsnorm_gain(heads of x, gain, eps))`` (b, heads, s, hd) in
    ``dtype`` of the product x (b, s, heads x hd) float32, the gain (hd,)
    and the angles' tables cos and ``signed_sin`` (s, hd) float32; with
    ``gain`` None, ``rope(heads of x)``."""
    b, s, width = x.shape
    hd = width // heads
    rows = row_tile(s)
    x, cos, sin = _padded(x, 1, rows), _padded(cos, 0, rows), \
        _padded(sin, 0, rows)
    sp = x.shape[1]
    lying, split, one, table = _specs(rows, hd)
    grid, out = (b, sp // rows, heads), [((b, heads, sp, hd), dtype)]
    if gain is None:
        (o,) = _call(
            functools.partial(_turn_fwd_kernel, _sub_rows(rows)),
            "otpu_head_rope_fwd", (x, cos, sin), grid,
            [lying, table, table], [split], out,
            ("parallel", "parallel", "arbitrary"), interpret)
        return o[:, :, :s]
    (o,) = _call(
        functools.partial(_fwd_kernel, eps, _sub_rows(rows)),
        "otpu_head_norm_rope_fwd", (x, gain.reshape(1, hd), cos, sin),
        grid, [lying, one, table, table], [split], out,
        ("parallel", "parallel", "arbitrary"), interpret)
    return o[:, :, :s]


def heads_backward(x, gain, cos, sin, do, *, eps: float,
                   dtype=jnp.float32, interpret: bool = False):
    """(dx (b, s, heads x hd) in ``dtype``, dgain (hd,) float32) of
    ``heads_forward``'s result for its cotangent ``do`` (b, heads, s, hd),
    from x, the gain and the tables as ``heads_forward`` took them; dgain is
    summed over the batch, the positions and the heads in float32.  With
    ``gain`` None x is not read (None will do) and dgain is None."""
    b, heads, s, hd = do.shape
    rows = row_tile(s)
    do = _padded(do, 2, rows)
    cos, sin = _padded(cos, 0, rows), _padded(sin, 0, rows)
    sp = do.shape[2]
    grid, dx_shape = (b, sp // rows, heads), ((b, sp, heads * hd), dtype)
    lying, split, one, table = _specs(rows, hd)
    if gain is None:
        (dx,) = _call(
            functools.partial(_turn_bwd_kernel, _sub_rows(rows)),
            "otpu_head_rope_bwd", (cos, sin, do), grid,
            [table, table, split], [lying], [dx_shape],
            ("parallel", "parallel", "parallel"), interpret)
        return dx[:, :s], None
    x = _padded(x, 1, rows)
    dx, dg = _call(
        functools.partial(_bwd_kernel, eps, _sub_rows(rows)),
        "otpu_head_norm_rope_bwd", (x, gain.reshape(1, hd), cos, sin, do),
        grid, [lying, one, table, table, split],
        [lying, pl.BlockSpec((8, hd), lambda z, i, h: (0, 0))],
        [dx_shape, ((8, hd), jnp.float32)],
        ("arbitrary", "arbitrary", "arbitrary"), interpret)
    return dx[:, :s], jnp.sum(dg, axis=0)
