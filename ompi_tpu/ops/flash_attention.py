"""Pallas flash-attention kernels: ring attention's block update, and
causal attention's forward pass and backward block pair.

Ring attention (``ompi_tpu/parallel/model.py``) rotates K/V shards around
the sequence-parallel mesh axis with ``ppermute`` and, per step, combines
one K/V block into a running (max, numerator, denominator) softmax state.
That per-step block combine is the FLOPs hot spot — two MXU matmuls plus
the online-softmax rescale — and is what ``flash_block_update[_biased]``
fuses: one VMEM round-trip instead of the five separate HBM-materialised
intermediates (scores, max, probs, weighted-V, rescales) the jnp version
produces.  ``ring_attention`` is their only caller.

The ring/communication structure stays at the JAX level (XLA schedules the
ICI ppermute); only the local block math drops into Pallas — the same
split the reference makes between its coll algorithms (schedules) and its
op kernels (``ompi/mca/op/avx``).

Block update, grid: (batch*heads, q row tiles).  K/V blocks ride whole in
VMEM (s_kv up to a few thousand at 128-lane alignment); scores compute at
f32 on the MXU via ``preferred_element_type``; the state passes through
HBM between two calls, which the ring's ``ppermute`` between them needs.

The causal train step (``model.causal_flash_attention``), which has all
of K and V on the chip, runs these where Mosaic compiles (a TPU; the CPU
runs the ``jnp`` twins in ``parallel/model``):

- forward, ``flash_causal_forward``: one call a layer, q, k, v whole,
  the blocks chosen by the index maps, the softmax state in VMEM scratch
  from a q tile's first kv tile to its last;
- backward, ``attn_block_backward``: the five matmuls of one (q block,
  kv block) pair, with the float32 gradient accumulators passed through
  the call in place.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.base.jaxenv import pallas_interpret

Q_TILE = 256


def _block_kernel(scale, biased, *refs):
    if biased:
        bias_ref, q_ref, k_ref, v_ref, m_ref, num_ref, den_ref, \
            mo_ref, numo_ref, deno_ref = refs
    else:
        q_ref, k_ref, v_ref, m_ref, num_ref, den_ref, \
            mo_ref, numo_ref, deno_ref = refs
        bias_ref = None
    q = q_ref[0]            # (tq, d)
    k = k_ref[0]            # (skv, d)
    v = v_ref[0]            # (skv, dv)
    m = m_ref[0]            # (tq, LANES) broadcast copies, col 0 is live
    num = num_ref[0]        # (tq, dv)
    den = den_ref[0]        # (tq, LANES)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (tq, skv)
    if bias_ref is not None:
        # additive bias per (q row, kv col): -inf entries mask (causal,
        # padding), finite entries shift (ALiBi) — fused into the same
        # VMEM pass
        s = s + bias_ref[...]
    blk_max = jnp.max(s, axis=-1, keepdims=True)         # (tq, 1)
    new_m = jnp.maximum(m[:, :1], blk_max)               # (tq, 1)
    c = jnp.exp(m[:, :1] - new_m)                        # (tq, 1)
    p = jnp.exp(s - new_m)                               # (tq, skv)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (tq, dv)
    numo_ref[0] = (num * c + pv).astype(num.dtype)
    deno_ref[0] = (den[:, :1] * c + jnp.sum(p, axis=-1, keepdims=True)
                   ) * jnp.ones_like(den)
    mo_ref[0] = new_m * jnp.ones_like(m)


def _update_jnp(q, k_blk, v_blk, m, num, den, bias=None):
    """The same block update in plain jnp — autodiff reference and the
    source of the custom-VJP backward (recompute, flash-style: nothing
    beyond the step inputs is saved).  ``bias`` (sq, skv) is added to
    the scores (broadcast over batch/heads)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
    if bias is not None:
        s = s + bias
    new_m = jnp.maximum(m, s.max(axis=-1))
    c = jnp.exp(m - new_m)
    p = jnp.exp(s - new_m[..., None])
    new_num = num * c[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
    new_den = den * c + p.sum(axis=-1)
    return new_m, new_num, new_den


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def flash_block_update(q, k_blk, v_blk, m, num, den, interpret=None):
    """One online-softmax accumulation step against a K/V block.

    q: (b, h, sq, d); k_blk: (b, h, skv, d); v_blk: (b, h, skv, dv);
    m/den: (b, h, sq); num: (b, h, sq, dv).  ``dv`` may differ from ``d``
    (latent attention: q and k 192 wide, v and the numerator 128): the
    scores contract over ``d``, whatever it is, and only ``dv`` shapes
    the numerator.  Returns updated (m, num, den).  Forward runs the
    fused Pallas kernel; reverse-mode recomputes through the jnp block
    math (the Pallas custom-VJP pattern — kernels have no autodiff rule).
    ``interpret``: None resolves from the process's default devices; a
    caller tracing for other devices (a mesh) passes their mode.
    """
    return _update_pallas(q, k_blk, v_blk, m, num, den,
                          interpret=interpret)


def _flash_fwd(q, k_blk, v_blk, m, num, den, interpret):
    return (_update_pallas(q, k_blk, v_blk, m, num, den,
                           interpret=interpret),
            (q, k_blk, v_blk, m, num, den))


def _flash_bwd(interpret, res, ct):
    _, vjp = jax.vjp(_update_jnp, *res)
    return vjp(ct)


flash_block_update.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def flash_block_update_biased(q, k_blk, v_blk, m, num, den, bias,
                              interpret=None):
    """Block update with an additive score bias (sq, skv): -inf masks
    (causal ring attention, padding), finite shifts (ALiBi).  Same
    fused Pallas forward; reverse recomputes through the jnp twin."""
    return _update_pallas(q, k_blk, v_blk, m, num, den, bias=bias,
                          interpret=interpret)


def _flash_biased_fwd(q, k_blk, v_blk, m, num, den, bias, interpret):
    return (_update_pallas(q, k_blk, v_blk, m, num, den, bias=bias,
                           interpret=interpret),
            (q, k_blk, v_blk, m, num, den, bias))


# _flash_bwd handles both residual arities: jax.vjp adapts to the
# 6- (unbiased) vs 7-element (biased) tuple
flash_block_update_biased.defvjp(_flash_biased_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_pallas(q, k_blk, v_blk, m, num, den, bias=None, *,
                   interpret=None):
    # ``interpret`` is part of the jit cache key: an explicit False (the
    # AOT Mosaic gate) can never be served a cached interpreter trace,
    # and vice versa.  None = the process's default devices, at trace
    # time.
    if interpret is None:
        interpret = pallas_interpret()
    b, h, sq, d = q.shape
    skv, dv = k_blk.shape[2], v_blk.shape[-1]
    scale = 1.0 / math.sqrt(d)
    bh = b * h
    tq = min(Q_TILE, sq)
    if sq % tq:
        tq = sq  # ragged seq tiles: fall back to one tile per (b, h)

    lanes = 128
    qf = q.reshape(bh, sq, d)
    kf = k_blk.reshape(bh, skv, d)
    vf = v_blk.reshape(bh, skv, dv)
    # carry scalars per row are lane-broadcast so refs stay (…, 128)-tiled
    mf = jnp.broadcast_to(m.reshape(bh, sq)[..., None], (bh, sq, lanes))
    nf = num.reshape(bh, sq, dv)
    df = jnp.broadcast_to(den.reshape(bh, sq)[..., None], (bh, sq, lanes))

    grid = (bh, sq // tq)
    row = lambda i, j: (i, j, 0)
    blk = lambda i, j: (i, 0, 0)
    # a block's last dimension is the array's own, so a width that is no
    # multiple of the 128 lanes (192) is Mosaic's to lay out: it pads the
    # tile in VMEM and the contraction takes the MXU passes of 256
    q_spec = pl.BlockSpec((1, tq, d), row)
    k_spec = pl.BlockSpec((1, skv, d), blk)
    v_spec = pl.BlockSpec((1, skv, dv), blk)
    n_spec = pl.BlockSpec((1, tq, dv), row)
    s_spec = pl.BlockSpec((1, tq, lanes), row)

    biased = bias is not None
    in_specs = [q_spec, k_spec, v_spec, s_spec, n_spec, s_spec]
    operands = [qf, kf, vf, mf.astype(jnp.float32), nf,
                df.astype(jnp.float32)]
    if biased:
        # (sq, skv) shared across (b, h): one q-tile row slice per step
        in_specs.insert(0, pl.BlockSpec((tq, skv), lambda i, j: (j, 0)))
        operands.insert(0, bias.astype(jnp.float32))

    # inside shard_map(check_vma=True) — the train step — every output
    # must say which mesh axes it varies over: those of its operands
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    mo, numo, deno = pl.pallas_call(
        functools.partial(_block_kernel, scale, biased),
        out_shape=(
            jax.ShapeDtypeStruct(mf.shape, jnp.float32, vma=vma),
            jax.ShapeDtypeStruct(nf.shape, nf.dtype, vma=vma),
            jax.ShapeDtypeStruct(df.shape, jnp.float32, vma=vma),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(s_spec, n_spec, s_spec),
        interpret=interpret,
        name="otpu_flash_block_update",
    )(*operands)

    return (mo[..., 0].reshape(b, h, sq).astype(m.dtype),
            numo.reshape(num.shape),
            deno[..., 0].reshape(b, h, sq).astype(den.dtype))


#: the backward kernel's tile: so many q positions a grid step, against
#: so many kv positions at a time (a block of 1,024 is one tile)
BWD_TILE = 1024
#: the tile on the diagonal goes by strips of so many kv positions, each
#: against the q positions from its own first on: the rest see none of it
BWD_STRIP = 256
#: a tile of 1,024 at 192 / 128 holds k, v, q, do, the three float32
#: accumulators twice (in, out) and a few (1024, 1024) float32 score
#: arrays: over Mosaic's default 16 MiB of the v5e's 128 (offline compile)
BWD_VMEM_LIMIT = 64 << 20


def _bwd_block_kernel(scale, strip, ij_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                      dqo_ref, dko_ref, dvo_ref):
    """One q tile of block i against kv block j, a tile of kv positions
    at a time, **transposed**: scores are held (kv, q), so that the
    logsumexp and delta of a q row are row vectors that broadcast down
    the sublanes, and of the five matmuls only dq's contracts over the
    first axis of both operands."""
    t = pl.program_id(1)
    diagonal = ij_ref[0] == ij_ref[1]
    tile = q_ref.shape[1]
    nt_dims = (((1,), (1,)), ((), ()))
    nn_dims = (((1,), (0,)), ((), ()))
    tn_dims = (((0,), (0,)), ((), ()))
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)

    @pl.when(t == 0)
    def _():
        dko_ref[...] = dk_ref[...]
        dvo_ref[...] = dv_ref[...]

    dqo_ref[...] = dq_ref[...]

    def part(c, lo, n, masked):
        """kv positions lo .. lo + n of tile c against the q tile's
        positions from lo on."""
        rows = pl.ds(c * tile + lo, n)
        k, v = k_ref[0, rows, :], v_ref[0, rows, :]
        q, do = q_ref[0, lo:, :], do_ref[0, lo:, :]
        s = dot(k, q, nt_dims) * scale                      # (kv, q)
        if masked:
            at = lambda axis: jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                       axis)
            s = jnp.where(at(0) <= at(1), s, -jnp.inf)
        p = jnp.exp(s - lse_ref[0, :, lo:])
        dvo_ref[0, rows, :] += dot(p.astype(do.dtype), do, nn_dims)
        ds = (p * (dot(v, do, nt_dims) - delta_ref[0, :, lo:])
              * scale).astype(q.dtype)
        dko_ref[0, rows, :] += dot(ds, q, nn_dims)
        dqo_ref[0, lo:, :] += dot(ds, k, tn_dims)

    # by position: a kv tile of the diagonal pair lies wholly under the
    # diagonal (c < t), on it (c == t: masked, by strips) or wholly above
    # it (p is 0 there: skipped); every tile of another pair lies under
    for c in range(k_ref.shape[1] // tile):
        pl.when(jnp.logical_or(jnp.logical_not(diagonal), c < t))(
            functools.partial(part, c, 0, tile, False))

        @pl.when(jnp.logical_and(diagonal, c == t))
        def _():
            for lo in range(0, tile, strip):
                part(c, lo, strip, True)


def _tile(length, most):
    """A tile of ``length``: ``most`` where that divides it, else whole."""
    most = min(length, most)
    return most if length % most == 0 else length


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def attn_block_backward(ij, q, k, v, do, lse, delta, dq, dk, dv, *,
                        block: int, interpret=None):
    """One block pair of causal attention's flash backward, fused: q
    block ``ij[0]`` against kv block ``ij[1]`` (``block`` positions
    each; the pair whose two are equal is masked by position), the
    pair's terms added into the float32 accumulators and those handed
    back: ``dq`` at block i, ``dk`` and ``dv`` at block j, every other
    block as it came (the three alias their inputs).

    q, k: (b, h, s, d); v, do: (b, h, s, dv), all of q's dtype; lse,
    delta: (b, h, s) float32 (the forward's logsumexp; the row sums of
    do * o); dq, dk: (b, h, s, d) and dv: (b, h, s, dv) float32.  The
    arrays come whole and ``ij`` picks the blocks in the index maps (a
    scalar-prefetch operand), so a walk over the pairs, unrolled or by
    ``lax.scan``, slices nothing.  Five matmuls a pair with float32
    accumulation, ``p`` and ``ds`` cast to q's dtype for theirs; no
    (block, block) array leaves VMEM.  The ``jnp`` twin is
    ``parallel/model._bwd_pair``.
    """
    if interpret is None:
        interpret = pallas_interpret()
    b, h, s, d = q.shape
    hv = v.shape[-1]
    bh = b * h
    tq = _tile(block, BWD_TILE)
    nt = block // tq

    flat = lambda a: a.reshape(bh, s, a.shape[-1])
    row = lambda a: a.reshape(bh, 1, s).astype(jnp.float32)
    q_map = lambda g, t, ij: (g, ij[0] * nt + t, 0)
    kv_map = lambda g, t, ij: (g, ij[1], 0)
    row_map = lambda g, t, ij: (g, 0, ij[0] * nt + t)
    q_spec = lambda width: pl.BlockSpec((1, tq, width), q_map)
    kv_spec = lambda width: pl.BlockSpec((1, block, width), kv_map)
    row_spec = pl.BlockSpec((1, 1, tq), row_map)

    operands = [ij.astype(jnp.int32), flat(q), flat(k), flat(v), flat(do),
                row(lse), row(delta), flat(dq), flat(dk), flat(dv)]
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    acc_specs = [q_spec(d), kv_spec(d), kv_spec(hv)]
    out = pl.pallas_call(
        functools.partial(_bwd_block_kernel, 1.0 / math.sqrt(d),
                          _tile(tq, BWD_STRIP)),
        out_shape=tuple(jax.ShapeDtypeStruct(o.shape, jnp.float32, vma=vma)
                        for o in operands[7:]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, nt),
            in_specs=[q_spec(d), kv_spec(d), kv_spec(hv), q_spec(hv),
                      row_spec, row_spec] + acc_specs,
            out_specs=acc_specs),
        input_output_aliases={7: 0, 8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=BWD_VMEM_LIMIT),
        interpret=interpret,
        name="otpu_attn_block_backward",
    )(*operands)
    return tuple(o.reshape(a.shape) for o, a in zip(out, (dq, dk, dv)))


#: the causal forward kernel's tile: so many q positions a grid step
#: against so many kv positions (a block of 1,024 is one tile).  On the
#: chip 1,024 squared beats every other pair from 512 to 4,096 at both
#: cells' shapes (PERF.md section 6, PR 38)
FWD_TILE = 1024
#: q, k, v and o tiles twice (the pipeline's two buffers), the float32
#: numerator and a few (1024, 1024) float32 score arrays: as the backward
FWD_VMEM_LIMIT = 64 << 20


def _causal_fwd_kernel(scale, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_ref, den_ref, num_ref):
    """q tile i against kv tile j of one (batch, head): one online-
    softmax update of the running max, denominator and float32
    numerator, which live in VMEM scratch from the row's first kv tile
    (j = 0) to the diagonal one (j = i), where ``o`` and the logsumexp
    are written.  A kv tile above the diagonal (j > i) does nothing.
    Scores are held (q, kv): a row's statistics are columns, and the two
    matmuls are the MXU's plain forms."""
    i, j = pl.program_id(1), pl.program_id(2)
    tile = q_ref.shape[1]
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)
        num_ref[...] = jnp.zeros(num_ref.shape, jnp.float32)

    def update(diagonal):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = dot(q, k, (((1,), (1,)), ((), ()))) * scale     # (q, kv)
        if diagonal:
            at = lambda axis: jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                       axis)
            s = jnp.where(at(0) >= at(1), s, -jnp.inf)
        # every row sees its tile's first kv position at least, so its
        # running max is finite from the first update on
        m = m_ref[...]
        new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        c = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        den_ref[...] = den_ref[...] * c + jnp.sum(p, axis=1, keepdims=True)
        num_ref[...] = num_ref[...] * c + dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
        m_ref[...] = new_m

    pl.when(j < i)(functools.partial(update, False))

    @pl.when(j == i)
    def _():
        update(True)
        o_ref[0] = num_ref[...] / den_ref[...]
        # a row's logsumexp is a column here and a row where the backward
        # reads it: one (tile, 128) transposition a q tile, no pass of
        # o's size
        lse = m_ref[...] + jnp.log(den_ref[...])
        lse_ref[0] = jnp.broadcast_to(lse, (tile, 128)).T[:1]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def flash_causal_forward(q, k, v, *, block: int, interpret=None):
    """Causal attention's forward pass in one call: ``o`` (b, h, s, dv)
    float32 and the logsumexp (b, h, s) float32 of q, k (b, h, s, d)
    and v (b, h, s, dv), ``s`` a multiple of ``block``.

    The arrays come whole; the grid is (b x h, q tiles, kv tiles), the
    tile ``block`` or 1,024 positions, and the index maps pick the
    tiles, a kv tile above the diagonal clamped to the diagonal's (the
    one already there: not fetched again).  Scores, softmax state and
    ``o`` in float32, ``p`` cast to v's dtype for ``p v``, scale
    ``1 / sqrt(d)``; the diagonal tile is masked by position.  A width
    that is no multiple of 128 lanes (192) is Mosaic's to lay out.  The
    logsumexp leaves as (b x h, 1, s), the shape ``attn_block_backward``
    reads.  The ``jnp`` twin is ``parallel/model._causal_fwd_blocks``.
    """
    if interpret is None:
        interpret = pallas_interpret()
    b, h, s, d = q.shape
    hv = v.shape[-1]
    bh = b * h
    tile = _tile(block, FWD_TILE)
    nt = s // tile

    flat = lambda a: a.reshape(bh, s, a.shape[-1])
    q_map = lambda g, i, j: (g, i, 0)
    kv_map = lambda g, i, j: (g, jnp.minimum(i, j), 0)
    kv_spec = lambda width: pl.BlockSpec((1, tile, width), kv_map)
    operands = [flat(q), flat(k), flat(v)]
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    f32 = jnp.float32
    o, lse = pl.pallas_call(
        functools.partial(_causal_fwd_kernel, 1.0 / math.sqrt(d)),
        out_shape=(jax.ShapeDtypeStruct((bh, s, hv), f32, vma=vma),
                   jax.ShapeDtypeStruct((bh, 1, s), f32, vma=vma)),
        grid=(bh, nt, nt),
        in_specs=[pl.BlockSpec((1, tile, d), q_map), kv_spec(d), kv_spec(hv)],
        out_specs=(pl.BlockSpec((1, tile, hv), q_map),
                   pl.BlockSpec((1, 1, tile), lambda g, i, j: (g, 0, i))),
        scratch_shapes=[pltpu.VMEM((tile, 1), f32), pltpu.VMEM((tile, 1), f32),
                        pltpu.VMEM((tile, hv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=FWD_VMEM_LIMIT),
        interpret=interpret,
        name="otpu_flash_causal_forward",
    )(*operands)
    return o.reshape(b, h, s, hv), lse.reshape(b, h, s)
