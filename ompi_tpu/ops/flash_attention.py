"""Pallas flash-attention kernels: causal attention's forward pass and
its backward block pair.

The causal train step (``parallel/causal.causal_flash_attention``), which
has all of K and V on the chip, runs these where Mosaic compiles (a TPU;
the CPU runs the ``jnp`` twins in ``parallel/model``):

- forward, ``flash_causal_forward``: one call a layer, q, k, v whole,
  the blocks chosen by the index maps, the softmax state in VMEM scratch
  from a q tile's first kv tile to its last;
- backward, ``attn_block_backward``: the five matmuls of one (q block,
  kv block) pair, with the float32 gradient accumulators passed through
  the call in place.

k and v come with the model's own number of key-value heads, (b, n_kv,
s, .) beside q (b, h, s, .): a grid step is one query head, and the
index maps hand it the block of the key-value head its group shares
(``h // n_kv`` consecutive query heads a group, read from the shapes).
Nothing is repeated in HBM; with as many key-value heads as query heads
the maps are a head's own.

A static ``window`` (None: plain causal attention, and then every grid,
index map and kernel body is what it was without the argument) makes
both kernels sliding-window attention: key j is visible to query i iff 0
<= i - j < ``window``, a whole number w of blocks.  q block i then meets
kv blocks max(0, i - w) .. i and no other: the diagonal one under the
triangular mask as ever, the far one (i - w) under its mirror (key
column c visible to query row r iff c > r), those between under none.
The forward's grid holds the w + 1 kv tiles a q tile can reach, its index
map starting at the far tile; the backward's walk
(``parallel/causal._window_pairs``) leaves out the pairs out of reach and
the kernel tells the far pair from ``ij`` and w.

A ``select`` makes both kernels attention under a data-dependent
selection, which travels **packed, eight keys a byte**: (b, s, s / 8)
int8, query-major.  The keys go by groups of ``8 x lanes`` (1,024 where
that divides s: ``select_lanes``), and within a group byte c (lane c of
128) holds key ``lanes x m + c`` in bit m: bit plane m of a (rows, lanes)
byte tile is the mask of keys ``lanes x m ..`` of the group, and of the
transposed byte tile (lanes, q) it is those rows of the key-major mask, so
a reader unpacks its tile in VMEM with shifts, ands, compares and
tile-aligned concatenation (``_selected``) and nothing crosses lanes.
``ops/sparse_attention.pack_selection`` / ``unpack_selection`` are the
format's two ends outside a kernel.

A static ``bd`` (a block length B; None: nothing here is other than it
was) makes both kernels attention under **block diffusion's mask**, which
is not under the diagonal.  The rows are two halves of equal length, a
noisy copy of a sequence before its clean copy, both at positions 0 .. L -
1; with ``blk = position // B``, query row i sees key row j iff both are
noisy and ``blk(j) == blk(i)``, or i is noisy, j clean and ``blk(j) <
blk(i)``, or both are clean and ``blk(j) <= blk(i)``: a clean row sees no
noisy one.  ``blk(i) - blk(j)`` then lies within bounds that the pair's
two halves give, so a tile's mask is two compares against scalars
(``_bd_mask``) and never an array.  Which tile pairs hold a visible entry
is static (``bd_pairs``): the forward's grid walks a table of them through
its index maps (``_bd_fwd_kernel``), the backward's caller walks the same
list; a pair wholly visible runs the unmasked body, a pair with no visible
entry is walked by neither.

Scores compute in float32 on the MXU via ``preferred_element_type``.
Ring attention's block update (``parallel/flagship.ring_attention``) is
plain ``jnp`` on every platform and no kernel of this module.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.base.jaxenv import pallas_interpret

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


#: a tile pair under block diffusion's mask: wholly visible, or masked
#: inside the tile (one with no visible entry is in no list)
BD_WHOLE, BD_MASKED = 1, 2
_BD_FAR = 1 << 30       # no bound on blk(i) - blk(j) from above


def _bd_bounds(q_noisy, kv_noisy):
    """(lo, hi): query row i sees key row j iff ``lo <= blk(i) - blk(j) <=
    hi``, by the halves the two lie in (Python or traced booleans; a clean
    query and a noisy key are never paired): both noisy ``==``, a noisy
    query and a clean key ``<``, both clean ``<=``."""
    if isinstance(q_noisy, bool):
        return (1 if q_noisy and not kv_noisy else 0,
                0 if q_noisy and kv_noisy else _BD_FAR)
    return (jnp.where(jnp.logical_and(q_noisy, jnp.logical_not(kv_noisy)),
                      1, 0),
            jnp.where(jnp.logical_and(q_noisy, kv_noisy), 0, _BD_FAR))


def bd_pairs(nb: int, block: int, bl: int) -> list:
    """The (q block, kv block, kind) triples attention under block
    diffusion's mask walks over ``nb`` blocks of ``block`` rows, the first
    half of them the noisy copy: q block by q block, a noisy one's noisy
    kv blocks before its clean ones, each ascending; ``kind`` is
    ``BD_WHOLE`` or ``BD_MASKED``, and a pair with no visible entry is left
    out.  Static: the forward's grid, the backward's walk, the ``jnp``
    twins and the counters all read this list."""
    half = nb // 2
    if nb % 2 or bl < 1:
        raise ValueError(f"{nb} blocks of {block} rows are no two halves of "
                         f"whole blocks, or the block length {bl} is none")
    blocks = lambda t: ((t % half) * block // bl,
                        ((t % half + 1) * block - 1) // bl)
    out = []
    for i in range(nb):
        for j in range(nb):
            if j < half <= i:
                continue
            lo, hi = _bd_bounds(i < half, j < half)
            (q_lo, q_hi), (k_lo, k_hi) = blocks(i), blocks(j)
            least, most = q_lo - k_hi, q_hi - k_lo
            if most < lo or least > hi:
                continue
            out.append((i, j, BD_WHOLE if lo <= least and most <= hi
                        else BD_MASKED))
    return out


def _bd_mask(shape, q_axis: int, q_first, kv_first, q_noisy, kv_noisy,
             bl: int):
    """A tile's mask under block diffusion: ``shape``'s axis ``q_axis``
    runs over query positions from ``q_first``, the other over key
    positions from ``kv_first`` (positions inside a half; scalars, maybe
    traced, as the two halves' flags are)."""
    def blk(first, axis):
        pos = first + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        if bl & (bl - 1):
            return jax.lax.div(pos, jnp.int32(bl))
        return pos >> (bl.bit_length() - 1)

    lo, hi = _bd_bounds(q_noisy, kv_noisy)
    d = blk(q_first, q_axis) - blk(kv_first, 1 - q_axis)
    return jnp.logical_and(d >= lo, d <= hi)


def _bwd_block_kernel(scale, strip, rep, far_by, ij_ref, q_ref, k_ref, v_ref,
                      do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                      dqo_ref, dko_ref, dvo_ref, chosen=None, live=None,
                      bd=None):
    """One q tile of block i of one query head against kv block j of the
    key-value head its group shares, a tile of kv positions at a time,
    **transposed**: scores are held (kv, q), so that the logsumexp and
    delta of a q row are row vectors that broadcast down the sublanes,
    and of the five matmuls only dq's contracts over the first axis of
    both operands.  The ``rep`` query heads of a group are consecutive
    grid steps on one ``dk`` / ``dv`` block, which stays in VMEM from
    the group's first tile, where it takes the incoming accumulator, to
    its last: the group's sum is made in float32, here.  ``far_by``
    (None without a window) is the window in blocks: the pair whose
    blocks lie that far apart is the far one, masked the other way.
    ``chosen`` (None without a selection: ``_bwd_select_kernel`` gives
    it) makes kv tile c's mask (kv, q) of a selection: every kv tile is
    then masked by it and by nothing else, where ``live`` says the pair
    selects anything at all.  ``bd`` (None without block diffusion's mask:
    (the block length, the blocks a half)) reads the pair's kind from
    ``ij_ref[2]``: every kv tile of a ``BD_WHOLE`` pair goes unmasked, of
    a ``BD_MASKED`` one under ``_bd_mask`` of its positions."""
    t = pl.program_id(1)
    diagonal = ij_ref[0] == ij_ref[1]
    tile = q_ref.shape[1]
    nt_dims = (((1,), (1,)), ((), ()))
    nn_dims = (((1,), (0,)), ((), ()))
    tn_dims = (((0,), (0,)), ((), ()))
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)

    first = t == 0          # of the group's: the block's first visit
    if rep > 1:
        first = jnp.logical_and(first, pl.program_id(0) % rep == 0)

    @pl.when(first)
    def _():
        dko_ref[...] = dk_ref[...]
        dvo_ref[...] = dv_ref[...]

    dqo_ref[...] = dq_ref[...]

    def part(c, lo, n, masked, far=False):
        """kv positions lo .. lo + n of tile c against the q tile's
        positions from lo on; of the far pair's tile, against those
        before lo + n (the diagonal strip's mirror: the rest see none of
        it), kv position lo + a visible to q position b iff lo + a > b."""
        rows = pl.ds(c * tile + lo, n)
        qs = slice(0, lo + n) if far else slice(lo, None)
        k, v = k_ref[0, rows, :], v_ref[0, rows, :]
        q, do = q_ref[0, qs, :], do_ref[0, qs, :]
        s = dot(k, q, nt_dims) * scale                      # (kv, q)
        if chosen is not None:
            s = jnp.where(chosen(c), s, -jnp.inf)
        elif masked and bd is not None:
            block = k_ref.shape[1]
            s = jnp.where(_bd_mask(
                s.shape, 1, (ij_ref[0] % bd[1]) * block + t * tile,
                (ij_ref[1] % bd[1]) * block + c * tile,
                ij_ref[0] < bd[1], ij_ref[1] < bd[1], bd[0]), s, -jnp.inf)
        elif masked:
            at = lambda axis: jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                       axis)
            s = jnp.where(at(0) + lo > at(1) if far else at(0) <= at(1), s,
                          -jnp.inf)
        p = jnp.exp(s - lse_ref[0, :, qs])
        dvo_ref[0, rows, :] += dot(p.astype(do.dtype), do, nn_dims)
        ds = (p * (dot(v, do, nt_dims) - delta_ref[0, :, qs])
              * scale).astype(q.dtype)
        dko_ref[0, rows, :] += dot(ds, q, nn_dims)
        dqo_ref[0, qs, :] += dot(ds, k, tn_dims)

    if chosen is not None:
        # the selection holds the diagonal too: no tile goes by position
        for c in range(k_ref.shape[1] // tile):
            pl.when(live)(functools.partial(part, c, 0, tile, True))
        return
    if bd is not None:
        # by the pair's kind alone: no tile goes by strips
        inside = ij_ref[2] == BD_MASKED
        for c in range(k_ref.shape[1] // tile):
            pl.when(jnp.logical_not(inside))(
                functools.partial(part, c, 0, tile, False))
            pl.when(inside)(functools.partial(part, c, 0, tile, True))
        return
    # by position: a kv tile of the diagonal pair lies wholly under the
    # diagonal (c < t), on it (c == t: masked, by strips) or wholly above
    # it (p is 0 there: skipped); every tile of another pair lies under
    if far_by is None:
        whole = lambda c: jnp.logical_or(jnp.logical_not(diagonal), c < t)
    else:
        # the far pair mirrors the diagonal one: a kv tile lies wholly in
        # reach (c > t), on the window's edge (c == t) or out of reach
        far = ij_ref[0] - ij_ref[1] == far_by
        whole = lambda c: jnp.where(diagonal, c < t,
                                    jnp.where(far, c > t, True))
    for c in range(k_ref.shape[1] // tile):
        pl.when(whole(c))(functools.partial(part, c, 0, tile, False))

        @pl.when(jnp.logical_and(diagonal, c == t))
        def _():
            for lo in range(0, tile, strip):
                part(c, lo, strip, True)

        if far_by is not None:
            @pl.when(jnp.logical_and(far, c == t))
            def _():
                for lo in range(0, tile, strip):
                    part(c, lo, strip, True, far=True)


def _bwd_select_kernel(scale, rep, heads, nb, lanes, ij_ref, flags_ref, q_ref,
                       k_ref, v_ref, do_ref, lse_ref, delta_ref, selt_ref,
                       *accs):
    """``_bwd_block_kernel`` under a selection: ``selt_ref`` (1, bytes, q
    tile) int8 is the pair's block of the packed selection, key-major as
    the scores are held (``_select_blocks`` says which bytes; a kv tile's
    mask is unpacked in VMEM); ``flags_ref`` (b x blocks x blocks) says
    which pairs select anything, and a pair that does not only hands its
    accumulators on."""
    block, tile = k_ref.shape[1], q_ref.shape[1]
    live = flags_ref[(pl.program_id(0) // heads) * nb * nb
                     + ij_ref[0] * nb + ij_ref[1]] != 0

    def chosen(c):
        if tile == block:
            return _selected(selt_ref[0], 0, lanes, tile, ij_ref[1])
        return _selected(selt_ref[0, pl.ds(c * tile // 8, tile // 8), :], 0,
                         lanes, tile)

    _bwd_block_kernel(scale, None, rep, None, ij_ref, q_ref, k_ref, v_ref,
                      do_ref, lse_ref, delta_ref, *accs, chosen=chosen,
                      live=live)


def _tile(length, most):
    """A tile of ``length``: ``most`` where that divides it, else whole."""
    most = min(length, most)
    return most if length % most == 0 else length


def _heads_a_group(q, k):
    """The query heads that share one key-value head, from the shapes of
    q (b, h, s, d) and k (b, n_kv, s, d)."""
    h, n_kv = q.shape[1], k.shape[1]
    if h % n_kv:
        raise ValueError(f"{h} query heads on {n_kv} key-value heads")
    return h // n_kv


def _group(g, rep):
    """Row ``b_i * n_kv + h_i // rep`` of the flattened k and v for grid
    step ``g = b_i * h + h_i``."""
    return g if rep == 1 else g // rep


def _window_blocks(window, block: int, length: int):
    """A window in blocks (None where there is none): a whole number of
    them, fewer than the sequence has."""
    if window is None:
        return None
    if window % block or not 0 < window < length:
        raise ValueError(f"a window of {window} positions is no whole number "
                         f"of blocks of {block} inside {length} positions")
    return window // block


#: keys a group of a packed selection where that divides the length: eight
#: bit planes of 128 lanes
SELECT_GROUP = 1024


def select_lanes(length: int) -> int:
    """The bytes a group of a packed selection of ``length`` keys: an
    eighth of the largest power of two up to ``SELECT_GROUP`` that divides
    it (128 at a multiple of 1,024)."""
    if length % 8:
        raise ValueError(f"a selection of {length} keys packs into no whole "
                         "number of bytes")
    group = SELECT_GROUP
    while length % group:
        group //= 2
    return group // 8


def _select_blocks(length: int, tile: int):
    """How a reader's kv tiles of ``tile`` keys lie in a packed selection
    of ``length`` keys: (``select_lanes``, the bytes a block of the packed
    axis, the kv tiles that share one block).  A tile is whole groups, or
    some of one group's bit planes (then the block is the group's bytes,
    and ``_selected`` finds a tile's planes in them by the tile's number)."""
    lanes = select_lanes(length)
    group = 8 * lanes
    if tile % group and (group % tile or tile % lanes):
        raise ValueError(f"a tile of {tile} keys is no whole number of "
                         f"groups of {group} or of their {lanes}-key planes")
    return lanes, max(tile, group) // 8, max(1, group // tile)


def _selected(packed, axis: int, lanes: int, keys: int, j=0):
    """The boolean mask of ``keys`` keys along ``axis``, unpacked from the
    int8 bytes of a packed selection along it (a block as
    ``_select_blocks`` cuts them): whole groups of ``lanes`` bytes (every
    plane of each, in order), or one group's bytes, of which kv tile ``j``
    (may be traced) is ``keys / lanes`` planes.  Shifts, ands, compares
    and a concatenation of whole planes: in a kernel nothing crosses lanes
    or sublane tiles."""
    x = packed.astype(jnp.int32)        # sign-extended: bit 7 stays bit 7
    planes = min(keys // lanes, 8)
    first = 0 if planes == 8 else (j % (8 // planes)) * planes
    parts = [jax.lax.slice_in_dim(x, lo, lo + lanes, axis=axis) >> (first + m)
             for lo in range(0, x.shape[axis], lanes) for m in range(planes)]
    bits = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)
    return (bits & 1) != 0


def _tile_flags(select, tile: int):
    """Which (q tile, kv tile) pairs of a packed selection (b, s, s / 8)
    select anything: int32 (b x tiles x tiles), 1 or 0.  One bitwise-or
    reduction of the bytes by (q tile, group) says which planes of the
    group hold a bit; a kv tile is some of those planes."""
    b, s, _ = select.shape
    nt = s // tile
    lanes = _select_blocks(s, tile)[0]
    ors = jax.lax.reduce(select.reshape(b, nt, tile, -1, lanes), jnp.int8(0),
                         jax.lax.bitwise_or, (2, 4)).astype(jnp.int32)
    planes = (ors[..., None] >> jnp.arange(8, dtype=jnp.int32)) & 1
    return jnp.any(planes.reshape(b, nt, nt, tile // lanes) != 0,
                   axis=-1).astype(jnp.int32).reshape(-1)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "window",
                                             "bd", "scale"))
def attn_block_backward(ij, q, k, v, do, lse, delta, dq, dk, dv, *,
                        block: int, interpret=None, window=None,
                        select=None, bd=None, scale=None):
    """One block pair of causal attention's flash backward, fused: q
    block ``ij[0]`` against kv block ``ij[1]`` (``block`` positions
    each; the pair whose two are equal is masked by position), the
    pair's terms added into the float32 accumulators and those handed
    back: ``dq`` at block i, ``dk`` and ``dv`` at block j, every other
    block as it came (the three alias their inputs).

    q: (b, h, s, d) and do: (b, h, s, dv); k: (b, n_kv, s, d) and v:
    (b, n_kv, s, dv), ``h`` a multiple of ``n_kv``, all of q's dtype;
    lse, delta: (b, h, s) float32 (the forward's logsumexp; the row
    sums of do * o); dq: (b, h, s, d), dk: (b, n_kv, s, d) and dv: (b,
    n_kv, s, dv) float32.  A key-value head's ``dk`` and ``dv`` gather
    its ``h // n_kv`` query heads' terms in float32 (the head axis of
    the grid is sequential where heads share one).  The
    arrays come whole and ``ij`` picks the blocks in the index maps (a
    scalar-prefetch operand), so a walk over the pairs, unrolled or by
    ``lax.scan``, slices nothing.  Five matmuls a pair with float32
    accumulation, ``p`` and ``ds`` cast to q's dtype for theirs; no
    (block, block) array leaves VMEM.  The ``jnp`` twin is
    ``parallel/causal._bwd_pair``.  With ``window`` (positions, whole
    blocks) the pair whose blocks lie the window apart is the far one
    and masked as such; the caller walks no pair beyond it.  With
    ``select`` = (the packed selection key-major (b, s / 8, s q) int8:
    ``flash_causal_forward``'s, its last two axes exchanged; its pairs'
    flags as ``_tile_flags(.., block)`` gives them of the query-major one)
    a pair is masked by its tile of the selection and by nothing else, and
    a pair that selects nothing hands the accumulators on
    (``_bwd_select_kernel``).  With ``bd`` (block diffusion's block
    length; static) ``ij`` holds three entries, one of ``bd_pairs``'
    triples, and the pair is masked as its kind says.  ``scale`` (static;
    None: ``1 / sqrt(d)``) is the scores' own.
    """
    if interpret is None:
        interpret = pallas_interpret()
    b, h, s, d = q.shape
    hv = v.shape[-1]
    bh = b * h
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    rep = _heads_a_group(q, k)
    far_by = _window_blocks(window, block, s)
    tq = _tile(block, BWD_TILE)
    nt = block // tq

    flat = lambda a: a.reshape(-1, s, a.shape[-1])
    row = lambda a: a.reshape(bh, 1, s).astype(jnp.float32)
    q_map = lambda g, t, ij: (g, ij[0] * nt + t, 0)
    kv_map = lambda g, t, ij: (_group(g, rep), ij[1], 0)
    row_map = lambda g, t, ij: (g, 0, ij[0] * nt + t)
    q_spec = lambda width: pl.BlockSpec((1, tq, width), q_map)
    kv_spec = lambda width: pl.BlockSpec((1, block, width), kv_map)
    row_spec = pl.BlockSpec((1, 1, tq), row_map)

    operands = [ij.astype(jnp.int32), flat(q), flat(k), flat(v), flat(do),
                row(lse), row(delta), flat(dq), flat(dk), flat(dv)]
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    acc_specs = [q_spec(d), kv_spec(d), kv_spec(hv)]
    if select is not None:
        return _select_block_backward(
            operands, select, (q_spec, kv_spec, row_spec, acc_specs),
            (b, h, s, d, hv, rep, tq, nt, block), vma, interpret,
            (dq, dk, dv), scale)
    out = pl.pallas_call(
        functools.partial(_bwd_block_kernel, scale,
                          _tile(tq, BWD_STRIP), rep, far_by,
                          bd=None if bd is None else (bd, s // block // 2)),
        out_shape=tuple(jax.ShapeDtypeStruct(o.shape, jnp.float32, vma=vma)
                        for o in operands[7:]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, nt),
            in_specs=[q_spec(d), kv_spec(d), kv_spec(hv), q_spec(hv),
                      row_spec, row_spec] + acc_specs,
            out_specs=acc_specs),
        input_output_aliases={7: 0, 8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            # a group's heads revisit its dk and dv block: in turn
            dimension_semantics=("parallel" if rep == 1 else "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=BWD_VMEM_LIMIT),
        interpret=interpret,
        name="otpu_attn_block_backward" if bd is None
        else "otpu_attn_bd_backward",
    )(*operands)
    return tuple(o.reshape(a.shape) for o, a in zip(out, (dq, dk, dv)))


def _select_block_backward(operands, select, specs, dims, vma, interpret,
                           like, scale):
    """``attn_block_backward``'s call under a selection: one more scalar
    operand (the pairs' flags) and one more input (the pair's block of the
    packed selection)."""
    q_spec, kv_spec, row_spec, acc_specs = specs
    b, h, s, d, hv, rep, tq, nt, block = dims
    selt, flags = select
    lanes, nbytes, share = _select_blocks(s, block)
    lift = lambda spec: pl.BlockSpec(
        spec.block_shape, lambda g, t, ij, fl: spec.index_map(g, t, ij))
    sel_spec = pl.BlockSpec((1, nbytes, tq), lambda g, t, ij, fl: (
        g // h, ij[1] // share, ij[0] * nt + t))
    ins = [q_spec(d), kv_spec(d), kv_spec(hv), q_spec(hv), row_spec, row_spec]
    out = pl.pallas_call(
        functools.partial(_bwd_select_kernel, scale, rep, h,
                          s // block, lanes),
        out_shape=tuple(jax.ShapeDtypeStruct(o.shape, jnp.float32, vma=vma)
                        for o in operands[7:]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b * h, nt),
            in_specs=[lift(sp) for sp in ins] + [sel_spec]
            + [lift(sp) for sp in acc_specs],
            out_specs=[lift(sp) for sp in acc_specs]),
        input_output_aliases={9: 0, 10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel" if rep == 1 else "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=BWD_VMEM_LIMIT),
        interpret=interpret,
        name="otpu_attn_select_backward",
    )(operands[0], flags, *operands[1:7], selt, *operands[7:])
    return tuple(o.reshape(a.shape) for o, a in zip(out, like))


#: the causal forward kernel's tile: so many q positions a grid step
#: against so many kv positions (a block of 1,024 is one tile).  On the
#: chip 1,024 squared beats every other pair from 512 to 4,096 at both
#: cells' shapes (PERF.md section 6, PR 38)
FWD_TILE = 1024
#: q, k, v and o tiles twice (the pipeline's two buffers), the float32
#: numerator and a few (1024, 1024) float32 score arrays: as the backward
FWD_VMEM_LIMIT = 64 << 20


def _online_update(scale, q_ref, k_ref, v_ref, m_ref, den_ref, num_ref, mask,
                   chosen=None):
    """One online-softmax update of a q tile's running max, denominator
    and float32 numerator (VMEM scratch) by one kv tile.  ``mask``: None,
    ``"diagonal"`` (key column c visible to query row r iff c <= r),
    ``"far"`` (a window's far tile: iff c > r) or ``"select"`` (iff
    ``chosen()``, the tile (q, kv) of a selection, says so).  Scores
    are held (q, kv):
    a row's statistics are columns, and the two matmuls are the MXU's
    plain forms."""
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    s = dot(q, k, (((1,), (1,)), ((), ()))) * scale     # (q, kv)
    if mask == "select":
        s = jnp.where(chosen(), s, -jnp.inf)
    elif mask is not None:
        at = lambda axis: jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   axis)
        s = jnp.where(at(0) < at(1) if mask == "far" else at(0) >= at(1), s,
                      -jnp.inf)
    # without a window every row sees its tile's first kv position at
    # least, so its running max is finite from the first update on
    m = m_ref[...]
    new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    at_m = new_m
    if mask in ("far", "select"):
        # a window's far tile comes first and its last query row sees
        # nothing of it (under a selection any row may see nothing of
        # any tile): that row's max stays -inf, and exp(-inf - -inf)
        # must not arise; against 0 its c and p are exp(-inf) = 0
        at_m = jnp.where(new_m == -jnp.inf, 0.0, new_m)
    c = jnp.exp(m - at_m)
    p = jnp.exp(s - at_m)
    den_ref[...] = den_ref[...] * c + jnp.sum(p, axis=1, keepdims=True)
    num_ref[...] = num_ref[...] * c + dot(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
    m_ref[...] = new_m


def _init_state(m_ref, den_ref, num_ref):
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    den_ref[...] = jnp.zeros(den_ref.shape, jnp.float32)
    num_ref[...] = jnp.zeros(num_ref.shape, jnp.float32)


def _write_out(o_ref, lse_ref, m_ref, den_ref, num_ref):
    o_ref[0] = num_ref[...] / den_ref[...]
    # a row's logsumexp is a column here and a row where the backward
    # reads it: one (tile, 128) transposition a q tile, no pass of
    # o's size
    lse = m_ref[...] + jnp.log(den_ref[...])
    lse_ref[0] = jnp.broadcast_to(lse, (o_ref.shape[1], 128)).T[:1]


def _causal_fwd_kernel(scale, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_ref, den_ref, num_ref):
    """q tile i against kv tile j of one (batch, head): one online-
    softmax update (``_online_update``) of the running max, denominator
    and float32 numerator, which live in VMEM scratch from the row's
    first kv tile (j = 0) to the diagonal one (j = i), where ``o`` and
    the logsumexp are written.  A kv tile above the diagonal (j > i)
    does nothing."""
    i, j = pl.program_id(1), pl.program_id(2)
    state = (m_ref, den_ref, num_ref)
    update = functools.partial(_online_update, scale, q_ref, k_ref, v_ref,
                               *state)

    @pl.when(j == 0)
    def _():
        _init_state(*state)

    pl.when(j < i)(functools.partial(update, None))

    @pl.when(j == i)
    def _():
        update("diagonal")
        _write_out(o_ref, lse_ref, *state)


def _window_fwd_kernel(scale, w, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_ref, den_ref, num_ref):
    """``_causal_fwd_kernel`` under a window of ``w`` tiles: the grid's
    third axis is the w + 1 kv tiles q tile i can reach, step n being kv
    tile j = i - w + n: the far one first (n = 0, masked the far way),
    the diagonal one last (n = w: masked, then ``o`` and the logsumexp
    are written), those between under no mask.  A step before the
    sequence's start (j < 0: the first w q tiles have them) does
    nothing."""
    i, n = pl.program_id(1), pl.program_id(2)
    j = i - w + n
    state = (m_ref, den_ref, num_ref)
    update = functools.partial(_online_update, scale, q_ref, k_ref, v_ref,
                               *state)

    @pl.when(n == 0)
    def _():
        _init_state(*state)

    pl.when(jnp.logical_and(n == 0, j >= 0))(functools.partial(update, "far"))
    pl.when(jnp.logical_and(jnp.logical_and(n > 0, n < w), j >= 0))(
        functools.partial(update, None))

    @pl.when(n == w)
    def _():
        update("diagonal")
        _write_out(o_ref, lse_ref, *state)


def _select_fwd_kernel(scale, heads, lanes, flags_ref, q_ref, k_ref, v_ref,
                       sel_ref, o_ref, lse_ref, m_ref, den_ref, num_ref):
    """``_causal_fwd_kernel`` under a selection: every kv tile up to the
    diagonal one is masked by its bits of ``sel_ref`` (1, q tile, bytes)
    int8, the tile's block of the packed selection (``_select_blocks``;
    unpacked in VMEM; it holds the diagonal too) and by nothing else; a
    tile pair that selects nothing (``flags_ref`` (b x tiles x tiles)) is
    passed over."""
    g, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nt = pl.num_programs(1)
    state = (m_ref, den_ref, num_ref)
    chosen = lambda: _selected(sel_ref[0], 1, lanes, k_ref.shape[1], j)

    @pl.when(j == 0)
    def _():
        _init_state(*state)

    live = flags_ref[(g // heads) * nt * nt + i * nt + jnp.minimum(i, j)] != 0
    pl.when(jnp.logical_and(j <= i, live))(functools.partial(
        _online_update, scale, q_ref, k_ref, v_ref, *state, "select",
        chosen))

    @pl.when(j == i)
    def _():
        _write_out(o_ref, lse_ref, *state)


def _bd_fwd_kernel(scale, bl, half, kv_ref, kind_ref, q_ref, k_ref, v_ref,
                   o_ref, lse_ref, m_ref, den_ref, num_ref):
    """``_causal_fwd_kernel`` under block diffusion's mask: the grid's
    third axis is the most kv tiles a q tile meets, and step n of q tile i
    reads the table (``_bd_table``: flat, ``i x steps + n``): kv tile
    ``kv_ref[..]`` (the index maps read the same entry) under kind
    ``kind_ref[..]``: ``BD_WHOLE`` no mask, ``BD_MASKED`` ``_bd_mask`` of
    the two tiles' positions and halves, 0 a step past the row's last pair,
    which does nothing.  ``o`` and the logsumexp are written at the last
    step.  A masked tile may show a row nothing (a noisy row of a half's
    first block sees no clean key): the update guards its running max as
    under a selection."""
    i, n = pl.program_id(1), pl.program_id(2)
    steps = pl.num_programs(2)
    tile = q_ref.shape[1]
    state = (m_ref, den_ref, num_ref)
    j, kind = kv_ref[i * steps + n], kind_ref[i * steps + n]

    @pl.when(n == 0)
    def _():
        _init_state(*state)

    pl.when(kind == BD_WHOLE)(functools.partial(
        _online_update, scale, q_ref, k_ref, v_ref, *state, None))
    pl.when(kind == BD_MASKED)(functools.partial(
        _online_update, scale, q_ref, k_ref, v_ref, *state, "select",
        lambda: _bd_mask((tile, k_ref.shape[1]), 0, (i % half) * tile,
                         (j % half) * tile, i < half, j < half, bl)))

    @pl.when(n == steps - 1)
    def _():
        _write_out(o_ref, lse_ref, *state)


def _bd_table(nt: int, tile: int, bl: int):
    """``bd_pairs`` as the forward's grid reads it: (steps, kv tiles, kinds),
    the two flat int32 (nt x steps), a row padded with its last kv tile
    (fetched already) under kind 0."""
    rows = [[] for _ in range(nt)]
    for i, j, kind in bd_pairs(nt, tile, bl):
        rows[i].append((j, kind))
    steps = max(len(r) for r in rows)
    rows = [r + [(r[-1][0], 0)] * (steps - len(r)) for r in rows]
    flat = lambda at: jnp.asarray([e[at] for r in rows for e in r], jnp.int32)
    return steps, flat(0), flat(1)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "window",
                                             "bd", "scale"))
def flash_causal_forward(q, k, v, *, block: int, interpret=None, window=None,
                         select=None, bd=None, scale=None):
    """Causal attention's forward pass in one call: ``o`` (b, h, s, dv)
    float32 and the logsumexp (b, h, s) float32 of q (b, h, s, d), k
    (b, n_kv, s, d) and v (b, n_kv, s, dv), ``h`` a multiple of ``n_kv``
    and ``s`` of ``block``.

    The arrays come whole; the grid is (b x h, q tiles, kv tiles), the
    tile ``block`` or 1,024 positions, and the index maps pick the
    tiles, of the key-value head the query head's group shares, a kv
    tile above the diagonal clamped to the diagonal's (the one already
    there: not fetched again).  Scores, softmax state and
    ``o`` in float32, ``p`` cast to v's dtype for ``p v``, the scores'
    scale ``1 / sqrt(d)`` unless ``scale`` (static) gives one; the diagonal
    tile is masked by position.  A width
    that is no multiple of 128 lanes (192) is Mosaic's to lay out.  The
    logsumexp leaves as (b x h, 1, s), the shape ``attn_block_backward``
    reads.  The ``jnp`` twin is ``parallel/causal._causal_fwd_blocks``.
    With ``window`` (positions, whole tiles) the grid's third axis is
    the window's tiles and the diagonal one (``_window_fwd_kernel``), the
    index map starting at the far tile (clamped to the sequence's first).
    With ``select`` (b, s, s / 8) int8, a selection packed eight keys a
    byte, query-major (the module's head has the layout;
    ``ops/sparse_attention.pack_selection`` makes it of a mask), key u is
    visible to query t iff its bit of row t is set (the selection holds
    causality: nothing above the diagonal), every row selects a key, and a
    tile pair that selects nothing is passed over
    (``_select_fwd_kernel``).  With ``bd`` (block diffusion's block length;
    static) the rows are a noisy and a clean half and the grid's third axis
    walks ``bd_pairs``' tile pairs of a q tile (``_bd_fwd_kernel``).
    """
    if interpret is None:
        interpret = pallas_interpret()
    b, h, s, d = q.shape
    hv = v.shape[-1]
    bh = b * h
    rep = _heads_a_group(q, k)
    tile = _tile(block, FWD_TILE)
    nt = s // tile
    scale = 1.0 / math.sqrt(d) if scale is None else scale

    flat = lambda a: a.reshape(-1, s, a.shape[-1])
    q_map = lambda g, i, j: (g, i, 0)
    kv_map = lambda g, i, j: (_group(g, rep), jnp.minimum(i, j), 0)
    kernel, reach = functools.partial(_causal_fwd_kernel, scale), nt
    w = _window_blocks(window, tile, s)
    if w is not None:
        kv_map = lambda g, i, n: (_group(g, rep), jnp.maximum(i - w + n, 0),
                                  0)
        kernel, reach = functools.partial(_window_fwd_kernel, scale,
                                          w), w + 1
    kv_spec = lambda width: pl.BlockSpec((1, tile, width), kv_map)
    operands = [flat(q), flat(k), flat(v)]
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    f32 = jnp.float32
    if bd is not None:
        steps, kv_of, kinds = _bd_table(nt, tile, bd)
        kv_map = lambda g, i, n, kv, kd: (_group(g, rep), kv[i * steps + n],
                                          0)
        q_map = lambda g, i, n, kv, kd: (g, i, 0)
        o, lse = pl.pallas_call(
            functools.partial(_bd_fwd_kernel, scale, bd, nt // 2),
            out_shape=(jax.ShapeDtypeStruct((bh, s, hv), f32, vma=vma),
                       jax.ShapeDtypeStruct((bh, 1, s), f32, vma=vma)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(bh, nt, steps),
                in_specs=[pl.BlockSpec((1, tile, d), q_map),
                          pl.BlockSpec((1, tile, d), kv_map),
                          pl.BlockSpec((1, tile, hv), kv_map)],
                out_specs=(pl.BlockSpec((1, tile, hv), q_map),
                           pl.BlockSpec((1, 1, tile),
                                        lambda g, i, n, kv, kd: (g, 0, i))),
                scratch_shapes=[pltpu.VMEM((tile, 1), f32),
                                pltpu.VMEM((tile, 1), f32),
                                pltpu.VMEM((tile, hv), f32)]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=FWD_VMEM_LIMIT),
            interpret=interpret,
            name="otpu_flash_bd_forward",
        )(kv_of, kinds, *operands)
        return o.reshape(b, h, s, hv), lse.reshape(b, h, s)
    if select is not None:
        lift = lambda fn: (lambda g, i, j, fl: fn(g, i, j))
        vma = vma | jax.typeof(select).vma
        lanes, nbytes, share = _select_blocks(s, tile)
        o, lse = pl.pallas_call(
            functools.partial(_select_fwd_kernel, scale, h, lanes),
            out_shape=(jax.ShapeDtypeStruct((bh, s, hv), f32, vma=vma),
                       jax.ShapeDtypeStruct((bh, 1, s), f32, vma=vma)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(bh, nt, nt),
                in_specs=[pl.BlockSpec((1, tile, d), lift(q_map)),
                          pl.BlockSpec((1, tile, d), lift(kv_map)),
                          pl.BlockSpec((1, tile, hv), lift(kv_map)),
                          pl.BlockSpec((1, tile, nbytes), lambda g, i, j, fl: (
                              g // h, i, jnp.minimum(i, j) // share))],
                out_specs=(pl.BlockSpec((1, tile, hv), lift(q_map)),
                           pl.BlockSpec((1, 1, tile),
                                        lambda g, i, j, fl: (g, 0, i))),
                scratch_shapes=[pltpu.VMEM((tile, 1), f32),
                                pltpu.VMEM((tile, 1), f32),
                                pltpu.VMEM((tile, hv), f32)]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=FWD_VMEM_LIMIT),
            interpret=interpret,
            name="otpu_flash_select_forward",
        )(_tile_flags(select, tile), *operands, select)
        return o.reshape(b, h, s, hv), lse.reshape(b, h, s)
    o, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((bh, s, hv), f32, vma=vma),
                   jax.ShapeDtypeStruct((bh, 1, s), f32, vma=vma)),
        grid=(bh, nt, reach),
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
