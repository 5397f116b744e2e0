"""Pallas kernels of learned sparse attention (DeepSeek-V3.2's DSA: a
lightning indexer scores every earlier key, a query attends to its
``topk`` best): what stands beside the two flash kernels in
``parallel/dsa.dsa_attention`` where Mosaic compiles (a TPU; the CPU
runs the ``jnp`` twins in ``parallel/model``).

- ``index_select``: a tile of query rows against every earlier key.  The
  index scores ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` are made
  by chunks of keys into VMEM scratch and never leave it; the row's
  ``k``-th largest is found **exactly** by counting passes over the
  scratch (a bisection on the scores' bits: 32 passes, then one over the
  key's index among the scores that tie with it, so that a row selects
  exactly ``min(t + 1, topk)`` keys whatever ties); written are the
  selection **packed, eight keys a byte** ((b, s, s / 8) int8,
  query-major: ``ops/flash_attention``'s head has the layout, which the
  write pass makes plane by plane and every reader unpacks in VMEM), and
  each row's logsumexp of its selected scores.
- ``index_loss``: the indexer's alignment loss and its gradient in one
  pass over the causal tile pairs: ``KL(pbar[t, .] || softmax_S(I[t, .]))``
  a row, ``pbar`` the attention probabilities of the selected keys
  averaged over the query heads (made again from q, k and the flash
  forward's logsumexp: one more ``q k^T`` a layer), and the gradient
  ``softmax_S(I) - pbar`` taken through relu to ``qI``, ``kI`` and ``w``.
  Scores are held transposed, (kv, q), as the flash backward holds them:
  a row's statistics are row vectors, and the selection is read packed and
  key-major, (b, s / 8, s).
- ``pack_selection`` / ``unpack_selection``: the format's two ends outside
  a kernel (a given mask enters ``select=`` through the first; the ``jnp``
  twins read their blocks through the second); ``selection_bytes`` the
  same selection as ``jnp.packbits`` would pack it.

Everything after a product is float32; matmul inputs are the arrays' own
type (bfloat16 in a step).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ompi_tpu.base.jaxenv import pallas_interpret
from ompi_tpu.ops.flash_attention import (_select_blocks, _selected, _tile,
                                          select_lanes)

#: query rows a grid step of ``index_select``: their scores against every
#: key are (256, 16,384) int32 of VMEM scratch, 16 MiB
SELECT_ROWS = 256
#: keys a counting pass of ``index_select`` walks at a time (its write pass
#: walks a group of the packed selection, plane by plane)
SELECT_CHUNK = 512
#: the scratch, the selection's two buffers and a chunk's temporaries
SELECT_VMEM_LIMIT = 64 << 20
#: ``index_loss``'s tile: so many q positions against so many kv positions
LOSS_TILE = 512
LOSS_VMEM_LIMIT = 64 << 20
LANES = 128
INT_MIN = -2 ** 31


def ordered_bits(f):
    """float32 -> int32 whose signed order is the floats' (-0.0 just below
    0.0): what the counting passes compare."""
    b = jax.lax.bitcast_convert_type(f, jnp.int32)
    return jnp.where(b >= 0, b, b ^ jnp.int32(0x7FFFFFFF))


def ordered_floats(k):
    """``ordered_bits``'s inverse."""
    return jax.lax.bitcast_convert_type(
        jnp.where(k >= 0, k, k ^ jnp.int32(0x7FFFFFFF)), jnp.float32)


def _plane_bit(m):
    """Bit ``m`` of a byte (an int, or an array of them) as the int32
    whose low byte it is, in int8's range: what a byte's eight bits add up
    to before the cast to int8."""
    return jnp.where(m == 7, -128, jnp.left_shift(1, m)).astype(jnp.int32)


def pack_selection(mask):
    """A mask (b, r, s), key u visible to row t iff ``mask[b, t, u]`` is
    not 0, as a packed selection (b, r, s / 8) int8 in the kernels' layout
    (``ops/flash_attention``'s head): what ``index_select`` writes and
    ``select=`` takes.  ``s`` a multiple of 8."""
    *lead, s = mask.shape
    lanes = select_lanes(s)
    planes = (mask != 0).reshape(*lead, s // (8 * lanes), 8, lanes)
    byte = sum(jnp.where(planes[..., m, :], _plane_bit(m), 0)
               for m in range(8))
    return byte.astype(jnp.int8).reshape(*lead, s // 8)


def unpack_selection(packed, first=0, keys=None):
    """``pack_selection``'s inverse, by blocks: the boolean mask (b, r,
    ``keys``) of keys ``first`` .. ``first + keys`` (every key without
    them) of the packed rows (b, r, s / 8).  ``first``, a multiple of
    ``keys``, may be traced; a block is whole groups or whole planes of
    one (``_select_blocks``)."""
    lead, s = packed.shape[:-1], 8 * packed.shape[-1]
    keys = s if keys is None else keys
    lanes, nbytes, share = _select_blocks(s, keys)
    tile = first // keys
    block = jax.lax.dynamic_slice_in_dim(packed, (tile // share) * nbytes,
                                         nbytes, axis=-1)
    if share == 1:          # whole groups: a group a row, its planes in turn
        block = block.reshape(*lead, -1, lanes)
        keys = 8 * lanes
    return _selected(block, block.ndim - 1, lanes, keys, tile).reshape(
        *lead, -1)


def selection_bytes(packed):
    """A packed selection (b, r, s / 8) as ``jnp.packbits(mask, axis=-1,
    bitorder="little")`` packs its mask: uint8, key u in bit ``u % 8`` of
    byte ``u // 8`` (what a check reads as ``dsa_selection_seq``).  The
    bits of a group, unpacked in key order, times their weights: an int8
    product leaves keys on lanes, where ``packbits``' own reshape to (..,
    8) is a relayout of the whole mask on a TPU."""
    lanes = select_lanes(8 * packed.shape[-1])
    # a group a row, the groups of a row apart: a move of whole tiles, made
    # once of the bytes behind the barrier (without it XLA moves each of the
    # eight planes: 12.5 against 6.4 ms a step of Keye's, PERF.md section 6,
    # PR 61); int8 all along
    group = jax.lax.optimization_barrier(jnp.moveaxis(
        packed.reshape(-1, packed.shape[-1] // lanes, lanes), 1, 0))
    bits = jnp.concatenate([(group >> m) & 1 for m in range(8)], axis=-1)
    u = jnp.arange(8 * lanes)
    weights = jnp.where(u[:, None] // 8 == jnp.arange(lanes),
                        _plane_bit(u % 8)[:, None], 0).astype(jnp.int8)
    # a byte's bits are disjoint: every partial sum lies in int8's range
    out = jax.lax.dot_general(bits, weights, (((2,), (0,)), ((), ())),
                              preferred_element_type=jnp.int8)
    return jax.lax.bitcast_convert_type(
        jnp.moveaxis(out, 0, 1), jnp.uint8).reshape(packed.shape)


def _lane_sum(x):
    """(rows, n x 128) -> (rows, 128): the lane tiles added up on the
    VPU; the one cross-lane sum is the caller's, once a pass."""
    out = x[:, :LANES]
    for lo in range(LANES, x.shape[1], LANES):
        out = out + x[:, lo:lo + LANES]
    return out


def _index_select_kernel(topk, chunk, lanes, qi_ref, ki_ref, w_ref, sel_ref,
                         lse_ref, key_ref):
    """Query rows i R .. (i + 1) R of one sequence.  ``qi_ref`` (1, J, R,
    di), ``ki_ref`` (1, S, di), ``w_ref`` (1, R, J) float32, the scale in
    it; ``sel_ref`` (1, R, S / 8) int8, the rows' selection packed in
    groups of ``8 x lanes`` keys, ``lse_ref`` (1, 1, R); ``key_ref`` (R,
    S) int32 scratch: the scores' ordered bits, ``INT_MIN`` where a key
    lies behind the query."""
    i = pl.program_id(1)
    heads, rows = qi_ref.shape[1], qi_ref.shape[2]
    s = ki_ref.shape[1]
    f32, i32 = jnp.float32, jnp.int32
    t = i * rows + jax.lax.broadcasted_iota(i32, (rows, 1), 0)
    want = jnp.minimum(t + 1, topk).astype(f32)             # keys a row takes
    reach = ((i + 1) * rows + chunk - 1) // chunk           # chunks in reach
    nt_dims = (((1,), (1,)), ((), ()))

    def at(c):
        cols = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        col = c * chunk + jax.lax.broadcasted_iota(i32, (rows, chunk), 1)
        return cols, col

    def score(c, top):
        cols, col = at(c)
        kc = ki_ref[0, cols, :]
        acc = jnp.zeros((rows, chunk), f32)
        for j in range(heads):
            z = jax.lax.dot_general(qi_ref[0, j], kc, nt_dims,
                                    preferred_element_type=f32)
            acc = acc + w_ref[0, :, j:j + 1] * jnp.maximum(z, 0.0)
        seen = col <= t
        key_ref[:, cols] = jnp.where(seen, ordered_bits(acc), INT_MIN)
        return jnp.maximum(top, jnp.max(jnp.where(seen, acc, -jnp.inf),
                                        axis=1, keepdims=True))

    top = jax.lax.fori_loop(0, reach, score, jnp.full((rows, 1), -jnp.inf,
                                                      f32))

    def count(pred):
        """How many keys in reach of each row meet ``pred(key, col)``."""
        def body(c, n):
            cols, col = at(c)
            return n + _lane_sum(jnp.where(pred(key_ref[:, cols], col),
                                           1.0, 0.0))
        n = jax.lax.fori_loop(0, reach, body, jnp.zeros((rows, LANES), f32))
        return jnp.sum(n, axis=1, keepdims=True)

    # the k-th largest key, bit by bit from the top: in the order of the
    # keys less INT_MIN, which are unsigned; ``u`` is the largest such
    # number that ``want`` keys reach
    u = jnp.zeros((rows, 1), i32)
    for bit in range(31, -1, -1):
        cand = u | jnp.int32(INT_MIN if bit == 31 else 1 << bit)
        bar = cand ^ jnp.int32(INT_MIN)
        n = count(lambda key, col: key >= bar)
        u = jnp.where(n >= want, cand, u)
    tau = u ^ jnp.int32(INT_MIN)
    # the keys that tie with it: the first ``need`` of them by position
    need = want - count(lambda key, col: key > tau)
    last = jnp.zeros((rows, 1), i32)
    for bit in range((s - 1).bit_length() - 1, -1, -1):
        cand = last | jnp.int32(1 << bit)
        n = count(lambda key, col: jnp.logical_and(key == tau, col < cand))
        last = jnp.where(n < need, cand, last)

    def write(g, den):
        """Group g's keys, a bit plane of ``lanes`` keys at a time: plane
        m is bit m of the group's ``lanes`` bytes."""
        byte = jnp.zeros((rows, lanes), i32)
        for m in range(8):
            lo = pl.multiple_of((g * 8 + m) * lanes, lanes)
            col = lo + jax.lax.broadcasted_iota(i32, (rows, lanes), 1)
            key = key_ref[:, pl.ds(lo, lanes)]
            chosen = jnp.logical_and(col <= t, jnp.logical_or(
                key > tau, jnp.logical_and(key == tau, col <= last)))
            byte = byte | jnp.where(chosen, _plane_bit(m), 0)
            den = den + jnp.where(
                chosen, jnp.exp(ordered_floats(key) - top), 0.0)
        sel_ref[0, :, pl.ds(pl.multiple_of(g * lanes, lanes), lanes)] = \
            byte.astype(jnp.int8)
        return den

    den = jax.lax.fori_loop(0, s // (8 * lanes), write,
                            jnp.zeros((rows, lanes), f32))
    lse = top + jnp.log(jnp.sum(den, axis=1, keepdims=True))
    lse_ref[0] = jnp.broadcast_to(lse, (rows, LANES)).T[:1]


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def index_select(qi, ki, w, *, topk: int, interpret=None):
    """The selection of every query of ``qi`` (b, J, s, di) against the
    one index key a position ``ki`` (b, s, di) under the heads' weights
    ``w`` (b, s, J) float32 (the scale in them): (the selection packed
    eight keys a byte, (b, s, s / 8) int8 in the layout of
    ``ops/flash_attention``'s head (``unpack_selection`` reads it back):
    key u's bit of row t is set iff u is among the ``min(t + 1, topk)``
    keys u <= t of largest ``I[t, u] = sum_j w[t, j] relu(qi[t, j] .
    ki[u])``, a tie at the bar going to the earlier key; the logsumexp (b,
    s) float32 of each row's selected scores).  Exact: the bar is the row's
    k-th largest score, found by counting.  The ``jnp`` twin is
    ``parallel/dsa._index_select_blocks``."""
    if interpret is None:
        interpret = pallas_interpret()
    b, heads, s, di = qi.shape
    rows = _tile(s, SELECT_ROWS)
    chunk = _tile(s, SELECT_CHUNK)
    operands = [qi, ki, w]
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    sel, lse = pl.pallas_call(
        functools.partial(_index_select_kernel, topk, chunk,
                          select_lanes(s)),
        out_shape=(jax.ShapeDtypeStruct((b, s, s // 8), jnp.int8, vma=vma),
                   jax.ShapeDtypeStruct((b, 1, s), jnp.float32, vma=vma)),
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((1, heads, rows, di),
                               lambda g, i: (g, 0, i, 0)),
                  pl.BlockSpec((1, s, di), lambda g, i: (g, 0, 0)),
                  pl.BlockSpec((1, rows, heads), lambda g, i: (g, i, 0))],
        out_specs=(pl.BlockSpec((1, rows, s // 8), lambda g, i: (g, i, 0)),
                   pl.BlockSpec((1, 1, rows), lambda g, i: (g, 0, i))),
        scratch_shapes=[pltpu.VMEM((rows, s), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=SELECT_VMEM_LIMIT),
        interpret=interpret,
        name="otpu_dsa_index_select",
    )(*operands)
    return sel, lse.reshape(b, s)


def _index_loss_kernel(scale, rep, lanes, q_ref, k_ref, lse_ref, qi_ref,
                       ki_ref, wt_ref, ilse_ref, selt_ref, kl_ref, dqi_ref,
                       dwt_ref, dki_ref):
    """q tile i against kv tile j of one sequence, scores held (kv, q).
    ``q_ref`` (1, H, R, d), ``k_ref`` (1, G, C, d), ``lse_ref`` (1, H, 1,
    R); ``qi_ref`` (1, J, R, di), ``ki_ref`` (1, C, di), ``wt_ref`` (1, J,
    1, R), ``ilse_ref`` (1, 1, R), ``selt_ref`` (1, bytes, R) int8 (the kv
    tile's block of the packed selection, key-major: at 16,384 positions a
    group's 128 bytes, of whose eight planes a tile of 512 keys is four).
    ``kl_ref`` (1, 1, R), ``dqi_ref`` (1, J, R, di) and
    ``dwt_ref`` (1, J, 1, R) gather a q tile's kv tiles; ``dki_ref`` (1,
    1, C, di) is this pair's own part."""
    i, j = pl.program_id(1), pl.program_id(2)
    heads, index_heads = q_ref.shape[1], qi_ref.shape[1]
    f32 = jnp.float32
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=f32)
    nt_dims = (((1,), (1,)), ((), ()))
    nn_dims = (((1,), (0,)), ((), ()))
    tn_dims = (((0,), (0,)), ((), ()))

    @pl.when(j == 0)
    def _():
        kl_ref[...] = jnp.zeros(kl_ref.shape, f32)
        dqi_ref[...] = jnp.zeros(dqi_ref.shape, f32)
        dwt_ref[...] = jnp.zeros(dwt_ref.shape, f32)

    @pl.when(j > i)
    def _():
        dki_ref[...] = jnp.zeros(dki_ref.shape, f32)

    @pl.when(j <= i)
    def _():
        chosen = _selected(selt_ref[0], 0, lanes, ki_ref.shape[1], j)  # (C, R)

        def head(h, acc):
            sc = dot(k_ref[0, h // rep], q_ref[0, h], nt_dims) * scale
            return acc + jnp.exp(sc - lse_ref[0, h])
        pbar = jnp.where(chosen, jax.lax.fori_loop(
            0, heads, head, jnp.zeros(chosen.shape, f32)) / heads, 0.0)
        ki = ki_ref[0]

        def index(h, acc):
            z = dot(ki, qi_ref[0, h], nt_dims)
            return acc + wt_ref[0, h] * jnp.maximum(z, 0.0)
        logq = jax.lax.fori_loop(0, index_heads, index,
                                 jnp.zeros(chosen.shape, f32)) - ilse_ref[0]
        kl_ref[0] += jnp.sum(jnp.where(
            pbar > 0.0, pbar * (jnp.log(jnp.where(pbar > 0.0, pbar, 1.0))
                                - logq), 0.0), axis=0, keepdims=True)
        di = jnp.where(chosen, jnp.exp(jnp.where(chosen, logq, 0.0)) - pbar,
                       0.0)

        def back(h, dki):
            qh = qi_ref[0, h]
            z = dot(ki, qh, nt_dims)
            dwt_ref[0, h] += jnp.sum(di * jnp.maximum(z, 0.0), axis=0,
                                     keepdims=True)
            g = jnp.where(z > 0.0, di * wt_ref[0, h], 0.0).astype(qh.dtype)
            dqi_ref[0, h] += dot(g, ki, tn_dims)
            return dki + dot(g, qh, nn_dims)
        dki_ref[0, 0] = jax.lax.fori_loop(
            0, index_heads, back, jnp.zeros(dki_ref.shape[2:], f32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_loss(q, k, lse, qi, ki, w, ilse, sel, *, interpret=None):
    """The indexer's alignment loss by row and its gradient, in one pass:
    ``(kl (b, s), dqi (b, J, s, di), dki (b, s, di), dw (b, s, J))``, all
    float32, of ``kl[t] = sum_{u in S_t} pbar[t, u] (log pbar[t, u] -
    log softmax_{S_t}(I[t, .])[u])`` with ``S_t`` the keys ``sel`` (b, s,
    s / 8) selects (packed, query-major, as ``index_select`` writes it:
    the kernel reads it key-major, transposed here as bytes), ``I`` as
    ``index_select`` makes it of ``qi`` (b, J, s,
    di), ``ki`` (b, s, di) and ``w`` (b, s, J), ``ilse`` (b, s) its
    logsumexp over ``S_t``, and ``pbar[t, u]`` the mean over the query
    heads of ``exp(q[t, h] . k[u, g(h)] / sqrt(d) - lse[t, h])`` (q (b, H,
    s, d), k (b, G, s, d), ``lse`` (b, H, s): the flash forward's).  The
    gradients are ``sum_t kl[t]``'s with ``pbar`` a constant.  The ``jnp``
    twin is ``parallel/dsa._index_loss_blocks``."""
    if interpret is None:
        interpret = pallas_interpret()
    b, heads, s, d = q.shape
    groups = k.shape[1]
    index_heads, di = qi.shape[1], qi.shape[3]
    tile = _tile(s, LOSS_TILE)
    nt = s // tile
    f32 = jnp.float32
    # row vectors a head, and the selection key-major: scores are (kv, q)
    operands = [q, k, lse.reshape(b, heads, 1, s), qi, ki,
                jnp.swapaxes(w, 1, 2).reshape(b, index_heads, 1, s),
                ilse.reshape(b, 1, s), jnp.swapaxes(sel, 1, 2)]
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    kv = lambda g, i, j: jnp.minimum(i, j)
    lanes, nbytes, share = _select_blocks(s, tile)
    out = lambda shape: jax.ShapeDtypeStruct(shape, f32, vma=vma)
    kl, dqi, dwt, dki = pl.pallas_call(
        functools.partial(_index_loss_kernel, 1.0 / math.sqrt(d),
                          heads // groups, lanes),
        out_shape=(out((b, 1, s)), out((b, index_heads, s, di)),
                   out((b, index_heads, 1, s)), out((b, nt, s, di))),
        grid=(b, nt, nt),
        in_specs=[
            pl.BlockSpec((1, heads, tile, d), lambda g, i, j: (g, 0, i, 0)),
            pl.BlockSpec((1, groups, tile, d),
                         lambda g, i, j: (g, 0, kv(g, i, j), 0)),
            pl.BlockSpec((1, heads, 1, tile), lambda g, i, j: (g, 0, 0, i)),
            pl.BlockSpec((1, index_heads, tile, di),
                         lambda g, i, j: (g, 0, i, 0)),
            pl.BlockSpec((1, tile, di), lambda g, i, j: (g, kv(g, i, j), 0)),
            pl.BlockSpec((1, index_heads, 1, tile),
                         lambda g, i, j: (g, 0, 0, i)),
            pl.BlockSpec((1, 1, tile), lambda g, i, j: (g, 0, i)),
            pl.BlockSpec((1, nbytes, tile),
                         lambda g, i, j: (g, kv(g, i, j) // share, i))],
        out_specs=(
            pl.BlockSpec((1, 1, tile), lambda g, i, j: (g, 0, i)),
            pl.BlockSpec((1, index_heads, tile, di),
                         lambda g, i, j: (g, 0, i, 0)),
            pl.BlockSpec((1, index_heads, 1, tile),
                         lambda g, i, j: (g, 0, 0, i)),
            pl.BlockSpec((1, 1, tile, di), lambda g, i, j: (g, i, j, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=LOSS_VMEM_LIMIT),
        interpret=interpret,
        name="otpu_dsa_index_loss",
    )(*operands)
    return (kl.reshape(b, s), dqi, jnp.sum(dki, axis=1),
            jnp.swapaxes(dwt.reshape(b, index_heads, s), 1, 2))
