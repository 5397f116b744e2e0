"""Causal flash attention on the held heads: in full, under a sliding
window (``causal_flash_attention``), under a learned selection
(``selected_flash_attention``) or under block diffusion's mask over a
noisy and a clean copy of a sequence (``block_diffusion_flash_attention``:
the one mask here that is not under the diagonal), each a ``custom_vjp`` over
``ops/flash_attention``'s kernels with a ``jnp`` twin that the CPU and
the tests' references run, the backward pass's two walks of the block
pairs, and what a pass holds, from the shapes (``flash_on_kernels``,
``pass_counts``: an attention sublayer's ``plan`` reads them).  The
attention sublayers (``parallel/attention.py``, ``parallel/dsa.py``) stand
on it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ompi_tpu.parallel.layers import contract
from ompi_tpu.parallel.sublayer import on_mosaic


def flash_on_kernels(interpret: bool) -> tuple:
    """``(on_kernel, why)`` of causal attention's two passes, under any of
    this module's masks: ``ops/flash_attention``'s kernels wherever Mosaic
    compiles, every shape the callers send having tiles; their ``jnp``
    twins elsewhere."""
    return on_mosaic(interpret)


def _tri_bias(block: int):
    i = jnp.arange(block)
    return jnp.where(i[:, None] >= i[None, :], 0.0,
                     -jnp.inf).astype(jnp.float32)


def _group_blocks(a, n_kv: int, block: int):
    """A query-side array (b, h, s, ...) by blocks of ``block``
    positions, the ``h / n_kv`` query heads that share a key-value head
    folded into a block's rows: (blocks, b, n_kv, h / n_kv * block,
    ...).  The ``jnp`` twins' layout (the kernels group through their
    index maps): a group's rows meet its one k and v block in one
    contraction, and the sum over them is dk's and dv's own."""
    b, h, s = a.shape[:3]
    nb, rep = s // block, h // n_kv
    a = jnp.moveaxis(a.reshape(b, n_kv, rep, nb, block, *a.shape[3:]), 3, 0)
    return a.reshape(nb, b, n_kv, rep * block, *a.shape[5:])


def _ungroup_blocks(a, h: int):
    """``_group_blocks``'s inverse: (b, h, s, ...) again."""
    nb, b, n_kv, rows = a.shape[:4]
    block = rows * n_kv // h
    a = a.reshape(nb, b, n_kv, h // n_kv, block, *a.shape[4:])
    return jnp.moveaxis(a, 0, 3).reshape(b, h, nb * block, *a.shape[5:])


def _group_bias(block: int, rep: int):
    """The diagonal block's triangular bias for a group's folded rows."""
    return jnp.tile(_tri_bias(block), (rep, 1))


def _far_bias(block: int, rep: int):
    """A window's far block's bias for a group's folded rows: key column
    c visible to query row r iff c > r (the diagonal block's mirror)."""
    i = jnp.arange(block)
    return jnp.tile(jnp.where(i[:, None] < i[None, :], 0.0,
                              -jnp.inf).astype(jnp.float32), (rep, 1))


def _window_pairs(nb: int, w):
    """The (q block, kv block) pairs causal attention walks over ``nb``
    blocks, q block by q block, kv blocks ascending: kv blocks 0 .. i, or
    under a window of ``w`` blocks max(0, i - w) .. i.  The kernels' grid
    and the ``jnp`` twins walk these and no other."""
    return [(i, j) for i in range(nb)
            for j in range(0 if w is None else max(0, i - w), i + 1)]


def _window_in_blocks(window, block: int, length: int):
    """A static window in blocks: None where there is none or it covers
    the sequence (plain causal attention, bit for bit); else a whole
    number of blocks."""
    if window is None or window >= length:
        return None
    if window % block:
        raise ValueError(f"a window of {window} positions is no whole "
                         f"number of blocks of {block}")
    return window // block


def _select_bias(select, i, j, block: int, rep: int):
    """A selection's (q block i, kv block j) as a bias for a group's
    folded rows, (b, 1, rep x block, block): 0 where the packed ``select``
    (b, s, s / 8) says a key is visible, -inf elsewhere; the block's bits
    alone are unpacked.  ``i`` and ``j`` may be traced."""
    from ompi_tpu.ops.sparse_attention import unpack_selection

    rows = jax.lax.dynamic_slice_in_dim(select, i * block, block, axis=1)
    tile = unpack_selection(rows, j * block, block)
    bias = jnp.where(tile, 0.0, -jnp.inf).astype(jnp.float32)
    return jnp.tile(bias, (1, rep, 1))[:, None]


def _bd_bias(i, j, block: int, rep: int, bl: int, half: int):
    """Block diffusion's (q block i, kv block j) as a bias for a group's
    folded rows, (rep x block, block): 0 where the key is visible
    (``ops/flash_attention._bd_mask``: the kernels' own rule), -inf
    elsewhere.  ``i`` and ``j`` may be traced; ``half`` is the blocks a
    half."""
    from ompi_tpu.ops.flash_attention import _bd_mask

    seen = _bd_mask((block, block), 0, (i % half) * block,
                    (j % half) * block, i < half, j < half, bl)
    return jnp.tile(jnp.where(seen, 0.0, -jnp.inf).astype(jnp.float32),
                    (rep, 1))


def _causal_fwd_blocks(q, k, v, block, interpret, window=None, select=None,
                       bd=None, scale=None):
    """Causal attention's forward pass: (o float32, logsumexp float32)
    of q (b, h, s, hd), k (b, n_kv, s, hd) and v (b, n_kv, s, hv): each
    key-value head is read by ``h / n_kv`` consecutive query heads, and
    v, and so the numerator and o, may be of another width than q and k
    (latent attention: 192 and 128).  Where Mosaic compiles
    (``interpret`` false: a TPU) it is one call of
    ``ops/flash_attention.flash_causal_forward``, which takes the three
    whole.  Elsewhere (the CPU) it is the loop below, that kernel's
    ``jnp`` twin: q block i of a group's query heads meets kv blocks
    0..i of ``block`` positions, the diagonal one under a triangular
    bias, each through one online-softmax update with float32 scores;
    the running max, numerator and denominator are float32 whatever q,
    k, v are.  Under a static ``window`` (positions; a whole number w of
    blocks) q block i meets kv blocks max(0, i - w) .. i, the far one (i
    - w) under ``_far_bias``; its last query row sees nothing of it, and
    that row's running max stays -inf through it.  Under ``select`` (b,
    s, s / 8) int8 (a data-dependent selection that holds causality,
    packed eight keys a byte as ``ops/sparse_attention.pack_selection``
    packs a mask; None: everything here is what it was) every block pair
    goes under its tile of the selection (``_select_bias``) and under no
    mask by position, and any row may see nothing of any block.  Under
    ``bd`` (block diffusion's block length: the rows are a noisy half
    before a clean one) q block i meets the kv blocks ``bd_pairs`` lists
    for it, each under ``_bd_bias``.  ``scale`` (static; None: ``1 /
    sqrt(hd)``, and every call is the one without the argument) is the
    scores' own, handed to the kernel or multiplied in here."""
    w = _window_in_blocks(window, block, q.shape[2])
    if flash_on_kernels(interpret)[0]:
        from ompi_tpu.ops.flash_attention import flash_causal_forward

        if bd is not None:
            return flash_causal_forward(q, k, v, block=block,
                                        interpret=False, bd=bd, scale=scale)
        if select is not None:
            return flash_causal_forward(q, k, v, block=block,
                                        interpret=False, select=select,
                                        scale=scale)
        return flash_causal_forward(q, k, v, block=block, interpret=False,
                                    window=None if w is None else window,
                                    scale=scale)
    h, s, hd = q.shape[1:]
    nb = s // block
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    bias = _group_bias(block, h // k.shape[1])
    qb = _group_blocks(q, k.shape[1], block)
    outs, lses = [], []
    pairs = _walked_pairs(nb, block, w, bd)
    for i in range(nb):
        qi = qb[i]
        zero = (qi[..., 0] * 0).astype(jnp.float32)    # carries q's vma
        m, den = zero - jnp.inf, zero
        num = jnp.zeros(v.shape[-1:], jnp.float32) + zero[..., None]
        for j in (j for at, j in pairs if at == i):
            kj = k[:, :, j * block:(j + 1) * block]
            vj = v[:, :, j * block:(j + 1) * block]
            sc = contract("bhqd,bhkd->bhqk", qi, kj, q.dtype) * scale
            if bd is not None:
                sc = sc + _bd_bias(i, j, block, h // k.shape[1], bd, nb // 2)
            elif select is not None:
                sc = sc + _select_bias(select, i, j, block, h // k.shape[1])
            elif j == i:
                sc = sc + bias
            far = select is None and bd is None and w is not None \
                and j == i - w
            if far:
                sc = sc + _far_bias(block, h // k.shape[1])
            new_m = at_m = jnp.maximum(m, sc.max(axis=-1))
            # a row that sees nothing yet (of a window's far block, or of
            # any block under a selection): exp(-inf - 0) = 0
            if far or select is not None or bd is not None:
                at_m = jnp.where(new_m == -jnp.inf, 0.0, new_m)
            c = jnp.exp(m - at_m)
            p = jnp.exp(sc - at_m[..., None])
            num = num * c[..., None] + contract("bhqk,bhkd->bhqd", p, vj,
                                                 q.dtype)
            den = den * c + p.sum(axis=-1)
            m = new_m
        outs.append(num / den[..., None])
        lses.append(m + jnp.log(den))
    return (_ungroup_blocks(jnp.stack(outs), h),
            _ungroup_blocks(jnp.stack(lses), h))


# what a layer's ``jax.checkpoint`` keeps of causal attention (an attention
# sublayer's ``keeps``): the forward kernel's two results, float32 as it
# writes them, which are all its backward pass reads beside q, k and v.  Named in the forward
# rule before anything reads them, so that a checkpointed layer's
# backward pass holds no second run of the kernel.
ATTN_OUT = "otpu_attn_out"
ATTN_LSE = "otpu_attn_lse"
ATTN_KEEPS = (ATTN_OUT, ATTN_LSE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def causal_flash_attention(q, k, v, block: int, interpret: bool,
                           window=None, scale=None):
    """Causal self-attention of q (b, h, s, hd), k (b, n_kv, s, hd) and
    v (b, n_kv, s, hv) whose length is a multiple of ``block``: k and v
    come with the model's own key-value heads, each shared by ``h /
    n_kv`` consecutive query heads, and are repeated nowhere; their
    gradients are the group's sums, made in float32.  Forward:
    ``_causal_fwd_blocks`` (on a TPU one kernel call, the blocks chosen
    in its index maps; on the CPU a ``jnp`` loop over the blocks).
    Backward: the flash backward by the same blocks (scores recomputed
    from q, k and the saved logsumexp in float32; no (s, s) array is
    ever held), its matmul inputs in q's dtype; on a TPU each block pair
    one call of the fused kernel (``_causal_bwd_fused``), on the CPU
    ``_bwd_pair``'s einsums.

    ``window`` (static; None: every earlier key) makes it sliding-window
    attention: key j is visible to query i iff 0 <= i - j < ``window``,
    a whole number of blocks.  Both passes then walk the block pairs a
    window can reach and no other (``_window_pairs``), the far pair under
    its own mask; a window that covers the sequence is None, bit for
    bit.  With None every branch, grid and kernel is what it was before
    the argument.  ``scale`` (static; None: ``1 / sqrt(hd)``) is the
    scores' scale where a model gives its own (granitemoehybrid's
    ``attention_multiplier``), in both passes."""
    return _causal_fwd_blocks(q, k, v, block, interpret, window,
                              scale=scale)[0]


def pass_counts(b: int, h: int, n_kv: int, s: int, block: int, window=None,
                bd=None, topk=None, doc: bool = False) -> dict:
    """The SPC counters one attention layer application moves (an
    attention sublayer's ``plan``; the backward rule is no second
    application), from the shapes: q (b, h, s, .) on ``n_kv`` key-value
    heads in blocks of ``block``.  ``attn_built`` 1;
    ``attn_shared_kv_built`` 1 where k and v come with fewer heads than q
    and go to the kernels, or their twins, that way; ``attn_window_built``
    1 under a ``window`` shorter than the sequence; ``attn_pairs_walked``
    the block pairs a pass walks and ``attn_pairs_causal`` those a full
    causal pass of its length would.  Under block diffusion's mask (``bd``)
    also ``bd_built`` 1, ``bd_pairs_visible`` the (query, key) pairs a
    pass attends to (``bd_visible_pairs``) and ``bd_pairs_causal`` those a
    causal pass over its rows would.  Under a learned selection (``topk``,
    the most keys a query selects) ``dsa_built`` 1, ``dsa_keys_selected``
    the (query, key) pairs attended to, ``min(t + 1, topk)`` a query,
    ``dsa_keys_causal`` those a full causal pass would, and
    ``dsa_mask_bytes`` the bytes of the selection a pass reads, packed
    eight keys a byte.  Under a document mask (``doc``) ``doc_built`` 1,
    the mask made (``document_selection``); nothing was chosen, and
    ``dsa_*`` count nothing."""
    nb = s // block
    w = _window_in_blocks(window, block, s)
    out = {"attn_built": 1, "attn_shared_kv_built": int(n_kv < h),
           "attn_window_built": int(w is not None),
           "attn_pairs_walked": len(_walked_pairs(nb, block, w, bd)),
           "attn_pairs_causal": nb * (nb + 1) // 2}
    if bd is not None:
        out.update(bd_built=1,
                   bd_pairs_visible=b * bd_visible_pairs(s // 2, bd),
                   bd_pairs_causal=b * s * (s + 1) // 2)
    if topk is not None:
        full = min(s, topk)
        out.update(dsa_built=1, dsa_mask_bytes=b * s * s // 8,
                   dsa_keys_selected=b * (full * (full + 1) // 2
                                          + (s - full) * topk),
                   dsa_keys_causal=b * s * (s + 1) // 2)
    if doc:
        out["doc_built"] = 1
    return out


def _causal_fwd(q, k, v, block, interpret, window=None, scale=None):
    o, lse = _causal_fwd_blocks(q, k, v, block, interpret, window,
                                scale=scale)
    o = checkpoint_name(o, ATTN_OUT)
    lse = checkpoint_name(lse, ATTN_LSE)
    return o, (q, k, v, o, lse)


#: up to this many blocks the backward pass's block pairs are unrolled
#: (10 pairs at OLMoE's 4 blocks: what that step has always compiled
#: to); beyond it they are walked by one ``lax.scan``, a pair's scores
#: held at a time.  Unrolled, the 36 pairs of 8 blocks let XLA hold 15
#: and more (h, block, block) float32 score blocks at once: 19.6 GB for
#: the JoyAI step (offline compile for a v5e, PR 35)
UNROLLED_BLOCKS = 4


def _bwd_pair(qi, kj, vj, doi, lse_i, delta_i, bias, scale, dt):
    """One block pair of the flash backward: (dq, dk, dv) parts.  A
    head of the query side is a key-value head's, its rows the group's
    (``_group_blocks``), so dk and dv sum the group in float32.
    ``bias``: the diagonal pair's, a window's far pair's, or None."""
    sc = contract("bhqd,bhkd->bhqk", qi, kj, dt) * scale
    if bias is not None:
        sc = sc + bias
    p = jnp.exp(sc - lse_i[..., None])
    dv = contract("bhqk,bhqd->bhkd", p, doi, dt)
    dp = contract("bhqd,bhkd->bhqk", doi, vj, dt)
    ds = p * (dp - delta_i[..., None]) * scale
    return (contract("bhqk,bhkd->bhqd", ds, kj, dt),
            contract("bhqk,bhqd->bhkd", ds, qi, dt), dv)


def _causal_bwd(block, interpret, window, res, do, select=None, bd=None,
                scale=None):
    q, k, v, o, lse = res
    h, n_kv = q.shape[1], k.shape[1]
    nb = q.shape[2] // block
    w = _window_in_blocks(window, block, q.shape[2])
    do = do.astype(jnp.float32)
    delta = jnp.sum(do * o, axis=-1)                     # (b, h, s)
    if flash_on_kernels(interpret)[0]:
        return _causal_bwd_fused(q, k, v, do, lse, delta, block, w, select,
                                 bd, scale)
    if nb > UNROLLED_BLOCKS:
        return _causal_bwd_scanned(q, k, v, do, lse, delta, block, w,
                                   select, bd, scale)
    dt = q.dtype
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    bias = _group_bias(block, h // n_kv)
    far = None if w is None else _far_bias(block, h // n_kv)
    qb, dob, lseb, deltab = (_group_blocks(a, n_kv, block)
                             for a in (q, do, lse, delta))
    cut = lambda a, i: a[:, :, i * block:(i + 1) * block]
    dq = [0.0] * nb
    dk = [0.0] * nb
    dv = [0.0] * nb
    for i, j in _walked_pairs(nb, block, w, bd):
        dq_c, dk_c, dv_c = _bwd_pair(
            qb[i], cut(k, j), cut(v, j), dob[i], lseb[i], deltab[i],
            _bd_bias(i, j, block, h // n_kv, bd, nb // 2) if bd is not None
            else _select_bias(select, i, j, block, h // n_kv)
            if select is not None
            else bias if j == i else far if i - j == w else None, scale, dt)
        dq[i], dk[j], dv[j] = dq[i] + dq_c, dk[j] + dk_c, dv[j] + dv_c
    cat = lambda parts: jnp.concatenate(parts, axis=2).astype(dt)
    return _ungroup_blocks(jnp.stack(dq), h).astype(dt), cat(dk), cat(dv)


def _walked_pairs(nb: int, block: int, w, bd) -> list:
    """The (q block, kv block) pairs a pass walks: ``_window_pairs``, or
    under block diffusion's mask those of ``bd_pairs``."""
    if bd is None:
        return _window_pairs(nb, w)
    from ompi_tpu.ops.flash_attention import bd_pairs

    return [(i, j) for i, j, _ in bd_pairs(nb, block, bd)]


def _causal_bwd_scanned(q, k, v, do, lse, delta, block, w=None, select=None,
                        bd=None, scale=None):
    """The same pairs in the same order (q block by q block, kv blocks
    ascending), one a step of a ``lax.scan`` over float32 accumulators."""
    dt = q.dtype
    h, n_kv = q.shape[1], k.shape[1]
    nb = q.shape[2] // block
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    tri = _group_bias(block, h // n_kv)
    qb, dob, lseb, deltab = (_group_blocks(a, n_kv, block)
                             for a in (q, do, lse, delta))
    kb, vb = (_group_blocks(a, n_kv, block) for a in (k, v))
    pairs = _walked_pairs(nb, block, w, bd)
    zero = lambda a: (a * 0).astype(jnp.float32)         # carries a's vma

    def step(acc, ij):
        i, j = ij
        bias = jnp.where(i == j, tri, 0.0)
        if w is not None:
            bias = jnp.where(i - j == w, _far_bias(block, h // n_kv), bias)
        if select is not None:
            bias = _select_bias(select, i, j, block, h // n_kv)
        if bd is not None:
            bias = _bd_bias(i, j, block, h // n_kv, bd, nb // 2)
        dq_c, dk_c, dv_c = _bwd_pair(
            qb[i], kb[j], vb[j], dob[i], lseb[i], deltab[i], bias, scale,
            dt)
        dq, dk, dv = acc
        return (dq.at[i].add(dq_c), dk.at[j].add(dk_c),
                dv.at[j].add(dv_c)), None

    (dq, dk, dv), _ = jax.lax.scan(
        step, (zero(qb), zero(kb), zero(vb)),
        (jnp.asarray([p[0] for p in pairs]),
         jnp.asarray([p[1] for p in pairs])))
    return (_ungroup_blocks(dq, h).astype(dt),
            _ungroup_blocks(dk, n_kv).astype(dt),
            _ungroup_blocks(dv, n_kv).astype(dt))


def _causal_bwd_fused(q, k, v, do, lse, delta, block, w=None, select=None,
                      bd=None, scale=None):
    """The same pairs in the same order, each one call of the fused
    Pallas kernel (``ops/flash_attention.attn_block_backward``, whose
    ``jnp`` twin is ``_bwd_pair``): a pair's scores never leave VMEM,
    and the float32 accumulators pass through every call in place, dk's
    and dv's with k's and v's own heads.  Both walks: unrolled up to
    ``UNROLLED_BLOCKS`` blocks, one ``lax.scan`` beyond; the arrays go
    in whole and the pair is an operand, so neither slices.  Under a
    selection the kernel reads the packed bytes key-major, as it holds
    the scores: (b, s / 8, s), transposed once here, beside the pairs'
    flags.  Under block diffusion's mask the operand is one of
    ``bd_pairs``' triples, the pair's kind behind it."""
    from ompi_tpu.ops.flash_attention import (_tile_flags,
                                              attn_block_backward, bd_pairs)

    dt = q.dtype
    nb = q.shape[2] // block
    do = do.astype(dt)                  # what ``contract`` makes of it
    if select is not None:
        select = (jnp.swapaxes(select, 1, 2), _tile_flags(select, block))
        pair = lambda acc, ij: attn_block_backward(
            ij, q, k, v, do, lse, delta, *acc, block=block, interpret=False,
            select=select, scale=scale)
    elif bd is not None:
        pair = lambda acc, ij: attn_block_backward(
            ij, q, k, v, do, lse, delta, *acc, block=block, interpret=False,
            bd=bd, scale=scale)
    else:
        pair = lambda acc, ij: attn_block_backward(
            ij, q, k, v, do, lse, delta, *acc, block=block,
            interpret=False, window=None if w is None else w * block,
            scale=scale)
    pairs = _window_pairs(nb, w) if bd is None else bd_pairs(nb, block, bd)
    acc = tuple(jnp.zeros(a.shape, jnp.float32) for a in (q, k, v))
    vma = tuple(frozenset().union(*(jax.typeof(a).vma
                                    for a in (q, k, v, do))))
    if vma:                 # the carry varies as the kernel's results do
        acc = jax.lax.pcast(acc, vma, to="varying")
    if nb > UNROLLED_BLOCKS:
        acc, _ = jax.lax.scan(lambda acc, ij: (pair(acc, ij), None), acc,
                              jnp.asarray(pairs, jnp.int32))
    else:
        for ij in pairs:
            acc = pair(acc, jnp.asarray(ij, jnp.int32))
    return tuple(a.astype(dt) for a in acc)


def _causal_bwd_rule(block, interpret, window, scale, res, do):
    return _causal_bwd(block, interpret, window, res, do, scale=scale)


causal_flash_attention.defvjp(_causal_fwd, _causal_bwd_rule)


# -- learned sparse attention (DeepSeek-V3.2's DSA) ---------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def selected_flash_attention(q, k, v, select, block: int, interpret: bool,
                             topk: int, scale=None):
    """``causal_flash_attention`` under a data-dependent selection:
    ``select`` (b, s, s / 8) int8, query-major and packed eight keys a
    byte (``ops/sparse_attention.index_select`` writes it so;
    ``pack_selection`` packs a given mask), says which keys u <= t query
    t attends to (every row selects a key).  Returns (o (b, h, s, hv)
    float32, the logsumexp (b, h, s) float32 over the selected keys);
    ``topk``, the most keys a row selects, is read by nothing here (a
    sublayer's plan counts by it: ``pass_counts``).
    No gradient passes through the selection, and none through the
    logsumexp handed out (what reads it reads a constant).  Both passes
    walk every causal block pair under its tile of the selection
    (``_causal_fwd_blocks``, ``_causal_bwd``: the same kernels and twins),
    a pair that selects nothing passed over by the kernels.  A **document
    mask** of a packed row is such a selection (``document_selection``:
    key u <= t of query t's document), ``topk`` then None.
    ``scale`` as ``causal_flash_attention``'s."""
    return _causal_fwd_blocks(q, k, v, block, interpret, select=select,
                              scale=scale)


def _selected_fwd(q, k, v, select, block, interpret, topk, scale=None):
    o, lse = _causal_fwd_blocks(q, k, v, block, interpret, select=select,
                                scale=scale)
    o = checkpoint_name(o, ATTN_OUT)
    lse = checkpoint_name(lse, ATTN_LSE)
    return (o, lse), (q, k, v, o, lse, select)


def _selected_bwd(block, interpret, topk, scale, res, cts):
    *res, select = res
    return (*_causal_bwd(block, interpret, None, tuple(res), cts[0],
                         select=select, scale=scale), None)


def document_selection(doc):
    """A packed row's document mask as a selection for
    ``selected_flash_attention``: (b, s, s / 8) int8, key u visible to
    query t iff ``u <= t`` and ``doc_u == doc_t`` (``doc`` (b, s) int32, a
    position's document).  Every row selects its own key; a tile pair
    wholly across a boundary selects nothing and is passed over by the
    kernels' flags.  32 MB of bits a row of 16,384."""
    from ompi_tpu.ops.sparse_attention import pack_selection

    t = jnp.arange(doc.shape[1])
    return pack_selection((t[:, None] >= t[None, :])
                          & (doc[:, :, None] == doc[:, None, :]))


selected_flash_attention.defvjp(_selected_fwd, _selected_bwd)


# -- block diffusion (BD3-LM's training pass; SDAR) ---------------------------
def bd_visible_pairs(length: int, bl: int) -> int:
    """The (query, key) pairs one sequence of ``length`` tokens in blocks
    of ``bl`` attends to under block diffusion's mask over its ``2 x
    length`` rows: the noisy half against itself by blocks, against the
    clean half strictly before its block, the clean half against itself up
    to its block."""
    n = length // bl
    return length * bl + bl * bl * (n * (n - 1) // 2 + n * (n + 1) // 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def block_diffusion_flash_attention(q, k, v, block: int, interpret: bool,
                                    bl: int):
    """``causal_flash_attention`` under **block diffusion's mask**: the
    rows of q (b, h, 2L, hd), k (b, n_kv, 2L, hd) and v (b, n_kv, 2L, hv)
    are a noisy copy of a sequence of L tokens before its clean copy, both
    at positions 0 .. L - 1 (the caller's RoPE says so; nothing here reads
    a position but through the mask), L a multiple of ``block``, and with
    ``blk = position // bl`` query row i sees key row j iff both are noisy
    and ``blk(j) == blk(i)`` (its own block, both ways: a **later** key
    too), or i is noisy, j clean and ``blk(j) < blk(i)``, or both are clean
    and ``blk(j) <= blk(i)``; a clean row sees no noisy one.  Both passes
    walk the block pairs that hold a visible entry and no other
    (``ops/flash_attention.bd_pairs``: static, 80 of a causal walk's 136
    at 16 blocks), a pair wholly visible under no mask, the others under
    ``_bd_mask`` of their positions, never an array.  Returns o (b, h, 2L,
    hv) float32; the forward keeps o and the logsumexp (``ATTN_KEEPS``)
    and the backward recomputes the scores under the same mask.  A
    sibling of ``selected_flash_attention``: ``causal_flash_attention``
    and everything it compiles to are untouched."""
    return _causal_fwd_blocks(q, k, v, block, interpret, bd=bl)[0]


def _bd_fwd(q, k, v, block, interpret, bl):
    if (q.shape[2] // 2) % block:
        raise ValueError(f"a half of {q.shape[2] // 2} rows is no whole "
                         f"number of blocks of {block}")
    o, lse = _causal_fwd_blocks(q, k, v, block, interpret, bd=bl)
    o = checkpoint_name(o, ATTN_OUT)
    lse = checkpoint_name(lse, ATTN_LSE)
    return o, (q, k, v, o, lse)


def _bd_bwd(block, interpret, bl, res, do):
    return _causal_bwd(block, interpret, None, res, do, bd=bl)


block_diffusion_flash_attention.defvjp(_bd_fwd, _bd_bwd)
