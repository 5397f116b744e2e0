"""A public model's sublayers (OLMoE, JoyAI-LLM-Flash, Nemotron-3-Super,
LFM2-8B-A1B, Qwen3-Next-80B-A3B, SmallThinker-21BA3B, Keye-VL-2.0-30B-A3B's
language model): causal flash
attention, in full, under a sliding window or under a learned selection,
with its two walks of the
block pairs, the three attention sublayers, learned sparse attention (a
lightning indexer, its exact top-k and its alignment loss), Mamba-2's
chunked scan and mixer, LFM2's gated short convolution, Gated DeltaNet's
chunked rule and operator, and ``decoder_layer``, which chooses a layer's
sublayers by what it holds.
The primitives come from ``parallel/layers.py`` and the expert blocks
from ``parallel/experts.py``; ``parallel/train.py`` builds the step on
``decoder_layer``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ompi_tpu.parallel import experts
from ompi_tpu.parallel.layers import (cast_param, l2norm, layernorm, matmul,
                                      project_rope, rmsnorm_gain, rope,
                                      swiglu)
from ompi_tpu.runtime import spc


def _tri_bias(block: int):
    i = jnp.arange(block)
    return jnp.where(i[:, None] >= i[None, :], 0.0,
                     -jnp.inf).astype(jnp.float32)


def _contract(eq, a, b, compute_dtype):
    """A float32 einsum of blocked attention, its inputs in
    ``compute_dtype``."""
    if jnp.dtype(compute_dtype) == jnp.float32:
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(eq, a.astype(compute_dtype), b.astype(compute_dtype),
                      preferred_element_type=jnp.float32)


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
    folded rows, (b, 1, rep x block, block): 0 where ``select`` (b, s, s)
    says a key is visible, -inf elsewhere.  ``i`` and ``j`` may be
    traced."""
    b, s, _ = select.shape
    nb = s // block
    tile = select.reshape(b, nb, block, nb, block)[:, i, :, j]
    bias = jnp.where(tile != 0, 0.0, -jnp.inf).astype(jnp.float32)
    return jnp.tile(bias, (1, rep, 1))[:, None]


def _causal_fwd_blocks(q, k, v, block, interpret, window=None, select=None):
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
    s, s) int8 (a data-dependent selection that holds causality; None:
    everything here is what it was) every block pair goes under its tile
    of the selection (``_select_bias``) and under no mask by position,
    and any row may see nothing of any block."""
    w = _window_in_blocks(window, block, q.shape[2])
    if not interpret:
        from ompi_tpu.ops.flash_attention import flash_causal_forward

        if select is not None:
            return flash_causal_forward(q, k, v, block=block,
                                        interpret=False, select=select)
        return flash_causal_forward(q, k, v, block=block, interpret=False,
                                    window=None if w is None else window)
    h, s, hd = q.shape[1:]
    nb = s // block
    scale = 1.0 / math.sqrt(hd)
    bias = _group_bias(block, h // k.shape[1])
    qb = _group_blocks(q, k.shape[1], block)
    outs, lses = [], []
    for i in range(nb):
        qi = qb[i]
        zero = (qi[..., 0] * 0).astype(jnp.float32)    # carries q's vma
        m, den = zero - jnp.inf, zero
        num = jnp.zeros(v.shape[-1:], jnp.float32) + zero[..., None]
        for j in range(0 if w is None else max(0, i - w), i + 1):
            kj = k[:, :, j * block:(j + 1) * block]
            vj = v[:, :, j * block:(j + 1) * block]
            sc = _contract("bhqd,bhkd->bhqk", qi, kj, q.dtype) * scale
            if select is not None:
                sc = sc + _select_bias(select, i, j, block, h // k.shape[1])
            elif j == i:
                sc = sc + bias
            far = select is None and w is not None and j == i - w
            if far:
                sc = sc + _far_bias(block, h // k.shape[1])
            new_m = at_m = jnp.maximum(m, sc.max(axis=-1))
            # a row that sees nothing yet (of a window's far block, or of
            # any block under a selection): exp(-inf - 0) = 0
            if far or select is not None:
                at_m = jnp.where(new_m == -jnp.inf, 0.0, new_m)
            c = jnp.exp(m - at_m)
            p = jnp.exp(sc - at_m[..., None])
            num = num * c[..., None] + _contract("bhqk,bhkd->bhqd", p, vj,
                                                 q.dtype)
            den = den * c + p.sum(axis=-1)
            m = new_m
        outs.append(num / den[..., None])
        lses.append(m + jnp.log(den))
    return (_ungroup_blocks(jnp.stack(outs), h),
            _ungroup_blocks(jnp.stack(lses), h))


# what a layer's ``jax.checkpoint`` keeps of causal attention
# (``train.layer_checkpoint_policy`` saves these beside an expert block's):
# the forward kernel's two results, float32 as it writes them, which are
# all its backward pass reads beside q, k and v.  Named in the forward
# rule before anything reads them, so that a checkpointed layer's
# backward pass holds no second run of the kernel.
ATTN_OUT = "otpu_attn_out"
ATTN_LSE = "otpu_attn_lse"
# and of a learned sparse attention sublayer (``dsa_attention``): the
# selection (an int8 mask; made again it costs the index scores and the
# counting passes, and a second choice need not be the first), its rows'
# logsumexp, and the alignment loss's rows and gradients, which its one
# pass makes together
DSA_SELECTION = "otpu_dsa_selection"
DSA_INDEX_LSE = "otpu_dsa_index_lse"
DSA_LOSS = "otpu_dsa_loss"
CHECKPOINT_KEEPS = (ATTN_OUT, ATTN_LSE, DSA_SELECTION, DSA_INDEX_LSE,
                    DSA_LOSS)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_flash_attention(q, k, v, block: int, interpret: bool,
                           window=None):
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
    the argument."""
    return _causal_fwd_blocks(q, k, v, block, interpret, window)[0]


def _count_built(q, k, block, window) -> None:
    """SPC ``attn_built``: the causal attention passes made, forward
    rule or backward rule, while steps were traced (JAX traces a pass
    more than once); ``attn_shared_kv_built``: those of them whose k and
    v came with fewer heads than q and went to the kernels, or their
    twins, that way; ``attn_window_built``: those made under a window;
    ``attn_pairs_walked`` the block pairs the passes walk and
    ``attn_pairs_causal`` those full causal passes of their lengths
    would."""
    nb = q.shape[2] // block
    w = _window_in_blocks(window, block, q.shape[2])
    spc.record("attn_built", 1)
    if k.shape[1] < q.shape[1]:
        spc.record("attn_shared_kv_built", 1)
    if w is not None:
        spc.record("attn_window_built", 1)
    spc.record("attn_pairs_walked", len(_window_pairs(nb, w)))
    spc.record("attn_pairs_causal", nb * (nb + 1) // 2)


def _causal_fwd(q, k, v, block, interpret, window=None):
    _count_built(q, k, block, window)
    o, lse = _causal_fwd_blocks(q, k, v, block, interpret, window)
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
    sc = _contract("bhqd,bhkd->bhqk", qi, kj, dt) * scale
    if bias is not None:
        sc = sc + bias
    p = jnp.exp(sc - lse_i[..., None])
    dv = _contract("bhqk,bhqd->bhkd", p, doi, dt)
    dp = _contract("bhqd,bhkd->bhqk", doi, vj, dt)
    ds = p * (dp - delta_i[..., None]) * scale
    return (_contract("bhqk,bhkd->bhqd", ds, kj, dt),
            _contract("bhqk,bhqd->bhkd", ds, qi, dt), dv)


def _causal_bwd(block, interpret, window, res, do, select=None):
    q, k, v, o, lse = res
    _count_built(q, k, block, window)
    h, n_kv = q.shape[1], k.shape[1]
    nb = q.shape[2] // block
    w = _window_in_blocks(window, block, q.shape[2])
    do = do.astype(jnp.float32)
    delta = jnp.sum(do * o, axis=-1)                     # (b, h, s)
    if not interpret:
        return _causal_bwd_fused(q, k, v, do, lse, delta, block, w, select)
    if nb > UNROLLED_BLOCKS:
        return _causal_bwd_scanned(q, k, v, do, lse, delta, block, w,
                                   select)
    dt = q.dtype
    scale = 1.0 / math.sqrt(q.shape[-1])
    bias = _group_bias(block, h // n_kv)
    far = None if w is None else _far_bias(block, h // n_kv)
    qb, dob, lseb, deltab = (_group_blocks(a, n_kv, block)
                             for a in (q, do, lse, delta))
    cut = lambda a, i: a[:, :, i * block:(i + 1) * block]
    dq = [0.0] * nb
    dk = [0.0] * nb
    dv = [0.0] * nb
    for i, j in _window_pairs(nb, w):
        dq_c, dk_c, dv_c = _bwd_pair(
            qb[i], cut(k, j), cut(v, j), dob[i], lseb[i], deltab[i],
            _select_bias(select, i, j, block, h // n_kv)
            if select is not None
            else bias if j == i else far if i - j == w else None, scale, dt)
        dq[i], dk[j], dv[j] = dq[i] + dq_c, dk[j] + dk_c, dv[j] + dv_c
    cat = lambda parts: jnp.concatenate(parts, axis=2).astype(dt)
    return _ungroup_blocks(jnp.stack(dq), h).astype(dt), cat(dk), cat(dv)


def _causal_bwd_scanned(q, k, v, do, lse, delta, block, w=None, select=None):
    """The same pairs in the same order (q block by q block, kv blocks
    ascending), one a step of a ``lax.scan`` over float32 accumulators."""
    dt = q.dtype
    h, n_kv = q.shape[1], k.shape[1]
    nb = q.shape[2] // block
    scale = 1.0 / math.sqrt(q.shape[-1])
    tri = _group_bias(block, h // n_kv)
    qb, dob, lseb, deltab = (_group_blocks(a, n_kv, block)
                             for a in (q, do, lse, delta))
    kb, vb = (_group_blocks(a, n_kv, block) for a in (k, v))
    pairs = _window_pairs(nb, w)
    zero = lambda a: (a * 0).astype(jnp.float32)         # carries a's vma

    def step(acc, ij):
        i, j = ij
        bias = jnp.where(i == j, tri, 0.0)
        if w is not None:
            bias = jnp.where(i - j == w, _far_bias(block, h // n_kv), bias)
        if select is not None:
            bias = _select_bias(select, i, j, block, h // n_kv)
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


def _causal_bwd_fused(q, k, v, do, lse, delta, block, w=None, select=None):
    """The same pairs in the same order, each one call of the fused
    Pallas kernel (``ops/flash_attention.attn_block_backward``, whose
    ``jnp`` twin is ``_bwd_pair``): a pair's scores never leave VMEM,
    and the float32 accumulators pass through every call in place, dk's
    and dv's with k's and v's own heads.  Both walks: unrolled up to
    ``UNROLLED_BLOCKS`` blocks, one ``lax.scan`` beyond; the arrays go
    in whole and the pair is an operand, so neither slices.  Under a
    selection the kernel reads the mask key-major, as it holds the
    scores: transposed once here, beside the pairs' flags."""
    from ompi_tpu.ops.flash_attention import (_tile_flags,
                                              attn_block_backward)

    dt = q.dtype
    nb = q.shape[2] // block
    do = do.astype(dt)                  # what ``_contract`` makes of it
    if select is not None:
        select = (jnp.swapaxes(select, 1, 2), _tile_flags(select, block))
        pair = lambda acc, ij: attn_block_backward(
            ij, q, k, v, do, lse, delta, *acc, block=block, interpret=False,
            select=select)
    else:
        pair = lambda acc, ij: attn_block_backward(
            ij, q, k, v, do, lse, delta, *acc, block=block,
            interpret=False, window=None if w is None else w * block)
    pairs = _window_pairs(nb, w)
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


causal_flash_attention.defvjp(_causal_fwd, _causal_bwd)


# -- learned sparse attention (DeepSeek-V3.2's DSA) ---------------------------
def _count_dsa(q, topk: int) -> None:
    """SPC ``dsa_built``: the attention passes made under a selection,
    forward rule or backward rule, while steps were traced (as
    ``attn_window_built``); ``dsa_keys_selected`` the (query, key) pairs
    those passes attend to, ``min(t + 1, topk)`` a query, and
    ``dsa_keys_causal`` those full causal passes of their lengths would,
    both from the shapes."""
    b, _, s, _ = q.shape
    full = min(s, topk)
    spc.record("dsa_built", 1)
    spc.record("dsa_keys_selected",
               b * (full * (full + 1) // 2 + (s - full) * topk))
    spc.record("dsa_keys_causal", b * s * (s + 1) // 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def selected_flash_attention(q, k, v, select, block: int, interpret: bool,
                             topk: int):
    """``causal_flash_attention`` under a data-dependent selection:
    ``select`` (b, s, s) int8, query-major, says which keys u <= t query
    t attends to (every row selects a key).  Returns (o (b, h, s, hv)
    float32, the logsumexp (b, h, s) float32 over the selected keys);
    ``topk``, the most keys a row selects, is read by the counters alone.
    No gradient passes through the selection, and none through the
    logsumexp handed out (what reads it reads a constant).  Both passes
    walk every causal block pair under its tile of the selection
    (``_causal_fwd_blocks``, ``_causal_bwd``: the same kernels and twins),
    a pair that selects nothing passed over by the kernels."""
    return _causal_fwd_blocks(q, k, v, block, interpret, select=select)


def _selected_fwd(q, k, v, select, block, interpret, topk):
    _count_built(q, k, block, None)
    _count_dsa(q, topk)
    o, lse = _causal_fwd_blocks(q, k, v, block, interpret, select=select)
    o = checkpoint_name(o, ATTN_OUT)
    lse = checkpoint_name(lse, ATTN_LSE)
    return (o, lse), (q, k, v, o, lse, select)


def _selected_bwd(block, interpret, topk, res, cts):
    *res, select = res
    _count_dsa(res[0], topk)
    return (*_causal_bwd(block, interpret, None, tuple(res), cts[0],
                         select=select), None)


selected_flash_attention.defvjp(_selected_fwd, _selected_bwd)


def index_scores(qi, ki, w):
    """The lightning indexer's scores of query rows ``qi`` (b, J, r, di)
    against every key ``ki`` (b, s, di) under the heads' weights ``w`` (b,
    r, J) float32 (the scale in them): ``I[t, u] = sum_j w[t, j] relu(qi[t,
    j] . ki[u])`` (b, r, s) float32; the products' inputs in ``qi``'s type
    with float32 results, relu, the weights and the sum over the heads,
    head by head in their order, in float32 (the kernels' order)."""
    out = 0.0
    for j in range(qi.shape[1]):
        z = _contract("brd,bsd->brs", qi[:, j], ki, qi.dtype)
        out = out + w[:, :, j, None] * jnp.maximum(z, 0.0)
    return out


def select_topk(scores, first: int, topk: int):
    """The exact selection of query rows ``first`` .. of ``scores`` (b, r,
    s) float32: a boolean (b, r, s), true at the ``min(t + 1, topk)`` keys
    u <= t of largest score, a tie at the bar going to the earlier key.
    ``ops/sparse_attention.index_select``'s ``jnp`` twin, pass for pass:
    the bar is found by counting (the k-th largest of a row, bit by bit of
    the scores' ordered bits; then the last position among those that tie
    with it), which costs a row ``32 + log2(s)`` passes and never sorts."""
    from ompi_tpu.ops.sparse_attention import INT_MIN, ordered_bits

    b, r, s = scores.shape
    i32 = jnp.int32
    t = first + jnp.arange(r, dtype=i32)[:, None]
    col = jnp.arange(s, dtype=i32)[None, :]
    seen = col <= t
    key = jnp.where(seen, ordered_bits(scores), INT_MIN)
    want = jnp.minimum(t + 1, topk)
    count = lambda pred: jnp.sum(pred, axis=-1, keepdims=True, dtype=i32)
    u = jnp.zeros((b, r, 1), i32)
    for bit in range(31, -1, -1):
        cand = u | i32(INT_MIN if bit == 31 else 1 << bit)
        u = jnp.where(count(key >= (cand ^ i32(INT_MIN))) >= want, cand, u)
    tau = u ^ i32(INT_MIN)
    need = want - count(key > tau)
    last = jnp.zeros((b, r, 1), i32)
    for bit in range((s - 1).bit_length() - 1, -1, -1):
        cand = last | i32(1 << bit)
        last = jnp.where(count((key == tau) & (col < cand)) < need, cand,
                         last)
    return seen & ((key > tau) | ((key == tau) & (col <= last)))


def _index_select_blocks(qi, ki, w, topk: int, rows: int, interpret: bool):
    """(the selection (b, s, s) int8, each row's logsumexp over its
    selected scores (b, s) float32) of the indexer's ``qi`` (b, J, s, di),
    ``ki`` (b, s, di) and ``w`` (b, s, J).  Where Mosaic compiles one call
    of ``ops/sparse_attention.index_select``, which keeps a tile's scores
    in VMEM; elsewhere (the CPU) ``rows`` query rows at a time
    (``index_scores``, ``select_topk``), so that no (s, s, J) array and
    only one block's (rows, s) scores are ever held."""
    if not interpret:
        from ompi_tpu.ops.sparse_attention import index_select

        return index_select(qi, ki, w, topk=topk, interpret=False)
    b, heads, s, di = qi.shape
    rows = rows if s % rows == 0 else s
    nb = s // rows

    def block(xs):
        qb, wb, first = xs
        sc = index_scores(qb, ki, wb)
        chosen = select_topk(sc, first, topk)
        lse = jax.nn.logsumexp(jnp.where(chosen, sc, -jnp.inf), axis=-1)
        return chosen.astype(jnp.int8), lse

    sel, lse = jax.lax.map(block, (
        jnp.moveaxis(qi.reshape(b, heads, nb, rows, di), 2, 0),
        jnp.moveaxis(w.reshape(b, nb, rows, heads), 1, 0),
        jnp.arange(nb, dtype=jnp.int32) * rows))
    return (jnp.moveaxis(sel, 0, 1).reshape(b, s, s),
            jnp.moveaxis(lse, 0, 1).reshape(b, s))


def mean_attention_rows(qb, k, lse_b, chosen):
    """``pbar`` (b, r, s) float32 of query rows ``qb`` (b, h, r, d): the
    mean over the query heads of ``exp(q . k / sqrt(d) - lse)`` at the
    ``chosen`` keys (b, r, s), 0 elsewhere; ``k`` (b, n_kv, s, d),
    ``lse_b`` (b, h, r) the attention's own logsumexp."""
    b, h, r, d = qb.shape
    n_kv = k.shape[1]
    qg = qb.reshape(b, n_kv, h // n_kv, r, d)
    sc = _contract("bgerd,bgsd->bgers", qg, k, qb.dtype) / math.sqrt(d)
    p = jnp.exp(sc - lse_b.reshape(b, n_kv, h // n_kv, r)[..., None])
    return jnp.where(chosen, jnp.sum(p, axis=(1, 2)) / h, 0.0)


def _index_loss_rows(qi, ki, w, q, k, lse, select, rows: int):
    """The alignment loss by row (b, s), differentiable in ``qi``, ``ki``
    and ``w`` (``ops/sparse_attention.index_loss``'s ``jnp`` twin):
    ``KL(pbar[t, .] || softmax_S(I[t, .]))`` over the selected keys, a
    block of ``rows`` query rows at a time."""
    b, heads, s, di = qi.shape
    rows = rows if s % rows == 0 else s
    nb = s // rows
    by_rows = lambda a, axis: jnp.moveaxis(a.reshape(
        a.shape[:axis] + (nb, rows) + a.shape[axis + 1:]), axis, 0)

    def block(xs):
        qib, wb, qb, lse_b, sel_b = xs
        chosen = sel_b != 0
        sc = index_scores(qib, ki, wb)
        logq = sc - jax.nn.logsumexp(jnp.where(chosen, sc, -jnp.inf),
                                     axis=-1, keepdims=True)
        pbar = mean_attention_rows(qb, k, lse_b, chosen)
        live = pbar > 0.0
        return jnp.sum(jnp.where(live, pbar * (jnp.log(jnp.where(
            live, pbar, 1.0)) - jnp.where(chosen, logq, 0.0)), 0.0), axis=-1)

    kl = jax.lax.map(block, (by_rows(qi, 2), by_rows(w, 1), by_rows(q, 2),
                             by_rows(lse, 2), by_rows(select, 1)))
    return jnp.moveaxis(kl, 0, 1).reshape(b, s)


def _index_loss_blocks(qi, ki, w, q, k, lse, ilse, select, rows, interpret):
    """(the alignment loss by row (b, s), its sum's gradients with respect
    to ``qi``, ``ki`` and ``w``): where Mosaic compiles one call of
    ``ops/sparse_attention.index_loss``, which makes the four in one pass
    over the causal tile pairs; elsewhere ``_index_loss_rows`` and its
    autodiff."""
    if not interpret:
        from ompi_tpu.ops.sparse_attention import index_loss

        return index_loss(q, k, lse, qi, ki, w, ilse, select,
                          interpret=False)
    kl, back = jax.vjp(lambda *a: _index_loss_rows(*a, q, k, lse, select,
                                                   rows), qi, ki, w)
    return (kl, *back(jnp.ones_like(kl)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def index_alignment_loss(qi, ki, w, q, k, lse, ilse, select, rows: int,
                         interpret: bool):
    """DSA's alignment loss of one layer: (``sum_t KL(pbar[t, .] ||
    softmax_{S_t}(I[t, .]))``, the same by row (b, s), which is reported
    and carries no gradient).  ``pbar`` is made from q, k and the
    attention's logsumexp, all three read as constants (the published
    loss detaches the attention's distribution); the gradient reaches
    ``qi``, ``ki`` and ``w`` alone.  The forward rule makes the loss and
    its gradients in one pass and names them (``DSA_LOSS``), so a
    checkpointed layer's backward pass only scales what its forward pass
    kept."""
    kl = _index_loss_blocks(qi, ki, w, q, k, lse, ilse, select, rows,
                            interpret)[0]
    return jnp.sum(kl), kl


def _index_loss_fwd(qi, ki, w, q, k, lse, ilse, select, rows, interpret):
    kl, dqi, dki, dw = (checkpoint_name(a, DSA_LOSS) for a in
                        _index_loss_blocks(qi, ki, w, q, k, lse, ilse,
                                           select, rows, interpret))
    return (jnp.sum(kl), kl), (dqi.astype(qi.dtype), dki.astype(ki.dtype),
                               dw)


def _index_loss_bwd(rows, interpret, res, cts):
    scale = cts[0]
    return tuple((g * scale).astype(g.dtype) for g in res) + (None,) * 5


index_alignment_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def dsa_attention(p, x, cfg, *, interpret: bool, at=None):
    """Grouped-query attention under DeepSeek-V3.2's learned sparse
    attention (DSA), **without** the residual add, on the residual stream
    ``x`` (b, s, d) float32.  q, k and v are ``gqa_attention``'s QK-normed
    form with RoPE over the whole head (lfm2's).  Beside them a **lightning
    indexer** reads the normed input with its gradient stopped: ``qI = hI
    W_qI`` (``index_heads`` heads of ``index_head_dim``), one key a
    position ``kI = LayerNorm(hI W_kI)``, RoPE on both, the heads' weights
    ``w = hI W_wI`` in float32; ``I[t, u] = sum_j w[t, j] relu(qI[t, j] .
    kI[u]) / sqrt(heads x width)`` for u <= t.  Query t attends to ``S_t``,
    the ``min(t + 1, index_topk)`` keys of largest ``I[t, .]``, chosen
    exactly (``_index_select_blocks``) and a constant of the step: softmax
    over ``S_t`` through the flash kernels under the selection's tiles
    (``selected_flash_attention``).  The indexer learns from
    ``index_alignment_loss`` alone, whose ``pbar`` is read from q, k and
    the kernels' logsumexp as constants; nothing else of the step reaches
    its leaves.

    Returns (the sublayer's output, {``index_kl_sum``: the alignment loss
    summed over the rows}, what a check reads: ``attn_qk_in`` / ``attn_qk``
    as ``gqa_attention``; the selection packed eight keys a byte
    (``dsa_selection_seq`` (b, s, s / 8) uint8, key u in bit u % 8 of byte
    u // 8); the index key ``dsa_ki_seq`` (T, di), the first key-value
    head's ``dsa_k_seq`` and ``dsa_v_seq`` (T, hd) and every key-value
    head's ``dsa_kall_seq`` (T, n_kv hd) whole; and at the rows ``at``
    (flat token rows of this shard) ``dsa_qi_at`` (R, J di), ``dsa_w_at``
    (R, J), the scores made again from those ``dsa_index_at`` (R, s),
    every head's q ``dsa_q_at`` (R, h hd) and logsumexp ``dsa_lse_at`` (R,
    h), the first head's ``dsa_o_at`` (R, hd), the row's loss
    ``dsa_kl_at`` (R,))."""
    b, s, _ = x.shape
    nh, nkv, dt = cfg.n_heads_here, cfg.n_kv_heads_here, cfg.compute_dtype
    eps, theta = cfg.rms_norm_eps, cfg.rope_theta
    heads, di, topk = cfg.index_heads, cfg.index_head_dim, cfg.index_topk
    split = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], eps)
        q_in, k_in = (split(matmul(h, p[m], dt), n)
                      for m, n in (("wq", nh), ("wk", nkv)))
        q, k = (rope(rmsnorm_gain(t, p[g], eps), theta)
                for t, g in ((q_in, "q_norm"), (k_in, "k_norm")))
        first = lambda a, c: jnp.concatenate(
            [a[:, 0], c[:, 0]], -1).reshape(b * s, -1)
        seen = {"attn_qk_in": first(q_in, k_in), "attn_qk": first(q, k)}
        q, k = q.astype(dt), k.astype(dt)
        v = split(matmul(h, p["wv"], dt), nkv).astype(dt)
    with jax.named_scope("otpu_dsa_index"):
        hi = jax.lax.stop_gradient(h)
        qi = rope(split(matmul(hi, p["index_wq"], dt), heads), theta
                  ).astype(dt)
        ki = layernorm(matmul(hi, p["index_wk"], dt), p["index_k_norm"],
                       p["index_k_bias"], eps)
        ki = rope(ki[:, None], theta)[:, 0].astype(dt)
        w = jnp.dot(hi, p["index_ww"], precision=jax.lax.Precision.HIGHEST
                    ) * (heads * di) ** -0.5
    with jax.named_scope("otpu_dsa_select"):
        sel, ilse = _index_select_blocks(
            *(jax.lax.stop_gradient(a) for a in (qi, ki, w)), topk,
            cfg.index_q_chunk, interpret)
        sel = checkpoint_name(sel, DSA_SELECTION)
        ilse = checkpoint_name(ilse, DSA_INDEX_LSE)
    o, lse = selected_flash_attention(q, k, v, sel, min(cfg.attn_block, s),
                                      interpret, topk)
    with jax.named_scope("otpu_dsa_loss"):
        kl_sum, kl = index_alignment_loss(
            qi, ki, w, *(jax.lax.stop_gradient(a) for a in (q, k, lse)),
            ilse, sel, cfg.index_q_chunk, interpret)
    with jax.named_scope("otpu_stats"):
        rows = lambda t: t.reshape(b * s, -1).astype(jnp.float32)
        seen.update(
            dsa_selection_seq=jnp.packbits(sel.astype(jnp.uint8), axis=-1,
                                           bitorder="little"),
            dsa_ki_seq=rows(ki), dsa_k_seq=rows(k[:, 0]),
            dsa_v_seq=rows(v[:, 0]),
            dsa_kall_seq=rows(k.transpose(0, 2, 1, 3)))
        if at is not None:
            bi, ti = at // s, at % s
            qi_at, w_at = qi[bi, :, ti], w[bi, ti]          # (R, J, di)
            seen.update(
                dsa_qi_at=qi_at.reshape(len(at), -1).astype(jnp.float32),
                dsa_w_at=w_at,
                dsa_index_at=index_scores(
                    qi_at[:, :, None], ki[bi], w_at[:, None])[:, 0],
                dsa_q_at=q[bi, :, ti].reshape(len(at), -1).astype(
                    jnp.float32),
                dsa_lse_at=lse[bi, :, ti], dsa_o_at=o[bi, 0, ti],
                dsa_kl_at=kl[bi, ti])
    with jax.named_scope("otpu_attn_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return matmul(o, p["wo"], dt), {"index_kl_sum": kl_sum}, seen


def olmoe_attention(p, x, cfg, *, interpret: bool):
    """OLMoE's attention sublayer on the residual stream ``x`` (b, s, d)
    float32: pre-norm; q, k, v, o projections without bias; RMSNorm with
    a gain over the whole width of q and of k **before** the heads are
    split (QK-norm); RoPE; causal attention; residual add."""
    b, s, d = x.shape
    nh, dt = cfg.num_attention_heads, cfg.compute_dtype
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps)
        q = rmsnorm_gain(matmul(h, p["wq"], dt), p["q_norm"],
                         cfg.rms_norm_eps)
        k = rmsnorm_gain(matmul(h, p["wk"], dt), p["k_norm"],
                         cfg.rms_norm_eps)
        v = matmul(h, p["wv"], dt)
        heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
        q, k = rope(heads(q), cfg.rope_theta), rope(heads(k), cfg.rope_theta)
        q, k, v = q.astype(dt), k.astype(dt), heads(v).astype(dt)
    o = causal_flash_attention(q, k, v, min(cfg.attn_block, s), interpret)
    with jax.named_scope("otpu_attn_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        return x + matmul(o, p["wo"], dt)


def mla_attention(p, x, cfg, *, interpret: bool):
    """DeepSeek-V3's latent attention sublayer (arXiv:2412.19437 section
    2.1.1) on the residual stream ``x`` (b, s, d) float32: pre-norm; q
    through a normed latent of ``q_lora_rank``; k's no-position part and
    v through a normed latent of ``kv_lora_rank``; one rotary key of
    ``qk_rope_head_dim`` that every head shares; causal ``softmax(q k^T
    / sqrt(nope + rope)) v`` with q, k of one width and v of another;
    residual add.  The two inner norms, RoPE and the softmax in float32;
    matmul inputs in ``compute_dtype``.  Training holds no cache, so the
    latents are expanded to full keys and values.  q and the shared
    rotary key leave their projections with RoPE on (``project_rope``)."""
    b, s, _ = x.shape
    nh, dt, eps = cfg.num_attention_heads, cfg.compute_dtype, cfg.rms_norm_eps
    nope, rot, hv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank, theta = cfg.kv_lora_rank, cfg.rope_theta
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], eps)
        cq = rmsnorm_gain(matmul(h, p["wq_a"], dt), p["q_a_norm"], eps)
        q = project_rope(cq, p["wq_b"], nh, nope, theta, dt)
        # (b, s, rank + rot), the rotary key behind the latent
        kv = project_rope(h, p["wkv_a"], 1, rank, theta, dt)[:, :, 0]
        ckv = rmsnorm_gain(kv[..., :rank], p["kv_a_norm"], eps)
        kvb = matmul(ckv, p["wkv_b"], dt).reshape(b, s, nh, nope + hv)
        k = jnp.concatenate([kvb[..., :nope].astype(dt), jnp.broadcast_to(
            kv[:, :, None, rank:].astype(dt), (b, s, nh, rot))], -1)
        heads = lambda t: t.transpose(0, 2, 1, 3)        # (b, nh, s, .)
        q, k, v = (heads(q.astype(dt)), heads(k),
                   heads(kvb[..., nope:].astype(dt)))
    o = causal_flash_attention(q, k, v, min(cfg.attn_block, s), interpret)
    with jax.named_scope("otpu_attn_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * hv)
        return x + matmul(o, p["wo"], dt)


def gqa_attention(p, x, cfg, *, interpret: bool,
                  kind: str = "full_attention"):
    """Grouped-query attention, **without** the residual add, on the
    residual stream ``x`` (b, s, d) float32: pre-norm; q, k, v, o
    projections without bias; the ``n_heads_here`` query heads held here
    and the ``n_kv_heads_here`` key-value heads they read (each read by
    ``num_attention_heads / num_key_value_heads`` query heads of the
    model, by as many of those as are held here); causal softmax
    attention.  k and v go to ``causal_flash_attention`` as they leave
    their projections, with their own heads: the flash kernels read a
    group's shared head through their index maps and sum its query
    heads' gradients in float32.

    (A fifth model's q, k and v, Keye-VL-2.0's, share lfm2's form below,
    the per-head QK-norm and RoPE over the whole head, but its sublayer is
    ``dsa_attention``, which puts a learned selection between them and the
    kernels: there is no fifth branch here.)

    Four models' sublayer, told apart by what the layer holds and, where
    the leaves cannot say, by the layer's ``kind`` (its ``layer_types``
    name; ``full_attention`` where nobody says) under the configuration.
    nemotron_h's (Nemotron-3-Super) holds no ``q_norm``: no rotary
    embedding (the positions come from the state-space layers), q, k
    and v cast as they leave their projections.  lfm2's (LFM2-8B-A1B)
    holds ``q_norm`` and ``k_norm`` (head width,): RMSNorm with a gain
    over **each head's** width of q and of k, then RoPE in the
    half-split form, both in float32.  qwen3_next's (Qwen3-Next-80B-A3B)
    holds them too and a ``wq`` twice as wide as ``wo`` is long: a
    head's columns are its query and then its **gate**; the norms and
    RoPE as lfm2's, RoPE on the leading ``rotary_width`` entries of the
    head only (``partial_rotary_factor``), and ``o * sigmoid(gate)``, in
    float32, before ``W_o``.  The head's width is the configuration's
    ``head_width`` (``head_dim`` where the file gives one), whatever the
    hidden width.  smallthinker's (SmallThinker-21BA3B) holds no
    ``q_norm`` either, and is a ``layer_types`` model: RoPE over the whole
    head, in float32, on the kinds of layer the configuration's
    ``rope_kinds`` names (its ``sliding_attention`` layers) and none on
    the others (its ``full_attention`` layers), and a
    ``sliding_attention`` layer attends to the last ``sliding_window``
    keys (``causal_flash_attention``'s ``window``) where the sequence is
    longer than that.  Whether a kind is turned is the configuration's to
    say in every form but nemotron_h's: lfm2's and qwen3_next's
    ``rope_kinds`` name their one kind of attention.

    Returns (the sublayer's output, by token row what the
    norm and RoPE read and made of the first query head and the first
    key-value head side by side, ``attn_qk_in`` and ``attn_qk`` (T, 2
    hd): of a QK-normed layer that is turned, and of every layer of a
    model that goes by ``rope_kinds`` without a norm, the two alike where
    the layer is not turned; of a gated layer also the first
    head's o and gate side by side, ``attn_og_in`` (T, 2 hd), and what
    the gate made of them, ``attn_og`` (T, hd); of a layer under a window
    what the kernels read and made of the first query head and its
    key-value head: ``attn_win_q`` (T, hd), ``attn_win_k_seq`` and
    ``attn_win_v_seq`` (T, hd) whole, because a row reads a window of
    them, and ``attn_win_o`` (T, hd))."""
    b, s, _ = x.shape
    nh, nkv, dt = cfg.n_heads_here, cfg.n_kv_heads_here, cfg.compute_dtype
    seen, gate = {}, None
    turned = kind in cfg.rope_kinds
    turn = (lambda t: rope(t, cfg.rope_theta, cfg.rotary_width)) if turned \
        else (lambda t: t)
    window = cfg.sliding_window if kind == "sliding_attention" else None
    first = lambda a, c: jnp.concatenate(
        [a[:, 0], c[:, 0]], -1).reshape(b * s, -1)
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps)
        if "q_norm" in p:
            split = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
            q_in, k_in = (split(matmul(h, p[w], dt), n)
                          for w, n in (("wq", nh), ("wk", nkv)))
            if p["wq"].shape[-1] == 2 * p["wo"].shape[0]:
                q_in, gate = jnp.split(q_in, 2, axis=-1)
            q, k = (turn(rmsnorm_gain(t, p[g], cfg.rms_norm_eps))
                    for t, g in ((q_in, "q_norm"), (k_in, "k_norm")))
            if turned:
                seen = {"attn_qk_in": first(q_in, k_in),
                        "attn_qk": first(q, k)}
            q, k = q.astype(dt), k.astype(dt)
            v = split(matmul(h, p["wv"], dt), nkv).astype(dt)
        elif cfg.layer_types:
            split = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
            q_in, k_in, v = (split(matmul(h, p[w], dt), n) for w, n in (
                ("wq", nh), ("wk", nkv), ("wv", nkv)))
            q, k = turn(q_in), turn(k_in)
            # reported of a layer that is not turned too: that it was left
            # alone is what a check reads
            seen = {"attn_qk_in": first(q_in, k_in), "attn_qk": first(q, k)}
            q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
        else:
            heads = lambda t, n: t.reshape(b, s, n, -1).transpose(
                0, 2, 1, 3).astype(dt)
            q, k, v = (heads(matmul(h, p[w], dt), n)
                       for w, n in (("wq", nh), ("wk", nkv), ("wv", nkv)))
    o = causal_flash_attention(q, k, v, min(cfg.attn_block, s), interpret,
                               window)
    if window is not None:
        rows = lambda t: t[:, 0].reshape(b * s, -1).astype(jnp.float32)
        seen.update(attn_win_q=rows(q), attn_win_k_seq=rows(k),
                    attn_win_v_seq=rows(v), attn_win_o=rows(o))
    with jax.named_scope("otpu_attn_proj"):
        if gate is not None:
            gated = o * jax.nn.sigmoid(gate)
            seen["attn_og_in"] = jnp.concatenate(
                [o[:, 0], gate[:, 0]], -1).reshape(b * s, -1)
            seen["attn_og"] = gated[:, 0].reshape(b * s, -1)
            o = gated
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return matmul(o, p["wo"], dt), seen


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """Mamba-2's state-space scan (arXiv:2405.21060, the chunked form of
    its section 6) in float32: per head the state ``h_t = exp(dt_t a)
    h_{t-1} + dt_t x_t b_t^T`` (p x n) and the output ``y_t = h_t c_t``,
    from a zero state, never reset.  ``x`` (bt, s, h, p); ``dt`` (bt, s,
    h), positive; ``a`` (h,), negative; ``b``, ``c`` (bt, s, g, n), each
    group's shared by ``h / g`` consecutive heads.  Returns y (bt, s, h,
    p), without the skip term.

    The sequence is cut into chunks of ``chunk`` positions (padded at
    the end with dt = 0, which leaves the state as it is).  Inside a
    chunk position i reads position j <= i through ``exp(sum_{j<k<=i}
    dt_k a)``, a (chunk, chunk) matrix a head times ``c_i . b_j``; each
    chunk leaves ``sum_j exp(sum_{k>j} dt_k a) dt_j x_j b_j^T`` to the
    state; the states go from chunk to chunk by a ``lax.scan`` of
    ``s / chunk`` steps; position i of a chunk reads the state that
    entered it through ``exp(sum_{k<=i} dt_k a)``.  The running sums,
    the exponentials, the states and every product are float32 at the
    highest precision: at a chip's share of the heads they are under a
    hundredth of a layer's operations.  The backward pass is autodiff's
    through the same chunks."""
    bt, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    _f32 = lambda eq, one, two: _contract(eq, one, two, jnp.float32)
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    # (bt, chunks, groups, heads a group, position, .)
    xd = (x * dt[..., None]).reshape(bt, nc, chunk, g, r, p) \
        .transpose(0, 1, 3, 4, 2, 5)
    da = (dt * a).reshape(bt, nc, chunk, g, r).transpose(0, 1, 3, 4, 2)
    bc = b.reshape(bt, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cc = c.reshape(bt, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cum = jnp.cumsum(da, axis=-1)                        # sum_{k<=i} dt_k a
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                 # (.., i, j)
    cb = _f32("zcgin,zcgjn->zcgij", cc, bc)
    y = _f32("zcgrij,zcgrjp->zcgrip", cb[:, :, :, None] * decay, xd)
    to_end = jnp.exp(cum[..., -1:] - cum)                # (.., j)
    left = _f32("zcgrjp,zcgjn->zcgrpn", xd * to_end[..., None], bc)
    through = jnp.exp(cum[..., -1])                      # a chunk's decay

    def carry(state, xs):
        left_c, through_c = xs
        return state * through_c[..., None, None] + left_c, state

    _, entered = jax.lax.scan(
        carry, left[:, 0] * 0,                           # carries x's vma
        (jnp.moveaxis(left, 1, 0), jnp.moveaxis(through, 1, 0)))
    y = y + _f32("zcgin,zcgrpn->zcgrip", cc, jnp.moveaxis(entered, 0, 1)) \
        * jnp.exp(cum)[..., None]
    return y.transpose(0, 1, 4, 2, 3, 5).reshape(bt, s + pad, h, p)[:, :s]


def mamba_mixer(p, x, cfg):
    """nemotron_h's Mamba-2 mixer, **without** the residual add, on the
    residual stream ``x`` (b, s, d) float32, for the ``n_mamba_heads_here``
    heads and ``n_groups_here`` B/C groups held here: pre-norm; ``[z |
    xBC | dt] = u W_in`` (matmul inputs in ``compute_dtype``); ``xBC <-
    silu(causal depthwise convolution over conv_kernel positions, with
    bias)``, split into x (heads x ``mamba_head_dim``), B and C (groups x
    ``ssm_state_size``); ``dt <- softplus(dt + dt_bias)``, ``a =
    -exp(A_log)``; the scan in chunks of ``chunk_size``
    (``ssd_chunked``) plus ``D x``; ``rmsnorm over each group of (y *
    silu(z)) * gain``; ``y W_out``.  Everything between the two
    projections is float32.  Returns (the sublayer's output, what the
    scan read and made of the first held head, by token row: its step
    ``ssm_dt_seq`` (T,), its ``ssm_x_seq`` (T, p) and its group's
    ``ssm_b_seq`` and ``ssm_c_seq`` (T, n) whole, because a position's
    state holds every earlier one, and the scan's ``ssm_y`` (T, p)
    before the skip term)."""
    b, s, d = x.shape
    nh, hd, n = cfg.n_mamba_heads_here, cfg.mamba_head_dim, cfg.ssm_state_size
    g, dt, eps = cfg.n_groups_here, cfg.compute_dtype, cfg.rms_norm_eps
    inner = nh * hd
    with jax.named_scope("otpu_ssm_proj"):
        u = rmsnorm_gain(x, p["norm"], eps)
        zxd = matmul(u.reshape(b * s, d), p["in_proj"], dt).reshape(b, s, -1)
        z, xbc, step = (zxd[..., :inner], zxd[..., inner:-nh], zxd[..., -nh:])
    with jax.named_scope("otpu_ssm_conv"):
        taps = p["conv_w"].shape[0]
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        xbc = jax.nn.silu(p["conv_b"] + sum(
            padded[:, k:k + s] * p["conv_w"][k] for k in range(taps)))
    with jax.named_scope("otpu_ssm_scan"):
        xs = xbc[..., :inner].reshape(b, s, nh, hd)
        bs, cs = (xbc[..., inner + k * g * n:inner + (k + 1) * g * n]
                  .reshape(b, s, g, n) for k in (0, 1))
        step = jax.nn.softplus(step + p["dt_bias"])
        y = ssd_chunked(xs, step, -jnp.exp(p["A_log"]), bs, cs,
                        cfg.chunk_size)
        rows = lambda t: t.reshape((b * s,) + t.shape[2:])
        seen = {"ssm_dt_seq": rows(step[:, :, 0]),
                "ssm_x_seq": rows(xs[:, :, 0]),
                "ssm_b_seq": rows(bs[:, :, 0]),
                "ssm_c_seq": rows(cs[:, :, 0]), "ssm_y": rows(y[:, :, 0])}
        y = (y + p["D"][:, None] * xs).reshape(b, s, inner)
    with jax.named_scope("otpu_ssm_norm"):
        y = (y * jax.nn.silu(z)).reshape(b, s, g, inner // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y.reshape(b, s, inner) * p["gate_norm"]
    with jax.named_scope("otpu_ssm_proj"):
        return matmul(y.reshape(b * s, inner), p["out_proj"], dt
                      ).reshape(b, s, d), seen


#: the leading channels of a short convolution whose gate path a step
#: reports (``short_conv``): one tile's lanes of the hidden width
CONV_SAMPLE = 128


def short_conv(p, x, cfg):
    """lfm2's gated short convolution (LFM2-8B-A1B's ``conv`` operator),
    **without** the residual add, on the residual stream ``x`` (b, s, d)
    float32: pre-norm; ``[B | C | u] = n W_in`` (d, 3 d; matmul inputs
    in ``compute_dtype``); ``z_t = sum_j w_j (B * u)_{t - (taps - 1) +
    j}``, a causal depthwise convolution of ``conv_kernel`` taps a
    channel (``conv_w`` (taps, d), the last tap on the position itself)
    with zeros before the sequence's start, no bias and no activation;
    ``(C * z) W_out``.  The two gates and the taps, everything between
    the two projections, are float32.  The sequence is never reset
    inside a packed row.  Returns (the sublayer's output, of the first
    ``CONV_SAMPLE`` channels by token row what the gate path read,
    ``conv_bcu_seq`` (T, B | C | u) whole, because a position's result
    holds the ``taps - 1`` before it, and made, ``conv_y`` (T, .): C *
    z)."""
    b, s, d = x.shape
    dt, taps = cfg.compute_dtype, p["conv_w"].shape[0]
    with jax.named_scope("otpu_conv_proj"):
        n = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps)
        bcu = matmul(n.reshape(b * s, d), p["in_proj"], dt).reshape(
            b, s, 3, d)
    with jax.named_scope("otpu_conv_gate"):
        gated = jnp.pad(bcu[:, :, 0] * bcu[:, :, 2],
                        ((0, 0), (taps - 1, 0), (0, 0)))
        y = bcu[:, :, 1] * sum(gated[:, k:k + s] * p["conv_w"][k]
                               for k in range(taps))
        c = min(CONV_SAMPLE, d)
        seen = {"conv_bcu_seq": bcu[..., :c].reshape(b * s, 3 * c),
                "conv_y": y[..., :c].reshape(b * s, c)}
    with jax.named_scope("otpu_conv_proj"):
        return matmul(y.reshape(b * s, d), p["out_proj"], dt
                      ).reshape(b, s, d), seen


@jax.custom_vjp
def unit_lower_inverse(low):
    """``(I + low)^-1`` of strictly lower-triangular matrices ``low``
    (.., c, c) by forward substitution, row by row in float32 (row i of
    the inverse less the identity is ``-low_i`` plus itself times the
    rows above, which are done): sums of products and no matmul, so no
    rounding below float32 whatever the platform's default.  The rows
    are written in place, a loop autodiff would keep every state of: the
    gradient is written out, ``-T^T ct T^T`` of the result ``T``."""
    return _unit_lower_inverse_fwd(low)[0]


def _unit_lower_inverse_fwd(low):
    a = -low
    for i in range(1, low.shape[-1]):
        row = a[..., i, :i]
        a = a.at[..., i, :i].add(
            jnp.sum(row[..., :, None] * a[..., :i, :i], axis=-2))
    t = a + jnp.eye(low.shape[-1], dtype=low.dtype)
    return t, t


def _unit_lower_inverse_bwd(t, ct):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_contract("...ij,...jk->...ik",
                       _contract("...ij,...jk->...ik", tt, ct, jnp.float32),
                       tt, jnp.float32),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _count_gdn(part: str, on_kernel: bool) -> None:
    """SPC ``gdn_<part>_built``: the passes of a Gated DeltaNet layer's
    ``part`` (``rule``, ``conv``) made while steps were traced (the XLA
    form, whose backward pass is autodiff's and not seen here, or the
    kernel path's forward and backward rules: JAX traces a pass more
    than once); ``gdn_<part>_kernel_built``: those of them made on the
    Pallas kernels.  What reads is the second over the first."""
    spc.record(f"gdn_{part}_built", 1)
    if on_kernel:
        spc.record(f"gdn_{part}_kernel_built", 1)


def _kernel_views(arrays, hk, hv):
    """(q, k, v, their lane blocks) as ``ops/gated_delta`` reads them: of
    three arrays (bt, s, heads x 128) each from its first block; of one,
    the convolution's [q | k | v], key head h's q at block h, its k at
    ``hk + h`` and its value heads at ``2 hk / r + h`` blocks of r heads."""
    if len(arrays) == 3:
        return (*arrays, (0, 0, 0))
    (qkv,) = arrays
    return qkv, qkv, qkv, (0, hk, 2 * hk * hk // hv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kernel_rule(arrays, g, beta, chunk, hk, unit):
    """The chunked rule on the Pallas kernels (``ops/gated_delta``) for
    ``hk`` key heads 128 wide: o (bt, s, hv x 128) of ``arrays``, either
    (q, k, v) as the rule reads them, heads side by side, or with
    ``unit`` = (eps, scale) the convolution's one [q | k | v], whose q
    and k rows the kernels put at unit length themselves.  The forward
    kernel also writes, for a backward pass, the state that entered each
    chunk and the chunk's ``T``; the backward kernel makes a chunk's
    other parts again from those.  Nothing a chunk is kept or recomputed
    by XLA."""
    from ompi_tpu.ops import gated_delta as rule_kernel

    _count_gdn("rule", True)
    *views, at = _kernel_views(arrays, hk, g.shape[2])
    return rule_kernel.rule_forward(*views, g, beta, chunk=chunk, hk=hk,
                                    at=at, unit=unit)


def _kernel_rule_fwd(arrays, g, beta, chunk, hk, unit):
    from ompi_tpu.ops import gated_delta as rule_kernel

    _count_gdn("rule", True)
    *views, at = _kernel_views(arrays, hk, g.shape[2])
    o, kept = rule_kernel.rule_forward(*views, g, beta, chunk=chunk, hk=hk,
                                       at=at, unit=unit, states=True)
    return o, (arrays, g, beta, kept)


def _kernel_rule_bwd(chunk, hk, unit, res, do):
    from ompi_tpu.ops import gated_delta as rule_kernel

    _count_gdn("rule", True)
    arrays, g, beta, kept = res
    *views, at = _kernel_views(arrays, hk, g.shape[2])
    *d_qkv, dg, dbeta = rule_kernel.rule_backward(
        *views, g, beta, kept, do, chunk=chunk, hk=hk, at=at, unit=unit)
    if len(arrays) == 1:
        d_qkv = [jnp.concatenate(d_qkv, axis=-1)]
    return tuple(d_qkv), dg, dbeta


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


def _rule_on_kernels(interpret, chunk, dk, dv, r, s) -> bool:
    """Whether the rule runs on the Pallas kernels: where Mosaic compiles
    (``interpret`` false: a TPU) and the shape has tiles."""
    if interpret:
        return False
    from ompi_tpu.ops import gated_delta as rule_kernel

    return rule_kernel.supported(chunk, dk, dv, r, s)


def gated_delta_chunked(q, k, v, g, beta, chunk: int, interpret: bool = True):
    """The gated delta rule (arXiv:2412.06464, the chunked form of its
    section 3.3 and of qwen3_next's modelling code) in float32: per
    value head, from a zero state S (dk x dv) that is never reset, ``S
    <- exp(g_t) S``, ``S <- S + k_t (beta_t (v_t - S^T k_t))^T``, ``o_t
    = S^T q_t``.  ``q``, ``k`` (bt, s, hk, dk), as the rule reads them
    (normalised, q scaled); ``v`` (bt, s, hv, dv); ``g`` (bt, s, hv), not
    positive; ``beta`` (bt, s, hv); each key head is read by ``hv / hk``
    consecutive value heads.  Returns o (bt, s, hv, dv).

    The sequence is cut into chunks of ``chunk`` positions (padded at
    the end with zeros: k = 0 writes nothing, g = 0 leaves the state as
    it is).  With ``c_i`` the running sum of g inside a chunk, ``L_ij =
    beta_i (k_i . k_j) exp(c_i - c_j)`` for j < i and ``T = (I +
    L)^-1`` (``unit_lower_inverse``), a chunk's own writes are ``U = T
    (beta v)`` less what they read of the state that entered, ``W = T
    (beta k exp(c))`` times S: ``V' = U - W S``; its output is ``(q
    exp(c)) S + ((q k^T) exp(c_i - c_j), j <= i) V'``, and it leaves ``S
    exp(c_last) + (k exp(c_last - c))^T V'``.  T, U, W and the masked
    products are made for every chunk at once; the states go from chunk
    to chunk by a ``lax.scan`` of ``s / chunk`` steps, four small
    products each (``ssd_chunked``'s form, but a chunk's writes depend
    on the state it reads, so they lie inside the scan).  Running sums,
    exponentials, the solve, the states and every product are float32
    at the highest precision.  The backward pass is autodiff's through
    the same chunks.

    Where Mosaic compiles (``interpret`` false: a TPU) and the shape has
    tiles (``ops/gated_delta.supported``) the same chunks run in Pallas
    kernels that keep the state in VMEM (``_kernel_rule``), forward and
    backward; everywhere else this XLA form, which is their oracle."""
    bt, s, hk, dk = k.shape
    hv, dv = v.shape[2:]
    r = hv // hk
    if _rule_on_kernels(interpret, chunk, dk, dv, r, s):
        flat = lambda t: t.reshape(bt, s, -1)
        return _kernel_rule((flat(q), flat(k), flat(v)), g, beta, chunk, hk,
                            None).reshape(v.shape)
    _count_gdn("rule", False)
    _f32 = lambda eq, one, two: _contract(eq, one, two, jnp.float32)
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    nc = (s + pad) // chunk
    # (bt, chunks, key heads, [value heads a key head,] position, .)
    qc, kc = (t.reshape(bt, nc, chunk, hk, dk).transpose(0, 1, 3, 2, 4)
              for t in (q, k))
    vc = v.reshape(bt, nc, chunk, hk, r, dv).transpose(0, 1, 3, 4, 2, 5)
    gc, bc = (t.reshape(bt, nc, chunk, hk, r).transpose(0, 1, 3, 4, 2)
              for t in (g, beta))
    cum = jnp.cumsum(gc, axis=-1)                        # c_i
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                 # (.., i, j <= i)
    kk = _f32("zcgid,zcgjd->zcgij", kc, kc)[:, :, :, None]
    low = jnp.where(i[:, None] > i[None, :],
                    bc[..., :, None] * kk * decay, 0.0)
    solve = unit_lower_inverse(low)
    # a key head's q and k go into every product as they are, its value
    # heads' decays on the other operand: no copy of them a value head
    wrote = _f32("zcgrij,zcgrjp->zcgrip", solve, vc * bc[..., None])
    read = _f32("zcgrij,zcgjd->zcgrid",
                solve * (bc * jnp.exp(cum))[..., None, :], kc)
    qk = _f32("zcgid,zcgjd->zcgij", qc, kc)[:, :, :, None] * decay

    def carry(state, xs):
        wrote_c, read_c, qk_c, q_c, k_c, cum_c = xs
        new = wrote_c - _f32("zgrid,zgrdp->zgrip", read_c, state)
        out = _f32("zgid,zgrdp->zgrip", q_c, state) \
            * jnp.exp(cum_c)[..., None] \
            + _f32("zgrij,zgrjp->zgrip", qk_c, new)
        last = cum_c[..., -1:]                           # a chunk's decay
        return state * jnp.exp(last)[..., None] + _f32(
            "zgjd,zgrjp->zgrdp", k_c,
            new * jnp.exp(last - cum_c)[..., None]), out

    # a zero state that carries the inputs' vma
    zero = (kc[:, 0, :, None, 0, :, None]
            * wrote[:, 0, :, :, 0, None, :]) * 0
    # a step's own products are made again in its backward step: kept,
    # they are three more arrays of every chunk's (positions, dv) beside
    # the states (at 16,384 positions 0.8 GB a layer, which did not fit)
    _, o = jax.lax.scan(jax.checkpoint(carry), zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (wrote, read, qk, qc, kc, cum)))
    # (chunks, bt, hk, r, position, dv) -> (bt, s, hv, dv)
    return o.transpose(1, 0, 4, 2, 3, 5).reshape(bt, s + pad, hv, dv)[:, :s]


@jax.custom_vjp
def _kernel_conv(x, w):
    """The causal depthwise convolution and its silu on the Pallas
    kernels (``ops/causal_conv``): ``silu(sum_j w[j] x[t - (taps - 1) +
    j])`` (b, s, c) of x (b, s, c) and the taps w (taps, c), float32.
    Only x and w are kept for the backward kernel, which makes the
    pre-activation again, writes dx and sums dw in one pass over x and
    the cotangent."""
    from ompi_tpu.ops import causal_conv

    _count_gdn("conv", True)
    return causal_conv.conv_forward(x, w)


def _kernel_conv_fwd(x, w):
    from ompi_tpu.ops import causal_conv

    _count_gdn("conv", True)
    return causal_conv.conv_forward(x, w), (x, w)


def _kernel_conv_bwd(res, dy):
    from ompi_tpu.ops import causal_conv

    _count_gdn("conv", True)
    return causal_conv.conv_backward(*res, dy)


_kernel_conv.defvjp(_kernel_conv_fwd, _kernel_conv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _kernel_conv_rule(x, w, g, beta, chunk, hk, unit):
    """The convolution and the rule behind it, both on their kernels, as
    one rule of autodiff: (the convolution's [q | k | v] (b, s, c), the
    rule's o) as ``_kernel_conv(x, w)`` and ``_kernel_rule`` of that one
    array make them.  What differs is what a backward pass keeps: x, w,
    g, beta and the rule's states and inverses, not [q | k | v], which
    the backward rule makes again by a second ``conv_forward`` in front
    of the rule's backward kernel.  Kept, as ``_kernel_rule`` after
    ``_kernel_conv`` keeps it, it lives from a checkpointed layer's
    recomputed pass through the rule's backward kernel, 0.54 GB at
    16,384 positions: Qwen3-Next's step then compiles to a peak of 15.91
    GB of a v5e's 16 and this way to 15.64, for 1.6 ms a layer on the
    chip (PR 54)."""
    with jax.named_scope("otpu_gdn_conv"):
        qkv = _kernel_conv(x, w)
    with jax.named_scope("otpu_gdn_rule"):
        return qkv, _kernel_rule((qkv,), g, beta, chunk, hk, unit)


def _kernel_conv_rule_fwd(x, w, g, beta, chunk, hk, unit):
    with jax.named_scope("otpu_gdn_conv"):
        qkv = _kernel_conv(x, w)
    with jax.named_scope("otpu_gdn_rule"):
        o, (_, _, _, kept) = _kernel_rule_fwd((qkv,), g, beta, chunk, hk,
                                              unit)
    return (qkv, o), (x, w, g, beta, kept)


def _kernel_conv_rule_bwd(chunk, hk, unit, res, cts):
    x, w, g, beta, kept = res
    d_seen, do = cts
    with jax.named_scope("otpu_gdn_conv"):
        # behind the cotangent: the compiler would else take the
        # recomputed pass's call for this one and keep its result
        x, do = jax.lax.optimization_barrier((x, do))
        qkv = _kernel_conv(x, w)
    with jax.named_scope("otpu_gdn_rule"):
        (d_qkv,), dg, dbeta = _kernel_rule_bwd(
            chunk, hk, unit, ((qkv,), g, beta, kept), do)
    with jax.named_scope("otpu_gdn_conv"):
        return (*_kernel_conv_bwd((x, w), d_qkv + d_seen), dg, dbeta)


_kernel_conv_rule.defvjp(_kernel_conv_rule_fwd, _kernel_conv_rule_bwd)


def _conv_on_kernels(interpret, taps, c, s) -> bool:
    """Whether the convolution runs on the Pallas kernels: where Mosaic
    compiles (``interpret`` false: a TPU) and the shape has tiles."""
    if interpret:
        return False
    from ompi_tpu.ops import causal_conv

    return causal_conv.supported(taps, c, s)


#: what the delta rule's L2 norms add under the root (``layers.l2norm``'s)
L2NORM_EPS = 1e-6


def gated_delta_net(p, x, cfg, *, interpret: bool = True):
    """qwen3_next's Gated DeltaNet operator (Qwen3-Next-80B-A3B's
    ``linear_attention`` layers; arXiv:2412.06464), **without** the
    residual add, on the residual stream ``x`` (b, s, d) float32, whole
    (``linear_num_key_heads`` key heads, ``linear_num_value_heads`` value
    heads): pre-norm; ``[q | k | v | z] = n W_qkvz`` in that order
    (``in_proj``, matmul inputs in ``compute_dtype``) and ``[b | a] = n
    W_ba`` (``ba_proj``, float32); ``[q | k | v] <- silu(causal depthwise
    convolution of conv_kernel taps a channel, zeros before the
    sequence's start, no bias)``; ``beta = sigmoid(b)``, ``g =
    -exp(A_log) softplus(a + dt_bias)`` a value head; q and k
    L2-normalised over a head, q times ``1 / sqrt(key width)``; the
    gated delta rule in chunks of ``chunk_size`` (``gated_delta_chunked``);
    ``rmsnorm over each head of o * gain * silu(z)`` (the gate behind
    the gain); ``y W_out``.  Everything between the two large
    projections is float32.  The sequence is never reset inside a packed
    row.  Where Mosaic compiles (``interpret`` false: a TPU) and the
    width is whole tiles of lanes (``_conv_on_kernels``) the convolution
    and its silu run in Pallas kernels that read and write each array
    once a pass (``_kernel_conv``); everywhere else the lines here, which
    are the kernels' oracle.  Where the rule runs on its Pallas kernels
    (``_rule_on_kernels``)
    they read q, k and v where the convolution left them, one array, and
    put q's and k's rows at unit length themselves (``_kernel_rule``):
    a 4D view of q, k or v costs XLA two relayouts of it a pass.
    Returns (the sublayer's output, what the rule read and made of
    the first value head, by token row: ``gdn_q_seq``, ``gdn_k_seq`` (T,
    dk), ``gdn_v_seq`` (T, dv), ``gdn_g_seq``, ``gdn_beta_seq`` (T,)
    whole, because a position's state holds every earlier one, and the
    rule's ``gdn_o`` (T, dv))."""
    b, s, d = x.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key, val, dt = hk * dk, hv * dv, cfg.compute_dtype
    with jax.named_scope("otpu_gdn_proj"):
        n = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps).reshape(b * s, d)
        # W_qkvz's product by its two readers' columns: what the
        # convolution reads is done with before z is read, and no slice
        # of the (T, q | k | v | z) float32 array is ever written
        w = cast_param(p["in_proj"], dt)
        qkv, z = (matmul(n, cols, dt, weight=False).reshape(b, s, -1)
                  for cols in (w[:, :2 * key + val], w[:, 2 * key + val:]))
        ba = jnp.dot(n, p["ba_proj"], precision=jax.lax.Precision.HIGHEST
                     ).reshape(b, s, 2, hv)
    with jax.named_scope("otpu_gdn_rule"):
        beta = jax.nn.sigmoid(ba[:, :, 0])
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, :, 1]
                                                   + p["dt_bias"])
    taps, unit = p["conv_w"].shape[0], (L2NORM_EPS, dk ** -0.5)
    conv_on = _conv_on_kernels(interpret, taps, qkv.shape[2], s)
    rule_on = _rule_on_kernels(interpret, cfg.chunk_size, dk, dv, hv // hk,
                               s) and 2 * hk % (hv // hk) == 0
    if conv_on and rule_on:
        qkv, o = _kernel_conv_rule(qkv, p["conv_w"], g, beta,
                                   cfg.chunk_size, hk, unit)
    else:
        with jax.named_scope("otpu_gdn_conv"):
            if conv_on:
                qkv = _kernel_conv(qkv, p["conv_w"])
            else:
                _count_gdn("conv", False)
                padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
                qkv = jax.nn.silu(sum(padded[:, j:j + s] * p["conv_w"][j]
                                      for j in range(taps)))
    with jax.named_scope("otpu_gdn_rule"):
        heads = lambda t, n, width: t.reshape(b, s, n, width)
        if rule_on:
            # the kernels read q, k and v where the convolution left them
            # and norm q and k themselves: only the first head, which the
            # step reports, is cut out and normed here
            if not conv_on:
                o = _kernel_rule((qkv,), g, beta, cfg.chunk_size, hk, unit)
            o = heads(o, hv, dv)
            q, k, v = (heads(qkv[..., first:first + width], 1, width)
                       for first, width in ((0, dk), (key, dk), (2 * key, dv)))
            q, k = l2norm(q, L2NORM_EPS) * dk ** -0.5, l2norm(k, L2NORM_EPS)
        else:
            q, k = (l2norm(heads(qkv[..., j * key:(j + 1) * key], hk, dk),
                           L2NORM_EPS) for j in (0, 1))
            q = q * dk ** -0.5
            v = heads(qkv[..., 2 * key:], hv, dv)
            o = gated_delta_chunked(q, k, v, g, beta, cfg.chunk_size,
                                    interpret)
        rows = lambda t: t.reshape((b * s,) + t.shape[2:])
        seen = {"gdn_q_seq": rows(q[:, :, 0]), "gdn_k_seq": rows(k[:, :, 0]),
                "gdn_v_seq": rows(v[:, :, 0]), "gdn_g_seq": rows(g[:, :, 0]),
                "gdn_beta_seq": rows(beta[:, :, 0]),
                "gdn_o": rows(o[:, :, 0])}
    with jax.named_scope("otpu_gdn_norm"):
        y = rmsnorm_gain(o, p["gate_norm"], cfg.rms_norm_eps) \
            * jax.nn.silu(z.reshape(b, s, hv, dv))
    with jax.named_scope("otpu_gdn_proj"):
        return matmul(y.reshape(b * s, val), p["out_proj"], dt
                      ).reshape(b, s, d), seen


def decoder_layer(p, x, cfg, *, interpret: bool, bias=None,
                  kind: str = "full_attention", at=None):
    """One decoder layer of a public model, its sublayers chosen by what
    the layer holds and the configuration's published keys say.

    A layer of a ``hybrid_override_pattern`` (Nemotron-3-Super) has
    **one** sublayer: the Mamba-2 mixer where it holds ``in_proj``, the
    latent relu2 expert block (``experts.moe_latent_block``) where it
    holds a router, else grouped-query attention without RoPE.

    Any other model's layer has an operator and then a feed-forward,
    each behind its own norm and with its own residual add.  The
    operator: of a ``layer_types`` model the Gated DeltaNet operator
    where the layer holds ``ba_proj`` (Qwen3-Next-80B-A3B's
    ``linear_attention``), the gated short convolution where it holds
    ``in_proj`` (LFM2-8B-A1B's ``conv``), else grouped-query attention
    with a per-head QK-norm and RoPE (LFM2's, and with an output gate
    and RoPE on part of the head Qwen3-Next's; with neither norm nor
    gate SmallThinker-21BA3B's, whose ``full_attention`` and
    ``sliding_attention`` layers hold the same leaves: ``kind``, the
    layer's ``layer_types`` name, a static argument, says which this is,
    and ``gqa_attention`` reads RoPE and the window off it; a window
    layer's sublayer goes under ``otpu_swa``; a ``sparse_attention``
    layer's is ``dsa_attention``, under ``otpu_dsa``, which also reads
    ``at``, the token rows a step samples, and whose alignment loss goes
    out beside the router's statistics); latent attention where
    ``kv_lora_rank`` is set (JoyAI-LLM-Flash); else OLMoE's attention.
    The feed-forward: a dense SwiGLU where the layer has no router
    (JoyAI's and LFM2's leading layers), else the sparse MLP
    (``experts.moe_sorted_block``: every expert here, softmax scores,
    OLMoE; or, where ``routes_to_held``,
    ``experts.moe_shared_local_block``: a share of the experts, beside a
    shared one if the model has it; sigmoid scores chosen under ``bias``,
    JoyAI and LFM2; softmax scores with no bias, Qwen3-Next).

    Returns (x, the router's statistics, what the router, a mixer's
    scan, a short convolution's gate path, the delta rule or RoPE read
    and made by token row); the last two hold nothing of a sublayer the
    layer has not."""
    if cfg.hybrid_override_pattern:
        if "in_proj" in p:
            with jax.named_scope("otpu_mamba"):
                y, seen = mamba_mixer(p, x, cfg)
            return x + y, {}, seen
        if "router" not in p:
            with jax.named_scope("otpu_attention"):
                return x + gqa_attention(p, x, cfg, interpret=interpret)[0], \
                    {}, {}
        with jax.named_scope("otpu_moe"):
            y, stats, routed = experts.moe_latent_block(
                p, x, cfg, bias, interpret=interpret)
        return x + y, stats, routed
    seen, routed, index_stats = {}, None, {}
    if cfg.router_before_attention and "router" in p:
        with jax.named_scope("otpu_moe"):
            rows = x.reshape(-1, x.shape[-1])
            routed = (rows, experts.router_logits(p, rows))
    if cfg.layer_types and "ba_proj" in p:
        with jax.named_scope("otpu_gdn"):
            y, seen = gated_delta_net(p, x, cfg, interpret=interpret)
        x = x + y
    elif cfg.layer_types and "in_proj" in p:
        with jax.named_scope("otpu_conv"):
            y, seen = short_conv(p, x, cfg)
        x = x + y
    elif cfg.layer_types and kind == "sparse_attention":
        with jax.named_scope("otpu_dsa"):
            y, index_stats, seen = dsa_attention(p, x, cfg,
                                                 interpret=interpret, at=at)
        x = x + y
    elif cfg.layer_types:
        with jax.named_scope("otpu_swa") if kind == "sliding_attention" \
                else jax.named_scope("otpu_attention"):
            y, seen = gqa_attention(p, x, cfg, interpret=interpret,
                                    kind=kind)
        x = x + y
    elif cfg.kv_lora_rank:
        with jax.named_scope("otpu_mla"):
            x = mla_attention(p, x, cfg, interpret=interpret)
    else:
        with jax.named_scope("otpu_attention"):
            x = olmoe_attention(p, x, cfg, interpret=interpret)
    if "router" not in p:
        with jax.named_scope("otpu_dense_mlp"):
            h = rmsnorm_gain(x, p["ln2"], cfg.rms_norm_eps)
            y = swiglu(h.reshape(-1, h.shape[-1]), p["gate"], p["up"],
                       p["down"], cfg.compute_dtype)
        return x + y.reshape(x.shape), index_stats, seen
    with jax.named_scope("otpu_moe"):
        if cfg.routes_to_held:
            y, stats, made = experts.moe_shared_local_block(
                p, x, cfg, bias, interpret=interpret, routed=routed)
        else:
            y, stats, made = experts.moe_sorted_block(
                p, x, cfg, interpret=interpret)
    return x + y, {**stats, **index_stats}, {**made, **seen}
