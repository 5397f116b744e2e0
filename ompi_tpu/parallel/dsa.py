"""Learned sparse attention (DeepSeek-V3.2's DSA; Keye-VL-2.0's
``sparse_attention`` layers): a lightning indexer scores every earlier
key, each query attends to its ``index_topk`` best through
``selected_flash_attention``, and an alignment loss teaches the indexer
attention's own distribution.  ``DSA`` is the sublayer's entry in
``parallel/model.py``'s table.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ompi_tpu.parallel.attention import gqa_shapes, normed_qk, qk_plan
from ompi_tpu.parallel.causal import (ATTN_KEEPS, flash_on_kernels,
                                      pass_counts, selected_flash_attention)
from ompi_tpu.parallel.layers import (contract, layernorm, matmul,
                                      rmsnorm_gain, rope)
from ompi_tpu.parallel.sublayer import Sublayer, held, on_mosaic, zeros


# what a layer's ``jax.checkpoint`` keeps of a learned sparse attention
# sublayer beside attention's own: the selection (packed eight keys a byte,
# (b, s, s / 8) int8; made again it costs the index scores and the counting
# passes, and a second choice need not be the first), its rows' logsumexp,
# and the alignment loss's rows and gradients, which its one pass makes
# together
DSA_SELECTION = "otpu_dsa_selection"
DSA_INDEX_LSE = "otpu_dsa_index_lse"
DSA_LOSS = "otpu_dsa_loss"


def index_scores(qi, ki, w):
    """The lightning indexer's scores of query rows ``qi`` (b, J, r, di)
    against every key ``ki`` (b, s, di) under the heads' weights ``w`` (b,
    r, J) float32 (the scale in them): ``I[t, u] = sum_j w[t, j] relu(qi[t,
    j] . ki[u])`` (b, r, s) float32; the products' inputs in ``qi``'s type
    with float32 results, relu, the weights and the sum over the heads,
    head by head in their order, in float32 (the kernels' order)."""
    out = 0.0
    for j in range(qi.shape[1]):
        z = contract("brd,bsd->brs", qi[:, j], ki, qi.dtype)
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


def index_on_kernels(interpret: bool) -> tuple:
    """``(on_kernel, why)`` of the indexer's selection and of its
    alignment loss: ``ops/sparse_attention``'s kernels wherever Mosaic
    compiles, their ``jnp`` twins by blocks of rows elsewhere."""
    return on_mosaic(interpret)


def _index_select_blocks(qi, ki, w, topk: int, rows: int, interpret: bool):
    """(the selection (b, s, s / 8) int8, packed eight keys a byte as the
    kernel packs it, each row's logsumexp over its selected scores (b, s)
    float32) of the indexer's ``qi`` (b, J, s, di),
    ``ki`` (b, s, di) and ``w`` (b, s, J).  Where Mosaic compiles one call
    of ``ops/sparse_attention.index_select``, which keeps a tile's scores
    in VMEM; elsewhere (the CPU) ``rows`` query rows at a time
    (``index_scores``, ``select_topk``), so that no (s, s, J) array and
    only one block's (rows, s) scores are ever held."""
    from ompi_tpu.ops.sparse_attention import index_select, pack_selection

    if index_on_kernels(interpret)[0]:
        return index_select(qi, ki, w, topk=topk, interpret=False)
    b, heads, s, di = qi.shape
    rows = rows if s % rows == 0 else s
    nb = s // rows

    def block(xs):
        qb, wb, first = xs
        sc = index_scores(qb, ki, wb)
        chosen = select_topk(sc, first, topk)
        lse = jax.nn.logsumexp(jnp.where(chosen, sc, -jnp.inf), axis=-1)
        return pack_selection(chosen), lse

    sel, lse = jax.lax.map(block, (
        jnp.moveaxis(qi.reshape(b, heads, nb, rows, di), 2, 0),
        jnp.moveaxis(w.reshape(b, nb, rows, heads), 1, 0),
        jnp.arange(nb, dtype=jnp.int32) * rows))
    return (jnp.moveaxis(sel, 0, 1).reshape(b, s, s // 8),
            jnp.moveaxis(lse, 0, 1).reshape(b, s))


def mean_attention_rows(qb, k, lse_b, chosen):
    """``pbar`` (b, r, s) float32 of query rows ``qb`` (b, h, r, d): the
    mean over the query heads of ``exp(q . k / sqrt(d) - lse)`` at the
    ``chosen`` keys (b, r, s), 0 elsewhere; ``k`` (b, n_kv, s, d),
    ``lse_b`` (b, h, r) the attention's own logsumexp."""
    b, h, r, d = qb.shape
    n_kv = k.shape[1]
    qg = qb.reshape(b, n_kv, h // n_kv, r, d)
    sc = contract("bgerd,bgsd->bgers", qg, k, qb.dtype) / math.sqrt(d)
    p = jnp.exp(sc - lse_b.reshape(b, n_kv, h // n_kv, r)[..., None])
    return jnp.where(chosen, jnp.sum(p, axis=(1, 2)) / h, 0.0)


def _index_loss_rows(qi, ki, w, q, k, lse, select, rows: int):
    """The alignment loss by row (b, s), differentiable in ``qi``, ``ki``
    and ``w`` (``ops/sparse_attention.index_loss``'s ``jnp`` twin):
    ``KL(pbar[t, .] || softmax_S(I[t, .]))`` over the selected keys, a
    block of ``rows`` query rows at a time, whose rows of the packed
    ``select`` (b, s, s / 8) are unpacked with it."""
    from ompi_tpu.ops.sparse_attention import unpack_selection

    b, heads, s, di = qi.shape
    rows = rows if s % rows == 0 else s
    nb = s // rows
    by_rows = lambda a, axis: jnp.moveaxis(a.reshape(
        a.shape[:axis] + (nb, rows) + a.shape[axis + 1:]), axis, 0)

    def block(xs):
        qib, wb, qb, lse_b, sel_b = xs
        chosen = unpack_selection(sel_b)
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
    if index_on_kernels(interpret)[0]:
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
    as ``gqa_attention``; the selection packed eight keys a byte in key
    order (``dsa_selection_seq`` (b, s, s / 8) uint8, key u in bit u % 8
    of byte u // 8: ``selection_bytes`` permutes into it the bits of the
    kernels' packing, (b, s, s / 8) int8 by groups of 1,024 keys, which is
    what travels between the kernels and what ``DSA_SELECTION`` keeps);
    the index key ``dsa_ki_seq`` (T, di), the first key-value
    head's ``dsa_k_seq`` and ``dsa_v_seq`` (T, hd) and every key-value
    head's ``dsa_kall_seq`` (T, n_kv hd) whole; and at the rows ``at``
    (flat token rows of this shard) ``dsa_qi_at`` (R, J di), ``dsa_w_at``
    (R, J), the scores made again from those ``dsa_index_at`` (R, s),
    every head's q ``dsa_q_at`` (R, h hd) and logsumexp ``dsa_lse_at`` (R,
    h), the first head's ``dsa_o_at`` (R, hd), the row's loss
    ``dsa_kl_at`` (R,))."""
    from ompi_tpu.ops.sparse_attention import selection_bytes

    b, s, _ = x.shape
    nh, nkv, dt = cfg.n_heads_here, cfg.n_kv_heads_here, cfg.compute_dtype
    eps, theta = cfg.rms_norm_eps, cfg.rope_theta
    heads, di, topk = cfg.index_heads, cfg.index_head_dim, cfg.index_topk
    split = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], eps)
        q, k, _, seen = normed_qk(p, h, cfg, interpret=interpret)
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
            dsa_selection_seq=selection_bytes(sel),
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


def _dsa_shapes(cfg) -> dict:
    """Attention's leaves (``gqa_shapes``) and the indexer's: its queries,
    its one key and that key's LayerNorm, its heads' weights."""
    d, di = cfg.hidden_size, cfg.index_head_dim
    return {**gqa_shapes(cfg), "index_wq": (d, cfg.index_heads * di),
            "index_wk": (d, di), "index_k_norm": (di,),
            "index_k_bias": (di,), "index_ww": (d, cfg.index_heads)}


def _dsa_plan(cfg, b, s, interpret) -> dict:
    """What ``dsa_attention`` holds: attention under the selection, q and
    k by ``normed_qk`` with RoPE on, the indexer's two kernels."""
    counts = pass_counts(b, cfg.n_heads_here, cfg.n_kv_heads_here, s,
                         min(cfg.attn_block, s), topk=cfg.index_topk)
    qk, moved = qk_plan(cfg, interpret)
    return held({**counts, **moved}, flash=flash_on_kernels(interpret),
                qk=qk, index=index_on_kernels(interpret))


#: Keye-VL-2.0's ``sparse_attention`` layers' operator
DSA = Sublayer(
    name="sparse_attention", group="dsa", scope="otpu_dsa",
    run=dsa_attention, shapes=_dsa_shapes,
    undecayed=("ln1", "q_norm", "k_norm", "index_k_norm", "index_k_bias"),
    starts={"index_k_bias": zeros},
    reports=lambda cfg: {
        "attn_qk_in": 1, "attn_qk": 1, "dsa_selection_seq": 2,
        "dsa_kl_at": 0, **{"dsa_" + k: 1 for k in (
            "ki_seq", "k_seq", "v_seq", "kall_seq", "qi_at", "w_at",
            "index_at", "q_at", "lse_at", "o_at")}},
    keeps=ATTN_KEEPS + (DSA_SELECTION, DSA_INDEX_LSE, DSA_LOSS),
    plan=_dsa_plan)
