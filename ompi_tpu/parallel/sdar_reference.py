"""A plain reference for SDAR-30B-A3B's block-diffusion training step in
``parallel/train.py``: the step's noise, forward over a noisy and a clean
copy of every sequence, the masked rows' weighted cross-entropy, the
auxiliary load-balancing loss, gradients and one AdamW update in
straightforward ``jax.numpy``, float32, matmuls at the highest precision;
the ``(2L, 2L)`` mask written out from its four rules as a dense boolean,
attention as a dense ``softmax(QK^T + mask)V`` with the key-value heads
repeated, every held expert applied to every row and weighted by a dense
mask of the router's choice.  No kernel, no blocks, no checkpoint, no
donation: it holds (T, V) logits, (h, 2L, 2L) scores and (E, T, f)
activations, so it is for small widths (the tests);
``benchmark/harness/sdarkit.py`` is the benchmark's own copy, blocked to
fit beside the program's state.

The layers are the published ``config.json``'s of JetLM/SDAR-30B-A3B-Chat
(``model_type`` ``sdar_moe``; the Qwen3-MoE family's keys), and what goes
in and which keys a query sees are block diffusion's training pass
(BD3-LM, arXiv:2503.09573, section 3 and its appendix; SDAR,
arXiv:2510.06303, trains by the same pass).  ``norm(x) = x / sqrt(mean(x^2)
+ rms_norm_eps) * gain``; no bias.

* **The noise.**  A sequence ``x0`` of ``L`` tokens is ``L / B`` blocks of
  ``B`` (``block_length``).  With ``key = fold_in(fold_in(PRNGKey(
  noise_seed), spare_0), spare_1)``, ``spare`` the last two ids of the
  sequence's ``labels``: ``k_c = bits(fold_in(key, 0), (L / B,)) >> 8`` a
  block and ``k_i = bits(fold_in(key, 1), (L,)) >> 8`` a token, uniform
  24-bit integers.  The level is ``t_c = t_min + (1 - t_min) u_c`` on the
  grid of 2^-24, in integers so that no rounding can differ: ``q_c = m +
  floor((2^24 - m) k_c / 2^24)``, ``m = round(t_min 2^24)``, ``t_c = q_c /
  2^24``; token ``i`` of block ``c`` is replaced by the mask token
  (``mask_token_here``) iff ``k_i < q_c``: ``xt``.
* **The rows.**  The model reads the ``2L`` rows ``[xt ; x0]`` at positions
  ``[0..L-1 ; 0..L-1]``.  With ``blk(i) = pos_i // B`` query row ``i`` sees
  key row ``j`` iff: both noisy and ``blk(j) == blk(i)``; ``i`` noisy, ``j``
  clean and ``blk(j) < blk(i)``; both clean and ``blk(j) <= blk(i)``; a
  clean row sees no noisy one.
* **Layer l** on the residual stream ``x`` (b, 2L, d): ``h = norm_1(x)``;
  q, k, v = ``h W_q``, ``h W_k``, ``h W_v`` on heads of ``head_dim``, each
  key-value head read by ``heads / kv heads`` query heads; ``q <-
  RoPE(norm_head(q))``, ``k <- RoPE(norm_head(k))`` (a gain over each
  head's width, then ``rotate_half`` RoPE over the whole head at the row's
  position); ``a = softmax over the visible keys of q . k /
  sqrt(head_dim)``; ``x <- x + (a v) W_o``; ``h2 = norm_2(x)``; ``p =
  softmax(h2 W_router)`` over all the experts, the ``num_experts_per_tok``
  largest, their probabilities normalised to one; ``x <- x + sum_e w_e
  W_down,e(silu(W_gate,e h2) * W_up,e h2)``, no shared expert.
* **The loss** reads the noisy half's rows: ``L_bd = (1 / (b L)) sum_i m_i
  (1 / t_blk(i)) (-log softmax(logits_i)[x0_i])``, ``m_i`` 1 where row
  ``i`` of ``xt`` is the mask token: no shift, MDLM's weight for the linear
  schedule (arXiv:2406.07524).  The step's loss is ``L_bd`` plus
  ``aux_loss_coef`` times HF's load-balancing loss over all ``2L`` rows'
  routing, every layer's rows in one mean.

Departures, each for a stated reason: **the share** (the rank holds
``experts_here`` of the routed experts and ``vocab_here`` rows of the
vocabulary, the mask token the slice's last row; what the absent experts
would add is left out; ``tests/test_sdar_train.py`` ties the share to the
whole layer); attention is not masked between packed documents; the
auxiliary loss and its coefficient are the trainer's; AdamW decays every
matrix and no gain.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.olmoe_reference import _norm, _rope
from ompi_tpu.parallel.qwen3next_reference import (adamw_step,  # noqa: F401
                                                   layers_of as _layers_of)
from ompi_tpu.parallel.config import ModelConfig

KINDS = {"B": "bd_moe"}
#: deliberately wrong variants, for the tests that a comparison tells them:
#: a plain causal mask over the 2L rows; a noisy row that also sees its own
#: block's clean copy (``<=`` for ``<``); the loss without its weight; the
#: masked rows drawn at a fixed rate of one half
WRONG = ("causal", "leak", "unweighted", "half_rate")


def noise(tokens, labels, cfg: ModelConfig, wrong=None):
    """(the blocks' levels (b, L / B), the masked tokens (b, L) bool)."""
    b, length = tokens.shape
    base = jax.random.PRNGKey(cfg.noise_seed)
    m = round(cfg.t_min * 2 ** 24)
    a1, a0 = divmod(2 ** 24 - m, 2 ** 12)
    levels, masked = [], []
    for row in range(b):
        key = jax.random.fold_in(jax.random.fold_in(
            base, labels[row, -2].astype(jnp.uint32)),
            labels[row, -1].astype(jnp.uint32))
        k_c = jax.random.bits(jax.random.fold_in(key, 0),
                              (length // cfg.block_length,), jnp.uint32) >> 8
        k_i = jax.random.bits(jax.random.fold_in(key, 1), (length,),
                              jnp.uint32) >> 8
        # floor((2^24 - m) k_c / 2^24) in 32 bits: both factors in two
        # limbs of 12 bits, the low product's own low 12 bits dropped first
        k1, k0 = k_c // 2 ** 12, k_c % 2 ** 12
        q_c = m + a1 * k1 + (a1 * k0 + a0 * k1 + a0 * k0 // 2 ** 12) \
            // 2 ** 12
        if wrong == "half_rate":
            q_c = jnp.full_like(q_c, 2 ** 23)
        levels.append(q_c.astype(jnp.float32) / 2 ** 24)
        masked.append(k_i < jnp.repeat(q_c, cfg.block_length))
    return jnp.stack(levels), jnp.stack(masked)


def mask(length: int, bl: int, wrong=None):
    """The ``(2L, 2L)`` boolean: row ``i`` sees column ``j``."""
    blk = jnp.arange(length) // bl
    i, j = blk[:, None], blk[None, :]
    if wrong == "causal":
        rows = jnp.arange(2 * length)
        return rows[:, None] >= rows[None, :]
    never = jnp.zeros((length, length), bool)
    return jnp.block([[i == j, j <= i if wrong == "leak" else j < i],
                      [never, j <= i]])


def attention(p, x, cfg: ModelConfig, wrong=None):
    """The attention sublayer's output on the ``2L`` rows, without the
    residual add."""
    b, rows, _ = x.shape
    length = rows // 2
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_width
    h = _norm(x, p["ln1"], cfg.rms_norm_eps)
    heads = lambda t, n: t.reshape(b, rows, n, -1).transpose(0, 2, 1, 3)
    q, k, v = (heads(h @ p[w], n) for w, n in (
        ("wq", nh), ("wk", nkv), ("wv", nkv)))
    # both halves at positions 0 .. L - 1
    turn = lambda t: jnp.concatenate([
        _rope(t[:, :, :length], cfg.rope_theta),
        _rope(t[:, :, length:], cfg.rope_theta)], axis=2)
    q = turn(_norm(q, p["q_norm"], cfg.rms_norm_eps))
    k = turn(_norm(k, p["k_norm"], cfg.rms_norm_eps))
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    a = jax.nn.softmax(jnp.where(mask(length, cfg.block_length, wrong), sc,
                                 -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", a, v)
    return o.transpose(0, 2, 1, 3).reshape(b, rows, -1) @ p["wo"]


def route(p, rows, cfg: ModelConfig):
    """(probabilities (T, E), the dense one-hot of the choice (T, k, E),
    the weight every expert has on every row (T, E))."""
    probs = jax.nn.softmax(rows @ p["router"], axis=-1)
    _, top_e = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    choice = jax.nn.one_hot(top_e, cfg.num_experts)
    chosen = jnp.einsum("tke,te->tk", choice, probs)
    if cfg.norm_topk_prob:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return probs, choice, jnp.einsum("tk,tke->te", chosen, choice)


def experts(p, x, cfg: ModelConfig):
    """(the held SwiGLU experts' weighted parts on the post-attention
    stream ``x`` (b, 2L, d), without the residual add; the slots every
    expert received; the probabilities' sum an expert)."""
    b, rows, d = x.shape
    h = _norm(x, p["ln2"], cfg.rms_norm_eps).reshape(b * rows, d)
    probs, choice, weight = route(p, h, cfg)
    first = cfg.first_expert_here
    here = weight[:, first:first + cfg.n_experts_here]      # (T, E here)
    act = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["gate"])) \
        * jnp.einsum("td,edf->etf", h, p["up"])
    y = jnp.einsum("te,etd->td", here,
                   jnp.einsum("etf,efd->etd", act, p["down"]))
    return y.reshape(b, rows, d), jnp.sum(choice, axis=(0, 1)), \
        jnp.sum(probs, axis=0)


def layers_of(params, cfg: ModelConfig):
    """(letter, the layer's leaves) of the held layers in their order."""
    return _layers_of(params, cfg, KINDS)


def forward(params, ids, cfg: ModelConfig, wrong=None):
    """(the noisy half's logits (b, L, V), slots an expert a layer (L, E),
    probabilities' sum an expert a layer (L, E)) of the rows ``ids`` (b,
    2L), ``[xt ; x0]``."""
    x = params["embed"][ids]
    loads, prob_sums = [], []
    for _, p in layers_of(params, cfg):
        x = x + attention(p, x, cfg, wrong)
        y, load, prob_sum = experts(p, x, cfg)
        x = x + y
        loads.append(load)
        prob_sums.append(prob_sum)
    noisy = x[:, :ids.shape[1] // 2]
    return _norm(noisy, params["final_norm"], cfg.rms_norm_eps) \
        @ params["head"], jnp.stack(loads), jnp.stack(prob_sums)


def loss_parts(params, tokens, labels, cfg: ModelConfig, wrong=None,
               terms=("ce", "aux")):
    """(total, (``L_bd``, the weighted auxiliary loss, slots an expert a
    layer (L, E), the levels (b, L / B), the masked tokens (b, L))) of the
    clean sequences ``tokens`` (b, L); of ``labels`` the last two ids are
    read, the noise's key.  ``terms`` names the parts that make the total
    (a test differentiates one at a time)."""
    levels, masked = noise(tokens, labels, cfg, wrong)
    xt = jnp.where(masked, cfg.mask_token_here, tokens)
    logits, loads, prob_sums = forward(
        params, jnp.concatenate([xt, tokens], axis=1), cfg, wrong)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[..., None], -1)[..., 0]
    weight = jnp.where(masked, 1.0 / jnp.repeat(
        levels, cfg.block_length, axis=1), 0.0)
    if wrong == "unweighted":
        weight = masked.astype(jnp.float32)
    ce = -jnp.sum(weight * picked) / tokens.size
    rows = loads.shape[0] * 2 * tokens.size  # every layer's rows in one mean
    aux = cfg.aux_loss_coef * cfg.num_experts * jnp.sum(
        (jnp.sum(loads, 0) / rows) * (jnp.sum(prob_sums, 0) / rows))
    parts = {"ce": ce, "aux": aux}
    return sum(parts[t] for t in terms), (ce, aux, loads, levels, masked)


def grads(params, tokens, labels, cfg: ModelConfig, wrong=None,
          terms=("ce", "aux")):
    """((total, (``L_bd``, auxiliary loss, loads, levels, mask)), the
    gradient of the total with respect to the parameters)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(
            params, tokens, labels, cfg, wrong, terms)


def train_steps(params, batches, cfg: ModelConfig):
    """Parameters after one AdamW step a (tokens, labels) batch, and the
    (total, ``L_bd``, auxiliary) losses of each."""
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, (tokens, labels) in enumerate(batches, 1):
        (total, (ce, aux, *_)), g = grads(params, tokens, labels, cfg)
        params, mom, var = adamw_step(params, mom, var, t, g, cfg)
        losses.append((total, ce, aux))
    return params, losses
