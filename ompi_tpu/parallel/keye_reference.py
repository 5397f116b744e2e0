"""A plain reference for the training step of Keye-VL-2.0-30B-A3B's
language model in ``parallel/train.py``: forward, cross-entropy, the
auxiliary load-balancing loss, the indexer's alignment loss, gradients and
one AdamW update in straightforward ``jax.numpy``, float32, matmuls at the
highest precision; the index scores as a dense (s, s) array, the choice by
``jax.lax.top_k``, attention as a dense ``softmax(QK^T + mask)V`` under the
selection with the key-value heads repeated, every held expert applied to
every token and weighted by a dense mask of the router's choice, the two
detachments as ``jax.lax.stop_gradient`` where the description puts them.
No kernel, no counting pass, no blocks, no checkpoint, no donation: it
holds (T, V) logits, (h, s, s) scores and (E, T, f) activations, so it is
for small widths (the tests); ``benchmark/harness/keyekit.py`` is the
benchmark's own copy, blocked to fit beside the program's state.

The equations are the published ``config.json``'s of
Kwai-Keye/Keye-VL-2.0-30B-A3B (the Qwen3-MoE family's keys and
``sa_config``) and, for the sparse attention, DeepSeek-V3.2's report's
(DSA: lightning indexer, token-granular top-k, KL alignment loss).
``norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * gain``; no bias but the
indexer's LayerNorm's.  Layer ``l`` on the residual stream ``x``:

* ``h = norm_1(x)``; q, k, v = ``h W_q``, ``h W_k``, ``h W_v`` on heads of
  ``head_dim``, each key-value head read by ``heads / kv heads`` query
  heads; ``q <- RoPE(norm_head(q))``, ``k <- RoPE(norm_head(k))``: a gain
  over each head's width, then RoPE (``rotate_half``, the whole head,
  ``rope_theta``);
* the indexer reads ``hI = stop_gradient(h)``: ``qI = hI W_qI`` on
  ``index_heads`` heads of ``index_head_dim``, ``kI = LayerNorm(hI W_kI)``
  (one key a position; gain and bias), RoPE on both over the whole width,
  ``w = hI W_wI``; ``I[t, u] = sum_j w[t, j] relu(qI[t, j] . kI[u]) /
  sqrt(index_heads x index_head_dim)`` for u <= t;
* ``S_t``: the ``min(t + 1, index_topk)`` keys u <= t of largest ``I[t,
  u]``; a constant, through which no gradient passes;
* ``a[t, h, .] = softmax over S_t of q[t, h] . k[., g(h)] / sqrt(head_dim)``;
  ``x <- x + (a v) W_o``;
* ``h2 = norm_2(x)``; ``p = softmax(h2 W_router)`` over all the experts,
  the ``num_experts_per_tok`` largest, their probabilities normalised to
  one; ``x <- x + sum_e w_e W_down,e(silu(W_gate,e h2) * W_up,e h2)``, no
  shared expert, every layer sparse, no balancing bias;
* the layer's alignment loss: ``sum_t KL(pbar[t, .] || softmax over S_t of
  I[t, .])`` with ``pbar[t, u] = stop_gradient(mean_h a[t, h, u])``.
* After the last layer one ``norm``, then an untied head.  The loss is the
  cross-entropy plus ``aux_loss_coef`` times the load-balancing loss of
  HF's ``load_balancing_loss_func`` (every layer's rows in one mean) plus
  ``index_loss_coef`` times the alignment losses' sum over the layers,
  averaged over the tokens.

Departures, each for a stated reason:

* **the share**: the rank holds ``experts_here`` of the routed experts
  and ``vocab_here`` rows of the vocabulary, as one chip of an
  expert-parallel deployment holds them; attention, indexer, routers and
  norms are whole.  What the absent experts would add is left out;
  embedding, logits and loss are over the slice
  (``tests/test_keye_train.py`` ties the share to the whole layer);
* the published indexer's Hadamard rotation of ``qI`` and ``kI`` changes
  no product in exact arithmetic, and its FP8 quantisation is inference's:
  both are left out;
* a tie at the selection's bar goes to the earlier key
  (``jax.lax.top_k``'s own order), so that a row selects exactly
  ``min(t + 1, index_topk)``;
* the step trains on text ids: M-RoPE's three components are equal there,
  so it is one-dimensional RoPE; the vision tower is left out;
* attention is not masked between packed documents; the auxiliary loss and
  its coefficient are the trainer's; AdamW decays every matrix and no gain
  or bias.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.olmoe_reference import _norm, _rope
from ompi_tpu.parallel.qwen3next_reference import (adamw_step,  # noqa: F401
                                                   layers_of as _layers_of)
from ompi_tpu.parallel.config import ModelConfig

KINDS = {"S": "dsa_moe"}


def layernorm(x, gain, bias, eps: float):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gain + bias


def index_scores(p, h, cfg: ModelConfig):
    """The indexer's scores (b, s, s) of the normed rows ``h`` (b, s, d),
    -inf where a key lies behind its query."""
    b, s, _ = h.shape
    heads, di = cfg.index_heads, cfg.index_head_dim
    qi = _rope((h @ p["index_wq"]).reshape(b, s, heads, di).transpose(
        0, 2, 1, 3), cfg.rope_theta)                        # (b, J, s, di)
    ki = _rope(layernorm(h @ p["index_wk"], p["index_k_norm"],
                         p["index_k_bias"], cfg.rms_norm_eps)[:, None],
               cfg.rope_theta)[:, 0]                        # (b, s, di)
    w = h @ p["index_ww"]                                   # (b, s, J)
    sc = jnp.einsum("btj,bjtu->btu", w, jax.nn.relu(
        jnp.einsum("bjtd,bud->bjtu", qi, ki))) / jnp.sqrt(
            jnp.float32(heads * di))
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    return jnp.where(seen, sc, -jnp.inf)


def select(scores, topk: int):
    """(b, s, s) whether key u is among the ``min(t + 1, topk)`` keys u <=
    t of largest score (``scores`` -inf behind the query)."""
    s = scores.shape[-1]
    _, top = jax.lax.top_k(scores, min(topk, s))            # (b, s, k)
    chosen = jnp.sum(jax.nn.one_hot(top, s, dtype=jnp.int32), axis=-2) > 0
    return chosen & (scores > -jnp.inf)


def attention(p, x, cfg: ModelConfig, chosen=None):
    """(the attention sublayer's output without the residual add, the
    layer's alignment loss summed over its rows, the selection).  With
    ``chosen`` (b, s, s) the selection is given, not made."""
    b, s, _ = x.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_width
    h = _norm(x, p["ln1"], cfg.rms_norm_eps)
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q, k, v = (heads(h @ p[w], n) for w, n in (
        ("wq", nh), ("wk", nkv), ("wv", nkv)))
    q = _rope(_norm(q, p["q_norm"], cfg.rms_norm_eps), cfg.rope_theta)
    k = _rope(_norm(k, p["k_norm"], cfg.rms_norm_eps), cfg.rope_theta)
    scores = index_scores(p, jax.lax.stop_gradient(h), cfg)
    if chosen is None:
        chosen = select(jax.lax.stop_gradient(scores), cfg.index_topk)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    a = jax.nn.softmax(jnp.where(chosen[:, None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", a, v)
    pbar = jax.lax.stop_gradient(jnp.mean(a, axis=1))       # (b, s, s)
    logq = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    live = pbar > 0.0
    kl = jnp.sum(jnp.where(live, pbar * (
        jnp.log(jnp.where(live, pbar, 1.0))
        - jnp.where(chosen, logq, 0.0)), 0.0))
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"], kl, chosen


def route(p, rows, cfg: ModelConfig):
    """(probabilities (T, E), the dense one-hot of the choice (T, k, E),
    the weight every expert has on every token (T, E))."""
    probs = jax.nn.softmax(rows @ p["router"], axis=-1)
    _, top_e = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    choice = jax.nn.one_hot(top_e, cfg.num_experts)
    chosen = jnp.einsum("tke,te->tk", choice, probs)
    if cfg.norm_topk_prob:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return probs, choice, jnp.einsum("tk,tke->te", chosen, choice)


def experts(p, x, cfg: ModelConfig):
    """(the held SwiGLU experts' weighted parts on the post-attention
    stream ``x`` (b, s, d), without the residual add; the slots every
    expert received; the probabilities' sum an expert)."""
    b, s, d = x.shape
    h = _norm(x, p["ln2"], cfg.rms_norm_eps).reshape(b * s, d)
    probs, choice, weight = route(p, h, cfg)
    first = cfg.first_expert_here
    here = weight[:, first:first + cfg.n_experts_here]      # (T, E here)
    act = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["gate"])) \
        * jnp.einsum("td,edf->etf", h, p["up"])
    y = jnp.einsum("te,etd->td", here,
                   jnp.einsum("etf,efd->etd", act, p["down"]))
    return y.reshape(b, s, d), jnp.sum(choice, axis=(0, 1)), \
        jnp.sum(probs, axis=0)


def layers_of(params, cfg: ModelConfig):
    """(letter, the layer's leaves) of the held layers in their order."""
    return _layers_of(params, cfg, KINDS)


def forward(params, tokens, cfg: ModelConfig, selections=None):
    """(logits (b, s, V), slots an expert a layer (L, E), probabilities'
    sum an expert a layer (L, E), the alignment loss summed over rows a
    layer (L,), the selections (L, b, s, s)); ``selections`` gives them
    instead."""
    x = params["embed"][tokens]
    loads, prob_sums, kls, made = [], [], [], []
    for i, (_, p) in enumerate(layers_of(params, cfg)):
        y, kl, chosen = attention(
            p, x, cfg, None if selections is None else selections[i])
        x = x + y
        y, load, prob_sum = experts(p, x, cfg)
        x = x + y
        loads.append(load)
        prob_sums.append(prob_sum)
        kls.append(kl)
        made.append(chosen)
    return _norm(x, params["final_norm"], cfg.rms_norm_eps) \
        @ params["head"], jnp.stack(loads), jnp.stack(prob_sums), \
        jnp.stack(kls), jnp.stack(made)


def loss_parts(params, tokens, labels, cfg: ModelConfig, selections=None,
               terms=("ce", "aux", "index")):
    """(total, (cross-entropy, the weighted auxiliary loss, the weighted
    alignment loss, slots an expert a layer (L, E), the selections));
    ``labels`` may be longer than ``tokens``: the first ``s`` are read.
    ``terms`` names the parts that make the total (a test differentiates
    one at a time)."""
    logits, loads, prob_sums, kls, made = forward(params, tokens, cfg,
                                                  selections)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels[:, :tokens.shape[1], None], -1)
    ce = -jnp.mean(picked)
    rows = loads.shape[0] * tokens.size     # every layer's rows in one mean
    aux = cfg.aux_loss_coef * cfg.num_experts * jnp.sum(
        (jnp.sum(loads, 0) / rows) * (jnp.sum(prob_sums, 0) / rows))
    index = cfg.index_loss_coef * jnp.sum(kls) / tokens.size
    parts = {"ce": ce, "aux": aux, "index": index}
    return sum(parts[t] for t in terms), (ce, aux, index, loads, made)


def grads(params, tokens, labels, cfg: ModelConfig, selections=None,
          terms=("ce", "aux", "index")):
    """((total, (cross-entropy, auxiliary loss, alignment loss, loads,
    selections)), the gradient of the total with respect to the
    parameters)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(
            params, tokens, labels, cfg, selections, terms)


def train_steps(params, batches, cfg: ModelConfig, selections=None):
    """Parameters after one AdamW step a (tokens, labels) batch, and the
    (total, cross-entropy, auxiliary, alignment) losses of each;
    ``selections`` gives each batch's (a program's own)."""
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, (tokens, labels) in enumerate(batches, 1):
        (total, (ce, aux, index, _, _)), g = grads(
            params, tokens, labels, cfg,
            None if selections is None else selections[t - 1])
        params, mom, var = adamw_step(params, mom, var, t, g, cfg)
        losses.append((total, ce, aux, index))
    return params, losses
