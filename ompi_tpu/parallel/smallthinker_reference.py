"""A plain reference for SmallThinker-21BA3B's training step of
``parallel/train.py``: forward, cross-entropy, the auxiliary
load-balancing loss, gradients and one AdamW update in straightforward
``jax.numpy``, float32, matmuls at the highest precision; attention as a
dense ``softmax(QK^T + mask)V`` over (s, s) scores with the key-value
heads repeated and **the window written as its inequality**, RoPE by
layer, the router on the layer's input, every held expert applied to
every token and weighted by a dense mask of the router's choice.  No
kernel, no sort, no blocks, no checkpoint, no donation: it holds (T, V)
logits, (h, s, s) scores and (E, T, f) activations, so it is for small
widths (the tests); ``benchmark/harness/smallthinkerkit.py`` is the
benchmark's own copy, blocked to fit beside the program's state.

The equations are the published ``config.json``'s of
PowerInfer/SmallThinker-21BA3B-Instruct and its report's
(arXiv:2507.20984).  ``norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) *
gain``; no bias anywhere.  Layer ``l`` on the residual stream ``x``:

* ``r = x W_router``: the router's logits, **read from the layer's input,
  before the attention sublayer** and before its norm;
* ``h = norm_1(x)``; q, k, v = ``h W_q``, ``h W_k``, ``h W_v`` on heads of
  ``head_dim``, each key-value head read by ``heads / kv heads`` query
  heads; no QK-norm; where ``rope_layout[l]`` is 1 RoPE (``rotate_half``,
  the whole head, ``rope_theta``, no scaling) on q and k, where it is 0
  **none**; causal ``softmax(q k^T / sqrt(head_dim)) v`` in which key j
  is visible to query i iff ``0 <= i - j``, and where
  ``sliding_window_layout[l]`` is 1 also ``i - j < sliding_window_size``;
  ``x <- x + o W_o``;
* ``h2 = norm_2(x)``; ``p = softmax(r)`` over all the experts, the
  ``moe_num_active_primary_experts`` largest, weights ``p_chosen /
  sum(p_chosen)`` (``norm_topk_prob``: the same numbers as a softmax over
  the chosen logits); ``x <- x + sum_e w_e W_down,e(relu(W_gate,e h2) *
  W_up,e h2)``: ReGLU experts of ``moe_ffn_hidden_size``, no shared one,
  every layer sparse, no balancing bias.
* After the last layer one ``norm``, then an untied head.  The loss is the
  cross-entropy plus ``aux_loss_coef`` times the load-balancing loss of
  HF's ``load_balancing_loss_func`` (every layer's rows in one mean).

Departures, each for a stated reason:

* **the share**: the rank holds ``experts_here`` of the routed experts
  and ``vocab_here`` rows of the vocabulary, as one chip of an
  expert-parallel deployment holds them; attention, routers and norms are
  whole.  The router scores and chooses among all the experts; a layer's
  feed-forward is the held experts' weighted parts, and what the absent
  experts would add is left out.  Embedding, logits and loss are over the
  slice (``tests/test_smallthinker_train.py`` ties the share to the whole
  layer: the shares' routed parts, attention's counted once, add up to
  the uncut layer);
* the "secondary experts" the family's description mentions are no key of
  the published configuration and are left out;
* the router reads the stream **before** the attention norm (the report
  places it before attention; the file is silent on the norm);
* attention is not masked between packed documents;
* the auxiliary loss and its coefficient are the trainer's (the published
  file gives none); AdamW decays every matrix and no gain.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.olmoe_reference import _norm, _rope
from ompi_tpu.parallel.qwen3next_reference import (adamw_step,  # noqa: F401
                                                   layers_of as _layers_of)
from ompi_tpu.parallel.config import ModelConfig

KINDS = {"A": "attn_moe", "W": "swa_moe"}


def visible(s: int, window: int = 0):
    """(s, s) whether key j is visible to query i: ``0 <= i - j``, and
    under a window also ``i - j < window``."""
    away = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    return (away >= 0) & ((away < window) if window else True)


def attention(p, x, cfg: ModelConfig, letter: str):
    """The attention sublayer of a full (``A``) or sliding-window (``W``)
    layer, without the residual add."""
    b, s, _ = x.shape
    nh, nkv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_width
    kind = {"A": "full_attention", "W": "sliding_attention"}[letter]
    h = _norm(x, p["ln1"], cfg.rms_norm_eps)
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q, k, v = (heads(h @ p[w], n) for w, n in (
        ("wq", nh), ("wk", nkv), ("wv", nkv)))
    if kind in cfg.rope_kinds:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    mask = visible(s, cfg.sliding_window if letter == "W" else 0)
    w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def route(p, rows, cfg: ModelConfig):
    """(probabilities (T, E), the dense one-hot of the choice (T, k, E),
    the weight every expert has on every token (T, E)) of the rows the
    router reads."""
    probs = jax.nn.softmax(rows @ p["router"], axis=-1)
    _, top_e = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    choice = jax.nn.one_hot(top_e, cfg.num_experts)
    chosen = jnp.einsum("tke,te->tk", choice, probs)
    if cfg.norm_topk_prob:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return probs, choice, jnp.einsum("tk,tke->te", chosen, choice)


def experts(p, x, routed, cfg: ModelConfig):
    """The held ReGLU experts' weighted parts on the post-attention
    stream ``x`` (b, s, d), under the routing ``routed`` made of the
    layer's input (``route``), without the residual add."""
    b, s, d = x.shape
    h = _norm(x, p["ln2"], cfg.rms_norm_eps).reshape(b * s, d)
    first = cfg.first_expert_here
    here = routed[2][:, first:first + cfg.n_experts_here]    # (T, E here)
    act = jax.nn.relu(jnp.einsum("td,edf->etf", h, p["gate"])) \
        * jnp.einsum("td,edf->etf", h, p["up"])
    y = jnp.einsum("te,etd->td", here,
                   jnp.einsum("etf,efd->etd", act, p["down"]))
    return y.reshape(b, s, d)


def layer(p, x, cfg: ModelConfig, letter: str):
    """(the layer's output, the slots every expert received, the
    probabilities' sum an expert)."""
    routed = route(p, x.reshape(-1, x.shape[-1]), cfg)   # before attention
    x = x + attention(p, x, cfg, letter)
    x = x + experts(p, x, routed, cfg)
    return x, jnp.sum(routed[1], axis=(0, 1)), jnp.sum(routed[0], axis=0)


def layers_of(params, cfg: ModelConfig):
    """(letter, the layer's leaves) of the held layers in their order."""
    return _layers_of(params, cfg, KINDS)


def forward(params, tokens, cfg: ModelConfig):
    """(logits (b, s, V), slots an expert a layer (L, E), probabilities'
    sum an expert a layer (L, E))."""
    x = params["embed"][tokens]
    loads, prob_sums = [], []
    for letter, p in layers_of(params, cfg):
        x, load, prob_sum = layer(p, x, cfg, letter)
        loads.append(load)
        prob_sums.append(prob_sum)
    return _norm(x, params["final_norm"], cfg.rms_norm_eps) \
        @ params["head"], jnp.stack(loads), jnp.stack(prob_sums)


def loss_parts(params, tokens, labels, cfg: ModelConfig):
    """(total, (cross-entropy, the weighted auxiliary loss, slots an
    expert a layer (L, E))); ``labels`` may be longer than ``tokens`` (the
    batch's form for a model with a next-next-token head): the first ``s``
    are read."""
    logits, loads, prob_sums = forward(params, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels[:, :tokens.shape[1], None], -1)
    ce = -jnp.mean(picked)
    rows = loads.shape[0] * tokens.size     # every layer's rows in one mean
    aux = cfg.aux_loss_coef * cfg.num_experts * jnp.sum(
        (jnp.sum(loads, 0) / rows) * (jnp.sum(prob_sums, 0) / rows))
    return ce + aux, (ce, aux, loads)


def grads(params, tokens, labels, cfg: ModelConfig):
    """((total, (cross-entropy, auxiliary loss, loads)), the gradient of
    the total with respect to the parameters)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(
            params, tokens, labels, cfg)


def train_steps(params, batches, cfg: ModelConfig):
    """Parameters after one AdamW step a (tokens, labels) batch, and the
    (total, cross-entropy, auxiliary) losses of each."""
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, (tokens, labels) in enumerate(batches, 1):
        (total, (ce, aux, _)), g = grads(params, tokens, labels, cfg)
        params, mom, var = adamw_step(params, mom, var, t, g, cfg)
        losses.append((total, ce, aux))
    return params, losses
