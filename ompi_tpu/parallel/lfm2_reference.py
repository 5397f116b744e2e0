"""A plain reference for LFM2-8B-A1B's training step of
``parallel/train.py``: forward, cross-entropy, gradients, one AdamW update
and the routers' bias update in straightforward ``jax.numpy``, float32,
matmuls at the highest precision, the short convolution as its three
shifted products written out, attention as a full ``softmax(QK^T +
mask)V`` with the key-value heads repeated, every held expert applied to
every token and weighted by a dense mask of the router's choice, one
shared matrix for the embedding and the head.  No kernel, no sort, no
scan, no blocking, no donation: it holds (T, V) logits, (h, s, s) scores
and (E, T, f) activations, so it is for small widths (the tests);
``benchmark/harness/lfm2kit.py`` is the benchmark's own copy, blocked to
fit beside the program's state.

The equations are ``lfm2_moe``'s (the published ``config.json`` of
LiquidAI/LFM2-8B-A1B and its modelling code).  ``norm(x) = x /
sqrt(mean(x^2) + norm_eps) * gain``; no bias anywhere.  Every layer is
``h = x + Op(norm_op(x))``, then ``out = h + FFN(norm_ffn(h))``:

* ``Op`` of a ``conv`` layer: ``[B | C | u] = n W_in`` in that order;
  ``z_t = sum_{j=0..2} w_j (B * u)_{t-2+j}``, a depthwise causal
  convolution of ``conv_L_cache`` = 3 taps a channel with zeros before
  the sequence's start, no bias, no activation; ``Op = (C * z) W_out``.
* ``Op`` of a ``full_attention`` layer: q (heads x head width), k and v
  (key-value heads x head width); ``norm`` with a gain over each head's
  width of q and of k; RoPE (``rotate_half`` over the whole head width,
  ``rope_theta``, no scaling); causal ``softmax(q k^T / sqrt(head
  width)) v``, each key-value head read by ``num_attention_heads /
  num_key_value_heads`` query heads; ``W_o``.
* ``FFN`` of the model's first ``num_dense_layers`` layers: SwiGLU of
  ``intermediate_size``.  Of the others: ``s = sigmoid(n W_r)`` over all
  the experts; the ``num_experts_per_tok`` largest of ``s + b`` (``b``
  the balancing bias, ``use_expert_bias``: the choice only, and no
  gradient reaches it); weights ``routed_scaling_factor * s_chosen /
  sum(s_chosen)`` (``norm_topk_prob``); SwiGLU experts of
  ``moe_intermediate_size``; no shared expert.
* After the last layer one ``norm`` (lfm2 names it ``embedding_norm``),
  then logits against the embedding matrix itself
  (``tie_word_embeddings``).

Departures, each for a stated reason:

* **the share**: the rank holds ``experts_here`` of the routed experts
  and ``vocab_here`` rows of the vocabulary, as one chip of an
  expert-parallel deployment holds them; operators, dense MLP, routers
  and norms are whole.  The router scores and chooses among all the
  experts; a sparse layer's feed-forward is the held experts' weighted
  parts, and what the absent experts would add is left out.  Embedding,
  logits and loss are over the slice (``tests/test_lfm2_train.py`` ties
  the share to the whole layer: the shares' routed parts add up to the
  uncut layer);
* the published file gives none of: the denominator's small constant
  (1e-20, as the program's ``route_sigmoid_bias``), the bias's update
  (DeepSeek-V3's sign rule, ``b += gamma * sign(mean load - load)``, no
  auxiliary loss), the optimiser (the configuration file lists them as
  assumed);
* the convolution is not reset and attention not masked between packed
  documents;
* the loads that move the bias are the whole batch's, not one
  data-parallel rank's; AdamW decays every matrix and the taps, no
  gain (``olmoe_reference.adamw_step``: this model's undecayed leaves
  are its gains), and never touches the bias.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.olmoe_reference import _norm, _rope, adamw_step
from ompi_tpu.parallel.config import ModelConfig

KINDS = {"c": "conv_dense", "a": "attn_dense", "C": "conv_moe",
         "A": "attn_moe"}


def conv_taps(a, w):
    """``z_t = sum_j w_j a_{t - (taps - 1) + j}`` along axis 1 of ``a``
    (b, s, d), ``w`` (taps, d), zeros before position 0: each tap's
    shifted product written out."""
    taps, s = w.shape[0], a.shape[1]
    z = jnp.zeros_like(a)
    for j in range(taps):
        back = taps - 1 - j                 # positions this tap looks back
        if back < s:
            z = z.at[:, back:].add(a[:, :s - back] * w[j])
    return z


def short_conv(p, x, cfg: ModelConfig):
    """``Op`` of a ``conv`` layer, without the residual add."""
    d = x.shape[-1]
    bcu = _norm(x, p["ln1"], cfg.rms_norm_eps) @ p["in_proj"]
    b_gate, c_gate, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    return (c_gate * conv_taps(b_gate * u, p["conv_w"])) @ p["out_proj"]


def attention(p, x, cfg: ModelConfig):
    """``Op`` of a ``full_attention`` layer, without the residual add."""
    b, s, _ = x.shape
    nh, nkv, eps = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.rms_norm_eps
    h = _norm(x, p["ln1"], eps)
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q = _norm(heads(h @ p["wq"], nh), p["q_norm"], eps)
    k = _norm(heads(h @ p["wk"], nkv), p["k_norm"], eps)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    k, v = (jnp.repeat(t, nh // nkv, axis=1)
            for t in (k, heads(h @ p["wv"], nkv)))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(p, h, bias, cfg: ModelConfig):
    """(scores (T, E), the dense one-hot of the choice (T, k, E), the
    weight every expert has on every token (T, E))."""
    scores = jax.nn.sigmoid(h @ p["router"])
    _, top_e = jax.lax.top_k(scores + bias, cfg.num_experts_per_tok)
    choice = jax.nn.one_hot(top_e, cfg.num_experts)
    chosen = jnp.einsum("tke,te->tk", choice, scores)
    if cfg.norm_topk_prob:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    weight = jnp.einsum("tk,tke->te", chosen * cfg.routed_scaling_factor,
                        choice)
    return scores, choice, weight


def experts(p, x, bias, cfg: ModelConfig):
    """(``FFN`` of a sparse layer with the experts held here on the
    residual stream ``x`` (b, s, d), without the residual add; the slots
    every expert of all of them received)."""
    b, s, d = x.shape
    h = _norm(x, p["ln2"], cfg.rms_norm_eps).reshape(b * s, d)
    _, choice, weight = route(p, h, bias, cfg)
    first = cfg.first_expert_here
    here = weight[:, first:first + cfg.n_experts_here]       # (T, E here)
    act = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["gate"])) \
        * jnp.einsum("td,edf->etf", h, p["up"])
    y = jnp.einsum("te,etd->td", here,
                   jnp.einsum("etf,efd->etd", act, p["down"]))
    return y.reshape(b, s, d), jnp.sum(choice, axis=(0, 1))


def layers_of(params, cfg: ModelConfig):
    """(letter, the layer's leaves) of the held layers in their order,
    from the tree's runs of like layers (``cfg.segments``)."""
    for unit, n, first in cfg.segments:
        group = params["layers"][f"l{first}"]
        for i in range(n):
            for letter in unit:
                yield letter, jax.tree.map(lambda a: a[i],
                                           group[KINDS[letter]])


def forward(params, tokens, cfg: ModelConfig, bias, head=None):
    """(logits (b, s, V), slots an expert a sparse layer (L, E)).
    ``head`` (d, V), where given, stands in the embedding matrix's place
    under the cross-entropy: the untied control."""
    x = params["embed"][tokens]
    loads = []
    for letter, p in layers_of(params, cfg):
        if letter in "cC":
            x = x + short_conv(p, x, cfg)
        else:
            x = x + attention(p, x, cfg)
        if letter in "ca":
            x = x + swiglu(_norm(x, p["ln2"], cfg.rms_norm_eps), p["gate"],
                           p["up"], p["down"])
        else:
            y, load = experts(p, x, bias["layers"][len(loads)], cfg)
            x = x + y
            loads.append(load)
    head = params["embed"].T if head is None else head
    return _norm(x, params["final_norm"], cfg.rms_norm_eps) @ head, \
        jnp.stack(loads)


def loss_parts(params, tokens, labels, cfg: ModelConfig, bias, head=None):
    """(cross-entropy, slots an expert a sparse layer (L, E)); ``labels``
    may be longer than ``tokens`` (the batch's form for a model with a
    next-next-token head): the first ``s`` are read."""
    logits, loads = forward(params, tokens, cfg, bias, head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels[:, :tokens.shape[1], None], -1)
    return -jnp.mean(picked), loads


def grads(params, tokens, labels, cfg: ModelConfig, bias, head=None):
    """((loss, loads), the gradient of the loss with respect to the
    parameters: the tied matrix's is the sum of the gather's and the
    head's; none flows to the bias)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(
            params, tokens, labels, cfg, bias, head)


def zero_bias(cfg: ModelConfig) -> dict:
    return {"layers": jnp.zeros((cfg.n_sparse_here, cfg.num_experts))}


def bias_step(bias, loads, cfg: ModelConfig) -> dict:
    return {"layers": bias["layers"] + cfg.bias_update_gamma * jnp.sign(
        jnp.mean(loads, -1, keepdims=True) - loads)}


def train_steps(params, batches, cfg: ModelConfig):
    """Parameters and biases after one AdamW step a (tokens, labels)
    batch, and the loss of each."""
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    bias, losses = zero_bias(cfg), []
    for t, (tokens, labels) in enumerate(batches, 1):
        (loss, loads), g = grads(params, tokens, labels, cfg, bias)
        params, mom, var = adamw_step(params, mom, var, t, g, cfg)
        bias = bias_step(bias, loads, cfg)
        losses.append(loss)
    return params, bias, losses
