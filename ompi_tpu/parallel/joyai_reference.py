"""A plain reference for JoyAI-LLM-Flash's training step of
``parallel/train.py``: forward, both cross-entropies, gradients, one AdamW
update and the routers' bias update in straightforward ``jax.numpy``,
float32, matmuls at the highest precision, attention as a full
``softmax(QK^T + mask)V``, every held expert applied to every token and
weighted by a dense mask of the router's choice.  No kernel, no sort, no
scan, no blocking, no donation: it holds (T, V) logits, (h, s, s) scores
and (E, T, f) activations, so it is for small widths (the tests);
``benchmark/harness/joyaikit.py`` is the benchmark's own copy, blocked to
fit beside the program's state.

The model's published ``config.json`` uses DeepSeek-V3's keys letter for
letter, so the equations are those of ``modeling_deepseek.py`` and of
arXiv:2412.19437 sections 2.1-2.2: pre-norm blocks; latent attention (q
through a normed latent of ``q_lora_rank``; ``[c_kv | k_rope] = x W_kva``,
``[k_nope | v] = norm(c_kv) W_kvb``; RoPE on interleaved pairs of q's and
k's last ``qk_rope_head_dim`` entries, the one rotary key shared by every
head; ``softmax(q k^T / sqrt(qk_nope + qk_rope)) v``); a dense SwiGLU in
the first ``first_k_dense_replace`` layers; then routed experts beside a
shared one: ``s = sigmoid(h W_r)`` over all the experts, the top k of
``s + b`` (``b`` the balancing bias: the choice only), weights
``routed_scaling_factor * s_chosen / sum(s_chosen)``; after a step
``b += gamma * sign(mean load - load)``; one next-next-token module
(``h' = M [norm(emb(t_{i+1})) ; norm(h_i)]``, a sparse layer, a norm, the
same embedding and head, its cross-entropy against ``t_{i+2}`` weighted
``mtp_loss_coef``).  Departures, each for a stated reason:

* **the share**: the rank holds ``experts_here`` of the routed experts and
  ``vocab_here`` rows of the vocabulary.  The router scores and chooses
  among all the experts; the layer's output is the shared expert plus the
  held experts' weighted parts, and what the absent experts would add is
  left out.  The embedding, the logits and both losses are over the slice
  of the vocabulary (``tests/test_joyai_train.py`` ties the share to the
  whole layer: the shares' routed parts and the shared expert once add up
  to the uncut layer);
* ``n_group`` = ``topk_group`` = 1 in the published file, so DeepSeek-V3's
  group-limited choice is the plain top k and is not written out;
* the published file does not give them: ``mtp_loss_coef``, ``gamma``, no
  sequence-wise auxiliary loss, that the embedding's half comes first
  under ``M`` and ``h_i`` is taken before the final norm (DeepSeek-V3's
  own, the configuration file lists them as assumed);
* the loads that move the bias are the whole batch's, not one
  data-parallel rank's: so that ``dp = 2`` and ``dp = 1`` are one model;
* AdamW decays every leaf but the norms' gains, and never touches the
  bias (the HF model holds no optimiser).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.olmoe_reference import _norm, adamw_step
from ompi_tpu.parallel.config import ModelConfig


def _rope(x, theta):
    """Rotary embedding of (..., s, hd) on interleaved pairs."""
    hd, s = x.shape[-1], x.shape[-2]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                     -1).reshape(x.shape)


def attention(p, x, cfg: ModelConfig):
    """The latent attention sublayer with its residual add."""
    b, s, _ = x.shape
    nh, eps, rank = cfg.num_attention_heads, cfg.rms_norm_eps, \
        cfg.kv_lora_rank
    nope, hv = cfg.qk_nope_head_dim, cfg.v_head_dim
    h = _norm(x, p["ln1"], eps)
    heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
    q = heads(_norm(h @ p["wq_a"], p["q_a_norm"], eps) @ p["wq_b"])
    kv = h @ p["wkv_a"]
    kvb = heads(_norm(kv[..., :rank], p["kv_a_norm"], eps) @ p["wkv_b"])
    k_rope = _rope(kv[:, None, :, rank:], cfg.rope_theta)   # one for all
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                              cfg.rope_theta)], -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_rope, (b, nh, s, k_rope.shape[-1]))], -1)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, kvb[..., nope:])
    assert o.shape[-1] == hv
    return x + o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


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


def sparse_mlp(p, h, bias, cfg: ModelConfig):
    """(the shared expert plus the held experts' weighted parts on rows
    ``h`` (T, d), the slots every expert of all of them received)."""
    _, choice, weight = route(p, h, bias, cfg)
    first = cfg.first_expert_here
    here = weight[:, first:first + cfg.n_experts_here]       # (T, E here)
    act = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["gate"])) \
        * jnp.einsum("td,edf->etf", h, p["up"])
    y = jnp.einsum("te,etd->td", here,
                   jnp.einsum("etf,efd->etd", act, p["down"]))
    y = y + swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y, jnp.sum(choice, axis=(0, 1))


def sparse_layer(p, x, bias, cfg: ModelConfig):
    b, s, d = x.shape
    x = attention(p, x, cfg)
    y, load = sparse_mlp(p, _norm(x, p["ln2"], cfg.rms_norm_eps
                                  ).reshape(b * s, d), bias, cfg)
    return x + y.reshape(b, s, d), load


def forward(params, tokens, labels, cfg: ModelConfig, bias):
    """(logits (b, s, V), the module's logits (b, s, V), slots an expert
    a sparse layer (L, E), the module's last).  ``labels`` (b, s + 1):
    the next token of every position and, one further, the one after."""
    b, s = tokens.shape
    eps = cfg.rms_norm_eps
    x = params["embed"][tokens]
    loads = []
    for i in range(cfg.n_dense_here):
        p = jax.tree.map(lambda a: a[i], params["dense"])
        x = attention(p, x, cfg)
        x = x + swiglu(_norm(x, p["ln2"], eps), p["gate"], p["up"],
                       p["down"])
    for i in range(cfg.n_sparse_here):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        x, load = sparse_layer(p, x, bias["layers"][i], cfg)
        loads.append(load)
    logits = _norm(x, params["final_norm"], eps) @ params["head"]
    mtp = params["mtp"]
    joined = jnp.concatenate(
        [_norm(params["embed"][labels[:, :s]], mtp["enorm"], eps),
         _norm(x, mtp["hnorm"], eps)], -1)
    x2, load = sparse_layer(mtp, joined @ mtp["proj"], bias["mtp"][0], cfg)
    loads.append(load)
    logits2 = _norm(x2, mtp["norm"], eps) @ params["head"]
    return logits, logits2, jnp.stack(loads)


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def loss_parts(params, tokens, labels, cfg: ModelConfig, bias):
    """(total, (cross-entropy, the module's as weighted into the total,
    slots an expert a sparse layer (L, E)))."""
    logits, logits2, loads = forward(params, tokens, labels, cfg, bias)
    s = tokens.shape[1]
    ce = _cross_entropy(logits, labels[:, :s])
    mtp = cfg.mtp_loss_coef * _cross_entropy(logits2, labels[:, 1:])
    return ce + mtp, (ce, mtp, loads)


def grads(params, tokens, labels, cfg: ModelConfig, bias):
    """((total, (ce, mtp, loads)), the gradient of total with respect to
    the parameters; none flows to the bias)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(
            params, tokens, labels, cfg, bias)


def zero_bias(cfg: ModelConfig) -> dict:
    return {"layers": jnp.zeros((cfg.n_sparse_here, cfg.num_experts)),
            "mtp": jnp.zeros((1, cfg.num_experts))}


def bias_step(bias, loads, cfg: ModelConfig) -> dict:
    """The biases after a step whose sparse layers' experts received
    ``loads`` (L, E), the module's last."""
    moved = jnp.concatenate([bias["layers"], bias["mtp"]]) \
        + cfg.bias_update_gamma * jnp.sign(
            jnp.mean(loads, -1, keepdims=True) - loads)
    return {"layers": moved[:-1], "mtp": moved[-1:]}


def train_steps(params, batches, cfg: ModelConfig):
    """Parameters and biases after one AdamW step a (tokens, labels)
    batch, and the total loss of each."""
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    bias, losses = zero_bias(cfg), []
    for t, (tokens, labels) in enumerate(batches, 1):
        (total, (_, _, loads)), g = grads(params, tokens, labels, cfg, bias)
        params, mom, var = adamw_step(params, mom, var, t, g, cfg)
        bias = bias_step(bias, loads, cfg)
        losses.append(total)
    return params, bias, losses
