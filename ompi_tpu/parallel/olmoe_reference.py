"""A plain reference for the OLMoE training step of ``parallel/train.py``:
forward, loss, gradients and one AdamW update in straightforward
``jax.numpy``, float32, matmuls at the highest precision, attention as a
full ``softmax(QK^T + mask)V``, every expert applied to every token and
weighted by a dense one-hot of the router's choice.  No kernel, no sort,
no blocking, no donation: it holds a (T, V) array of logits and an
(E, T, f) array of expert activations, so it is for small widths (the
tests) and for a machine with room (``benchmark/harness/olmoekit.py`` is
the benchmark's own copy, blocked to fit beside the program's state).

It follows the Hugging Face ``olmoe`` model (``modeling_olmoe.py``):
pre-norm block; q, k, v, o projections without bias; RMSNorm with a gain
over the whole width of q and of k before the heads are split; RoPE
(``rotate_half``); causal attention; router ``softmax(x W_r)`` over all
experts in float32, top k, weights not renormalised; expert
``down(silu(gate x) * up x)``; final norm; linear head; mean
cross-entropy; ``load_balancing_loss_func`` over every layer's rows at
once.  Departures, each for a stated reason:

* the router z-loss (mean squared logsumexp of the router's logits) is
  not in the HF model; OLMoE was trained with it (arXiv:2409.02060);
* the load-balancing loss takes the whole batch's statistics, not one
  data-parallel rank's: so that ``dp = 2`` and ``dp = 1`` are one model;
* AdamW decays every leaf but the norms' gains (the HF model holds no
  optimiser; the paper's exact exclusions are not asserted here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.config import ModelConfig
from ompi_tpu.parallel.train import (is_decayed, leaf_names, _leaf,
                                     _set_leaf)


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    hd, s = x.shape[-1], x.shape[-2]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


def forward(params, tokens, cfg: ModelConfig):
    """(logits (b, s, V), per layer the router's (probs (T, E), one-hot
    choice (T, k, E), logits (T, E)))."""
    b, s = tokens.shape
    nh, eps = cfg.num_attention_heads, cfg.rms_norm_eps
    x = params["embed"][tokens]
    routed = []
    for i in range(cfg.layers_here):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        h = _norm(x, p["ln1"], eps)
        q = _norm(h @ p["wq"], p["q_norm"], eps)
        k = _norm(h @ p["wk"], p["k_norm"], eps)
        v = h @ p["wv"]
        heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
        q, k, v = _rope(heads(q), cfg.rope_theta), \
            _rope(heads(k), cfg.rope_theta), heads(v)
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
        x = x + o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]

        h = _norm(x, p["ln2"], eps).reshape(b * s, -1)
        logits = h @ p["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, cfg.num_experts_per_tok)
        if cfg.norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
        choice = jax.nn.one_hot(top_e, cfg.num_experts)        # (T, k, E)
        weight = jnp.einsum("tk,tke->te", top_w, choice)       # (T, E)
        act = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["gate"])) \
            * jnp.einsum("td,edf->etf", h, p["up"])
        y = jnp.einsum("etf,efd->etd", act, p["down"])
        x = x + jnp.einsum("te,etd->td", weight, y).reshape(b, s, -1)
        routed.append((probs, choice, logits))
    h = _norm(x, params["final_norm"], eps)
    return h @ params["head"], routed


def loss_parts(params, tokens, labels, cfg: ModelConfig):
    """(total, (cross-entropy, load-balancing and z as weighted into
    the total, slots an expert a layer (L, E)))."""
    logits, routed = forward(params, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
    probs = jnp.concatenate([r[0] for r in routed])     # every layer's rows
    choice = jnp.concatenate([r[1] for r in routed])
    lb = cfg.num_experts * jnp.sum(
        jnp.mean(choice, axis=0) * jnp.mean(probs, axis=0)[None, :])
    lse = jax.nn.logsumexp(jnp.concatenate([r[2] for r in routed]), -1)
    z = jnp.mean(lse * lse)
    lb, z = cfg.aux_loss_coef * lb, cfg.z_loss_coef * z
    total = ce + lb + z
    loads = jnp.stack([jnp.sum(r[1], axis=(0, 1)) for r in routed])
    return total, (ce, lb, z, loads)


def grads(params, tokens, labels, cfg: ModelConfig):
    """((total, (ce, lb, z, loads)), the gradient of total)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(
            params, tokens, labels, cfg)


def adamw_step(params, mom, var, t: int, g, cfg: ModelConfig):
    """One update, ``t`` counted from 1: (params, mom, var)."""
    out = ({}, {}, {})
    for name, path in leaf_names(cfg):
        p, m, v, gi = (_leaf(tr, path) for tr in (params, mom, var, g))
        m = cfg.adam_b1 * m + (1 - cfg.adam_b1) * gi
        v = cfg.adam_b2 * v + (1 - cfg.adam_b2) * gi * gi
        upd = (m / (1 - cfg.adam_b1 ** t)) / (
            jnp.sqrt(v / (1 - cfg.adam_b2 ** t)) + cfg.adam_eps)
        if is_decayed(name):
            upd = upd + cfg.weight_decay * p
        lr = cfg.lr * min(1.0, t / cfg.warmup_steps)
        for tree, leaf in zip(out, (p - lr * upd, m, v)):
            _set_leaf(tree, path, leaf)
    return out


def train_steps(params, batches, cfg: ModelConfig):
    """Parameters after one AdamW step a (tokens, labels) batch, and the
    total loss of each."""
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, (tokens, labels) in enumerate(batches, 1):
        (total, _), g = grads(params, tokens, labels, cfg)
        params, mom, var = adamw_step(params, mom, var, t, g, cfg)
        losses.append(total)
    return params, losses
