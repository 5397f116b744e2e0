"""A plain reference for Ouro-2.6B's looped training step of
``parallel/train.py``: forward, the expected loss under the exit
distribution with its entropy bonus, gradients and one AdamW update in
straightforward ``jax.numpy``, float32, matmuls at the highest precision;
**the passes as a Python loop over the same dictionary of leaves**,
attention as a dense ``softmax(QK^T + mask)V`` over (s, s) scores, the
passes' full logits, the loss as its definition, gradients by ``jax.grad``
with no custom rule.  No kernel, no blocks, no scan, no checkpoint, no
donation: it holds (T, b, s, V) logits and (h, s, s) scores, so it is for
small widths (the tests); ``benchmark/harness/ourokit.py`` is the
benchmark's own copy, blocked to fit beside the program's state.

The equations are the published ``config.json``'s of ByteDance/Ouro-2.6B
and its report's (*Scaling Latent Reasoning via Looped Language Models*,
arXiv:2510.25741).  ``norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) *
gain``; no bias but the gate's.  A layer on the residual stream ``x`` is a
**sandwich**: every sublayer normed before and behind, the second norm
ahead of the residual add:

* ``a = Attn(norm_1(x))``, ``x <- x + norm_1post(a)``: q, k, v = ``h
  W_q``, ``h W_k``, ``h W_v`` on heads of ``head_dim`` (a key-value head a
  query head), no per-head norm; RoPE (``rotate_half``, the whole head,
  ``rope_theta``) on q and k; causal ``softmax(q k^T / sqrt(head_dim))
  v``; ``W_o``;
* ``m = W_down(silu(W_gate h) * W_up h)`` with ``h = norm_2(x)``, ``x <- x
  + norm_2post(m)``.

The model: ``h_0 = Embed(ids)``; for pass t = 1 .. ``total_ut_steps``
``h_t = norm_f(Layers(h_{t-1}))``, **the same layers with the same leaves
every pass**, the final norm at the end of every pass, its output what the
next pass reads.  Behind every pass the one head and the one exit gate read
``h_t``: ``logits_t = h_t W_head``, ``lambda_t = sigmoid(h_t . w_g +
b_g)`` a row.  The exit distribution a row: ``p_t = lambda_t prod_{j<t} (1
- lambda_j)`` for t < T, and the last pass takes what is left, ``p_T =
prod_{j<T} (1 - lambda_j)``.  The loss is the report's Stage I objective:
with ``CE_t,i = -log softmax(logits_t,i)[label_i]``,

``L = (1 / (b s)) sum_i [ sum_t p_t,i CE_t,i - exit_beta H(p_.,i) ]``,
``H(p) = -sum_t p_t log p_t``.

Departures, each for a stated reason: the gate, the distribution and the
entropy are a token row's and the loss their mean over rows (the file's
``assumed``); attention is not masked between packed documents;
``early_exit_threshold`` is generation's, a training step runs every pass;
AdamW decays every matrix and neither a gain nor the gate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.config import ModelConfig
from ompi_tpu.parallel.olmoe_reference import _norm, _rope
from ompi_tpu.parallel.qwen3next_reference import adamw_step  # noqa: F401
from ompi_tpu.parallel.qwen3next_reference import layers_of as _layers_of

KINDS = {"a": "attn_dense"}


def attention(p, h, cfg: ModelConfig):
    """Causal attention of the normed rows ``h`` (b, s, d), through
    ``W_o``."""
    b, s, _ = h.shape
    nh, hd = cfg.num_attention_heads, cfg.head_width
    heads = lambda t: t.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    q, k, v = (heads(h @ p[w]) for w in ("wq", "wk", "wv"))
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def layer(p, x, cfg: ModelConfig):
    """One sandwich layer on the residual stream ``x`` (b, s, d)."""
    eps = cfg.rms_norm_eps
    a = attention(p, _norm(x, p["ln1"], eps), cfg)
    x = x + _norm(a, p["ln1_post"], eps)
    h = _norm(x, p["ln2"], eps)
    m = (jax.nn.silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]
    return x + _norm(m, p["ln2_post"], eps)


def layers_of(params, cfg: ModelConfig):
    """The held layers' leaves in their order."""
    return [p for _, p in _layers_of(params, cfg, KINDS)]


def passes(params, tokens, cfg: ModelConfig):
    """``h_t`` (T, b, s, d) of every pass."""
    x, out = params["embed"][tokens], []
    for _ in range(cfg.total_ut_steps):
        for p in layers_of(params, cfg):        # the same leaves every pass
            x = layer(p, x, cfg)
        x = _norm(x, params["final_norm"], cfg.rms_norm_eps)
        out.append(x)
    return jnp.stack(out)


def exit_probabilities(gate):
    """The exit distribution (T, ...) from the gate's products (T, ...), as
    its definition: products of sigmoids."""
    lam = jax.nn.sigmoid(gate)
    left, out = jnp.ones_like(gate[0]), []
    for t in range(gate.shape[0] - 1):
        out.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(out + [left])


def loss_parts(params, tokens, labels, cfg: ModelConfig):
    """(total, (every pass's mean cross-entropy (T,), the expected
    cross-entropy, ``exit_beta`` x the mean entropy, the exit distribution
    (T, b, s))); ``labels`` may be longer than ``tokens``: the first ``s``
    are read."""
    hs = passes(params, tokens, cfg)
    logp = jax.nn.log_softmax(hs @ params["head"], axis=-1)
    ce = -jnp.take_along_axis(
        logp, jnp.broadcast_to(labels[None, :, :tokens.shape[1], None],
                               hs.shape[:3] + (1,)), -1)[..., 0]
    gate = hs @ params["exit_gate"]["w"] + params["exit_gate"]["b"][0]
    p = exit_probabilities(gate)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                                 0.0), axis=0)
    expected = jnp.mean(jnp.sum(p * ce, axis=0))
    bonus = cfg.exit_beta * jnp.mean(entropy)
    return expected - bonus, (jnp.mean(ce, axis=(1, 2)), expected, bonus, p)


def grads(params, tokens, labels, cfg: ModelConfig):
    """((total, (the passes' cross-entropies, the expected one, the
    weighted entropy, p)), the gradient of the total with respect to the
    parameters)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(
            params, tokens, labels, cfg)


def train_steps(params, batches, cfg: ModelConfig):
    """Parameters after one AdamW step a (tokens, labels) batch, and each
    step's losses in the program's order (the total, every pass's
    cross-entropy, the expected one, the weighted entropy)."""
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, (tokens, labels) in enumerate(batches, 1):
        (total, (by_pass, expected, bonus, _)), g = grads(
            params, tokens, labels, cfg)
        params, mom, var = adamw_step(params, mom, var, t, g, cfg)
        losses.append(jnp.concatenate([
            jnp.stack([total]), by_pass, jnp.stack([expected, bonus])]))
    return params, losses
