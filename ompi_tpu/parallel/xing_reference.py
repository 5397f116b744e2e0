"""A plain reference for Xing4.0-29B-A4B's training step of
``parallel/train.py``: forward, the cross-entropy, gradients, one AdamW
update and the routers' bias update in straightforward ``jax.numpy``,
float32, matmuls at the highest precision, a Python loop over the layers, a
loop of ``hc_sinkhorn_iters`` sweeps, attention as a full ``softmax(QK^T +
mask)V``, every held expert applied to every token and weighted by a dense
mask of the router's choice.  No kernel, no sort, no scan, no checkpoint, no
blocking, no cache: it is for small widths (the tests);
``benchmark/harness/xingkit.py`` is the benchmark's own copy, blocked to
fit beside the program's state.

The model's published ``config.json`` (``model_type`` ``xing4_0``) uses
DeepSeek-V3's keys for latent attention, the router, the shared expert, the
dense SwiGLU and YaRN (arXiv:2412.19437 sections 2.1-2.2,
``modeling_deepseek_v3.py``), and five keys of its own for the residual
path: **manifold-constrained hyper-connections** (Xie et al., *mHC*,
arXiv:2512.24880 section 4, on Zhu et al., *Hyper-Connections*,
arXiv:2409.19606).  With n = ``hc_mult`` and a token's stream X in R^(n x d):

* in: ``X_0 = [e; ..; e]``, ``e = Embed(id)``;
* a sublayer F (latent attention, or the feed-forward; F holds its own
  pre-norm and adds no residual), with its own ``phi`` (n d, n^2 + 2 n;
  columns pre | post | res), gates ``alpha`` (3,) and offsets ``b``:
  ``x' = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)``; ``m = x' phi``;
  ``Hpre = sigmoid(alpha_pre m[:n] + b_pre)``; ``Hpost = 2
  sigmoid(alpha_post m[n:2n] + b_post)``; ``Hres =
  Sinkhorn(clip(alpha_res mat(m[2n:]) + b_res, clamp_min, clamp_max))``:
  ``M = exp(.)``, then ``hc_sinkhorn_iters`` times ``M <- M / (colsum(M) +
  hc_eps)``, ``M <- M / (rowsum(M) + hc_eps)``; ``u = Hpre X``; ``y =
  F(u)``; ``X <- Hres X + Hpost^T y``;
* a layer: the attention sublayer so, then the feed-forward so (a dense
  SwiGLU in the held leading layers, then the shared expert plus the routed
  ones: ``s = sigmoid(h W_r)``, the top k of ``s + bias``, weights
  ``routed_scaling_factor s_chosen / sum(s_chosen)``);
* out: ``h = sum_i X_L[i]``, ``logits = RMSNorm_f(h) W_head``;
* latent attention with **YaRN**: over the 32 pairs of the rotary part
  ``inv_freq = inter (1 - mask) + extra mask``, ``extra = theta^(-2i /
  rot)``, ``inter = extra / factor``, ``mask = 1 - clip((i - low) / (high -
  low), 0, 1)``, ``low`` / ``high`` the floor / ceiling of ``rot ln(original
  / (beta 2 pi)) / (2 ln theta)`` at ``beta_fast`` / ``beta_slow``; cos and
  sin times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``;
  scores times ``(nope + rot)^(-1/2) mscale(factor, mscale_all_dim)^2``,
  ``mscale(s, m) = 0.1 m ln s + 1``.

Departures from the sources, each for a stated reason:

* **the share**: the rank holds ``heads_here`` of the attention heads
  (``wq_b``, ``wkv_b`` and ``wo`` cut by heads, the two latents whole),
  ``experts_here`` of the routed experts and ``vocab_here`` rows of the
  vocabulary.  The router scores and chooses among all the experts; what the
  absent heads and experts would add to a sublayer's ``y`` is left out, and
  that partial ``y`` is what ``Hpost^T`` writes into the stream.  Given no
  share (``heads_here``, ``experts_here``, ``vocab_here`` 0) it is the whole
  model;
* the next-n module is not run (``mtp_here`` 0): how it joins a stream of n
  is in neither the file nor arXiv:2512.24880;
* what the file does not settle, as the configuration file's ``assumed``
  lists: the stream's start and end as Hyper-Connections has them; ``phi``'s
  column order; a sweep's columns before its rows (the paper's
  ``T_r(T_c(.))``); ``hc_eps`` in both divisions and nowhere else; the clamp
  ahead of the exponential and on nothing else; no gain in the path's norm;
  RoPE on interleaved pairs (DeepSeek-V3's ``rope_interleave``);
* ``n_group`` = ``topk_group`` = 1 as published, so the group-limited choice
  is the plain top k; no sequence-wise auxiliary loss; AdamW and the sign
  rule on the bias as ``joyai_reference`` (the HF model holds no optimiser).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.config import ModelConfig
from ompi_tpu.parallel.joyai_reference import route, swiglu
from ompi_tpu.parallel.olmoe_reference import _norm, adamw_step


def yarn_inv_freq(cfg: ModelConfig):
    """(the rotary part's ``rot / 2`` inverse frequencies under the file's
    ``rope_scaling``, what cos and sin are multiplied by); plain RoPE's
    where the file has none."""
    rot, theta, yarn = cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn
    extra = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    if yarn is None:
        return extra, 1.0
    turns = lambda beta: rot * math.log(
        yarn["original_max_position_embeddings"] / (beta * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(turns(yarn["beta_fast"])), 0)
    high = min(math.ceil(turns(yarn["beta_slow"])), rot - 1)
    ramp = (jnp.arange(rot // 2, dtype=jnp.float32) - low) / (
        high - low if high != low else 0.001)
    mask = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return (extra / yarn["factor"]) * (1.0 - mask) + extra * mask, \
        mscale(yarn["factor"], yarn["mscale"]) \
        / mscale(yarn["factor"], yarn["mscale_all_dim"])


def mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _rope(x, cfg: ModelConfig):
    """Rotary embedding of (..., s, rot) on interleaved pairs."""
    inv, by = yarn_inv_freq(cfg)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = by * jnp.cos(ang), by * jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def score_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn and cfg.yarn["mscale_all_dim"]:
        scale *= mscale(cfg.yarn["factor"], cfg.yarn["mscale_all_dim"]) ** 2
    return scale


def attention(p, u, cfg: ModelConfig):
    """The latent attention sublayer's ``y`` from its input ``u`` (b, s,
    d): pre-norm, the held heads, no residual."""
    b, s, _ = u.shape
    nh, eps, rank = p["wo"].shape[0] // cfg.v_head_dim, cfg.rms_norm_eps, \
        cfg.kv_lora_rank
    nope = cfg.qk_nope_head_dim
    h = _norm(u, p["ln1"], eps)
    heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
    q = heads(_norm(h @ p["wq_a"], p["q_a_norm"], eps) @ p["wq_b"])
    kv = h @ p["wkv_a"]
    kvb = heads(_norm(kv[..., :rank], p["kv_a_norm"], eps) @ p["wkv_b"])
    k_rope = _rope(kv[:, None, :, rank:], cfg)              # one for all
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_rope, (b, nh, s, k_rope.shape[-1]))], -1)
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * score_scale(cfg)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, kvb[..., nope:])
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def dense_mlp(p, u, cfg: ModelConfig):
    return swiglu(_norm(u, p["ln2"], cfg.rms_norm_eps), p["gate"], p["up"],
                  p["down"])


def sparse_mlp(p, u, bias, cfg: ModelConfig):
    """(the shared expert plus the held experts' weighted parts from the
    sublayer's input ``u`` (b, s, d), the slots every expert of all of them
    received): the held experts are those of ``p``, from
    ``cfg.first_expert_here`` on."""
    b, s, d = u.shape
    h = _norm(u, p["ln2"], cfg.rms_norm_eps).reshape(b * s, d)
    _, choice, weight = route(p, h, bias, cfg)
    first = cfg.first_expert_here
    here = weight[:, first:first + p["gate"].shape[0]]      # (T, E here)
    act = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["gate"])) \
        * jnp.einsum("td,edf->etf", h, p["up"])
    y = jnp.einsum("te,etd->td", here,
                   jnp.einsum("etf,efd->etd", act, p["down"]))
    y = y + swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y.reshape(b, s, d), jnp.sum(choice, axis=(0, 1))


def maps(p, x, cfg: ModelConfig, at: str):
    """``(Hpre (b, s, n), Hpost (b, s, n), Hres (b, s, n, n))`` of the
    stream ``x`` (b, s, n, d) from the path's leaves ``<at>_phi``,
    ``<at>_alpha``, ``<at>_b``."""
    b, s, n, d = x.shape
    flat = x.reshape(b, s, n * d)
    normed = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + cfg.rms_norm_eps)
    m = normed @ p[f"{at}_phi"]
    alpha, off = p[f"{at}_alpha"], p[f"{at}_b"]
    pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + off[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + off[n:2 * n])
    raw = (alpha[2] * m[..., 2 * n:] + off[2 * n:]).reshape(b, s, n, n)
    res = jnp.exp(jnp.clip(raw, cfg.mhc_h_res_clamp_min,
                           cfg.mhc_h_res_clamp_max))
    for _ in range(cfg.hc_sinkhorn_iters):
        res = res / (jnp.sum(res, axis=-2, keepdims=True) + cfg.hc_eps)
        res = res / (jnp.sum(res, axis=-1, keepdims=True) + cfg.hc_eps)
    return pre, post, res


def mixed(res, post, x, y):
    """``Hres X + Hpost^T y``: the stream behind a sublayer."""
    return jnp.einsum("bsij,bsjd->bsid", res, x) \
        + post[..., None] * y[:, :, None, :]


def sublayer(f, p, x, cfg: ModelConfig, at: str):
    """The stream behind the sublayer ``f`` (``u -> (y, extra)``), and
    ``extra``."""
    pre, post, res = maps(p, x, cfg, at)
    y, extra = f(jnp.einsum("bsn,bsnd->bsd", pre, x))
    return mixed(res, post, x, y), extra


def layer(p, x, bias, cfg: ModelConfig):
    """One layer on the stream: (x, the routed experts' loads, None of a
    dense layer); ``bias`` None says the layer is dense."""
    x, _ = sublayer(lambda u: (attention(p, u, cfg), None), p, x, cfg, "hc1")
    if bias is None:
        return sublayer(lambda u: (dense_mlp(p, u, cfg), None), p, x, cfg,
                        "hc2")
    return sublayer(lambda u: sparse_mlp(p, u, bias, cfg), p, x, cfg, "hc2")


def forward(params, tokens, cfg: ModelConfig, bias):
    """(logits (b, s, V), slots an expert a sparse layer (L, E))."""
    e = params["embed"][tokens]
    x = jnp.stack([e] * cfg.hc_mult, axis=2)
    loads = []
    for i in range(cfg.n_dense_here):
        x, _ = layer(jax.tree.map(lambda a: a[i], params["dense"]), x, None,
                     cfg)
    for i in range(cfg.n_sparse_here):
        x, load = layer(jax.tree.map(lambda a: a[i], params["layers"]), x,
                        bias["layers"][i], cfg)
        loads.append(load)
    h = jnp.sum(x, axis=2)
    return _norm(h, params["final_norm"], cfg.rms_norm_eps) \
        @ params["head"], jnp.stack(loads)


def loss_parts(params, tokens, labels, cfg: ModelConfig, bias):
    """(the mean cross-entropy, the slots an expert a sparse layer (L, E)).
    ``labels`` may be one longer than ``tokens`` (the batch's form for a
    model with a next-n module): the first ``s`` are read."""
    logits, loads = forward(params, tokens, cfg, bias)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels[:, :tokens.shape[1], None], -1)
    return -jnp.mean(picked), loads


def grads(params, tokens, labels, cfg: ModelConfig, bias):
    """((loss, loads), the gradient of the loss with respect to the
    parameters; none flows to the bias)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(
            params, tokens, labels, cfg, bias)


def zero_bias(cfg: ModelConfig) -> dict:
    return {"layers": jnp.zeros((cfg.n_sparse_here, cfg.num_experts))}


def bias_step(bias, loads, cfg: ModelConfig) -> dict:
    """The biases after a step whose sparse layers' experts received
    ``loads`` (L, E)."""
    return {"layers": bias["layers"] + cfg.bias_update_gamma * jnp.sign(
        jnp.mean(loads, -1, keepdims=True) - loads)}


def train_steps(params, batches, cfg: ModelConfig):
    """Parameters and biases after one AdamW step a (tokens, labels) batch,
    and the loss of each."""
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    bias, losses = zero_bias(cfg), []
    for t, (tokens, labels) in enumerate(batches, 1):
        (total, loads), g = grads(params, tokens, labels, cfg, bias)
        params, mom, var = adamw_step(params, mom, var, t, g, cfg)
        bias = bias_step(bias, loads, cfg)
        losses.append(total)
    return params, bias, losses
