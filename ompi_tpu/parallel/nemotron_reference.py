"""A plain reference for Nemotron-3-Super's training step of
``parallel/train.py``: forward, cross-entropy, gradients, one AdamW update
and the routers' bias update in straightforward ``jax.numpy``, float32,
matmuls at the highest precision, the state-space layer **as the
token-by-token recurrence** (one ``lax.scan`` step a position, no chunk),
attention as a full ``softmax(QK^T + mask)V``, every held expert applied
to every token and weighted by a dense mask of the router's choice.  No
kernel, no sort, no chunk, no blocking, no donation: it holds (T, V)
logits, (h, s, s) scores, (E, T, f) activations and a state a position,
so it is for small widths (the tests); ``benchmark/harness/nemotronkit.py``
is the benchmark's own copy, blocked to fit beside the program's state.

The equations are ``nemotron_h``'s (the published ``config.json`` of
NVIDIA-Nemotron-3-Super-120B-A12B-BF16; the mixer is Mamba-2's,
arXiv:2405.21060).  Every layer is ``x <- x + f(rmsnorm(x) * gain)`` with
exactly one ``f``, by the layer's letter in ``hybrid_override_pattern``:

* ``M``: ``[z | xBC | dt] = u W_in``; ``xBC <- silu(causal depthwise
  convolution over conv_kernel positions, with bias)``, split into x
  (heads x mamba_head_dim), B, C (groups x ssm_state_size); ``dt <-
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; a head's state
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D
  x_t``, B and C shared by the heads of a group; ``y <- rmsnorm over each
  group of (y * silu(z)) * gain``; ``f = y W_out``.
* ``*``: q, k, v, o projections without bias, each key-value head read by
  ``num_attention_heads / num_key_value_heads`` query heads, causal
  softmax, no rotary embedding.
* ``E``: ``s = sigmoid(u W_r)`` over all the experts; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` the balancing bias:
  the choice only); weights ``routed_scaling_factor * s_chosen /
  sum(s_chosen)``; ``f = (sum_k w_k relu(l W_up,k)^2 W_down,k) W_lat_up +
  relu(u W_s_up)^2 W_s_down`` with ``l = u W_lat_down``.  After a step ``b
  += gamma * sign(mean load - load)``.

Departures, each for a stated reason:

* **the share**: the rank holds ``mamba_heads_here`` of a mixer's heads
  with their B/C groups, ``heads_here`` query heads with the key-value
  heads they read, ``experts_here`` of the routed experts and
  ``vocab_here`` rows of the vocabulary, as one chip of a deployment that
  is tensor-parallel by heads and expert-parallel holds them.  Every layer
  runs without its all-reduce or its exchange: what the absent heads and
  the absent experts would add to a layer's output is left out
  (``tests/test_nemotron_train.py`` ties the share to the whole layer: the
  head shares of a mixer, and the expert shares of an expert layer with
  the shared expert counted once, add up to the uncut layer);
* the multi-token-prediction module (``mtp_hybrid_override_pattern``) is
  left out: how its shared-weight heads join embedding and hidden state
  is not in the published file;
* the state is not reset between packed documents;
* the loads that move the bias are the whole batch's; AdamW decays every
  matrix and no gain, convolution bias, ``A_log``, ``D`` or ``dt_bias``,
  and never touches the balancing bias.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.config import ModelConfig
from ompi_tpu.parallel.train import _leaf, _set_leaf, leaf_names

UNDECAYED = ("norm", "gate_norm", "ln1", "ln2", "final_norm", "conv_b",
             "A_log", "D", "dt_bias")
KINDS = {"M": "mamba", "*": "attn", "E": "moe"}


def _norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def recurrence(x, dt, a, b, c):
    """The state-space layer one position at a time: x (bt, s, h, p), dt
    (bt, s, h), a (h,), b, c (bt, s, g, n) -> y (bt, s, h, p)."""
    bt, s, h, p = x.shape
    g, n = b.shape[2:]
    per = lambda t: jnp.repeat(t, h // g, axis=2)        # a group's heads

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs                 # (bt, h, p) (bt, h) (bt, h, n)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        return state, jnp.einsum("zhpn,zhn->zhp", state, c_t)

    first = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((bt, h, p, n), x.dtype),
                        (first(x), first(dt), first(per(b)), first(per(c))))
    return jnp.moveaxis(y, 0, 1)


def mixer(p, x, cfg: ModelConfig):
    """``f`` of an ``M`` layer on the heads held here."""
    b, s, _ = x.shape
    nh, hd, n = cfg.n_mamba_heads_here, cfg.mamba_head_dim, cfg.ssm_state_size
    g, eps, inner = cfg.n_groups_here, cfg.rms_norm_eps, \
        cfg.n_mamba_heads_here * cfg.mamba_head_dim
    zxd = _norm(x, p["norm"], eps) @ p["in_proj"]
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:-nh], zxd[..., -nh:]
    taps = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        padded[:, k:k + s] * p["conv_w"][k] for k in range(taps)))
    xs = xbc[..., :inner].reshape(b, s, nh, hd)
    bs = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
    cs = xbc[..., inner + g * n:].reshape(b, s, g, n)
    y = recurrence(xs, jax.nn.softplus(dt + p["dt_bias"]),
                   -jnp.exp(p["A_log"]), bs, cs) + p["D"][:, None] * xs
    y = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return (y.reshape(b, s, inner) * p["gate_norm"]) @ p["out_proj"]


def attention(p, x, cfg: ModelConfig):
    """``f`` of a ``*`` layer on the query heads held here."""
    b, s, _ = x.shape
    nh, nkv = cfg.n_heads_here, cfg.n_kv_heads_here
    h = _norm(x, p["ln1"], cfg.rms_norm_eps)
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q = heads(h @ p["wq"], nh)
    k, v = (jnp.repeat(heads(h @ p[w], nkv), nh // nkv, axis=1)
            for w in ("wk", "wv"))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


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


def experts(p, x, bias, cfg: ModelConfig, shared: bool = True):
    """(``f`` of an ``E`` layer with the experts held here, the slots
    every expert of all of them received); without ``shared`` the routed
    part alone."""
    b, s, d = x.shape
    h = _norm(x, p["ln2"], cfg.rms_norm_eps).reshape(b * s, d)
    _, choice, weight = route(p, h, bias, cfg)
    first = cfg.first_expert_here
    here = weight[:, first:first + cfg.n_experts_here]       # (T, E here)
    latent = h @ p["lat_down"]
    act = jnp.square(jax.nn.relu(jnp.einsum("tl,elf->etf", latent, p["up"])))
    y = jnp.einsum("te,etl->tl", here,
                   jnp.einsum("etf,efl->etl", act, p["down"])) @ p["lat_up"]
    if shared:
        y = y + relu2(h, p["shared_up"], p["shared_down"])
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


def forward(params, tokens, cfg: ModelConfig, bias):
    """(logits (b, s, V), slots an expert an ``E`` layer (L, E))."""
    x = params["embed"][tokens]
    loads = []
    for letter, p in layers_of(params, cfg):
        if letter == "M":
            x = x + mixer(p, x, cfg)
        elif letter == "*":
            x = x + attention(p, x, cfg)
        else:
            y, load = experts(p, x, bias["layers"][len(loads)], cfg)
            x = x + y
            loads.append(load)
    return _norm(x, params["final_norm"], cfg.rms_norm_eps) \
        @ params["head"], jnp.stack(loads)


def loss_parts(params, tokens, labels, cfg: ModelConfig, bias):
    """(cross-entropy, slots an expert an ``E`` layer (L, E)); ``labels``
    may be longer than ``tokens`` (the batch's form for a model with a
    next-next-token head): the first ``s`` are read."""
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
    return {"layers": bias["layers"] + cfg.bias_update_gamma * jnp.sign(
        jnp.mean(loads, -1, keepdims=True) - loads)}


def adamw_step(params, mom, var, t: int, g, cfg: ModelConfig):
    """One update, ``t`` counted from 1: (params, mom, var)."""
    out = ({}, {}, {})
    for name, path in leaf_names(cfg):
        p, m, v, gi = (_leaf(tr, path) for tr in (params, mom, var, g))
        m = cfg.adam_b1 * m + (1 - cfg.adam_b1) * gi
        v = cfg.adam_b2 * v + (1 - cfg.adam_b2) * gi * gi
        upd = (m / (1 - cfg.adam_b1 ** t)) / (
            jnp.sqrt(v / (1 - cfg.adam_b2 ** t)) + cfg.adam_eps)
        if name.rsplit(".", 1)[-1] not in UNDECAYED:
            upd = upd + cfg.weight_decay * p
        lr = cfg.lr * min(1.0, t / cfg.warmup_steps)
        for tree, leaf in zip(out, (p - lr * upd, m, v)):
            _set_leaf(tree, path, leaf)
    return out


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
