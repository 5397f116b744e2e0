"""A plain reference for Qwen3-Next-80B-A3B's training step of
``parallel/train.py``: forward, cross-entropy, the auxiliary
load-balancing loss, gradients and one AdamW update in straightforward
``jax.numpy``, float32, matmuls at the highest precision; **the gated
delta rule one position at a time** (the recurrence as a ``lax.scan`` over
positions, no chunk, no solve), the convolution as its four shifted
products written out, attention as a full ``softmax(QK^T + mask)V`` with
the key-value heads repeated, every held expert applied to every token
and weighted by a dense mask of the router's choice.  No kernel, no sort,
no blocking, no donation: it holds (T, V) logits, (h, s, s) scores and
(E, T, f) activations, so it is for small widths (the tests);
``benchmark/harness/qwen3nextkit.py`` is the benchmark's own copy, blocked
to fit beside the program's state.

The equations are ``qwen3_next``'s (the published ``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct and the family's modelling code; Gated
DeltaNet is arXiv:2412.06464).  ``norm(x) = x / sqrt(mean(x^2) +
rms_norm_eps) * gain``; no bias anywhere.  Every layer is ``h = x +
Op(norm_in(x))``, then ``out = h + MoE(norm_post(h))``; every layer is
sparse.

* ``Op`` of a ``linear_attention`` layer (``hk`` key heads and ``hv``
  value heads): ``[q | k | v | z] = n W_qkvz`` in that order, ``[b | a] =
  n W_ba``; ``[q | k | v] <- silu(depthwise causal convolution of
  linear_conv_kernel_dim taps a channel, zeros before the sequence's
  start, no bias)``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a
  + dt_bias)`` a value head; q and k L2-normalised over a head (``x /
  sqrt(sum x^2 + 1e-6)``), q times ``1 / sqrt(key width)``, each key
  head read by ``hv / hk`` consecutive value heads; per value head, from
  a zero state S (key width x value width): ``S <- exp(g_t) S``; ``S <-
  S + k_t (beta_t (v_t - S^T k_t))^T``; ``o_t = S^T q_t``; ``y =
  norm_head(o) * gain * silu(z)`` a head; ``Op = y W_out``.
* ``Op`` of a ``full_attention`` layer: ``[q | gate]`` a head from ``n
  W_q``; k and v; ``norm`` with a gain over each head's width of q and of
  k; RoPE (``rotate_half``) on the leading ``partial_rotary_factor`` of
  the head (``rope_theta``, no scaling), the rest as it is; causal
  ``softmax(q k^T / sqrt(head_dim)) v``, each key-value head read by
  ``heads / kv heads`` query heads; ``(o * sigmoid(gate)) W_o``.
* ``MoE``: ``p = softmax(n W_r)`` over all the experts; the
  ``num_experts_per_tok`` largest; weights ``p_chosen / sum(p_chosen)``
  (``norm_topk_prob``); SwiGLU experts of ``moe_intermediate_size``; plus
  ``sigmoid(n w_g) * SwiGLU_shared(n)``.  No balancing bias.
* After the last layer one ``norm``, then an untied head.  The loss is the
  cross-entropy plus ``aux_loss_coef`` times the load-balancing loss of
  HF's ``load_balancing_loss_func`` (every layer's rows in one mean:
  ``E * sum_e (slots_e / rows / k) ...`` in OLMoE's form).

Departures, each for a stated reason:

* the family's norms are zero-centred, ``x / rms * (1 + w)`` with w from
  zero (the gated norm's gain is plain, from one): here the gain ``1 +
  w`` is its own leaf from one, the same function, gradient and update,
  because no gain is decayed;
* ``W_qkvz``'s and ``W_ba``'s columns are in the order written above; the
  published checkpoint groups them by key head, which with seeded weights
  is a permutation;
* **the share**: the rank holds ``experts_here`` of the routed experts
  and ``vocab_here`` rows of the vocabulary, as one chip of an
  expert-parallel deployment holds them; operators, routers, the shared
  expert and norms are whole.  The router scores and chooses among all
  the experts; a layer's feed-forward is the shared expert's gated part
  and the held experts' weighted parts, and what the absent experts would
  add is left out.  Embedding, logits and loss are over the slice
  (``tests/test_qwen3next_train.py`` ties the share to the whole layer:
  the shares' routed parts, the shared expert counted once, add up to the
  uncut layer);
* the multi-token-prediction module the family's description mentions is
  no key of the published configuration and is left out;
* the rule's state and the convolution are not reset and attention not
  masked between packed documents;
* AdamW decays every matrix and the taps, no gain, no ``A_log`` and no
  ``dt_bias`` (``model.UNDECAYED``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.lfm2_reference import conv_taps, swiglu
from ompi_tpu.parallel.olmoe_reference import _norm, _rope
from ompi_tpu.parallel.config import ModelConfig
from ompi_tpu.parallel.train import _leaf, _set_leaf, is_decayed, leaf_names

KINDS = {"L": "gdn_moe", "A": "attn_moe"}


def delta_rule(q, k, v, g, beta):
    """The gated delta rule one position at a time: q, k (b, s, hv, dk),
    as the rule reads them, v (b, s, hv, dv), g and beta (b, s, hv) ->
    o (b, s, hv, dv), from a zero state."""
    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., :, None] * (
            beta_t[..., None] * (v_t - read))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    zero = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[-1:], v.dtype)
    _, o = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def gdn(p, x, cfg: ModelConfig):
    """``Op`` of a ``linear_attention`` layer, without the residual add."""
    b, s, _ = x.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key, val = hk * dk, hv * dv
    n = _norm(x, p["ln1"], cfg.rms_norm_eps)
    qkvz, ba = n @ p["in_proj"], n @ p["ba_proj"]
    qkv = jax.nn.silu(conv_taps(qkvz[..., :2 * key + val], p["conv_w"]))
    z = qkvz[..., 2 * key + val:].reshape(b, s, hv, dv)
    per_value = lambda t: jnp.repeat(t.reshape(b, s, hk, dk), hv // hk, 2)
    q = per_value(qkv[..., :key])
    k = per_value(qkv[..., key:2 * key])
    v = qkv[..., 2 * key:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o = delta_rule(l2norm(q) / jnp.sqrt(jnp.float32(dk)), l2norm(k), v, g,
                   beta)
    y = _norm(o, p["gate_norm"], cfg.rms_norm_eps) * jax.nn.silu(z)
    return y.reshape(b, s, val) @ p["out_proj"]


def rope_leading(x, theta, rotary: int):
    """RoPE on the leading ``rotary`` entries of a head, the rest as it
    is."""
    return jnp.concatenate([_rope(x[..., :rotary], theta), x[..., rotary:]],
                           -1)


def attention(p, x, cfg: ModelConfig):
    """``Op`` of a ``full_attention`` layer, without the residual add."""
    b, s, _ = x.shape
    nh, nkv, eps = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.rms_norm_eps
    hd = cfg.head_width
    rotary = int(hd * cfg.partial_rotary_factor)
    h = _norm(x, p["ln1"], eps)
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    qg = heads(h @ p["wq"], nh)                     # a head: [q | gate]
    q, gate = qg[..., :hd], qg[..., hd:]
    q = rope_leading(_norm(q, p["q_norm"], eps), cfg.rope_theta, rotary)
    k = rope_leading(_norm(heads(h @ p["wk"], nkv), p["k_norm"], eps),
                     cfg.rope_theta, rotary)
    k, v = (jnp.repeat(t, nh // nkv, axis=1)
            for t in (k, heads(h @ p["wv"], nkv)))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v) * jax.nn.sigmoid(gate)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def route(p, h, cfg: ModelConfig):
    """(probabilities (T, E), the dense one-hot of the choice (T, k, E),
    the weight every expert has on every token (T, E))."""
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    _, top_e = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    choice = jax.nn.one_hot(top_e, cfg.num_experts)
    chosen = jnp.einsum("tke,te->tk", choice, probs)
    if cfg.norm_topk_prob:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    return probs, choice, jnp.einsum("tk,tke->te", chosen, choice)


def experts(p, x, cfg: ModelConfig, shared: bool = True):
    """(``MoE`` with the experts held here on the residual stream ``x``
    (b, s, d), without the residual add: the gated shared expert, unless
    ``shared`` is false, plus the held experts' weighted parts; the slots
    every expert of all of them received; the probabilities' sum an
    expert)."""
    b, s, d = x.shape
    h = _norm(x, p["ln2"], cfg.rms_norm_eps).reshape(b * s, d)
    probs, choice, weight = route(p, h, cfg)
    first = cfg.first_expert_here
    here = weight[:, first:first + cfg.n_experts_here]       # (T, E here)
    act = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["gate"])) \
        * jnp.einsum("td,edf->etf", h, p["up"])
    y = jnp.einsum("te,etd->td", here,
                   jnp.einsum("etf,efd->etd", act, p["down"]))
    if shared:
        y = y + jax.nn.sigmoid(h @ p["shared_w_g"]) * swiglu(
            h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y.reshape(b, s, d), jnp.sum(choice, axis=(0, 1)), \
        jnp.sum(probs, axis=0)


def layers_of(params, cfg: ModelConfig, kinds: dict = KINDS):
    """(letter, the layer's leaves) of the held layers in their order,
    from the tree's runs of like layers (``cfg.segments``); ``kinds``
    names a letter's group in the tree (another ``layer_types`` model's
    reference brings its own)."""
    for unit, n, first in cfg.segments:
        group = params["layers"][f"l{first}"]
        for i in range(n):
            for letter in unit:
                yield letter, jax.tree.map(lambda a: a[i],
                                           group[kinds[letter]])


def forward(params, tokens, cfg: ModelConfig):
    """(logits (b, s, V), slots an expert a layer (L, E), probabilities'
    sum an expert a layer (L, E))."""
    x = params["embed"][tokens]
    loads, prob_sums = [], []
    for letter, p in layers_of(params, cfg):
        x = x + (gdn if letter == "L" else attention)(p, x, cfg)
        y, load, prob_sum = experts(p, x, cfg)
        x = x + y
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


def adamw_step(params, mom, var, t: int, g, cfg: ModelConfig):
    """One AdamW update of every leaf, ``t`` counted from 1: (params, mom,
    var); decoupled weight decay on every leaf ``train.is_decayed`` names
    (the other references' lists of undecayed leaves are their models')."""
    lr = cfg.lr * min(1.0, t / cfg.warmup_steps)
    out = ({}, {}, {})
    for name, path in leaf_names(cfg):
        p, m, v, gi = (_leaf(tree, path) for tree in (params, mom, var, g))
        m = cfg.adam_b1 * m + (1.0 - cfg.adam_b1) * gi
        v = cfg.adam_b2 * v + (1.0 - cfg.adam_b2) * gi * gi
        step = (m / (1.0 - cfg.adam_b1 ** t)) / (
            jnp.sqrt(v / (1.0 - cfg.adam_b2 ** t)) + cfg.adam_eps)
        if is_decayed(name):
            step = step + cfg.weight_decay * p
        for tree, leaf in zip(out, (p - lr * step, m, v)):
            _set_leaf(tree, path, leaf)
    return out


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
