"""A plain reference for granite-4.0-h-micro's padding-free training step
of ``parallel/train.py``: forward, loss, gradients and one AdamW update in
straightforward ``jax.numpy``, float32, matmuls at the highest precision;
**the state-space layer one position at a time** (a ``lax.scan`` over the
positions whose state is set to zero where a document starts), the
convolution as four shifted adds under the same test, attention as a dense
``softmax(QK^T + mask)V`` over (s, s) scores under the document mask, the
full logits, gradients by ``jax.grad`` with no custom rule.  No kernel, no
chunk, no block, no checkpoint, no donation: it holds (b, s, V) logits and
(h, s, s) scores, so it is for small widths (the tests);
``benchmark/harness/granitekit.py`` is the benchmark's own copy, blocked to
fit beside the program's state.

The equations are the family's own modelling code's (transformers 4.57.6,
``models/granitemoehybrid/modeling_granitemoehybrid.py``) on the published
``config.json`` of ibm-granite/granite-4.0-h-micro.  ``norm(x) = x /
sqrt(mean(x^2) + rms_norm_eps) * gain``.  ``x = embedding_multiplier *
Embed(ids)``.  A layer: ``x <- x + residual_multiplier *
Mixer(norm_1(x))``, then ``x <- x + residual_multiplier * W_down(silu(W_gate
h) * W_up h)`` with ``h = norm_2(x)`` (the modelling code's one ``W_in`` of
twice the width is ``[W_gate | W_up]``).  ``logits = norm_f(x) Embed^T /
logits_scaling`` (the head is the embedding).  The mixer by the layer's
``layer_types`` name:

* ``attention``: q, k, v, o without bias on heads of ``hidden_size /
  num_attention_heads``, every key-value head read by
  ``num_attention_heads / num_key_value_heads`` query heads, **no rotary
  embedding** (``position_embedding_type`` ``nope``), scores ``q . k *
  attention_multiplier`` (1 / 64 at a head of 64, not 1 / 8), causal
  softmax **inside the query's document**;
* ``mamba`` (``GraniteMoeHybridMambaLayer``, Mamba-2's mixer,
  arXiv:2405.21060): ``[z | xBC | dt] = h W_in``; ``xBC <- silu(conv(xBC)
  + b)``, depthwise, causal, ``mamba_d_conv`` taps, **a tap counted only
  where the position it reads lies in the same document**; ``xBC`` splits
  into x (heads x ``mamba_d_head``), B and C (``mamba_d_state`` each, one
  of each for all the heads: ``mamba_n_groups`` 1); ``dt <- softplus(dt +
  dt_bias)`` with no clamp; ``A = -exp(A_log)``; a head's state ``H_t =
  exp(dt_t A) H_(t-1) + dt_t x_t B_t^T``, **``H_(t-1)`` taken as zero where
  position t starts a document**, ``y_t = H_t C_t + D x_t``; ``y <- norm(y
  * silu(z))`` with one gain over all the inner channels; ``y W_out``.

A row's documents: ``doc_t`` is the count of positions before t that hold
the end-of-document id (``eos_token_here``), so the position behind one
starts the next document (the modelling code is handed ``seq_idx`` and
``cu_seq_lens``; here they are made from the ids).

Departures, each for a stated reason: **the share** (``heads_here``,
``mamba_heads_here``, ``vocab_here``): the held Mamba heads beside the one
B/C group whole, the held query heads with the key-value heads they read,
a slice of the vocabulary; what the absent heads would add is left out,
and the gated norm is over the held channels (on the pair of chips its sum
of squares would be all-reduced; ``mixer``'s ``tp_axis`` does that for the
share test); every row's loss counts, an end-of-document row's too (its
label is the next document's first token); AdamW decays every matrix and
the convolution's taps, and neither a gain, a bias nor a head's scalar;
the leaves start as ``train.init_model_params`` draws them (the file's
``assumed``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.config import ModelConfig
from ompi_tpu.parallel.olmoe_reference import _norm
from ompi_tpu.parallel.qwen3next_reference import adamw_step  # noqa: F401
from ompi_tpu.parallel.qwen3next_reference import layers_of as _layers_of

KINDS = {"m": "mamba_dense", "a": "attn_dense"}


def documents(ids, eos: int):
    """``doc`` (b, s): the positions before each that hold ``eos`` (a row
    is one document where ``eos`` is negative)."""
    ends = (ids == eos).astype(jnp.int32)
    return jnp.cumsum(ends, axis=1) - ends


def recurrence(x, dt, a, b, c, doc):
    """The state-space layer one position at a time: x (bt, s, h, p), dt
    (bt, s, h), a (h,), b, c (bt, s, n), doc (bt, s) -> y (bt, s, h, p);
    the state that position t reads is zero where ``doc_t`` is not
    ``doc_(t-1)``."""
    bt, _, h, p = x.shape

    def step(carry, xs):
        state, before = carry
        x_t, dt_t, b_t, c_t, doc_t = xs
        state = jnp.where((doc_t == before)[:, None, None, None], state, 0.0)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return (state, doc_t), jnp.einsum("zhpn,zn->zhp", state, c_t)

    first = (jnp.zeros((bt, h, p, b.shape[-1]), x.dtype), doc[:, 0])
    _, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c, doc)))
    return jnp.moveaxis(y, 0, 1)


def convolution(xbc, w, bias, doc):
    """``silu(bias + sum_k w_k xbc_(t - k'))`` over the taps, a tap counted
    where the position it reads exists and lies in position t's document."""
    taps, s = w.shape[0], xbc.shape[1]
    out = bias + xbc * w[taps - 1]
    for back in range(1, taps):
        read = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :s]
        same = jnp.pad(doc, ((0, 0), (back, 0)), constant_values=-1)[:, :s] \
            == doc
        out = out + jnp.where(same[..., None], read, 0.0) * w[taps - 1 - back]
    return jax.nn.silu(out)


def mixer(p, h, doc, cfg: ModelConfig, tp_axis=None):
    """The Mamba-2 mixer of the normed rows ``h`` (b, s, d), for the held
    heads; with ``tp_axis`` the gated norm's two sums cross that axis (the
    share test's pair)."""
    b, s, _ = h.shape
    nh, hd, n = cfg.n_mamba_heads_here, cfg.mamba_head_dim, cfg.ssm_state_size
    inner = nh * hd
    zxd = h @ p["in_proj"]
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:-nh], zxd[..., -nh:]
    xbc = convolution(xbc, p["conv_w"], p["conv_b"], doc)
    xs = xbc[..., :inner].reshape(b, s, nh, hd)
    y = recurrence(xs, jax.nn.softplus(dt + p["dt_bias"]),
                   -jnp.exp(p["A_log"]), xbc[..., inner:inner + n],
                   xbc[..., inner + n:], doc)
    y = (y + p["D"][:, None] * xs).reshape(b, s, inner) * jax.nn.silu(z)
    squares, count = jnp.sum(y * y, -1, keepdims=True), inner
    if tp_axis is not None:
        squares, count = (jax.lax.psum(t, tp_axis) for t in (squares, count))
    y = y * jax.lax.rsqrt(squares / count + cfg.rms_norm_eps)
    return (y * p["gate_norm"]) @ p["out_proj"]


def attention(p, h, doc, cfg: ModelConfig):
    """Grouped-query attention of the normed rows ``h`` (b, s, d) under the
    document mask, through ``W_o``."""
    b, s, _ = h.shape
    nh, nkv = cfg.n_heads_here, cfg.n_kv_heads_here
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q = heads(h @ p["wq"], nh)
    k, v = (jnp.repeat(heads(h @ p[w], nkv), nh // nkv, axis=1)
            for w in ("wk", "wv"))
    sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) * cfg.attention_multiplier
    t = jnp.arange(s)
    mask = (t[:, None] >= t[None, :]) & (doc[:, :, None] == doc[:, None, :])
    w = jax.nn.softmax(jnp.where(mask[:, None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def layer(letter: str, p, x, doc, cfg: ModelConfig):
    """One layer on the residual stream ``x`` (b, s, d)."""
    eps, by = cfg.rms_norm_eps, cfg.residual_multiplier
    if letter == "m":
        x = x + by * mixer(p, _norm(x, p["norm"], eps), doc, cfg)
    else:
        x = x + by * attention(p, _norm(x, p["ln1"], eps), doc, cfg)
    h = _norm(x, p["ln2"], eps)
    return x + by * ((jax.nn.silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"])


def forward(params, tokens, cfg: ModelConfig):
    """The logits (b, s, V) of a batch of packed rows."""
    doc = documents(tokens, cfg.eos_token_here)
    x = cfg.embedding_multiplier * params["embed"][tokens]
    for letter, p in _layers_of(params, cfg, KINDS):
        x = layer(letter, p, x, doc, cfg)
    h = _norm(x, params["final_norm"], cfg.rms_norm_eps)
    return h @ params["embed"].T / cfg.logits_scaling


def loss_parts(params, tokens, labels, cfg: ModelConfig):
    """(the mean cross-entropy over every row, the rows' (logsumexp, label's
    logit) (b, s, 2)); ``labels`` may be longer than ``tokens``: the first
    ``s`` are read."""
    logits = forward(params, tokens, cfg)
    lab = labels[:, :tokens.shape[1]]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, lab[..., None], -1)[..., 0]
    return jnp.mean(lse - picked), jnp.stack([lse, picked], axis=-1)


def grads(params, tokens, labels, cfg: ModelConfig):
    """((the loss, the rows), the gradient of the loss with respect to the
    parameters: the tied matrix's is the sum of the gather's and the
    head's)."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(
            params, tokens, labels, cfg)


def train_steps(params, batches, cfg: ModelConfig):
    """Parameters after one AdamW step a (tokens, labels) batch, and each
    step's loss."""
    mom = jax.tree.map(jnp.zeros_like, params)
    var = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, (tokens, labels) in enumerate(batches, 1):
        (total, _), g = grads(params, tokens, labels, cfg)
        params, mom, var = adamw_step(params, mom, var, t, g, cfg)
        losses.append(total)
    return params, losses
