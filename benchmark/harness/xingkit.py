"""What the ``train_step_kit`` call kind reads for Xing4.0-29B-A4B: the
benchmark's own copy of the plain reference of its training step on one
chip's share of an eight-chip deployment, written independently of the
program (``ompi_tpu.parallel``), what a check compares and in which units,
and the functions that count a step's model FLOP and the bytes its residual
path has to move.  The batch (Zipf ids), the probe and sample rules and the
blocked experts and head are ``harness/olmoekit``'s: a kit states a model,
not a second harness.

The published ``config.json`` (``model_type`` ``xing4_0``) uses DeepSeek-V3's
keys for latent attention, the router, the shared expert, the dense SwiGLU
and YaRN (arXiv:2412.19437 sections 2.1-2.2; ``modeling_deepseek_v3.py``),
and five of its own for the residual path, **manifold-constrained
hyper-connections** (Xie et al., *mHC*, arXiv:2512.24880 section 4, on Zhu
et al., *Hyper-Connections*, arXiv:2409.19606).  With n = ``hc_mult`` and a
token's stream X in R^(n x d):

* in: ``X_0 = [e; ..; e]``, ``e = Embed(id)``;
* a sublayer F (it holds its own pre-norm and adds no residual), with its
  own ``phi`` (n d, n^2 + 2 n; columns pre | post | res), gates ``alpha``
  (3,) and offsets ``b``: ``x' = vec(X) / sqrt(mean(vec(X)^2) +
  rms_norm_eps)``; ``m = x' phi``; ``Hpre = sigmoid(alpha_pre m[:n] +
  b_pre)``; ``Hpost = 2 sigmoid(alpha_post m[n:2n] + b_post)``; ``Hres =
  Sinkhorn(clip(alpha_res mat(m[2n:]) + b_res, clamp_min, clamp_max))``, row
  major: ``M = exp(.)``, then ``hc_sinkhorn_iters`` times ``M <- M /
  (colsum(M) + hc_eps)``, ``M <- M / (rowsum(M) + hc_eps)``; ``u = Hpre X``;
  ``y = F(u)``; **``X <- Hres X + Hpost^T y``**;
* a layer: latent attention so, then the feed-forward so: a dense SwiGLU in
  the held leading layers; behind them ``s = sigmoid(h W_r)`` over all the
  experts, the top k of ``s + bias`` (the bias: the choice only), weights
  ``routed_scaling_factor s_chosen / sum(s_chosen)``, the shared expert on
  every token; after a step ``bias += gamma sign(mean load - load)``;
* out: ``h = sum_i X_L[i]``, ``logits = RMSNorm_f(h) W_head``;
* **latent attention** as DeepSeek-V3's (``c_q = norm(x W_qa)``, ``q = c_q
  W_qb`` in heads of ``[nope | rope]``; ``[c_kv | k_rope] = x W_kva``,
  ``[k_nope | v] = norm(c_kv) W_kvb``; RoPE on interleaved pairs of the rope
  parts, the one rotary key shared by every head), under **YaRN**:
  ``inv_freq = inter (1 - mask) + extra mask`` over the pairs of the rotary
  part, ``extra = theta^(-2i / rot)``, ``inter = extra / factor``, ``mask =
  1 - clip((i - low) / (high - low), 0, 1)``, ``low`` / ``high`` the floor /
  ceiling of ``rot ln(original / (beta 2 pi)) / (2 ln theta)`` at
  ``beta_fast`` / ``beta_slow`` clamped to 0 .. rot - 1; cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``; scores times
  ``(nope + rot)^(-1/2) mscale(factor, mscale_all_dim)^2``, ``mscale(s, m) =
  0.1 m ln s + 1``.

Everything float32, every matmul at the highest precision, no kernel, no
sort.  Departures:

* **the share** (``heads_here``, ``experts_here``, ``expert_share``,
  ``vocab_here``, ``dense_here``): the held heads' ``wq_b``, ``wkv_b`` and
  ``wo`` with the two latents whole; every held expert on every token under a
  dense mask of the router's choice among **all** the experts; what the
  absent heads and experts would add to a sublayer's ``y`` is left out, and
  that partial ``y`` is what ``Hpost^T`` writes into the stream; the
  embedding, the logits and the loss are over the slice of the vocabulary;
* the next-n module is not held (``mtp_here`` 0): how it joins a stream of n
  is in neither the file nor the paper;
* what the file does not settle is the configuration file's ``assumed``;
* at the published widths the (T, V) logits, the (s, s) scores of all heads
  at once, the (E, T, f) activations of all held experts at once and five
  layers' activations do not fit beside the program's parameters, so the head
  runs by blocks of rows, attention one head at a time, the experts one after
  the other, and every layer is recomputed in the backward pass (``lax.map``
  / ``lax.scan`` / ``jax.checkpoint``).  The arithmetic of every element is
  the same; only what is held at once differs.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from harness import olmoekit as ok
from harness.olmoekit import (PROBE_UNIT, REGRET_UNIT, RMS_UNIT,  # noqa: F401
                              ROW_BLOCKS, SAMPLE_UNIT, probe_positions,
                              rank_order, sample_rows, tokens_of, zipf_cdf)

ATTENTION = ("ln1", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
             "wkv_b", "wo")
PATH = ("phi", "alpha", "b")        # the residual path's leaves a sublayer
HC1 = tuple("hc1_" + k for k in PATH)
HC2 = tuple("hc2_" + k for k in PATH)
DENSE = ATTENTION + HC1 + ("ln2", "gate", "up", "down") + HC2
SPARSE = ATTENTION + HC1 + ("ln2", "router", "gate", "up", "down",
                            "shared_gate", "shared_up", "shared_down") + HC2
UNDECAYED = ("ln1", "ln2", "q_a_norm", "kv_a_norm", "final_norm",
             "hc1_alpha", "hc1_b", "hc2_alpha", "hc2_b")
# variants of the reference that are deliberately wrong: plain RoPE and 1 /
# sqrt(nope + rot) in place of YaRN's frequencies and scale; 1 and 5 sweeps in
# place of ``hc_sinkhorn_iters``; the mixing map replaced by the identity;
# Hpost without its factor 2; the router's two (``joyaikit``'s)
WRONG = ("plain_rope", "sweeps_1", "sweeps_5", "res_identity",
         "post_unscaled", "softmax", "bias_in_weights")
OUTPUTS = ("losses", "load_share", "local_share", "row_means",
           "route_regret", "bias", "grad_log_rms", "grad_probe")
PRECISION = ("router_logits", "router_scores", "router_weights", "head_rows",
             "hc_pre", "hc_post", "hc_res", "hc_defect")
# the variants of ``precision_want`` that are controls (tools/kit_check.py):
# the router and the head in bfloat16; the maps' product in bfloat16; the
# path's wrong parts, each from the step's own stream; the router's wrong
# parts; and, run again as a whole model, plain RoPE (``WHOLE_CONTROLS``)
WHOLE_CONTROLS = ("plain_rope",)
PART_CONTROLS = ("bf16", "maps_bf16", "sweeps_1", "sweeps_5", "res_identity",
                 "post_unscaled", "bias_in_weights", "softmax") \
    + WHOLE_CONTROLS
BIAS_UNIT = 1.0         # a balancing bias in units of gamma (joyaikit's)
# a leaf whose largest probed entry is over this many RMS is probed in units
# of that entry (``nemotronkit.HOT_ENTRY``)
HOT_ENTRY = 32.0
# a leaf of fewer entries than this (the path's gates ``alpha``, 3 a sublayer,
# and offsets ``b``, 24) is compared **entry by entry alone** (64 probes read
# all or most of it), not by its gradient's RMS as well.  Each entry is a sum
# over every token of terms that cancel, so what bfloat16 matmul inputs in
# front of it move is a share of what cancelled, not of what is left, and it
# is unbounded as a share of the RMS when the leaf is few numbers and the
# largest passes near zero (``ourokit.checked`` says the same of a looped
# model's one-number gate bias).  ``dense.hc1_alpha`` **is** one number: the
# first sublayer's stream is n copies of the embedding, so ``Hres X`` is X
# whatever ``Hres`` is (its rows sum to one) and attention's pre-norm takes
# ``Hpre``'s scale out, and only ``alpha_post``'s entry is not ~0 (3.6e-3
# beside 5e-9 and 4e-11, my chip run, PR 73, call H).  Read on the chip while
# the RMS was still compared (my chip runs, PR 73, 30 checks of 15 runs):
# these eight leaves lay up to 0.78 units from the reference in 29 checks and
# **5.8 in one** (``dense.hc1_alpha``, a traced run's second check: its RMS
# 30% off with every entry's share of it exact), where every larger leaf lay
# within 0.17.  The same cotangents reach ``hc*_phi`` against the rows they
# belong to, 344,064 sums that do not cancel alike, and those are compared
# both ways; the small leaves' own gradients are held to the reference at
# small widths (``tests/test_xing_train.py``)
SMALL_LEAF = 128
#: the batch, biases and routing the last float32 ``reference_step`` ran on,
#: for ``precision_want``'s whole-model controls (``granitekit._STEP``: no
#: parameter is kept here)
_STEP: dict = {}


def load_config(path: str) -> dict:
    """The configuration file as the reference reads it (``olmoekit``'s: the
    published keys, the share and the ``train`` group, flat)."""
    return ok.load_config(path)


def n_dense(cfg: dict) -> int:
    return cfg["dense_here"]


def n_sparse(cfg: dict) -> int:
    return cfg["layers_here"] - cfg["dense_here"]


def held(cfg: dict) -> dict:
    """The heads and experts this rank holds."""
    return {"heads": cfg.get("heads_here") or cfg["num_attention_heads"],
            "experts": cfg.get("experts_here") or cfg["n_routed_experts"],
            "first_expert": cfg.get("expert_share", 0)
            * cfg.get("experts_here", 0)}


def leaves(cfg: dict) -> tuple:
    """Every trained leaf's name, in the order the program reports them."""
    return ("embed",) + tuple("dense." + k for k in DENSE) * bool(
        n_dense(cfg)) + SPARSE + ("final_norm", "head")


def checked(cfg: dict) -> tuple:
    """The leaves whose gradients a check compares: of a dense and of a
    sparse layer one ``phi``, one ``alpha`` and one ``b`` of each of the
    path's two sets, latent attention's ``wq_b`` (YaRN turns its rotary
    columns' products) and ``wo``, the feed-forwards' ``down``; of the sparse
    layers also ``wq_a``, ``wkv_b``, the router, the held experts' ``gate``
    and the shared expert's ``up``; the final norm and the head.  Between
    them their gradients cross every sublayer's backward pass and the
    path's around each."""
    dense = tuple("dense." + k for k in (
        "wq_b", "wo", "hc1_phi", "hc1_alpha", "hc1_b", "down", "hc2_phi",
        "hc2_alpha", "hc2_b")) * bool(n_dense(cfg))
    return dense + ("wq_a", "wq_b", "wkv_b", "wo", "hc1_phi", "hc1_alpha",
                    "hc1_b", "router", "gate", "down", "shared_up", "hc2_phi",
                    "hc2_alpha", "hc2_b", "final_norm", "head")


def probed(cfg: dict) -> tuple:
    """The checked leaves whose gradient is also compared entry by entry:
    all of them."""
    return checked(cfg)


def _place(name: str) -> tuple:
    """(the group of the parameter tree leaf ``name`` lies in, or None for
    the tree's top; its key there)."""
    group, _, leaf = name.rpartition(".")
    if group:
        return group, leaf
    return ("layers", name) if name in SPARSE else (None, name)


def leaf_of(params: dict, name: str):
    group, leaf = _place(name)
    return params[leaf] if group is None else params[group][leaf]


def put_leaf(tree: dict, name: str, a) -> None:
    group, leaf = _place(name)
    (tree if group is None else tree.setdefault(group, {}))[leaf] = a


def tree_of(by_name: dict) -> dict:
    """The parameter tree from {leaf name: array}."""
    tree: dict = {}
    for name, a in by_name.items():
        put_leaf(tree, name, a)
    return tree


def layer_sizes(cfg: dict) -> dict:
    """Elements of one layer's leaves, by kind of layer."""
    d, nh = cfg["hidden_size"], held(cfg)["heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rot, hv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, e, ff = cfg["moe_intermediate_size"], held(cfg)["experts"], \
        cfg["intermediate_size"]
    fs, n = f * cfg["n_shared_experts"], cfg["hc_mult"]
    maps = n * n + 2 * n
    att = {"ln1": d, "wq_a": d * qr, "q_a_norm": qr,
           "wq_b": qr * nh * (nope + rot), "wkv_a": d * (kr + rot),
           "kv_a_norm": kr, "wkv_b": kr * nh * (nope + hv),
           "wo": nh * hv * d}
    path = lambda at: {f"{at}_phi": n * d * maps, f"{at}_alpha": 3,
                       f"{at}_b": maps}
    return {"dense": {**att, **path("hc1"), "ln2": d, "gate": d * ff,
                      "up": d * ff, "down": ff * d, **path("hc2")},
            "sparse": {**att, **path("hc1"), "ln2": d,
                       "router": d * cfg["n_routed_experts"],
                       "gate": e * d * f, "up": e * d * f, "down": e * f * d,
                       "shared_gate": d * fs, "shared_up": d * fs,
                       "shared_down": fs * d, **path("hc2")}}


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this rank holds."""
    per, v = layer_sizes(cfg), cfg["vocab_here"]
    return {"embed": v * cfg["hidden_size"],
            **{"dense." + k: n_dense(cfg) * s
               for k, s in per["dense"].items() if n_dense(cfg)},
            **{k: n_sparse(cfg) * s for k, s in per["sparse"].items()},
            "final_norm": cfg["hidden_size"], "head": cfg["hidden_size"] * v}


# -- the reference -------------------------------------------------------------
def mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict, plain: bool = False):
    """(the rotary part's inverse frequencies, float32, what cos and sin are
    multiplied by, the scores' scale): YaRN's under the file's
    ``rope_scaling``, plain RoPE's and 1 / sqrt(nope + rot) where the file
    has none or ``plain`` asks so."""
    import jax.numpy as jnp

    rot, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    yarn = None if plain else cfg.get("rope_scaling")
    extra = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    scale = (cfg["qk_nope_head_dim"] + rot) ** -0.5
    if not yarn:
        return extra, 1.0, scale
    factor = yarn["factor"]
    turns = lambda beta: rot * math.log(
        yarn["original_max_position_embeddings"] / (beta * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(turns(yarn["beta_fast"])), 0)
    high = min(math.ceil(turns(yarn["beta_slow"])), rot - 1)
    ramp = (jnp.arange(rot // 2, dtype=jnp.float32) - low) / (
        high - low if high != low else 0.001)
    mask = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    all_dim = yarn.get("mscale_all_dim") or 0
    return ((extra / factor) * (1.0 - mask) + extra * mask,
            mscale(factor, yarn.get("mscale") or 1)
            / mscale(factor, all_dim or 1),
            scale * (mscale(factor, all_dim) ** 2 if all_dim else 1.0))


def _rope(x, inv, by):
    """Rotary embedding of (..., s, rot) on interleaved pairs."""
    import jax.numpy as jnp

    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (by * jnp.cos(ang)).astype(x.dtype), \
        (by * jnp.sin(ang)).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def _attention(q, k, v, scale: float):
    """Full causal softmax attention, one (batch, head) at a time; q and k
    of one width, v of another; scores times ``scale``."""
    import jax
    import jax.numpy as jnp

    b, h, s, _ = q.shape
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(qkv):
        qi, ki, vi = qkv
        sc = jnp.where(mask, (qi @ ki.T) * jnp.asarray(scale, qi.dtype),
                       -jnp.inf)
        return jax.nn.softmax(sc, axis=-1) @ vi

    flat = lambda t: t.reshape(b * h, s, t.shape[-1])
    return jax.lax.map(one, (flat(q), flat(k), flat(v))).reshape(
        b, h, s, v.shape[-1])


def _latent_attention(p, u, cfg, wrong):
    """The attention sublayer's ``y`` from its input ``u``: no residual."""
    import jax.numpy as jnp

    b, s, _ = u.shape
    nh, eps, rank = held(cfg)["heads"], cfg["rms_norm_eps"], \
        cfg["kv_lora_rank"]
    nope = cfg["qk_nope_head_dim"]
    inv, by, scale = yarn_inv_freq(cfg, plain=wrong == "plain_rope")
    h = ok._norm(u, p["ln1"], eps)
    heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
    q = heads(ok._norm(h @ p["wq_a"], p["q_a_norm"], eps) @ p["wq_b"])
    kv = h @ p["wkv_a"]
    kvb = heads(ok._norm(kv[..., :rank], p["kv_a_norm"], eps) @ p["wkv_b"])
    k_rope = _rope(kv[:, None, :, rank:], inv, by)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, by)], -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_rope, (b, nh, s, k_rope.shape[-1]))], -1)
    o = _attention(q, k, kvb[..., nope:], scale)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _sparse_mlp(p, u, bias, cfg, wrong, routed):
    """(a sparse layer's feed-forward ``y`` from its input ``u``, the slots
    every expert received, the routing's regret)."""
    import jax
    import jax.numpy as jnp

    b, s, d = u.shape
    e, k_top = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    h = ok._norm(u, p["ln2"], cfg["rms_norm_eps"]).reshape(b * s, d)
    logits = h @ p["router"]
    scores = jax.nn.softmax(logits, -1) if wrong == "softmax" \
        else jax.nn.sigmoid(logits)
    biased = scores + bias
    own, top_e = jax.lax.top_k(biased, k_top)
    regret = jnp.zeros((), u.dtype)
    if routed is not None:
        under = jnp.take_along_axis(biased, routed, axis=-1)
        regret = jnp.max((own[:, -1] - jnp.min(under, axis=-1))
                         / (REGRET_UNIT * own[:, -1]))
        top_e = routed
    chosen = jnp.take_along_axis(
        biased if wrong == "bias_in_weights" else scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    choice = jax.nn.one_hot(top_e, e, dtype=u.dtype)            # (T, k, E)
    weight = jnp.einsum("tk,tke->te",
                        chosen * cfg["routed_scaling_factor"], choice)
    here = held(cfg)
    first = here["first_expert"]
    y = ok._experts(h, weight[:, first:first + here["experts"]], p["gate"],
                    p["up"], p["down"])
    y = y + _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y.reshape(b, s, d), jnp.sum(choice, axis=(0, 1)), regret


def _maps(p, x, cfg, at, wrong):
    """``(Hpre (b, s, n), Hpost (b, s, n), Hres (b, s, n, n))`` of the
    stream ``x`` (b, s, n, d) from ``<at>_phi``, ``<at>_alpha``, ``<at>_b``."""
    import jax
    import jax.numpy as jnp

    b, s, n, d = x.shape
    flat = x.reshape(b, s, n * d)
    normed = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + jnp.asarray(cfg["rms_norm_eps"], x.dtype))
    m = normed @ p[f"{at}_phi"]
    alpha, off = p[f"{at}_alpha"], p[f"{at}_b"]
    pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + off[:n])
    post = jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + off[n:2 * n])
    if wrong != "post_unscaled":
        post = 2.0 * post
    if wrong == "res_identity":
        return pre, post, jnp.broadcast_to(jnp.eye(n, dtype=x.dtype),
                                           (b, s, n, n))
    raw = (alpha[2] * m[..., 2 * n:] + off[2 * n:]).reshape(b, s, n, n)
    res = jnp.exp(jnp.clip(raw, cfg["mhc_h_res_clamp_min"],
                           cfg["mhc_h_res_clamp_max"]))
    sweeps = {"sweeps_1": 1, "sweeps_5": 5}.get(wrong,
                                                cfg["hc_sinkhorn_iters"])
    eps = jnp.asarray(cfg["hc_eps"], x.dtype)
    for _ in range(sweeps):
        res = res / (jnp.sum(res, axis=-2, keepdims=True) + eps)
        res = res / (jnp.sum(res, axis=-1, keepdims=True) + eps)
    return pre, post, res


def _sublayer(f, p, x, cfg, at, wrong):
    """(the stream behind the sublayer ``f``: ``u -> (y, *extra)``,
    ``extra``)."""
    import jax.numpy as jnp

    pre, post, res = _maps(p, x, cfg, at, wrong)
    y, *extra = f(jnp.einsum("bsn,bsnd->bsd", pre, x))
    return jnp.einsum("bsij,bsjd->bsid", res, x) \
        + post[..., None] * y[:, :, None, :], extra


def loss_parts(params, tokens, labels, cfg: dict, bias: dict,
               wrong: str | None = None, routed=None):
    """(loss, {losses, loads, rows, regret}) of one batch, in the
    parameters' own type throughout (float32; bfloat16 for the control).
    ``labels`` may be one longer than ``tokens`` (the batch's form): the
    first ``s`` are read.  ``bias`` {layers (L, E)}.  With ``routed`` (L, T,
    k), the experts a program chose, the top k is not taken here but given,
    and ``regret`` says how far that choice is from this model's own under
    its own scores plus bias (``olmoekit.loss_parts``).  ``wrong`` names a
    deliberately wrong variant (``WRONG``), for the tests and controls that
    a comparison catches it."""
    import jax
    import jax.numpy as jnp

    b, s = tokens.shape
    attend = lambda p: lambda u: (_latent_attention(p, u, cfg, wrong),)

    @jax.checkpoint
    def dense(p, x):
        x, _ = _sublayer(attend(p), p, x, cfg, "hc1", wrong)
        x, _ = _sublayer(lambda u: (_swiglu(
            ok._norm(u, p["ln2"], cfg["rms_norm_eps"]), p["gate"], p["up"],
            p["down"]),), p, x, cfg, "hc2", wrong)
        return x

    @jax.checkpoint
    def sparse(p, x, row, chosen):
        x, _ = _sublayer(attend(p), p, x, cfg, "hc1", wrong)
        x, (load, regret) = _sublayer(lambda u: _sparse_mlp(
            p, u, row, cfg, wrong, chosen), p, x, cfg, "hc2", wrong)
        return x, load, regret

    e = params["embed"][tokens]
    x = jnp.stack([e] * cfg["hc_mult"], axis=2)
    for i in range(n_dense(cfg)):
        x = dense({k: v[i] for k, v in params["dense"].items()}, x)
    loads, regrets = [], []
    for i in range(n_sparse(cfg)):
        x, load, regret = sparse(
            {k: v[i] for k, v in params["layers"].items()}, x,
            bias["layers"][i], None if routed is None else routed[i])
        loads.append(load)
        regrets.append(regret)
    h = ok._norm(jnp.sum(x, axis=2), params["final_norm"],
                 cfg["rms_norm_eps"]).reshape(b * s, -1)
    rows = ok._head(h, params["head"], labels[:, :s].reshape(b * s),
                    cfg.get("loss_block_rows", 1024))
    ce = jnp.mean(rows[:, 0] - rows[:, 1])
    return ce, {"losses": jnp.stack([ce, ce]), "loads": jnp.stack(loads),
                "rows": rows, "regret": jnp.stack(regrets)}


def bias_step(bias: dict, loads, cfg: dict):
    """The biases (L, E) after a step whose experts received ``loads``."""
    import jax.numpy as jnp

    return bias["layers"] + cfg["bias_update_gamma"] * jnp.sign(
        jnp.mean(loads, -1, keepdims=True) - loads)


def _items(cfg: dict) -> tuple:
    """``cfg`` as a hashable key: its scalars, and the YaRN group's."""
    scalars = lambda d: tuple(sorted(
        (k, v) for k, v in d.items()
        if isinstance(v, (int, float, bool, str))))
    return scalars(cfg) + (("rope_scaling", scalars(
        cfg.get("rope_scaling") or {})),)


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, wrt: tuple, wrong):
    import jax

    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])

    def run(params, tokens, labels, bias, routed):
        diff = {n: leaf_of(params, n) for n in wrt}

        def loss(diff):
            merged = jax.tree.map(lambda a: a, params)      # a copy's dicts
            for n, a in diff.items():
                put_leaf(merged, n, a)
            return loss_parts(merged, tokens, labels, cfg, bias, wrong,
                              routed)

        with jax.default_matmul_precision("highest"):
            (_, aux), g = jax.value_and_grad(loss, has_aux=True)(diff)
        return aux, g

    return jax.jit(run)


def reference_step(params, tokens, labels, cfg: dict, bias: dict,
                   wrt: tuple, wrong: str | None = None,
                   routed=None) -> dict:
    """One step's statistics from the reference, in the form ``step_stats``
    puts a program's in: ``losses``, ``loads``, ``rows``, ``regret``,
    ``bias`` (after the update), and for each leaf of ``wrt`` its gradient's
    ``grad_sq`` and ``grad_probe``; ``grads`` holds the whole gradients of
    ``wrt``.  Parameters given in bfloat16 make the **control**: the same
    model computed throughout in the nearest precision below the one the
    configuration states."""
    import jax.numpy as jnp

    if wrong is None and leaf_of(params, "embed").dtype == jnp.float32:
        # what the whole-model controls run again (``precision_want``): the
        # float32 reference's own batch, never a control's
        _STEP.update(tokens=tokens, labels=labels, wrt=wrt, bias=bias,
                     routed=routed)
    aux, g = _grad_program(_items(cfg), tuple(wrt), wrong)(
        params, tokens, labels, bias, routed)
    aux, g = ({k: v.astype(jnp.float32) for k, v in t.items()}
              for t in (aux, g))
    flat = {n: g[n].reshape(-1) for n in wrt}
    f32 = {k: jnp.asarray(v).astype(jnp.float32) for k, v in bias.items()}
    return {**aux, "grads": g, "bias": bias_step(f32, aux["loads"], cfg),
            "grad_sq": {n: jnp.sum(f * f) for n, f in flat.items()},
            "grad_probe": {n: f[probe_positions(n, f.shape[0])]
                           for n, f in flat.items()}}


def step_stats(aux: dict, bias_after: dict, cfg: dict) -> dict:
    """A program step's ``aux`` (``parallel/train.py``: raw statistics) and
    the biases its state holds after it, in the reference's form.  A step
    routes every token to its own top k, so its regret is 0 by definition."""
    out = {k: np.asarray(aux[k]) for k in ("loads", "rows")}
    out["losses"] = np.asarray(aux["losses"])[:2]       # total, ce
    out["regret"] = np.zeros(out["loads"].shape[:1], np.float32)
    out["bias"] = np.asarray(bias_after["layers"])
    for k in ("grad_sq", "grad_probe"):
        out[k] = dict(zip(leaves(cfg), np.asarray(aux[k])))
    return out


def compared(stats: dict, cfg: dict, wrt: tuple) -> dict:
    """What a check compares of one step's statistics, each in its unit
    (``olmoekit``'s constants): the loss and the cross-entropy as they are;
    the share of a layer's slots every one of all the experts received, and
    the held experts' together; the head's logsumexp and label logit averaged
    over quarters of the rows; the routing's regret; the biases after the
    update in units of gamma; and for the leaves of ``wrt`` the gradient's
    RMS as log10 over ``RMS_UNIT`` (0 on both sides for a leaf of fewer than
    ``SMALL_LEAF`` entries, which the probes read whole: the constant says
    why) and its
    probed entries in units of ``PROBE_UNIT`` RMS, or of ``PROBE_UNIT /
    HOT_ENTRY`` times the largest of them where that is more
    (``nemotronkit.compared`` says why)."""
    rows = np.asarray(stats["rows"], np.float32)
    sizes = leaf_sizes(cfg)
    # a leaf no token reached (a layer's held experts with no slot) reads
    # the floor on both sides
    rms = np.maximum(1e-30, np.sqrt(
        [float(stats["grad_sq"][n]) / sizes[n] for n in wrt]))
    probe = np.stack([np.asarray(stats["grad_probe"][n]) for n in wrt])
    scale = PROBE_UNIT * np.maximum(rms, np.abs(probe).max(axis=1)
                                    / HOT_ENTRY)
    share = np.asarray(stats["loads"]) / (
        rows.shape[0] * cfg["num_experts_per_tok"])
    here = held(cfg)
    first = here["first_expert"]
    return {k: np.asarray(v, np.float32) for k, v in {
        "losses": stats["losses"], "load_share": share,
        "local_share": share[:, first:first + here["experts"]].sum(-1),
        "row_means": rows.reshape(ROW_BLOCKS, -1, 2).mean(axis=1),
        "route_regret": stats["regret"],
        "bias": np.asarray(stats["bias"]) / (
            BIAS_UNIT * cfg["bias_update_gamma"]),
        "grad_log_rms": np.log10(np.where(np.asarray(
            [sizes[n] for n in wrt]) < SMALL_LEAF, 1.0, rms)) / RMS_UNIT,
        "grad_probe": probe / scale[:, None]}.items()}


# -- the float32 parts of a step, read from the step alone -----------------------
def path_ends(sample: dict) -> dict:
    """The path's rows of the **first and the last held sublayer** from a
    step's ``sample``: the first layer's attention sublayer's (``hc1_*``,
    row 0) and the last layer's feed-forward's (``hc2_*``, the last row),
    stacked; ``in`` (2, R, n d), ``pre`` and ``post`` (2, R, n), ``res`` (2,
    R, n, n)."""
    pick = lambda part: np.stack([np.asarray(sample[f"hc1_{part}"])[0],
                                  np.asarray(sample[f"hc2_{part}"])[-1]])
    out = {part: pick(part) for part in ("in", "pre", "post", "res")}
    out["in"] = out["in"].reshape(out["in"].shape[:2] + (-1,))
    return out


def defects(res) -> np.ndarray:
    """``res`` (.., R, n, n): per leading entry the largest row-sum and the
    largest column-sum defect from one over the rows, (.., 2)."""
    res = np.asarray(res, np.float64)
    return np.stack([np.abs(res.sum(-1) - 1.0).max(axis=(-1, -2)),
                     np.abs(res.sum(-2) - 1.0).max(axis=(-1, -2))], -1)


def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made at ``sample_rows``, in units of
    ``SAMPLE_UNIT``: the routers' logits, their sigmoid scores and the chosen
    weights (times 100, so that a step of 1e-4 is one unit), the head's
    logsumexp and label logit, and of the first and the last held sublayer
    the path's three maps and the mixing map's largest row-sum and column-sum
    defect (``path_ends``, ``defects``)."""
    s = aux["sample"]
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    ends = path_ends(s)
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "router_logits": s["router_logits"],
        "router_scores": np.asarray(s["router_scores"]) * 100.0,
        "router_weights": np.asarray(s["router_weights"]) * 100.0,
        "head_rows": np.asarray(aux["rows"])[at],
        "hc_pre": ends["pre"], "hc_post": ends["post"],
        "hc_res": ends["res"], "hc_defect": defects(ends["res"])}.items()}


def path_rows(x, phi, alpha, off, cfg: dict, variant=None):
    """``(Hpre (R, n), Hpost (R, n), Hres (R, n, n))`` in float64 from the
    stream's rows ``x`` (R, n d) a sublayer's path read and its three leaves;
    ``variant`` a control: ``maps_bf16`` (the product ``x' phi`` with inputs
    and result rounded to bfloat16), ``sweeps_1`` / ``sweeps_5``,
    ``res_identity``, ``post_unscaled``."""
    n = cfg["hc_mult"]
    x, phi, alpha, off = (np.asarray(a, np.float64)
                          for a in (x, phi, alpha, off))
    normed = x / np.sqrt(np.mean(x * x, -1, keepdims=True)
                         + cfg["rms_norm_eps"])
    low = ok._bf16 if variant == "maps_bf16" else (lambda a: a)
    m = low(low(normed) @ low(phi))
    sigmoid = lambda a: 1.0 / (1.0 + np.exp(-a))
    pre = sigmoid(alpha[0] * m[:, :n] + off[:n])
    post = sigmoid(alpha[1] * m[:, n:2 * n] + off[n:2 * n]) * (
        1.0 if variant == "post_unscaled" else 2.0)
    if variant == "res_identity":
        return pre, post, np.broadcast_to(np.eye(n), (x.shape[0], n, n))
    raw = (alpha[2] * m[:, 2 * n:] + off[2 * n:]).reshape(-1, n, n)
    res = np.exp(np.clip(raw, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    for _ in range({"sweeps_1": 1, "sweeps_5": 5}.get(
            variant, cfg["hc_sinkhorn_iters"])):
        res = res / (res.sum(-2, keepdims=True) + cfg["hc_eps"])
        res = res / (res.sum(-1, keepdims=True) + cfg["hc_eps"])
    return pre, post, res


def precision_want(aux: dict, by_name: dict, bias_before, head, labels,
                   cfg: dict, variant: str | None = None) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own inputs
    to each part** at the precision the configuration states: the routers'
    logits from the rows each router read and its weights (``by_name``: every
    leaf on the host) in float64; the sigmoid scores from the step's own
    logits and the chosen weights from those scores at the step's own choice,
    in float64; the head's rows from the rows the head read
    (``olmoekit._head_program``: inputs rounded to the compute type, every
    product exact); the path's maps of the first and the last held sublayer
    from the stream each read and its three leaves, in float64
    (``path_rows``).  ``variant`` gives a **control**, which has to lie
    outside: ``bf16`` (router, scores, weights and head as a bfloat16
    implementation would have made them), ``bias_in_weights``, ``softmax``
    (``joyaikit``'s), ``path_rows``' five, and the whole-model ones
    (``WHOLE_CONTROLS``), each of which runs the reference again on the last
    checked batch as that wrong model and returns what ``compared`` makes of
    it."""
    import jax
    import jax.numpy as jnp

    if variant in WHOLE_CONTROLS:
        params = jax.device_put(tree_of({n: by_name[n]
                                         for n in leaves(cfg)}))
        out = reference_step(params, _STEP["tokens"], _STEP["labels"], cfg,
                             _STEP["bias"], _STEP["wrt"], wrong=variant,
                             routed=_STEP["routed"])
        return compared({k: np.asarray(v) if not isinstance(v, dict) else v
                         for k, v in out.items() if k != "grads"}, cfg,
                        _STEP["wrt"])
    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()
         if k.startswith(("router_", "head_"))}
    exact = lambda a: np.asarray(a, np.float64)
    low = ok._bf16 if variant == "bf16" else exact
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    logits = low(np.einsum("lrd,lde->lre", low(s["router_in"]),
                           low(exact(by_name["router"]))))
    own = s["router_logits"]        # the step's, as the sigmoid read them
    if variant == "softmax":
        top = own.max(axis=-1, keepdims=True)
        scores = np.exp(own - top) / np.exp(own - top).sum(-1, keepdims=True)
    else:
        scores = low(1.0 / (1.0 + np.exp(-own)))
    experts = np.asarray(aux["experts"])[:, at]
    base = scores + exact(bias_before)[:, None, :] \
        if variant == "bias_in_weights" else scores
    chosen = np.take_along_axis(base, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    weights = low(chosen * cfg["routed_scaling_factor"])
    lab = np.asarray(labels)[:, :-1].reshape(-1)[at]
    rows, head_logits = ok._head_program(cfg["compute_dtype"])(
        jnp.asarray(aux["sample"]["head_in"]), head, jnp.asarray(lab))
    if variant == "bf16":           # the head's logits kept in bfloat16
        hl = ok._bf16(head_logits)
        top = hl.max(axis=-1)
        picked = np.take_along_axis(hl, lab[:, None], -1)[:, 0]
        rows = low(np.stack([top + np.log(np.exp(
            hl - top[:, None]).sum(axis=-1)), picked], axis=-1))
    ends = path_ends(aux["sample"])
    first = ("dense.hc1_{}", 0) if n_dense(cfg) else ("hc1_{}", 0)
    made = [path_rows(x, *(np.asarray(by_name[name.format(part)])[layer]
                           for part in PATH), cfg, variant)
            for x, (name, layer) in zip(ends["in"], (first, ("hc2_{}", -1)))]
    pre, post, res = (np.stack(part) for part in zip(*made))
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "router_logits": logits, "router_scores": scores * 100.0,
        "router_weights": weights * 100.0,
        "head_rows": np.asarray(rows, np.float64),
        "hc_pre": pre, "hc_post": post, "hc_res": res,
        "hc_defect": defects(res)}.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (``olmoekit.adamw_leaf`` with this
    model's undecayed leaves)."""
    return ok.adamw_leaf("ln1" if name.rsplit(".", 1)[-1] in UNDECAYED
                         else "matrix", p, g, cfg)


# -- operations and bytes counted from the shapes ---------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token meets in one layer's part of each kind
    and in the head: latent attention's five projections over the held
    heads; the path's ``phi`` (a sublayer's: two a layer); the held routed
    experts at the **mean** load (``experts_here`` / ``n_routed_experts`` of a
    token's ``num_experts_per_tok`` slots land here)."""
    per = layer_sizes(cfg)["sparse"]
    f, d = cfg["moe_intermediate_size"], cfg["hidden_size"]
    return {"latent_proj": sum(per[k] for k in ("wq_a", "wq_b", "wkv_a",
                                                "wkv_b", "wo")),
            "hc_maps": per["hc1_phi"], "router": per["router"],
            "shared": 3 * d * f * cfg["n_shared_experts"],
            "experts_mean": 3 * d * f * cfg["num_experts_per_tok"]
            * held(cfg)["experts"] / cfg["n_routed_experts"],
            "dense_mlp": 3 * d * cfg["intermediate_size"],
            "head": d * cfg["vocab_here"]}


def attention_forward_flops(cfg: dict) -> float:
    """Causal latent attention's forward FLOP a step over the held heads: q
    k^T over ``nope + rope`` and p v over ``v_head_dim``, the lower triangle
    only: 2 x b x heads x (192 + 128) x s^2 / 2 a layer."""
    b, s = cfg["micro_batch"], cfg["seq_len"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    return float(b * held(cfg)["heads"] * width * s * s * cfg["layers_here"])


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters a
    token meets x tokens (the path's ``phi`` among them), plus causal latent
    attention over the held heads at three times its forward; the held routed
    experts **at the mean load**.  The path's elementwise work (norm, gates,
    sweeps, read and write: about 60 multiply-adds an entry of the stream a
    sublayer), recomputed layers, the masked half of the diagonal blocks, the
    float32 products at six bfloat16 passes (``phi``'s, the routers') and the
    optimiser's work are not model FLOP and lower the share.
    ``flash_forward`` and ``attn_backward`` are the kernels' own counts (the
    backward five products a pair where the forward has two)."""
    tokens = cfg["micro_batch"] * cfg["seq_len"]
    per = matmul_params_per_token(cfg)
    layers, sparse = cfg["layers_here"], n_sparse(cfg)
    parts = {
        "latent_proj": 6.0 * per["latent_proj"] * tokens * layers,
        "hc_maps": 6.0 * per["hc_maps"] * tokens * 2 * layers,
        "dense_mlp": 6.0 * per["dense_mlp"] * tokens * n_dense(cfg),
        "router": 6.0 * per["router"] * tokens * sparse,
        "shared": 6.0 * per["shared"] * tokens * sparse,
        "experts": 6.0 * per["experts_mean"] * tokens * sparse,
        "head": 6.0 * per["head"] * tokens,
        "attention": 3.0 * attention_forward_flops(cfg)}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = attention_forward_flops(cfg)
    parts["attn_backward"] = 2.5 * attention_forward_flops(cfg)
    return parts


#: the stream's passes over HBM a sublayer in each pass of a step, in units of
#: one forward application's (``hc_min_bytes``): the recomputed pass is a
#: forward again; the backward pass reads the stream and its cotangent as
#: often as the forward read the stream, and writes a cotangent
HC_PASSES = {"forward": 1, "remat": 1, "backward": 2}


def hc_min_bytes(cfg: dict, b: int, s: int) -> dict:
    """The bytes the residual path has to move through HBM, **at least**:
    each sublayer application reads the float32 stream (b, s, n, d) once for
    its maps and its read, once more for its write, and writes it once, and
    reads the sublayer's ``y`` (b, s, d): 3 x b s n d x 4 + b s d x 4 bytes
    ``a_sublayer``; ``a_step`` that times the sublayers (two a held layer)
    times ``HC_PASSES``' four; by ``pass`` the same split.  The maps
    themselves (n^2 + 2 n floats a token) and ``phi`` are not counted."""
    stream = b * s * cfg["hc_mult"] * cfg["hidden_size"] * 4
    one = 3 * stream + stream // cfg["hc_mult"]
    sublayers = 2 * cfg["layers_here"]
    return {"a_sublayer": one, "sublayers": sublayers,
            "pass": {k: one * sublayers * v for k, v in HC_PASSES.items()},
            "a_step": one * sublayers * sum(HC_PASSES.values())}
