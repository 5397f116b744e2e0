"""What the ``train_step`` call kind shares: the benchmark's own copy of
the plain reference of an OLMoE training step, written independently of
the program (``ompi_tpu.parallel``), the token ids a batch is made of,
and the functions that count a step's model FLOP and each kernel's.

The reference follows the Hugging Face ``olmoe`` model
(``modeling_olmoe.py``): pre-norm block; q, k, v, o projections without
bias; RMSNorm with a gain over the whole width of q and of k **before**
the heads are split; RoPE (``rotate_half``); causal attention as a full
``softmax(QK^T + mask)V``; router ``softmax(x W_r)`` over all experts,
top k, weights **not** renormalised; every expert applied to every token
and weighted by the dense one-hot of the router's choice; final norm;
linear head; mean cross-entropy; ``load_balancing_loss_func`` over every
layer's rows at once.  Everything float32, every matmul at the highest
precision, no kernel, no sort, no donation.  Departures:

* the router z-loss (mean squared logsumexp of the router's logits) is
  not in the HF model; OLMoE was trained with it (arXiv:2409.02060);
* at the published widths the (T, V) logits, the (s, s) scores of all
  heads at once and the (E, T, f) activations of all experts at once do
  not fit beside the program's 7.5 GB of state, so the head runs by
  blocks of rows, attention one (batch, head) at a time and the experts
  one after the other (``lax.map`` / ``lax.scan``, each step
  recomputed in the backward pass).  The arithmetic of every element is
  the same; only what is held at once differs.
"""
from __future__ import annotations

import functools
import json
import zlib

import numpy as np

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
                "router", "gate", "up", "down")
LEAVES = ("embed",) + LAYER_LEAVES + ("final_norm", "head")
GAINS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")
PROBE = 64
SAMPLE_ROWS = 16
# The harness applies one tolerance to everything a check compares, so
# each compared quantity is put in a unit in which one step of the
# tolerance (0.005) says what it should of that quantity (``compared``):
# a gradient entry in units of 128 RMS of its leaf (0.64 RMS: bfloat16
# matmul inputs move an entry by up to 0.1 RMS, a wrong backward pass by
# whole RMS), a leaf's RMS as log10 over 4 (5% of the RMS: once a model
# has learnt which tokens are frequent their logits are of order 3,
# bfloat16 inputs move them by a hundredth, and the gradient's RMS, which
# those tokens' columns dominate, by up to 1.3% in every leaf at once), a
# routing regret in units of 32 k-th probabilities, the head's rows
# averaged over quarters; and what a float32 part of the step made from
# its own inputs in units of 0.01 (``precision_got``), where float32 is
# within 1e-4 and bfloat16 is not.
PROBE_UNIT = 128.0
RMS_UNIT = 4.0
REGRET_UNIT = 32.0
ROW_BLOCKS = 4
SAMPLE_UNIT = 0.01
ZIPF_EXPONENT = 1.0


def load_config(path: str) -> dict:
    """The configuration file as the reference reads it: the published
    keys, ``layers_here`` and the ``train`` group, flat."""
    with open(path, encoding="utf-8") as f:
        body = json.load(f)
    return {**{k: v for k, v in body.items() if k != "train"},
            **body.get("train", {})}


def leaf_of(params: dict, name: str):
    return params["layers"][name] if name in LAYER_LEAVES else params[name]


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this rank holds."""
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_experts"]
    n, v = cfg["layers_here"], cfg["vocab_size"]
    layer = {"ln1": d, "wq": d * d, "wk": d * d, "wv": d * d, "wo": d * d,
             "q_norm": d, "k_norm": d, "ln2": d, "router": d * e,
             "gate": e * d * f, "up": e * d * f, "down": e * f * d}
    return {"embed": v * d, **{k: n * s for k, s in layer.items()},
            "final_norm": d, "head": d * v}


def sample_rows(rows: int) -> np.ndarray:
    """The token rows a step reports activations at (the rule the
    program states in ``parallel/train.sample_rows``)."""
    n = min(SAMPLE_ROWS, rows)
    return (np.arange(1, n + 1) * rows) // n - 1


def probe_positions(name: str, size: int) -> np.ndarray:
    """The flat positions of leaf ``name`` a step reports on: drawn from
    the leaf's name alone (the rule the program states in
    ``parallel/train.probe_positions``)."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    return np.sort(rng.integers(0, size, PROBE)).astype(np.int64)


# -- the batch ---------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def zipf_cdf(vocab: int) -> np.ndarray:
    """The cumulative Zipf law (exponent 1) over ``vocab`` ranks, as
    float32 (the uniform draw has 24 useful bits)."""
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** ZIPF_EXPONENT
    return (np.cumsum(w) / np.sum(w)).astype(np.float32)


def rank_order(vocab: int, seed: int) -> np.ndarray:
    """Which token id holds which rank of the law: a permutation drawn
    from ``seed``."""
    return np.random.default_rng([seed, vocab]).permutation(vocab).astype(
        np.int32)


def tokens_of(bits, cdf, order):
    """Token ids from uniform int32 bit patterns, on the device: the
    pattern as a fraction of 2**32 picks a rank by the cumulative law,
    the rank a token id by ``order``."""
    import jax.numpy as jnp

    u = (bits.astype(jnp.uint32) >> 8).astype(jnp.float32) * (2.0 ** -24)
    rank = jnp.searchsorted(cdf, u, side="right")
    return order[jnp.minimum(rank, order.shape[0] - 1)]


# -- the reference -------------------------------------------------------------
def _norm(x, gain, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    import jax.numpy as jnp

    hd, s = x.shape[-1], x.shape[-2]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return (x * jnp.cos(ang) + rot * jnp.sin(ang)).astype(x.dtype)


def _attention(q, k, v):
    """Full causal softmax attention, one (batch, head) at a time."""
    import jax
    import jax.numpy as jnp

    b, h, s, hd = q.shape
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(qkv):
        qi, ki, vi = qkv
        sc = jnp.where(mask, (qi @ ki.T) / jnp.sqrt(hd).astype(qi.dtype),
                       -jnp.inf)
        return jax.nn.softmax(sc, axis=-1) @ vi

    flat = lambda t: t.reshape(b * h, s, hd)
    return jax.lax.map(one, (flat(q), flat(k), flat(v))).reshape(b, h, s, hd)


def _experts(h, weight, gate, up, down):
    """Every expert on every token, weighted by ``weight`` (T, E), one
    expert after the other."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def one(acc, xs):
        g, u, d, w = xs
        y = (jax.nn.silu(h @ g) * (h @ u)) @ d
        return acc + w[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (gate, up, down, weight.T))
    return out


def _head(h, head, labels, rows):
    """Per row of the head (logsumexp, the label's logit), by blocks."""
    import jax
    import jax.numpy as jnp

    t, d = h.shape
    rows = min(rows, t)

    @jax.checkpoint
    def one(xs):
        hb, lb = xs
        logits = hb @ head
        return jnp.stack([jax.nn.logsumexp(logits, axis=-1),
                          jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]],
                         axis=-1)

    return jax.lax.map(one, (h.reshape(t // rows, rows, d),
                             labels.reshape(t // rows, rows))).reshape(t, 2)


def loss_parts(params, tokens, labels, cfg: dict, wrong: str | None = None,
               routed=None):
    """(total, {losses, loads, rows, regret}) of one batch, in the
    parameters' own type throughout (float32; bfloat16 for the control).
    With ``routed`` (L, T, k), the experts a program chose, the router's
    top k is not taken here but given: each token goes to those experts
    under this model's own probabilities for them, and ``regret`` says
    how far the choice is from this model's own: per layer, the most by
    which the k-th largest probability of a token exceeds the smallest
    it was routed under, in units of ``REGRET_UNIT`` times the former (0:
    the same choice up to exact ties).  bfloat16 turns a near-tie another way, and where a
    whole batch shares one (every late position of a freshly drawn model
    attends to nearly the same mean) for thousands of tokens at once, so
    two right computations differ by whole experts' outputs on those
    rows: with the choice given, everything else is compared element by
    element, and the choice itself by its regret.  ``wrong`` names a
    deliberately wrong variant, for the tests that a comparison catches
    it: ``renorm`` (top-k weights renormalised), ``qknorm_per_head``
    (QK-norm after the head split)."""
    import jax
    import jax.numpy as jnp

    b, s = tokens.shape
    nh, eps, e = cfg["num_attention_heads"], cfg["rms_norm_eps"], \
        cfg["num_experts"]
    k_top, n_layers = cfg["num_experts_per_tok"], cfg["layers_here"]
    x = params["embed"][tokens]
    slots = prob_sum = z_sum = 0.0
    loads, regrets = [], []
    for i in range(n_layers):
        p = {name: params["layers"][name][i] for name in LAYER_LEAVES}
        h = _norm(x, p["ln1"], eps)
        heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
        if wrong == "qknorm_per_head":
            hd = x.shape[-1] // nh
            q = heads(_norm((h @ p["wq"]).reshape(b, s, nh, hd),
                            p["q_norm"].reshape(nh, hd), eps
                            ).reshape(b, s, -1))
            k = heads(_norm((h @ p["wk"]).reshape(b, s, nh, hd),
                            p["k_norm"].reshape(nh, hd), eps
                            ).reshape(b, s, -1))
        else:
            q = heads(_norm(h @ p["wq"], p["q_norm"], eps))
            k = heads(_norm(h @ p["wk"], p["k_norm"], eps))
        o = _attention(_rope(q, cfg["rope_theta"]),
                       _rope(k, cfg["rope_theta"]), heads(h @ p["wv"]))
        x = x + o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]

        h = _norm(x, p["ln2"], eps).reshape(b * s, -1)
        logits = h @ p["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k_top)
        if routed is not None:
            own_kth, top_e = top_w[:, -1], routed[i]
            top_w = jnp.take_along_axis(probs, top_e, axis=-1)
            regrets.append(jnp.max(
                (own_kth - jnp.min(top_w, axis=-1))
                / (REGRET_UNIT * own_kth)))
        if cfg.get("norm_topk_prob") or wrong == "renorm":
            top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
        choice = jax.nn.one_hot(top_e, e, dtype=x.dtype)        # (T, k, E)
        weight = jnp.einsum("tk,tke->te", top_w, choice)
        x = x + _experts(h, weight, p["gate"], p["up"], p["down"]
                         ).reshape(b, s, -1)
        count = jnp.sum(choice, axis=(0, 1))
        loads.append(count)
        lse = jax.nn.logsumexp(logits, axis=-1)
        slots, prob_sum = slots + count, prob_sum + jnp.sum(probs, axis=0)
        z_sum = z_sum + jnp.sum(lse * lse)
    h = _norm(x, params["final_norm"], eps).reshape(b * s, -1)
    rows = _head(h, params["head"], labels.reshape(b * s),
                 cfg.get("loss_block_rows", 1024))
    routed = n_layers * b * s
    ce = jnp.mean(rows[:, 0] - rows[:, 1])
    lb = e * jnp.sum((slots / routed) * (prob_sum / routed))
    z = z_sum / routed
    lb, z = cfg["aux_loss_coef"] * lb, cfg["z_loss_coef"] * z
    total = ce + lb + z
    return total, {"losses": jnp.stack([total, ce, lb, z]),
                   "loads": jnp.stack(loads), "rows": rows,
                   "regret": jnp.stack(regrets) if regrets
                   else jnp.zeros((n_layers,), x.dtype)}


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, wrt: tuple, wrong):
    import jax

    cfg = dict(cfg_items)

    def run(params, tokens, labels, routed):
        diff = {n: leaf_of(params, n) for n in wrt}

        def loss(diff):
            layers = {**params["layers"],
                      **{n: a for n, a in diff.items() if n in LAYER_LEAVES}}
            merged = {**params, **{n: a for n, a in diff.items()
                                   if n not in LAYER_LEAVES},
                      "layers": layers}
            return loss_parts(merged, tokens, labels, cfg, wrong, routed)

        with jax.default_matmul_precision("highest"):
            (_, aux), g = jax.value_and_grad(loss, has_aux=True)(diff)
        return aux, g

    return jax.jit(run)


def reference_step(params, tokens, labels, cfg: dict, wrt: tuple,
                   wrong: str | None = None, routed=None) -> dict:
    """One step's statistics from the reference, in the form
    ``step_stats`` puts a program's in: ``losses``, ``loads``, ``rows``,
    ``regret`` (``loss_parts``: 0 without ``routed``), and for each leaf
    of ``wrt`` its gradient's ``grad_sq`` and ``grad_probe``; ``grads``
    holds the whole gradients of ``wrt``.  Parameters given in bfloat16
    make the **control**: the same model computed throughout in the
    nearest precision below the one the configuration states."""
    import jax.numpy as jnp

    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    aux, g = _grad_program(items, tuple(wrt), wrong)(params, tokens, labels,
                                                     routed)
    aux, g = ({k: v.astype(jnp.float32) for k, v in t.items()}
              for t in (aux, g))
    flat = {n: g[n].reshape(-1) for n in wrt}
    return {**aux, "grads": g,
            "grad_sq": {n: jnp.sum(f * f) for n, f in flat.items()},
            "grad_probe": {n: f[probe_positions(n, f.shape[0])]
                           for n, f in flat.items()}}


def step_stats(aux: dict) -> dict:
    """A program step's ``aux`` (``parallel/train.py``: raw statistics)
    in the reference's form.  A step routes every token to its own top
    k, so its regret is 0 by definition."""
    out = {k: np.asarray(aux[k]) for k in ("losses", "loads", "rows")}
    out["regret"] = np.zeros(out["loads"].shape[:1], np.float32)
    for k in ("grad_sq", "grad_probe"):
        out[k] = dict(zip(LEAVES, np.asarray(aux[k])))
    return out


def compared(stats: dict, cfg: dict, checked: tuple) -> dict:
    """What a check compares of one step's statistics, each in its unit
    (the constants above): the losses as they are; the share of the
    step's slots every expert received; the head's logsumexp and label
    logit averaged over quarters of the rows; the routing's regret; and
    for the leaves of ``checked`` the gradient's RMS as log10 over
    ``RMS_UNIT`` and its probed entries in units of ``PROBE_UNIT`` RMS."""
    rows = np.asarray(stats["rows"], np.float32)
    sizes = leaf_sizes(cfg)
    rms = np.sqrt([float(stats["grad_sq"][n]) / sizes[n] for n in checked])
    probe = np.stack([np.asarray(stats["grad_probe"][n]) for n in checked])
    return {k: np.asarray(v, np.float32) for k, v in {
        "losses": stats["losses"],
        "load_share": np.asarray(stats["loads"]) / (
            rows.shape[0] * cfg["num_experts_per_tok"]),
        "row_means": rows.reshape(ROW_BLOCKS, -1, 2).mean(axis=1),
        "route_regret": stats["regret"],
        "grad_log_rms": np.log10(rms) / RMS_UNIT,
        "grad_probe": probe / (PROBE_UNIT * rms[:, None])}.items()}


# -- the float32 parts of a step, read from the step alone -----------------------
def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made at ``sample_rows``, in units of
    ``SAMPLE_UNIT`` (the router's chosen probabilities in units of
    ``SAMPLE_UNIT`` over the experts, so that they too are of order
    100): the router's logits, their logsumexp, the top k of their
    softmax, and the head's logsumexp and label logit."""
    s = aux["sample"]
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "router_logits": s["router_logits"], "router_lse": s["router_lse"],
        "router_weights": np.asarray(s["router_weights"])
        * cfg["num_experts"],
        "head_rows": np.asarray(aux["rows"])[at]}.items()}


def _bf16(a):
    import ml_dtypes

    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float64)


@functools.lru_cache(maxsize=None)
def _head_program(dtype: str):
    import jax
    import jax.numpy as jnp

    def run(h, head, labels):
        logits = jnp.dot(h.astype(dtype).astype(jnp.float32),
                         head.astype(dtype).astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        return jnp.stack([jax.nn.logsumexp(logits, axis=-1),
                          jnp.take_along_axis(logits, labels[:, None],
                                              -1)[:, 0]], axis=-1), logits

    return jax.jit(run)


def precision_want(aux: dict, router, head, labels, cfg: dict,
                   lowered: bool = False) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own
    inputs to each part** (so that the bfloat16 matmuls in front of a
    part do not enter) at the precision the configuration states: the
    router's logits from the rows the router read and the router's
    weights in float64; their logsumexp and the top k of their softmax
    from the step's own logits in float64; the head's rows from the rows
    the head read, its inputs rounded to the compute type, every product
    exact (``router`` (L, d, E) on the host, ``head`` (d, V) on the
    device).  ``lowered`` gives the **control**: each part as a
    bfloat16 implementation of it would have made it (inputs and result
    of the router's matmul, the logsumexp, the probabilities, the head's
    logits and rows rounded to bfloat16), which has to lie outside."""
    import jax.numpy as jnp

    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()}
    low = _bf16 if lowered else (lambda a: np.asarray(a, np.float64))
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    logits = low(np.einsum("lrd,lde->lre", low(s["router_in"]),
                           low(router)))
    own = s["router_logits"]        # the step's, as the softmax read them
    top = own.max(axis=-1, keepdims=True)
    lse = top[..., 0] + np.log(np.exp(own - top).sum(axis=-1))
    experts = np.asarray(aux["experts"])[:, at]
    weights = low(np.take_along_axis(np.exp(own - lse[..., None]), experts,
                                     axis=-1))
    lse = low(lse)
    rows, head_logits = _head_program(cfg["compute_dtype"])(
        jnp.asarray(aux["sample"]["head_in"]), head,
        jnp.asarray(np.asarray(labels).reshape(-1)[at]))
    if lowered:         # the head's logits kept in bfloat16
        hl = _bf16(head_logits)
        top = hl.max(axis=-1)
        picked = np.take_along_axis(hl, np.asarray(labels).reshape(
            -1)[at][:, None], -1)[:, 0]
        rows = low(np.stack([top + np.log(np.exp(
            hl - top[:, None]).sum(axis=-1)), picked], axis=-1))
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "router_logits": logits, "router_lse": lse,
        "router_weights": weights * cfg["num_experts"],
        "head_rows": np.asarray(rows, np.float64)}.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (both moments start at zero;
    the learning rate is the first step's of a linear warm-up)."""
    import jax.numpy as jnp

    b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
    m, v = (1 - b1) * g, (1 - b2) * g * g
    upd = (m / (1 - b1)) / (jnp.sqrt(v / (1 - b2)) + cfg["adam_eps"])
    if name not in GAINS:
        upd = upd + cfg["weight_decay"] * p
    return p - first_lr(cfg) * upd


def first_lr(cfg: dict) -> float:
    return cfg["lr"] / max(1, cfg.get("warmup_steps", 1))


# -- operations counted from the shapes -------------------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token meets in one layer and in the head."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {"attention": 4 * d * d, "router": d * cfg["num_experts"],
            "experts": cfg["num_experts_per_tok"] * 3 * d * f,
            "head": d * cfg["vocab_size"]}


def attention_forward_flops(cfg: dict) -> float:
    """Causal attention's forward FLOP a step: two matmuls over the
    lower triangle, 2 x 2 x b x d x s^2 / 2 a layer."""
    b, s, d = cfg["micro_batch"], cfg["seq_len"], cfg["hidden_size"]
    return 2.0 * b * d * s * s * cfg["layers_here"]


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters
    a token meets x tokens (forward, and twice that backward), plus
    causal attention at three times its forward.  What the program
    spends beyond that (the masked half of the diagonal blocks, scores
    recomputed in the backward pass, the optimiser's elementwise work)
    is not model FLOP and lowers the share."""
    tokens = cfg["micro_batch"] * cfg["seq_len"]
    per = matmul_params_per_token(cfg)
    n = cfg["layers_here"]
    parts = {"attention_proj": 6.0 * per["attention"] * tokens * n,
             "router": 6.0 * per["router"] * tokens * n,
             "experts": 6.0 * per["experts"] * tokens * n,
             "head": 6.0 * per["head"] * tokens,
             "attention": 3.0 * attention_forward_flops(cfg)}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = attention_forward_flops(cfg)
    return parts


def adamw_bytes(cfg: dict) -> float:
    """HBM bytes AdamW moves a step: per parameter the weight, the
    gradient and the two moments read (16) and the weight and the
    moments written (12)."""
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_experts"]
    layer = 4 * d * d + d * e + 3 * e * d * f + 4 * d
    return 28.0 * (cfg["layers_here"] * layer
                   + 2 * d * cfg["vocab_size"] + d)
