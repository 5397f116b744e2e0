"""What the ``train_step_share`` call kind shares: the benchmark's own
copy of the plain reference of JoyAI-LLM-Flash's training step on one
chip's share of an expert-parallel deployment, written independently of
the program (``ompi_tpu.parallel``), and the functions that count a
step's model FLOP and the attention kernel's.  The batch (Zipf ids), the
probe and sample rules and the blocked experts and head are
``harness/olmoekit``'s: a kit states a model, not a second harness.

The model's published ``config.json`` uses DeepSeek-V3's keys letter for
letter; the equations are those of ``modeling_deepseek.py`` and of
arXiv:2412.19437 sections 2.1-2.2.  Pre-norm blocks.  **Latent
attention**: ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` in heads of ``[nope
| rope]``; ``[c_kv | k_rope] = x W_kva``, ``[k_nope | v] = norm(c_kv)
W_kvb``; RoPE on interleaved pairs of the rope parts, the one rotary key
shared by every head; causal ``softmax(q k^T / sqrt(nope + rope)) v`` in
full.  A dense SwiGLU in the first ``first_k_dense_replace`` layers.
**Sparse layers**: ``s = sigmoid(h W_r)`` over all the experts; the top k
of ``s + b`` (``b`` the balancing bias: the choice only); weights
``routed_scaling_factor * s_chosen / sum(s_chosen)``; the shared expert
on every token.  After a step ``b += gamma * sign(mean load - load)``.
**The next-next-token module**: ``h' = M [norm(emb(t_{i+1})) ; norm(h_i)]``
(``h_i`` before the final norm), one sparse layer, a norm, the same
embedding and head; its cross-entropy against ``t_{i+2}`` enters the loss
times ``mtp_loss_coef``.  Everything float32, every matmul at the highest
precision, no kernel, no sort.  Departures:

* **the share** (the configuration's ``experts_here``, ``expert_share``,
  ``vocab_here``): every held expert runs on every token under a dense
  mask of the router's choice among **all** the experts; what the absent
  experts would add is left out; the embedding, the logits and both
  losses are over the slice of the vocabulary;
* ``n_group`` = ``topk_group`` = 1 as published, so the group-limited
  choice is the plain top k;
* no sequence-wise auxiliary loss; the loads that move the bias are the
  whole batch's;
* at the published widths the (T, V) logits, the (s, s) scores of all
  heads at once, the (E, T, f) activations of all held experts at once
  and six layers' activations do not fit beside the program's 8.2 GB of
  state, so the head runs by blocks of rows, attention one head at a
  time, the experts one after the other, and every layer is recomputed
  in the backward pass (``lax.map`` / ``lax.scan`` / ``jax.checkpoint``).
  The arithmetic of every element is the same; only what is held at once
  differs.
"""
from __future__ import annotations

import functools

import numpy as np

from harness import olmoekit as ok
from harness.olmoekit import (PROBE_UNIT, REGRET_UNIT, RMS_UNIT,  # noqa: F401
                              ROW_BLOCKS, SAMPLE_UNIT, load_config,
                              probe_positions, rank_order, sample_rows,
                              tokens_of, zipf_cdf)

ATTENTION = ("ln1", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
             "wkv_b", "wo")
SPARSE = ATTENTION + ("ln2", "router", "gate", "up", "down", "shared_gate",
                      "shared_up", "shared_down")
DENSE = ATTENTION + ("ln2", "gate", "up", "down")
MODULE = ("enorm", "hnorm", "proj") + SPARSE + ("norm",)
LEAVES = ("embed",) + tuple("dense." + k for k in DENSE) + SPARSE + tuple(
    "mtp." + k for k in MODULE) + ("final_norm", "head")
GAINS = ("ln1", "ln2", "q_a_norm", "kv_a_norm", "enorm", "hnorm", "norm",
         "final_norm")
WRONG = ("softmax", "bias_in_weights", "rope_on_nope", "unnormalised",
         "mtp_fed_t_i")
# one more unit beside olmoekit's: a balancing bias in units of gamma, so
# that a sign applied the wrong way, or to the wrong load, is a whole unit
BIAS_UNIT = 1.0


def _place(name: str) -> tuple:
    """(the group of the parameter tree leaf ``name`` lies in, or None
    for the tree's top; its key there)."""
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


def tree_of(leaves: dict) -> dict:
    """The parameter tree from {leaf name: array}."""
    tree: dict = {}
    for name, a in leaves.items():
        put_leaf(tree, name, a)
    return tree


def n_sparse(cfg: dict) -> int:
    return cfg["layers_here"] - cfg["first_k_dense_replace"]


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this rank holds."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rot, hv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    f, e, ff = cfg["moe_intermediate_size"], cfg["experts_here"], \
        cfg["intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    att = {"ln1": d, "wq_a": d * qr, "q_a_norm": qr,
           "wq_b": qr * nh * (nope + rot), "wkv_a": d * (kr + rot),
           "kv_a_norm": kr, "wkv_b": kr * nh * (nope + hv),
           "wo": nh * hv * d}
    sparse = {**att, "ln2": d, "router": d * cfg["n_routed_experts"],
              "gate": e * d * f, "up": e * d * f, "down": e * f * d,
              "shared_gate": d * fs, "shared_up": d * fs,
              "shared_down": fs * d}
    dense = {**att, "ln2": d, "gate": d * ff, "up": d * ff, "down": ff * d}
    module = {"enorm": d, "hnorm": d, "proj": 2 * d * d, **sparse, "norm": d}
    v = cfg["vocab_here"]
    return {"embed": v * d,
            **{"dense." + k: cfg["first_k_dense_replace"] * s
               for k, s in dense.items()},
            **{k: n_sparse(cfg) * s for k, s in sparse.items()},
            **{"mtp." + k: s for k, s in module.items()},
            "final_norm": d, "head": d * v}


# -- the reference -------------------------------------------------------------
def _rope(x, theta):
    """Rotary embedding of (..., s, hd) on interleaved pairs."""
    import jax.numpy as jnp

    hd, s = x.shape[-1], x.shape[-2]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                     -1).reshape(x.shape).astype(x.dtype)


def _attention(q, k, v):
    """Full causal softmax attention, one (batch, head) at a time; q and
    k of one width, v of another."""
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

    flat = lambda t: t.reshape(b * h, s, t.shape[-1])
    return jax.lax.map(one, (flat(q), flat(k), flat(v))).reshape(
        b, h, s, v.shape[-1])


def _latent_attention(p, x, cfg, wrong):
    import jax.numpy as jnp

    b, s, _ = x.shape
    nh, eps, rank = cfg["num_attention_heads"], cfg["rms_norm_eps"], \
        cfg["kv_lora_rank"]
    nope, theta = cfg["qk_nope_head_dim"], cfg["rope_theta"]
    h = ok._norm(x, p["ln1"], eps)
    heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
    q = heads(ok._norm(h @ p["wq_a"], p["q_a_norm"], eps) @ p["wq_b"])
    kv = h @ p["wkv_a"]
    kvb = heads(ok._norm(kv[..., :rank], p["kv_a_norm"], eps) @ p["wkv_b"])
    k_rope = kv[:, None, :, rank:]
    if wrong == "rope_on_nope":
        q = jnp.concatenate([_rope(q[..., :nope], theta), q[..., nope:]], -1)
        k_nope = _rope(kvb[..., :nope], theta)
    else:
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
        k_nope, k_rope = kvb[..., :nope], _rope(k_rope, theta)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (b, nh, s, k_rope.shape[-1]))], -1)
    o = _attention(q, k, kvb[..., nope:])
    return x + o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _sparse_layer(p, x, bias, cfg, wrong, routed):
    """(x, the slots every expert received, the routing's regret)."""
    import jax
    import jax.numpy as jnp

    b, s, d = x.shape
    e, k_top = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    x = _latent_attention(p, x, cfg, wrong)
    h = ok._norm(x, p["ln2"], cfg["rms_norm_eps"]).reshape(b * s, d)
    logits = h @ p["router"]
    scores = jax.nn.softmax(logits, -1) if wrong == "softmax" \
        else jax.nn.sigmoid(logits)
    biased = scores + bias
    own, top_e = jax.lax.top_k(biased, k_top)
    regret = jnp.zeros((), x.dtype)
    if routed is not None:
        under = jnp.take_along_axis(biased, routed, axis=-1)
        regret = jnp.max((own[:, -1] - jnp.min(under, axis=-1))
                         / (REGRET_UNIT * own[:, -1]))
        top_e = routed
    chosen = jnp.take_along_axis(
        biased if wrong == "bias_in_weights" else scores, top_e, axis=-1)
    if cfg["norm_topk_prob"] and wrong != "unnormalised":
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    choice = jax.nn.one_hot(top_e, e, dtype=x.dtype)            # (T, k, E)
    weight = jnp.einsum("tk,tke->te",
                        chosen * cfg["routed_scaling_factor"], choice)
    first = cfg["expert_share"] * cfg["experts_here"]
    y = ok._experts(h, weight[:, first:first + cfg["experts_here"]],
                    p["gate"], p["up"], p["down"])
    y = y + _swiglu(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return x + y.reshape(b, s, d), jnp.sum(choice, axis=(0, 1)), regret


def loss_parts(params, tokens, labels, cfg: dict, bias: dict,
               wrong: str | None = None, routed=None):
    """(total, {losses, loads, rows, mtp_rows, regret}) of one batch, in
    the parameters' own type throughout (float32; bfloat16 for the
    control).  ``labels`` (b, s + 1): every position's next token and,
    one further, the one after.  ``bias`` {layers (L, E), mtp (1, E)}.
    With ``routed`` (L + 1, T, k), the experts a program chose (the
    module's last), the top k is not taken here but given, and
    ``regret`` says how far that choice is from this model's own under
    its own scores plus bias (``olmoekit.loss_parts``).  ``wrong`` names
    a deliberately wrong variant (``WRONG``), for the tests and controls
    that a comparison catches it."""
    import jax
    import jax.numpy as jnp

    b, s = tokens.shape
    eps = cfg["rms_norm_eps"]
    given = (lambda i: None) if routed is None else (lambda i: routed[i])

    @jax.checkpoint
    def dense(p, x):
        x = _latent_attention(p, x, cfg, wrong)
        return x + _swiglu(ok._norm(x, p["ln2"], eps), p["gate"], p["up"],
                           p["down"])

    sparse = jax.checkpoint(
        lambda p, x, bias_row, chosen: _sparse_layer(p, x, bias_row, cfg,
                                                     wrong, chosen))
    x = params["embed"][tokens]
    for i in range(cfg["first_k_dense_replace"]):
        x = dense({k: v[i] for k, v in params["dense"].items()}, x)
    loads, regrets = [], []
    for i in range(n_sparse(cfg)):
        x, load, regret = sparse({k: v[i] for k, v in
                                  params["layers"].items()}, x,
                                 bias["layers"][i], given(i))
        loads.append(load)
        regrets.append(regret)
    block = cfg.get("loss_block_rows", 1024)
    h = ok._norm(x, params["final_norm"], eps).reshape(b * s, -1)
    rows = ok._head(h, params["head"], labels[:, :s].reshape(b * s), block)
    mtp = params["mtp"]
    fed = tokens if wrong == "mtp_fed_t_i" else labels[:, :s]
    joined = jnp.concatenate(
        [ok._norm(params["embed"][fed], mtp["enorm"], eps),
         ok._norm(x, mtp["hnorm"], eps)], -1)
    x2, load, regret = sparse(mtp, joined @ mtp["proj"], bias["mtp"][0],
                              given(n_sparse(cfg)))
    loads.append(load)
    regrets.append(regret)
    h2 = ok._norm(x2, mtp["norm"], eps).reshape(b * s, -1)
    rows2 = ok._head(h2, params["head"], labels[:, 1:].reshape(b * s), block)
    ce = jnp.mean(rows[:, 0] - rows[:, 1])
    mtp_ce = cfg["mtp_loss_coef"] * jnp.mean(rows2[:, 0] - rows2[:, 1])
    total = ce + mtp_ce
    return total, {"losses": jnp.stack([total, ce, mtp_ce]),
                   "loads": jnp.stack(loads), "rows": rows,
                   "mtp_rows": rows2, "regret": jnp.stack(regrets)}


def bias_step(bias: dict, loads, cfg: dict):
    """The biases (L + 1, E), the module's last, after a step whose
    experts received ``loads``."""
    import jax.numpy as jnp

    return jnp.concatenate([bias["layers"], bias["mtp"]]) \
        + cfg["bias_update_gamma"] * jnp.sign(
            jnp.mean(loads, -1, keepdims=True) - loads)


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, wrt: tuple, wrong):
    import jax

    cfg = dict(cfg_items)

    def run(params, tokens, labels, bias, routed):
        diff = {n: leaf_of(params, n) for n in wrt}

        def loss(diff):
            merged = {k: dict(v) if isinstance(v, dict) else v
                      for k, v in params.items()}
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
    """One step's statistics from the reference, in the form
    ``step_stats`` puts a program's in: ``losses``, ``loads``, ``rows``,
    ``mtp_rows``, ``regret``, ``bias`` (after the update), and for each
    leaf of ``wrt`` its gradient's ``grad_sq`` and ``grad_probe``;
    ``grads`` holds the whole gradients of ``wrt``.  Parameters given in
    bfloat16 make the **control**: the same model computed throughout in
    the nearest precision below the one the configuration states."""
    import jax.numpy as jnp

    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    aux, g = _grad_program(items, tuple(wrt), wrong)(
        params, tokens, labels, bias, routed)
    aux, g = ({k: v.astype(jnp.float32) for k, v in t.items()}
              for t in (aux, g))
    flat = {n: g[n].reshape(-1) for n in wrt}
    f32 = {k: v.astype(jnp.float32) for k, v in bias.items()}
    return {**aux, "grads": g, "bias": bias_step(f32, aux["loads"], cfg),
            "grad_sq": {n: jnp.sum(f * f) for n, f in flat.items()},
            "grad_probe": {n: f[probe_positions(n, f.shape[0])]
                           for n, f in flat.items()}}


def step_stats(aux: dict, bias_after: dict) -> dict:
    """A program step's ``aux`` (``parallel/train.py``: raw statistics)
    and the biases its state holds after it, in the reference's form.  A
    step routes every token to its own top k, so its regret is 0 by
    definition."""
    out = {k: np.asarray(aux[k]) for k in ("loads", "rows", "mtp_rows")}
    losses = np.asarray(aux["losses"])      # total, ce, lb, z, mtp
    out["losses"] = losses[[0, 1, 4]]
    out["regret"] = np.zeros(out["loads"].shape[:1], np.float32)
    out["bias"] = np.concatenate([np.asarray(bias_after["layers"]),
                                  np.asarray(bias_after["mtp"])])
    for k in ("grad_sq", "grad_probe"):
        out[k] = dict(zip(LEAVES, np.asarray(aux[k])))
    return out


def compared(stats: dict, cfg: dict, checked: tuple) -> dict:
    """What a check compares of one step's statistics, each in its unit
    (``olmoekit``'s constants): the loss and both cross-entropies as
    they are; the share of a layer's slots every one of all the experts
    received, and the held experts' together; both heads' logsumexp and
    label logit averaged over quarters of the rows; the routing's
    regret; the biases after the update in units of gamma; and for the
    leaves of ``checked`` the gradient's RMS as log10 over ``RMS_UNIT``
    and its probed entries in units of ``PROBE_UNIT`` RMS."""
    rows = np.stack([np.asarray(stats[k], np.float32)
                     for k in ("rows", "mtp_rows")])
    sizes = leaf_sizes(cfg)
    rms = np.sqrt([float(stats["grad_sq"][n]) / sizes[n] for n in checked])
    probe = np.stack([np.asarray(stats["grad_probe"][n]) for n in checked])
    share = np.asarray(stats["loads"]) / (
        rows.shape[1] * cfg["num_experts_per_tok"])
    first = cfg["expert_share"] * cfg["experts_here"]
    return {k: np.asarray(v, np.float32) for k, v in {
        "losses": stats["losses"], "load_share": share,
        "local_share": share[:, first:first + cfg["experts_here"]].sum(-1),
        "row_means": rows.reshape(2, ROW_BLOCKS, -1, 2).mean(axis=2),
        "route_regret": stats["regret"],
        "bias": np.asarray(stats["bias"]) / (
            BIAS_UNIT * cfg["bias_update_gamma"]),
        "grad_log_rms": np.log10(rms) / RMS_UNIT,
        "grad_probe": probe / (PROBE_UNIT * rms[:, None])}.items()}


# -- the float32 parts of a step, read from the step alone -----------------------
def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made at ``sample_rows``, in units of
    ``SAMPLE_UNIT``: the routers' logits, their sigmoid scores (times
    100, so that a score's step of 1e-4 is one unit), the chosen weights
    (times 100 likewise), and both heads' logsumexp and label logit."""
    s = aux["sample"]
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "router_logits": s["router_logits"],
        "router_scores": np.asarray(s["router_scores"]) * 100.0,
        "router_weights": np.asarray(s["router_weights"]) * 100.0,
        "head_rows": np.stack([np.asarray(aux["rows"])[at],
                               np.asarray(aux["mtp_rows"])[at]])}.items()}


def precision_want(aux: dict, router, bias_before, head, labels, cfg: dict,
                   variant: str | None = None) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own
    inputs to each part** at the precision the configuration states: the
    routers' logits from the rows each router read and its weights
    (``router`` (L + 1, d, E) on the host) in float64; the sigmoid
    scores from the step's own logits and the chosen weights from those
    scores at the step's own choice, in float64; both heads' rows from
    the rows each head read (``olmoekit._head_program``: inputs rounded
    to the compute type, every product exact).  ``variant`` gives a
    **control**, which has to lie outside: ``bf16`` (each part as a
    bfloat16 implementation would have made it), ``bias_in_weights``
    (the weights taken from score + bias), ``softmax`` (scores by a
    softmax over the experts)."""
    import jax.numpy as jnp

    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()}
    low = ok._bf16 if variant == "bf16" else (
        lambda a: np.asarray(a, np.float64))
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    logits = low(np.einsum("lrd,lde->lre", low(s["router_in"]),
                           low(router)))
    own = s["router_logits"]        # the step's, as the sigmoid read them
    if variant == "softmax":
        top = own.max(axis=-1, keepdims=True)
        scores = np.exp(own - top) / np.exp(own - top).sum(-1, keepdims=True)
    else:
        scores = low(1.0 / (1.0 + np.exp(-own)))
    experts = np.asarray(aux["experts"])[:, at]
    base = scores + np.asarray(bias_before, np.float64)[:, None, :] \
        if variant == "bias_in_weights" else scores
    chosen = np.take_along_axis(base, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    weights = low(chosen * cfg["routed_scaling_factor"])
    lab = np.asarray(labels)
    flat = lambda a: np.asarray(a).reshape(-1)[at]
    heads = []
    for h_in, lb in ((aux["sample"]["head_in"], lab[:, :-1]),
                     (aux["sample"]["mtp_head_in"], lab[:, 1:])):
        rows, head_logits = ok._head_program(cfg["compute_dtype"])(
            jnp.asarray(h_in), head, jnp.asarray(flat(lb)))
        if variant == "bf16":       # the head's logits kept in bfloat16
            hl = ok._bf16(head_logits)
            top = hl.max(axis=-1)
            picked = np.take_along_axis(hl, flat(lb)[:, None], -1)[:, 0]
            rows = low(np.stack([top + np.log(np.exp(
                hl - top[:, None]).sum(axis=-1)), picked], axis=-1))
        heads.append(np.asarray(rows, np.float64))
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "router_logits": logits, "router_scores": scores * 100.0,
        "router_weights": weights * 100.0,
        "head_rows": np.stack(heads)}.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (``olmoekit.adamw_leaf`` with
    this model's gains)."""
    return ok.adamw_leaf("ln1" if name.rsplit(".", 1)[-1] in GAINS
                         else "matrix", p, g, cfg)


# -- operations counted from the shapes -------------------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token meets in one layer of each kind, in
    the module's projection and in one use of the head; the held routed
    experts at the **mean** load (``experts_here`` / ``n_routed_experts``
    of a token's ``num_experts_per_tok`` slots land here)."""
    sizes = {k: v / max(1, n_sparse(cfg)) if k in SPARSE else v
             for k, v in leaf_sizes(cfg).items()}
    latent = sum(sizes[k] for k in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"))
    f, d = cfg["moe_intermediate_size"], cfg["hidden_size"]
    return {"latent_proj": latent, "router": sizes["router"],
            "shared": 3 * d * f * cfg["n_shared_experts"],
            "experts_mean": 3 * d * f * cfg["num_experts_per_tok"]
            * cfg["experts_here"] / cfg["n_routed_experts"],
            "dense_mlp": 3 * d * cfg["intermediate_size"],
            "mtp_proj": 2 * d * d, "head": d * cfg["vocab_here"]}


def n_attention_layers(cfg: dict) -> int:
    return cfg["layers_here"] + cfg["num_nextn_predict_layers"]


def attention_forward_flops(cfg: dict) -> float:
    """Causal latent attention's forward FLOP a step: q k^T over ``nope
    + rope`` and p v over ``v_head_dim``, the lower triangle only: 2 x b
    x heads x (192 + 128) x s^2 / 2 a layer, the module's too."""
    b, s = cfg["micro_batch"], cfg["seq_len"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    return float(b * cfg["num_attention_heads"] * width * s * s
                 * n_attention_layers(cfg))


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters
    a token meets x tokens, plus causal attention at three times its
    forward; the held routed experts **at the mean load** (a step whose
    held experts are hot does more, one where they are cold less: 2% of
    the step at the mean).  Recomputed layers (rematerialisation), the
    masked half of the diagonal blocks, recomputed scores and the
    optimiser's work are not model FLOP and lower the share."""
    tokens = cfg["micro_batch"] * cfg["seq_len"]
    per = matmul_params_per_token(cfg)
    routers = n_sparse(cfg) + cfg["num_nextn_predict_layers"]
    parts = {
        "latent_proj": 6.0 * per["latent_proj"] * tokens
        * n_attention_layers(cfg),
        "dense_mlp": 6.0 * per["dense_mlp"] * tokens
        * cfg["first_k_dense_replace"],
        "router": 6.0 * per["router"] * tokens * routers,
        "shared": 6.0 * per["shared"] * tokens * routers,
        "experts": 6.0 * per["experts_mean"] * tokens * routers,
        "mtp_proj": 6.0 * per["mtp_proj"] * tokens
        * cfg["num_nextn_predict_layers"],
        "head": 6.0 * per["head"] * tokens
        * (1 + cfg["num_nextn_predict_layers"]),
        "attention": 3.0 * attention_forward_flops(cfg)}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = attention_forward_flops(cfg)
    return parts
