"""What the ``train_step_kit`` call kind reads for Nemotron-3-Super: the
benchmark's own copy of the plain reference of its training step on one
chip's share of a deployment that is tensor-parallel by heads and
expert-parallel, written independently of the program
(``ompi_tpu.parallel``), what a check compares and in which units, and
the functions that count a step's model FLOP.  The batch (Zipf ids), the
probe and sample rules and the blocked head are ``harness/olmoekit``'s: a
kit states a model, not a second harness.

The equations are ``nemotron_h``'s (the published ``config.json``; the
mixer is Mamba-2's, arXiv:2405.21060).  Every layer is ``x <- x +
f(rmsnorm(x) * gain)`` with exactly one ``f``, by the layer's letter in
``hybrid_override_pattern``.  **M**: ``[z | xBC | dt] = u W_in``; ``xBC <-
silu(causal depthwise convolution over conv_kernel positions, with
bias)``, split into x (heads x mamba_head_dim), B, C (groups x
ssm_state_size); ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a
head; a head's state ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t
= h_t C_t + D x_t``, B and C shared by a group's heads; ``y <- rmsnorm
over each group of (y * silu(z)) * gain``; ``f = y W_out``.  **\\***: q, k,
v, o projections without bias, every key-value head read by
``num_attention_heads / num_key_value_heads`` query heads, causal softmax
in full, no rotary embedding.  **E**: ``s = sigmoid(u W_r)`` over all the
experts; the ``num_experts_per_tok`` largest of ``s + b`` (``b`` the
balancing bias: the choice only); weights ``routed_scaling_factor *
s_chosen / sum(s_chosen)``; ``f = (sum_k w_k relu(l W_up,k)^2 W_down,k)
W_lat_up + relu(u W_s_up)^2 W_s_down`` with ``l = u W_lat_down``.  After a
step ``b += gamma * sign(mean load - load)``.  Everything float32, every
matmul at the highest precision, no kernel, no sort, **the state-space
layer one position at a time** (no chunk's products).  Departures:

* **the share** (the configuration's ``mamba_heads_here``, ``heads_here``,
  ``experts_here``, ``expert_share``, ``vocab_here``): the held Mamba heads
  with their groups, the held query heads with the key-value heads they
  read, every held expert on every token under a dense mask of the
  router's choice among **all** the experts; what the absent heads and
  experts would add is left out; embedding, logits and loss are over the
  slice of the vocabulary;
* the multi-token-prediction module is left out (``mtp_here`` 0); the
  state is never reset between packed documents; the loads that move the
  bias are the whole batch's;
* at the published widths a state a position (4.3 GB a layer), the (s, s)
  scores of all heads, the (E, T, f) activations of all held experts, the
  (T, V) logits and eleven layers' activations do not fit beside the
  program's parameters, so the recurrence keeps the states of one block of
  ``chunk_size`` positions for its backward pass (the steps are the same,
  one a position), attention runs one head at a time, the experts one
  after the other, the head by blocks of rows, and every layer is
  recomputed in the backward pass (``lax.scan`` / ``lax.map`` /
  ``jax.checkpoint``).  The arithmetic of every element is the same; only
  what is held at once differs.
"""
from __future__ import annotations

import functools

import numpy as np

from harness import olmoekit as ok
from harness.olmoekit import (PROBE_UNIT, REGRET_UNIT, RMS_UNIT,  # noqa: F401
                              ROW_BLOCKS, SAMPLE_UNIT, load_config,
                              probe_positions, rank_order, sample_rows,
                              tokens_of, zipf_cdf)

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}
LAYER_LEAVES = {
    "mamba": ("norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "gate_norm", "out_proj"),
    "attn": ("ln1", "wq", "wk", "wv", "wo"),
    "moe": ("ln2", "router", "lat_down", "lat_up", "up", "down",
            "shared_up", "shared_down")}
UNDECAYED = ("norm", "gate_norm", "ln1", "ln2", "final_norm", "conv_b",
             "A_log", "D", "dt_bias")
WRONG = ("softmax", "bias_in_weights", "unnormalised", "rope",
         "relu_not_squared", "state_reset_at_blocks", "bc_per_head")
OUTPUTS = ("losses", "load_share", "local_share", "row_means",
           "route_regret", "bias", "grad_log_rms", "grad_probe")
PRECISION = ("router_logits", "router_scores", "router_weights",
             "head_rows", "ssm_y")
# the variants of ``precision_want`` that are controls (tools/kit_check.py)
PART_CONTROLS = ("bf16", "scan_bf16", "bias_in_weights", "softmax")
BIAS_UNIT = 1.0         # a balancing bias in units of gamma (joyaikit's)
# the embedding's gradient is compared by its RMS alone: it lies in the
# rows of the tokens that occur, a frequent token's row hundreds of times
# a rare one's, and an entry carries the bfloat16 error of the whole
# backward pass (a few percent) **of its row's size**, which is 1.2 of the
# leaf's RMS in a hot row (1.9 tolerances: seed 4200000713, PERF.md 2); a
# step reports no row's size to compare it by.  The head's gradient is as
# uneven but one matmul from its inputs: 0.48 at the widest
RMS_ONLY = ("embed",)
# a leaf whose largest probed entry is over this many RMS is probed in
# units of that entry (``compared``): PROBE_UNIT / HOT_ENTRY = 4 of it
HOT_ENTRY = 32.0
# the scan's result in units of SAMPLE_UNIT over this: a head's y is of
# order a tenth to one at initialisation, a sum of terms of either sign
# over as many positions as the head remembers, so an entry near zero
# carries the float32 sums' absolute error (up to 2e-6 on the chip,
# PERF.md 2): the tolerance's atol is 1e-5 of y
SSM_SCALE = 5.0


def segments(cfg: dict) -> list:
    """The held layers as runs of like layers, ``(unit, repeats, first
    layer)``: the rule by which the program's parameter tree is grouped
    (``parallel/train.ModelConfig.segments``), stated again: a unit is
    one letter or two different ones, the longest run wins."""
    first = cfg["first_layer_here"]
    pattern = cfg["hybrid_override_pattern"][first:first + cfg["layers_here"]]
    out, i = [], 0
    while i < len(pattern):
        unit, n = pattern[i], 1
        for width in (1, 2):
            cand = pattern[i:i + width]
            if len(set(cand)) != width:
                continue
            m = 1
            while pattern[i + m * width:i + (m + 1) * width] == cand:
                m += 1
            if m > 1 and m * width > len(unit) * n:
                unit, n = cand, m
        out.append((unit, n, i))
        i += len(unit) * n
    return out


def leaves(cfg: dict) -> tuple:
    """Every trained leaf's name, in the order the program reports them
    (``l<first layer>.<kind>.<leaf>``, stacked over a run's repeats)."""
    return ("embed",) + tuple(
        f"l{first}.{KINDS[c]}.{leaf}" for unit, _, first in segments(cfg)
        for c in unit for leaf in LAYER_LEAVES[KINDS[c]]) \
        + ("final_norm", "head")


def checked(cfg: dict) -> tuple:
    """The leaves whose gradients a check compares: of the first run
    that has the kind, every matrix and every scalar of a mixer, the
    router, both latent projections, the shared and the routed experts'
    matrices and attention's four; of the **last** layer of each kind the
    leaf nearest the residual stream (another run of the walk); head,
    final norm and embedding.  Between them their gradients cross every
    sublayer's backward pass, the scan's on both sides of its state."""
    runs = segments(cfg)
    out = []
    for kind in ("mamba", "moe", "attn"):
        mine = [first for unit, _, first in runs
                if kind in (KINDS[c] for c in unit)]
        out += [f"l{mine[0]}.{kind}.{leaf}" for leaf in LAYER_LEAVES[kind]
                if leaf not in ("norm", "ln1", "ln2")]
        last = {"mamba": "out_proj", "moe": "down", "attn": "wo"}[kind]
        if mine[-1] != mine[0]:
            out.append(f"l{mine[-1]}.{kind}.{last}")
    return tuple(out) + ("head", "final_norm", "embed")


def probed(cfg: dict) -> tuple:
    """The checked leaves whose gradient is also compared entry by
    entry: all but ``RMS_ONLY``."""
    return tuple(n for n in checked(cfg) if n not in RMS_ONLY)


def _path(name: str) -> tuple:
    parts = tuple(name.split("."))
    return ("layers",) + parts if len(parts) > 1 else parts


def leaf_of(params: dict, name: str):
    for k in _path(name):
        params = params[k]
    return params


def put_leaf(tree: dict, name: str, a) -> None:
    *groups, leaf = _path(name)
    for k in groups:
        tree = tree.setdefault(k, {})
    tree[leaf] = a


def tree_of(by_name: dict) -> dict:
    """The parameter tree from {leaf name: array}."""
    tree: dict = {}
    for name, a in by_name.items():
        put_leaf(tree, name, a)
    return tree


def held(cfg: dict) -> dict:
    """What of each layer this rank holds: Mamba heads and their groups,
    query heads and the key-value heads they read, experts."""
    nh = cfg["mamba_heads_here"] or cfg["mamba_num_heads"]
    q = cfg["heads_here"] or cfg["num_attention_heads"]
    per_kv = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return {"mamba_heads": nh,
            "groups": nh * cfg["n_groups"] // cfg["mamba_num_heads"],
            "q_heads": q, "kv_heads": max(1, q // per_kv),
            "experts": cfg["experts_here"] or cfg["n_routed_experts"],
            "first_expert": cfg["expert_share"] * cfg["experts_here"]}


def layer_sizes(cfg: dict) -> dict:
    """Elements of one layer's leaves, by kind."""
    d, here = cfg["hidden_size"], held(cfg)
    inner = here["mamba_heads"] * cfg["mamba_head_dim"]
    bc = 2 * here["groups"] * cfg["ssm_state_size"]
    hd = d // cfg["num_attention_heads"]
    lat, f, e = cfg["moe_latent_size"], cfg["moe_intermediate_size"], \
        here["experts"]
    fs = cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]
    return {
        "mamba": {"norm": d, "in_proj": d * (2 * inner + bc
                                             + here["mamba_heads"]),
                  "conv_w": cfg["conv_kernel"] * (inner + bc),
                  "conv_b": inner + bc, "dt_bias": here["mamba_heads"],
                  "A_log": here["mamba_heads"], "D": here["mamba_heads"],
                  "gate_norm": inner, "out_proj": inner * d},
        "attn": {"ln1": d, "wq": d * here["q_heads"] * hd,
                 "wk": d * here["kv_heads"] * hd,
                 "wv": d * here["kv_heads"] * hd,
                 "wo": here["q_heads"] * hd * d},
        "moe": {"ln2": d, "router": d * cfg["n_routed_experts"],
                "lat_down": d * lat, "lat_up": lat * d, "up": e * lat * f,
                "down": e * f * lat, "shared_up": d * fs,
                "shared_down": fs * d}}


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this rank holds."""
    per = layer_sizes(cfg)
    out = {"embed": cfg["vocab_here"] * cfg["hidden_size"]}
    for unit, n, first in segments(cfg):
        for c in unit:
            out.update({f"l{first}.{KINDS[c]}.{leaf}": n * size
                        for leaf, size in per[KINDS[c]].items()})
    out.update(final_norm=cfg["hidden_size"],
               head=cfg["hidden_size"] * cfg["vocab_here"])
    return out


# -- the reference -------------------------------------------------------------
def _recurrence(x, dt, a, b, c, block: int, wrong, low: bool):
    """The state-space layer one position at a time: x (bt, s, h, p), dt
    (bt, s, h), a (h,), b, c (bt, s, h, n) -> y (bt, s, h, p).  Blocks
    of ``block`` positions only bound what the backward pass holds: the
    state a position inside one block, the state between blocks.  With
    ``low`` (a control) the decay and the state are rounded to bfloat16
    at every position."""
    import jax
    import jax.numpy as jnp

    bt, s, h, p = x.shape
    n = b.shape[-1]
    pad = -s % block
    if pad:             # dt = 0: the state stays, the outputs are cut off
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))[:t.ndim])
                       for t in (x, dt, b, c))
    round_ = (lambda t: t.astype(jnp.bfloat16).astype(t.dtype)) if low \
        else (lambda t: t)

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs
        state = round_(round_(jnp.exp(dt_t * a))[..., None, None] * state
                       + (dt_t[..., None] * x_t)[..., None]
                       * b_t[..., None, :])
        return state, jnp.einsum("zhpn,zhn->zhp", state, c_t)

    @jax.checkpoint
    def one_block(state, xs):
        if wrong == "state_reset_at_blocks":
            state = state * 0
        return jax.lax.scan(step, state, xs)

    # (blocks, positions of a block, batch, ...)
    cut = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        (-1, block) + (bt,) + t.shape[2:])
    _, y = jax.lax.scan(one_block, jnp.zeros((bt, h, p, n), x.dtype),
                        (cut(x), cut(dt), cut(b), cut(c)))
    return jnp.moveaxis(y.reshape((s + pad, bt, h, p)), 0, 1)[:, :s]


def _mixer(p, x, cfg, wrong, low):
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    here = held(cfg)
    nh, g = here["mamba_heads"], here["groups"]
    hd, n, eps = cfg["mamba_head_dim"], cfg["ssm_state_size"], \
        cfg["layer_norm_epsilon"]
    inner = nh * hd
    zxd = ok._norm(x, p["norm"], eps) @ p["in_proj"]
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:-nh], zxd[..., -nh:]
    taps = cfg["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        padded[:, k:k + s] * p["conv_w"][k] for k in range(taps)))
    xs = xbc[..., :inner].reshape(b, s, nh, hd)
    bs = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
    cs = xbc[..., inner + g * n:].reshape(b, s, g, n)
    if wrong == "bc_per_head":      # a head reads the next group's B
        bs = jnp.roll(bs, 1, axis=-1)
    per = lambda t: jnp.repeat(t, nh // g, axis=2)
    y = _recurrence(xs, jax.nn.softplus(dt + p["dt_bias"]),
                    -jnp.exp(p["A_log"]), per(bs), per(cs),
                    cfg["chunk_size"], wrong, low)
    y = y + p["D"][:, None] * xs
    y = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return (y.reshape(b, s, inner) * p["gate_norm"]) @ p["out_proj"]


def _rope(x, theta):
    import jax.numpy as jnp

    hd, s = x.shape[-1], x.shape[-2]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return (x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1)
            * jnp.sin(ang)).astype(x.dtype)


def _attention(p, x, cfg, wrong):
    """Grouped-query attention in full, one (batch, head) at a time."""
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    here = held(cfg)
    nh, nkv = here["q_heads"], here["kv_heads"]
    h = ok._norm(x, p["ln1"], cfg["layer_norm_epsilon"])
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q = heads(h @ p["wq"], nh)
    k, v = (jnp.repeat(heads(h @ p[w], nkv), nh // nkv, axis=1)
            for w in ("wk", "wv"))
    if wrong == "rope":
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    hd = q.shape[-1]
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(qkv):
        qi, ki, vi = qkv
        sc = jnp.where(mask, (qi @ ki.T) / jnp.sqrt(hd).astype(qi.dtype),
                       -jnp.inf)
        return jax.nn.softmax(sc, axis=-1) @ vi

    flat = lambda t: t.reshape(b * nh, s, hd)
    o = jax.lax.map(one, (flat(q), flat(k), flat(v))).reshape(b, nh, s, hd)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def _experts(latent, weight, up, down, wrong):
    """Every held expert on every token's latent, weighted by ``weight``
    (T, E here), one expert after the other."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def one(acc, xs):
        u, d, w = xs
        act = jax.nn.relu(latent @ u)
        if wrong != "relu_not_squared":
            act = act * act
        return acc + w[:, None] * (act @ d), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(latent), (up, down, weight.T))
    return out


def _expert_layer(p, x, bias, cfg, wrong, routed):
    """(``f`` of an E layer, the slots every expert received, the
    routing's regret)."""
    import jax
    import jax.numpy as jnp

    b, s, d = x.shape
    e, k_top = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    h = ok._norm(x, p["ln2"], cfg["layer_norm_epsilon"]).reshape(b * s, d)
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
    here = held(cfg)
    first = here["first_expert"]
    y = _experts(h @ p["lat_down"],
                 weight[:, first:first + here["experts"]], p["up"],
                 p["down"], wrong) @ p["lat_up"]
    shared = jax.nn.relu(h @ p["shared_up"])
    y = y + (shared * shared) @ p["shared_down"]
    return y.reshape(b, s, d), jnp.sum(choice, axis=(0, 1)), regret


def loss_parts(params, tokens, labels, cfg: dict, bias: dict,
               wrong: str | None = None, routed=None, low: bool = False):
    """(loss, {losses, loads, rows, regret}) of one batch, in the
    parameters' own type throughout (float32; bfloat16 for the control).
    ``labels`` may be one longer than ``tokens`` (the batch's form for a
    model with a next-next-token head): the first ``s`` are read.
    ``bias`` {layers (L, E)}.  With ``routed`` (L, T, k), the experts a
    program chose, the top k is not taken here but given, and ``regret``
    says how far that choice is from this model's own under its own
    scores plus bias (``olmoekit.loss_parts``).  ``wrong`` names a
    deliberately wrong variant (``WRONG``), for the tests and controls
    that a comparison catches it; ``low`` holds the scan's decay and
    state in bfloat16."""
    import jax
    import jax.numpy as jnp

    b, s = tokens.shape
    x = params["embed"][tokens]
    mixer = jax.checkpoint(lambda p, x: x + _mixer(p, x, cfg, wrong, low))
    attend = jax.checkpoint(lambda p, x: x + _attention(p, x, cfg, wrong))
    expert = jax.checkpoint(lambda p, x, row, chosen: _expert_layer(
        p, x, row, cfg, wrong, chosen))
    loads, regrets = [], []
    for unit, n, first in segments(cfg):
        group = params["layers"][f"l{first}"]
        for i in range(n):
            for c in unit:
                p = {k: v[i] for k, v in group[KINDS[c]].items()}
                if c == "M":
                    x = mixer(p, x)
                elif c == "*":
                    x = attend(p, x)
                else:
                    j = len(loads)
                    y, load, regret = expert(
                        p, x, bias["layers"][j],
                        None if routed is None else routed[j])
                    x = x + y
                    loads.append(load)
                    regrets.append(regret)
    h = ok._norm(x, params["final_norm"], cfg["layer_norm_epsilon"]
                 ).reshape(b * s, -1)
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


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, wrt: tuple, wrong, low):
    import jax

    cfg = dict(cfg_items)

    def run(params, tokens, labels, bias, routed):
        diff = {n: leaf_of(params, n) for n in wrt}

        def loss(diff):
            merged = jax.tree.map(lambda a: a, params)      # a copy's dicts
            for n, a in diff.items():
                put_leaf(merged, n, a)
            return loss_parts(merged, tokens, labels, cfg, bias, wrong,
                              routed, low)

        with jax.default_matmul_precision("highest"):
            (_, aux), g = jax.value_and_grad(loss, has_aux=True)(diff)
        return aux, g

    return jax.jit(run)


def reference_step(params, tokens, labels, cfg: dict, bias: dict,
                   wrt: tuple, wrong: str | None = None, routed=None,
                   low: bool = False) -> dict:
    """One step's statistics from the reference, in the form
    ``step_stats`` puts a program's in: ``losses``, ``loads``, ``rows``,
    ``regret``, ``bias`` (after the update), and for each leaf of ``wrt``
    its gradient's ``grad_sq`` and ``grad_probe``; ``grads`` holds the
    whole gradients of ``wrt``.  Parameters given in bfloat16 make the
    **control**: the same model computed throughout in the nearest
    precision below the one the configuration states."""
    import jax.numpy as jnp

    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    aux, g = _grad_program(items, tuple(wrt), wrong, low)(
        params, tokens, labels, bias, routed)
    aux, g = ({k: v.astype(jnp.float32) for k, v in t.items()}
              for t in (aux, g))
    flat = {n: g[n].reshape(-1) for n in wrt}
    f32 = {k: v.astype(jnp.float32) for k, v in bias.items()}
    return {**aux, "grads": g, "bias": bias_step(f32, aux["loads"], cfg),
            "grad_sq": {n: jnp.sum(f * f) for n, f in flat.items()},
            "grad_probe": {n: f[probe_positions(n, f.shape[0])]
                           for n, f in flat.items()}}


def step_stats(aux: dict, bias_after: dict, cfg: dict) -> dict:
    """A program step's ``aux`` (``parallel/train.py``: raw statistics)
    and the biases its state holds after it, in the reference's form.  A
    step routes every token to its own top k, so its regret is 0 by
    definition."""
    out = {k: np.asarray(aux[k]) for k in ("loads", "rows")}
    out["losses"] = np.asarray(aux["losses"])[:2]       # total, ce
    out["regret"] = np.zeros(out["loads"].shape[:1], np.float32)
    out["bias"] = np.asarray(bias_after["layers"])
    for k in ("grad_sq", "grad_probe"):
        out[k] = dict(zip(leaves(cfg), np.asarray(aux[k])))
    return out


def compared(stats: dict, cfg: dict, wrt: tuple) -> dict:
    """What a check compares of one step's statistics, each in its unit
    (``olmoekit``'s constants): the loss and the cross-entropy as they
    are; the share of a layer's slots every one of all the experts
    received, and the held experts' together; the head's logsumexp and
    label logit averaged over quarters of the rows; the routing's
    regret; the biases after the update in units of gamma; and for the
    leaves of ``wrt`` the gradient's RMS as log10 over ``RMS_UNIT`` and,
    but for ``RMS_ONLY``, its probed entries in units of ``PROBE_UNIT``
    RMS, or of ``PROBE_UNIT / HOT_ENTRY`` times the largest of them where
    that is more: a leaf whose gradient is uneven (the head's columns and
    the router's follow the labels' and the experts' counts) has entries
    tens of times its RMS, and bfloat16's relative error on one of those
    is no fault of the step's; at 4 times the largest probed entry it is
    under a fifth of a tolerance, and an entry wrong by its own size is
    still fifty."""
    rows = np.asarray(stats["rows"], np.float32)
    sizes = leaf_sizes(cfg)
    rms = np.sqrt([float(stats["grad_sq"][n]) / sizes[n] for n in wrt])
    entries = [i for i, n in enumerate(wrt) if n not in RMS_ONLY]
    probe = np.stack([np.asarray(stats["grad_probe"][wrt[i]])
                      for i in entries])
    scale = PROBE_UNIT * np.maximum(rms[entries],
                                    np.abs(probe).max(axis=1) / HOT_ENTRY)
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
        "grad_log_rms": np.log10(rms) / RMS_UNIT,
        "grad_probe": probe / scale[:, None]}.items()}


# -- the float32 parts of a step, read from the step alone -----------------------
def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made at ``sample_rows``, in units of
    ``SAMPLE_UNIT``: the routers' logits, their sigmoid scores and the
    chosen weights (times 100, so that a step of 1e-4 is one unit), the
    head's logsumexp and label logit, and what every Mamba layer's scan
    made of its first held head (times ``SSM_SCALE``)."""
    s = aux["sample"]
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "router_logits": s["router_logits"],
        "router_scores": np.asarray(s["router_scores"]) * 100.0,
        "router_weights": np.asarray(s["router_weights"]) * 100.0,
        "head_rows": np.asarray(aux["rows"])[at],
        "ssm_y": np.asarray(s["ssm_y"]) * SSM_SCALE}.items()}


def scan_rows(dt, x, b, c, a, at, sequences: int, low) -> np.ndarray:
    """The first head's ``y`` (M, rows ``at``, p) of every Mamba layer
    from what its scan read: dt (M, T), x (M, T, p), b, c (M, T, n), a
    (M,), ``sequences`` of T / sequences positions each from a zero
    state; the recurrence one position at a time in float64, decay and
    state through ``low`` at every position."""
    m, t, p = x.shape
    length = t // sequences
    out = np.zeros((m, t, p))
    for seq in range(sequences):
        state = np.zeros((m, p, b.shape[-1]))
        for i in range(seq * length, (seq + 1) * length):
            state = low(low(np.exp(dt[:, i] * a))[:, None, None] * state
                        + (dt[:, i, None] * x[:, i])[:, :, None]
                        * b[:, i, None, :])
            out[:, i] = np.einsum("mpn,mn->mp", state, c[:, i])
    return out[:, at]


def precision_want(aux: dict, by_name: dict, bias_before, head, labels,
                   cfg: dict, variant: str | None = None) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own
    inputs to each part** at the precision the configuration states: the
    routers' logits from the rows each router read and its weights
    (``by_name``: every leaf on the host) in float64; the sigmoid scores
    from the step's own logits and the chosen weights from those scores
    at the step's own choice, in float64; the head's rows from the rows
    the head read (``olmoekit._head_program``: inputs rounded to the
    compute type, every product exact); the scans' results from the
    step's own dt, x, B and C of the first held head, the recurrence one
    position at a time in float64.  ``variant`` gives a **control**,
    which has to lie outside: ``bf16`` (router, scores, weights and head
    as a bfloat16 implementation would have made them), ``scan_bf16``
    (the scan's decay and state held in bfloat16), ``bias_in_weights``
    (the weights taken from score + bias), ``softmax`` (scores by a
    softmax over the experts)."""
    import jax.numpy as jnp

    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()}
    exact = lambda a: np.asarray(a, np.float64)
    low = ok._bf16 if variant == "bf16" else exact
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    of_kind = lambda kind, leaf: np.concatenate([
        np.asarray(by_name[f"l{first}.{kind}.{leaf}"], np.float64)
        for unit, _, first in segments(cfg)
        if kind in (KINDS[c] for c in unit)])
    logits = low(np.einsum("lrd,lde->lre", low(s["router_in"]),
                           low(of_kind("moe", "router"))))
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
    y = scan_rows(s["ssm_dt_seq"], s["ssm_x_seq"], s["ssm_b_seq"],
                  s["ssm_c_seq"], -np.exp(of_kind("mamba", "A_log")[:, 0]),
                  at, cfg["micro_batch"],
                  ok._bf16 if variant == "scan_bf16" else exact)
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "router_logits": logits, "router_scores": scores * 100.0,
        "router_weights": weights * 100.0,
        "head_rows": np.asarray(rows, np.float64),
        "ssm_y": y * SSM_SCALE}.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (``olmoekit.adamw_leaf`` with
    this model's undecayed leaves)."""
    return ok.adamw_leaf("ln1" if name.rsplit(".", 1)[-1] in UNDECAYED
                         else "matrix", p, g, cfg)


# -- operations counted from the shapes -------------------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token meets in one layer of each kind and
    in the head; the held routed experts at the **mean** load
    (``experts_here`` / ``n_routed_experts`` of a token's
    ``num_experts_per_tok`` slots land here)."""
    per = layer_sizes(cfg)
    here = held(cfg)
    return {"mamba_proj": per["mamba"]["in_proj"] + per["mamba"]["out_proj"],
            "attn_proj": sum(per["attn"][k] for k in ("wq", "wk", "wv", "wo")),
            "router": per["moe"]["router"],
            "latent_proj": per["moe"]["lat_down"] + per["moe"]["lat_up"],
            "shared": per["moe"]["shared_up"] + per["moe"]["shared_down"],
            "experts_mean": 2 * cfg["moe_latent_size"]
            * cfg["moe_intermediate_size"] * cfg["num_experts_per_tok"]
            * here["experts"] / cfg["n_routed_experts"],
            "head": cfg["hidden_size"] * cfg["vocab_here"]}


def attention_forward_flops(cfg: dict) -> float:
    """Causal attention's forward FLOP a step: q k^T and p v over
    ``head_dim``, the lower triangle only: 2 x b x held query heads x 2
    x head_dim x s^2 / 2 an attention layer."""
    first = cfg["first_layer_here"]
    pattern = cfg["hybrid_override_pattern"][first:first + cfg["layers_here"]]
    b, s = cfg["micro_batch"], cfg["seq_len"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return float(b * held(cfg)["q_heads"] * 2 * hd * s * s
                 * pattern.count("*"))


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters
    a token meets x tokens; causal attention at three times its forward;
    the state-space layers by the **recurrence's** count (a position and
    head: decay and add of the (p, n) state and its product with C, 6 p n
    forward, three times that a step), not by the chunked form's
    products; the held routed experts **at the mean load**.  Recomputed
    layers, the chunked scan's extra products, the masked half of
    diagonal attention blocks and the optimiser's work are not model
    FLOP and lower the share."""
    first = cfg["first_layer_here"]
    pattern = cfg["hybrid_override_pattern"][first:first + cfg["layers_here"]]
    n_m, n_a, n_e = (pattern.count(c) for c in "M*E")
    tokens = cfg["micro_batch"] * cfg["seq_len"]
    per = matmul_params_per_token(cfg)
    parts = {
        "mamba_proj": 6.0 * per["mamba_proj"] * tokens * n_m,
        "ssm_scan": 3.0 * 6.0 * held(cfg)["mamba_heads"]
        * cfg["mamba_head_dim"] * cfg["ssm_state_size"] * tokens * n_m,
        "attn_proj": 6.0 * per["attn_proj"] * tokens * n_a,
        "attention": 3.0 * attention_forward_flops(cfg),
        "router": 6.0 * per["router"] * tokens * n_e,
        "latent_proj": 6.0 * per["latent_proj"] * tokens * n_e,
        "shared": 6.0 * per["shared"] * tokens * n_e,
        "experts": 6.0 * per["experts_mean"] * tokens * n_e,
        "head": 6.0 * per["head"] * tokens}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = attention_forward_flops(cfg)
    return parts


# the published period (layers 27 to 37 of the 88), for a reader of this
# file; a check asks ``leaves(cfg)`` and ``checked(cfg)`` of its own
# configuration, which a rehearsal cuts
_PERIOD = {"hybrid_override_pattern": "MEMEMEMEM*E", "first_layer_here": 0,
           "layers_here": 11}
LEAVES = leaves(_PERIOD)
CHECKED = checked(_PERIOD)
