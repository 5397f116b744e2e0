"""What the ``train_step_kit`` call kind reads for SmallThinker-21BA3B: the
benchmark's own copy of the plain reference of its training step on one
chip's share of a 4-chip expert-parallel deployment, written independently
of the program (``ompi_tpu.parallel``), what a check compares and in which
units, and the functions that count a step's model FLOP.  The batch (Zipf
ids), the probe and sample rules, RoPE and the blocked head are
``harness/olmoekit``'s, a grouped tree's leaf by its name
``harness/nemotronkit``'s: a kit states a model, not a second harness.

The equations are the published ``config.json``'s of
PowerInfer/SmallThinker-21BA3B-Instruct and its report's
(arXiv:2507.20984).  ``norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) *
gain``; no bias anywhere.  Layer ``l`` on the residual stream ``x``: ``r =
x W_router``, 64 logits a token **read from the layer's input, before the
attention sublayer and its norm**; ``h = norm_1(x)``; q, k, v = ``h W_q``,
``h W_k``, ``h W_v`` on 28 query and 4 key-value heads of 128, no QK-norm;
where ``rope_layout[l]`` is 1 RoPE (rotate-half, the whole head, theta
1.5e6) on q and k, where it is 0 **none**; causal ``softmax(q k^T /
sqrt(128)) v`` in which key j is visible to query i iff ``0 <= i - j``, and
where ``sliding_window_layout[l]`` is 1 also ``i - j < 4096``; ``x <- x + o
W_o``; ``h2 = norm_2(x)``; ``p = softmax(r)``, the 6 largest, weights
``p_chosen / sum(p_chosen)``; ``x <- x + sum_e w_e W_down,e(relu(W_gate,e
h2) * W_up,e h2)``: ReGLU experts 768 wide, no shared one, every layer
sparse, no balancing bias.  After the last layer one ``norm``, then an
untied head.  The loss is the cross-entropy plus ``aux_loss_coef`` times
HF's load-balancing loss over every layer's rows in one mean.  Everything
float32, every matmul at the highest precision, no kernel, no sort, the
window as its inequality in a dense mask.  Departures:

* **the share** (the configuration's ``experts_here``, ``expert_share``,
  ``vocab_here``): every held expert on every token under a dense mask of
  the router's choice among **all** the experts; what the absent experts
  would add is left out; attention, routers and norms are whole;
  embedding, logits and loss are over the slice of the vocabulary;
* attention is never masked between packed documents; the family's
  "secondary experts" are no key of the published configuration;
* at the published widths the (s, s) scores of a head, the (E, T, f)
  activations of all held experts, the (T, V) logits and four layers'
  activations do not fit beside the program's parameters, so attention
  runs one (batch, head, block of query rows) at a time against every key
  under the mask, the experts one after the other, the head by blocks of
  rows, and every layer is recomputed in the backward pass (``lax.map`` /
  ``lax.scan`` / ``jax.checkpoint``).  The arithmetic of every element is
  the same; only what is held at once differs.
"""
from __future__ import annotations

import functools

import numpy as np

from harness import olmoekit as ok
from harness.nemotronkit import leaf_of, put_leaf, tree_of  # noqa: F401
from harness.olmoekit import (PROBE_UNIT, ROW_BLOCKS,  # noqa: F401
                              SAMPLE_UNIT, probe_positions, rank_order,
                              sample_rows, tokens_of, zipf_cdf)

# a layer's letter by its kind of attention (every layer is sparse), and
# the group it goes by in the program's parameter tree
KINDS = {"A": "attn_moe", "W": "swa_moe"}
LAYER = ("ln1", "wq", "wk", "wv", "wo", "ln2", "router", "gate", "up",
         "down")
UNDECAYED = ("ln1", "ln2", "final_norm")
WRONG = ("no_window", "rope_full", "no_rope_window", "router_post", "silu",
         "unnormalised")
OUTPUTS = ("losses", "load_share", "local_share", "row_means",
           "route_regret", "grad_log_rms", "grad_probe")
PRECISION = ("router_logits", "router_scores", "router_weights",
             "head_rows", "rope_qk", "window_o", "expert_out")
# the variants of ``precision_want`` that are controls (tools/kit_check.py)
PART_CONTROLS = ("bf16", "no_window", "rope_full", "no_rope_window",
                 "router_post", "silu", "unnormalised")
# the embedding's gradient is compared by its RMS alone (``nemotronkit``
# says why)
RMS_ONLY = ("embed",)
# Every unit below was read on the chip at the published widths with the
# embedding's rows at the file's ``start.embed_init_std`` 2.0 (PERF.md 2; my
# chip runs, PR 63: ``tools/kit_check.py --dump``, 6 + 12 seeds).  Until
# then the rows were drawn at 0.02, the routers of layers 1 to 3 collapsed
# (the fullest expert 7 to 10 times the mean) and several units stood wide
# for that; with rows of 2.0 the fullest expert takes 1.9 to 2.8 times the
# mean in every layer (a Zipf law's head routes as one), and they do not.
#
# a gradient's RMS as log10 over this: ``olmoekit.RMS_UNIT``'s 4, a limit
# of 4.7% of an RMS.  The program's RMS lies within 0.24% of the
# reference's on every leaf (0.05 at the widest, a router), the reference
# in bfloat16 within 0.26-1.2%: an RMS tells a wrong gradient, no precision
RMS_UNIT = 4.0
# a leaf whose largest probed entry is over this many RMS is probed in
# units of that entry (``qwen3nextkit.HOT_ENTRY``, 8 there): most checked
# leaves have one at 4 to 12 RMS.  ``gate``'s gradient passes relu's kink,
# where bfloat16 and float32 take a pre-activation near zero for different
# signs: the full layer's ``gate`` reads 0.23 and 0.50 on two of six
# seeds, every other leaf at most 0.07 on all, the reference in bfloat16
# 0.04-0.22.  The probes tell a wrong gradient, no precision
HOT_ENTRY = 4.0
# a routing regret in units of this many k-th probabilities
# (``olmoekit``'s).  The routers read the un-normed stream, whose entries
# are the token's own row's (of order 2): a logit is of order 2 and the
# sixth and seventh probabilities part as in any other model of the
# benchmark.  The step's choice costs at most 0.94% of the sixth
# probability under the reference's own scores (0.06 here), the reference
# in bfloat16 2.9-4.0% more than that (0.18-0.25)
REGRET_UNIT = 32.0
# the total, the cross-entropy and the head's mean logsumexp, each **less
# the loss of a uniform guess**, ln(``vocab_here``) = 10.545, in units of
# the tolerance over this.  All three are of order eleven, where the
# tolerance's rtol alone is 4.1e-3 of them whatever the scale; what a run
# at initialisation has to get right is the half by which they exceed
# ln V.  Read raw: the program lies at most 1.1e-4 from the reference in
# its total and cross-entropy and 1.3e-5 in a quarter's mean logsumexp; the
# reference in bfloat16 2.2e-3 to 3.7e-3 in a mean logsumexp on every seed
# (its own total is rounded to a bfloat16 step of 0.0625 and lies 1e-3 to
# 3e-2 off by chance: with ln V left in, at a limit of 4.7e-3, one seed in
# six read 1.05 and one in about fifteen would pass).  At 8 the limit is
# 8.1e-4: the program reads 0.14, the control 2.7 at the narrowest
LOSS_SCALE = 8.0
# the auxiliary loss over its coefficient (E sum_e f_e P_e, 6.13 to 6.21
# here where an even router gives 6) over this: ``qwen3nextkit``'s 3 for
# the same sum.  It no longer rests on a few experts' probabilities: the
# program lies at most 4.7e-5 from the reference (0.012), the reference in
# bfloat16 4.5e-3 to 2.0e-2 (1.1-5.0) on five seeds of six and 2.6e-5 on
# one (its sum is a bfloat16 number too); a wrong router or wrong weights
# move it by tenths
AUX_SCALE = 3.0
# the label's logit averaged over a quarter of the rows (a few hundredths,
# either sign: the tolerance's atol does the work) over this: the program
# lies at most 1.4e-4 from the reference and the reference in bfloat16 no
# farther (5e-5 to 2.5e-4), so it tells no precision; it tells a head read
# at the wrong rows.  At 4 the limit is 1.25e-3: the program reads 0.12
LABEL_SCALE = 4.0
# a head's q and k as attention reads them in units of SAMPLE_UNIT over
# this (``qwen3nextkit.ROPE_SCALE``: positions to 16,383, where one more
# bit of a float32 inverse frequency is 8e-4 rad, and an entry that no norm
# has bounded is up to 5).  Read on the chip at 0.0125: the program
# 0.41-0.64 in four checks, RoPE on the full layer or off a window layer
# 1,312 at the narrowest: at 0.005 they read 0.26 and 525; with the rows at
# 2.0 (PR 63) 0.21-0.31 and 525 in six checks: a normed entry is what it was
ROPE_SCALE = 0.005
# the first head's window output in units of SAMPLE_UNIT over this: o is a
# mean of up to 4,096 rows of v, of order a few hundredths, and the kernel
# rounds p to bfloat16 for p v (2^-9 of a term; over the 1,500 keys that
# carry a row's weight that is 3e-5 of an entry, 1e-4 at the widest of a
# check's 6,144 entries).  Read on the chip at 1: the program 1.53-2.38 in
# four checks, a window layer attending in full 5,024 at the narrowest; at
# 0.1 0.24-0.42 in 50 more and 607: at 0.05 they read 0.21 and 300; with the
# rows at 2.0 (PR 63) 0.20-0.32 and 151 in six checks
WINDOW_SCALE = 0.05
# the held experts' weighted sum at the sampled rows in units of
# SAMPLE_UNIT over this: of order a tenth to one.  The want rounds where
# the program does (rows, matrices and the hidden product to bfloat16), so
# it agrees to 1e-5 of the sum until a hidden entry rounds to the other
# neighbour (a float32 sum's last bits decide: some ten of a check's
# 77,000), which is worth a whole bfloat16 step of that entry times a row
# of W_down: read on the chip at a scale of 1 (PERF.md 2) 0.11-1.6 in 29
# checks and 20.9 in one (1.2e-3 of the sum), a tail that needs room.  A
# want that keeps the hidden product exact has no such tail and lies 1.5e-3
# to 2.6e-3 away in every check, more with every step taken: worse.  silu
# in relu's place lies 0.15 away at the narrowest.  At 0.01 the limit is
# 5e-3 of the sum: the tail reads 0.24, silu 25; with the rows at 2.0 (PR 63)
# 0.000-0.005 in five checks, 0.11 in one, silu 50 at the narrowest
EXPERT_SCALE = 0.01
#: query rows of one head that attention scores at once
ATTN_ROWS = 2048


def load_config(path: str) -> dict:
    """The configuration file as the reference reads it (``olmoekit``'s:
    the published keys, ``layers_here`` and the ``train`` group, flat),
    with the experts' number also under the names the harness reads them
    by."""
    cfg = ok.load_config(path)
    return {**cfg, "num_experts": cfg["moe_num_primary_experts"],
            "n_routed_experts": cfg["moe_num_primary_experts"],
            "num_experts_per_tok": cfg["moe_num_active_primary_experts"]}


def pattern(cfg: dict) -> str:
    """The held layers' letters: ``W`` where ``sliding_window_layout`` is
    1, else ``A``; every layer holds a router."""
    first = cfg["first_layer_here"]
    return "".join("W" if on else "A" for on in cfg["sliding_window_layout"][
        first:first + cfg["layers_here"]])


def turned(cfg: dict) -> list:
    """Whether RoPE turns q and k, a held layer each (``rope_layout``)."""
    first = cfg["first_layer_here"]
    return [bool(on) for on in cfg["rope_layout"][
        first:first + cfg["layers_here"]]]


def segments(cfg: dict) -> list:
    """The held layers as runs of like layers, ``(letter, repeats, first
    layer)``: the rule by which the program's parameter tree is grouped,
    stated again."""
    out = []
    for i, c in enumerate(pattern(cfg)):
        if out and out[-1][0] == c:
            out[-1][1] += 1
        else:
            out.append([c, 1, i])
    return [tuple(run) for run in out]


def leaves(cfg: dict) -> tuple:
    """Every trained leaf's name, in the order the program reports them
    (``l<first layer>.<kind>.<leaf>``, stacked over a run's repeats)."""
    return ("embed",) + tuple(
        f"l{first}.{KINDS[c]}.{leaf}" for c, _, first in segments(cfg)
        for leaf in LAYER) + ("final_norm", "head")


def checked(cfg: dict) -> tuple:
    """The leaves whose gradients a check compares: attention's four
    matrices and the router of every run (the full layer's and the window
    run's: both kinds of mask and both of RoPE lie behind them); the held
    experts' three of the runs of **one** layer (the full layer here: a
    run of three layers' are 1.1 GB of float32 gradients, which do not fit
    beside the reference's own arrays); final norm, head and embedding."""
    out = []
    for c, n, first in segments(cfg):
        name = lambda leaf: f"l{first}.{KINDS[c]}.{leaf}"
        out += [name(leaf) for leaf in ("wq", "wk", "wv", "wo", "router")]
        if n == 1:
            out += [name(leaf) for leaf in ("gate", "up", "down")]
    return tuple(out) + ("final_norm", "head", "embed")


def probed(cfg: dict) -> tuple:
    """The checked leaves whose gradient is also compared entry by entry:
    all but ``RMS_ONLY``."""
    return tuple(n for n in checked(cfg) if n not in RMS_ONLY)


def held(cfg: dict) -> dict:
    """The experts this rank holds."""
    return {"experts": cfg["experts_here"] or cfg["num_experts"],
            "first_expert": cfg["expert_share"] * cfg["experts_here"]}


def layer_sizes(cfg: dict) -> dict:
    """Elements of one layer's leaves."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f, e = cfg["moe_ffn_hidden_size"], held(cfg)["experts"]
    return {"ln1": d, "wq": d * q, "wk": d * kv, "wv": d * kv, "wo": q * d,
            "ln2": d, "router": d * cfg["num_experts"], "gate": e * d * f,
            "up": e * d * f, "down": e * f * d}


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this rank holds."""
    per = layer_sizes(cfg)
    out = {"embed": cfg["vocab_here"] * cfg["hidden_size"]}
    for c, n, first in segments(cfg):
        out.update({f"l{first}.{KINDS[c]}.{leaf}": n * size
                    for leaf, size in per.items()})
    out.update(final_norm=cfg["hidden_size"],
               head=cfg["hidden_size"] * cfg["vocab_here"])
    return out


# -- the reference -------------------------------------------------------------
def _attention_rows(q, k, v, window: int):
    """Causal softmax attention of q, k, v (b, h, s, hd), one (batch, head)
    and ``ATTN_ROWS`` query rows at a time against every key under the
    mask: key j visible to query i iff ``0 <= i - j`` and, with a window,
    ``i - j < window``."""
    import jax
    import jax.numpy as jnp

    b, h, s, hd = q.shape
    rows = min(ATTN_ROWS, s)
    keys = jnp.arange(s)

    def one_head(qkv):
        qi, ki, vi = qkv

        @jax.checkpoint
        def one_block(xs):
            qb, first = xs
            sc = (qb @ ki.T) / jnp.sqrt(hd).astype(qb.dtype)
            away = (first + jnp.arange(rows))[:, None] - keys[None, :]
            mask = (away >= 0) & ((away < window) if window else True)
            return jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1) @ vi

        return jax.lax.map(one_block, (
            qi.reshape(s // rows, rows, hd),
            jnp.arange(0, s, rows))).reshape(s, -1)

    flat = lambda t: t.reshape(b * h, s, -1)
    return jax.lax.map(one_head, (flat(q), flat(k), flat(v))
                       ).reshape(b, h, s, -1)


def _attention(p, x, cfg, window: bool, rope: bool):
    """The attention sublayer, without the residual add."""
    import jax.numpy as jnp

    b, s, _ = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h = ok._norm(x, p["ln1"], cfg["rms_norm_eps"])
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q, k, v = (heads(h @ p[w], n) for w, n in (
        ("wq", nh), ("wk", nkv), ("wv", nkv)))
    if rope:
        q, k = ok._rope(q, cfg["rope_theta"]), ok._rope(k, cfg["rope_theta"])
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    o = _attention_rows(q, k, v, cfg["sliding_window_size"] if window else 0)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]


def _experts(h, weight, gate, up, down, act):
    """Every held expert on every token, weighted by ``weight`` (T, E),
    one expert after the other (``qwen3nextkit._experts``)."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def one(h, g, u, d, w):
        return w[:, None] * ((act(h @ g) * (h @ u)) @ d)

    out, _ = jax.lax.scan(lambda acc, xs: (acc + one(h, *xs), None),
                          jnp.zeros_like(h), (gate, up, down, weight.T))
    return out


def _route(p, rows, cfg, wrong, routed):
    """(the weight every expert has on every token (T, E), the slots every
    expert received, the probabilities' sum an expert, the routing's
    regret) of the rows the router reads."""
    import jax
    import jax.numpy as jnp

    e, k_top = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(rows @ p["router"], -1)
    own, top_e = jax.lax.top_k(probs, k_top)
    regret = jnp.zeros((), rows.dtype)
    if routed is not None:
        under = jnp.take_along_axis(probs, routed, axis=-1)
        regret = jnp.max((own[:, -1] - jnp.min(under, axis=-1))
                         / (REGRET_UNIT * own[:, -1]))
        top_e = routed
    chosen = jnp.take_along_axis(probs, top_e, axis=-1)
    if cfg["norm_topk_prob"] and wrong != "unnormalised":
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    choice = jax.nn.one_hot(top_e, e, dtype=rows.dtype)         # (T, k, E)
    return jnp.einsum("tk,tke->te", chosen, choice), \
        jnp.sum(choice, axis=(0, 1)), jnp.sum(probs, axis=0), regret


def _layer(p, x, cfg, letter, rope, wrong, routed):
    """(the layer's output, the slots every expert received, the
    probabilities' sum an expert, the routing's regret)."""
    import jax

    b, s, d = x.shape
    window = letter == "W" and wrong != "no_window"
    if wrong == "rope_full" and letter == "A":
        rope = True
    if wrong == "no_rope_window" and letter == "W":
        rope = False
    rows_in = x.reshape(b * s, d)       # the router's: before attention
    x = x + _attention(p, x, cfg, window, rope)
    h = ok._norm(x, p["ln2"], cfg["rms_norm_eps"]).reshape(b * s, d)
    weight, load, prob_sum, regret = _route(
        p, h if wrong == "router_post" else rows_in, cfg, wrong, routed)
    here = held(cfg)
    first = here["first_expert"]
    y = _experts(h, weight[:, first:first + here["experts"]], p["gate"],
                 p["up"], p["down"],
                 jax.nn.silu if wrong == "silu" else jax.nn.relu)
    return x + y.reshape(b, s, d), load, prob_sum, regret


def loss_parts(params, tokens, labels, cfg: dict, wrong: str | None = None,
               routed=None):
    """(total, {losses, loads, rows, regret}) of one batch, in the
    parameters' own type throughout (float32; bfloat16 for the control).
    ``labels`` may be one longer than ``tokens``: the first ``s`` are read.
    With ``routed`` (L, T, k), the experts a program chose, the top k is
    not taken here but given, and ``regret`` says how far that choice is
    from this model's own under its own probabilities.  ``losses`` holds
    the total, the cross-entropy and the auxiliary loss as weighted into
    the total.  ``wrong`` names a deliberately wrong variant (``WRONG``),
    for the tests that a comparison catches it."""
    import jax
    import jax.numpy as jnp

    b, s = tokens.shape
    x = params["embed"][tokens]
    ropes = turned(cfg)
    loads, prob_sums, regrets = [], [], []
    for c, n, first in segments(cfg):
        group = params["layers"][f"l{first}"][KINDS[c]]
        for i in range(n):
            j = len(loads)
            run = jax.checkpoint(functools.partial(
                _layer, cfg=cfg, letter=c, rope=ropes[j], wrong=wrong))
            x, load, prob_sum, regret = run(
                {k: v[i] for k, v in group.items()}, x,
                routed=None if routed is None else routed[j])
            loads.append(load)
            prob_sums.append(prob_sum)
            regrets.append(regret)
    h = ok._norm(x, params["final_norm"], cfg["rms_norm_eps"]
                 ).reshape(b * s, -1)
    rows = ok._head(h, params["head"], labels[:, :s].reshape(b * s),
                    cfg.get("loss_block_rows", 1024))
    ce = jnp.mean(rows[:, 0] - rows[:, 1])
    loads = jnp.stack(loads)
    routed_rows = loads.shape[0] * b * s    # every layer's rows in one mean
    aux = jnp.asarray(cfg["aux_loss_coef"] * cfg["num_experts"], x.dtype) \
        * jnp.sum((jnp.sum(loads, 0) / routed_rows)
                  * (jnp.sum(jnp.stack(prob_sums), 0) / routed_rows))
    total = ce + aux
    return total, {"losses": jnp.stack([total, ce, aux]), "loads": loads,
                   "rows": rows, "regret": jnp.stack(regrets)}


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, wrt: tuple, wrong):
    import jax

    cfg = dict(cfg_items)       # the two layouts as tuples: hashable

    def run(params, tokens, labels, routed):
        diff = {n: leaf_of(params, n) for n in wrt}

        def loss(diff):
            merged = jax.tree.map(lambda a: a, params)      # a copy's dicts
            for n, a in diff.items():
                put_leaf(merged, n, a)
            return loss_parts(merged, tokens, labels, cfg, wrong, routed)

        with jax.default_matmul_precision("highest"):
            (_, aux), g = jax.value_and_grad(loss, has_aux=True)(diff)
        return aux, g

    return jax.jit(run)


def reference_step(params, tokens, labels, cfg: dict, bias: dict,
                   wrt: tuple, wrong: str | None = None,
                   routed=None) -> dict:
    """One step's statistics from the reference, in the form
    ``step_stats`` puts a program's in: ``losses``, ``loads``, ``rows``,
    ``regret``, and for each leaf of ``wrt`` its gradient's ``grad_sq``
    and ``grad_probe``; ``grads`` holds the whole gradients of ``wrt``.
    ``bias`` is the kind's: this model's routers choose under none, and it
    is not read.  Parameters given in bfloat16 make the **control**: the
    same model computed throughout in the nearest precision below the one
    the configuration states."""
    import jax.numpy as jnp

    items = tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
        if isinstance(v, (int, float, bool, str))
        or k in ("sliding_window_layout", "rope_layout")))
    aux, g = _grad_program(items, tuple(wrt), wrong)(
        params, tokens, labels, routed)
    aux, g = ({k: v.astype(jnp.float32) for k, v in t.items()}
              for t in (aux, g))
    flat = {n: g[n].reshape(-1) for n in wrt}
    return {**aux, "grads": g,
            "grad_sq": {n: jnp.sum(f * f) for n, f in flat.items()},
            "grad_probe": {n: f[probe_positions(n, f.shape[0])]
                           for n, f in flat.items()}}


def step_stats(aux: dict, bias_after: dict, cfg: dict) -> dict:
    """A program step's ``aux`` (``parallel/train.py``: raw statistics) in
    the reference's form (``bias_after``, the kind's, holds nothing for
    this model).  A step routes every token to its own top k, so its
    regret is 0 by definition."""
    out = {k: np.asarray(aux[k]) for k in ("loads", "rows")}
    out["losses"] = np.asarray(aux["losses"])[:3]       # total, ce, aux
    out["regret"] = np.zeros(out["loads"].shape[:1], np.float32)
    for k in ("grad_sq", "grad_probe"):
        out[k] = dict(zip(leaves(cfg), np.asarray(aux[k])))
    return out


def compared(stats: dict, cfg: dict, wrt: tuple) -> dict:
    """What a check compares of one step's statistics, each in its unit
    (``qwen3nextkit.compared``'s, but for the scales: the total, the
    cross-entropy and the head's logsumexp averaged over quarters of the
    rows, each less ln(``vocab_here``), times ``LOSS_SCALE``, the auxiliary loss over its coefficient
    times ``AUX_SCALE``, the label's logit averaged likewise times
    ``LABEL_SCALE``; the share of a layer's slots every one of all the
    experts received, and
    the held experts' together; the routing's regret; and for the leaves
    of ``wrt`` the gradient's RMS as log10 over ``RMS_UNIT`` and, but for
    ``RMS_ONLY``, its probed entries in units of ``PROBE_UNIT`` RMS, or of
    ``PROBE_UNIT / HOT_ENTRY`` times the largest of them where that is
    more)."""
    rows = np.asarray(stats["rows"], np.float32)
    sizes = leaf_sizes(cfg)
    rms = np.maximum(1e-30, np.sqrt(
        [float(stats["grad_sq"][n]) / sizes[n] for n in wrt]))
    entries = [i for i, n in enumerate(wrt) if n not in RMS_ONLY]
    probe = np.stack([np.asarray(stats["grad_probe"][wrt[i]])
                      for i in entries])
    scale = PROBE_UNIT * np.maximum(rms[entries],
                                    np.abs(probe).max(axis=1) / HOT_ENTRY)
    share = np.asarray(stats["loads"]) / (
        rows.shape[0] * cfg["num_experts_per_tok"])
    here = held(cfg)
    first = here["first_expert"]
    losses = np.asarray(stats["losses"], np.float64)
    uniform = np.log(cfg["vocab_here"] or cfg["vocab_size"])
    return {k: np.asarray(v, np.float32) for k, v in {
        "losses": np.append(LOSS_SCALE * (losses[:2] - uniform),
                            AUX_SCALE * losses[2] / cfg["aux_loss_coef"]),
        "load_share": share,
        "local_share": share[:, first:first + here["experts"]].sum(-1),
        "row_means": (rows.reshape(ROW_BLOCKS, -1, 2).astype(np.float64)
                      .mean(axis=1) - (uniform, 0.0))
        * (LOSS_SCALE, LABEL_SCALE),
        "route_regret": stats["regret"],
        "grad_log_rms": np.log10(rms) / RMS_UNIT,
        "grad_probe": probe / scale[:, None]}.items()}


# -- the float32 parts of a step, read from the step alone -----------------------
def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made at ``sample_rows``, in units of
    ``SAMPLE_UNIT``: the routers' logits, their probabilities (times the
    experts' number, whose mean is then 1) and the chosen weights (both
    times 100), the head's logsumexp and label logit, the first query and
    key-value head of every layer as attention read them (``rope_qk``,
    times ``ROPE_SCALE``: behind RoPE on a window layer, as projected on a
    full one), the first head's output of every window layer
    (``window_o``, times ``WINDOW_SCALE``) and the held experts' weighted
    sum (``expert_out``, times ``EXPERT_SCALE``)."""
    s = aux["sample"]
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "router_logits": s["router_logits"],
        "router_scores": np.asarray(s["router_scores"])
        * (100.0 * cfg["num_experts"]),
        "router_weights": np.asarray(s["router_weights"]) * 100.0,
        "head_rows": np.asarray(aux["rows"])[at],
        "rope_qk": np.asarray(s["attn_qk"]) * ROPE_SCALE,
        "window_o": np.asarray(s["attn_win_o"]) * WINDOW_SCALE,
        "expert_out": np.asarray(s["router_expert_out"]) * EXPERT_SCALE,
    }.items()}


def rope_rows(qk_in, on, at, seq_len: int, theta: float) -> np.ndarray:
    """The first query head and the first key-value head side by side
    (layers, rows ``at``, 2 hd) as attention reads them: behind RoPE over
    the whole head where ``on`` (a layer each) says so, as they came
    elsewhere; in float64, a row's position its place in its own sequence,
    the angles made as a float32 implementation makes them
    (``lfm2kit.rope_rows``)."""
    hd = qk_in.shape[-1] // 2
    inv = (np.float32(1.0) / np.float32(theta) ** (
        np.arange(0, hd, 2, dtype=np.float32) / np.float32(hd))
    ).astype(np.float32)
    ang = ((at % seq_len).astype(np.float32)[:, None] * inv[None, :]
           ).astype(np.float64)
    out = np.array(qk_in, np.float64)
    for lo in (0, hd):
        x1, x2 = qk_in[..., lo:lo + hd // 2], qk_in[..., lo + hd // 2:lo + hd]
        turned_ = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                  x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
        out[..., lo:lo + hd] = np.where(np.asarray(on)[:, None, None],
                                        turned_, qk_in[..., lo:lo + hd])
    return out


def window_rows(q, k, v, at, seq_len: int, window: int) -> np.ndarray:
    """The first head's ``o`` (layers, rows ``at``, hd) from what the
    kernels read of it: q at those rows (layers, R, hd), its key-value
    head's k and v whole (layers, T, hd); in float64, over exactly the
    keys a row sees: ``0 <= i - j < window`` inside its own sequence
    (``window`` 0: every earlier key)."""
    out = np.zeros(q.shape)
    for r, row in enumerate(at):
        start = row - row % seq_len
        lo = start if not window else max(start, row - window + 1)
        sc = np.einsum("ld,lkd->lk", q[:, r], k[:, lo:row + 1]) \
            / np.sqrt(q.shape[-1])
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[:, r] = np.einsum("lk,lkd->ld", p / p.sum(-1, keepdims=True),
                              v[:, lo:row + 1])
    return out


def expert_rows(rows_in, weights, experts, mats: dict, cfg: dict, dtype,
                act) -> np.ndarray:
    """The held experts' weighted sum (layers, R, d) at the sampled rows
    from the normed rows they read (layers, R, d), the chosen weights and
    experts (layers, R, k) and the held experts' matrices (``mats``: gate,
    up, down, a layer each (E here, ., .)): matmul inputs rounded to the
    compute type (the hidden product on its way into ``W_down`` too),
    every product and sum in float64."""
    low = ok._bf16 if dtype == "bfloat16" else (lambda a: np.asarray(
        a, np.float64))
    here = held(cfg)
    out = np.zeros(rows_in.shape)
    x = low(rows_in)
    # an expert's three matrices are rounded once, when a row first meets it
    of = functools.lru_cache(maxsize=None)(lambda layer, j: tuple(
        low(mats[m][layer][j]) for m in ("gate", "up", "down")))
    for layer in range(rows_in.shape[0]):
        for r in range(rows_in.shape[1]):
            for w, e in zip(weights[layer, r], experts[layer, r]):
                j = int(e) - here["first_expert"]
                if not 0 <= j < here["experts"]:
                    continue
                g, u, d = of(layer, j)
                hidden = low(act(x[layer, r] @ g) * (x[layer, r] @ u))
                out[layer, r] += w * (hidden @ d)
    return out


def precision_want(aux: dict, by_name: dict, bias_before, head, labels,
                   cfg: dict, variant: str | None = None) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own
    inputs to each part** at the precision the configuration states: the
    routers' logits from the rows each router read (the layer's input)
    and its weights (``by_name``: every leaf on the host) in float64; the
    softmax probabilities from the step's own logits and the chosen
    weights from those probabilities at the step's own choice, in float64;
    the head's rows from the rows the head read against ``head`` (d, V)
    (``olmoekit._head_program``); ``rope_qk`` from the step's own projected
    q and k in float64, turned on the layers ``rope_layout`` names
    (``rope_rows``); ``window_o`` from the step's own q, k and v of the
    first head over exactly the visible keys in float64 (``window_rows``);
    ``expert_out`` from the rows the experts read, the step's own weights
    and choice and the held experts' matrices (``expert_rows``).
    ``bias_before`` is the kind's and is not read.  ``variant`` gives a
    **control**, which has to lie outside: ``bf16`` (router, probabilities,
    weights and the head as a bfloat16 implementation would have made
    them), ``no_window`` (a window layer attending in full),
    ``rope_full`` (RoPE on the full layer too), ``no_rope_window`` (RoPE
    left off the window layers), ``router_post`` (the router reading the
    normed post-attention stream, every other model's place for it),
    ``silu`` (in relu's place), ``unnormalised`` (the six weights left as
    the probabilities stand)."""
    import jax.numpy as jnp

    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()}
    exact = lambda a: np.asarray(a, np.float64)
    low = ok._bf16 if variant == "bf16" else exact
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    of_leaf = lambda leaf: np.concatenate([
        np.asarray(by_name[f"l{first}.{KINDS[c]}.{leaf}"], np.float64)
        for c, _, first in segments(cfg)])
    router_in = low(s["router_expert_in" if variant == "router_post"
                      else "router_in"])
    logits = low(np.einsum("lrd,lde->lre", router_in,
                           low(of_leaf("router"))))
    own = s["router_logits"]        # the step's, as the softmax read them
    top = own.max(axis=-1, keepdims=True)
    scores = low(np.exp(own - top) / np.exp(own - top).sum(-1, keepdims=True))
    experts = np.asarray(aux["experts"])[:, at]
    chosen = np.take_along_axis(scores, experts, axis=-1)
    if cfg["norm_topk_prob"] and variant != "unnormalised":
        chosen = chosen / chosen.sum(-1, keepdims=True)
    weights = low(chosen)
    lab = np.asarray(labels)[:, :-1].reshape(-1)[at]
    rows, head_logits = ok._head_program(cfg["compute_dtype"])(
        jnp.asarray(aux["sample"]["head_in"]), head, jnp.asarray(lab))
    if variant == "bf16":           # the head's logits kept in bfloat16
        hl = ok._bf16(head_logits)
        top = hl.max(axis=-1)
        picked = np.take_along_axis(hl, lab[:, None], -1)[:, 0]
        rows = low(np.stack([top + np.log(np.exp(
            hl - top[:, None]).sum(axis=-1)), picked], axis=-1))
    on = turned(cfg)
    if variant == "rope_full":
        on = [True] * len(on)
    if variant == "no_rope_window":
        on = [t and c != "W" for t, c in zip(on, pattern(cfg))]
    qk = rope_rows(s["attn_qk_in"], on, at, cfg["seq_len"],
                   cfg["rope_theta"])
    o = window_rows(s["attn_win_q"], s["attn_win_k_seq"],
                    s["attn_win_v_seq"], at, cfg["seq_len"],
                    0 if variant == "no_window"
                    else cfg["sliding_window_size"])
    relu = lambda a: np.maximum(a, 0.0)
    silu = lambda a: a / (1.0 + np.exp(-a))
    by_layer = lambda leaf: [
        by_name[f"l{first}.{KINDS[c]}.{leaf}"][i]
        for c, n, first in segments(cfg) for i in range(n)]
    y = expert_rows(s["router_expert_in"], s["router_weights"], experts,
                    {m: by_layer(m) for m in ("gate", "up", "down")}, cfg,
                    cfg["compute_dtype"], silu if variant == "silu" else relu)
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "router_logits": logits,
        "router_scores": scores * (100.0 * cfg["num_experts"]),
        "router_weights": weights * 100.0,
        "head_rows": np.asarray(rows, np.float64),
        "rope_qk": qk * ROPE_SCALE, "window_o": o * WINDOW_SCALE,
        "expert_out": y * EXPERT_SCALE}.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (``olmoekit.adamw_leaf`` with
    this model's undecayed leaves)."""
    return ok.adamw_leaf("ln1" if name.rsplit(".", 1)[-1] in UNDECAYED
                         else "matrix", p, g, cfg)


# -- operations counted from the shapes -------------------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token meets in one layer's part of each kind
    and in the head; the held routed experts at the **mean** load
    (``experts_here`` / ``num_experts`` of a token's 6 slots land here)."""
    per = layer_sizes(cfg)
    return {"attn_proj": sum(per[k] for k in ("wq", "wk", "wv", "wo")),
            "router": per["router"],
            "experts_mean": 3 * cfg["hidden_size"]
            * cfg["moe_ffn_hidden_size"] * cfg["num_experts_per_tok"]
            * held(cfg)["experts"] / cfg["num_experts"],
            "head": cfg["hidden_size"] * cfg["vocab_here"]}


def visible_positions(cfg: dict) -> dict:
    """The (query, key) positions one sequence's attention sees, by the
    layer's letter: a full layer's lower triangle as the other kits count
    it, s^2 / 2; a window layer's w (w + 1) / 2 + (s - w) w, the triangle
    of the first w queries and w keys a query beyond them (s <= w: the
    full layer's)."""
    s, w = cfg["seq_len"], cfg["sliding_window_size"]
    return {"A": s * s / 2.0,
            "W": s * s / 2.0 if s <= w else w * (w + 1) / 2.0 + (s - w) * w}


def attention_forward_flops(cfg: dict) -> float:
    """Attention's forward FLOP a step over the **visible** positions
    only: q k^T and p v over the head width, 2 x 2 x head width x query
    heads a position, a layer by its kind."""
    see = visible_positions(cfg)
    return float(cfg["micro_batch"] * cfg["num_attention_heads"] * 4
                 * cfg["head_dim"] * sum(see[c] for c in pattern(cfg)))


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters
    a token meets x tokens; attention at three times its forward over the
    visible positions; the held routed experts **at the mean load**.
    Recomputed layers, the masked halves of the diagonal and far tiles,
    the backward kernel's second q k^T, the routers' float32 matmuls at
    six passes and the optimiser's work are not model FLOP and lower the
    share.  ``flash_forward`` and ``attn_backward`` are what the two
    kernels have to compute of the visible positions: the forward's two
    products, and the fused backward's five (q k^T again, dv, dp, dq, dk:
    2.5 times the forward), so that neither's share of the peak can read
    over 100% however the kernels mask."""
    held_pattern = pattern(cfg)
    layers = len(held_pattern)
    tokens = cfg["micro_batch"] * cfg["seq_len"]
    per = matmul_params_per_token(cfg)
    forward = attention_forward_flops(cfg)
    parts = {
        "attn_proj": 6.0 * per["attn_proj"] * tokens * layers,
        "attention": 3.0 * forward,
        "router": 6.0 * per["router"] * tokens * layers,
        "experts": 6.0 * per["experts_mean"] * tokens * layers,
        "head": 6.0 * per["head"] * tokens}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = forward
    parts["attn_backward"] = 2.5 * forward
    return parts
