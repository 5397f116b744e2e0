"""What the ``train_step_kit`` call kind reads for Keye-VL-2.0-30B-A3B's
language model: the benchmark's own copy of the plain reference of its
training step on one chip's share of an 8-chip expert-parallel deployment,
written independently of the program (``ompi_tpu.parallel``), what a check
compares and in which units, and the functions that count a step's model
FLOP and each new kernel's.  The batch (Zipf ids), the probe and sample
rules, RoPE and the blocked head are ``harness/olmoekit``'s, a grouped
tree's leaf by its name ``harness/nemotronkit``'s, the QK-norm's rows
``harness/lfm2kit``'s: a kit states a model, not a second harness.

The equations are the published ``config.json``'s of
Kwai-Keye/Keye-VL-2.0-30B-A3B (the Qwen3-MoE family's keys and
``sa_config``) and, for the sparse attention, DeepSeek-V3.2's report's
(DSA).  ``norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * gain``.  Layer
``l`` on the residual stream ``x``: ``h = norm_1(x)``; q, k, v = ``h W_q``,
``h W_k``, ``h W_v`` on 32 query and 4 key-value heads of 128; ``q <-
RoPE(norm_head(q))``, ``k <- RoPE(norm_head(k))`` (a gain over each head's
128, then rotate-half RoPE over the whole head, theta 1e7).  The indexer
reads ``hI = stop_gradient(h)``: ``qI = hI W_qI`` (16 heads of 64), ``kI =
LayerNorm(hI W_kI)`` (one key a position; gain, bias), RoPE over the whole
64 of both, ``w = hI W_wI``; ``I[t, u] = sum_j w[t, j] relu(qI[t, j] .
kI[u]) / sqrt(64 x 16)`` for u <= t.  ``S_t``: the ``min(t + 1, 2048)``
keys of largest ``I[t, .]``, a constant.  ``a[t, h, .] = softmax over S_t
of q[t, h] . k[., g(h)] / sqrt(128)``; ``x <- x + (a v) W_o``.  ``h2 =
norm_2(x)``; ``p = softmax(h2 W_router)`` over 128 experts, the 8 largest,
normalised to one; ``x <- x + sum_e w_e W_down,e(silu(W_gate,e h2) *
W_up,e h2)``, experts 768 wide, no shared one.  The loss: cross-entropy +
``aux_loss_coef`` x HF's load-balancing loss (every layer's rows in one
mean) + ``index_loss_coef`` x ``mean_t sum_layers KL(pbar[t, .] || softmax
over S_t of I[t, .])`` with ``pbar = stop_gradient(mean_h a)``.
Everything float32, every matmul at the highest precision, no kernel, no
counting pass: a dense (rows, s) score block and ``lax.top_k``.
Departures:

* **the share** (``experts_here``, ``expert_share``, ``vocab_here``): every
  held expert on every token under a dense mask of the router's choice
  among **all** the experts; what the absent experts would add is left out;
  attention, indexer, routers and norms are whole; embedding, logits and
  loss are over the slice of the vocabulary;
* the published indexer's Hadamard rotation changes no product in exact
  arithmetic and its FP8 is inference's: both left out.  A tie at the bar
  goes to the earlier key (``lax.top_k``'s order).  Text ids only: M-RoPE
  is RoPE; no tower.  Attention is never masked between packed documents;
* at the published widths the (s, s) scores, the (E, T, f) activations,
  the (T, V) logits and four layers' activations do not fit beside the
  program's parameters, so the attention sublayer runs ``ATTN_ROWS`` query
  rows at a time against every key (all 32 heads of a block together:
  ``pbar`` is their mean), the experts one after the other, the head by
  blocks of rows, and every layer is recomputed in the backward pass
  (``lax.map`` / ``lax.scan`` / ``jax.checkpoint``).  The arithmetic of
  every element is the same; only what is held at once differs.

**How the selection is compared.**  The kind hands the reference the
step's routing (``routed``: "under the step's own routing") and nothing
else, so the step's selection goes the same way through this module:
``step_stats`` keeps the last checked step's packed selection, and
``reference_step`` runs under it unless told otherwise.  The choice itself
is compared by its **regret** under the reference's own float32 scores
(``select_regret``) and by its **count** (``select_count``: exactly
``min(t + 1, 2048)`` a row); the overlap with the reference's own set is
printed and has no limit, because bfloat16 inputs turn near-ties.
"""
from __future__ import annotations

import functools

import numpy as np

from harness import olmoekit as ok
from harness.lfm2kit import rope_rows
from harness.smallthinkerkit import rope_rows as rope_only
from harness.nemotronkit import leaf_of, put_leaf, tree_of  # noqa: F401
from harness.olmoekit import (PROBE_UNIT, ROW_BLOCKS,  # noqa: F401
                              SAMPLE_UNIT, probe_positions, rank_order,
                              sample_rows, tokens_of, zipf_cdf)

KIND = "dsa_moe"
LAYER = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "index_wq",
         "index_wk", "index_k_norm", "index_k_bias", "index_ww", "ln2",
         "router", "gate", "up", "down")
UNDECAYED = ("ln1", "ln2", "q_norm", "k_norm", "index_k_norm",
             "index_k_bias", "final_norm")
# whole-model variants of the reference that are deliberately wrong
WRONG = ("no_index_loss", "hi_attached", "pbar_attached")
OUTPUTS = ("losses", "load_share", "local_share", "row_means",
           "route_regret", "select_regret", "select_count", "grad_log_rms",
           "grad_probe")
PRECISION = ("router_logits", "router_scores", "router_weights",
             "head_rows", "rope_qk", "index_rows", "select_o", "kl_rows")
# the variants of ``precision_want`` that are controls (tools/kit_check.py):
# the float32 parts in bfloat16; every earlier key in the selection's place;
# the best half of the selection; the indexer without its relu; q and k
# without their head norms; and the three whole-model ones of ``WRONG``
PART_CONTROLS = ("bf16", "no_selection", "top_half", "no_relu",
                 "no_head_norm") + WRONG
RMS_ONLY = ("embed",)
# a gradient's RMS as log10 over this (a limit of 9.6% of an RMS at 8, where
# ``olmoekit``'s 4 gives 4.7%): the indexer's leaves learn from a loss whose
# ``pbar`` rests on bfloat16 attention probabilities, and a collapsed
# router's gradient is a sum over a few experts' tokens
RMS_UNIT = 8.0
# a leaf whose largest probed entry is over this many RMS is probed in units
# of that entry (``smallthinkerkit.HOT_ENTRY``)
HOT_ENTRY = 4.0
# a probed entry in units of ``PROBE_UNIT`` over this.  Read on the chip with
# the embedding's rows at ``embed_init_std`` 2.0 (PERF.md 2; my chip runs,
# PR 58) at 1: the program 0.03-0.06 in five checks, the indexer's input
# left attached 1.67 and 1.79, pbar left attached 1.34 and 2.26: at a
# quarter they read 0.12-0.25 and 5.3 at the narrowest, so the limit stands
# a factor of four from the program and five from the nearest wrong model
PROBE_SCALE = 0.25
# a routing regret in units of this many k-th probabilities (``olmoekit``'s:
# the routers read the normed stream, as OLMoE's and Qwen3-Next's do)
REGRET_UNIT = 32.0
# the selection's regret: (the sum of the reference's own best k scores of a
# row less the sum over the step's chosen set) over k times the standard
# deviation of the row's scores, the widest row of a layer, over this.  The
# step's scores are bfloat16-input sums of 16 x 64 products of entries of
# order one, accurate to about 2^-9 of a score; a key is turned only if it
# lies that near the bar, and it then costs that much: a row's mean loss a
# key is under 1e-4 of the spread.  A selection of the best 1,024 and any
# 1,024 others, or of every earlier key, loses a tenth and more of it.  In
# units of the tolerance (atol 5e-3): 1e-4 reads 0.02, a tenth 20
SELECT_REGRET_UNIT = 1.0
# the total, the cross-entropy and the head's mean logsumexp in units of the
# tolerance over this (``smallthinkerkit.LOSS_SCALE``'s value; that kit takes
# ln V off them first since PR 63, this one compares them as they stand)
LOSS_SCALE = 8.0
# the auxiliary loss over its coefficient over this: held loosely, as
# ``smallthinkerkit`` held its own until PR 63 (a sum that rests on a few
# experts' probabilities where the routers collapse); not read again here
# since this file's rows went to 2.0 (PERF.md 7)
AUX_SCALE = 0.1
# the alignment loss (mean_t sum_layers KL, a few tenths: the tolerance's
# atol does the work) as it stands: its ``pbar`` is made of bfloat16
# products under a float32 logsumexp, good to about 2^-9 of a probability,
# and a KL moves by the square of that, so the program lies within 1e-4 of
# the reference; without relu, or with the selection halved, by hundredths
INDEX_SCALE = 1.0
LABEL_SCALE = 0.5
# a head's q and k behind the norm and RoPE in units of SAMPLE_UNIT over
# this (``qwen3nextkit.ROPE_SCALE``'s reasoning: positions to 16,383, where
# the float32 product of a position and an inverse frequency is good to
# 1e-3 rad, on normed entries of order one to three).  Read on the chip at
# 0.05 (PERF.md 2; my chip runs, PR 58): the program 0.88-1.41 in four
# checks (one of them refused a run for 2 of 1.16 M positions), q and k
# without their head norms 279 at the narrowest: at 0.0125 they read
# 0.22-0.35 and 70
ROPE_SCALE = 0.0125
# a row's index scores in units of SAMPLE_UNIT over this: sums of 1,024
# exact products of the step's own bfloat16 entries, of order a tenth;
# float32 accumulation lies within 1e-6 of float64, so the part is held
# tightly and tells a wrong formula.  Read on the chip: the program 0.004-
# 0.006, scores kept in bfloat16 9.7, no relu 50,000
INDEX_ROW_SCALE = 1.0
# the first head's output over exactly the chosen keys in units of
# SAMPLE_UNIT over this (``smallthinkerkit.WINDOW_SCALE``: the kernel rounds
# p to bfloat16 for p v).  Read on the chip at 0.05: the program 0.38-0.53
# in four checks, every earlier key attended to 442, the selection's better
# half alone 216 at the narrowest: at 0.02 they read 0.15-0.21, 177 and 86
SELECT_O_SCALE = 0.02
# a row's alignment loss (sum over its selected keys, of order a tenth) in
# units of SAMPLE_UNIT over this: the kernel's pbar is exp of a bfloat16
# product less a float32 logsumexp, a probability good to 2^-9 of itself.
# Read on the chip at 0.25: the program 0.29-0.48 in four checks, the
# selection's better half alone 227, no relu 895 at the narrowest: at 0.1
# they read 0.12-0.19, 91 and 358
KL_ROW_SCALE = 0.1
#: query rows of all heads that the attention sublayer scores at once
ATTN_ROWS = 256

#: the last checked step's packed selection (``step_stats``), and what the
#: last ``reference_step`` ran on (``precision_want``'s whole-model controls)
_STEP: dict = {}


def load_config(path: str) -> dict:
    """The configuration file as the reference reads it (``olmoekit``'s:
    the published keys, ``layers_here`` and the ``train`` group, flat),
    with the experts' number also under the name the harness reads it by
    and ``sa_config``'s three sizes at the top."""
    cfg = ok.load_config(path)
    sa = cfg["sa_config"]
    return {**cfg, "n_routed_experts": cfg["num_experts"],
            "index_heads": sa["indexer_num_heads"],
            "index_head_dim": sa["indexer_head_dim"],
            "index_topk": sa["topk"]}


def layers(cfg: dict) -> int:
    return cfg["layers_here"]


def leaves(cfg: dict) -> tuple:
    """Every trained leaf's name, in the order the program reports them
    (all the held layers are one run, ``l<first>.dsa_moe.<leaf>``, stacked
    over the layers)."""
    first = cfg["first_layer_here"]
    return ("embed",) + tuple(f"l{first}.{KIND}.{leaf}" for leaf in LAYER) \
        + ("final_norm", "head")


def _name(cfg: dict, leaf: str) -> str:
    return f"l{cfg['first_layer_here']}.{KIND}.{leaf}"


def checked(cfg: dict) -> tuple:
    """The leaves whose gradients a check compares: attention's four
    matrices, the indexer's three, the routers, final norm, head and
    embedding (the held experts' three are 1.2 GB of float32 gradients for
    the one run of four layers, which do not fit beside the reference's own
    arrays at the published widths: where the run is one layer, or the
    experts are small, they are checked too)."""
    out = [_name(cfg, leaf) for leaf in (
        "wq", "wk", "wv", "wo", "index_wq", "index_wk", "index_ww",
        "router")]
    per = layer_sizes(cfg)
    if cfg["layers_here"] * (per["gate"] + per["up"] + per["down"]) \
            <= 1 << 26:
        out += [_name(cfg, leaf) for leaf in ("gate", "up", "down")]
    return tuple(out) + ("final_norm", "head", "embed")


def probed(cfg: dict) -> tuple:
    return tuple(n for n in checked(cfg) if n not in RMS_ONLY)


def held(cfg: dict) -> dict:
    return {"experts": cfg["experts_here"] or cfg["num_experts"],
            "first_expert": cfg["expert_share"] * cfg["experts_here"]}


def layer_sizes(cfg: dict) -> dict:
    """Elements of one layer's leaves."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f, e = cfg["moe_intermediate_size"], held(cfg)["experts"]
    j, di = cfg["index_heads"], cfg["index_head_dim"]
    return {"ln1": d, "wq": d * q, "wk": d * kv, "wv": d * kv, "wo": q * d,
            "q_norm": hd, "k_norm": hd, "index_wq": d * j * di,
            "index_wk": d * di, "index_k_norm": di, "index_k_bias": di,
            "index_ww": d * j, "ln2": d, "router": d * cfg["num_experts"],
            "gate": e * d * f, "up": e * d * f, "down": e * f * d}


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this rank holds."""
    out = {"embed": cfg["vocab_here"] * cfg["hidden_size"]}
    out.update({_name(cfg, leaf): cfg["layers_here"] * size
                for leaf, size in layer_sizes(cfg).items()})
    out.update(final_norm=cfg["hidden_size"],
               head=cfg["hidden_size"] * cfg["vocab_here"])
    return out


# -- the reference -------------------------------------------------------------
def _layernorm(x, gain, bias, eps):
    import jax.numpy as jnp

    x = x - jnp.mean(x, -1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain \
        + bias


def _attention(p, x, cfg, wrong, packed):
    """(the attention sublayer's output without the residual add, the
    alignment loss summed over the rows, the selection's regret and the
    least overlap with this model's own choice, each over the rows).
    ``packed`` (b, s, s / 8) uint8 gives the selection (a program's own);
    None: this model's own."""
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    heads_i, di, topk = cfg["index_heads"], cfg["index_head_dim"], \
        cfg["index_topk"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = ok._norm(x, p["ln1"], eps)
    split = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q, k, v = (split(h @ p[w], n) for w, n in (
        ("wq", nh), ("wk", nkv), ("wv", nkv)))
    q = ok._rope(ok._norm(q, p["q_norm"], eps), theta)
    k = ok._rope(ok._norm(k, p["k_norm"], eps), theta)
    hi = h if wrong == "hi_attached" else jax.lax.stop_gradient(h)
    qi = ok._rope(split(hi @ p["index_wq"], heads_i), theta)
    ki = ok._rope(_layernorm(hi @ p["index_wk"], p["index_k_norm"],
                             p["index_k_bias"], eps)[:, None], theta)[:, 0]
    w = (hi @ p["index_ww"]) / jnp.sqrt(jnp.asarray(heads_i * di, x.dtype))
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    rows = min(ATTN_ROWS, s)
    keys = jnp.arange(s)
    kmost = min(topk, s)

    def one_batch(xs):
        qb, kb, vb, qib, kib, wb, packed_b = xs

        @jax.checkpoint
        def one_block(ys):
            qr, qir, wr, first, packed_r = ys
            t = first + jnp.arange(rows)
            seen = keys[None, :] <= t[:, None]
            sc_i = jnp.einsum("rj,jru->ru", wr, jax.nn.relu(
                jnp.einsum("jrd,ud->jru", qir, kib)))
            masked = jnp.where(seen, sc_i, -jnp.inf)
            flat = jax.lax.stop_gradient(masked)
            best, top = jax.lax.top_k(flat, kmost)
            own = (jnp.sum(jax.nn.one_hot(top, s, dtype=jnp.int32), -2)
                   > 0) & seen
            want = jnp.minimum(t + 1, topk)
            if packed_r is None:
                chosen = own
            else:
                chosen = jnp.unpackbits(packed_r, axis=-1,
                                        bitorder="little")[:, :s] != 0
            # the choice's regret under this model's own scores, in units of
            # the row's spread a key; and its overlap with this model's own
            finite = jnp.where(seen, flat, 0.0)
            mean = jnp.sum(finite, -1) / (t + 1)
            std = jnp.sqrt(jnp.maximum(jnp.sum(jnp.where(
                seen, (flat - mean[:, None]) ** 2, 0.0), -1) / (t + 1),
                1e-30))
            regret = (jnp.sum(jnp.where(best > -jnp.inf, best, 0.0), -1)
                      - jnp.sum(jnp.where(chosen & seen, finite, 0.0), -1)) \
                / (want * std)
            overlap = jnp.sum(chosen & own, -1) / want
            sc = jnp.einsum("hrd,hud->hru", qr, kb) / jnp.sqrt(
                jnp.asarray(hd, qr.dtype))
            a = jax.nn.softmax(jnp.where(chosen[None], sc, -jnp.inf), -1)
            o = jnp.einsum("hru,hud->hrd", a, vb)
            pbar = jnp.mean(a, axis=0)
            if wrong != "pbar_attached":
                pbar = jax.lax.stop_gradient(pbar)
            logq = jax.nn.log_softmax(jnp.where(chosen, masked, -jnp.inf),
                                      -1)
            live = pbar > 0
            kl = jnp.sum(jnp.where(live, pbar * (jnp.log(jnp.where(
                live, pbar, 1.0)) - jnp.where(chosen, logq, 0.0)), 0.0), -1)
            return o, kl, regret, overlap

        by = lambda a, axis: jnp.moveaxis(a.reshape(
            a.shape[:axis] + (s // rows, rows) + a.shape[axis + 1:]), axis, 0)
        o, kl, regret, overlap = jax.lax.map(one_block, (
            by(qb, 1), by(qib, 1), by(wb, 0), jnp.arange(0, s, rows),
            None if packed_b is None else by(packed_b, 0)))
        return (jnp.moveaxis(o, 0, 1).reshape(nh, s, hd), kl.reshape(s),
                regret.reshape(s), overlap.reshape(s))

    o, kl, regret, overlap = jax.lax.map(
        one_batch, (q, k, v, qi, ki, w, packed))
    y = o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"]
    return y, jnp.sum(kl), jnp.max(regret), jnp.min(overlap), kl


def _experts(h, weight, gate, up, down):
    """Every held expert on every token, weighted by ``weight`` (T, E),
    one expert after the other (``qwen3nextkit._experts``)."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def one(h, g, u, d, w):
        return w[:, None] * ((jax.nn.silu(h @ g) * (h @ u)) @ d)

    out, _ = jax.lax.scan(lambda acc, xs: (acc + one(h, *xs), None),
                          jnp.zeros_like(h), (gate, up, down, weight.T))
    return out


def _route(p, rows, cfg, routed):
    """(the weight every expert has on every token (T, E), the slots every
    expert received, the probabilities' sum an expert, the routing's
    regret)."""
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
    if cfg["norm_topk_prob"]:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    choice = jax.nn.one_hot(top_e, e, dtype=rows.dtype)         # (T, k, E)
    return jnp.einsum("tk,tke->te", chosen, choice), \
        jnp.sum(choice, axis=(0, 1)), jnp.sum(probs, axis=0), regret


def _layer(p, x, cfg, wrong, routed, packed):
    b, s, d = x.shape
    y, kl, sel_regret, overlap, _ = _attention(p, x, cfg, wrong, packed)
    x = x + y
    h = ok._norm(x, p["ln2"], cfg["rms_norm_eps"]).reshape(b * s, d)
    weight, load, prob_sum, regret = _route(p, h, cfg, routed)
    here = held(cfg)
    first = here["first_expert"]
    y = _experts(h, weight[:, first:first + here["experts"]], p["gate"],
                 p["up"], p["down"])
    return x + y.reshape(b, s, d), load, prob_sum, regret, kl, sel_regret, \
        overlap


def loss_parts(params, tokens, labels, cfg: dict, wrong: str | None = None,
               routed=None, selection=None):
    """(total, {losses, loads, rows, regret, select_regret, overlap}) of
    one batch, in the parameters' own type throughout (float32; bfloat16
    for the control).  ``labels`` may be longer than ``tokens``: the first
    ``s`` are read.  With ``routed`` (L, T, k) and ``selection`` (L, b, s,
    s / 8) uint8, what a program chose, neither choice is made here but
    given, and ``regret`` / ``select_regret`` say how far each is from this
    model's own under its own scores.  ``losses`` holds the total, the
    cross-entropy, the auxiliary loss and the alignment loss as weighted
    into the total.  ``wrong`` names a deliberately wrong variant
    (``WRONG``): the alignment loss left out of the total, the indexer's
    input not detached, ``pbar`` not detached."""
    import jax
    import jax.numpy as jnp

    b, s = tokens.shape
    x = params["embed"][tokens]
    group = params["layers"][f"l{cfg['first_layer_here']}"][KIND]
    outs = []
    for i in range(cfg["layers_here"]):
        run = jax.checkpoint(functools.partial(_layer, cfg=cfg, wrong=wrong))
        x, *out = run({k: v[i] for k, v in group.items()}, x,
                      routed=None if routed is None else routed[i],
                      packed=None if selection is None else selection[i])
        outs.append(out)
    loads, prob_sums, regrets, kls, sel_regrets, overlaps = (
        jnp.stack(col) for col in zip(*outs))
    h = ok._norm(x, params["final_norm"], cfg["rms_norm_eps"]
                 ).reshape(b * s, -1)
    rows = ok._head(h, params["head"], labels[:, :s].reshape(b * s),
                    cfg.get("loss_block_rows", 1024))
    ce = jnp.mean(rows[:, 0] - rows[:, 1])
    routed_rows = loads.shape[0] * b * s    # every layer's rows in one mean
    aux = jnp.asarray(cfg["aux_loss_coef"] * cfg["num_experts"], x.dtype) \
        * jnp.sum((jnp.sum(loads, 0) / routed_rows)
                  * (jnp.sum(prob_sums, 0) / routed_rows))
    index = jnp.asarray(cfg.get("index_loss_coef", 1.0), x.dtype) \
        * jnp.sum(kls) / (b * s)
    if wrong == "no_index_loss":
        index = jnp.zeros_like(index)
    total = ce + aux + index
    return total, {"losses": jnp.stack([total, ce, aux, index]),
                   "loads": loads, "rows": rows, "regret": regrets,
                   "select_regret": sel_regrets, "overlap": overlaps}


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, wrt: tuple, wrong, selected: bool):
    import jax

    cfg = {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_items}

    def run(params, tokens, labels, routed, selection):
        diff = {n: leaf_of(params, n) for n in wrt}

        def loss(diff):
            merged = jax.tree.map(lambda a: a, params)      # a copy's dicts
            for n, a in diff.items():
                put_leaf(merged, n, a)
            return loss_parts(merged, tokens, labels, cfg, wrong, routed,
                              selection if selected else None)

        with jax.default_matmul_precision("highest"):
            (_, aux), g = jax.value_and_grad(loss, has_aux=True)(diff)
        return aux, g

    return jax.jit(run)


def reference_step(params, tokens, labels, cfg: dict, bias: dict,
                   wrt: tuple, wrong: str | None = None, routed=None,
                   selection="step") -> dict:
    """One step's statistics from the reference, in the form
    ``step_stats`` puts a program's in: ``losses``, ``loads``, ``rows``,
    ``regret``, ``select_regret``, ``overlap``, ``count`` and for each leaf
    of ``wrt`` its gradient's ``grad_sq`` and ``grad_probe``; ``grads``
    holds the whole gradients of ``wrt``.  ``bias`` is the kind's: this
    model's routers choose under none.  ``selection``: ``"step"`` the last
    checked step's (``step_stats`` kept it; None where there is none), an
    array (L, b, s, s / 8) uint8, or None for this model's own choice.
    Parameters given in bfloat16 make the **control**: the same model
    computed throughout in the nearest precision below the stated one."""
    import jax
    import jax.numpy as jnp

    if isinstance(selection, str):
        selection = _STEP.get("selection")
    if wrong is None and leaf_of(params, "head").dtype == jnp.float32:
        # what the whole-model controls run again (``precision_want``): the
        # float32 reference's own batch, never a control's
        _STEP.update(params=params, tokens=tokens, labels=labels, wrt=wrt,
                     routed=routed)
    items = tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in cfg.items()
        if isinstance(v, (int, float, bool, str)) or k == "sa_config"))
    given = selection is not None
    aux, g = _grad_program(items, tuple(wrt), wrong, given)(
        params, tokens, labels, routed,
        jax.device_put(selection) if given else jnp.zeros((), jnp.uint8))
    aux, g = ({k: v.astype(jnp.float32) for k, v in t.items()}
              for t in (aux, g))
    flat = {n: g[n].reshape(-1) for n in wrt}
    s = tokens.shape[1]
    count = np.broadcast_to(np.minimum(np.arange(s) + 1, cfg["index_topk"]),
                            (cfg["layers_here"], tokens.shape[0], s))
    return {**aux, "count": count.astype(np.float32), "grads": g,
            "grad_sq": {n: jnp.sum(f * f) for n, f in flat.items()},
            "grad_probe": {n: f[probe_positions(n, f.shape[0])]
                           for n, f in flat.items()}}


#: set bits of a byte
_BITS = np.unpackbits(np.arange(256, dtype=np.uint8)[:, None], axis=1).sum(1)


def step_stats(aux: dict, bias_after: dict, cfg: dict) -> dict:
    """A program step's ``aux`` (``parallel/train.py``: raw statistics) in
    the reference's form, and the step's packed selection kept for the
    reference that follows.  A step routes every token to its own top k and
    selects its own best keys, so both regrets are 0 by definition; its
    ``count`` is the keys each row's selection holds."""
    packed = np.asarray(aux["sample"]["dsa_selection_seq"])
    _STEP["selection"] = packed
    out = {k: np.asarray(aux[k]) for k in ("loads", "rows")}
    losses = np.asarray(aux["losses"])
    out["losses"] = losses[[0, 1, 2, 4]]        # total, ce, aux, alignment
    zero = np.zeros(out["loads"].shape[:1], np.float32)
    out.update(regret=zero, select_regret=zero,
               count=_BITS[packed].sum(-1).astype(np.float32))
    for k in ("grad_sq", "grad_probe"):
        out[k] = dict(zip(leaves(cfg), np.asarray(aux[k])))
    return out


def compared(stats: dict, cfg: dict, wrt: tuple) -> dict:
    """What a check compares of one step's statistics, each in its unit
    (``smallthinkerkit.compared``'s, and: the alignment loss times
    ``INDEX_SCALE``; the selection's regret (``SELECT_REGRET_UNIT``) and
    the keys every row selects, raw, so that one key too many or too few
    lies outside the tolerance)."""
    if "overlap" in stats:
        print("selection: least share of a row's chosen keys that are the "
              "reference's own, a layer: "
              f"{np.round(np.asarray(stats['overlap'], np.float64), 4)}",
              flush=True)
    rows = np.asarray(stats["rows"], np.float32)
    sizes = leaf_sizes(cfg)
    rms = np.maximum(1e-30, np.sqrt(
        [float(stats["grad_sq"][n]) / sizes[n] for n in wrt]))
    entries = [i for i, n in enumerate(wrt) if n not in RMS_ONLY]
    probe = np.stack([np.asarray(stats["grad_probe"][wrt[i]])
                      for i in entries])
    scale = PROBE_UNIT * PROBE_SCALE * np.maximum(
        rms[entries], np.abs(probe).max(axis=1) / HOT_ENTRY)
    share = np.asarray(stats["loads"]) / (
        rows.shape[0] * cfg["num_experts_per_tok"])
    here = held(cfg)
    first = here["first_expert"]
    losses = np.asarray(stats["losses"], np.float64)
    return {k: np.asarray(v, np.float32) for k, v in {
        "losses": np.array([LOSS_SCALE * losses[0], LOSS_SCALE * losses[1],
                            AUX_SCALE * losses[2] / cfg["aux_loss_coef"],
                            INDEX_SCALE * losses[3]]),
        "load_share": share,
        "local_share": share[:, first:first + here["experts"]].sum(-1),
        "row_means": rows.reshape(ROW_BLOCKS, -1, 2).mean(axis=1)
        * (LOSS_SCALE, LABEL_SCALE),
        "route_regret": stats["regret"],
        "select_regret": np.asarray(stats["select_regret"])
        / SELECT_REGRET_UNIT,
        "select_count": stats["count"],
        "grad_log_rms": np.log10(rms) / RMS_UNIT,
        "grad_probe": probe / scale[:, None]}.items()}


# -- the float32 parts of a step, read from the step alone -----------------------
def _causal(rows_at, seq_len: int, width: int):
    """(R, width) whether key u lies at or before row ``at``'s position."""
    return np.arange(width)[None, :] <= (rows_at % seq_len)[:, None]


def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made at ``sample_rows``, in units of
    ``SAMPLE_UNIT``: the routers' logits, probabilities and chosen weights
    (``smallthinkerkit``'s units), the head's rows, the first query and
    key-value head behind the head norm and RoPE (``rope_qk``), the
    indexer's scores of the row made again by XLA from the step's own
    bfloat16 ``qI``, ``kI`` and float32 ``w`` (``index_rows``; the kernel
    keeps its own in VMEM and is judged by what it selects), the first
    head's output (``select_o``) and the row's alignment loss
    (``kl_rows``)."""
    s = aux["sample"]
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    seen = _causal(at, cfg["seq_len"], np.asarray(s["dsa_index_at"]).shape[-1])
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "router_logits": s["router_logits"],
        "router_scores": np.asarray(s["router_scores"])
        * (100.0 * cfg["num_experts"]),
        "router_weights": np.asarray(s["router_weights"]) * 100.0,
        "head_rows": np.asarray(aux["rows"])[at],
        "rope_qk": np.asarray(s["attn_qk"]) * ROPE_SCALE,
        "index_rows": np.where(seen, np.asarray(s["dsa_index_at"]), 0.0)
        * INDEX_ROW_SCALE,
        "select_o": np.asarray(s["dsa_o_at"]) * SELECT_O_SCALE,
        "kl_rows": np.asarray(s["dsa_kl_at"]) * KL_ROW_SCALE}.items()}


def index_row(qi, ki, w, relu: bool = True) -> np.ndarray:
    """(s,) index scores of one sampled row from the step's own ``qi`` (J
    di,), ``ki`` (s, di) and ``w`` (J,; the scale in it), in float64."""
    z = ki @ qi.reshape(w.shape[0], -1).T                   # (s, J)
    return (np.maximum(z, 0.0) if relu else z) @ w


def chosen_rows(aux: dict, cfg: dict) -> np.ndarray:
    """(layers, R, s) the step's selection at the sampled rows."""
    packed = np.asarray(aux["sample"]["dsa_selection_seq"])
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    s = cfg["seq_len"]
    rows = packed[:, at // s, at % s]                       # (L, R, s / 8)
    return np.unpackbits(rows, axis=-1, bitorder="little")[..., :s] != 0


def precision_want(aux: dict, by_name: dict, bias_before, head, labels,
                   cfg: dict, variant: str | None = None) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own
    inputs to each part** at the precision the configuration states
    (``smallthinkerkit.precision_want``'s router and head; ``rope_qk`` from
    the step's own projected q and k and the two gains in float64,
    ``lfm2kit.rope_rows``); ``index_rows`` from the step's own ``qI``,
    ``kI`` and ``w`` in float64; ``select_o`` from the first head's q, its
    key-value head's k and v over **exactly the chosen keys** in float64;
    ``kl_rows`` from every head's q and k (``pbar``, its softmax over the
    chosen keys in float64) and the float64 index scores.  ``variant``
    gives a **control**, which has to lie outside: ``bf16`` (router, head,
    index scores and pbar as a bfloat16 implementation would have made
    them), ``no_selection`` (every earlier key attended to), ``top_half``
    (the better half of the selection by the row's own scores),
    ``no_relu`` (the indexer without it), ``no_head_norm`` (q and k as
    projected, RoPE alone); and the whole-model ones (``WRONG``), which run
    the reference again on the last checked batch with the alignment loss
    left out, the indexer's input attached or ``pbar`` attached, and return
    what ``compared`` makes of it."""
    import jax.numpy as jnp

    if variant in WRONG:
        out = reference_step(
            _STEP["params"], _STEP["tokens"], _STEP["labels"], cfg, {},
            _STEP["wrt"], wrong=variant, routed=_STEP["routed"])
        return compared({k: np.asarray(v) if not isinstance(v, dict) else v
                         for k, v in out.items() if k != "grads"}, cfg,
                        _STEP["wrt"])
    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()
         if k != "dsa_selection_seq"}
    exact = lambda a: np.asarray(a, np.float64)
    low = ok._bf16 if variant == "bf16" else exact
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    seq = cfg["seq_len"]
    of_leaf = lambda leaf: np.asarray(by_name[_name(cfg, leaf)], np.float64)
    logits = low(np.einsum("lrd,lde->lre", low(s["router_in"]),
                           low(of_leaf("router"))))
    own = s["router_logits"]        # the step's, as the softmax read them
    top = own.max(axis=-1, keepdims=True)
    scores = low(np.exp(own - top) / np.exp(own - top).sum(-1, keepdims=True))
    experts = np.asarray(aux["experts"])[:, at]
    chosen_w = np.take_along_axis(scores, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen_w = chosen_w / chosen_w.sum(-1, keepdims=True)
    weights = low(chosen_w)
    lab = np.asarray(labels)[:, :-1].reshape(-1)[at]
    rows, head_logits = ok._head_program(cfg["compute_dtype"])(
        jnp.asarray(aux["sample"]["head_in"]), head, jnp.asarray(lab))
    if variant == "bf16":           # the head's logits kept in bfloat16
        hl = ok._bf16(head_logits)
        top = hl.max(axis=-1)
        picked = np.take_along_axis(hl, lab[:, None], -1)[:, 0]
        rows = low(np.stack([top + np.log(np.exp(
            hl - top[:, None]).sum(axis=-1)), picked], axis=-1))
    if variant == "no_head_norm":   # q and k as projected, RoPE alone
        qk = rope_only(s["attn_qk_in"], [True] * layers(cfg), at, seq,
                       cfg["rope_theta"])
    else:
        qk = rope_rows(s["attn_qk_in"], of_leaf("q_norm"), of_leaf("k_norm"),
                       at, seq, cfg["rope_theta"], cfg["rms_norm_eps"])
    # the selection's parts, a sampled row of a layer at a time: the keys
    # are those of the row's own sequence
    chosen = chosen_rows(aux, cfg)                          # (L, R, s)
    hd, topk = cfg["head_dim"], cfg["index_topk"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    index = np.zeros(s["dsa_index_at"].shape)
    o = np.zeros(s["dsa_o_at"].shape)
    kl = np.zeros(s["dsa_kl_at"].shape)
    for layer in range(chosen.shape[0]):
        for r, row in enumerate(at):
            own = slice(row - row % seq, row - row % seq + seq)
            seen = np.arange(seq) <= row % seq
            index[layer, r] = low(np.where(seen, index_row(
                s["dsa_qi_at"][layer, r], s["dsa_ki_seq"][layer, own],
                s["dsa_w_at"][layer, r], relu=variant != "no_relu"), 0.0))
            pick = chosen[layer, r]
            if variant == "no_selection":
                pick = seen
            if variant == "top_half" and pick.sum() > topk // 2:
                bar = np.sort(index[layer, r][pick])[-(topk // 2)]
                pick = pick & (index[layer, r] >= bar)
            keys = pick.nonzero()[0]
            q = s["dsa_q_at"][layer, r].reshape(nh, hd)
            kk = s["dsa_kall_seq"][layer, own][keys].reshape(-1, nkv, hd)
            sc = np.einsum("hd,uhd->hu", q, np.repeat(kk, nh // nkv, 1)) \
                / np.sqrt(hd)
            a = np.exp(sc - sc.max(-1, keepdims=True))
            a = low(a / a.sum(-1, keepdims=True))
            o[layer, r] = a[0] @ s["dsa_v_seq"][layer, own][keys]
            pbar = a.mean(0)
            sc_i = index[layer, r][keys]
            logq = sc_i - sc_i.max() - np.log(np.exp(sc_i - sc_i.max()).sum())
            live = pbar > 0
            kl[layer, r] = np.sum(pbar[live] * (np.log(pbar[live])
                                                - logq[live]))
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "router_logits": logits,
        "router_scores": scores * (100.0 * cfg["num_experts"]),
        "router_weights": weights * 100.0,
        "head_rows": np.asarray(rows, np.float64),
        "rope_qk": qk * ROPE_SCALE,
        "index_rows": index * INDEX_ROW_SCALE,
        "select_o": o * SELECT_O_SCALE, "kl_rows": kl * KL_ROW_SCALE}.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (``olmoekit.adamw_leaf`` with
    this model's undecayed leaves)."""
    return ok.adamw_leaf("ln1" if name.rsplit(".", 1)[-1] in UNDECAYED
                         else "matrix", p, g, cfg)


# -- operations counted from the shapes -------------------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token meets in one layer's part of each kind
    and in the head; the held routed experts at the **mean** load."""
    per = layer_sizes(cfg)
    return {"attn_proj": sum(per[k] for k in ("wq", "wk", "wv", "wo")),
            "index_proj": sum(per[k] for k in ("index_wq", "index_wk",
                                               "index_ww")),
            "router": per["router"],
            "experts_mean": 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * cfg["num_experts_per_tok"]
            * held(cfg)["experts"] / cfg["num_experts"],
            "head": cfg["hidden_size"] * cfg["vocab_here"]}


def positions(cfg: dict) -> dict:
    """The (query, key) positions of one sequence: ``selected`` those the
    attention attends to, ``min(t + 1, topk)`` a query; ``causal`` those
    the indexer scores, s (s + 1) / 2."""
    s, k = cfg["seq_len"], min(cfg["index_topk"], cfg["seq_len"])
    return {"selected": k * (k + 1) / 2.0 + (s - k) * k,
            "causal": s * (s + 1) / 2.0}


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters
    a token meets x tokens; attention at three times its forward over the
    **selected** positions only (2 x 2 x 128 x 32 heads a position); the
    index scores at three times their forward over the **causal** positions
    (2 x 16 x 64 a position); the held routed experts **at the mean load**.
    What an implementation does beyond that (a masked dense walk computes
    the whole triangle where the model needs 23.4% of it; recomputed
    layers; the alignment loss's second q k^T; the float32 routers; AdamW)
    is not model FLOP and lowers the share: no count follows the
    implementation.  The kernels' own counts, so that no share of a peak
    can read over 100% however a kernel masks: ``flash_forward`` the
    forward's two products over the selected positions and
    ``attn_backward`` the fused backward's five (2.5 times that);
    ``index_select`` the score products of ``otpu_dsa_index_select`` over
    the causal positions; ``index_loss`` what ``otpu_dsa_index_loss`` has
    to compute: the scores again and their three transposes over the
    causal positions (4 x 2 x 16 x 64), and one more q k^T over the
    selected ones (2 x 128 x 32)."""
    n = cfg["layers_here"]
    tokens = cfg["micro_batch"] * cfg["seq_len"]
    per = matmul_params_per_token(cfg)
    pos = positions(cfg)
    b = cfg["micro_batch"]
    attn = float(b * n * cfg["num_attention_heads"] * 4 * cfg["head_dim"]
                 * pos["selected"])
    index = float(b * n * 2 * cfg["index_heads"] * cfg["index_head_dim"]
                  * pos["causal"])
    parts = {
        "attn_proj": 6.0 * per["attn_proj"] * tokens * n,
        "index_proj": 6.0 * per["index_proj"] * tokens * n,
        "attention": 3.0 * attn,
        "index_scores": 3.0 * index,
        "router": 6.0 * per["router"] * tokens * n,
        "experts": 6.0 * per["experts_mean"] * tokens * n,
        "head": 6.0 * per["head"] * tokens}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = attn
    parts["attn_backward"] = 2.5 * attn
    parts["index_select"] = index
    parts["index_loss"] = 4.0 * index + attn / 2.0
    return parts
