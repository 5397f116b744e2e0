"""What the ``train_step_kit`` call kind reads for SDAR-30B-A3B-Chat: the
benchmark's own copy of the plain reference of its block-diffusion training
step on one chip's share of an 8-chip expert-parallel deployment, written
independently of the program (``ompi_tpu.parallel``), what a check compares
and in which units, and the functions that count a step's model FLOP and
both flash kernels'.  The batch's form, the probe and sample rules, RoPE
and the blocked head are ``harness/olmoekit``'s, a grouped tree's leaf by
its name ``harness/nemotronkit``'s, the QK-norm's rows ``harness/lfm2kit``'s:
a kit states a model, not a second harness.

The layers are the published ``config.json``'s of JetLM/SDAR-30B-A3B-Chat
(``model_type`` ``sdar_moe``, the Qwen3-MoE family's keys); what goes in and
which keys a query sees are block diffusion's training pass (BD3-LM,
arXiv:2503.09573; SDAR, arXiv:2510.06303, trains by it).  ``norm(x) = x /
sqrt(mean(x^2) + rms_norm_eps) * gain``.

**The noise.**  A sequence ``x0`` of ``L`` tokens is ``L / B`` blocks of
``B`` (``block_length``).  ``key = fold_in(fold_in(PRNGKey(noise_seed),
spare_0), spare_1)``, ``spare`` the last two ids of the sequence's labels (a
batch holds two ids more than the step reads); ``k_c = bits(fold_in(key,
0), (L / B,)) >> 8`` a block and ``k_i = bits(fold_in(key, 1), (L,)) >> 8``
a token, uniform 24-bit integers.  The level is ``t_c = t_min + (1 - t_min)
u_c`` on the grid of 2^-24, in integers so that no rounding can differ:
``q_c = m + floor((2^24 - m) k_c / 2^24)``, ``m = round(t_min 2^24)``,
``t_c = q_c / 2^24``; token ``i`` of block ``c`` is
replaced by the mask token (``mask_token_here``) iff ``k_i < q_c``: ``xt``.
**The rows.**  The model reads the ``2L`` rows ``[xt ; x0]`` at positions
``[0..L-1 ; 0..L-1]``; with ``blk(i) = pos_i // B`` query row ``i`` sees key
row ``j`` iff both are noisy and ``blk(j) == blk(i)``; or ``i`` noisy, ``j``
clean and ``blk(j) < blk(i)``; or both clean and ``blk(j) <= blk(i)``; a
clean row sees no noisy one.  **Layer l**: ``h = norm_1(x)``; q, k, v = ``h
W_q``, ``h W_k``, ``h W_v`` on 32 query and 4 key-value heads of 128; ``q <-
RoPE(norm_head(q))``, ``k <- RoPE(norm_head(k))`` (a gain over each head's
128, then rotate-half RoPE over the whole head at the row's position, theta
1e6); ``a = softmax over the visible keys of q . k / sqrt(128)``; ``x <- x +
(a v) W_o``; ``h2 = norm_2(x)``; ``p = softmax(h2 W_router)`` over 128
experts, the 8 largest, normalised to one; ``x <- x + sum_e w_e
W_down,e(silu(W_gate,e h2) * W_up,e h2)``, experts 768 wide, no shared one.
**The loss** reads the noisy half's rows: ``L_bd = (1 / (b L)) sum_i m_i (1
/ t_blk(i)) (-log softmax(logits_i)[x0_i])``, ``m_i`` 1 where row ``i`` is
masked (no shift; MDLM's weight, arXiv:2406.07524), plus ``aux_loss_coef`` x
HF's load-balancing loss over all ``2L`` rows' routing, every layer's rows
in one mean.  Everything float32, every matmul at the highest precision, no
kernel: the ``(2L, 2L)`` mask written out from the four rules as a dense
boolean by blocks of query rows.  Departures:

* **the share** (``experts_here``, ``expert_share``, ``vocab_here``): every
  held expert on every row under a dense mask of the router's choice among
  **all** the experts; what the absent experts would add is left out;
  attention, routers and norms are whole; embedding, logits and loss are
  over the slice of the vocabulary, whose last row is the mask token and
  is drawn by no datum (``zipf_cdf`` and ``rank_order`` run over the other
  ``vocab_here - 1`` ids);
* attention is never masked between packed documents;
* at the published widths the (2L, 2L) scores, the (E, T, f) activations,
  the (T, V) logits and four layers' activations do not fit beside the
  program's parameters, so attention runs one head and ``ATTN_ROWS`` query
  rows at a time against every key, the experts one after the other, the
  head by blocks of rows, and every layer is recomputed in the backward
  pass (``lax.map`` / ``lax.scan`` / ``jax.checkpoint``).  The arithmetic
  of every element is the same; only what is held at once differs.

**How the noise is compared.**  The kind hands the reference the batch and
the step's routing and nothing else, so the reference **draws the noise
again** from the stated rule (``noise``), and ``precision_want`` does so
once more on the host's copy of the labels, the level's product in 64-bit
integers there (``noise_on_host``): what the step reports of its
own draw (``bd_mask``, ``bd_levels``) has to equal that bit for bit (the
``noise`` entry compares the mask's bits and the levels' four bytes each,
so that one flipped bit lies outside the tolerance), and its masked count
and weights' sum are compared beside it (``bd_weights``).
"""
from __future__ import annotations

import functools

import numpy as np

from harness import olmoekit as ok
from harness.lfm2kit import rope_rows
from harness.nemotronkit import leaf_of, put_leaf, tree_of  # noqa: F401
from harness.olmoekit import (PROBE_UNIT, ROW_BLOCKS,  # noqa: F401
                              SAMPLE_UNIT, probe_positions, sample_rows,
                              tokens_of)

KIND = "bd_moe"
LAYER = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2", "router",
         "gate", "up", "down")
UNDECAYED = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")
# variants of the reference that are deliberately wrong: a plain causal mask
# over the 2L rows; a noisy row that also sees its own block's clean copy
# (``<=`` for ``<``); the loss without its weight; the masked rows drawn at
# a fixed rate of one half
WRONG = ("causal", "leak", "unweighted", "half_rate")
OUTPUTS = ("losses", "load_share", "local_share", "row_means",
           "route_regret", "grad_log_rms", "grad_probe")
PRECISION = ("router_logits", "router_scores", "router_weights",
             "head_rows", "rope_qk", "bd_o", "noise", "bd_weights")
# the variants of ``precision_want`` that are controls (tools/kit_check.py):
# the float32 parts in bfloat16; the first head's output under a causal mask
# and under the leaking one; the noise at a fixed rate; and, run again as a
# whole model, the loss without its weight
WHOLE_CONTROLS = ("unweighted",)
PART_CONTROLS = ("bf16", "causal", "leak", "half_rate") + WHOLE_CONTROLS
RMS_ONLY = ("embed",)
# The units below are ``smallthinkerkit``'s and ``keyekit``'s for the same
# quantities at the same widths and rows (PERF.md 2), read again on the chip
# for this model (my chip runs, PR 64: ``tools/kit_check.py``).
# a gradient's RMS as log10 over this (``olmoekit.RMS_UNIT``'s 4: a limit of
# 4.7% of an RMS)
RMS_UNIT = 4.0
# a leaf whose largest probed entry is over this many RMS is probed in units
# of that entry (``smallthinkerkit.HOT_ENTRY``)
HOT_ENTRY = 4.0
# a routing regret in units of this many k-th probabilities (``olmoekit``'s)
REGRET_UNIT = 32.0
# the total and ``L_bd`` **over the masked rows' mean weight** (``sum_i m_i /
# t_i / (b L)``, 1 in expectation and 0.7 to 1.6 by the draw: a few rows of a
# block drawn at t near t_min weigh hundreds) less ln(``vocab_here``), and the
# head's mean logsumexp less the same, in units of the tolerance over this
# (``smallthinkerkit.LOSS_SCALE``'s 8 and its reasoning: what a run at
# initialisation has to get right is what exceeds the uniform guess)
LOSS_SCALE = 8.0
# the auxiliary loss over its coefficient over this (``smallthinkerkit``'s)
AUX_SCALE = 3.0
# the label's logit averaged over a quarter of the rows over this
LABEL_SCALE = 4.0
# a head's q and k behind the norm and RoPE in units of SAMPLE_UNIT over
# this (``keyekit.ROPE_SCALE``: normed entries of order one to three; here
# positions run to 8,191)
ROPE_SCALE = 0.0125
# the first head's output over exactly the visible keys in units of
# SAMPLE_UNIT over this: o is a mean of up to 8,192 rows of v, of order a
# few hundredths, and the kernel rounds p to bfloat16 for p v
# (``smallthinkerkit.WINDOW_SCALE`` reads the same quantity at 0.05).  The
# leaking mask adds 4 keys to a row that sees 1,024 to 8,192: the first
# sampled noisy row's o moves by 4 / 1,024 of a value row.  Read on the chip
# at 0.1 (PERF.md 2; my chip runs, PR 64): the program 0.35-0.58 in six
# checks and 1.09 in one (which refused a run for 4 of 58,877 positions), the
# leaking mask 14.4 at the narrowest of three, a causal mask 2,842: at 0.025
# they read 0.09-0.27, 3.6 and 710, the limit a factor of four from either
BD_O_SCALE = 0.025
#: query rows of one head that attention scores at once
ATTN_ROWS = 2048

#: what the last float32 ``reference_step`` ran on (``precision_want``'s
#: whole-model controls run it again)
_STEP: dict = {}


def load_config(path: str) -> dict:
    """The configuration file as the reference reads it (``olmoekit``'s: the
    published keys, ``layers_here`` and the ``train`` group, flat), with the
    experts' number also under the name the harness reads it by."""
    cfg = ok.load_config(path)
    return {**cfg, "n_routed_experts": cfg["num_experts"]}


def zipf_cdf(vocab: int) -> np.ndarray:
    """``olmoekit.zipf_cdf`` over the slice's **text** ids: every row of
    ``vocab`` but the last, which is the mask token's and drawn by no
    datum."""
    return ok.zipf_cdf(vocab - 1)


def rank_order(vocab: int, seed: int) -> np.ndarray:
    """Which text id holds which rank of the law (``olmoekit.rank_order``
    over the ``vocab - 1`` text ids)."""
    return ok.rank_order(vocab - 1, seed)


def leaves(cfg: dict) -> tuple:
    """Every trained leaf's name, in the order the program reports them
    (all the held layers are one run, ``l<first>.bd_moe.<leaf>``, stacked
    over the layers)."""
    return ("embed",) + tuple(_name(cfg, leaf) for leaf in LAYER) \
        + ("final_norm", "head")


def _name(cfg: dict, leaf: str) -> str:
    return f"l{cfg['first_layer_here']}.{KIND}.{leaf}"


def checked(cfg: dict) -> tuple:
    """The leaves whose gradients a check compares: attention's four
    matrices (both sides of the mask and of RoPE at repeated positions lie
    behind them), the routers, final norm, head and embedding (its rows'
    gradient sums the noisy and the clean copy's; the mask token's row the
    masked rows').  The held experts' three are 1.2 GB of float32 gradients
    for the one run of four layers, which do not fit beside the reference's
    own arrays at the published widths: where the experts are small they
    are checked too."""
    out = [_name(cfg, leaf) for leaf in ("wq", "wk", "wv", "wo", "router")]
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
    return {"ln1": d, "wq": d * q, "wk": d * kv, "wv": d * kv, "wo": q * d,
            "q_norm": hd, "k_norm": hd, "ln2": d,
            "router": d * cfg["num_experts"], "gate": e * d * f,
            "up": e * d * f, "down": e * f * d}


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this rank holds."""
    out = {"embed": cfg["vocab_here"] * cfg["hidden_size"]}
    out.update({_name(cfg, leaf): cfg["layers_here"] * size
                for leaf, size in layer_sizes(cfg).items()})
    out.update(final_norm=cfg["hidden_size"],
               head=cfg["hidden_size"] * cfg["vocab_here"])
    return out


# -- the reference ------------------------------------------------------------
def noise_bits(labels, length: int, cfg: dict):
    """The draw's two arrays of 24-bit integers, (b, L / B) a block and (b,
    L) a token, a sequence at a time."""
    import jax
    import jax.numpy as jnp

    base = jax.random.PRNGKey(cfg["noise_seed"])
    by_block, by_token = [], []
    for row in range(labels.shape[0]):
        key = jax.random.fold_in(jax.random.fold_in(
            base, labels[row, -2].astype(jnp.uint32)),
            labels[row, -1].astype(jnp.uint32))
        by_block.append(jax.random.bits(
            jax.random.fold_in(key, 0), (length // cfg["block_length"],),
            jnp.uint32) >> 8)
        by_token.append(jax.random.bits(
            jax.random.fold_in(key, 1), (length,), jnp.uint32) >> 8)
    return jnp.stack(by_block), jnp.stack(by_token)


def noise(labels, length: int, cfg: dict, wrong=None):
    """(the blocks' levels (b, L / B) float32, the masked tokens (b, L)
    bool) of a batch whose labels are ``labels``: the stated rule."""
    import jax.numpy as jnp

    k_c, k_i = noise_bits(labels, length, cfg)
    m = round(cfg["t_min"] * 2 ** 24)
    a1, a0 = divmod(2 ** 24 - m, 2 ** 12)
    # floor((2^24 - m) k_c / 2^24) in 32 bits: both factors in two limbs of
    # 12 bits, the low product's own low 12 bits dropped first
    k1, k0 = k_c // 2 ** 12, k_c % 2 ** 12
    q_c = m + a1 * k1 + (a1 * k0 + a0 * k1 + a0 * k0 // 2 ** 12) // 2 ** 12
    if wrong == "half_rate":
        q_c = jnp.full_like(q_c, 2 ** 23)
    return q_c.astype(jnp.float32) / 2 ** 24, \
        k_i < jnp.repeat(q_c, cfg["block_length"], axis=1)


def noise_on_host(labels, length: int, cfg: dict, wrong=None):
    """``noise`` with the level's product in 64-bit integers on the host:
    the rule as it is written, beside the 32-bit limbs that a device
    computes it by."""
    k_c, k_i = (np.asarray(a).astype(np.uint64)
                for a in noise_bits(labels, length, cfg))
    m = round(cfg["t_min"] * 2 ** 24)
    q_c = m + ((2 ** 24 - m) * k_c >> np.uint64(24))
    if wrong == "half_rate":
        q_c = np.full_like(q_c, 2 ** 23)
    return (q_c.astype(np.float64) / 2 ** 24).astype(np.float32), \
        k_i < np.repeat(q_c, cfg["block_length"], axis=1)


def visible(rows, length: int, bl: int, wrong=None):
    """(R, 2L) whether the query rows ``rows`` (their places among a
    sequence's ``2L`` rows, noisy half first) see each key row: the four
    rules, written out."""
    import jax.numpy as jnp

    keys = jnp.arange(2 * length)
    if wrong == "causal":
        return keys[None, :] <= rows[:, None]
    q_noisy, k_noisy = (rows < length)[:, None], (keys < length)[None, :]
    q_blk, k_blk = ((rows % length) // bl)[:, None], \
        ((keys % length) // bl)[None, :]
    earlier = (k_blk <= q_blk) if wrong == "leak" else (k_blk < q_blk)
    return jnp.where(
        q_noisy, jnp.where(k_noisy, k_blk == q_blk, earlier),
        jnp.logical_and(jnp.logical_not(k_noisy), k_blk <= q_blk))


def _attention_rows(q, k, v, bl: int, wrong):
    """Dense masked softmax attention of q, k, v (b, h, 2L, hd), one
    (batch, head) and ``ATTN_ROWS`` query rows at a time against every key
    under ``visible``."""
    import jax
    import jax.numpy as jnp

    b, h, rows_all, hd = q.shape
    rows = min(ATTN_ROWS, rows_all)

    def one_head(qkv):
        qi, ki, vi = qkv

        @jax.checkpoint
        def one_block(xs):
            qb, first = xs
            sc = (qb @ ki.T) / jnp.sqrt(hd).astype(qb.dtype)
            see = visible(first + jnp.arange(rows), rows_all // 2, bl, wrong)
            return jax.nn.softmax(jnp.where(see, sc, -jnp.inf), -1) @ vi

        return jax.lax.map(one_block, (
            qi.reshape(rows_all // rows, rows, hd),
            jnp.arange(0, rows_all, rows))).reshape(rows_all, -1)

    flat = lambda t: t.reshape(b * h, rows_all, -1)
    return jax.lax.map(one_head, (flat(q), flat(k), flat(v))
                       ).reshape(b, h, rows_all, -1)


def _attention(p, x, cfg, wrong):
    """The attention sublayer on the ``2L`` rows, without the residual
    add."""
    import jax.numpy as jnp

    b, rows, _ = x.shape
    length = rows // 2
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = ok._norm(x, p["ln1"], eps)
    heads = lambda t, n: t.reshape(b, rows, n, -1).transpose(0, 2, 1, 3)
    q, k, v = (heads(h @ p[w], n) for w, n in (
        ("wq", nh), ("wk", nkv), ("wv", nkv)))
    # both halves at positions 0 .. L - 1
    turn = lambda t: jnp.concatenate([ok._rope(t[:, :, :length], theta),
                                      ok._rope(t[:, :, length:], theta)], 2)
    q = turn(ok._norm(q, p["q_norm"], eps))
    k = turn(ok._norm(k, p["k_norm"], eps))
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    o = _attention_rows(q, k, v, cfg["block_length"], wrong)
    return o.transpose(0, 2, 1, 3).reshape(b, rows, -1) @ p["wo"]


def _experts(h, weight, gate, up, down):
    """Every held expert on every row, weighted by ``weight`` (T, E), one
    expert after the other (``qwen3nextkit._experts``)."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def one(h, g, u, d, w):
        return w[:, None] * ((jax.nn.silu(h @ g) * (h @ u)) @ d)

    out, _ = jax.lax.scan(lambda acc, xs: (acc + one(h, *xs), None),
                          jnp.zeros_like(h), (gate, up, down, weight.T))
    return out


def _route(p, rows, cfg, routed):
    """(the weight every expert has on every row (T, E), the slots every
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


def _layer(p, x, cfg, wrong, routed):
    b, rows, d = x.shape
    x = x + _attention(p, x, cfg, wrong)
    h = ok._norm(x, p["ln2"], cfg["rms_norm_eps"]).reshape(b * rows, d)
    weight, load, prob_sum, regret = _route(p, h, cfg, routed)
    here = held(cfg)
    first = here["first_expert"]
    y = _experts(h, weight[:, first:first + here["experts"]], p["gate"],
                 p["up"], p["down"])
    return x + y.reshape(b, rows, d), load, prob_sum, regret


def loss_parts(params, tokens, labels, cfg: dict, wrong: str | None = None,
               routed=None):
    """(total, {losses, loads, rows, regret, levels, masked, weights}) of
    one batch, in the parameters' own type throughout (float32; bfloat16
    for the control): ``tokens`` (b, L) the clean sequences, of ``labels``
    the last two ids a row (the noise's key).  With ``routed`` (layers, b x
    2L, k), the experts a program chose, the top k is not taken here but
    given, and ``regret`` says how far that choice is from this model's own
    under its own probabilities.  ``losses`` holds the total, ``L_bd`` and
    the auxiliary loss as weighted into the total; ``rows`` the noisy half's
    logsumexp and clean token's logit a row; ``weights`` (masked rows,
    their weights' sum).  ``wrong`` names a deliberately wrong variant
    (``WRONG``)."""
    import jax
    import jax.numpy as jnp

    b, length = tokens.shape
    levels, masked = noise(labels, length, cfg, wrong)
    xt = jnp.where(masked, jnp.asarray(cfg["mask_token_here"], tokens.dtype),
                   tokens)
    x = params["embed"][jnp.concatenate([xt, tokens], axis=1)]
    group = params["layers"][f"l{cfg['first_layer_here']}"][KIND]
    outs = []
    for i in range(cfg["layers_here"]):
        run = jax.checkpoint(functools.partial(_layer, cfg=cfg, wrong=wrong))
        x, *out = run({k: v[i] for k, v in group.items()}, x,
                      routed=None if routed is None else routed[i])
        outs.append(out)
    loads, prob_sums, regrets = (jnp.stack(col) for col in zip(*outs))
    h = ok._norm(x[:, :length], params["final_norm"], cfg["rms_norm_eps"]
                 ).reshape(b * length, -1)
    rows = ok._head(h, params["head"], tokens.reshape(b * length),
                    cfg.get("loss_block_rows", 1024))
    # the levels and the weights in float32 whatever the parameters are: a
    # control computed in bfloat16 is a model in bfloat16, not another noise
    weight = jnp.where(masked, 1.0 / jnp.repeat(
        levels, cfg["block_length"], axis=1), 0.0).reshape(-1)
    if wrong == "unweighted":
        weight = masked.reshape(-1).astype(jnp.float32)
    ce = (jnp.sum(weight.astype(rows.dtype) * (rows[:, 0] - rows[:, 1]))
          / (b * length)).astype(x.dtype)
    routed_rows = loads.shape[0] * 2 * b * length   # every layer's rows
    aux = jnp.asarray(cfg["aux_loss_coef"] * cfg["num_experts"], x.dtype) \
        * jnp.sum((jnp.sum(loads, 0) / routed_rows)
                  * (jnp.sum(prob_sums, 0) / routed_rows))
    total = ce + aux
    return total, {"losses": jnp.stack([total, ce, aux]), "loads": loads,
                   "rows": rows, "regret": regrets,
                   "weights": jnp.stack([
                       jnp.sum(masked.astype(jnp.float32)),
                       jnp.sum(weight)]).astype(jnp.float32)}


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, wrt: tuple, wrong):
    import jax

    cfg = dict(cfg_items)

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
    """One step's statistics from the reference, in the form ``step_stats``
    puts a program's in: ``losses``, ``loads``, ``rows``, ``regret``,
    ``weights`` and for each leaf of ``wrt`` its gradient's ``grad_sq`` and
    ``grad_probe``; ``grads`` holds the whole gradients of ``wrt``.
    ``bias`` is the kind's: this model's routers choose under none.  The
    noise is drawn here again, from ``labels``.  Parameters given in
    bfloat16 make the **control**: the same model computed throughout in
    the nearest precision below the stated one."""
    import jax.numpy as jnp

    if wrong is None and leaf_of(params, "head").dtype == jnp.float32:
        # what the whole-model controls run again (``precision_want``): the
        # float32 reference's own batch, never a control's
        _STEP.update(params=params, tokens=tokens, labels=labels, wrt=wrt,
                     routed=routed)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
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
    the reference's form.  A step routes every row to its own top k, so its
    regret is 0 by definition."""
    out = {k: np.asarray(aux[k]) for k in ("loads", "rows")}
    out["losses"] = np.asarray(aux["losses"])[:3]       # total, L_bd, aux
    out["regret"] = np.zeros(out["loads"].shape[:1], np.float32)
    out["weights"] = np.asarray([aux["bd_masked"], aux["bd_weight_sum"]],
                                np.float32)
    for k in ("grad_sq", "grad_probe"):
        out[k] = dict(zip(leaves(cfg), np.asarray(aux[k])))
    return out


def compared(stats: dict, cfg: dict, wrt: tuple) -> dict:
    """What a check compares of one step's statistics, each in its unit
    (``smallthinkerkit.compared``'s, and: the total and ``L_bd`` over the
    masked rows' mean weight before ln(``vocab_here``) is taken off them,
    so that a draw's few heavy rows scale both sides alike; the share of a
    layer's slots over all ``2L`` rows a sequence)."""
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
        2 * rows.shape[0] * cfg["num_experts_per_tok"])
    here = held(cfg)
    first = here["first_expert"]
    losses = np.asarray(stats["losses"], np.float64)
    uniform = np.log(cfg["vocab_here"] or cfg["vocab_size"])
    mean_weight = max(1e-30, float(np.asarray(stats["weights"])[1])
                      / rows.shape[0])
    return {k: np.asarray(v, np.float32) for k, v in {
        "losses": np.append(
            LOSS_SCALE * ((losses[:2] - (losses[2], 0.0)) / mean_weight
                          - uniform),
            AUX_SCALE * losses[2] / cfg["aux_loss_coef"]),
        "load_share": share,
        "local_share": share[:, first:first + here["experts"]].sum(-1),
        "row_means": (rows.reshape(ROW_BLOCKS, -1, 2).astype(np.float64)
                      .mean(axis=1) - (uniform, 0.0))
        * (LOSS_SCALE, LABEL_SCALE),
        "route_regret": stats["regret"],
        "grad_log_rms": np.log10(rms) / RMS_UNIT,
        "grad_probe": probe / scale[:, None]}.items()}


# -- the float32 parts of a step, read from the step alone --------------------
def _noise_code(masked, levels) -> np.ndarray:
    """A draw as numbers a tolerance can hold to the bit: the mask's bits
    as 0 / 1 and every level's four bytes, each 0 .. 255."""
    return np.concatenate([
        np.asarray(masked, np.float64).reshape(-1),
        np.ascontiguousarray(np.asarray(levels, np.float32)).view(
            np.uint8).astype(np.float64).reshape(-1)])


def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made, in units of ``SAMPLE_UNIT``: at
    the sampled rows the routers' logits, probabilities and chosen weights
    (``smallthinkerkit``'s units), the head's rows (the noisy half's own
    sampled rows), the first query and key-value head behind the head norm
    and RoPE (``rope_qk``) and the first head's output (``bd_o``); the
    step's own draw to the bit (``noise``) and its masked rows and their
    weights' sum (``bd_weights``)."""
    s = aux["sample"]
    at_head = sample_rows(np.asarray(aux["rows"]).shape[0])
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "router_logits": s["router_logits"],
        "router_scores": np.asarray(s["router_scores"])
        * (100.0 * cfg["num_experts"]),
        "router_weights": np.asarray(s["router_weights"]) * 100.0,
        "head_rows": np.asarray(aux["rows"])[at_head],
        "rope_qk": np.asarray(s["attn_qk"]) * ROPE_SCALE,
        "bd_o": np.asarray(s["bd_o"]) * BD_O_SCALE,
        "noise": _noise_code(np.asarray(aux["bd_mask"]) != 0,
                             aux["bd_levels"]),
        "bd_weights": np.asarray([aux["bd_masked"], aux["bd_weight_sum"]],
                                 np.float64) * 0.01}.items()}


def keys_seen(row: int, length: int, bl: int, variant=None) -> np.ndarray:
    """The key rows (flat, of the whole batch's ``b x 2L``) that flat query
    row ``row`` sees: the four rules again, for one row on the host."""
    start, at = row - row % (2 * length), row % (2 * length)
    if variant == "causal":
        return start + np.arange(at + 1)
    blk = (at % length) // bl
    clean = start + length + np.arange(
        (blk + 1) * bl if at >= length or variant == "leak" else blk * bl)
    if at >= length:
        return clean
    return np.concatenate([start + blk * bl + np.arange(bl), clean])


def bd_rows(q, k, v, at, length: int, bl: int, variant=None) -> np.ndarray:
    """The first head's ``o`` (layers, rows ``at``, hd) from what the
    kernels read of it: q at those rows (layers, R, hd), its key-value
    head's k and v whole (layers, T, hd); in float64, over exactly the keys
    a row sees (``keys_seen``)."""
    out = np.zeros(q.shape)
    for r, row in enumerate(at):
        keys = keys_seen(int(row), length, bl, variant)
        sc = np.einsum("ld,lkd->lk", q[:, r], k[:, keys]) \
            / np.sqrt(q.shape[-1])
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[:, r] = np.einsum("lk,lkd->ld", p / p.sum(-1, keepdims=True),
                              v[:, keys])
    return out


def precision_want(aux: dict, by_name: dict, bias_before, head, labels,
                   cfg: dict, variant: str | None = None) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own
    inputs to each part** at the precision the configuration states
    (``smallthinkerkit.precision_want``'s router and head, the head's labels
    the clean tokens of its sampled rows; ``rope_qk`` from the step's own
    projected q and k and the two gains in float64, a row's position its
    place in its own half, ``lfm2kit.rope_rows``; ``bd_o`` from the first
    head's q, its key-value head's k and v over **exactly the visible
    keys** in float64, ``bd_rows``); ``noise`` and ``bd_weights`` from the
    noise drawn again here from the host's ``labels`` by the stated rule.
    ``variant`` gives a **control**, which has to lie outside: ``bf16``
    (router, probabilities, weights and the head as a bfloat16
    implementation would have made them), ``causal`` (a plain causal mask
    over the 2L rows), ``leak`` (a noisy row also sees its own block's
    clean copy), ``half_rate`` (the masked rows drawn at a fixed rate of one
    half); and the whole-model one (``WHOLE_CONTROLS``: ``unweighted``),
    which runs the reference again on the last checked batch and returns
    what ``compared`` makes of it."""
    import jax.numpy as jnp

    if variant in WHOLE_CONTROLS:
        out = reference_step(
            _STEP["params"], _STEP["tokens"], _STEP["labels"], cfg, {},
            _STEP["wrt"], wrong=variant, routed=_STEP["routed"])
        return compared({k: np.asarray(v) if not isinstance(v, dict) else v
                         for k, v in out.items() if k != "grads"}, cfg,
                        _STEP["wrt"])
    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()}
    exact = lambda a: np.asarray(a, np.float64)
    low = ok._bf16 if variant == "bf16" else exact
    length, bl = cfg["seq_len"], cfg["block_length"]
    n_head = np.asarray(aux["rows"]).shape[0]
    at, at_head = sample_rows(2 * n_head), sample_rows(n_head)
    of_leaf = lambda leaf: np.asarray(by_name[_name(cfg, leaf)], np.float64)
    logits = low(np.einsum("lrd,lde->lre", low(s["router_in"]),
                           low(of_leaf("router"))))
    own = s["router_logits"]        # the step's, as the softmax read them
    top = own.max(axis=-1, keepdims=True)
    scores = low(np.exp(own - top) / np.exp(own - top).sum(-1, keepdims=True))
    experts = np.asarray(aux["experts"])[:, at]
    chosen = np.take_along_axis(scores, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    weights = low(chosen)
    # a head row's label is its own clean token: ids 1 .. L of a sequence's
    # L + 2 are ``labels``' first L, so token p > 0 is ``labels[p - 1]``
    lab = np.asarray(labels)[at_head // length, at_head % length - 1]
    rows, head_logits = ok._head_program(cfg["compute_dtype"])(
        jnp.asarray(aux["sample"]["head_in"]), head, jnp.asarray(lab))
    if variant == "bf16":           # the head's logits kept in bfloat16
        hl = ok._bf16(head_logits)
        top = hl.max(axis=-1)
        picked = np.take_along_axis(hl, lab[:, None], -1)[:, 0]
        rows = low(np.stack([top + np.log(np.exp(
            hl - top[:, None]).sum(axis=-1)), picked], axis=-1))
    qk = rope_rows(s["attn_qk_in"], of_leaf("q_norm"), of_leaf("k_norm"), at,
                   length, cfg["rope_theta"], cfg["rms_norm_eps"])
    o = bd_rows(s["bd_q"], s["bd_k_seq"], s["bd_v_seq"], at, length, bl,
                variant if variant in ("causal", "leak") else None)
    levels, masked = noise_on_host(
        jnp.asarray(np.asarray(labels)), length, cfg,
        "half_rate" if variant == "half_rate" else None)
    weight = np.where(masked, 1.0 / np.repeat(
        levels.astype(np.float64), bl, axis=1), 0.0)
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "router_logits": logits,
        "router_scores": scores * (100.0 * cfg["num_experts"]),
        "router_weights": weights * 100.0,
        "head_rows": np.asarray(rows, np.float64),
        "rope_qk": qk * ROPE_SCALE, "bd_o": o * BD_O_SCALE,
        "noise": _noise_code(masked, levels),
        "bd_weights": np.asarray([masked.sum(), weight.sum()]) * 0.01,
    }.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (``olmoekit.adamw_leaf`` with
    this model's undecayed leaves)."""
    return ok.adamw_leaf("ln1" if name.rsplit(".", 1)[-1] in UNDECAYED
                         else "matrix", p, g, cfg)


# -- operations counted from the shapes ---------------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one row meets in one layer's part of each kind
    and in the head; the held routed experts at the **mean** load."""
    per = layer_sizes(cfg)
    return {"attn_proj": sum(per[k] for k in ("wq", "wk", "wv", "wo")),
            "router": per["router"],
            "experts_mean": 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * cfg["num_experts_per_tok"]
            * held(cfg)["experts"] / cfg["num_experts"],
            "head": cfg["hidden_size"] * cfg["vocab_here"]}


def visible_pairs(cfg: dict) -> dict:
    """The (query, key) pairs one sequence's attention sees over its ``2L``
    rows, by region: the clean half against itself up to its block, the
    noisy half against the clean one strictly before its block, the noisy
    half against itself by blocks; ``causal`` what a causal pass over the
    ``2L`` rows has."""
    length, bl = cfg["seq_len"], cfg["block_length"]
    n = length // bl
    return {"clean_clean": bl * bl * n * (n + 1) // 2,
            "noisy_clean": bl * bl * n * (n - 1) // 2,
            "noisy_noisy": length * bl,
            "causal": 2 * length * (2 * length + 1) // 2}


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters a
    row meets x the ``2L`` rows every layer walks; attention at three times
    its forward over the **visible** pairs only (2 x 2 x 128 x 32 heads a
    pair); the held routed experts **at the mean load**; the head over the
    rows masked **at the mean** (``L (1 + t_min) / 2``: the loss needs no
    other row's logits).  What an implementation does beyond that (the
    masked parts of the diagonal tiles, a head read over every noisy row,
    recomputed layers, the backward kernel's second q k^T, the float32
    routers, AdamW) is not model FLOP and lowers the share: no count
    follows the implementation.  ``flash_forward`` and ``attn_backward``
    are what the two kernels have to compute of the visible pairs: the
    forward's two products, and the fused backward's five (2.5 times the
    forward), so that neither's share of the peak can read over 100%
    however the kernels mask."""
    n = cfg["layers_here"]
    b, length = cfg["micro_batch"], cfg["seq_len"]
    rows = 2 * b * length
    per = matmul_params_per_token(cfg)
    see = visible_pairs(cfg)
    pairs = see["clean_clean"] + see["noisy_clean"] + see["noisy_noisy"]
    attn = float(b * n * cfg["num_attention_heads"] * 4 * cfg["head_dim"]
                 * pairs)
    parts = {
        "attn_proj": 6.0 * per["attn_proj"] * rows * n,
        "attention": 3.0 * attn,
        "router": 6.0 * per["router"] * rows * n,
        "experts": 6.0 * per["experts_mean"] * rows * n,
        "head": 6.0 * per["head"] * b * length * (1.0 + cfg["t_min"]) / 2.0}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = attn
    parts["attn_backward"] = 2.5 * attn
    return parts
