"""What the ``train_step_kit`` call kind reads for Ouro-2.6B: the benchmark's
own copy of the plain reference of its looped training step on one chip's
four layers, written independently of the program (``ompi_tpu.parallel``),
what a check compares and in which units, and the functions that count a
step's model FLOP.  The batch (Zipf ids), the probe and sample rules, RoPE
and the blocked head are ``harness/olmoekit``'s, attention a block of query
rows at a time and RoPE's rows in float64 ``harness/smallthinkerkit``'s: a
kit states a model, not a second harness.

The equations are the published ``config.json``'s of ByteDance/Ouro-2.6B and
its report's (*Scaling Latent Reasoning via Looped Language Models*,
arXiv:2510.25741).  ``norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * gain``;
no bias but the gate's.  A layer on the residual stream ``x`` is a
**sandwich**: ``a = Attn(norm_1(x))``, ``x <- x + norm_1post(a)``; ``m =
W_down(silu(W_gate h) * W_up h)`` with ``h = norm_2(x)``, ``x <- x +
norm_2post(m)``.  ``Attn``: q, k, v = ``h W_q``, ``h W_k``, ``h W_v`` on 16
heads of 128 (a key-value head a query head), no per-head norm; RoPE
(rotate-half, the whole head, theta 1e6); causal ``softmax(q k^T /
sqrt(128)) v``; ``W_o``.  The model: ``h_0 = Embed(ids)``; for pass t = 1 ..
``total_ut_steps`` ``h_t = norm_f(Layers(h_{t-1}))``, **the same layers with
the same leaves every pass**: the passes are a Python loop over one
dictionary of leaves.  Behind every pass the one head and the one exit gate
read ``h_t``: ``logits_t = h_t W_head``, ``lambda_t = sigmoid(h_t . w_g +
b_g)`` a row; ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for t < T and the
last pass takes what is left.  The loss is the report's Stage I objective,
``L = (1 / (b s)) sum_i [ sum_t p_t,i CE_t,i - exit_beta H(p_.,i) ]``, as its
definition: products of sigmoids, ``-sum p log p``, gradients from
``jax.grad`` with no custom rule.  Everything float32, every matmul at the
highest precision, no kernel.  Departures:

* the gate, the distribution and the entropy are a token row's, the loss
  their mean over rows; attention is never masked between packed documents;
  ``early_exit_threshold`` is generation's (the file's ``assumed``);
* at the published widths the (s, s) scores of a head, the (4 T, V) logits
  and sixteen layer applications' activations do not fit beside the
  program's parameters, so attention runs one (batch, head, block of query
  rows) at a time against every key under the mask, the head by blocks of
  rows, and every layer application is recomputed in the backward pass
  (``lax.map`` / ``jax.checkpoint``).  The arithmetic of every element is
  the same; only what is held at once differs.
"""
from __future__ import annotations

import functools

import numpy as np

from harness import olmoekit as ok
from harness.olmoekit import (PROBE_UNIT, ROW_BLOCKS,  # noqa: F401
                              SAMPLE_UNIT, probe_positions, rank_order,
                              sample_rows, tokens_of, zipf_cdf)
from harness.smallthinkerkit import _attention_rows, rope_rows

KIND = "attn_dense"
LAYER = ("ln1", "wq", "wk", "wv", "wo", "ln1_post", "ln2", "gate", "up",
         "down", "ln2_post")
GATE = ("exit_gate.w", "exit_gate.b")
UNDECAYED = ("ln1", "ln1_post", "ln2", "ln2_post", "final_norm", "w", "b")
# variants of the reference that are deliberately wrong: one pass in place of
# four (its h read by all four heads and gates); four passes with the pass's
# norm left out between them (the head and the gate still read the normed
# rows); the loss of the last pass alone; the exit distribution held constant
# at 1 / T (the gate's gradient then reads zero); a layer without its two
# second norms
WRONG = ("one_pass", "no_pass_norm", "last_pass_loss", "uniform_exit",
         "no_post_norm")
OUTPUTS = ("losses", "exit_mean", "lse_means", "label_means", "grad_log_rms",
           "grad_probe")
PRECISION = ("head_rows", "rope_qk", "exit_logit", "exit_p", "exit_entropy")
# the variants of ``precision_want`` that are controls (tools/kit_check.py):
# the float32 parts in bfloat16 and, each run again as a whole model, the
# five wrong models
WHOLE_CONTROLS = WRONG
PART_CONTROLS = ("bf16",) + WHOLE_CONTROLS
# the embedding's gradient is compared by its RMS alone (``nemotronkit`` says
# why: most of its rows no token reads)
RMS_ONLY = ("embed",)
# The units are ``smallthinkerkit``'s for the same quantities at the same
# widths (PERF.md 2), read again on the chip for this model (my chip runs,
# PR 67: ``tools/kit_check.py``; PERF.md 2 has the readings).
# a gradient's RMS as log10 over this (``olmoekit.RMS_UNIT``'s 4: a limit of
# 4.7% of an RMS)
RMS_UNIT = 4.0
# a leaf whose largest probed entry is over this many RMS is probed in units
# of that entry (``smallthinkerkit.HOT_ENTRY``)
HOT_ENTRY = 4.0
# the total, every pass's mean cross-entropy and the expected one, each
# **less the loss of a uniform guess**, ln(``vocab_size``) = 10.80, in units
# of the tolerance over this.  A pass's cross-entropy is a mean over 8,192
# rows of logsumexp less the label's logit, and the label's logit carries
# what bfloat16 matmul inputs leave of sixteen layer applications (a logit
# moves by some hundredths, either sign): read raw on the chip (my chip runs,
# PR 67, ``tools/kit_check.py --dump``, 3 seeds) the program lies at most
# 6.3e-4 from the reference in its total and 1.6e-3 in a pass's
# cross-entropy (the fourth pass's; the first's 4e-4), the reference in
# bfloat16 3.8e-2 to 4.3e-2 in its total and expected cross-entropy.  At 1
# the limit is 5.1e-3: the program reads 0.31 there and at most 0.36 in a
# run's 26 checks, the control 5.2 at the narrowest of nine: **this is the
# limit by which the bfloat16 reference comes out as not correct** (``smallthinkerkit.LOSS_SCALE`` 8 would put the limit at 6.6e-4,
# inside the program's own fourth pass)
LOSS_SCALE = 1.0
# a pass's logsumexp averaged over a quarter of the rows, less ln V, over
# this: no label enters it.  **It widens as the model learns**: with every
# leaf moved the same way by AdamW's first steps the frequent ids' logits
# rise fast (the label's mean logit 0.1 to 0.3 five steps in, 0.9 to 1.3 at
# a run's first check, 17 steps in, 2.3 to 2.5 at its second, about 46 in),
# the logsumexp then rests on a few large logits, and what bfloat16 leaves
# of them is common to a sequence's rows.  Read raw (``tools/kit_check.py
# --dump`` at 4, 16 and 45 warm steps, 6 checks; a run's own 26 checks in
# units): the program's widest mean 1.3e-4 five steps in, 2.3e-3 and 4.5e-4
# at 17, 5.6e-4 at 46, and 3.5e-3 in one of 26 checks (the next 1.5e-3); the
# reference in bfloat16 2.6e-2 to 4.1e-2 at its widest mean in every check.
# At 0.3 the limit is 1.7e-2: the program reads 0.21 at its widest, the
# control 1.6 at the narrowest (``smallthinkerkit.LOSS_SCALE`` 8, the
# first unit here, put the limit at 6.4e-4, which holds five steps in and
# not at a run's checks: four of six runs read ``correct`` false by it)
LSE_SCALE = 0.3
# the entropy bonus over ``exit_beta`` (the mean entropy of the exit
# distribution, 1.213 nats at a gate of zero, ln 4 = 1.386 under a uniform
# one) over this
ENTROPY_SCALE = 1.0
# the batch's mean exit probability a pass (1/2 .. 1/8) over this
EXIT_SCALE = 0.1
# the label's logit averaged over a quarter of the rows (0.1 to 0.3 five
# steps in, 2.5 at a run's second check) over this.  It tells no precision:
# at every step read the program lies 2e-4 to 2.7e-3 from the reference and
# the reference in bfloat16 5e-4 to 2.8e-3, the same noise of 2,048 rows'
# logits (0.007 to 0.016 a row, a part of it common to a sequence's rows);
# it tells a head read at the wrong rows or against the wrong labels, which
# moves it by its own size.  At 0.5 the limit is 1.0e-2 to 1.1e-2: the
# program reads 0.27 at its widest (``smallthinkerkit.LABEL_SCALE`` 4 put
# the limit at 1.25e-3, inside the program's own: the cell's first run on
# the chip read ``correct`` false by this, 1.118)
LABEL_SCALE = 0.5
# a head's q and k as attention reads them in units of SAMPLE_UNIT over this
# (``smallthinkerkit.ROPE_SCALE``)
ROPE_SCALE = 0.005
#: what the last float32 ``reference_step`` ran on (``precision_want``'s
#: whole-model controls run it again)
_STEP: dict = {}


def load_config(path: str) -> dict:
    """The configuration file as the reference reads it (``olmoekit``'s: the
    published keys, ``layers_here`` and the ``train`` group, flat), the whole
    vocabulary under ``vocab_here`` where the file holds all of it (0), and
    no expert under the names the harness reads them by."""
    cfg = ok.load_config(path)
    return {**cfg, "vocab_here": cfg.get("vocab_here") or cfg["vocab_size"],
            "experts_here": 0, "num_experts": 0, "n_routed_experts": 0,
            "num_experts_per_tok": 0}


def _name(leaf: str) -> str:
    """A layer leaf's name: the held layers are one run of like layers, which
    the program's tree names by the run's first place in the held pattern."""
    return f"l0.{KIND}.{leaf}"


def leaves(cfg: dict) -> tuple:
    """Every trained leaf's name, in the order the program reports them (one
    run of like layers, ``l0.attn_dense.<leaf>``, stacked over the held
    layers; then the norm, the head and the gate)."""
    return ("embed",) + tuple(_name(leaf) for leaf in LAYER) \
        + ("final_norm", "head") + GATE


def checked(cfg: dict) -> tuple:
    """The leaves whose gradients a check compares: every one (1.6 GB of
    float32 gradients at the published widths, which fit beside the
    reference's own arrays: nothing here is an expert's) but the gate's
    bias.  That leaf is one number, the sum over every row and pass of the
    gate's cotangents, whose terms cancel: its "RMS" is the absolute value
    of what the cancelling leaves, and read on the chip (my chip runs, PR
    67, seed 2147590002) the program's lay 3.3% from the reference's (3.6
    units) where every other leaf's lay within 0.1%, the reference's in
    bfloat16 19% (22 units).  The same cotangents reach ``exit_gate.w``
    against the rows they belong to, 2,048 sums that do not cancel alike;
    the bias's own gradient is held to the reference at small widths
    (``tests/test_ouro_train.py``)."""
    return tuple(n for n in leaves(cfg) if n != "exit_gate.b")


def probed(cfg: dict) -> tuple:
    """The checked leaves whose gradient is also compared entry by entry:
    all but ``RMS_ONLY``."""
    return tuple(n for n in checked(cfg) if n not in RMS_ONLY)


def _path(name: str) -> tuple:
    parts = tuple(name.split("."))
    return parts if len(parts) == 1 or parts[0] == "exit_gate" \
        else ("layers",) + parts


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


def layer_sizes(cfg: dict) -> dict:
    """Elements of one layer's leaves."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"ln1": d, "wq": d * q, "wk": d * kv, "wv": d * kv, "wo": q * d,
            "ln1_post": d, "ln2": d, "gate": d * f, "up": d * f,
            "down": f * d, "ln2_post": d}


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this chip holds: each layer's once, however
    many passes read it."""
    d, v = cfg["hidden_size"], cfg["vocab_here"]
    return {"embed": v * d,
            **{_name(leaf): cfg["layers_here"] * size
               for leaf, size in layer_sizes(cfg).items()},
            "final_norm": d, "head": d * v, "exit_gate.w": d,
            "exit_gate.b": 1}


# -- the reference -------------------------------------------------------------
def _layer(p, x, cfg, wrong):
    """One sandwich layer on the residual stream ``x`` (b, s, d)."""
    import jax

    b, s, d = x.shape
    nh, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    behind = (lambda y, gain: y) if wrong == "no_post_norm" \
        else (lambda y, gain: ok._norm(y, gain, eps))
    h = ok._norm(x, p["ln1"], eps)
    heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
    q, k, v = (heads(h @ p[w]) for w in ("wq", "wk", "wv"))
    q, k = ok._rope(q, cfg["rope_theta"]), ok._rope(k, cfg["rope_theta"])
    o = _attention_rows(q, k, v, 0)
    x = x + behind(o.transpose(0, 2, 1, 3).reshape(b, s, -1) @ p["wo"],
                   p["ln1_post"])
    h = ok._norm(x, p["ln2"], eps)
    m = (jax.nn.silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]
    return x + behind(m, p["ln2_post"])


def exit_probabilities(gate, wrong=None):
    """The exit distribution (T, rows) from the gate's products (T, rows),
    as its definition: products of sigmoids, the last pass what is left."""
    import jax
    import jax.numpy as jnp

    if wrong == "uniform_exit":
        return jnp.full_like(gate, 1.0 / gate.shape[0])
    lam = jax.nn.sigmoid(gate)
    left, out = jnp.ones_like(gate[0]), []
    for t in range(gate.shape[0] - 1):
        out.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(out + [left])


def loss_parts(params, tokens, labels, cfg: dict, wrong: str | None = None):
    """(total, {losses, rows, exit_mean}) of one batch, in the parameters' own
    type throughout (float32; bfloat16 for the control).  ``labels`` may be
    longer than ``tokens``: the first ``s`` are read.  ``losses`` holds the
    total, every pass's mean cross-entropy, the expected cross-entropy and
    ``exit_beta`` x the mean entropy; ``rows`` (b s, T, 2) every pass's
    logsumexp and label's logit a row; ``exit_mean`` (T,) the batch's mean
    exit probability a pass.  ``wrong`` names a deliberately wrong variant
    (``WRONG``), for the tests that a comparison catches it."""
    import jax
    import jax.numpy as jnp

    b, s = tokens.shape
    n, t = b * s, cfg["total_ut_steps"]
    group = params["layers"]["l0"][KIND]
    run = jax.checkpoint(functools.partial(_layer, cfg=cfg, wrong=wrong))
    x, hs = params["embed"][tokens], []
    for _ in range(1 if wrong == "one_pass" else t):
        for i in range(cfg["layers_here"]):     # the same leaves every pass
            x = run({k: v[i] for k, v in group.items()}, x)
        h = ok._norm(x, params["final_norm"], cfg["rms_norm_eps"])
        hs.append(h)
        if wrong != "no_pass_norm":
            x = h
    hs = jnp.stack(hs * t if wrong == "one_pass" else hs).reshape(t, n, -1)
    rows = ok._head(hs.reshape(t * n, -1), params["head"],
                    jnp.tile(labels[:, :s].reshape(n), t),
                    cfg.get("loss_block_rows", 1024)).reshape(t, n, 2)
    ce = rows[..., 0] - rows[..., 1]
    gate = hs @ params["exit_gate"]["w"] + params["exit_gate"]["b"][0]
    p = exit_probabilities(gate, wrong)
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1)),
                                 0), axis=0)
    expected = jnp.mean(jnp.sum(p * ce, axis=0))
    bonus = jnp.asarray(cfg["exit_beta"], x.dtype) * jnp.mean(entropy)
    total = jnp.mean(ce[-1]) if wrong == "last_pass_loss" \
        else expected - bonus
    return total, {
        "losses": jnp.concatenate([jnp.stack([total]), jnp.mean(ce, axis=1),
                                   jnp.stack([expected, bonus])]),
        "rows": rows.transpose(1, 0, 2), "exit_mean": jnp.mean(p, axis=1)}


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, wrt: tuple, wrong):
    import jax

    cfg = dict(cfg_items)

    def run(params, tokens, labels):
        diff = {n: leaf_of(params, n) for n in wrt}

        def loss(diff):
            merged = jax.tree.map(lambda a: a, params)      # a copy's dicts
            for n, a in diff.items():
                put_leaf(merged, n, a)
            return loss_parts(merged, tokens, labels, cfg, wrong)

        with jax.default_matmul_precision("highest"):
            (_, aux), g = jax.value_and_grad(loss, has_aux=True)(diff)
        return aux, g

    return jax.jit(run)


def reference_step(params, tokens, labels, cfg: dict, bias: dict,
                   wrt: tuple, wrong: str | None = None,
                   routed=None) -> dict:
    """One step's statistics from the reference, in the form ``step_stats``
    puts a program's in: ``losses``, ``rows``, ``exit_mean``, and for each
    leaf of ``wrt`` its gradient's ``grad_sq`` and ``grad_probe``; ``grads``
    holds the whole gradients of ``wrt``.  ``bias`` and ``routed`` are the
    kind's: nothing routes, and they are not read.  Parameters given in
    bfloat16 make the **control**: the same model computed throughout in
    the nearest precision below the one the configuration states."""
    import jax.numpy as jnp

    if wrong is None and leaf_of(params, "head").dtype == jnp.float32:
        # what the whole-model controls run again (``precision_want``): the
        # float32 reference's own batch, never a control's
        _STEP.update(params=params, tokens=tokens, labels=labels, wrt=wrt)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    aux, g = _grad_program(items, tuple(wrt), wrong)(params, tokens, labels)
    aux, g = ({k: v.astype(jnp.float32) for k, v in t.items()}
              for t in (aux, g))
    flat = {n: g[n].reshape(-1) for n in wrt}
    return {**aux, "grads": g,
            "grad_sq": {n: jnp.sum(f * f) for n, f in flat.items()},
            "grad_probe": {n: f[probe_positions(n, f.shape[0])]
                           for n, f in flat.items()}}


def step_stats(aux: dict, bias_after: dict, cfg: dict) -> dict:
    """A program step's ``aux`` (``parallel/train.py``: raw statistics) in
    the reference's form (``bias_after``, the kind's, holds nothing for this
    model).  ``tools/kit_check.py`` prints a share cell's load from the
    ``aux`` it hands over here: a step that routes nothing reports none, so
    the 0 slots it would read are set there."""
    aux.setdefault("local_slots", np.float32(0.0))
    out = {k: np.asarray(aux[k]) for k in ("losses", "rows", "exit_mean")}
    for k in ("grad_sq", "grad_probe"):
        out[k] = dict(zip(leaves(cfg), np.asarray(aux[k])))
    return out


def compared(stats: dict, cfg: dict, wrt: tuple) -> dict:
    """What a check compares of one step's statistics, each in its unit: the
    total, every pass's cross-entropy and the expected one, each less
    ln(``vocab_size``), times ``LOSS_SCALE``; the entropy bonus over
    ``exit_beta`` times ``ENTROPY_SCALE``; every pass's logsumexp averaged
    over quarters of the rows, less ln(``vocab_size``), times ``LSE_SCALE``
    and the label's logit averaged likewise times ``LABEL_SCALE``; the batch's mean exit probability a pass
    over ``EXIT_SCALE``; and for the leaves of ``wrt`` the gradient's RMS as
    log10 over ``RMS_UNIT`` and, but for ``RMS_ONLY``, its probed entries in
    units of ``PROBE_UNIT`` RMS, or of ``PROBE_UNIT / HOT_ENTRY`` times the
    largest of them where that is more."""
    rows = np.asarray(stats["rows"], np.float32)            # (n, T, 2)
    sizes = leaf_sizes(cfg)
    rms = np.maximum(1e-30, np.sqrt(
        [float(stats["grad_sq"][n]) / sizes[n] for n in wrt]))
    entries = [i for i, n in enumerate(wrt) if n not in RMS_ONLY]
    probe = np.stack([np.asarray(stats["grad_probe"][wrt[i]])
                      for i in entries])
    scale = PROBE_UNIT * np.maximum(rms[entries],
                                    np.abs(probe).max(axis=1) / HOT_ENTRY)
    losses = np.asarray(stats["losses"], np.float64)
    uniform = np.log(cfg["vocab_here"])
    beta = cfg["exit_beta"] or 1.0
    means = rows.reshape((ROW_BLOCKS, -1) + rows.shape[1:]).astype(
        np.float64).mean(axis=1)                            # (blocks, T, 2)
    return {k: np.asarray(v, np.float32) for k, v in {
        "losses": np.append(LOSS_SCALE * (losses[:-1] - uniform),
                            ENTROPY_SCALE * losses[-1] / beta),
        "exit_mean": np.asarray(stats["exit_mean"], np.float64) / EXIT_SCALE,
        "lse_means": (means[..., 0] - uniform) * LSE_SCALE,
        "label_means": means[..., 1] * LABEL_SCALE,
        "grad_log_rms": np.log10(rms) / RMS_UNIT,
        "grad_probe": probe / scale[:, None]}.items()}


# -- the float32 parts of a step, read from the step alone -----------------------
def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made at ``sample_rows``, in units of
    ``SAMPLE_UNIT``: every pass's logsumexp and label's logit, the first
    query and key head of every layer application behind RoPE (``rope_qk``,
    times ``ROPE_SCALE``), the gate's product, the exit distribution and its
    entropy."""
    s = aux["sample"]
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "head_rows": np.asarray(aux["rows"])[at],
        "rope_qk": np.asarray(s["attn_qk"]) * ROPE_SCALE,
        "exit_logit": s["exit_logit"], "exit_p": aux["exit_p"],
        "exit_entropy": s["exit_entropy"]}.items()}


def exit_rows(gate: np.ndarray) -> tuple:
    """(p, the entropy) (R, T), (R,) of the gate's products ``gate`` (R, T)
    in float64, as the definition: products of sigmoids."""
    lam = 1.0 / (1.0 + np.exp(-gate))
    left, out = np.ones(gate.shape[0]), []
    for t in range(gate.shape[1] - 1):
        out.append(lam[:, t] * left)
        left = left * (1.0 - lam[:, t])
    p = np.stack(out + [left], axis=1)
    safe = np.where(p > 0, p, 1.0)
    return p, -np.sum(p * np.log(safe), axis=1)


def precision_want(aux: dict, by_name: dict, bias_before, head, labels,
                   cfg: dict, variant: str | None = None) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own inputs
    to each part** at the precision the configuration states: every pass's
    head rows from the rows the head read against ``head`` (d, V)
    (``olmoekit._head_program``: inputs rounded to the compute type, every
    product exact); ``rope_qk`` from the step's own projected q and k in
    float64 (``smallthinkerkit.rope_rows``, every application turned); the
    gate's product from the step's own ``h_t`` rows and the gate's leaves
    (``by_name``) in float64, the distribution from the step's own gate
    products and the entropy from those, as the definitions.  ``bias_before``
    is the kind's and is not read.  ``variant`` gives a **control**, which has
    to lie outside: ``bf16`` (the head, the gate's product and the
    distribution as a bfloat16 implementation would have made them), and the
    whole-model ones (``WHOLE_CONTROLS``), each of which runs the reference
    again on the last checked batch as that wrong model and returns what
    ``compared`` makes of it."""
    import jax.numpy as jnp

    if variant in WHOLE_CONTROLS:
        out = reference_step(_STEP["params"], _STEP["tokens"],
                             _STEP["labels"], cfg, {}, _STEP["wrt"],
                             wrong=variant)
        return compared({k: np.asarray(v) if not isinstance(v, dict) else v
                         for k, v in out.items() if k != "grads"}, cfg,
                        _STEP["wrt"])
    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()}
    low = ok._bf16 if variant == "bf16" else (
        lambda a: np.asarray(a, np.float64))
    n = np.asarray(aux["rows"]).shape[0]
    at = sample_rows(n)
    t, d = s["head_in"].shape[1:]
    lab = np.repeat(np.asarray(labels)[:, :-1].reshape(-1)[at], t)
    rows, head_logits = ok._head_program(cfg["compute_dtype"])(
        jnp.asarray(aux["sample"]["head_in"]).reshape(-1, d), head,
        jnp.asarray(lab))
    if variant == "bf16":           # the head's logits kept in bfloat16
        hl = ok._bf16(head_logits)
        top = hl.max(axis=-1)
        picked = np.take_along_axis(hl, lab[:, None], -1)[:, 0]
        rows = low(np.stack([top + np.log(np.exp(
            hl - top[:, None]).sum(axis=-1)), picked], axis=-1))
    on = [True] * s["attn_qk_in"].shape[0]
    qk = rope_rows(s["attn_qk_in"], on, at, cfg["seq_len"],
                   cfg["rope_theta"])
    gate = low(low(s["head_in"]) @ low(np.asarray(by_name["exit_gate.w"]))
               + np.float64(np.asarray(by_name["exit_gate.b"])[0]))
    p, entropy = exit_rows(s["exit_logit"])  # from the step's own products
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "head_rows": np.asarray(rows, np.float64).reshape(-1, t, 2),
        "rope_qk": qk * ROPE_SCALE, "exit_logit": gate, "exit_p": low(p),
        "exit_entropy": low(entropy)}.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (``olmoekit.adamw_leaf`` with this
    model's undecayed leaves: every gain and the gate)."""
    return ok.adamw_leaf("ln1" if name.rsplit(".", 1)[-1] in UNDECAYED
                         else "matrix", p, g, cfg)


# -- operations counted from the shapes -------------------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token meets in one layer application's parts and
    in one pass's head."""
    per = layer_sizes(cfg)
    return {"attn_proj": sum(per[k] for k in ("wq", "wk", "wv", "wo")),
            "dense_mlp": sum(per[k] for k in ("gate", "up", "down")),
            "head": cfg["hidden_size"] * cfg["vocab_here"]}


def causal_pairs(cfg: dict) -> int:
    """The (query, key) pairs one sequence's causal attention sees: s (s +
    1) / 2."""
    return cfg["seq_len"] * (cfg["seq_len"] + 1) // 2


def attention_forward_flops(cfg: dict) -> float:
    """Attention's forward FLOP a step over the causal pairs: q k^T and p v
    over the head width, 2 x 2 x head width x heads a pair, every layer
    application."""
    return float(cfg["micro_batch"] * causal_pairs(cfg) * 4 * cfg["head_dim"]
                 * cfg["num_attention_heads"] * cfg["layers_here"]
                 * cfg["total_ut_steps"])


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters a
    token meets x tokens x the ``total_ut_steps`` x ``layers_here`` layer
    applications; attention at three times its forward over the causal
    pairs; the head 6 x its parameters x tokens x the passes (every pass's
    rows go through it).  Recomputed layers, the masked halves of the
    diagonal tiles, the backward kernel's second q k^T, the gate, the norms
    and the optimiser's work are not model FLOP and lower the share.
    ``flash_forward`` and ``attn_backward`` are what the two kernels have to
    compute of the causal pairs: the forward's two products, and the fused
    backward's five (2.5 times the forward), so that neither's share of the
    peak can read over 100% however the kernels mask."""
    tokens = cfg["micro_batch"] * cfg["seq_len"]
    passes = cfg["total_ut_steps"]
    applications = passes * cfg["layers_here"]
    per = matmul_params_per_token(cfg)
    forward = attention_forward_flops(cfg)
    parts = {
        "attn_proj": 6.0 * per["attn_proj"] * tokens * applications,
        "dense_mlp": 6.0 * per["dense_mlp"] * tokens * applications,
        "attention": 3.0 * forward,
        "head": 6.0 * per["head"] * tokens * passes}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = forward
    parts["attn_backward"] = 2.5 * forward
    return parts
