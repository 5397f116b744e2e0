"""What the ``train_step_kit`` call kind reads for LFM2-8B-A1B: the
benchmark's own copy of the plain reference of its training step on one
chip's share of a 4-chip expert-parallel deployment, written independently
of the program (``ompi_tpu.parallel``), what a check compares and in which
units, and the functions that count a step's model FLOP.  The batch (Zipf
ids), the probe and sample rules, RoPE and the blocked head are
``harness/olmoekit``'s, a grouped tree's leaf by its name
``harness/nemotronkit``'s: a kit states a model, not a second harness.

The equations are ``lfm2_moe``'s (the published ``config.json`` and the
model's code).  ``norm(x) = x / sqrt(mean(x^2) + norm_eps) * gain``; no
bias anywhere.  Every layer is ``h = x + Op(norm_op(x))``, then ``out = h
+ FFN(norm_ffn(h))``.  **conv**: ``[B | C | u] = n W_in`` in that order;
``z_t = sum_{j=0..2} w_j (B * u)_{t-2+j}``, a depthwise causal convolution
of ``conv_L_cache`` 3 taps a channel, zeros before the sequence's start,
no bias, no activation; ``Op = (C * z) W_out``.  **full_attention**: q
(32 heads x 64), k and v (8 heads x 64); ``norm`` with a gain over each
head's 64 of q and of k; RoPE (rotate-half over all 64, ``rope_theta``, no
scaling); causal ``softmax(q k^T / 8) v`` in full, every key-value head
read by 4 query heads; ``W_o``.  **FFN** of the model's first
``num_dense_layers`` layers: SwiGLU of ``intermediate_size``; of the
others ``s = sigmoid(n W_r)`` over all the experts, the
``num_experts_per_tok`` largest of ``s + b`` (``b`` the balancing bias:
the choice only), weights ``routed_scaling_factor * s_chosen /
sum(s_chosen)``, SwiGLU experts of ``moe_intermediate_size``, no shared
expert.  After the last layer one ``norm``, then logits **against the
embedding matrix itself**.  After a step ``b += gamma * sign(mean load -
load)``.  Everything float32, every matmul at the highest precision, no
kernel, no sort, the convolution as three shifted products.  Departures:

* **the share** (the configuration's ``experts_here``, ``expert_share``,
  ``vocab_here``): every held expert on every token under a dense mask of
  the router's choice among **all** the experts; what the absent experts
  would add is left out; operators, dense MLP, routers and norms are
  whole; embedding, logits and loss are over the slice of the vocabulary;
* the convolution is never reset and attention never masked between packed
  documents; the loads that move the bias are the whole batch's;
* at the published widths the (s, s) scores of all heads, the (E, T, f)
  activations of all held experts, the (T, V) logits and six layers'
  activations do not fit beside the program's parameters, so attention
  runs one (batch, head) at a time, the experts one after the other, the
  head by blocks of rows, and every layer is recomputed in the backward
  pass (``lax.map`` / ``lax.scan`` / ``jax.checkpoint``).  The arithmetic
  of every element is the same; only what is held at once differs.
"""
from __future__ import annotations

import functools

import numpy as np

from harness import olmoekit as ok
from harness.nemotronkit import leaf_of, put_leaf  # noqa: F401
from harness.olmoekit import (PROBE_UNIT, REGRET_UNIT, RMS_UNIT,  # noqa: F401
                              ROW_BLOCKS, SAMPLE_UNIT, probe_positions,
                              rank_order, sample_rows, tokens_of, zipf_cdf)

# a layer's letter by its operator and feed-forward, and the group it goes
# by in the program's parameter tree (``parallel/train.PATTERN_KINDS``)
KINDS = {"c": "conv_dense", "a": "attn_dense", "C": "conv_moe",
         "A": "attn_moe"}
OPERATOR = {"conv": ("ln1", "in_proj", "conv_w", "out_proj"),
            "attn": ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm")}
FFN = {"dense": ("ln2", "gate", "up", "down"),
       "moe": ("ln2", "router", "gate", "up", "down")}
GAINS = ("ln1", "ln2", "q_norm", "k_norm", "final_norm")
WRONG = ("softmax", "bias_in_weights", "unnormalised", "no_rope", "untied",
         "qk_norm_whole_width", "conv_two_taps", "conv_ungated")
OUTPUTS = ("losses", "load_share", "local_share", "row_means",
           "route_regret", "bias", "grad_log_rms", "grad_probe")
PRECISION = ("router_logits", "router_scores", "router_weights",
             "head_rows", "conv_y", "rope_qk")
# the variants of ``precision_want`` that are controls (tools/kit_check.py)
PART_CONTROLS = ("bf16", "conv_bf16", "bias_in_weights", "softmax",
                 "untied", "no_rope")
BIAS_UNIT = 1.0         # a balancing bias in units of gamma (joyaikit's)
# a leaf whose largest probed entry is over this many RMS is probed in
# units of that entry (``compared``, as ``nemotronkit``'s)
HOT_ENTRY = 32.0
# a convolution layer's C * z in units of SAMPLE_UNIT over this: it is of
# order one at initialisation (B, C, u and z of standard deviation 0.9), so
# one step of the tolerance's atol is 5e-5 of it and its rtol does the
# work: float32 reads a thousandth of the tolerance on the chip, bfloat16
# gates and taps 56 to 106 (PERF.md 2)
CONV_SCALE = 1.0
# a head's q and k behind RoPE in units of SAMPLE_UNIT over this: an entry
# is of order one, and a float32 angle at position 8,191 is good to its
# last bit and no further: 5,324 rad at the second frequency, whose last
# bit is 4.9e-4 rad, on top of the last bit of the inverse frequency
# (3e-4 rad there).  Turned by that much a pair of size 3 moves by 3e-3
# whatever the entry's own size (read on the chip: up to 2.6 tolerances at
# a scale of 1, PERF.md 2), so the tolerance's atol is 5e-3 of an entry:
# float32 lies inside, q and k left unrotated 350 times outside
ROPE_SCALE = 0.05
# the untied control's head: drawn from this seed at the configuration's
# init_std, as a second matrix would be
UNTIED_SEED = 20251007


def load_config(path: str) -> dict:
    """The configuration file as the reference reads it (``olmoekit``'s:
    the published keys, ``layers_here`` and the ``train`` group, flat),
    with ``num_experts`` also under DeepSeek-V3's name, by which
    ``tools/kit_check.py`` reads a share cell's experts."""
    cfg = ok.load_config(path)
    return {**cfg, "n_routed_experts": cfg["num_experts"]}


def pattern(cfg: dict) -> str:
    """The held layers' letters: by ``layer_types`` a ``c`` or an ``a``,
    small in the model's first ``num_dense_layers`` layers, capital
    behind them."""
    first = cfg["first_layer_here"]
    letters = ["c" if kind == "conv" else "a" for kind in cfg["layer_types"]]
    return "".join(c if i < cfg["num_dense_layers"] else c.upper()
                   for i, c in enumerate(letters)
                   )[first:first + cfg["layers_here"]]


def segments(cfg: dict) -> list:
    """The held layers as runs of like layers, ``(letter, repeats, first
    layer)``: the rule by which the program's parameter tree is grouped
    (``parallel/train.ModelConfig.segments`` for a ``layer_types`` model),
    stated again."""
    out = []
    for i, c in enumerate(pattern(cfg)):
        if out and out[-1][0] == c:
            out[-1][1] += 1
        else:
            out.append([c, 1, i])
    return [tuple(run) for run in out]


def layer_leaves(letter: str) -> tuple:
    op, ffn = KINDS[letter].split("_")
    return OPERATOR[op] + FFN[ffn]


def leaves(cfg: dict) -> tuple:
    """Every trained leaf's name, in the order the program reports them
    (``l<first layer>.<kind>.<leaf>``, stacked over a run's repeats); the
    tied matrix goes by ``embed`` and there is no ``head``."""
    return ("embed",) + tuple(
        f"l{first}.{KINDS[c]}.{leaf}" for c, _, first in segments(cfg)
        for leaf in layer_leaves(c)) + ("final_norm",)


def checked(cfg: dict) -> tuple:
    """The leaves whose gradients a check compares: of the first run that
    has it, a convolution's ``W_in``, taps and ``W_out``, attention's four
    matrices and two QK-norm gains, the dense MLP's three, the router and
    the experts' three; of the **last** run of each operator (another run
    of the walk) the leaves nearest the residual stream, and of the last
    convolution run its taps too; final norm and the tied matrix.  Between
    them their gradients cross every sublayer's backward pass."""
    runs = segments(cfg)
    out = []
    for part, table in (("conv", OPERATOR), ("attn", OPERATOR),
                        ("dense", FFN), ("moe", FFN)):
        mine = [(c, first) for c, _, first in runs
                if part in KINDS[c].split("_")]
        if not mine:
            continue
        c, first = mine[0]
        out += [f"l{first}.{KINDS[c]}.{leaf}" for leaf in table[part]
                if leaf not in ("ln1", "ln2")]
        c, last = mine[-1]
        if last != first and part in OPERATOR:
            out += [f"l{last}.{KINDS[c]}.{leaf}" for leaf in {
                "conv": ("conv_w", "out_proj"), "attn": ("wo",)}[part]]
            out.append(f"l{last}.{KINDS[c]}.down")
    return tuple(dict.fromkeys(out)) + ("final_norm", "embed")


def probed(cfg: dict) -> tuple:
    """The checked leaves whose gradient is also compared entry by entry:
    all of them, the tied matrix's at its head-side rows (``compared``)."""
    return checked(cfg)


def tree_of(by_name: dict) -> dict:
    """The parameter tree from {leaf name: array}.  ``head`` (d, V) is
    the tied matrix transposed, for a reader of the head's rows
    (``precision_want``): the model below reads ``embed`` for both uses."""
    tree: dict = {}
    for name, a in by_name.items():
        put_leaf(tree, name, a)
    tree["head"] = tree["embed"].T
    return tree


def held(cfg: dict) -> dict:
    """The experts this rank holds."""
    return {"experts": cfg["experts_here"] or cfg["num_experts"],
            "first_expert": cfg["expert_share"] * cfg["experts_here"]}


def layer_sizes(cfg: dict) -> dict:
    """Elements of one layer's leaves, by part."""
    d, hd = cfg["hidden_size"], \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    kv, ff = cfg["num_key_value_heads"] * hd, cfg["intermediate_size"]
    f, e = cfg["moe_intermediate_size"], held(cfg)["experts"]
    return {"conv": {"ln1": d, "in_proj": d * 3 * d,
                     "conv_w": cfg["conv_L_cache"] * d, "out_proj": d * d},
            "attn": {"ln1": d, "wq": d * d, "wk": d * kv, "wv": d * kv,
                     "wo": d * d, "q_norm": hd, "k_norm": hd},
            "dense": {"ln2": d, "gate": d * ff, "up": d * ff,
                      "down": ff * d},
            "moe": {"ln2": d, "router": d * cfg["num_experts"],
                    "gate": e * d * f, "up": e * d * f, "down": e * f * d}}


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this rank holds."""
    per = layer_sizes(cfg)
    out = {"embed": cfg["vocab_here"] * cfg["hidden_size"]}
    for c, n, first in segments(cfg):
        for part in KINDS[c].split("_"):
            out.update({f"l{first}.{KINDS[c]}.{leaf}": n * size
                        for leaf, size in per[part].items()})
    out["final_norm"] = cfg["hidden_size"]
    return out


# -- the reference -------------------------------------------------------------
def _short_conv(p, x, cfg, wrong):
    """``Op`` of a conv layer: the three taps as shifted products."""
    import jax.numpy as jnp

    d, s = x.shape[-1], x.shape[1]
    bcu = ok._norm(x, p["ln1"], cfg["norm_eps"]) @ p["in_proj"]
    gate_b, gate_c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    a = u if wrong == "conv_ungated" else gate_b * u
    taps = cfg["conv_L_cache"]
    z = jnp.zeros_like(a)
    for j in range(1 if wrong == "conv_two_taps" else 0, taps):
        back = taps - 1 - j
        if back < s:
            z = z.at[:, back:].add(a[:, :s - back] * p["conv_w"][j])
    return (gate_c * z) @ p["out_proj"]


def _attention(p, x, cfg, wrong):
    """``Op`` of a full_attention layer, one (batch, head) at a time
    (``olmoekit._attention``)."""
    import jax.numpy as jnp

    b, s, d = x.shape
    nh, nkv, eps = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["norm_eps"]
    h = ok._norm(x, p["ln1"], eps)
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q, k = h @ p["wq"], h @ p["wk"]
    if wrong == "qk_norm_whole_width":      # OLMoE's: before the split
        tile = lambda g, n: jnp.tile(g, n)
        q = heads(ok._norm(q, tile(p["q_norm"], nh), eps), nh)
        k = heads(ok._norm(k, tile(p["k_norm"], nkv), eps), nkv)
    else:
        q = ok._norm(heads(q, nh), p["q_norm"], eps)
        k = ok._norm(heads(k, nkv), p["k_norm"], eps)
    if wrong != "no_rope":
        q, k = (ok._rope(t, cfg["rope_theta"]) for t in (q, k))
    k, v = (jnp.repeat(t, nh // nkv, axis=1)
            for t in (k, heads(h @ p["wv"], nkv)))
    o = ok._attention(q, k, v)
    return o.transpose(0, 2, 1, 3).reshape(b, s, d) @ p["wo"]


def _expert_ffn(p, x, bias, cfg, wrong, routed):
    """(``FFN`` of a sparse layer, the slots every expert received, the
    routing's regret)."""
    import jax
    import jax.numpy as jnp

    b, s, d = x.shape
    e, k_top = cfg["num_experts"], cfg["num_experts_per_tok"]
    h = ok._norm(x, p["ln2"], cfg["norm_eps"]).reshape(b * s, d)
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
    y = ok._experts(h, weight[:, first:first + here["experts"]], p["gate"],
                    p["up"], p["down"])
    return y.reshape(b, s, d), jnp.sum(choice, axis=(0, 1)), regret


def untied_head(shape, cfg: dict, dtype):
    """The untied control's head (d, V): a second matrix, drawn as the
    first was."""
    import jax

    return (cfg.get("init_std", 0.02) * jax.random.normal(
        jax.random.PRNGKey(UNTIED_SEED), shape)).astype(dtype)


def loss_parts(params, tokens, labels, cfg: dict, bias: dict,
               wrong: str | None = None, routed=None):
    """(loss, {losses, loads, rows, regret}) of one batch, in the
    parameters' own type throughout (float32; bfloat16 for the control).
    ``labels`` may be one longer than ``tokens`` (the batch's form for a
    model with a next-next-token head): the first ``s`` are read.
    ``bias`` {layers (L, E)}.  With ``routed`` (L, T, k), the experts a
    program chose, the top k is not taken here but given, and ``regret``
    says how far that choice is from this model's own under its own
    scores plus bias (``olmoekit.loss_parts``).  ``wrong`` names a
    deliberately wrong variant (``WRONG``), for the tests and controls
    that a comparison catches it."""
    import jax
    import jax.numpy as jnp

    b, s = tokens.shape
    x = params["embed"][tokens]
    operator = {
        "conv": jax.checkpoint(
            lambda p, x: x + _short_conv(p, x, cfg, wrong)),
        "attn": jax.checkpoint(
            lambda p, x: x + _attention(p, x, cfg, wrong))}

    @jax.checkpoint
    def dense(p, x):
        h = ok._norm(x, p["ln2"], cfg["norm_eps"])
        return x + (jax.nn.silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]

    sparse = jax.checkpoint(lambda p, x, row, chosen: _expert_ffn(
        p, x, row, cfg, wrong, chosen))
    loads, regrets = [], []
    for c, n, first in segments(cfg):
        group = params["layers"][f"l{first}"][KINDS[c]]
        op, ffn = KINDS[c].split("_")
        for i in range(n):
            p = {k: v[i] for k, v in group.items()}
            x = operator[op](p, x)
            if ffn == "dense":
                x = dense(p, x)
                continue
            j = len(loads)
            y, load, regret = sparse(p, x, bias["layers"][j],
                                     None if routed is None else routed[j])
            x = x + y
            loads.append(load)
            regrets.append(regret)
    h = ok._norm(x, params["final_norm"], cfg["norm_eps"]).reshape(b * s, -1)
    head = params["embed"].T        # the tied head: the one matrix again
    if wrong == "untied":
        head = untied_head(head.shape, cfg, head.dtype)
    rows = ok._head(h, head, labels[:, :s].reshape(b * s),
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
def _grad_program(cfg_items: tuple, wrt: tuple, wrong):
    import jax

    cfg = dict(cfg_items)

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


def embed_rows_read(tokens, cfg: dict):
    """Whether each probed entry of the tied matrix lies in a row that
    ``tokens`` read: there the gradient is the gather's and the head's
    together, elsewhere the head's alone."""
    import jax.numpy as jnp

    size = cfg["vocab_here"] * cfg["hidden_size"]
    rows = probe_positions("embed", size) // cfg["hidden_size"]
    return jnp.isin(jnp.asarray(rows), tokens)


def reference_step(params, tokens, labels, cfg: dict, bias: dict,
                   wrt: tuple, wrong: str | None = None,
                   routed=None) -> dict:
    """One step's statistics from the reference, in the form
    ``step_stats`` puts a program's in: ``losses``, ``loads``, ``rows``,
    ``regret``, ``bias`` (after the update), ``embed_probe_read``, and for
    each leaf of ``wrt`` its gradient's ``grad_sq`` and ``grad_probe``;
    ``grads`` holds the whole gradients of ``wrt`` (the tied matrix's: the
    sum of both uses).  Parameters given in bfloat16 make the **control**:
    the same model computed throughout in the nearest precision below the
    one the configuration states."""
    import jax.numpy as jnp

    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    items += (("layer_types", tuple(cfg["layer_types"])),)
    aux, g = _grad_program(items, tuple(wrt), wrong)(
        params, tokens, labels, bias, routed)
    aux, g = ({k: v.astype(jnp.float32) for k, v in t.items()}
              for t in (aux, g))
    flat = {n: g[n].reshape(-1) for n in wrt}
    f32 = {k: v.astype(jnp.float32) for k, v in bias.items()}
    return {**aux, "grads": g, "bias": bias_step(f32, aux["loads"], cfg),
            "embed_probe_read": embed_rows_read(tokens, cfg),
            "grad_sq": {n: jnp.sum(f * f) for n, f in flat.items()},
            "grad_probe": {n: f[probe_positions(n, f.shape[0])]
                           for n, f in flat.items()}}


def step_stats(aux: dict, bias_after: dict, cfg: dict) -> dict:
    """A program step's ``aux`` (``parallel/train.py``: raw statistics)
    and the biases its state holds after it, in the reference's form.  A
    step routes every token to its own top k, so its regret is 0 by
    definition."""
    out = {k: np.asarray(aux[k]) for k in ("loads", "rows",
                                           "embed_probe_read")}
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
    label logit averaged over quarters of the rows; the routing's regret;
    the biases after the update in units of gamma; and for the leaves of
    ``wrt`` the gradient's RMS as log10 over ``RMS_UNIT`` and its probed
    entries in units of ``PROBE_UNIT`` RMS, or of ``PROBE_UNIT /
    HOT_ENTRY`` times the largest of them where that is more
    (``nemotronkit.compared`` says why).  **The tied matrix** is compared
    by its RMS, which holds both uses' sum, and entry by entry **where the
    two uses can be told apart**: at the probed entries in rows that no
    token of the batch read (``embed_probe_read`` false), whose gradient
    is the head's alone, one matmul from its inputs, in units of
    ``PROBE_UNIT`` times those entries' own RMS.  An entry in a row
    the gather read carries the whole backward pass's bfloat16 error of
    its row's size, hundreds of times a rare row's (why every cell
    compares an embedding by its RMS): those are set to zero on both
    sides."""
    rows = np.asarray(stats["rows"], np.float32)
    sizes = leaf_sizes(cfg)
    # a leaf no token reached (a run's held experts with no slot) reads
    # the floor on both sides
    rms = np.maximum(1e-30, np.sqrt(
        [float(stats["grad_sq"][n]) / sizes[n] for n in wrt]))
    probe = np.stack([np.asarray(stats["grad_probe"][n]) for n in wrt])
    unit = rms.copy()
    if "embed" in wrt:
        # the head's side alone, in units of those entries' own RMS: the
        # leaf's is the gather's hot rows', hundreds of times theirs
        at = wrt.index("embed")
        probe[at] = np.where(np.asarray(stats["embed_probe_read"]), 0.0,
                             probe[at])
        unit[at] = max(1e-30, np.sqrt(np.sum(probe[at] ** 2) / max(
            1, np.count_nonzero(probe[at]))))
    scale = PROBE_UNIT * np.maximum(unit, np.abs(probe).max(axis=1)
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
        "grad_log_rms": np.log10(rms) / RMS_UNIT,
        "grad_probe": probe / scale[:, None]}.items()}


# -- the float32 parts of a step, read from the step alone -----------------------
def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made at ``sample_rows``, in units of
    ``SAMPLE_UNIT``: the routers' logits, their sigmoid scores and the
    chosen weights (times 100, so that a step of 1e-4 is one unit), the
    head's logsumexp and label logit, what every convolution layer's
    gates and taps made of its first channels (``conv_y``, times
    ``CONV_SCALE``), and the first query and key-value head of every
    attention layer behind the QK-norm and RoPE (``rope_qk``, times
    ``ROPE_SCALE``)."""
    s = aux["sample"]
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "router_logits": s["router_logits"],
        "router_scores": np.asarray(s["router_scores"]) * 100.0,
        "router_weights": np.asarray(s["router_weights"]) * 100.0,
        "head_rows": np.asarray(aux["rows"])[at],
        "conv_y": np.asarray(s["conv_y"]) * CONV_SCALE,
        "rope_qk": np.asarray(s["attn_qk"]) * ROPE_SCALE}.items()}


def conv_rows(bcu, w, at, seq_len: int, low) -> np.ndarray:
    """``C * z`` (layers, rows ``at``, channels) of every convolution
    layer from what its gate path read: ``bcu`` (layers, T, B | C | u) of
    the sampled channels by token row, ``w`` (layers, taps, channels); a
    row's taps reach back inside its own sequence of ``seq_len`` rows and
    read zeros before its start.  Every product and sum through ``low``."""
    n, t, width = bcu.shape
    c = width // 3
    gate_b, gate_c, u = bcu[..., :c], bcu[..., c:2 * c], bcu[..., 2 * c:]
    a = low(gate_b * u)
    taps = w.shape[1]
    z = np.zeros((n, len(at), c))
    for j in range(taps):
        back = taps - 1 - j
        inside = (at % seq_len) >= back             # else before the start
        term = low(a[:, np.maximum(at - back, 0)] * w[:, j, None, :])
        z = low(z + np.where(inside[None, :, None], term, 0.0))
    return low(gate_c[:, at] * z)


def rope_rows(qk_in, q_gain, k_gain, at, seq_len: int, theta: float,
              eps: float, rotate: bool = True) -> np.ndarray:
    """The first query head and the first key-value head side by side
    (layers, rows ``at``, 2 hd) behind the per-head norm and RoPE, from
    the projections' results ``qk_in`` at those rows and the two gains
    (layers, hd), in float64; a row's position is its place in its own
    sequence.  The angles are made as a float32 implementation makes them
    (the inverse frequencies and their product with the position rounded
    to float32: at position 8,191 one more bit of either is 5e-4 rad),
    their cosines and sines in float64."""
    hd = qk_in.shape[-1] // 2
    inv = (np.float32(1.0) / np.float32(theta) ** (
        np.arange(0, hd, 2, dtype=np.float32) / np.float32(hd))
    ).astype(np.float32)
    ang = ((at % seq_len).astype(np.float32)[:, None] * inv[None, :]
           ).astype(np.float64)
    out = []
    for x, gain in ((qk_in[..., :hd], q_gain), (qk_in[..., hd:], k_gain)):
        x = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) \
            * gain[:, None, :]
        if rotate:
            x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
            x = np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
        out.append(x)
    return np.concatenate(out, -1)


def precision_want(aux: dict, by_name: dict, bias_before, head, labels,
                   cfg: dict, variant: str | None = None) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own
    inputs to each part** at the precision the configuration states: the
    routers' logits from the rows each router read and its weights
    (``by_name``: every leaf on the host) in float64; the sigmoid scores
    from the step's own logits and the chosen weights from those scores at
    the step's own choice, in float64; the head's rows from the rows the
    head read against ``head`` (d, V), the tied matrix transposed
    (``olmoekit._head_program``: inputs rounded to the compute type, every
    product exact); ``conv_y`` from the step's own B, C and u and the
    layer's taps in float64 (``conv_rows``); ``rope_qk`` from the step's
    own q and k and the two gains in float64 (``rope_rows``).  ``variant``
    gives a **control**, which has to lie outside: ``bf16`` (router,
    scores, weights and head as a bfloat16 implementation would have made
    them), ``conv_bf16`` (the gates and the taps in bfloat16),
    ``bias_in_weights`` (the weights taken from score + bias), ``softmax``
    (scores by a softmax over the experts), ``untied`` (the head's rows
    against a second, independently drawn matrix), ``no_rope`` (q and k
    behind their norm, not rotated)."""
    import jax.numpy as jnp

    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()}
    exact = lambda a: np.asarray(a, np.float64)
    low = ok._bf16 if variant == "bf16" else exact
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    of_part = lambda part, leaf: np.concatenate([
        np.asarray(by_name[f"l{first}.{KINDS[c]}.{leaf}"], np.float64)
        for c, _, first in segments(cfg) if part in KINDS[c].split("_")])
    logits = low(np.einsum("lrd,lde->lre", low(s["router_in"]),
                           low(of_part("moe", "router"))))
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
    if variant == "untied":
        head = untied_head(head.shape, cfg, head.dtype)
    rows, head_logits = ok._head_program(cfg["compute_dtype"])(
        jnp.asarray(aux["sample"]["head_in"]), head, jnp.asarray(lab))
    if variant == "bf16":           # the head's logits kept in bfloat16
        hl = ok._bf16(head_logits)
        top = hl.max(axis=-1)
        picked = np.take_along_axis(hl, lab[:, None], -1)[:, 0]
        rows = low(np.stack([top + np.log(np.exp(
            hl - top[:, None]).sum(axis=-1)), picked], axis=-1))
    channels = s["conv_y"].shape[-1]
    y = conv_rows(s["conv_bcu_seq"],
                  of_part("conv", "conv_w")[..., :channels], at,
                  cfg["seq_len"], ok._bf16 if variant == "conv_bf16"
                  else exact)
    qk = rope_rows(s["attn_qk_in"], of_part("attn", "q_norm"),
                   of_part("attn", "k_norm"), at, cfg["seq_len"],
                   cfg["rope_theta"], cfg["norm_eps"],
                   rotate=variant != "no_rope")
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "router_logits": logits, "router_scores": scores * 100.0,
        "router_weights": weights * 100.0,
        "head_rows": np.asarray(rows, np.float64),
        "conv_y": y * CONV_SCALE, "rope_qk": qk * ROPE_SCALE}.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (``olmoekit.adamw_leaf`` with
    this model's gains)."""
    return ok.adamw_leaf("ln1" if name.rsplit(".", 1)[-1] in GAINS
                         else "matrix", p, g, cfg)


# -- operations counted from the shapes -------------------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token meets in one layer's part of each kind
    and in the head; the held routed experts at the **mean** load
    (``experts_here`` / ``num_experts`` of a token's
    ``num_experts_per_tok`` slots land here)."""
    per = layer_sizes(cfg)
    return {"conv_proj": per["conv"]["in_proj"] + per["conv"]["out_proj"],
            "attn_proj": sum(per["attn"][k] for k in ("wq", "wk", "wv",
                                                       "wo")),
            "dense": sum(per["dense"][k] for k in ("gate", "up", "down")),
            "router": per["moe"]["router"],
            "experts_mean": 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * cfg["num_experts_per_tok"]
            * held(cfg)["experts"] / cfg["num_experts"],
            "head": cfg["hidden_size"] * cfg["vocab_here"]}


def attention_forward_flops(cfg: dict) -> float:
    """Causal attention's forward FLOP a step: q k^T and p v over the
    head width, the lower triangle only: 2 x b x query heads x 2 x head
    width x s^2 / 2 an attention layer."""
    b, s = cfg["micro_batch"], cfg["seq_len"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return float(b * cfg["num_attention_heads"] * 2 * hd * s * s
                 * pattern(cfg).lower().count("a"))


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters
    a token meets x tokens; causal attention at three times its forward;
    the held routed experts **at the mean load**; the tied head once (its
    matrix's other use is a gather).  The short convolution's gates and
    taps (7 multiply-adds a channel and position), recomputed layers, the
    masked half of diagonal attention blocks, the routers' float32
    matmuls at six bfloat16 passes and the optimiser's work are not model
    FLOP and lower the share."""
    held_pattern = pattern(cfg)
    n_conv, n_attn = (held_pattern.lower().count(c) for c in "ca")
    n_dense = sum(c.islower() for c in held_pattern)
    n_moe = len(held_pattern) - n_dense
    tokens = cfg["micro_batch"] * cfg["seq_len"]
    per = matmul_params_per_token(cfg)
    parts = {
        "conv_proj": 6.0 * per["conv_proj"] * tokens * n_conv,
        "attn_proj": 6.0 * per["attn_proj"] * tokens * n_attn,
        "attention": 3.0 * attention_forward_flops(cfg),
        "dense": 6.0 * per["dense"] * tokens * n_dense,
        "router": 6.0 * per["router"] * tokens * n_moe,
        "experts": 6.0 * per["experts_mean"] * tokens * n_moe,
        "head": 6.0 * per["head"] * tokens}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = attention_forward_flops(cfg)
    return parts
