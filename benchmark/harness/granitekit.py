"""What the ``train_step_kit`` call kind reads for granite-4.0-h-micro: the
benchmark's own copy of the plain reference of its padding-free training
step on one chip's share of a tensor-parallel pair, written independently of
the program (``ompi_tpu.parallel``), the batch (a packed row of documents),
what a check compares and in which units, and the functions that count a
step's model FLOP.  The probe and sample rules are ``harness/olmoekit``'s: a
kit states a model, not a second harness.

The equations are the family's modelling code's (transformers 4.57.6,
``models/granitemoehybrid/modeling_granitemoehybrid.py``) on the published
``config.json``.  ``norm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * gain``.
``x = embedding_multiplier * Embed(ids)``.  A layer: ``x <- x +
residual_multiplier * Mixer(norm_1(x))``, then ``x <- x + residual_multiplier
* W_down(silu(W_gate h) * W_up h)``, ``h = norm_2(x)``.  ``logits =
norm_f(x) Embed^T / logits_scaling``.  **attention**: q, k, v, o without
bias, every key-value head read by ``num_attention_heads /
num_key_value_heads`` query heads, **no rotary embedding**, scores ``q . k *
attention_multiplier``, causal softmax inside the query's document.
**mamba** (Mamba-2's mixer, arXiv:2405.21060): ``[z | xBC | dt] = h W_in``;
``xBC <- silu(conv(xBC) + b)``, depthwise, causal, a tap counted only where
the position it reads lies in the same document; x (heads x ``mamba_d_head``),
B and C (``mamba_d_state`` each, **one of each for all the heads**); ``dt <-
softplus(dt + dt_bias)``; ``A = -exp(A_log)``; a head's state ``H_t =
exp(dt_t A) H_(t-1) + dt_t x_t B_t^T``, ``H_(t-1)`` zero where t starts a
document, ``y_t = H_t C_t + D x_t``; ``y <- norm(y * silu(z))`` with one gain
over the held channels; ``y W_out``.  A position's document is the count of
end-of-document ids before it in its row.  Everything float32, every matmul
at the highest precision, no kernel, **the state-space layer one position at
a time** (no chunk's products).  Departures:

* **the share** (``mamba_heads_here``, ``heads_here``, ``vocab_here``): the
  held Mamba heads beside the one B/C group whole, the held query heads with
  the key-value heads they read, a slice of the vocabulary whose last row is
  the end-of-document id; what the absent heads would add is left out, and
  the gated norm is over the held channels (on the pair its sum of squares
  would be all-reduced: no code stands in for the absent chip);
* every row's loss counts, an end-of-document row's too;
* at the published widths a state a position (1 MB), the (s, s) scores of a
  head, the (T, V) logits and ten layers' activations do not fit beside the
  program's parameters, so the recurrence keeps the states of one block of
  ``mamba_chunk_size`` positions for its backward pass (the steps are the
  same, one a position), attention runs one head and ``ATTN_ROWS`` query
  rows at a time against every key under the mask, the head by blocks of
  rows, and every layer is recomputed in the backward pass.  The arithmetic
  of every element is the same; only what is held at once differs.
"""
from __future__ import annotations

import functools

import numpy as np

from harness import olmoekit as ok
from harness.olmoekit import (PROBE_UNIT, RMS_UNIT, ROW_BLOCKS,  # noqa: F401
                              SAMPLE_UNIT, probe_positions, sample_rows)

KINDS = {"mamba": "mamba_dense", "attention": "attn_dense"}
OPERATOR = {"mamba": ("norm", "in_proj", "conv_w", "conv_b", "dt_bias",
                      "A_log", "D", "gate_norm", "out_proj"),
            "attention": ("ln1", "wq", "wk", "wv", "wo")}
FFN = ("ln2", "gate", "up", "down")
UNDECAYED = ("norm", "gate_norm", "ln1", "ln2", "final_norm", "conv_b",
             "A_log", "D", "dt_bias")
# variants of the reference that are deliberately wrong: the scan's state
# carried across a document's start; the convolution's taps read across one;
# attention under the triangle alone; the scores' scale 1 / sqrt(head) in
# place of ``attention_multiplier``; a residual multiplier of one
WRONG = ("no_scan_reset", "no_conv_reset", "no_doc_mask", "sqrt_scale",
         "residual_one")
OUTPUTS = ("losses", "row_means", "conv_x", "grad_log_rms", "grad_probe")
PRECISION = ("head_rows", "ssm_y")
# the variants of ``precision_want`` that are controls (tools/kit_check.py):
# the float32 parts in bfloat16, the scan's decay and state in bfloat16, the
# scan of the first heads without its resets and, each run again as a whole
# model, the five wrong models
WHOLE_CONTROLS = WRONG
PART_CONTROLS = ("bf16", "scan_bf16", "scan_no_reset") + WHOLE_CONTROLS
# the tied matrix's gradient is compared by its RMS alone (``nemotronkit``
# says why: most of its rows no token reads, a hot row's entries carry the
# whole backward pass's error of the row's size)
RMS_ONLY = ("embed",)
# a leaf whose largest probed entry is over this many RMS is probed in units
# of that entry (``nemotronkit.HOT_ENTRY``)
HOT_ENTRY = 32.0
# the scan's result in units of SAMPLE_UNIT over this (``nemotronkit``'s)
SSM_SCALE = 5.0
# the first layer's convolution's output (the first held head's channels) at
# the rows behind a document's start, over four: an entry is of order one
# half, bfloat16 matmul inputs in front of it (``in_proj``'s) move it by up to
# 0.004 (read on the chip as it is, my chip runs, PR 69: 0.56 and 0.79 of the
# tolerance in a run's two checks, 0.14 and 0.20 at this unit), a tap read
# across the start by tenths (the control ``no_conv_reset``: PERF.md 2)
CONV_SCALE = 0.25
#: query rows a block of the reference's attention
ATTN_ROWS = 1024
#: documents a row is cut into at most (the lengths come from its leading
#: words; at a median of 1,024 a row of 16,384 holds about ten)
DOCS = 64
#: the documents' lengths (the traffic file's): log-normal with a median of
#: a sixteenth of the row and this sigma, clipped to a 1,024th of the row ..
#: the row: 1,024, and 16 .. 16,384, at a row of 16,384 (``length_law``)
SIGMA = 1.0
#: the rows behind a document's start that ``conv_x`` reads, of so many of
#: the batch's first documents
BEHIND, STARTS = 3, 8
#: the batch the last float32 ``reference_step`` ran on, for
#: ``precision_want``'s whole-model controls, which run it again from the
#: leaves they are handed: no parameter is kept here (2.6 GB held on the
#: device beside the trainer's state left the timed step no room: PR 69's
#: first chip run)
_STEP: dict = {}


def load_config(path: str) -> dict:
    """The configuration file as the reference reads it (``olmoekit``'s: the
    published keys, the share and the ``train`` group, flat), and no expert
    under the names the harness reads them by."""
    return {**ok.load_config(path), "experts_here": 0, "num_experts": 0,
            "n_routed_experts": 0}


# -- the batch: a packed row of documents -----------------------------------------
def zipf_cdf(vocab: int) -> np.ndarray:
    """The cumulative Zipf law over the slice's rows but its last, which is
    the end-of-document id's."""
    return ok.zipf_cdf(vocab - 1)


def rank_order(vocab: int, seed: int) -> np.ndarray:
    return ok.rank_order(vocab - 1, seed)


def length_law(seq_len: int) -> tuple:
    """(median, shortest, longest) of a document's length in a row of
    ``seq_len`` tokens."""
    return seq_len / 16.0, max(1, seq_len // 1024), seq_len


def document_lengths(bits):
    """The lengths (rows, ``DOCS``) int32 of a row's documents from its
    leading ``DOCS`` bit patterns (a row holds two ids more than its
    tokens): a word, mixed, is a uniform draw, its inverse normal CDF a
    standard normal z, and the length ``median exp(SIGMA z)`` rounded and
    clipped (``length_law``)."""
    import jax.numpy as jnp
    from jax.scipy.special import ndtri

    median, shortest, longest = length_law(bits.shape[1] - 2)
    words = bits[:, :DOCS].astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
    u = ((words >> 8).astype(jnp.float32) + 0.5) * (2.0 ** -24)
    return jnp.clip(jnp.round(median * jnp.exp(SIGMA * ndtri(u))),
                    shortest, longest).astype(jnp.int32)


def tokens_of(bits, cdf, order):
    """A batch's ids from its bit patterns alone, on the device: every
    position a Zipf id of the slice's other rows (``olmoekit.tokens_of``),
    then the end-of-document id (the slice's last row, ``order``'s length)
    at each document's last position, the documents laid end to end from the
    row's start with ``document_lengths``; the last is cut at the row's
    end."""
    import jax.numpy as jnp

    ids = ok.tokens_of(bits, cdf, order)
    ends = jnp.cumsum(document_lengths(bits), axis=1) - 1
    rows = jnp.arange(ids.shape[0])[:, None]
    return ids.at[rows, ends].set(order.shape[0], mode="drop")


def documents(tokens, eos: int):
    """``doc`` (b, s): the end-of-document ids before each position."""
    import jax.numpy as jnp

    ends = (tokens == eos).astype(jnp.int32)
    return jnp.cumsum(ends, axis=1) - ends


def boundary_rows(doc) -> np.ndarray:
    """The token rows ``conv_x`` reads, from the batch's documents ``doc``
    (b, s) on the host: the ``BEHIND`` rows from the start of each of the
    first ``STARTS`` documents that start behind another in their row
    (repeated from the first where the batch has fewer, row 0 where it has
    none), as rows of the flattened batch."""
    doc = np.asarray(doc)
    b, s = doc.shape
    starts = np.flatnonzero(np.concatenate(
        [np.zeros((b, 1), bool), doc[:, 1:] != doc[:, :-1]], axis=1))
    if not starts.size:
        starts = np.zeros(1, np.int64)
    starts = np.resize(starts[:STARTS], STARTS)
    return np.minimum((starts[:, None] + np.arange(BEHIND)).ravel(),
                      b * s - 1)


# -- the leaves ---------------------------------------------------------------------
def segments(cfg: dict) -> list:
    """The held layers as runs of like layers, ``(layer_types name, repeats,
    first held layer)``: a unit is one layer (it holds two sublayers)."""
    first = cfg["first_layer_here"]
    held, out = cfg["layer_types"][first:first + cfg["layers_here"]], []
    for i, name in enumerate(held):
        if out and out[-1][0] == name:
            out[-1][1] += 1
        else:
            out.append([name, 1, i])
    return [tuple(run) for run in out]


def layer_leaves(name: str) -> tuple:
    return OPERATOR[name] + FFN


def leaves(cfg: dict) -> tuple:
    """Every trained leaf's name, in the order the program reports them
    (``l<first layer>.<kind>.<leaf>``, stacked over a run's repeats); the
    tied matrix goes by ``embed`` and there is no ``head``."""
    return ("embed",) + tuple(
        f"l{first}.{KINDS[name]}.{leaf}" for name, _, first in segments(cfg)
        for leaf in layer_leaves(name)) + ("final_norm",)


def checked(cfg: dict) -> tuple:
    """The leaves whose gradients a check compares: of the first run of
    each operator every matrix and every scalar of the mixer, attention's
    four, and the SwiGLU's three behind the first; of the **last** run of
    Mamba layers (another run of the walk, behind the attention layer) the
    leaves nearest the residual stream and the convolution's taps; the final
    norm and the tied matrix.  Between them their gradients cross every
    sublayer's backward pass, the scan's on both sides of its state."""
    runs = segments(cfg)
    out = []
    for name in ("mamba", "attention"):
        mine = [first for kind, _, first in runs if kind == name]
        if not mine:
            continue
        out += [f"l{mine[0]}.{KINDS[name]}.{leaf}" for leaf in OPERATOR[name]
                if leaf not in ("norm", "ln1")]
        out += [f"l{mine[0]}.{KINDS[name]}.{leaf}"
                for leaf in (FFN[1:] if name == "mamba" else ("down",))]
        if mine[-1] != mine[0]:
            out += [f"l{mine[-1]}.{KINDS[name]}.{leaf}"
                    for leaf in ("conv_w", "out_proj", "down")]
    return tuple(out) + ("final_norm", "embed")


def probed(cfg: dict) -> tuple:
    """The checked leaves whose gradient is also compared entry by entry:
    all but ``RMS_ONLY``."""
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
    """The parameter tree from {leaf name: array}.  ``head`` (d, V) is the
    tied matrix transposed, for a reader of the head's rows
    (``precision_want``): the model below reads ``embed`` for both uses."""
    tree: dict = {}
    for name, a in by_name.items():
        put_leaf(tree, name, a)
    tree["head"] = tree["embed"].T
    return tree


def held(cfg: dict) -> dict:
    """What of each mixer this chip holds."""
    q = cfg["heads_here"] or cfg["num_attention_heads"]
    per_kv = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return {"mamba_heads": cfg["mamba_heads_here"] or cfg["mamba_n_heads"],
            "q_heads": q, "kv_heads": max(1, q // per_kv)}


def layer_sizes(cfg: dict) -> dict:
    """Elements of one layer's leaves, by its ``layer_types`` name."""
    d, f, here = cfg["hidden_size"], cfg["shared_intermediate_size"], held(cfg)
    inner = here["mamba_heads"] * cfg["mamba_d_head"]
    bc = 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    hd = d // cfg["num_attention_heads"]
    ffn = {"ln2": d, "gate": d * f, "up": d * f, "down": f * d}
    return {
        "mamba": {"norm": d,
                  "in_proj": d * (2 * inner + bc + here["mamba_heads"]),
                  "conv_w": cfg["mamba_d_conv"] * (inner + bc),
                  "conv_b": inner + bc, "dt_bias": here["mamba_heads"],
                  "A_log": here["mamba_heads"], "D": here["mamba_heads"],
                  "gate_norm": inner, "out_proj": inner * d, **ffn},
        "attention": {"ln1": d, "wq": d * here["q_heads"] * hd,
                      "wk": d * here["kv_heads"] * hd,
                      "wv": d * here["kv_heads"] * hd,
                      "wo": here["q_heads"] * hd * d, **ffn}}


def leaf_sizes(cfg: dict) -> dict:
    """Elements of every leaf this chip holds."""
    per = layer_sizes(cfg)
    out = {"embed": cfg["vocab_here"] * cfg["hidden_size"]}
    for name, n, first in segments(cfg):
        out.update({f"l{first}.{KINDS[name]}.{leaf}": n * size
                    for leaf, size in per[name].items()})
    out["final_norm"] = cfg["hidden_size"]
    return out


# -- the reference -------------------------------------------------------------
def _recurrence(x, dt, a, b, c, doc, block: int, reset: bool):
    """The state-space layer one position at a time: x (bt, s, h, p), dt
    (bt, s, h), a (h,), b, c (bt, s, n), doc (bt, s) -> y (bt, s, h, p); with
    ``reset`` the state a position reads is zero where its document is not
    the position before's.  Blocks of ``block`` positions only bound what
    the backward pass holds: the state a position inside one block, the
    state between blocks."""
    import jax
    import jax.numpy as jnp

    bt, s, h, p = x.shape
    pad = -s % block
    if pad:             # dt = 0: the state stays, the outputs are cut off
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                               * (t.ndim - 2)) for t in (x, dt, b, c))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")

    def step(carry, xs):
        state, before = carry
        x_t, dt_t, b_t, c_t, doc_t = xs
        if reset:
            state = jnp.where((doc_t == before)[:, None, None, None], state,
                              jnp.zeros_like(state))
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return (state, doc_t), jnp.einsum("zhpn,zn->zhp", state, c_t)

    one_block = jax.checkpoint(lambda carry, xs: jax.lax.scan(step, carry,
                                                              xs))
    # (blocks, positions of a block, batch, ...)
    cut = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        (-1, block) + (bt,) + t.shape[2:])
    first = (jnp.zeros((bt, h, p, b.shape[-1]), x.dtype), doc[:, 0])
    _, y = jax.lax.scan(one_block, first, tuple(
        cut(t) for t in (x, dt, b, c, doc)))
    return jnp.moveaxis(y.reshape((s + pad, bt, h, p)), 0, 1)[:, :s]


def _convolution(xbc, w, bias, doc, reset: bool):
    """``silu(bias + sum_k w_k xbc_(t - k'))`` as shifted adds, a tap counted
    where the position it reads exists and (``reset``) lies in position t's
    document."""
    import jax
    import jax.numpy as jnp

    taps, s = w.shape[0], xbc.shape[1]
    out = bias + xbc * w[taps - 1]
    for back in range(1, taps):
        read = jnp.pad(xbc, ((0, 0), (back, 0), (0, 0)))[:, :s]
        if reset:
            same = jnp.pad(doc, ((0, 0), (back, 0)),
                           constant_values=-1)[:, :s] == doc
            read = jnp.where(same[..., None], read, jnp.zeros_like(read))
        out = out + read * w[taps - 1 - back]
    return jax.nn.silu(out)


def _mixer(p, h, doc, cfg, wrong):
    """(the mixer's output, the first held head's channels behind the
    convolution (b, s, p))."""
    import jax
    import jax.numpy as jnp

    b, s, _ = h.shape
    nh, hd, n = held(cfg)["mamba_heads"], cfg["mamba_d_head"], \
        cfg["mamba_d_state"]
    inner = nh * hd
    zxd = h @ p["in_proj"]
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:-nh], zxd[..., -nh:]
    xbc = _convolution(xbc, p["conv_w"], p["conv_b"], doc,
                       wrong != "no_conv_reset")
    xs = xbc[..., :inner].reshape(b, s, nh, hd)
    y = _recurrence(xs, jax.nn.softplus(dt + p["dt_bias"]),
                    -jnp.exp(p["A_log"]), xbc[..., inner:inner + n],
                    xbc[..., inner + n:], doc, cfg["mamba_chunk_size"],
                    wrong != "no_scan_reset")
    y = (y + p["D"][:, None] * xs).reshape(b, s, inner) * jax.nn.silu(z)
    y = ok._norm(y, p["gate_norm"], cfg["rms_norm_eps"])
    return y @ p["out_proj"], xs[:, :, 0]


def _attention(p, h, doc, cfg, wrong):
    """Grouped-query attention under the document mask, one head and
    ``ATTN_ROWS`` query rows at a time against every key."""
    import jax
    import jax.numpy as jnp

    b, s, _ = h.shape
    nh, nkv = held(cfg)["q_heads"], held(cfg)["kv_heads"]
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q = heads(h @ p["wq"], nh)
    k, v = (jnp.repeat(heads(h @ p[w], nkv), nh // nkv, axis=1)
            for w in ("wk", "wv"))
    hd = q.shape[-1]
    scale = hd ** -0.5 if wrong == "sqrt_scale" \
        else cfg["attention_multiplier"]
    rows = min(ATTN_ROWS, s)
    keys = jnp.arange(s)

    def one_head(xs):
        qi, ki, vi, di = xs

        @jax.checkpoint
        def one_block(ys):
            qb, first = ys
            at = first + jnp.arange(rows)
            mask = at[:, None] >= keys[None, :]
            if wrong != "no_doc_mask":
                mask = mask & (di[at][:, None] == di[None, :])
            sc = jnp.where(mask, (qb @ ki.T) * jnp.asarray(scale, qb.dtype),
                           -jnp.inf)
            return jax.nn.softmax(sc, axis=-1) @ vi

        return jax.lax.map(one_block, (qi.reshape(s // rows, rows, hd),
                                       jnp.arange(0, s, rows))).reshape(s, -1)

    flat = lambda t: t.reshape(b * nh, s, hd)
    o = jax.lax.map(one_head, (flat(q), flat(k), flat(v),
                               jnp.repeat(doc, nh, axis=0)))
    return o.reshape(b, nh, s, hd).transpose(0, 2, 1, 3).reshape(
        b, s, -1) @ p["wo"]


def _layer(name: str, p, x, doc, cfg, wrong):
    """(one layer's output on the residual stream ``x`` (b, s, d), a Mamba
    layer's first head behind the convolution or None)."""
    import jax

    eps = cfg["rms_norm_eps"]
    by = 1.0 if wrong == "residual_one" else cfg["residual_multiplier"]
    behind = None
    if name == "mamba":
        y, behind = _mixer(p, ok._norm(x, p["norm"], eps), doc, cfg, wrong)
    else:
        y = _attention(p, ok._norm(x, p["ln1"], eps), doc, cfg, wrong)
    x = x + by * y
    h = ok._norm(x, p["ln2"], eps)
    return x + by * ((jax.nn.silu(h @ p["gate"]) * (h @ p["up"]))
                     @ p["down"]), behind


def _head(h, embed, labels, rows, scaling):
    """Per row of the tied head (logsumexp, the label's logit) of ``h
    Embed^T / scaling``, by blocks."""
    import jax
    import jax.numpy as jnp

    t, d = h.shape
    rows = min(rows, t)

    @jax.checkpoint
    def one(xs):
        hb, lb = xs
        logits = (hb @ embed.T) / jnp.asarray(scaling, hb.dtype)
        return jnp.stack([jax.nn.logsumexp(logits, axis=-1),
                          jnp.take_along_axis(logits, lb[:, None], -1)[:, 0]],
                         axis=-1)

    return jax.lax.map(one, (h.reshape(t // rows, rows, d),
                             labels.reshape(t // rows, rows))).reshape(t, 2)


def loss_parts(params, tokens, labels, cfg: dict, at, wrong=None):
    """(loss, {losses, rows, conv_x}) of one batch, in the parameters' own
    type throughout (float32; bfloat16 for the control).  ``labels`` may be
    longer than ``tokens``: the first ``s`` are read.  ``at``: the token rows
    ``conv_x`` reads of the first Mamba layer (``boundary_rows``).  ``wrong``
    names a deliberately wrong variant (``WRONG``)."""
    import jax
    import jax.numpy as jnp

    b, s = tokens.shape
    doc = documents(tokens, cfg["eos_token_here"])
    x = jnp.asarray(cfg["embedding_multiplier"], params["embed"].dtype) \
        * params["embed"][tokens]
    conv_x = None
    for name, n, first in segments(cfg):
        group = params["layers"][f"l{first}"][KINDS[name]]
        run = jax.checkpoint(functools.partial(
            _layer, name, doc=doc, cfg=cfg, wrong=wrong))
        for i in range(n):
            x, behind = run({k: v[i] for k, v in group.items()}, x)
            if conv_x is None and behind is not None:
                conv_x = behind.reshape(b * s, -1)[at]
    h = ok._norm(x, params["final_norm"], cfg["rms_norm_eps"]
                 ).reshape(b * s, -1)
    rows = _head(h, params["embed"], labels[:, :s].reshape(b * s),
                 cfg.get("loss_block_rows", 1024), cfg["logits_scaling"])
    ce = jnp.mean(rows[:, 0] - rows[:, 1])
    return ce, {"losses": jnp.stack([ce, ce]), "rows": rows,
                "conv_x": conv_x}


@functools.lru_cache(maxsize=None)
def _grad_program(cfg_items: tuple, wrt: tuple, wrong):
    import jax

    cfg = dict(cfg_items)
    cfg["layer_types"] = list(cfg["layer_types"])

    def run(params, tokens, labels, at):
        diff = {n: leaf_of(params, n) for n in wrt}

        def loss(diff):
            merged = jax.tree.map(lambda a: a, params)      # a copy's dicts
            for n, a in diff.items():
                put_leaf(merged, n, a)
            return loss_parts(merged, tokens, labels, cfg, at, wrong)

        with jax.default_matmul_precision("highest"):
            (_, aux), g = jax.value_and_grad(loss, has_aux=True)(diff)
        return aux, g

    return jax.jit(run)


def reference_step(params, tokens, labels, cfg: dict, bias: dict,
                   wrt: tuple, wrong: str | None = None,
                   routed=None) -> dict:
    """One step's statistics from the reference, in the form ``step_stats``
    puts a program's in: ``losses``, ``rows``, ``conv_x``, and for each leaf
    of ``wrt`` its gradient's ``grad_sq`` and ``grad_probe`` (the tied
    matrix's: the sum of both uses); ``grads`` holds the whole gradients of
    ``wrt``.  ``bias`` and ``routed`` are the kind's: nothing routes, and
    they are not read.  Parameters given in bfloat16 make the **control**:
    the same model computed throughout in the nearest precision below the
    one the configuration states."""
    import jax
    import jax.numpy as jnp

    doc = np.asarray(jax.device_get(documents(tokens,
                                              cfg["eos_token_here"])))
    if wrong is None and leaf_of(params, "embed").dtype == jnp.float32:
        # what the whole-model controls run again (``precision_want``): the
        # float32 reference's own batch, never a control's
        _STEP.update(tokens=tokens, labels=labels, wrt=wrt)
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    items += (("layer_types", tuple(cfg["layer_types"])),)
    aux, g = _grad_program(items, tuple(wrt), wrong)(
        params, tokens, labels, jnp.asarray(boundary_rows(doc)))
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
    model): ``conv_x`` from what the first Mamba layer's scan read of its
    first held head, at the rows behind the batch's documents' starts (the
    step's own ``doc``).  ``tools/kit_check.py`` prints a share cell's load
    from the ``aux`` it hands over here: a step that routes nothing reports
    none, so the 0 slots it would read are set there."""
    aux.setdefault("local_slots", np.float32(0.0))
    out = {"losses": np.asarray(aux["losses"])[:2],     # total, ce
           "rows": np.asarray(aux["rows"]),
           "conv_x": np.asarray(aux["sample"]["ssm_x_seq"])[0][
               boundary_rows(aux["doc"])]}
    for k in ("grad_sq", "grad_probe"):
        out[k] = dict(zip(leaves(cfg), np.asarray(aux[k])))
    return out


def compared(stats: dict, cfg: dict, wrt: tuple) -> dict:
    """What a check compares of one step's statistics, each in its unit
    (``olmoekit``'s constants): the loss and the cross-entropy as they are;
    the head's logsumexp and label logit averaged over quarters of the rows;
    the first layer's convolution's output behind the documents' starts
    times ``CONV_SCALE``; and for the leaves of ``wrt`` the gradient's RMS as
    log10 over ``RMS_UNIT`` and, but for ``RMS_ONLY``, its probed entries in
    units of ``PROBE_UNIT`` RMS, or of ``PROBE_UNIT / HOT_ENTRY`` times the
    largest of them where that is more (``nemotronkit.compared``)."""
    rows = np.asarray(stats["rows"], np.float32)
    sizes = leaf_sizes(cfg)
    rms = np.maximum(1e-30, np.sqrt(
        [float(stats["grad_sq"][n]) / sizes[n] for n in wrt]))
    entries = [i for i, n in enumerate(wrt) if n not in RMS_ONLY]
    probe = np.stack([np.asarray(stats["grad_probe"][wrt[i]])
                      for i in entries])
    scale = PROBE_UNIT * np.maximum(rms[entries],
                                    np.abs(probe).max(axis=1) / HOT_ENTRY)
    return {k: np.asarray(v, np.float32) for k, v in {
        "losses": stats["losses"],
        "row_means": rows.reshape(ROW_BLOCKS, -1, 2).mean(axis=1),
        "conv_x": np.asarray(stats["conv_x"]) * CONV_SCALE,
        "grad_log_rms": np.log10(rms) / RMS_UNIT,
        "grad_probe": probe / scale[:, None]}.items()}


# -- the float32 parts of a step, read from the step alone -----------------------
def precision_got(aux: dict, cfg: dict) -> dict:
    """What the step's float32 parts made at ``sample_rows``, in units of
    ``SAMPLE_UNIT``: the head's logsumexp and label logit, and what every
    Mamba layer's scan made of its first held head (times ``SSM_SCALE``)."""
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    return {k: np.asarray(v, np.float32) / SAMPLE_UNIT for k, v in {
        "head_rows": np.asarray(aux["rows"])[at],
        "ssm_y": np.asarray(aux["sample"]["ssm_y"]) * SSM_SCALE}.items()}


def scan_rows(dt, x, b, c, a, doc, at, low, reset: bool = True) -> np.ndarray:
    """The first head's ``y`` (M, rows ``at``, p) of every Mamba layer from
    what its scan read: dt (M, T), x (M, T, p), b, c (M, T, n), a (M,), doc
    (T,) the rows' documents (a row's first position differs from the row
    before's last); the recurrence one position at a time in float64, the
    state zero where a document starts (``reset``), decay and state through
    ``low`` at every position."""
    m, t, p = x.shape
    out = np.zeros((m, t, p))
    state = np.zeros((m, p, b.shape[-1]))
    for i in range(t):
        if i == 0 or (reset and doc[i] != doc[i - 1]):
            state = np.zeros_like(state)
        state = low(low(np.exp(dt[:, i] * a))[:, None, None] * state
                    + (dt[:, i, None] * x[:, i])[:, :, None]
                    * b[:, i, None, :])
        out[:, i] = np.einsum("mpn,mn->mp", state, c[:, i])
    return out[:, at]


@functools.lru_cache(maxsize=None)
def _head_program(dtype: str, scaling: float):
    """``olmoekit._head_program`` with the logits over ``scaling``."""
    import jax
    import jax.numpy as jnp

    def run(h, head, labels):
        logits = jnp.dot(h.astype(dtype).astype(jnp.float32),
                         head.astype(dtype).astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST) / scaling
        return jnp.stack([jax.nn.logsumexp(logits, axis=-1),
                          jnp.take_along_axis(logits, labels[:, None],
                                              -1)[:, 0]], axis=-1), logits

    return jax.jit(run)


def precision_want(aux: dict, by_name: dict, bias_before, head, labels,
                   cfg: dict, variant: str | None = None) -> dict:
    """What ``precision_got`` reads, recomputed **from the step's own inputs
    to each part** at the precision the configuration states: the head's rows
    from the rows the head read against ``head`` (d, V), the tied matrix
    transposed (inputs rounded to the compute type, every product exact, the
    logits over ``logits_scaling``); the scans' results from the step's own
    dt, x, B and C of the first held head, the recurrence one position at a
    time in float64 over the batch's documents (the step's own ``doc``).
    ``bias_before`` is the kind's and is not read.  ``variant`` gives a
    **control**, which has to lie outside: ``bf16`` (the head as a bfloat16
    implementation would have made it), ``scan_bf16`` (the scan's decay and
    state held in bfloat16), ``scan_no_reset`` (the state carried across the
    documents' starts), and the whole-model ones (``WHOLE_CONTROLS``), each
    of which runs the reference again on the last checked batch as that
    wrong model and returns what ``compared`` makes of it."""
    import jax.numpy as jnp

    if variant in WHOLE_CONTROLS:
        import jax

        params = jax.device_put({k: v for k, v in tree_of(
            {n: by_name[n] for n in leaves(cfg)}).items() if k != "head"})
        out = reference_step(params, _STEP["tokens"], _STEP["labels"], cfg,
                             {}, _STEP["wrt"], wrong=variant)
        return compared({k: np.asarray(v) if not isinstance(v, dict) else v
                         for k, v in out.items() if k != "grads"}, cfg,
                        _STEP["wrt"])
    s = {k: np.asarray(v, np.float64) for k, v in aux["sample"].items()}
    exact = lambda a: np.asarray(a, np.float64)
    at = sample_rows(np.asarray(aux["rows"]).shape[0])
    lab = np.asarray(labels)[:, :-1].reshape(-1)[at]
    rows, head_logits = _head_program(
        cfg["compute_dtype"], float(cfg["logits_scaling"]))(
            jnp.asarray(aux["sample"]["head_in"]), head, jnp.asarray(lab))
    if variant == "bf16":           # the head's logits kept in bfloat16
        hl = ok._bf16(head_logits)
        top = hl.max(axis=-1)
        picked = np.take_along_axis(hl, lab[:, None], -1)[:, 0]
        rows = ok._bf16(np.stack([top + np.log(np.exp(
            hl - top[:, None]).sum(axis=-1)), picked], axis=-1))
    a_log = np.concatenate([
        np.asarray(by_name[f"l{first}.{KINDS[name]}.A_log"], np.float64)
        for name, _, first in segments(cfg) if name == "mamba"])
    doc = np.asarray(aux["doc"])
    # a row's documents told from the row before's
    doc = (doc + np.arange(doc.shape[0])[:, None] * (doc.max() + 1)).ravel()
    y = scan_rows(s["ssm_dt_seq"], s["ssm_x_seq"], s["ssm_b_seq"],
                  s["ssm_c_seq"], -np.exp(a_log[:, 0]), doc, at,
                  ok._bf16 if variant == "scan_bf16" else exact,
                  reset=variant != "scan_no_reset")
    return {k: np.asarray(v / SAMPLE_UNIT, np.float32) for k, v in {
        "head_rows": np.asarray(rows, np.float64),
        "ssm_y": y * SSM_SCALE}.items()}


def adamw_leaf(name: str, p, g, cfg: dict):
    """The first AdamW update of one leaf (``olmoekit.adamw_leaf`` with this
    model's undecayed leaves)."""
    return ok.adamw_leaf("ln1" if name.rsplit(".", 1)[-1] in UNDECAYED
                         else "matrix", p, g, cfg)


# -- operations counted from the shapes -------------------------------------------
def matmul_params_per_token(cfg: dict) -> dict:
    """Matmul parameters one token meets in one layer's parts and in the
    head (the tied matrix once: the embedding's gather is no product)."""
    per = layer_sizes(cfg)
    return {"mamba_proj": per["mamba"]["in_proj"] + per["mamba"]["out_proj"],
            "attn_proj": sum(per["attention"][k]
                             for k in ("wq", "wk", "wv", "wo")),
            "dense_mlp": sum(per["mamba"][k] for k in ("gate", "up", "down")),
            "head": cfg["hidden_size"] * cfg["vocab_here"]}


@functools.lru_cache(maxsize=None)
def mean_visible_pairs(seq_len: int) -> float:
    """The (query, key) pairs a row of ``seq_len`` tokens sees under its
    documents' mask at the mean over the traffic's law: documents of
    ``document_lengths``'s lengths laid end to end, the last cut at the row's
    end, ``sum n (n + 1) / 2``; the mean of 4,096 rows drawn here from one
    fixed seed (numpy's normal: the count follows the law, not a run's
    batches)."""
    rng = np.random.default_rng(69)
    median, shortest, longest = length_law(seq_len)
    lengths = np.clip(np.round(median * np.exp(SIGMA * rng.standard_normal(
        (4096, DOCS)))), shortest, longest)
    ends = np.minimum(np.cumsum(lengths, axis=1), seq_len)
    cut = np.diff(np.concatenate([np.zeros((4096, 1)), ends], axis=1), axis=1)
    cut[:, -1] += seq_len - ends[:, -1]        # a last document to the end
    return float(np.mean(np.sum(cut * (cut + 1) / 2, axis=1)))


def causal_pairs(cfg: dict) -> int:
    """The pairs a row's attention would see under the triangle alone."""
    return cfg["seq_len"] * (cfg["seq_len"] + 1) // 2


def attention_forward_flops(cfg: dict) -> float:
    """Attention's forward FLOP a step **over the pairs the documents' masks
    leave** at the traffic's mean: q k^T and p v over the head width, 2 x 2 x
    head width x held query heads a pair, every attention layer."""
    n_a = sum(name == "attention" for name, n, _ in segments(cfg)
              for _ in range(n))
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["micro_batch"] * mean_visible_pairs(cfg["seq_len"]) * 4.0 \
        * hd * held(cfg)["q_heads"] * n_a


def step_flops(cfg: dict) -> dict:
    """Model FLOP of one training step by part: 6 x the matmul parameters a
    token meets x tokens; the state-space layers by the **recurrence's**
    count (a position and head: decay and add of the (p, n) state and its
    product with C, 6 p n forward, three times that a step), not by the
    chunked form's products; attention at three times its forward over the
    pairs the documents' masks leave; the tied head once.  Recomputed layers,
    the chunked scan's extra products, the tile pairs the flash kernels walk
    across a document's start, the masks, the norms and the optimiser's work
    are not model FLOP and lower the share.  ``flash_forward`` is what the
    forward kernel has to compute of the visible pairs."""
    runs = {name: sum(n for kind, n, _ in segments(cfg) if kind == name)
            for name in KINDS}
    tokens = cfg["micro_batch"] * cfg["seq_len"]
    per = matmul_params_per_token(cfg)
    forward = attention_forward_flops(cfg)
    parts = {
        "mamba_proj": 6.0 * per["mamba_proj"] * tokens * runs["mamba"],
        "ssm_scan": 3.0 * 6.0 * held(cfg)["mamba_heads"]
        * cfg["mamba_d_head"] * cfg["mamba_d_state"] * tokens * runs["mamba"],
        "attn_proj": 6.0 * per["attn_proj"] * tokens * runs["attention"],
        "attention": 3.0 * forward,
        "dense_mlp": 6.0 * per["dense_mlp"] * tokens
        * (runs["mamba"] + runs["attention"]),
        "head": 6.0 * per["head"] * tokens}
    parts["step"] = sum(parts.values())
    parts["flash_forward"] = forward
    return parts
