"""The attention sublayers on ``causal_flash_attention``: OLMoE's,
DeepSeek-V3's latent attention (JoyAI-LLM-Flash; Xing4.0 over a share of
its heads under YaRN) and grouped-query
attention in the forms Nemotron-3-Super, LFM2, Qwen3-Next, SmallThinker
and SDAR (over a noisy and a clean copy of every sequence, on
``block_diffusion_flash_attention``) publish, each with its entry in
``parallel/model.py``'s table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.causal import (ATTN_KEEPS,
                                      block_diffusion_flash_attention,
                                      causal_flash_attention,
                                      document_selection, flash_on_kernels,
                                      pass_counts, selected_flash_attention)
from ompi_tpu.parallel.layers import (matmul, project_rope, rmsnorm_gain,
                                      rope, rope_tables)
from ompi_tpu.parallel.sublayer import INTERPRET, Sublayer, held


def olmoe_attention(p, x, cfg, *, interpret: bool, at=None):
    """OLMoE's attention sublayer, **without** the residual add, on the
    residual stream ``x`` (b, s, d) float32: pre-norm; q, k, v, o
    projections without bias; RMSNorm with a gain over the whole width of
    q and of k **before** the heads are split (QK-norm); RoPE; causal
    attention.  Reports nothing."""
    b, s, d = x.shape
    nh, dt = cfg.num_attention_heads, cfg.compute_dtype
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps)
        q = rmsnorm_gain(matmul(h, p["wq"], dt), p["q_norm"],
                         cfg.rms_norm_eps)
        k = rmsnorm_gain(matmul(h, p["wk"], dt), p["k_norm"],
                         cfg.rms_norm_eps)
        v = matmul(h, p["wv"], dt)
        heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
        q, k = rope(heads(q), cfg.rope_theta), rope(heads(k), cfg.rope_theta)
        q, k, v = q.astype(dt), k.astype(dt), heads(v).astype(dt)
    o = causal_flash_attention(q, k, v, min(cfg.attn_block, s), interpret)
    with jax.named_scope("otpu_attn_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        return matmul(o, p["wo"], dt), {}, {}


def mla_attention(p, x, cfg, *, interpret: bool, at=None):
    """DeepSeek-V3's latent attention sublayer (arXiv:2412.19437 section
    2.1.1), **without** the residual add, on the residual stream ``x`` (b,
    s, d) float32: pre-norm; q
    through a normed latent of ``q_lora_rank``; k's no-position part and
    v through a normed latent of ``kv_lora_rank``; one rotary key of
    ``qk_rope_head_dim`` that every head shares; causal ``softmax(q k^T
    / sqrt(nope + rope)) v`` with q, k of one width and v of another.
    The two inner norms, RoPE and the softmax in float32;
    matmul inputs in ``compute_dtype``.  Training holds no cache, so the
    latents are expanded to full keys and values.  q and the shared
    rotary key leave their projections with RoPE on (``project_rope``).
    Over the ``n_heads_here`` heads held here, a tensor-parallel group's
    member's share: the head-wise leaves ``wq_b``, ``wkv_b`` and ``wo`` are
    cut, the two latents whole, and what the absent heads would add to the
    output is left out.  Under a ``rope_scaling`` of type ``yarn`` the
    rotary frequencies are YaRN's and the scores' scale
    ``cfg.attention_scale`` (``layers.yarn_inv_freq``)."""
    b, s, _ = x.shape
    nh, dt, eps = cfg.n_heads_here, cfg.compute_dtype, cfg.rms_norm_eps
    nope, rot, hv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank, theta, yarn = cfg.kv_lora_rank, cfg.rope_theta, cfg.yarn
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], eps)
        cq = rmsnorm_gain(matmul(h, p["wq_a"], dt), p["q_a_norm"], eps)
        q = project_rope(cq, p["wq_b"], nh, nope, theta, dt, yarn)
        # (b, s, rank + rot), the rotary key behind the latent
        kv = project_rope(h, p["wkv_a"], 1, rank, theta, dt, yarn)[:, :, 0]
        ckv = rmsnorm_gain(kv[..., :rank], p["kv_a_norm"], eps)
        kvb = matmul(ckv, p["wkv_b"], dt).reshape(b, s, nh, nope + hv)
        k = jnp.concatenate([kvb[..., :nope].astype(dt), jnp.broadcast_to(
            kv[:, :, None, rank:].astype(dt), (b, s, nh, rot))], -1)
        heads = lambda t: t.transpose(0, 2, 1, 3)        # (b, nh, s, .)
        q, k, v = (heads(q.astype(dt)), heads(k),
                   heads(kvb[..., nope:].astype(dt)))
    o = causal_flash_attention(q, k, v, min(cfg.attn_block, s), interpret,
                               None, cfg.attention_scale)
    with jax.named_scope("otpu_attn_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * hv)
        return matmul(o, p["wo"], dt), {}, {}


def normed_turned_heads(t, gain, cfg, turned: bool, positions=None):
    """Heads ``t`` (b, n, s, hd) float32 under the per-head QK-norm
    (RMSNorm with ``gain`` (hd,) over each head's width; none where
    ``gain`` is None) and, where ``turned``, RoPE in the half-split form
    over the leading ``cfg.rotary_width`` entries at ``positions``: the
    ``jnp`` lines, which are the twin of ``ops/head_norm_rope``'s kernels
    and every other shape's path."""
    if gain is not None:
        t = rmsnorm_gain(t, gain, cfg.rms_norm_eps)
    return rope(t, cfg.rope_theta, cfg.rotary_width, positions) \
        if turned else t


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _kernel_heads(prod, gain, cos, sin, heads, eps, dtype):
    """``normed_turned_heads`` of the product ``prod`` (b, s, heads x hd)
    float32 where it lies, split into heads and cast to ``dtype``, (b,
    heads, s, hd), on the Pallas kernels (``ops/head_norm_rope``), one
    pass each way; ``cos`` and ``sin`` (s, hd) are ``rope_tables``', the
    second with its sign on (``signed_sin``).  Only the product, the gain
    and the tables are kept for the backward kernel, which makes the norm
    again and writes the product's cotangent in ``dtype``, which is what
    its readers, the projections' transposes, cast it to.  A head without
    a norm has ``gain`` None: the tables alone are kept, the turn being
    linear."""
    from ompi_tpu.ops import head_norm_rope

    return head_norm_rope.heads_forward(prod, gain, cos, sin, heads=heads,
                                  eps=eps, dtype=dtype)


def _kernel_heads_fwd(prod, gain, cos, sin, heads, eps, dtype):
    from ompi_tpu.ops import head_norm_rope

    return head_norm_rope.heads_forward(
        prod, gain, cos, sin, heads=heads, eps=eps, dtype=dtype), (
            None if gain is None else prod, gain, cos, sin)


def _kernel_heads_bwd(heads, eps, dtype, res, do):
    from ompi_tpu.ops import head_norm_rope

    dprod, dgain = head_norm_rope.heads_backward(*res, do, eps=eps,
                                                 dtype=dtype)
    return dprod.astype(jnp.float32), dgain, None, None


_kernel_heads.defvjp(_kernel_heads_fwd, _kernel_heads_bwd)


def qk_on_kernels(interpret, cfg, turned, gated) -> tuple:
    """``(on_kernel, why)`` of q's and k's way to the flash kernels: on
    the Pallas kernels where Mosaic compiles (``interpret`` false: a TPU),
    RoPE turns the layer and the head has tiles
    (``ops/head_norm_rope.refusal``); ``why`` names the clause that
    refused, "" where the kernels are taken."""
    if interpret:
        return False, INTERPRET
    if not turned:
        return False, "RoPE does not turn the layer"
    from ompi_tpu.ops import head_norm_rope

    why = head_norm_rope.refusal(cfg.head_width, cfg.rotary_width, gated)
    return not why, why


def normed_qk(p, h, cfg, *, interpret: bool, turned: bool = True,
              positions=None):
    """q and k of a ``layer_types`` sublayer from the normed input ``h``
    (b, s, d): ``h W_q`` and ``h W_k`` split into the ``n_heads_here`` and
    ``n_kv_heads_here`` heads, a gate split off behind every query head
    where ``wq`` is twice as wide as ``wo`` is long, where the layer holds
    ``q_norm`` and ``k_norm`` (lfm2's form: qwen3_moe's, sdar_moe's,
    Keye's, qwen3_next's) RMSNorm with those gains over each head, where it
    holds none (smallthinker's form, ouro's) no norm, RoPE where ``turned``
    (at ``positions``), all float32, then the cast to ``compute_dtype``.

    Where Mosaic compiles, RoPE turns the layer and the head has tiles
    (``qk_on_kernels``) each of the two is one pass of
    ``ops/head_norm_rope``'s kernel over its projection's product where it
    lies, and one back, with the norm's step or without; elsewhere the
    ``jnp`` lines (``normed_turned_heads`` between a transposition and a
    cast).  SPC ``attn_qk_built`` counts both ways' q and k a layer
    application, ``attn_qk_kernel_built`` the kernels' (``qk_plan``).

    Returns (q (b, nh, s, hd), k (b, nkv, s, hd), the gate (b, nh, s, hd)
    float32 or None, and of the first query head and the first key-value
    head side by side, by token row, the product and what the norm and
    RoPE made of it in float32: ``attn_qk_in``, ``attn_qk`` (T, 2 hd),
    which on the kernels are those two heads' products made again from
    their own columns and the forward kernel's float32 output over
    them)."""
    b, s, _ = h.shape
    nh, nkv, dt = cfg.n_heads_here, cfg.n_kv_heads_here, cfg.compute_dtype
    gated = p["wq"].shape[-1] == 2 * p["wo"].shape[0]
    gains = [p.get(g) for g in ("q_norm", "k_norm")]
    on_kernels, _ = qk_on_kernels(interpret, cfg, turned, gated)
    side = lambda a, c: jnp.concatenate([a, c], -1).reshape(b * s, -1)
    gate = None
    if on_kernels:
        from ompi_tpu.ops.head_norm_rope import signed_sin

        hd = cfg.head_width
        cos, sin = rope_tables(s, hd, cfg.rope_theta, positions)
        sin = signed_sin(sin)
        prods = [matmul(h, p[w], dt) for w in ("wq", "wk")]
        q, k = (_kernel_heads(t, g, cos, sin, n, cfg.rms_norm_eps, dt)
                for t, g, n in zip(prods, gains, (nh, nkv)))
        # the first heads' products made again, from their own columns,
        # and the same kernel over them in float32, so that what a check
        # reads of the norm and RoPE is the kernel's arithmetic: a slice
        # of the whole product as a second reader has XLA lay it out for
        # the slice and copy it for the kernel (3.2 ms a step of SDAR's
        # on the v5e, PR 65)
        ins = [matmul(h, p[w][:, :hd], dt) for w in ("wq", "wk")]
        outs = [_kernel_heads(t, g, cos, sin, 1, cfg.rms_norm_eps,
                              jnp.float32)[:, 0]
                for t, g in zip(ins, gains)]
    else:
        split = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
        q_in, k_in = (split(matmul(h, p[w], dt), n)
                      for w, n in (("wq", nh), ("wk", nkv)))
        if gated:
            q_in, gate = jnp.split(q_in, 2, axis=-1)
        q, k = (normed_turned_heads(t, g, cfg, turned, positions)
                for t, g in zip((q_in, k_in), gains))
        ins, outs = (q_in[:, 0], k_in[:, 0]), (q[:, 0], k[:, 0])
    seen = {"attn_qk_in": side(*ins), "attn_qk": side(*outs)}
    return q.astype(dt), k.astype(dt), gate, seen


def gqa_attention(p, x, cfg, *, interpret: bool, at=None, kind: str = "",
                  windowed: bool = False, diffused: bool = False, doc=None):
    """Grouped-query attention, **without** the residual add, on the
    residual stream ``x`` (b, s, d) float32: pre-norm; q, k, v, o
    projections without bias; the ``n_heads_here`` query heads held here
    and the ``n_kv_heads_here`` key-value heads they read; causal softmax
    attention.  k and v go to ``causal_flash_attention`` as they leave
    their projections, with their own heads: the flash kernels read a
    group's shared head through their index maps and sum its query
    heads' gradients in float32.

    Six models' sublayer, told apart by what the layer holds and, where
    the leaves cannot say, by the entry that runs it (``kind``, its
    ``layer_types`` name, ``windowed`` and ``diffused``).  Without
    ``q_norm`` and outside ``layer_types`` (nemotron_h): no rotary
    embedding, q, k and v cast as they leave their projections.  With ``q_norm`` and ``k_norm``
    (head width,) (lfm2): RMSNorm with a gain over **each head's** width
    of q and of k, then RoPE in the half-split form where ``kind`` is one
    of ``cfg.rope_kinds``, both in float32; a ``wq`` twice as wide as
    ``wo`` is long (qwen3_next) holds a **gate** behind every head's
    query, RoPE turns the leading ``rotary_width`` entries only, and ``o *
    sigmoid(gate)``, in float32, goes to ``W_o``.  A ``layer_types`` layer
    without ``q_norm`` (smallthinker, ouro): RoPE over the whole head by
    ``rope_kinds`` and no norm, and under ``windowed`` the last
    ``sliding_window`` keys (``causal_flash_attention``'s ``window``).
    With a norm or without, q and k of a ``layer_types`` layer are
    ``normed_qk``'s: one Pallas pass each way where Mosaic compiles, the
    layer is turned and a head has tiles, the ``jnp`` lines elsewhere.
    Heads are ``head_width`` wide whatever the hidden width.  (Keye-VL-2.0's q, k and v share
    lfm2's form, but its sublayer is ``dsa.dsa_attention``.)  Under
    ``diffused`` (sdar_moe; lfm2's form) the ``s`` rows are a noisy copy
    of every sequence before its clean copy: RoPE turns both halves at
    positions ``0 .. s / 2 - 1`` and attention goes under block diffusion's
    mask in blocks of ``block_length``
    (``block_diffusion_flash_attention``: a noisy row sees its own block's
    noisy rows and every earlier block's clean ones).  The scores' scale
    is ``cfg.attention_scale`` where the file gives one (granitemoehybrid's
    ``attention_multiplier``: 1 / 64 at a head of 64, not 1 / 8), handed
    to the kernels and their twins; under ``doc`` (b, s) int32, a packed
    row's documents (granitemoehybrid's ``cu_seq_lens``), a query sees the
    earlier keys of its own document alone: the mask goes to
    ``selected_flash_attention`` packed eight keys a byte
    (``document_selection``, made here under the sublayer's scope).

    Returns (the sublayer's output, no statistics, by token row what
    ``_gqa_reports`` lists: the first query head and the first key-value
    head side by side before the norm and behind RoPE, ``attn_qk_in`` and
    ``attn_qk`` (T, 2 hd), the two alike where the layer is not turned; a
    gated layer's first head's o and gate side by side, ``attn_og_in`` (T,
    2 hd), and gated, ``attn_og`` (T, hd); under a window what the kernels
    read and made of the first query head and its key-value head,
    ``attn_win_q``, ``attn_win_o`` (T, hd) and ``attn_win_k_seq``,
    ``attn_win_v_seq`` (T, hd) whole, because a row reads a window of
    them; under ``diffused`` the same four as ``bd_q``, ``bd_o``,
    ``bd_k_seq`` and ``bd_v_seq``)."""
    b, s, _ = x.shape
    nh, nkv, dt = cfg.n_heads_here, cfg.n_kv_heads_here, cfg.compute_dtype
    seen, gate = {}, None
    turned = kind in cfg.rope_kinds
    # both copies of a sequence stand at its positions
    positions = jnp.tile(jnp.arange(s // 2), 2) if diffused else None
    window = cfg.sliding_window if windowed else None
    split = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps)
        if "q_norm" in p or cfg.layer_types:
            q, k, gate, qk_seen = normed_qk(
                p, h, cfg, interpret=interpret, turned=turned,
                positions=positions)
            # a model without a QK-norm reports a layer that is not turned
            # too: that it was left alone is what a check reads
            if turned or "q_norm" not in p:
                seen = qk_seen
            v = split(matmul(h, p["wv"], dt), nkv).astype(dt)
        else:
            heads = lambda t, n: t.reshape(b, s, n, -1).transpose(
                0, 2, 1, 3).astype(dt)
            q, k, v = (heads(matmul(h, p[w], dt), n)
                       for w, n in (("wq", nh), ("wk", nkv), ("wv", nkv)))
    if diffused:
        o = block_diffusion_flash_attention(
            q, k, v, min(cfg.attn_block, s // 2), interpret,
            cfg.block_length)
    elif doc is not None:
        o, _ = selected_flash_attention(
            q, k, v, document_selection(doc), min(cfg.attn_block, s),
            interpret, None, cfg.attention_scale)
    else:
        o = causal_flash_attention(q, k, v, min(cfg.attn_block, s),
                                   interpret, window, cfg.attention_scale)
    if window is not None or diffused:
        rows = lambda t: t[:, 0].reshape(b * s, -1).astype(jnp.float32)
        names = ("bd_q", "bd_k_seq", "bd_v_seq", "bd_o") if diffused else (
            "attn_win_q", "attn_win_k_seq", "attn_win_v_seq", "attn_win_o")
        seen.update(zip(names, map(rows, (q, k, v, o))))
    with jax.named_scope("otpu_attn_proj"):
        if gate is not None:
            gated = o * jax.nn.sigmoid(gate)
            seen["attn_og_in"] = jnp.concatenate(
                [o[:, 0], gate[:, 0]], -1).reshape(b * s, -1)
            seen["attn_og"] = gated[:, 0].reshape(b * s, -1)
            o = gated
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return matmul(o, p["wo"], dt), {}, seen


def _olmoe_shapes(cfg) -> dict:
    d = cfg.hidden_size
    return {"ln1": (d,), "wq": (d, d), "wk": (d, d), "wv": (d, d),
            "wo": (d, d), "q_norm": (d,), "k_norm": (d,)}


def _mla_shapes(cfg) -> dict:
    d, nh = cfg.hidden_size, cfg.n_heads_here
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {"ln1": (d,), "wq_a": (d, cfg.q_lora_rank),
            "q_a_norm": (cfg.q_lora_rank,),
            "wq_b": (cfg.q_lora_rank, nh * qk),
            "wkv_a": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            "kv_a_norm": (cfg.kv_lora_rank,),
            "wkv_b": (cfg.kv_lora_rank,
                      nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (nh * cfg.v_head_dim, d)}


def gqa_shapes(cfg, qk_norm=None) -> dict:
    """The gain, q and o over the held query heads, k and v over the
    key-value heads they read, heads of ``head_width``; ``wq`` holds a
    gate beside every query head under ``attn_output_gate``; the per-head
    QK-norm's two gains where the model has one (``qk_norm``: the
    configuration's unless given)."""
    d, hd = cfg.hidden_size, cfg.head_width
    q, kv = cfg.n_heads_here * hd, cfg.n_kv_heads_here * hd
    norms = cfg.qk_norm if qk_norm is None else qk_norm
    return {"ln1": (d,), "wq": (d, q * (2 if cfg.attn_output_gate else 1)),
            "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            **({"q_norm": (hd,), "k_norm": (hd,)} if norms else {})}


def _gqa_reports(cfg, kind: str, windowed: bool,
                 diffused: bool = False) -> dict:
    """What ``gqa_attention`` reports of a ``layer_types`` layer of
    ``kind``: q and k around the norm and RoPE of a kind RoPE turns, and of
    every kind of a model without a QK-norm; o around its gate; of a window
    layer, and of one under block diffusion's mask, what the kernels read
    and made."""
    keys = ("attn_qk_in", "attn_qk") \
        if kind in cfg.rope_kinds or not cfg.qk_norm else ()
    if cfg.attn_output_gate:
        keys += ("attn_og_in", "attn_og")
    if windowed:
        keys += ("attn_win_q", "attn_win_k_seq", "attn_win_v_seq",
                 "attn_win_o")
    if diffused:
        keys += ("bd_q", "bd_k_seq", "bd_v_seq", "bd_o")
    return dict.fromkeys(keys, 1)


def qk_plan(cfg, interpret: bool, turned: bool = True) -> tuple:
    """(``normed_qk``'s decision for a layer that RoPE turns or not, the
    SPC counters one call of it moves: ``attn_qk_built`` 2, q and k, and
    ``attn_qk_kernel_built`` 2 where they go by the kernels).  A gate
    stands behind the query heads under ``attn_output_gate``
    (``gqa_shapes``), which is what ``normed_qk`` reads off ``wq``."""
    on, why = qk_on_kernels(interpret, cfg, turned, cfg.attn_output_gate)
    return (on, why), {"attn_qk_built": 2, "attn_qk_kernel_built": 2 * on}


def _stacked_plan(cfg, b, s, interpret) -> dict:
    """What OLMoE's attention and latent attention hold: every query head
    its own key and value head (latent attention's the heads held here)."""
    nh = cfg.n_heads_here
    return held(pass_counts(b, nh, nh, s, min(cfg.attn_block, s)),
                flash=flash_on_kernels(interpret))


def _gqa_plan(cfg, b, s, interpret, kind: str = "", windowed: bool = False,
              diffused: bool = False, qk_norm=None) -> dict:
    """What ``gqa_attention`` holds of a layer of ``kind``, as it
    branches: under block diffusion's mask no document is read, under a
    document mask no window; q and k are ``normed_qk``'s where the layer
    holds a QK-norm or the model goes by ``layer_types``."""
    doc = cfg.eos_token_here >= 0 and not diffused
    counts = pass_counts(
        b, cfg.n_heads_here, cfg.n_kv_heads_here, s,
        min(cfg.attn_block, s // 2 if diffused else s),
        window=cfg.sliding_window if windowed and not doc else None,
        bd=cfg.block_length if diffused else None, doc=doc)
    parts = {"flash": flash_on_kernels(interpret)}
    if (cfg.qk_norm if qk_norm is None else qk_norm) or cfg.layer_types:
        parts["qk"], moved = qk_plan(cfg, interpret, kind in cfg.rope_kinds)
        counts.update(moved)
    return held(counts, **parts)


def _gqa(name: str, group: str, scope: str, windowed: bool = False,
         diffused: bool = False, post_norm: str = "") -> Sublayer:
    """A ``layer_types`` model's grouped-query attention by its name."""
    bound = dict(kind=name, windowed=windowed, diffused=diffused)
    return Sublayer(
        name=name, group=group, scope=scope,
        run=functools.partial(gqa_attention, **bound), shapes=gqa_shapes,
        undecayed=("ln1", "q_norm", "k_norm"),
        reports=functools.partial(_gqa_reports, **bound), keeps=ATTN_KEEPS,
        post_norm=post_norm, plan=functools.partial(_gqa_plan, **bound))


#: lfm2's and qwen3_next's attention, smallthinker's in full, ouro's (the
#: one with a norm behind it: ``post_norm``)
FULL = _gqa("full_attention", "attn", "otpu_attention",
            post_norm="ln1_post")
#: smallthinker's under its window (``sliding_window``): a full layer's
#: leaves
WINDOW = _gqa("sliding_attention", "swa", "otpu_swa", windowed=True)
#: sdar_moe's: a full layer's leaves, over a noisy and a clean copy of
#: every sequence under block diffusion's mask (``block_length``)
DIFFUSED = _gqa("block_diffusion_attention", "bd", "otpu_bd", diffused=True)
#: nemotron_h's ``*``: no QK-norm, no RoPE, a chip's share of the heads
SHARED_KV = Sublayer(
    name="*", group="attn", scope="otpu_attention", run=gqa_attention,
    shapes=functools.partial(gqa_shapes, qk_norm=False), undecayed=("ln1",),
    keeps=ATTN_KEEPS, plan=functools.partial(_gqa_plan, qk_norm=False))
#: the stacked tree's two (OLMoE's; JoyAI's where ``kv_lora_rank`` is set)
OLMOE = Sublayer(
    scope="otpu_attention", run=olmoe_attention, shapes=_olmoe_shapes,
    undecayed=("ln1", "q_norm", "k_norm"), keeps=ATTN_KEEPS,
    plan=_stacked_plan)
MLA = Sublayer(
    scope="otpu_mla", run=mla_attention, shapes=_mla_shapes,
    undecayed=("ln1", "q_a_norm", "kv_a_norm"), keeps=ATTN_KEEPS,
    plan=_stacked_plan)
