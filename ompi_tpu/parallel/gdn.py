"""Gated DeltaNet (arXiv:2412.06464; qwen3_next's ``linear_attention``
layers): the chunked delta rule as ``jnp`` and on ``ops/gated_delta``'s
kernels, the causal convolution before it on ``ops/causal_conv``'s, the
rules that join the two, and the operator, with its entry in
``parallel/model.py``'s table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.layers import (cast_param, contract, l2norm, matmul,
                                      rmsnorm_gain)
from ompi_tpu.parallel.sublayer import (INTERPRET, Sublayer, held,
                                        log_uniform_1_16, uniform_taps)


@jax.custom_vjp
def unit_lower_inverse(low):
    """``(I + low)^-1`` of strictly lower-triangular matrices ``low``
    (.., c, c) by forward substitution, row by row in float32 (row i of
    the inverse less the identity is ``-low_i`` plus itself times the
    rows above, which are done): sums of products and no matmul, so no
    rounding below float32 whatever the platform's default.  The rows
    are written in place, a loop autodiff would keep every state of: the
    gradient is written out, ``-T^T ct T^T`` of the result ``T``."""
    return _unit_lower_inverse_fwd(low)[0]


def _unit_lower_inverse_fwd(low):
    a = -low
    for i in range(1, low.shape[-1]):
        row = a[..., i, :i]
        a = a.at[..., i, :i].add(
            jnp.sum(row[..., :, None] * a[..., :i, :i], axis=-2))
    t = a + jnp.eye(low.shape[-1], dtype=low.dtype)
    return t, t


def _unit_lower_inverse_bwd(t, ct):
    tt = jnp.swapaxes(t, -1, -2)
    return (-contract("...ij,...jk->...ik",
                       contract("...ij,...jk->...ik", tt, ct, jnp.float32),
                       tt, jnp.float32),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _kernel_views(arrays, hk, hv):
    """(q, k, v, their lane blocks) as ``ops/gated_delta`` reads them: of
    three arrays (bt, s, heads x 128) each from its first block; of one,
    the convolution's [q | k | v], key head h's q at block h, its k at
    ``hk + h`` and its value heads at ``2 hk / r + h`` blocks of r heads."""
    if len(arrays) == 3:
        return (*arrays, (0, 0, 0))
    (qkv,) = arrays
    return qkv, qkv, qkv, (0, hk, 2 * hk * hk // hv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kernel_rule(arrays, g, beta, chunk, hk, unit):
    """The chunked rule on the Pallas kernels (``ops/gated_delta``) for
    ``hk`` key heads 128 wide: o (bt, s, hv x 128) of ``arrays``, either
    (q, k, v) as the rule reads them, heads side by side, or with
    ``unit`` = (eps, scale) the convolution's one [q | k | v], whose q
    and k rows the kernels put at unit length themselves.  The forward
    kernel also writes, for a backward pass, the state that entered each
    chunk and the chunk's ``T``; the backward kernel makes a chunk's
    other parts again from those.  Nothing a chunk is kept or recomputed
    by XLA."""
    from ompi_tpu.ops import gated_delta as rule_kernel

    *views, at = _kernel_views(arrays, hk, g.shape[2])
    return rule_kernel.rule_forward(*views, g, beta, chunk=chunk, hk=hk,
                                    at=at, unit=unit)


def _kernel_rule_fwd(arrays, g, beta, chunk, hk, unit):
    from ompi_tpu.ops import gated_delta as rule_kernel

    *views, at = _kernel_views(arrays, hk, g.shape[2])
    o, kept = rule_kernel.rule_forward(*views, g, beta, chunk=chunk, hk=hk,
                                       at=at, unit=unit, states=True)
    return o, (arrays, g, beta, kept)


def _kernel_rule_bwd(chunk, hk, unit, res, do):
    from ompi_tpu.ops import gated_delta as rule_kernel

    arrays, g, beta, kept = res
    *views, at = _kernel_views(arrays, hk, g.shape[2])
    *d_qkv, dg, dbeta = rule_kernel.rule_backward(
        *views, g, beta, kept, do, chunk=chunk, hk=hk, at=at, unit=unit)
    if len(arrays) == 1:
        d_qkv = [jnp.concatenate(d_qkv, axis=-1)]
    return tuple(d_qkv), dg, dbeta


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


def rule_on_kernels(interpret, chunk, dk, dv, r, s) -> tuple:
    """``(on_kernel, why)`` of the chunked rule: on the Pallas kernels
    where Mosaic compiles (``interpret`` false: a TPU) and the shape has
    tiles (``ops/gated_delta.refusal``); ``why`` names the clause that
    refused, "" where the kernels are taken."""
    if interpret:
        return False, INTERPRET
    from ompi_tpu.ops import gated_delta as rule_kernel

    why = rule_kernel.refusal(chunk, dk, dv, r, s)
    return not why, why


def gated_delta_chunked(q, k, v, g, beta, chunk: int, interpret: bool = True):
    """The gated delta rule (arXiv:2412.06464, the chunked form of its
    section 3.3 and of qwen3_next's modelling code) in float32: per
    value head, from a zero state S (dk x dv) that is never reset, ``S
    <- exp(g_t) S``, ``S <- S + k_t (beta_t (v_t - S^T k_t))^T``, ``o_t
    = S^T q_t``.  ``q``, ``k`` (bt, s, hk, dk), as the rule reads them
    (normalised, q scaled); ``v`` (bt, s, hv, dv); ``g`` (bt, s, hv), not
    positive; ``beta`` (bt, s, hv); each key head is read by ``hv / hk``
    consecutive value heads.  Returns o (bt, s, hv, dv).

    The sequence is cut into chunks of ``chunk`` positions (padded at
    the end with zeros: k = 0 writes nothing, g = 0 leaves the state as
    it is).  With ``c_i`` the running sum of g inside a chunk, ``L_ij =
    beta_i (k_i . k_j) exp(c_i - c_j)`` for j < i and ``T = (I +
    L)^-1`` (``unit_lower_inverse``), a chunk's own writes are ``U = T
    (beta v)`` less what they read of the state that entered, ``W = T
    (beta k exp(c))`` times S: ``V' = U - W S``; its output is ``(q
    exp(c)) S + ((q k^T) exp(c_i - c_j), j <= i) V'``, and it leaves ``S
    exp(c_last) + (k exp(c_last - c))^T V'``.  T, U, W and the masked
    products are made for every chunk at once; the states go from chunk
    to chunk by a ``lax.scan`` of ``s / chunk`` steps, four small
    products each (``ssd_chunked``'s form, but a chunk's writes depend
    on the state it reads, so they lie inside the scan).  Running sums,
    exponentials, the solve, the states and every product are float32
    at the highest precision.  The backward pass is autodiff's through
    the same chunks.

    Where Mosaic compiles (``interpret`` false: a TPU) and the shape has
    tiles (``ops/gated_delta.supported``) the same chunks run in Pallas
    kernels that keep the state in VMEM (``_kernel_rule``), forward and
    backward; everywhere else this XLA form, which is their oracle."""
    bt, s, hk, dk = k.shape
    hv, dv = v.shape[2:]
    r = hv // hk
    if rule_on_kernels(interpret, chunk, dk, dv, r, s)[0]:
        flat = lambda t: t.reshape(bt, s, -1)
        return _kernel_rule((flat(q), flat(k), flat(v)), g, beta, chunk, hk,
                            None).reshape(v.shape)
    _f32 = lambda eq, one, two: contract(eq, one, two, jnp.float32)
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    nc = (s + pad) // chunk
    # (bt, chunks, key heads, [value heads a key head,] position, .)
    qc, kc = (t.reshape(bt, nc, chunk, hk, dk).transpose(0, 1, 3, 2, 4)
              for t in (q, k))
    vc = v.reshape(bt, nc, chunk, hk, r, dv).transpose(0, 1, 3, 4, 2, 5)
    gc, bc = (t.reshape(bt, nc, chunk, hk, r).transpose(0, 1, 3, 4, 2)
              for t in (g, beta))
    cum = jnp.cumsum(gc, axis=-1)                        # c_i
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                 # (.., i, j <= i)
    kk = _f32("zcgid,zcgjd->zcgij", kc, kc)[:, :, :, None]
    low = jnp.where(i[:, None] > i[None, :],
                    bc[..., :, None] * kk * decay, 0.0)
    solve = unit_lower_inverse(low)
    # a key head's q and k go into every product as they are, its value
    # heads' decays on the other operand: no copy of them a value head
    wrote = _f32("zcgrij,zcgrjp->zcgrip", solve, vc * bc[..., None])
    read = _f32("zcgrij,zcgjd->zcgrid",
                solve * (bc * jnp.exp(cum))[..., None, :], kc)
    qk = _f32("zcgid,zcgjd->zcgij", qc, kc)[:, :, :, None] * decay

    def carry(state, xs):
        wrote_c, read_c, qk_c, q_c, k_c, cum_c = xs
        new = wrote_c - _f32("zgrid,zgrdp->zgrip", read_c, state)
        out = _f32("zgid,zgrdp->zgrip", q_c, state) \
            * jnp.exp(cum_c)[..., None] \
            + _f32("zgrij,zgrjp->zgrip", qk_c, new)
        last = cum_c[..., -1:]                           # a chunk's decay
        return state * jnp.exp(last)[..., None] + _f32(
            "zgjd,zgrjp->zgrdp", k_c,
            new * jnp.exp(last - cum_c)[..., None]), out

    # a zero state that carries the inputs' vma
    zero = (kc[:, 0, :, None, 0, :, None]
            * wrote[:, 0, :, :, 0, None, :]) * 0
    # a step's own products are made again in its backward step: kept,
    # they are three more arrays of every chunk's (positions, dv) beside
    # the states (at 16,384 positions 0.8 GB a layer, which did not fit)
    _, o = jax.lax.scan(jax.checkpoint(carry), zero, tuple(
        jnp.moveaxis(t, 1, 0) for t in (wrote, read, qk, qc, kc, cum)))
    # (chunks, bt, hk, r, position, dv) -> (bt, s, hv, dv)
    return o.transpose(1, 0, 4, 2, 3, 5).reshape(bt, s + pad, hv, dv)[:, :s]


@jax.custom_vjp
def _kernel_conv(x, w):
    """The causal depthwise convolution and its silu on the Pallas
    kernels (``ops/causal_conv``): ``silu(sum_j w[j] x[t - (taps - 1) +
    j])`` (b, s, c) of x (b, s, c) and the taps w (taps, c), float32.
    Only x and w are kept for the backward kernel, which makes the
    pre-activation again, writes dx and sums dw in one pass over x and
    the cotangent."""
    from ompi_tpu.ops import causal_conv

    return causal_conv.conv_forward(x, w)


def _kernel_conv_fwd(x, w):
    from ompi_tpu.ops import causal_conv

    return causal_conv.conv_forward(x, w), (x, w)


def _kernel_conv_bwd(res, dy):
    from ompi_tpu.ops import causal_conv

    return causal_conv.conv_backward(*res, dy)


_kernel_conv.defvjp(_kernel_conv_fwd, _kernel_conv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _kernel_conv_rule(x, w, g, beta, chunk, hk, unit):
    """The convolution and the rule behind it, both on their kernels, as
    one rule of autodiff: (the convolution's [q | k | v] (b, s, c), the
    rule's o) as ``_kernel_conv(x, w)`` and ``_kernel_rule`` of that one
    array make them.  What differs is what a backward pass keeps: x, w,
    g, beta and the rule's states and inverses, not [q | k | v], which
    the backward rule makes again by a second ``conv_forward`` in front
    of the rule's backward kernel.  Kept, as ``_kernel_rule`` after
    ``_kernel_conv`` keeps it, it lives from a checkpointed layer's
    recomputed pass through the rule's backward kernel, 0.54 GB at
    16,384 positions: Qwen3-Next's step then compiles to a peak of 15.91
    GB of a v5e's 16 and this way to 15.64, for 1.6 ms a layer on the
    chip (PR 54)."""
    with jax.named_scope("otpu_gdn_conv"):
        qkv = _kernel_conv(x, w)
    with jax.named_scope("otpu_gdn_rule"):
        return qkv, _kernel_rule((qkv,), g, beta, chunk, hk, unit)


def _kernel_conv_rule_fwd(x, w, g, beta, chunk, hk, unit):
    with jax.named_scope("otpu_gdn_conv"):
        qkv = _kernel_conv(x, w)
    with jax.named_scope("otpu_gdn_rule"):
        o, (_, _, _, kept) = _kernel_rule_fwd((qkv,), g, beta, chunk, hk,
                                              unit)
    return (qkv, o), (x, w, g, beta, kept)


def _kernel_conv_rule_bwd(chunk, hk, unit, res, cts):
    x, w, g, beta, kept = res
    d_seen, do = cts
    with jax.named_scope("otpu_gdn_conv"):
        # behind the cotangent: the compiler would else take the
        # recomputed pass's call for this one and keep its result
        x, do = jax.lax.optimization_barrier((x, do))
        qkv = _kernel_conv(x, w)
    with jax.named_scope("otpu_gdn_rule"):
        (d_qkv,), dg, dbeta = _kernel_rule_bwd(
            chunk, hk, unit, ((qkv,), g, beta, kept), do)
    with jax.named_scope("otpu_gdn_conv"):
        return (*_kernel_conv_bwd((x, w), d_qkv + d_seen), dg, dbeta)


_kernel_conv_rule.defvjp(_kernel_conv_rule_fwd, _kernel_conv_rule_bwd)


def conv_on_kernels(interpret, taps, c, s) -> tuple:
    """``(on_kernel, why)`` of the convolution: on the Pallas kernels
    where Mosaic compiles (``interpret`` false: a TPU) and the shape has
    tiles (``ops/causal_conv.refusal``)."""
    if interpret:
        return False, INTERPRET
    from ompi_tpu.ops import causal_conv

    why = causal_conv.refusal(taps, c, s)
    return not why, why


#: what the delta rule's L2 norms add under the root (``layers.l2norm``'s)
L2NORM_EPS = 1e-6


def gated_delta_net(p, x, cfg, *, interpret: bool = True, at=None):
    """qwen3_next's Gated DeltaNet operator (Qwen3-Next-80B-A3B's
    ``linear_attention`` layers; arXiv:2412.06464), **without** the
    residual add, on the residual stream ``x`` (b, s, d) float32, whole
    (``linear_num_key_heads`` key heads, ``linear_num_value_heads`` value
    heads): pre-norm; ``[q | k | v | z] = n W_qkvz`` in that order
    (``in_proj``, matmul inputs in ``compute_dtype``) and ``[b | a] = n
    W_ba`` (``ba_proj``, float32); ``[q | k | v] <- silu(causal depthwise
    convolution of conv_kernel taps a channel, zeros before the
    sequence's start, no bias)``; ``beta = sigmoid(b)``, ``g =
    -exp(A_log) softplus(a + dt_bias)`` a value head; q and k
    L2-normalised over a head, q times ``1 / sqrt(key width)``; the
    gated delta rule in chunks of ``chunk_size`` (``gated_delta_chunked``);
    ``rmsnorm over each head of o * gain * silu(z)`` (the gate behind
    the gain); ``y W_out``.  Everything between the two large
    projections is float32.  The sequence is never reset inside a packed
    row.  Where Mosaic compiles (``interpret`` false: a TPU) and the
    width is whole tiles of lanes (``conv_on_kernels``) the convolution
    and its silu run in Pallas kernels that read and write each array
    once a pass (``_kernel_conv``); everywhere else the lines here, which
    are the kernels' oracle.  Where the rule runs on its Pallas kernels
    (``rule_on_kernels``)
    they read q, k and v where the convolution left them, one array, and
    put q's and k's rows at unit length themselves (``_kernel_rule``):
    a 4D view of q, k or v costs XLA two relayouts of it a pass.
    Returns (the sublayer's output, no statistics, what the rule read and
    made of the first value head, by token row: ``gdn_q_seq``, ``gdn_k_seq`` (T,
    dk), ``gdn_v_seq`` (T, dv), ``gdn_g_seq``, ``gdn_beta_seq`` (T,)
    whole, because a position's state holds every earlier one, and the
    rule's ``gdn_o`` (T, dv))."""
    b, s, d = x.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key, val, dt = hk * dk, hv * dv, cfg.compute_dtype
    with jax.named_scope("otpu_gdn_proj"):
        n = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps).reshape(b * s, d)
        # W_qkvz's product by its two readers' columns: what the
        # convolution reads is done with before z is read, and no slice
        # of the (T, q | k | v | z) float32 array is ever written
        w = cast_param(p["in_proj"], dt)
        qkv, z = (matmul(n, cols, dt, weight=False).reshape(b, s, -1)
                  for cols in (w[:, :2 * key + val], w[:, 2 * key + val:]))
        ba = jnp.dot(n, p["ba_proj"], precision=jax.lax.Precision.HIGHEST
                     ).reshape(b, s, 2, hv)
    with jax.named_scope("otpu_gdn_rule"):
        beta = jax.nn.sigmoid(ba[:, :, 0])
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, :, 1]
                                                   + p["dt_bias"])
    taps, unit = p["conv_w"].shape[0], (L2NORM_EPS, dk ** -0.5)
    conv_on, _ = conv_on_kernels(interpret, taps, qkv.shape[2], s)
    # the kernels read the convolution's one array where a key head's
    # value heads start at a lane block; else q, k and v apart
    # (``gated_delta_chunked``): by the kernels either way
    rule_on = rule_on_kernels(interpret, cfg.chunk_size, dk, dv, hv // hk,
                              s)[0] and 2 * hk % (hv // hk) == 0
    if conv_on and rule_on:
        qkv, o = _kernel_conv_rule(qkv, p["conv_w"], g, beta,
                                   cfg.chunk_size, hk, unit)
    else:
        with jax.named_scope("otpu_gdn_conv"):
            if conv_on:
                qkv = _kernel_conv(qkv, p["conv_w"])
            else:
                padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
                qkv = jax.nn.silu(sum(padded[:, j:j + s] * p["conv_w"][j]
                                      for j in range(taps)))
    with jax.named_scope("otpu_gdn_rule"):
        heads = lambda t, n, width: t.reshape(b, s, n, width)
        if rule_on:
            # the kernels read q, k and v where the convolution left them
            # and norm q and k themselves: only the first head, which the
            # step reports, is cut out and normed here
            if not conv_on:
                o = _kernel_rule((qkv,), g, beta, cfg.chunk_size, hk, unit)
            o = heads(o, hv, dv)
            q, k, v = (heads(qkv[..., first:first + width], 1, width)
                       for first, width in ((0, dk), (key, dk), (2 * key, dv)))
            q, k = l2norm(q, L2NORM_EPS) * dk ** -0.5, l2norm(k, L2NORM_EPS)
        else:
            q, k = (l2norm(heads(qkv[..., j * key:(j + 1) * key], hk, dk),
                           L2NORM_EPS) for j in (0, 1))
            q = q * dk ** -0.5
            v = heads(qkv[..., 2 * key:], hv, dv)
            o = gated_delta_chunked(q, k, v, g, beta, cfg.chunk_size,
                                    interpret)
        rows = lambda t: t.reshape((b * s,) + t.shape[2:])
        seen = {"gdn_q_seq": rows(q[:, :, 0]), "gdn_k_seq": rows(k[:, :, 0]),
                "gdn_v_seq": rows(v[:, :, 0]), "gdn_g_seq": rows(g[:, :, 0]),
                "gdn_beta_seq": rows(beta[:, :, 0]),
                "gdn_o": rows(o[:, :, 0])}
    with jax.named_scope("otpu_gdn_norm"):
        y = rmsnorm_gain(o, p["gate_norm"], cfg.rms_norm_eps) \
            * jax.nn.silu(z.reshape(b, s, hv, dv))
    with jax.named_scope("otpu_gdn_proj"):
        return matmul(y.reshape(b * s, val), p["out_proj"], dt
                      ).reshape(b, s, d), {}, seen


def _gdn_shapes(cfg) -> dict:
    """The gain, ``in_proj`` (d, q | k | v | z), ``ba_proj`` (d, b | a: a
    column a value head each), the taps (kernel, q | k | v), ``A_log`` and
    ``dt_bias`` a value head, the gated norm's gain over a head,
    ``out_proj``."""
    d, hv = cfg.hidden_size, cfg.linear_num_value_heads
    key = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    val = hv * cfg.linear_value_head_dim
    return {"ln1": (d,), "in_proj": (d, 2 * key + 2 * val),
            "ba_proj": (d, 2 * hv),
            "conv_w": (cfg.conv_kernel, 2 * key + val), "A_log": (hv,),
            "dt_bias": (hv,), "gate_norm": (cfg.linear_value_head_dim,),
            "out_proj": (val, d)}


def _gdn_plan(cfg, b, s, interpret) -> dict:
    """What ``gated_delta_net`` holds: the rule's and the convolution's
    decisions at the configuration's heads, a layer application one of
    each (SPC ``gdn_rule_built``, ``gdn_conv_built`` and their
    ``_kernel_built``)."""
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    rule = rule_on_kernels(interpret, cfg.chunk_size, dk, dv, hv // hk, s)
    conv = conv_on_kernels(interpret, cfg.conv_kernel,
                           2 * hk * dk + hv * dv, s)
    return held({"gdn_rule_built": 1, "gdn_rule_kernel_built": int(rule[0]),
                 "gdn_conv_built": 1, "gdn_conv_kernel_built": int(conv[0])},
                rule=rule, conv=conv)


#: qwen3_next's ``linear_attention`` (``conv_kernel``: the file's
#: ``linear_conv_kernel_dim``); ``dt_bias`` starts at one
GDN = Sublayer(
    name="linear_attention", group="gdn", scope="otpu_gdn",
    run=gated_delta_net, shapes=_gdn_shapes,
    undecayed=("ln1", "A_log", "dt_bias", "gate_norm"),
    starts={"conv_w": uniform_taps, "A_log": log_uniform_1_16},
    reports=lambda cfg: {"gdn_q_seq": 1, "gdn_k_seq": 1, "gdn_v_seq": 1,
                         "gdn_g_seq": 0, "gdn_beta_seq": 0, "gdn_o": 1},
    plan=_gdn_plan)
