"""What every sublayer of a public model is made of: a parameter's cast,
the matmul with float32 results, RMSNorm with a gain, LayerNorm, an L2 norm, the rotary
embeddings and the two feed-forwards.  ``parallel/experts.py`` and
``parallel/model.py`` build on these; nothing here imports either (of the
package only ``parallel/config.py``'s YaRN scale).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.config import yarn_mscale


def cast_param(w, dtype):
    """A parameter leaf in the matmuls' ``dtype``, under the scope
    ``otpu_cast``: XLA makes a pass of its own of a large leaf's cast
    (and of its transposition), which a trace then tells from the
    sublayer's other work."""
    with jax.named_scope("otpu_cast"):
        return w.astype(dtype)


def matmul(a, w, compute_dtype, weight: bool = True):
    """``a @ w`` with inputs in ``compute_dtype`` and a float32 result:
    bfloat16 inputs accumulate in float32 on the MXU; float32 inputs
    multiply at the highest precision (on a TPU the default would round
    them to bfloat16 on the way in).  ``w`` is a parameter leaf
    (``cast_param``) unless ``weight`` is false."""
    f32 = jnp.dtype(compute_dtype) == jnp.float32
    dtype = jnp.float32 if f32 else compute_dtype
    a = a.astype(dtype)
    w = cast_param(w, dtype) if weight else w.astype(dtype)
    if f32:
        return jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST)
    return jnp.dot(a, w, preferred_element_type=jnp.float32)


def contract(eq, a, b, compute_dtype):
    """A float32 einsum of blocked attention, its inputs in
    ``compute_dtype``."""
    if jnp.dtype(compute_dtype) == jnp.float32:
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(eq, a.astype(compute_dtype), b.astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def rmsnorm_gain(x, gain, eps: float):
    """RMSNorm with a learned gain, in float32 whatever ``x`` is."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def layernorm(x, gain, bias, eps: float):
    """LayerNorm with a learned gain and bias over the last axis, in
    float32: the norm a lightning indexer puts on its one key."""
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain + bias


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32: the
    norm a delta-rule layer puts on its queries and keys (no gain, a sum
    where RMSNorm has a mean)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def rope_tables(s: int, rot: int, theta: float, positions=None):
    """(cos, sin) (s, rot) float32 of ``rope``'s angles: a pair's angle on
    both its halves, at positions 0..s-1 or at ``positions`` (s,)."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    at = jnp.arange(s, dtype=jnp.float32) if positions is None \
        else jnp.asarray(positions).astype(jnp.float32)
    ang = at[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)           # (s, rot)
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, theta: float, rotary: int | None = None, positions=None):
    """Rotary position embedding on ``x`` (b, h, s, hd) at positions
    0..s-1, the half-split form of the HF models (``rotate_half``), over
    the leading ``rotary`` entries of the head (all of it where None:
    OLMoE's and lfm2's; qwen3_next's ``partial_rotary_factor`` turns the
    first quarter and passes the rest as it is).  ``positions`` (s,)
    gives the rows others (block diffusion's two copies of a sequence
    repeat 0..L-1); None is the call without it, bit for bit."""
    hd, s = x.shape[-1], x.shape[-2]
    rot = hd if rotary is None else rotary
    cos, sin = rope_tables(s, rot, theta, positions)
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    whole = rot == hd
    turned = (x if whole else x[..., :rot]) * cos \
        + jnp.concatenate([-x2, x1], -1) * sin
    return turned if whole else jnp.concatenate([turned, x[..., rot:]], -1)


def yarn_inv_freq(rot: int, theta: float, yarn: dict):
    """The ``rot / 2`` inverse frequencies of a rotary part ``rot`` wide
    under YaRN (arXiv:2309.00071; ``transformers.modeling_rope_utils.
    _compute_yarn_parameters`` with ``truncate`` at its default, true),
    float32: pair i turns at ``extra_i = theta^(-2i / rot)`` where it makes
    more than ``beta_fast`` turns over the ``original_max_position_
    embeddings`` the base was trained at, at ``extra_i / factor`` where
    fewer than ``beta_slow``, and on the line between the two pairs'
    numbers in between: ``inter (1 - mask) + extra mask``, ``mask = 1 -
    clip((i - low) / (high - low), 0, 1)``.  Returns (the frequencies,
    what cos and sin are multiplied by: ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)``)."""
    factor, original = yarn["factor"], yarn["original_max_position_embeddings"]
    turns_at = lambda beta: rot * math.log(
        original / (beta * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_at(yarn["beta_fast"])), 0)
    high = min(math.ceil(turns_at(yarn["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001           # the source's guard against a 0 / 0
    extra = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    mask = 1.0 - jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                          / (high - low), 0.0, 1.0)
    return (extra / factor) * (1.0 - mask) + extra * mask, \
        yarn_mscale(factor, yarn["mscale"]) \
        / yarn_mscale(factor, yarn["mscale_all_dim"])


def _rope_tables(x, theta: float, first: int, seq_axis: int, yarn=None):
    """(cos, sin) of ``rope_interleaved``, shaped to broadcast against
    ``x``: a pair's angle on both its entries, 1 and 0 on the entries
    before ``first``.  ``yarn`` (a configuration's ``rope_scaling`` group;
    None: plain RoPE, and the tables are the call's without it) scales the
    frequencies and the two tables (``yarn_inv_freq``)."""
    width, s = x.shape[-1], x.shape[seq_axis]
    hd = width - first
    if yarn is None:
        inv, by = 1.0 / (theta ** (jnp.arange(
            0, hd, 2, dtype=jnp.float32) / hd)), 1.0
    else:
        inv, by = yarn_inv_freq(hd, theta, yarn)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    pad = lambda a, fill: jnp.concatenate(
        [jnp.full((s, first), fill, jnp.float32),
         jnp.repeat(a if by == 1.0 else by * a, 2, -1)], -1)
    shape = [1] * x.ndim
    shape[seq_axis], shape[-1] = s, width
    return (pad(jnp.cos(ang), 1.0).reshape(shape),
            pad(jnp.sin(ang), 0.0).reshape(shape))


def rope_interleaved(x, theta: float, first: int = 0, seq_axis: int = -2):
    """Rotary position embedding on interleaved pairs (x[2i], x[2i+1])
    (DeepSeek-V3's ``rope_interleave``) of the entries from ``first`` on
    of ``x``'s last axis, at positions 0..s-1 along ``seq_axis``; the
    entries before ``first`` pass unchanged.  The pair's partner comes
    by ``jnp.roll``, which XLA for a TPU writes to HBM as shifted copies
    (a 191-wide and a one-lane slice each way, the lane padded to 128:
    2.6 GB a layer and pass of the JoyAI step for q's 268 MB, offline
    compile, PR 41), so the model no longer takes this way
    (``project_rope``): it is the ``jnp`` twin the tests compare that
    with."""
    cos, sin = _rope_tables(x, theta, first, seq_axis)
    is_first = (jnp.arange(x.shape[-1]) - first) % 2 == 0
    partner = jnp.where(is_first, -jnp.roll(x, -1, -1), jnp.roll(x, 1, -1))
    return x * cos + partner * sin


def rotary_partner_columns(w, compute_dtype):
    """The columns ``wp`` of interleaved rotary columns ``w`` (.., rot)
    with ``a @ wp`` the rotary partner of ``a @ w``: ``wp[:, 2i] =
    -w[:, 2i+1]``, ``wp[:, 2i+1] = w[:, 2i]``.  A product with a signed
    permutation (each result one input times 1 or -1: exact in any
    dtype), because a swap of neighbouring columns any other way is a
    lane rotation or an array two lanes wide."""
    rot = w.shape[-1]
    i = jnp.arange(0, rot, 2)
    swap = jnp.zeros((rot, rot), jnp.float32) \
        .at[i, i + 1].set(1.0).at[i + 1, i].set(-1.0)
    return matmul(w, swap, compute_dtype, weight=False).astype(w.dtype)


def rope_partnered(x, partner, theta: float, seq_axis: int = -2, yarn=None):
    """``rope_interleaved`` of ``x`` on its trailing ``partner.shape[-1]``
    entries, given their partners (``partner[2i] = -x[2i+1]``,
    ``partner[2i+1] = x[2i]``, counted from the first rotary entry): one
    elementwise pass, the partner set behind the leading entries by a pad
    (where those are a multiple of 128 lanes, as a latent head's are, it
    starts a tile of its own).  ``yarn``: ``_rope_tables``'."""
    first = x.shape[-1] - partner.shape[-1]
    cos, sin = _rope_tables(x, theta, first, seq_axis, yarn)
    partner = jnp.pad(partner, ((0, 0),) * (x.ndim - 1) + ((first, 0),))
    return x * cos + partner * sin


def project_rope(a, w, heads: int, first: int, theta: float, compute_dtype,
                 yarn=None):
    """``a @ w`` (b, s, heads x width) split into ``heads`` with
    ``rope_interleaved(.., first=first, seq_axis=1)`` on each, float32
    (b, s, heads, width), with no shifted copy of the product: the
    partner of column j of ``a @ w`` is, sign apart, column j^1 of the
    same product, so ``a @ rotary_partner_columns(w's rotary columns)``
    **is** the partner, the same dot products of the same inputs
    accumulated the same way (for JoyAI's q 51 GFLOP a layer and pass in
    place of the 2.6 GB the rolled copies moved, PR 41).  Its gradient
    is autodiff's: elementwise passes and matmuls.  ``yarn``: the
    configuration's ``rope_scaling`` group (``_rope_tables``)."""
    b, s, _ = a.shape
    w = cast_param(w, compute_dtype).reshape(w.shape[0], heads, -1)
    wp = rotary_partner_columns(w[..., first:], compute_dtype)
    dot = lambda cols: matmul(a, cols.reshape(cols.shape[0], -1),
                              compute_dtype, weight=False) \
        .reshape(b, s, heads, -1)
    return rope_partnered(dot(w), dot(wp), theta, seq_axis=1, yarn=yarn)


def ffn_bwd_written(compute_dtype, d: int, ff: int) -> tuple:
    """``(written, why)``: whether ``swiglu`` and ``relu2`` on a stream
    ``d`` wide with ``ff`` hidden units take their written backward rule,
    and where not the clause.  Not under a float32 ``compute_dtype``
    (``matmul``'s own condition; the references' and the CPU tests' way):
    nothing is rounded on the way into a product, so there is nothing to
    make once in a narrower dtype.  Not where the feed-forward is narrower
    than the stream: the rule spares elementwise passes over (T, ff) and
    pays a cast of the incoming cotangent (T, d) behind its barrier, which
    a shared expert of 512 on a stream of 2,048 lost by (Qwen3-Next's
    step, 16.8 -> 18.2 ms in the scope, PR 72) where every feed-forward
    at least as wide as its stream gained."""
    if jnp.dtype(compute_dtype) == jnp.float32:
        return False, ("compute_dtype float32: the plain lines, their "
                       "backward pass autodiff's")
    if ff < d:
        return False, (f"{ff} hidden units on a stream of {d}: narrower "
                       "than the stream, the cotangent's cast costs more "
                       "than the rule spares")
    return True, ""


def _ffn_backward(hb, ups, down, ct, act_and_cotangents):
    """The backward pass both feed-forwards share, on operands in the
    matmuls' dtype ``dt``: ``ups`` the matrices ``hb`` (T, d) is
    multiplied by (SwiGLU's gate and up, relu2's up), ``ct`` (T, d) the
    float32 cotangent of the result.  ``act_and_cotangents(d_act, *pre)``
    gives, in float32, the activation and the cotangent of each
    pre-activation ``pre[i] = hb @ ups[i]``.  **Every elementwise array is
    made once, rounded to ``dt`` where a product's float32 operand would
    be rounded on the way into the MXU, and handed to the products behind
    ``optimization_barrier``**: left to itself XLA makes ``act`` and each
    cotangent anew from the float32 pre-activations as the operand side of
    every product that reads it (five times a SwiGLU layer, an exponential
    and a division a value each; ``dW_down`` at 41% of a v5e's peak in
    Granite's step, PR 72), behind the barrier it makes them in the
    epilogue of the last recomputed product and every product after is
    ``dt`` x ``dt``.  Returns (dh, the cotangent of each of ``ups``, that
    of ``down``), rounded to ``dt`` as the transposes of the casts round
    them."""
    dt = hb.dtype
    dot = functools.partial(matmul, compute_dtype=dt, weight=False)
    pre = [dot(hb, w) for w in ups]
    ctb = ct.astype(dt)
    d_act = dot(ctb, down.T).astype(dt).astype(jnp.float32)
    act, *d_pre = act_and_cotangents(d_act, *pre)
    act, ctb, *d_pre = jax.lax.optimization_barrier(
        (act.astype(dt), ctb, *(d.astype(dt) for d in d_pre)))
    dh, *more = (dot(d, w.T) for d, w in zip(d_pre, ups))
    return (sum(more, dh).astype(dt),
            *(dot(hb.T, d).astype(dt) for d in d_pre),
            dot(act.T, ctb).astype(dt))


def _swiglu_parts(d_act, g, u):
    s = jax.nn.sigmoid(g)
    return (g * s * u, d_act * u * (s * (1. + g * (1. - s))),
            d_act * (g * s))


def _relu2_parts(d_act, u):
    r = jax.nn.relu(u)
    return r * r, d_act * (2. * r)


def _swiglu_lines(h, gate, up, down, compute_dtype):
    act = jax.nn.silu(matmul(h, gate, compute_dtype)) \
        * matmul(h, up, compute_dtype)
    return matmul(act, down, compute_dtype)


def _relu2_lines(h, up, down, compute_dtype):
    act = jnp.square(jax.nn.relu(matmul(h, up, compute_dtype)))
    return matmul(act, down, compute_dtype)


def _written(lines, parts):
    """``lines`` (a feed-forward's plain lines) over operands already in the
    matmuls' dtype, with ``_ffn_backward`` as its backward rule.  The
    residuals are the operands alone: under a layer's ``jax.checkpoint``
    nothing more is kept than autodiff keeps, and the pre-activations are
    made again in the rule as they were in the recomputed pass."""
    fn = jax.custom_vjp(lambda hb, *ws: lines(hb, *ws, hb.dtype))
    fn.defvjp(lambda hb, *ws: (lines(hb, *ws, hb.dtype), (hb, *ws)),
              lambda kept, ct: _ffn_backward(kept[0], kept[1:-1], kept[-1],
                                             ct, parts))
    return fn


_swiglu_written = _written(_swiglu_lines, _swiglu_parts)
_relu2_written = _written(_relu2_lines, _relu2_parts)


def _feed_forward(lines, written, h, ws, compute_dtype):
    """``lines`` on rows ``h`` and the leaves ``ws``, the first (d, ff): as
    they stand where ``ffn_bwd_written`` says so, else ``written`` over
    the cast operands (the casts, their scope and their transposes stay
    autodiff's)."""
    if not ffn_bwd_written(compute_dtype, *ws[0].shape)[0]:
        return lines(h, *ws, compute_dtype)
    return written(h.astype(compute_dtype),
                   *(cast_param(w, compute_dtype) for w in ws))


def swiglu(h, gate, up, down, compute_dtype):
    """``down(silu(gate h) * up h)``: a dense feed-forward, or a shared
    expert, on rows ``h`` (T, d), float32.  In a narrower
    ``compute_dtype`` its backward pass is written out (``_ffn_backward``;
    ``ffn_bwd_written`` decides)."""
    return _feed_forward(_swiglu_lines, _swiglu_written, h, (gate, up, down),
                         compute_dtype)


def relu2(h, up, down, compute_dtype):
    """``down(relu(up h)^2)``: nemotron_h's feed-forward (no gate), a
    shared expert on rows ``h`` (T, d); its backward pass as
    ``swiglu``'s."""
    return _feed_forward(_relu2_lines, _relu2_written, h, (up, down),
                         compute_dtype)
