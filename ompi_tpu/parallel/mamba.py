"""Mamba-2 (arXiv:2405.21060; nemotron_h's ``M`` layers): the state-space
scan in chunks and the mixer around it, with the mixer's entry in
``parallel/model.py``'s table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.parallel.layers import contract, matmul, rmsnorm_gain
from ompi_tpu.parallel.sublayer import (Sublayer, log_uniform_1_16,
                                        uniform_taps)


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """Mamba-2's state-space scan (arXiv:2405.21060, the chunked form of
    its section 6) in float32: per head the state ``h_t = exp(dt_t a)
    h_{t-1} + dt_t x_t b_t^T`` (p x n) and the output ``y_t = h_t c_t``,
    from a zero state, never reset.  ``x`` (bt, s, h, p); ``dt`` (bt, s,
    h), positive; ``a`` (h,), negative; ``b``, ``c`` (bt, s, g, n), each
    group's shared by ``h / g`` consecutive heads.  Returns y (bt, s, h,
    p), without the skip term.

    The sequence is cut into chunks of ``chunk`` positions (padded at
    the end with dt = 0, which leaves the state as it is).  Inside a
    chunk position i reads position j <= i through ``exp(sum_{j<k<=i}
    dt_k a)``, a (chunk, chunk) matrix a head times ``c_i . b_j``; each
    chunk leaves ``sum_j exp(sum_{k>j} dt_k a) dt_j x_j b_j^T`` to the
    state; the states go from chunk to chunk by a ``lax.scan`` of
    ``s / chunk`` steps; position i of a chunk reads the state that
    entered it through ``exp(sum_{k<=i} dt_k a)``.  The running sums,
    the exponentials, the states and every product are float32 at the
    highest precision: at a chip's share of the heads they are under a
    hundredth of a layer's operations.  The backward pass is autodiff's
    through the same chunks."""
    bt, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    _f32 = lambda eq, one, two: contract(eq, one, two, jnp.float32)
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    # (bt, chunks, groups, heads a group, position, .)
    xd = (x * dt[..., None]).reshape(bt, nc, chunk, g, r, p) \
        .transpose(0, 1, 3, 4, 2, 5)
    da = (dt * a).reshape(bt, nc, chunk, g, r).transpose(0, 1, 3, 4, 2)
    bc = b.reshape(bt, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cc = c.reshape(bt, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cum = jnp.cumsum(da, axis=-1)                        # sum_{k<=i} dt_k a
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                 # (.., i, j)
    cb = _f32("zcgin,zcgjn->zcgij", cc, bc)
    y = _f32("zcgrij,zcgrjp->zcgrip", cb[:, :, :, None] * decay, xd)
    to_end = jnp.exp(cum[..., -1:] - cum)                # (.., j)
    left = _f32("zcgrjp,zcgjn->zcgrpn", xd * to_end[..., None], bc)
    through = jnp.exp(cum[..., -1])                      # a chunk's decay

    def carry(state, xs):
        left_c, through_c = xs
        return state * through_c[..., None, None] + left_c, state

    _, entered = jax.lax.scan(
        carry, left[:, 0] * 0,                           # carries x's vma
        (jnp.moveaxis(left, 1, 0), jnp.moveaxis(through, 1, 0)))
    y = y + _f32("zcgin,zcgrpn->zcgrip", cc, jnp.moveaxis(entered, 0, 1)) \
        * jnp.exp(cum)[..., None]
    return y.transpose(0, 1, 4, 2, 3, 5).reshape(bt, s + pad, h, p)[:, :s]


def mamba_mixer(p, x, cfg, *, interpret: bool = True, at=None):
    """nemotron_h's Mamba-2 mixer, **without** the residual add, on the
    residual stream ``x`` (b, s, d) float32, for the ``n_mamba_heads_here``
    heads and ``n_groups_here`` B/C groups held here: pre-norm; ``[z |
    xBC | dt] = u W_in`` (matmul inputs in ``compute_dtype``); ``xBC <-
    silu(causal depthwise convolution over conv_kernel positions, with
    bias)``, split into x (heads x ``mamba_head_dim``), B and C (groups x
    ``ssm_state_size``); ``dt <- softplus(dt + dt_bias)``, ``a =
    -exp(A_log)``; the scan in chunks of ``chunk_size``
    (``ssd_chunked``) plus ``D x``; ``rmsnorm over each group of (y *
    silu(z)) * gain``; ``y W_out``.  Everything between the two
    projections is float32.  Returns (the sublayer's output, no
    statistics, what the scan read and made of the first held head, by
    token row: its step
    ``ssm_dt_seq`` (T,), its ``ssm_x_seq`` (T, p) and its group's
    ``ssm_b_seq`` and ``ssm_c_seq`` (T, n) whole, because a position's
    state holds every earlier one, and the scan's ``ssm_y`` (T, p)
    before the skip term)."""
    b, s, d = x.shape
    nh, hd, n = cfg.n_mamba_heads_here, cfg.mamba_head_dim, cfg.ssm_state_size
    g, dt, eps = cfg.n_groups_here, cfg.compute_dtype, cfg.rms_norm_eps
    inner = nh * hd
    with jax.named_scope("otpu_ssm_proj"):
        u = rmsnorm_gain(x, p["norm"], eps)
        zxd = matmul(u.reshape(b * s, d), p["in_proj"], dt).reshape(b, s, -1)
        z, xbc, step = (zxd[..., :inner], zxd[..., inner:-nh], zxd[..., -nh:])
    with jax.named_scope("otpu_ssm_conv"):
        taps = p["conv_w"].shape[0]
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        xbc = jax.nn.silu(p["conv_b"] + sum(
            padded[:, k:k + s] * p["conv_w"][k] for k in range(taps)))
    with jax.named_scope("otpu_ssm_scan"):
        xs = xbc[..., :inner].reshape(b, s, nh, hd)
        bs, cs = (xbc[..., inner + k * g * n:inner + (k + 1) * g * n]
                  .reshape(b, s, g, n) for k in (0, 1))
        step = jax.nn.softplus(step + p["dt_bias"])
        y = ssd_chunked(xs, step, -jnp.exp(p["A_log"]), bs, cs,
                        cfg.chunk_size)
        rows = lambda t: t.reshape((b * s,) + t.shape[2:])
        seen = {"ssm_dt_seq": rows(step[:, :, 0]),
                "ssm_x_seq": rows(xs[:, :, 0]),
                "ssm_b_seq": rows(bs[:, :, 0]),
                "ssm_c_seq": rows(cs[:, :, 0]), "ssm_y": rows(y[:, :, 0])}
        y = (y + p["D"][:, None] * xs).reshape(b, s, inner)
    with jax.named_scope("otpu_ssm_norm"):
        y = (y * jax.nn.silu(z)).reshape(b, s, g, inner // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y.reshape(b, s, inner) * p["gate_norm"]
    with jax.named_scope("otpu_ssm_proj"):
        return matmul(y.reshape(b * s, inner), p["out_proj"], dt
                      ).reshape(b, s, d), {}, seen


def _mixer_shapes(cfg) -> dict:
    """The pre-norm's gain, ``in_proj`` (d, z + x + B + C + dt), the
    convolution's taps (kernel, x + B + C) and bias, ``dt_bias``, ``A_log``
    and ``D`` a head, the gated norm's gain, ``out_proj``: of the held
    heads and their B/C groups."""
    d, nh = cfg.hidden_size, cfg.n_mamba_heads_here
    inner = nh * cfg.mamba_head_dim
    bc = 2 * cfg.n_groups_here * cfg.ssm_state_size
    return {"norm": (d,), "in_proj": (d, 2 * inner + bc + nh),
            "conv_w": (cfg.conv_kernel, inner + bc), "conv_b": (inner + bc,),
            "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
            "gate_norm": (inner,), "out_proj": (inner, d)}


def _mixer_dt_bias(key, shape, cfg):
    """The inverse softplus of a step drawn log-uniformly between
    ``time_step_min`` and ``time_step_max``, at least ``time_step_floor``
    (arXiv:2405.21060's code)."""
    step = jnp.maximum(cfg.time_step_floor, jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, np.log(cfg.time_step_min),
        np.log(cfg.time_step_max))))
    return step + jnp.log(-jnp.expm1(-step))


#: nemotron_h's ``M``; ``D`` starts at one
MIXER = Sublayer(
    name="M", group="mamba", scope="otpu_mamba", run=mamba_mixer,
    shapes=_mixer_shapes,
    undecayed=("norm", "conv_b", "dt_bias", "A_log", "D", "gate_norm"),
    starts={"conv_w": uniform_taps, "conv_b": uniform_taps,
            "dt_bias": _mixer_dt_bias, "A_log": log_uniform_1_16},
    reports=lambda cfg: {"ssm_dt_seq": 0, "ssm_x_seq": 1, "ssm_b_seq": 1,
                         "ssm_c_seq": 1, "ssm_y": 1})
