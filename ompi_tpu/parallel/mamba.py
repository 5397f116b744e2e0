"""Mamba-2 (arXiv:2405.21060; nemotron_h's ``M`` layers and
granitemoehybrid's ``mamba`` layers): the state-space scan in chunks and the
mixer around it, both reset at the document boundaries of a packed row
where the caller gives the row's documents, with the mixer's entries in
``parallel/model.py``'s table.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.parallel.layers import contract, matmul, rmsnorm_gain
from ompi_tpu.parallel.sublayer import (INTERPRET, Sublayer, held,
                                        log_uniform_1_16, uniform_taps)


def ssd_chunked(x, dt, a, b, c, chunk: int, doc=None):
    """Mamba-2's state-space scan (arXiv:2405.21060, the chunked form of
    its section 6) in float32: per head the state ``h_t = exp(dt_t a)
    h_{t-1} + dt_t x_t b_t^T`` (p x n) and the output ``y_t = h_t c_t``,
    from a zero state, reset to zero where a document starts.  ``x`` (bt,
    s, h, p); ``dt`` (bt, s, h), positive; ``a`` (h,), negative; ``b``,
    ``c`` (bt, s, g, n), each group's shared by ``h / g`` consecutive
    heads; ``doc`` (bt, s) int32, a position's document, never falling
    along a row (``objective.documents``; None: a row is one document, and
    the jaxpr is the one without the argument).  Returns y (bt, s, h, p),
    without the skip term.

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
    hundredth of a layer's operations.  The backward pass of this form is
    autodiff's through the same chunks.

    This XLA form is the CPU's and the oracle of the Pallas kernels
    (``ops/ssd_scan``) that ``mamba_mixer`` takes where Mosaic compiles and
    the shape has tiles (``_kernel_scan``): there the chunks' matrices and
    the states stay in VMEM, forward and backward.

    Under ``doc`` a document starts anywhere in a chunk, and with j <= i in
    a chunk: position i reads j iff ``doc_i == doc_j``; j's term reaches
    the chunk's state iff ``doc_j`` is the chunk's last position's; the
    chunk passes the state that entered it on iff its first and its last
    position lie in the document of the chunk before's last; position i
    reads the entering state iff ``doc_i`` is that document.  Every mask
    is a ``where`` over a finite product: no ``-inf - -inf`` arises."""
    bt, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    _f32 = lambda eq, one, two: contract(eq, one, two, jnp.float32)
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    i = jnp.arange(chunk)
    seen = i[:, None] >= i[None, :]                      # j <= i
    if doc is not None:
        # (bt, chunks, position); the padding lies in the last document
        dc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge").reshape(
            bt, nc, chunk)
        wide = lambda t: t[:, :, None, None]         # over groups and heads
        seen = wide(seen & (dc[..., :, None] == dc[..., None, :]))
        # the document that enters a chunk: the one before's last
        # position's (the first chunk's state enters as zero)
        entering = jnp.concatenate([dc[:, :1, 0], dc[:, :-1, -1]], axis=1)
        reads_entered = wide(dc == entering[..., None])
        leaves_state = wide(dc == dc[..., -1:])
        passes_on = wide((dc[..., 0] == dc[..., -1])
                         & (dc[..., 0] == entering))
    # (bt, chunks, groups, heads a group, position, .)
    xd = (x * dt[..., None]).reshape(bt, nc, chunk, g, r, p) \
        .transpose(0, 1, 3, 4, 2, 5)
    da = (dt * a).reshape(bt, nc, chunk, g, r).transpose(0, 1, 3, 4, 2)
    bc = b.reshape(bt, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cc = c.reshape(bt, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cum = jnp.cumsum(da, axis=-1)                        # sum_{k<=i} dt_k a
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                 # (.., i, j)
    cb = _f32("zcgin,zcgjn->zcgij", cc, bc)
    y = _f32("zcgrij,zcgrjp->zcgrip", cb[:, :, :, None] * decay, xd)
    to_end = jnp.exp(cum[..., -1:] - cum)                # (.., j)
    if doc is not None:
        to_end = jnp.where(leaves_state, to_end, 0.0)
    left = _f32("zcgrjp,zcgjn->zcgrpn", xd * to_end[..., None], bc)
    through = jnp.exp(cum[..., -1])                      # a chunk's decay
    if doc is not None:
        through = jnp.where(passes_on, through, 0.0)

    def carry(state, xs):
        left_c, through_c = xs
        return state * through_c[..., None, None] + left_c, state

    _, entered = jax.lax.scan(
        carry, left[:, 0] * 0,                           # carries x's vma
        (jnp.moveaxis(left, 1, 0), jnp.moveaxis(through, 1, 0)))
    from_entered = jnp.exp(cum)
    if doc is not None:
        from_entered = jnp.where(reads_entered, from_entered, 0.0)
    y = y + _f32("zcgin,zcgrpn->zcgrip", cc, jnp.moveaxis(entered, 0, 1)) \
        * from_entered[..., None]
    return y.transpose(0, 1, 4, 2, 3, 5).reshape(bt, s + pad, h, p)[:, :s]


def _scan_views(xbc, heads, p, groups, chunk):
    """([x | B | C] three times, their lane blocks) as ``ops/ssd_scan``
    reads the convolution's one array: x's heads from lane 0, group g's B
    at block ``heads p / 128 + g`` and its C ``groups`` blocks on."""
    from ompi_tpu.ops import ssd_scan

    first = heads * p // ssd_scan.LANES
    return (xbc, xbc, xbc), dict(
        chunk=chunk, p=p, groups=groups, at=(0, first, first + groups))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kernel_scan(xbc, dt, a, skip, doc, chunk, p, groups):
    """The chunked scan and its skip term on the Pallas kernels
    (``ops/ssd_scan``): ``y + skip x`` (b, s, heads x p) of the
    convolution's [x | B | C] (b, s, heads x p + 2 groups x 128), read
    where it lies, ``dt`` (b, s, heads), ``a`` and ``skip`` (heads,) and
    ``doc`` (b, s) int32 or None.  The forward kernel also writes, for a
    backward pass, the state that entered each chunk; the backward kernel
    makes a chunk's other parts again from that.  Nothing a chunk is kept
    or recomputed by XLA, and no (b, s, heads, p) view of x or y exists."""
    from ompi_tpu.ops import ssd_scan

    views, how = _scan_views(xbc, dt.shape[2], p, groups, chunk)
    return ssd_scan.scan_forward(*views, dt, a, doc, skip, **how)


def _kernel_scan_fwd(xbc, dt, a, skip, doc, chunk, p, groups):
    from ompi_tpu.ops import ssd_scan

    views, how = _scan_views(xbc, dt.shape[2], p, groups, chunk)
    y, kept = ssd_scan.scan_forward(*views, dt, a, doc, skip, states=True,
                                    **how)
    return y, (xbc, dt, a, skip, doc, kept)


def _kernel_scan_bwd(chunk, p, groups, res, dy):
    from ompi_tpu.ops import ssd_scan

    xbc, dt, a, skip, doc, kept = res
    views, how = _scan_views(xbc, dt.shape[2], p, groups, chunk)
    *d_xbc, d_dt, d_a, d_skip = ssd_scan.scan_backward(
        *views, dt, a, doc, skip, kept, dy, **how)
    return jnp.concatenate(d_xbc, axis=-1), d_dt, d_a, d_skip, None


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def scan_on_kernels(interpret, chunk, p, n, heads, groups, s) -> tuple:
    """``(on_kernel, why)`` of the scan: on the Pallas kernels where
    Mosaic compiles (``interpret`` false: a TPU) and the shape has tiles
    (``ops/ssd_scan.refusal``); ``why`` names the clause that refused, ""
    where the kernels are taken."""
    if interpret:
        return False, INTERPRET
    from ompi_tpu.ops import ssd_scan

    why = ssd_scan.refusal(chunk, p, n, heads, groups, s)
    return not why, why


def causal_taps(xbc, w, bias, doc=None):
    """``silu`` of the causal depthwise convolution of ``xbc`` (b, s, ch)
    with taps ``w`` (taps, ch) and ``bias`` (ch,): ``bias + sum_k w_k
    xbc_(t - (taps - 1 - k))``, positions before the row's start read as
    zero; under ``doc`` (b, s) a tap counts iff the position it reads lies
    in position t's document, so no tap reads across a document's start
    (None: the lines without the argument)."""
    taps, s = w.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    if doc is None:
        return jax.nn.silu(bias + sum(
            padded[:, k:k + s] * w[k] for k in range(taps)))
    before = jnp.pad(doc, ((0, 0), (taps - 1, 0)), constant_values=-1)
    return jax.nn.silu(bias + sum(
        (padded[:, k:k + s] if k == taps - 1 else jnp.where(
            (before[:, k:k + s] == doc)[..., None], padded[:, k:k + s], 0.0))
        * w[k] for k in range(taps)))


def mamba_mixer(p, x, cfg, *, interpret: bool = True, at=None, doc=None,
                tp_axis=None):
    """Mamba-2's mixer (nemotron_h's ``M``, granitemoehybrid's ``mamba``),
    **without** the residual add, on the residual stream ``x`` (b, s, d)
    float32, for the ``n_mamba_heads_here`` heads and ``n_groups_here`` B/C
    groups held here: pre-norm; ``[z | xBC | dt] = u W_in`` (matmul inputs
    in ``compute_dtype``); ``xBC <- silu(causal depthwise convolution over
    conv_kernel positions, with bias)`` (``causal_taps``), split into x
    (heads x ``mamba_head_dim``), B and C (groups x ``ssm_state_size``);
    ``dt <- softplus(dt + dt_bias)``, ``a = -exp(A_log)``; the scan in
    chunks of ``chunk_size`` (``ssd_chunked``) plus ``D x``; ``rmsnorm
    over each group of (y * silu(z)) * gain``; ``y W_out``.  Everything
    between the two projections is float32.

    **The share.**  A chip holds whole B/C groups with their heads
    (nemotron_h: 16 heads, one group of eight), or a part of one group's
    heads with that group whole (granitemoehybrid's one group: B, C and
    their convolution are then computed alike by every holder of the
    group's heads, and the gated norm's statistic is the group's: the
    mean of squares is the sum over the held channels over the count of
    them, and where the caller names the mesh axis the group's holders lie
    along, ``tp_axis``, both sums cross it by ``psum``; with None, the one
    chip of a cell, the norm is over the held channels).

    **Documents.**  Under ``doc`` (b, s) int32, a packed row's documents,
    the convolution reads no tap across a document's start and the state
    that enters a document is zero.

    Returns (the sublayer's output, no statistics, what the scan read and
    made of the first held head, by token row: its step ``ssm_dt_seq``
    (T,), its ``ssm_x_seq`` (T, p) and its group's ``ssm_b_seq`` and
    ``ssm_c_seq`` (T, n) whole, because a position's state holds every
    earlier one, and the scan's ``ssm_y`` (T, p) before the skip term)."""
    b, s, d = x.shape
    nh, hd, n = cfg.n_mamba_heads_here, cfg.mamba_head_dim, cfg.ssm_state_size
    g, dt, eps = cfg.n_groups_here, cfg.compute_dtype, cfg.rms_norm_eps
    inner = nh * hd
    with jax.named_scope("otpu_ssm_proj"):
        u = rmsnorm_gain(x, p["norm"], eps)
        zxd = matmul(u.reshape(b * s, d), p["in_proj"], dt).reshape(b, s, -1)
        z, xbc, step = (zxd[..., :inner], zxd[..., inner:-nh], zxd[..., -nh:])
    with jax.named_scope("otpu_ssm_conv"):
        xbc = causal_taps(xbc, p["conv_w"], p["conv_b"], doc)
    with jax.named_scope("otpu_ssm_scan"):
        step = jax.nn.softplus(step + p["dt_bias"])
        a = -jnp.exp(p["A_log"])
        rows = lambda t: t.reshape((b * s,) + t.shape[2:])
        # what the first held head and its group read, as lane slices of
        # the convolution's array: no (b, s, heads, p) view for a probe
        seen = {"ssm_dt_seq": rows(step[:, :, 0]),
                "ssm_x_seq": rows(xbc[..., :hd]),
                "ssm_b_seq": rows(xbc[..., inner:inner + n]),
                "ssm_c_seq": rows(xbc[..., inner + g * n:inner + (g + 1) * n])}
        if scan_on_kernels(interpret, cfg.chunk_size, hd, n, nh, g, s)[0]:
            # the skip term inside the kernels; the first head's y less it
            # (to an ulp of ``D x``, a hundred-thousandth of a check's unit)
            y = _kernel_scan(xbc, step, a, p["D"], doc, cfg.chunk_size, hd, g)
            seen["ssm_y"] = rows(y[..., :hd] - p["D"][0] * xbc[..., :hd])
        else:
            xs = xbc[..., :inner].reshape(b, s, nh, hd)
            bs, cs = (xbc[..., inner + k * g * n:inner + (k + 1) * g * n]
                      .reshape(b, s, g, n) for k in (0, 1))
            y = ssd_chunked(xs, step, a, bs, cs, cfg.chunk_size, doc)
            seen["ssm_y"] = rows(y[:, :, 0])
            y = (y + p["D"][:, None] * xs).reshape(b, s, inner)
    with jax.named_scope("otpu_ssm_norm"):
        y = (y * jax.nn.silu(z)).reshape(b, s, g, inner // g)
        if tp_axis is None or nh * cfg.n_groups >= cfg.mamba_num_heads:
            mean_sq = jnp.mean(y * y, axis=-1, keepdims=True)
        else:       # a group's heads lie on several chips of ``tp_axis``
            mean_sq = jax.lax.psum(
                jnp.sum(y * y, axis=-1, keepdims=True), tp_axis) \
                / jax.lax.psum(inner // g, tp_axis)
        y = y * jax.lax.rsqrt(mean_sq + eps)
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


def _mixer_plan(cfg, b, s, interpret) -> dict:
    """What ``mamba_mixer`` holds: the scan's decision at the held heads
    and groups, a layer application one scan (SPC ``ssm_scan_built``,
    ``ssm_scan_kernel_built``); over a packed row's documents the
    convolution and the scan are each made under them (``doc_built``
    2)."""
    scan = scan_on_kernels(
        interpret, cfg.chunk_size, cfg.mamba_head_dim, cfg.ssm_state_size,
        cfg.n_mamba_heads_here, cfg.n_groups_here, s)
    return held({"ssm_scan_built": 1, "ssm_scan_kernel_built": int(scan[0]),
                 "doc_built": 2 * (cfg.eos_token_here >= 0)}, scan=scan)


def _mixer_dt_bias(key, shape, cfg):
    """The inverse softplus of a step drawn log-uniformly between
    ``time_step_min`` and ``time_step_max``, at least ``time_step_floor``
    (arXiv:2405.21060's code)."""
    step = jnp.maximum(cfg.time_step_floor, jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, np.log(cfg.time_step_min),
        np.log(cfg.time_step_max))))
    return step + jnp.log(-jnp.expm1(-step))


def _head_numbers(key, shape, cfg):
    """A granitemoehybrid head's ``A_log`` as its modelling code starts it:
    the logarithm of the head's number, counted from one, of the heads held
    here (this share's first), in every layer of a stacked leaf."""
    return jnp.broadcast_to(jnp.log(jnp.arange(
        1, shape[-1] + 1, dtype=jnp.float32)), shape)


#: nemotron_h's ``M``; ``D`` starts at one
MIXER = Sublayer(
    name="M", group="mamba", scope="otpu_mamba", run=mamba_mixer,
    shapes=_mixer_shapes,
    undecayed=("norm", "conv_b", "dt_bias", "A_log", "D", "gate_norm"),
    starts={"conv_w": uniform_taps, "conv_b": uniform_taps,
            "dt_bias": _mixer_dt_bias, "A_log": log_uniform_1_16},
    reports=lambda cfg: {"ssm_dt_seq": 0, "ssm_x_seq": 1, "ssm_b_seq": 1,
                         "ssm_c_seq": 1, "ssm_y": 1}, plan=_mixer_plan)
#: granitemoehybrid's ``mamba``: the same mixer under its ``layer_types``
#: name, before a feed-forward; ``A_log`` starts at the heads' numbers
TYPED_MIXER = dataclasses.replace(
    MIXER, name="mamba", starts={**MIXER.starts, "A_log": _head_numbers})
