"""lfm2's gated short convolution (LFM2-8B-A1B's ``conv`` layers), with
its entry in ``parallel/model.py``'s table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.parallel.layers import matmul, rmsnorm_gain
from ompi_tpu.parallel.sublayer import Sublayer, uniform_taps

#: the leading channels of a short convolution whose gate path a step
#: reports (``short_conv``): one tile's lanes of the hidden width
CONV_SAMPLE = 128


def short_conv(p, x, cfg, *, interpret: bool = True, at=None):
    """lfm2's gated short convolution (LFM2-8B-A1B's ``conv`` operator),
    **without** the residual add, on the residual stream ``x`` (b, s, d)
    float32: pre-norm; ``[B | C | u] = n W_in`` (d, 3 d; matmul inputs
    in ``compute_dtype``); ``z_t = sum_j w_j (B * u)_{t - (taps - 1) +
    j}``, a causal depthwise convolution of ``conv_kernel`` taps a
    channel (``conv_w`` (taps, d), the last tap on the position itself)
    with zeros before the sequence's start, no bias and no activation;
    ``(C * z) W_out``.  The two gates and the taps, everything between
    the two projections, are float32.  The sequence is never reset
    inside a packed row.  Returns (the sublayer's output, no statistics,
    of the first ``CONV_SAMPLE`` channels by token row what the gate path read,
    ``conv_bcu_seq`` (T, B | C | u) whole, because a position's result
    holds the ``taps - 1`` before it, and made, ``conv_y`` (T, .): C *
    z)."""
    b, s, d = x.shape
    dt, taps = cfg.compute_dtype, p["conv_w"].shape[0]
    with jax.named_scope("otpu_conv_proj"):
        n = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps)
        bcu = matmul(n.reshape(b * s, d), p["in_proj"], dt).reshape(
            b, s, 3, d)
    with jax.named_scope("otpu_conv_gate"):
        gated = jnp.pad(bcu[:, :, 0] * bcu[:, :, 2],
                        ((0, 0), (taps - 1, 0), (0, 0)))
        y = bcu[:, :, 1] * sum(gated[:, k:k + s] * p["conv_w"][k]
                               for k in range(taps))
        c = min(CONV_SAMPLE, d)
        seen = {"conv_bcu_seq": bcu[..., :c].reshape(b * s, 3 * c),
                "conv_y": y[..., :c].reshape(b * s, c)}
    with jax.named_scope("otpu_conv_proj"):
        return matmul(y.reshape(b * s, d), p["out_proj"], dt
                      ).reshape(b, s, d), {}, seen


def _conv_shapes(cfg) -> dict:
    """The operator norm's gain, ``in_proj`` (d, B | C | u), the taps
    (kernel, d), ``out_proj``."""
    d = cfg.hidden_size
    return {"ln1": (d,), "in_proj": (d, 3 * d),
            "conv_w": (cfg.conv_kernel, d), "out_proj": (d, d)}


#: lfm2_moe's ``conv`` (``conv_kernel``: the file's ``conv_L_cache``)
CONV = Sublayer(
    name="conv", group="conv", scope="otpu_conv", run=short_conv,
    shapes=_conv_shapes, undecayed=("ln1",), starts={"conv_w": uniform_taps},
    reports=lambda cfg: {"conv_bcu_seq": 1, "conv_y": 1})
