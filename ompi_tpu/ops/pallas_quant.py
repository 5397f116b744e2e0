"""Pallas block-quantization kernels — the device half of coll/quant.

The EQuARX-style codec (PAPERS.md, arxiv 2506.17615) at kernel
granularity: one *block* is one 128-lane row of the flattened operand,
and each block carries an f32 scale ``max(|x|)/127`` next to its int8
payload.  Three entry points, shape-polymorphic like
``ops/pallas_reduce.py``:

``encode_int8(x)``
    Flatten + pad ``x`` to ``(rows, 128)`` lanes and quantize through a
    tiled VMEM kernel: per-row absmax → scale, round-half-even to int8.
    Returns ``(q (rows,128) int8, scales (rows,1) f32)``.

``dequant_accumulate(q, s)``
    The dequant-accumulate reduction: ``sum_i q[i] * s[i]`` over a
    ``(k, rows, 128)`` stack of quantized contributions in ONE VMEM
    pass — the post-allgather fold of the block-quantized allreduce,
    fused so no dequantized intermediate ever lands in HBM (the
    ``reduce_stack`` shape pointed at quantized operands).

``decode_int8(q, s)``
    Elementwise ``q * s`` back to f32 (the allgather decode).

Mosaic tiling discipline (pallas_guide.md): int8 blocks keep the
(32, 128) minimum tile; per-row scales are produced LANE-PADDED to
``(rows, 128)`` inside the kernel (a trailing dim of 1 is not a legal
Mosaic tile) and sliced to ``(rows, 1)`` at the XLA level, so only 4
bytes per BLOCK — not per element — ride any gather.  Off-TPU the
kernels run in interpreter mode so the CPU test mesh exercises the
same code path, and ``interpret`` is an explicit static jit key so the
AOT gate can force real Mosaic lowering (the ``combine2`` contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ompi_tpu.base.jaxenv import pallas_interpret

LANES = 128          # one codec block = one lane row
ROW_TILE = 256       # 256x128 f32 tile = 128 KiB per operand in VMEM


def _pad_rows(flat, rows_mult: int):
    """Flatten → (rows, LANES) padded so rows % rows_mult == 0."""
    n = flat.size
    rows = max(1, -(-n // LANES))
    rows = -(-rows // rows_mult) * rows_mult
    pad = rows * LANES - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANES), rows


def _enc_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:]
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)      # (tile, 1)
    inv = jnp.where(amax > 0, 127.0 / amax, jnp.zeros_like(amax))
    # round-half-even (jnp.round == np.rint): DETERMINISTIC, so every
    # rank/process encodes identical bytes — the cross-process
    # determinism the host codec tests pin (stochastic rounding would
    # trade that away for unbiasedness)
    q_ref[:] = jnp.round(x * inv).astype(jnp.int8)
    # scale lane-padded to the full row (trailing dim 1 is not a legal
    # Mosaic tile); the XLA caller slices [:, :1]
    s_ref[:] = jnp.broadcast_to(amax * (1.0 / 127.0), x.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def encode_int8(x, *, interpret=None):
    """Block-quantize ``x`` → ``(q (rows,128) int8, s (rows,1) f32)``.

    ``interpret`` is a static jit-cache-key ingredient (see
    ``pallas_reduce.combine2``): None resolves from the backend at
    trace time; an explicit value (the AOT Mosaic gate passes False)
    always wins."""
    flat = x.reshape(-1).astype(jnp.float32)
    x2, rows = _pad_rows(flat, ROW_TILE)
    grid = (rows // ROW_TILE,)
    spec = pl.BlockSpec((ROW_TILE, LANES), lambda i: (i, 0))
    q, s = pl.pallas_call(
        _enc_kernel,
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32)),
        grid=grid, in_specs=[spec], out_specs=(spec, spec),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(x2)
    return q, s[:, :1]


def _deq_acc_kernel(k, q_ref, s_ref, o_ref):
    acc = q_ref[0].astype(jnp.float32) * s_ref[0]
    for i in range(1, k):   # k is static — unrolled VPU chain
        acc = acc + q_ref[i].astype(jnp.float32) * s_ref[i]
    o_ref[:] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_accumulate(q, s, *, interpret=None):
    """``sum_i q[i] * s[i]`` over a (k, rows, 128) quantized stack in
    one streaming VMEM pass; ``s`` is (k, rows, 1) per-block scales
    (broadcast to lane width at the XLA level so the kernel's tiles
    stay legal)."""
    k, rows = q.shape[0], q.shape[1]
    if k == 1:
        return decode_int8(q[0], s[0], interpret=interpret)
    sb = jnp.broadcast_to(s, (k, rows, LANES))
    # row tile sized so k int8 + k f32 operand tiles + out fit VMEM
    tile = max(8, min(ROW_TILE, 4096 // k * 8))
    pad = (-rows) % tile
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
        sb = jnp.pad(sb, ((0, 0), (0, pad), (0, 0)))
    rows_p = rows + pad
    out = pl.pallas_call(
        functools.partial(_deq_acc_kernel, k),
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.float32),
        grid=(rows_p // tile,),
        in_specs=[pl.BlockSpec((k, tile, LANES), lambda i: (0, i, 0)),
                  pl.BlockSpec((k, tile, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((tile, LANES), lambda i: (i, 0)),
        interpret=pallas_interpret() if interpret is None else interpret,
    )(q, sb)
    return out[:rows]


def _dec_kernel(q_ref, s_ref, o_ref):
    o_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_int8(q, s, *, interpret=None):
    """Elementwise dequant of one (rows, 128) quantized block array
    (``s`` is (rows, 1)); leading axes fold into rows first."""
    lead = q.shape[:-2]
    rows = 1
    for d in q.shape[:-1]:
        rows *= d
    q2 = q.reshape(rows, LANES)
    s2 = jnp.broadcast_to(s, q.shape[:-1] + (LANES,)).reshape(rows, LANES)
    tile = ROW_TILE
    pad = (-rows) % tile
    if pad:
        q2 = jnp.pad(q2, ((0, pad), (0, 0)))
        s2 = jnp.pad(s2, ((0, pad), (0, 0)))
    rows_p = rows + pad
    spec = pl.BlockSpec((tile, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _dec_kernel,
        out_shape=jax.ShapeDtypeStruct((rows_p, LANES), jnp.float32),
        grid=(rows_p // tile,), in_specs=[spec, spec], out_specs=spec,
        interpret=pallas_interpret() if interpret is None else interpret,
    )(q2, s2)
    return out[:rows].reshape(lead + (q.shape[-2], LANES))
