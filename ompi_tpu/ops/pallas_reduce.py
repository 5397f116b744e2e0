"""Pallas VPU reduction kernels — the op/avx analog on TPU.

Two entry points, both shape-polymorphic over arbitrary operand shapes:

``combine2(op_name, a, b)``
    Elementwise ``a (op) b`` through a tiled VMEM kernel — the two-operand
    reduction primitive every MPI_Reduce-family algorithm folds with
    (reference kernel table ``ompi/mca/op/avx/op_avx_functions.c``).

``reduce_stack(op_name, x)``
    Reduce a ``(k, ...)`` stack along axis 0 in ONE pass through VMEM.
    This is the fused form of the k-1 chained folds the coll algorithm
    library performs after an allgather (Rabenseifner post-reduce, tree
    reduce leaves) — a bandwidth win over materialising each intermediate
    in HBM.

Operands are flattened and padded to (rows, 128) lanes; the grid walks
row-tiles so arbitrarily large buffers stream through VMEM.  Off-TPU the
kernels run in interpreter mode so the same code path is exercised by the
CPU test mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ompi_tpu.base.jaxenv import pallas_interpret

LANES = 128
ROW_TILE = 512  # 512x128 f32 tile = 256 KiB per operand in VMEM

_FOLDS = {
    "SUM": lambda a, b: a + b,
    "PROD": lambda a, b: a * b,
    "MAX": jnp.maximum,
    "MIN": jnp.minimum,
    "BAND": lambda a, b: a & b,
    "BOR": lambda a, b: a | b,
    "BXOR": lambda a, b: a ^ b,
    "LAND": lambda a, b: ((a != 0) & (b != 0)).astype(a.dtype),
    "LOR": lambda a, b: ((a != 0) | (b != 0)).astype(a.dtype),
    "LXOR": lambda a, b: ((a != 0) ^ (b != 0)).astype(a.dtype),
}
_BITWISE = ("BAND", "BOR", "BXOR")


def supported_ops() -> tuple:
    return tuple(_FOLDS)


def _supported_dtype(op_name: str, dtype) -> bool:
    if op_name in _BITWISE:
        return jnp.issubdtype(dtype, jnp.integer) or dtype == jnp.bool_
    return jnp.issubdtype(dtype, jnp.floating) or \
        jnp.issubdtype(dtype, jnp.integer)


def _pad_rows(flat, rows_mult: int):
    """Flatten → (rows, LANES) padded so rows % rows_mult == 0."""
    n = flat.size
    rows = max(1, -(-n // LANES))
    rows = -(-rows // rows_mult) * rows_mult
    pad = rows * LANES - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANES), rows


def _combine_kernel(fold, a_ref, b_ref, o_ref):
    o_ref[:] = fold(a_ref[:], b_ref[:])


@functools.partial(jax.jit, static_argnums=0,
                   static_argnames=("interpret",))
def combine2(op_name: str, a, b, *, interpret=None):
    """Elementwise ``a (op) b`` on the VPU; shape/dtype of ``a``.

    ``interpret`` is a static jit-cache-key ingredient: None resolves
    from the backend at trace time; an explicit value (the AOT Mosaic
    gate passes False) always wins and can never be served a cached
    interpreter trace."""
    fold = _FOLDS[op_name]
    a2, rows = _pad_rows(a.ravel(), ROW_TILE)
    b2, _ = _pad_rows(b.ravel(), ROW_TILE)
    grid = (rows // ROW_TILE,)
    spec = pl.BlockSpec((ROW_TILE, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_combine_kernel, fold),
        out_shape=jax.ShapeDtypeStruct(a2.shape, a2.dtype),
        grid=grid, in_specs=[spec, spec], out_specs=spec,
        name="otpu_combine2",
        interpret=pallas_interpret() if interpret is None else interpret,
    )(a2, b2)
    return out.ravel()[: a.size].reshape(a.shape)


def _stack_kernel(fold, k, x_ref, o_ref):
    acc = x_ref[0]
    for i in range(1, k):  # k is static — unrolled VPU chain, one VMEM pass
        acc = fold(acc, x_ref[i])
    o_ref[:] = acc


@functools.partial(jax.jit, static_argnames=("op_name", "interpret"))
def reduce_stack(op_name: str, x, *, interpret=None):
    """Reduce ``x[k, ...]`` along axis 0 in one streaming VMEM pass.

    ``interpret`` is a static jit-cache-key ingredient (see combine2)."""
    fold = _FOLDS[op_name]
    k = x.shape[0]
    if k == 1:
        return x[0]
    # row tile sized so k operand tiles + out fit VMEM comfortably
    tile = max(8, min(ROW_TILE, 4096 // k * 8))
    per = x[0].size
    rows_k = max(1, -(-per // LANES))
    rows_k = -(-rows_k // tile) * tile
    pad = rows_k * LANES - per
    xp = jnp.pad(x.reshape(k, per), ((0, 0), (0, pad)))
    xp = xp.reshape(k, rows_k, LANES)
    out = pl.pallas_call(
        functools.partial(_stack_kernel, fold, k),
        out_shape=jax.ShapeDtypeStruct((rows_k, LANES), x.dtype),
        grid=(rows_k // tile,),
        in_specs=[pl.BlockSpec((k, tile, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((tile, LANES), lambda i: (i, 0)),
        name="otpu_reduce_stack",
        interpret=pallas_interpret() if interpret is None else interpret,
    )(xp)
    return out.ravel()[:per].reshape(x.shape[1:])


def device_fold(op_name: str, dtype):
    """Return a two-operand fold callable for (op, dtype), or None.

    The op framework's component query hook: None means "this kernel set
    does not cover the type", and selection falls through to the next
    component (plain-XLA jnp fold), mirroring the reference's per-type
    function tables (``op_avx_functions.c`` dispatch by flags+type).
    """
    if op_name not in _FOLDS or not _supported_dtype(op_name, dtype):
        return None
    return functools.partial(combine2, op_name)
