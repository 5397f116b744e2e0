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

``combine2`` flattens and pads its operands to (rows, 128) lanes, and so
does ``reduce_stack`` for a stack of rank 3 or more (a gathered
``(n, 1, S)`` stack reshapes to ``(n, rows, 128)`` for free).  A 2-D
``(k, N)`` stack of a 32-bit dtype with N a multiple of 128 is blocked as
it stands, ``(k, C)`` in and ``(1, C)`` out: on the chip such an array is
tiled ``T(4,128)``, and a reshape to ``(k, rows, 128)`` in front of the
kernel is a copy of the whole stack (63% of the call, PERF.md PR 28).
The grid walks the tiles so arbitrarily large buffers stream through
VMEM.  Off-TPU the kernels run in interpreter mode so the same code path
is exercised by the CPU test mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ompi_tpu.base.jaxenv import pallas_interpret

LANES = 128
ROW_TILE = 512  # 512x128 f32 tile = 256 KiB per operand in VMEM
# 2-D stacks: a (k, C) block holds this many elements once k is padded to
# its sublane tile (4 MiB of 32-bit; with the (1, C) output, both
# double-buffered, 10 MiB of a v5e's 16 MiB of scoped VMEM at k = 4).
# Swept on the chip at k = 4, 4 x 64-256 MiB (PR 28): C 16Ki 582 GB/s,
# 64Ki 682, 256Ki 704; XLA's own reduce 660.
ROWS_BLOCK_ELEMS = 1 << 20

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


def _stack_rows_kernel(fold, k, x_ref, o_ref):
    acc = x_ref[0:1, :]
    for i in range(1, k):  # one row of the (k, C) block at a time
        acc = fold(acc, x_ref[i:i + 1, :])
    o_ref[:] = acc


def _reduce_rows(fold, x, interpret):
    """``reduce_stack`` of a 2-D ``(k, N)`` stack, N a multiple of LANES:
    no reshape and no pad in front of the kernel, a bitcast behind it.
    The last block may be partial: the fold is elementwise, so what its
    padding holds never reaches the clipped output."""
    k, n = x.shape
    k_tiled = 4 if k <= 4 else -(-k // 8) * 8  # rows the block takes in VMEM
    c = min(n, max(LANES, ROWS_BLOCK_ELEMS // k_tiled // LANES * LANES))
    out = pl.pallas_call(
        functools.partial(_stack_rows_kernel, fold, k),
        out_shape=jax.ShapeDtypeStruct((1, n), x.dtype),
        grid=(pl.cdiv(n, c),),
        in_specs=[pl.BlockSpec((k, c), lambda j: (0, j))],
        out_specs=pl.BlockSpec((1, c), lambda j: (0, j)),
        name="otpu_reduce_stack_rows",
        interpret=pallas_interpret() if interpret is None else interpret,
    )(x)
    return out.reshape(n)


@functools.partial(jax.jit, static_argnames=("op_name", "interpret"))
def reduce_stack(op_name: str, x, *, interpret=None):
    """Reduce ``x[k, ...]`` along axis 0 in one streaming VMEM pass.

    ``interpret`` is a static jit-cache-key ingredient (see combine2)."""
    fold = _FOLDS[op_name]
    k = x.shape[0]
    if k == 1:
        return x[0]
    if x.ndim == 2 and x.dtype.itemsize == 4 and x.shape[1] % LANES == 0:
        return _reduce_rows(fold, x, interpret)
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
