"""Fused compute+communicate kernels — the collective matmul.

THE reason the explicit-schedule transport exists (SURVEY §2.6; module
docstring of :mod:`ompi_tpu.ops.pallas_collectives`): XLA schedules a
matmul THEN an all-reduce; an explicit kernel interleaves them so the
ICI is busy while the MXU computes.  The classic case is the
contraction-sharded ("tensor-parallel k-split") matmul

    C = Σ_i  A_i @ B_i        A_i: (M, K/n),  B_i: (K/n, N)

whose partial products ring-reduce across the mesh.  The fused schedule
computes the row-block of the partial product **just in time**, one ring
step before it is needed, so each step's remote DMA flies while the MXU
computes the next block:

  step k: start DMA of the running partial for block (my-k) rightward
          compute local partial P[my-1-k]      <- overlaps the DMA
          wait DMA; fold P[my-1-k] + incoming into the running partial

After n-1 such steps block (my+1) is fully reduced; a plain all-gather
ring replicates C.  Ring schedule = ``coll_base_allreduce.c:341``; the
overlap is the TPU-first "async collective matmul" the compiler cannot
always produce on its own.

Interpreter-mode runs (tests, virtual meshes) execute the same schedule
serially; on hardware the DMA/compute overlap is real.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from ompi_tpu.base.jaxenv import pallas_interpret
from ompi_tpu.ops.pallas_collectives import _ag_phase, _mods, _ring_kernels


def _prep_operands(a, b, mesh, axis):
    """Shared wrapper preamble: validate the contraction, promote mixed
    dtypes OUTSIDE the kernel (mismatched refs vs VMEM scratch fail
    tracing), and extract the static shapes.  Returns
    (a, b, n, m, k_loc, n_out, dtype)."""
    n = mesh.shape[axis]
    m, k_loc = int(a.shape[1]), int(a.shape[2])
    n_out = int(b.shape[2])
    if int(b.shape[1]) != k_loc:
        raise ValueError(
            f"contraction mismatch: a has K/n={k_loc}, b has "
            f"{int(b.shape[1])}")
    dtype = np.result_type(a.dtype, b.dtype)
    if a.dtype != dtype or b.dtype != dtype:
        a = a.astype(dtype)
        b = b.astype(dtype)
    return a, b, n, m, k_loc, n_out, dtype


@functools.lru_cache(maxsize=64)
def _build_fused_matmul(n: int, axis: str, m_blk: int, k_loc: int,
                        n_out: int, dtype_str: str, interpret: bool,
                        align: int, with_ag: bool, cid: int):
    """ONE fused matmul+ring builder for both output layouts.

    ``align=0, with_ag=True``: the all-reduce form — after the fused
    reduce-scatter, block (my+1) is complete and an all-gather ring
    replicates the full product (out: (n, m_blk, n_out)).
    ``align=-1, with_ag=False``: the owner-aligned reduce-scatter form —
    block ``my`` completes locally and IS the output (out: (m_blk,
    n_out)), the Megatron-style row-parallel GEMM.  Same VMEM staging,
    just-in-time block compute, and DMA/semaphore discipline either way
    (a fix to one schedule is a fix to both).
    """
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)

    def kernel(a_ref, b_ref, out_ref, a_vmem, b_vmem, acc_ref, recv_ref,
               local_sem, send_sem, rs_sems, *maybe_ag_sems):
        my = lax.axis_index(axis)
        right = lax.rem(my + 1, n)
        barrier(right, lax.rem(my - 1 + n, n))
        # operands land in VMEM first: compute dereferences need VMEM
        # residency on hardware (ANY-space inputs may live in HBM)
        ca = pltpu.make_async_copy(a_ref, a_vmem, local_sem)
        ca.start()
        ca.wait()
        cb = pltpu.make_async_copy(b_ref, b_vmem, local_sem)
        cb.start()
        cb.wait()

        def partial(b):
            """Local partial product for row-block b (MXU work)."""
            rows = a_vmem[pl.ds(b * m_blk, m_blk), :]
            return jnp.dot(rows, b_vmem[...],
                           preferred_element_type=jnp.float32
                           ).astype(acc_ref.dtype)

        # the block sent at step 0 is needed first
        first = lax.rem(my + align + n, n)
        acc_ref[pl.ds(first, 1)] = partial(first)[None]

        def rs_step(k, carry):
            send_idx = lax.rem(my + align - k + 2 * n, n)
            recv_idx = lax.rem(my + align - 1 - k + 2 * n, n)
            rdma = pltpu.make_async_remote_copy(
                src_ref=acc_ref.at[send_idx], dst_ref=recv_ref.at[k],
                send_sem=send_sem, recv_sem=rs_sems.at[k],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            # the overlap: THIS matmul runs while the DMA is in flight
            mine = partial(recv_idx)
            rdma.wait()
            acc_ref[pl.ds(recv_idx, 1)] = \
                mine[None] + recv_ref[pl.ds(k, 1)]
            return carry

        lax.fori_loop(0, n - 1, rs_step, 0)
        done = lax.rem(my + align + 1 + n, n)
        if with_ag:
            cp = pltpu.make_async_copy(acc_ref.at[done],
                                       out_ref.at[done], local_sem)
            cp.start()
            cp.wait()
            _ag_phase(lax, pl, pltpu, n=n, my=my, right=right,
                      out_ref=out_ref, send_sem=send_sem,
                      ag_sems=maybe_ag_sems[0])
        else:
            cp = pltpu.make_async_copy(acc_ref.at[done], out_ref,
                                       local_sem)
            cp.start()
            cp.wait()

    out_shape = (n, m_blk, n_out) if with_ag else (m_blk, n_out)
    scratch = [pltpu.VMEM((n * m_blk, k_loc), jnp.dtype(dtype_str)),
               pltpu.VMEM((k_loc, n_out), jnp.dtype(dtype_str)),
               pltpu.VMEM((n, m_blk, n_out), jnp.dtype(dtype_str)),
               pltpu.VMEM((n - 1, m_blk, n_out), jnp.dtype(dtype_str)),
               pltpu.SemaphoreType.DMA(()),
               pltpu.SemaphoreType.DMA(()),
               pltpu.SemaphoreType.DMA((n - 1,))]
    if with_ag:
        scratch.append(pltpu.SemaphoreType.DMA((n - 1,)))

    def call(a, b):   # a: (n*m_blk, k_loc), b: (k_loc, n_out)
        kw = {}
        cp = cparams(cid)
        if cp is not None:
            kw["compiler_params"] = cp
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(out_shape, dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=scratch,
            interpret=interpret,
            **kw,
        )(a, b)

    return call


@functools.lru_cache(maxsize=256)
def _jit_matmul_reduce_scatter(mesh, axis: str, m: int, k_loc: int,
                               n_out: int, dtype_str: str,
                               interpret: bool):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    m_blk = -(-m // n)
    m_pad = m_blk * n
    inner = _build_fused_matmul(n, axis, m_blk, k_loc, n_out,
                                dtype_str, interpret, align=-1,
                                with_ag=False, cid=11)

    # a: (1, m, k_loc), b: (1, k_loc, n_out)
    def otpu_pallas_matmul_reduce_scatter(a, b):
        a2 = a[0]
        if m_pad != m:
            a2 = jnp.pad(a2, ((0, m_pad - m), (0, 0)))
        return inner(a2, b[0])[None]     # (1, m_blk, n_out)

    return jax.jit(shard_map(otpu_pallas_matmul_reduce_scatter, mesh=mesh,
                             in_specs=(P(axis), P(axis)),
                             out_specs=P(axis), check_vma=False))


def matmul_reduce_scatter(a, b, mesh, axis: str,
                          interpret: Optional[bool] = None):
    """Row-parallel fused GEMM: device i returns row-block i of
    Σ_j A_j @ B_j (global shape (n, M/n-padded, N) sharded on the mesh
    axis) — the reduce-scatter half of :func:`matmul_allreduce`, the
    Megatron-style TP output projection.  M is padded to a multiple of
    n; callers slice the tail block if M % n != 0."""
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    a, b, n, m, k_loc, n_out, dtype = _prep_operands(a, b, mesh, axis)
    if n == 1:
        return (a[0] @ b[0])[None]
    return _jit_matmul_reduce_scatter(mesh, axis, m, k_loc, n_out,
                                      str(dtype), interpret)(a, b)


@functools.lru_cache(maxsize=256)
def _jit_matmul_allreduce(mesh, axis: str, m: int, k_loc: int,
                          n_out: int, dtype_str: str, interpret: bool):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    m_blk = -(-m // n)
    m_pad = m_blk * n
    inner = _build_fused_matmul(n, axis, m_blk, k_loc, n_out,
                                dtype_str, interpret, align=0,
                                with_ag=True, cid=10)

    # a: (1, m, k_loc), b: (1, k_loc, n_out)
    def otpu_pallas_matmul_allreduce(a, b):
        a2 = a[0]
        if m_pad != m:
            a2 = jnp.pad(a2, ((0, m_pad - m), (0, 0)))
        out = inner(a2, b[0])            # (n, m_blk, n_out)
        return out.reshape(m_pad, n_out)[:m]

    return jax.jit(shard_map(otpu_pallas_matmul_allreduce, mesh=mesh,
                             in_specs=(P(axis), P(axis)),
                             out_specs=P(), check_vma=False))


def matmul_allreduce(a, b, mesh, axis: str,
                     interpret: Optional[bool] = None):
    """Contraction-sharded matmul with fused ring reduction.

    ``a``: (n, M, K/n) — per-device A shards on the leading mesh axis;
    ``b``: (n, K/n, N) — matching contraction shards.  Returns the
    replicated (M, N) product Σ_i A_i @ B_i, computed by the fused
    just-in-time-block ring (compute overlaps each step's DMA).
    """
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    a, b, n, m, k_loc, n_out, dtype = _prep_operands(a, b, mesh, axis)
    if n == 1:
        return a[0] @ b[0]
    return _jit_matmul_allreduce(mesh, axis, m, k_loc, n_out,
                                 str(dtype), interpret)(a, b)
