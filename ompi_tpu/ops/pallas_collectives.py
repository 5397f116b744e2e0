"""Pallas remote-DMA ring collectives — the explicit ICI transport path.

The reference's lowest layer is an explicit transport with RDMA verbs
(``/root/reference/opal/mca/btl/btl.h:949`` put / ``:987`` get); its
collectives are schedules of those verbs over a topology.  coll/xla rides
XLA's compiler-scheduled collectives instead — this module is the
explicit-schedule twin: ring algorithms written directly against the ICI
with ``pltpu.make_async_remote_copy`` (one-sided remote DMA + send/recv
semaphore discipline), the TPU-native form of the reference's
``btl_put``-based ring (``coll_base_allreduce.c:341``).

Why have both: XLA's collectives are near-optimal for the standard cases,
but an explicit schedule composes with compute inside ONE kernel (overlap
of reduce + forward per ring step, custom quantized wire formats, PP
activation handoff fused into the stage loop) — the knob the reference
keeps by owning its transport.  SURVEY.md §2.6 maps this slot to "Pallas
remote DMA".

All kernels are SPMD under ``shard_map`` over a 1-D mesh axis; payloads
are split into per-device ring blocks outside the kernel.  They run in
interpreter mode on a virtual CPU mesh (tests) and compile for real
multi-chip ICI unchanged.

Two accumulator regimes (round 4):

* **fused** — the whole (n, blk) accumulator lives in VMEM; lowest
  latency, bounded by VMEM size (the component's ``vmem_max_bytes``).
* **segmented** — the accumulator and receive buffers are HBM-resident
  and only a bounded double-buffered window (2 × ``seg`` elements)
  streams through VMEM for the reduction, so payload size is bounded by
  HBM, not VMEM — the explicit-DMA twin of the reference's *segmented*
  ring (``coll_base_allreduce.c:618`` ring_segmented) whose entire point
  is pipelining large payloads through bounded buffers.

The **bidirectional** ring variant splits the payload in half and runs
mirrored clockwise/counter-clockwise schedules concurrently — ICI links
are duplex, so both directions carry traffic every step and the bisection
time halves (the reference gets the same effect from its two-proc-group
rdb/segmented hybrids; here it is one kernel).

**Torus schedules** (``all_reduce_torus``) ride sub-rings of a
linearized (n0, n1) mesh — reduce-scatter along one torus dimension,
all-reduce along the other on 1/n0-sized blocks, all-gather back — so
every link of BOTH dimensions carries traffic and per-phase step count
follows the axis lengths, not their product (coll/han's hierarchical
composition, expressed as explicit DMA).  The **explicit all-to-all**
(pairwise exchange over direct per-peer DMAs, ``coll_base_alltoall.c``)
is the SP/MoE dispatch primitive.

Reduction is parameterized (sum/max/min/prod) — one op argument, the
same way ``ompi_op``'s function table parameterizes the reference's ring
(``coll_base_allreduce.c:341`` takes any ``ompi_op_t``).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from ompi_tpu.base.jaxenv import pallas_interpret


# ``interpret=None`` on every public wrapper below resolves from the
# devices the call is built for (``mesh``), never a fixed default: a
# caller on a TPU that omits the flag must not run the interpreter.


def _op_fn(jnp, op: str):
    """Elementwise fold for a ring-kernel reduction op name."""
    try:
        return {
            "sum": lambda a, b: a + b,
            "max": jnp.maximum,
            "min": jnp.minimum,
            "prod": lambda a, b: a * b,
        }[op]
    except KeyError:
        raise ValueError(
            f"unsupported ring reduction {op!r}: one of sum/max/min/prod")


def _mods():
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return jax, jnp, lax, pl, pltpu


def _ring_kernels(n: int, axis: str, interpret: bool):
    """Build the kernel-constructor namespace once per (n, axis, mode)."""
    jax, jnp, lax, pl, pltpu = _mods()

    def compiler_params(collective_id: int):
        # distinct collective_id per kernel family: concurrent pallas
        # collectives must not share barrier/semaphore identity on real
        # hardware (Mosaic matches collective instances by this id)
        if interpret:
            return None
        return pltpu.CompilerParams(has_side_effects=True,
                                    collective_id=collective_id)

    def barrier(*peers):
        """Kernel-entry barrier with every DMA peer: signal each peer's
        barrier semaphore (allocated per collective_id), wait until all
        of them have signalled ours.  On hardware no remote DMA may
        depart before the receiver's kernel is live — its recv
        semaphores and scratch only exist then (Mosaic refuses a
        collective_id kernel without this).  The interpreter emulates
        remote copies as per-op rendezvous, so it needs no barrier and
        does not model one."""
        if interpret:
            return
        bsem = pltpu.get_barrier_semaphore()
        for p in peers:
            pltpu.semaphore_signal(
                bsem, 1, device_id=p,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(bsem, len(peers))

    return jax, jnp, lax, pl, pltpu, compiler_params, barrier


def _ring_fn(lax, axis: str, sub):
    """(ring position, position->logical-device-id map) for this device.

    ``sub=None``: the ring IS the whole 1-D mesh (identity map).
    ``sub=(n0, n1, j)``: the mesh linearizes a (n0, n1) torus row-major
    and the ring rides axis j — position p maps to device p*n1+i1
    (column ring pinned at my i1) or i0*n1+p (row ring pinned at my
    i0).  Index arithmetic on scalar LOGICAL ids keeps every kernel
    interpreter-runnable (the Pallas interpreter has no multi-axis DMA
    mesh support) and lowers identically on hardware, where ICI routes
    non-neighbor ids."""
    my = lax.axis_index(axis)
    if sub is None:
        return my, (lambda p: p)
    n0, n1, j = sub
    i0 = my // n1
    i1 = lax.rem(my, n1)
    if j == 0:
        return i0, (lambda p: p * n1 + i1)
    return i1, (lambda p: i0 * n1 + p)


@functools.lru_cache(maxsize=64)
def _build_right_permute(n: int, axis: str, shape, dtype_str: str,
                         interpret: bool):
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)

    def kernel(x_ref, out_ref, send_sem, recv_sem):
        my = lax.axis_index(axis)
        right = lax.rem(my + 1, n)
        barrier(right, lax.rem(my - 1 + n, n))
        rdma = pltpu.make_async_remote_copy(
            src_ref=x_ref, dst_ref=out_ref,
            send_sem=send_sem, recv_sem=recv_sem,
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma.wait()

    def call(x):
        kw = {}
        cp = cparams(1)
        if cp is not None:
            kw["compiler_params"] = cp
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(shape, dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())],
            interpret=interpret,
            **kw,
        )(x)

    return call


@functools.lru_cache(maxsize=64)
def _build_all_gather(n: int, axis: str, blk_shape, dtype_str: str,
                      interpret: bool, sub=None, cid: int = 2):
    """Ring all-gather: n-1 steps, each forwarding the freshest block to
    the right neighbor (``jax docs distributed`` canonical schedule; the
    reference's ``coll_base_allgather.c`` ring)."""
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)

    def kernel(x_ref, out_ref, local_sem, send_sem, recv_sems):
        my, dev = _ring_fn(lax, axis, sub)
        right = dev(lax.rem(my + 1, n))
        barrier(right, dev(lax.rem(my - 1 + n, n)))
        cp = pltpu.make_async_copy(x_ref, out_ref.at[my], local_sem)
        cp.start()
        cp.wait()

        def step(k, carry):
            slot = lax.rem(my - k + n, n)
            rdma = pltpu.make_async_remote_copy(
                src_ref=out_ref.at[slot], dst_ref=out_ref.at[slot],
                send_sem=send_sem, recv_sem=recv_sems.at[k],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            rdma.wait()   # send done + block (my-k-1) landed from the left
            return carry

        lax.fori_loop(0, n - 1, step, 0)

    def call(x):
        kw = {}
        cp = cparams(cid)
        if cp is not None:
            kw["compiler_params"] = cp
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n,) + blk_shape, dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(x)

    return call


@functools.lru_cache(maxsize=64)
def _build_all_gather_bidi(n: int, axis: str, blk_shape, dtype_str: str,
                           interpret: bool, sub=None):
    """Bidirectional ring all-gather: every step sends the freshest
    right-going block right AND the freshest left-going block left, so
    both directions of each duplex ICI link carry payload and the
    schedule finishes in ceil((n-1)/2) steps instead of n-1 — the
    duplex trick of ``_build_all_reduce`` ("bidi") applied to the
    gather schedule (reference menu analog:
    ``coll_base_allgather.c`` neighbor-exchange, which also halves the
    step count by pairing directions).

    Right-going chain at step k ships block (my-k) and lands block
    (my-1-k) from the left; left-going ships (my+k) and lands
    (my+1+k).  r_cnt = n//2 right deliveries + l_cnt = n-1-n//2 left
    deliveries cover the n-1 remote blocks exactly once.  The paired
    steps run in a fori_loop (constant kernel size in n, like the
    unidirectional builder); only the at-most-one direction-lopsided
    tail step (even n: r_cnt = l_cnt + 1) is emitted separately.
    """
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(
        n, axis, interpret)
    r_cnt = n // 2
    l_cnt = n - 1 - r_cnt
    paired = min(r_cnt, l_cnt)

    def kernel(x_ref, out_ref, local_sem, send_r, send_l, recv_r,
               recv_l):
        my, dev = _ring_fn(lax, axis, sub)
        right = dev(lax.rem(my + 1, n))
        left = dev(lax.rem(my - 1 + n, n))
        barrier(right, left)
        cp = pltpu.make_async_copy(x_ref, out_ref.at[my], local_sem)
        cp.start()
        cp.wait()

        def rdma_right(k):
            slot = lax.rem(my - k + n, n)
            return pltpu.make_async_remote_copy(
                src_ref=out_ref.at[slot], dst_ref=out_ref.at[slot],
                send_sem=send_r, recv_sem=recv_r.at[k],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

        def step(k, carry):
            r = rdma_right(k)
            slot_l = lax.rem(my + k, n)
            ld = pltpu.make_async_remote_copy(
                src_ref=out_ref.at[slot_l], dst_ref=out_ref.at[slot_l],
                send_sem=send_l, recv_sem=recv_l.at[k],
                device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            r.start()    # both directions in flight together —
            ld.start()   # that simultaneity IS the bandwidth win
            r.wait()
            ld.wait()
            return carry

        lax.fori_loop(0, paired, step, 0)
        if r_cnt > paired:           # even n: one right-only tail step
            r = rdma_right(paired)
            r.start()
            r.wait()

    def call(x):
        kw = {}
        cp = cparams(16)
        if cp is not None:
            kw["compiler_params"] = cp
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n,) + blk_shape, dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((max(1, r_cnt),)),
                            pltpu.SemaphoreType.DMA((max(1, l_cnt),))],
            interpret=interpret,
            **kw,
        )(x)

    return call


def _rs_phase(lax, pl, pltpu, *, n, my, right, acc_ref, recv_ref,
              send_sem, rs_sems, align: int, fold, stage_ref=None,
              decode=None):
    """The shared ring reduce-scatter phase: n-1 steps, each sending the
    running partial for block (my+align-k) to the right neighbor and
    fusing the incoming partial into block (my+align-1-k).  After the
    loop, block (my+align+1) % n is fully reduced on this device —
    align=0 for the all-reduce schedule (owner my+1), align=-1 for
    owner-aligned reduce-scatter (owner my).  ONE copy of the DMA /
    semaphore / accumulate discipline, shared by every ring kernel.
    ``fold`` is the elementwise reduction.

    ``stage_ref``/``decode`` are the wire-codec hooks (wire16): when
    given, each outgoing partial is written through ``stage_ref`` (a
    single (rows, 128) VMEM buffer at the WIRE dtype — safe to reuse
    per step because the wait covers send completion) and incoming
    partials pass through ``decode`` before the fold.

    Refs are block-leading 3-D — acc (n, rows, 128), recv (n-1, rows,
    128) — so every slice rides the UNTILED leading dim: Mosaic tiles
    the trailing (rows, 128) pair and rejects row-slices of a tiled
    dim ("slice must be aligned to tiling (8)"), which a flat (n, blk)
    layout would need."""

    def rs_step(k, carry):
        send_idx = lax.rem(my + align - k + 2 * n, n)
        recv_idx = lax.rem(my + align - 1 - k + 2 * n, n)
        if stage_ref is None:
            src = acc_ref.at[send_idx]
        else:
            stage_ref[...] = acc_ref[send_idx].astype(stage_ref.dtype)
            src = stage_ref
        rdma = pltpu.make_async_remote_copy(
            src_ref=src, dst_ref=recv_ref.at[k],
            send_sem=send_sem, recv_sem=rs_sems.at[k],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma.wait()   # my partial for block recv_idx arrived
        part = recv_ref[k]
        if decode is not None:
            part = decode(part)
        acc_ref[recv_idx] = fold(acc_ref[recv_idx], part)
        return carry

    lax.fori_loop(0, n - 1, rs_step, 0)
    return lax.rem(my + align + 1 + n, n)   # the completed block


@functools.lru_cache(maxsize=64)
def _build_all_reduce(n: int, axis: str, rows: int, dtype_str: str,
                      interpret: bool, op: str = "sum", sub=None):
    """Ring all-reduce: n-1 reduce-scatter steps with the fold fused
    into the ring loop, then n-1 all-gather steps — one kernel, the
    explicit-DMA form of ``coll_base_allreduce.c:341``.

    Per-device payload is pre-shaped to (n, rows, 128) — lane-major
    block-leading layout so all slicing rides the untiled leading dim
    (see ``_rs_phase``).  Distinct recv slots per step (scratch
    (n-1, rows, 128)) make the schedule self-synchronizing: no slot is
    ever reused, so the send/recv semaphore pair is the only ordering
    needed (the capacity/backpressure dance of a 2-slot scheme is
    deliberately traded for VMEM).
    """
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    fold = _op_fn(jnp, op)

    def kernel(x_ref, out_ref, acc_ref, recv_ref,
               local_sem, send_sem, rs_sems, ag_sems):
        my, dev = _ring_fn(lax, axis, sub)
        right = dev(lax.rem(my + 1, n))
        barrier(right, dev(lax.rem(my - 1 + n, n)))
        cp = pltpu.make_async_copy(x_ref, acc_ref, local_sem)
        cp.start()
        cp.wait()

        done = _rs_phase(lax, pl, pltpu, n=n, my=my, right=right,
                         acc_ref=acc_ref, recv_ref=recv_ref,
                         send_sem=send_sem, rs_sems=rs_sems, align=0,
                         fold=fold)
        cp2 = pltpu.make_async_copy(acc_ref.at[done], out_ref.at[done],
                                    local_sem)
        cp2.start()
        cp2.wait()

        _ag_phase(lax, pl, pltpu, n=n, my=my, right=right,
                  out_ref=out_ref, send_sem=send_sem, ag_sems=ag_sems)

    def call(x):  # x: (n, rows, 128) per device
        kw = {}
        cp = cparams(3)
        if cp is not None:
            kw["compiler_params"] = cp
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, rows, 128), dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((n, rows, 128),
                                       jnp.dtype(dtype_str)),
                            pltpu.VMEM((n - 1, rows, 128),
                                       jnp.dtype(dtype_str)),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((n - 1,)),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(x)

    return call


@functools.lru_cache(maxsize=64)
def _build_all_reduce_wire16(n: int, axis: str, rows: int,
                             interpret: bool, op: str = "sum"):
    """Wire-compressed ring all-reduce: f32 accumulation on-chip, bf16
    bytes on the ICI — each ring step casts the outgoing partial to
    bf16 (one VPU pass), DMAs HALF the bytes, and folds the incoming
    partial back at f32.  Per-step wire time halves; each partial takes
    one bf16 rounding per hop, so the ABSOLUTE error is bounded by
    ~n · 2^-8 · max|partial| (relative error is unbounded where the
    true sum cancels toward zero — inherent to any compressed
    reduction, and why this is opt-in) — the gradient-allreduce
    compression trade every
    DDP-style framework offers, possible here precisely because the
    transport is owned (the reference's ``ompi_op`` contract is
    full-precision end-to-end; an MPI layer cannot change the wire
    format without owning the btl).

    The completed block is rounded to bf16 BEFORE the all-gather phase,
    so every rank returns bit-identical results (MPI allreduce
    reproducibility contract) at bf16 value precision.  Output is bf16
    (n, rows, 128); the wrapper upcasts."""
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    fold = _op_fn(jnp, op)

    def kernel(x_ref, out_ref, acc_ref, stage_ref, recv_ref,
               local_sem, send_sem, rs_sems, ag_sems):
        my = lax.axis_index(axis)
        right = lax.rem(my + 1, n)
        barrier(right, lax.rem(my - 1 + n, n))
        cp = pltpu.make_async_copy(x_ref, acc_ref, local_sem)
        cp.start()
        cp.wait()

        # the shared ring discipline with the bf16 wire codec hooks
        done = _rs_phase(lax, pl, pltpu, n=n, my=my, right=right,
                         acc_ref=acc_ref, recv_ref=recv_ref,
                         send_sem=send_sem, rs_sems=rs_sems, align=0,
                         fold=fold, stage_ref=stage_ref,
                         decode=lambda p: p.astype(jnp.float32))
        # round the completed block ONCE and circulate the rounded
        # value: every rank ends bit-identical
        stage_ref[...] = acc_ref[done].astype(jnp.bfloat16)
        cp2 = pltpu.make_async_copy(stage_ref, out_ref.at[done],
                                    local_sem)
        cp2.start()
        cp2.wait()
        _ag_phase(lax, pl, pltpu, n=n, my=my, right=right,
                  out_ref=out_ref, send_sem=send_sem, ag_sems=ag_sems)

    def call(x):  # x: (n, rows, 128) f32 -> (n, rows, 128) bf16
        kw = {}
        cp = cparams(15)
        if cp is not None:
            kw["compiler_params"] = cp
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, rows, 128),
                                           "bfloat16"),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((n, rows, 128),
                                       jnp.dtype("float32")),
                            pltpu.VMEM((rows, 128),
                                       jnp.dtype("bfloat16")),
                            pltpu.VMEM((n - 1, rows, 128),
                                       jnp.dtype("bfloat16")),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((n - 1,)),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(x)

    return call


@functools.lru_cache(maxsize=64)
def _build_reduce_scatter(n: int, axis: str, rows: int, dtype_str: str,
                          interpret: bool, op: str = "sum",
                          sub=None, wire16: bool = False,
                          cid: int = 4):
    """Ring reduce-scatter: n-1 steps, fold fused into the ring;
    device i ends owning fully-reduced block i (the first half of
    ``coll_base_allreduce.c:341``'s ring, block-owner aligned).
    Blocks are (rows, 128) — see ``_rs_phase`` on the layout.

    ``wire16`` (f32 payloads): partials cross the wire at bf16 through
    ``_rs_phase``'s codec hooks, folds stay f32, and — unlike the
    all-reduce twin — the owner's result needs no rounding pass: each
    block lives on exactly one rank, so full-f32 output is returned
    (absolute error ~n·2^-8·max|partial| from the wire roundings)."""
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    fold = _op_fn(jnp, op)

    def kernel(x_ref, out_ref, acc_ref, recv_ref,
               local_sem, send_sem, rs_sems, *maybe_stage):
        my, dev = _ring_fn(lax, axis, sub)
        right = dev(lax.rem(my + 1, n))
        barrier(right, dev(lax.rem(my - 1 + n, n)))
        cp = pltpu.make_async_copy(x_ref, acc_ref, local_sem)
        cp.start()
        cp.wait()

        # align=-1: the completed block is `my` — it IS my result
        done = _rs_phase(lax, pl, pltpu, n=n, my=my, right=right,
                         acc_ref=acc_ref, recv_ref=recv_ref,
                         send_sem=send_sem, rs_sems=rs_sems, align=-1,
                         fold=fold,
                         stage_ref=maybe_stage[0] if wire16 else None,
                         decode=(lambda p: p.astype(jnp.float32))
                         if wire16 else None)
        cp2 = pltpu.make_async_copy(acc_ref.at[done], out_ref, local_sem)
        cp2.start()
        cp2.wait()

    def call(x):  # x: (n, rows, 128) per device -> (rows, 128)
        kw = {}
        cp = cparams(cid)
        if cp is not None:
            kw["compiler_params"] = cp
        dt = jnp.dtype(dtype_str)
        recv_dt = jnp.dtype("bfloat16") if wire16 else dt
        scratch = [pltpu.VMEM((n, rows, 128), dt),
                   pltpu.VMEM((n - 1, rows, 128), recv_dt),
                   pltpu.SemaphoreType.DMA(()),
                   pltpu.SemaphoreType.DMA(()),
                   pltpu.SemaphoreType.DMA((n - 1,))]
        if wire16:
            scratch.append(pltpu.VMEM((rows, 128), recv_dt))
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((rows, 128), dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=scratch,
            interpret=interpret,
            **kw,
        )(x)

    return call


def _ag_phase(lax, pl, pltpu, *, n, my, right, out_ref, send_sem,
              ag_sems):
    """The shared ring all-gather phase of the all-reduce kernels: n-1
    steps, each forwarding the freshest completed block (my+1-k) to the
    right neighbor in place on ``out_ref`` — pure DMA, no window."""

    def ag_step(k, carry):
        fwd = lax.rem(my + 1 - k + n, n)
        rdma = pltpu.make_async_remote_copy(
            src_ref=out_ref.at[fwd], dst_ref=out_ref.at[fwd],
            send_sem=send_sem, recv_sem=ag_sems.at[k],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma.wait()   # completed block (my-k)%n landed from the left
        return carry

    lax.fori_loop(0, n - 1, ag_step, 0)


def _seg_fold_row(lax, pl, pltpu, *, acc_row, recv_row, nseg: int, va,
                  vb, load_sems, wb_sems, fold):
    """Fold one received HBM row into one accumulator row through the
    2-slot double-buffered VMEM window: while segment s reduces,
    segment s+1's loads are already in flight, and writebacks drain one
    segment behind.  Fully drained on return, so the window is
    immediately reusable (the bidi kernel folds both directions through
    one window).

    ``acc_row(s)`` / ``recv_row(s)`` hand back the (S, 128) ref of
    segment s — the caller owns the block/direction addressing, always
    through untiled leading dims (see ``_rs_phase`` on why)."""

    def start_load(s):
        slot = lax.rem(s, 2)
        pltpu.make_async_copy(acc_row(s), va.at[slot],
                              load_sems.at[slot, 0]).start()
        pltpu.make_async_copy(recv_row(s), vb.at[slot],
                              load_sems.at[slot, 1]).start()

    def wait_wb(slot, s_of_wb):
        # descriptor only carries the byte count to decrement
        pltpu.make_async_copy(va.at[slot], acc_row(s_of_wb),
                              wb_sems.at[slot]).wait()

    start_load(0)

    def seg_step(s, c):
        slot = lax.rem(s, 2)

        @pl.when(s + 1 < nseg)
        def _prefetch():
            @pl.when(s >= 1)
            def _drain_prev_wb():
                # slot 1-slot's writeback (segment s-1) must land
                # before its VMEM buffer is reloaded
                wait_wb(1 - slot, s - 1)
            start_load(s + 1)

        pltpu.make_async_copy(acc_row(s), va.at[slot],
                              load_sems.at[slot, 0]).wait()
        pltpu.make_async_copy(recv_row(s), vb.at[slot],
                              load_sems.at[slot, 1]).wait()
        va[slot] = fold(va[slot], vb[slot])
        pltpu.make_async_copy(va.at[slot], acc_row(s),
                              wb_sems.at[slot]).start()
        return c

    lax.fori_loop(0, nseg, seg_step, 0)
    # drain outstanding writebacks before this row is sent next step
    wait_wb(lax.rem(nseg - 1, 2), nseg - 1)
    if nseg >= 2:
        wait_wb(lax.rem(nseg - 2, 2), nseg - 2)


def _seg_rs_phase(lax, pl, pltpu, *, n, my, right, acc_ref, recv_ref,
                  send_sem, rs_sems, align: int, fold, nseg: int,
                  va, vb, load_sems, wb_sems):
    """Segmented twin of ``_rs_phase``: acc/recv live in HBM as
    (n, nseg, S, 128) / (n-1, nseg, S, 128); the fold streams through
    the bounded VMEM window (``_seg_fold_row``) — the bounded-buffer
    pipeline of the reference's segmented ring
    (``coll_base_allreduce.c:618``), which exists precisely so payload
    size is bounded by main memory, not the staging buffer."""

    def rs_step(k, carry):
        send_idx = lax.rem(my + align - k + 2 * n, n)
        recv_idx = lax.rem(my + align - 1 - k + 2 * n, n)
        rdma = pltpu.make_async_remote_copy(
            src_ref=acc_ref.at[send_idx], dst_ref=recv_ref.at[k],
            send_sem=send_sem, recv_sem=rs_sems.at[k],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        rdma.start()
        rdma.wait()   # my partial for block recv_idx arrived (HBM)
        _seg_fold_row(lax, pl, pltpu,
                      acc_row=lambda s: acc_ref.at[recv_idx, s],
                      recv_row=lambda s: recv_ref.at[k, s],
                      nseg=nseg, va=va, vb=vb, load_sems=load_sems,
                      wb_sems=wb_sems, fold=fold)
        return carry

    lax.fori_loop(0, n - 1, rs_step, 0)
    return lax.rem(my + align + 1 + n, n)   # the completed block


@functools.lru_cache(maxsize=64)
def _build_all_reduce_seg(n: int, axis: str, nseg: int, srows: int,
                          dtype_str: str, interpret: bool,
                          op: str = "sum"):
    """Segmented ring all-reduce for large payloads: HBM-resident
    (n, nseg, S, 128) accumulator, bounded VMEM window, same ring
    schedule as the fused kernel.  The all-gather phase is pure
    HBM↔HBM remote DMA and needs no window at all."""
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    fold = _op_fn(jnp, op)

    def kernel(x_ref, out_ref, acc_ref, recv_ref, va, vb,
               local_sem, send_sem, load_sems, wb_sems, rs_sems, ag_sems):
        my = lax.axis_index(axis)
        right = lax.rem(my + 1, n)
        barrier(right, lax.rem(my - 1 + n, n))
        cp = pltpu.make_async_copy(x_ref, acc_ref, local_sem)
        cp.start()
        cp.wait()

        done = _seg_rs_phase(
            lax, pl, pltpu, n=n, my=my, right=right, acc_ref=acc_ref,
            recv_ref=recv_ref, send_sem=send_sem, rs_sems=rs_sems,
            align=0, fold=fold, nseg=nseg,
            va=va, vb=vb, load_sems=load_sems, wb_sems=wb_sems)
        cp2 = pltpu.make_async_copy(acc_ref.at[done], out_ref.at[done],
                                    local_sem)
        cp2.start()
        cp2.wait()

        _ag_phase(lax, pl, pltpu, n=n, my=my, right=right,
                  out_ref=out_ref, send_sem=send_sem, ag_sems=ag_sems)

    def call(x):  # x: (n, nseg, S, 128) per device
        kw = {}
        cp = cparams(5)
        if cp is not None:
            kw["compiler_params"] = cp
        dt = jnp.dtype(dtype_str)
        # acc/recv are HBM-resident ring state: Mosaic only allocates
        # VMEM/SMEM/semaphore scratch, so HBM buffers ride as extra
        # ANY-space outputs (discarded) — same kernel arg order
        out, _, _ = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((n, nseg, srows, 128),
                                            dtype_str),
                       jax.ShapeDtypeStruct((n, nseg, srows, 128),
                                            dtype_str),
                       jax.ShapeDtypeStruct((n - 1, nseg, srows, 128),
                                            dtype_str)),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((2, srows, 128), dt),
                            pltpu.VMEM((2, srows, 128), dt),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((n - 1,)),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(x)
        return out

    return call


@functools.lru_cache(maxsize=64)
def _build_reduce_scatter_seg(n: int, axis: str, nseg: int, srows: int,
                              dtype_str: str, interpret: bool,
                              op: str = "sum"):
    """Segmented ring reduce-scatter (owner-aligned, align=-1) — the
    large-payload twin of ``_build_reduce_scatter``."""
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    fold = _op_fn(jnp, op)

    def kernel(x_ref, out_ref, acc_ref, recv_ref, va, vb,
               local_sem, send_sem, load_sems, wb_sems, rs_sems):
        my = lax.axis_index(axis)
        right = lax.rem(my + 1, n)
        barrier(right, lax.rem(my - 1 + n, n))
        cp = pltpu.make_async_copy(x_ref, acc_ref, local_sem)
        cp.start()
        cp.wait()

        done = _seg_rs_phase(
            lax, pl, pltpu, n=n, my=my, right=right, acc_ref=acc_ref,
            recv_ref=recv_ref, send_sem=send_sem, rs_sems=rs_sems,
            align=-1, fold=fold, nseg=nseg,
            va=va, vb=vb, load_sems=load_sems, wb_sems=wb_sems)
        cp2 = pltpu.make_async_copy(acc_ref.at[done], out_ref, local_sem)
        cp2.start()
        cp2.wait()

    def call(x):  # x: (n, nseg, S, 128) per device -> (nseg, S, 128)
        kw = {}
        cp = cparams(6)
        if cp is not None:
            kw["compiler_params"] = cp
        dt = jnp.dtype(dtype_str)
        # HBM ring state as extra ANY outputs (see _build_all_reduce_seg)
        out, _, _ = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((nseg, srows, 128),
                                            dtype_str),
                       jax.ShapeDtypeStruct((n, nseg, srows, 128),
                                            dtype_str),
                       jax.ShapeDtypeStruct((n - 1, nseg, srows, 128),
                                            dtype_str)),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((2, srows, 128), dt),
                            pltpu.VMEM((2, srows, 128), dt),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(x)
        return out

    return call


def _bidi_done_and_ag(lax, pl, pltpu, *, n, my, right, left,
                      acc_ref, out_ref, local_sem, send_cw_sem,
                      send_ccw_sem, ag_cw_sems, ag_ccw_sems):
    """Shared tail of the bidirectional all-reduce kernels: copy each
    direction's completed half-block out, then run the mirrored
    all-gather rings (both duplex directions busy every step).

    Refs are direction-leading — acc/out (n, 2, ..., S, 128), dir 0 =
    clockwise half, dir 1 = counter-clockwise — so the per-direction
    slices ride untiled leading dims (see ``_rs_phase``)."""
    done_cw = lax.rem(my + 1, n)
    done_ccw = lax.rem(my - 1 + n, n)
    c1 = pltpu.make_async_copy(acc_ref.at[done_cw, 0],
                               out_ref.at[done_cw, 0], local_sem)
    c1.start()
    c1.wait()
    c2 = pltpu.make_async_copy(acc_ref.at[done_ccw, 1],
                               out_ref.at[done_ccw, 1], local_sem)
    c2.start()
    c2.wait()

    def ag_step(k, carry):
        f_cw = lax.rem(my + 1 - k + n, n)
        f_ccw = lax.rem(my - 1 + k + n, n)
        d_cw = pltpu.make_async_remote_copy(
            src_ref=out_ref.at[f_cw, 0],
            dst_ref=out_ref.at[f_cw, 0],
            send_sem=send_cw_sem, recv_sem=ag_cw_sems.at[k],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        d_ccw = pltpu.make_async_remote_copy(
            src_ref=out_ref.at[f_ccw, 1],
            dst_ref=out_ref.at[f_ccw, 1],
            send_sem=send_ccw_sem, recv_sem=ag_ccw_sems.at[k],
            device_id=left,
            device_id_type=pltpu.DeviceIdType.LOGICAL)
        d_cw.start()
        d_ccw.start()
        d_cw.wait()
        d_ccw.wait()
        return carry

    lax.fori_loop(0, n - 1, ag_step, 0)


@functools.lru_cache(maxsize=64)
def _build_all_reduce_seg_bidi(n: int, axis: str, nseg: int, srows: int,
                               dtype_str: str, interpret: bool,
                               op: str = "sum"):
    """Segmented AND bidirectional ring all-reduce — the large-payload
    champion: the (n, 2, nseg, S, 128) payload is HBM-resident, dir 0
    rides the clockwise ring and dir 1 the counter-clockwise ring
    concurrently (both duplex ICI directions carry a half-payload every
    step), and each direction's fold streams through ONE shared
    double-buffered VMEM window (``_seg_fold_row`` drains fully between
    directions, so the window is reused — folds are VPU-sequential
    anyway; it is the DMAs that overlap).
    """
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    fold = _op_fn(jnp, op)

    def kernel(x_ref, out_ref, acc_ref, recv_cw, recv_ccw, va, vb,
               local_sem, send_cw_sem, send_ccw_sem, load_sems, wb_sems,
               rs_cw_sems, rs_ccw_sems, ag_cw_sems, ag_ccw_sems):
        my = lax.axis_index(axis)
        right = lax.rem(my + 1, n)
        left = lax.rem(my - 1 + n, n)
        barrier(right, left)
        cp = pltpu.make_async_copy(x_ref, acc_ref, local_sem)
        cp.start()
        cp.wait()

        def rs_step(k, carry):
            s_cw = lax.rem(my - k + 2 * n, n)
            r_cw = lax.rem(my - 1 - k + 2 * n, n)
            s_ccw = lax.rem(my + k, n)
            r_ccw = lax.rem(my + 1 + k, n)
            d_cw = pltpu.make_async_remote_copy(
                src_ref=acc_ref.at[s_cw, 0],
                dst_ref=recv_cw.at[k],
                send_sem=send_cw_sem, recv_sem=rs_cw_sems.at[k],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            d_ccw = pltpu.make_async_remote_copy(
                src_ref=acc_ref.at[s_ccw, 1],
                dst_ref=recv_ccw.at[k],
                send_sem=send_ccw_sem, recv_sem=rs_ccw_sems.at[k],
                device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            d_cw.start()
            d_ccw.start()          # both directions' DMAs in flight
            d_cw.wait()
            _seg_fold_row(lax, pl, pltpu,
                          acc_row=lambda s: acc_ref.at[r_cw, 0, s],
                          recv_row=lambda s: recv_cw.at[k, s],
                          nseg=nseg, va=va, vb=vb,
                          load_sems=load_sems, wb_sems=wb_sems,
                          fold=fold)
            d_ccw.wait()
            _seg_fold_row(lax, pl, pltpu,
                          acc_row=lambda s: acc_ref.at[r_ccw, 1, s],
                          recv_row=lambda s: recv_ccw.at[k, s],
                          nseg=nseg, va=va, vb=vb,
                          load_sems=load_sems, wb_sems=wb_sems,
                          fold=fold)
            return carry

        lax.fori_loop(0, n - 1, rs_step, 0)
        _bidi_done_and_ag(lax, pl, pltpu, n=n, my=my, right=right,
                          left=left, acc_ref=acc_ref,
                          out_ref=out_ref, local_sem=local_sem,
                          send_cw_sem=send_cw_sem,
                          send_ccw_sem=send_ccw_sem,
                          ag_cw_sems=ag_cw_sems, ag_ccw_sems=ag_ccw_sems)

    def call(x):  # x: (n, 2, nseg, S, 128) per device
        kw = {}
        cp = cparams(12)
        if cp is not None:
            kw["compiler_params"] = cp
        dt = jnp.dtype(dtype_str)
        # HBM ring state as extra ANY outputs (see _build_all_reduce_seg)
        out, _, _, _ = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct((n, 2, nseg, srows, 128),
                                            dtype_str),
                       jax.ShapeDtypeStruct((n, 2, nseg, srows, 128),
                                            dtype_str),
                       jax.ShapeDtypeStruct((n - 1, nseg, srows, 128),
                                            dtype_str),
                       jax.ShapeDtypeStruct((n - 1, nseg, srows, 128),
                                            dtype_str)),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((2, srows, 128), dt),
                            pltpu.VMEM((2, srows, 128), dt),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((n - 1,)),
                            pltpu.SemaphoreType.DMA((n - 1,)),
                            pltpu.SemaphoreType.DMA((n - 1,)),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(x)
        return out

    return call


@functools.lru_cache(maxsize=64)
def _build_all_reduce_bidi(n: int, axis: str, rows: int, dtype_str: str,
                           interpret: bool, op: str = "sum"):
    """Bidirectional ring all-reduce: the (n, 2, rows, 128) payload is
    split into a clockwise half (dir 0, sent rightward) and a
    counter-clockwise half (dir 1, sent leftward), with mirrored
    reduce-scatter + all-gather schedules running concurrently.  ICI
    links are duplex, so both directions carry a half-payload every
    step — per-step wire time halves vs the unidirectional ring.

    CW completes block (my+1)'s dir-0 half; CCW completes block
    (my-1)'s dir-1 half; the mirrored all-gather phases circulate both.
    """
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    fold = _op_fn(jnp, op)

    def kernel(x_ref, out_ref, acc_ref, recv_cw, recv_ccw,
               local_sem, send_cw_sem, send_ccw_sem,
               rs_cw_sems, rs_ccw_sems, ag_cw_sems, ag_ccw_sems):
        my = lax.axis_index(axis)
        right = lax.rem(my + 1, n)
        left = lax.rem(my - 1 + n, n)
        barrier(right, left)
        cp = pltpu.make_async_copy(x_ref, acc_ref, local_sem)
        cp.start()
        cp.wait()

        def rs_step(k, carry):
            s_cw = lax.rem(my - k + 2 * n, n)
            r_cw = lax.rem(my - 1 - k + 2 * n, n)
            s_ccw = lax.rem(my + k, n)
            r_ccw = lax.rem(my + 1 + k, n)
            d_cw = pltpu.make_async_remote_copy(
                src_ref=acc_ref.at[s_cw, 0],
                dst_ref=recv_cw.at[k],
                send_sem=send_cw_sem, recv_sem=rs_cw_sems.at[k],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            d_ccw = pltpu.make_async_remote_copy(
                src_ref=acc_ref.at[s_ccw, 1],
                dst_ref=recv_ccw.at[k],
                send_sem=send_ccw_sem, recv_sem=rs_ccw_sems.at[k],
                device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            d_cw.start()
            d_ccw.start()
            d_cw.wait()
            d_ccw.wait()
            acc_ref[r_cw, 0] = fold(acc_ref[r_cw, 0], recv_cw[k])
            acc_ref[r_ccw, 1] = fold(acc_ref[r_ccw, 1], recv_ccw[k])
            return carry

        lax.fori_loop(0, n - 1, rs_step, 0)
        _bidi_done_and_ag(lax, pl, pltpu, n=n, my=my, right=right,
                          left=left, acc_ref=acc_ref,
                          out_ref=out_ref, local_sem=local_sem,
                          send_cw_sem=send_cw_sem,
                          send_ccw_sem=send_ccw_sem,
                          ag_cw_sems=ag_cw_sems, ag_ccw_sems=ag_ccw_sems)

    def call(x):  # x: (n, 2, rows, 128) per device
        kw = {}
        cp = cparams(7)
        if cp is not None:
            kw["compiler_params"] = cp
        dt = jnp.dtype(dtype_str)
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, 2, rows, 128),
                                           dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((n, 2, rows, 128), dt),
                            pltpu.VMEM((n - 1, rows, 128), dt),
                            pltpu.VMEM((n - 1, rows, 128), dt),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((n - 1,)),
                            pltpu.SemaphoreType.DMA((n - 1,)),
                            pltpu.SemaphoreType.DMA((n - 1,)),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(x)

    return call


@functools.lru_cache(maxsize=64)
def _build_all_to_all(n: int, axis: str, blk_shape, dtype_str: str,
                      interpret: bool):
    """Explicit all-to-all: n-1 steps, at step k every device DMAs its
    block for the device k hops right DIRECTLY to that device (ICI
    routes non-neighbor transfers), landing in the sender's slot —
    the SP/MoE dispatch primitive (``lax.all_to_all`` twin;
    ``coll_base_alltoall.c`` pairwise-exchange algorithm, where step k
    pairs (i, i+k)).  Fully symmetric: one DMA per device per step.
    """
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)

    def kernel(x_ref, out_ref, local_sem, send_sem, recv_sems):
        my = lax.axis_index(axis)
        # pairwise exchange touches every peer: the entry barrier must
        # cover them all, not just ring neighbors
        barrier(*[lax.rem(my + k, n) for k in range(1, n)])
        cp = pltpu.make_async_copy(x_ref.at[my], out_ref.at[my],
                                   local_sem)
        cp.start()
        cp.wait()

        def step(k, carry):
            peer = lax.rem(my + k, n)     # send my block for `peer`
            rdma = pltpu.make_async_remote_copy(
                src_ref=x_ref.at[peer], dst_ref=out_ref.at[my],
                send_sem=send_sem, recv_sem=recv_sems.at[k - 1],
                device_id=peer,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            rdma.wait()   # send done + block from (my-k) landed
            return carry

        lax.fori_loop(1, n, step, 0)

    def call(x):  # x: (n, *blk) per device -> (n, *blk) transposed
        kw = {}
        cp = cparams(9)
        if cp is not None:
            kw["compiler_params"] = cp
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n,) + blk_shape, dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(x)

    return call


@functools.lru_cache(maxsize=64)
def _build_all_to_all_v(n: int, axis: str, max_rows: int, width: int,
                        chunk: int, dtype_str: str, interpret: bool):
    """Ragged pairwise all-to-all — true alltoallv for MoE/EP dispatch
    (``coll_base_alltoall.c`` pairwise exchange with per-pair sizes).

    The per-pair row counts arrive as a runtime (n, n) int32 table in
    SMEM, so ONE compile serves every routing outcome — MoE re-routes
    every step, and a counts-specialized kernel would recompile per
    batch.  Each pair moves ceil(cnt/chunk) fixed-shape (chunk, W)
    DMAs: Mosaic needs static DMA shapes, but trip counts may be
    dynamic scalars — wasted wire is bounded by chunk-1 rows per pair,
    vs the padded ``all_to_all`` moving max_rows for every pair
    regardless of raggedness.

    Asymmetric counts mean send and receive chunk totals differ per
    device, so the send loop uses ``wait_send`` and a separate receive
    loop drains ``recv_sems`` by ``wait_recv`` — the split-phase form
    of the symmetric kernels' ``wait()``.
    """
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    nchunks = _ragged_nchunks(max_rows, chunk, interpret)

    def kernel(counts_ref, x_ref, out_ref, local_sem, send_sem,
               recv_sems):
        my = lax.axis_index(axis)
        barrier(*[lax.rem(my + k, n) for k in range(1, n)])

        # local block: out[my] rows [:counts[my,my]] come from x[my]
        def local_chunk(c, carry):
            sl = pl.ds(c * chunk, chunk)
            cp = pltpu.make_async_copy(x_ref.at[my, sl],
                                       out_ref.at[my, sl], local_sem)
            cp.start()
            cp.wait()
            return carry

        lax.fori_loop(0, nchunks(counts_ref[my, my]), local_chunk, 0)

        def pair_step(k, carry):
            dst = lax.rem(my + k, n)
            src = lax.rem(my - k + n, n)

            def send_chunk(c, carry2):
                sl = pl.ds(c * chunk, chunk)
                rdma = pltpu.make_async_remote_copy(
                    src_ref=x_ref.at[dst, sl],
                    dst_ref=out_ref.at[my, sl],
                    send_sem=send_sem, recv_sem=recv_sems.at[k - 1],
                    device_id=dst,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                rdma.start()
                rdma.wait_send()
                return carry2

            lax.fori_loop(0, nchunks(counts_ref[my, dst]), send_chunk,
                          0, unroll=False)

            def recv_chunk(c, carry2):
                sl = pl.ds(c * chunk, chunk)
                # shape-only descriptor: wait_recv consumes exactly one
                # inbound (chunk, W) DMA's bytes from recv_sems[k-1]
                pltpu.make_async_remote_copy(
                    src_ref=out_ref.at[src, sl],
                    dst_ref=out_ref.at[src, sl],
                    send_sem=send_sem, recv_sem=recv_sems.at[k - 1],
                    device_id=src,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                ).wait_recv()
                return carry2

            lax.fori_loop(0, nchunks(counts_ref[src, my]), recv_chunk,
                          0, unroll=False)
            return carry

        lax.fori_loop(1, n, pair_step, 0)

    def call(counts, x):  # counts: (n, n) i32; x: (n, max_rows, W)
        kw = {}
        cp = cparams(13)
        if cp is not None:
            kw["compiler_params"] = cp
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, max_rows, width),
                                           dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(counts, x)

    return call


@functools.lru_cache(maxsize=64)
def _build_all_gather_v(n: int, axis: str, max_rows: int, width: int,
                        chunk: int, dtype_str: str, interpret: bool):
    """Ragged ring all-gather (true allgatherv): per-rank valid row
    counts arrive as a runtime (n,) int32 table, and each ring step
    forwards a block as ceil(count/chunk) fixed-shape (chunk, W) DMAs —
    wire bytes follow the raggedness instead of every block moving
    max_rows (``coll_base_allgatherv.c`` ring with per-peer counts).
    Same static-shape/dynamic-trip-count discipline as
    ``_build_all_to_all_v``; the interpreter runs the symmetric
    full-block schedule (its DMA emulation needs matched op counts) and
    the ragged trip counts are AOT-compile-proven."""
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    nchunks = _ragged_nchunks(max_rows, chunk, interpret)

    def kernel(counts_ref, x_ref, out_ref, local_sem, send_sem,
               recv_sems):
        my = lax.axis_index(axis)
        right = lax.rem(my + 1, n)
        left = lax.rem(my - 1 + n, n)
        barrier(right, left)

        def local_chunk(c, carry):
            sl = pl.ds(c * chunk, chunk)
            cp = pltpu.make_async_copy(x_ref.at[sl],
                                       out_ref.at[my, sl], local_sem)
            cp.start()
            cp.wait()
            return carry

        lax.fori_loop(0, nchunks(counts_ref[my]), local_chunk, 0)

        def step(k, carry):
            s_send = lax.rem(my - k + 1 + 2 * n, n)   # freshest block
            s_recv = lax.rem(my - k + 2 * n, n)       # lands from left

            def send_chunk(c, c2):
                sl = pl.ds(c * chunk, chunk)
                rdma = pltpu.make_async_remote_copy(
                    src_ref=out_ref.at[s_send, sl],
                    dst_ref=out_ref.at[s_send, sl],
                    send_sem=send_sem, recv_sem=recv_sems.at[k - 1],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
                rdma.start()
                rdma.wait_send()
                return c2

            lax.fori_loop(0, nchunks(counts_ref[s_send]), send_chunk,
                          0, unroll=False)

            def recv_chunk(c, c2):
                sl = pl.ds(c * chunk, chunk)
                pltpu.make_async_remote_copy(
                    src_ref=out_ref.at[s_recv, sl],
                    dst_ref=out_ref.at[s_recv, sl],
                    send_sem=send_sem, recv_sem=recv_sems.at[k - 1],
                    device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                ).wait_recv()
                return c2

            lax.fori_loop(0, nchunks(counts_ref[s_recv]), recv_chunk,
                          0, unroll=False)
            return carry

        lax.fori_loop(1, n, step, 0)

    def call(counts, x):  # counts: (n,) i32; x: (max_rows, W)
        kw = {}
        cp = cparams(14)
        if cp is not None:
            kw["compiler_params"] = cp
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, max_rows, width),
                                           dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA((n - 1,))],
            interpret=interpret,
            **kw,
        )(counts, x)

    return call


@functools.lru_cache(maxsize=64)
def _build_bcast(n: int, axis: str, nseg: int, srows: int,
                 dtype_str: str, interpret: bool):
    """Pipelined segmented ring broadcast — the "clamped conveyor": root
    streams S segments rightward and every hop forwards segment s one
    wave after receiving it, so all links are busy simultaneously and
    total time ≈ (S + n - 2) segment-hops instead of (n-1) full-payload
    hops — the explicit-DMA form of the reference's pipeline bcast
    (``coll_base_bcast.c`` pipeline/chain algorithms).

    The schedule is fully symmetric (SPMD-clean, no masked DMAs — a
    masked send would desync the per-op DMA rendezvous the interpreter
    emulates remote copies with): at wave j, the device at ring position
    r = (my-root) mod n forwards slot ``clamp(j-r, 0, S-1)``.  Below the
    clamp the payload is not-yet-valid filler that a valid write always
    overwrites before the receiver forwards that slot (position r first
    forwards slot s at wave s+r, having received the valid copy at wave
    s+r-1); above the clamp it is a benign same-bytes re-send.  The last
    device aims its writes at a sink row (``out[S]``) so the conveyor
    never races root's source rows.
    """
    jax, jnp, lax, pl, pltpu, cparams, barrier = _ring_kernels(n, axis, interpret)
    waves = nseg + n - 2

    # root arrives as a runtime SMEM scalar, not a cache key: the kernel
    # only uses it through rel = (my - root) mod n, so one compile
    # serves every root (round-robin-root workloads stay cache-hot)
    def kernel(root_ref, x_ref, out_ref, local_sem, send_sem, recv_sem):
        my = lax.axis_index(axis)
        right = lax.rem(my + 1, n)
        barrier(right, lax.rem(my - 1 + n, n))
        rel = lax.rem(my - root_ref[0] + n, n)
        # everyone seeds out with its local buffer: root's rows are the
        # payload, other devices' rows are pre-valid filler the conveyor
        # overwrites in time
        cp = pltpu.make_async_copy(x_ref, out_ref.at[pl.ds(0, nseg)],
                                   local_sem)
        cp.start()
        cp.wait()

        def wave(j, carry):
            slot = lax.clamp(0, j - rel, nseg - 1)
            # the ring's last device (rel n-1) writes into root's sink
            # row: root's real rows are the source of truth
            dst = lax.select(rel == n - 1, nseg, slot)
            # ONE recv semaphore for all waves (semaphore memory is a
            # small fixed chip resource — per-wave semaphores would
            # scale with payload size): safe because each sender's
            # wave-j+1 DMA starts only after its wave-j wait(), so
            # signals arrive in wave order and every wave moves the
            # same byte count; run-ahead just accumulates counts
            rdma = pltpu.make_async_remote_copy(
                src_ref=out_ref.at[slot], dst_ref=out_ref.at[dst],
                send_sem=send_sem, recv_sem=recv_sem,
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            rdma.wait()
            return carry

        lax.fori_loop(0, waves, wave, 0)

    def call(root, x):  # x: (nseg, S, 128) per device; root's rows back
        kw = {}
        cp = cparams(8)
        if cp is not None:
            kw["compiler_params"] = cp
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((nseg + 1, srows, 128),
                                           dtype_str),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())],
            interpret=interpret,
            **kw,
        )(root, x)
        return out[:nseg]

    return call


# -- public entry points (shard_map wrappers) ----------------------------
#
# Each wrapper resolves to a CACHED jitted program (lru keyed on mesh /
# shape / dtype / op / variant): building jax.jit around a fresh closure
# per call would retrace and recompile every time, turning each
# collective into compile time (jax.sharding.Mesh is hashable and
# equality-stable, so it can key the cache directly).

@functools.lru_cache(maxsize=256)
def _jit_right_permute(mesh, axis: str, payload_shape, dtype_str: str,
                       interpret: bool):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    fn = _build_right_permute(n, axis, (1,) + payload_shape, dtype_str,
                              interpret)

    def otpu_pallas_right_permute(t):
        return fn(t)

    return jax.jit(shard_map(
        otpu_pallas_right_permute, mesh=mesh, in_specs=P(axis),
        out_specs=P(axis), check_vma=False))


def right_permute(x, mesh, axis: str, interpret: Optional[bool] = None):
    """Rotate the leading (rank) axis by +1 via neighbor remote DMA —
    the PP activation-handoff primitive (``lax.ppermute`` twin)."""
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    if mesh.shape[axis] == 1:
        return x
    return _jit_right_permute(mesh, axis, tuple(x.shape[1:]),
                              str(x.dtype), interpret)(x)


@functools.lru_cache(maxsize=256)
def _jit_all_gather(mesh, axis: str, blk_shape, dtype_str: str,
                    interpret: bool, variant: str = "ring"):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    build = (_build_all_gather_bidi if variant == "bidi"
             else _build_all_gather)
    inner = build(n, axis, blk_shape, dtype_str, interpret)

    def otpu_pallas_all_gather(t):  # t: (1, *S)
        return inner(t[0])             # (n, *S)

    return jax.jit(shard_map(
        otpu_pallas_all_gather, mesh=mesh, in_specs=P(axis),
        out_specs=P(), check_vma=False))


def all_gather(x, mesh, axis: str, interpret: Optional[bool] = None,
               variant: str = "ring"):
    """(n, *S) sharded -> (n, *S) replicated via the DMA ring.

    ``variant="bidi"`` runs the bidirectional schedule (both ICI
    directions per step, ceil((n-1)/2) steps); n<=2 degenerates to the
    plain ring (one remote block — nothing to pair)."""
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    n = mesh.shape[axis]
    if n == 1:
        return x
    if n <= 2:
        variant = "ring"
    return _jit_all_gather(mesh, axis, tuple(x.shape[1:]), str(x.dtype),
                           interpret, variant)(x)


#: default VMEM window (elements) for the segmented kernels when the
#: caller does not size it
_DEFAULT_SEG_ELEMS = 131072


def _ragged_nchunks(max_rows: int, chunk: int, interpret: bool):
    """Trip-count rule shared by the ragged (counts-driven) kernels.

    The interpreter emulates every remote DMA as a cross-device
    rendezvous, so per-device op counts must be SYMMETRIC there:
    interpret mode always moves whole blocks (validating addressing
    and semaphore schedules); the dynamic ragged trip counts are a
    hardware feature, compile-proven by the AOT gate."""
    full = (max_rows + chunk - 1) // chunk

    def nchunks(rows):
        if interpret:
            return full
        return (rows + chunk - 1) // chunk

    return nchunks


def _rows_for(elems: int) -> int:
    """128-lane rows covering ``elems`` elements (≥1).  Every kernel
    payload is shaped (..., rows, 128): Mosaic tiles the trailing two
    dims, so the lane dim must be exactly 128 and all block/segment
    indexing rides untiled leading dims."""
    return max(1, -(-elems // 128))


def _seg_rows(rows: int, seg_elems: int | None) -> tuple[int, int]:
    """(window rows, padded block rows): the VMEM window is
    ``seg_elems`` rounded down to whole 128-lane rows, never exceeding
    the ring block; the block is rounded up to a whole number of
    windows."""
    srows = max(1, min((seg_elems or _DEFAULT_SEG_ELEMS) // 128, rows))
    return srows, -(-rows // srows) * srows


def _pad_value(op: str, dtype) -> float | int:
    """Neutral element used to pad the flattened payload to n equal ring
    blocks — must not perturb the fold, for any dtype (±inf is not a
    valid neutral for integers: use the dtype's extrema there).

    ml_dtypes types (bfloat16, fp8) report numpy kind 'V': treat
    anything np.finfo understands as floating (ml_dtypes registers its
    finfo), only genuinely integer kinds go to np.iinfo — the old
    kind=='f' test sent bf16 to iinfo and max/min bf16 rings raised
    "Invalid integer data type 'V'" (found by the round-5 randomized
    kernel sweep)."""
    dtype = np.dtype(dtype)
    if op == "sum":
        return 0
    if op == "prod":
        return 1
    if dtype.kind in "iu":
        lim = np.iinfo(dtype)
    else:
        import ml_dtypes

        lim = (np.finfo(dtype) if dtype.kind == "f"
               else ml_dtypes.finfo(dtype))
    return lim.min if op == "max" else lim.max


@functools.lru_cache(maxsize=256)
def _jit_reduce_scatter(mesh, axis: str, payload_shape, dtype_str: str,
                        op: str, interpret: bool, variant: str,
                        seg_elems):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    blk = int(np.prod(payload_shape)) if payload_shape else 1
    rows = _rows_for(blk)
    if variant == "seg":
        srows, rows = _seg_rows(rows, seg_elems)
        inner = _build_reduce_scatter_seg(n, axis, rows // srows, srows,
                                          dtype_str, interpret, op)
        shape_in = (n, rows // srows, srows, 128)
    elif variant == "wire16":
        if dtype_str not in ("float32", "f32"):
            raise ValueError(
                "wire16 compresses float32 payloads to bf16 wire "
                f"bytes; got dtype {dtype_str}")
        inner = _build_reduce_scatter(n, axis, rows, dtype_str,
                                      interpret, op, wire16=True)
        shape_in = (n, rows, 128)
    else:
        inner = _build_reduce_scatter(n, axis, rows, dtype_str,
                                      interpret, op)
        shape_in = (n, rows, 128)
    padded = rows * 128

    def otpu_pallas_reduce_scatter(t):  # t: (1, n, *S)
        r2 = t[0].reshape(n, blk)
        if padded != blk:
            r2 = jnp.pad(r2, ((0, 0), (0, padded - blk)),
                         constant_values=_pad_value(op, dtype_str))
        out = inner(r2.reshape(shape_in))
        return out.reshape(-1)[:blk].reshape((1,) + payload_shape)

    return jax.jit(shard_map(
        otpu_pallas_reduce_scatter, mesh=mesh, in_specs=P(axis),
        out_specs=P(axis), check_vma=False))


def reduce_scatter(x, mesh, axis: str, op: str = "sum",
                   interpret: Optional[bool] = None, variant: str = "fused",
                   seg_elems: int | None = None):
    """(n, n, *S) sharded on the leading rank axis -> (n, *S) sharded:
    rank i receives the reduction of everyone's block i via the DMA
    ring.  ``variant='seg'`` uses the HBM-resident segmented kernel
    (window of ``seg_elems``) for payloads too large for VMEM."""
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    payload_shape = tuple(x.shape[2:])
    if mesh.shape[axis] == 1:
        return x.reshape((1,) + payload_shape)
    return _jit_reduce_scatter(mesh, axis, payload_shape, str(x.dtype),
                               op, interpret, variant, seg_elems)(x)


def reduce_scatter_sum(x, mesh, axis: str,
                       interpret: Optional[bool] = None):
    return reduce_scatter(x, mesh, axis, "sum", interpret)


@functools.lru_cache(maxsize=256)
def _jit_all_reduce(mesh, axis: str, payload_shape, dtype_str: str,
                    op: str, interpret: bool, variant: str, seg_elems):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    size = int(np.prod(payload_shape)) if payload_shape else 1
    blk = -(-size // n)                # ceil
    rows = _rows_for(blk)
    if variant == "seg":
        srows, rows = _seg_rows(rows, seg_elems)
        inner = _build_all_reduce_seg(n, axis, rows // srows, srows,
                                      dtype_str, interpret, op)
        shape_in = (n, rows // srows, srows, 128)
    elif variant == "seg_bidi":
        hrows = -(-rows // 2)
        srows, hrows = _seg_rows(hrows, seg_elems)
        rows = 2 * hrows
        inner = _build_all_reduce_seg_bidi(n, axis, hrows // srows,
                                           srows, dtype_str, interpret,
                                           op)
        shape_in = (n, 2, hrows // srows, srows, 128)
    elif variant == "bidi":
        hrows = -(-rows // 2)          # even row split per direction
        rows = 2 * hrows
        inner = _build_all_reduce_bidi(n, axis, hrows, dtype_str,
                                       interpret, op)
        shape_in = (n, 2, hrows, 128)
    elif variant == "wire16":
        if dtype_str not in ("float32", "f32"):
            raise ValueError(
                "wire16 compresses float32 payloads to bf16 wire "
                f"bytes; got dtype {dtype_str}")
        raw = _build_all_reduce_wire16(n, axis, rows, interpret, op)
        inner = (lambda t: raw(t).astype("float32"))
        shape_in = (n, rows, 128)
    else:
        inner = _build_all_reduce(n, axis, rows, dtype_str, interpret,
                                  op)
        shape_in = (n, rows, 128)
    padded = rows * 128 * n

    def otpu_pallas_all_reduce(t):  # t: (1, *S)
        flat = t.reshape(-1)
        if padded != size:
            flat = jnp.pad(flat, (0, padded - size),
                           constant_values=_pad_value(op, dtype_str))
        out = inner(flat.reshape(shape_in))
        return out.reshape(-1)[:size].reshape(payload_shape)

    return jax.jit(shard_map(
        otpu_pallas_all_reduce, mesh=mesh, in_specs=P(axis),
        out_specs=P(), check_vma=False))


def all_reduce(x, mesh, axis: str, op: str = "sum",
               interpret: Optional[bool] = None, variant: str = "fused",
               seg_elems: int | None = None):
    """(n, *S) sharded -> (*S) replicated reduction via a ring kernel.

    The per-rank payload is flattened and neutrally-padded to n equal
    ring blocks outside the kernel (XLA fuses the pad/reshape into the
    surrounding program).  Variants:

    * ``'fused'``    — whole accumulator in VMEM (lowest latency, small).
    * ``'seg'``      — HBM accumulator + bounded VMEM window of
      ``seg_elems`` (large payloads; `coll_base_allreduce.c:618` twin).
    * ``'bidi'``     — both ICI directions carry half the payload each
      step (duplex links; halves per-step wire time).  VMEM-bounded.
    * ``'seg_bidi'`` — both at once: HBM-resident halves ride both
      directions concurrently, folds stream through the shared window
      (the large-payload duplex champion).
    * ``'wire16'``   — f32 accumulation, bf16 wire bytes: each step
      casts the outgoing partial to bf16 (half the ICI time) and folds
      at f32.  Results are bit-identical on every rank at bf16 value
      precision; absolute error ≤ ~n·2^-8·max|partial| (relative error
      unbounded under cancellation) — the opt-in gradient-compression
      trade; f32 payloads only.
    """
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    payload_shape = tuple(x.shape[1:])
    if mesh.shape[axis] == 1:
        return x.reshape(payload_shape)
    return _jit_all_reduce(mesh, axis, payload_shape, str(x.dtype), op,
                           interpret, variant, seg_elems)(x)


def all_reduce_sum(x, mesh, axis: str,
                   interpret: Optional[bool] = None):
    return all_reduce(x, mesh, axis, "sum", interpret)


@functools.lru_cache(maxsize=256)
def _jit_all_to_all(mesh, axis: str, blk_shape, dtype_str: str,
                    interpret: bool):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    inner = _build_all_to_all(n, axis, blk_shape, dtype_str, interpret)

    def otpu_pallas_all_to_all(t):  # t: (1, n, *S)
        return inner(t[0])[None]       # (1, n, *S): row = my received

    return jax.jit(shard_map(
        otpu_pallas_all_to_all, mesh=mesh, in_specs=P(axis),
        out_specs=P(axis), check_vma=False))


def all_to_all(x, mesh, axis: str, interpret: Optional[bool] = None):
    """(n, n, *S) sharded on the leading rank axis: rank i's block j
    moves to rank j's slot i (``x[i, j] -> out[j, i]``, the coll/xla
    ``alltoall_array`` convention) via direct per-peer remote DMA."""
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    n = mesh.shape[axis]
    if x.ndim < 2 or x.shape[0] != n or x.shape[1] != n:
        # the kernel indexes n blocks per rank: anything else would be
        # an out-of-bounds remote DMA, not a reshape-able layout
        raise ValueError(
            f"all_to_all needs a ({n}, {n}, *S) array on this mesh, "
            f"got {tuple(x.shape)}")
    if n == 1:
        return x
    return _jit_all_to_all(mesh, axis, tuple(x.shape[2:]), str(x.dtype),
                           interpret)(x)


@functools.lru_cache(maxsize=256)
def _jit_all_gather_v(mesh, axis: str, max_rows: int, width: int,
                      chunk: int, dtype_str: str, interpret: bool):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    inner = _build_all_gather_v(n, axis, max_rows, width, chunk,
                                dtype_str, interpret)

    def otpu_pallas_all_gather_v(c, t):  # c: (n,) replicated; t: (1, R, W)
        return inner(c, t[0])          # (n, R, W) replicated

    return jax.jit(shard_map(
        otpu_pallas_all_gather_v, mesh=mesh, in_specs=(P(), P(axis)),
        out_specs=P(), check_vma=False))


def all_gather_v(x, counts, mesh, axis: str, chunk_rows: int = 8,
                 interpret: Optional[bool] = None):
    """Ragged all-gather (true allgatherv): ``x`` is (n, R, W) sharded
    on the leading rank axis — rank i's block carries ``counts[i]``
    valid rows (≤ R) — and every rank receives (n, R, W) with
    ``out[i, :counts[i]]`` valid.  ``counts`` is a runtime operand:
    one compile serves every raggedness.  Wire bytes per block are
    ceil(count/chunk_rows)*chunk_rows rows where the padded all_gather
    always moves R.  W must be a multiple of 128 lanes."""
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    jax, jnp, lax, pl, pltpu = _mods()

    n = mesh.shape[axis]
    if x.ndim != 3 or x.shape[0] != n:
        raise ValueError(
            f"all_gather_v needs a ({n}, R, W) array on this mesh, "
            f"got {tuple(x.shape)}")
    if x.shape[2] % 128 != 0:
        raise ValueError(
            f"all_gather_v row width must be a multiple of 128 lanes, "
            f"got {x.shape[2]} (pad the feature dim)")
    if n == 1:
        return x
    chunk_rows = int(chunk_rows)
    R = int(x.shape[1])
    # clamp to the block size (see all_to_all_v: an oversized count
    # means out-of-bounds remote DMA on hardware)
    counts = jnp.clip(jnp.asarray(counts, jnp.int32), 0, R)
    if counts.shape != (n,):
        raise ValueError(
            f"all_gather_v needs ({n},) counts, got "
            f"{tuple(counts.shape)}")
    if R == 0 or x.shape[2] == 0:
        # zero-row / zero-width slab: every count clamps to 0 valid
        # rows, so the gather is a no-op.  Return without building a
        # kernel — an empty block has no (chunk, W) window to slice
        # (interpret-mode DMA discharge rejects the slice statically)
        return x
    Rp = -(-R // chunk_rows) * chunk_rows
    if Rp != R:
        x = jnp.pad(x, ((0, 0), (0, Rp - R), (0, 0)))
    fn = _jit_all_gather_v(mesh, axis, Rp, int(x.shape[2]), chunk_rows,
                           str(x.dtype), interpret)
    out = fn(counts, x)
    return out[:, :R] if Rp != R else out


@functools.lru_cache(maxsize=256)
def _jit_all_to_all_v(mesh, axis: str, max_rows: int, width: int,
                      chunk: int, dtype_str: str, interpret: bool):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    inner = _build_all_to_all_v(n, axis, max_rows, width, chunk,
                                dtype_str, interpret)

    # c: (n, n) replicated; t: (1, n, R, W)
    def otpu_pallas_all_to_all_v(c, t):
        return inner(c, t[0])[None]

    return jax.jit(shard_map(
        otpu_pallas_all_to_all_v, mesh=mesh, in_specs=(P(), P(axis)),
        out_specs=P(axis), check_vma=False))


def all_to_all_v(x, counts, mesh, axis: str, chunk_rows: int = 8,
                 interpret: Optional[bool] = None):
    """Ragged all-to-all (true alltoallv): ``x`` is (n, n, R, W)
    sharded on the leading rank axis — rank i's block j carries
    ``counts[i, j]`` valid rows (≤ R) for rank j — and rank j receives
    them in ``out[j, i, :counts[i, j]]`` (the ``alltoall_array``
    row-is-my-received convention).  Rows past the count are
    unspecified.

    ``counts`` is a runtime (n, n) int32 operand, NOT a compile-time
    constant: one compiled program serves every MoE routing outcome.
    Wire bytes per pair are ceil(count/chunk_rows)*chunk_rows rows —
    ≤1.2x the ideal ragged byte count for real dispatch sizes, where
    the padded ``all_to_all`` moves the full R regardless.  W must be
    a multiple of 128 lanes (MoE hidden dims are)."""
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    jax, jnp, lax, pl, pltpu = _mods()

    n = mesh.shape[axis]
    if x.ndim != 4 or x.shape[0] != n or x.shape[1] != n:
        raise ValueError(
            f"all_to_all_v needs a ({n}, {n}, R, W) array on this "
            f"mesh, got {tuple(x.shape)}")
    if x.shape[3] % 128 != 0:
        raise ValueError(
            f"all_to_all_v row width must be a multiple of 128 lanes, "
            f"got {x.shape[3]} (pad the feature dim)")
    if n == 1:
        return x
    chunk_rows = int(chunk_rows)
    R = int(x.shape[2])
    # clamp to the block size: a count beyond R would drive the chunk
    # loops past the block on hardware — out-of-bounds remote DMA into
    # the neighbor's adjacent slot, not an error
    counts = jnp.clip(jnp.asarray(counts, jnp.int32), 0, R)
    if counts.shape != (n, n):
        raise ValueError(
            f"all_to_all_v needs an ({n}, {n}) counts table, got "
            f"{tuple(counts.shape)}")
    if R == 0 or x.shape[3] == 0:
        # zero-row / zero-width slab: every count clamps to 0 valid
        # rows, so the exchange is a no-op.  Return without building a
        # kernel — an empty block has no (chunk, W) window to slice
        # (interpret-mode DMA discharge rejects the slice statically)
        return x
    # the kernel slices fixed (chunk, W) windows: the row dim must be a
    # whole number of chunks or the last window overruns the buffer
    Rp = -(-R // chunk_rows) * chunk_rows
    if Rp != R:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Rp - R), (0, 0)))
    fn = _jit_all_to_all_v(mesh, axis, Rp, int(x.shape[3]), chunk_rows,
                           str(x.dtype), interpret)
    out = fn(counts, x)
    return out[:, :, :R] if Rp != R else out


@functools.lru_cache(maxsize=256)
def _jit_all_reduce_torus(mesh, axes, payload_shape, dtype_str: str,
                          op: str, interpret: bool):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    a0, a1 = axes
    n0, n1 = mesh.shape[a0], mesh.shape[a1]
    size = int(np.prod(payload_shape)) if payload_shape else 1
    rows0 = _rows_for(-(-size // n0))
    size1 = rows0 * 128                # phase-1 block, in elements
    rows1 = _rows_for(-(-size1 // n1))
    # the kernels run over a FLATTENED 1-D mesh with sub-ring index
    # arithmetic ((i0, i1) <-> i0*n1+i1): scalar LOGICAL device ids
    # stay interpreter-runnable and lower identically on hardware.
    # Transpose the device grid into ``axes`` order first — the sub-ring
    # arithmetic assumes a0-major linearization, and axes=("y","x") on
    # an ("x","y") mesh would otherwise still sum correctly but walk
    # non-neighbor ICI links
    flat_mesh = _torus_flat_mesh(mesh, a0, a1)
    rs0 = _build_reduce_scatter(n0, "_t", rows0, dtype_str, interpret,
                                op, sub=(n0, n1, 0))
    ar1 = _build_all_reduce(n1, "_t", rows1, dtype_str, interpret, op,
                            sub=(n0, n1, 1))
    ag0 = _build_all_gather(n0, "_t", (rows0, 128), dtype_str,
                            interpret, sub=(n0, n1, 0))
    pad = _pad_value(op, dtype_str)

    def otpu_pallas_all_reduce_torus(t):  # t: (1, *S)
        flat = t.reshape(-1)
        if rows0 * 128 * n0 != size:
            flat = jnp.pad(flat, (0, rows0 * 128 * n0 - size),
                           constant_values=pad)
        part = rs0(flat.reshape(n0, rows0, 128))  # (rows0, 128) over a0
        pflat = part.reshape(-1)
        if rows1 * 128 * n1 != size1:
            pflat = jnp.pad(pflat, (0, rows1 * 128 * n1 - size1),
                            constant_values=pad)
        red = ar1(pflat.reshape(n1, rows1, 128))  # over a1
        red = red.reshape(-1)[:size1].reshape(rows0, 128)
        full = ag0(red)                           # (n0, rows0, 128)
        return full.reshape(-1)[:size].reshape(payload_shape)

    return jax.jit(shard_map(
        otpu_pallas_all_reduce_torus, mesh=flat_mesh, in_specs=P("_t"),
        out_specs=P(), check_vma=False))


def all_reduce_torus(x, mesh, axes=("x", "y"), op: str = "sum",
                     interpret: Optional[bool] = None):
    """(n0, n1, *S) sharded over both torus axes -> (*S) replicated
    reduction: reduce-scatter rings along ``axes[0]``, all-reduce rings
    along ``axes[1]`` on the scattered blocks, all-gather rings along
    ``axes[0]`` back.  Per-step wire time scales with the axis lengths
    (n0 + n1 ring steps on 1/n0-sized blocks) rather than one n0*n1
    ring, and every link of BOTH torus dimensions carries traffic — the
    2D schedule the reference reaches for with coll/han's hierarchical
    composition (``coll_han``), expressed as three explicit-DMA phases.
    """
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    axes = tuple(axes)
    payload_shape = tuple(x.shape[2:])
    n0, n1 = mesh.shape[axes[0]], mesh.shape[axes[1]]
    if n0 == 1 or n1 == 1:
        # a degenerate torus axis is a plain 1-D ring (a single pod
        # row/column): the zero-sized (n-1, blk) recv scratch of an
        # n=1 sub-ring cannot build
        flat_mesh = _torus_flat_mesh(mesh, *axes)
        return all_reduce(x.reshape((n0 * n1,) + payload_shape),
                          flat_mesh, "_t", op, interpret)
    fn = _jit_all_reduce_torus(mesh, axes, payload_shape,
                               str(x.dtype), op, interpret)
    return fn(x.reshape((n0 * n1,) + payload_shape))


def _torus_flat_mesh(mesh, a0, a1):
    """Flatten the torus into a0-major order (see _jit_all_reduce_torus:
    the sub-ring arithmetic assumes (i0, i1) <-> i0*n1+i1, and the
    transpose keeps sub-rings on physical ICI neighbors)."""
    from jax.sharding import Mesh

    devs = np.asarray(mesh.devices)
    order = tuple(mesh.axis_names.index(a) for a in (a0, a1))
    devs = np.transpose(devs, order + tuple(
        i for i in range(devs.ndim) if i not in order))
    return Mesh(devs.reshape(-1), ("_t",))


@functools.lru_cache(maxsize=32)
def _jit_reduce_scatter_torus(mesh, axes, payload_shape, dtype_str: str,
                              op: str, interpret: bool):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    a0, a1 = axes
    n0, n1 = mesh.shape[a0], mesh.shape[a1]
    N = n0 * n1
    blk = int(np.prod(payload_shape)) if payload_shape else 1
    rb = _rows_for(blk)
    flat_mesh = _torus_flat_mesh(mesh, a0, a1)
    # phase 1: scatter-reduce n0 super-blocks (n1 blocks each) down the
    # columns; phase 2: scatter-reduce the n1 surviving partials along
    # the row — device (i0, i1) ends with global block i0*n1+i1 fully
    # reduced.  Block boundaries stay row-aligned because each block is
    # padded to rb whole rows BEFORE the phase-1 stacking.
    # distinct collective_ids: two same-id kernels in one program
    # would share one Mosaic barrier semaphore, and a fast device
    # entering phase 2 could release a neighbor still at its phase-1
    # entry barrier (the hazard the _ring_kernels barrier comment
    # documents) — same discipline as _jit_all_reduce_torus's (4,3,2)
    rs0 = _build_reduce_scatter(n0, "_t", n1 * rb, dtype_str, interpret,
                                op, sub=(n0, n1, 0))
    rs1 = _build_reduce_scatter(n1, "_t", rb, dtype_str, interpret, op,
                                sub=(n0, n1, 1), cid=17)
    padded = rb * 128

    def otpu_pallas_reduce_scatter_torus(t):  # t: (1, N, *S)
        r2 = t[0].reshape(N, blk)
        if padded != blk:
            r2 = jnp.pad(r2, ((0, 0), (0, padded - blk)),
                         constant_values=_pad_value(op, dtype_str))
        p1 = rs0(r2.reshape(n0, n1 * rb, 128))   # (n1*rb, 128)
        p2 = rs1(p1.reshape(n1, rb, 128))        # (rb, 128)
        return p2.reshape(-1)[:blk].reshape((1,) + payload_shape)

    return jax.jit(shard_map(
        otpu_pallas_reduce_scatter_torus, mesh=flat_mesh, in_specs=P("_t"),
        out_specs=P("_t"), check_vma=False))


def reduce_scatter_torus(x, mesh, axes=("x", "y"), op: str = "sum",
                         interpret: Optional[bool] = None):
    """(N, N, *S) sharded -> (N, *S) sharded over the torus, N=n0*n1:
    two scatter-reduce phases (columns then rows), each ring walking
    physical ICI neighbors of its own torus dimension — the decomposed
    form of ``all_reduce_torus``'s first phase, for callers that want
    the scattered result (TP gradient buckets, han-style hierarchies).
    """
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    axes = tuple(axes)
    payload_shape = tuple(x.shape[2:])
    n0, n1 = mesh.shape[axes[0]], mesh.shape[axes[1]]
    if n0 == 1 or n1 == 1:             # degenerate: plain 1-D ring
        flat_mesh = _torus_flat_mesh(mesh, *axes)
        return reduce_scatter(
            x.reshape((n0 * n1, n0 * n1) + payload_shape), flat_mesh,
            "_t", op, interpret)
    fn = _jit_reduce_scatter_torus(mesh, axes, payload_shape,
                                   str(x.dtype), op, interpret)
    return fn(x.reshape((n0 * n1, n0 * n1) + payload_shape))


@functools.lru_cache(maxsize=32)
def _jit_all_gather_torus(mesh, axes, blk_shape, dtype_str: str,
                          interpret: bool):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    a0, a1 = axes
    n0, n1 = mesh.shape[a0], mesh.shape[a1]
    N = n0 * n1
    blk = int(np.prod(blk_shape)) if blk_shape else 1
    rb = _rows_for(blk)
    flat_mesh = _torus_flat_mesh(mesh, a0, a1)
    # phase 1: gather the row's n1 blocks; phase 2: gather the n0
    # super-blocks down the column — (n0, n1) row-major == flat id
    # distinct collective_ids per phase (see _jit_reduce_scatter_torus)
    ag1 = _build_all_gather(n1, "_t", (rb, 128), dtype_str, interpret,
                            sub=(n0, n1, 1))
    ag0 = _build_all_gather(n0, "_t", (n1 * rb, 128), dtype_str,
                            interpret, sub=(n0, n1, 0), cid=18)

    def otpu_pallas_all_gather_torus(t):  # t: (1, *S)
        flat = t[0].reshape(-1)
        if rb * 128 != blk:
            flat = jnp.pad(flat, (0, rb * 128 - blk))
        row = ag1(flat.reshape(rb, 128))          # (n1, rb, 128)
        full = ag0(row.reshape(n1 * rb, 128))     # (n0, n1*rb, 128)
        return full.reshape(N, rb * 128)[:, :blk].reshape(
            (N,) + blk_shape)

    return jax.jit(shard_map(
        otpu_pallas_all_gather_torus, mesh=flat_mesh, in_specs=P("_t"),
        out_specs=P(), check_vma=False))


def all_gather_torus(x, mesh, axes=("x", "y"),
                     interpret: Optional[bool] = None):
    """(N, *S) sharded over the torus -> (N, *S) replicated: row rings
    then column rings, each on its own ICI dimension — (n1-1) + (n0-1)
    steps instead of the 1-D ring's N-1."""
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    axes = tuple(axes)
    blk_shape = tuple(x.shape[1:])
    n0, n1 = mesh.shape[axes[0]], mesh.shape[axes[1]]
    if n0 == 1 or n1 == 1:
        flat_mesh = _torus_flat_mesh(mesh, *axes)
        return all_gather(x, flat_mesh, "_t", interpret)
    fn = _jit_all_gather_torus(mesh, axes, blk_shape, str(x.dtype),
                               interpret)
    return fn(x)


@functools.lru_cache(maxsize=256)
def _jit_bcast(mesh, axis: str, payload_shape, dtype_str: str,
               interpret: bool, seg_elems: int):
    jax, jnp, lax, pl, pltpu = _mods()
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    size = int(np.prod(payload_shape)) if payload_shape else 1
    srows = max(1, min(seg_elems // 128, _rows_for(size)))
    nseg = -(-_rows_for(size) // srows)
    padded = nseg * srows * 128
    inner = _build_bcast(n, axis, nseg, srows, dtype_str, interpret)

    def otpu_pallas_bcast(r, t):  # r: (1,) int32; t: (1, *S)
        flat = t.reshape(-1)
        if padded != size:
            flat = jnp.pad(flat, (0, padded - size))
        out = inner(r, flat.reshape(nseg, srows, 128))  # root's rows
        return out.reshape(-1)[:size].reshape((1,) + payload_shape)

    return jax.jit(shard_map(
        otpu_pallas_bcast, mesh=mesh, in_specs=(P(), P(axis)),
        out_specs=P(axis), check_vma=False))


def bcast(x, mesh, axis: str, root: int = 0, interpret: Optional[bool] = None,
          seg_elems: int = 65536):
    """(n, *S) sharded -> (n, *S) with every row equal to root's row,
    via the pipelined segmented ring (time ≈ (S + n - 2) segment-hops).
    ``root`` is a runtime operand — every root shares one compile."""
    if interpret is None:
        interpret = pallas_interpret(mesh.devices.flat)
    jax, jnp, lax, pl, pltpu = _mods()

    n = mesh.shape[axis]
    if n == 1:
        return x
    fn = _jit_bcast(mesh, axis, tuple(x.shape[1:]), str(x.dtype),
                    interpret, int(seg_elems))
    return fn(jnp.asarray([int(root) % n], dtype=jnp.int32), x)
