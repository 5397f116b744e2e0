"""AOT-lower every coll/pallas kernel against a *real* TPU topology.

The explicit-DMA collectives (``ops/pallas_collectives.py``) and the
fused collective-matmul forms (``ops/pallas_overlap.py``) run under the
Pallas interpreter in CI, which validates the schedules but never shows
them to the Mosaic TPU compiler.  JAX's ahead-of-time path closes that
gap without hardware attached: ``jax.experimental.topologies`` builds a
compile-only device set for a named TPU topology and ``jit(...).lower()
.compile()`` then runs the full XLA:TPU + Mosaic pipeline — semaphore
allocation, VMEM budgeting, ``collective_id`` plumbing, remote-DMA
lowering — exactly as a live pod would, minus execution.

This is the compile-contract analog of the reference's hardware-proven
transport layer (``opal/mca/btl/btl.h:878-1078``): a kernel that fails
here would fail on a real v5e slice.

Run: ``python -m ompi_tpu.tools.pallas_aot`` (CPU client; no TPU needed;
``--out FILE`` also writes the JSON it prints; no record of a run is
committed, ``tests/test_pallas_aot.py`` compiles its own rows).
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

DEFAULT_TOPOLOGY = "v5e:2x4"


def build_meshes(topology: str = DEFAULT_TOPOLOGY):
    """(mesh1d, mesh2d) over compile-only devices of ``topology``.

    ``mesh2d`` uses the topology's natural RxC shape (e.g. 2x4 for
    ``v5e:2x4``) so the torus kernel's sub-rings follow physical ICI
    links; ``mesh1d`` flattens the same devices for the ring kernels.
    """
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(topology, "tpu")
    devs = np.asarray(topo.devices)
    n = devs.size
    mesh1d = Mesh(devs.reshape(n), ("x",))
    rows, cols = topology.split(":")[1].split("x")[:2] if ":" in topology else (1, n)
    try:
        shape2 = (int(rows), int(cols))
    except Exception:
        shape2 = (1, n)
    mesh2d = None
    if shape2[0] * shape2[1] == n and shape2[0] > 1 and shape2[1] > 1:
        mesh2d = Mesh(devs.reshape(shape2), ("x", "y"))
    return mesh1d, mesh2d


def _sds(shape, dtype, mesh, spec):
    import jax
    from jax.sharding import NamedSharding

    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def cases(mesh1d, mesh2d):
    """Yield (name, build) pairs; build() -> (jitted_fn, args tuple of
    ShapeDtypeStruct).  Shapes are small but structurally honest: every
    kernel takes its multi-step ring/segment path."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ompi_tpu.ops import pallas_collectives as pc
    from ompi_tpu.ops import pallas_overlap as po

    n = mesh1d.shape["x"]
    f32 = jnp.float32
    bf16 = jnp.bfloat16
    PAY = 16384                    # flat per-rank payload (64 KiB f32)
    SEG = 4096                     # forces 4 ring segments

    def ring_arg(shape, dtype=f32, mesh=mesh1d):
        return _sds((n,) + shape, dtype, mesh, P("x"))

    out = []

    def case(name, fn):
        out.append((name, fn))

    case("right_permute", lambda: (
        pc._jit_right_permute(mesh1d, "x", (8, 128), "float32", False),
        (ring_arg((8, 128)),)))
    case("all_gather", lambda: (
        pc._jit_all_gather(mesh1d, "x", (8, 128), "float32", False,
                           "ring"),
        (ring_arg((8, 128)),)))
    case("all_gather_bidi", lambda: (
        pc._jit_all_gather(mesh1d, "x", (8, 128), "float32", False,
                           "bidi"),
        (ring_arg((8, 128)),)))
    case("reduce_scatter_fused", lambda: (
        pc._jit_reduce_scatter(mesh1d, "x", (PAY,), "float32", "sum",
                               False, "fused", None),
        (_sds((n, n, PAY), f32, mesh1d, P("x")),)))
    case("reduce_scatter_seg", lambda: (
        pc._jit_reduce_scatter(mesh1d, "x", (PAY,), "float32", "sum",
                               False, "seg", SEG),
        (_sds((n, n, PAY), f32, mesh1d, P("x")),)))
    for variant in ("fused", "seg", "bidi", "seg_bidi"):
        case(f"all_reduce_{variant}", lambda v=variant: (
            pc._jit_all_reduce(mesh1d, "x", (n * PAY,), "float32",
                               "sum", False, v,
                               SEG if "seg" in v else None),
            (ring_arg((n * PAY,)),)))
    case("all_reduce_max", lambda: (
        pc._jit_all_reduce(mesh1d, "x", (n * PAY,), "float32", "max",
                           False, "fused", None),
        (ring_arg((n * PAY,)),)))
    case("all_reduce_wire16", lambda: (
        pc._jit_all_reduce(mesh1d, "x", (n * PAY,), "float32", "sum",
                           False, "wire16", None),
        (ring_arg((n * PAY,)),)))
    case("reduce_scatter_wire16", lambda: (
        pc._jit_reduce_scatter(mesh1d, "x", (PAY,), "float32", "sum",
                               False, "wire16", None),
        (_sds((n, n, PAY), f32, mesh1d, P("x")),)))
    case("all_to_all", lambda: (
        pc._jit_all_to_all(mesh1d, "x", (8, 128), "float32", False),
        (_sds((n, n, 8, 128), f32, mesh1d, P("x")),)))
    case("all_to_all_v_ragged", lambda: (
        pc._jit_all_to_all_v(mesh1d, "x", 64, 256, 8, "float32", False),
        (_sds((n, n), jnp.int32, mesh1d, P()),
         _sds((n, n, 64, 256), f32, mesh1d, P("x")))))
    case("all_gather_v_ragged", lambda: (
        pc._jit_all_gather_v(mesh1d, "x", 64, 256, 8, "float32",
                             False),
        (_sds((n,), jnp.int32, mesh1d, P()),
         _sds((n, 64, 256), f32, mesh1d, P("x")))))
    case("bcast", lambda: (
        pc._jit_bcast(mesh1d, "x", (PAY,), "float32", False, SEG),
        (_sds((1,), jnp.int32, mesh1d, P()), ring_arg((PAY,)))))
    if mesh2d is not None:
        import numpy as np
        from jax.sharding import Mesh

        n0, n1 = mesh2d.shape["x"], mesh2d.shape["y"]
        # the torus jit runs over the same devices flattened (its body
        # does sub-ring index arithmetic); the arg must be sharded on
        # that flat mesh, mirroring all_reduce_torus()'s reshape
        flat = Mesh(np.asarray(mesh2d.devices).reshape(-1), ("_t",))
        case("all_reduce_torus", lambda: (
            pc._jit_all_reduce_torus(mesh2d, ("x", "y"),
                                     (n0 * n1 * PAY,), "float32",
                                     "sum", False),
            (_sds((n0 * n1, n0 * n1 * PAY), f32, flat, P("_t")),)))
        N2 = n0 * n1
        case("reduce_scatter_torus", lambda: (
            pc._jit_reduce_scatter_torus(mesh2d, ("x", "y"), (PAY,),
                                         "float32", "sum", False),
            (_sds((N2, N2, PAY), f32, flat, P("_t")),)))
        case("all_gather_torus", lambda: (
            pc._jit_all_gather_torus(mesh2d, ("x", "y"), (PAY,),
                                     "float32", False),
            (_sds((N2, PAY), f32, flat, P("_t")),)))
    m, k_loc, n_out = 256, 256, 256
    case("matmul_allreduce", lambda: (
        po._jit_matmul_allreduce(mesh1d, "x", m, k_loc, n_out,
                                 "bfloat16", False),
        (_sds((n, m, k_loc), bf16, mesh1d, P("x")),
         _sds((n, k_loc, n_out), bf16, mesh1d, P("x")))))
    case("matmul_reduce_scatter", lambda: (
        po._jit_matmul_reduce_scatter(mesh1d, "x", m, k_loc, n_out,
                                      "bfloat16", False),
        (_sds((n, m, k_loc), bf16, mesh1d, P("x")),
         _sds((n, k_loc, n_out), bf16, mesh1d, P("x")))))

    # -- production-size cases: VMEM budgets and semaphore pressure are
    # shape-dependent, so tiny-shape compiles alone would under-prove
    # the contract.  Sizes mirror the sweep's upper rows (64MB payloads
    # per device; TP-layer-scale fused GEMM).
    BIG = (64 << 20) // 4                  # 64MB f32 per device
    case("big_all_reduce_seg", lambda: (
        pc._jit_all_reduce(mesh1d, "x", (BIG,), "float32", "sum",
                           False, "seg", None),
        (ring_arg((BIG,)),)))
    case("big_all_reduce_seg_bidi", lambda: (
        pc._jit_all_reduce(mesh1d, "x", (BIG,), "float32", "sum",
                           False, "seg_bidi", None),
        (ring_arg((BIG,)),)))
    case("big_all_reduce_fused_4mb", lambda: (
        pc._jit_all_reduce(mesh1d, "x", ((4 << 20) // 4,), "float32",
                           "sum", False, "fused", None),
        (ring_arg(((4 << 20) // 4,)),)))
    case("big_matmul_allreduce_1k", lambda: (
        po._jit_matmul_allreduce(mesh1d, "x", 1024, 1024, 1024,
                                 "bfloat16", False),
        (_sds((n, 1024, 1024), bf16, mesh1d, P("x")),
         _sds((n, 1024, 1024), bf16, mesh1d, P("x")))))
    case("big_all_to_all_v", lambda: (
        pc._jit_all_to_all_v(mesh1d, "x", 2048, 1024, 8, "float32",
                             False),
        (_sds((n, n), jnp.int32, mesh1d, P()),
         _sds((n, n, 2048, 1024), f32, mesh1d, P("x")))))

    # -- single-chip hot kernels: the MFU path must be Mosaic-proven
    # too (causal attention's two kernels + the VPU reduction kernels
    # behind mca/op).  They take no mesh to read the platform from, so
    # interpret=False is passed EXPLICITLY (a static jit-cache-key
    # ingredient: no cached interpreter trace is served).
    import numpy as _np
    from jax.sharding import Mesh as _Mesh

    one = _Mesh(_np.asarray(mesh1d.devices).reshape(-1)[:1], ("one",))

    from ompi_tpu.ops import flash_attention as fa
    from ompi_tpu.ops import pallas_reduce as pr

    # the OLMoE train step's attention (``parallel/model
    # .causal_flash_attention``) at the benchmark cell's shape (2 x 16
    # heads x 4,096 x 128, bfloat16, causal) through the model's own
    # entry, which where Mosaic compiles is one call of
    # ``flash_causal_forward`` (its own cases are below)
    def olmoe_attention():
        from ompi_tpu.parallel import causal

        qkv = _sds((2, 16, 4096, 128), bf16, one, P())
        return jax.jit(lambda q, k, v: causal._causal_fwd_blocks(
            q, k, v, 1024, False)), (qkv, qkv, qkv)

    case("olmoe_causal_attention_4k", olmoe_attention)
    # attention's backward (``causal._causal_bwd``): the fused
    # block-pair kernel at the two cells' shapes (the arrays come whole
    # and a scalar-prefetch operand picks the pair, so the plain and the
    # diagonal pair are one compiled kernel), and the two walks over the
    # pairs, OLMoE's 10 unrolled and JoyAI's 36 by ``lax.scan``: the
    # accumulators must alias through every call (no ``copy``, no
    # ``dynamic-update-slice`` of their size beside it)
    # ``n_kv`` key-value heads under the ``h`` query heads: k, v and the
    # accumulators dk, dv come with the model's own (the kernels' index
    # maps read a group's shared head; nothing is repeated)
    def attn_bwd_args(b, h, s, d, hv, n_kv=None):
        n_kv = n_kv or h
        wide = lambda w, dt, n=h: _sds((b, n, s, w), dt, one, P())
        row = _sds((b, h, s), jnp.float32, one, P())
        return (wide(d, bf16), wide(d, bf16, n_kv), wide(hv, bf16, n_kv),
                wide(hv, bf16), row, row)

    def attn_block_backward(b, h, s, d, hv, n_kv=None, **window):
        n_kv = n_kv or h
        wide = lambda w, n=h: _sds((b, n, s, w), jnp.float32, one, P())
        return fa.attn_block_backward, (
            (_sds((2,), jnp.int32, one, P()),)
            + attn_bwd_args(b, h, s, d, hv, n_kv)
            + (wide(d), wide(d, n_kv), wide(hv, n_kv))), {
                "block": 1024, "interpret": False, **window}

    def attn_backward_walk(b, h, s, d, hv, n_kv=None, window=None, **bd):
        from ompi_tpu.parallel import causal

        q, k, v, _, lse, _ = attn_bwd_args(b, h, s, d, hv, n_kv)
        o = _sds((b, h, s, hv), jnp.float32, one, P())  # and its cotangent
        bwd = causal._causal_bwd
        return jax.jit(lambda q, k, v, o, lse, do: bwd(
            1024, False, window, (q, k, v, o, lse), do, **bd)), (
                q, k, v, o, lse, o)

    # attention's forward (``causal._causal_fwd_blocks`` where Mosaic
    # compiles): one call a layer, q, k and v whole, the blocks through
    # the index maps, the softmax state in VMEM scratch
    def flash_causal_forward(b, h, s, d, hv, n_kv=None, **window):
        q, k, v = attn_bwd_args(b, h, s, d, hv, n_kv)[:3]
        return fa.flash_causal_forward, (q, k, v), {
            "block": 1024, "interpret": False, **window}

    case("olmoe_flash_causal_forward",
         lambda: flash_causal_forward(2, 16, 4096, 128, 128))
    case("joyai_flash_causal_forward",
         lambda: flash_causal_forward(1, 32, 8192, 192, 128))
    # LFM2's: 2 x 32 query heads on 8 key-value heads x 8,192 at a head
    # width of 64 (half the MXU's contraction depth, half a tile's lanes)
    case("lfm2_flash_causal_forward",
         lambda: flash_causal_forward(2, 32, 8192, 64, 64, 8))
    case("olmoe_attn_block_backward_1k",
         lambda: attn_block_backward(2, 16, 4096, 128, 128))
    case("joyai_attn_block_backward_1k",
         lambda: attn_block_backward(1, 32, 8192, 192, 128))
    case("olmoe_attn_backward_walk_4k",
         lambda: attn_backward_walk(2, 16, 4096, 128, 128))
    case("joyai_attn_backward_walk_8k",
         lambda: attn_backward_walk(1, 32, 8192, 192, 128))
    # Xing4.0's: latent attention's 192 / 128 over the 16 heads of 32 held,
    # 4,096 positions, the scores' scale YaRN's (a static argument)
    yarn_scale = (0.1 * math.log(64.0) + 1.0) ** 2 / math.sqrt(192.0)
    case("xing_flash_causal_forward",
         lambda: flash_causal_forward(1, 16, 4096, 192, 128,
                                      scale=yarn_scale))
    case("xing_attn_block_backward_1k",
         lambda: attn_block_backward(1, 16, 4096, 192, 128,
                                     scale=yarn_scale))
    case("lfm2_attn_block_backward_1k",
         lambda: attn_block_backward(2, 32, 8192, 64, 64, 8))
    case("lfm2_attn_backward_walk_8k",
         lambda: attn_backward_walk(2, 32, 8192, 64, 64, 8))
    # Qwen3-Next's: 16 query heads on 2 key-value heads x 16,384 at a
    # head width of 256 (twice the MXU's contraction depth; a tile of
    # 1,024 holds twice the operands of a 128-wide one; 16 blocks)
    case("qwen3next_flash_causal_forward",
         lambda: flash_causal_forward(1, 16, 16384, 256, 256, 2))
    case("qwen3next_attn_block_backward_1k",
         lambda: attn_block_backward(1, 16, 16384, 256, 256, 2))
    case("qwen3next_attn_backward_walk_16k",
         lambda: attn_backward_walk(1, 16, 16384, 256, 256, 2))
    # SmallThinker's window layers: 28 query heads on 4 key-value heads
    # (7 a group) x 16,384 at a head width of 128 under a window of 4,096:
    # the forward's grid holds 5 kv tiles a q tile where a full layer's
    # holds 16, the backward's one loop walks 70 pairs where it walks 136
    case("smallthinker_flash_window_forward",
         lambda: flash_causal_forward(1, 28, 16384, 128, 128, 4,
                                      window=4096))
    case("smallthinker_attn_window_backward",
         lambda: attn_backward_walk(1, 28, 16384, 128, 128, 4, window=4096))
    # SDAR's layers: 32 query heads on 4 key-value heads x 16,384 rows (a
    # noisy and a clean copy of 8,192 tokens) at a head width of 128 under
    # block diffusion's mask in blocks of 4: the forward's grid holds 9 kv
    # tiles a q tile, the backward's one loop walks 80 pairs of 136
    case("sdar_flash_bd_forward",
         lambda: flash_causal_forward(1, 32, 16384, 128, 128, 4, bd=4))
    case("sdar_attn_bd_backward",
         lambda: attn_backward_walk(1, 32, 16384, 128, 128, 4, bd=4))
    # and q's and k's way to those kernels (``attention._kernel_heads``:
    # ``ops/head_norm_rope``'s two kernels; Keye's layers have the same
    # shape): per-head RMSNorm, RoPE, the head split and the cast over the
    # float32 products (1, 16384, 4096) and (1, 16384, 512) where they lie;
    # the first heads' own products through the forward kernel in float32
    # (what ``attn_qk`` reads of a step); and the kernels' other tile, a
    # head of two lane tiles (16 on 2 of 256), which no cell runs
    def head_norm_rope(backward, heads=(32, 4), hd=128, dtype=bf16,
                       rows=(1, 16384), normed=True):
        from ompi_tpu.ops.head_norm_rope import signed_sin
        from ompi_tpu.parallel import attention
        from ompi_tpu.parallel.layers import rope_tables

        def made(q, k, *gains):
            with jax.named_scope("otpu_attn_proj"):     # the sublayers'
                cos, sin = rope_tables(rows[1], hd, 1e6)
                return tuple(
                    attention._kernel_heads(t, g, cos, signed_sin(sin), n,
                                            1e-6, dtype)
                    for t, g, n in zip((q, k), gains or (None, None), heads))

        rep = lambda *s, dt=f32: _sds(s, dt, one, P())
        fn, prods = made, tuple(rep(*rows, n * hd) for n in heads)
        if backward and normed:
            fn = jax.grad(lambda *a: sum(
                jnp.sum(t.astype(f32)) for t in made(*a)), (0, 1, 2, 3))
        elif backward:
            # the turn's transpose reads nothing of q and k: the cotangents
            # are the arguments the program is placed by
            fn = lambda dq, dk: jax.vjp(made, *(
                jnp.zeros(t.shape, f32) for t in prods))[1]((dq, dk))
            return jax.jit(fn), tuple(
                rep(rows[0], n, rows[1], hd, dt=dtype) for n in heads)
        return jax.jit(fn), (*prods,
                             *((rep(hd), rep(hd)) if normed else ()))

    case("sdar_head_norm_rope_forward", lambda: head_norm_rope(False))
    case("sdar_head_norm_rope_backward", lambda: head_norm_rope(True))
    case("sdar_head_norm_rope_first_head",
         lambda: head_norm_rope(False, (1, 1), dtype=f32))
    case("sdar_head_norm_rope_256_forward",
         lambda: head_norm_rope(False, (16, 2), 256))
    case("sdar_head_norm_rope_256_backward",
         lambda: head_norm_rope(True, (16, 2), 256))
    # the same way for a head that is turned and not normed (no gain: the
    # backward reads the cotangent and the tables alone): SmallThinker's
    # window layers, 28 on 4 x 16,384, and Ouro's sixteen applications, 16
    # on 16 x (2, 4096); and the first heads' products in float32
    small = dict(heads=(28, 4), normed=False)
    ouro = dict(heads=(16, 16), rows=(2, 4096), normed=False)
    case("smallthinker_head_rope_forward",
         lambda: head_norm_rope(False, **small))
    case("smallthinker_head_rope_backward",
         lambda: head_norm_rope(True, **small))
    case("smallthinker_head_rope_first_head", lambda: head_norm_rope(
        False, **dict(small, heads=(1, 1), dtype=f32)))
    case("ouro_head_rope_forward", lambda: head_norm_rope(False, **ouro))
    case("ouro_head_rope_backward", lambda: head_norm_rope(True, **ouro))
    case("ouro_head_rope_first_head", lambda: head_norm_rope(
        False, **dict(ouro, heads=(1, 1), dtype=f32)))
    # Nemotron-3-Super's share: 4 query heads on 1 key-value head x
    # 8,192 at a head width of 128, the whole head axis one group
    case("nemotron3_flash_causal_forward",
         lambda: flash_causal_forward(1, 4, 8192, 128, 128, 1))
    case("nemotron3_attn_backward_walk_8k",
         lambda: attn_backward_walk(1, 4, 8192, 128, 128, 1))
    # the experts' grouped matmul (``experts._kernel_matmul``: ``ops/
    # grouped_matmul``'s three kernels, forward and both transposed) at
    # a cell's rows a call, held experts and both expert matrices:
    # OLMoE's every slot at once, under autodiff
    def gmm_forms(m, g, d, f):
        from ompi_tpu.parallel import experts

        def loss(a, b, up, down, sizes):
            return (jnp.sum(experts._kernel_matmul(a, up, sizes, bf16))
                    + jnp.sum(experts._kernel_matmul(b, down, sizes, bf16)))

        rep = lambda *s: _sds(s, f32, one, P())
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3))), (
            rep(m, d), rep(m, f), rep(g, d, f), rep(g, f, d),
            _sds((g,), jnp.int32, one, P()))

    # and as a trip of the held experts' loop makes them
    # (``experts._grouped_matmul``: the forward product, and the
    # transposes with the matrices' gradients added to running sums in
    # place) at the chunk the loop walks at a share cell's shapes
    # (``experts.chunk_rows``): ``t`` tokens of ``k`` slots, ``g`` of
    # ``total`` experts held
    def gmm_trip_forms(t, k, g, total, d, f):
        from ompi_tpu.parallel import experts

        m = experts.chunk_rows(t, k, g, total)

        def trip(a, b, up, down, sizes, ct_d, ct_f, sum_up, sum_down):
            gmm, transposes = experts._grouped_matmul(sizes, bf16, False)
            return (gmm(a, up), gmm(b, down),
                    transposes(a, up, ct_f, sum_up),
                    transposes(b, down, ct_d, sum_down))

        rep = lambda *s: _sds(s, f32, one, P())
        return jax.jit(trip, donate_argnums=(7, 8)), (
            rep(m, d), rep(m, f), rep(g, d, f), rep(g, f, d),
            _sds((g,), jnp.int32, one, P()), rep(m, d), rep(m, f),
            rep(g, d, f), rep(g, f, d))

    case("gmm_olmoe", lambda: gmm_forms(65536, 64, 2048, 1024))
    case("gmm_lfm2", lambda: gmm_trip_forms(16384, 4, 8, 32, 2048, 1792))
    case("gmm_joyai", lambda: gmm_trip_forms(8192, 8, 16, 256, 2048, 768))
    case("gmm_nemotron",
         lambda: gmm_trip_forms(8192, 22, 8, 512, 1024, 2688))
    case("gmm_qwen3next",
         lambda: gmm_trip_forms(16384, 10, 32, 512, 2048, 512))
    case("gmm_smallthinker",
         lambda: gmm_trip_forms(16384, 6, 16, 64, 2560, 768))
    case("gmm_xing", lambda: gmm_trip_forms(4096, 4, 8, 64, 3584, 1024))
    # the same loop's two row scatter-adds (``ops/row_scatter``: the
    # trip's output rows times their weights into the layer's sums, the
    # rows' cotangents times one into the cotangent of ``h``), sums and
    # results in one buffer each, at the chunk and the width a share
    # cell sends (Keye's are SDAR's; Nemotron's rows are its latent's)
    def row_scatter_forms(t, k, g, total, d):
        from ompi_tpu.ops import row_scatter
        from ompi_tpu.parallel import experts

        m = experts.chunk_rows(t, k, g, total)

        def trip(out, dh, token, offsets, y, w, dxs):
            return (row_scatter.row_scatter_add(out, token, offsets, y, w),
                    row_scatter.row_scatter_add(dh, token, offsets, dxs,
                                                jnp.ones_like(w)))

        rep = lambda *s: _sds(s, f32, one, P())
        ints = lambda *s: _sds(s, jnp.int32, one, P())
        sums = rep(t, *row_scatter.tile_shape(d))
        return jax.jit(trip, donate_argnums=(0, 1)), (
            sums, sums, ints(m), ints(g + 1), rep(m, d), rep(m), rep(m, d))

    case("row_scatter_smallthinker",
         lambda: row_scatter_forms(16384, 6, 16, 64, 2560))
    case("row_scatter_sdar",
         lambda: row_scatter_forms(16384, 8, 16, 128, 2048))
    case("row_scatter_lfm2",
         lambda: row_scatter_forms(16384, 4, 8, 32, 2048))
    case("row_scatter_qwen3next",
         lambda: row_scatter_forms(16384, 10, 32, 512, 2048))
    case("row_scatter_joyai",
         lambda: row_scatter_forms(8192, 8, 16, 256, 2048))
    case("row_scatter_nemotron",
         lambda: row_scatter_forms(8192, 22, 8, 512, 1024))
    # the chunked delta rule (``gdn._kernel_rule``: ``ops/gated_delta``'s
    # two kernels) as the Qwen3-Next cell's step builds it: 16 key heads,
    # 32 value heads, 128 / 128, 16,384 positions in chunks of 64, q, k
    # and v read from the convolution's one array and normed in the
    # kernels, every product float32 at the highest precision
    def gdn_rule(backward):
        from ompi_tpu.parallel import gdn

        rep = lambda *s: _sds(s, f32, one, P())
        rule = lambda qkv, g, beta: gdn._kernel_rule(
            (qkv,), g, beta, 64, 16, (gdn.L2NORM_EPS, 128 ** -0.5))
        if backward:
            rule = jax.grad(lambda *a, rule=rule: jnp.sum(rule(*a)),
                            (0, 1, 2))
        return jax.jit(rule), (rep(1, 16384, 8192), rep(1, 16384, 32),
                               rep(1, 16384, 32))

    case("qwen3next_gdn_rule_forward", lambda: gdn_rule(False))
    case("qwen3next_gdn_rule_backward", lambda: gdn_rule(True))
    # the DeltaNet convolution and its silu (``gdn._kernel_conv``:
    # ``ops/causal_conv``'s two kernels) at the same cell's shape: 4 taps
    # over the (1, 16384, 8192) float32 [q | k | v]
    def gdn_conv(backward):
        from ompi_tpu.parallel import gdn

        rep = lambda *s: _sds(s, f32, one, P())
        conv = gdn._kernel_conv
        if backward:
            conv = jax.grad(lambda *a: jnp.sum(gdn._kernel_conv(*a)),
                            (0, 1))
        return jax.jit(conv), (rep(1, 16384, 8192), rep(4, 8192))

    case("qwen3next_gdn_conv_forward", lambda: gdn_conv(False))
    case("qwen3next_gdn_conv_backward", lambda: gdn_conv(True))
    # Mamba-2's chunked scan and its skip term (``mamba._kernel_scan``:
    # ``ops/ssd_scan``'s two kernels) as the two Mamba cells' steps build it, x, B and C read
    # from the convolution's one array: Granite's 32 held heads of 64 in
    # one group, 16,384 positions in chunks of 256 under a packed row's
    # documents; Nemotron's 16 heads, 8,192 positions in chunks of 128 and
    # no documents; every product float32 at the highest precision
    def ssd_scan(backward, heads, s, chunk, documents):
        from ompi_tpu.parallel import mamba

        rep = lambda *s: _sds(s, f32, one, P())
        scan = lambda xbc, dt, a, skip, *doc: mamba._kernel_scan(
            xbc, dt, a, skip, doc[0] if doc else None, chunk, 64, 1)
        if backward:
            scan = jax.grad(lambda *a, scan=scan: jnp.sum(scan(*a)),
                            (0, 1, 2, 3))
        return jax.jit(scan), (
            rep(1, s, heads * 64 + 256), rep(1, s, heads), rep(heads),
            rep(heads)
        ) + ((_sds((1, s), jnp.int32, one, P()),) if documents else ())

    case("granite_ssd_scan_forward",
         lambda: ssd_scan(False, 32, 16384, 256, True))
    case("granite_ssd_scan_backward",
         lambda: ssd_scan(True, 32, 16384, 256, True))
    case("nemotron3_ssd_scan_forward",
         lambda: ssd_scan(False, 16, 8192, 128, False))
    case("nemotron3_ssd_scan_backward",
         lambda: ssd_scan(True, 16, 8192, 128, False))
    case("vpu_combine2_sum", lambda: (
        pr.combine2, ("SUM", _sds((PAY,), f32, one, P()),
                      _sds((PAY,), f32, one, P())),
        {"interpret": False}))
    case("vpu_reduce_stack_max", lambda: (
        pr.reduce_stack, ("MAX", _sds((8, PAY), f32, one, P())),
        {"interpret": False}))
    # reduce_stack at a benchmark cell's size (4 rows of 64 MiB), as a
    # program input and as a gather hands it over: the module must hold
    # the kernel and bitcasts only (``entry_ops``) -- a relayout copy in
    # front of the kernel was 63% of the call before anyone looked (PR 28)
    ROW = (64 << 20) // 4
    case("vpu_reduce_stack_rows_prod_f32", lambda: (
        pr.reduce_stack, ("PROD", _sds((4, ROW), f32, one, P())),
        {"interpret": False}))
    # Keye-VL-2.0's learned sparse attention at the cell's shape (1 x 32
    # query heads on 4 key-value heads x 16,384 at 128; an indexer of 16
    # heads of 64, top 2,048): both flash kernels under a selection packed
    # eight keys a byte (query-major for the forward and the loss, which
    # transposes it; key-major for the backward pair), and the two kernels
    # of ``ops/sparse_attention``
    def select_args(b, s, key_major=False):
        return _sds((b, s // 8, s) if key_major else (b, s, s // 8),
                    jnp.int8, one, P())

    def flash_select_forward(b, h, s, d, n_kv, **scale):
        q, k, v = attn_bwd_args(b, h, s, d, d, n_kv)[:3]
        return fa.flash_causal_forward, (q, k, v), {
            "block": 1024, "interpret": False, "select": select_args(b, s),
            **scale}

    def attn_select_backward(b, h, s, d, n_kv, **scale):
        fn, args, kw = attn_block_backward(b, h, s, d, d, n_kv)
        flags = _sds((b * (s // 1024) ** 2,), jnp.int32, one, P())
        return fn, args, {**kw, "select": (select_args(b, s, True), flags),
                          **scale}

    def dsa_index_args(b, s, heads, di):
        return (_sds((b, heads, s, di), bf16, one, P()),
                _sds((b, s, di), bf16, one, P()),
                _sds((b, s, heads), f32, one, P()))

    def dsa_index_select(b, s, heads, di, topk):
        from ompi_tpu.ops import sparse_attention as sa

        return sa.index_select, dsa_index_args(b, s, heads, di), {
            "topk": topk, "interpret": False}

    def dsa_index_loss(b, h, s, d, n_kv, heads, di):
        from ompi_tpu.ops import sparse_attention as sa

        q, k = attn_bwd_args(b, h, s, d, d, n_kv)[:2]
        row = _sds((b, s), f32, one, P())
        return sa.index_loss, (
            q, k, _sds((b, h, s), f32, one, P()),
            *dsa_index_args(b, s, heads, di), row, select_args(b, s)), {
                "interpret": False}

    case("keye_flash_select_forward",
         lambda: flash_select_forward(1, 32, 16384, 128, 4))
    case("keye_attn_select_backward",
         lambda: attn_select_backward(1, 32, 16384, 128, 4))
    # granite-4.0-h-micro's attention layer under a packed row's document
    # mask, which is such a selection (1 x 16 query heads on 4 key-value
    # heads x 16,384 at 64, the scores' scale the file's 1 / 64)
    case("granite_flash_select_forward",
         lambda: flash_select_forward(1, 16, 16384, 64, 4, scale=1 / 64))
    case("granite_attn_select_backward",
         lambda: attn_select_backward(1, 16, 16384, 64, 4, scale=1 / 64))
    case("keye_dsa_index_select",
         lambda: dsa_index_select(1, 16384, 16, 64, 2048))
    case("keye_dsa_index_loss",
         lambda: dsa_index_loss(1, 32, 16384, 128, 4, 16, 64))
    case("vpu_reduce_stack_rows_band_i32", lambda: (
        pr.reduce_stack, ("BAND", _sds((4, ROW), jnp.int32, one, P())),
        {"interpret": False}))
    case("vpu_reduce_stack_gathered_prod_f32", lambda: (
        pr.reduce_stack, ("PROD", _sds((4, 1, ROW // 4), f32, one, P())),
        {"interpret": False}))

    # -- the datatype engine's streaming pack of an index list, at the
    # benchmark cell's own size and ids (``benchmark/harness/ddtkit
    # .atom_ids`` of ``ddt_pack.lammps_atomic.f32.4Mof32M``: 4,194,304 of
    # 33,554,432 atoms, three float32 each).  The whole of ``IndexPlan
    # .pack`` is compiled, so ``entry_ops`` shows what XLA puts around the
    # kernel: the three element gathers it replaced were ``fusion``s
    # (270 ms a call on the chip), a relayout would be a ``copy``
    def ddt_compact():
        import zlib

        import jax

        from ompi_tpu import datatype as dt
        from ompi_tpu.datatype.plan import plan_for
        from ompi_tpu.ops import pallas_ddt

        name, atoms, sent = "ddt_pack.lammps_atomic.f32.4Mof32M", 1 << 25, \
            1 << 22
        rng = _np.random.default_rng(zlib.crc32(name.encode()) & 0x7FFFFFFF)
        ids = _np.sort(rng.choice(atoms, sent, replace=False))
        plan = plan_for(dt.indexed_block(3, 3 * ids, dt.FLOAT32).commit())
        # this process's devices are CPUs; the described chip takes Mosaic
        pallas_ddt.pallas_interpret = lambda: False
        return jax.jit(plan.pack), (
            _sds((3 * atoms,), f32, one, P()),
            *(_sds(a.shape, a.dtype, one, P()) for a in plan.stream[0]))

    case("ddt_compact_lammps_f32", ddt_compact)

    # -- coll/quant codec kernels: encode / dequant-accumulate / decode
    # lower through Mosaic at sweep scale (1M-element operands, 8-rank
    # stacks).
    from ompi_tpu.ops import pallas_quant as pq

    QROWS = ((1 << 20) // pq.LANES)        # 1M f32 elements
    case("quant_encode_int8_1m", lambda: (
        pq.encode_int8, (_sds((QROWS, pq.LANES), f32, one, P()),),
        {"interpret": False}))
    case("quant_dequant_accumulate_8x", lambda: (
        pq.dequant_accumulate,
        (_sds((8, QROWS, pq.LANES), jnp.int8, one, P()),
         _sds((8, QROWS, 1), f32, one, P())),
        {"interpret": False}))
    case("quant_decode_int8_1m", lambda: (
        pq.decode_int8,
        (_sds((QROWS, pq.LANES), jnp.int8, one, P()),
         _sds((QROWS, 1), f32, one, P())),
        {"interpret": False}))

    # -- the composed flagship train step (forward + backward, ring
    # attention, shard_map under check_vma=True) on one device and on
    # the 2x2 (sp, tp) mesh the default factorisation gives four chips:
    # the only place the sp / tp collectives are compiled for the chip
    from ompi_tpu.parallel import flagship, train
    from ompi_tpu.parallel.config import load_model_config
    from ompi_tpu.parallel.mesh import make_mesh

    def train_step(devices):
        mesh, spec = make_mesh(devices)
        dims = flagship.model_dims(spec)
        step, _ = flagship.build_flagship_step(mesh, spec)
        pspecs = flagship.param_specs()
        params = {k: _sds(v.shape, f32, mesh, pspecs[k])
                  for k, v in flagship.init_params(spec).items()}
        x = _sds((dims["batch"], dims["seq"], dims["d"]), f32, mesh,
                 P("dp", "sp", None))
        return step, (params, x)

    # -- a public model's step at a benchmark cell's own configuration
    # file, on one device: forward, the flash kernel, the grouped expert
    # matmuls, backward and AdamW (OLMoE-1B-7B, one layer of 16;
    # JoyAI-LLM-Flash, one chip's share of a 16-chip deployment;
    # Nemotron-3-Super, one chip's share of a 64-chip deployment: the
    # chunked state-space scan, grouped-query attention, latent experts;
    # LFM2-8B-A1B, one chip's share of a 4-chip deployment: gated short
    # convolutions, attention at a head width of 64, a tied head;
    # Qwen3-Next-80B-A3B, one chip's share of a 16-chip deployment: the
    # chunked gated delta rule, output-gated attention at 256 wide)
    def model_config(config):
        return load_model_config(os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))),
            "benchmark", "configs", config + ".json"))

    def model_step(devices, config):
        from ompi_tpu.parallel.mesh import MeshSpec

        mesh, spec = make_mesh(devices, MeshSpec())
        cfg = model_config(config)
        step, _ = train.build_train_step(mesh, spec, model=cfg)
        rep = lambda s, dt=f32: _sds(s, dt, mesh, P())
        tree = jax.tree.map(rep, train.model_param_shapes(cfg),
                            is_leaf=lambda x: isinstance(x, tuple))
        bias = {}
        if cfg.topk_method == "noaux_tc":
            bias = {"layers": rep((cfg.n_sparse_here, cfg.num_experts))}
            if cfg.n_mtp_here:
                bias["mtp"] = rep((1, cfg.num_experts))
        elif cfg.pattern_here:      # ``place``'s rows of no entries
            bias = {"layers": rep((cfg.n_sparse_here, 0))}
        ids = lambda n: _sds((cfg.micro_batch, n), jnp.int32, mesh,
                             P("dp", None))
        return step.jitted, (
            (tree, tree, tree, rep((), jnp.int32), bias),
            ids(cfg.seq_len), ids(cfg.seq_len + cfg.n_mtp_here))

    # -- one latent-attention sublayer (``attention.mla_attention``) of
    # JoyAI's step, forward and gradient, at the cell's shapes: what
    # stands between the projections and the two kernels.  Its compiled
    # text must hold no ``_roll_static`` in any ``op_name`` and no array
    # 191 wide: ``jnp.roll`` on q's (1, 8192, 32, 192) float32 array was
    # 2.6 GB of shifted copies a layer and pass (PR 41)
    def mla_operands(devices):
        from ompi_tpu.parallel import attention

        one_dev = _Mesh(_np.asarray(devices), ("one",))
        cfg = model_config("joyai-flash-train-1chip")
        rep = lambda s: _sds(s, f32, one_dev, P())
        leaves = {k: rep(v) for k, v in attention.MLA.shapes(cfg).items()}
        loss = lambda p, x: jnp.sum((x + attention.mla_attention(
            p, x, cfg, interpret=False)[0]) ** 2)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))), (
            leaves, rep((cfg.micro_batch, cfg.seq_len, cfg.hidden_size)))

    # -- a partitioned allreduce's group program (coll/xla _group_fn) over
    # four chips, at the sizes ``rank1-partitioned`` releases: ``entry_ops``
    # must show one ``all-reduce`` a member.  Where XLA's combiner merges
    # them into one, the sums come out in another order than the
    # per-bucket program's (seen on four v5e chips, PR 34)
    def pallreduce_group(members, nbytes):
        from ompi_tpu.api import op as op_mod
        from ompi_tpu.mca.coll.xla import XlaCollModule

        mod = XlaCollModule(None, topo_devs[:4])
        return mod._group_fn(op_mod.SUM, members), (
            _sds((4, nbytes // 4), f32, mod.mesh, P(mod.axis)),) * members

    topo_devs = list(_np.asarray(mesh1d.devices).reshape(-1))
    case("olmoe_step_1chip", lambda: model_step(
        topo_devs[:1], "olmoe-1b-7b-train-1chip"))
    case("joyai_step_1chip", lambda: model_step(
        topo_devs[:1], "joyai-flash-train-1chip"))
    case("joyai_mla_operands", lambda: mla_operands(topo_devs[:1]))
    case("nemotron3_step_1chip", lambda: model_step(
        topo_devs[:1], "nemotron3-super-train-1chip"))
    case("lfm2_step_1chip", lambda: model_step(
        topo_devs[:1], "lfm2-8b-a1b-train-1chip"))
    case("qwen3next_step_1chip", lambda: model_step(
        topo_devs[:1], "qwen3-next-80b-a3b-train-1chip"))
    case("smallthinker_step_1chip", lambda: model_step(
        topo_devs[:1], "smallthinker-21b-a3b-train-1chip"))
    case("keye_step_1chip", lambda: model_step(
        topo_devs[:1], "keye-vl2-30b-a3b-train-1chip"))
    case("sdar_step_1chip", lambda: model_step(
        topo_devs[:1], "sdar-30b-a3b-train-1chip"))
    case("ouro_step_1chip", lambda: model_step(
        topo_devs[:1], "ouro-2.6b-train-1chip"))
    case("granite_step_1chip", lambda: model_step(
        topo_devs[:1], "granite-4.0-h-micro-train-1chip"))
    case("xing_step_1chip", lambda: model_step(
        topo_devs[:1], "xing4.0-29b-a4b-train-1chip"))
    case("train_step_1dev", lambda: train_step(topo_devs[:1]))
    if len(topo_devs) >= 4:
        case("train_step_2x2", lambda: train_step(topo_devs[:4]))
        case("pallreduce_group_3x25MiB_2x2",
             lambda: pallreduce_group(3, 25 << 20))
        case("pallreduce_group_32x2MiB_2x2",
             lambda: pallreduce_group(32, 2 << 20))
    return out


def entry_ops(compiled) -> dict:
    """Opcode counts of a compiled module's ENTRY computation, parameters
    left out: what XLA put around a kernel (``custom-call``)."""
    import collections
    import re

    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    ops = re.findall(r"^\s*(?:ROOT )?\S+ = .*?\s([a-z][\w\-]*)\(",
                     entry[:entry.index("\n}")], re.M)
    return dict(collections.Counter(o for o in ops if o != "parameter"))


def run(topology: str = DEFAULT_TOPOLOGY, only: str | None = None,
        verbose: bool = True, dump: str | None = None) -> dict:
    """Compile every case (those whose name holds ``only``); with
    ``dump`` also write each compiled module's optimised HLO text to
    ``<dump>/<case>.hlo.txt`` (what ``runtime/trace.scope_map`` reads;
    two trees' texts with ``metadata={...}`` stripped say whether a
    change moved any instruction)."""
    t0 = time.time()
    try:
        mesh1d, mesh2d = build_meshes(topology)
    except Exception as e:  # no libtpu / unknown topology
        return {"topology": topology, "ok": False,
                "error": f"{type(e).__name__}: {e}"[:500], "rows": []}
    rows = []
    for name, build in cases(mesh1d, mesh2d):
        if only and only not in name:
            continue
        row = {"kernel": name, "lowered": False, "compiled": False}
        try:
            ts = time.time()
            built = build()
            fn, args = built[0], built[1]
            kwargs = built[2] if len(built) > 2 else {}
            lowered = fn.lower(*args, **kwargs)
            row["lowered"] = True
            row["lower_s"] = round(time.time() - ts, 2)
            ts = time.time()
            compiled = lowered.compile()
            row["compiled"] = True
            row["compile_s"] = round(time.time() - ts, 2)
            row["entry_ops"] = entry_ops(compiled)
            if dump:
                os.makedirs(dump, exist_ok=True)
                with open(os.path.join(dump, name + ".hlo.txt"), "w",
                          encoding="utf-8") as f:
                    f.write(compiled.as_text())
            try:
                mem = compiled.memory_analysis()
                row["peak_vmem_bytes"] = int(
                    getattr(mem, "temp_size_in_bytes", 0) or 0)
                row["argument_bytes"] = int(
                    getattr(mem, "argument_size_in_bytes", 0) or 0)
                # the most the program holds at once, arguments
                # included: what has to fit the chip (the two above sum
                # to more: JoyAI's step ran on a v5e at 17.86 GB of them)
                row["peak_bytes"] = int(
                    getattr(mem, "peak_memory_in_bytes", 0) or 0)
            except Exception:
                pass
        except Exception as e:
            msg = f"{type(e).__name__}: {e}"
            row["error"] = msg[:800]
        rows.append(row)
        if verbose:
            ok = "OK " if row["compiled"] else "FAIL"
            print(f"[pallas-aot] {ok} {name}"
                  + ("" if row["compiled"] else
                     f" :: {row.get('error', '?')[:160]}"),
                  file=sys.stderr, flush=True)

    n_ok = sum(r["compiled"] for r in rows)
    return {"topology": topology, "ok": n_ok == len(rows) and n_ok > 0,
            "n_kernels": len(rows), "n_compiled": n_ok,
            "grade": "aot-tpu-compile", "elapsed_s": round(time.time() - t0, 1),
            "rows": rows}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="pallas_aot")
    ap.add_argument("--topology", default=DEFAULT_TOPOLOGY)
    ap.add_argument("--out", default=None, help="write JSON here")
    ap.add_argument("--only", default=None,
                    help="substring filter on kernel names")
    ap.add_argument("--dump", default=None,
                    help="write each compiled module's HLO text here")
    args = ap.parse_args(argv)
    res = run(args.topology, args.only, dump=args.dump)
    text = json.dumps(res, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
