"""The flagship training step: dp × pp × sp × tp(+ep) in one shard_map.

Assembles the explicit-SPMD transformer (model.py) and pipeline
(pipeline.py) into a jitted train step over a 4-axis mesh:

- activations sharded (dp: batch, sp: sequence), weights sharded (pp:
  layers, tp: hidden/heads/experts)
- grad sync = ``psum`` over (dp, sp) of per-shard partial gradients —
  the DP allreduce (≅ ``coll_base_allreduce.c`` ring; SURVEY.md §2.6)
- loss = mean over all output elements, so one lr fits every mesh
- loss reduced across the pipeline with a pp-masked psum

Model dims are *derived from the mesh spec* so every axis size divides its
tensor dims — the driver's ``dryrun_multichip`` runs this for arbitrary
device counts.
"""
from __future__ import annotations

import numpy as np

from ompi_tpu.base.var import VarType, registry
from ompi_tpu.parallel.mesh import MeshSpec
from ompi_tpu.parallel.model import transformer_block
from ompi_tpu.parallel.pipeline import pipeline_apply

_sp_impl_var = registry.register(
    "parallel", None, "sp_impl", vtype=VarType.STRING, default="ring",
    enum_values={"ring": 0, "ulysses": 1},
    help="Sequence/context-parallel attention scheme: 'ring' (ppermute "
         "K/V rotation, O(s_local) memory) or 'ulysses' (all-to-all "
         "head<->seq reshard, 2 collectives; local heads must divide sp)")

_causal_var = registry.register(
    "parallel", None, "causal", vtype=VarType.BOOL, default=False,
    help="Autoregressive (causal) attention masking at GLOBAL sequence "
         "positions — ring attention builds the per-step block bias "
         "from the shard offsets; ulysses masks the full sequence "
         "after its reshard")

_remat_var = registry.register(
    "parallel", None, "remat", vtype=VarType.BOOL, default=False,
    help="Rematerialize each transformer block in the backward pass "
         "(jax.checkpoint): activation HBM drops from all layers' "
         "intermediates to one block's, paying ~1/3 more FLOPs — the "
         "standard long-context/deep-stack memory lever")

_zero1_var = registry.register(
    "parallel", None, "zero1", vtype=VarType.BOOL, default=False,
    help="ZeRO-1 distributed optimizer: gradients reduce-scatter over "
         "dp (instead of allreduce), each dp rank updates its 1/dp "
         "parameter slice + momentum shard, and the updated slices "
         "rebuild via an exact masked psum — optimizer state memory "
         "drops by dp")

_bucket_var = registry.register(
    "parallel", None, "bucket_overlap", vtype=VarType.BOOL, default=False,
    help="Bucketed dp-gradient sync (the mca/part Pready schedule "
         "expressed in-jit): one psum per local-layer bucket issued "
         "late-layer-first instead of one whole-tree psum, so XLA can "
         "overlap each bucket's allreduce with work on other buckets — "
         "bit-identical parameters to the single-psum path "
         "(parallel/dryrun.py run_bucket_overlap_check pins it)")

_momentum_var = registry.register(
    "parallel", None, "momentum", vtype=VarType.FLOAT, default=0.0,
    help="SGD momentum for the flagship step (state is dp-sharded "
         "under parallel_zero1)")

_compute_dtype_var = registry.register(
    "parallel", None, "compute_dtype", vtype=VarType.STRING,
    default="float32", enum_values={"float32": 0, "bfloat16": 1},
    help="Block compute precision: bfloat16 runs the MXU at full rate "
         "and halves activation bytes (params stay float32 storage; "
         "cast at block entry, loss/grads accumulate in float32)")


def model_dims(spec: MeshSpec, layers: int = None) -> dict:
    """``layers`` defaults to one per pipeline stage; override (a
    multiple of pp) to hold model depth fixed across mesh specs — the
    pp=2-vs-pp=1 equivalence tests depend on it.

    ``OTPU_MODEL_SCALE`` multiplies the width/sequence dims (default 1:
    the compile-check scale every correctness test uses).
    ``chip_smoke.py`` raises it so the SAME flagship program runs at
    MXU-saturating sizes instead of tracing-scale ones."""
    import os

    scale = max(1, int(os.environ.get("OTPU_MODEL_SCALE", "1") or 1))
    tp, sp, dp, pp = spec.tp, spec.sp, spec.dp, spec.pp
    L = pp if layers is None else int(layers)
    if L % pp:
        raise ValueError(f"layers={L} not divisible by pp={pp}")
    d = 8 * scale
    hd = 4 * scale
    n_heads = 2 * tp
    ff = 8 * tp * scale
    n_experts = 2 * tp
    ffe = 4 * scale
    s_local = 4 * scale
    M = 2                      # microbatches
    mb = tp                    # microbatch rows per device (keeps MoE even)
    t_local = mb * s_local     # MoE tokens per device per microbatch
    cap = max(1, (t_local // tp) // n_experts * 2)
    return dict(
        d=d, hd=hd, n_heads=n_heads, h_local=n_heads // tp, ff=ff,
        n_experts=n_experts, ffe=ffe, seq=s_local * sp, s_local=s_local,
        M=M, mb=mb, batch=mb * M * dp, b_local=mb * M, capacity=cap,
        layers=L, layers_local=L // pp,
    )


def init_params(spec: MeshSpec, seed: int = 0, layers: int = None) -> dict:
    dims = model_dims(spec, layers)
    rng = np.random.RandomState(seed)
    d, L = dims["d"], dims["layers"]
    hh = dims["n_heads"] * dims["hd"]

    def w(*shape):
        return rng.normal(0, 0.5 / np.sqrt(shape[-2]), shape).astype(
            np.float32)

    return {
        "wq": w(L, d, hh), "wk": w(L, d, hh), "wv": w(L, d, hh),
        "wo": w(L, hh, d),
        "w1": w(L, d, dims["ff"]), "w2": w(L, dims["ff"], d),
        "wr": w(L, d, dims["n_experts"]),
        "we1": w(L, dims["n_experts"], d, dims["ffe"]),
        "we2": w(L, dims["n_experts"], dims["ffe"], d),
    }


def param_specs(P) -> dict:
    return {
        "wq": P("pp", None, "tp"), "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"), "wo": P("pp", "tp", None),
        "w1": P("pp", None, "tp"), "w2": P("pp", "tp", None),
        "wr": P("pp", None, None),
        "we1": P("pp", "tp", None, None), "we2": P("pp", "tp", None, None),
    }


def build_train_step(mesh, spec: MeshSpec, lr: float = 2.0,
                     layers: int = None):
    """Return (jitted_step, place) where step(params, x) -> (params, loss).

    ``place(params, x_np)`` device_puts globals with the right shardings.

    The loss is the MEAN of ``0.5 * y**2`` over every output element, so
    one ``lr`` fits every mesh and scale: batch, sequence and width all
    grow with the mesh spec and ``OTPU_MODEL_SCALE``, and a summed loss
    would grow the effective step with them until the widest meshes
    diverge.  The default falls monotonically for a dozen steps at
    scales 1 and 64 on every tested mesh, float32 and bfloat16.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ompi_tpu.base.jaxenv import pallas_interpret

    dims = model_dims(spec, layers)
    # the attention kernel follows the MESH's devices, not the
    # process's: an offline compile for a TPU topology takes the flash
    # path a chip would
    interpret = pallas_interpret(mesh.devices.flat)
    tp, sp_n, pp = spec.tp, spec.sp, spec.pp
    M, mb, s_l, d = dims["M"], dims["mb"], dims["s_local"], dims["d"]
    n_elems = dims["batch"] * dims["seq"] * d
    sp_impl = str(_sp_impl_var.value)
    causal = bool(_causal_var.value)

    compute_dtype = jnp.dtype(str(_compute_dtype_var.value))

    def apply_block(layer, x_mb):
        if compute_dtype != jnp.float32:
            # bf16 compute: params cast per block (storage stays f32 —
            # the master-weights discipline), activations stay bf16
            # across the stack; the f32 loss/grad path upcasts at exit
            layer = jax.tree.map(
                lambda a: a.astype(compute_dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, layer)
        out = transformer_block(
            layer, x_mb, sp=sp_n, tp=tp,
            n_heads_local=dims["h_local"],
            n_experts=dims["n_experts"], capacity=dims["capacity"],
            sp_impl=sp_impl, causal=causal, interpret=interpret)
        return out

    if bool(_remat_var.value):
        # recompute the block in the backward instead of storing its
        # activations — the jax.checkpoint form of the trade every
        # deep/long-context stack makes on HBM-bound chips
        # prevent_cse=False: apply_block runs inside pipeline_apply's
        # scan, which already provides the CSE barrier — the default
        # setting would only add optimization barriers on the hot path
        apply_block = jax.checkpoint(apply_block, prevent_cse=False)

    def stage_fn(stage_params, x_mb):
        for i in range(dims["layers_local"]):
            layer = jax.tree.map(lambda a: a[i], stage_params)
            x_mb = apply_block(layer, x_mb)
        return x_mb

    zero1 = bool(_zero1_var.value)
    mu = float(_momentum_var.value)
    if mu and not zero1:
        raise ValueError(
            "parallel_momentum is implemented by the ZeRO-1 sharded "
            "optimizer state — set --mca parallel_zero1 1 with it "
            "(a silently momentum-free run would corrupt comparisons)")
    bucket_overlap = bool(_bucket_var.value)
    if bucket_overlap and zero1:
        raise ValueError(
            "parallel_bucket_overlap buckets the dp ALLREDUCE; ZeRO-1 "
            "already reduce-scatters the dp sum — the combination is "
            "unsupported (a silent fallback would corrupt comparisons)")
    dp = spec.dp

    def bucketed_dp_sync(g):
        """Per-local-layer psum buckets, LATE layer first — the Pready
        release order of a backward pass (the last layer's gradient is
        finished first).  Elementwise psum over the same replica set
        makes each bucket bit-identical to its slice of the whole-leaf
        psum; jnp.stack restores the leaf."""
        parts = [jax.lax.psum(g[i], ("dp", "sp"))
                 for i in range(g.shape[0] - 1, -1, -1)]
        return jnp.stack(parts[::-1], axis=0)

    def body(state, x):
        if zero1:
            params, carry_m = state
        else:
            params, carry_m = state, None

        def loss_fn(ps):
            # activations enter the pipeline in compute_dtype so the
            # scan carries / ppermute handoffs stay half-width too
            xmb = x.reshape(M, mb, s_l, d).astype(compute_dtype)
            y = pipeline_apply(stage_fn, ps, xmb, pp=pp,
                               vary_axes=("pp", "tp"))
            # pipeline_apply outputs are zero off the last pp stage, so
            # the psum over pp collects exactly the last stage's loss.
            # y is value-replicated across tp but vma-varying (it came
            # through tp collectives): count the tp=0 replica only, so
            # the psum over ALL axes is both value-correct and provably
            # unvarying — gradients to the other tp shards still flow
            # through the block's internal tp-psum transposes
            yf = y.astype(jnp.float32)     # f32 loss accumulation
            local = (0.5 / n_elems) * jnp.sum(yf * yf)   # global mean
            local = jnp.where(jax.lax.axis_index("tp") == 0, local, 0.0)
            return jax.lax.psum(local, ("dp", "pp", "sp", "tp"))

        # differentiate w.r.t. a per-shard (varying) view of the
        # params, so the gradients come back as each shard's PARTIAL and
        # the collectives below are the one sync.  Taken w.r.t. the
        # replicated params, autodiff's own transpose would already
        # psum over every axis a leaf is replicated on, and the
        # explicit psum below would sum that sum again: a step dp*sp
        # times the gradient
        local_view = jax.tree.map(
            lambda p: jax.lax.pcast(p, ("dp", "sp"), to="varying"), params)
        local_view["wr"] = jax.lax.pcast(local_view["wr"], "tp",
                                         to="varying")
        loss, grads = jax.value_and_grad(loss_fn)(local_view)
        if not zero1:
            sync = bucketed_dp_sync if bucket_overlap else \
                (lambda g: jax.lax.psum(g, ("dp", "sp")))
            grads = jax.tree.map(sync, grads)
            grads["wr"] = jax.lax.psum(grads["wr"], "tp")
            new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new, loss
        # ZeRO-1: the dp sum rides a reduce-scatter (same bytes as the
        # allreduce it replaces), each dp rank owns 1/dp of the flat
        # parameter/momentum state, and the updated slices all-gather
        # back — the FSDP/ZeRO optimizer-state sharding pattern in
        # psum_scatter + all_gather form
        from jax.flatten_util import ravel_pytree

        grads = jax.tree.map(lambda g: jax.lax.psum(g, "sp"), grads)
        grads["wr"] = jax.lax.psum(grads["wr"], "tp")
        # grads and params share one pytree structure: a single ravel
        # provides both the flat vector and the shared unravel
        gflat, unravel = ravel_pytree(grads)
        total = gflat.shape[0]
        chunk = -(-total // dp)
        gpad = jnp.pad(gflat, (0, chunk * dp - total))
        gsl = jax.lax.psum_scatter(gpad.reshape(dp, chunk), "dp",
                                   scatter_dimension=0, tiled=False)
        m = carry_m
        m_new = mu * m + gsl
        r = jax.lax.axis_index("dp")
        # rebuild via masked psum, NOT all_gather: psum's output is
        # provably dp-INVARIANT under the vma checker (all_gather's
        # equal-by-construction result still types as varying), so the
        # replicated param out_specs hold without weakening check_vma
        contrib = jax.lax.dynamic_update_slice(
            jnp.zeros((chunk * dp,), gsl.dtype), -lr * m_new,
            (r * chunk,))
        delta_flat = jax.lax.psum(contrib, "dp")[:total]
        dtree = unravel(delta_flat)
        # leaves REPLICATED over tp (wr): the flat state mixes
        # tp-sharded leaves, so their delta types tp-varying even
        # though its value is identical on every tp shard — one exact
        # masked psum (only shard 0 contributes) restores provable
        # tp-invariance with zero fp perturbation.  UNCONDITIONAL:
        # m_spec carries "tp" even at axis size 1
        tpi = jax.lax.axis_index("tp")
        for k, sspec in pspecs.items():
            if "tp" not in tuple(sspec):
                dtree[k] = jax.lax.psum(
                    jnp.where(tpi == 0, dtree[k],
                              jnp.zeros_like(dtree[k])), "tp")
        new = jax.tree.map(lambda p_, d_: p_ + d_, params, dtree)
        return (new, m_new), loss

    pspecs = param_specs(P)
    # check_vma=True is LOAD-BEARING for correctness, not just a lint:
    # the varying-manifest tracking is what makes the ppermute/psum
    # transposes in the pp>=2 backward correct.  With it off the
    # composed step compiles and descends — with silently wrong
    # pipeline gradients (caught by test_pp2_matches_pp1_same_model).
    if zero1:
        # momentum shard: one (chunk,) block per (dp, pp, tp) shard of
        # the flat local parameter vector — a 1-D array sharded over
        # all three axes (sp replicates: grads are sp-summed first)
        m_spec = P(("dp", "pp", "tp"))
        state_specs = ((pspecs, m_spec), P("dp", "sp", None))
        out_state_specs = ((pspecs, m_spec), P())
    else:
        state_specs = (pspecs, P("dp", "sp", None))
        out_state_specs = (pspecs, P())
    step = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=state_specs,
        out_specs=out_state_specs,
        check_vma=True))

    def place(params, x_np):
        p = {k: jax.device_put(v, NamedSharding(mesh, pspecs[k]))
             for k, v in params.items()}
        x = jax.device_put(
            np.asarray(x_np, np.float32),
            NamedSharding(mesh, P("dp", "sp", None)))
        if zero1:
            # local flat size: each leaf's global shape divided by the
            # MESH size of every axis its spec shards it over — the
            # same division shard_map applies, so body's traced
            # ravel_pytree total always agrees (axis sizes come from
            # mesh.shape, never a hand-maintained map)
            sizes = 0
            for k, v in params.items():
                shp = list(np.asarray(v).shape)
                for dim, ax in enumerate(pspecs[k]):
                    if ax is None:
                        continue
                    for a in (ax if isinstance(ax, tuple) else (ax,)):
                        shp[dim] //= mesh.shape[a]
                sizes += int(np.prod(shp))
            chunk = -(-sizes // spec.dp)
            m0 = np.zeros(chunk * spec.dp * spec.pp * spec.tp,
                          np.float32)
            mdev = jax.device_put(m0, NamedSharding(mesh, m_spec))
            return (p, mdev), x
        return p, x

    return step, place
