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

import dataclasses
import json
import time
import zlib

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
                     layers: int = None, model: "ModelConfig" = None):
    """Return (jitted_step, place) where step(params, x) -> (params, loss).

    With ``model`` (a public model's :class:`ModelConfig`) the widths come
    from the configuration and not from the mesh: ``step(state, tokens,
    labels) -> (state, aux)`` and ``place(params, tokens, labels)``, see
    ``_build_model_step``; ``lr`` and ``layers`` are then the
    configuration's.

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

    if model is not None:
        return _build_model_step(mesh, spec, model)
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


# -- a public model's training step (OLMoE): widths from a configuration
# file, not from the mesh -----------------------------------------------
#: a layer's leaves, stacked over the layers this rank holds
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ln2",
                "router", "gate", "up", "down")
PROBE = 64              # entries of each leaf that a step reports
SAMPLE_ROWS = 16        # token rows whose activations a step reports
#: what a step's ``aux`` holds: small raw statistics, for whoever reads
#: them outside the step (no unit, no scaling).  ``losses`` (the total,
#: cross-entropy, and the load-balancing and router z losses as weighted
#: into the total); ``loads`` (L, E) the slots an expert received;
#: ``rows`` (T, 2) every row's logsumexp over the vocabulary and its
#: label's logit; ``experts`` (L, T, k) the experts every token chose;
#: by leaf in ``leaf_names()``'s order ``grad_sq`` (the gradient's sum of
#: squares), ``grad_probe`` and ``param_probe`` (the gradient and the
#: updated parameter at ``probe_positions``); and ``sample``, what went
#: into and came out of the float32 parts at ``sample_rows`` of each
#: shard: ``router_in`` (L, R, d), ``router_logits`` (L, R, E),
#: ``router_lse`` (L, R), ``router_weights`` (L, R, k), ``head_in``
#: (R, d), so that their precision can be read from one step alone


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """A public model's widths (the keys of its published
    ``config.json``), how much of it this rank holds (``layers_here``)
    and how it is trained (the ``train`` group of the file)."""
    hidden_size: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    num_experts: int
    num_experts_per_tok: int
    vocab_size: int
    layers_here: int
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    norm_topk_prob: bool = False
    seq_len: int = 4096
    micro_batch: int = 2
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 0.001
    lr: float = 4e-4
    warmup_steps: int = 1           # lr rises linearly over these steps
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    adam_eps: float = 1e-8
    weight_decay: float = 0.1
    init_std: float = 0.02
    compute_dtype: str = "bfloat16"
    attn_block: int = 1024
    loss_block_rows: int = 1024

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise NotImplementedError("grouped-query attention: "
                                      "num_key_value_heads != heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is not a multiple of the heads")


def load_model_config(path: str, **overrides) -> ModelConfig:
    """The configuration file of a public model: the keys of its
    ``config.json`` at the top level, ``layers_here``, and a ``train``
    group; keys this dataclass does not know (the file's prose) are
    left alone.  A model this path cannot run raises."""
    with open(path, encoding="utf-8") as f:
        body = json.load(f)
    if body.get("hidden_act") != "silu" or body.get("attention_bias") \
            or body.get("clip_qkv") or body.get("tie_word_embeddings") \
            or body.get("rope_scaling"):
        raise NotImplementedError(
            f"{path}: the model path runs silu experts, no biases, no "
            "clipping, an untied head and plain RoPE")
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    merged = {**body, **body.get("train", {}), **overrides}
    return ModelConfig(**{k: v for k, v in merged.items() if k in known})


def model_param_shapes(cfg: ModelConfig) -> dict:
    d, f, e = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    n, v = cfg.layers_here, cfg.vocab_size
    layer = {"ln1": (n, d), "wq": (n, d, d), "wk": (n, d, d),
             "wv": (n, d, d), "wo": (n, d, d), "q_norm": (n, d),
             "k_norm": (n, d), "ln2": (n, d), "router": (n, d, e),
             "gate": (n, e, d, f), "up": (n, e, d, f), "down": (n, e, f, d)}
    return {"embed": (v, d), "layers": layer, "final_norm": (d,),
            "head": (d, v)}


def is_gain(name: str) -> bool:
    """A norm's gain: starts at one, and is not decayed."""
    return name in ("ln1", "ln2", "q_norm", "k_norm", "final_norm")


def leaf_names() -> list:
    """(name, path) of every leaf in a fixed order."""
    return [("embed", ("embed",))] + [
        (k, ("layers", k)) for k in LAYER_LEAVES] + [
        ("final_norm", ("final_norm",)), ("head", ("head",))]


def _leaf(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _set_leaf(tree, path, leaf) -> None:
    """Put ``leaf`` at ``path`` of a tree being built (``{"layers": {}}``
    to start with)."""
    for k in path[:-1]:
        tree = tree[k]
    tree[path[-1]] = leaf


def probe_positions(name: str, size: int) -> np.ndarray:
    """The ``PROBE`` flat positions of leaf ``name`` that a step reports
    the gradient and the updated parameter at: drawn once from the
    leaf's name, so any other implementation finds the same ones."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    return np.sort(rng.integers(0, size, PROBE)).astype(np.int64)


def sample_rows(rows: int) -> np.ndarray:
    """The ``SAMPLE_ROWS`` rows of a shard of ``rows`` token rows that a
    step reports activations at: evenly spaced, the last row among
    them."""
    n = min(SAMPLE_ROWS, rows)
    return (np.arange(1, n + 1) * rows) // n - 1


def init_model_params(cfg: ModelConfig, seed: int = 0) -> dict:
    """Float32 master parameters drawn on the default device from
    ``seed``: normal(0, ``init_std``) matrices, gains of one."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)

    def draw(name, shape):
        if is_gain(name):
            return jnp.ones(shape, jnp.float32)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return cfg.init_std * jax.random.normal(k, shape, jnp.float32)

    shapes = model_param_shapes(cfg)
    return {"embed": draw("embed", shapes["embed"]),
            "layers": {k: draw(k, s) for k, s in shapes["layers"].items()},
            "final_norm": draw("final_norm", shapes["final_norm"]),
            "head": draw("head", shapes["head"])}


def head_cross_entropy(h, w, labels, block_rows: int, compute_dtype):
    """Summed cross-entropy of ``softmax(h @ w)`` against ``labels``, by
    blocks of ``block_rows`` rows so that no (T, V) array is ever held.
    The forward pass also makes the two gradients (``softmax - onehot``
    is at hand in each block), so the backward pass only scales them:
    the head's logits are computed once a step, not twice.  Returns
    (the sum over rows, per row (logsumexp, the label's logit))."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.parallel.model import matmul

    t, d = h.shape
    nblk = t // block_rows
    if nblk * block_rows != t:
        raise ValueError(f"{t} rows are not whole blocks of {block_rows}")

    def run(h, w, labels):
        def block(carry, xs):
            total, dw = carry
            hb, lb = xs
            logits = matmul(hb, w, compute_dtype)            # (rows, V) f32
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
            dlogits = jnp.exp(logits - lse[:, None]) - jax.nn.one_hot(
                lb, logits.shape[-1], dtype=jnp.float32)
            dh = matmul(dlogits, w.T, compute_dtype)
            dw = dw + matmul(hb.T, dlogits, compute_dtype)
            return (total + jnp.sum(lse - picked), dw), (
                dh, jnp.stack([lse, picked], axis=-1))

        vma = tuple(jax.typeof(h).vma | jax.typeof(labels).vma)
        zero = (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32))
        if vma:         # the scan's carry varies as its inputs do
            zero = jax.lax.pcast(zero, vma, to="varying")
        (total, dw), (dh, rows) = jax.lax.scan(
            block, zero, (h.reshape(nblk, block_rows, d),
                          labels.reshape(nblk, block_rows)))
        return total, rows.reshape(t, 2), dh.reshape(t, d), dw

    @jax.custom_vjp
    def ce(h, w):
        total, rows, _, _ = run(h, w, labels)
        return total, rows

    def fwd(h, w):
        total, rows, dh, dw = run(h, w, labels)
        return (total, rows), (dh, dw)

    def bwd(res, ct):
        dh, dw = res
        return ct[0] * dh, ct[0] * dw

    ce.defvjp(fwd, bwd)
    return ce(h, w)


def model_loss(params, tokens, labels, cfg: ModelConfig, *, interpret: bool,
               n_global: int, axes: tuple = ()):
    """The training loss of one micro-batch shard and what a step
    reports of it.  ``n_global`` is the tokens of the whole batch and
    ``axes`` the mesh axes it is sharded over: sums cross them by
    ``psum``, so every shard returns the whole batch's loss."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.parallel.model import olmoe_block, rmsnorm_gain

    psum = (lambda a: jax.lax.psum(a, axes)) if axes else (lambda a: a)
    b, s = tokens.shape
    with jax.named_scope("otpu_embed"):
        x = params["embed"][tokens]                          # (b, s, d) f32
    slots = prob_sum = z_sum = 0.0
    loads, chosen, samples = [], [], []
    at = sample_rows(b * s)
    for i in range(cfg.layers_here):
        layer = jax.tree.map(lambda a: a[i], params["layers"])
        x, st, routed = olmoe_block(layer, x, cfg, interpret=interpret)
        st = jax.tree.map(psum, st)
        loads.append(st["slots"])
        chosen.append(routed.pop("experts"))
        samples.append({"router_" + k: v[at] for k, v in routed.items()})
        slots, prob_sum = slots + st["slots"], prob_sum + st["prob_sum"]
        z_sum = z_sum + st["z_sum"]
    with jax.named_scope("otpu_head"):
        h = rmsnorm_gain(x, params["final_norm"], cfg.rms_norm_eps)
        ce_sum, rows = head_cross_entropy(
            h.reshape(b * s, -1), params["head"], labels.reshape(b * s),
            min(cfg.loss_block_rows, b * s), cfg.compute_dtype)
    routed = cfg.layers_here * n_global     # rows of all routers' logits
    ce = psum(ce_sum) / n_global
    # HF's load_balancing_loss_func: every layer's rows in one mean
    lb = cfg.num_experts * jnp.sum((slots / routed) * (prob_sum / routed))
    z = z_sum / routed
    lb, z = cfg.aux_loss_coef * lb, cfg.z_loss_coef * z
    total = ce + lb + z
    sample = jax.tree.map(lambda *a: jnp.stack(a), *samples)
    sample["head_in"] = h.reshape(b * s, -1)[at]
    return total, {"losses": jnp.stack([total, ce, lb, z]),
                   "loads": jnp.stack(loads), "rows": rows,
                   "experts": jnp.stack(chosen),
                   "sample": sample}


def adamw(cfg: ModelConfig, name: str, p, g, m, v, t):
    """One AdamW update of one leaf in float32 (decoupled weight decay
    on everything but the norms' gains; ``t`` counts from 1; the
    learning rate rises linearly over the first ``warmup_steps``)."""
    import jax.numpy as jnp

    m = cfg.adam_b1 * m + (1.0 - cfg.adam_b1) * g
    v = cfg.adam_b2 * v + (1.0 - cfg.adam_b2) * g * g
    mhat = m / (1.0 - cfg.adam_b1 ** t)
    vhat = v / (1.0 - cfg.adam_b2 ** t)
    step = mhat / (jnp.sqrt(vhat) + cfg.adam_eps)
    if not is_gain(name):
        step = step + cfg.weight_decay * p
    lr = cfg.lr * jnp.minimum(1.0, t / cfg.warmup_steps)
    return p - lr * step, m, v


def _build_model_step(mesh, spec: MeshSpec, cfg: ModelConfig):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ompi_tpu.base.jaxenv import pallas_interpret
    from ompi_tpu.runtime import spc, trace

    if spec.pp != 1 or spec.sp != 1 or spec.tp != 1 or spec.ep != 1:
        raise NotImplementedError(
            f"the model path shards the batch over dp only; {spec} asks "
            "for pp, sp, tp or ep > 1 (expert and tensor parallelism of a "
            "public model are a later cell's)")
    if cfg.micro_batch % spec.dp:
        raise ValueError(f"micro_batch {cfg.micro_batch} does not divide "
                         f"over dp = {spec.dp}")
    interpret = pallas_interpret(mesh.devices.flat)
    n_global = cfg.micro_batch * cfg.seq_len
    names = leaf_names()
    shapes = model_param_shapes(cfg)
    probes = {n: np.unravel_index(
        probe_positions(n, int(np.prod(_leaf(shapes, path)))),
        _leaf(shapes, path)) for n, path in names}

    def body(state, tokens, labels):
        params, mom, var, t = state

        def loss_fn(ps):
            return model_loss(ps, tokens, labels, cfg, interpret=interpret,
                              n_global=n_global, axes=("dp",))

        # as in the toy's step: differentiate a per-shard view, so the
        # gradients come back as each shard's partial and the psum below
        # is the one sync
        local = jax.tree.map(
            lambda p: jax.lax.pcast(p, ("dp",), to="varying"), params)
        (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(local)
        grads = jax.tree.map(lambda g: jax.lax.psum(g, "dp"), grads)
        t = t + 1
        tf = t.astype(jnp.float32)
        new_p, new_m, new_v = {"layers": {}}, {"layers": {}}, {"layers": {}}
        sq, g_probe, p_probe = [], [], []
        with jax.named_scope("otpu_adamw"):
            for name, path in names:
                g = _leaf(grads, path)
                p, m, v = adamw(cfg, name, _leaf(params, path), g,
                                _leaf(mom, path), _leaf(var, path), tf)
                for tree, leaf in ((new_p, p), (new_m, m), (new_v, v)):
                    _set_leaf(tree, path, leaf)
                sq.append(jnp.sum(g * g))
                g_probe.append(g[probes[name]])
                p_probe.append(p[probes[name]])
        aux.update(grad_sq=jnp.stack(sq), grad_probe=jnp.stack(g_probe),
                   param_probe=jnp.stack(p_probe))
        return (new_p, new_m, new_v, t), aux

    rep = P()
    batch = P("dp", None)
    rows = P(None, "dp", None)
    aux_specs = {"losses": rep, "loads": rep, "rows": batch,
                 "experts": rows, "grad_sq": rep, "grad_probe": rep,
                 "param_probe": rep,
                 "sample": {"router_in": rows, "router_logits": rows,
                            "router_lse": P(None, "dp"),
                            "router_weights": rows, "head_in": batch}}

    def otpu_train_step(state, tokens, labels):
        return shard_map(body, mesh=mesh, in_specs=(rep, batch, batch),
                         out_specs=(rep, aux_specs), check_vma=True)(
            state, tokens, labels)

    jitted = jax.jit(otpu_train_step, donate_argnums=(0,))
    slots = n_global * cfg.num_experts_per_tok * cfg.layers_here
    trace.bind_profiler()
    count = [0]

    def step(state, tokens, labels):
        """One optimiser step: ``(state, aux)``; ``state`` is donated.
        Nothing here reads the device: what it computed comes back in
        ``aux`` (``record_step_stats`` reads it, outside any timing)."""
        count[0] += 1
        spc.record("train_steps")
        spc.record("train_tokens", n_global)
        spc.record("moe_token_slots", slots)
        if count[0] == 1:
            # the first call traces, lowers and compiles (or loads the
            # cached program): counted as every device program's is
            t0 = time.perf_counter()
            out = jitted(state, tokens, labels)
            spc.record("device_program_builds")
            spc.record("device_program_first_call_us",
                       (time.perf_counter() - t0) * 1e6)
            return out
        if trace.profiler_on():
            with jax.profiler.StepTraceAnnotation("otpu.train.step",
                                                  step_num=count[0]):
                return jitted(state, tokens, labels)
        return jitted(state, tokens, labels)

    step.jitted = jitted

    def place(params, tokens, labels):
        """``(state, tokens, labels)`` on the mesh: the state is the
        parameters, AdamW's two moments at zero and the step count."""
        put = lambda a, s: jax.device_put(a, NamedSharding(mesh, s))
        params = jax.tree.map(lambda a: put(a, rep), params)
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)
        state = (params, zeros(), zeros(), put(jnp.zeros((), jnp.int32), rep))
        return state, put(tokens, batch), put(labels, batch)

    return step, place


def record_step_stats(aux) -> int:
    """Read a finished step's expert loads (this blocks on the device:
    call it outside anything timed) and keep SPC ``moe_max_expert_load``
    at the fullest expert's slots of any step read so far."""
    from ompi_tpu.runtime import spc

    fullest = int(np.asarray(aux["loads"]).max())
    seen = spc.read("moe_max_expert_load")
    if fullest > seen:
        spc.record("moe_max_expert_load", fullest - seen)
    return fullest
