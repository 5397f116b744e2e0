"""The invented ("flagship") training step: dp × pp × sp × tp(+ep) in one
shard_map, and the per-device transformer block it runs.

The only place the ``pp`` / ``sp`` / ``tp`` shardings run (a public model's
step, ``parallel/train.py``, shards the batch over dp alone).  The blocks
run *inside* ``shard_map`` — the MPI-flavoured explicit-SPMD style: every
cross-device exchange is a named collective on a mesh axis, the
device-side mirror of the reference's coll algorithms (ring allreduce
``coll_base_allreduce.c:341``, pairwise alltoall ``coll_base_alltoall.c``,
binomial pipelines) rather than GSPMD auto-propagation:

- activations sharded (dp: batch, sp: sequence), weights sharded (pp:
  layers, tp: hidden/heads/experts)
- grad sync = ``psum`` over (dp, sp) of per-shard partial gradients —
  the DP allreduce (≅ ``coll_base_allreduce.c`` ring; SURVEY.md §2.6)
- loss = mean over all output elements, so one lr fits every mesh
- loss reduced across the pipeline with a pp-masked psum

Model dims are *derived from the mesh spec* so every axis size divides its
tensor dims — the driver's ``dryrun_multichip`` runs this for arbitrary
device counts.  One variant: float32, non-causal ring attention, plain SGD.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ompi_tpu.parallel.mesh import MeshSpec
from ompi_tpu.parallel.pipeline import pipeline_apply


def rmsnorm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _update_jnp(q, k_blk, v_blk, m, num, den, bias=None):
    """One online-softmax accumulation step against a K/V block.

    q: (b, h, sq, d); k_blk: (b, h, skv, d); v_blk: (b, h, skv, dv);
    m/den: (b, h, sq); num: (b, h, sq, dv).  ``bias`` (sq, skv) is added
    to the scores (broadcast over batch/heads): -inf entries mask.
    Returns updated (m, num, den)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
    if bias is not None:
        s = s + bias
    new_m = jnp.maximum(m, s.max(axis=-1))
    c = jnp.exp(m - new_m)
    p = jnp.exp(s - new_m[..., None])
    new_num = num * c[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
    new_den = den * c + p.sum(axis=-1)
    return new_m, new_num, new_den


def ring_attention(q, k, v, axis: str, n_shards: int, causal: bool = False):
    """Flash-style ring attention over the sequence-parallel axis.

    q/k/v local: (b, h_local, s_local, hd).  K/V blocks rotate around the
    ``axis`` ring via ``ppermute`` (the CP/ring-attention neighbor exchange,
    SURVEY.md §2.6) while the numerator/denominator accumulate with the
    running-max rescaling, so memory stays O(s_local) regardless of the
    global sequence length — long context is a first-class mesh axis.

    ``causal=True`` applies the autoregressive mask at GLOBAL positions:
    shard i's queries own rows [i*s_local, (i+1)*s_local); the block
    visiting at ring step t originated at shard (i-t) mod n, so an
    additive 0/-inf bias built from the two shard offsets masks exactly
    the future positions.  Step 0 is the diagonal block (every query
    row sees at least its own position), which keeps the running max
    finite before any fully-masked later block arrives.

    The per-step block combine (two matmuls + online-softmax rescale,
    ``_update_jnp``) is plain ``jnp`` on every platform; the ring
    structure stays at the XLA level so the compiler schedules the ICI
    ppermute.
    """
    s_local = q.shape[-2]
    # derive the accumulator inits FROM q (0*q + const) so they inherit
    # q's varying-manifest axes: fresh jnp.zeros/full would be unvarying
    # and the scan carry would trip the vma checker under check_vma=True
    m0 = q[..., 0] * 0 - jnp.inf
    num0 = q * 0
    den0 = q[..., 0] * 0
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    my = jax.lax.axis_index(axis) if n_shards > 1 else 0

    def step_bias(t):
        # kv block at step t came from shard (my - t) mod n
        src = jax.lax.rem(my - t + n_shards, n_shards)
        qpos = my * s_local + jnp.arange(s_local)[:, None]
        kpos = src * s_local + jnp.arange(s_local)[None, :]
        # q.dtype (not f32): a wider bias would promote the scan
        # carry under bfloat16 inputs and break lax.scan's
        # carry-type invariant
        return jnp.where(qpos >= kpos, 0.0, -jnp.inf).astype(q.dtype)

    def body(carry, t):
        k_blk, v_blk, m, num, den = carry
        m, num, den = _update_jnp(q, k_blk, v_blk, m, num, den,
                                  step_bias(t) if causal else None)
        if n_shards > 1:
            k_blk = jax.lax.ppermute(k_blk, axis, perm)
            v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return (k_blk, v_blk, m, num, den), None

    (_, _, _, num, den), _ = jax.lax.scan(
        body, (k, v, m0, num0, den0), jnp.arange(n_shards))
    return num / den[..., None]


def _full_attention(q, k, v, causal: bool = False):
    """Plain softmax attention with the scores whole: the reference the
    tests hold ``ring_attention`` and the model path's blocked causal
    attention to."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        sq, skv = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def attention_block(p, x, *, sp: int, tp: int, n_heads_local: int):
    """Sequence-parallel (ring) attention with tp-sharded heads; psum
    output proj.

    x local: (b, s_local, d) replicated over tp.  Head projections are
    column-sharded over tp (h_local = H/tp); the output projection is
    row-sharded, so its partial products combine with a ``psum`` over tp —
    the tensor-parallel allreduce (DP/TP table row, SURVEY.md §2.6).
    """
    b, s_l, d = x.shape
    h = rmsnorm(x)

    def heads(w):
        y = h @ w  # (b, s_l, h_local*hd)
        return y.reshape(b, s_l, n_heads_local, -1).transpose(0, 2, 1, 3)

    q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
    o = ring_attention(q, k, v, "sp", sp)            # (b, h_l, s_l, hd)
    o = o.transpose(0, 2, 1, 3).reshape(b, s_l, -1)  # (b, s_l, h_l*hd)
    o = o @ p["wo"]
    if tp > 1:
        o = jax.lax.psum(o, "tp")
    return x + o


def mlp_block(p, x, *, tp: int):
    """Megatron-style tp MLP: column-shard w1, row-shard w2, psum combine."""
    h = rmsnorm(x)
    y = jax.nn.gelu(h @ p["w1"]) @ p["w2"]
    if tp > 1:
        y = jax.lax.psum(y, "tp")
    return x + y


def moe_block(p, x, *, tp: int, n_experts: int, capacity: int):
    """Top-1 MoE with experts sharded over tp (the ep axis) via all_to_all.

    Local tokens are chunked over tp (each tp shard routes its slice),
    dispatched to expert-home shards with ``all_to_all`` (the MoE dispatch
    ≅ pairwise alltoall, SURVEY.md §2.6 EP row), processed by the local
    expert FFNs, returned by the inverse all_to_all, and the chunks
    re-replicated with ``all_gather``.  Static capacity per (expert,
    source-shard); overflow tokens fall through on the residual path.
    """
    b, s_l, d = x.shape
    xf = rmsnorm(x).reshape(b * s_l, d)
    t = xf.shape[0]
    tc = t // tp
    e_l = n_experts // tp
    r = jax.lax.axis_index("tp") if tp > 1 else 0
    chunk = jax.lax.dynamic_slice_in_dim(xf, r * tc, tc, 0)  # (tc, d)

    logits = chunk @ p["wr"]                        # (tc, E)
    probs = jax.nn.softmax(logits, axis=-1)
    eid = jnp.argmax(probs, axis=-1)                # (tc,)
    # routing bookkeeping in f32 ALWAYS: bf16 cumsum cannot count
    # past 256 exactly, silently colliding capacity slots at
    # production token counts
    oh = jax.nn.one_hot(eid, n_experts, dtype=jnp.float32)       # (tc, E)
    pos = (jnp.cumsum(oh, axis=0) - 1.0) * oh                    # (tc, E)
    keep = oh * (pos < capacity)
    pos_oh = jax.nn.one_hot(
        jnp.clip(pos.astype(jnp.int32), 0, capacity - 1), capacity,
        dtype=xf.dtype)                                          # (tc, E, cap)
    disp = (keep[..., None] * pos_oh).astype(xf.dtype)           # (tc, E, cap)

    ex_in = jnp.einsum("tec,td->ecd", disp, chunk)   # (E, cap, d)
    ex_in = ex_in.reshape(tp, e_l, capacity, d)
    if tp > 1:
        ex_in = jax.lax.all_to_all(ex_in, "tp", split_axis=0, concat_axis=0)
    # (tp, e_l, cap, d): leading dim is now source shard
    ex_in = ex_in.transpose(1, 0, 2, 3).reshape(e_l, tp * capacity, d)
    hid = jax.nn.gelu(jnp.einsum("etd,edf->etf", ex_in, p["we1"]))
    ex_out = jnp.einsum("etf,efd->etd", hid, p["we2"])
    ex_out = ex_out.reshape(e_l, tp, capacity, d).transpose(1, 0, 2, 3)
    if tp > 1:
        ex_out = jax.lax.all_to_all(ex_out, "tp", split_axis=0, concat_axis=0)
    ex_out = ex_out.reshape(n_experts, capacity, d)

    gate = jnp.einsum("tec,te->t", disp, probs)      # kept-assignment prob
    out_chunk = jnp.einsum("tec,ecd->td", disp, ex_out) * gate[:, None]
    if tp > 1:
        out = jax.lax.all_gather(out_chunk, "tp", axis=0, tiled=True)  # (t, d)
    else:
        out = out_chunk
    return x + out.reshape(b, s_l, d)


def transformer_block(p, x, *, sp, tp, n_heads_local, n_experts, capacity):
    x = attention_block(p, x, sp=sp, tp=tp, n_heads_local=n_heads_local)
    x = mlp_block(p, x, tp=tp)
    x = moe_block(p, x, tp=tp, n_experts=n_experts, capacity=capacity)
    return x


def model_dims(spec: MeshSpec, layers: int = None) -> dict:
    """``layers`` defaults to one per pipeline stage; override (a
    multiple of pp) to hold model depth fixed across mesh specs — the
    pp=2-vs-pp=1 equivalence tests depend on it.

    ``OTPU_MODEL_SCALE`` multiplies the width/sequence dims (default 1:
    the compile-check scale every correctness test uses).
    ``chip_smoke.py`` raises it so the SAME flagship program runs at
    MXU-saturating sizes instead of tracing-scale ones."""
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


def param_specs() -> dict:
    return {
        "wq": P("pp", None, "tp"), "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"), "wo": P("pp", "tp", None),
        "w1": P("pp", None, "tp"), "w2": P("pp", "tp", None),
        "wr": P("pp", None, None),
        "we1": P("pp", "tp", None, None), "we2": P("pp", "tp", None, None),
    }


def build_flagship_step(mesh, spec: MeshSpec, lr: float = 2.0,
                        layers: int = None):
    """Return (jitted_step, place) where step(params, x) -> (params, loss).

    ``place(params, x_np)`` device_puts globals with the right shardings.

    The loss is the MEAN of ``0.5 * y**2`` over every output element, so
    one ``lr`` fits every mesh and scale: batch, sequence and width all
    grow with the mesh spec and ``OTPU_MODEL_SCALE``, and a summed loss
    would grow the effective step with them until the widest meshes
    diverge.  The default falls monotonically for a dozen steps at
    scales 1 and 64 on every tested mesh.
    """
    dims = model_dims(spec, layers)
    tp, sp_n, pp = spec.tp, spec.sp, spec.pp
    M, mb, s_l, d = dims["M"], dims["mb"], dims["s_local"], dims["d"]
    n_elems = dims["batch"] * dims["seq"] * d

    def stage_fn(stage_params, x_mb):
        for i in range(dims["layers_local"]):
            layer = jax.tree.map(lambda a: a[i], stage_params)
            x_mb = transformer_block(
                layer, x_mb, sp=sp_n, tp=tp,
                n_heads_local=dims["h_local"],
                n_experts=dims["n_experts"], capacity=dims["capacity"])
        return x_mb

    def otpu_flagship_step(params, x):
        def loss_fn(ps):
            y = pipeline_apply(stage_fn, ps, x.reshape(M, mb, s_l, d), pp=pp,
                               vary_axes=("pp", "tp"))
            # pipeline_apply outputs are zero off the last pp stage, so
            # the psum over pp collects exactly the last stage's loss.
            # y is value-replicated across tp but vma-varying (it came
            # through tp collectives): count the tp=0 replica only, so
            # the psum over ALL axes is both value-correct and provably
            # unvarying — gradients to the other tp shards still flow
            # through the block's internal tp-psum transposes
            local = (0.5 / n_elems) * jnp.sum(y * y)     # global mean
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
        grads = jax.tree.map(lambda g: jax.lax.psum(g, ("dp", "sp")), grads)
        grads["wr"] = jax.lax.psum(grads["wr"], "tp")
        new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new, loss

    pspecs = param_specs()
    # check_vma=True is LOAD-BEARING for correctness, not just a lint:
    # the varying-manifest tracking is what makes the ppermute/psum
    # transposes in the pp>=2 backward correct.  With it off the
    # composed step compiles and descends — with silently wrong
    # pipeline gradients (caught by test_pp2_matches_pp1_same_model).
    step = jax.jit(shard_map(
        otpu_flagship_step, mesh=mesh,
        in_specs=(pspecs, P("dp", "sp", None)),
        out_specs=(pspecs, P()),
        check_vma=True))

    def place(params, x_np):
        p = {k: jax.device_put(v, NamedSharding(mesh, pspecs[k]))
             for k, v in params.items()}
        x = jax.device_put(
            np.asarray(x_np, np.float32),
            NamedSharding(mesh, P("dp", "sp", None)))
        return p, x

    return step, place
