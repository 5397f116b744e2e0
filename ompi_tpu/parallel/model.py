"""Per-device transformer block bodies with explicit mesh collectives.

These run *inside* ``shard_map`` — the MPI-flavoured explicit-SPMD style:
every cross-device exchange is a named collective on a mesh axis, the
device-side mirror of the reference's coll algorithms (ring allreduce
``coll_base_allreduce.c:341``, pairwise alltoall ``coll_base_alltoall.c``,
binomial pipelines) rather than GSPMD auto-propagation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ompi_tpu.base.jaxenv import pallas_interpret


def rmsnorm(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def ring_attention(q, k, v, axis: str, n_shards: int, use_flash=None,
                   causal: bool = False, interpret=None):
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

    The per-step block combine (two MXU matmuls + online-softmax rescale)
    is the hot op: on TPU it drops into the fused Pallas kernel
    (``ompi_tpu/ops/flash_attention.py``); the ring structure itself stays
    at the XLA level so the compiler schedules the ICI ppermute.

    ``interpret`` is the Pallas mode of the devices this is traced for
    (None: the process's default devices); the fused kernel is the
    default exactly where it compiles through Mosaic.
    """
    hd = q.shape[-1]
    s_local = q.shape[-2]
    scale = 1.0 / math.sqrt(hd)
    if interpret is None:
        interpret = pallas_interpret()
    if use_flash is None:
        use_flash = not interpret
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
        # carry under bfloat16 compute and break lax.scan's
        # carry-type invariant; the flash kernel upcasts internally
        return jnp.where(qpos >= kpos, 0.0, -jnp.inf).astype(q.dtype)

    def body(carry, t):
        k_blk, v_blk, m, num, den = carry
        bias = step_bias(t) if causal else None
        if use_flash:
            from ompi_tpu.ops.flash_attention import (
                flash_block_update, flash_block_update_biased)

            if causal:
                new_m, num, den = flash_block_update_biased(
                    q, k_blk, v_blk, m, num, den, bias, interpret)
            else:
                new_m, num, den = flash_block_update(q, k_blk, v_blk, m,
                                                     num, den, interpret)
        else:
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
            if bias is not None:
                s = s + bias
            new_m = jnp.maximum(m, s.max(axis=-1))
            c = jnp.exp(m - new_m)
            p = jnp.exp(s - new_m[..., None])
            num = num * c[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
            den = den * c + p.sum(axis=-1)
        if n_shards > 1:
            k_blk = jax.lax.ppermute(k_blk, axis, perm)
            v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return (k_blk, v_blk, new_m, num, den), None

    (_, _, _, num, den), _ = jax.lax.scan(
        body, (k, v, m0, num0, den0), jnp.arange(n_shards))
    return num / den[..., None]


def ulysses_attention(q, k, v, axis: str, n_shards: int,
                      causal: bool = False):
    """DeepSpeed-Ulysses sequence parallelism: all-to-all head↔sequence
    reshard instead of the ring's K/V rotation.

    q/k/v local: (b, h_local, s_local, hd) with h_local % n_shards == 0.
    One ``all_to_all`` turns the sequence axis local-complete (each shard
    keeps h_local/n_shards heads over the FULL sequence), attention runs
    locally with no inter-step dependency, and the inverse all_to_all
    restores sequence sharding.  Two reshard phases (four ``all_to_all``
    calls: q/k/v scatter + the output inverse) vs the ring's
    n_shards ppermute steps — better for short-ish sequences on fast ICI;
    the ring wins at very long context (O(s_local) memory).  The MoE-
    dispatch-shaped exchange of SURVEY.md §2.6's alltoall row.
    """
    if n_shards == 1:
        return _full_attention(q, k, v, causal)

    def scatter_heads(t):   # (b, h_l, s_l, hd) -> (b, h_l/n, s, hd)
        return jax.lax.all_to_all(t, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    # after the reshard each shard holds the FULL sequence, so the
    # causal mask is the plain global lower-triangle
    o = _full_attention(scatter_heads(q), scatter_heads(k),
                        scatter_heads(v), causal)  # (b, h_l/n, s, hd)
    # inverse reshard: full-sequence heads -> my seq block, all heads
    return jax.lax.all_to_all(o, axis, split_axis=2, concat_axis=1,
                              tiled=True)


def _full_attention(q, k, v, causal: bool = False):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        sq, skv = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def attention_block(p, x, *, sp: int, tp: int, n_heads_local: int,
                    interpret: bool, sp_impl: str = "ring",
                    causal: bool = False):
    """Sequence-parallel attention with tp-sharded heads; psum output proj.

    ``interpret`` is the Pallas mode of the mesh this is traced for,
    resolved once by the caller (``build_train_step``): it decides both
    whether ring attention takes the fused kernel and how that compiles.

    x local: (b, s_local, d) replicated over tp.  Head projections are
    column-sharded over tp (h_local = H/tp); the output projection is
    row-sharded, so its partial products combine with a ``psum`` over tp —
    the tensor-parallel allreduce (DP/TP table row, SURVEY.md §2.6).

    ``sp_impl`` picks the context-parallel scheme: "ring" (ppermute K/V
    rotation, O(s_local) memory — long context) or "ulysses" (all-to-all
    head↔seq reshard, 2 collectives — short/medium context on fast ICI).
    """
    b, s_l, d = x.shape
    h = rmsnorm(x)

    def heads(w):
        y = h @ w  # (b, s_l, h_local*hd)
        return y.reshape(b, s_l, n_heads_local, -1).transpose(0, 2, 1, 3)

    q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
    if sp_impl == "ulysses" and sp > 1:
        if n_heads_local % sp:
            # silent ring fallback would invalidate any collective-count
            # comparison the user is running — fail loudly instead
            raise ValueError(
                f"ulysses needs local heads divisible by sp "
                f"({n_heads_local} % {sp}); use sp_impl='ring'")
        o = ulysses_attention(q, k, v, "sp", sp,
                              causal=causal)        # (b, h_l, s_l, hd)
    else:
        o = ring_attention(q, k, v, "sp", sp, causal=causal,
                           interpret=interpret)     # (b, h_l, s_l, hd)
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
    # production token counts (compute_dtype must not leak here)
    oh = jax.nn.one_hot(eid, n_experts, dtype=jnp.float32)       # (tc, E)
    pos = (jnp.cumsum(oh, axis=0) - 1.0) * oh                    # (tc, E)
    keep = oh * (pos < capacity)
    pos_oh = jax.nn.one_hot(
        jnp.clip(pos.astype(jnp.int32), 0, capacity - 1), capacity,
        dtype=xf.dtype)                                          # (tc, E, cap)
    # mask back to compute dtype (exact 0/1): the expert einsums
    # and the residual must stay in compute precision
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


def transformer_block(p, x, *, sp, tp, n_heads_local, n_experts, capacity,
                      interpret: bool, sp_impl: str = "ring",
                      causal: bool = False):
    x = attention_block(p, x, sp=sp, tp=tp, n_heads_local=n_heads_local,
                        sp_impl=sp_impl, causal=causal, interpret=interpret)
    x = mlp_block(p, x, tp=tp)
    x = moe_block(p, x, tp=tp, n_experts=n_experts, capacity=capacity)
    return x


# -- a public model's blocks (OLMoE, JoyAI-LLM-Flash, Nemotron-3-Super):
# parallel/train.py's model path ---------------------------------------------
def cast_param(w, dtype):
    """A parameter leaf in the matmuls' ``dtype``, under the scope
    ``otpu_cast``: XLA makes a pass of its own of a large leaf's cast
    (and of its transposition), which a trace then tells from the
    sublayer's other work."""
    with jax.named_scope("otpu_cast"):
        return w.astype(dtype)


def matmul(a, w, compute_dtype, weight: bool = True):
    """``a @ w`` with inputs in ``compute_dtype`` and a float32 result:
    bfloat16 inputs accumulate in float32 on the MXU; float32 inputs
    multiply at the highest precision (on a TPU the default would round
    them to bfloat16 on the way in).  ``w`` is a parameter leaf
    (``cast_param``) unless ``weight`` is false."""
    f32 = jnp.dtype(compute_dtype) == jnp.float32
    dtype = jnp.float32 if f32 else compute_dtype
    a = a.astype(dtype)
    w = cast_param(w, dtype) if weight else w.astype(dtype)
    if f32:
        return jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST)
    return jnp.dot(a, w, preferred_element_type=jnp.float32)


def rmsnorm_gain(x, gain, eps: float):
    """RMSNorm with a learned gain, in float32 whatever ``x`` is."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def rope(x, theta: float):
    """Rotary position embedding on ``x`` (b, h, s, hd) at positions
    0..s-1, the half-split form of the HF models (``rotate_half``)."""
    hd, s = x.shape[-1], x.shape[-2]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)           # (s, hd)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _tri_bias(block: int):
    i = jnp.arange(block)
    return jnp.where(i[:, None] >= i[None, :], 0.0,
                     -jnp.inf).astype(jnp.float32)


def _contract(eq, a, b, compute_dtype):
    """A float32 einsum of blocked attention, its inputs in
    ``compute_dtype``."""
    if jnp.dtype(compute_dtype) == jnp.float32:
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum(eq, a.astype(compute_dtype), b.astype(compute_dtype),
                      preferred_element_type=jnp.float32)


def _causal_fwd_blocks(q, k, v, block, interpret):
    """Causal attention's forward pass: (o float32, logsumexp float32)
    of q, k (b, h, s, hd) and v (b, h, s, hv); v, and so the numerator
    and o, may be of another width than q and k (latent attention: 192
    and 128).  Where Mosaic compiles (``interpret`` false: a TPU) it is
    one call of ``ops/flash_attention.flash_causal_forward``, which
    takes the three whole.  Elsewhere (the CPU) it is the loop below,
    that kernel's ``jnp`` twin: q block i meets kv blocks 0..i of
    ``block`` positions, the diagonal one under a triangular bias, each
    through one online-softmax update with float32 scores; the running
    max, numerator and denominator are float32 whatever q, k, v are."""
    if not interpret:
        from ompi_tpu.ops.flash_attention import flash_causal_forward

        return flash_causal_forward(q, k, v, block=block, interpret=False)
    b, h, s, hd = q.shape
    nb = s // block
    scale = 1.0 / math.sqrt(hd)
    bias = _tri_bias(block)
    outs, lses = [], []
    for i in range(nb):
        qi = q[:, :, i * block:(i + 1) * block]
        zero = (qi[..., 0] * 0).astype(jnp.float32)    # carries q's vma
        m, den = zero - jnp.inf, zero
        num = jnp.zeros(v.shape[-1:], jnp.float32) + zero[..., None]
        for j in range(i + 1):
            kj = k[:, :, j * block:(j + 1) * block]
            vj = v[:, :, j * block:(j + 1) * block]
            sc = _contract("bhqd,bhkd->bhqk", qi, kj, q.dtype) * scale
            if j == i:
                sc = sc + bias
            new_m = jnp.maximum(m, sc.max(axis=-1))
            c = jnp.exp(m - new_m)
            p = jnp.exp(sc - new_m[..., None])
            num = num * c[..., None] + _contract("bhqk,bhkd->bhqd", p, vj,
                                                 q.dtype)
            den = den * c + p.sum(axis=-1)
            m = new_m
        outs.append(num / den[..., None])
        lses.append(m + jnp.log(den))
    return jnp.concatenate(outs, axis=2), jnp.concatenate(lses, axis=2)


# what a layer's ``jax.checkpoint`` keeps of causal attention
# (``train.layer_checkpoint_policy`` saves these beside an expert block's):
# the forward kernel's two results, float32 as it writes them, which are
# all its backward pass reads beside q, k and v.  Named in the forward
# rule before anything reads them, so that a checkpointed layer's
# backward pass holds no second run of the kernel.
ATTN_OUT = "otpu_attn_out"
ATTN_LSE = "otpu_attn_lse"
CHECKPOINT_KEEPS = (ATTN_OUT, ATTN_LSE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def causal_flash_attention(q, k, v, block: int, interpret: bool):
    """Causal self-attention of (b, h, s, hd) q, k and (b, h, s, hv) v
    whose length is a multiple of ``block``.  Forward:
    ``_causal_fwd_blocks`` (on a TPU one kernel call, the blocks chosen
    in its index maps; on the CPU a ``jnp`` loop over the blocks).
    Backward: the flash backward by the same blocks (scores recomputed
    from q, k and the saved logsumexp in float32; no (s, s) array is
    ever held), its matmul inputs in q's dtype; on a TPU each block pair
    one call of the fused kernel (``_causal_bwd_fused``), on the CPU
    ``_bwd_pair``'s einsums."""
    return _causal_fwd_blocks(q, k, v, block, interpret)[0]


def _causal_fwd(q, k, v, block, interpret):
    o, lse = _causal_fwd_blocks(q, k, v, block, interpret)
    o = checkpoint_name(o, ATTN_OUT)
    lse = checkpoint_name(lse, ATTN_LSE)
    return o, (q, k, v, o, lse)


#: up to this many blocks the backward pass's block pairs are unrolled
#: (10 pairs at OLMoE's 4 blocks: what that step has always compiled
#: to); beyond it they are walked by one ``lax.scan``, a pair's scores
#: held at a time.  Unrolled, the 36 pairs of 8 blocks let XLA hold 15
#: and more (h, block, block) float32 score blocks at once: 19.6 GB for
#: the JoyAI step (offline compile for a v5e, PR 35)
UNROLLED_BLOCKS = 4


def _bwd_pair(qi, kj, vj, doi, lse_i, delta_i, bias, scale, dt):
    """One block pair of the flash backward: (dq, dk, dv) parts."""
    sc = _contract("bhqd,bhkd->bhqk", qi, kj, dt) * scale
    if bias is not None:
        sc = sc + bias
    p = jnp.exp(sc - lse_i[..., None])
    dv = _contract("bhqk,bhqd->bhkd", p, doi, dt)
    dp = _contract("bhqd,bhkd->bhqk", doi, vj, dt)
    ds = p * (dp - delta_i[..., None]) * scale
    return (_contract("bhqk,bhkd->bhqd", ds, kj, dt),
            _contract("bhqk,bhqd->bhkd", ds, qi, dt), dv)


def _causal_bwd(block, interpret, res, do):
    q, k, v, o, lse = res
    nb = q.shape[2] // block
    do = do.astype(jnp.float32)
    delta = jnp.sum(do * o, axis=-1)                     # (b, h, s)
    if not interpret:
        return _causal_bwd_fused(q, k, v, do, lse, delta, block)
    if nb > UNROLLED_BLOCKS:
        return _causal_bwd_scanned(q, k, v, do, lse, delta, block)
    dt = q.dtype
    scale = 1.0 / math.sqrt(q.shape[-1])
    bias = _tri_bias(block)
    cut = lambda a, i: a[:, :, i * block:(i + 1) * block]
    dq = [0.0] * nb
    dk = [0.0] * nb
    dv = [0.0] * nb
    for i in range(nb):
        qi, doi = cut(q, i), cut(do, i)
        lse_i, delta_i = cut(lse, i), cut(delta, i)
        for j in range(i + 1):
            dq_c, dk_c, dv_c = _bwd_pair(
                qi, cut(k, j), cut(v, j), doi, lse_i, delta_i,
                bias if j == i else None, scale, dt)
            dq[i], dk[j], dv[j] = dq[i] + dq_c, dk[j] + dk_c, dv[j] + dv_c
    cat = lambda parts: jnp.concatenate(parts, axis=2).astype(dt)
    return cat(dq), cat(dk), cat(dv)


def _causal_bwd_scanned(q, k, v, do, lse, delta, block):
    """The same pairs in the same order (q block by q block, kv blocks
    ascending), one a step of a ``lax.scan`` over float32 accumulators."""
    dt = q.dtype
    b, h, s, _ = q.shape
    nb = s // block
    scale = 1.0 / math.sqrt(q.shape[-1])
    tri = _tri_bias(block)
    blocks = lambda a: jnp.moveaxis(
        a.reshape(b, h, nb, block, *a.shape[3:]), 2, 0)
    qb, kb, vb, dob = blocks(q), blocks(k), blocks(v), blocks(do)
    lseb, deltab = blocks(lse), blocks(delta)
    pairs = [(i, j) for i in range(nb) for j in range(i + 1)]
    zero = lambda a: (a * 0).astype(jnp.float32)         # carries a's vma

    def step(acc, ij):
        i, j = ij
        dq_c, dk_c, dv_c = _bwd_pair(
            qb[i], kb[j], vb[j], dob[i], lseb[i], deltab[i],
            jnp.where(i == j, tri, 0.0), scale, dt)
        dq, dk, dv = acc
        return (dq.at[i].add(dq_c), dk.at[j].add(dk_c),
                dv.at[j].add(dv_c)), None

    (dq, dk, dv), _ = jax.lax.scan(
        step, (zero(qb), zero(kb), zero(vb)),
        (jnp.asarray([p[0] for p in pairs]),
         jnp.asarray([p[1] for p in pairs])))
    whole = lambda a: jnp.moveaxis(a, 0, 2).reshape(
        b, h, s, a.shape[-1]).astype(dt)
    return whole(dq), whole(dk), whole(dv)


def _causal_bwd_fused(q, k, v, do, lse, delta, block):
    """The same pairs in the same order, each one call of the fused
    Pallas kernel (``ops/flash_attention.attn_block_backward``, whose
    ``jnp`` twin is ``_bwd_pair``): a pair's scores never leave VMEM,
    and the float32 accumulators pass through every call in place.
    Both walks: unrolled up to ``UNROLLED_BLOCKS`` blocks, one
    ``lax.scan`` beyond; the arrays go in whole and the pair is an
    operand, so neither slices."""
    from ompi_tpu.ops.flash_attention import attn_block_backward

    dt = q.dtype
    nb = q.shape[2] // block
    do = do.astype(dt)                  # what ``_contract`` makes of it
    pair = lambda acc, ij: attn_block_backward(
        ij, q, k, v, do, lse, delta, *acc, block=block, interpret=False)
    pairs = [(i, j) for i in range(nb) for j in range(i + 1)]
    acc = tuple(jnp.zeros(a.shape, jnp.float32) for a in (q, k, v))
    vma = tuple(frozenset().union(*(jax.typeof(a).vma
                                    for a in (q, k, v, do))))
    if vma:                 # the carry varies as the kernel's results do
        acc = jax.lax.pcast(acc, vma, to="varying")
    if nb > UNROLLED_BLOCKS:
        acc, _ = jax.lax.scan(lambda acc, ij: (pair(acc, ij), None), acc,
                              jnp.asarray(pairs, jnp.int32))
    else:
        for ij in pairs:
            acc = pair(acc, jnp.asarray(ij, jnp.int32))
    return tuple(a.astype(dt) for a in acc)


causal_flash_attention.defvjp(_causal_fwd, _causal_bwd)


def olmoe_attention(p, x, cfg, *, interpret: bool):
    """OLMoE's attention sublayer on the residual stream ``x`` (b, s, d)
    float32: pre-norm; q, k, v, o projections without bias; RMSNorm with
    a gain over the whole width of q and of k **before** the heads are
    split (QK-norm); RoPE; causal attention; residual add."""
    b, s, d = x.shape
    nh, dt = cfg.num_attention_heads, cfg.compute_dtype
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps)
        q = rmsnorm_gain(matmul(h, p["wq"], dt), p["q_norm"],
                         cfg.rms_norm_eps)
        k = rmsnorm_gain(matmul(h, p["wk"], dt), p["k_norm"],
                         cfg.rms_norm_eps)
        v = matmul(h, p["wv"], dt)
        heads = lambda t: t.reshape(b, s, nh, -1).transpose(0, 2, 1, 3)
        q, k = rope(heads(q), cfg.rope_theta), rope(heads(k), cfg.rope_theta)
        q, k, v = q.astype(dt), k.astype(dt), heads(v).astype(dt)
    o = causal_flash_attention(q, k, v, min(cfg.attn_block, s), interpret)
    with jax.named_scope("otpu_attn_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
        return x + matmul(o, p["wo"], dt)


def _rope_tables(x, theta: float, first: int, seq_axis: int):
    """(cos, sin) of ``rope_interleaved``, shaped to broadcast against
    ``x``: a pair's angle on both its entries, 1 and 0 on the entries
    before ``first``."""
    width, s = x.shape[-1], x.shape[seq_axis]
    hd = width - first
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    pad = lambda a, fill: jnp.concatenate(
        [jnp.full((s, first), fill, jnp.float32), jnp.repeat(a, 2, -1)], -1)
    shape = [1] * x.ndim
    shape[seq_axis], shape[-1] = s, width
    return (pad(jnp.cos(ang), 1.0).reshape(shape),
            pad(jnp.sin(ang), 0.0).reshape(shape))


def rope_interleaved(x, theta: float, first: int = 0, seq_axis: int = -2):
    """Rotary position embedding on interleaved pairs (x[2i], x[2i+1])
    (DeepSeek-V3's ``rope_interleave``) of the entries from ``first`` on
    of ``x``'s last axis, at positions 0..s-1 along ``seq_axis``; the
    entries before ``first`` pass unchanged.  The pair's partner comes
    by ``jnp.roll``, which XLA for a TPU writes to HBM as shifted copies
    (a 191-wide and a one-lane slice each way, the lane padded to 128:
    2.6 GB a layer and pass of the JoyAI step for q's 268 MB, offline
    compile, PR 41), so the model no longer takes this way
    (``project_rope``): it is the ``jnp`` twin the tests compare that
    with."""
    cos, sin = _rope_tables(x, theta, first, seq_axis)
    is_first = (jnp.arange(x.shape[-1]) - first) % 2 == 0
    partner = jnp.where(is_first, -jnp.roll(x, -1, -1), jnp.roll(x, 1, -1))
    return x * cos + partner * sin


def rotary_partner_columns(w, compute_dtype):
    """The columns ``wp`` of interleaved rotary columns ``w`` (.., rot)
    with ``a @ wp`` the rotary partner of ``a @ w``: ``wp[:, 2i] =
    -w[:, 2i+1]``, ``wp[:, 2i+1] = w[:, 2i]``.  A product with a signed
    permutation (each result one input times 1 or -1: exact in any
    dtype), because a swap of neighbouring columns any other way is a
    lane rotation or an array two lanes wide."""
    rot = w.shape[-1]
    i = jnp.arange(0, rot, 2)
    swap = jnp.zeros((rot, rot), jnp.float32) \
        .at[i, i + 1].set(1.0).at[i + 1, i].set(-1.0)
    return matmul(w, swap, compute_dtype, weight=False).astype(w.dtype)


def rope_partnered(x, partner, theta: float, seq_axis: int = -2):
    """``rope_interleaved`` of ``x`` on its trailing ``partner.shape[-1]``
    entries, given their partners (``partner[2i] = -x[2i+1]``,
    ``partner[2i+1] = x[2i]``, counted from the first rotary entry): one
    elementwise pass, the partner set behind the leading entries by a pad
    (where those are a multiple of 128 lanes, as a latent head's are, it
    starts a tile of its own)."""
    first = x.shape[-1] - partner.shape[-1]
    cos, sin = _rope_tables(x, theta, first, seq_axis)
    partner = jnp.pad(partner, ((0, 0),) * (x.ndim - 1) + ((first, 0),))
    return x * cos + partner * sin


def project_rope(a, w, heads: int, first: int, theta: float, compute_dtype):
    """``a @ w`` (b, s, heads x width) split into ``heads`` with
    ``rope_interleaved(.., first=first, seq_axis=1)`` on each, float32
    (b, s, heads, width), with no shifted copy of the product: the
    partner of column j of ``a @ w`` is, sign apart, column j^1 of the
    same product, so ``a @ rotary_partner_columns(w's rotary columns)``
    **is** the partner, the same dot products of the same inputs
    accumulated the same way (for JoyAI's q 51 GFLOP a layer and pass in
    place of the 2.6 GB the rolled copies moved, PR 41).  Its gradient
    is autodiff's: elementwise passes and matmuls."""
    b, s, _ = a.shape
    w = cast_param(w, compute_dtype).reshape(w.shape[0], heads, -1)
    wp = rotary_partner_columns(w[..., first:], compute_dtype)
    dot = lambda cols: matmul(a, cols.reshape(cols.shape[0], -1),
                              compute_dtype, weight=False) \
        .reshape(b, s, heads, -1)
    return rope_partnered(dot(w), dot(wp), theta, seq_axis=1)


def mla_attention(p, x, cfg, *, interpret: bool):
    """DeepSeek-V3's latent attention sublayer (arXiv:2412.19437 section
    2.1.1) on the residual stream ``x`` (b, s, d) float32: pre-norm; q
    through a normed latent of ``q_lora_rank``; k's no-position part and
    v through a normed latent of ``kv_lora_rank``; one rotary key of
    ``qk_rope_head_dim`` that every head shares; causal ``softmax(q k^T
    / sqrt(nope + rope)) v`` with q, k of one width and v of another;
    residual add.  The two inner norms, RoPE and the softmax in float32;
    matmul inputs in ``compute_dtype``.  Training holds no cache, so the
    latents are expanded to full keys and values.  q and the shared
    rotary key leave their projections with RoPE on (``project_rope``)."""
    b, s, _ = x.shape
    nh, dt, eps = cfg.num_attention_heads, cfg.compute_dtype, cfg.rms_norm_eps
    nope, rot, hv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rank, theta = cfg.kv_lora_rank, cfg.rope_theta
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], eps)
        cq = rmsnorm_gain(matmul(h, p["wq_a"], dt), p["q_a_norm"], eps)
        q = project_rope(cq, p["wq_b"], nh, nope, theta, dt)
        # (b, s, rank + rot), the rotary key behind the latent
        kv = project_rope(h, p["wkv_a"], 1, rank, theta, dt)[:, :, 0]
        ckv = rmsnorm_gain(kv[..., :rank], p["kv_a_norm"], eps)
        kvb = matmul(ckv, p["wkv_b"], dt).reshape(b, s, nh, nope + hv)
        k = jnp.concatenate([kvb[..., :nope].astype(dt), jnp.broadcast_to(
            kv[:, :, None, rank:].astype(dt), (b, s, nh, rot))], -1)
        heads = lambda t: t.transpose(0, 2, 1, 3)        # (b, nh, s, .)
        q, k, v = (heads(q.astype(dt)), heads(k),
                   heads(kvb[..., nope:].astype(dt)))
    o = causal_flash_attention(q, k, v, min(cfg.attn_block, s), interpret)
    with jax.named_scope("otpu_attn_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * hv)
        return x + matmul(o, p["wo"], dt)


def swiglu(h, gate, up, down, compute_dtype):
    """``down(silu(gate h) * up h)``: a dense feed-forward, or a shared
    expert, on rows ``h`` (T, d)."""
    act = jax.nn.silu(matmul(h, gate, compute_dtype)) \
        * matmul(h, up, compute_dtype)
    return matmul(act, down, compute_dtype)


def relu2(h, up, down, compute_dtype):
    """``down(relu(up h)^2)``: nemotron_h's feed-forward (no gate), a
    shared expert on rows ``h`` (T, d)."""
    act = jnp.square(jax.nn.relu(matmul(h, up, compute_dtype)))
    return matmul(act, down, compute_dtype)


def gqa_attention(p, x, cfg, *, interpret: bool):
    """nemotron_h's attention sublayer, **without** the residual add, on
    the residual stream ``x`` (b, s, d) float32: pre-norm; q, k, v, o
    projections without bias; the ``n_heads_here`` query heads held here
    and the ``n_kv_heads_here`` key-value heads they read (each read by
    ``num_attention_heads / num_key_value_heads`` query heads of the
    model, by as many of those as are held here); no rotary embedding
    (the positions come from the state-space layers); causal softmax
    attention.  The flash kernels take one k and v a query head, so the
    key-value heads are repeated into that layout (the repeat's
    transpose adds the query heads' gradients up)."""
    b, s, _ = x.shape
    nh, nkv, dt = cfg.n_heads_here, cfg.n_kv_heads_here, cfg.compute_dtype
    with jax.named_scope("otpu_attn_proj"):
        h = rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps)
        heads = lambda t, n: t.reshape(b, s, n, -1).transpose(
            0, 2, 1, 3).astype(dt)
        q = heads(matmul(h, p["wq"], dt), nh)
        k, v = (jnp.repeat(heads(matmul(h, p[w], dt), nkv), nh // nkv, 1)
                for w in ("wk", "wv"))
    o = causal_flash_attention(q, k, v, min(cfg.attn_block, s), interpret)
    with jax.named_scope("otpu_attn_proj"):
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return matmul(o, p["wo"], dt)


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """Mamba-2's state-space scan (arXiv:2405.21060, the chunked form of
    its section 6) in float32: per head the state ``h_t = exp(dt_t a)
    h_{t-1} + dt_t x_t b_t^T`` (p x n) and the output ``y_t = h_t c_t``,
    from a zero state, never reset.  ``x`` (bt, s, h, p); ``dt`` (bt, s,
    h), positive; ``a`` (h,), negative; ``b``, ``c`` (bt, s, g, n), each
    group's shared by ``h / g`` consecutive heads.  Returns y (bt, s, h,
    p), without the skip term.

    The sequence is cut into chunks of ``chunk`` positions (padded at
    the end with dt = 0, which leaves the state as it is).  Inside a
    chunk position i reads position j <= i through ``exp(sum_{j<k<=i}
    dt_k a)``, a (chunk, chunk) matrix a head times ``c_i . b_j``; each
    chunk leaves ``sum_j exp(sum_{k>j} dt_k a) dt_j x_j b_j^T`` to the
    state; the states go from chunk to chunk by a ``lax.scan`` of
    ``s / chunk`` steps; position i of a chunk reads the state that
    entered it through ``exp(sum_{k<=i} dt_k a)``.  The running sums,
    the exponentials, the states and every product are float32 at the
    highest precision: at a chip's share of the heads they are under a
    hundredth of a layer's operations.  The backward pass is autodiff's
    through the same chunks."""
    bt, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    _f32 = lambda eq, one, two: _contract(eq, one, two, jnp.float32)
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    # (bt, chunks, groups, heads a group, position, .)
    xd = (x * dt[..., None]).reshape(bt, nc, chunk, g, r, p) \
        .transpose(0, 1, 3, 4, 2, 5)
    da = (dt * a).reshape(bt, nc, chunk, g, r).transpose(0, 1, 3, 4, 2)
    bc = b.reshape(bt, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cc = c.reshape(bt, nc, chunk, g, n).transpose(0, 1, 3, 2, 4)
    cum = jnp.cumsum(da, axis=-1)                        # sum_{k<=i} dt_k a
    i = jnp.arange(chunk)
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                              cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                 # (.., i, j)
    cb = _f32("zcgin,zcgjn->zcgij", cc, bc)
    y = _f32("zcgrij,zcgrjp->zcgrip", cb[:, :, :, None] * decay, xd)
    to_end = jnp.exp(cum[..., -1:] - cum)                # (.., j)
    left = _f32("zcgrjp,zcgjn->zcgrpn", xd * to_end[..., None], bc)
    through = jnp.exp(cum[..., -1])                      # a chunk's decay

    def carry(state, xs):
        left_c, through_c = xs
        return state * through_c[..., None, None] + left_c, state

    _, entered = jax.lax.scan(
        carry, left[:, 0] * 0,                           # carries x's vma
        (jnp.moveaxis(left, 1, 0), jnp.moveaxis(through, 1, 0)))
    y = y + _f32("zcgin,zcgrpn->zcgrip", cc, jnp.moveaxis(entered, 0, 1)) \
        * jnp.exp(cum)[..., None]
    return y.transpose(0, 1, 4, 2, 3, 5).reshape(bt, s + pad, h, p)[:, :s]


def mamba_mixer(p, x, cfg):
    """nemotron_h's Mamba-2 mixer, **without** the residual add, on the
    residual stream ``x`` (b, s, d) float32, for the ``n_mamba_heads_here``
    heads and ``n_groups_here`` B/C groups held here: pre-norm; ``[z |
    xBC | dt] = u W_in`` (matmul inputs in ``compute_dtype``); ``xBC <-
    silu(causal depthwise convolution over conv_kernel positions, with
    bias)``, split into x (heads x ``mamba_head_dim``), B and C (groups x
    ``ssm_state_size``); ``dt <- softplus(dt + dt_bias)``, ``a =
    -exp(A_log)``; the scan in chunks of ``chunk_size``
    (``ssd_chunked``) plus ``D x``; ``rmsnorm over each group of (y *
    silu(z)) * gain``; ``y W_out``.  Everything between the two
    projections is float32.  Returns (the sublayer's output, what the
    scan read and made of the first held head, by token row: its step
    ``ssm_dt_seq`` (T,), its ``ssm_x_seq`` (T, p) and its group's
    ``ssm_b_seq`` and ``ssm_c_seq`` (T, n) whole, because a position's
    state holds every earlier one, and the scan's ``ssm_y`` (T, p)
    before the skip term)."""
    b, s, d = x.shape
    nh, hd, n = cfg.n_mamba_heads_here, cfg.mamba_head_dim, cfg.ssm_state_size
    g, dt, eps = cfg.n_groups_here, cfg.compute_dtype, cfg.rms_norm_eps
    inner = nh * hd
    with jax.named_scope("otpu_ssm_proj"):
        u = rmsnorm_gain(x, p["norm"], eps)
        zxd = matmul(u.reshape(b * s, d), p["in_proj"], dt).reshape(b, s, -1)
        z, xbc, step = (zxd[..., :inner], zxd[..., inner:-nh], zxd[..., -nh:])
    with jax.named_scope("otpu_ssm_conv"):
        taps = p["conv_w"].shape[0]
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        xbc = jax.nn.silu(p["conv_b"] + sum(
            padded[:, k:k + s] * p["conv_w"][k] for k in range(taps)))
    with jax.named_scope("otpu_ssm_scan"):
        xs = xbc[..., :inner].reshape(b, s, nh, hd)
        bs, cs = (xbc[..., inner + k * g * n:inner + (k + 1) * g * n]
                  .reshape(b, s, g, n) for k in (0, 1))
        step = jax.nn.softplus(step + p["dt_bias"])
        y = ssd_chunked(xs, step, -jnp.exp(p["A_log"]), bs, cs,
                        cfg.chunk_size)
        rows = lambda t: t.reshape((b * s,) + t.shape[2:])
        seen = {"ssm_dt_seq": rows(step[:, :, 0]),
                "ssm_x_seq": rows(xs[:, :, 0]),
                "ssm_b_seq": rows(bs[:, :, 0]),
                "ssm_c_seq": rows(cs[:, :, 0]), "ssm_y": rows(y[:, :, 0])}
        y = (y + p["D"][:, None] * xs).reshape(b, s, inner)
    with jax.named_scope("otpu_ssm_norm"):
        y = (y * jax.nn.silu(z)).reshape(b, s, g, inner // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y.reshape(b, s, inner) * p["gate_norm"]
    with jax.named_scope("otpu_ssm_proj"):
        return matmul(y.reshape(b * s, inner), p["out_proj"], dt
                      ).reshape(b, s, d), seen


def decoder_layer(p, x, cfg, *, interpret: bool, bias=None):
    """One decoder layer of a public model, its sublayers chosen by what
    the layer holds and the configuration's published keys say.  A layer
    of a ``hybrid_override_pattern`` has **one** sublayer: the Mamba-2
    mixer where it holds ``in_proj``, the latent relu2 expert block
    (``moe.moe_latent_block``) where it holds a router, else
    grouped-query attention without RoPE.  Any other model's layer has
    attention (latent where ``kv_lora_rank`` is set, else OLMoE's) and
    then a dense SwiGLU where the layer has no router, else the sparse
    MLP (``moe.moe_sorted_block``: every expert here, softmax scores; or
    ``moe.moe_shared_local_block``: a share of the experts beside a
    shared one, sigmoid scores chosen under ``bias``).  Returns (x, the
    router's statistics, what the router, or a mixer's scan, read and
    made by token row); the last two are empty for a layer with
    neither."""
    from ompi_tpu.parallel import moe

    if cfg.hybrid_override_pattern:
        if "in_proj" in p:
            with jax.named_scope("otpu_mamba"):
                y, seen = mamba_mixer(p, x, cfg)
            return x + y, {}, seen
        if "router" not in p:
            with jax.named_scope("otpu_attention"):
                return x + gqa_attention(p, x, cfg, interpret=interpret), \
                    {}, {}
        with jax.named_scope("otpu_moe"):
            y, stats, routed = moe.moe_latent_block(p, x, cfg, bias)
        return x + y, stats, routed
    if cfg.kv_lora_rank:
        with jax.named_scope("otpu_mla"):
            x = mla_attention(p, x, cfg, interpret=interpret)
    else:
        with jax.named_scope("otpu_attention"):
            x = olmoe_attention(p, x, cfg, interpret=interpret)
    if "router" not in p:
        with jax.named_scope("otpu_dense_mlp"):
            h = rmsnorm_gain(x, p["ln2"], cfg.rms_norm_eps)
            y = swiglu(h.reshape(-1, h.shape[-1]), p["gate"], p["up"],
                       p["down"], cfg.compute_dtype)
        return x + y.reshape(x.shape), {}, {}
    with jax.named_scope("otpu_moe"):
        if cfg.scoring_func == "sigmoid":
            y, stats, routed = moe.moe_shared_local_block(p, x, cfg, bias)
        else:
            y, stats, routed = moe.moe_sorted_block(p, x, cfg)
    return x + y, stats, routed
