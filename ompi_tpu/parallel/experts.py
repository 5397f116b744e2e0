"""The expert blocks of a public model's sparse layers: a learned top-k
router with no dropped token, sort-and-gather dispatch, grouped expert
matmuls and a weighted combine.  ``moe_sorted_block`` holds every expert
(OLMoE); ``moe_shared_local_block`` (DeepSeek-V3's layer: JoyAI-LLM-Flash;
without a shared expert lfm2_moe's: LFM2-8B-A1B; by softmax scores with
no bias beside a sigmoid-gated shared expert qwen3_next's:
Qwen3-Next-80B-A3B; by a router that read the layer's input, with
relu-gated experts, SmallThinker-21BA3B's) and ``moe_latent_block``
(nemotron_h's LatentMoE) hold a share of the routed experts, beside a
shared one where the model has it.  ``parallel/model.decoder_layer``
chooses among them; the primitives come from ``parallel/layers.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ompi_tpu.parallel.layers import (cast_param, ffn_bwd_written, matmul,
                                      relu2, rmsnorm_gain, swiglu)
from ompi_tpu.parallel.sublayer import INTERPRET, Sublayer, held


def route_topk(logits, top_k: int, normalize: bool = False):
    """``softmax`` over every expert, then the ``top_k`` largest:
    returns (probs (T, E), weights (T, k)) and experts (T, k).
    The weights are the chosen probabilities as they stand unless
    ``normalize`` (OLMoE's ``norm_topk_prob`` is false)."""
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if normalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return probs, weights, experts


def sorted_dispatch(experts, n_experts: int):
    """Sort-and-gather dispatch of the T x k token-slots: returns
    (token of each sorted slot, where each (token, k) slot went, the
    slots each expert received).  Every slot is kept: a group is as
    long as its expert is popular, so no token is ever dropped."""
    t, k = experts.shape
    flat = experts.reshape(t * k)
    order = jnp.argsort(flat, stable=True)
    place = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    sizes = jnp.zeros((n_experts,), jnp.int32).at[flat].add(1)
    return order // k, place.reshape(t, k), sizes


def gmm_on_kernel(interpret: bool, compute_dtype, m: int, k: int,
                  n: int) -> tuple:
    """``(on_kernel, why)`` of a grouped matmul ``(m, k) x (g, k, n)``
    with its two transposes: on the Pallas kernel
    (``ops/grouped_matmul``) where Mosaic compiles (``interpret`` false:
    a TPU), the inputs are bfloat16 and the shape has tiles (its
    ``refusal``); ``why`` names the clause that refused, "" where the
    kernel is taken."""
    if interpret:
        return False, INTERPRET
    if jnp.dtype(compute_dtype) != jnp.bfloat16:
        return False, (f"compute_dtype {jnp.dtype(compute_dtype).name}: "
                       "the kernel's inputs are bfloat16")
    from ompi_tpu.ops import grouped_matmul as kernel

    why = kernel.refusal(m, k, n)
    return not why, why


def scatter_on_kernel(interpret: bool, rows: int, d: int, dtype) -> tuple:
    """``(on_kernel, why)`` of the held experts' loops' row scatter-adds,
    chunks of ``rows`` rows of ``d`` entries into sums of ``dtype``: on the
    Pallas row kernel (``ops/row_scatter``) where Mosaic compiles and a
    row is whole lane tiles (its ``refusal``)."""
    if interpret:
        return False, INTERPRET
    from ompi_tpu.ops import row_scatter

    why = row_scatter.refusal(rows, d, dtype)
    return not why, why


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_matmul(a, w, sizes, dtype):
    """``a`` (m, k) times the stacked ``w`` (g, k, n) by group on the
    Pallas kernel (``ops/grouped_matmul.gmm``), both cast to ``dtype`` on
    the way in, with its gradient written out: the rows' is ``gmm``
    against the transposed matrices, the matrices' ``tgmm``, both of the
    cotangent cast to ``dtype`` (what the MXU takes of a float32 one:
    ``lax.ragged_dot``'s transposes on a TPU hand it over in float32 and
    the kernel rounds it, ``contract_precision<bf16>``) and both float32
    until they are cast to ``a``'s and ``w``'s dtypes."""
    return _kernel_matmul_fwd(a, w, sizes, dtype)[0]


def _kernel_matmul_fwd(a, w, sizes, dtype):
    from ompi_tpu.ops import grouped_matmul as kernel

    a16, w16 = a.astype(dtype), cast_param(w, dtype)
    # the empty arrays carry the primals' dtypes to the backward pass
    return kernel.gmm(a16, w16, sizes), (
        a16, w16, sizes, jnp.zeros((0,), a.dtype), jnp.zeros((0,), w.dtype))


def _kernel_matmul_bwd(dtype, res, ct):
    from ompi_tpu.ops import grouped_matmul as kernel

    a16, w16, sizes, a0, w0 = res
    ct = ct.astype(dtype)
    da = kernel.gmm(ct, w16, sizes, transpose_rhs=True)
    dw = kernel.tgmm(a16, ct, sizes)
    return da.astype(a0.dtype), dw.astype(w0.dtype), None


_kernel_matmul.defvjp(_kernel_matmul_fwd, _kernel_matmul_bwd)


def _grouped_matmul(sizes, compute_dtype, interpret: bool):
    """``gmm(a, w)``: rows of ``a`` sorted by expert times the experts'
    stacked matrices ``w`` (group e is the ``sizes[e]`` rows that expert
    e received), inputs in ``compute_dtype``, float32 results.  Where
    Mosaic compiles (``interpret`` false: a TPU), the inputs are bfloat16
    and the shape has tiles (``gmm_on_kernel``) it is the
    Pallas kernel (``_kernel_matmul``); everywhere else
    ``lax.ragged_dot``.  And ``transposes(a, w, ct, acc)``, what a
    backward pass that is written out calls (``local_expert_ffn``'s):
    (the cotangent of ``a`` under ``ct``, the running float32 sum ``acc``
    (g, k, n) with ``w``'s gradient added), by the same two kernels as
    ``_kernel_matmul_bwd`` with ``acc`` updated in place, or by
    ``ragged_dot``'s own transposes and an add."""
    f32 = jnp.dtype(compute_dtype) == jnp.float32
    prec = jax.lax.Precision.HIGHEST if f32 else None
    def on_kernel(a, w):
        return gmm_on_kernel(interpret, compute_dtype, *a.shape,
                             w.shape[2])[0]

    def gmm(a, w):
        if on_kernel(a, w):
            return _kernel_matmul(a, w, sizes, compute_dtype)
        return jax.lax.ragged_dot(
            a.astype(compute_dtype), cast_param(w, compute_dtype), sizes,
            precision=prec, preferred_element_type=jnp.float32)

    def transposes(a, w, ct, acc):
        if not on_kernel(a, w):
            da, dw = jax.vjp(gmm, a, w)[1](ct)
            return da, acc + dw
        from ompi_tpu.ops import grouped_matmul as kernel

        ct = ct.astype(compute_dtype)
        da = kernel.gmm(ct, cast_param(w, compute_dtype), sizes,
                        transpose_rhs=True)
        return da.astype(a.dtype), kernel.tgmm(
            a.astype(compute_dtype), ct, sizes, acc)

    return gmm, transposes


#: a gated expert's activation by the configuration's name for it:
#: SwiGLU's silu, ReGLU's relu
GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def grouped_expert_ffn(xs, gate, up, down, sizes, compute_dtype,
                       interpret: bool = True, act: str = "silu"):
    """Gated experts on slots sorted by expert: ``down(act(gate x) * up
    x)`` as three grouped matmuls (``_grouped_matmul``); ``act`` is
    SwiGLU's ``silu`` or ReGLU's ``relu`` (``GATE_ACTS``)."""
    gmm, _ = _grouped_matmul(sizes, compute_dtype, interpret)
    hidden = GATE_ACTS[act](gmm(xs, gate)) * gmm(xs, up)
    return gmm(hidden, down)


def grouped_expert_ffn_vjp(xs, gate, up, down, sizes, compute_dtype,
                           interpret: bool = True, act: str = "silu"):
    """``grouped_expert_ffn``'s value and ``back(dy, sums)``: under the
    value's cotangent ``dy``, (``xs``'s cotangent, the running float32
    sums of ``gate``'s, ``up``'s and ``down``'s gradients with this
    call's added: ``_grouped_matmul``'s ``transposes``)."""
    gmm, transposes = _grouped_matmul(sizes, compute_dtype, interpret)
    hidden, act_back = jax.vjp(lambda g, u: GATE_ACTS[act](g) * u,
                               gmm(xs, gate), gmm(xs, up))

    def back(dy, sums):
        dhidden, sum_down = transposes(hidden, down, dy, sums[2])
        dg, du = act_back(dhidden)
        by_gate, sum_gate = transposes(xs, gate, dg, sums[0])
        by_up, sum_up = transposes(xs, up, du, sums[1])
        return by_gate + by_up, (sum_gate, sum_up, sum_down)

    return gmm(hidden, down), back


def grouped_relu2_ffn_vjp(xs, up, down, sizes, compute_dtype,
                          interpret: bool = True):
    """relu2 experts (nemotron_h: two matrices, no gate) on slots sorted
    by expert, ``down(relu(up x)^2)`` as two grouped matmuls
    (``_grouped_matmul``): the value and ``back(dy, sums)``, as
    ``grouped_expert_ffn_vjp``'s: (``xs``'s cotangent, the running sums
    of ``up``'s and ``down``'s gradients)."""
    gmm, transposes = _grouped_matmul(sizes, compute_dtype, interpret)
    hidden, act_back = jax.vjp(lambda a: jnp.square(jax.nn.relu(a)),
                               gmm(xs, up))

    def back(dy, sums):
        dhidden, sum_down = transposes(hidden, down, dy, sums[1])
        dxs, sum_up = transposes(xs, up, *act_back(dhidden), sums[0])
        return dxs, (sum_up, sum_down)

    return gmm(hidden, down), back


def moe_sorted_block(p, x, cfg, bias=None, *, interpret: bool = True,
                     routed=None):
    """OLMoE's sparse MLP sublayer on the residual stream ``x`` (b, s,
    d): pre-norm, a learned router (logits and softmax in float32), the
    top k of all experts with no capacity, sort-and-gather dispatch,
    grouped expert matmuls, weighted combine.  Returns (the sublayer's
    output, before the residual add; the router's statistics: slots an
    expert received, summed probabilities an expert, summed squared
    logsumexp of the logits; and by token row what the router read and
    made: ``in`` (T, d), ``logits`` (T, E), ``lse`` (T,), ``weights`` and
    ``experts`` (T, k))."""
    b, s, d = x.shape
    t, k = b * s, cfg.num_experts_per_tok
    h = rmsnorm_gain(x, p["ln2"], cfg.rms_norm_eps).reshape(t, d)
    with jax.named_scope("otpu_router"):
        logits = jnp.dot(h, p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        probs, weights, experts = route_topk(logits, k, cfg.norm_topk_prob)
    with jax.named_scope("otpu_dispatch"):
        token, place, sizes = sorted_dispatch(experts, cfg.num_experts)
    with jax.named_scope("otpu_experts"):
        y = grouped_expert_ffn(h.astype(cfg.compute_dtype)[token],
                               p["gate"], p["up"], p["down"], sizes,
                               cfg.compute_dtype, interpret)
    with jax.named_scope("otpu_combine"):
        out = jnp.sum(y[place] * weights[..., None], axis=1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    stats = {"slots": sizes.astype(jnp.float32),
             "prob_sum": jnp.sum(probs, axis=0),
             "z_sum": jnp.sum(lse * lse)}
    return out.reshape(b, s, d), stats, {
        "in": h, "logits": logits, "lse": lse, "weights": weights,
        "experts": experts}


# -- a share of the routed experts beside a shared one (DeepSeek-V3's
# expert layer on one rank of an expert-parallel deployment) -------------

# what a layer's ``jax.checkpoint`` keeps of an expert block
# (``objective.model_loss``'s policy saves these names and nothing else): the
# router's float32 product, the integers chosen and sorted from it, the
# chosen experts' scores, and the held experts' sum in the latent.  Small
# beside a layer's activations, and dear to make again: the six-pass
# product, the top-k (a whole sort on a TPU), the argsort, the loop.
ROUTER_LOGITS = "otpu_router_logits"
CHOSEN_EXPERTS = "otpu_chosen_experts"
CHOSEN_SCORES = "otpu_chosen_scores"
DISPATCH_ORDER = "otpu_dispatch_order"
DISPATCH_SIZES = "otpu_dispatch_sizes"
EXPERT_SLOTS = "otpu_expert_slots"
LATENT_SUM = "otpu_latent_sum"
CHECKPOINT_KEEPS = (ROUTER_LOGITS, CHOSEN_EXPERTS, CHOSEN_SCORES,
                    DISPATCH_ORDER, DISPATCH_SIZES, EXPERT_SLOTS, LATENT_SUM)


@jax.custom_vjp
def chosen_scores(scores, experts):
    """``scores`` (T, E) at the chosen ``experts`` (T, k), bit for bit
    what ``jnp.take_along_axis(scores, experts, axis=-1)`` gathers, as k
    compares of an expert's column against the lane's number and a sum
    over the lanes that holds one entry and zeros.  A v5e walks a gather's
    single entries at about 10 ns each (1.84 ms a layer for Nemotron's
    8,192 x 22 of 512) and compares a lane in a thousandth of that (0.05
    ms a layer in the same step; PR 59, ``PERF.md`` section 5), and XLA
    fuses each compare into its sum, so no (T, k, E) array is written.
    The transpose is written out the same way: a token's experts are
    distinct, so the k terms added are the scatter-add's result bit for
    bit (0.1 ms a layer where the scatter took 1.56), and what is kept
    for it is ``experts``."""
    lane = jnp.arange(scores.shape[-1], dtype=experts.dtype)
    return jnp.stack(
        [jnp.sum(jnp.where(experts[:, j:j + 1] == lane, scores, 0), axis=-1)
         for j in range(experts.shape[1])], axis=-1)


def _chosen_scores_fwd(scores, experts):
    # the empty array carries the scores' width to the transpose
    return chosen_scores(scores, experts), (
        experts, jnp.zeros((0, scores.shape[-1]), scores.dtype))


def _chosen_scores_bwd(res, ct):
    experts, width = res
    lane = jnp.arange(width.shape[-1], dtype=experts.dtype)
    dscores = sum(
        jnp.where(experts[:, j:j + 1] == lane, ct[:, j:j + 1], 0)
        for j in range(experts.shape[1]))
    # written once, as the scatter's result was: fused into what reads it
    # (the router's two float32 products) the k terms are made again in
    # every pass of both (seen on the v5e: 3.5 ms of Nemotron's step)
    return jax.lax.optimization_barrier(dscores), None


chosen_scores.defvjp(_chosen_scores_fwd, _chosen_scores_bwd)


def count_keys(keys, bins: int):
    """How many of the integer ``keys`` (any shape) equal each of 0 ..
    ``bins`` - 1: int32 (bins,), exactly ``zeros(bins).at[keys].add(1)``
    of keys in range, as a compare of every key against every bin and a
    sum over the keys.  A v5e adds a scatter's indices one at a time
    (1.57 ms a layer for Nemotron's 180,224, into 9 bins as into 512);
    the compares cost T k ``bins`` lanes and fuse into the sum (0.003 and
    0.1 ms a layer in the same step; PR 59, ``PERF.md`` section 5)."""
    hit = keys.reshape(-1, 1) == jnp.arange(bins, dtype=keys.dtype)
    return jnp.sum(hit, axis=0, dtype=jnp.int32)


def route_chosen(scores, bias, top_k: int, normalize: bool, scale: float):
    """The ``top_k`` largest of ``scores`` (T, E) + ``bias`` (the
    balancing bias enters the choice and nothing else; None where the
    router has none), weights the chosen scores themselves
    (``chosen_scores``: dense compares, no gather and no scatter of T k
    single entries), normalised to sum to one if ``normalize`` and times
    ``scale``.  Returns (weights (T, k), experts (T, k)).  The experts
    are named (``CHOSEN_EXPERTS``) before anything reads them, and their
    scores as read (``CHOSEN_SCORES``), so what a checkpoint recomputes
    of the weights is the normalisation: no second top-k."""
    _, experts = jax.lax.top_k(
        scores if bias is None else scores + jax.lax.stop_gradient(bias),
        top_k)
    experts = checkpoint_name(experts, CHOSEN_EXPERTS)
    weights = checkpoint_name(chosen_scores(scores, experts), CHOSEN_SCORES)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return weights * scale, experts


def route_sigmoid_bias(logits, bias, top_k: int, normalize: bool,
                       scale: float):
    """DeepSeek-V3's ``noaux_tc`` routing with one group: ``sigmoid``
    scores over every expert, the choice and the weights by
    ``route_chosen`` under ``bias``.  Returns (scores (T, E), weights
    (T, k), experts (T, k))."""
    scores = jax.nn.sigmoid(logits)
    weights, experts = route_chosen(scores, bias, top_k, normalize, scale)
    return scores, weights, experts


def local_dispatch(experts, first: int, n_here: int):
    """The T x k token-slots with those of the ``n_here`` experts held
    here (``first`` and up) in front, sorted by expert: returns (the
    slots in that order, the slots each held expert received:
    ``count_keys``, dense compares in place of a scatter-add of T k
    indices; a slot held nowhere has a key past the last bin and counts
    in none).  Only integers are sorted; no row of activations moves
    here."""
    t, k = experts.shape
    here = experts.reshape(t * k) - first
    key = jnp.where((here >= 0) & (here < n_here), here, n_here)
    order = jnp.argsort(key, stable=True)
    return order, count_keys(key, n_here)


#: the most row tiles (``ops/grouped_matmul.ROW_TILE``) a trip of the held
#: experts' loop walks.  On the v5e 2,048 rows were the fastest or within
#: 0.3% of it in all five cells that hold a share (200 to 2,300 rows a
#: group, rows of 1,024 to 2,560 floats): at 1,024 the trips' own cost
#: shows, at 4,096 and up the rows past the last slot do, and XLA's
#: scatter-add of 4,096 to 16,384 rows of 2,560 floats runs at a third of
#: its rate (my chip runs, PR 57: ``PERF.md`` section 6)
CHUNK_TILES = 8


def chunk_rows(t: int, k: int, held: int, total: int) -> int:
    """The rows a trip of ``local_expert_ffn``'s loop walks, from the
    shapes alone (``t`` tokens of ``k`` slots each, ``held`` of ``total``
    experts here): half the mean load rounded up to a power of two, so
    that small shapes run several trips too, and at most ``CHUNK_TILES``
    row tiles, which is what every cell's shapes give.  Whole row tiles,
    or whole sublane tiles of 16 under one: every trip's products have
    tiles (``ops/grouped_matmul.supported``)."""
    from ompi_tpu.ops.grouped_matmul import ROW_TILE

    mean = max(1, t * k * held // total)
    return min(CHUNK_TILES * ROW_TILE,
               max(16, (1 << (mean - 1).bit_length()) // 2))


def local_expert_ffn(h, order, weights, sizes, mats: tuple, cfg,
                     ffn=grouped_expert_ffn_vjp, interpret: bool = True):
    """The held experts' weighted part of the layer's output (T, d):
    gather the held slots' rows, grouped matmuls (``ffn`` over the held
    experts' stacked matrices ``mats``: ``grouped_expert_ffn_vjp`` over
    a gated expert's three, ``grouped_relu2_ffn_vjp`` over relu2's two),
    scatter-add by token.
    The slots held vary from step to step (0 to every slot a token can
    send here) and none is dropped.  They are walked in chunks of
    ``chunk_rows`` rows by a loop that runs as many times as the held
    slots need (``lax.fori_loop`` to a count read on the device), so a
    hot rank runs more trips and one chunk's buffers are held at a time.
    A trip costs by the rows of its chunk and by the experts that have
    rows in it, and by nothing else: the matrices are cast once, before
    the loop, and every sum the loop makes is added to in place.  Such a
    loop has no transpose, so the backward pass is written out: the same
    loop over the same chunks, each chunk's forward recomputed and its
    cotangents added where they belong, the rows' and the weights' by
    scatter-add into the carry, the matrices' by the kernel into their
    running float32 sums (``_grouped_matmul``'s ``transposes``: an
    expert with no row in the chunk is not touched).  The rows' sums of
    both loops (the output, the cotangent of ``h``) are added to by the
    Pallas row kernel where Mosaic compiles and a row is whole lane
    tiles (``ops/row_scatter``: row DMAs in place, the live rows alone,
    weighted inside), and are then carried a row as tiles of its own
    (``row_scatter.as_tiles``) and turned to rows once, after the loop;
    everywhere else by ``.at[].add``."""
    t, d = h.shape
    k, n_here = cfg.num_experts_per_tok, sizes.shape[0]
    rows = chunk_rows(t, k, n_here, cfg.num_experts)
    on_kernel, _ = scatter_on_kernel(interpret, rows, d, h.dtype)
    if on_kernel:
        from ompi_tpu.ops import row_scatter
    padded = -(-t * k // rows) * rows
    order = jnp.pad(order, (0, padded - t * k))

    def chunk(lo, order, sizes, h, flat_w, *mats):
        """Chunk ``lo``'s (slot and token of each row, which rows hold a
        slot, how many of them each held expert has, their weights, their
        experts' output, its ``back``)."""
        with jax.named_scope("otpu_dispatch"):
            slot = jax.lax.dynamic_slice_in_dim(order, lo, rows)
            token = slot // k
            ends = jnp.cumsum(sizes)
            live = lo + jnp.arange(rows) < ends[-1]
            here = jnp.clip(jnp.minimum(ends, lo + rows)
                            - jnp.maximum(ends - sizes, lo), 0, rows)
        # rows past the last held slot belong to no group: a grouped
        # matmul leaves them as they were in memory (seen on the v5e:
        # NaN), in its transposes too, so they are cut off on both sides
        # (the row kernel does not read them)
        xs = jnp.where(live[:, None], h[token], 0.0)
        y, back = ffn(xs, *mats, here, cfg.compute_dtype, interpret)
        with jax.named_scope("otpu_combine"):
            w = jnp.where(live, flat_w[slot], 0.0)
            if not on_kernel:
                y = jnp.where(live[:, None], y, 0.0)
            return slot, token, live, here, w, y, back

    def add_rows(acc, token, live, here, y, w=None):
        """``acc`` with the chunk's live rows ``y``, times ``w`` where
        given, added by token: on the row kernel (``acc`` as tiles a row)
        or by XLA's scatter-add of every row, the dead ones as zeros.
        SPC ``moe_scatter_built`` counts a layer application's forward
        loop's, ``moe_scatter_kernel_built`` those on the kernel
        (``_held_plan``)."""
        if on_kernel:
            offsets = jnp.concatenate(
                [jnp.zeros((1,), here.dtype), jnp.cumsum(here)])
            # one kernel for both loops (a step builds it once): the
            # cotangents' rows go in under weights of one
            ones = jnp.ones((rows,), y.dtype)
            return row_scatter.row_scatter_add(
                acc, token, offsets, y, ones if w is None else w)
        if w is None:
            return acc.at[token].add(jnp.where(live[:, None], y, 0.0))
        return acc.at[token].add(y * w[:, None])

    def as_rows(sums):
        return row_scatter.as_rows(sums) if on_kernel else sums

    def trips(sizes):
        return (jnp.sum(sizes) + rows - 1) // rows

    def cast(mats):
        return tuple(cast_param(m, cfg.compute_dtype) for m in mats)

    def zeros(like, *args):
        """A loop's starting sums, shaped as ``like`` (array, dtype)
        pairs and varying as ``args`` do, as the body's results will."""
        zero = [jnp.zeros(a.shape, dtype) for a, dtype in like]
        if on_kernel:     # the rows' sums: the first of either loop's
            zero[0] = row_scatter.as_tiles(zero[0])
        vma = tuple(frozenset().union(*(jax.typeof(a).vma for a in args)))
        return jax.lax.pcast(zero, vma, to="varying") if vma else zero

    @jax.custom_vjp
    def run(order, sizes, h, flat_w, *mats):
        mats = cast(mats)

        def body(c, out):
            _, token, live, here, w, y, _ = chunk(
                c * rows, order, sizes, h, flat_w, *mats)
            with jax.named_scope("otpu_combine"):
                return add_rows(out, token, live, here, y, w)
        (out,) = zeros([(h, h.dtype)], order, sizes, h, flat_w, *mats)
        return as_rows(jax.lax.fori_loop(0, trips(sizes), body, out))

    def fwd(*args):
        return run(*args), args

    def bwd(args, ct):
        order, sizes, h, flat_w, *mats = args
        mats16 = cast(mats)

        def body(c, sums):
            dh, dw, dmats = sums
            slot, token, live, here, w, y, back = chunk(
                c * rows, order, sizes, h, flat_w, *mats16)
            with jax.named_scope("otpu_combine"):
                dout = ct[token]
                dw = dw.at[slot].add(
                    jnp.where(live, jnp.sum(y * dout, axis=1), 0.0))
                dy = jnp.where(live[:, None], dout * w[:, None], 0.0)
            dxs, dmats = back(dy, dmats)
            return add_rows(dh, token, live, here, dxs), dw, dmats

        dh, dw, *dmats = zeros(
            [(h, h.dtype), (flat_w, flat_w.dtype)]
            + [(m, jnp.float32) for m in mats], ct, *args)
        zero = (dh, dw, tuple(dmats))
        dh, dw, dmats = jax.lax.fori_loop(0, trips(sizes), body, zero)
        return (None, None, as_rows(dh), dw) + tuple(
            s.astype(m.dtype) for s, m in zip(dmats, mats))

    run.defvjp(fwd, bwd)
    return run(order, sizes, h, weights.reshape(t * k), *mats)


def router_logits(p, rows):
    """The router's product of ``rows`` (T, d): float32 at the highest
    precision, under the checkpoint's name (``ROUTER_LOGITS``)."""
    with jax.named_scope("otpu_router"):
        return checkpoint_name(
            jnp.dot(rows, p["router"], precision=jax.lax.Precision.HIGHEST),
            ROUTER_LOGITS)


def _route_to_held(p, x, cfg, bias, routed=None):
    """What both expert blocks of a rank that holds a share do first, on
    the residual stream ``x`` (b, s, d): pre-norm; the router's scores
    over **all** the experts in float32; the top k; the held slots sorted
    by expert.  ``routed``: None, or where the router stands before
    attention (``decoder_layer``) the rows it read and the logits it made
    of them (``router_logits``), which are then not made here: the
    experts still read the normed ``x``, and ``in`` is what the router
    read.  Two routers, told apart by ``scoring_func``: ``sigmoid``
    scores chosen under ``bias`` (E,) (DeepSeek-V3's ``noaux_tc``: JoyAI,
    Nemotron-3-Super, LFM2), or ``softmax`` probabilities with no bias
    (qwen3_next's: Qwen3-Next, the weights OLMoE's ``route_topk`` gives,
    here under the checkpoint's names; ``bias`` is not read).  Returns
    (the normed rows (T, d), the held slots' order and sizes, the
    router's statistics: ``slots`` an expert of all of them received and,
    of a softmax router, ``prob_sum`` an expert for the auxiliary loss;
    by token row what the router read and made: ``in``, ``logits``,
    ``scores`` (T, E), ``weights`` and ``experts`` (T, k)).  The product,
    the chosen experts and the dispatch's integers carry their
    ``CHECKPOINT_KEEPS`` names, so a checkpointed layer's backward pass
    reads the forward pass's and routes as it did."""
    b, s, d = x.shape
    t, k = b * s, cfg.num_experts_per_tok
    h = rmsnorm_gain(x, p["ln2"], cfg.rms_norm_eps).reshape(t, d)
    stats = {}
    read, logits = (h, router_logits(p, h)) if routed is None else routed
    with jax.named_scope("otpu_router"):
        if cfg.scoring_func == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            weights, experts = route_chosen(
                scores, None, k, cfg.norm_topk_prob,
                cfg.routed_scaling_factor)
            stats["prob_sum"] = jnp.sum(scores, axis=0)
        else:
            scores, weights, experts = route_sigmoid_bias(
                logits, bias, k, cfg.norm_topk_prob,
                cfg.routed_scaling_factor)
    with jax.named_scope("otpu_dispatch"):
        order, sizes = local_dispatch(experts, cfg.first_expert_here,
                                      cfg.n_experts_here)
        order = checkpoint_name(order, DISPATCH_ORDER)
        sizes = checkpoint_name(sizes, DISPATCH_SIZES)
        slots = checkpoint_name(count_keys(experts, cfg.num_experts),
                                EXPERT_SLOTS)
    stats["slots"] = slots.astype(jnp.float32)
    return h, order, sizes, stats, {
        "in": read, "logits": logits, "scores": scores, "weights": weights,
        "experts": experts}


def moe_shared_local_block(p, x, cfg, bias, *, interpret: bool = True,
                           routed=None):
    """The sparse MLP sublayer of a rank that holds ``experts_here`` of
    the routed experts, on the residual stream ``x`` (b, s, d): pre-norm;
    the router's scores over **all** the experts in float32 and the top
    k (``_route_to_held``); the shared expert on every token, where the
    model has one; the held experts on the slots routed to them
    (``local_expert_ffn``).  What the absent experts would add is left
    out.  Three models' sublayer.  DeepSeek-V3's (arXiv:2412.19437
    section 2.1.2; JoyAI-LLM-Flash): sigmoid scores chosen under
    ``bias`` (E,), a shared expert (``n_shared_experts``) added as it
    is.  lfm2_moe's (LFM2-8B-A1B): the same router, no shared expert:
    the result is the held experts' part alone.  qwen3_next's
    (Qwen3-Next-80B-A3B): softmax probabilities with no bias, and the
    shared expert times ``sigmoid(h w_g)`` a token where the layer holds
    ``shared_w_g`` (d, 1), the gate in float32.  SmallThinker-21BA3B's:
    qwen3_next's router over logits made from the layer's input
    (``routed``: ``_route_to_held``), no shared expert, and experts gated
    by ``cfg.mlp_hidden_act`` (``relu``: ReGLU).  Returns (the sublayer's
    output before the residual add; the router's statistics
    (``_route_to_held``); by token row what the router read and made:
    ``in``, ``logits``, ``scores`` (T, E), ``weights`` and ``experts``
    (T, k), a gated shared expert's ``shared_gate`` (T, 1), and where
    ``routed`` is given the normed rows the held experts read and their
    weighted sum, ``expert_in`` and ``expert_out`` (T, d))."""
    h, order, sizes, stats, seen = _route_to_held(p, x, cfg, bias, routed)
    shared = None
    if cfg.n_shared_experts:
        with jax.named_scope("otpu_shared_expert"):
            shared = swiglu(h, p["shared_gate"], p["shared_up"],
                            p["shared_down"], cfg.compute_dtype)
            if "shared_w_g" in p:
                mix = jax.nn.sigmoid(jnp.dot(
                    h, p["shared_w_g"],
                    precision=jax.lax.Precision.HIGHEST))
                shared, seen = shared * mix, {**seen, "shared_gate": mix}
    with jax.named_scope("otpu_experts"):
        out = local_expert_ffn(
            h, order, seen["weights"], sizes, (p["gate"], p["up"], p["down"]),
            cfg, functools.partial(grouped_expert_ffn_vjp,
                                   act=cfg.mlp_hidden_act), interpret)
        if routed is not None:
            # the router read other rows than the experts: what these read
            # and made goes beside what it read
            seen = {**seen, "expert_in": h, "expert_out": out}
        if shared is not None:
            out = shared + out
    return out.reshape(x.shape), stats, seen


def moe_latent_block(p, x, cfg, bias, *, interpret: bool = True,
                     routed=None):
    """nemotron_h's expert sublayer (its LatentMoE) on the residual
    stream ``x`` (b, s, d), on a rank that holds ``experts_here`` of the
    routed experts: pre-norm; the router's sigmoid scores over **all**
    the experts in float32; the top k of score + ``bias`` (E,)
    (``route_sigmoid_bias``: DeepSeek-V3's rule); a relu2 shared expert
    on the hidden width on every token; the routed experts in a latent
    of ``moe_latent_size``: ``l = h W_lat_down``, the held experts'
    ``relu(l W_up)^2 W_down`` on the slots routed to them, weighted and
    added up by token (``local_expert_ffn``), and that sum through
    ``W_lat_up``.  What the absent experts would add is left out.
    Returns what ``moe_shared_local_block`` does."""
    dt = cfg.compute_dtype
    h, order, sizes, stats, seen = _route_to_held(p, x, cfg, bias)
    with jax.named_scope("otpu_shared_expert"):
        out = relu2(h, p["shared_up"], p["shared_down"], dt)
    with jax.named_scope("otpu_latent"):
        latent = matmul(h, p["lat_down"], dt)
    with jax.named_scope("otpu_experts"):
        # ``lat_up``'s weight gradient reads this sum: kept, or a layer's
        # backward pass runs the held experts' loop once more to make it
        latent = checkpoint_name(
            local_expert_ffn(latent, order, seen["weights"], sizes,
                             (p["up"], p["down"]), cfg,
                             grouped_relu2_ffn_vjp, interpret),
            LATENT_SUM)
    with jax.named_scope("otpu_latent"):
        out = out + matmul(latent, p["lat_up"], dt)
    return out.reshape(x.shape), stats, seen


def dense_mlp(p, x, cfg, bias=None, *, interpret: bool = True, routed=None):
    """A dense layer's feed-forward on the residual stream ``x`` (b, s,
    d): pre-norm, SwiGLU.  No router: no statistics, nothing reported."""
    h = rmsnorm_gain(x, p["ln2"], cfg.rms_norm_eps)
    y = swiglu(h.reshape(-1, h.shape[-1]), p["gate"], p["up"], p["down"],
               cfg.compute_dtype)
    return y.reshape(x.shape), {}, {}


# -- the feed-forwards a layer may end in (``parallel/model.py``'s table) ----
def _dense_shapes(cfg) -> dict:
    d, ff = cfg.hidden_size, cfg.intermediate_size
    return {"ln2": (d,), "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def _routed_shapes(cfg) -> dict:
    """The gain, the router over all the experts, the held experts' three,
    and where the model has a shared expert its three and, where that is
    gated, ``shared_w_g`` (d, 1)."""
    d, f, e, fs = (cfg.hidden_size, cfg.expert_width, cfg.n_experts_here,
                   cfg.shared_width)
    out = {"ln2": (d,), "router": (d, cfg.num_experts),
           "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d)}
    if fs:
        out.update(shared_gate=(d, fs), shared_up=(d, fs),
                   shared_down=(fs, d))
    if cfg.shared_expert_gate:
        out["shared_w_g"] = (d, 1)
    return out


def _latent_shapes(cfg) -> dict:
    """The gain, the router over all the experts, the latent's two
    projections, the held experts' two matrices in the latent, the shared
    expert's two on the hidden width."""
    d, e, lat, f = (cfg.hidden_size, cfg.n_experts_here, cfg.moe_latent_size,
                    cfg.expert_width)
    fs = cfg.moe_shared_expert_intermediate_size * cfg.n_shared_experts
    return {"ln2": (d,), "router": (d, cfg.num_experts),
            "lat_down": (d, lat), "lat_up": (lat, d), "up": (e, lat, f),
            "down": (e, f, lat), "shared_up": (d, fs), "shared_down": (fs, d)}


def _held_reports(cfg) -> dict:
    keys = ("in", "logits", "scores", "weights")
    if cfg.shared_expert_gate:
        keys += ("shared_gate",)
    if cfg.router_before_attention:
        keys += ("expert_in", "expert_out")
    return dict.fromkeys(keys, 1)


def _ffn_plan(cfg, first: tuple) -> tuple:
    """What an application that calls ``layers.swiglu`` or ``relu2`` (a
    dense feed-forward, a shared expert) adds to its sublayer's plan, from
    the shape (d, ff) of its ``first`` matrix: (the counters, ``held``'s
    ``written``).  SPC ``ffn_built`` counts the applications,
    ``ffn_bwd_written_built`` those whose backward pass is the written
    rule (``layers.ffn_bwd_written``)."""
    made = ffn_bwd_written(cfg.compute_dtype, *first)
    return ({"ffn_built": 1, "ffn_bwd_written_built": int(made[0])},
            {"ffn_bwd": made})


def _dense_plan(cfg, b, s, interpret) -> dict:
    return held(*_ffn_plan(cfg, _dense_shapes(cfg)["gate"]))


def _routed_plan(cfg, interpret, m: int, products: list,
                 scatter=None, shared=None) -> dict:
    """What an expert block holds whose grouped matmuls are ``products``,
    (k, n) each, over ``m`` rows: SPC ``moe_gmm_built`` counts them a
    layer application (a gated expert's three, relu2's two; their
    transposes are the backward pass's and no second application),
    ``moe_gmm_kernel_built`` those on the kernel; the part is on the
    kernel where every product is, else the first refused one says why.
    ``scatter``: a held experts' loop's row scatter-add's decision.
    ``shared``: the shape (d, ff) of a shared expert's first matrix where
    the block runs one (``_ffn_plan``)."""
    made = [gmm_on_kernel(interpret, cfg.compute_dtype, m, k, n)
            for k, n in products]
    on = sum(ok for ok, _ in made)
    why = next((why for ok, why in made if not ok), "")
    counts = {"moe_gmm_built": len(made), "moe_gmm_kernel_built": on}
    parts = {"gmm": (on == len(made), why)}
    if scatter is not None:
        parts["scatter"] = scatter
        counts.update(moe_scatter_built=1,
                      moe_scatter_kernel_built=int(scatter[0]))
    ffn, written = _ffn_plan(cfg, shared) if shared else ({}, None)
    return held({**counts, **ffn}, written, **parts)


def _sorted_plan(cfg, b, s, interpret) -> dict:
    """``moe_sorted_block``'s: every slot of the shard in one call."""
    d, f = cfg.hidden_size, cfg.expert_width
    return _routed_plan(cfg, interpret, b * s * cfg.num_experts_per_tok,
                        [(d, f), (d, f), (f, d)])


def _held_plan(cfg, b, s, interpret, latent: bool = False) -> dict:
    """``local_expert_ffn``'s, as ``moe_shared_local_block`` (gated
    experts on the hidden width) or ``moe_latent_block`` (relu2 experts in
    the latent) calls it: a trip's ``chunk_rows`` rows, and the forward
    loop's one row scatter-add into float32 sums (SPC
    ``moe_scatter_built``, ``moe_scatter_kernel_built``)."""
    rows = chunk_rows(b * s, cfg.num_experts_per_tok, cfg.n_experts_here,
                      cfg.num_experts)
    f = cfg.expert_width
    d = cfg.moe_latent_size if latent else cfg.hidden_size
    return _routed_plan(
        cfg, interpret, rows,
        [(d, f), (f, d)] if latent else [(d, f), (d, f), (f, d)],
        scatter=scatter_on_kernel(interpret, rows, d, jnp.float32),
        shared=_latent_shapes(cfg)["shared_up"] if latent
        else _routed_shapes(cfg).get("shared_gate"))


DENSE = Sublayer(group="dense", scope="otpu_dense_mlp", run=dense_mlp,
                 shapes=_dense_shapes, undecayed=("ln2",),
                 post_norm="ln2_post", plan=_dense_plan)
#: OLMoE's: every expert here
SORTED = Sublayer(
    group="moe", scope="otpu_moe", run=moe_sorted_block,
    shapes=_routed_shapes, undecayed=("ln2",),
    reports=lambda cfg: {"in": 1, "logits": 1, "lse": 0, "weights": 1},
    plan=_sorted_plan)
#: a share of the experts (``cfg.routes_to_held``)
SHARED_LOCAL = Sublayer(
    group="moe", scope="otpu_moe", run=moe_shared_local_block,
    shapes=_routed_shapes, undecayed=("ln2",), reports=_held_reports,
    keeps=CHECKPOINT_KEEPS, plan=_held_plan)
#: nemotron_h's ``E``
LATENT = Sublayer(
    name="E", group="moe", scope="otpu_moe", run=moe_latent_block,
    shapes=_latent_shapes, undecayed=("ln2",), reports=_held_reports,
    keeps=CHECKPOINT_KEEPS, plan=functools.partial(_held_plan, latent=True))
