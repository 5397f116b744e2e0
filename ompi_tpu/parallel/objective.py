"""A public model's training objective: the embedding, the walked layers
(``parallel/model.decoder_layer``, a run of like layers under one
``lax.scan``, each layer under ``jax.checkpoint``), the head's
cross-entropy in blocks of rows, the routers' losses and the next-n
module's, with what a step reports of them (its ``aux``, listed in
``parallel/train.py``); and, for a model trained by block diffusion
(``block_length``), the step's noise, the noisy and the clean copy of
every sequence side by side, and the masked rows' weighted loss; for a
looped model (``total_ut_steps``) the walk run that many times over one
set of leaves, the exit gate behind every pass and the expected loss under
the exit distribution (``looped_loss``); for a model trained padding-free
(``eos_token_here``) the documents of a packed row, made from its ids
(``documents``) and handed down the walk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.parallel import experts, hyper
from ompi_tpu.parallel.config import ModelConfig
from ompi_tpu.parallel.layers import matmul, rmsnorm_gain
from ompi_tpu.parallel.model import (CHECKPOINT_KEEPS, decoder_layer,
                                     kind_of_letter, layer_kinds)

SAMPLE_ROWS = 16        # token rows whose activations a step reports


def sample_rows(rows: int) -> np.ndarray:
    """The ``SAMPLE_ROWS`` rows of a shard of ``rows`` token rows that a
    step reports activations at: evenly spaced, the last row among
    them."""
    n = min(SAMPLE_ROWS, rows)
    return (np.arange(1, n + 1) * rows) // n - 1


def documents(ids, eos: int):
    """A packed row's documents from its ids (b, s): ``doc`` (b, s) int32, a
    position's document, counted from 0 along its row: the positions before
    it that hold the end-of-document id ``eos``, so the position behind one
    starts the next.  Integers alone: whoever holds the ids makes the same
    ``doc``."""
    ends = (ids == eos).astype(jnp.int32)
    return jnp.cumsum(ends, axis=1) - ends


def head_cross_entropy(h, w, labels, block_rows: int, compute_dtype,
                       weights=None, scope: str = "otpu_bd_loss",
                       logits_scaling: float = 1.0):
    """Summed cross-entropy of ``softmax(h @ w)`` against ``labels``, by
    blocks of ``block_rows`` rows so that no (T, V) array is ever held.
    The forward pass also makes the two gradients (``softmax - onehot``
    is at hand in each block), so the backward pass only scales them:
    the head's logits are computed once a step, not twice.  ``weights``
    (T,) float32 (None: one a row, and the program is the call's without
    it) gives each row's share of the sum, a constant of the step (no
    gradient reaches it; its work is traced under ``scope``); a row
    of weight zero adds nothing to either gradient.  The logits are ``h @
    w / logits_scaling`` (granitemoehybrid's; at 1.0 the program is the
    call's without it), divided in each block as they leave the product,
    and so are both gradients.  Returns (the weighted sum over rows, per
    row (logsumexp, the label's logit) of the scaled logits: no gradient
    passes through the second)."""
    t, d = h.shape
    nblk = t // block_rows
    if nblk * block_rows != t:
        raise ValueError(f"{t} rows are not whole blocks of {block_rows}")

    by_block = () if weights is None else (
        weights.reshape(nblk, block_rows),)

    def run(h, w, labels):
        def block(carry, xs):
            total, dw = carry
            hb, lb, *wb = xs
            logits = matmul(hb, w, compute_dtype)            # (rows, V) f32
            if logits_scaling != 1.0:
                logits = logits / logits_scaling
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
            dlogits = jnp.exp(logits - lse[:, None]) - jax.nn.one_hot(
                lb, logits.shape[-1], dtype=jnp.float32)
            lost = lse - picked
            if logits_scaling != 1.0:       # d loss / d (h @ w)
                dlogits = dlogits / logits_scaling
            if wb:
                with jax.named_scope(scope):
                    dlogits, lost = dlogits * wb[0][:, None], lost * wb[0]
            dh = matmul(dlogits, w.T, compute_dtype)
            dw = dw + matmul(hb.T, dlogits, compute_dtype, weight=False)
            return (total + jnp.sum(lost), dw), (
                dh, jnp.stack([lse, picked], axis=-1))

        vma = tuple(jax.typeof(h).vma | jax.typeof(labels).vma)
        zero = (jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32))
        if vma:         # the scan's carry varies as its inputs do
            zero = jax.lax.pcast(zero, vma, to="varying")
        (total, dw), (dh, rows) = jax.lax.scan(
            block, zero, (h.reshape(nblk, block_rows, d),
                          labels.reshape(nblk, block_rows), *by_block))
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


def block_diffusion_noise(tokens, labels, cfg: ModelConfig):
    """A step's noise (BD3-LM's training pass, arXiv:2503.09573): (a noise
    level a block (b, s / B) float32, which tokens are replaced by the mask
    token (b, s) bool).  A sequence's draw is keyed by ``cfg.noise_seed``
    and the sequence's **two spare ids**, the last two of its ``labels``
    (a batch holds two ids more than the step reads): ``key =
    fold_in(fold_in(PRNGKey(noise_seed), spare[0]), spare[1])``; ``k_c =
    bits(fold_in(key, 0), (s / B,)) >> 8`` a block and ``k_i =
    bits(fold_in(key, 1), (s,)) >> 8`` a token, uniform 24-bit integers.
    The level is ``t_c = t_min + (1 - t_min) u_c`` on the grid of 2^-24,
    **in integers**: ``q_c = m + floor((2^24 - m) k_c / 2^24)`` with ``m =
    round(t_min 2^24)`` and ``t_c = q_c / 2^24``; token i of block c is
    masked iff ``k_i < q_c``, with probability ``t_c`` exactly.  No float
    is rounded and no compiler's fused multiply-add can turn a bit (the
    float form differed in its last bit between a jitted and an op-by-op
    run on one machine): one seed and one order of batches repeat their
    noise, two batches draw different noise, and whoever holds the batch
    draws it again bit for bit."""
    b, s = tokens.shape
    bl = cfg.block_length
    base = jax.random.PRNGKey(cfg.noise_seed)
    floor_ = round(cfg.t_min * (1 << 24))
    a1, a0 = ((1 << 24) - floor_) >> 12, ((1 << 24) - floor_) & 0xFFF

    def draw(spare):
        key = jax.random.fold_in(jax.random.fold_in(base, spare[0]), spare[1])
        return (jax.random.bits(jax.random.fold_in(key, 0), (s // bl,),
                                jnp.uint32) >> 8,
                jax.random.bits(jax.random.fold_in(key, 1), (s,),
                                jnp.uint32) >> 8)

    by_block, by_token = jax.vmap(draw)(labels[:, -2:].astype(jnp.uint32))
    # (2^24 - m) k / 2^24 by limbs of 12 bits: no product passes 32 bits
    k1, k0 = by_block >> 12, by_block & 0xFFF
    grid = floor_ + a1 * k1 + ((a1 * k0 + a0 * k1 + ((a0 * k0) >> 12)) >> 12)
    levels = grid.astype(jnp.float32) * (2.0 ** -24)
    return levels, by_token < jnp.repeat(grid, bl, axis=1)


def layer_checkpoint_policy():
    """What a walked layer's ``jax.checkpoint`` keeps for its backward
    pass: what its sublayers name (their ``keeps``: an expert block's
    routing results, causal attention's o and logsumexp) and nothing else,
    so a layer that names nothing is recomputed whole."""
    return jax.checkpoint_policies.save_only_these_names(
        *CHECKPOINT_KEEPS)


def _walk_layers(run, stacked, x, bias, n: int):
    """``n`` like layers in turn: ``run(layer, x, bias row) -> (x,
    out)``; returns (x, the outs stacked).  More than one is a
    ``lax.scan`` over the stacked leaves, so the layer is traced and
    compiled once however many there are."""
    if n == 1:
        x, out = run(jax.tree.map(lambda a: a[0], stacked), x,
                     None if bias is None else bias[0])
        return x, jax.tree.map(lambda a: a[None], out)
    return jax.lax.scan(
        lambda x, xs: run(xs[0], x, xs[1]), x, (stacked, bias))


def _walk_pattern(run_of, layers, x, bias, cfg: ModelConfig):
    """The held layers of a ``hybrid_override_pattern`` or ``layer_types``
    model in turn, a run of like layers at a time (``cfg.segments``;
    ``layers`` holds a group a run): a run's unit is called once, or
    scanned over its repeats (``_walk_layers``), each of its layers
    through ``run_of(kind)``, ``kind`` its kind's name.  ``bias`` (the held
    expert layers, E) gives each layer with a router its row (None: the
    routers choose under none).  Returns (x, what the layers' ``run``
    gave: the routers' statistics and chosen experts and the sampled
    rows, each stacked in the layers' order over the layers that have
    it; a unit of two letters has no key in both)."""
    by_letter = kind_of_letter(cfg)
    stats, chosen, sample, done = {}, [], {}, 0   # done: routers walked
    for unit, n, first in cfg.segments:
        kinds = [by_letter[letter] for letter in unit]

        def unit_run(group, x, bias_row, kinds=kinds):
            out = {}
            for kind in kinds:
                x, out[kind.letter] = run_of(kind.name)(
                    group[kind.name], x, bias_row if kind.routes else None)
            return x, out

        rows = None
        if bias is not None and any(kind.routes for kind in kinds):
            rows, done = bias[done:done + n], done + n
        x, out = _walk_layers(unit_run, layers[f"l{first}"], x, rows, n)
        for letter in unit:
            st, experts, seen = out[letter]
            for into, part in ((stats, st), (sample, seen)):
                for k, v in part.items():
                    into.setdefault(k, []).append(v)
            if experts is not None:
                chosen.append(experts)
    with jax.named_scope("otpu_stats"):
        cat = lambda of: {k: jnp.concatenate(v) for k, v in of.items()}
        return x, (cat(stats), jnp.concatenate(chosen) if chosen else None,
                   cat(sample))


def exit_distribution(gate):
    """A looped model's exit distribution from its gate's products ``gate``
    (T, rows) float32, a pass a row: with ``lambda_t = sigmoid(gate_t)``,
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for t < T and the last
    pass takes what is left, ``p_T = prod_{j<T} (1 - lambda_j)`` (its own
    gate is not read).  Returns (p, log p), the logarithm summed from
    ``log_sigmoid`` of the product and of its negative, so that no
    ``log(0)`` arises however far the gate saturates; at T = 1 p is 1 and
    log p 0 exactly."""
    stay = jax.nn.log_sigmoid(-gate[:-1])           # log(1 - lambda_t)
    none = jnp.zeros_like(gate[:1])
    log_p = jnp.concatenate([none, jnp.cumsum(stay, axis=0)]) \
        + jnp.concatenate([jax.nn.log_sigmoid(gate[:-1]), none])
    return jnp.exp(log_p), log_p


def loop_counts(cfg: ModelConfig, b: int, s: int) -> dict:
    """The SPC counters a looped model's step moves beside its layers'
    (``train.plan_of``), from the shapes: ``loop_built`` 1, the passes,
    the layers held, the layer applications (passes x layers: over the
    layers held, the times a leaf is read a pass of the step) and the rows
    the head reads (passes x tokens of a shard (b, s))."""
    t = cfg.total_ut_steps
    return {"loop_built": 1, "loop_passes": t,
            "loop_layers_held": cfg.layers_here,
            "loop_layer_applications": t * cfg.layers_here,
            "loop_head_rows": t * b * s}


def looped_loss(params, x, labels, cfg: ModelConfig, run_of, psum,
                n_global: int, at_head):
    """A looped model's loss (Ouro, arXiv:2510.25741: its Stage I
    objective) from the embedded rows ``x`` (b, s, d), and what a step
    reports of it.  ``h_0 = x``; for pass t = 1 .. ``total_ut_steps``
    **``h_t = RMSNorm_f(Layers(h_{t-1}))``**: one ``lax.scan`` over the
    passes whose body closes over ``params["layers"]``, so every pass reads
    the same leaves, the tree holds each once and a leaf's gradient is the
    sum over the passes; each layer application under the walk's
    ``jax.checkpoint`` as in any model.  Behind every pass the one head and
    the one exit gate read ``h_t``: ``lambda_t = sigmoid(h_t . w + b)``,
    the exit distribution ``p`` a token row (``exit_distribution``), and

    ``L = (1 / (b s)) sum_i [ sum_t p_t,i CE_t,i - exit_beta H(p_.,i) ]``

    with ``CE_t,i`` the row's cross-entropy behind pass t and ``H`` the
    entropy.  The head reads all ``T b s`` rows in **one** blocked
    cross-entropy, the rows weighted by ``p`` as a constant (one (d, V)
    gradient accumulated, no (rows, V) array held); the gate's gradient
    comes through ``p_t,i`` times the row's own cross-entropy, which the
    head returns a row, and through the entropy.

    ``aux``: ``losses`` [total, the passes' mean cross-entropies, the
    expected cross-entropy, ``exit_beta`` x the mean entropy]; ``rows`` (b
    s, T, 2); ``sample`` the layers' rows stacked over the T x layers
    applications, ``head_in`` (R, T, d), ``exit_logit`` (R, T) and
    ``exit_entropy`` (R,); ``exit_p`` (R, T) at the sampled rows and
    ``exit_mean`` (T,) over the whole batch; ``loads`` and ``experts`` with
    no entry, since nothing routes."""
    b, s, d = x.shape
    n, t = b * s, cfg.total_ut_steps

    def one_pass(x, _):
        with jax.named_scope("otpu_loop_pass"):
            with jax.named_scope("otpu_layers"):
                x, (_, _, seen) = _walk_pattern(run_of, params["layers"], x,
                                                None, cfg)
            h = rmsnorm_gain(x, params["final_norm"], cfg.rms_norm_eps)
        return h, (h, seen)

    _, (hs, seen) = jax.lax.scan(one_pass, x, None, length=t)
    hs = hs.reshape(t, n, d)
    with jax.named_scope("otpu_exit_gate"):
        gate = jnp.sum(hs * params["exit_gate"]["w"], -1) \
            + params["exit_gate"]["b"][0]
        p, log_p = exit_distribution(gate)                   # (t, n) f32
    with jax.named_scope("otpu_head"):
        ce_sum, rows = head_cross_entropy(
            hs.reshape(t * n, d), params["head"],
            jnp.tile(labels[:, :s].reshape(n), t),
            min(cfg.loss_block_rows, n), cfg.compute_dtype,
            jax.lax.stop_gradient(p).reshape(t * n), scope="otpu_exit_loss")
    with jax.named_scope("otpu_exit_loss"):
        rows = jax.lax.stop_gradient(rows).reshape(t, n, 2)
        lost = rows[..., 0] - rows[..., 1]
        # the head weighted its rows by p as a constant; p's own gradient
        # is the row's cross-entropy: a term of value zero that carries it
        through_p = jnp.sum(p * lost)
        expected = ce_sum + (through_p - jax.lax.stop_gradient(through_p))
        entropy = -jnp.sum(p * log_p, axis=0)                # (n,)
    with jax.named_scope("otpu_loss"):
        ce = psum(expected) / n_global
        bonus = cfg.exit_beta * psum(jnp.sum(entropy)) / n_global
        total = ce - bonus
    with jax.named_scope("otpu_stats"):
        losses = jnp.concatenate([
            jnp.stack([total]), psum(jnp.sum(lost, axis=1)) / n_global,
            jnp.stack([ce, bonus])])
        sample = {k: v.reshape((-1,) + v.shape[2:]) for k, v in seen.items()}
        sample.update(head_in=hs[:, at_head].transpose(1, 0, 2),
                      exit_logit=gate[:, at_head].T,
                      exit_entropy=entropy[at_head])
        return total, {
            "losses": losses, "rows": rows.transpose(1, 0, 2),
            "loads": jnp.zeros((0, cfg.num_experts), jnp.float32),
            "experts": jnp.zeros((0, n, 0), jnp.int32), "sample": sample,
            "exit_p": p[:, at_head].T,
            "exit_mean": psum(jnp.sum(p, axis=1)) / n_global}


def model_loss(params, tokens, labels, cfg: ModelConfig, *, interpret: bool,
               n_global: int, axes: tuple = (), bias=None):
    """The training loss of one micro-batch shard and what a step
    reports of it.  ``n_global`` is the tokens of the whole batch and
    ``axes`` the mesh axes it is sharded over: sums cross them by
    ``psum``, so every shard returns the whole batch's loss.  ``bias``
    holds the routers' balancing biases where they choose under one
    (``layers`` (L, E) and ``mtp`` (1, E)); nothing is differentiated
    with respect to it.  Where the model has a next-next-token module,
    ``labels`` is one position longer than ``tokens``: ``labels[:, i]``
    follows ``tokens[:, i]`` and ``labels[:, i + 1]`` follows that.

    A model trained by block diffusion (``cfg.block_length``: SDAR, on
    BD3-LM's objective) reads ``tokens`` as the clean sequences ``x0`` and
    of ``labels`` the last two ids alone, which key the step's noise
    (``block_diffusion_noise``).  The layers walk the ``2 s`` rows ``[xt ;
    x0]`` of every sequence, the noisy copy (a masked token replaced by
    ``mask_token_here``) before the clean one, and know nothing of the
    halves but through their attention sublayer's mask and positions.  The
    head reads the noisy half's rows, and the loss is ``(1 / (b s)) sum_i
    m_i / t_blk(i) x (-log softmax(logits_i)[x0_i])``, ``m_i`` 1 where row
    i is masked: no shift, MDLM's weight for the linear schedule
    (arXiv:2406.07524), an unmasked row at weight zero; the load-balancing
    loss is over all ``2 s`` rows' routing.  ``rows`` then holds the noisy
    half's rows, and ``aux`` also the noise: ``bd_mask`` (b, s) uint8,
    ``bd_levels`` (b, s / B), ``bd_masked`` the masked rows and
    ``bd_weight_sum`` their weights' sum, over the whole batch.

    A looped model (``cfg.total_ut_steps``: Ouro) walks its held layers
    that many times and has its own loss and ``aux``: ``looped_loss``.

    A model trained padding-free (``cfg.eos_token_here`` a row of the
    vocabulary: granite-4.0-h-micro) reads each row of ``tokens`` as
    documents laid end to end, each ending in that id: ``documents`` makes
    a position's document once, under ``otpu_embed``, and the walk hands
    it to every layer, whose operator reads nothing across a document's
    start.  Every row's loss counts, an end-of-document row's too (its
    label is the next document's first token).  ``aux`` then holds
    ``doc`` (b, s) int32, every position's document.
    The embedding's rows are times ``cfg.embedding_multiplier`` and the
    logits over ``cfg.logits_scaling``; a model without a router reports
    ``loads`` and ``experts`` with no entry.

    A model with several residual streams (``cfg.hc_mult`` n > 1:
    Xing4.0-29B-A4B's manifold-constrained hyper-connections,
    ``parallel/hyper.py``) walks a stream (b, n, s, d), stream-major: n
    copies of the embedding's rows, made under ``otpu_embed``; the walk's
    carry and a layer's checkpoint hold all n; the head reads their sum,
    made under ``otpu_head``.  ``sample`` then holds the path's rows of
    every held layer (``hyper.reports``), the dense layers' first, and
    ``aux`` ``hc_defect``, the largest defect from doubly stochastic of the
    mixing maps at the sampled rows."""
    psum = (lambda a: jax.lax.psum(a, axes)) if axes else (lambda a: a)
    b, s = tokens.shape
    ids, levels, masked = tokens, None, None
    if cfg.block_length:
        with jax.named_scope("otpu_bd_noise"):
            levels, masked = block_diffusion_noise(tokens, labels, cfg)
            ids = jnp.concatenate([jnp.where(
                masked, jnp.asarray(cfg.mask_token_here, tokens.dtype),
                tokens), tokens], axis=1)
    at = sample_rows(ids.size)          # of the rows the layers walk
    at_head = sample_rows(b * s)        # of the rows the head reads
    bias = bias or {}
    doc = None
    if cfg.eos_token_here >= 0:
        with jax.named_scope("otpu_embed"):
            doc = documents(ids, cfg.eos_token_here)

    @functools.cache    # one function a kind, so that JAX traces it once
    def run_of(kind: str):
        """A layer's run, ``kind`` its kind's name (``model.layer_kinds``):
        static, because two kinds of attention layer hold the same
        leaves."""
        operator = layer_kinds(cfg)[kind].operator
        own = set(operator.reports(cfg)) if operator else ()
        # the residual path's rows, under their names; ``hyper.seen`` cut
        # them at the sampled rows
        cut = {k for path in hyper.SETS for k in hyper.reports(cfg, path)} \
            if cfg.hc_mult > 1 else ()
        own = {*own, *cut}

        def run(layer, x, bias_row):
            x, st, seen = decoder_layer(layer, x, cfg, interpret=interpret,
                                        bias=bias_row, kind=kind, at=at,
                                        doc=doc)
            experts = seen.pop("experts", None)
            with jax.named_scope("otpu_stats"):
                # an operator's rows under their own names, a router's
                # behind ``router_``: at the sampled rows, but for what
                # was cut there already (``_at``) and the sequences a
                # position's result holds every earlier row of (``_seq``)
                out = (jax.tree.map(psum, st), experts, {
                    k if k in own else "router_" + k:
                    v if k in cut or k.endswith(("_seq", "_at")) else v[at]
                    for k, v in seen.items()})
            return x, out

        if cfg.layers_here + cfg.n_mtp_here > 1 or cfg.total_ut_steps > 1:
            # a layer's activations are recomputed in its backward pass,
            # so that one layer's are held at a time and not every
            # layer's; with one layer there is nothing to save.  Kept from
            # the forward pass is only what its sublayers name
            return jax.checkpoint(run, policy=layer_checkpoint_policy())
        return run

    with jax.named_scope("otpu_embed"):
        x = params["embed"][ids]                             # (b, s, d) f32
        if cfg.embedding_multiplier != 1.0:
            x = cfg.embedding_multiplier * x
        if cfg.hc_mult > 1:     # the stream starts as hc_mult copies
            x = jnp.broadcast_to(x[:, None], (b, cfg.hc_mult, s,
                                              x.shape[-1]))
    if cfg.total_ut_steps:
        return looped_loss(params, x, labels, cfg, run_of, psum, n_global,
                           at_head)
    with jax.named_scope("otpu_layers"):
        if cfg.pattern_here:
            x, (st, chosen, sample) = _walk_pattern(
                run_of, params["layers"], x, bias.get("layers"), cfg)
        else:
            lead = None
            if cfg.n_dense_here:
                x, lead = _walk_layers(run_of("dense"), params["dense"], x,
                                       None, cfg.n_dense_here)
            x, (st, chosen, sample) = _walk_layers(
                run_of("layers"), params["layers"], x, bias.get("layers"),
                cfg.n_sparse_here)
            if cfg.hc_mult > 1 and lead is not None:
                # the residual path's rows of every held layer, the dense
                # ones' first
                with jax.named_scope("otpu_stats"):
                    sample = {k: jnp.concatenate([lead[2][k], v])
                              if k in lead[2] else v
                              for k, v in sample.items()}
    head_rows = min(cfg.loss_block_rows, b * s)
    # a tied head reads the embedding matrix itself: one leaf, whose
    # gradient is the sum of the gather's and the cross-entropy's
    head = params["embed"].T if cfg.tie_word_embeddings else params["head"]
    with jax.named_scope("otpu_head"):
        if masked is None:
            targets, weighted = labels[:, :s], ()
        else:
            # the noisy half's rows against their own clean tokens
            x, targets = x[:, :s], tokens
            with jax.named_scope("otpu_bd_loss"):
                weights = jnp.where(masked, 1.0 / jnp.repeat(
                    levels, cfg.block_length, axis=1), 0.0)
                weighted = (weights.reshape(b * s),)
        if cfg.hc_mult > 1:     # the head reads the streams' sum
            x = jnp.sum(x, axis=1)
        h = rmsnorm_gain(x, params["final_norm"], cfg.rms_norm_eps)
        ce_sum, rows = head_cross_entropy(
            h.reshape(b * s, -1), head, targets.reshape(b * s), head_rows,
            cfg.compute_dtype, *weighted,
            logits_scaling=cfg.logits_scaling)
    # rows of all routers' logits
    routed = cfg.n_sparse_here * n_global * ids.shape[1] // s
    with jax.named_scope("otpu_loss"):
        ce = psum(ce_sum) / n_global
        lb = z = jnp.zeros((), jnp.float32)
        if "prob_sum" in st:
            # HF's load_balancing_loss_func: every layer's rows in one
            # mean
            slots, prob_sum = (jnp.sum(st["slots"], 0),
                               jnp.sum(st["prob_sum"], 0))
            lb = cfg.num_experts * jnp.sum((slots / routed)
                                           * (prob_sum / routed))
            if "z_sum" in st:
                z = jnp.sum(st["z_sum"], 0) / routed
        lb, z = cfg.aux_loss_coef * lb, cfg.z_loss_coef * z
        total = ce + lb + z
        # a learned selection's alignment loss, every layer's rows in one
        # mean a token: its gradient reaches the indexers' leaves alone
        index = None
        if "index_kl_sum" in st:
            index = cfg.index_loss_coef * jnp.sum(st["index_kl_sum"]) \
                / n_global
            total = total + index
    losses = [ce, lb, z]
    with jax.named_scope("otpu_stats"):
        sample["head_in"] = h.reshape(b * s, -1)[at_head]
        if "slots" in st:
            loads = st["slots"]
        else:       # no layer routes (``looped_loss``'s form)
            loads = jnp.zeros((0, cfg.num_experts), jnp.float32)
            chosen = jnp.zeros((0, b * s, 0), jnp.int32)
    aux = {} if doc is None else {"doc": doc}
    if cfg.hc_mult > 1:
        with jax.named_scope("otpu_stats"):
            # what the sweeps left of every sampled mixing map
            worst = hyper.defect(jax.lax.stop_gradient(jnp.stack(
                [sample[f"{at}_res"] for at in hyper.SETS])))
            aux["hc_defect"] = jax.lax.pmax(worst, axes) if axes else worst
    if masked is not None:
        with jax.named_scope("otpu_stats"):
            aux.update(
                bd_mask=masked.astype(jnp.uint8), bd_levels=levels,
                bd_masked=psum(jnp.sum(masked.astype(jnp.float32))),
                bd_weight_sum=psum(jnp.sum(weights)))
    if cfg.n_mtp_here:
        # DeepSeek-V3's multi-token prediction, depth one: the last
        # layer's output (before the final norm) joined with the next
        # token's embedding, one more sparse layer, the same embedding
        # and head, a cross-entropy against the token after the next
        mtp = params["mtp"]
        with jax.named_scope("otpu_mtp"):
            nxt = rmsnorm_gain(params["embed"][labels[:, :s]], mtp["enorm"],
                               cfg.rms_norm_eps)
            prev = rmsnorm_gain(x, mtp["hnorm"], cfg.rms_norm_eps)
            joined = jnp.concatenate([nxt, prev], -1).reshape(b * s, -1)
            x2 = matmul(joined, mtp["proj"], cfg.compute_dtype
                        ).reshape(b, s, -1)
            with jax.named_scope("otpu_layers"):
                x2, (st2, chosen2, sample2) = _walk_layers(
                    run_of("layers"), jax.tree.map(lambda a: a[None], {
                        k: v for k, v in mtp.items()
                        if k not in ("enorm", "hnorm", "proj", "norm")}),
                    x2, bias.get("mtp"), 1)
            with jax.named_scope("otpu_head"):
                h2 = rmsnorm_gain(x2, mtp["norm"], cfg.rms_norm_eps)
                ce2_sum, aux["mtp_rows"] = head_cross_entropy(
                    h2.reshape(b * s, -1), head,
                    labels[:, 1:].reshape(b * s), head_rows,
                    cfg.compute_dtype)
        with jax.named_scope("otpu_loss"):
            losses.append(cfg.mtp_loss_coef * psum(ce2_sum) / n_global)
            total = total + losses[-1]
        with jax.named_scope("otpu_stats"):
            loads = jnp.concatenate([loads, st2["slots"]])
            chosen = jnp.concatenate([chosen, chosen2])
            sample = {**{k: jnp.concatenate([sample[k], sample2[k]])
                         for k in sample2},
                      "head_in": sample["head_in"],
                      "mtp_head_in": h2.reshape(b * s, -1)[at]}
    if cfg.n_experts_here < cfg.num_experts:
        first = cfg.first_expert_here
        chunk = experts.chunk_rows(ids.size, cfg.num_experts_per_tok,
                                   cfg.n_experts_here, cfg.num_experts)
        with jax.named_scope("otpu_stats"):
            held = jnp.sum(loads[:, first:first + cfg.n_experts_here],
                           axis=1)
            aux["local_slots"] = jnp.sum(held)
            # what the held experts' loops walked: a layer's held slots
            # in whole chunks (``experts.local_expert_ffn``)
            aux["chunk_rows"] = jnp.sum(
                (held.astype(jnp.int32) + chunk - 1) // chunk * chunk)
    if index is not None:
        losses.append(index)
    with jax.named_scope("otpu_stats"):
        losses = jnp.stack([total] + losses)
    return total, {"losses": losses, "loads": loads, "rows": rows,
                   "experts": chosen, "sample": sample, **aux}
