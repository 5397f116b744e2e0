"""JoyAI-LLM-Flash's training step on the normal path (``parallel/train
.py``'s model path: latent attention, a dense layer, sparse layers with a
shared expert beside a share of the routed ones, sigmoid routing under a
balancing bias, a next-next-token module) against the plain reference
(``parallel/joyai_reference.py``) at small widths on seeded random
weights: hidden 64, 4 heads of 16 + 8 / 16, latents 32 and 16, dense width
96, 16 experts of width 32 of which 4 are held (share 1 of 4), top 4, a
slice of 64 of 512 ids, sequences of 32, 1 dense + 2 sparse layers and the
module.  Float32 compute meets the reference at rtol 1e-5.  The benchmark's
own copy of the reference (``benchmark/harness/joyaikit.py``) is held to
the same, and its deliberately wrong variants must fail."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import (attention, causal, config, experts,
                               joyai_reference, layers, objective, train)
from ompi_tpu.parallel.flagship import _full_attention
from ompi_tpu.parallel.mesh import MeshSpec, make_mesh
from ompi_tpu.runtime import spc

import built

ref = built.programs(joyai_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PUBLISHED = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=4, num_experts_per_tok=4, vocab_size=512,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1,
    moe_intermediate_size=32, n_shared_experts=1, scoring_func="sigmoid",
    topk_method="noaux_tc", routed_scaling_factor=2.5, norm_topk_prob=True,
    num_nextn_predict_layers=1, rope_theta=32e6, rms_norm_eps=1e-6)
SHARE = dict(layers_here=3, experts_here=4, expert_share=1, vocab_here=64)
TRAIN = dict(seq_len=32, micro_batch=2, attn_block=16, loss_block_rows=16,
             lr=1e-2, aux_loss_coef=0.0, z_loss_coef=0.0, mtp_loss_coef=0.3,
             bias_update_gamma=0.001)
F32 = config.ModelConfig(compute_dtype="float32", num_experts=16,
                        **PUBLISHED, **SHARE, **TRAIN)
LEAVES = [name for name, _ in train.leaf_names(F32)]
CLOSE = dict(rtol=1e-5, atol=1e-6)


def batch_of(seed, vocab=64):
    """(inputs (2, 32), labels (2, 33): the next token and the one after)
    from 34 ids a sequence."""
    return built.batch(F32, seed, vocab)


def some_bias(cfg=F32, scale=0.01):
    """Biases that are not zero, so that a choice made without them, or
    weights made with them, differ."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    return {"layers": scale * jax.random.normal(
        k1, (cfg.n_sparse_here, cfg.num_experts)),
        "mtp": scale * jax.random.normal(k2, (1, cfg.num_experts))}


@pytest.fixture(scope="module")
def params():
    return built.params(F32, 3)


@pytest.fixture(scope="module")
def reference(params):
    (total, (ce, mtp, loads)), grads = ref.grads(
        params, *batch_of(0), F32, some_bias())
    return dict(parts=np.asarray([total, ce, mtp]), loads=np.asarray(loads),
                grads=grads)


def system_loss(params, cfg, batch, bias):
    tokens, labels = batch
    return objective.model_loss(params, tokens, labels, cfg, interpret=True,
                            n_global=tokens.size, bias=bias)


@pytest.fixture(scope="module")
def system(params):
    (total, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: system_loss(p, F32, batch_of(0), some_bias()),
        has_aux=True))(params)
    return dict(total=total, aux=aux, grads=grads)


def run_steps(cfg, params, seeds, dp=1, fresh=False):
    """The state and each step's ``aux`` after one optimiser step a
    seed's batch, through ``build_train_step`` on ``dp`` CPU devices:
    the process's one step of ``cfg``, or (``fresh``) one built now, which
    is traced under what the caller has patched."""
    step, place = (built.fresh_step if fresh else built.step)(cfg, dp)
    state, out = None, []
    for seed in seeds:
        tokens, labels = batch_of(seed)
        if state is None:
            state, tokens, labels = place(jax.tree.map(jnp.copy, params),
                                          tokens, labels)
        state, aux = step(state, tokens, labels)
        out.append(aux)
    return state, out


def test_forward_logits_of_both_heads(params, system):
    """Every row's logsumexp and label logit, of the head and of the
    module's use of it."""
    tokens, labels = batch_of(0)
    with jax.default_matmul_precision("highest"):
        logits, logits2, _ = ref.forward(params, tokens, labels, F32,
                                         some_bias())
    for got, lg, lab in ((system["aux"]["rows"], logits, labels[:, :-1]),
                         (system["aux"]["mtp_rows"], logits2, labels[:, 1:])):
        lg = lg.reshape(-1, F32.vocab_rows)
        want = jnp.stack([jax.nn.logsumexp(lg, -1), jnp.take_along_axis(
            lg, lab.reshape(-1, 1), -1)[:, 0]], -1)
        np.testing.assert_allclose(got, want, **CLOSE)


def test_loss_and_its_parts(system, reference):
    losses = np.asarray(system["aux"]["losses"])   # total, ce, lb, z, mtp
    np.testing.assert_allclose(losses[[0, 1, 4]], reference["parts"],
                               **CLOSE)
    assert losses[2] == losses[3] == 0             # no auxiliary loss
    np.testing.assert_array_equal(system["aux"]["loads"], reference["loads"])
    assert system["aux"]["loads"].shape == (3, 16)
    assert (system["aux"]["loads"].sum(-1) == 64 * 4).all()
    held = reference["loads"][:, 4:8].sum()
    assert system["aux"]["local_slots"] == held


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf(leaf, system, reference):
    path = dict(train.leaf_names(F32))[leaf]
    got = train._leaf(system["grads"], path)
    want = train._leaf(reference["grads"], path)
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("dp", [1, 2])
def test_parameters_and_bias_after_three_steps(dp, params):
    """``dp = 2`` gives the parameters and the balancing biases of ``dp
    = 1`` and of the reference on the same global batches (the loads
    that move the bias are the whole batch's)."""
    seeds = (0, 1, 2)
    state, auxes = run_steps(F32, params, seeds, dp=dp)
    want, bias, losses = ref.train_steps(
        params, [batch_of(s) for s in seeds], F32)
    np.testing.assert_allclose([a["losses"][0] for a in auxes], losses,
                               rtol=1e-5)
    for name, path in train.leaf_names(F32):
        np.testing.assert_allclose(
            train._leaf(state[0], path), train._leaf(want, path), rtol=1e-5,
            atol=0.01 * 3 * F32.lr, err_msg=name)
    for key in ("layers", "mtp"):
        np.testing.assert_array_equal(state[4][key], bias[key])
    moved = np.abs(np.asarray(state[4]["layers"]))
    assert moved.max() <= 3 * F32.bias_update_gamma + 1e-9 and moved.any()


def test_what_the_checkpoint_keeps_changes_no_number(params, monkeypatch):
    """A layer's checkpoint keeps the routing results an expert block
    names (``experts.CHECKPOINT_KEEPS``) and attention's o and logsumexp
    (``model.CHECKPOINT_KEEPS``): every gradient entry, and what a step
    reports, is bit for bit what the bare checkpoint gives.  The
    gradients are compared one primitive at a time (``disable_jit``),
    where a number depends on the program alone: run eagerly, each
    checkpointed layer's backward pass is a compiled unit of its own, in
    which XLA's CPU backend fuses a recomputed o into ``delta``'s sum and
    adds that loop up in another order than over a stored o, so the bare
    checkpoint's dq and dk move in their last bit.  The step jitted whole
    (``run_steps``) is compared as compiled: two steps of the test's own,
    each traced under the policy of its moment."""
    def grads():
        with jax.disable_jit():
            return jax.value_and_grad(
                lambda p: system_loss(p, F32, batch_of(0), some_bias()),
                has_aux=True)(params)

    (total, kept_aux), kept = grads()
    _, (stepped,) = run_steps(F32, params, (0,), fresh=True)
    monkeypatch.setattr(objective, "layer_checkpoint_policy",
                        lambda: jax.checkpoint_policies.nothing_saveable)
    (bare_total, aux), bare = grads()
    _, (bare_stepped,) = run_steps(F32, params, (0,), fresh=True)
    assert total == bare_total
    for name, path in train.leaf_names(F32):
        np.testing.assert_array_equal(train._leaf(kept, path),
                                      train._leaf(bare, path), name)
    for key in ("losses", "loads", "experts"):
        np.testing.assert_array_equal(kept_aux[key], aux[key], key)
        np.testing.assert_array_equal(stepped[key], bare_stepped[key], key)
    for key in ("grad_sq", "grad_probe", "param_probe"):
        np.testing.assert_array_equal(stepped[key], bare_stepped[key], key)


# causal attention alone, at the widths its two callers under a
# checkpoint give it: latent attention's (q, k 192 wide, v 128) and
# grouped-query attention's (4 query heads on one key-value head of 128)
SEAMS = {"latent": (4, 4, 192, 128), "grouped_query": (4, 1, 128, 128)}


@pytest.mark.parametrize("seam", SEAMS)
def test_attention_alone_keeps_o_and_the_logsumexp(seam):
    """``causal_flash_attention`` under ``jax.checkpoint`` with the
    layers' policy and with none: o, dq, dk and dv are bit for bit the
    same (one primitive at a time, as above), and the backward pass the
    policy leaves holds no second forward pass (no ``exp`` but the block
    pairs' own)."""
    nh, nkv, hd, hv = SEAMS[seam]
    rng = np.random.default_rng(44)
    draw = lambda n, w: jnp.asarray(rng.normal(size=(2, n, 64, w)),
                                    jnp.float32)
    q, k, v, w = draw(nh, hd), draw(nkv, hd), draw(nkv, hv), draw(nh, hv)

    def attend(q, k, v):
        k, v = (jnp.repeat(a, nh // nkv, 1) for a in (k, v))
        o = causal.causal_flash_attention(jnp.tanh(q), k, v, 16, True)
        return jnp.sum(o * w), o

    def exps(jaxpr):
        inner = [getattr(sub, "jaxpr", sub) for eqn in jaxpr.eqns
                 for val in eqn.params.values()
                 for sub in (val if isinstance(val, (tuple, list)) else [val])]
        return sum(eqn.primitive.name == "exp" for eqn in jaxpr.eqns) + sum(
            exps(sub) for sub in inner if hasattr(sub, "eqns"))

    got = {}
    for name, policy in (("kept", objective.layer_checkpoint_policy()),
                         ("bare", None)):
        run = jax.value_and_grad(jax.checkpoint(attend, policy=policy),
                                 (0, 1, 2), has_aux=True)
        with jax.disable_jit():
            (_, o), grads = run(q, k, v)
        got[name] = (o, *grads), exps(jax.make_jaxpr(run)(q, k, v).jaxpr)
    for kept, bare in zip(got["kept"][0], got["bare"][0]):
        np.testing.assert_array_equal(kept, bare)
    # 4 blocks: 10 block pairs, each an exp of its scores and, forward,
    # one of the running maximum's step
    assert (got["kept"][1], got["bare"][1]) == (30, 50)


def test_a_step_reports_what_it_counted(params):
    if "train_steps" not in spc.counters():
        spc.init()
    names = ("train_steps", "train_steps_read", "moe_local_slots",
             "moe_absent_slots", "moe_chunk_rows")
    before = {k: spc.read(k) for k in names}
    _, (aux,) = run_steps(F32, params, (0,))
    fullest = train.record_step_stats(aux)
    moved = {k: spc.read(k) - before[k] for k in names}
    assert moved["train_steps"] == moved["train_steps_read"] == 1
    # tokens, routed slots and bias updates: the configuration's
    # constants times the steps issued (2 sparse layers + the module)
    assert F32.micro_batch * F32.seq_len == 64 and F32.n_mtp_here == 1
    assert F32.num_experts_per_tok * F32.n_routers == 4 * 3
    assert moved["moe_local_slots"] == int(aux["local_slots"])
    assert moved["moe_local_slots"] + moved["moe_absent_slots"] == 768
    # the three loops' held slots (the loads' columns 4-7) in whole chunks
    rows = experts.chunk_rows(64, 4, 4, 16)
    held = np.asarray(aux["loads"])[:, 4:8].sum(axis=1).astype(int)
    assert moved["moe_chunk_rows"] == int(aux["chunk_rows"]) \
        == (-(-held // rows) * rows).sum() >= moved["moe_local_slots"]
    assert fullest == np.asarray(aux["loads"]).max() >= 16
    assert aux["grad_probe"].shape == (len(LEAVES), train.PROBE)
    assert aux["sample"]["router_scores"].shape == (3, objective.SAMPLE_ROWS, 16)
    assert aux["sample"]["mtp_head_in"].shape == (objective.SAMPLE_ROWS, 64)


def sparse_leaves(cfg, seed=5, hot=None):
    """One sparse layer's MLP leaves (every routed expert, not a share);
    with ``hot`` that expert's router column is aligned with every
    normed row, so that every token chooses it."""
    rng = np.random.default_rng(seed)
    d, e, f = cfg.hidden_size, cfg.num_experts, cfg.expert_width
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.2, shape), jnp.float32)
    router = jnp.asarray(rng.normal(0, 0.02, (d, e)), jnp.float32)
    if hot is not None:
        router = router.at[:, hot].set(1.0)
    return {"ln2": jnp.ones((d,)), "router": router, "gate": draw(e, d, f),
            "up": draw(e, d, f), "down": draw(e, f, d),
            "shared_gate": draw(d, f), "shared_up": draw(d, f),
            "shared_down": draw(f, d)}


def share_of(p, cfg):
    """The leaves a rank of ``cfg``'s share holds of ``p``."""
    lo = cfg.first_expert_here
    cut = {k: p[k][lo:lo + cfg.n_experts_here]
           for k in ("gate", "up", "down")}
    return {**p, **cut}


def test_a_hot_held_expert_drops_no_slot():
    """Every token's first choice is held expert 5: its group is as long
    as the batch, the held slots are several of the loop's chunks
    (``experts.chunk_rows``), and the output is what the dense reference
    computes, so nothing fell through."""
    p = share_of(sparse_leaves(F32, hot=5), F32)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.uniform(0.5, 1.5, (2, 32, 64)), jnp.float32)
    bias = jnp.zeros((16,))
    out, stats, routed = experts.moe_shared_local_block(p, x, F32, bias)
    assert (routed["experts"][:, 0] == 5).all()
    assert stats["slots"][5] == 64 and stats["slots"].sum() == 256
    held = int(stats["slots"][4:8].sum())
    rows = experts.chunk_rows(64, 4, 4, 16)
    assert stats["slots"][5] > rows and held > 2 * rows     # several trips
    h = ref._norm(x, p["ln2"], F32.rms_norm_eps).reshape(64, 64)
    with jax.default_matmul_precision("highest"):
        want, load = ref.sparse_mlp(p, h, bias, F32)
    np.testing.assert_array_equal(stats["slots"], load)
    np.testing.assert_allclose(out.reshape(64, 64), want, rtol=1e-5,
                               atol=1e-5)


def test_no_held_slot_is_an_empty_part():
    """A router that never chooses a held expert: the layer's output is
    the shared expert alone, and every chunk is skipped."""
    p = sparse_leaves(F32)
    p["router"] = p["router"].at[:, 4:8].set(-1.0)
    p = share_of(p, F32)
    x = jnp.asarray(np.random.default_rng(2).uniform(0.5, 1.5, (2, 32, 64)),
                    jnp.float32)
    out, stats, _ = experts.moe_shared_local_block(p, x, F32, jnp.zeros((16,)))
    assert stats["slots"][4:8].sum() == 0
    h = ref._norm(x, p["ln2"], F32.rms_norm_eps).reshape(64, 64)
    with jax.default_matmul_precision("highest"):
        want = ref.swiglu(h, p["shared_gate"], p["shared_up"],
                          p["shared_down"])
    np.testing.assert_allclose(out.reshape(64, 64), want, **CLOSE)


def test_the_shares_add_up():
    """The four shares' routed parts plus the shared expert once equal
    the uncut layer of the reference: what ties the chip's share to the
    model."""
    p = sparse_leaves(F32, seed=11)
    x = jnp.asarray(np.random.default_rng(12).normal(0, 1, (2, 32, 64)),
                    jnp.float32)
    bias = some_bias()["layers"][0]
    uncut = dataclasses.replace(F32, experts_here=0, expert_share=0)
    h = ref._norm(x, p["ln2"], F32.rms_norm_eps).reshape(64, 64)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.sparse_mlp(p, h, bias, uncut)
        shared = ref.swiglu(h, p["shared_gate"], p["shared_up"],
                            p["shared_down"])
    total, slots = shared, 0
    for share in range(4):
        cfg = dataclasses.replace(F32, expert_share=share)
        out, stats, _ = experts.moe_shared_local_block(share_of(p, cfg), x, cfg,
                                                   bias)
        total = total + (out.reshape(64, 64) - shared)
        lo = cfg.first_expert_here
        slots += int(stats["slots"][lo:lo + 4].sum())
    assert slots == 64 * 4                  # every slot is some share's
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def test_a_sliced_vocabulary_is_a_smaller_vocabulary(params):
    """The share's embedding and head have ``vocab_here`` rows and
    columns, and the step is that of an uncut model whose vocabulary is
    that small."""
    assert params["embed"].shape == (64, 64)
    assert params["head"].shape == (64, 64)
    small = dataclasses.replace(F32, vocab_size=64, vocab_here=0)
    assert train.model_param_shapes(small) == train.model_param_shapes(F32)
    loss = built.program(system_loss)
    got = loss(params, small, batch_of(0), some_bias())[0]
    want = loss(params, F32, batch_of(0), some_bias())[0]
    assert float(got) == float(want)


def test_the_attention_backward_by_scan_is_the_unrolled_one():
    """Beyond ``UNROLLED_BLOCKS`` blocks the flash backward walks its
    block pairs by a scan: the same pairs in the same order, so the
    same gradients (8 blocks of 4 against 2 of 16)."""
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(0, 1, (2, 4, 32, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(0, 1, (2, 4, 32, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (2, 4, 32, 16)), jnp.float32)

    def grads(block):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            causal.causal_flash_attention(q, k, v, block, True) * w),
            argnums=(0, 1, 2)))(q, k, v)

    assert 32 // 4 > causal.UNROLLED_BLOCKS >= 32 // 16
    for got, want in zip(grads(4), grads(16)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    full = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        _full_attention(q, k, v, True) * w), argnums=(0, 1, 2)))(
        q, k, v)
    for got, want in zip(grads(4), full):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def _ulps(got, want, scale):
    """|got - want| in float32 ulps of ``scale`` (an array of the terms'
    sizes: a sum that cancels is judged by what was summed)."""
    return float(np.max(np.abs(np.float32(got) - np.float32(want))
                        / np.spacing(np.float32(scale))))


def _swapped(x, first):
    """The rotary partner of ``x``'s entries from ``first`` on, by index
    arithmetic of its own (neither a roll nor a product)."""
    pairs = x[..., first:].reshape(*x.shape[:-1], -1, 2)
    return jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(
        *x.shape[:-1], -1)


def _mla_attention_rolled(p, x, cfg):
    """``attention.mla_attention`` as it was before PR 41: RoPE by
    ``rope_interleaved`` (two rolls) on the projections' results."""
    b, s, _ = x.shape
    nh, dt, eps = cfg.num_attention_heads, cfg.compute_dtype, cfg.rms_norm_eps
    nope, rot, hv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    h = layers.rmsnorm_gain(x, p["ln1"], eps)
    cq = layers.rmsnorm_gain(layers.matmul(h, p["wq_a"], dt), p["q_a_norm"],
                            eps)
    q = layers.matmul(cq, p["wq_b"], dt).reshape(b, s, nh, nope + rot)
    q = layers.rope_interleaved(q, cfg.rope_theta, first=nope, seq_axis=1)
    kv = layers.matmul(h, p["wkv_a"], dt)
    ckv = layers.rmsnorm_gain(kv[..., :cfg.kv_lora_rank], p["kv_a_norm"], eps)
    kvb = layers.matmul(ckv, p["wkv_b"], dt).reshape(b, s, nh, nope + hv)
    k_rot = layers.rope_interleaved(kv[..., cfg.kv_lora_rank:], cfg.rope_theta)
    k = jnp.concatenate([kvb[..., :nope].astype(dt), jnp.broadcast_to(
        k_rot[:, :, None].astype(dt), (b, s, nh, rot))], -1)
    heads = lambda t: t.transpose(0, 2, 1, 3)
    o = causal.causal_flash_attention(
        heads(q.astype(dt)), heads(k), heads(kvb[..., nope:].astype(dt)),
        min(cfg.attn_block, s), True)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, nh * hv)
    return x + layers.matmul(o, p["wo"], dt)


ROPE_CASES = [
    # the elementwise pass given the partner, at ISSUE 41's two shapes
    ("pass", (2, 256, 4, 192), 128, 1), ("pass", (2, 256, 64), 0, -2),
    # the projection with RoPE on: q's shape, the shared rotary key's
    # behind its latent, and both in the matmuls' other dtype
    ("project", (4, 192), 128, "float32"), ("project", (1, 64), 0, "float32"),
    ("project", (1, 80), 16, "float32"), ("project", (4, 192), 128,
                                          "bfloat16"),
    # the sublayer whole, at this file's small widths
    ("sublayer", None, None, "float32"), ("sublayer", None, None, "bfloat16")]


@pytest.mark.parametrize("what,shape,first,arg", ROPE_CASES, ids=[
    "-".join(str(x) for x in c if x is not None).replace(" ", "")
    for c in ROPE_CASES])
def test_rope_without_the_rolled_copies_is_rope_interleaved(
        what, shape, first, arg, params):
    """``layers.project_rope`` / ``rope_partnered`` (the partner a product
    of its own; no ``jnp.roll``) against the twin ``rope_interleaved``:
    float32 values before any cast and the gradient with respect to the
    input within one float32 ulp of the terms summed; the projection's
    gradients with respect to its input and weights (other sums: the
    partner's part goes through its own matmul) within a few ulps of the
    largest entry; and ``mla_attention``'s output and parameter
    gradients against the sublayer as it was."""
    rng = np.random.default_rng(41)
    normal = lambda *s: jnp.asarray(rng.normal(0, 1, s), jnp.float32)
    theta = 32e6
    if what == "pass":
        x, g = normal(*shape), normal(*shape)
        new = lambda x: layers.rope_partnered(x, _swapped(x, first), theta,
                                             seq_axis=arg)
        old = lambda x: layers.rope_interleaved(x, theta, first, arg)
        # the two terms of an entry's sum: itself and its partner
        terms = lambda t: jnp.abs(t) + jnp.abs(jnp.pad(
            _swapped(t, first), ((0, 0),) * (t.ndim - 1) + ((first, 0),)))
        assert _ulps(new(x), old(x), terms(x)) <= 1
        dnew, dold = (jax.jit(jax.grad(lambda x: jnp.sum(f(x) * g)))(x)
                      for f in (new, old))
        assert _ulps(dnew, dold, terms(g)) <= 1
        return
    if what == "project":
        heads, width = shape
        a, w = normal(2, 256, 32), normal(32, heads * width)
        g = normal(2, 256, heads, width)
        new = lambda a, w: layers.project_rope(a, w, heads, first, theta, arg)
        old = lambda a, w: layers.rope_interleaved(
            layers.matmul(a, w, arg).reshape(2, 256, heads, width), theta,
            first, 1)
        assert new(a, w).dtype == jnp.float32
        # the same dot products of the same inputs (on the CPU a product
        # of another width may sum them in another order)
        assert _ulps(new(a, w), old(a, w), jnp.max(jnp.abs(old(a, w)))) <= 4
        for got, want in zip(*(jax.jit(jax.grad(
                lambda a, w: jnp.sum(f(a, w) * g), argnums=(0, 1)))(a, w)
                for f in (new, old))):
            assert got.dtype == want.dtype == jnp.float32
            loose = 16 if arg == "float32" else 2 ** 17  # bfloat16 operands
            assert _ulps(got, want, jnp.max(jnp.abs(want))) <= loose
        return
    cfg = config.ModelConfig(compute_dtype=arg, num_experts=16, **PUBLISHED,
                            **SHARE, **TRAIN)
    leaves = {k: params["mtp"][k] for k in attention.MLA.shapes(cfg)}
    x, g = normal(2, 32, cfg.hidden_size), normal(2, 32, cfg.hidden_size)
    new = lambda p, x: x + attention.mla_attention(
        p, x, cfg, interpret=True)[0]
    old = lambda p, x: _mla_attention_rolled(p, x, cfg)
    tol = CLOSE if arg == "float32" else dict(rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(new(leaves, x), old(leaves, x), **tol)
    (pn, xn), (po, xo) = (jax.jit(jax.grad(lambda p, x: jnp.sum(f(p, x) * g),
                                   argnums=(0, 1)))(leaves, x)
                          for f in (new, old))
    np.testing.assert_allclose(xn, xo, **tol)
    for k in leaves:
        assert pn[k].dtype == po[k].dtype == jnp.float32
        np.testing.assert_allclose(pn[k], po[k], **tol, err_msg=k)


def test_the_configuration_file_gives_the_published_widths():
    cfg = train.load_model_config(os.path.join(
        BENCH, "configs", "joyai-flash-train-1chip.json"))
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.intermediate_size, cfg.expert_width,
            cfg.num_experts, cfg.num_experts_per_tok, cfg.n_shared_experts,
            cfg.routed_scaling_factor, cfg.scoring_func, cfg.vocab_size,
            cfg.num_nextn_predict_layers) == (
        2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 256, 8, 1, 2.5,
        "sigmoid", 129280, 1)
    assert (cfg.n_dense_here, cfg.n_sparse_here, cfg.n_experts_here,
            cfg.first_expert_here, cfg.vocab_rows, cfg.seq_len,
            cfg.micro_batch) == (1, 4, 16, 0, 16160, 8192, 1)
    shapes = train.model_param_shapes(cfg)
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 680_439_808             # 10.9 GB at 16 bytes each


def test_a_model_the_path_cannot_run_is_refused(tmp_path):
    with open(os.path.join(BENCH, "configs",
                           "joyai-flash-train-1chip.json")) as f:
        body = json.load(f)
    for key, value in (("n_group", 8), ("rope_interleave", False),
                       ("rope_scaling", {"type": "yarn"})):
        path = tmp_path / f"{key}.json"
        path.write_text(json.dumps({**body, key: value}))
        with pytest.raises(NotImplementedError):
            train.load_model_config(str(path))
    with pytest.raises(NotImplementedError, match="router"):
        dataclasses.replace(F32, topk_method="greedy")


# OLMoE's losses (total, cross-entropy, load balancing, z) of three steps
# from seed 3's parameters on the batches of seeds 0, 1, 2, as float32 bit
# patterns, read at the parent of the PR that generalised the walk (PR 35:
# commit 9997a7b) with one layer, as the benchmark's OLMoE configuration
# has; float32 and bfloat16 compute
OLMOE_AT_PARENT = {
    "float32": [[1085419428, 1085367925, 1017469360, 999248892],
                [1085407509, 1085356210, 1017444550, 999139182],
                [1085478217, 1085425777, 1017826014, 998781521]],
    "bfloat16": [[1085419398, 1085367894, 1017469584, 999249311],
                 [1085407115, 1085355858, 1017436727, 999127645],
                 [1085474075, 1085421858, 1017763143, 998805607]]}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_olmoes_losses_are_bit_for_bit_the_parents(dtype):
    """OLMoE runs through the same walk as before it was generalised:
    with its one layer nothing is rematerialised and nothing scanned, so
    the program is the parent's (with two layers the layers are scanned
    and recomputed, and one loss of twelve differs in its last bit)."""
    cfg = config.ModelConfig(
        hidden_size=64, intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=4, num_experts=8, num_experts_per_tok=2,
        vocab_size=256, layers_here=1, seq_len=32, micro_batch=2,
        attn_block=16, loss_block_rows=16, lr=1e-2, compute_dtype=dtype)
    params = train.init_model_params(cfg, seed=3)
    mesh, spec = make_mesh(jax.devices()[:1], MeshSpec(dp=1))
    step, place = train.build_train_step(mesh, spec, model=cfg)
    state, got = None, []
    for seed in (0, 1, 2):
        ids = np.random.default_rng(seed).integers(0, 256, (2, 33)).astype(
            np.int32)
        batch = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])
        if state is None:
            state, *batch = place(params, *batch)
        state, aux = step(state, *batch)
        got.append(np.asarray(aux["losses"], np.float32).view(
            np.uint32).tolist())
    assert state[4] == {}                   # no bias where none is chosen under
    assert got == OLMOE_AT_PARENT[dtype]


# -- the benchmark's own copy of the reference ------------------------------
@pytest.fixture(scope="module")
def kit():
    sys.path.insert(0, BENCH)
    try:
        from harness import joyaikit
        yield joyaikit
    finally:
        sys.path.remove(BENCH)


KIT_CFG = {**PUBLISHED, **SHARE, **TRAIN, "n_routed_experts": 16,
           "adam_b1": 0.9, "adam_b2": 0.95, "adam_eps": 1e-8,
           "weight_decay": 0.1, "compute_dtype": "float32"}


def kit_step(kit, params, wrong=None, routed=None):
    return kit.reference_step(params, *batch_of(0), KIT_CFG, some_bias(),
                              tuple(LEAVES), wrong, routed)


def test_the_kits_leaves_are_the_programs(kit, params):
    assert list(kit.LEAVES) == LEAVES
    sizes = kit.leaf_sizes(KIT_CFG)
    for name, path in train.leaf_names(F32):
        assert kit.leaf_of(params, name) is train._leaf(params, path)
        assert sizes[name] == train._leaf(params, path).size
    rebuilt = kit.tree_of({n: kit.leaf_of(params, n) for n in kit.LEAVES})
    assert jax.tree.structure(rebuilt) == jax.tree.structure(params)


def test_the_benchmarks_kit_is_the_repos_reference(kit, params, reference):
    out = kit_step(kit, params)
    np.testing.assert_allclose(out["losses"], reference["parts"], **CLOSE)
    np.testing.assert_array_equal(out["loads"], reference["loads"])
    want_bias = ref.bias_step(some_bias(), reference["loads"], F32)
    np.testing.assert_allclose(
        out["bias"], np.concatenate([want_bias["layers"], want_bias["mtp"]]),
        rtol=1e-6)
    for name, path in train.leaf_names(F32):
        want = train._leaf(reference["grads"], path)
        np.testing.assert_allclose(
            out["grads"][name], want, rtol=1e-5,
            atol=2e-5 * float(jnp.abs(want).max()), err_msg=name)


@pytest.mark.parametrize("dp", [1, 2])
def test_a_step_reports_what_the_kit_computes(dp, kit, params):
    """The quantities the benchmark's check compares, from the step's raw
    statistics and the biases its state holds (on two shards the same as
    on one), under the step's own routing, and the updated parameters."""
    state, (aux,) = run_steps(F32, params, (0,), dp=dp)
    aux, after = jax.device_get((aux, state[4]))
    zero = ref.zero_bias(F32)
    out = kit.reference_step(params, *batch_of(0), KIT_CFG, zero,
                             tuple(LEAVES), routed=aux["experts"])
    got = kit.compared(kit.step_stats(aux, after), KIT_CFG, tuple(LEAVES))
    want = kit.compared(out, KIT_CFG, tuple(LEAVES))
    assert set(got) == {"losses", "load_share", "local_share", "row_means",
                        "route_regret", "bias", "grad_log_rms", "grad_probe"}
    assert np.abs(got["bias"]).max() == 1   # one step of gamma
    for key in got:
        np.testing.assert_allclose(got[key], want[key], rtol=1e-4,
                                   atol=1e-4, err_msg=key)
    for i, name in enumerate(LEAVES):
        p = kit.leaf_of(params, name)
        new = kit.adamw_leaf(name, p, out["grads"][name], KIT_CFG)
        pos = kit.probe_positions(name, p.size)
        np.testing.assert_allclose(
            aux["param_probe"][i], new.reshape(-1)[pos], rtol=1e-5,
            atol=0.01 * F32.lr, err_msg=name)


@pytest.mark.parametrize("variant", ["bf16", "bias_in_weights", "softmax"])
def test_a_float32_part_is_told_from_a_wrong_one(variant, kit, params):
    """The routers' logits, sigmoid scores and chosen weights and both
    heads' rows, recomputed from the step's own inputs to each part: the
    step's are within a twentieth of the benchmark's tolerance; a
    bfloat16 router, weights that include the bias and a softmax in the
    sigmoid's place each lie outside it."""
    mesh, spec = make_mesh(jax.devices()[:1], MeshSpec(dp=1))
    step, place = train.build_train_step(mesh, spec, model=F32)
    state, tokens, labels = place(jax.tree.map(jnp.copy, params),
                                  *batch_of(0))
    bias = some_bias(scale=0.05)
    before = np.concatenate([bias["layers"], bias["mtp"]])
    state = state[:4] + (bias,)             # donated into the step
    _, aux = step(state, tokens, labels)
    aux = jax.device_get(aux)
    routers = np.concatenate([params["layers"]["router"],
                              params["mtp"]["router"][None]])
    args = (aux, routers, before, params["head"], batch_of(0)[1], KIT_CFG)
    got, want = kit.precision_got(aux, KIT_CFG), kit.precision_want(*args)
    wrong = kit.precision_want(*args, variant=variant)
    outside = []
    for key in got:
        tol = 0.005 + 0.000375 * np.abs(want[key])
        assert got[key].shape == want[key].shape
        assert (np.abs(got[key] - want[key]) < 0.05 * tol).all(), key
        outside.append((np.abs(wrong[key] - want[key]) > tol).any())
    assert any(outside)


# a wrong variant and a leaf whose gradient it moves
WITNESS = {"softmax": "down", "bias_in_weights": "down",
           "rope_on_nope": "wq_b", "unnormalised": "down",
           "mtp_fed_t_i": "mtp.proj"}


@pytest.mark.parametrize("wrong", sorted(WITNESS))
def test_a_wrong_variant_fails_the_comparison(wrong, kit, params, system):
    """Softmax scores, the bias in the weights, RoPE on the part without
    position, weights not normalised, the module fed the token itself:
    under the step's own routing the gradient of a leaf behind the
    variant lies a hundred times farther from the program's than the
    right model's does (1e-5), and the losses differ too."""
    assert set(WITNESS) == set(kit.WRONG)
    routed = None if wrong == "softmax" else jnp.asarray(
        system["aux"]["experts"])
    out = kit_step(kit, params, wrong, routed)
    got = np.asarray(system["aux"]["losses"])[[0, 1, 4]]
    assert (got != np.asarray(out["losses"])).any()
    leaf = WITNESS[wrong]
    mine = train._leaf(system["grads"], dict(train.leaf_names(F32))[leaf])
    off = float(jnp.linalg.norm(mine - out["grads"][leaf])
                / jnp.linalg.norm(mine))
    assert off > 1e-3, (wrong, off)


def test_the_kit_counts_the_steps_flop(kit):
    cfg = kit.load_config(os.path.join(BENCH, "configs",
                                       "joyai-flash-train-1chip.json"))
    flops = kit.step_flops(cfg)
    assert sum(kit.leaf_sizes(cfg).values()) == 680_439_808
    assert round(flops["step"] / 1e12, 1) == 27.8
    latent = (flops["attention"] + flops["latent_proj"]) / flops["step"]
    assert 0.71 < latent < 0.73             # latent attention is 72%
    assert flops["flash_forward"] * 3 == flops["attention"]
    assert round(flops["experts"] / flops["step"], 2) == 0.02
