"""Qwen3-Next-80B-A3B's training step on the normal path
(``parallel/train.py``'s model path under ``layer_types``: Gated DeltaNet
operators in chunks, output-gated grouped-query attention with a per-head
QK-norm and RoPE on part of the head, softmax-routed experts under no bias
beside a sigmoid-gated shared one) against the plain reference
(``parallel/qwen3next_reference.py``: the delta rule one position at a
time) at small widths on seeded random weights: hidden 64; 2 key and 4
value heads of 16, 4 taps, chunks of 8; 4 query heads of 32 on 1
key-value head, 8 entries turned; 16 experts of width 24, top 4, a shared
expert of 24; held here: one period (three DeltaNet layers, one attention
layer), 4 experts (share 1 of 4), 64 of 256 ids.  Float32 compute meets
the reference at rtol 1e-5."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import (attention, causal, config, experts, gdn,
                               layers, train)
from ompi_tpu.parallel import qwen3next_reference

import built

ref = built.programs(qwen3next_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = os.path.join(BENCH, "configs", "qwen3-next-80b-a3b-train-1chip.json")
TYPES = ("linear_attention",) * 3 + ("full_attention",)
PUBLISHED = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=1, head_dim=32, partial_rotary_factor=0.25,
    num_experts=16, num_experts_per_tok=4, vocab_size=256,
    layer_types=TYPES * 2, moe_intermediate_size=24, n_shared_experts=1,
    moe_shared_expert_intermediate_size=24, conv_kernel=4,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16, norm_topk_prob=True,
    rms_norm_eps=1e-6, rope_theta=1e7, attn_output_gate=True,
    shared_expert_gate=True)
SHARE = dict(layers_here=4, first_layer_here=0, experts_here=4,
             expert_share=1, vocab_here=64, mtp_here=0)
TRAIN = dict(seq_len=40, micro_batch=2, attn_block=8, loss_block_rows=8,
             chunk_size=8, lr=1e-2, aux_loss_coef=0.001, z_loss_coef=0.0)
F32 = config.ModelConfig(compute_dtype="float32", **PUBLISHED, **SHARE,
                        **TRAIN)
NAMES = train.leaf_names(F32)
CLOSE = dict(rtol=1e-5, atol=1e-6)
# leaves whose gradient reaches them through the decay or beta alone: a
# difference of sums some orders under the sums' own size, whose small
# entries float32 gives to two digits, and Adam divides an entry by its
# own size: their updates are compared by the largest deviation only
THROUGH_THE_GATES = ("ba_proj", "A_log", "dt_bias")


def batch_of(seed, vocab=64):
    """(inputs (2, 40), labels (2, 41)) from 42 ids a sequence: the
    batch's form for every share cell, of which this model reads the
    first 40 labels."""
    ids = np.random.default_rng(seed).integers(0, vocab, (2, 42)).astype(
        np.int32)
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def layer_of(cfg, kind, seed=5):
    """One layer's leaves of ``kind`` (``gdn_moe`` or ``attn_moe``) drawn
    as ``init_model_params`` would, the matrices wide enough (0.3) that
    every part matters."""
    first = {"gdn_moe": 0, "attn_moe": 3}[kind]
    one = dataclasses.replace(cfg, init_std=0.3, layers_here=1,
                              first_layer_here=first)
    (group,) = train.init_model_params(one, seed)["layers"].values()
    assert {k: v.shape[1:] for k, v in group[kind].items()} \
        == train.pattern_layer_shapes(cfg)[kind]
    return jax.tree.map(lambda a: a[0], group[kind])


def close(got, want, **kw):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **{**CLOSE, **kw})


def near(got, want, rel=2e-5, err_msg=""):
    """Within ``rel`` of the largest entry: a gradient's small entries
    are sums of large terms, so float32's last bits are of that size."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rel * 10, err_msg=err_msg,
                               atol=rel * max(1e-30, np.abs(want).max()))


# -- the delta rule ----------------------------------------------------------------
def rule_inputs(seed, s, bt=2, hk=2, hv=4, dk=16, dv=16):
    """q, k as the rule reads them, v, g <= 0 and beta in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = layers.l2norm(jax.random.normal(ks[0], (bt, s, hk, dk))) * dk ** -0.5
    k = layers.l2norm(jax.random.normal(ks[1], (bt, s, hk, dk)))
    v = jax.random.normal(ks[2], (bt, s, hv, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (bt, s, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (bt, s, hv)))
    return q, k, v, g, beta


def by_positions(q, k, v, g, beta):
    """The reference's recurrence, a key head read by its value heads."""
    per_value = lambda t: jnp.repeat(t, v.shape[2] // t.shape[2], axis=2)
    return ref.delta_rule(per_value(q), per_value(k), v, g, beta)


@pytest.mark.parametrize("length", [27, 40, 5, 1],
                         ids=["no-multiple", "whole-chunks",
                              "under-a-chunk", "one-position"])
def test_the_chunked_rule_is_the_recurrence_over_positions(length):
    """Forward and gradient, in chunks of 8: at a length that is no
    multiple of the chunk, at whole chunks, and at one shorter than a
    chunk."""
    args = rule_inputs(length, length)
    with jax.default_matmul_precision("highest"):
        want = by_positions(*args)
        got = built.program(gdn.gated_delta_chunked)(*args, 8)
        close(got, want, rtol=1e-4, atol=1e-6)
        weight = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        loss = lambda fn: lambda *a: jnp.sum(fn(*a) * weight)
        g_want = jax.jit(jax.grad(loss(by_positions), range(5)))(*args)
        g_got = jax.jit(jax.grad(loss(
            lambda *a: gdn.gated_delta_chunked(*a, 8)), range(5)))(*args)
    for name, a, b in zip("qkvgb", g_got, g_want):
        near(a, b, rel=1e-4, err_msg=name)


def test_without_beta_nothing_is_written_and_the_state_only_decays():
    """beta = 0 writes nothing: from the zero state every output is zero,
    whatever g is; and a chunk's result does not depend on the chunk."""
    q, k, v, g, beta = rule_inputs(3, 24)
    chunked = built.program(gdn.gated_delta_chunked)
    got = chunked(q, k, v, g, beta * 0, 8)
    np.testing.assert_array_equal(np.asarray(got), 0.0)
    with jax.default_matmul_precision("highest"):
        close(chunked(q, k, v, g, beta, 8), chunked(q, k, v, g, beta, 4),
              rtol=1e-4, atol=1e-6)


def test_without_decay_and_with_beta_one_it_is_the_plain_delta_rule():
    """g = 0 and beta = 1: ``S <- S + k (v - S^T k)^T``, in numpy
    float64."""
    q, k, v, g, beta = rule_inputs(4, 19, bt=1, hk=1, hv=1)
    got = built.program(gdn.gated_delta_chunked)(
        q, k, v, g * 0, beta * 0 + 1, 8)
    qn, kn, vn = (np.asarray(t, np.float64)[0, :, 0] for t in (q, k, v))
    state, want = np.zeros((16, 16)), []
    for t in range(19):
        state = state + np.outer(kn[t], vn[t] - state.T @ kn[t])
        want.append(state.T @ qn[t])
    close(got[0, :, 0], np.stack(want), rtol=1e-4, atol=1e-6)


def test_the_unit_lower_inverse_and_its_gradient():
    low = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 8, 8)), -1)
    want = jnp.linalg.inv(jnp.eye(8) + low)
    close(gdn.unit_lower_inverse(low), want, rtol=1e-4, atol=1e-5)
    weight = jax.random.normal(jax.random.PRNGKey(1), low.shape)
    g_want = jax.jit(jax.grad(lambda a: jnp.sum(
        jnp.linalg.inv(jnp.eye(8) + jnp.tril(a, -1)) * weight)))(low)
    g_got = jax.jit(jax.grad(lambda a: jnp.sum(
        gdn.unit_lower_inverse(jnp.tril(a, -1)) * weight)))(low)
    near(g_got, g_want, rel=1e-4)


# -- the sublayers ---------------------------------------------------------------
def test_the_delta_net_operator_is_the_references_and_reports_its_rule():
    p = layer_of(F32, "gdn_moe")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    got, _, seen = gdn.gated_delta_net(p, x, F32)
    with jax.default_matmul_precision("highest"):
        close(got, ref.gdn(p, x, F32), rtol=1e-4, atol=5e-5)
    assert {k: v.shape for k, v in seen.items()} == {
        "gdn_q_seq": (80, 16), "gdn_k_seq": (80, 16), "gdn_v_seq": (80, 16),
        "gdn_g_seq": (80,), "gdn_beta_seq": (80,), "gdn_o": (80, 16)}
    # what it reports is what the rule read and made of the first value
    # head: the recurrence over those alone gives the same rows
    one = lambda t: jnp.asarray(t).reshape((2, 40, 1) + t.shape[1:])
    with jax.default_matmul_precision("highest"):
        again = ref.delta_rule(*(one(seen["gdn_" + k + "_seq"])
                                 for k in ("q", "k", "v", "g", "beta")))
    close(again.reshape(80, 16), seen["gdn_o"], rtol=1e-4, atol=1e-6)
    assert float(jnp.max(seen["gdn_g_seq"])) < 0 \
        and 0 < float(jnp.min(seen["gdn_beta_seq"]))


@pytest.mark.parametrize("heads,kv", [(4, 1), (8, 2), (4, 4)],
                         ids=["4-on-1", "8-on-2", "4-on-4"])
def test_gated_attention_with_partial_rope_is_the_references(heads, kv):
    cfg = dataclasses.replace(F32, num_attention_heads=heads,
                              num_key_value_heads=kv)
    p = layer_of(cfg, "attn_moe")
    assert p["wq"].shape == (64, 2 * heads * 32) \
        and p["wo"].shape == (heads * 32, 64) and p["wk"].shape \
        == (64, kv * 32) and cfg.rotary_width == 8
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    got, _, seen = attention.FULL.run(p, x, cfg, interpret=True)
    with jax.default_matmul_precision("highest"):
        close(got, ref.attention(p, x, cfg), rtol=1e-4, atol=1e-5)
    assert seen["attn_qk"].shape == seen["attn_qk_in"].shape == (80, 64) \
        and seen["attn_og_in"].shape == (80, 64) \
        and seen["attn_og"].shape == (80, 32)
    # RoPE turned the first 8 of the 32 and passed the rest: behind the
    # norm alone (gain 1) the other 24 are the norm's
    q_in = np.asarray(seen["attn_qk_in"])[:, :32]
    normed = q_in / np.sqrt((q_in * q_in).mean(-1, keepdims=True) + 1e-6)
    close(np.asarray(seen["attn_qk"])[:, 8:32], normed[:, 8:], rtol=1e-5)
    assert np.abs(np.asarray(seen["attn_qk"])[1:, :8]
                  - normed[1:, :8]).max() > 1e-3
    o, gate = np.split(np.asarray(seen["attn_og_in"], np.float64), 2, -1)
    close(seen["attn_og"], o / (1 + np.exp(-gate)), rtol=1e-5)


def test_rope_on_the_whole_head_is_the_code_it_was():
    """``rotary`` None and the head's own width take the path every
    other model takes, bit for bit; a part of the head leaves the rest
    untouched."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 12, 16))
    whole = layers.rope(x, 1e4)
    np.testing.assert_array_equal(np.asarray(layers.rope(x, 1e4, 16)),
                                  np.asarray(whole))
    part = layers.rope(x, 1e4, 4)
    np.testing.assert_array_equal(np.asarray(part[..., 4:]),
                                  np.asarray(x[..., 4:]))
    np.testing.assert_array_equal(
        np.asarray(part[..., :4]), np.asarray(layers.rope(x[..., :4], 1e4)))


def test_without_a_gate_the_sublayer_is_lfm2s_bit_for_bit():
    """A layer whose ``wq`` is as wide as ``wo`` is long takes the path
    it took before the gate was there: lfm2's attention, RoPE on the
    whole head, the same numbers as a composition of the parts and no
    gate reported."""
    from test_lfm2_train import F32 as LFM2, layer_of as their_layer

    p = their_layer(LFM2, "attn_moe")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    got, _, seen = attention.FULL.run(p, x, LFM2, interpret=True)
    assert set(seen) == {"attn_qk_in", "attn_qk"} \
        and LFM2.rotary_width is None and LFM2.head_width == 16
    b, s, dt = 2, 32, LFM2.compute_dtype
    h = layers.rmsnorm_gain(x, p["ln1"], LFM2.rms_norm_eps)
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q, k = (layers.rope(layers.rmsnorm_gain(
        heads(layers.matmul(h, p[w], dt), n), p[g], LFM2.rms_norm_eps),
        LFM2.rope_theta) for w, n, g in (("wq", 4, "q_norm"),
                                         ("wk", 2, "k_norm")))
    o = causal.causal_flash_attention(
        q, k, heads(layers.matmul(h, p["wv"], dt), 2), 16, True)
    want = layers.matmul(o.transpose(0, 2, 1, 3).reshape(b, s, -1), p["wo"],
                        dt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_expert_block_with_a_gated_shared_expert_is_the_references():
    p = layer_of(F32, "attn_moe")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 64))
    got, stats, seen = experts.moe_shared_local_block(p, x, F32, None)
    with jax.default_matmul_precision("highest"):
        want, loads, prob_sum = ref.experts(p, x, F32)
    close(got, want, rtol=1e-4, atol=1e-5)
    close(stats["slots"], loads)
    close(stats["prob_sum"], prob_sum, rtol=1e-5)
    assert set(stats) == {"slots", "prob_sum"}
    assert seen["shared_gate"].shape == (80, 1) \
        and seen["scores"].shape == (80, 16)
    close(jnp.sum(seen["scores"], -1), np.ones(80), rtol=1e-5)
    close(jnp.sum(seen["weights"], -1), np.ones(80), rtol=1e-5)


def test_the_four_shares_expert_parts_add_up_to_the_uncut_layer():
    """The 4 expert shares of a layer (4 of 16 each), **the shared expert
    counted once**, add up to the uncut reference's layer: what the
    expert-parallel group's exchange would make of them."""
    whole = dataclasses.replace(F32, experts_here=0, expert_share=0)
    p = layer_of(whole, "gdn_moe")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.experts(p, x, whole)
        routed_only, _, _ = ref.experts(p, x, whole, shared=False)
    shared = want - routed_only         # what every chip computes alike
    total = 0.0
    for j in range(4):
        part = dataclasses.replace(F32, experts_here=4, expert_share=j)
        mine = {**p, **{k: p[k][4 * j:4 * j + 4]
                        for k in ("gate", "up", "down")}}
        out = experts.moe_shared_local_block(mine, x, part, None)[0]
        total = total + (out - shared)  # a share's routed part
    close(total + shared, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(shared).max()) > 1e-3


def test_softmax_routing_to_every_expert_is_the_sorted_blocks():
    """``_route_to_held`` by softmax scores with every expert held routes
    as OLMoE's ``moe_sorted_block`` does: the same experts, weights,
    loads and probabilities' sums."""
    whole = dataclasses.replace(F32, experts_here=0, expert_share=0,
                                n_shared_experts=0,
                                shared_expert_gate=False)
    p = layer_of(dataclasses.replace(F32, experts_here=0, expert_share=0),
                 "attn_moe")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 64))
    _, order, sizes, stats, seen = experts._route_to_held(p, x, whole, None)
    out, theirs, routed = experts.moe_sorted_block(p, x, whole)
    np.testing.assert_array_equal(np.asarray(seen["experts"]),
                                  np.asarray(routed["experts"]))
    close(seen["weights"], routed["weights"], rtol=1e-6)
    close(seen["logits"], routed["logits"], rtol=1e-6)
    close(stats["slots"], theirs["slots"])
    close(stats["prob_sum"], theirs["prob_sum"], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(sizes),
                                  np.asarray(theirs["slots"], np.int32))
    mine = experts.moe_shared_local_block(p, x, whole, None)[0]
    close(mine, out, rtol=1e-4, atol=1e-5)


def test_the_layers_are_walked_by_their_types():
    assert F32.pattern_here == "LLLA"
    assert F32.segments == (("L", 3, 0), ("A", 1, 3))
    assert (F32.n_sparse_here, F32.n_routers, F32.conv_kernel,
            F32.head_width, F32.rotary_width, F32.shared_width) \
        == (4, 4, 4, 32, 8, 24)
    assert F32.routes_to_held
    shapes = train.model_param_shapes(F32)
    assert shapes["layers"]["l0"]["gdn_moe"]["in_proj"] == (3, 64, 192)
    assert shapes["layers"]["l0"]["gdn_moe"]["ba_proj"] == (3, 64, 8)
    assert shapes["layers"]["l0"]["gdn_moe"]["conv_w"] == (3, 4, 128)
    assert shapes["layers"]["l0"]["gdn_moe"]["shared_w_g"] == (3, 64, 1)
    assert shapes["layers"]["l3"]["attn_moe"]["wq"] == (1, 64, 256)
    assert shapes["layers"]["l3"]["attn_moe"]["wo"] == (1, 128, 64)
    assert set(train.pattern_layer_shapes(F32)) == {
        "gdn_dense", "gdn_moe", "attn_dense", "attn_moe"}
    later = dataclasses.replace(F32, first_layer_here=2, layers_here=6)
    assert later.pattern_here == "LALLLA"
    params = built.params(F32, 0)
    gdn = params["layers"]["l0"]["gdn_moe"]
    np.testing.assert_array_equal(np.asarray(gdn["dt_bias"]), 1.0)
    assert 0 <= float(gdn["A_log"].min()) \
        and float(gdn["A_log"].max()) <= np.log(16.0)
    assert float(jnp.abs(gdn["conv_w"]).max()) <= 0.5
    assert not train.is_decayed("l0.gdn_moe.A_log") \
        and not train.is_decayed("l0.gdn_moe.dt_bias") \
        and not train.is_decayed("l0.gdn_moe.gate_norm") \
        and train.is_decayed("l0.gdn_moe.conv_w") \
        and train.is_decayed("l0.gdn_moe.shared_w_g")


# -- the whole step --------------------------------------------------------------------
@pytest.fixture(scope="module")
def stepped():
    """Three steps of the program from seed 3, and the reference's."""
    step, place = built.step(F32)
    params = built.params(F32, 3)
    batches = [batch_of(s) for s in range(3)]
    state, _, _ = place(jax.tree.map(jnp.copy, params), *batches[0])
    assert state[4]["layers"].shape == (4, 0)       # rows of no entries
    auxes = []
    for tokens, labels in batches:
        state, aux = step(state, tokens, labels)
        auxes.append(jax.device_get(aux))
    with jax.default_matmul_precision("highest"):
        want = ref.train_steps(params, batches, F32)
    return dict(params=params, batches=batches, state=state, auxes=auxes,
                want=want, step=step)


def test_three_steps_are_the_references(stepped):
    params, losses = stepped["want"]
    close([a["losses"][:3] for a in stepped["auxes"]],
          [[float(x) for x in row] for row in losses])
    assert stepped["state"][4]["layers"].shape == (4, 0)
    for name, path in NAMES:
        # Adam's first steps move an entry by the learning rate times
        # its gradient's sign, so one whose gradient is next to nothing
        # goes either way: a thousandth of a leaf may lie outside a
        # hundredth of the three steps, none outside the three steps
        off = np.abs(np.asarray(train._leaf(stepped["state"][0], path))
                     - np.asarray(train._leaf(params, path)))
        assert off.max() <= 3 * F32.lr, name
        if not name.endswith(THROUGH_THE_GATES):
            assert np.mean(off > 0.01 * 3 * F32.lr) <= 2e-3, name


def test_one_step_reports_the_references_loads_and_gradients(stepped):
    tokens, labels = stepped["batches"][0]
    aux = stepped["auxes"][0]
    (total, (ce, lb, loads)), g = ref.grads(stepped["params"], tokens,
                                            labels, F32)
    close(aux["losses"], [total, ce, lb, 0.0])
    assert float(lb) > 0
    close(aux["loads"], loads)
    assert aux["loads"].shape == (4, 16) and aux["experts"].shape \
        == (4, 80, 4)
    first = F32.first_expert_here
    assert aux["local_slots"] == loads[:, first:first + 4].sum()
    sample = aux["sample"]
    assert sample["gdn_q_seq"].shape == (3, 80, 16) \
        and sample["gdn_g_seq"].shape == (3, 80) \
        and sample["gdn_o"].shape == (3, 16, 16) \
        and sample["attn_qk"].shape == (1, 16, 64) \
        and sample["attn_og"].shape == (1, 16, 32) \
        and sample["router_scores"].shape == (4, 16, 16) \
        and sample["router_shared_gate"].shape == (4, 16, 1)
    for (name, path), sq, probe in zip(NAMES, aux["grad_sq"],
                                       aux["grad_probe"]):
        leaf = np.asarray(train._leaf(g, path))
        close(sq, np.sum(leaf * leaf), rtol=1e-4, err_msg=name)
        near(probe, leaf.reshape(-1)[train.probe_positions(
            name, leaf.size)], err_msg=name)


def test_the_parameters_after_one_update_are_the_references(stepped):
    tokens, labels = stepped["batches"][0]
    step, place = built.step(F32)
    state, t, l = place(jax.tree.map(jnp.copy, stepped["params"]), tokens,
                        labels)
    state, _ = step(state, t, l)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.train_steps(stepped["params"], [(tokens, labels)], F32)
    for name, path in NAMES:
        got, ours = (np.asarray(train._leaf(tree, path))
                     for tree in (state[0], want))
        # the first update is lr x sign(g) (x (1 - decay)): an entry whose
        # gradient is next to nothing may turn either way
        assert np.abs(got - ours).max() <= 2 * F32.lr, name
        if not name.endswith(THROUGH_THE_GATES):
            assert np.mean(np.abs(got - ours) > 1e-3 * F32.lr) <= 2e-3, name


def test_the_losses_repeat_bit_for_bit_from_one_seed(stepped):
    # a second build and a second draw, not the process's kept ones:
    # whether they give the first's numbers is what is asked
    step, place = built.fresh_step(F32)
    state, _, _ = place(train.init_model_params(F32, 3),
                        *stepped["batches"][0])
    for (tokens, labels), before in zip(stepped["batches"],
                                        stepped["auxes"]):
        state, aux = step(state, tokens, labels)
        np.testing.assert_array_equal(np.asarray(aux["losses"]),
                                      before["losses"])


def test_bfloat16_compute_stays_near_float32(stepped):
    cfg = dataclasses.replace(F32, compute_dtype="bfloat16")
    step, place = built.step(cfg)
    state, t, l = place(built.params(cfg, 3),
                        *stepped["batches"][0])
    _, aux = step(state, t, l)
    close(aux["losses"][1], stepped["auxes"][0]["losses"][1], rtol=5e-3)


def test_two_data_parallel_ranks_are_one_model(stepped):
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    step, place = built.step(F32, 2)
    state, t, l = place(built.params(F32, 3),
                        *stepped["batches"][0])
    _, aux = step(state, t, l)
    want = stepped["auxes"][0]
    close(aux["losses"], want["losses"], rtol=1e-5)
    close(aux["loads"], want["loads"])
    close(aux["grad_sq"], want["grad_sq"], rtol=1e-4)


# -- the configuration -------------------------------------------------------------------
def test_the_benchmarks_configuration_loads_at_its_published_widths():
    cfg = train.load_model_config(CONFIG)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_width, cfg.rotary_width) \
        == (2048, 16, 2, 256, 64)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.conv_kernel, cfg.chunk_size) == (16, 32, 128, 128, 4, 64)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_width,
            cfg.shared_width, cfg.n_experts_here, cfg.first_expert_here) \
        == (512, 10, 512, 512, 32, 0)
    assert (cfg.scoring_func, cfg.topk_method, cfg.norm_topk_prob,
            cfg.attn_output_gate, cfg.shared_expert_gate) \
        == ("softmax", "greedy", True, True, True)
    assert cfg.layer_types == TYPES * 12 and cfg.pattern_here == "LLLA"
    assert (cfg.vocab_size, cfg.vocab_rows, cfg.seq_len, cfg.micro_batch,
            cfg.n_mtp_here, cfg.rms_norm_eps, cfg.rope_theta,
            cfg.aux_loss_coef, cfg.z_loss_coef) \
        == (151936, 18992, 16384, 1, 0, 1e-6, 1e7, 0.001, 0.0)
    shapes = train.model_param_shapes(cfg)
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
    assert count(shapes) == 625_667_136                  # 625.67 M
    per = {k: count(v) for k, v in train.pattern_layer_shapes(cfg).items()}
    assert per["gdn_moe"] == 138_582_208 and per["attn_moe"] == 132_127_232
    assert 3 * per["gdn_moe"] + per["attn_moe"] + 2 * 18992 * 2048 + 2048 \
        == 625_667_136
    with open(CONFIG) as f:
        body = json.load(f)
    assert body["kit"] == "qwen3nextkit" and body["chips_a_layer"] == 16
    assert "625.67 M" in body["arithmetic"] and "10.01 GB" \
        in body["arithmetic"]


def test_the_files_published_keys_are_the_catalogs():
    """Every key of the catalog's row, under the same name and with the
    same value (the guide's rule, checked where the catalog is at hand)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
    with open(CONFIG) as f:
        body = json.load(f)
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert body[key] == value, key


REFUSED = [
    (dict(layer_types=TYPES + ("sliding_attention",) + TYPES[1:]),
     "layer_types"),
    (dict(heads_here=2), "heads_here"),
    (dict(mtp_here=1), "mtp_here"),
    (dict(kv_lora_rank=16), "kv_lora_rank"),
    (dict(num_key_value_heads=3), "heads_here"),
    (dict(scoring_func="softmax", topk_method="noaux_tc"), "router"),
]


@pytest.mark.parametrize("change,key", REFUSED,
                         ids=[f"{k}-{i}" for i, (_, k) in enumerate(REFUSED)])
def test_what_the_path_cannot_run_is_refused_by_its_key(change, key):
    with pytest.raises(NotImplementedError, match=key):
        dataclasses.replace(F32, **change)


def test_a_head_width_of_its_own_is_a_layer_types_models():
    from test_olmoe_train import F32 as OLMOE

    with pytest.raises(NotImplementedError, match="head_dim"):
        dataclasses.replace(OLMOE, head_dim=2 * OLMOE.hidden_size
                            // OLMOE.num_attention_heads)
    with pytest.raises(NotImplementedError, match="attn_output_gate"):
        dataclasses.replace(OLMOE, attn_output_gate=True)
    with pytest.raises(ValueError, match="linear_num_value_heads"):
        dataclasses.replace(F32, linear_num_value_heads=3)


@pytest.mark.parametrize("key,value,named", [
    ("rope_scaling", {"type": "yarn"}, "RoPE"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("use_expert_bias", True, "use_expert_bias"),
    ("attention_bias", True, "biases"),
    ("hidden_act", "gelu", "silu")])
def test_a_published_file_the_path_cannot_run_is_refused(tmp_path, key,
                                                          value, named):
    with open(CONFIG) as f:
        body = json.load(f)
    path = tmp_path / "changed.json"
    path.write_text(json.dumps({**body, key: value}))
    with pytest.raises(NotImplementedError, match=named):
        train.load_model_config(str(path))


def test_the_kinds_follow_the_interval(tmp_path):
    """``full_attention_interval`` 2 makes every other layer attend, as
    the family's configuration class derives ``layer_types``."""
    with open(CONFIG) as f:
        body = json.load(f)
    path = tmp_path / "changed.json"
    path.write_text(json.dumps({**body, "full_attention_interval": 2}))
    assert train.load_model_config(str(path)).pattern_here == "LALA"


# -- the benchmark's own copy of the reference -----------------------------------------
@pytest.fixture(scope="module")
def kit():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import qwen3nextkit
        yield qwen3nextkit
    finally:
        sys.path.remove(BENCH)


KIT_CFG = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 32, "partial_rotary_factor": 0.25,
    "num_experts": 16, "n_routed_experts": 16, "num_experts_per_tok": 4,
    "vocab_size": 256, "full_attention_interval": 4,
    "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1e7, **SHARE, **TRAIN,
    "compute_dtype": "float32", "init_std": 0.02}


def test_the_kit_names_the_programs_leaves(kit):
    assert kit.leaves(KIT_CFG) == tuple(n for n, _ in NAMES)
    assert kit.pattern(KIT_CFG) == F32.pattern_here
    assert [(c, n, first) for c, n, first in kit.segments(KIT_CFG)] \
        == list(F32.segments)
    checked = kit.checked(KIT_CFG)
    assert set(checked) <= set(kit.leaves(KIT_CFG)) \
        and kit.probed(KIT_CFG) == tuple(n for n in checked if n != "embed")
    shapes = train.model_param_shapes(F32)
    assert kit.leaf_sizes(KIT_CFG) == {
        n: int(np.prod(train._leaf(shapes, p))) for n, p in NAMES}
    for name in ("l0.gdn_moe.in_proj", "l0.gdn_moe.ba_proj",
                 "l0.gdn_moe.conv_w", "l0.gdn_moe.A_log",
                 "l0.gdn_moe.dt_bias", "l0.gdn_moe.gate_norm",
                 "l0.gdn_moe.out_proj", "l0.gdn_moe.router",
                 "l0.gdn_moe.shared_w_g", "l0.gdn_moe.shared_gate",
                 "l0.gdn_moe.shared_down", "l3.attn_moe.wq",
                 "l3.attn_moe.wk", "l3.attn_moe.wv", "l3.attn_moe.wo",
                 "l3.attn_moe.q_norm", "l3.attn_moe.k_norm",
                 "l3.attn_moe.router", "l3.attn_moe.shared_w_g",
                 "l3.attn_moe.gate", "l3.attn_moe.up", "l3.attn_moe.down",
                 "final_norm", "head", "embed"):
        assert name in checked, name
    # the held experts' three of the last run only: a run of three
    # layers' do not fit beside the reference at the published widths
    assert "l0.gdn_moe.gate" not in checked
    assert not any(n.endswith((".ln1", ".ln2")) for n in checked)


def test_the_kits_reference_is_the_repositorys(kit):
    tokens, labels = batch_of(4)
    params = built.params(F32, 11)
    (total, (ce, lb, loads)), want = ref.grads(params, tokens, labels, F32)
    wrt = kit.checked(KIT_CFG)
    tree = kit.tree_of({n: kit.leaf_of(params, n)
                        for n in kit.leaves(KIT_CFG)})
    got = kit.reference_step(tree, tokens, labels, KIT_CFG, {}, wrt)
    close(got["losses"], [total, ce, lb])
    close(got["loads"], loads)
    for name in wrt:
        near(got["grads"][name], kit.leaf_of(want, name), err_msg=name)


def test_the_kit_compares_a_step_of_the_program_within_its_tolerance(kit):
    """What the kind does on the chip, here in float32: the step's
    statistics and float32 parts in the kit's units lie within a
    hundredth of the tolerance of the reference's under the step's own
    routing; every wrong model lies outside it somewhere, and every
    control of a part outside it at that part."""
    tokens, labels = batch_of(4)
    params = built.params(F32, 11)
    step, place = built.step(F32)
    state, t, l = place(jax.tree.map(jnp.copy, params), tokens, labels)
    state, aux = step(state, t, l)
    aux = jax.device_get(aux)
    wrt = kit.checked(KIT_CFG)
    bias = jax.device_get(state[4])
    got = {**kit.compared(kit.step_stats(aux, bias, KIT_CFG), KIT_CFG, wrt),
           **kit.precision_got(aux, KIT_CFG)}
    by_name = {n: np.asarray(kit.leaf_of(params, n))
               for n in kit.leaves(KIT_CFG)}
    tree = kit.tree_of(by_name)

    def units(side, want):
        return {k: float(np.max(np.abs(np.float64(side[k]) - want[k])
                                / (0.005 + 0.000375 * np.abs(want[k]))))
                for k in side}

    def whole(**kw):
        out = jax.device_get({k: v for k, v in kit.reference_step(
            tree, tokens, labels, KIT_CFG, bias, wrt,
            routed=aux["experts"], **kw).items() if k != "grads"})
        return kit.compared(out, KIT_CFG, wrt)

    def parts(variant=None):
        return kit.precision_want(aux, by_name, bias["layers"],
                                  jnp.asarray(tree["head"]), labels, KIT_CFG,
                                  variant=variant)

    want = {**whole(), **parts()}
    assert set(want) == set(kit.OUTPUTS + kit.PRECISION) == set(got)
    assert max(units(got, want).values()) < 0.02, units(got, want)
    assert want["losses"].shape == (3,) \
        and want["grad_probe"].shape == (len(kit.probed(KIT_CFG)), 64)
    for wrong in kit.WRONG:
        assert max(units(whole(wrong=wrong), want).values()) > 1, wrong
    for variant, part in (("bf16", "router_logits"), ("rule_bf16", "gdn_o"),
                          ("beta_one", "gdn_o"), ("no_decay", "gdn_o"),
                          ("no_gate", "attn_gated"),
                          ("rope_whole", "rope_qk"),
                          ("sigmoid", "router_scores"),
                          ("no_shared_gate", "shared_gate")):
        assert units(parts(variant), want)[part] > 1, variant
    assert set(kit.PART_CONTROLS) == {
        "bf16", "rule_bf16", "beta_one", "no_decay", "no_gate",
        "rope_whole", "sigmoid", "no_shared_gate"}


def test_the_kit_counts_the_published_steps_operations(kit):
    cfg = kit.load_config(CONFIG)
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 512
    assert kit.pattern(cfg) == "LLLA"
    per = kit.matmul_params_per_token(cfg)
    assert per["gdn_proj"] == 2048 * 12288 + 2048 * 64 + 4096 * 2048
    assert per["attn_proj"] == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert per["shared"] == 3 * 2048 * 512 + 2048
    assert per["experts_mean"] == 3 * 2048 * 512 * 10 * 32 / 512
    assert per["head"] == 2048 * 18992
    total = 3 * per["gdn_proj"] + per["attn_proj"] + 4 * (
        per["router"] + per["shared"] + per["experts_mean"]) + per["head"]
    assert abs(total / 1e6 - 191.86) < 0.01
    flops = kit.step_flops(cfg)
    assert abs(flops["step"] / 1e12 - 25.5) < 0.05
    assert flops["flash_forward"] == 16 * 2 * 256 * 16384 * 16384
    assert abs(flops["gdn_proj"] / 1e12 - 9.9) < 0.05 \
        and abs(flops["attention"] / 1e12 - 6.6) < 0.05 \
        and abs(flops["head"] / 1e12 - 3.8) < 0.05
    # the program's own products; ISSUE 51 reckoned 0.87 with k k^T and
    # q k^T a value head, which ``gated_delta_chunked`` makes a key head
    assert abs(3 * flops["rule_forward"] / 1e12 - 0.77) < 0.01
    assert sum(kit.leaf_sizes(cfg).values()) == 625_667_136
