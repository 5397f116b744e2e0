"""LFM2-8B-A1B's training step on the normal path (``parallel/train.py``'s
model path under ``layer_types``: gated short convolutions, grouped-query
attention with a per-head QK-norm and RoPE, a dense SwiGLU in the leading
layers and sigmoid-routed experts with no shared one behind them, a tied
head) against the plain reference (``parallel/lfm2_reference.py``) at
small widths on seeded random weights: hidden 64, 4 query heads of 16
reading 2 key-value heads, a dense MLP of 96, 8 experts of width 24, top
2, 3 taps; held here: the published ``layer_types``' layers 1 to 6 (a
dense convolution layer, an attention layer, three convolution layers,
an attention layer), 2 experts (share 1 of 4), 64 of 256 ids.  Float32
compute meets the reference at rtol 1e-5."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import (attention, causal, config, experts, layers,
                               objective, short_conv, train)
from ompi_tpu.parallel import lfm2_reference
from ompi_tpu.parallel import nemotron_reference
from ompi_tpu.runtime import spc

import built

ref = built.programs(lfm2_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = os.path.join(BENCH, "configs", "lfm2-8b-a1b-train-1chip.json")
TYPES = ("conv", "conv", "full_attention", "conv", "conv", "conv",
         "full_attention", "conv", "conv", "conv", "full_attention", "conv")
PUBLISHED = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
    vocab_size=256, layer_types=TYPES, first_k_dense_replace=2,
    moe_intermediate_size=24, conv_kernel=3, scoring_func="sigmoid",
    topk_method="noaux_tc", norm_topk_prob=True, routed_scaling_factor=1.0,
    rope_theta=1e6, tie_word_embeddings=True)
SHARE = dict(layers_here=6, first_layer_here=1, experts_here=2,
             expert_share=1, vocab_here=64)
TRAIN = dict(seq_len=32, micro_batch=2, attn_block=16, loss_block_rows=16,
             lr=1e-2, aux_loss_coef=0.0, z_loss_coef=0.0,
             bias_update_gamma=0.001)
F32 = config.ModelConfig(compute_dtype="float32", **PUBLISHED, **SHARE,
                        **TRAIN)
NAMES = train.leaf_names(F32)
CLOSE = dict(rtol=1e-5, atol=1e-6)


def batch_of(seed, vocab=64):
    """(inputs (2, 32), labels (2, 33)) from 34 ids a sequence: the
    batch's form for every share cell, of which this model reads the
    first 32 labels."""
    ids = np.random.default_rng(seed).integers(0, vocab, (2, 34)).astype(
        np.int32)
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def some_bias(cfg=F32, scale=0.01):
    return {"layers": scale * jax.random.normal(
        jax.random.PRNGKey(7), (cfg.n_sparse_here, cfg.num_experts))}


def layer_of(cfg, kind, seed=5):
    """One layer's leaves of ``kind`` (``conv_dense``, ``attn_moe``, ...)
    drawn as ``init_model_params`` would, the matrices wide enough (0.3)
    that every part matters."""
    types = ("conv" if kind.startswith("conv") else "full_attention",) * 2
    one = dataclasses.replace(
        cfg, init_std=0.3, layer_types=types, layers_here=1,
        first_layer_here=0 if kind.endswith("dense") else 1,
        first_k_dense_replace=1)
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


# -- the sublayers ---------------------------------------------------------------
def conv_by_positions(p, x, cfg):
    """The gated short convolution one position at a time, in numpy
    float64: position t reads positions t - 2, t - 1 and t of its own
    sequence, and nothing before position 0."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    b, s, d = x.shape
    taps = p["conv_w"].shape[0]
    n = x / np.sqrt((x * x).mean(-1, keepdims=True) + cfg.rms_norm_eps) \
        * p["ln1"]
    bcu = n @ p["in_proj"]
    gate_b, gate_c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    y = np.zeros((b, s, d))
    for t in range(s):
        z = np.zeros((b, d))
        for j in range(taps):
            at = t - (taps - 1) + j
            if at >= 0:
                z += p["conv_w"][j] * gate_b[:, at] * u[:, at]
        y[:, t] = gate_c[:, t] * z
    return y @ p["out_proj"]


@pytest.mark.parametrize("length", [27, 3, 2, 1])
def test_the_short_convolution_is_the_loop_over_positions(length):
    """Forward against a position-by-position loop, and every leaf's and
    the input's gradient against the reference's, at a sequence longer
    than the taps, as long, and shorter."""
    p = layer_of(F32, "conv_dense")
    x = jax.random.normal(jax.random.PRNGKey(length), (2, length, 64))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(short_conv.short_conv(p, x, F32)[0] * probe),
        argnums=(0, 1)))(p, x)
    close(short_conv.short_conv(p, x, F32)[0], conv_by_positions(p, x, F32),
          rtol=1e-4, atol=1e-5)
    with jax.default_matmul_precision("highest"):
        close(ref.short_conv(p, x, F32), conv_by_positions(p, x, F32),
              rtol=1e-4, atol=1e-5)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(ref.short_conv(p, x, F32) * probe),
            argnums=(0, 1)))(p, x)
    close(got, want, rtol=1e-4)
    for k in ("ln1", "in_proj", "conv_w", "out_proj"):
        near(got_g[0][k], want_g[0][k], err_msg=k)
    near(got_g[1], want_g[1])


def test_the_short_convolution_reports_what_its_gate_path_read_and_made():
    p = layer_of(F32, "conv_moe")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64))
    _, _, seen = short_conv.short_conv(p, x, F32)
    c = min(short_conv.CONV_SAMPLE, 64)
    assert seen["conv_bcu_seq"].shape == (18, 3 * c)
    bcu = np.asarray(seen["conv_bcu_seq"], np.float64).reshape(2, 9, 3, c)
    gated = bcu[:, :, 0] * bcu[:, :, 2]
    w = np.asarray(p["conv_w"], np.float64)[:, :c]
    z = w[2] * gated
    z[:, 1:] += w[1] * gated[:, :-1]
    z[:, 2:] += w[0] * gated[:, :-2]
    close(seen["conv_y"], (bcu[:, :, 1] * z).reshape(18, c), rtol=1e-5)


@pytest.mark.parametrize("heads,kv", [(4, 2), (8, 2), (4, 4)],
                         ids=["2to1", "4to1", "1to1"])
def test_attention_with_qk_norm_and_rope_is_the_references(heads, kv):
    cfg = dataclasses.replace(F32, num_attention_heads=heads,
                              num_key_value_heads=kv)
    p = layer_of(cfg, "attn_moe")
    p = {**p, "q_norm": p["q_norm"] * 1.3, "k_norm": p["k_norm"] * 0.7}
    assert p["wk"].shape == (64, kv * 64 // heads) \
        and p["q_norm"].shape == (64 // heads,)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(ref.attention(p, x, cfg) * probe),
            argnums=(0, 1)))(p, x)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(attention.FULL.run(
            p, x, cfg, interpret=True)[0] * probe), argnums=(0, 1)))(p, x)
    close(got, want, rtol=1e-4)
    for k in ("ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm"):
        near(got_g[0][k], want_g[0][k], err_msg=k)
    near(got_g[1], want_g[1])
    _, _, seen = attention.FULL.run(p, x, cfg, interpret=True)
    hd = 64 // heads
    assert seen["attn_qk"].shape == seen["attn_qk_in"].shape == (64, 2 * hd)
    # row 0 is position 0: RoPE turns nothing there, so what is left is
    # the per-head norm and its gain
    q0 = np.asarray(seen["attn_qk_in"])[0, :hd]
    close(seen["attn_qk"][0, :hd], q0 / np.sqrt(
        np.mean(q0 * q0) + cfg.rms_norm_eps) * 1.3, rtol=1e-5)


def test_without_a_qk_norm_the_sublayer_is_nemotrons_bit_for_bit():
    """A layer that holds no ``q_norm`` takes the path it took before the
    branch was there: nemotron_h's attention, no rotary embedding, the
    same numbers as its reference to the last bit of ``close`` and
    nothing reported."""
    from test_nemotron_train import F32 as NEMOTRON, layer_of as their_layer

    cfg = dataclasses.replace(NEMOTRON, heads_here=8, num_key_value_heads=4)
    p = their_layer(cfg, "attn")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    got, _, seen = attention.SHARED_KV.run(p, x, cfg, interpret=True)
    assert seen == {}
    with jax.default_matmul_precision("highest"):
        close(got, nemotron_reference.attention(p, x, cfg), rtol=1e-4)
    b, s, dt = 2, 32, cfg.compute_dtype
    h = layers.rmsnorm_gain(x, p["ln1"], cfg.rms_norm_eps)
    heads = lambda t, n: t.reshape(b, s, n, -1).transpose(0, 2, 1, 3)
    q = heads(layers.matmul(h, p["wq"], dt), 8)
    k, v = (jnp.repeat(heads(layers.matmul(h, p[w], dt), 2), 4, 1)
            for w in ("wk", "wv"))
    o = causal.causal_flash_attention(q, k, v, 16, True)
    want = layers.matmul(o.transpose(0, 2, 1, 3).reshape(b, s, -1), p["wo"],
                        dt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_expert_block_without_a_shared_expert_under_uneven_routing():
    """A bias that sends every token to expert 2 (held) and none to
    expert 3 (held): the block keeps every slot, has no shared expert's
    leaves, its output and every gradient are the reference's, expert 3's
    gradient is zero."""
    p = layer_of(F32, "conv_moe")
    assert "shared_gate" not in p
    bias = jnp.zeros((8,)).at[2].set(10.0).at[3].set(-10.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        (want, load), want_g = jax.jit(jax.value_and_grad(
            lambda p, x: (lambda y, load: (jnp.sum(y * probe), load))(
                *ref.experts(p, x, bias, F32)),
            argnums=(0, 1), has_aux=True))(p, x)
    (got, stats), got_g = jax.jit(jax.value_and_grad(
        lambda p, x: (lambda y, st, _: (jnp.sum(y * probe), st))(
            *experts.moe_shared_local_block(p, x, F32, bias)),
        argnums=(0, 1), has_aux=True))(p, x)
    assert load[2] == 64 and load[3] == 0
    # the hot expert's group alone is more than one of the loop's chunks
    assert 64 > experts.chunk_rows(
        64, F32.num_experts_per_tok, F32.n_experts_here, F32.num_experts)
    close(stats["slots"], load)
    close(got, want, rtol=1e-4)
    for k in ("ln2", "router", "gate", "up", "down"):
        near(got_g[0][k], want_g[0][k], err_msg=k)
    near(got_g[1], want_g[1])
    assert not np.any(np.asarray(got_g[0]["up"][1]))
    assert np.any(np.asarray(got_g[0]["up"][0]))


# -- the share and the model --------------------------------------------------------
def test_the_four_shares_expert_parts_add_up_to_the_uncut_layer():
    """The 4 expert shares of a sparse layer (2 of 8 each; no shared
    expert to count once) add up to the uncut reference's layer: what the
    expert-parallel group's exchange would make of them."""
    whole = dataclasses.replace(F32, experts_here=0, expert_share=0)
    p = layer_of(whole, "attn_moe")
    bias = some_bias()["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(p, x, bias, whole)
    total = 0.0
    for j in range(4):
        part = dataclasses.replace(F32, experts_here=2, expert_share=j)
        mine = {**p, **{k: p[k][2 * j:2 * j + 2]
                        for k in ("gate", "up", "down")}}
        total = total + experts.moe_shared_local_block(mine, x, part,
                                                       bias)[0]
    close(total, want, rtol=1e-4, atol=1e-5)


def test_the_layers_are_walked_by_their_types():
    assert F32.pattern_here == "cACCCA"
    assert F32.segments == (("c", 1, 0), ("A", 1, 1), ("C", 3, 2),
                            ("A", 1, 5))
    assert (F32.n_sparse_here, F32.n_routers, F32.conv_kernel) == (5, 5, 3)
    assert [n for n, _ in NAMES][:3] == [
        "embed", "l0.conv_dense.ln1", "l0.conv_dense.in_proj"]
    assert NAMES[-1][0] == "final_norm"         # no head: it is tied
    shapes = train.model_param_shapes(F32)
    assert "head" not in shapes
    assert shapes["layers"]["l2"]["conv_moe"]["gate"] == (3, 2, 64, 24)
    assert shapes["layers"]["l1"]["attn_moe"]["wk"] == (1, 64, 32)
    assert shapes["layers"]["l1"]["attn_moe"]["k_norm"] == (1, 16)
    whole = dataclasses.replace(F32, first_layer_here=0, layers_here=12)
    assert whole.pattern_here == "ccACCCACCCAC"


# -- the whole step --------------------------------------------------------------------
@pytest.fixture(scope="module")
def stepped():
    """Three steps of the program from seed 3, and the reference's."""
    step, place = built.step(F32)
    params = built.params(F32, 3)
    batches = [batch_of(s) for s in range(3)]
    state, _, _ = place(jax.tree.map(jnp.copy, params), *batches[0])
    auxes = []
    if "train_steps" not in spc.counters():
        spc.init()
    before = spc.read("train_steps")
    for tokens, labels in batches:
        state, aux = step(state, tokens, labels)
        auxes.append(jax.device_get(aux))
    counted = spc.read("train_steps") - before
    with jax.default_matmul_precision("highest"):
        want = ref.train_steps(params, batches, F32)
    return dict(params=params, batches=batches, state=state, auxes=auxes,
                want=want, counted=counted, step=step)


def test_three_steps_are_the_references(stepped):
    params, bias, losses = stepped["want"]
    close([a["losses"][0] for a in stepped["auxes"]], losses)
    close(stepped["state"][4]["layers"], bias["layers"], atol=1e-7)
    for name, path in NAMES:
        # Adam's first steps move an entry by the learning rate times
        # its gradient's sign, so one whose gradient is next to nothing
        # goes either way: a thousandth of a leaf may lie outside a
        # hundredth of the three steps, none outside the three steps
        off = np.abs(np.asarray(train._leaf(stepped["state"][0], path))
                     - np.asarray(train._leaf(params, path)))
        assert off.max() <= 3 * F32.lr, name
        assert np.mean(off > 0.01 * 3 * F32.lr) <= 1e-3, name
    # the steps issued; the bias updates in them are the configuration's
    # constant (a router each) times it
    assert stepped["counted"] == 3 and F32.n_routers == 5


def test_one_step_reports_the_references_loads_and_gradients(stepped):
    tokens, labels = stepped["batches"][0]
    aux = stepped["auxes"][0]
    (loss, loads), g = ref.grads(stepped["params"], tokens, labels, F32,
                                 ref.zero_bias(F32))
    close(aux["losses"][:2], [loss, loss])
    close(aux["loads"], loads)
    assert aux["loads"].shape == (5, 8) and aux["experts"].shape \
        == (5, 64, 2)
    first = F32.first_expert_here
    assert aux["local_slots"] == loads[:, first:first + 2].sum()
    sample = aux["sample"]
    assert sample["conv_bcu_seq"].shape == (4, 64, 192) \
        and sample["conv_y"].shape == (4, 16, 64) \
        and sample["attn_qk"].shape == (2, 16, 32) \
        and sample["router_logits"].shape == (5, 16, 8)
    for (name, path), sq, probe in zip(NAMES, aux["grad_sq"],
                                       aux["grad_probe"]):
        leaf = np.asarray(train._leaf(g, path))
        close(sq, np.sum(leaf * leaf), rtol=1e-4, err_msg=name)
        near(probe, leaf.reshape(-1)[train.probe_positions(
            name, leaf.size)], err_msg=name)
    rows = train.probe_positions("embed", 64 * 64) // 64
    np.testing.assert_array_equal(
        aux["embed_probe_read"], np.isin(rows, np.asarray(tokens)))


@pytest.mark.parametrize("on_tpu", [False, True], ids=["twins", "kernels"])
def test_the_step_holds_no_k_or_v_a_query_head(traced_step, on_tpu):
    """4 query heads on 2 key-value heads, two attention layers, 2 x 32
    positions at a head width of 16 (``traced_step``'s
    ``holds_no_repeat`` says what is held)."""
    assert (F32.n_heads_here, F32.n_kv_heads_here) == (4, 2)
    traced_step(F32, *batch_of(0), on_tpu).holds_no_repeat(2, 4, 2, 32, 16)


def test_every_leafs_gradient_is_the_references():
    tokens, labels = batch_of(4)
    params, bias = built.params(F32, 11), some_bias()
    (_, aux), got = jax.jit(jax.value_and_grad(
        lambda ps: objective.model_loss(ps, tokens, labels, F32, interpret=True,
                                    n_global=64, bias=bias),
        has_aux=True))(params)
    (_, loads), want = ref.grads(params, tokens, labels, F32, bias)
    close(aux["loads"], loads)
    for name, path in NAMES:
        near(train._leaf(got, path), train._leaf(want, path), err_msg=name)


def test_the_tied_matrixs_gradient_is_the_sum_of_both_uses():
    """One matrix under the gather and under the cross-entropy: its
    gradient is the gather's (a second matrix held fixed under the head)
    plus the head's (the gather held fixed), and a model whose head is a
    second, independently drawn matrix has another loss and another
    gradient."""
    tokens, labels = batch_of(4)
    params, bias = built.params(F32, 11), some_bias()
    got = jax.jit(jax.grad(lambda ps: objective.model_loss(
        ps, tokens, labels, F32, interpret=True, n_global=64,
        bias=bias)[0]))(params)["embed"]
    embed = params["embed"]
    with jax.default_matmul_precision("highest"):
        gather = jax.jit(jax.grad(lambda ps: ref.loss_parts(
            ps, tokens, labels, F32, bias, head=embed.T)[0]))(params)["embed"]
        head = jax.jit(jax.grad(lambda h: ref.loss_parts(
            params, tokens, labels, F32, bias, head=h)[0]))(embed.T)
        other = 0.02 * jax.random.normal(jax.random.PRNGKey(99),
                                         embed.T.shape)
        (untied, _), untied_g = ref.grads(params, tokens, labels, F32, bias,
                                          head=other)
        (tied, _), _ = ref.grads(params, tokens, labels, F32, bias)
    near(got, gather + head.T)
    assert np.abs(np.asarray(head)).max() > 1e-4 \
        and np.abs(np.asarray(gather)).max() > 1e-4
    assert abs(float(untied) - float(tied)) > 1e-3
    scale = np.abs(np.asarray(got)).max()
    assert np.abs(np.asarray(untied_g["embed"]) - np.asarray(got)).max() \
        > 0.1 * scale
    # decayed and updated once: one leaf, and it is a matrix
    assert [n for n, _ in NAMES].count("embed") == 1 \
        and train.is_decayed("embed")


def test_what_the_checkpoint_keeps_changes_no_number(monkeypatch):
    tokens, labels = batch_of(4)
    params, bias = built.params(F32, 11), some_bias()

    def grads():
        return jax.jit(jax.value_and_grad(
            lambda ps: objective.model_loss(ps, tokens, labels, F32,
                                        interpret=True, n_global=64,
                                        bias=bias), has_aux=True))(params)

    (loss, aux), got = grads()
    monkeypatch.setattr(objective, "layer_checkpoint_policy",
                        lambda: jax.checkpoint_policies.nothing_saveable)
    (bare_loss, bare_aux), bare = grads()
    assert loss == bare_loss
    for name, path in NAMES:
        np.testing.assert_array_equal(train._leaf(got, path),
                                      train._leaf(bare, path), name)
    for key in ("losses", "loads", "experts"):
        np.testing.assert_array_equal(aux[key], bare_aux[key], key)


def test_the_taps_are_decayed_and_no_gain_is():
    undecayed = {n for n, _ in NAMES if not train.is_decayed(n)}
    assert undecayed == {n for n, _ in NAMES if n.rsplit(".", 1)[-1] in (
        "ln1", "ln2", "q_norm", "k_norm", "final_norm")}
    params = built.params(F32, 3)["layers"]["l2"]["conv_moe"]
    assert params["conv_w"].shape == (3, 3, 64)
    assert np.abs(np.asarray(params["conv_w"])).max() <= 3 ** -0.5
    assert np.all(np.asarray(params["ln1"]) == 1.0)


def test_the_losses_repeat_bit_for_bit_from_one_seed(stepped):
    # a second build and a second draw, not the process's kept ones:
    # whether they give the first's numbers is what is asked
    step, place = built.fresh_step(F32)
    state, _, _ = place(train.init_model_params(F32, 3),
                        *stepped["batches"][0])
    for (tokens, labels), first in zip(stepped["batches"],
                                       stepped["auxes"]):
        state, aux = step(state, tokens, labels)
        assert np.asarray(aux["losses"]).tobytes() \
            == np.asarray(first["losses"]).tobytes()


def test_bfloat16_compute_stays_near_float32(stepped):
    cfg = dataclasses.replace(F32, compute_dtype="bfloat16")
    step, place = built.step(cfg)
    state, tokens, labels = place(built.params(cfg, 3),
                                  *stepped["batches"][0])
    _, aux = step(state, tokens, labels)
    close(aux["losses"][0], stepped["auxes"][0]["losses"][0], rtol=3e-3)


def test_two_data_parallel_ranks_are_one_model(stepped):
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    step, place = built.step(F32, 2)
    state, tokens, labels = place(built.params(F32, 3),
                                  *stepped["batches"][0])
    state, aux = step(state, tokens, labels)
    first = stepped["auxes"][0]
    close(aux["losses"], first["losses"])
    close(aux["loads"], first["loads"])
    close(aux["grad_sq"], first["grad_sq"], rtol=1e-4)
    np.testing.assert_array_equal(aux["embed_probe_read"],
                                  first["embed_probe_read"])


# -- what the path reads and what it refuses ----------------------------------------------
def test_the_benchmarks_configuration_loads_at_its_published_widths():
    cfg = train.load_model_config(CONFIG)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.expert_width, cfg.conv_kernel,
            cfg.first_k_dense_replace, cfg.rope_theta, cfg.rms_norm_eps,
            cfg.routed_scaling_factor, cfg.vocab_size,
            cfg.n_shared_experts, cfg.tie_word_embeddings) == (
        2048, 7168, 32, 8, 32, 4, 1792, 3, 2, 1000000, 1e-5, 1, 65536, 0,
        True)
    assert len(cfg.layer_types) == 24 and cfg.layer_types.count("conv") == 18
    assert cfg.pattern_here == "cACCCA"
    assert (cfg.n_heads_here, cfg.n_kv_heads_here, cfg.n_experts_here,
            cfg.first_expert_here, cfg.vocab_rows, cfg.n_mtp_here,
            cfg.seq_len, cfg.micro_batch) == (32, 8, 8, 0, 16384, 0, 8192, 2)
    shapes = train.model_param_shapes(cfg)
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 606_456_064             # 9.70 GB at 16 bytes each
    per = {k: sum(int(np.prod(s)) for s in v.values())
           for k, v in train.pattern_layer_shapes(cfg).items()}
    assert per == {"conv_dense": 60_827_648, "conv_moe": 104_933_376,
                   "attn_dense": 54_530_176, "attn_moe": 98_635_904}
    assert shapes["embed"] == (16384, 2048) and "head" not in shapes


def test_the_files_published_keys_are_the_catalogs():
    with open(CONFIG) as f:
        body = json.load(f)
    assert (body["conv_L_cache"], body["conv_bias"], body["norm_eps"],
            body["num_dense_layers"], body["num_hidden_layers"],
            body["use_expert_bias"], body["model_type"],
            body["max_position_embeddings"]) == (
        3, False, 1e-5, 2, 24, True, "lfm2_moe", 128000)
    assert body["layer_types"][:7] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention"]
    assert body["kit"] == "lfm2kit" and body["chips_a_layer"] == 4


REFUSED = [
    (dict(layer_types=TYPES[:5] + ("sliding_attention",) + TYPES[6:]),
     "layer_types"),
    (dict(heads_here=2), "heads_here"),
    (dict(kv_lora_rank=16), "kv_lora_rank"),
    (dict(scoring_func="softmax", topk_method="noaux_tc"), "router"),
    (dict(num_key_value_heads=3), "heads_here"),
    (dict(mtp_here=1, num_nextn_predict_layers=1), "mtp_here"),
    (dict(hybrid_override_pattern="MEM*"), "layer_types"),
]


@pytest.mark.parametrize("change,key", REFUSED,
                         ids=[f"{k}-{i}" for i, (_, k) in enumerate(REFUSED)])
def test_what_the_path_cannot_run_is_refused_by_its_key(change, key):
    with pytest.raises(NotImplementedError, match=key):
        dataclasses.replace(F32, **change)


def test_layers_outside_the_types_are_refused():
    with pytest.raises(ValueError, match="layers_here"):
        dataclasses.replace(F32, first_layer_here=9)


@pytest.mark.parametrize("key,value,named", [
    ("conv_bias", True, "conv_bias"),
    ("use_expert_bias", False, "use_expert_bias"),
    ("rope_scaling", {"type": "yarn"}, "RoPE"),
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


def test_a_tied_head_is_any_models(tmp_path):
    """``tie_word_embeddings`` true no longer refuses a file: OLMoE's
    cell's, tied, loads, holds no ``head`` leaf and steps."""
    with open(os.path.join(BENCH, "configs",
                           "olmoe-1b-7b-train-1chip.json")) as f:
        body = json.load(f)
    path = tmp_path / "tied.json"
    path.write_text(json.dumps({**body, "tie_word_embeddings": True}))
    cfg = train.load_model_config(
        str(path), hidden_size=64, intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=4, num_experts=8,
        num_experts_per_tok=2, vocab_size=256, layers_here=1, seq_len=32,
        micro_batch=2, attn_block=16, loss_block_rows=16,
        compute_dtype="float32")
    assert "head" not in train.model_param_shapes(cfg)
    tokens, labels = batch_of(1)
    params = built.params(cfg, 0)
    loss, aux = objective.model_loss(params, tokens, labels[:, :32], cfg,
                                 interpret=True, n_global=64)
    assert np.isfinite(float(loss)) and aux["rows"].shape == (64, 2)


# -- the benchmark's own copy of the reference -----------------------------------------
@pytest.fixture(scope="module")
def kit():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import lfm2kit
        yield lfm2kit
    finally:
        sys.path.remove(BENCH)


KIT_CFG = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "vocab_size": 256, "layer_types": list(TYPES), "num_dense_layers": 2,
    "moe_intermediate_size": 24, "conv_L_cache": 3, "norm_eps": 1e-5,
    "norm_topk_prob": True, "routed_scaling_factor": 1.0,
    "rope_theta": 1e6, "n_routed_experts": 8, **SHARE, **TRAIN,
    "compute_dtype": "float32", "init_std": 0.02}


def test_the_kit_names_the_programs_leaves(kit):
    assert kit.leaves(KIT_CFG) == tuple(n for n, _ in NAMES)
    assert kit.pattern(KIT_CFG) == F32.pattern_here
    assert [(c, n, first) for c, n, first in kit.segments(KIT_CFG)] \
        == list(F32.segments)
    checked = kit.checked(KIT_CFG)
    assert set(checked) <= set(kit.leaves(KIT_CFG)) \
        and kit.probed(KIT_CFG) == checked
    shapes = train.model_param_shapes(F32)
    assert kit.leaf_sizes(KIT_CFG) == {
        n: int(np.prod(train._leaf(shapes, p))) for n, p in NAMES}
    for name in ("l0.conv_dense.in_proj", "l0.conv_dense.conv_w",
                 "l0.conv_dense.out_proj", "l0.conv_dense.down",
                 "l1.attn_moe.wk", "l1.attn_moe.q_norm",
                 "l1.attn_moe.k_norm", "l1.attn_moe.router",
                 "l1.attn_moe.gate", "l2.conv_moe.out_proj",
                 "l5.attn_moe.wo", "final_norm", "embed"):
        assert name in checked, name
    assert "head" not in kit.leaves(KIT_CFG)


def test_the_kits_reference_is_the_repositorys(kit):
    tokens, labels = batch_of(4)
    params, bias = built.params(F32, 11), some_bias()
    (loss, loads), want = ref.grads(params, tokens, labels, F32, bias)
    wrt = kit.checked(KIT_CFG)
    tree = kit.tree_of({n: kit.leaf_of(params, n)
                        for n in kit.leaves(KIT_CFG)})
    assert tree["head"].shape == (64, 64)
    got = kit.reference_step(tree, tokens, labels, KIT_CFG, bias, wrt)
    close(got["losses"], [loss, loss])
    close(got["loads"], loads)
    close(got["bias"], ref.bias_step(bias, loads, F32)["layers"])
    for name in wrt:
        near(got["grads"][name], kit.leaf_of(want, name), err_msg=name)


def test_the_kit_compares_a_step_of_the_program_within_its_tolerance(kit):
    """What the kind does on the chip, here in float32: the step's
    statistics and float32 parts in the kit's units lie within a
    hundredth of the tolerance of the reference's under the step's own
    routing; every wrong model lies outside it somewhere, and every
    control of a part outside it at that part."""
    tokens, labels = batch_of(4)
    # a bias wide enough that it turns choices and would move a weight,
    # and not so wide that the first router sends the held experts nothing
    params, bias = built.params(F32, 11), some_bias(scale=0.1)
    step, place = built.step(F32)
    state, t, l = place(jax.tree.map(jnp.copy, params), tokens, labels)
    state = state[:4] + (jax.tree.map(jnp.copy, bias),)
    state, aux = step(state, t, l)
    aux = jax.device_get(aux)
    wrt = kit.checked(KIT_CFG)
    got = {**kit.compared(kit.step_stats(aux, jax.device_get(state[4]),
                                         KIT_CFG), KIT_CFG, wrt),
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
    # the tied matrix is compared entry by entry at its head-side rows
    at = wrt.index("embed")
    read = np.asarray(aux["embed_probe_read"])
    assert 0 < read.sum() < read.size
    assert np.all(want["grad_probe"][at][read] == 0) \
        and np.any(want["grad_probe"][at][~read] != 0)
    for wrong in kit.WRONG:
        assert max(units(whole(wrong=wrong), want).values()) > 1, wrong
    for variant, part in (("bf16", "router_logits"), ("conv_bf16", "conv_y"),
                          ("bias_in_weights", "router_weights"),
                          ("softmax", "router_scores"),
                          ("untied", "head_rows"), ("no_rope", "rope_qk")):
        assert units(parts(variant), want)[part] > 1, variant
    assert set(kit.PART_CONTROLS) == {"bf16", "conv_bf16", "bias_in_weights",
                                      "softmax", "untied", "no_rope"}


def test_the_kit_counts_the_published_steps_operations(kit):
    cfg = kit.load_config(CONFIG)
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 32
    per = kit.matmul_params_per_token(cfg)
    assert per["conv_proj"] == 2048 * 6144 + 2048 * 2048
    assert per["attn_proj"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert per["dense"] == 3 * 2048 * 7168
    assert per["experts_mean"] == 3 * 2048 * 1792 * 4 * 8 / 32
    assert per["head"] == 2048 * 16384
    total = 4 * per["conv_proj"] + 2 * per["attn_proj"] + per["dense"] \
        + 5 * (per["router"] + per["experts_mean"]) + per["head"]
    assert abs(total / 1e6 - 221.05) < 0.01
    flops = kit.step_flops(cfg)
    assert abs(flops["step"] / 1e12 - 25.0) < 0.05
    assert flops["flash_forward"] == 2 * 32 * 2 * 64 * 8192 * 8192 * 2
    assert abs(flops["conv_proj"] / 1e12 - 6.6) < 0.05 \
        and abs(flops["experts"] / 1e12 - 5.4) < 0.05
    assert sum(kit.leaf_sizes(cfg).values()) == 606_456_064
