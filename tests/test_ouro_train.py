"""Ouro-2.6B's looped training step on the normal path (``parallel/train.py``'s
model path under ``layer_types``: every layer ``full_attention`` without a
QK-norm, then a dense SwiGLU, each sublayer in a sandwich of norms; the walk
run ``total_ut_steps`` times over one set of leaves, the exit gate and the
expected loss in ``parallel/objective.looped_loss``) against the plain
reference (``parallel/ouro_reference.py``: the passes as a Python loop over
one dictionary of leaves, products of sigmoids, the loss as its definition)
at small widths on seeded random weights: hidden 64, 4 heads of 16, a
feed-forward of 96, 256 ids, 2 layers, 2 and 4 passes, 2 rows of 64 tokens.
Float32 compute meets the reference at rtol 1e-5."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import ouro_reference
from ompi_tpu.parallel import (attention, config, experts, model, objective,
                               train)
from ompi_tpu.runtime import trace

import built

ref = built.programs(ouro_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = os.path.join(BENCH, "configs", "ouro-2.6b-train-1chip.json")
SMALL = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
             num_key_value_heads=4, intermediate_size=96, vocab_size=256,
             layers_here=2, seq_len=64, micro_batch=2, attn_block=16,
             loss_block_rows=32, lr=1e-2, warmup_steps=1,
             compute_dtype="float32")
CLOSE = dict(rtol=1e-5, atol=1e-6)


def small(**change) -> config.ModelConfig:
    """The cell's file at the tests' widths."""
    return config.load_model_config(CONFIG, **{**SMALL, **change})


F32 = small()                       # four passes
NAMES = train.leaf_names(F32)


def batch_of(seed, cfg=F32):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (cfg.micro_batch, cfg.seq_len + 2)
    ).astype(np.int32)
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def spread_params(cfg, seed, gate=0.3):
    """Parameters drawn as ``init_model_params`` would, the matrices at 0.2
    and the gate drawn too, so that the four passes' p differ a row."""
    params = train.init_model_params(
        dataclasses.replace(cfg, init_std=0.2), seed)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 100))
    params["exit_gate"] = {
        "w": gate * jax.random.normal(k1, (cfg.hidden_size,), jnp.float32),
        "b": gate * jax.random.normal(k2, (1,), jnp.float32)}
    return params


def close(got, want, **kw):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **{**CLOSE, **kw})


def near(got, want, rel=2e-5, err_msg=""):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rel * 10, err_msg=err_msg,
                               atol=rel * max(1e-30, np.abs(want).max()))


def loss_of(cfg, tokens, labels):
    return lambda p: objective.model_loss(
        p, tokens, labels, cfg, interpret=True, n_global=tokens.size)


ref_grads = jax.jit(ref.grads, static_argnums=3)


# -- the file and the tree ------------------------------------------------------
def test_the_cells_file_loads_as_a_looped_dense_model():
    cfg = config.load_model_config(CONFIG)
    assert cfg.pattern_here == "aaaa" and cfg.segments == (("a", 4, 0),) \
        and cfg.total_ut_steps == 4 and cfg.exit_beta == 0.1 \
        and cfg.sandwich_norm and not cfg.qk_norm and cfg.num_experts == 0 \
        and cfg.n_sparse_here == 0 and cfg.vocab_rows == 49152 \
        and cfg.rope_kinds == ("full_attention",) \
        and (cfg.seq_len, cfg.micro_batch) == (4096, 2)
    kind = model.layer_kinds(cfg)["attn_dense"]
    assert kind.operator is attention.FULL \
        and kind.feed_forward is experts.DENSE and not kind.routes
    # the tree holds each leaf once: the count at the published widths
    shapes = jax.eval_shape(lambda: built.params(cfg, 0))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 406_884_353
    assert [n for n, _ in train.leaf_names(cfg)][-3:] == [
        "head", "exit_gate.w", "exit_gate.b"]
    assert shapes["layers"]["l0"]["attn_dense"]["ln1_post"].shape == (4, 2048)
    for name in ("l0.attn_dense.ln1_post", "l0.attn_dense.ln2_post",
                 "exit_gate.w", "exit_gate.b", "final_norm"):
        assert not train.is_decayed(name), name
    assert train.is_decayed("l0.attn_dense.down")


def test_the_gate_starts_at_zero_and_the_second_norms_at_one():
    params = built.params(F32, 1)
    assert not np.any(np.asarray(params["exit_gate"]["w"])) \
        and not np.any(np.asarray(params["exit_gate"]["b"]))
    group = params["layers"]["l0"]["attn_dense"]
    assert np.all(np.asarray(group["ln1_post"]) == 1) \
        and np.all(np.asarray(group["ln2_post"]) == 1)
    tokens, labels = batch_of(0)
    _, aux = jax.jit(loss_of(F32, tokens, labels))(params)
    close(aux["exit_mean"], [0.5, 0.25, 0.125, 0.125])


@pytest.mark.parametrize("file,change,match", [
    ("sdar-30b-a3b-train-1chip", dict(total_ut_steps=4), "total_ut_steps"),
    ("keye-vl2-30b-a3b-train-1chip", dict(train=dict(exit_beta=0.1)),
     "exit_beta"),
    ("ouro-2.6b-train-1chip", dict(total_ut_steps=0), "ouro"),
    ("ouro-2.6b-train-1chip", dict(num_experts=8), "ouro"),
    ("ouro-2.6b-train-1chip", dict(layer_types=["sliding_attention"] * 48),
     "ouro"),
], ids=["steps-elsewhere", "beta-elsewhere", "no-pass", "experts",
        "a-window"])
def test_a_file_this_path_cannot_run_is_refused_by_name(tmp_path, file,
                                                        change, match):
    with open(os.path.join(BENCH, "configs", file + ".json"),
              encoding="utf-8") as f:
        body = json.load(f)
    for key, value in change.items():
        body[key] = {**body[key], **value} if key == "train" else value
    path = tmp_path / "changed.json"
    path.write_text(json.dumps(body))
    with pytest.raises(NotImplementedError, match=match):
        config.load_model_config(str(path))


@pytest.mark.parametrize("change", [
    dict(total_ut_steps=0), dict(qk_norm=True), dict(num_experts=4),
    dict(tie_word_embeddings=True), dict(first_k_dense_replace=1),
    dict(exit_beta=-0.1)],
    ids=["a-norm-without-a-loop", "a-qk-norm", "experts", "a-tied-head",
         "a-sparse-layer", "a-negative-beta"])
def test_a_configuration_the_loop_cannot_train_is_refused(change):
    with pytest.raises(NotImplementedError, match="total_ut_steps"):
        dataclasses.replace(F32, **change)


def test_the_scopes_and_counters_are_named():
    from ompi_tpu.runtime import spc

    spc.init()
    loop = ("otpu_loop_pass", "otpu_exit_gate", "otpu_exit_loss")
    at = trace.STEP_SCOPES.index(loop[0])
    assert trace.STEP_SCOPES[at:at + 3] == loop
    before = {k: spc.read(k) for k in (
        "loop_built", "loop_passes", "loop_layers_held",
        "loop_layer_applications", "loop_head_rows")}
    tokens, labels = batch_of(0)
    text = jax.jit(loss_of(F32, tokens, labels)).lower(
        built.params(F32, 0)).as_text(debug_info=True)
    for scope in loop:
        assert scope in text, scope
    assert "otpu_bd_loss" not in text
    # lowering moves no counter: the step's plan counts the walk, from
    # the shapes, and a built step's first call feeds them
    assert {k: spc.read(k) for k in before} == before
    counts = train.plan_of(F32, *tokens.shape)["counts"]
    assert {k: counts[k] for k in before} == objective.loop_counts(
        F32, *tokens.shape) == dict(
            loop_built=1, loop_passes=4, loop_layers_held=2,
            loop_layer_applications=8, loop_head_rows=512)
    assert counts["attn_built"] == 8 and counts["attn_qk_built"] == 16


# -- the objective --------------------------------------------------------------
@pytest.mark.parametrize("passes,beta", [(4, 0.1), (2, 0.1), (4, 0.0)],
                         ids=["four", "two", "no-entropy"])
def test_the_objective_and_every_gradient_are_the_references(passes, beta):
    cfg = small(total_ut_steps=passes, exit_beta=beta)
    tokens, labels = batch_of(0)
    params = spread_params(cfg, 3)
    (total, aux), g = jax.jit(jax.value_and_grad(
        loss_of(cfg, tokens, labels), has_aux=True))(params)
    (want, (by_pass, expected, bonus, p)), g_want = ref_grads(
        params, tokens, labels, cfg)
    close(total, want)
    close(aux["losses"], [want, *by_pass, expected, bonus])
    assert aux["losses"].shape == (passes + 3,)
    assert (float(bonus) > 0) == (beta > 0)
    at = objective.sample_rows(tokens.size)
    close(aux["exit_p"], np.asarray(p).reshape(passes, -1)[:, at].T)
    close(aux["exit_mean"], np.asarray(p).mean(axis=(1, 2)))
    assert aux["rows"].shape == (128, passes, 2) \
        and aux["loads"].shape == (0, 0) \
        and aux["experts"].shape == (0, 128, 0) \
        and aux["sample"]["head_in"].shape == (16, passes, 64) \
        and aux["sample"]["attn_qk"].shape == (2 * passes, 16, 32)
    for name, path in train.leaf_names(cfg):
        near(train._leaf(g, path), train._leaf(g_want, path), err_msg=name)
    # the gate learns through both ways: p's share of a row's loss and the
    # entropy (beta 0 leaves the first alone)
    assert np.abs(np.asarray(g["exit_gate"]["w"])).max() > 1e-4


def test_a_leafs_gradient_is_the_sum_over_the_passes():
    """Against the same model with four copies of the layers' leaves,
    unshared, a copy a pass: the four copies' gradients added."""
    tokens, labels = batch_of(1)
    params = spread_params(F32, 4)
    _, g = jax.jit(jax.value_and_grad(loss_of(F32, tokens, labels),
                                      has_aux=True))(params)

    def unshared(copies):
        x, hs = params["embed"][tokens], []
        for layers in copies:
            for p in ref.layers_of({"layers": layers}, F32):
                x = ref.layer(p, x, F32)
            x = ref._norm(x, params["final_norm"], F32.rms_norm_eps)
            hs.append(x)
        hs = jnp.stack(hs)
        logp = jax.nn.log_softmax(hs @ params["head"], -1)
        ce = -jnp.take_along_axis(logp, jnp.broadcast_to(
            labels[None, :, :64, None], hs.shape[:3] + (1,)), -1)[..., 0]
        p = ref.exit_probabilities(
            hs @ params["exit_gate"]["w"] + params["exit_gate"]["b"][0])
        return jnp.mean(jnp.sum(p * ce, 0)) - F32.exit_beta * jnp.mean(
            -jnp.sum(p * jnp.log(p), 0))

    with jax.default_matmul_precision("highest"):
        by_copy = jax.jit(jax.grad(unshared))([params["layers"]] * 4)
    summed = jax.tree.map(lambda *gs: sum(gs), *by_copy)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(g["layers"]),
            jax.tree.leaves(summed)):
        near(got, want, err_msg=str(path))
    # and no pass's share is nothing
    for copy in by_copy:
        assert np.abs(np.asarray(
            copy["l0"]["attn_dense"]["wq"])).max() > 1e-6


def test_one_pass_is_the_plain_mean_cross_entropy_bit_for_bit():
    cfg = small(total_ut_steps=1)
    tokens, labels = batch_of(2)
    params = spread_params(cfg, 5)
    (total, aux), g = jax.jit(jax.value_and_grad(
        loss_of(cfg, tokens, labels), has_aux=True))(params)
    x = params["embed"][tokens]
    run = lambda layer, x: model.decoder_layer(
        layer, x, cfg, interpret=True, kind="attn_dense")[0]
    for i in range(2):
        x = run(jax.tree.map(lambda a: a[i],
                             params["layers"]["l0"]["attn_dense"]), x)
    h = objective.rmsnorm_gain(x, params["final_norm"], cfg.rms_norm_eps)
    plain, _ = objective.head_cross_entropy(
        h.reshape(128, -1), params["head"], labels[:, :64].reshape(128), 32,
        "float32")
    assert float(total) == float(plain) / 128
    # the total is the expected cross-entropy, and no entropy is taken off
    np.testing.assert_array_equal(
        np.asarray(aux["losses"])[[0, 2, 3]],
        np.asarray([total, total, 0.0], np.float32))
    close(aux["losses"][1], total, rtol=1e-6)   # the rows' own mean
    assert np.all(np.asarray(aux["exit_p"]) == 1.0) \
        and np.all(np.asarray(aux["sample"]["exit_entropy"]) == 0.0)
    assert not np.any(np.asarray(g["exit_gate"]["w"])) \
        and not np.any(np.asarray(g["exit_gate"]["b"]))


@pytest.mark.parametrize("at", [40.0, -40.0, 1e4, -1e4])
def test_the_exit_distribution_sums_to_one_however_far_the_gate_goes(at):
    gate = jnp.full((4, 8), at, jnp.float32).at[:, 1].set(0.0) \
        .at[1, 2].set(-at)
    (p, log_p), dgate = jax.jit(lambda g: (
        objective.exit_distribution(g), jax.jit(jax.grad(lambda g: jnp.sum(
            (lambda p, lp: p * lp)(*objective.exit_distribution(g)))))(g)))(
                gate)
    assert np.all(np.isfinite(p)) and np.all(np.isfinite(log_p)) \
        and np.all(np.isfinite(dgate))
    close(np.sum(p, axis=0), np.ones(8), rtol=1e-6)
    close(p[:, 1], [0.5, 0.25, 0.125, 0.125])
    assert np.all(np.isfinite(np.asarray(p * log_p)))
    if abs(at) == 40.0:
        close(p, ref.exit_probabilities(gate), atol=1e-12)


def test_the_second_norms_gains_scale_the_sublayers_share_of_the_stream():
    """With the two second norms' gains scaled the sublayer's share of the
    stream scales, and the layer's input norm does not see it."""
    params = spread_params(F32, 6)
    layer = jax.tree.map(lambda a: a[0],
                         params["layers"]["l0"]["attn_dense"])
    x = params["embed"][batch_of(3)[0]]
    run = jax.jit(lambda layer: model.decoder_layer(
        layer, x, F32, interpret=True, kind="attn_dense")[0])
    only = lambda leaf, by: {**layer, leaf: layer[leaf] * by}
    base = run(layer)
    attn = run(only("ln2_post", 0.0)) - x        # attention's share alone
    close(run({**only("ln1_post", 3.0), "ln2_post": layer["ln2_post"] * 0.0})
          - x, 3.0 * attn, rtol=1e-5, atol=1e-5)
    ffn = base - x - attn
    close(run(only("ln2_post", 2.0)) - x - attn, 2.0 * ffn, rtol=1e-5,
          atol=1e-5)
    # a sublayer's share is normed: W_o scaled changes nothing
    close(run(only("wo", 5.0)), base, rtol=1e-4, atol=1e-4)
    # and without the flag the same leaves give a plain pre-norm layer
    plain = dataclasses.replace(F32, sandwich_norm=False, total_ut_steps=0,
                                exit_beta=0.0)
    assert "ln1_post" not in train.pattern_layer_shapes(plain)["attn_dense"]
