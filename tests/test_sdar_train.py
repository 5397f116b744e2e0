"""SDAR-30B-A3B's block-diffusion training step on the normal path
(``parallel/train.py``'s model path under ``layer_types``: every layer
``block_diffusion_attention``: QK-normed attention over a noisy and a clean
copy of every sequence under block diffusion's mask; a softmax router, no
shared expert; the step's noise and the masked rows' weighted loss in
``parallel/objective.py``) against the plain reference
(``parallel/sdar_reference.py``: the (2L, 2L) mask written out, dense masked
softmax) at small widths on seeded random weights: hidden 64; 8 query heads
of 16 on 2 key-value heads; 64 tokens in blocks of 4, tiles of 16; 16
experts of width 24, top 3; held here: 4 layers, 4 experts (share 1 of 4),
64 of 256 ids, the mask token the last.  Float32 compute meets the
reference at rtol 1e-5."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import sdar_reference
from ompi_tpu.parallel import (attention, config, layers, model, objective,
                               train)

import built

ref = built.programs(sdar_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = os.path.join(BENCH, "configs", "sdar-30b-a3b-train-1chip.json")
PUBLISHED = dict(
    hidden_size=64, intermediate_size=24, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, num_experts=16,
    num_experts_per_tok=3, vocab_size=256,
    layer_types=("block_diffusion_attention",) * 8, moe_intermediate_size=24,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6, block_length=4,
    mask_token_here=63)
SHARE = dict(layers_here=4, first_layer_here=0, experts_here=4,
             expert_share=1, vocab_here=64, mtp_here=0)
TRAIN = dict(seq_len=64, micro_batch=2, attn_block=16, loss_block_rows=16,
             lr=1e-2, aux_loss_coef=0.001, z_loss_coef=0.0, noise_seed=7,
             t_min=0.001)
F32 = config.ModelConfig(compute_dtype="float32", **PUBLISHED, **SHARE,
                        **TRAIN)
NAMES = train.leaf_names(F32)
CLOSE = dict(rtol=1e-5, atol=1e-6)


def batch_of(seed, vocab=63, cfg=F32):
    """(x0, labels): text ids only; the two ids behind x0 key the noise."""
    ids = np.random.default_rng(seed).integers(
        0, vocab, (cfg.micro_batch, cfg.seq_len + 2)).astype(np.int32)
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def spread_params(cfg, seed):
    """Parameters drawn as ``init_model_params`` would, the matrices wide
    enough (0.2) that the routers' scores spread and no two near-tie."""
    return train.init_model_params(
        dataclasses.replace(cfg, init_std=0.2), seed)


def layer_of(cfg, seed=5):
    one = dataclasses.replace(cfg, init_std=0.3, layers_here=1)
    (group,) = train.init_model_params(one, seed)["layers"].values()
    assert {k: v.shape[1:] for k, v in group["bd_moe"].items()} \
        == train.pattern_layer_shapes(cfg)["bd_moe"]
    return jax.tree.map(lambda a: a[0], group["bd_moe"])


def close(got, want, **kw):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **{**CLOSE, **kw})


def near(got, want, rel=2e-5, err_msg=""):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rel * 10, err_msg=err_msg,
                               atol=rel * max(1e-30, np.abs(want).max()))


#: the reference's gradients compiled (cfg, the variant and the terms
#: static): nothing rests on what the compiler fuses there
ref_grads = jax.jit(ref.grads, static_argnames=("cfg", "wrong", "terms"))


# -- the configuration --------------------------------------------------------
def test_the_published_file_loads_as_block_diffusion():
    cfg = config.load_model_config(CONFIG)
    assert cfg.pattern_here == "BBBB" and cfg.segments == (("B", 4, 0),)
    assert (cfg.block_length, cfg.mask_token_here, cfg.noise_seed,
            cfg.t_min) == (4, 18991, 20251006, 0.001)
    assert (cfg.hidden_size, cfg.head_width, cfg.num_attention_heads,
            cfg.n_kv_heads_here, cfg.expert_width, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.n_experts_here, cfg.vocab_rows) \
        == (2048, 128, 32, 4, 768, 128, 8, 16, 18992)
    assert cfg.rope_theta == 1e6 and cfg.qk_norm and cfg.routes_to_held \
        and not cfg.n_shared_experts and cfg.seq_len == 8192
    kinds = model.layer_kinds(cfg)
    assert kinds["bd_moe"].operator is attention.DIFFUSED \
        and kinds["bd_moe"].letter == "B"
    shapes = train.model_param_shapes(cfg)
    assert sum(int(np.prod(train._leaf(shapes, p)))
               for _, p in train.leaf_names(cfg)) == 456_346_624


@pytest.mark.parametrize("change,match", [
    (dict(model_type="qwen3_moe"), "grouped-query|block_length"),
    (dict(block_length=0), "block_length"),
    (dict(mlp_only_layers=[3]), "mlp_only_layers"),
    (dict(sliding_window=4096), "sdar_moe|window"),
], ids=["another-model-type", "no-block-length", "dense-layers", "a-window"])
def test_a_file_this_path_cannot_run_is_refused_by_name(tmp_path, change,
                                                        match):
    import json

    with open(CONFIG, encoding="utf-8") as f:
        body = json.load(f)
    body.update(change)
    path = tmp_path / "changed.json"
    path.write_text(json.dumps(body))
    with pytest.raises(NotImplementedError, match=match):
        config.load_model_config(str(path))


@pytest.mark.parametrize("change", [
    dict(seq_len=66), dict(mask_token_here=64), dict(t_min=0.0),
    dict(layer_types=("block_diffusion_attention", "full_attention") * 4),
    dict(tie_word_embeddings=True), dict(block_length=0)],
    ids=["no-whole-blocks", "mask-token-outside", "t-min-zero",
         "a-mixed-pattern", "a-tied-head", "no-block-length"])
def test_a_configuration_block_diffusion_cannot_train_is_refused(change):
    with pytest.raises(NotImplementedError, match="block_length"):
        dataclasses.replace(F32, **change)


# -- RoPE at given positions --------------------------------------------------
def test_rope_without_positions_is_bit_for_bit_what_it_was():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 24, 16))
    plain = lambda x: layers.rope(x, 1e6)
    assert str(jax.make_jaxpr(plain)(x)) == str(jax.make_jaxpr(
        lambda x: layers.rope(x, 1e6, None, None))(x))
    np.testing.assert_array_equal(
        layers.rope(x, 1e6), layers.rope(x, 1e6, positions=jnp.arange(24)))
    part = layers.rope(x, 1e6, 8)
    np.testing.assert_array_equal(
        part, layers.rope(x, 1e6, 8, positions=jnp.arange(24)))


def test_rope_at_repeated_positions_turns_both_halves_alike():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 24, 16))
    both = layers.rope(x, 1e6, positions=jnp.tile(jnp.arange(12), 2))
    np.testing.assert_array_equal(both[:, :, :12],
                                  layers.rope(x[:, :, :12], 1e6))
    np.testing.assert_array_equal(both[:, :, 12:],
                                  layers.rope(x[:, :, 12:], 1e6))


# -- the noise ----------------------------------------------------------------
def test_the_noise_is_the_references_bit_for_bit():
    tokens, labels = batch_of(1)
    levels, masked = jax.jit(lambda t, l: objective.block_diffusion_noise(
        t, l, F32))(tokens, labels)
    want_levels, want_masked = ref.noise(tokens, labels, F32)    # op by op
    np.testing.assert_array_equal(levels, want_levels)
    np.testing.assert_array_equal(masked, want_masked)
    assert levels.shape == (2, 16) and masked.shape == (2, 64)
    assert float(levels.min()) >= 0.001 and float(levels.max()) < 1.0
    # the level's product in 64-bit integers, as the rule is written
    base = jax.random.PRNGKey(F32.noise_seed)
    for row in range(2):
        key = jax.random.fold_in(jax.random.fold_in(
            base, int(labels[row, -2])), int(labels[row, -1]))
        k_c = np.asarray(jax.random.bits(jax.random.fold_in(key, 0), (16,),
                                         jnp.uint32) >> 8).astype(np.uint64)
        m = round(0.001 * 2 ** 24)
        q_c = m + ((2 ** 24 - m) * k_c >> np.uint64(24))
        np.testing.assert_array_equal(
            np.asarray(levels[row]), (q_c / 2 ** 24).astype(np.float32))


def test_two_batches_draw_different_noise_and_one_repeats():
    tokens, labels = batch_of(2)
    draw = jax.jit(lambda t, l: objective.block_diffusion_noise(t, l, F32))
    first, again = draw(tokens, labels), draw(tokens, labels)
    np.testing.assert_array_equal(first[0], again[0])
    np.testing.assert_array_equal(first[1], again[1])
    other = draw(tokens, labels.at[:, -1].add(1))
    assert not np.array_equal(first[0], other[0]) \
        and not np.array_equal(first[1], other[1])
    # the two sequences of one batch hold different spare ids too
    assert not np.array_equal(first[0][0], first[0][1])
    seeded = jax.jit(lambda t, l: objective.block_diffusion_noise(
        t, l, dataclasses.replace(F32, noise_seed=8)))(tokens, labels)
    assert not np.array_equal(first[1], seeded[1])


def test_the_masked_share_of_a_block_follows_its_level():
    """Over many blocks a token is masked with its block's own probability:
    blocks of 64 tokens, by their level's third."""
    cfg = dataclasses.replace(F32, block_length=64, seq_len=4096,
                              attn_block=64, micro_batch=1)
    shares = [[], [], []]
    for seed in range(8):
        ids = np.random.default_rng(seed).integers(0, 63, (1, 4098))
        levels, masked = objective.block_diffusion_noise(
            jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:]), cfg)
        share = np.asarray(masked).reshape(64, 64).mean(-1)
        for t, got in zip(np.asarray(levels)[0], share):
            shares[min(2, int(3 * t))].append(got - t)
            assert abs(got - t) < 5 * np.sqrt(t * (1 - t) / 64) + 1e-9
    for third in shares:
        assert len(third) > 100 and abs(np.mean(third)) < 0.02


# -- the sublayer -------------------------------------------------------------
def test_the_block_diffusion_sublayer_is_the_references():
    """Output and the gradients through every leaf of the sublayer, over a
    noisy and a clean copy of two sequences."""
    p = layer_of(F32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))
    run = attention.DIFFUSED.run
    y, stats, seen = jax.jit(lambda p, x: run(p, x, F32, interpret=True))(
        p, x)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref.attention(p, x, F32))(p, x)
    close(y, want, rtol=1e-4, atol=1e-5)
    assert stats == {} and set(seen) == set(attention.DIFFUSED.reports(F32))
    assert seen["bd_k_seq"].shape == (256, 16) \
        and seen["attn_qk"].shape == (256, 32)
    ours = lambda p, x: jnp.sum(run(p, x, F32, interpret=True)[0] ** 2)
    theirs = lambda p, x: jnp.sum(ref.attention(p, x, F32) ** 2)
    g_got = jax.jit(jax.grad(ours, argnums=(0, 1)))(p, x)
    with jax.default_matmul_precision("highest"):
        g_want = jax.jit(jax.grad(theirs, argnums=(0, 1)))(p, x)
    for leaf in attention.DIFFUSED.shapes(F32):
        near(g_got[0][leaf], g_want[0][leaf], rel=1e-4, err_msg=leaf)
    near(g_got[1], g_want[1], rel=1e-4)


@pytest.mark.parametrize("wrong", ["causal", "leak"])
def test_a_wrong_mask_differs(wrong):
    p = layer_of(F32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))
    y = jax.jit(lambda p, x: attention.DIFFUSED.run(
        p, x, F32, interpret=True)[0])(p, x)
    with jax.default_matmul_precision("highest"):
        right = ref.attention(p, x, F32)
        other = ref.attention(p, x, F32, wrong)
    off = float(jnp.abs(other - right).max())
    assert off > 1e-2 and float(jnp.abs(y - right).max()) < 1e-3 * off
    if wrong == "leak":     # the clean half is untouched by the leak
        np.testing.assert_allclose(other[:, 64:], right[:, 64:], atol=1e-6)
        assert float(jnp.abs(other[:, :64] - right[:, :64]).max()) > 1e-2


# -- the leak test ------------------------------------------------------------
def _noisy_logits(params, ids):
    """The noisy half's logits of the rows ``ids`` (b, 2L) through the
    program's own embedding, layers, final norm and head."""
    from ompi_tpu.parallel.layers import matmul, rmsnorm_gain

    x = params["embed"][ids]
    group = params["layers"]["l0"]["bd_moe"]
    for i in range(F32.layers_here):
        x, _, _ = model.decoder_layer(
            jax.tree.map(lambda a: a[i], group), x, F32, interpret=True,
            kind="bd_moe")
    half = ids.shape[1] // 2
    h = rmsnorm_gain(x[:, :half], params["final_norm"], F32.rms_norm_eps)
    return matmul(h, params["head"], "float32")


@pytest.mark.parametrize("block", [0, 5, 15])
def test_a_noisy_blocks_logits_see_its_own_noise_and_the_clean_past(block):
    """**The leak test.**  The logits of noisy block c are a function of
    xt's block c and x0's blocks before c and of nothing else: changing x0
    in block c or later, or xt outside block c, leaves them bit for bit;
    changing xt inside the block, or x0 before it, moves them."""
    params = spread_params(F32, 9)
    rng = np.random.default_rng(block)
    ids = jnp.asarray(rng.integers(0, 64, (2, 128)).astype(np.int32))
    run = jax.jit(_noisy_logits)
    base = run(params, ids)
    lo, hi = 4 * block, 4 * block + 4
    own = lambda logits: np.asarray(logits[:, lo:hi])
    other = rng.integers(0, 64, (2, 128)).astype(np.int32)
    # x0 (the clean half) in block c and later; xt outside block c
    later = ids.at[:, 64 + lo:].set(other[:, 64 + lo:])
    outside = ids.at[:, :lo].set(other[:, :lo]).at[:, hi:64].set(
        other[:, hi:64])
    both = later.at[:, :lo].set(other[:, :lo]).at[:, hi:64].set(
        other[:, hi:64])
    for changed in (later, outside, both):
        assert not np.array_equal(np.asarray(changed), np.asarray(ids))
        np.testing.assert_array_equal(own(run(params, changed)), own(base))
    inside = ids.at[:, lo].set((ids[:, lo] + 1) % 64)
    assert not np.array_equal(own(run(params, inside)), own(base))
    if block:
        before = ids.at[:, 64 + lo - 1].set((ids[:, 64 + lo - 1] + 1) % 64)
        assert not np.array_equal(own(run(params, before)), own(base))


# -- the share ----------------------------------------------------------------
def test_the_shares_routed_parts_sum_to_the_whole_layer():
    """**The share test.**  The four shares' routed parts summed, what
    every chip computes alike (attention, the residual stream) counted
    once, equal the uncut reference's layer, over the 2L rows."""
    whole = dataclasses.replace(F32, experts_here=0, expert_share=0,
                                layers_here=1)
    p = jax.tree.map(lambda a: a[0], train.init_model_params(
        dataclasses.replace(whole, init_std=0.3), 5)["layers"]["l0"][
            "bd_moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))
    with jax.default_matmul_precision("highest"):
        y = x + ref.attention(p, x, whole)
        want = y + ref.experts(p, y, whole)[0]
    parts, loads = [], []
    for share in range(4):
        cfg = dataclasses.replace(F32, expert_share=share, layers_here=1)
        held = {**p, **{k: p[k][4 * share:4 * share + 4]
                        for k in ("gate", "up", "down")}}
        out, st, _ = jax.jit(lambda q, x, cfg=cfg: model.decoder_layer(
            q, x, cfg, interpret=True, kind="bd_moe"))(held, x)
        # what every share computes alike: the stream behind attention
        parts.append(out - y)
        loads.append(np.asarray(st["slots"]))
    close(y + sum(parts), want, rtol=1e-4, atol=1e-5)
    for load in loads[1:]:
        np.testing.assert_array_equal(load, loads[0])
    assert loads[0].sum() == 2 * 128 * 3


# -- the loss -----------------------------------------------------------------
def test_the_weighted_head_is_the_plain_one_with_weights_of_one():
    h = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (32, 48))
    labels = jnp.arange(64) % 48
    plain = lambda h, w: objective.head_cross_entropy(h, w, labels, 16,
                                                      "float32")
    ones = lambda h, w: objective.head_cross_entropy(
        h, w, labels, 16, "float32", jnp.ones((64,)))
    close(plain(h, w)[0], ones(h, w)[0])
    weights = jnp.where(jnp.arange(64) % 3 == 0, 2.5, 0.0)
    weighted = lambda h, w: objective.head_cross_entropy(
        h, w, labels, 16, "float32", weights)[0]
    logp = lambda h, w: jax.nn.log_softmax(jnp.dot(
        h, w, precision="highest"), -1)
    want = lambda h, w: -jnp.sum(weights * jnp.take_along_axis(
        logp(h, w), labels[:, None], -1)[:, 0])
    close(weighted(h, w), want(h, w), rtol=1e-5)
    for got, ref_ in zip(jax.grad(weighted, (0, 1))(h, w),
                         jax.grad(want, (0, 1))(h, w)):
        near(got, ref_, rel=1e-5)
    # a row of weight zero adds nothing to either gradient
    dh = jax.grad(weighted)(h, w)
    assert not np.any(np.asarray(dh)[np.asarray(weights) == 0])
    # and without weights the program is the call's without the argument
    text = lambda fn: str(jax.make_jaxpr(fn)(h, w))
    assert text(plain) == text(lambda h, w: objective.head_cross_entropy(
        h, w, labels, 16, "float32", None))
    assert "otpu_bd_loss" not in text(plain)


def test_the_objective_is_the_references():
    tokens, labels = batch_of(0)
    params = spread_params(F32, 3)
    total, aux = jax.jit(lambda p: objective.model_loss(
        p, tokens, labels, F32, interpret=True, n_global=tokens.size))(
            params)
    (want, (ce, lb, loads, levels, masked)), _ = ref_grads(
        params, tokens, labels, F32)
    close(total, want)
    close(aux["losses"], [want, ce, lb, 0.0])
    close(aux["loads"], loads)
    np.testing.assert_array_equal(aux["bd_mask"] != 0, masked)
    np.testing.assert_array_equal(aux["bd_levels"], levels)
    assert float(aux["bd_masked"]) == float(np.asarray(masked).sum())
    weight = np.where(masked, 1.0 / np.repeat(np.asarray(levels), 4, 1), 0.0)
    close(aux["bd_weight_sum"], weight.sum(), rtol=1e-6)
    assert aux["rows"].shape == (128, 2) and aux["experts"].shape \
        == (4, 256, 3)
    assert float(lb) > 0


@pytest.mark.parametrize("wrong", ref.WRONG)
def test_a_wrong_model_differs_from_the_step(wrong):
    """A causal mask, a leaking mask, an unweighted loss and masked rows at
    a fixed rate each lie outside what the program meets the reference
    by."""
    tokens, labels = batch_of(0)
    params = spread_params(F32, 3)
    total, aux = jax.jit(lambda p: objective.model_loss(
        p, tokens, labels, F32, interpret=True, n_global=tokens.size))(
            params)
    (right, _), g_right = ref_grads(params, tokens, labels, F32)
    (other, _), g_other = ref_grads(params, tokens, labels, F32, wrong)
    assert abs(float(total) - float(right)) < 2e-5 * abs(float(right))
    assert abs(float(other) - float(right)) > 1e-3 * abs(float(right))
    wq = ("layers", "l0", "bd_moe", "wq")
    off = np.abs(np.asarray(train._leaf(g_other, wq))
                 - np.asarray(train._leaf(g_right, wq))).max()
    assert off > 1e-2 * np.abs(np.asarray(train._leaf(g_right, wq))).max()
