"""The training step of Keye-VL-2.0-30B-A3B's language model on the normal
path (``parallel/train.py``'s model path under ``layer_types``: every layer
``sparse_attention``: QK-normed attention under DeepSeek-V3.2's learned
selection, an indexer with its own alignment loss; a softmax router, no
shared expert) against the plain reference (``parallel/keye_reference.py``:
a dense score array, ``lax.top_k``, dense masked softmax, the two
detachments as ``stop_gradient``) at small widths on seeded random weights:
hidden 64; 8 query heads of 16 on 2 key-value heads; an indexer of 4 heads
of 8, top 24 of 64 positions in blocks of 16; 16 experts of width 24, top
3; held here: 4 layers, 4 experts (share 1 of 4), 64 of 256 ids.  Float32
compute meets the reference at rtol 1e-5."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import keye_reference
from ompi_tpu.parallel import (attention, config, dsa, model, objective,
                               train)

import built

ref = built.programs(keye_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = os.path.join(BENCH, "configs", "keye-vl2-30b-a3b-train-1chip.json")
PUBLISHED = dict(
    hidden_size=64, intermediate_size=24, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, num_experts=16,
    num_experts_per_tok=3, vocab_size=256,
    layer_types=("sparse_attention",) * 8, moe_intermediate_size=24,
    norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e7, index_heads=4,
    index_head_dim=8, index_topk=24, index_q_chunk=16, index_kv_chunk=16)
SHARE = dict(layers_here=4, first_layer_here=0, experts_here=4,
             expert_share=1, vocab_here=64, mtp_here=0)
TRAIN = dict(seq_len=64, micro_batch=2, attn_block=16, loss_block_rows=16,
             lr=1e-2, aux_loss_coef=0.001, z_loss_coef=0.0,
             index_loss_coef=1.0)
F32 = config.ModelConfig(compute_dtype="float32", **PUBLISHED, **SHARE,
                        **TRAIN)
NAMES = train.leaf_names(F32)
INDEX = ("index_wq", "index_wk", "index_k_norm", "index_k_bias", "index_ww")
CLOSE = dict(rtol=1e-5, atol=1e-6)


def batch_of(seed, vocab=64, cfg=F32):
    ids = np.random.default_rng(seed).integers(
        0, vocab, (cfg.micro_batch, cfg.seq_len + 2)).astype(np.int32)
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def spread_params(cfg, seed):
    """Parameters drawn as ``init_model_params`` would, the matrices wide
    enough (0.2) that the indexer's scores spread and no two near-tie."""
    return train.init_model_params(
        dataclasses.replace(cfg, init_std=0.2), seed)


def layer_of(cfg, seed=5):
    one = dataclasses.replace(cfg, init_std=0.3, layers_here=1)
    (group,) = train.init_model_params(one, seed)["layers"].values()
    assert {k: v.shape[1:] for k, v in group["dsa_moe"].items()} \
        == train.pattern_layer_shapes(cfg)["dsa_moe"]
    return jax.tree.map(lambda a: a[0], group["dsa_moe"])


def close(got, want, **kw):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **{**CLOSE, **kw})


def near(got, want, rel=2e-5, err_msg=""):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rel * 10, err_msg=err_msg,
                               atol=rel * max(1e-30, np.abs(want).max()))


#: the reference's gradients compiled (cfg and the terms static): its dense
#: arrays' many small operations one by one take a test's time, and nothing
#: rests on what the compiler fuses there
ref_grads = jax.jit(ref.grads, static_argnames=("cfg", "terms"))


def unpacked(packed, s):
    return np.unpackbits(np.asarray(packed), axis=-1,
                         bitorder="little")[..., :s] != 0


# -- the sublayer ------------------------------------------------------------------------
def test_the_sparse_attention_sublayer_is_the_references():
    """Output, alignment loss and the selection itself, and the gradients
    of both through every leaf of the sublayer."""
    p = layer_of(F32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    at = objective.sample_rows(128)
    sublayer = jax.jit(lambda p, x: dsa.dsa_attention(
        p, x, F32, interpret=True, at=at))
    y, stats, seen = sublayer(p, x)
    with jax.default_matmul_precision("highest"):
        want, kl, chosen = jax.jit(lambda p, x: ref.attention(p, x, F32))(
            p, x)
    close(y, want, rtol=1e-4, atol=1e-5)
    close(stats["index_kl_sum"], kl, rtol=1e-4)
    got = unpacked(seen["dsa_selection_seq"], 64)
    np.testing.assert_array_equal(got, chosen)
    np.testing.assert_array_equal(
        got.sum(-1), np.broadcast_to(np.minimum(np.arange(64) + 1, 24),
                                     (2, 64)))
    assert seen["dsa_index_at"].shape == (16, 64) \
        and seen["dsa_kl_at"].shape == (16,)
    assert float(np.asarray(seen["dsa_kl_at"]).min()) > 0
    ours = lambda p, x: (lambda y, st, _: jnp.sum(y * y)
                         + st["index_kl_sum"])(*dsa.dsa_attention(
                             p, x, F32, interpret=True))
    theirs = lambda p, x: (lambda y, kl, _: jnp.sum(y * y) + kl)(
        *ref.attention(p, x, F32, chosen))
    g_got = jax.jit(jax.grad(ours, argnums=(0, 1)))(p, x)
    with jax.default_matmul_precision("highest"):
        g_want = jax.jit(jax.grad(theirs, argnums=(0, 1)))(p, x)
    for leaf in g_want[0]:
        if leaf in ("ln2", "router", "gate", "up", "down"):
            continue
        near(g_got[0][leaf], g_want[0][leaf], rel=1e-4, err_msg=leaf)
    near(g_got[1], g_want[1], rel=1e-4)


@pytest.mark.parametrize("control", ["every_key", "top_half", "no_relu"])
def test_a_wrong_selection_or_indexer_differs(control):
    p = layer_of(F32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    y = jax.jit(lambda p, x: dsa.dsa_attention(
        p, x, F32, interpret=True)[0])(p, x)
    if control == "no_relu":
        import unittest.mock

        # op by op: a program traced under the patch would stay the
        # process's ``ref.attention`` at these shapes
        with unittest.mock.patch.object(jax.nn, "relu", lambda a: a):
            wrong = ref.plain.attention(p, x, F32)[0]
    else:
        topk = {"every_key": 64, "top_half": 12}[control]
        wrong = ref.attention(p, x, dataclasses.replace(
            F32, index_topk=topk))[0]
    assert float(jnp.abs(y - wrong).max()) > 1e-3


def test_a_sequence_no_longer_than_topk_attends_to_every_earlier_key():
    """Up to ``index_topk`` positions every key is chosen: the sublayer is
    causal attention (lfm2's form), whatever the indexer holds."""
    cfg = dataclasses.replace(F32, seq_len=16)
    p = layer_of(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64))
    y, _, seen = jax.jit(lambda p, x: dsa.dsa_attention(
        p, x, cfg, interpret=True))(p, x)
    assert unpacked(seen["dsa_selection_seq"], 16).sum() == 2 * 16 * 17 // 2
    plain = dataclasses.replace(
        cfg, layer_types=("full_attention",) * 8, index_topk=0,
        index_heads=0, index_head_dim=0)
    want = attention.FULL.run(p, x, plain, interpret=True)[0]
    close(y, want, rtol=1e-5, atol=1e-6)


def test_the_shares_layer_outputs_add_up_to_the_uncut_layer():
    """The shares of a layer (eight at the published widths; here 4 of 4
    experts each of 16), **attention and indexer counted once**, add up to
    the uncut reference's layer: what the expert-parallel group's exchange
    would make of them."""
    whole = dataclasses.replace(F32, experts_here=0, expert_share=0)
    p = layer_of(whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64))
    with jax.default_matmul_precision("highest"):
        # every chip's alike
        alike = x + jax.jit(lambda p, x: ref.attention(p, x, whole)[0])(p, x)
        want = alike + ref.experts(p, alike, whole)[0]
    total, kls = 0.0, []
    for j in range(4):
        part = dataclasses.replace(F32, experts_here=4, expert_share=j)
        mine = {**p, **{k: p[k][4 * j:4 * j + 4]
                        for k in ("gate", "up", "down")}}
        out, stats, _ = jax.jit(lambda p, x, part=part: model.decoder_layer(
            p, x, part, interpret=True, kind="dsa_moe"))(mine, x)
        total = total + (out - alike)       # a share's routed part
        kls.append(float(stats["index_kl_sum"]))
    close(total + alike, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want - alike).max()) > 1e-3
    assert max(kls) == min(kls) > 0         # the indexer is every chip's


def test_the_layers_are_walked_as_one_run_of_sparse_attention():
    assert F32.pattern_here == "SSSS" and F32.segments == (("S", 4, 0),)
    assert (F32.n_sparse_here, F32.n_routers, F32.head_width,
            F32.shared_width, F32.index_topk) == (4, 4, 16, 0, 24)
    assert F32.routes_to_held
    shapes = train.model_param_shapes(F32)["layers"]["l0"]["dsa_moe"]
    assert shapes["wq"] == (4, 64, 128) and shapes["wk"] == (4, 64, 32)
    assert shapes["index_wq"] == (4, 64, 32) \
        and shapes["index_wk"] == (4, 64, 8) \
        and shapes["index_k_norm"] == shapes["index_k_bias"] == (4, 8) \
        and shapes["index_ww"] == (4, 64, 4) \
        and shapes["gate"] == (4, 4, 64, 24)
    assert set(train.pattern_layer_shapes(F32)) == {"dsa_dense", "dsa_moe"}
    params = built.params(F32, 0)["layers"]["l0"]["dsa_moe"]
    assert float(params["index_k_norm"].min()) == 1.0 \
        and not np.any(np.asarray(params["index_k_bias"]))
    assert not train.is_decayed("l0.dsa_moe.index_k_bias") \
        and not train.is_decayed("l0.dsa_moe.index_k_norm") \
        and train.is_decayed("l0.dsa_moe.index_ww")


# -- the configuration -------------------------------------------------------------------
def test_the_benchmarks_configuration_loads_at_its_published_widths():
    cfg = train.load_model_config(CONFIG)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_width, cfg.rotary_width) \
        == (2048, 32, 4, 128, None)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_width,
            cfg.shared_width, cfg.n_experts_here, cfg.first_expert_here) \
        == (128, 8, 768, 0, 16, 0)
    assert (cfg.scoring_func, cfg.topk_method, cfg.norm_topk_prob,
            cfg.qk_norm, cfg.router_before_attention, cfg.mlp_hidden_act) \
        == ("softmax", "greedy", True, True, False, "silu")
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.index_q_chunk, cfg.index_kv_chunk, cfg.index_loss_coef) \
        == (16, 64, 2048, 512, 512, 1.0)
    assert cfg.layer_types == ("sparse_attention",) * 48 \
        and cfg.pattern_here == "SSSS" and cfg.segments == (("S", 4, 0),)
    assert (cfg.vocab_size, cfg.vocab_rows, cfg.seq_len, cfg.micro_batch,
            cfg.n_mtp_here, cfg.rms_norm_eps, cfg.rope_theta,
            cfg.aux_loss_coef, cfg.z_loss_coef, cfg.attn_block) \
        == (151936, 18992, 16384, 1, 0, 1e-6, 1e7, 0.001, 0.0, 1024)
    shapes = train.model_param_shapes(cfg)
    held = sum(int(np.prod(train._leaf(shapes, path)))
               for _, path in train.leaf_names(cfg))
    assert held == 465_391_104
    with open(CONFIG, encoding="utf-8") as f:
        body = json.load(f)
    assert "465,391,104" in body["arithmetic"] \
        and "465,391,104" in body["reduced_from"]["layers"]
    assert (body["layers_here"], body["experts_here"], body["chips_a_layer"],
            body["vocab_here"]) == (4, 16, 8, 18992)


def test_the_embeddings_rows_alone_are_drawn_at_their_own_width():
    """``embed_init_std`` widens the embedding's rows by its ratio to
    ``init_std``, from the same draw, and moves no other leaf; the
    benchmark's file gives 2.0, and a file that gives none (every other
    model's) draws as before."""
    cfg = train.load_model_config(CONFIG)
    assert (cfg.init_std, cfg.embed_init_std) == (0.02, 2.0)
    assert F32.embed_init_std is None
    plain = built.params(F32, 3)
    wide = train.init_model_params(
        dataclasses.replace(F32, embed_init_std=2.0), 3)
    for name, path in NAMES:
        got, want = train._leaf(wide, path), train._leaf(plain, path)
        if name == "embed":
            close(got, want * (2.0 / F32.init_std), rtol=1e-6)
            assert abs(float(jnp.std(got)) - 2.0) < 0.05
        else:
            assert np.array_equal(np.asarray(got), np.asarray(want)), name


def test_wide_embedding_rows_keep_the_routers_from_choosing_as_one():
    """Why the file gives ``embed_init_std``: with rows as narrow as the
    matrices, what attention adds to every position alike outweighs a
    token's own row and a layer's router sends most tokens to the same
    few experts; with wide rows the fullest expert of the last layer holds
    a fraction of that."""
    cfg = dataclasses.replace(F32, seq_len=256, micro_batch=1, vocab_here=256,
                              index_topk=64)
    tokens, _ = batch_of(2, vocab=256, cfg=cfg)

    def fullest(embed_std):
        params = train.init_model_params(
            dataclasses.replace(cfg, embed_init_std=embed_std), 7)
        _, loads, *_ = ref.forward(params, tokens, cfg)
        return int(np.asarray(loads)[-1].max())

    narrow, wide = fullest(None), fullest(2.0)
    assert narrow > 0.6 * cfg.seq_len and wide < 0.5 * narrow, (narrow, wide)


def test_the_files_published_keys_are_the_catalogs():
    """Every number of the catalog's ``config`` under the same key, the
    nested groups whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"]
    with open(CONFIG, encoding="utf-8") as f:
        body = json.load(f)
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert body[key] == value, key


def _file_with(tmp_path, **change):
    with open(CONFIG, encoding="utf-8") as f:
        body = json.load(f)
    for key, value in change.items():
        if value is None:
            body.pop(key, None)
        else:
            body[key] = value
    path = tmp_path / "changed.json"
    path.write_text(json.dumps(body))
    return str(path)


SCALED = {"rope_type": "yarn", "factor": 4.0, "mrope_section": [16, 24, 24]}
REFUSED_FILES = [
    ({"rope_scaling": SCALED}, "rope_scaling"),
    ({"rope_scaling": {"rope_type": "default", "type": "default",
                       "mrope_section": [16, 24, 16]}}, "rope_scaling"),
    ({"rope_scaling": {"rope_type": "default"}}, "rope_scaling"),
    ({"rope_scaling": {"rope_type": "linear", "factor": 2.0}},
     "rope_scaling"),
    ({"sliding_window": 4096}, "sa_config"),
    ({"use_sliding_window": True}, "sa_config"),
    ({"kv_lora_rank": 512}, "sa_config"),
    ({"hybrid_override_pattern": "M*E"}, "sa_config"),
    ({"model_type": "qwen3_moe"}, "sa_config"),
    ({"layer_types": ["full_attention"] * 48}, "sa_config"),
    ({"sa_config": None}, "sa_config"),
    ({"sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                    "indexer_num_kv_heads": 2, "topk": 2048}}, "sa_config"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
]


@pytest.mark.parametrize("change,named", REFUSED_FILES,
                         ids=[f"{n}-{i}" for i, (_, n) in
                              enumerate(REFUSED_FILES)])
def test_a_published_file_the_path_cannot_run_is_refused(tmp_path, change,
                                                         named):
    with pytest.raises(NotImplementedError, match=named):
        train.load_model_config(_file_with(tmp_path, **change))


@pytest.mark.parametrize("config", [
    "smallthinker-21b-a3b-train-1chip", "lfm2-8b-a1b-train-1chip",
    "joyai-flash-train-1chip", "nemotron3-super-train-1chip"])
def test_a_learned_selection_in_another_kind_of_model_is_refused(tmp_path,
                                                                 config):
    with open(os.path.join(BENCH, "configs", config + ".json"),
              encoding="utf-8") as f:
        body = json.load(f)
    body["sa_config"] = {"indexer_head_dim": 64, "indexer_num_heads": 16,
                         "indexer_num_kv_heads": 1, "topk": 2048}
    path = tmp_path / "with_sa.json"
    path.write_text(json.dumps(body))
    with pytest.raises(NotImplementedError, match="sa_config"):
        train.load_model_config(str(path))


@pytest.mark.parametrize("change,named", [
    (dict(index_topk=0), "index_topk"),
    (dict(index_heads=0), "index_topk"),
    (dict(index_head_dim=7), "index_topk"),
    (dict(attn_output_gate=True), "index_topk"),
    (dict(layer_types=("full_attention",) * 8), "index_topk"),
    (dict(layer_types=("sparse_attention", "sliding_attention") * 4,
          sliding_window=16), "index_topk"),
], ids=["no-topk", "no-heads", "odd-width", "gated", "no-sparse-layer",
        "beside-a-window"])
def test_a_configuration_the_sublayer_cannot_run_is_refused(change, named):
    with pytest.raises(NotImplementedError, match=named):
        dataclasses.replace(F32, **change)


def test_the_other_models_files_still_refuse_a_scaled_rope(tmp_path):
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b-train-1chip.json"),
              encoding="utf-8") as f:
        body = json.load(f)
    body["rope_scaling"] = {"rope_type": "linear", "factor": 2.0}
    path = tmp_path / "scaled.json"
    path.write_text(json.dumps(body))
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        train.load_model_config(str(path))
