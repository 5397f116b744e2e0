"""SmallThinker-21BA3B's training step on the normal path
(``parallel/train.py``'s model path under ``layer_types``: full-attention
layers without RoPE beside sliding-window layers with it, two kinds of the
same leaves; a router that reads the layer's input before attention;
relu-gated experts with no shared one) against the plain reference
(``parallel/smallthinker_reference.py``: dense masked softmax, the window
as its inequality) at small widths on seeded random weights: hidden 64; 8
query heads of 16 on 2 key-value heads; a window of 16 positions in blocks
of 8 over 40; 16 experts of width 24, top 3; held here: one period (a full
layer, three window layers), 4 experts (share 1 of 4), 64 of 256 ids.
Float32 compute meets the reference at rtol 1e-5."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import (attention, config, experts, layers, model,
                               objective, train)
from ompi_tpu.parallel import smallthinker_reference

import built

ref = built.programs(smallthinker_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
CONFIG = os.path.join(BENCH, "configs",
                      "smallthinker-21b-a3b-train-1chip.json")
TYPES = ("full_attention",) + ("sliding_attention",) * 3
PUBLISHED = dict(
    hidden_size=64, intermediate_size=24, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, num_experts=16,
    num_experts_per_tok=3, vocab_size=256, layer_types=TYPES * 2,
    moe_intermediate_size=24, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=1.5e6, sliding_window=16, rope_kinds=("sliding_attention",),
    qk_norm=False, router_before_attention=True, mlp_hidden_act="relu")
SHARE = dict(layers_here=4, first_layer_here=0, experts_here=4,
             expert_share=1, vocab_here=64, mtp_here=0)
TRAIN = dict(seq_len=40, micro_batch=2, attn_block=8, loss_block_rows=8,
             lr=1e-2, aux_loss_coef=0.001, z_loss_coef=0.0)
F32 = config.ModelConfig(compute_dtype="float32", **PUBLISHED, **SHARE,
                        **TRAIN)
NAMES = train.leaf_names(F32)
CLOSE = dict(rtol=1e-5, atol=1e-6)


def batch_of(seed, vocab=64, cfg=F32):
    """(inputs (2, s), labels (2, s + 1)) from s + 2 ids a sequence: the
    batch's form for every share cell."""
    ids = np.random.default_rng(seed).integers(
        0, vocab, (cfg.micro_batch, cfg.seq_len + 2)).astype(np.int32)
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def layer_of(cfg, kind, seed=5):
    """One layer's leaves of ``kind`` (``attn_moe`` or ``swa_moe``) drawn
    as ``init_model_params`` would, the matrices wide enough (0.3) that
    every part matters."""
    first = {"attn_moe": 0, "swa_moe": 1}[kind]
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


# -- the sublayers -----------------------------------------------------------
@pytest.mark.parametrize("kind,letter", [("attn_moe", "A"), ("swa_moe", "W")],
                         ids=["full", "window"])
def test_an_attention_sublayer_is_the_references(kind, letter):
    """A full layer without RoPE and a window layer with it, by the kind
    the walk hands down: the same leaves, two sublayers."""
    p = layer_of(F32, kind)
    assert "q_norm" not in p and set(p) >= {"wq", "wk", "wv", "wo", "router"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    entry = {"A": attention.FULL, "W": attention.WINDOW}[letter]
    got, _, seen = entry.run(p, x, F32, interpret=True)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, x, F32, letter)
    close(got, want, rtol=1e-4, atol=1e-5)
    assert set(seen) == {"attn_qk_in", "attn_qk"} | (
        set() if letter == "A" else {"attn_win_q", "attn_win_k_seq",
                                     "attn_win_v_seq", "attn_win_o"})
    # a layer that is not turned reports q and k as they were left
    assert bool(jnp.all(seen["attn_qk"] == seen["attn_qk_in"])) \
        == (letter == "A")


@pytest.mark.parametrize("control", ["no_window", "rope_on_full",
                                     "no_rope_on_window"])
def test_the_wrong_kind_of_layer_differs(control):
    """The window left out, RoPE on the full layer, RoPE left off a
    window layer: each is another function of the same leaves."""
    p = layer_of(F32, "swa_moe")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 64))
    change = dataclasses.replace
    wrong = {
        "no_window": dict(kind="sliding_attention", cfg=change(
            F32, sliding_window=4096, attn_block=8)),
        "rope_on_full": dict(kind="full_attention", cfg=change(
            F32, rope_kinds=("full_attention", "sliding_attention"))),
        "no_rope_on_window": dict(kind="sliding_attention", cfg=change(
            F32, rope_kinds=()))}[control]
    letter = "A" if control == "rope_on_full" else "W"
    got = model.NAMED[wrong["kind"]].run(p, x, wrong["cfg"],
                                         interpret=True)[0]
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, x, F32, letter)
    assert float(jnp.abs(got - want).max()) > 1e-2


def test_a_sequence_shorter_than_the_window_is_full_attention_bit_for_bit():
    """At 40 positions a window of 4,096 reaches every earlier key: a
    window layer is then the full layer's attention with RoPE, the same
    branches and the same bits."""
    wide = dataclasses.replace(F32, sliding_window=4096, attn_block=8)
    p = layer_of(F32, "swa_moe")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 64))
    got = attention.WINDOW.run(p, x, wide, interpret=True)[0]
    turned = dataclasses.replace(F32, rope_kinds=("full_attention",))
    full = attention.FULL.run(p, x, turned, interpret=True)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))


def test_rope_turns_window_layers_only():
    """A window layer's ``attn_qk`` holds its first heads turned by
    ``layers.rope`` at theta 1.5e6 (the full layer's holds them as they
    were projected: the test above)."""
    p = layer_of(F32, "swa_moe")
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, 64))
    _, _, seen = attention.WINDOW.run(p, x, F32, interpret=True)
    q_in = seen["attn_qk_in"][:, :16].reshape(2, 1, 40, 16)
    close(seen["attn_qk"][:, :16].reshape(2, 1, 40, 16),
          layers.rope(q_in, F32.rope_theta), rtol=1e-6)
    assert float(jnp.abs(seen["attn_qk"] - seen["attn_qk_in"]).max()) > 1e-2


def test_the_router_reads_the_layers_input():
    """``decoder_layer`` makes the logits from the un-normed stream that
    enters the layer; a router on the post-attention stream (every other
    model's place for it) chooses other experts."""
    p = layer_of(F32, "swa_moe")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, 64))
    out, stats, seen = model.decoder_layer(p, x, F32, interpret=True,
                                           kind="swa_moe")
    rows = x.reshape(80, 64)
    close(seen["in"], rows)
    close(seen["logits"], jnp.dot(rows, p["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    with jax.default_matmul_precision("highest"):
        want, loads, prob_sum = ref.layer(p, x, F32, "W")
    close(out, want, rtol=1e-4, atol=1e-5)
    close(stats["slots"], loads)
    close(stats["prob_sum"], prob_sum, rtol=1e-5)
    close(jnp.sum(seen["weights"], -1), np.ones(80), rtol=1e-5)
    after = dataclasses.replace(F32, router_before_attention=False)
    late, _, seen_late = model.decoder_layer(p, x, after, interpret=True,
                                             kind="swa_moe")
    assert float(jnp.abs(late - want).max()) > 1e-2
    assert np.mean(np.asarray(seen_late["experts"])
                   != np.asarray(seen["experts"])) > 0.3


def test_the_experts_are_gated_by_relu_not_silu():
    p = layer_of(F32, "attn_moe")
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 40, 64))
    rows = x.reshape(80, 64)
    routed = (rows, experts.router_logits(p, rows))
    got = experts.moe_shared_local_block(p, x, F32, None, routed=routed)[0]
    with jax.default_matmul_precision("highest"):
        want = ref.experts(p, x, ref.route(p, rows, F32), F32)
    close(got, want, rtol=1e-4, atol=1e-5)
    silu = dataclasses.replace(F32, mlp_hidden_act="silu")
    other = experts.moe_shared_local_block(p, x, silu, None,
                                           routed=routed)[0]
    assert float(jnp.abs(other - want).max()) > 1e-2
    # the written-out backward of the held experts' loop follows the
    # activation
    loss = lambda fn: lambda p: jnp.sum(fn(p) ** 2)
    g_got = jax.jit(jax.grad(loss(lambda p: experts.moe_shared_local_block(
        p, x, F32, None, routed=routed)[0])))(p)
    with jax.default_matmul_precision("highest"):
        g_want = jax.jit(jax.grad(loss(lambda p: ref.experts(
            p, x, ref.route(p, rows, F32), F32))))(p)
    for leaf in ("gate", "up", "down", "ln2"):
        near(g_got[leaf], g_want[leaf], rel=1e-4, err_msg=leaf)


def test_the_four_shares_layer_outputs_add_up_to_the_uncut_layer():
    """The 4 expert shares of a layer (4 of 16 each), **attention's part
    counted once**, add up to the uncut reference's layer: what the
    expert-parallel group's exchange would make of them."""
    whole = dataclasses.replace(F32, experts_here=0, expert_share=0)
    p = layer_of(whole, "swa_moe")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 64))
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.layer(p, x, whole, "W")
        alike = x + ref.attention(p, x, whole, "W")  # every chip's alike
    total = 0.0
    for j in range(4):
        part = dataclasses.replace(F32, experts_here=4, expert_share=j)
        mine = {**p, **{k: p[k][4 * j:4 * j + 4]
                        for k in ("gate", "up", "down")}}
        out = model.decoder_layer(mine, x, part, interpret=True,
                                  kind="swa_moe")[0]
        total = total + (out - alike)       # a share's routed part
    close(total + alike, want, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want - alike).max()) > 1e-3


def test_the_layers_are_walked_by_their_kinds():
    assert F32.pattern_here == "AWWW"
    assert F32.segments == (("A", 1, 0), ("W", 3, 1))
    assert (F32.n_sparse_here, F32.n_routers, F32.head_width,
            F32.rotary_width, F32.shared_width, F32.sliding_window) \
        == (4, 4, 16, None, 0, 16)
    assert F32.routes_to_held
    shapes = train.model_param_shapes(F32)
    assert shapes["layers"]["l0"]["attn_moe"]["wq"] == (1, 64, 128)
    assert shapes["layers"]["l1"]["swa_moe"]["wk"] == (3, 64, 32)
    assert shapes["layers"]["l1"]["swa_moe"]["gate"] == (3, 4, 64, 24)
    assert "q_norm" not in shapes["layers"]["l1"]["swa_moe"]
    assert set(train.pattern_layer_shapes(F32)) == {
        "attn_dense", "attn_moe", "swa_dense", "swa_moe"}
    later = dataclasses.replace(F32, first_layer_here=2, layers_here=6)
    assert later.pattern_here == "WWAWWW"


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
    for name, path in NAMES:
        # Adam's first steps move an entry by the learning rate times
        # its gradient's sign, so one whose gradient is next to nothing
        # goes either way: a thousandth of a leaf may lie outside a
        # hundredth of the three steps, none outside the three steps
        off = np.abs(np.asarray(train._leaf(stepped["state"][0], path))
                     - np.asarray(train._leaf(params, path)))
        assert off.max() <= 3 * F32.lr, name
        assert np.mean(off > 0.01 * 3 * F32.lr) <= 2e-3, name


def test_one_step_reports_the_references_loads_and_gradients(stepped):
    """Loss parts, loads and the gradient of every leaf, through the
    jitted step."""
    tokens, labels = stepped["batches"][0]
    aux = stepped["auxes"][0]
    (total, (ce, lb, loads)), g = ref.grads(stepped["params"], tokens,
                                            labels, F32)
    close(aux["losses"], [total, ce, lb, 0.0])
    assert float(lb) > 0
    close(aux["loads"], loads)
    assert aux["loads"].shape == (4, 16) and aux["experts"].shape \
        == (4, 80, 3)
    first = F32.first_expert_here
    assert aux["local_slots"] == loads[:, first:first + 4].sum()
    sample = aux["sample"]
    assert sample["attn_qk"].shape == (4, 16, 32) \
        and sample["attn_win_k_seq"].shape == (3, 80, 16) \
        and sample["attn_win_o"].shape == (3, 16, 16) \
        and sample["router_in"].shape == (4, 16, 64) \
        and sample["router_scores"].shape == (4, 16, 16)
    for (name, path), sq, probe in zip(NAMES, aux["grad_sq"],
                                       aux["grad_probe"]):
        leaf = np.asarray(train._leaf(g, path))
        close(sq, np.sum(leaf * leaf), rtol=1e-4, err_msg=name)
        near(probe, leaf.reshape(-1)[train.probe_positions(
            name, leaf.size)], err_msg=name)


def test_the_loss_and_its_gradients_one_primitive_at_a_time():
    """The same through ``jax.disable_jit`` (``model_loss`` and its
    gradient run eagerly, every scan a Python loop: a full and a window
    layer of 24 positions, three blocks under a window of two, on one
    sequence): loss parts, loads and the gradient of every leaf are the
    reference's, so nothing rests on what a compiler fused."""
    cfg = dataclasses.replace(F32, layers_here=2, seq_len=24, micro_batch=1)
    params = built.params(cfg, 3)
    tokens, labels = batch_of(0, cfg=cfg)
    with jax.disable_jit():
        (_, aux), got = jax.value_and_grad(
            lambda p: objective.model_loss(p, tokens, labels, cfg,
                                       interpret=True, n_global=24),
            has_aux=True)(params)
    (total, (ce, lb, loads)), g = ref.grads(params, tokens, labels, cfg)
    close(aux["losses"], [total, ce, lb, 0.0])
    close(aux["loads"], loads)
    assert aux["sample"]["attn_win_o"].shape == (1, 16, 16)
    for name, path in train.leaf_names(cfg):
        near(train._leaf(got, path), train._leaf(g, path), err_msg=name)


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


def test_the_step_walks_the_window_layers_under_their_own_scope(traced_step):
    """As a TPU traces it: the forward kernel of the window layers (one
    scanned run) with a grid of the window's 3 tiles where the full
    layer's has 5, and the window layers' ops under ``otpu_swa``."""
    tokens, labels = batch_of(0)
    step = traced_step(F32, tokens, labels, True)
    grids = sorted(e.params["grid_mapping"].grid[-1] for e in step.eqns
                   if e.primitive.name == "pallas_call"
                   and e.params["name"] == "otpu_flash_causal_forward")
    assert set(grids) == {3, 5}
    names = {str(e.source_info.name_stack) for e in step.eqns}
    assert any("otpu_swa" in n for n in names) \
        and any("otpu_attention" in n for n in names)


# -- the configuration -------------------------------------------------------------------
def test_the_benchmarks_configuration_loads_at_its_published_widths():
    cfg = train.load_model_config(CONFIG)
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_width, cfg.rotary_width) \
        == (2560, 28, 4, 128, None)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.expert_width,
            cfg.shared_width, cfg.n_experts_here, cfg.first_expert_here) \
        == (64, 6, 768, 0, 16, 0)
    assert (cfg.scoring_func, cfg.topk_method, cfg.norm_topk_prob,
            cfg.qk_norm, cfg.router_before_attention, cfg.mlp_hidden_act,
            cfg.sliding_window, cfg.rope_kinds) \
        == ("softmax", "greedy", True, False, True, "relu", 4096,
            ("sliding_attention",))
    assert cfg.layer_types == TYPES * 13 and cfg.pattern_here == "AWWW"
    assert cfg.segments == (("A", 1, 0), ("W", 3, 1))
    assert (cfg.vocab_size, cfg.vocab_rows, cfg.seq_len, cfg.micro_batch,
            cfg.n_mtp_here, cfg.rms_norm_eps, cfg.rope_theta,
            cfg.aux_loss_coef, cfg.z_loss_coef, cfg.attn_block) \
        == (151936, 37984, 16384, 1, 0, 1e-6, 1.5e6, 0.001, 0.0, 1024)
    shapes = train.model_param_shapes(cfg)
    count = lambda tree: sum(int(np.prod(s)) for s in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))
    assert count(shapes) == 656_529_920                  # 656.53 M
    per = {k: count(v) for k, v in train.pattern_layer_shapes(cfg).items()}
    assert per["attn_moe"] == per["swa_moe"] == 115_512_320
    with open(CONFIG) as f:
        body = json.load(f)
    assert body["kit"] == "smallthinkerkit" and body["chips_a_layer"] == 4
    assert "656,529,920" in body["arithmetic"] and "10.50 GB" \
        in body["arithmetic"]


def test_the_files_published_keys_are_the_catalogs():
    """Every key of the catalog's row, under the same name and with the
    same value (the guide's rule, checked where the catalog is at hand)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct"]
    with open(CONFIG) as f:
        body = json.load(f)
    assert body["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert body[key] == value, key


REFUSED = [
    (dict(sliding_window=0), "sliding_window"),
    (dict(sliding_window=12), "sliding_window"),
    (dict(layer_types=("full_attention",) * 8), "sliding_window"),
    (dict(layer_types=(), qk_norm=True, router_before_attention=False,
          mlp_hidden_act="silu", sliding_window=0, num_key_value_heads=8),
     "head_dim"),
    (dict(mlp_hidden_act="gelu"), "mlp_hidden_act"),
    (dict(first_k_dense_replace=1), "mlp_hidden_act"),
    (dict(heads_here=2), "heads_here"),
    (dict(mtp_here=1), "mtp_here"),
    (dict(kv_lora_rank=16), "kv_lora_rank"),
]


@pytest.mark.parametrize("change,key", REFUSED,
                         ids=[f"{k}-{i}" for i, (_, k) in enumerate(REFUSED)])
def test_what_the_path_cannot_run_is_refused_by_its_key(change, key):
    with pytest.raises(NotImplementedError, match=key):
        dataclasses.replace(F32, **change)


def test_a_router_before_attention_is_a_layer_types_models():
    olmoe = train.load_model_config(os.path.join(
        BENCH, "configs", "olmoe-1b-7b-train-1chip.json"))
    for change in (dict(router_before_attention=True), dict(qk_norm=False)):
        with pytest.raises(NotImplementedError,
                           match="router_before_attention"):
            dataclasses.replace(olmoe, **change)
    with pytest.raises(NotImplementedError, match="sliding_window"):
        dataclasses.replace(olmoe, sliding_window=4096)


def _changed(tmp_path, **change):
    with open(CONFIG) as f:
        body = json.load(f)
    path = tmp_path / "changed.json"
    path.write_text(json.dumps({**body, **change}))
    return str(path)


@pytest.mark.parametrize("key,value,named", [
    ("moe_primary_router_apply_softmax", False,
     "moe_primary_router_apply_softmax"),
    ("rope_layout", [1, 1, 1, 1] * 13, "rope_layout"),
    ("sliding_window_size", 1000, "sliding_window"),
    ("rope_scaling", {"type": "yarn"}, "RoPE"),
    ("attention_bias", True, "biases"),
    ("hidden_act", "gelu", "silu")])
def test_a_published_file_the_path_cannot_run_is_refused(tmp_path, key,
                                                          value, named):
    with pytest.raises(NotImplementedError, match=named):
        train.load_model_config(_changed(tmp_path, **{key: value}))


@pytest.mark.parametrize("config,key,value", [
    ("qwen3-next-80b-a3b-train-1chip", "sliding_window_size", 4096),
    ("lfm2-8b-a1b-train-1chip", "sliding_window_layout", [0, 1]),
    ("joyai-flash-train-1chip", "sliding_window", 4096),
    ("olmoe-1b-7b-train-1chip", "rope_layout", [0, 1])])
def test_a_window_in_another_kind_of_model_is_refused(tmp_path, config, key,
                                                      value):
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        body = json.load(f)
    path = tmp_path / "changed.json"
    path.write_text(json.dumps({**body, key: value}))
    with pytest.raises(NotImplementedError, match="sliding_window"):
        train.load_model_config(str(path))


def test_the_kinds_follow_the_layouts(tmp_path):
    """Another period: every other layer a window layer with RoPE."""
    layout = [0, 1] * 26
    cfg = train.load_model_config(_changed(
        tmp_path, sliding_window_layout=layout, rope_layout=layout))
    assert cfg.pattern_here == "AWAW" and cfg.rope_kinds \
        == ("sliding_attention",)
    assert cfg.segments == (("A", 1, 0), ("W", 1, 1), ("A", 1, 2),
                            ("W", 1, 3))


# -- the benchmark's own copy of the reference -----------------------------------------
@pytest.fixture(scope="module")
def kit():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import smallthinkerkit
        yield smallthinkerkit
    finally:
        sys.path.remove(BENCH)


KIT_CFG = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 16, "n_routed_experts": 16,
    "moe_num_primary_experts": 16, "num_experts_per_tok": 3,
    "moe_ffn_hidden_size": 24, "vocab_size": 256,
    "sliding_window_layout": [0, 1, 1, 1] * 2,
    "rope_layout": [0, 1, 1, 1] * 2, "sliding_window_size": 16,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1.5e6,
    **SHARE, **TRAIN, "compute_dtype": "float32", "init_std": 0.02}


def test_the_kit_names_the_programs_leaves(kit):
    assert kit.leaves(KIT_CFG) == tuple(n for n, _ in NAMES)
    assert kit.pattern(KIT_CFG) == F32.pattern_here
    assert [(c, n, first) for c, n, first in kit.segments(KIT_CFG)] \
        == list(F32.segments)
    assert kit.turned(KIT_CFG) == [False, True, True, True]
    checked = kit.checked(KIT_CFG)
    assert set(checked) <= set(kit.leaves(KIT_CFG)) \
        and kit.probed(KIT_CFG) == tuple(n for n in checked if n != "embed")
    shapes = train.model_param_shapes(F32)
    assert kit.leaf_sizes(KIT_CFG) == {
        n: int(np.prod(train._leaf(shapes, p))) for n, p in NAMES}
    for run in ("l0.attn_moe", "l1.swa_moe"):
        for leaf in ("wq", "wk", "wv", "wo", "router"):
            assert f"{run}.{leaf}" in checked
    # the held experts' three of the one-layer run only: a run of three
    # layers' do not fit beside the reference at the published widths
    assert "l0.attn_moe.gate" in checked and "l1.swa_moe.gate" not in checked
    assert {"final_norm", "head", "embed"} <= set(checked)
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
    fiftieth of the tolerance of the reference's under the step's own
    routing; every wrong model lies outside it somewhere, and every
    control of a part outside it at that part."""
    tokens, labels = batch_of(4)
    # matrices wide enough (0.1) that a part's control shows at these
    # widths as it does at the published ones
    params = train.init_model_params(
        dataclasses.replace(F32, init_std=0.1), 11)
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
        and want["grad_probe"].shape == (len(kit.probed(KIT_CFG)), 64) \
        and want["rope_qk"].shape == (4, 16, 32) \
        and want["window_o"].shape == (3, 16, 16)
    for wrong in kit.WRONG:
        assert max(units(whole(wrong=wrong), want).values()) > 1, wrong
    for variant, part in (("bf16", "head_rows"), ("no_window", "window_o"),
                          ("rope_full", "rope_qk"),
                          ("no_rope_window", "rope_qk"),
                          ("router_post", "router_logits"),
                          ("silu", "expert_out"),
                          ("unnormalised", "router_weights")):
        assert units(parts(variant), want)[part] > 1, variant
    assert set(kit.PART_CONTROLS) == {
        "bf16", "no_window", "rope_full", "no_rope_window", "router_post",
        "silu", "unnormalised"}


def test_the_kit_counts_the_published_steps_operations(kit):
    """Attention counts the VISIBLE positions only: a full layer s^2 / 2,
    a window layer 4,096 x 4,097 / 2 + 12,288 x 4,096; the backward
    kernel's five products are 2.5 forwards."""
    cfg = kit.load_config(CONFIG)
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 64
    assert kit.pattern(cfg) == "AWWW" and kit.turned(cfg) == [
        False, True, True, True]
    per = kit.matmul_params_per_token(cfg)
    assert per["attn_proj"] == 20_971_520 and per["router"] == 163_840
    assert per["experts_mean"] == 3 * 2560 * 768 * 6 * 16 / 64
    assert per["head"] == 2560 * 37984
    see = kit.visible_positions(cfg)
    assert see == {"A": 16384 * 16384 / 2,
                   "W": 4096 * 4097 / 2 + 12288 * 4096}
    flops = kit.step_flops(cfg)
    assert flops["flash_forward"] == 28 * 4 * 128 * (see["A"] + 3 * see["W"])
    assert flops["attn_backward"] == 2.5 * flops["flash_forward"]
    assert flops["attention"] == 3 * flops["flash_forward"]
    assert abs(flops["flash_forward"] / 1e12 - 4.45) < 0.01
    assert abs(flops["step"] / 1e12 - 34.7) < 0.05
    assert abs(flops["attn_proj"] / 1e12 - 8.25) < 0.05 \
        and abs(flops["head"] / 1e12 - 9.56) < 0.05 \
        and abs(flops["experts"] / 1e12 - 3.48) < 0.05
    assert sum(kit.leaf_sizes(cfg).values()) == 656_529_920
    short = dict(cfg, seq_len=4096)
    assert kit.visible_positions(short)["W"] \
        == kit.visible_positions(short)["A"]
