"""Xing4.0-29B-A4B's training step on the normal path (``parallel/train
.py``'s model path on four residual streams: manifold-constrained
hyper-connections around latent attention under YaRN, a dense SwiGLU, and a
shared expert beside a share of the sigmoid-routed ones) against the plain
reference (``parallel/xing_reference.py``) at small widths on seeded random
weights: hidden 64, n 4, 20 sweeps, 2 + 2 layers, 4 heads of 16 + 8 / 16 of
which 2 are held, latents 32 and 16, dense width 96, 8 experts of width 32 of
which 2 are held (share 1 of 4), top 2, a slice of 64 of 512 ids, rows of
32.  Float32 compute meets the reference at rtol 1e-5; each control lies
outside the kind's tolerance; the shares add up to the uncut layer."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import xing_reference
from ompi_tpu.parallel import config, hyper, layers, objective, train

import built

ref = built.programs(xing_reference)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIGS = os.path.join(BENCH, "configs")
YARN = dict(factor=64, original_max_position_embeddings=16, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)
PUBLISHED = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4,
    num_key_value_heads=4, num_experts_per_tok=2, vocab_size=512,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=2,
    moe_intermediate_size=32, n_shared_experts=1, scoring_func="sigmoid",
    topk_method="noaux_tc", routed_scaling_factor=2.0, norm_topk_prob=True,
    num_nextn_predict_layers=1, rope_theta=10000.0, rms_norm_eps=1e-6,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, rope_scaling=YARN)
SHARE = dict(layers_here=4, dense_here=2, experts_here=2, expert_share=1,
             heads_here=2, vocab_here=64, mtp_here=0)
TRAIN = dict(seq_len=32, micro_batch=2, attn_block=16, loss_block_rows=16,
             lr=1e-2, aux_loss_coef=0.0, z_loss_coef=0.0,
             bias_update_gamma=0.001, hc_gate_start=1.0, hc_offset_std=1.0,
             hc_res_diag=2.0)
F32 = config.ModelConfig(compute_dtype="float32", num_experts=8,
                         **PUBLISHED, **SHARE, **TRAIN)
LEAVES = [name for name, _ in train.leaf_names(F32)]
CLOSE = dict(rtol=1e-5, atol=1e-6)


def batch_of(seed, vocab=64):
    """(inputs (2, 32), labels (2, 33)) from 34 ids a row: the batch's form
    for every kit cell; this model reads the first 32 labels."""
    ids = np.random.default_rng(seed).integers(0, vocab, (2, 34)).astype(
        np.int32)
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def some_bias(cfg=F32, scale=0.01):
    return {"layers": scale * jax.random.normal(
        jax.random.PRNGKey(7), (cfg.n_sparse_here, cfg.num_experts))}


@pytest.fixture(scope="module")
def params():
    return built.params(F32, 3)


@pytest.fixture(scope="module")
def reference(params):
    # jitted, so that JAX's persistent cache hands the program on to the
    # next worker that makes this fixture
    (loss, loads), grads = jax.jit(lambda p: ref.grads(
        p, *batch_of(0), F32, some_bias()))(params)
    return dict(loss=loss, loads=np.asarray(loads), grads=grads)


@pytest.fixture(scope="module")
def system(params):
    tokens, labels = batch_of(0)
    (total, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: objective.model_loss(
            p, tokens, labels, F32, interpret=True, n_global=tokens.size,
            bias=some_bias()), has_aux=True))(params)
    return dict(total=total, aux=aux, grads=grads)


def test_loss_loads_and_every_leafs_gradient(system, reference):
    np.testing.assert_allclose(system["total"], reference["loss"], **CLOSE)
    np.testing.assert_array_equal(system["aux"]["loads"], reference["loads"])
    for name, path in train.leaf_names(F32):
        got = np.asarray(train._leaf(system["grads"], path))
        want = np.asarray(train._leaf(reference["grads"], path))
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-6 * max(1.0, np.abs(want).max()),
            err_msg=name)


def test_a_steps_parameters_biases_and_loss(params):
    """Through ``build_train_step``: the parameters after the update, the
    biases the sign rule moved and the loss, against the reference's AdamW;
    the path's gates and offsets are not decayed."""
    step, place = built.step(F32)
    batches = [batch_of(0)]
    state, got = None, []
    for tokens, labels in batches:
        if state is None:
            state, tokens, labels = place(jax.tree.map(jnp.copy, params),
                                          tokens, labels)
        state, aux = step(state, tokens, labels)
        got.append(aux["losses"][0])
    want_p, want_b, want = jax.jit(
        lambda p: ref.train_steps(p, batches, F32))(params)
    np.testing.assert_allclose(got, want, **CLOSE)
    np.testing.assert_allclose(state[4]["layers"], want_b["layers"],
                               atol=1e-7)
    for name, path in train.leaf_names(F32):
        # AdamW's first steps are g / |g|: where a gradient entry is of its
        # own rounding's size the update is a coin's side, a part of lr
        np.testing.assert_allclose(
            train._leaf(state[0], path), train._leaf(want_p, path),
            rtol=1e-5, atol=0.05 * F32.lr, err_msg=name)
        last = name.rsplit(".", 1)[-1]
        assert train.is_decayed(name) == (
            last not in hyper.UNDECAYED and last not in (
                "ln1", "ln2", "q_a_norm", "kv_a_norm", "final_norm")), name
    assert train.is_decayed("hc1_phi") and not train.is_decayed("hc2_b")


def test_hres_is_doubly_stochastic_to_what_twenty_sweeps_leave(system,
                                                              params):
    """Every sampled mixing map's rows sum to one (the last sweep norms
    them) and its columns to one less what 20 sweeps leave: the step's
    ``hc_defect`` is the reference's own maps' at the same rows, not zero."""
    sample = system["aux"]["sample"]
    res = np.stack([np.asarray(sample[f"{at}_res"], np.float64)
                    for at in hyper.SETS])
    assert res.shape == (2, F32.layers_here, objective.SAMPLE_ROWS, 4, 4)
    assert np.abs(res.sum(-1) - 1.0).max() < 2e-6
    cols = np.abs(res.sum(-2) - 1.0).max()
    got = float(system["aux"]["hc_defect"])
    assert 1e-6 < got < 0.05 and abs(got - cols) < 1e-6
    tokens, _ = batch_of(0)
    x = jnp.stack([params["embed"][tokens]] * 4, axis=2)
    _, _, want = ref.maps(jax.tree.map(lambda a: a[0], params["dense"]), x,
                          F32, "hc1")
    at = objective.sample_rows(tokens.size)
    np.testing.assert_allclose(sample["hc1_res"][0],
                               want.reshape(-1, 4, 4)[at], **CLOSE)
    one = dataclasses.replace(F32, hc_sinkhorn_iters=1)
    raw = jnp.log(want.reshape(-1, 4, 4)[at]).transpose(1, 2, 0)
    assert float(hyper.defect(hyper.sinkhorn(raw, one).transpose(2, 0, 1))) \
        < 1e-5     # a doubly stochastic map is the sweeps' fixed point


#: ``transformers.modeling_rope_utils._compute_yarn_parameters`` (4.57.6) on
#: the published keys: rope_theta 10,000, a rotary part of 64, factor 64 from
#: 4,096, beta_fast 32, beta_slow 1, truncate its default; low 10, high 23
YARN_INV_FREQ = (
    1.0, 0.7498942017555237, 0.5623413324356079, 0.4216965138912201,
    0.3162277638912201, 0.23713736236095428, 0.17782793939113617,
    0.1333521455526352, 0.10000000149011612, 0.07498941570520401,
    0.05623412877321243, 0.0389765165746212, 0.026833752170205116,
    0.018326841294765472, 0.012396659702062607, 0.008286424912512302,
    0.00545673118904233, 0.0035241420846432447, 0.002216922352090478,
    0.0013431437546387315, 0.000767764518968761, 0.00039617903530597687,
    0.00016243898426182568, 2.0836272597080097e-05, 1.5625000742147677e-05,
    1.1717096640495583e-05, 8.786582839093171e-06, 6.589007625734666e-06,
    4.9410591600462794e-06, 3.7052716379548656e-06, 2.7785615657194285e-06,
    2.08362735065748e-06)


def test_yarns_table_and_scale_are_the_sources():
    cfg = train.load_model_config(os.path.join(
        CONFIGS, "xing4.0-29b-a4b-train-1chip.json"))
    inv, by = layers.yarn_inv_freq(64, cfg.rope_theta, cfg.yarn)
    np.testing.assert_allclose(inv, YARN_INV_FREQ, rtol=2e-6)
    assert by == 1.0
    assert abs(cfg.attention_scale * 192 ** 0.5 - 2.004739701682487) < 1e-12
    np.testing.assert_allclose(ref.yarn_inv_freq(cfg)[0], YARN_INV_FREQ,
                               rtol=2e-6)
    assert (cfg.n_heads_here, cfg.n_dense_here, cfg.n_sparse_here,
            cfg.n_mtp_here, cfg.hc_mult) == (16, 1, 4, 0, 4)
    # the tables under YaRN are not plain RoPE's, and plain RoPE's are as
    # they were
    x = jnp.ones((1, 8, 1, 8))
    plain = layers._rope_tables(x, 1e4, 0, 1)
    np.testing.assert_array_equal(plain[0], layers._rope_tables(
        x, 1e4, 0, 1, None)[0])
    assert np.abs(plain[1] - layers._rope_tables(x, 1e4, 0, 1, YARN)[1]
                  ).max() > 0.1


@pytest.fixture(scope="module")
def kit():
    sys.path.insert(0, BENCH)
    try:
        from harness import xingkit
        from harness import protocol
        kind = protocol.load_module("kinds", "train_step_kit", BENCH)
        yield xingkit, kind.TOLERANCE
    finally:
        sys.path.remove(BENCH)


def kit_cfg():
    return {**PUBLISHED, **SHARE, **TRAIN, "n_routed_experts": 8,
            "compute_dtype": "float32", "mhc_h_res_clamp_min": -30,
            "mhc_h_res_clamp_max": 30}


@pytest.fixture(scope="module")
def checked(kit, system, params):
    """What the kind compares of the program's step, and of the kit's
    reference from the same parameters under the step's routing."""
    xk, _ = kit
    cfg, wrt = kit_cfg(), xk.checked(kit_cfg())
    tokens, labels = batch_of(0)
    aux = jax.device_get(system["aux"])
    flat = {n: np.asarray(train._leaf(system["grads"], p)).reshape(-1)
            for n, p in train.leaf_names(F32)}
    aux["grad_sq"] = np.asarray([np.sum(f * f) for f in flat.values()])
    aux["grad_probe"] = np.stack([f[train.probe_positions(n, f.size)]
                                  for n, f in flat.items()])
    bias = some_bias()
    after = {"layers": train.bias_update(F32, bias["layers"], aux["loads"])}
    got = xk.compared(xk.step_stats(aux, after, cfg), cfg, wrt)

    def want(wrong=None, dtype=jnp.float32):
        out = xk.reference_step(
            jax.tree.map(lambda a: a.astype(dtype), params), tokens, labels,
            cfg, bias, wrt, wrong=wrong, routed=aux["experts"])
        return xk.compared(jax.device_get(
            {k: v for k, v in out.items() if k != "grads"}), cfg, wrt)
    return got, want


def units(got, want, tol):
    return max(float(np.max(np.abs(np.float64(got[k]) - want[k]) / (
        tol["atol"] + tol["rtol"] * np.abs(want[k])))) for k in want)


def test_the_step_lies_inside_the_kinds_tolerance(kit, checked):
    got, want = checked
    assert units(got, want(), kit[1]) < 0.05


@pytest.mark.parametrize("wrong", ["bf16", "plain_rope", "res_identity"])
def test_a_control_lies_outside_the_kinds_tolerance(kit, checked, wrong):
    """Tentpole 4's controls as whole models, each against the program's
    step: the model in bfloat16; plain RoPE and 1 / sqrt(nope + rot); the
    identity for the mixing map.  (1 and 5 sweeps and Hpost without its 2
    are held part by part, ``test_a_parts_control_lies_outside``: 5 sweeps
    leave the maps of this size too near 20's for the whole model's
    statistics to tell, and a program a variant costs the suite 15 s.)"""
    got, want = checked
    control = want(dtype=jnp.bfloat16) if wrong == "bf16" else want(wrong)
    assert units(got, control, kit[1]) > 1.0


@pytest.mark.parametrize("variant", [
    "bf16", "maps_bf16", "sweeps_1", "sweeps_5", "res_identity",
    "post_unscaled", "bias_in_weights", "softmax"])
def test_a_parts_control_lies_outside(kit, system, params, variant):
    """The float32 parts recomputed from the step's own inputs to them
    agree with the step's; each part control does not (the maps' product in
    bfloat16 among them)."""
    xk, tol = kit
    cfg = kit_cfg()
    aux = jax.device_get(system["aux"])
    by_name = {n: np.asarray(train._leaf(params, p))
               for n, p in train.leaf_names(F32)}
    args = (aux, by_name, np.asarray(some_bias()["layers"]), params["head"],
            np.asarray(batch_of(0)[1]), cfg)
    got, want = xk.precision_got(aux, cfg), xk.precision_want(*args)
    assert sorted(got) == sorted(xk.PRECISION)
    assert units(got, want, tol) < 0.05
    assert units(xk.precision_want(*args, variant=variant), want, tol) > 1.0


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The guide's share test: for one dense and one sparse layer from one
    input stream, the two head shares' attention ``y`` add up to the uncut
    reference's, the expert shares' ``y`` with the shared expert counted
    once to the uncut feed-forward's, and ``Hres X`` once plus ``Hpost^T`` of
    those sums is the uncut layer's stream."""
    whole = dataclasses.replace(F32, heads_here=0, experts_here=0,
                                expert_share=0)
    full = built.params(whole, 5)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 32, 4, 64))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(12), (8,))
    nope, hv = F32.qk_nope_head_dim + F32.qk_rope_head_dim, F32.v_head_dim

    def heads(p, share):
        """``p`` with attention's head-wise leaves cut to 2 of 4 heads."""
        cut = lambda w, width, axis: jnp.take(
            w, jnp.arange(2 * share * width, 2 * (share + 1) * width), axis)
        return {**p, "wq_b": cut(p["wq_b"], nope, 1),
                "wkv_b": cut(p["wkv_b"], F32.qk_nope_head_dim + hv, 1),
                "wo": cut(p["wo"], hv, 0)}

    def experts(p, share):
        return {**p, **{k: p[k][2 * share:2 * share + 2]
                        for k in ("gate", "up", "down")}}

    with jax.default_matmul_precision("highest"):
        for group, sparse in (("dense", False), ("layers", True)):
            p = jax.tree.map(lambda a: a[0], full[group])
            pre, post, res = ref.maps(p, x, whole, "hc1")
            u = jnp.einsum("bsn,bsnd->bsd", pre, x)
            parts = sum(ref.attention(heads(p, s), u, whole)
                        for s in (0, 1))
            np.testing.assert_allclose(parts, ref.attention(p, u, whole),
                                       rtol=1e-4, atol=1e-6)
            x1 = ref.mixed(res, post, x, parts)
            pre, post, res = ref.maps(p, x1, whole, "hc2")
            u = jnp.einsum("bsn,bsnd->bsd", pre, x1)
            if sparse:
                shared = ref.swiglu(
                    ref._norm(u, p["ln2"], 1e-6), p["shared_gate"],
                    p["shared_up"], p["shared_down"])
                routed = sum(ref.sparse_mlp(
                    experts(p, s), u, bias, dataclasses.replace(
                        whole, experts_here=2, expert_share=s))[0] - shared
                    for s in range(4))
                y, _ = ref.sparse_mlp(p, u, bias, whole)
                np.testing.assert_allclose(routed + shared, y, rtol=1e-4,
                                           atol=1e-6)
                y = routed + shared
            else:
                y = ref.dense_mlp(p, u, whole)
            want, _ = ref.layer(p, x, bias if sparse else None, whole)
            np.testing.assert_allclose(ref.mixed(res, post, x1, y), want,
                                       rtol=1e-4, atol=1e-5)


TEN = sorted(f for f in os.listdir(CONFIGS)
             if f.endswith("-train-1chip.json") and not f.startswith("xing"))


@pytest.mark.parametrize("name", TEN)
def test_at_one_stream_nothing_moves(name):
    """The ten other model files: no leaf, no plan entry and no scope of
    the residual path (their initial bytes are pinned by
    ``tests/test_model_tree.py``)."""
    from test_model_tree import SMALL, TREES

    assert len(TEN) == 10
    path = os.path.join(CONFIGS, name)
    for cfg in (train.load_model_config(path),
                train.load_model_config(path, **SMALL, **TREES[name][0])):
        assert cfg.hc_mult == 1 and not cfg.rope_scaling \
            and cfg.dense_here == -1
        assert not any("hc" in n.rsplit(".", 1)[-1].split("_")[0]
                       for n, _ in train.leaf_names(cfg))
        plan = train.plan_of(cfg, cfg.micro_batch, cfg.seq_len,
                             interpret=True)
        assert not any("hc" in row for row in plan["rows"])
        assert not any(k.startswith("hc_") for k in plan["counts"])
    ids = np.zeros((cfg.micro_batch, cfg.seq_len + 2), np.int32)
    text = jax.jit(lambda p: objective.model_loss(
        p, jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:]), cfg,
        interpret=True, n_global=ids[:, :-2].size)[0]).lower(
            jax.eval_shape(lambda: built.params(cfg, 0))
    ).as_text(debug_info=True)
    assert "otpu_layers" in text and "otpu_hc" not in text


def xing_body():
    with open(os.path.join(CONFIGS, "xing4.0-29b-a4b-train-1chip.json"),
              encoding="utf-8") as f:
        return json.load(f)


REFUSED = {
    "mtp_here": (dict(mtp_here=1), "mtp_here"),
    "layer_types": (dict(layer_types=["full_attention"] * 40),
                    "layer_types"),
    "hybrid_override_pattern": (dict(hybrid_override_pattern="M" * 40),
                                "hybrid_override_pattern"),
    "total_ut_steps": (dict(total_ut_steps=4), "total_ut_steps"),
    "block_length": (dict(block_length=4, mask_token_here=3),
                     "block_length"),
    "eos_token_here": (dict(eos_token_here=3), "eos_token_here"),
    "sandwich_norm": (dict(sandwich_norm=True), "sandwich_norm"),
    "rope_scaling_linear": (dict(rope_scaling={"type": "linear",
                                               "factor": 4}),
                            "rope_scaling"),
    "rope_scaling_truncate": (dict(rope_scaling={**YARN, "type": "yarn",
                                                 "truncate": False}),
                              "rope_scaling"),
    "heads_here": (dict(heads_here=12), "heads_here"),
    "dense_here": (dict(dense_here=3), "dense_here"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_the_path_does_not_run_is_refused_by_name(tmp_path, case):
    change, key = REFUSED[case]
    body = {**xing_body(), **change}
    path = tmp_path / "xing.json"
    path.write_text(json.dumps(body))
    with pytest.raises((NotImplementedError, ValueError, KeyError),
                       match=key):
        train.load_model_config(str(path))


@pytest.mark.parametrize("name", TEN + ["hc_in_train"])
def test_the_paths_keys_are_an_xing_files_alone(tmp_path, name):
    """``hc_mult`` (or any ``hc_*`` / ``mhc_*`` key) in a file of another
    ``model_type`` is refused by name, at the top level or under ``train``;
    so is YaRN outside latent attention."""
    source = TEN[0] if name == "hc_in_train" else name
    with open(os.path.join(CONFIGS, source), encoding="utf-8") as f:
        body = json.load(f)
    if name == "hc_in_train":
        body["train"]["mhc_h_res_clamp_max"] = 30
        key = "mhc_h_res_clamp_max"
    else:
        body["hc_mult"], key = 4, "hc_mult"
    path = tmp_path / "other.json"
    path.write_text(json.dumps(body))
    with pytest.raises(NotImplementedError, match=key):
        train.load_model_config(str(path))
    if "kv_lora_rank" not in body:
        del body["hc_mult" if name != "hc_in_train" else "train"]
        body["rope_scaling"] = {**YARN, "type": "yarn"}
        path.write_text(json.dumps(body))
        with pytest.raises((NotImplementedError, KeyError),
                           match="rope_scaling|train"):
            train.load_model_config(str(path))
