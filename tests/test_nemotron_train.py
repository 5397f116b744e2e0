"""Nemotron-3-Super's training step on the normal path (``parallel/train
.py``'s model path under a ``hybrid_override_pattern``: Mamba-2 mixers,
grouped-query attention without RoPE, relu2 experts in a latent beside a
shared one, sigmoid routing under a balancing bias, one sublayer a layer)
against the plain reference (``parallel/nemotron_reference.py``: the
state-space layer as the token-by-token recurrence) at small widths on
seeded random weights: hidden 64, 16 Mamba heads of 8 in 8 groups with a
state of 8 and chunks of 8, 16 query heads of 4 reading 2 key-value
heads, 32 experts of width 24 in a latent of 16 beside a shared one of
48, top 3; held here: 4 Mamba heads (2 groups), 4 query heads, 8 experts
(share 1 of 4), 64 of 512 ids, the six layers ``MEMEM*`` of the pattern.
Float32 compute meets the reference at rtol 1e-5."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import (attention, config, experts, mamba, objective,
                               train)
from ompi_tpu.parallel import nemotron_reference
from ompi_tpu.parallel.mesh import MeshSpec, make_mesh
from ompi_tpu.runtime import spc

import built

ref = built.programs(nemotron_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
PUBLISHED = dict(
    hidden_size=64, intermediate_size=24, num_attention_heads=16,
    num_key_value_heads=2, num_experts=32, num_experts_per_tok=3,
    vocab_size=512, hybrid_override_pattern="EMEMEM*EMEM*", mamba_num_heads=16,
    mamba_head_dim=8, n_groups=8, ssm_state_size=8, conv_kernel=4,
    chunk_size=8, moe_latent_size=16, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_shared_experts=1,
    mlp_hidden_act="relu2", scoring_func="sigmoid", topk_method="noaux_tc",
    routed_scaling_factor=5.0, norm_topk_prob=True,
    num_nextn_predict_layers=1)
SHARE = dict(layers_here=6, first_layer_here=1, heads_here=4,
             mamba_heads_here=4, experts_here=8, expert_share=1,
             vocab_here=64, mtp_here=0)
TRAIN = dict(seq_len=32, micro_batch=2, attn_block=16, loss_block_rows=16,
             lr=1e-2, aux_loss_coef=0.0, z_loss_coef=0.0,
             bias_update_gamma=0.001)
F32 = config.ModelConfig(compute_dtype="float32", **PUBLISHED, **SHARE,
                        **TRAIN)
NAMES = train.leaf_names(F32)
CLOSE = dict(rtol=1e-5, atol=1e-6)


def batch_of(seed, vocab=64):
    """(inputs (2, 32), labels (2, 33)) from 34 ids a sequence: the
    batch's form for a model with a next-next-token head, of which this
    one reads the first 32 labels."""
    ids = np.random.default_rng(seed).integers(0, vocab, (2, 34)).astype(
        np.int32)
    return jnp.asarray(ids[:, :-2]), jnp.asarray(ids[:, 1:])


def some_bias(cfg=F32, scale=0.01):
    return {"layers": scale * jax.random.normal(
        jax.random.PRNGKey(7), (cfg.n_sparse_here, cfg.num_experts))}


def layer_of(cfg, kind, seed=5):
    """One layer's leaves of ``kind`` drawn as ``init_model_params``
    would, the matrices wide enough (0.3) that every part matters."""
    shapes = train.pattern_layer_shapes(cfg)[kind]
    wide = dataclasses.replace(cfg, init_std=0.3)
    tree = train.init_model_params(dataclasses.replace(
        wide, hybrid_override_pattern="M*E", first_layer_here=0,
        layers_here=3), seed)["layers"]
    (group,) = [g[kind] for g in tree.values() if kind in g]
    assert {k: v.shape[1:] for k, v in group.items()} == shapes
    return jax.tree.map(lambda a: a[0], group)


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
@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("length", [32, 27, 5])
def test_the_chunked_scan_is_the_recurrence(length, groups):
    """Forward and every input's gradient, at lengths that are whole
    chunks of 8, that are not, and that are less than one."""
    keys = jax.random.split(jax.random.PRNGKey(length + groups), 5)
    heads, hd, n = 4, 8, 8
    x = jax.random.normal(keys[0], (2, length, heads, hd))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (2, length, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b, c = (jax.random.normal(k, (2, length, groups, n)) for k in keys[3:])
    probe = jax.random.normal(keys[0], (2, length, heads, hd))
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda *args: jnp.sum(ref.recurrence(*args) * probe),
            argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda *args: jnp.sum(mamba.ssd_chunked(*args, 8) * probe),
        argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    close(got, want, rtol=1e-4)
    for g, w in zip(got_g, want_g):
        near(g, w)
    close(built.program(mamba.ssd_chunked)(x, dt, a, b, c, 8),
          ref.recurrence(x, dt, a, b, c), rtol=1e-4, atol=1e-5)


def test_a_long_decay_does_not_overflow_the_chunk():
    """A step of 30 a position: ``exp`` of a running sum's difference
    above the diagonal would overflow if it were ever taken."""
    x = jnp.ones((1, 16, 2, 4))
    dt = jnp.full((1, 16, 2), 30.0)
    a = -jnp.array([3.0, 5.0])
    b = c = jnp.ones((1, 16, 1, 4))
    y, g = jax.jit(jax.value_and_grad(
        lambda dt: jnp.sum(mamba.ssd_chunked(x, dt, a, b, c, 8))))(dt)
    assert np.isfinite(np.asarray(y)) and np.all(np.isfinite(np.asarray(g)))
    close(built.program(mamba.ssd_chunked)(x, dt, a, b, c, 8),
          ref.recurrence(x, dt, a, b, c))


@pytest.mark.parametrize("groups_here", [1, 2])
def test_the_mixer_is_the_references(groups_here):
    cfg = dataclasses.replace(F32, mamba_heads_here=2 * groups_here)
    p = layer_of(cfg, "mamba")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 27, 64))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(ref.mixer(p, x, cfg) * probe),
            argnums=(0, 1)))(p, x)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(mamba.mamba_mixer(p, x, cfg)[0] * probe),
        argnums=(0, 1)))(p, x)
    close(got, want, rtol=1e-4)
    for k in p:
        near(got_g[0][k], want_g[0][k], err_msg=k)
    near(got_g[1], want_g[1])


@pytest.mark.parametrize("heads,kv,here", [(16, 4, 8), (16, 4, 2),
                                           (16, 1, 16), (32, 2, 4)],
                         ids=["4to1-two-kv", "4to1-part-of-one",
                              "16to1", "16to1-quarter"])
def test_grouped_query_attention_is_the_references(heads, kv, here):
    cfg = dataclasses.replace(F32, hidden_size=64, num_attention_heads=heads,
                              num_key_value_heads=kv, heads_here=here)
    p = layer_of(cfg, "attn")
    assert p["wk"].shape == (64, max(1, here * kv // heads) * 64 // heads)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(ref.attention(p, x, cfg) * probe),
            argnums=(0, 1)))(p, x)
    got, got_g = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(attention.gqa_attention(
            p, x, cfg, interpret=True)[0] * probe), argnums=(0, 1)))(p, x)
    close(got, want, rtol=1e-4)
    for k in p:
        near(got_g[0][k], want_g[0][k], err_msg=k)
    near(got_g[1], want_g[1])


def test_the_latent_block_under_uneven_routing():
    """A bias that sends every token to expert 9 (held) and none to
    expert 10 (held): the block keeps every slot, its output and every
    gradient are the reference's, expert 10's gradient is zero."""
    p = layer_of(F32, "moe")
    bias = jnp.zeros((32,)).at[9].set(10.0).at[10].set(-10.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
    with jax.default_matmul_precision("highest"):
        (want, load), want_g = jax.jit(jax.value_and_grad(
            lambda p, x: (lambda y, load: (jnp.sum(y * probe), load))(
                *ref.experts(p, x, bias, F32)),
            argnums=(0, 1), has_aux=True))(p, x)
    (got, stats), got_g = jax.jit(jax.value_and_grad(
        lambda p, x: (lambda y, st, _: (jnp.sum(y * probe), st))(
            *experts.moe_latent_block(p, x, F32, bias)),
        argnums=(0, 1), has_aux=True))(p, x)
    assert load[9] == 64 and load[10] == 0
    # the hot expert's group alone is more than one of the loop's chunks
    assert 64 > experts.chunk_rows(
        64, F32.num_experts_per_tok, F32.n_experts_here, F32.num_experts)
    close(stats["slots"], load)
    close(got, want, rtol=1e-4)
    for k in p:
        near(got_g[0][k], want_g[0][k], err_msg=k)
    near(got_g[1], want_g[1])
    here = 10 - F32.first_expert_here
    assert not np.any(np.asarray(got_g[0]["up"][here]))
    assert np.any(np.asarray(got_g[0]["up"][here - 1]))


# -- the share and the model --------------------------------------------------------
def test_the_head_shares_add_up_to_the_uncut_layers():
    """The 8 head shares of a mixer (2 heads and their group each) and
    of an attention layer (2 query heads and the key-value head they
    read) add up to the uncut layer: what a tensor-parallel group's
    all-reduce would make of them."""
    whole = dataclasses.replace(F32, heads_here=0, mamba_heads_here=0)
    part = dataclasses.replace(F32, heads_here=2, mamba_heads_here=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 27, 64))
    p = layer_of(whole, "mamba")
    inner, gn = 16 * 8, 8 * 8
    cols = lambda j, lo, width: np.arange(lo + j * width, lo + (j + 1) * width)
    total = 0.0
    for j in range(8):
        inside = np.concatenate([cols(j, 0, 16), cols(j, inner, 8),
                                 cols(j, inner + gn, 8)])    # of x | B | C
        proj = np.concatenate([cols(j, 0, 16), inner + inside,
                               cols(j, 2 * inner + 2 * gn, 2)])
        mine = {"norm": p["norm"], "in_proj": p["in_proj"][:, proj],
                "conv_w": p["conv_w"][:, inside],
                "conv_b": p["conv_b"][inside],
                "gate_norm": p["gate_norm"][cols(j, 0, 16)],
                "out_proj": p["out_proj"][cols(j, 0, 16)],
                **{k: p[k][cols(j, 0, 2)]
                   for k in ("dt_bias", "A_log", "D")}}
        total = total + mamba.mamba_mixer(mine, x, part)[0]
    with jax.default_matmul_precision("highest"):
        close(total, ref.mixer(p, x, whole), rtol=1e-4, atol=1e-5)
    p = layer_of(whole, "attn")
    total, x = 0.0, x[:, :16]
    for j in range(8):
        q, kv = cols(j, 0, 8), cols(j // 4, 0, 4)
        mine = {"ln1": p["ln1"], "wq": p["wq"][:, q], "wk": p["wk"][:, kv],
                "wv": p["wv"][:, kv], "wo": p["wo"][q]}
        total = total + attention.gqa_attention(mine, x, part,
                                            interpret=True)[0]
    with jax.default_matmul_precision("highest"):
        close(total, ref.attention(p, x, whole), rtol=1e-4, atol=1e-5)


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """The 16 expert shares of an expert layer (2 of 32 each), the
    shared expert counted once and each share's routed part through the
    latent's up projection, add up to the uncut reference's layer."""
    whole = dataclasses.replace(F32, experts_here=0, expert_share=0)
    p = layer_of(whole, "moe")
    bias = some_bias()["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(p, x, bias, whole)
        h = ref._norm(x, p["ln2"], whole.rms_norm_eps)
        shared = ref.relu2(h, p["shared_up"], p["shared_down"])
    total = shared
    for j in range(16):
        part = dataclasses.replace(F32, experts_here=2, expert_share=j)
        mine = {**p, "up": p["up"][2 * j:2 * j + 2],
                "down": p["down"][2 * j:2 * j + 2]}
        total = total + experts.moe_latent_block(mine, x, part, bias)[0] - shared
    close(total, want, rtol=1e-4, atol=1e-5)


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
                want=want, counted=counted)


@pytest.mark.parametrize("on_tpu", [False, True], ids=["twins", "kernels"])
def test_the_step_holds_no_k_or_v_a_query_head(traced_step, on_tpu):
    """The 4 query heads held here read 1 key-value head, one attention
    layer, 2 x 32 positions at a head width of 4 (``traced_step``'s
    ``holds_no_repeat`` says what is held)."""
    assert (F32.n_heads_here, F32.n_kv_heads_here) == (4, 1)
    traced_step(F32, *batch_of(0), on_tpu).holds_no_repeat(2, 4, 1, 32, 4)


def test_the_pattern_is_walked_in_runs_of_like_layers():
    assert F32.pattern_here == "MEMEM*"
    assert F32.segments == (("ME", 2, 0), ("M", 1, 4), ("*", 1, 5))
    period = dataclasses.replace(F32, hybrid_override_pattern="MEMEMEMEM*E",
                                 first_layer_here=0, layers_here=11)
    assert period.segments == (("ME", 4, 0), ("M", 1, 8), ("*", 1, 9),
                               ("E", 1, 10))
    assert (period.n_sparse_here, period.n_routers) == (5, 5)
    assert [n for n, _ in NAMES][:3] == [
        "embed", "l0.mamba.norm", "l0.mamba.in_proj"]
    assert train.model_param_shapes(F32)["layers"]["l0"]["moe"]["up"] \
        == (2, 8, 16, 24)


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
    # the steps issued; the state-space layers' tokens in them are the
    # configuration's constant (tokens x M layers held) times it
    assert stepped["counted"] == 3
    assert F32.micro_batch * F32.seq_len * F32.pattern_here.count("M") \
        == 64 * 3


def test_one_step_reports_the_references_loads_and_gradients(stepped):
    tokens, labels = stepped["batches"][0]
    aux = stepped["auxes"][0]
    (loss, loads), g = ref.grads(stepped["params"], tokens, labels, F32,
                                 ref.zero_bias(F32))
    close(aux["losses"][:2], [loss, loss])
    close(aux["loads"], loads)
    assert aux["loads"].shape == (2, 32) and aux["experts"].shape \
        == (2, 64, 3)
    first = F32.first_expert_here
    assert aux["local_slots"] == loads[:, first:first + 8].sum()
    for (name, path), sq, probe in zip(NAMES, aux["grad_sq"],
                                       aux["grad_probe"]):
        leaf = np.asarray(train._leaf(g, path))
        close(sq, np.sum(leaf * leaf), rtol=1e-4, err_msg=name)
        near(probe, leaf.reshape(-1)[train.probe_positions(
            name, leaf.size)], err_msg=name)


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


def test_what_the_checkpoint_keeps_changes_no_number(monkeypatch):
    """A layer's checkpoint keeps an expert block's routing results
    (``experts.CHECKPOINT_KEEPS``): the backward pass reads them and does not
    make them again, so every gradient entry, and what a step reports, is
    bit for bit what the bare checkpoint (nothing kept) gives."""
    tokens, labels = batch_of(4)
    params, bias = built.params(F32, 11), some_bias()

    def grads():
        return jax.jit(jax.value_and_grad(
            lambda ps: objective.model_loss(ps, tokens, labels, F32,
                                        interpret=True, n_global=64,
                                        bias=bias), has_aux=True))(params)

    def one_step():
        mesh, spec = make_mesh(jax.devices()[:1], MeshSpec(dp=1))
        step, place = train.build_train_step(mesh, spec, model=F32)
        state, _, _ = place(jax.tree.map(jnp.copy, params), tokens, labels)
        return jax.device_get(step(state, tokens, labels)[1])

    (loss, aux), got = grads()
    stepped = one_step()
    monkeypatch.setattr(objective, "layer_checkpoint_policy",
                        lambda: jax.checkpoint_policies.nothing_saveable)
    (bare_loss, bare_aux), bare = grads()
    bare_stepped = one_step()
    assert loss == bare_loss
    entries = 0
    for name, path in NAMES:
        leaf = np.asarray(train._leaf(got, path))
        np.testing.assert_array_equal(leaf, train._leaf(bare, path), name)
        entries += leaf.size
    assert entries > 50_000
    for key in ("losses", "loads", "experts"):
        np.testing.assert_array_equal(aux[key], bare_aux[key], key)
        np.testing.assert_array_equal(stepped[key], bare_stepped[key], key)
    np.testing.assert_array_equal(stepped["grad_sq"], bare_stepped["grad_sq"])


def test_no_gain_and_no_mixer_scalar_is_decayed():
    undecayed = {n for n, _ in NAMES if not train.is_decayed(n)}
    assert undecayed == {n for n, _ in NAMES if n.rsplit(".", 1)[-1] in (
        "norm", "gate_norm", "ln1", "ln2", "final_norm", "conv_b", "A_log",
        "D", "dt_bias")}
    p, g = jnp.full((4,), 2.0), jnp.zeros((4,))
    for name, moved in (("l0.mamba.A_log", False), ("l0.mamba.D", False),
                        ("l0.mamba.dt_bias", False),
                        ("l0.mamba.gate_norm", False),
                        ("l0.mamba.conv_b", False),
                        ("l0.mamba.conv_w", True),
                        ("l0.mamba.in_proj", True)):
        new, _, _ = train.adamw(F32, name, p, g, g, g, jnp.float32(1.0))
        assert bool(np.any(np.asarray(new) != 2.0)) == moved, name


def test_a_mixers_leaves_start_as_its_authors_start_them():
    params = built.params(F32, 3)["layers"]["l0"]["mamba"]
    assert np.all(np.asarray(params["D"]) == 1.0)
    assert np.all(np.asarray(params["gate_norm"]) == 1.0)
    a = np.exp(np.asarray(params["A_log"]))
    assert np.all((a >= 1.0) & (a <= 16.0))
    step = np.log1p(np.exp(np.asarray(params["dt_bias"])))
    assert np.all((step >= 0.00099) & (step <= 0.101))
    assert np.abs(np.asarray(params["conv_w"])).max() <= 0.5
    assert params["conv_w"].shape == (2, 4, 4 * 8 + 2 * 2 * 8)


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


# -- what the path reads and what it refuses ----------------------------------------------
def test_the_benchmarks_configuration_loads_at_its_published_widths():
    cfg = train.load_model_config(os.path.join(
        BENCH, "configs", "nemotron3-super-train-1chip.json"))
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.n_groups, cfg.ssm_state_size, cfg.chunk_size,
            cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_latent_size,
            cfg.expert_width, cfg.moe_shared_expert_intermediate_size,
            cfg.routed_scaling_factor, cfg.vocab_size,
            cfg.num_nextn_predict_layers) == (
        4096, 32, 2, 128, 64, 8, 128, 128, 512, 22, 1024, 2688, 5376, 5,
        131072, 1)
    assert cfg.pattern_here == "MEMEMEMEM*E"
    assert (cfg.n_heads_here, cfg.n_kv_heads_here, cfg.n_mamba_heads_here,
            cfg.n_groups_here, cfg.n_experts_here, cfg.first_expert_here,
            cfg.vocab_rows, cfg.n_mtp_here, cfg.seq_len, cfg.micro_batch) \
        == (4, 1, 16, 1, 8, 0, 16384, 0, 8192, 1)
    shapes = train.model_param_shapes(cfg)
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 700_862_960             # 11.2 GB at 16 bytes each
    kinds = train.pattern_layer_shapes(cfg)
    per = {k: sum(int(np.prod(s)) for s in v.values())
           for k, v in kinds.items()}
    assert per == {"mamba": 13_708_592, "attn": 5_246_976,
                   "moe": 98_570_240}


REFUSED = [
    (dict(heads_here=12), "heads_here"),
    (dict(mamba_heads_here=3), "mamba_heads_here"),
    (dict(n_group=2), "n_group"),
    (dict(topk_group=2), "topk_group"),
    (dict(mtp_here=1), "mtp_here"),
    (dict(mtp_here=-1), "mtp_here"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(hybrid_override_pattern="", first_layer_here=0,
          mlp_hidden_act="silu", moe_latent_size=0), "num_key_value_heads"),
]


@pytest.mark.parametrize("change,key", REFUSED,
                         ids=[f"{k}-{i}" for i, (_, k) in enumerate(REFUSED)])
def test_what_the_path_cannot_run_is_refused_by_its_key(change, key):
    with pytest.raises(NotImplementedError, match=key):
        dataclasses.replace(F32, **change)


def test_layers_outside_the_pattern_are_refused():
    with pytest.raises(ValueError, match="layers_here"):
        dataclasses.replace(F32, first_layer_here=9)
    with pytest.raises(ValueError, match="letters"):
        dataclasses.replace(F32, hybrid_override_pattern="M-MEMEM*EMEM*")


@pytest.mark.parametrize("key,value", [
    ("mlp_hidden_act", "silu"), ("use_conv_bias", False),
    ("mamba_proj_bias", True), ("mlp_bias", True), ("head_dim", 64),
    ("expand", 4), ("sliding_window", 4096), ("n_group", 8)])
def test_a_published_file_the_path_cannot_run_is_refused(tmp_path, key,
                                                          value):
    with open(os.path.join(BENCH, "configs",
                           "nemotron3-super-train-1chip.json")) as f:
        body = json.load(f)
    path = tmp_path / "changed.json"
    path.write_text(json.dumps({**body, key: value}))
    with pytest.raises(NotImplementedError):
        train.load_model_config(str(path))


# -- the benchmark's own copy of the reference -----------------------------------------
@pytest.fixture(scope="module")
def kit():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import nemotronkit
        yield nemotronkit
    finally:
        sys.path.remove(BENCH)


KIT_CFG = {**PUBLISHED, **SHARE, **TRAIN, "n_routed_experts": 32,
           "layer_norm_epsilon": 1e-5, "rope_theta": 10000.0,
           "compute_dtype": "float32"}


def test_the_kit_names_the_programs_leaves(kit):
    assert kit.leaves(KIT_CFG) == tuple(n for n, _ in NAMES)
    assert set(kit.checked(KIT_CFG)) <= set(kit.leaves(KIT_CFG))
    shapes = train.model_param_shapes(F32)
    assert kit.leaf_sizes(KIT_CFG) == {
        n: int(np.prod(train._leaf(shapes, p))) for n, p in NAMES}
    assert "l0.mamba.A_log" in kit.CHECKED and "l9.attn.wk" in kit.CHECKED \
        and "l10.moe.down" in kit.CHECKED and "l8.mamba.out_proj" \
        in kit.CHECKED and len(kit.LEAVES) == 1 + 17 + 9 + 5 + 8 + 2


def test_the_kits_reference_is_the_repositorys(kit):
    tokens, labels = batch_of(4)
    params, bias = built.params(F32, 11), some_bias()
    (loss, loads), want = ref.grads(params, tokens, labels, F32, bias)
    wrt = kit.checked(KIT_CFG)
    got = kit.reference_step(params, tokens, labels, KIT_CFG, bias, wrt)
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
    params, bias = built.params(F32, 11), some_bias(scale=0.3)
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

    def units(side, want):
        return {k: float(np.max(np.abs(np.float64(side[k]) - want[k])
                                / (0.005 + 0.000375 * np.abs(want[k]))))
                for k in side}

    def whole(**kw):
        out = jax.device_get({k: v for k, v in kit.reference_step(
            params, tokens, labels, KIT_CFG, bias, wrt,
            routed=aux["experts"], **kw).items() if k != "grads"})
        return kit.compared(out, KIT_CFG, wrt)

    def parts(variant=None):
        return kit.precision_want(aux, by_name, bias["layers"],
                                  params["head"], labels, KIT_CFG,
                                  variant=variant)

    want = {**whole(), **parts()}
    assert set(want) == set(kit.OUTPUTS + kit.PRECISION) == set(got)
    assert max(units(got, want).values()) < 0.02, units(got, want)
    for wrong in kit.WRONG:
        assert max(units(whole(wrong=wrong), want).values()) > 1, wrong
    assert max(units(whole(low=True), want).values()) > 0.02
    for variant, part in (("bf16", "router_logits"), ("scan_bf16", "ssm_y"),
                          ("bias_in_weights", "router_weights"),
                          ("softmax", "router_scores")):
        assert units(parts(variant), want)[part] > 1, variant
    assert set(kit.PART_CONTROLS) == {"bf16", "scan_bf16",
                                      "bias_in_weights", "softmax"}


def test_the_kit_counts_the_published_steps_operations(kit):
    cfg = kit.load_config(os.path.join(
        BENCH, "configs", "nemotron3-super-train-1chip.json"))
    per = kit.matmul_params_per_token(cfg)
    assert per["mamba_proj"] == 4096 * 2320 + 1024 * 4096
    assert per["attn_proj"] == 2 * 4096 * 512 + 2 * 4096 * 128
    assert per["experts_mean"] == 2 * 1024 * 2688 * 22 * 8 / 512
    flops = kit.step_flops(cfg)
    assert abs(flops["step"] / 1e12 - 21.1) < 0.05
    assert abs((flops["shared"] + flops["latent_proj"] + flops["router"]
                + flops["experts"]) / flops["step"] - 0.66) < 0.02
    assert flops["flash_forward"] == 4 * 256 * 8192 * 8192
    assert sum(kit.leaf_sizes(cfg).values()) == 700_862_960
