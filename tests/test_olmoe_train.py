"""OLMoE's training step on the normal path (``parallel/train.py``'s model
path) against the plain reference (``parallel/olmoe_reference.py``) at small
widths on seeded random weights: hidden 64, 4 heads, 8 experts top 2, expert
width 32, vocabulary 256, sequences of 32, 2 layers.  Float32 compute meets
the reference at rtol 1e-5 (sums in another order); bfloat16 compute at the
tolerance its test states.  The benchmark's own copy of the reference
(``benchmark/harness/olmoekit.py``) is held to the same, and its two
deliberately wrong variants must fail."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import olmoe_reference
from ompi_tpu.parallel import config, objective, train
from ompi_tpu.parallel.experts import moe_sorted_block
from ompi_tpu.parallel.mesh import MeshSpec, make_mesh
from ompi_tpu.runtime import spc

import built

ref = built.programs(olmoe_reference)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
WIDTHS = dict(hidden_size=64, intermediate_size=32, num_attention_heads=4,
              num_key_value_heads=4, num_experts=8, num_experts_per_tok=2,
              vocab_size=256, layers_here=2, seq_len=32, micro_batch=2,
              attn_block=16, loss_block_rows=16, lr=1e-2)
F32 = config.ModelConfig(compute_dtype="float32", **WIDTHS)
BF16 = config.ModelConfig(compute_dtype="bfloat16", **WIDTHS)
LEAVES = [name for name, _ in train.leaf_names(F32)]
CLOSE = dict(rtol=1e-5, atol=1e-6)


def batch_of(seed):
    toks = np.random.default_rng(seed).integers(
        0, WIDTHS["vocab_size"], (2, 33)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


@pytest.fixture(scope="module")
def params():
    return built.params(F32, 3)


@pytest.fixture(scope="module")
def reference(params):
    (total, (ce, lb, z, loads)), grads = ref.grads(params, *batch_of(0), F32)
    return dict(total=total, parts=np.asarray([total, ce, lb, z]),
                loads=np.asarray(loads), grads=grads)


def system_loss(params, cfg, batch):
    tokens, labels = batch
    return objective.model_loss(params, tokens, labels, cfg, interpret=True,
                            n_global=tokens.size)


@pytest.fixture(scope="module")
def system(params):
    (total, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: system_loss(p, F32, batch_of(0)), has_aux=True))(params)
    return dict(total=total, aux=aux, grads=grads)


@pytest.mark.parametrize("on_tpu", [False, True], ids=["twins", "kernels"])
def test_a_key_value_head_a_query_head_counts_as_no_shared_one(traced_step,
                                                               on_tpu):
    """Every query head reads a key-value head of its own: the step's
    attention passes are counted, none as one whose k and v are shared,
    and the kernels take k and v as wide as q."""
    got = traced_step(F32, *batch_of(0), on_tpu)
    assert got.built > 0 and got.shared == 0
    forward = got.kernels("otpu_flash_causal_forward")
    assert bool(forward) == on_tpu
    assert all(ins[0][0] == ins[1][0] == ins[2][0] for ins, _ in forward)


def run_steps(cfg, params, seeds, dp=1):
    """Parameters and each step's ``aux`` after one optimiser step a
    seed's batch, through ``build_train_step`` on ``dp`` CPU devices."""
    step, place = built.step(cfg, dp)
    state = None
    out = []
    for seed in seeds:
        tokens, labels = batch_of(seed)
        if state is None:
            state, tokens, labels = place(jax.tree.map(jnp.copy, params),
                                          tokens, labels)
        state, aux = step(state, tokens, labels)
        out.append(aux)
    return state[0], out


def test_forward_logits(params, system):
    """Every row's logsumexp and label logit, which is what the blocked
    head keeps of the (T, V) logits."""
    tokens, labels = batch_of(0)
    with jax.default_matmul_precision("highest"):
        logits, _ = ref.forward(params, tokens, F32)
    logits = logits.reshape(-1, F32.vocab_size)
    want = jnp.stack([jax.nn.logsumexp(logits, -1), jnp.take_along_axis(
        logits, labels.reshape(-1, 1), -1)[:, 0]], -1)
    np.testing.assert_allclose(system["aux"]["rows"], want, **CLOSE)


def test_loss_and_its_three_parts(system, reference):
    np.testing.assert_allclose(system["aux"]["losses"], reference["parts"],
                               **CLOSE)
    np.testing.assert_array_equal(system["aux"]["loads"], reference["loads"])
    assert system["aux"]["loads"].sum() == 2 * 64 * 2    # every slot kept


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf(leaf, system, reference):
    path = dict(train.leaf_names(F32))[leaf]
    got = train._leaf(system["grads"], path)
    want = train._leaf(reference["grads"], path)
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("dp", [1, 2])
def test_parameters_after_three_adamw_steps(dp, params):
    """``dp = 2`` gives the parameters of ``dp = 1`` and of the
    reference on the same global batches.  An entry whose gradient is
    noise (AdamW's first steps move every entry by about ``lr`` whatever
    its gradient's size) may land a fraction of a step away: the limit
    is a hundredth of the three steps taken."""
    seeds = (0, 1, 2)
    got, auxes = run_steps(F32, params, seeds, dp=dp)
    want, losses = ref.train_steps(params, [batch_of(s) for s in seeds], F32)
    np.testing.assert_allclose([a["losses"][0] for a in auxes], losses,
                               rtol=1e-5)
    for name, path in train.leaf_names(F32):
        np.testing.assert_allclose(
            train._leaf(got, path), train._leaf(want, path), rtol=1e-5,
            atol=0.01 * 3 * F32.lr, err_msg=name)


def test_a_step_reports_what_it_counted(params):
    if "train_steps" not in spc.counters():
        spc.init()
    before = spc.read("train_steps")
    _, (aux,) = run_steps(F32, params, (0,))
    # the steps issued; tokens and routed slots are constants times it
    assert spc.read("train_steps") - before == 1
    tokens = F32.micro_batch * F32.seq_len
    assert tokens == 64
    assert tokens * F32.num_experts_per_tok * F32.n_routers == 256
    assert int(np.asarray(aux["loads"]).sum()) == 256
    fullest = train.record_step_stats(aux)
    assert fullest == np.asarray(aux["loads"]).max() >= 16
    assert spc.read("moe_max_expert_load") >= fullest
    assert aux["grad_probe"].shape == (len(LEAVES), train.PROBE)
    assert aux["grad_sq"].shape == (len(LEAVES),)
    assert aux["sample"]["router_in"].shape == (2, objective.SAMPLE_ROWS, 64)
    assert aux["sample"]["head_in"].shape == (objective.SAMPLE_ROWS, 64)


def test_no_token_is_dropped_when_one_expert_takes_a_whole_batch():
    """Every token's first choice is expert 0 (its router column is
    aligned with every normed row): the group is 64 rows long, the other
    groups share the second choices, and the output is what the dense
    reference computes, so nothing fell through."""
    cfg = F32
    rng = np.random.default_rng(5)
    d, e, f = cfg.hidden_size, cfg.num_experts, cfg.intermediate_size
    p = {"ln2": jnp.ones((d,)),
         "router": jnp.asarray(rng.normal(0, 0.02, (d, e)), jnp.float32
                               ).at[:, 0].set(1.0),
         "gate": jnp.asarray(rng.normal(0, 0.2, (e, d, f)), jnp.float32),
         "up": jnp.asarray(rng.normal(0, 0.2, (e, d, f)), jnp.float32),
         "down": jnp.asarray(rng.normal(0, 0.2, (e, f, d)), jnp.float32)}
    x = jnp.asarray(rng.uniform(0.5, 1.5, (2, 32, d)), jnp.float32)
    out, stats, routed = moe_sorted_block(p, x, cfg)
    experts = routed["experts"]
    assert experts.shape == (64, 2) and (experts[:, 0] == 0).all()
    assert stats["slots"][0] == 64 and stats["slots"].sum() == 128
    h = ref._norm(x, p["ln2"], cfg.rms_norm_eps).reshape(64, d)
    probs = jax.nn.softmax(h @ p["router"], -1)
    top_w, top_e = jax.lax.top_k(probs, 2)
    weight = jnp.einsum("tk,tke->te", top_w, jax.nn.one_hot(top_e, e))
    act = jax.nn.silu(jnp.einsum("td,edf->etf", h, p["gate"])) \
        * jnp.einsum("td,edf->etf", h, p["up"])
    want = jnp.einsum("te,etd->td", weight,
                      jnp.einsum("etf,efd->etd", act, p["down"]))
    np.testing.assert_allclose(out.reshape(64, d), want, rtol=1e-5,
                               atol=1e-6)


def test_bfloat16_compute_meets_the_reference_within_its_tolerance(
        params, reference):
    """bfloat16 matmul inputs carry 8 bits, so a product is off by up to
    2**-8 of itself and the losses, of order 5, by under 2e-2.  A
    gradient leaf is held by its relative L2 error: 2% where every
    token reaches the leaf, 15% for the sparse MLP's leaves, where one
    top-2 choice of the 128 that bfloat16 flips at a near-tie moves
    whole rows of an expert's gradient (at 8,192 tokens a flip is a
    thousandth of a group; the benchmark's tolerance is set there)."""
    (total, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: system_loss(p, BF16, batch_of(0)), has_aux=True))(params)
    np.testing.assert_allclose(aux["losses"], reference["parts"], atol=2e-2,
                               rtol=1e-2)
    assert not np.allclose(aux["losses"], reference["parts"], **CLOSE)
    sparse = ("ln2", "router", "gate", "up", "down")
    for name, path in train.leaf_names(F32):
        got, want = train._leaf(grads, path), train._leaf(
            reference["grads"], path)
        off = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert 0 < off < (0.15 if name in sparse else 0.02), (name, off)


def test_a_mesh_that_shards_the_model_is_refused(params):
    mesh, spec = make_mesh(jax.devices()[:2], MeshSpec(tp=2))
    with pytest.raises(NotImplementedError, match="dp only"):
        train.build_train_step(mesh, spec, model=F32)


def test_the_configuration_file_gives_the_published_widths():
    cfg = train.load_model_config(os.path.join(
        BENCH, "configs", "olmoe-1b-7b-train-1chip.json"))
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.intermediate_size, cfg.vocab_size,
            cfg.seq_len, cfg.micro_batch, cfg.layers_here) == (
        2048, 16, 64, 8, 1024, 50304, 4096, 2, 1)
    shapes = train.model_param_shapes(cfg)
    count = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert count == 625_616_896         # 10.0 GB at 16 bytes a parameter


# -- the benchmark's own copy of the reference ------------------------------
@pytest.fixture(scope="module")
def kit():
    sys.path.insert(0, BENCH)
    try:
        from harness import olmoekit
        yield olmoekit
    finally:
        sys.path.remove(BENCH)


KIT_CFG = {**WIDTHS, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
           "aux_loss_coef": 0.01, "z_loss_coef": 0.001}


def kit_step(kit, params, wrong=None):
    return kit.reference_step(params, *batch_of(0), KIT_CFG, tuple(LEAVES),
                              wrong)


def test_the_benchmarks_reference_is_the_repos(kit, params, reference):
    out = kit_step(kit, params)
    np.testing.assert_allclose(out["losses"], reference["parts"], **CLOSE)
    np.testing.assert_array_equal(out["loads"], reference["loads"])
    for name, path in train.leaf_names(F32):
        want = train._leaf(reference["grads"], path)
        np.testing.assert_allclose(
            out["grads"][name], want, rtol=1e-5,
            atol=1e-5 * float(jnp.abs(want).max()), err_msg=name)


KIT_TRAIN = {**KIT_CFG, "adam_b1": 0.9, "adam_b2": 0.95, "adam_eps": 1e-8,
             "weight_decay": 0.1, "compute_dtype": "float32"}


@pytest.mark.parametrize("dp", [1, 2])
def test_a_step_reports_what_the_benchmarks_reference_computes(dp, kit,
                                                               params):
    """The quantities the benchmark's check compares, leaf by leaf, from
    the step's raw statistics (on two shards the same as on one), and
    the updated parameters."""
    _, (aux,) = run_steps(F32, params, (0,), dp=dp)
    aux = jax.device_get(aux)
    out = kit_step(kit, params)
    got = kit.compared(kit.step_stats(aux), KIT_TRAIN, tuple(LEAVES))
    want = kit.compared(out, KIT_TRAIN, tuple(LEAVES))
    for key in ("losses", "load_share", "row_means", "route_regret",
                "grad_log_rms"):
        np.testing.assert_allclose(got[key], want[key], rtol=1e-5,
                                   atol=1e-5, err_msg=key)
    np.testing.assert_allclose(got["grad_probe"], want["grad_probe"],
                               rtol=1e-4, atol=1e-4)
    for i, name in enumerate(LEAVES):
        p = kit.leaf_of(params, name)
        assert p.size == kit.leaf_sizes(KIT_TRAIN)[name]
        new = kit.adamw_leaf(name, p, out["grads"][name], KIT_TRAIN)
        pos = kit.probe_positions(name, p.size)
        np.testing.assert_allclose(
            aux["param_probe"][i], new.reshape(-1)[pos], rtol=1e-5,
            atol=0.01 * F32.lr, err_msg=name)


def test_a_float32_part_is_told_from_a_bfloat16_one(kit, params):
    """The router's logits, their logsumexp and chosen probabilities and
    the head's rows, recomputed from the step's own inputs to each part:
    the step's are within a twentieth of the benchmark's tolerance, and
    each part as bfloat16 would have made it is outside it."""
    _, (aux,) = run_steps(F32, params, (0,))
    aux = jax.device_get(aux)
    labels = batch_of(0)[1]
    args = (aux, params["layers"]["router"], params["head"], labels,
            KIT_TRAIN)
    got = kit.precision_got(aux, KIT_TRAIN)
    want = kit.precision_want(*args)
    low = kit.precision_want(*args, lowered=True)
    assert set(got) == {"router_logits", "router_lse", "router_weights",
                        "head_rows"}
    for key in got:
        tol = 0.005 + 0.000375 * np.abs(want[key])
        assert got[key].shape == want[key].shape
        assert (np.abs(got[key] - want[key]) < 0.05 * tol).all(), key
        assert (np.abs(low[key] - want[key]) > tol).any(), key


@pytest.mark.parametrize("wrong", ["renorm", "qknorm_per_head"])
def test_a_wrong_variant_fails_the_comparison(wrong, kit, params, system):
    """Top-k weights renormalised, or QK-norm after the head split: the
    loss and the gradients move far outside what the right model is
    held to."""
    out = kit_step(kit, params, wrong)
    assert not np.allclose(system["aux"]["losses"], out["losses"], **CLOSE)
    assert abs(float(system["aux"]["losses"][1] - out["losses"][1])) > 1e-4
    leaf = {"renorm": "down", "qknorm_per_head": "wq"}[wrong]
    got = train._leaf(system["grads"], dict(train.leaf_names(F32))[leaf])
    want = out["grads"][leaf]
    assert float(jnp.abs(got - want).max()) > 0.05 * float(
        jnp.abs(want).max())
