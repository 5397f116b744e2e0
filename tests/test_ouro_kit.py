"""The benchmark's own copy of the reference of Ouro-2.6B's looped training
step (``benchmark/harness/ourokit.py``) against the repository's
(``parallel/ouro_reference.py``), what the ``train_step_kit`` kind compares
of a step of the program in the kit's units with every control outside the
tolerance, and the kit's count of the published step's operations; at
``tests/test_ouro_train.py``'s small widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import train

from test_ouro_train import (BENCH, CONFIG, F32, NAMES, SMALL, batch_of,
                             close, near, ref_grads, spread_params)
import built


@pytest.fixture(scope="module")
def kit():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import ourokit
        yield ourokit
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def kit_cfg(kit):
    return {**kit.load_config(CONFIG), **SMALL, "vocab_here": 256}


def test_the_kit_names_the_programs_leaves(kit, kit_cfg):
    assert kit.leaves(kit_cfg) == tuple(n for n, _ in NAMES)
    # every leaf but the gate's bias, one number whose terms cancel
    assert kit.checked(kit_cfg) == kit.leaves(kit_cfg)[:-1]
    assert kit.probed(kit_cfg) == tuple(
        n for n in kit.checked(kit_cfg) if n != "embed")
    shapes = train.model_param_shapes(F32)
    assert kit.leaf_sizes(kit_cfg) == {
        n: int(np.prod(train._leaf(shapes, p))) for n, p in NAMES}
    params = built.params(F32, 0)
    tree = kit.tree_of({n: kit.leaf_of(params, n)
                        for n in kit.leaves(kit_cfg)})
    assert jax.tree.structure(tree) == jax.tree.structure(params)
    whole = kit.load_config(CONFIG)
    assert whole["vocab_here"] == 49152 and whole["n_routed_experts"] == 0 \
        and kit.zipf_cdf(whole["vocab_here"]).shape == (49152,)


def test_the_kits_reference_is_the_repositorys(kit, kit_cfg):
    tokens, labels = batch_of(4)
    params = spread_params(F32, 11)
    (total, (by_pass, expected, bonus, p)), want = ref_grads(
        params, tokens, labels, F32)
    wrt = kit.checked(kit_cfg)
    got = kit.reference_step(params, tokens, labels, kit_cfg, {}, wrt)
    close(got["losses"], [total, *by_pass, expected, bonus], rtol=2e-5)
    close(got["exit_mean"], np.asarray(p).mean(axis=(1, 2)))
    assert got["rows"].shape == (128, 4, 2)
    for name in wrt:
        near(got["grads"][name], kit.leaf_of(want, name), rel=1e-4,
             err_msg=name)
    assert set(kit.WRONG) == {"one_pass", "no_pass_norm", "last_pass_loss",
                              "uniform_exit", "no_post_norm"}
    for wrong in kit.WRONG:
        other = kit.reference_step(params, tokens, labels, kit_cfg, {}, wrt,
                                   wrong=wrong)
        assert abs(float(other["losses"][0]) - float(total)) \
            > 2e-4 * abs(float(total)), wrong
        assert other["losses"].shape == (7,) \
            and other["rows"].shape == (128, 4, 2)
    uniform = kit.reference_step(params, tokens, labels, kit_cfg, {}, wrt,
                                 wrong="uniform_exit")
    assert not np.any(np.asarray(uniform["grads"]["exit_gate.w"]))
    close(uniform["exit_mean"], [0.25] * 4)


def test_the_kit_compares_a_step_of_the_program_within_its_tolerance(
        kit, kit_cfg):
    """What the kind does on the chip, here in float32: the step's
    statistics and float32 parts in the kit's units lie within a fiftieth
    of the tolerance of the reference's; every control lies outside it."""
    tokens, labels = batch_of(4)
    params = spread_params(F32, 11)
    step, place = built.step(F32)
    state, t, l = place(jax.tree.map(jnp.copy, params), tokens, labels)
    state, aux = step(state, t, l)
    aux = jax.device_get(aux)
    wrt = kit.checked(kit_cfg)
    bias = jax.device_get(state[4])
    got = {**kit.compared(kit.step_stats(aux, bias, kit_cfg), kit_cfg, wrt),
           **kit.precision_got(aux, kit_cfg)}
    assert float(aux["local_slots"]) == 0.0     # what kit_check.py prints
    by_name = {n: np.asarray(kit.leaf_of(params, n))
               for n in kit.leaves(kit_cfg)}
    tree = kit.tree_of(by_name)

    def units(side, want):
        return {k: float(np.max(np.abs(np.float64(side[k]) - want[k])
                                / (0.005 + 0.000375 * np.abs(want[k]))))
                for k in side}

    out = jax.device_get({k: v for k, v in kit.reference_step(
        tree, tokens, labels, kit_cfg, bias, wrt,
        routed=aux["experts"]).items() if k != "grads"})

    def parts(variant=None):
        return kit.precision_want(aux, by_name, bias["layers"],
                                  jnp.asarray(tree["head"]),
                                  np.asarray(labels), kit_cfg,
                                  variant=variant)

    want = {**kit.compared(out, kit_cfg, wrt), **parts()}
    assert set(want) == set(kit.OUTPUTS + kit.PRECISION) == set(got)
    off = units(got, want)
    assert max(off.values()) < 0.02, off
    assert want["losses"].shape == (7,) \
        and want["lse_means"].shape == (4, 4) \
        and want["label_means"].shape == (4, 4) \
        and want["head_rows"].shape == (16, 4, 2) \
        and want["rope_qk"].shape == (8, 16, 32) \
        and want["exit_p"].shape == (16, 4)
    assert units(parts("bf16"), want)["head_rows"] > 1
    for variant, part in (("one_pass", "losses"),
                          ("no_pass_norm", "losses"),
                          ("last_pass_loss", "losses"),
                          ("uniform_exit", "exit_mean"),
                          ("uniform_exit", "grad_log_rms"),
                          ("no_post_norm", "grad_probe")):
        assert units(parts(variant), want)[part] > 1, variant
    assert kit.PART_CONTROLS == ("bf16",) + kit.WRONG


def test_the_kit_counts_the_published_steps_operations(kit):
    """Every layer application and every pass's head are counted, attention
    over the causal pairs; no count follows the implementation."""
    cfg = kit.load_config(CONFIG)
    per = kit.matmul_params_per_token(cfg)
    assert per == {"attn_proj": 16_777_216, "dense_mlp": 34_603_008,
                   "head": 2048 * 49152}
    assert kit.causal_pairs(cfg) == 8_390_656
    flops = kit.step_flops(cfg)
    assert flops["flash_forward"] == 16 * 2 * 8_390_656 * 4 * 128 * 16
    assert flops["attn_backward"] == 2.5 * flops["flash_forward"]
    assert abs(flops["attn_proj"] / 48e12 - 0.275) < 0.001
    assert abs(flops["dense_mlp"] / 48e12 - 0.567) < 0.001
    assert abs(flops["attention"] / 48e12 - 0.137) < 0.001
    assert abs(flops["head"] / 3e12 - 6.60) < 0.005
    assert abs(flops["step"] / 1e12 - 66.8) < 0.05
    assert sum(kit.leaf_sizes(cfg).values()) == 406_884_353
