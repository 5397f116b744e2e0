"""The benchmark's own copy of the reference of SDAR-30B-A3B's
block-diffusion training step (``benchmark/harness/sdarkit.py``) against the
repository's (``parallel/sdar_reference.py``), what the ``train_step_kit``
kind compares of a step of the program in the kit's units with every control
outside the tolerance, the noise drawn again by the kit, and the kit's count
of the published step's operations; at ``tests/test_sdar_train.py``'s small
widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import sdar_reference
from ompi_tpu.parallel import train

from test_sdar_train import (BENCH, CONFIG, F32, NAMES, SHARE, TRAIN,
                             batch_of, close, near, ref_grads,
                             spread_params)
import built

ref = built.programs(sdar_reference)


@pytest.fixture(scope="module")
def kit():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import sdarkit
        yield sdarkit
    finally:
        sys.path.remove(BENCH)


KIT_CFG = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 16, "n_routed_experts": 16,
    "num_experts_per_tok": 3, "moe_intermediate_size": 24, "vocab_size": 256,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "block_length": 4, "mask_token_here": 63,
    **SHARE, **TRAIN, "compute_dtype": "float32", "init_std": 0.02}


def test_the_kit_names_the_programs_leaves(kit):
    assert kit.leaves(KIT_CFG) == tuple(n for n, _ in NAMES)
    checked = kit.checked(KIT_CFG)
    assert set(checked) <= set(kit.leaves(KIT_CFG)) \
        and kit.probed(KIT_CFG) == tuple(n for n in checked if n != "embed")
    shapes = train.model_param_shapes(F32)
    assert kit.leaf_sizes(KIT_CFG) == {
        n: int(np.prod(train._leaf(shapes, p))) for n, p in NAMES}
    for leaf in ("wq", "wk", "wv", "wo", "router", "gate", "up", "down"):
        assert f"l0.bd_moe.{leaf}" in checked
    assert {"final_norm", "head", "embed"} <= set(checked)
    # at the published widths the four layers' experts do not fit beside
    # the reference
    assert "l0.bd_moe.gate" not in kit.checked(kit.load_config(CONFIG))


def test_no_datum_draws_the_mask_token(kit):
    cdf, order = kit.zipf_cdf(64), kit.rank_order(64, 5)
    assert cdf.shape == (63,) and sorted(order) == list(range(63))
    bits = jnp.asarray(np.random.default_rng(0).integers(
        -2 ** 31, 2 ** 31, (4, 4096)).astype(np.int32))
    ids = np.asarray(kit.tokens_of(bits, jnp.asarray(cdf),
                                   jnp.asarray(order)))
    assert ids.max() == 62 and ids.min() == 0
    cfg = kit.load_config(CONFIG)
    assert kit.zipf_cdf(cfg["vocab_here"]).shape == (18991,) \
        and cfg["mask_token_here"] == 18991


def test_the_kits_reference_is_the_repositorys(kit):
    tokens, labels = batch_of(4)
    params = spread_params(F32, 11)
    (total, (ce, lb, loads, levels, masked)), want = ref_grads(
        params, tokens, labels, F32)
    wrt = kit.checked(KIT_CFG)
    tree = kit.tree_of({n: kit.leaf_of(params, n)
                        for n in kit.leaves(KIT_CFG)})
    got = kit.reference_step(tree, tokens, labels, KIT_CFG, {}, wrt)
    close(got["losses"], [total, ce, lb], rtol=2e-5)
    close(got["loads"], loads)
    assert not np.any(np.asarray(got["regret"]))    # its own choice
    for name in wrt:
        near(got["grads"][name], kit.leaf_of(want, name), rel=1e-4,
             err_msg=name)
    # the noise three ways: the kit's on the device, the kit's with the
    # level's product in 64 bits on the host, the repository's
    for draw in (kit.noise, kit.noise_on_host):
        lv, mk = draw(labels, 64, KIT_CFG)
        np.testing.assert_array_equal(np.asarray(lv), np.asarray(levels))
        np.testing.assert_array_equal(np.asarray(mk), np.asarray(masked))
    assert float(got["weights"][0]) == float(np.asarray(masked).sum())
    for wrong in ("leak", "unweighted"):    # a mask's and the loss's
        (other, _), _ = ref_grads(params, tokens, labels, F32, wrong)
        theirs = kit.reference_step(tree, tokens, labels, KIT_CFG, {}, wrt,
                                    wrong=wrong)
        close(theirs["losses"][0], other, rtol=2e-5)
    # the mask by rows is the mask written whole
    for wrong in (None, "causal", "leak"):
        np.testing.assert_array_equal(
            kit.visible(jnp.arange(128), 64, 4, wrong),
            ref.mask(64, 4, wrong))


def test_the_kit_compares_a_step_of_the_program_within_its_tolerance(kit):
    """What the kind does on the chip, here in float32: the step's
    statistics and float32 parts in the kit's units lie within a fiftieth
    of the tolerance of the reference's under the step's own routing, the
    noise at zero; every control lies outside it."""
    tokens, labels = batch_of(4)
    params = spread_params(F32, 11)
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

    out = jax.device_get({k: v for k, v in kit.reference_step(
        tree, tokens, labels, KIT_CFG, bias, wrt,
        routed=aux["experts"]).items() if k != "grads"})

    def parts(variant=None):
        return kit.precision_want(aux, by_name, bias["layers"],
                                  jnp.asarray(tree["head"]),
                                  np.asarray(labels), KIT_CFG,
                                  variant=variant)

    want = {**kit.compared(out, KIT_CFG, wrt), **parts()}
    assert set(want) == set(kit.OUTPUTS + kit.PRECISION) == set(got)
    off = units(got, want)
    assert max(off.values()) < 0.02, off
    assert off["noise"] == 0.0
    assert want["losses"].shape == (3,) \
        and want["bd_o"].shape == (4, 16, 16) \
        and want["noise"].shape == (2 * 64 + 2 * 16 * 4,) \
        and want["bd_weights"].shape == (2,)
    # one flipped bit of the mask, or one level a bit off, lies outside
    flipped = {**aux, "bd_mask": np.array(aux["bd_mask"])}
    flipped["bd_mask"][1, 17] ^= 1
    assert units(kit.precision_got(flipped, KIT_CFG), want)["noise"] > 1
    nudged = {**aux, "bd_levels": np.nextafter(
        np.array(aux["bd_levels"]), np.float32(2))}
    assert units(kit.precision_got(nudged, KIT_CFG), want)["noise"] > 1
    for variant, part in (("bf16", "head_rows"), ("bf16", "router_logits"),
                          ("causal", "bd_o"), ("leak", "bd_o"),
                          ("half_rate", "noise"),
                          ("half_rate", "bd_weights"),
                          ("unweighted", "losses"),
                          ("unweighted", "grad_probe")):
        assert units(parts(variant), want)[part] > 1, variant
    assert set(kit.PART_CONTROLS) == {"bf16", "causal", "leak", "half_rate",
                                      "unweighted"}
    assert set(kit.WRONG) == set(ref.WRONG)


def test_the_kit_counts_the_published_steps_operations(kit):
    """Attention counts the VISIBLE pairs only, the head the rows masked at
    the mean; no count follows the implementation."""
    cfg = kit.load_config(CONFIG)
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 128
    per = kit.matmul_params_per_token(cfg)
    assert per["attn_proj"] == 18_874_368 and per["router"] == 262_144
    assert per["experts_mean"] == 3 * 2048 * 768 * 8 * 16 / 128
    assert per["head"] == 2048 * 18992
    see = kit.visible_pairs(cfg)
    assert see == {"clean_clean": 16 * 2048 * 2049 // 2,
                   "noisy_clean": 16 * 2048 * 2047 // 2,
                   "noisy_noisy": 8192 * 4,
                   "causal": 16384 * 16385 // 2}
    pairs = see["clean_clean"] + see["noisy_clean"] + see["noisy_noisy"]
    assert pairs == 67_141_632 and abs(100 * pairs / see["causal"]
                                       - 50.02) < 0.005
    flops = kit.step_flops(cfg)
    assert flops["flash_forward"] == 4 * 32 * 4 * 128 * pairs
    assert flops["attn_backward"] == 2.5 * flops["flash_forward"]
    assert flops["attention"] == 3 * flops["flash_forward"]
    assert abs(flops["flash_forward"] / 1e12 - 4.40) < 0.005
    assert abs(flops["attn_proj"] / 12e12 - 0.618) < 0.001
    assert abs(flops["experts"] / 12e12 - 0.155) < 0.001
    assert abs(flops["head"] / 3e12 - 0.319) < 0.001
    assert abs(flops["step"] / 1e12 - 23.5) < 0.06
    assert sum(kit.leaf_sizes(cfg).values()) == 456_346_624
    # a small L by hand: 8 tokens in blocks of 2 are 4 blocks
    small = dict(cfg, seq_len=8, block_length=2)
    assert kit.visible_pairs(small) == {
        "clean_clean": 4 * 10, "noisy_clean": 4 * 6, "noisy_noisy": 16,
        "causal": 136}
    assert sum(v for k, v in kit.visible_pairs(small).items()
               if k != "causal") == int(np.asarray(ref.mask(8, 2)).sum())
