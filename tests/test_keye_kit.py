"""The benchmark's own copy of the reference of Keye-VL-2.0-30B-A3B's
training step (``benchmark/harness/keyekit.py``) against the repository's
(``parallel/keye_reference.py``), what the ``train_step_kit`` kind compares
of a step of the program in the kit's units with every control outside the
tolerance, and the kit's count of the published step's operations; at
``tests/test_keye_train.py``'s small widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import train

from test_keye_train import (BENCH, CONFIG, F32, NAMES, SHARE, TRAIN,
                             batch_of, close, near, ref_grads,
                             spread_params)
import built


@pytest.fixture(scope="module")
def kit():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import keyekit
        yield keyekit
    finally:
        sys.path.remove(BENCH)


KIT_CFG = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 16, "num_experts": 16, "n_routed_experts": 16,
    "num_experts_per_tok": 3, "moe_intermediate_size": 24, "vocab_size": 256,
    "index_heads": 4, "index_head_dim": 8, "index_topk": 24,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1e7,
    **SHARE, **TRAIN, "compute_dtype": "float32", "init_std": 0.02}


def test_the_kit_names_the_programs_leaves(kit):
    assert kit.leaves(KIT_CFG) == tuple(n for n, _ in NAMES)
    checked = kit.checked(KIT_CFG)
    assert set(checked) <= set(kit.leaves(KIT_CFG)) \
        and kit.probed(KIT_CFG) == tuple(n for n in checked if n != "embed")
    shapes = train.model_param_shapes(F32)
    assert kit.leaf_sizes(KIT_CFG) == {
        n: int(np.prod(train._leaf(shapes, p))) for n, p in NAMES}
    for leaf in ("wq", "wk", "wv", "wo", "index_wq", "index_wk", "index_ww",
                 "router", "gate", "up", "down"):
        assert f"l0.dsa_moe.{leaf}" in checked
    assert {"final_norm", "head", "embed"} <= set(checked)
    # at the published widths the four layers' experts do not fit beside
    # the reference
    assert "l0.dsa_moe.gate" not in kit.checked(kit.load_config(CONFIG))


def test_the_kits_reference_is_the_repositorys(kit):
    tokens, labels = batch_of(4)
    params = spread_params(F32, 11)
    (total, (ce, lb, index, loads, made)), want = ref_grads(
        params, tokens, labels, F32)
    wrt = kit.checked(KIT_CFG)
    tree = kit.tree_of({n: kit.leaf_of(params, n)
                        for n in kit.leaves(KIT_CFG)})
    got = kit.reference_step(tree, tokens, labels, KIT_CFG, {}, wrt,
                             selection=None)
    close(got["losses"], [total, ce, lb, index], rtol=2e-5)
    close(got["loads"], loads)
    # its own choice: no regret beyond the two sums' last bits
    assert np.abs(np.asarray(got["select_regret"])).max() < 1e-5 \
        and np.all(np.asarray(got["overlap"]) == 1.0)
    for name in wrt:
        near(got["grads"][name], kit.leaf_of(want, name), rel=1e-4,
             err_msg=name)
    # and under a given selection: the repository's own, packed
    packed = np.packbits(np.asarray(made), axis=-1, bitorder="little")
    again = kit.reference_step(tree, tokens, labels, KIT_CFG, {}, wrt,
                               selection=packed)
    close(again["losses"], got["losses"])


def test_the_kit_compares_a_step_of_the_program_within_its_tolerance(kit):
    """What the kind does on the chip, here in float32: the step's
    statistics and float32 parts in the kit's units lie within a fiftieth
    of the tolerance of the reference's under the step's own routing and
    selection; every control lies outside it."""
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
    assert want["losses"].shape == (4,) \
        and want["select_count"].shape == (4, 2, 64) \
        and want["index_rows"].shape == (4, 16, 64) \
        and want["select_o"].shape == (4, 16, 16) \
        and want["kl_rows"].shape == (4, 16)
    # a selection one key short, or one key too many, lies outside
    for change in (0, 1):
        packed = np.array(aux["sample"]["dsa_selection_seq"])
        packed[0, 0, 40, 0] = 0xFF if change else (packed[0, 0, 40, 0] & 0xFE)
        stats = kit.step_stats({**aux, "sample": {
            **aux["sample"], "dsa_selection_seq": packed}}, bias, KIT_CFG)
        moved = units(kit.compared(stats, KIT_CFG, wrt), want)
        assert moved["select_count"] > 1 or not change
    kit.step_stats(aux, bias, KIT_CFG)      # the step's own again
    for variant, part in (("bf16", "head_rows"), ("no_selection", "select_o"),
                          ("top_half", "select_o"), ("no_relu", "index_rows"),
                          ("no_head_norm", "rope_qk"),
                          ("no_index_loss", "losses"),
                          ("hi_attached", "grad_probe"),
                          ("pbar_attached", "grad_probe")):
        assert units(parts(variant), want)[part] > 1, variant
    assert units(parts("no_relu"), want)["kl_rows"] > 1
    assert set(kit.PART_CONTROLS) == {
        "bf16", "no_selection", "top_half", "no_relu", "no_head_norm",
        "no_index_loss", "hi_attached", "pbar_attached"}
    # a worse selection shows in its regret: the reference under a
    # selection of every row's first keys
    first = np.zeros((4, 2, 64, 64), bool)
    for t_ in range(64):
        first[:, :, t_, :min(t_ + 1, 24)] = True
    worse = whole(selection=np.packbits(first, axis=-1, bitorder="little"))
    assert units(worse, want)["select_regret"] > 1


def test_the_kit_counts_the_published_steps_operations(kit):
    """Attention counts the SELECTED positions only, the index scores the
    causal ones; no count follows the implementation."""
    cfg = kit.load_config(CONFIG)
    assert cfg["n_routed_experts"] == cfg["num_experts"] == 128
    assert (cfg["index_heads"], cfg["index_head_dim"], cfg["index_topk"]) \
        == (16, 64, 2048)
    per = kit.matmul_params_per_token(cfg)
    assert per["attn_proj"] == 18_874_368 and per["router"] == 262_144
    assert per["index_proj"] == 2048 * (1024 + 64 + 16)
    assert per["experts_mean"] == 3 * 2048 * 768 * 8 * 16 / 128
    assert per["head"] == 2048 * 18992
    pos = kit.positions(cfg)
    assert pos == {"selected": 2048 * 2049 / 2 + 14336 * 2048,
                   "causal": 16384 * 16385 / 2}
    assert abs(100 * pos["selected"] / pos["causal"] - 23.4) < 0.05
    flops = kit.step_flops(cfg)
    assert flops["flash_forward"] == 4 * 32 * 4 * 128 * pos["selected"]
    assert flops["attn_backward"] == 2.5 * flops["flash_forward"]
    assert flops["attention"] == 3 * flops["flash_forward"]
    assert flops["index_select"] == 4 * 2 * 16 * 64 * pos["causal"]
    assert flops["index_scores"] == 3 * flops["index_select"]
    assert abs(flops["step"] / 1e12 - 23.6) < 0.05
    assert abs(flops["flash_forward"] / 4e12 - 0.515) < 0.002 \
        and abs(flops["index_select"] / 4e12 - 0.275) < 0.002
    assert sum(kit.leaf_sizes(cfg).values()) == 465_391_104
    short = dict(cfg, seq_len=2048)
    assert kit.positions(short)["selected"] == kit.positions(short)["causal"]
