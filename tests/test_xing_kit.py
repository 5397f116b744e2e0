"""The benchmark's own copy of the reference of Xing4.0-29B-A4B's training
step (``benchmark/harness/xingkit.py``) against the repository's
(``parallel/xing_reference.py``), the leaves it names, and its counts of the
step's operations and of the bytes the residual path has to move, by hand at
``tests/test_xing_train.py``'s small widths and at the published ones."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import train
from ompi_tpu.parallel import xing_reference

from test_xing_train import (BENCH, CONFIGS, F32, batch_of, kit_cfg,
                             some_bias)
import built

ref = built.programs(xing_reference)

CONFIG = os.path.join(CONFIGS, "xing4.0-29b-a4b-train-1chip.json")
NAMES = train.leaf_names(F32)


@pytest.fixture(scope="module")
def kit():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import xingkit
        yield xingkit
    finally:
        sys.path.remove(BENCH)


def test_the_kit_names_the_programs_leaves(kit):
    cfg = kit_cfg()
    assert kit.leaves(cfg) == tuple(n for n, _ in NAMES)
    assert set(kit.checked(cfg)) <= set(kit.leaves(cfg)) \
        and kit.probed(cfg) == kit.checked(cfg)
    for group in ("dense.", ""):
        for leaf in ("hc1_phi", "hc1_alpha", "hc1_b", "hc2_phi",
                     "hc2_alpha", "hc2_b", "wq_b"):
            assert group + leaf in kit.checked(cfg)
    shapes = train.model_param_shapes(F32)
    assert kit.leaf_sizes(cfg) == {
        n: int(np.prod(train._leaf(shapes, p))) for n, p in NAMES}
    params = built.params(F32, 0)
    tree = kit.tree_of({n: kit.leaf_of(params, n) for n in kit.leaves(cfg)})
    assert jax.tree.structure(tree) == jax.tree.structure(params)
    assert {n for n, _ in NAMES if not train.is_decayed(n)} == {
        n for n in kit.leaves(cfg)
        if n.rsplit(".", 1)[-1] in kit.UNDECAYED}
    whole = kit.load_config(CONFIG)
    published = train.load_model_config(CONFIG)
    assert kit.leaves(whole) == tuple(
        n for n, _ in train.leaf_names(published))
    assert sum(kit.leaf_sizes(whole).values()) == 700_363_790
    per = kit.layer_sizes(whole)
    assert (sum(per["dense"].values()), sum(per["sparse"].values())) \
        == (116_400_438, 116_629_814)


def test_the_kits_copy_is_the_programs_reference(kit):
    """Loss, loads, the biases after the update and every leaf's gradient of
    the kit's blocked float32 copy against ``parallel/xing_reference.py``
    (that each wrong variant is another model:
    ``tests/test_xing_train.py``'s controls)."""
    cfg, params = kit_cfg(), built.params(F32, 3)
    tokens, labels = batch_of(0)
    bias = some_bias()
    (loss, loads), grads = jax.jit(lambda p: ref.grads(
        p, tokens, labels, F32, bias))(params)
    names = kit.leaves(cfg)
    out = kit.reference_step(params, tokens, labels, cfg, bias, names)
    np.testing.assert_allclose(out["losses"], [loss, loss], rtol=1e-6)
    np.testing.assert_array_equal(out["loads"], loads)
    np.testing.assert_allclose(
        out["bias"], ref.bias_step(bias, loads, F32)["layers"], atol=1e-8)
    assert not np.asarray(out["regret"]).any()
    for name, path in NAMES:
        want = np.asarray(train._leaf(grads, path))
        np.testing.assert_allclose(
            out["grads"][name], want, rtol=1e-4,
            atol=1e-6 * max(1.0, np.abs(want).max()), err_msg=name)
    inv, by, scale = kit.yarn_inv_freq(kit.load_config(CONFIG))
    assert by == 1.0 and abs(scale * 192 ** 0.5 - 2.004739701682487) < 1e-12
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(
        train.load_model_config(CONFIG))[0], rtol=1e-6)


def test_the_counts_by_hand(kit):
    """``step_flops`` and ``hc_min_bytes`` at the tiny widths by hand, and
    the published step's parts."""
    cfg = kit_cfg()
    t, d, n = 2 * 32, 64, 4
    flops = kit.step_flops(cfg)
    proj = 64 * 32 + 32 * 2 * 24 + 64 * 24 + 16 * 2 * 32 + 2 * 16 * 64
    assert flops["latent_proj"] == 6.0 * proj * t * 4
    assert flops["hc_maps"] == 6.0 * (n * d * 24) * t * 8
    assert flops["dense_mlp"] == 6.0 * 3 * d * 96 * t * 2
    assert flops["router"] == 6.0 * d * 8 * t * 2
    assert flops["shared"] == 6.0 * 3 * d * 32 * t * 2
    assert flops["experts"] == 6.0 * (3 * d * 32 * 2 * 2 / 8) * t * 2
    assert flops["head"] == 6.0 * d * 64 * t
    assert flops["flash_forward"] == 2 * 2 * (24 + 16) * 32 * 32 * 4
    assert flops["attention"] == 3.0 * flops["flash_forward"] \
        and flops["attn_backward"] == 2.5 * flops["flash_forward"]
    assert flops["step"] == sum(flops[k] for k in (
        "latent_proj", "hc_maps", "dense_mlp", "router", "shared", "experts",
        "head", "attention"))
    moved = kit.hc_min_bytes(cfg, 2, 32)
    stream = 2 * 32 * n * d * 4
    assert moved["a_sublayer"] == 3 * stream + 2 * 32 * d * 4
    assert moved["sublayers"] == 8 and moved["a_step"] == 4 * 8 * moved[
        "a_sublayer"] == sum(moved["pass"].values())
    whole = kit.load_config(CONFIG)
    parts = kit.step_flops(whole)
    assert round(parts["step"] / 1e12, 2) == 8.94 \
        and round(parts["attention"] / 1e12, 2) == 1.29
    assert kit.hc_min_bytes(whole, 1, 4096)["a_step"] == 30_534_533_120
