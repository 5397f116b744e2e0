"""The benchmark's own copy of the reference of granite-4.0-h-micro's
padding-free training step (``benchmark/harness/granitekit.py``) against the
repository's (``parallel/granite_reference.py``), the batch it makes of a
row's bit patterns, what the ``train_step_kit`` kind compares of a step of
the program in the kit's units with every control outside the tolerance, and
the kit's count of the published step's operations; at
``tests/test_granite_train.py``'s small widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import train

from test_granite_train import (BENCH, CONFIG, EOS, F32, SMALL, close, near,
                                packed, ref_grads, spread)
import built

NAMES = train.leaf_names(F32)


@pytest.fixture(scope="module")
def kit():
    import sys

    sys.path.insert(0, BENCH)
    try:
        from harness import granitekit
        yield granitekit
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def kit_cfg(kit):
    return {**kit.load_config(CONFIG), **SMALL}


def test_the_kit_names_the_programs_leaves(kit, kit_cfg):
    assert kit.leaves(kit_cfg) == tuple(n for n, _ in NAMES)
    assert kit.segments(kit_cfg) == [("mamba", 1, 0), ("attention", 1, 1),
                                     ("mamba", 1, 2)]
    assert set(kit.checked(kit_cfg)) <= set(kit.leaves(kit_cfg)) \
        and kit.probed(kit_cfg) == kit.checked(kit_cfg)[:-1]
    shapes = train.model_param_shapes(F32)
    assert kit.leaf_sizes(kit_cfg) == {
        n: int(np.prod(train._leaf(shapes, p))) for n, p in NAMES}
    params = built.params(F32, 0)
    tree = kit.tree_of({n: kit.leaf_of(params, n)
                        for n in kit.leaves(kit_cfg)})
    assert tree.pop("head").shape == (64, 64)       # the tied matrix's twin
    assert jax.tree.structure(tree) == jax.tree.structure(params)
    whole = kit.load_config(CONFIG)
    assert sum(kit.leaf_sizes(whole).values()) == 652_970_080
    assert kit.segments(whole) == [("mamba", 5, 0), ("attention", 1, 5),
                                   ("mamba", 4, 6)]
    assert kit.checked(whole)[-2:] == ("final_norm", "embed") \
        and "l6.mamba_dense.out_proj" in kit.checked(whole) \
        and "l5.attn_dense.wq" in kit.checked(whole)


def test_a_batch_is_documents_laid_end_to_end_from_its_bits(kit):
    """At a row of 16,384: about ten documents of a median of 1,024, each
    ending in the slice's last id and no other position holding it, the
    same batch from the same bits; at a tiny row the law scales with it."""
    vocab = 12544
    cdf, order = kit.zipf_cdf(vocab), kit.rank_order(vocab, 5)
    assert cdf.shape == order.shape == (vocab - 1,) and order.max() == vocab - 2
    bits = np.random.default_rng(3).integers(
        -2 ** 31, 2 ** 31, (24, 16386)).astype(np.int32)
    run = jax.jit(lambda b: kit.tokens_of(b, jnp.asarray(cdf),
                                          jnp.asarray(order)))
    ids = np.asarray(run(bits))
    assert ids.min() >= 0 and ids.max() == vocab - 1
    np.testing.assert_array_equal(ids, np.asarray(run(bits)))
    lengths = np.asarray(kit.document_lengths(jnp.asarray(bits)))
    assert lengths.min() >= 16 and lengths.max() <= 16384 \
        and 700 < np.median(lengths) < 1500
    for row, own in zip(ids, lengths):
        ends = np.cumsum(own) - 1
        np.testing.assert_array_equal(np.flatnonzero(row == vocab - 1),
                                      ends[ends < 16386])
    per_row = (ids[:, :16384] == vocab - 1).sum(axis=1) + 1
    assert 6 < per_row.mean() < 14
    # the count of the pairs the masks leave follows the same law
    doc = np.asarray(kit.documents(jnp.asarray(ids[:, :16384]), vocab - 1))
    pairs = np.mean([np.sum(n * (n + 1) // 2)
                     for n in map(np.bincount, doc)])
    assert 0.6 < pairs / kit.mean_visible_pairs(16384) < 1.6
    assert kit.mean_visible_pairs(16384) < 0.4 * 16384 * 16385 / 2
    # a row of 64: documents of about 4
    tiny = np.asarray(kit.document_lengths(jnp.asarray(bits[:, :66])))
    assert tiny.min() >= 1 and tiny.max() <= 64 and 2 <= np.median(tiny) <= 8
    # the rows a check reads behind the documents' starts
    at = kit.boundary_rows(np.asarray([[0, 0, 1, 1, 1, 2], [0, 1, 1, 1, 1, 1]]))
    np.testing.assert_array_equal(at[:9], [2, 3, 4, 5, 6, 7, 7, 8, 9])
    assert at.shape == (24,) and at.max() <= 11
    assert set(kit.boundary_rows(np.zeros((2, 8), int))) == {0, 1, 2}


def test_the_kits_reference_is_the_repositorys(kit, kit_cfg):
    tokens, labels = packed(4, (21, 11, 30, 2), rows=2)
    params = spread(F32, 11)
    (total, rows), want = ref_grads(params, tokens, labels, F32)
    wrt = kit.checked(kit_cfg)
    got = kit.reference_step(params, tokens, labels, kit_cfg, {}, wrt)
    close(got["losses"], [total, total], rtol=2e-5)
    close(got["rows"], np.asarray(rows).reshape(-1, 2), atol=2e-5)
    assert got["conv_x"].shape == (24, 32)
    for name in wrt:
        near(got["grads"][name], kit.leaf_of(want, name), rel=1e-4,
             err_msg=name)
    assert set(kit.WRONG) == {"no_scan_reset", "no_conv_reset",
                              "no_doc_mask", "sqrt_scale", "residual_one"}
    # a wrong model is another function (each of the five is held outside
    # the tolerance below): without its resets the convolution's output
    # differs just behind a document's start
    leak = kit.reference_step(params, tokens, labels, kit_cfg, {}, wrt,
                              wrong="no_conv_reset")
    assert abs(float(leak["losses"][0]) - float(total)) \
        > 1e-5 * abs(float(total))
    assert np.abs(np.asarray(leak["conv_x"] - got["conv_x"])).max() > 0.05


def test_the_kit_compares_a_step_of_the_program_within_its_tolerance(
        kit, kit_cfg):
    """What the kind does on the chip, here in float32: the step's
    statistics and float32 parts in the kit's units lie within a fiftieth
    of the tolerance of the reference's; every control lies outside it."""
    tokens, labels = packed(4, (21, 11, 30, 2), rows=2)
    params = spread(F32, 11)
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
    assert want["losses"].shape == (2,) and want["row_means"].shape == (4, 2) \
        and want["conv_x"].shape == (24, 32) \
        and want["head_rows"].shape == (16, 2) \
        and want["ssm_y"].shape == (2, 16, 32) \
        and want["grad_probe"].shape == (len(wrt) - 1, 64)
    for variant, part in (("bf16", "head_rows"), ("scan_bf16", "ssm_y"),
                          ("scan_no_reset", "ssm_y"),
                          ("no_scan_reset", "grad_log_rms"),
                          ("no_conv_reset", "conv_x"),
                          ("no_doc_mask", "grad_probe"),
                          ("sqrt_scale", "grad_log_rms"),
                          ("residual_one", "grad_log_rms")):
        assert units(parts(variant), want)[part] > 1, variant
    assert kit.PART_CONTROLS == ("bf16", "scan_bf16", "scan_no_reset") \
        + kit.WRONG


def test_the_kit_counts_the_published_steps_operations(kit):
    """Every layer's matrices, the scans by the recurrence's count,
    attention over the pairs the documents' masks leave, the tied head once;
    no count follows the implementation."""
    cfg = kit.load_config(CONFIG)
    per = kit.matmul_params_per_token(cfg)
    assert per == {"mamba_proj": 8_978_432 + 4_194_304,
                   "attn_proj": 2 * 2048 * 1024 + 2 * 2048 * 256,
                   "dense_mlp": 3 * 2048 * 8192, "head": 2048 * 12544}
    flops = kit.step_flops(cfg)
    tokens = 16384
    assert flops["mamba_proj"] == 6.0 * per["mamba_proj"] * tokens * 9
    assert flops["dense_mlp"] == 6.0 * per["dense_mlp"] * tokens * 10
    assert flops["ssm_scan"] == 3.0 * 6 * 32 * 64 * 128 * tokens * 9
    assert flops["head"] == 6.0 * per["head"] * tokens
    pairs = kit.mean_visible_pairs(16384)
    assert flops["flash_forward"] == pairs * 4.0 * 64 * 16 \
        and flops["attention"] == 3 * flops["flash_forward"]
    assert 0.1 < pairs / kit.causal_pairs(cfg) < 0.4
    assert flops["step"] == sum(flops[k] for k in (
        "mamba_proj", "ssm_scan", "attn_proj", "attention", "dense_mlp",
        "head"))
    assert 64e12 < flops["step"] < 67e12
    # the mixers' matrices and scans, the step's distinctive part
    assert 0.18 < (flops["mamba_proj"] + flops["ssm_scan"]) / flops["step"] \
        < 0.20
