"""What Xing4.0-29B-A4B's step names and counts, at
``tests/test_xing_train.py``'s small widths: the residual path's five scopes
where they belong in the traced step, the streams' copies under
``otpu_embed`` and their sum under ``otpu_head``; the three counters, two fed
from ``step.plan()`` and one read back; the plan's rows; two data-parallel
ranks one model."""
import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.parallel import hyper, objective, train
from ompi_tpu.runtime import spc, trace

from test_xing_train import F32, batch_of, some_bias
import built

HC = ("otpu_hc", "otpu_hc_maps", "otpu_hc_sinkhorn", "otpu_hc_read",
      "otpu_hc_write")
COUNTERS = ("hc_built", "hc_sweeps_built", "hc_defect_ppm")


def run_steps(cfg, params, dp=1, seeds=(0,), fresh=False):
    step, place = (built.fresh_step if fresh else built.step)(cfg, dp)
    state = None
    for seed in seeds:
        tokens, labels = batch_of(seed)
        if state is None:
            state, tokens, labels = place(jax.tree.map(jnp.copy, params),
                                          tokens, labels)
        state, aux = step(state, tokens, labels)
    return step, state, aux


def test_the_scopes_are_named_and_nested():
    assert trace.STEP_SCOPES[-5:] == HC
    tokens, labels = batch_of(0)
    text = jax.jit(lambda p: objective.model_loss(
        p, tokens, labels, F32, interpret=True, n_global=tokens.size,
        bias=some_bias())[0]).lower(
            built.params(F32, 0)).as_text(debug_info=True)
    paths = [ln for ln in text.splitlines() if "otpu_hc" in ln]
    assert paths
    for inner in HC[1:]:
        mine = [ln for ln in paths if inner + "/" in ln or inner + '"' in ln]
        assert mine and all("otpu_hc/" in ln for ln in mine), inner
    # a sublayer's own work lies beside the path's, not under it
    assert not any("otpu_hc/" in ln and "otpu_mla" in ln.split(
        "otpu_hc/", 1)[1] for ln in paths)
    assert any("otpu_embed" in ln and "broadcast" in ln
               for ln in text.splitlines())
    for scope in ("otpu_layers", "otpu_mla", "otpu_moe", "otpu_dense_mlp",
                  "otpu_head"):
        assert scope in text, scope
    for chain, which, unknown in (trace.scope_of_path(
            "jit(f)/transpose(jvp(otpu_layers))/while/body/checkpoint/"
            "rematted_computation/otpu_hc/otpu_hc_sinkhorn/div"),):
        assert chain == ["otpu_layers", "otpu_hc", "otpu_hc_sinkhorn"] \
            and which == "remat" and not unknown


def test_the_counters_the_plan_and_two_ranks():
    spc.init()
    assert set(COUNTERS) <= set(spc.counters())
    before = {k: spc.read(k) for k in COUNTERS}
    params = built.params(F32, 3)
    # a step of its own: its first call is what feeds the counters
    step, state, aux = run_steps(F32, params, fresh=True)
    plan = step.plan()
    # 4 layers of two sublayers, 20 sweeps each
    assert (plan["counts"]["hc_built"], plan["counts"]["hc_sweeps_built"]) \
        == (8, 160)
    assert [r["kind"] for r in plan["rows"]] == ["dense", "layers"]
    for row in plan["rows"]:
        assert row["hc"] == {
            "scope": "otpu_hc", "impl": "xla",
            "why": "the residual path has no Pallas kernel", "parts": {},
            "counts": {"hc_built": 1, "hc_sweeps_built": 20}}
    assert spc.read("hc_built") - before["hc_built"] == 8
    assert spc.read("hc_sweeps_built") - before["hc_sweeps_built"] == 160
    assert spc.read("hc_defect_ppm") == before["hc_defect_ppm"]
    train.record_step_stats(aux)
    ppm = int(round(1e6 * float(aux["hc_defect"])))
    assert spc.read("hc_defect_ppm") == max(before["hc_defect_ppm"], ppm) > 0
    train.record_step_stats(aux)        # a high-water gauge: no second add
    assert spc.read("hc_defect_ppm") == max(before["hc_defect_ppm"], ppm)
    # two data-parallel ranks, a row each, are one model
    _, two, aux2 = run_steps(F32, params, dp=2)
    np.testing.assert_allclose(aux2["losses"], aux["losses"], rtol=1e-5)
    # a shard samples its own 16 rows: the largest defect over both
    assert aux2["sample"]["hc1_res"].shape == (4, 32, 4, 4)
    np.testing.assert_allclose(aux2["hc_defect"], hyper.defect(jnp.stack(
        [aux2["sample"][f"{at}_res"] for at in hyper.SETS])), rtol=1e-6)
    for name, path in train.leaf_names(F32):
        np.testing.assert_allclose(
            train._leaf(two[0], path), train._leaf(state[0], path),
            rtol=1e-5, atol=0.05 * F32.lr, err_msg=name)
