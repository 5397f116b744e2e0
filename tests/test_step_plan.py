"""What a step holds, stated once (``train.plan_of``, ``step.plan()``): the
implementation of every layer application and the reason for it, from the
configuration and the shapes, by the decision functions the traced code
asks; and the ``*_built`` counters and the volumes beside them, fed from
the plan at a built step's first call, by layer applications and not by
JAX's trace visits.  Over the ten step cells' configuration files, at
their published widths where nothing is traced and at
``test_model_tree``'s tiny cuts where a step runs.
"""
import dataclasses
import gc
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_model_tree import CONFIGS, SMALL, TREES

from ompi_tpu.parallel import (attention, causal, config, dsa, experts, gdn,
                               layers, mamba, model, objective, train)
from ompi_tpu.parallel.mesh import MeshSpec, make_mesh
from ompi_tpu.parallel.sublayer import INTERPRET
from ompi_tpu.runtime import spc

import built

CELLS = sorted(TREES)
assert len(CELLS) == 11
#: a family's decision function, by the part of a sublayer's plan it
#: answers for
DECISIONS = {"flash": (causal, "flash_on_kernels"),
             "qk": (attention, "qk_on_kernels"),
             "index": (dsa, "index_on_kernels"),
             "gmm": (experts, "gmm_on_kernel"),
             "scatter": (experts, "scatter_on_kernel"),
             "rule": (gdn, "rule_on_kernels"),
             "conv": (gdn, "conv_on_kernels"),
             "scan": (mamba, "scan_on_kernels")}


#: the parts that are a backward rule written out, no kernel
WRITTEN = {"ffn_bwd": (layers, "ffn_bwd_written")}


def full(name, **change):
    """A cell's file as the chip runs it."""
    return config.load_model_config(os.path.join(CONFIGS, name), **change)


def tiny(name, **change):
    """``test_model_tree``'s cut of a cell's file, in the file's own
    ``compute_dtype``."""
    return full(name, **{**SMALL, **TREES[name][0], **change})


def plan_at(cfg, interpret=False):
    return train.plan_of(cfg, cfg.micro_batch, cfg.seq_len,
                         interpret=interpret)


def sublayers(plan):
    """(the row, "operator" or "ffn", that sublayer's plan) of every
    sublayer a plan's rows hold."""
    return [(row, key, row[key]) for row in plan["rows"]
            for key in ("operator", "ffn") if row[key]]


def kernel_parts(part) -> dict:
    """A sublayer's parts that have a Pallas kernel: all but the backward
    rules written out (``sublayer.held``'s ``written``: ``ffn_bwd``, whose
    decision knows no ``interpret``)."""
    return {k: v for k, v in part["parts"].items() if k not in WRITTEN}


def held_layers(row):
    """The held layers a row names, from its label."""
    if "-" in row["layers"]:
        first, last = map(int, row["layers"].split("-"))
        return list(range(first, last + 1))
    return [int(n) for n in row["layers"].split(",")]


# -- the rows ------------------------------------------------------------------
@pytest.mark.parametrize("cut", [full, tiny], ids=["full", "tiny"])
@pytest.mark.parametrize("name", CELLS)
def test_the_rows_cover_every_held_layer_once(name, cut):
    cfg = cut(name)
    plan = plan_at(cfg)
    rows = [r for r in plan["rows"] if r["layers"] != "mtp"]
    kinds = {}
    for row in rows:
        assert len(held_layers(row)) == row["run"]
        assert row["passes"] == (cfg.total_ut_steps or 1)
        for layer in held_layers(row):
            assert layer not in kinds, f"layer {layer} in two rows"
            kinds[layer] = row["kind"]
    # every held layer, in the walk's order, under its own kind
    assert [kinds[n] for n in sorted(kinds)] \
        == [kind.name for kind in model.kinds_here(cfg)]
    assert sorted(kinds) == list(range(1, cfg.layers_here + 1))
    assert len(plan["rows"]) - len(rows) == bool(cfg.n_mtp_here)
    for row, key, part in sublayers(plan):
        kind = model.layer_kinds(cfg)[row["kind"]]
        entry = kind.operator if key == "operator" else kind.feed_forward
        assert part["scope"] == entry.scope
        assert part["impl"] in ("kernel", "xla")
        # a kernel taken gives no reason, a kernel refused gives one
        assert bool(part["why"]) == (part["impl"] == "xla")
        for piece in part["parts"].values():
            assert piece["impl"] in ("kernel", "written", "xla")
            assert bool(piece["why"]) == (piece["impl"] == "xla")
        # a backward rule written out is no kernel: ``impl`` speaks of the
        # parts that have one
        kernels = kernel_parts(part)
        assert (part["impl"] == "kernel") == (bool(kernels) and all(
            piece["impl"] == "kernel" for piece in kernels.values()))


@pytest.mark.parametrize("name", CELLS)
def test_every_count_is_a_declared_counter_times_run_and_passes(name):
    cfg = tiny(name)
    plan = plan_at(cfg)
    want = {}
    for row, _, part in sublayers(plan):
        for counter, by in part["counts"].items():
            assert by > 0
            want[counter] = want.get(counter, 0) \
                + by * row["run"] * row["passes"]
    if cfg.total_ut_steps:
        want.update(objective.loop_counts(cfg, cfg.micro_batch, cfg.seq_len))
    for row in plan["rows"]:        # the residual path's, a sublayer each
        for counter, by in row.get("hc", {"counts": {}})["counts"].items():
            held = sum(row[k] is not None for k in ("operator", "ffn"))
            want[counter] = want.get(counter, 0) \
                + by * held * row["run"] * row["passes"]
    assert plan["counts"] == want
    assert set(want) <= set(spc._COUNTERS)
    assert "loop_exit_depth" not in want      # read back a step, not built


def test_on_the_cpu_every_kernel_is_refused_for_one_reason():
    for name in CELLS:
        for _, _, part in sublayers(plan_at(full(name), interpret=True)):
            assert part["impl"] == "xla"
            assert all(piece == {"impl": "xla", "why": INTERPRET}
                       for piece in kernel_parts(part).values())
            assert not any(k.endswith("_kernel_built")
                           for k in part["counts"])
    # and ``interpret`` left out is the process's own devices': the CPU's
    cfg = tiny(CELLS[0])
    assert train.plan_of(cfg, 1, 32) == train.plan_of(cfg, 1, 32, True)


# -- the ten cells as the chip runs them ------------------------------------------
NARROW = ("%d hidden units on a stream of %d: narrower than the stream, the "
          "cotangent's cast costs more than the rule spares")
#: file -> the parts refused where Mosaic compiles, {(layers, part): why};
#: every other part of every layer is on its kernels or its written rule
REFUSED = {
    "granite-4.0-h-micro-train-1chip.json": {
        ("6", "qk"): "RoPE does not turn the layer"},
    "lfm2-8b-a1b-train-1chip.json": {
        ("2", "qk"): "head width 64 is not a multiple of 128",
        ("6", "qk"): "head width 64 is not a multiple of 128"},
    "qwen3-next-80b-a3b-train-1chip.json": {
        ("4", "qk"): "RoPE turns 64 of a head's 256 entries: the partner "
                     "is no rotation of the tile"},
    "smallthinker-21b-a3b-train-1chip.json": {
        ("1", "qk"): "RoPE does not turn the layer"},
    # a shared expert narrower than the stream keeps autodiff's backward
    "joyai-flash-train-1chip.json": dict.fromkeys(
        [("2-5", "ffn_bwd"), ("mtp", "ffn_bwd")], NARROW % (768, 2048)),
    "xing4.0-29b-a4b-train-1chip.json": {
        ("2-5", "ffn_bwd"): NARROW % (1024, 3584)},
}
REFUSED["qwen3-next-80b-a3b-train-1chip.json"].update(dict.fromkeys(
    [("1-3", "ffn_bwd"), ("4", "ffn_bwd")], NARROW % (512, 2048)))
#: file -> some of the counters its step feeds at the first call
COUNTS = {
    "granite-4.0-h-micro-train-1chip.json": dict(
        ssm_scan_built=9, ssm_scan_kernel_built=9, attn_built=1,
        doc_built=19),
    "nemotron3-super-train-1chip.json": dict(
        ssm_scan_built=5, ssm_scan_kernel_built=5, moe_gmm_built=10,
        moe_gmm_kernel_built=10, moe_scatter_built=5,
        moe_scatter_kernel_built=5, attn_shared_kv_built=1),
    "qwen3-next-80b-a3b-train-1chip.json": dict(
        gdn_rule_built=3, gdn_rule_kernel_built=3, gdn_conv_built=3,
        gdn_conv_kernel_built=3, attn_qk_built=2),
    "smallthinker-21b-a3b-train-1chip.json": dict(
        attn_built=4, attn_window_built=3, attn_pairs_walked=346,
        attn_pairs_causal=544, attn_qk_built=8, attn_qk_kernel_built=6,
        moe_gmm_built=12, moe_gmm_kernel_built=12),
    "joyai-flash-train-1chip.json": dict(
        attn_built=6, moe_gmm_built=15, moe_gmm_kernel_built=15),
    "olmoe-1b-7b-train-1chip.json": dict(
        attn_built=1, moe_gmm_built=3, moe_gmm_kernel_built=3),
    "ouro-2.6b-train-1chip.json": dict(
        attn_built=16, attn_qk_built=32, attn_qk_kernel_built=32,
        loop_passes=4, loop_layers_held=4, loop_layer_applications=16),
    "sdar-30b-a3b-train-1chip.json": dict(
        attn_built=4, bd_built=4, attn_pairs_walked=320,
        attn_pairs_causal=544, bd_pairs_visible=4 * 67_141_632,
        bd_pairs_causal=4 * 134_225_920),
    "keye-vl2-30b-a3b-train-1chip.json": dict(
        attn_built=4, dsa_built=4, attn_qk_kernel_built=8,
        dsa_mask_bytes=4 * 16384 * 16384 // 8),
    "lfm2-8b-a1b-train-1chip.json": dict(
        attn_built=2, attn_qk_built=4, moe_gmm_built=15,
        moe_gmm_kernel_built=15),
    "xing4.0-29b-a4b-train-1chip.json": dict(
        attn_built=5, attn_pairs_walked=50, hc_built=10,
        hc_sweeps_built=200, moe_gmm_built=12, moe_gmm_kernel_built=12,
        ffn_built=5, ffn_bwd_written_built=1),
}


@pytest.mark.parametrize("name", CELLS)
def test_a_cells_step_names_what_it_refuses_and_counts_by_layers(name):
    plan = plan_at(full(name))
    refused = {(row["layers"], piece): made["why"]
               for row, _, part in sublayers(plan)
               for piece, made in part["parts"].items()
               if made["impl"] == "xla"}
    assert refused == REFUSED.get(name, {})
    assert {k: plan["counts"].get(k, 0) for k in COUNTS[name]} \
        == COUNTS[name]
    assert "attn_qk_kernel_built" not in plan["counts"] \
        or plan["counts"]["attn_qk_kernel_built"] == 2 * sum(
            row["run"] * row["passes"] for row, _, part in sublayers(plan)
            if part["parts"].get("qk", {}).get("impl") == "kernel")


def test_smallthinkers_file_gives_window_3_of_4_and_pairs_346_of_544():
    """What ``attn.window_share`` and ``attn.pairs_walked_share`` divide:
    three window layers of four, and 136 + 3 x 70 block pairs of 4 x 136
    (traced, the scanned run of three counted once: 50% and 75.7%)."""
    plan = plan_at(full("smallthinker-21b-a3b-train-1chip.json"))
    assert [(r["layers"], r["kind"], r["run"]) for r in plan["rows"]] \
        == [("1", "attn_moe", 1), ("2-4", "swa_moe", 3)]
    counts = plan["counts"]
    assert (counts["attn_window_built"], counts["attn_built"]) == (3, 4)
    assert (counts["attn_pairs_walked"], counts["attn_pairs_causal"]) \
        == (136 + 3 * 70, 4 * 136) == (346, 544)
    window = plan["rows"][1]["operator"]
    assert (window["scope"], window["impl"], window["why"]) \
        == ("otpu_swa", "kernel", "")


@pytest.mark.parametrize("change,layers,part,why", [
    (dict(), "1", "qk", "RoPE does not turn the layer"),
    (dict(), "2-4", "qk", "head width 16 is not a multiple of 128"),
    (dict(), "1", "gmm", "a width of 64 is not a multiple of 128"),
    (dict(hidden_size=128), "2-4", "gmm",
     "a width of 24 is not a multiple of 128"),
    (dict(compute_dtype="float32"), "2-4", "gmm",
     "compute_dtype float32: the kernel's inputs are bfloat16"),
    (dict(), "2-4", "scatter", "a row of 64 entries is no whole lane tiles "
                               "of 128"),
], ids=["not-turned", "head", "width", "expert-width", "dtype", "scatter"])
def test_a_refused_shape_names_its_clause(change, layers, part, why):
    cfg = tiny("smallthinker-21b-a3b-train-1chip.json", **change)
    (row,) = [r for r in plan_at(cfg)["rows"] if r["layers"] == layers]
    held = row["operator" if part == "qk" else "ffn"]
    assert held["parts"][part] == {"impl": "xla", "why": why}
    assert f"{part}: {why}" in held["why"].split("; ")


@pytest.mark.parametrize("name,change,part,why", [
    ("qwen3-next-80b-a3b-train-1chip.json", {}, "rule",
     "a key head is 16 wide, not 128"),
    ("qwen3-next-80b-a3b-train-1chip.json",
     dict(linear_key_head_dim=128, linear_value_head_dim=128,
          chunk_size=96), "rule",
     "chunk 96 does not divide a step's 256 rows"),
    ("qwen3-next-80b-a3b-train-1chip.json",
     dict(linear_num_value_heads=2), "conv",
     "96 channels are no whole lane tiles of 128"),
    ("nemotron3-super-train-1chip.json", {}, "scan",
     "a state of 8 a channel is not a tile's 128 lanes"),
    ("granite-4.0-h-micro-train-1chip.json", dict(mamba_d_state=128), "scan",
     "a chunk of 8 positions is not 1 to 4 lane blocks of 128"),
], ids=["rule-head", "rule-chunk", "conv", "scan-state", "scan-chunk"])
def test_a_refused_operator_names_its_clause(name, change, part, why):
    whys = {made["why"] for _, _, held in sublayers(plan_at(
        tiny(name, **change))) for piece, made in held["parts"].items()
        if piece == part}
    assert whys == {why}


def test_counts_scale_with_a_runs_length_and_a_looped_models_passes():
    name = "smallthinker-21b-a3b-train-1chip.json"
    whole, two = (plan_at(full(name, layers_here=n))["counts"]
                  for n in (4, 2))
    # the full layer and one window layer; then two window layers more
    assert (two["attn_built"], two["attn_window_built"]) == (2, 1)
    assert whole["attn_pairs_walked"] - two["attn_pairs_walked"] == 2 * 70
    assert whole["moe_gmm_built"] == 2 * two["moe_gmm_built"] == 12
    ouro = "ouro-2.6b-train-1chip.json"
    for passes in (1, 2, 4):
        cfg = dataclasses.replace(full(ouro), total_ut_steps=passes)
        plan = plan_at(cfg)
        assert [r["passes"] for r in plan["rows"]] == [passes]
        assert plan["counts"]["attn_built"] == passes * cfg.layers_here
        assert plan["counts"]["loop_layer_applications"] \
            == passes * plan["counts"]["loop_layers_held"]
        assert plan["counts"]["loop_head_rows"] \
            == passes * cfg.micro_batch * cfg.seq_len
    # under block diffusion the layers walk 2 s rows of every sequence
    sdar = plan_at(full("sdar-30b-a3b-train-1chip.json"))
    assert sdar["counts"]["bd_pairs_causal"] \
        == 4 * (2 * 8192) * (2 * 8192 + 1) // 2


# -- the traced step and the plan ask the same functions ---------------------------
def spy_on(monkeypatch):
    """Every decision function wrapped: ``calls[part]`` lists the
    arguments behind ``interpret`` (a written rule's knows none: all of
    them) of each call made since, and
    ``again(asked, part, interpret)`` asks the unwrapped function each of
    ``asked[part]`` anew."""
    calls, plain = {}, {}
    for part, (module, fn) in {**DECISIONS, **WRITTEN}.items():
        plain[part] = getattr(module, fn)

        def spied(*args, _part=part):
            calls.setdefault(_part, []).append(args[_part not in WRITTEN:])
            return plain[_part](*args)

        monkeypatch.setattr(module, fn, spied)
    again = lambda asked, part, interpret: [
        plain[part](*(() if part in WRITTEN else (interpret,)), *args)
        for args in asked.get(part, [])]
    return calls, again


@pytest.fixture
def no_persistent_cache():
    """JAX's persistent compile cache off while a test builds steps: a
    worker that wrote a step's CPU executable there has died in
    ``compilation_cache.put_executable_and_time`` (a whole run of PR 71),
    and nothing here reads a hit."""
    from jax.experimental.compilation_cache import compilation_cache

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("name", CELLS)
def test_the_traced_step_decides_as_the_plan_and_feeds_its_counts(
        name, monkeypatch, no_persistent_cache):
    """A tiny cut's step, built and run once on the CPU: every decision
    function the trace called gives, asked again for a TPU, what the
    plan's rows hold for a TPU, kernel and reason, family by family; no
    traced line moves a counter, and the first call moves each by the
    plan's count, once a built step."""
    cfg = tiny(name, compute_dtype="float32") \
        if name.startswith(("qwen3", "granite")) else tiny(name)
    spc.init()
    # (the process holds other tests' steps too, ``tests/built.py``: those
    # of like rows are counted before this one is listed, and before the
    # spies hear a plan ask)
    want = plan_at(cfg, interpret=True)
    like = lambda: [p for p in train.plan_of_built_steps()
                    if p["rows"] == want["rows"] and p["b"] == want["b"]]
    gc.collect()
    others = len(like())
    calls, again = spy_on(monkeypatch)
    mesh, spec = make_mesh(jax.devices()[:1], MeshSpec(dp=1))
    # a step of its own: the first call's counts are what is read
    step, place = train.build_train_step(mesh, spec, model=cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_rows, (
        cfg.micro_batch, cfg.seq_len + 2)), jnp.int32)
    args = place(built.params(cfg, 3), ids[:, :-2], ids[:, 1:])
    with pytest.raises(RuntimeError, match="has not run yet"):
        step.plan()
    # but for the calls and the build record's own
    counted = lambda: {k: v for k, v in spc.counters().items()
                       if not k.startswith(("device_", "train_"))}
    before = counted()
    jax.make_jaxpr(step.jitted)(*args)
    assert counted() == before              # a trace counts nothing
    # what the trace asked, before a plan asks the same functions
    traced = {part: list(made) for part, made in calls.items()}
    state, _ = step(*args)
    plan = step.plan()
    assert plan == want
    moved = {k: v - before[k] for k, v in counted().items()
             if v != before[k]}
    assert moved == plan["counts"]
    # a second call feeds nothing more
    step(state, *args[1:])
    assert {k: spc.read(k) - before[k] for k in moved} == moved
    # the first call listed this step, once
    listed = like()
    assert len(listed) == others + 1
    assert all(p["module"] == "jit_otpu_train_step" for p in listed)
    # what the trace asked is what the plan asked: here, and for a TPU
    for interpret in (True, False):
        held = sublayers(plan_at(cfg, interpret))
        for part in (*DECISIONS, *WRITTEN):
            planned = [(made["impl"] != "xla", made["why"])
                       for _, _, sub in held
                       for piece, made in sub["parts"].items()
                       if piece == part]
            asked = again(traced, part, interpret)
            assert bool(planned) == bool(asked), part
            if planned and part == "gmm":
                # a layer's products are one part: on the kernel where
                # every one is, else the first refused one's clause
                assert {on for on, _ in planned} == {all(
                    on for on, _ in asked)}
                assert {why for _, why in planned} <= {
                    why for _, why in asked}
            else:
                assert set(planned) == set(asked), part


def test_no_traced_module_records_a_counter():
    """The model path's traced modules do not import ``runtime/spc``: the
    counters are fed in ``train.py``, outside JAX."""
    import ast
    import inspect

    from ompi_tpu.parallel import short_conv, sublayer

    for module in (attention, causal, dsa, experts, gdn, mamba, model,
                   objective, layers, short_conv, sublayer):
        tree = ast.parse(inspect.getsource(module))
        names = {a.name for node in ast.walk(tree)
                 if isinstance(node, (ast.Import, ast.ImportFrom))
                 for a in node.names}
        assert "spc" not in names, module.__name__
    fed = [node for node in ast.walk(ast.parse(inspect.getsource(train)))
           if isinstance(node, ast.Call)
           and ast.unparse(node.func) == "spc.record"]
    literal = {node.args[0].value for node in fed
               if isinstance(node.args[0], ast.Constant)}
    # by name only what a call or a read-back step counts; the plan's
    # counters go through one loop over ``plan()["counts"]``
    assert not {n for n in literal if n.endswith("_built")
                or n.startswith(("attn_pairs", "bd_pairs", "dsa_"))}
    assert "loop_exit_depth" in literal
