"""A train step's instructions by the program's own scopes
(``runtime/trace.scope_map``, ``step.scopes()``): the classification of an
``op_name`` path, the parse of an optimised HLO text (fusions, nested
fusions, the compiler's own instructions and what they inherit), and the
two model steps at the small widths of ``test_joyai_train`` and
``test_olmoe_train`` compiled on the CPU: only vocabulary scopes, every
instruction the program wrote lies under one, a checkpointed layer's
recomputation is told from its first forward pass, the update is the
update; and the scopes move no computation."""
import contextlib
import dataclasses
import functools
import json
import os
import re

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from test_joyai_train import F32 as JOYAI
from test_lfm2_train import F32 as LFM2
from test_nemotron_train import F32 as NEMOTRON
from test_olmoe_train import F32 as OLMOE_TWO_LAYERS
from test_qwen3next_train import F32 as QWEN3NEXT
from test_keye_train import F32 as KEYE
from test_sdar_train import F32 as SDAR
from test_parallel import MODEL_PATH
from test_smallthinker_train import F32 as SMALLTHINKER

from ompi_tpu.parallel import model, objective, train
from ompi_tpu.parallel.mesh import MeshSpec, make_mesh

import built as once
from ompi_tpu.runtime import trace
from ompi_tpu.tools import hlo_same

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OLMOE = dataclasses.replace(OLMOE_TWO_LAYERS, layers_here=1)  # as the cell

# the paths ISSUE 37 read off a compiled step, and what each is
PATHS = [
    ("jit(step)/jvp()/while/body/closed_call/otpu_moe/otpu_experts/"
     "dot_general", ["otpu_moe", "otpu_experts"], "forward"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/otpu_mla/dot_general", ["otpu_mla"], "remat"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "otpu_moe/otpu_experts/transpose", ["otpu_moe", "otpu_experts"],
     "backward"),
    ("jit(step)/jvp(otpu_head)/reduce_sum", ["otpu_head"], "forward"),
    ("jit(step)/transpose(jvp(otpu_head))/mul", ["otpu_head"], "backward"),
    ("jit(step)/otpu_adamw/sub", ["otpu_adamw"], "update"),
    # a scope repeated by a checkpoint's recomputation counts once; the
    # jit's own name is no scope; an update scope wins over transpose(
    ("jit(otpu_train_step)/transpose(jvp(otpu_layers))/while/body/"
     "checkpoint/rematted_computation/otpu_layers/otpu_mla/otpu_attn_proj/"
     "mul", ["otpu_layers", "otpu_mla", "otpu_attn_proj"], "remat"),
    ("jit(f)/transpose(jvp(otpu_bias_update))/sign", ["otpu_bias_update"],
     "update"),
    # a Pallas kernel's own name, before its pallas_call, is no scope
    ("jit(otpu_train_step)/jvp(otpu_layers)/while/body/closed_call/otpu_mla/"
     "jit(flash_causal_forward)/otpu_flash_causal_forward/pallas_call",
     ["otpu_layers", "otpu_mla"], "forward"),
    ("jit(otpu_train_step)/transpose(jvp(otpu_layers))/while/body/"
     "checkpoint/rematted_computation/otpu_layers/otpu_mla/"
     "jit(flash_causal_forward)/otpu_flash_causal_forward/pallas_call",
     ["otpu_layers", "otpu_mla"], "remat"),
    # two instructions the compiler folded into one (a reshape and a
    # transpose that move nothing): their paths joined, the first read
    ("jit(otpu_train_step)/jvp(otpu_layers)/otpu_attention/otpu_attn_proj/"
     "transpose;jit(otpu_train_step)/jvp(otpu_layers)/otpu_attention/"
     "otpu_attn_proj/reshape",
     ["otpu_layers", "otpu_attention", "otpu_attn_proj"], "forward"),
]


@pytest.mark.parametrize(
    "path,chain,which", PATHS,
    ids=[p[0].split("/", 1)[1][:60].replace(";", "+") for p in PATHS])
def test_a_path_gives_its_scopes_and_its_pass(path, chain, which):
    assert trace.scope_of_path(path) == (chain, which, [])
    text = ("HloModule jit_step, is_scheduled=true\n\n"
            "ENTRY %main.1 (p: f32[4]) -> f32[4] {\n"
            "  %p = f32[4]{0} parameter(0)\n"
            f"  ROOT %neg.1 = f32[4]{{0}} negate(%p), metadata={{op_name="
            f"\"{path}\" source_file=\"x.py\" source_line=1}}\n}}\n")
    got = trace.scope_map(text)
    assert got["module"] == "jit_step"
    assert got["ops"]["neg.1"] == {
        "chain": chain, "pass": which, "mixed": False, "inherited": False,
        "opcode": "negate"}


def test_a_name_outside_the_vocabulary_is_reported_not_kept():
    chain, which, unknown = trace.scope_of_path(
        "jit(f)/jvp(otpu_head)/otpu_new_part/add")
    assert (chain, which, unknown) == (["otpu_head"], "forward",
                                       ["otpu_new_part"])


# what the TPU compiler makes of a step, in small: a fusion that carries
# its root's path, one that mixes two scopes, a fusion of fusions without
# metadata, a reduction's computation (no op of its own), the compiler's
# own convert and ragged-dot (which inherit), a copy that feeds a tuple
# only (which does not)
M = 'metadata={op_name="jit(f)/%s" source_file="x.py" source_line=1}'
TEXT = f"""HloModule jit_f, is_scheduled=true, entry_computation_layout={{()->f32[]}}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), {M % "jvp(otpu_head)/reduce_sum"}
}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  %mul.1 = f32[8]{{0}} multiply(%param_0, %param_0), {M % "jvp(otpu_layers)/otpu_mla/otpu_attn_proj/mul"}
  ROOT %exp.1 = f32[8]{{0}} exponential(%mul.1), {M % "jvp(otpu_layers)/otpu_mla/otpu_attn_proj/exp"}
}}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  %sub.1 = f32[8]{{0}} subtract(%param_0.1, %param_0.1), {M % "otpu_stats/sub"}
  ROOT %add.1 = f32[8]{{0}} add(%sub.1, %param_0.1), {M % "otpu_adamw/add"}
}}

%fused_computation.4 (param_0.3: f32[8]) -> f32[8] {{
  %param_0.3 = f32[8]{{0}} parameter(0)
  ROOT %neg.4 = f32[8]{{0}} negate(%param_0.3), {M % "transpose(jvp(otpu_layers))/otpu_moe/otpu_router/neg"}
}}

%fused_computation.3 (param_0.2: f32[8]) -> f32[8] {{
  %param_0.2 = f32[8]{{0}} parameter(0)
  %fusion.5 = f32[8]{{0}} fusion(%param_0.2), kind=kLoop, calls=%fused_computation.4
  ROOT %fusion.6 = f32[8]{{0:T(8)S(1)}} fusion(%fusion.5), kind=kCustom, calls=%fused_computation.4
}}

ENTRY %main.2 (w: f32[8], x: f32[8]) -> (f32[8], f32[], f32[8]) {{
  %w = f32[8]{{0}} parameter(0)
  %x = f32[8]{{0:T(8)S(1)}} parameter(1)
  %convert.7 = bf16[8]{{0}} convert(%w)
  %ragged-dot-none.3 = f32[8]{{0}} custom-call(%x, %convert.7), custom_call_target="ragged-dot", metadata={{op_name="ragged-dot-none"}}
  %fusion.1 = f32[8]{{0}} fusion(%ragged-dot-none.3), kind=kLoop, calls=%fused_computation.1, {M % "jvp(otpu_layers)/otpu_mla/otpu_attn_proj/exp"}
  %fusion.2 = f32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, {M % "otpu_adamw/add"}
  %fusion.3 = f32[8]{{0:T(8)S(1)}} fusion(%fusion.2), kind=kCustom, calls=%fused_computation.3
  %reduce.1 = f32[] reduce(%fusion.3, %x), dimensions={{0}}, to_apply=%region_0.1, {M % "jvp(otpu_head)/reduce_sum"}
  %copy.8 = f32[8]{{0}} copy(%w)
  ROOT %tuple.1 = (f32[8]{{0}}, f32[], f32[8]{{0}}) tuple(%fusion.2, %reduce.1, %copy.8)
}}
"""


@pytest.fixture(scope="module")
def parsed():
    return trace.scope_map(TEXT)


def test_only_what_runs_as_an_op_is_listed(parsed):
    assert parsed["module"] == "jit_f" and parsed["unknown"] == []
    assert sorted(parsed["ops"]) == [
        "convert.7", "copy.8", "fusion.1", "fusion.2", "fusion.3",
        "ragged-dot-none.3", "reduce.1", "tuple.1", "w", "x"]


def test_a_fusion_carries_its_roots_scopes(parsed):
    one = parsed["ops"]["fusion.1"]
    assert one["chain"] == ["otpu_layers", "otpu_mla", "otpu_attn_proj"]
    assert (one["pass"], one["mixed"], one["opcode"]) == (
        "forward", False, "fusion")


def test_a_fusion_of_two_scopes_is_mixed_and_says_of_which(parsed):
    two = parsed["ops"]["fusion.2"]
    assert (two["chain"], two["pass"], two["mixed"]) == (
        ["otpu_adamw"], "update", True)
    assert two["kinds"] == ["otpu_adamw:update", "otpu_stats:forward"]


def test_a_fusion_of_fusions_without_metadata_takes_its_inner_roots(parsed):
    three = parsed["ops"]["fusion.3"]
    assert three["chain"] == ["otpu_layers", "otpu_moe", "otpu_router"]
    assert (three["pass"], three["mixed"], three["inherited"]) == (
        "backward", False, False)


def test_the_compilers_own_instructions_inherit_from_their_users(parsed):
    dot, cast = parsed["ops"]["ragged-dot-none.3"], parsed["ops"]["convert.7"]
    # its own op_name is not a path of the program's: chain and pass come
    # from its user, fusion.1; the cast's from the dot it feeds
    for op in (dot, cast):
        assert op["chain"] == ["otpu_layers", "otpu_mla", "otpu_attn_proj"]
        assert (op["pass"], op["inherited"]) == ("forward", True)


def test_nothing_is_inherited_through_a_tuple(parsed):
    copy = parsed["ops"]["copy.8"]
    assert (copy["chain"], copy["pass"], copy["inherited"]) == (
        [], None, False)


# -- the two model steps, compiled on the CPU at small widths ---------------
def built(cfg):
    """A step of this file's own, which has never run (``scopes()``
    raises, its text is compiled under the checkpoint policy of the
    moment), and its arguments."""
    mesh, spec = make_mesh(jax.devices()[:1], MeshSpec(dp=1))
    step, place = train.build_train_step(mesh, spec, model=cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_rows, (cfg.micro_batch, cfg.seq_len + 2)).astype(
            np.int32)
    n = cfg.seq_len + cfg.n_mtp_here
    return step, place(once.params(cfg, 0),
                       ids[:, :cfg.seq_len], ids[:, 1:1 + n])


def run_once(step, args):
    """``step`` after its first call, with the text it compiled to as
    ``step.text``: read from what the call left in memory, like
    ``scopes()``, so the program is compiled once."""
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding), args)
    step(*args)         # the state is donated: the text needs shapes only
    step.text = step.jitted.lower(*shapes).compile().as_text()
    return step


@pytest.fixture(scope="module")
def joyai():
    step, args = built(JOYAI)
    with pytest.raises(RuntimeError, match="has not run"):
        step.scopes()
    before = len(train.scopes_of_built_steps())
    run_once(step, args)
    return step, step.scopes(), before


@pytest.fixture(scope="module")
def olmoe():
    step = run_once(*built(OLMOE))
    return step, step.scopes()


@pytest.fixture(scope="module")
def nemotron():
    step = run_once(*built(NEMOTRON))
    return step, step.scopes()


@pytest.fixture(scope="module")
def lfm2():
    step = run_once(*built(LFM2))
    return step, step.scopes()


@pytest.fixture(scope="module")
def qwen3next():
    step = run_once(*built(QWEN3NEXT))
    return step, step.scopes()


@pytest.fixture(scope="module")
def smallthinker():
    step = run_once(*built(SMALLTHINKER))
    return step, step.scopes()


@pytest.fixture(scope="module")
def keye():
    step = run_once(*built(KEYE))
    return step, step.scopes()


@pytest.fixture(scope="module")
def sdar():
    step = run_once(*built(SDAR))
    return step, step.scopes()


def ran(scopes):
    return {k: v for k, v in scopes["ops"].items()
            if v["opcode"] not in trace.TRIVIAL_OPCODES}


@pytest.mark.parametrize("which", ["joyai", "olmoe", "nemotron", "lfm2",
                                   "qwen3next", "smallthinker", "keye",
                                   "sdar"])
def test_a_compiled_step_names_only_vocabulary_scopes(which, request):
    scopes = request.getfixturevalue(which)[1]
    assert scopes["module"] == "jit_otpu_train_step"
    assert scopes["unknown"] == []
    named = {s for v in scopes["ops"].values() for s in v["chain"]}
    assert named <= set(trace.STEP_SCOPES)
    assert {"otpu_embed", "otpu_layers", "otpu_attn_proj", "otpu_moe",
            "otpu_router", "otpu_dispatch", "otpu_experts",
            "otpu_head", "otpu_stats", "otpu_adamw"} <= named
    assert ({"otpu_mamba", "otpu_ssm_proj", "otpu_ssm_conv", "otpu_ssm_scan",
             "otpu_ssm_norm", "otpu_latent"} <= named) \
        == (which == "nemotron")
    assert ({"otpu_conv", "otpu_conv_proj", "otpu_conv_gate"} <= named) \
        == (which == "lfm2")
    assert ({"otpu_gdn", "otpu_gdn_proj", "otpu_gdn_conv", "otpu_gdn_rule",
             "otpu_gdn_norm"} <= named) == (which == "qwen3next")
    assert ("otpu_swa" in named) == (which == "smallthinker")
    assert ({"otpu_dsa", "otpu_dsa_index", "otpu_dsa_select",
             "otpu_dsa_loss"} <= named) == (which == "keye")
    assert ({"otpu_bd", "otpu_bd_noise", "otpu_bd_loss"} <= named) \
        == (which == "sdar")
    assert not {"otpu_bd", "otpu_bd_noise", "otpu_bd_loss"} & named \
        or which == "sdar"
    assert {v["pass"] for v in scopes["ops"].values()} <= {
        None, *trace.PASSES}


def test_every_op_of_a_mixer_lands_under_its_scope(nemotron):
    """Every instruction whose path passes through ``mamba_mixer``'s
    parts has ``otpu_mamba`` and one of the four parts in its chain, in
    the forward pass, the recomputed one and the backward one; the
    chunks' recurrence (a ``while``) is under the scan's scope; and what
    is under the scan's scope is under the mixer's."""
    ops = ran(nemotron[1])
    parts = {"otpu_ssm_proj", "otpu_ssm_conv", "otpu_ssm_scan",
             "otpu_ssm_norm"}
    mixer = [v for v in ops.values() if "otpu_mamba" in v["chain"]]
    assert len(mixer) > 100
    loose = [v for v in mixer if not parts & set(v["chain"])
             and not v["inherited"]]
    assert loose == []
    for part in parts:
        under = [v for v in ops.values() if part in v["chain"]]
        assert all("otpu_mamba" in v["chain"] for v in under), part
        assert {"forward", "remat", "backward"} <= {v["pass"]
                                                    for v in under}, part
    assert any(v["opcode"] == "while" and "otpu_ssm_scan" in v["chain"]
               for v in ops.values())
    latent = [v for v in ops.values() if "otpu_latent" in v["chain"]]
    assert latent and all("otpu_moe" in v["chain"] for v in latent)


def test_every_op_of_a_short_convolution_lands_under_its_scope(lfm2):
    """Every instruction whose path passes through ``short_conv`` has
    ``otpu_conv`` and one of its two parts in its chain, in the forward
    pass, the recomputed one and the backward one; and the layer's other
    sublayers keep the scopes they have in the other models."""
    ops = ran(lfm2[1])
    parts = {"otpu_conv_proj", "otpu_conv_gate"}
    conv = [v for v in ops.values() if "otpu_conv" in v["chain"]]
    assert len(conv) > 30
    assert [v for v in conv if not parts & set(v["chain"])
            and not v["inherited"]] == []
    for part in parts:
        under = [v for v in ops.values() if part in v["chain"]]
        assert all("otpu_conv" in v["chain"] for v in under), part
        assert {"forward", "remat", "backward"} <= {v["pass"]
                                                    for v in under}, part
    named = {s for v in ops.values() for s in v["chain"]}
    assert {"otpu_attention", "otpu_dense_mlp", "otpu_bias_update"} <= named
    assert "otpu_shared_expert" not in named and "otpu_mla" not in named


def test_every_op_of_a_delta_net_operator_lands_under_its_scope(qwen3next):
    """Every instruction whose path passes through ``gated_delta_net`` has
    ``otpu_gdn`` and one of its four parts in its chain, in the forward
    pass, the recomputed one and the backward one; the chunks' recurrence
    (a ``while``) is under the rule's scope; the attention gate lies under
    ``otpu_attn_proj`` and the shared expert's gate under
    ``otpu_shared_expert``; and no bias update is in the step."""
    ops = ran(qwen3next[1])
    parts = {"otpu_gdn_proj", "otpu_gdn_conv", "otpu_gdn_rule",
             "otpu_gdn_norm"}
    gdn = [v for v in ops.values() if "otpu_gdn" in v["chain"]]
    assert len(gdn) > 100
    assert [v for v in gdn if not parts & set(v["chain"])
            and not v["inherited"]] == []
    for part in parts:
        under = [v for v in ops.values() if part in v["chain"]]
        assert all("otpu_gdn" in v["chain"] for v in under), part
        assert {"forward", "remat", "backward"} <= {v["pass"]
                                                    for v in under}, part
    assert any(v["opcode"] == "while" and "otpu_gdn_rule" in v["chain"]
               for v in ops.values())
    named = {s for v in ops.values() for s in v["chain"]}
    assert {"otpu_attention", "otpu_shared_expert", "otpu_loss"} <= named
    assert not {"otpu_bias_update", "otpu_dense_mlp", "otpu_mla",
                "otpu_conv"} & named


def test_a_window_layers_attention_lands_under_its_own_scope(smallthinker):
    """The three window layers' attention sublayers (one scanned run) are
    under ``otpu_swa`` in every pass, their projections under
    ``otpu_attn_proj`` inside it; the full layer's keeps
    ``otpu_attention``; no instruction is under both; and the routers'
    products, made before attention, are under ``otpu_moe`` /
    ``otpu_router`` and in the forward pass alone."""
    ops = ran(smallthinker[1])
    swa = [v for v in ops.values() if "otpu_swa" in v["chain"]]
    full = [v for v in ops.values() if "otpu_attention" in v["chain"]]
    assert len(swa) > 50 and len(full) > 50
    assert not [v for v in swa if "otpu_attention" in v["chain"]]
    for under in (swa, full):
        assert {"forward", "remat", "backward"} <= {v["pass"] for v in under}
        assert any("otpu_attn_proj" in v["chain"] for v in under)


def test_a_sparse_attention_sublayer_lands_under_its_own_scopes(keye):
    """The four sparse-attention sublayers (one scanned run) are under
    ``otpu_dsa`` in every pass and never under ``otpu_attention``; the
    indexer, the selection and the alignment loss each under its own scope
    inside it, beside ``otpu_attn_proj``; the selection in the forward pass
    alone (it is kept, and nothing differentiates it)."""
    ops = ran(keye[1])
    dsa = [v for v in ops.values() if "otpu_dsa" in v["chain"]]
    assert len(dsa) > 100
    assert not [v for v in ops.values() if "otpu_attention" in v["chain"]]
    assert {"forward", "remat", "backward"} <= {v["pass"] for v in dsa}
    for part in ("otpu_dsa_index", "otpu_dsa_select", "otpu_dsa_loss",
                 "otpu_attn_proj"):
        under = [v for v in ops.values() if part in v["chain"]]
        assert under and all("otpu_dsa" in v["chain"] for v in under), part
    assert {v["pass"] for v in ops.values()
            if "otpu_dsa_select" in v["chain"]} == {"forward"}
    assert {"forward", "remat", "backward"} <= {
        v["pass"] for v in ops.values() if "otpu_dsa_index" in v["chain"]}


def test_a_block_diffusion_step_lands_under_its_own_scopes(sdar):
    """The four block-diffusion sublayers (one scanned run) are under
    ``otpu_bd`` in every pass and never under ``otpu_attention``, their
    projections beside the kernels' twins inside it; the noise is drawn
    under ``otpu_bd_noise`` in the forward pass alone (nothing
    differentiates it) and outside the layers; the weighted loss's own work
    is under ``otpu_bd_loss`` inside ``otpu_head``."""
    ops = ran(sdar[1])
    bd = [v for v in ops.values() if "otpu_bd" in v["chain"]]
    assert len(bd) > 50
    assert not [v for v in ops.values() if "otpu_attention" in v["chain"]]
    assert {"forward", "remat", "backward"} <= {v["pass"] for v in bd}
    under = [v for v in ops.values() if "otpu_attn_proj" in v["chain"]]
    assert under and all("otpu_bd" in v["chain"] for v in under)
    noise = [v for v in ops.values() if "otpu_bd_noise" in v["chain"]]
    assert noise and {v["pass"] for v in noise} == {"forward"}
    assert not any("otpu_layers" in v["chain"] for v in noise)
    loss = [v for v in ops.values() if "otpu_bd_loss" in v["chain"]]
    assert loss and all("otpu_head" in v["chain"] for v in loss)


@pytest.mark.parametrize("which", ["joyai", "olmoe", "nemotron", "lfm2",
                                   "qwen3next", "smallthinker", "keye",
                                   "sdar"])
def test_every_instruction_the_program_wrote_has_a_chain(which, request):
    """Not a parameter, constant, tuple or bitcast, and with a path of
    the program's (``pass`` None: the compiler's own, which on the CPU
    are copies and rewritten reductions)."""
    ops = ran(request.getfixturevalue(which)[1])
    bare = [k for k, v in ops.items() if v["pass"] and not v["chain"]]
    assert bare == []
    assert sum(v["pass"] is not None for v in ops.values()) > len(ops) * 0.8


def test_a_checkpointed_layers_recomputation_is_told_apart(joyai, olmoe):
    """JoyAI's shape has more than one layer, so each is recomputed in
    its backward pass: ops under ``rematted_computation``, in the layers'
    own scopes.  One layer (the OLMoE cell's cut) checkpoints nothing."""
    remat = [v for v in ran(joyai[1]).values() if v["pass"] == "remat"]
    assert len(remat) > 50
    assert all("otpu_layers" in v["chain"] for v in remat)
    assert {"otpu_mla", "otpu_attn_proj", "otpu_moe", "otpu_dense_mlp"} <= {
        s for v in remat for s in v["chain"]}
    assert not [v for v in olmoe[1]["ops"].values() if v["pass"] == "remat"]
    for _, scopes in (joyai[:2], olmoe):
        passes = {v["pass"] for v in ran(scopes).values()}
        assert {"forward", "backward", "update"} <= passes


# what an expert block's routing runs as, by an instruction's own line
# (fused ones too): the dispatch's argsort, the router's top-k (a custom
# call on the CPU, a whole sort on the TPU), the chosen scores (since PR
# 59 compares of an expert's column against the lane's number, summed:
# the router's only ``eq``), the router's (T, E) product, the held
# experts' loop
ROUTING = {
    "sort": lambda line, path: " sort(" in line,
    "top-k": lambda line, path: path.endswith("/top_k"),
    "chosen scores": lambda line, path: path.endswith("otpu_router/eq"),
    "router product": lambda line, path:
        path.endswith("otpu_router/dot_general"),
    "experts' loop": lambda line, path:
        " while(" in line and "otpu_experts" in path,
}


# causal attention's own products (on the CPU the kernels' ``jnp`` twins:
# scores, numerator, and the backward pairs'), which lie in an attention
# sublayer outside its projections
ATTENTION = {
    "attention's products": lambda line, path:
        path.endswith("/dot_general") and trace.scope_of_path(path)[0][-1:]
        in (["otpu_mla"], ["otpu_attention"], ["otpu_swa"], ["otpu_dsa"],
            ["otpu_bd"]),
}
# a learned selection's two dear parts: the counting passes that find a
# row's bar (comparisons summed a row) and the alignment loss's products
SELECTION = {
    "the selection's counting": lambda line, path:
        "otpu_dsa_select" in path and path.endswith("/reduce_sum"),
    "the alignment loss's products": lambda line, path:
        "otpu_dsa_loss" in path and path.endswith("/dot_general"),
}


CONFIGS = dict(joyai=JOYAI, nemotron=NEMOTRON, lfm2=LFM2,
               qwen3next=QWEN3NEXT, smallthinker=SMALLTHINKER, keye=KEYE,
               sdar=SDAR)


@functools.cache
def bare_text(cfg):
    """``cfg``'s step compiled under the bare checkpoint, which keeps
    nothing: once a process, for the tests of what the layers' own policy
    keeps."""
    kept = objective.layer_checkpoint_policy
    objective.layer_checkpoint_policy = \
        lambda: jax.checkpoint_policies.nothing_saveable
    try:
        step, args = built(cfg)
        return step.jitted.lower(*args).compile().as_text()
    finally:
        objective.layer_checkpoint_policy = kept


def routing_by_pass(text, kinds_of=ROUTING):
    """{pass: the kinds of ``kinds_of`` among its instructions} and
    {pass: its scopes} of a step's compiled ``text``: the module
    fixture's (``step.text``) or ``bare_text``'s."""
    kinds, scopes = {}, {}
    for line in text.splitlines():
        path = re.search(r'op_name="([^"]*)"', line)
        if " = " not in line or not path:
            continue
        chain, which, _ = trace.scope_of_path(path.group(1))
        scopes.setdefault(which, set()).update(chain)
        kinds.setdefault(which, set()).update(
            k for k, is_it in kinds_of.items() if is_it(line, path.group(1)))
    return kinds, scopes


@pytest.mark.parametrize("which,loop_is_read", [("joyai", False),
                                                ("nemotron", True),
                                                ("lfm2", False),
                                                ("qwen3next", False),
                                                ("smallthinker", False)],
                         ids=["joyai", "nemotron", "lfm2", "qwen3next",
                              "smallthinker"])
def test_a_layers_checkpoint_keeps_the_routing_and_nothing_else(
        which, loop_is_read, request):
    """``model_loss``'s checkpoint keeps what an expert block names
    (``experts.CHECKPOINT_KEEPS``): no recomputed instruction is the
    dispatch's sort, the router's top-k, the chosen scores' compares
    or the (T, E) product, nor, where the loop's sum is read by a weight
    gradient (Nemotron's ``lat_up``; JoyAI's XLA drops by itself), the
    held experts' loop; what is not named (attention's projections, a
    mixer) is recomputed as before.  The bare checkpoint recomputes
    every one of them: the patterns see what they are meant to."""
    cfg = CONFIGS[which]
    kinds, scopes = routing_by_pass(request.getfixturevalue(which)[0].text)
    assert kinds["forward"] == set(ROUTING)
    assert kinds["remat"] == set()
    assert "otpu_attn_proj" in scopes["remat"]
    assert ("otpu_mamba" in scopes["remat"]) == (cfg is NEMOTRON)
    assert ("otpu_conv_gate" in scopes["remat"]) == (cfg is LFM2)
    assert ("otpu_gdn_rule" in scopes["remat"]) == (cfg is QWEN3NEXT)
    # the elementwise rest of the router is recomputed: scores, weights
    assert "otpu_router" in scopes["remat"]
    bare, _ = routing_by_pass(bare_text(cfg))
    assert bare["forward"] == set(ROUTING)
    assert bare["remat"] == set(ROUTING) - (
        set() if loop_is_read else {"experts' loop"})


def test_a_layers_checkpoint_keeps_the_selection_and_the_losss_gradients(
        keye):
    """``model_loss``'s checkpoint keeps a sparse-attention sublayer's
    selection and its alignment loss's rows and gradients
    (``model.CHECKPOINT_KEEPS``): the recomputed pass does not select
    again, and the loss's one pass runs in the forward pass alone (its
    backward rule scales what was kept).  The bare checkpoint recomputes
    both: the patterns see what they are meant to."""
    kinds, scopes = routing_by_pass(keye[0].text, SELECTION)
    assert kinds["forward"] == set(SELECTION)
    # (the twin's loss makes its gradients by autodiff inside its forward
    # rule, so those products' paths read ``transpose(jvp(..))``: they are
    # no recomputation, and on a TPU they are the one kernel's)
    assert kinds.get("remat", set()) == set()
    assert "the selection's counting" not in kinds.get("backward", set())
    assert {"otpu_attn_proj", "otpu_dsa_index"} <= scopes["remat"]
    bare, _ = routing_by_pass(bare_text(KEYE), SELECTION)
    assert bare["forward"] == bare["remat"] == set(SELECTION)


@pytest.mark.parametrize("which", list(CONFIGS))
def test_a_layers_checkpoint_keeps_attentions_forward_results(which,
                                                              request):
    """``model_loss``'s checkpoint keeps causal attention's o and
    logsumexp (``model.CHECKPOINT_KEEPS``): attention's own products are
    in the forward and the backward pass and in no recomputed one, while
    the projections that make q, k and v (which the backward pass reads
    and nothing keeps) are recomputed as before.  The bare checkpoint
    recomputes the products too: the pattern sees what it is meant to."""
    kinds, scopes = routing_by_pass(request.getfixturevalue(which)[0].text,
                                    ATTENTION)
    assert kinds["forward"] == kinds["backward"] == set(ATTENTION)
    assert kinds.get("remat", set()) == set()
    assert "otpu_attn_proj" in scopes["remat"]
    bare, _ = routing_by_pass(bare_text(CONFIGS[which]), ATTENTION)
    assert bare["forward"] == bare["remat"] == set(ATTENTION)


@pytest.mark.parametrize("which", ["joyai", "olmoe"])
def test_the_updates_ops_are_the_update(which, request):
    ops = ran(request.getfixturevalue(which)[1])
    update = {k: v for k, v in ops.items() if v["pass"] == "update"}
    assert len(update) >= 10
    for v in ops.values():
        under = bool({"otpu_adamw", "otpu_bias_update"} & set(v["chain"]))
        assert under == (v["pass"] == "update") or v["inherited"]
    if which == "joyai":
        assert any("otpu_bias_update" in v["chain"] for v in update.values())


def test_the_process_gives_the_maps_of_the_steps_it_ran(joyai):
    step, scopes, before = joyai
    maps = train.scopes_of_built_steps()
    assert len(maps) > before       # the step that had not run was left out
    assert scopes in maps
    assert step.scopes() == scopes          # a pure function of the text


def test_the_vocabulary_is_the_sources_and_the_benchmarks():
    """Every ``named_scope`` the model path's files open (a sublayer's
    own is its entry's ``scope``) is in ``STEP_SCOPES``, every name of it
    is opened somewhere, and the benchmark's data file repeats it."""
    opened = {entry.scope for entry in model.SUBLAYERS}
    for name in MODEL_PATH:
        with open(os.path.join(ROOT, "ompi_tpu", "parallel", name + ".py"),
                  encoding="utf-8") as f:
            opened |= set(re.findall(r'named_scope\("(otpu_\w+)"\)',
                                     f.read()))
    assert opened == set(trace.STEP_SCOPES)
    assert set(trace.UPDATE_SCOPES) <= opened
    with open(os.path.join(ROOT, "benchmark", "harness", "scopes.json"),
              encoding="utf-8") as f:
        data = json.load(f)
    # the benchmark's file is the leading part of the tuple (a PR that
    # adds a cell may not edit it), and every name behind it is listed
    # by a metric file that reads it (``vocabulary``, for
    # readers/trace_scope_share_wide.py)
    held = len(data["scopes"])
    assert data["scopes"] == list(trace.STEP_SCOPES[:held])
    listed = set()
    metrics = os.path.join(ROOT, "benchmark", "metrics")
    for name in os.listdir(metrics):
        with open(os.path.join(metrics, name), encoding="utf-8") as f:
            listed |= set(json.load(f).get("params", {}).get(
                "vocabulary", ()))
    # the residual path's five wait for room in ``per_layer`` with the
    # metrics that would list them (PERF.md section 7, ROADMAP.md D0)
    waiting = {s for s in trace.STEP_SCOPES if s.startswith("otpu_hc")}
    assert len(waiting) == 5
    assert set(trace.STEP_SCOPES[held:]) - waiting <= listed \
        <= set(trace.STEP_SCOPES) - waiting
    assert data["passes"] == list(trace.PASSES)
    assert data["update_scopes"] == list(trace.UPDATE_SCOPES)


@pytest.mark.parametrize("cfg", [JOYAI, OLMOE], ids=["joyai", "olmoe"])
def test_the_scopes_move_no_computation(cfg, monkeypatch):
    """The step compiled with every ``named_scope`` a no-op is the same
    program, instruction for instruction, once the metadata is gone
    (``tools/hlo_same``: what PR 37 showed of both cells' steps offline,
    for a v5e)."""
    def text():
        step, args = built(cfg)
        return step.jitted.lower(*args).compile().as_text()

    # the compile cache's key leaves the metadata out, so the second
    # text would be the first's, loaded: compile both (JAX decides once
    # a process whether it uses the cache, hence the resets)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        scoped = text()
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert "otpu_adamw" in scoped and "otpu_adamw" not in bare
    assert hlo_same.compare(scoped, bare).startswith("EQUAL")


def test_hlo_same_tells_a_moved_instruction_from_a_renamed_one():
    renamed = TEXT.replace("otpu_stats/sub", "otpu_loss/sub").replace(
        "%mul.1", "%mul.77")
    assert hlo_same.compare(TEXT, renamed) == "EQUAL but for instruction names"
    assert hlo_same.compare(TEXT, TEXT.replace("x.py", "y.py")) == "EQUAL"
    moved = TEXT.replace("subtract(%param_0.1, %param_0.1)",
                         "multiply(%param_0.1, %param_0.1)")
    assert moved != TEXT and hlo_same.compare(TEXT, moved) == "DIFFERENT"


def test_the_residual_paths_scopes_and_counters_are_named():
    """PR 73's five scopes stand behind every name that was there, the
    whole before its four parts, and its three counters are SPC's: two fed
    from a step's plan, one read back (``tests/test_xing_step.py`` moves
    them)."""
    from ompi_tpu.runtime import spc

    at = trace.STEP_SCOPES.index("otpu_exit_loss")
    assert trace.STEP_SCOPES[at + 1:at + 6] == (
        "otpu_hc", "otpu_hc_maps", "otpu_hc_sinkhorn", "otpu_hc_read",
        "otpu_hc_write")
    spc.init()
    assert {"hc_built", "hc_sweeps_built", "hc_defect_ppm"} \
        <= set(spc.counters())
    for which, path in (
            ("forward", "jit(f)/jvp(otpu_layers)/otpu_hc/otpu_hc_read/mul"),
            ("remat", "jit(f)/transpose(jvp(otpu_layers))/checkpoint/"
             "rematted_computation/otpu_hc/otpu_hc_write/add"),
            ("backward", "jit(f)/transpose(jvp(otpu_layers))/otpu_hc/"
             "otpu_hc_maps/dot_general")):
        chain, got, unknown = trace.scope_of_path(path)
        assert (chain[:2], got, unknown) == (["otpu_layers", "otpu_hc"],
                                             which, []), path
