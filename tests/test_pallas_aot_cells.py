"""AOT compile-contract tests, the second file (``test_pallas_aot.py``
is the first; ``aot_rows.py`` holds what both share and says why there are
two): OLMoE's, Qwen3-Next's, SmallThinker's, Keye's and SDAR's kernels and
steps at the cells' shapes, the experts' grouped matmul at every cell's, and
the whole inventory (slow).
"""
import json
import re

import pytest

import aot_rows
from aot_rows import (fits_a_v5e, kernel_bodies, op_paths, rows_with_texts,
                      run_aot_subprocess,
                      stacked_weight_gradients_read_what_was_made)

pytestmark = aot_rows.SKIP_AOT


@pytest.fixture(scope="module")
def olmoe_rows():
    """One child for the OLMoE cases: the cell's attention, its two
    kernels alone and the whole step, for one v5e device.  The child
    has a time limit (an offline compile has run for 40 minutes before
    now, PR 27): 240 s, of which the step takes about 15."""
    pytest.importorskip("libtpu")
    res = run_aot_subprocess("--only", "olmoe", "--topology", "v5e:2x2")
    assert res.get("rows"), res.get("error")
    return {r["kernel"]: r for r in res["rows"]}


def test_olmoe_attention_aot_compiles_at_the_cells_shape(olmoe_rows):
    """Causal attention's forward pass as the OLMoE step calls it, 2 x
    16 heads x 4,096 x 128 in bfloat16: through the model's entry and
    alone it is one kernel call that takes q, k and v whole (no slice,
    no concatenate beside it)."""
    for name in ("olmoe_causal_attention_4k", "olmoe_flash_causal_forward"):
        assert olmoe_rows[name].get("compiled"), json.dumps(
            olmoe_rows[name], indent=1)
        ops = olmoe_rows[name]["entry_ops"]
        assert ops["custom-call"] == 1, ops
        assert not {"slice", "concatenate", "fusion"} & set(ops), ops


def test_olmoe_attention_backward_aot_compiles_at_the_cells_shape(
        olmoe_rows):
    """The fused block pair of attention's backward at the OLMoE step's
    shape (2 x 16 heads x 4,096 x 128 in bfloat16, blocks of 1,024): the
    kernel alone, the pair a scalar operand, so the plain and the
    diagonal pair are one compiled kernel; and the ten pairs unrolled."""
    row = olmoe_rows["olmoe_attn_block_backward_1k"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"].get("custom-call", 0) >= 1, row["entry_ops"]
    walk = olmoe_rows["olmoe_attn_backward_walk_4k"]
    assert walk.get("compiled"), json.dumps(walk, indent=1)
    assert walk["entry_ops"]["custom-call"] >= 10
    assert "while" not in walk["entry_ops"], walk["entry_ops"]


def test_olmoe_train_step_aot_compiles_from_the_cells_configuration(
        olmoe_rows):
    """The whole step of ``benchmark/configs/olmoe-1b-7b-train-1chip
    .json`` (published widths, one layer): attention's forward kernel
    once and the backward's block pair ten times, nine grouped expert
    matmuls, one loop over the head's row blocks."""
    row = olmoe_rows["olmoe_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["custom-call"] >= 29
    assert row["entry_ops"]["while"] == 1
    assert row["compile_s"] < 120


@pytest.fixture(scope="module")
def qwen3next_rows():
    """One child for the Qwen3-Next-80B-A3B cases: attention's two kernels
    at a head width of 256 alone and the whole step of the cell's own
    configuration file, and the delta rule's and the DeltaNet
    convolution's two kernels each at the cell's shape, for one v5e
    device (about 2 min of the 600: the step's 16,384 positions)."""
    return rows_with_texts("qwen3next_")


def test_attention_aot_compiles_at_a_head_width_of_256(qwen3next_rows):
    """q, k and v 256 wide, 16 query heads on 2 key-value heads (8 a
    group) x 16,384 positions in 16 blocks of 1,024: the forward kernel
    in one call, the backward's block pair alone and as the 136 pairs of
    one ``lax.scan``.  A tile of 1,024 holds twice the operands of a
    128-wide one and compiles under the kernels' own VMEM limits as they
    are; k and v are repeated a query head nowhere."""
    row = qwen3next_rows["qwen3next_flash_causal_forward"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"].get("custom-call", 0) >= 1, row["entry_ops"]
    pair = qwen3next_rows["qwen3next_attn_block_backward_1k"]
    assert pair.get("compiled"), json.dumps(pair, indent=1)
    assert pair["entry_ops"].get("custom-call", 0) >= 1, pair["entry_ops"]
    walk = qwen3next_rows["qwen3next_attn_backward_walk_16k"]
    assert walk.get("compiled"), json.dumps(walk, indent=1)
    assert walk["entry_ops"].get("while") == 1, walk["entry_ops"]
    for case in ("qwen3next_flash_causal_forward",
                 "qwen3next_attn_backward_walk_16k", "qwen3next_step_1chip"):
        with open(qwen3next_rows[case]["hlo"], encoding="utf-8") as f:
            assert not re.search(r"bf16\[1,2,8,16384,256\]", f.read()), case


def test_qwen3next_train_step_aot_compiles_from_the_cells_configuration(
        qwen3next_rows):
    """The whole step of ``benchmark/configs/qwen3-next-80b-a3b-train-1chip
    .json`` (published widths; layers 0-3 of 48, 32 of 512 experts, 1 x
    16,384 tokens): it fits the chip beside its 7.5 GB of state with the
    delta rule on its kernels and no step-wise checkpoint (PR 52: the
    forward kernel in the forward and the recomputed pass, the backward
    kernel once, all under ``otpu_gdn_rule``, and no loop of XLA's
    there), the three like DeltaNet layers are one loop, and the state's
    fifth slot is rows of no entries."""
    row = qwen3next_rows["qwen3next_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["while"] >= 3
    assert row["compile_s"] < 400
    assert fits_a_v5e(row), json.dumps(row, indent=1)
    assert row["argument_bytes"] < 3 * 4 * 625_667_136 + (1 << 20)
    scopes = {name for _, path in op_paths(row) for name in
              re.findall(r"otpu_gdn\w*", path)}
    assert scopes == {"otpu_gdn", "otpu_gdn_proj", "otpu_gdn_conv",
                      "otpu_gdn_rule", "otpu_gdn_norm",
                      "otpu_gdn_rule_fwd", "otpu_gdn_rule_bwd",
                      "otpu_gdn_conv_fwd", "otpu_gdn_conv_bwd"}
    rule = [(line, path) for line, path in op_paths(row)
            if "/otpu_gdn_rule/" in path]
    kernels = sorted(path.split("jit(otpu_train_step)/")[1]
                     for line, path in rule if " custom-call(" in line)
    assert [(k.split("/")[0], "rematted_computation" in k,
             k.split("/")[-2]) for k in kernels] == [
        ("jvp(otpu_layers)", False, "otpu_gdn_rule_fwd"),
        ("transpose(jvp(otpu_layers))", False, "otpu_gdn_rule_bwd"),
        ("transpose(jvp(otpu_layers))", True, "otpu_gdn_rule_fwd")], kernels
    assert not [line for line, _ in rule if " while(" in line]
    # the convolution's kernels (PR 54) under ``otpu_gdn_conv``: forward in
    # the forward and the recomputed pass, and once more in front of the
    # rule's backward kernel, where [q | k | v] is made again and not kept
    # (the compiler may not take the recomputed pass's call for it)
    conv = sorted(path.split("jit(otpu_train_step)/")[1]
                  for line, path in op_paths(row)
                  if "/otpu_gdn_conv/" in path and " custom-call(" in line)
    assert [(k.split("/")[0], "rematted_computation" in k,
             k.split("/")[-2]) for k in conv] == [
        ("jvp(otpu_layers)", False, "otpu_gdn_conv_fwd"),
        ("transpose(jvp(otpu_layers))", False, "otpu_gdn_conv_bwd"),
        ("transpose(jvp(otpu_layers))", False, "otpu_gdn_conv_fwd"),
        ("transpose(jvp(otpu_layers))", True, "otpu_gdn_conv_fwd")], conv


def test_the_deltanet_convolution_aot_compiles_at_the_cells_shape(
        qwen3next_rows):
    """``gdn._kernel_conv`` where Mosaic compiles, 4 taps over the (1,
    16384, 8192) float32 [q | k | v]: the forward is one kernel call and
    nothing beside it (no padded copy, no relayout: a ``fusion`` or a
    ``copy`` would be one), its gradient the backward kernel alone, and
    neither holds more than its operands and results (x and y, 1.07 GB;
    x, dy and dx, 1.61 GB).  All arithmetic is float32."""
    fwd = qwen3next_rows["qwen3next_gdn_conv_forward"]
    bwd = qwen3next_rows["qwen3next_gdn_conv_backward"]
    for row, arrays in ((fwd, 2), (bwd, 3)):
        assert row.get("compiled"), json.dumps(row, indent=1)
        ops = row["entry_ops"]
        assert ops.get("custom-call") == 1, ops
        assert not {"fusion", "copy", "pad", "while"} & set(ops), ops
        assert row["peak_bytes"] < arrays * 4 * 16384 * 8192 + (1 << 20)
    with open(fwd["hlo"], encoding="utf-8") as f:
        bodies = kernel_bodies(f.read(), "otpu_gdn_conv_")
    with open(bwd["hlo"], encoding="utf-8") as f:
        bodies.update(kernel_bodies(f.read(), "otpu_gdn_conv_"))
    assert sorted(bodies) == ["otpu_gdn_conv_bwd", "otpu_gdn_conv_fwd"]
    for name, text in bodies.items():
        assert "xf32>" in text and "bf16" not in text, name


def test_the_delta_rule_aot_compiles_at_the_cells_shape(qwen3next_rows):
    """``gated_delta_chunked`` where Mosaic compiles, at 16 key heads, 32
    value heads, 128 / 128 and 16,384 positions in chunks of 64: the
    forward alone is one kernel call and no loop; its gradient is the
    forward kernel, which also writes the entering states (0.54 GB) and
    the inverses (0.13 GB), and the backward kernel."""
    fwd = qwen3next_rows["qwen3next_gdn_rule_forward"]
    assert fwd.get("compiled"), json.dumps(fwd, indent=1)
    assert fwd["entry_ops"].get("custom-call") == 1, fwd["entry_ops"]
    bwd = qwen3next_rows["qwen3next_gdn_rule_backward"]
    assert bwd.get("compiled"), json.dumps(bwd, indent=1)
    assert bwd["entry_ops"].get("custom-call") == 2, bwd["entry_ops"]
    for row in (fwd, bwd):
        assert "while" not in row["entry_ops"], row["entry_ops"]
    # every product of the kernels is float32 at the highest precision
    with open(bwd["hlo"], encoding="utf-8") as f:
        bodies = kernel_bodies(f.read(), "otpu_gdn_rule_")
    assert sorted(bodies) == ["otpu_gdn_rule_bwd", "otpu_gdn_rule_fwd"]
    for name, text in bodies.items():
        products = [ln for ln in text.split("\n") if "tpu.matmul" in ln]
        assert len(products) > 40, (name, len(products))
        assert all("contract_precision<fp32>" in ln
                   and "xf32>" in ln and "bf16" not in ln
                   for ln in products), name
    # operands and results, the states, the inverses: under 3 GB
    assert bwd["peak_bytes"] < 3 << 30


@pytest.fixture(scope="module")
def smallthinker_rows():
    """One child for the SmallThinker-21BA3B cases: attention's two
    kernels under a window of 4,096 at the cell's shape and the whole step
    of the cell's own configuration file, for one v5e device (about a
    minute of the 600)."""
    return rows_with_texts("smallthinker_")


def test_the_window_kernels_aot_compile_at_the_cells_shape(
        smallthinker_rows):
    """28 query heads on 4 key-value heads (7 a group) x 16,384 positions
    at a head width of 128 under a window of 4,096: the forward kernel in
    one call whose grid holds 5 kv tiles a q tile, the backward's 70 block
    pairs one ``lax.scan`` with the far pair's masked strips in the
    kernel; both under the kernels' own VMEM limits as they are, and k
    and v repeated a query head nowhere."""
    row = smallthinker_rows["smallthinker_flash_window_forward"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"].get("custom-call", 0) >= 1, row["entry_ops"]
    walk = smallthinker_rows["smallthinker_attn_window_backward"]
    assert walk.get("compiled"), json.dumps(walk, indent=1)
    assert walk["entry_ops"].get("while") == 1, walk["entry_ops"]
    with open(walk["hlo"], encoding="utf-8") as f:
        text = f.read()
    assert re.search(r"s32\[70,2\]", text) \
        and not re.search(r"s32\[136,2\]", text)
    for case in ("smallthinker_flash_window_forward",
                 "smallthinker_attn_window_backward",
                 "smallthinker_step_1chip"):
        with open(smallthinker_rows[case]["hlo"], encoding="utf-8") as f:
            assert not re.search(r"bf16\[1,4,7,16384,128\]", f.read()), case


def test_smallthinker_train_step_aot_compiles_from_the_cells_configuration(
        smallthinker_rows):
    """The whole step of ``benchmark/configs/smallthinker-21b-a3b-train-
    1chip.json`` (published widths; layers 0-3 of 52, 16 of 64 experts, 1
    x 16,384 tokens): it fits the chip beside its 7.9 GB of state, the
    three like window layers are one loop, the forward kernel stands once
    in the full layer (under ``otpu_attention``) and once in the window
    run's body (under ``otpu_swa``) and nowhere in a recomputed pass, and
    the routers' float32 products stand before their layers'
    attention."""
    row = smallthinker_rows["smallthinker_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["while"] >= 3
    assert row["compile_s"] < 300
    assert fits_a_v5e(row), json.dumps(row, indent=1)
    assert row["argument_bytes"] < 3 * 4 * 656_529_920 + (1 << 20)
    kernels = [path.split("jit(otpu_train_step)/")[1]
               for line, path in op_paths(row) if " custom-call(" in line]
    forward = sorted(p for p in kernels if "/otpu_flash_causal_forward/" in p)
    assert not [p for p in forward if "rematted_computation" in p]
    assert [("otpu_swa" in p, "otpu_attention" in p) for p in forward] \
        == [(False, True), (True, False)], forward
    backward = [p for p in kernels if "/otpu_attn_block_backward/" in p]
    assert {"otpu_swa" in p for p in backward} == {True, False}
    # the window layers' q and k, turned and not normed, reach the flash
    # kernels through ``ops/head_norm_rope``'s pair without a gain (PR 68),
    # in the window run's body alone: the full layer is not turned, and
    # the lines are its way
    _holds_the_head_norm_rope_kernels(kernels, "otpu_swa", "otpu_head_rope")
    assert not [p for p in kernels if "otpu_head_norm_rope" in p]


@pytest.fixture(scope="module")
def keye_rows():
    """One child for the Keye-VL-2.0-30B-A3B cases: both flash kernels
    under a selection's tiles, the two kernels of ``ops/sparse_attention``
    and the whole step of the cell's own configuration file, for one v5e
    device (about a minute of the 600)."""
    return rows_with_texts("keye_")


def test_the_sparse_attention_kernels_aot_compile_at_the_cells_shape(
        keye_rows):
    """32 query heads on 4 key-value heads x 16,384 positions at a head
    width of 128 under a selection packed eight keys a byte, int8 (1,
    16384, 2048); an indexer of 16 heads of 64 and top 2,048: the forward
    kernel under the selection's tiles, the backward pair under its bytes
    key-major (1, 2048, 16384), the index / select kernel (a tile's scores
    in 16 MiB of VMEM scratch, 46 counting passes) and the alignment
    loss's one pass, each one Mosaic call under its own VMEM limit; no
    array of (16384, 16384) is in or around any of them."""
    for case in ("keye_flash_select_forward", "keye_attn_select_backward",
                 "keye_dsa_index_select", "keye_dsa_index_loss"):
        row = keye_rows[case]
        assert row.get("compiled"), json.dumps(row, indent=1)
        assert row["entry_ops"].get("custom-call") == 1, (case,
                                                          row["entry_ops"])
        with open(row["hlo"], encoding="utf-8") as f:
            text = f.read()
        assert ("s8[1,2048,16384]" if case == "keye_attn_select_backward"
                else "s8[1,16384,2048]") in text, case
        assert not re.search(r"\[(\d+,)*16384,16384[\],]", text), case
    # the index scores never leave the kernel: no (s, s) float32 array, and
    # no (s, s, heads) one, in or around it
    with open(keye_rows["keye_dsa_index_select"]["hlo"],
              encoding="utf-8") as f:
        assert not re.search(r"f32\[1,(16,)?16384,16384", f.read())


def test_keye_train_step_aot_compiles_from_the_cells_configuration(keye_rows):
    """The whole step of ``benchmark/configs/keye-vl2-30b-a3b-train-
    1chip.json`` (published widths; layers 0-3 of 48, 16 of 128 experts, 1
    x 16,384 tokens): it fits the chip beside its 5.6 GB of state, the four
    like layers are one loop, and each of the sublayer's four kernels
    stands under ``otpu_dsa`` in the pass it belongs to and in no
    recomputed one: the selection and the alignment loss in the forward
    pass alone (the checkpoint keeps the mask and the loss's gradients),
    the flash forward too, the backward pairs in the backward pass.  The
    selection is packed wherever it goes: the scan's stack of kept
    residuals holds s8[4,1,16384,2048], and an array of (16384, 16384) is
    made under ``otpu_stats`` if anywhere (the check's layout of the bits:
    the compiler fuses it away today)."""
    row = keye_rows["keye_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["while"] >= 3
    assert row["compile_s"] < 300
    assert fits_a_v5e(row), json.dumps(row, indent=1)
    assert row["argument_bytes"] < 3 * 4 * 465_391_104 + (1 << 20)
    kernels = [path.split("jit(otpu_train_step)/")[1]
               for line, path in op_paths(row) if " custom-call(" in line]
    for name, scope in (("otpu_dsa_index_select", "otpu_dsa_select"),
                        ("otpu_dsa_index_loss", "otpu_dsa_loss"),
                        ("otpu_flash_select_forward", "otpu_dsa"),
                        ("otpu_attn_select_backward", "otpu_dsa")):
        found = [p for p in kernels if f"/{name}/" in p]
        assert found and all(scope in p for p in found), (name, found)
        assert not [p for p in found if "rematted_computation" in p], name
        assert all(("transpose(" in p) == (name == "otpu_attn_select_backward")
                   for p in found), (name, found)
    assert not [p for p in kernels if "/otpu_flash_causal_forward/" in p
                or "/otpu_attn_block_backward/" in p]
    _holds_the_head_norm_rope_kernels(kernels, "otpu_dsa")
    with open(row["hlo"], encoding="utf-8") as f:
        text = f.read()
    assert "s8[4,1,16384,2048]" in text
    square = [line for line in text.splitlines()
              if re.search(r"= \w+\[(\d+,)*16384,16384[\],]", line)]
    assert all("otpu_stats" in line for line in square), [
        line[:300] for line in square if "otpu_stats" not in line][:5]


def _holds_the_head_norm_rope_kernels(kernels, operator,
                                      name="otpu_head_norm_rope"):
    """Of a step's kernel paths: ``<name>_fwd`` for q, for k and for the
    first head of each (``attn_qk``) in the forward pass, for q and k
    again in the recomputed one, which no check reads, ``_bwd`` for q and
    k in the backward pass, all under ``operator``'s ``otpu_attn_proj``;
    ``name`` is the normed pair's, or ``otpu_head_rope`` for a head that
    is turned and not normed."""
    found = {kernel: [p for p in kernels if f"/{kernel}/" in p]
             for kernel in (name + "_fwd", name + "_bwd")}
    for paths in found.values():
        assert all(f"{operator}/otpu_attn_proj" in p for p in paths), paths
    fwd = found[name + "_fwd"]
    assert sorted("rematted_computation" in p for p in fwd) == [
        False, False, False, False, True, True], fwd
    assert sorted(p.startswith("transpose(") for p in fwd) == [
        False, False, False, False, True, True], fwd
    bwd = found[name + "_bwd"]
    assert len(bwd) == 2 and all(
        p.startswith("transpose(") and "rematted_computation" not in p
        for p in bwd), bwd


@pytest.fixture(scope="module")
def ouro_rows():
    """One child for Ouro-2.6B's cases: the whole looped step of the cell's
    own configuration file and q's and k's way to the flash kernels at
    its shape, for one v5e device (about 20 s of the 600)."""
    return rows_with_texts("ouro_")


def test_ouro_train_step_aot_compiles_from_the_cells_configuration(
        ouro_rows):
    """The whole step of ``benchmark/configs/ouro-2.6b-train-1chip.json``
    (published widths; layers 0-3 of 48 walked four times, the whole
    vocabulary, 2 x 4,096 tokens): it fits the chip beside its 4.9 GB of
    state, which holds every leaf once (406,884,353 parameters and AdamW's
    two moments); the passes and the layers are loops, forward and
    backward; both flash kernels stand under ``otpu_loop_pass`` in the pass
    they belong to and in no recomputed one (the checkpoint keeps o and the
    logsumexp); q and k reach them through ``ops/head_norm_rope``'s pair
    without a gain (PR 68: one call each in the forward and the recomputed
    pass, one each back, and the first heads' in the forward pass); no
    router, no grouped matmul and no other model's kernel is in it."""
    row = ouro_rows["ouro_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["while"] >= 3
    assert row["compile_s"] < 300
    assert fits_a_v5e(row), json.dumps(row, indent=1)
    assert row["argument_bytes"] < 3 * 4 * 406_884_353 + (1 << 20)
    kernels = [path.split("jit(otpu_train_step)/")[1]
               for line, path in op_paths(row) if " custom-call(" in line]
    for name in ("otpu_flash_causal_forward", "otpu_attn_block_backward"):
        found = [p for p in kernels if f"/{name}/" in p]
        assert found and all(
            "otpu_loop_pass/otpu_layers" in p and "otpu_attention" in p
            for p in found), (name, found)
        assert not [p for p in found if "rematted_computation" in p], name
        assert all(("transpose(" in p) == (name == "otpu_attn_block_backward")
                   for p in found), (name, found)
    assert not [p for p in kernels if "otpu_gmm" in p or "otpu_moe" in p
                or "otpu_row_scatter" in p or "otpu_head_norm_rope" in p
                or "_bd_" in p]
    _holds_the_head_norm_rope_kernels(kernels, "otpu_attention",
                                      "otpu_head_rope")
    assert all("otpu_loop_pass/otpu_layers" in p for p in kernels
               if "otpu_head_rope" in p)
    paths = [path for _, path in op_paths(row)]
    for scope in ("otpu_exit_gate", "otpu_exit_loss", "otpu_head"):
        assert any(scope in p for p in paths), scope
    stacked_weight_gradients_read_what_was_made(
        row, "otpu_dense_mlp", "f32[8192,5632]", 3)


@pytest.fixture(scope="module")
def granite_rows():
    """One child for granite-4.0-h-micro's cases: both flash kernels under a
    packed row's document mask at a head of 64 with the file's scale, and
    the whole padding-free step of the cell's own configuration file, for
    one v5e device (about 40 s of the 600)."""
    return rows_with_texts("granite_")


def test_the_document_mask_kernels_aot_compile_at_the_cells_shape(
        granite_rows):
    """16 query heads on 4 key-value heads x 16,384 positions at a head
    width of 64 under a document mask packed eight keys a byte, int8 (1,
    16384, 2048), the scores' scale 1 / 64 a static argument: one Mosaic
    call each; no array of (16384, 16384) is in or around either."""
    for case in ("granite_flash_select_forward",
                 "granite_attn_select_backward"):
        row = granite_rows[case]
        assert row.get("compiled"), json.dumps(row, indent=1)
        assert row["entry_ops"].get("custom-call") == 1, (case,
                                                          row["entry_ops"])
        with open(row["hlo"], encoding="utf-8") as f:
            text = f.read()
        assert ("s8[1,2048,16384]" if case == "granite_attn_select_backward"
                else "s8[1,16384,2048]") in text, case
        assert not re.search(r"\[(\d+,)*16384,16384[\],]", text), case


def test_granite_train_step_aot_compiles_from_the_cells_configuration(
        granite_rows):
    """The whole step of ``benchmark/configs/granite-4.0-h-micro-train-
    1chip.json`` (published widths; layers 0-9 of 40, half the heads of each
    mixer, an eighth of the vocabulary, 1 x 16,384 tokens): it fits the chip
    beside its 7.8 GB of state (652,970,080 parameters and AdamW's two
    moments); the two runs of Mamba layers are loops; the attention layer's
    two kernels are the selection's, under ``otpu_attention`` in the pass
    they belong to and in no recomputed one; the document mask is packed
    wherever it goes and no (16384, 16384) array of floats is made; no
    router, no grouped matmul and no other model's kernel is in it."""
    row = granite_rows["granite_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["while"] >= 3
    assert row["compile_s"] < 300
    assert fits_a_v5e(row), json.dumps(row, indent=1)
    assert row["argument_bytes"] < 3 * 4 * 652_970_080 + (1 << 20)
    kernels = [path.split("jit(otpu_train_step)/")[1]
               for line, path in op_paths(row) if " custom-call(" in line]
    for name in ("otpu_flash_select_forward", "otpu_attn_select_backward"):
        found = [p for p in kernels if f"/{name}/" in p]
        assert found and all("otpu_attention" in p for p in found), (name,
                                                                     found)
        assert not [p for p in found if "rematted_computation" in p], name
        assert all(("transpose(" in p) == (name == "otpu_attn_select_backward")
                   for p in found), (name, found)
    assert not [p for p in kernels if "otpu_gmm" in p or "otpu_moe" in p
                or "otpu_row_scatter" in p or "otpu_head_" in p
                or "_bd_" in p or "/otpu_flash_causal_forward/" in p
                or "/otpu_attn_block_backward/" in p or "otpu_dsa" in p]
    paths = [path for _, path in op_paths(row)]
    for scope in ("otpu_mamba/otpu_ssm_scan", "otpu_mamba/otpu_ssm_conv",
                  "otpu_dense_mlp", "otpu_embed", "otpu_head"):
        assert any(scope in p for p in paths), scope
    with open(row["hlo"], encoding="utf-8") as f:
        text = f.read()
    assert "s8[1,16384,2048]" in text
    assert not re.search(r"= (f32|bf16)\[(\d+,)*16384,16384[\],]", text)
    # the scan on its kernels (PR 70) under ``otpu_ssm_scan``, in each of
    # the two runs of Mamba layers: the forward kernel in the forward and
    # the recomputed pass, the backward kernel once; no loop of XLA's
    # there, and no (chunks, heads, chunk, chunk) array anywhere
    scan = sorted({k for k in kernels if "/otpu_ssm_scan/" in k})
    assert sorted({(k.split("/")[0], "rematted_computation" in k,
                    k.split("/")[-2]) for k in scan}) == [
        ("jvp(otpu_layers)", False, "otpu_ssd_scan_fwd"),
        ("transpose(jvp(otpu_layers))", False, "otpu_ssd_scan_bwd"),
        ("transpose(jvp(otpu_layers))", True, "otpu_ssd_scan_fwd")], scan
    assert not [line for line, path in op_paths(row)
                if "/otpu_ssm_scan/" in path and " while(" in line]
    assert not re.search(r"f32\[64,32,256,256\]", text)
    # the feed-forward's backward pass written out (PR 72): a run's three
    # stacked weight gradients read what the rule made once; the step's
    # peak is at most what it was before
    stacked_weight_gradients_read_what_was_made(
        row, "otpu_dense_mlp", "f32[16384,8192]", 6)
    assert row["peak_bytes"] <= 15_345_353_216


@pytest.fixture(scope="module")
def xing_rows():
    """One child for Xing4.0-29B-A4B's cases: both flash kernels at 192 /
    128 over the 16 held heads under YaRN's scale, and the whole step of the
    cell's own configuration file on four residual streams, for one v5e
    device (about a minute of the 600)."""
    return rows_with_texts("xing_")


def test_the_latent_kernels_aot_compile_over_the_held_heads(xing_rows):
    """q and k 192 wide, v 128, 16 heads x 4,096 positions in 4 blocks of
    1,024, the scores' scale 2.00474 / sqrt(192) a static argument: one
    Mosaic call each."""
    for case in ("xing_flash_causal_forward", "xing_attn_block_backward_1k"):
        row = xing_rows[case]
        assert row.get("compiled"), json.dumps(row, indent=1)
        assert row["entry_ops"].get("custom-call") == 1, (case,
                                                          row["entry_ops"])
        with open(row["hlo"], encoding="utf-8") as f:
            assert "bf16[1,16,4096,192]" in f.read(), case


def test_xing_train_step_aot_compiles_from_the_cells_configuration(
        xing_rows):
    """The whole step of ``benchmark/configs/xing4.0-29b-a4b-train-1chip
    .json`` (published widths; 1 dense + 4 sparse layers of 40, 16 of 32
    heads, 8 of 64 experts, an eighth of the vocabulary, 1 x 4,096 tokens on
    four residual streams): it fits the chip beside its 8.4 GB of state
    (700,363,790 parameters and AdamW's two moments); the four sparse layers
    are one loop; latent attention's two kernels lie under ``otpu_mla`` and
    the experts' under ``otpu_moe``, none under the residual path; the
    path's five scopes hold ops in the forward, the recomputed and the
    backward pass, none of them a custom call or a loop of XLA's; the
    streams' copies lie under ``otpu_embed`` and their sum under
    ``otpu_head``; the maps' product is float32.  The streams are held
    stream-major (PR 74), (1, 4, 4096, 3584), each a dense slab: the step
    holds no (T, n d) view of them (no ``f32[4096,14336]``, no
    ``f32[4096,4,3584]``), the one layout of the stream the compiler
    chose, no ``pad``, ``copy`` or ``transpose`` of the stream's size, and
    its peak is at most what it was with the streams token-major."""
    row = xing_rows["xing_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["while"] >= 2
    assert row["compile_s"] < 300
    assert fits_a_v5e(row), json.dumps(row, indent=1)
    assert row["argument_bytes"] < 3 * 4 * 700_363_790 + (1 << 20)
    kernels = [path.split("jit(otpu_train_step)/")[1]
               for line, path in op_paths(row) if " custom-call(" in line]
    for name, scope in (("otpu_flash_causal_forward", "otpu_mla"),
                        ("otpu_attn_block_backward", "otpu_mla"),
                        ("otpu_gmm", "otpu_moe")):
        found = [p for p in kernels if f"/{name}" in p]
        assert found and all(scope in p for p in found), (name, found)
    assert not [p for p in kernels if "otpu_hc" in p]
    paths = [(line, path) for line, path in op_paths(row)
             if "otpu_hc" in path]
    assert not [path for line, path in paths if " while(" in line]
    for scope in ("otpu_hc_maps", "otpu_hc_sinkhorn", "otpu_hc_read",
                  "otpu_hc_write"):
        mine = [path for _, path in paths if f"otpu_hc/{scope}" in path]
        assert [p for p in mine if "rematted_computation" in p], scope
        assert [p for p in mine if "transpose(" in p
                and "rematted_computation" not in p], scope
        assert [p for p in mine if "transpose(" not in p
                and "rematted_computation" not in p], scope
    every = [path for _, path in op_paths(row)]
    for scope in ("otpu_mla", "otpu_moe", "otpu_dense_mlp", "otpu_embed",
                  "otpu_head"):
        assert any(scope in p for p in every), scope
    with open(row["hlo"], encoding="utf-8") as f:
        text = f.read()
    assert not re.search(r"bf16\[4096,14336\]|bf16\[14336,24\]", text)
    layouts = set(re.findall(r"f32\[1,4,4096,3584\]\{[0-9,]*", text))
    assert len(layouts) == 1, layouts
    assert not re.search(
        r"f32\[4096,14336\]|f32\[1,4096,4,3584\]|f32\[4096,4,3584\]", text)
    # a pass of its own over the stream: an instruction of the entry or of
    # a loop body, not one inside a fusion (a join is a fused pad there)
    fused, whole = False, []
    for line in text.split("\n"):
        if line.endswith("{") and not line.startswith(" "):
            fused = "fused_computation" in line.split("(", 1)[0]
        elif not fused and re.search(
                r"= f32\[(1,)?4,4096,3584\]\S* (pad|copy|transpose)\(", line):
            whole.append(line[:200])
    assert not whole, whole[:3]
    assert row["peak_bytes"] <= 15_425_060_864


@pytest.fixture(scope="module")
def nemotron_scan_rows():
    """One child for the scan's two kernels at Nemotron-3-Super's shape
    (the cell's other cases are ``test_pallas_aot.py``'s; about 10 s)."""
    return rows_with_texts("nemotron3_ssd_scan")


@pytest.mark.parametrize("rows,cell,arrays_gb", [
    ("granite_rows", "granite", 1.5), ("nemotron_scan_rows", "nemotron3", 0.5)])
def test_the_ssd_scan_kernels_aot_compile_at_a_cells_shape(rows, cell,
                                                           arrays_gb, request):
    """``mamba._kernel_scan`` where Mosaic compiles, x, B and C read from
    the convolution's one array: Granite's 32 heads of 64 over 16,384
    positions in chunks of 256 under a packed row's documents, Nemotron's
    16 heads over 8,192 in chunks of 128 without: the forward alone is one
    kernel call and no loop; its gradient is the forward kernel, which
    also writes the entering states (67 MB at Granite's shape), and the
    backward kernel; every product of either is float32 at the highest
    precision and nothing in them is bfloat16."""
    rows = request.getfixturevalue(rows)
    fwd, bwd = (rows[f"{cell}_ssd_scan_{way}"]
                for way in ("forward", "backward"))
    for row, calls in ((fwd, 1), (bwd, 2)):
        assert row.get("compiled"), json.dumps(row, indent=1)
        assert row["entry_ops"].get("custom-call") == calls, row["entry_ops"]
        assert "while" not in row["entry_ops"], row["entry_ops"]
    with open(bwd["hlo"], encoding="utf-8") as f:
        text = f.read()
    bodies = kernel_bodies(text, "otpu_ssd_scan_")
    assert sorted(bodies) == ["otpu_ssd_scan_bwd", "otpu_ssd_scan_fwd"]
    for name, body in bodies.items():
        products = [ln for ln in body.split("\n") if "tpu.matmul" in ln]
        assert len(products) > 10, (name, len(products))
        assert all("contract_precision<fp32>" in ln
                   and "xf32>" in ln and "bf16" not in ln
                   for ln in products), name
        assert "bf16" not in body, name
    # no (.., chunk, heads, p) view of x and no (chunks, heads, chunk,
    # chunk) array around the kernels
    assert not re.search(r"f32\[1,64,(256|128),1,(32|16),64\]", text)
    assert not re.search(r"f32\[64,(32|16),(256|128),(256|128)\]", text)
    # operands and results, the states
    assert bwd["peak_bytes"] < arrays_gb * (1 << 30)


@pytest.fixture(scope="module")
def sdar_rows():
    """One child for the SDAR-30B-A3B cases: both flash kernels under block
    diffusion's mask, the two kernels of ``ops/head_norm_rope`` and the
    whole step of the cell's own configuration file, for one v5e device
    (about a minute of the 600)."""
    return rows_with_texts("sdar_")


def test_the_block_diffusion_kernels_aot_compile_at_the_cells_shape(
        sdar_rows):
    """32 query heads on 4 key-value heads x 16,384 rows (a noisy and a
    clean copy of 8,192 tokens) at a head width of 128 in blocks of 4: the
    forward kernel in one call whose grid holds the 9 kv tiles a q tile
    meets at most, read from a table of 16 x 9 entries; the backward's 80
    tile pairs one ``lax.scan`` over triples, a pair's kind an operand;
    both under the kernels' own VMEM limits as they are, no mask an array,
    k and v repeated a query head nowhere."""
    row = sdar_rows["sdar_flash_bd_forward"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"].get("custom-call") == 1, row["entry_ops"]
    with open(row["hlo"], encoding="utf-8") as f:
        text = f.read()
    assert "otpu_flash_bd_forward" in text and "s32[144]" in text
    walk = sdar_rows["sdar_attn_bd_backward"]
    assert walk.get("compiled"), json.dumps(walk, indent=1)
    assert walk["entry_ops"].get("while") == 1, walk["entry_ops"]
    with open(walk["hlo"], encoding="utf-8") as f:
        text = f.read()
    assert re.search(r"s32\[80,3\]", text) \
        and not re.search(r"s32\[136,[23]\]", text)
    for case in ("sdar_flash_bd_forward", "sdar_attn_bd_backward",
                 "sdar_step_1chip"):
        with open(sdar_rows[case]["hlo"], encoding="utf-8") as f:
            text = f.read()
        assert not re.search(r"bf16\[1,4,8,16384,128\]", text), case
        assert not re.search(r"\[(\d+,)*16384,16384[\],]", text), case


@pytest.mark.parametrize("way,kernel,heads,hd", [
    ("forward", "otpu_head_norm_rope_fwd", "32|4", 128),
    ("backward", "otpu_head_norm_rope_bwd", "32|4", 128),
    ("first_head", "otpu_head_norm_rope_fwd", "1", 128),
    ("256_forward", "otpu_head_norm_rope_fwd", "16|2", 256),
    ("256_backward", "otpu_head_norm_rope_bwd", "16|2", 256)])
def test_the_head_norm_rope_kernels_aot_compile_at_the_cells_shape(
        sdar_rows, way, kernel, heads, hd):
    """q's and k's way from their projections' float32 products, (1, 16384,
    4096) and (1, 16384, 512), to the flash kernels' operands, bfloat16 (1,
    32 | 4, 16384, 128), and back (``ops/head_norm_rope``, PR 65; Keye's
    layers have the same shapes): one Mosaic call each for q and for k,
    the products read and their cotangents written where they lie, so no
    copy, transposition or array of (.., 16384, 32, 128) stands beside
    them, and no float32 array of the heads' shape anywhere.  The same of
    the first heads' own products with a float32 result (what a step's
    ``attn_qk`` reads), and of the kernels' other tile, 16 heads on 2 of
    256, which no cell runs."""
    row = sdar_rows[f"sdar_head_norm_rope_{way}"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    ops = row["entry_ops"]
    assert ops["custom-call"] == 2 and not {"copy", "transpose"} & set(ops)
    with open(row["hlo"], encoding="utf-8") as f:
        text = f.read()
    assert sorted(kernel_bodies(text, "otpu_head_norm_rope_")) == [kernel]
    if way == "first_head":
        assert "f32[1,1,16384,128]" in text and "bf16[" not in text
        return
    assert not re.search(r"f32\[1,(%s),16384,%d\]" % (heads, hd), text)
    assert not re.search(r"\[1,16384,(%s),%d\]" % (heads, hd), text)
    if way.endswith("backward"):
        assert "bf16[1,16384,4096]" in text and f"f32[8,{hd}]" in text


@pytest.mark.parametrize("way,kernel", [
    ("forward", "otpu_head_rope_fwd"), ("backward", "otpu_head_rope_bwd"),
    ("first_head", "otpu_head_rope_fwd")])
@pytest.mark.parametrize("rows,cell,b,s,heads", [
    ("smallthinker_rows", "smallthinker", 1, 16384, (28, 4)),
    ("ouro_rows", "ouro", 2, 4096, (16, 16))])
def test_the_head_rope_kernels_aot_compile_at_the_cells_shape(
        rows, cell, b, s, heads, way, kernel, request):
    """The same way for a head that is turned and not normed (PR 68):
    SmallThinker's window layers, 28 on 4 x 16,384, and Ouro's sixteen
    applications, 16 on 16 x (2, 4096).  One Mosaic call each for q and
    for k, no copy, transposition or array of (b, s, n, 128) beside them
    and no float32 array of the heads' shape; the backward's arguments are
    the cotangents alone (the turn is linear: no product is kept), it
    writes the products' cotangents in bfloat16 where they lie and holds
    no gain's sums; the first heads' own products leave in float32."""
    row = request.getfixturevalue(rows)[f"{cell}_head_rope_{way}"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert not {"copy", "transpose"} & set(row["entry_ops"])
    with open(row["hlo"], encoding="utf-8") as f:
        text = f.read()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert sorted(kernel_bodies(text, "otpu_head_")) == [kernel]
    if way == "first_head":
        assert f"f32[{b},1,{s},128]" in text and "bf16[" not in text
        return
    either = "|".join(map(str, sorted(set(heads))))
    assert not re.search(r"f32\[%d,(%s),%d,128\]" % (b, either, s), text)
    assert not re.search(r"\[%d,%d,(%s),128\]" % (b, s, either), text)
    if way == "backward":
        entry = text[text.index("ENTRY"):].split("\n", 1)[0]
        assert re.findall(r"(\w+)\[", entry.split("->")[0]) == ["bf16"] * 2
        assert f"bf16[{b},{s},{heads[0] * 128}]" in text
        assert "f32[8,128]" not in text


def test_sdar_train_step_aot_compiles_from_the_cells_configuration(
        sdar_rows):
    """The whole step of ``benchmark/configs/sdar-30b-a3b-train-1chip.json``
    (published widths; layers 0-3 of 48, 16 of 128 experts, 1 x 8,192
    tokens, so 16,384 rows a layer): it fits the chip beside its 5.5 GB of
    state, the four like layers are one loop, and both kernels stand under
    ``otpu_bd`` in the pass they belong to and in no recomputed one (the
    checkpoint keeps o and the logsumexp); no causal or selection kernel
    is in it."""
    row = sdar_rows["sdar_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["while"] >= 3
    assert row["compile_s"] < 300
    assert fits_a_v5e(row), json.dumps(row, indent=1)
    assert row["argument_bytes"] < 3 * 4 * 456_346_624 + (1 << 20)
    kernels = [path.split("jit(otpu_train_step)/")[1]
               for line, path in op_paths(row) if " custom-call(" in line]
    for name in ("otpu_flash_bd_forward", "otpu_attn_bd_backward"):
        found = [p for p in kernels if f"/{name}/" in p]
        assert found and all("otpu_bd" in p for p in found), (name, found)
        assert not [p for p in found if "rematted_computation" in p], name
        assert all(("transpose(" in p) == (name == "otpu_attn_bd_backward")
                   for p in found), (name, found)
    assert not [p for p in kernels if "/otpu_flash_causal_forward/" in p
                or "/otpu_attn_block_backward/" in p
                or "select" in p.rsplit("/", 2)[-2]]
    # q and k reach them through ``ops/head_norm_rope`` (PR 65), under
    # ``otpu_attn_proj``: one call each in the forward and the recomputed
    # pass, one each back
    _holds_the_head_norm_rope_kernels(kernels, "otpu_bd")


@pytest.fixture(scope="module")
def gmm_rows():
    """One child for the experts' grouped matmul at the six model cells'
    shapes, forward and both transposed products of both expert
    matrices, for one v5e device (about 25 s of the 600)."""
    return rows_with_texts("gmm_")


@pytest.mark.parametrize("cell", ["lfm2", "olmoe", "joyai", "nemotron",
                                  "qwen3next", "smallthinker", "xing"])
def test_grouped_matmul_aot_compiles_at_a_cells_shapes(cell, gmm_rows):
    """``ops/grouped_matmul``'s three kernels at the tiles the module
    chooses for a cell's rows a call, held experts and both expert
    matrices (PR 47): Mosaic takes the whole contraction and the widest
    column tile in the VMEM the module asks for, and each of the six
    products is one custom call whose ``op_name`` carries its kernel's
    name, which is how a trace finds it.  OLMoE's are every slot at once
    under autodiff; a share cell's are a trip of the held experts' loop
    at ``experts.chunk_rows`` rows (PR 57): the matrices' gradients are
    added to running float32 sums that come in and go out in one buffer
    (the module's ``input_output_alias``), and no instruction copies a
    sum."""
    row = gmm_rows["gmm_" + cell]
    assert row.get("compiled"), json.dumps(row, indent=1)
    kernels = [path for line, path in op_paths(row)
               if " custom-call(" in line]
    for name in ("otpu_gmm", "otpu_gmm_nt", "otpu_gmm_t"):
        assert sum(f"({name})" in p or f"/{name}/" in p
                   for p in kernels) == 2, kernels
    if cell == "olmoe":
        return
    with open(row["hlo"], encoding="utf-8") as f:
        text = f.read()
    head = text[:text.index("\n")]
    assert "{3}: (7, {}, may-alias)" in head \
        and "{5}: (8, {}, may-alias)" in head, head[:300]
    sums = [ln for ln in text.splitlines()
            if re.search(r" = f32\[\d+,\d+,\d+\]\S* (copy|add)\(", ln)]
    assert not sums, sums[:3]


@pytest.fixture(scope="module")
def row_scatter_rows():
    """One child for the held experts' loop's row scatter-add at the six
    share cells' shapes, the output's (weighted) and the cotangent's, for
    one v5e device (about 10 s of the 600)."""
    return rows_with_texts("row_scatter_")


@pytest.mark.parametrize("cell", ["smallthinker", "sdar", "lfm2",
                                  "qwen3next", "joyai", "nemotron"])
def test_row_scatter_aot_compiles_at_a_cells_shapes(cell, row_scatter_rows):
    """``ops/row_scatter``'s kernel at the chunk (``experts.chunk_rows``)
    and the width a share cell sends, under the rows' weights and under
    ones (PR 66): Mosaic takes single-row DMAs between the sums in HBM, a row
    as tiles of its own (``row_scatter.tile_shape``: SmallThinker's 20
    lane tiles as (4, 640)), and the two VMEM buffers, each call is one
    custom call whose ``op_name`` carries the kernel's name, the sums
    come in and go out in one buffer (``input_output_alias``), and
    nothing else stands in the program but the ones: no copy to another
    layout."""
    row = row_scatter_rows["row_scatter_" + cell]
    assert row.get("compiled"), json.dumps(row, indent=1)
    kernels = [path for line, path in op_paths(row)
               if " custom-call(" in line]
    assert sum("otpu_row_scatter_add" in p for p in kernels) == 2, kernels
    with open(row["hlo"], encoding="utf-8") as f:
        head = f.readline()
    assert "{0}: (0, {}, may-alias)" in head \
        and "{1}: (1, {}, may-alias)" in head, head[:300]
    assert row["entry_ops"] == {"constant": 1, "broadcast": 1,
                                "custom-call": 2, "tuple": 1}


@pytest.mark.parametrize("rows,case", [
    ("qwen3next_rows", "qwen3next_step_1chip"),
    ("smallthinker_rows", "smallthinker_step_1chip"),
    ("keye_rows", "keye_step_1chip"),
    ("sdar_rows", "sdar_step_1chip")])
def test_a_checkpoints_recomputed_pass_aot_holds_no_routing(
        rows, case, request):
    aot_rows.recomputed_pass_holds_no_routing(
        request.getfixturevalue(rows)[case])


@pytest.mark.parametrize("rows,case,calls", [
    ("qwen3next_rows", "qwen3next_step_1chip",
     ["jvp(otpu_layers)/otpu_attention"])])
def test_a_checkpoints_recomputed_pass_aot_holds_no_attention_forward(
        rows, case, calls, request):
    aot_rows.recomputed_pass_holds_no_attention_forward(
        request.getfixturevalue(rows)[case], calls)


@pytest.mark.slow
def test_all_kernels_aot_compile():
    pytest.importorskip("libtpu")
    res = run_aot_subprocess()
    if not res.get("rows") and res.get("error"):
        # the gate never reached compilation (offline topology/plugin
        # unavailable) — an environment outage, not a lowering failure
        pytest.skip(f"AOT topology unavailable: {res['error'][:160]}")
    bad = [r for r in res["rows"] if not r.get("compiled")]
    assert res["rows"], "AOT produced no kernel rows"
    assert not bad, (
        "kernels failed Mosaic AOT compile:\n"
        + json.dumps(bad, indent=1))
    # the full inventory: 10 ring variants + torus + both fused GEMMs
    names = {r["kernel"] for r in res["rows"]}
    for expect in ("right_permute", "all_gather", "reduce_scatter_fused",
                   "reduce_scatter_seg", "all_reduce_fused",
                   "all_reduce_seg", "all_reduce_bidi",
                   "all_reduce_seg_bidi", "all_reduce_max", "all_reduce_wire16", "reduce_scatter_wire16",
                   "all_to_all", "all_to_all_v_ragged", "all_gather_v_ragged", "bcast",
                   "all_gather_bidi", "all_reduce_torus", "matmul_allreduce",
                   "matmul_reduce_scatter",
                   # single-chip hot kernels (the MFU path)
                   "olmoe_flash_causal_forward",
                   "joyai_attn_block_backward_1k", "vpu_combine2_sum",
                   "vpu_reduce_stack_max",
                   "vpu_reduce_stack_rows_prod_f32",
                   "vpu_reduce_stack_rows_band_i32",
                   "vpu_reduce_stack_gathered_prod_f32",
                   "ddt_compact_lammps_f32",
                   # the composed flagship step
                   "train_step_1dev", "train_step_2x2",
                   "pallreduce_group_3x25MiB_2x2",
                   "pallreduce_group_32x2MiB_2x2"):
        assert expect in names, f"AOT case list lost {expect}"
