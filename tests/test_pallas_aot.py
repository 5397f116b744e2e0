"""AOT compile-contract test: every coll/pallas kernel must lower
through the real Mosaic TPU compiler (offline, against a v5e-8
topology) — the CI teeth behind ``tools/pallas_aot``.

The interpreter suite (test_pallas_coll.py) proves the *schedules*;
this proves the *lowering*: semaphore allocation, VMEM/HBM placement,
collective_id barrier plumbing, (rows, 128) tiling.  A kernel that
fails here would fail on a live pod — the compile-time analog of the
reference's hardware-proven transport contract
(``/root/reference/opal/mca/btl/btl.h:878-1078``).
"""
import json
import os
import re
import subprocess
import sys
import tempfile

import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("OTPU_SKIP_AOT", "") not in ("", "0"),
    reason="AOT gate disabled by OTPU_SKIP_AOT")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_aot_subprocess(*extra, limit: int = 240, **env_extra) -> dict:
    """Run the AOT gate in a CPU-pinned subprocess: compile-only,
    bounded, and with the topology
    client's state kept out of the pytest process.  A lowering failure
    fails loudly from the result file."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    out = os.path.join(tempfile.mkdtemp(prefix="otpu_aot_"),
                       "pallas_aot.json")
    proc = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.pallas_aot",
         "--out", out, *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=limit)
    if proc.returncode not in (0, 1) or not os.path.exists(out):
        raise RuntimeError(
            f"pallas_aot gate crashed (rc={proc.returncode}):\n"
            f"{proc.stderr[-1500:]}")
    with open(out) as f:
        return json.load(f)


def test_flagship_step_aot_compiles_with_no_custom_call():
    """The composed flagship step at full width — ring attention,
    forward and backward, inside shard_map(check_vma=True) — must
    compile for one v5e device and for the 2x2 mesh (the only place the
    sp / tp collectives are compiled for the chip), and hold no Mosaic
    ``custom-call`` anywhere in its text, loops included (XLA's own
    ``AllocateBuffer`` stay): ring attention has one block update, plain
    ``jnp``, on every platform."""
    rows = _rows_with_texts("train_step", OTPU_MODEL_SCALE="64")
    assert set(rows) == {"train_step_1dev", "train_step_2x2"}
    for name, row in rows.items():
        assert row.get("compiled"), json.dumps(row, indent=1)
        with open(row["hlo"], encoding="utf-8") as f:
            assert "tpu_custom_call" not in f.read(), name


def test_reduce_stack_aot_holds_the_kernel_alone():
    """``reduce_stack`` at a benchmark cell's size (4 rows of 64 MiB) for
    one v5e device: a 2-D program-input stack (PROD f32, BAND i32) and
    the rank-3 stack a gather hands over must compile to the kernel and
    bitcasts, with no ``copy`` and no ``fusion`` beside it.  The relayout
    copy XLA used to put in front of the kernel (63% of every call on
    the chip) shows here, offline, as a ``fusion``."""
    pytest.importorskip("libtpu")
    res = _run_aot_subprocess("--only", "vpu_reduce_stack", "--topology",
                              "v5e:2x2")
    assert res.get("rows"), res.get("error")
    rows = {r["kernel"]: r for r in res["rows"]}
    assert set(rows) == {"vpu_reduce_stack_max",
                         "vpu_reduce_stack_rows_prod_f32",
                         "vpu_reduce_stack_rows_band_i32",
                         "vpu_reduce_stack_gathered_prod_f32"}
    bad = [r for r in rows.values() if not r.get("compiled")]
    assert not bad, json.dumps(bad, indent=1)
    for name, row in rows.items():
        ops = row["entry_ops"]
        assert ops.get("custom-call") == 1, (name, ops)
        assert set(ops) <= {"custom-call", "bitcast"}, (name, ops)


def test_index_list_stream_aot_holds_the_kernel_alone():
    """``IndexPlan.pack`` of the LAMMPS list at ``rank1-ddt``'s own size
    (4,194,304 blocks of 3 out of a (100663296,) float32 buffer, the
    cell's ids) for one v5e device: the streaming kernel and bitcasts.
    The parent's program was three element-gather ``fusion``s with a
    ``copy`` and a ``reshape`` (270 ms a call on the chip); a relayout in
    front of or behind the kernel would show as a ``copy`` or a
    ``fusion`` too."""
    pytest.importorskip("libtpu")
    res = _run_aot_subprocess("--only", "ddt_compact", "--topology",
                              "v5e:2x2")
    assert res.get("rows"), res.get("error")
    (row,) = res["rows"]
    assert row["kernel"] == "ddt_compact_lammps_f32"
    assert row.get("compiled"), json.dumps(row, indent=1)
    ops = row["entry_ops"]
    assert ops.get("custom-call") == 1, ops
    assert set(ops) <= {"custom-call", "bitcast"}, ops


def test_pallreduce_group_aot_keeps_an_all_reduce_a_member():
    """A partitioned allreduce's group program for four v5e chips, at
    ``rank1-partitioned``'s sizes: one ``all-reduce`` a member.  With
    the members' psums left independent XLA's combiner makes ONE
    all-reduce of them, and on the chips its sums differ from the
    per-bucket program's in a third of the positions (PR 34)."""
    pytest.importorskip("libtpu")
    res = _run_aot_subprocess("--only", "pallreduce_group", "--topology",
                              "v5e:2x2")
    assert res.get("rows"), res.get("error")
    rows = {r["kernel"]: r for r in res["rows"]}
    assert set(rows) == {"pallreduce_group_3x25MiB_2x2",
                         "pallreduce_group_32x2MiB_2x2"}
    for name, members in (("pallreduce_group_3x25MiB_2x2", 3),
                          ("pallreduce_group_32x2MiB_2x2", 32)):
        assert rows[name].get("compiled"), json.dumps(rows[name], indent=1)
        assert rows[name]["entry_ops"].get("all-reduce") == members, \
            rows[name]["entry_ops"]


@pytest.fixture(scope="module")
def olmoe_rows():
    """One child for the OLMoE cases: the cell's attention, its two
    kernels alone and the whole step, for one v5e device.  The child
    has a time limit (an offline compile has run for 40 minutes before
    now, PR 27): 240 s, of which the step takes about 15."""
    pytest.importorskip("libtpu")
    res = _run_aot_subprocess("--only", "olmoe", "--topology", "v5e:2x2")
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


def _rows_with_texts(only: str, **env_extra) -> dict:
    """{case: its row, with ``hlo`` the file of its compiled text} of one
    child that compiles the cases named ``only`` for a v5e 2x2."""
    pytest.importorskip("libtpu")
    dump = tempfile.mkdtemp(prefix="otpu_aot_hlo_")
    res = _run_aot_subprocess("--only", only, "--topology", "v5e:2x2",
                              "--dump", dump, limit=600, **env_extra)
    assert res.get("rows"), res.get("error")
    return {r["kernel"]: dict(r, hlo=os.path.join(
        dump, r["kernel"] + ".hlo.txt")) for r in res["rows"]}


def _kernel_bodies(hlo_text: str, prefix: str) -> dict:
    """{kernel name: its Mosaic module as MLIR text} of the compiled
    text's ``custom-call`` lines whose kernel is named ``prefix``..."""
    from ompi_tpu.tools import hlo_same

    out = {}
    for line in hlo_text.split("\n"):
        name = re.search(r"/(%s\w*)/pallas_call" % prefix, line)
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        if " custom-call(" in line and name and body:
            out[name.group(1)] = hlo_same.kernel_text(body.group(1))
    return out


@pytest.fixture(scope="module")
def joyai_rows():
    """One child for the JoyAI-LLM-Flash cases: attention's two kernels
    at 192 / 128 alone, the latent sublayer's operands and the whole
    step of the cell's own configuration file for one v5e device (about
    65 s of the 600)."""
    return _rows_with_texts("joyai")


def test_attention_forward_aot_compiles_at_192_and_128(joyai_rows):
    """The forward pass in one call with q and k 192 wide and v 128 (1 x
    32 heads x 8,192): one kernel, the 192 lanes Mosaic's to lay out."""
    row = joyai_rows["joyai_flash_causal_forward"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"].get("custom-call") == 1, row["entry_ops"]
    assert not {"slice", "concatenate"} & set(row["entry_ops"])


def test_attention_backward_aot_compiles_at_192_and_128(joyai_rows):
    """The backward's block pair with q and k 192 wide and v 128 (1 x 32
    heads x 8,192, blocks of 1,024), alone and as the 36 pairs of one
    ``lax.scan``: the kernel is in the loop's body, one loop."""
    row = joyai_rows["joyai_attn_block_backward_1k"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"].get("custom-call", 0) >= 1, row["entry_ops"]
    walk = joyai_rows["joyai_attn_backward_walk_8k"]
    assert walk.get("compiled"), json.dumps(walk, indent=1)
    assert walk["entry_ops"].get("while") == 1, walk["entry_ops"]


def test_joyai_train_step_aot_compiles_from_the_cells_configuration(
        joyai_rows):
    """The whole step of ``benchmark/configs/joyai-flash-train-1chip
    .json`` (published widths; 1 dense + 4 sparse layers, the module, 16
    of 256 experts): it fits the chip beside its 7.6 GiB of state, and
    the four sparse layers are one loop, so the compile stays near a
    minute."""
    row = joyai_rows["joyai_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["while"] >= 3
    assert row["compile_s"] < 400


@pytest.mark.parametrize("case", ["joyai_mla_operands", "joyai_step_1chip"])
def test_latent_attentions_operands_aot_hold_no_rolled_copy(case, joyai_rows):
    """One latent-attention sublayer, forward and gradient, at the cell's
    shapes, and the whole step: q's rotary partner is a product of its
    own (``layers.project_rope``), so the compiled text holds no
    ``jnp.roll`` (``_roll_static`` in an ``op_name``) and no 191-wide
    slice of q's (1, 8192, 32, 192) float32 array, which XLA wrote to HBM
    as 2.6 GB of shifted copies a layer and pass (PR 41)."""
    row = joyai_rows[case]
    assert row.get("compiled"), json.dumps(row, indent=1)
    with open(row["hlo"], encoding="utf-8") as f:
        text = f.read()
    assert "otpu_attn_proj" in text
    assert "_roll_static" not in text
    assert "[1,8192,32,191]" not in text


@pytest.fixture(scope="module")
def nemotron_rows():
    """One child for the Nemotron-3-Super cases: attention's two kernels
    with the share's 4 query heads on its 1 key-value head alone and the
    whole step of the cell's own configuration file, for one v5e device
    (about 60 s of the 600)."""
    return _rows_with_texts("nemotron3")


# the (b, n_kv, rep, s, hd) broadcast that ``jnp.repeat`` of k or v to the
# query heads made, until PR 48, in the two cells that share key-value
# heads, as the compiled texts of that time held it
REPEATED = {"nemotron_rows": r"= bf16\[4,8192,128\]\S* broadcast\(",
            "lfm2_rows": r"bf16\[2,8,4,8192,64\]"}


@pytest.mark.parametrize("rows,prefix", [("nemotron_rows", "nemotron3"),
                                         ("lfm2_rows", "lfm2")])
def test_shared_key_value_heads_aot_compile_and_repeat_nothing(rows, prefix,
                                                               request):
    """Query heads on fewer key-value heads (Nemotron's 4 on 1 at a head
    width of 128, LFM2's 32 on 8 at 64; 8,192 positions): the forward
    kernel and the backward's 36 pairs by one ``lax.scan`` compile with
    k, v, dk and dv at the key-value heads' count, a group's dk and dv
    block revisited by its query heads in turn; and neither they nor the
    whole step hold an array of k or v repeated a query head."""
    repeated = REPEATED[rows]
    rows = request.getfixturevalue(rows)
    cases = [prefix + "_flash_causal_forward",
             prefix + "_attn_backward_walk_8k", prefix + "_step_1chip"]
    for case in cases:
        row = rows[case]
        assert row.get("compiled"), json.dumps(row, indent=1)
        with open(row["hlo"], encoding="utf-8") as f:
            assert not re.search(repeated, f.read()), case
    assert rows[cases[0]]["entry_ops"].get("custom-call", 0) >= 1
    assert rows[cases[1]]["entry_ops"].get("while") == 1


@pytest.fixture(scope="module")
def lfm2_rows():
    """One child for the LFM2-8B-A1B cases: attention's two kernels at a
    head width of 64 alone and the whole step of the cell's own
    configuration file, for one v5e device (about 45 s of the 600)."""
    return _rows_with_texts("lfm2_")


def test_attention_forward_aot_compiles_at_a_head_width_of_64(lfm2_rows):
    """The forward pass in one call with q, k and v 64 wide (2 x 32
    query heads on 8 key-value heads x 8,192): the kernel compiles as it
    is, half a tile's lanes Mosaic's to lay out.  XLA itself keeps such
    an array with the 8,192 positions minor (64 is half a lane tile) and
    copies it into the kernel's layout, here and in the step (PERF.md 5
    has what the copies cost on the chip)."""
    row = lfm2_rows["lfm2_flash_causal_forward"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"].get("custom-call", 0) >= 1, row["entry_ops"]
    assert not {"concatenate", "fusion"} & set(row["entry_ops"])


def test_attention_backward_aot_compiles_at_a_head_width_of_64(lfm2_rows):
    """The backward's block pair with q, k and v 64 wide (2 x 32 query
    heads on 8 key-value heads x 8,192, blocks of 1,024), alone and as
    the 36 pairs of one
    ``lax.scan``: the kernel is in the loop's body, one loop."""
    row = lfm2_rows["lfm2_attn_block_backward_1k"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"].get("custom-call", 0) >= 1, row["entry_ops"]
    walk = lfm2_rows["lfm2_attn_backward_walk_8k"]
    assert walk.get("compiled"), json.dumps(walk, indent=1)
    assert walk["entry_ops"].get("while") == 1, walk["entry_ops"]


def test_lfm2_train_step_aot_compiles_from_the_cells_configuration(
        lfm2_rows):
    """The whole step of ``benchmark/configs/lfm2-8b-a1b-train-1chip
    .json`` (published widths; layers 1-6 of 24, 8 of 32 experts, 2 x
    8,192 tokens): it fits the chip beside its 7.3 GB of state, the
    three like convolution layers are one loop, and the tied matrix is
    one argument of the state's three trees (no ``head`` beside it)."""
    row = lfm2_rows["lfm2_step_1chip"]
    assert row.get("compiled"), json.dumps(row, indent=1)
    assert row["entry_ops"]["while"] >= 3
    assert row["compile_s"] < 300
    assert fits_a_v5e(row), json.dumps(row, indent=1)
    assert row["argument_bytes"] < 3 * 4 * 606_456_064 + (1 << 20)


@pytest.fixture(scope="module")
def qwen3next_rows():
    """One child for the Qwen3-Next-80B-A3B cases: attention's two kernels
    at a head width of 256 alone and the whole step of the cell's own
    configuration file, and the delta rule's and the DeltaNet
    convolution's two kernels each at the cell's shape, for one v5e
    device (about 2 min of the 600: the step's 16,384 positions)."""
    return _rows_with_texts("qwen3next_")


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
    """``model._kernel_conv`` where Mosaic compiles, 4 taps over the (1,
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
        bodies = _kernel_bodies(f.read(), "otpu_gdn_conv_")
    with open(bwd["hlo"], encoding="utf-8") as f:
        bodies.update(_kernel_bodies(f.read(), "otpu_gdn_conv_"))
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
        bodies = _kernel_bodies(f.read(), "otpu_gdn_rule_")
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
    return _rows_with_texts("smallthinker_")


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


@pytest.fixture(scope="module")
def keye_rows():
    """One child for the Keye-VL-2.0-30B-A3B cases: both flash kernels
    under a selection's tiles, the two kernels of ``ops/sparse_attention``
    and the whole step of the cell's own configuration file, for one v5e
    device (about a minute of the 600)."""
    return _rows_with_texts("keye_")


def test_the_sparse_attention_kernels_aot_compile_at_the_cells_shape(
        keye_rows):
    """32 query heads on 4 key-value heads x 16,384 positions at a head
    width of 128 under an int8 selection (1, 16384, 16384); an indexer of
    16 heads of 64 and top 2,048: the forward kernel under the mask's
    tiles, the backward pair under the mask key-major, the index / select
    kernel (a tile's scores in 16 MiB of VMEM scratch, 46 counting passes)
    and the alignment loss's one pass, each one Mosaic call under its own
    VMEM limit."""
    for case in ("keye_flash_select_forward", "keye_attn_select_backward",
                 "keye_dsa_index_select", "keye_dsa_index_loss"):
        row = keye_rows[case]
        assert row.get("compiled"), json.dumps(row, indent=1)
        assert row["entry_ops"].get("custom-call") == 1, (case,
                                                          row["entry_ops"])
        with open(row["hlo"], encoding="utf-8") as f:
            text = f.read()
        assert "s8[1,16384,16384]" in text, case
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
    the flash forward too, the backward pairs in the backward pass."""
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


@pytest.fixture(scope="module")
def gmm_rows():
    """One child for the experts' grouped matmul at the six model cells'
    shapes, forward and both transposed products of both expert
    matrices, for one v5e device (about 25 s of the 600)."""
    return _rows_with_texts("gmm_")


@pytest.mark.parametrize("cell", ["lfm2", "olmoe", "joyai", "nemotron",
                                  "qwen3next", "smallthinker"])
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


def op_paths(row):
    """(line, ``op_name`` path) of every instruction of a row's compiled
    text that has one."""
    with open(row["hlo"], encoding="utf-8") as f:
        for line in f:
            if " = " in line and 'op_name="' in line:
                yield line, line.split('op_name="', 1)[1].split('"', 1)[0]


def fits_a_v5e(row) -> bool:
    """The most a compiled step holds at once (``memory_analysis()``'s
    ``peak_memory_in_bytes``: the arguments, which the donated state's
    results alias, and the temporaries alive at the worst moment) lies
    under a v5e's 15.75 GiB.  The sum of the arguments and
    ``temp_size_in_bytes`` bounds nothing the chip needs: JoyAI's step
    with o and the logsumexp kept reads 17.86 GB by it, compiles for the
    v5e and runs on one (peak 14.46 GB)."""
    return 0 < row["peak_bytes"] < 15.75 * 2 ** 30


@pytest.mark.parametrize("rows,case", [
    ("joyai_rows", "joyai_step_1chip"),
    ("nemotron_rows", "nemotron3_step_1chip"),
    ("lfm2_rows", "lfm2_step_1chip"),
    ("qwen3next_rows", "qwen3next_step_1chip"),
    ("smallthinker_rows", "smallthinker_step_1chip"),
    ("keye_rows", "keye_step_1chip")])
def test_a_checkpoints_recomputed_pass_aot_holds_no_routing(rows, case,
                                                            request):
    """A walked layer's checkpoint keeps what the expert block names
    (``experts.CHECKPOINT_KEEPS``, PR 43), so in the step compiled for a v5e
    no instruction under ``rematted_computation`` is a ``sort`` (the
    dispatch's argsort, and the top-k, which the TPU's compiler writes as
    a whole sort of (8192, E)), any other part of the top-k, the gather
    of the chosen scores (T k single entries: 1.8 ms a layer on the
    chip), the router's float32 product or the held experts' loop
    (``test_train_scopes.ROUTING``); they run in the forward pass, and a
    layer's other work is still recomputed.  The step fits the chip
    (``fits_a_v5e``)."""
    from test_train_scopes import ROUTING

    row = request.getfixturevalue(rows)[case]
    assert row.get("compiled"), json.dumps(row, indent=1)
    kinds = {"forward": set(), "remat": set()}
    recomputed = 0
    for line, path in op_paths(row):
        remat = "rematted_computation" in path
        recomputed += remat and "otpu_attn_proj" in path
        kinds["remat" if remat else "forward"].update(
            k for k, is_it in ROUTING.items() if is_it(line, path))
    assert recomputed > 20
    assert kinds == {"forward": set(ROUTING), "remat": set()}
    assert fits_a_v5e(row), json.dumps(row, indent=1)


@pytest.mark.parametrize("rows,case,calls", [
    ("joyai_rows", "joyai_step_1chip", ["jvp(otpu_layers)/otpu_mla",
                                        "jvp(otpu_layers)/while/body",
                                        "jvp(otpu_mtp)/otpu_layers/otpu_mla"]),
    ("nemotron_rows", "nemotron3_step_1chip",
     ["jvp(otpu_layers)/otpu_attention"]),
    ("lfm2_rows", "lfm2_step_1chip",
     ["jvp(otpu_layers)/otpu_attention"] * 2),
    ("qwen3next_rows", "qwen3next_step_1chip",
     ["jvp(otpu_layers)/otpu_attention"])])
def test_a_checkpoints_recomputed_pass_aot_holds_no_attention_forward(
        rows, case, calls, request):
    """A walked layer's checkpoint keeps causal attention's o and
    logsumexp (``model.CHECKPOINT_KEEPS``, PR 44), so in the step compiled
    for a v5e the forward kernel (``otpu_flash_causal_forward``) stands
    once a layer, in the forward pass, and nowhere under
    ``rematted_computation``: JoyAI's in the dense layer, in the body
    that the four sparse layers scan and in the module (six calls a
    step, twelve before), Nemotron's in its one attention layer; the
    backward kernel is where it was, and the step fits the chip
    (``fits_a_v5e``)."""
    row = request.getfixturevalue(rows)[case]
    assert row.get("compiled"), json.dumps(row, indent=1)
    kernels = [path for line, path in op_paths(row)
               if " custom-call(" in line]
    forward = sorted(p for p in kernels if "/otpu_flash_causal_forward/" in p)
    assert not [p for p in forward if "rematted_computation" in p]
    assert len(forward) == len(calls), forward
    for path, where in zip(forward, calls):
        assert path.startswith("jit(otpu_train_step)/" + where), path
    assert sum("/otpu_attn_block_backward/" in p
               for p in kernels) >= len(calls)
    assert fits_a_v5e(row), json.dumps(row, indent=1)


@pytest.mark.slow
def test_all_kernels_aot_compile():
    pytest.importorskip("libtpu")
    res = _run_aot_subprocess()
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
