"""AOT compile-contract test: every coll/pallas kernel must lower
through the real Mosaic TPU compiler (offline, against a v5e-8
topology) — the CI teeth behind ``tools/pallas_aot``.

The interpreter suite (test_pallas_coll.py) proves the *schedules*;
this proves the *lowering*: semaphore allocation, VMEM/HBM placement,
collective_id barrier plumbing, (rows, 128) tiling.  A kernel that
fails here would fail on a live pod — the compile-time analog of the
reference's hardware-proven transport contract
(``/root/reference/opal/mca/btl/btl.h:878-1078``).

This file holds the collectives' cases and JoyAI's, Nemotron's and LFM2's;
``test_pallas_aot_cells.py`` the other cells' and the grouped matmul's
(``aot_rows.py``: what both share, and why there are two).
"""
import json
import re

import pytest

import aot_rows
from aot_rows import fits_a_v5e, rows_with_texts, run_aot_subprocess

pytestmark = aot_rows.SKIP_AOT


def test_flagship_step_aot_compiles_with_no_custom_call():
    """The composed flagship step at full width — ring attention,
    forward and backward, inside shard_map(check_vma=True) — must
    compile for one v5e device and for the 2x2 mesh (the only place the
    sp / tp collectives are compiled for the chip), and hold no Mosaic
    ``custom-call`` anywhere in its text, loops included (XLA's own
    ``AllocateBuffer`` stay): ring attention has one block update, plain
    ``jnp``, on every platform."""
    rows = rows_with_texts("train_step", OTPU_MODEL_SCALE="64")
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
    res = run_aot_subprocess("--only", "vpu_reduce_stack", "--topology",
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
    res = run_aot_subprocess("--only", "ddt_compact", "--topology",
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
    res = run_aot_subprocess("--only", "pallreduce_group", "--topology",
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
def joyai_rows():
    """One child for the JoyAI-LLM-Flash cases: attention's two kernels
    at 192 / 128 alone, the latent sublayer's operands and the whole
    step of the cell's own configuration file for one v5e device (about
    65 s of the 600)."""
    return rows_with_texts("joyai")


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
    return rows_with_texts("nemotron3")


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
    return rows_with_texts("lfm2_")


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


@pytest.mark.parametrize("rows,case", [
    ("joyai_rows", "joyai_step_1chip"),
    ("nemotron_rows", "nemotron3_step_1chip"),
    ("lfm2_rows", "lfm2_step_1chip")])
def test_a_checkpoints_recomputed_pass_aot_holds_no_routing(
        rows, case, request):
    aot_rows.recomputed_pass_holds_no_routing(
        request.getfixturevalue(rows)[case])


def test_a_shared_experts_stacked_weight_gradients_aot_read_what_was_made(
        nemotron_rows):
    """Nemotron's relu2 shared expert over its four ``E`` layers:
    ``layers._ffn_backward``'s products, and the step's peak at most what
    it was before the rule."""
    row = nemotron_rows["nemotron3_step_1chip"]
    aot_rows.stacked_weight_gradients_read_what_was_made(
        row, "otpu_shared_expert", "f32[8192,5376]", 2)
    assert row["peak_bytes"] <= 14_088_552_448


@pytest.mark.parametrize("rows,case,calls", [
    ("joyai_rows", "joyai_step_1chip", ["jvp(otpu_layers)/otpu_mla",
                                        "jvp(otpu_layers)/while/body",
                                        "jvp(otpu_mtp)/otpu_layers/otpu_mla"]),
    ("nemotron_rows", "nemotron3_step_1chip",
     ["jvp(otpu_layers)/otpu_attention"]),
    ("lfm2_rows", "lfm2_step_1chip",
     ["jvp(otpu_layers)/otpu_attention"] * 2)])
def test_a_checkpoints_recomputed_pass_aot_holds_no_attention_forward(
        rows, case, calls, request):
    aot_rows.recomputed_pass_holds_no_attention_forward(
        request.getfixturevalue(rows)[case], calls)
