"""``ouro-train-1chip`` (PR 67): the kit's count of a looped step's
operations against a count written out by hand (projections, feed-forward,
causal pairs, four heads) at 2 x 4,096 and at a small size; the cell's
entries, found by name; and that each of its metric files loads and selects
the cell's one point."""
import os

import pytest

from harness import manifest as mf
from harness import ourokit, readerkit

CELL = "ouro-train-1chip"
CONFIG = "ouro-2.6b-train-1chip"
TRAFFIC = "packed-4k-looped-steps"
NEW = ["ouro.mfu", "ouro.flash_mfu", "ouro.attn_bwd_mfu", "loop.pass_share",
       "loop.head_share", "loop.exit_share", "loop.cast_share",
       "loop.applications_per_layer"]


@pytest.fixture(scope="module")
def real():
    return mf.load(mf.REPO_ROOT)


@pytest.fixture(scope="module")
def cfg():
    return ourokit.load_config(os.path.join(
        mf.BENCH_DIR, "configs", CONFIG + ".json"))


def by_hand(d, f, heads, hd, vocab, b, s, layers, passes) -> dict:
    """A step's model FLOP, a forward pass written out: a layer application
    is four projections, three feed-forward matrices and two products over
    the causal pairs, a query row at a time; a pass ends in the head over
    every token; three times the forward a step."""
    tokens = b * s
    pairs = b * sum(range(1, s + 1))
    proj = 2 * tokens * (3 * d * heads * hd + heads * hd * d)
    ffn = 2 * tokens * 3 * d * f
    attention = 2 * 2 * pairs * hd * heads
    head = 2 * tokens * d * vocab
    return {"attn_proj": 3 * passes * layers * proj,
            "dense_mlp": 3 * passes * layers * ffn,
            "attention": 3 * passes * layers * attention,
            "head": 3 * passes * head,
            "flash_forward": passes * layers * attention,
            "attn_backward": 2.5 * passes * layers * attention}


def test_the_steps_model_flop_by_hand(cfg):
    """16 layer applications of 0.275 + 0.567 + 0.137 TFLOP forward and
    four heads of 1.649: 22.27 forward, 66.8 model TFLOP a step."""
    want = by_hand(2048, 5632, 16, 128, 49152, 2, 4096, 4, 4)
    flops = ourokit.step_flops(cfg)
    for part, count in want.items():
        assert flops[part] == count, part
    assert flops["step"] == sum(want[k] for k in (
        "attn_proj", "dense_mlp", "attention", "head"))
    assert 66.7e12 < flops["step"] < 66.9e12
    assert round(want["attn_proj"] / 48e12, 3) == 0.275 \
        and round(want["dense_mlp"] / 48e12, 3) == 0.567 \
        and round(want["attention"] / 48e12, 3) == 0.137 \
        and round(want["head"] / 3e12, 2) == 6.60
    assert ourokit.causal_pairs(cfg) == 8_390_656
    # the four heads are 30% of the step, attention 14% of an application
    assert 0.29 < want["head"] / flops["step"] < 0.30
    assert 0.13 < want["attention"] / (flops["step"] - want["head"]) < 0.15


@pytest.mark.parametrize("passes,layers,s", [(4, 2, 64), (2, 2, 64),
                                             (1, 4, 32)])
def test_the_count_at_a_small_size(cfg, passes, layers, s):
    small = dict(cfg, hidden_size=64, intermediate_size=96, head_dim=16,
                 num_attention_heads=4, num_key_value_heads=4,
                 vocab_size=256, vocab_here=256, micro_batch=2, seq_len=s,
                 layers_here=layers, total_ut_steps=passes)
    want = by_hand(64, 96, 4, 16, 256, 2, s, layers, passes)
    flops = ourokit.step_flops(small)
    for part, count in want.items():
        assert flops[part] == count, part


def test_the_tree_is_held_once(cfg):
    assert sum(ourokit.leaf_sizes(cfg).values()) == 406_884_353
    assert ourokit.leaves(cfg)[-2:] == ("exit_gate.w", "exit_gate.b")
    assert len(ourokit.PART_CONTROLS) == 6 and len(ourokit.WRONG) == 5


def test_the_cells_entries(real):
    names = [w["name"] for w in real["workloads"]]
    cell = real["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    (config,) = [c for c in real["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["layers"] and config["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    (point,) = mf.traffic_points(TRAFFIC)
    assert (point["name"], point["kind"], point["e2e"], point["sequences"],
            point["seq_len"], point["bytes"]) == (
        "train_step.ouro.bf16.2x4096", "train_step_kit", "small_msg_us", 2,
        4096, 4 * 2 * 4098)
    # by name, not by their distance from the end
    metrics = [m["name"] for m in real["per_layer"]]
    at = metrics.index("ouro.mfu")
    assert metrics[at:at + len(NEW)] == NEW
    reports = {m["name"] for m in mf.metrics_of(real, "per_layer", CELL)}
    assert set(NEW) <= reports and {
        "device.idle_share", "device.idle_in_framework",
        "device.idle_in_launch", "step.hbm_peak_share",
        "attn.pairs_walked_share", "compile.trace_s",
        "launch.pjit_us"} <= reports
    assert not {n for n in reports if n.startswith("moe.")}
    assert {m["name"] for m in mf.metrics_of(real, "end_to_end", CELL)} \
        == {"small_msg_us", "setup_s"}


@pytest.mark.parametrize("name", NEW)
def test_a_metric_file_loads_and_selects_the_point(real, name):
    spec = mf.metric_spec(name)
    assert os.path.exists(os.path.join(mf.BENCH_DIR, "readers",
                                       spec["reader"] + ".py"))
    (point,) = mf.traffic_points(TRAFFIC)
    if "select" in spec.get("params", {}):
        assert readerkit.select([point], spec["params"]) == [point]
    if spec["reader"] == "trace_kit_flops":
        assert spec["params"]["count"] in ourokit.step_flops(
            ourokit.load_config(os.path.join(
                mf.BENCH_DIR, "configs", CONFIG + ".json")))
    if spec["reader"] == "trace_scope_share_wide":
        assert spec["params"]["vocabulary"][-3:] == [
            "otpu_loop_pass", "otpu_exit_gate", "otpu_exit_loss"]
