"""``sdar-train-1chip`` (PR 64): the kit's count of a block-diffusion
step's operations against a count written out by hand, the three regions'
pairs at 8,192 tokens and at a small length; the cell's entries; and that
each of its metric files loads and selects the cell's one point."""
import os

import pytest

from harness import manifest as mf
from harness import readerkit, sdarkit

CELL = "sdar-train-1chip"
NEW = ["sdar.mfu", "sdar.tokens_per_s", "sdar.local_load",
       "sdar.remat_share", "sdar.unnamed_share", "sdar.flash_mfu",
       "sdar.attn_bwd_mfu", "bd.operator_share", "bd.noise_share",
       "bd.loss_share", "bd.visible_share", "bd.masked_share"]


@pytest.fixture(scope="module")
def real():
    return mf.load(mf.REPO_ROOT)


@pytest.fixture(scope="module")
def cfg():
    return sdarkit.load_config(os.path.join(
        mf.BENCH_DIR, "configs", "sdar-30b-a3b-train-1chip.json"))


def by_hand(length: int, bl: int) -> dict:
    """The visible (query, key) pairs of one sequence, a query row at a
    time: a clean row of block c sees the (c + 1) bl clean keys up to its
    block; a noisy row of block c the bl noisy keys of its block and the
    c bl clean keys before it."""
    clean = sum((pos // bl + 1) * bl for pos in range(length))
    noisy_clean = sum((pos // bl) * bl for pos in range(length))
    return {"clean_clean": clean, "noisy_clean": noisy_clean,
            "noisy_noisy": length * bl,
            "causal": sum(range(1, 2 * length + 1))}


@pytest.mark.parametrize("length,bl", [(8192, 4), (48, 4), (64, 1), (32, 32),
                                       (96, 32)])
def test_the_visible_pairs_are_the_hand_count(cfg, length, bl):
    assert sdarkit.visible_pairs(dict(cfg, seq_len=length, block_length=bl)) \
        == by_hand(length, bl)


def test_the_pairs_at_the_cells_length(cfg):
    see = sdarkit.visible_pairs(cfg)
    assert see == {"clean_clean": 16 * 2048 * 2049 // 2,
                   "noisy_clean": 16 * 2048 * 2047 // 2,
                   "noisy_noisy": 8192 * 4, "causal": 134_225_920}
    assert sum(see.values()) - see["causal"] == 67_141_632


def test_the_steps_model_flop_by_hand(cfg):
    """A layer's forward: 0.618 (projections) + 0.009 (router) + 0.155
    (held experts at the mean load) + 1.100 TFLOP (attention over the
    visible pairs) over the 16,384 rows; the head over the 4,100 rows
    masked at the mean; three times that a step."""
    rows, pairs = 16384, 67_141_632
    proj = 2 * (2048 * (4096 + 512 + 512) + 4096 * 2048) * rows
    router = 2 * 2048 * 128 * rows
    experts = 2 * 3 * 2048 * 768 * 8 * 16 / 128 * rows
    attention = 4 * 128 * 32 * pairs
    head = 2 * 2048 * 18992 * 8192 * 1.001 / 2
    flops = sdarkit.step_flops(cfg)
    assert flops["attn_proj"] == 3 * 4 * proj
    assert flops["router"] == 3 * 4 * router
    assert flops["experts"] == 3 * 4 * experts
    assert flops["attention"] == 3 * 4 * attention
    assert flops["head"] == pytest.approx(3 * head)
    assert flops["step"] == pytest.approx(
        3 * (4 * (proj + router + experts + attention) + head))
    assert 23.4e12 < flops["step"] < 23.6e12
    assert round(attention / 1e12, 3) == 1.100
    assert flops["flash_forward"] == 4 * attention
    assert flops["attn_backward"] == 10 * attention
    # the mechanism is 58% of a layer
    assert 0.58 < attention / (proj + router + experts + attention) < 0.59


def test_the_cells_entries(real):
    cell = real["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "sdar-30b-a3b-train-1chip",
            "packed-8k-block-diffusion-steps", 1)
    config = real["configs"][-1]
    assert config["name"] == cell["config"] and config["reduced"] == [
        "layers", "experts", "vocab", "ranks"]
    (point,) = mf.traffic_points(cell["traffic"])
    assert (point["name"], point["kind"], point["e2e"], point["sequences"],
            point["seq_len"], point["bytes"]) == (
        "train_step.sdar.bf16.1x8192", "train_step_kit", "small_msg_us", 1,
        8192, 4 * 8194)
    assert [m["name"] for m in real["per_layer"]][-12:] == NEW
    reports = {m["name"] for m in mf.metrics_of(real, "per_layer", CELL)}
    assert set(NEW) <= reports and {
        "device.idle_share", "moe.gmm_share", "moe.route_share",
        "attn.shared_kv_share", "attn.pairs_walked_share",
        "step.hbm_peak_share"} <= reports
    assert {m["name"] for m in mf.metrics_of(real, "end_to_end", CELL)} \
        == {"small_msg_us", "setup_s"}


@pytest.mark.parametrize("name", NEW)
def test_a_metric_file_loads_and_selects_the_point(real, name):
    spec = mf.metric_spec(name)
    assert os.path.exists(os.path.join(mf.BENCH_DIR, "readers",
                                       spec["reader"] + ".py"))
    (point,) = mf.traffic_points("packed-8k-block-diffusion-steps")
    if "select" in spec.get("params", {}):
        assert readerkit.select([point], spec["params"]) == [point]
    if spec["reader"] == "trace_kit_flops":
        assert spec["params"]["count"] in sdarkit.step_flops(
            sdarkit.load_config(os.path.join(
                mf.BENCH_DIR, "configs",
                "sdar-30b-a3b-train-1chip.json")))
