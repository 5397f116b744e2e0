"""``granite-train-1chip`` (PR 69): the kit's count of a padding-free step's
operations against a count written out by hand (the mixers' projections,
the scans by the recurrence, the SwiGLUs, attention over the pairs the
documents' masks leave, the tied head) at 1 x 16,384 and at a small size;
the cell's entries, found by name; and that each metric file whose list the
cell was appended to loads and selects the cell's one point.  The cell
brought no metric: ``per_layer`` holds its 128."""
import os

import numpy as np
import pytest

from harness import granitekit, readerkit
from harness import manifest as mf

CELL = "granite-train-1chip"
CONFIG = "granite-4.0-h-micro-train-1chip"
TRAFFIC = "packed-16k-docs-steps"
APPENDED = ["ssm.mixer_share", "ssm.scan_share", "nemo.mfu",
            "nemo.remat_share", "nemo.unnamed_share", "lfm2.flash_mfu",
            "attn.shared_kv_share", "step.hbm_peak_share"]


@pytest.fixture(scope="module")
def real():
    return mf.load(mf.REPO_ROOT)


@pytest.fixture(scope="module")
def cfg():
    return granitekit.load_config(os.path.join(
        mf.BENCH_DIR, "configs", CONFIG + ".json"))


def by_hand(d, f, q_heads, kv_heads, hd, m_heads, p, n, vocab, tokens,
            mamba, attention, pairs) -> dict:
    """A step's model FLOP, a forward pass written out and three times
    that: a Mamba layer is its two projections and, a position and head, the
    state's decay and add and its product with C; an attention layer four
    projections and two products over the visible pairs; every layer a
    SwiGLU; the tied head once."""
    inner = m_heads * p
    in_proj = d * (2 * inner + 2 * n + m_heads)
    return {
        "mamba_proj": 3 * mamba * 2 * tokens * (in_proj + inner * d),
        "ssm_scan": 3 * mamba * tokens * m_heads * 6 * p * n,
        "attn_proj": 3 * attention * 2 * tokens * (
            2 * d * q_heads * hd + 2 * d * kv_heads * hd),
        "attention": 3 * attention * 2 * 2 * pairs * hd * q_heads,
        "dense_mlp": 3 * (mamba + attention) * 2 * tokens * 3 * d * f,
        "head": 3 * 2 * tokens * d * vocab}


def test_the_steps_model_flop_by_hand(cfg):
    """Nine Mamba layers of 1.295 (projections) + 0.077 (scan) TFLOP, ten
    SwiGLUs of 4.95, one attention layer, the head over 12,544 ids: about 65
    model TFLOP a step."""
    pairs = granitekit.mean_visible_pairs(16384)
    want = by_hand(2048, 8192, 16, 4, 64, 32, 64, 128, 12544, 16384, 9, 1,
                   pairs)
    flops = granitekit.step_flops(cfg)
    for part, count in want.items():
        assert flops[part] == pytest.approx(count, rel=1e-12), part
    assert flops["step"] == pytest.approx(sum(want.values()), rel=1e-12)
    assert flops["flash_forward"] == pytest.approx(want["attention"] / 3)
    assert 64e12 < flops["step"] < 67e12
    assert round(want["mamba_proj"] / 9e12, 3) == 1.295 \
        and round(want["ssm_scan"] / 9e12, 3) == 0.077 \
        and round(want["dense_mlp"] / 10e12, 2) == 4.95 \
        and round(want["head"] / 1e12, 2) == 2.53
    # the boundaries leave attention a fifth to a third of the triangle
    assert 0.15 < pairs / granitekit.causal_pairs(cfg) < 0.35


@pytest.mark.parametrize("layers,first,s", [(3, 4, 64), (10, 0, 32),
                                            (4, 3, 128)])
def test_the_count_at_a_small_size(cfg, layers, first, s):
    small = dict(cfg, hidden_size=64, shared_intermediate_size=96,
                 num_attention_heads=4, num_key_value_heads=2, heads_here=2,
                 mamba_n_heads=4, mamba_d_head=32, mamba_heads_here=2,
                 mamba_d_state=16, vocab_size=256, vocab_here=64,
                 micro_batch=2, seq_len=s, layers_here=layers,
                 first_layer_here=first)
    held = cfg["layer_types"][first:first + layers]
    want = by_hand(64, 96, 2, 1, 16, 2, 32, 16, 64, 2 * s,
                   held.count("mamba"), held.count("attention"),
                   2 * granitekit.mean_visible_pairs(s))
    flops = granitekit.step_flops(small)
    for part, count in want.items():
        assert flops[part] == pytest.approx(count, rel=1e-12), part


def test_the_tree_and_the_controls(cfg):
    assert sum(granitekit.leaf_sizes(cfg).values()) == 652_970_080
    assert granitekit.leaves(cfg)[0] == "embed" \
        and granitekit.leaves(cfg)[-1] == "final_norm" \
        and "head" not in granitekit.leaves(cfg)
    assert len(granitekit.PART_CONTROLS) == 8 and len(granitekit.WRONG) == 5
    assert granitekit.length_law(16384) == (1024.0, 16, 16384)
    lengths = np.asarray(granitekit.document_lengths(
        np.random.default_rng(0).integers(
            -2 ** 31, 2 ** 31, (4, 16386)).astype(np.int32)))
    assert lengths.shape == (4, 64) and lengths.min() >= 16


def test_the_cells_entries(real):
    names = [w["name"] for w in real["workloads"]]
    cell = real["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    (config,) = [c for c in real["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["layers", "heads", "vocab"] \
        and config["source"] == "https://huggingface.co/ibm-granite/" \
        "granite-4.0-h-micro/blob/main/config.json"
    (point,) = mf.traffic_points(TRAFFIC)
    assert (point["name"], point["kind"], point["e2e"], point["sequences"],
            point["seq_len"], point["bytes"]) == (
        "train_step.granite.bf16.1x16384", "train_step_kit", "small_msg_us",
        1, 16384, 4 * 16386)
    assert len(real["per_layer"]) == 128
    reports = {m["name"] for m in mf.metrics_of(real, "per_layer", CELL)}
    assert set(APPENDED) <= reports and {
        "device.idle_share", "device.idle_in_framework",
        "device.idle_in_launch", "compile.trace_s", "launch.pjit_us"} \
        <= reports
    assert not {n for n in reports
                if n.startswith(("moe.", "loop.", "bd.", "dsa."))}
    assert {m["name"] for m in mf.metrics_of(real, "end_to_end", CELL)} \
        == {"small_msg_us", "setup_s"}


@pytest.mark.parametrize("name", APPENDED)
def test_a_metric_file_loads_and_selects_the_point(real, name):
    spec = mf.metric_spec(name)
    assert os.path.exists(os.path.join(mf.BENCH_DIR, "readers",
                                       spec["reader"] + ".py"))
    (point,) = mf.traffic_points(TRAFFIC)
    if "select" in spec.get("params", {}):
        assert readerkit.select([point], spec["params"]) == [point]
    if spec["reader"] == "trace_kit_flops":
        assert spec["params"]["count"] in granitekit.step_flops(
            granitekit.load_config(os.path.join(
                mf.BENCH_DIR, "configs", CONFIG + ".json")))
    if spec["reader"] == "trace_scope_share_wide":
        assert {"otpu_mamba", "otpu_ssm_scan"} <= set(
            spec["params"]["vocabulary"])
