"""``xing-train-1chip`` (PR 73): the kit's count of a step's operations
against a count written out by hand (latent attention's projections and
products over the held heads, the path's maps, the dense SwiGLU, the router,
the shared and the held experts at the mean load, the head) and the bytes the
residual path has to move, at 1 x 4,096 and at a small size; the cell's
entries, found by name; and that each metric file whose list the cell was
appended to loads and selects the cell's one point.  The cell brought no
metric: ``per_layer`` holds its 128."""
import os

import pytest

from harness import manifest as mf
from harness import readerkit, xingkit

CELL = "xing-train-1chip"
CONFIG = "xing4.0-29b-a4b-train-1chip"
TRAFFIC = "packed-4k-hyper-steps"
APPENDED = ["nemo.mfu", "nemo.remat_share", "nemo.unnamed_share",
            "lfm2.flash_mfu", "keye.attn_bwd_mfu", "moe.gmm_share",
            "moe.gmm_kernel_share", "moe.live_row_share", "moe.route_share",
            "step.hbm_peak_share"]


@pytest.fixture(scope="module")
def real():
    return mf.load(mf.REPO_ROOT)


@pytest.fixture(scope="module")
def cfg():
    return xingkit.load_config(os.path.join(
        mf.BENCH_DIR, "configs", CONFIG + ".json"))


def by_hand(d, ff, f, heads, qr, kr, nope, rot, hv, n, experts, held, top,
            vocab, b, s, dense, sparse) -> dict:
    """A step's model FLOP, a forward pass written out and three times
    that: a layer is latent attention's five projections and its two
    products over the lower triangle, and the path's ``phi`` twice; a dense
    layer a SwiGLU; a sparse one the router, the shared expert and a token's
    ``top`` slots' share of the held experts."""
    t, layers = b * s, dense + sparse
    proj = d * qr + qr * heads * (nope + rot) + d * (kr + rot) \
        + kr * heads * (nope + hv) + heads * hv * d
    return {
        "latent_proj": 3 * layers * 2 * t * proj,
        "hc_maps": 3 * 2 * layers * 2 * t * n * d * (n * n + 2 * n),
        "attention": 3 * layers * 2 * b * heads * (nope + rot + hv)
        * s * s / 2,
        "dense_mlp": 3 * dense * 2 * t * 3 * d * ff,
        "router": 3 * sparse * 2 * t * d * experts,
        "shared": 3 * sparse * 2 * t * 3 * d * f,
        "experts": 3 * sparse * 2 * t * 3 * d * f * top * held / experts,
        "head": 3 * 2 * t * d * vocab}


def test_the_steps_model_flop_by_hand(cfg):
    """Five layers' latent attention 2.04 + 1.29 TFLOP, the dense SwiGLU
    2.44, four shared experts 1.08, the held experts 0.54 at the mean load
    of 256 slots, the head over 16,384 ids 1.44, the path's maps 0.08: 8.94
    model TFLOP a step."""
    want = by_hand(3584, 9216, 1024, 16, 768, 512, 128, 64, 128, 4, 64, 8, 4,
                   16384, 1, 4096, 1, 4)
    flops = xingkit.step_flops(cfg)
    for part, count in want.items():
        assert flops[part] == pytest.approx(count, rel=1e-12), part
    assert flops["step"] == pytest.approx(sum(want.values()), rel=1e-12)
    assert flops["flash_forward"] == pytest.approx(want["attention"] / 3)
    assert flops["attn_backward"] == pytest.approx(
        2.5 * flops["flash_forward"])
    assert [round(flops[k] / 1e12, 2) for k in (
        "latent_proj", "attention", "dense_mlp", "shared", "experts", "head",
        "hc_maps", "step")] == [2.04, 1.29, 2.44, 1.08, 0.54, 1.44, 0.08,
                                8.94]


def test_the_count_at_a_small_size(cfg):
    small = dict(cfg, hidden_size=64, intermediate_size=96,
                 num_attention_heads=4, heads_here=2, q_lora_rank=32,
                 kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, moe_intermediate_size=32, n_routed_experts=8,
                 experts_here=2, num_experts_per_tok=2, vocab_here=64,
                 micro_batch=2, seq_len=32, layers_here=4, dense_here=2)
    want = by_hand(64, 96, 32, 2, 32, 16, 16, 8, 16, 4, 8, 2, 2, 64, 2, 32,
                   2, 2)
    flops = xingkit.step_flops(small)
    for part, count in want.items():
        assert flops[part] == pytest.approx(count, rel=1e-12), part


def test_the_paths_least_bytes(cfg):
    """A sublayer reads the float32 stream (1, 4,096, 4, 3,584) twice and
    writes it once, and reads ``y``: 763 MB; ten sublayers in the forward and
    the recomputed pass and twice that in the backward: 30.5 GB a step, 37 ms
    at a v5e's 819 GB/s."""
    moved = xingkit.hc_min_bytes(cfg, 1, 4096)
    stream = 4096 * 4 * 3584 * 4
    assert moved["a_sublayer"] == 3 * stream + 4096 * 3584 * 4 == 763_363_328
    assert moved["sublayers"] == 10
    assert moved["pass"] == {"forward": 10 * moved["a_sublayer"],
                             "remat": 10 * moved["a_sublayer"],
                             "backward": 20 * moved["a_sublayer"]}
    assert moved["a_step"] == 40 * moved["a_sublayer"] == 30_534_533_120
    assert round(moved["a_step"] / 819e9 * 1e3, 1) == 37.3


def test_the_tree_and_the_controls(cfg):
    assert sum(xingkit.leaf_sizes(cfg).values()) == 700_363_790
    assert xingkit.leaves(cfg)[0] == "embed" \
        and xingkit.leaves(cfg)[-2:] == ("final_norm", "head")
    assert len(xingkit.leaves(cfg)) == 1 + 18 + 22 + 2
    assert xingkit.held(cfg) == {"heads": 16, "experts": 8,
                                 "first_expert": 0}
    assert len(xingkit.PART_CONTROLS) == 9 and len(xingkit.WRONG) == 7 \
        and set(xingkit.WHOLE_CONTROLS) <= set(xingkit.WRONG)
    assert len(xingkit.PRECISION) == 8 and len(xingkit.OUTPUTS) == 8
    for group in ("dense.", ""):
        assert {group + leaf for leaf in (
            "hc1_phi", "hc1_alpha", "hc1_b", "hc2_phi", "hc2_alpha", "hc2_b",
            "wq_b")} <= set(xingkit.checked(cfg))


def test_the_cells_entries(real):
    names = [w["name"] for w in real["workloads"]]
    cell = real["workloads"][names.index(CELL)]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    (config,) = [c for c in real["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["layers", "experts", "heads", "vocab",
                                 "mtp"] \
        and config["source"] == "https://huggingface.co/XingChen-AGI/" \
        "Xing4.0-29B-A4B/blob/main/config.json"
    (point,) = mf.traffic_points(TRAFFIC)
    assert (point["name"], point["kind"], point["e2e"], point["sequences"],
            point["seq_len"], point["bytes"]) == (
        "train_step.xing.bf16.1x4096", "train_step_kit", "small_msg_us", 1,
        4096, 4 * 4098)
    assert len(real["per_layer"]) == 128
    reports = {m["name"] for m in mf.metrics_of(real, "per_layer", CELL)}
    assert set(APPENDED) <= reports and {
        "device.idle_share", "device.idle_in_framework",
        "device.idle_in_launch", "compile.trace_s", "launch.pjit_us"} \
        <= reports
    assert not {n for n in reports
                if n.startswith(("mla.", "joyai.", "hc.", "loop.", "bd.",
                                 "dsa.", "ssm."))}
    assert "nemo.tokens_per_s" not in reports
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
        assert spec["params"]["count"] in xingkit.step_flops(
            xingkit.load_config(os.path.join(
                mf.BENCH_DIR, "configs", CONFIG + ".json")))
