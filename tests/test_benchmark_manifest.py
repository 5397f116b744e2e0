"""The benchmark's manifest inside the tier-1 run: ``BENCHMARK.json`` and
every file it names pass the rules that code can check
(``benchmark/harness/manifest.validate`` and ``validate_harness``: what
``benchmark/check_manifest.py`` runs by hand), and every cell is rehearsed
at tiny sizes on CPU devices through ``run_cell``, in a copied tree and a
process of its own (``run_cell`` boots and finalizes the program, freezes
the collector and sets JAX's cache options, none of which a test worker
should keep).  Nothing here is a measurement."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import built

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL = "rank1-ddt"
KIB = 1 << 10
MIB = 1 << 20

# rank1-ddt: a point's own parameters, cut to a rehearsal; bytes follow
TINY = {"grid": {514: 18, 130: 10}, "n": {4096: 16, 8192: 64},
        "atoms": {33554432: 4096}, "sent": {4194304: 512}}


def _cut_ddt(p):
    for key, small in TINY.items():
        if key in p:
            p[key] = small[p[key]]
    p["bytes"] = 4 * ((p["grid"] - 2) ** 2 if "grid" in p else
                      2 * p["n"] ** 2 if "n" in p else 3 * p["sent"])


def _cut_buckets(p):
    """A gradient set cut to 64 bytes a bucket (the whole set where the
    point is one call): every point keeps its count of buckets, so a
    step still launches what it launches at full size."""
    p["bytes"] = p.get("buckets", 1) * 64


# olmoe-train-1chip: the kind reads its widths from the configuration
# file its point names, so the rehearsal writes tiny widths there (the
# first satellite's: 2 layers, as the CPU tests of the model) and cuts
# the point's batch to match
TINY_MODEL = dict(hidden_size=64, intermediate_size=32,
                  num_attention_heads=4, num_key_value_heads=4,
                  num_experts=8, num_experts_per_tok=2, vocab_size=256,
                  layers_here=2)
# float32 compute: with 64 tokens one routing choice that bfloat16 flips
# moves an expert's load by a sixteenth of the mean, far over a tolerance
# set at 8,192 tokens; the walk is of the code, not of the precision
TINY_TRAIN = dict(seq_len=32, micro_batch=2, attn_block=16,
                  loss_block_rows=16, compute_dtype="float32")


def _tiny_model(config):
    config.update(TINY_MODEL)
    config["train"].update(TINY_TRAIN)


def _cut_batch(p):
    p.update(sequences=TINY_TRAIN["micro_batch"],
             seq_len=TINY_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 1)


# joyai-train-1chip: the same, at the widths of tests/test_joyai_train.py
# (1 dense + 2 sparse layers and the module, 4 of 16 experts, 64 of 512 ids)
TINY_SHARE = dict(hidden_size=64, intermediate_size=96,
                  num_attention_heads=4, num_key_value_heads=4,
                  q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16,
                  moe_intermediate_size=32, n_routed_experts=16,
                  num_experts_per_tok=4, vocab_size=512, vocab_here=64,
                  experts_here=4, layers_here=3)
TINY_SHARE_TRAIN = dict(seq_len=32, micro_batch=1, attn_block=16,
                        loss_block_rows=16, compute_dtype="float32")


def _tiny_share(config):
    config.update(TINY_SHARE)
    config["train"].update(TINY_SHARE_TRAIN)


def _cut_batch_share(p):
    p.update(sequences=TINY_SHARE_TRAIN["micro_batch"],
             seq_len=TINY_SHARE_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 2)


# nemotron3-train-1chip: the same, at the widths of
# tests/test_nemotron_train.py (the seven layers MEMEM*E of the published
# pattern: a scanned run of two ME and one layer of each kind, as the
# period's four and three; 4 of 16 Mamba heads, 4 of 16 query heads, 8 of
# 32 experts, 64 of 512 ids)
TINY_HYBRID = dict(hidden_size=64, intermediate_size=24, head_dim=4,
                   num_attention_heads=16, num_key_value_heads=2,
                   mamba_num_heads=16, mamba_head_dim=8, n_groups=8,
                   ssm_state_size=8, chunk_size=8, moe_latent_size=16,
                   moe_intermediate_size=24,
                   moe_shared_expert_intermediate_size=48,
                   n_routed_experts=32, num_experts_per_tok=3,
                   vocab_size=512, vocab_here=64, experts_here=8,
                   heads_here=4, mamba_heads_here=4, first_layer_here=31,
                   layers_here=7)


def _tiny_hybrid(config):
    config.update(TINY_HYBRID)
    config["train"].update(TINY_SHARE_TRAIN)


# lfm2-train-1chip: the same, at the widths of tests/test_lfm2_train.py
# (the published layer_types' layers 1 to 6: a dense convolution layer, an
# attention layer, a scanned run of three convolution layers, an attention
# layer; 4 query heads of 16 reading 2 key-value heads, 2 of 8 experts, 64
# of 256 ids)
TINY_TYPED = dict(hidden_size=64, intermediate_size=96,
                  num_attention_heads=4, num_key_value_heads=2,
                  moe_intermediate_size=24, num_experts=8,
                  num_experts_per_tok=2, vocab_size=256, vocab_here=64,
                  experts_here=2)
TINY_TYPED_TRAIN = dict(seq_len=32, micro_batch=2, attn_block=16,
                        loss_block_rows=16, compute_dtype="float32")


def _tiny_typed(config):
    config.update(TINY_TYPED)
    config["train"].update(TINY_TYPED_TRAIN)


def _cut_batch_typed(p):
    p.update(sequences=TINY_TYPED_TRAIN["micro_batch"],
             seq_len=TINY_TYPED_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 2)


# qwen3next-train-1chip: the same, at the widths of
# tests/test_qwen3next_train.py (one period: a scanned run of three Gated
# DeltaNet layers and an output-gated attention layer; 2 key and 4 value
# heads of 16, 4 query heads of 32 on 1 key-value head with 8 entries
# turned, 4 of 16 experts beside the gated shared one, 64 of 256 ids)
TINY_NEXT = dict(hidden_size=64, intermediate_size=96, head_dim=32,
                 num_attention_heads=4, num_key_value_heads=1,
                 linear_num_key_heads=2, linear_num_value_heads=4,
                 linear_key_head_dim=16, linear_value_head_dim=16,
                 moe_intermediate_size=24, shared_expert_intermediate_size=24,
                 num_experts=16, num_experts_per_tok=4, vocab_size=256,
                 vocab_here=64, experts_here=4)
TINY_NEXT_TRAIN = dict(seq_len=40, micro_batch=1, attn_block=8,
                       loss_block_rows=8, chunk_size=8,
                       compute_dtype="float32")


def _tiny_next(config):
    config.update(TINY_NEXT)
    config["train"].update(TINY_NEXT_TRAIN)


def _cut_batch_next(p):
    p.update(sequences=TINY_NEXT_TRAIN["micro_batch"],
             seq_len=TINY_NEXT_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 2)


# smallthinker-train-1chip: the same, at the widths of
# tests/test_smallthinker_train.py (one period: a full layer without RoPE
# and a scanned run of three window layers with it; 8 query heads of 16 on
# 2 key-value heads, a window of 16 in blocks of 8 over 40 positions, 4 of
# 16 relu-gated experts, 64 of 256 ids)
TINY_THINKER = dict(hidden_size=64, head_dim=16, num_attention_heads=8,
                    num_key_value_heads=2, moe_ffn_hidden_size=24,
                    moe_num_primary_experts=16,
                    moe_num_active_primary_experts=3, sliding_window_size=16,
                    vocab_size=256, vocab_here=64, experts_here=4)
TINY_THINKER_TRAIN = dict(seq_len=40, micro_batch=1, attn_block=8,
                          loss_block_rows=8, compute_dtype="float32")


def _tiny_thinker(config):
    config.update(TINY_THINKER)
    config["train"].update(TINY_THINKER_TRAIN)


def _cut_batch_thinker(p):
    p.update(sequences=TINY_THINKER_TRAIN["micro_batch"],
             seq_len=TINY_THINKER_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 2)


# keye-train-1chip: the same, at the widths of tests/test_keye_train.py (a
# scanned run of four sparse-attention layers: 8 query heads of 16 on 2
# key-value heads, an indexer of 4 heads of 8, top 24 of 64 positions in
# blocks of 16, 4 of 16 experts, 64 of 256 ids)
KEYE = "keye-train-1chip"
TINY_KEYE = dict(hidden_size=64, head_dim=16, num_attention_heads=8,
                 num_key_value_heads=2, moe_intermediate_size=24,
                 num_experts=16, num_experts_per_tok=3, vocab_size=256,
                 vocab_here=64, experts_here=4,
                 rope_scaling={"rope_type": "default", "type": "default",
                               "mrope_section": [2, 3, 3]},
                 sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
                            "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                            "q_chunk_size": 16, "topk": 24})
TINY_KEYE_TRAIN = dict(seq_len=64, micro_batch=1, attn_block=16,
                       loss_block_rows=16, compute_dtype="float32")


def _tiny_keye(config):
    config.update(TINY_KEYE)
    config["train"].update(TINY_KEYE_TRAIN)


def _cut_batch_keye(p):
    p.update(sequences=TINY_KEYE_TRAIN["micro_batch"],
             seq_len=TINY_KEYE_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 2)


# sdar-train-1chip: the same, at the widths of tests/test_sdar_train.py (a
# scanned run of four block-diffusion layers: 8 query heads of 16 on 2
# key-value heads over a noisy and a clean copy of 64 tokens in blocks of 4,
# tiles of 16; 4 of 16 experts, 64 of 256 ids, the mask token the last)
SDAR = "sdar-train-1chip"
TINY_SDAR = dict(hidden_size=64, head_dim=16, num_attention_heads=8,
                 num_key_value_heads=2, moe_intermediate_size=24,
                 num_experts=16, num_experts_per_tok=3, vocab_size=256,
                 vocab_here=64, experts_here=4, mask_token_here=63)
TINY_SDAR_TRAIN = dict(seq_len=64, micro_batch=1, attn_block=16,
                       loss_block_rows=16, compute_dtype="float32")


def _tiny_sdar(config):
    config.update(TINY_SDAR)
    config["train"].update(TINY_SDAR_TRAIN)


def _cut_batch_sdar(p):
    p.update(sequences=TINY_SDAR_TRAIN["micro_batch"],
             seq_len=TINY_SDAR_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 2)


# ouro-train-1chip: the same, at the widths of tests/test_ouro_train.py (a
# scanned run of two sandwich layers walked four times: 4 heads of 16, a
# feed-forward of 96, all 256 ids, 2 rows of 64 tokens, tiles of 16)
OURO = "ouro-train-1chip"
TINY_OURO = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
                 num_key_value_heads=4, intermediate_size=96, vocab_size=256,
                 layers_here=2)
TINY_OURO_TRAIN = dict(seq_len=64, micro_batch=2, attn_block=16,
                       loss_block_rows=32, compute_dtype="float32")


def _tiny_ouro(config):
    config.update(TINY_OURO)
    config["train"].update(TINY_OURO_TRAIN)


def _cut_batch_ouro(p):
    p.update(sequences=TINY_OURO_TRAIN["micro_batch"],
             seq_len=TINY_OURO_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 2)


# granite-train-1chip: the same, at the widths of tests/test_granite_train.py
# (layers 4 to 6, a Mamba-2 layer each side of the attention layer: half of
# 4 Mamba heads of 32 beside the one B/C group, half of 4 query heads on 2
# key-value heads, 64 of 256 ids whose last ends a document, chunks of 8, 2
# packed rows of 64 tokens in documents of about 4, tiles of 16)
GRANITE = "granite-train-1chip"
TINY_GRANITE = dict(hidden_size=64, num_attention_heads=4,
                    num_key_value_heads=2, heads_here=2, mamba_n_heads=4,
                    mamba_d_head=32, mamba_heads_here=2, mamba_d_state=16,
                    mamba_chunk_size=8, intermediate_size=96,
                    shared_intermediate_size=96, vocab_size=256,
                    vocab_here=64, eos_token_here=63, layers_here=3,
                    first_layer_here=4)
TINY_GRANITE_TRAIN = dict(seq_len=64, micro_batch=2, attn_block=16,
                          loss_block_rows=32, compute_dtype="float32")


def _tiny_granite(config):
    config.update(TINY_GRANITE)
    config["train"].update(TINY_GRANITE_TRAIN)


def _cut_batch_granite(p):
    p.update(sequences=TINY_GRANITE_TRAIN["micro_batch"],
             seq_len=TINY_GRANITE_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 2)


# xing-train-1chip: the same, at the widths of tests/test_xing_train.py (one
# of 2 leading dense layers and 2 sparse ones on 4 residual streams of 64
# under 20 sweeps, 2 of 4 heads of 16 + 8 / 16 under YaRN, 2 of 8 experts
# of 32 beside a shared one, 64 of 512 ids, 2 packed rows of 32 tokens, tiles
# of 16)
XING = "xing-train-1chip"
TINY_XING = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
                 num_key_value_heads=4, heads_here=2, q_lora_rank=32,
                 kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, moe_intermediate_size=32, n_routed_experts=8,
                 num_experts_per_tok=2, experts_here=2, vocab_size=512,
                 vocab_here=64, layers_here=3, dense_here=1)
TINY_XING_TRAIN = dict(seq_len=32, micro_batch=2, attn_block=16,
                       loss_block_rows=16, compute_dtype="float32")


def _tiny_xing(config):
    config.update(TINY_XING)
    config["rope_scaling"]["original_max_position_embeddings"] = 16
    config["train"].update(TINY_XING_TRAIN)


def _cut_batch_xing(p):
    p.update(sequences=TINY_XING_TRAIN["micro_batch"],
             seq_len=TINY_XING_TRAIN["seq_len"])
    p["bytes"] = 4 * p["sequences"] * (p["seq_len"] + 2)


#: the cells later PRs brought, in the order they were appended: a test of
#: an earlier cell's place at the end of a list leaves out the ones behind
#: it (``LATER[LATER.index(cell) + 1:]``)
LATER = (KEYE, SDAR, OURO, GRANITE, XING)


def held_once(real, new) -> None:
    """Every metric of the set ``new`` is an entry of ``per_layer``, once:
    found by membership, wherever a fold or a later PR puts it."""
    names = [m["name"] for m in real["per_layer"]]
    assert [names.count(name) for name in new] == [1] * len(new)


def cell_and_config(real, name) -> tuple:
    """(the cell called ``name``, the configuration it names), each found
    by its name, whatever its place in its list."""
    (cell,) = [w for w in real["workloads"] if w["name"] == name]
    (config,) = [c for c in real["configs"] if c["name"] == cell["config"]]
    return cell, config


def _cut_bytes(small):
    """Large points cut to at most 64 KiB, each size to its own so that
    no two points share a program they do not share at full size.  A
    point stays on its side of the program's one size threshold: a
    ``bcast`` of ``bcast_sa_min_bytes`` (256 KiB) or more is the masked
    all-reduce, a smaller one the tree."""
    def cut(p):
        if p["bytes"] >= 4 * MIB:
            p["bytes"] = 256 * KIB if p["kind"] == "bcast" \
                else small[p["bytes"]]
    return cut


# a cell: CPU devices (its own ranks, but rank1-ddt, whose typed
# exchanges need more than one and whose configuration is widened in the
# copy), points, end-to-end metrics, the traffic files cut in the copy
# and how, and the shift that cuts the pools with the points
CELLS = {
    "osu-2x2-mix": dict(
        devices=4, points=20, pool_shift=10,
        metrics={"small_msg_us", "allreduce_busbw", "coll_busbw",
                 "setup_s"},
        cut={"large-set": _cut_bytes(
            {4 * MIB: 16 * KIB, 16 * MIB: 48 * KIB, 64 * MIB: 64 * KIB})}),
    "rank1-mix": dict(
        devices=1, points=14, pool_shift=10,
        metrics={"small_msg_us", "reduce_local_bw", "setup_s"},
        cut={"reduce-local-set": _cut_bytes(
            {4 * MIB: 16 * KIB, 16 * MIB: 32 * KIB, 64 * MIB: 64 * KIB})}),
    "rank1-blocking-xl": dict(
        devices=1, points=6, pool_shift=12,
        metrics={"reduce_local_bw", "setup_s"},
        cut={"reduce-local-xl-set": _cut_bytes(
            {64 * MIB: 16 * KIB, 128 * MIB: 32 * KIB, 256 * MIB: 64 * KIB})}),
    "rank1-ddt": dict(
        devices=8, points=13, pool_shift=10, widen="ddt-device-1chip",
        metrics={"small_msg_us", "reduce_local_bw", "setup_s"},
        cut={"ddt-face-transpose-mix": _cut_ddt}),
    "rank1-partitioned": dict(
        devices=1, points=4, pool_shift=10,
        metrics={"small_msg_us", "setup_s"},
        cut={"grad-bucket-steps": _cut_buckets}),
    "olmoe-train-1chip": dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("olmoe-1b-7b-train-1chip", _tiny_model),
        cut={"packed-4k-steps": _cut_batch}),
    "joyai-train-1chip": dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("joyai-flash-train-1chip", _tiny_share),
        cut={"packed-8k-steps": _cut_batch_share}),
    "nemotron3-train-1chip": dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("nemotron3-super-train-1chip", _tiny_hybrid),
        cut={"packed-8k-hybrid-steps": _cut_batch_share}),
    "lfm2-train-1chip": dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("lfm2-8b-a1b-train-1chip", _tiny_typed),
        cut={"packed-8k-conv-steps": _cut_batch_typed}),
    "qwen3next-train-1chip": dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("qwen3-next-80b-a3b-train-1chip", _tiny_next),
        cut={"packed-16k-deltanet-steps": _cut_batch_next}),
    "smallthinker-train-1chip": dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("smallthinker-21b-a3b-train-1chip", _tiny_thinker),
        cut={"packed-16k-window-steps": _cut_batch_thinker}),
    KEYE: dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("keye-vl2-30b-a3b-train-1chip", _tiny_keye),
        cut={"packed-16k-sparse-steps": _cut_batch_keye}),
    SDAR: dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("sdar-30b-a3b-train-1chip", _tiny_sdar),
        cut={"packed-8k-block-diffusion-steps": _cut_batch_sdar}),
    OURO: dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("ouro-2.6b-train-1chip", _tiny_ouro),
        cut={"packed-4k-looped-steps": _cut_batch_ouro}),
    GRANITE: dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("granite-4.0-h-micro-train-1chip", _tiny_granite),
        cut={"packed-16k-docs-steps": _cut_batch_granite}),
    XING: dict(
        devices=1, points=1, pool_shift=0,
        metrics={"small_msg_us", "setup_s"},
        config=("xing4.0-29b-a4b-train-1chip", _tiny_xing),
        cut={"packed-4k-hyper-steps": _cut_batch_xing}),
}
NEW_CELLS = [c for c in CELLS if c != CELL]
# cells whose calls are steps: many collectives or none a call
# what a step no longer counts: each a constant of the configuration
# times SPC train_steps (the slots read back hold the constants)
PER_STEP_CONSTANTS = ("train_tokens", "moe_token_slots", "train_mtp_tokens",
                      "train_ssm_layer_tokens", "moe_bias_updates")
STEP_CELLS = ("rank1-partitioned", "olmoe-train-1chip", "joyai-train-1chip",
              "nemotron3-train-1chip", "lfm2-train-1chip",
              "qwen3next-train-1chip", "smallthinker-train-1chip", KEYE,
              SDAR, OURO, GRANITE, XING)
CALL_CELLS = [c for c in NEW_CELLS if c not in STEP_CELLS]

# the per-layer metrics of the build record (PR 53): read by their own
# readers in the child, since a rehearsal is a ``--trace 0`` run
BUILD_METRICS = ("compile.trace_s", "compile.lower_s",
                 "compile.own_backend_s", "compile.cache_hit_share",
                 "compile.own_programs", "compile.other_s")
PEAK_METRIC = "step.hbm_peak_share"
# the share of the held experts' loops' rows that held a slot (PR 57),
# and the cells whose rank holds a share of the experts
LIVE_ROWS = "moe.live_row_share"
SHARE_CELLS = ("joyai-train-1chip", "nemotron3-train-1chip",
               "lfm2-train-1chip", "qwen3next-train-1chip",
               "smallthinker-train-1chip", KEYE, SDAR, XING)
# what routing and dispatch cost such a cell's step (PR 59)
ROUTE_SHARE = "moe.route_share"

# the child: run_cell as the command calls it, but for the three
# arguments it keeps for rehearsals.  What the program counts is read
# around it: the programs coll/xla names as it builds them, and SPC
# ``device_program_builds`` when the measured time starts and at the end;
# after the measurement, while the run still holds its steps, the build
# record's metrics as their files and readers give them (against a v5e's
# memory: the CPU has no row in the table of peaks).
REHEARSAL = """
import json, os, sys
sys.path[:0] = [{bench!r}, {repo!r}]
import run
from harness import manifest, protocol
from ompi_tpu.mca.coll import xla
from ompi_tpu.runtime import spc

programs, builds, layer = [], [], {{}}
name_of, measure = xla._program_name, protocol.measure
bench_dir = os.path.join({root!r}, "benchmark")

def named(coll, variant=None):
    programs.append(name_of(coll, variant))
    return programs[-1]

def measured(*args, **kw):
    builds.append(spc.read("device_program_builds"))
    out = measure(*args, **kw)
    ctx = {{"points": [], "run": {{"workload": {cell!r}}}, "trace": None,
           "device_kind": "TPU v5 lite"}}
    for name in {metrics!r}:
        spec = manifest.metric_spec(name, bench_dir)
        layer[name] = protocol.load_module(
            "readers", spec["reader"], bench_dir).read(
                ctx, spec.get("params", {{}}))
    return out

xla._program_name, protocol.measure = named, measured
result = run.run_cell({cell!r}, seed=2147483999, seconds=0.3, trace=False,
                      platform="cpu", root={root!r}, min_window_s=0.002)
builds.append(spc.read("device_program_builds"))
print("counters " + json.dumps({{k: v for k, v in spc.counters().items()
                                 if k.startswith(("device_", "train_",
                                                  "moe_", "attn_", "doc_",
                                                  "dsa_", "bd_", "loop_",
                                                  "hc_"))}}))
print("programs " + json.dumps(sorted(set(programs))))
print("builds " + json.dumps(builds))
print("layer " + json.dumps(layer))
print("result " + json.dumps(result))
"""


def scopes_up_to(last: str, n: int = None) -> tuple:
    """The ``n`` names of the program's ``trace.STEP_SCOPES`` that end in
    ``last`` (all of them up to it where ``n`` is None): where a metric
    file's ``vocabulary`` stands in the tuple, whatever later PRs put
    behind it."""
    from ompi_tpu.runtime import trace

    upto = trace.STEP_SCOPES[:trace.STEP_SCOPES.index(last) + 1]
    return upto if n is None else upto[-n:]


@pytest.fixture(scope="module")
def mf():
    sys.path.insert(0, BENCH)
    try:
        from harness import manifest
        yield manifest
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def real(mf):
    return mf.load(REPO)


def test_the_real_manifest_passes_every_rule(mf, real):
    raw = os.path.getsize(os.path.join(REPO, "BENCHMARK.json"))
    assert mf.validate(real, REPO, raw_bytes=raw) == []


def test_every_name_resolves_to_its_file(mf, real):
    assert mf.validate_harness(real, REPO) == []


def test_a_broken_manifest_is_refused(mf, real):
    broken = json.loads(json.dumps(real))
    broken["workloads"][-1]["why"] = "x" * 201
    assert any("why" in e for e in mf.validate(broken, REPO))
    broken = json.loads(json.dumps(real))
    broken["end_to_end"][0]["workloads"].append("no-such-cell")
    assert mf.validate(broken, REPO)


def test_rank1_ddt_is_one_chip_under_the_two_one_chip_metrics(mf, real):
    cell = mf.by_name(real["workloads"], CELL, "workload")
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "ddt-device-1chip", "ddt-face-transpose-mix")
    assert [m["name"] for m in mf.metrics_of(real, "end_to_end", CELL)] \
        == ["small_msg_us", "reduce_local_bw", "setup_s"]
    points = mf.traffic_points(cell["traffic"], BENCH)
    chosen = mf.points_by_metric(real, CELL, points, BENCH)
    assert len(points) == 13
    assert len(chosen["small_msg_us"]) == 7
    assert len(chosen["reduce_local_bw"]) == 6
    assert chosen["ddt.roofline"] == chosen["reduce_local_bw"]
    assert mf.raw_points(real, CELL, points, BENCH) \
        == set(chosen["ddt.vs_manual"])


def _stage(cell, root):
    """A copy of the benchmark under ``root`` with ``cell`` cut to tiny
    sizes; returns the child's environment (the cell's CPU devices)."""
    spec = CELLS[cell]
    devices = spec["devices"]
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))

    def edit(path, fn):
        with open(path, encoding="utf-8") as f:
            obj = json.load(f)
        fn(obj)
        with open(path, "w", encoding="utf-8") as f:
            json.dump(obj, f)

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    if "widen" in spec:     # more devices than the cell's own ranks
        edit(os.path.join(root, "BENCHMARK.json"), lambda m: [
            w.update(chips=devices) for w in m["workloads"]])
        edit(os.path.join(bench, "configs", spec["widen"] + ".json"),
             lambda c: c.update(ranks=devices, chips=devices))
    if "config" in spec:    # a model at the widths of a rehearsal
        name, tiny = spec["config"]
        edit(os.path.join(bench, "configs", name + ".json"), tiny)
    edit(os.path.join(bench, "cells", cell + ".json"),
         lambda c: c.update(pool_bytes_per_point=max(
             64 * KIB, c["pool_bytes_per_point"] >> spec["pool_shift"])))
    for traffic, cut in spec["cut"].items():
        edit(os.path.join(bench, "traffic", traffic + ".json"),
             lambda mix: [cut(p) for p in mix["points"]])

    return dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))


def _rehearse(cell, root):
    """One run of ``cell`` at tiny sizes on its CPU devices, in a copy of
    the benchmark under ``root`` and a process of its own."""
    env = _stage(cell, root)
    done = subprocess.run(
        [sys.executable, "-c", REHEARSAL.format(
            bench=BENCH, repo=REPO, cell=cell, root=root,
            metrics=BUILD_METRICS + (PEAK_METRIC, LIVE_ROWS))],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.splitlines()

    def tagged(tag):
        return [json.loads(ln[len(tag) + 1:]) for ln in lines
                if ln.startswith(tag + " ")]
    return {"cell": cell,
            "points": {p["name"]: p for p in tagged("point")},
            "run": tagged("run")[0], "counters": tagged("counters")[0],
            "programs": tagged("programs")[0], "builds": tagged("builds")[0],
            "layer": tagged("layer")[0],
            "result": tagged("result")[0]}


@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    """Each cell's one child a session, started by the first test that
    asks on whichever worker (``built.shared``)."""
    done = {}

    def of(cell):
        if cell not in done:
            root = str(tmp_path_factory.mktemp("bench-" + cell))
            done[cell] = dict(
                built.shared("rehearsal-" + cell,
                             lambda: _rehearse(cell, root)),
                spec=CELLS[cell])
        return done[cell]
    return of


@pytest.fixture
def rehearsal(request, rehearsals):
    return rehearsals(request.param)


def of_cells(*cells):
    return pytest.mark.parametrize("rehearsal", cells, indirect=True)


every_cell = of_cells(*CELLS)


@every_cell
def test_the_rehearsal_is_correct_at_every_point(rehearsal):
    result, spec = rehearsal["result"], rehearsal["spec"]
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > spec["points"] * 2
    assert result["device"]["count"] == spec["devices"]
    assert len(rehearsal["points"]) == spec["points"]
    assert all(p["windows"] >= 1 for p in rehearsal["points"].values())


@every_cell
def test_the_rehearsal_reports_the_cells_end_to_end_metrics(rehearsal):
    metrics = rehearsal["result"]["metrics"]
    assert set(metrics) == rehearsal["spec"]["metrics"]
    assert all(m["value"] > 0 for m in metrics.values())


@every_cell
def test_the_rehearsal_reads_the_build_record(rehearsal):
    """The six ``compile.*`` metrics of the build record read something
    in every cell: the own programs' three phases and the rest's took
    time, no phase twice (together under the set-up they lie in), at
    least one own program went through the backend, and the share of
    cache hits is one.  A step cell reads its step's compiled peak as a
    share of a chip's memory; a cell that builds no step reads none."""
    layer, run = rehearsal["layer"], rehearsal["run"]
    assert all(layer[m] is not None for m in BUILD_METRICS), layer
    phases = [layer["compile." + m] for m in
              ("trace_s", "lower_s", "own_backend_s", "other_s")]
    assert all(s > 0 for s in phases) and sum(phases) < run["setup_s"]
    assert layer["compile.own_programs"] >= 1
    assert 0 <= layer["compile.cache_hit_share"] <= 100
    # the harness's own count is of the whole process
    assert layer["compile.own_backend_s"] <= run["compile_s"]
    if rehearsal["cell"].endswith("-train-1chip"):
        assert 0 < layer[PEAK_METRIC] < 100
        # one step, so the first call's seconds hold its three phases
        first = rehearsal["counters"]["device_program_first_call_us"] / 1e6
        assert sum(phases[:3]) < first
    else:
        assert layer[PEAK_METRIC] is None


@of_cells("rank1-blocking-xl", "rank1-ddt")
def test_programs_no_first_call_counts_are_counted_as_built(rehearsal):
    """The cells whose programs are built by module-level jits and the
    datatype engine, which ``device_program_builds`` never saw."""
    assert rehearsal["layer"]["compile.own_programs"] > 0
    if rehearsal["cell"] == "rank1-blocking-xl":
        assert rehearsal["counters"]["device_program_builds"] == 0


def test_the_build_metrics_are_entries_of_the_manifest(mf, real):
    """Appended, every cell under the six, the step cells under the
    seventh, each moving what the issue says."""
    by_name = {m["name"]: m for m in real["per_layer"]}
    cells = [w["name"] for w in real["workloads"]]
    for name in BUILD_METRICS:
        m = by_name[name]
        assert (m["workloads"], m["moves"], m["source"]) == (
            cells, "setup_s", "program_counter")
        assert m["layer"] == by_name["compile.backend_s"]["layer"]
    peak = by_name[PEAK_METRIC]
    assert peak["workloads"] == [c for c in cells
                                 if c.endswith("-train-1chip")]
    assert (peak["moves"], peak["better"]) == ("small_msg_us", "lower")
    assert peak["layer"] == by_name["train.mfu"]["layer"]
    held_once(real, list(BUILD_METRICS) + [PEAK_METRIC])


def test_the_convolutions_kernel_share_is_an_entry_of_the_manifest(real):
    """An entry (PR 54), found by its name: a data file on
    ``program_counter`` in the one cell whose model has a DeltaNet
    convolution, under the layer and the end-to-end metric of the rule's
    ``gdn.kernel_share``."""
    by_name = {m["name"]: m for m in real["per_layer"]}
    conv, rule = by_name["gdn.conv_kernel_share"], by_name["gdn.kernel_share"]
    held_once(real, ["gdn.conv_kernel_share", "gdn.kernel_share"])
    assert {k: v for k, v in conv.items() if k != "name"} \
        == {k: v for k, v in rule.items() if k != "name"}
    assert conv["workloads"] == ["qwen3next-train-1chip"]
    with open(os.path.join(BENCH, "metrics", "gdn.conv_kernel_share.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    assert (spec["reader"], spec["params"]) == ("program_counter", {
        "name": "gdn_conv_kernel_built", "over": "gdn_conv_built",
        "scale": 100})


@of_cells(*CALL_CELLS)
def test_the_counters_account_for_the_calls(rehearsal):
    """SPC ``device_collectives`` moved by what the harness issued
    (``correct`` holds the two equal): at least the timed calls of every
    collective point, and not at all where the cell holds none.  Every
    program was built in set-up."""
    points = rehearsal["points"].values()
    timed = sum(p["k"] * p["windows"] for p in points
                if p["kind"] != "stack_reduce")
    seen = rehearsal["run"]["spc_device_collectives"]
    assert seen > timed if timed else seen == 0
    assert rehearsal["counters"]["device_collectives"] >= seen
    at_measure, at_end = rehearsal["builds"]
    assert at_measure == at_end
    # one program a slot, shape and op; the handle shares allreduce's
    slots = {(p["kind"].replace("_init", ""), p["op"], p["bytes"])
             for p in points if p["kind"] != "stack_reduce"}
    assert at_end == len(slots)


@of_cells(*NEW_CELLS)
def test_a_points_bytes_follow_from_its_parameters(rehearsal):
    """``bytes`` is S as the traffic file gives it; the bus bytes are
    nccl-tests' factor times S on four ranks and absent on one; a stack
    moves every row once and writes one."""
    n = rehearsal["spec"]["devices"]
    factor = {"allreduce": 2 * (n - 1) / n, "allgather": (n - 1) / n,
              "reduce_scatter": (n - 1) / n, "alltoall": (n - 1) / n,
              "bcast": 1.0}
    for row in rehearsal["points"].values():
        assert row["n"] == n and row["bytes"] <= 256 * KIB, row["name"]
        if row["kind"] == "stack_reduce":
            assert row["moved_bytes"] == 5 * row["bytes"], row["name"]
            assert "bus_bytes" not in row
        elif n > 1:
            slot = row["kind"].replace("_init", "")
            assert row["bus_bytes"] == factor[slot] * row["bytes"], \
                row["name"]
        else:
            assert "bus_bytes" not in row and "moved_bytes" not in row


@of_cells("osu-2x2-mix")
def test_both_regimes_of_bcast_are_walked(rehearsal):
    """The 1 KiB point is the ``ppermute`` tree, the two large ones
    (cut to ``bcast_sa_min_bytes``, not below) the masked all-reduce."""
    assert {"otpu_bcast_tree", "otpu_bcast_psum"} <= set(
        rehearsal["programs"])
    assert {"otpu_allreduce_sum", "otpu_allreduce_prod", "otpu_allgather",
            "otpu_reduce_scatter_sum", "otpu_alltoall"} <= set(
        rehearsal["programs"])


@of_cells(CELL)
def test_moved_bytes_is_twice_the_packed_size(rehearsal):
    for row in rehearsal["points"].values():
        assert row["moved_bytes"] == 2 * row["bytes"], row["name"]
        assert "bus_bytes" not in row


@of_cells(CELL)
def test_plans_and_programs_are_built_in_set_up(rehearsal):
    """One plan a datatype object and count: every point commits its own
    datatype, so 13 (equal regular maps share the plan object, not the
    build); one of them an index list.  The two typed slots are the only
    collectives."""
    assert rehearsal["counters"]["device_ddt_plan_builds"] == 13
    assert rehearsal["counters"]["device_ddt_index_plans"] == 1
    assert rehearsal["counters"]["device_ddt_packs"] > 0
    assert rehearsal["counters"]["device_ddt_unpacks"] > 0
    calls = sum(p["k"] * p["windows"] for p in rehearsal["points"].values()
                if p["kind"] == "ddt_to_self")
    assert rehearsal["run"]["spc_device_collectives"] > calls


@of_cells("rank1-partitioned")
def test_a_step_counts_a_collective_a_bucket(rehearsal):
    """SPC ``device_collectives`` moves once a bucket released: by more
    than the timed steps' buckets (``correct`` holds the harness's count
    and the program's equal), and nothing is built after set-up."""
    points = rehearsal["points"].values()
    assert {p["collectives_per_call"] for p in points} == {4, 51, 32, 1}
    timed = sum(p["k"] * p["windows"] * p["collectives_per_call"]
                for p in points)
    assert rehearsal["run"]["spc_device_collectives"] > timed
    at_measure, at_end = rehearsal["builds"]
    assert at_measure == at_end


@of_cells("olmoe-train-1chip")
def test_a_train_step_counts_its_tokens_and_moves_no_collective(rehearsal):
    """The step's ``psum`` passes no ``world.*_array`` slot; the
    trainer counts the steps issued and nothing that is a constant times
    them (tokens; routed slots: tokens x 2 experts x 2 layers at the
    rehearsal's widths), the fullest expert holds at least the mean
    load, and the step's program is the one program built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_TRAIN["micro_batch"] * TINY_TRAIN["seq_len"]
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert row["collectives_per_call"] == 0 and row["tolerance"]["why"]
    assert c["train_steps"] > row["k"] * row["windows"]
    assert not set(PER_STEP_CONSTANTS) & set(c)
    assert c["moe_max_expert_load"] >= tokens * 2 / 8
    assert rehearsal["builds"] == [1, 1]


@of_cells("joyai-train-1chip")
def test_a_share_step_counts_its_slots_here_and_elsewhere(rehearsal):
    """The trainer's counters on one chip's share: the steps issued,
    and no constant times them (the module's tokens, the routers' bias
    updates: 2 sparse layers and the module at the rehearsal's widths,
    which the slots read back hold); over the steps read
    back every slot went to a held expert or to an absent one; the
    step's program is the one program built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_SHARE_TRAIN["micro_batch"] * TINY_SHARE_TRAIN["seq_len"]
    assert row["kind"] == "train_step_share" and row["tolerance"]["why"]
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    assert not set(PER_STEP_CONSTANTS) & set(c)
    assert c["train_steps_read"] >= 3
    assert c["moe_local_slots"] + c["moe_absent_slots"] \
        == c["train_steps_read"] * tokens * 4 * 3
    assert rehearsal["builds"] == [1, 1]


@of_cells("nemotron3-train-1chip")
def test_a_hybrid_step_counts_its_state_space_tokens(rehearsal):
    """The trainer's counters on one chip's share of a hybrid model, by
    the kind that reads everything from the kit: the steps issued, and
    no constant times them (the state-space layers' tokens, the routers'
    bias updates: 3 Mamba-2 and 3 expert layers at the rehearsal's
    widths, no next-n module); over the steps read back every slot went
    to a held
    expert or to an absent one; the step's program is the one program
    built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_SHARE_TRAIN["micro_batch"] * TINY_SHARE_TRAIN["seq_len"]
    assert row["kind"] == "train_step_kit" and row["tolerance"]["why"]
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    assert not set(PER_STEP_CONSTANTS) & set(c)
    assert c["train_steps_read"] >= 3
    assert c["moe_local_slots"] + c["moe_absent_slots"] \
        == c["train_steps_read"] * tokens * 3 * 3
    assert rehearsal["builds"] == [1, 1]


@of_cells("lfm2-train-1chip")
def test_a_typed_step_counts_its_routers_and_its_slots(rehearsal):
    """The trainer's counters on one chip's share of a ``layer_types``
    model, by the kind that reads everything from the kit: the steps
    issued, and no constant times them (the routers' bias updates: 5
    sparse layers of the 6 held, no next-n module, no state-space
    layer); over the steps read
    back every slot went to a held expert or to an absent one; the
    step's program is the one program built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_TYPED_TRAIN["micro_batch"] * TINY_TYPED_TRAIN["seq_len"]
    assert row["kind"] == "train_step_kit" and row["tolerance"]["why"]
    assert row["name"] == "train_step.lfm2.bf16.2x8192"
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    assert not set(PER_STEP_CONSTANTS) & set(c)
    assert c["train_steps_read"] >= 3
    assert c["moe_local_slots"] + c["moe_absent_slots"] \
        == c["train_steps_read"] * tokens * 2 * 5
    assert rehearsal["builds"] == [1, 1]


@of_cells("qwen3next-train-1chip")
def test_a_delta_rule_step_counts_its_routers_and_its_slots(rehearsal):
    """The trainer's counters on one chip's share of qwen3_next's period,
    by the kind that reads everything from the kit and with no file of
    the harness edited for it: 4 routers that choose by softmax under no
    bias, so no bias update is counted; over the steps read back every
    slot went to a held expert or to an absent one; the step's program is
    the one program built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_NEXT_TRAIN["micro_batch"] * TINY_NEXT_TRAIN["seq_len"]
    assert row["kind"] == "train_step_kit" and row["tolerance"]["why"]
    assert row["name"] == "train_step.qwen3next.bf16.1x16384"
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    assert not set(PER_STEP_CONSTANTS) & set(c)
    assert c["train_steps_read"] >= 3
    assert c["moe_local_slots"] + c["moe_absent_slots"] \
        == c["train_steps_read"] * tokens * 4 * 4
    assert rehearsal["builds"] == [1, 1]


@of_cells("smallthinker-train-1chip")
def test_a_window_step_counts_its_routers_and_its_slots(rehearsal):
    """The trainer's counters on one chip's share of smallthinker's
    period, by the kind that reads everything from the kit and with no
    file of the harness edited for it: 4 routers before attention that
    choose by softmax under no bias; over the steps read back every slot
    went to a held expert or to an absent one; the step's program is the
    one program built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_THINKER_TRAIN["micro_batch"] * TINY_THINKER_TRAIN["seq_len"]
    assert row["kind"] == "train_step_kit" and row["tolerance"]["why"]
    assert row["name"] == "train_step.smallthinker.bf16.1x16384"
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    assert not set(PER_STEP_CONSTANTS) & set(c)
    assert c["train_steps_read"] >= 3
    assert c["moe_local_slots"] + c["moe_absent_slots"] \
        == c["train_steps_read"] * tokens * 3 * 4
    assert rehearsal["builds"] == [1, 1]


@of_cells(KEYE)
def test_a_sparse_attention_step_counts_its_routers_and_its_selection(
        rehearsal):
    """The trainer's counters on one chip's share of Keye-VL-2.0's language
    model, by the kind that reads everything from the kit and with no file
    of the harness edited for it: 4 softmax routers under no bias; every
    attention pass made under a selection, of 24 of up to 64 keys a query;
    the step's program is the one program built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_KEYE_TRAIN["micro_batch"] * TINY_KEYE_TRAIN["seq_len"]
    assert row["kind"] == "train_step_kit" and row["tolerance"]["why"]
    assert row["name"] == "train_step.keye.bf16.1x16384"
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    assert c["moe_local_slots"] + c["moe_absent_slots"] \
        == c["train_steps_read"] * tokens * 3 * 4
    assert c["dsa_built"] == c["attn_built"] > 0
    assert c["dsa_keys_selected"] * (64 * 65 // 2) \
        == c["dsa_keys_causal"] * (24 * 25 // 2 + 40 * 24)
    # the selection a pass reads, eight keys a byte: 64 x 64 / 8
    assert c["dsa_mask_bytes"] == c["dsa_built"] * 64 * 64 // 8
    assert rehearsal["builds"] == [1, 1]


def test_the_sparse_cells_metrics_are_entries_of_the_manifest(real):
    """Entries since PR 58, each found by its name: data files on
    readers that are there, in the one cell whose model selects its keys,
    each moving ``small_msg_us``; the selection's seven under a layer of
    their own; the cell's name at the end of the lists every share cell is
    in."""
    new = ["keye.mfu", "keye.tokens_per_s", "keye.local_load",
           "keye.remat_share", "keye.unnamed_share", "keye.flash_mfu",
           "keye.attn_bwd_mfu", "dsa.operator_share", "dsa.index_share",
           "dsa.select_share", "dsa.loss_share", "dsa.selected_share",
           "dsa.index_mfu", "dsa.loss_mfu"]
    held_once(real, new + [ROUTE_SHARE])
    by_name = {m["name"]: m for m in real["per_layer"]}
    for name in new:
        m = by_name[name]
        assert ([c for c in m["workloads"] if c not in LATER[1:]],
                m["moves"]) == ([KEYE], "small_msg_us")
        twin = by_name.get(name.replace("keye.", "smallthinker."))
        if twin and twin is not m:
            assert {k: m[k] for k in ("unit", "better", "source", "layer")} \
                == {k: twin[k] for k in ("unit", "better", "source", "layer")}
        with open(os.path.join(BENCH, "metrics", name + ".json"),
                  encoding="utf-8") as f:
            assert json.load(f)["reader"] in (
                "trace_kit_flops", "point_rate", "program_counter",
                "trace_scope_share_wide")
    assert len({by_name[n]["layer"] for n in new[-7:]}) == 1
    cell, config = cell_and_config(real, KEYE)
    assert (cell["chips"], config["name"]) == (1, cell["config"])
    for m in real["end_to_end"] + real["per_layer"]:
        if "qwen3next-train-1chip" in m.get("workloads", ()) \
                and len(m["workloads"]) > 1:
            assert [c for c in m["workloads"]
                    if c not in LATER[1:]][-1] == KEYE, m["name"]
    with open(os.path.join(BENCH, "metrics", "dsa.selected_share.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    assert (spec["reader"], spec["params"]) == ("program_counter", {
        "name": "dsa_keys_selected", "over": "dsa_keys_causal",
        "scale": 100})
    with open(os.path.join(BENCH, "metrics", "dsa.loss_share.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    from ompi_tpu.runtime import trace

    # later PRs put names behind these
    assert spec["params"]["scopes"] == ["otpu_dsa_loss"] \
        and tuple(spec["params"]["vocabulary"]) == scopes_up_to(
            "otpu_dsa_loss", len(spec["params"]["vocabulary"]))


@of_cells(SDAR)
def test_a_block_diffusion_step_counts_its_rows_and_its_visible_pairs(
        rehearsal):
    """The trainer's counters on one chip's share of SDAR, by the kind that
    reads everything from the kit and with no file of the harness edited
    for it: 4 softmax routers under no bias over the ``2 L`` rows of a
    noisy and a clean copy; every attention pass made under block
    diffusion's mask, 64 tokens in blocks of 4 and tiles of 16; the rows
    the steps read back had masked; the step's program is the one program
    built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_SDAR_TRAIN["micro_batch"] * TINY_SDAR_TRAIN["seq_len"]
    assert row["kind"] == "train_step_kit" and row["tolerance"]["why"]
    assert row["name"] == "train_step.sdar.bf16.1x8192"
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    assert c["moe_local_slots"] + c["moe_absent_slots"] \
        == c["train_steps_read"] * 2 * tokens * 3 * 4
    assert c["bd_built"] == c["attn_built"] > 0
    # 16 blocks of 4: 16 x 17 / 2 + 16 x 15 / 2 blocks squared and 64 x 4
    assert c["bd_pairs_visible"] * (128 * 129 // 2) \
        == c["bd_pairs_causal"] * (16 * (136 + 120) + 64 * 4)
    # 8 tiles of 16: 4 + 6 + 4 + 10 tile pairs of a causal walk's 36
    assert c["attn_pairs_walked"] * 36 == c["attn_pairs_causal"] * 24
    assert 0 < c["bd_rows_masked"] < c["train_steps_read"] * tokens
    assert rehearsal["builds"] == [1, 1]


def test_the_block_diffusion_cells_metrics_are_entries_of_the_manifest(real):
    """Entries since PR 64, each found by its name: data files on
    readers that are there, in the one cell trained by block diffusion,
    each moving ``small_msg_us``; the mechanism's five under a layer of
    their own; the cell's name at the end of the lists every share cell is
    in and of the walked pairs' list."""
    new = ["sdar.mfu", "sdar.tokens_per_s", "sdar.local_load",
           "sdar.remat_share", "sdar.unnamed_share", "sdar.flash_mfu",
           "sdar.attn_bwd_mfu", "bd.operator_share", "bd.noise_share",
           "bd.loss_share", "bd.visible_share", "bd.masked_share"]
    held_once(real, new)
    by_name = {m["name"]: m for m in real["per_layer"]}
    readers = {}
    for name in new:
        m = by_name[name]
        assert (m["workloads"], m["moves"]) == ([SDAR], "small_msg_us")
        twin = by_name.get(name.replace("sdar.", "keye."))
        if twin and twin is not m:
            assert {k: m[k] for k in ("unit", "better", "source", "layer")} \
                == {k: twin[k] for k in ("unit", "better", "source", "layer")}
        with open(os.path.join(BENCH, "metrics", name + ".json"),
                  encoding="utf-8") as f:
            readers[name] = json.load(f)
        assert readers[name]["reader"] in (
            "trace_kit_flops", "point_rate", "program_counter",
            "trace_scope_share_wide")
    assert len({by_name[n]["layer"] for n in new[-5:]}) == 1
    cell, config = cell_and_config(real, SDAR)
    assert (cell["chips"], config["reduced"]) == (
        1, ["layers", "experts", "vocab", "ranks"])
    for m in real["end_to_end"] + real["per_layer"]:
        then = [c for c in m.get("workloads", ()) if c not in LATER[2:]]
        if KEYE in then and len(then) > 1:
            assert then[-1] == SDAR, m["name"]
    assert SDAR in by_name["attn.pairs_walked_share"]["workloads"]
    assert (readers["bd.visible_share"]["reader"],
            readers["bd.visible_share"]["params"]) == ("program_counter", {
                "name": "bd_pairs_visible", "over": "bd_pairs_causal",
                "scale": 100})
    assert readers["bd.masked_share"]["params"] == {
        "name": "bd_rows_masked", "over": "train_steps_read",
        "scale": 100 / 8192}
    assert readers["sdar.tokens_per_s"]["params"]["amount"] == 8192
    for name, count, pattern in (
            ("sdar.flash_mfu", "flash_forward", "^otpu_flash"),
            ("sdar.attn_bwd_mfu", "attn_backward", "^otpu_attn_.*backward")):
        assert (readers[name]["params"]["count"],
                readers[name]["params"]["pattern"]) == (count, pattern)
    from ompi_tpu.runtime import trace

    for name, scope in (("bd.operator_share", "otpu_bd"),
                        ("bd.noise_share", "otpu_bd_noise"),
                        ("bd.loss_share", "otpu_bd_loss")):
        spec = readers[name]["params"]      # later PRs put names behind
        assert spec["scopes"] == [scope] and tuple(
            spec["vocabulary"]) == scopes_up_to(
                "otpu_bd_loss", len(spec["vocabulary"]))


@of_cells(OURO)
def test_a_looped_step_counts_its_passes_and_routes_nothing(rehearsal):
    """The trainer's counters on one chip's four layers of Ouro (two here),
    by the kind that reads everything from the kit and with no file of the
    harness edited for it: the loop's shape from what was traced, the mean
    exit pass of the steps read back (a gate that has hardly moved: 1.875),
    no slot and no expert's load anywhere, every attention pass a plain
    causal walk; the step's program is the one program built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_OURO_TRAIN["micro_batch"] * TINY_OURO_TRAIN["seq_len"]
    assert row["kind"] == "train_step_kit" and row["tolerance"]["why"]
    assert row["name"] == "train_step.ouro.bf16.2x4096"
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    assert c["loop_built"] > 0 \
        and c["loop_passes"] == 4 * c["loop_built"] \
        and c["loop_layers_held"] == 2 * c["loop_built"] \
        and c["loop_layer_applications"] == 8 * c["loop_built"] \
        and c["loop_head_rows"] == 4 * tokens * c["loop_built"]
    assert abs(c["loop_exit_depth"] / c["train_steps_read"] - 1875) < 2
    for name in ("moe_local_slots", "moe_absent_slots", "moe_chunk_rows",
                 "moe_max_expert_load", "moe_gmm_built", "bd_built",
                 "attn_window_built", "attn_shared_kv_built"):
        assert c.get(name, 0) == 0, name
    assert c["attn_built"] > 0 \
        and c["attn_pairs_walked"] == c["attn_pairs_causal"] > 0
    assert rehearsal["builds"] == [1, 1]


def test_the_looped_cells_metrics_are_entries_of_the_manifest(real):
    """Entries since PR 67, each found by its name: data files on
    readers that are there, in the one looped cell, each moving
    ``small_msg_us``; the loop's five under a layer of their own; the
    cell's name at the end of the lists SmallThinker's cell is in that
    this step has, and of none of the experts'.  Eight and not the twelve
    the issue lists: ``per_layer`` holds 128 entries at most."""
    names = [m["name"] for m in real["per_layer"]]
    new = ["ouro.mfu", "ouro.flash_mfu", "ouro.attn_bwd_mfu",
           "loop.pass_share", "loop.head_share", "loop.exit_share",
           "loop.cast_share", "loop.applications_per_layer"]
    held_once(real, new)
    assert len(names) <= 128
    by_name = {m["name"]: m for m in real["per_layer"]}
    readers = {}
    for name in new:
        m = by_name[name]
        assert (m["workloads"], m["moves"]) == ([OURO], "small_msg_us")
        twin = by_name.get(name.replace("ouro.", "sdar."))
        if twin and twin is not m:
            assert {k: m[k] for k in ("unit", "better", "source", "layer")} \
                == {k: twin[k] for k in ("unit", "better", "source", "layer")}
        with open(os.path.join(BENCH, "metrics", name + ".json"),
                  encoding="utf-8") as f:
            readers[name] = json.load(f)
        assert readers[name]["reader"] in (
            "trace_kit_flops", "program_counter", "trace_scope_share_wide")
    assert len({by_name[n]["layer"] for n in new[-5:]}) == 1
    cell, config = cell_and_config(real, OURO)
    assert (cell["traffic"], cell["chips"], config["reduced"]) == (
        "packed-4k-looped-steps", 1, ["layers"])
    for m in real["end_to_end"] + real["per_layer"]:
        lists = m.get("workloads", ())
        if m["name"].startswith("moe.") or "local_load" in m["name"]:
            assert OURO not in lists, m["name"]
        elif m["name"].startswith(("compile.", "launch.", "device.")) \
                and "smallthinker-train-1chip" in lists:
            assert OURO in lists, m["name"]
    for name in ("small_msg_us", "step.hbm_peak_share",
                 "attn.pairs_walked_share"):
        (m,) = [m for m in real["end_to_end"] + real["per_layer"]
                if m["name"] == name]
        assert OURO in m["workloads"], name
    assert (readers["loop.applications_per_layer"]["reader"],
            readers["loop.applications_per_layer"]["params"]) == (
        "program_counter", {"name": "loop_layer_applications",
                            "over": "loop_layers_held"})
    assert (readers["ouro.mfu"]["params"]["count"],
            "pattern" in readers["ouro.mfu"]["params"]) == ("step", False)
    for name, count, pattern in (
            ("ouro.flash_mfu", "flash_forward", "^otpu_flash"),
            ("ouro.attn_bwd_mfu", "attn_backward", "^otpu_attn_.*backward")):
        assert (readers[name]["params"]["count"],
                readers[name]["params"]["pattern"]) == (count, pattern)
    from ompi_tpu.runtime import trace

    for name, scopes in (
            ("loop.pass_share", ["otpu_loop_pass"]),
            ("loop.head_share", ["otpu_head"]),
            ("loop.exit_share", ["otpu_exit_gate", "otpu_exit_loss"]),
            ("loop.cast_share", ["otpu_cast"])):
        spec = readers[name]["params"]
        assert spec["scopes"] == scopes and tuple(
            spec["vocabulary"]) == scopes_up_to(
                "otpu_exit_loss", len(spec["vocabulary"]))
        assert set(scopes) <= set(trace.STEP_SCOPES)


@of_cells(GRANITE)
def test_a_padding_free_step_counts_its_documents_and_routes_nothing(
        rehearsal):
    """The trainer's counters on one chip's share of granite-4.0-h-micro
    (layers 4 to 6 here), by the kind that reads everything from the kit
    and with no file of the harness edited for it: the scans, convolutions
    and masks built under a row's documents, the documents and the pairs
    their masks leave of the steps read back (rows of 64 tokens in documents
    of about 4), no slot and no expert's load anywhere, every attention
    pass over the model's own key-value heads; the step's program is the
    one program built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    rows, s = (TINY_GRANITE_TRAIN[k] for k in ("micro_batch", "seq_len"))
    assert row["kind"] == "train_step_kit" and row["tolerance"]["why"]
    assert row["name"] == "train_step.granite.bf16.1x16384"
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    read = c["train_steps_read"]
    assert c["doc_built"] >= 3 and read > 0
    assert c["doc_pairs_causal"] == read * rows * s * (s + 1) // 2
    assert 4 * rows * read < c["doc_starts"] < 40 * rows * read
    assert s * rows * read <= c["doc_pairs_visible"] \
        < 0.5 * c["doc_pairs_causal"]
    for name in ("moe_local_slots", "moe_absent_slots", "moe_chunk_rows",
                 "moe_max_expert_load", "moe_gmm_built", "bd_built",
                 "dsa_built", "attn_window_built", "loop_built"):
        assert c.get(name, 0) == 0, name
    assert c["attn_built"] == c["attn_shared_kv_built"] > 0 \
        and c["attn_pairs_walked"] == c["attn_pairs_causal"] > 0
    assert rehearsal["builds"] == [1, 1]


def test_the_padding_free_cells_entries_are_the_manifests(real):
    """PR 69 brought a configuration, a cell and list entries, and no metric
    (``per_layer`` holds its 128): everything found by name.  The cell's
    name stands at the end of every list it is in: the ones every model
    cell is in, and Nemotron's state-space and whole-step shares, LFM2's
    forward kernel at a head of 64 and the grouped-query counter, whose
    readers take everything from the point's kind and kit."""
    assert len(real["per_layer"]) == 128
    at = [w["name"] for w in real["workloads"]].index(GRANITE)
    cell = real["workloads"][at]
    (config,) = [c for c in real["configs"] if c["name"] == cell["config"]]
    assert (cell["traffic"], cell["chips"], config["reduced"]) == (
        "packed-16k-docs-steps", 1, ["layers", "heads", "vocab"])
    assert config["source"] == "https://huggingface.co/ibm-granite/" \
        "granite-4.0-h-micro/blob/main/config.json"
    # behind every cell that was there before it, whatever came later
    before = [w["name"] for w in real["workloads"]
              if w["name"] not in LATER[LATER.index(GRANITE):]]
    assert [w["name"] for w in real["workloads"]][:at] == before
    listed = {m["name"]: [c for c in m["workloads"]
                          if c not in LATER[LATER.index(GRANITE) + 1:]]
              for m in real["end_to_end"] + real["per_layer"]
              if GRANITE in m.get("workloads", ())}
    assert all(cells[-1] == GRANITE and len(cells) > 1
               for cells in listed.values())
    every = {m["name"] for m in real["end_to_end"] + real["per_layer"]
             if {"nemotron3-train-1chip", "lfm2-train-1chip", OURO}
             <= set(m.get("workloads", ()))}
    assert every < set(listed) and "small_msg_us" in every \
        and "step.hbm_peak_share" in every \
        and {n for n in every if n.startswith("compile.")} >= set(
            BUILD_METRICS)
    assert {n: cells[:-1] for n, cells in listed.items()
            if n not in every} == {
        "ssm.mixer_share": ["nemotron3-train-1chip"],
        "ssm.scan_share": ["nemotron3-train-1chip"],
        "nemo.mfu": ["nemotron3-train-1chip"],
        "nemo.remat_share": ["nemotron3-train-1chip"],
        "nemo.unnamed_share": ["nemotron3-train-1chip"],
        "lfm2.flash_mfu": ["lfm2-train-1chip"],
        "attn.shared_kv_share": [
            "nemotron3-train-1chip", "lfm2-train-1chip",
            "qwen3next-train-1chip", "smallthinker-train-1chip", KEYE, SDAR]}
    for name in listed:
        if name in {m["name"] for m in real["per_layer"]}:
            with open(os.path.join(BENCH, "metrics", name + ".json"),
                      encoding="utf-8") as f:
                spec = json.load(f)
            select = spec.get("params", {}).get("select", {})
            assert select.get("kind", "train_step_kit") in (
                "train_step_kit", ["train_step_kit"]) \
                or "train_step_kit" in select["kind"], name
    assert not [m["name"] for m in real["per_layer"]
                if m["name"].startswith(("moe.", "doc.", "granite."))
                and GRANITE in m.get("workloads", ())]


@of_cells(XING)
def test_a_stream_step_counts_its_sublayers_and_its_sweeps(rehearsal):
    """The trainer's counters on one chip's share of Xing4.0-29B-A4B (a dense
    and two sparse layers here), by the kind that reads everything from the
    kit and with no file of the harness edited for it: six sublayer
    applications on four residual streams of 20 sweeps each, a mixing map
    that 20 sweeps leave a little short of doubly stochastic, 2 sigmoid
    routers of 2 of 8 experts under a bias with 2 held, latent attention's
    plain causal walk over the held heads; the step's program is the one
    program built, in set-up."""
    (row,), c = rehearsal["points"].values(), rehearsal["counters"]
    tokens = TINY_XING_TRAIN["micro_batch"] * TINY_XING_TRAIN["seq_len"]
    assert row["kind"] == "train_step_kit" and row["tolerance"]["why"]
    assert row["name"] == "train_step.xing.bf16.1x4096"
    assert rehearsal["run"]["spc_device_collectives"] == 0
    assert c["train_steps"] > row["k"] * row["windows"]
    assert (c["hc_built"], c["hc_sweeps_built"]) == (6, 120)
    assert 0 < c["hc_defect_ppm"] < 50_000
    assert c["moe_local_slots"] + c["moe_absent_slots"] \
        == c["train_steps_read"] * tokens * 2 * 2
    assert c["moe_gmm_built"] == 6 and c["moe_scatter_built"] == 2 \
        and c["moe_chunk_rows"] >= c["moe_local_slots"] > 0
    assert c["attn_built"] == 3 and c["attn_shared_kv_built"] == 0 \
        and c["attn_pairs_walked"] == c["attn_pairs_causal"] > 0
    for name in ("bd_built", "dsa_built", "attn_window_built", "loop_built",
                 "doc_built"):
        assert c.get(name, 0) == 0, name
    assert rehearsal["builds"] == [1, 1]


def test_the_stream_cells_entries_are_the_manifests(real):
    """PR 73 brought a configuration, a cell and list entries, and no metric
    (``per_layer`` holds its 128; the path's four wait, PERF.md section 7):
    everything found by name.  The cell's name stands at the end of every
    list it is in: the ones every step cell is in, the whole-step shares and
    the two kernels' rates by the kit's counts, and the experts' four, whose
    readers take everything from the point's kind and kit."""
    assert len(real["per_layer"]) == 128
    cell, config = cell_and_config(real, XING)
    assert (cell["traffic"], cell["chips"], config["reduced"]) == (
        "packed-4k-hyper-steps", 1,
        ["layers", "experts", "heads", "vocab", "mtp"])
    assert config["source"] == "https://huggingface.co/XingChen-AGI/" \
        "Xing4.0-29B-A4B/blob/main/config.json"
    names = [w["name"] for w in real["workloads"]]
    assert LATER[-1] == XING and names.index(XING) > names.index(GRANITE)
    listed = {m["name"]: m["workloads"]
              for m in real["end_to_end"] + real["per_layer"]
              if XING in m.get("workloads", ())}
    assert all(cells[-1] == XING and len(cells) > 1
               for cells in listed.values())
    every = {m["name"] for m in real["end_to_end"] + real["per_layer"]
             if {"nemotron3-train-1chip", "lfm2-train-1chip", OURO, GRANITE}
             <= set(m.get("workloads", ()))}
    assert every < set(listed) and "small_msg_us" in every \
        and "step.hbm_peak_share" in every \
        and {n for n in every if n.startswith("compile.")} >= set(
            BUILD_METRICS)
    assert sorted(set(listed) - every) == [
        "keye.attn_bwd_mfu", "lfm2.flash_mfu", "moe.gmm_kernel_share",
        "moe.gmm_share", "moe.live_row_share", "moe.route_share", "nemo.mfu",
        "nemo.remat_share", "nemo.unnamed_share"]
    for name in listed:
        if name in {m["name"] for m in real["per_layer"]}:
            with open(os.path.join(BENCH, "metrics", name + ".json"),
                      encoding="utf-8") as f:
                spec = json.load(f)
            select = spec.get("params", {}).get("select", {})
            assert select.get("kind", "train_step_kit") in (
                "train_step_kit", ["train_step_kit"]) \
                or "train_step_kit" in select["kind"], name
    assert not [m["name"] for m in real["per_layer"]
                if m["name"].startswith(("mla.", "joyai.", "hc.", "xing."))
                and XING in m.get("workloads", ())]
    assert "nemo.tokens_per_s" not in listed    # its amount is 8,192


def test_the_window_cells_metrics_are_entries_of_the_manifest(real):
    """Entries since PR 56, each found by its name: data files on
    readers that are there, in the one cell whose model has a window, each
    moving ``small_msg_us``; the window's three under a layer of their
    own; the cell's name at the end of the lists every step cell is in."""
    cell = "smallthinker-train-1chip"
    new = ["smallthinker.mfu", "smallthinker.tokens_per_s",
           "smallthinker.local_load", "smallthinker.remat_share",
           "smallthinker.unnamed_share", "smallthinker.flash_mfu",
           "smallthinker.attn_bwd_mfu", "swa.operator_share",
           "attn.window_share", "attn.pairs_walked_share"]
    held_once(real, new + [LIVE_ROWS])
    by_name = {m["name"]: m for m in real["per_layer"]}
    for name in new:
        m = by_name[name]
        # the walked pairs are read where a mask spares some, PR 64's cell,
        # and where every pair of the triangle is walked, PR 67's
        assert (m["workloads"], m["moves"]) == (
            [cell, SDAR, OURO] if name == "attn.pairs_walked_share"
            else [cell], "small_msg_us")
        twin = by_name.get(name.replace("smallthinker.", "qwen3next."))
        if twin and twin is not m:
            assert {k: m[k] for k in ("unit", "better", "source", "layer")} \
                == {k: twin[k] for k in ("unit", "better", "source", "layer")}
    assert by_name["smallthinker.attn_bwd_mfu"]["layer"] \
        == by_name["kernel.flash_mfu"]["layer"]
    assert len({by_name[n]["layer"] for n in new[-3:]}) == 1
    held, config = cell_and_config(real, cell)
    assert (held["chips"], config["name"]) == (1, held["config"])
    for m in real["end_to_end"] + real["per_layer"]:
        if "qwen3next-train-1chip" in m.get("workloads", ()) \
                and len(m["workloads"]) > 1:
            assert [c for c in m["workloads"]
                    if c not in LATER][-1] == cell, m["name"]
    for name, params in (
            ("attn.window_share", {"name": "attn_window_built",
                                   "over": "attn_built", "scale": 100}),
            ("attn.pairs_walked_share", {"name": "attn_pairs_walked",
                                         "over": "attn_pairs_causal",
                                         "scale": 100})):
        with open(os.path.join(BENCH, "metrics", name + ".json"),
                  encoding="utf-8") as f:
            spec = json.load(f)
        assert (spec["reader"], spec["params"]) == ("program_counter",
                                                    params)


@of_cells("olmoe-train-1chip", *SHARE_CELLS)
def test_the_loops_rows_are_counted_where_a_share_is_held(rehearsal):
    """SPC ``moe_chunk_rows``: over the steps read back, every layer's
    held slots in whole chunks, so never less than the slots and less
    than a chunk a loop more; ``moe.live_row_share`` reads the one over
    the other through its file and ``program_counter``.  OLMoE's step
    holds every expert and has no loop: nothing is counted and the
    reader finds nothing."""
    c, share = rehearsal["counters"], rehearsal["layer"][LIVE_ROWS]
    if rehearsal["cell"] not in SHARE_CELLS:
        assert c["moe_chunk_rows"] == c["moe_local_slots"] == 0
        assert share is None
        return
    assert c["moe_chunk_rows"] >= c["moe_local_slots"] > 0
    assert c["moe_chunk_rows"] % 16 == 0
    # the reader read while the run still held its steps, the counters
    # are the run's last: both are a share of whole chunks
    assert 0 < share <= 100
    assert 0 < 100 * c["moe_local_slots"] / c["moe_chunk_rows"] <= 100


def test_the_live_row_share_is_an_entry_of_the_manifest(real):
    """Appended behind everything that was there (PR 57): a data file on
    ``program_counter`` under the expert block's layer, in the five
    cells whose rank holds a share of the experts and not in OLMoE's."""
    (m,) = [x for x in real["per_layer"] if x["name"] == LIVE_ROWS]
    twin = {x["name"]: x for x in real["per_layer"]}["moe.gmm_kernel_share"]
    assert m == {"name": LIVE_ROWS, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": twin["layer"],
                 "moves": "small_msg_us", "workloads": list(SHARE_CELLS)}
    assert "olmoe-train-1chip" in twin["workloads"]
    with open(os.path.join(BENCH, "metrics", LIVE_ROWS + ".json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    assert (spec["reader"], spec["params"]) == ("program_counter", {
        "name": "moe_local_slots", "over": "moe_chunk_rows", "scale": 100})


def test_the_route_share_is_an_entry_of_the_manifest(real):
    """Appended behind everything that was there (PR 59): a data file on
    ``trace_scope_share_wide`` under the expert block's layer, in the six
    cells whose rank holds a share of the experts and not in OLMoE's; it
    selects both kinds of such a cell's point through its file, reads
    the router's and the dispatch's scopes, and shares the run's one wide
    reduction with ``dsa.select_share`` (one vocabulary: the program's
    ``STEP_SCOPES`` behind ``scopes.json``'s, as far as PR 58 brought it:
    the three names of PR 64 stand behind it, in that PR's own files)."""
    from ompi_tpu.runtime import trace

    # found by name: later PRs' entries stand behind it
    assert {x["name"]: x for x in real["per_layer"]}[ROUTE_SHARE] == {
        "name": ROUTE_SHARE, "unit": "%", "better": "lower",
        "source": "device_trace",
        "layer": {x["name"]: x for x in real["per_layer"]}[LIVE_ROWS][
            "layer"],
        "moves": "small_msg_us", "workloads": list(SHARE_CELLS)}

    def spec_of(name):
        with open(os.path.join(BENCH, "metrics", name + ".json"),
                  encoding="utf-8") as f:
            return json.load(f)

    spec = spec_of(ROUTE_SHARE)
    assert spec["reader"] == "trace_scope_share_wide"
    assert spec["params"]["scopes"] == ["otpu_router", "otpu_dispatch"]
    assert spec["params"]["vocabulary"] == spec_of("dsa.select_share")[
        "params"]["vocabulary"]
    with open(os.path.join(BENCH, "harness", "scopes.json"),
              encoding="utf-8") as f:
        base = json.load(f)["scopes"]
    assert tuple(base + spec["params"]["vocabulary"]) \
        == scopes_up_to("otpu_dsa_loss")
    kinds = set()
    for cell in real["workloads"]:
        with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"),
                  encoding="utf-8") as f:
            points = json.load(f).get("points", [])
        if cell["name"] in SHARE_CELLS:
            kinds |= {p["kind"] for p in points}
        elif cell["name"] not in (OURO, GRANITE):   # a kit cell in which
            #                             nothing routes:
            #                             the metric does not list it
            assert not {p["kind"] for p in points} & set(
                spec["params"]["select"]["kind"]), cell["name"]
    assert kinds == set(spec["params"]["select"]["kind"])


def test_train_check_tells_the_program_from_its_control(tmp_path):
    """``benchmark/tools/train_check.py`` at the rehearsal's widths: one
    step of the program lies within the kind's tolerance of the
    benchmark's reference at every compared position; the reference
    computed in bfloat16 lies far outside the program's, and each
    float32 part as bfloat16 would have made it outside the
    tolerance."""
    env = _stage("olmoe-train-1chip", str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "train_check.py"),
         "--platform", "cpu", "--root", str(tmp_path), "--seeds", "2",
         "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
            if ln.startswith("seed ")]
    assert len(rows) == 2
    for row in rows:
        assert row["program"]["outside"] == 0
        assert row["program"]["widest_units"] < 0.01
        assert row["program"]["param_dev_in_lr"] < 0.01
        # at 64 tokens the losses are half the size and the tolerance,
        # set at 8,192, is not tight: the control lies a hundred times
        # farther out than the program, and outside on the chip
        assert row["control_bf16"]["widest_units"] > 0.5
        assert row["control_bf16"]["widest_units"] \
            > 100 * row["program"]["widest_units"]
        assert all(u > 1 for u in
                   row["control_parts"]["units_by_group"].values())


def test_share_check_tells_the_program_from_its_controls(tmp_path):
    """``benchmark/tools/share_check.py`` at the rehearsal's widths: one
    step of the program, after a few steps have moved the balancing
    biases, lies within the kind's tolerance of the kit's reference; the
    reference computed in bfloat16 lies far outside the program's, and a
    bfloat16 router and a softmax in the sigmoid's place outside the
    tolerance (the bias in the weights only as far as four steps of
    gamma = 0.001 move a weight of order 0.6: seen on the chip after a
    run's sixty)."""
    env = _stage("joyai-train-1chip", str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "share_check.py"),
         "--platform", "cpu", "--root", str(tmp_path), "--seeds", "2",
         "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
            if ln.startswith("seed ")]
    assert len(rows) == 2
    for row in rows:
        assert row["program"]["widest_units"] < 0.05
        assert row["control_bf16"]["widest_units"] \
            > 100 * row["program"]["widest_units"]
        assert row["parts_bf16"]["widest_units"] > 1
        assert row["parts_softmax"]["widest_units"] > 1
        assert row["parts_bias_in_weights"]["widest_units"] \
            > 10 * row["program"]["units_by_group"]["router_weights"]


def test_kit_check_tells_the_hybrid_program_from_its_controls(tmp_path):
    """``benchmark/tools/kit_check.py`` on the hybrid cell at the
    rehearsal's widths: one step of the program, after a few steps have
    moved the balancing biases, lies within the kind's tolerance of the
    kit's reference (the state-space layer one position at a time); the
    reference computed in bfloat16 lies far outside the program's, and a
    bfloat16 router, a scan whose decay and state are held in bfloat16
    and a softmax in the sigmoid's place outside the tolerance (the bias
    in the weights only as far as four steps of gamma move a weight)."""
    env = _stage("nemotron3-train-1chip", str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "kit_check.py"),
         "--workload", "nemotron3-train-1chip", "--platform", "cpu",
         "--root", str(tmp_path), "--seeds", "2", "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
            if ln.startswith("seed ")]
    (summary,) = [json.loads(ln[8:]) for ln in done.stdout.splitlines()
                  if ln.startswith("summary ")]
    assert len(rows) == 2
    for row in rows:
        assert row["program"]["widest_units"] < 0.05
        assert row["control_bf16"]["widest_units"] \
            > 100 * row["program"]["widest_units"]
        assert row["parts_bf16"]["widest_units"] > 1
        assert row["parts_scan_bf16"]["units_by_group"]["ssm_y"] > 1
        assert row["parts_softmax"]["widest_units"] > 1
        assert row["parts_bias_in_weights"]["widest_units"] \
            > 10 * row["program"]["units_by_group"]["router_weights"]
    assert summary["program"] == max(r["program"]["widest_units"]
                                     for r in rows)
    assert summary["parts_scan_bf16"] == min(
        r["parts_scan_bf16"]["widest_units"] for r in rows)


def test_kit_check_tells_the_typed_program_from_its_controls(tmp_path):
    """``benchmark/tools/kit_check.py`` on LFM2's cell at the rehearsal's
    widths, with no file of the harness edited for it: one step of the
    program lies within the kind's tolerance of ``lfm2kit``'s reference;
    the reference computed in bfloat16 lies far outside the program's,
    and each of the kit's six controls of a part outside the tolerance
    at that part: a bfloat16 router and head, gates and taps in
    bfloat16, the bias in the weights, a softmax in the sigmoid's place,
    an untied head, RoPE left out."""
    env = _stage("lfm2-train-1chip", str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "kit_check.py"),
         "--workload", "lfm2-train-1chip", "--platform", "cpu",
         "--root", str(tmp_path), "--seeds", "2", "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
            if ln.startswith("seed ")]
    (summary,) = [json.loads(ln[8:]) for ln in done.stdout.splitlines()
                  if ln.startswith("summary ")]
    assert len(rows) == 2
    for row in rows:
        assert row["program"]["widest_units"] < 0.05
        assert row["control_bf16"]["widest_units"] \
            > 100 * row["program"]["widest_units"]
        for variant, part in (("bf16", "router_logits"),
                              ("conv_bf16", "conv_y"),
                              ("softmax", "router_scores"),
                              ("untied", "head_rows"),
                              ("no_rope", "rope_qk")):
            assert row["parts_" + variant]["units_by_group"][part] > 1, \
                variant
        assert row["parts_bias_in_weights"]["widest_units"] \
            > 10 * row["program"]["units_by_group"]["router_weights"]
    assert summary["program"] == max(r["program"]["widest_units"]
                                     for r in rows)
    assert summary["parts_conv_bf16"] == min(
        r["parts_conv_bf16"]["widest_units"] for r in rows)


def test_kit_check_tells_the_delta_rule_program_from_its_controls(tmp_path):
    """``benchmark/tools/kit_check.py`` on Qwen3-Next's cell at the
    rehearsal's widths, with no file of the harness edited for it: one
    step of the program lies within the kind's tolerance of
    ``qwen3nextkit``'s reference (the delta rule one position at a time);
    the reference computed in bfloat16 lies far outside the program's,
    and each of the kit's eight controls of a part outside the tolerance
    at that part: a bfloat16 router and head, the rule's decay and states
    in bfloat16, beta left at 1, the decay left out, the attention gate
    left out, RoPE over the whole head, a sigmoid in the softmax's place,
    the shared expert's gate left out."""
    env = _stage("qwen3next-train-1chip", str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "kit_check.py"),
         "--workload", "qwen3next-train-1chip", "--platform", "cpu",
         "--root", str(tmp_path), "--seeds", "2", "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
            if ln.startswith("seed ")]
    (summary,) = [json.loads(ln[8:]) for ln in done.stdout.splitlines()
                  if ln.startswith("summary ")]
    assert len(rows) == 2
    for row in rows:
        assert row["program"]["widest_units"] < 0.05
        assert row["control_bf16"]["widest_units"] \
            > 100 * row["program"]["widest_units"]
        for variant, part in (("bf16", "router_logits"),
                              ("rule_bf16", "gdn_o"), ("beta_one", "gdn_o"),
                              ("no_decay", "gdn_o"),
                              ("no_gate", "attn_gated"),
                              ("rope_whole", "rope_qk"),
                              ("sigmoid", "router_scores"),
                              ("no_shared_gate", "shared_gate")):
            assert row["parts_" + variant]["units_by_group"][part] > 1, \
                variant
    assert summary["program"] == max(r["program"]["widest_units"]
                                     for r in rows)
    assert summary["parts_rule_bf16"] == min(
        r["parts_rule_bf16"]["widest_units"] for r in rows)


def test_kit_check_tells_the_sparse_program_from_its_controls(tmp_path):
    """``benchmark/tools/kit_check.py`` on Keye's cell at the rehearsal's
    widths, with no file of the harness edited for it: one step of the
    program lies within the kind's tolerance of ``keyekit``'s reference
    under the step's own routing and selection; the reference in bfloat16
    lies far outside the program's, and each of the kit's eight controls
    outside the tolerance where it bites: a bfloat16 router and head, the
    selection left out, its better half alone, the indexer without relu, q
    and k without their head norms, the alignment loss left out, the
    indexer's input attached, pbar attached."""
    env = _stage(KEYE, str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "kit_check.py"),
         "--workload", KEYE, "--platform", "cpu",
         "--root", str(tmp_path), "--seeds", "1", "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    (row,) = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
              if ln.startswith("seed ")]
    assert "selection: least share" in done.stdout
    assert row["program"]["widest_units"] < 0.05
    assert row["control_bf16"]["widest_units"] \
        > 100 * row["program"]["widest_units"]
    for variant, part in (("bf16", "head_rows"),
                          ("no_selection", "select_o"),
                          ("top_half", "select_o"),
                          ("no_relu", "index_rows"),
                          ("no_head_norm", "rope_qk"),
                          ("no_index_loss", "losses"),
                          ("pbar_attached", "grad_probe")):
        assert row["parts_" + variant]["units_by_group"][part] > 1, variant
    # the indexer's input attached sends the alignment loss's gradient into
    # the stream: from matrices of 0.02 at these widths a tenth of what it
    # is at the published ones, where the unit is set; told from the
    # program's here
    attached = row["parts_hi_attached"]["units_by_group"]["grad_probe"]
    assert attached > 0.1 and attached > 50 * row["program"][
        "units_by_group"]["grad_probe"]


def test_kit_check_tells_the_block_diffusion_program_from_its_controls(
        tmp_path):
    """``benchmark/tools/kit_check.py`` on SDAR's cell at the rehearsal's
    widths, with no file of the harness edited for it: one step of the
    program lies within the kind's tolerance of ``sdarkit``'s reference
    under the step's own routing, **the noise drawn again by the kit equal
    to the step's bit for bit**; the reference in bfloat16 lies far outside
    the program's, and each of the kit's five controls outside the
    tolerance where it bites: a bfloat16 router and head, a plain causal
    mask over the 2L rows, a noisy row that sees its own block's clean
    copy, masked rows drawn at a fixed rate of one half, a loss without the
    weight."""
    env = _stage(SDAR, str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "kit_check.py"),
         "--workload", SDAR, "--platform", "cpu",
         "--root", str(tmp_path), "--seeds", "2", "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
            if ln.startswith("seed ")]
    assert len(rows) == 2
    for row in rows:
        assert row["program"]["widest_units"] < 0.05
        assert row["program"]["units_by_group"]["noise"] == 0.0
        assert row["control_bf16"]["widest_units"] \
            > 100 * row["program"]["widest_units"]
        for variant, part in (("bf16", "head_rows"), ("causal", "bd_o"),
                              ("leak", "bd_o"), ("half_rate", "noise"),
                              ("half_rate", "bd_weights"),
                              ("unweighted", "losses"),
                              ("unweighted", "grad_probe")):
            assert row["parts_" + variant]["units_by_group"][part] > 1, \
                variant


def test_kit_check_tells_the_looped_program_from_its_controls(tmp_path):
    """``benchmark/tools/kit_check.py`` on Ouro's cell at the rehearsal's
    widths, with no file of the harness edited for it (a step that routes
    nothing gives its row's load as not a number): one step of the program
    lies within the kind's tolerance of ``ourokit``'s reference; the
    reference in bfloat16 lies far outside the program's, and each of the
    kit's controls outside the tolerance where it bites: a bfloat16 head,
    one pass in place of four, the pass's norm left out between the passes,
    the last pass's loss alone, the exit distribution held at a quarter
    each (the gate's gradient then zero), a layer without its second
    norms."""
    env = _stage(OURO, str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "kit_check.py"),
         "--workload", OURO, "--platform", "cpu",
         "--root", str(tmp_path), "--seeds", "1", "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    (row,) = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
              if ln.startswith("seed ")]
    assert row["program"]["widest_units"] < 0.05
    assert row["local_load"] != row["local_load"]       # nothing routes
    assert row["control_bf16"]["widest_units"] \
        > 100 * row["program"]["widest_units"]
    for variant, part in (("bf16", "head_rows"), ("one_pass", "lse_means"),
                          ("no_pass_norm", "grad_probe"),
                          ("last_pass_loss", "losses"),
                          ("uniform_exit", "exit_mean"),
                          ("uniform_exit", "grad_log_rms"),
                          ("no_post_norm", "grad_probe")):
        assert row["parts_" + variant]["units_by_group"][part] > 1, variant


def test_kit_check_tells_the_padding_free_program_from_its_controls(
        tmp_path):
    """``benchmark/tools/kit_check.py`` on granite-4.0-h-micro's cell at the
    rehearsal's widths, with no file of the harness edited for it: one step
    of the program lies within the kind's tolerance of ``granitekit``'s
    reference; the reference in bfloat16 lies far outside the program's, and
    each of the kit's controls outside the tolerance where it bites: a
    bfloat16 head, the scan's state in bfloat16, the scan and the whole
    model without the scan's resets, the convolution read across a
    document's start, attention under the triangle alone, the scale 1 /
    sqrt(head), a residual multiplier of one."""
    env = _stage(GRANITE, str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "kit_check.py"),
         "--workload", GRANITE, "--platform", "cpu",
         "--root", str(tmp_path), "--seeds", "1", "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    (row,) = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
              if ln.startswith("seed ")]
    assert row["program"]["widest_units"] < 0.05
    assert row["local_load"] != row["local_load"]       # nothing routes
    assert row["control_bf16"]["widest_units"] \
        > 100 * row["program"]["widest_units"]
    for variant, part in (("bf16", "head_rows"), ("scan_bf16", "ssm_y"),
                          ("scan_no_reset", "ssm_y"),
                          ("no_scan_reset", "grad_log_rms"),
                          ("no_conv_reset", "conv_x"),
                          ("no_doc_mask", "grad_probe"),
                          ("sqrt_scale", "grad_log_rms"),
                          ("residual_one", "grad_log_rms")):
        assert row["parts_" + variant]["units_by_group"][part] > 1, variant


def test_kit_check_tells_the_window_program_from_its_controls(tmp_path):
    """``benchmark/tools/kit_check.py`` on SmallThinker's cell at the
    rehearsal's widths, with no file of the harness edited for it: one
    step of the program lies within the kind's tolerance of
    ``smallthinkerkit``'s reference (dense masked softmax, the window as
    its inequality); the reference computed in bfloat16 lies far outside
    the program's, and each of the kit's seven controls of a part outside
    the tolerance at that part: a bfloat16 router and head, the window
    left out, RoPE on the full layer, RoPE left off a window layer, the
    router reading the post-attention stream, silu in relu's place, the
    chosen weights left unnormalised."""
    env = _stage("smallthinker-train-1chip", str(tmp_path))
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "kit_check.py"),
         "--workload", "smallthinker-train-1chip", "--platform", "cpu",
         "--root", str(tmp_path), "--seeds", "2", "--base", "2147483990"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    rows = [json.loads(ln[5:]) for ln in done.stdout.splitlines()
            if ln.startswith("seed ")]
    (summary,) = [json.loads(ln[8:]) for ln in done.stdout.splitlines()
                  if ln.startswith("summary ")]
    assert len(rows) == 2
    for row in rows:
        assert row["program"]["widest_units"] < 0.05
        assert row["control_bf16"]["widest_units"] \
            > 100 * row["program"]["widest_units"]
        for variant, part in (("bf16", "head_rows"),
                              ("no_window", "window_o"),
                              ("rope_full", "rope_qk"),
                              ("no_rope_window", "rope_qk"),
                              ("router_post", "router_logits"),
                              ("unnormalised", "router_weights")):
            assert row["parts_" + variant]["units_by_group"][part] > 1, \
                variant
        # an expert 24 wide from matrices of 0.02 makes a hundredth of what
        # one 768 wide does, and the part's unit is set at the published
        # widths (silu reads 25 there): here it is told from the program's
        silu = row["parts_silu"]["units_by_group"]["expert_out"]
        assert silu > 0.2 and silu > 100 * row["program"][
            "units_by_group"]["expert_out"]
    assert summary["program"] == max(r["program"]["widest_units"]
                                     for r in rows)
    assert summary["parts_no_window"] == min(
        r["parts_no_window"]["widest_units"] for r in rows)


def test_a_share_of_the_busy_seconds_counts_no_loop_twice(tmp_path,
                                                          monkeypatch):
    """``trace_op_busy_share``: the matching leaf ops' seconds over the
    point's device-busy seconds, whatever ``while`` ops the table also
    lists; nothing to read without a match or a trace."""
    sys.path.insert(0, BENCH)
    try:
        from harness import hostspans, protocol
        reader = protocol.load_module("readers", "trace_op_busy_share", BENCH)
        monkeypatch.setattr(hostspans, "write_table", lambda *a: None)
        ops = {"while.3 s32[]": 6.0, "otpu_flash_causal_forward.2": 1.0,
               "otpu_flash_causal_forward.5": 0.5, "fusion.9 f32[8]": 4.5}
        ctx = {"points": [{"name": "p", "kind": "train_step_share"}],
               "trace": {"points": {"p": {"ops": ops, "busy_s": 7.5}}}}
        params = {"pattern": "^otpu_flash", "table": "t",
                  "select": {"kind": "train_step_share"}}
        assert reader.read(ctx, params) == pytest.approx(20.0)
        assert reader.read(ctx, {**params, "pattern": "^ragged"}) is None
        assert reader.read({**ctx, "trace": None}, params) is None
    finally:
        sys.path.remove(BENCH)
