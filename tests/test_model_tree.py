"""The parameter tree where it is an interface: the benchmark's kits find
a leaf's probe positions and its draw by its name, and a checkpoint's
reader by its path.  For each of the step cells' configuration files the
names, their order and the shapes at the published widths, and the initial
values of a tiny cut, are held to digests taken on the tree of PR 59
(commit d0e779b, before the kinds of layer were declared in one table; a
later model's on the tree of the PR that brought it), so that a refactor of
the tree's makers is checked here and not on the chip.
"""
import hashlib
import json
import os

import numpy as np
import pytest

from ompi_tpu.parallel import train

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
SMALL = dict(seq_len=32, micro_batch=1, attn_block=16, loss_block_rows=16)

#: file -> (a tiny cut of it, the digest of the full tree's names, order
#: and shapes, the digest of the cut's initial values from seed 3)
TREES = {
    "olmoe-1b-7b-train-1chip.json": (
        dict(hidden_size=64, intermediate_size=32, num_attention_heads=4,
             num_key_value_heads=4, num_experts=8, num_experts_per_tok=2,
             vocab_size=256, layers_here=2),
        "d114ae721afa755e", "3210e5632133e9ca"),
    "joyai-flash-train-1chip.json": (
        dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
             num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
             vocab_size=512, vocab_here=64, experts_here=4, layers_here=3),
        "db0dacb73db86109", "3b24788a3332874b"),
    "nemotron3-super-train-1chip.json": (
        dict(hidden_size=64, intermediate_size=24, head_dim=4,
             num_attention_heads=16, num_key_value_heads=2,
             mamba_num_heads=16, mamba_head_dim=8, n_groups=8,
             ssm_state_size=8, chunk_size=8, moe_latent_size=16,
             moe_intermediate_size=24,
             moe_shared_expert_intermediate_size=48, num_experts=32,
             num_experts_per_tok=3, vocab_size=512, vocab_here=64,
             experts_here=8, heads_here=4, mamba_heads_here=4,
             first_layer_here=31, layers_here=7),
        "cc0ece8d7eafdc20", "6dd8af640681527d"),
    "lfm2-8b-a1b-train-1chip.json": (
        dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
             num_key_value_heads=2, moe_intermediate_size=24, num_experts=8,
             num_experts_per_tok=2, vocab_size=256, vocab_here=64,
             experts_here=2),
        "1886be0cfc38a036", "c48e912164e92e10"),
    "qwen3-next-80b-a3b-train-1chip.json": (
        dict(hidden_size=64, intermediate_size=96, head_dim=32,
             num_attention_heads=4, num_key_value_heads=1,
             linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=16, linear_value_head_dim=16,
             moe_intermediate_size=24,
             moe_shared_expert_intermediate_size=24, num_experts=16,
             num_experts_per_tok=4, vocab_size=256, vocab_here=64,
             experts_here=4, chunk_size=8),
        "73615e8dac3652fc", "5c604ced7fa2ab7a"),
    "smallthinker-21b-a3b-train-1chip.json": (
        dict(hidden_size=64, head_dim=16, num_attention_heads=8,
             num_key_value_heads=2, moe_intermediate_size=24, num_experts=16,
             num_experts_per_tok=3, sliding_window=16, vocab_size=256,
             vocab_here=64, experts_here=4),
        "46552449519ce22e", "1ca12155a3bda8c9"),
    "keye-vl2-30b-a3b-train-1chip.json": (
        dict(hidden_size=64, head_dim=16, num_attention_heads=8,
             num_key_value_heads=2, moe_intermediate_size=24, num_experts=16,
             num_experts_per_tok=3, vocab_size=256, vocab_here=64,
             experts_here=4, index_heads=4, index_head_dim=8, index_topk=24,
             index_q_chunk=16, index_kv_chunk=16),
        "98ae55b966aecc18", "304bc0dbfb628c4f"),
    # PR 64's own tree, pinned as it was brought
    "sdar-30b-a3b-train-1chip.json": (
        dict(hidden_size=64, head_dim=16, num_attention_heads=8,
             num_key_value_heads=2, moe_intermediate_size=24, num_experts=16,
             num_experts_per_tok=3, vocab_size=256, vocab_here=64,
             experts_here=4, mask_token_here=63),
        "f44cc625a1563a7e", "6690f312d2e32ef4"),
    # PR 67's own tree, pinned as it was brought: each layer's leaves once
    # (a second gain behind each sublayer), the exit gate behind the head
    "ouro-2.6b-train-1chip.json": (
        dict(hidden_size=64, head_dim=16, num_attention_heads=4,
             num_key_value_heads=4, intermediate_size=96, vocab_size=256,
             layers_here=2),
        "6bf59e92c7fae8cb", "934f087bce1c92b6"),
    # PR 69's own tree, pinned as it was brought: a Mamba-2 mixer or
    # attention, then a dense SwiGLU, on a share of each mixer's heads; the
    # tied matrix once
    "granite-4.0-h-micro-train-1chip.json": (
        dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             heads_here=2, mamba_n_heads=4, mamba_d_head=32,
             mamba_heads_here=2, mamba_d_state=16, mamba_chunk_size=8,
             shared_intermediate_size=96, vocab_size=256, vocab_here=64,
             eos_token_here=63, layers_here=3, first_layer_here=4),
        "7bb1f947f7dfc79b", "8250c5af4151712d"),
    # PR 73's own tree, pinned as it was brought: latent attention over a
    # share of its heads, one of two leading dense layers, and around each
    # sublayer the residual path's three leaves (``hc1_*``, ``hc2_*``)
    "xing4.0-29b-a4b-train-1chip.json": (
        dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
             num_key_value_heads=4, heads_here=2, q_lora_rank=32,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, moe_intermediate_size=32, num_experts=8,
             num_experts_per_tok=2, vocab_size=512, vocab_here=64,
             experts_here=2, layers_here=3, hc_gate_start=1.0,
             hc_offset_std=1.0, hc_res_diag=2.0),
        "59211c710b2c4185", "c511356b9b705cd6"),
}


def tree_digest(cfg) -> str:
    """Of every leaf's name, path and shape, in ``leaf_names``' order."""
    shapes = train.model_param_shapes(cfg)
    rows = [[name, list(path), list(train._leaf(shapes, path))]
            for name, path in train.leaf_names(cfg)]
    return hashlib.sha256(json.dumps(rows).encode()).hexdigest()[:16]


def init_digest(cfg, seed: int = 3) -> str:
    """Of every leaf's initial bytes, in ``leaf_names``' order."""
    params, digest = train.init_model_params(cfg, seed), hashlib.sha256()
    for name, path in train.leaf_names(cfg):
        leaf = np.asarray(train._leaf(params, path))
        assert leaf.dtype == np.float32, name
        digest.update(name.encode() + leaf.tobytes())
    return digest.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(TREES))
def test_the_tree_is_what_it_was_at_pr_59(name):
    tiny, tree, init = TREES[name]
    path = os.path.join(CONFIGS, name)
    assert tree_digest(train.load_model_config(path)) == tree
    assert init_digest(train.load_model_config(path, **SMALL, **tiny)) == init


def test_the_seven_cells_files_are_the_ones_pinned():
    assert sorted(TREES) == sorted(
        f for f in os.listdir(CONFIGS) if f.endswith("-train-1chip.json"))


if __name__ == "__main__":        # the digests, for a tree that moves them
    for name, (tiny, _, _) in sorted(TREES.items()):
        path = os.path.join(CONFIGS, name)
        print(name, tree_digest(train.load_model_config(path)),
              init_digest(train.load_model_config(path, **SMALL, **tiny)))
