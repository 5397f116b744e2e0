"""A configuration's ``start`` group (``kinds/train_step_kit.start_of``):
the tree a cell runs from is pinned here, at a tiny cut, because
``tests/test_model_tree.py`` pins the tree the file's ``train`` group
alone gives and a ``benchmark`` PR may not move that pin (PERF.md 7)."""
import hashlib
import os

import numpy as np

from ompi_tpu.parallel import train

from harness import manifest

CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "configs", "smallthinker-21b-a3b-train-1chip.json")
# tests/test_model_tree.py's cut of this file and its digest of that cut's
# initial values from seed 3, taken on the tree of PR 59
TINY = dict(seq_len=32, micro_batch=1, attn_block=16, loss_block_rows=16,
            hidden_size=64, head_dim=16, num_attention_heads=8,
            num_key_value_heads=2, moe_intermediate_size=24, num_experts=16,
            num_experts_per_tok=3, sliding_window=16, vocab_size=256,
            vocab_here=64, experts_here=4)
AT_PR_59, AS_RUN = "1ca12155a3bda8c9", "eb2921d58328c0a7"


def _tree(**start):
    cfg = train.load_model_config(CONFIG, **TINY, **start)
    params = train.init_model_params(cfg, 3)
    return cfg, {name: np.asarray(train._leaf(params, path))
                 for name, path in train.leaf_names(cfg)}


def _digest(leaves) -> str:
    digest = hashlib.sha256()
    for name, leaf in leaves.items():
        digest.update(name.encode() + leaf.tobytes())
    return digest.hexdigest()[:16]


def test_the_start_group_moves_the_embedding_and_no_other_leaf():
    start = manifest.load_json(CONFIG)["start"]
    assert list(start) == ["embed_init_std"]
    (plain, old), (cfg, new) = _tree(), _tree(**start)
    assert _digest(old) == AT_PR_59
    assert _digest(new) == AS_RUN
    for name in old:
        if name != "embed":
            assert old[name].tobytes() == new[name].tobytes(), name
    # the same draw from the same key, at the rows' own width
    np.testing.assert_allclose(
        new["embed"], old["embed"] * (start["embed_init_std"]
                                      / plain.init_std), rtol=1e-6)
    assert (cfg.init_std, cfg.embed_init_std) == (plain.init_std, 2.0)


def test_the_kind_hands_the_start_group_to_the_loader(monkeypatch):
    from harness import protocol
    kit = protocol.load_module("kinds", "train_step_kit", os.path.dirname(
        os.path.dirname(CONFIG)))
    monkeypatch.setattr(kit, "config_path", lambda point: CONFIG)
    assert kit.start_of({}) == {"embed_init_std": 2.0}
    other = CONFIG.replace("smallthinker-21b-a3b", "keye-vl2-30b-a3b")
    monkeypatch.setattr(kit, "config_path", lambda point: other)
    assert kit.start_of({}) == {}     # Keye's file gives it in ``train``
