"""``parallel/model.py``'s table of the kinds of sublayer: every entry is
whole (its scope is in the vocabulary, its function runs on exactly the
leaves its shapes name and reports exactly what it says it reports, the
leaves it starts or leaves undecayed are its own), every name and letter
the seven cells' configuration files use resolves to one, and
``decoder_layer`` looks its sublayers up and asks the leaves nothing.
"""
import ast
import inspect
import os

import jax
import jax.numpy as jnp
import pytest

from test_model_tree import CONFIGS, SMALL, TREES

from ompi_tpu.parallel import config, experts, model, objective, train
from ompi_tpu.runtime import trace


def tiny(name):
    return config.load_model_config(
        os.path.join(CONFIGS, name), compute_dtype="float32", **SMALL,
        **TREES[name][0])


def held():
    """Each entry of the table with the first of the seven files' tiny cuts
    that holds it."""
    out = {}
    for name in sorted(TREES):
        cfg = tiny(name)
        for kind in model.kinds_here(cfg):
            for part in kind.parts:
                out.setdefault(part, cfg)
    return out


HELD = held()


def test_every_entry_is_held_by_a_cell():
    assert set(HELD) == set(model.SUBLAYERS)


@pytest.mark.parametrize(
    "entry", model.SUBLAYERS,
    ids=[f"{e.group or e.run.__name__}-{e.name or e.scope}"
         for e in model.SUBLAYERS])
def test_an_entry_is_whole(entry):
    cfg = HELD[entry]
    assert entry.scope in trace.STEP_SCOPES
    shapes = entry.shapes(cfg)
    # what it starts is its own; of its leaves those that any entry leaves
    # undecayed (``is_decayed`` goes by a leaf's last name) it names itself
    assert set(entry.starts) <= set(shapes)
    assert {k for k in shapes if k in model.UNDECAYED} \
        == set(entry.undecayed) & set(shapes)
    assert all(len(shapes[k]) == 1 for k in set(entry.undecayed) & set(shapes))
    assert set(entry.keeps) <= set(model.CHECKPOINT_KEEPS)
    keys = jax.random.split(jax.random.PRNGKey(0), len(shapes) + 1)
    p = {k: 0.1 * jax.random.normal(key, shape)
         for key, (k, shape) in zip(keys, shapes.items())}
    x = jax.random.normal(keys[-1], (1, cfg.seq_len, cfg.hidden_size))
    at = objective.sample_rows(cfg.seq_len)
    if entry in model.OPERATORS:
        y, stats, rows = entry.run(p, x, cfg, interpret=True, at=at)
    else:
        flat = x.reshape(-1, x.shape[-1])
        routed = (flat, experts.router_logits(p, flat)) \
            if cfg.router_before_attention and "router" in shapes else None
        bias = jnp.zeros(cfg.num_experts) \
            if cfg.topk_method == "noaux_tc" else None
        y, stats, rows = entry.run(p, x, cfg, bias, interpret=True,
                                   routed=routed)
        rows.pop("experts", None)       # the choice, beside the sample
    assert y.shape == x.shape and y.dtype == jnp.float32
    reports = entry.reports(cfg)
    assert set(rows) == set(reports)
    for key, value in rows.items():
        assert value.ndim == 1 + reports[key] or key.endswith("_seq"), key


@pytest.mark.parametrize("name", sorted(TREES))
def test_a_cells_layers_resolve(name):
    """Every ``layer_types`` name and pattern letter of the file is an
    entry's, the kinds they make are the tree's groups, and what the walked
    layers report is what a step's ``sample`` is laid out for."""
    cfg = config.load_model_config(os.path.join(CONFIGS, name))
    assert set(cfg.layer_types) | set(cfg.hybrid_override_pattern) \
        <= set(model.NAMED)
    kinds = model.layer_kinds(cfg)
    assert all(kind.name == key for key, kind in kinds.items())
    walked = model.kinds_here(cfg)
    assert len(walked) == cfg.layers_here \
        and sum(k.routes for k in walked) == cfg.n_sparse_here
    if cfg.pattern_here:
        assert "".join(k.letter for k in walked) == cfg.pattern_here
        groups = {g for run in train.model_param_shapes(cfg)["layers"].values()
                  for g in run}
        assert groups == {k.name for k in walked}
    assert set(train.pattern_layer_shapes(cfg)) == set(kinds)
    # ``init_model_params`` goes by a leaf's last name: no two sublayers of
    # one model start a leaf of one name differently
    starts = [(leaf, start) for kind in kinds.values() for part in kind.parts
              for leaf, start in part.starts.items()]
    assert len(dict(starts)) == len(set(starts))


def test_decoder_layer_asks_the_leaves_nothing():
    """No ``in p`` (nor any other membership test) is left in
    ``decoder_layer``: its sublayers are the table's, by the layer's
    kind."""
    tree = ast.parse(inspect.getsource(model.decoder_layer))
    probes = [node for node in ast.walk(tree) if isinstance(node, ast.Compare)
              and any(isinstance(op, (ast.In, ast.NotIn)) for op in node.ops)]
    assert probes == []
    source = inspect.getsource(model)
    for spelled in list(config.LAYER_TYPES) + [
            kind for cfg in HELD.values() for kind in model.layer_kinds(cfg)
            if kind not in ("dense", "layers")]:
        assert f'"{spelled}"' not in source, spelled
