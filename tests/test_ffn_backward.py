"""The feed-forward's backward pass written out (``layers.swiglu``,
``layers.relu2`` in a ``compute_dtype`` narrower than float32;
``layers._ffn_backward``): its gradients against autodiff of the plain
lines with the same casts, alone and through a checkpointed scan over a
stack of layers; the plain lines, bit for bit, under float32; what it
keeps for the backward pass; and what a step's plan and the SPC counters
say of it (``ffn_built``, ``ffn_bwd_written_built``).  CPU, seconds.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_model_tree import CONFIGS

from ompi_tpu.parallel import config, layers, train
from ompi_tpu.runtime import spc


BF16 = jnp.bfloat16
#: two units in bfloat16's last place at a leaf's largest value (8 bits of
#: significand: a unit is 2^-8 to 2^-7 of the value)
TWO_PLACES = 2 * 2.0 ** -7
#: rows x width x ff: whole tiles, and no multiple of 128 anywhere
SHAPES = {"tiles": (256, 128, 384), "ragged": (200, 96, 328)}


def plain_swiglu(h, gate, up, down, dt):
    """``layers.swiglu`` as it stood before the rule: autodiff's."""
    act = jax.nn.silu(layers.matmul(h, gate, dt)) * layers.matmul(h, up, dt)
    return layers.matmul(act, down, dt)


def plain_relu2(h, up, down, dt):
    act = jnp.square(jax.nn.relu(layers.matmul(h, up, dt)))
    return layers.matmul(act, down, dt)


#: name -> (the function, its plain lines, its leaves behind ``h``)
FFNS = {"swiglu": (layers.swiglu, plain_swiglu, ("gate", "up", "down")),
        "relu2": (layers.relu2, plain_relu2, ("up", "down"))}
CASES = [(name, leaf) for name, (_, _, leaves) in FFNS.items()
         for leaf in ("h",) + leaves]


def operands(name, shape, seed=0, stack=None):
    """(h, the matrices, a float32 weight on the result): the matrices at
    the scale a model's start at, ``stack`` of each where given."""
    t, d, ff = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    lead = () if stack is None else (stack,)
    mats = {"gate": jax.random.normal(keys[1], lead + (d, ff)) * d ** -.5,
            "up": jax.random.normal(keys[2], lead + (d, ff)) * d ** -.5,
            "down": jax.random.normal(keys[3], lead + (ff, d)) * ff ** -.5}
    return (jax.random.normal(keys[0], (t, d)),
            [mats[leaf] for leaf in FFNS[name][2]],
            jax.random.normal(keys[4], (t, d)))


@functools.lru_cache(maxsize=None)
def both_gradients(name, shape_id, dt):
    """{leaf: (the rule's gradient, autodiff's of the plain lines)}."""
    fn, plain, leaves = FFNS[name]
    h, mats, weight = operands(name, SHAPES[shape_id])
    grads = [jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a, dt) * weight),
                      argnums=tuple(range(1 + len(mats)))))(h, *mats)
             for f in (fn, plain)]
    return dict(zip(("h",) + leaves, zip(*grads)))


def widest(got, want) -> float:
    """The largest difference relative to the leaf's largest value."""
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("shape_id", SHAPES)
@pytest.mark.parametrize("name,leaf", CASES)
def test_the_rules_gradient_is_autodiffs_within_two_bfloat16_places(
        name, leaf, shape_id):
    got, want = both_gradients(name, shape_id, BF16)[leaf]
    assert got.dtype == want.dtype == jnp.float32
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(want))) > 0
    assert widest(got, want) <= TWO_PLACES


@pytest.mark.parametrize("shape_id", SHAPES)
@pytest.mark.parametrize("name", FFNS)
def test_the_value_is_the_plain_lines_bit_for_bit(name, shape_id):
    """The rule's primal is the plain lines: in bfloat16 too."""
    fn, plain, _ = FFNS[name]
    h, mats, _ = operands(name, SHAPES[shape_id])
    for dt in (BF16, jnp.float32):
        np.testing.assert_array_equal(fn(h, *mats, dt), plain(h, *mats, dt))


@pytest.mark.parametrize("name,leaf", CASES)
def test_float32_keeps_the_plain_lines_and_autodiffs_gradient_exactly(
        name, leaf):
    got, want = both_gradients(name, "ragged", jnp.float32)[leaf]
    np.testing.assert_array_equal(got, want)


@functools.lru_cache(maxsize=None)
def stacked_gradients(name):
    """The gradients of three layers' stacked leaves through ``lax.scan``
    over ``jax.checkpoint`` of a residual layer, as the walk runs a run of
    like layers: (the rule's, autodiff's of the plain lines)."""
    fn, plain, leaves = FFNS[name]
    h, mats, weight = operands(name, SHAPES["ragged"], seed=1, stack=3)

    def loss(f, h, *mats):
        layer = jax.checkpoint(lambda x, ws: x + f(x, *ws, BF16))
        out, _ = jax.lax.scan(lambda x, ws: (layer(x, ws), None), h, mats)
        return jnp.sum(out * weight)

    grads = [jax.jit(jax.grad(functools.partial(loss, f),
                      argnums=tuple(range(1 + len(mats)))))(h, *mats)
             for f in (fn, plain)]
    return dict(zip(("h",) + leaves, zip(*grads)))


@pytest.mark.parametrize("slot", [0, 1, 2])
@pytest.mark.parametrize("name,leaf", [c for c in CASES if c[1] != "h"])
def test_a_checkpointed_scans_stacked_gradient_slot_by_slot(name, leaf, slot):
    got, want = stacked_gradients(name)[leaf]
    assert got.shape == want.shape and got.shape[0] == 3
    assert widest(got[slot], want[slot]) <= TWO_PLACES


@pytest.mark.parametrize("name", FFNS)
def test_a_checkpointed_scans_gradient_of_its_input(name):
    got, want = stacked_gradients(name)["h"]
    assert widest(got, want) <= TWO_PLACES


@pytest.mark.parametrize("name", FFNS)
def test_the_rule_keeps_its_cast_operands_alone(name):
    """Nothing of a layer's (T, ff) arrays is kept for the backward pass:
    the pre-activations are made again in the rule, as autodiff's
    recomputed pass made them under the layer's checkpoint."""
    fn, _, leaves = FFNS[name]
    t, d, ff = SHAPES["ragged"]
    h, mats, _ = operands(name, SHAPES["ragged"])
    _, pullback = jax.vjp(lambda *a: fn(*a, BF16), h, *mats)
    kept = [a for a in jax.tree.leaves(pullback) if hasattr(a, "shape")]
    assert sorted(a.shape for a in kept if a.dtype == BF16) \
        == sorted([(t, d)] + [m.shape for m in mats])
    assert not [a.shape for a in kept if a.shape == (t, ff)]


@pytest.mark.parametrize("name", FFNS)
@pytest.mark.parametrize("dt,barriers", [(BF16, 1), (jnp.float32, 0)],
                         ids=["bfloat16", "float32"])
def test_one_barrier_stands_between_the_elementwise_arrays_and_the_products(
        name, dt, barriers):
    fn, _, leaves = FFNS[name]
    h, mats, weight = operands(name, SHAPES["tiles"])
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(fn(*a, dt) * weight),
        argnums=tuple(range(1 + len(mats)))))(h, *mats))
    assert text.count("optimization_barrier") == barriers
    # the activation, the incoming cotangent and a cotangent a product of h
    if barriers:
        (line,) = [at for at in text.splitlines()
                   if "optimization_barrier" in at]
        assert line.count("bf16[") >= 2 + len(leaves) - 1
        assert "f32[" not in line


def test_the_decision_is_matmuls_float32_or_not_and_the_widths():
    assert layers.ffn_bwd_written(BF16, 2048, 8192) == (True, "")
    assert layers.ffn_bwd_written("bfloat16", 2048, 2048) == (True, "")
    written, why = layers.ffn_bwd_written(jnp.float32, 2048, 8192)
    assert not written and why.startswith("compute_dtype float32")
    assert layers.ffn_bwd_written("float32", 2048, 8192) == (written, why)
    # narrower than the stream: Qwen3-Next's shared expert, JoyAI's
    for ff in (512, 768):
        written, why = layers.ffn_bwd_written(BF16, 2048, ff)
        assert not written
        assert why.startswith(f"{ff} hidden units on a stream of 2048")


@pytest.mark.parametrize("name", FFNS)
def test_a_feed_forward_narrower_than_the_stream_keeps_autodiffs_pass(name):
    fn, plain, _ = FFNS[name]
    h, mats, weight = operands(name, (64, 128, 96))
    grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a, BF16) * weight),
                              argnums=tuple(range(1 + len(mats))))
    assert "optimization_barrier" not in str(jax.make_jaxpr(grad(fn))(
        h, *mats))
    for got, want in zip(grad(fn)(h, *mats), grad(plain)(h, *mats)):
        np.testing.assert_array_equal(got, want)


# -- what a step's plan says of it --------------------------------------------------
#: file -> the layer applications that call ``swiglu`` or ``relu2``, all on
#: the written rule
FFN_BUILT = {"granite-4.0-h-micro-train-1chip.json": 10,
             "ouro-2.6b-train-1chip.json": 16,
             "nemotron3-super-train-1chip.json": 5,
             "olmoe-1b-7b-train-1chip.json": 0}
#: file -> (those applications, those of them on the written rule): the
#: shared experts narrower than the stream keep autodiff's backward pass
FFN_NARROW = {"joyai-flash-train-1chip.json": (6, 1),
              "qwen3-next-80b-a3b-train-1chip.json": (4, 0)}


def plan_of(name, **change):
    cfg = config.load_model_config(os.path.join(CONFIGS, name), **change)
    return train.plan_of(cfg, cfg.micro_batch, cfg.seq_len, interpret=False)


@pytest.mark.parametrize("name", FFN_BUILT)
def test_a_cells_plan_counts_its_feed_forwards_all_on_the_written_rule(name):
    plan = plan_of(name)
    counts = plan["counts"]
    assert counts.get("ffn_built", 0) == FFN_BUILT[name]
    assert counts.get("ffn_bwd_written_built", 0) == FFN_BUILT[name]
    assert {"ffn_built", "ffn_bwd_written_built"} <= set(spc._COUNTERS)
    at = [row["ffn"]["parts"]["ffn_bwd"] for row in plan["rows"]
          if row["ffn"] and "ffn_bwd" in row["ffn"]["parts"]]
    assert bool(at) == bool(FFN_BUILT[name])
    assert all(part == {"impl": "written", "why": ""} for part in at)
    # the rule is no Pallas kernel: a dense feed-forward stays ``xla``
    for row in plan["rows"]:
        if row["ffn"] and row["ffn"]["scope"] == "otpu_dense_mlp":
            assert (row["ffn"]["impl"], row["ffn"]["why"]) \
                == ("xla", "the sublayer has no Pallas kernel")
            assert sum(row["ffn"]["counts"].values()) == 2


@pytest.mark.parametrize("name", FFN_NARROW)
def test_a_narrow_shared_expert_is_counted_and_not_written(name):
    plan = plan_of(name)
    built, written = FFN_NARROW[name]
    assert plan["counts"]["ffn_built"] == built
    assert plan["counts"].get("ffn_bwd_written_built", 0) == written
    refused = [row["ffn"]["parts"]["ffn_bwd"]["why"] for row in plan["rows"]
               if row["ffn"] and row["ffn"]["parts"].get(
                   "ffn_bwd", {}).get("impl") == "xla"]
    assert refused and all("narrower than the stream" in why
                           for why in refused)


@pytest.mark.parametrize("name", [n for n, by in FFN_BUILT.items() if by])
def test_under_float32_the_plan_names_the_clause_and_counts_none_written(
        name):
    plan = plan_of(name, compute_dtype="float32")
    assert plan["counts"]["ffn_built"] == FFN_BUILT[name]
    assert "ffn_bwd_written_built" not in plan["counts"]
    at = [row["ffn"]["parts"]["ffn_bwd"] for row in plan["rows"]
          if row["ffn"] and "ffn_bwd" in row["ffn"]["parts"]]
    assert at and all(part == {
        "impl": "xla", "why": layers.ffn_bwd_written("float32", 1, 1)[1]}
        for part in at)
