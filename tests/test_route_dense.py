"""The router's and the dispatch's dense forms (``parallel/experts``:
``chosen_scores``, ``count_keys``) against the gather, the scatter and the
scatter-add whose place they took, at the six share configurations'
routing shapes (tokens cut to a CPU's size; k, experts and held experts as
published) under both routers: bit for bit the same values, gradients and
counts.  And the six share steps traced at their tests' small widths: no
``gather`` and no ``scatter`` is left under ``otpu_router`` or
``otpu_dispatch`` outside the held experts' loop, where OLMoE's step (the
control: ``moe_sorted_block`` keeps its forms) still has them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_train_scopes import (JOYAI, KEYE, LFM2, NEMOTRON, OLMOE, QWEN3NEXT,
                               SMALLTHINKER, built)

from ompi_tpu.parallel import experts

# (tokens, k, experts, held here) of the six share cells, tokens cut
SHAPES = {"joyai": (64, 8, 256, 16), "nemotron3": (96, 22, 512, 8),
          "lfm2": (128, 4, 32, 8), "qwen3next": (128, 10, 512, 32),
          "smallthinker": (128, 6, 64, 16), "keye": (128, 8, 128, 16)}
ROUTERS = ["sigmoid_bias", "softmax"]
cells = pytest.mark.parametrize("cell", list(SHAPES))
routers = pytest.mark.parametrize("router", ROUTERS)


def routed(cell, router, seed=3):
    """(scores (T, E), bias or None, chosen experts (T, k), held) as the
    router makes them, with one expert (the last) that nobody chose."""
    t, k, e, held = SHAPES[cell]
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.standard_normal((t, e)), jnp.float32)
    logits = logits.at[:, -1].set(-30.0)
    if router == "softmax":
        scores, bias = jax.nn.softmax(logits, axis=-1), None
    else:
        scores = jax.nn.sigmoid(logits)
        bias = jnp.asarray(0.3 * rng.standard_normal((e,)),
                           jnp.float32).at[-1].set(0.0)
    _, chosen = experts.route_chosen(scores, bias, k, True, 2.5)
    return scores, bias, chosen, held


def same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def gathered_route(scores, bias, top_k, normalize, scale):
    """``route_chosen`` as it stood before PR 59: the chosen scores by
    ``take_along_axis``, their gradient its scatter."""
    _, chosen = jax.lax.top_k(
        scores if bias is None else scores + jax.lax.stop_gradient(bias),
        top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return weights * scale, chosen


@cells
@routers
def test_the_chosen_scores_are_the_gathered_ones(cell, router):
    scores, bias, chosen, _ = routed(cell, router)
    want = jnp.take_along_axis(scores, chosen, axis=-1)
    same_bits(experts.chosen_scores(scores, chosen), want)
    same_bits(jax.jit(experts.chosen_scores)(scores, chosen), want)
    k = chosen.shape[1]
    got_w, got_e = experts.route_chosen(scores, bias, k, True, 2.5)
    want_w, want_e = gathered_route(scores, bias, k, True, 2.5)
    same_bits(got_e, want_e)
    same_bits(got_w, want_w)
    # a token's experts are distinct, which is what makes the sums exact
    assert all(len(set(row)) == k for row in np.asarray(chosen).tolist())


@cells
@routers
def test_their_gradient_is_the_scatters(cell, router):
    scores, bias, chosen, _ = routed(cell, router)
    k = chosen.shape[1]
    ct = jnp.asarray(np.random.default_rng(4).standard_normal(chosen.shape),
                     jnp.float32)
    want = jax.vjp(lambda s: jnp.take_along_axis(s, chosen, axis=-1),
                   scores)[1](ct)[0]
    got = jax.vjp(lambda s: experts.chosen_scores(s, chosen), scores)[1](ct)
    same_bits(got[0], want)
    # through the weights' normalisation, jitted as a step runs it
    def loss(route, s):
        return jnp.sum(route(s, bias, k, True, 2.5)[0] * ct)
    same_bits(jax.jit(jax.grad(lambda s: loss(experts.route_chosen, s)))(
        scores), jax.jit(jax.grad(lambda s: loss(gathered_route, s)))(scores))


@cells
@routers
def test_the_counts_are_the_scatter_adds(cell, router):
    _, _, chosen, held = routed(cell, router)
    t, k, e, _ = SHAPES[cell]
    flat = chosen.reshape(t * k)
    slots = experts.count_keys(chosen, e)
    same_bits(slots, jnp.zeros((e,), jnp.int32).at[flat].add(1))
    assert int(slots[-1]) == 0 and int(slots.sum()) == t * k
    # the held experts start at ``first``; every other slot has the key
    # ``held``, the bin of the slots held nowhere
    for first in (0, e - held):
        here = flat - first
        key = jnp.where((here >= 0) & (here < held), here, held)
        want = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
        same_bits(experts.count_keys(key, held + 1), want)
        same_bits(jax.jit(experts.count_keys, static_argnums=1)(
            key, held + 1), want)
        order, sizes = experts.local_dispatch(chosen, first, held)
        same_bits(sizes, want[:held])
        same_bits(sizes, slots[first:first + held])
        same_bits(order, jnp.argsort(key, stable=True))
        assert int(want[held]) == t * k - int(sizes.sum()) > 0


# -- the steps as traced: what addresses single entries, by scope ---------
def entry_walks(jaxpr, stack=""):
    """(primitive, name stack) of every gather and scatter of a jaxpr and
    of the jaxprs its equations hold, the stacks joined from the
    outermost equation's in."""
    found = []
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            found.append((name, here))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += entry_walks(sub, here)
    return found


def traced_walks(cfg):
    """The walks of ``cfg``'s step (``test_train_scopes.built``: traced,
    not run) under the router's or the dispatch's scope."""
    step, args = built(cfg)
    walks = entry_walks(jax.make_jaxpr(step.jitted)(*args).jaxpr)
    return [(name, path) for name, path in walks
            if "otpu_router" in path or "otpu_dispatch" in path]


@pytest.mark.parametrize("cfg", [JOYAI, NEMOTRON, LFM2, QWEN3NEXT,
                                 SMALLTHINKER, KEYE],
                         ids=["joyai", "nemotron3", "lfm2", "qwen3next",
                              "smallthinker", "keye"])
def test_a_share_step_walks_no_entry_in_routing_or_dispatch(cfg):
    """Outside the held experts' loop (``otpu_experts``: its chunk reads
    ``order`` by a slice, and ``flat_w[slot]`` and ``dw.at[slot].add``
    stand under ``otpu_combine``) nothing under the router's or the
    dispatch's scope is a gather or a scatter, in any pass."""
    outside = [w for w in traced_walks(cfg) if "otpu_experts" not in w[1]]
    assert outside == []


def test_the_control_still_walks_them():
    """OLMoE's block is not this PR's: ``sorted_dispatch`` scatters the
    slots' places and adds up the sizes, under ``otpu_dispatch``; so the
    walk above sees what it looks for."""
    walks = traced_walks(OLMOE)
    assert {name for name, path in walks if "otpu_dispatch" in path} >= {
        "scatter", "scatter-add"}
