"""Which rows reach which leaf of SDAR-30B-A3B's block-diffusion step: the
masked-row loss and the load-balancing loss a term at a time against the
reference (``parallel/sdar_reference`` differentiated a term at a time);
an unmasked row's logits carry weight zero, so the head's gradient is the
masked rows' alone; and the clean half reaches the loss only through the
keys and values the noisy half reads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import objective, train

from test_sdar_train import (F32, NAMES, batch_of, near, ref_grads,
                             spread_params)


@pytest.fixture(scope="module")
def stepped():
    return dict(params=spread_params(F32, 3), batches=[batch_of(0)])


def _grads_of(term, params, tokens, labels):
    """The gradient of one term of ``model_loss``'s total: ``ce`` the
    weighted masked-row loss, ``aux`` the load-balancing loss."""
    def loss(p):
        _, aux = objective.model_loss(p, tokens, labels, F32,
                                      interpret=True, n_global=tokens.size)
        return aux["losses"][1 if term == "ce" else 2]
    return jax.jit(jax.grad(loss))(params)


@pytest.mark.parametrize("term", ["ce", "aux"])
def test_each_terms_gradient_is_the_references(stepped, term):
    tokens, labels = stepped["batches"][0]
    g = _grads_of(term, stepped["params"], tokens, labels)
    want = ref_grads(stepped["params"], tokens, labels, F32,
                     terms=(term,))[1]
    for name, path in NAMES:
        near(train._leaf(g, path), train._leaf(want, path), rel=1e-4,
             err_msg=name)
    last = lambda name: name.rsplit(".", 1)[-1]
    if term == "aux":
        # the load-balancing loss reads the routers' probabilities: no
        # gradient of it reaches the head, the final norm or the last
        # layer's experts
        for name, path in NAMES:
            if last(name) in ("head", "final_norm"):
                assert not np.any(np.asarray(train._leaf(g, path))), name
        assert np.any(np.asarray(g["layers"]["l0"]["bd_moe"]["router"]))


def test_the_heads_gradient_is_the_masked_rows_alone(stepped):
    """Changing the clean token of an **unmasked** row changes neither the
    loss nor any gradient through the head's labels; of a masked row it
    does."""
    tokens, labels = stepped["batches"][0]
    params = stepped["params"]
    _, masked = objective.block_diffusion_noise(tokens, labels, F32)
    masked = np.asarray(masked)
    assert masked.any() and not masked.all()
    h = jax.random.normal(jax.random.PRNGKey(0), (128, 64))
    weights = jnp.asarray(masked.reshape(-1), jnp.float32)
    loss = lambda lab: objective.head_cross_entropy(
        h, params["head"], lab, 16, "float32", weights)[0]
    flat = tokens.reshape(-1)
    base = float(loss(flat))
    free = int(np.flatnonzero(~masked.reshape(-1))[0])
    held = int(np.flatnonzero(masked.reshape(-1))[0])
    assert float(loss(flat.at[free].set((flat[free] + 1) % 63))) == base
    assert float(loss(flat.at[held].set((flat[held] + 1) % 63))) != base


def test_the_clean_half_reaches_the_loss_through_attention_alone(stepped):
    """The clean copy's rows are read by no head: with attention's output
    projection at zero in every layer, the gradient of the masked-row loss
    with respect to the clean tokens' embedding rows that no noisy row
    shares is exactly zero."""
    tokens, labels = stepped["batches"][0]
    params = jax.tree.map(lambda a: a, stepped["params"])
    group = dict(params["layers"]["l0"]["bd_moe"])
    group["wo"] = jnp.zeros_like(group["wo"])
    params = {**params, "layers": {"l0": {"bd_moe": group}}}
    g = _grads_of("ce", params, tokens, labels)
    _, masked = objective.block_diffusion_noise(tokens, labels, F32)
    # ids that appear in x0 only at masked places stand in no noisy row
    ids, hidden = np.asarray(tokens), np.asarray(masked)
    only_clean = sorted(set(ids[hidden]) - set(ids[~hidden]))
    assert only_clean
    rows = np.asarray(g["embed"])[only_clean]
    assert not np.any(rows)
    assert np.any(np.asarray(g["embed"])[F32.mask_token_here])
