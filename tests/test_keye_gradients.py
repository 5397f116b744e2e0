"""Which loss reaches which leaf of Keye-VL-2.0-30B-A3B's training step:
no gradient of the cross-entropy or of the load-balancing loss reaches an
indexer leaf, and none of the alignment loss reaches any other, both
**exactly** zero; each side is the reference's (``parallel/keye_reference``
differentiated a term at a time)."""
import jax
import numpy as np
import pytest

from ompi_tpu.parallel import objective, train

from test_keye_train import (F32, INDEX, NAMES, batch_of, near, ref_grads,
                             spread_params)


@pytest.fixture(scope="module")
def stepped():
    return dict(params=spread_params(F32, 3), batches=[batch_of(0)])


def _grads_of(term, params, tokens, labels):
    """The gradient of one term of ``model_loss``'s total: ``main`` the
    cross-entropy and the load-balancing loss, ``index`` the alignment
    loss."""
    def loss(p):
        total, aux = objective.model_loss(p, tokens, labels, F32,
                                      interpret=True, n_global=tokens.size)
        index = aux["losses"][4]
        return index if term == "index" else total - index
    return jax.jit(jax.grad(loss))(params)


def test_no_gradient_of_the_cross_entropy_reaches_an_indexer_leaf(stepped):
    """Exactly zero, not small: the indexer reads the normed input
    detached and the selection is a constant."""
    tokens, labels = stepped["batches"][0]
    g = _grads_of("main", stepped["params"], tokens, labels)
    for name, path in NAMES:
        leaf = np.asarray(train._leaf(g, path))
        if name.rsplit(".", 1)[-1] in INDEX:
            assert not np.any(leaf), name
        else:
            assert np.any(leaf), name
    want = ref_grads(stepped["params"], tokens, labels, F32,
                     terms=("ce", "aux"))[1]
    for name, path in NAMES:
        near(train._leaf(g, path), train._leaf(want, path), rel=1e-4,
             err_msg=name)


def test_no_gradient_of_the_alignment_loss_reaches_any_other_leaf(stepped):
    """Exactly zero on every leaf but the indexer's five: ``pbar`` is read
    from q, k and the logsumexp as constants."""
    tokens, labels = stepped["batches"][0]
    g = _grads_of("index", stepped["params"], tokens, labels)
    for name, path in NAMES:
        leaf = np.asarray(train._leaf(g, path))
        last = name.rsplit(".", 1)[-1]
        # the first layer's indexer is reached by its own layer's loss
        # alone; a later layer's input is detached, so nothing flows back
        assert np.any(leaf) == (last in INDEX), name
    want = ref_grads(stepped["params"], tokens, labels, F32,
                     terms=("index",))[1]
    for name, path in NAMES:
        near(train._leaf(g, path), train._leaf(want, path), rel=1e-4,
             err_msg=name)


