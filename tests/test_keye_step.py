"""The whole training step of Keye-VL-2.0-30B-A3B's language model through
``train.build_train_step`` against ``parallel/keye_reference.py`` under the
program's own selections: three steps' losses and parameters, one step's
loads, selection and every leaf's gradient, the update, bit-for-bit
repeats, bfloat16 compute, two data-parallel ranks; at
``tests/test_keye_train.py``'s small widths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.parallel import keye_reference
from ompi_tpu.parallel import train

from test_keye_train import (F32, NAMES, batch_of, close, near, ref_grads,
                             spread_params, unpacked)
import built

ref = built.programs(keye_reference)


@pytest.fixture(scope="module")
def stepped():
    """Three steps of the program from seed 3, and the reference's under
    the program's own selections."""
    step, place = built.step(F32)
    params = spread_params(F32, 3)
    batches = [batch_of(s) for s in range(3)]
    state, _, _ = place(jax.tree.map(jnp.copy, params), *batches[0])
    auxes = []
    for tokens, labels in batches:
        state, aux = step(state, tokens, labels)
        auxes.append(jax.device_get(aux))
    chosen = [jnp.asarray(unpacked(a["sample"]["dsa_selection_seq"], 64))
              for a in auxes]
    want = ref.train_steps(params, batches, F32, chosen)
    return dict(params=params, batches=batches, state=state, auxes=auxes,
                want=want, step=step, chosen=chosen)


def test_three_steps_are_the_references(stepped):
    params, losses = stepped["want"]
    # the first step's at float32's own width; the later ones start from
    # parameters an entry of which Adam's sign rule may have turned
    got = [a["losses"][[0, 1, 2, 4]] for a in stepped["auxes"]]
    want = [[float(x) for x in row] for row in losses]
    close(got[0], want[0], rtol=2e-5)
    close(got, want, rtol=2e-4)
    for name, path in NAMES:
        off = np.abs(np.asarray(train._leaf(stepped["state"][0], path))
                     - np.asarray(train._leaf(params, path)))
        assert off.max() <= 3 * F32.lr, name
        assert np.mean(off > 0.01 * 3 * F32.lr) <= 2e-3, name


def test_one_step_reports_the_references_losses_and_gradients(stepped):
    """Loss parts, loads, the selection and the gradient of every leaf,
    through the jitted step; the selection is the reference's own."""
    tokens, labels = stepped["batches"][0]
    aux = stepped["auxes"][0]
    (total, (ce, lb, index, loads, made)), g = ref_grads(
        stepped["params"], tokens, labels, F32)
    np.testing.assert_array_equal(stepped["chosen"][0], made)
    close(aux["losses"], [total, ce, lb, 0.0, index])
    assert float(lb) > 0 and float(index) > 0
    close(aux["loads"], loads)
    sample = aux["sample"]
    assert sample["dsa_selection_seq"].shape == (4, 2, 64, 8) \
        and sample["dsa_index_at"].shape == (4, 16, 64) \
        and sample["dsa_kall_seq"].shape == (4, 128, 32) \
        and sample["dsa_kl_at"].shape == (4, 16) \
        and sample["attn_qk"].shape == (4, 16, 32) \
        and sample["router_scores"].shape == (4, 16, 16)
    for (name, path), sq, probe in zip(NAMES, aux["grad_sq"],
                                       aux["grad_probe"]):
        leaf = np.asarray(train._leaf(g, path))
        close(sq, np.sum(leaf * leaf), rtol=2e-4, err_msg=name)
        near(probe, leaf.reshape(-1)[train.probe_positions(
            name, leaf.size)], rel=1e-4, err_msg=name)


def test_the_parameters_after_one_update_are_the_references(stepped):
    tokens, labels = stepped["batches"][0]
    step, place = built.step(F32)
    state, t, l = place(jax.tree.map(jnp.copy, stepped["params"]), tokens,
                        labels)
    state, _ = step(state, t, l)
    want, _ = ref.train_steps(stepped["params"], [(tokens, labels)], F32,
                              stepped["chosen"][:1])
    for name, path in NAMES:
        got, ours = (np.asarray(train._leaf(tree, path))
                     for tree in (state[0], want))
        assert np.abs(got - ours).max() <= 2 * F32.lr, name
        assert np.mean(np.abs(got - ours) > 1e-3 * F32.lr) <= 2e-3, name


def test_the_losses_repeat_bit_for_bit_from_one_seed(stepped):
    # a second build and a second draw, not the process's kept ones:
    # whether they give the first's numbers is what is asked
    step, place = built.fresh_step(F32)
    state, _, _ = place(spread_params(F32, 3), *stepped["batches"][0])
    for (tokens, labels), before in zip(stepped["batches"],
                                        stepped["auxes"]):
        state, aux = step(state, tokens, labels)
        np.testing.assert_array_equal(np.asarray(aux["losses"]),
                                      before["losses"])
        np.testing.assert_array_equal(
            np.asarray(aux["sample"]["dsa_selection_seq"]),
            before["sample"]["dsa_selection_seq"])


def test_bfloat16_compute_stays_near_float32(stepped):
    cfg = dataclasses.replace(F32, compute_dtype="bfloat16")
    step, place = built.step(cfg)
    state, t, l = place(spread_params(cfg, 3), *stepped["batches"][0])
    _, aux = step(state, t, l)
    close(aux["losses"][1], stepped["auxes"][0]["losses"][1], rtol=5e-3)
    close(aux["losses"][4], stepped["auxes"][0]["losses"][4], rtol=5e-2)


def test_two_data_parallel_ranks_are_one_model(stepped):
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    step, place = built.step(F32, 2)
    state, t, l = place(spread_params(F32, 3), *stepped["batches"][0])
    _, aux = step(state, t, l)
    want = stepped["auxes"][0]
    close(aux["losses"], want["losses"], rtol=1e-5)
    close(aux["loads"], want["loads"])
    close(aux["grad_sq"], want["grad_sq"], rtol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(aux["sample"]["dsa_selection_seq"]),
        want["sample"]["dsa_selection_seq"])


