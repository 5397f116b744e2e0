"""The whole looped training step of Ouro-2.6B through
``train.build_train_step`` against ``parallel/ouro_reference.py``: three
steps' losses and parameters, one step's seven losses, exit distribution and
every leaf's gradient, the update, bit-for-bit repeats, what is read back
into SPC, bfloat16 compute, two data-parallel ranks; at
``tests/test_ouro_train.py``'s small widths."""
import dataclasses

import jax
import numpy as np
import pytest

from ompi_tpu.parallel import ouro_reference as ref
from ompi_tpu.parallel import objective, train
from ompi_tpu.runtime import spc

import built
from test_ouro_train import (F32, NAMES, batch_of, close, near, ref_grads,
                             spread_params)


@pytest.fixture(scope="module")
def stepped():
    """Three steps of the program from seed 3, and the reference's."""
    step, place = built.step(F32)
    params = built.params(F32, 3, spread_params)
    batches = [batch_of(s) for s in range(3)]
    state, _, _ = place(built.params(F32, 3, spread_params), *batches[0])
    auxes = []
    for tokens, labels in batches:
        state, aux = step(state, tokens, labels)
        auxes.append(jax.device_get(aux))
    want = built.program(ref.train_steps)(params, batches, F32)
    return dict(params=params, batches=batches, state=state, auxes=auxes,
                want=want, step=step)


def test_three_steps_are_the_references(stepped):
    params, losses = stepped["want"]
    got = [a["losses"] for a in stepped["auxes"]]
    close(got[0], losses[0], rtol=2e-5)
    close(got, losses, rtol=2e-4)
    for name, path in NAMES:
        off = np.abs(np.asarray(train._leaf(stepped["state"][0], path))
                     - np.asarray(train._leaf(params, path)))
        assert off.max() <= 3 * F32.lr, name
        assert np.mean(off > 0.01 * 3 * F32.lr) <= 2e-3, name
    assert stepped["state"][4]["layers"].shape == (0, 0)


def test_one_step_reports_the_references_losses_exits_and_gradients(stepped):
    tokens, labels = stepped["batches"][0]
    aux = stepped["auxes"][0]
    (total, (by_pass, expected, bonus, p)), g = ref_grads(
        stepped["params"], tokens, labels, F32)
    close(aux["losses"], [total, *by_pass, expected, bonus])
    assert aux["losses"].shape == (7,)
    at = objective.sample_rows(tokens.size)
    close(aux["exit_p"], np.asarray(p).reshape(4, -1)[:, at].T)
    close(aux["exit_mean"], np.asarray(p).mean(axis=(1, 2)))
    close(np.asarray(aux["exit_p"]).sum(-1), np.ones(16), rtol=1e-6)
    assert aux["rows"].shape == (128, 4, 2) and aux["loads"].shape == (0, 0)
    for (name, path), sq, probe in zip(NAMES, aux["grad_sq"],
                                       aux["grad_probe"]):
        leaf = np.asarray(train._leaf(g, path))
        close(sq, np.sum(leaf * leaf), rtol=2e-4, err_msg=name)
        near(probe, leaf.reshape(-1)[train.probe_positions(
            name, leaf.size)], rel=1e-4, err_msg=name)


def test_the_parameters_after_one_update_are_the_references(stepped):
    tokens, labels = stepped["batches"][0]
    step, place = built.step(F32)
    state, t, l = place(built.params(F32, 3, spread_params), tokens, labels)
    state, _ = step(state, t, l)
    want, _ = built.program(ref.train_steps)(
        stepped["params"], [(tokens, labels)], F32)
    for name, path in NAMES:
        got, ours = (np.asarray(train._leaf(tree, path))
                     for tree in (state[0], want))
        assert np.abs(got - ours).max() <= 2 * F32.lr, name
        assert np.mean(np.abs(got - ours) > 1e-3 * F32.lr) <= 2e-3, name
    # the gate and the second norms move by the rate alone: not decayed
    moved = np.asarray(state[0]["exit_gate"]["w"]) \
        - np.asarray(stepped["params"]["exit_gate"]["w"])
    assert np.abs(moved).max() <= F32.lr * (1 + 1e-5)


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


def test_a_step_read_back_counts_its_exit_depth_and_no_slot(stepped):
    spc.init()
    names = ("train_steps_read", "loop_exit_depth", "moe_local_slots",
             "moe_max_expert_load")
    before = {k: spc.read(k) for k in names}
    step, place = built.step(F32)
    tokens, labels = stepped["batches"][0]
    state, t, l = place(built.params(F32, 0), tokens, labels)
    _, aux = step(state, t, l)
    assert train.record_step_stats(aux) == 0
    moved = {k: spc.read(k) - v for k, v in before.items()}
    # a gate of zero: p = 1/2, 1/4, 1/8, 1/8 and the mean exit pass 1.875
    assert moved == dict(train_steps_read=1, loop_exit_depth=1875,
                         moe_local_slots=0, moe_max_expert_load=0)


@pytest.mark.parametrize("dp", [1, 2])
def test_bfloat16_compute_and_two_ranks_stay_near_the_reference(dp):
    cfg = dataclasses.replace(F32, compute_dtype="bfloat16")
    if dp > len(jax.devices()):
        pytest.skip("one device")
    step, place = built.step(cfg, dp)
    params = built.params(cfg, 3, spread_params)
    tokens, labels = batch_of(0)
    state, t, l = place(built.params(cfg, 3, spread_params), tokens, labels)
    _, aux = step(state, t, l)
    (total, (by_pass, expected, bonus, p)), _ = ref_grads(
        params, tokens, labels, F32)
    close(aux["losses"], [total, *by_pass, expected, bonus], rtol=3e-2)
    assert aux["rows"].shape == (128, 4, 2) \
        and aux["exit_p"].shape == (16 * dp, 4) \
        and aux["sample"]["head_in"].shape == (16 * dp, 4, 64) \
        and aux["experts"].shape == (0, 128, 0)
    close(aux["exit_mean"], np.asarray(p).mean(axis=(1, 2)), rtol=3e-2)
