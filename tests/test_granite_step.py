"""The whole padding-free training step of granite-4.0-h-micro through
``train.build_train_step`` against ``parallel/granite_reference.py``: three
steps' losses and parameters, one step's gradients as the step reports them,
bit-for-bit repeats, two data-parallel ranks, what is read back into SPC,
bfloat16 compute; at ``tests/test_granite_train.py``'s small widths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ompi_tpu.parallel import granite_reference
from ompi_tpu.parallel import train
from ompi_tpu.runtime import spc

from test_granite_train import (F32, close, loss_of, near, packed, ref_grads,
                                spread)
import built

ref = built.programs(granite_reference)


def batches(n, cfg=F32):
    return [packed(10 + i, (21, 11, 30, 2), rows=cfg.micro_batch, cfg=cfg)
            for i in range(n)]


def run_steps(cfg, params, dp=1, n=3, fresh=False):
    params = jax.tree.map(jnp.array, params)    # the step donates its state
    step, place = (built.fresh_step if fresh else built.step)(cfg, dp)
    state, losses = None, []
    for tokens, labels in batches(n, cfg):
        if state is None:
            state, tok, lab = place(params, tokens, labels)
        else:
            tok, lab = (jax.device_put(t, tok.sharding)
                        for t in (tokens, labels))
        state, aux = step(state, tok, lab)
        losses.append(np.asarray(aux["losses"]))
    return jax.device_get(state[0]), losses, aux


def ref_steps(params, batches, cfg):
    """``ref.train_steps``, its two halves jitted."""
    update = jax.jit(ref.adamw_step, static_argnums=(3, 5))
    mom = var = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, (tokens, labels) in enumerate(batches, 1):
        (total, _), g = ref_grads(params, tokens, labels, cfg)
        params, mom, var = update(params, mom, var, t, g, cfg)
        losses.append(total)
    return params, losses


def test_three_steps_are_the_references_and_repeat_and_two_ranks_are_one():
    params = spread(F32, 9)
    got, losses, aux = run_steps(F32, params)
    want, want_losses = ref_steps(params, batches(3), F32)
    close([l[0] for l in losses], want_losses)
    for name, path in train.leaf_names(F32):
        near(train._leaf(got, path), train._leaf(want, path), rel=1e-4,
             err_msg=name)
    # from one seed the losses repeat bit for bit, through a second build
    _, again, _ = run_steps(F32, params, fresh=True)
    np.testing.assert_array_equal(np.stack(losses), np.stack(again))
    # two data-parallel ranks, a row each, are one model
    two, two_losses, aux2 = run_steps(F32, params, dp=2)
    close(np.stack(two_losses), np.stack(losses), rtol=1e-5)
    for name, path in train.leaf_names(F32):
        near(train._leaf(two, path), train._leaf(got, path), rel=1e-4,
             err_msg=name)
    np.testing.assert_array_equal(aux2["doc"], aux["doc"])
    # what the last step reports of its gradients is the reference's
    tokens, labels = batches(3)[-1]
    before, _ = ref_steps(params, batches(2), F32)
    _, g = ref_grads(before, tokens, labels, F32)
    for (name, path), sq, probe in zip(train.leaf_names(F32), aux["grad_sq"],
                                       aux["grad_probe"]):
        leaf = np.asarray(train._leaf(g, path))
        close(sq, np.sum(leaf * leaf), rtol=2e-3, err_msg=name)
        near(probe, leaf.reshape(-1)[train.probe_positions(
            name, leaf.size)], rel=2e-3, err_msg=name)
    # what a finished step leaves in the counters
    spc.init()
    before = {k: spc.read(k) for k in ("doc_starts", "doc_pairs_visible",
                                       "doc_pairs_causal")}
    assert train.record_step_stats(aux) == 0
    pairs = sum(n * (n + 1) // 2 for n in (21, 11, 30, 2))
    assert {k: spc.read(k) - v for k, v in before.items()} == dict(
        doc_starts=8, doc_pairs_visible=2 * pairs,
        doc_pairs_causal=2 * 64 * 65 // 2)


def test_bfloat16_compute_stays_near_float32():
    params = built.params(F32, 2)
    tokens, labels = packed(6, (21, 11, 32), rows=2)
    low = dataclasses.replace(F32, compute_dtype="bfloat16")
    (want, _), g_want = jax.jit(jax.value_and_grad(
        loss_of(F32, tokens, labels), has_aux=True))(params)
    (got, _), g = jax.jit(jax.value_and_grad(
        loss_of(low, tokens, labels), has_aux=True))(params)
    assert abs(float(got) - float(want)) < 2e-2
    for name, path in train.leaf_names(F32):
        a, b = (np.asarray(train._leaf(t, path)) for t in (g, g_want))
        assert np.sqrt(np.mean((a - b) ** 2)) <= 0.1 * max(
            1e-8, np.sqrt(np.mean(b ** 2))), name
