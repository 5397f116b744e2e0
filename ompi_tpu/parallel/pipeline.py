"""GPipe-style pipeline over the pp mesh axis, inside shard_map.

Stage-to-stage activation handoff is a ``ppermute`` ring — the device-side
shape of PP's stage-rank send/recv (SURVEY.md §2.6 PP row, reference
``pml_ob1_isend.c:233``).  Microbatches stream through M + pp - 1 steps;
bubble steps compute on masked-out state (standard for static-shape SPMD
pipelines).  Degenerates cleanly to a plain microbatch loop at pp == 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pipeline_apply(stage_fn, stage_params, x_microbatches, *, pp: int,
                   vary_axes: tuple = ("pp",)):
    """Run microbatches through pp stages; returns (M, *mb_shape) outputs.

    ``stage_fn(stage_params, x_mb) -> y_mb`` is this device's stage (its
    shard of the layer stack).  ``x_microbatches``: (M, *mb_shape), only
    read at stage 0; outputs are collected at stage pp-1 and zero elsewhere.

    ``vary_axes``: mesh axes the stage outputs are device-varying over
    beyond the input's own (``pp`` always; add e.g. ``tp`` when stage_fn
    runs tensor-parallel collectives).  The carries are pre-marked with
    ``pcast(to="varying")`` so the scan type-checks under ``check_vma=True`` — which is
    load-bearing: vma tracking is what makes the ppermute/psum
    TRANSPOSES correct, and with it off the pp>=2 backward silently
    computes wrong gradients (caught by test_pp2_matches_pp1_same_model).
    """
    M = x_microbatches.shape[0]
    r = jax.lax.axis_index("pp") if pp > 1 else 0
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    state = jnp.zeros_like(x_microbatches[0])
    outbuf = jnp.zeros_like(x_microbatches)
    state = jax.lax.pcast(state, vary_axes, to="varying")
    outbuf = jax.lax.pcast(outbuf, vary_axes, to="varying")
    x_microbatches = jax.lax.pcast(x_microbatches, vary_axes, to="varying")

    def body(carry, t):
        state, outbuf = carry
        inp = jax.lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        cur = jnp.where(r == 0, inp, state)
        valid = (t >= r) & ((t - r) < M)
        y = stage_fn(stage_params, cur)
        y = jnp.where(valid, y, jnp.zeros_like(y))
        oidx = jnp.clip(t - (pp - 1), 0, M - 1)
        collect = (r == pp - 1) & valid
        prev = jax.lax.dynamic_index_in_dim(outbuf, oidx, 0, keepdims=False)
        outbuf = jax.lax.dynamic_update_index_in_dim(
            outbuf, jnp.where(collect, y, prev), oidx, 0)
        if pp > 1:
            state = jax.lax.ppermute(y, "pp", perm)
        else:
            state = y
        return (state, outbuf), None

    (_, outbuf), _ = jax.lax.scan(
        body, (state, outbuf), jnp.arange(M + pp - 1))
    return outbuf
