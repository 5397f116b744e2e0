"""MoE/EP ragged dispatch with the true alltoallv — the workload the
counts-driven pallas kernels exist for.

Every routing step of a mixture-of-experts layer sends a DIFFERENT
number of tokens between each pair of ranks.  A padded ``all_to_all``
must move the worst-case count for every pair; the ragged kernel
(`ops.pallas_collectives.all_to_all_v`) takes the (n, n) counts table
as a runtime operand and moves only (chunk-rounded) real tokens — and
because the counts are data, ONE compiled program serves every routing
outcome, where a shape-specialized kernel would recompile per batch.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
         python examples/ragged_dispatch.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ompi_tpu.ops.pallas_collectives import all_to_all_v

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.array(devs), ("ep",))
    d_model = 256                 # token feature width (128-lane aligned)
    cap = 64                      # worst-case tokens per (src, dst) pair

    rng = np.random.default_rng(0)
    # a routing step: rank i holds cap-padded token blocks for each
    # expert home j, with counts[i, j] real tokens
    counts = rng.integers(4, cap + 1, (n, n)).astype(np.int32)
    tokens = rng.standard_normal((n, n, cap, d_model)).astype(np.float32)

    out = np.asarray(all_to_all_v(jnp.asarray(tokens), counts, mesh,
                                  "ep"))
    # rank j now holds out[j, i, :counts[i, j]] = rank i's tokens for it
    for j in range(n):
        for i in range(n):
            c = counts[i, j]
            np.testing.assert_array_equal(out[j, i, :c],
                                          tokens[i, j, :c])

    ideal = counts.sum() * d_model * 4
    chunk = 8
    ragged = (-(-counts // chunk) * chunk).sum() * d_model * 4
    padded = n * n * cap * d_model * 4
    print(f"dispatch verified on {n} ranks: ideal {ideal >> 10} KiB, "
          f"ragged wire {ragged >> 10} KiB "
          f"({ragged / ideal:.2f}x ideal), padded all_to_all would "
          f"move {padded >> 10} KiB ({padded / ideal:.2f}x)")

    # the inverse (combine) is the same kernel with transposed counts
    back = np.asarray(all_to_all_v(jnp.asarray(out), counts.T, mesh,
                                   "ep"))
    for i in range(n):
        for j in range(n):
            c = counts[i, j]
            np.testing.assert_array_equal(back[i, j, :c],
                                          tokens[i, j, :c])
    print("combine (inverse dispatch) verified: counts.T round-trips")


if __name__ == "__main__":
    main()
