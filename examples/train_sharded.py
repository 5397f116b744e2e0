"""Flagship demo: explicit-SPMD transformer training on a device mesh.

The parallel layer end to end — dp/pp/sp/tp(+ep) mesh, ring attention
over sp, Megatron-style tp matmuls, MoE alltoall dispatch, GPipe
microbatching over pp — with every cross-device exchange an explicit
mesh collective (the framework's device-side coll path).

Run on whatever devices jax finds:
  python examples/train_sharded.py            # the machine's chip(s)
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_sharded.py        # 8-dev CPU mesh
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


def main() -> None:
    from ompi_tpu.parallel.dryrun import run_training_step

    devices = jax.devices()
    print(f"training on {len(devices)} {devices[0].platform} device(s)")
    loss = run_training_step(devices)
    print(f"done; initial loss {loss:.4f}")


if __name__ == "__main__":
    main()
