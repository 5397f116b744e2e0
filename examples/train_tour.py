"""Flagship training-step tour: every parallel-layer knob, one run each.

Runs the composed dp/pp/sp/tp training step on the first 8 devices jax
finds (8 virtual CPU devices with the command below; 8 chips where
there are that many) under each configuration the framework exposes,
printing the one-step loss so the effect of each knob is visible:

  baseline   f32, dense attention, store-all activations, allreduce dp
  causal     autoregressive masking at global sequence positions
  remat      per-block rematerialization (jax.checkpoint)
  bf16       bfloat16 compute precision (f32 master storage + loss)
  zero1      ZeRO-1: reduce-scattered grads + dp-sharded momentum
  the works  all of the above composed

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
         python examples/train_tour.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    import jax

    from ompi_tpu.base.var import registry
    from ompi_tpu.parallel.dryrun import parse_spec, run_training_step

    devs = jax.devices()[:8]
    spec = parse_spec("dp=2,pp=2,sp=2,tp=1")
    knobs = {
        "otpu_parallel_causal": False,
        "otpu_parallel_remat": False,
        "otpu_parallel_compute_dtype": "float32",
        "otpu_parallel_zero1": False,
        "otpu_parallel_momentum": 0.0,
    }
    saved = {k: registry.lookup(k).value for k in knobs}

    def run(tag, **over):
        for k, dv in knobs.items():
            registry.lookup(k).set(over.get(k, dv))
        loss = run_training_step(devs, spec)
        print(f"{tag:10s} loss {float(loss):10.4f}")

    try:
        run("baseline")
        run("causal", otpu_parallel_causal=True)
        run("remat", otpu_parallel_remat=True)
        run("bf16", otpu_parallel_compute_dtype="bfloat16")
        run("zero1", otpu_parallel_zero1=True,
            otpu_parallel_momentum=0.9)
        run("the works", otpu_parallel_causal=True,
            otpu_parallel_remat=True,
            otpu_parallel_compute_dtype="bfloat16",
            otpu_parallel_zero1=True, otpu_parallel_momentum=0.9)
    finally:
        for k, v in saved.items():
            registry.lookup(k).set(v)
    print("TRAIN TOUR OK")


if __name__ == "__main__":
    main()
