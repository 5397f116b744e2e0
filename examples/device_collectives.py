"""Device-collective tour: coll/xla, coll/pallas, and the fused GEMM.

Runs in the conductor/device-world model (one process drives every
device rank over the local mesh).  Shows the three device transports a
user can select between:

1. **coll/xla** (default): compiler-scheduled `lax.psum`-family
   collectives — the right default.
2. **coll/pallas** (`--mca coll_pallas_priority 95` or the in-process
   override below): explicit remote-DMA ring schedules, with segmented
   HBM kernels above the VMEM crossover and a pipelined bcast.
3. **ops/pallas_overlap**: the fused collective matmul — per-block
   compute overlapping each ring step's DMA.

Runs on the devices jax finds: the machine's chips, or with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
an 8-virtual-device mesh (the Pallas kernels then run interpreted —
``interpret`` resolves from the mesh's devices).
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import ompi_tpu  # noqa: E402


def main() -> None:
    world = ompi_tpu.init()
    n = world.size
    print(f"device world: {n} rank(s)")
    rng = np.random.default_rng(0)

    # -- 1. coll/xla (the default owner of the *_array slots) ----------
    x = rng.standard_normal((n, 1024)).astype(np.float32)
    out = np.asarray(world.allreduce_array(x))
    np.testing.assert_allclose(out, x.sum(0), rtol=1e-4, atol=1e-5)
    owner = world.c_coll["allreduce_array"].__self__.__class__.__name__
    print(f"allreduce via {owner}: ok")

    # -- 2. coll/pallas (explicit remote-DMA rings) --------------------
    if n == 1:
        print("SKIPPED: rings need >1 device — run with "
              "JAX_PLATFORMS=cpu "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "for a virtual mesh")
    if n > 1:
        from ompi_tpu.base.var import registry
        from ompi_tpu.runtime import init as rt

        var = registry.lookup("otpu_coll_pallas_priority")
        if var is None:
            raise SystemExit("coll/pallas did not register its vars "
                             "(component excluded?)")
        old = var.value
        var.set(95)       # the MPI_T-style cvar write API
        rt.reset_for_testing()
        try:
            w2 = ompi_tpu.init()
            owner = w2.c_coll["allreduce_array"].__self__ \
                .__class__.__name__
            out = np.asarray(w2.allreduce_array(x))
            np.testing.assert_allclose(out, x.sum(0), rtol=1e-4,
                                       atol=1e-5)
            b = np.asarray(w2.bcast_array(x, root=n - 1))
            np.testing.assert_allclose(
                b, np.broadcast_to(x[n - 1], x.shape), rtol=1e-6)
            print(f"allreduce + pipelined bcast via {owner}: ok")
        finally:
            var.set(old)
            rt.reset_for_testing()
            ompi_tpu.init()

    # -- 3. the fused collective matmul --------------------------------
    if n > 1:
        import jax
        from jax.sharding import Mesh

        from ompi_tpu.ops import pallas_overlap as po

        devs = jax.devices()[:n]
        mesh = Mesh(np.array(devs), ("x",))
        M, K, N = 64, 16 * n, 128     # Mosaic: N a multiple of 128
        a = rng.standard_normal((n, M, K // n)).astype(np.float32)
        bb = rng.standard_normal((n, K // n, N)).astype(np.float32)
        y = np.asarray(po.matmul_allreduce(
            jax.device_put(a), jax.device_put(bb), mesh, "x"))
        np.testing.assert_allclose(
            y, sum(a[i] @ bb[i] for i in range(n)), rtol=1e-3, atol=1e-3)
        print("fused matmul+allreduce (compute overlaps the ring DMA): ok")

    # -- 4. duplex + torus schedules ------------------------------------
    if n >= 4 and n % 2 == 0:
        import jax
        from jax.sharding import Mesh

        from ompi_tpu.ops import pallas_collectives as pc

        devs = jax.devices()[:n]
        mesh1 = Mesh(np.array(devs), ("x",))
        g = rng.standard_normal((n, 256)).astype(np.float32)
        # Mosaic: ring all-gather blocks are (8, 128) tiles
        gt = rng.standard_normal((n, 8, 128)).astype(np.float32)
        y = np.asarray(pc.all_gather(jax.device_put(gt), mesh1, "x",
                                     variant="bidi"))
        np.testing.assert_allclose(y, gt, rtol=1e-6)
        print("bidirectional all-gather (duplex ICI, ceil((n-1)/2) "
              "steps): ok")
        mesh2 = Mesh(np.array(devs).reshape(2, n // 2), ("x", "y"))
        x2 = rng.standard_normal((n, n, 128)).astype(np.float32)
        r = np.asarray(pc.reduce_scatter_torus(jax.device_put(x2),
                                               mesh2))
        np.testing.assert_allclose(r, x2.sum(0), rtol=1e-4, atol=1e-5)
        a2 = np.asarray(pc.all_gather_torus(jax.device_put(g), mesh2))
        np.testing.assert_allclose(a2, g, rtol=1e-6)
        print("2D-torus reduce-scatter + all-gather (per-dimension "
              "sub-rings): ok")

    ompi_tpu.finalize()
    print("DEVICE COLLECTIVES OK")


if __name__ == "__main__":
    main()
