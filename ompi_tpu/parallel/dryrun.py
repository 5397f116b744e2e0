"""Driver-facing dry run: one full dp/pp/sp/tp(+ep) training step."""
from __future__ import annotations

import sys

import numpy as np


def make_step_and_args(devices, spec=None, layers=None):
    """Shared flagship-path setup: (jitted step, (params, x)) on a mesh."""
    from ompi_tpu.parallel.flagship import (build_flagship_step, init_params,
                                            model_dims)
    from ompi_tpu.parallel.mesh import make_mesh

    mesh, mspec = make_mesh(devices, spec)
    dims = model_dims(mspec, layers)
    step, place = build_flagship_step(mesh, mspec, layers=layers)
    rng = np.random.RandomState(1)
    x = rng.normal(0, 1, (dims["batch"], dims["seq"], dims["d"]))
    params, xd = place(init_params(mspec, layers=layers), x)
    return step, (params, xd), mspec


def parse_spec(text: str):
    """'dp=1,pp=2,sp=2,tp=2' -> MeshSpec (the driver/dryrun override)."""
    from ompi_tpu.parallel.mesh import MeshSpec

    sizes = {}
    for part in str(text).split(","):
        k, _, v = part.partition("=")
        sizes[k.strip()] = int(v)
    return MeshSpec(**sizes)


def run_training_step(devices, spec=None) -> float:
    """Jit + run one train step over a mesh of the given devices.

    When no spec override is given and the default mesh leaves the
    pipeline axis inactive (pp only self-activates at >=16 devices), a
    second pp-active step runs on the same devices so every dry run
    validates the composed dp x pp x sp x tp program — the round-2 gap
    where the pp>=2 backward had silently-wrong gradients."""
    from ompi_tpu.parallel.mesh import MeshSpec, default_axis_sizes

    loss = _one_descending_step(devices, spec)
    n = len(devices)
    half = default_axis_sizes(n // 2) if n >= 4 else None
    if (spec is None and half is not None and half.pp == 1
            and default_axis_sizes(n).pp == 1):
        # pp=2 over half the factorization; odd counts drop one device.
        # half.pp must itself be 1 or doubling it would not cover
        # 2*(n//2) devices (e.g. n=33: half=16 already has pp=2)
        sizes = half.sizes()
        sizes["pp"] = 2
        _one_descending_step(devices[:2 * (n // 2)], MeshSpec(**sizes))
    return loss


def run_pallas_ring_check(devs, mesh, interp: bool) -> dict:
    """coll/pallas validation: every ring-kernel variant executes on
    THIS mesh (compiled on real TPU, interpreter elsewhere) and matches
    numpy.  Each kernel is named before it runs (a hang shows where)
    and a kernel that raises is reported with the compiler's or
    runtime's message and recorded False — the rest still run."""
    import jax

    from ompi_tpu.ops import pallas_collectives as pc
    from ompi_tpu.ops import pallas_overlap as po

    n = len(devs)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, 256)).astype(np.float32)
    x2 = rng.standard_normal((n, n, 16)).astype(np.float32)
    # Mosaic refuses (the interpreter does not) all-gather blocks that
    # are not (8, 128) tiles, all-to-all blocks and fused-GEMM outputs
    # narrower than 128 lanes, and a bf16 wire under n*8*128 elements
    xt = rng.standard_normal((n, 8, 128)).astype(np.float32)
    x2t = rng.standard_normal((n, n, 128)).astype(np.float32)
    xw = rng.standard_normal((n, n * 8 * 128)).astype(np.float32)
    put = jax.device_put
    checks = {}

    def chk(name, run, want, tol=1e-4, same=None):
        print(f"pallas first run: {name} ...", file=sys.stderr, flush=True)
        try:
            got = np.asarray(run())
            checks[name] = bool(same(got) if same is not None else
                                np.allclose(got, want, atol=tol, rtol=tol))
        except Exception as exc:
            msg = " ".join(str(exc).split())
            print(f"pallas first run: {name} RAISED "
                  f"{type(exc).__name__}: {msg[:600]}", file=sys.stderr,
                  flush=True)
            checks[name] = False

    def ar(op="sum", src=x, **kw):
        return lambda: pc.all_reduce(put(src), mesh, "x", op,
                                     interpret=interp, **kw)

    chk("allreduce_fused", ar(), x.sum(0))
    chk("allreduce_seg", ar(variant="seg", seg_elems=64), x.sum(0))
    chk("allreduce_bidi", ar(variant="bidi"), x.sum(0))
    chk("allreduce_seg_bidi", ar(variant="seg_bidi", seg_elems=32),
        x.sum(0))
    chk("allreduce_max", ar("max"), x.max(0), tol=1e-6)
    chk("allreduce_wire16", ar(src=xw, variant="wire16"), xw.sum(0),
        tol=0.25)
    chk("reduce_scatter",
        lambda: pc.reduce_scatter(put(x2), mesh, "x", "sum",
                                  interpret=interp), x2.sum(0))
    chk("allgather",
        lambda: pc.all_gather(put(xt), mesh, "x", interpret=interp),
        xt, tol=1e-6)
    chk("allgather_bidi",
        lambda: pc.all_gather(put(xt), mesh, "x", interpret=interp,
                              variant="bidi"), xt, tol=1e-6)
    chk("bcast",
        lambda: pc.bcast(put(x), mesh, "x", root=1, interpret=interp),
        np.broadcast_to(x[1], x.shape), tol=1e-6)
    chk("alltoall",
        lambda: pc.all_to_all(put(x2t), mesh, "x", interpret=interp),
        np.swapaxes(x2t, 0, 1), tol=1e-6)
    xv = rng.standard_normal((n, n, 8, 128)).astype(np.float32)
    cnt = rng.integers(1, 9, (n, n)).astype(np.int32)
    chk("alltoallv_ragged",
        lambda: pc.all_to_all_v(put(xv), cnt, mesh, "x",
                                interpret=interp), None,
        same=lambda got: all(
            np.array_equal(got[j, i, :cnt[i, j]], xv[i, j, :cnt[i, j]])
            for i in range(n) for j in range(n)))
    if n % 2 == 0 and n >= 4:
        from jax.sharding import Mesh

        mesh2 = Mesh(np.asarray(devs).reshape(2, n // 2), ("x", "y"))
        chk("allreduce_torus",
            lambda: pc.all_reduce_torus(
                put(x.reshape(2, n // 2, -1)), mesh2, ("x", "y"),
                interpret=interp), x.sum(0))
        chk("reduce_scatter_torus",
            lambda: pc.reduce_scatter_torus(put(x2), mesh2, ("x", "y"),
                                            interpret=interp), x2.sum(0))
        chk("allgather_torus",
            lambda: pc.all_gather_torus(put(x), mesh2, ("x", "y"),
                                        interpret=interp), x, tol=1e-6)

    # the fused compute+communicate kernels are part of the evidence
    # set too (pallas_overlap: new collective_ids, real RDMA semantics
    # on hardware)
    m, k_loc, n_out = 8 * n, 16, 128
    a = rng.standard_normal((n, m, k_loc)).astype(np.float32)
    bb = rng.standard_normal((n, k_loc, n_out)).astype(np.float32)
    want = sum(a[i] @ bb[i] for i in range(n))
    chk("matmul_allreduce",
        lambda: po.matmul_allreduce(put(a), put(bb), mesh, "x",
                                    interpret=interp), want, tol=1e-3)
    chk("matmul_reduce_scatter",
        lambda: po.matmul_reduce_scatter(put(a), put(bb), mesh, "x",
                                         interpret=interp),
        want.reshape(n, m // n, n_out), tol=1e-3)
    return checks


def run_tolerance_check(coll, approx_fn, exact_fn=None,
                        sizes=(1 << 10, 1 << 14), dtypes=("float32",),
                        nranks=4, band=0.02, seed=0) -> dict:
    """Tolerance-band twin of the bit-exactness checks: lossy
    collective tiers (coll/quant) cannot promise bit-identical results,
    so this harness pins them to a RELATIVE-ERROR BAND against the f32
    exact result instead.

    For every (size, dtype) cell: seeded inputs ``(nranks, size)``,
    ``exact_fn(stack)`` (default: the f64-accumulated f32 sum — the
    allreduce reference), ``approx_fn(stack)`` (the path under test),
    and the max absolute deviation normalized by ``max(|exact|)``.
    Returns ``{"coll/size/dtype": rel_error}``; any cell outside the
    band raises a LOUD report naming the failing (coll, size, dtype)
    cell — a tolerance regression must name its cell, not drown in an
    aggregate."""
    report: dict = {}
    failures = []
    for size in sizes:
        for di, dtype in enumerate(dtypes):
            rng = np.random.default_rng([int(seed), int(size), di])
            stack = rng.standard_normal((nranks, int(size))).astype(dtype)
            exact = np.asarray(
                np.sum(stack.astype(np.float64), axis=0).astype(dtype)
                if exact_fn is None else exact_fn(stack))
            approx = np.asarray(approx_fn(stack))
            denom = max(float(np.max(np.abs(exact))), 1e-12)
            rel = float(np.max(np.abs(approx.astype(np.float64)
                                      - exact.astype(np.float64)))
                        / denom)
            report[f"{coll}/{size}/{dtype}"] = rel
            if not np.isfinite(rel) or rel > band:
                failures.append((size, dtype, rel))
    if failures:
        cells = "; ".join(
            f"({coll}, {size}, {dtype}) rel error {rel:.3e} > band "
            f"{band:g}" for size, dtype, rel in failures)
        raise RuntimeError(f"tolerance check FAILED: {cells}")
    worst = max(report.values()) if report else 0.0
    print(f"tolerance dryrun ok: {coll} {len(report)} cells, max rel "
          f"error {worst:.3e} within band {band:g}")
    return report


def run_mp_training_step(spec_text: str = "") -> float:
    """Multi-process dryrun body: one flagship train step over the
    GLOBAL device mesh of a ``tpurun --device-world`` job.

    Runs inside each rank: ``init()`` boots the instance, whose
    device-world wire-up ran ``jax.distributed.initialize`` (coordinator
    address from the coord service), so ``jax.devices()`` spans every
    process — the train step's psums cross real process boundaries.
    """
    import jax

    import ompi_tpu

    w = ompi_tpu.init()
    rte = w.rte
    if not getattr(rte, "device_world_booted", False):
        raise RuntimeError(
            "device world did not boot (launch with tpurun --device-world)")
    if jax.process_count() < 2:
        raise RuntimeError(
            f"expected a multi-process device world, got "
            f"{jax.process_count()} process(es)")
    loss = _one_descending_step(
        jax.devices(), parse_spec(spec_text) if spec_text else None)
    ompi_tpu.finalize()
    return loss


def _one_descending_step(devices, spec) -> float:
    import jax

    step, (params, xd), spec = make_step_and_args(devices, spec)
    new_params, loss = step(params, xd)
    jax.block_until_ready(new_params)
    loss = float(loss)
    if not np.isfinite(loss):
        raise RuntimeError(f"non-finite loss {loss}")
    # one more step on the updated params: SGD must have moved them
    _, loss2 = step(new_params, xd)
    if not float(loss2) < loss:
        raise RuntimeError(
            f"training step did not descend: {loss} -> {float(loss2)}")
    print(f"dryrun ok: mesh={spec.sizes()} loss {loss:.6f} -> "
          f"{float(loss2):.6f}")
    return loss
