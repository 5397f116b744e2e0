"""Ragged-collective edge cases ahead of MoE dispatch traffic: zero-count
contributions, ranks receiving nothing, empty slabs, and single-member
communicators must round-trip without the caller special-casing —
fuzzed count matrices over the host (``comm.alltoallv``) and device
(``*v_array`` / ``ops.pallas_collectives``) paths."""
import os
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import ompi_tpu

import launch

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def mesh():
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) != 8:
        pytest.skip("needs 8 virtual devices")
    return Mesh(np.array(devs), ("x",))


def _check_a2av(mesh, x, counts):
    from ompi_tpu.ops import pallas_collectives as pc

    out = np.asarray(pc.all_to_all_v(x, counts, mesh, "x"))
    assert out.shape == x.shape
    n = x.shape[0]
    for i in range(n):
        for j in range(n):
            c = int(counts[i, j])
            np.testing.assert_array_equal(out[j, i, :c], x[i, j, :c],
                                          err_msg=f"pair {i}->{j}")


def test_device_a2av_fuzzed_count_matrices(mesh):
    """Seeded fuzz over count matrices with forced degenerate rows and
    columns: a rank that contributes nothing (all-zero row) and a rank
    that receives nothing (all-zero column) must round-trip like any
    other raggedness — no special-casing at the call site."""
    n, R, W = 8, 11, 128
    rng = np.random.default_rng(1234)
    for trial in range(4):
        x = rng.standard_normal((n, n, R, W)).astype(np.float32)
        counts = rng.integers(0, R + 1, (n, n)).astype(np.int32)
        counts[int(rng.integers(n))] = 0        # sends nothing
        counts[:, int(rng.integers(n))] = 0     # receives nothing
        _check_a2av(mesh, x, counts)


def test_device_a2av_all_zero_counts(mesh):
    n, R, W = 8, 5, 128
    x = np.random.default_rng(0).standard_normal(
        (n, n, R, W)).astype(np.float32)
    _check_a2av(mesh, x, np.zeros((n, n), np.int32))


def test_device_a2av_empty_slab(mesh):
    """R == 0: every count clamps to zero valid rows and the exchange
    degenerates to a shape-preserving no-op (regression: building a
    zero-row kernel used to fail in interpret-mode DMA discharge)."""
    from ompi_tpu.ops import pallas_collectives as pc

    n, W = 8, 128
    x = np.zeros((n, n, 0, W), np.float32)
    out = np.asarray(pc.all_to_all_v(x, np.zeros((n, n), np.int32),
                                     mesh, "x"))
    assert out.shape == (n, n, 0, W)
    # malformed counts still surface on the degenerate path
    with pytest.raises(ValueError, match="counts"):
        pc.all_to_all_v(x, np.zeros((n,), np.int32), mesh, "x")


def test_device_agv_fuzzed_counts_and_empty_slab(mesh):
    from ompi_tpu.ops import pallas_collectives as pc

    n, R, W = 8, 9, 128
    rng = np.random.default_rng(99)
    for trial in range(4):
        x = rng.standard_normal((n, R, W)).astype(np.float32)
        counts = rng.integers(0, R + 1, n).astype(np.int32)
        counts[int(rng.integers(n))] = 0        # contributes nothing
        out = np.asarray(pc.all_gather_v(x, counts, mesh, "x"))
        for i in range(n):
            c = int(counts[i])
            np.testing.assert_array_equal(out[i, :c], x[i, :c])
    # R == 0 slab (regression: zero-row kernel build)
    empty = np.zeros((n, 0, W), np.float32)
    out = np.asarray(pc.all_gather_v(empty, np.zeros(n, np.int32),
                                     mesh, "x"))
    assert out.shape == (n, 0, W)
    with pytest.raises(ValueError, match="counts"):
        pc.all_gather_v(empty, np.zeros((n, 2), np.int32), mesh, "x")


def test_device_single_member_mesh_roundtrip():
    """n == 1 communicator: ragged exchange is the identity, including
    on an empty slab."""
    import jax
    from jax.sharding import Mesh

    from ompi_tpu.ops import pallas_collectives as pc

    mesh1 = Mesh(np.array(jax.devices()[:1]), ("x",))
    x = np.arange(3 * 128, dtype=np.float32).reshape(1, 1, 3, 128)
    out = np.asarray(pc.all_to_all_v(x, np.array([[2]], np.int32),
                                     mesh1, "x"))
    np.testing.assert_array_equal(out[0, 0, :2], x[0, 0, :2])
    g = np.asarray(pc.all_gather_v(x[0], np.array([2], np.int32),
                                   mesh1, "x"))
    np.testing.assert_array_equal(g[0, :2], x[0, 0, :2])
    e = np.asarray(pc.all_to_all_v(np.zeros((1, 1, 0, 128), np.float32),
                                   np.zeros((1, 1), np.int32),
                                   mesh1, "x"))
    assert e.shape == (1, 1, 0, 128)


def test_component_alltoallv_array_zero_rows_and_cols():
    """The in-process device-comm path (``comm.alltoallv_array``)
    returns correctly-typed zero-length views for zero-count cells."""
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    w = ompi_tpu.init()
    try:
        if w.size != 8:
            pytest.skip("needs 8 virtual devices")
        n, R, W = 8, 6, 128
        rng = np.random.default_rng(7)
        host = rng.standard_normal((n, n, R, W)).astype(np.float32)
        counts = rng.integers(0, R + 1, (n, n))
        counts[3] = 0       # rank 3 sends nothing
        counts[:, 5] = 0    # rank 5 receives nothing
        outs = w.alltoallv_array(host, counts)
        for i in range(n):
            for j in range(n):
                blk = np.asarray(outs[i][j])
                c = int(counts[j][i])
                assert blk.shape[0] == c, (i, j)
                np.testing.assert_array_equal(blk, host[j, i, :c])
        assert all(np.asarray(b).shape[0] == 0 for b in outs[5])
    finally:
        rt.reset_for_testing()


def test_host_alltoallv_self_comm_zero_and_empty():
    """Single-member host communicator (coll/self): alltoallv returns
    the send buffer unchanged, including a zero-length one."""
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    ompi_tpu.init()
    try:
        s = ompi_tpu.COMM_SELF
        blk = np.arange(4, dtype=np.float32)
        out = s.alltoallv([blk])
        np.testing.assert_array_equal(np.asarray(out[0]), blk)
        out0 = s.alltoallv([np.zeros(0, np.float32)])
        assert np.asarray(out0[0]).shape == (0,)
    finally:
        rt.reset_for_testing()


def test_mp_host_alltoallv_zero_count_cells(tmp_path):
    """Multi-process host path (btl wire + probe/recv): forced
    zero-count cells — one rank sends nothing to anyone, another
    receives nothing from anyone — round-trip typed and exact."""
    script = tmp_path / "a2av_zero.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu

        ompi_tpu.init()
        w = ompi_tpu.COMM_WORLD
        me, n = w.rank, w.size
        rng = np.random.default_rng(11)          # same plan on every rank
        base = rng.standard_normal((n, n, 24))
        cnts = rng.integers(0, 24, (n, n))
        cnts[1] = 0        # rank 1 sends nothing
        cnts[:, 2] = 0     # rank 2 receives nothing
        send = [base[me, j, : cnts[me][j]].astype(np.float32)
                for j in range(n)]
        got = w.alltoallv(send)
        for src in range(n):
            blk = np.asarray(got[src])
            assert blk.dtype == np.float32, (src, blk.dtype)
            assert blk.shape[0] == cnts[src][me], (src, blk.shape)
            assert np.allclose(blk, base[src, me, : cnts[src][me]]
                               .astype(np.float32)), src
        if me == 2:
            assert all(np.asarray(b).shape[0] == 0 for b in got)
        # allgatherv with a zero contribution from rank 0
        gcnt = [0 if r == 0 else 5 for r in range(n)]
        gout = w.allgatherv(base[me, 0, : gcnt[me]].astype(np.float32))
        for r in range(n):
            g = np.asarray(gout[r]).view(np.float32)
            assert g.shape[0] == gcnt[r], (r, g.shape)
            assert np.allclose(g, base[r, 0, : gcnt[r]]
                               .astype(np.float32)), r
        w.barrier()
        if me == 0:
            print("RAGGED ZERO OK")
        ompi_tpu.finalize()
    """))
    env = dict(os.environ, JAX_PLATFORMS="cpu", OTPU_SANITIZE="1")
    env.pop("OTPU_RANK", None)
    env.pop("OTPU_NPROCS", None)
    r = launch.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun", "-n", "3",
         sys.executable, str(script)],
        180, env)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-1500:]
    assert "RAGGED ZERO OK" in r.stdout
