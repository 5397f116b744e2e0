"""Collective tests on the 8-virtual-device world: coll/xla device
collectives vs numpy references, conductor host collectives, selection."""
import numpy as np
import pytest

import ompi_tpu


@pytest.fixture(scope="module")
def world():
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    w = ompi_tpu.init()
    if w.size != 8:
        pytest.skip("needs 8 virtual devices")
    yield w
    rt.reset_for_testing()


@pytest.fixture(scope="module")
def xla(world):
    from ompi_tpu.mca.coll.xla import XlaCollModule

    return next(m for m in world.coll_modules
                if isinstance(m, XlaCollModule))


def _world_data(xla, shape=(4,), dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    host = rng.standard_normal((8, *shape)).astype(dtype)
    return host, xla.make_world_array(host)


def test_selection_order(world):
    # xla (90) must own the *_array slots; conductor (40) the host slots
    assert world.c_coll["allreduce_array"].__self__.__class__.__name__ \
        == "XlaCollModule"
    assert world.c_coll["allreduce"].__self__.__class__.__name__ \
        == "ConductorModule"


def test_device_allreduce_sum(world, xla):
    host, dev = _world_data(xla)
    out = np.asarray(world.allreduce_array(dev))
    np.testing.assert_allclose(out, host.sum(0), rtol=1e-5)


def test_device_allreduce_max_min(world, xla):
    from ompi_tpu.api import op

    host, dev = _world_data(xla, seed=1)
    np.testing.assert_allclose(
        np.asarray(world.allreduce_array(dev, op.MAX)), host.max(0))
    np.testing.assert_allclose(
        np.asarray(world.allreduce_array(dev, op.MIN)), host.min(0))


def test_device_allreduce_prod_band(world, xla):
    from ompi_tpu.api import op

    host = np.ones((8, 3), np.float32) * 2
    dev = xla.make_world_array(host)
    np.testing.assert_allclose(
        np.asarray(world.allreduce_array(dev, op.PROD)), host.prod(0))
    hosti = (np.arange(24).reshape(8, 3) % 7 + 1).astype(np.int32)
    devi = xla.make_world_array(hosti)
    np.testing.assert_array_equal(
        np.asarray(world.allreduce_array(devi, op.BAND)),
        np.bitwise_and.reduce(hosti, 0))


def test_gathered_stack_keeps_the_rows_of_128_kernel(world, xla):
    """allreduce of an op with no native collective hands its gathered
    (n, S) stack to ``reduce_stack`` with rank 3: on the chip a gathered
    stack lies a row at a time, which the (k, rows, 128) blocks read
    with no copy, and the 2-D block (``otpu_reduce_stack_rows``, for a
    program input) would need one."""
    import re

    import jax

    from ompi_tpu.api import op

    P = xla._P
    fn = xla._shard_map(lambda t: xla._reduce_in_shard(op.PROD)(t[0]),
                        P(xla.axis), P(), name="otpu_allreduce_prod")
    x = xla.make_world_array(np.ones((8, 1024), np.float32))
    names = re.findall(r"name=(otpu_reduce_stack\w*)",
                       str(jax.make_jaxpr(fn)(x)))
    assert names == ["otpu_reduce_stack"]


def test_device_bcast(world, xla):
    host, dev = _world_data(xla, seed=2)
    out = np.asarray(world.bcast_array(dev, root=3))
    for i in range(8):
        np.testing.assert_allclose(out[i], host[3], rtol=1e-6)


def test_device_allgather(world, xla):
    host, dev = _world_data(xla, seed=3)
    out = np.asarray(world.allgather_array(dev))
    np.testing.assert_allclose(out, host, rtol=1e-6)


def test_device_reduce_scatter(world, xla):
    host = np.random.default_rng(4).standard_normal((8, 8, 5)) \
        .astype(np.float32)
    dev = xla.make_world_array(host)
    out = np.asarray(world.reduce_scatter_array(dev))
    # rank i's block = sum over ranks of block i
    expect = host.sum(0)  # (8, 5)
    np.testing.assert_allclose(out.reshape(8, 5), expect, rtol=1e-4)


def test_device_alltoall(world, xla):
    host = np.arange(8 * 8 * 2, dtype=np.float32).reshape(8, 8, 2)
    dev = xla.make_world_array(host)
    out = np.asarray(world.alltoall_array(dev))
    np.testing.assert_array_equal(out, np.swapaxes(host, 0, 1))


def test_device_ppermute_ring(world, xla):
    host, dev = _world_data(xla, seed=5)
    perm = [(i, (i + 1) % 8) for i in range(8)]
    out = np.asarray(world.ppermute_array(dev, perm))
    np.testing.assert_allclose(out, np.roll(host, 1, axis=0), rtol=1e-6)


def test_device_barrier(world):
    world.barrier()  # conductor host barrier → device barrier; must not hang


def test_host_collectives(world):
    from ompi_tpu.api import op

    host = np.arange(16, dtype=np.float64).reshape(8, 2)
    np.testing.assert_allclose(world.allreduce(host), host.sum(0))
    np.testing.assert_allclose(world.allgather(host), host)
    np.testing.assert_allclose(world.reduce(host, op.MAX), host.max(0))
    np.testing.assert_allclose(world.scan(host), np.cumsum(host, 0))
    ex = world.exscan(host)
    assert np.all(ex[0] == 0)
    np.testing.assert_allclose(ex[1:], np.cumsum(host, 0)[:-1])
    a2a = np.arange(8 * 8, dtype=np.int64).reshape(8, 8)
    np.testing.assert_array_equal(world.alltoall(a2a), a2a.T)
    rs = world.reduce_scatter(np.ones((8, 16), np.float32))
    assert np.asarray(rs).shape == (8, 2)
    assert np.all(np.asarray(rs) == 8)


def test_nonblocking_host(world):
    req = world.iallreduce(np.ones((8, 2), np.float32))
    req.wait()
    np.testing.assert_allclose(req.result, np.full(2, 8.0))
    world.ibarrier().wait()


def test_agree(world):
    assert world.agree(0b1011) == 0b1011


def test_comm_self_collectives():
    from ompi_tpu.runtime import init as rt

    s = rt.comm_self()
    assert s.size == 1
    out = s.allreduce(np.array([3.0]))
    assert out[0] == 3.0
    assert s.c_coll["allreduce"].__self__.__class__.__name__ \
        == "SelfCollModule"


def test_comm_dup_split(world):
    d = world.dup()
    assert d.cid != world.cid and d.size == 8
    halves = world.split(color=0 if world.rank < 4 else 1, key=0)
    assert halves is not None
    d.free()


def test_split_device_subcomm(world, xla):
    """Splitting the device world yields a sub-mesh communicator whose
    coll/xla runs on the member devices only."""
    sub = world.create(world.group.incl([0, 2, 4, 6]))
    assert sub is not None and sub.size == 4
    from ompi_tpu.mca.coll.xla import XlaCollModule

    submod = [m for m in sub.coll_modules if isinstance(m, XlaCollModule)]
    assert submod, "coll/xla must select on the sub-communicator"
    host = np.ones((4, 3), np.float32)
    out = np.asarray(sub.allreduce_array(submod[0].make_world_array(host)))
    np.testing.assert_allclose(out, np.full(3, 4.0))


def test_device_reduce_root_semantics(world, xla):
    host, dev = _world_data(xla, seed=10)
    out = np.asarray(world.reduce_array(dev, root=2))
    np.testing.assert_allclose(out[2], host.sum(0), rtol=1e-5)
    for i in (0, 1, 3, 7):
        np.testing.assert_array_equal(out[i], np.zeros_like(out[i]))


def test_device_gather_root_semantics(world, xla):
    host, dev = _world_data(xla, seed=11)
    out = np.asarray(world.gather_array(dev, root=5))
    np.testing.assert_allclose(out[5], host, rtol=1e-6)
    assert not out[0].any() and not out[7].any()


def test_device_scatter_from_root(world, xla):
    # per-rank buffers (8, 8, 3); only root's row is significant
    rng = np.random.default_rng(12)
    host = rng.standard_normal((8, 8, 3)).astype(np.float32)
    dev = xla.make_world_array(host)
    out = np.asarray(world.scatter_array(dev, root=4))
    # rank i receives root's block i
    np.testing.assert_allclose(out, host[4], rtol=1e-6)


def test_device_scan_exscan(world, xla):
    host, dev = _world_data(xla, seed=13)
    out = np.asarray(world.scan_array(dev))
    np.testing.assert_allclose(out, np.cumsum(host, 0), rtol=1e-4)
    ex = np.asarray(world.exscan_array(dev))
    np.testing.assert_array_equal(ex[0], np.zeros_like(ex[0]))
    np.testing.assert_allclose(ex[1:], np.cumsum(host, 0)[:-1], rtol=1e-4)


def test_device_allgatherv(world, xla):
    host = np.random.default_rng(14).standard_normal((8, 4, 2)) \
        .astype(np.float32)
    dev = xla.make_world_array(host)
    counts = [1, 2, 3, 4, 4, 3, 2, 1]
    outs = world.allgatherv_array(dev, counts)
    for i, o in enumerate(outs):
        np.testing.assert_allclose(np.asarray(o), host[i, :counts[i]],
                                   rtol=1e-6)


def test_device_alltoallv(world, xla):
    host = np.arange(8 * 8 * 3, dtype=np.float32).reshape(8, 8, 3)
    dev = xla.make_world_array(host)
    # asymmetric so a counts[i][j]/counts[j][i] transpose bug is caught
    counts = [[(2 * i + j) % 4 for j in range(8)] for i in range(8)]
    outs = world.alltoallv_array(dev, counts)
    for i in range(8):
        for j in range(8):
            np.testing.assert_array_equal(
                np.asarray(outs[i][j]), host[j, i, :counts[j][i]])


def test_persistent_allreduce(world, xla):
    host, dev = _world_data(xla, seed=15)
    h = world.allreduce_array_init(dev)
    out = np.asarray(h(dev))
    np.testing.assert_allclose(out, host.sum(0), rtol=1e-5)
    req = h.start(dev)
    req.wait()
    np.testing.assert_allclose(np.asarray(req.result), host.sum(0),
                               rtol=1e-5)
    # same shape/op/dtype shares the compiled program with the eager path
    assert h.fn is xla._cache[("allreduce", "SUM", dev.shape, dev.dtype)][0]


def test_spc_device_counters_bump(world, xla):
    from ompi_tpu.runtime import spc

    before = spc.read("device_collectives")
    host, dev = _world_data(xla, seed=16)
    world.allreduce_array(dev)
    assert spc.read("device_collectives") >= before + 1


def test_alltoallw_per_peer_dtypes(world):
    """MPI_Alltoallw: per-peer buffers and datatypes
    (``ompi/mpi/c/alltoallw.c``) — conductor matrix form."""
    n = world.size
    # sendbufs[src][dst]: int32 to even receivers, float64 to odd
    sendbufs = [[np.array([s], np.int32) if d % 2 == 0
                 else np.array([s + 0.5], np.float64) for d in range(n)]
                for s in range(n)]
    recvtypes = [np.int32 if r % 2 == 0 else np.float64 for r in range(n)]
    out = world.alltoallw(sendbufs, recvtypes)
    for r in range(n):
        for s in range(n):
            got = out[r][s][0]
            assert got == (s if r % 2 == 0 else s + 0.5), (r, s, got)
