"""Process topologies: dims_create, cart/graph/dist_graph, cart_sub,
neighbor collectives (SURVEY.md §2.3 topo framework)."""
import functools
import textwrap
from pathlib import Path

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.api.errors import MpiError
from ompi_tpu.api.status import PROC_NULL
from ompi_tpu.mca.topo import CartTopo, GraphTopo, dims_create
from ompi_tpu.runtime import init as rt

from launch import tpurun

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def world():
    rt.reset_for_testing()
    w = ompi_tpu.init()
    yield w
    rt.reset_for_testing()


class TestDimsCreate:
    def test_balanced_factorization(self):
        assert dims_create(8, 3) == [2, 2, 2]
        assert dims_create(12, 2) == [4, 3]
        assert dims_create(7, 2) == [7, 1]
        assert dims_create(24, 3) == [4, 3, 2]

    def test_fixed_dims_honored(self):
        assert dims_create(8, 2, [2, 0]) == [2, 4]
        assert dims_create(8, 2, [0, 8]) == [1, 8]
        with pytest.raises(MpiError):
            dims_create(7, 2, [2, 0])  # 7 not divisible by 2

    def test_exact_fixed(self):
        assert dims_create(6, 2, [2, 3]) == [2, 3]
        with pytest.raises(MpiError):
            dims_create(8, 2, [2, 3])


class TestCartTopo:
    def test_rank_coords_roundtrip(self):
        t = CartTopo([2, 4], [False, False])
        for r in range(8):
            assert t.rank_of(t.coords_of(r)) == r
        assert t.coords_of(5) == [1, 1]
        assert t.rank_of([1, 1]) == 5

    def test_shift_nonperiodic_edges(self):
        t = CartTopo([4], [False])
        assert t.shift(0, 0, 1) == (PROC_NULL, 1)
        assert t.shift(3, 0, 1) == (2, PROC_NULL)
        assert t.shift(1, 0, 1) == (0, 2)

    def test_shift_periodic_wraps(self):
        t = CartTopo([4], [True])
        assert t.shift(0, 0, 1) == (3, 1)
        assert t.shift(3, 0, 1) == (2, 0)

    def test_graph_neighbors(self):
        # square: 0-1, 0-3, 1-2, 2-3
        g = GraphTopo([2, 4, 6, 8], [1, 3, 0, 2, 1, 3, 0, 2])
        assert g.neighbors_of(0) == [1, 3]
        assert g.neighbors_of(2) == [1, 3]


class TestDeviceWorldCart:
    def test_cart_create_and_accessors(self, world):
        if world.size < 8:
            pytest.skip("needs 8 ranks")
        cart = world.cart_create([2, 4], periods=[True, False])
        assert cart is not None
        dims, periods, coords = cart.cart_get()
        assert dims == [2, 4] and periods == [True, False]
        assert cart.cart_rank(coords) == cart.rank
        src, dst = cart.cart_shift(1, 1)
        if coords[1] == 3:
            assert dst == PROC_NULL
        cart.free()

    def test_cart_excludes_extra_ranks(self, world):
        if world.size < 8:
            pytest.skip("needs 8 ranks")
        # 6-rank grid on an 8-rank comm: top facade ranks get None
        high = world.as_rank(world.size - 1)
        assert high.cart_create([2, 3]) is None

    def test_cart_sub_splits_axes(self, world):
        if world.size < 8:
            pytest.skip("needs 8 ranks")
        cart = world.cart_create([2, 4])
        row = cart.cart_sub([False, True])   # keep the 4-axis
        assert row.size == 4
        assert row.topo.dims == [4]
        col = cart.cart_sub([True, False])
        assert col.size == 2
        assert col.topo.dims == [2]

    def test_neighbor_allgather_conductor(self, world):
        if world.size < 8:
            pytest.skip("needs 8 ranks")
        cart = world.cart_create([8], periods=[True])
        table = np.arange(8, dtype=np.int64)[:, None] * 10
        got = cart.neighbor_allgather(table)
        # ring: neighbors of rank 0 are 7 (minus) and 1 (plus)
        assert got[0][0] == 70 and got[1][0] == 10

    def test_neighbor_alltoall_conductor(self, world):
        if world.size < 8:
            pytest.skip("needs 8 ranks")
        cart = world.cart_create([8], periods=[True])
        # rank r sends [r, 0] to its minus neighbor, [r, 1] to its plus
        bufs = np.array([[[r, 0], [r, 1]] for r in range(8)], np.int64)
        got = cart.neighbor_alltoall(bufs)
        # slot 0 (from minus neighbor 7): 7 sent its plus-slot [7, 1]
        assert got[0].tolist() == [7, 1]
        # slot 1 (from plus neighbor 1): 1 sent its minus-slot [1, 0]
        assert got[1].tolist() == [1, 0]


_tpurun = functools.partial(tpurun, timeout=240)


class TestMultiprocessTopo:
    def test_halo_exchange(self, tmp_path):
        script = tmp_path / "halo.py"
        script.write_text(textwrap.dedent("""
            import numpy as np, ompi_tpu
            w = ompi_tpu.init()
            cart = w.cart_create([2, 2], periods=[True, True])
            dims, periods, coords = cart.cart_get()
            # 1-D halo along each axis via cart_shift + sendrecv
            local = np.full(4, float(cart.rank))
            for d in range(2):
                src, dst = cart.cart_shift(d, 1)
                halo = np.zeros(4)
                cart.sendrecv(local, dst, halo, src)
                expect = cart.cart_rank(
                    [(c - (1 if i == d else 0)) % dims[i]
                     for i, c in enumerate(coords)])
                assert halo[0] == float(expect), (d, halo, expect)
            # neighbor allgather: 4 slots (2 dims x minus/plus)
            got = cart.neighbor_allgather(local)
            assert len(got) == 4
            if w.rank == 0:
                print("TOPO HALO OK")
            ompi_tpu.finalize()
        """))
        r = _tpurun(4, script)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "TOPO HALO OK" in r.stdout


def test_topo_test_and_type_introspection(world):
    """MPI_Topo_test + MPI_Type_get_contents/set_name
    (``ompi/mpi/c/topo_test.c``, ``type_get_contents.c``)."""
    assert world.topo_test() == "undefined"
    cart = world.cart_create([world.size], periods=[True])
    assert cart.topo_test() == "cart"
    cart.free()

    from ompi_tpu.datatype import FLOAT32, vector

    dt = vector(3, 2, 5, FLOAT32)
    comb, contents = dt.get_envelope()
    assert comb == "vector"
    assert dt.get_contents() == contents
    dt.set_name("my_vec")
    assert dt.get_name() == "my_vec"
    d2 = dt.dup()
    assert d2.get_envelope()[0] == "dup"
