"""One-sided RMA: windows, put/get/accumulate/atomics, fence/lock/PSCW
(SURVEY.md §2.3 osc framework)."""
import functools
import textwrap
from pathlib import Path

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.runtime import init as rt

from launch import tpurun

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def world():
    rt.reset_for_testing()
    w = ompi_tpu.init()
    yield w
    rt.reset_for_testing()


class TestLocalWindows:
    def test_create_put_get(self, world):
        win = ompi_tpu.Win.create(world, size=8)
        win.put(np.arange(4, dtype=np.float64), target=1, offset=2)
        got = win.get(4, target=1, offset=2)
        assert got.tolist() == [0.0, 1.0, 2.0, 3.0]
        assert win.get(1, target=1, offset=0)[0] == 0.0
        win.free()

    def test_accumulate_and_fetch(self, world):
        win = ompi_tpu.Win.create(world, size=4)
        win.accumulate(np.ones(4), target=0)
        win.accumulate(np.ones(4) * 2, target=0)
        assert win.get(4, target=0).tolist() == [3.0] * 4
        old = win.get_accumulate(np.ones(4), target=0)
        assert old.tolist() == [3.0] * 4
        assert win.get(4, target=0).tolist() == [4.0] * 4
        win.free()

    def test_fetch_and_op_cas(self, world):
        win = ompi_tpu.Win.create(world, size=2)
        assert win.fetch_and_op(5.0, target=0) == 0.0
        assert win.fetch_and_op(3.0, target=0) == 5.0
        assert win.compare_and_swap(9.0, compare=8.0, target=0) == 8.0
        assert win.get(1, target=0)[0] == 9.0
        win.free()

    def test_expose_existing_base(self, world):
        base = np.arange(6, dtype=np.int64)
        win = ompi_tpu.Win.create(world, base=base)
        assert win.get(3, target=world.rank, offset=3).tolist() == [3, 4, 5]
        win.put(np.array([99]), target=world.rank, offset=0)
        assert base[0] == 99  # window exposes, not copies, my own base
        win.free()

    def test_sync_noops_and_free(self, world):
        win = ompi_tpu.Win.create(world, size=2)
        win.fence()
        win.lock(0)
        win.unlock(0)
        win.lock_all()
        win.unlock_all()
        win.flush_all()
        win.free()
        with pytest.raises(Exception):
            win.put(np.zeros(1), 0)


_tpurun = functools.partial(tpurun, timeout=420)


class TestMultiprocessRma:
    def test_put_get_fence(self, tmp_path):
        script = tmp_path / "rma.py"
        script.write_text(textwrap.dedent("""
            import numpy as np, ompi_tpu
            w = ompi_tpu.init()
            win = ompi_tpu.Win.create(w, size=8)
            win.fence()
            # everyone writes its rank into slot [rank] of the right neighbor
            t = (w.rank + 1) % w.size
            win.put(np.array([float(w.rank)]), target=t, offset=w.rank)
            win.fence()
            left = (w.rank - 1) % w.size
            assert win.local[left] == float(left), win.local
            # direct remote read of the left neighbor's region
            got = win.get(1, target=left, offset=(left - 1) % w.size)
            assert got[0] == float((left - 1) % w.size)
            win.fence()
            win.free()
            if w.rank == 0:
                print("RMA FENCE OK")
            ompi_tpu.finalize()
        """))
        r = _tpurun(4, script)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "RMA FENCE OK" in r.stdout

    def test_passive_lock_accumulate(self, tmp_path):
        script = tmp_path / "lockacc.py"
        script.write_text(textwrap.dedent("""
            import numpy as np, ompi_tpu
            w = ompi_tpu.init()
            win = ompi_tpu.Win.create(w, size=1)
            # all ranks atomically add into rank 0's counter under lock
            for _ in range(10):
                win.lock(0, win.LOCK_SHARED)
                win.accumulate(np.ones(1), target=0)
                win.unlock(0)
            w.barrier()
            if w.rank == 0:
                assert win.local[0] == 10.0 * w.size, win.local
                print("RMA LOCK OK")
            win.free()
            ompi_tpu.finalize()
        """))
        r = _tpurun(4, script)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "RMA LOCK OK" in r.stdout

    def test_exclusive_lock_read_modify_write(self, tmp_path):
        script = tmp_path / "excl.py"
        script.write_text(textwrap.dedent("""
            import numpy as np, ompi_tpu
            w = ompi_tpu.init()
            win = ompi_tpu.Win.create(w, size=1)
            # non-atomic get+put forced atomic by the exclusive lock
            for _ in range(5):
                win.lock(0, win.LOCK_EXCLUSIVE)
                cur = win.get(1, target=0)[0]
                win.put(np.array([cur + 1.0]), target=0)
                win.unlock(0)
            w.barrier()
            if w.rank == 0:
                assert win.local[0] == 5.0 * w.size, win.local
                print("RMA EXCL OK")
            win.free()
            ompi_tpu.finalize()
        """))
        r = _tpurun(4, script)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "RMA EXCL OK" in r.stdout

    def test_fetch_and_op_global_counter(self, tmp_path):
        script = tmp_path / "fao.py"
        script.write_text(textwrap.dedent("""
            import numpy as np, ompi_tpu
            w = ompi_tpu.init()
            win = ompi_tpu.Win.create(w, size=1, dtype=np.int64)
            # classic ticket counter: each rank draws 5 unique tickets
            tickets = [int(win.fetch_and_op(1, target=0)) for _ in range(5)]
            w.barrier()
            all_t = w.allgather(np.array(tickets, dtype=np.int64))
            if w.rank == 0:
                flat = sorted(np.asarray(all_t).ravel().tolist())
                assert flat == list(range(5 * w.size)), flat
                print("RMA FAO OK")
            win.free()
            ompi_tpu.finalize()
        """))
        r = _tpurun(4, script)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "RMA FAO OK" in r.stdout

    def test_pscw(self, tmp_path):
        script = tmp_path / "pscw.py"
        script.write_text(textwrap.dedent("""
            import numpy as np, ompi_tpu
            from ompi_tpu.api.group import Group
            w = ompi_tpu.init()
            win = ompi_tpu.Win.create(w, size=4)
            others = Group([r for r in range(w.size) if r != w.rank])
            win.post(others)      # expose to everyone else
            win.start(others)     # access everyone else
            for t in range(w.size):
                if t != w.rank:
                    win.put(np.array([float(w.rank)]), target=t,
                            offset=w.rank % 4)
            win.complete()
            win.wait()
            for r in range(w.size):
                if r != w.rank:
                    assert win.local[r % 4] == float(r), win.local
            if w.rank == 0:
                print("RMA PSCW OK")
            win.free()
            ompi_tpu.finalize()
        """))
        r = _tpurun(4, script)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "RMA PSCW OK" in r.stdout


def test_dynamic_window_attach_detach(tmp_path):
    """MPI_Win_create_dynamic + attach/detach: RMA into regions exposed
    after window creation (``ompi/mpi/c/win_create_dynamic.c``)."""
    import textwrap

    script = tmp_path / "dyn.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu
        from ompi_tpu.api.win import Win

        w = ompi_tpu.init()
        win = Win.create_dynamic(w)
        mem = np.full(4, w.rank * 10.0)
        h = win.attach_region(mem)
        # share my handle with everyone (the app-level address exchange
        # real MPI dynamic windows also need)
        handles = w.allgather(np.array([h], np.int64))
        handles = [int(np.ravel(x)[0]) for x in np.asarray(handles)]
        w.barrier()
        peer = (w.rank + 1) % w.size
        got = win.get(4, peer, offset=0, region=handles[peer])
        assert got.tolist() == [peer * 10.0] * 4, got
        win.put(np.array([99.0]), peer, offset=1, region=handles[peer])
        win.fence()
        w.barrier()
        assert mem[1] == 99.0, mem
        win.detach_region(h)
        w.barrier()   # both sides detached before probing
        # detached region: gets raise, puts are dropped (erroneous per MPI)
        from ompi_tpu.api.errors import MpiError
        try:
            win.get(4, peer, offset=0, region=handles[peer])
            raise AssertionError("get from detached region succeeded")
        except MpiError:
            pass
        w.barrier()
        win.free()
        print(f"DYN OK {w.rank}")
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("DYN OK") == 2
