"""osc/rdma — mapped-window one-sided RMA (no target-side agent).

Re-creates the osc/pt2pt multiprocess scenarios on the direct path the
reference implements in ``ompi/mca/osc/rdma/``: put/get as direct stores,
accumulate under the native accumulate lock, CAS-backed passive locks, and
message-free PSCW over shared counters.
"""
import sys
import textwrap
from pathlib import Path

import pytest

from ompi_tpu import native

from launch import tpurun as _tpurun

# every scenario here asserts RdmaModule SELECTION, and osc/rdma's
# comm_query requires the native atomics — without the toolchain the
# same jobs run correctly on osc/pt2pt (covered by test_osc.py), so
# there is nothing rdma-specific left to test
pytestmark = pytest.mark.skipif(not native.available(),
                                reason="osc/rdma needs native atomics")

REPO = Path(__file__).resolve().parent.parent


def test_rdma_selected_and_put_get_fence(tmp_path):
    script = tmp_path / "rdma1.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.win import Win
        w = ompi_tpu.init()
        r = w.rank
        win = Win.create(w, size=8, dtype=np.float64)
        assert type(win.module).__name__ == 'RdmaModule', type(win.module)
        # no servicing agent thread: the one-sided property
        assert not hasattr(win.module, '_agent')
        win.local[:] = r * 1.0
        win.fence()
        # everyone writes its rank into the right neighbor's slot r
        win.put(np.array([100.0 + r]), (r + 1) % w.size, offset=r)
        win.fence()
        # and reads the left neighbor's whole region: its writer was
        # rank left-1, who wrote 100+writer at offset writer
        left = (r - 1) % w.size
        writer = (left - 1) % w.size
        got = win.get(8, left, offset=0)
        assert got[writer] == 100.0 + writer, got
        win.fence()
        win.free()
        print(f"rdma putget OK rank {r}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("rdma putget OK") == 4


def test_rdma_accumulate_and_fetch_op(tmp_path):
    script = tmp_path / "rdma2.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.win import Win
        w = ompi_tpu.init()
        r = w.rank
        win = Win.create(w, size=2, dtype=np.int64)
        assert type(win.module).__name__ == 'RdmaModule'
        win.fence()
        # concurrent atomic accumulates into rank 0's counter
        for _ in range(50):
            win.accumulate(np.array([1], np.int64), 0, offset=0)
        win.fence()
        if r == 0:
            assert win.local[0] == 50 * w.size, win.local
        # fetch_and_op global ticket counter at rank 0 slot 1
        t = int(win.fetch_and_op(1, 0, offset=1))
        assert 0 <= t < w.size
        win.fence()
        if r == 0:
            assert win.local[1] == w.size
        win.free()
        print(f"rdma acc OK rank {r}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("rdma acc OK") == 4


def test_rdma_passive_lock_and_cas(tmp_path):
    script = tmp_path / "rdma3.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.win import Win
        w = ompi_tpu.init()
        r = w.rank
        win = Win.create(w, size=4, dtype=np.int64)
        win.fence()
        # exclusive-lock read-modify-write on rank 0 (lock via CAS word)
        for _ in range(25):
            win.lock(0, Win.LOCK_EXCLUSIVE)
            v = win.get(1, 0, offset=0)
            win.put(v + 1, 0, offset=0)
            win.unlock(0)
        w.barrier()
        if r == 0:
            assert win.local[0] == 25 * w.size, win.local
        # native int64 CAS: single winner election
        old = win.compare_and_swap(r + 1, 0, 0, offset=2)
        wins = np.asarray(w.allgather(
            np.array([1 if old == 0 else 0], np.int64)))
        assert wins.sum() == 1, wins
        win.fence()
        win.free()
        print(f"rdma lock OK rank {r}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("rdma lock OK") == 4


def test_rdma_pscw(tmp_path):
    """PSCW epochs ride shared counters — zero messages, zero agent."""
    script = tmp_path / "rdma4.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.win import Win
        w = ompi_tpu.init()
        r = w.rank
        win = Win.create(w, size=4, dtype=np.float64)
        origin_group = w.group.incl([0]) if hasattr(w.group, 'incl') else None
        from ompi_tpu.api.group import Group
        origins = Group([w.group.world_rank(0)])
        targets = Group([w.group.world_rank(1)])
        if r == 1:
            win.post(origins)       # expose to rank 0
            win.wait()
            assert win.local[2] == 77.5, win.local
        elif r == 0:
            win.start(targets)
            win.put(np.array([77.5]), 1, offset=2)
            win.complete()
        w.barrier()
        win.free()
        print(f"rdma pscw OK rank {r}")
    """))
    r = _tpurun(2, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("rdma pscw OK") == 2


def test_rdma_excluded_falls_back_to_pt2pt(tmp_path):
    script = tmp_path / "rdma5.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.win import Win
        w = ompi_tpu.init()
        win = Win.create(w, size=2, dtype=np.float64)
        assert type(win.module).__name__ == 'Pt2ptModule', type(win.module)
        win.fence()
        win.put(np.array([5.0]), (w.rank + 1) % w.size, offset=0)
        win.fence()
        assert win.local[0] == 5.0
        win.free()
        print("fallback OK")
    """))
    r = _tpurun(2, [sys.executable, str(script)],
                extra=("--mca", "osc", "^rdma"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("fallback OK") == 2


def test_shared_query_and_request_rma(tmp_path):
    """MPI_Win_allocate_shared + shared_query direct load/store view, and
    the request-based Rput/Rget family (``win_shared_query.c``,
    ``rput.c``)."""

    script = tmp_path / "wsq.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu
        from ompi_tpu.api.win import Win

        w = ompi_tpu.init()
        win, buf = Win.allocate_shared(w, 8, np.float64)
        buf[:] = w.rank * 100.0
        win.fence()
        # direct view of the right neighbour's memory (same node: shm)
        peer = (w.rank + 1) % w.size
        view = win.shared_query(peer)
        assert view[0] == peer * 100.0, view
        win.fence()
        # request-based RMA
        r1 = win.rput(np.array([7.0]), peer, offset=1)
        r1.wait()
        win.flush(peer)
        r2 = win.rget(2, peer, offset=0)
        r2.wait()
        got = r2.result
        assert got[1] == 7.0, got
        win.fence()
        win.free()
        print(f"WSQ OK {w.rank}")
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("WSQ OK") == 2
