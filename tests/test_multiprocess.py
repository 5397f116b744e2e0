"""Multi-process integration: tpurun + coordination service + btl/sm+tcp +
coll/basic — the ``mpirun -n N`` smoke tests of SURVEY §4."""
import sys
import textwrap
from pathlib import Path

import pytest

from launch import tpurun as _tpurun

REPO = Path(__file__).resolve().parent.parent


def test_mp_ring():
    r = _tpurun(4, [sys.executable, str(REPO / "examples" / "ring.py")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "token now 0" in r.stdout


def test_mp_connectivity_sm_and_tcp_only():
    r = _tpurun(4, [sys.executable, str(REPO / "examples" / "connectivity.py")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "connectivity OK: 4 ranks" in r.stdout
    # force the tcp path (exclude shared memory)
    r2 = _tpurun(3, ["--mca", "btl", "^sm",
                     sys.executable, str(REPO / "examples" / "connectivity.py")])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "connectivity OK: 3 ranks" in r2.stdout


def test_mp_collectives_and_split(tmp_path):
    script = tmp_path / "coll.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        w = ompi_tpu.init()
        r = w.rank
        assert w.allreduce(np.array([float(r + 1)]))[0] == 10.0
        g = w.allgather(np.array([r * 10]))
        assert g.ravel().tolist() == [0, 10, 20, 30]
        assert w.scan(np.array([1]))[0] == r + 1
        assert w.exscan(np.array([1]))[0] == r
        a2a = w.alltoall(np.arange(4, dtype=np.int64) + 100 * r)
        assert a2a.ravel().tolist() == [r, 100 + r, 200 + r, 300 + r], a2a
        b = w.bcast(np.array([7.5]) if r == 2 else np.zeros(1), root=2)
        assert b[0] == 7.5
        sub = w.split(color=r % 2, key=-r)
        assert sub.size == 2
        # key=-r reverses rank order inside each color
        assert sub.rank == (1 if r < 2 else 0)
        rs = w.reduce_scatter(np.ones(8, np.float32))
        assert rs.tolist() == [4.0, 4.0]
        w.barrier()
        if r == 0:
            print("MP COLLECTIVES OK")
        ompi_tpu.finalize()
    """))
    r = _tpurun(4, [sys.executable, str(script)], timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MP COLLECTIVES OK" in r.stdout


def test_mp_rendezvous_large_message(tmp_path):
    script = tmp_path / "big.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        w = ompi_tpu.init()
        n = 1 << 18  # 2MB float64 >> sm/tcp eager limits -> RNDV path
        if w.rank == 0:
            data = np.arange(n, dtype=np.float64)
            w.send(data, dest=1, tag=5)
        elif w.rank == 1:
            buf = np.zeros(n, np.float64)
            st = w.recv(buf, source=0, tag=5)
            assert st._nbytes == n * 8
            assert buf[0] == 0 and buf[-1] == n - 1
            assert np.all(buf == np.arange(n))
            print("RNDV OK")
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, [sys.executable, str(script)], timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "RNDV OK" in r.stdout


def test_tpurun_failure_teardown(tmp_path):
    script = tmp_path / "fail.py"
    script.write_text(textwrap.dedent("""
        import sys, time, os
        if int(os.environ["OTPU_RANK"]) == 1:
            sys.exit(3)
        time.sleep(30)
    """))
    r = _tpurun(3, [sys.executable, str(script)], timeout=60)
    assert r.returncode == 3
    assert "terminated with exit code 3" in r.stderr


def test_mp_alltoallv_typed_and_alltoallw(tmp_path):
    """Host alltoallv returns rank r's block typed as sendbufs[r].dtype
    (regression: remote blocks used to come back as raw uint8 while the
    self block stayed typed); alltoallw retypes per peer."""
    script = tmp_path / "a2av.py"
    script.write_text("""
import numpy as np
import ompi_tpu

ompi_tpu.init()
w = ompi_tpu.COMM_WORLD
me, n = w.rank, w.size
rng = np.random.default_rng(5)              # same plan on every rank
base = rng.standard_normal((n, n, 40))
cnts = rng.integers(0, 40, (n, n))
send = [base[me, j, : cnts[me][j]].astype(np.float32) for j in range(n)]
got = w.alltoallv(send)
for src in range(n):
    blk = got[src]
    assert blk.dtype == np.float32, (src, blk.dtype)
    assert np.allclose(blk, base[src, me, : cnts[src][me]]
                       .astype(np.float32)), src
# w-variant: heterogeneous per-peer dtypes via recvtypes
send_w = [np.arange(4 + me, dtype=np.int64) if (me + j) % 2 == 0
          else np.arange(4 + me, dtype=np.float32) for j in range(n)]
rts = [np.int64 if (j + me) % 2 == 0 else np.float32 for j in range(n)]
got_w = w.alltoallw(send_w, rts)
for src in range(n):
    assert got_w[src].dtype == np.dtype(rts[src]), (src, got_w[src].dtype)
    assert np.allclose(got_w[src].astype(np.float64),
                       np.arange(4 + src)), src
if me == 0:
    print("a2av typed ok", flush=True)
ompi_tpu.finalize()
""")
    r = _tpurun(3, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "a2av typed ok" in r.stdout
