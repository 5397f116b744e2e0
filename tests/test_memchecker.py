"""memchecker — buffer-ownership checking (valgrind-annotation analog)."""
import sys
import textwrap
from pathlib import Path

from launch import tpurun as _tpurun

REPO = Path(__file__).resolve().parent.parent


def test_racy_write_to_inflight_send_buffer_caught(tmp_path):
    script = tmp_path / "mc.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        w = ompi_tpu.init()
        n = 1 << 18                       # rendezvous-sized
        if w.rank == 0:
            data = np.arange(n, dtype=np.float64)
            req = w.isend(data, 1, tag=7)
            try:
                data[0] = 999.0           # write while MPI owns the buffer
                raise SystemExit("memchecker missed the racy write")
            except ValueError:
                print("racy write caught")
            req.wait()
            data[0] = 999.0               # completed: writable again
        else:
            buf = np.zeros(n)
            w.recv(buf, 0, tag=7)
            assert buf[0] == 0.0 and buf[-1] == n - 1   # data uncorrupted
        w.barrier()
        print(f"mc OK rank {w.rank}")
    """))
    r = _tpurun(2, [sys.executable, str(script)],
                extra=("--mca", "memchecker_enable", "1"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "racy write caught" in r.stdout
    assert r.stdout.count("mc OK") == 2


def test_disabled_by_default(tmp_path):
    script = tmp_path / "mc_off.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        w = ompi_tpu.init()
        n = 1 << 18
        if w.rank == 0:
            data = np.arange(n, dtype=np.float64)
            req = w.isend(data, 1, tag=7)
            req.wait()
            data[0] = 1.0    # no guard when disabled
        else:
            buf = np.zeros(n)
            w.recv(buf, 0, tag=7)
        w.barrier()
        print("off OK")
    """))
    r = _tpurun(2, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("off OK") == 2
