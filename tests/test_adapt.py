"""coll/adapt — event-driven segmented bcast/reduce (off by default)."""
import sys
import textwrap
from pathlib import Path

from launch import tpurun as _tpurun

REPO = Path(__file__).resolve().parent.parent


def test_adapt_pipelined_bcast_reduce(tmp_path):
    script = tmp_path / "adapt.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        w = ompi_tpu.init()
        r = w.rank
        mod = w.c_coll['bcast'].__self__
        assert type(mod).__name__ == 'AdaptModule', type(mod).__name__
        # many 4k segments pipeline through the binomial tree
        data = np.arange(5000, dtype=np.float64)
        out = w.bcast(data if r == 2 else np.zeros(5000), root=2)
        assert np.array_equal(out, data)
        red = w.reduce(np.full(3000, float(r + 1)), root=1)
        if r == 1:
            assert np.allclose(red, sum(range(1, w.size + 1)))
        else:
            assert red is None
        # the nonblocking form is the native one
        req = mod.ibcast(w, data if r == 0 else np.zeros(5000), root=0)
        req.wait()
        w.barrier()
        print(f"adapt OK rank {r}")
    """))
    r = _tpurun(4, [sys.executable, str(script)],
                extra=("--mca", "coll_adapt_priority", "60",
                       "--mca", "coll_adapt_segsize", "4k"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("adapt OK") == 4


def test_adapt_disabled_by_default(tmp_path):
    script = tmp_path / "noadapt.py"
    script.write_text(textwrap.dedent("""
        import ompi_tpu
        w = ompi_tpu.init()
        assert type(w.c_coll['bcast'].__self__).__name__ != 'AdaptModule'
        print("noadapt OK")
    """))
    r = _tpurun(2, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("noadapt OK") == 2
