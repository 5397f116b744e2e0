"""Breadth components: coll/inter, coll/sync, hook/comm_method, mpisync."""
import sys
import textwrap
from pathlib import Path

from launch import tpurun as _tpurun

REPO = Path(__file__).resolve().parent.parent


def test_intercomm_collectives(tmp_path):
    """coll/inter: two-group semantics over a connect/accept bridge."""
    script = tmp_path / "inter.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.status import PROC_NULL, ROOT
        w = ompi_tpu.init()
        r = w.rank
        side = w.split(0 if r < 2 else 1)
        inter = (side.accept("ic-port") if r < 2
                 else side.connect("ic-port"))
        assert type(inter.c_coll['allreduce'].__self__).__name__ \\
            == 'InterCollModule'

        inter.barrier()

        # each group receives the OTHER group's sum
        out = inter.allreduce(np.array([float(r + 1)]))
        expect = 3.0 + 4.0 if r < 2 else 1.0 + 2.0   # other group's ranks+1
        assert out[0] == expect, (r, out)

        # allgather: the other group's rows
        g = inter.allgather(np.array([r], np.int64))
        expect_rows = [2, 3] if r < 2 else [0, 1]
        assert np.asarray(g).ravel().tolist() == expect_rows, g

        # rooted bcast from group A rank 1 into group B
        if r == 1:
            inter.bcast(np.array([9.25]), ROOT)
        elif r == 0:
            inter.bcast(np.zeros(1), PROC_NULL)
        else:
            got = inter.bcast(np.zeros(1), 1)   # root's rank in its group
            assert got[0] == 9.25, got

        # rooted reduce: group B's sum lands at group A rank 0
        if r == 0:
            red = inter.reduce(np.zeros(1), root=ROOT)
            assert red[0] == (2 + 1) + (3 + 1), red
        elif r == 1:
            inter.reduce(np.zeros(1), root=PROC_NULL)
        else:
            inter.reduce(np.array([float(r + 1)]), root=0)

        inter.barrier()
        print(f"inter OK rank {r}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("inter OK") == 4


def test_coll_sync_injects_barriers(tmp_path):
    script = tmp_path / "sync.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        w = ompi_tpu.init()
        fn = w.c_coll['bcast']
        assert getattr(fn, '__sync_wrapped__', False), 'sync not interposed'
        # storm of rooted collectives; sync's barriers keep queues bounded
        for i in range(25):
            out = w.bcast(np.array([float(i)]) if w.rank == 0
                          else np.zeros(1), root=0)
            assert out[0] == float(i)
        print("sync OK")
    """))
    r = _tpurun(2, [sys.executable, str(script)],
                extra=("--mca", "coll_sync_barrier_after", "5"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("sync OK") == 2


def test_hook_comm_method_matrix(tmp_path):
    script = tmp_path / "hook.py"
    script.write_text("import ompi_tpu; ompi_tpu.init()\n")
    r = _tpurun(3, [sys.executable, str(script)],
                extra=("--mca", "hook_comm_method_display", "1"))
    assert r.returncode == 0, r.stdout + r.stderr
    # every rank printed its transport row; sm serves same-host peers
    assert r.stdout.count("[comm_method]") == 3
    assert "sm" in r.stdout


def test_era_tree_agreement_with_failure(tmp_path):
    """The ERA-shaped tree agreement (default algorithm) stays uniform
    when a participant dies mid-stream; the kv algorithm remains
    selectable."""
    script = tmp_path / "era.py"
    script.write_text(textwrap.dedent("""
        import os, time
        import numpy as np, ompi_tpu
        from ompi_tpu.api.errors import ProcFailedError
        from ompi_tpu.api.errhandler import ERRORS_RETURN
        w = ompi_tpu.init()
        w.set_errhandler(ERRORS_RETURN)  # ULFM apps opt out of abort
        r = w.rank
        assert w.agree(1) == 1          # clean round over the tree
        if r == 1:
            os._exit(1)                 # die before the next round
        deadline = time.time() + 30
        while time.time() < deadline and not w.get_failed().size:
            time.sleep(0.1)
        # next agreement: survivors agree uniformly and all observe the
        # unacknowledged failure
        try:
            w.agree(1)
            raise SystemExit("expected ProcFailedError")
        except ProcFailedError as exc:
            assert exc.flag == 1
        w.ack_failed()
        assert w.agree(1) == 1          # acknowledged: clean again
        print(f"era ft OK rank {r}")
    """))
    r = _tpurun(4, [sys.executable, str(script)], timeout=120,
                extra=("--enable-recovery",))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("era ft OK") == 3

    # the coordinator-decides algorithm remains selectable
    script2 = tmp_path / "kv.py"
    script2.write_text(textwrap.dedent("""
        import ompi_tpu
        w = ompi_tpu.init()
        assert w.agree(1) == 1
        print("kv agree OK")
    """))
    r2 = _tpurun(2, [sys.executable, str(script2)],
                 extra=("--mca", "coll_ftagree_algorithm", "kv"))
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert r2.stdout.count("kv agree OK") == 2


def test_mpisync_clock_offsets():
    r = _tpurun(3, [sys.executable, "-m", "ompi_tpu.tools.mpisync"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "rank offset_us rtt_us" in r.stdout
    # rows for ranks 1 and 2 with numeric offsets
    lines = [l for l in r.stdout.splitlines() if l.startswith("[0] ")]
    # peer rows only: rank column != 0 (the reference-clock row)
    data = [l for l in lines
            if l.split()[1].isdigit() and l.split()[1] != "0"]
    assert len(data) == 2, r.stdout
