"""Seeded protocol-crossover stress: mixed message sizes, tags and
orderings driven across every host-path protocol boundary in one job —
eager (<=512k), RNDV, RGET (>512k), multi-rail striping (>2m) — plus a
mixed-collective soak against numpy goldens.  The reference leans on
external suites (ompi-tests/MTT) for this class of coverage; here it is
in-tree and deterministic (fixed seed)."""
import functools
import os
import textwrap

from launch import tpurun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


_tpurun = functools.partial(tpurun, timeout=420)


def test_p2p_protocol_crossover_stress(tmp_path):
    script = tmp_path / "p2p_stress.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu

        w = ompi_tpu.init()
        rng = np.random.default_rng(1234)     # same stream on both ranks
        # sizes straddle every protocol boundary: eager<=512k, rndv/rget
        # >512k, striping >2m; plus odd sizes and 1-byte messages
        sizes = [1, 7, 1024, 65536, 262144, 524287, 524289,
                 1 << 20, (2 << 20) + 13, 3 << 20]
        NOPS = 60
        plan = [(int(rng.integers(len(sizes))), int(rng.integers(50)),
                 int(rng.integers(2))) for _ in range(NOPS)]
        peer = 1 - w.rank
        for i, (si, tag, nb) in enumerate(plan):
            n = sizes[si]
            if w.rank == 0:
                data = (np.arange(n, dtype=np.uint8) + i) % 251
                if nb:
                    w.isend(data, dest=peer, tag=tag).wait()
                else:
                    w.send(data, dest=peer, tag=tag)
            else:
                buf = np.empty(n, np.uint8)
                st = w.recv(buf, source=0, tag=tag)
                want = (np.arange(n, dtype=np.uint8) + i) % 251
                assert np.array_equal(buf, want), (i, n, tag)
        # reverse direction with several in-flight irecvs (ooo matching)
        if w.rank == 1:
            for i in range(8):
                n = sizes[i % len(sizes)]
                w.send((np.arange(n, dtype=np.uint8) * 3 + i) % 249,
                       dest=0, tag=100 + i)
        else:
            reqs, bufs = [], []
            for i in range(8):
                n = sizes[i % len(sizes)]
                bufs.append(np.empty(n, np.uint8))
                reqs.append(w.irecv(bufs[-1], source=1, tag=100 + i))
            for i, r in enumerate(reqs):
                r.wait()
                n = sizes[i % len(sizes)]
                want = (np.arange(n, dtype=np.uint8) * 3 + i) % 249
                assert np.array_equal(bufs[i], want), i
        print(f"P2P STRESS OK {w.rank}", flush=True)
        ompi_tpu.finalize()
    """))
    r = _tpurun(2, script)
    assert r.stdout.count("P2P STRESS OK") == 2, r.stdout + r.stderr
    assert r.returncode == 0, r.stdout + r.stderr


def test_collective_mixed_size_soak(tmp_path):
    script = tmp_path / "coll_soak.py"
    script.write_text(textwrap.dedent("""
        import numpy as np, ompi_tpu
        from ompi_tpu.api import op

        w = ompi_tpu.init()
        n = w.size
        rng = np.random.default_rng(77)       # same stream on all ranks
        # sizes straddle the coll/sm slot boundary (2m) and the tuned
        # ladder breakpoints
        sizes = [8, 1000, 65536, 262144, 1 << 20, (2 << 20) + 40]
        for it in range(12):
            nel = sizes[int(rng.integers(len(sizes)))] // 8
            coll = int(rng.integers(4))
            base = np.arange(nel, dtype=np.float64)
            mine = base * (w.rank + 1) + it
            all_rows = np.stack([base * (r + 1) + it for r in range(n)])
            if coll == 0:
                got = w.allreduce(mine)
                np.testing.assert_allclose(got, all_rows.sum(0), rtol=1e-12)
            elif coll == 1:
                got = w.allreduce(mine, op.MAX)
                np.testing.assert_allclose(got, all_rows.max(0))
            elif coll == 2:
                got = w.bcast(mine if w.rank == it % n else
                              np.empty_like(mine), root=it % n)
                np.testing.assert_allclose(
                    got, base * (it % n + 1) + it)
            else:
                got = w.allgather(mine)
                np.testing.assert_allclose(np.asarray(got), all_rows)
        w.barrier()
        print(f"COLL SOAK OK {w.rank}", flush=True)
        ompi_tpu.finalize()
    """))
    r = _tpurun(4, script)
    assert r.stdout.count("COLL SOAK OK") == 4, r.stdout + r.stderr
    assert r.returncode == 0, r.stdout + r.stderr
