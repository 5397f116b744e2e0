"""OpenSHMEM-style PGAS layer: symmetric heap, put/get, atomics, scoll."""
import sys
import textwrap
from pathlib import Path

import numpy as np

from launch import tpurun as _tpurun

REPO = Path(__file__).resolve().parent.parent


def test_symmetric_heap_allocator():
    """memheap invariant: collective allocs give identical offsets, and
    free+coalesce reclaims the space."""
    from ompi_tpu.shmem import _Shmem

    heap = _Shmem.__new__(_Shmem)
    heap.heap_bytes = 1 << 12
    heap.free_list = [(0, 1 << 12)]
    a = heap.alloc(100)
    b = heap.alloc(200)
    assert a != b and a % 16 == 0 and b % 16 == 0
    heap.release(a, 100)
    heap.release(b, 200)
    c = heap.alloc(1 << 12 - 1)   # coalesced space serves a big block
    assert c == 0


def test_pgas_ring_example():
    r = _tpurun(4, [sys.executable, str(REPO / "examples" / "pgas_ring.py")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "pgas ring OK: 4 PEs, counter 10" in r.stdout


def test_shmem_put_get_atomics_colls(tmp_path):
    script = tmp_path / "shmem_all.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu.shmem as shmem
        shmem.init()
        me, n = shmem.my_pe(), shmem.n_pes()

        x = shmem.array(4, np.float64)
        x.local[:] = me * 10.0
        shmem.barrier_all()

        # get from right neighbor
        got = shmem.get(x, 4, (me + 1) % n)
        assert got.tolist() == [((me + 1) % n) * 10.0] * 4, got
        shmem.barrier_all()   # everyone done reading before anyone writes

        # put into left neighbor's second element
        shmem.p(x, 500.0 + me, (me - 1) % n, index=1)
        shmem.barrier_all()
        assert x.local[1] == 500.0 + (me + 1) % n, x.local

        # typed atomics on a shared int64 counter at PE 0
        c = shmem.array(1, np.int64)
        c.local[0] = 0
        shmem.barrier_all()
        old = shmem.atomic_fetch_add(c, 1, 0)
        assert 0 <= old < n
        shmem.barrier_all()
        if me == 0:
            assert c.local[0] == n, c.local

        # compare-and-swap: exactly one PE wins the election slot
        e = shmem.array(1, np.int64)
        e.local[0] = -1
        shmem.barrier_all()
        prev = shmem.atomic_compare_swap(e, -1, me, 0)
        shmem.barrier_all()
        winner = int(shmem.g(e, 0))
        assert 0 <= winner < n
        got_it = (prev == -1)
        wins = np.asarray(shmem._get().world.allgather(
            np.array([1 if got_it else 0], np.int64)))
        assert wins.sum() == 1, wins

        # scoll: reductions + collect
        y = shmem.array(2, np.float64)
        y.local[:] = [me + 1.0, me * 2.0]
        shmem.sum_to_all(y)
        assert y.local[0] == n * (n + 1) / 2
        z = shmem.array(1, np.int64)
        z.local[0] = me * me
        coll = shmem.collect(z)
        assert coll.tolist() == [i * i for i in range(n)], coll
        # broadcast
        b = shmem.array(3, np.float64)
        b.local[:] = me
        shmem.broadcast(b, root=2)
        assert b.local.tolist() == [2.0, 2.0, 2.0]

        shmem.barrier_all()
        print(f"shmem OK pe {me}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("shmem OK") == 4


def test_shmem_sync_locks_strided(tmp_path):
    """wait_until/test, distributed locks, iput/iget, nbi, alltoall,
    bitwise/prod reductions (shmem_lock.c / shmem_iput / wait_until)."""
    script = tmp_path / "shmem_sync.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu.shmem as shmem
        shmem.init()
        me, n = shmem.my_pe(), shmem.n_pes()

        # wait_until: PE0 signals each peer's flag word in turn
        f = shmem.array(1, np.int64)
        f.local[0] = 0
        shmem.barrier_all()
        if me == 0:
            for pe in range(1, n):
                shmem.p(f, pe * 7, pe)
            shmem.quiet()
        else:
            shmem.wait_until(f, shmem.CMP_EQ, me * 7)
            assert not shmem.test(f, shmem.CMP_NE, me * 7)

        # distributed lock protects a read-modify-write on PE 0
        lock = shmem.array(1, np.int64)
        tot = shmem.array(1, np.int64)
        lock.local[0] = 0
        tot.local[0] = 0
        shmem.barrier_all()
        for _ in range(3):
            shmem.set_lock(lock)
            v = int(shmem.g(tot, 0))
            shmem.p(tot, v + 1, 0)
            shmem.quiet()
            shmem.clear_lock(lock)
        shmem.barrier_all()
        if me == 0:
            assert tot.local[0] == 3 * n, tot.local
            # free lock: try-acquire succeeds; a second try fails until
            # the holder clears it
            assert shmem.test_lock(lock) is True
            assert shmem.test_lock(lock) is False
            shmem.clear_lock(lock)
        shmem.barrier_all()

        # strided iput/iget: write every 2nd slot of the right neighbor
        s = shmem.array(8, np.float64)
        s.local[:] = -1.0
        shmem.barrier_all()
        shmem.iput(s, np.array([me, me, me, me], float), tst=2, sst=1,
                   count=4, pe=(me + 1) % n)
        shmem.barrier_all()
        left = (me - 1) % n
        assert s.local[::2].tolist() == [left] * 4, s.local
        back = shmem.iget(s, tst=1, sst=2, count=4, pe=me)
        assert back.tolist() == [left] * 4

        # nbi put completes by quiet
        q = shmem.array(1, np.float64)
        q.local[0] = 0
        shmem.barrier_all()
        shmem.put_nbi(q, np.array([me + 1.0]), (me + 1) % n)
        shmem.quiet()
        shmem.barrier_all()
        assert q.local[0] == ((me - 1) % n) + 1.0

        # alltoall + prod/bitwise reductions
        a = shmem.array(n, np.int64)
        a.local[:] = [me * n + j for j in range(n)]
        out = shmem.alltoall(a)
        assert out.tolist() == [j * n + me for j in range(n)], out
        pr = shmem.array(1, np.int64)
        pr.local[0] = me + 1
        shmem.prod_to_all(pr)
        import math
        assert pr.local[0] == math.factorial(n)
        bw = shmem.array(1, np.int64)
        bw.local[0] = 1 << me
        shmem.or_to_all(bw)
        assert bw.local[0] == (1 << n) - 1

        shmem.barrier_all()
        print(f"shmem sync OK pe {me}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("shmem sync OK") == 4


def test_shmem_contexts_bitwise_accessibility(tmp_path):
    """shmem_ctx_* ordering domains, bitwise/set atomics, strided
    alltoalls, pe/addr accessibility, calloc/align/realloc
    (oshmem/include/shmem.h.in:180-207 families)."""
    script = tmp_path / "shmem_new.py"
    script.write_text(textwrap.dedent("""
        import numpy as np
        import ompi_tpu.shmem as shmem

        shmem.init()
        me, n = shmem.my_pe(), shmem.n_pes()

        # -- contexts: independent issue streams, implicit quiet on destroy
        flags = shmem.calloc(1, np.int64)
        shmem.barrier_all()
        ctx = shmem.ctx_create(shmem.Ctx.PRIVATE)
        ctx.atomic_add(flags, 1, pe=0)
        ctx.quiet()
        shmem.barrier_all()
        if me == 0:
            assert flags.local[0] == n, flags.local
        shmem.ctx_destroy(ctx)
        try:
            ctx.put(flags, 1, 0)
            raise SystemExit("destroyed ctx accepted an op")
        except Exception:
            pass
        # default context is always usable
        shmem.CTX_DEFAULT.fence()

        # -- bitwise + set atomics
        bits = shmem.calloc(1, np.int64)
        shmem.barrier_all()
        shmem.atomic_or(bits, 1 << me, pe=0)
        shmem.quiet()
        shmem.barrier_all()
        if me == 0:
            assert bits.local[0] == (1 << n) - 1, bits.local
        shmem.barrier_all()
        old = shmem.atomic_fetch_and(bits, ~(1 << me), pe=0)
        assert old >= 0
        shmem.barrier_all()
        if me == 0:
            assert bits.local[0] == 0, bits.local
        shmem.barrier_all()   # readers finish before the next mutation
        shmem.atomic_set(bits, 7, pe=0)
        shmem.barrier_all()
        if me == 0:
            assert bits.local[0] == 7
        shmem.barrier_all()
        x = shmem.calloc(1, np.int64)
        shmem.barrier_all()
        shmem.atomic_xor(x, me + 1, pe=(me + 1) % n)
        shmem.quiet()
        shmem.barrier_all()
        assert x.local[0] == ((me - 1) % n) + 1, x.local

        # -- strided alltoalls (spec: src index sst*(j*ne+k))
        ne, sst, dst = 2, 2, 3
        a = shmem.array(dst * n * ne, np.int64)
        a.local[:] = -1
        a.local[: sst * n * ne : sst] = [
            me * 100 + v for v in range(n * ne)]
        shmem.barrier_all()
        got = shmem.alltoalls(a, dst=dst, sst=sst, nelems=ne)
        want = []
        for j in range(n):
            want += [j * 100 + me * ne, j * 100 + me * ne + 1]
        assert got.tolist() == want, (got.tolist(), want)
        assert a.local[: dst * n * ne : dst].tolist() == want

        # -- accessibility + ptr
        assert shmem.pe_accessible(me) and shmem.pe_accessible(0)
        assert not shmem.pe_accessible(n) and not shmem.pe_accessible(-1)
        assert shmem.addr_accessible(a, (me + 1) % n)
        ptr = shmem.shmem_ptr(a, me)
        assert ptr is not None and ptr[0] == a.local[0]

        # -- allocation variants
        c = shmem.calloc(8, np.float32)
        assert c.local.tolist() == [0.0] * 8
        al = shmem.align(256, 4, np.float64)
        assert al.offset % 256 == 0
        al.local[:] = me
        r = shmem.realloc(al, 8)
        assert r.count == 8 and r.local[:4].tolist() == [me] * 4

        print(f"SHMEM NEW OK {me}", flush=True)
        shmem.finalize()
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.stdout.count("SHMEM NEW OK") == 4, r.stdout + r.stderr
    assert r.returncode == 0, r.stdout + r.stderr


def test_shmem_global_exit(tmp_path):
    """shmem_global_exit terminates every PE with the given status."""
    script = tmp_path / "gexit.py"
    script.write_text(textwrap.dedent("""
        import time
        import ompi_tpu.shmem as shmem

        shmem.init()
        shmem.barrier_all()
        if shmem.my_pe() == 1:
            shmem.global_exit(3)
        time.sleep(30)   # never reached on any PE if global_exit works
        print("SURVIVED", flush=True)
    """))
    r = _tpurun(3, [sys.executable, str(script)], timeout=60)
    assert "SURVIVED" not in r.stdout, r.stdout + r.stderr
    assert r.returncode != 0


def test_shmem_active_set_barrier_sync_info(tmp_path):
    """shmem_barrier/sync over a (PE_start, logPE_stride, PE_size)
    active set + the info/version and deprecated cache no-op surface."""
    script = tmp_path / "aset.py"
    script.write_text("""
import numpy as np
import ompi_tpu.shmem as sh

sh.init()
me, n = sh.my_pe(), sh.n_pes()
assert sh.info_get_version()[0] >= 1
assert "shmem" in sh.info_get_name()
sh.set_cache_inv(); sh.udcflush(); sh.clear_cache_line_inv(0)

flag = sh.array(4, np.int64)
flag.local[:] = 0
# active set = even PEs (stride 2^1): they barrier among themselves
# while odd PEs only make the collective split calls
evens = list(range(0, n, 2))
if me in evens:
    sh.p(flag, me + 1, me, index=me)
    sh.barrier(0, 1, len(evens))     # quiet + subset barrier
    sh.barrier(0, 1, len(evens))     # repeat: cached comm, no re-split
    # after the subset barrier every even PE sees every even PE's put
    for pe in evens:
        got = sh.g(flag, pe, index=pe)
        assert got == pe + 1, (me, pe, got)
else:
    pass   # odd PEs NEVER call: create_group is non-collective over
           # the world — the OpenSHMEM active-set contract
sh.sync_all()
sh.sync(0, 0, n)                     # whole-world active set
sh.barrier()                         # default = all PEs
sh.finalize()
print("aset ok", flush=True)
""")
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("aset ok") == 4
