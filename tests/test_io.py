"""MPI-IO: File object, views, individual + collective I/O, sharedfp.

Mirrors the reference's io test strategy (SURVEY §4): datatype-view
round-trips single-process, then tpurun multi-rank collective I/O with the
two-phase fcoll path, ending in the SURVEY Phase-6 payoff — a sharded-array
checkpoint written and restored through subarray file views across 4 ranks.
"""
import os
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from launch import tpurun as _tpurun

REPO = Path(__file__).resolve().parent.parent


# -- single-process: views + fbtl ---------------------------------------

def test_view_extents_contiguous_and_vector():
    from ompi_tpu.datatype import FLOAT64, core
    from ompi_tpu.mca.io.ompio import view_extents

    # contiguous byte view
    runs = list(view_extents(0, core.BYTE, 3, 5))
    assert runs == [(3, 5)]
    # vector view: 2 doubles every 4 doubles → stream maps to strided file
    ft = core.vector(2, 2, 4, FLOAT64)
    runs = list(view_extents(100, ft, 0, 48))
    # tile = 32 data bytes over extent 8*... : first tile two blocks of 16
    assert runs[0] == (100, 16)
    assert runs[1] == (100 + 32, 16)
    assert sum(ln for _, ln in runs) == 48


def test_file_individual_roundtrip(tmp_path):
    import ompi_tpu
    from ompi_tpu.api.file import File

    path = str(tmp_path / "ind.dat")
    w = ompi_tpu.init()
    f = File.open(ompi_tpu.COMM_SELF, path, "c+")
    data = np.arange(100, dtype=np.float32)
    assert f.write_at(0, data) == 400
    back = np.zeros(100, np.float32)
    assert f.read_at(0, back) == 100
    assert np.array_equal(back, data)
    # individual pointer I/O
    f.seek(0)
    f.write(np.array([7, 8, 9], np.int64))
    assert f.get_position() == 24
    f.seek(8)
    one = np.zeros(1, np.int64)
    f.read(one)
    assert one[0] == 8
    assert f.get_size() == 400
    f.set_size(16)
    assert f.get_size() == 16
    f.close()
    File.delete(path)
    assert not os.path.exists(path)


def test_file_strided_view(tmp_path):
    """A vector filetype interleaves two ranks' columns in one file."""
    import ompi_tpu
    from ompi_tpu.api.file import File
    from ompi_tpu.datatype import FLOAT64, core

    path = str(tmp_path / "view.dat")
    f = File.open(ompi_tpu.COMM_SELF, path, "c+")
    # even slots through a 1-every-2 vector view
    ft = core.vector(4, 1, 2, FLOAT64)
    f.set_view(0, FLOAT64, ft)
    f.write_at(0, np.array([1., 2., 3., 4.]))
    # odd slots: same view displaced one double
    f.set_view(8, FLOAT64, ft)
    f.write_at(0, np.array([10., 20., 30., 40.]))
    f.set_view(0, FLOAT64, FLOAT64)   # flat view
    allv = np.zeros(8)
    f.read_at(0, allv)
    assert allv.tolist() == [1., 10., 2., 20., 3., 30., 4., 40.]
    f.close()


def test_file_datatype_buffer_triple(tmp_path):
    """Non-contiguous MEMORY through the convertor pack/unpack path."""
    import ompi_tpu
    from ompi_tpu.api.file import File
    from ompi_tpu.datatype import FLOAT64, core

    from ompi_tpu.api.errors import MpiError

    path = str(tmp_path / "triple.dat")
    f = File.open(ompi_tpu.COMM_SELF, path, "c+")
    mem = np.arange(8, dtype=np.float64)
    # memory type: every other element (vector 4x1 stride 2)
    mt = core.vector(4, 1, 2, FLOAT64)
    f.write_at(0, (mem, 1, mt))            # writes 0,2,4,6
    back = np.zeros(4)
    f.read_at(0, back)
    assert back.tolist() == [0., 2., 4., 6.]
    # read back into strided memory
    dst = np.zeros(8)
    f.read_at(0, (dst, 1, mt))
    assert dst.tolist() == [0., 0., 2., 0., 4., 0., 6., 0.]
    # pointer-based triple read: advances by the STREAM size (32 bytes),
    # not the destination array's 64 bytes
    f.seek(0)
    dst2 = np.zeros(8)
    f.read((dst2, 1, mt))
    assert f.get_position() == 32
    assert dst2.tolist() == [0., 0., 2., 0., 4., 0., 6., 0.]
    with pytest.raises(MpiError):
        f.seek(0, whence=9)
    f.close()
    with pytest.raises(MpiError):
        f.write(np.zeros(1))   # closed file must error, not hit a stale fd


def test_file_errors(tmp_path):
    import ompi_tpu
    from ompi_tpu.api.errors import MpiError
    from ompi_tpu.api.file import File

    with pytest.raises(MpiError):
        File.delete(str(tmp_path / "missing.dat"))
    f = File.open(ompi_tpu.COMM_SELF, str(tmp_path / "e.dat"), "c+")
    f.close()
    with pytest.raises(MpiError):
        f.read_at(0, np.zeros(1))
    with pytest.raises(MpiError):
        File.open(ompi_tpu.COMM_SELF, str(tmp_path / "e.dat"), "cx+")


# -- multi-process: collective I/O + sharedfp ---------------------------

def test_mp_collective_write_read(tmp_path):
    """4 ranks interleave blocks via write_at_all (two-phase), read back
    with read_at_all, and exercise the shared file pointer."""
    path = tmp_path / "coll.dat"
    script = tmp_path / "coll_io.py"
    script.write_text(textwrap.dedent(f"""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.file import File
        w = ompi_tpu.init()
        r = w.rank
        f = File.open(w, {str(path)!r}, "c+")
        # rank r owns bytes [r*32, (r+1)*32): contiguous blocks
        # (offsets are in etype units = bytes under the default view)
        data = np.full(8, float(r), np.float32)
        f.write_at_all(r * 32, data)
        # overlapping read: everyone reads the whole file collectively
        back = np.zeros(32, np.float32)
        f.read_at_all(0, back)
        expect = np.repeat(np.arange(4, dtype=np.float32), 8)
        assert np.array_equal(back, expect), back
        # shared file pointer: every rank appends one record; records are
        # disjoint and cover 4 slots
        f.set_view(128, None, None)   # past the collective region
        rec = np.full(2, 100.0 + r, np.float32)
        f.write_shared(rec)
        w.barrier()
        tail = np.zeros(8, np.float32)
        f.read_at(0, tail)
        got = sorted(set(tail.tolist()))
        assert got == [100.0, 101.0, 102.0, 103.0], tail
        f.close()
        # reopening must start the shared pointer at 0 again (no leak of
        # the previous open's counter)
        f3 = File.open(w, {str(path)!r}, "+")
        f3.set_view(128, None, None)
        one = np.zeros(2, np.float32)
        f3.read_shared(one)
        assert one[0] in (100.0, 101.0, 102.0, 103.0), one
        f3.close()
        print(f"coll io OK rank {{r}}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("coll io OK") == 4


def test_mp_sharded_checkpoint_subarray(tmp_path):
    """SURVEY Phase-6 payoff: a (8, 8) global array sharded 2x2 across 4
    ranks checkpoints through subarray file views with write_at_all and
    restores through the same views — and the file equals the dense
    row-major global array."""
    path = tmp_path / "ckpt.dat"
    script = tmp_path / "ckpt.py"
    script.write_text(textwrap.dedent(f"""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.file import File
        from ompi_tpu.datatype import FLOAT64, core
        w = ompi_tpu.init()
        r = w.rank
        G, B = 8, 4                     # global 8x8, 4x4 blocks, 2x2 grid
        gi, gj = divmod(r, 2)
        block = (np.arange(B * B, dtype=np.float64).reshape(B, B)
                 + 100.0 * r)
        ft = core.subarray([G, G], [B, B], [gi * B, gj * B],
                           core.ORDER_C, FLOAT64)
        f = File.open(w, {str(path)!r}, "c+")
        f.set_view(0, FLOAT64, ft)
        f.write_at_all(0, block)        # collective checkpoint
        f.close()

        # restore through the same view
        f2 = File.open(w, {str(path)!r}, "r")
        f2.set_view(0, FLOAT64, ft)
        back = np.zeros((B, B))
        f2.read_at_all(0, back)
        assert np.array_equal(back, block), (r, back)
        f2.close()

        # rank 0 validates the dense file layout
        if r == 0:
            whole = np.fromfile({str(path)!r}, np.float64).reshape(G, G)
            for rr in range(4):
                i, j = divmod(rr, 2)
                expect = (np.arange(16, dtype=np.float64).reshape(4, 4)
                          + 100.0 * rr)
                assert np.array_equal(
                    whole[i*4:(i+1)*4, j*4:(j+1)*4], expect), rr
        w.barrier()
        print(f"checkpoint OK rank {{r}}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("checkpoint OK") == 4


def test_mp_two_aggregator_fcoll(tmp_path):
    """Force 2 aggregators so the aggregator-to-aggregator piece exchange
    path runs (the deadlock-prone corner of two-phase I/O)."""
    path = tmp_path / "agg2.dat"
    script = tmp_path / "agg2.py"
    script.write_text(textwrap.dedent(f"""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.file import File
        w = ompi_tpu.init()
        r = w.rank
        f = File.open(w, {str(path)!r}, "c+")
        # strided interleave: rank r writes 4-byte words at stride 4
        data = np.full(64, r + 1, np.uint8)
        f.write_at_all(r * 64, data)
        back = np.zeros(256, np.uint8)
        f.read_at_all(0, back)
        expect = np.repeat(np.arange(1, 5, dtype=np.uint8), 64)
        assert np.array_equal(back, expect)
        f.close()
        print(f"agg2 OK rank {{r}}")
    """))
    r = _tpurun(4, [sys.executable, str(script)],
                extra=("--mca", "io_ompio_num_aggregators", "2"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("agg2 OK") == 4


def test_mp_fcoll_dynamic_ragged_pattern(tmp_path):
    """fcoll/dynamic_gen2 analog: a ragged pattern — two dense data
    islands separated by a huge hole.  Static address stripes would give
    one aggregator nearly all bytes (the hole splits the span, not the
    data); the dynamic strategy negotiates equal accessed-byte shares
    from the ranks' extents.  Runs the SAME pattern under both forced
    strategies plus auto (which must pick dynamic here), and all three
    files must agree byte-for-byte."""
    for alg in ("dynamic", "static", "auto"):
        path = tmp_path / f"rag_{alg}.dat"
        script = tmp_path / f"rag_{alg}.py"
        script.write_text(textwrap.dedent(f"""
            import numpy as np, ompi_tpu
            from ompi_tpu.api.file import File
            from ompi_tpu.datatype import core
            w = ompi_tpu.init()
            r = w.rank
            f = File.open(w, {str(path)!r}, "c+")
            # ONE collective call spans two 1KB data islands 1MB apart
            # (vector view: 2 blocks of 256B, 1MB stride): the spanned
            # region is ~0.2% data -> the auto heuristic must go dynamic
            ft = core.vector(2, 256, 1 << 20, core.BYTE)
            f.set_view(r * 256, core.BYTE, ft)
            data = np.concatenate([
                np.full(256, 10 * (r + 1), np.uint8),
                np.full(256, 10 * (r + 1) + 5, np.uint8)])
            f.write_at_all(0, data)
            mod = f.io_module
            assert mod.last_fcoll_alg == {("dynamic" if alg == "auto"
                                           else alg)!r}, \\
                (mod.last_fcoll_alg, {alg!r})
            f.set_view(0, core.BYTE, core.BYTE)
            back = np.zeros(1024, np.uint8)
            f.read_at_all(0, back)
            expect = np.repeat(np.arange(1, 5, dtype=np.uint8) * 10, 256)
            assert np.array_equal(back, expect), back[::256]
            back2 = np.zeros(1024, np.uint8)
            f.read_at_all(1 << 20, back2)
            assert np.array_equal(
                back2, np.repeat(np.arange(1, 5, dtype=np.uint8) * 10 + 5,
                                 256)), back2[::256]
            f.close()
            print(f"ragged {alg} OK rank {{r}}")
        """))
        r = _tpurun(4, [sys.executable, str(script)],
                    extra=("--mca", "io_ompio_num_aggregators", "2",
                           "--mca", "io_ompio_fcoll", alg))
        assert r.returncode == 0, (alg, r.stdout + r.stderr)
        assert r.stdout.count(f"ragged {alg} OK") == 4, (alg, r.stdout)
    ref = (tmp_path / "rag_dynamic.dat").read_bytes()
    assert (tmp_path / "rag_static.dat").read_bytes() == ref
    assert (tmp_path / "rag_auto.dat").read_bytes() == ref


def test_fcoll_domain_partitioning_unit():
    """The dynamic partition balances ACCESSED bytes: two islands of
    equal size with a huge hole between them -> with 2 aggregators the
    cut lands in the hole, one island per aggregator (static would hand
    both islands to aggregator 0 when the hole dominates the right
    half... or split island A)."""
    from ompi_tpu.mca.io.ompio import OmpioModule

    class FakeComm:
        size = 2
        rank = 0

        def allgatherv(self, flat):
            import numpy as np
            # rank 0: island A [0, 1000); rank 1: island B [10**6, 10**6+1000)
            return [np.array([0, 1000], np.int64),
                    np.array([1 << 20, 1000], np.int64)]

    class FakeComponent:
        class fcoll_var:
            value = "dynamic"

        class num_aggs_var:
            value = 2

    mod = OmpioModule.__new__(OmpioModule)
    mod._c = FakeComponent
    aggs, edges = mod._file_domains(FakeComm(), [[0, 1000]])
    assert len(edges) == 3
    # the cut must land between the islands, giving each agg ~1000 bytes
    assert 1000 <= edges[1] <= (1 << 20), edges
    # routing splits a run crossing the cut
    pieces = list(OmpioModule._route(edges, 900, 200))
    assert sum(t for _, _, t in pieces) == 200


def test_split_collectives(tmp_path):
    """MPI_File_*_all_begin/end semantics: one outstanding split
    collective per handle, matching end, same buffer at end
    (``ompi/mpi/c/file_read_all_begin.c`` family)."""
    from ompi_tpu.api import file as fmod

    path = str(tmp_path / "split.bin")
    f = fmod.File.open(None, path, fmod.MODE_CREATE | fmod.MODE_RDWR)
    data = np.arange(8, dtype=np.int32)
    f.write_all_begin(data)
    with pytest.raises(RuntimeError):       # one outstanding per handle
        f.write_all_begin(data)
    with pytest.raises(RuntimeError):       # mismatched end kind
        f.read_all_end(data)
    assert f.write_all_end(data) == data.nbytes
    with pytest.raises(RuntimeError):       # end without begin
        f.write_all_end(data)

    f.seek(0)
    out = np.zeros_like(data)
    f.read_all_begin(out)
    with pytest.raises(RuntimeError):       # wrong buffer at end
        f.read_all_end(np.zeros_like(data))
    f.read_all_end(out)
    np.testing.assert_array_equal(out, data)

    # at-variants do not move the individual pointer
    fp_before = f.get_position()
    two = (data * 2).copy()
    f.write_at_all_begin(0, two)
    f.write_at_all_end(two)
    back = np.zeros_like(data)
    f.read_at_all_begin(0, back)
    f.read_at_all_end(back)
    np.testing.assert_array_equal(back, two)
    assert f.get_position() == fp_before
    f.close()


def test_ordered_single_process(tmp_path):
    from ompi_tpu.api import file as fmod

    path = str(tmp_path / "ordered.bin")
    f = fmod.File.open(None, path, fmod.MODE_CREATE | fmod.MODE_RDWR)
    a = np.arange(4, dtype=np.float32)
    b = np.arange(4, 8, dtype=np.float32)
    assert f.write_ordered(a) == a.nbytes   # appends at shared pointer
    f.write_ordered_begin(b)
    assert f.write_ordered_end(b) == b.nbytes
    f.seek_shared(0)
    out = np.zeros(8, np.float32)
    f.read_ordered_begin(out)
    f.read_ordered_end(out)
    np.testing.assert_array_equal(out, np.arange(8, dtype=np.float32))
    f.close()


def test_mp_ordered_collective(tmp_path):
    """read/write_ordered across 4 ranks: rank-ordered disjoint regions
    from ONE shared-pointer carve-out (sharedfp ordered algorithm)."""
    path = tmp_path / "ordered_mp.dat"
    script = tmp_path / "ordered_mp.py"
    script.write_text(textwrap.dedent(f"""
        import numpy as np, ompi_tpu
        from ompi_tpu.api.file import File
        w = ompi_tpu.init()
        r = w.rank
        f = File.open(w, {str(path)!r}, "c+")
        # ragged per-rank records: rank r writes r+1 floats of value r
        rec = np.full(r + 1, float(r), np.float32)
        f.write_ordered(rec)
        w.barrier()
        # the file must be rank-ordered: 0 | 1 1 | 2 2 2 | 3 3 3 3
        whole = np.zeros(10, np.float32)
        f.read_at(0, whole)
        expect = np.concatenate([np.full(i + 1, float(i), np.float32)
                                 for i in range(4)])
        assert np.array_equal(whole, expect), whole
        # ordered read: same carve-out discipline, everyone gets its own
        # region back
        f.seek_shared(0)
        w.barrier()
        mine = np.zeros(r + 1, np.float32)
        f.read_ordered(mine)
        assert np.array_equal(mine, rec), (r, mine)
        f.close()
        print(f"ordered io OK rank {{r}}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("ordered io OK") == 4


def test_nonblocking_individual_and_shared(tmp_path):
    """MPI_File_iread/iwrite (+_all/_at_all/_shared) request forms and
    the byte-offset/type-extent/shared-position accessors."""
    from ompi_tpu.api import file as fmod
    from ompi_tpu.datatype import FLOAT32, vector

    path = str(tmp_path / "nb.bin")
    f = fmod.File.open(None, path, fmod.MODE_CREATE | fmod.MODE_RDWR)
    data = np.arange(16, dtype=np.int32)
    r = f.iwrite(data)
    r.wait()
    assert r.result == data.nbytes
    assert f.get_position() == data.nbytes  # etype BYTE: bytes==etypes
    f.seek(0)
    out = np.zeros_like(data)
    f.iread(out).wait()
    np.testing.assert_array_equal(out, data)

    # nonblocking collectives (single-rank degenerate but full path)
    f.seek(0)
    f.iwrite_all(data * 3).wait()
    f.seek(0)
    out2 = np.zeros_like(data)
    f.iread_all(out2).wait()
    np.testing.assert_array_equal(out2, data * 3)
    f.iwrite_at_all(0, data).wait()
    out3 = np.zeros_like(data)
    f.iread_at_all(0, out3).wait()
    np.testing.assert_array_equal(out3, data)

    # shared-pointer request forms + get_position_shared
    assert f.get_position_shared() == 0
    f.iwrite_shared(data).wait()
    assert f.get_position_shared() == data.nbytes
    out4 = np.zeros_like(data)
    f._shared_reset(0)
    f.iread_shared(out4).wait()
    np.testing.assert_array_equal(out4, data)

    # get_byte_offset through a strided view; get_type_extent per datarep
    ft = vector(2, 1, 2, FLOAT32)         # 4B used, 4B gap, 4B used
    f.set_view(8, FLOAT32, ft)
    # etype offset 0 -> disp; offset 1 -> second used f32 (skip the gap)
    assert f.get_byte_offset(0) == 8
    assert f.get_byte_offset(1) == 8 + 8
    # offset 2 -> next tile (extent 12 bytes)
    assert f.get_byte_offset(2) == 8 + 12
    assert f.get_type_extent(ft) == ft.extent
    f.close()
