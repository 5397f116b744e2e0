"""Derived datatypes on device buffers: ``datatype.pack_array`` /
``unpack_array`` (the convertor bound to a ``jax.Array``, its plan run by
``mca/accelerator/jax_acc``) and the typed ``ppermute_array`` /
``alltoall_array`` slots.  Every pattern is compared three ways, bit for
bit: the device path, the host ``Convertor`` on the same bytes, and plain
numpy indexing from the pattern's published definition (ddtbench's
NAS_MG faces, FFT2 transpose and LAMMPS_atomic index list; ``to_self.c``'s
indexed and vector twins)."""
import glob
import os
import warnings

import numpy as np
import pytest

from ompi_tpu import datatype as dt
from ompi_tpu.api.errors import ErrorClass, MpiError
from ompi_tpu.datatype import Convertor, ConvertorFlags
from ompi_tpu.datatype.plan import IndexPlan, RegularPlan, plan_for
from ompi_tpu.mca.accelerator import jax_acc
from ompi_tpu.runtime import spc

NAMED = {"float32": dt.FLOAT32, "int32": dt.INT32}
FACES = {"mg_x": 2, "mg_y": 1, "mg_z": 0}   # the axis the face is normal to


def _ids(atoms, sent, seed=7):
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(atoms, sent, replace=False))


def _pattern(name, size, named):
    """(buffer shape, datatype, count, numpy indexing of the pack)."""
    if name in FACES:
        g = size
        sub, index = [g - 2] * 3, [slice(1, -1)] * 3
        sub[FACES[name]], index[FACES[name]] = 1, 1
        t = dt.subarray((g, g, g), sub, (1, 1, 1), dt.ORDER_C, named)
        return (g, g, g), t.commit(), 1, lambda x: x[tuple(index)].ravel()
    if name == "fft2":
        n = size
        t = dt.resized(dt.vector(n, 2, 2 * n, named), 0, 8).commit()
        return (n, 2 * n), t, n, lambda x: x.reshape(n, n, 2).transpose(
            1, 0, 2).ravel()
    # an index list: ``size`` atoms of which one in 8 is sent, three
    # elements each (LAMMPS_atomic), or (atoms, one in how many, elements)
    atoms, one_in, block = size if name == "list" else (size, 8, 3)
    ids = _ids(atoms, atoms // one_in)
    t = dt.indexed_block(block, block * ids, named).commit()
    return (block * atoms,), t, 1, lambda x: x.reshape(-1, block)[
        ids].ravel()


# sorted, distinct and dense: every one streams (``plan.stream``), from one
# element in two to one in 64, blocks of 1, 2, 3 and 100; 240,000 atoms
# make three grid steps of which the last is short (its window is moved
# back inside the buffer), 16,384 of 2**20 take steps of 32 rows to keep a
# window under ``STREAM_WINDOW``, 600 atoms of 100 end in half an output row
LISTS = [("lammps_atomic", 4096), ("lammps_atomic", 240000),
         ("list", (6000, 2, 1)), ("list", (14400, 16, 2)),
         ("list", (1 << 20, 64, 1)), ("list", (600, 3, 100))]
PATTERNS = [(f, g) for g in (10, 18) for f in FACES] + [
    ("fft2", 16), ("fft2", 64)] + LISTS
IDS = ["{}.{}".format(n, "x".join(map(str, s)) if n == "list" else s)
       for n, s in PATTERNS]


def _values(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if dtype == "int32":
        return rng.integers(-2**31, 2**31 - 1, shape, dtype=np.int32)
    # every bit pattern but NaNs, whose payloads numpy's == cannot compare
    return rng.integers(-2**23, 2**23, shape).astype(np.float32) / 64


def _dev(x):
    import jax.numpy as jnp

    return jnp.asarray(x)


def _host_pack(x, count, t):
    return np.frombuffer(dt.pack(np.ascontiguousarray(x), count, t),
                         x.dtype)


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and a.tobytes() == b.tobytes())


@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("pattern", PATTERNS, ids=IDS)
def test_pack_three_ways(pattern, dtype):
    shape, t, count, index = _pattern(*pattern, NAMED[dtype])
    x = _values(shape, dtype)
    got = dt.pack_array(_dev(x), count, t)
    assert jax_acc.is_device_array(got) and got.ndim == 1
    assert _same_bits(got, index(x))
    assert _same_bits(got, _host_pack(x, count, t))


@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("pattern", PATTERNS, ids=IDS)
def test_unpack_into_nothing_is_zero_outside_the_map(pattern, dtype):
    shape, t, count, index = _pattern(*pattern, NAMED[dtype])
    packed = index(_values(shape, dtype, seed=1))
    got = dt.unpack_array(_dev(packed), count, t)
    want = np.zeros(got.shape, packed.dtype)
    assert dt.unpack(packed.tobytes(), want, count, t) == packed.nbytes
    assert _same_bits(got, want)
    # numpy indexing: packing what was unpacked gives the stream back,
    # and nothing else is set
    full = np.zeros(int(np.prod(shape)), packed.dtype)
    full[:got.size] = np.asarray(got)
    assert _same_bits(index(full.reshape(shape)), packed)
    assert np.count_nonzero(np.asarray(got)) == np.count_nonzero(packed)


@pytest.mark.parametrize("pattern", PATTERNS, ids=IDS)
def test_unpack_into_keeps_every_other_byte(pattern):
    shape, t, count, index = _pattern(*pattern, dt.FLOAT32)
    packed = index(_values(shape, "float32", seed=2))
    dst = _values(shape, "float32", seed=3)
    got = dt.unpack_array(_dev(packed), count, t, into=_dev(dst))
    assert got.shape == shape
    want = dst.copy()
    dt.unpack(packed.tobytes(), want, count, t)
    assert _same_bits(got, want)
    assert _same_bits(index(np.asarray(got)), packed)
    untouched = np.ones(shape, bool)
    marker = np.zeros(shape, np.float32)
    dt.unpack(np.ones(packed.size, np.float32).tobytes(), marker, count, t)
    untouched[marker == 1] = False
    assert np.array_equal(np.asarray(got)[untouched], dst[untouched])


@pytest.mark.parametrize("pattern,form", [
    (("mg_x", 18), RegularPlan), (("mg_y", 18), RegularPlan),
    (("mg_z", 18), RegularPlan), (("fft2", 64), RegularPlan),
    (("lammps_atomic", 4096), IndexPlan)], ids=lambda p: str(p))
def test_the_form_is_read_from_the_map(pattern, form):
    _, t, count, _ = _pattern(*pattern, dt.FLOAT32)
    plan = plan_for(t, count)
    assert type(plan) is form
    assert plan_for(t, count) is plan           # cached on the datatype
    assert plan.packed * 4 == count * t.size


def _bits(n, seed=9):
    """float32 of every kind: random bit patterns (NaNs with payloads and
    subnormals among them) behind a few chosen by hand."""
    bits = np.random.default_rng(seed).integers(0, 1 << 32, n,
                                                dtype=np.uint32)
    bits[:6] = [0x80000000, 0x7FC00001, 0xFFA5A5A5, 0x00000001,
                0x807FFFFF, 0x7F800000]  # -0.0, two NaNs, subnormals, inf
    return bits


@pytest.mark.parametrize("pattern", LISTS, ids=IDS[-len(LISTS):])
def test_a_dense_sorted_list_streams_and_moves_bits(pattern):
    """The streaming form is taken and the packed stream is numpy's
    indexing of the same bytes bit for bit, NaN payloads, ``-0.0`` and
    subnormals included; the index list itself stays on the host until
    an unpack asks for it."""
    import jax

    (n,), t, count, index = _pattern(*pattern, dt.FLOAT32)
    t = t.dup()                                     # a plan of its own
    spc.init()
    before = [spc.read("device_ddt_stream_plans"),
              spc.read("device_ddt_stream_packs")]
    bits = np.roll(_bits(n), int(index(np.arange(n))[0]))  # specials sent
    got = dt.pack_array(_dev(bits.view(np.float32)), count, t)
    plan = plan_for(t, count)
    assert isinstance(plan, IndexPlan) and plan.stream is not None
    ops = str(jax.make_jaxpr(plan.pack)(_dev(bits.view(np.float32)),
                                        *plan.index_args("pack")))
    assert "pallas_call" in ops
    assert got.dtype == np.float32
    assert _same_bits(np.asarray(got).view(np.uint32), index(bits))
    assert [spc.read("device_ddt_stream_plans"),
            spc.read("device_ddt_stream_packs")] == [before[0] + 1,
                                                     before[1] + 1]
    # a buffer longer than the map needs, and not whole tiles
    longer = np.concatenate([bits, _bits(77, seed=10)])
    got = dt.pack_array(_dev(longer.view(np.float32)), count, t)
    assert _same_bits(np.asarray(got).view(np.uint32), index(bits))
    assert list(plan._device) == [(True, None)]     # the tables alone
    dt.unpack_array(got, count, t)
    assert set(plan._device) == {(True, None), (False, None)}
    assert plan._device[False, None][0].shape == plan.index.shape


def _unsorted(named):
    ids = _ids(4096, 512)[::-1].copy()
    return dt.indexed_block(3, 3 * ids, named), 3 * 4096


def _overlapping(named):
    starts = np.arange(0, 3000, 2)          # blocks of 3 share an element
    return dt.indexed_block(3, starts, named), 3002


def _sparse(named):
    ids = _ids(1 << 22, 400)                # one block in 10,000
    return dt.indexed_block(3, 3 * ids, named), 3 << 22


def _dense(named):
    return dt.indexed_block(3, 3 * _ids(4096, 512), named), 3 * 4096


@pytest.mark.parametrize("make,named", [
    (_unsorted, dt.FLOAT32), (_overlapping, dt.FLOAT32),
    (_sparse, dt.INT32), (_dense, dt.INT16), (_dense, dt.BFLOAT16),
    (_dense, dt.INT64)], ids=["unsorted", "overlapping", "sparse", "int16",
                              "bfloat16", "int64"])
def test_every_other_list_keeps_the_gather(make, named):
    """Chosen from the index array and the type alone: an unsorted list,
    one whose blocks overlap, one in 10,000 (a window over
    ``STREAM_SLABS``) and 2- or 8-byte types trace the program they
    traced before there was a kernel."""
    import jax

    t, n = make(named)
    t = t.commit()
    spc.init()
    before = spc.read("device_ddt_stream_plans")
    plan = plan_for(t, 1)
    assert isinstance(plan, IndexPlan) and plan.stream is None
    assert spc.read("device_ddt_stream_plans") == before
    dtype = np.dtype(t.runs[2])
    if dtype.itemsize == 8:
        return                              # no 8-byte arrays without x64
    x = np.random.default_rng(11).integers(0, 1 << 16, n).astype(dtype)
    tables = plan.index_args("pack")
    assert [a.shape for a in tables] == [plan.index.shape]
    ops = str(jax.make_jaxpr(plan.pack)(_dev(x), *tables))
    assert "gather" in ops and "pallas_call" not in ops
    assert _same_bits(dt.pack_array(_dev(x), 1, t), _host_pack(x, 1, t))


def test_fft2_is_one_transpose():
    _, t, count, _ = _pattern("fft2", 64, dt.FLOAT32)
    plan = plan_for(t, count)
    assert plan.strides == (128, 2) and plan.sizes == (64, 64, 2)
    assert plan.perm == (1, 0, 2) and plan.starts == (0, 0, 0)


@pytest.mark.parametrize("face,starts,sizes", [
    ("mg_x", [1, 1, 1], [16, 16, 1]), ("mg_y", [1, 1, 1], [16, 1, 16]),
    ("mg_z", [1, 1, 1], [1, 16, 16])])
def test_a_face_is_one_slice_of_the_grid_as_it_stands(face, starts, sizes):
    """Given the grid in its own shape the plan slices it and reshapes
    nothing (a reshape of tiled dimensions copies the whole grid on a
    TPU); given it flat, it cuts the view at its own strides."""
    _, t, _, _ = _pattern(face, 18, dt.FLOAT32)
    plan = plan_for(t, 1)
    view, at, size, _ = plan._layout((18, 18, 18), 18 ** 3)
    assert (view, at, size) == ((18, 18, 18), starts, sizes)
    flat = plan._layout(None, 18 ** 3)
    assert int(np.prod(flat[0])) == 18 ** 3
    x = _values((18, 18, 18), "float32")
    assert _same_bits(dt.pack_array(_dev(x), 1, t),
                      dt.pack_array(_dev(x.ravel()), 1, t))
    import jax

    hlo = jax.jit(plan.pack).lower(_dev(x)).as_text()
    assert "reshape" not in hlo.split("slice")[0]   # sliced as it stands


def test_to_self_twins_yield_one_plan():
    """``to_self.c``: ``create_indexed_constant_gap_ddt(80, 100, 1)`` and
    its "optimized" vector twin are one type map, so one plan object."""
    number, contig, gap = 80, 100, 1
    disp = np.arange(number) * (contig + gap)
    indexed = dt.indexed([contig] * number, disp, dt.FLOAT32).commit()
    vector = dt.vector(number, contig, contig + gap, dt.FLOAT32).commit()
    assert indexed == vector
    plan = plan_for(indexed, 1)
    assert isinstance(plan, RegularPlan) and plan_for(vector, 1) is plan
    x = _values((indexed.extent // 4,), "float32")
    got = dt.pack_array(_dev(x), 1, indexed)
    assert _same_bits(got, x[:80 * 101 - 1].reshape(-1)[
        (disp[:, None] + np.arange(contig)).ravel()])
    assert _same_bits(got, _host_pack(x, 1, vector))
    back = dt.unpack_array(got, 1, vector, into=_dev(np.zeros_like(x)))
    want = np.zeros_like(x)
    dt.unpack(np.asarray(got).tobytes(), want, 1, indexed)
    assert _same_bits(back, want)


@pytest.mark.parametrize("count", [2, 3])
def test_an_extent_the_strides_do_not_divide_falls_back(count):
    """count > 1 of the twins: elements 8079 apart, rows 101: no array
    view, so an index list, and the same bits."""
    t = dt.vector(80, 100, 101, dt.FLOAT32).commit()
    plan = plan_for(t, count)
    assert isinstance(plan, IndexPlan) and plan.block == 100
    x = _values((count * t.extent // 4,), "float32")
    got = dt.pack_array(_dev(x), count, t)
    assert _same_bits(got, _host_pack(x, count, t))
    back = dt.unpack_array(got, count, t)
    want = np.zeros(back.shape, np.float32)
    dt.unpack(np.asarray(got).tobytes(), want, count, t)
    assert _same_bits(back, want)


def test_blocks_of_several_lengths():
    t = dt.indexed([2, 4, 6], [0, 7, 20], dt.INT32).commit()
    plan = plan_for(t, 1)
    assert isinstance(plan, IndexPlan) and plan.block == 2
    x = _values((40,), "int32")
    assert _same_bits(dt.pack_array(_dev(x), 1, t), _host_pack(x, 1, t))


def test_a_contiguous_type_is_a_slice():
    t = dt.contiguous(12, dt.FLOAT32).commit()
    x = _values((48,), "float32")
    assert isinstance(plan_for(t, 3), RegularPlan)
    assert _same_bits(dt.pack_array(_dev(x), 3, t), x[:36])


def test_a_million_blocks_build_without_a_loop():
    ids = _ids(1 << 23, 1 << 20)
    t = dt.indexed_block(3, 3 * ids, dt.FLOAT32).commit()
    plan = plan_for(t, 1)
    # the type map stayed arrays: no Segment a block was ever made
    assert t._segments is None and t.runs is not None
    assert t.size == 12 << 20 and plan.packed == 3 << 20
    assert isinstance(plan, IndexPlan) and plan.block == 3
    assert plan.sorted and plan.unique


def test_the_convertor_is_the_one_engine():
    _, t, count, index = _pattern("mg_y", 10, dt.FLOAT32)
    x = _values((10, 10, 10), "float32")
    conv = Convertor(t, count, _dev(x))
    assert conv.flags & ConvertorFlags.DEVICE
    packed = conv.pack()
    assert jax_acc.is_device_array(packed) and conv.finished
    assert _same_bits(packed, index(x))
    conv = Convertor(t, count, _dev(np.zeros_like(x)))
    out = conv.unpack(packed)
    assert jax_acc.is_device_array(out) and out.shape == x.shape
    assert _same_bits(index(np.asarray(out)), index(x))
    with pytest.raises(ValueError, match="whole stream"):
        Convertor(t, count, _dev(x)).pack(max_bytes=16)


@pytest.mark.parametrize("flag", [ConvertorFlags.EXTERNAL32,
                                  ConvertorFlags.CHECKSUM])
def test_host_only_flags_raise_on_a_device_buffer(flag):
    x = _dev(np.zeros(8, np.float32))
    with pytest.raises(RuntimeError, match="DEVICE"):
        Convertor(dt.contiguous(8, dt.FLOAT32), 1, x, flags=flag)


def test_host_entries_name_the_array_entries():
    x = _dev(np.zeros(8, np.float32))
    t = dt.contiguous(8, dt.FLOAT32)
    with pytest.raises(TypeError, match="pack_array"):
        dt.pack(x, 1, t)
    with pytest.raises(TypeError, match="unpack_array"):
        dt.unpack(b"\0" * 32, x, 1, t)


def test_a_heterogeneous_struct_has_no_device_plan():
    """The rule: a ``create_struct`` of different elementary types raises
    ``MpiError`` on a device buffer (a jax.Array holds one dtype); one of
    a single type is an ordinary map."""
    mixed = dt.create_struct([1, 1], [0, 4], [dt.FLOAT32, dt.INT32])
    with pytest.raises(MpiError) as e:
        dt.pack_array(_dev(np.zeros(4, np.float32)), 1, mixed.commit())
    assert e.value.error_class == ErrorClass.ERR_TYPE
    assert "one elementary type" in str(e.value)
    same = dt.create_struct([2, 1], [0, 16], [dt.FLOAT32, dt.FLOAT32])
    x = _values((8,), "float32")
    assert _same_bits(dt.pack_array(_dev(x), 1, same.commit()),
                      _host_pack(x, 1, same))


def test_a_wrong_dtype_or_a_short_buffer_is_refused():
    _, t, count, _ = _pattern("mg_z", 10, dt.FLOAT32)
    with pytest.raises(MpiError) as e:
        dt.pack_array(_dev(np.zeros((10, 10, 10), np.int32)), count, t)
    assert e.value.error_class == ErrorClass.ERR_TYPE
    with pytest.raises(MpiError) as e:
        dt.pack_array(_dev(np.zeros(100, np.float32)), count, t)
    assert e.value.error_class == ErrorClass.ERR_TRUNCATE
    odd = dt.hvector(4, 1, 6, dt.FLOAT32).commit()     # 6-byte stride
    with pytest.raises(MpiError, match="aligned"):
        dt.pack_array(_dev(np.zeros(16, np.float32)), 1, odd)


def test_nothing_crosses_to_the_host(monkeypatch):
    import jax

    def refuse(_x):
        raise AssertionError("to_host on the device datatype path")

    monkeypatch.setattr(jax_acc, "to_host", refuse)
    shape, t, count, _ = _pattern("fft2", 16, dt.FLOAT32)
    x = _dev(_values(shape, "float32"))
    dst = _dev(np.zeros(shape, np.float32))
    dt.unpack_array(dt.pack_array(x, count, t), count, t)      # warm
    with jax.transfer_guard("disallow"):
        packed = dt.pack_array(x, count, t)
        out = dt.unpack_array(packed, count, t, into=dst)
    assert _same_bits(out, np.asarray(x))


def test_counters_move_and_plans_build_once():
    _, t, count, _ = _pattern("mg_x", 10, dt.FLOAT32)
    t = t.dup()                                     # a plan of its own
    x = _dev(_values((10, 10, 10), "float32"))
    names = ("device_ddt_packs", "device_ddt_unpacks", "device_ddt_bytes",
             "device_ddt_plan_builds", "device_ddt_index_plans")
    spc.init()
    before = {n: spc.read(n) for n in names}
    for _ in range(3):
        packed = dt.pack_array(x, count, t)
    dt.unpack_array(packed, count, t)
    moved = {n: spc.read(n) - before[n] for n in names}
    assert moved == {"device_ddt_packs": 3, "device_ddt_unpacks": 1,
                     "device_ddt_bytes": 4 * 64 * 4,
                     "device_ddt_plan_builds": 1,
                     "device_ddt_index_plans": 0}


def test_spans_under_a_profiler_session_and_none_without(tmp_path):
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    _, t, count, _ = _pattern("mg_y", 18, dt.FLOAT32)
    x = _dev(_values((18, 18, 18), "float32"))
    warm = dt.pack_array(x, count, t)
    dt.unpack_array(warm, count, t)
    assert not TraceAnnotation.is_enabled()
    other = dt.subarray((18,) * 3, (16, 2, 16), (1, 1, 1), dt.ORDER_C,
                        dt.FLOAT32).commit()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            packed = dt.pack_array(x, count, t)
        dt.unpack_array(packed, count, t)
        dt.pack_array(x, 1, other)                  # a plan built inside
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    warnings.simplefilter("ignore", DeprecationWarning)
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("otpu.ddt.pack") == 3
    assert names.count("otpu.ddt.unpack") == 1
    assert names.count("otpu.ddt.plan") == 1
    assert any(n.startswith("PjitFunction(otpu_ddt_pack_regular)")
               for n in names)
    assert any(n.startswith("PjitFunction(otpu_ddt_unpack_regular)")
               for n in names)


# -- the kernel --------------------------------------------------------------
@pytest.mark.parametrize("b", [2, 4])
@pytest.mark.parametrize("flat", [False, True], ids=["matrix", "flat"])
def test_block_transpose_kernel_interpreted(b, flat):
    from ompi_tpu.ops import pallas_ddt

    rows, cols = 512, 1024
    x = _values((rows, b * cols), "int32")
    got = pallas_ddt.transpose_blocks(_dev(x.ravel() if flat else x), rows,
                                      cols, b, interpret=True)
    assert _same_bits(got, x.reshape(rows, cols, b).transpose(
        1, 0, 2).ravel())


def test_a_whole_transpose_of_small_blocks_takes_the_kernel():
    n = 512
    t = dt.resized(dt.vector(n, 2, 2 * n, dt.FLOAT32), 0, 8).commit()
    plan = plan_for(t, n)
    assert plan._blocks(2 * n * n) == (n, n, 2)
    assert plan._blocks(2 * n * n + 2) is None       # not the whole buffer
    x = _values((n, 2 * n), "float32")
    packed = dt.pack_array(_dev(x), n, t)
    assert _same_bits(packed, x.reshape(n, n, 2).transpose(1, 0, 2).ravel())
    assert _same_bits(dt.unpack_array(packed, n, t), x.ravel())
    assert _same_bits(dt.unpack_array(packed, n, t, into=_dev(x * 0)), x)
    small = plan_for(dt.resized(dt.vector(64, 2, 128, dt.FLOAT32), 0,
                                8).commit(), 64)
    assert small._blocks(2 * 64 * 64) is None        # under a tile: XLA


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip (the compiler is installed; no chip is
    attached).  Inside a fixture, never at import: see
    tests/test_pallas_aot.py."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("which", ["pack", "unpack"])
def test_block_transpose_kernel_compiles_for_v5e(v5e, which, monkeypatch):
    """The offline Mosaic compile at the benchmark's size: 8192 x 8192
    8-byte elements, a matrix in and the flat stream out (pack), the flat
    stream in (unpack); no temporary, so no relayout copy either side."""
    import jax
    import jax.numpy as jnp

    from ompi_tpu.ops import pallas_ddt

    # the process's devices are CPUs; the described chip takes Mosaic
    monkeypatch.setattr(pallas_ddt, "pallas_interpret", lambda: False)
    n = 8192
    t = dt.resized(dt.vector(n, 2, 2 * n, dt.FLOAT32), 0, 8).commit()
    plan = plan_for(t, n)
    shape = (n, 2 * n) if which == "pack" else (2 * n * n,)
    arg = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e)
    compiled = jax.jit(getattr(plan, which)).lower(arg).compile()
    assert "otpu_ddt_transpose_blocks" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# -- the typed slots -------------------------------------------------------
@pytest.fixture(scope="module", params=[1, 4], ids=["1rank", "4ranks"])
def module(request):
    import jax

    from ompi_tpu.mca.coll.xla import XlaCollModule

    n = request.param
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")
    spc.init()
    return XlaCollModule(None, jax.devices()[:n])


def _rows(fn, x):
    """``fn`` on each rank's buffer, stacked: pack or unpack, by the
    standalone entries."""
    return np.stack([np.asarray(fn(_dev(r))) for r in x])


@pytest.mark.parametrize("recv", [False, True], ids=["contig", "typed"])
def test_typed_ppermute_is_pack_plain_slot_unpack(module, recv):
    n, g = module.n, 10
    _, face, _, _ = _pattern("mg_x", g, dt.FLOAT32)
    _, other, _, _ = _pattern("mg_y", g, dt.FLOAT32)   # lands elsewhere
    x = _values((n, g, g, g), "float32", seed=4)
    perm = tuple((i, (i + 1) % n) for i in range(n))
    before = len(module._cache)
    out = module.ppermute_array(None, module.make_world_array(x), perm,
                                sendtype=face,
                                recvtype=other if recv else None)
    assert len(module._cache) == before + 1         # one program
    packed = _rows(lambda r: dt.pack_array(r, 1, face), x)
    moved = np.asarray(module.ppermute_array(
        None, module.make_world_array(packed), perm))
    want = _rows(lambda r: dt.unpack_array(r, 1, other), moved) \
        if recv else moved
    assert _same_bits(out, want)
    numpy_way = np.stack([x[(i - 1) % n][1:-1, 1:-1, 1].ravel()
                          for i in range(n)])
    assert _same_bits(moved, numpy_way)


@pytest.mark.parametrize("pattern", LISTS[:1] + LISTS[2:4],
                         ids=IDS[-len(LISTS):][:1] + IDS[-len(LISTS):][2:4])
def test_typed_ppermute_streams_an_index_list(module, pattern):
    """The streaming pack inside the slot's one program, vmapped over the
    ranks: the tables are the program's arguments, the index list is not
    (no unpack), and the bits are numpy's."""
    n = module.n
    (size,), t, count, index = _pattern(*pattern, dt.FLOAT32)
    t = t.dup()
    bits = np.stack([_bits(size, seed=20 + i) for i in range(n)])
    perm = tuple((i, (i + 1) % n) for i in range(n))
    out = module.ppermute_array(
        None, module.make_world_array(bits.view(np.float32)), perm,
        sendtype=t, count=count)
    plan = plan_for(t, count)
    assert plan.stream is not None
    assert list(plan._device) == [(True, module._replicated)]
    assert _same_bits(np.asarray(out).view(np.uint32), np.stack(
        [index(bits[(i - 1) % n]) for i in range(n)]))


def test_to_self_on_the_identity_permutation(module):
    n, g = module.n, 18
    _, face, _, index = _pattern("mg_z", g, dt.FLOAT32)
    x = _values((n, g, g, g), "float32", seed=5)
    out = module.ppermute_array(None, module.make_world_array(x),
                                tuple((i, i) for i in range(n)),
                                sendtype=face)
    assert _same_bits(out, np.stack([index(r) for r in x]))


@pytest.mark.parametrize("pattern", [
    ("fft2", 16), ("lammps_atomic", 64), ("lammps_atomic", 4096),
    ("list", (600, 3, 100))], ids=["regular", "index", "stream",
                                   "stream100"])
def test_typed_alltoall_is_pack_plain_slot_unpack(module, pattern):
    n = module.n
    shape, t, count, index = _pattern(*pattern, dt.FLOAT32)
    x = _values((n, n) + shape, "float32", seed=6)
    xd = module.make_world_array(x)
    out = module.alltoall_array(None, xd, sendtype=t, count=count)
    packed = np.stack([_rows(lambda r: dt.pack_array(r, count, t), row)
                       for row in x])
    moved = np.asarray(module.alltoall_array(
        None, module.make_world_array(packed)))
    assert _same_bits(out, moved)
    assert _same_bits(out, np.stack([np.stack(
        [index(x[j, i]) for j in range(n)]) for i in range(n)]))
    both = module.alltoall_array(None, xd, sendtype=t, recvtype=t,
                                 count=count)
    want = np.stack([_rows(lambda r: dt.unpack_array(r, count, t), row)
                     for row in moved])
    assert _same_bits(both, want)


def test_packed_sizes_must_agree(module):
    n = module.n
    _, small, _, _ = _pattern("mg_x", 10, dt.FLOAT32)
    _, large, _, _ = _pattern("mg_x", 18, dt.FLOAT32)
    x = module.make_world_array(np.zeros((n, 10, 10, 10), np.float32))
    with pytest.raises(MpiError) as e:
        module.ppermute_array(None, x, ((0, 0),), sendtype=small,
                              recvtype=large)
    assert e.value.error_class == ErrorClass.ERR_TRUNCATE


def test_untyped_calls_build_the_programs_they_built(module):
    """``None`` is the old path: the same cache keys, the same program
    names, and a typed call beside it adds a key and changes none."""
    n = module.n
    _, face, _, _ = _pattern("mg_z", 10, dt.FLOAT32)   # no test's before
    a = module.make_world_array(np.zeros((n, n, 6), np.float32))
    p = module.make_world_array(np.zeros((n, 10, 10, 10), np.float32))
    perm = tuple((i, (i + 1) % n) for i in range(n))
    module.alltoall_array(None, a)
    module.ppermute_array(None, p, perm)
    k_a2a = ("alltoall", a.shape, a.dtype)
    k_perm = ("ppermute", perm, p.shape, p.dtype)
    assert k_a2a == module._keyfor("alltoall", a)
    assert k_perm == module._keyfor("ppermute", p, perm)
    assert module._cache[k_a2a][0].__name__ == "otpu_alltoall"
    assert module._cache[k_perm][0].__name__ == "otpu_ppermute"
    held = (module._cache[k_a2a], module._cache[k_perm])
    builds = spc.read("device_program_builds")
    module.ppermute_array(None, p, perm, sendtype=face)
    module.ppermute_array(None, p, perm, sendtype=face)    # a cache hit
    module.alltoall_array(None, a)
    module.ppermute_array(None, p, perm)
    assert spc.read("device_program_builds") == builds + 1
    assert (module._cache[k_a2a], module._cache[k_perm]) == held
    typed = k_perm + (plan_for(face, 1).key, None)
    assert module._cache[typed][0].__name__ == "otpu_ppermute_ddt"


def test_the_communicator_takes_the_datatypes():
    import ompi_tpu
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    world = ompi_tpu.init()
    try:
        n, g = world.size, 10
        _, face, _, index = _pattern("mg_y", g, dt.FLOAT32)
        x = _values((n, g, g, g), "float32", seed=8)
        xd = world.c_coll["allreduce_array"].__self__.make_world_array(x)
        perm = tuple((i, (i + 1) % n) for i in range(n))
        out = world.ppermute_array(xd, perm, sendtype=face)
        assert _same_bits(out, np.stack([index(x[(i - 1) % n])
                                         for i in range(n)]))
        plain = world.ppermute_array(xd, perm)
        assert _same_bits(plain, np.roll(x, 1, axis=0))
    finally:
        rt.reset_for_testing()
