"""Wire-byte probes on compiled HLO: the binomial device gather/reduce
trees must move O(n·S)-class traffic, not the n²·S / 2n·S of the
all_gather- or allreduce-then-mask constructions they replaced
(``coll_base_gather.c`` / ``coll_base_reduce.c`` binomial algorithms).

The probe reads the actual compiled program: every collective-permute's
operand bytes times its source_target_pairs count is exactly the bytes
that cross links per execution — no timing noise, valid on the virtual
CPU mesh because it's a property of the program, not the clock.
"""
import re

import numpy as np
import pytest

import ompi_tpu

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "f64": 8, "s8": 1, "u8": 1, "pred": 1}


@pytest.fixture(scope="module")
def world():
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    w = ompi_tpu.init()
    if w.size != 8:
        pytest.skip("needs 8 virtual devices")
    yield w
    rt.reset_for_testing()


@pytest.fixture(scope="module")
def xla(world):
    from ompi_tpu.mca.coll.xla import XlaCollModule

    return next(m for m in world.coll_modules
                if isinstance(m, XlaCollModule))


def _wire_bytes(hlo: str) -> int:
    """Total link-crossing bytes per execution: Σ over collective-
    permutes of operand bytes × pair count."""
    total = 0
    for line in hlo.splitlines():
        if "collective-permute" not in line or \
                "source_target_pairs" not in line:
            continue
        if "-done" in line:
            continue   # async pair: count the -start (has the shape)
        shape = re.search(r"(\w+)\[([\d,]*)\]", line)
        pairs = re.search(r"source_target_pairs=\{(.*?)\}[,)]", line)
        if not shape or not pairs:
            continue
        dt = _DTYPE_BYTES.get(shape.group(1))
        if dt is None:
            continue
        dims = shape.group(2)
        elems = int(np.prod([int(d) for d in dims.split(",")])) \
            if dims else 1
        npairs = pairs.group(1).count("{")
        total += dt * elems * npairs
    return total


def _compiled_hlo(xla_mod, before_keys, arg) -> str:
    new = [k for k in xla_mod._cache if k not in before_keys]
    assert len(new) == 1, new
    fn = xla_mod._cache[new[0]][0]
    return fn.lower(arg).compile().as_text()


def test_gather_wire_bytes_binomial(world, xla):
    host = np.random.default_rng(0).standard_normal((8, 128)) \
        .astype(np.float32)
    dev = xla.make_world_array(host)
    before = set(xla._cache)
    out = np.asarray(world.gather_array(dev, root=3))
    np.testing.assert_allclose(out[3], host, rtol=1e-6)  # still right
    hlo = _compiled_hlo(xla, before, dev)
    S = 128 * 4
    # binomial: k=1: 4 pairs x S, k=2: 2 x 2S, k=4: 1 x 4S = 12S total;
    # all_gather+mask moved n*(n-1)*S = 56S
    assert "all-gather" not in hlo
    wire = _wire_bytes(hlo)
    assert 0 < wire <= 14 * S, f"gather moves {wire} B vs 12S={12 * S}"


def test_reduce_wire_bytes_binomial(world, xla):
    host = np.random.default_rng(1).standard_normal((8, 128)) \
        .astype(np.float32)
    dev = xla.make_world_array(host)
    before = set(xla._cache)
    out = np.asarray(world.reduce_array(dev, root=2))
    np.testing.assert_allclose(out[2], host.sum(0), rtol=1e-5)
    hlo = _compiled_hlo(xla, before, dev)
    S = 128 * 4
    # binomial reduce: (n-1) block sends = 7S; allreduce+mask rode the
    # full ring at ~2(n-1)S per device
    assert "all-reduce" not in hlo
    wire = _wire_bytes(hlo)
    assert 0 < wire <= 8 * S, f"reduce moves {wire} B vs 7S={7 * S}"


def test_scatter_wire_bytes_binomial(world, xla):
    host = np.random.default_rng(2).standard_normal((8, 8, 128)) \
        .astype(np.float32)
    dev = xla.make_world_array(host)
    before = set(xla._cache)
    out = np.asarray(world.scatter_array(dev, root=4))
    np.testing.assert_allclose(out, host[4], rtol=1e-6)
    hlo = _compiled_hlo(xla, before, dev)
    S = 128 * 4
    # binomial halving: k=4: 1x4S, k=2: 2x2S, k=1: 4x1S = 12S; the
    # all_to_all construction moved every rank's dead freight (56S)
    assert "all-to-all" not in hlo
    wire = _wire_bytes(hlo)
    assert 0 < wire <= 14 * S, f"scatter moves {wire} B vs 12S={12 * S}"


def test_bcast_large_is_one_allreduce(world, xla):
    """Above bcast_sa_min_bytes the program is one masked all-reduce over
    the shard as it arrives: no tree hops, no second ring phase, no
    pad/slice around it — and still correct from any root."""
    S = xla.bcast_sa_min_bytes // 4 + 1024   # f32 elems, above the bar
    host = np.random.default_rng(3).standard_normal((8, S)) \
        .astype(np.float32)
    dev = xla.make_world_array(host)
    before = set(xla._cache)
    out = np.asarray(world.bcast_array(dev, root=6))
    np.testing.assert_array_equal(out, np.broadcast_to(host[6], out.shape))
    hlo = _compiled_hlo(xla, before, dev)
    assert len(re.findall(r" all-reduce(?:-start)?\(", hlo)) == 1, hlo
    for op in ("collective-permute", "all-gather", "reduce-scatter",
               "dynamic-update-slice", "pad"):
        assert not re.search(rf" {op}(?:-start)?\(", hlo), op


def _special_payload(dtype, shape, seed, minus_zero):
    """(8, *shape) of ``dtype``: every row random and different, with the
    values an all-reduce with zeros could mangle (NaN, inf, the dtype's
    extremes; ``-0.0`` and a subnormal if asked) planted at the front of
    each row, in another order on every rank."""
    rng = np.random.default_rng(seed)
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return rng.integers(0, 2, (8, *shape)).astype(np.bool_)
    if dtype.kind == "i":
        host = rng.integers(-2**31, 2**31, (8, *shape)).astype(dtype)
        info = np.iinfo(dtype)
        special = [info.min, info.max, -1, 0]
    else:
        import ml_dtypes

        host = rng.standard_normal((8, *shape)).astype(dtype)
        info = ml_dtypes.finfo(dtype)
        special = [0.0, np.nan, -np.nan, np.inf, -np.inf, info.min,
                   info.max, info.tiny]
        if minus_zero:
            special += [-0.0, -info.smallest_subnormal]
    flat = host.reshape(8, -1)
    for r in range(8):
        flat[r, :len(special)] = np.roll(np.array(special, dtype), r)
    return host


_FLOAT_SUM = pytest.mark.xfail(strict=True, reason=(
    "the large regime sums in the payload's own dtype: -0.0 + 0.0 is +0.0 "
    "and subnormals flush. Summing the bits as integers hands both over, "
    "and measured 1551 against 1259 us a 64 MiB call on four v5e chips "
    "(PERF.md, PR 25), over ISSUE 25's 2%"))


@pytest.mark.parametrize("dtype,three_d,root,minus_zero", [
    *((dt, three_d, root, False)
      for dt in ("float32", "bfloat16", "int32", "bool")
      for three_d in (False, True) for root in (0, 3, 7)),
    pytest.param("float32", False, 7, True, marks=_FLOAT_SUM),
    pytest.param("bfloat16", True, 0, True, marks=_FLOAT_SUM)])
def test_bcast_large_hands_over_roots_bits(world, xla, dtype, three_d, root,
                                           minus_zero):
    """A broadcast hands over root's bits: NaN, inf and the dtype's
    extremes arrive unchanged in the large regime, on a size that 8 does
    not divide (the case the scatter's padding served) and on a 3-D
    shape.  (The CPU mesh keeps a quiet NaN's bits; the TPU's float
    all-reduce returns the canonical NaN: PERF.md, PR 25.)"""
    import jax.numpy as jnp

    dtype = np.dtype(jnp.dtype(dtype))
    elems = xla.bcast_sa_min_bytes // dtype.itemsize
    shape = (3, 7, elems // 21 + 1) if three_d else (elems + 3,)
    host = _special_payload(dtype, shape, root, minus_zero)
    before = set(xla._cache)
    out = np.asarray(world.bcast_array(xla.make_world_array(host), root=root))
    assert out.dtype == dtype and out.shape == host.shape
    (key,) = set(xla._cache) - before
    assert xla._cache[key][0].__name__ == "otpu_bcast_psum"
    bits = np.dtype(f"uint{dtype.itemsize * 8}")
    np.testing.assert_array_equal(
        out.view(bits), np.broadcast_to(host[root], host.shape).view(bits))


def test_bcast_small_stays_binomial(world, xla):
    host = np.random.default_rng(4).standard_normal((8, 64)) \
        .astype(np.float32)
    dev = xla.make_world_array(host)
    before = set(xla._cache)
    out = np.asarray(world.bcast_array(dev, root=2))
    np.testing.assert_allclose(out, np.broadcast_to(host[2], out.shape),
                               rtol=1e-6)
    hlo = _compiled_hlo(xla, before, dev)
    assert "collective-permute" in hlo       # the tree
    assert "reduce-scatter" not in hlo
