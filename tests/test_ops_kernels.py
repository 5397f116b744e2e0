"""Pallas kernel + op-framework tests (run on the CPU mesh, interpret mode).

Reference model: the op/avx kernel tests ``test/datatype/reduce_local.c``
+ ``check_op.sh`` — every op kernel checked against a golden host
computation — and the op framework selection in
``ompi/mca/op/base/op_base_op_select.c``.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.ops import pallas_reduce as pr


def _stack_kernel_names(op, x):
    """Names of the ``pallas_call``s ``reduce_stack(op, x)`` traces to."""
    jaxpr = jax.make_jaxpr(functools.partial(pr.reduce_stack, op))(x)
    return re.findall(r"name=(otpu_\w+)", str(jaxpr))


class TestPallasReduce:
    @pytest.mark.parametrize("op,npfn", [
        ("SUM", np.add), ("PROD", np.multiply),
        ("MAX", np.maximum), ("MIN", np.minimum),
    ])
    def test_combine2_float(self, op, npfn):
        rng = np.random.RandomState(3)
        a = rng.normal(size=(7, 531)).astype(np.float32)
        b = rng.normal(size=(7, 531)).astype(np.float32)
        out = pr.combine2(op, jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_allclose(np.asarray(out), npfn(a, b), rtol=1e-6)

    @pytest.mark.parametrize("op,npfn", [
        ("BAND", np.bitwise_and), ("BOR", np.bitwise_or),
        ("BXOR", np.bitwise_xor),
    ])
    def test_combine2_bitwise(self, op, npfn):
        rng = np.random.RandomState(4)
        a = rng.randint(0, 1 << 30, size=773).astype(np.int32)
        b = rng.randint(0, 1 << 30, size=773).astype(np.int32)
        out = pr.combine2(op, jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_array_equal(np.asarray(out), npfn(a, b))

    def test_combine2_logical(self):
        a = jnp.asarray([0, 1, 2, 0], jnp.int32)
        b = jnp.asarray([0, 0, 3, 5], jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(pr.combine2("LXOR", a, b)), [0, 1, 0, 1])

    @pytest.mark.parametrize("k", [2, 5, 8])
    def test_reduce_stack(self, k):
        rng = np.random.RandomState(k)
        x = rng.normal(size=(k, 3, 411)).astype(np.float32)
        out = pr.reduce_stack("SUM", jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out), x.sum(0), rtol=1e-5,
                                   atol=1e-6)

    def test_reduce_stack_k1_and_large(self):
        x = np.arange(10, dtype=np.float32).reshape(1, 10)
        np.testing.assert_array_equal(
            np.asarray(pr.reduce_stack("MAX", jnp.asarray(x))), x[0])
        big = np.ones((4, 70000), np.float32)
        np.testing.assert_array_equal(
            np.asarray(pr.reduce_stack("SUM", jnp.asarray(big))),
            np.full(70000, 4, np.float32))

    @pytest.mark.parametrize("op,dtype", [
        ("PROD", np.float32), ("BAND", np.int32), ("MAX", np.int32)])
    @pytest.mark.parametrize("n", [
        128, 65536, 65536 + 128, 3 * 65536,
        262144 + 128])  # a partial last block at every k (C <= 262144)
    @pytest.mark.parametrize("k", [2, 3, 4, 5, 8])
    def test_reduce_stack_2d_bit_equal(self, k, n, op, dtype):
        """A (k, N) stack of a 32-bit dtype, N a multiple of 128, is
        blocked as it stands ((k, C) in, (1, C) out)."""
        rng = np.random.RandomState(k * 1000 + n % 997)
        if dtype is np.float32:
            x = rng.normal(size=(k, n)).astype(dtype)
        else:
            x = rng.randint(-(1 << 31), 1 << 31, size=(k, n),
                            dtype=np.int64).astype(dtype)
        assert _stack_kernel_names(op, x) == ["otpu_reduce_stack_rows"]
        want = functools.reduce(
            {"PROD": np.multiply, "BAND": np.bitwise_and,
             "MAX": np.maximum}[op], list(x))
        got = np.asarray(pr.reduce_stack(op, jnp.asarray(x)))
        assert got.dtype == want.dtype and got.shape == (n,)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("shape,dtype,name", [
        ((4, 1024), jnp.float32, "otpu_reduce_stack_rows"),
        ((4, 1024), jnp.int32, "otpu_reduce_stack_rows"),
        ((4, 1, 1024), jnp.float32, "otpu_reduce_stack"),   # a gathered stack
        ((4, 1000), jnp.float32, "otpu_reduce_stack"),      # ragged N
        ((4, 1024), jnp.bfloat16, "otpu_reduce_stack"),     # sub-32-bit
        ((5, 3, 411), jnp.float32, "otpu_reduce_stack"),
    ])
    def test_reduce_stack_path_by_input(self, shape, dtype, name):
        """The input decides the block shape, at trace time; the kernel's
        name says which (what ``kernel.in_kernel_share`` reads on the
        chip)."""
        x = jax.ShapeDtypeStruct(shape, dtype)
        assert _stack_kernel_names("SUM", x) == [name]

    def test_device_fold_coverage(self):
        assert pr.device_fold("SUM", jnp.float32) is not None
        assert pr.device_fold("BAND", jnp.float32) is None  # bitwise≠float
        assert pr.device_fold("BAND", jnp.int32) is not None
        assert pr.device_fold("MAXLOC", jnp.float32) is None


class TestOpFramework:
    def test_selection_and_fallback(self):
        from ompi_tpu.api import op as op_mod
        from ompi_tpu.mca.op import base as op_base

        fn = op_mod.jax_fold(op_mod.SUM, jnp.float32)
        a, b = jnp.arange(8.0), jnp.ones(8)
        np.testing.assert_allclose(np.asarray(fn(a, b)),
                                   np.arange(8.0) + 1)
        # MAXLOC has no elementwise device kernel in any component
        with pytest.raises(Exception):
            op_mod.jax_fold(op_mod.MAXLOC, jnp.float32)
        assert op_base.select_fold("SUM", jnp.float32) is not None

    def test_exclude_component_var(self):
        """--mca op ^pallas_vpu forces the plain-XLA fold (reference:
        ``--mca op ^avx``)."""
        from ompi_tpu.base import mca
        from ompi_tpu.mca.op import base as op_base

        fw = mca.framework("op")
        names = set(fw.components) if fw.opened else None
        if names is not None:
            assert {"pallas_vpu", "xla"} <= names
        op_base.reset_cache()
        fold = op_base.select_fold("PROD", jnp.float32)
        a, b = jnp.full(4, 3.0), jnp.full(4, 2.0)
        np.testing.assert_allclose(np.asarray(fold(a, b)), np.full(4, 6.0))
