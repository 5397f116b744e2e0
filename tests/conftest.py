"""Test harness: run everything on the XLA CPU backend with 8 virtual devices.

This is the "fake multi-device backend" the reference never had (SURVEY.md §4):
single-host N-rank testing the way Open MPI uses ``mpirun -n 8
--oversubscribe`` over btl/self+sm.  Must run before jax is imported anywhere.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def prewarm_native():
    """Build (or load) the otpu_native .so ONCE at session start.

    The first ``native.available()`` call may pay a ~2-minute g++
    compile into OTPU_NATIVE_CACHE; letting that land inside whichever
    test happens to call it first eats that test's subprocess timeout
    and double-compiles under multi-process launches.  Warming here makes every later call a
    cheap cache hit — including the tpurun children, which inherit the
    populated cache directory."""
    if os.environ.get("OTPU_NATIVE_DISABLE"):
        yield
        return
    from ompi_tpu import native

    native.available()
    yield


@pytest.fixture
def fresh_registry():
    """Isolated var registry state for config-system tests."""
    from ompi_tpu.base import mca, output, var

    saved_vars = dict(var.registry._vars)
    saved_state = {
        name: (v._value, v._source, v._source_detail)
        for name, v in saved_vars.items()
    }
    saved_alias = dict(var.registry._alias)
    saved_pvars = dict(var.registry._pvars)
    saved_file = dict(var.registry._file)
    saved_loaded = var.registry._files_loaded
    yield var.registry
    var.registry._vars = saved_vars
    for name, (val, src, detail) in saved_state.items():
        v = saved_vars[name]
        v._value, v._source, v._source_detail = val, src, detail
    var.registry._alias = saved_alias
    var.registry._pvars = saved_pvars
    var.registry._file = saved_file
    var.registry._files_loaded = saved_loaded
    var.registry._cli.clear()
    var.registry._deprecation_warned.clear()
    output._help_seen.clear()
