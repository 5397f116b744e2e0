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


@pytest.fixture
def traced_step(monkeypatch):
    """``traced_step(cfg, tokens, labels, on_tpu)``: a public model's
    train step (``parallel/train.build_train_step`` on one device)
    traced to a jaxpr and nothing more, as the CPU runs it (the ``jnp``
    twins) or, ``on_tpu``, as a TPU does (the Pallas kernels as
    ``pallas_call`` equations; tracing lowers nothing).  Returns
    ``eqns`` (every equation, the sub-programs' included), ``built`` and
    ``shared`` (what the step's plan, ``train.plan_of`` at the traced
    shapes, adds to the SPC counters ``attn_built`` and
    ``attn_shared_kv_built``), ``kernels(name)`` (the operands' and
    the results' shapes of each ``pallas_call`` of that name) and
    ``holds_no_repeat(b, nh, nkv, s, hd)``, which asserts that ``nh``
    query heads read ``nkv`` key-value heads through the kernels' index
    maps and nothing else."""
    import types

    import jax

    from ompi_tpu.parallel import train
    from ompi_tpu.parallel.mesh import MeshSpec, make_mesh

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    def trace(cfg, tokens, labels, on_tpu):
        if on_tpu:
            monkeypatch.setattr(train, "pallas_interpret",
                                lambda devices=None: False)
        mesh, spec = make_mesh(jax.devices()[:1], MeshSpec(dp=1))
        step, place = train.build_train_step(mesh, spec, model=cfg)
        args = place(train.init_model_params(cfg, 3), tokens, labels)
        eqns = list(walk(jax.make_jaxpr(step.jitted)(*args).jaxpr))
        counts = train.plan_of(cfg, *tokens.shape,
                               interpret=not on_tpu)["counts"]
        built, shared = (counts.get(n, 0) for n in (
            "attn_built", "attn_shared_kv_built"))
        shapes = lambda vs: [v.aval.shape for v in vs]
        kernels = lambda name: [
            (shapes(e.invars), shapes(e.outvars)) for e in eqns
            if e.primitive.name == "pallas_call"
            and e.params["name"] == name]

        def holds_no_repeat(b, nh, nkv, s, hd):
            """k and v reach the kernels ``nkv`` heads a batch entry
            and dk and dv leave them so (the scalar-prefetch pair is the
            backward's first operand), no ``jnp.repeat``'s broadcast (b,
            nkv, nh / nkv, s, hd) is anywhere in the step, and every
            pass counts as one whose k and v are shared."""
            assert built == shared > 0
            assert not [e for e in eqns
                        if e.primitive.name == "broadcast_in_dim"
                        and e.outvars[0].aval.shape
                        == (b, nkv, nh // nkv, s, hd)]
            forward = kernels("otpu_flash_causal_forward")
            backward = kernels("otpu_attn_block_backward")
            assert bool(forward) == bool(backward) == on_tpu
            per_q, per_kv = (b * nh, s, hd), (b * nkv, s, hd)
            for ins, outs in forward:
                assert ins == [per_q, per_kv, per_kv] and outs[0] == per_q
            for ins, outs in backward:
                assert ins[1:5] == [per_q, per_kv, per_kv, per_q]
                assert ins[7:] == outs == [per_q, per_kv, per_kv]

        return types.SimpleNamespace(
            eqns=eqns, built=built, shared=shared, kernels=kernels,
            holds_no_repeat=holds_no_repeat)

    return trace
