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


def _module_fixtures(item) -> list:
    """The module-scoped fixtures of its own file that ``item`` takes, by
    name: as arguments, or by ``request.getfixturevalue`` of a parameter
    that names one (``rows`` in the two AOT files, ``which`` in
    ``test_train_scopes.py``)."""
    defs = item.session._fixturemanager.getfixturedefs
    named = [v for v in getattr(getattr(item, "callspec", None), "params",
                                {}).values() if isinstance(v, str)]
    file = item.nodeid.split("::")[0]
    return [n for n in (*getattr(item, "fixturenames", ()), *named)
            if any(d.scope == "module" and d.baseid == file
                   for d in defs(n, item) or ())]


#: the files whose seconds are a child process's: an offline compile a
#: ``*_rows`` fixture, made once a session (``built.shared``)
DEALT_FIRST = ("test_pallas_aot.py", "test_pallas_aot_cells.py")


def pytest_collection_modifyitems(items):
    """A module's users of one module-scoped fixture stand side by side,
    fixtures in the order the module first takes them.  ``--dist load``
    deals runs of consecutive items, a twelfth of what is still pending a
    run, so a fixture's users are split between two workers, each building
    it, only where a run ends.  The modules keep their places: a worker
    keeps the run it was dealt, and the light files between the heavy ones
    are what keeps a run of a hundred items from being a quarter of an
    hour's.  But for ``DEALT_FIRST``: dealt where the alphabet has them,
    half-way, the seventy items are one run, and its worker was still
    waiting for their children 200 s after the five others had ended (PR
    75's warm run); dealt first, the same wait lies beside the whole run."""
    first, rows = {}, {}
    for i, item in enumerate(items):
        seen = first.setdefault(item.module, {})
        key = sorted(seen.setdefault(n, len(seen))
                     for n in _module_fixtures(item))
        rows.setdefault(item.module, []).append((key, i, item))
    for module in rows.values():
        for (_, i, _), (_, _, item) in zip(module, sorted(
                module, key=lambda row: row[:2])):
            items[i] = item
    items.sort(key=lambda item: os.path.basename(
        item.nodeid.split("::")[0]) not in DEALT_FIRST)


@pytest.fixture(scope="session", autouse=True)
def session_dir(tmp_path_factory):
    """``built.shared`` keeps what is made once a session in the run's
    temporary directory: a worker's own is a subdirectory of it."""
    import built

    base = tmp_path_factory.getbasetemp()
    built.SESSION_DIR = str(
        base.parent if os.environ.get("PYTEST_XDIST_WORKER") else base)


#: memory mappings a worker may hold when a module ends, of the 65,530 a
#: process may (``vm.max_map_count``)
MAPPINGS = 30000


def release_programs(above: int = MAPPINGS) -> tuple:
    """(the process's memory mappings before, after): above ``above`` of
    them, every program JAX compiled here is let go.  A compiled CPU program
    holds four or five mappings until its cache entry goes, a worker that
    has run a few model files holds tens of thousands of programs, and the
    compile (or the load from the persistent cache) that would pass the
    kernel's limit dies of a segmentation fault inside XLA: the worker
    deaths of PR 69-74 (ROADMAP.md C13).  What ``tests/built.py`` keeps is
    compiled again when next called."""
    def held():
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)

    before = held()
    if before <= above:
        return before, before
    import gc

    import jax

    jax.clear_caches()
    gc.collect()
    return before, held()


@pytest.fixture(scope="module", autouse=True)
def mappings_in_bounds():
    yield
    release_programs()


@pytest.fixture(scope="session", autouse=True)
def prewarm_native():
    """Build (or load) the otpu_native .so at session start, so that the
    first ``native.available()`` does not land inside whichever test
    happens to call it first, and the tpurun children find the cache
    directory (``OTPU_NATIVE_CACHE``, else under the temporary directory)
    populated.  The build is 1.5 s of g++ (PR 75); six workers that find
    the directory empty each build, and each puts its file in place by
    ``os.replace``: nine seconds between them, no lock."""
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

    import built as once
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
        # a step of its own, traced under the patch and never run
        mesh, spec = make_mesh(jax.devices()[:1], MeshSpec(dp=1))
        step, place = train.build_train_step(mesh, spec, model=cfg)
        args = place(once.params(cfg, 3), tokens, labels)
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
