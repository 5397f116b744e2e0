"""The build record (``runtime/trace.bind_builds``): every device program
accounts for its own build from JAX's compile events.  A program's three
phases (trace, lower, backend: a compile or a load from the persistent
cache) are booked once, into SPC counters and, while the ring is on,
``build`` spans; a ``jax.jit`` traced inside another's trace is inside the
outer phase already; a program of another name goes to
``device_other_build_us``; a cached call reaches no callback; a step's
``scopes()`` and ``memory()`` book nothing.  And the rule the record tells
the program's own programs by holds for every ``jax.jit`` of the device
path's sources."""
import ast
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ompi_tpu.base.var import registry
from ompi_tpu.parallel import config, train
from ompi_tpu.parallel.mesh import MeshSpec, make_mesh
from ompi_tpu.runtime import spc, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
PHASES = ("device_program_trace_us", "device_program_lower_us",
          "device_program_backend_us")
COUNTERS = PHASES + ("device_program_cache_requests",
                     "device_program_cache_hits",
                     "device_programs_compiled", "device_other_build_us")
TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT = trace._BUILD_PHASES
# the device path's sources, whose every jax.jit the rule is held to
WALKED = ("ops", "mca/coll", "mca/accelerator", "datatype", "parallel")
# OLMoE at the widths of tests/test_olmoe_train.py: the toy model
TOY = config.ModelConfig(
    compute_dtype="float32", hidden_size=64, intermediate_size=32,
    num_attention_heads=4, num_key_value_heads=4, num_experts=8,
    num_experts_per_tok=2, vocab_size=256, layers_here=2, seq_len=32,
    micro_batch=2, attn_block=16, loss_block_rows=16, lr=1e-2)


@pytest.fixture(scope="module", autouse=True)
def bound():
    if "device_programs_compiled" not in spc.counters():
        spc.init()
    trace.bind_builds()
    trace.bind_builds()         # idempotent: one set of listeners


def counters():
    return {k: spc.read(k) for k in COUNTERS}


def moved(before):
    return {k: spc.read(k) - v for k, v in before.items()}


class Heard:
    """Every event ``jax.monitoring`` fires while it is open, by a
    listener of the test's own: what reaches it reaches the record's."""

    def __enter__(self):
        import jax.monitoring as mon

        self.events = []
        self._scalar = lambda e, v, **kw: self.events.append(e)
        self._plain = lambda e, **kw: self.events.append(e)
        self._secs = lambda e, s, **kw: self.events.append(e)
        mon.register_scalar_listener(self._scalar)
        mon.register_event_listener(self._plain)
        mon.register_event_duration_secs_listener(self._secs)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring as mon

        mon.unregister_scalar_listener(self._scalar)
        mon.unregister_event_listener(self._plain)
        mon.unregister_event_duration_listener(self._secs)


def test_a_named_programs_phases_are_booked_once():
    def otpu_test_doubled(x):
        return x * 2 + 1

    fn = jax.jit(otpu_test_doubled)
    x = jnp.arange(8.0)
    x.block_until_ready()
    before = counters()
    t0 = time.perf_counter()
    fn(x).block_until_ready()
    wall_us = (time.perf_counter() - t0) * 1e6
    got = moved(before)
    assert all(got[k] > 0 for k in PHASES), got
    assert sum(got[k] for k in PHASES) <= wall_us
    assert got["device_programs_compiled"] == 1
    assert got["device_other_build_us"] == 0
    # 100 cached calls: no counter moves, no callback is reached
    before = counters()
    with Heard() as heard:
        for _ in range(100):
            fn(x)
    assert heard.events == []
    assert not any(moved(before).values())
    # a retrace for a new shape books as any build does
    fn(jnp.arange(16.0))
    assert moved(before)["device_programs_compiled"] == 1


def test_an_inner_jit_is_not_counted_twice():
    @jax.jit
    def otpu_test_inner(x):
        time.sleep(0.2)         # runs while it is traced, inside the outer
        return x + 1

    def otpu_test_outer(x):
        return otpu_test_inner(x) * 3

    before = counters()
    t0 = time.perf_counter()
    jax.jit(otpu_test_outer)(jnp.ones(4)).block_until_ready()
    wall = time.perf_counter() - t0
    got = moved(before)
    assert 0.2e6 <= got["device_program_trace_us"] < 0.4e6
    assert sum(got[k] for k in PHASES) <= wall * 1e6
    assert got["device_programs_compiled"] == 1


def test_a_program_of_another_name_is_the_rests():
    def callers_own(x):
        return x - 1

    before = counters()
    jax.jit(callers_own)(jnp.ones(4)).block_until_ready()
    got = moved(before)
    assert got["device_other_build_us"] > 0
    assert not any(got[k] for k in COUNTERS
                   if k != "device_other_build_us")
    assert trace.own_program("jit(otpu_allreduce_sum)")
    assert trace.own_program("reduce_stack")
    assert not trace.own_program("jit(callers_own)")
    assert not trace.own_program("jit(otpu")


@pytest.fixture
def empty_cache(tmp_path):
    """JAX's persistent cache in an empty directory that keeps every
    program, however small; what was configured comes back after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path), 0.0, 0)):
        jax.config.update(n, v)
    cc.reset_cache()
    try:
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_the_second_build_reads_a_hit(empty_cache, monkeypatch):
    """An empty cache directory: the build misses and writes; after
    ``jax.clear_caches()`` the same program is loaded, and the metric's
    own file and reader give 100 over that build."""
    def otpu_test_cached(x):
        return jnp.tanh(x) @ x.T

    x = jnp.ones((8, 8))
    x.block_until_ready()
    before = counters()
    jax.jit(otpu_test_cached)(x).block_until_ready()
    cold = moved(before)
    assert cold["device_program_cache_requests"] == 1
    assert cold["device_program_cache_hits"] == 0
    jax.clear_caches()
    before = counters()
    jax.jit(otpu_test_cached)(x).block_until_ready()
    warm = moved(before)
    assert warm["device_program_cache_requests"] == 1
    assert warm["device_program_cache_hits"] == 1
    assert warm["device_programs_compiled"] == 1
    assert warm["device_program_backend_us"] > 0

    sys.path.insert(0, BENCH)
    try:
        from harness import manifest
        from readers import program_counter

        spec = manifest.metric_spec("compile.cache_hit_share", BENCH)
        # nothing served reads 0, not nothing
        for part, want in ((cold, 0.0), (warm, 100.0)):
            monkeypatch.setattr(spc, "counters", lambda part=part: part)
            assert program_counter.read({}, spec["params"]) == want
    finally:
        sys.path.remove(BENCH)


def test_a_callback_never_raises():
    """An unknown event, an event without a name, an exit that was never
    entered, a cache event outside any build: nothing is raised into
    JAX's compile path and nothing is left open."""
    before = counters()
    trace._build_enter("/jax/no/such/event", 1.0, fun_name="f")
    trace._build_enter("/jax/no/such/event")
    trace._build_event("/jax/no/such/event")
    trace._build_event(trace._CACHE_HIT)
    trace._build_exit("/jax/no/such/event", 0.5)
    trace._build_exit(trace._CACHE_LOAD, 0.5)
    trace._build_exit(BACKEND_EVENT, 0.5, fun_name="jit(otpu_never_entered)")
    trace._build_exit(BACKEND_EVENT)
    assert not any(moved(before).values())
    trace._build_enter(LOWER_EVENT, 1.0)        # no fun_name: the rest's
    trace._build_exit(LOWER_EVENT, 0.25)
    got = moved(before)
    assert got["device_other_build_us"] == 0.25e6
    assert not any(v for k, v in got.items()
                   if k != "device_other_build_us")
    assert not trace._builds.open
    # an inner exit that is lost does not hold the outer open
    trace._build_enter(TRACE_EVENT, 1.0, fun_name="otpu_test_outer")
    trace._build_enter(TRACE_EVENT, 1.0, fun_name="lost")
    trace._build_enter(BACKEND_EVENT, 1.0, fun_name="jit(eager)")
    trace._build_event(trace._CACHE_REQUEST)
    trace._build_exit(BACKEND_EVENT, 0.5, fun_name="jit(eager)")
    before = counters()
    trace._build_exit(TRACE_EVENT, 2.0, fun_name="lost")
    trace._build_exit(TRACE_EVENT, 3.0, fun_name="otpu_test_outer")
    got = moved(before)
    assert got["device_program_trace_us"] == 3e6
    assert got["device_program_cache_requests"] == 1
    assert got["device_programs_compiled"] == 0
    assert not trace._builds.open


@pytest.fixture
def ring():
    registry.set("otpu_trace_enable", True)
    trace.reset_for_testing()
    try:
        yield
    finally:
        registry.set("otpu_trace_enable", False)
        trace.reset_for_testing()


def test_the_ring_holds_a_span_a_phase(ring):
    def otpu_test_spanned(x):
        return x * x

    t0 = trace.now()
    jax.jit(otpu_test_spanned)(jnp.ones(4)).block_until_ready()
    jax.jit(lambda x: x + 2)(jnp.ones(4)).block_until_ready()
    t1 = trace.now()
    spans = [e for e in trace._ring if e is not None and e[2] == "build"]
    own = [e for e in spans if e[6]["own"]]
    assert [e[1] for e in own] \
        == ["build.trace", "build.lower", "build.backend"]
    assert {e[6]["program"] for e in own} \
        == {"otpu_test_spanned", "jit(otpu_test_spanned)"}
    assert own[-1][6]["cache"] in ("hit", "miss") \
        and "load_us" in own[-1][6]
    # on the ring's clock, one after the other inside the call
    ends = [e[3] + e[4] for e in own]
    assert t0 <= own[0][3] and ends == sorted(ends) and ends[-1] <= t1
    assert all(a <= b[3] + 1000 for a, b in zip(ends, own[1:]))
    rest = [e for e in spans if not e[6]["own"]]
    assert {e[1] for e in rest} \
        == {"build.trace", "build.lower", "build.backend"}
    assert "build" in trace.CATEGORIES


def test_nothing_is_written_with_the_ring_off():
    assert not trace.enabled

    def otpu_test_unspanned(x):
        return x - 3

    jax.jit(otpu_test_unspanned)(jnp.ones(4)).block_until_ready()
    assert trace._ring is None or not any(
        e is not None and e[2] == "build" for e in trace._ring)


@pytest.fixture(scope="module")
def toy_step():
    mesh, spec = make_mesh(jax.devices()[:1], MeshSpec(dp=1))
    step, place = train.build_train_step(mesh, spec, model=TOY)
    toks = np.random.default_rng(0).integers(
        0, TOY.vocab_size, (2, 33)).astype(np.int32)
    state, tokens, labels = place(train.init_model_params(TOY, 0),
                                  toks[:, :-1], toks[:, 1:])
    before = counters()
    first = spc.read("device_program_first_call_us")
    state, _ = step(state, tokens, labels)
    jax.block_until_ready(state)
    return dict(step=step, state=state, tokens=tokens, labels=labels,
                built=moved(before),
                first_call_us=spc.read("device_program_first_call_us")
                - first)


def test_a_steps_first_call_holds_its_three_phases(toy_step):
    """The step's kernels' own jits are traced inside its trace: one
    program, its phases under the first call's seconds."""
    built = toy_step["built"]
    assert built["device_programs_compiled"] == 1
    assert all(built[k] > 0 for k in PHASES)
    assert sum(built[k] for k in PHASES) < toy_step["first_call_us"]


def test_scopes_and_memory_move_nothing(toy_step):
    step = toy_step["step"]
    before = counters()
    with Heard() as heard:
        scopes, memory = step.scopes(), step.memory()
    assert not any(moved(before).values())
    # what JAX fires for the second lowering reaches the listeners and
    # is dropped there
    assert all(e == TRACE_EVENT for e in heard.events), heard.events
    assert not getattr(trace._builds, "open", None)
    assert set(memory) == set(train.MEMORY_FIELDS) and len(memory) == 5
    assert all(isinstance(v, int) and v >= 0 for v in memory.values())
    assert memory["peak_memory_in_bytes"] >= (
        memory["argument_size_in_bytes"] + memory["output_size_in_bytes"]
        - memory["alias_size_in_bytes"])
    # the state is donated: its outputs alias its arguments
    assert memory["alias_size_in_bytes"] > 0
    (listed,) = [m for m in train.memory_of_built_steps()
                 if m["peak_memory_in_bytes"]
                 == memory["peak_memory_in_bytes"]]
    assert listed["module"] == scopes["module"]
    # a cached step executes no build: no event at all
    state = toy_step["state"]
    with Heard() as heard:
        state, _ = step(state, toy_step["tokens"], toy_step["labels"])
    toy_step["state"] = state
    assert heard.events == []
    assert not any(moved(before).values())


def test_memory_before_the_first_call_raises():
    mesh, spec = make_mesh(jax.devices()[:1], MeshSpec(dp=1))
    step, _ = train.build_train_step(mesh, spec, model=TOY)
    with pytest.raises(RuntimeError, match="has not run"):
        step.memory()


def test_the_step_reader_reads_the_peak_as_a_share(toy_step, capsys):
    sys.path.insert(0, BENCH)
    try:
        from harness import manifest
        from readers import step_memory

        spec = manifest.metric_spec("step.hbm_peak_share", BENCH)
        share = step_memory.read({"device_kind": "TPU v5 lite"},
                                 spec.get("params", {}))
    finally:
        sys.path.remove(BENCH)
    peak = max(m["peak_memory_in_bytes"]
               for m in train.memory_of_built_steps())
    assert share == 100.0 * peak / 16e9 and 0 < share < 100
    assert "memory [" in capsys.readouterr().out


# -- the rule, held to the sources ----------------------------------------

def _is_jax_jit(node) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name) and node.value.id == "jax")


def _constant_head(node):
    """The leading constant text of a string expression, or None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values \
            and isinstance(node.values[0], ast.Constant):
        return node.values[0].value
    return None


def _jit_sites(path):
    """[(line, name or None, how)] of every ``jax.jit`` in a file: the
    decorated function's name; of ``jax.jit(f)`` and
    ``jax.jit(shard_map(f, ...))`` the local function ``f``'s, or what
    the enclosing function assigns to ``f.__name__``: a constant's head,
    or, where that is the enclosing function's parameter ``name``, the
    heads of what its callers in the file pass."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    parent = {c: n for n in ast.walk(tree) for c in ast.iter_child_nodes(n)}
    sites = []
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if any(_is_jax_jit(n) for n in ast.walk(dec)):
                    sites.append((node.lineno, [node.name], "decorator"))
        if not (isinstance(node, ast.Call) and _is_jax_jit(node.func)):
            continue
        if isinstance(parent.get(node), ast.FunctionDef) \
                and node in parent[node].decorator_list:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Call) \
                and getattr(arg.func, "id", "") == "shard_map":
            arg = arg.args[0]
        assert isinstance(arg, ast.Name), \
            f"{path}:{node.lineno}: jax.jit of something unnamed"
        enclosing = node
        while not isinstance(enclosing, ast.FunctionDef):
            enclosing = parent[enclosing]
        renamed = [n.value for n in ast.walk(enclosing)
                   if isinstance(n, ast.Assign) and any(
                       isinstance(t, ast.Attribute) and t.attr == "__name__"
                       and getattr(t.value, "id", None) == arg.id
                       for t in n.targets)]
        if not renamed:
            defs = [n.name for n in ast.walk(enclosing)
                    if isinstance(n, ast.FunctionDef) and n.name == arg.id]
            assert defs, f"{path}:{node.lineno}: {arg.id} is no local def"
            sites.append((node.lineno, defs, "local def"))
            continue
        (value,) = renamed
        if isinstance(value, ast.Name):     # the enclosing function's
            params = [a.arg for a in enclosing.args.args          # parameter
                      + enclosing.args.kwonlyargs]
            assert value.id in params, f"{path}:{node.lineno}"
            passed = [kw.value for call in ast.walk(tree)
                      if isinstance(call, ast.Call)
                      and getattr(call.func, "attr", None) == enclosing.name
                      for kw in call.keywords if kw.arg == value.id]
            assert passed, f"{path}:{node.lineno}: no caller names it"
            names = []
            for v in passed:
                if isinstance(v, ast.Call) \
                        and getattr(v.func, "id", "") == "_program_name":
                    names.append("otpu_")   # held below, by calling it
                else:
                    names.append(_constant_head(v))
            sites.append((node.lineno, names, "named by its callers"))
        else:
            sites.append((node.lineno, [_constant_head(value)],
                          "renamed"))
    return sites


def test_every_jit_of_the_device_path_is_named_by_the_rule():
    """Every ``jax.jit`` under the device path's sources builds a program
    the record takes for the program's own: ``otpu_*``, or one of
    ``trace.OWN_PROGRAMS``; and that tuple lists nothing that is gone."""
    from ompi_tpu.mca.coll.xla import _program_name

    assert _program_name("allreduce").startswith("otpu_")
    assert _program_name("allreduce", "sum").startswith("otpu_")
    found, listed = 0, set()
    for sub in WALKED:
        top = os.path.join(REPO, "ompi_tpu", sub)
        for dirpath, _, files in os.walk(top):
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                for line, names, how in _jit_sites(path):
                    found += 1
                    for name in names:
                        where = f"{os.path.relpath(path, REPO)}:{line}"
                        assert name, f"{where}: ({how}) no static name"
                        if name in trace.OWN_PROGRAMS:
                            listed.add(name)
                        else:
                            assert name.startswith("otpu_"), \
                                f"{where}: ({how}) {name!r} is neither " \
                                "otpu_* nor in trace.OWN_PROGRAMS"
    assert found >= 30          # the walk saw the sources
    assert listed == set(trace.OWN_PROGRAMS)
