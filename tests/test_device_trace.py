"""The device path's spans on the JAX profiler's clock: with a profiler
session open every ``*_array`` slot and the persistent handle write one
``otpu.coll.*`` span a call around one ``PjitFunction(otpu_*)``; a new
shape writes ``otpu.coll.get`` / ``build`` / ``first_call`` and moves the
three SPC counters; with no session nothing is constructed and results
are bit-equal.  One profiler session serves the whole module (a session
costs about a second on the CPU)."""
import glob
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import ompi_tpu
from ompi_tpu.api.errors import ErrorClass, MpiError
from ompi_tpu.runtime import spc, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 8
CALLS = 3
COUNTERS = ("device_slow_path", "device_program_builds",
            "device_program_first_call_us")
_COUNTS = [[(2 * i + j) % 4 + 1 for j in range(N)] for i in range(N)]
_PERM = [(i, (i + 1) % N) for i in range(N)]

# slot -> (input shape, call, the program it runs, has a _fast path)
SLOTS = {
    "allreduce_array": ((N, 4), lambda w, x: w.allreduce_array(x),
                        "otpu_allreduce_sum", True),
    "bcast_array": ((N, 4), lambda w, x: w.bcast_array(x, 3),
                    "otpu_bcast_tree", True),
    "allgather_array": ((N, 4), lambda w, x: w.allgather_array(x),
                        "otpu_allgather", True),
    "reduce_scatter_array": ((N, N, 4),
                             lambda w, x: w.reduce_scatter_array(x),
                             "otpu_reduce_scatter_sum", True),
    "alltoall_array": ((N, N, 4), lambda w, x: w.alltoall_array(x),
                       "otpu_alltoall", True),
    "allgatherv_array": ((N, 5), lambda w, x: w.allgatherv_array(
        x, [1, 2, 3, 4, 5, 4, 3, 2]), "otpu_allgather", True),
    "alltoallv_array": ((N, N, 5),
                        lambda w, x: w.alltoallv_array(x, _COUNTS),
                        "otpu_alltoall", True),
    "reduce_array": ((N, 4), lambda w, x: w.reduce_array(x, root=2),
                     "otpu_reduce_sum", False),
    "gather_array": ((N, 4), lambda w, x: w.gather_array(x, 1),
                     "otpu_gather", False),
    "scatter_array": ((N, N, 4), lambda w, x: w.scatter_array(x, 1),
                      "otpu_scatter", False),
    "scan_array": ((N, 4), lambda w, x: w.scan_array(x),
                   "otpu_scan_sum", False),
    "exscan_array": ((N, 4), lambda w, x: w.exscan_array(x),
                     "otpu_exscan_sum", False),
    "ppermute_array": ((N, 4), lambda w, x: w.ppermute_array(x, _PERM),
                       "otpu_ppermute", False),
    "psum_scatter_array": ((N, N, 6), lambda w, x: w.c_coll[
        "psum_scatter_array"](w, x), "otpu_reduce_scatter_sum", True),
}
HANDLE = "allreduce_init"           # the persistent handle's span
NEW_SHAPE = "test.new_shape"        # the test's own span around a new shape


class Span:
    def __init__(self, event):
        self.name = event.name
        self.start = int(event.start_ns)
        self.end = self.start + int(event.duration_ns)
        self.args = {k: v for k, v in event.stats}
        self.children = []

    def find(self, prefix):
        """Outermost descendants whose name starts with ``prefix`` (JAX
        writes PjitFunction(f) twice, one inside the other)."""
        out = []
        for c in self.children:
            out += [c] if c.name.startswith(prefix) else c.find(prefix)
        return out


def _main_line(log_dir):
    """The issuing thread's events as top-level spans with children."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    # reading an event's stats warns about a builtin type of jaxlib's
    warnings.simplefilter("ignore", DeprecationWarning)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = sorted((Span(e) for e in line.events),
                           key=lambda s: (s.start, -s.end))
            if not any(s.name.startswith("otpu.coll.") for s in spans):
                continue
            top, stack = [], []
            for s in spans:
                while stack and not (stack[-1].start <= s.start
                                     and s.end <= stack[-1].end):
                    stack.pop()
                (stack[-1].children if stack else top).append(s)
                stack.append(s)
            return top
    raise AssertionError("no host line holds an otpu.coll.* span")


def _as_numpy(out):
    if isinstance(out, list):
        return [_as_numpy(o) for o in out]
    return np.asarray(out)


def _flat(out):
    if isinstance(out, list):
        return [a for o in out for a in _flat(o)]
    return [out]


def _counters():
    return tuple(spc.read(n) for n in COUNTERS)


@pytest.fixture(scope="module")
def world():
    from ompi_tpu.runtime import init as rt

    rt.reset_for_testing()
    w = ompi_tpu.init()
    if w.size != N:
        pytest.skip("needs 8 virtual devices")
    yield w
    rt.reset_for_testing()


@pytest.fixture(scope="module")
def traced(world, tmp_path_factory):
    """Every slot warmed, then called CALLS times inside ONE profiler
    session, then a new shape twice.  Returns the parsed main line, the
    inputs, what the calls returned, and the counters around the new
    shape."""
    import jax

    xla = world.c_coll["allreduce_array"].__self__
    rng = np.random.default_rng(24)
    inputs = {slot: xla.make_world_array(
        rng.integers(-8, 8, shape).astype(np.float32))
        for slot, (shape, _, _, _) in SLOTS.items()}
    inputs[HANDLE] = inputs["allreduce_array"]
    calls = {slot: spec[1] for slot, spec in SLOTS.items()}
    handle = world.allreduce_array_init(inputs[HANDLE])
    calls[HANDLE] = lambda w, x: handle(x)
    for slot, call in calls.items():        # first calls: outside the session
        call(world, inputs[slot])
    fresh = xla.make_world_array(np.ones((N, 7), np.float32))
    log_dir = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    results = {}
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        for slot, call in calls.items():
            for _ in range(CALLS):
                results[slot] = call(world, inputs[slot])
        with jax.profiler.TraceAnnotation(NEW_SHAPE):
            c0 = _counters()
            world.allreduce_array(fresh)    # a new shape: a new program
            c1 = _counters()
            world.allreduce_array(fresh)    # again: the fast path
            c2 = _counters()
        jax.block_until_ready(jax.tree_util.tree_leaves(results))
    finally:
        jax.profiler.stop_trace()
    return {"top": _main_line(log_dir), "inputs": inputs, "calls": calls,
            "results": {s: _as_numpy(r) for s, r in results.items()},
            "counters": (c0, c1, c2), "handle": handle, "xla": xla}


@pytest.mark.parametrize("slot", list(SLOTS) + [HANDLE])
def test_one_span_a_call_around_one_named_program(traced, slot):
    program = SLOTS[slot][2] if slot in SLOTS else "otpu_allreduce_sum"
    fast = SLOTS[slot][3] if slot in SLOTS else True
    spans = [s for s in traced["top"] if s.name == "otpu.coll." + slot]
    assert len(spans) == CALLS
    for s in spans:
        assert s.args == {}                 # no arguments on the hot path
        # (the v-variants slice their result afterwards: jax's own
        # programs, not the program's)
        (pjit,) = s.find("PjitFunction(otpu_")
        assert pjit.name == f"PjitFunction({program})"
        # a slot with a _fast path never leaves it on a warm cache; the
        # others go through _get on every call, and say so
        gets = s.find("otpu.coll.get")
        assert len(gets) == (0 if fast else 1)
        assert not s.find("otpu.coll.build")
        assert not s.find("otpu.coll.first_call")
    # no span of the device path lies outside a call's span
    assert not [s for s in traced["top"]
                if s.name.startswith(("PjitFunction(otpu_",
                                      "otpu.coll.get"))]


def test_a_new_shape_writes_get_build_first_call_and_counts(traced):
    want = {"coll": "allreduce", "shape": "(8, 7)", "dtype": "float32"}
    (marker,) = [s for s in traced["top"] if s.name == NEW_SHAPE]
    first, again = marker.find("otpu.coll.allreduce_array")
    assert not again.find("otpu.coll.")     # the repeat took the fast path
    (get,) = first.find("otpu.coll.get")
    (build,) = get.find("otpu.coll.build")
    (first_call,) = first.find("otpu.coll.first_call")
    for s in (get, build, first_call):
        assert s.args == want
    assert first_call.start >= get.end      # the call follows the lookup
    (pjit,) = first_call.find("PjitFunction(")
    assert pjit.name == "PjitFunction(otpu_allreduce_sum)"
    c0, c1, c2 = traced["counters"]
    assert c1[0] - c0[0] == 1 and c1[1] - c0[1] == 1
    assert c1[2] - c0[2] > 0                # microseconds in the first call
    assert c2 == c1                         # a cache hit moves none of them


@pytest.mark.parametrize("slot", list(SLOTS) + [HANDLE])
def test_no_session_no_annotation_and_the_same_bits(traced, world,
                                                    monkeypatch, slot):
    made = []

    class Counting(trace.profiler_span):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace, "profiler_span", Counting)
    assert not trace.profiler_on()
    out = _as_numpy(traced["calls"][slot](world, traced["inputs"][slot]))
    assert made == []
    want = traced["results"][slot]
    for a, b in zip(_flat(out), _flat(want), strict=True):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_every_cached_program_carries_the_programs_name(traced):
    names = {fn.__name__ for fn, _ in traced["xla"]._cache.values()}
    assert names and all(n.startswith("otpu_") for n in names)
    assert {spec[2] for spec in SLOTS.values()} <= names


def test_a_freed_handle_says_what_it_was(traced):
    h = traced["handle"]
    h.free()
    with pytest.raises(MpiError) as err:
        h(traced["inputs"][HANDLE])
    assert err.value.error_class is ErrorClass.ERR_REQUEST
    assert "allreduce" in str(err.value)
    with pytest.raises(MpiError):
        h.start(traced["inputs"][HANDLE])


def test_the_ring_has_no_device_category_and_the_base_layer_no_jax():
    assert "device" not in trace.CATEGORIES and "coll" in trace.CATEGORIES
    code = ("import sys; import ompi_tpu.runtime.trace as t, ompi_tpu; "
            "assert 'jax' not in sys.modules, 'jax was imported'; "
            "assert t.profiler_on() is False and t.profiler_span is None")
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
