"""The timing protocol, one for every cell.

Device calls here are stream-ordered: a collective returns when its
program is enqueued.  What a user of such a runtime pays for is what
nccl-tests measures: **k calls issued back to back, closed by one
``block_until_ready``; the window's wall time over k is the per-call
time.**  k is calibrated per point in warm-up to the smallest power of
two whose window lasts at least ``MIN_WINDOW_S``, which keeps the closing
round trip (about 1.4 ms on the v5e machine) under 3% of a window, and a
power of two stays the same on a parent and a change unless the change is
large.  Each call takes the next of a pool of distinct inputs (an
application reduces many tensors, not one).  ``HOLD`` outputs are kept
alive and the window is closed on all of them; earlier outputs are
dropped as the loop goes, so the runtime frees them as their programs
finish.

A run goes round the cell's points in an order shuffled from the seed,
one window per point per round, until the time is up.  A point has two
values: ``per_call_us``, the median of its windows' per-call times (what
PR 23's four metrics read, and what a stall inside a run cannot move),
and ``per_call_mean_us``, all its windows' seconds over all its calls,
as ``osu_allreduce`` reports total time over iterations (what an
end-to-end metric added since PR 26 reads: a stall counts).
"""
from __future__ import annotations

import collections
import contextlib
import functools
import importlib.util
import itertools
import random
import time

import numpy as np

from harness import data, manifest, readerkit, stats
from harness.tracered import ISSUE, ROUND, SYNC     # the harness's spans

MIN_WINDOW_S = 0.05
MAX_K = 1 << 20
HOLD = 2                    # outputs kept alive; the window closes on them
SAMPLE = 1 << 20            # positions compared where an array is longer
EDGE = 1024                 # first and last positions always compared


def _no_span(_name: str):
    return contextlib.nullcontext()


class Env:
    """What a call kind needs of the booted program: the world, its
    size, the devices, and the sharding ``make_world_array`` gives (row
    i on the device of rank i)."""

    def __init__(self, world, devices) -> None:
        self.world = world
        self.n = world.size
        self.devices = list(devices)
        self.module = world.c_coll["allreduce_array"].__self__
        probe = self.module.make_world_array(
            np.zeros((self.n, 1), np.float32))
        self.rank_sharding = probe.sharding
        self.mesh = probe.sharding.mesh
        self.axis = probe.sharding.spec[0]
        self._generators: dict = {}

    def generator(self, shape, dtype: str, op: str, sharding,
                  values: str | None = None):
        """The jitted ``key -> array`` that makes one input on the
        device.  Points of one shape, type, op and ``values`` share it:
        every program a run builds or loads is set-up (about 0.4 s each
        on the v5e machine, cache hit or not)."""
        import jax

        spec = (shape, dtype, op, sharding, values)
        if spec not in self._generators:
            self._generators[spec] = jax.jit(
                lambda key: data.values(key, shape, dtype, op, values),
                out_shardings=sharding)
        return self._generators[spec]


def load_kind(point: dict, bench_dir: str):
    """The call kind of a point.  A kind that defines ``wrap`` is a
    wrapper: the point names a second kind under ``inner``, and
    ``wrap(inner kind)`` returns the kind the point runs (``blocking``
    closes every call of any kind by its own sync)."""
    kind = load_module("kinds", point["kind"], bench_dir)
    if hasattr(kind, "wrap"):
        return kind.wrap(load_module("kinds", point["inner"], bench_dir))
    return kind


def load_module(directory: str, name: str, bench_dir: str):
    """A call kind or a reader: one Python file found by its name."""
    path = manifest.code_file(directory, name, bench_dir)
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class PointRun:
    """One point of a traffic mix, bound to the booted program."""

    def __init__(self, env: Env, point: dict, kind, seed: int,
                 pool_bytes: int, pool_max: int, want_raw: bool) -> None:
        import jax

        self.point = point
        self.name = point["name"]
        self.kind = kind
        self.n = env.n
        gen = env.generator(kind.input_shape(point, env.n), point["dtype"],
                            point.get("op", "SUM"),
                            kind.input_sharding(env), point.get("values"))
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 data.stable_hash(self.name))
        prepare = getattr(kind, "prepare", None)

        def entry(key):
            """One pool entry: the generated array, or what the kind's
            ``prepare`` makes of it (the array itself is then dropped
            here, so the pool is held once).  The pool's size is that of
            the generated array either way."""
            x = gen(key)
            self.chip_bytes = max(s.data.nbytes
                                  for s in x.addressable_shards)
            return x if prepare is None else prepare(env, point, x)

        first = entry(key)
        count = max(2, min(pool_max, pool_bytes // self.chip_bytes))
        self.pool = [first] + [entry(jax.random.fold_in(key, i))
                               for i in range(1, count)]
        self.call, self.bind_collectives = kind.bind(env, point, first)
        self.raw = None
        if want_raw:        # a metric of the cell reads this point's twin
            if not hasattr(kind, "bind_raw"):
                raise ValueError(f"point {self.name!r}: a metric reads its "
                                 f"raw twin and kind {point['kind']!r} "
                                 "has none")
            self.raw = kind.bind_raw(env, point, first)
        per_call = getattr(kind, "collectives_per_call", None)
        self.collectives_per_call = per_call(point) if per_call \
            else kind.COLLECTIVES_PER_CALL
        self.tolerance = getattr(kind, "TOLERANCE", None)
        self.programs_per_call = None   # observed in a traced run's trace
        self.k = 1
        self.windows: list = []         # (window_s, issue_s) per window
        self.raw_windows: list = []

    # the numbers the readers take
    def per_call_s(self) -> float:
        return stats.median(w / self.k for w, _ in self.windows)

    def mean_call_s(self) -> float:
        """All the windows' seconds over all the calls in them."""
        return sum(w for w, _ in self.windows) / (self.k * len(self.windows))

    def issue_s(self) -> float:
        return stats.median(i / self.k for _, i in self.windows)

    def raw_per_call_s(self):
        if not self.raw_windows:
            return None
        return stats.median(w / self.k for w, _ in self.raw_windows)

    def summary(self) -> dict:
        """One row of the per-point table (printed on an earlier line of
        every run; the readers select rows from it)."""
        t, t_all = self.per_call_s(), self.mean_call_s()
        row = {
            **readerkit.point_row(self.point),
            "n": self.n, "k": self.k, "pool": len(self.pool),
            "windows": len(self.windows),
            "per_call_us": t * 1e6, "per_call_mean_us": t_all * 1e6,
            "issue_us": self.issue_s() * 1e6,
            "collectives_per_call": self.collectives_per_call,
        }
        if self.programs_per_call is not None:
            row["programs_per_call"] = self.programs_per_call
        if self.tolerance is not None:
            row["tolerance"] = self.tolerance
        bus = self.kind.bus_bytes(self.point, self.n)
        if bus:
            row["bus_bytes"] = bus
            row["busbw_GBps"] = bus / t / 1e9
        moved = self.kind.moved_bytes(self.point, self.n)
        if moved:
            row["moved_bytes"] = moved
            row["moved_GBps"] = moved / t / 1e9
        raw = self.raw_per_call_s()
        if raw is not None:
            row["raw_per_call_us"] = raw * 1e6
            row["raw_windows"] = len(self.raw_windows)
            row["fw_over_raw"] = raw / t
        return row


def window(call, pool, k: int, point: str | None = None) -> tuple:
    """k calls back to back over the pool, closed by one sync on the
    outputs still held.  Returns (window seconds, issue seconds).  With
    ``point`` the two halves are spans in the profiler's trace."""
    import jax

    held = collections.deque(maxlen=HOLD)
    push = held.append
    inputs = itertools.islice(itertools.cycle(pool), k)
    # a TraceAnnotation starts its clock when it is made, not entered
    span = jax.profiler.TraceAnnotation if point else _no_span
    t0 = time.perf_counter()
    with span(f"{ISSUE}{point}"):
        for x in inputs:
            push(call(x))
    t1 = time.perf_counter()
    with span(SYNC):
        jax.block_until_ready(list(held))
    t2 = time.perf_counter()
    return t2 - t0, t1 - t0


def warm_and_calibrate(pr: PointRun, min_window_s: float = MIN_WINDOW_S
                       ) -> int:
    """Touch every input of the pool once (the first call compiles),
    then find k.  Returns the calls issued."""
    issued = 0
    for fn in (pr.call, pr.raw):
        if fn is not None:
            window(fn, pr.pool, len(pr.pool))
            issued += len(pr.pool) if fn is pr.call else 0
    k = 1
    while True:
        dt, _ = window(pr.call, pr.pool, k)
        issued += k
        if dt >= min_window_s or k >= MAX_K:
            break
        k *= 2
    pr.k = k
    return issued


def sample_positions(length: int, rng: np.random.Generator) -> np.ndarray:
    """Positions along the last axis that a check compares: all of them
    up to ``SAMPLE``, else both edges and a seeded sample."""
    if length <= SAMPLE:
        return np.arange(length, dtype=np.int32)
    body = rng.integers(EDGE, length - EDGE, SAMPLE - 2 * EDGE)
    pos = np.concatenate([np.arange(EDGE), body,
                          np.arange(length - EDGE, length)])
    # always SAMPLE of them (a position may repeat): another count would
    # be another shape, and every seed would compile its own gather
    return np.sort(pos).astype(np.int32)


@functools.cache
def _take_program():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda a, p: jnp.take(a, p, axis=-1))


def _take_last(arr, pos):
    return _take_program()(arr, pos)


def mismatches(want: np.ndarray, got: np.ndarray, tolerance) -> int:
    """The positions at which a result is not its reference: bit for
    bit, or, under a kind's ``TOLERANCE``, outside ``np.isclose``'s
    ``atol + rtol x |want|``.  The limit is 0 either way, so a stated
    tolerance is all the room there is.  Another dtype or shape fails at
    every position."""
    if want.dtype != got.dtype or want.shape != got.shape:
        return max(want.size, 1)
    if tolerance is None:
        return int(np.count_nonzero(want != got))
    close = np.isclose(got, want, rtol=tolerance["rtol"],
                       atol=tolerance["atol"])
    return int(close.size - np.count_nonzero(close))


def check(pr: PointRun, rng: np.random.Generator) -> bool:
    """One call on a seeded input of the pool, compared with the kind's
    plain numpy reference: bit for bit, or within the kind's
    ``TOLERANCE`` where it states one (``mismatches``).  Where the kind
    computes every position of the last axis independently, a long array
    is compared at ``sample_positions`` only, so that only those cross
    to the host.  A kind whose call takes a set of arrays gives them by
    ``inputs_of``; its reference and its call then return as many.  The
    number compared and its limit are printed, pass or fail."""
    bad, compared = check_counts(pr, rng)
    how = "differ" if pr.tolerance is None else (
        f"lie outside rtol {pr.tolerance['rtol']} atol "
        f"{pr.tolerance['atol']}")
    print(f"check {pr.name}: {bad} of {compared} positions {how}; limit 0",
          flush=True)
    return bad == 0


def check_counts(pr: PointRun, rng: np.random.Generator,
                 lowered=None) -> tuple:
    """(positions that fail, positions compared) for one call on a
    seeded input of the pool.  With ``lowered`` (a numpy dtype) it is the
    **control** that is compared and not the program: the kind's
    reference put in the program's place and computed in that lower
    precision, from the same inputs (``tools/control.py``)."""
    state = pr.pool[int(rng.integers(len(pr.pool)))]
    inputs_of = getattr(pr.kind, "inputs_of", None)
    xs = list(inputs_of(state)) if inputs_of else [state]
    outs = []
    if lowered is None:
        out = pr.call(state)
        outs = list(out) if inputs_of else [out]
    if pr.kind.ELEMENTWISE_LAST_AXIS and xs[0].shape[-1] > SAMPLE:
        length = xs[0].shape[-1]
        if any(a.shape[-1] != length for a in xs + outs):
            raise ValueError(f"{pr.name}: a kind that is elementwise along "
                             "the last axis keeps its length")
        pos = sample_positions(length, rng)
        xs = [_take_last(a, pos) for a in xs]
        outs = [_take_last(a, pos) for a in outs]
    xs = [np.asarray(a) for a in xs]
    want = pr.kind.reference(pr.point, pr.n, xs if inputs_of else xs[0])
    wants = list(want) if inputs_of else [want]
    if lowered is not None:
        low = [x.astype(lowered) for x in xs]
        out = pr.kind.reference(pr.point, pr.n, low if inputs_of else low[0])
        outs = [np.asarray(o).astype(np.asarray(w).dtype) for o, w in zip(
            list(out) if inputs_of else [out], wants)]
    if len(wants) != len(outs):
        raise ValueError(f"{pr.name}: the call returned {len(outs)} arrays, "
                         f"the reference {len(wants)}")
    bad = compared = 0
    for w, g in zip(wants, outs):
        w = np.asarray(w)
        bad += mismatches(w, np.asarray(g), pr.tolerance)
        compared += w.size
    return bad, compared


def measure(points: list, seconds: float, seed: int,
            with_raw: bool = False, rounds: int | None = None) -> None:
    """Whole rounds over ``points`` in an order shuffled from ``seed``
    until ``seconds`` are up.  With ``with_raw`` each point's raw twin
    gets a window beside the framework's, the two alternating which goes
    first.  With ``rounds`` it makes exactly that many, framework only,
    as spans in the profiler's trace."""
    import jax

    traced = rounds is not None
    rng = random.Random(seed)
    done = 0
    t_end = time.perf_counter() + seconds
    while done < rounds if traced else (time.perf_counter() < t_end
                                        or done == 0):
        order = list(points)
        rng.shuffle(order)
        with (jax.profiler.TraceAnnotation(ROUND) if traced
              else contextlib.nullcontext()):
            for pr in order:
                sides = [(pr.call, pr.windows)]
                if with_raw and not traced and pr.raw is not None:
                    sides.append((pr.raw, pr.raw_windows))
                    if done % 2:
                        sides.reverse()
                for fn, into in sides:
                    into.append(window(fn, pr.pool, pr.k,
                                       pr.name if traced else None))
        done += 1
