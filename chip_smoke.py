#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the default device path still
starts on the chip.

    python chip_smoke.py            # on a machine with a TPU; one process

Drives, once, what a user gets with no ``--mca`` overrides, through the
entry points a user calls: ``ompi_tpu.init()`` -> the single-process
device world -> ``COMM_WORLD.*_array`` collectives owned by ``coll/xla``;
then the flagship train step at ``OTPU_MODEL_SCALE=64`` (the repo's
widest configuration; float32, ring attention in plain ``jnp``) on its
meshes; then the Pallas kernels a public model's step selects on a TPU,
standalone against their XLA twins.  Every result is checked against
numpy / the jnp twin.

It is a smoke, not a benchmark: it reports seconds per phase (first call,
which compiles, apart from later calls) and no rate.  Any exception ends
the run non-zero with its traceback; a whole-run watchdog turns a hang
into a failure with stacks.  It needs a TPU: on any other platform it
exits non-zero before doing any work.  It starts no child that needs the
chip (a chip belongs to one process).  The last line of stdout is
``{"ok": true, "device": {...}}``.
"""
import contextlib
import faulthandler
import functools
import json
import os
import sys
import time

import numpy as np

WATCHDOG_S = 1100           # the contract allows 1200 s, compile included
MODEL_SCALE = 64            # d 512, head dim 256
PRIMARY_BYTES = 16 << 20    # BASELINE.json's headline: f32 allreduce a rank
SPOT_BYTES = 4 << 20
FLASH_SHAPE = (4, 8, 2048, 128)         # b, h, s, head width; bf16
ROPE_SHAPE = (1, 8192, 32, 1536)        # b, s, heads, the latent's rank
# b, s, heads, head width, groups, state, chunk: the hybrid cell's mixer
SSM_SHAPE = (1, 8192, 16, 64, 1, 128, 128)
# the two Mamba cells' scans for the Pallas kernels, in the same order:
# Granite's under a packed row's documents and Nemotron's
SSM_KERNEL_SHAPES = ((1, 16384, 32, 64, 1, 128, 256), SSM_SHAPE)
# each loss is taken BEFORE its update: four losses observe three
# updates, and every one of them must have lowered the loss
TRAIN_STEPS = 4
MOSAIC_CALL = "tpu_custom_call"         # Mosaic's custom-call target


class Clock:
    """Seconds per phase, the first (compiling) call of each program
    kept apart from the calls after it."""

    def __init__(self) -> None:
        self.phase: dict = {}
        self.cold = 0.0
        self.steady = 0.0
        self.steady_calls = 0

    @contextlib.contextmanager
    def timed(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phase[name] = round(time.perf_counter() - t0, 2)

    def call(self, fn, *args, first: bool):
        """``fn(*args)`` closed by ``block_until_ready``, booked as a
        cold or a steady call."""
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        if first:
            self.cold += dt
        else:
            self.steady += dt
            self.steady_calls += 1
        return out


class CompileCounters:
    """What JAX itself reports about compilation: seconds in the
    backend compiler and persistent-cache hits / writes."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.requests = self.hits = self.writes = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1     # recorded where an entry is written

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs


def _require(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def _on_platform(arr, platform: str, what: str) -> None:
    found = sorted({d.platform for d in arr.devices()})
    _require(found == [platform],
             f"{what} lives on {found}, expected only {platform!r}")


def _has_mosaic(compiled) -> bool:
    return MOSAIC_CALL in compiled.as_text()


# -- 1. identify -----------------------------------------------------------
def describe(devs, cache_dir: str) -> None:
    import importlib.metadata as md

    import jax
    import jaxlib

    from ompi_tpu import native
    from ompi_tpu.base import hwloc

    print(f"platform {devs[0].platform}  device_kind "
          f"{devs[0].device_kind!r}  count {len(devs)}")
    print("coords " + " ".join(
        f"{t.index}:{t.coords}/{t.core_on_chip}"
        for t in hwloc.device_topology(devs)))
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {md.version('libtpu')}")
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache {cache_dir} ({entries} entries at start)")
    # a fact, not a gate: which lane the host paths are on
    print(f"native.available() {native.available()}", flush=True)


# -- 2. boot through the normal entry --------------------------------------
def boot(devs):
    import ompi_tpu
    from ompi_tpu.mca.coll.xla import XlaCollModule

    world = ompi_tpu.init()
    _require(world.rte.is_device_world,
             f"init() booted {type(world.rte).__name__}, not the "
             "single-process device world")
    _require(world.size == len(devs),
             f"world.size {world.size} != {len(devs)} devices")
    owner = world.c_coll["allreduce_array"].__self__
    _require(isinstance(owner, XlaCollModule),
             f"allreduce_array is owned by {type(owner).__name__}, "
             "not XlaCollModule")
    print(f"world.size {world.size}  allreduce_array owner "
          f"{type(owner).__name__}", flush=True)
    return world


# -- 3. collectives through COMM_WORLD -------------------------------------
def collectives(world, clock: Clock, platform: str = "tpu",
                primary_bytes: int = PRIMARY_BYTES,
                spot_bytes: int = SPOT_BYTES) -> None:
    import ompi_tpu

    n = world.size
    xla = world.c_coll["allreduce_array"].__self__
    rng = np.random.default_rng(0)

    def run(name, fn, host, want, rtol=1e-5, atol=1e-5):
        x = xla.make_world_array(host)
        _on_platform(x, platform, f"{name} input")
        for first in (True, False):
            out = clock.call(fn, x, first=first)
            _on_platform(out, platform, f"{name} result")
            np.testing.assert_allclose(np.asarray(out), want, rtol=rtol,
                                       atol=atol, err_msg=name)
        print(f"  {name} {host.nbytes // n} B/rank ok", flush=True)

    host = rng.standard_normal((n, primary_bytes // 4)).astype(np.float32)
    run("allreduce_array", world.allreduce_array, host, host.sum(0))

    spot = spot_bytes // 4
    host = rng.standard_normal((n, spot)).astype(np.float32)
    run("bcast_array", lambda x: world.bcast_array(x, root=n - 1), host,
        np.broadcast_to(host[n - 1], host.shape), rtol=0, atol=0)
    run("allgather_array", world.allgather_array, host, host, rtol=0,
        atol=0)
    blk = max(1, spot // n)
    host2 = rng.standard_normal((n, n, blk)).astype(np.float32)
    run("reduce_scatter_array", world.reduce_scatter_array, host2,
        host2.sum(0))
    run("alltoall_array", world.alltoall_array, host2,
        np.swapaxes(host2, 0, 1), rtol=0, atol=0)
    # an op with no native collective: gather + whatever fold mca/op
    # selects on these devices (op/pallas_vpu outranks op/xla on a TPU)
    from ompi_tpu.api import op as op_mod

    stack = op_mod.jax_stack_reduce(ompi_tpu.PROD, np.dtype("float32"))
    print(f"  mca/op stack fold for PROD: "
          f"{getattr(stack, 'func', stack).__module__}", flush=True)
    hostp = rng.uniform(0.5, 1.5, (n, 1 << 18)).astype(np.float32)
    run("allreduce_array[PROD]",
        lambda x: world.allreduce_array(x, ompi_tpu.PROD), hostp,
        hostp.prod(0))

    # one persistent handle (MPI_Allreduce_init analog), called twice
    x = xla.make_world_array(host)
    handle = world.allreduce_array_init(x)
    for _ in range(2):
        out = clock.call(handle, x, first=False)
        _on_platform(out, platform, "persistent allreduce result")
        np.testing.assert_allclose(np.asarray(out), host.sum(0),
                                   rtol=1e-5, atol=1e-5)
    print("  allreduce_array_init handle x2 ok", flush=True)

    # a partitioned allreduce: three buckets released last to first are
    # one group launch, and its sums are a launch a bucket's bit for bit
    # (across chips only while XLA's combiner does not merge the
    # members' all-reduces: coll/xla _group_fn)
    bk = [xla.make_world_array(
        rng.standard_normal((n, spot)).astype(np.float32))
        for _ in range(3)]
    req = world.pallreduce_init(bk)
    _require([len(members) for members, _ in req._plan] == [3],
             f"3 x {spot_bytes} B planned as {req._plan}")
    req.start()
    req.pready_list(range(2, -1, -1))
    req.wait()
    for out, b in zip(req.result, bk):
        _on_platform(out, platform, "pallreduce result")
        np.testing.assert_array_equal(
            np.asarray(out).view(np.uint32),
            np.asarray(world.allreduce_array(b)).view(np.uint32))
    req.free()
    print("  pallreduce_init 3 buckets, one launch, bits ok", flush=True)


# -- 4. the flagship trainer -----------------------------------------------
def trainer(devs, clock: Clock, scale: int = MODEL_SCALE) -> None:
    import jax

    import __graft_entry__
    from ompi_tpu.parallel.dryrun import make_step_and_args
    from ompi_tpu.parallel.mesh import MeshSpec

    specs = [None]
    if len(devs) == 4:
        # the pipeline-active mesh run_training_step adds on four chips
        specs.append(MeshSpec(dp=1, pp=2, sp=1, tp=2))
    old_scale = os.environ.get("OTPU_MODEL_SCALE")
    os.environ["OTPU_MODEL_SCALE"] = str(scale)
    try:
        for spec in specs:
            step, (params, xd), mspec = make_step_and_args(devs, spec)
            t0 = time.perf_counter()
            compiled = step.lower(params, xd).compile()
            clock.cold += time.perf_counter() - t0
            losses = []
            for _ in range(TRAIN_STEPS):
                params, loss = clock.call(compiled, params, xd,
                                          first=False)
                losses.append(float(loss))
            _require(all(np.isfinite(losses)),
                     f"non-finite loss in {losses}")
            _require(all(b < a for a, b in zip(losses, losses[1:])),
                     f"loss not falling at every step: {losses}")
            print(f"  train mesh={mspec.sizes()} float32 scale {scale} "
                  "losses " + " -> ".join(f"{v:.6f}" for v in losses),
                  flush=True)
        # the driver's own entry, jitted the way the driver jits it
        fn, args = __graft_entry__.entry()
        _, loss = clock.call(jax.jit(fn), *args, first=True)
        _require(np.isfinite(float(loss)), "entry() loss not finite")
        print(f"  __graft_entry__.entry() jitted: loss {float(loss):.6f}",
              flush=True)
    finally:
        if old_scale is None:
            os.environ.pop("OTPU_MODEL_SCALE", None)
        else:
            os.environ["OTPU_MODEL_SCALE"] = old_scale


# -- 5. the kernels a model's step selects, against their XLA twins -------
def kernels(clock: Clock, expect_interpret: bool = False,
            flash_shape=FLASH_SHAPE, dtype: str = "bfloat16",
            reduce_elems: int = 1 << 20, rope_shape=ROPE_SHAPE,
            ssm_shape=SSM_SHAPE,
            ssm_kernel_shapes=SSM_KERNEL_SHAPES) -> None:
    import jax
    import jax.numpy as jnp

    from ompi_tpu.base.jaxenv import pallas_interpret
    from ompi_tpu.ops import flash_attention as fa
    from ompi_tpu.ops import pallas_reduce as pr

    # interpret is left to resolve by itself, here and in every call
    _require(pallas_interpret() == expect_interpret,
             f"pallas interpret resolved to {pallas_interpret()}, "
             f"expected {expect_interpret}")

    def compile_checked(name, jitted, *args):
        t0 = time.perf_counter()
        compiled = jitted.lower(*args).compile()
        clock.cold += time.perf_counter() - t0
        _require(_has_mosaic(compiled) != expect_interpret,
                 f"{name}: compiled HLO {'has' if expect_interpret else 'lacks'}"
                 f" the Mosaic custom call ({MOSAIC_CALL})")
        return compiled

    b, h, sq, d = flash_shape
    dt = jnp.dtype(dtype)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    # the twin, in float32 at full matmul precision, is the reference;
    # the band is the input dtype's (the kernel rounds p to it for p@v)
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-4

    def close(label, g, w):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        _require(g.shape == w.shape and np.all(np.isfinite(g)),
                 f"{label}: shape {g.shape} / non-finite")
        err = float(np.max(np.abs(g - w)) / max(1.0, np.max(np.abs(w))))
        _require(err <= tol, f"{label}: error {err:.3e} of max|ref| "
                             f"exceeds {tol:g}")

    # causal attention's two kernels against their jnp twins, q and k as
    # wide as v (128 / 128) and half as wide again (192 / 128), a
    # key-value head a query head, and at half the width (64 / 64) with
    # every query head on one key-value head, read through the index
    # maps: the forward pass in one call; the backward's fused block
    # pair, a plain pair and the diagonal one through one compiled kernel
    from ompi_tpu.parallel import causal, layers, mamba

    block, f32 = min(sq, 1024), jnp.float32
    cut = lambda x, n: x[:, :, n * block:(n + 1) * block]
    for wide, hv, n_kv in ((d, d, h), (d * 3 // 2, d, h),
                           (d // 2, d // 2, 1)):
        name = f"attn_block_backward {wide}/{hv} {h} on {n_kv}"
        keys = jax.random.split(jax.random.PRNGKey(wide), 7)
        draw = lambda key, w, n, t=dt: jax.random.normal(
            key, (b, n, 2 * block, w), t)
        qb, kb, vb, dob = (draw(key, w, n) for key, w, n in zip(
            keys, (wide, wide, hv, hv), (h, n_kv, n_kv, h)))
        acc = tuple(draw(key, w, n, f32) for key, w, n in zip(
            keys[4:], (wide, wide, hv), (h, n_kv, n_kv)))
        up = tuple(x.astype(f32) for x in (qb, kb, vb, dob))
        o, lse = causal._causal_fwd_blocks(*up[:3], block, True)
        fwd = f"flash_causal_forward {wide}/{hv} {h} on {n_kv}"
        got = clock.call(compile_checked(fwd, jax.jit(
            lambda *a: fa.flash_causal_forward(*a, block=block)),
            qb, kb, vb), qb, kb, vb, first=False)
        for part, g, w in zip(("o", "lse"), got, (o, lse)):
            close(f"{fwd}.{part}", g, w)
        print(f"  {fwd} (block {block}) {dtype} matches its jnp twin",
              flush=True)
        delta = jnp.sum(up[3] * o, axis=-1)
        args = (qb, kb, vb, dob, lse, delta) + acc
        compiled = compile_checked(name, jax.jit(
            lambda ij, *a: fa.attn_block_backward(ij, *a, block=block)),
            jnp.zeros(2, jnp.int32), *args)
        fold = lambda x, n: causal._group_blocks(x, n_kv, block)[n]
        for i, j in ((1, 0), (1, 1)):
            got = clock.call(compiled, jnp.asarray((i, j), jnp.int32),
                             *args, first=False)
            dq, dk, dv = causal._bwd_pair(
                fold(up[0], i), cut(up[1], j), cut(up[2], j),
                fold(up[3], i), fold(lse, i), fold(delta, i),
                causal._group_bias(block, h // n_kv) if i == j else None,
                1.0 / wide ** 0.5, f32)
            for part, g, a0, w, n in zip(
                    ("dq", "dk", "dv"), got, acc,
                    (dq.reshape(b, h, block, wide), dk, dv), (i, j, j)):
                close(f"{name}.{part} pair {(i, j)}",
                      cut(g, n) - cut(a0, n), w)
        print(f"  {name} (block {block}) {dtype} matches its jnp twin",
              flush=True)

    # latent attention's q from its projection with RoPE on, the partner
    # a product of its own and no rolled copy (``layers.project_rope``; no
    # kernel: XLA's fusions), against ``rope_interleaved`` of the same
    # product in float32 at full precision, heads 192 wide, 128 unrotated
    rb, rs, rh, rank = rope_shape
    wide, theta = d * 3 // 2, 32e6
    a = jax.random.normal(kq, (rb, rs, rank), dt)
    w = jax.random.normal(kk, (rank, rh * wide), f32) / rank ** 0.5
    got = clock.call(jax.jit(lambda a, w: layers.project_rope(
        a, w, rh, d, theta, dt)), a, w, first=True)
    _require(got.dtype == f32, f"project_rope gives {got.dtype}")
    want = jax.jit(lambda a, w: layers.rope_interleaved(
        layers.matmul(a, w.astype(dt), f32, weight=False).reshape(
            rb, rs, rh, wide), theta, d, 1))(a, w)
    g, want = np.asarray(got), np.asarray(want)
    err = float(np.max(np.abs(g - want)) / np.max(np.abs(want)))
    _require(g.shape == want.shape and err <= 1e-5,
             f"project_rope {g.shape}: error {err:.3e} of max|ref| "
             "exceeds 1e-05")
    print(f"  project_rope {g.shape} {dtype} matches rope_interleaved in "
          f"float32 ({err:.2e} of max|ref|)", flush=True)

    # Mamba-2's state-space scan in chunks (``mamba.ssd_chunked``, the
    # XLA form: batched matmuls and one loop over the chunks) against
    # the recurrence one position at a time, at the hybrid cell's widths:
    # steps between 0.001 and 0.1 and decays of 1 to 16 a unit step, as
    # the mixer's leaves start
    sb, ss, sh, sp_, sg, sn, chunk = ssm_shape
    from ompi_tpu.parallel import nemotron_reference

    xs = jax.random.normal(kq, (sb, ss, sh, sp_), f32)
    step = jnp.exp(jax.random.uniform(kk, (sb, ss, sh), f32,
                                      np.log(0.001), np.log(0.1)))
    decay = -jax.random.uniform(kv, (sh,), f32, 1.0, 16.0)
    bs, cs = (jax.random.normal(k, (sb, ss, sg, sn), f32) for k in (kk, kv))
    got = clock.call(jax.jit(lambda *args: mamba.ssd_chunked(*args, chunk)),
                     xs, step, decay, bs, cs, first=True)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(nemotron_reference.recurrence)(xs, step, decay, bs, cs)
    g, want = np.asarray(got), np.asarray(want)
    err = float(np.max(np.abs(g - want)) / np.max(np.abs(want)))
    # both sides are float32 sums over thousands of positions (1.4e-5
    # apart on the v5e at 8,192); a wrong scan is wrong by its whole size
    _require(g.shape == want.shape and err <= 1e-4,
             f"ssd_chunked {g.shape}: error {err:.3e} of max|ref| exceeds "
             "1e-04")
    print(f"  ssd_chunked {g.shape} in chunks of {chunk} matches the "
          f"recurrence over {ss} positions ({err:.2e} of max|ref|)",
          flush=True)

    # the same scan on its Pallas kernels (``ops/ssd_scan``: the chunks'
    # matrices and the states in VMEM, forward and backward) against the
    # XLA form and autodiff through it, with and without a packed row's
    # documents (a dozen, one starting on a chunk's first position and one
    # on a chunk's last), at both Mamba cells' shapes: the largest error
    # over the largest entry of y and of the five gradients
    from ompi_tpu.ops import ssd_scan

    interpret = pallas_interpret()
    flat = lambda t: t.reshape(t.shape[:2] + (-1,))
    for sb, ss, sh, sp_, sg, sn, chunk in ssm_kernel_shapes:
        xs, weigh = (jax.random.normal(k, (sb, ss, sh, sp_), f32)
                     for k in (kq, kv))
        step = jnp.exp(jax.random.uniform(kk, (sb, ss, sh), f32,
                                          np.log(0.001), np.log(0.1)))
        decay = -jax.random.uniform(kv, (sh,), f32, 1.0, 16.0)
        bs, cs = (jax.random.normal(k, (sb, ss, sg, sn), f32) * sn ** -0.5
                  for k in (kk, kq))
        starts = np.zeros((sb, ss), np.int32)
        starts[:, np.linspace(1, ss - 1, 10, dtype=int)] = 1
        starts[:, [chunk, 3 * chunk - 1]] = 1
        docs = jnp.asarray(np.cumsum(starts, axis=1, dtype=np.int32))
        for doc in (None, docs):
            want = clock.call(jax.jit(lambda *args: jax.vjp(
                lambda *a: mamba.ssd_chunked(*a, chunk, doc), *args[:5])[1](
                    args[5]) + (mamba.ssd_chunked(*args[:5], chunk, doc),)),
                xs, step, decay, bs, cs, weigh, first=True)
            how = dict(chunk=chunk, p=sp_, groups=sg, interpret=interpret)
            y, kept = clock.call(functools.partial(
                ssd_scan.scan_forward, states=True, **how), flat(xs),
                flat(bs), flat(cs), step, decay, doc, first=True)
            dx, db, dc, ddt, da, _ = clock.call(functools.partial(
                ssd_scan.scan_backward, **how), flat(xs), flat(bs), flat(cs),
                step, decay, doc, None, kept, flat(weigh), first=True)
            errs = {name: float(jnp.max(jnp.abs(g.reshape(w.shape) - w))
                                / jnp.max(jnp.abs(w)))
                    for name, g, w in zip(("dx", "ddt", "da", "db", "dc", "y"),
                                          (dx, ddt, da, db, dc, y), want)}
            _require(max(errs.values()) <= 1e-4,
                     f"ssd_scan {xs.shape} in chunks of {chunk}: error "
                     f"{errs} of max|ref| exceeds 1e-04")
            print(f"  ssd_scan kernels {xs.shape} in chunks of {chunk}, "
                  f"{'12 documents' if doc is not None else 'one document'}"
                  f": y and the five gradients match the XLA form ("
                  + ", ".join(f"{k} {v:.1e}" for k, v in errs.items())
                  + " of max|ref|)", flush=True)

    a = jax.random.normal(kq, (reduce_elems,), jnp.float32)
    bb = jax.random.normal(kk, (reduce_elems,), jnp.float32)
    stack = jax.random.normal(kv, (8, reduce_elems // 8), jnp.float32)
    compile_checked("combine2", pr.combine2, "SUM", a, bb)
    compile_checked("reduce_stack", pr.reduce_stack, "MAX", stack)
    for first in (True, False):
        got = clock.call(pr.combine2, "SUM", a, bb, first=first)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(a + bb))
    for first in (True, False):
        got = clock.call(pr.reduce_stack, "MAX", stack, first=first)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.max(stack, axis=0)))
    got = clock.call(pr.reduce_stack, "SUM", stack, first=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.sum(stack, axis=0)),
                               rtol=1e-5, atol=1e-5)
    print(f"  pallas_reduce combine2 / reduce_stack ({reduce_elems} "
          "elems) match jnp", flush=True)


def main() -> int:
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    t_start = time.perf_counter()
    clock = Clock()
    with clock.timed("identify"):
        from ompi_tpu.base.jaxenv import compile_cache_dir, require_tpu

        devs = require_tpu("chip_smoke")    # or exit, before any work
        counters = CompileCounters()
        cache_dir = compile_cache_dir()     # before the first compile
        describe(devs, cache_dir)
    with clock.timed("boot"):
        world = boot(devs)
    with clock.timed("collectives"):
        collectives(world, clock)
    with clock.timed("trainer"):
        trainer(devs, clock)
    with clock.timed("kernels"):
        kernels(clock)
    with clock.timed("finalize"):
        import ompi_tpu

        ompi_tpu.finalize()
    faulthandler.cancel_dump_traceback_later()
    print("summary: "
          + " | ".join(f"{k} {v}s" for k, v in clock.phase.items())
          + f" | total {time.perf_counter() - t_start:.1f}s"
          + f" | first calls (compile) {clock.cold:.1f}s"
          + f" | {clock.steady_calls} steady calls {clock.steady:.2f}s"
          + f" | backend compile {counters.compile_s:.1f}s over "
            f"{counters.requests} cacheable compiles: "
            f"{counters.hits} cache hits, {counters.writes} written")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
